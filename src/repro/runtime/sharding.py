"""Logical-axis sharding resolver.

Every parameter/activation/cache dim carries a *logical* axis name (see
models/common.py). This module maps logical names -> mesh PartitionSpecs with:

- a priority list of candidate mesh axes per logical name,
- divisibility guards (a candidate is skipped unless the dim size is a
  multiple of the product of the candidate mesh axis sizes) — this is what
  lets e.g. smollm's 9 heads or minicpm's 122753 vocab fall back gracefully,
- one-mesh-axis-per-spec bookkeeping (an axis is never used twice),
- a tensor-parallel fallback: if a >=2D weight ends up with no "model" axis,
  its "embed" dim is tried (row/col parallel fallback),
- an FSDP pass (cfg.fsdp): the largest still-unsharded dim of large params is
  sharded over ("pod","data")/("data",) so optimizer state scales with the
  full device count (ZeRO-3 style).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Candidate mesh axes per logical axis name, in priority order. Each
# candidate is a tuple of mesh axis names (jointly assigned to the dim).
RULES: dict = {
    "batch":     [("pod", "data"), ("data",), ("pod",)],
    "island":    [("pod", "data"), ("data",), ("pod",)],
    "vocab":     [("model",)],
    "mlp":       [("model",)],
    "heads":     [("model",)],
    "kv_heads":  [("model",)],
    "expert":    [("model",)],
    "ssm_inner": [("model",)],
    "ssm_heads": [("model",)],
    "kv_seq":    [("model",)],     # decode KV caches: flash-decoding layout
    # replicated by default:
    "embed": [], "head_dim": [], "seq": [], "lora": [], "rope_dim": [],
    "ssm_state": [], "conv_k": [], "expert_in": [], "ssm_groups": [],
    "layers": [], "enc_seq": [], "stats": [],
}

# logical dims eligible for the tensor-parallel fallback
_TP_FALLBACK = ("embed",)
_FSDP_CANDIDATES = [("pod", "data"), ("data",), ("pod",)]
_FSDP_MIN_SIZE = 1 << 20    # params smaller than 1M elements stay replicated


def abstract_mesh(sizes: Sequence[int], names: Sequence[str]):
    """A device-free ``jax.sharding.AbstractMesh`` of the given axis sizes
    (the resolver's tests need no devices)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(sizes), tuple(names))


def _axes_fit(mesh: Mesh, cand: Tuple[str, ...], dim: int,
              used: set) -> bool:
    if any(a not in mesh.shape or a in used for a in cand):
        return False
    prod = math.prod(mesh.shape[a] for a in cand)
    return prod > 1 and dim % prod == 0


def logical_to_spec(axes: Sequence[Optional[str]], shape: Sequence[int],
                    mesh: Mesh, fsdp: bool = False) -> P:
    assert len(axes) == len(shape), (axes, shape)
    rules = {**RULES, **dict(active_overrides())}
    used: set = set()
    assignment: list = [None] * len(axes)
    for i, (name, dim) in enumerate(zip(axes, shape)):
        if name is None:
            continue
        for cand in rules.get(name, []):
            if _axes_fit(mesh, cand, dim, used):
                assignment[i] = cand if len(cand) > 1 else cand[0]
                used.update(cand)
                break
    # tensor-parallel fallback: big weight with no model axis -> shard embed
    # (suppressed when an override disables TP, e.g. pure-DP small models)
    if dict(active_overrides()).get("__no_tp_fallback__"):
        pass
    elif "model" in mesh.shape and "model" not in used and len(shape) >= 2:
        for i, (name, dim) in enumerate(zip(axes, shape)):
            if name in _TP_FALLBACK and assignment[i] is None \
                    and _axes_fit(mesh, ("model",), dim, used):
                assignment[i] = "model"
                used.add("model")
                break
    # FSDP pass: shard the largest remaining dim over the data axes
    if fsdp and math.prod(shape) >= _FSDP_MIN_SIZE:
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        done = False
        for i in order:
            if assignment[i] is not None or axes[i] == "layers" or done:
                continue
            for cand in _FSDP_CANDIDATES:
                if _axes_fit(mesh, cand, shape[i], used):
                    assignment[i] = cand if len(cand) > 1 else cand[0]
                    used.update(cand)
                    done = True
                    break
    return P(*assignment)


def tree_shardings(tree_sds, axes_tree, mesh: Mesh, fsdp: bool = False):
    """Map (ShapeDtypeStruct tree, logical-axes tree) -> NamedSharding tree."""
    def f(sds, axes):
        if sds is None:
            return None
        if axes is None or (isinstance(axes, tuple) and len(axes) == 0
                            and getattr(sds, "ndim", 0) > 0):
            axes = (None,) * sds.ndim
        return NamedSharding(mesh, logical_to_spec(axes, sds.shape, mesh, fsdp))
    return jax.tree.map(f, tree_sds, axes_tree,
                        is_leaf=lambda x: x is None)


# --------------------------------------------------------------------------
# Activation constraints via an ambient mesh (+ per-arch rule overrides)
# --------------------------------------------------------------------------
_ACTIVE_MESH: list = [None]
_ACTIVE_OVERRIDES: list = [()]


class use_mesh:
    """Context manager installing a mesh (and optional per-arch logical-rule
    overrides, e.g. smollm's pure-DP mapping) for activation constraints."""

    def __init__(self, mesh: Optional[Mesh], overrides=()):
        self.mesh = mesh
        self.overrides = tuple(overrides)

    def __enter__(self):
        _ACTIVE_MESH.append(self.mesh)
        _ACTIVE_OVERRIDES.append(self.overrides)
        return self.mesh

    def __exit__(self, *exc):
        _ACTIVE_MESH.pop()
        _ACTIVE_OVERRIDES.pop()


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH[-1]


def active_overrides():
    return _ACTIVE_OVERRIDES[-1]


def constrain(x, logical_axes: Sequence[Optional[str]]):
    """with_sharding_constraint by logical names; no-op without a mesh."""
    mesh = active_mesh()
    if mesh is None:
        return x
    spec = logical_to_spec(logical_axes, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# Mesh-sharded pairwise dominance sweep (the archive-scale selection engine)
# --------------------------------------------------------------------------
_SWEEP_AXES = ("pod", "data")


def _sweep_axes(mesh) -> Tuple[str, ...]:
    if not isinstance(mesh, Mesh):
        return ()
    return tuple(a for a in _SWEEP_AXES
                 if a in mesh.shape and mesh.shape[a] > 1)


def sharded_dominance_pass(objectives, groups=None):
    """Row-block-parallel fused dominance sweep over the active mesh.

    Each device takes a contiguous block of rows against the full column set
    (objectives are replicated; the O(N^2) compare work splits evenly), then:
    - counts: every shard scatters its row-block counts into a zero-padded
      full-length vector and a psum over the sweep axes yields the counts
      replicated on all devices (the front-peeling loop needs them whole),
    - bitmap: stays row-sharded across the mesh — N^2/8 bytes of dominance
      bits never gather onto one device; the peeling popcounts run shard-wise
      under the same sharding.

    Drop-in ``pass_fn`` for evolution.nsga2.nondominated_ranks; falls back to
    the single-device fused kernel only when no real mesh is active or the
    sweep axes are trivial. Arbitrary N shards: each shard's row block must
    be 32-aligned for the bitmap words, so N pads up to the next
    ``n_shards*32`` multiple with +BIG sentinel rows (group -1) — sentinels
    never strictly dominate and never set a bitmap bit on a real row, the
    same trick the fused kernel plays for indivisible N — and the outputs
    slice back to N.
    """
    from repro.kernels import ops as kops   # deferred: keep import DAG thin
    from repro.kernels.dominance import BIG, _ceil_to

    mesh = active_mesh()
    n = objectives.shape[0]
    axes = _sweep_axes(mesh)
    n_shards = math.prod(mesh.shape[a] for a in axes) if axes else 1
    if n_shards <= 1 or objectives.ndim != 2:
        return kops.dominance_pass(objectives, groups=groups)

    g = (groups if groups is not None
         else jnp.zeros((n,), jnp.int32)).astype(jnp.int32)
    n_p = _ceil_to(n, n_shards * 32)
    if n_p != n:
        pad = n_p - n
        objectives = jnp.concatenate(
            [objectives,
             jnp.full((pad, objectives.shape[1]), BIG, objectives.dtype)])
        g = jnp.concatenate([g, jnp.full((pad,), -1, jnp.int32)])

    def sweep(rows, cols, g_rows, g_cols):
        cnt, bm = kops.dominance_pass(rows, cols, groups=g_rows[:, 0],
                                      groups_cols=g_cols[:, 0])
        shard = jnp.int32(0)
        for a in axes:
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        full = jnp.zeros((n_p,), jnp.int32)
        full = jax.lax.dynamic_update_slice(full, cnt,
                                            (shard * rows.shape[0],))
        return jax.lax.psum(full, axes), bm

    fn = jax.shard_map(
        sweep, mesh=mesh,
        in_specs=(P(axes, None), P(None, None), P(axes, None), P(None, None)),
        out_specs=(P(None), P(axes, None)),
        check_vma=False,
    )
    g2 = g[:, None]
    cnt, bm = fn(objectives, objectives, g2, g2)
    if n_p != n:
        # sentinel columns land in the sliced-off words (or as always-zero
        # bits of the last kept word); sentinel rows are dropped outright
        cnt, bm = cnt[:n], bm[:n, :_ceil_to(n, 32) // 32]
    return cnt, bm
