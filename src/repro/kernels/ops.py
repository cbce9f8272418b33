"""jit'd wrappers around the Pallas kernels: the routes each kernel takes.

On TPU (``on_tpu()``) every wrapper runs its compiled Pallas kernel; that is
the only route there, and interpret mode and the jnp reference are never
reached. On the CPU, where the tests run, a wrapper runs the kernel in
Pallas interpret mode while its grid is small and the jitted jnp reference
from kernels/ref.py beyond that (interpret cost grows with the grid);
dry-run lowering always takes the reference. The references are the
oracles the tests and chip_smoke.py hold the kernels to (``ref.TOL``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.diffusion import diffuse_evaporate as _diffuse_pallas
from repro.kernels.dominance import dominance_pass as _dom_pass_pallas
from repro.kernels.dominance import dominated_counts as _dom_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.gp import gp_matrix as _gp_matrix_pallas
from repro.kernels.gp import gp_sqdist as _gp_sqdist_pallas

# Interpret-mode execution threshold: beyond this many grid steps the python
# interpreter cost explodes, so non-TPU backends fall back to the reference.
_INTERPRET_GRID_LIMIT = 4096


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------------
# Flash attention
# --------------------------------------------------------------------------
def flash_available(q, k, *, block_q=512, block_k=512) -> bool:
    """Can the Pallas kernel handle these shapes on this backend?"""
    b, s, h, d = q.shape  # model layout (B,S,H,D)
    if d % 8 != 0 or s < 8:
        return False
    if h % k.shape[2] != 0:
        return False
    if not on_tpu():
        bq, bk = min(block_q, s), min(block_k, s)
        if s % bq or s % bk:
            return False
        return b * h * (s // bq) * (s // bk) <= _INTERPRET_GRID_LIMIT \
            and not _in_dryrun()
    return s % min(block_q, s) == 0 and s % min(block_k, s) == 0


_DRYRUN = [False]


def set_dryrun(flag: bool):
    """Dry-run lowering must not inline interpret-mode kernels (HLO blowup)."""
    _DRYRUN[0] = flag


def _in_dryrun() -> bool:
    return _DRYRUN[0]


def flash_attention_gqa(q, k, v, *, causal=True, block_q=512, block_k=512):
    """Model-layout wrapper: q (B,S,H,D), k/v (B,S,KH,D) -> (B,S,H,D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    s = qt.shape[2]
    out = _flash_pallas(qt, kt, vt, causal=causal,
                        block_q=min(block_q, s), block_k=min(block_k, s),
                        interpret=not on_tpu())
    return out.transpose(0, 2, 1, 3)


def flash_attention_gqa_diff(q, k, v, *, causal=True, block_q=512,
                             block_k=512):
    """Differentiable flash attention (custom_vjp with the Pallas backward
    kernels) in model layout — usable inside training loss functions."""
    from repro.kernels.flash_attention_bwd import flash_attention_diff
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    s = qt.shape[2]
    out = flash_attention_diff(qt, kt, vt, causal, min(block_q, s),
                               min(block_k, s), not on_tpu())
    return out.transpose(0, 2, 1, 3)


def flash_attention_or_ref(q, k, v, *, causal=True):
    """(B,H,S,D) layout; kernel when available, else the oracle."""
    if on_tpu() or flash_available(q.transpose(0, 2, 1, 3),
                                   k.transpose(0, 2, 1, 3)):
        return _flash_pallas(q, k, v, causal=causal, interpret=not on_tpu())
    return ref.flash_attention_ref(q, k, v, causal=causal)


# --------------------------------------------------------------------------
# Ants diffusion
# --------------------------------------------------------------------------
def diffuse_evaporate(chem, rate, evap):
    n, w, _ = chem.shape
    if on_tpu():
        return _diffuse_pallas(chem, rate, evap, interpret=False)
    if n <= _INTERPRET_GRID_LIMIT // 8 and not _in_dryrun():
        return _diffuse_pallas(chem, rate, evap, interpret=True)
    return ref.diffuse_evaporate_ref(chem, rate, evap)


# --------------------------------------------------------------------------
# NSGA-II dominance
# --------------------------------------------------------------------------
# Pairwise-pass accounting: every full O(Ni*Nj) dominance sweep bumps this
# counter when its wrapper is entered (trace/call level). The fused selection
# engine must cost exactly ONE pass per nondominated_ranks call; the peeling
# baseline costs one per front — tests assert both through this counter.
_PAIRWISE_PASSES = [0]

# Interpret-mode dominance threshold, in grid steps: beyond this the python
# interpreter loop costs more than the one-shot jnp reference on CPU (the
# reference materializes the (Ni, Nj, M) compare but runs fully vectorized).
_DOMINANCE_INTERPRET_STEPS = 64


def reset_pairwise_pass_count() -> None:
    _PAIRWISE_PASSES[0] = 0


def pairwise_pass_count() -> int:
    return _PAIRWISE_PASSES[0]


def dominated_counts(objectives):
    _PAIRWISE_PASSES[0] += 1
    n = objectives.shape[0]
    if on_tpu():
        return _dom_pallas(objectives, interpret=False)
    if (-(-n // 512)) ** 2 <= _DOMINANCE_INTERPRET_STEPS and n >= 8 \
            and not _in_dryrun():
        return _dom_pallas(objectives, interpret=True)
    return ref.dominated_counts_ref(objectives)


# --------------------------------------------------------------------------
# GP covariance assembly (surrogate-assisted exploration)
# --------------------------------------------------------------------------
# Same routing as dominance: compiled kernel on TPU; on the CPU the kernel
# in interpret mode for single-tile grids, the jitted reference otherwise.
# All routes assemble distances through ref.gp_sqdist_ref / ref.gp_kernel_fn,
# but they are not bit-identical: a compiler may contract a*b+c into an FMA
# on one side only or reassociate a reduction (and on the chip the VPU
# rounds as it does), so the contract is agreement within ref.TOL
# ["gp_sqdist"] / ["gp_matrix"] — f32 rounding, which a bf16 computation
# fails. The reference route is ALWAYS jitted: eager op-by-op execution
# rounds differently again and is no engine path. Single-tile grids only:
# embedded in a jitted caller, a one-step interpret kernel costs the same
# as the inlined reference, but the interpreter's grid sequencing loses to
# the one-shot jnp assembly from ~4 steps up (and an EAGER interpret call
# pays ~200 ms of per-call trace/lower overhead regardless — eager callers
# always want the jitted reference route).
_GP_INTERPRET_STEPS = 1

_gp_sqdist_ref_jit = jax.jit(ref.gp_sqdist_ref)

# kind/lengthscale/variance are static so both sides see literal constants
# (a traced lengthscale could fold differently than the kernel's baked one);
# distinct hyper-parameter values are drawn from small fixed grids, so the
# compile-cache footprint stays bounded.
_gp_matrix_ref_jit = jax.jit(
    lambda x1, x2, kind, lengthscale, variance: ref.gp_matrix_ref(
        x1, x2, kind=kind, lengthscale=lengthscale, variance=variance),
    static_argnums=(2, 3, 4))


def _gp_use_interpret(n1: int, n2: int, block: int = 256) -> bool:
    steps = (-(-n1 // block)) * (-(-n2 // block))
    return steps <= _GP_INTERPRET_STEPS and not _in_dryrun()


def gp_sqdist(x1, x2):
    """(N1, D) x (N2, D) -> (N1, N2) f32 squared distances (fused pass)."""
    if on_tpu():
        return _gp_sqdist_pallas(x1, x2, interpret=False)
    if _gp_use_interpret(x1.shape[0], x2.shape[0]):
        return _gp_sqdist_pallas(x1, x2, interpret=True)
    return _gp_sqdist_ref_jit(x1, x2)


def gp_matrix(x1, x2, *, kind="matern52", lengthscale=0.2, variance=1.0):
    """Fused covariance assembly for fixed hyper-parameters."""
    if on_tpu():
        return _gp_matrix_pallas(x1, x2, kind=kind, lengthscale=lengthscale,
                                 variance=variance, interpret=False)
    if _gp_use_interpret(x1.shape[0], x2.shape[0]):
        return _gp_matrix_pallas(x1, x2, kind=kind, lengthscale=lengthscale,
                                 variance=variance, interpret=True)
    return _gp_matrix_ref_jit(x1, x2, kind, float(lengthscale),
                              float(variance))


# --------------------------------------------------------------------------
# Blocked Cholesky / triangular solve (archive-scale GP factorization)
# --------------------------------------------------------------------------
# Routing discipline as above: TPU kernel, CPU interpret for small grids,
# jitted blocked oracle otherwise — all through the shared tile helpers in
# kernels/ref.py with the same (block, block) dot shapes, so on the CPU the
# kernel and the oracle are bitwise identical per (shape, block); on the
# chip the MXU accumulates its tile dots in its own order and the two agree
# within ref.TOL["chol"] / ["tri_solve"]. The factor IS block-size-
# dependent at the last bit (see the contract comment in ref.py), so these
# wrappers take block= explicitly and default it to one pinned value.
# The oracle route is the ENGINE route on CPU (gemm-bound left-looking
# schedule, ~2-4x over the vmapped LAPACK grid at n=4096 — see
# benchmarks gp_chol_4096); interpret mode exists to execute the actual
# kernel program on small shapes so tests pin kernel == oracle.
# The blocked grid must NOT be vmapped on CPU (measured pathological);
# sweep lengthscale grids with a python loop under one jit instead.
_CHOL_INTERPRET_STEPS = 64

_CHOL_BLOCK = 256        # pinned default tile edge (64 * 2**j required)
_TRSM_RHS_BLOCK = 256


def _chol_block_ok(block: int) -> bool:
    q, r = divmod(block, ref.CHOL_BASE)
    return r == 0 and q >= 1 and (q & (q - 1)) == 0


def _ceil_to(n: int, b: int) -> int:
    return -(-n // b) * b


_chol_blocked_ref_jit = jax.jit(
    lambda a, block: ref.chol_blocked_ref(a, block=block),
    static_argnums=(1,))

# n stays a TRACED argument: the true count grows every tell round while
# the padded shape only changes at block boundaries — static n would force
# a recompile of the whole blocked program per round. gp_tile_ref uses n
# only in integer comparisons, so traced vs baked n is float-op identical.
_gp_chol_ref_jit = jax.jit(
    lambda x, n, kind, lengthscale, nugget, block: ref.gp_chol_blocked_ref(
        x, n, kind=kind, lengthscale=lengthscale, nugget=nugget,
        block=block),
    static_argnums=(2, 3, 4, 5))

_tri_solve_ref_jit = jax.jit(
    lambda l, b, trans, block, rhs_block: ref.tri_solve_blocked_ref(
        l, b, trans=trans, block=block, rhs_block=rhs_block),
    static_argnums=(2, 3, 4))


def _chol_steps(nb: int) -> int:
    # diag + panel + trailing grid steps across all k of the blocked sweep
    return sum(1 + t + t * t for t in (nb - k - 1 for k in range(nb)))


def _pad_identity(a, n_p):
    n = a.shape[0]
    ap = jnp.zeros((n_p, n_p), jnp.float32).at[:n, :n].set(
        a.astype(jnp.float32))
    if n_p > n:
        pad_diag = jnp.concatenate([jnp.zeros(n, jnp.float32),
                                    jnp.ones(n_p - n, jnp.float32)])
        ap = ap + jnp.diag(pad_diag)
    return ap


def chol_factor(a, *, block=_CHOL_BLOCK):
    """Lower Cholesky factor of a (n, n) SPD matrix via the blocked
    engine; pads to a block multiple with identity (factors as
    blkdiag(L, I)) and slices back. Bit-reproducible per (n, block)."""
    from repro.kernels.cholesky import chol_blocked
    assert _chol_block_ok(block), f"block must be 64*2^j, got {block}"
    n = a.shape[0]
    n_p = _ceil_to(n, block)
    ap = _pad_identity(a, n_p)
    if on_tpu():
        return chol_blocked(ap, block=block, interpret=False)[:n, :n]
    if _chol_steps(n_p // block) <= _CHOL_INTERPRET_STEPS \
            and not _in_dryrun():
        return chol_blocked(ap, block=block, interpret=True)[:n, :n]
    return _chol_blocked_ref_jit(ap, block)[:n, :n]


def gp_chol(x, *, kind="matern52", lengthscale=0.2, nugget=1e-4,
            block=_CHOL_BLOCK):
    """Fused covariance assembly + blocked Cholesky: x (n, d) unit-cube
    points -> lower factor of [K(x, x) + nugget I]. Zero-pads x to a block
    multiple (gp_tile_ref masks the pad to identity rows) and slices back;
    K never exists as an unfactored (n, n) intermediate on the kernel
    path. Callers sweeping a lengthscale grid loop this SERIALLY under one
    jit (vmapping the blocked program is pathological on CPU)."""
    from repro.kernels.cholesky import gp_chol_blocked
    assert _chol_block_ok(block), f"block must be 64*2^j, got {block}"
    n = x.shape[0]
    n_p = _ceil_to(n, block)
    xp = jnp.zeros((n_p, x.shape[1]), jnp.float32).at[:n].set(
        x.astype(jnp.float32))
    if on_tpu():
        return gp_chol_blocked(xp, n, kind=kind, lengthscale=lengthscale,
                               nugget=nugget, block=block,
                               interpret=False)[:n, :n]
    if _chol_steps(n_p // block) <= _CHOL_INTERPRET_STEPS \
            and not _in_dryrun():
        return gp_chol_blocked(xp, n, kind=kind, lengthscale=lengthscale,
                               nugget=nugget, block=block,
                               interpret=True)[:n, :n]
    return _gp_chol_ref_jit(xp, n, kind, float(lengthscale), float(nugget),
                            block)[:n, :n]


def tri_solve(l, b, *, trans=False, block=_CHOL_BLOCK,
              rhs_block=_TRSM_RHS_BLOCK):
    """Blocked triangular solve against a lower factor: L X = B
    (trans=False) or L^T X = B (trans=True); b (n, m) or (n,). Pads L
    with identity and B with zeros to tile multiples, slices back."""
    from repro.kernels.cholesky import tri_solve_blocked
    assert _chol_block_ok(block), f"block must be 64*2^j, got {block}"
    n = l.shape[0]
    vec = b.ndim == 1
    bm = b[:, None] if vec else b
    m = bm.shape[1]
    n_p = _ceil_to(n, block)
    m_p = _ceil_to(m, rhs_block)
    lp = _pad_identity(l, n_p)
    bp = jnp.zeros((n_p, m_p), jnp.float32).at[:n, :m].set(
        bm.astype(jnp.float32))
    steps = (n_p // block) + (m_p // rhs_block) * (n_p // block)
    if on_tpu():
        xs = tri_solve_blocked(lp, bp, trans=trans, block=block,
                               rhs_block=rhs_block, interpret=False)
    elif steps <= _CHOL_INTERPRET_STEPS and not _in_dryrun():
        xs = tri_solve_blocked(lp, bp, trans=trans, block=block,
                               rhs_block=rhs_block, interpret=True)
    else:
        xs = _tri_solve_ref_jit(lp, bp, trans, block, rhs_block)
    xs = xs[:n, :m]
    return xs[:, 0] if vec else xs


def dominance_pass(rows, cols=None, groups=None, groups_cols=None):
    """Fused single-pass sweep -> (counts (Ni,) i32, bitmap (Ni, W) u32).
    Kernel on TPU, interpret mode for small CPU grids, jnp reference
    otherwise — all three are bit-exact (integer outputs). The CPU gate
    counts work in 256x256 pair tiles."""
    _PAIRWISE_PASSES[0] += 1
    ni = rows.shape[0]
    nj = cols.shape[0] if cols is not None else ni
    if on_tpu():
        return _dom_pass_pallas(rows, cols, groups, groups_cols,
                                interpret=False)
    steps = (-(-ni // 256)) * (-(-nj // 256))
    if steps <= _DOMINANCE_INTERPRET_STEPS and not _in_dryrun():
        return _dom_pass_pallas(rows, cols, groups, groups_cols,
                                interpret=True)
    return ref.dominance_pass_ref(rows, cols, groups, groups_cols)
