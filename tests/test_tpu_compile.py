"""Compile-only checks of the main-path Pallas kernels for one TPU v5e chip.

The TPU compiler ships with jaxlib and compiles for a chip that is described,
not attached: these tests lower each kernel at the widths the system runs and
let the chip's compiler accept or refuse it. They catch what interpret mode
cannot (block shapes that break the (8, 128) tiling, lane slices the compiler
cannot lower, kernels over the VMEM budget). Nothing runs, so they say
nothing about results or speed; ``chip_smoke.py`` runs the same kernels on a
chip against their references.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file. All compile tests live in this one file so that one worker holds
the library.
"""
import base64
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.kernels.cholesky import gp_chol_blocked, tri_solve_blocked
from repro.kernels.diffusion import diffuse_evaporate
from repro.kernels.dominance import dominance_pass, dominated_counts
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gp import gp_matrix


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compile is written to a persistent cache but can
    # never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices), ("data",))


def _compile(fn, one_chip, *shapes):
    """Lower ``fn`` at ``(shape, dtype)`` pairs for the described chip and
    assert the compiled program holds a Pallas kernel."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


F32, I32, BF16 = jnp.float32, jnp.int32, jnp.bfloat16


def test_diffuse_evaporate_compiles_at_ants_world(one_chip):
    # 4096 lanes of the paper's 72x72 world (configs/ants_netlogo.CONFIG)
    _compile(diffuse_evaporate, one_chip,
             ((4096, 72, 72), F32), ((4096,), F32), ((4096,), F32))


@pytest.mark.parametrize("lanes", [5, 320])
def test_ants_tick_compiles_without_scatter_at_config(one_chip, lanes,
                                                      monkeypatch):
    """The whole ants evaluation at CONFIG (one individual's 5 replicate
    lanes, and 64 individuals' 320): the tick's deposit counts compile to
    contractions under ``ants.deposit``, with no scatter left in the loop."""
    import re

    from repro.ants import simulate_batch
    from repro.configs.ants_netlogo import CONFIG
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # the chip's route
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), lanes))
    text = _compile(
        lambda k, d, e: simulate_batch.__wrapped__(CONFIG, k, d, e), one_chip,
        (keys.shape, keys.dtype), ((lanes,), F32), ((lanes,), F32))
    assert not re.search(r"\bscatter\(", text)
    contractions = re.findall(r'op_name="([^"]*pi,pj->ij[^"]*)"', text)
    assert contractions
    assert all("ants.deposit" in n for n in contractions)


@pytest.mark.parametrize("n", [256, 8192])
def test_dominance_pass_compiles_with_groups(one_chip, n):
    # 256: one island archive in a single block; 8192: column blocks
    _compile(lambda f, g: dominance_pass(f, groups=g), one_chip,
             ((n, 3), F32), ((n,), I32))


def test_dominated_counts_compiles(one_chip):
    _compile(dominated_counts, one_chip, ((8192, 3), F32))


def test_gp_matrix_compiles(one_chip):
    _compile(gp_matrix, one_chip, ((2048, 2), F32), ((2048, 2), F32))


def test_gp_chol_compiles_at_block_256(one_chip):
    _compile(lambda x: gp_chol_blocked(x, 1000, block=256), one_chip,
             ((1024, 2), F32))


def test_tri_solve_compiles_at_block_256(one_chip):
    _compile(lambda l, b: tri_solve_blocked(l, b, block=256), one_chip,
             ((1024, 1024), F32), ((1024, 256), F32))


def test_flash_attention_forward_compiles_at_smollm_widths(one_chip):
    # smollm-135m: 9 query heads over 3 kv heads, head_dim 64, 2k context
    _compile(flash_attention, one_chip, ((1, 9, 2048, 64), BF16),
             ((1, 3, 2048, 64), BF16), ((1, 3, 2048, 64), BF16))


def test_island_merge_compiles_on_a_four_chip_mesh(four_chips, monkeypatch):
    """The island stages on a ("data",) mesh of four chips: the TPU
    compiler cannot partition a Pallas kernel itself, so every kernel of
    the program must sit inside a shard_map (island-local top-k ranking,
    the row-sharded archive sweep)."""
    from repro.evolution import NSGA2Config, init_island_state
    from repro.evolution.island import make_merge
    from repro.kernels import ops
    from repro.runtime import sharding as shd

    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # the chip's route
    cfg = NSGA2Config(mu=16, genome_dim=2, bounds=((0., 99.),) * 2,
                      n_objectives=3)
    st = jax.eval_shape(lambda k: init_island_state(
        cfg, k, n_islands=8, archive_size=256), jax.random.key(0))
    rep = NamedSharding(four_chips, PartitionSpec())
    isl = NamedSharding(four_chips, PartitionSpec("data"))

    def place(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding if x.ndim else rep), tree)

    with shd.use_mesh(four_chips):
        text = jax.jit(make_merge(cfg, merge_top_k=8)).lower(
            place(st.archive, rep), place(st.islands, isl)).compile(
            ).as_text()
    assert "tpu_custom_call" in text


def test_island_superstep_compiles_with_its_record_at_config(one_chip,
                                                            monkeypatch):
    """The island cell's superstep at CONFIG (8 islands x 16 children x 5
    replicates, one GA step an epoch), with the record it returns beside
    the state: every child lane's final ants state. The islands' initial
    evaluation sits behind a ``conditional``: its diffusion kernel is in
    a branch computation, never in the straight-line program, so an
    epoch whose islands are all evaluated does not run it."""
    from repro.ants import simulate_batch_state
    from repro.configs.ants_netlogo import BOUNDS, CONFIG
    from repro.evolution import NSGA2Config, init_island_state, \
        make_superstep
    from repro.explore import replicated_batch_state
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # the chip's route
    cfg = NSGA2Config(mu=16, genome_dim=2, bounds=BOUNDS, n_objectives=3)
    st = jax.eval_shape(lambda k: init_island_state(
        cfg, k, n_islands=8, archive_size=256), jax.random.key(0))
    evaluate = replicated_batch_state(
        lambda k, g: simulate_batch_state(CONFIG, k, g[:, 0], g[:, 1]), 5)
    superstep = make_superstep(cfg, evaluate, lam=16, steps_per_epoch=1,
                               merge_top_k=8)
    _, record = jax.eval_shape(lambda s: superstep(s, 1), st)
    assert record.step.lanes.chem.shape == (8, 80, 72, 72)
    args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), st)
    text = jax.jit(superstep, static_argnums=1, donate_argnums=0).lower(
        args, 1).compile().as_text()
    assert "tpu_custom_call" in text
    straight, branched, kernels = _computation_reach(text)
    init_eval = {c for c, names in kernels.items()
                 if any("islands.init_eval" in n and "diffuse_evaporate" in n
                        for n in names)}
    assert branched, "no conditional in the compiled superstep"
    assert init_eval and init_eval <= branched
    assert not init_eval & straight


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.-]+) ")
_CALLED = re.compile(r"\b(calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%([\w.-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_BRANCH_KEYS = ("true_computation", "false_computation")
_KERNEL_OP = re.compile(r'custom_call_target="tpu_custom_call".*?'
                        r'op_name="([^"]*)"')


def _computation_reach(text: str):
    """(computations the entry reaches without entering a conditional's
    branch, computations reached from some branch, computation -> op_names
    of its Pallas kernels) of a compiled module's text."""
    edges, branch_edges, kernels, entry = {}, {}, {}, None
    for block in text.split("\n\n"):
        head = _COMPUTATION.match(block)
        if not head:
            continue
        name = head.group(1)
        entry = name if block.startswith("ENTRY") else entry
        called = _CALLED.findall(block)
        edges[name] = {c for k, c in called if k not in _BRANCH_KEYS}
        branch_edges[name] = {c for k, c in called if k in _BRANCH_KEYS} | set(
            re.findall(r"%([\w.-]+)", " ".join(_BRANCHES.findall(block))))
        kernels[name] = _KERNEL_OP.findall(block)

    def reach(roots, follow_branches):
        seen, todo = set(), list(roots)
        while todo:
            c = todo.pop()
            if c in seen:
                continue
            seen.add(c)
            todo += edges.get(c, ())
            if follow_branches:
                todo += branch_edges.get(c, ())
        return seen

    straight = reach([entry], False)
    branched = reach(set().union(*branch_edges.values()), True)
    return straight, branched, kernels


# The streaming cells' chunk program, compiled for a described v5e, as
# ``operations_digest`` reduces it, on the tree before
# ``simulate_batch_state`` existed (commit d596e63): the program the cells
# time must not change with the entry that returns the final state.
CHUNK_PROGRAM_DIGESTS = {
    64: "3785a104359c1bf5302a842038b503ea3f3d734338cd92112428e816d92fea2b",
    1: "3defac3f6ee77ff66361b23cb970d2b006d9429b2efd87a21e631cb9bba9216c",
}
_SOURCE_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames")
_METADATA = re.compile(r",? metadata=\{[^}]*\}")
_KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]*)"')


def _kernel_without_locations(match):
    """A Pallas kernel's serialized body as the sha256 of its MLIR printed
    without source locations (they name the callers' files and
    functions)."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    module = ir.Module.parse(base64.b64decode(match.group(1)), context=ctx)
    asm = module.operation.get_asm(enable_debug_info=False)
    return '"body":"sha256:%s"' % hashlib.sha256(asm.encode()).hexdigest()


def operations_digest(text: str) -> str:
    """sha256 of a compiled module's instructions with the source tables,
    every op's metadata and the kernels' source locations left out."""
    blocks = [b for b in text.split("\n\n")
              if not b.startswith(_SOURCE_TABLES)]
    ops = _KERNEL_BODY.sub(_kernel_without_locations,
                           _METADATA.sub("", "\n\n".join(blocks)))
    return hashlib.sha256(ops.encode()).hexdigest()


@pytest.mark.parametrize("individuals", [64, 1])
def test_the_streaming_cells_chunk_program_is_unchanged(one_chip,
                                                        individuals,
                                                        monkeypatch):
    """``egi_init.chunk64`` and ``egi_init.single``'s chunk program, built
    as their entry builds it, compiles to the same operations as before."""
    import json

    from repro.kernels import ops

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench")
    monkeypatch.syspath_prepend(bench)
    from entries import streaming_init

    monkeypatch.setattr(ops, "on_tpu", lambda: True)   # the chip's route
    with open(os.path.join(bench, "configs", "ants_egi_init.json")) as f:
        config = json.load(f)
    keys = jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), individuals))
    text = jax.jit(streaming_init.make_eval(config)).lower(
        jax.ShapeDtypeStruct(keys.shape, keys.dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((individuals, 2), F32, sharding=one_chip)
    ).compile().as_text()
    assert operations_digest(text) == CHUNK_PROGRAM_DIGESTS[individuals]
