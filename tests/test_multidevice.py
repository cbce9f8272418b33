"""Device residency and multi-device bit-exactness of the island engine.

Three layers of guarantee for the superstep scan (evolution/island.py) and
the padded mesh-sharded dominance sweep (runtime/sharding.py):

- transfer-guard regression: a full warmed epoch/superstep executes under
  ``jax.transfer_guard("disallow")`` — zero host transfers on the hot path,
- subprocess bit-exactness: the scanned epoch produces byte-identical state
  at 1 vs 4 (and 8, slow-marked) forced host devices (the device count is
  fixed at jax import, hence one subprocess per count),
- padding, not fallback: ``sharded_dominance_pass`` at prime/odd N on a
  real multi-device mesh matches the single-device oracle exactly.

The CI ``multidevice`` job re-runs this module with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so the in-process
tests here also exercise a real 4-device mesh, not just subprocesses.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.evolution import (NSGA2Config, host_snapshot, init_island_state,
                             make_epoch, make_superstep, place_island_state,
                             run_islands)
from repro.launch.mesh import compat_make_mesh, init_distributed
from repro.runtime import sharding as shd

_REPO = os.path.join(os.path.dirname(__file__), "..")


def _run_forced(script: str, devices: int) -> str:
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       env=env, capture_output=True, text=True, timeout=300,
                       cwd=_REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def _zdt(keys, genomes):
    x0 = genomes[:, 0]
    g = 1 + 9 * genomes[:, 1:].mean(axis=1)
    f2 = g * (1 - jnp.sqrt(jnp.clip(x0 / g, 0, 1)))
    return jnp.stack([x0, f2], axis=1)


# ---------------------------------------------------------------------------
# zero host transfers on the hot path
# ---------------------------------------------------------------------------
def test_epoch_runs_under_transfer_guard_disallow():
    """One full epoch (evolve -> padded sharded merge -> reseed), warmed,
    with device-committed state: `transfer_guard("disallow")` turns ANY
    implicit host transfer into an error. Runs on whatever mesh the ambient
    device count gives (1 locally, 4 in the CI multidevice job)."""
    cfg = NSGA2Config(mu=16, genome_dim=4, bounds=((0., 1.),) * 4,
                      n_objectives=2)
    n = len(jax.devices())
    mesh = compat_make_mesh((n,), ("data",)) if n > 1 else None
    with shd.use_mesh(mesh):
        state = init_island_state(cfg, jax.random.key(0), n_islands=8,
                                  archive_size=96)
        epoch = jax.jit(make_epoch(cfg, _zdt, lam=8, steps_per_epoch=2))
        state = epoch(state)                      # warm (compile transfers)
        jax.block_until_ready(state.archive.objectives)
        with jax.transfer_guard("disallow"):
            state = epoch(state)
            jax.block_until_ready(state.archive.objectives)


def test_superstep_runs_donated_under_transfer_guard():
    """The production shape of the hot loop: K epochs scanned into one
    jitted call with the state donated, under transfer_guard."""
    cfg = NSGA2Config(mu=8, genome_dim=3, bounds=((0., 1.),) * 3,
                      n_objectives=2)
    n = len(jax.devices())
    mesh = compat_make_mesh((n,), ("data",)) if n > 1 else None
    with shd.use_mesh(mesh):
        state = init_island_state(cfg, jax.random.key(1), n_islands=4,
                                  archive_size=64)
        sstep = jax.jit(make_superstep(cfg, _zdt, lam=8, steps_per_epoch=1),
                        static_argnums=1, donate_argnums=0)
        state, _ = sstep(state, 3)
        jax.block_until_ready(state.archive.objectives)
        with jax.transfer_guard("disallow"):
            state, _ = sstep(state, 3)
            jax.block_until_ready(state.archive.objectives)
    assert int(state.epoch) == 6


# ---------------------------------------------------------------------------
# superstep semantics on the host side
# ---------------------------------------------------------------------------
def test_superstep_equals_epoch_loop_bit_exact():
    cfg = NSGA2Config(mu=8, genome_dim=4, bounds=((0., 1.),) * 4,
                      n_objectives=2)
    state = init_island_state(cfg, jax.random.key(2), n_islands=3,
                              archive_size=32)
    epoch = jax.jit(make_epoch(cfg, _zdt, lam=8, steps_per_epoch=2))
    ref = state
    for _ in range(4):
        ref = epoch(ref)
    got, _ = jax.jit(make_superstep(cfg, _zdt, lam=8, steps_per_epoch=2),
                     static_argnums=1)(state, 4)
    np.testing.assert_array_equal(np.asarray(got.archive.objectives),
                                  np.asarray(ref.archive.objectives))
    np.testing.assert_array_equal(np.asarray(got.islands.genomes),
                                  np.asarray(ref.islands.genomes))
    assert int(got.epoch) == 4
    assert int(got.total_evaluations) == int(ref.total_evaluations)


def test_run_islands_superstep_grain_invariant():
    """The final state must not depend on how epochs are grouped into
    supersteps (grain 1 = per-epoch checkpoints, grain 0 = one program)."""
    cfg = NSGA2Config(mu=8, genome_dim=4, bounds=((0., 1.),) * 4,
                      n_objectives=2)
    kw = dict(n_islands=3, lam=8, steps_per_epoch=2, epochs=5,
              archive_size=32)
    fused = run_islands(cfg, _zdt, jax.random.key(3), **kw)
    snaps = []
    per_epoch = run_islands(cfg, _zdt, jax.random.key(3),
                            checkpoint_fn=snaps.append, **kw)
    paired = run_islands(cfg, _zdt, jax.random.key(3),
                         epochs_per_superstep=2, **kw)
    for other in (per_epoch, paired):
        np.testing.assert_array_equal(np.asarray(fused.archive.objectives),
                                      np.asarray(other.archive.objectives))
        np.testing.assert_array_equal(np.asarray(fused.islands.genomes),
                                      np.asarray(other.islands.genomes))
    # per-epoch checkpointing delivered every boundary, as host snapshots
    assert [int(s.epoch) for s in snaps] == [1, 2, 3, 4, 5]
    assert all(isinstance(s.archive.objectives, np.ndarray) for s in snaps)


def test_resume_from_host_snapshot_is_bit_exact():
    """Checkpoint snapshots are independent host copies (donation-safe) and
    a resume from one replays the remaining epochs bit-for-bit."""
    cfg = NSGA2Config(mu=8, genome_dim=4, bounds=((0., 1.),) * 4,
                      n_objectives=2)
    kw = dict(n_islands=3, lam=8, steps_per_epoch=2, epochs=4,
              archive_size=32)
    snaps = []
    full = run_islands(cfg, _zdt, jax.random.key(4),
                       checkpoint_fn=snaps.append, **kw)
    resumed = run_islands(cfg, _zdt, jax.random.key(4),
                          start_state=snaps[1], **kw)
    np.testing.assert_array_equal(np.asarray(full.archive.objectives),
                                  np.asarray(resumed.archive.objectives))
    np.testing.assert_array_equal(np.asarray(full.islands.genomes),
                                  np.asarray(resumed.islands.genomes))
    assert int(resumed.total_evaluations) == int(full.total_evaluations)


def test_host_snapshot_shares_no_buffers_with_live_state():
    cfg = NSGA2Config(mu=8, genome_dim=3, bounds=((0., 1.),) * 3,
                      n_objectives=2)
    state = init_island_state(cfg, jax.random.key(5), n_islands=2,
                              archive_size=16)
    before = np.asarray(state.islands.genomes).copy()
    snap = host_snapshot(state)
    # donating the live state must leave the snapshot fully readable —
    # array leaves as host numpy, the PRNG keys as fresh device buffers
    bump = jax.jit(lambda s: jax.tree.map(
        lambda x: x + 1 if x.dtype == jnp.float32 else x, s),
        donate_argnums=0)
    bump(state)
    assert isinstance(snap.islands.genomes, np.ndarray)
    np.testing.assert_array_equal(snap.islands.genomes, before)
    key_bytes = np.asarray(jax.random.key_data(snap.islands.rng))
    assert key_bytes.shape[0] == 2 and key_bytes.dtype == np.uint32


def test_pipeline_honours_reseed_frac_and_merge_top_k():
    """Satellite fix: the pipelined path used to silently build
    `make_reseed(cfg)` with the default fraction regardless of the caller's
    reseed_frac. Replaying the documented double-buffered schedule by hand
    from the same initial state — with non-default reseed_frac AND
    merge_top_k — must reproduce run_islands(pipeline=True) bit-for-bit."""
    from repro.evolution import (IslandState, make_evolve, make_merge,
                                 make_reseed)
    cfg = NSGA2Config(mu=8, genome_dim=4, bounds=((0., 1.),) * 4,
                      n_objectives=2)
    epochs, frac, top_k = 3, 0.25, 4
    state0 = init_island_state(cfg, jax.random.key(6), n_islands=3,
                               archive_size=32)
    got = run_islands(cfg, _zdt, jax.random.key(99), n_islands=3, lam=8,
                      steps_per_epoch=2, epochs=epochs, archive_size=32,
                      merge_top_k=top_k, reseed_frac=frac, pipeline=True,
                      start_state=state0)

    evolve = jax.jit(make_evolve(cfg, _zdt, lam=8, steps_per_epoch=2))
    merge_islands = jax.jit(make_merge(cfg, merge_top_k=top_k))
    reseed = jax.jit(make_reseed(cfg, reseed_frac=frac))
    archive = state0.archive
    evolved = evolve(state0.islands)
    for e in range(epochs):
        new_archive = merge_islands(archive, evolved)
        if e + 1 < epochs:
            seeded = reseed(evolved, archive)       # stale-archive reseed
            next_evolved = evolve(seeded)
        archive = new_archive
        if e + 1 < epochs:
            evolved = next_evolved
    np.testing.assert_array_equal(np.asarray(got.islands.genomes),
                                  np.asarray(evolved.genomes))
    np.testing.assert_array_equal(np.asarray(got.archive.objectives),
                                  np.asarray(archive.objectives))


# ---------------------------------------------------------------------------
# multi-device subprocess harness
# ---------------------------------------------------------------------------
_EPOCH_DIGEST = """
    import hashlib, jax, jax.numpy as jnp, numpy as np
    from repro.evolution import NSGA2Config, init_island_state, \\
        make_superstep
    from repro.launch.mesh import compat_make_mesh
    from repro.runtime import sharding as shd

    def zdt(keys, genomes):
        x0 = genomes[:, 0]
        g = 1 + 9 * genomes[:, 1:].mean(axis=1)
        f2 = g * (1 - jnp.sqrt(jnp.clip(x0 / g, 0, 1)))
        return jnp.stack([x0, f2], axis=1)

    nd = len(jax.devices())
    assert nd == %d, jax.devices()
    cfg = NSGA2Config(mu=16, genome_dim=4, bounds=((0., 1.),) * 4,
                      n_objectives=2)
    mesh = compat_make_mesh((nd,), ("data",)) if nd > 1 else None
    with shd.use_mesh(mesh):
        state = init_island_state(cfg, jax.random.key(0), n_islands=8,
                                  archive_size=96)
        sstep = jax.jit(make_superstep(cfg, zdt, lam=8, steps_per_epoch=2),
                        static_argnums=1, donate_argnums=0)
        state, _ = sstep(state, 4)
        with jax.transfer_guard("disallow"):
            state, _ = sstep(state, 2)
            jax.block_until_ready(state.archive.objectives)
    h = hashlib.sha256()
    h.update(np.asarray(state.archive.objectives).tobytes())
    h.update(np.asarray(state.archive.genomes).tobytes())
    h.update(np.asarray(state.islands.genomes).tobytes())
    print("DIGEST", h.hexdigest())
"""


@pytest.mark.parametrize("devices", [
    4, pytest.param(8, marks=pytest.mark.slow)])
def test_scanned_epoch_bit_exact_vs_single_device(devices):
    """The scanned, donated, mesh-sharded superstep at 4 (and 8) forced
    host devices produces byte-identical state to the single-device run —
    and executes transfer-guard-clean at every count."""
    ref = _run_forced(_EPOCH_DIGEST % 1, 1)
    got = _run_forced(_EPOCH_DIGEST % devices, devices)
    assert ref.strip().splitlines()[-1] == got.strip().splitlines()[-1]


@pytest.mark.parametrize("n,devices", [
    (997, 4),                                    # prime N
    (1001, 4),                                   # odd, 7x11x13
    pytest.param(509, 8, marks=pytest.mark.slow)])
def test_sharded_pass_pads_odd_sizes_on_multidevice_mesh(n, devices):
    """Padding, not fallback: N indivisible by n_shards*32 must still run
    the real shard_map sweep (asserted via psum presence: the sharded path
    is the only one touching collectives) and match the oracle exactly."""
    script = f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import compat_make_mesh
        from repro.runtime import sharding as shd
        from repro.evolution import nsga2
        from repro.kernels import ref
        nd = len(jax.devices())
        assert nd == {devices}, jax.devices()
        mesh = compat_make_mesh((nd,), ("data",))
        n = {n}
        f = jax.random.uniform(jax.random.key(0), (n, 3), jnp.float32)
        g = (jnp.arange(n) % 3).astype(jnp.int32)
        with shd.use_mesh(mesh):
            hlo = jax.jit(shd.sharded_dominance_pass).lower(f).as_text()
            assert "all_reduce" in hlo or "all-reduce" in hlo, \\
                "padded sizes must shard, not fall back to one device"
            cnt, bm = shd.sharded_dominance_pass(f, groups=g)
            ranks = jax.jit(lambda x: nsga2.nondominated_ranks(
                x, pass_fn=shd.sharded_dominance_pass))(f)
        cnt_ref, bm_ref = ref.dominance_pass_ref(f, groups=g)
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))
        np.testing.assert_array_equal(np.asarray(bm), np.asarray(bm_ref))
        np.testing.assert_array_equal(np.asarray(ranks),
                                      ref.nondominated_ranks_ref(f))
        print("OK")
    """
    assert "OK" in _run_forced(script, devices)


def test_placed_state_is_sharded_from_birth():
    """init_island_state commits island-axis leaves to the mesh: on a real
    multi-device mesh the genomes arrive sharded (addressable shards hold
    a strict subset of islands), archive and keys replicated."""
    script = """
        import jax, jax.numpy as jnp
        from repro.launch.mesh import compat_make_mesh
        from repro.runtime import sharding as shd
        from repro.evolution import NSGA2Config, init_island_state
        nd = len(jax.devices())
        assert nd == 4, jax.devices()
        mesh = compat_make_mesh((nd,), ("data",))
        cfg = NSGA2Config(mu=8, genome_dim=3, bounds=((0., 1.),) * 3,
                          n_objectives=2)
        with shd.use_mesh(mesh):
            state = init_island_state(cfg, jax.random.key(0), n_islands=8,
                                      archive_size=64)
        shards = state.islands.genomes.addressable_shards
        assert len(shards) == 4, len(shards)
        assert all(s.data.shape == (2, 8, 3) for s in shards), \\
            [s.data.shape for s in shards]
        assert state.archive.objectives.addressable_shards[0].data.shape \\
            == state.archive.objectives.shape
        print("OK")
    """
    assert "OK" in _run_forced(script, 4)


def test_init_distributed_is_noop_single_process():
    assert init_distributed() is False
