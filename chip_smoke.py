"""Smoke run of the system's main path on a TPU, checked against references.

    python chip_smoke.py              # one chip: kernels, then calibration
    python chip_smoke.py --chips 4    # island calibration: 4-chip mesh vs 1

One chip, in order; any failure exits non-zero:

1. device: platform, kind and count. Anything but a TPU stops here.
2. kernels at real widths. Each is compiled from its jitted caller in
   ``repro.kernels.ops``. The compiled program must hold a
   ``tpu_custom_call``, so a silent route to the reference cannot pass.
   Each result is compared with its jitted reference: dominance exactly, the
   f32 kernels within ``ref.TOL``.
3. foraging: the ants model at the paper's size (``configs/ants_netlogo.
   CONFIG``: 72x72 world, 125 ants, 1000 ticks) must carry food off, most
   of it from the nearest source. At CONFIG no source empties within the
   1000 ticks (the CPU reference agrees), so the calibration's objectives
   sit at that cap and cannot show that the colony works.
4. main path at CONFIG with 5 replicates reduced to their median.
   ``calibrate(reduced=False)`` streams a 1024-individual initial
   population through a DeviceEnvironment pool, then runs 2 island epochs.
   Two rounds of ``calibrate_surrogate(reduced=False)`` follow; the second
   fits the GP, so the distance kernel runs inside the engine.

``--chips 4`` runs only the island calibration, smaller: on a 4-chip
``("data",)`` mesh with 4 device-set pool members, then on a 1-device mesh
in the same process, and requires identical archive digests.

Sizes: on a v5e the ants model costs about 30 us per lane and tick, almost
all of it outside the diffusion kernel, so the smoke scores about a
thousand individuals (the paper's initial population is 200,000).

Timings are printed on the lines before the last, labelled as a smoke run:
they are single samples, not a benchmark. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Everything runs in this one process: a child would find the chip held.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
# (init_population, init_chunk, lam) of the one-chip calibration and of
# the two runs the --chips 4 check compares
ONE_CHIP_RUN = (1024, 256, 16)
MESH_RUN = (256, 64, 4)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check_device(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if info["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{info['platform']!r}); nothing was run")
    if info["count"] < chips:
        raise SystemExit(f"chip_smoke: {chips} chips requested, JAX found "
                         f"{info['count']}")
    return info


# ---------------------------------------------------------------------------
# kernels at real widths
# ---------------------------------------------------------------------------
def run_kernel(name, caller, reference, args):
    """Compile ``caller`` (its program must hold a Pallas kernel), time one
    call after a warm-up, and return (kernel output, reference output)."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(caller).lower(*args).compile()
    t_compile = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{name}: no Pallas kernel in the compiled "
                             f"program (routed to the reference?)")
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    got = jax.block_until_ready(compiled(*args))
    t_run = time.perf_counter() - t0
    log(f"kernel {name}: compile {t_compile:.3f} s, run "
        f"{t_run * 1e3:.3f} ms (smoke timing: one call after a warm-up)")
    return got, jax.block_until_ready(jax.jit(reference)(*args))


def kernels_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.ants_netlogo import CONFIG
    from repro.kernels import ops, ref

    def close(name, got, want, tol):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol,
                                   err_msg=f"{name} vs its reference")

    ks = jax.random.split(jax.random.key(0), 8)
    # diffusion over 2048 individuals x 5 replicates of the paper's 72x72
    # world
    n, w = 2048 * 5, CONFIG.world_size
    chem = jax.random.uniform(ks[0], (n, w, w), jnp.float32)
    rate = jax.random.uniform(ks[1], (n,), jnp.float32)
    evap = jax.random.uniform(ks[2], (n,), jnp.float32)
    got, want = run_kernel("diffuse_evaporate (10240, 72, 72)",
                           ops.diffuse_evaporate, ref.diffuse_evaporate_ref,
                           (chem, rate, evap))
    close("diffuse_evaporate", got, want, ref.TOL["diffusion"])

    # dominance over 8192 tick-valued objective rows (ties included) in 8
    # island groups
    f = jnp.floor(jax.random.uniform(ks[3], (8192, 3)) * CONFIG.max_ticks)
    g = jnp.arange(8192, dtype=jnp.int32) % 8
    (cnt, bm), (cnt_r, bm_r) = run_kernel(
        "dominance_pass N=8192, 8 groups",
        lambda f, g: ops.dominance_pass(f, groups=g),
        lambda f, g: ref.dominance_pass_ref(f, groups=g), (f, g))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_r))
    np.testing.assert_array_equal(np.asarray(bm), np.asarray(bm_r))

    x = jax.random.uniform(ks[4], (2048, 2), jnp.float32)
    got, want = run_kernel("gp_matrix 2048x2048", ops.gp_matrix,
                           ref.gp_matrix_ref, (x, x))
    close("gp_matrix", got, want, ref.TOL["gp_matrix"])

    # fused assembly + blocked Cholesky of K + 1e-2 I, 1000 points, block
    # 256 (pads to 1024 like the engine route)
    n_gp, n_p, block, nugget = 1000, 1024, 256, 1e-2
    x = jax.random.uniform(ks[5], (n_gp, 2), jnp.float32)

    def chol_ref(x):
        xp = jnp.zeros((n_p, 2), jnp.float32).at[:n_gp].set(x)
        return ref.gp_chol_blocked_ref(xp, n_gp, kind="matern52",
                                       lengthscale=0.2, nugget=nugget,
                                       block=block)[:n_gp, :n_gp]

    l_fac, want = run_kernel(
        "gp_chol n=1000 block=256",
        lambda x: ops.gp_chol(x, nugget=nugget, block=block), chol_ref, (x,))
    close("gp_chol", l_fac, want, ref.TOL["chol"])

    b = jax.random.normal(ks[6], (n_gp, 256), jnp.float32)

    def solve_ref(l, b):
        lp = jnp.eye(n_p, dtype=jnp.float32).at[:n_gp, :n_gp].set(l)
        bp = jnp.zeros((n_p, 256), jnp.float32).at[:n_gp].set(b)
        return ref.tri_solve_blocked_ref(lp, bp, trans=False, block=block,
                                         rhs_block=256)[:n_gp]

    got, want = run_kernel(
        "tri_solve n=1000 rhs=256 block=256",
        lambda l, b: ops.tri_solve(l, b, block=block), solve_ref,
        (l_fac, b))
    close("tri_solve", got, want, ref.TOL["tri_solve"])


# ---------------------------------------------------------------------------
# foraging at the paper's size
# ---------------------------------------------------------------------------
def foraging_phase() -> None:
    """64 colonies at CONFIG with rates drawn from the calibration bounds,
    1000 ticks on the chip: every colony carries food off its nearest
    source, and on average more of it than off the farthest (the model's
    colony-level behaviour). Prints the mean units taken per source."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.ants.model import food_sources, init_state, make_step
    from repro.configs.ants_netlogo import BOUNDS, CONFIG

    n = 64
    kd, ke, kr = jax.random.split(jax.random.key(1), 3)
    d = jax.random.uniform(kd, (n,), minval=BOUNDS[0][0], maxval=BOUNDS[0][1])
    e = jax.random.uniform(ke, (n,), minval=BOUNDS[1][0], maxval=BOUNDS[1][1])
    step = make_step(CONFIG)

    @jax.jit
    def food_after_run(keys, d, e):
        st, _ = jax.lax.scan(
            lambda st, t: (step(st, t, d / 100.0, e / 100.0), None),
            init_state(CONFIG, keys),
            jnp.arange(CONFIG.max_ticks, dtype=jnp.int32))
        return st.food

    t0 = time.perf_counter()
    food = np.asarray(food_after_run(jax.random.split(kr, n), d, e))
    wall = time.perf_counter() - t0
    food0, masks = food_sources(CONFIG)
    masks = np.asarray(masks, np.float32)
    taken = (np.einsum("kij,ij->k", masks, np.asarray(food0))
             - np.einsum("kij,nij->nk", masks, food))
    mean = taken.mean(axis=0)
    log(f"foraging: {n} colonies x {CONFIG.max_ticks} ticks in {wall:.1f} s "
        f"(smoke timing, compile included); mean food units taken per "
        f"source (nearest first): {mean.tolist()}")
    if not ((taken[:, 0] > 0).all() and mean[0] > mean[2]):
        raise AssertionError(f"foraging: the colonies did not work the "
                             f"nearest source first: {taken.tolist()}")


# ---------------------------------------------------------------------------
# the main path: DSL -> pool -> device ants evaluation -> island NSGA-II
# ---------------------------------------------------------------------------
def island_calibration(name: str, sizes, *, mesh=None, pool_devices=1):
    """``calibrate`` at CONFIG from a clean output directory, with
    ``sizes`` = (init_population, init_chunk, lam); checks the pool
    accounting and the objectives, returns the final state."""
    import numpy as np

    from repro.configs.ants_netlogo import CONFIG
    from repro.launch.explore import calibrate

    population, chunk, lam = sizes
    out = os.path.join(OUT, name)
    shutil.rmtree(out, ignore_errors=True)          # no resume: a fresh run
    t0 = time.perf_counter()
    state, front = calibrate(
        reduced=False, n_islands=8, mu=16, lam=lam, steps_per_epoch=1,
        epochs=2, replicates=5, archive_size=256,
        init_population=population, init_chunk=chunk,
        fault_rate=0.0, pool_devices=pool_devices, mesh=mesh, out_dir=out,
        printer=lambda m: log(f"{name}: {m}"))
    wall = time.perf_counter() - t0
    init = front["init"]
    chunks = -(-population // chunk)
    if init["attempts"] != chunks:
        raise AssertionError(f"{name}: {init['attempts']} pool attempts for "
                             f"{chunks} chunks at fault_rate=0: a retry "
                             f"hid an error")
    valid = np.asarray(state.archive.valid)
    obj = np.asarray(state.archive.objectives)[valid]
    if not (obj.size and (obj >= 0).all()
            and (obj <= CONFIG.max_ticks).all()):
        raise AssertionError(f"{name}: archive objectives outside "
                             f"[0, {CONFIG.max_ticks}]: {obj}")
    if not front["objectives"]:
        raise AssertionError(f"{name}: empty Pareto front")
    log(f"{name} (smoke run, not a benchmark): init {population} "
        f"individuals in {init['wall_s']:.1f} s "
        f"({population / init['wall_s']:.2f} evaluations/s, "
        f"{init['attempts']} attempts for {chunks} chunks); islands "
        f"{front['evaluations'] - population} evaluations in "
        f"{front['wall_s']:.1f} s; calibrate wall {wall:.1f} s; front "
        f"{len(front['objectives'])} points")
    return state


def kernels_in_island_programs(state) -> None:
    """The programs calibrate ran hold the Pallas diffusion and dominance
    kernels: lower (not compile) the ants evaluation and the archive merge
    at their run shapes and find the kernels by name."""
    import jax
    import jax.numpy as jnp

    from repro.ants import simulate_batch
    from repro.configs.ants_netlogo import BOUNDS, CONFIG
    from repro.evolution import NSGA2Config
    from repro.evolution.island import make_merge

    n = ONE_CHIP_RUN[1] * 5
    keys = jax.random.split(jax.random.key(0), n)
    rates = jnp.full((n,), 50.0, jnp.float32)
    ants = simulate_batch.lower(CONFIG, keys, rates, rates).as_text()
    cfg = NSGA2Config(mu=16, genome_dim=2, bounds=BOUNDS, n_objectives=3)
    merge = jax.jit(make_merge(cfg, merge_top_k=8)).lower(
        state.archive, state.islands).as_text()
    for prog, text, kernel in (("ants evaluation", ants, "diffuse_evaporate"),
                               ("archive merge", merge, "dominance_pass")):
        if f'kernel_name = "{kernel}"' not in text:
            raise AssertionError(f"the {prog} program holds no {kernel} "
                                 f"Pallas kernel")
    log("calibrate's programs hold the diffuse_evaporate and dominance_pass "
        "Pallas kernels")


def surrogate_phase() -> None:
    import numpy as np

    from repro.configs.ants_netlogo import CONFIG
    from repro.launch.explore import calibrate_surrogate

    out = os.path.join(OUT, "surrogate")
    shutil.rmtree(out, ignore_errors=True)
    t0 = time.perf_counter()
    # round 0 is the Sobol seed (n_init = q); round 1 asks the fitted GP
    res, summary = calibrate_surrogate(
        reduced=False, rounds=2, q=4, n_init=4, replicates=3,
        fault_rate=0.0, pool_devices=1, out_dir=out,
        printer=lambda m: log(f"surrogate: {m}"))
    wall = time.perf_counter() - t0
    y = np.asarray(res.objectives)
    if res.attempts != len(y) or len(y) != 8:
        raise AssertionError(f"surrogate: {res.attempts} attempts for "
                             f"{len(y)} of 8 evaluations at fault_rate=0")
    if not ((y >= 0).all() and (y <= CONFIG.max_ticks).all()):
        raise AssertionError(f"surrogate objectives outside "
                             f"[0, {CONFIG.max_ticks}]: {y}")
    log(f"surrogate (smoke run, not a benchmark): {len(y)} evaluations in "
        f"{wall:.1f} s ({len(y) / wall:.2f} evaluations/s), best "
        f"{summary['best_objective']:.0f}")


def archive_digest(state) -> str:
    import numpy as np
    h = hashlib.sha256()
    for x in (state.archive.objectives, state.archive.genomes,
              state.islands.genomes):
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()


def mesh_phase() -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_island_mesh

    devs = jax.devices()
    mesh4 = make_island_mesh(data=4)
    mesh1 = jax.sharding.Mesh(np.asarray(devs[:1]), ("data",))
    d4 = archive_digest(island_calibration("mesh4", MESH_RUN, mesh=mesh4,
                                           pool_devices=4))
    d1 = archive_digest(island_calibration("mesh1", MESH_RUN, mesh=mesh1,
                                           pool_devices=1))
    log(f"archive digest, 4-chip mesh: {d4}")
    log(f"archive digest, 1-device mesh: {d1}")
    if d4 != d1:
        raise AssertionError("the 4-chip island calibration diverged from "
                             "the 1-device run")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the island calibration on a 4-chip mesh, "
                         "compared with a 1-device mesh")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    t0 = time.perf_counter()
    log(f"compile cache: {enable_compile_cache()}")
    info = check_device(args.chips)
    if args.chips == 4:
        mesh_phase()
    else:
        kernels_phase()
        foraging_phase()
        kernels_in_island_programs(
            island_calibration("calibrate", ONE_CHIP_RUN))
        surrogate_phase()
    log(f"smoke wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
