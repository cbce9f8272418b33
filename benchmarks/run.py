"""Benchmark harness — one benchmark per paper claim/figure.

Prints ``name,us_per_call,derived`` CSV rows and (with ``--json``) writes a
machine-readable ``BENCH_results.json`` so the perf trajectory is tracked
across PRs (name -> us_per_call + derived metrics, plus backend and git sha).
Each benchmark measures the steady state (post-compile) on this host; the
paper-scale projections next to them come from the roofline artifacts
(benchmarks/roofline.py).

    python benchmarks/run.py                        # full shapes, CSV only
    python benchmarks/run.py --json BENCH_results.json
    python benchmarks/run.py --reduced --only nsga2 # CI smoke shapes

Paper claims covered:
  ants_tick             the simulation workload itself (Fig 1/2 model)
  ants_eval_throughput  §4.6: "200,000 individuals evaluated in one hour"
  island_epoch          §4.6 island model end-to-end epoch
  island_scaling        the EGI scale-out story on one host: the scanned,
                        donated, mesh-sharded superstep vs simulated device
                        count (forced host devices, one subprocess each),
                        bit-exact across counts and transfer-guard-clean
  nsga2_dominance       §4.5 non-dominated sorting: the fused single-pass
                        selection engine vs the per-front peeling baseline
  nsga2_generation      §4.5 Listing 4 one generational step
  workflow_submit       §2 engine overhead per delegated task
  replication_median    §4.4 Listing 3 replication + median
  egi_200k_init         §4.6: 200k-individual GA init streamed through the
                        fault-tolerant EnvironmentPool — throughput and
                        makespan failure-free vs >=30% injected failures
                        (bit-exact), plus mid-population kill+resume
  egi_200k_init_{k}dev  the same streaming init delegated to DEVICE-SET
                        pool members (make_init_pool(pool_devices=k), one
                        DeviceEnvironment per forced device) vs simulated
                        device count — bit-exact across counts and vs the
                        thread-backed member baseline
  service_two_tenant    the always-on delegation layer: two concurrent
                        experiments through ONE shared pool via the
                        persistent priority task queue, bit-exact vs their
                        serial one-pool-each references
  gp_covariance         surrogate engine hot spot: fused one-pass GP
                        covariance assembly (engine route of the Pallas
                        kernel) vs the naive broadcast jnp reference that
                        materializes the (N, N, D) difference tensor
  gp_chol               archive-scale GP factorization: the blocked fused
                        assemble+factor engine (serial lengthscale sweep
                        under one jit) vs assembling the (G, N, N) stack
                        and vmapping jnp.linalg.cholesky over the grid,
                        kernel-vs-oracle bit-exactness asserted in-bench
  surrogate_bigN        past the O(N^3) wall: a warm surrogate ask/tell
                        round at 50k-point history via the inducing-point
                        engine + incremental rank-q tell, with the regret
                        vs the exact dense path reported
  surrogate_ants        adaptive vs static design of experiments: GP+q-EI
                        ask/tell evaluations-to-target vs the LHS baseline
                        on the ants model (plus proposals/s of the warm
                        ask path)
  lm_train_step         the 2026-scale "expensive task" (reduced smollm)
  bandit_router_throughput  live traffic as the experiment: requests/s
                        through the UCB router over competing serving
                        arms vs direct generation pinned to the oracle
                        arm (router overhead, not arm-mix compute), with
                        the cumulative-regret breakdown (sublinear growth
                        asserted at full shapes) in the JSON row
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

RESULTS: dict = {}


class Timing(float):
    """A per-call time in us that *is* its median (arithmetic works as
    before) but carries the raw repeat samples, so rows can report the
    min/max spread — this host's timings fluctuate ~2x under load, and a
    single-shot mean is indistinguishable from a real regression."""
    samples: tuple

    def __new__(cls, samples):
        obj = super().__new__(cls, float(np.median(np.asarray(samples))))
        obj.samples = tuple(float(s) for s in samples)
        return obj

    def scaled(self, k: float) -> "Timing":
        return Timing([s * k for s in self.samples])


def timeit(fn, *, warmup=2, iters=5):
    """Median-of-``iters`` per-call time (us) with the samples attached."""
    for _ in range(warmup):
        fn()
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        samples.append((time.perf_counter() - t0) * 1e6)
    return Timing(samples)


def row(name, us, derived, **extra):
    """Record one result row. ``extra`` keys land in the JSON entry as-is
    (structured metrics a derived-string can't carry — e.g. the bandit
    row's regret breakdown, which tools/check_bench.py validates)."""
    print(f"{name},{us:.1f},{derived}")
    entry = {"us_per_call": round(float(us), 1), "derived": derived}
    if isinstance(us, Timing):
        entry["repeats"] = len(us.samples)
        entry["min_us"] = round(min(us.samples), 1)
        entry["max_us"] = round(max(us.samples), 1)
    else:
        entry["repeats"] = 1
    entry.update(extra)
    RESULTS[name] = entry


def bench_ants_tick(reduced=False):
    from repro.ants import init_state, make_step
    from repro.configs.ants_netlogo import REDUCED
    n = 8 if reduced else 64
    keys = jax.random.split(jax.random.key(0), n)
    state = init_state(REDUCED, keys)
    step = jax.jit(make_step(REDUCED))
    d = jnp.full((n,), 0.5)
    e = jnp.full((n,), 0.1)

    def one():
        nonlocal state
        state = step(state, jnp.int32(1), d, e)
        jax.block_until_ready(state.chem)

    us = timeit(one)
    row(f"ants_tick_{n}lanes", us, f"{n / (us / 1e6):.0f}_lane_ticks_per_s")


def bench_ants_eval_throughput(reduced=False):
    """The paper's 200k evals/hour claim, measured on this host."""
    from repro.ants import simulate_batch
    from repro.configs.ants_netlogo import REDUCED
    n = 4 if reduced else 32
    keys = jax.random.split(jax.random.key(0), n)
    d = jax.random.uniform(jax.random.key(1), (n,)) * 99
    e = jax.random.uniform(jax.random.key(2), (n,)) * 99

    def one():
        simulate_batch(REDUCED, keys, d, e).block_until_ready()

    us = timeit(one, warmup=1, iters=3)
    per_hour = n / (us / 1e6) * 3600
    row("ants_eval_throughput", us.scaled(1 / n),
        f"{per_hour:.0f}_evals_per_hour_single_CPU_core")


def bench_island_epoch(reduced=False):
    from repro.ants import simulate_batch
    from repro.configs.ants_netlogo import BOUNDS, REDUCED
    from repro.evolution import NSGA2Config, init_island_state, make_epoch
    from repro.explore import replicated_batch
    n_islands, reps = (2, 2) if reduced else (4, 3)
    cfg = NSGA2Config(mu=8, genome_dim=2, bounds=BOUNDS, n_objectives=3)
    eval_fn = replicated_batch(
        lambda k, g: simulate_batch(REDUCED, k, g[:, 0], g[:, 1]), reps)
    epoch = jax.jit(make_epoch(cfg, eval_fn, lam=8, steps_per_epoch=1))
    state = init_island_state(cfg, jax.random.key(0), n_islands=n_islands,
                              archive_size=64)

    def one():
        nonlocal state
        state = epoch(state)
        jax.block_until_ready(state.archive.objectives)

    us = timeit(one, warmup=1, iters=3)
    evals = n_islands * 8 * reps   # islands x lam x replicates (steady state)
    row(f"island_epoch_{n_islands}islands", us,
        f"{evals / (us / 1e6):.0f}_sim_runs_per_s")


def bench_nsga2_dominance(reduced=False):
    """§4.5 sorting hot spot at archive scale: the fused single-pass engine
    (one O(N^2) sweep + popcount peeling) vs the pre-engine peeling baseline
    (one full pairwise pass per front, jitted lax.while_loop) — both jitted
    and warmed, apples to apples."""
    from repro.evolution import nsga2
    n, m = (512, 3) if reduced else (8192, 3)
    iters = 3    # median-of-3 even at full shape: the headline x-factor row
    f = jax.random.uniform(jax.random.key(0), (n, m), jnp.float32)
    fused = jax.jit(nsga2.nondominated_ranks)
    peel = jax.jit(nsga2.nondominated_ranks_peel_while)

    us_fused = timeit(lambda: jax.block_until_ready(fused(f)),
                      warmup=1, iters=iters)
    us_peel = timeit(lambda: jax.block_until_ready(peel(f)),
                     warmup=1, iters=iters)
    ranks = np.asarray(fused(f))
    np.testing.assert_array_equal(ranks, np.asarray(peel(f)))
    passes = int(ranks[ranks < n].max()) + 1   # peel ran one pass per front

    pairs_per_s = n * n / (us_fused / 1e6) / 1e9
    row(f"nsga2_dominance_{n}", us_fused,
        f"{us_peel / us_fused:.1f}x_vs_peeling_baseline_"
        f"{pairs_per_s:.2f}_Gpairs_per_s")
    row(f"nsga2_dominance_{n}_peel_baseline", us_peel,
        f"{passes}_pairwise_passes")


def _require_cpu_host(bench: str) -> None:
    """The forced-host-device scaling rows time the CPU in child processes
    that the parent starts after it has touched JAX: on a chip host the
    rows would carry CPU times under the chip's backend header, and a
    child that reached for the chip would find it held by this process."""
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{bench} models multi-device scaling on forced CPU host "
            f"devices; refusing to run on backend "
            f"{jax.default_backend()!r}")


def bench_island_scaling(reduced=False):
    """Device-resident epoch scaling vs simulated device count (ROADMAP's
    EGI scale-out story): one subprocess per forced host device count (the
    count is fixed at jax import) runs the dominance-sweep-bound epoch as a
    scanned, donated superstep on a ("data",) mesh and re-runs it under
    ``jax.transfer_guard("disallow")`` — see benchmarks/island_scaling.py.
    Digests are asserted identical across counts (multi-device epochs are
    bit-exact vs single-device). On this 1-core host the k forced devices
    time-share the core, so the measured wall is k serialized per-device
    turns and ONE real device's critical path is wall/k — the derived
    simulated speedup is t1 / (tk / k), honest about the model
    (docs/performance.md)."""
    _require_cpu_host("bench_island_scaling")
    shape = "reduced" if reduced else "full"
    counts = (1, 2) if reduced else (1, 2, 4, 8)
    child = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "island_scaling.py")
    results = {}
    for k in counts:
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": f"--xla_force_host_platform_device_count={k}"}
        r = subprocess.run([sys.executable, child, "--shape", shape],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        assert r.returncode == 0, r.stdout + r.stderr
        results[k] = json.loads(r.stdout.strip().splitlines()[-1])
        assert results[k]["devices"] == k

    digests = {res["digest"] for res in results.values()}
    assert len(digests) == 1, \
        f"multi-device epochs diverged from single-device: {results}"
    t1 = float(np.median(results[1]["samples_s"]))
    for k in counts:
        us = Timing([s * 1e6 for s in results[k]["samples_s"]])
        sim_speedup = t1 / ((us / 1e6) / k)
        row(f"island_scaling_{k}dev", us,
            f"{sim_speedup:.1f}x_simulated_speedup_vs_1dev_"
            f"{t1 / (us / 1e6):.2f}x_raw_wall_bit_exact_True_"
            f"transfer_guard_clean")
        if not reduced and k == 8:
            assert sim_speedup >= 2.5, (
                f"8 simulated devices must reach >=2.5x simulated epoch "
                f"speedup (got {sim_speedup:.2f}x)")


def bench_nsga2_generation(reduced=False):
    from repro.evolution import NSGA2Config
    from repro.evolution.ga import evaluate_initial, init_state, make_step
    mu = 16 if reduced else 64
    cfg = NSGA2Config(mu=mu, genome_dim=4, bounds=((0., 1.),) * 4,
                      n_objectives=3)

    def zdt(keys, genomes):
        f1 = genomes[:, 0]
        return jnp.stack([f1, 1 - f1, (genomes ** 2).sum(1)], 1)

    state = evaluate_initial(cfg, init_state(cfg, jax.random.key(0)), zdt)
    step = jax.jit(make_step(cfg, zdt, lam=mu))

    def one():
        nonlocal state
        state = step(state)
        jax.block_until_ready(state.objectives)

    us = timeit(one)
    row(f"nsga2_generation_mu{mu}", us,
        f"{mu / (us / 1e6):.0f}_offspring_per_s")


def bench_workflow_submit(reduced=False):
    from repro.core import Context, LocalEnvironment, PyTask, Val
    env = LocalEnvironment()
    t = PyTask("noop", lambda ctx: {"y": ctx["x"]}, inputs=(Val("x"),),
               outputs=(Val("y"),))

    def one():
        for _ in range(100):
            env.submit(t, Context(x=1.0))

    us = timeit(one).scaled(1 / 100)
    row("workflow_submit", us, f"{1e6 / us:.0f}_tasks_per_s")


def bench_replication_median(reduced=False):
    from repro.ants import simulate_batch
    from repro.configs.ants_netlogo import REDUCED
    from repro.explore import replicated_batch
    reps = 2 if reduced else 5
    eval_fn = replicated_batch(
        lambda k, g: simulate_batch(REDUCED, k, g[:, 0], g[:, 1]), reps)
    keys = jax.random.split(jax.random.key(0), 4)
    genomes = jax.random.uniform(jax.random.key(1), (4, 2)) * 99
    jfn = jax.jit(eval_fn)

    def one():
        jfn(keys, genomes).block_until_ready()

    us = timeit(one, warmup=1, iters=3)
    row(f"replication_median_{reps}x", us,
        f"{4 * reps / (us / 1e6):.0f}_sim_runs_per_s")


def bench_egi_200k_init(reduced=False):
    """§4.6 headline at harness scale: a 200k-individual GA initial
    population evaluated through the fault-tolerant EnvironmentPool in
    device-sized chunks. Three legs: failure-free, >=30% injected job
    failures (asserted bit-exact vs. failure-free), and kill+resume from a
    mid-population checkpoint (asserted bit-exact too). The fitness is a
    cheap ants-shaped surrogate so the bench measures the delegation
    harness, not the simulator (ants_eval_throughput covers that)."""
    import shutil
    import tempfile

    from repro.evolution import NSGA2Config, ga
    from repro.launch.explore import make_init_pool

    n, chunk = (4096, 512) if reduced else (200_000, 4096)
    cfg = NSGA2Config(mu=16, genome_dim=2, bounds=((0., 100.), (0., 100.)),
                      n_objectives=3)

    def eval_fn(keys, genomes):
        noise = jax.vmap(lambda k: jax.random.normal(k, (3,)))(keys)
        d, e = genomes[:, 0], genomes[:, 1]
        return jnp.stack([(d - 30.) ** 2 + (e - 10.) ** 2,
                          jnp.abs(d - e), d + e], 1) + 0.1 * noise

    def run(rate, **kw):
        # chaos legs get extra pool rounds: at a 35% per-attempt fail rate
        # and ~50 chunk jobs, 9 rounds leave a per-run chance of some job
        # exhausting the pool (member pick order is timing-dependent);
        # 13 rounds make exhaustion statistically impossible (~1e-4)
        pool = make_init_pool(rate, backoff_s=0.01,
                              retries=12 if rate else 8)
        try:
            return ga.evaluate_population_streaming(
                cfg, eval_fn, 0, n_total=n, chunk=chunk, environment=pool,
                **kw)
        finally:
            pool.shutdown()

    # median-of-3 per leg (like every other row): the delegation harness
    # wall fluctuates with thread scheduling, a single shot is noise
    repeats = 3
    cleans = [run(0.0) for _ in range(repeats)]
    chaoses = [run(0.35) for _ in range(repeats)]
    clean, chaos = cleans[0], chaoses[0]
    bit_exact = all(
        np.array_equal(clean.objectives, r.objectives)
        for r in cleans[1:] + chaoses)
    assert bit_exact, "chaos run diverged from failure-free run"

    fulls = []
    for _ in range(repeats):
        ckpt = tempfile.mkdtemp(prefix="egi200k_")
        try:
            half = clean.chunks_total // 2
            part = run(0.35, checkpoint_dir=ckpt, stop_after_chunks=half)
            assert part.interrupted and part.chunks_done >= half
            fulls.append(run(0.35, checkpoint_dir=ckpt))
        finally:
            shutil.rmtree(ckpt, ignore_errors=True)
    full = fulls[0]
    resume_exact = all(np.array_equal(clean.objectives, r.objectives)
                       for r in fulls)
    assert all(r.resumed_chunks > 0 for r in fulls) and resume_exact, \
        "resumed run must be bit-exact and actually resume"

    us_clean = Timing([r.wall_s * 1e6 for r in cleans])
    us_chaos = Timing([r.wall_s * 1e6 for r in chaoses])
    us_full = Timing([r.wall_s * 1e6 for r in fulls])
    row("egi_200k_init", us_clean,
        f"{n / (us_clean / 1e6) * 3600:.0f}_evals_per_hour_failure_free_"
        f"{clean.chunks_total}_chunks")
    row("egi_200k_init_fail35", us_chaos,
        f"{n / (us_chaos / 1e6) * 3600:.0f}_evals_per_hour_at_35pct_"
        f"injected_failures_{chaos.attempts}_attempts_bit_exact_{bit_exact}")
    row("egi_200k_init_resume", us_full,
        f"resumed_{full.resumed_chunks}_of_{full.chunks_total}_chunks_"
        f"bit_exact_{resume_exact}")


def bench_egi_device_scaling(reduced=False):
    """ROADMAP open item 1, measured: the 200k streaming init through
    DEVICE-SET pool members (``make_init_pool(pool_devices=k)``) vs
    simulated device count — one subprocess per forced host device count
    (fixed at jax import), see benchmarks/egi_scaling.py. Digests are
    asserted identical across counts AND vs the pre-existing thread-backed
    member pool at 1 device (the single-member path the device rows must
    not change). On this 1-core host the k forced devices time-share the
    core, so the measured wall is k serialized per-device turns and ONE
    real device's critical path is wall/k — the derived simulated speedup
    is t1 / (tk / k), the same honest model as island_scaling
    (docs/performance.md)."""
    _require_cpu_host("bench_egi_device_scaling")
    shape = "reduced" if reduced else "full"
    counts = (1, 2) if reduced else (1, 2, 4)
    n_total = 4096 if reduced else 200_000
    child = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "egi_scaling.py")

    def spawn(k, extra=()):
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": f"--xla_force_host_platform_device_count={k}"}
        r = subprocess.run([sys.executable, child, "--shape", shape,
                            *extra], env=env, capture_output=True,
                           text=True, timeout=1200)
        assert r.returncode == 0, r.stdout + r.stderr
        res = json.loads(r.stdout.strip().splitlines()[-1])
        assert res["devices"] == k
        return res

    results = {k: spawn(k) for k in counts}
    baseline = spawn(1, ("--threads",))            # current thread path
    digests = {res["digest"] for res in results.values()}
    digests.add(baseline["digest"])
    assert len(digests) == 1, \
        f"device-set pools diverged from the thread-member path: {results}"

    t1 = float(np.median(results[1]["samples_s"]))
    for k in counts:
        us = Timing([s * 1e6 for s in results[k]["samples_s"]])
        sim_speedup = t1 / ((us / 1e6) / k)
        row(f"egi_200k_init_{k}dev", us,
            f"{sim_speedup:.1f}x_simulated_speedup_vs_1dev_"
            f"{n_total / (us / 1e6) * 3600:.0f}_evals_per_hour_"
            f"bit_exact_True")
        if not reduced and k == 4:
            assert sim_speedup >= 1.5, (
                f"4 simulated devices must reach >=1.5x simulated init "
                f"speedup (got {sim_speedup:.2f}x)")


def bench_service_two_tenant(reduced=False):
    """The always-on service (ROADMAP open item 1): TWO experiments share
    ONE pool through the persistent priority queue, vs the same two
    experiments run back-to-back one-pool-each. Both tenants are asserted
    bit-exact against their serial references (pure tasks: the dispatch
    interleave never changes values); the row reports the multi-tenant
    throughput and the makespan ratio vs serial."""
    import threading

    from repro.core import ExplorationService
    from repro.evolution import NSGA2Config, ga
    from repro.launch.explore import make_init_pool

    n, chunk = (1024, 128) if reduced else (16384, 512)
    cfg = NSGA2Config(mu=16, genome_dim=2, bounds=((0., 100.), (0., 100.)),
                      n_objectives=3)

    def eval_fn(keys, genomes):
        noise = jax.vmap(lambda k: jax.random.normal(k, (3,)))(keys)
        d, e = genomes[:, 0], genomes[:, 1]
        return jnp.stack([(d - 30.) ** 2 + (e - 10.) ** 2,
                          jnp.abs(d - e), d + e], 1) + 0.1 * noise

    def serial(seed):
        pool = make_init_pool(backoff_s=0.01)
        try:
            return ga.evaluate_population_streaming(
                cfg, eval_fn, seed, n_total=n, chunk=chunk, environment=pool)
        finally:
            pool.shutdown()

    serial(0)                       # warm the jit cache outside both timings
    t0 = time.perf_counter()
    refs = [serial(0), serial(1)]
    t_serial = time.perf_counter() - t0

    pool = make_init_pool(backoff_s=0.01)
    service = ExplorationService(pool)
    results = [None, None]

    def tenant(slot, seed):
        results[slot] = ga.evaluate_population_streaming(
            cfg, eval_fn, seed, n_total=n, chunk=chunk, service=service,
            experiment_id=f"tenant{seed}")

    t0 = time.perf_counter()
    threads = [threading.Thread(target=tenant, args=(s, s)) for s in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_service = time.perf_counter() - t0
    service.shutdown()
    pool.shutdown()

    bit_exact = all(
        np.array_equal(refs[s].objectives, results[s].objectives)
        for s in (0, 1))
    assert bit_exact, "service tenants diverged from serial references"
    jobs = refs[0].chunks_total + refs[1].chunks_total
    row("service_two_tenant_throughput", t_service * 1e6,
        f"{2 * n / t_service:.0f}_evals_per_s_2_tenants_{jobs}_jobs_"
        f"one_pool_speedup_{t_serial / t_service:.2f}x_vs_serial_"
        f"bit_exact_{bit_exact}")


def bench_gp_covariance(reduced=False):
    """Batched GP cross-covariance assembly at surrogate-archive scale, as
    the acquisition optimizer runs it: every q-EI sweep scores all
    multi-start candidate batches against the full N-point archive. The
    engine assembles the whole (B, q, N) cross-covariance block in ONE
    fused batched pass (the `gp_matrix` assembly vmapped over starts —
    natively the Pallas kernel on TPU, its bit-identical jitted jnp route
    on this CPU host), vs the jnp reference that assembles per start in a
    python loop of jit-compiled calls (the unbatched shape every
    restart-loop GP implementation has). Bit-exactness of the Pallas
    kernel itself is asserted at a padded prime shape (interpret mode)."""
    from repro.kernels import ref as kref
    from repro.kernels.gp import gp_matrix as gp_pallas

    n, d, b, q = (512, 16, 16, 8) if reduced else (4096, 16, 48, 8)
    x = jax.random.uniform(jax.random.key(0), (n, d), jnp.float32)
    xs = jax.random.uniform(jax.random.key(1), (b, q, d), jnp.float32)

    batched = jax.jit(
        lambda x, xs: jax.vmap(lambda s: kref.gp_matrix_ref(s, x))(xs))
    per_start = jax.jit(lambda s, x: kref.gp_matrix_ref(s, x))

    def loop():
        outs = [per_start(xs[i], x) for i in range(b)]
        jax.block_until_ready(outs[-1])

    us_fused = timeit(lambda: jax.block_until_ready(batched(x, xs)),
                      warmup=1, iters=3)
    us_loop = timeit(loop, warmup=1, iters=3)
    got = np.asarray(batched(x, xs))
    np.testing.assert_array_equal(got[b // 2],
                                  np.asarray(per_start(xs[b // 2], x)))
    # the Pallas kernel is bitwise the engine's assembly (prime N -> padded
    # tiles; jit-compiled executions, see kernels/ops.py)
    xp = x[:251]
    np.testing.assert_array_equal(
        np.asarray(gp_pallas(xp, xp, interpret=True, block=128)),
        np.asarray(jax.jit(kref.gp_matrix_ref)(xp, xp)))

    pairs_per_s = b * q * n / (us_fused / 1e6) / 1e9
    row(f"gp_covariance_{n}", us_fused,
        f"{us_loop / us_fused:.2f}x_vs_per_start_loop_jnp_ref_"
        f"{pairs_per_s:.2f}_Gpairs_per_s")


def bench_gp_chol(reduced=False):
    """Archive-scale GP factorization: the blocked fused assemble+factor
    engine (serial lengthscale sweep under ONE jit — vmapping the blocked
    program is pathological on CPU, see kernels/ops.py) vs the dense
    baseline every restart-loop GP fit runs: assemble the full (G, N, N)
    covariance stack and vmap ``jnp.linalg.cholesky`` over the grid.
    Bit-exactness of the Pallas kernel vs the jitted oracle is asserted
    in-bench at an interpret-mode shape (prime true size, padded tiles)."""
    from repro.kernels import ref as kref
    from repro.kernels.cholesky import gp_chol_blocked

    n, g, block = (256, 2, 128) if reduced else (4096, 5, 512)
    d, nugget = 8, 1e-4
    grid = (0.05, 0.1, 0.2, 0.4, 0.8)[:g]
    x = jax.random.uniform(jax.random.key(0), (n, d), jnp.float32)

    @jax.jit
    def blocked_sweep(x):
        return jnp.stack([
            kref.gp_chol_blocked_ref(x, n, kind="matern52", lengthscale=ls,
                                     nugget=nugget, block=block)
            for ls in grid])

    @jax.jit
    def lapack_sweep(x):
        d2 = kref.gp_sqdist_ref(x, x)
        ks = jnp.stack([kref.gp_kernel_fn("matern52", d2, ls, 1.0)
                        + nugget * jnp.eye(n, dtype=jnp.float32)
                        for ls in grid])
        return jnp.linalg.cholesky(ks)

    us_blk = timeit(lambda: jax.block_until_ready(blocked_sweep(x)),
                    warmup=1, iters=3)
    us_lap = timeit(lambda: jax.block_until_ready(lapack_sweep(x)),
                    warmup=1, iters=3)
    # same factor, different algorithm: agreement to float32 tolerance
    np.testing.assert_allclose(np.asarray(blocked_sweep(x)),
                               np.asarray(lapack_sweep(x)),
                               rtol=2e-4, atol=2e-4)
    # the Pallas kernel is bitwise the engine's oracle (interpret mode,
    # prime true size inside padded tiles, fused assembly path)
    ns, bs = 83, 64
    xs = jnp.zeros((128, d), jnp.float32).at[:ns].set(x[:ns])
    np.testing.assert_array_equal(
        np.asarray(gp_chol_blocked(xs, ns, kind="matern52", lengthscale=0.2,
                                   nugget=nugget, block=bs, interpret=True)),
        np.asarray(jax.jit(lambda xp: kref.gp_chol_blocked_ref(
            xp, ns, kind="matern52", lengthscale=0.2, nugget=nugget,
            block=bs))(xs)))
    speedup = float(us_lap) / float(us_blk)
    # regression floor, not the headline: steady-state on this idle
    # single-core host the fused blocked sweep measures ~1.3x (block=512;
    # block=256 is 4x slower — tile-dot dispatch overhead dominates); the
    # gap widens to 2-3x when the LAPACK path degrades under load (its
    # per-factor time was measured fluctuating 0.71-1.58s across
    # sessions), so a 2x hard assert would be a coin flip. The row
    # records the measured multiple; the assert catches the engine
    # falling back behind the baseline.
    if not reduced:
        assert speedup >= 1.15, (
            f"blocked factorization must beat the vmapped LAPACK grid "
            f"path at n={n} (got {speedup:.2f}x)")
    row(f"gp_chol_{n}", us_blk,
        f"{speedup:.2f}x_vs_vmapped_lapack_grid{g}_bit_exact_True")


def bench_surrogate_bigN(reduced=False):
    """The O(N^3) wall, measured end to end: a warm surrogate ask/tell
    round at archive-scale history through the inducing-point engine
    (``gp_fit(n_max_exact=...)`` routing + incremental rank-q ``tell``),
    plus the price of approximating — the regret of the inducing run vs
    the exact dense run from identical seeded history on a synthetic
    objective (exact is infeasible at the big N; the regret leg runs at a
    size where both paths fit)."""
    from repro.explore import SurrogateConfig, SurrogateExplorer

    n, q, d = (2048, 8, 2) if reduced else (50_000, 8, 2)

    def f(g):
        return np.asarray((g[:, 0] - 0.3) ** 2 + (g[:, 1] - 0.7) ** 2
                          + 0.01 * np.sin(17 * g[:, 0]), np.float32)

    def seeded(m, seed=0):
        rng = np.random.default_rng(seed)
        x = rng.random((m, d), np.float32).astype(np.float32)
        return x, f(x)

    cfg = SurrogateConfig(bounds=((0., 1.),) * d, q=q, n_init=16, seed=0,
                          n_max_exact=1024, n_inducing=256)
    ex = SurrogateExplorer(cfg)
    x0, y0 = seeded(n)
    ex.load_state_arrays({"x01": x0, "y": y0, "round": np.int32(n // q)})

    def one_round():
        xq = ex.ask()              # warm: incremental state, no refit
        ex.tell(xq, [float(v) for v in f(xq)])

    us = timeit(one_round, warmup=1, iters=3)   # warmup pays the cold fit
    if not reduced:
        assert us < 2e6, f"ask/tell round at N={n} must stay under 2s " \
                         f"(got {us / 1e6:.2f}s)"

    # regret leg: inducing vs exact from the same history, same budget
    n2, rounds = (256, 1) if reduced else (1536, 3)
    x2, y2 = seeded(n2, seed=1)
    bests = {}
    for tag, nme in (("exact", 4096), ("inducing", 512)):
        c = SurrogateConfig(bounds=((0., 1.),) * d, q=q, n_init=16, seed=0,
                            n_max_exact=nme, n_inducing=256)
        e2 = SurrogateExplorer(c)
        e2.load_state_arrays({"x01": x2.copy(), "y": y2.copy(),
                              "round": np.int32(n2 // q)})
        for _ in range(rounds):
            xq = e2.ask()
            e2.tell(xq, [float(v) for v in f(xq)])
        bests[tag] = float(e2.best[1])
    regret = bests["inducing"] - bests["exact"]
    row(f"surrogate_tell_{n // 1000}k", us,
        f"{q / (us / 1e6):.1f}_proposals_per_s_warm_round_n{n}_"
        f"regret_vs_exact_{regret:.2e}")


def bench_surrogate_ants(reduced=False):
    """Adaptive vs static DoE on the ants model: evaluations needed to
    reach the objective a median LHS run attains with its FULL budget.

    Baseline: LHS over several seeds (median final best = the target;
    median first-reach = the LHS evals-to-target, non-reachers counted as
    budget+1). Surrogate: one deterministic GP+q-EI run, Sobol-seeded.
    The fitness is the time to deplete the nearest food source (objective
    0, median of 3 replicates) — the landscape with real structure on the
    reduced config. Also times the warm ask() path (proposals/s)."""
    from repro.configs.ants_netlogo import BOUNDS
    from repro.core import Context, Val
    from repro.explore import (LHSSampling, SurrogateConfig,
                               SurrogateExplorer, run_surrogate)
    from repro.launch.explore import ants_scalar_eval

    budget, n_seeds, q, n_init = (24, 2, 4, 8) if reduced \
        else (96, 5, 8, 16)
    eval_fn = ants_scalar_eval(reduced=True, replicates=3, objective=0)
    jeval = jax.jit(eval_fn)

    dv, ev = Val("d", float), Val("e", float)
    finals, reaches = [], []
    trajs = []
    for seed in range(n_seeds):
        pts = list(LHSSampling({dv: BOUNDS[0], ev: BOUNDS[1]}, budget,
                               seed=seed).contexts(Context()))
        g = jnp.asarray([[p["d"], p["e"]] for p in pts], jnp.float32)
        keys = jax.vmap(lambda i: jax.random.fold_in(
            jax.random.key(1000 + seed), i))(jnp.arange(budget))
        y = np.asarray(jeval(keys, g))
        finals.append(float(y.min()))
        trajs.append(np.minimum.accumulate(y))
    target = float(np.median(finals))
    for traj in trajs:
        hit = np.nonzero(traj <= target)[0]
        reaches.append(int(hit[0]) + 1 if len(hit) else budget + 1)
    lhs_evals = int(np.median(reaches))

    cfg = SurrogateConfig(bounds=BOUNDS, q=q, n_init=n_init, seed=0)
    rounds = (budget - cfg.n_init_padded) // q + cfg.n_init_padded // q
    res = run_surrogate(cfg, eval_fn, rounds=rounds)
    hit = np.nonzero(res.objectives <= target)[0]
    surr_evals = int(hit[0]) + 1 if len(hit) else budget + 1
    # full shapes: enforce the claim. Reduced CI smoke shapes are too
    # marginal (tiny budget, 2 LHS seeds, noisy objective) to assert on a
    # foreign microarchitecture — there the row just records the numbers.
    if not reduced:
        assert surr_evals < lhs_evals, (
            f"surrogate must reach the LHS-budget target in fewer evals "
            f"(target {target}: surrogate {surr_evals}, lhs {lhs_evals})")

    row("surrogate_ants_evals_to_target", res.wall_s * 1e6 / budget,
        f"{surr_evals}_evals_vs_{lhs_evals}_lhs_evals_to_target_"
        f"{target:.0f}_best_{res.best_objective:.0f}")

    # warm proposals/s: the GP fit + q-EI multi-start ask on full history
    ex = SurrogateExplorer(cfg)
    ex.load_state_arrays({
        "x01": (np.asarray(res.genomes, np.float32) - ex._lo) / ex._span,
        "y": np.asarray(res.objectives, np.float32),
        "round": np.int32(res.rounds_done)})
    ex.ask()                                    # warm the jits
    us = timeit(lambda: ex.ask(), warmup=1, iters=3)
    row(f"surrogate_propose_q{q}", us,
        f"{q / (us / 1e6):.0f}_proposals_per_s_n{len(res.objectives)}")


def bench_lm_train_step(reduced=False):
    import dataclasses
    from repro.configs import get_config
    from repro.models import build
    from repro.train import OptimizerConfig, init_train_state, make_train_step
    cfg = dataclasses.replace(get_config("smollm-135m", reduced=True),
                              dtype="float32", use_flash_kernel=False)
    model = build(cfg)
    state, _ = init_train_state(model, jax.random.key(0))
    step = jax.jit(make_train_step(model, OptimizerConfig(), 1))
    b, s = (2, 32) if reduced else (4, 128)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (b, s + 1), 0,
                                          cfg.vocab_size)}

    def one():
        nonlocal state
        state, m = step(state, batch)
        jax.block_until_ready(m["loss"])

    us = timeit(one, warmup=1, iters=3)
    row("lm_train_step_reduced", us,
        f"{b * s / (us / 1e6):.0f}_tokens_per_s_single_CPU_core")


def bench_bandit_router(reduced=False):
    """Bandit-allocated serving: requests/s through the UCB router over
    three competing arms (greedy / temperature / int8) vs the no-router
    baseline — the same request stream pinned directly to the oracle arm
    (best fixed arm in hindsight, i.e. the arm the router converges to;
    pinning a DIFFERENT arm would conflate router overhead with the
    arms' own compute differences, which the reward already prices).
    Full shapes assert router throughput >= 0.9x direct and sublinear
    regret (second-half per-request regret below first-half). Both
    passes are median-of-3: each is only a fraction of a second of wall
    clock, too noisy for a single-shot ratio."""
    import numpy as np
    from repro.launch.bandit_serve import make_arm_set
    from repro.serve import BanditConfig, BanditRouter, token_diversity

    requests, b, s, new = (10, 2, 8, 8) if reduced else (64, 4, 16, 24)
    cfg, arms, _spawn = make_arm_set("smollm-135m", reduced=True,
                                     new_tokens=new)

    def prompts_at(req):
        rng = np.random.default_rng((7 << 20) + req)
        return rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)

    key = jax.random.key(7)
    for a in arms:                       # compile every arm outside timing
        a.generate_fn(prompts_at(0), key)

    router = None

    def routed_pass():
        nonlocal router
        for a in arms:
            a.stats = type(a.stats)()    # fresh bandit state per repeat
        router = BanditRouter(arms, BanditConfig(policy="ucb", ucb_c=0.5,
                                                 seed=7),
                              quality_fn=token_diversity)
        for r in range(requests):
            router.route(prompts_at(r))

    router_us = timeit(routed_pass, warmup=1, iters=3)
    oracle = next(a for a in arms if a.name == router.oracle_arm())

    def direct_pass():
        for r in range(requests):
            oracle.generate_fn(prompts_at(r), jax.random.fold_in(key, r))

    direct_us = timeit(direct_pass, warmup=1, iters=3)
    rps = requests / (float(router_us) / 1e6)
    ratio = float(direct_us) / float(router_us)

    regret = router.regret_curve()
    h = len(regret) // 2
    first = float(regret[h - 1]) / h
    second = float(regret[-1] - regret[h - 1]) / (len(regret) - h)
    if not reduced:
        assert ratio >= 0.9, f"router {ratio:.3f}x direct (< 0.9x)"
        assert second < first, (
            f"regret not sublinear: {second:.4f}/req second half vs "
            f"{first:.4f}/req first half")
    row("bandit_router_throughput", router_us.scaled(1 / requests),
        f"{rps:.1f}_req_per_s_{ratio:.2f}x_vs_direct_oracle",
        regret={"cumulative": round(float(regret[-1]), 4),
                "per_request_first_half": round(first, 4),
                "per_request_second_half": round(second, 4),
                "oracle_arm": router.oracle_arm()})


BENCHES = [
    bench_ants_tick,
    bench_ants_eval_throughput,
    bench_island_epoch,
    bench_island_scaling,
    bench_nsga2_dominance,
    bench_nsga2_generation,
    bench_workflow_submit,
    bench_replication_median,
    bench_egi_200k_init,
    bench_egi_device_scaling,
    bench_service_two_tenant,
    bench_gp_covariance,
    bench_gp_chol,
    bench_surrogate_bigN,
    bench_surrogate_ants,
    bench_lm_train_step,
    bench_bandit_router,
]


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _git_dirty() -> bool:
    """True when the working tree differs from git_sha — without this flag
    a BENCH_results.json committed alongside its own generating change
    carries the PRE-commit sha with no way to tell (the provenance hole
    this fixes)."""
    try:
        out = subprocess.run(
            ["git", "status", "--porcelain"], capture_output=True,
            text=True, cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10)
        return bool(out.stdout.strip()) if out.returncode == 0 else True
    except Exception:
        return True


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reduced", action="store_true",
                    help="CI smoke shapes (small N, CPU interpret friendly)")
    ap.add_argument("--only", default="",
                    help="substring filter on benchmark function names")
    ap.add_argument("--json", default="",
                    help="also write machine-readable results to this path")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    for bench in BENCHES:
        if args.only and args.only not in bench.__name__:
            continue
        bench(reduced=args.reduced)

    if args.json:
        payload = {
            "schema": "repro-bench/v2",
            "backend": jax.default_backend(),
            "device_count": len(jax.devices()),
            "git_sha": _git_sha(),
            "dirty": _git_dirty(),
            "reduced": bool(args.reduced),
            "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "benchmarks": RESULTS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"[bench] wrote {args.json} ({len(RESULTS)} entries)",
              file=sys.stderr)


if __name__ == "__main__":
    main()
