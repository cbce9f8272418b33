"""Chaos suite: the fault-tolerant environment layer under injected
failures, hangs, and corruption (ISSUE 4 tentpole).

Every test drives a workload through deterministic fault injection
(core/faults.FaultSpec) and asserts the two paper-critical properties:
(1) the workload completes **bit-exact** vs. its failure-free run — retry,
resubmission, speculation and work stealing may change *where* and *when*
pure jobs run, never what they return; and (2) provenance counts the
retries/speculation that actually happened.

Injected hangs are bounded (hang_s a few seconds, interruptible) so this
suite can never wedge even without pytest-timeout; CI additionally runs it
under ``--timeout`` as a belt-and-braces guard.
"""
import time

import numpy as np
import pytest

from repro.core import (Capsule, Context, EnvironmentPool, FaultSpec,
                        JaxTask, LocalEnvironment, PyTask, TaskError, Val,
                        puzzle)
from repro.core.faults import corrupt_output

x = Val("x", float)
y = Val("y", float)

SQ = PyTask("sq", lambda ctx: {"y": ctx["x"] ** 2}, inputs=(x,),
            outputs=(y,))


def make_pool(*envs, **kw):
    kw.setdefault("backoff_s", 0.0)
    return EnvironmentPool(list(envs), **kw)


# ---------------------------------------------------------------------------
# FaultSpec determinism
# ---------------------------------------------------------------------------
def test_fault_decisions_are_deterministic():
    spec = FaultSpec(fail_rate=0.5, fail_limit=None, seed=3)
    first = [spec.decide("job", a) for a in range(32)]
    again = [spec.decide("job", a) for a in range(32)]
    assert first == again
    assert set(first) <= {"ok", "fail"}
    assert "fail" in first and "ok" in first      # rate 0.5 hits both


def test_fault_rates_roughly_respected():
    spec = FaultSpec(fail_rate=0.3, fail_limit=None, seed=0)
    fails = sum(spec.decide(f"job{i}", 0) == "fail" for i in range(2000))
    assert 0.25 < fails / 2000 < 0.35


def test_corrupt_output_changes_fingerprint():
    from repro.core.cache import hash_context
    out = Context(y=4.0)
    assert hash_context(corrupt_output(out)) != hash_context(out)
    arr = Context(objectives=np.arange(6.0).reshape(2, 3))
    assert hash_context(corrupt_output(arr)) != hash_context(arr)


# ---------------------------------------------------------------------------
# single environment: fail-once / fail-always / hang / corrupt
# ---------------------------------------------------------------------------
def test_fail_once_retries_and_matches_clean_run():
    clean = LocalEnvironment().submit(SQ, Context(x=3.0))
    env = LocalEnvironment(retries=3, backoff_s=0.0,
                           faults=FaultSpec(fail_rate=1.0, fail_limit=1))
    out, meta = env.submit_traced(SQ, Context(x=3.0))
    assert out["y"] == clean["y"] == 9.0
    assert meta["retries"] == 1
    assert [a["outcome"] for a in meta["attempts"]] == ["fail", "ok"]
    assert env.stats.failed == 1 and env.stats.retried == 1


def test_fail_always_exhausts_retries():
    env = LocalEnvironment(retries=2, backoff_s=0.0,
                           faults=FaultSpec(fail_rate=1.0, fail_limit=None))
    with pytest.raises(RuntimeError, match="failed after 3 attempts"):
        env.submit(SQ, Context(x=3.0))
    assert env.stats.failed == 3


def test_hang_past_timeout_is_detected_and_resubmitted():
    env = LocalEnvironment(
        retries=3, backoff_s=0.0, timeout_s=0.15,
        faults=FaultSpec(hang_rate=1.0, hang_limit=1, hang_s=5.0))
    t0 = time.monotonic()
    out, meta = env.submit_traced(SQ, Context(x=4.0))
    wall = time.monotonic() - t0
    env.release_hangs()
    assert out["y"] == 16.0
    assert wall < 5.0, "resubmission must beat the injected hang"
    assert [a["outcome"] for a in meta["attempts"]] == ["hang", "ok"]
    assert env.stats.hung == 1


def test_corrupt_result_detected_by_fingerprint_and_retried():
    env = LocalEnvironment(
        retries=3, backoff_s=0.0,
        faults=FaultSpec(corrupt_rate=1.0, corrupt_limit=1))
    out, meta = env.submit_traced(SQ, Context(x=5.0))
    assert out["y"] == 25.0
    assert [a["outcome"] for a in meta["attempts"]] == ["corrupt", "ok"]
    assert env.stats.corrupted == 1


def test_declaration_bugs_never_retry_under_faults():
    bad = PyTask("bad", lambda ctx: {}, outputs=(y,))
    env = LocalEnvironment(retries=5, backoff_s=0.0,
                           faults=FaultSpec(fail_rate=0.0))
    with pytest.raises(TaskError, match="missing outputs"):
        env.submit(bad, Context())
    assert env.stats.retried == 0


# ---------------------------------------------------------------------------
# pool: resubmission, balancing, speculation, work stealing
# ---------------------------------------------------------------------------
def test_pool_routes_around_fail_always_member():
    bad = LocalEnvironment(name="bad", capacity=2,
                           faults=FaultSpec(fail_rate=1.0, fail_limit=None))
    good = LocalEnvironment(name="good", capacity=2)
    pool = make_pool(bad, good, retries=4)
    out, meta = pool.submit_traced(SQ, Context(x=6.0))
    assert out["y"] == 36.0
    envs = [(a["environment"], a["outcome"]) for a in meta["attempts"]]
    assert ("good", "ok") in envs
    assert all(o == "fail" for e, o in envs if e == "bad")
    assert pool.stats.resubmissions == sum(o != "ok" for _, o in envs)
    assert_member_invariant(pool)
    pool.shutdown()


def test_pool_map_explore_bit_exact_under_30pct_failures():
    ctxs = [Context(x=float(i)) for i in range(48)]
    ref = [c["y"] for c in LocalEnvironment().map_explore(SQ, ctxs)]
    envs = [LocalEnvironment(name=f"w{i}", capacity=2,
                             faults=FaultSpec(fail_rate=0.3, seed=i))
            for i in range(2)] + [LocalEnvironment(name="stable", capacity=2)]
    pool = make_pool(*envs, retries=6, lane_size=4)
    got = [c["y"] for c in pool.map_explore(SQ, ctxs)]
    assert got == ref
    assert pool.stats.completed == len(ctxs)
    assert_member_invariant(pool)
    pool.shutdown()


def test_pool_work_stealing_drains_slow_member():
    slow = LocalEnvironment(name="slow", capacity=1, latency_s=0.25)
    fast = LocalEnvironment(name="fast", capacity=4)
    pool = make_pool(slow, fast, lane_size=2)
    ctxs = [Context(x=float(i)) for i in range(24)]
    t0 = time.monotonic()
    got = [c["y"] for c in pool.map_explore(SQ, ctxs)]
    wall = time.monotonic() - t0
    assert got == [i ** 2 for i in range(24)]
    # static partition would leave slow ~1/5 of 12 lanes at 2x0.25s each;
    # stealing must shift nearly all of them to the idle fast member
    assert pool.stats.lanes_stolen >= 1
    assert wall < 2.0
    pool.shutdown()


def test_pool_speculative_duplicate_first_result_wins():
    hang = LocalEnvironment(
        name="hangs", capacity=1,
        faults=FaultSpec(hang_rate=1.0, hang_limit=None, hang_s=3.0))
    fast = LocalEnvironment(name="fast", capacity=2)
    pool = make_pool(hang, fast, retries=4, lane_size=4, speculative=2)
    ctxs = [Context(x=float(i)) for i in range(16)]
    t0 = time.monotonic()
    got = [c["y"] for c in pool.map_explore(SQ, ctxs)]
    wall = time.monotonic() - t0
    assert got == [i ** 2 for i in range(16)]
    assert wall < 3.0, "speculation must beat the injected hang"
    assert pool.stats.speculative_wins >= 1
    pool.shutdown()


def test_pool_hang_member_with_timeout_on_submit_path():
    hang = LocalEnvironment(
        name="hangs", capacity=1, timeout_s=0.1,
        faults=FaultSpec(hang_rate=1.0, hang_limit=None, hang_s=4.0))
    fast = LocalEnvironment(name="fast", capacity=2)
    pool = make_pool(hang, fast, retries=4)
    t0 = time.monotonic()
    outs, metas = [], []
    for i in range(4):
        out, meta = pool.submit_traced(SQ, Context(x=float(i)))
        outs.append(out["y"])
        metas.append(meta)
    wall = time.monotonic() - t0
    assert outs == [0.0, 1.0, 4.0, 9.0]
    assert wall < 4.0, "hang detection must beat the injected hang"
    hangs = sum(1 for m in metas for a in m["attempts"]
                if a["outcome"] == "hang")
    assert pool.stats.hung_attempts == hangs
    # every job ultimately completed on the healthy member
    for m in metas:
        assert m["attempts"][-1]["environment"] == "fast"
        assert m["attempts"][-1]["outcome"] == "ok"
    pool.shutdown()


def test_pool_speculative_submit_returns_on_first_result():
    """The winner must return IMMEDIATELY — a hung duplicate may not delay
    the job it was duplicated to protect (regression: _race used to join
    every copy before returning)."""
    hang = LocalEnvironment(
        name="hangs", capacity=2,
        faults=FaultSpec(hang_rate=1.0, hang_limit=None, hang_s=3.0))
    fast = LocalEnvironment(name="fast", capacity=2)
    pool = make_pool(hang, fast, retries=2, speculative=2)
    t0 = time.monotonic()
    out, meta = pool.submit_traced(SQ, Context(x=8.0))
    wall = time.monotonic() - t0
    assert out["y"] == 64.0
    assert meta["speculative"] is True
    assert wall < 2.0, "first verified result must win without joining " \
                       "the hung duplicate"
    pool.shutdown()


def test_single_env_speculation_records_attempts():
    env = LocalEnvironment(speculative=3)
    out, meta = env.submit_traced(SQ, Context(x=3.0))
    assert out["y"] == 9.0
    assert meta["speculative"] is True
    assert meta["attempts"] and any(
        a["outcome"] == "ok" for a in meta["attempts"])


def test_pool_corruption_is_resubmitted_elsewhere():
    evil = LocalEnvironment(
        name="evil", capacity=2,
        faults=FaultSpec(corrupt_rate=1.0, corrupt_limit=None))
    good = LocalEnvironment(name="good", capacity=2)
    pool = make_pool(evil, good, retries=4)
    out, meta = pool.submit_traced(SQ, Context(x=7.0))
    assert out["y"] == 49.0
    outcomes = {a["environment"]: a["outcome"] for a in meta["attempts"]}
    assert outcomes.get("good") == "ok"
    assert pool.stats.corrupt_attempts == sum(
        1 for a in meta["attempts"] if a["outcome"] == "corrupt")
    pool.shutdown()


def test_pool_single_member_equals_bare_environment():
    """No faults, one member: the pool is a transparent wrapper."""
    ctxs = [Context(x=float(i)) for i in range(10)]
    ref = [c["y"] for c in LocalEnvironment().map_explore(SQ, ctxs)]
    pool = make_pool(LocalEnvironment())
    assert [c["y"] for c in pool.map_explore(SQ, ctxs)] == ref
    out, meta = pool.submit_traced(SQ, Context(x=3.0))
    assert out["y"] == 9.0 and meta["retries"] == 0
    pool.shutdown()


# ---------------------------------------------------------------------------
# balancer accounting regressions (ISSUE 10 satellites)
# ---------------------------------------------------------------------------
def assert_member_invariant(pool):
    """Per-member provenance must balance: every attempt that was submitted
    ended as exactly one of completed/failed/hung/corrupted."""
    for name, s in pool.member_stats().items():
        assert s["submitted"] == (s["completed"] + s["failed"]
                                  + s["hung"] + s["corrupted"]), \
            f"member {name} attempt accounting is out of balance: {s}"


class _BrokenBatch(LocalEnvironment):
    """Fault-free (faults=None) member whose batched lane path always
    raises — the shape of a device member with a broken runtime."""

    def map_explore(self, task, contexts):
        raise RuntimeError("injected batched-lane failure")


def test_failed_batched_lane_not_credited_toward_drain_rate():
    """Regression: the batched-jax lane path used to bump ``m.completed``
    in a ``finally``, so a member whose batch RAISED was still credited —
    inflating drain_rate() and steering the balancer toward the broken
    member. A failing member's drain rate must never exceed a healthy
    one's."""
    sq_jax = JaxTask("sqj", lambda x: {"y": x * x}, inputs=(x,),
                     outputs=(y,))
    broken = _BrokenBatch(name="broken", capacity=2)
    good = LocalEnvironment(name="good", capacity=2)
    pool = make_pool(broken, good, retries=6, lane_size=4)
    ctxs = [Context(x=float(i)) for i in range(16)]
    got = [c["y"] for c in pool.map_explore(sq_jax, ctxs)]
    assert got == [float(i) ** 2 for i in range(16)]
    b = next(m for m in pool.members if m.name == "broken")
    g = next(m for m in pool.members if m.name == "good")
    assert b.completed == 0, "a raised batch must not count as completed"
    assert b.busy_s > 0.0, "the failed batches did consume the member"
    assert b.drain_rate() <= g.drain_rate()
    pool.shutdown()


def test_map_explore_taskerror_releases_lane_running_slot():
    """Regression: run_lane's TaskError early-return skipped the
    ``lane_running`` decrement, leaking the counter that gates speculative
    duplication. Every exit path must release the slot."""
    bad = PyTask("bad", lambda ctx: {}, inputs=(x,), outputs=(y,))
    pool = make_pool(LocalEnvironment(name="solo", capacity=1),
                     lane_size=4, speculative=2)
    with pytest.raises(TaskError, match="missing outputs"):
        pool.map_explore(bad, [Context(x=float(i)) for i in range(4)])
    # one member / one slot / one lane: the aborting run_lane is the only
    # writer, so the counter state after the raise is deterministic
    assert pool._debug_lane_running == [0]
    pool.shutdown()


def test_member_stats_count_failed_attempts_as_submitted():
    """Regression: ``_attempt_on`` bumped submitted/completed only on
    success, so failed attempts vanished from per-member provenance and
    ``submitted == completed + failed + hung + corrupted`` never held on
    a flaky member."""
    flaky = LocalEnvironment(name="flaky", capacity=2,
                             faults=FaultSpec(fail_rate=1.0, fail_limit=2))
    stable = LocalEnvironment(name="stable", capacity=2)
    pool = make_pool(flaky, stable, retries=6)
    for i in range(6):
        assert pool.submit(SQ, Context(x=float(i)))["y"] == float(i) ** 2
    ms = pool.member_stats()
    fs = ms["flaky"]
    assert fs["failed"] > 0
    assert fs["submitted"] == fs["completed"] + fs["failed"], \
        "failed attempts must count as submitted"
    assert_member_invariant(pool)
    pool.shutdown()


# ---------------------------------------------------------------------------
# scheduler-level: whole workflows on a chaotic pool
# ---------------------------------------------------------------------------
def _exploration_workflow():
    from repro.core import aggregate, explore
    from repro.explore import GridSampling, StatisticTask, median
    z = Val("z", float)
    head = Capsule(PyTask("head", lambda ctx: {}))
    sq_c = Capsule(SQ)
    med_c = Capsule(StatisticTask("med", [(y, z, median)]))
    wf = (puzzle(head)
          >> explore(GridSampling({x: [float(i) for i in range(1, 10)]}))
          >> sq_c >> aggregate() >> med_c)
    return wf, med_c


def test_workflow_on_chaotic_pool_bit_exact_with_provenance():
    wf, med_c = _exploration_workflow()
    ref = wf.run(environment=LocalEnvironment())
    ref_z = ref[med_c][0]["z"]

    wf2, med2 = _exploration_workflow()
    pool = make_pool(
        LocalEnvironment(name="flaky", capacity=2,
                         faults=FaultSpec(fail_rate=0.5, fail_limit=2,
                                          seed=11)),
        LocalEnvironment(name="stable", capacity=2),
        retries=6)
    res = wf2.run(environment=pool)
    assert res[med2][0]["z"] == ref_z == 25.0
    rec = wf2.workflow.last_record
    # provenance: per-attempt traces are present and every retry that the
    # pool performed is visible as a non-ok attempt
    n_bad = sum(1 for t in rec.tasks for a in (t.attempts or ())
                if a["outcome"] != "ok")
    n_retries = sum(t.retries for t in rec.tasks)
    assert n_bad == n_retries
    for t in rec.tasks:
        assert t.attempts, "pool firings must carry per-attempt records"
        assert t.attempts[-1]["outcome"] == "ok"
    pool.shutdown()


def test_workflow_serial_path_untouched_by_pool_changes():
    """The serial reference scheduler on a plain environment stays the
    bit-exact baseline (regression guard for the tentpole refactor)."""
    wf, med_c = _exploration_workflow()
    serial = wf.run(environment=LocalEnvironment(), scheduler="serial")
    wf2, med2 = _exploration_workflow()
    asynch = wf2.run(environment=LocalEnvironment(), scheduler="async")
    assert serial[med_c][0]["z"] == asynch[med2][0]["z"]


# ---------------------------------------------------------------------------
# streaming 200k-style init: chaos + checkpoint/resume (reduced shapes)
# ---------------------------------------------------------------------------
def _stream_setup():
    import jax
    import jax.numpy as jnp

    from repro.evolution import NSGA2Config

    cfg = NSGA2Config(mu=8, genome_dim=2, bounds=((0., 100.), (0., 100.)),
                      n_objectives=3)

    def eval_fn(keys, genomes):
        noise = jax.vmap(lambda k: jax.random.normal(k, (3,)))(keys)
        d, e = genomes[:, 0], genomes[:, 1]
        return jnp.stack([(d - 30.) ** 2, jnp.abs(d - e), d + e], 1) + noise

    return cfg, eval_fn


def test_streaming_init_bit_exact_under_failures_hangs_and_corruption():
    from repro.evolution import ga
    cfg, eval_fn = _stream_setup()
    clean = ga.evaluate_population_streaming(cfg, eval_fn, 0, n_total=600,
                                             chunk=100)
    pool = make_pool(
        LocalEnvironment(name="fails", capacity=2,
                         faults=FaultSpec(fail_rate=0.4, seed=1)),
        LocalEnvironment(name="corrupts", capacity=2,
                         faults=FaultSpec(corrupt_rate=0.4,
                                          corrupt_limit=None, seed=2)),
        LocalEnvironment(name="stable", capacity=2),
        retries=8)
    chaos = ga.evaluate_population_streaming(cfg, eval_fn, 0, n_total=600,
                                             chunk=100, environment=pool)
    assert np.array_equal(clean.objectives, chaos.objectives)
    assert np.array_equal(clean.genomes, chaos.genomes)
    assert chaos.attempts >= chaos.chunks_total
    assert_member_invariant(pool)
    pool.shutdown()


def test_streaming_init_resumes_mid_population(tmp_path):
    from repro.evolution import ga
    cfg, eval_fn = _stream_setup()
    ckpt = str(tmp_path / "init")
    clean = ga.evaluate_population_streaming(cfg, eval_fn, 0, n_total=640,
                                             chunk=64)
    part = ga.evaluate_population_streaming(
        cfg, eval_fn, 0, n_total=640, chunk=64, checkpoint_dir=ckpt,
        stop_after_chunks=5)
    assert part.interrupted and part.objectives is None
    assert part.chunks_done == 5
    from repro.core.scheduler import RunRecord, _utcnow
    rec = RunRecord(workflow="resume", scheduler="stream",
                    environment="inline", started_at=_utcnow())
    full = ga.evaluate_population_streaming(
        cfg, eval_fn, 0, n_total=640, chunk=64, checkpoint_dir=ckpt,
        record=rec)
    assert not full.interrupted
    assert full.resumed_chunks == 5
    assert np.array_equal(clean.objectives, full.objectives)
    # provenance: resumed chunks appear as cache hits, the rest as streams
    modes = [t.mode for t in rec.tasks]
    assert modes.count("cache") == 5 and modes.count("stream") == 5


def test_streaming_init_resumes_under_chaos(tmp_path):
    """Kill and resume through the drivers' pool at 35 % injected job
    failures: the resumed population is the failure-free one."""
    from repro.evolution import ga
    from repro.launch.explore import make_init_pool
    cfg, eval_fn = _stream_setup()
    clean = ga.evaluate_population_streaming(cfg, eval_fn, 0, n_total=640,
                                             chunk=64)
    ckpt = str(tmp_path / "init")

    def run(**kw):
        pool = make_init_pool(0.35, backoff_s=0.01, retries=12)
        try:
            return ga.evaluate_population_streaming(
                cfg, eval_fn, 0, n_total=640, chunk=64, environment=pool,
                checkpoint_dir=ckpt, **kw)
        finally:
            pool.shutdown()

    part = run(stop_after_chunks=5)
    assert part.interrupted and part.chunks_done >= 5
    full = run()
    assert not full.interrupted and full.resumed_chunks > 0
    assert np.array_equal(clean.objectives, full.objectives)
    assert np.array_equal(clean.genomes, full.genomes)


def test_streaming_init_seeds_ga_state():
    import jax
    from repro.evolution import ga
    cfg, eval_fn = _stream_setup()
    res = ga.evaluate_population_streaming(cfg, eval_fn, 0, n_total=256,
                                           chunk=64)
    state = ga.init_state_from_population(cfg, jax.random.key(1),
                                          res.genomes, res.objectives)
    assert state.genomes.shape == (cfg.mu, cfg.genome_dim)
    assert bool(state.valid.all())
    assert int(state.evaluations) == 256
    # the selected mu must all come from the evaluated population
    pop = {tuple(g) for g in np.asarray(res.genomes).round(6).tolist()}
    sel = {tuple(g) for g in np.asarray(state.genomes).round(6).tolist()}
    assert sel <= pop


# ---------------------------------------------------------------------------
# surrogate ask/tell loop: chaos + checkpoint/resume (ISSUE 5)
# ---------------------------------------------------------------------------
def _surrogate_setup():
    # the shared tiny config/fitness (tests/conftest.py): equal configs
    # hash alike, so the per-config jit cache is shared across the
    # surrogate, chaos, and golden suites in one process
    from conftest import surrogate_quadratic, surrogate_tiny_config
    return surrogate_tiny_config(), surrogate_quadratic


@pytest.mark.slow
def test_surrogate_ask_tell_bit_exact_at_35pct_chaos():
    """The adaptive loop through a 35%-fault pool (fail + hang + corrupt
    members) must be bit-identical to the failure-free serial run: the
    OSPREY-style re-prioritization may reorder dispatch, never results."""
    from repro.explore.surrogate import run_surrogate
    cfg, eval_fn = _surrogate_setup()
    clean = run_surrogate(cfg, eval_fn, rounds=5)
    pool = make_pool(
        LocalEnvironment(name="fails", capacity=1,
                         faults=FaultSpec(fail_rate=0.35, seed=1)),
        LocalEnvironment(name="hangs", capacity=1, timeout_s=0.2,
                         faults=FaultSpec(hang_rate=0.35, hang_s=3.0,
                                          hang_limit=None, seed=2)),
        LocalEnvironment(name="corrupts", capacity=1,
                         faults=FaultSpec(corrupt_rate=0.35,
                                          corrupt_limit=None, seed=3)),
        retries=8)
    chaos = run_surrogate(cfg, eval_fn, rounds=5, environment=pool,
                          max_inflight=2)
    pool.shutdown()
    assert not chaos.interrupted
    assert np.array_equal(clean.objectives, chaos.objectives)
    assert np.array_equal(clean.genomes, chaos.genomes)
    assert chaos.best_objective == clean.best_objective
    # faults actually fired: more attempts than evaluations
    assert chaos.attempts > 5 * cfg.q


@pytest.mark.slow
def test_surrogate_resumes_from_mid_run_checkpoint_under_chaos(tmp_path):
    """Kill the loop mid-run, resume it on a 35%-fault pool: the resumed
    trajectory must continue exactly where the straight run would be."""
    from repro.core.scheduler import RunRecord, _utcnow
    from repro.explore.surrogate import run_surrogate
    cfg, eval_fn = _surrogate_setup()
    ckpt = str(tmp_path / "surrogate")
    straight = run_surrogate(cfg, eval_fn, rounds=5)
    part = run_surrogate(cfg, eval_fn, rounds=5, checkpoint_dir=ckpt,
                         stop_after_rounds=3)
    assert part.interrupted and part.rounds_done == 3
    pool = make_pool(
        LocalEnvironment(name="flaky", capacity=2,
                         faults=FaultSpec(fail_rate=0.35, seed=5)),
        LocalEnvironment(name="stable", capacity=2),
        retries=8)
    rec = RunRecord(workflow="surrogate-resume", scheduler="ask-tell",
                    environment="pool", started_at=_utcnow())
    full = run_surrogate(cfg, eval_fn, rounds=5, environment=pool,
                         checkpoint_dir=ckpt, record=rec)
    pool.shutdown()
    assert not full.interrupted and full.resumed_rounds == 3
    assert np.array_equal(straight.objectives, full.objectives)
    assert np.array_equal(straight.genomes, full.genomes)
    # provenance: resumed rounds appear as cache hits, live ones as
    # surrogate firings with per-attempt traces
    modes = [t.mode for t in rec.tasks]
    assert modes.count("cache") == 3 * cfg.q
    assert modes.count("surrogate") == 2 * cfg.q
    live = [t for t in rec.tasks if t.mode == "surrogate"]
    assert all(t.attempts for t in live)


@pytest.mark.slow
def test_surrogate_reprioritizes_pending_candidates_under_chaos():
    """With a dispatch window smaller than the batch, arrivals re-score the
    queued candidates (OSPREY-style) — and that reordering must still never
    change what gets evaluated."""
    from repro.explore.surrogate import run_surrogate
    cfg, eval_fn = _surrogate_setup()
    clean = run_surrogate(cfg, eval_fn, rounds=5)
    pool = make_pool(
        LocalEnvironment(name="w0", capacity=1,
                         faults=FaultSpec(fail_rate=0.35, seed=7)),
        LocalEnvironment(name="w1", capacity=1,
                         faults=FaultSpec(fail_rate=0.35, seed=8)),
        retries=8)
    chaos = run_surrogate(cfg, eval_fn, rounds=5, environment=pool,
                          max_inflight=1)
    pool.shutdown()
    assert chaos.repriorities >= 1
    assert np.array_equal(clean.objectives, chaos.objectives)
