"""Device-set pool members (ISSUE 10 tentpole).

Proves the three guarantees DeviceEnvironment must give before the pool
may scale the paper's 200k streaming init over device subsets:

- **placement**: host-side attempts pin to the member's own devices
  (thread-local ``jax.default_device`` round-robin) and batched JaxTask
  lanes are explicitly placed on the member's subset — read back from
  the output arrays' sharding, not inferred;
- **bit-identity**: the ``egi`` streaming init through 1/2/4 device-set
  members is byte-identical to the inline run AND to the existing
  thread-backed ``make_init_pool`` path, at any forced device count (the
  count is fixed at jax import, hence one subprocess per count);
- **chaos**: a 35%-fault pool over 2 device members stays bit-exact and
  keeps the per-member attempt accounting balanced.

The CI ``multidevice`` job re-runs this module under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so the in-process
tests exercise real multi-device placement, not just subprocesses.
"""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core import Context, DeviceEnvironment, EnvironmentPool, \
    JaxTask, PyTask, Val, make_device_members

_REPO = os.path.join(os.path.dirname(__file__), "..")

x = Val("x", float)
y = Val("y", float)


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


# ---------------------------------------------------------------------------
# partitioning
# ---------------------------------------------------------------------------
def test_make_device_members_partitions_disjointly():
    devs = jax.local_devices()
    k = min(2, len(devs))
    members = make_device_members(None, k)
    assert len(members) == k
    ids = [d.id for m in members for d in m.devices]
    assert sorted(ids) == sorted(d.id for d in devs)   # disjoint cover
    assert len(set(m.name for m in members)) == k      # distinguishable
    for m in members:
        assert m.capacity == 2 * len(m.devices)
    with pytest.raises(ValueError):
        make_device_members(devs, len(devs) + 1)
    with pytest.raises(ValueError):
        make_device_members(devs, 0)


def test_make_device_members_accepts_mesh_and_explicit_devices():
    devs = jax.local_devices()
    members = make_device_members(devs, 1)
    assert [d.id for d in members[0].devices] == [d.id for d in devs]
    if len(devs) > 1:
        from repro.launch.mesh import compat_make_mesh
        mesh = compat_make_mesh((len(devs),), ("data",))
        members = make_device_members(mesh, len(devs))
        assert all(len(m.devices) == 1 for m in members)


# ---------------------------------------------------------------------------
# placement (meaningful on >1 device: the CI multidevice job)
# ---------------------------------------------------------------------------
@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
def test_py_attempts_pin_to_member_devices():
    """A PyTask's jax ops must land on the member's own device, not the
    process default (device 0)."""
    target = jax.local_devices()[1]

    def fn(ctx):
        import jax.numpy as jnp
        arr = jnp.asarray(ctx["x"]) * 2.0      # uncommitted -> default dev
        return {"y": float(arr),
                "dev": float(next(iter(arr.devices())).id)}

    probe = PyTask("probe", fn, inputs=(x,),
                   outputs=(y, Val("dev", float)))
    env = DeviceEnvironment([target])
    for i in range(3):
        out = env.submit(probe, Context(x=float(i)))
        assert out["y"] == 2.0 * i
        assert out["dev"] == float(target.id)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >= 4 devices")
def test_batched_lanes_land_on_member_device_subsets():
    """Each member's batched map_explore places its lanes on exactly its
    own device subset (read back from the output sharding)."""
    sqj = JaxTask("sqj", lambda x: {"y": x * x}, inputs=(x,), outputs=(y,))
    members = make_device_members(None, 2)
    ctxs = [Context(x=float(i)) for i in range(8)]
    for m in members:
        outs = m.map_explore(sqj, ctxs)
        assert [float(c["y"]) for c in outs] == [float(i) ** 2
                                                 for i in range(8)]
        assert m.last_lane_devices == tuple(sorted(d.id for d in m.devices))
    # the two members used disjoint silicon
    assert not (set(members[0].last_lane_devices)
                & set(members[1].last_lane_devices))
    # ragged lane count: single-device fallback stays on member devices
    m = members[0]
    m.map_explore(sqj, [Context(x=float(i)) for i in range(5)])
    assert len(m.last_lane_devices) == 1
    assert m.last_lane_devices[0] in {d.id for d in m.devices}


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >= 4 devices")
def test_pool_lane_fast_path_dispatches_to_member_devices():
    """Through the pool's batched-lane fast path, whichever member runs a
    lane places it on its OWN subset — never on another member's."""
    sqj = JaxTask("sqj", lambda x: {"y": x * x}, inputs=(x,), outputs=(y,))
    members = make_device_members(None, 2)
    pool = EnvironmentPool(members, backoff_s=0.0, lane_size=8)
    ctxs = [Context(x=float(i)) for i in range(32)]
    got = [float(c["y"]) for c in pool.map_explore(sqj, ctxs)]
    assert got == [float(i) ** 2 for i in range(32)]
    for m in members:
        if m.last_lane_devices is not None:    # this member ran >= 1 batch
            assert set(m.last_lane_devices) <= {d.id for d in m.devices}
    pool.shutdown()


# ---------------------------------------------------------------------------
# streaming-init bit-identity (subprocess: forced device counts)
# ---------------------------------------------------------------------------
_STREAM_DIGESTS = """
    import hashlib, json
    import jax, jax.numpy as jnp, numpy as np
    from repro.evolution import ga, NSGA2Config
    from repro.launch.explore import make_init_pool

    cfg = NSGA2Config(mu=8, genome_dim=2, bounds=((0., 100.), (0., 100.)),
                      n_objectives=3)

    def eval_fn(keys, genomes):
        noise = jax.vmap(lambda k: jax.random.normal(k, (3,)))(keys)
        d, e = genomes[:, 0], genomes[:, 1]
        return jnp.stack([(d - 30.) ** 2, jnp.abs(d - e), d + e], 1) + noise

    def digest(res):
        return hashlib.sha256(np.asarray(res.objectives).tobytes()
                              + np.asarray(res.genomes).tobytes()).hexdigest()

    out = {"n_dev": len(jax.devices())}
    out["inline"] = digest(ga.evaluate_population_streaming(
        cfg, eval_fn, 0, n_total=360, chunk=60))
    pool = make_init_pool(0.0)                     # thread-backed baseline
    out["threads"] = digest(ga.evaluate_population_streaming(
        cfg, eval_fn, 0, n_total=360, chunk=60, environment=pool))
    pool.shutdown()
    for k in KS:
        pool = make_init_pool(0.0, pool_devices=k)
        out[f"dev{k}"] = digest(ga.evaluate_population_streaming(
            cfg, eval_fn, 0, n_total=360, chunk=60, environment=pool))
        pool.shutdown()
    print(json.dumps(out))
"""


def test_streaming_init_bit_identical_across_device_pool_sizes():
    """On 4 forced devices: inline == thread pool == 1/2/4 device-set
    members; and a 1-forced-device run reproduces the same digest (the
    device count never leaks into results)."""
    import json
    four = json.loads(_run_forced(
        _STREAM_DIGESTS.replace("KS", "(1, 2, 4)"), 4))
    ref = four["inline"]
    assert four["n_dev"] == 4
    for key in ("threads", "dev1", "dev2", "dev4"):
        assert four[key] == ref, f"{key} diverged from inline"
    one = json.loads(_run_forced(
        _STREAM_DIGESTS.replace("KS", "(1,)"), 1))
    assert one["n_dev"] == 1
    assert one["inline"] == one["threads"] == one["dev1"] == ref


@pytest.mark.slow
def test_chaos_device_pool_stays_bit_exact_at_35pct_faults():
    """A 35%-fault mix over 2 device-set members (on 4 forced devices)
    must reproduce the failure-free digest bit-for-bit, with every
    member's attempt accounting balanced."""
    out = _run_forced("""
        import hashlib
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import EnvironmentPool, FaultSpec, \\
            make_device_members
        from repro.evolution import ga, NSGA2Config

        cfg = NSGA2Config(mu=8, genome_dim=2,
                          bounds=((0., 100.), (0., 100.)), n_objectives=3)

        def eval_fn(keys, genomes):
            noise = jax.vmap(lambda k: jax.random.normal(k, (3,)))(keys)
            d, e = genomes[:, 0], genomes[:, 1]
            return jnp.stack([(d - 30.) ** 2, jnp.abs(d - e), d + e],
                             1) + noise

        def digest(res):
            return hashlib.sha256(
                np.asarray(res.objectives).tobytes()
                + np.asarray(res.genomes).tobytes()).hexdigest()

        clean = digest(ga.evaluate_population_streaming(
            cfg, eval_fn, 0, n_total=360, chunk=60))
        members = make_device_members(
            None, 2,
            faults=lambda i: FaultSpec(fail_rate=0.25, fail_limit=None,
                                       hang_rate=0.05, hang_limit=2,
                                       hang_s=0.3, corrupt_rate=0.05,
                                       corrupt_limit=2, seed=i))
        pool = EnvironmentPool(members, retries=8, backoff_s=0.0)
        res = ga.evaluate_population_streaming(
            cfg, eval_fn, 0, n_total=360, chunk=60, environment=pool)
        assert digest(res) == clean, "chaos run diverged"
        assert res.attempts > res.chunks_total, "faults never fired"
        for name, s in pool.member_stats().items():
            assert s["submitted"] == (s["completed"] + s["failed"]
                                      + s["hung"] + s["corrupted"]), \\
                (name, s)
        pool.shutdown()
        print("CHAOS_OK", res.attempts)
    """, 4)
    assert "CHAOS_OK" in out


# ---------------------------------------------------------------------------
# device errors surface at once; only ragged fan-outs leave the device
# ---------------------------------------------------------------------------
def _device_failure(ctx):
    """A task whose compiled program fails at run time on the device: the
    runtime raises ``jax.errors.JaxRuntimeError``."""
    import jax.numpy as jnp

    def boom(v):
        raise RuntimeError("device fault")

    out = jax.jit(lambda v: jax.pure_callback(boom, v, v))(
        jnp.float32(ctx["x"]))
    return {"y": float(out)}


def test_device_error_is_not_retried_by_environment_or_pool():
    from repro.core.faults import is_device_error
    task = PyTask("devfail", _device_failure, inputs=(x,), outputs=(y,))
    env = DeviceEnvironment(jax.local_devices()[:1], retries=3,
                            backoff_s=0.0)
    with pytest.raises(jax.errors.JaxRuntimeError) as err:
        env.submit(task, Context(x=1.0))
    assert is_device_error(err.value)
    assert env.stats.retried == 0 and env.stats.failed == 1
    pool = EnvironmentPool(make_device_members(None, 1), retries=4,
                           backoff_s=0.0)
    try:
        with pytest.raises(jax.errors.JaxRuntimeError):
            pool.submit(task, Context(x=1.0))
        snap = pool.stats.snapshot()
        assert snap["resubmissions"] == 0 and snap["failed"] == 1
        assert snap["in_flight"] == 0
    finally:
        pool.shutdown()


def test_transient_errors_still_retry():
    """An ordinary task exception keeps its transient semantics."""
    from repro.core.faults import is_device_error
    calls = []

    def flaky(ctx):
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return {"y": ctx["x"]}

    assert not is_device_error(OSError("transient"))
    env = DeviceEnvironment(jax.local_devices()[:1], retries=3,
                            backoff_s=0.0)
    out = env.submit(PyTask("flaky", flaky, inputs=(x,), outputs=(y,)),
                     Context(x=2.0))
    assert out["y"] == 2.0 and env.stats.retried == 2


def test_device_map_explore_host_path_only_for_ragged_contexts():
    import numpy as np
    sq = JaxTask("sq", lambda x: {"y": x * x}, inputs=(x,), outputs=(y,))
    env = DeviceEnvironment(jax.local_devices()[:1])
    # ragged shapes cannot be lanes: the host path runs them one by one
    ragged = [Context(x=np.ones(2, np.float32)),
              Context(x=np.ones(3, np.float32))]
    outs = env.map_explore(sq, ragged)
    assert [np.asarray(o["y"]).shape for o in outs] == [(2,), (3,)]
    assert env.last_lane_devices is None
    # a batchable fan-out whose program fails raises, no host fallback
    bad = JaxTask("bad", lambda x: {"y": jax.pure_callback(
        lambda v: (_ for _ in ()).throw(RuntimeError("device fault")),
        jax.ShapeDtypeStruct((), np.float32), x,
        vmap_method="sequential")},
        inputs=(x,), outputs=(y,))
    with pytest.raises(jax.errors.JaxRuntimeError):
        env.map_explore(bad, [Context(x=np.float32(i)) for i in range(4)])
