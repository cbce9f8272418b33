"""The program's own marks in a profiler trace: the ants tick's phases as
named scopes on the compiled operations, and the host spans of a pool
attempt and of a chunk's steps."""
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.ants import model, simulate_batch
from repro.configs.ants_netlogo import AntsConfig
from repro.core.prototype import Context, Val
from repro.core.task import PyTask
from repro.evolution import NSGA2Config, ga
from repro.launch.explore import make_init_pool

CFG = AntsConfig(world_size=16, population=16, max_ticks=8,
                 nest_radius=3.0, food_radius=2.0)
PHASES = ("ants.rng", "ants.sense", "ants.move", "ants.deposit",
          "ants.diffuse", "ants.sources")
METADATA = re.compile(r",? metadata=\{[^}]*\}")


def _operations(text):
    """The compiled module's instructions, without their metadata and the
    source-location tables that follow them."""
    return METADATA.sub("", text.split("\n\nFileNames", 1)[0])


def _compiled_text():
    keys = jax.random.split(jax.random.key(0), 2)
    rates = jnp.full((2,), 50.0)
    fn = jax.jit(simulate_batch.__wrapped__, static_argnums=0)
    return fn.lower(CFG, keys, rates, rates).compile().as_text()


def test_the_compiled_tick_names_each_of_its_phases():
    names = re.findall(r'op_name="([^"]*)"', _compiled_text())
    found = {m for n in names for m in re.findall(r"ants\.[a-z]+", n)}
    assert found == set(PHASES)


def test_the_compiled_tick_deposits_without_scatter():
    """The deposit's per-patch counts are contractions, not scatter-adds,
    and they carry the deposit's scope."""
    text = _compiled_text()
    assert not re.search(r"\bscatter\(", text)
    contractions = re.findall(r'op_name="([^"]*pi,pj->ij[^"]*)"', text)
    assert contractions
    assert all("ants.deposit" in n for n in contractions)


def test_the_scopes_add_metadata_and_leave_the_operations(monkeypatch):
    scoped = _compiled_text()
    # the same program traced anew with every named scope made a no-op
    monkeypatch.setattr(model.jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        plain = _compiled_text()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert "ants.sense" not in plain
    assert _operations(scoped) == _operations(plain)


def _spans(directory):
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)[0]
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, ln.name,
             {str(k): str(v) for k, v in e.stats})
            for ln in host.lines for e in ln.events
            if e.name.startswith("repro.")]


def test_a_pool_attempt_is_a_span_naming_its_member_and_round(tmp_path):
    task = PyTask("double", lambda ctx: {"y": 2 * ctx["x"]},
                  inputs=(Val("x", int),), outputs=(Val("y", int),))
    pool = make_init_pool(workers=1)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            out = pool.submit(task, Context(x=21))
        finally:
            jax.profiler.stop_trace()
    finally:
        pool.shutdown()
    assert out["y"] == 42
    spans = _spans(str(tmp_path))
    assert [(s[0], s[4]) for s in spans] == [
        ("repro.pool.attempt", {"member": "worker0", "round": "0"})]


def test_a_chunk_records_its_steps_in_order_and_returns_as_before(tmp_path):
    cfg = NSGA2Config(mu=4, genome_dim=2, bounds=((0.0, 1.0), (0.0, 1.0)),
                      n_objectives=1)

    def evaluate(keys, genomes):
        return genomes.sum(-1, keepdims=True)

    task = ga.make_chunk_task(cfg, evaluate, 7)
    want = np.asarray(ga.population_chunk(cfg, 7, 2, 3)[1]).sum(
        -1, keepdims=True)
    task.run(Context(chunk=1, size=3))          # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = task.run(Context(chunk=2, size=3))["objectives"]
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_allclose(got, want)
    spans = _spans(str(tmp_path))
    assert [s[0] for s in spans] == ["repro.chunk.inputs",
                                     "repro.chunk.dispatch",
                                     "repro.chunk.wait", "repro.chunk.fetch"]
    assert len({s[3] for s in spans}) == 1
    ends = [s[2] for s in spans]
    assert all(a <= b for a, b in zip(ends, [s[1] for s in spans][1:]))
