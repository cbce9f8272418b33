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


ISLAND_STAGES = ("islands.init_eval", "islands.select", "islands.merge",
                 "islands.reseed")


def _island_eval(cfg, replicates=2):
    from repro.ants import simulate_batch_state
    from repro.explore import replicated_batch_state
    return replicated_batch_state(
        lambda keys, genomes: simulate_batch_state(cfg, keys, genomes[:, 0],
                                                   genomes[:, 1]),
        replicates)


def test_the_compiled_superstep_names_the_island_stages_and_tick_phases():
    from repro.evolution import init_island_state, make_superstep
    ga_cfg = NSGA2Config(mu=4, genome_dim=2, bounds=((0., 99.),) * 2)
    state = init_island_state(ga_cfg, jax.random.key(0), n_islands=2,
                              archive_size=8)
    superstep = make_superstep(ga_cfg, _island_eval(CFG), lam=4,
                               steps_per_epoch=1, merge_top_k=2)
    text = jax.jit(superstep, static_argnums=1).lower(state, 1).compile(
        ).as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    # a stage is an op_name component (``state.islands.genomes`` is an
    # argument's path)
    found = {m for n in names
             for m in re.findall(r"(?<![\w.])islands\.[a-z_]+", n)}
    assert found == set(ISLAND_STAGES)
    ticks = {m for n in names for m in re.findall(r"ants\.[a-z]+", n)}
    assert ticks == set(PHASES)
    # the initial evaluation's ticks carry its stage and their phase
    assert any("islands.init_eval" in n and "ants.sense" in n for n in names)


def test_a_calibration_records_a_superstep_and_a_checkpoint_each_epoch(
        tmp_path, monkeypatch):
    from repro.launch import explore
    monkeypatch.setattr(explore, "CONFIG", CFG)
    seen = []
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # no per-call Python events
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        explore.calibrate(reduced=False, n_islands=2, mu=4, lam=4,
                          steps_per_epoch=1, epochs=2, replicates=2,
                          archive_size=8, merge_top_k=2,
                          out_dir=str(tmp_path / "out"),
                          printer=lambda m: None,
                          epoch_hook=lambda s, r: seen.append(
                              (int(s.epoch), r.step.lanes.chem.shape)))
    finally:
        jax.profiler.stop_trace()
    assert seen == [(1, (2, 8, 16, 16)), (2, (2, 8, 16, 16))]
    spans = _spans(str(tmp_path / "trace"))
    steps = [s for s in spans if s[0] == "repro.islands.superstep"]
    marks = [s for s in spans if s[0] == "repro.islands.checkpoint"]
    assert [(s[4]["epoch"], s[4]["evaluations"]) for s in steps] == [
        ("0", "16"), ("1", "8")]
    assert [s[4]["epoch"] for s in marks] == ["1", "2"]
    # epoch 1's checkpoint is written while epoch 2's superstep is queued
    assert steps[1][1] < marks[0][1]
