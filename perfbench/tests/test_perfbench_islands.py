"""The island GA's cell on the CPU: the plain selection reference against
the program's NSGA-II, the superstep's record at a tiny size against both
references, and the cell's entry run as a tiny cell, sound and with faults
planted in what it reads."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_cells  # noqa: E402
from reference import nsga2 as ref  # noqa: E402

from repro.evolution import archive as program_archive  # noqa: E402
from repro.evolution import nsga2  # noqa: E402


def _objectives(seed, n, m, ties, invalid):
    rng = np.random.default_rng(seed)
    obj = rng.random((n, m)).astype(np.float32) * 100
    if ties:            # few distinct values: shared fronts, equal values
        obj = np.floor(obj / 25).astype(np.float32) * 25
    valid = np.ones(n, bool)
    if invalid:
        valid[rng.choice(n, n // 4, replace=False)] = False
        obj[~valid] = nsga2.BIG
    return obj, valid


@jax.jit
def _program(genomes, obj, valid):
    """The program's ranks, crowding, (16 of 32) selection, and the merge
    of the last 8 rows into an archive of the first 24."""
    cfg = nsga2.NSGA2Config(mu=16, genome_dim=2, bounds=((0., 1.),) * 2)
    ranks = nsga2.nondominated_ranks(obj, valid)
    crowd = nsga2.crowding_distance(obj, ranks)
    idx, _, _ = nsga2.select_mu(cfg, genomes, obj, valid)
    arch = program_archive.Archive(genomes[:24], obj[:24], valid[:24])
    merged = program_archive.merge(arch, genomes[24:], obj[24:], valid[24:])
    return ranks, crowd, idx, merged


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("ties", [False, True], ids=["spread", "ties"])
@pytest.mark.parametrize("invalid", [False, True], ids=["valid", "invalid"])
def test_the_selection_reference_agrees_with_the_program(seed, ties,
                                                         invalid):
    obj, valid = _objectives(seed, 32, 3, ties, invalid)
    genomes = np.random.default_rng(seed + 100).random((32, 2)).astype(
        np.float32)
    ranks, crowd, idx, merged = _program(genomes, obj, valid)
    want_ranks = ref.ranks(obj, valid)
    np.testing.assert_array_equal(np.asarray(ranks)[valid], want_ranks[valid])
    np.testing.assert_array_equal(np.asarray(crowd)[valid],
                                  ref.crowding(obj, want_ranks)[valid])
    np.testing.assert_array_equal(np.asarray(idx),
                                  ref.truncation_order(obj, valid)[:16])
    want = ref.merge((genomes[:24], obj[:24], valid[:24]),
                     (genomes[24:], obj[24:], valid[24:]))
    assert ref.mismatched_rows(merged, want) == 0


# ---------------------------------------------------------------------------
# the cell at a tiny size
# ---------------------------------------------------------------------------
TINY_GA = {"n_islands": 2, "mu": 8, "lam": 8, "archive_size": 16,
           "merge_top_k": 4}


def island_cell():
    """``islands.8x16`` with the tiny model, 2 islands of 8 and 8 children
    sampled (the benchmark's own tiny cells are those of tiny_cells.py)."""
    import harness
    c = harness.Cell(harness.load_benchmark(), "islands.8x16")
    c.config = dict(c.config, model=dict(tiny_cells.TINY_MODEL),
                    ga=dict(c.config["ga"], **TINY_GA))
    c.traffic = dict(c.traffic, check_sample=8)
    return c


@pytest.fixture(scope="module")
def tiny_model():
    """``calibrate(reduced=False)`` runs the tiny model."""
    from repro.configs.ants_netlogo import AntsConfig
    from repro.launch import explore
    mp = pytest.MonkeyPatch()
    mp.setattr(explore, "CONFIG", AntsConfig(**tiny_cells.TINY_MODEL))
    yield explore
    mp.undo()


@pytest.fixture(scope="module")
def tiny_run(tiny_model, tmp_path_factory):
    """One run of the tiny cell through the harness: its result, and the
    job and epochs its entry checked."""
    from entries import island_ga
    seen = {}
    mp = pytest.MonkeyPatch()

    def check(job, epochs):
        seen.update(job=job, epochs=epochs)
        return island_ga_check(job, epochs)

    island_ga_check = island_ga.check
    mp.setattr(island_ga, "check", check)
    c = island_cell()
    c.entry = lambda: island_ga
    try:
        result = tiny_cells.run(c, tmp_path_factory.mktemp("islands"))
    finally:
        mp.undo()
    return result, seen["job"], seen["epochs"]


def test_the_tiny_cell_is_correct_and_counts_its_window(tiny_run):
    result, job, epochs = tiny_run
    assert result["correct"], result["checks"]
    assert "0 programs compiled inside it" in result["_notes"][0]
    checks = {n: c["value"] for n, c in result["checks"].items()}
    assert checks["diverged"] == 0.0 and checks["chem_gap"] == 0.0
    assert checks["selection_mismatch"] == 0.0
    # the window holds whole epochs of 2 islands x 8 children
    assert result["attempted"] > 0 and result["attempted"] % 16 == 0
    assert result["metrics"]["evals_per_s"]["value"] > 0
    # the objectives spread at this size, so selection is not all ties
    objectives = np.asarray(epochs.last[1].step.child_objectives)
    assert len(np.unique(objectives[..., 0])) > 1


def _perturbed_lane(job, epochs):
    """One sampled child's lane ends one patch off for one ant."""
    from entries import island_ga
    state, record = epochs.last
    i, c = island_ga.sample(job.seed, TINY_GA["n_islands"], TINY_GA["lam"],
                            int(job.traffic["check_sample"]))[0]
    lanes = record.step.lanes
    lane = c * int(job.config["replicates"])
    lanes = lanes._replace(ant_pos=lanes.ant_pos.at[i, lane, 0, 0].add(1))
    return state, record._replace(step=record.step._replace(lanes=lanes))


def _swapped_survivor(job, epochs):
    """Two survivors of an island that differ trade places."""
    state, record = epochs.last
    ev = record.evolved
    g = np.asarray(ev.genomes[0])
    j = next(j for j in range(1, len(g)) if (g[j] != g[0]).any())
    swap = jnp.arange(len(g)).at[0].set(j).at[j].set(0)
    ev = ev._replace(genomes=ev.genomes.at[0].set(ev.genomes[0][swap]),
                     objectives=ev.objectives.at[0].set(
                         ev.objectives[0][swap]))
    return state, record._replace(evolved=ev)


def _dropped_archive_member(job, epochs):
    """The archive's best member is gone, the rest move up."""
    state, record = epochs.last
    a = state.archive
    a = a._replace(genomes=np.roll(a.genomes, -1, axis=0),
                   objectives=np.roll(a.objectives, -1, axis=0),
                   valid=np.concatenate([a.valid[1:], [False]]))
    return state._replace(archive=a), record


@pytest.mark.parametrize("fault,number", [
    (_perturbed_lane, "diverged"),
    (_swapped_survivor, "selection_mismatch"),
    (_dropped_archive_member, "selection_mismatch")],
    ids=["perturbed-child-lane", "swapped-survivor",
         "dropped-archive-member"])
def test_a_planted_fault_fails_the_cells_check(tiny_run, fault, number):
    import copy

    from entries import island_ga
    _, job, epochs = tiny_run
    broken = copy.copy(epochs)
    broken.last = fault(job, epochs)
    numbers = island_ga.check(job, broken)
    assert numbers[number] > job.config["limits"][number], numbers


def test_simulate_batch_state_is_simulate_batch_with_its_final_state():
    import tap
    from repro.ants import simulate_batch, simulate_batch_state
    from repro.configs.ants_netlogo import AntsConfig

    cfg = AntsConfig(**tiny_cells.TINY_MODEL)
    keys = jax.random.split(jax.random.key(3), 4)
    rates = jnp.array([10.0, 50.0, 90.0, 30.0])
    objectives, state = simulate_batch_state(cfg, keys, rates, rates[::-1])
    np.testing.assert_array_equal(
        objectives, simulate_batch(cfg, keys, rates, rates[::-1]))
    _, tapped = jax.jit(lambda k, d, e: tap.tapped(simulate_batch, cfg, k, d,
                                                   e))(keys, rates,
                                                       rates[::-1])
    for field in tap.STATE_FIELDS[:-1]:
        np.testing.assert_array_equal(getattr(state, field), tapped[field])


def test_the_golden_island_epoch_agrees_with_the_selection_reference():
    """The epoch ``tests/test_golden.py`` pins (two islands of 8 on a noisy
    ZDT1, two steps, top-4 merge): its last step's survivors and its
    archive are the reference's selection of the pools it recorded."""
    from repro.evolution import NSGA2Config, init_island_state, \
        make_recorded_epoch

    cfg = NSGA2Config(mu=8, genome_dim=3, bounds=((0., 1.),) * 3,
                      n_objectives=2)

    def fitness(keys, genomes):
        noise = jax.vmap(lambda k: jax.random.normal(k, (2,)))(keys)
        f1 = genomes[:, 0]
        g = 1.0 + 9.0 * genomes[:, 1:].mean(1)
        return jnp.stack([f1, g * (1.0 - jnp.sqrt(f1 / g))], 1) \
            + 0.01 * noise

    start = init_island_state(cfg, jax.random.key(0), n_islands=2,
                              archive_size=32)
    state, record = jax.jit(make_recorded_epoch(
        cfg, fitness, lam=8, steps_per_epoch=2, merge_top_k=4))(start)
    step, ev = record.step, record.evolved
    survivors, archive = ref.island_epoch(
        (step.parent_genomes, step.parent_objectives, step.parent_valid),
        (step.children, step.child_objectives),
        (ev.genomes, ev.objectives, ev.valid), 4, start.archive)
    assert ref.mismatched_rows((ev.genomes, ev.objectives, ev.valid),
                               survivors) == 0
    assert ref.mismatched_rows(state.archive, archive) == 0
