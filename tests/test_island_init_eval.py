"""The islands' initial evaluation runs only where an island needs it.

Islands arrive unevaluated in the first epoch only. Under ``vmap`` a
per-island ``lax.cond`` becomes a select that runs both branches, so the
evolve stage asks once, over the island batch, whether any island is still
unevaluated: every later epoch leaves the initial evaluation out. These
tests count the initial evaluations through a ``jax.debug.callback`` in
the fitness task, which is called with ``mu`` genomes by the initial
evaluation and with ``lam`` by a GA step, and hold the epoch to the
per-island ``vmap(cond)`` form bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.evolution import (NSGA2Config, init_island_state, make_superstep,
                             run_islands)
from repro.evolution import ga, island

MU, LAM, N_ISLANDS = 6, 4, 3
CFG = NSGA2Config(mu=MU, genome_dim=3, bounds=((0., 1.),) * 3,
                  n_objectives=2)


class Counter:
    """A ZDT1 fitness task that counts the initial evaluations it serves:
    one a call with ``MU`` genomes, per island under ``vmap``."""

    def __init__(self):
        self.calls = 0

    def _count(self, genomes):
        # one population, or a batch of them where vmap hands it whole
        self.calls += np.asarray(genomes).size // (MU * CFG.genome_dim)

    def __call__(self, keys, genomes):
        if genomes.shape[0] == MU:
            jax.debug.callback(self._count, genomes)
        x0 = genomes[:, 0]
        g = 1 + 9 * genomes[:, 1:].mean(axis=1)
        f2 = g * (1 - jnp.sqrt(jnp.clip(x0 / g, 0, 1)))
        return jnp.stack([x0, f2], axis=1)

    def read(self):
        jax.effects_barrier()
        calls, self.calls = self.calls, 0
        return calls


def per_island_cond_evolve(cfg, eval_fn, *, lam, steps_per_epoch):
    """The evolve stage with the epoch-0 ``lax.cond`` inside the island
    ``vmap``: a select that evaluates every island's initial population in
    every epoch and keeps it where the island was unevaluated."""
    step = ga.make_recorded_step(cfg, eval_fn, lam)

    def initial(s):
        with jax.named_scope("islands.init_eval"):
            return ga.evaluate_initial(cfg, s, eval_fn)

    def evolve_island(istate):
        istate = jax.lax.cond(istate.valid.any(), lambda s: s, initial,
                              istate)
        return island._repeat(step, istate, steps_per_epoch)

    def evolve(islands):
        islands = island._constrain_islands(islands)
        islands, record = island._per_island(jax.vmap(evolve_island))(islands)
        return island._constrain_islands(islands), record

    return evolve


@pytest.fixture(scope="module")
def supersteps():
    """(counting fitness, superstep, the same built on the per-island
    cond), jitted once for the module."""
    count = Counter()
    kw = dict(lam=LAM, steps_per_epoch=2, merge_top_k=3)
    new = jax.jit(make_superstep(CFG, count, **kw), static_argnums=1)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(island, "make_recorded_evolve", per_island_cond_evolve)
        old = jax.jit(make_superstep(CFG, count, **kw), static_argnums=1)
    return count, new, old


def _leaves(tree):
    return [np.asarray(jax.random.key_data(x) if island._is_key(x) else x)
            for x in jax.tree.leaves(tree)]


def _assert_identical(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


def _fresh(state, i):
    """``state`` with island ``i`` unevaluated."""
    valid = state.islands.valid.at[i].set(False)
    return state._replace(islands=state.islands._replace(valid=valid))


@pytest.mark.parametrize("epoch, initial_evaluations", [
    ("epoch0", N_ISLANDS),      # every island unevaluated
    ("epoch1", 0),              # every island evaluated: never executed
    ("mixed", N_ISLANDS),       # one unevaluated: the per-island select
])
def test_superstep_evaluates_initial_populations_only_when_one_is_needed(
        supersteps, epoch, initial_evaluations):
    count, new, old = supersteps
    state = init_island_state(CFG, jax.random.key(5), n_islands=N_ISLANDS,
                              archive_size=16)
    if epoch != "epoch0":
        state, _ = new(state, 1)
        assert bool(state.islands.valid.all())
        if epoch == "mixed":
            state = _fresh(state, 1)
    count.read()
    got = new(state, 1)
    assert count.read() == initial_evaluations
    _assert_identical(got, old(state, 1))


def test_a_mixed_batch_changes_only_the_unevaluated_island(supersteps):
    """The evaluated islands come out of a mixed batch as from a batch of
    evaluated islands, the unevaluated one as from a batch of unevaluated
    islands."""
    _, new, _ = supersteps
    state = init_island_state(CFG, jax.random.key(6), n_islands=N_ISLANDS,
                              archive_size=16)
    state, _ = new(state, 1)
    _, evaluated = new(state, 1)
    _, unevaluated = new(_fresh(state, slice(None)), 1)
    _, got = new(_fresh(state, 1), 1)
    for a, b, c in zip(_leaves(got.evolved), _leaves(evaluated.evolved),
                       _leaves(unevaluated.evolved)):
        np.testing.assert_array_equal(a[[0, 2]], b[[0, 2]])
        np.testing.assert_array_equal(a[1], c[1])
    # its initial evaluation is counted on top of the evaluated island's
    assert int(got.evolved.evaluations[1]) == int(
        evaluated.evolved.evaluations[1]) + MU


@pytest.mark.parametrize("pipeline", [False, True])
def test_a_run_evaluates_each_island_initially_once(pipeline):
    """Both schedules of ``run_islands``: over four epochs, one initial
    evaluation per island, in the first."""
    count = Counter()
    state = run_islands(CFG, count, jax.random.key(7), n_islands=N_ISLANDS,
                        lam=LAM, steps_per_epoch=1, epochs=4,
                        archive_size=16, pipeline=pipeline)
    assert int(state.epoch) == 4
    assert count.read() == N_ISLANDS
