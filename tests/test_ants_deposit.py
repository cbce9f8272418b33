"""The ants tick's deposit: food taken and chemical dropped through per-patch
counts (one-hot contractions over the ants) instead of scatter-adds, with
the drop rounded as one addition per ant, as the scatter-add rounds it."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ants import init_state, make_step, model, simulate_batch
from repro.configs.ants_netlogo import CONFIG, AntsConfig

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "tests"))

import tiny_cells  # noqa: E402


def _bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


def _added_per_ant(chem, food, pos, pickup, carrying):
    """The deposit ant by ant, in order: one f32 addition of the drop per
    carrying ant, one unit taken per ant picking up, then food floored."""
    chem, food = np.array(chem, np.float32), np.array(food, np.float32)
    for lane in range(chem.shape[0]):
        for (y, x), take, drop in zip(pos[lane], pickup[lane], carrying[lane]):
            food[lane, y, x] = np.float32(food[lane, y, x] - np.float32(take))
            if drop:
                chem[lane, y, x] = np.float32(chem[lane, y, x]
                                              + np.float32(model.DEPOSIT))
    return chem, np.maximum(food, np.float32(0.0))


@jax.jit
def _scatter_added(chem, food, pos, pickup, carrying):
    """The deposit as scatter-adds into each lane's fields."""
    def lane(chem, food, pos, pickup, carrying):
        at = (pos[:, 0], pos[:, 1])
        food = jnp.maximum(food.at[at].add(-pickup.astype(jnp.float32)), 0.0)
        chem = chem.at[at].add(model.DEPOSIT * carrying.astype(jnp.float32))
        return chem, food
    return jax.vmap(lane)(chem, food, pos, pickup, carrying)


@jax.jit
def _contracted(chem, food, pos, pickup, carrying):
    """The tick's own deposit."""
    here = jax.vmap(lambda c, q: c[q[:, 0], q[:, 1]])(chem, pos)
    return jax.vmap(model._deposit)(chem, food, here, pos, pickup, carrying)


def _crafted_lanes(w, p, seed):
    """Four lanes: every ant on one patch; ants on the world's corners and
    edges; crowds of 2-12 ants on shared patches; ants spread at random.
    A non-integer field with values just under powers of two, food of 0, 1
    and 2 units, pickups on empty and on full patches."""
    rng = np.random.default_rng(seed)
    n = 4
    chem = (rng.uniform(0, 1, (n, w, w))
            * 10.0 ** rng.uniform(-3, 4, (n, w, w))).astype(np.float32)
    under = 2.0 ** rng.integers(4, 13, (n, w, w)) * (
        1 - rng.integers(1, 200, (n, w, w)) * 2.0 ** -24)
    chem = np.where(rng.uniform(0, 1, (n, w, w)) < 0.3, under, chem)
    chem = chem.astype(np.float32)
    food = rng.choice(np.array([0.0, 0.0, 1.0, 2.0], np.float32), (n, w, w))
    pos = rng.integers(0, w, (n, p, 2)).astype(np.int32)
    pos[0] = rng.integers(0, w, 2)
    edge = np.array([(0, 0), (0, w - 1), (w - 1, 0), (w - 1, w - 1),
                     (0, w // 2), (w // 2, 0), (w - 1, w // 2),
                     (w // 2, w - 1)], np.int32)
    pos[1] = edge[rng.integers(0, len(edge), p)]
    crowds = rng.integers(0, w, (p // 4, 2))
    pos[2] = crowds[np.minimum(np.arange(p) // rng.integers(2, 13),
                               len(crowds) - 1)]
    pickup = rng.uniform(0, 1, (n, p)) < 0.5
    carrying = rng.uniform(0, 1, (n, p)) < 0.7
    carrying[0, :2] = True
    # on lane 0's one patch, a value where adding the whole drop at once
    # rounds otherwise than adding it ant by ant
    k, drop = int(carrying[0].sum()), np.float32(model.DEPOSIT)
    for c in rng.uniform(0, 4096, 4096).astype(np.float32):
        each = c
        for _ in range(k):
            each = np.float32(each + drop)
        if each != np.float32(c + drop * k):
            chem[0, pos[0, 0, 0], pos[0, 0, 1]] = c
            break
    return chem, food, pos, pickup, carrying


@pytest.mark.parametrize("w, p, seed", [(16, 40, 0), (16, 40, 1),
                                        (CONFIG.world_size,
                                         CONFIG.population, 2)])
def test_the_deposit_equals_adding_per_ant(w, p, seed):
    """Bit for bit, in food and in the chemical field: the contraction
    deposit, the scatter-add deposit (whose CPU scatter adds one update at
    a time, as the chip's does), and an addition per ant in NumPy."""
    lanes = _crafted_lanes(w, p, seed)
    chem, food = _contracted(*lanes)
    for want_chem, want_food in (_added_per_ant(*lanes),
                                 _scatter_added(*lanes)):
        np.testing.assert_array_equal(_bits(food), _bits(want_food))
        np.testing.assert_array_equal(_bits(chem), _bits(want_chem))
    # the lanes reach a patch where one rounding of the whole drop differs
    y, x = lanes[2][0, 0]
    k = int(lanes[4][0].sum())
    once = np.float32(lanes[0][0, y, x] + np.float32(model.DEPOSIT * k))
    assert _bits(once) != _bits(chem[0, y, x])


@pytest.mark.parametrize("most", [1, 2, 16, 125, 128])
def test_adding_repeatedly_rounds_as_each_addition(most):
    """``_add_repeatedly`` against one rounded addition at a time, over
    values across binades, just under powers of two, zero and slightly
    negative (a diffused field can dip an ulp below zero)."""
    rng = np.random.default_rng(most)
    n = 200_000
    c = rng.uniform(0, 1, n) * 10.0 ** rng.uniform(-4, 6.5, n)
    c[: n // 8] = 2.0 ** rng.integers(-2, 23, n // 8) * (
        1 - rng.integers(1, 500, n // 8) * 2.0 ** -24)
    c[n // 8: n // 8 + 100] = 0.0
    c[n // 8 + 100: n // 8 + 200] = -1e-7
    c = c.astype(np.float32)
    k = rng.integers(0, most + 1, n).astype(np.float32)
    want = c.copy()
    for j in range(most):
        want = np.where(k > j, (want + np.float32(60.0)).astype(np.float32),
                        want)
    got = jax.jit(lambda c, k: model._add_repeatedly(c, k, 60.0, most))(c, k)
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("seed", [0, 1])
def test_simulate_batch_reaches_the_references_state(seed):
    """At the tiny model, ``simulate_batch`` (its final state read through
    the tick loop's carry) and the plain reference agree exactly: the
    objectives, food taken per source, ants carrying, ant positions, and
    the chemical field's total and projection."""
    import tap
    from reference import ants as ants_ref

    model_settings = dict(tiny_cells.TINY_MODEL)
    cfg = AntsConfig(**model_settings)
    keys = jax.random.split(jax.random.key(seed), 4)
    d = jnp.array([0.0, 20.0, 50.0, 99.0])
    e = jnp.array([99.0, 5.0, 50.0, 0.0])
    obj, st = jax.jit(lambda k, d, e: tap.tapped(
        simulate_batch, cfg, k, d, e))(keys, d, e)
    np.testing.assert_array_equal(
        np.asarray(obj), np.asarray(simulate_batch(cfg, keys, d, e)))
    got = ants_ref.summarize(model_settings, obj, st["chem"], st["food"],
                             st["ant_pos"], st["carrying"])
    want = jax.jit(lambda k, d, e: ants_ref.simulate(
        model_settings, k, d, e))(keys, d, e)
    assert (np.asarray(want)[:, ants_ref.CHEM_SUM] > 0).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_step_deposits_as_before_from_a_crafted_state():
    """One tick of ``make_step`` from a non-integer field, with diffusion
    and evaporation at 0 (the kernel is then the identity): the field gains
    exactly one rounded addition of the drop per carrying ant."""
    cfg = AntsConfig(world_size=16, population=40, max_ticks=8,
                     nest_radius=3.0, food_radius=2.0)
    chem, _, pos, _, carrying = _crafted_lanes(16, 40, 3)
    state = init_state(cfg, jax.random.split(jax.random.key(3), 4))
    state = state._replace(chem=jnp.asarray(chem), ant_pos=jnp.asarray(pos),
                           carrying=jnp.asarray(carrying))
    zero = jnp.zeros((4,), jnp.float32)
    after = jax.jit(make_step(cfg))(state, jnp.int32(0), zero, zero)
    want, _ = _added_per_ant(chem, np.asarray(state.food),
                             np.asarray(after.ant_pos),
                             np.zeros(carrying.shape, bool),
                             np.asarray(after.carrying))
    np.testing.assert_array_equal(_bits(after.chem), _bits(want))
