"""Vectorized JAX re-implementation of the NetLogo 'ants' foraging model
(Wilensky 1999) — the paper's §4 case study.

Faithful mechanics:
- a colony of `population` ants leaves the nest (world center); ants without
  food wander, biased towards chemical ("sniff"); ants that reach food pick a
  piece up and head back to the nest, dropping chemical along the way;
- patches diffuse chemical to their 8 neighbours at `diffusion_rate`% and
  evaporate at `evaporation_rate`% per tick (the fused Pallas kernel);
- 3 food sources at increasing distances from the nest;
- fitness (paper Listing 1): the first tick at which each source empties
  (max_ticks if it never empties).

The simulation is *natively batched*: every state array carries a leading
``lanes`` dim (parameter candidates x replications), one ``lax.scan`` over
ticks advances all lanes in lockstep, and the diffusion kernel runs once per
tick on the whole (N, W, W) stack. This is the TPU-native adaptation of the
paper's "one grid job per parameter set" (DESIGN.md §2): grid jobs become
SIMD lanes.

NetLogo's continuous headings/wiggle become a stochastic (Gumbel-jittered)
argmax over the 8-neighbourhood at patch granularity — a documented
simplification; colony-level behaviour (trail formation, nearer sources
emptying first) is preserved and asserted in tests.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.ants_netlogo import AntsConfig
from repro.kernels import ops as kops


class AntsState(NamedTuple):
    chem: jnp.ndarray        # (N, W, W) f32 chemical field
    food: jnp.ndarray        # (N, W, W) f32 food units
    ant_pos: jnp.ndarray     # (N, P, 2) i32 patch coordinates
    carrying: jnp.ndarray    # (N, P) bool
    ticks_empty: jnp.ndarray  # (N, 3) i32 first tick each source emptied
    rng: jax.Array           # (N,) keys


def _dist2(w, cy, cx):
    ii = jnp.arange(w)
    dy = ii[:, None] - cy
    dx = ii[None, :] - cx
    return dy * dy + dx * dx


def food_sources(cfg: AntsConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(W,W) initial food grid and (3,W,W) source masks (NetLogo layout)."""
    w = cfg.world_size
    c = w // 2
    r2 = cfg.food_radius ** 2
    centers = jnp.array([
        [c, c + int(0.6 * c)],                 # source 1: right of nest
        [c + int(0.6 * c), c - int(0.6 * c)],  # source 2: lower-left
        [c - int(0.8 * c), c - int(0.8 * c)],  # source 3: upper-left (far)
    ])
    masks = jnp.stack([
        _dist2(w, centers[i, 0], centers[i, 1]) <= r2 for i in range(3)])
    food = jnp.zeros((w, w), jnp.float32)
    for i in range(3):
        food = jnp.where(masks[i], 1.0 + (i % 2), food)
    return food, masks


def nest_mask(cfg: AntsConfig) -> jnp.ndarray:
    w = cfg.world_size
    c = w // 2
    return _dist2(w, c, c) <= cfg.nest_radius ** 2


DEPOSIT = 60.0      # chemical an ant carrying food drops on its patch a tick

_OFFSETS = jnp.array([(-1, -1), (-1, 0), (-1, 1), (0, -1),
                      (0, 1), (1, -1), (1, 0), (1, 1)], jnp.int32)


def init_state(cfg: AntsConfig, keys) -> AntsState:
    n = keys.shape[0]
    w = cfg.world_size
    c = w // 2
    food, _ = food_sources(cfg)
    return AntsState(
        chem=jnp.zeros((n, w, w), jnp.dtype(cfg.chem_dtype)),
        food=jnp.broadcast_to(food, (n, w, w)),
        ant_pos=jnp.full((n, cfg.population, 2), c, jnp.int32),
        carrying=jnp.zeros((n, cfg.population), bool),
        ticks_empty=jnp.full((n, 3), cfg.max_ticks, jnp.int32),
        rng=keys,
    )


def _lane_step(cfg: AntsConfig, chem, food, ant_pos, carrying, key, nest,
               toward_nest_cached):
    """Per-lane ant logic (vmapped over lanes). Returns the new ant state
    and the chemical and food fields after the ants' deposit."""
    w = cfg.world_size
    p = cfg.population
    # phases of the tick as named scopes: each scope names the device
    # operations it compiles to (op_name metadata) in a profiler trace
    with jax.named_scope("ants.sense"):
        # neighbour gather
        npos = ant_pos[:, None, :] + _OFFSETS[None, :, :]      # (P,8,2)
        inb = ((npos >= 0) & (npos < w)).all(-1)               # (P,8)
        npc = jnp.clip(npos, 0, w - 1)
        chem_n = jnp.where(inb, chem[npc[..., 0], npc[..., 1]], 0.0)
    with jax.named_scope("ants.rng"):
        gumbel = jax.random.gumbel(key, (p, 8))
    with jax.named_scope("ants.sense"):
        # forage: follow chemical above sniff threshold, else wander
        sniff = jnp.where(chem_n > 0.05, chem_n, 0.0)
        forage = jnp.where(inb, jnp.log1p(sniff) * 8.0 + gumbel, -1e9)
        # return: move toward nest (precomputed per-patch descent scores)
        ret = jnp.where(inb, -toward_nest_cached[npc[..., 0], npc[..., 1]]
                        + 0.5 * gumbel, -1e9)
        scores = jnp.where(carrying[:, None], ret, forage)
        choice = jnp.argmax(scores, axis=-1)

    with jax.named_scope("ants.move"):
        new_pos = npc[jnp.arange(p), choice]
        on_food = food[new_pos[:, 0], new_pos[:, 1]] > 0
        on_nest = nest[new_pos[:, 0], new_pos[:, 1]]
        pickup = (~carrying) & on_food
        dropoff = carrying & on_nest
        new_carrying = (carrying | pickup) & ~dropoff

    with jax.named_scope("ants.deposit"):
        # the field at each ant's new patch, as sensed: a one-hot select
        # over the 8 neighbours (exact: one nonzero term), not a gather
        here = jnp.where(jnp.arange(8) == choice[:, None], chem_n,
                         0.0).sum(axis=1)
        chem, food = _deposit(chem, food, here, new_pos, pickup, new_carrying)
    return new_pos, new_carrying, food, chem


def _deposit(chem, food, here, pos, pickup, carrying):
    """One lane's deposit without scatter-adds: each ant in ``pickup``
    takes a unit of ``food`` (W, W) and each ant ``carrying`` food drops
    ``DEPOSIT`` on ``chem`` (W, W), at its patch ``pos`` (P, 2), where the
    field holds ``here`` (P,). Returns the new (chem, food).

    Both are one-hot contractions over the ants on the MXU. Food taken is
    a per-patch count: 0/1 operands and sums of at most P are exact. The
    chemical is added per ant, to the value ``here``, as a scatter-add of
    the drops rounds it (one rounding per ant), and the first carrying ant
    on each patch writes the result back: its three bf16 pieces are the
    only nonzero terms of their patch, so their f32 sum is exact."""
    w = food.shape[-1]
    rows = jax.nn.one_hot(pos[:, 0], w, dtype=jnp.bfloat16)       # (P, W)
    cols = jax.nn.one_hot(pos[:, 1], w, dtype=jnp.bfloat16)
    taken = jnp.einsum("pi,pj->ij", rows * pickup[:, None].astype(rows.dtype),
                       cols, preferred_element_type=jnp.float32)
    # per ant, in one pairwise sum: the carrying ants on its patch in the
    # low bits and those before it in the high bits, so that the first
    # carrying ant on each patch finds the high bits clear
    p = pos.shape[0]
    high = 1 << p.bit_length()
    patch = pos[:, 0] * w + pos[:, 1]
    tally = jnp.where((patch[:, None] == patch[None, :]) & carrying[None, :],
                      jnp.where(jnp.tri(p, k=-1, dtype=bool), high + 1, 1),
                      0).sum(axis=1)
    first = carrying & (tally < high)
    count = (tally & (high - 1)).astype(jnp.float32)
    if here.dtype == jnp.float32:
        value = _add_repeatedly(here, count, DEPOSIT, p)
    else:       # a lower-precision field takes the drop in its own dtype
        value = here + (DEPOSIT * count).astype(here.dtype)
    pieces = _bf16_pieces(jnp.where(first, value, 0.0))           # (3, P)
    settled = jnp.einsum(
        "kpi,kpj->ij", rows[None] * pieces[:, :, None],
        jnp.broadcast_to(cols, (3,) + cols.shape),
        preferred_element_type=jnp.float32)
    # a drop leaves at least DEPOSIT less an ulp on its patch
    chem = jnp.where(settled > 0, settled.astype(chem.dtype), chem)
    return chem, jnp.maximum(food - taken, 0.0)


def _bf16_pieces(x):
    """f32 ``x`` >= 0 as three bf16 arrays (3, ...): its significand's top,
    middle and low 8 bits, which sum back to ``x`` exactly in any order."""
    x = x.astype(jnp.float32)
    pieces = []
    for _ in range(3):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        top = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                           jnp.float32)
        pieces.append(top)
        x = x - top
    return jnp.stack(pieces).astype(jnp.bfloat16)


def _add_repeatedly(c, k, amount: float, most: int):
    """``c`` with ``amount`` added ``k`` times, rounding after each
    addition, elementwise (f32 ``c``; whole f32 ``k`` in [0, most]).

    One addition per ant is what a scatter-add of the drops gives, and it
    can differ from ``c + amount * k``, one rounding, by an ulp. This takes
    ``most.bit_length() + 1`` passes, not ``k``: from ``amount`` up to
    2**26 (for 60.0), ``amount`` is a multiple of the spacing of f32
    values, so an addition that stays in a value's binade is exact and only
    the one that crosses into the next binade rounds. Each pass jumps to
    that crossing or to the last addition; ``most`` additions from
    ``amount`` on cross at most ``most.bit_length()`` binades. (A field
    holds at most 60 x ants x ticks: 7.5e6 at CONFIG.)"""
    a = jnp.float32(amount)
    f = jnp.where(k > 0, c + a, c)
    r = jnp.maximum(k - 1, 0.0)
    for _ in range(most.bit_length() + 1):
        exponent = jax.lax.bitcast_convert_type(f, jnp.uint32) & 0x7F800000
        top = 2 * jax.lax.bitcast_convert_type(exponent, jnp.float32)
        # s: the first addition that reaches ``top``, estimated then fixed
        # by exact comparisons (sums below ``top`` are representable)
        s = jnp.ceil((top - f) * (1 / a))
        s = jnp.where(f + a * (s - 1) >= top, s - 1, s)
        s = jnp.where(f + a * s < top, s + 1, s)
        m = jnp.minimum(s, r)
        f = f + a * m
        r = r - m
    return f


def make_step(cfg: AntsConfig):
    nest = nest_mask(cfg)
    w = cfg.world_size
    c = w // 2
    toward = _dist2(w, c, c).astype(jnp.float32)   # smaller = closer to nest
    _, masks = food_sources(cfg)
    lane_step = jax.vmap(
        functools.partial(_lane_step, cfg, nest=nest,
                          toward_nest_cached=toward))

    def step(state: AntsState, tick, diffusion, evaporation) -> AntsState:
        """diffusion/evaporation: (N,) fractions in [0,1]."""
        with jax.named_scope("ants.rng"):
            keys = jax.vmap(jax.random.split)(state.rng)       # (N,2,key)
            rng, move_keys = keys[:, 0], keys[:, 1]
        new_pos, carrying, food, chem = lane_step(
            state.chem, state.food, state.ant_pos, state.carrying, move_keys)
        with jax.named_scope("ants.diffuse"):
            chem = kops.diffuse_evaporate(
                chem.astype(jnp.float32), diffusion,
                evaporation).astype(state.chem.dtype)
        with jax.named_scope("ants.sources"):
            src_left = jnp.einsum("kij,nij->nk", masks.astype(jnp.float32),
                                  food)
            newly_empty = (src_left <= 0) & (
                state.ticks_empty == cfg.max_ticks)
            ticks_empty = jnp.where(newly_empty, tick, state.ticks_empty)
        return AntsState(chem, food, new_pos, carrying, ticks_empty, rng)

    return step


def _simulate(cfg: AntsConfig, keys, diffusion_rates,
              evaporation_rates) -> AntsState:
    """Every lane's final state after ``max_ticks`` ticks: one ``lax.scan``
    over the ticks whose carry is the ``AntsState``."""
    diffusion = jnp.clip(diffusion_rates / 100.0, 0.0, 1.0)
    evaporation = jnp.clip(evaporation_rates / 100.0, 0.0, 1.0)
    state = init_state(cfg, keys)
    step = make_step(cfg)

    def tick_fn(state, tick):
        return step(state, tick, diffusion, evaporation), None

    state, _ = jax.lax.scan(tick_fn, state,
                            jnp.arange(cfg.max_ticks, dtype=jnp.int32))
    return state


@functools.partial(jax.jit, static_argnums=(0,))
def simulate_batch(cfg: AntsConfig, keys, diffusion_rates, evaporation_rates):
    """keys: (N,) PRNG keys; rates: (N,) NetLogo percentages in [0, 99].
    Returns (N, 3) f32 objectives (first-empty ticks, lower = better)."""
    state = _simulate(cfg, keys, diffusion_rates, evaporation_rates)
    return state.ticks_empty.astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0,))
def simulate_batch_state(cfg: AntsConfig, keys, diffusion_rates,
                         evaporation_rates):
    """``simulate_batch`` that also returns every lane's final state:
    ``(objectives (N, 3) f32, AntsState)``, the same computation."""
    state = _simulate(cfg, keys, diffusion_rates, evaporation_rates)
    return state.ticks_empty.astype(jnp.float32), state


def simulate(cfg: AntsConfig, key, diffusion_rate, evaporation_rate):
    """Single-lane convenience wrapper. Returns (3,) objectives."""
    out = simulate_batch(cfg, key[None],
                         jnp.asarray(diffusion_rate, jnp.float32)[None],
                         jnp.asarray(evaporation_rate, jnp.float32)[None])
    return out[0]
