"""Plain float32 reference of the ants evaluation the benchmark times.

Written from the model's description (NetLogo 'ants', Wilensky 1999, as the
paper's case study uses it), in straightforward ``jax.numpy``: no Pallas
kernel, no batching tricks beyond ``vmap`` over lanes, and no import from
the program under test. It consumes the same random stream (threefry keys
split per tick, Gumbel draws per ant and neighbour) so that a lane's
trajectory can be followed decision by decision.

Semantics, per tick and lane:

- each ant scores its 8 neighbouring patches (off-world ones excluded):
  foraging ants prefer chemical above the sniff threshold 0.05,
  ``8 log(1 + chem) + gumbel``; ants carrying food head for the nest,
  ``-dist2(patch, nest) + 0.5 gumbel``; the ant moves to the best patch;
- an ant without food that lands on food picks one unit up; an ant with
  food that lands on the nest drops it; every ant carrying food after
  that deposits 60 units of chemical on its patch;
- the field diffuses (each patch hands ``rate / 8`` of its value to each
  in-world neighbour and keeps what would fall off the edge), then
  evaporates by ``evaporation``;
- the objective is the first tick each of the 3 food sources is empty,
  capped at ``max_ticks``.

Lanes are replicated individuals: each individual's key splits into
``replicates`` lane keys and the objectives (and the state summaries) are
reduced by the median across replicates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

OFFSETS = np.array([(-1, -1), (-1, 0), (-1, 1), (0, -1),
                    (0, 1), (1, -1), (1, 0), (1, 1)], np.int32)
SNIFF = 0.05
DEPOSIT = 60.0

# columns of a lane's summary row: 3 first-empty ticks, food units taken per
# source, ants carrying food, the sum of the ants' flat patch indices, the
# field's total chemical and its projection on a fixed +-1 pattern
EXACT_COLUMNS = slice(0, 8)
CHEM_SUM, CHEM_PROJ = 8, 9
N_COLUMNS = 10


def _dist2(w, cy, cx):
    ii = np.arange(w)
    return (ii[:, None] - cy) ** 2 + (ii[None, :] - cx) ** 2


def world(model: dict):
    """(food0 (W,W) f32, source masks (3,W,W) bool, nest (W,W) bool,
    dist2 to the nest (W,W) f32) for the model settings ``model``."""
    w = int(model["world_size"])
    c = w // 2
    r2 = float(model["food_radius"]) ** 2
    centers = [(c, c + int(0.6 * c)),
               (c + int(0.6 * c), c - int(0.6 * c)),
               (c - int(0.8 * c), c - int(0.8 * c))]
    masks = np.stack([_dist2(w, cy, cx) <= r2 for cy, cx in centers])
    food0 = np.zeros((w, w), np.float32)
    for i in range(3):
        food0[masks[i]] = 1.0 + (i % 2)
    nest = _dist2(w, c, c) <= float(model["nest_radius"]) ** 2
    return food0, masks, nest, _dist2(w, c, c).astype(np.float32)


def projection(w: int) -> np.ndarray:
    """A fixed +-1 pattern over the world (same for every run)."""
    rng = np.random.default_rng(20150614)
    return rng.choice(np.array([-1.0, 1.0], np.float32), size=(w, w))


def summarize(model: dict, ticks_empty, chem, food, ant_pos, carrying):
    """Per-lane summary rows (L, N_COLUMNS) f32 of a final state."""
    food0, masks, _, _ = world(model)
    w = food0.shape[0]
    m = jnp.asarray(masks, jnp.float32)
    taken = (m * (jnp.asarray(food0) - food.astype(jnp.float32))[:, None]
             ).sum(axis=(2, 3))
    flat = (ant_pos[..., 0] * w + ant_pos[..., 1]).sum(axis=1)
    chem = chem.astype(jnp.float32)
    return jnp.concatenate([
        ticks_empty.astype(jnp.float32), taken,
        carrying.sum(axis=1, dtype=jnp.int32)[:, None].astype(jnp.float32),
        flat[:, None].astype(jnp.float32),
        chem.sum(axis=(1, 2))[:, None],
        (chem * jnp.asarray(projection(w))).sum(axis=(1, 2))[:, None],
    ], axis=1)


def diffuse(chem, rate, evaporation):
    """Plain NetLogo diffusion on a bounded world, then evaporation.
    chem (L,W,W) f32; rate, evaporation (L,) fractions."""
    w = chem.shape[-1]
    share = chem * (rate / 8.0)[:, None, None]
    padded = jnp.pad(share, ((0, 0), (1, 1), (1, 1)))
    inside = jnp.pad(jnp.ones((w, w), jnp.float32), 1)
    received = jnp.zeros_like(chem)
    neighbours = jnp.zeros((w, w), jnp.float32)
    for dy, dx in OFFSETS:
        received = received + padded[:, 1 + dy:1 + dy + w, 1 + dx:1 + dx + w]
        neighbours = neighbours + inside[1 + dy:1 + dy + w, 1 + dx:1 + dx + w]
    return (chem - share * neighbours + received) * (
        1.0 - evaporation)[:, None, None]


def simulate(model: dict, keys, diffusion_pct, evaporation_pct,
             chem_dtype=jnp.float32):
    """Run ``max_ticks`` ticks for lanes (keys (L,), rates (L,) in percent).
    Returns per-lane summary rows (L, N_COLUMNS). ``chem_dtype`` stores the
    field between ticks (float32 for the reference; the control lowers it)."""
    food0, masks, nest, toward = world(model)
    w, p = food0.shape[0], int(model["population"])
    ticks = int(model["max_ticks"])
    n = keys.shape[0]
    rate = jnp.clip(diffusion_pct / 100.0, 0.0, 1.0)
    evap = jnp.clip(evaporation_pct / 100.0, 0.0, 1.0)
    offsets = jnp.asarray(OFFSETS)
    nest = jnp.asarray(nest)
    toward = jnp.asarray(toward)
    m = jnp.asarray(masks, jnp.float32)

    def lane(chem, food, pos, carrying, key):
        cand = pos[:, None, :] + offsets[None]                 # (P, 8, 2)
        inb = ((cand >= 0) & (cand < w)).all(-1)
        cand = jnp.clip(cand, 0, w - 1)
        chem_n = jnp.where(inb, chem[cand[..., 0], cand[..., 1]], 0.0)
        g = jax.random.gumbel(key, (p, 8))
        sniff = jnp.where(chem_n > SNIFF, chem_n, 0.0)
        forage = jnp.where(inb, jnp.log1p(sniff) * 8.0 + g, -1e9)
        home = jnp.where(inb, -toward[cand[..., 0], cand[..., 1]] + 0.5 * g,
                         -1e9)
        best = jnp.argmax(jnp.where(carrying[:, None], home, forage), -1)
        pos = cand[jnp.arange(p), best]
        pickup = ~carrying & (food[pos[:, 0], pos[:, 1]] > 0)
        drop = carrying & nest[pos[:, 0], pos[:, 1]]
        carrying = (carrying | pickup) & ~drop
        food = jnp.maximum(
            food.at[pos[:, 0], pos[:, 1]].add(-pickup.astype(jnp.float32)),
            0.0)
        deposit = jnp.zeros((w, w), jnp.float32).at[pos[:, 0], pos[:, 1]].add(
            DEPOSIT * carrying.astype(jnp.float32))
        return pos, carrying, food, deposit

    def tick(state, t):
        chem, food, pos, carrying, empty, rng = state
        split = jax.vmap(jax.random.split)(rng)
        rng, move = split[:, 0], split[:, 1]
        pos, carrying, food, deposit = jax.vmap(lane)(
            chem.astype(jnp.float32), food, pos, carrying, move)
        chem = diffuse(chem.astype(jnp.float32) + deposit, rate, evap)
        left = (m[None] * food[:, None]).sum(axis=(2, 3))
        empty = jnp.where((left <= 0) & (empty == ticks), t, empty)
        return (chem.astype(chem_dtype), food, pos, carrying, empty, rng), None

    state = (jnp.zeros((n, w, w), chem_dtype),
             jnp.broadcast_to(jnp.asarray(food0), (n, w, w)),
             jnp.full((n, p, 2), w // 2, jnp.int32),
             jnp.zeros((n, p), bool),
             jnp.full((n, 3), ticks, jnp.int32),
             keys)
    (chem, food, pos, carrying, empty, _), _ = jax.lax.scan(
        tick, state, jnp.arange(ticks, dtype=jnp.int32))
    return summarize(model, empty, chem, food, pos, carrying)


def replicated(model: dict, replicates: int, keys, genomes,
               chem_dtype=jnp.float32):
    """Individuals (keys (n,), genomes (n, 2) = diffusion, evaporation in
    percent) -> (n, N_COLUMNS): every individual runs ``replicates`` lanes
    on keys split from its own, reduced by the median across them."""
    n = genomes.shape[0]
    lane_keys = jax.vmap(lambda k: jax.random.split(k, replicates))(keys)
    lane_keys = lane_keys.reshape(n * replicates)
    lanes = jnp.repeat(genomes, replicates, axis=0)
    rows = simulate(model, lane_keys, lanes[:, 0], lanes[:, 1], chem_dtype)
    return jnp.median(rows.reshape(n, replicates, -1), axis=1)


def population_chunk(bounds, seed: int, i: int, size: int):
    """Chunk ``i`` of the streamed initial population drawn from ``seed``:
    (keys (size,), genomes (size, D)) uniform in ``bounds``."""
    lo = jnp.array([b[0] for b in bounds], jnp.float32)
    hi = jnp.array([b[1] for b in bounds], jnp.float32)
    kc = jax.random.fold_in(jax.random.key(seed), i)
    kg, ke = jax.random.split(kc)
    genomes = jax.random.uniform(kg, (size, len(bounds)), jnp.float32) * (
        hi - lo) + lo
    return jax.random.split(ke, size), genomes


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    """Numbers compared for a sample of individuals' summary rows:
    ``diverged``, the share of individuals whose exact columns (ticks, food
    taken, ants carrying, ant positions) differ at all, and ``chem_gap``, the
    median over individuals of the larger relative gap of the field's total
    and of its projection, against the reference's total."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    exact = (got[:, EXACT_COLUMNS] != want[:, EXACT_COLUMNS]).any(axis=1)
    scale = np.maximum(np.abs(want[:, CHEM_SUM]), 1e-30)
    gap = np.maximum(np.abs(got[:, CHEM_SUM] - want[:, CHEM_SUM]),
                     np.abs(got[:, CHEM_PROJ] - want[:, CHEM_PROJ])) / scale
    bad = ~np.isfinite(got).all(axis=1)
    return {"diverged": float(np.mean(exact | bad)),
            "chem_gap": float(np.median(np.where(bad, np.inf, gap)))}
