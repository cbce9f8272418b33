"""Plain reference of the island GA's selection, in numpy.

Written from NSGA-II's description (Deb et al. 2002) and the order the
system states for truncation, with no import from the program under test:

- ``ranks``: O(N^2) dominance counting among the valid rows (``a``
  dominates ``b`` when it is no worse in every objective and better in
  one, objectives minimized), fronts peeled by count; invalid rows get
  rank N;
- ``crowding``: per front and objective, the rows sorted by value (ties
  by position), the two ends infinite, each other row the gap between its
  neighbours over the front's span (at least 1e-12), summed over the
  objectives; all in float32, objective by objective;
- truncation: a stable sort on the float32 key ``rank * 1e6 -
  min(crowding, 1e5)`` (invalid rows last, by position), the key the
  system states for environmental selection, the islands' top-k and the
  archive merge. In float32 that key keeps crowding to the key's spacing
  (0.0625 at rank 1, 1 at ranks 8-15): the reference keeps it so too;
- ``merge``: the archive followed by the incoming rows, truncated to the
  archive's size.
"""
from __future__ import annotations

import numpy as np

SPAN_FLOOR = np.float32(1e-12)
CROWD_CAP = np.float32(1e5)
RANK_SCALE = np.float32(1e6)
INVALID_RANK = 10 ** 9


def ranks(objectives, valid) -> np.ndarray:
    """(N,) int front index of each row (0 = Pareto); invalid rows N."""
    obj = np.asarray(objectives, np.float32)
    valid = np.asarray(valid, bool)
    n = obj.shape[0]
    le = (obj[:, None, :] <= obj[None, :, :]).all(-1)
    lt = (obj[:, None, :] < obj[None, :, :]).any(-1)
    dom = le & lt & valid[:, None] & valid[None, :]    # dom[j, i]: j over i
    count = dom.sum(axis=0)
    out = np.full(n, n, np.int64)
    active = valid.copy()
    r = 0
    while active.any():
        front = active & (count == 0)
        out[front] = r
        count = count - dom[front].sum(axis=0)
        active &= ~front
        r += 1
    return out


def crowding(objectives, rank) -> np.ndarray:
    """(N,) float32 crowding distance within each front."""
    obj = np.asarray(objectives, np.float32)
    n, m = obj.shape
    out = np.zeros(n, np.float32)
    for r in np.unique(rank):
        rows = np.flatnonzero(rank == r)
        for k in range(m):
            vals = obj[rows, k]
            order = rows[np.argsort(vals, kind="stable")]
            sv = obj[order, k]
            span = max(np.float32(sv.max() - sv.min()), SPAN_FLOOR)
            gap = np.full(len(order), np.inf, np.float32)
            if len(order) > 2:
                with np.errstate(over="ignore"):
                    gap[1:-1] = (sv[2:] - sv[:-2]) / span
            out[order] = out[order] + gap
    return out


def truncation_order(objectives, valid) -> np.ndarray:
    """Row indices best first: by rank, then by crowding, then position."""
    valid = np.asarray(valid, bool)
    rank = ranks(objectives, valid)
    crowd = np.minimum(crowding(objectives, rank), CROWD_CAP)
    rank = np.where(valid, rank, INVALID_RANK).astype(np.float32)
    key = rank * RANK_SCALE - np.maximum(crowd, np.float32(0.0))
    return np.argsort(key.astype(np.float32), kind="stable")


def select(genomes, objectives, valid, k: int):
    """The best ``k`` rows of a pool, in order: (genomes, objectives,
    valid)."""
    idx = truncation_order(objectives, valid)[:k]
    return (np.asarray(genomes)[idx], np.asarray(objectives)[idx],
            np.asarray(valid)[idx])


def merge(archive, incoming):
    """``archive`` and ``incoming`` as (genomes, objectives, valid); the
    archive's size of the pool they make, in order."""
    pool = [np.concatenate([a, b]) for a, b in zip(archive, incoming)]
    return select(*pool, len(archive[0]))


def island_epoch(parents, children, evolved, top_k: int, archive):
    """The selection of an island epoch whose last step bred ``children``:

    - ``parents``: (genomes, objectives, valid), each (n_islands, mu, ...),
      the islands before the step;
    - ``children``: (genomes, objectives), (n_islands, lam, ...), as
      evaluated;
    - ``evolved``: (genomes, objectives, valid) of the islands after the
      step, the pool the archive merge draws from;
    - ``archive``: (genomes, objectives, valid) before the merge.

    Returns (survivors of every island, each an (mu+lam -> mu) selection;
    the archive after the merge of each evolved island's best ``top_k``,
    or of the whole islands where ``top_k`` is 0 or at least mu).
    """
    pg, po, pv = (np.asarray(x) for x in parents)
    cg, co = (np.asarray(x) for x in children)
    n, mu = pv.shape
    survivors = [select(np.concatenate([pg[i], cg[i]]),
                        np.concatenate([po[i], co[i]]),
                        np.concatenate([pv[i], np.ones(len(cg[i]), bool)]),
                        mu) for i in range(n)]
    survivors = tuple(np.stack(x) for x in zip(*survivors))
    eg, eo, ev = (np.asarray(x) for x in evolved)
    if 0 < top_k < mu:
        best = [select(eg[i], eo[i], ev[i], top_k) for i in range(n)]
    else:                       # whole islands, as they stand
        best = [(eg[i], eo[i], ev[i]) for i in range(n)]
    incoming = tuple(np.concatenate(x) for x in zip(*best))
    return survivors, merge(tuple(np.asarray(x) for x in archive), incoming)


def mismatched_rows(got, want) -> int:
    """Rows of (genomes, objectives, valid) that differ at all."""
    g = [np.asarray(x) for x in got]
    w = [np.asarray(x) for x in want]
    lead = g[2].shape
    bad = np.zeros(lead, bool)
    for a, b in zip(g, w):
        a = a.reshape(lead + (-1,))
        b = b.reshape(lead + (-1,))
        bad |= (a != b).any(axis=-1)
    return int(bad.sum())
