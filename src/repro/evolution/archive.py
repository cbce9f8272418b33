"""Global Pareto archive — the island model's merge target (paper §4.6:
"When an island is finished, its final population is merged back into a
global archive")."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.evolution import nsga2
from repro.runtime.sharding import sharded_dominance_pass


class Archive(NamedTuple):
    genomes: jnp.ndarray      # (A, D)
    objectives: jnp.ndarray   # (A, M)
    valid: jnp.ndarray        # (A,) bool


def init_archive(size, genome_dim, n_objectives):
    return Archive(
        genomes=jnp.zeros((size, genome_dim), jnp.float32),
        objectives=jnp.full((size, n_objectives), nsga2.BIG, jnp.float32),
        valid=jnp.zeros((size,), bool),
    )


def merge(archive: Archive, genomes, objectives, valid=None) -> Archive:
    """Truncate (archive + incoming) to archive size by (rank, -crowding).

    The pool-wide non-dominated sort is the archive-scale O(pool^2) hot spot;
    it runs through the mesh-sharded single-pass sweep (which falls back to
    the local fused kernel when no mesh is active)."""
    a = archive.genomes.shape[0]
    if valid is None:
        valid = jnp.ones((genomes.shape[0],), bool)
    pool_g = jnp.concatenate([archive.genomes, genomes.astype(jnp.float32)])
    pool_o = jnp.concatenate([archive.objectives,
                              objectives.astype(jnp.float32)])
    pool_v = jnp.concatenate([archive.valid, valid])
    ranks = nsga2.nondominated_ranks(pool_o, pool_v,
                                     pass_fn=sharded_dominance_pass)
    crowd = nsga2.crowding_distance(pool_o, ranks)
    key_val = nsga2.truncation_key(ranks, crowd, pool_v)
    order = jnp.argsort(key_val)[:a]
    return Archive(pool_g[order], pool_o[order], pool_v[order])


@jax.jit
def pareto_front(archive: Archive):
    """Boolean mask of rank-0 members (host-side readout helper; one
    compiled program per archive shape, not one per call)."""
    ranks = nsga2.nondominated_ranks(archive.objectives, archive.valid)
    return archive.valid & (ranks == 0)
