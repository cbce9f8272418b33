"""Replication (paper §4.4): run a stochastic task several times with
independent random sources and aggregate — "OpenMOLE provides the necessary
mechanisms to easily replicate executions and aggregate the results using a
simple statistical descriptor."

Two forms:
- ``Replicate(capsule, seed_sampling, statistic_capsule)`` — the workflow
  construct (exploration + aggregation transitions), Listing 3 one-to-one.
- ``replicated_median(eval_fn, n)`` — the fused device-side form used inside
  GA fitness: vmap over replicate keys, median across the replicate axis.
  On a mesh this folds replication into the same SPMD program as the
  candidate fan-out (lanes = candidates x replicates).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.dsl import Puzzle, aggregate, explore
from repro.core.workflow import Capsule
from repro.explore.sampling import SeedSampling


def Replicate(model_capsule: Capsule, seed_sampling: SeedSampling,
              statistic_capsule: Capsule) -> Puzzle:
    """model runs once per seed; outputs aggregate into the statistic task."""
    p = Puzzle.from_capsule(_identity_head(model_capsule))
    return (p >> explore(seed_sampling) >> model_capsule
            >> aggregate() >> statistic_capsule)


def _identity_head(model_capsule: Capsule) -> Capsule:
    from repro.core.task import PyTask
    return Capsule(PyTask(f"{model_capsule.task.name}_head", lambda ctx: {}))


def replicated(eval_fn: Callable, n_replicates: int,
               reducer: Callable = jnp.median) -> Callable:
    """Lift eval_fn(key, genome)->objectives to (keys, genomes)->(N, M)
    objectives with `n_replicates` independent seeds reduced per genome."""

    def replicated_eval(keys, genomes):
        def per_genome(key, genome):
            rkeys = jax.random.split(key, n_replicates)
            objs = jax.vmap(lambda k: eval_fn(k, genome))(rkeys)
            return reducer(objs, axis=0)

        return jax.vmap(per_genome)(keys, genomes)

    return replicated_eval


def replicated_batch(batch_eval_fn: Callable, n_replicates: int,
                     reducer: Callable = jnp.median) -> Callable:
    """Same but for natively-batched eval fns (keys (L,), genomes (L, D)) ->
    (L, M): replicates become extra lanes, reduced after the flat call.
    This is the high-throughput path for the ants simulator."""
    with_lanes = replicated_batch_state(
        lambda keys, genomes: (batch_eval_fn(keys, genomes), None),
        n_replicates, reducer)
    return lambda keys, genomes: with_lanes(keys, genomes)[0]


def replicated_batch_state(batch_eval_fn: Callable, n_replicates: int,
                           reducer: Callable = jnp.median) -> Callable:
    """``replicated_batch`` for eval fns that return ``(objectives,
    lanes)``: returns the reduced (N, M) objectives and ``lanes`` as the
    flat call left them, lane ``i * n_replicates + r`` being replicate
    ``r`` of genome ``i``."""

    def replicated_eval(keys, genomes):
        n, d = genomes.shape
        rkeys = jax.vmap(lambda k: jax.random.split(k, n_replicates))(keys)
        flat_keys = rkeys.reshape(n * n_replicates)
        flat_genomes = jnp.repeat(genomes, n_replicates, axis=0)
        objs, lanes = batch_eval_fn(flat_keys, flat_genomes)
        objs = objs.reshape(n, n_replicates, -1)
        return reducer(objs, axis=1), lanes

    return replicated_eval
