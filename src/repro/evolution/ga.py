"""GA drivers: generational (paper Listing 4) and steady-state NSGA-II.

``eval_fn(keys, genomes) -> objectives`` is the *fitness task* — in the
paper's workflow terms it is the (replicated, aggregated) model-execution
capsule; here it is any pure JAX function, e.g.
``explore.replication.replicated_median(ants fitness)`` or an LM
hyper-parameter probe. Everything is fixed-shape and jit-able; one GA step is
one device program.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.evolution import nsga2
from repro.evolution.nsga2 import NSGA2Config


class GAState(NamedTuple):
    genomes: jnp.ndarray       # (mu, D)
    objectives: jnp.ndarray    # (mu, M)
    valid: jnp.ndarray         # (mu,) bool
    rng: jax.Array
    generation: jnp.ndarray    # () i32
    evaluations: jnp.ndarray   # () i32


def init_state(cfg: NSGA2Config, key) -> GAState:
    k_pop, k_rng = jax.random.split(key)
    lo, hi = cfg.lo(), cfg.hi()
    genomes = jax.random.uniform(
        k_pop, (cfg.mu, cfg.genome_dim), jnp.float32) * (hi - lo) + lo
    return GAState(
        genomes=genomes,
        objectives=jnp.full((cfg.mu, cfg.n_objectives), nsga2.BIG, jnp.float32),
        valid=jnp.zeros((cfg.mu,), bool),
        rng=k_rng,
        generation=jnp.int32(0),
        evaluations=jnp.int32(0),
    )


def evaluated(out):
    """``(objectives, lanes)`` of an evaluation's output. A fitness task
    returns the (N, M) objectives, or ``(objectives, lanes)`` where
    ``lanes`` is what its simulated lanes ended in (e.g. ``AntsState``)."""
    return out if isinstance(out, tuple) else (out, None)


def evaluate_initial(cfg: NSGA2Config, state: GAState, eval_fn) -> GAState:
    rng, k_eval = jax.random.split(state.rng)
    keys = jax.random.split(k_eval, cfg.mu)
    objectives, _ = evaluated(eval_fn(keys, state.genomes))
    return state._replace(objectives=objectives,
                          valid=jnp.ones((cfg.mu,), bool),
                          rng=rng,
                          evaluations=state.evaluations + cfg.mu)


class StepRecord(NamedTuple):
    """What one (mu + lambda) generation saw and made, beside its new
    state: the parents before the step, the children with the keys they
    were evaluated on (raw key data) and their objectives, and what the
    evaluation's lanes ended in (``None`` where it returns objectives
    only)."""
    parent_genomes: jnp.ndarray     # (mu, D)
    parent_objectives: jnp.ndarray  # (mu, M)
    parent_valid: jnp.ndarray       # (mu,) bool
    children: jnp.ndarray           # (lam, D)
    child_keys: jnp.ndarray         # (lam, 2) u32 key data
    child_objectives: jnp.ndarray   # (lam, M)
    lanes: Any


def make_recorded_step(cfg: NSGA2Config, eval_fn: Callable,
                       lam: int) -> Callable:
    """One (mu + lambda) NSGA-II generation as a pure function
    ``state -> (state, StepRecord)``."""

    def step(state: GAState):
        rng, k_off, k_eval = jax.random.split(state.rng, 3)
        with jax.named_scope("islands.select"):
            ranks = nsga2.nondominated_ranks(state.objectives, state.valid)
            crowd = nsga2.crowding_distance(state.objectives, ranks)
            children, _ = nsga2.make_offspring(cfg, k_off, state.genomes,
                                               ranks, crowd, lam)
        keys = jax.random.split(k_eval, lam)
        child_obj, lanes = evaluated(eval_fn(keys, children))
        pool_g = jnp.concatenate([state.genomes, children])
        pool_o = jnp.concatenate([state.objectives, child_obj])
        pool_v = jnp.concatenate([state.valid, jnp.ones((lam,), bool)])
        with jax.named_scope("islands.select"):
            idx, _, _ = nsga2.select_mu(cfg, pool_g, pool_o, pool_v)
        new = GAState(
            genomes=pool_g[idx],
            objectives=pool_o[idx],
            valid=pool_v[idx],
            rng=rng,
            generation=state.generation + 1,
            evaluations=state.evaluations + lam,
        )
        return new, StepRecord(state.genomes, state.objectives, state.valid,
                               children, jax.random.key_data(keys),
                               child_obj, lanes)

    return step


def make_step(cfg: NSGA2Config, eval_fn: Callable, lam: int) -> Callable:
    """One (mu + lambda) NSGA-II generation as a pure function."""
    step = make_recorded_step(cfg, eval_fn, lam)
    return lambda state: step(state)[0]


def run_generational(cfg: NSGA2Config, eval_fn, key, *, lam: int,
                     generations: int, jit: bool = True,
                     hooks=()) -> GAState:
    """Paper Listing 4: GenerationalGA(evolution)(fitness, lambda)."""
    state = init_state(cfg, key)
    init_eval = jax.jit(functools.partial(evaluate_initial, cfg,
                                          eval_fn=eval_fn)) if jit else \
        functools.partial(evaluate_initial, cfg, eval_fn=eval_fn)
    state = init_eval(state)
    step = make_step(cfg, eval_fn, lam)
    if jit:
        step = jax.jit(step)
    for _ in range(generations):
        state = step(state)
        for hook in hooks:
            hook(state)
    return state


# ---------------------------------------------------------------------------
# Paper-scale streaming initialization (§4.6: "200,000 individuals evaluated
# in one hour" on EGI). The initial population is generated and evaluated in
# device-sized chunks; each chunk is a *pure job* (a deterministic function
# of (seed, chunk index)) so it can be delegated to an unreliable
# EnvironmentPool, resubmitted on failure, and verified by fingerprint —
# results are bit-exact regardless of which environment evaluated what, and
# the contiguous completed prefix checkpoints to disk for mid-population
# resume.
# ---------------------------------------------------------------------------
class StreamingResult(NamedTuple):
    """Outcome of one (possibly interrupted/resumed) streaming evaluation."""
    genomes: Optional[np.ndarray]      # (n_total, D) — None when interrupted
    objectives: Optional[np.ndarray]   # (n_total, M) — None when interrupted
    chunks_done: int
    chunks_total: int
    resumed_chunks: int                # chunks served from the checkpoint
    interrupted: bool
    attempts: int                      # environment attempts incl. retries
    wall_s: float


def chunk_sizes(n_total: int, chunk: int) -> List[int]:
    """Chunk layout of a streamed population (full chunks + remainder)."""
    sizes = [chunk] * (n_total // chunk)
    if n_total % chunk:
        sizes.append(n_total % chunk)
    return sizes


def population_chunk(cfg: NSGA2Config, seed: int, i: int, size: int):
    """Deterministic chunk ``i`` of the initial population: ``(keys,
    genomes)``. Pure in (cfg, seed, i, size) — the property that makes
    chunks resubmittable, checkpointable, and bit-exact under failures."""
    kc = jax.random.fold_in(jax.random.key(seed), i)
    kg, ke = jax.random.split(kc)
    lo, hi = cfg.lo(), cfg.hi()
    genomes = jax.random.uniform(
        kg, (size, cfg.genome_dim), jnp.float32) * (hi - lo) + lo
    keys = jax.random.split(ke, size)
    return keys, genomes


def make_chunk_task(cfg: NSGA2Config, eval_fn: Callable, seed: int):
    """Wrap one chunk evaluation as a PyTask so the environment layer owns
    delegation, retry, speculation, and fingerprint verification. The
    context carries only ``(chunk, size)`` ints: inputs digest cheaply,
    and the genome/key material regenerates inside the job."""
    from repro.core.prototype import Val
    from repro.core.task import PyTask
    jeval = jax.jit(eval_fn)

    def fn(ctx):
        # host spans of one chunk (jax.profiler.TraceAnnotation: recorded
        # only while a profiler trace runs, on the device trace's clock)
        span = jax.profiler.TraceAnnotation
        i, size = int(ctx["chunk"]), int(ctx["size"])
        with span("repro.chunk.inputs"):
            keys, genomes = population_chunk(cfg, seed, i, size)
        with span("repro.chunk.dispatch"):
            out = jeval(keys, genomes)
        with span("repro.chunk.wait"):
            jax.block_until_ready(out)
        with span("repro.chunk.fetch"):
            objectives = np.asarray(out)
        return {"objectives": objectives}

    return PyTask("init_chunk", fn,
                  inputs=(Val("chunk", int), Val("size", int)),
                  outputs=(Val("objectives"),))


def evaluate_population_streaming(
        cfg: NSGA2Config, eval_fn: Callable, seed: int, *, n_total: int,
        chunk: int = 4096, environment=None, checkpoint_dir: str = None,
        checkpoint_every: int = 8, stop_after_chunks: Optional[int] = None,
        record=None, progress: Callable[[int, int], None] = None,
        service=None, experiment_id: str = "ga-init"
        ) -> StreamingResult:
    """Evaluate an ``n_total``-individual initial population in streaming
    chunks, optionally through a (fault-injected) environment or pool —
    or as one tenant of a shared ExplorationService.

    Args:
        cfg: GA configuration (bounds/dims/objectives).
        eval_fn: ``(keys, genomes) -> objectives`` fitness batch.
        seed: population seed — the whole run is a pure function of it.
        n_total: population size (the paper's 200,000).
        chunk: individuals per job (one device program per job).
        environment: Environment or EnvironmentPool; None = serial
            reference loop (bit-exact baseline).
        service: ExplorationService to delegate chunks to (mutually
            exclusive with ``environment``) — the GA then shares the
            service's pool with concurrent tenants, and completed chunks
            are memoized across driver restarts by the service cache.
        experiment_id: this run's tenant id on the service.
        checkpoint_dir: when given, the contiguous completed prefix is
            committed there every ``checkpoint_every`` chunks and the run
            resumes from the newest commit.
        stop_after_chunks: evaluate only this many chunks then return
            ``interrupted=True`` (after committing a checkpoint) — the
            mid-population kill switch the resume test/bench drives.
        record: optional RunRecord; one per-attempt TaskRecord is appended
            per chunk (mode "stream"; resumed chunks appear as cache hits).
        progress: optional ``(chunks_done, chunks_total)`` callback.
    """
    from repro import checkpoint
    from repro.core.cache import inputs_digest
    from repro.core.prototype import Context
    from repro.core.scheduler import TaskRecord

    if service is not None and environment is not None:
        raise ValueError("pass either environment= or service=, not both")
    t0 = time.monotonic()
    sizes = chunk_sizes(n_total, chunk)
    n_chunks = len(sizes)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    task = make_chunk_task(cfg, eval_fn, seed)
    done: List[Optional[np.ndarray]] = [None] * n_chunks

    # -- resume: restore the contiguous prefix committed last run ----------
    resumed = 0
    if checkpoint_dir is not None:
        last = checkpoint.latest_step(checkpoint_dir)
        if last:
            like = {"objectives": jax.ShapeDtypeStruct(
                (int(offsets[last]), cfg.n_objectives), jnp.float32)}
            prefix = np.asarray(
                checkpoint.restore(checkpoint_dir, last, like)["objectives"])
            for i in range(last):
                done[i] = prefix[offsets[i]:offsets[i + 1]]
            resumed = last
            if record is not None:
                for i in range(last):
                    record.tasks.append(TaskRecord(
                        task=task.name, capsule=i,
                        environment="checkpoint",
                        inputs_digest=inputs_digest(
                            task, Context(chunk=i, size=sizes[i])),
                        started_s=0.0, wall_s=0.0, retries=0,
                        cache_hit=True, mode="cache"))

    committed = [resumed]

    def commit(force: bool = False):
        # Each commit rewrites the whole completed prefix (one atomic
        # artifact, restore needs no chunk manifest); checkpoint_every
        # bounds how often that O(prefix) write happens, and pruning keeps
        # only the newest commits on disk.
        if checkpoint_dir is None:
            return
        k = committed[0]
        while k < n_chunks and done[k] is not None:
            k += 1
        if k > committed[0] and (force or k - committed[0]
                                 >= checkpoint_every or k == n_chunks):
            checkpoint.save(
                checkpoint_dir, k,
                {"objectives": np.concatenate(done[:k], axis=0)},
                blocking=True)
            checkpoint.prune(checkpoint_dir, keep=2)
            committed[0] = k

    todo = [i for i in range(n_chunks) if done[i] is None]
    if stop_after_chunks is not None:
        todo = todo[:max(0, stop_after_chunks - resumed)]
    attempts = 0

    def note(i, meta):
        nonlocal attempts
        n_att = len(meta.get("attempts") or ()) or 1
        attempts += n_att
        if record is not None:
            record.tasks.append(TaskRecord(
                task=task.name, capsule=i,
                environment=(environment.name if environment is not None
                             else getattr(service, "name", None)
                             or "inline"),
                inputs_digest=inputs_digest(
                    task, Context(chunk=i, size=sizes[i])),
                started_s=meta["t0"] - t0 if "t0" in meta else 0.0,
                wall_s=meta.get("wall_s", 0.0),
                retries=meta.get("retries", 0), cache_hit=False,
                mode="stream",
                attempts=list(meta.get("attempts") or ()) or None))

    if service is not None:
        if todo:
            tids = service.submit_tasks(
                experiment_id,
                [(task, Context(chunk=i, size=sizes[i])) for i in todo])
            tid_to_i = dict(zip(tids, todo))
            n_done = 0
            for tid, out in service.as_completed(experiment_id, tids):
                i = tid_to_i[tid]
                if out is None:
                    service.result(experiment_id, tid)  # raises the error
                done[i] = out["objectives"]
                note(i, {"retries": 0, "wall_s": 0.0})
                n_done += 1
                commit()
                if progress:
                    progress(resumed + n_done, n_chunks)
    elif environment is None:
        for n_done, i in enumerate(todo):
            a_t0 = time.monotonic()
            out = task.run(Context(chunk=i, size=sizes[i]))
            done[i] = out["objectives"]
            note(i, {"t0": a_t0, "wall_s": time.monotonic() - a_t0,
                     "retries": 0})
            commit()
            if progress:
                progress(resumed + n_done + 1, n_chunks)
    elif todo:
        import concurrent.futures as cf
        futures = {environment.submit_async(
            task, Context(chunk=i, size=sizes[i])): i for i in todo}
        n_done = 0
        for f in cf.as_completed(futures):
            i = futures[f]
            out, meta = f.result()
            done[i] = out["objectives"]
            note(i, meta)
            n_done += 1
            commit()
            if progress:
                progress(resumed + n_done, n_chunks)

    commit(force=True)
    n_ready = sum(d is not None for d in done)
    if n_ready < n_chunks:
        return StreamingResult(
            genomes=None, objectives=None, chunks_done=n_ready,
            chunks_total=n_chunks, resumed_chunks=resumed, interrupted=True,
            attempts=attempts, wall_s=time.monotonic() - t0)
    genomes = np.concatenate(
        [np.asarray(population_chunk(cfg, seed, i, sizes[i])[1])
         for i in range(n_chunks)], axis=0)
    return StreamingResult(
        genomes=genomes, objectives=np.concatenate(done, axis=0),
        chunks_done=n_chunks, chunks_total=n_chunks, resumed_chunks=resumed,
        interrupted=False, attempts=attempts,
        wall_s=time.monotonic() - t0)


def select_top_streaming(cfg: NSGA2Config, genomes, objectives, k: int,
                         block: int = 2048):
    """Top-``k`` of an archive-scale population by (rank, -crowding),
    hierarchically: the O(N^2) dominance pass runs per block, block winners
    re-compete — 200k individuals never enter one quadratic pass."""
    g = np.asarray(genomes)
    o = np.asarray(objectives, dtype=np.float32)

    def top(gi, oi, kk):
        valid = jnp.ones((len(oi),), bool)
        ranks = nsga2.nondominated_ranks(jnp.asarray(oi), valid)
        crowd = nsga2.crowding_distance(jnp.asarray(oi), ranks)
        keyv = nsga2.truncation_key(ranks, crowd, valid)
        idx = np.asarray(jnp.argsort(keyv))[:kk]
        return gi[idx], oi[idx]

    while len(g) > max(k, block):
        gs, os_ = [], []
        for lo in range(0, len(g), block):
            gi, oi = top(g[lo:lo + block], o[lo:lo + block],
                         min(k, block, len(g) - lo))
            gs.append(gi)
            os_.append(oi)
        g2, o2 = np.concatenate(gs), np.concatenate(os_)
        if len(g2) >= len(g):
            break
        g, o = g2, o2
    return top(g, o, min(k, len(g)))


def init_state_from_population(cfg: NSGA2Config, key, genomes,
                               objectives) -> GAState:
    """Seed a GAState from an already-evaluated population (the streamed
    200k init): the best ``mu`` by NSGA-II truncation become the
    population; evaluations counts the full population."""
    g, o = select_top_streaming(cfg, genomes, objectives, cfg.mu)
    return GAState(
        genomes=jnp.asarray(g, jnp.float32),
        objectives=jnp.asarray(o, jnp.float32),
        valid=jnp.ones((len(g),), bool),
        rng=key,
        generation=jnp.int32(0),
        evaluations=jnp.int32(len(np.asarray(genomes))),
    )


def run_chunked(cfg: NSGA2Config, eval_fn, key, *, lam: int,
                generations: int, chunk: int = 8) -> GAState:
    """Same result as run_generational but scans `chunk` generations per
    device program — the launcher's checkpoint boundary."""
    state = init_state(cfg, key)
    state = jax.jit(functools.partial(evaluate_initial, cfg,
                                      eval_fn=eval_fn))(state)
    step = make_step(cfg, eval_fn, lam)

    @jax.jit
    def run_chunk(state):
        def body(s, _):
            return step(s), None
        s, _ = jax.lax.scan(body, state, None, length=chunk)
        return s

    for _ in range(generations // chunk):
        state = run_chunk(state)
    for _ in range(generations % chunk):
        state = jax.jit(step)(state)
    return state
