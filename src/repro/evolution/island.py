"""The island model (paper §4.6 / Listing 5): many independently-evolving
sub-populations, periodically merged into a global Pareto archive, reseeded
from it, and repeated until the evaluation budget is spent.

TPU adaptation (DESIGN.md §2): islands are lanes of a leading ``island`` axis
sharded over the data (and pod) mesh axes. One *epoch* =

    vmap(K steady-state NSGA-II steps)  -- island-local, zero communication
    all-islands merge into the archive  -- the only collective (gather+sort)
    reseed islands from the archive     -- broadcast

The three stages are built separately (`make_evolve` / `make_merge` /
`make_reseed`) so the driver can either compose them bulk-synchronously
(`make_epoch`, bit-identical to the fused epoch) or software-pipeline them
(`run_islands(pipeline=True)`): the evaluation-heavy evolve of epoch k+1 is
dispatched right after the selection-heavy merge of epoch k, so
`simulate_batch` overlaps the archive's O(pool^2) dominance sort — the
double-buffered schedule. In pipelined mode the reseed draws from the archive
as of epoch k-1 (one epoch stale), which is exactly EGI's asynchronous-merge
semantics: islands never wait for the global archive to catch up.

EGI's asynchronous merges become (pipelined) bulk-synchronous epochs; K
controls the sync/async trade-off. Stragglers cannot exist inside an epoch
(fixed step count, SPMD); node loss is handled by checkpointing (archive +
island states) at superstep boundaries — losing a superstep loses only that
many epochs of those islands' work, the paper's own failure semantics.

Device residency: the synchronous driver runs *supersteps* — K epochs fused
into one `jax.lax.scan` inside one jitted, buffer-donating call — so the hot
path performs zero host transfers. Checkpoint snapshots are harvested
asynchronously at superstep boundaries (`copy_to_host_async` + independent
host buffers, so the next donated dispatch can reuse the device memory), and
`init_island_state` commits island-axis leaves to the active mesh with
explicit NamedShardings at birth (`place_island_state`): populations are
sharded before the first epoch rather than resharded inside it.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.evolution import ga, nsga2
from repro.evolution.archive import Archive, init_archive, merge
from repro.evolution.nsga2 import NSGA2Config
from repro.runtime.sharding import active_mesh, constrain, logical_to_spec


class IslandState(NamedTuple):
    islands: ga.GAState        # leaves have leading (n_islands,) dim
    archive: Archive
    epoch: jnp.ndarray         # () i32
    total_evaluations: jnp.ndarray


def _is_key(x) -> bool:
    return jnp.issubdtype(getattr(x, "dtype", None), jax.dtypes.prng_key)


def _constrain_islands(istate: ga.GAState) -> ga.GAState:
    """Pin the island axis to the data/pod mesh axes.

    Typed PRNG key leaves are skipped: GSPMD on jax 0.4.x cannot validate a
    leading-axis sharding against the key dtype's hidden trailing (2,) data
    dims inside scanned bodies (tile-assignment rank mismatch on u32[n, 2]).
    The keys are (n_islands,)-tiny; they ride along replicated."""
    def c(x):
        if x.ndim >= 1 and not _is_key(x):
            return constrain(x, ("island",) + (None,) * (x.ndim - 1))
        return x
    return jax.tree.map(c, istate)


def place_island_state(state: IslandState, mesh=None) -> IslandState:
    """Commit `state` to the mesh with explicit NamedShardings: island-axis
    leaves shard over the island mesh axes, the archive and scalars
    replicate. Without this, fresh inits and checkpoint resumes arrive
    replicated (or host-committed) and the first epoch pays a reshard.
    No-op without a mesh or on abstract values (eval_shape tracing)."""
    mesh = mesh or active_mesh()
    if mesh is None:
        return state
    leaves = jax.tree.leaves(state)
    if any(isinstance(x, jax.core.Tracer) for x in leaves):
        return state
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())

    def put_island(x):
        if x.ndim < 1 or _is_key(x):   # keys replicate: see _constrain_islands
            return jax.device_put(x, replicated)
        spec = logical_to_spec(("island",) + (None,) * (x.ndim - 1),
                               x.shape, mesh)
        return jax.device_put(x, NamedSharding(mesh, spec))

    return IslandState(
        islands=jax.tree.map(put_island, state.islands),
        archive=jax.tree.map(lambda x: jax.device_put(x, replicated),
                             state.archive),
        epoch=jax.device_put(state.epoch, replicated),
        total_evaluations=jax.device_put(state.total_evaluations, replicated),
    )


def init_island_state(cfg: NSGA2Config, key, *, n_islands: int,
                      archive_size: int) -> IslandState:
    keys = jax.random.split(key, n_islands)
    islands = jax.vmap(lambda k: ga.init_state(cfg, k))(keys)
    state = IslandState(
        islands=islands,
        archive=init_archive(archive_size, cfg.genome_dim, cfg.n_objectives),
        epoch=jnp.int32(0),
        total_evaluations=jnp.int32(0),
    )
    return place_island_state(state)


def _per_island(fn: Callable) -> Callable:
    """``fn`` (pytree with a leading island axis -> same) run on each
    device's own islands. Under a multi-device mesh it becomes a
    ``shard_map`` over the island mesh axes: island-local work needs no
    communication, and the TPU compiler cannot partition a Pallas kernel
    by itself. An island count the axes do not divide runs replicated on
    every device."""

    def run(tree):
        mesh = active_mesh()
        if mesh is None or mesh.size == 1:
            return fn(tree)
        n_i = jax.tree.leaves(tree)[0].shape[0]
        spec = logical_to_spec(("island",), (n_i,), mesh)
        return jax.shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                             check_vma=False)(tree)

    return run


# ---------------------------------------------------------------------------
# Epoch stages
# ---------------------------------------------------------------------------
def _repeat(fn: Callable, x, times: int):
    """``fn`` (x -> (x, record)) applied ``times`` times in one `lax.scan`:
    the last x and the last record."""
    blank = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                         jax.eval_shape(fn, x)[1])
    (x, record), _ = jax.lax.scan(lambda c, _: (fn(c[0]), None),
                                  (x, blank), None, length=times)
    return x, record


class EpochRecord(NamedTuple):
    """What an epoch's islands went through, beside its new state (none of
    it is checkpointed): each island's record of the epoch's last GA step,
    and the islands after evolve, before the reseed (the pool the archive
    merge drew from). Leaves have a leading (n_islands,) dim."""
    step: ga.StepRecord
    evolved: ga.GAState


def make_recorded_evolve(cfg: NSGA2Config, eval_fn: Callable, *, lam: int,
                         steps_per_epoch: int) -> Callable:
    """islands -> (islands after K island-local NSGA-II steps, each
    island's ``StepRecord`` of the last step): the evaluation-heavy stage,
    with zero cross-island communication."""
    step = ga.make_recorded_step(cfg, eval_fn, lam)

    def initial(s):
        with jax.named_scope("islands.init_eval"):
            return ga.evaluate_initial(cfg, s, eval_fn)

    def maybe_initial(istate: ga.GAState):
        return jax.lax.cond(istate.valid.any(), lambda s: s, initial,
                            istate)

    def evolve_local(islands: ga.GAState):
        # Islands arrive unevaluated in the first epoch only. Under vmap a
        # per-island cond is a select that runs the initial evaluation on
        # every island, so the question is asked once, over this device's
        # islands: when all are evaluated (every epoch after the first) the
        # initial evaluation is not executed at all; otherwise the
        # per-island cond evaluates the fresh ones and leaves the rest as
        # they were.
        islands = jax.lax.cond(islands.valid.any(axis=1).all(),
                               lambda s: s, jax.vmap(maybe_initial),
                               islands)
        return jax.vmap(lambda s: _repeat(step, s, steps_per_epoch))(
            islands)

    def evolve(islands: ga.GAState):
        islands = _constrain_islands(islands)
        islands, record = _per_island(evolve_local)(islands)
        return _constrain_islands(islands), record

    return evolve


def make_evolve(cfg: NSGA2Config, eval_fn: Callable, *, lam: int,
                steps_per_epoch: int) -> Callable:
    """islands -> islands after K island-local NSGA-II steps (the
    evaluation-heavy stage; zero cross-island communication)."""
    evolve = make_recorded_evolve(cfg, eval_fn, lam=lam,
                                  steps_per_epoch=steps_per_epoch)
    return lambda islands: evolve(islands)[0]


def make_merge(cfg: NSGA2Config, *, merge_top_k: int = 0) -> Callable:
    """(archive, islands) -> archive — the selection-heavy stage and the only
    cross-island communication.

    merge_top_k > 0: each island contributes only its best k individuals
    (by rank, then crowding) to the archive merge instead of its whole
    population — the merge's O(pool^2) dominance pass shrinks by
    (mu/k)^2 while preserving every island-local Pareto point for k >= the
    island front size (§Perf hillclimb; the paper's islands likewise merge
    *finished populations*, so this is a strict refinement). The per-island
    rank/crowding runs donor-batched: all islands' populations flatten into
    ONE grouped single-pass dominance launch instead of a vmapped launch per
    island pool."""

    def top_k(pop):
        """(objectives, valid) of n islands -> (n, merge_top_k) indices of
        each island's best, ranked in one grouped launch."""
        obj, valid = pop
        n, mu = valid.shape
        flat_o = obj.reshape(n * mu, -1)
        flat_v = valid.reshape(n * mu)
        groups = jnp.repeat(jnp.arange(n, dtype=jnp.int32), mu)
        ranks = nsga2.nondominated_ranks(flat_o, flat_v, groups=groups)
        crowd = nsga2.crowding_distance(flat_o, ranks, groups=groups,
                                        n_groups=n)
        key_val = nsga2.truncation_key(ranks, crowd, flat_v)
        return jnp.argsort(key_val.reshape(n, mu), axis=1)[:, :merge_top_k]

    def merge_islands(archive: Archive, islands: ga.GAState) -> Archive:
        n_i, mu = islands.genomes.shape[:2]
        if merge_top_k and merge_top_k < mu:
            idx = _per_island(top_k)((islands.objectives, islands.valid))
            sel_g = jnp.take_along_axis(islands.genomes, idx[..., None],
                                        axis=1)
            sel_o = jnp.take_along_axis(islands.objectives, idx[..., None],
                                        axis=1)
            sel_v = jnp.take_along_axis(islands.valid, idx, axis=1)
            flat_g = sel_g.reshape(n_i * merge_top_k, -1)
            flat_o = sel_o.reshape(n_i * merge_top_k, -1)
            flat_v = sel_v.reshape(n_i * merge_top_k)
        else:
            flat_g = islands.genomes.reshape(n_i * mu, -1)
            flat_o = islands.objectives.reshape(n_i * mu, -1)
            flat_v = islands.valid.reshape(n_i * mu)
        return merge(archive, flat_g, flat_o, flat_v)

    return merge_islands


def make_reseed(cfg: NSGA2Config, *, reseed_frac: float = 0.5) -> Callable:
    """(islands, archive) -> islands with a fraction of each population
    replaced by archive samples (the paper: "each island gets 50 individuals
    sampled from the global population")."""

    def reseed_islands(islands: ga.GAState, archive: Archive) -> ga.GAState:
        mu = islands.genomes.shape[1]
        k_all = jax.vmap(jax.random.split)(islands.rng)
        rngs, k_seed = k_all[:, 0], k_all[:, 1]

        def reseed(istate_g, istate_o, istate_v, k):
            a = archive.genomes.shape[0]
            n_replace = max(int(mu * reseed_frac), 1)
            pick = jax.random.randint(k, (n_replace,), 0, a)
            ok = archive.valid[pick]
            slots = jnp.arange(n_replace)
            # replace the last n_replace slots (population is unordered
            # post-selection; slots are arbitrary but fixed-shape)
            g = istate_g.at[mu - 1 - slots].set(
                jnp.where(ok[:, None], archive.genomes[pick],
                          istate_g[mu - 1 - slots]))
            o = istate_o.at[mu - 1 - slots].set(
                jnp.where(ok[:, None], archive.objectives[pick],
                          istate_o[mu - 1 - slots]))
            v = istate_v.at[mu - 1 - slots].set(
                jnp.where(ok, True, istate_v[mu - 1 - slots]))
            return g, o, v

        g, o, v = jax.vmap(reseed)(islands.genomes, islands.objectives,
                                   islands.valid, k_seed)
        islands = islands._replace(genomes=g, objectives=o, valid=v,
                                   rng=rngs)
        return _constrain_islands(islands)

    return reseed_islands


def make_recorded_epoch(cfg: NSGA2Config, eval_fn: Callable, *, lam: int,
                        steps_per_epoch: int, reseed_frac: float = 0.5,
                        merge_top_k: int = 0) -> Callable:
    """Returns jit-able epoch(state) -> (state, EpochRecord): the
    bulk-synchronous composition evolve -> merge -> reseed."""
    evolve = make_recorded_evolve(cfg, eval_fn, lam=lam,
                                  steps_per_epoch=steps_per_epoch)
    merge_islands = make_merge(cfg, merge_top_k=merge_top_k)
    reseed_islands = make_reseed(cfg, reseed_frac=reseed_frac)

    def epoch(state: IslandState):
        islands, step = evolve(state.islands)
        n_i = islands.genomes.shape[0]
        with jax.named_scope("islands.merge"):
            archive = merge_islands(state.archive, islands)
        with jax.named_scope("islands.reseed"):
            reseeded = reseed_islands(islands, archive)
        evals = state.total_evaluations + n_i * (
            steps_per_epoch * lam + (state.epoch == 0) * cfg.mu)
        return (IslandState(reseeded, archive, state.epoch + 1, evals),
                EpochRecord(step, islands))

    return epoch


def make_epoch(cfg: NSGA2Config, eval_fn: Callable, *, lam: int,
               steps_per_epoch: int, reseed_frac: float = 0.5,
               merge_top_k: int = 0) -> Callable:
    """Returns jit-able epoch(state) -> state (the bulk-synchronous
    composition evolve -> merge -> reseed)."""
    epoch = make_recorded_epoch(cfg, eval_fn, lam=lam,
                                steps_per_epoch=steps_per_epoch,
                                reseed_frac=reseed_frac,
                                merge_top_k=merge_top_k)
    return lambda state: epoch(state)[0]


def make_superstep(cfg: NSGA2Config, eval_fn: Callable, *, lam: int,
                   steps_per_epoch: int, reseed_frac: float = 0.5,
                   merge_top_k: int = 0) -> Callable:
    """Returns superstep(state, k) -> (state, EpochRecord of the k-th
    epoch): k epochs fused into ONE device program via `jax.lax.scan` over
    the bulk-synchronous epoch. jit it with k static (`static_argnums=1`)
    and the state donated (`donate_argnums=0`) and the evolve→merge→reseed
    chain runs k epochs with in-place buffers and zero host transfers — the
    device-resident hot path. The record comes out of the same program; it
    stays on the device until a caller reads it."""
    epoch = make_recorded_epoch(cfg, eval_fn, lam=lam,
                                steps_per_epoch=steps_per_epoch,
                                reseed_frac=reseed_frac,
                                merge_top_k=merge_top_k)

    def superstep(state: IslandState, k: int):
        return _repeat(epoch, state, k)

    return superstep


def host_snapshot(state: IslandState) -> IslandState:
    """An independent host-side copy of `state` for checkpointing: the live
    device buffers may be donated to the next superstep immediately after.
    Array leaves land as numpy (`copy_to_host_async` first, so the D2H
    copies overlap instead of serializing); typed PRNG keys round-trip
    through `key_data` into a fresh buffer sharing nothing with the donated
    state."""
    for leaf in jax.tree.leaves(state):
        if hasattr(leaf, "copy_to_host_async"):
            leaf.copy_to_host_async()

    def f(x):
        if _is_key(x):
            return jax.random.wrap_key_data(
                np.asarray(jax.random.key_data(x)))
        return np.asarray(x)

    return jax.tree.map(f, state)


def run_islands(cfg: NSGA2Config, eval_fn, key, *, n_islands: int,
                lam: int, steps_per_epoch: int, epochs: int,
                archive_size: int = 1024, checkpoint_fn=None,
                merge_top_k: int = 0, reseed_frac: float = 0.5,
                pipeline: bool = False, epochs_per_superstep: int = 0,
                start_state: IslandState = None,
                epoch_hook=None) -> IslandState:
    """Host loop over supersteps (the checkpoint/restart boundary).

    pipeline=False: supersteps — `epochs_per_superstep` epochs scanned into
    one jitted, donated device program each (`make_superstep`); the host
    only dispatches and harvests checkpoint snapshots at the boundaries.
    The snapshot of superstep s is flushed to `checkpoint_fn` *after*
    superstep s+1 has been dispatched, so disk I/O overlaps device compute.
    epochs_per_superstep=0 picks the natural grain: every remaining epoch
    in one program when there is no checkpoint_fn or epoch_hook, else 1
    (per-epoch checkpoints, the historical contract).
    pipeline=True: the double-buffered schedule — merge of epoch k and evolve
    of epoch k+1 are dispatched back-to-back with no data dependency between
    them (the reseed feeding evolve k+1 reads the archive of epoch k-1), so
    jax's async dispatch overlaps evaluation with selection. Archive contents
    trail by one epoch relative to the synchronous schedule; the final state
    has every epoch merged.

    epoch_hook: called after `checkpoint_fn` at every boundary with the
    snapshot and the `EpochRecord` of the superstep's last epoch, still on
    the device (None on the pipelined schedule)."""

    def _flush(snapshot, record):
        with jax.profiler.TraceAnnotation("repro.islands.checkpoint",
                                          epoch=int(snapshot.epoch)):
            if checkpoint_fn is not None:
                checkpoint_fn(snapshot)
            if epoch_hook is not None:
                epoch_hook(snapshot, record)

    state = start_state if start_state is not None else init_island_state(
        cfg, key, n_islands=n_islands, archive_size=archive_size)
    state = place_island_state(state)
    e0 = int(state.epoch)
    if e0 >= epochs:
        return state

    if not pipeline:
        sstep = make_superstep(cfg, eval_fn, lam=lam,
                               steps_per_epoch=steps_per_epoch,
                               reseed_frac=reseed_frac,
                               merge_top_k=merge_top_k)
        donating = jax.jit(sstep, static_argnums=1, donate_argnums=0)
        # a caller-held start_state must survive the run (resume replays
        # checkpoint snapshots): its superstep runs without donation, every
        # state we created ourselves is donated.
        fn = jax.jit(sstep, static_argnums=1) if start_state is not None \
            else donating
        harvest = checkpoint_fn is not None or epoch_hook is not None
        grain = epochs_per_superstep or (1 if harvest else epochs - e0)
        n_i = state.islands.genomes.shape[0]
        span = jax.profiler.TraceAnnotation
        pending = None
        for s in range(e0, epochs, grain):
            k = min(grain, epochs - s)
            with span("repro.islands.superstep", epoch=s, evaluations=n_i * (
                    k * steps_per_epoch * lam + (s == 0) * cfg.mu)):
                state, record = fn(state, k)
            fn = donating
            if harvest:
                if pending is not None:
                    _flush(*pending)      # flush overlaps device compute
                pending = host_snapshot(state), record
        if pending is not None:
            _flush(*pending)
        return state

    evolve = jax.jit(make_evolve(cfg, eval_fn, lam=lam,
                                 steps_per_epoch=steps_per_epoch))
    merge_islands = jax.jit(make_merge(cfg, merge_top_k=merge_top_k))
    reseed_islands = jax.jit(make_reseed(cfg, reseed_frac=reseed_frac))
    n_i = state.islands.genomes.shape[0]     # honour start_state's count
    per_epoch = n_i * steps_per_epoch * lam
    archive = state.archive
    evolved = evolve(state.islands)          # epoch e0 evaluation in flight
    total = state.total_evaluations
    for e in range(e0, epochs):
        total = total + per_epoch + (e == 0) * n_i * cfg.mu
        new_archive = merge_islands(archive, evolved)     # selection, epoch e
        if e + 1 < epochs:
            # reseed from the *stale* archive so evolve(e+1) does not wait
            # for merge(e); both are now in flight together.
            seeded = reseed_islands(evolved, archive)
            next_evolved = evolve(seeded)                 # evaluation, e+1
        archive = new_archive
        # checkpoint the *seeded* islands (ready to evolve epoch e+1): a
        # resume then continues the schedule bit-for-bit instead of
        # silently skipping the boundary reseed.
        state = IslandState(seeded if e + 1 < epochs else evolved,
                            archive, jnp.int32(e + 1), jnp.int32(total))
        _flush(state, None)
        if e + 1 < epochs:
            evolved = next_evolved
    return state
