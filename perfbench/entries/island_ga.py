"""Entry kind ``island_ga``: the paper's island NSGA-II over the ants model.

The run drives the program's own path, ``launch.explore.calibrate(
reduced=False)`` (``run_islands``: one jitted, donated superstep per epoch,
the epoch's checkpoint written while the next superstep runs), with the
configuration's ``ga`` settings and a fresh output directory. The islands
start from ``init_island_state`` drawn from ``--seed``, handed to the
calibration as its epoch-0 checkpoint (``calibrate`` resumes from it). The
model is the one ``calibrate(reduced=False)`` runs, ``CONFIG``; the run
refuses a configuration whose ``model`` is another.

Every committed epoch reaches ``calibrate``'s ``epoch_hook`` with its
state and the superstep's record (each island's last GA step, every child
lane's final ``AntsState``, the islands the archive merged). An epoch
*completes* when the hook receives it. Warm-up is epoch 0, which compiles
(or loads) the superstep; the window opens at its completion and closes at
the first completion ``seconds`` or more later, where the hook stops the
calibration. ``evals_per_s`` is the growth of ``total_evaluations`` over the
window (the epochs that completed inside it) over its length.

``correct``, from the window's last epoch:

- ``diverged`` and ``chem_gap``: the lanes of a seeded sample of
  ``check_sample`` children (their genomes and evaluation keys, from the
  record) are recomputed by the plain reference (``reference/ants.py``)
  and each lane's summary row is compared with that of the program's final
  lane state (``compare``); a lane counts as diverged also where its
  child's objectives, as the program selected on them, are not the median
  of the reference's lanes;
- ``selection_mismatch``: the share of the islands' survivors and of the
  archive's members that differ from the plain selection reference
  (``reference/nsga2.py``) applied to the record's pools and to the
  archive before the epoch.

The run fails at once, before any compile, on a program without
``simulate_batch_state`` or without ``calibrate``'s ``epoch_hook``.
"""
from __future__ import annotations

import inspect
import os
import shutil
import sys
import time

import numpy as np

from harness import WindowClosed

MAX_EPOCHS = 64        # the window closes far sooner


def require_program():
    """Raise where the program cannot run this cell."""
    import repro.ants
    from repro.launch import explore
    missing = [n for n, ok in (
        ("repro.ants.simulate_batch_state",
         hasattr(repro.ants, "simulate_batch_state")),
        ("calibrate(epoch_hook=...)",
         "epoch_hook" in inspect.signature(explore.calibrate).parameters))
        if not ok]
    if missing:
        raise RuntimeError(f"the program lacks {', '.join(missing)}: it "
                           f"cannot run the island GA cell")
    return explore


def ga_settings(config: dict) -> dict:
    """``calibrate``'s keyword arguments for the configuration."""
    ga = config["ga"]
    return dict(n_islands=int(ga["n_islands"]), mu=int(ga["mu"]),
                lam=int(ga["lam"]),
                steps_per_epoch=int(ga["steps_per_epoch"]),
                replicates=int(config["replicates"]),
                archive_size=int(ga["archive_size"]),
                merge_top_k=int(ga["merge_top_k"]),
                reseed_frac=float(ga["reseed_frac"]))


def seed_checkpoint(explore, config: dict, seed: int, out_dir: str):
    """The islands ``init_island_state`` draws from ``seed``, written as
    ``calibrate``'s epoch-0 checkpoint under ``out_dir``."""
    import jax
    from repro import checkpoint
    from repro.evolution import NSGA2Config, init_island_state

    s = ga_settings(config)
    cfg = NSGA2Config(mu=s["mu"], genome_dim=2, bounds=explore.BOUNDS,
                      n_objectives=int(config["ga"]["n_objectives"]))
    state = init_island_state(cfg, jax.random.key(seed),
                              n_islands=s["n_islands"],
                              archive_size=s["archive_size"])
    checkpoint.save(os.path.join(out_dir, "checkpoints"), 0, state,
                    blocking=True)


class Epochs:
    """``calibrate``'s epoch hook: times each completion, opens and closes
    the window, and keeps the state before and the (state, record) of the
    window's last epoch."""

    def __init__(self, job):
        self.window = job.window
        self.done = []            # (completion time, total evaluations)
        self.before = None        # the state an epoch started from
        self.last = None          # (state, record) of the latest epoch

    def __call__(self, state, record):
        t = time.monotonic()
        self.done.append((t, int(state.total_evaluations)))
        if self.last is not None:
            self.before = self.last[0]
        self.last = (state, record)
        if self.window.open_t is None:
            self.window.open(t)
        elif self.window.due(t):
            self.window.close(t)
            raise WindowClosed

    def evaluations(self) -> int:
        """Evaluations of the epochs that completed inside the window."""
        w = self.window
        at_open = [e for t, e in self.done if t <= w.open_t][-1]
        at_close = [e for t, e in self.done if t <= w.close_t][-1]
        return at_close - at_open


def drive(job, explore, out_dir: str) -> Epochs:
    """Seed, warm up and run the calibration until the window closes."""
    shutil.rmtree(out_dir, ignore_errors=True)
    seed_checkpoint(explore, job.config, job.seed, out_dir)
    epochs = Epochs(job)
    try:
        with job.span("calibrate"):
            explore.calibrate(reduced=False, epochs=MAX_EPOCHS,
                              out_dir=out_dir, epoch_hook=epochs,
                              printer=lambda m: print(m, file=sys.stderr),
                              **ga_settings(job.config))
    except WindowClosed:
        pass
    if job.window.close_t is None:
        raise RuntimeError(f"{MAX_EPOCHS} epochs ran out before the window "
                           f"closed")
    return epochs


def sample(seed: int, n_islands: int, lam: int, size: int):
    """(island, child) pairs of a seeded sample of an epoch's children."""
    rng = np.random.default_rng(seed)
    flat = rng.choice(n_islands * lam, size=min(size, n_islands * lam),
                      replace=False)
    return sorted((int(i) // lam, int(i) % lam) for i in flat)


def lane_rows(model: dict, record, picks, replicates: int) -> np.ndarray:
    """Summary rows of the program's final states of the picked children's
    lanes, (children x replicates, N_COLUMNS), replicate fastest; summed in
    a compiled program, as the reference's are (the field's sums taken op
    by op differ from those in about the 7th digit)."""
    import jax
    from reference import ants as ants_ref

    isl = np.array([i for i, _ in picks])[:, None]
    lanes = np.array([[c * replicates + r for r in range(replicates)]
                      for _, c in picks])

    def picked(field):       # (islands, lanes, ...) -> (picked lanes, ...)
        x = np.asarray(getattr(record.step.lanes, field))[isl, lanes]
        return x.reshape((-1,) + x.shape[2:])

    summarize = jax.jit(lambda *state: ants_ref.summarize(model, *state))
    return np.asarray(summarize(*map(picked, (
        "ticks_empty", "chem", "food", "ant_pos", "carrying"))))


def reference_lane_rows(model: dict, record, picks, replicates: int,
                        chem_dtype=None) -> np.ndarray:
    """The plain reference's summary rows of the same lanes, from the
    children's genomes and evaluation keys (each key split into its
    replicates' lane keys, as ``reference.ants.replicated`` splits it)."""
    import jax
    import jax.numpy as jnp
    from reference import ants as ants_ref

    genomes = np.asarray(record.step.children)
    keys = np.asarray(record.step.child_keys)
    g = np.repeat(np.stack([genomes[i, c] for i, c in picks]), replicates,
                  axis=0)
    k = jax.random.wrap_key_data(jnp.asarray(
        np.stack([keys[i, c] for i, c in picks])))
    dtype = jnp.float32 if chem_dtype is None else jnp.dtype(chem_dtype)

    def lanes(kk, gg):
        lane_keys = jax.vmap(lambda x: jax.random.split(x, replicates))(kk)
        return ants_ref.simulate(model, lane_keys.reshape(-1), gg[:, 0],
                                 gg[:, 1], dtype)

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lanes)(k, jnp.asarray(g)))


def compare_lanes(got, want, objectives, replicates: int) -> dict:
    """``reference.ants.compare`` over lanes, a lane counting as diverged
    also where the objectives its child was selected on (``objectives``,
    one row a child) are not the median of the reference's lanes."""
    from reference import ants as ants_ref

    numbers = ants_ref.compare(got, want)
    exact = ants_ref.EXACT_COLUMNS
    lane_off = ((got[:, exact] != want[:, exact]).any(axis=1)
                | ~np.isfinite(got).all(axis=1))
    medians = np.median(want[:, :3].reshape(-1, replicates, 3), axis=1)
    child_off = (np.asarray(objectives) != medians).any(axis=1)
    numbers["diverged"] = float(np.mean(
        lane_off | np.repeat(child_off, replicates)))
    return numbers


def selection_mismatch(before, state, record, merge_top_k: int) -> float:
    """Share of survivors and archive members that differ from the plain
    reference's selection of the epoch."""
    from reference import nsga2 as nsga2_ref

    step, evolved = record.step, record.evolved

    def host(*xs):
        return tuple(np.asarray(x) for x in xs)

    survivors, archive = nsga2_ref.island_epoch(
        host(step.parent_genomes, step.parent_objectives, step.parent_valid),
        host(step.children, step.child_objectives),
        host(evolved.genomes, evolved.objectives, evolved.valid),
        merge_top_k,
        host(before.archive.genomes, before.archive.objectives,
             before.archive.valid))
    got_survivors = host(evolved.genomes, evolved.objectives, evolved.valid)
    got_archive = host(state.archive.genomes, state.archive.objectives,
                       state.archive.valid)
    bad = (nsga2_ref.mismatched_rows(got_survivors, survivors)
           + nsga2_ref.mismatched_rows(got_archive, archive))
    return bad / (got_survivors[2].size + got_archive[2].size)


def check(job, epochs: Epochs) -> dict:
    """The numbers ``correct`` compares, from the window's last epoch."""
    cfg = job.config
    s = ga_settings(cfg)
    state, record = epochs.last
    picks = sample(job.seed, s["n_islands"], s["lam"],
                   int(job.traffic["check_sample"]))
    got = lane_rows(cfg["model"], record, picks, s["replicates"])
    want = reference_lane_rows(cfg["model"], record, picks, s["replicates"])
    objectives = np.asarray(record.step.child_objectives)
    numbers = compare_lanes(got, want, [objectives[i, c] for i, c in picks],
                            s["replicates"])
    numbers["selection_mismatch"] = selection_mismatch(
        epochs.before, state, record, min(s["merge_top_k"], s["mu"]))
    numbers["sampled"] = len(picks)
    return numbers


def run(job) -> dict:
    import costs
    from repro.configs.ants_netlogo import AntsConfig

    explore = require_program()
    cfg = job.config
    if AntsConfig(**cfg["model"]) != explore.CONFIG:
        raise RuntimeError(f"calibrate(reduced=False) runs {explore.CONFIG}, "
                           f"not the configuration's model {cfg['model']}")
    s = ga_settings(cfg)
    epochs = drive(job, explore, os.path.join(job.work_dir, "calibration"))
    job.drained()
    t = time.monotonic()
    numbers = check(job, epochs)
    ref_s = time.monotonic() - t
    w = job.window
    evaluations = epochs.evaluations()
    model = cfg["model"]
    lanes = s["n_islands"] * s["steps_per_epoch"] * s["lam"] * s[
        "replicates"]
    inside = sum(1 for t_, _ in epochs.done if w.open_t < t_ <= w.close_t)
    return {
        "e2e": {"evals_per_s": evaluations / w.length},
        "attempted": evaluations,
        "failed": 0,
        "checks": [(n, numbers[n], float(v))
                   for n, v in cfg["limits"].items()],
        "facts": {
            "ants_module": "jit_superstep",
            "lanes_per_program": lanes,
            "ticks": int(model["max_ticks"]),
            "tick_bytes": costs.tick_bytes(model),
            "tick_flops": costs.tick_flops(model),
        },
        "notes": [f"{evaluations} evaluations in {inside} epochs inside the "
                  f"window; {numbers['sampled']} children compared with the "
                  f"reference and every island's selection and the archive "
                  f"merge with the selection reference in {ref_s:.1f} s"],
    }
