"""Entry kind ``streaming_init``: the paper's streamed initial population.

The window drives the program's own path, built as the explore driver
builds it: ``evolution.ga.evaluate_population_streaming`` over the
population the cell's traffic names, in chunks of ``chunk`` individuals,
through ``launch.explore.make_init_pool(pool_devices=<chips>)`` (one
``DeviceEnvironment`` member per chip, two chunks in flight on each), with
a fresh init checkpoint directory. The fitness is the replicated ants
evaluation (``replicates`` lanes per individual, median-reduced) of the
configuration's model, read out with the final simulated state of every
lane (see ``tap.py``).

Set-up builds the pool and runs one chunk on every member, which compiles
(or loads) every program the stream uses. The stream then starts: the
pool takes all of its tasks at once, and only then reports completions.
The window opens at the latest chunk completed by then and closes at the
first completion ``seconds`` or more after that. ``evals_per_s`` is the
individuals of the chunks completed inside the window over its length. Once it closes the
stream stops: queued chunks are cancelled, chunks already on a chip run to
their end.

``correct``: a sample of the window's individuals, drawn from the seed, is
recomputed by the plain reference (``reference/ants.py``) from the same
seed, and their summary rows (objectives, food taken per source, ants
carrying, ant positions, the chemical field's total and projection,
median over replicates) are compared: ``diverged`` and ``chem_gap``
against the configuration's limits.
"""
from __future__ import annotations

import concurrent.futures as cf
import os
import threading
import time

import numpy as np

from harness import WindowClosed


class ChunkTap:
    """Stands in front of the program's pool as the streaming driver's
    environment: passes every chunk on, and records when each completed
    and what it returned."""

    def __init__(self, pool):
        self.pool = pool
        self.name = pool.name
        self.lock = threading.Condition()
        self.done = []            # (completion time, chunk, objectives)
        self.failed = 0
        self.futures = []

    def submit_async(self, task, context):
        fut = self.pool.submit_async(task, context)
        chunk = int(context["chunk"])

        def finished(f):
            t = time.monotonic()
            if f.cancelled():
                return
            if f.exception() is not None:
                with self.lock:
                    self.failed += 1
                return
            out, _meta = f.result()
            with self.lock:
                self.done.append((t, chunk, np.asarray(out["objectives"])))
                self.lock.notify_all()

        fut.add_done_callback(finished)
        self.futures.append(fut)
        return fut

    def completions(self, at_least: int = 0, timeout: float = 5.0):
        """The completions so far, sorted by time, once ``at_least`` of
        them are recorded (a future's waiters may hear of it before its
        done-callbacks ran)."""
        with self.lock:
            self.lock.wait_for(lambda: len(self.done) >= at_least, timeout)
            return sorted(self.done, key=lambda d: d[0])


def make_eval(config: dict, chem_dtype: str = None):
    """The fitness the stream evaluates: the program's replicated ants
    evaluation, each lane read out as a summary row of its final state."""
    from repro.ants import simulate_batch
    from repro.configs.ants_netlogo import AntsConfig
    from repro.explore import replicated_batch

    import tap
    from reference import ants as ants_ref

    model = dict(config["model"])
    if chem_dtype is not None:
        model["chem_dtype"] = chem_dtype
    cfg = AntsConfig(**model)

    def lanes(keys, genomes):
        obj, st = tap.tapped(simulate_batch, cfg, keys, genomes[:, 0],
                             genomes[:, 1])
        return ants_ref.summarize(model, obj, st["chem"], st["food"],
                                  st["ant_pos"], st["carrying"])

    batch = replicated_batch(lanes, int(config["replicates"]))

    def ants_evaluation(keys, genomes):
        return batch(keys, genomes)

    return ants_evaluation


def ga_config(config: dict):
    from repro.evolution import NSGA2Config
    ga = config["ga"]
    return NSGA2Config(mu=int(ga["mu"]), genome_dim=len(ga["bounds"]),
                       bounds=tuple(tuple(b) for b in ga["bounds"]),
                       n_objectives=int(ga["n_objectives"]))


def stream(job, eval_fn, *, chunk: int, population: int, seed: int,
           checkpoint_dir: str):
    """Warm every member up, then stream until the window closes. Returns
    the tap (completions) and the set-up's end time."""
    from repro.core.prototype import Context
    from repro.evolution import ga
    from repro.launch.explore import make_init_pool

    cfg = ga_config(job.config)
    pool = make_init_pool(pool_devices=len(job.devices))
    tap = ChunkTap(pool)
    try:
        with job.span("warmup"):      # every member's chip at once
            task = ga.make_chunk_task(cfg, eval_fn, seed)
            with cf.ThreadPoolExecutor(len(pool.members)) as ex:
                for f in [ex.submit(m.env.run_attempt, task,
                                    Context(chunk=0, size=chunk))
                          for m in pool.members]:
                    f.result()
        window = job.window

        def progress(k, _n):
            done = tap.completions(at_least=k)
            with job.span("progress"):
                if window.open_t is None:
                    # the stream reports its first completion once the
                    # pool has taken every task; the window opens at the
                    # latest completion by then, so that intake is set-up
                    window.open(done[-1][0])
                due = [t for t, _, _ in done if window.due(t)]
                if due:
                    window.close(due[0])
                    raise WindowClosed

        try:
            with job.span("stream"):
                ga.evaluate_population_streaming(
                    cfg, eval_fn, seed, n_total=population, chunk=chunk,
                    environment=tap, checkpoint_dir=checkpoint_dir,
                    progress=progress)
        except WindowClosed:
            pass
        if window.close_t is None:
            raise RuntimeError(f"the population of {population} ran out "
                               f"before the window closed")
    finally:
        pool.shutdown()
        # a future cancelled in the queue never reaches "cancelled and
        # notified", which is what cf.wait waits for
        cf.wait([f for f in tap.futures if not f.cancelled()])
    return tap


def window_rows(job, tap, chunk: int):
    """(chunk index, row, objectives row) of every individual whose chunk
    completed inside the window."""
    w = job.window
    rows = []
    for t, i, obj in tap.completions():
        if w.open_t < t <= w.close_t:
            rows.extend((i, j, obj[j]) for j in range(chunk))
    return rows


def reference_rows(job, picks, chunk: int, seed: int):
    """The plain reference's summary rows for (chunk, row) picks."""
    import jax
    import jax.numpy as jnp
    from reference import ants as ants_ref

    cfg = job.config
    chunks = {i: ants_ref.population_chunk(cfg["ga"]["bounds"], seed, i,
                                           chunk)
              for i in sorted({i for i, _ in picks})}
    keys = jnp.stack([chunks[i][0][j] for i, j in picks])
    genomes = jnp.stack([chunks[i][1][j] for i, j in picks])
    with jax.default_matmul_precision("highest"):
        rows = jax.jit(lambda k, g: ants_ref.replicated(
            cfg["model"], int(cfg["replicates"]), k, g))(keys, genomes)
    return np.asarray(rows)


def check(job, rows, seed: int, chunk: int):
    """Compare a seeded sample of the window's individuals with the
    reference; returns (numbers, sample size)."""
    from reference import ants as ants_ref

    size = min(int(job.traffic["check_sample"]), len(rows))
    rng = np.random.default_rng(seed)
    pick = sorted(rng.choice(len(rows), size=size, replace=False).tolist())
    got = np.stack([rows[n][2] for n in pick])
    want = reference_rows(job, [(rows[n][0], rows[n][1]) for n in pick],
                          chunk, seed)
    return ants_ref.compare(got, want), size


def run(job) -> dict:
    import costs

    tr, cfg = job.traffic, job.config
    chunk, population = int(tr["chunk"]), int(tr["population"])
    seed = job.seed
    eval_fn = make_eval(cfg)
    ckpt = os.path.join(job.work_dir, "init_checkpoints")
    tap = stream(job, eval_fn, chunk=chunk, population=population,
                 seed=seed, checkpoint_dir=ckpt)
    job.drained()
    rows = window_rows(job, tap, chunk)
    t = time.monotonic()
    numbers, sampled = check(job, rows, seed, chunk)
    ref_s = time.monotonic() - t
    limits = cfg["limits"]
    w = job.window
    model = cfg["model"]
    return {
        "e2e": {"evals_per_s": len(rows) / w.length},
        "attempted": len(rows),
        "failed": tap.failed * chunk,
        "checks": [(n, numbers[n], float(limits[n])) for n in limits],
        "facts": {
            "ants_module": "jit_ants_evaluation",
            "lanes_per_program": chunk * int(cfg["replicates"]),
            "ticks": int(model["max_ticks"]),
            "tick_bytes": costs.tick_bytes(model),
            "tick_flops": costs.tick_flops(model),
            "kernel": "%diffuse_evaporate",
            "kernel_bytes_per_call": costs.diffusion_bytes(
                model, chunk * int(cfg["replicates"])),
        },
        "notes": [f"{len(rows)} individuals in {len(rows) // chunk} chunks "
                  f"inside the window; {sampled} compared with the "
                  f"reference in {ref_s:.1f} s"],
    }
