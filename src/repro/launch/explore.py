"""The paper-centric driver: calibrate the ants model with island-model
NSGA-II (default) or the surrogate-assisted GP ask/tell engine, with
checkpointing (fault tolerance) — §4 A-to-Z at production scale.

    PYTHONPATH=src python -m repro.launch.explore --islands 8 --epochs 5 \
        --reduced --out /tmp/ants_calibration

    PYTHONPATH=src python -m repro.launch.explore --method surrogate \
        --reduced --rounds 8 --out /tmp/ants_surrogate
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.ants import simulate_batch, simulate_batch_state
from repro.configs.ants_netlogo import BOUNDS, CONFIG, REDUCED
from repro.core import (Context, EnvironmentPool, FaultSpec,
                        LocalEnvironment, SavePopulationHook,
                        make_device_members)
from repro.core.cache import hash_value
from repro.core.scheduler import RunRecord, TaskRecord, _utcnow
from repro.evolution import (NSGA2Config, ga, init_island_state, make_epoch,
                             pareto_front, run_islands)
from repro.explore import (MOSurrogateConfig, SurrogateConfig,
                           replicated_batch, replicated_batch_state,
                           run_surrogate, run_surrogate_mo)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import init_distributed, make_host_mesh, \
    make_island_mesh
from repro.runtime import sharding as shd


def make_init_pool(fault_rate: float = 0.0, *, workers: int = 3,
                   capacity: int = 2, retries: int = 8,
                   backoff_s: float = 0.05, timeout_s: float = None,
                   pool_devices: int = 0) -> EnvironmentPool:
    """THE local evaluation-pool factory (drivers, benches, and the
    service mode all build their pools here): a few heterogeneous local
    workers, optionally with an injected per-attempt failure rate (the
    paper's unreliable-EGI regime, reproduced on one host).

    ``pool_devices=k`` switches the members from host threads to k
    :class:`~repro.core.environment.DeviceEnvironment`s over disjoint
    subsets of the local devices, so the streaming init and surrogate
    fan-outs scale with device count (run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to try it on
    one CPU host). ``workers``/``capacity`` are ignored in that mode —
    member count is k and capacity defaults per device set."""
    if pool_devices:
        envs = make_device_members(
            None, pool_devices, timeout_s=timeout_s,
            faults=((lambda i: FaultSpec(fail_rate=fault_rate, seed=i))
                    if fault_rate > 0 else None))
    else:
        envs = [LocalEnvironment(
            name=f"worker{i}", capacity=capacity, timeout_s=timeout_s,
            faults=(FaultSpec(fail_rate=fault_rate, seed=i)
                    if fault_rate > 0 else None))
            for i in range(workers)]
    return EnvironmentPool(envs, retries=retries, backoff_s=backoff_s)


def calibrate(*, reduced: bool = True, n_islands: int = 8, mu: int = 16,
              lam: int = 16, steps_per_epoch: int = 4, epochs: int = 5,
              replicates: int = 5, archive_size: int = 256,
              merge_top_k: int = 8, out_dir: str = "/tmp/ants", mesh=None,
              pipeline: bool = False, reseed_frac: float = 0.5,
              epochs_per_superstep: int = 0, init_population: int = 0,
              init_chunk: int = 2048, fault_rate: float = 0.0,
              pool_devices: int = 0, printer=print, epoch_hook=None):
    """Island NSGA-II calibration of the ants model. ``epoch_hook(state,
    record)``, where given, sees every committed epoch after its checkpoint:
    the host snapshot and the superstep's ``EpochRecord`` (each island's
    last GA step with every child lane's final ``AntsState``, and the
    islands the archive merged), on the device."""
    ants_cfg = REDUCED if reduced else CONFIG
    ga_cfg = NSGA2Config(mu=mu, genome_dim=2, bounds=BOUNDS, n_objectives=3)
    eval_fn = replicated_batch(
        lambda keys, genomes: simulate_batch(ants_cfg, keys, genomes[:, 0],
                                             genomes[:, 1]),
        replicates)
    island_eval = replicated_batch_state(
        lambda keys, genomes: simulate_batch_state(
            ants_cfg, keys, genomes[:, 0], genomes[:, 1]),
        replicates)
    mesh = mesh or make_host_mesh()
    os.makedirs(out_dir, exist_ok=True)
    pop_hook = SavePopulationHook(os.path.join(out_dir, "populations"))
    ckpt_dir = os.path.join(out_dir, "checkpoints")

    # restart-safe: resume island state from the last committed epoch
    state_sds = jax.eval_shape(
        lambda k: init_island_state(ga_cfg, k, n_islands=n_islands,
                                    archive_size=archive_size),
        jax.random.key(0))
    start = None
    if (last := checkpoint.latest_step(ckpt_dir)) is not None:
        start = checkpoint.restore(ckpt_dir, last, state_sds)
        printer(f"[explore] resumed at epoch {last}")
    init_record = None

    # run-record provenance (same schema the workflow scheduler emits):
    # one TaskRecord per committed epoch, resumed epochs marked cache hits
    record = RunRecord(
        workflow="ants-calibration",
        scheduler="islands-pipelined" if pipeline else "islands",
        environment=f"mesh{dict(mesh.shape)}",
        started_at=_utcnow())
    run_t0 = time.monotonic()
    cfg_digest = hash_value({
        "reduced": reduced, "n_islands": n_islands, "mu": mu, "lam": lam,
        "steps_per_epoch": steps_per_epoch, "replicates": replicates,
        "archive_size": archive_size, "merge_top_k": merge_top_k})
    last_epoch_t = [run_t0]
    if start is not None:
        for e in range(1, int(last) + 1):
            record.tasks.append(TaskRecord(
                task="island_epoch", capsule=e,
                environment=record.environment, inputs_digest=cfg_digest,
                started_s=0.0, wall_s=0.0, retries=0, cache_hit=True,
                mode="cache"))

    def on_epoch(state):
        e = int(state.epoch)
        checkpoint.save(ckpt_dir, e, state, blocking=True)
        now = time.monotonic()
        record.tasks.append(TaskRecord(
            task="island_epoch", capsule=e, environment=record.environment,
            inputs_digest=cfg_digest, started_s=last_epoch_t[0] - run_t0,
            wall_s=now - last_epoch_t[0], retries=0, cache_hit=False,
            mode="pipelined" if pipeline else "lanes"))
        last_epoch_t[0] = now
        mask = np.asarray(pareto_front(state.archive))
        obj = np.asarray(state.archive.objectives)
        pop_hook(Context(generation=e,
                         genomes=np.asarray(state.archive.genomes),
                         objectives=obj))
        printer(f"[explore] epoch {e}: evals={int(state.total_evaluations)} "
                f"front={int(mask.sum())} "
                f"best t1={obj[mask, 0].min() if mask.any() else float('nan'):.0f}")

    # -- paper-scale streaming init: evaluate a large initial population
    # through the (optionally fault-injected) environment pool, in chunks,
    # with mid-population checkpoint/resume; seed the islands from its top
    # individuals. Skipped when resuming past epoch 0 (the island state
    # already embodies it).
    if init_population and start is None:
        if init_population < n_islands * mu:
            raise ValueError(
                f"--init-population must cover the island populations: "
                f"need >= n_islands*mu = {n_islands * mu}, "
                f"got {init_population}")
        pool = make_init_pool(fault_rate, pool_devices=pool_devices)
        try:
            sres = ga.evaluate_population_streaming(
                ga_cfg, eval_fn, 0, n_total=init_population,
                chunk=init_chunk, environment=pool, record=record,
                checkpoint_dir=os.path.join(out_dir, "init_checkpoints"),
                progress=lambda k, n: printer(
                    f"[explore] init chunk {k}/{n}") if k % 8 == 0 else None)
        finally:
            pool.shutdown()
        printer(f"[explore] init: {init_population} individuals in "
                f"{sres.wall_s:.1f}s ({sres.attempts} attempts, "
                f"{sres.resumed_chunks} chunks resumed) -> "
                f"{init_population / max(sres.wall_s, 1e-9) * 3600:.0f} "
                f"evals/hour")
        top_g, top_o = ga.select_top_streaming(
            ga_cfg, sres.genomes, sres.objectives, n_islands * mu)
        st0 = init_island_state(ga_cfg, jax.random.key(0),
                                n_islands=n_islands,
                                archive_size=archive_size)
        islands = st0.islands._replace(
            genomes=jnp.asarray(top_g).reshape(n_islands, mu, -1),
            objectives=jnp.asarray(top_o).reshape(n_islands, mu, -1),
            valid=jnp.ones((n_islands, mu), bool))
        # epoch-0 accounting re-adds n_islands*mu for the (skipped) initial
        # evaluation; pre-subtract so the total counts init_population once
        start = st0._replace(
            islands=islands,
            total_evaluations=jnp.int32(init_population - n_islands * mu))
        init_record = sres

    t0 = time.time()
    with shd.use_mesh(mesh):
        state = run_islands(
            ga_cfg, island_eval, jax.random.key(0), n_islands=n_islands,
            lam=lam, steps_per_epoch=steps_per_epoch, epochs=epochs,
            archive_size=archive_size, checkpoint_fn=on_epoch,
            merge_top_k=min(merge_top_k, mu), reseed_frac=reseed_frac,
            pipeline=pipeline, epochs_per_superstep=epochs_per_superstep,
            start_state=start, epoch_hook=epoch_hook)
    dt = time.time() - t0
    evals = int(state.total_evaluations)
    printer(f"[explore] done: {evals} evaluations in {dt:.1f}s "
            f"({evals / max(dt, 1e-9) * 3600:.0f} evals/hour on "
            f"{len(jax.devices())} host device(s))")

    archive = jax.device_get(state.archive)   # one-device readout
    mask = np.asarray(pareto_front(archive))
    front = {
        "genomes": archive.genomes[mask].tolist(),
        "objectives": archive.objectives[mask].tolist(),
        "evaluations": evals,
        "wall_s": dt,
    }
    if init_record is not None:
        front["init"] = {"n_individuals": init_population,
                         "wall_s": init_record.wall_s,
                         "attempts": init_record.attempts,
                         "resumed_chunks": init_record.resumed_chunks,
                         "fault_rate": fault_rate}
    with open(os.path.join(out_dir, "pareto_front.json"), "w") as f:
        json.dump(front, f, indent=2)
    record.finalize(dt)
    record.save(os.path.join(out_dir, "provenance.json"))
    return state, front


def ants_scalar_eval(reduced: bool = True, replicates: int = 3,
                     objective: int = 0):
    """(keys (n,), genomes (n, 2)) -> (n,) scalar fitness for the
    surrogate: the replicated-median time to deplete food source
    ``objective`` (minimize). Source 0 (nearest) is the default: on the
    reduced config it is the objective with real structure — the farther
    sources mostly saturate at the tick horizon. ``objective=None``
    averages all three."""
    ants_cfg = REDUCED if reduced else CONFIG
    batch = replicated_batch(
        lambda keys, genomes: simulate_batch(ants_cfg, keys, genomes[:, 0],
                                             genomes[:, 1]),
        replicates)

    def eval_fn(keys, genomes):
        obj = batch(keys, genomes)
        return obj.mean(axis=-1) if objective is None \
            else obj[:, objective]

    return eval_fn


def calibrate_surrogate(*, reduced: bool = True, rounds: int = 8, q: int = 8,
                        n_init: int = 16, replicates: int = 3,
                        acquisition: str = "qei", fault_rate: float = 0.0,
                        pool_devices: int = 0,
                        out_dir: str = "/tmp/ants_surrogate",
                        printer=print):
    """Surrogate-assisted calibration of the ants model: Sobol seeding,
    then GP + q-EI rounds streamed through the fault-tolerant environment
    pool, checkpointed per round (restart-safe), with the same WfCommons-
    style provenance the other drivers emit."""
    os.makedirs(out_dir, exist_ok=True)
    cfg = SurrogateConfig(bounds=BOUNDS, q=q, n_init=n_init,
                          acquisition=acquisition, seed=0)
    eval_fn = ants_scalar_eval(reduced, replicates)
    record = RunRecord(workflow="ants-surrogate", scheduler="ask-tell",
                       environment="pool", started_at=_utcnow())
    pool = make_init_pool(fault_rate, pool_devices=pool_devices)
    t0 = time.time()
    try:
        res = run_surrogate(
            cfg, eval_fn, rounds=rounds, environment=pool, record=record,
            checkpoint_dir=os.path.join(out_dir, "surrogate_checkpoints"),
            progress=lambda r, n: printer(f"[explore] round {r}/{n}"))
    finally:
        pool.shutdown()
    dt = time.time() - t0
    printer(f"[explore] surrogate: {len(res.objectives)} evaluations in "
            f"{dt:.1f}s ({res.attempts} attempts, {res.repriorities} "
            f"re-prioritizations, {res.resumed_rounds} rounds resumed); "
            f"best {res.best_objective:.1f} at {res.best_genome}")
    out = {
        "best_genome": np.asarray(res.best_genome).tolist(),
        "best_objective": res.best_objective,
        "genomes": np.asarray(res.genomes).tolist(),
        "objectives": np.asarray(res.objectives).tolist(),
        "rounds": res.rounds_done,
        "attempts": res.attempts,
        "repriorities": res.repriorities,
        "fault_rate": fault_rate,
        "wall_s": dt,
    }
    with open(os.path.join(out_dir, "surrogate_result.json"), "w") as f:
        json.dump(out, f, indent=2)
    record.finalize(dt)
    record.save(os.path.join(out_dir, "provenance.json"))
    return res, out


def ants_mo_eval(reduced: bool = True, replicates: int = 3):
    """(keys (n,), genomes (n, 2)) -> (n, 3) replicated-median times to
    deplete each food source — the paper's three calibration objectives,
    fed raw to the multi-objective surrogate (all minimized)."""
    ants_cfg = REDUCED if reduced else CONFIG
    return replicated_batch(
        lambda keys, genomes: simulate_batch(ants_cfg, keys, genomes[:, 0],
                                             genomes[:, 1]),
        replicates)


def calibrate_surrogate_mo(*, reduced: bool = True, rounds: int = 8,
                           q: int = 8, n_init: int = 16,
                           replicates: int = 3, fault_rate: float = 0.0,
                           pool_devices: int = 0,
                           out_dir: str = "/tmp/ants_surrogate_mo",
                           printer=print):
    """Multi-objective surrogate calibration: per-objective GPs + qEHVI
    batches bred from the NSGA-II Pareto archive (see
    :mod:`repro.explore.moacq`), streamed through the fault-tolerant
    environment pool with per-round checkpoints and the same provenance
    schema the other drivers emit."""
    os.makedirs(out_dir, exist_ok=True)
    cfg = MOSurrogateConfig(bounds=BOUNDS, n_objectives=3, q=q,
                            n_init=n_init, seed=0)
    eval_fn = ants_mo_eval(reduced, replicates)
    record = RunRecord(workflow="ants-surrogate-mo", scheduler="ask-tell",
                       environment="pool", started_at=_utcnow())
    pool = make_init_pool(fault_rate, pool_devices=pool_devices)
    t0 = time.time()
    try:
        res = run_surrogate_mo(
            cfg, eval_fn, rounds=rounds, environment=pool, record=record,
            checkpoint_dir=os.path.join(out_dir, "surrogate_checkpoints"),
            progress=lambda r, n: printer(f"[explore] round {r}/{n}"))
    finally:
        pool.shutdown()
    dt = time.time() - t0
    printer(f"[explore] surrogate-mo: {len(res.objectives)} evaluations in "
            f"{dt:.1f}s ({res.attempts} attempts, {res.resumed_rounds} "
            f"rounds resumed); front {len(res.front_objectives)} points, "
            f"hypervolume {res.hv:.3g}")
    out = {
        "front_genomes": np.asarray(res.front_genomes).tolist(),
        "front_objectives": np.asarray(res.front_objectives).tolist(),
        "hypervolume": res.hv,
        "genomes": np.asarray(res.genomes).tolist(),
        "objectives": np.asarray(res.objectives).tolist(),
        "rounds": res.rounds_done,
        "attempts": res.attempts,
        "fault_rate": fault_rate,
        "wall_s": dt,
    }
    with open(os.path.join(out_dir, "surrogate_mo_result.json"), "w") as f:
        json.dump(out, f, indent=2)
    record.finalize(dt)
    record.save(os.path.join(out_dir, "provenance.json"))
    return res, out


def calibrate_service(*, reduced: bool = True, init_population: int = 2048,
                      init_chunk: int = 256, rounds: int = 4, q: int = 8,
                      n_init: int = 16, replicates: int = 3,
                      fault_rate: float = 0.0, pool_devices: int = 0,
                      out_dir: str = "/tmp/ants_service", printer=print):
    """Service mode: TWO experiments — a streaming GA-population init and a
    surrogate calibration — run *concurrently* as tenants of ONE
    :class:`~repro.core.service.ExplorationService` over one shared
    environment pool (the paper's always-on delegation layer, ROADMAP
    open item 1). The queue journals to ``<out>/queue.jsonl`` and outputs
    memoize under ``<out>/cache``, so killing this driver mid-run and
    rerunning it resumes both tenants without re-executing finished work.
    """
    from repro.core import ExplorationService

    os.makedirs(out_dir, exist_ok=True)
    ants_cfg = REDUCED if reduced else CONFIG
    ga_cfg = NSGA2Config(mu=16, genome_dim=2, bounds=BOUNDS, n_objectives=3)
    ga_eval = replicated_batch(
        lambda keys, genomes: simulate_batch(ants_cfg, keys, genomes[:, 0],
                                             genomes[:, 1]),
        replicates)
    sur_cfg = SurrogateConfig(bounds=BOUNDS, q=q, n_init=n_init, seed=0)
    sur_eval = ants_scalar_eval(reduced, replicates)

    pool = make_init_pool(fault_rate, pool_devices=pool_devices)
    service = ExplorationService(
        pool, cache=os.path.join(out_dir, "cache"),
        journal=os.path.join(out_dir, "queue.jsonl"))
    results: dict = {}
    errors: list = []

    def ga_tenant():
        try:
            results["ga"] = ga.evaluate_population_streaming(
                ga_cfg, ga_eval, 0, n_total=init_population,
                chunk=init_chunk, service=service, experiment_id="ga-init")
        except Exception as e:            # surfaced after join
            errors.append(e)

    def surrogate_tenant():
        try:
            results["surrogate"] = run_surrogate(
                sur_cfg, sur_eval, rounds=rounds, service=service,
                experiment_id="surrogate")
        except Exception as e:
            errors.append(e)

    t0 = time.time()
    import threading
    tenants = [threading.Thread(target=ga_tenant, name="tenant-ga"),
               threading.Thread(target=surrogate_tenant,
                                name="tenant-surrogate")]
    try:
        for t in tenants:
            t.start()
        for t in tenants:
            t.join()
    finally:
        for eid in ("ga-init", "surrogate"):
            service.record(eid).save(
                os.path.join(out_dir, f"provenance_{eid}.json"))
        service.shutdown()
        pool.shutdown()
    if errors:
        raise errors[0]
    dt = time.time() - t0
    sres, rres = results["ga"], results["surrogate"]
    n_jobs = sres.chunks_done + rres.rounds_done * q
    printer(f"[explore] service: 2 tenants, {n_jobs} jobs through one pool "
            f"in {dt:.1f}s — init {init_population} individuals "
            f"({sres.attempts} attempts), surrogate best "
            f"{rres.best_objective:.1f} at {rres.best_genome} "
            f"({rres.repriorities} queue re-prioritizations)")
    out = {
        "init": {"n_individuals": init_population,
                 "attempts": sres.attempts, "wall_s": sres.wall_s},
        "surrogate": {"best_genome": np.asarray(rres.best_genome).tolist(),
                      "best_objective": rres.best_objective,
                      "repriorities": rres.repriorities,
                      "wall_s": rres.wall_s},
        "queue": service.query(),
        "fault_rate": fault_rate,
        "wall_s": dt,
    }
    with open(os.path.join(out_dir, "service_result.json"), "w") as f:
        json.dump(out, f, indent=2)
    return results, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--method",
                    choices=("islands", "surrogate", "surrogate-mo",
                             "service"),
                    default="islands",
                    help="islands: fused island-model NSGA-II; surrogate: "
                         "GP + q-EI ask/tell through the environment pool; "
                         "surrogate-mo: per-objective GPs + qEHVI batches "
                         "bred from the Pareto archive; "
                         "service: GA init + surrogate calibration "
                         "concurrently through one shared "
                         "ExplorationService (restart-safe queue)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--islands", type=int, default=8)
    ap.add_argument("--mu", type=int, default=16)
    ap.add_argument("--lam", type=int, default=16)
    ap.add_argument("--steps-per-epoch", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--replicates", type=int, default=5)
    ap.add_argument("--pipeline", action="store_true",
                    help="double-buffer epochs: evaluation of epoch k+1 "
                         "overlaps archive selection of epoch k (reseed "
                         "reads a one-epoch-stale archive, EGI-style)")
    ap.add_argument("--reseed-frac", type=float, default=0.5,
                    help="fraction of each island population replaced by "
                         "archive samples at every epoch boundary")
    ap.add_argument("--superstep", type=int, default=0,
                    help="epochs fused into one scanned, buffer-donating "
                         "device program between checkpoints (0 = auto: "
                         "1 per checkpoint, all epochs when uncheckpointed)")
    ap.add_argument("--mesh", default="",
                    help="island mesh spec: 'data=N' or 'pod=P,data=N' "
                         "(0 = all devices); default: every local/global "
                         "device on a 1D data axis")
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize before building "
                         "the mesh (multi-process/multi-host SPMD; combine "
                         "with --coordinator/--num-processes/--process-id "
                         "or the standard cluster env vars)")
    ap.add_argument("--coordinator", default=None,
                    help="coordinator address host:port for --distributed")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--init-population", type=int, default=0,
                    help="evaluate a large initial population (the paper's "
                         "200000) through the fault-tolerant environment "
                         "pool before the island run, streaming in "
                         "--init-chunk jobs with mid-population "
                         "checkpoint/resume; islands seed from its top "
                         "individuals")
    ap.add_argument("--init-chunk", type=int, default=2048)
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="injected per-attempt job-failure rate for the "
                         "init pool (chaos mode; results stay bit-exact)")
    ap.add_argument("--pool-devices", type=int, default=0,
                    help="partition the local devices into this many "
                         "disjoint DeviceEnvironment pool members (0 = "
                         "thread-backed members on the default device); "
                         "the streaming init / surrogate fan-outs then "
                         "scale with device count")
    ap.add_argument("--rounds", type=int, default=8,
                    help="surrogate ask/tell rounds (of --q proposals each)")
    ap.add_argument("--q", type=int, default=8,
                    help="surrogate proposals per round (q-EI batch size)")
    ap.add_argument("--n-init", type=int, default=16,
                    help="Sobol space-filling evaluations seeding the GP")
    ap.add_argument("--acquisition", choices=("qei", "qucb"), default="qei")
    ap.add_argument("--out", default="/tmp/ants")
    args = ap.parse_args()
    enable_compile_cache()
    if args.distributed or args.num_processes or args.coordinator:
        init_distributed(coordinator=args.coordinator,
                         num_processes=args.num_processes,
                         process_id=args.process_id,
                         force=args.distributed)
    mesh = None
    if args.mesh:
        spec = dict(kv.split("=") for kv in args.mesh.split(","))
        mesh = make_island_mesh(pod=int(spec.get("pod", 1)),
                                data=int(spec.get("data", 0)))
    if args.method == "service":
        calibrate_service(reduced=args.reduced,
                          init_population=args.init_population or 2048,
                          init_chunk=min(args.init_chunk, 256),
                          rounds=args.rounds, q=args.q, n_init=args.n_init,
                          replicates=args.replicates,
                          fault_rate=args.fault_rate,
                          pool_devices=args.pool_devices, out_dir=args.out)
        return
    if args.method == "surrogate-mo":
        calibrate_surrogate_mo(reduced=args.reduced, rounds=args.rounds,
                               q=args.q, n_init=args.n_init,
                               replicates=args.replicates,
                               fault_rate=args.fault_rate,
                               pool_devices=args.pool_devices,
                               out_dir=args.out)
        return
    if args.method == "surrogate":
        calibrate_surrogate(reduced=args.reduced, rounds=args.rounds,
                            q=args.q, n_init=args.n_init,
                            replicates=args.replicates,
                            acquisition=args.acquisition,
                            fault_rate=args.fault_rate,
                            pool_devices=args.pool_devices,
                            out_dir=args.out)
        return
    calibrate(reduced=args.reduced, n_islands=args.islands, mu=args.mu,
              lam=args.lam, steps_per_epoch=args.steps_per_epoch,
              epochs=args.epochs, replicates=args.replicates, mesh=mesh,
              pipeline=args.pipeline, reseed_frac=args.reseed_frac,
              epochs_per_superstep=args.superstep,
              init_population=args.init_population,
              init_chunk=args.init_chunk, fault_rate=args.fault_rate,
              pool_devices=args.pool_devices, out_dir=args.out)


if __name__ == "__main__":
    main()
