"""Readings that the correctness limits are set from (not a benchmark run).

    python3 perfbench/readings.py --workload egi_init.chunk64 \
        --seeds 11 12 13 --control-seeds 21 22 23

For every seed, the cell's timed program at the cell's own size, compared
with the plain reference exactly as a run compares it; for every control
seed, the control in the program's place:

- ``streaming_init`` cells: the chunk program the window drives, run by
  the pool's members (seeds spread over the cell's chips) for chunks 1,
  2, ... of the seed's population until they hold ``check_sample``
  individuals, all of them compared. The control is the model's own
  lower-precision path, ``chem_dtype="bfloat16"``, through the same
  program.

Prints one JSON line per reading and, last, the largest program reading and
the smallest control reading of every number. Everything runs in this one
process, on the chip JAX finds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def streaming(cell, seeds, control_seeds, devices):
    import concurrent.futures as cf
    import itertools

    import numpy as np
    from repro.core.prototype import Context
    from repro.evolution import ga
    from repro.launch.explore import make_init_pool

    from entries import streaming_init as entry
    from reference import ants as ants_ref

    chunk = int(cell.traffic["chunk"])
    n = -(-int(cell.traffic["check_sample"]) // chunk)
    cfg = entry.ga_config(cell.config)
    members = [m.env for m in make_init_pool(
        pool_devices=len(devices)).members]
    job = harness.Job(cell, 0, 0, False, devices, T0, harness.OUT)
    for kind, seeds_, dtype in (("program", seeds, None),
                                ("control", control_seeds, "bfloat16")):
        eval_fn = entry.make_eval(cell.config, dtype)

        def program(seed, member):
            t = time.monotonic()
            task = ga.make_chunk_task(cfg, eval_fn, seed)
            got = np.concatenate([
                member.run_attempt(task, Context(chunk=i, size=chunk))[0][
                    "objectives"] for i in range(1, n + 1)])
            return got, time.monotonic() - t

        # seeds spread over the pool's members, one chip each
        with cf.ThreadPoolExecutor(len(members)) as ex:
            runs = list(ex.map(program, seeds_, itertools.cycle(members)))
        for seed, (got, secs) in zip(seeds_, runs):
            t = time.monotonic()
            want = entry.reference_rows(
                job, [(i, j) for i in range(1, n + 1) for j in range(chunk)],
                chunk, seed)
            yield kind, seed, ants_ref.compare(got, want), \
                secs + time.monotonic() - t


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    import jax
    cell = harness.Cell(harness.load_benchmark(), args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    harness.enable_cache()
    harness.check_device(cell.chips)
    devices = jax.devices()[:cell.chips]
    kinds = {"streaming_init": streaming}
    worst = {"program": {}, "control": {}}
    for kind, seed, numbers, secs in kinds[cell.traffic["entry"]](
            cell, args.seeds, args.control_seeds, devices):
        print(json.dumps({"kind": kind, "seed": seed, **numbers,
                          "seconds": secs}), flush=True)
        pick = max if kind == "program" else min
        for n, v in numbers.items():
            worst[kind][n] = pick(worst[kind].get(n, v), v)
    print(json.dumps({"largest_program": worst["program"],
                      "smallest_control": worst["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
