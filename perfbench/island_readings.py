"""Control readings for the island cell's ``diverged`` and ``chem_gap``
limits (not a benchmark run).

    python3 perfbench/island_readings.py --workload islands.8x16 \
        --control-seeds 21 22 23

For every seed: the islands ``init_island_state`` draws from it (as a run
of the cell starts them), ``check_sample`` of their individuals picked as a
run picks children, each evaluated on a key drawn from the seed. Their
lanes are computed by the plain reference in the configuration's float32
and in bfloat16 (the nearest precision below), and the bfloat16 lanes are
compared with the float32 ones exactly as a run compares the program's
(``entries/island_ga.py`` ``compare_lanes``). The program's own readings
are those of the cell's runs (their ``checks``). Prints one JSON line per
seed and, last, the smallest reading of every number. Runs in this one
process, on the chip JAX finds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def control(cell, seed: int) -> dict:
    import jax
    import numpy as np
    from repro.configs.ants_netlogo import BOUNDS
    from repro.evolution import NSGA2Config, init_island_state

    from entries import island_ga

    cfg = cell.config
    s = island_ga.ga_settings(cfg)
    ga_cfg = NSGA2Config(mu=s["mu"], genome_dim=2, bounds=BOUNDS,
                         n_objectives=int(cfg["ga"]["n_objectives"]))
    islands = init_island_state(ga_cfg, jax.random.key(seed),
                                n_islands=s["n_islands"],
                                archive_size=s["archive_size"]).islands
    keys = jax.random.split(jax.random.fold_in(jax.random.key(seed), 1),
                            (s["n_islands"], s["mu"]))
    record = SimpleNamespace(step=SimpleNamespace(
        children=np.asarray(islands.genomes),
        child_keys=np.asarray(jax.random.key_data(keys))))
    picks = island_ga.sample(seed, s["n_islands"], s["mu"],
                             int(cell.traffic["check_sample"]))
    r = s["replicates"]
    want = island_ga.reference_lane_rows(cfg["model"], record, picks, r)
    low = island_ga.reference_lane_rows(cfg["model"], record, picks, r,
                                        "bfloat16")
    objectives = np.median(low[:, :3].reshape(-1, r, 3), axis=1)
    return island_ga.compare_lanes(low, want, objectives, r)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="islands.8x16")
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.load_benchmark(), args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    harness.enable_cache()
    harness.check_device(cell.chips)
    smallest = {}
    for seed in args.control_seeds:
        t = time.monotonic()
        numbers = control(cell, seed)
        print(json.dumps({"kind": "control", "seed": seed, **numbers,
                          "seconds": time.monotonic() - t}), flush=True)
        for n, v in numbers.items():
            smallest[n] = min(smallest.get(n, v), v)
    print(json.dumps({"smallest_control": smallest}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
