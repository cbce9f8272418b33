"""Benchmark cells cut to a size the CPU runs in seconds, for the tests."""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

TINY_MODEL = {"world_size": 16, "population": 16, "max_ticks": 40,
              "nest_radius": 3.0, "food_radius": 2.0,
              "diffusion_rate": 50.0, "evaporation_rate": 50.0,
              "chem_dtype": "float32"}
SEED = 2 ** 31 + 17            # past 31 bits, as the benchmark's seeds are


def cell(name: str, **model):
    """The BENCHMARK.json cell ``name`` with the tiny model (``model``
    overriding its keys) and a load to match."""
    c = harness.Cell(harness.load_benchmark(), name)
    c.config = dict(c.config, model=dict(TINY_MODEL, **model))
    c.traffic = dict(c.traffic, chunk=min(int(c.traffic["chunk"]), 4),
                     population=4000,
                     check_sample=min(int(c.traffic["check_sample"]), 32))
    return c


def run(c, out_dir, seed: int = SEED, seconds: float = 0.3) -> dict:
    """One run of a tiny cell on the CPU (no look for a chip)."""
    return harness.run_cell(c, seed=seed, seconds=seconds, trace=False,
                            t0=time.monotonic(), require_tpu=False,
                            out_dir=str(out_dir), compile_cache=False)


def job(c, out_dir, seed: int = SEED, seconds: float = 0.3):
    """A Job for calling an entry's parts directly."""
    import jax
    return harness.Job(c, seed, seconds, False, jax.devices()[:1],
                       time.monotonic(), str(out_dir))
