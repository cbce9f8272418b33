"""Run one benchmark cell once and print its result line.

    python3 perfbench/run.py --workload egi_init.chunk64 --seed 7 \
        --seconds 10 --trace 0

The cell, its configuration and traffic are named in BENCHMARK.json at the
checkout's root; see harness.py. The last line of standard output is one
JSON object (correct, attempted, failed, metrics, device, checks). Exits
non-zero, printing no result, where JAX finds no TPU or fewer chips than
the cell needs.
"""
import os
import sys
import time

T0 = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T0))
