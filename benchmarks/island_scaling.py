"""Child process for the island_scaling_{k}dev bench rows (benchmarks/run.py
spawns one per simulated device count — XLA's forced host device count is
fixed at jax import, so every count needs a fresh process):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python benchmarks/island_scaling.py --shape full

Times the dominance-sweep-bound island epoch (big archive, cheap synthetic
objective: the merge's O(pool^2) sharded sweep dominates the program, the
shape the EGI scaling story is about) as ONE scanned, donated superstep per
call, on a ("data",) mesh over all forced devices, and proves device
residency en passant: the timed program re-runs under
``jax.transfer_guard("disallow")``. Prints a JSON line with the raw
per-epoch wall samples and a sha256 state digest; the parent checks digests
match across device counts (bit-exactness) and derives the simulated
speedup — on this 1-core host, k forced devices time-share the core, so one
real device's critical path is wall/k (see docs/performance.md).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

SHAPES = {
    # archive_size, n_islands, mu, lam, steps_per_epoch
    "full": (6144, 8, 128, 16, 1),
    "reduced": (768, 8, 32, 8, 1),
}


def synthetic_eval(keys, genomes):
    x0 = genomes[:, 0]
    g = 1 + 9 * genomes[:, 1:].mean(axis=1)
    f2 = g * (1 - jnp.sqrt(jnp.clip(x0 / g, 0, 1)))
    return jnp.stack([x0, f2, (genomes ** 2).sum(1)], axis=1)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(SHAPES), default="full")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    args = ap.parse_args(argv)

    from repro.evolution import NSGA2Config, init_island_state, \
        make_superstep
    from repro.launch.mesh import make_island_mesh
    from repro.runtime import sharding as shd

    archive_size, n_islands, mu, lam, steps = SHAPES[args.shape]
    dim = 4
    cfg = NSGA2Config(mu=mu, genome_dim=dim, bounds=((0., 1.),) * dim,
                      n_objectives=3)
    devices = len(jax.devices())
    mesh = make_island_mesh() if devices > 1 else None
    with shd.use_mesh(mesh):
        state = init_island_state(cfg, jax.random.key(0),
                                  n_islands=n_islands,
                                  archive_size=archive_size)
        sstep = jax.jit(make_superstep(cfg, synthetic_eval, lam=lam,
                                       steps_per_epoch=steps),
                        static_argnums=1, donate_argnums=0)
        for _ in range(args.warmup):
            state, _ = sstep(state, 1)
            jax.block_until_ready(state.archive.objectives)
        samples = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            state, _ = sstep(state, 1)
            jax.block_until_ready(state.archive.objectives)
            samples.append(time.perf_counter() - t0)
        # zero host transfers in the timed program, asserted not claimed
        with jax.transfer_guard("disallow"):
            state, _ = sstep(state, 1)
            jax.block_until_ready(state.archive.objectives)

    h = hashlib.sha256()
    h.update(np.asarray(state.archive.objectives).tobytes())
    h.update(np.asarray(state.islands.genomes).tobytes())
    print(json.dumps({"devices": devices, "shape": args.shape,
                      "samples_s": samples, "digest": h.hexdigest()}))


if __name__ == "__main__":
    main()
