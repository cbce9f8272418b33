"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — required because only dryrun.py forces 512 host
devices; tests and benches see 1 device.
"""
from __future__ import annotations

import jax


def compat_make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in ``Auto`` sharding mode (the
    sharding rules in runtime/sharding.py constrain, they do not type)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat_make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist, as a 1D 'data' mesh (tests/laptop)."""
    n = len(jax.devices())
    return compat_make_mesh((n,), ("data",))


def make_island_mesh(pod: int = 1, data: int = 0):
    """The island-evolution mesh: the ``("pod", "data")`` axes the island
    logical axis resolves onto (runtime/sharding.RULES). data=0 spreads all
    (global, post-``init_distributed``) devices over the data axis; pod > 1
    folds the leading factor onto a pod axis (multi-host: one pod per
    process group)."""
    n = len(jax.devices())
    if data <= 0:
        if n % max(pod, 1):
            raise ValueError(f"pod={pod} does not divide {n} devices")
        data = n // max(pod, 1)
    if pod > 1:
        return compat_make_mesh((pod, data), ("pod", "data"))
    return compat_make_mesh((data,), ("data",))


def init_distributed(*, coordinator: str = None, num_processes: int = None,
                     process_id: int = None, force: bool = False) -> bool:
    """`jax.distributed.initialize` for the multi-process mesh entry path
    (launch/explore.py --distributed): every process contributes its local
    devices to one global mesh, and the SPMD epoch program spans them. A
    no-op (returns False) when no argument is given and force is False, so
    single-process drivers call it unconditionally; force=True with all-None
    arguments defers to the standard cluster environment variables
    (JAX_COORDINATOR_ADDRESS etc.)."""
    if not force and coordinator is None and num_processes is None \
            and process_id is None:
        return False
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~3 links/chip on v5e 2D torus)
HBM_BYTES = 16 * 2 ** 30        # 16 GB HBM per chip
