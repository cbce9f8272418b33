"""Fused diffuse+evaporate stencil Pallas kernel — the per-tick hot spot of
the paper's ants workload.

NetLogo ``diffuse chemical rate`` semantics on a *bounded* world: every patch
gives ``rate/8`` of its value to each of its 8 neighbours; shares that would
fall off the edge are kept (edge patches have <8 neighbours). Followed by the
evaporation multiply — fused into one VMEM pass.

The GA evaluates thousands of candidate worlds at once, so the caller's
array is (N, W, W) with N the vectorized population lane. The kernel works
on the (W, W, N) view: lanes on the 128-wide lane axis, world columns on
sublanes, world rows on the untiled leading axis. XLA already keeps the ants
state N-minor (72 is a poor lane width), so the two transposes around the
call are layout bitcasts, not copies. Each grid step owns ``LANES`` = 128
lanes with the full world resident in VMEM (N pads up to a multiple of
128 with zero worlds). Neighbour shifts are static slices of the leading
axis (rows) and zero-filled slice+concatenate moves along sublanes
(columns), which the TPU compiler lowers at any W.

VMEM at the paper's W = 72 (measured against the v5e compiler): one
(72, 72, 128) f32 block is 72 x 9 (8, 128) tiles of 4 KiB = 2.53 MiB with no
padding, so the double-buffered input and output blocks take 4 x 2.53 =
10.1 MiB. The kernel walks the block in strips of ``STRIP`` = 8 output rows
(plus one halo row each side), which keeps its temporaries near 1.6 MiB;
the compiler accepts the kernel at a 12 MiB limit and refuses it at
11.5 MiB. ``vmem_limit_bytes`` = 32 MiB leaves 2.7x headroom (a v5e core has
128 MiB of VMEM; the scoped default is 16 MiB). Without the strips the
whole-block temporaries needed 25 MiB on top of the buffers.

Routes (kernels/ops.py): compiled on TPU, interpret mode for CPU tests. The
jnp oracle ``ref.diffuse_evaporate_ref`` adds the same terms in the same
order, so the kernel agrees with it to f32 rounding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
STRIP = 8
VMEM_LIMIT = 32 * 2 ** 20


def _shift(x, d, axis):
    """``out[..., i, ...] = x[..., i + d, ...]`` along ``axis``, zero where
    ``i + d`` falls off the world (d in {-1, 0, 1})."""
    if d == 0:
        return x
    n = x.shape[axis]
    zero = jnp.zeros_like(jax.lax.slice_in_dim(x, 0, 1, axis=axis))
    if d == 1:
        return jnp.concatenate(
            [jax.lax.slice_in_dim(x, 1, n, axis=axis), zero], axis)
    return jnp.concatenate(
        [zero, jax.lax.slice_in_dim(x, 0, n - 1, axis=axis)], axis)


def _diffuse_kernel(chem_ref, rate_ref, evap_ref, o_ref):
    """One (W, W, bl) block of lane worlds, ``STRIP`` output rows at a time:
    a strip reads its rows plus one halo row on each side (zero past the
    world's edge), so in-kernel temporaries stay strip-sized."""
    w = chem_ref.shape[0]
    rate = rate_ref[...]                       # (1, bl) in [0, 1]
    keep = 1.0 - evap_ref[...]                 # (1, bl)
    for lo in range(0, w, STRIP):
        hi = min(lo + STRIP, w)
        h = hi - lo
        share = chem_ref[max(lo - 1, 0):min(hi + 1, w)] * rate / 8.0
        zero = jnp.zeros((1,) + share.shape[1:], share.dtype)
        share = jnp.concatenate(([zero] if lo == 0 else []) + [share]
                                + ([zero] if hi == w else []))
        # share row r is world row lo - 1 + r; add the (di, dj) terms in
        # the oracle's row-major order
        acc = jnp.zeros((h,) + share.shape[1:], share.dtype)
        for di in (-1, 0, 1):
            rows = share[1 + di:1 + di + h]
            for dj in (-1, 0, 1):
                if (di, dj) != (0, 0):
                    acc = acc + _shift(rows, dj, 1)
        # in-bounds neighbour count: 8 interior, 5 edge, 3 corner
        row = jax.lax.broadcasted_iota(jnp.int32, (h, 1, 1), 0) + lo
        col = jax.lax.broadcasted_iota(jnp.int32, (1, w, 1), 1)
        span_r = 3 - (row == 0).astype(jnp.int32) - (row == w - 1)
        span_c = 3 - (col == 0).astype(jnp.int32) - (col == w - 1)
        ncount = (span_r * span_c - 1).astype(jnp.float32)
        kept = chem_ref[lo:hi] - share[1:1 + h] * ncount
        o_ref[lo:hi] = (kept + acc) * keep


def diffuse_evaporate(chem, rate, evap, *, interpret=False):
    """chem: (N, W, W) f32; rate/evap: (N,) f32 fractions in [0,1]."""
    n, w, _ = chem.shape
    n_p = -(-n // LANES) * LANES
    if n_p != n:                # pad lanes: zero worlds, zero rates
        chem = jnp.pad(chem, ((0, n_p - n), (0, 0), (0, 0)))
        rate = jnp.pad(rate, (0, n_p - n))
        evap = jnp.pad(evap, (0, n_p - n))
    lanes = pl.BlockSpec((1, LANES), lambda i: (0, i))
    world = pl.BlockSpec((w, w, LANES), lambda i: (0, 0, i))
    out = pl.pallas_call(
        _diffuse_kernel,
        grid=(n_p // LANES,),
        in_specs=[world, lanes, lanes],
        out_specs=world,
        out_shape=jax.ShapeDtypeStruct((w, w, n_p), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="diffuse_evaporate",
    )(chem.transpose(1, 2, 0), rate[None, :], evap[None, :])
    return out.transpose(2, 0, 1)[:n]
