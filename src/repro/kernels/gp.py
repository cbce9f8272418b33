"""Batched Gaussian-process covariance assembly — the O(N^2 * D) hot spot of
surrogate-assisted exploration (explore/surrogate.py).

Every GP fit and every acquisition evaluation assembles covariance or
cross-covariance matrices; at archive scale (thousands of observations x
thousands of candidates, every optimizer step) that assembly dominates the
proposal loop. Two entry points share one tiling scheme:

``gp_sqdist``
    (N1, D) x (N2, D) -> (N1, N2) squared Euclidean distances via the
    expanded form ||a||^2 + ||b||^2 - 2 a.b — one fused pass, tile-local
    norms and cross terms, no global (N1, N2, D) intermediate ever
    materialized (the product intermediate is tile-local). Used by the
    lengthscale-fit path, where the covariance map must stay traceable in
    the lengthscale.

``gp_matrix``
    The fully fused assembly: distances AND the stationary covariance map
    (Matérn-5/2 or RBF, fixed hyper-parameters) in one kernel — the
    acquisition hot path, where hyper-parameters are frozen per round.

Grid = (num_i_blocks, num_j_blocks), both parallel (each tile is
independent). Feature dim D is tiny (genome dims, <= 32), so blocks are
(block, D) rows against (block, D) columns:

    VMEM ≈ 2*block*D*4 B     (row/col tiles)
         + block^2 * 4 B     (the output tile)
         + block^2 * D * 4 B (tile-local product)  ≈ 4.5 MB at block=256,
                                                     D=16

Indivisible N pads rows with zeros up to a block multiple (the padded
covariance entries are sliced off by the caller, so — unlike dominance.py's
+BIG sentinels, which must not perturb *reductions* — any finite pad value
is correct here; zeros keep ||pad||^2 = 0 and every tile finite).

Bit-exactness: the kernel body computes through ``ref.gp_sqdist_ref`` /
``ref.gp_kernel_fn`` — the same helpers the jnp oracle uses — so kernel and
reference agree bitwise per element (asserted across shapes/dtypes,
including prime N and duplicate rows, in tests/test_surrogate.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.dominance import _ceil_to, _pad_rows, effective_block


def _sqdist_kernel(x1_ref, x2_ref, o_ref):
    o_ref[...] = ref.gp_sqdist_ref(x1_ref[...], x2_ref[...])


def _matrix_kernel(x1_ref, x2_ref, o_ref, *, kind, lengthscale, variance):
    d2 = ref.gp_sqdist_ref(x1_ref[...], x2_ref[...])
    o_ref[...] = ref.gp_kernel_fn(kind, d2, lengthscale, variance)


def _tiled_call(kernel, x1, x2, *, block, interpret):
    n1, d = x1.shape
    n2 = x2.shape[0]
    bs = effective_block(max(n1, n2), block, 8)
    n1_p, n2_p = _ceil_to(n1, bs), _ceil_to(n2, bs)
    out = pl.pallas_call(
        kernel,
        grid=(n1_p // bs, n2_p // bs),
        in_specs=[
            pl.BlockSpec((bs, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bs, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bs, bs), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n1_p, n2_p), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(_pad_rows(x1.astype(jnp.float32), n1_p, 0.0),
      _pad_rows(x2.astype(jnp.float32), n2_p, 0.0))
    return out[:n1, :n2]


def gp_sqdist(x1, x2, *, block=256, interpret=False):
    """x1: (N1, D), x2: (N2, D) -> (N1, N2) f32 squared distances."""
    return _tiled_call(_sqdist_kernel, x1, x2, block=block,
                       interpret=interpret)


def gp_matrix(x1, x2, *, kind="matern52", lengthscale=0.2, variance=1.0,
              block=256, interpret=False):
    """Fused covariance assembly: x1 (N1, D), x2 (N2, D) -> (N1, N2) f32
    K[i, j] = k(x1[i], x2[j]) for fixed (python-float) hyper-parameters."""
    kern = functools.partial(_matrix_kernel, kind=kind,
                             lengthscale=float(lengthscale),
                             variance=float(variance))
    return _tiled_call(kern, x1, x2, block=block, interpret=interpret)
