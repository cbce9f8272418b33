"""Blocked right-looking Cholesky factorization + blocked triangular solves
— the O(N^3) wall of GP surrogate fitting (explore/surrogate.py,
explore/bigfit.py), turned into tile dots.

``jnp.linalg.cholesky`` lowers to a LAPACK-style unblocked column sweep on
CPU and a single fused op elsewhere; at archive scale (N in the thousands,
once per lengthscale grid point per round) it is elementwise-bound and
serial. The blocked factorization spends its n^3/3 flops in (block, block)
tile dots instead — MXU work on TPU, gemm-bound on CPU via the jitted
oracle route — and on the CPU runs the 4096-point lengthscale grid
faster than the vmapped LAPACK path.

Three kernels per step k of the right-looking schedule:

  diag     factor tile (k, k) -> L_kk AND its explicit inverse (one call;
           the inverse is what makes panel/solve steps tile DOTS instead
           of substitution sweeps — ref.tri_inv_base_ref).
  panel    L_ik = A_ik @ L_kk^-T for i > k      grid (nb-k-1,), parallel
  trailing A_ij -= L_ik L_jk^T for k < j <= i   grid (nb-k-1, nb-k-1),
           parallel x parallel, upper tiles pass through untouched.

The python-static k loop stitches steps with dynamic_update_slice (in-place
on TPU under jit). ``gp_chol_blocked`` fuses covariance assembly into the
k = 0 sweep: the step-0 kernels take the (block, d) input tiles and
assemble their covariance tile via ``ref.gp_tile_ref`` exactly where the
factorization first touches it, so K + nugget I never exists as an
unfactored matrix in HBM — only the progressively factored buffer does.

The triangular solve kernel keeps the whole X panel for one RHS column
block in VMEM scratch across the sequential row-block dimension. VMEM
ceiling: one (block, n_p) L row panel + the (n_p, rhs_block) scratch
= 4 * n_p * (block + rhs_block) bytes ~ 16 MB at n_p = 8192 with the
256 defaults — callers beyond that shrink rhs_block (the gate in
kernels/ops.py only routes small shapes here anyway; the big-N engine
route is the bitwise-identical jitted oracle).

Bit-exactness: every kernel body computes through the shared tile helpers
in kernels/ref.py (chol_tile_ref / tri_inv_tile_ref / gp_tile_ref) with
the same (block, block) dot shapes and update order as the blocked
oracles — see the contract comment above ref.chol_base_ref. The factor is
bit-reproducible per (shape, block) but block-size-dependent at the last
bit, so callers pin block= where bitwise stability matters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

VMEM_LIMIT = 32 * 2 ** 20


# ---------------------------------------------------------------------------
# step kernels (plain and fused-assembly variants)
# ---------------------------------------------------------------------------
def _diag_kernel(a_ref, l_ref, linv_ref):
    l = ref.chol_tile_ref(a_ref[...])
    l_ref[...] = l
    linv_ref[...] = ref.tri_inv_tile_ref(l)


def _gp_diag_kernel(x_ref, l_ref, linv_ref, *, n, kind, lengthscale, nugget):
    a = ref.gp_tile_ref(x_ref[...], x_ref[...], 0, 0, n, kind=kind,
                        lengthscale=lengthscale, nugget=nugget)
    l = ref.chol_tile_ref(a)
    l_ref[...] = l
    linv_ref[...] = ref.tri_inv_tile_ref(l)


def _panel_kernel(a_ref, linv_ref, o_ref):
    o_ref[...] = ref.tile_dot(a_ref[...], linv_ref[...].T)


def _gp_panel_kernel(xi_ref, x0_ref, linv_ref, o_ref, *, block, n, kind,
                     lengthscale, nugget):
    row0 = (pl.program_id(0) + 1) * block
    a = ref.gp_tile_ref(xi_ref[...], x0_ref[...], row0, 0, n, kind=kind,
                        lengthscale=lengthscale, nugget=nugget)
    o_ref[...] = ref.tile_dot(a, linv_ref[...].T)


def _trailing_kernel(a_ref, pi_ref, pj_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    a = a_ref[...]
    o_ref[...] = jnp.where(
        j <= i, a - ref.tile_dot(pi_ref[...], pj_ref[...].T), a)


def _gp_trailing_kernel(xi_ref, xj_ref, pi_ref, pj_ref, o_ref, *, block, n,
                        kind, lengthscale, nugget):
    i, j = pl.program_id(0), pl.program_id(1)
    a = ref.gp_tile_ref(xi_ref[...], xj_ref[...], (i + 1) * block,
                        (j + 1) * block, n, kind=kind,
                        lengthscale=lengthscale, nugget=nugget)
    o_ref[...] = jnp.where(
        j <= i, a - ref.tile_dot(pi_ref[...], pj_ref[...].T), a)


def _call(kernel, grid, in_specs, out_specs, out_shape, args, interpret,
          semantics, scratch_shapes=()):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=list(scratch_shapes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret)(*args)


def _factor_steps(m, first_step, nb, block, interpret):
    """Shared right-looking driver: ``first_step(0)`` produces the step-0
    (l00, linv, panel, trailing) pieces — from the matrix buffer or fused
    from the inputs — and every later step reads the buffer ``m``."""
    bs = block
    spec = pl.BlockSpec((bs, bs), lambda i: (i, 0))
    one = pl.BlockSpec((bs, bs), lambda i: (0, 0))
    for k in range(nb):
        t = nb - k - 1
        if k == 0:
            l_kk, linv, panel, trail = first_step()
        else:
            s = k * bs
            a_kk = jax.lax.dynamic_slice(m, (s, s), (bs, bs))
            l_kk, linv = _call(
                _diag_kernel, (1,), [one],
                [one, one],
                [jax.ShapeDtypeStruct((bs, bs), jnp.float32)] * 2,
                (a_kk,), interpret, ("arbitrary",))
            panel = trail = None
            if t:
                a_panel = jax.lax.dynamic_slice(m, (s + bs, s),
                                                (t * bs, bs))
                panel = _call(
                    _panel_kernel, (t,), [spec, one], spec,
                    jax.ShapeDtypeStruct((t * bs, bs), jnp.float32),
                    (a_panel, linv), interpret, ("parallel",))
                a_trail = jax.lax.dynamic_slice(m, (s + bs, s + bs),
                                                (t * bs, t * bs))
                trail = _call(
                    _trailing_kernel, (t, t),
                    [pl.BlockSpec((bs, bs), lambda i, j: (i, j)),
                     pl.BlockSpec((bs, bs), lambda i, j: (i, 0)),
                     pl.BlockSpec((bs, bs), lambda i, j: (j, 0))],
                    pl.BlockSpec((bs, bs), lambda i, j: (i, j)),
                    jax.ShapeDtypeStruct((t * bs, t * bs), jnp.float32),
                    (a_trail, panel, panel), interpret,
                    ("parallel", "parallel"))
        s = k * bs
        m = jax.lax.dynamic_update_slice(m, l_kk, (s, s))
        if t:
            m = jax.lax.dynamic_update_slice(m, panel, (s + bs, s))
            m = jax.lax.dynamic_update_slice(m, trail, (s + bs, s + bs))
    return jnp.tril(m)


def chol_blocked(a, *, block=256, interpret=False):
    """Blocked right-looking Cholesky: a (n_p, n_p) f32 SPD with
    n_p % block == 0 (identity-pad past the true size — kernels/ops.py
    does) -> lower L. Bitwise equal to ref.chol_blocked_ref at the same
    block."""
    n_p = a.shape[0]
    nb = n_p // block
    bs = block
    a = a.astype(jnp.float32)
    spec = pl.BlockSpec((bs, bs), lambda i: (i, 0))
    one = pl.BlockSpec((bs, bs), lambda i: (0, 0))

    def first_step():
        t = nb - 1
        l00, linv = _call(
            _diag_kernel, (1,), [one], [one, one],
            [jax.ShapeDtypeStruct((bs, bs), jnp.float32)] * 2,
            (a[:bs, :bs],), interpret, ("arbitrary",))
        if not t:
            return l00, linv, None, None
        panel = _call(
            _panel_kernel, (t,), [spec, one], spec,
            jax.ShapeDtypeStruct((t * bs, bs), jnp.float32),
            (a[bs:, :bs], linv), interpret, ("parallel",))
        trail = _call(
            _trailing_kernel, (t, t),
            [pl.BlockSpec((bs, bs), lambda i, j: (i, j)),
             pl.BlockSpec((bs, bs), lambda i, j: (i, 0)),
             pl.BlockSpec((bs, bs), lambda i, j: (j, 0))],
            pl.BlockSpec((bs, bs), lambda i, j: (i, j)),
            jax.ShapeDtypeStruct((t * bs, t * bs), jnp.float32),
            (a[bs:, bs:], panel, panel), interpret, ("parallel", "parallel"))
        return l00, linv, panel, trail

    return _factor_steps(a, first_step, nb, block, interpret)


def gp_chol_blocked(x, n, *, kind="matern52", lengthscale=0.2, nugget=1e-4,
                    block=256, interpret=False):
    """Fused covariance assembly + factorization: x (n_p, d) zero-padded
    unit-cube inputs (true count n, n_p % block == 0) -> lower Cholesky of
    [K(x, x) + nugget I] with identity past n. The step-0 kernels assemble
    each covariance tile from the input tiles (ref.gp_tile_ref) at first
    touch, so the unfactored K never round-trips HBM; steps k > 0 run the
    plain blocked schedule on the progressively factored buffer. Bitwise
    equal to ref.gp_chol_blocked_ref at the same block."""
    n_p, d = x.shape
    nb = n_p // block
    bs = block
    x = x.astype(jnp.float32)
    kw = dict(n=n, kind=kind, lengthscale=float(lengthscale),
              nugget=float(nugget))
    xspec = pl.BlockSpec((bs, d), lambda i: (i, 0))
    xone = pl.BlockSpec((bs, d), lambda i: (0, 0))
    one = pl.BlockSpec((bs, bs), lambda i: (0, 0))
    spec = pl.BlockSpec((bs, bs), lambda i: (i, 0))
    m0 = jnp.zeros((n_p, n_p), jnp.float32)

    def first_step():
        t = nb - 1
        l00, linv = _call(
            functools.partial(_gp_diag_kernel, **kw), (1,), [xone],
            [one, one], [jax.ShapeDtypeStruct((bs, bs), jnp.float32)] * 2,
            (x[:bs],), interpret, ("arbitrary",))
        if not t:
            return l00, linv, None, None
        panel = _call(
            functools.partial(_gp_panel_kernel, block=bs, **kw), (t,),
            [xspec, xone, one], spec,
            jax.ShapeDtypeStruct((t * bs, bs), jnp.float32),
            (x[bs:], x[:bs], linv), interpret, ("parallel",))
        trail = _call(
            functools.partial(_gp_trailing_kernel, block=bs, **kw), (t, t),
            [pl.BlockSpec((bs, d), lambda i, j: (i, 0)),
             pl.BlockSpec((bs, d), lambda i, j: (j, 0)),
             pl.BlockSpec((bs, bs), lambda i, j: (i, 0)),
             pl.BlockSpec((bs, bs), lambda i, j: (j, 0))],
            pl.BlockSpec((bs, bs), lambda i, j: (i, j)),
            jax.ShapeDtypeStruct((t * bs, t * bs), jnp.float32),
            (x[bs:], x[bs:], panel, panel), interpret,
            ("parallel", "parallel"))
        return l00, linv, panel, trail

    return _factor_steps(m0, first_step, nb, block, interpret)


# ---------------------------------------------------------------------------
# blocked triangular solve
# ---------------------------------------------------------------------------
def _diag_inv_kernel(l_ref, o_ref):
    o_ref[0] = ref.tri_inv_tile_ref(l_ref[...])


def _solve_fwd_kernel(l_ref, linv_ref, b_ref, o_ref, x_scr, *, nb, block):
    i = pl.program_id(1)
    acc = b_ref[...]
    for j in range(nb):
        lij = l_ref[:, j * block:(j + 1) * block]
        d = ref.tile_dot(lij, x_scr[j])
        acc = acc - jnp.where(j < i, d, jnp.zeros_like(d))
    xi = ref.tile_dot(linv_ref[0], acc)
    x_scr[i] = xi
    o_ref[...] = xi


def _solve_bwd_kernel(l_ref, linv_ref, b_ref, o_ref, x_scr, *, nb, block):
    r = nb - 1 - pl.program_id(1)
    acc = b_ref[...]
    for j in range(nb):
        ljr = l_ref[j * block:(j + 1) * block, :]
        d = ref.tile_dot(ljr.T, x_scr[j])
        acc = acc - jnp.where(j > r, d, jnp.zeros_like(d))
    xr = ref.tile_dot(linv_ref[0].T, acc)
    x_scr[r] = xr
    o_ref[...] = xr


def tri_solve_blocked(l, b, *, trans=False, block=256, rhs_block=256,
                      interpret=False):
    """Blocked triangular solve: L (n_p, n_p) lower (identity-padded),
    B (n_p, m_p), tile multiples -> X with L X = B (forward) or
    L^T X = B (trans=True). Grid = (RHS column blocks [parallel], row
    blocks [sequential]); the solved X panel persists in VMEM scratch
    across the sequential dimension (see module docstring for the VMEM
    ceiling). Bitwise equal to ref.tri_solve_blocked_ref at the same
    (block, rhs_block)."""
    n_p = l.shape[0]
    m_p = b.shape[1]
    nb, ncb = n_p // block, m_p // rhs_block
    bs = block
    l = l.astype(jnp.float32)
    b = b.astype(jnp.float32)

    linvs = pl.pallas_call(
        _diag_inv_kernel, grid=(nb,),
        in_specs=[pl.BlockSpec((bs, bs), lambda i: (i, i))],
        out_specs=pl.BlockSpec((1, bs, bs), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, bs, bs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)(l)

    if not trans:
        kernel = functools.partial(_solve_fwd_kernel, nb=nb, block=bs)
        l_spec = pl.BlockSpec((bs, n_p), lambda c, i: (i, 0))
        linv_spec = pl.BlockSpec((1, bs, bs), lambda c, i: (i, 0, 0))
        b_spec = pl.BlockSpec((bs, rhs_block), lambda c, i: (i, c))
    else:
        kernel = functools.partial(_solve_bwd_kernel, nb=nb, block=bs)
        l_spec = pl.BlockSpec((n_p, bs), lambda c, i: (0, nb - 1 - i))
        linv_spec = pl.BlockSpec((1, bs, bs),
                                 lambda c, i: (nb - 1 - i, 0, 0))
        b_spec = pl.BlockSpec((bs, rhs_block),
                              lambda c, i: (nb - 1 - i, c))

    return pl.pallas_call(
        kernel, grid=(ncb, nb),
        in_specs=[l_spec, linv_spec, b_spec],
        out_specs=b_spec,
        out_shape=jax.ShapeDtypeStruct((n_p, m_p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((nb, bs, rhs_block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret)(l, linvs, b)
