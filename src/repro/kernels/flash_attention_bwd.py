"""Backward pass for the causal flash-attention kernel (dQ, dK, dV), plus a
forward variant that also emits the row logsumexp needed by the backward.

Standard flash backward (Dao et al.):
    L   = m + log(l)                       (from forward, per row)
    D   = rowsum(dO * O)                   (per row)
    P   = exp(Q K^T * scale - L)
    dV  = P^T dO
    dS  = P * (dO V^T - D)
    dQ  = dS K * scale
    dK  = dS^T Q * scale

Two kernels: dQ accumulates over k-blocks (k innermost, sequential); dK/dV
accumulate over q-blocks (q innermost). Both keep f32 accumulators in VMEM
scratch. GQA is handled by computing per-q-head dK/dV and group-summing
outside the kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Forward with logsumexp output
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, block_q, block_k, causal):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_cur = jnp.max(s, axis=-1)[:, None]
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_prev + p.sum(-1)[:, None]
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] +
                         jnp.log(jnp.maximum(l_scr[...], 1e-30)))[:, 0]


def flash_attention_fwd(q, k, v, *, causal=True, block_q=512, block_k=512,
                        interpret=False):
    """(B,H,S,D) x (B,KH,S,D)^2 -> (out (B,H,S,D), lse (B,H,S) f32)."""
    b, h, s, d = q.shape
    kh = k.shape[1]
    group = h // kh
    block_q, block_k = min(block_q, s), min(block_k, s)
    nq, nk = s // block_q, s // block_k
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, qi)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward: dQ kernel (accumulate over k-blocks)
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dq_ref,
               acc_scr, *, scale, block_q, block_k, causal):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, None]
    dsum = dsum_ref[0, 0][:, None]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    p = jnp.exp(s - lse)
    dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    ds = p * (dov - dsum)
    acc_scr[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Backward: dK/dV kernel (accumulate over q-blocks)
# ---------------------------------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale, block_q, block_k, causal):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, None]
    dsum = dsum_ref[0, 0][:, None]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    p = jnp.exp(s - lse)                                   # (bq, bk)
    dv_scr[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),                   # (bk, d)
        preferred_element_type=jnp.float32)
    dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    ds = p * (dov - dsum)
    dk_scr[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),                   # (bk, d)
        preferred_element_type=jnp.float32) * scale

    @pl.when(qi == nq - 1)
    def _fin():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, do, *, causal=True, block_q=512,
                        block_k=512, interpret=False):
    """Returns (dq (B,H,S,D), dk_h (B,H,S,D), dv_h (B,H,S,D)) — per-q-head
    dK/dV; the GQA group-sum to (B,KH,S,D) happens in the caller."""
    b, h, s, d = q.shape
    kh = k.shape[1]
    group = h // kh
    block_q, block_k = min(block_q, s), min(block_k, s)
    nq, nk = s // block_q, s // block_k
    scale = 1.0 / math.sqrt(d)
    dsum = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, qi)),
            pl.BlockSpec((1, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, qi)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, dsum)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal),
        grid=(b, h, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bi, hi, ki, qi: (bi, hi, qi)),
            pl.BlockSpec((1, 1, block_q), lambda bi, hi, ki, qi: (bi, hi, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, s, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, dsum)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Differentiable wrapper
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_diff(q, k, v, causal=True, block_q=512, block_k=512,
                         interpret=False):
    out, _ = flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                                 block_k=block_k, interpret=interpret)
    return out


def _diff_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                                   block_k=block_k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _diff_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    b, h, s, d = q.shape
    kh = k.shape[1]
    dq, dk_h, dv_h = flash_attention_bwd(
        q, k, v, out, lse, do, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret)
    # GQA: sum per-q-head contributions within each kv group
    dk = dk_h.reshape(b, kh, h // kh, s, d).sum(2).astype(k.dtype)
    dv = dv_h.reshape(b, kh, h // kh, s, d).sum(2).astype(v.dtype)
    return dq, dk, dv


flash_attention_diff.defvjp(_diff_fwd, _diff_bwd)
