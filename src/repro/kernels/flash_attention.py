"""Causal flash attention (online softmax) Pallas TPU kernel with GQA.

TPU mapping: grid = (batch, q_heads, num_q_blocks, num_k_blocks) with the
k-block dim innermost ("arbitrary" = sequential on TPU), so the running
(m, l, acc) state lives in VMEM scratch across k iterations. Block shapes are
(block_q, head_dim) / (block_k, head_dim) — head_dim is 64/128 in all our
configs, matching MXU lane width; block_q/block_k default to 512/512 which
keeps the working set (q + k + v + acc + scores) well under VMEM:
  512*128*4B * 3 + 512*512*4B + 512*128*4B ≈ 1.4 MB.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, block_q, block_k, seq_len, causal):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1)[:, None]           # (bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                         # (bq, bk)
    l_new = alpha * l_prev + p.sum(axis=-1)[:, None]
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, block_q=512, block_k=512,
                    interpret=False):
    """q: (B, H, S, D); k, v: (B, KH, S, D) with H % KH == 0. Returns (B,H,S,D).

    Blocks over (q, k); GQA handled by the k/v index_map (h -> h // group).
    """
    b, h, s, d = q.shape
    kh = k.shape[1]
    assert h % kh == 0, (h, kh)
    group = h // kh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    nq, nk = s // block_q, s // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_len=s, causal=causal)

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
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),   # output acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
