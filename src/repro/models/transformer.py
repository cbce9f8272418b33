"""Decoder-only LM assembly (dense / MoE / SSM / hybrid).

The layer stack is expressed as cfg.pattern (a short tuple of (mixer, ff)
kinds) repeated cfg.n_blocks times. Block parameters are *stacked* along a
leading "layers" axis and the stack runs under ``lax.scan`` — this keeps the
HLO size O(pattern) instead of O(n_layers) (critical for 512-way SPMD
compiles) and gives remat a natural unit.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, MLA_, SSM, DENSE_FF, MOE_FF, NO_FF
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (pack, embed_init, dense_init, make_norm,
                                 apply_norm)
from repro.runtime.sharding import constrain

_ZERO_AUX = {"load_balance_loss": jnp.float32(0.0),
             "dropped_frac": jnp.float32(0.0)}


# ===========================================================================
# Init
# ===========================================================================
def _layer_init(cfg, mixer, ff, key, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    parts: Dict[str, Any] = {"norm1": make_norm(cfg, dtype)}
    if mixer == ATTN:
        parts["mixer"] = attn.gqa_init(cfg, k1, dtype)
    elif mixer == MLA_:
        parts["mixer"] = attn.mla_init(cfg, k1, dtype)
    elif mixer == SSM:
        parts["mixer"] = ssm_mod.ssm_init(cfg, k1, dtype)
    else:
        raise ValueError(mixer)
    if ff != NO_FF:
        parts["norm2"] = make_norm(cfg, dtype)
        if ff == DENSE_FF:
            parts["ff"] = mlp_mod.mlp_init(cfg, k2, dtype)
        elif ff == MOE_FF:
            parts["ff"] = moe_mod.moe_init(cfg, k2, dtype)
        else:
            raise ValueError(ff)
    return pack(**parts)


def _block_init(cfg, key, dtype):
    keys = jax.random.split(key, len(cfg.pattern))
    parts = {f"layer{i}": _layer_init(cfg, mixer, ff, keys[i], dtype)
             for i, (mixer, ff) in enumerate(cfg.pattern)}
    return pack(**parts)


def _stack_pairs(pairs):
    """[(params, axes), ...] -> (stacked params, axes with 'layers' prepended)."""
    params = [p for p, _ in pairs]
    axes = pairs[0][1]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params)
    axes_stacked = jax.tree.map(lambda ax: ("layers",) + tuple(ax), axes,
                                is_leaf=lambda x: isinstance(x, tuple))
    return stacked, axes_stacked


def init_params(cfg, key, dtype):
    """Returns (params, axes) pair for the whole LM."""
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    block_keys = jax.random.split(k_blocks, cfg.n_blocks)
    blocks = _stack_pairs([_block_init(cfg, bk, dtype) for bk in block_keys])
    parts = dict(
        embed=embed_init(k_emb, cfg.padded_vocab, cfg.d_model, dtype),
        blocks=blocks,
        final_norm=make_norm(cfg, dtype),
    )
    if not cfg.tie_embeddings:
        parts["unembed"] = dense_init(k_head, (cfg.d_model, cfg.padded_vocab),
                                      ("embed", "vocab"), dtype, scale=0.02)
    return pack(**parts)


# ===========================================================================
# Forward (full sequence: train / prefill)
# ===========================================================================
def _apply_layer(cfg, lp, mixer, ff, x, positions, mask, rng,
                 cache=None, write_cache=False):
    """One (mixer, ff) layer. Returns (x, aux, new_cache)."""
    aux = _ZERO_AUX
    new_cache = cache
    h = apply_norm(cfg, x, lp["norm1"])
    if mixer == ATTN:
        if write_cache:
            out, new_cache = attn.gqa_prefill(cfg, lp["mixer"], h, positions,
                                              mask, cache)
        else:
            out = attn.gqa_apply(cfg, lp["mixer"], h, positions, mask)
    elif mixer == MLA_:
        if write_cache:
            out, new_cache = attn.mla_apply(cfg, lp["mixer"], h, positions,
                                            mask, cache)
        else:
            out = attn.mla_apply(cfg, lp["mixer"], h, positions, mask)
    elif mixer == SSM:
        if write_cache:
            out, new_cache = ssm_mod.ssm_apply(cfg, lp["mixer"], h,
                                               return_cache=True)
        else:
            out = ssm_mod.ssm_apply(cfg, lp["mixer"], h)
    else:
        raise ValueError(mixer)
    x = x + out
    if ff != NO_FF:
        h = apply_norm(cfg, x, lp["norm2"])
        if ff == DENSE_FF:
            out = mlp_mod.mlp_apply(cfg, lp["ff"], h)
        else:
            out, moe_aux = moe_mod.moe_apply(cfg, lp["ff"], h, rng)
            aux = {"load_balance_loss": moe_aux["load_balance_loss"],
                   "dropped_frac": moe_aux["dropped_frac"]}
        x = x + out
    x = constrain(x, ("batch", "seq", None))
    return x, aux, new_cache


def _remat_wrap(cfg, fn):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def forward(cfg, params, tokens, rng=None, caches=None, write_cache=False,
            inputs_embeds=None, positions=None):
    """Full-sequence forward. tokens: (B,S) int32 (or inputs_embeds (B,S,d)).

    Returns (hidden (B,S,d), aux, new_caches). Logits are computed by the
    caller (loss wants f32 logits, prefill wants only the last position).
    """
    if inputs_embeds is None:
        x = params["embed"][tokens]
    else:
        x = inputs_embeds
    b, s = x.shape[:2]
    x = constrain(x, ("batch", "seq", None))
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    mask = jnp.tril(jnp.ones((s, s), bool))

    def block_fn(carry, xs):
        x, lb, dropped = carry
        bp, bc = xs
        new_bc = {}
        for i, (mixer, ff) in enumerate(cfg.pattern):
            name = f"layer{i}"
            cache_i = bc.get(name) if bc is not None else None
            x, aux, nc = _apply_layer(cfg, bp[name], mixer, ff, x, positions,
                                      mask, rng, cache_i, write_cache)
            new_bc[name] = nc if nc is not None else {}
            lb = lb + aux["load_balance_loss"]
            dropped = dropped + aux["dropped_frac"]
        return (x, lb, dropped), new_bc

    block_fn = _remat_wrap(cfg, block_fn)
    init = (x, jnp.float32(0.0), jnp.float32(0.0))
    if caches is None:
        caches = {f"layer{i}": {} for i in range(len(cfg.pattern))}
    (x, lb, dropped), new_caches = jax.lax.scan(
        block_fn, init, (params["blocks"], caches))
    x = apply_norm(cfg, x, params["final_norm"])
    aux = {"load_balance_loss": lb, "dropped_frac": dropped / cfg.n_layers}
    return x, aux, new_caches


def logits_from_hidden(cfg, params, hidden):
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", hidden, params["embed"])
    else:
        logits = hidden @ params["unembed"]
    return mask_padded_vocab(cfg, logits)


def mask_padded_vocab(cfg, logits):
    """Vocab-padded slots never win argmax/softmax."""
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
    return jnp.where(pad, jnp.asarray(-1e30, logits.dtype), logits)


# ===========================================================================
# Caches
# ===========================================================================
def init_cache(cfg, batch, max_seq, dtype):
    """Stacked (over blocks) cache pytree + its logical axes tree."""
    per_layer = {}
    axes_per_layer = {}
    for i, (mixer, _) in enumerate(cfg.pattern):
        name = f"layer{i}"
        if mixer == ATTN:
            per_layer[name] = attn.gqa_init_cache(cfg, batch, max_seq, dtype)
            axes_per_layer[name] = attn.gqa_cache_axes()
        elif mixer == MLA_:
            per_layer[name] = attn.mla_init_cache(cfg, batch, max_seq, dtype)
            axes_per_layer[name] = attn.mla_cache_axes()
        elif mixer == SSM:
            per_layer[name] = ssm_mod.ssm_init_cache(cfg, batch, dtype)
            axes_per_layer[name] = ssm_mod.ssm_cache_axes()
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.n_blocks,) + x.shape), per_layer)
    axes = jax.tree.map(lambda ax: ("layers",) + tuple(ax), axes_per_layer,
                        is_leaf=lambda x: isinstance(x, tuple))
    return stacked, axes


# ===========================================================================
# Decode (one token)
# ===========================================================================
def decode_step(cfg, params, token, positions, caches):
    """token: (B,1) int32; positions: (B,) int32. Returns (logits, caches)."""
    x = params["embed"][token]
    x = constrain(x, ("batch", None, None))

    def block_fn(x, xs):
        bp, bc = xs
        new_bc = {}
        for i, (mixer, ff) in enumerate(cfg.pattern):
            name = f"layer{i}"
            lp = bp[name]
            h = apply_norm(cfg, x, lp["norm1"])
            if mixer == ATTN:
                out, nc = attn.gqa_decode(cfg, lp["mixer"], h, positions,
                                          bc[name])
            elif mixer == MLA_:
                out, nc = attn.mla_decode(cfg, lp["mixer"], h, positions,
                                          bc[name])
            else:
                out, nc = ssm_mod.ssm_decode(cfg, lp["mixer"], h, bc[name])
            x = x + out
            new_bc[name] = nc
            if ff != NO_FF:
                h = apply_norm(cfg, x, lp["norm2"])
                if ff == DENSE_FF:
                    out = mlp_mod.mlp_apply(cfg, lp["ff"], h)
                else:
                    out, _ = moe_mod.moe_apply(cfg, lp["ff"], h)
                x = x + out
        return x, new_bc

    x, new_caches = jax.lax.scan(block_fn, x, (params["blocks"], caches))
    x = apply_norm(cfg, x, params["final_norm"])
    logits = logits_from_hidden(cfg, params, x)
    return logits, new_caches
