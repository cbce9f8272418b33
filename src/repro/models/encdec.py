"""Encoder-decoder backbone (Whisper-base). The audio conv frontend is a
STUB: callers provide precomputed frame embeddings (B, enc_seq, d_model).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models.common import (pack, embed_init, make_norm, apply_norm,
                                 sinusoidal_positions)
from repro.models.transformer import _stack_pairs
from repro.runtime.sharding import constrain


# ===========================================================================
# Init
# ===========================================================================
def _enc_layer_init(cfg, key, dtype):
    k1, k2 = jax.random.split(key)
    return pack(
        norm1=make_norm(cfg, dtype),
        self_attn=attn.gqa_init(cfg, k1, dtype),
        norm2=make_norm(cfg, dtype),
        ff=mlp_mod.mlp_init(cfg, k2, dtype),
    )


def _dec_layer_init(cfg, key, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return pack(
        norm1=make_norm(cfg, dtype),
        self_attn=attn.gqa_init(cfg, k1, dtype),
        norm_x=make_norm(cfg, dtype),
        cross_attn=attn.xattn_init(cfg, k2, dtype),
        norm2=make_norm(cfg, dtype),
        ff=mlp_mod.mlp_init(cfg, k3, dtype),
    )


def init_params(cfg, key, dtype):
    k_emb, k_enc, k_dec = jax.random.split(key, 3)
    enc_keys = jax.random.split(k_enc, cfg.n_encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.n_layers)
    return pack(
        embed=embed_init(k_emb, cfg.padded_vocab, cfg.d_model, dtype),
        enc_blocks=_stack_pairs([_enc_layer_init(cfg, k, dtype)
                                 for k in enc_keys]),
        enc_norm=make_norm(cfg, dtype),
        dec_blocks=_stack_pairs([_dec_layer_init(cfg, k, dtype)
                                 for k in dec_keys]),
        final_norm=make_norm(cfg, dtype),
    )


# ===========================================================================
# Encoder
# ===========================================================================
def encode(cfg, params, frames):
    """frames: (B, enc_seq, d) stub embeddings -> encoder states."""
    b, t, d = frames.shape
    x = frames + sinusoidal_positions(t, d).astype(frames.dtype)[None]
    x = constrain(x, ("batch", "enc_seq", None))
    zero_pos = jnp.zeros((b, t), jnp.int32)    # RoPE at pos 0 == identity
    full_mask = jnp.ones((t, t), bool)

    def block(x, lp):
        h = apply_norm(cfg, x, lp["norm1"])
        x = x + attn.gqa_apply(cfg, lp["self_attn"], h, zero_pos, full_mask)
        h = apply_norm(cfg, x, lp["norm2"])
        x = x + mlp_mod.mlp_apply(cfg, lp["ff"], h)
        return x, None

    x, _ = jax.lax.scan(block, x, params["enc_blocks"])
    return apply_norm(cfg, x, params["enc_norm"])


# ===========================================================================
# Decoder (full sequence)
# ===========================================================================
def decode_full(cfg, params, tokens, enc_out, caches=None, write_cache=False):
    b, s = tokens.shape
    x = params["embed"][tokens]
    x = constrain(x, ("batch", "seq", None))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    mask = jnp.tril(jnp.ones((s, s), bool))

    def block(x, xs):
        lp, bc = xs
        h = apply_norm(cfg, x, lp["norm1"])
        if write_cache:
            out, nc_self = attn.gqa_prefill(cfg, lp["self_attn"], h, positions,
                                            mask, bc["self"])
        else:
            out = attn.gqa_apply(cfg, lp["self_attn"], h, positions, mask)
            nc_self = {}
        x = x + out
        h = apply_norm(cfg, x, lp["norm_x"])
        kv = attn.xattn_kv(lp["cross_attn"], enc_out)
        x = x + attn.xattn_apply(cfg, lp["cross_attn"], h, kv)
        h = apply_norm(cfg, x, lp["norm2"])
        x = x + mlp_mod.mlp_apply(cfg, lp["ff"], h)
        nc = {"self": nc_self,
              "cross_k": kv[0], "cross_v": kv[1]} if write_cache else {}
        return x, nc

    if caches is None:
        assert not write_cache
        def block_nc(x, lp):
            x, _ = block(x, (lp, {}))
            return x, None
        x, _ = jax.lax.scan(block_nc, x, params["dec_blocks"])
    else:
        x, caches = jax.lax.scan(block, x, (params["dec_blocks"], caches))
    x = apply_norm(cfg, x, params["final_norm"])
    return x, caches


def logits_from_hidden(cfg, params, hidden):
    from repro.models.transformer import mask_padded_vocab
    return mask_padded_vocab(
        cfg, jnp.einsum("bsd,vd->bsv", hidden, params["embed"]))


# ===========================================================================
# Caches + decode step
# ===========================================================================
def init_cache(cfg, batch, max_seq, dtype):
    hd = cfg.resolved_head_dim
    self_c = attn.gqa_init_cache(cfg, batch, max_seq, dtype)
    per = {"self": self_c,
           "cross_k": jnp.zeros((batch, cfg.encoder_seq_len, cfg.n_heads, hd), dtype),
           "cross_v": jnp.zeros((batch, cfg.encoder_seq_len, cfg.n_heads, hd), dtype)}
    axes = {"self": attn.gqa_cache_axes(),
            "cross_k": ("batch", "enc_seq", "heads", "head_dim"),
            "cross_v": ("batch", "enc_seq", "heads", "head_dim")}
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), per)
    axes = jax.tree.map(lambda ax: ("layers",) + tuple(ax), axes,
                        is_leaf=lambda x: isinstance(x, tuple))
    return stacked, axes


def decode_step(cfg, params, token, positions, caches):
    """token: (B,1); caches from init_cache/prefill."""
    x = params["embed"][token]

    def block(x, xs):
        lp, bc = xs
        h = apply_norm(cfg, x, lp["norm1"])
        out, nc_self = attn.gqa_decode(cfg, lp["self_attn"], h, positions,
                                       bc["self"])
        x = x + out
        h = apply_norm(cfg, x, lp["norm_x"])
        x = x + attn.xattn_apply(cfg, lp["cross_attn"], h,
                                 (bc["cross_k"], bc["cross_v"]))
        h = apply_norm(cfg, x, lp["norm2"])
        x = x + mlp_mod.mlp_apply(cfg, lp["ff"], h)
        return x, {"self": nc_self, "cross_k": bc["cross_k"],
                   "cross_v": bc["cross_v"]}

    x, new_caches = jax.lax.scan(block, x, (params["dec_blocks"], caches))
    x = apply_norm(cfg, x, params["final_norm"])
    return logits_from_hidden(cfg, params, x), new_caches
