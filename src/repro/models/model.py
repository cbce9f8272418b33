"""Unified model API used by train/serve/launch.

``Model(cfg)`` dispatches decoder-only vs encoder-decoder assemblies and
exposes:
  init(key) / abstract_init(key)      -> (params, axes) | (params_sds, axes)
  loss(params, batch, rng)            -> (loss, metrics)
  prefill(params, batch)              -> (last_logits, caches)
  decode(params, batch, caches)       -> (logits, caches)
  init_cache(batch, max_seq)

Cross-entropy is computed in token chunks under remat so the (tokens, vocab)
logits tensor is never materialized at full size — with 100k+ vocabularies
this is the difference between fitting HBM and not.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import encdec, transformer
from repro.runtime.sharding import constrain

CE_CHUNK = 1024     # tokens per cross-entropy chunk


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def chunked_softmax_xent(hidden, weight, targets, transpose_weight,
                         z_loss_coef=1e-4, vocab_size=None):
    """Mean CE over tokens, computed in chunks. hidden: (T,d) f-any,
    weight: (d,V) or (V,d) if transpose_weight; targets: (T,) int32.
    vocab_size: logical vocab — padded slots beyond it are masked out."""
    t, d = hidden.shape
    chunk = min(CE_CHUNK, t)
    pad = (-t) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad), constant_values=-1)
    n = hidden.shape[0] // chunk
    hidden = hidden.reshape(n, chunk, d)
    targets = targets.reshape(n, chunk)
    v_padded = weight.shape[0] if transpose_weight else weight.shape[-1]
    vocab_mask = None
    if vocab_size is not None and vocab_size < v_padded:
        vocab_mask = jnp.arange(v_padded) >= vocab_size

    @jax.checkpoint
    def chunk_fn(carry, xs):
        loss_sum, z_sum, count = carry
        h, tg = xs
        if transpose_weight:
            logits = jnp.einsum("cd,vd->cv", h, weight)
        else:
            logits = h @ weight
        logits = logits.astype(jnp.float32)
        if vocab_mask is not None:
            logits = jnp.where(vocab_mask, -1e30, logits)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.clip(tg, 0)[:, None], axis=-1)[:, 0]
        valid = (tg >= 0).astype(jnp.float32)
        loss_sum = loss_sum + ((lse - tgt) * valid).sum()
        z_sum = z_sum + (jnp.square(lse) * valid).sum()
        count = count + valid.sum()
        return (loss_sum, z_sum, count), None

    (loss_sum, z_sum, count), _ = jax.lax.scan(
        chunk_fn, (jnp.float32(0), jnp.float32(0), jnp.float32(0)),
        (hidden, targets))
    count = jnp.maximum(count, 1.0)
    return loss_sum / count + z_loss_coef * z_sum / count, count


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------ init
    def init(self, key):
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            return encdec.init_params(cfg, key, _dtype(cfg))
        return transformer.init_params(cfg, key, _dtype(cfg))

    def abstract_init(self, key=None):
        """(params ShapeDtypeStruct tree, axes tree) — no allocation."""
        captured = {}

        def only_params(k):
            p, ax = self.init(k)
            captured["axes"] = ax
            return p

        key = key if key is not None else jax.random.key(0)
        sds = jax.eval_shape(only_params, key)
        return sds, captured["axes"]

    # ------------------------------------------------------------------ loss
    def loss(self, params, batch, rng=None):
        cfg = self.cfg
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        if cfg.is_encoder_decoder:
            enc_out = encdec.encode(cfg, params, batch["frames"])
            hidden, _ = encdec.decode_full(cfg, params, inputs, enc_out)
            aux = {"load_balance_loss": jnp.float32(0.0),
                   "dropped_frac": jnp.float32(0.0)}
            weight, transpose = params["embed"], True
        else:
            hidden, aux, _ = transformer.forward(cfg, params, inputs, rng)
            if cfg.tie_embeddings:
                weight, transpose = params["embed"], True
            else:
                weight, transpose = params["unembed"], False
        b, s, d = hidden.shape
        ce, count = chunked_softmax_xent(
            hidden.reshape(b * s, d), weight, targets.reshape(b * s),
            transpose, vocab_size=cfg.vocab_size)
        loss = ce + aux["load_balance_loss"]
        metrics = {"ce": ce, "tokens": count,
                   "load_balance_loss": aux["load_balance_loss"],
                   "dropped_frac": aux["dropped_frac"]}
        return loss, metrics

    # --------------------------------------------------------------- serving
    def init_cache(self, batch, max_seq):
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            return encdec.init_cache(cfg, batch, max_seq, _dtype(cfg))
        return transformer.init_cache(cfg, batch, max_seq, _dtype(cfg))

    def prefill(self, params, batch, caches):
        """Full-sequence prefill; returns (last-position logits, caches)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        if cfg.is_encoder_decoder:
            enc_out = encdec.encode(cfg, params, batch["frames"])
            hidden, caches = encdec.decode_full(cfg, params, tokens, enc_out,
                                                caches, write_cache=True)
            last = hidden[:, -1:, :]
            logits = encdec.logits_from_hidden(cfg, params, last)
        else:
            hidden, _, caches = transformer.forward(
                cfg, params, tokens, caches=caches, write_cache=True)
            last = hidden[:, -1:, :]
            logits = transformer.logits_from_hidden(cfg, params, last)
        return logits.astype(jnp.float32), caches

    def decode(self, params, batch, caches):
        """batch: {token (B,1), positions (B,)}; one decode step."""
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            logits, caches = encdec.decode_step(
                cfg, params, batch["token"], batch["positions"], caches)
        else:
            logits, caches = transformer.decode_step(
                cfg, params, batch["token"], batch["positions"], caches)
        return logits.astype(jnp.float32), caches


def build(cfg: ModelConfig) -> Model:
    return Model(cfg)
