"""Batched serving engine: prefill a batch of prompts, then decode steps with
greedy or temperature sampling. Designed so both phases are single jit-able
functions.

Continuous-batching-lite: finished sequences (EOS) are masked and their slots
keep decoding pad tokens without affecting others; a host-side loop can swap
new requests into free slots between jit steps (slot admission is host logic,
the device step is shape-stable).

The first post-prefill token goes through the SAME sampling path as every
decode step (``sample_token``): it is drawn with the configured temperature
from a split of the request rng, and it is EOS-masked — a prefill that emits
``eos_id`` finishes the sequence immediately instead of seeding a decode loop
that keeps generating real tokens after EOS. Both were historically broken
(argmax-always and done-starts-all-False); tests/test_data_serve.py pins the
fixed behaviour with seeded stub-model regressions.

Compiled programs are cached per (model, ServeConfig, length) in
``_compiled`` so repeated ``generate`` calls — a serving loop routing many
requests — pay tracing/compilation once. ``ServeConfig`` is frozen (hashable)
for exactly this reason.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0      # 0 => greedy
    eos_id: int = -1              # -1 => never stop early
    pad_id: int = 0


def sample_token(logits, sc: ServeConfig, key):
    """Draw one token per row from (B, V) logits — THE sampling decision,
    shared by the post-prefill first token and every decode step so the
    two can never disagree on temperature handling again."""
    if sc.temperature > 0:
        nxt = jax.random.categorical(key, logits / sc.temperature)
    else:
        nxt = jnp.argmax(logits, axis=-1)
    return nxt.astype(jnp.int32)


def make_prefill_step(model):
    def prefill_step(params, batch, cache):
        logits, cache = model.prefill(params, batch, cache)
        return logits, cache
    return prefill_step


def make_decode_step(model, sc: ServeConfig):
    def decode_step(params, carry):
        cache, token, positions, rng, done = carry
        logits, cache = model.decode(
            params, {"token": token, "positions": positions}, cache)
        rng, sub = jax.random.split(rng)
        nxt = sample_token(logits[:, -1], sc, sub)
        done = jnp.logical_or(done, nxt == sc.eos_id)
        nxt = jnp.where(done, sc.pad_id, nxt)
        return (cache, nxt[:, None], positions + 1, rng, done), nxt
    return decode_step


@functools.lru_cache(maxsize=64)
def _compiled(model, sc: ServeConfig):
    """Jitted prefill + decode-scan for one (model, ServeConfig) pair.

    jax.jit caches on function identity, and ``make_prefill_step(model)``
    used to mint a fresh closure per ``generate`` call — every request
    retraced and recompiled both phases, which is why the old serve driver
    could only report a tok/s "incl. compile". One cache entry per
    configuration makes the steady-state path actually steady."""
    prefill = jax.jit(make_prefill_step(model))
    decode = make_decode_step(model, sc)

    @jax.jit
    def decode_scan(params, carry):
        return jax.lax.scan(lambda c, _: decode(params, c), carry, None,
                            length=sc.max_new_tokens - 1)

    return prefill, decode_scan


def generate(model, params, prompts, sc: ServeConfig, *, max_seq=None,
             frames=None, rng=None):
    """prompts: (B, S) int32. Returns (B, max_new_tokens) int32."""
    b, s = prompts.shape
    max_seq = max_seq or (s + sc.max_new_tokens)
    cache, _ = model.init_cache(b, max_seq)
    batch = {"tokens": prompts}
    if frames is not None:
        batch["frames"] = frames
    prefill, decode_scan = _compiled(model, sc)
    logits, cache = prefill(params, batch, cache)
    rng = rng if rng is not None else jax.random.key(0)
    rng, sub = jax.random.split(rng)
    # the first token is a sampling step like any other: same temperature
    # path as decode, and EOS-masked — a prefill emitting eos_id finishes
    # the sequence at once (done seeds from it, the token pads out).
    first = sample_token(logits[:, -1], sc, sub)
    done = first == sc.eos_id
    first = jnp.where(done, sc.pad_id, first)

    carry = (cache, first[:, None], jnp.full((b,), s, jnp.int32), rng, done)
    carry, tokens = decode_scan(params, carry)
    return jnp.concatenate([first[:, None], tokens.T], axis=1)
