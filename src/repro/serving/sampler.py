"""Token samplers — jit-composable objects usable both *inside* the fused
on-device decode loop (``models.transformer.decode_steps``) and standalone
from host code.

Design:
  * A ``Sampler`` is a frozen dataclass (hashable → safe to close over in a
    jitted function, or to pass as a static argument) mapping per-row logits
    to token ids.
  * Stochastic samplers consume one typed PRNG key **per batch row**
    (``keys: (B,)``). The engine derives row keys by folding a base key with
    the request id and the token index, so a request's token stream is a pure
    function of ``(seed, rid, token_index)`` — independent of how decode
    iterations are grouped into fused horizons, which slot the request lands
    in, or what else is in the batch. That is what makes fused-vs-unfused
    (and dense-vs-paged) streams exactly reproducible.
  * ``greedy`` stays importable as a module-level default (a callable
    ``GreedySampler`` instance), and ``sample_top_p`` keeps its original
    single-key functional form for existing callers.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


def fold_row_keys(base_key: jax.Array, rids: jax.Array, steps: jax.Array) -> jax.Array:
    """Per-row sampling keys: fold the engine's base key with each row's
    request id and token index. ``rids``/``steps`` are (B,) int32 (traced
    values are fine — this runs inside the fused decode loop)."""
    return jax.vmap(
        lambda r, s: jax.random.fold_in(jax.random.fold_in(base_key, r), s)
    )(rids, steps)


def _top_p_filter(logits: jax.Array, top_p: float, temperature: float) -> jax.Array:
    """(B, V) logits → (B, V) logits with the nucleus tail set to -inf."""
    logits = logits / jnp.maximum(temperature, 1e-6)
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
    return jnp.where(logits >= cutoff, logits, -jnp.inf)


@dataclasses.dataclass(frozen=True)
class Sampler:
    """Base sampler: (B, V) logits → (B,) int32 tokens."""

    #: whether ``keys`` must be provided (drives engine seed requirements)
    stochastic = False

    def __call__(
        self, logits: jax.Array, keys: Optional[jax.Array] = None
    ) -> jax.Array:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class GreedySampler(Sampler):
    """Argmax. A row whose logits are not all finite samples token id V,
    one past the vocabulary, so a numerical fault shows in the token stream
    (and poisons the slot's next embedding) instead of passing as a
    plausible token."""

    def __call__(
        self, logits: jax.Array, keys: Optional[jax.Array] = None
    ) -> jax.Array:
        tok = jnp.argmax(logits, axis=-1)
        finite = jnp.all(jnp.isfinite(logits), axis=-1)
        return jnp.where(finite, tok, logits.shape[-1]).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class TopPSampler(Sampler):
    """Nucleus sampling with per-row key threading."""

    top_p: float = 0.9
    temperature: float = 1.0
    stochastic = True

    def __call__(
        self, logits: jax.Array, keys: Optional[jax.Array] = None
    ) -> jax.Array:
        if keys is None:
            raise ValueError(
                "TopPSampler needs per-row PRNG keys; pass keys=(B,) "
                "(the engine threads them from its seed)"
            )
        filtered = _top_p_filter(logits, self.top_p, self.temperature)
        return jax.vmap(
            lambda k, row: jax.random.categorical(k, row, axis=-1)
        )(keys, filtered).astype(jnp.int32)


#: module-level default — callable exactly like the old ``greedy`` function
greedy = GreedySampler()


def sample_top_p(
    logits: jax.Array, key: jax.Array, top_p: float = 0.9, temperature: float = 1.0
) -> jax.Array:
    """Nucleus sampling with one key for the whole batch (legacy form).
    (B, V) → (B,)."""
    filtered = _top_p_filter(logits, top_p, temperature)
    return jax.random.categorical(key, filtered, axis=-1).astype(jnp.int32)
