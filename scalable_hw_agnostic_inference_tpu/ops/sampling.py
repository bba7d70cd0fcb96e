"""On-device token sampling: temperature, top-k, top-p, greedy.

The reference's LLM path samples on-device inside the vLLM/NxD engine
(``global_topk: 64, "dynamic"``, reference
``cova/mllama-32-11b-vllm-trn1-config.yaml:18-22``). These are the jit-safe
equivalents the TPU engine composes into its decode step — no host round-trip
between logits and the sampled token. All knobs may be scalars or per-request
arrays (one entry per row of a continuous batch).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def greedy(logits: jax.Array) -> jax.Array:
    """Argmax over the vocab dim. logits ``[..., V]`` → tokens ``[...]``."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _mask_top_k(logits: jax.Array, k: jax.Array) -> jax.Array:
    """Keep the top ``k`` logits per row; ``k`` ``[...]`` (0 = off)."""
    V = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    k_eff = jnp.clip(k, 1, V)
    thresh = jnp.take_along_axis(sorted_desc, (k_eff - 1)[..., None], axis=-1)
    masked = jnp.where(logits >= thresh, logits, NEG_INF)
    return jnp.where((k > 0)[..., None], masked, logits)


def _mask_top_p(logits: jax.Array, p: jax.Array) -> jax.Array:
    """Nucleus sampling mask; ``p`` ``[...]`` in (0, 1] (1 = off)."""
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep tokens while cumulative prob (exclusive of self) < p; this always
    # keeps the top-1 token
    keep_sorted = (cum - probs) < p[..., None]
    kth = jnp.sum(keep_sorted, axis=-1, keepdims=True) - 1
    thresh = jnp.take_along_axis(sorted_desc, jnp.clip(kth, 0, None), axis=-1)
    masked = jnp.where(logits >= thresh, logits, NEG_INF)
    return jnp.where((p >= 1.0)[..., None], logits, masked)


def _broadcast_knobs(logits, temperature, top_k, top_p):
    batch_shape = logits.shape[:-1]
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), batch_shape)
    k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), batch_shape)
    p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), batch_shape)
    return t, k, p


def masked_scaled_logits(
    logits: jax.Array,
    temperature: float | jax.Array = 1.0,
    top_k: int | jax.Array = 0,
    top_p: float | jax.Array = 1.0,
) -> jax.Array:
    """The post-temperature/top-k/top-p logits ``sample_logits`` draws from
    (categorical over these == the actual sampling distribution)."""
    t, k, p = _broadcast_knobs(logits, temperature, top_k, top_p)
    scaled = logits.astype(jnp.float32) / jnp.maximum(t, 1e-6)[..., None]
    # each mask sorts the whole ``[rows, V]`` (at 32 x 200k, longer than
    # the rest of a decode step): skipped, on the device, in a step where
    # no row asks for that knob — a row with the knob off gets its logits
    # back unchanged from the mask too, so the outputs are the same bits
    scaled = jax.lax.cond(jnp.any(k > 0), _mask_top_k,
                          lambda lg, _: lg, scaled, k)
    return jax.lax.cond(jnp.any(p < 1.0), _mask_top_p,
                        lambda lg, _: lg, scaled, p)


def sample_excluding(
    logits: jax.Array,
    rng: jax.Array,
    exclude: jax.Array,
    temperature: float | jax.Array = 1.0,
    top_k: int | jax.Array = 0,
    top_p: float | jax.Array = 1.0,
) -> jax.Array:
    """Sample from the :func:`sample_logits` distribution with token
    ``exclude`` ``[...]`` removed — speculative decoding's rejection
    resample (the residual of a delta proposal is the target distribution
    with the rejected token zeroed, renormalized over the ORIGINAL
    support). The top-k/top-p masks are computed BEFORE the exclusion:
    recomputing them after would let a rank-(k+1) token into the support,
    emitting tokens vanilla sampling can never produce.
    """
    t, _, _ = _broadcast_knobs(logits, temperature, top_k, top_p)
    hole = exclude[..., None] == jnp.arange(logits.shape[-1])[None]
    masked = jnp.where(hole, NEG_INF,
                       masked_scaled_logits(logits, temperature, top_k, top_p))
    sampled = jax.random.categorical(rng, masked, axis=-1).astype(jnp.int32)
    # temperature 0: the argmax with the excluded token removed (raw logits
    # — greedy has full support minus the hole)
    return jnp.where(t <= 0.0, greedy(jnp.where(hole, NEG_INF, logits)),
                     sampled)


def sampling_probs(
    logits: jax.Array,
    temperature: float | jax.Array = 1.0,
    top_k: int | jax.Array = 0,
    top_p: float | jax.Array = 1.0,
) -> jax.Array:
    """The ACTUAL sampling distribution ``[..., V]`` — post temperature,
    top-k and top-p, the distribution :func:`sample_logits` draws from
    (a point mass on the argmax at ``temperature == 0``).

    Speculative decoding's rejection rule needs this exactly: a draft token
    is accepted with its probability under the real sampling distribution,
    not under the raw softmax — a draft outside the nucleus must always be
    rejected, or verification would commit tokens vanilla decode can never
    emit.
    """
    t, _, _ = _broadcast_knobs(logits, temperature, top_k, top_p)
    probs = jax.nn.softmax(
        masked_scaled_logits(logits, temperature, top_k, top_p), axis=-1)
    point = jax.nn.one_hot(greedy(logits), logits.shape[-1],
                           dtype=jnp.float32)
    return jnp.where((t <= 0.0)[..., None], point, probs)


def sample_logits(
    logits: jax.Array,
    rng: jax.Array,
    temperature: float | jax.Array = 1.0,
    top_k: int | jax.Array = 0,
    top_p: float | jax.Array = 1.0,
) -> jax.Array:
    """Sample tokens from ``[..., V]`` logits. Jit-safe; all knobs traceable.

    ``temperature == 0`` selects greedy decoding (per-row when the knob is a
    per-request array in a continuous batch).
    """
    t, _, _ = _broadcast_knobs(logits, temperature, top_k, top_p)

    def draw(_):
        masked = masked_scaled_logits(logits, temperature, top_k, top_p)
        sampled = jax.random.categorical(rng, masked, axis=-1)
        return jnp.where(t <= 0.0, greedy(logits), sampled.astype(jnp.int32))

    # an all-greedy step draws nothing (no noise over the vocabulary, no
    # sort): the argmax is what the other branch returns for such rows
    return jax.lax.cond(jnp.any(t > 0.0), draw,
                        lambda _: greedy(logits), None)
