"""LFM2-MoE's forward pass as LFM2-24B-A2B configures it, plain:
``jax.numpy``, float32, no kernels, no cache, no batching, no slot: ONE
full causal pass in which the short convolution is ``K`` shifted copies of
the whole sequence (the program prefills in programs of 256 or 512 tokens,
carries the convolution's tail in a slot from program to program and
decodes one token a row on that tail, so the check is of those forms
against this one). Written from the ``config.json`` of
``LiquidAI/LFM2-24B-A2B`` (``model_type: lfm2_moe``) and the public
``modeling_lfm2.py`` / ``modeling_lfm2_moe.py``; what those leave open is
listed under ``assumed`` in the configuration's file. It imports nothing of
the program.

On a sequence ``x`` of ``[T, hidden]`` (``x0 = Embed[ids]``, no scale), with
``rms(x; w) = x * rsqrt(mean(x^2) + norm_eps) * w``, every layer is

    h  = x + Mixer(rms(x; operator_norm))
    x' = h + FFN(rms(h; ffn_norm))

``layer_types[l] == "conv"`` (no bias, no activation, ``K = conv_L_cache``):

    [B | C | u] = W_in a                         hidden -> 3 hidden, in that order
    v_t = B_t * u_t
    c_t = k_0 v_{t-K+1} + ... + k_{K-1} v_t      per channel; v before 0 is 0
    Mixer = W_out (C_t * c_t)

``"full_attention"``: ``q, k, v`` without bias, an RMSNorm over each q and k
head, THEN rotary (half rotation, ``rope_theta``) on every lane, causal
softmax at ``head_dim ** -0.5``, grouped query heads, ``W_out``.

FFN, layer ``l < num_dense_layers``: ``W2(silu(W1 y) * W3 y)``. Otherwise
``s = sigmoid(W_r y)`` in float32 over all ``num_experts``; ``sel =
top_k(s + expert_bias)``; ``w = s[sel] / (sum + 1e-6)`` (``norm_topk_prob``)
times ``routed_scaling_factor``; the sum over the chosen experts of ``w_e
W2_e(silu(W1_e y) * W3_e y)``; no shared expert.

After the last layer ``rms(x; embedding_norm)``, then the head TIED to the
embedding.

``variant`` exists for the tests and the chip check only: it breaks the
mathematics on purpose so that the tolerance can be shown to refuse it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# What ``tolerance.lfm2_moe.json`` refuses by EVERY bound, at the tiny size
# (``tests/benchmark`` holds every name here to the largest-difference bound
# on the dry run's two short prompts) as at the published one: the
# convolution's taps reversed, the first gate dropped (``v = u``).
REFUSED_VARIANTS = ("taps_reversed", "no_gate_b")
ACCEPTED_VARIANTS = ()
# What it refuses by its MEAN bound (the tolerance file has the readings of
# both sizes): a continuation chunk that starts from a zero tail
# (``no_conv_tail``: THE variant that ties the check to the engine's slot;
# at the published widths every bound refuses it, at the tiny size's short
# prompts it moves K - 1 tokens of a chunk and little else), the selection
# bias dropped (``no_expert_bias``); and THE PRECISION CONTROL,
# ``weights_fp8``: every matrix a product reads, and the head, in float8
# e4m3 under one scale a matrix.
REFUSED_BY_MEAN = ("no_conv_tail", "no_expert_bias", "weights_fp8")
# What NO bound on the logits refuses reliably at the published widths, said
# so in the tolerance file with its readings and why: the head norms dropped
# (under seeded weights at N(0, 0.02) a head's q and k come out of their
# projections with an RMS of 0.9, so a norm to 1 changes little;
# ``tests/test_shortconv.py`` holds that line against ``transformers``).
NOT_REFUSED_RELIABLY = ("no_qk_norm",)

#: vocabulary rows a block of the tied head holds
VOCAB_BLOCK = 16384
#: a sequence is padded to a multiple of this many positions
PAD_STEP = 1024
#: the model's own epsilon under the chosen scores' sum (``ops/moe.py`` adds
#: 1e-20: a relative 1e-6 on sums of order 1)
ROUTE_EPS = 1e-6


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def fp8(w):
    """``w`` rounded to float8 e4m3 under one scale: its largest entry lands
    on 240. ``reduce_precision`` and not a pair of casts, which XLA's
    excess-precision rule may drop."""
    scale = jnp.max(jnp.abs(w)) / 240.0
    return jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def matrix(leaf, variant: str = ""):
    """A weight as float32: a plain array or ``{"kernel": W}``
    (``weights_fp8``: every matrix a product reads, through ``fp8``; the
    norms' scales, the router, its bias and the convolution's taps are read
    without a variant and stay as served)."""
    if isinstance(leaf, dict):
        leaf = leaf["kernel"]
    w = leaf.astype(jnp.float32)
    return fp8(w) if variant == "weights_fp8" else w


def rope_halves(x, positions, theta):
    """``x`` ``[T, H, D]`` turned on lanes ``(i, i + D/2)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gated(y, gate, up, down):
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def routed(y, moe: Dict[str, Any], *, top_k: int, renorm: bool,
           route_scale: float, variant: str = ""):
    """The routed part on ``y`` ``[T, hidden]``, one expert at a time."""
    s = jax.nn.sigmoid(y @ moe["router"]["kernel"].astype(jnp.float32))
    bias = 0.0 if variant == "no_expert_bias" \
        else moe["bias"].astype(jnp.float32)
    _, sel = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, sel, axis=1)
    if renorm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + ROUTE_EPS)
    w = route_scale * w
    dense_w = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], sel].set(w)
    ex = moe["experts"]

    def one(e, acc):
        gate, up, down = (matrix(jax.lax.dynamic_index_in_dim(
            ex[n], e, keepdims=False), variant)
            for n in ("gate", "up", "down"))
        return acc + gated(y, gate, up, down) \
            * jax.lax.dynamic_index_in_dim(dense_w, e, axis=1)

    return jax.lax.fori_loop(0, ex["down"].shape[0], one, jnp.zeros_like(y))


def short_conv(a, at: Dict[str, Any], *, carry_every: int, variant: str):
    """The gated short convolution on the normed stream ``a`` ``[T,
    hidden]``. ``carry_every``: the engine's chunk (``no_conv_tail`` alone
    reads it: the convolution sees nothing before each multiple of it)."""
    T = a.shape[0]
    pos = jnp.arange(T)
    start = (pos // carry_every) * carry_every \
        if variant == "no_conv_tail" else jnp.zeros_like(pos)
    b, c, u = jnp.split(a @ matrix(at["in"], variant), 3, axis=-1)
    v = u if variant == "no_gate_b" else b * u
    w = at["conv"].astype(jnp.float32)                        # [taps, chan]
    if variant == "taps_reversed":
        w = w[::-1]
    taps = w.shape[0]
    y = v * w[taps - 1]
    for back in range(1, taps):
        seen = (pos - back >= start)[:, None]
        y = y + jnp.where(seen, jnp.roll(v, back, axis=0), 0.0) \
            * w[taps - 1 - back]
    return (c * y) @ matrix(at["o"], variant)


def attention(a, at: Dict[str, Any], *, heads: int, kv_heads: int, d: int,
              theta: float, eps: float, variant: str):
    """Grouped causal attention over QK-normed, rotated heads."""
    T = a.shape[0]
    pos = jnp.arange(T)
    q = (a @ matrix(at["q"], variant)).reshape(T, heads, d)
    k = (a @ matrix(at["k"], variant)).reshape(T, kv_heads, d)
    v = (a @ matrix(at["v"], variant)).reshape(T, kv_heads, d)
    if variant != "no_qk_norm":
        q = rms_norm(q, at["q_norm"]["scale"].astype(jnp.float32), eps)
        k = rms_norm(k, at["k_norm"]["scale"].astype(jnp.float32), eps)
    q, k = rope_halves(q, pos, theta), rope_halves(k, pos, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1).transpose(1, 0, 2)
    v = jnp.repeat(v, heads // kv_heads, axis=1).transpose(1, 0, 2)
    see = pos[:, None] >= pos[None, :]

    def one_head(args):
        q_h, k_h, v_h = args
        s = (q_h @ k_h.T) * d ** -0.5
        return jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1) @ v_h

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2), k, v))
    return o.transpose(1, 0, 2).reshape(T, heads * d) @ matrix(at["o"],
                                                               variant)


@functools.partial(jax.jit, static_argnames=(
    "kind", "dense", "n_heads", "kv_heads", "head_dim", "eps", "theta",
    "top_k", "renorm", "route_scale", "carry_every", "variant"))
def layer(x, lp: Dict[str, Any], *, kind: str, dense: bool, n_heads: int,
          kv_heads: int, head_dim: int, eps: float, theta: float, top_k: int,
          renorm: bool, route_scale: float, carry_every: int,
          variant: str = ""):
    """One layer over ``x`` ``[T, hidden]`` at positions 0..T-1. ``lp`` is
    the engine's layer tree."""
    with jax.default_matmul_precision("highest"):
        a = rms_norm(x, matrix(lp["attn_norm"]["scale"]), eps)
        if kind == "conv":
            h = x + short_conv(a, lp["attn"], carry_every=carry_every,
                               variant=variant)
        else:
            assert kind == "full_attention", kind
            h = x + attention(a, lp["attn"], heads=n_heads,
                              kv_heads=kv_heads, d=head_dim, theta=theta,
                              eps=eps, variant=variant)
        y = rms_norm(h, matrix(lp["mlp_norm"]["scale"]), eps)
        if dense:
            m = lp["mlp"]
            return h + gated(y, *(matrix(m[n], variant)
                                  for n in ("gate", "up", "down")))
        return h + routed(y, lp["moe"], top_k=top_k, renorm=renorm,
                          route_scale=route_scale, variant=variant)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, final_norm, *, eps: float):
    return rms_norm(x, matrix(final_norm), eps)


@functools.partial(jax.jit, static_argnames=("variant",))
def _head_block(xn, rows, *, variant: str = ""):
    with jax.default_matmul_precision("highest"):
        return xn @ matrix(rows, variant).T


def log_probs(x, final_norm, embedding, *, eps: float, variant: str = ""):
    """Log-softmax over the vocabulary at every row of ``x``, the TIED head
    ``VOCAB_BLOCK`` rows of the embedding at a time (``weights_fp8``: one
    scale a block; the embedding as a LOOKUP stays as it is served)."""
    xn = _normed(x, final_norm, eps=eps)
    logits = jnp.concatenate(
        [_head_block(xn, embedding[a:a + VOCAB_BLOCK], variant=variant)
         for a in range(0, embedding.shape[0], VOCAB_BLOCK)], axis=1)
    return jax.nn.log_softmax(logits, axis=-1)


def logprobs(params: Dict[str, Any], model: Dict[str, Any],
             ids: List[int], rows: List[int], pad_to: int,
             variant: str = "") -> np.ndarray:
    """Log-probabilities ``[len(rows), vocab]`` after each of the positions
    ``rows`` of the sequence ``ids``. ``params`` is the engine's tree;
    ``model`` the published config's keys (``layer_types`` names each
    layer's mixer; ``num_dense_layers`` the layers with a dense MLP;
    ``engine.context_encoding_buckets``, or ``no_carry_every`` in a
    stand-in's file, the chunk ``no_conv_tail`` resets at). The sequence is
    padded at its END to the next multiple of ``PAD_STEP`` and never past
    ``pad_to``: causality keeps the padding out of every real position."""
    seq = np.zeros((min(pad_to, -(-len(ids) // PAD_STEP) * PAD_STEP),),
                   np.int32)
    seq[:len(ids)] = ids
    embedding = params["embed"]["embedding"]
    x = jnp.take(embedding, jnp.asarray(seq), axis=0).astype(jnp.float32)
    carry_every = (max(model["engine"]["context_encoding_buckets"])
                   if "engine" in model else int(model["no_carry_every"]))
    kinds = model["layer_types"]
    assert len(kinds) == model["num_hidden_layers"]
    heads = model["num_attention_heads"]
    for i, kind in enumerate(kinds):
        x = layer(
            x, params[f"layer_{i}"], kind=kind,
            dense=i < model["num_dense_layers"], n_heads=heads,
            kv_heads=model["num_key_value_heads"],
            head_dim=model.get("head_dim") or model["hidden_size"] // heads,
            eps=float(model["norm_eps"]),
            theta=float(model["rope_parameters"]["rope_theta"]),
            top_k=model["num_experts_per_tok"],
            renorm=bool(model["norm_topk_prob"]),
            route_scale=float(model["routed_scaling_factor"]),
            carry_every=int(carry_every), variant=variant)
    out = log_probs(x[jnp.asarray(rows)], params["final_norm"]["scale"],
                    embedding, eps=float(model["norm_eps"]),
                    variant=variant if variant == "weights_fp8" else "")
    return np.asarray(out)
