"""Mistral's forward pass, plain: ``jax.numpy``, float32, no kernels, no
cache, no batching. Written from the published description (Mistral 7B,
arXiv:2310.06825; the ``config.json`` of Mistral-7B-Instruct-v0.3) and
importing nothing of the program.

One decoder layer, on a sequence ``x`` of ``[T, hidden]``:

    h = x + Wo . attention(rope(Wq . n1(x)), rope(Wk . n1(x)), Wv . n1(x))
    y = h + Wdown . (silu(Wgate . n2(h)) * (Wup . n2(h)))

with ``n(x) = x / sqrt(mean(x^2) + eps) * g`` (RMSNorm), rotary position
embedding in the half-rotation layout (lane i pairs with lane i + d/2, angle
``pos * theta^(-2i/d)``), causal attention scaled by ``1/sqrt(head_dim)`` in
which each group of ``heads / kv_heads`` query heads shares one key/value
head (GQA), and no biases. v0.3 has no sliding window. The model is the
embedding, the layers, a last RMSNorm and the output head.

Departures from the description: none in the mathematics. Weights are
whatever the caller hands in, as ``[in, out]`` matrices in the type they are
served in; ``matrix`` brings each to float32 (int8 weight-only leaves are
dequantised, exactly).

``variant`` exists for the tests only: it breaks the mathematics on purpose
so that a test can show the tolerance refuses it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, positions, theta):
    """``x`` ``[T, H, D]``, ``positions`` ``[T]``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def matrix(leaf):
    """A weight as float32 ``[in, out]``: a plain array, ``{"kernel": W}``,
    or weight-only int8 ``{"kernel_q": int8 [in, out], "scale": f32 [out]}``
    (one scale per output channel), which is ``kernel_q * scale``."""
    if isinstance(leaf, dict):
        if "kernel_q" in leaf:
            return (leaf["kernel_q"].astype(jnp.float32)
                    * leaf["scale"].astype(jnp.float32)[None, :])
        leaf = leaf["kernel"]
    return leaf.astype(jnp.float32)


def _round_to(x, bits: int):
    """Symmetric per-tensor rounding to ``bits`` — the wrong-on-purpose
    'keys and values of a lower precision' variant."""
    scale = jnp.max(jnp.abs(x)) / (2 ** (bits - 1) - 1)
    return jnp.round(x / scale) * scale


def _round_blocks(x, bits: int, block: int = 16):
    """``x`` ``[T, H, D]`` rounded with one scale per ``block`` positions
    and head, as a quantised paged pool keeps it (the program's int8 pool:
    ``ops/quant.py``, one scale per block and KV head)."""
    T, H, D = x.shape
    pad = -T % block
    xb = jnp.pad(x, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, H, D)
    scale = jnp.maximum(jnp.max(jnp.abs(xb), axis=(1, 3), keepdims=True),
                        1e-8) / (2 ** (bits - 1) - 1)
    return (jnp.round(xb / scale) * scale).reshape(-1, H, D)[:T]


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "eps", "theta", "variant"))
def layer(x, w: Dict[str, Any], *, n_heads: int, n_kv: int, eps: float,
          theta: float, variant: str = ""):
    """One decoder layer over ``x`` ``[T, hidden]`` at positions 0..T-1."""
    with jax.default_matmul_precision("highest"):
        w = {k: matrix(v) for k, v in w.items()}
        T = x.shape[0]
        pos = jnp.arange(T)
        a = rms_norm(x, w["attn_norm"], eps)
        q = (a @ w["q"]).reshape(T, n_heads, -1)
        k = (a @ w["k"]).reshape(T, n_kv, -1)
        v = (a @ w["v"]).reshape(T, n_kv, -1)
        if variant != "no_rope":
            q, k = rope(q, pos, theta), rope(k, pos, theta)
        if variant == "kv_4bit":
            k, v = _round_to(k, 4), _round_to(v, 4)
        if variant == "kv_int8":
            k, v = _round_blocks(k, 8), _round_blocks(v, 8)
        group = n_heads // n_kv
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
        h = x + o.reshape(T, -1) @ w["o"]
        m = rms_norm(h, w["mlp_norm"], eps)
        return h + (jax.nn.silu(m @ w["gate"]) * (m @ w["up"])) @ w["down"]


@functools.partial(jax.jit, static_argnames=("eps",))
def log_probs(x, final_norm, head, *, eps: float):
    """Log-softmax over the vocabulary at every row of ``x``."""
    with jax.default_matmul_precision("highest"):
        logits = rms_norm(x, matrix(final_norm), eps) @ matrix(head)
        return jax.nn.log_softmax(logits, axis=-1)
