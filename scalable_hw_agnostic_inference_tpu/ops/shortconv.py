"""A double-gated short convolution (LFM2's ``conv`` layers): a causal
depthwise convolution of a few taps between two data-dependent gates, with
no attention and no scan.

On the normed stream ``a`` of a token, over ``D`` channels and ``K`` taps
(no bias anywhere, no activation):

    [B | C | u] = W_in a                (D -> 3 D, split in that order)
    v_t = B_t * u_t
    c_t = k_0 v_{t-K+1} + ... + k_{K-1} v_t        per channel; v before 0 is 0
    out_t = C_t * c_t                   (the caller applies ``W_out``)

The per-sequence state is the convolution's TAIL alone: the last ``K - 1``
values of ``v``, ``[K - 1, D]`` a slot in the activations' type (8 KiB at 3
taps of 2048 in bfloat16) and NO float32 leaf. This module holds the
function two ways, and the mixer's two phases as the engine calls them:

- :func:`recurrence`: one ``lax.scan`` step a token that carries the tail.
  THE oracle: the convolved form and the served path's tests are held to it.
- :func:`mix`: all ``T`` tokens at once behind a tail, ``K`` shifted
  products summed (what prefill, continuation and decode run: plain XLA, a
  product of a few taps between two matmuls fuses into one pass).

A PAD token does not enter the tail: each row's new tail is read at its own
length (as ``ops.ssm.inputs`` does), and a pad's output is never read.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .quant import quant_matmul


def state_shapes(cfg) -> Dict[str, Tuple[Tuple[int, ...], Optional[str]]]:
    """What ONE slot costs in ONE conv layer, by leaf: ``(shape, dtype)``.
    ``t``: the last ``conv_taps - 1`` inputs of the convolution, in the
    activations' type (``None``: the holder's own). There is no other."""
    return {"t": ((cfg.conv_taps - 1, cfg.dim), None)}


def gates(at: Dict, h: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``(v, C)`` from the normed stream ``h`` ``[B, T, D]``: the
    convolution's input ``v = B * u`` and the second gate, both in the
    stream's type."""
    b, c, u = jnp.split(quant_matmul(h, at["in"]), 3, axis=-1)
    return b * u, c


def mix(at: Dict, h: jax.Array, tail: Optional[jax.Array]):
    """The mixer on ``h`` ``[B, T, D]`` behind ``tail`` ``[B, K - 1, D]``
    (the ``v`` of the tokens before ``h``; ``None``: position 0, zeros).
    Returns ``(out [B, T, D]`` in the stream's type``, ext [B, K - 1 + T,
    D])``: the tail and then every token's ``v``, which the next tail is
    read from. Tap ``K - 1`` meets the current token."""
    B, T, D = h.shape
    w = at["conv"].astype(jnp.float32)                        # [K, D]
    K = w.shape[0]
    v, c = gates(at, h)
    if tail is None:
        tail = jnp.zeros((B, K - 1, D), v.dtype)
    ext = jnp.concatenate([tail.astype(v.dtype), v], axis=1)
    y = sum(ext[:, i:i + T].astype(jnp.float32) * w[i] for i in range(K))
    return (c.astype(jnp.float32) * y).astype(h.dtype), ext


def recurrence(at: Dict, h: jax.Array, tail: Optional[jax.Array] = None):
    """:func:`mix`'s function one token at a time, float32: a ``lax.scan``
    that carries the tail. Returns ``(out [B, T, D], tail [B, K - 1, D])``."""
    B, T, D = h.shape
    w = at["conv"].astype(jnp.float32)
    K = w.shape[0]
    v, c = gates(at, h.astype(jnp.float32))
    if tail is None:
        tail = jnp.zeros((B, K - 1, D), jnp.float32)

    def one(t, vc):
        v_t, c_t = vc
        seen = jnp.concatenate([t, v_t[:, None]], axis=1)     # [B, K, D]
        return seen[:, 1:], c_t * jnp.sum(seen * w, axis=1)

    tail, out = jax.lax.scan(
        one, tail.astype(jnp.float32),
        (jnp.moveaxis(v, 1, 0), jnp.moveaxis(c, 1, 0)))
    return jnp.moveaxis(out, 0, 1), tail


# -- the mixer's phases, as the engine's programs call them (the recurrent
# KINDS share this interface: ``ops.kda`` and ``ops.ssm`` have the same) ----

def prefill(at: Dict, h: jax.Array, state: Dict, slots: jax.Array,
            n_valid: jax.Array, cfg, *, carry: bool, kernel: bool):
    """A prefill (``carry`` False: from a ZERO tail, whatever the slot
    held) or continuation (``carry``: from the rows' ``slots`` of the arena
    ``state``) program's pass over ``h`` ``[B, T, D]``, the tail the last
    REAL token left written to the slots. ``kernel`` is the interface's:
    this kind has none. Returns ``(out [B, T, D], state)``."""
    tails = state["t"]
    out, ext = mix(at, h, tails[slots] if carry else None)
    # the last K - 1 REAL inputs: rows n .. n + K - 2 of the extension
    rows = n_valid[:, None] + jnp.arange(cfg.conv_taps - 1)[None, :]
    tail = jnp.take_along_axis(ext, rows[..., None], axis=1)
    return out, {"t": tails.at[slots].set(tail.astype(tails.dtype))}


def decode(at: Dict, h: jax.Array, state: Dict, slots: jax.Array, cfg, *,
           kernel: bool):
    """One decode step of ``h`` ``[B, 1, D]`` in place on the rows' slots
    (a padded row's is the null slot). Returns ``(out [B, 1, D], state)``."""
    tails = state["t"]
    out, ext = mix(at, h, tails[slots])
    return out, {"t": tails.at[slots].set(ext[:, 1:].astype(tails.dtype))}
