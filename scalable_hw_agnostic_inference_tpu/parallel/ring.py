"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has **no** sequence parallelism (explicitly disabled,
``sequence_parallel_enabled: False`` in reference
``cova/mllama-32-11b-vllm-trn1-config.yaml:17``) and reaches 128k context only
through static-shape bucketing. Long context is first-class here: sequences
shard over an ``sp`` mesh axis and attention runs either as

- :func:`ring_attention` — blockwise attention with online softmax; K/V blocks
  rotate around the ``sp`` ring via ``ppermute`` (ICI neighbor hops), so peak
  memory per chip is O(T/sp) and communication overlaps compute, or
- :func:`ulysses_attention` — two ``all_to_all`` reshards (seq<->heads) around
  a dense local attention, cheaper when heads >= sp.

Both are written for use inside ``shard_map`` over a named mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, mask, scale):
    """One (q-block x kv-block) attention contribution.

    Returns (scores_max, exp_scores @ v, exp_scores row-sums) for online
    softmax accumulation. Shapes: q [B,H,T,D], k/v [B,H,S,D], mask
    broadcastable to [B,H,T,S] (True = keep).
    """
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    # the max is a shift constant: stop_gradient it everywhere (including the
    # returned value) or the per-block correction factors pick up spurious
    # gradient terms that don't cancel across blocks
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m)
    o = jnp.einsum("bhts,bhsd->bhtd", p, v)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return m, o, l


def ring_attention_local(q, k, v, axis_name: str = "sp", causal: bool = False):
    """Ring attention body — call inside ``shard_map``.

    Args:
      q, k, v: local shards ``[B, H, T_local, D]`` (sequence sharded on
        ``axis_name``; same T_local on every device).
      causal: apply a causal mask over *global* positions.

    Returns the local output shard ``[B, H, T_local, D]``.
    """
    sp = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    B, H, T, D = q.shape
    S = k.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
    q32 = q.astype(jnp.float32)

    q_pos = my * T + jnp.arange(T)  # global positions of local queries

    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def step(carry, step_idx):
        k_blk, v_blk, o, m, l = carry
        # after `step_idx` rotations, the resident block originated on
        # device (my - step_idx) mod sp
        src = (my - step_idx) % sp
        if causal:
            k_pos = src * S + jnp.arange(S)
            mask = q_pos[:, None] >= k_pos[None, :]
            mask = mask[None, None, :, :]
        else:
            mask = None
        bm, bo, bl = _block_attn(
            q32, k_blk.astype(jnp.float32), v_blk.astype(jnp.float32), mask, scale
        )
        m_new = jnp.maximum(m, bm)
        corr = jnp.exp(m - m_new)
        bcorr = jnp.exp(bm - m_new)
        o = o * corr + bo * bcorr
        l = l * corr + bl * bcorr
        # rotate K/V to the next device; overlapped with the next block's
        # compute by XLA's async collective scheduling on ICI
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, o, m_new, l), None

    # initial accumulators are constants; mark them device-varying so the
    # scan carry type matches under shard_map's vma tracking
    def varying(x):
        return jax.lax.pcast(x, (axis_name,), to="varying")

    o0 = varying(jnp.zeros((B, H, T, D), jnp.float32))
    m0 = varying(jnp.full((B, H, T, 1), NEG_INF, jnp.float32))
    l0 = varying(jnp.zeros((B, H, T, 1), jnp.float32))
    (_, _, o, m, l), _ = jax.lax.scan(
        step, (k, v, o0, m0, l0), jnp.arange(sp)
    )
    out = o / jnp.maximum(l, 1e-20)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, axis_name: str = "sp", causal: bool = False):
    """Jit-friendly wrapper: shard_map ring attention over ``mesh``.

    Inputs/outputs are global arrays ``[B, H, T, D]`` sharded on dim 2.
    """
    fn = functools.partial(ring_attention_local, axis_name=axis_name, causal=causal)
    spec = P(None, None, axis_name, None)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def ulysses_attention_local(q, k, v, axis_name: str = "sp", causal: bool = False):
    """Ulysses-style SP body — call inside ``shard_map``.

    Reshards seq->heads with ``all_to_all``, runs dense local attention over
    the full sequence on H/sp heads, then reshards back. Requires
    ``H % sp == 0``.
    """
    sp = jax.lax.axis_size(axis_name)
    B, H, T, D = q.shape
    if H % sp:
        raise ValueError(f"heads {H} not divisible by sp={sp}")

    def seq_to_heads(x):
        # [B,H,T,D] seq-sharded -> [B,H/sp,T*sp,D] head-sharded
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    Tg = qh.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
    s = jnp.einsum("bhtd,bhsd->bhts", qh.astype(jnp.float32), kh.astype(jnp.float32))
    s = s * scale
    if causal:
        pos = jnp.arange(Tg)
        s = jnp.where(pos[:, None] >= pos[None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    oh = jnp.einsum("bhts,bhsd->bhtd", p, vh.astype(jnp.float32)).astype(q.dtype)
    return heads_to_seq(oh)


def ulysses_attention(q, k, v, mesh, axis_name: str = "sp", causal: bool = False):
    fn = functools.partial(ulysses_attention_local, axis_name=axis_name, causal=causal)
    spec = P(None, None, axis_name, None)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
