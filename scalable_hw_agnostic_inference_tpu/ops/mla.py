"""Multi-head latent attention (DeepSeek-V2/V3's MLA): one function computed
two ways, and the plain oracle of the paged kernel.

A token's cache entry is ONE row of ``cfg.latent_width`` lanes shared by all
heads: the normed latent ``c`` (``kv_lora_rank``), the rotary key ``k^r``
(``qk_rope_head_dim``) behind it, zeros to a lane multiple. The up
projection ``kv_b`` ``[rank, H * (nope + v)]`` holds per head ``W^K``
(keys without position) and ``W^V`` (values).

- **expanded** (prefill, and a continuation chunk over its gathered prefix):
  ``k_h = [W^K_h c ; k^r]``, ``v_h = W^V_h c``, then plain causal attention
  with keys of ``head_dim`` beside values of ``v_head_dim``.
- **absorbed** (decode, through the paged pool): ``q~_h = W^K_h^T q^n_h``;
  scores are ``[q~_h ; q^r_h] . [c ; k^r]``, ONE key row for all heads and
  read once; ``u_h = sum_s p c(s)``; ``o_h = W^V_h u_h``. The same
  function: ``q^n . (W^K c) = (W^K^T q^n) . c``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .quant import quant_matmul

NEG_INF = -1e30

#: names in a device trace (``jax.named_scope``); the kernel's own is
#: ``ops.pallas.mla_paged_attention.KERNEL_NAME``
ABSORB_NAME = "mla_absorb_q"
EXPAND_NAME = "mla_expand_prefill"


def softmax_scale(cfg) -> float:
    return float(cfg.head_dim) ** -0.5


def latent_rows(c: jax.Array, k_rope: jax.Array, width: int) -> jax.Array:
    """``c`` ``[..., rank]`` and ``k_rope`` ``[..., rope]`` as cache rows
    ``[..., width]``: side by side, zeros behind."""
    pad = width - c.shape[-1] - k_rope.shape[-1]
    row = jnp.concatenate([c, k_rope.astype(c.dtype)], axis=-1)
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)])


def _kvb(kv_b, cfg) -> Tuple[jax.Array, jax.Array]:
    """``(W^K [rank, H, nope], W^V [rank, H, v])`` of the ``kv_b`` leaf."""
    w = kv_b["kernel"].reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def expand(rows: jax.Array, kv_b, cfg) -> Tuple[jax.Array, jax.Array]:
    """Cache rows ``[B, S, width]`` -> per-head keys ``[B, S, H, head_dim]``
    (without position from the latent, then the shared rotary key) and
    values ``[B, S, H, v_head_dim]``."""
    B, S, _ = rows.shape
    R, H = cfg.kv_lora_rank, cfg.n_heads
    with jax.named_scope(EXPAND_NAME):
        kv = quant_matmul(rows[..., :R], kv_b).reshape(
            B, S, H, cfg.qk_nope_head_dim + cfg.v_head_dim)
        k_rope = jnp.broadcast_to(
            rows[:, :, None, R:R + cfg.qk_rope_head_dim],
            (B, S, H, cfg.qk_rope_head_dim))
        k = jnp.concatenate([kv[..., :cfg.qk_nope_head_dim], k_rope], -1)
        return k, kv[..., cfg.qk_nope_head_dim:]


def absorb_q(q: jax.Array, kv_b, cfg) -> jax.Array:
    """Queries ``[B, T, H, head_dim]`` (rotary part already turned) in the
    cache row's own coordinates ``[B, T, H, width]``: ``W^K^T q^n``, the
    rotary part behind it, zeros where the row holds zeros."""
    wk, _ = _kvb(kv_b, cfg)
    N = cfg.qk_nope_head_dim
    with jax.named_scope(ABSORB_NAME):
        qa = jnp.einsum("bthn,rhn->bthr", q[..., :N], wk.astype(q.dtype),
                        preferred_element_type=jnp.float32).astype(q.dtype)
        return latent_rows(qa, q[..., N:], cfg.latent_width)


def unabsorb(u: jax.Array, kv_b, cfg) -> jax.Array:
    """``u`` ``[B, T, H, rank]`` (the probabilities' sum of latents) ->
    ``o`` ``[B, T, H, v_head_dim]``."""
    _, wv = _kvb(kv_b, cfg)
    with jax.named_scope(ABSORB_NAME):
        return jnp.einsum("bthr,rhv->bthv", u, wv.astype(u.dtype),
                          preferred_element_type=jnp.float32).astype(u.dtype)


def latent_gather_attention(
    q_abs: jax.Array,       # [B, T, H, width] absorbed queries
    pool: jax.Array,        # [N, block_size, width] the paged latent pool
    tables: jax.Array,      # [B, M] physical block ids (0-padded)
    positions: jax.Array,   # [B, T] each query's own cache position
    *,
    rank: int,
    scale: float,
) -> jax.Array:
    """XLA gather reference of absorbed attention over the paged pool:
    query ``(b, t)`` sees the rows of ``tables[b]`` at positions
    ``<= positions[b, t]``. Returns ``u`` ``[B, T, H, rank]``. THE
    deviceless oracle of the kernel and the engine's decode path off the
    TPU."""
    B, T, H, W = q_abs.shape
    _N, block_size, _ = pool.shape
    L = tables.shape[1] * block_size
    goff = (tables[:, :, None] * block_size
            + jnp.arange(block_size)[None, None, :]).reshape(B, L)
    # float32 operands: this path runs where there is no MXU to feed, and
    # the CPU backend has no bf16 x bf16 -> f32 batched product
    ctx = pool.reshape(-1, W)[goff].astype(jnp.float32)   # [B, L, W]
    s = jnp.einsum("bthw,bsw->bhts", q_abs.astype(jnp.float32), ctx) * scale
    see = (jnp.arange(L)[None, None, :] <= positions[:, :, None])[:, None]
    p = jax.nn.softmax(jnp.where(see, s, NEG_INF), axis=-1)
    u = jnp.einsum("bhts,bsr->bthr", p, ctx[..., :rank])
    return u.astype(q_abs.dtype)


def paged_latent_attention(q_abs: jax.Array, pool: jax.Array,
                           tables: jax.Array, positions: jax.Array, *,
                           rank: int, scale: float,
                           paged: bool) -> jax.Array:
    """Absorbed attention of ``[B, T, H, width]`` queries over the pool,
    with implementation dispatch: the Pallas kernel (``T`` queries
    flattened into its row axis, a length a row) where ``paged``, the
    gather reference elsewhere. Returns ``u`` ``[B, T, H, rank]``."""
    if not paged:
        return latent_gather_attention(q_abs, pool, tables, positions,
                                       rank=rank, scale=scale)
    from .pallas.mla_paged_attention import mla_paged_decode

    B, T, H, W = q_abs.shape
    L = tables.shape[1] * pool.shape[1]
    u = mla_paged_decode(
        q_abs.reshape(B * T, H, W), pool,
        jnp.repeat(tables, T, axis=0) if T > 1 else tables,
        jnp.clip(positions + 1, 1, L).reshape(B * T), rank=rank, scale=scale)
    return u.reshape(B, T, H, rank)
