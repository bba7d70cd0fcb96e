"""Ragged paged attention: the second entry point of the one pool kernel.

ONE dispatch for heterogeneous context lengths (PAPERS.md 2604.15464,
"Ragged Paged Attention"): every row attends exactly its own live tiles
through its block table. The kernel body is ``paged_attention.py``'s —
since that body walks ``cdiv(lengths[b], tile)`` tiles a row, the
"bucketed" call and this one cost the same for the same rows, and what is
left of the difference is the caller's: this entry point is handed the
FULL table (``M = blocks_per_seq``), compiled once, where the bucketed
caller may pre-truncate it to a context bucket. int8 KV pools
(``SHAI_KV_QUANT=int8``) dequantize in-kernel on both.

The XLA gather-based reference for CPU/tier-1 lives in
``ops.attention.ragged_gather_attention``; ``ops.attention.
ragged_paged_attention`` dispatches between the two so every test runs
deviceless.

Mixed-phase fused rows (``SHAI_FUSED_STEP``): because the kernel is
row-oriented — each grid row carries its own ``(table, length)`` and pays
only its own live tiles — an engine step can fuse decode and chunked
prefill into ONE dispatch by pure layout, no kernel change: the ``B``
decode rows come first (length ``pos + 1`` each), then the continuation
chunk's ``C`` queries flattened one-per-row (all sharing the chunking
sequence's table, lengths ``start + t + 1``). The kernel never learns
which phase a row belongs to; ``ops.attention.
mixed_phase_ragged_attention`` builds this layout and splits the outputs
back at row ``B``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from .paged_attention import pool_attention


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "window"))
def ragged_paged_attention(
    q: jax.Array,           # [B, H, D] one query token per row
    k_pool: jax.Array,      # [N, block_size, Hkv, D] (float or int8 pool)
    v_pool: jax.Array,
    tables: jax.Array,      # [B, M] physical block ids (0-padded)
    lengths: jax.Array,     # [B] valid token count per row
    k_scale: Optional[jax.Array] = None,   # [N, Hkv] f32 (int8 pools)
    v_scale: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    window: int = 0,
) -> jax.Array:
    """Attend each row's query over its OWN ragged paged context in one
    dispatch. Returns ``[B, H, D]``.

    ``tables`` spans the full window (``M = blocks_per_seq``); per-row cost
    follows ``lengths``. Multi-token callers (speculative verify, ragged
    continuation prefill) flatten their ``T`` queries into the batch axis
    with per-query lengths.
    """
    return pool_attention("ragged_paged_attention", q, k_pool, v_pool,
                          tables, lengths, k_scale, v_scale, scale=scale,
                          interpret=interpret, window=window)
