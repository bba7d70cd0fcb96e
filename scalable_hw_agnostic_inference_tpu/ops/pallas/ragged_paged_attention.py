"""Ragged paged attention as a Pallas TPU kernel.

ONE dispatch for heterogeneous context lengths (PAPERS.md 2604.15464,
"Ragged Paged Attention"): every row attends exactly its own live blocks
through its block table, so the executable no longer needs a context-bucket
ladder dispatched on the LONGEST running sequence. Compared to the bucketed
kernel (``paged_attention.py``, whose grid/unroll conventions this follows):

- grid ``(B, M)`` with ``M = blocks_per_seq`` — the FULL window, compiled
  once. A short row costs what it uses, not what the longest row buckets to:
  blocks past a row's live count skip their softmax update entirely
  (``@pl.when(j < n_live)``) and re-map their K/V index to the row's block 0
  so Pallas elides the re-fetch (revisit elision). HBM traffic AND compute
  scale with tokens actually present, killing the pad waste the bucket
  ladder paid on every mixed-length batch.
- int8 KV pools (``SHAI_KV_QUANT=int8``) dequantize IN-KERNEL: the pool
  streams as int8 — half the HBM traffic of bf16 — and the per-block x
  kv-head f32 scales (``ops.quant.quantize_kv_blocks``) ride in as two tiny
  side inputs, applied right after the block load.

The XLA gather-based reference for CPU/tier-1 lives in
``ops.attention.ragged_gather_attention``; ``ops.attention.
ragged_paged_attention`` dispatches between the two so every test runs
deviceless.

Mixed-phase fused rows (``SHAI_FUSED_STEP``): because the kernel is
row-oriented — each grid row carries its own ``(table, length)`` and pays
only its own live blocks — an engine step can fuse decode and chunked
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
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

#: pool blocks per scale-operand block: the f32 sublane tile, the smallest
#: row count Mosaic accepts for a [rows, Hkv] block of the [N, Hkv] scales
_SCALE_ROWS = 8


def _ragged_kernel(tables_ref, lens_ref, *rest,
                   scale: float, block_size: int, n_blocks: int,
                   quantized: bool):
    # q_ref: [Hkv, group, D]; k_ref/v_ref: [block_size, Hkv, D] — one whole
    # pool block per grid step (the head axis must stay in the block shape:
    # a squeezed middle leaves Mosaic's last-two-dims tiling at (1, D),
    # rejected for Hkv > 1 — see paged_attention.py). With ``quantized``,
    # ks_ref/vs_ref [_SCALE_ROWS, Hkv] (SMEM) carry the f32 scales of the
    # _SCALE_ROWS-aligned group of pool blocks this step's block sits in
    # (one row per block: a single [Hkv] row is below Mosaic's (8, 128)
    # block-shape rule), and sidx_ref [B, M] the block's row in that group.
    if quantized:
        (sidx_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
         o_ref, m_ref, l_ref, acc_ref) = rest
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length = lens_ref[b]
    n_live = pl.cdiv(length, block_size)

    # the ragged core: a dead block (j past the row's live count) does NO
    # flops — its fetch was already elided by the index re-map below, and
    # skipping the update here removes the dot/softmax work the bucketed
    # kernel still paid for masked blocks inside its window
    @pl.when(j < jnp.maximum(n_live, 1))
    def _update():
        q = q_ref[:].astype(jnp.float32) * scale      # [Hkv, G, D]
        k = k_ref[:].astype(jnp.float32)              # [bs, Hkv, D]
        v = v_ref[:].astype(jnp.float32)
        hkv, g, _ = q.shape
        if quantized:
            # in-kernel dequant: the per-(block, head) f32 scale is constant
            # over a head's whole [bs, D] tile, so it factors out of both
            # dots — scale head h's scores and its p @ v instead of the
            # tile. The scales are SMEM scalars: a scalar times a tile is a
            # plain splat, where a [1, 1] VMEM value has no Mosaic
            # broadcast in both directions.
            row = sidx_ref[b, j]
            k_sc = [ks_ref[row, h] for h in range(hkv)]
            v_sc = [vs_ref[row, h] for h in range(hkv)]
        else:
            k_sc = v_sc = [1.0] * hkv
        # per-kv-head 2D dots unrolled over the static head count (Mosaic
        # rejects 3D dot_general in-kernel; Hkv is the per-shard head
        # count, 1-8)
        s = jnp.stack([
            jax.lax.dot_general(q[h], k[:, h, :], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * k_sc[h]
            for h in range(hkv)])                     # [Hkv, G, bs]
        k_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (hkv, g, block_size), 2)
        live = k_pos < length
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_ref[:, :, :1]                      # [Hkv, G, 1]
        bm = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, bm)
        # a fully-masked tail inside a live block keeps exp() off NEG_INF
        # poison the same way the bucketed kernel does: zero via the mask
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)                # [Hkv, G, 1]
        l_new = l_ref[:, :, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jnp.stack([
            jax.lax.dot_general(p[h], v[:, h, :], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) * v_sc[h]
            for h in range(hkv)])                     # [Hkv, G, D]
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_blocks - 1)
    def _finish():
        o_ref[:] = (acc_ref[:] / jnp.maximum(l_ref[:, :, :1], 1e-20)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
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
) -> jax.Array:
    """Attend each row's query over its OWN ragged paged context in one
    dispatch. Returns ``[B, H, D]``.

    ``tables`` spans the full window (``M = blocks_per_seq``); per-row cost
    follows ``lengths`` — dead blocks skip compute and elide their fetch.
    Multi-token callers (speculative verify, ragged continuation prefill)
    flatten their ``T`` queries into the batch axis with per-query lengths,
    exactly like the bucketed kernel's layout.
    """
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    N, block_size, Hkv, _ = k_pool.shape
    M = tables.shape[1]
    group = H // Hkv
    quantized = k_scale is not None
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        from ..attention import on_tpu_platform

        interpret = not on_tpu_platform()

    tables = tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    qt = q.reshape(B, Hkv, group, D) if group > 1 else q[:, :, None, :]

    # dead blocks re-map to the row's first block: consecutive grid steps
    # see an unchanged index -> no re-fetch (and no compute, via the
    # in-kernel skip)
    def kv_index(b, j, tables, lens, *_):
        n_live = pl.cdiv(lens[b], block_size)
        jj = jnp.where(j < jnp.maximum(n_live, 1), j, 0)
        return (tables[b, jj], 0, 0, 0)

    def sc_index(b, j, tables, lens, sidx):
        n_live = pl.cdiv(lens[b], block_size)
        jj = jnp.where(j < jnp.maximum(n_live, 1), j, 0)
        return (tables[b, jj] // _SCALE_ROWS, 0)

    grid = (B, M)
    kernel = functools.partial(
        _ragged_kernel, scale=scale, block_size=block_size, n_blocks=M,
        quantized=quantized)
    # scalar-prefetch operands ride every index map; the quantized call
    # prefetches a third one (each block's row within its scale group)
    in_specs = [
        pl.BlockSpec((None, Hkv, group, D), lambda b, j, *_: (b, 0, 0, 0)),
        pl.BlockSpec((None, block_size, Hkv, D), kv_index),
        pl.BlockSpec((None, block_size, Hkv, D), kv_index),
    ]
    prefetch = [tables, lengths]
    args = [qt, k_pool, v_pool]
    if quantized:
        prefetch.append(tables % _SCALE_ROWS)
        in_specs += [pl.BlockSpec((_SCALE_ROWS, Hkv), sc_index,
                                  memory_space=pltpu.SMEM)] * 2
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, Hkv, group, D),
                                   lambda b, j, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hkv, group, 128), jnp.float32),   # m
                pltpu.VMEM((Hkv, group, 128), jnp.float32),   # l
                pltpu.VMEM((Hkv, group, D), jnp.float32),     # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        interpret=interpret,
    )(*prefetch, *args)
    return out.reshape(B, H, D)
