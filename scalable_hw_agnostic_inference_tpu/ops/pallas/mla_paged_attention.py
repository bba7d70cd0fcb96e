"""Absorbed latent attention over the paged latent pool, one Pallas kernel.

``paged_attention``'s structure with ONE shared "head": grid ``(B,)``, a
loop over a row's live tiles only, the kernel's own double-buffered copies
of a tile's live pool blocks out of HBM, the next tile's (after a row's
last, the next ROW's first) in flight while this one computes, online
softmax in float32 across tiles. What differs is what a tile holds:

- a pool block is ``[block_size, width]``: a token's normed latent (``rank``
  lanes), the rotary key all heads share, zeros to a lane multiple. There
  is no per-head key or value anywhere: every query head meets the SAME
  rows, so a tile is read once for all heads.
- the queries arrive absorbed (``ops.mla.absorb_q``): ``[H, width]`` in the
  row's own coordinates, so the scores are one ``[H, width] x [tile,
  width]^T`` product, and the value of a row is its own first ``rank``
  lanes: ``p @ tile[:, :rank]`` — the bytes copied for the scores are the
  values too.

Returns ``u`` ``[B, H, rank]``, the probabilities' sum of latents; the
caller applies ``W^V`` (``ops.mla.unabsorb``).

A tile is ``_TILE_TOKENS`` tokens (1024: 64 blocks of 16) and its copies
are issued from an unrolled loop: what a tile costs beyond its bytes is its
fixed part. Measured alone on a v5e at 64 rows x 32 heads x 640 lanes (my
chip run, PR 32; PERF.md): rows of 3.0k-7.4k tokens 1.23 ms at a tile of
256, 0.97 at 512, 0.90 at 1024 (52% of the 576 values' time at 819 GB/s);
rows of 10k 2.31, 1.78, 1.57 ms (58%).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

#: the kernel's name in a device trace
KERNEL_NAME = "mla_paged_decode"

_TILE_TOKENS = 1024


def mla_tile_tokens(block_size: int) -> int:
    """Tokens one tile covers: a whole number of pool blocks (a block
    larger than a tile is its own tile)."""
    return max(_TILE_TOKENS // block_size, 1) * block_size


def _mla_kernel(tables_ref, lens_ref, q_ref, c_hbm, o_ref, cbuf, sem,
                base_ref, *, scale: float, block_size: int, tile: int,
                rank: int):
    # q_ref [H, W]; c_hbm [N, block_size, W] left in HBM; o_ref [H, rank];
    # cbuf [2, tile, W] VMEM; sem [2]; base_ref [1] SMEM: the slot this
    # row's first tile was prefetched into
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    n_heads = q_ref.shape[0]
    per_tile = tile // block_size
    max_blocks = tables_ref.shape[1]

    def n_blocks(row):
        return jnp.clip(pl.cdiv(lens_ref[row], block_size), 1, max_blocks)

    def each_copy(row, i, slot, act):
        """``act`` on the copy of every live block of tile ``i`` of ``row``
        into ``slot``; unrolled, a block past the row's last is skipped."""
        first = i * per_tile
        n_live = n_blocks(row) - first
        for u in range(per_tile):
            @pl.when(u < n_live)
            def _(u=u):
                blk = tables_ref[row, jnp.minimum(first + u, max_blocks - 1)]
                act(pltpu.make_async_copy(
                    c_hbm.at[blk],
                    cbuf.at[slot, pl.ds(u * block_size, block_size)],
                    sem.at[slot]))

    @pl.when(b == 0)
    def _first_row():
        # a block of a live tile past the row's last live block is never
        # copied; its p is 0, and 0 * stale-VMEM must not be NaN
        cbuf[...] = jnp.zeros_like(cbuf)
        base_ref[0] = 0
        each_copy(0, 0, 0, lambda c: c.start())

    base = base_ref[0]
    length = lens_ref[b]
    n_tiles = pl.cdiv(n_blocks(b), per_tile)
    q = q_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, (n_heads, tile), 1)

    def tile_step(i, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(base + i, 2)
        last = i + 1 == n_tiles
        nxt_row = jnp.where(last, b + 1, b)

        @pl.when(nxt_row < n_rows)
        def _prefetch():
            each_copy(jnp.minimum(nxt_row, n_rows - 1),
                      jnp.where(last, 0, i + 1), 1 - slot,
                      lambda c: c.start())

        each_copy(b, i, slot, lambda c: c.wait())
        c_tile = cbuf[slot]
        live = col < length - i * tile
        s = jax.lax.dot_general(
            q, c_tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [H, tile]
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a fully masked tile (a length-0 row) keeps m at NEG_INF, where
        # exp(NEG_INF - NEG_INF) = 1 would poison l/acc: zero via the mask
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(c_tile.dtype), c_tile[:, :rank],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr + pv

    _, l_fin, acc = jax.lax.fori_loop(
        0, n_tiles, tile_step,
        (jnp.full((n_heads, 1), NEG_INF, jnp.float32),
         jnp.zeros((n_heads, 1), jnp.float32),
         jnp.zeros((n_heads, rank), jnp.float32)))
    base_ref[0] = jax.lax.rem(base + n_tiles, 2)
    o_ref[...] = (acc / jnp.maximum(l_fin, 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def mla_paged_decode(
    q_abs: jax.Array,       # [B, H, W] absorbed queries, one token per row
    pool: jax.Array,        # [N, block_size, W] the paged latent pool
    tables: jax.Array,      # [B, M] physical block ids (0-padded)
    lengths: jax.Array,     # [B] valid token count per row
    *,
    rank: int,
    scale: float,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Each row's heads over the row's paged latents. Returns ``u``
    ``[B, H, rank]``. A row walks ``cdiv(lengths[b], tile)`` tiles of its
    table and never looks past them; a row of length 0 (an inactive or pad
    slot, a table of zeros) walks one tile of the null block and returns
    finite values. Multi-token callers flatten their queries into the row
    axis with a length each."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, W = q_abs.shape
    _N, block_size, _ = pool.shape
    if interpret is None:
        from ..attention import on_tpu_platform

        interpret = not on_tpu_platform()
    tile = mla_tile_tokens(block_size)
    row = lambda b, *_: (b, 0, 0)                     # noqa: E731
    kernel = functools.partial(_mla_kernel, scale=scale,
                               block_size=block_size, tile=tile, rank=rank)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, H, W), row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, H, rank), row),
            scratch_shapes=[
                pltpu.VMEM((2, tile, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q_abs.dtype),
        # rows run in order: each prefetches the next one's first tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), q_abs, pool)
