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

A tile is ``_TILE_TOKENS`` tokens (1024: 64 blocks of 16): 1.31 MB, 1.6 us
of HBM time at 819 GB/s, and 9,216 MXU row pushes. The kernel is as fast as
a tile's copies, its products and the scalar work of issuing 64 copies
OVERLAP, and what makes them is the order of one tile step:

- a tile is copied, waited for and multiplied in GROUPS of ``_WAIT_TOKENS``
  (256: 16 blocks). A group's copies signal one semaphore and ONE wait takes
  the group's bytes, so a group is always copied whole: where a row ends
  inside it the dead blocks copy the null block 0 (at most 15 blocks a
  row), and a group with no live block is neither copied nor waited for.
  Its scratch keeps what an earlier tile left there, which is finite (every
  byte the scratch ever holds is a zero, a live row's or the null block's)
  and meets a probability of exactly 0.
- the step is ONE straight line (the compiler predicates a guarded copy or
  wait, it does not branch): wait for group ``g``, start the NEXT tile's
  group ``g`` into the other slot, the scores of ``g``, then the softmax
  and the value product of ``g - 1``. The products run on what has landed
  while the rest of the tile and the next tile are in flight, the scalar
  unit computes the next copies' addresses in the same bundles as the MXU's
  pushes, and a ``[H, 256]`` score block at a time lives in registers
  where a whole tile's ``[H, 1024]`` spilled.
- the block table rides in flat and padded by a tile, so an entry's
  address is one add and needs no clamp; and the compiler's bounds checks
  of each copy's two addresses are OFF: they were half the scalar work of
  a start (14 of 28 bundles a copy) and with them on this same kernel takes
  0.69 ms where it takes 0.62. A block id is the allocator's, the scratch
  offsets are static, and the slot is ``rem(., 2)``.

Measured alone on a v5e at 64 rows x 32 heads x 640 lanes
(``scripts/mla_bench.py``; my chip runs, PR 51; PERF.md section 6): rows
drawn as ``decode-sat-8k`` draws them (2.0k-10k tokens, mean 5.4k) 0.88 ms a
call before this order (55% of the 576 values' time at 819 GB/s, 2.61 us per
1,024 tokens), 0.62 ms with it (78%, 1.84 us); rows of 10k 1.52 -> 1.12 ms
(59 -> 81%). Its copies ALONE take 0.62 ms (the copied bytes at 735 GB/s)
and its products alone 0.35: it is bound by its bytes, 640 lanes a token
where 576 are counted, so 90% is the ceiling. Sub-tiles of 128 or 512, tiles
of 512 or 2048, a wait a tile, per-group branches round dead groups and a
statically indexed slot (two scratch buffers, the step written twice) were
each measured and are no faster.
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
_WAIT_TOKENS = 256


def mla_tile_tokens(block_size: int) -> int:
    """Tokens one tile covers: a whole number of pool blocks (a block
    larger than a tile is its own tile)."""
    return max(_TILE_TOKENS // block_size, 1) * block_size


def mla_wait_tokens(block_size: int) -> int:
    """Tokens of a tile that are copied, waited for and multiplied
    together: whole pool blocks, a whole number of times in a tile."""
    tile = mla_tile_tokens(block_size)
    group = max(_WAIT_TOKENS // block_size, 1) * block_size
    return group if tile % group == 0 else tile


def _mla_kernel(tables_ref, lens_ref, q_ref, c_hbm, o_ref, cbuf, sem,
                base_ref, *, scale: float, block_size: int, tile: int,
                group: int, rank: int, max_blocks: int):
    # tables_ref [B * max_blocks + tile // block_size] SMEM, rows end to
    # end; q_ref [H, W]; c_hbm [N, block_size, W] left in HBM; o_ref
    # [H, rank]; cbuf [2, tile, W] VMEM; sem [2, groups]; base_ref [1]
    # SMEM: the slot this row's first tile was prefetched into
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    n_heads = q_ref.shape[0]
    per_tile = tile // block_size
    per_group = group // block_size
    n_groups = tile // group

    def n_blocks(row):
        return jnp.clip(pl.cdiv(lens_ref[row], block_size), 1, max_blocks)

    def start_group(row, i, slot, g, on=True):
        """Start the copies of group ``g`` of tile ``i`` of ``row`` into
        ``slot``, all of them or (no live block in the group, or not
        ``on``) none; a dead block of a live group copies the null block."""
        n_live = jnp.where(on, n_blocks(row) - i * per_tile, 0)
        at = row * max_blocks + i * per_tile
        for u in range(g * per_group, (g + 1) * per_group):
            blk = jnp.where(u < n_live, tables_ref[at + u], 0)

            @pl.when(g * per_group < n_live)
            def _(blk=blk, u=u):
                pltpu.make_async_copy(
                    c_hbm.at[blk],
                    cbuf.at[slot, pl.ds(u * block_size, block_size)],
                    sem.at[slot, g]).start()

    def wait_group(slot, g, n_live):
        """One wait for the whole of group ``g``'s bytes in ``slot``."""
        @pl.when(g * per_group < n_live)
        def _():
            dst = cbuf.at[slot, pl.ds(g * group, group)]
            pltpu.make_async_copy(dst, dst, sem.at[slot, g]).wait()

    @pl.when(b == 0)
    def _first_row():
        # a group of a live tile past the row's last live block is never
        # copied; its p is 0, and 0 * stale-VMEM must not be NaN
        cbuf[...] = jnp.zeros_like(cbuf)
        base_ref[0] = 0
        for g in range(n_groups):
            start_group(0, 0, 0, g)

    base = base_ref[0]
    length = lens_ref[b]
    n_tiles = pl.cdiv(n_blocks(b), per_tile)
    q = q_ref[...]
    col = jax.lax.broadcasted_iota(jnp.int32, (n_heads, group), 1)

    def tile_step(i, carry):
        slot = jax.lax.rem(base + i, 2)
        last = i + 1 == n_tiles
        nxt_row = jnp.where(last, b + 1, b)
        nxt = (jnp.minimum(nxt_row, n_rows - 1), jnp.where(last, 0, i + 1),
               jax.lax.rem(slot + 1, 2))
        n_live = n_blocks(b) - i * per_tile

        def land(g):
            """Group ``g`` has landed: the next tile's group ``g`` goes
            out, and this one's scores."""
            wait_group(slot, g, n_live)
            start_group(*nxt, g, on=nxt_row < n_rows)
            return jax.lax.dot_general(
                q, cbuf[slot, pl.ds(g * group, group)],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [H, group]

        def fold(g, s, m_prev, l_prev, acc):
            live = col < length - i * tile - g * group
            s = jnp.where(live, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # a fully masked group (a length-0 row) keeps m at NEG_INF,
            # where exp(NEG_INF - NEG_INF) = 1 would poison l/acc: zero via
            # the mask
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            pv = jax.lax.dot_general(
                p.astype(cbuf.dtype),
                cbuf[slot, pl.ds(g * group, group), :rank],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return (m_new, l_prev * corr + jnp.sum(p, axis=-1, keepdims=True),
                    acc * corr + pv)

        s = land(0)
        for g in range(n_groups):
            s_next = land(g + 1) if g + 1 < n_groups else None
            carry = fold(g, s, *carry)
            s = s_next
        return carry

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
    group = mla_wait_tokens(block_size)
    row = lambda b, *_: (b, 0, 0)                     # noqa: E731
    kernel = functools.partial(
        _mla_kernel, scale=scale, block_size=block_size, tile=tile,
        group=group, rank=rank, max_blocks=tables.shape[1])
    # rows end to end and a tile of zeros behind them: a tile's entries are
    # read at ``row * M + first + u`` whatever the table's width
    flat = jnp.pad(tables.astype(jnp.int32).reshape(-1),
                   (0, tile // block_size))
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
                pltpu.SemaphoreType.DMA((2, tile // group)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q_abs.dtype),
        # rows run in order: each prefetches the next one's first tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True),
        interpret=interpret,
        name=KERNEL_NAME,
    )(flat, lengths.astype(jnp.int32), q_abs, pool)
