"""Paged attention over the block pool as ONE Pallas TPU kernel body.

Each row (one query token of one sequence) attends its own KV context
*directly out of the paged block pool* through its block table — no
``[B, L, Hkv, Dh]`` materialization per layer per token. What a row costs
follows the tokens it HOLDS (``lengths``), not the width of the table it
was dispatched with:

- grid ``(B,)``, and inside a row a loop over its live **tiles** only:
  ``cdiv(lengths[b], tile)`` trips, so a 300-token row in a 2048-token
  window runs two 256-token tiles and the other six do not exist — no dead
  grid steps, no masked dots. (The body this replaces walked ``(B, M)`` one
  16-token pool block per grid step, 32,768 steps a Mistral decode step at
  0.54 us each, dead or live: its cost was its step count.)
- a tile is several pool blocks. A row's blocks are not contiguous, so the
  pools stay in HBM (``memory_space=pl.ANY``) and the kernel copies the
  tile's live blocks itself (``pltpu.make_async_copy``) into a
  double-buffered VMEM scratch; the next tile's copies — the next ROW's
  first tile after a row's last — are in flight while this one computes.
  Blocks of a live tile past the row's last live block are not copied:
  their scores are masked, and the V scratch is zeroed once so that a
  never-written page meets its probability of 0 as a finite number.
- the pool is viewed ``[N, block_size * Hkv, D]``: row ``t * Hkv + h`` of
  a block is token ``t``'s head ``h``. The TPU tiles an array's last two
  dims, so this view moves no tile and XLA makes it a bitcast (per shard
  under ``shard_map``), where ``[N, block_size, Hkv * D]`` is a relayout of
  the whole pool per layer (measured: 3.6 ms a decode step). A VMEM tile
  ``[tile * Hkv, D]`` is then dense for every per-shard head count, and
  the kernel never slices a head out of it: every q head meets every
  (token, kv head) row in ONE 2D dot, and the columns of other kv heads
  are masked exactly like dead tokens. The MXU idles through the surplus;
  the per-head ``[tile, Hkv, D] -> [tile, D]`` sublane gather the old body
  paid for is gone.
- online softmax across tiles with f32 ``m``/``l``/``acc`` carried in the
  loop; K enters its dot as stored, scores and ``p @ v`` accumulate in
  f32 (``p`` stays f32), the output is rounded once.
- int8 pools (``k_scale``/``v_scale``, per block x kv head) dequantize
  in-kernel: the scales of a row's blocks ride in as one small VMEM block
  and spread over the tile's (token, head) columns by a one-hot product.

``tile_tokens`` is the one place the tile size is decided; the engine's
pad accounting (``LLMEngine._note_dispatch_pad``) reads it too.

Reference capability this reproduces first-party: vLLM's paged attention
(``block_size: 4096`` at 128k ``max_model_len``,
``cova/mllama-32-11b-vllm-trn1-config.yaml:10-16``), which the reference
consumes from the vendored neuron fork.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30

#: VMEM the K and V tiles may take together, both slots of each
_TILE_VMEM_BYTES = 2 << 20
#: a tile's token count stays inside these whatever the budget allows:
#: under 128 the per-tile fixed cost shows again, over 256 a row of a few
#: hundred tokens pays for a mostly masked tile
_TILE_TOKENS_MIN, _TILE_TOKENS_MAX = 128, 256


def tile_tokens(block_size: int, n_kv_heads: int, head_dim: int,
                dtype) -> int:
    """Tokens one tile of the paged kernel covers, from what the call can
    see: the pool's block size, (per-shard) kv heads, head size and dtype
    against a VMEM budget. A whole number of pool blocks, or — for a block
    larger than the budget allows — an even part of one."""
    per_token = 4 * n_kv_heads * head_dim * np.dtype(dtype).itemsize
    want = min(max(_TILE_VMEM_BYTES // per_token, _TILE_TOKENS_MIN),
               _TILE_TOKENS_MAX)
    if block_size <= want:
        return want // block_size * block_size
    tile = block_size
    while tile > want and tile % 2 == 0:
        tile //= 2
    return tile


def first_live_tile(n_tokens, tile: int, window: int):
    """The first tile a row of ``n_tokens`` walks in a layer whose queries
    see ``window`` keys behind them (0 = all): the tile that holds key
    ``n_tokens - window``. The kernel and the engine's accounting share
    this rule (``n_tokens`` a Python int or a traced scalar)."""
    if not window:
        return 0
    lo = n_tokens - window
    lo = max(lo, 0) if isinstance(lo, int) else jnp.maximum(lo, 0)
    return lo // tile


def live_tile_tokens(n_tokens: int, tile: int, window: int = 0) -> int:
    """Token slots the kernel walks for a row holding ``n_tokens``: its
    live tiles, whole (a row of length 0 walks one). A window layer skips
    the tiles wholly below ``n_tokens - window``."""
    return (-(-max(n_tokens, 1) // tile)
            - first_live_tile(n_tokens, tile, window)) * tile


def _pool_kernel(tables_ref, lens_ref, *rest, scale: float, block_size: int,
                 tile: int, hkv: int, quantized: bool, window: int = 0):
    # q_ref/o_ref [H, D]; k_hbm/v_hbm [N, bs * Hkv, D] left in HBM, row
    # ``t * Hkv + h`` of a block is token t's head h; kbuf/vbuf [2, tile *
    # Hkv, D] VMEM in the same row order, sem [2 (k, v), 2 (slot)];
    # base_ref [1] SMEM: the slot this row's first tile was prefetched
    # into. With ``quantized``, ks_ref/vs_ref [Hkv, M] f32: the scales of
    # the row's table blocks.
    from jax.experimental.pallas import tpu as pltpu

    if quantized:
        q_ref, ks_ref, vs_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, \
            base_ref = rest
    else:
        q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, base_ref = rest
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    n_heads, d = q_ref.shape
    group = n_heads // hkv
    m_tab = tables_ref.shape[1]
    cols = tile * hkv
    # the copy unit: a pool block, or a tile-sized part of a larger one
    unit = min(block_size, tile)
    per_tile = tile // unit
    max_units = m_tab * block_size // unit

    def n_units(row):
        return jnp.clip(pl.cdiv(lens_ref[row], unit), 1, max_units)

    def first_tile(row):
        # a window layer (static ``window`` > 0) starts at the tile that
        # holds the row's first visible key; with no window this is the
        # Python 0 it always was, and nothing below traces differently
        if not window:
            return 0
        return first_live_tile(lens_ref[row], tile, window)

    def each_copy(row, i, slot, act):
        """``act`` on the K and V copy of every live unit of tile ``i`` of
        ``row`` into ``slot``. Under a window, the units of the first live
        tile that lie wholly below the window are not copied either."""
        first = i * per_tile
        u0 = 0 if not window else jnp.clip(
            jnp.maximum(lens_ref[row] - window, 0) // unit - first,
            0, per_tile)

        def one(u, carry):
            pos = (first + u) * unit
            blk = tables_ref[row, pos // block_size]
            dst = pl.ds(pl.multiple_of(u * unit * hkv, unit * hkv),
                        unit * hkv)
            if unit == block_size:
                src_k, src_v = k_hbm.at[blk], v_hbm.at[blk]
            else:
                part = pl.ds(pl.multiple_of(pos % block_size * hkv,
                                            unit * hkv), unit * hkv)
                src_k, src_v = k_hbm.at[blk, part], v_hbm.at[blk, part]
            act(pltpu.make_async_copy(src_k, kbuf.at[slot, dst],
                                      sem.at[0, slot]))
            act(pltpu.make_async_copy(src_v, vbuf.at[slot, dst],
                                      sem.at[1, slot]))
            return carry

        jax.lax.fori_loop(
            u0, jnp.minimum(n_units(row) - first, per_tile), one, 0)

    @pl.when(b == 0)
    def _first_row():
        # a page of a live tile past the row's last live page is never
        # copied; its p is 0, and 0 * stale-VMEM must not be NaN
        vbuf[...] = jnp.zeros_like(vbuf)
        base_ref[0] = 0
        each_copy(0, first_tile(0), 0, lambda c: c.start())

    base = base_ref[0]
    length = lens_ref[b]
    t0 = first_tile(b)
    n_tiles = pl.cdiv(n_units(b), per_tile)
    # K/V enter the dots as stored when q shares their dtype (int8 is exact
    # in every float type here); otherwise both sides go to f32
    dot_dt = (q_ref.dtype if kbuf.dtype in (q_ref.dtype, jnp.dtype(jnp.int8))
              else jnp.float32)
    q = q_ref[...].astype(dot_dt)
    # every q head meets every (token, kv head) column of the tile in ONE
    # 2D dot, and the columns of other kv heads are masked like dead
    # tokens: the MXU idles through the surplus, and no per-head slice of
    # a [tile, Hkv, D] tile (a sublane gather) is ever taken
    col = jax.lax.broadcasted_iota(jnp.int32, (n_heads, cols), 1)
    own_head = (col % hkv == jax.lax.broadcasted_iota(
        jnp.int32, (n_heads, cols), 0) // group)
    col_tok = col // hkv

    def tile_step(i, carry):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(base + (i - t0 if window else i), 2)
        # what to fetch while this tile computes: the row's next tile, or
        # after its last the next row's first
        last = i + 1 == n_tiles
        nxt_row = jnp.where(last, b + 1, b)

        @pl.when(nxt_row < n_rows)
        def _prefetch():
            nxt_first = first_tile(
                jnp.minimum(b + 1, n_rows - 1)) if window else 0
            each_copy(nxt_row, jnp.where(last, nxt_first, i + 1), 1 - slot,
                      lambda c: c.start())

        each_copy(b, i, slot, lambda c: c.wait())

        live = own_head & (col_tok < length - i * tile)
        if window:
            # the tile the window's lower edge cuts: keys below it masked
            live = live & (col_tok >= length - window - i * tile)
        s = jax.lax.dot_general(
            q, kbuf[slot].astype(dot_dt), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [H, cols]
        if quantized:
            k_sc, v_sc = _column_scales(
                (ks_ref[...], vs_ref[...]), i, length, hkv, tile, block_size)
            s = s * k_sc
        s = jnp.where(live, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a fully masked tile (a length-0 row) keeps m at NEG_INF, where
        # exp(NEG_INF - NEG_INF) = 1 would poison l/acc: zero via the mask
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)                    # [H, 1]
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p * v_sc if quantized else p, vbuf[slot].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr + pv

    _, l_fin, acc = jax.lax.fori_loop(
        t0, n_tiles, tile_step,
        (jnp.full((n_heads, 1), NEG_INF, jnp.float32),
         jnp.zeros((n_heads, 1), jnp.float32),
         jnp.zeros((n_heads, d), jnp.float32)))
    base_ref[0] = jax.lax.rem(
        base + (n_tiles - t0 if window else n_tiles), 2)
    o_ref[...] = (acc / jnp.maximum(l_fin, 1e-20)).astype(o_ref.dtype)


def _column_scales(scales, i, length, hkv: int, tile: int, block_size: int):
    """An int8 pool's per-(kv head, table block) scales, each [Hkv, M] ->
    one scale per (token, kv head) column of tile ``i``, each [1, tile *
    Hkv]."""
    m_tab, cols = scales[0].shape[1], tile * hkv
    # a table entry past the row's live blocks names a block the row does
    # not own: its scale is anything, and 0 * NaN is NaN
    own = jax.lax.broadcasted_iota(
        jnp.int32, (hkv, m_tab), 1) * block_size < length
    col = jax.lax.broadcasted_iota(jnp.int32, (m_tab, cols), 1)
    in_block = ((i * tile + col // hkv) // block_size
                == jax.lax.broadcasted_iota(jnp.int32, (m_tab, cols), 0)
                ).astype(jnp.float32)
    head = jax.lax.broadcasted_iota(jnp.int32, (hkv, cols), 0)
    own_col = head == jax.lax.broadcasted_iota(
        jnp.int32, (hkv, cols), 1) % hkv

    def spread(sc):
        by_head = jax.lax.dot_general(                    # [Hkv, cols]
            jnp.where(own, sc, 0.0), in_block, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        return jnp.sum(jnp.where(own_col, by_head, 0.0), axis=0,
                       keepdims=True)

    return [spread(sc) for sc in scales]


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "window"))
def paged_decode_attention(
    q: jax.Array,           # [B, H, D] one query token per row
    k_pool: jax.Array,      # [N, block_size, Hkv, D] the paged pool
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
    """Attend each row's query over its paged context. Returns ``[B, H, D]``.

    The one entry point of the pool kernel. A row walks
    ``cdiv(lengths[b], tile)`` tiles of its table and never looks past
    them, so the table's width only bounds what a row may hold. A row of
    length 0 (an inactive or pad slot, a table of zeros) walks one tile of
    the null block and returns finite values. A multi-token caller
    (speculative verify) flattens its ``T`` queries into the batch axis
    with per-query lengths.

    ``k_scale``/``v_scale``: per-block x kv-head f32 scales of an int8 pool
    (``SHAI_KV_QUANT=int8``), dequantized in-kernel. ``window`` (static;
    0 = none): a row's query sees its last ``window`` keys only — the
    tiles wholly below ``lengths[b] - window`` are neither copied nor
    computed, and the tile that edge cuts is masked below it."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    N, block_size, Hkv, _ = k_pool.shape
    M = tables.shape[1]
    quantized = k_scale is not None
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        from ..attention import on_tpu_platform

        interpret = not on_tpu_platform()
    tile = tile_tokens(block_size, Hkv, D, k_pool.dtype)

    row = lambda b, *_: (b, 0, 0)                     # noqa: E731
    in_specs = [pl.BlockSpec((None, H, D), row)]
    args = [q]
    if quantized:
        # the scales of each row's table blocks, kv heads leading: [B,
        # Hkv, M] f32, one small VMEM block a row
        in_specs += [pl.BlockSpec((None, Hkv, M), row)] * 2
        args += [jnp.swapaxes(sc.astype(jnp.float32)[tables], 1, 2)
                 for sc in (k_scale, v_scale)]
    # [N, bs, Hkv, D] -> [N, bs * Hkv, D]: (token, head) rows. The TPU
    # tiles a pool's last two dims, and this view keeps every tile where
    # it is, so XLA makes it a bitcast; [N, bs, Hkv * D] would not be (it
    # cost a relayout of the whole pool per layer: PERF.md, PR 25)
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    args += [k_pool.reshape(N, block_size * Hkv, D),
             v_pool.reshape(N, block_size * Hkv, D)]
    kernel = functools.partial(
        _pool_kernel, scale=scale, block_size=block_size, tile=tile,
        hkv=Hkv, quantized=quantized, window=int(window))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, H, D), row),
            scratch_shapes=[
                pltpu.VMEM((2, tile * Hkv, D), k_pool.dtype),
                pltpu.VMEM((2, tile * Hkv, D), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        # rows run in order: each prefetches the next one's first tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), *args)
