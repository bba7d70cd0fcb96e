"""Every Pallas kernel against its XLA oracle, at one engine's shapes.

One list of cases serves two checks that must not drift apart: the tier-1
test lowers each case for the v5e target without a device
(``tests/test_kernel_lowering.py``), and ``chip_smoke.py`` runs each case
compiled on the chip and compares it with the oracle on seeded inputs. A
case is the shape the engine dispatches for one (model, tensor-parallel
degree, block size, bucket) — not a toy.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .attention import dot_product_attention, ragged_gather_attention
from .pallas.flash_attention import flash_attention
from .pallas.paged_attention import paged_decode_attention, tile_tokens
from .quant import quantize_kv_blocks

#: max-abs error allowed against the oracle. Inputs are unit normal, so an
#: output (a convex mix of V rows) stays within |o| <= ~4, where one bf16
#: ulp is 2**-6: the bound is two ulps of the bf16 result, the kernel and
#: the oracle each rounding once from their own f32 accumulation order.
#: int8-KV doubles it: the oracle also rounds its dequantized K/V to bf16.
TOL_BF16 = 2 * 2.0 ** -6
TOL_INT8_KV = 2 * TOL_BF16


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One kernel at one shape: ``kernel(*make_inputs(key))`` must agree
    with ``oracle(*make_inputs(key))`` within ``tol``. ``kernel`` takes
    ``interpret=`` (False compiles through Mosaic)."""

    name: str
    make_inputs: Callable
    kernel: Callable
    oracle: Callable
    tol: float

    def max_abs_err(self, interpret: bool, seed: int = 0) -> float:
        """Run both sides on seeded inputs; NaN/Inf anywhere reads as inf."""
        args = jax.jit(self.make_inputs)(jax.random.PRNGKey(seed))
        out = jax.jit(lambda *a: self.kernel(*a, interpret=interpret))(*args)
        ref = jax.jit(self.oracle)(*args)
        out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
        if out.shape != ref.shape or not np.isfinite(out).all():
            return float("inf")
        return float(np.max(np.abs(out - ref)))


def _flash_case(H: int, Hkv: int, D: int, T: int, S: int,
                rows: int, window: int = 0) -> KernelCase:
    """Causal flash prefill of a ``T``-token bucket over ``S`` keys
    (``S > T`` is a continuation chunk behind ``S - T`` prior tokens),
    ``rows`` sequences with mixed true lengths. ``window``: a window
    layer's bound (the chunk's queries then cross its lower edge when
    ``S > window``)."""
    start = S - T
    # full bucket, one token, and (when rows allow) lengths in between
    lens = [start + n for n in (T, 1, T // 2 + 3, T - 1)][:rows]

    def make(key):
        kq, kk, kv = jax.random.split(key, 3)
        return (jax.random.normal(kq, (rows, T, H, D), jnp.bfloat16),
                jax.random.normal(kk, (rows, S, Hkv, D), jnp.bfloat16),
                jax.random.normal(kv, (rows, S, Hkv, D), jnp.bfloat16),
                jnp.asarray(lens, jnp.int32))

    return KernelCase(
        name=(f"flash-H{H}x{Hkv}-T{T}-S{S}-b{rows}"
              f"{f'-w{window}' if window else ''}"), make_inputs=make,
        kernel=lambda q, k, v, n, interpret: flash_attention(
            q, k, v, causal=True, lengths=n, interpret=interpret,
            window=window),
        oracle=lambda q, k, v, n: dot_product_attention(
            q, k, v, causal=True, kv_lengths=n, impl="xla", window=window),
        tol=TOL_BF16)


def _pool_case(H: int, Hkv: int, D: int, block_size: int,
               blocks_per_seq: int, rows: int, int8_kv: bool,
               tile_edges: bool = False, window: int = 0,
               one_seq: bool = False) -> KernelCase:
    """The paged-pool kernel over ``rows`` single-query rows with shuffled
    block tables: rows of mixed context lengths, each with its own table
    (a decode step), or, ``one_seq``, ``rows`` consecutive queries of ONE
    sequence flattened a query a row, all sharing its table (what
    speculative verify dispatches).

    ``tile_edges`` is what a kernel that walks several pool blocks a tile
    can get wrong: lengths on both sides of a tile's edge, and every pool
    block no row's live tokens sit in filled with NaN (an int8 pool: NaN
    scales), so a page that is fetched though dead, or multiplied though
    never fetched, shows in the output.

    ``window``: a window layer's bound. Lengths then sit at the window,
    just below it and far above it (and, with ``tile_edges``, where the
    window's lower edge meets a tile's), and the poison also fills every
    block wholly BELOW a row's window: a tile the kernel should have
    skipped, or a block of the edge tile it should not have copied."""
    L = blocks_per_seq * block_size
    t = tile_tokens(block_size, Hkv, D,
                    jnp.int8 if int8_kv else jnp.bfloat16)
    if one_seq:
        # consecutive queries that straddle the tile's edge, the window,
        # or both; else mid-table
        if window:
            mid = window + (t if tile_edges else 0)
        else:
            mid = t if tile_edges else L // 2 + 5
        lens = [mid - rows // 2 + 1 + i for i in range(rows)]
    elif window and tile_edges:
        # the window's lower edge on a tile's edge, one past it, one short
        lens = [window + t, window + t + 1, window + t - 1, window + 1,
                window + 2 * t + block_size + 3, L, L - 1, window]
    elif window:
        lens = [window - 1, window, window + 1, L, 1, block_size + 3,
                window + t + 5, 2 * window + 7]
    elif tile_edges:
        lens = [t - 1, t, t + 1, 2 * t, 2 * t + 1, 1, block_size + 3, L]
    else:
        # one token, a partial second block, mid-window, the full window
        lens = [1, block_size + 3, L // 2 + 5, L]
    lens = (lens * -(-rows // len(lens)))[:rows]
    lens = [min(max(n, 1), L) for n in lens]
    n_blocks = rows * blocks_per_seq + 1          # + the reserved block 0

    def make(key):
        kq, kk, kv, kt = jax.random.split(key, 4)
        shape = (n_blocks, block_size, Hkv, D)
        k = jax.random.normal(kk, shape, jnp.float32)
        v = jax.random.normal(kv, shape, jnp.float32)
        # every row owns distinct physical blocks, in shuffled order
        tables = 1 + jax.random.permutation(kt, n_blocks - 1).reshape(
            rows, blocks_per_seq).astype(jnp.int32)
        if one_seq:
            tables = jnp.repeat(tables[:1], rows, axis=0)
        q = jax.random.normal(kq, (rows, H, D), jnp.bfloat16)
        n = jnp.asarray(lens, jnp.int32)
        poison = lambda x: x                          # noqa: E731
        if tile_edges:
            # NaN in every block that holds no live token of any row (the
            # null block stays clean)
            first = jnp.arange(blocks_per_seq)[None, :] * block_size
            held = first < n[:, None]
            if window:
                held &= first + block_size > n[:, None] - window
            owned = jnp.zeros((n_blocks,), bool).at[0].set(True).at[
                jnp.where(held, tables, 0).ravel()].set(True)
            poison = lambda x: jnp.where(             # noqa: E731
                owned.reshape((-1,) + (1,) * (x.ndim - 1)), x, jnp.nan)
        if int8_kv:
            kq8, ks = quantize_kv_blocks(k)
            vq8, vs = quantize_kv_blocks(v)
            return q, kq8, vq8, tables, n, poison(ks), poison(vs)
        return (q, poison(k).astype(jnp.bfloat16),
                poison(v).astype(jnp.bfloat16), tables, n)

    def oracle(q, k, v, tables, n, ks=None, vs=None):
        # the gather reference multiplies masked slots by 0: it reads the
        # poisoned pool with the poison taken out
        if ks is None:
            k, v = jnp.nan_to_num(k), jnp.nan_to_num(v)
        else:
            ks, vs = jnp.nan_to_num(ks), jnp.nan_to_num(vs)
        return ragged_gather_attention(
            q[:, None], k, v, tables, (n - 1)[:, None], ks, vs,
            window=window)[:, 0]

    return KernelCase(
        name=(f"paged-H{H}x{Hkv}-bs{block_size}-M{blocks_per_seq}-b{rows}"
              f"-{'int8kv' if int8_kv else 'bf16'}"
              f"{'-edges' if tile_edges else ''}"
              f"{f'-w{window}' if window else ''}"
              f"{'-oneseq' if one_seq else ''}"),
        make_inputs=make,
        kernel=lambda *a, interpret: paged_decode_attention(
            *a, interpret=interpret, window=window),
        oracle=oracle, tol=TOL_INT8_KV if int8_kv else TOL_BF16)


def engine_cases(n_heads: int, n_kv_heads: int, head_dim: int, *,
                 tp: int = 1, block_size: int = 16,
                 buckets: Sequence[int] = (128, 512),
                 max_model_len: int = 2048, max_num_seqs: int = 4,
                 max_prefill_batch: int = 4,
                 window: int = 0) -> List[KernelCase]:
    """The kernel calls an engine of this geometry dispatches, per TP shard:
    flash at each prefill bucket (widest prefill batch) and at the first
    continuation start, then the paged kernel over the full block table,
    bf16 and int8-KV, a row a sequence and as one sequence's consecutive
    queries: ``max_num_seqs`` rows of mixed lengths, then 8 rows (the
    benchmark cells' batch) at the tile's edges over a NaN-poisoned pool.
    ``window`` (a model with window layers) adds the same calls under the
    window: flash at the top bucket and at the continuation start that
    crosses the window's edge, the paged kernel in both layouts (bf16:
    the boot refuses int8 KV with window layers) at, just below and far
    above the window, and at the tile's edges over the poisoned pool."""
    H, Hkv = n_heads // tp, n_kv_heads // tp
    M = max_model_len // block_size
    top = max(buckets)
    cases = [_flash_case(H, Hkv, head_dim, b, b, max_prefill_batch)
             for b in sorted(buckets)]
    if max_model_len >= 2 * top:
        cases.append(_flash_case(H, Hkv, head_dim, top, 2 * top, 1))
    for int8_kv in (False, True):
        for one_seq in (False, True):
            cases.append(_pool_case(H, Hkv, head_dim, block_size, M,
                                    max_num_seqs, int8_kv, one_seq=one_seq))
            cases.append(_pool_case(H, Hkv, head_dim, block_size, M, 8,
                                    int8_kv, tile_edges=True,
                                    one_seq=one_seq))
    if window:
        cases.append(_flash_case(H, Hkv, head_dim, top, top,
                                 max_prefill_batch, window))
        # the continuation chunk whose queries cross the window's edge
        prior = -(-window // top) * top
        if max_model_len >= prior + top:
            cases.append(_flash_case(H, Hkv, head_dim, top, prior + top, 1,
                                     window))
        for one_seq in (False, True):
            cases.append(_pool_case(H, Hkv, head_dim, block_size, M, 8,
                                    False, window=window, one_seq=one_seq))
            cases.append(_pool_case(H, Hkv, head_dim, block_size, M, 8,
                                    False, tile_edges=True, window=window,
                                    one_seq=one_seq))
    return cases


def _latent_flash_case(H: int, D: int, Dv: int, T: int, S: int) -> KernelCase:
    """Latent attention's expanded prefill: causal flash over keys of ``D``
    beside values of ``Dv``, every head its own keys; ``S > T`` is a
    continuation chunk behind its expanded prefix."""
    lens = [S - T + n for n in (T, T // 2 + 3)]

    def make(key):
        kq, kk, kv = jax.random.split(key, 3)
        return (jax.random.normal(kq, (2, T, H, D), jnp.bfloat16),
                jax.random.normal(kk, (2, S, H, D), jnp.bfloat16),
                jax.random.normal(kv, (2, S, H, Dv), jnp.bfloat16),
                jnp.asarray(lens, jnp.int32))

    return KernelCase(
        name=f"flash-latent-H{H}-D{D}v{Dv}-T{T}-S{S}", make_inputs=make,
        kernel=lambda q, k, v, n, interpret: flash_attention(
            q, k, v, causal=True, lengths=n, interpret=interpret,
            scale=D ** -0.5),
        oracle=lambda q, k, v, n: dot_product_attention(
            q, k, v, causal=True, kv_lengths=n, impl="xla",
            scale=D ** -0.5),
        tol=TOL_BF16)


def _latent_pool_case(H: int, width: int, rank: int, scale: float,
                      block_size: int, blocks_per_seq: int, rows: int,
                      tile_edges: bool = False, one_seq: bool = False,
                      wait_edges: bool = False) -> KernelCase:
    """The absorbed latent kernel over ``rows`` single-query rows with
    shuffled block tables: ragged lengths with an EMPTY row (length 0, a
    table of zeros) among them, or lengths on both sides of a tile's edge
    over a pool whose every block no row holds is NaN; ``one_seq``:
    consecutive queries of one sequence, a query a row. ``wait_edges``,
    over the same NaN pool, is what the ORDER of a tile's copies and waits
    can get wrong, a row a length (``rows`` is not read): an empty row
    FOLLOWED by a full one (the prefetch across rows), a last tile that
    holds exactly one block, both sides of every edge between the groups a
    tile is waited for in, the same a tile on, and rows of exactly two and
    three whole tiles."""
    from .mla import latent_gather_attention
    from .pallas.mla_paged_attention import (
        mla_paged_decode,
        mla_tile_tokens,
        mla_wait_tokens,
    )

    L = blocks_per_seq * block_size
    t = mla_tile_tokens(block_size)
    if one_seq:
        mid = t if tile_edges else L // 2 + 5
        lens = [mid - rows // 2 + 1 + i for i in range(rows)]
    elif wait_edges:
        w = mla_wait_tokens(block_size)
        lens = [0, L, t + 1, t + block_size]
        for edge in range(w, t, w):
            lens += [edge - 1, edge, edge + 1]
        lens += [t + w + 1, 2 * t, 3 * t]
        rows = len(lens)
    elif tile_edges:
        lens = [t - 1, t, t + 1, 2 * t, 2 * t + 1, 1, block_size + 3, L]
    else:
        lens = [0, 1, block_size + 3, L // 2 + 5, L]
    lens = (lens * -(-rows // len(lens)))[:rows]
    lens = [min(max(n, 0), L) for n in lens]
    n_blocks = rows * blocks_per_seq + 1

    def make(key):
        kq, kc, kt = jax.random.split(key, 3)
        c = jax.random.normal(kc, (n_blocks, block_size, width), jnp.float32)
        tables = 1 + jax.random.permutation(kt, n_blocks - 1).reshape(
            rows, blocks_per_seq).astype(jnp.int32)
        if one_seq:
            tables = jnp.repeat(tables[:1], rows, axis=0)
        n = jnp.asarray(lens, jnp.int32)
        tables = jnp.where((n > 0)[:, None], tables, 0)   # an empty slot
        if tile_edges or wait_edges:
            held = (jnp.arange(blocks_per_seq)[None, :] * block_size
                    < n[:, None])
            owned = jnp.zeros((n_blocks,), bool).at[0].set(True).at[
                jnp.where(held, tables, 0).ravel()].set(True)
            c = jnp.where(owned[:, None, None], c, jnp.nan)
        q = jax.random.normal(kq, (rows, H, width), jnp.bfloat16)
        return q, c.astype(jnp.bfloat16), tables, n

    live = np.asarray(lens) > 0

    def kernel(q, c, tables, n, interpret):
        u = mla_paged_decode(q, c, tables, n, rank=rank, scale=scale,
                             interpret=interpret)
        # an empty row's output is finite and otherwise anyone's
        return jnp.where(live[:, None, None], u, jnp.where(
            jnp.isfinite(u.astype(jnp.float32)), 0, jnp.nan).astype(u.dtype))

    def oracle(q, c, tables, n):
        u = latent_gather_attention(
            q[:, None], jnp.nan_to_num(c), tables, (n - 1)[:, None],
            rank=rank, scale=scale)[:, 0]
        return jnp.where(live[:, None, None], u, 0)

    return KernelCase(
        name=(f"mla-H{H}-w{width}r{rank}-bs{block_size}-M{blocks_per_seq}"
              f"-b{rows}{'-edges' if tile_edges else ''}"
              f"{'-waits' if wait_edges else ''}"
              f"{'-oneseq' if one_seq else ''}"),
        make_inputs=make, kernel=kernel, oracle=oracle, tol=TOL_BF16)


def latent_cases(n_heads: int, head_dim: int, v_head_dim: int, width: int,
                 rank: int, *, block_size: int = 16,
                 buckets: Sequence[int] = (1024, 2048),
                 max_model_len: int = 10240,
                 max_num_seqs: int = 8) -> List[KernelCase]:
    """The kernel calls an engine with a LATENT cache dispatches: flash
    over keys of ``head_dim`` beside values of ``v_head_dim`` at each
    prefill bucket and at the last continuation start, and the absorbed
    kernel over the full block table: ragged rows with an empty one, the
    tile's edges and the wait groups' edges over a NaN-poisoned pool, and
    one sequence's consecutive queries."""
    M = max_model_len // block_size
    top = max(buckets)
    scale = head_dim ** -0.5
    cases = [_latent_flash_case(n_heads, head_dim, v_head_dim, b, b)
             for b in sorted(buckets)]
    last = (max_model_len // top - 1) * top
    if last >= top:
        cases.append(_latent_flash_case(n_heads, head_dim, v_head_dim, top,
                                        last + top))
    cases.append(_latent_pool_case(n_heads, width, rank, scale, block_size,
                                   M, max_num_seqs))
    cases.append(_latent_pool_case(n_heads, width, rank, scale, block_size,
                                   M, 8, tile_edges=True))
    cases.append(_latent_pool_case(n_heads, width, rank, scale, block_size,
                                   M, 8, tile_edges=True, one_seq=True))
    cases.append(_latent_pool_case(n_heads, width, rank, scale, block_size,
                                   M, 0, wait_edges=True))
    return cases


#: the streamed and the tiled expert product against the grouped form. Rows
#: are unit normal and leaves N(0, 0.02) at widths in the thousands, so a
#: row's weighted sum over its experts stays under 1: the bound is two bf16
#: ulps there. The grouped form rounds ``g``, ``u``, ``silu(g) * u`` and each
#: expert's result to bf16, the streamed kernel only the weighted ``h``, the
#: tiled one ``h`` and each expert's result.
TOL_EXPERTS = 2 * 2.0 ** -8


#: the rows' deviation an expert case draws, by what an expert is
_X_STD = {"silu": 1.0, "relu2": 0.5}


def _expert_leaves(act: str, gate, up, down) -> Dict:
    """The stacked expert leaves as the layer holds them: an ungated
    (``relu2``) expert has no ``gate`` (the drawn one is dropped)."""
    if act == "relu2":
        return {"up": jnp.swapaxes(up, 1, 2), "down": down}
    return {"gate": gate, "up": up, "down": down}


def _expert_inputs(key, *, n_experts: int, held: int, top_k: int, D: int,
                   F: int, rows: int, x_std: float = 1.0):
    """``(x, sel, w, gate, up, down)``: ``rows`` rows of ``top_k`` distinct
    experts of ``n_experts`` each (a shared popularity plus each row's own
    noise: top-k of it, so some experts hold several rows and some none),
    the last row inactive, and the leaves of ``held`` experts. ``x_std``:
    the rows' deviation (``relu(u) ** 2`` grows with its square: at half,
    a row's weighted sum stays under 1 as the gated form's does at 1)."""
    kx, ks, kw, kg, ku, kd = jax.random.split(key, 6)
    leaf = lambda k, s: (jax.random.normal(k, s, jnp.float32)       # noqa: E731
                         * 0.02).astype(jnp.bfloat16)
    logits = (jax.random.normal(ks, (n_experts,))
              + jax.random.gumbel(kw, (rows, n_experts)))
    _, sel = jax.lax.top_k(logits, top_k)
    sel = sel.astype(jnp.int32).at[rows - 1].set(n_experts)
    w = jnp.full((rows, top_k), 1.0 / top_k, jnp.float32)
    x = jax.random.normal(kx, (rows, D), jnp.bfloat16)
    if x_std != 1.0:
        x = (x.astype(jnp.float32) * x_std).astype(jnp.bfloat16)
    return (x, sel, w, leaf(kg, (held, D, F)), leaf(ku, (held, D, F)),
            leaf(kd, (held, F, D)))


def _expert_case(n_experts: int, top_k: int, D: int, F: int,
                 rows: int, act: str = "silu") -> KernelCase:
    """The streamed expert product (``moe_grouped_ffn_streamed``) at one
    decode bucket: ``rows`` rows of ``top_k`` distinct experts each, drawn
    so that some experts hold several rows and some none; the last row is
    inactive (it chose no expert)."""
    from . import moe
    from .pallas.moe_ffn import moe_streamed_ffn

    make = functools.partial(_expert_inputs, n_experts=n_experts,
                             held=n_experts, top_k=top_k, D=D, F=F, rows=rows,
                             x_std=_X_STD[act])

    def sizes(sel):
        return moe.expert_counts(sel, n_experts)

    def kernel(x, sel, w, gate, up, down, interpret):
        ex = _expert_leaves(act, gate, up, down)
        return moe_streamed_ffn(
            x, *moe.streamed_operands(sel, w, sizes(sel), 0),
            ex.get("gate"), ex["up"], down, interpret=interpret, act=act)

    def oracle(x, sel, w, gate, up, down):
        return moe._grouped(_expert_leaves(act, gate, up, down), x, sel,
                            w, sizes(sel), 0, act)

    tag = "" if act == "silu" else f"-{act}"
    return KernelCase(
        name=f"experts-E{n_experts}k{top_k}-D{D}-F{F}-b{rows}{tag}",
        make_inputs=make, kernel=kernel, oracle=oracle, tol=TOL_EXPERTS)


def _tiled_expert_case(n_experts: int, top_k: int, D: int, F: int,
                       rows: int, held: int,
                       act: str = "silu") -> KernelCase:
    """The tiled expert product (``moe_grouped_ffn_tiled``) at one prefill
    bucket: ``rows`` tokens of ``top_k`` distinct experts of ``n_experts``
    each, a shared popularity making the groups uneven, the LAST ``held``
    experts held here (fewer than all: assignments held elsewhere), the
    last row inactive."""
    from . import moe

    first = n_experts - held

    make = functools.partial(_expert_inputs, n_experts=n_experts, held=held,
                             top_k=top_k, D=D, F=F, rows=rows,
                             x_std=_X_STD[act])

    def product(form):
        def run(x, sel, w, gate, up, down, **interpret):
            sizes = moe.expert_counts(sel, n_experts)[first:]
            return form(_expert_leaves(act, gate, up, down), x, sel, w,
                        sizes, first, act, **interpret)
        return run

    tag = "" if act == "silu" else f"-{act}"
    return KernelCase(
        name=(f"experts-tiled-E{held}of{n_experts}k{top_k}-D{D}-F{F}"
              f"-b{rows}{tag}"),
        make_inputs=make, kernel=product(moe._tiled),
        oracle=product(moe._grouped), tol=TOL_EXPERTS)


def expert_cases(n_experts: int, top_k: int, D: int, F: int, *,
                 max_num_seqs: int = 8, prefill_rows: int = 0,
                 held: int = 0, act: str = "silu") -> List[KernelCase]:
    """The streamed expert product at an engine's largest decode bucket and
    at one small one (rows the kernel pads to a tile of sublanes); with
    ``prefill_rows``, the tiled one at that bucket, ``held`` of the experts
    on this chip (default all). ``act``: what an expert is
    (``ops.pallas.moe_ffn.activation``)."""
    cases = [_expert_case(n_experts, top_k, D, F, rows, act)
             for rows in sorted({max_num_seqs, min(max_num_seqs, 8)})]
    if prefill_rows:
        cases.append(_tiled_expert_case(n_experts, top_k, D, F,
                                        prefill_rows, held or n_experts,
                                        act))
    return cases


#: the KDA kernels against the token-by-token recurrence, float32 on both
#: sides: what is left is the order of the sums (the chunk's triangular
#: solve against one step a token), well under 1e-4 of outputs near 1
TOL_KDA = 2e-4


def _kda_operands(key, shape_bthd):
    """Seeded operands of the recurrence at ``[B, T, H, d]``: L2-normed
    ``q`` (scaled) and ``k``, unit ``v``, a per-channel log-decay drawn as
    the public initialisation draws it (``A`` in U(1, 16) a head, ``dt``
    log-uniform in 0.001-0.1), ``beta`` in (0, 1)."""
    from . import kda

    B, T, H, d = shape_bthd
    kq, kk, kv, ka, kd, kb = jax.random.split(key, 6)
    q = kda._l2norm(jax.random.normal(kq, shape_bthd)) * d ** -0.5
    k = kda._l2norm(jax.random.normal(kk, shape_bthd))
    v = jax.random.normal(kv, shape_bthd)
    A = jax.random.uniform(ka, (H, 1), minval=1.0, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(kd, shape_bthd, minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    beta = jax.nn.sigmoid(jax.random.normal(kb, (B, T, H)))
    return q, k, v, -A * dt, beta


def _kda_chunk_case(H: int, d: int, T: int, rows: int) -> KernelCase:
    """The chunk kernel over ``T`` tokens from a state that is not zero (a
    continuation chunk), final state and outputs side by side."""
    from . import kda
    from .pallas.kda_chunk import kda_chunk_prefill

    def make(key):
        k0, k1 = jax.random.split(key)
        return _kda_operands(k0, (rows, T, H, d)) + (
            jax.random.normal(k1, (rows, H, d, d)),)

    def flat(o, s):
        return jnp.concatenate([o.reshape(-1), s.reshape(-1)])

    return KernelCase(
        name=f"kda-chunk-H{H}x{d}-T{T}-b{rows}", make_inputs=make,
        kernel=lambda *a, interpret: flat(
            *kda_chunk_prefill(*a, interpret=interpret)),
        oracle=lambda *a: flat(*kda.recurrence(*a)), tol=TOL_KDA)


def kda_state_bf16_err(case: KernelCase, key=None) -> float:
    """The precision control of a chunk case: the largest difference of the
    recurrence with its STATE rounded to bfloat16 after every token from the
    float32 recurrence, on the case's operands. ``case.tol`` has to refuse
    it: the logits of five layers under bfloat16 activations cannot tell
    the two apart (``benchmark/reference/tolerance.kimi_linear.json``), the
    final state and the outputs of one layer's scan can."""
    from . import kda

    q, k, v, g, beta, s0 = jax.jit(case.make_inputs)(
        jax.random.PRNGKey(0) if key is None else key)
    t_first = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)  # noqa: E731

    def one(s, x):
        o, s = kda.step(*x, s)
        return jax.lax.reduce_precision(s, exponent_bits=8,
                                        mantissa_bits=7), o

    s, o = jax.jit(lambda *a: jax.lax.scan(one, a[5], tuple(
        t_first(x) for x in a[:5])))(q, k, v, g, beta, s0)
    got = jnp.concatenate([jnp.moveaxis(o, 0, 1).reshape(-1), s.reshape(-1)])
    return float(jnp.max(jnp.abs(got - case.oracle(q, k, v, g, beta, s0))))


def _kda_step_case(H: int, d: int, rows: int, slots: int) -> KernelCase:
    """The step kernel for ``rows`` rows over an arena of ``slots`` and
    the null slot: the rows' slots are a permutation's head, the last two
    rows padded (the null slot twice); the arena comes back whole, so a
    write to a slot no row names shows."""
    from . import kda
    from .pallas.kda_step import kda_decode_step

    def make(key):
        k0, k1, k2 = jax.random.split(key, 3)
        q, k, v, g, beta = (a[:, 0] for a in _kda_operands(
            k0, (rows, 1, H, d)))
        ids = jax.random.permutation(k2, slots)[:rows].astype(jnp.int32)
        ids = jnp.where(jnp.arange(rows) >= rows - 2, slots, ids)
        return q, k, v, g, beta, jax.random.normal(
            k1, (slots + 1, H, d, d)), ids

    def keep_null(o, arena):
        # what lands in the null slot is nobody's: compare the rest
        return jnp.concatenate([o[:-2].reshape(-1), arena[:-1].reshape(-1)])

    def oracle(q, k, v, g, beta, arena, ids):
        return keep_null(*kda.step_slots(q, k, v, g, beta, arena, ids,
                                         kernel=False))

    return KernelCase(
        name=f"kda-step-H{H}x{d}-b{rows}-S{slots}", make_inputs=make,
        kernel=lambda *a, interpret: keep_null(
            *kda_decode_step(*a, interpret=interpret)),
        oracle=oracle, tol=TOL_KDA)


def kda_cases(n_heads: int, head_dim: int, *, bucket: int = 2048,
              max_num_seqs: int = 16) -> List[KernelCase]:
    """The kernel calls an engine with KDA layers dispatches: the chunk
    kernel over a prefill bucket (one row), and the step kernel at the
    largest decode bucket and at a small one."""
    return [_kda_chunk_case(n_heads, head_dim, bucket, 1)] + [
        _kda_step_case(n_heads, head_dim, rows, max_num_seqs)
        for rows in sorted({max_num_seqs, min(max_num_seqs, 4)})]


#: the state-space kernels against the token-by-token recurrence, float32
#: on both sides: what is left is the order of the sums (a chunk's decay
#: matrix against one step a token), relative to outputs of some tens
TOL_SSM = 2e-3


def _ssm_operands(key, B: int, T: int, H: int, P: int, N: int, G: int):
    """Seeded operands of the recurrence: unit ``x``, ``B`` and ``C``, a
    step size log-uniform in 0.001-0.1 and ``A`` = 1 .. heads, as the
    public initialisation draws them."""
    kx, kb, kc, kd = jax.random.split(key, 4)
    dt = jnp.exp(jax.random.uniform(kd, (B, T, H), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    return (jax.random.normal(kx, (B, T, H, P)),
            jax.random.normal(kb, (B, T, G, N)),
            jax.random.normal(kc, (B, T, G, N)), dt,
            -jnp.arange(1, H + 1, dtype=jnp.float32) * dt)


def _ssm_chunk_case(H: int, P: int, N: int, G: int, T: int,
                    rows: int) -> KernelCase:
    """The chunk kernel over ``T`` tokens from a state that is not zero (a
    continuation chunk), final state and outputs side by side."""
    from . import ssm
    from .pallas.ssm_chunk import ssm_chunk_prefill

    def make(key):
        k0, k1 = jax.random.split(key)
        return _ssm_operands(k0, rows, T, H, P, N, G) + (
            jax.random.normal(k1, (rows, H, P, N)),)

    def flat(y, s):
        return jnp.concatenate([y.reshape(-1), s.reshape(-1)])

    return KernelCase(
        name=f"ssm-chunk-H{H}x{P}x{N}-T{T}-b{rows}", make_inputs=make,
        kernel=lambda *a, interpret: flat(
            *ssm_chunk_prefill(*a, interpret=interpret)),
        oracle=lambda *a: flat(*ssm.recurrence(*a)), tol=TOL_SSM)


def _ssm_step_case(H: int, P: int, N: int, G: int, rows: int,
                   slots: int, padded: int = 2) -> KernelCase:
    """The step kernel for ``rows`` rows over an arena of ``slots`` and the
    null slot, as ``_kda_step_case``: the rows' slots are a permutation's
    head (neither sorted nor adjacent), the last ``padded`` rows carry the
    null slot; the arena comes back whole, so a write to a slot no row
    names shows."""
    from . import ssm
    from .pallas.ssm_step import ssm_decode_step

    live = rows - padded

    def make(key):
        k0, k1, k2 = jax.random.split(key, 3)
        ops = tuple(a[:, 0] for a in _ssm_operands(k0, rows, 1, H, P, N, G))
        ids = jax.random.permutation(k2, slots)[:rows].astype(jnp.int32)
        ids = jnp.where(jnp.arange(rows) >= live, slots, ids)
        return ops + (jax.random.normal(k1, (slots + 1, H, P, N)), ids)

    def keep_null(y, arena):
        return jnp.concatenate([y[:live].reshape(-1),
                                arena[:-1].reshape(-1)])

    return KernelCase(
        name=(f"ssm-step-H{H}x{P}x{N}-b{rows}-S{slots}"
              + ("" if padded == 2 else f"-pad{padded}")),
        make_inputs=make,
        kernel=lambda *a, interpret: keep_null(
            *ssm_decode_step(*a, interpret=interpret)),
        oracle=lambda *a: keep_null(*ssm.step_slots(*a, kernel=False)),
        tol=TOL_SSM)


def ssm_cases(heads: int, head_dim: int, state: int, groups: int, *,
              bucket: int = 512, prefill_rows: int = 1,
              max_num_seqs: int = 16) -> List[KernelCase]:
    """The kernel calls an engine with state-space mixers dispatches: the
    chunk kernel over a prefill bucket, and the step kernel at the largest
    decode bucket and at a small one (the last two rows padded), at the
    bucket of ONE row (a grid of one step, nothing padded) and at the
    largest with half its rows padded (the null slot again and again)."""
    step = functools.partial(_ssm_step_case, heads, head_dim, state, groups,
                             slots=max_num_seqs)
    steps = {c.name: c for c in [
        step(rows) for rows in sorted({max_num_seqs, min(max_num_seqs, 4)})
    ] + [step(1, padded=0), step(max_num_seqs, padded=max_num_seqs // 2)]}
    return [_ssm_chunk_case(heads, head_dim, state, groups, bucket,
                            prefill_rows)] + list(steps.values())
