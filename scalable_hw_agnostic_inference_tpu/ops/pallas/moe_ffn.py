"""The routed experts' product as ONE Pallas kernel that takes each expert's
three matrices out of HBM once a call: ``moe_streamed_ffn`` for a call with
FEW ROWS (a decode step), and its sibling ``moe_tiled_ffn`` for a call with
many (a prefill chunk), whose rows are grouped by expert first.

**Streamed.**

A decode step hands the expert layer a few dozen rows and ``k`` choices a
row: two or three rows an expert. At that size the layer costs the BYTES of
the experts touched and nothing else, so the kernel does not sort, gather,
group or scatter anything: it pushes ALL ``N`` rows through every touched
expert and weights the result by a dense combine matrix ``c`` ``[N, E]``
that is 0 where a row did not choose the expert:

    y = sum over touched e of ((silu(x @ gate[e]) * (x @ up[e])) * c[:, e])
        @ down[e]

The grid walks the touched experts (``ids``, ascending, by scalar prefetch;
behind the ``n`` touched ones the list repeats the last id, so the
pipeline fetches nothing new there, and the step is skipped) and, inside
an expert, tiles of its inner width ``F``. A step's ``gate``/``up``/
``down`` tiles arrive under the pipeline's own double buffering, the next
step's in flight while this one computes; ``x``, ``c`` and the float32
``[N, D]`` sum stay resident. Operands keep the leaves' type (bfloat16 in
every served configuration), products accumulate in float32, ``g``, ``u``
and ``silu(g) * u`` are NOT rounded in between (the grouped form rounds
each to the operand type); the weighted ``h`` is rounded once, to feed the
down product.

Shapes it takes on the chip: ``D`` and ``F`` multiples of 128 (a tile of
lanes); rows are padded here to a multiple of 16. ``ops.moe.expert_form``
sends any other width to the grouped form; the interpreter takes anything.

Measured alone on a v5e, three layers chained in one program
(``scripts/moe_bench.py``; my chip run, PR 33; PERF.md section 6), against
the grouped form on the same assignments: Kanana-2's layer (64 rows, top-6,
113 of 128 x [2048, 768] touched) 2.352 -> 1.425 ms, 91% of its bytes' time
at 819 GB/s; Trinity-Mini's (32 rows, top-8, 65 of 128 x [2048, 1024])
1.650 -> 1.100 ms, 91%; 8 rows (37 touched) 0.685 -> 0.472 ms, 90%. A step
that is a whole expert is the fastest: tiles of 128 to 384 columns read
within 0.5% at 768 columns and 2-10% slower at 1024.

**Tiled.** A prefill or continuation program hands the layer 512-2048
tokens: 32-96 rows an expert. There ``ops.moe.tiled_operands`` groups the
``N x k`` assignments by expert into a layout whose groups each start on a
tile of ``row_tile`` rows (a group is padded to whole tiles), the caller
gathers the rows into it, and the grid walks the ROW TILES: a
scalar-prefetched list names each tile's expert, consecutive tiles of one
expert name the same block, so the pipeline keeps it and an expert's three
matrices leave HBM once a call, the next expert's in flight under the double
buffering (a whole Kimi-Linear expert twice is 28.3 MB of
``_TILE_BUDGET_BYTES``). A step is gate, up, ``silu * up`` and down of one
tile of rows in float32 accumulation, ``h`` rounded once to feed the down
product, the result rounded once to the rows' type as the grouped product
rounds its own (in float32 where tiles of ``F`` add up); the routing weight
and the sum over a token's ``k`` parts are the caller's, in float32, as it
gathers the rows back. The static bound on tiles is ``ceil(N k / tile) +
count`` (``tile_bound``: every assignment could land on the held experts);
the tiles behind the last real one repeat its blocks, so nothing is fetched
or written for them, and are skipped.

Measured alone on a v5e the same way, each of the three layers routing its
own way (my chip run, PR 37; PERF.md section 6), against the ``ragged_dot``
form on the same assignments; ms a layer, whole (placement, gather into the
layout, kernel, gather back with weight and sum) and the kernel alone with
its share of the HELD experts' bytes' time, at row tiles 64 / 128 / 256:

- Kimi-Linear's largest program (2,048 tokens, top-8 of 256, 128 of 2304 x
  1024 held, 64 rows an expert, 133 real tiles of 128 of a bound of 256):
  ``ragged_dot`` form 9.13 (its three calls 7.38); tiled 4.24 / **4.20** /
  4.70, kernel 2.91 / 2.74 (81%) / 2.92;
- Kanana-2's (2,048, top-6, 128 of 2048 x 768, 96 rows an expert): 6.35
  (5.21); tiled **3.12** / 3.41 / 3.67, kernel 2.22 (67%) / 2.09 (71%) /
  2.11;
- Trinity-Mini's (1,024, top-8, 128 of 2048 x 1024, 64 rows an expert, the
  fullest 7.7 times the mean): 6.09 (5.60); tiled **2.94** / 3.22 / 3.52,
  kernel 2.62 (75%) / 2.58 (76%) / 2.62.

The kernel alone is fastest at 128 rows a tile everywhere (at 64 rows an
expert a 128-row tile computes twice the routed FLOPs, 1.2 ms of MXU time
under 2.2 ms of bytes; a 64-row tile doubles the steps), but the LAYER is
fastest at 64 wherever groups are shorter than a tile, because the layout
the rows are gathered into and back from is ``tile_bound`` x tile rows long
whatever lands in it: ``row_tile`` takes 128 where the assignments could
give every held expert a full tile and 64 below. The placement alone is
0.57-0.69 ms a call, the two gathers with it 0.66-1.47 ms of the layer.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: the kernel's name in a device trace; begins with ``ops.moe.GROUPED_NAME``
#: so that a metric reading the whole expert product finds every form
KERNEL_NAME = "moe_grouped_ffn_streamed"

#: bytes the double-buffered weight tiles of one grid step may hold; a whole
#: expert of either served model (9.4 MB, 12.6 MB) fits, so a step is an
#: expert and the fixed part of a step is paid ``touched`` times a layer
_TILE_BUDGET_BYTES = 32 * 2 ** 20
#: what the kernel holds beside the weight tiles: x, c, the f32 sum (twice:
#: the resident block and the product added to it), g, u and h of 128 rows
_RESIDENT_BYTES = 8 * 2 ** 20


def activation(act: str, g, u):
    """What stands between an expert's first product(s) and its down
    product, in float32: ``silu(g) * u`` of a gated expert (three
    matrices), ``relu(u) ** 2`` of an ungated one (two; ``g`` is None)."""
    if act == "relu2":
        assert g is None, "relu2 experts have no gate matrix"
        r = jnp.maximum(u, 0.0)
        return r * r
    assert act == "silu", act
    return jax.nn.silu(g) * u


def first_products(x, gu_refs):
    """``(g or None, u)`` in float32: ``x @ gate`` and ``x @ up`` of a gated
    expert (leaves ``[D, F]``), ``x @ up^T`` alone of an ungated one, whose
    ``up`` is stacked BY ROWS (``[F, D]``, as ``down`` is): the model width
    is then both leaves' minor dimension, whole lane tiles whatever ``F``
    is. (A leaf ``[E, 2688, 1856]`` the TPU stores transposed of its own
    accord, and re-lays it whole before every kernel call that wants it
    otherwise: 0.64 GB copied a routed block a decode step, PERF.md PR 45.)"""
    if len(gu_refs) == 2:
        g, u = (jnp.dot(x, r[...], preferred_element_type=jnp.float32)
                for r in gu_refs)
        return g, u
    (up_ref,) = gu_refs
    return None, jax.lax.dot_general(
        x, up_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def inner_tile(D: int, F: int, itemsize: int, n_mats: int = 3) -> int:
    """Columns of ``F`` one grid step takes: all of them where two copies of
    an expert's ``n_mats`` ``[D, F]`` matrices fit the budget or ``F`` is no
    multiple of 128 (a block must then be the whole width: 1856 = 14 x 128 +
    64 is taken whole, 39.9 MB of two matrices twice, inside the chip's
    VMEM under the raised limit), else the largest multiple of 128 dividing
    ``F`` that fits (at least 128)."""
    fits = lambda tf: (                                       # noqa: E731
        2 * n_mats * D * tf * itemsize <= _TILE_BUDGET_BYTES)
    if fits(F) or F % 128:
        return F
    tiles = [tf for tf in range(128, F, 128) if F % tf == 0 and fits(tf)]
    return max(tiles, default=128)


def _ffn_kernel(ids_ref, n_ref, x_ref, c_ref, *refs, act: str):
    # ids_ref [steps], n_ref [1] SMEM; x_ref [N, D]; c_ref [N, E'] f32;
    # refs: (g_ref,) u_ref [D, tf]; d_ref [tf, D]; o_ref [N, D] f32,
    # resident (an ungated expert has no g_ref)
    *gu_refs, d_ref, o_ref = refs
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _expert_tile():
        x = x_ref[...]
        g, u = first_products(x, gu_refs)
        # this expert's column of the combine matrix: a masked lane sum
        # (a dynamic lane slice would want an aligned start)
        c = c_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
        w = jnp.sum(jnp.where(lane == ids_ref[i], c, 0.0), axis=1,
                    keepdims=True)                              # [N, 1]
        h = (activation(act, g, u) * w).astype(d_ref.dtype)
        o_ref[...] += jnp.dot(h, d_ref[...],
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile_f", "interpret", "act"))
def moe_streamed_ffn(
    x: jax.Array,          # [N, D] the rows, in the experts' operand type
    combine: jax.Array,    # [N, E'] float32: a row's weight on each expert
    ids: jax.Array,        # [steps] int32 touched experts, ascending, then
                           # the last touched one repeated
    n_touched: jax.Array,  # [] or [1] int32: how many of ``ids`` are real
    gate: Optional[jax.Array],  # [E', D, F]; None: an ungated expert
    up: jax.Array,         # [E', D, F]; an ungated expert's: [E', F, D]
    down: jax.Array,       # [E', F, D]
    *,
    tile_f: Optional[int] = None,
    interpret: Optional[bool] = None,
    act: str = "silu",
) -> jax.Array:
    """``[N, D]`` float32: every row through every touched expert, weighted
    by ``combine``. ``E'`` is the experts the leaves stack (the held slice);
    ``steps`` bounds the walk (``min(E', N * k)`` covers any routing).
    ``act`` and whether there is a ``gate`` say what an expert IS
    (:func:`activation`): static, and part of no name."""
    from jax.experimental.pallas import tpu as pltpu

    N, D = x.shape
    _E, F, _ = down.shape
    firsts = [m for m in (gate, up) if m is not None]
    n_mats, itemsize = len(firsts) + 1, up.dtype.itemsize
    if interpret is None:
        from ..attention import on_tpu_platform

        interpret = not on_tpu_platform()
    tf = tile_f or inner_tile(D, F, itemsize, n_mats)
    nj = F // tf
    rows = -(-N // 16) * 16
    if rows != N:
        x = jnp.pad(x, ((0, rows - N), (0, 0)))
        combine = jnp.pad(combine, ((0, rows - N), (0, 0)))
    steps = ids.shape[0]

    def tile(i, j, ids_ref, n_ref):
        # behind the last touched expert: the block it ended on, so the
        # pipeline has nothing to fetch
        return jnp.where(i < n_ref[0], j, nj - 1)

    whole = lambda i, j, *_: (0, 0)                       # noqa: E731
    by_rows = pl.BlockSpec((None, tf, D), lambda i, j, ids_ref, n_ref: (
        ids_ref[i], tile(i, j, ids_ref, n_ref), 0))
    first = by_rows if gate is None else pl.BlockSpec(
        (None, D, tf), lambda i, j, ids_ref, n_ref: (
            ids_ref[i], 0, tile(i, j, ids_ref, n_ref)))
    out = pl.pallas_call(
        functools.partial(_ffn_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(steps, nj),
            in_specs=[
                pl.BlockSpec((rows, D), whole),
                pl.BlockSpec(combine.shape, whole),
                *[first] * len(firsts),
                by_rows,
            ],
            out_specs=pl.BlockSpec((rows, D), whole),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, D), jnp.float32),
        # every step adds into the one resident block: nothing is parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(2 * n_mats * D * tf * itemsize
                              + _RESIDENT_BYTES)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(ids.astype(jnp.int32), n_touched.astype(jnp.int32).reshape(1),
      x, combine.astype(jnp.float32), *firsts, down)
    return out[:N]


#: the tiled kernel's name in a device trace: begins with
#: ``ops.moe.GROUPED_NAME`` (the whole expert product's metrics read it) and
#: does not contain ``KERNEL_NAME`` (the streamed kernel's own roofline
#: does not)
TILED_NAME = "moe_grouped_ffn_tiled"


def row_tile(n_assignments: int, count: int) -> int:
    """Rows of one grid step of the tiled kernel, from the call's static
    shapes (the assignments it groups, the experts it holds): one MXU tile
    of rows where the assignments could give every held expert one, half
    of it below (module docstring: the kernel alone is fastest at 128, the
    layer with its gathers at 64 once groups are shorter than a tile)."""
    return 128 if n_assignments >= 128 * count else 64


def tile_bound(n_assignments: int, count: int, tm: int) -> int:
    """The most row tiles ``n_assignments`` rows in ``count`` groups, each
    padded to a multiple of ``tm``, can fill."""
    return -(-n_assignments // tm) + min(count, n_assignments)


def _tiled_kernel(te_ref, n_ref, x_ref, *refs, act: str):
    # te_ref [tiles], n_ref [1] SMEM; x_ref [tm, D]; refs: (g_ref,) u_ref
    # [D, tf]; d_ref [tf, D]; o_ref [tm, D], resident over j
    *gu_refs, d_ref, o_ref = refs
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _row_tile():
        x = x_ref[...]
        g, u = first_products(x, gu_refs)
        h = activation(act, g, u).astype(d_ref.dtype)
        part = jnp.dot(h, d_ref[...], preferred_element_type=jnp.float32)

        @pl.when(j == 0)
        def _first():
            o_ref[...] = part.astype(o_ref.dtype)

        @pl.when(j > 0)
        def _add():
            o_ref[...] += part.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_f", "interpret", "act"))
def moe_tiled_ffn(
    xs: jax.Array,           # [tiles * tm, D] the rows grouped by expert,
                             # each group starting on a row tile
    tile_expert: jax.Array,  # [tiles] int32: the expert of each row tile,
                             # behind the last real tile that one's again
    n_tiles: jax.Array,      # [] or [1] int32: how many tiles are real
    gate: Optional[jax.Array],  # [E', D, F]; None: an ungated expert
    up: jax.Array,           # [E', D, F]; an ungated expert's: [E', F, D]
    down: jax.Array,         # [E', F, D]
    *,
    tile_f: Optional[int] = None,
    interpret: Optional[bool] = None,
    act: str = "silu",
) -> jax.Array:
    """``[tiles * tm, D]``: each row through its tile's expert, unweighted,
    in the rows' type where a step is a whole expert (rounded once, as the
    grouped product rounds its result) and in float32 where tiles of the
    inner width add up. Rows of the tiles behind ``n_tiles`` are not
    written."""
    from jax.experimental.pallas import tpu as pltpu

    R, D = xs.shape
    _E, F, _ = down.shape
    firsts = [m for m in (gate, up) if m is not None]
    n_mats, itemsize = len(firsts) + 1, up.dtype.itemsize
    tiles = tile_expert.shape[0]
    tm = R // tiles
    if interpret is None:
        from ..attention import on_tpu_platform

        interpret = not on_tpu_platform()
    tf = tile_f or inner_tile(D, F, itemsize, n_mats)
    nj = F // tf
    out_dtype = xs.dtype if nj == 1 else jnp.float32

    def row(i, n_ref):
        # behind the last real tile: the block it ended on (nothing to
        # fetch, nothing new to write back)
        return jnp.maximum(jnp.minimum(i, n_ref[0] - 1), 0)

    def col(i, j, n_ref):
        return jnp.where(i < n_ref[0], j, nj - 1)

    rows = lambda i, j, te_ref, n_ref: (row(i, n_ref), 0)   # noqa: E731
    by_rows = pl.BlockSpec((None, tf, D), lambda i, j, te_ref, n_ref: (
        te_ref[i], col(i, j, n_ref), 0))
    first = by_rows if gate is None else pl.BlockSpec(
        (None, D, tf), lambda i, j, te_ref, n_ref: (
            te_ref[i], 0, col(i, j, n_ref)))
    return pl.pallas_call(
        functools.partial(_tiled_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles, nj),
            in_specs=[
                pl.BlockSpec((tm, D), rows),
                *[first] * len(firsts),
                by_rows,
            ],
            out_specs=pl.BlockSpec((tm, D), rows),
        ),
        out_shape=jax.ShapeDtypeStruct((R, D), out_dtype),
        # a tile's sum over j lives in its resident block; tiles of one
        # expert follow each other, so its weights stay
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(
                2 * n_mats * D * tf * itemsize
                # the rows and the result twice, the product in float32;
                # g, u and h of one tile
                + tm * (D * (2 * itemsize + 3 * 4) + tf * 3 * 4)
                + 4 * 2 ** 20)),
        interpret=interpret,
        name=TILED_NAME,
    )(tile_expert.astype(jnp.int32), n_tiles.astype(jnp.int32).reshape(1),
      xs, *firsts, down)
