"""The routed experts' product for a call with FEW ROWS, one Pallas kernel
that streams each touched expert's three matrices out of HBM once.

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
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: the kernel's name in a device trace; begins with ``ops.moe.GROUPED_NAME``
#: so that a metric reading the whole expert product finds both forms
KERNEL_NAME = "moe_grouped_ffn_streamed"

#: bytes the double-buffered weight tiles of one grid step may hold; a whole
#: expert of either served model (9.4 MB, 12.6 MB) fits, so a step is an
#: expert and the fixed part of a step is paid ``touched`` times a layer
_TILE_BUDGET_BYTES = 32 * 2 ** 20
#: what the kernel holds beside the weight tiles: x, c, the f32 sum (twice:
#: the resident block and the product added to it), g, u and h of 128 rows
_RESIDENT_BYTES = 8 * 2 ** 20


def inner_tile(D: int, F: int, itemsize: int) -> int:
    """Columns of ``F`` one grid step takes: all of them where two copies of
    an expert's three ``[D, F]`` matrices fit the budget, else the largest
    multiple of 128 dividing ``F`` that does (at least 128)."""
    fits = lambda tf: 2 * 3 * D * tf * itemsize <= _TILE_BUDGET_BYTES  # noqa: E731
    if fits(F) or F % 128:
        return F
    tiles = [tf for tf in range(128, F, 128) if F % tf == 0 and fits(tf)]
    return max(tiles, default=128)


def _ffn_kernel(ids_ref, n_ref, x_ref, c_ref, g_ref, u_ref, d_ref, o_ref):
    # ids_ref [steps], n_ref [1] SMEM; x_ref [N, D]; c_ref [N, E'] f32;
    # g_ref, u_ref [D, tf]; d_ref [tf, D]; o_ref [N, D] f32, resident
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _expert_tile():
        x = x_ref[...]
        g = jnp.dot(x, g_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)
        # this expert's column of the combine matrix: a masked lane sum
        # (a dynamic lane slice would want an aligned start)
        c = c_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1)
        w = jnp.sum(jnp.where(lane == ids_ref[i], c, 0.0), axis=1,
                    keepdims=True)                              # [N, 1]
        h = (jax.nn.silu(g) * u * w).astype(d_ref.dtype)
        o_ref[...] += jnp.dot(h, d_ref[...],
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile_f", "interpret"))
def moe_streamed_ffn(
    x: jax.Array,          # [N, D] the rows, in the experts' operand type
    combine: jax.Array,    # [N, E'] float32: a row's weight on each expert
    ids: jax.Array,        # [steps] int32 touched experts, ascending, then
                           # the last touched one repeated
    n_touched: jax.Array,  # [] or [1] int32: how many of ``ids`` are real
    gate: jax.Array,       # [E', D, F]
    up: jax.Array,         # [E', D, F]
    down: jax.Array,       # [E', F, D]
    *,
    tile_f: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``[N, D]`` float32: every row through every touched expert, weighted
    by ``combine``. ``E'`` is the experts the leaves stack (the held slice);
    ``steps`` bounds the walk (``min(E', N * k)`` covers any routing)."""
    from jax.experimental.pallas import tpu as pltpu

    N, D = x.shape
    _E, _, F = gate.shape
    if interpret is None:
        from ..attention import on_tpu_platform

        interpret = not on_tpu_platform()
    tf = tile_f or inner_tile(D, F, gate.dtype.itemsize)
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
    out = pl.pallas_call(
        _ffn_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(steps, nj),
            in_specs=[
                pl.BlockSpec((rows, D), whole),
                pl.BlockSpec(combine.shape, whole),
                pl.BlockSpec((None, D, tf), lambda i, j, ids_ref, n_ref: (
                    ids_ref[i], 0, tile(i, j, ids_ref, n_ref))),
                pl.BlockSpec((None, D, tf), lambda i, j, ids_ref, n_ref: (
                    ids_ref[i], 0, tile(i, j, ids_ref, n_ref))),
                pl.BlockSpec((None, tf, D), lambda i, j, ids_ref, n_ref: (
                    ids_ref[i], tile(i, j, ids_ref, n_ref), 0)),
            ],
            out_specs=pl.BlockSpec((rows, D), whole),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, D), jnp.float32),
        # every step adds into the one resident block: nothing is parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=(2 * 3 * D * tf * gate.dtype.itemsize
                              + _RESIDENT_BYTES)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(ids.astype(jnp.int32), n_touched.astype(jnp.int32).reshape(1),
      x, combine.astype(jnp.float32), gate, up, down)
    return out[:N]
