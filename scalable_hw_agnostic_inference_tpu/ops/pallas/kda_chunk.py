"""KDA's chunked prefill as a Pallas kernel: ``ops.kda.chunk_math`` a grid
step for a GROUP of heads, their states carried in VMEM from chunk to chunk.

The grid is ``(rows, heads / Hg, chunks)``, ``Hg = min(H, HEAD_GROUP)``; the
chunk axis is sequential and the group's transposed states ``[Hg, d_v,
d_k]`` float32 stay in a VMEM scratch across it: read from ``s0`` at the
group's first chunk, written to the output state at its last. One head's
chunk is a CHAIN of dependent products (the cumulative decay, the ``A``
rows, the Neumann inverse, ``u``, the outputs and the state), each of
16-128 streamed rows: alone in a grid step it leaves the four MXUs waiting
for the product before (a critical path of 3,775 cycles for 888 eight-row
pushes, by the compiler's own schedule). ``Hg`` chains side by side in one
basic block interleave: from four heads on the step is bound by the MXUs'
888 x 8 / 4 cycles a head and no longer by the chain.

Operands are taken AS THE LAYER HAS THEM, ``[B, T, H, d]`` float32: a block
is ``(CHUNK, Hg, d)``, whose last two dims are whole ``(8, 128)`` tiles when
``Hg`` is 8 (or all of fewer heads), and head ``j``'s ``[CHUNK, d]`` is the
strided read ``ref[:, j, :]``. Nothing is transposed or copied around the
kernel where ``T`` is whole chunks and ``H`` whole groups, as in the served
programs (the head-major form wrote seven float32 arrays of the operands'
size a layer), and ``beta`` enters as a ``[CHUNK, Hg]`` block of columns:
``kb = beta k`` and ``vb = beta v`` are formed here. The body is
``chunk_math`` itself: the plain chunked form (``ops.kda.chunked``) and this
kernel cannot drift apart.

Cost a chunk and head (``C = 64``, ``d = 128``): 19 matrix products, 16.8
MFLOP of float32 products in 888 eight-row pushes (six bfloat16 passes each
but the mask product's three), of which the recurrence itself is ``6 d^2`` a
token, 6.3 MFLOP: the chunk's triangular solve and its decay bookkeeping
are the rest.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..kda import CHUNK, chunk_math

#: the kernel's name in a device trace
KERNEL_NAME = "kda_chunk_prefill"
#: heads a grid step takes: ONE sublane tile of the layer's layout (a block's
#: second-minor dimension is the heads, so a group is a whole tile of 8 or
#: the whole dimension); fewer heads than 8 go all in one step, a count above
#: 8 that 8 does not divide is padded to whole groups with identity heads.
#: Not a tunable on this layout: 16 heads of 128 a step are refused for scoped
#: VMEM, and ``scripts/kda_bench.py`` times 1, 2, 4 and 8 on a head-major twin
HEAD_GROUP = 8


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, s_ref,
            st_ref):
    c = pl.program_id(2)
    hg, d = st_ref.shape[0], st_ref.shape[-1]

    @pl.when(c == 0)
    def _enter():
        st_ref[...] = s0_ref[...]

    def heads(ref):                     # [CHUNK, hg, d] -> [hg, CHUNK, d]
        return jnp.stack([ref[:, j, :] for j in range(hg)])

    beta = jnp.stack([beta_ref[:, j:j + 1] for j in range(hg)])
    k = heads(k_ref)
    o, st = chunk_math(heads(q_ref), k, k * beta, heads(v_ref) * beta,
                       heads(g_ref), st_ref[...])
    for j in range(hg):
        o_ref[:, j, :] = o[j]
    st_ref[...] = st

    @pl.when(c == pl.num_programs(2) - 1)
    def _leave():
        s_ref[...] = st


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_chunk_prefill(q, k, v, g, beta, s0=None, *,
                      interpret: Optional[bool] = None):
    """``ops.kda.recurrence``'s function and signature: ``q, k, v, g``
    ``[B, T, H, d]``, ``beta`` ``[B, T, H]``, ``s0`` ``[B, H, d, d]``
    transposed (``None``: zeros) -> ``(o [B, T, H, d], s_T)`` float32."""
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, d = q.shape
    if interpret is None:
        from ..attention import on_tpu_platform

        interpret = not on_tpu_platform()
    if s0 is None:
        s0 = jnp.zeros((B, H, d, d), jnp.float32)
    pad = -T % CHUNK
    n_chunks = (T + pad) // CHUNK
    hg = min(H, HEAD_GROUP)
    # heads that fill the last group: zeros everywhere (``beta = 0`` and
    # ``g = 0`` are the identity), cut off the results again
    more = -H % hg
    n_groups = (H + more) // hg

    def whole_blocks(a):                        # [B, T + pad, H + more, d]
        return jnp.pad(a.astype(jnp.float32),
                       ((0, 0), (0, pad), (0, more), (0, 0)))

    # beta by group, a column a head: [B, groups, T + pad, hg]
    b = jnp.pad(beta.astype(jnp.float32), ((0, 0), (0, pad), (0, more)))
    b = jnp.moveaxis(b.reshape(B, T + pad, n_groups, hg), 2, 1)
    s0 = jnp.pad(s0.astype(jnp.float32),
                 ((0, 0), (0, more), (0, 0), (0, 0)))
    tok = pl.BlockSpec((None, CHUNK, hg, d), lambda i, h, c: (i, c, h, 0))
    col = pl.BlockSpec((None, None, CHUNK, hg), lambda i, h, c: (i, h, c, 0))
    state = pl.BlockSpec((None, hg, d, d), lambda i, h, c: (i, h, 0, 0))
    o, s = pl.pallas_call(
        _kernel,
        grid=(B, n_groups, n_chunks),
        in_specs=[tok] * 4 + [col, state],
        out_specs=[tok, state],
        out_shape=[jax.ShapeDtypeStruct((B, T + pad, H + more, d),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((B, H + more, d, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hg, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(whole_blocks(q), whole_blocks(k), whole_blocks(v), whole_blocks(g), b,
      s0)
    return o[:, :T, :H], s[:, :H]
