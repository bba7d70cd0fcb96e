"""KDA's chunked prefill as a Pallas kernel: ``ops.kda.chunk_math`` a grid
step, the state carried in VMEM from chunk to chunk.

The grid is ``(rows, heads, chunks)``; the chunk axis is sequential and a
head's transposed state ``[d_v, d_k]`` float32 stays in a VMEM scratch
across it: it is read from ``s0`` at the head's first chunk and written to
the output state at its last. Operands arrive head-major ``[B, H, T, d]``
float32 (a block's last two dims are then ``(CHUNK, d)``, whole tiles);
``beta`` is folded into ``k`` and ``v`` outside (``kb``, ``vb``), so no
lane-sparse ``[T]`` vector enters. The body is ``chunk_math`` itself: the
plain chunked form (``ops.kda.chunked``) and this kernel cannot drift apart.

Cost a chunk and head (``C = 64``, ``d = 128``): about 17 MFLOP of float32
products, of which the recurrence itself is ``6 d^2`` a token, 6.3 MFLOP:
the chunk's triangular solve and its decay bookkeeping are the rest.
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


def _kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, s0_ref, o_ref, s_ref,
            st_ref):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _enter():
        st_ref[...] = s0_ref[...]

    o, st = chunk_math(q_ref[...], k_ref[...], kb_ref[...], vb_ref[...],
                       g_ref[...], st_ref[...])
    o_ref[...] = o
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
    b = beta.astype(jnp.float32)[..., None]

    def head_major(a):
        a = jnp.moveaxis(a.astype(jnp.float32), 2, 1)         # [B, H, T, d]
        return jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))

    tok = pl.BlockSpec((None, None, CHUNK, d), lambda i, h, c: (i, h, c, 0))
    state = pl.BlockSpec((None, None, d, d), lambda i, h, c: (i, h, 0, 0))
    o, s = pl.pallas_call(
        _kernel,
        grid=(B, H, n_chunks),
        in_specs=[tok] * 5 + [state],
        out_specs=[tok, state],
        out_shape=[jax.ShapeDtypeStruct((B, H, T + pad, d), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, d, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(head_major(q), head_major(k), head_major(k * b), head_major(v * b),
      head_major(g), s0.astype(jnp.float32))
    return jnp.moveaxis(o[:, :, :T], 1, 2), s
