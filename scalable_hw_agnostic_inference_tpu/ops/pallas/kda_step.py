"""KDA's decode step as a Pallas kernel: one token for ``B`` rows, each on
its own slot of the state arena, in place.

The arena ``[S, H, d_v, d_k]`` float32 (transposed states; the last slot is
the null slot) is an input aliased to the output: a grid step ``(row, head
block)`` reads the ``HEADS`` states of ``slots[row]`` (scalar prefetch picks
the block), decays their lanes, applies the delta rule's rank-one
correction and writes them back to where they came from. A padded or
finished row carries the null slot, so no real slot is touched for it. The
step is bound by the arena's bytes: a row reads and writes ``H * d * d * 4``
bytes a layer (2 MiB at 32 heads of 128) and computes 0.4 MFLOP on them,
all on the vector unit (the state never meets the MXU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: the kernel's name in a device trace
KERNEL_NAME = "kda_decode_step"
#: heads a grid step takes (8 x 64 KiB in, the same out, double buffered)
HEADS = 8


def _kernel(slots_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, o_ref,
            so_ref):
    # q, k, v, g, b refs [hb, d] float32 (b: beta on every lane); s_ref and
    # so_ref [hb, d_v, d_k]; o_ref [hb, d_v]
    hb, d = q_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
    eye = row == col

    def as_col(r):        # [1, d] -> [d, 1]
        return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)

    def as_row(c):        # [d, 1] -> [1, d]
        return jnp.sum(jnp.where(eye, c, 0.0), axis=0, keepdims=True)

    for h in range(hb):
        k = k_ref[h:h + 1]
        s = s_ref[h] * jnp.exp(g_ref[h:h + 1])
        pred = jnp.sum(s * k, axis=1, keepdims=True)          # S'^T k
        u = as_col(b_ref[h:h + 1]) * (as_col(v_ref[h:h + 1]) - pred)
        s = s + u * k
        so_ref[h] = s
        o_ref[h:h + 1] = as_row(
            jnp.sum(s * q_ref[h:h + 1], axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_step(q, k, v, g, beta, arena, slots, *,
                    interpret: Optional[bool] = None):
    """``q, k, v, g`` ``[B, H, d]``, ``beta`` ``[B, H]``, ``arena``
    ``[S, H, d, d]`` float32 (aliased to the result: in place where the
    caller donates it), ``slots`` ``[B]``
    int32. Returns ``(o [B, H, d] float32, arena)``."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, d = q.shape
    if interpret is None:
        from ..attention import on_tpu_platform

        interpret = not on_tpu_platform()
    hb = HEADS if H % HEADS == 0 else H
    f32 = lambda a: a.astype(jnp.float32)                     # noqa: E731
    vec = pl.BlockSpec((None, hb, d), lambda i, j, slots_ref: (i, j, 0))
    state = pl.BlockSpec((None, hb, d, d),
                         lambda i, j, slots_ref: (slots_ref[i], j, 0, 0))
    o, arena = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hb),
            in_specs=[vec] * 5 + [state],
            out_specs=[vec, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, d), jnp.float32),
                   jax.ShapeDtypeStruct(arena.shape, jnp.float32)],
        # the arena (operand 6, the scalar prefetch counted) is output 1
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(slots.astype(jnp.int32), f32(q), f32(k), f32(v), f32(g),
      jnp.broadcast_to(f32(beta)[..., None], (B, H, d)), arena)
    return o, arena
