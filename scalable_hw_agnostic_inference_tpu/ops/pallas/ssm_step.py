"""The state-space mixer's decode step as a Pallas kernel: one token for
``B`` rows, each on its own slot of the state arena, in place.

The arena ``[S, H, P, N]`` float32 (the last slot is the null slot) is an
input aliased to the output: a grid step ``(row, group)`` reads the ``H /
G`` states of the group's heads in ``slots[row]`` (scalar prefetch picks
the block), scales each by its head's decay, adds ``dt x (outer) B``, reads
it against ``C`` and writes it back to where it came from. A padded or
finished row carries the null slot, so no real slot is touched for it. The
step is bound by the arena's bytes: a row reads and writes ``H * P * N * 4``
bytes a layer (2 MiB at 64 heads of 64 x 128) and computes 4 operations a
state element on them, all on the vector unit.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: the kernel's name in a device trace
KERNEL_NAME = "ssm_decode_step"


def _kernel(slots_ref, u_ref, a_ref, b_ref, c_ref, s_ref, o_ref, so_ref):
    # u_ref [hg, P] (dt * x); a_ref [hg, N] (the decay on every lane);
    # b_ref, c_ref [1, N]; s_ref and so_ref [hg, P, N]; o_ref [hg, P]
    hg, P = u_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (P, P), 1)
    eye = row == col

    def as_col(r):        # [1, P] -> [P, 1]
        return jnp.sum(jnp.where(eye, r, 0.0), axis=1, keepdims=True)

    def as_row(c):        # [P, 1] -> [1, P]
        return jnp.sum(jnp.where(eye, c, 0.0), axis=0, keepdims=True)

    b, c = b_ref[...], c_ref[...]
    for h in range(hg):
        s = s_ref[h] * a_ref[h:h + 1] + as_col(u_ref[h:h + 1]) * b
        so_ref[h] = s
        o_ref[h:h + 1] = as_row(jnp.sum(s * c, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode_step(x, Bm, Cm, dt, ld, arena, slots, *,
                    interpret: Optional[bool] = None):
    """``x`` ``[B, H, P]``, ``Bm, Cm`` ``[B, G, N]``, ``dt, ld`` ``[B, H]``
    (``ld`` the log-decay), ``arena`` ``[S, H, P, N]`` float32 (aliased to
    the result: in place where the caller donates it), ``slots`` ``[B]``
    int32. Returns ``(y [B, H, P] float32, arena)``."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, P = x.shape
    G, N = Bm.shape[-2:]
    hg = H // G
    if interpret is None:
        from ..attention import on_tpu_platform

        interpret = not on_tpu_platform()
    f32 = lambda a: a.astype(jnp.float32)                     # noqa: E731
    heads = lambda w: pl.BlockSpec(                           # noqa: E731
        (None, hg, w), lambda i, j, slots_ref: (i, j, 0))
    group = pl.BlockSpec((None, None, 1, N),
                         lambda i, j, slots_ref: (i, j, 0, 0))
    state = pl.BlockSpec((None, hg, P, N),
                         lambda i, j, slots_ref: (slots_ref[i], j, 0, 0))
    y, arena = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, G),
            in_specs=[heads(P), heads(N), group, group, state],
            out_specs=[heads(P), state],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(arena.shape, jnp.float32)],
        # the arena (operand 5, the scalar prefetch counted) is output 1
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(slots.astype(jnp.int32), f32(x) * f32(dt)[..., None],
      jnp.broadcast_to(jnp.exp(f32(ld))[..., None], (B, H, N)),
      f32(Bm)[:, :, None], f32(Cm)[:, :, None], arena)
    return y, arena
