"""The state-space mixer's chunked prefill as a Pallas kernel:
``ops.ssm.chunk_head`` for each head of a group a grid step, the group's
states carried in VMEM from chunk to chunk.

The grid is ``(rows, groups, chunks)``; the chunk axis is sequential and
the states ``[H / G, P, N]`` float32 of a group's heads stay in a VMEM
scratch across it: read from ``s0`` at the group's first chunk and written
to the output state at its last. Operands arrive token-major as the
projections leave them (``dt x`` ``[B, T, H * P]``, ``B`` and ``C`` ``[B,
T, G * N]``: a block is one group's lanes, no transpose outside), the
within-chunk cumulative log-decay twice, as columns ``[B, G, T, H / G]``
and as rows ``[B, G, H / G, T]`` (a decay matrix is their difference, and
the kernel turns nothing). ``C . B`` is taken once a group and chunk. The
body is ``chunk_head`` itself: the plain chunked form (``ops.ssm.chunked``)
and this kernel cannot drift apart.

Cost a chunk and head (``C = 128``, ``P = 64``, ``N = 128``): 6.3 MFLOP of
float32 products and an eighth of the group's 4.2, against the recurrence's
own ``4 P N`` a token, 4.2 MFLOP a chunk.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..ssm import CHUNK, chunk_head, chunk_operands, chunk_scores

#: the kernel's name in a device trace
KERNEL_NAME = "ssm_chunk_prefill"


def _kernel(x_ref, b_ref, c_ref, gc_ref, gr_ref, s0_ref, y_ref, s_ref,
            st_ref):
    # x_ref, y_ref [C, hg * P]; b_ref, c_ref [C, N]; gc_ref [C, hg];
    # gr_ref [hg, C]; s0_ref, s_ref, st_ref [hg, P, N]
    ch = pl.program_id(2)
    hg, P, _ = st_ref.shape

    @pl.when(ch == 0)
    def _enter():
        st_ref[...] = s0_ref[...]

    Bm, Cm = b_ref[...], c_ref[...]
    CB = chunk_scores(Bm, Cm)
    for h in range(hg):
        y, st = chunk_head(x_ref[:, h * P:(h + 1) * P], Bm, Cm, CB,
                           gc_ref[:, h:h + 1], gr_ref[h:h + 1, :],
                           st_ref[h])
        y_ref[:, h * P:(h + 1) * P] = y
        st_ref[h] = st

    @pl.when(ch == pl.num_programs(2) - 1)
    def _leave():
        s_ref[...] = st_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_chunk_prefill(x, Bm, Cm, dt, ld, s0=None, *,
                      interpret: Optional[bool] = None):
    """``ops.ssm.recurrence``'s function and signature: ``x`` ``[B, T, H,
    P]``, ``Bm, Cm`` ``[B, T, G, N]``, ``dt, ld`` ``[B, T, H]``, ``s0``
    ``[B, H, P, N]`` (``None``: zeros) -> ``(y [B, T, H, P], s_T)``
    float32."""
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, P = x.shape
    G, N = Bm.shape[-2:]
    hg = H // G
    if interpret is None:
        from ..attention import on_tpu_platform

        interpret = not on_tpu_platform()
    if s0 is None:
        s0 = jnp.zeros((B, H, P, N), jnp.float32)
    pad = -T % CHUNK
    Tp = T + pad
    xdt, cum = chunk_operands(x, dt, ld, pad)
    gc = cum.reshape(B, Tp, G, hg).transpose(0, 2, 1, 3)      # [B, G, T, hg]
    lanes = lambda m: jnp.pad(                                # noqa: E731
        m.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0))
    ).reshape(B, Tp, G * N)
    tok = lambda w: pl.BlockSpec(                             # noqa: E731
        (None, CHUNK, w), lambda i, g, c: (i, c, g))
    state = pl.BlockSpec((None, hg, P, N), lambda i, g, c: (i, g, 0, 0))
    y, s = pl.pallas_call(
        _kernel,
        grid=(B, G, Tp // CHUNK),
        in_specs=[tok(hg * P), tok(N), tok(N),
                  pl.BlockSpec((None, None, CHUNK, hg),
                               lambda i, g, c: (i, g, c, 0)),
                  pl.BlockSpec((None, None, hg, CHUNK),
                               lambda i, g, c: (i, g, 0, c)),
                  state],
        out_specs=[tok(hg * P), state],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, H * P), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, P, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hg, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(xdt.reshape(B, Tp, H * P), lanes(Bm), lanes(Cm), gc,
      gc.transpose(0, 1, 3, 2), s0.astype(jnp.float32))
    return y[:, :T].reshape(B, T, H, P), s
