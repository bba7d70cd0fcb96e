"""The state-space mixer's decode step as a Pallas kernel: one token for
``B`` rows, each on its own slot of the state arena, in place.

The arena ``[S, H, P, N]`` float32 (the last slot is the null slot) is an
input aliased to the output. A grid step is a ROW: grid ``(B,)``, and the
state block is the whole of ``slots[row]`` (scalar prefetch picks it), ``H
* P * N * 4`` bytes in and as many out (2 MiB each at 64 heads of 64 x 128;
8 MiB of VMEM double-buffered, which the call asks for by its own
``vmem_limit_bytes``): 128 grid steps a call at 128 rows where a step of
one group's heads (256 KiB, the form this replaces) made 1,024. A padded or
finished row carries the null slot, so no real slot is touched for it.

Every head's state ``[P, N]`` (8 vector registers at 64 x 128) is scaled by
its head's decay, has ``dt x (outer) B`` added, is written back to where it
came from and read against ``C``. What bounded the body was the cross-lane
unit: a lane broadcast and a lane reduction a state register (1,024 a row)
take longer than the row's DMA, and the masked reductions that turned ``u``
and ``y`` round in the replaced form were as many again (``PERF.md``
section 6, PR 52, has each form's time). So the body has ONE cross-lane
pass a state register:

- ``u = dt x`` comes in as the row's ``[H, P]`` block and a group's ``[H /
  G, P]`` is transposed ONCE (a register in, ``P / 8`` out), so a head's
  ``u`` is a COLUMN over the sublanes, which one lane broadcast a register
  spreads over ``N``. The decay is a scalar a head, read from SMEM (the
  second scalar prefetch) and splat. ``B`` and ``C`` are the row's ``[G,
  N]`` block, a sublane broadcast a group. Nothing of the state's size, no
  ``[B, H, N]`` broadcast and no transpose is made outside.
- ``y = S C`` goes through the MXU, which is otherwise idle, as ``C S^T``:
  the new state is the STATIONARY operand, loaded transposed as the MXU
  loads any ``q k^T``, so ``y`` leaves as a row over ``P``, the layout of
  the ``[H, P]`` output block. It stays float32: state and ``C`` are each
  split into three parts that bfloat16 holds exactly and that sum to the
  value exactly (:func:`_split`), and the six products ``Precision.HIGHEST``
  forms (hi hi, hi mid, mid hi, hi lo, lo hi, mid mid; each exact in the
  float32 accumulator, the three dropped ones under ``2 ** -32`` of the
  term) are ONE product a head: ``C``'s parts stand on three sublanes of a
  left operand ``[8, 3 N]``, zero where a pair is dropped, against ``[S_hi
  | S_mid | S_lo]``, and the eight sublanes of the result are added. The
  compiler's own ``HIGHEST`` loads the state's parts six times a head,
  this three times.

The groups are a ROLLED loop (eight heads unrolled inside): 64 heads
unrolled ran as fast and cost every boot 12 s, because each of the 32 call
sites of the eight decode programs is lowered again at every start, cache
or no cache. The step is bound by the arena's bytes: a row reads and writes
``H * P * N * 4`` bytes a layer, and the copy in and the copy out together
reach 80% of 819 GB/s at the large buckets (6.4 us a row); a lone row
takes 10.7 us, 5.1 of them its two copies, so the body is under 5.6 us a
row and what is left at 128 rows is the pipeline's DMA.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: the kernel's name in a device trace
KERNEL_NAME = "ssm_decode_step"


def _split(v):
    """``v`` float32 as three float32 parts, each exact in bfloat16 and
    summing to ``v`` exactly: its top 8 significand bits (the low 16 bits
    of the word masked off), the next 8 of what that left, and the rest."""
    top = lambda t: jax.lax.bitcast_convert_type(             # noqa: E731
        jax.lax.bitcast_convert_type(t, jnp.uint32) & jnp.uint32(0xFFFF0000),
        jnp.float32)
    hi = top(v)
    mid = top(v - hi)
    return hi, mid, v - hi - mid


def _kernel(slots_ref, a_ref, u_ref, b_ref, c_ref, s_ref, o_ref, so_ref):
    # a_ref [B * H] in SMEM (the decay); u_ref [H, P] (dt * x); b_ref, c_ref
    # [G, N]; s_ref and so_ref [H, P, N]; o_ref [H, P]
    H, _, N = s_ref.shape
    G = b_ref.shape[0]
    hg = H // G
    row = pl.program_id(0) * H
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, N), 0)

    def group(g, carry):
        h0 = pl.multiple_of(g * hg, hg)
        b = b_ref[pl.ds(g, 1)]
        c_hi, c_mid, c_lo = _split(c_ref[pl.ds(g, 1)])
        parts = jnp.where(sub == 0, c_hi, jnp.where(sub == 1, c_mid, c_lo))
        # C's (hi, mid, lo) meet S_hi, its (hi, mid) S_mid, its (hi) S_lo
        c3 = jnp.concatenate(
            [jnp.where(sub < 3 - k, parts, 0.0) for k in range(3)], axis=1)
        u = u_ref[pl.ds(h0, hg)].T                            # [P, hg]
        for j in range(hg):
            s = s_ref[h0 + j] * a_ref[row + h0 + j] + u[:, j:j + 1] * b
            so_ref[h0 + j] = s
            six = jax.lax.dot_general(                        # [8, P]
                c3, jnp.concatenate(_split(s), axis=1),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            o_ref[pl.ds(h0 + j, 1)] = jnp.sum(six, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, G, group, 0)


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
    if interpret is None:
        from ..attention import on_tpu_platform

        interpret = not on_tpu_platform()
    f32 = lambda a: a.astype(jnp.float32)                     # noqa: E731
    rows = lambda r, c: pl.BlockSpec(                         # noqa: E731
        (None, r, c), lambda i, slots_ref, a_ref: (i, 0, 0))
    state = pl.BlockSpec(
        (None, H, P, N), lambda i, slots_ref, a_ref: (slots_ref[i], 0, 0, 0))
    y, arena = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[rows(H, P), rows(G, N), rows(G, N), state],
            out_specs=[rows(H, P), state],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(arena.shape, jnp.float32)],
        # the arena (operand 5, the scalar prefetches counted) is output 1
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a state block in and one out, double-buffered, and room for
            # the row's small blocks
            vmem_limit_bytes=4 * H * P * N * 4 + (4 << 20)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(slots.astype(jnp.int32), jnp.exp(f32(ld)).reshape(B * H),
      f32(x) * f32(dt)[..., None], f32(Bm), f32(Cm), arena)
    return y, arena
