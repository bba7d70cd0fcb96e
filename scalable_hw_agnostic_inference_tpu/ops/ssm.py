"""A Mamba-2 state-space mixer (the SSD form with a SCALAR decay a head):
a linear recurrence whose per-sequence state is constant in the context's
length.

Per head ``h`` of ``P`` channels, with ``N`` state lanes, on a token's
``x`` (``[P]``), a step size ``dt > 0`` and a decay ``a = exp(-exp(A_log)
dt)`` (a scalar a head), and ``B``, ``C`` (``[N]``, shared by the
``H / G`` heads of a group), the state ``S`` (``[P, N]``) moves as

    S_t = a_t S_{t-1} + dt_t x_t (outer) B_t
    y_t = S_t C_t

(the skip ``D x_t`` and the gated group norm are the layer's, in
:func:`output`). This module holds the function three ways, and everything
of the mixer around it:

- :func:`recurrence`: one ``lax.scan`` step a token, float32. THE oracle:
  the chunked form, both Pallas kernels (``ops.pallas.ssm_chunk``,
  ``ops.pallas.ssm_step``) and the served path's tests are held to it.
- :func:`chunk_head`: ``CHUNK`` tokens of one head at once from the state
  that enters the chunk. With ``G_t`` the inclusive cumulative log-decay,
  ``y_t = e^{G_t} C_t S_0 + sum_{s <= t} e^{G_t - G_s} (C_t . B_s) dt_s
  x_s`` and ``S_C = e^{G_C} S_0 + sum_s e^{G_C - G_s} dt_s x_s (outer)
  B_s``: every exponent is of a difference that is never positive, so
  nothing overflows whatever the decay. Written on plain 2-D values so that
  the chunk kernel's body IS this function; :func:`chunked` maps it over
  rows and heads and scans it over the chunks (the engine's prefill off the
  TPU).
- :func:`step`: one token for a batch of rows (the engine's decode off the
  TPU; the step kernel's oracle beside the recurrence).

The state is float32 always, ``[H, P, N]`` a slot (``N`` on the lanes); the
convolution's tail (the last ``conv - 1`` inputs of the ONE depthwise
convolution over ``x``, ``B`` and ``C`` together) lives beside it in the
activations' type.

A PAD token is the identity: ``dt = 0`` gives ``a = 1`` and adds nothing,
and the tail is read from the last REAL tokens (:func:`inputs`).

``CHUNK`` is 128, the published ``chunk_size``; the function computed does
not depend on it.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .kda import write_slots
from .quant import quant_matmul

#: tokens one step of the chunked form takes
CHUNK = 128
_HI = jax.lax.Precision.HIGHEST


# -- the mixer around the recurrence ---------------------------------------

def dims(cfg) -> Tuple[int, int, int, int]:
    """``(heads, head channels, state lanes, groups)``."""
    return cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups


def conv_width(cfg) -> int:
    """Channels the depthwise convolution runs over: ``x``, ``B``, ``C``."""
    H, P, N, G = dims(cfg)
    return H * P + 2 * G * N


def state_shapes(cfg) -> Dict[str, Tuple[Tuple[int, ...], Optional[str]]]:
    """What ONE slot costs in ONE state-space layer, by leaf: ``(shape,
    dtype)``. ``s``: the state, float32; ``t``: the last ``conv - 1``
    inputs of the convolution, in the activations' type (``None``: the
    holder's own)."""
    H, P, N, _ = dims(cfg)
    return {"s": ((H, P, N), "float32"),
            "t": ((cfg.ssm_conv - 1, conv_width(cfg)), None)}


def inputs(at: Dict, h: jax.Array, tail: Optional[jax.Array],
           n_valid: Optional[jax.Array], cfg):
    """The recurrence's operands from the normed stream ``h`` ``[B, T, D]``.

    ``tail`` ``[B, conv - 1, conv_width]``: the convolution's inputs of the
    tokens before ``h`` (``None``: position 0, zeros). ``n_valid`` ``[B]``:
    real tokens of each row (``None``: all); the rest are pads, which get
    ``dt = 0`` and do not enter the new tail.

    Returns ``(z, x, Bm, Cm, dt, new_tail)``: the gate ``z`` ``[B, T, H *
    P]`` in the stream's type, ``x`` ``[B, T, H, P]``, ``Bm, Cm`` ``[B, T,
    G, N]`` and ``dt`` ``[B, T, H]`` float32, ``new_tail`` like ``tail``."""
    B, T, _ = h.shape
    H, P, N, G = dims(cfg)
    K, inner, wide = cfg.ssm_conv, H * P, conv_width(cfg)
    zxd = quant_matmul(h, at["in"])
    z, pre, dt = (zxd[..., :inner], zxd[..., inner:inner + wide],
                  zxd[..., inner + wide:])
    if tail is None:
        tail = jnp.zeros((B, K - 1, wide), pre.dtype)
    ext = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
    w = at["conv"].astype(jnp.float32)                        # [K, wide]
    y = sum(ext[:, i:i + T].astype(jnp.float32) * w[i] for i in range(K))
    y = jax.nn.silu(y + at["conv_bias"].astype(jnp.float32))
    x = y[..., :inner].reshape(B, T, H, P)
    Bm = y[..., inner:inner + G * N].reshape(B, T, G, N)
    Cm = y[..., inner + G * N:].reshape(B, T, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + at["dt_bias"].astype(jnp.float32))
    if n_valid is None:
        return z, x, Bm, Cm, dt, ext[:, T:]
    real = jnp.arange(T)[None, :] < n_valid[:, None]          # [B, T]
    dt = jnp.where(real[..., None], dt, 0.0)
    # the last conv - 1 REAL inputs: rows n .. n + K - 2 of the extension
    rows = n_valid[:, None] + jnp.arange(K - 1)[None, :]
    return z, x, Bm, Cm, dt, jnp.take_along_axis(ext, rows[..., None],
                                                 axis=1)


def log_decay(at: Dict, dt: jax.Array) -> jax.Array:
    """``log a`` ``[..., H]`` float32: ``-exp(A_log) dt``, never positive."""
    return -jnp.exp(at["A_log"].astype(jnp.float32)) * dt


def output(at: Dict, x: jax.Array, y: jax.Array, z: jax.Array,
           cfg) -> jax.Array:
    """``y`` ``[B, T, H, P]`` with the skip ``D x`` added, gated by
    ``silu(z)`` FIRST and then RMS-normed over each of the ``G`` groups of
    ``H P / G`` channels, as ``[B, T, H * P]`` in the stream's type (the
    caller applies ``W_out``)."""
    B, T, H, P = y.shape
    G = cfg.ssm_groups
    y = y.astype(jnp.float32) + (
        at["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32))
    y = y.reshape(B, T, H * P) * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(B, T, G, H * P // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                            + cfg.rms_eps)
    y = yg.reshape(B, T, H * P) * at["norm"]["scale"].astype(jnp.float32)
    return y.astype(z.dtype)


# -- the recurrence, one token at a time (the oracle) ----------------------

def _per_head(m: jax.Array, H: int) -> jax.Array:
    """``[..., G, N]`` -> ``[..., H, N]``: head ``h`` reads group
    ``h // (H / G)``."""
    return jnp.repeat(m, H // m.shape[-2], axis=-2)


def step(x, Bm, Cm, dt, ld, s):
    """One token: ``x`` ``[..., H, P]``, ``Bm, Cm`` ``[..., G, N]``, ``dt``
    and ``ld`` (the log-decay) ``[..., H]``, ``s`` ``[..., H, P, N]``.
    Returns ``(y [..., H, P], s)``."""
    H = x.shape[-2]
    s = s * jnp.exp(ld)[..., None, None] + (
        (dt[..., None] * x)[..., None] * _per_head(Bm, H)[..., None, :])
    return jnp.sum(s * _per_head(Cm, H)[..., None, :], axis=-1), s


def recurrence(x, Bm, Cm, dt, ld, s0=None):
    """``x`` ``[B, T, H, P]``, ``Bm, Cm`` ``[B, T, G, N]``, ``dt, ld``
    ``[B, T, H]``, ``s0`` ``[B, H, P, N]`` (``None``: zeros). Returns ``(y
    [B, T, H, P], s_T)``, float32: a ``lax.scan`` over the tokens."""
    B, T, H, P = x.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, P, Bm.shape[-1]), jnp.float32)
    f32 = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)  # noqa: E731

    def one(s, t):
        y, s = step(*t, s)
        return s, y

    s, y = jax.lax.scan(one, s0.astype(jnp.float32),
                        (f32(x), f32(Bm), f32(Cm), f32(dt), f32(ld)))
    return jnp.moveaxis(y, 0, 1), s


# -- the chunked form -------------------------------------------------------

def _mm(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _mm_nt(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def chunk_scores(Bm, Cm):
    """``C_t . B_s`` of one chunk and group, ``[C, C]``: every head of the
    group reads it."""
    return _mm_nt(Cm, Bm)


def chunk_head(xdt, Bm, Cm, CB, gcol, grow, st):
    """One chunk of one head. ``xdt = dt * x`` ``[C, P]``; ``Bm, Cm`` ``[C,
    N]`` (the head's group's); ``CB`` their :func:`chunk_scores`; ``gcol``
    ``[C, 1]`` and ``grow`` ``[1, C]``: the inclusive cumulative log-decay
    of the chunk, as a column and as a row; ``st`` ``[P, N]`` the state that
    enters. All float32. Returns ``(y [C, P], st_out)``."""
    C = xdt.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    L = jnp.where(col <= row, jnp.exp(jnp.minimum(gcol - grow, 0.0)), 0.0)
    y = _mm(CB * L, xdt) + jnp.exp(gcol) * _mm_nt(Cm, st)
    g_end = gcol[C - 1:C]                                     # [1, 1]
    # onto the lanes first, then down the sublanes: Mosaic has no
    # broadcast of one element both ways at once
    keep = jnp.exp(jnp.broadcast_to(g_end, (1, st.shape[1])))
    st = st * keep + _mm((xdt * jnp.exp(g_end - gcol)).T, Bm)
    return y, st


def chunk_operands(x, dt, ld, pad: int):
    """What both chunked forms are handed beside ``Bm`` and ``Cm``, padded
    by ``pad`` identity tokens to whole chunks: ``xdt`` ``[B, T', H, P]``
    and the within-chunk inclusive cumulative log-decay ``[B, T', H]``."""
    B, T, H, P = x.shape
    xdt = jnp.pad(x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None],
                  ((0, 0), (0, pad), (0, 0), (0, 0)))
    ld = jnp.pad(ld.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    cum = jnp.cumsum(ld.reshape(B, (T + pad) // CHUNK, CHUNK, H), axis=2)
    return xdt, cum.reshape(B, T + pad, H)


def chunked(x, Bm, Cm, dt, ld, s0=None):
    """:func:`recurrence`'s function, ``CHUNK`` tokens a step: same
    arguments and results. ``T`` is padded to whole chunks with identity
    tokens. Plain ``jnp`` (the engine's prefill off the TPU, and the chunk
    kernel's shape-for-shape twin)."""
    B, T, H, P = x.shape
    G, N = Bm.shape[-2:]
    if s0 is None:
        s0 = jnp.zeros((B, H, P, N), jnp.float32)
    pad = -T % CHUNK
    n = (T + pad) // CHUNK
    xdt, cum = chunk_operands(x, dt, ld, pad)
    grp = lambda m: jnp.pad(                                  # noqa: E731
        m.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0))
    ).reshape(B, n, CHUNK, G, N).transpose(0, 3, 1, 2, 4)     # [B,G,n,C,N]
    Bg, Cg = grp(Bm), grp(Cm)
    CBg = jax.vmap(jax.vmap(jax.vmap(chunk_scores)))(Bg, Cg)
    xh = xdt.reshape(B, n, CHUNK, H, P).transpose(0, 3, 1, 2, 4)
    gh = cum.reshape(B, n, CHUNK, H).transpose(0, 3, 1, 2)    # [B,H,n,C]

    def one_head(xs, bs, cs, cbs, gs, sh):
        def one(st, t):
            xc, bc, cc, cb, g = t
            y, st = chunk_head(xc, bc, cc, cb, g[:, None], g[None, :], st)
            return st, y

        st, y = jax.lax.scan(one, sh, (xs, bs, cs, cbs, gs))
        return y.reshape(-1, P), st

    rep = lambda m: jnp.repeat(m, H // G, axis=1)             # noqa: E731
    y, s = jax.vmap(jax.vmap(one_head))(
        xh, rep(Bg), rep(Cg), rep(CBg), gh, s0.astype(jnp.float32))
    return jnp.moveaxis(y[:, :, :T], 1, 2), s


def scan(x, Bm, Cm, dt, ld, s0=None, *, kernel: bool):
    """The prefill's scan with implementation dispatch: the Pallas chunk
    kernel where ``kernel`` (the TPU), :func:`chunked` elsewhere."""
    if not kernel:
        return chunked(x, Bm, Cm, dt, ld, s0)
    from .pallas.ssm_chunk import ssm_chunk_prefill

    return ssm_chunk_prefill(x, Bm, Cm, dt, ld, s0)


def step_slots(x, Bm, Cm, dt, ld, arena, slots, *, kernel: bool):
    """One decode step for ``B`` rows over their slots of ``arena`` ``[S, H,
    P, N]``: ``x`` ``[B, H, P]``, ``Bm, Cm`` ``[B, G, N]``, ``dt, ld`` ``[B,
    H]``, ``slots`` ``[B]`` int32 (a padded row's is the arena's last, the
    null slot). Returns ``(y [B, H, P], arena)``: the kernel updates the
    arena in place; the plain form gathers, steps and scatters."""
    if kernel:
        from .pallas.ssm_step import ssm_decode_step

        return ssm_decode_step(x, Bm, Cm, dt, ld, arena, slots)
    y, s = step(x, Bm, Cm, dt, ld, arena[slots])
    return y, arena.at[slots].set(s)


# -- a mixer's phases, as the engine's programs call them (the recurrent
# KINDS share this interface: ``ops.kda`` has the same two) ----------------

def prefill(at: Dict, h: jax.Array, state: Dict, slots: jax.Array,
            n_valid: jax.Array, cfg, *, carry: bool, kernel: bool):
    """A prefill (``carry`` False: from a ZERO state and tail, whatever the
    slot held) or continuation (``carry``: from the rows' ``slots`` of the
    arena ``state``) program's pass over ``h`` ``[B, T, D]``, the state and
    tail the last REAL token left written to the slots. Returns ``(out [B,
    T, H * P], state)``."""
    z, x, Bm, Cm, dt, tail = inputs(
        at, h, state["t"][slots] if carry else None, n_valid, cfg)
    y, s = scan(x, Bm, Cm, dt, log_decay(at, dt),
                state["s"][slots] if carry else None, kernel=kernel)
    return output(at, x, y, z, cfg), write_slots(state, slots, s, tail)


def decode(at: Dict, h: jax.Array, state: Dict, slots: jax.Array, cfg, *,
           kernel: bool):
    """One decode step of ``h`` ``[B, 1, D]`` in place on the rows' slots.
    Returns ``(out [B, 1, H * P], state)``."""
    tails = state["t"]
    z, x, Bm, Cm, dt, tail = inputs(at, h, tails[slots], None, cfg)
    y, arena = step_slots(x[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0],
                          log_decay(at, dt)[:, 0], state["s"], slots,
                          kernel=kernel)
    state = {"s": arena, "t": tails.at[slots].set(tail.astype(tails.dtype))}
    return output(at, x, y[:, None], z, cfg), state
