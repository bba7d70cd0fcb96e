"""Kimi Delta Attention (KDA, Kimi Linear's linear-attention layer): a gated
delta rule whose per-sequence state is constant in the context's length.

Per head, on a token's ``q, k, v`` (``d`` channels each; ``q, k`` L2-normed,
``q`` scaled by ``d ** -0.5``), a log-decay ``g <= 0`` per key CHANNEL
(``alpha = exp(g)``) and a write strength ``beta`` in ``(0, 1)``, the state
``S`` (``[d_k, d_v]``) moves as

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

This module holds the function three ways, and everything of the layer
around it:

- :func:`recurrence`: one ``lax.scan`` step a token, float32. THE oracle:
  the chunked form, both Pallas kernels (``ops.pallas.kda_chunk``,
  ``ops.pallas.kda_step``) and the served path's tests are held to it.
- :func:`chunk_math`: ``CHUNK`` tokens at once from the states that enter
  the chunk (the WY form of the delta rule with a per-channel decay), for a
  GROUP of heads on a leading axis: 19 matrix products a head, the heads'
  chains side by side. It is written on plain values so that the chunk
  kernel's body IS this function (a grid step takes a group of heads);
  :func:`chunked` maps it over rows, a row's heads one group, and scans it
  over the chunks (the engine's prefill off the TPU).
- :func:`step`: one token for a batch of rows (the engine's decode off the
  TPU; the step kernel's oracle beside the recurrence).

The state is STORED TRANSPOSED, ``s[..., j, i] = S[i, j]`` (``[d_v, d_k]``):
the decay then scales lanes, which is what both kernels want. Float32
always; the convolution's tail (the last ``conv - 1`` inputs of the q, k and
v convolutions) lives beside it in the activations' type.

A PAD token is the identity: ``beta = 0`` and ``g = 0`` leave ``S`` as it
was, and the tail is read from the last REAL tokens (:func:`inputs`).

``chunk_math`` exponentiates differences of the cumulative log-decay against
a reference point a ``BLOCK`` of 16 rows, the block's middle, so that the two
factors of a kept entry stay within ``exp(+-8 * max|g|)`` of each other's
inverse: sound for ``|g| <= 8`` a token and channel (``alpha >= 0.0003``;
the public initialisation gives at most about 3). Against the chunk's start
alone a factor would reach ``exp(64 * |g|)`` and overflow; against the
block's start, ``exp(-16 * |g|)`` times a small ``q`` fell under float32's
smallest normal at ``|g| = 5`` and the CPU flushed it (PR 34's test).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .quant import quant_matmul

#: tokens one step of the chunked form takes
CHUNK = 64
#: rows that share a reference point of the cumulative decay
BLOCK = 16
#: added under the root of the q and k norms (the public kernels' value)
L2_EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST
#: largest exponent taken (float32 overflows past 88): a KEPT entry stays
#: under it while 8 * |g| does; masked entries may reach it and are dropped
_EXP_CAP = 87.0


# -- the layer around the recurrence ---------------------------------------

def state_shapes(cfg) -> Dict[str, Tuple[Tuple[int, ...], Optional[str]]]:
    """What ONE slot costs in ONE KDA layer, by leaf: ``(shape, dtype)``.
    ``s``: the transposed state a head, float32; ``t``: the last
    ``conv - 1`` inputs of the three convolutions, side by side, in the
    activations' type (``None``: the holder's own)."""
    H, d = cfg.kda_heads, cfg.kda_head_dim
    return {"s": ((H, d, d), "float32"),
            "t": ((cfg.kda_conv - 1, 3 * H * d), None)}


def _l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def inputs(at: Dict, h: jax.Array, tail: Optional[jax.Array],
           n_valid: Optional[jax.Array], cfg):
    """The recurrence's operands from the normed stream ``h`` ``[B, T, D]``.

    ``tail`` ``[B, conv - 1, 3 * H * d]``: the convolutions' inputs of the
    tokens before ``h`` (``None``: position 0, zeros). ``n_valid`` ``[B]``:
    real tokens of each row (``None``: all); the rest are pads, which get
    ``beta = 0``, ``g = 0`` and do not enter the new tail.

    Returns ``(q, k, v, g, beta, new_tail)``: ``q, k, v, g`` ``[B, T, H, d]``
    float32, ``beta`` ``[B, T, H]`` float32, ``new_tail`` like ``tail``."""
    B, T, _ = h.shape
    H, d, K = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
    pre = jnp.concatenate(
        [quant_matmul(h, at[n]) for n in ("q", "k", "v")], axis=-1)
    if tail is None:
        tail = jnp.zeros((B, K - 1, pre.shape[-1]), pre.dtype)
    ext = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
    w = jnp.concatenate([at[n] for n in ("q_conv", "k_conv", "v_conv")],
                        axis=-1).astype(jnp.float32)          # [K, 3HD]
    y = sum(ext[:, i:i + T].astype(jnp.float32) * w[i] for i in range(K))
    y = jax.nn.silu(y).reshape(B, T, 3, H, d)
    q = _l2norm(y[:, :, 0]) * (d ** -0.5)
    k = _l2norm(y[:, :, 1])
    v = y[:, :, 2]
    f = quant_matmul(quant_matmul(h, at["f_a"]), at["f_b"])
    g = -jnp.exp(at["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        f.astype(jnp.float32).reshape(B, T, H, d)
        + at["dt_bias"].astype(jnp.float32).reshape(H, d))
    beta = jax.nn.sigmoid(quant_matmul(h, at["b"]).astype(jnp.float32))
    if n_valid is None:
        return q, k, v, g, beta, ext[:, T:]
    real = jnp.arange(T)[None, :] < n_valid[:, None]          # [B, T]
    g = jnp.where(real[..., None, None], g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    # the last conv - 1 REAL inputs: rows n .. n + K - 2 of the extension
    rows = n_valid[:, None] + jnp.arange(K - 1)[None, :]
    return q, k, v, g, beta, jnp.take_along_axis(ext, rows[..., None], axis=1)


def output(at: Dict, h: jax.Array, o: jax.Array, cfg) -> jax.Array:
    """``o`` ``[B, T, H, d]`` under its per-head RMSNorm and the layer's
    low-rank sigmoid gate, as ``[B, T, H * d]`` in the stream's type (the
    caller applies ``W_o``)."""
    B, T, H, d = o.shape
    o = o.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_eps)
    o = o * at["o_norm"]["scale"].astype(jnp.float32)
    gate = quant_matmul(quant_matmul(h, at["g_a"]), at["g_b"])
    o = o * jax.nn.sigmoid(gate.astype(jnp.float32).reshape(B, T, H, d))
    return o.reshape(B, T, H * d).astype(h.dtype)


# -- the recurrence, one token at a time (the oracle) ----------------------

def step(q, k, v, g, beta, s):
    """One token: ``q, k, v, g`` ``[..., d]``, ``beta`` ``[...]``, ``s``
    ``[..., d_v, d_k]`` (transposed). Returns ``(o [..., d_v], s)``."""
    s = s * jnp.exp(g)[..., None, :]
    pred = jnp.sum(s * k[..., None, :], axis=-1)              # S'^T k
    u = beta[..., None] * (v - pred)
    s = s + u[..., :, None] * k[..., None, :]
    return jnp.sum(s * q[..., None, :], axis=-1), s


def recurrence(q, k, v, g, beta, s0=None):
    """``q, k, v, g`` ``[B, T, H, d]``, ``beta`` ``[B, T, H]``, ``s0``
    ``[B, H, d, d]`` transposed (``None``: zeros). Returns
    ``(o [B, T, H, d], s_T)``, float32: a ``lax.scan`` over the tokens."""
    B, T, H, d = q.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, d, d), jnp.float32)
    f32 = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)  # noqa: E731

    def one(s, x):
        o, s = step(*x, s)
        return s, o

    s, o = jax.lax.scan(one, s0.astype(jnp.float32),
                        (f32(q), f32(k), f32(v), f32(g), f32(beta)))
    return jnp.moveaxis(o, 0, 1), s


# -- the chunked form -------------------------------------------------------

def _mm(a, b, precision=_HI):
    """``a [..., M, K] @ b [..., K, N]``, the leading (head) axes batched."""
    n = a.ndim - 2
    return jax.lax.dot_general(
        a, b, (((n + 1,), (n,)), (tuple(range(n)),) * 2),
        precision=precision, preferred_element_type=jnp.float32)


def _mm_nt(a, b):
    """``a [..., M, K] @ b [..., N, K]^T``, the leading axes batched."""
    n = a.ndim - 2
    return jax.lax.dot_general(
        a, b, (((n + 1,), (n + 1,)), (tuple(range(n)),) * 2),
        precision=_HI, preferred_element_type=jnp.float32)


def _mm_tn(a, b):
    """``a [..., K, M]^T @ b [..., K, N]``, the leading axes batched."""
    n = a.ndim - 2
    return jax.lax.dot_general(
        a, b, (((n,), (n,)), (tuple(range(n)),) * 2),
        precision=_HI, preferred_element_type=jnp.float32)


def _bf16_part(x):
    """The leading bfloat16 of a float32, by truncation, as a float32."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _mask_mm(mask, x):
    """``mask @ x`` for a 0/1 ``mask`` ``[M, K]`` and a general float32
    ``x`` ``[..., K, N]``, in the three passes that are not a product with
    an exact zero. ``Precision.HIGHEST`` on the TPU is six products of
    bfloat16 parts (``hi``, ``mid``, ``lo`` of each operand, split by
    truncation); a mask's ``mid`` and ``lo`` are exactly 0, so three of the
    six add nothing. What is left is ``mask @ lo + mask @ mid + mask @
    hi``, in that order (the compiler's own), each a product of operands
    that bfloat16 holds exactly: one pass each, whatever the precision."""
    hi = _bf16_part(x)
    mid = _bf16_part(x - hi)
    lo = _bf16_part((x - hi) - mid)
    m = jnp.broadcast_to(mask.astype(jnp.float32),
                         x.shape[:-2] + mask.shape)
    one = jax.lax.Precision.DEFAULT
    return (_mm(m, lo, one) + _mm(m, mid, one)) + _mm(m, hi, one)


def _neumann(x, eye, n: int):
    """``(I + x)^-1`` of a matrix nilpotent of index ``n`` (a power of
    two): ``(I - x)(I + x^2)(I + x^4)...``, products only."""
    inv, p = eye - x, x
    m = 2
    while m < n:
        p = _mm(p, p)
        inv = _mm(inv, eye + p)
        m *= 2
    return inv


def chunk_math(q, k, kb, vb, g, st):
    """One chunk of a GROUP of heads, the heads' chains side by side.
    ``q, k, g`` ``[Hg, C, d]``; ``kb = beta * k`` and ``vb = beta * v``
    ``[Hg, C, d]``; ``st`` ``[Hg, d_v, d_k]`` the transposed states that
    enter. All float32. Returns ``(o [Hg, C, d_v], st_out)``.

    With ``G`` the inclusive cumulative log-decay, ``u`` the delta rule's
    corrected values solve ``(I + A) u = beta (v - K+ S0)``, ``A_ij = beta_i
    (k_i e^{G_i}) . (k_j e^{-G_j})`` for ``j < i``; then ``o = Q+ S0 + P u``
    with ``P_ij = (q_i e^{G_i}) . (k_j e^{-G_j})`` for ``j <= i`` and ``S_C =
    Diag(e^{G_C}) S0 + (k e^{G_C - G})^T u``. Exponents are taken against
    the cumulative decay at the middle of the row's block of ``BLOCK`` rows,
    never against the chunk's start alone (module docstring); ``(I + A)^-1``
    is the block-diagonal part's Neumann product times the block-lower
    remainder's, matrix products only.

    19 matrix products a head (``C = 64``, ``BLOCK = 16``): the cumulative
    decay (1: a 0/1 mask's product, three passes, :func:`_mask_mm`); a
    block's ``A`` and ``P`` rows in ONE product, ``[kb e ; q e]`` against the
    block's ``k e^-`` (4); the Neumann products (6, then 1, 2, 1); ``[kb ;
    q] e^G`` against the state in ONE product (1); ``u``, ``P u`` and the
    state's update (3). The reference point of block ``lo`` is the
    cumulative decay through its row ``BLOCK / 2 - 1``, which IS a row of
    ``G``: no second mask product."""
    C = q.shape[-2]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    G = _mask_mm(col <= row, g)                               # cumulative
    a_rows, p_rows = [], []
    for lo in range(0, C, BLOCK):
        # the cumulative decay at the MIDDLE of the block: the reference
        # point of its exponents, half a block from any of its rows
        mid = lo + BLOCK // 2 - 1
        rs = G[..., mid:mid + 1, :]                           # [Hg, 1, d]
        e = jnp.exp(G[..., lo:lo + BLOCK, :] - rs)
        kneg = k * jnp.exp(jnp.minimum(rs - G, _EXP_CAP))     # [Hg, C, d]
        both = _mm_nt(jnp.concatenate(
            [kb[..., lo:lo + BLOCK, :] * e, q[..., lo:lo + BLOCK, :] * e],
            axis=-2), kneg)                                   # [Hg, 2B, C]
        a_rows.append(both[..., :BLOCK, :])
        p_rows.append(both[..., BLOCK:, :])
    A = jnp.where(col < row, jnp.concatenate(a_rows, axis=-2), 0.0)
    P = jnp.where(col <= row, jnp.concatenate(p_rows, axis=-2), 0.0)
    eye = (row == col).astype(jnp.float32)
    diag = jnp.where(row // BLOCK == col // BLOCK, A, 0.0)
    inv_d = _neumann(diag, eye, BLOCK)
    inv = _mm(_neumann(_mm(inv_d, A - diag), eye, C // BLOCK), inv_d)
    decay = jnp.exp(G)                                        # from the start
    both = _mm_nt(jnp.concatenate([kb * decay, q * decay], axis=-2), st)
    u = _mm(inv, vb - both[..., :C, :])                       # [Hg, C, d_v]
    o = both[..., C:, :] + _mm(P, u)
    g_end = G[..., C - 1:C, :]
    st = st * jnp.exp(g_end) + _mm_tn(u, k * jnp.exp(g_end - G))
    return o, st


def chunked(q, k, v, g, beta, s0=None):
    """:func:`recurrence`'s function, ``CHUNK`` tokens a step: same
    arguments and results. ``T`` is padded to whole chunks with identity
    tokens. Plain ``jnp`` (the engine's prefill off the TPU, and the chunk
    kernel's shape-for-shape twin): a row's heads are ONE group."""
    B, T, H, d = q.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, d, d), jnp.float32)
    pad = -T % CHUNK
    bh = lambda a: jnp.moveaxis(jnp.pad(                      # noqa: E731
        a.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
            B, (T + pad) // CHUNK, CHUNK, H, d), 3, 2)   # [B, n, H, C, d]
    b = beta.astype(jnp.float32)[..., None]
    xs = (bh(q), bh(k), bh(k * b), bh(v * b), bh(g))

    def one_row(qr, kr, kbr, vbr, gr, sr):
        def one(st, x):
            o, st = chunk_math(*x, st)
            return st, o

        st, o = jax.lax.scan(one, sr, (qr, kr, kbr, vbr, gr))
        return jnp.moveaxis(o, 1, 2).reshape(-1, H, d), st    # [T, H, d]

    o, s = jax.vmap(one_row)(*xs, s0.astype(jnp.float32))
    return o[:, :T], s


def scan(q, k, v, g, beta, s0=None, *, kernel: bool):
    """The prefill's scan with implementation dispatch: the Pallas chunk
    kernel where ``kernel`` (the TPU), :func:`chunked` elsewhere."""
    if not kernel:
        return chunked(q, k, v, g, beta, s0)
    from .pallas.kda_chunk import kda_chunk_prefill

    return kda_chunk_prefill(q, k, v, g, beta, s0)


def step_slots(q, k, v, g, beta, arena, slots, *, kernel: bool):
    """One decode step for ``B`` rows over their slots of ``arena``
    ``[S, H, d, d]``: ``q, k, v, g`` ``[B, H, d]``, ``beta`` ``[B, H]``,
    ``slots`` ``[B]`` int32 (a padded row's is the arena's last, the null
    slot). Returns ``(o [B, H, d], arena)``: the kernel updates the arena in
    place; the plain form gathers, steps and scatters."""
    if kernel:
        from .pallas.kda_step import kda_decode_step

        return kda_decode_step(q, k, v, g, beta, arena, slots)
    o, s = step(q, k, v, g, beta, arena[slots])
    return o, arena.at[slots].set(s)


# -- a mixer's phases, as the engine's programs call them (the recurrent
# KINDS share this interface: ``ops.ssm`` has the same two) ----------------

def write_slots(state: Dict, slots: jax.Array, s: jax.Array,
                tail: jax.Array) -> Dict:
    """A recurrent layer's slot arena with ``slots``' states and tails
    replaced (``[K, ...]`` each): THE write seam of prefill and
    continuation. A dummy row's slot is the null slot (the arena's last)."""
    return {"s": state["s"].at[slots].set(s.astype(state["s"].dtype)),
            "t": state["t"].at[slots].set(tail.astype(state["t"].dtype))}


def prefill(at: Dict, h: jax.Array, state: Dict, slots: jax.Array,
            n_valid: jax.Array, cfg, *, carry: bool, kernel: bool):
    """A prefill (``carry`` False: from a ZERO state and tail, whatever the
    slot held) or continuation (``carry``: from the rows' ``slots`` of the
    arena ``state``) program's pass over ``h`` ``[B, T, D]``, the state and
    tail the last REAL token left written to the slots. Returns ``(out [B,
    T, H * d], state)``."""
    q, k, v, g, beta, tail = inputs(
        at, h, state["t"][slots] if carry else None, n_valid, cfg)
    if carry:
        o, s = scan(q, k, v, g, beta, state["s"][slots], kernel=kernel)
    else:
        o, s = scan(q, k, v, g, beta, kernel=kernel)
    state = write_slots(state, slots, s, tail)
    return output(at, h, o, cfg), state


def decode(at: Dict, h: jax.Array, state: Dict, slots: jax.Array, cfg, *,
           kernel: bool):
    """One decode step of ``h`` ``[B, 1, D]`` in place on the rows' slots.
    Returns ``(out [B, 1, H * d], state)``."""
    arena, tails = state["s"], state["t"]
    q, k, v, g, beta, tail = inputs(at, h, tails[slots], None, cfg)
    o, arena = step_slots(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                          arena, slots, kernel=kernel)
    state = {"s": arena, "t": tails.at[slots].set(tail.astype(tails.dtype))}
    return output(at, h, o[:, None], cfg), state
