"""Kimi Linear's forward pass as Kimi-Linear-48B-A3B-Instruct configures it,
plain: ``jax.numpy``, float32, no kernels, no cache, no batching, no chunked
scan: the delta rule is ONE ``lax.scan`` step a token over the whole
sequence (the program prefills in chunks of 64 through a kernel, carries the
state from program to program and decodes one recurrent step a row, so the
check is of those forms against this one). Written from the ``config.json``
of ``moonshotai/Kimi-Linear-48B-A3B-Instruct`` (``model_type: kimi_linear``),
the Kimi Linear report (arXiv 2510.26692) and the public ``modeling_kimi.py``;
what those leave open is listed under ``assumed`` in the configuration's
file. It imports nothing of the program.

On a sequence ``x`` of ``[T, hidden]`` (``x0 = Embed[ids]``), layer ``l``
(1-based in ``linear_attn_config``), ``h = n_in(x)`` (RMSNorm,
``rms_norm_eps``); no positional embedding anywhere.

KDA layer (``kda_layers``), ``H`` heads of ``d``:

    q, k, v = silu(conv4(Wq h)), silu(conv4(Wk h)), silu(conv4(Wv h))
                                  causal depthwise, 4 taps, no bias
    q = q / |q| * d ** -0.5;  k = k / |k|            per head (L2)
    g = -exp(A_log_h) * softplus(Wf_b Wf_a h + dt_bias)   [H, d], per CHANNEL
    beta = sigmoid(Wb h)                                  [H]
    S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T    per head
    o = S_t^T q;   y = Wo [rmsnorm_head(o) * sigmoid(Wg_b Wg_a h)]

MLA layer (``full_attn_layers``): ``q = Wq h`` (no q latent), ``[c ; kr] =
Wkva h``, ``c = n_kv(c)``, ``[k^n_h ; v_h] = Wkvb_h c``, scores ``(q^n_h .
k^n_h + q^r_h . kr) / sqrt(nope + rope)`` with the "rope" dims NOT rotated
(``mla_use_nope``), causal softmax, ``x = x + Wo [o_h]``. Queries are walked
a block at a time so that 16k tokens fit.

``FFN`` for ``l <= first_k_dense_replace``: ``Down(silu(Gate m) * Up m)``.
Else ``s = sigmoid(Wr m)`` in float32 over ALL published experts; ``sel =
top_k(s + b)``; ``w = s[sel] / (sum + 1e-20)`` (``moe_renormalize``), ``w =
routed_scaling_factor * w``; ``FFN(m) = sum over the experts HELD here +
Shared(m)``: the stacked expert leaves are experts ``first .. first +
count`` of the router's (this chip's share of an expert-parallel layout;
what the absent experts would add is left out, here as in the program).

``variant`` exists for the tests and the chip check only: it breaks the
mathematics on purpose so that the tolerance can be shown to refuse it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# What ``tolerance.kimi_linear.json`` must refuse by its LARGEST-difference
# bound, at the tiny size as at the published one (``tests/benchmark`` holds
# every name here to that bound): a continuation chunk that starts from a
# zero state and tail (THE variant that ties the check to the engine's
# carry), no decay, one decay a head, no beta, no L2 norm, no convolution.
REFUSED_VARIANTS = ("no_carry", "no_decay", "decay_per_head", "no_beta",
                    "no_qk_l2norm", "no_conv")
ACCEPTED_VARIANTS = ()
# What it must refuse by its MEAN bound. ``rope_on``: the MLA layer's 64
# "rope" dims rotated after all (one layer of five is MLA, so the shift is
# everywhere and small: steady in the mean, under the largest difference's
# tail). ``weights_fp8``: THE PRECISION CONTROL, the nearest precision below
# the bfloat16 the configuration states in its name: every matrix a product
# reads, and the head, in float8 e4m3 under one scale a matrix.
REFUSED_BY_MEAN = ("rope_on", "weights_fp8")
# A lower precision that NO bound on the logits refuses, and said so in the
# tolerance file with its readings: the recurrent STATE, float32 as served,
# rounded to bfloat16 after every token. The chunk kernel's own case holds
# that line (``ops/kernel_check.py`` ``kda_state_bf16_err``).
NOT_REFUSED_RELIABLY = ("state_bf16",)

#: vocabulary columns a block of the head holds
VOCAB_BLOCK = 16384
#: a sequence is padded to a multiple of this many positions
PAD_STEP = 2048
#: query rows one block of the MLA layer's attention holds
QUERY_BLOCK = 2048
#: added under the root of the q and k norms (the public kernels' value)
L2_EPS = 1e-6


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def fp8(w):
    """``w`` rounded to float8 e4m3 (4 exponent bits, 3 of mantissa) under
    one scale: its largest entry lands on 240, the largest such a format
    holds beside an infinity. ``reduce_precision`` and not a pair of casts,
    which XLA's excess-precision rule may drop."""
    scale = jnp.max(jnp.abs(w)) / 240.0
    return jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def matrix(leaf, variant: str = ""):
    """A weight as float32: a plain array or ``{"kernel": W}``
    (``weights_fp8``: every matrix a product reads, through ``fp8``; the
    norms' scales, the router, the convolutions' taps and the decay's two
    vectors are read without a variant and stay as they are served)."""
    if isinstance(leaf, dict):
        leaf = leaf["kernel"]
    w = leaf.astype(jnp.float32)
    return fp8(w) if variant == "weights_fp8" else w


def gated(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def rope_pairs(x, positions, theta):
    """``x`` ``[T, ..., D]`` turned on lanes ``(2i, 2i+1)`` (``rope_on``
    only: the model itself turns nothing)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def routed(m, moe: Dict[str, Any], *, top_k: int, renorm: bool,
           route_scale: float, first: int, variant: str = ""):
    """The routed FFN on ``m`` ``[T, hidden]``: routed over every expert the
    router scores, computed for the experts the leaves stack (``first`` on),
    one at a time."""
    s = jax.nn.sigmoid(m @ moe["router"]["kernel"].astype(jnp.float32))
    _, sel = jax.lax.top_k(s + moe["bias"].astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, sel, axis=1)
    if renorm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    w = route_scale * w
    dense_w = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], sel].set(w)
    ex = moe["experts"]

    def one(e, acc):
        mats = [matrix(jax.lax.dynamic_index_in_dim(ex[n], e,
                                                    keepdims=False), variant)
                for n in ("gate", "up", "down")]
        return acc + gated(m, *mats) * jax.lax.dynamic_index_in_dim(
            dense_w, first + e, axis=1)

    y = jax.lax.fori_loop(0, ex["gate"].shape[0], one, jnp.zeros_like(m))
    sh = moe["shared"]
    return y + gated(m, *(matrix(sh[n], variant)
                          for n in ("gate", "up", "down")))


def kda_attention(h, at: Dict[str, Any], *, heads: int, d: int, eps: float,
                  carry_every: int, variant: str):
    """The KDA half of a layer on the normed stream ``h`` ``[T, hidden]``.
    ``carry_every``: the engine's chunk (``no_carry`` alone reads it: state
    and convolution start from nothing at every multiple of it)."""
    T = h.shape[0]
    pos = jnp.arange(T)
    start = (pos // carry_every) * carry_every if variant == "no_carry" \
        else jnp.zeros_like(pos)

    def conv(x, w):
        if variant == "no_conv":
            return jax.nn.silu(x)
        taps = w.shape[0]
        y = x * w[taps - 1]
        for back in range(1, taps):
            seen = (pos - back >= start)[:, None]
            y = y + jnp.where(seen, jnp.roll(x, back, axis=0), 0.0) \
                * w[taps - 1 - back]
        return jax.nn.silu(y)

    def heads_of(x):
        return x.reshape(T, heads, d)

    q, k, v = (heads_of(conv(h @ matrix(at[n], variant),
                             at[f"{n}_conv"].astype(jnp.float32)))
               for n in ("q", "k", "v"))
    if variant == "no_qk_l2norm":
        # the norm's data-dependent part dropped, its fixed part kept (a
        # vector of unit entries has norm sqrt(d)): with no scale at all
        # |k|^2 is in the tens, I - beta k k^T expands, the state overflows
        # and a NaN reference compares as equal to anything
        q, k = q * d ** -0.5, k * d ** -0.5
    else:
        q, k = (x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)
                for x in (q, k))
    q = q * d ** -0.5
    g = -jnp.exp(at["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        heads_of(h @ matrix(at["f_a"], variant) @ matrix(at["f_b"], variant))
        + at["dt_bias"].astype(jnp.float32).reshape(heads, d))
    alpha = jnp.exp(g)
    if variant == "no_decay":
        alpha = jnp.ones_like(alpha)
    elif variant == "decay_per_head":
        alpha = jnp.broadcast_to(alpha.mean(-1, keepdims=True), alpha.shape)
    beta = jax.nn.sigmoid(h @ matrix(at["b"], variant))    # [T, H]
    if variant == "no_beta":
        beta = jnp.ones_like(beta)
    fresh = pos == start                                   # no_carry's resets

    def token(S, x):
        q_t, k_t, v_t, a_t, b_t, new = x
        S = jnp.where(new, 0.0, S) * a_t[:, :, None]       # Diag(alpha) S
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, S))
        S = S + k_t[:, :, None] * u[:, None, :]
        if variant == "state_bf16":
            # reduce_precision, not a pair of casts: XLA is allowed excess
            # precision and drops a float32 -> bfloat16 -> float32 round trip
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v, alpha, beta,
                         fresh & (variant == "no_carry")))
    o = rms_norm(o, at["o_norm"]["scale"].astype(jnp.float32), eps)
    o = o * jax.nn.sigmoid(heads_of(h @ matrix(at["g_a"], variant)
                                    @ matrix(at["g_b"], variant)))
    return o.reshape(T, heads * d) @ matrix(at["o"], variant)


def mla_attention(h, at: Dict[str, Any], *, heads: int, rank: int, nope: int,
                  rope_dim: int, v_dim: int, eps: float, theta: float,
                  variant: str):
    """The MLA half of a layer: expanded, the "rope" dims unturned, the
    queries a block of ``QUERY_BLOCK`` at a time."""
    T = h.shape[0]
    pos = jnp.arange(T)
    q = (h @ matrix(at["q"], variant)).reshape(T, heads, nope + rope_dim)
    ckr = h @ matrix(at["kv_a"], variant)
    c = rms_norm(ckr[:, :rank], matrix(at["kv_norm"]["scale"]), eps)
    kr, qr = ckr[:, rank:], q[..., nope:]
    if variant == "rope_on":
        qr, kr = rope_pairs(qr, pos, theta), rope_pairs(kr, pos, theta)
    kv = (c @ matrix(at["kv_b"], variant)).reshape(T, heads, nope + v_dim)
    kn, v = kv[..., :nope].transpose(1, 0, 2), kv[..., nope:].transpose(
        1, 0, 2)                                           # [H, T, .]
    scale = (nope + rope_dim) ** -0.5
    blk = min(QUERY_BLOCK, T)
    # whole blocks of queries: the rows behind T are zeros and are dropped
    n_blk = -(-T // blk)
    q = jnp.pad(q, ((0, n_blk * blk - T), (0, 0), (0, 0)))
    qr = jnp.pad(qr, ((0, n_blk * blk - T), (0, 0), (0, 0)))

    def one_block(b0):
        rows = b0 + jnp.arange(blk)
        see = rows[:, None] >= pos[None, :]

        def one_head(args):
            qn_h, qr_h, kn_h, v_h = args
            s = (qn_h @ kn_h.T + qr_h @ kr.T) * scale
            return jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1) @ v_h

        qb = jax.lax.dynamic_slice_in_dim(q, b0, blk, axis=0)
        qrb = jax.lax.dynamic_slice_in_dim(qr, b0, blk, axis=0)
        o = jax.lax.map(one_head, (qb[..., :nope].transpose(1, 0, 2),
                                   qrb.transpose(1, 0, 2), kn, v))
        return o.transpose(1, 0, 2).reshape(blk, -1)       # [blk, H * v]

    o = jax.lax.map(one_block, jnp.arange(n_blk) * blk).reshape(
        n_blk * blk, -1)[:T]
    return o @ matrix(at["o"], variant)


@functools.partial(jax.jit, static_argnames=(
    "kda", "kda_heads", "kda_dim", "n_heads", "rank", "nope", "rope_dim",
    "v_dim", "eps", "theta", "moe", "top_k", "renorm", "route_scale",
    "first", "carry_every", "variant"))
def layer(x, lp: Dict[str, Any], *, kda: bool, kda_heads: int, kda_dim: int,
          n_heads: int, rank: int, nope: int, rope_dim: int, v_dim: int,
          eps: float, theta: float, moe: bool, top_k: int, renorm: bool,
          route_scale: float, first: int, carry_every: int,
          variant: str = ""):
    """One decoder layer over ``x`` ``[T, hidden]`` at positions 0..T-1.
    ``lp`` is the engine's layer tree."""
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, matrix(lp["attn_norm"]["scale"]), eps)
        if kda:
            x = x + kda_attention(h, lp["attn"], heads=kda_heads, d=kda_dim,
                                  eps=eps, carry_every=carry_every,
                                  variant=variant)
        else:
            x = x + mla_attention(h, lp["attn"], heads=n_heads, rank=rank,
                                  nope=nope, rope_dim=rope_dim, v_dim=v_dim,
                                  eps=eps, theta=theta, variant=variant)
        m = rms_norm(x, matrix(lp["mlp_norm"]["scale"]), eps)
        if moe:
            f = routed(m, lp["moe"], top_k=top_k, renorm=renorm,
                       route_scale=route_scale, first=first, variant=variant)
        else:
            f = gated(m, *(matrix(lp["mlp"][n], variant)
                           for n in ("gate", "up", "down")))
        return x + f


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, final_norm, *, eps: float):
    return rms_norm(x, matrix(final_norm), eps)


@functools.partial(jax.jit, static_argnames=("variant",))
def _head_block(xn, block, *, variant: str = ""):
    with jax.default_matmul_precision("highest"):
        return xn @ matrix(block, variant)


def log_probs(x, final_norm, head, *, eps: float, variant: str = ""):
    """Log-softmax over the vocabulary at every row of ``x``, the head
    ``VOCAB_BLOCK`` columns at a time (``weights_fp8``: one scale a block;
    the embedding is a lookup, not a product, and stays as it is served)."""
    kernel = head["kernel"] if isinstance(head, dict) else head
    xn = _normed(x, final_norm, eps=eps)
    logits = jnp.concatenate(
        [_head_block(xn, kernel[:, a:a + VOCAB_BLOCK], variant=variant)
         for a in range(0, kernel.shape[1], VOCAB_BLOCK)], axis=1)
    return jax.nn.log_softmax(logits, axis=-1)


def logprobs(params: Dict[str, Any], model: Dict[str, Any],
             ids: List[int], rows: List[int], pad_to: int,
             variant: str = "") -> np.ndarray:
    """Log-probabilities ``[len(rows), vocab]`` after each of the positions
    ``rows`` of the sequence ``ids``. ``params`` is the engine's tree;
    ``model`` the published config's keys (``linear_attn_config`` names the
    layers of each kind, 1-based; ``num_experts`` the experts held here,
    from ``experts_held_first`` on; ``engine.context_encoding_buckets``, or
    ``no_carry_every`` in a stand-in's file, the chunk ``no_carry`` resets
    at). The sequence is padded at its END to the next multiple of
    ``PAD_STEP`` and never past ``pad_to``: causality and the recurrence's
    direction keep the padding out of every real position."""
    seq = np.zeros((min(pad_to, -(-len(ids) // PAD_STEP) * PAD_STEP),),
                   np.int32)
    seq[:len(ids)] = ids
    x = jnp.take(params["embed"]["embedding"], jnp.asarray(seq), axis=0
                 ).astype(jnp.float32)
    lin = model["linear_attn_config"]
    carry_every = (max(model["engine"]["context_encoding_buckets"])
                   if "engine" in model else int(model["no_carry_every"]))
    for i in range(model["num_hidden_layers"]):
        x = layer(
            x, params[f"layer_{i}"], kda=(i + 1) in lin["kda_layers"],
            kda_heads=lin["num_heads"], kda_dim=lin["head_dim"],
            n_heads=model["num_attention_heads"],
            rank=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
            rope_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"],
            eps=model["rms_norm_eps"], theta=float(model["rope_theta"]),
            moe=i >= model["first_k_dense_replace"],
            top_k=model["num_experts_per_token"],
            renorm=bool(model["moe_renormalize"]),
            route_scale=float(model["routed_scaling_factor"]),
            first=int(model.get("experts_held_first", 0)),
            carry_every=int(carry_every), variant=variant)
    out = log_probs(x[jnp.asarray(rows)], params["final_norm"]["scale"],
                    params["lm_head"], eps=model["rms_norm_eps"],
                    variant=variant if variant == "weights_fp8" else "")
    return np.asarray(out)
