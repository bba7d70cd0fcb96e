"""Nemotron-H's forward pass as NVIDIA-Nemotron-3-Nano-30B-A3B configures
it, plain: ``jax.numpy``, float32, no kernels, no cache, no batching, no
chunked scan: the state-space recurrence is ONE ``lax.scan`` step a token
over the whole sequence (the program prefills in chunks of 128 through a
kernel, carries the state and the convolution's tail in a slot from program
to program and decodes one recurrent step a row, so the check is of those
forms against this one). Written from the ``config.json`` of
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` (``model_type: nemotron_h``),
the public ``modeling_nemotron_h.py`` and, for the mixer and its gated norm,
``transformers``' ``models/zamba2`` (``Zamba2MambaMixer.torch_forward``,
``Zamba2RMSNormGated``), which is this form; what those leave open is listed
under ``assumed`` in the configuration's file. It imports nothing of the
program.

On a sequence ``x`` of ``[T, hidden]`` (``x0 = Embed[ids]``) every block is
ONE part behind ONE norm, by the letter of ``hybrid_override_pattern``:

    x <- x + Part(rmsnorm(x; layer_norm_epsilon))

``M`` (a Mamba-2 mixer: ``H`` heads of ``P``, state ``N``, ``G`` groups):

    [z | xBC | dt] = W_in h                        (H P | H P + 2 G N | H)
    xBC = silu(conv4(xBC) + b_conv)                causal, depthwise
    xBC -> x [H, P] | B [G, N] | C [G, N]
    dt = softplus(dt + dt_bias);  a = exp(-exp(A_log) dt)       a head
    S_t = a_t S_{t-1} + dt_t x_t (outer) B_t       [P, N] a head, group h // (H/G)
    y_t = S_t C_t + D x_t
    y = rmsnorm over each of G groups of H P / G channels (y * silu(z)) * w
    out = W_out y

``*`` (attention): ``q, k, v, o`` without bias, grouped query heads, causal
softmax at ``head_dim ** -0.5``, NO positional embedding.

``E`` (routed): ``s = sigmoid(W_r m)`` in float32 over ALL published
experts; ``sel = top_k(s + b)``; ``w = s[sel] / (sum + 1e-20)``
(``norm_topk_prob``), ``w = routed_scaling_factor * w``; an expert is
``W_down relu(W_up m) ** 2`` (two matrices, no gate); ``E(m) = sum over the
experts HELD here + Shared(m)``: the stacked expert leaves are experts
``first .. first + count`` of the router's (this chip's share of an
expert-parallel layout; what the absent experts would add is left out, here
as in the program).

After the last block a final RMSNorm, then the untied head.

``variant`` exists for the tests and the chip check only: it breaks the
mathematics on purpose so that the tolerance can be shown to refuse it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# What ``tolerance.nemotron_h.json`` refuses by EVERY bound, at the tiny size
# (``tests/benchmark`` holds every name here to the largest-difference bound
# on the dry run's two short prompts) as at the published one: the mixer
# without its skip ``D x``.
REFUSED_VARIANTS = ("no_skip_D",)
ACCEPTED_VARIANTS = ()
# What it refuses by its MEAN bound (at the published widths every one but
# the last by the largest difference too: the tolerance file has the
# readings of both sizes): a continuation chunk
# that starts from a zero state and tail (``no_carry``: THE variant that
# ties the check to the engine's carry), the convolution blind to the
# tokens before a chunk (``no_conv_tail``: the tail alone), one norm over
# all channels, silu where ``relu ** 2`` stands; and THE PRECISION CONTROL,
# ``weights_fp8``: every matrix a product reads, and the head, in float8
# e4m3 under one scale a matrix, refused by the mean and by it alone.
REFUSED_BY_MEAN = ("no_carry", "no_conv_tail", "norm_ungrouped",
                   "silu_for_relu2", "weights_fp8")
# What NO bound on the logits refuses reliably, said so in the tolerance file
# with its readings and why: the recurrent STATE, float32 as served, rounded
# to bfloat16 after every token; and rotary embedding on the ONE attention
# block of nine after all (``rope_on``: two to three times the right path's
# mean at the published widths, at the mean bound on one seed of two).
NOT_REFUSED_RELIABLY = ("state_bf16", "rope_on")

#: vocabulary columns a block of the head holds
VOCAB_BLOCK = 16384
#: a sequence is padded to a multiple of this many positions
PAD_STEP = 1024


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def fp8(w):
    """``w`` rounded to float8 e4m3 under one scale: its largest entry lands
    on 240. ``reduce_precision`` and not a pair of casts, which XLA's
    excess-precision rule may drop."""
    scale = jnp.max(jnp.abs(w)) / 240.0
    return jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def matrix(leaf, variant: str = ""):
    """A weight as float32: a plain array or ``{"kernel": W}``
    (``weights_fp8``: every matrix a product reads, through ``fp8``; the
    norms' scales, the router, the convolution's taps and bias, ``A_log``,
    ``dt_bias`` and ``D`` are read without a variant and stay as served)."""
    if isinstance(leaf, dict):
        leaf = leaf["kernel"]
    w = leaf.astype(jnp.float32)
    return fp8(w) if variant == "weights_fp8" else w


def relu2(m, up, down, variant: str = ""):
    u = m @ up
    act = jax.nn.silu(u) if variant == "silu_for_relu2" else jnp.square(
        jax.nn.relu(u))
    return act @ down


def rope_halves(x, positions, theta):
    """``x`` ``[T, H, D]`` turned on lanes ``(i, i + D/2)`` (``rope_on``
    only: the model itself turns nothing)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def routed(m, moe: Dict[str, Any], *, top_k: int, renorm: bool,
           route_scale: float, first: int, variant: str = ""):
    """The routed part on ``m`` ``[T, hidden]``: routed over every expert
    the router scores, computed for the experts the leaves stack (``first``
    on), one at a time."""
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
        # both of an expert's matrices are stacked by its 1856 rows
        # ([E, F, D]): ``up`` is W_up as torch holds it, ``down`` W_down^T
        up, down = (matrix(jax.lax.dynamic_index_in_dim(
            ex[n], e, keepdims=False), variant) for n in ("up", "down"))
        return acc + relu2(m, up.T, down, variant) \
            * jax.lax.dynamic_index_in_dim(dense_w, first + e, axis=1)

    y = jax.lax.fori_loop(0, ex["down"].shape[0], one, jnp.zeros_like(m))
    sh = moe["shared"]
    return y + relu2(m, matrix(sh["up"], variant),
                     matrix(sh["down"], variant), variant)


def mixer(h, at: Dict[str, Any], *, heads: int, p: int, n: int, groups: int,
          eps: float, carry_every: int, variant: str):
    """The Mamba-2 mixer on the normed stream ``h`` ``[T, hidden]``.
    ``carry_every``: the engine's chunk (``no_carry`` and ``no_conv_tail``
    alone read it: state and convolution, or the convolution alone, start
    from nothing at every multiple of it)."""
    T = h.shape[0]
    inner, gn = heads * p, groups * n
    pos = jnp.arange(T)
    blind = variant in ("no_carry", "no_conv_tail")
    start = (pos // carry_every) * carry_every if blind \
        else jnp.zeros_like(pos)
    zxd = h @ matrix(at["in"], variant)
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:2 * inner + 2 * gn],
                  zxd[:, 2 * inner + 2 * gn:])
    w = at["conv"].astype(jnp.float32)                       # [taps, chan]
    taps = w.shape[0]
    y = xbc * w[taps - 1]
    for back in range(1, taps):
        seen = (pos - back >= start)[:, None]
        y = y + jnp.where(seen, jnp.roll(xbc, back, axis=0), 0.0) \
            * w[taps - 1 - back]
    xbc = jax.nn.silu(y + at["conv_bias"].astype(jnp.float32))
    x = xbc[:, :inner].reshape(T, heads, p)
    B = jnp.repeat(xbc[:, inner:inner + gn].reshape(T, groups, n),
                   heads // groups, axis=1)                  # [T, H, N]
    C = jnp.repeat(xbc[:, inner + gn:].reshape(T, groups, n),
                   heads // groups, axis=1)
    dt = jax.nn.softplus(dt + at["dt_bias"].astype(jnp.float32))  # [T, H]
    a = jnp.exp(-jnp.exp(at["A_log"].astype(jnp.float32)) * dt)
    fresh = (pos == start) & (variant == "no_carry")

    def token(S, t):
        x_t, b_t, c_t, dt_t, a_t, new = t
        S = jnp.where(new, 0.0, S) * a_t[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if variant == "state_bf16":
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hpn,hn->hp", S, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((heads, p, n), jnp.float32),
                        (x, B, C, dt, a, fresh))
    if variant != "no_skip_D":
        y = y + at["D"].astype(jnp.float32)[:, None] * x
    y = y.reshape(T, inner) * jax.nn.silu(z)
    scale = at["norm"]["scale"].astype(jnp.float32)
    if variant == "norm_ungrouped":
        y = rms_norm(y, scale, eps)
    else:
        y = rms_norm(y.reshape(T, groups, inner // groups), 1.0,
                     eps).reshape(T, inner) * scale
    return y @ matrix(at["o"], variant)


def attention(h, at: Dict[str, Any], *, heads: int, kv_heads: int, d: int,
              theta: float, variant: str):
    """The attention block: grouped query heads, causal, no positional
    embedding."""
    T = h.shape[0]
    pos = jnp.arange(T)
    q = (h @ matrix(at["q"], variant)).reshape(T, heads, d)
    k = (h @ matrix(at["k"], variant)).reshape(T, kv_heads, d)
    v = (h @ matrix(at["v"], variant)).reshape(T, kv_heads, d)
    if variant == "rope_on":
        q, k = rope_halves(q, pos, theta), rope_halves(k, pos, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1).transpose(1, 0, 2)
    v = jnp.repeat(v, heads // kv_heads, axis=1).transpose(1, 0, 2)
    see = pos[:, None] >= pos[None, :]

    def one_head(args):
        q_h, k_h, v_h = args
        s = (q_h @ k_h.T) * d ** -0.5
        return jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1) @ v_h

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2), k, v))
    return o.transpose(1, 0, 2).reshape(T, heads * d) @ matrix(at["o"],
                                                               variant)


@functools.partial(jax.jit, static_argnames=(
    "letter", "ssm_heads", "ssm_p", "ssm_n", "ssm_groups", "n_heads",
    "kv_heads", "head_dim", "eps", "theta", "top_k", "renorm",
    "route_scale", "first", "carry_every", "variant"))
def block(x, lp: Dict[str, Any], *, letter: str, ssm_heads: int, ssm_p: int,
          ssm_n: int, ssm_groups: int, n_heads: int, kv_heads: int,
          head_dim: int, eps: float, theta: float, top_k: int, renorm: bool,
          route_scale: float, first: int, carry_every: int,
          variant: str = ""):
    """One block over ``x`` ``[T, hidden]`` at positions 0..T-1. ``lp`` is
    the engine's layer tree."""
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x, matrix(lp["norm"]["scale"]), eps)
        if letter == "M":
            return x + mixer(h, lp["attn"], heads=ssm_heads, p=ssm_p,
                             n=ssm_n, groups=ssm_groups, eps=eps,
                             carry_every=carry_every, variant=variant)
        if letter == "*":
            return x + attention(h, lp["attn"], heads=n_heads,
                                 kv_heads=kv_heads, d=head_dim, theta=theta,
                                 variant=variant)
        assert letter == "E", letter
        return x + routed(h, lp["moe"], top_k=top_k, renorm=renorm,
                          route_scale=route_scale, first=first,
                          variant=variant)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, final_norm, *, eps: float):
    return rms_norm(x, matrix(final_norm), eps)


@functools.partial(jax.jit, static_argnames=("variant",))
def _head_block(xn, blk, *, variant: str = ""):
    with jax.default_matmul_precision("highest"):
        return xn @ matrix(blk, variant)


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
    ``model`` the published config's keys (``hybrid_override_pattern`` names
    each block's part; ``n_routed_experts`` the experts held here, from
    ``experts_held_first`` on; ``engine.context_encoding_buckets``, or
    ``no_carry_every`` in a stand-in's file, the chunk ``no_carry`` and
    ``no_conv_tail`` reset at). The sequence is padded at its END to the
    next multiple of ``PAD_STEP`` and never past ``pad_to``: causality and
    the recurrence's direction keep the padding out of every real
    position."""
    seq = np.zeros((min(pad_to, -(-len(ids) // PAD_STEP) * PAD_STEP),),
                   np.int32)
    seq[:len(ids)] = ids
    x = jnp.take(params["embed"]["embedding"], jnp.asarray(seq), axis=0
                 ).astype(jnp.float32)
    carry_every = (max(model["engine"]["context_encoding_buckets"])
                   if "engine" in model else int(model["no_carry_every"]))
    pattern = model["hybrid_override_pattern"]
    assert len(pattern) == model["num_hidden_layers"]
    for i, letter in enumerate(pattern):
        x = block(
            x, params[f"layer_{i}"], letter=letter,
            ssm_heads=model["mamba_num_heads"],
            ssm_p=model["mamba_head_dim"], ssm_n=model["ssm_state_size"],
            ssm_groups=model["n_groups"],
            n_heads=model["num_attention_heads"],
            kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"],
            eps=float(model["layer_norm_epsilon"]),
            theta=float(model["rope_theta"]),
            top_k=model["num_experts_per_tok"],
            renorm=bool(model["norm_topk_prob"]),
            route_scale=float(model["routed_scaling_factor"]),
            first=int(model.get("experts_held_first", 0)),
            carry_every=int(carry_every), variant=variant)
    out = log_probs(x[jnp.asarray(rows)], params["final_norm"]["scale"],
                    params["lm_head"],
                    eps=float(model["layer_norm_epsilon"]),
                    variant=variant if variant == "weights_fp8" else "")
    return np.asarray(out)
