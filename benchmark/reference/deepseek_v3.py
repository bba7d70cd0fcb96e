"""DeepSeek-V3's forward pass as Kanana-2-30B-A3B configures it, plain:
``jax.numpy``, float32, no kernels, no cache, no batching, no grouped
product, attention EXPANDED only (the program decodes absorbed, so the check
is of one form against the other). Written from the ``config.json`` of
``kakaocorp/kanana-2-30b-a3b-instruct-2601`` (``model_type: deepseek_v3``)
and the public ``modeling_deepseek_v3.py`` of ``transformers``; it imports
nothing of the program.

On a sequence ``x`` of ``[T, hidden]`` (``x0 = Embed[ids]``), layer ``l``,
``h = n_in(x)`` (RMSNorm, ``rms_norm_eps``), ``H`` heads:

    q = Wq h                          [T, H, nope + rope]   (no q latent)
    [c ; kr] = Wkva h                 [T, rank], [T, rope]  ONE of each a token
    c = n_kv(c)                       RMSNorm over the latent, its own scale
    q^r, kr = rope(q^r), rope(kr)     theta, pairs on lanes (2i, 2i+1)
                                      (rope_interleave), no scaling
    [k^n_h ; v_h] = Wkvb_h c          [T, nope], [T, v] per head
    s_h(t, s) = (q^n_h(t) . k^n_h(s) + q^r_h(t) . kr(s)) / sqrt(nope + rope)
    o_h = causal_softmax(s_h) v_h;    x = x + Wo [o_1 .. o_H]
    m = n_mlp(x);                     x = x + FFN(m)

``FFN`` for ``l < first_k_dense_replace``: ``Down(silu(Gate m) * Up m)`` of
``intermediate_size``. Else ``s = sigmoid(Wr m)`` in float32; ``sel =
top_k(s + b)`` with ``b`` a stored vector that only selects (``noaux_tc``;
``n_group`` 1, so no group limit); ``w = s[sel]``, ``w = w / (sum w + 1e-20)``
(``norm_topk_prob``), ``w = routed_scaling_factor * w``; ``FFN(m) = sum_e w_e
Down_e(silu(Gate_e m) * Up_e m) + Shared(m)``, ``Shared`` ONE gated MLP of
width ``n_shared_experts * moe_intermediate_size``. The experts are walked
one at a time (the transient is one expert's three matrices in float32),
the heads one at a time, and the vocabulary in blocks (the head in float32
is a gigabyte): the reference runs beside the served model. ``logits =
W_head n_final(x)``, untied.

``variant`` exists for the tests and the chip check only: it breaks the
mathematics on purpose so that the tolerance can be shown to refuse it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# What ``tolerance.deepseek_v3.json`` must refuse by its LARGEST-difference
# bound, at the tiny size as at the published one (``tests/benchmark`` holds
# every name here to that bound): the rotary pairs taken as (i, i + rope/2),
# the latent's norm dropped. Nothing is pinned as accepted.
REFUSED_VARIANTS = ("rope_half_split", "no_kv_norm")
ACCEPTED_VARIANTS = ()
# What it must refuse by its MEAN bound (the largest difference of this
# architecture is set by discrete routing and by a softmax that a few keys
# carry, and is heavy-tailed; the mean over 192 positions is steady): the
# softmax scaled by the keys' width without position (128 ** -0.5), the
# chosen scores not renormalised (at published width the largest bound
# refuses it too, 5.2; at the tiny size it reads 2.2-3.8), the routed
# output not scaled, and the nearest precision below the one the
# configuration states for what it alone keeps: the CACHE ROWS on float8
# (e4m3), one scale a tensor. The tolerance file has the chip's readings.
REFUSED_BY_MEAN = ("scale_128", "no_renorm", "no_route_scale", "latent_fp8")
# What it does NOT refuse reliably, and says so: the experts' products on
# float8 operands read 0.26 where the right path reads 0.22-0.25 (the
# seeded attention is sharp, so a rounding anywhere is amplified layer by
# layer and the floor is high; the tolerance file says more).
NOT_REFUSED_RELIABLY = ("experts_fp8",)

#: vocabulary columns a block of the head holds
VOCAB_BLOCK = 16384
#: a sequence is padded to a multiple of this many positions
PAD_STEP = 2048


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, positions, theta, interleave: bool):
    """``x`` ``[T, ..., D]`` turned by ``positions`` ``[T]``: lanes
    ``(2i, 2i+1)`` by ``positions * theta ** (-2i / D)`` where
    ``interleave``, else lanes ``(i, i + D/2)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def matrix(leaf):
    """A weight as float32: a plain array or ``{"kernel": W}``."""
    if isinstance(leaf, dict):
        leaf = leaf["kernel"]
    return leaf.astype(jnp.float32)


def _to_fp8(a):
    """A tensor rounded to float8 e4m3 under one scale."""
    scale = jnp.max(jnp.abs(a)) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def gated(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def routed(m, moe: Dict[str, Any], *, top_k: int, renorm: bool,
           route_scale: float, variant: str):
    """The routed FFN on ``m`` ``[T, hidden]``, one expert at a time."""
    s = jax.nn.sigmoid(m @ moe["router"]["kernel"].astype(jnp.float32))
    _, sel = jax.lax.top_k(s + moe["bias"].astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, sel, axis=1)
    if renorm and variant != "no_renorm":
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    if variant != "no_route_scale":
        w = route_scale * w
    # [T, E]: a token's weight on each expert, 0 where it was not chosen
    dense_w = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], sel].set(w)
    ex = moe["experts"]

    def one(e, acc):
        mats = [jax.lax.dynamic_index_in_dim(ex[n], e, keepdims=False
                                             ).astype(jnp.float32)
                for n in ("gate", "up", "down")]
        if variant == "experts_fp8":
            gate, up, down = [_to_fp8(w_) for w_ in mats]
            m8 = _to_fp8(m)
            y_ = _to_fp8(jax.nn.silu(m8 @ gate) * (m8 @ up)) @ down
        else:
            y_ = gated(m, *mats)
        return acc + y_ * jax.lax.dynamic_index_in_dim(dense_w, e, axis=1)

    y = jax.lax.fori_loop(0, s.shape[1], one, jnp.zeros_like(m))
    sh = moe["shared"]
    return y + gated(m, matrix(sh["gate"]), matrix(sh["up"]),
                     matrix(sh["down"]))


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "rank", "nope", "rope_dim", "v_dim", "eps", "theta",
    "interleave", "moe", "top_k", "renorm", "route_scale", "variant"))
def layer(x, lp: Dict[str, Any], *, n_heads: int, rank: int, nope: int,
          rope_dim: int, v_dim: int, eps: float, theta: float,
          interleave: bool, moe: bool, top_k: int, renorm: bool,
          route_scale: float, variant: str = ""):
    """One decoder layer over ``x`` ``[T, hidden]`` at positions 0..T-1.
    ``lp`` is the engine's layer tree (``attn``: ``q``, ``kv_a``,
    ``kv_norm``, ``kv_b``, ``o``)."""
    with jax.default_matmul_precision("highest"):
        at = lp["attn"]
        T = x.shape[0]
        pos = jnp.arange(T)
        h = rms_norm(x, matrix(lp["attn_norm"]["scale"]), eps)
        q = (h @ matrix(at["q"])).reshape(T, n_heads, nope + rope_dim)
        ckr = h @ matrix(at["kv_a"])
        c, kr = ckr[:, :rank], ckr[:, rank:]
        if variant != "no_kv_norm":
            c = rms_norm(c, matrix(at["kv_norm"]["scale"]), eps)
        pairs = interleave and variant != "rope_half_split"
        qr = rope(q[..., nope:], pos, theta, pairs)       # [T, H, rope]
        kr = rope(kr, pos, theta, pairs)                  # [T, rope]
        if variant == "latent_fp8":
            c, kr = _to_fp8(c), _to_fp8(kr)
        kv = (c @ matrix(at["kv_b"])).reshape(T, n_heads, nope + v_dim)
        width = nope if variant == "scale_128" else nope + rope_dim
        see = pos[:, None] >= pos[None, :]

        def one_head(args):
            qn_h, qr_h, kn_h, v_h = args
            s = (qn_h @ kn_h.T + qr_h @ kr.T) / jnp.sqrt(jnp.float32(width))
            s = jnp.where(see, s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v_h

        o = jax.lax.map(one_head, (
            q[..., :nope].transpose(1, 0, 2), qr.transpose(1, 0, 2),
            kv[..., :nope].transpose(1, 0, 2),
            kv[..., nope:].transpose(1, 0, 2)))           # [H, T, v]
        x = x + o.transpose(1, 0, 2).reshape(T, -1) @ matrix(at["o"])
        m = rms_norm(x, matrix(lp["mlp_norm"]["scale"]), eps)
        if moe:
            f = routed(m, lp["moe"], top_k=top_k, renorm=renorm,
                       route_scale=route_scale, variant=variant)
        else:
            f = gated(m, matrix(lp["mlp"]["gate"]), matrix(lp["mlp"]["up"]),
                      matrix(lp["mlp"]["down"]))
        return x + f


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, final_norm, *, eps: float):
    return rms_norm(x, matrix(final_norm), eps)


@jax.jit
def _head_block(xn, block):
    with jax.default_matmul_precision("highest"):
        return xn @ block.astype(jnp.float32)


def log_probs(x, final_norm, head, *, eps: float):
    """Log-softmax over the vocabulary at every row of ``x``, the head
    ``VOCAB_BLOCK`` columns at a time."""
    kernel = head["kernel"] if isinstance(head, dict) else head
    xn = _normed(x, final_norm, eps=eps)
    logits = jnp.concatenate(
        [_head_block(xn, kernel[:, a:a + VOCAB_BLOCK])
         for a in range(0, kernel.shape[1], VOCAB_BLOCK)], axis=1)
    return jax.nn.log_softmax(logits, axis=-1)


def logprobs(params: Dict[str, Any], model: Dict[str, Any],
             ids: List[int], rows: List[int], pad_to: int,
             variant: str = "") -> np.ndarray:
    """Log-probabilities ``[len(rows), vocab]`` after each of the positions
    ``rows`` of the sequence ``ids``. ``params`` is the engine's tree
    (``embed``, ``layer_<i>`` with ``attn`` and ``mlp`` or ``moe`` and two
    norms, ``final_norm``, ``lm_head``); ``model`` the published config's
    keys. The sequence is padded at its END, to the next multiple of
    ``PAD_STEP`` and never past ``pad_to`` (the check's longest prompt), so
    that a few compiled layers of each kind serve every prompt and a short
    prompt does not pay a long one's attention; causality keeps the padding
    out of every real position."""
    seq = np.zeros((min(pad_to, -(-len(ids) // PAD_STEP) * PAD_STEP),),
                   np.int32)
    seq[:len(ids)] = ids
    x = jnp.take(params["embed"]["embedding"], jnp.asarray(seq), axis=0
                 ).astype(jnp.float32)
    for i in range(model["num_hidden_layers"]):
        x = layer(
            x, params[f"layer_{i}"],
            n_heads=model["num_attention_heads"],
            rank=model["kv_lora_rank"], nope=model["qk_nope_head_dim"],
            rope_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"],
            eps=model["rms_norm_eps"], theta=float(model["rope_theta"]),
            interleave=bool(model["rope_interleave"]),
            moe=i >= model["first_k_dense_replace"],
            top_k=model["num_experts_per_tok"],
            renorm=bool(model["norm_topk_prob"]),
            route_scale=float(model["routed_scaling_factor"]),
            variant=variant)
    out = log_probs(x[jnp.asarray(rows)], params["final_norm"]["scale"],
                    params["lm_head"], eps=model["rms_norm_eps"])
    return np.asarray(out)
