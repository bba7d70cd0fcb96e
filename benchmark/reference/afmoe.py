"""AFMoE's forward pass (Trinity-Mini), plain: ``jax.numpy``, float32, no
kernels, no cache, no batching, no grouped product. Written from the
``config.json`` of ``arcee-ai/Trinity-Mini`` and, where that file has no key,
from the public ``modeling_afmoe.py`` of ``transformers`` (the configuration
file lists each such item under ``assumed``); it imports nothing of the
program. The sandbox's ``transformers`` has no ``models/afmoe``, so this
module is the repo's statement of the mathematics.

On a sequence ``x`` of ``[T, hidden]`` (``x0 = Embed[ids] * sqrt(hidden)``,
``mup_enabled``), layer ``l``:

    a = n_in(x);  q = Wq a  [T, H, D];  k = Wk a, v = Wv a  [T, Hkv, D];  g = Wg a
    q = n_q(q), k = n_k(k)            RMSNorm over each head's D, one scale each
    sliding_attention: rope(q), rope(k) (theta, half-rotation), query i sees
        keys j with 0 <= i - j < sliding_window
    full_attention:    NO rotary embedding, plain causal mask
    o = softmax(q k^T / sqrt(D)) v    H / Hkv query heads share a KV head
    x = x + n_post_attn(Wo (o * sigmoid(g)))
    m = n_pre_mlp(x);  x = x + n_post_mlp(FFN(m))

``FFN`` for ``l < num_dense_layers``: ``Down(silu(Gate m) * Up m)``. Else:
``s = sigmoid(Wr m)`` in float32; ``sel = top_k(s + b)`` with ``b`` a stored
vector that only selects; ``w = s[sel]``, ``w = w / (sum w + 1e-20)``
(``route_norm``), ``w = route_scale * w``; ``FFN(m) = sum_e w_e Down_e(
silu(Gate_e m) * Up_e m) + Shared(m)``. No token is dropped. The experts are
walked ONE AT A TIME (every token through expert ``e``, weighted by its
``w_e``, which is 0 where ``e`` was not chosen), so the transient is one
expert's three matrices in float32 (25 MB at published width) and never a
layer's 3.2 GB; attention is walked one KV head's group at a time for the
same reason. ``logits = W_head n_final(x)``, untied.

``variant`` exists for the tests and the chip check only: it breaks the
mathematics on purpose so that the tolerance can be shown to refuse it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# What ``tolerance.afmoe.json`` must refuse by its LARGEST-difference bound,
# at the tiny size as at the published one (``tests/benchmark`` holds every
# name here to that bound): the window dropped from the sliding layers, the
# output gate dropped. Nothing is pinned as accepted.
REFUSED_VARIANTS = ("no_window", "no_gate")
ACCEPTED_VARIANTS = ()
# What it must refuse by its MEAN bound. The largest difference of this
# architecture is set by discrete routing (one of a token's eight experts
# flipped on rounding moves a log-probability by up to 1.1 on the right
# path), so a variant that shifts every position a little shows in the mean
# and not in the largest: rotary embedding applied on the full layers too,
# the chosen scores not renormalised, and the nearest precision below the one
# the configuration states: the experts' three products on float8 (e4m3)
# operands, weights and activations, one scale a tensor, accumulated in
# float32. At published width on the chip the mean bound refuses all three
# (the tolerance file has the readings); at the tiny size the tests hold the
# first two to it and the third to a multiple of the right path's mean.
REFUSED_BY_MEAN = ("rope_on_full", "no_renorm", "experts_fp8")


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, positions, theta):
    """``x`` ``[T, H, D]``, ``positions`` ``[T]``; half-rotation layout."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def matrix(leaf):
    """A weight as float32: a plain array or ``{"kernel": W}``."""
    if isinstance(leaf, dict):
        leaf = leaf["kernel"]
    return leaf.astype(jnp.float32)


def _to_fp8(a):
    """A tensor rounded to float8 e4m3 under one scale: an operand of the
    wrong-on-purpose 'experts' products in the nearest lower precision'."""
    scale = jnp.max(jnp.abs(a)) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def gated(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def routed(m, moe: Dict[str, Any], *, top_k: int, route_norm: bool,
           route_scale: float, variant: str):
    """The routed FFN on ``m`` ``[T, hidden]``, one expert at a time."""
    s = jax.nn.sigmoid(m @ moe["router"]["kernel"].astype(jnp.float32))
    _, sel = jax.lax.top_k(s + moe["bias"].astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, sel, axis=1)
    if route_norm and variant != "no_renorm":
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    w = route_scale * w
    n_experts = s.shape[1]
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
        elif variant == "experts_fp8_weights":
            # the weights alone rounded, activations left in float32: a
            # measured control that neither list claims (the tolerance
            # file has its readings)
            y_ = gated(m, *[_to_fp8(w_) for w_ in mats])
        else:
            y_ = gated(m, *mats)
        return acc + y_ * jax.lax.dynamic_index_in_dim(dense_w, e, axis=1)

    y = jax.lax.fori_loop(0, n_experts, one, jnp.zeros_like(m))
    sh = moe["shared"]
    return y + gated(m, matrix(sh["gate"]), matrix(sh["up"]),
                     matrix(sh["down"]))


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "eps", "theta", "window", "use_rope", "moe", "top_k",
    "route_norm", "route_scale", "variant"))
def layer(x, lp: Dict[str, Any], *, n_heads: int, n_kv: int, eps: float,
          theta: float, window: int, use_rope: bool, moe: bool, top_k: int,
          route_norm: bool, route_scale: float, variant: str = ""):
    """One decoder layer over ``x`` ``[T, hidden]`` at positions 0..T-1.
    ``lp`` is the engine's layer tree; ``window`` 0 = plain causal."""
    with jax.default_matmul_precision("highest"):
        at = lp["attn"]
        T = x.shape[0]
        pos = jnp.arange(T)
        a = rms_norm(x, matrix(lp["attn_norm"]["scale"]), eps)
        q = (a @ matrix(at["q"])).reshape(T, n_heads, -1)
        k = (a @ matrix(at["k"])).reshape(T, n_kv, -1)
        v = (a @ matrix(at["v"])).reshape(T, n_kv, -1)
        g = a @ matrix(at["gate"])
        q = rms_norm(q, matrix(at["q_norm"]["scale"]), eps)
        k = rms_norm(k, matrix(at["k_norm"]["scale"]), eps)
        if use_rope or variant == "rope_on_full":
            q, k = rope(q, pos, theta), rope(k, pos, theta)
        behind = pos[:, None] - pos[None, :]              # i - j
        see = behind >= 0
        if window and variant != "no_window":
            see = see & (behind < window)
        group = n_heads // n_kv
        qg = q.reshape(T, n_kv, group, -1).transpose(1, 2, 0, 3)

        def one_kv_head(args):
            qh, kh, vh = args             # [group, T, D], [T, D], [T, D]
            s = jnp.einsum("gtd,sd->gts", qh, kh) / jnp.sqrt(
                jnp.float32(qh.shape[-1]))
            s = jnp.where(see[None], s, -jnp.inf)
            return jnp.einsum("gts,sd->gtd", jax.nn.softmax(s, axis=-1), vh)

        o = jax.lax.map(one_kv_head, (qg, k.transpose(1, 0, 2),
                                      v.transpose(1, 0, 2)))
        o = o.transpose(2, 0, 1, 3).reshape(T, -1)        # [T, H * D]
        if variant != "no_gate":
            o = o * jax.nn.sigmoid(g)
        x = x + rms_norm(o @ matrix(at["o"]),
                         matrix(lp["post_attn_norm"]["scale"]), eps)
        m = rms_norm(x, matrix(lp["mlp_norm"]["scale"]), eps)
        if moe:
            f = routed(m, lp["moe"], top_k=top_k, route_norm=route_norm,
                       route_scale=route_scale, variant=variant)
        else:
            f = gated(m, matrix(lp["mlp"]["gate"]), matrix(lp["mlp"]["up"]),
                      matrix(lp["mlp"]["down"]))
        return x + rms_norm(f, matrix(lp["post_mlp_norm"]["scale"]), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def log_probs(x, final_norm, head, *, eps: float):
    """Log-softmax over the vocabulary at every row of ``x``."""
    with jax.default_matmul_precision("highest"):
        logits = rms_norm(x, matrix(final_norm), eps) @ matrix(head)
        return jax.nn.log_softmax(logits, axis=-1)


def logprobs(params: Dict[str, Any], model: Dict[str, Any],
             ids: List[int], rows: List[int], pad_to: int,
             variant: str = "") -> np.ndarray:
    """Log-probabilities ``[len(rows), vocab]`` after each of the positions
    ``rows`` of the sequence ``ids``. ``params`` is the engine's tree
    (``embed``, ``layer_<i>`` with ``attn`` and ``mlp`` or ``moe`` and four
    norms, ``final_norm``, ``lm_head``); ``model`` the published config's
    keys. The sequence is padded at its END to ``pad_to`` so that one
    compiled layer of each kind serves every prompt; causality keeps the
    padding out of every real position."""
    seq = np.zeros((pad_to,), np.int32)
    seq[:len(ids)] = ids
    x = jnp.take(params["embed"]["embedding"], jnp.asarray(seq), axis=0
                 ).astype(jnp.float32)
    if model.get("mup_enabled"):
        x = x * jnp.sqrt(jnp.float32(model["hidden_size"]))
    for i in range(model["num_hidden_layers"]):
        sliding = model["layer_types"][i] == "sliding_attention"
        x = layer(
            x, params[f"layer_{i}"],
            n_heads=model["num_attention_heads"],
            n_kv=model["num_key_value_heads"], eps=model["rms_norm_eps"],
            theta=float(model["rope_theta"]),
            window=int(model["sliding_window"]) if sliding else 0,
            use_rope=sliding, moe=i >= model["num_dense_layers"],
            top_k=model["num_experts_per_tok"],
            route_norm=bool(model["route_norm"]),
            route_scale=float(model["route_scale"]), variant=variant)
    out = log_probs(x[jnp.asarray(rows)], params["final_norm"]["scale"],
                    params["lm_head"], eps=model["rms_norm_eps"])
    return np.asarray(out)
