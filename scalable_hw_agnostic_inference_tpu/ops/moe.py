"""The routed expert layer: a sigmoid router, top-k on score plus a stored
bias, and ONE grouped matrix product over the experts a step touched.

``expert_layer`` serves prefill (thousands of tokens) and decode (a row's
``k`` assignments) alike: the ``N x k`` (token, expert) assignments are
sorted by expert, counted into group sizes, and pushed through three grouped
products (gate, up, down: ``jax.lax.ragged_dot`` over the stacked expert
leaves ``[E, D, F]`` / ``[E, F, D]``), then weighted and summed back onto
their tokens. 128 dense products would cost ``E / k`` times the routed
FLOPs; the grouped product costs the assignments' own, and reads the
weights of the experts that hold at least one.

The layer is told which experts it HOLDS (``held = (first, count)``: the
stacked leaves are that slice of the ``E``). It routes over all ``E`` —
every holder makes the same choice — and computes its own experts' part;
assignments to experts held elsewhere, and every assignment of an inactive
or padded row, go to no expert at all (they sort behind the last group and
carry weight 0). Summing the holders' parts, plus the shared expert once,
is the uncut layer.

No token is dropped, whatever the load: there is no capacity.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .quant import quant_matmul

#: the grouped product's name in a device trace (``jax.named_scope``; the
#: fused ops XLA makes of it carry it in their metadata, the ragged-dot
#: custom call in its name)
GROUPED_NAME = "moe_grouped_ffn"


def route(mp: Dict, x2: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """``x2`` ``[N, D]`` -> ``(sel [N, k] int32, w [N, k] float32)``: the
    experts each token goes to and the weight of each. Scores are a sigmoid
    in float32; the stored bias only selects; the chosen scores are
    renormalised (``route_norm``) and scaled (``route_scale``)."""
    s = jax.nn.sigmoid(
        x2.astype(jnp.float32) @ mp["router"]["kernel"].astype(jnp.float32))
    _, sel = jax.lax.top_k(s + mp["bias"].astype(jnp.float32),
                           cfg.n_experts_per_tok)
    w = jnp.take_along_axis(s, sel, axis=1)
    if cfg.route_norm:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * cfg.route_scale


def gated_mlp(p: Dict, x: jax.Array) -> jax.Array:
    """``Down(silu(Gate x) * Up x)`` over ``nn.Dense``-shaped leaves."""
    return quant_matmul(
        jax.nn.silu(quant_matmul(x, p["gate"])) * quant_matmul(x, p["up"]),
        p["down"])


def expert_layer(mp: Dict, x: jax.Array, cfg, *,
                 active: Optional[jax.Array] = None,
                 held: Optional[Tuple[int, int]] = None):
    """The routed FFN on ``x`` ``[..., D]``. Returns ``(y, stats)``:
    ``y`` like ``x``; ``stats`` int32 ``[2]``: distinct experts that got at
    least one assignment, and the largest assignment count on one expert
    (both over ALL experts and the active rows).

    ``active`` (bool, ``x``'s leading shape): rows that hold a real token.
    ``held``: ``(first, count)`` of the experts ``mp["experts"]`` stacks;
    default all."""
    E, k = cfg.n_experts, cfg.n_experts_per_tok
    first, count = held or (0, E)
    lead, D = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, D)
    N = x2.shape[0]
    sel, w = route(mp, x2, cfg)
    if active is not None:
        sel = jnp.where(active.reshape(N, 1), sel, E)     # to no expert
    flat = sel.reshape(N * k)
    # per-expert counts over every expert: the routing statistics, and
    # (sliced) the group sizes of the experts held here
    counts = jnp.sum(
        flat[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)
    stats = jnp.stack([jnp.sum(counts > 0, dtype=jnp.int32),
                       jnp.max(counts)])
    local = flat - first
    mine = (local >= 0) & (local < count)
    key = jnp.where(mine, local, count)                   # others: behind
    order = jnp.argsort(key, stable=True)
    tok = order // k
    xs = x2[tok]                                          # [N * k, D]
    sizes = counts[first:first + count]
    ex = mp["experts"]
    with jax.named_scope(GROUPED_NAME):
        g = jax.lax.ragged_dot(xs, ex["gate"], sizes)
        u = jax.lax.ragged_dot(xs, ex["up"], sizes)
        d = jax.lax.ragged_dot(jax.nn.silu(g) * u, ex["down"], sizes)
    ws = jnp.where(mine, w.reshape(N * k), 0.0)[order]
    # rows behind the last group hold whatever the product left there:
    # select, do not multiply (0 * garbage may be NaN)
    d = jnp.where((ws > 0)[:, None], d.astype(jnp.float32) * ws[:, None],
                  0.0)
    # back to token order by a gather through the sort's inverse (a
    # scatter of N * k integers, not a second sort), then the k parts of
    # each token summed
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(N * k, dtype=order.dtype))
    y = d[inverse].reshape(N, k, D).sum(axis=1).astype(x.dtype)
    if cfg.n_shared_experts:
        y = y + gated_mlp(mp["shared"], x2)
    return y.reshape(*lead, D), stats
