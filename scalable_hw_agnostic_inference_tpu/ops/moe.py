"""The routed expert layer: a sigmoid router, top-k on score plus a stored
bias, and ONE product over the experts a step touched, in the form the
call's row count asks for.

``expert_layer`` serves prefill (thousands of tokens) and decode (a row's
``k`` assignments) alike, and ``expert_form`` — a pure function of the
call's static row count and the model's widths, which the engine's
accounting calls too — picks how the product is made:

- ``"streamed"`` (every decode bucket: at most ``STREAMED_MAX_ROWS`` rows):
  ``ops.pallas.moe_ffn`` pushes ALL the rows through every touched expert
  and weights them by a dense ``[N, E]`` combine matrix, 0 where a row did
  not choose the expert: no sort, no permutation, no ragged boundary, no
  scatter. It costs ``E_touched / k`` times the routed FLOPs, which at few
  rows hide under the bytes: each touched expert is read once, the next one
  in flight meanwhile. A Kanana-2 decode layer alone (64 rows, 113 experts
  touched): 2.35 ms grouped, 1.43 ms streamed, 91% of its bytes' time (my
  chip run, PR 33; ``scripts/moe_bench.py``).
- ``"tiled"`` (prefill, continuation, verify: 512-2048 tokens, 32-96 rows an
  expert): the ``N x k`` (token, expert) assignments are grouped by expert
  into a layout in which each group starts on a tile of 64 or 128 rows
  (``tiled_operands``: no sort, an assignment's place is the count of
  earlier ones to its expert), the rows gathered into it, and ONE kernel
  (``ops.pallas.moe_ffn.moe_tiled_ffn``) walks the row tiles, keeping an
  expert's three matrices across its tiles: gate, up, ``silu * up`` and
  down fused, each held expert that got a row read once a call. The result
  is weighted and summed back onto its tokens in float32. It costs the
  assignments' own FLOPs rounded up to whole tiles (at 64 rows an expert
  twice the routed FLOPs, 1.2 ms of MXU time at Kimi-Linear's widths, under
  the 2.2 ms its 128 held experts' bytes take), a gather of the rows into
  the layout and one back: 4.20 ms a Kimi-Linear layer of 2,048 tokens,
  3.12 Kanana-2's, 2.94 Trinity-Mini's of 1,024, the kernel 67-81% of the
  held bytes' time (my chip run, PR 37).
- ``"grouped"`` (widths no kernel can tile, ``D`` no multiple of 128 or
  ``F`` none of 64: the CPU stand-ins, whatever their rows; the plain form
  and both kernels' oracle): the assignments sorted by expert, counted into group
  sizes, pushed through three grouped products (gate, up, down:
  ``jax.lax.ragged_dot`` over the stacked expert leaves ``[E, D, F]`` /
  ``[E, F, D]``), then weighted and summed back. It costs the assignments'
  own FLOPs plus a sort, a gather and a scatter of ``N x k`` rows, writes
  ``g``, ``u`` and ``silu(g) * u`` to HBM between its calls, and on the chip
  libtpu's grouped product does not overlap an expert's bytes with its
  weight pushes: 55-61% of the bytes' time at two or three rows an expert
  (ledger, PR 32), and at a prefill chunk's 64-96 rows an expert 23-32%:
  a Kimi-Linear layer of 2,048 tokens (top-8 of 256, 128 of 2304 x 1024
  held) took 9.13 ms where its bytes take 2.21, Kanana-2's (top-6, 128 of
  2048 x 768) 6.35 for 1.48, Trinity-Mini's 1,024 tokens (top-8, 128 of
  2048 x 1024) 6.09 for 1.97 (my chip run, PR 37; ``scripts/moe_bench.py``;
  PERF.md section 6).

The bound between streamed and tiled comes from the chip's peaks and is no
option: an expert's bytes over 819 GB/s against ``N`` rows of its FLOPs over
197 TFLOP/s cross near 480 rows (all rows through every expert would cost a
2,048-token chunk 21 times its routed FLOPs); ``STREAMED_MAX_ROWS`` 128 is
one MXU tile of rows and leaves the margin the weight pushes need. All
three forms read the same leaves in the same layout; nothing is repacked at
load.

The layer is told which experts it HOLDS (``held = (first, count)``: the
stacked leaves are that slice of the ``E``). It routes over all ``E`` —
every holder makes the same choice — and computes its own experts' part;
assignments to experts held elsewhere, and every assignment of an inactive
or padded row, go to no expert at all (grouped: they sort behind the last
group and carry weight 0; tiled: they have no row in the layout and are
selected away; streamed: a zero of the combine matrix). Summing
the holders' parts, plus the shared expert once, is the uncut layer.

What an expert IS is data (``cfg.mlp_act``), a static argument of every
form and part of no name: ``"silu"``, ``Down(silu(Gate x) * Up x)`` over
three matrices stacked ``[E, D, F]``, ``[E, D, F]``, ``[E, F, D]``; or
``"relu2"``, ``Down(relu(Up x) ** 2)`` over TWO, both stacked by the expert's
``F`` rows (``[E, F, D]``: ``ops.pallas.moe_ffn.first_products`` says why),
so that a width that is no multiple of 128 (1856 = 14 x 128 + 64) is taken
whole by both kernels. Alone on a v5e at 2688 x 1856, 64 of 128 experts
held (``scripts/ssm_bench.py``; my chip run, PR 45): streamed, 128 rows, 61
held experts touched 1.67 ms, 89% of their bytes' time (the plain form
15.5); tiled, 2,048 rows 3.42 ms, 46% (the plain form 22.1).

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

#: the most rows a call may hold and take the streamed form: one MXU tile
#: of rows (module docstring: the peaks cross near 480)
STREAMED_MAX_ROWS = 128


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


def gated_mlp(p: Dict, x: jax.Array, act: str = "silu") -> jax.Array:
    """``Down(silu(Gate x) * Up x)`` over ``nn.Dense``-shaped leaves; with
    ``act`` ``"relu2"`` the two-matrix ``Down(relu(Up x) ** 2)`` (no
    ``gate`` leaf)."""
    if act == "relu2":
        return quant_matmul(
            jnp.square(jax.nn.relu(quant_matmul(x, p["up"]))), p["down"])
    return quant_matmul(
        jax.nn.silu(quant_matmul(x, p["gate"])) * quant_matmul(x, p["up"]),
        p["down"])


def expert_counts(sel: jax.Array, n_experts: int) -> jax.Array:
    """``[n_experts]`` int32: the assignments each expert got (an id of
    ``n_experts`` or more, a row that chose no expert, counts nowhere)."""
    return jnp.sum(
        sel.reshape(-1)[:, None]
        == jnp.arange(n_experts, dtype=jnp.int32)[None, :],
        axis=0, dtype=jnp.int32)


def expert_form(n_rows: int, cfg) -> str:
    """``"streamed"``, ``"tiled"`` or ``"grouped"``: the form the expert
    product of a call with ``n_rows`` rows takes (module docstring). A
    function of the static row count and the model's widths, and of nothing
    else."""
    # ``D`` is a block's lanes and must be whole tiles; ``F`` is taken in
    # tiles of 128 or, where it is no multiple of that, whole (1856 = 14 x
    # 128 + 64): half a tile of lanes is the least a whole-width block
    # leaves the MXU
    if cfg.dim % 128 or cfg.moe_mlp_dim % 64:
        return "grouped"
    return "streamed" if n_rows <= STREAMED_MAX_ROWS else "tiled"


def _grouped(ex: Dict, x2: jax.Array, sel: jax.Array, w: jax.Array,
             sizes: jax.Array, first: int, act: str = "silu") -> jax.Array:
    """``[N, D]`` float32: the ``N x k`` assignments sorted by expert,
    through three grouped products (two of an ungated expert), weighted and
    summed back."""
    N, k = sel.shape
    count = sizes.shape[0]
    local = sel.reshape(N * k) - first
    mine = (local >= 0) & (local < count)
    key = jnp.where(mine, local, count)                   # others: behind
    order = jnp.argsort(key, stable=True)
    tok = order // k
    xs = x2[tok]                                          # [N * k, D]
    with jax.named_scope(GROUPED_NAME):
        if act == "relu2":
            # an ungated expert's ``up`` is stacked by rows, ``[E, F, D]``
            # (``ops.pallas.moe_ffn.first_products`` says why)
            u = jax.lax.ragged_dot(xs, jnp.swapaxes(ex["up"], 1, 2), sizes)
            h = jnp.square(jax.nn.relu(u))
        else:
            g = jax.lax.ragged_dot(xs, ex["gate"], sizes)
            u = jax.lax.ragged_dot(xs, ex["up"], sizes)
            h = jax.nn.silu(g) * u
        d = jax.lax.ragged_dot(h, ex["down"], sizes)
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
    return d[inverse].reshape(N, k, -1).sum(axis=1)


def _tile_experts(sizes: jax.Array, tm: int, tiles: int):
    """``(pstart [count], tile_expert [tiles], n_tiles)``: where each
    expert's group starts in a layout that pads every group to a multiple
    of ``tm`` rows, the expert of each of its ``tiles`` row tiles (behind
    the ``n_tiles`` real ones the last real one's again), and how many
    tiles are real."""
    count = sizes.shape[0]
    per = -(-sizes // tm)                                 # tiles an expert
    end = jnp.cumsum(per)
    steps = jnp.arange(tiles, dtype=jnp.int32)
    tile_expert = jnp.sum(end[None, :] <= steps[:, None], axis=1,
                          dtype=jnp.int32)
    last = jnp.max(jnp.where(per > 0, jnp.arange(count, dtype=jnp.int32), 0))
    return (end - per) * tm, jnp.minimum(tile_expert, last), end[-1]


def tiled_operands(sel: jax.Array, sizes: jax.Array, first: int, tm: int,
                   block: int = 256):
    """What the tiled kernel is told of a routing, for the ``count`` experts
    held from ``first`` on: the assignments grouped by expert, in token
    order inside a group, each group starting on a tile of ``tm`` rows.
    ``(tok [R] int32, tile_expert [tiles] int32, n_tiles int32, mine
    [N * k] bool, pos [N * k] int32)``: the token of each row of the layout
    (a row that pads a group: token 0), each tile's expert, how many tiles
    are real, and where each assignment's row lies (``mine`` False: nowhere,
    held elsewhere or inactive; ``pos`` 0). ``R = tiles * tm``; ``tiles`` is
    the most the rows could fill.

    No sort: an assignment's place in its group is the number of earlier
    assignments to the same expert, counted exactly by a triangular product
    over blocks of ``block`` one-hot rows (0/1 operands, float32 sums) and
    the blocks' running totals; the layout's tokens are then ONE scatter of
    ``N * k`` integers."""
    from .pallas.moe_ffn import tile_bound

    N, k = sel.shape
    A, count = N * k, sizes.shape[0]
    tiles = tile_bound(A, count, tm)
    pstart, tile_expert, n_tiles = _tile_experts(sizes, tm, tiles)
    local = sel.reshape(A) - first
    mine = (local >= 0) & (local < count)
    padded = -(-A // block) * block
    onehot = (jnp.pad(local, (0, padded - A), constant_values=-1)[:, None]
              == jnp.arange(count, dtype=jnp.int32)[None, :]).reshape(
                  padded // block, block, count)
    ones = onehot.astype(jnp.bfloat16)
    earlier = jnp.einsum(
        "ij,bjc->bic", jnp.tril(jnp.ones((block, block), jnp.bfloat16), -1),
        ones, preferred_element_type=jnp.float32)
    total = jnp.sum(ones, axis=1, dtype=jnp.float32)
    before = jnp.cumsum(total, axis=0) - total
    place = earlier + (before + pstart.astype(jnp.float32))[:, None, :]
    pos = jnp.sum(jnp.where(onehot, place, 0.0), axis=-1).reshape(
        padded)[:A].astype(jnp.int32)
    R = tiles * tm
    a = jnp.arange(A, dtype=jnp.int32)
    # the others land behind the layout, each on an index of its own, and
    # are dropped
    tok = jnp.zeros((R,), jnp.int32).at[jnp.where(mine, pos, R + a)].set(
        a // k, mode="drop", unique_indices=True)
    return tok, tile_expert, n_tiles, mine, pos


def _tiled(ex: Dict, x2: jax.Array, sel: jax.Array, w: jax.Array,
           sizes: jax.Array, first: int, act: str = "silu", *,
           interpret: Optional[bool] = None,
           tile_rows: Optional[int] = None) -> jax.Array:
    """``[N, D]`` float32: the assignments grouped by expert into a layout
    of whole row tiles, through ONE kernel that reads each held expert once
    (``ops.pallas.moe_ffn.moe_tiled_ffn``), weighted and summed back.
    ``tile_rows``: the bench's, to try a row tile other than ``row_tile``'s."""
    from .pallas.moe_ffn import moe_tiled_ffn, row_tile

    N, k = sel.shape
    tok, tile_expert, n_tiles, mine, pos = tiled_operands(
        sel, sizes, first, tile_rows or row_tile(N * k, sizes.shape[0]))
    d = moe_tiled_ffn(x2[tok], tile_expert, n_tiles, ex.get("gate"),
                      ex["up"], ex["down"], interpret=interpret, act=act)
    # an assignment computed nowhere here reads row 0 and is selected away
    # (select, do not multiply: the rows behind the last real tile hold
    # whatever was there)
    d = d[pos].astype(jnp.float32) * w.reshape(N * k, 1)
    return jnp.where(mine[:, None], d, 0.0).reshape(N, k, -1).sum(axis=1)


def streamed_operands(sel: jax.Array, w: jax.Array, sizes: jax.Array,
                      first: int):
    """What the streamed kernel is told of a routing, for the ``count``
    experts held from ``first`` on: ``(combine [N, count] float32, ids
    [steps] int32, n_touched int32)``. ``combine`` holds a row's weight on
    each expert, 0 where it did not choose it (an inactive row: all 0);
    ``ids`` the touched experts in ascending order without a sort (the i-th
    is the number of experts whose running count of touched ones is <= i),
    behind the last one that one again; ``steps`` is the most experts the
    rows can touch."""
    N, k = sel.shape
    count = sizes.shape[0]
    held = jnp.arange(first, first + count, dtype=jnp.int32)
    combine = jnp.sum(
        jnp.where(sel[:, :, None] == held[None, None, :], w[:, :, None],
                  0.0), axis=1)
    touched = sizes > 0
    steps = jnp.arange(min(count, N * k), dtype=jnp.int32)
    ids = jnp.sum(jnp.cumsum(touched)[None, :] <= steps[:, None], axis=1,
                  dtype=jnp.int32)
    last = jnp.max(jnp.where(touched, jnp.arange(count, dtype=jnp.int32), 0))
    return (combine, jnp.minimum(ids, last),
            jnp.sum(touched, dtype=jnp.int32))


def _streamed(ex: Dict, x2: jax.Array, sel: jax.Array, w: jax.Array,
              sizes: jax.Array, first: int, act: str = "silu") -> jax.Array:
    """``[N, D]`` float32: every row through every touched expert held
    here, weighted by the dense combine matrix (``ops.pallas.moe_ffn``)."""
    from .pallas.moe_ffn import moe_streamed_ffn

    return moe_streamed_ffn(x2, *streamed_operands(sel, w, sizes, first),
                            ex.get("gate"), ex["up"], ex["down"], act=act)


_FORMS = {"streamed": _streamed, "tiled": _tiled, "grouped": _grouped}


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
    E = cfg.n_experts
    first, count = held or (0, E)
    lead, D = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, D)
    N = x2.shape[0]
    sel, w = route(mp, x2, cfg)
    if active is not None:
        sel = jnp.where(active.reshape(N, 1), sel, E)     # to no expert
    # per-expert counts over every expert: the routing statistics, and
    # (sliced) the group sizes of the experts held here
    counts = expert_counts(sel, E)
    stats = jnp.stack([jnp.sum(counts > 0, dtype=jnp.int32),
                       jnp.max(counts)])
    product = _FORMS[expert_form(N, cfg)]
    y = product(mp["experts"], x2, sel, w, counts[first:first + count],
                first, cfg.mlp_act).astype(x.dtype)
    if cfg.n_shared_experts:
        y = y + gated_mlp(mp["shared"], x2, cfg.mlp_act)
    return y.reshape(*lead, D), stats
