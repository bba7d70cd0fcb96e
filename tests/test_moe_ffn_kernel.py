"""The streamed and the tiled expert product (``ops/pallas/moe_ffn.py``,
interpreter mode) against the grouped form of ``ops/moe.py`` as the plain
oracle, and the one function that chooses between the three."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_hw_agnostic_inference_tpu.models.llama import LlamaConfig
from scalable_hw_agnostic_inference_tpu.ops import kernel_check, moe
from scalable_hw_agnostic_inference_tpu.ops.pallas import moe_ffn

E, D, F, K = 8, 256, 128, 2
CFG = dataclasses.replace(
    LlamaConfig.tiny_afmoe(), n_experts=E, n_experts_per_tok=K, dim=D,
    moe_mlp_dim=F)

#: largest difference allowed between the forms. Operands are bfloat16
#: values held in float32, so both forms multiply the same numbers exactly
#: and differ by the order of their float32 sums alone: outputs reach 8
#: here, where a float32 ulp is 5e-7, and sums of 256 and 128 terms drift
#: by a few tens of them at the worst.
BOUND = 2e-5


def _bf16_values(key, shape, scale):
    return (jax.random.normal(key, shape) * scale).astype(
        jnp.bfloat16).astype(jnp.float32)


def _layer(seed=0, router_scale=0.3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    return {
        "router": {"kernel": jax.random.normal(ks[0], (D, E)) * router_scale},
        "bias": jax.random.normal(ks[1], (E,)) * 0.05,
        "experts": {"gate": _bf16_values(ks[2], (E, D, F), 0.1),
                    "up": _bf16_values(ks[3], (E, D, F), 0.1),
                    "down": _bf16_values(ks[4], (E, F, D), 0.1)},
        "shared": {n: {"kernel": _bf16_values(k, s, 0.1)}
                   for n, k, s in (("gate", ks[5], (D, F)),
                                   ("up", ks[6], (D, F)),
                                   ("down", ks[7], (F, D)))}}


def _both_forms(mp, x, monkeypatch, **kw):
    """``expert_layer`` under the form its rows choose (streamed: every
    case here holds at most 128 rows of tileable widths) and under the
    grouped one."""
    assert moe.expert_form(x.shape[0], CFG) == "streamed"
    streamed = moe.expert_layer(mp, x, CFG, **kw)
    monkeypatch.setattr(moe, "expert_form", lambda n, cfg: "grouped")
    grouped = moe.expert_layer(mp, x, CFG, **kw)
    monkeypatch.undo()
    return streamed, grouped


def _one_expert(mp):
    # a bias that only selects: every row's first choice is expert 5
    return {**mp, "bias": jnp.zeros((E,)).at[5].set(10.0)}


def _unchosen(mp):
    # experts 0, 3 and 6 are nobody's choice
    return {**mp, "bias": jnp.zeros((E,)).at[jnp.asarray([0, 3, 6])].set(
        -10.0)}


CASES = {
    "rows-1": dict(rows=1),
    "rows-8": dict(rows=8),
    "rows-64": dict(rows=64),
    "every-row-on-one-expert": dict(rows=8, layer=_one_expert),
    "experts-nobody-chose": dict(rows=8, layer=_unchosen),
    "inactive-rows": dict(
        rows=8, active=[True, False, True, True, False, False, True, False]),
    "no-active-row": dict(rows=8, active=[False] * 8),
    "held-slice": dict(rows=8, held=(2, 4)),
    "held-slice-nobody-chose": dict(rows=1, held=(0, 2),
                                    layer=lambda mp: {
                                        **mp, "bias": jnp.zeros((E,)).at[
                                            :2].set(-10.0)}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_streamed_form_is_the_grouped_form(case, monkeypatch):
    c = CASES[case]
    mp = c.get("layer", lambda m: m)(_layer())
    x = _bf16_values(jax.random.PRNGKey(3), (c["rows"], D), 1.0)
    kw = {}
    if "active" in c:
        kw["active"] = jnp.asarray(c["active"])
    if "held" in c:
        lo, n = c["held"]
        mp = {**mp, "experts": {name: w[lo:lo + n]
                                for name, w in mp["experts"].items()}}
        kw["held"] = c["held"]
    (ys, stats_s), (yg, stats_g) = _both_forms(mp, x, monkeypatch, **kw)
    assert np.asarray(stats_s).tolist() == np.asarray(stats_g).tolist()
    ys, yg = np.asarray(ys), np.asarray(yg)
    assert np.isfinite(ys).all()
    assert np.abs(ys - yg).max() < BOUND
    if case == "no-active-row":
        routed = dataclasses.replace(CFG, n_shared_experts=0)
        y, _ = moe.expert_layer(mp, x, routed, **kw)
        assert not np.asarray(y).any()
    elif case != "held-slice-nobody-chose":
        assert np.abs(yg).max() > 1e-3       # the oracle says something


def test_bfloat16_operands_accumulate_in_float32(monkeypatch):
    """In the configuration's own type the streamed form is at least as
    close to a float32 product of the same bfloat16 operands as the
    grouped one, which rounds ``g``, ``u`` and ``silu(g) * u`` between its
    calls."""
    mp32 = _layer()
    x32 = _bf16_values(jax.random.PRNGKey(3), (8, D), 1.0)
    exact, _ = moe.expert_layer(mp32, x32, CFG)
    mp = {**mp32, "experts": {n: w.astype(jnp.bfloat16)
                              for n, w in mp32["experts"].items()},
          "shared": {n: {"kernel": p["kernel"].astype(jnp.bfloat16)}
                     for n, p in mp32["shared"].items()}}
    (ys, _), (yg, _) = _both_forms(mp, x32.astype(jnp.bfloat16), monkeypatch)
    assert ys.dtype == jnp.bfloat16
    err = lambda y: np.abs(np.asarray(y, np.float32)            # noqa: E731
                           - np.asarray(exact)).max()
    # outputs reach 8, where a bfloat16 ulp is 2 ** -5: the result and the
    # shared expert's part are each rounded once
    assert 4 < np.abs(np.asarray(exact)).max() < 16
    assert err(ys) <= err(yg) + 2.0 ** -9
    assert err(ys) <= 2 * 2.0 ** -5


def test_the_walk_skips_what_nobody_touched():
    """The ids behind the touched experts repeat the last one, and the
    kernel adds nothing for them: poisoned leaves of untouched experts
    never reach the sum."""
    mp = _layer()
    x = _bf16_values(jax.random.PRNGKey(3), (4, D), 1.0)
    sel = jnp.asarray([[1, 4], [4, 6], [1, 6], [4, 1]], jnp.int32)
    w = jnp.full((4, K), 0.5)
    sizes = jnp.zeros((E,), jnp.int32).at[jnp.asarray([1, 4, 6])].set(
        jnp.asarray([3, 3, 2]))
    untouched = jnp.asarray([0, 2, 3, 5, 7])
    poisoned = {n: leaf.at[untouched].set(jnp.nan)
                for n, leaf in mp["experts"].items()}
    y = moe._streamed(poisoned, x, sel, w, sizes, 0)
    want = moe._grouped(mp["experts"], x, sel, w, sizes, 0)
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < BOUND


@pytest.mark.parametrize("tile_f", [128, 256])
def test_tiles_of_the_inner_width_sum_to_the_whole(tile_f):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = _bf16_values(ks[0], (16, 128), 1.0)
    gate, up = (_bf16_values(k, (4, 128, 256), 0.1) for k in ks[1:3])
    down = _bf16_values(ks[3], (4, 256, 128), 0.1)
    combine = jnp.abs(jax.random.normal(ks[4], (16, 4))).at[:, 2].set(0.0)
    ids, n = jnp.asarray([0, 1, 3, 3], jnp.int32), jnp.asarray(3)
    y = moe_ffn.moe_streamed_ffn(x, combine, ids, n, gate, up, down,
                                 tile_f=tile_f, interpret=True)
    h = jnp.einsum("nd,edf->enf", x, gate)
    h = jax.nn.silu(h) * jnp.einsum("nd,edf->enf", x, up)
    want = jnp.einsum("enf,efd,ne->nd", h, down, combine)
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < BOUND


def _sel(groups, k=K, n_experts=E):
    """``[N, k]`` assignments in which expert ``e`` gets ``groups[e]`` rows
    (``groups[n_experts]``: assignments to no expert), dealt round the
    tokens so that a token's ``k`` choices differ where the counts allow."""
    flat = np.concatenate([np.full(n, e) for e, n in enumerate(groups)])
    assert flat.size % k == 0
    return jnp.asarray(flat.reshape(k, -1).T, jnp.int32)


#: tile 64 (``moe_ffn.row_tile`` of some 600 assignments over 8 or 4
#: experts); case -> rows of each of the 8 experts
#: (a ninth entry: assignments to no expert, as an inactive row's), and
#: ``held`` where the leaves stack a slice
TILED_CASES = {
    "uneven-groups": dict(groups=[5, 130, 64, 1, 257, 40, 100, 43]),
    "an-empty-expert": dict(groups=[90, 0, 120, 0, 200, 60, 0, 170]),
    "the-first-and-last-empty": dict(groups=[0, 100, 100, 100, 100, 100,
                                             140, 0]),
    "one-expert-holds-most-rows": dict(groups=[2, 3, 600, 1, 4, 2, 3, 25]),
    "a-group-of-exactly-one-tile": dict(groups=[128, 64, 64, 128, 64, 64,
                                                64, 64]),
    "a-group-of-one-row-over-a-tile": dict(groups=[129, 63, 64, 257, 63, 64,
                                                   64, 64]),
    "held-with-assignments-elsewhere": dict(
        groups=[70, 90, 10, 150, 128, 33, 99, 60], held=(2, 4)),
    "held-and-nobody-chose-them": dict(
        groups=[200, 100, 0, 0, 0, 0, 140, 200], held=(2, 4)),
    "inactive-rows": dict(groups=[50, 60, 70, 80, 20, 30, 40, 50, 240]),
    "no-active-row": dict(groups=[0] * 8 + [400]),
}


@pytest.mark.parametrize("case", sorted(TILED_CASES))
def test_the_tiled_form_is_the_grouped_form(case):
    """``_tiled`` against ``_grouped`` (``ragged_dot``) on the same
    assignments: bfloat16 values in float32, so the forms multiply the same
    numbers and differ by the order of their sums."""
    c = TILED_CASES[case]
    sel = _sel(c["groups"])
    N = sel.shape[0]
    assert moe.expert_form(N, CFG) == "tiled"
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = _bf16_values(ks[0], (N, D), 1.0)
    w = jax.random.uniform(ks[1], (N, K), minval=0.1)
    first, count = c.get("held", (0, E))
    ex = {n: leaf[first:first + count]
          for n, leaf in _layer()["experts"].items()}
    sizes = moe.expert_counts(sel, E)[first:first + count]
    assert np.asarray(sizes).tolist() == c["groups"][first:first + count]
    got = np.asarray(moe._tiled(ex, x, sel, w, sizes, first))
    want = np.asarray(moe._grouped(ex, x, sel, w, sizes, first))
    assert got.shape == (N, D) and np.isfinite(got).all()
    assert np.abs(got - want).max() < BOUND
    if int(np.asarray(sizes).sum()):
        assert np.abs(want).max() > 1e-3     # the oracle says something
    else:
        assert not got.any()


@pytest.mark.parametrize("groups", [
    [129, 0, 64, 257, 1, 0, 128, 61],
    [0, 0, 300, 0, 0, 1, 255, 256],
], ids=["mixed", "empty-ends-and-a-full-tile"])
def test_the_tiled_layout_starts_each_group_on_a_tile(groups):
    """Each expert's rows lie together from a multiple of the tile on, in
    token order (what a stable sort by expert would give, without one), the
    rows that pad a group read token 0, the tiles behind the last real one
    repeat its expert, and every held assignment is told where its row
    is."""
    sel = _sel(groups)
    N = sel.shape[0]
    tm = 128
    tok, tile_expert, n_tiles, mine, pos = (
        np.asarray(a) for a in moe.tiled_operands(
            sel, jnp.asarray(groups, jnp.int32), 0, tm))
    tiles = moe_ffn.tile_bound(N * K, E, tm)
    assert tile_expert.shape == (tiles,) and tok.shape == (tiles * tm,)
    want = [e for e, g in enumerate(groups) for _ in range(-(-g // tm))]
    n = len(want)
    assert int(n_tiles) == n and 8 <= n < tiles
    assert tile_expert[:n].tolist() == want
    assert (tile_expert[n:] == want[-1]).all()
    assert mine.all()
    flat_sel = np.asarray(sel).ravel()
    assert len(set(pos.tolist())) == N * K                # one row each
    for a in range(N * K):
        assert tile_expert[pos[a] // tm] == flat_sel[a]
        assert tok[pos[a]] == a // K
    for e in range(E):                                    # token order
        at = pos[flat_sel == e]
        assert (np.diff(at) == 1).all()
        assert at.size == 0 or at[0] % tm == 0
    pad = np.ones(tiles * tm, bool)
    pad[pos] = False
    assert not tok[pad].any()


def test_the_tiled_walk_skips_the_tiles_behind_the_last():
    """Poisoned leaves of experts nobody chose never reach a row, and rows
    of the layout behind the last real tile are never read back."""
    groups = [0, 150, 0, 0, 130, 0, 120, 0]
    sel = _sel(groups)
    N = sel.shape[0]
    x = _bf16_values(jax.random.PRNGKey(3), (N, D), 1.0)
    w = jnp.full((N, K), 0.5)
    ex = _layer()["experts"]
    poisoned = {n: leaf.at[jnp.asarray([0, 2, 3, 5, 7])].set(jnp.nan)
                for n, leaf in ex.items()}
    sizes = jnp.asarray(groups, jnp.int32)
    got = moe._tiled(poisoned, x, sel, w, sizes, 0)
    want = moe._grouped(ex, x, sel, w, sizes, 0)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < BOUND


@pytest.mark.parametrize("tile_f", [128, 256])
def test_tiles_of_the_inner_width_sum_to_the_whole_in_the_tiled_kernel(
        tile_f):
    """A step that is a whole expert answers in the rows' type; tiles of
    the inner width add up in float32."""
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    tm, tiles = 16, 5
    xs = _bf16_values(ks[0], (tiles * tm, 128), 1.0)
    gate, up = (_bf16_values(k, (4, 128, 256), 0.1) for k in ks[1:3])
    down = _bf16_values(ks[3], (4, 256, 128), 0.1)
    tile_expert = jnp.asarray([0, 0, 1, 3, 3], jnp.int32)
    y = moe_ffn.moe_tiled_ffn(xs, tile_expert, jnp.asarray(4), gate, up,
                              down, tile_f=tile_f, interpret=True)
    assert y.dtype == jnp.float32
    e = jnp.repeat(tile_expert, tm)
    h = jnp.einsum("nd,ndf->nf", xs, gate[e])
    h = jax.nn.silu(h) * jnp.einsum("nd,ndf->nf", xs, up[e])
    want = jnp.einsum("nf,nfd->nd", h, down[e])
    real = 4 * tm
    assert np.abs(np.asarray(y)[:real] - np.asarray(want)[:real]).max() < BOUND
    whole = moe_ffn.moe_tiled_ffn(
        xs.astype(jnp.bfloat16), tile_expert, jnp.asarray(4),
        *(m.astype(jnp.bfloat16) for m in (gate, up, down)), interpret=True)
    assert whole.dtype == jnp.bfloat16


@pytest.mark.parametrize("preset,decode,prefill", [
    ("kanana2_stage", (1, 2, 4, 8, 16, 32, 64), (1024, 2048)),
    ("trinity_mini_stage", (1, 2, 4, 8, 16, 32), (512, 1024, 2048)),
    ("kimi_linear_stage", (1, 2, 4, 8, 16), (1024, 2048)),
])
def test_decode_buckets_stream_and_prefill_buckets_group(
        preset, decode, prefill):
    """The three forms by shape. At the cells' published widths (2048 x
    768, 2048 x 1024, 2304 x 1024) every decode bucket takes the streamed
    form and every prefill or continuation bucket the tiled one (sorted
    into groups as the grouped form sorts them, through one kernel); the
    CPU stand-ins, whose widths no kernel can tile, keep the plain grouped
    form (``ragged_dot``) at any row count."""
    cfg = getattr(LlamaConfig, preset)()
    assert (cfg.dim, cfg.moe_mlp_dim) in ((2048, 768), (2048, 1024),
                                          (2304, 1024))
    assert {moe.expert_form(n, cfg) for n in decode} == {"streamed"}
    assert {moe.expert_form(n, cfg) for n in prefill} == {"tiled"}
    assert moe.expert_form(moe.STREAMED_MAX_ROWS, cfg) == "streamed"
    assert moe.expert_form(moe.STREAMED_MAX_ROWS + 1, cfg) == "tiled"
    for tiny in (LlamaConfig.tiny_afmoe(), LlamaConfig.tiny_mla(),
                 LlamaConfig.tiny_kda()):
        assert {moe.expert_form(n, tiny) for n in (1, 129, 2048)} == {
            "grouped"}
    # the row tile by the static shapes: one MXU tile where the assignments
    # could give every held expert one (Kimi's 2,048 x 8 over 128 held),
    # half of it below (Kanana's 2,048 x 6, Trinity's 1,024 x 8)
    assert moe_ffn.row_tile(2048 * 8, 128) == 128
    assert moe_ffn.row_tile(2048 * 6, 128) == 64
    assert moe_ffn.row_tile(1024 * 8, 128) == 64
    assert moe_ffn.tile_bound(2048 * 8, 128, 128) == 256
    # a step is a whole expert at these widths: its fixed part is paid
    # once, and in the tiled kernel an expert's matrices leave HBM once
    assert moe_ffn.inner_tile(cfg.dim, cfg.moe_mlp_dim, 2) == cfg.moe_mlp_dim
    assert moe_ffn.inner_tile(4096, 2048, 2) == 512


@pytest.mark.parametrize("case", kernel_check.expert_cases(
    8, 2, 256, 128, max_num_seqs=16, prefill_rows=300, held=5),
    ids=lambda c: c.name)
def test_the_chip_checks_case_builder_agrees_with_its_oracle(case):
    """``ops.kernel_check.expert_cases`` is what
    ``tests/test_kernel_lowering.py`` compiles for the v5e at the cells'
    widths: here the same builder, small, interpreted, against its oracle
    (an inactive last row among the rows; the tiled case holds five of the
    eight experts)."""
    assert case.max_abs_err(interpret=True) <= case.tol


def test_the_trace_name_is_read_with_the_grouped_product():
    """``moe_ffn_share.moe`` sums ops matching ``ragged-dot`` or
    ``moe_grouped_ffn``: the kernel's name keeps it reading the whole
    expert product."""
    assert moe_ffn.KERNEL_NAME.startswith(moe.GROUPED_NAME)
    assert moe_ffn.KERNEL_NAME != moe.GROUPED_NAME


def test_the_tiled_trace_name_is_read_with_the_product_not_the_streamed():
    """``moe_ffn_share.*`` reads the tiled kernel (its name begins with the
    grouped product's); ``moe_streamed_hbm_roofline.moe``, a ``re.search``
    of the streamed kernel's name, does not."""
    import re

    assert moe_ffn.TILED_NAME.startswith(moe.GROUPED_NAME)
    assert not re.search(moe_ffn.KERNEL_NAME, moe_ffn.TILED_NAME)
    assert not re.search(moe_ffn.TILED_NAME, moe_ffn.KERNEL_NAME)
