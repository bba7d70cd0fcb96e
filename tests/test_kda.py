"""Recurrent slot state (Kimi-Linear-48B-A3B / ``kimi_linear``: KDA linear
attention three layers of four beside an MLA layer's latent pool) on the
engine's normal path, at the tiny size on the CPU: the engine (a chunked
scan from a zero state, continuation chunks that read and write a slot,
one recurrent step a row) against the plain token-by-token reference on
logits; one program against continuation chunks; a reused slot against a
fresh engine; padded and finished rows; preemption, cancellation and both
kinds of leak accounting; the chunked form and both kernels (interpret
mode) against the recurrence; the expert layer's shares under this router;
what the boot refuses, by name; the counters; and the other architectures'
programs untouched."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import check
from benchmark.spec import Spec
from scalable_hw_agnostic_inference_tpu.engine import EngineConfig, runner
from scalable_hw_agnostic_inference_tpu.engine.cache import (
    PagedKVCache,
    RecurrentSpec,
)
from scalable_hw_agnostic_inference_tpu.engine.engine import (
    LLMEngine,
    SamplingParams,
)
from scalable_hw_agnostic_inference_tpu.models.llama import (
    KDA_A_RANGE,
    KDA_CONV_STD,
    KDA_DT_RANGE,
    LlamaConfig,
    LlamaForCausalLM,
    cache_leaves,
    cache_specs,
    geometry_params,
    state_leaves,
)
from scalable_hw_agnostic_inference_tpu.ops import kda, kernel_check
from scalable_hw_agnostic_inference_tpu.ops.moe import expert_layer, gated_mlp

SPEC = Spec()
NAME = "kimi-linear-48b-a3b-bf16-ep2"
TINY = LlamaConfig.tiny_kda()
TINY_MODEL = SPEC.dry_run_model("tiny-kda")
REF = SPEC.reference("kimi_linear")
TOL = SPEC.tolerance("tolerance.kimi_linear.json")


@pytest.fixture(scope="module")
def tiny_params():
    return geometry_params(TINY, dtype=jnp.float32, seed=3)


@pytest.fixture(scope="module", autouse=True)
def one_compile_a_program():
    """The engines of this file that ask for the same step program get ONE
    jitted function, and so one compile: a builder's result is a pure
    function of its arguments and of ``SHAI_PAGED_DECODE`` (which picks the
    decode kernels), and some thirty engines are booted here at one tiny
    shape."""
    import os

    from scalable_hw_agnostic_inference_tpu.engine import engine as engine_mod

    built = {}

    def shared(build):
        def get(*args, **kw):
            key = (build.__name__, args, tuple(sorted(kw.items())),
                   os.environ.get("SHAI_PAGED_DECODE"))
            if key not in built:
                built[key] = build(*args, **kw)
            return built[key]
        return get

    patch = pytest.MonkeyPatch()
    patch.setattr(engine_mod, "make_prefill", shared(runner.make_prefill))
    patch.setattr(engine_mod, "make_decode", shared(runner.make_decode))
    patch.setattr(runner, "make_prefill_cont",
                  shared(runner.make_prefill_cont))
    yield
    patch.undo()


def _engine(params, cfg=TINY, **over):
    kw = dict(max_model_len=128, max_num_seqs=3, block_size=8,
              context_encoding_buckets=(16, 32), max_new_tokens=16)
    kw.update(over)
    return LLMEngine(cfg, params, EngineConfig(**kw))


def _prompt(n, seed=7):
    rng = np.random.default_rng(seed + n)
    return [1] + [int(t) for t in rng.integers(3, 500, n - 1)]


GREEDY = SamplingParams(temperature=0.0, max_new_tokens=8, logprobs=5)


def _against_reference(fin, prompt, params, variant=""):
    gen = fin.token_ids
    seq = prompt + gen[:-1]
    rows = [len(prompt) - 1 + k for k in range(len(gen))]
    ref = REF.logprobs(params["params"], TINY_MODEL, seq, rows, 128, variant)
    assert np.isfinite(ref).all(), variant
    got = check.compare(fin.logprobs, ref)
    got["mean"] = got["sum_abs_logprob_diff"] / got["compared"]
    return got


# -- the presets ------------------------------------------------------------

TINY_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "intermediate_size": "mlp_dim", "moe_intermediate_size": "moe_mlp_dim",
    "model_max_length": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps", "tie_word_embeddings": "tie_embeddings",
    "first_k_dense_replace": "n_dense_layers",
    "num_experts": "n_experts_held",
    "num_experts_per_token": "n_experts_per_tok",
    "num_shared_experts": "n_shared_experts",
    "moe_renormalize": "route_norm",
    "routed_scaling_factor": "route_scale"}


def _lin(cfg):
    """``linear_attn_config`` as a ``LlamaConfig`` states it (1-based)."""
    kinds = cfg.layer_types
    return {"kda_layers": [i + 1 for i, k in enumerate(kinds)
                           if k == "linear_attention"],
            "full_attn_layers": [i + 1 for i, k in enumerate(kinds)
                                 if k == "full_attention"],
            "head_dim": cfg.kda_head_dim, "num_heads": cfg.kda_heads,
            "short_conv_kernel_size": cfg.kda_conv}


@pytest.mark.parametrize("key", sorted(TINY_FIELDS))
def test_the_tiny_stand_in_is_the_programs_preset(key):
    assert getattr(TINY, TINY_FIELDS[key]) == TINY_MODEL[key], key


def test_the_tiny_stand_in_has_the_cuts_pattern():
    assert _lin(TINY) == TINY_MODEL["linear_attn_config"]
    assert TINY.layer_types == LlamaConfig.kimi_linear_stage().layer_types
    assert TINY_MODEL["published_num_experts"] == TINY.n_experts == 16
    assert TINY.held == (TINY_MODEL["experts_held_first"], 8)
    assert not TINY.rope_on_full_attention and TINY_MODEL["mla_use_nope"]


@pytest.mark.parametrize("key", sorted(TINY_FIELDS))
def test_the_stage_is_the_published_model_cut_in_depth_and_experts(key):
    """``LlamaConfig.kimi_linear_stage()`` against the configuration file
    (the published config's keys): every one but the depth and the experts
    HELD, which the file lists under ``reduced`` with their layer lists."""
    full, stage = (LlamaConfig.kimi_linear_48b(),
                   LlamaConfig.kimi_linear_stage())
    pub = SPEC.config(NAME)
    attr = TINY_FIELDS[key]
    if key == "num_hidden_layers":
        assert (full.n_layers, stage.n_layers, pub[key]) == (27, 5, 5)
        assert pub["published"][key] == 27
        assert stage.n_moe_layers == 4 and full.n_moe_layers == 26
        return
    if key == "num_experts":
        assert (full.n_experts_held, stage.n_experts_held, pub[key]) == (
            256, 128, 128)
        assert full.n_experts == stage.n_experts == 256 == (
            pub["published"][key])
        assert stage.held == (pub["experts_held_first"], 128)
        return
    assert getattr(stage, attr) == getattr(full, attr) == pub[key], key


def test_the_stage_keeps_a_whole_period_in_the_published_ratio():
    full, stage = (LlamaConfig.kimi_linear_48b(),
                   LlamaConfig.kimi_linear_stage())
    pub = SPEC.config(NAME)
    assert _lin(full) == pub["published"]["linear_attn_config"]
    assert _lin(stage) == pub["linear_attn_config"]
    assert pub["reduced"] == ["num_hidden_layers", "num_experts",
                              "linear_attn_config"]
    # three KDA layers to one MLA layer behind the leading dense layer,
    # itself KDA as published layer 1 is
    assert stage.layer_types[1:] == full.layer_types[4:8]
    assert stage.layer_types[0] == full.layer_types[0] == "linear_attention"
    assert stage.kda_layers == (0, 1, 2, 3) and stage.recurrent
    assert len(full.kda_layers) == 20


def test_a_layer_says_what_it_costs_the_pool_and_a_slot():
    assert [cache_leaves(TINY, li) for li in range(5)] == (
        [{}] * 4 + [{"c": (128,)}])
    assert cache_leaves(TINY) == {"c": (128,)}
    assert state_leaves(TINY) == {"s": ((4, 16, 16), "float32"),
                                  "t": ((3, 192), None)}
    stage = LlamaConfig.kimi_linear_stage()
    (s_shape, _), (t_shape, _) = (state_leaves(stage)[n] for n in "st")
    assert s_shape == (32, 128, 128) and t_shape == (3, 3 * 4096)
    assert np.prod(s_shape) * 4 == 2_097_152          # the issue's 2.10 MB
    assert np.prod(t_shape) * 2 == 73_728             # and its 74 KB
    plain = LlamaConfig.tiny()
    assert state_leaves(plain) == {} and not plain.recurrent
    assert TINY.engine_only


def test_cache_specs_follow_the_leaves():
    """The repair: a latent or an empty layer no longer gets ``k``/``v``
    specs; plain heads get what they got."""
    assert set(cache_specs(TINY)) == {"c"}
    assert set(cache_specs(LlamaConfig.tiny_mla(), axis_size=2)) == {"c"}
    plain = LlamaConfig.tiny()
    from jax.sharding import PartitionSpec as P
    assert cache_specs(plain, axis_size=2) == {
        "k": P(None, None, "tp", None), "v": P(None, None, "tp", None)}
    assert cache_specs(plain, axis_size=3) == {"k": P(), "v": P()}
    assert cache_specs(plain) == {"k": P(), "v": P()}


# -- the function three ways ------------------------------------------------

def _operands(T, B=2, H=2, d=16, seed=0):
    return kernel_check._kda_operands(jax.random.PRNGKey(seed), (B, T, H, d))


# ``chunked`` takes a row's heads as ONE group (1, 2, 3 heads side by
# side in ``chunk_math``); the state that enters is never zero
@pytest.mark.parametrize("T,H", [(1, 2), (63, 2), (64, 2), (150, 2),
                                 (150, 1), (70, 3), (150, 3)])
def test_the_chunked_form_is_the_recurrence(T, H):
    args = _operands(T, H=H)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (2, H, 16, 16))
    o, s = kda.recurrence(*args, s0)
    oc, sc = jax.jit(kda.chunked)(*args, s0)
    np.testing.assert_allclose(np.asarray(oc), np.asarray(o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(s), atol=2e-5)


def test_a_state_carried_between_calls_is_one_scan():
    args = _operands(150)
    o, s = kda.recurrence(*args)
    cut = lambda a, b: tuple(x[:, a:b] for x in args)       # noqa: E731
    oa, sa = kda.chunked(*cut(0, 70))
    ob, sb = kda.chunked(*cut(70, 150), sa)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([oa, ob], 1)), np.asarray(o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sb), np.asarray(s), atol=2e-5)


@pytest.mark.parametrize("H,fast", [(2, 8.0), (1, 5.0), (3, 5.0), (16, 5.0)])
def test_a_fast_channel_does_not_overflow_the_chunk(H, fast):
    """Log-decays of -8 a token (the bound the module states) and of -5
    (where ``exp(-16 |g|)`` times a small ``q`` once fell under float32's
    smallest normal) over whole chunks beside channels that hardly decay:
    every exponent is taken against its block's middle, in the plain form
    and in the kernel's grouped grid step."""
    from scalable_hw_agnostic_inference_tpu.ops.pallas.kda_chunk import (
        kda_chunk_prefill,
    )

    q, k, v, g, beta = _operands(128, B=1, H=H)
    g = jnp.full_like(g, -fast).at[..., ::2].set(-1e-3)
    o, s = kda.recurrence(q, k, v, g, beta)
    for form in (kda.chunked,
                 lambda *a: kda_chunk_prefill(*a, interpret=True)):
        oc, sc = form(q, k, v, g, beta)
        assert np.isfinite(np.asarray(oc)).all()
        np.testing.assert_allclose(np.asarray(oc), np.asarray(o), atol=2e-5)
        np.testing.assert_allclose(np.asarray(sc), np.asarray(s), atol=2e-5)


# -- the grouped body against the arithmetic it replaced --------------------

def _former_mask_mm(mask, x):
    """The 0/1 mask's product as it stood: ONE product at
    ``Precision.HIGHEST``, six bfloat16 passes on the TPU."""
    from scalable_hw_agnostic_inference_tpu.ops.kda import _mm

    return _mm(jnp.broadcast_to(mask.astype(jnp.float32),
                                x.shape[:-2] + mask.shape), x)


def _former_chunk_math(q, k, kb, vb, g, st):
    """``ops.kda.chunk_math`` as it stood before a grid step took a group
    of heads, kept HERE as the yardstick of what the regrouping may not
    move: ONE head (plain 2-D values), 25 products, every one of them at
    ``Precision.HIGHEST``. ``Gs`` is a second mask product, a block's ``A``
    and ``P`` rows are two products against the same ``k e^-``, ``kb e^G``
    and ``q e^G`` two against the same state."""
    from scalable_hw_agnostic_inference_tpu.ops.kda import (
        _EXP_CAP, _mm, _mm_nt, _neumann, BLOCK)

    C, d = q.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    G = _former_mask_mm(col <= row, g)
    Gs = _former_mask_mm(col < (row // BLOCK) * BLOCK + BLOCK // 2, g)
    a_rows, p_rows = [], []
    for lo in range(0, C, BLOCK):
        rs = Gs[lo:lo + 1]
        e = jnp.exp(G[lo:lo + BLOCK] - rs)
        kneg = k * jnp.exp(jnp.minimum(rs - G, _EXP_CAP))
        a_rows.append(_mm_nt(kb[lo:lo + BLOCK] * e, kneg))
        p_rows.append(_mm_nt(q[lo:lo + BLOCK] * e, kneg))
    A = jnp.where(col < row, jnp.concatenate(a_rows, axis=0), 0.0)
    P = jnp.where(col <= row, jnp.concatenate(p_rows, axis=0), 0.0)
    eye = (row == col).astype(jnp.float32)
    diag = jnp.where(row // BLOCK == col // BLOCK, A, 0.0)
    inv_d = _neumann(diag, eye, BLOCK)
    inv = _mm(_neumann(_mm(inv_d, A - diag), eye, C // BLOCK), inv_d)
    decay = jnp.exp(G)
    u = _mm(inv, vb - _mm_nt(kb * decay, st))
    o = _mm_nt(q * decay, st) + _mm(P, u)
    g_end = G[C - 1:C]
    st = st * jnp.exp(g_end) + _mm(u.T, k * jnp.exp(g_end - G))
    return o, st


@pytest.mark.parametrize("seed,H,d", [(0, 1, 16), (1, 2, 16), (2, 3, 16),
                                      (3, 4, 128)])
def test_the_grouped_body_gives_the_former_results_bit_for_bit(
        seed, H, d, monkeypatch):
    """A block's stacked ``[kb e ; q e]`` product, the stacked ``[kb ; q]
    e^G`` product against the state, ``Gs`` read from ``G``'s rows, the
    head axis and the transposed-operand product of the state's update:
    none of them moves a bit of what one head's 25 products gave. The mask
    product is the former one on both sides here (the CPU sums a float32
    product in another order than three products of parts; the test below
    holds ``_mask_mm`` to it)."""
    monkeypatch.setattr(kda, "_mask_mm", _former_mask_mm)
    q, k, v, g, beta = (a[0] for a in _operands(kda.CHUNK, B=1, H=H, d=d,
                                                seed=seed))
    hm = lambda a: jnp.moveaxis(a, 1, 0)                     # noqa: E731
    b = beta[..., None]
    ops = (hm(q), hm(k), hm(k * b), hm(v * b), hm(g),
           jax.random.normal(jax.random.PRNGKey(seed + 50), (H, d, d)))
    # a function of its own: no trace of ``chunk_math`` made with the
    # shipped mask product is found again
    o, st = jax.jit(lambda *a: kda.chunk_math(*a))(*ops)
    former = jax.jit(_former_chunk_math)
    for h in range(H):
        o_h, st_h = former(*(a[h] for a in ops))
        np.testing.assert_array_equal(np.asarray(o[h]), np.asarray(o_h))
        np.testing.assert_array_equal(np.asarray(st[h]), np.asarray(st_h))


@pytest.mark.parametrize("seed,d,scale", [(0, 16, 1.0), (1, 128, 1.0),
                                          (2, 128, 50.0), (3, 128, 1e-3)])
def test_the_three_pass_mask_product_is_the_six_pass_one(seed, d, scale):
    """``_mask_mm`` against the former ``Precision.HIGHEST`` product of the
    cumulative decay. What the three passes rest on is exact and held
    exactly: the three parts are bfloat16 values, they are cut by
    TRUNCATION (a part never exceeds what it is cut from, as rounding does
    half the time: ``Precision.HIGHEST`` cuts so) and they sum back to the
    operand in float32. The products themselves are the same number on the
    TPU (``scripts/kda_bench.py`` records the bits from the chip); the CPU
    sums in another order, so here the two agree to a few float32 steps,
    and the three passes stand no further from the exact sum."""
    from scalable_hw_agnostic_inference_tpu.ops.kda import _bf16_part

    g = jnp.moveaxis(_operands(kda.CHUNK, B=1, H=3, d=d, seed=seed)[3][0],
                     1, 0) * scale                            # [H, C, d]
    hi = _bf16_part(g)
    mid = _bf16_part(g - hi)
    lo = _bf16_part((g - hi) - mid)
    for part, whole in ((hi, g), (mid, g - hi), (lo, (g - hi) - mid)):
        np.testing.assert_array_equal(
            np.asarray(part.astype(jnp.bfloat16).astype(jnp.float32)),
            np.asarray(part))
        assert (np.abs(np.asarray(part)) <= np.abs(np.asarray(whole))).all()
    np.testing.assert_array_equal(np.asarray((hi + mid) + lo), np.asarray(g))
    C = kda.CHUNK
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    three = np.asarray(kda._mask_mm(col <= row, g))
    six = np.asarray(_former_mask_mm(col <= row, g))
    exact = np.cumsum(np.asarray(g, np.float64), axis=-2)
    np.testing.assert_allclose(three, six, rtol=1e-6, atol=0)
    assert np.abs(three - exact).max() <= np.abs(six - exact).max()


def test_a_pad_token_is_the_identity(tiny_params):
    """``inputs`` with fewer real tokens than the bucket: the pads' beta and
    log-decay are 0, the state the scan leaves is the last real token's and
    so is the tail."""
    at = tiny_params["params"]["layer_1"]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 24, TINY.dim))
    n = jnp.asarray([24, 9])
    q, k, v, g, beta, tail = kda.inputs(at, h, None, n, TINY)
    assert not np.asarray(beta[1, 9:]).any()
    assert not np.asarray(g[1, 9:]).any()
    assert np.asarray(beta[1, :9]).all()
    _, s = kda.chunked(q, k, v, g, beta)
    short = kda.inputs(at, h[1:, :9], None, None, TINY)
    _, s_short = kda.chunked(*short[:5])
    np.testing.assert_allclose(np.asarray(s[1]), np.asarray(s_short[0]),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail[1]),
                                  np.asarray(short[5][0]))
    # a prompt shorter than the convolution keeps what was there before it
    old = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 192))
    *_, t2 = kda.inputs(at, h, old, jnp.asarray([2, 0]), TINY)
    np.testing.assert_array_equal(np.asarray(t2[1]), np.asarray(old[1]))
    np.testing.assert_array_equal(np.asarray(t2[0, 0]), np.asarray(old[0, 2]))


KDA_CASES = kernel_check.kda_cases(2, 16, bucket=160, max_num_seqs=6)
# the chunk kernel at other groups than the two heads above: one head, three
# (fewer than a group of eight: all in one step), sixteen (two steps of
# eight) and twelve (padded to sixteen with identity heads); two rows, a
# state that is not zero, ``T`` no multiple of ``CHUNK``
GROUP_CASES = [kernel_check._kda_chunk_case(H, 16, 150, 2)
               for H in (1, 3, 16, 12)]


@pytest.mark.parametrize("case", KDA_CASES + GROUP_CASES,
                         ids=lambda c: c.name)
def test_kda_kernels_agree_with_the_recurrence(case):
    assert case.tol == kernel_check.TOL_KDA == 2e-4
    assert case.max_abs_err(interpret=True) <= case.tol


@pytest.mark.parametrize("H,grid", [(1, (1, 1)), (2, (1, 2)), (3, (1, 3)),
                                    (8, (1, 8)), (12, (2, 8)), (16, (2, 8)),
                                    (20, (3, 8)), (32, (4, 8))])
def test_a_grid_step_takes_eight_heads_or_all_of_fewer(H, grid):
    """A block's second-minor dimension is the heads: a whole tile of
    eight, or the whole dimension of fewer; a count above eight that eight
    does not divide is padded to whole groups, never taken in one step."""
    from scalable_hw_agnostic_inference_tpu.ops.pallas import kda_chunk

    groups, hg = grid
    x = jax.ShapeDtypeStruct((2, 2 * kda.CHUNK, H, 16), jnp.float32)
    beta = jax.ShapeDtypeStruct((2, 2 * kda.CHUNK, H), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda *a: kda_chunk.kda_chunk_prefill.__wrapped__(
        *a, interpret=False))(x, x, x, x, beta)
    [call] = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert tuple(call.params["grid_mapping"].grid) == (2, groups, 2)
    assert call.invars[0].aval.shape == (2, 2 * kda.CHUNK, groups * hg, 16)
    o, s = jaxpr.out_avals
    assert o.shape == x.shape and s.shape == (2, H, 16, 16)


def test_the_chunk_cases_tolerance_refuses_a_bfloat16_state():
    """What the logits cannot tell (the tolerance file's ``state_bf16``)
    the kernel's own case can: the state rounded after every token is tens
    of times over the bound the chunk kernel keeps (the test above)."""
    chunk = KDA_CASES[0]
    assert "chunk" in chunk.name
    assert kernel_check.kda_state_bf16_err(chunk) > 10 * chunk.tol


def test_the_cases_cover_both_kernels_and_the_null_slot():
    names = [c.name for c in kernel_check.kda_cases(32, 128)]
    assert names == ["kda-chunk-H32x128-T2048-b1",
                     "kda-step-H32x128-b4-S16", "kda-step-H32x128-b16-S16"]
    q, k, v, g, beta, arena, ids = KDA_CASES[-1].make_inputs(
        jax.random.PRNGKey(0))
    assert arena.shape[0] == 7 and list(np.asarray(ids[-2:])) == [6, 6]
    assert len(set(np.asarray(ids[:-2]).tolist())) == len(ids) - 2


def test_the_step_kernel_leaves_every_other_slot_alone():
    from scalable_hw_agnostic_inference_tpu.ops.pallas.kda_step import (
        kda_decode_step,
    )

    q, k, v, g, beta, arena, ids = KDA_CASES[-1].make_inputs(
        jax.random.PRNGKey(4))
    _, after = kda_decode_step(q, k, v, g, beta, arena, ids, interpret=True)
    named = set(np.asarray(ids).tolist())
    for slot in range(arena.shape[0] - 1):
        same = np.array_equal(np.asarray(after[slot]), np.asarray(arena[slot]))
        assert same == (slot not in named), slot


# -- the engine against the plain reference, on logits ----------------------

@pytest.mark.parametrize("n_prompt,env", [
    (20, {}),                       # one prefill bucket, recurrent decode
    (75, {}),    # chunks of 32 at starts 32 and 64 read the slot's state
    (40, {"SHAI_PAGED_DECODE": "1"}),     # both decode kernels, interpreted
    (30, {"SHAI_ASYNC_DECODE": "0"}),     # the lock-step loop
], ids=["one-bucket", "carried-chunks", "kernels", "lock-step"])
def test_engine_agrees_with_the_plain_reference_on_logits(
        tiny_params, n_prompt, env, monkeypatch):
    for k_, v_ in env.items():
        monkeypatch.setenv(k_, v_)
    prompt = _prompt(n_prompt)
    [fin] = _engine(tiny_params).generate([prompt], GREEDY)
    got = _against_reference(fin, prompt, tiny_params)
    assert got["finite"] and got["max_abs_logprob_diff"] < 0.6, got
    assert got["mean"] < 0.15, got


#: what two walks of one prompt may differ by AT THE LOGITS. The stream
#: between the layers and the logits themselves are bfloat16, so a float32's
#: last bit in a layer's output, where it falls on a rounding boundary, is a
#: whole bfloat16 step further down: 2 ** -6 of a logit between 2 and 4,
#: 2 ** -5 between 4 and 8, and a few of those by the fifth layer (twenty
#: prompts read 0, 0.0156 or up to 0.093 at an unchanged token, on the
#: 25-product form as on this one). A lost carry reads 0.71 in the mean
#: (``tolerance.kimi_linear.json``, the tiny size).
LOGIT_STEPS = 2.0 ** -3


@pytest.mark.parametrize("seed", [7, 8, 10])
def test_one_program_and_continuation_chunks_give_one_answer(tiny_params,
                                                             seed):
    """75 tokens through ONE prefill program (a bucket of 128) and through
    three (32, 32, 11: the state carried from program to program), on three
    prompts. The two walks cut the chunks elsewhere, so they differ in a
    float32's last bits. WHERE NOTHING ROUNDS THAT, they are held to it: the
    first layer is a KDA layer on the embedded tokens, and the state and
    tail it leaves in the slot are the same to 1e-6 of values near 1
    whatever the prompt. At the logits, five layers of a bfloat16 stream
    later, they are held to ``LOGIT_STEPS``, and a greedy token may differ
    only where both walks' two best tokens tie within that (seed 10 does, a
    step apart, on the 25-product form too); the walks are different
    sequences from there on and the comparison ends."""
    prompt = _prompt(75, seed)
    one_eng = _engine(tiny_params, context_encoding_buckets=(16, 32, 128))
    eng = _engine(tiny_params)
    # the prompt alone: no decode step touches the slot behind it
    first = SamplingParams(temperature=0.0, max_new_tokens=1)
    one_eng.generate([prompt], first)
    eng.generate([prompt], first)
    assert eng.obs.snapshot()["kda"]["chunk_carries"] == 2
    assert one_eng.obs.snapshot()["kda"]["chunk_carries"] == 0
    lay1, lay3 = one_eng.cache.kv[0], eng.cache.kv[0]
    assert lay1["s"].dtype == jnp.float32 and np.asarray(lay1["s"][0]).any()
    np.testing.assert_allclose(np.asarray(lay3["s"][0]),
                               np.asarray(lay1["s"][0]), atol=1e-6, rtol=0)
    np.testing.assert_array_equal(np.asarray(lay3["t"][0], np.float32),
                                  np.asarray(lay1["t"][0], np.float32))
    [one] = one_eng.generate([prompt], GREEDY)
    [three] = eng.generate([prompt], GREEDY)
    assert len(three.token_ids) == len(one.token_ids) == 8
    for a, b in zip(three.logprobs, one.logprobs):
        if a["token"] != b["token"]:
            for walk in (a, b):
                top = walk["top_logprobs"]
                assert top[0] - top[1] < LOGIT_STEPS, (seed, a, b)
            break
        assert abs(a["logprob"] - b["logprob"]) < LOGIT_STEPS, (seed, a, b)


@pytest.fixture(scope="module")
def right_and_wrong(tiny_params):
    """Differences of the served path against the reference, right and under
    every variant, over three prompts (one through carried chunks)."""
    variants = ("",) + REF.REFUSED_VARIANTS + REF.REFUSED_BY_MEAN + (
        REF.NOT_REFUSED_RELIABLY)
    mean = dict.fromkeys(variants, 0.0)
    worst = dict.fromkeys(variants, 0.0)
    eng = _engine(tiny_params)
    for n in (20, 75, 33):
        prompt = _prompt(n)
        [fin] = eng.generate([prompt], GREEDY)
        for variant in variants:
            got = _against_reference(fin, prompt, tiny_params, variant)
            mean[variant] += got["mean"] / 3
            worst[variant] = max(worst[variant], got["max_abs_logprob_diff"])
    return mean, worst


@pytest.mark.parametrize("variant",
                         REF.REFUSED_VARIANTS + REF.REFUSED_BY_MEAN)
def test_broken_mathematics_is_refused(right_and_wrong, variant):
    """Every refused variant reads far from the right path at the tiny size
    too, and over the tolerance's mean bound; ``no_carry`` is the one that
    only a continuation chunk can show."""
    mean, worst = right_and_wrong
    assert mean[variant] > 2.5 * mean[""], (variant, mean)
    assert mean[variant] > TOL["mean_abs_logprob_diff"], (variant, mean)
    assert mean[""] < TOL["mean_abs_logprob_diff"]
    assert worst[""] < TOL["max_abs_logprob_diff"]


def test_no_carry_shows_only_where_a_chunk_continues(tiny_params):
    short, long_ = _prompt(20), _prompt(75)
    eng = _engine(tiny_params)
    f_short, f_long = eng.generate([short, long_], GREEDY)
    same = _against_reference(f_short, short, tiny_params, "no_carry")
    right = _against_reference(f_short, short, tiny_params)
    # no boundary of 32 crossed: the variant is the right path
    assert same["mean"] == pytest.approx(right["mean"], rel=1e-4)
    assert _against_reference(f_long, long_, tiny_params,
                              "no_carry")["mean"] > 1.0


def test_the_variant_lists_are_disjoint_and_name_the_precision():
    lists = (REF.REFUSED_VARIANTS, REF.REFUSED_BY_MEAN,
             REF.NOT_REFUSED_RELIABLY, REF.ACCEPTED_VARIANTS)
    names = [v for lst in lists for v in lst]
    assert len(names) == len(set(names)) == 9
    # the precision below the stated bfloat16 is refused, by the mean; the
    # one no bound on the logits refuses is said to be so, with its readings
    assert REF.REFUSED_BY_MEAN == ("rope_on", "weights_fp8")
    assert REF.NOT_REFUSED_RELIABLY == ("state_bf16",)
    for name in ("weights_fp8", "state_bf16", "no_carry"):
        assert name in TOL["reason"]


# -- slots: reuse, padding, finished rows, leaks ----------------------------

def test_a_reused_slot_answers_as_a_fresh_engine(tiny_params):
    """Three requests after three others, through the same three slots,
    with nothing cleared between: prefill from position 0 starts from a
    zero state whatever the slot held."""
    first = [_prompt(n, seed=1) for n in (40, 22, 70)]
    then = [_prompt(n, seed=2) for n in (25, 66, 18)]
    eng = _engine(tiny_params)
    eng.generate(first, GREEDY)
    dirty = [np.asarray(eng.cache.kv[0]["s"][slot]).any()
             for slot in range(3)]
    assert all(dirty)                      # the slots hold the old states
    again = eng.generate(then, GREEDY)
    fresh = _engine(tiny_params).generate(then, GREEDY)
    for a, b in zip(again, fresh):
        assert a.token_ids == b.token_ids
        assert [e["logprob"] for e in a.logprobs] == [
            e["logprob"] for e in b.logprobs]
    assert eng.cache.slots_live == 0 and eng.cache.leaked_bytes == 0


@pytest.mark.parametrize("env", [{}, {"SHAI_PAGED_DECODE": "1"}],
                         ids=["plain", "kernels"])
def test_padded_and_finished_rows_write_to_no_slot(tiny_params, env,
                                                   monkeypatch):
    """Three rows decode in a bucket of 4 (one padded row); one finishes
    early. The padded row steps the NULL slot; the finished row's slot is
    not stepped again once the batch recomposes; a slot nobody was ever
    admitted to stays zeros."""
    for k_, v_ in env.items():
        monkeypatch.setenv(k_, v_)
    eng = _engine(tiny_params, max_num_seqs=5)
    sp = [SamplingParams(temperature=0.0, max_new_tokens=n)
          for n in (3, 9, 9)]
    rids = [eng.add_request(_prompt(n), p)
            for n, p in zip((20, 24, 28), sp)]
    snap = None
    done = {}
    while len(done) < 3:
        for f in eng.step():
            done[f.req_id] = f
        if rids[0] in done and snap is None:
            eng.finish_pending()
            snap = [np.asarray(lay["s"][0]).copy()
                    for lay in eng.cache.kv[:4]]
    eng.finish_pending()
    for lay, before in zip(eng.cache.kv[:4], snap):
        # slot 0 (the row that finished first) as it was when it finished
        np.testing.assert_array_equal(np.asarray(lay["s"][0]), before)
        # slots 3 and 4 never held a sequence: no padded row reached them
        assert not np.asarray(lay["s"][3:5]).any()
        assert not np.asarray(lay["t"][3:5], np.float32).any()
        assert np.asarray(lay["s"][5]).any()      # the null slot took them
    solo = [_engine(tiny_params).generate([_prompt(n)], p)[0].token_ids
            for n, p in zip((20, 24, 28), sp)]
    assert [done[r].token_ids for r in rids] == solo


def test_a_preempted_request_resumes_on_its_own_tokens(tiny_params):
    """Four requests over a pool that two outgrow: the engine preempts and
    re-admits; the resumed prefill REBUILDS the state from position 0, and
    every request ends with its solo tokens. Neither kind of state leaks."""
    prompts = [_prompt(n) for n in (30, 28, 26, 24)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=12)
    solo = [_engine(tiny_params).generate([p], sp)[0].token_ids
            for p in prompts]
    eng = _engine(tiny_params, num_blocks=11, max_model_len=64)
    fins = eng.generate(prompts, sp)
    assert [f.token_ids for f in fins] == solo
    assert eng.obs.snapshot()["preemptions"] > 0
    assert eng.cache.allocator.n_free == 10
    assert eng.cache.leaked_bytes == 0 == eng.cache.state_leaked_bytes
    assert eng.cache.slots_live == 0 == eng.cache.state_used_bytes


def test_a_cancelled_request_gives_both_kinds_of_state_back(tiny_params):
    eng = _engine(tiny_params)
    keep, drop, long_ = _prompt(21), _prompt(27), _prompt(90)
    sp = SamplingParams(temperature=0.0, max_new_tokens=10)
    r_keep = eng.add_request(keep, sp)
    r_drop = eng.add_request(drop, sp)
    r_long = eng.add_request(long_, sp)           # cancelled mid-prefill
    for _ in range(3):
        eng.step()
    assert eng.cache.slots_live == 3
    assert eng.cache.state_used_bytes == 3 * eng.cache.state_bytes // 4
    assert eng.cancel(r_drop).stop_reason == "cancelled"
    assert eng.cancel(r_long).stop_reason == "cancelled"
    assert eng.cache.slots_live == 1
    done = {}
    while r_keep not in done:
        for f in eng.step():
            done[f.req_id] = f
    [solo] = _engine(tiny_params).generate([keep], sp)
    assert done[r_keep].token_ids == solo.token_ids
    assert eng.cache.leaked_bytes == 0 and eng.cache.slots_live == 0
    assert eng.cache.allocator.n_free == eng.cache.total_blocks - 1


def test_batched_rows_decode_as_they_do_alone(tiny_params):
    prompts = [_prompt(n) for n in (20, 9, 33)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=10)
    together = _engine(tiny_params).generate(prompts, sp)
    for p, f in zip(prompts, together):
        [alone] = _engine(tiny_params).generate([p], sp)
        assert f.token_ids == alone.token_ids


# -- two kinds of state in one manager --------------------------------------

def _cache(**kw):
    spec = RecurrentSpec((0, 1, 3), {"s": ((2, 4, 4), "float32"),
                                     "t": ((3, 24), None)}, n_slots=3)
    return PagedKVCache(2, {"c": (128,)}, 9, 8, 4, recurrent=spec, **kw)


def test_the_pool_is_sized_by_the_layers_that_have_rows():
    cache = _cache()
    assert [sorted(lay) for lay in cache.kv] == [
        ["s", "t"], ["s", "t"], ["c"], ["s", "t"], ["c"]]
    assert cache.kv[0]["s"].shape == (4, 2, 4, 4)       # 3 slots + null
    assert cache.kv[0]["s"].dtype == jnp.float32
    assert cache.kv[0]["t"].dtype == cache.kv[2]["c"].dtype == jnp.bfloat16
    assert cache.pool_bytes == 2 * 9 * 8 * 128 * 2      # TWO paged layers
    assert cache.state_bytes == 3 * 4 * (2 * 4 * 4 * 4 + 3 * 24 * 2)
    plain = PagedKVCache(2, {"c": (128,)}, 9, 8, 4)
    assert plain.state_bytes == 0 and plain.pool_bytes == cache.pool_bytes


def test_a_sequence_is_admitted_with_its_slot_and_gives_it_back():
    cache = _cache()
    with pytest.raises(ValueError, match="admits with a slot"):
        cache.admit(1, 20)
    with pytest.raises(ValueError, match="admits with a slot"):
        cache.admit(1, 20, slot=3)                      # the null slot
    cache.admit(1, 20, slot=2)
    with pytest.raises(ValueError, match="slot 2 is held by seq 1"):
        cache.admit(2, 9, slot=2)
    cache.admit(2, 9, slot=0)
    per_slot = cache.state_bytes // 4
    assert cache.slots_live == 2
    assert cache.state_used_bytes == 2 * per_slot
    cache.release(1)
    assert cache.slots_live == 1 and cache.leaked_bytes == 0
    cache.admit(3, 12, slot=2)                          # reused, uncleared
    # a sequence that vanishes without a release is a leak of BOTH kinds
    alloc = cache._seqs.pop(3)
    assert cache.state_leaked_bytes == per_slot
    assert cache.leaked_bytes == per_slot + (
        cache.pool_bytes * len(alloc.blocks) / cache.total_blocks)


@pytest.mark.parametrize("kw", [{"quant": True}, {"tier": object()}])
def test_the_arena_has_no_int8_pool_and_no_host_tier(kw):
    with pytest.raises((ValueError, AssertionError)):
        _cache(**kw)


# -- what the boot refuses, by name -----------------------------------------

@pytest.mark.parametrize("env,over,names", [
    ({}, {"enable_prefix_caching": True},
     "enable_prefix_caching .*restores no state.* with recurrent state"),
    ({"SHAI_KVTIER": "1"}, {},
     "SHAI_KVTIER .*migration.* with recurrent state"),
    ({}, {"speculative_model": "[ngram]", "num_speculative_tokens": 2},
     "speculative decoding .*rolled back.* with recurrent state"),
    ({"SHAI_KV_COW": "1"}, {}, "SHAI_KV_COW .* with recurrent state"),
    ({}, {"tensor_parallel_size": 2},
     "tensor_parallel_size > 1 .* with recurrent state"),
    ({}, {"quantization": "int8"},
     "quantization: int8 .* with recurrent state"),
    ({"SHAI_KV_QUANT": "int8"}, {},
     "SHAI_KV_QUANT=int8 .* with recurrent state"),
], ids=["prefix-caching", "kvtier", "speculation", "copy-on-write", "tp",
        "int8-weights", "int8-kv"])
def test_unsupported_combinations_are_refused_by_name(
        tiny_params, env, over, names, monkeypatch):
    for k_, v_ in env.items():
        monkeypatch.setenv(k_, v_)
    with pytest.raises(ValueError, match=names):
        _engine(tiny_params, **over)


def test_the_deleted_switches_are_not_read(tiny_params, monkeypatch):
    """``SHAI_RAGGED_ATTENTION`` and ``SHAI_FUSED_STEP`` chose programs that
    are gone. A deployment that still sets them boots (they were refused
    here by name) and serves what one without them serves."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    prompt = _prompt(40)             # a prefill and one continuation chunk
    [plain] = _engine(tiny_params).generate([prompt], sp)
    monkeypatch.setenv("SHAI_RAGGED_ATTENTION", "1")
    monkeypatch.setenv("SHAI_FUSED_STEP", "1")
    [flagged] = _engine(tiny_params).generate([prompt], sp)
    assert flagged.token_ids == plain.token_ids


def test_a_soft_prefix_is_refused_and_a_snapshot_is_a_cold_manifest(
        tiny_params):
    """A soft prefix's prefill carries no slot: refused at the door. A
    migration snapshot banks no blocks (the tier is refused at boot), so
    its manifest is the cold rung's: the peer recomputes from the tokens,
    which rebuilds the state as a resume after preemption does."""
    eng = _engine(tiny_params)
    with pytest.raises(ValueError, match="soft prefix .* recurrent state"):
        eng.add_request(_prompt(9), prefix=np.zeros((4, TINY.dim)))
    prompt = _prompt(20)
    rid = eng.add_request(prompt, GREEDY)
    eng.step()
    eng.finish_pending()
    man = eng.snapshot_sequence(rid)
    assert man["prompt_ids"][:20] == prompt and not man.get("kv_hashes")
    assert eng.cache.tier is None


@pytest.mark.parametrize("kw,names", [
    ({"quant": True}, "int8"), ({"mesh": object()}, "tensor_parallel_size")])
def test_kda_weights_are_not_born_int8_or_sharded(kw, names):
    plain = dataclasses.replace(TINY, n_experts=0, n_dense_layers=0,
                                experts_held=(), kv_lora_rank=0,
                                layer_types=("linear_attention",) * 5)
    with pytest.raises(ValueError, match=names + ".*recurrent state"):
        geometry_params(plain, **kw)


def test_the_flax_module_refuses_a_recurrent_config():
    with pytest.raises(ValueError, match="paged"):
        LlamaForCausalLM(TINY).init(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 8), jnp.int32))


# -- the seeded leaves ------------------------------------------------------

def test_the_kda_leaves_and_their_draws(tiny_params):
    at = tiny_params["params"]["layer_2"]["attn"]
    assert sorted(at) == ["A_log", "b", "dt_bias", "f_a", "f_b", "g_a",
                          "g_b", "k", "k_conv", "o", "o_norm", "q", "q_conv",
                          "v", "v_conv"]
    assert at["q_conv"].shape == (4, 64) and at["f_a"]["kernel"].shape == (
        64, 16)
    a = np.exp(np.asarray(at["A_log"]))
    assert (a >= KDA_A_RANGE[0]).all() and (a <= KDA_A_RANGE[1]).all()
    dt = np.asarray(jax.nn.softplus(at["dt_bias"]))
    assert dt.min() >= KDA_DT_RANGE[0] * 0.99
    assert dt.max() <= KDA_DT_RANGE[1] * 1.01
    stage = jax.eval_shape(
        lambda: geometry_params(LlamaConfig.kimi_linear_stage()))["params"]
    assert stage["layer_1"]["moe"]["experts"]["gate"].shape == (
        128, 2304, 1024)                               # the held experts
    assert stage["layer_1"]["moe"]["router"]["kernel"].shape == (2304, 256)
    conv = geometry_params(
        dataclasses.replace(TINY, n_layers=1, layer_types=(
            "linear_attention",), n_experts=0, experts_held=(),
            n_dense_layers=0, kda_heads=64, kda_head_dim=64),
        seed=2)["params"]["layer_0"]["attn"]["v_conv"]
    assert abs(float(jnp.std(conv.astype(jnp.float32))) - KDA_CONV_STD) < 0.02
    whole = sum(np.prod(a.shape) * a.dtype.itemsize
                for a in jax.tree.leaves(stage))
    table = SPEC.config(NAME)["memory"]
    assert whole == pytest.approx(table["weights_bytes"], rel=1e-3)
    assert table["weights_bytes"] == pytest.approx(9.32e9, rel=0.01)


# -- the expert layer's shares under this router ----------------------------

@pytest.mark.parametrize("n_shares,E,k", [(2, 16, 8), (2, 256, 8)],
                         ids=["tiny-2x8of16", "published-2x128of256"])
def test_two_shares_of_the_experts_sum_to_the_uncut_layer(n_shares, E, k):
    """The guide's share test with THIS router: top-``k`` of ``E`` sigmoid
    scores, renormalised and scaled by 2.446, one shared expert; every
    holder routes over all ``E`` and computes its own half; the halves plus
    the shared expert ONCE are the uncut layer."""
    cfg = dataclasses.replace(TINY, n_experts=E, n_experts_per_tok=k,
                              dim=32, moe_mlp_dim=16, experts_held=())
    D, F = 32, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    mp = {"router": {"kernel": jax.random.normal(ks[0], (D, E)) * 0.3},
          "bias": jax.random.normal(ks[1], (E,)) * 0.05,
          "experts": {"gate": jax.random.normal(ks[2], (E, D, F)) * 0.2,
                      "up": jax.random.normal(ks[3], (E, D, F)) * 0.2,
                      "down": jax.random.normal(ks[4], (E, F, D)) * 0.2},
          "shared": {n: {"kernel": jax.random.normal(kk, s) * 0.2}
                     for n, kk, s in (("gate", ks[5], (D, F)),
                                      ("up", ks[6], (D, F)),
                                      ("down", ks[7], (F, D)))}}
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 7, D))
    active = jnp.arange(21).reshape(3, 7) % 5 != 0
    whole, stats = expert_layer(mp, x, cfg, active=active)
    routed = dataclasses.replace(cfg, n_shared_experts=0)
    parts = gated_mlp(mp["shared"], x)
    per = E // n_shares
    for share in range(n_shares):
        lo = share * per
        held = {**mp, "experts": {n: w[lo:lo + per]
                                  for n, w in mp["experts"].items()}}
        part, st = expert_layer(held, x, routed, active=active,
                                held=(lo, per))
        assert (st == stats).all()
        parts = parts + part
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=2e-5, atol=2e-6)


def test_the_layer_function_hands_the_configurations_share_on(tiny_params):
    """``engine/runner.py`` passes ``cfg.held`` to ``expert_layer``: the
    engine's routed output is the held half's, the reference's too."""
    mp = tiny_params["params"]["layer_1"]["moe"]
    assert mp["experts"]["gate"].shape[0] == 8 == TINY.held[1]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 6, TINY.dim))
    got, _ = expert_layer(mp, x, TINY, held=TINY.held)
    want = REF.routed(x[0], mp, top_k=TINY.n_experts_per_tok, renorm=True,
                      route_scale=2.446, first=0)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    with pytest.raises(Exception):      # all 16 claimed, 8 stacked
        expert_layer(mp, x, TINY)[0].block_until_ready()


# -- counters, gauges, the ledger -------------------------------------------

def test_kda_counters_and_the_arena_in_the_ledger(tiny_params):
    eng = _engine(tiny_params)
    prompts = [_prompt(n) for n in (20, 75)]
    fins = eng.generate(prompts, SamplingParams(temperature=0.0,
                                                max_new_tokens=8))
    eng.finish_pending()
    snap = eng.obs.snapshot()
    n_kda = len(TINY.kda_layers)
    assert snap["kda"]["prefill_tokens"] == (20 + 75) * n_kda
    assert snap["kda"]["chunk_carries"] == 2            # 75 = 32 + 32 + 11
    steps = snap["dispatches_by_phase"]["decode"]
    rows = sum(len(f.token_ids) for f in fins)
    # every decode dispatch steps its live rows in every KDA layer; the
    # async lookahead may step a row once past its last token
    assert (rows - 2) * n_kda <= snap["kda"]["rows_stepped"] <= (
        2 * steps * n_kda)
    assert snap["kda"]["slots_live"] == 0
    assert max(s.get("state_slots_live", 0)
               for s in eng.obs.recent_steps(256)) == 2
    assert snap["mla"]["layer_steps"] == steps          # ONE latent layer
    hbm = eng.obs.hbm.snapshot()
    assert hbm["recurrent_state_bytes"] == eng.cache.state_bytes > 0
    assert hbm["kv_pool_bytes"] == eng.cache.pool_bytes
    from scalable_hw_agnostic_inference_tpu.serve.metrics import (
        EngineTelemetryCollector,
    )

    fams = {f.name: f for f in EngineTelemetryCollector(
        lambda: eng.obs, "t").collect()}
    got = {s.labels["counter"]: s.value
           for s in fams["shai_engine_kda"].samples}
    assert got == {k_: float(v_) for k_, v_ in snap["kda"].items()}


def test_a_model_without_recurrent_layers_counts_none():
    cfg = LlamaConfig.tiny_mla()
    eng = _engine(geometry_params(cfg, dtype=jnp.float32, seed=1), cfg=cfg)
    eng.generate([_prompt(12)], SamplingParams(temperature=0.0,
                                               max_new_tokens=4))
    snap = eng.obs.snapshot()
    assert "kda" not in snap and "mla" in snap
    assert "recurrent_state_bytes" not in eng.obs.hbm.snapshot()
    assert all("state_slots_live" not in s
               for s in eng.obs.recent_steps(16))


def test_the_budget_prices_the_arena_and_the_one_paged_layer():
    from scalable_hw_agnostic_inference_tpu.core.budget import (
        GIB,
        causal_lm_budget,
    )

    cfg = SPEC.config(NAME)
    eng = {k_: v_ for k_, v_ in cfg["engine"].items()
           if k_ not in ("quantization", "context_encoding_buckets")}
    b = causal_lm_budget(
        LlamaConfig.kimi_linear_stage(),
        EngineConfig(**eng, context_encoding_buckets=tuple(
            cfg["engine"]["context_encoding_buckets"])))
    mem = cfg["memory"]
    assert b.params_gib * GIB == pytest.approx(mem["weights_bytes"],
                                               rel=2e-3)
    assert b.kv_gib * GIB == pytest.approx(
        mem["kv_pool_bytes"] + mem["state_arena_bytes"])
    assert mem["kv_pool_bytes"] == 16704 * 16 * 640 * 2     # ONE layer
    assert mem["state_arena_bytes"] == 17 * 4 * (2_097_152 + 73_728)
    assert b.fits


# -- the other architectures' programs are what they were -------------------

def _step_program_text(cfg, kv_leaf, program):
    params = jax.eval_shape(lambda: geometry_params(cfg))
    B, bs, M = 2, 8, 4
    sds = jax.ShapeDtypeStruct
    kv = [dict(kv_leaf) for _ in range(cfg.n_layers)]
    pre = (params, kv, sds((1, 16), jnp.int32), sds((1,), jnp.int32),
           sds((1, M), jnp.int32))
    if program == "decode":
        return str(jax.make_jaxpr(runner.make_decode(
            cfg, bs, M, B, paged=True, feedback=True))(
            params, kv, sds((B,), jnp.int32), sds((B,), jnp.int32),
            sds((B, M), jnp.int32), sds((B,), jnp.float32),
            sds((2,), jnp.uint32), sds((), jnp.int32),
            sds((B,), jnp.float32), sds((B,), jnp.int32),
            sds((B,), jnp.float32)))
    if program == "prefill":
        return str(jax.make_jaxpr(runner.make_prefill(cfg, bs, M, 16))(*pre))
    return str(jax.make_jaxpr(runner.make_prefill_cont(
        cfg, bs, M, 16, start_blocks=2))(*pre))


@pytest.mark.parametrize("preset", ["tiny", "tiny_afmoe", "tiny_mla"])
@pytest.mark.parametrize("program", ["decode", "prefill", "cont"])
def test_with_no_kda_layer_the_step_programs_are_what_they_were(
        preset, program):
    """The Mistral, Trinity and Kanana stand-ins' step programs with the KDA
    kind's fields SET but no layer of that kind trace to the very jaxpr of
    the plain config's: the same arguments, nothing of the recurrent path
    traced. (Against the parent commit itself the real-width programs of
    ``mistral_7b``, ``trinity_mini_stage`` and ``kanana2_stage`` were
    compared text for text: PERF.md, PR 34.)"""
    plain = getattr(LlamaConfig, preset)()
    named = dataclasses.replace(plain, kda_heads=4, kda_head_dim=16,
                                kda_conv=4)
    assert not named.recurrent and named.held is None
    leaf = {n: jax.ShapeDtypeStruct((9, 8) + per, jnp.float32)
            for n, per in cache_leaves(plain).items()}
    a, b = (_step_program_text(c, leaf, program) for c in (plain, named))
    assert a == b
    assert "kda_" not in a


@pytest.mark.parametrize("preset", ["tiny", "tiny_afmoe", "tiny_mla"])
@pytest.mark.parametrize("program", ["decode", "prefill", "cont"])
def test_the_stand_ins_keep_the_plain_expert_product(preset, program):
    """At widths no kernel can tile the expert product stays ``ragged_dot``
    whatever the rows, so the stand-ins' step programs (and Mistral's, which
    has no expert layer) trace no expert kernel: with the tiled form in the
    tree they are the parent's character for character (compared text for
    text against the parent commit at 16 and 256 rows, with ``mistral_7b``
    at 512 and the routed stages' decode programs: PERF.md, PR 37)."""
    cfg = getattr(LlamaConfig, preset)()
    leaf = {n: jax.ShapeDtypeStruct((9, 8) + per, jnp.float32)
            for n, per in cache_leaves(cfg).items()}
    text = _step_program_text(cfg, leaf, program)
    assert "moe_grouped_ffn_tiled" not in text
    assert "moe_grouped_ffn_streamed" not in text
    assert ("ragged_dot" in text) == bool(cfg.n_moe_layers)


def test_a_short_flash_call_asks_for_no_more_vmem_than_it_did():
    """The flash kernel asks Mosaic for VMEM past its default only where K
    and V of a head outgrow it (16k keys of 192): Kanana's longest call
    (10,240 keys) and every shorter one trace as they did."""
    from scalable_hw_agnostic_inference_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )

    def text(S):
        sds = jax.ShapeDtypeStruct
        return str(jax.make_jaxpr(
            lambda q, k, v, n: flash_attention(
                q, k, v, causal=True, lengths=n, interpret=False))(
            sds((1, 128, 2, 192), jnp.bfloat16),
            sds((1, S, 2, 192), jnp.bfloat16),
            sds((1, S, 2, 128), jnp.bfloat16), sds((1,), jnp.int32)))

    assert "compiler_params=FrozenDict({})" in text(10240)
    assert "compiler_params=FrozenDict({})" not in text(16384)
