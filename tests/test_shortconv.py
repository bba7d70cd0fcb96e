"""A double-gated short convolution as a third kind of slot state
(LFM2-24B-A2B / ``lfm2_moe``: ``out(C * conv3(B * x))`` in three layers of
four, QK-normed rotary heads narrower than the TPU's lanes in the fourth,
sigmoid-routed experts with no shared one, a head tied to the embedding) on
the engine's normal path, at the tiny size on the CPU: the convolved form
against the token recurrence; prefill, a carried chunk and decode steps
against one pass; a pad and a reused slot; rows of unequal length; the conv
mixer, the attention mixer and a whole dense-FFN block against
``transformers.models.lfm2``; the engine (prefill, continuation chunks that
read a slot's tail, decode through slots and the padded pool) against the
plain reference on logits; every broken variant refused; what the boot
refuses, by name; the counters; both attention kernels at heads of 64,
interpreted; and the other architectures' programs untouched.

The guide's "shares add up" test has no place here: no expert share is cut
(every one of a layer's experts is held), so there is nothing to add up."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import check
from benchmark.spec import Spec
from scalable_hw_agnostic_inference_tpu.engine import EngineConfig, runner
from scalable_hw_agnostic_inference_tpu.engine.engine import (
    LLMEngine,
    SamplingParams,
)
from scalable_hw_agnostic_inference_tpu.models.llama import (
    LlamaConfig,
    cache_leaves,
    conv_tap_range,
    geometry_params,
    state_leaves,
)
from scalable_hw_agnostic_inference_tpu.obs.steploop import RECURRENT_KINDS
from scalable_hw_agnostic_inference_tpu.ops import kernel_check, shortconv
from scalable_hw_agnostic_inference_tpu.ops.attention import (
    dot_product_attention,
)

SPEC = Spec()
NAME = "lfm2-24b-a2b-bf16"
TINY = LlamaConfig.tiny_lfm2()
TINY_MODEL = SPEC.dry_run_model("tiny-lfm2")
REF = SPEC.reference("lfm2_moe")
TOL = SPEC.tolerance("tolerance.lfm2_moe.json")
KINDS = ["conv", "full_attention", "conv", "conv", "conv", "full_attention",
         "conv", "conv", "conv"]


@pytest.fixture(scope="module")
def tiny_params():
    return geometry_params(TINY, dtype=jnp.float32, seed=3)


@pytest.fixture(scope="module", autouse=True)
def one_compile_a_program():
    """The engines of this file that ask for the same step program get ONE
    jitted function, and so one compile (as ``tests/test_ssm.py``)."""
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

FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_dense_layers": "n_dense_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "conv_L_cache": "conv_taps", "intermediate_size": "mlp_dim",
    "moe_intermediate_size": "moe_mlp_dim",
    "max_position_embeddings": "max_seq_len", "norm_eps": "rms_eps",
    "num_experts": "n_experts", "num_experts_per_tok": "n_experts_per_tok",
    "norm_topk_prob": "route_norm", "routed_scaling_factor": "route_scale"}


def _published(model, key, cfg):
    if key == "layer_types":
        return list(cfg.layer_types) == model[key]
    if key == "rope_theta":
        return cfg.rope_theta == model["rope_parameters"]["rope_theta"]
    return getattr(cfg, FIELDS[key]) == model[key]


@pytest.mark.parametrize("key", sorted(FIELDS) + ["layer_types",
                                                  "rope_theta"])
def test_the_tiny_stand_in_is_the_programs_preset(key):
    """``benchmark/configs/dry_run/tiny-lfm2.json`` against
    ``LlamaConfig.tiny_lfm2()`` field by field: the reference reads the
    file, the dry run serves the preset, and the two cannot drift."""
    assert _published(TINY_MODEL, key, TINY)
    assert (TINY_MODEL["head_dim"], TINY_MODEL["head_lanes"],
            TINY_MODEL["tie_word_embeddings"]) == (
        TINY.head_dim, TINY.head_lanes, TINY.tie_embeddings)


@pytest.mark.parametrize("key", sorted(FIELDS) + ["layer_types",
                                                  "rope_theta"])
def test_the_stage_is_the_published_model_cut_in_depth(key):
    """``lfm2_24b_stage()`` against the configuration file key by key, and
    the whole model against the keys the file says it cut."""
    m = SPEC.config(NAME)
    stage, whole = LlamaConfig.lfm2_24b_stage(), LlamaConfig.lfm2_24b()
    assert _published(m, key, stage)
    if key in m["published"]:
        assert _published({**m, **m["published"]}, key, whole)
        assert not _published(m, key, whole)
    else:
        assert _published(m, key, whole)           # no width is cut
    assert stage.head_dim == 64 and stage.head_lanes == 128
    assert stage.tie_embeddings and stage.qk_norm and not stage.experts_held
    assert stage.n_shared_experts == 0 and stage.mlp_act == "silu"


def test_the_stage_is_the_models_layers_one_to_nine():
    stage, whole = LlamaConfig.lfm2_24b_stage(), LlamaConfig.lfm2_24b()
    assert whole.n_layers == 40 and whole.layer_types.count("conv") == 30
    assert [i for i, t in enumerate(whole.layer_types)
            if t == "full_attention"] == list(range(2, 40, 4))
    assert list(stage.layer_types) == KINDS == list(whole.layer_types[1:10])
    assert list(TINY.layer_types) == KINDS
    # one dense layer, then two whole periods: 6 conv to 2 attention
    routed = [t for li, t in enumerate(stage.layer_types) if stage.moe_of(li)]
    assert (routed.count("conv"), routed.count("full_attention")) == (6, 2)
    assert not stage.moe_of(0) and stage.n_moe_layers == 8


def test_from_hf_reads_the_lfm2_moe_keys():
    m = {**SPEC.config(NAME), **SPEC.config(NAME)["published"]}
    hf = types.SimpleNamespace(**{k: m[k] for k in (
        "vocab_size", "hidden_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "intermediate_size",
        "max_position_embeddings", "rope_parameters", "norm_eps",
        "layer_types", "num_experts", "num_experts_per_tok",
        "moe_intermediate_size", "num_dense_layers", "norm_topk_prob",
        "routed_scaling_factor", "conv_L_cache", "use_expert_bias")})
    assert LlamaConfig.from_hf(hf) == LlamaConfig.lfm2_24b()


@pytest.mark.parametrize("preset,kind", [
    ("tiny", ""), ("tiny_afmoe", ""), ("tiny_mla", ""), ("tiny_kda", "kda"),
    ("tiny_ssm", "ssm"), ("tiny_lfm2", "conv")])
def test_a_models_recurrent_kind_is_looked_up(preset, kind):
    """``state_kind``, ``state_leaves``, ``_RECURRENT`` and
    ``count_recurrent`` take a kind by lookup: every kind a preset can have
    is one the runner has two phases for and the telemetry an entry for."""
    cfg = getattr(LlamaConfig, preset)()
    assert cfg.state_kind == kind
    assert bool(state_leaves(cfg)) == bool(kind) == cfg.recurrent
    if kind:
        assert kind in RECURRENT_KINDS
        mod = runner._RECURRENT[kind]
        assert mod.state_shapes(cfg) == state_leaves(cfg)
        assert callable(mod.prefill) and callable(mod.decode)
    assert set(runner._RECURRENT) == set(RECURRENT_KINDS)


def test_a_layer_says_what_it_costs_the_pool_and_a_slot():
    """A conv layer costs a slot its tail and the pool nothing; an attention
    layer costs the pool a row of ``head_lanes`` lanes a head."""
    assert state_leaves(TINY) == {"t": ((2, 64), None)}
    assert cache_leaves(TINY) == {"k": (2, 32), "v": (2, 32)}
    for li, t in enumerate(KINDS):
        assert bool(state_leaves(TINY, li)) == (t == "conv")
        assert bool(cache_leaves(TINY, li)) == (t == "full_attention")
    assert TINY.state_layers == (0, 2, 3, 4, 6, 7, 8)
    assert TINY.n_paged_layers == 2
    stage = LlamaConfig.lfm2_24b_stage()
    assert state_leaves(stage) == {"t": ((2, 2048), None)}     # 8 KiB, bf16
    assert cache_leaves(stage) == {"k": (8, 128), "v": (8, 128)}
    assert (stage.kv_lanes, stage.attn_scale) == (128, 64 ** -0.5)
    plain = LlamaConfig.mistral_7b()
    assert (plain.kv_lanes, plain.attn_scale) == (128, None)
    with pytest.raises(ValueError, match="head_lanes"):
        LlamaConfig(head_dim=64, head_lanes=32)


# -- the mixer: the convolved form, the recurrence, the phases ---------------

def _mixer(D=64, K=3, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"in": {"kernel": jax.random.normal(k[0], (D, 3 * D)) * D ** -0.5},
            "conv": jax.random.uniform(k[1], (K, D), jnp.float32,
                                       *conv_tap_range(K)),
            "o": {"kernel": jax.random.normal(k[2], (D, D)) * D ** -0.5}}


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("T", [1, 2, 3, 17, 64])
@pytest.mark.parametrize("K", [3, 4])
def test_the_convolved_form_is_the_token_recurrence(T, K):
    at = _mixer(K=K)
    h = jax.random.normal(jax.random.PRNGKey(T), (2, T, 64))
    tail = jax.random.normal(jax.random.PRNGKey(9), (2, K - 1, 64))
    for t0 in (None, tail):
        out, ext = shortconv.mix(at, h, t0)
        want, last = shortconv.recurrence(at, h, t0)
        _close(out, want)
        _close(ext[:, T:], last)


def test_tap_k_minus_one_meets_the_current_token():
    """``c_t = k_0 v_{t-2} + k_1 v_{t-1} + k_2 v_t`` by hand, ``v`` before
    position 0 zero."""
    at = _mixer()
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 64))
    v, c = shortconv.gates(at, h)
    w = at["conv"]
    for t in range(5):
        conv = sum(w[2 - b] * v[0, t - b] for b in range(3) if t - b >= 0)
        _close(shortconv.mix(at, h, None)[0][0, t], c[0, t] * conv)


def test_prefill_a_carried_chunk_and_decode_steps_are_one_pass():
    """20 tokens through a prefill program of 8, a continuation chunk of 8
    that reads the slot's tail, and 4 decode steps in place on the slot,
    against ONE pass over all 20."""
    at = _mixer()
    cfg = types.SimpleNamespace(dim=64, conv_taps=3)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 20, 64))
    want, _ = shortconv.recurrence(at, h)
    state = {"t": jnp.full((4, 2, 64), 7.0)}      # what the slots held
    slots = jnp.array([2, 0])
    full = jnp.array([8, 8])
    a, state = shortconv.prefill(at, h[:, :8], state, slots, full, cfg,
                                 carry=False, kernel=False)
    b, state = shortconv.prefill(at, h[:, 8:16], state, slots, full, cfg,
                                 carry=True, kernel=False)
    outs = [a, b]
    for t in range(16, 20):
        o, state = shortconv.decode(at, h[:, t:t + 1], state, slots, cfg,
                                    kernel=False)
        outs.append(o)
    _close(jnp.concatenate(outs, axis=1), want)
    assert (np.asarray(state["t"][1]) == 7).all()    # other slots untouched
    assert (np.asarray(state["t"][3]) == 7).all()


def test_a_pad_does_not_enter_the_tail_and_rows_take_their_own_length():
    """Three rows of 5, 8 and 1 real tokens in one program of 8: each row's
    tail is what its last REAL tokens left, its real outputs what the row
    gives alone, and a row of no token keeps a zero tail."""
    at = _mixer()
    cfg = types.SimpleNamespace(dim=64, conv_taps=3)
    h = jax.random.normal(jax.random.PRNGKey(3), (4, 8, 64))
    n = jnp.array([5, 8, 1, 0])
    state = {"t": jnp.full((5, 2, 64), 3.0)}
    out, state = shortconv.prefill(at, h, state, jnp.arange(4), n, cfg,
                                   carry=False, kernel=False)
    for r, k in enumerate([5, 8, 1]):
        want, tail = shortconv.recurrence(at, h[r:r + 1, :k])
        _close(out[r, :k], want[0])
        _close(state["t"][r], tail[0])
    assert not np.asarray(state["t"][3]).any()
    assert (np.asarray(state["t"][4]) == 3).all()


# -- library parity ---------------------------------------------------------

def _lfm2(**kw):
    torch = pytest.importorskip("torch")
    lfm2 = pytest.importorskip("transformers.models.lfm2")
    cfg = lfm2.Lfm2Config(
        vocab_size=TINY.vocab_size, hidden_size=TINY.dim,
        intermediate_size=TINY.mlp_dim, num_hidden_layers=2,
        num_attention_heads=TINY.n_heads,
        num_key_value_heads=TINY.n_kv_heads, norm_eps=TINY.rms_eps,
        rope_theta=TINY.rope_theta, conv_L_cache=TINY.conv_taps,
        conv_bias=False, block_auto_adjust_ff_dim=False,
        layer_types=["conv", "full_attention"], **kw)
    cfg._attn_implementation = "eager"
    return torch, lfm2.modeling_lfm2, cfg


def _t(torch, a):
    return torch.tensor(np.asarray(a, np.float32))


def _load_conv(torch, mod, at):
    with torch.no_grad():
        mod.in_proj.weight.copy_(_t(torch, at["in"]["kernel"]).T)
        mod.conv.weight.copy_(_t(torch, at["conv"]).T[:, None, :])
        mod.out_proj.weight.copy_(_t(torch, at["o"]["kernel"]).T)


def _load_attention(torch, mod, at):
    with torch.no_grad():
        for ours, theirs in (("q", "q_proj"), ("k", "k_proj"),
                             ("v", "v_proj"), ("o", "out_proj")):
            getattr(mod, theirs).weight.copy_(
                _t(torch, at[ours]["kernel"]).T)
        mod.q_layernorm.weight.copy_(_t(torch, at["q_norm"]["scale"]))
        mod.k_layernorm.weight.copy_(_t(torch, at["k_norm"]["scale"]))


def _rope_and_mask(torch, m, cfg, T):
    pos = torch.arange(T)[None]
    cos_sin = m.Lfm2RotaryEmbedding(cfg)(torch.zeros(1, T, TINY.dim), pos)
    mask = torch.full((T, T), float("-inf")).triu(1)[None, None]
    return cos_sin, mask


def _plain_attend(q, k, v, window):
    return dot_product_attention(q, k, v, causal=True,
                                 scale=TINY.attn_scale)


def test_the_conv_mixer_is_transformers_lfm2_short_conv(tiny_params):
    """``W_out (C * conv3(B * x))`` on this tree's leaves against
    ``Lfm2ShortConv.slow_forward``: the split's order, the taps' order, no
    activation."""
    torch, m, cfg = _lfm2()
    at = tiny_params["params"]["layer_0"]["attn"]
    mod = m.Lfm2ShortConv(cfg, 0).float()
    _load_conv(torch, mod, at)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 21, TINY.dim))
    with torch.no_grad():
        want = mod.slow_forward(_t(torch, h)).numpy()
    got = shortconv.mix(at, h, None)[0] @ at["o"]["kernel"]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_the_attention_mixer_is_transformers_lfm2_attention(tiny_params):
    """Head norms, THEN the half rotation at theta 1e6, softmax at
    ``head_dim ** -0.5`` with the heads riding on ``head_lanes``, through
    the engine's own layer function against ``Lfm2Attention``."""
    torch, m, cfg = _lfm2()
    lp = tiny_params["params"]["layer_1"]
    at = {**lp["attn"],
          "q_norm": {"scale": jnp.linspace(0.5, 1.5, TINY.head_dim)},
          "k_norm": {"scale": jnp.linspace(1.4, 0.6, TINY.head_dim)}}
    mod = m.Lfm2Attention(cfg, 1).float()
    _load_attention(torch, mod, at)
    T = 19
    x = jax.random.normal(jax.random.PRNGKey(6), (2, T, TINY.dim))
    cos_sin, mask = _rope_and_mask(torch, m, cfg, T)
    # the mixer alone: the layer function on a stream whose norm is the
    # identity, minus the residual (a block of one part: "mixer")
    kind = runner.LayerKind(part="mixer")
    one = {"attn": at, "norm": {"scale": jnp.ones((TINY.dim,))}}
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                               + TINY.rms_eps)
    pos = jnp.broadcast_to(jnp.arange(T), (2, T))
    got, _ = runner._layer(one, kind, x, pos, _plain_attend, TINY)
    with torch.no_grad():
        want = mod(_t(torch, normed), cos_sin, mask)[0].numpy()
    np.testing.assert_allclose(np.asarray(got - x), want, rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("li", [0, 1], ids=["conv", "attention"])
def test_a_whole_dense_block_is_transformers_lfm2_decoder_layer(
        tiny_params, li):
    """``h = x + Mixer(norm(x)); x' = h + W2(silu(W1 m) * W3 m)`` through
    the engine's layer function against ``Lfm2DecoderLayer`` (the dense
    sibling's block at the width as given), for both kinds of mixer."""
    torch, m, cfg = _lfm2()
    p = tiny_params["params"]
    mlp = p["layer_0"]["mlp"]                      # the tree's dense MLP
    lp = {"attn": p[f"layer_{li}"]["attn"], "mlp": mlp,
          "attn_norm": {"scale": jnp.linspace(0.7, 1.3, TINY.dim)},
          "mlp_norm": {"scale": jnp.linspace(1.2, 0.8, TINY.dim)}}
    layer = m.Lfm2DecoderLayer(cfg, li).float()
    with torch.no_grad():
        if li:
            _load_attention(torch, layer.self_attn, lp["attn"])
        else:
            _load_conv(torch, layer.conv, lp["attn"])
        for ours, theirs in (("gate", "w1"), ("up", "w3"), ("down", "w2")):
            getattr(layer.feed_forward, theirs).weight.copy_(
                _t(torch, mlp[ours]["kernel"]).T)
        layer.operator_norm.weight.copy_(
            _t(torch, lp["attn_norm"]["scale"]))
        layer.ffn_norm.weight.copy_(_t(torch, lp["mlp_norm"]["scale"]))
    T = 13
    x = jax.random.normal(jax.random.PRNGKey(8), (2, T, TINY.dim))
    cos_sin, mask = _rope_and_mask(torch, m, cfg, T)
    with torch.no_grad():
        want = layer(_t(torch, x), cos_sin, attention_mask=mask).numpy()
    kind = runner.LayerKind(state=not li)

    def attend_state(h, at, _v, _window):
        return shortconv.mix(at, h, None)[0]

    pos = jnp.broadcast_to(jnp.arange(T), (2, T))
    got, stats = runner._layer(lp, kind, x, pos,
                               _plain_attend if li else attend_state, TINY)
    assert stats is None
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-4, atol=3e-5)


# -- the engine against the plain reference, on logits ----------------------

@pytest.mark.parametrize("n_prompt,env", [
    (20, {}),                       # one prefill bucket, decode on the tails
    (75, {}),    # chunks of 32 at starts 32 and 64 read the slot's tail
    (40, {"SHAI_PAGED_DECODE": "1"}),     # the paged kernel, interpreted
], ids=["one-bucket", "carried-chunks", "kernels"])
def test_engine_agrees_with_the_plain_reference_on_logits(
        tiny_params, n_prompt, env, monkeypatch):
    for k_, v_ in env.items():
        monkeypatch.setenv(k_, v_)
    prompt = _prompt(n_prompt)
    [fin] = _engine(tiny_params).generate([prompt], GREEDY)
    got = _against_reference(fin, prompt, tiny_params)
    assert got["finite"] and got["max_abs_logprob_diff"] < 0.6, got
    assert got["mean"] < 0.1, got


def test_one_program_and_continuation_chunks_give_one_answer(tiny_params):
    """75 tokens through ONE prefill program (a bucket of 128) and through
    three (32, 32, 11: the tail carried from program to program)."""
    prompt = _prompt(75)
    [one] = _engine(tiny_params, context_encoding_buckets=(16, 32, 128)
                    ).generate([prompt], GREEDY)
    eng = _engine(tiny_params)
    [three] = eng.generate([prompt], GREEDY)
    assert eng.obs.snapshot()["conv"]["chunk_carries"] == 2
    # (two chunkings sum in two orders under a bfloat16 stream: the first
    # token's distribution, which the prompt alone decides, is nearly the
    # same, where a lost tail reads tenths: the variants' test below; later
    # tokens may part at a near-tie of two logits)
    a, b = three.logprobs[0], one.logprobs[0]
    assert three.token_ids[0] == one.token_ids[0]
    np.testing.assert_allclose(a["top_logprobs"], b["top_logprobs"],
                               atol=0.1)        # (two near-equal may swap)
    got = _against_reference(three, prompt, tiny_params)
    assert got["mean"] < 0.1, got


def test_a_batched_prefill_serves_rows_of_unequal_length(tiny_params):
    """Three prompts of 7, 19 and 30 tokens admitted into ONE prefill
    program: each row's tail is taken at its OWN length, and each answers
    as it does alone and as the reference says."""
    prompts = [_prompt(n) for n in (7, 19, 30)]
    eng = _engine(tiny_params, max_prefill_batch=4, max_num_seqs=4,
                  context_encoding_buckets=(32,))
    together = eng.generate(prompts, GREEDY)
    snap = eng.obs.snapshot()
    assert snap["dispatches_by_phase"]["prefill"] == 1
    assert snap["conv"]["prefill_tokens"] == (7 + 19 + 30) * 7
    for p, f in zip(prompts, together):
        [alone] = _engine(tiny_params, context_encoding_buckets=(32,)
                          ).generate([p], GREEDY)
        assert f.token_ids == alone.token_ids
        got = _against_reference(f, p, tiny_params)
        assert got["max_abs_logprob_diff"] < 0.6 and got["mean"] < 0.1, got


@pytest.fixture(scope="module")
def right_and_wrong(tiny_params):
    """Differences of the served path against the reference, right and under
    every variant, over three prompts (two through carried chunks)."""
    variants = ("",) + REF.REFUSED_VARIANTS + REF.REFUSED_BY_MEAN + (
        REF.NOT_REFUSED_RELIABLY)
    mean = dict.fromkeys(variants, 0.0)
    worst = dict.fromkeys(variants, 0.0)
    eng = _engine(tiny_params)
    for n in (40, 75, 100):
        prompt = _prompt(n)
        [fin] = eng.generate([prompt], GREEDY)
        for variant in variants:
            got = _against_reference(fin, prompt, tiny_params, variant)
            mean[variant] += got["mean"] / 3
            worst[variant] = max(worst[variant], got["max_abs_logprob_diff"])
    return mean, worst


@pytest.mark.parametrize("variant", REF.REFUSED_VARIANTS
                         + REF.REFUSED_BY_MEAN + REF.NOT_REFUSED_RELIABLY)
def test_broken_mathematics_is_refused(right_and_wrong, variant):
    """Every broken variant reads far from the right path at the tiny size:
    the check is not blind to the slot's tail, the taps' order, the first
    gate, the selection bias, the weights' precision, nor (here, where the
    published widths' seeded weights are: the tolerance file says why) to
    the head norms."""
    mean, worst = right_and_wrong
    assert mean[variant] > 2.0 * mean[""], (variant, mean)
    if variant != "weights_fp8":
        # (on the stand-in's FLOAT32 weights the precision control reads
        # four times the right path and around the bound that the chip's
        # bfloat16 readings set: the tolerance file has both sizes)
        assert mean[variant] > TOL["mean_abs_logprob_diff"], (variant, mean)
    if variant in REF.REFUSED_VARIANTS:
        assert worst[variant] > TOL["max_abs_logprob_diff"], (variant, worst)
    assert mean[""] < TOL["mean_abs_logprob_diff"] / 2
    assert worst[""] < TOL["max_abs_logprob_diff"] / 2


def test_the_tail_variant_shows_only_where_a_chunk_continues(tiny_params):
    short, long_ = _prompt(20), _prompt(75)
    eng = _engine(tiny_params)
    f_short, f_long = eng.generate([short, long_], GREEDY)
    right = _against_reference(f_short, short, tiny_params)
    # no boundary of 32 crossed: the variant is the right path
    same = _against_reference(f_short, short, tiny_params, "no_conv_tail")
    assert same["mean"] == pytest.approx(right["mean"], rel=1e-4)
    assert _against_reference(f_long, long_, tiny_params, "no_conv_tail")[
        "mean"] > 1.4 * _against_reference(f_long, long_, tiny_params)["mean"]


def test_the_variant_lists_are_disjoint_and_name_the_precision():
    names = (REF.REFUSED_VARIANTS + REF.REFUSED_BY_MEAN
             + REF.NOT_REFUSED_RELIABLY + REF.ACCEPTED_VARIANTS)
    assert sorted(names) == sorted(set(names)) == sorted([
        "no_conv_tail", "taps_reversed", "no_gate_b", "no_qk_norm",
        "no_expert_bias", "weights_fp8"])
    assert REF.REFUSED_VARIANTS == ("taps_reversed", "no_gate_b")
    assert "weights_fp8" in REF.REFUSED_BY_MEAN
    assert REF.NOT_REFUSED_RELIABLY == ("no_qk_norm",)
    for name in names:
        assert name in TOL["reason"], name


# -- slots ------------------------------------------------------------------

def test_a_reused_slot_answers_as_a_fresh_engine(tiny_params):
    """Three requests after three others, through the same three slots,
    with nothing cleared between: prefill from position 0 starts from a
    zero tail whatever the slot held."""
    first = [_prompt(n, seed=1) for n in (40, 22, 70)]
    then = [_prompt(n, seed=2) for n in (25, 66, 18)]
    eng = _engine(tiny_params)
    eng.generate(first, GREEDY)
    assert all(np.asarray(eng.cache.kv[0]["t"][slot], np.float32).any()
               for slot in range(3))       # the slots hold the old tails
    again = eng.generate(then, GREEDY)
    fresh = _engine(tiny_params).generate(then, GREEDY)
    for a, b in zip(again, fresh):
        assert a.token_ids == b.token_ids
        assert [e["logprob"] for e in a.logprobs] == [
            e["logprob"] for e in b.logprobs]
    assert eng.cache.slots_live == 0 and eng.cache.leaked_bytes == 0


def test_padded_rows_step_the_null_slot(tiny_params):
    """Three rows decode in a bucket of 4 (one padded row): the padded row
    steps the NULL slot; a slot nobody was ever admitted to stays zeros."""
    eng = _engine(tiny_params, max_num_seqs=5)
    prompts = [_prompt(n) for n in (20, 24, 28)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    fins = eng.generate(prompts, sp)
    for pi in TINY.state_layers:
        lay = eng.cache.kv[pi]
        assert sorted(lay) == ["t"]               # ONE leaf, no float32
        assert lay["t"].shape == (6, 2, 64)
        assert not np.asarray(lay["t"][3:5], np.float32).any()
        assert np.asarray(lay["t"][5], np.float32).any()   # the null slot
    assert sorted(eng.cache.kv[1]) == ["k", "v"]  # an attention layer's
    assert eng.cache.kv[1]["k"].shape[-2:] == (2, 32)      # on 32 lanes
    solo = [_engine(tiny_params).generate([p], sp)[0].token_ids
            for p in prompts]
    assert [f.token_ids for f in fins] == solo


def test_the_pools_pad_lanes_stay_zero(tiny_params):
    """Heads of 16 ride on 32 lanes: what prefill, a continuation chunk and
    decode write to lanes 16 on is zeros, and lanes 0-15 hold the keys."""
    eng = _engine(tiny_params)
    eng.generate([_prompt(75)], GREEDY)
    for pi in (1, 5):
        for leaf in eng.cache.kv[pi].values():
            a = np.asarray(leaf, np.float32)
            assert not a[..., TINY.head_dim:].any()
            assert a[..., :TINY.head_dim].any()


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


def test_a_soft_prefix_is_refused(tiny_params):
    eng = _engine(tiny_params)
    with pytest.raises(ValueError, match="soft prefix .* recurrent state"):
        eng.add_request(_prompt(9), prefix=np.zeros((4, TINY.dim)))


@pytest.mark.parametrize("kw,names", [
    ({"quant": True}, "int8"), ({"mesh": object()}, "tensor_parallel_size")])
def test_mixer_weights_are_not_born_int8_or_sharded(kw, names):
    plain = dataclasses.replace(TINY, n_experts=0, n_experts_per_tok=0,
                                moe_mlp_dim=0, n_dense_layers=0)
    with pytest.raises(ValueError, match=names + ".*recurrent state"):
        geometry_params(plain, **kw)


# -- the seeded leaves ------------------------------------------------------

def test_the_leaves_and_their_draws(tiny_params):
    p = tiny_params["params"]
    assert "lm_head" not in p                       # tied to the embedding
    for li, t in enumerate(KINDS):
        lay = p[f"layer_{li}"]
        assert sorted(lay) == sorted(
            ["attn", "attn_norm", "mlp_norm", "moe" if li else "mlp"]), li
        assert sorted(lay["attn"]) == (
            ["conv", "in", "o"] if t == "conv"
            else ["k", "k_norm", "o", "q", "q_norm", "v"]), li
    at = p["layer_0"]["attn"]
    assert at["in"]["kernel"].shape == (64, 192)
    assert at["conv"].shape == (3, 64) and at["o"]["kernel"].shape == (64, 64)
    mo = p["layer_1"]["moe"]
    assert sorted(mo) == ["bias", "experts", "router"]    # no shared expert
    assert sorted(mo["experts"]) == ["down", "gate", "up"]
    assert mo["experts"]["gate"].shape == (16, 64, 16)
    assert mo["router"]["kernel"].shape == (64, 16)
    assert np.asarray(mo["bias"]).any()             # seeded, so it selects
    lo, hi = conv_tap_range(3)
    assert (lo, hi) == (-3 ** -0.5, 3 ** -0.5)
    taps = np.asarray(geometry_params(dataclasses.replace(
        TINY, dim=2048, n_layers=1, layer_types=("conv",), n_experts=0,
        n_dense_layers=0, vocab_size=8), dtype=jnp.float32, seed=2)[
        "params"]["layer_0"]["attn"]["conv"])
    assert lo <= taps.min() < -0.5 and 0.5 < taps.max() <= hi
    stage = jax.eval_shape(
        lambda: geometry_params(LlamaConfig.lfm2_24b_stage()))["params"]
    assert stage["layer_1"]["moe"]["experts"]["up"].shape == (64, 2048, 1536)
    assert stage["layer_0"]["mlp"]["gate"]["kernel"].shape == (2048, 11776)
    assert stage["layer_0"]["attn"]["conv"].shape == (3, 2048)
    assert stage["layer_1"]["attn"]["k"]["kernel"].shape == (2048, 512)
    count = lambda t: sum(int(np.prod(a.shape))               # noqa: E731
                          for a in jax.tree.leaves(t))
    assert count(stage["layer_0"]["attn"]) == 16_783_360     # the issue's
    assert count(stage["layer_1"]["attn"]) == 10_485_888
    assert count(stage["layer_1"]["moe"]["experts"]) == 603_979_776
    table = SPEC.config(NAME)["memory"]
    whole = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in jax.tree.leaves(stage))
    assert whole == table["weights_bytes"] == 10_358_000_128


# -- counters, gauges, the ledger, the budget -------------------------------

def test_conv_counters_and_the_arena_in_the_ledger(tiny_params):
    eng = _engine(tiny_params)
    prompts = [_prompt(n) for n in (20, 75)]
    fins = eng.generate(prompts, SamplingParams(temperature=0.0,
                                                max_new_tokens=8))
    eng.finish_pending()
    snap = eng.obs.snapshot()
    n_conv = len(TINY.state_layers)
    assert n_conv == 7 and "kda" not in snap and "ssm" not in snap
    assert snap["conv"]["prefill_tokens"] == (20 + 75) * n_conv
    assert snap["conv"]["chunk_carries"] == 2           # 75 = 32 + 32 + 11
    steps = snap["dispatches_by_phase"]["decode"]
    rows = sum(len(f.token_ids) for f in fins)
    assert (rows - 2) * n_conv <= snap["conv"]["rows_stepped"] <= (
        2 * steps * n_conv)
    assert snap["conv"]["slots_live"] == 0
    assert max(s.get("state_slots_live", 0)
               for s in eng.obs.recent_steps(256)) == 2
    assert snap["moe"]["layer_steps"] == 8 * steps      # EIGHT routed layers
    hbm = eng.obs.hbm.snapshot()
    # ONE leaf a layer, in the pool's type: 4 slots x 7 layers x 2 x 64
    assert hbm["recurrent_state_bytes"] == eng.cache.state_bytes == (
        4 * 7 * 2 * 64 * 2)
    # the pool holds the heads on their 32 lanes, two layers of nine
    assert hbm["kv_pool_bytes"] == eng.cache.pool_bytes == (
        eng.cache.total_blocks * 8 * 2 * 2 * 2 * 32 * 2)
    assert len(eng.cache.kv) == 9
    from scalable_hw_agnostic_inference_tpu.serve.metrics import (
        EngineTelemetryCollector,
    )

    fams = {f.name: f for f in EngineTelemetryCollector(
        lambda: eng.obs, "t").collect()}
    got = {s.labels["counter"]: s.value
           for s in fams["shai_engine_conv"].samples}
    assert got == {k_: float(v_) for k_, v_ in snap["conv"].items()}
    assert "shai_engine_kda" not in fams and "shai_engine_ssm" not in fams


@pytest.mark.parametrize("preset", ["tiny", "tiny_afmoe", "tiny_kda",
                                    "tiny_ssm"])
def test_another_configuration_shows_no_conv_entry(preset):
    cfg = getattr(LlamaConfig, preset)()
    params = geometry_params(cfg, dtype=jnp.float32, seed=1)
    eng = _engine(params, cfg)
    eng.generate([_prompt(20)], SamplingParams(temperature=0.0,
                                               max_new_tokens=3))
    assert "conv" not in eng.obs.snapshot()


def test_the_budget_prices_the_arena_and_the_two_padded_layers():
    from scalable_hw_agnostic_inference_tpu.core.budget import (
        GIB,
        causal_lm_budget,
    )

    cfg = SPEC.config(NAME)
    eng = {k_: v_ for k_, v_ in cfg["engine"].items()
           if k_ not in ("quantization", "context_encoding_buckets")}
    b = causal_lm_budget(
        LlamaConfig.lfm2_24b_stage(),
        EngineConfig(**eng, context_encoding_buckets=tuple(
            cfg["engine"]["context_encoding_buckets"])))
    mem = cfg["memory"]
    assert b.params_gib * GIB == pytest.approx(mem["weights_bytes"],
                                               rel=2e-3)
    assert b.kv_gib * GIB == pytest.approx(
        mem["kv_pool_bytes"] + mem["state_arena_bytes"])
    assert mem["kv_pool_bytes"] == (
        cfg["engine"]["num_blocks"] * 16 * 2 * 2 * 8 * 128 * 2)  # TWO layers
    assert mem["state_arena_bytes"] == 129 * 7 * 8192
    assert b.fits


# -- 64-wide heads through both attention kernels ----------------------------

D64_CASES = [c for c in kernel_check.engine_cases(
    8, 2, 64, buckets=(32,), max_model_len=64, max_num_seqs=4)
    if "int8" not in c.name]


@pytest.mark.parametrize("case", D64_CASES, ids=lambda c: c.name)
def test_attention_kernels_agree_with_their_oracles_at_heads_of_64(case):
    """Flash and the paged kernel at ``D = 64`` in interpret mode. (For the
    v5e: flash lowers at 64 and the paged kernel does NOT, Mosaic refuses
    its 64-lane slice of a 128-lane tile, which is why the engine serves
    such heads on ``head_lanes``: ``tests/test_kernel_lowering.py``.)"""
    assert case.max_abs_err(interpret=True) <= case.tol


def test_the_cases_cover_flash_and_the_paged_kernel():
    names = [c.name for c in D64_CASES]
    assert any(n.startswith("flash") for n in names)
    assert any(n.startswith("paged") for n in names)


# -- the other architectures' programs are what they were -------------------

def _step_program_text(cfg, program):
    params = jax.eval_shape(lambda: geometry_params(cfg))
    B, bs, M = 2, 8, 4
    sds = jax.ShapeDtypeStruct
    leaf = {n: sds((9, 8) + per, jnp.float32)
            for n, per in cache_leaves(cfg).items()}
    arena = {n: sds((B + 1,) + tuple(shp), jnp.dtype(dt or jnp.float32))
             for n, (shp, dt) in state_leaves(cfg).items()}
    kv = [dict(arena if pi in cfg.state_layers else leaf)
          for pi in range(len(cfg.state_layers) + cfg.n_paged_layers)]
    slots = (sds((1,), jnp.int32),) if cfg.recurrent else ()
    pre = (params, kv, sds((1, 16), jnp.int32), sds((1,), jnp.int32),
           sds((1, M), jnp.int32)) + slots
    if program == "decode":
        return str(jax.make_jaxpr(runner.make_decode(
            cfg, bs, M, B, paged=True, feedback=True))(
            params, kv, sds((B,), jnp.int32), sds((B,), jnp.int32),
            sds((B, M), jnp.int32), sds((B,), jnp.float32),
            sds((2,), jnp.uint32), sds((), jnp.int32),
            sds((B,), jnp.float32), sds((B,), jnp.int32),
            sds((B,), jnp.float32),
            *((sds((B,), jnp.int32),) if cfg.recurrent else ())))
    if program == "prefill":
        return str(jax.make_jaxpr(runner.make_prefill(cfg, bs, M, 16))(*pre))
    return str(jax.make_jaxpr(runner.make_prefill_cont(
        cfg, bs, M, 16, start_blocks=2))(*pre))


@pytest.mark.parametrize("preset", ["tiny", "tiny_afmoe", "tiny_mla",
                                    "tiny_kda", "tiny_ssm"])
@pytest.mark.parametrize("program", ["decode", "prefill", "cont"])
def test_with_no_conv_layer_the_step_programs_are_what_they_were(
        preset, program):
    """The five stand-ins' step programs with this PR's taps field SET but
    no conv layer and no ``head_lanes`` trace to the very jaxpr of the plain
    config's: nothing of the new paths is traced. (Against the parent
    commit itself the programs were compared text for text: PERF.md,
    PR 50.)"""
    plain = getattr(LlamaConfig, preset)()
    named = dataclasses.replace(plain, conv_taps=5)
    assert named.state_kind == plain.state_kind and not plain.head_lanes
    a, b = (_step_program_text(c, program) for c in (plain, named))
    assert a == b
    assert "pad" not in a or preset == "tiny_mla" or "pad" in b


def test_the_new_programs_trace_the_kernels_on_padded_heads():
    """The stage's decode program at 128 rows: the streamed expert kernel
    in its eight routed layers, the paged kernel over 128-lane rows in its
    two attention layers, and no ``ragged_dot``."""
    cfg = dataclasses.replace(LlamaConfig.lfm2_24b_stage(), vocab_size=512)
    params = jax.eval_shape(lambda: geometry_params(cfg))
    B, bs, M = 128, 16, 4
    sds = jax.ShapeDtypeStruct
    leaf = {n: sds((9, bs) + per, jnp.bfloat16)
            for n, per in cache_leaves(cfg).items()}
    arena = {n: sds((B + 1,) + tuple(shp), jnp.dtype(dt or jnp.bfloat16))
             for n, (shp, dt) in state_leaves(cfg).items()}
    kv = [dict(arena if pi in cfg.state_layers else leaf) for pi in range(9)]
    text = str(jax.make_jaxpr(runner.make_decode(
        cfg, bs, M, B, paged=True, feedback=True))(
        params, kv, sds((B,), jnp.int32), sds((B,), jnp.int32),
        sds((B, M), jnp.int32), sds((B,), jnp.float32),
        sds((2,), jnp.uint32), sds((), jnp.int32), sds((B,), jnp.float32),
        sds((B,), jnp.int32), sds((B,), jnp.float32), sds((B,), jnp.int32)))
    for kernel in ("moe_grouped_ffn_streamed", "paged_decode_attention"):
        assert kernel in text, kernel
    assert "bf16[9,16,8,128]" in text              # the pool on 128 lanes
    assert "ragged_dot" not in text and "moe_grouped_ffn_tiled" not in text
