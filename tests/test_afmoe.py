"""The routed, windowed layer kinds (Trinity-Mini / AFMoE) on the engine's
normal path, at the tiny size on the CPU: the engine against the plain
reference on logits (prefill, decode through the paged cache, a prompt
longer than the window, a continuation chunk that crosses the window's
edge), the kernels against their oracles under a window, the expert
layer's shares against the uncut layer, Mistral's greedy tokens through the
one layer function against the parent's, the sampler's skipped sorts
against the sorts, the combinations the boot refuses, and the counters."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import check
from benchmark.spec import Spec
from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
from scalable_hw_agnostic_inference_tpu.engine.engine import (
    LLMEngine,
    SamplingParams,
)
from scalable_hw_agnostic_inference_tpu.engine import runner
from scalable_hw_agnostic_inference_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    geometry_params,
)
from scalable_hw_agnostic_inference_tpu.ops import kernel_check, sampling
from scalable_hw_agnostic_inference_tpu.ops.moe import expert_layer, gated_mlp
from scalable_hw_agnostic_inference_tpu.ops.pallas.paged_attention import (
    first_live_tile,
    live_tile_tokens,
)

SPEC = Spec()
TINY = LlamaConfig.tiny_afmoe()
TINY_MODEL = SPEC.dry_run_model("tiny-afmoe")


@pytest.fixture(scope="module")
def tiny_params():
    return geometry_params(TINY, dtype=jnp.float32, seed=3)


def _engine(params, cfg=TINY, **over):
    kw = dict(max_model_len=128, max_num_seqs=3, block_size=8,
              context_encoding_buckets=(16, 32), max_new_tokens=16)
    kw.update(over)
    return LLMEngine(cfg, params, EngineConfig(**kw))


def _prompt(n, seed=7):
    rng = np.random.default_rng(seed + n)
    return [1] + [int(t) for t in rng.integers(3, 500, n - 1)]


# -- the engine against the plain reference, on logits ----------------------

TINY_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "mlp_dim", "moe_intermediate_size": "moe_mlp_dim",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps", "tie_word_embeddings": "tie_embeddings",
    "sliding_window": "sliding_window", "num_dense_layers": "n_dense_layers",
    "num_experts": "n_experts", "num_experts_per_tok": "n_experts_per_tok",
    "num_shared_experts": "n_shared_experts", "route_norm": "route_norm",
    "route_scale": "route_scale", "mup_enabled": "embed_scale"}


@pytest.mark.parametrize("key", sorted(TINY_FIELDS) + ["layer_types"])
def test_the_tiny_stand_in_is_the_programs_preset(key):
    """``benchmark/configs/dry_run/tiny-afmoe.json`` against
    ``LlamaConfig.tiny_afmoe()`` field by field: the reference reads the
    file, the dry run serves the preset."""
    assert set(TINY_MODEL) - {"what", "score_func", "layer_types"} == set(
        TINY_FIELDS)
    if key == "layer_types":
        assert tuple(TINY_MODEL[key]) == TINY.layer_types
    else:
        assert TINY_MODEL[key] == getattr(TINY, TINY_FIELDS[key])


def test_the_stage_is_the_published_model_cut_in_depth_alone():
    full, stage = LlamaConfig.trinity_mini(), LlamaConfig.trinity_mini_stage()
    pub = SPEC.config("trinity-mini-bf16")
    assert full.n_layers == 32 and full.n_dense_layers == 2
    assert full.layer_types == ("sliding_attention",) * 3 + (
        "full_attention",) + full.layer_types[4:]
    assert stage.layer_types == tuple(pub["layer_types"])
    assert (stage.n_layers, stage.n_dense_layers) == (
        pub["num_hidden_layers"], pub["num_dense_layers"]) == (5, 1)
    for key, attr in TINY_FIELDS.items():
        if attr in ("n_layers", "n_dense_layers"):
            continue
        assert getattr(stage, attr) == getattr(full, attr) == pub[key], key
    assert stage.head_dim * stage.n_heads == 2 * stage.dim
    assert stage.window_layers == (0, 1, 2, 3) and stage.n_moe_layers == 4


@pytest.mark.parametrize("n_prompt,env", [
    (20, {}),                                 # below the window (32)
    (75, {}),      # past it: chunks of 32 at starts 32 and 64 cross its edge
    (40, {"SHAI_PAGED_DECODE": "1"}),         # the Pallas paged kernel
], ids=["below-window", "chunks-cross-the-edge", "paged-kernel"])
def test_engine_agrees_with_the_plain_reference_on_logits(
        tiny_params, n_prompt, env, monkeypatch):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    prompt = _prompt(n_prompt)
    [fin] = _engine(tiny_params).generate(
        [prompt], SamplingParams(temperature=0.0, max_new_tokens=8,
                                 logprobs=5))
    gen = fin.token_ids
    assert len(gen) == 8 and len(fin.logprobs) == 8
    seq = prompt + gen[:-1]
    rows = [len(prompt) - 1 + k for k in range(len(gen))]
    ref = SPEC.reference("afmoe")
    got = check.compare(fin.logprobs, ref.logprobs(
        tiny_params["params"], TINY_MODEL, seq, rows, 96))
    tol = SPEC.tolerance("tolerance.afmoe.json")
    assert got["finite"]
    assert got["max_abs_logprob_diff"] < tol["max_abs_logprob_diff"], got
    mean = got["sum_abs_logprob_diff"] / got["compared"]
    assert mean < 1.5 * tol["mean_abs_logprob_diff"], got
    # and the window is not decoration: without it the reference disagrees
    if n_prompt > TINY.sliding_window:
        wrong = check.compare(fin.logprobs, ref.logprobs(
            tiny_params["params"], TINY_MODEL, seq, rows, 96, "no_window"))
        assert wrong["sum_abs_logprob_diff"] / wrong["compared"] > 4 * mean


def test_batched_rows_decode_as_they_do_alone(tiny_params):
    """Padded and inactive rows route to no expert and touch no one's
    answer: three prompts together give each its solo tokens."""
    prompts = [_prompt(n) for n in (5, 44, 21)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    solo = [_engine(tiny_params).generate([p], sp)[0].token_ids
            for p in prompts]
    together = _engine(tiny_params).generate(prompts, sp)
    assert [f.token_ids for f in together] == solo


# -- Mistral through the one layer function ---------------------------------

#: greedy tokens of ``LlamaConfig.tiny()`` (flax init, PRNGKey(0)) taken on
#: the parent commit (784ea34: five spelled-out stacks) for these prompts:
#: batched prefill, a 46-token prompt through a continuation chunk, decode
PARENT_TOKENS = [
    [155] * 12,
    [12, 496, 508, 58, 148, 148, 232, 60, 60, 60, 193, 503],
    [312, 161, 161, 161, 362, 101, 162, 162, 162, 162, 36, 161],
    [155] * 12]


@pytest.mark.parametrize("env", [{}, {"SHAI_ASYNC_DECODE": "0"}],
                         ids=["async", "lock-step"])
def test_mistral_tiny_greedy_tokens_are_the_parents(env, monkeypatch):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    cfg = LlamaConfig.tiny()
    params = LlamaForCausalLM(cfg, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    rng = np.random.default_rng(7)
    prompts = [[1] + [int(t) for t in rng.integers(3, 500, n)]
               for n in (5, 20, 45, 3)]
    eng = LLMEngine(cfg, params, EngineConfig(
        max_model_len=96, max_num_seqs=3, block_size=8,
        context_encoding_buckets=(16, 32), max_new_tokens=16))
    fins = eng.generate(prompts, SamplingParams(temperature=0.0,
                                                max_new_tokens=12))
    assert [f.token_ids for f in fins] == PARENT_TOKENS
    snap = eng.obs.snapshot()
    assert "moe" not in snap and "window" not in snap


def test_a_config_with_no_window_layer_traces_no_window(monkeypatch):
    """The window argument adds nothing where no layer has a window: the
    decode program of an all-full config, with a window size set that no
    layer uses, lowers to the very text of the plain config's."""
    monkeypatch.setenv("SHAI_PAGED_DECODE", "1")
    plain = LlamaConfig.tiny()
    named = dataclasses.replace(
        plain, layer_types=("full_attention",) * plain.n_layers,
        sliding_window=8)
    assert not named.window_layers and named.engine_only
    params = jax.eval_shape(lambda: geometry_params(plain))
    B, bs, M = 2, 8, 4
    sds = jax.ShapeDtypeStruct
    kv = [{"k": sds((9, bs, 2, 16), jnp.float32),
           "v": sds((9, bs, 2, 16), jnp.float32)} for _ in range(2)]
    args = (params, kv, sds((B,), jnp.int32), sds((B,), jnp.int32),
            sds((B, M), jnp.int32), sds((B,), jnp.float32),
            sds((2,), jnp.uint32), sds((), jnp.int32),
            sds((B,), jnp.float32), sds((B,), jnp.int32),
            sds((B,), jnp.float32))
    texts = [runner.make_decode(c, bs, M, B).lower(*args).as_text()
             for c in (plain, named)]
    assert texts[0] == texts[1]


# -- the kernels against their oracles, under a window ----------------------

def _window_cases():
    return [c for c in kernel_check.engine_cases(
        4, 2, 64, block_size=8, buckets=(16, 32), max_model_len=512,
        max_num_seqs=4, max_prefill_batch=2, window=140)
        if "-w140" in c.name]


@pytest.mark.parametrize("case", _window_cases(), ids=lambda c: c.name)
def test_window_kernel_agrees_with_its_oracle(case):
    """At, just below and far above the window, at the tile's edges, over a
    pool poisoned with NaN in every block no row's window holds."""
    assert case.max_abs_err(interpret=True) <= case.tol


def test_window_cases_cover_the_kernels():
    names = [c.name for c in _window_cases()]
    assert sum(n.startswith("flash") for n in names) == 2
    assert sum(n.startswith("paged") for n in names) == 4
    assert sum(n.endswith("-oneseq") for n in names) == 2
    assert any("-edges" in n for n in names)


@pytest.mark.parametrize("n,tile,window,first,walked", [
    (300, 256, 0, 0, 512), (300, 256, 2048, 0, 512),
    (2048, 256, 2048, 0, 2048), (2049, 256, 2048, 0, 2304),
    (2304, 256, 2048, 1, 2048), (2305, 256, 2048, 1, 2304),
    (4096, 256, 2048, 8, 2048), (5000, 256, 2048, 11, 2304),
    (0, 256, 2048, 0, 256)])
def test_tiles_a_window_layer_walks(n, tile, window, first, walked):
    assert first_live_tile(n, tile, window) == first
    assert live_tile_tokens(n, tile, window) == walked


# -- the expert layer -------------------------------------------------------

def test_eight_shares_of_the_experts_sum_to_the_uncut_layer():
    """The guide's share test: 128 experts held in 8 shares of 16. Every
    holder routes over all 128 and computes its own experts' part; the
    parts plus the shared expert ONCE are the uncut layer."""
    cfg = dataclasses.replace(
        TINY, n_experts=128, n_experts_per_tok=8, dim=32, moe_mlp_dim=16)
    E, D, F = 128, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    mp = {"router": {"kernel": jax.random.normal(ks[0], (D, E)) * 0.3},
          "bias": jax.random.normal(ks[1], (E,)) * 0.05,
          "experts": {"gate": jax.random.normal(ks[2], (E, D, F)) * 0.2,
                      "up": jax.random.normal(ks[3], (E, D, F)) * 0.2,
                      "down": jax.random.normal(ks[4], (E, F, D)) * 0.2},
          "shared": {n: {"kernel": jax.random.normal(k, s) * 0.2}
                     for n, k, s in (("gate", ks[5], (D, F)),
                                     ("up", ks[6], (D, F)),
                                     ("down", ks[7], (F, D)))}}
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 7, D))
    active = jnp.arange(21).reshape(3, 7) % 5 != 0
    whole, stats = expert_layer(mp, x, cfg, active=active)
    routed = dataclasses.replace(cfg, n_shared_experts=0)
    parts = gated_mlp(mp["shared"], x)      # the shared expert, once
    for share in range(8):
        lo = share * 16
        held = {**mp, "experts": {n: w[lo:lo + 16]
                                  for n, w in mp["experts"].items()}}
        part, st = expert_layer(held, x, routed, active=active,
                                held=(lo, 16))
        assert (st == stats).all()          # every holder routes alike
        parts = parts + part
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=2e-5, atol=2e-6)
    n_active = int(active.sum())
    assert 8 <= int(stats[0]) <= min(128, 8 * n_active)
    assert int(stats[1]) <= n_active


def test_a_padded_row_routes_to_no_expert():
    cfg = dataclasses.replace(TINY, n_shared_experts=0)
    params = geometry_params(TINY, dtype=jnp.float32, seed=1)
    mp = params["params"]["layer_2"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 1, cfg.dim))
    none, stats = expert_layer(mp, x, cfg, active=jnp.zeros((4, 1), bool))
    assert not np.asarray(none).any() and (np.asarray(stats) == 0).all()
    one, stats = expert_layer(
        mp, x, cfg, active=jnp.asarray([[True], [False], [False], [False]]))
    assert np.asarray(one)[0].any() and not np.asarray(one)[1:].any()
    assert list(np.asarray(stats)) == [cfg.n_experts_per_tok, 1]


# -- the sampler ------------------------------------------------------------

def _sorted_sampler(logits, rng, temperature, top_k, top_p):
    """``sample_logits`` as it was before the sorts could be skipped."""
    t, k, p = sampling._broadcast_knobs(logits, temperature, top_k, top_p)
    scaled = logits.astype(jnp.float32) / jnp.maximum(t, 1e-6)[..., None]
    masked = sampling._mask_top_p(sampling._mask_top_k(scaled, k), p)
    drawn = jax.random.categorical(rng, masked, axis=-1).astype(jnp.int32)
    return jnp.where(t <= 0.0, sampling.greedy(logits), drawn)


@pytest.mark.parametrize("temps", [(0.0, 0.0, 0.0, 0.0), (0.8, 0.0, 1.3, 0.7)],
                         ids=["all-greedy", "mixed-temperature"])
@pytest.mark.parametrize("top_k", [(0, 0, 0, 0), (0, 5, 0, 40)],
                         ids=["no-top-k", "some-top-k"])
@pytest.mark.parametrize("top_p", [(1.0,) * 4, (1.0, 0.9, 0.5, 1.0)],
                         ids=["no-top-p", "some-top-p"])
def test_skipping_the_sorts_changes_no_token(temps, top_k, top_p):
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(11), (4, 777))
    knobs = (jnp.asarray(temps, jnp.float32), jnp.asarray(top_k, jnp.int32),
             jnp.asarray(top_p, jnp.float32))
    for seed in range(6):
        rng = jax.random.PRNGKey(seed)
        got = jax.jit(sampling.sample_logits)(logits, rng, *knobs)
        want = jax.jit(_sorted_sampler)(logits, rng, *knobs)
        assert (np.asarray(got) == np.asarray(want)).all()
    probs = sampling.sampling_probs(logits, *knobs)
    assert np.allclose(np.asarray(probs).sum(-1), 1.0, atol=1e-5)


# -- what the boot refuses, by name -----------------------------------------

@pytest.mark.parametrize("env,over,names", [
    ({"SHAI_KV_QUANT": "int8"}, {}, "SHAI_KV_QUANT=int8 with window layers"),
    ({"SHAI_KVTIER": "1"}, {"enable_prefix_caching": True},
     "SHAI_KVTIER"),
    ({}, {"quantization": "int8"}, "quantization: int8 with expert layers"),
    ({}, {"tensor_parallel_size": 2},
     "tensor_parallel_size > 1 with expert layers"),
], ids=["int8-kv", "kvtier", "int8-weights", "tp"])
def test_unsupported_combinations_are_refused_by_name(
        tiny_params, env, over, names, monkeypatch):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
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


@pytest.mark.parametrize("kw,names", [
    ({"quant": True}, "int8"), ({"mesh": object()}, "tensor_parallel_size")])
def test_expert_weights_are_not_born_int8_or_sharded(kw, names):
    with pytest.raises(ValueError, match=names):
        geometry_params(TINY, **kw)


def test_the_flax_module_refuses_what_only_the_engine_runs():
    model = LlamaForCausalLM(TINY, dtype=jnp.float32)
    with pytest.raises(ValueError, match="paged engine"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


# -- counters, budget -------------------------------------------------------

def test_routing_and_window_counters(tiny_params):
    eng = _engine(tiny_params)
    prompts = [_prompt(n) for n in (50, 9)]
    eng.generate(prompts, SamplingParams(temperature=0.0, max_new_tokens=10))
    snap = eng.obs.snapshot()
    moe, win = snap["moe"], snap["window"]
    n_moe, k, E = TINY.n_moe_layers, TINY.n_experts_per_tok, TINY.n_experts
    assert moe["layer_steps"] % n_moe == 0 and moe["layer_steps"] > 0
    steps = moe["layer_steps"] // n_moe
    # two rows a step at most, k assignments a row and expert layer
    assert k * n_moe * steps <= moe["assignments"] <= 2 * k * n_moe * steps
    assert k * moe["layer_steps"] <= moe["experts_touched"] <= min(
        E * moe["layer_steps"], moe["assignments"])
    assert moe["layer_steps"] <= moe["load_max"] <= moe["assignments"]
    # the 50-token row is past the window (32) in four window layers
    assert win["tokens_visible"] <= win["tokens_walked"]
    assert win["pool_dead_token_steps"] > 0
    assert win["pool_tokens_dead"] >= 0
    assert win["pool_dead_token_steps"] < win["pool_token_steps"]
    from scalable_hw_agnostic_inference_tpu.serve.metrics import (
        EngineTelemetryCollector,
    )

    fams = {f.name: f for f in EngineTelemetryCollector(
        lambda: eng.obs, "t").collect()}
    got = {s.labels["counter"]: s.value
           for s in fams["shai_engine_moe"].samples}
    assert got["experts_touched"] == moe["experts_touched"]
    assert "shai_engine_window" in fams


def test_the_form_of_the_expert_product_is_counted(monkeypatch):
    """A decode dispatch's expert layers are counted as streamed where
    ``expert_form`` of the program's rows says so: all of them at widths
    the kernel can tile (here 128 x 128, interpreted), none at the
    stand-in's own (64 x 16). The counter reaches ``/stats``'s snapshot
    and ``shai_engine_moe_total``; the tokens are the grouped form's."""
    from scalable_hw_agnostic_inference_tpu.ops import moe
    from scalable_hw_agnostic_inference_tpu.serve.metrics import (
        EngineTelemetryCollector,
    )

    wide = dataclasses.replace(TINY, dim=128, moe_mlp_dim=128, n_experts=8,
                               n_experts_per_tok=2)
    params = geometry_params(wide, dtype=jnp.float32, seed=3)
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    prompts = [_prompt(n) for n in (12, 9)]
    assert moe.expert_form(2, wide) == "streamed"
    eng = _engine(params, wide)
    fins = eng.generate(prompts, sp)
    got = eng.obs.snapshot()["moe"]
    assert got["streamed_layer_steps"] == got["layer_steps"] > 0
    fams = {f.name: f for f in EngineTelemetryCollector(
        lambda: eng.obs, "t").collect()}
    exported = {s.labels["counter"]: s.value
                for s in fams["shai_engine_moe"].samples}
    assert exported["streamed_layer_steps"] == got["layer_steps"]
    monkeypatch.setattr(moe, "expert_form", lambda n, cfg: "grouped")
    want = _engine(params, wide).generate(prompts, sp)
    assert [f.token_ids for f in fins] == [f.token_ids for f in want]
    monkeypatch.undo()
    assert moe.expert_form(2, TINY) == "grouped"
    narrow = _engine(geometry_params(TINY, dtype=jnp.float32, seed=3))
    narrow.generate(prompts[:1], sp)
    got = narrow.obs.snapshot()["moe"]
    assert got["streamed_layer_steps"] == 0 < got["layer_steps"]


@pytest.mark.parametrize("width,want_form", [(128, "tiled"),
                                             (None, "grouped")])
def test_the_tiled_form_is_counted_a_prefill_family_dispatch(
        width, want_form, monkeypatch):
    """``moe.tiled_layer_calls`` counts every expert layer of each prefill
    or continuation dispatch whose rows (the bucket: over 128 here) and
    widths give the tiled form (128 x 128, the kernel interpreted), and
    nothing at the stand-in's own widths (64 x 16), which keep
    ``ragged_dot``; beside ``dispatches_by_phase`` it reads the expert
    layers a program, and it reaches ``shai_engine_moe_total``. The tokens
    are the grouped form's."""
    from scalable_hw_agnostic_inference_tpu.ops import moe
    from scalable_hw_agnostic_inference_tpu.serve.metrics import (
        EngineTelemetryCollector,
    )

    cfg = TINY if width is None else dataclasses.replace(
        TINY, dim=width, moe_mlp_dim=width, n_experts=8,
        n_experts_per_tok=2)
    params = geometry_params(cfg, dtype=jnp.float32, seed=3)
    over = dict(max_model_len=512, context_encoding_buckets=(256,),
                max_new_tokens=4)
    sp = SamplingParams(temperature=0.0, max_new_tokens=3)
    prompts = [_prompt(140), _prompt(300)]    # one program; two (chunked)
    assert moe.expert_form(256, cfg) == want_form
    eng = _engine(params, cfg, **over)
    fins = eng.generate(prompts, sp)
    snap = eng.obs.snapshot()
    programs = sum(snap["dispatches_by_phase"].get(p, 0)
                   for p in ("prefill", "chunk"))
    assert programs >= 2
    n_moe = cfg.n_moe_layers
    if want_form == "tiled":
        assert snap["moe"]["tiled_layer_calls"] == n_moe * programs > 0
        fams = {f.name: f for f in EngineTelemetryCollector(
            lambda: eng.obs, "t").collect()}
        exported = {s.labels["counter"]: s.value
                    for s in fams["shai_engine_moe"].samples}
        assert exported["tiled_layer_calls"] == n_moe * programs
        monkeypatch.setattr(
            moe, "expert_form",
            lambda n, c: "grouped" if n > moe.STREAMED_MAX_ROWS
            else "streamed")
        want = _engine(params, cfg, **over).generate(prompts, sp)
        assert [f.token_ids for f in fins] == [f.token_ids for f in want]
    else:
        assert "tiled_layer_calls" not in snap["moe"]
    # the metric file's reader over this very snapshot
    import json

    from benchmark.readers import counter_ratio

    with open(os.path.join(SPEC.root, "benchmark", "layer_metrics",
                           "moe_tiled_layers_per_program.kda.json")) as f:
        reader = json.load(f)["reader"]
    got = counter_ratio.read(
        {"before": {"engine": {}}, "after": {"engine": snap}}, reader)
    assert got == (n_moe if want_form == "tiled" else 0)


def test_a_saturated_routed_engine_streams_and_counts_each_step_once(
        tiny_params, monkeypatch):
    """Five requests on two slots: the steady path retires a routed step's
    ``fetch`` array (routing counts behind the tokens) one step late, so
    every decode dispatch's counts must land exactly once — none lost to a
    flush, none read twice — and the tokens equal the lock-step oracle's."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=14)
    prompts = [_prompt(n) for n in (9, 12, 20, 7, 15)]
    eng = _engine(tiny_params, max_num_seqs=2)
    assert eng._async
    fins = eng.generate(prompts, sp)
    eng.finish_pending()                # the trailing lookahead, if any
    snap = eng.obs.snapshot()
    decodes = snap["dispatches_by_phase"]["decode"]
    assert snap["moe"]["layer_steps"] == TINY.n_moe_layers * decodes
    # it streamed: callers queued behind full slots flushed nothing
    assert snap["flush_by_reason"].get("admission", 0) <= len(prompts)
    assert snap["pipeline_flushes"] < 0.5 * snap["steps"], snap
    monkeypatch.setenv("SHAI_ASYNC_DECODE", "0")
    oracle = _engine(tiny_params, max_num_seqs=2)
    assert not oracle._async
    want = oracle.generate(prompts, sp)
    assert [f.token_ids for f in fins] == [f.token_ids for f in want]
    assert (oracle.obs.snapshot()["moe"]["layer_steps"]
            == snap["moe"]["layer_steps"])


def test_the_budget_knows_experts_the_gate_and_the_head_size():
    from scalable_hw_agnostic_inference_tpu.core.budget import (
        GIB,
        causal_lm_budget,
    )

    cfg = SPEC.config("trinity-mini-bf16")
    b = causal_lm_budget(
        LlamaConfig.trinity_mini_stage(),
        EngineConfig(**{k: v for k, v in cfg["engine"].items()
                        if k not in ("quantization",)
                        and k != "context_encoding_buckets"},
                     context_encoding_buckets=tuple(
                         cfg["engine"]["context_encoding_buckets"])))
    # the configuration file's own arithmetic, plus the float32 routers
    assert b.params_gib * GIB == pytest.approx(
        cfg["memory"]["weights_bytes"], rel=2e-3)
    assert b.kv_gib * GIB == pytest.approx(cfg["memory"]["kv_pool_bytes"])
    assert b.fits


def test_fp8_expert_products_move_the_mean_not_the_largest(tiny_params):
    """The reference's precision variant (the last of ``REFUSED_BY_MEAN``:
    the experts' products on float8 operands) against the served path: over a
    few prompts its mean difference is well above the right path's, which
    is what ``tolerance.afmoe.json`` refuses it by at published width (the
    file has the chip's readings); the largest difference is set by
    discrete routing and says little."""
    ref = SPEC.reference("afmoe")
    assert "experts_fp8" in ref.REFUSED_BY_MEAN
    assert not set(ref.REFUSED_BY_MEAN) & set(ref.REFUSED_VARIANTS)
    total = {"": 0.0, "experts_fp8": 0.0}
    eng = _engine(tiny_params)
    for n in (20, 40, 75, 33):
        prompt = _prompt(n, seed=11)
        [fin] = eng.generate([prompt], SamplingParams(
            temperature=0.0, max_new_tokens=8, logprobs=5))
        seq = prompt + fin.token_ids[:-1]
        rows = [len(prompt) - 1 + k for k in range(8)]
        for variant in total:
            got = check.compare(fin.logprobs, ref.logprobs(
                tiny_params["params"], TINY_MODEL, seq, rows, 96, variant))
            total[variant] += got["sum_abs_logprob_diff"] / got["compared"]
    assert total["experts_fp8"] > 1.5 * total[""], total


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The dry run's system under test for the routed configuration: the
    tiny stand-in behind the real server, as ``tests/benchmark`` boots it."""
    from benchmark.server import SystemUnderTest

    sut = SystemUnderTest(SPEC.config("trinity-mini-bf16"), SPEC.harness,
                          1234, str(tmp_path_factory.mktemp("sut")),
                          dry_run=True)
    sut.devices()
    sut.start()
    yield sut
    sut.stop()


def test_every_stand_in_gets_the_one_tiny_engine_shape(served):
    """No engine shape by a model's name: the routed stand-in is served
    with the rows its configuration states and ``tiny``'s own buckets."""
    from scalable_hw_agnostic_inference_tpu.serve.units.causal_lm import (
        _geometry_models,
        _stand_in_models,
    )

    assert set(_stand_in_models()) == {"tiny", "tiny-afmoe", "tiny-mla",
                                      "tiny-kda", "tiny-ssm", "tiny-lfm2"}
    assert not set(_stand_in_models()) & set(_geometry_models())
    ecfg = served.service.ecfg
    assert ecfg.max_num_seqs == served.config["engine"]["max_num_seqs"]
    assert tuple(ecfg.context_encoding_buckets) == (32, 64, 128)
    assert ecfg.max_prefill_batch == EngineConfig().max_prefill_batch
    assert (ecfg.model, ecfg.max_model_len, ecfg.block_size) == (
        "tiny-afmoe", 256, 16)


@pytest.mark.parametrize("variant", ["rope_on_full", "no_renorm"])
def test_the_mean_bound_refuses_what_the_largest_cannot(served, variant):
    """``REFUSED_BY_MEAN`` through the benchmark's own check at the tiny
    size: refused, and by the mean bound."""
    from benchmark import run as bench_run

    tol = SPEC.tolerance("tolerance.afmoe.json")
    got = bench_run.reference_check(SPEC, served, served.config, 99, True,
                                    variant)
    assert not got["passed"], got
    assert got["mean_abs_logprob_diff"] > tol["mean_abs_logprob_diff"]
