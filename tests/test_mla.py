"""Latent attention (Kanana-2-30B-A3B / ``deepseek_v3``) on the engine's
normal path, at the tiny size on the CPU: the engine (expanded prefill, a
continuation chunk over a latent prefix, absorbed decode through the paged
latent pool) against the plain expanded reference on logits; the absorbed
kernel in interpret mode against plain ``jnp``; the two forms of the one
function; interleaved rotary pairs; the cache with a latent leaf
(accounting, preemption and resume, copy-on-write); what the boot refuses,
by name; the counters; and the other architectures' programs untouched."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import check
from benchmark.spec import Spec
from scalable_hw_agnostic_inference_tpu.engine import EngineConfig, runner
from scalable_hw_agnostic_inference_tpu.engine.cache import PagedKVCache
from scalable_hw_agnostic_inference_tpu.engine.engine import (
    LLMEngine,
    SamplingParams,
)
from scalable_hw_agnostic_inference_tpu.models.llama import (
    LATENT_KVA_GAIN,
    LATENT_Q_GAIN,
    LlamaConfig,
    LlamaForCausalLM,
    cache_leaves,
    geometry_params,
)
from scalable_hw_agnostic_inference_tpu.ops import kernel_check, mla
from scalable_hw_agnostic_inference_tpu.ops.pallas.mla_paged_attention import (
    mla_tile_tokens,
    mla_wait_tokens,
)
from scalable_hw_agnostic_inference_tpu.ops.rope import (
    apply_rope,
    apply_rope_interleaved,
)

SPEC = Spec()
TINY = LlamaConfig.tiny_mla()
TINY_MODEL = SPEC.dry_run_model("tiny-mla")
REF = SPEC.reference("deepseek_v3")
TOL = SPEC.tolerance("tolerance.deepseek_v3.json")


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


def _against_reference(fin, prompt, params, variant="", model=TINY_MODEL):
    gen = fin.token_ids
    seq = prompt + gen[:-1]
    rows = [len(prompt) - 1 + k for k in range(len(gen))]
    got = check.compare(fin.logprobs, REF.logprobs(
        params["params"], model, seq, rows, 112, variant))
    got["mean"] = got["sum_abs_logprob_diff"] / got["compared"]
    return got


# -- the presets ------------------------------------------------------------

TINY_FIELDS = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "qk_head_dim": "head_dim",
    "v_head_dim": "v_head_dim", "rope_interleave": "rope_interleave",
    "rope_scaling": "rope_scaling", "intermediate_size": "mlp_dim",
    "moe_intermediate_size": "moe_mlp_dim",
    "max_position_embeddings": "max_seq_len", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps", "tie_word_embeddings": "tie_embeddings",
    "first_k_dense_replace": "n_dense_layers",
    "n_routed_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_tok",
    "n_shared_experts": "n_shared_experts", "norm_topk_prob": "route_norm",
    "routed_scaling_factor": "route_scale"}


@pytest.mark.parametrize("key", sorted(TINY_FIELDS))
def test_the_stage_is_the_published_model_cut_in_depth_alone(key):
    """``LlamaConfig.kanana2_stage()`` against the configuration file (the
    published config's keys): every one but the depth, which the file
    lists under ``reduced``."""
    full, stage = LlamaConfig.kanana2_30b(), LlamaConfig.kanana2_stage()
    pub = SPEC.config("kanana-2-30b-a3b-bf16")
    attr = TINY_FIELDS[key]
    if key == "num_hidden_layers":
        assert (full.n_layers, stage.n_layers, pub[key]) == (48, 7, 7)
        assert pub["published"][key] == 48 and pub["reduced"] == [key]
        assert stage.n_moe_layers == 6 and full.n_moe_layers == 47
        return
    assert getattr(stage, attr) == getattr(full, attr) == pub[key], key


def test_a_latent_config_states_its_own_widths():
    assert TINY.latent and not LlamaConfig.tiny().latent
    assert TINY.latent_width == 128          # 32 + 8, to a lane multiple
    assert LlamaConfig.kanana2_stage().latent_width == 640     # 512 + 64
    assert cache_leaves(TINY) == {"c": (128,)}
    assert cache_leaves(LlamaConfig.tiny()) == {"k": (2, 16), "v": (2, 16)}
    with pytest.raises(ValueError, match="latent attention: head_dim"):
        dataclasses.replace(TINY, head_dim=16)
    assert TINY.engine_only


# -- the engine against the plain expanded reference, on logits -------------

@pytest.mark.parametrize("n_prompt,env", [
    (20, {}),                        # one prefill bucket, absorbed decode
    (75, {}),     # chunks of 32 at starts 32 and 64 read a LATENT prefix
    (40, {"SHAI_PAGED_DECODE": "1"}),         # the Pallas latent kernel
    (75, {"SHAI_PAGED_DECODE": "1"}),
    (30, {"SHAI_ASYNC_DECODE": "0"}),         # the lock-step loop
], ids=["one-bucket", "latent-prefix-chunks", "kernel", "kernel-chunks",
        "lock-step"])
def test_engine_agrees_with_the_plain_reference_on_logits(
        tiny_params, n_prompt, env, monkeypatch):
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    prompt = _prompt(n_prompt)
    [fin] = _engine(tiny_params).generate(
        [prompt], SamplingParams(temperature=0.0, max_new_tokens=10,
                                 logprobs=5))
    assert len(fin.token_ids) == 10 and len(fin.logprobs) == 10
    got = _against_reference(fin, prompt, tiny_params)
    assert got["finite"]
    assert got["max_abs_logprob_diff"] < TOL["max_abs_logprob_diff"], got
    assert got["mean"] < 1.5 * TOL["mean_abs_logprob_diff"], got
    # and the mechanisms are not decoration: the rotary pairs split in
    # halves, or the latent's norm dropped, and the reference disagrees
    for variant in ("rope_half_split", "no_kv_norm"):
        wrong = _against_reference(fin, prompt, tiny_params, variant)
        assert wrong["mean"] > 4 * got["mean"], (variant, wrong, got)


@pytest.mark.parametrize("n_prompt", [20, 75], ids=["one-bucket", "chunks"])
def test_rotary_pairs_in_halves_are_configuration_too(tiny_params, n_prompt):
    """``rope_interleave`` false (pairs ``(i, i + rope/2)``, the layout of
    the DeepSeek checkpoints that do not set the key) is the other branch
    of ``runner._latent_qk``: held to the reference told the same."""
    halves = dataclasses.replace(TINY, rope_interleave=False)
    model = {**TINY_MODEL, "rope_interleave": False}
    prompt = _prompt(n_prompt)
    [fin] = _engine(tiny_params, halves).generate(
        [prompt], SamplingParams(temperature=0.0, max_new_tokens=10,
                                 logprobs=5))
    got = _against_reference(fin, prompt, tiny_params, model=model)
    assert got["finite"]
    assert got["max_abs_logprob_diff"] < TOL["max_abs_logprob_diff"], got
    assert got["mean"] < 1.5 * TOL["mean_abs_logprob_diff"], got
    wrong = _against_reference(fin, prompt, tiny_params)   # interleaved
    assert wrong["mean"] > 4 * got["mean"], (wrong, got)


def test_batched_rows_decode_as_they_do_alone(tiny_params):
    prompts = [_prompt(n) for n in (5, 44, 21)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    solo = [_engine(tiny_params).generate([p], sp)[0].token_ids
            for p in prompts]
    together = _engine(tiny_params).generate(prompts, sp)
    assert [f.token_ids for f in together] == solo


# -- one function, two forms ------------------------------------------------

def test_absorbed_attention_is_expanded_attention():
    """``ops.mla``: scores and values through the up-projected heads equal
    the absorbed form over the same cache rows, in float32."""
    cfg, B, S = TINY, 2, 19
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    kv_b = {"kernel": 0.3 * jax.random.normal(
        k[0], (cfg.kv_lora_rank, cfg.n_heads
               * (cfg.qk_nope_head_dim + cfg.v_head_dim)))}
    rows = mla.latent_rows(
        jax.random.normal(k[1], (B, S, cfg.kv_lora_rank)),
        jax.random.normal(k[2], (B, S, cfg.qk_rope_head_dim)),
        cfg.latent_width)
    assert rows.shape == (B, S, 128) and not np.asarray(rows[..., 40:]).any()
    q = jax.random.normal(k[3], (B, 1, cfg.n_heads, cfg.head_dim))
    scale = mla.softmax_scale(cfg)
    assert scale == pytest.approx(24 ** -0.5)
    keys, values = mla.expand(rows, kv_b, cfg)
    assert keys.shape == (B, S, 4, 24) and values.shape == (B, S, 4, 16)
    s = jnp.einsum("bthd,bshd->bhts", q, keys) * scale
    want = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), values)
    # the same rows as a paged pool of blocks of 4, tables in order
    bs, M = 4, 5
    pool = jnp.zeros((1 + B * M, bs, cfg.latent_width)).at[1:].set(
        jnp.pad(rows, ((0, 0), (0, M * bs - S), (0, 0))).reshape(
            B * M, bs, -1))
    tables = 1 + jnp.arange(B * M, dtype=jnp.int32).reshape(B, M)
    u = mla.latent_gather_attention(
        mla.absorb_q(q, kv_b, cfg), pool, tables,
        jnp.full((B, 1), S - 1, jnp.int32), rank=cfg.kv_lora_rank,
        scale=scale)
    got = mla.unabsorb(u, kv_b, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("dim", [8, 64])
def test_interleaved_rope_is_the_references(dim):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 3, dim))
    pos = jnp.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 100, 4000]], jnp.int32)
    got = apply_rope_interleaved(x, pos, 10000.0)
    for b in range(2):
        want = REF.rope(x[b], pos[b], 10000.0, True)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want),
                                   rtol=1e-5, atol=3e-4)
    # a permutation of the half-rotation: pair (2i, 2i+1) turns as the
    # half-rotation's pair (i, i + dim/2) does
    perm = np.concatenate([np.arange(0, dim, 2), np.arange(1, dim, 2)])
    half = apply_rope(x[..., perm], pos, 10000.0)
    np.testing.assert_allclose(np.asarray(got[..., perm]), np.asarray(half),
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(got), np.asarray(
        apply_rope(x, pos, 10000.0)))


# -- the kernel against plain jnp -------------------------------------------

def _kernel_cases():
    return kernel_check.latent_cases(
        4, 128, 64, 128, 32, block_size=8, buckets=(16, 32),
        max_model_len=2048, max_num_seqs=5) + kernel_check.latent_cases(
        2, 64, 64, 256, 160, block_size=128, buckets=(128,),
        max_model_len=1024, max_num_seqs=3)[-4:]


@pytest.mark.parametrize("case", _kernel_cases(), ids=lambda c: c.name)
def test_latent_kernels_agree_with_their_oracles(case):
    """Ragged rows with an empty one (length 0, a table of zeros), both
    sides of a tile's edge over a pool that is NaN wherever no row holds
    it, one sequence's queries a row each, and what the order of a tile's
    copies and waits can break (an empty row before a full one, a last
    tile of one block, every wait group's edge, rows of whole tiles);
    flash with values narrower than keys, a prefill bucket and a
    continuation chunk."""
    assert case.max_abs_err(interpret=True) <= case.tol


def test_the_cases_cover_the_latent_kernels():
    names = [c.name for c in _kernel_cases()]
    assert sum(n.startswith("flash-latent") for n in names) == 3
    assert sum(n.startswith("mla-") for n in names) == 8
    assert any(n.endswith("-edges-oneseq") for n in names)
    assert sum(n.endswith("-waits") for n in names) == 2
    assert mla_tile_tokens(16) == 1024 and mla_tile_tokens(8) == 1024
    assert mla_tile_tokens(4096) == 4096        # a block is its own tile
    # a tile is waited for in four groups; a block of a group's size or
    # more is its own group
    assert mla_wait_tokens(16) == 256 and mla_wait_tokens(8) == 256
    assert mla_wait_tokens(128) == 256 and mla_wait_tokens(512) == 512
    assert mla_wait_tokens(4096) == 4096


def test_the_wait_case_holds_the_lengths_the_issue_order_can_break():
    """An empty row FOLLOWED by a full one, a last tile of exactly one
    block, both sides of every wait group's edge, and whole tiles."""
    case = kernel_check.latent_cases(
        4, 128, 64, 128, 32, block_size=8, buckets=(16,),
        max_model_len=4096, max_num_seqs=5)[-1]
    assert case.name.endswith("-waits")
    _q, _c, tables, n = jax.jit(case.make_inputs)(jax.random.PRNGKey(0))
    lens = np.asarray(n).tolist()
    assert tables.shape == (len(lens), 512)
    assert not np.asarray(tables)[0].any()      # the empty row's table
    t, w, L = mla_tile_tokens(8), mla_wait_tokens(8), 4096
    assert lens[:2] == [0, L]
    assert t + 1 in lens and t + 8 in lens
    for edge in range(w, t, w):
        assert {edge - 1, edge, edge + 1} <= set(lens)
    assert {t + w + 1, 2 * t, 3 * t} <= set(lens)


# -- the cache with a latent leaf -------------------------------------------

def _latent_cache(total_blocks=9, **kw):
    return PagedKVCache(2, cache_leaves(TINY), total_blocks, 8, 4,
                        dtype=jnp.bfloat16, **kw)


def test_the_pool_is_what_the_attention_kind_says():
    cache = _latent_cache()
    assert [sorted(lay) for lay in cache.kv] == [["c"], ["c"]]
    assert cache.kv[0]["c"].shape == (9, 8, 128)
    assert cache.pool_bytes == 2 * 9 * 8 * 128 * 2
    plain = PagedKVCache(2, {"k": (2, 16), "v": (2, 16)}, 9, 8, 4)
    assert sorted(plain.kv[0]) == ["k", "v"]
    assert plain.kv[0]["k"].shape == (9, 8, 2, 16)


def test_block_accounting_does_not_look_inside_a_block():
    cache = _latent_cache()
    a = cache.admit(1, 20)                     # 3 blocks of 8
    assert len(a.blocks) == 3 and cache.allocator.n_free == 5
    cache.extend(1, 5)                         # 25 tokens: a fourth block
    assert len(cache.seq(1).blocks) == 4
    cache.admit(2, 9)
    assert cache.allocator.n_free == 2
    with pytest.raises(MemoryError):
        cache.admit(3, 30)
    cache.release(1)                           # preempted: its blocks return
    assert cache.allocator.n_free == 6
    b = cache.admit(1, 25)                     # resumed under its id
    assert len(b.blocks) == 4 and b.version != a.version


def test_copy_on_write_copies_the_latent_leaf():
    cache = _latent_cache()
    parent = cache.admit(1, 12)                # a partial tail block
    tail = parent.blocks[-1]
    for lay in cache.kv:
        lay["c"] = lay["c"].at[tail].set(3.0)
    child = cache.fork_sequence(1, 2)
    assert child.blocks == parent.blocks
    cache.extend(2, 1)                         # the first divergent write
    new_tail = cache.seq(2).blocks[-1]
    assert new_tail != tail and cache.cow_copies == 1
    for lay in cache.kv:
        assert np.asarray(lay["c"][new_tail], np.float32).min() == 3.0


def test_a_preempted_request_resumes_on_its_own_tokens(tiny_params):
    """Four requests over a pool that two outgrow: the engine preempts and
    re-admits (a latent row is recomputed by prefill like any other), and
    every request ends with its solo tokens."""
    prompts = [_prompt(n) for n in (30, 28, 26, 24)]
    sp = SamplingParams(temperature=0.0, max_new_tokens=12)
    solo = [_engine(tiny_params).generate([p], sp)[0].token_ids
            for p in prompts]
    eng = _engine(tiny_params, num_blocks=11, max_model_len=64)
    fins = eng.generate(prompts, sp)
    assert [f.token_ids for f in fins] == solo
    assert eng.obs.snapshot()["preemptions"] > 0
    assert eng.cache.allocator.n_free == 10


@pytest.mark.parametrize("kw,names", [
    ({"quant": True}, "SHAI_KV_QUANT=int8"), ({"tier": object()}, "SHAI_KVTIER")])
def test_the_cache_refuses_what_reads_k_and_v(kw, names):
    with pytest.raises(ValueError, match=names + r".*\['c'\].*latent cache"):
        _latent_cache(**kw)


# -- what the boot refuses, by name -----------------------------------------

@pytest.mark.parametrize("env,over,names", [
    ({}, {"tensor_parallel_size": 2},
     "tensor_parallel_size > 1 .* with a latent cache"),
    ({}, {"quantization": "int8"}, "quantization: int8 .* with a latent"),
    ({"SHAI_KV_QUANT": "int8"}, {}, "SHAI_KV_QUANT=int8 .* with a latent"),
    ({}, {"enable_prefix_caching": True},
     "enable_prefix_caching .* with a latent cache"),
    ({"SHAI_KVTIER": "1"}, {}, "SHAI_KVTIER .*kvnet frames.* with a latent"),
    ({}, {"speculative_model": "[ngram]", "num_speculative_tokens": 2},
     "speculative decoding .* with a latent cache"),
], ids=["tp", "int8-weights", "int8-kv", "prefix-caching", "kvtier",
        "speculation"])
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
def test_latent_weights_are_not_born_int8_or_sharded(kw, names):
    plain = dataclasses.replace(TINY, n_experts=0, n_dense_layers=0)
    with pytest.raises(ValueError, match=names + ".*latent"):
        geometry_params(plain, **kw)


def test_the_flax_module_refuses_a_latent_config():
    model = LlamaForCausalLM(TINY, dtype=jnp.float32)
    with pytest.raises(ValueError, match="latent attention.*paged engine"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


# -- the seeded leaves ------------------------------------------------------

def test_the_latent_leaves_and_their_gains(tiny_params):
    at = tiny_params["params"]["layer_1"]["attn"]
    assert sorted(at) == ["kv_a", "kv_b", "kv_norm", "o", "q"]
    assert at["q"]["kernel"].shape == (64, 4 * 24)
    assert at["kv_a"]["kernel"].shape == (64, 32 + 8)
    assert at["kv_b"]["kernel"].shape == (32, 4 * (16 + 16))
    assert at["o"]["kernel"].shape == (4 * 16, 64)
    assert at["kv_norm"]["scale"].shape == (32,)
    std = 64 ** -0.5
    for leaf, gain in (("q", LATENT_Q_GAIN), ("kv_a", LATENT_KVA_GAIN),
                       ("kv_b", 1.0), ("o", 1.0)):
        assert float(jnp.std(at[leaf]["kernel"])) == pytest.approx(
            std * gain, rel=0.08), leaf


def test_other_architectures_draw_what_they_drew():
    """The gains belong to the latent kind: a config without it gets every
    leaf at the tier's one deviation, the leaves it always had."""
    params = geometry_params(LlamaConfig.tiny_afmoe(), dtype=jnp.float32,
                             seed=3)["params"]
    at = params["layer_1"]["attn"]
    assert sorted(at) == ["gate", "k", "k_norm", "o", "q", "q_norm", "v"]
    for leaf in ("q", "k", "v", "o"):
        assert float(jnp.std(at[leaf]["kernel"])) == pytest.approx(
            64 ** -0.5, rel=0.08)


# -- counters, budget, the other architectures' programs --------------------

def test_latent_and_routing_counters(tiny_params):
    eng = _engine(tiny_params)
    prompts = [_prompt(n) for n in (20, 9)]
    fins = eng.generate(prompts, SamplingParams(temperature=0.0,
                                                max_new_tokens=8))
    eng.finish_pending()
    snap = eng.obs.snapshot()
    steps = snap["dispatches_by_phase"]["decode"]
    assert snap["mla"]["layer_steps"] == TINY.n_layers * steps
    assert snap["moe"]["layer_steps"] == TINY.n_moe_layers * steps
    # a row's step k reads its prompt and the k tokens decoded so far; the
    # async lookahead may run each row one step past its last token
    exact = sum(sum(len(p) + k for k in range(1, len(f.token_ids)))
                for p, f in zip(prompts, fins)) * TINY.n_layers
    over = sum(len(p) + len(f.token_ids)
               for p, f in zip(prompts, fins)) * TINY.n_layers
    assert exact <= snap["mla"]["tokens_visible"] <= exact + over
    assert "window" not in snap
    from scalable_hw_agnostic_inference_tpu.serve.metrics import (
        EngineTelemetryCollector,
    )

    fams = {f.name: f for f in EngineTelemetryCollector(
        lambda: eng.obs, "t").collect()}
    got = {s.labels["counter"]: s.value
           for s in fams["shai_engine_mla"].samples}
    assert got == {k: float(v) for k, v in snap["mla"].items()}


def test_a_model_without_a_latent_cache_counts_none(tiny_params):
    eng = _engine(geometry_params(LlamaConfig.tiny_afmoe(),
                                  dtype=jnp.float32, seed=1),
                  cfg=LlamaConfig.tiny_afmoe())
    eng.generate([_prompt(12)], SamplingParams(temperature=0.0,
                                               max_new_tokens=4))
    snap = eng.obs.snapshot()
    assert "mla" not in snap and "moe" in snap


def test_the_budget_prices_the_latent_pool_and_the_stage():
    from scalable_hw_agnostic_inference_tpu.core.budget import (
        GIB,
        causal_lm_budget,
    )

    cfg = SPEC.config("kanana-2-30b-a3b-bf16")
    eng = {k: v for k, v in cfg["engine"].items()
           if k not in ("quantization", "context_encoding_buckets")}
    b = causal_lm_budget(
        LlamaConfig.kanana2_stage(),
        EngineConfig(**eng, context_encoding_buckets=tuple(
            cfg["engine"]["context_encoding_buckets"])))
    # the configuration file's own arithmetic, plus the float32 routers
    assert b.params_gib * GIB == pytest.approx(
        cfg["memory"]["weights_bytes"], rel=2e-3)
    assert b.kv_gib * GIB == pytest.approx(cfg["memory"]["kv_pool_bytes"])
    assert cfg["memory"]["kv_pool_bytes_at_576_values"] * 640 == (
        cfg["memory"]["kv_pool_bytes"] * 576)
    assert b.fits


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


@pytest.mark.parametrize("preset", ["tiny", "tiny_afmoe"])
@pytest.mark.parametrize("program", ["decode", "prefill", "cont"])
def test_with_no_latent_kind_the_step_programs_are_what_they_were(
        preset, program):
    """The Mistral and Trinity stand-ins' step programs with the latent
    kind's fields SET but its rank 0 (no latent kind) trace to the very
    jaxpr of the plain config's: nothing of the latent path is traced
    unless the kind is there. (Against the parent commit itself the
    real-width programs were compared text for text: PERF.md, PR 32.)"""
    plain = getattr(LlamaConfig, preset)()
    named = dataclasses.replace(
        plain, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
        rope_interleave=True)
    assert not named.latent
    leaf = {n: jax.ShapeDtypeStruct((9, 8) + per, jnp.float32)
            for n, per in cache_leaves(plain).items()}
    a, b = (_step_program_text(c, leaf, program) for c in (plain, named))
    assert a == b
    assert "mla_" not in a


# -- what the tolerance refuses by its mean, and what it cannot -------------

def test_the_variant_lists_are_disjoint_and_say_what_is_not_refused():
    lists = (REF.REFUSED_VARIANTS, REF.REFUSED_BY_MEAN,
             REF.NOT_REFUSED_RELIABLY, REF.ACCEPTED_VARIANTS)
    names = [v for lst in lists for v in lst]
    assert len(names) == len(set(names)) == 7
    assert REF.NOT_REFUSED_RELIABLY == ("experts_fp8",)
    assert "latent_fp8" in REF.REFUSED_BY_MEAN   # the precision below bf16
    assert "does NOT refuse reliably" in TOL["reason"]
    assert "experts_fp8" in TOL["reason"]


@pytest.fixture(scope="module")
def right_and_wrong(tiny_params):
    """Mean differences of the served path against the reference, right
    and under every variant, summed over four prompts (two of them through
    continuation chunks)."""
    variants = ("",) + REF.REFUSED_BY_MEAN + REF.NOT_REFUSED_RELIABLY
    total = dict.fromkeys(variants, 0.0)
    eng = _engine(tiny_params)
    for n in (20, 40, 75, 33):
        prompt = _prompt(n, seed=11)
        [fin] = eng.generate([prompt], SamplingParams(
            temperature=0.0, max_new_tokens=8, logprobs=5))
        for variant in variants:
            total[variant] += _against_reference(
                fin, prompt, tiny_params, variant)["mean"] / 4
    return total


@pytest.mark.parametrize("variant", REF.REFUSED_BY_MEAN)
def test_a_small_shift_everywhere_moves_the_mean(right_and_wrong, variant):
    """``REFUSED_BY_MEAN`` at the tiny size: each reads well above the
    right path's mean (at published width the mean bound refuses each: the
    tolerance file has the chip's readings); the unrenormalised scores
    pass the bound itself here too."""
    assert right_and_wrong[variant] > 1.5 * right_and_wrong[""], (
        right_and_wrong)
    if variant == "no_renorm":
        assert right_and_wrong[variant] > TOL["mean_abs_logprob_diff"]
    assert right_and_wrong[""] < TOL["mean_abs_logprob_diff"]
