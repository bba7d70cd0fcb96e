"""Speculative decoding tests: drafter, acceptance math, KV rollback, and
the load-bearing one — greedy speculative decode must be token-for-token
identical to vanilla greedy decode (drafts may only ever change speed).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_hw_agnostic_inference_tpu.engine import (
    EngineConfig,
    PagedKVCache,
)
from scalable_hw_agnostic_inference_tpu.engine.engine import (
    LLMEngine,
    SamplingParams,
)
from scalable_hw_agnostic_inference_tpu.engine.speculative import (
    PromptLookupDrafter,
    accept_drafts,
)
from scalable_hw_agnostic_inference_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
)


# ---------------------------------------------------------------------------
# drafter (pure host-side)
# ---------------------------------------------------------------------------

def test_drafter_ngram_match_and_continuation():
    d = PromptLookupDrafter(4, lookup_max=4, lookup_min=1)
    # tail [3,4,1,2] recurs at position 2; continuation follows it
    assert d.draft([1, 2, 3, 4, 1, 2, 3, 4, 1, 2]) == [3, 4, 1, 2]


def test_drafter_prefers_most_recent_match():
    d = PromptLookupDrafter(3, lookup_max=2, lookup_min=1)
    # [1,2] occurs twice earlier; the later one (followed by 7) must win
    assert d.draft([1, 2, 9, 1, 2, 7, 1, 2])[0] == 7


def test_drafter_edge_cases():
    d = PromptLookupDrafter(4, lookup_max=4, lookup_min=1)
    assert d.draft([]) == []                 # empty history
    assert d.draft([5]) == []                # nothing earlier to match
    assert d.draft([1, 2, 3]) == []          # no repeat anywhere
    # lookup_min longer than the usable history: no n-gram to try
    strict = PromptLookupDrafter(4, lookup_max=4, lookup_min=3)
    assert strict.draft([1, 2]) == []
    assert strict.draft([1, 2, 1, 2]) == []  # only bigrams repeat; min is 3


def test_drafter_caps_proposal_at_k():
    d = PromptLookupDrafter(2, lookup_max=2, lookup_min=1)
    out = d.draft([1, 2, 3, 4, 5, 6, 1, 2])
    assert out == [3, 4]  # continuation truncated to k


def test_drafter_validates_knobs():
    with pytest.raises(ValueError):
        PromptLookupDrafter(0)
    with pytest.raises(ValueError):
        PromptLookupDrafter(4, lookup_max=2, lookup_min=3)


# ---------------------------------------------------------------------------
# acceptance walk (pure host-side)
# ---------------------------------------------------------------------------

def test_accept_drafts_greedy_prefix():
    o = np.array([5, 6, 8, 9])
    j, nxt = accept_drafts([5, 6, 7], o, o[:3], np.ones(3), 0.0, np.zeros(3))
    assert (j, nxt) == (2, 8)   # d[2]=7 != o[2]=8: commit o's correction


def test_accept_drafts_all_accepted_takes_bonus():
    o = np.array([5, 42])
    j, nxt = accept_drafts([5], o, o[:1], np.ones(1), 0.0, np.zeros(1))
    assert (j, nxt) == (1, 42)  # bonus sample from the position past the draft


def test_sample_excluding_stays_inside_vanilla_support():
    """The rejection resample removes the draft token AFTER top-k/top-p:
    with top_k=2 and the rank-1 token rejected, ONLY the rank-2 token may
    be emitted — never rank-3 (which vanilla sampling cannot produce)."""
    from scalable_hw_agnostic_inference_tpu.ops.sampling import (
        sample_excluding,
    )

    logits = jnp.asarray([[5.0, 4.0, 3.0, 2.0]])     # ranks: 0, 1, 2, 3
    exclude = jnp.asarray([0])                        # reject the rank-1 tok
    for seed in range(8):
        tok = int(sample_excluding(logits, jax.random.PRNGKey(seed),
                                   exclude, 1.0, 2, 1.0)[0])
        assert tok == 1, f"resample left vanilla's top-2 support: {tok}"
    # temperature 0: the argmax with the hole removed
    tok0 = int(sample_excluding(logits, jax.random.PRNGKey(0), exclude,
                                0.0, 0, 1.0)[0])
    assert tok0 == 1


def test_accept_drafts_rejection_sampling_uses_masked_resample():
    o = np.array([5, 6, 99])
    oex = np.array([11, 12])
    accept_p = np.array([1.0, 0.0])
    j, nxt = accept_drafts([5, 6], o, oex, accept_p, 1.0,
                           np.array([0.5, 0.5]))
    # first accepted (u < 1.0), second rejected (u >= 0.0): the corrected
    # sample excludes the rejected draft token
    assert (j, nxt) == (1, 12)


# ---------------------------------------------------------------------------
# config contract
# ---------------------------------------------------------------------------

def test_token_generation_buckets_is_an_ignored_key():
    """A manifest that still carries the window ladder boots: the key is
    recorded as ignored, whatever it holds, and names no field."""
    d = dict(max_model_len=256, block_size=16,
             context_encoding_buckets=[64, 128])
    for tg in ([64, 256], [64, 512], [60], [0]):
        cfg = EngineConfig.from_dict(dict(d, token_generation_buckets=tg))
        assert cfg.ignored_keys == ("token_generation_buckets",)
        assert not hasattr(cfg, "token_generation_buckets")
        assert cfg == EngineConfig.from_dict(d)
    with pytest.raises(TypeError):
        EngineConfig(token_generation_buckets=(64,))


def test_speculative_config_knobs():
    cfg = EngineConfig(speculative_model="[ngram]", num_speculative_tokens=4)
    assert cfg.speculative_enabled
    assert not EngineConfig().speculative_enabled
    # a named drafter with k=0 is vanilla decode (the vLLM contract)
    assert not EngineConfig(speculative_model="[ngram]").speculative_enabled
    with pytest.raises(ValueError):
        EngineConfig(speculative_model="eagle-1b")
    with pytest.raises(ValueError):
        EngineConfig(num_speculative_tokens=-1)
    with pytest.raises(ValueError):
        EngineConfig(speculative_model="[ngram]", num_speculative_tokens=2,
                     ngram_prompt_lookup_min=5, ngram_prompt_lookup_max=3)


# ---------------------------------------------------------------------------
# KV rollback
# ---------------------------------------------------------------------------

def test_cache_shrink_rolls_back_trailing_blocks():
    cache = PagedKVCache(1, {"k": (1, 4), "v": (1, 4)}, total_blocks=16,
                         block_size=4, blocks_per_seq=8, dtype=jnp.float32)
    free0 = cache.allocator.n_free
    cache.admit(0, 5)                      # 2 blocks
    cache.extend(0, 7)                     # 12 tokens -> 3 blocks
    assert cache.allocator.n_free == free0 - 3
    cache.shrink(0, 6)                     # back to 6 tokens -> 2 blocks
    assert cache.seq(0).n_tokens == 6
    assert len(cache.seq(0).blocks) == 2
    assert cache.allocator.n_free == free0 - 2
    cache.shrink(0, 0)                     # no-op
    assert cache.seq(0).n_tokens == 6
    cache.release(0)
    assert cache.allocator.n_free == free0


def test_cache_shrink_keeps_partially_used_block():
    cache = PagedKVCache(1, {"k": (1, 4), "v": (1, 4)}, total_blocks=16,
                         block_size=4, blocks_per_seq=8, dtype=jnp.float32)
    cache.admit(0, 4)                      # exactly 1 full block
    cache.extend(0, 4)                     # 8 tokens -> 2 blocks
    cache.shrink(0, 3)                     # 5 tokens still need 2 blocks
    assert cache.seq(0).n_tokens == 5
    assert len(cache.seq(0).blocks) == 2


# ---------------------------------------------------------------------------
# engine end-to-end (tiny model, CPU)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


def make_engine(tiny_model, spec=True, **over):
    cfg, _, params = tiny_model
    kw = dict(max_model_len=64, max_num_seqs=3, block_size=8,
              context_encoding_buckets=(16, 32), max_new_tokens=32)
    if spec:
        kw.update(speculative_model="[ngram]", num_speculative_tokens=4)
    kw.update(over)
    return LLMEngine(cfg, params, EngineConfig(**kw))


def _fuzz_prompts(seed, n):
    """Random prompts with embedded repetition (so drafting actually fires)
    plus pure-random tails (so acceptance also fails sometimes)."""
    rng = np.random.default_rng(seed)
    prompts = []
    for _ in range(n):
        base = rng.integers(3, 500, int(rng.integers(2, 6))).tolist()
        reps = int(rng.integers(2, 5))
        tail = rng.integers(3, 500, int(rng.integers(0, 4))).tolist()
        prompts.append((base * reps + tail)[:24])
    return prompts


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_spec_greedy_equivalence_fuzz(tiny_model):
    """THE speculative invariant: temperature-0 speculative output is
    bit-identical to vanilla greedy decode, prompt by prompt."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=16)
    for p in _fuzz_prompts(0, 8):
        [fv] = make_engine(tiny_model, spec=False).generate([p], sp)
        es = make_engine(tiny_model, spec=True)
        [fs] = es.generate([p], sp)
        assert fs.token_ids == fv.token_ids, f"prompt {p}"
        assert fs.stop_reason == fv.stop_reason
    assert es.spec.verify_steps > 0  # the last engine actually speculated


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_spec_greedy_equivalence_batched(tiny_model):
    """Continuous batching + speculation: staggered concurrent admissions
    must not change any sequence's greedy output."""
    prompts = _fuzz_prompts(7, 3)
    sp = SamplingParams(temperature=0.0, max_new_tokens=12)
    solo = [make_engine(tiny_model, spec=False).generate([p], sp)[0].token_ids
            for p in prompts]
    eng = make_engine(tiny_model, spec=True)
    ids, done = [], {}
    for p in prompts:
        ids.append(eng.add_request(p, sp))
        for f in eng.step():
            done[f.req_id] = f
    while eng.has_work:
        for f in eng.step():
            done[f.req_id] = f
    assert [done[i].token_ids for i in ids] == solo


def test_spec_eos_inside_accepted_run(tiny_model):
    """EOS discovered among accepted drafts must stop the request exactly
    where vanilla decode would."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=16)
    [probe] = make_engine(tiny_model, spec=False).generate(
        [_fuzz_prompts(3, 1)[0]], sp)
    assert len(probe.token_ids) >= 3
    eos = probe.token_ids[2]
    spe = SamplingParams(temperature=0.0, max_new_tokens=16, eos_id=eos)
    p = _fuzz_prompts(3, 1)[0]
    [fv] = make_engine(tiny_model, spec=False).generate([p], spe)
    [fs] = make_engine(tiny_model, spec=True).generate([p], spe)
    assert fs.token_ids == fv.token_ids
    assert fs.stop_reason == fv.stop_reason


def test_spec_partial_acceptance_rolls_back_reservation(tiny_model):
    """The cache must hold EXACTLY the committed tokens after every step —
    rejected drafts' block reservations go back to the pool atomically."""
    eng = make_engine(tiny_model, spec=True)
    bs = eng.ecfg.block_size
    p = _fuzz_prompts(11, 1)[0]
    eng.add_request(p, SamplingParams(temperature=0.0, max_new_tokens=24))
    while eng.has_work:
        eng.step()
        for s in eng.slots:
            if s is None or s.prefill_cursor is not None:
                continue
            alloc = eng.cache.seq(s.req.req_id)
            n_committed = s.req.orig_n_prompt + len(s.generated)
            assert alloc.n_tokens == n_committed
            assert len(alloc.blocks) == max(1, -(-n_committed // bs))
    # every block reclaimed at the end
    assert eng.cache.allocator.n_free == eng.ecfg.total_blocks - 1
    assert eng.spec.accepted <= eng.spec.drafted


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_spec_under_block_pressure_preempts_and_completes(tiny_model):
    """Speculative reservation (1+k tokens per step) under a tight pool:
    preemption must still drain every request with full-length output."""
    eng = make_engine(tiny_model, spec=True, num_blocks=13)
    sp = SamplingParams(temperature=0.0, max_new_tokens=12)
    fins = eng.generate([[1, 5, 9, 11], [1, 200, 300], [2, 7, 9, 13, 15]], sp)
    assert [f.stop_reason for f in fins] == ["length"] * 3
    assert all(len(f.token_ids) == 12 for f in fins)
    assert eng.cache.allocator.n_free == 12


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_grow_running_survives_later_slot_preemption(tiny_model):
    """Regression: while growing slot 0 under pool exhaustion, preemption
    may evict a LATER slot whose stale _Running the grow loop then visits —
    extending its already-released sequence used to KeyError the whole
    engine step. Tight pool + three greedy sequences reproduces it on the
    vanilla path; speculation (1+k reservations) only raises the pressure."""
    for spec in (False, True):
        eng = make_engine(tiny_model, spec=spec, num_blocks=7)
        fins = eng.generate(
            [[1, 2, 3, 4, 5, 6, 7, 8], [9, 10, 11, 12, 13, 14, 15, 16],
             [17, 18, 19, 20, 21, 22, 23, 24]],
            SamplingParams(temperature=0.0, max_new_tokens=40))
        assert len(fins) == 3
        assert eng.cache.allocator.n_free == 6  # pool fully reclaimed


def test_spec_sampling_smoke(tiny_model):
    """temperature > 0 path: rejection sampling completes, stats coherent."""
    eng = make_engine(tiny_model, spec=True)
    sp = SamplingParams(temperature=1.0, top_k=8, max_new_tokens=16)
    fins = eng.generate([_fuzz_prompts(5, 1)[0]] * 2, sp)
    assert all(len(f.token_ids) == 16 for f in fins)
    st = eng.spec.as_dict()
    assert st["spec_committed"] >= st["spec_accepted"]
    assert 0.0 <= st["spec_acceptance_rate"] <= 1.0


def test_spec_logprobs_align_with_tokens(tiny_model):
    """Every emitted token carries its own lp entry, accepted drafts
    included, identical in structure to the vanilla path."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=10, logprobs=3)
    p = _fuzz_prompts(0, 1)[0]
    [fv] = make_engine(tiny_model, spec=False).generate([p], sp)
    [fs] = make_engine(tiny_model, spec=True).generate([p], sp)
    assert fs.token_ids == fv.token_ids
    assert fs.logprobs is not None and len(fs.logprobs) == len(fs.token_ids)
    for e, t in zip(fs.logprobs, fs.token_ids):
        assert e["token"] == t
    # greedy: identical numeric logprobs for the identical tokens
    for a, b in zip(fs.logprobs, fv.logprobs):
        assert a["token"] == b["token"]
        assert np.isclose(a["logprob"], b["logprob"], atol=1e-5)


def test_spec_commits_multiple_tokens_on_repetitive_workload(tiny_model):
    """The acceptance-criterion benchmark: with k=4 on a repetitive-prompt
    workload, the engine averages >= 2 committed tokens per verify step
    (i.e. speculation actually pays, it doesn't just not-break)."""
    best = 0.0
    for seed in (0, 1, 2, 3, 4):
        eng = make_engine(tiny_model, spec=True)
        rng = np.random.default_rng(seed)
        base = rng.integers(3, 500, 4).tolist()
        prompt = (base * 6)[:24]
        eng.generate([prompt], SamplingParams(temperature=0.0,
                                              max_new_tokens=32))
        if eng.spec.verify_steps:
            best = max(best, eng.spec.tokens_per_verify)
        if best >= 2.0:
            break
    assert best >= 2.0, f"tokens/verify peaked at {best:.2f}"


def test_spec_disabled_keeps_vanilla_dispatch(tiny_model):
    """k=0 (or no speculative_model) must never build verify executables."""
    eng = make_engine(tiny_model, spec=False)
    [f] = eng.generate([[1, 2, 3, 1, 2, 3, 1, 2]],
                       SamplingParams(temperature=0.0, max_new_tokens=8))
    assert len(f.token_ids) == 8
    assert eng.spec is None
    assert not eng._verify_fns


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_spec_greedy_equivalence_cross_attention():
    """mllama path: the verify executable's cross-layer tail (slot-indexed
    encoder cache) must preserve greedy equivalence too."""
    from scalable_hw_agnostic_inference_tpu.models import llama as llama_mod

    cfg = llama_mod.LlamaConfig(
        vocab_size=512, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
        mlp_dim=128, max_seq_len=256, rope_theta=10000.0,
        tie_embeddings=True, cross_attention_layers=(1, 3))
    Lv = 34
    params = llama_mod.geometry_params(cfg, quant=False)
    states = np.asarray(
        np.random.default_rng(1).standard_normal((Lv, cfg.dim)), np.float32)
    prompt = ([7, 11, 13] * 4)[:10]
    sp = SamplingParams(temperature=0.0, max_new_tokens=8)

    def run(spec):
        kw = dict(max_model_len=64, max_num_seqs=1, block_size=8,
                  context_encoding_buckets=(16,), max_new_tokens=16)
        if spec:
            kw.update(speculative_model="[ngram]", num_speculative_tokens=3)
        eng = LLMEngine(cfg, params, EngineConfig(**kw), cross_seq_len=Lv)
        eng.add_request(prompt, sp, cross_states=states, cross_len=Lv)
        fins = []
        while eng.has_work:
            fins += eng.step()
        return fins[0]

    assert run(True).token_ids == run(False).token_ids


def test_metrics_publisher_spec_counters():
    """serve/metrics.py speculative plumbing: cumulative engine counters in,
    delta-advanced counters + a JSON push line out."""
    import io
    import json

    from scalable_hw_agnostic_inference_tpu.serve.metrics import (
        MetricsPublisher,
    )

    stream = io.StringIO()
    pub = MetricsPublisher("vllm-x", "pool-a", pod_name="pod-0",
                          stream=stream)
    pub.publish_spec(drafted=10, accepted=7, committed=12)
    pub.publish_spec(drafted=10, accepted=7, committed=12)  # no delta: quiet
    pub.publish_spec(drafted=20, accepted=15, committed=25)
    lines = [json.loads(ln) for ln in stream.getvalue().splitlines()]
    assert len(lines) == 2  # the unchanged snapshot emitted nothing
    data = lines[-1]["data"]
    assert data["vllm-x-spec-drafted"] == 20
    assert data["vllm-x-spec-accepted"] == 15
    assert data["vllm-x-spec-committed"] == 25
    assert data["vllm-x-spec-acceptance"] == 0.75
    if pub.registry is not None:  # prometheus available in the image
        got = {s.name: s.value
               for m in pub.registry.collect() for s in m.samples
               if s.name.startswith("shai_spec") and s.name.endswith("_total")}
        assert got["shai_spec_drafted_total"] == 20
        assert got["shai_spec_accepted_total"] == 15
        assert got["shai_spec_committed_total"] == 25


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_spec_warm_builds_verify_ladder(tiny_model):
    eng = make_engine(tiny_model, spec=True)
    n = eng.warm_executables()
    assert eng._verify_fns, "warmup must pre-compile the verify ladder"
    assert set(eng._verify_fns) == set(eng._decode_fns)
    assert n == eng.n_executables
