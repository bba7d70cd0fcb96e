"""Fused mixed-phase dispatch + copy-on-write KV fan-out (PR 16).

Two oracles pin the tentpole:

- ``SHAI_FUSED_STEP=1`` must be TOKEN-EXACT against the laddered ragged
  engine (the executable set it replaces): the fused executable runs the
  decode section's math and the continuation chunk's math verbatim in one
  dispatch, with the chunk scatter ordered before the decode writes
  exactly as the laddered device stream orders them — so tokens,
  logprobs, stop reasons, and pool balance are identical across
  greedy/topk/topp, both async disciplines, preemption, chunked prefill,
  prefix caching, and int8 KV.
- ``SHAI_KV_COW=1`` n>1 fan-out must be TOKEN-EXACT against n
  independent requests (threefry's per-row sampling independence makes
  the tiled one-row prefill logits sample identically) and POOL-EXACT on
  release — shared refcounted prompt blocks, lazy tail copy on first
  divergent write, zero leaked blocks under seeded cancel/evict fuzz.
"""

import numpy as np
import pytest

from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
from scalable_hw_agnostic_inference_tpu.engine.engine import (
    LLMEngine,
    SamplingParams,
)
from scalable_hw_agnostic_inference_tpu.engine.loop import EngineLoop
from scalable_hw_agnostic_inference_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
)


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, params


def make_engine(tiny_model, monkeypatch, *, fused=False, ragged=True,
                quant=False, cow=False, async_on=True, **over):
    cfg, params = tiny_model
    monkeypatch.setenv("SHAI_ASYNC_DECODE", "1" if async_on else "0")
    monkeypatch.setenv("SHAI_RAGGED_ATTENTION", "1" if ragged else "0")
    monkeypatch.setenv("SHAI_FUSED_STEP", "1" if fused else "0")
    monkeypatch.setenv("SHAI_KV_QUANT", "int8" if quant else "")
    monkeypatch.setenv("SHAI_KV_COW", "1" if cow else "0")
    kw = dict(max_model_len=128, max_num_seqs=3, block_size=8,
              context_encoding_buckets=(16, 32), max_new_tokens=16)
    kw.update(over)
    eng = LLMEngine(cfg, params, EngineConfig(**kw))
    assert eng._fused is (fused and ragged)
    assert eng._kv_cow is cow
    return eng


def pool_balanced(eng) -> bool:
    return eng.cache.allocator.n_free == eng.ecfg.total_blocks - 1


def assert_finished_equal(a, b):
    assert a.token_ids == b.token_ids, (a.req_id, a.token_ids, b.token_ids)
    assert a.stop_reason == b.stop_reason
    if a.logprobs is None or b.logprobs is None:
        assert a.logprobs == b.logprobs
        return
    assert len(a.logprobs) == len(b.logprobs)
    for e1, e2 in zip(a.logprobs, b.logprobs):
        assert e1["token"] == e2["token"]
        assert e1["logprob"] == pytest.approx(e2["logprob"], abs=1e-5)


MIXED = [[1, 5, 9], [2] * 20, [7, 3] * 14, [4]]  # mixed lengths, on purpose


# ---------------------------------------------------------------------------
# fused step: token-exact vs the laddered ragged engine
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.parametrize("sp", [
    SamplingParams(temperature=0.0, max_new_tokens=8, logprobs=2),
    pytest.param(SamplingParams(temperature=0.9, top_k=5, max_new_tokens=8),
                 marks=pytest.mark.slow),
    pytest.param(SamplingParams(temperature=0.7, top_p=0.8,
                                max_new_tokens=8),
                 marks=pytest.mark.slow),
], ids=["greedy", "topk", "topp"])
@pytest.mark.parametrize("async_on", [
    True,
    pytest.param(False, marks=pytest.mark.slow),
], ids=["async", "sync"])
def test_fused_matches_laddered_oracle(tiny_model, monkeypatch, sp,
                                       async_on):
    a = make_engine(tiny_model, monkeypatch, fused=True, async_on=async_on)
    b = make_engine(tiny_model, monkeypatch, fused=False, async_on=async_on)
    fa = a.generate(MIXED, sp)
    fb = b.generate(MIXED, sp)
    for x, y in zip(fa, fb):
        assert_finished_equal(x, y)
    assert pool_balanced(a) and pool_balanced(b)


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_fused_chunked_prefill_parity(tiny_model, monkeypatch):
    # prompt > largest bucket: the fused engine defers intermediate
    # chunks onto decode dispatches and runs the final chunk through a
    # chunk-only fused call; the laddered engine runs the rcont ladder
    rng = np.random.default_rng(5)
    long_prompt = rng.integers(3, 200, 70).tolist()
    # a decode companion so deferred chunks actually ride decode steps
    prompts = [long_prompt, [9, 8, 7]]
    sp = SamplingParams(temperature=0.0, max_new_tokens=8)
    outs, fused_eng = {}, None
    for fused in (True, False):
        eng = make_engine(tiny_model, monkeypatch, fused=fused)
        fins = eng.generate(prompts, sp)
        outs[fused] = [f.token_ids for f in fins]
        assert pool_balanced(eng)
        if fused:
            fused_eng = eng
    assert outs[True] == outs[False]
    # the fused engine never built a continuation executable
    assert not any(k[0] in ("cont", "rcont") for k in fused_eng._prefill)
    assert fused_eng._fused_fns
    # satellite: the pad ledger splits by phase, and the split sums
    # exactly to the cumulative totals (ONE accounting source)
    snap = fused_eng.obs.snapshot()
    by_phase = snap["pad_by_phase"]
    assert {"prefill", "decode", "chunk"} <= set(by_phase)
    assert sum(e["pad"] for e in by_phase.values()) == snap["pad_tokens"]
    assert sum(e["real"] for e in by_phase.values()) == snap["real_tokens"]


@pytest.mark.slow
def test_fused_preemption_parity(tiny_model, monkeypatch):
    # a pool too small for the batch forces recompute-preemption mid-run
    sp = SamplingParams(temperature=0.0, max_new_tokens=12)
    outs = {}
    for fused in (True, False):
        eng = make_engine(tiny_model, monkeypatch, fused=fused,
                          num_blocks=6)
        fins = eng.generate([[1, 2, 3, 4, 5, 6], [9, 8, 7, 6, 5]], sp)
        outs[fused] = [(f.token_ids, f.stop_reason) for f in fins]
        assert eng.obs.preemptions >= 1
        assert pool_balanced(eng)
    assert outs[True] == outs[False]


@pytest.mark.slow
def test_fused_int8_kv_parity(tiny_model, monkeypatch):
    # quant on BOTH sides: the fused step's requantizing decode write and
    # whole-block chunk scatter must match the laddered engine's bit-exact
    sp = SamplingParams(temperature=0.0, max_new_tokens=8)
    outs = {}
    for fused in (True, False):
        eng = make_engine(tiny_model, monkeypatch, fused=fused, quant=True)
        fins = eng.generate(MIXED, sp)
        outs[fused] = [f.token_ids for f in fins]
        assert pool_balanced(eng)
    assert outs[True] == outs[False]


@pytest.mark.slow
def test_fused_prefix_cache_parity(tiny_model, monkeypatch):
    # quant OFF + caching ON: fused cached admission runs the chunk-only
    # fused dispatch at the full chunk window (start as data)
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    rng = np.random.default_rng(7)
    prompt = rng.integers(3, 200, 40).tolist()
    outs = {}
    for fused in (True, False):
        eng = make_engine(tiny_model, monkeypatch, fused=fused,
                          enable_prefix_caching=True)
        f1 = eng.generate([prompt], sp)          # registers the prefix
        f2 = eng.generate([prompt + [5, 6]], sp)  # admits from cache
        outs[fused] = [f.token_ids for f in f1 + f2]
        assert eng.cache.n_evictable > 0  # the prefix really registered
        assert eng.cache.leaked_blocks == 0
    assert outs[True] == outs[False]


@pytest.mark.slow
def test_fused_int8_plus_prefix_cache_excluded(tiny_model, monkeypatch):
    # int8 + prefix-cache reuse falls back to laddered admission in fused
    # mode (the whole-bucket fused window would re-quantize the cached
    # tail block under a different scale) — the combination must still
    # WORK, it just declines the cached fast path
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    eng = make_engine(tiny_model, monkeypatch, fused=True, quant=True,
                      enable_prefix_caching=True)
    prompt = [7, 3] * 10
    eng.generate([prompt], sp)
    fins = eng.generate([prompt + [5]], sp)
    assert len(fins[0].token_ids) == 4
    assert eng.cache.leaked_blocks == 0


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_fused_ladder_collapses_and_stays_closed(tiny_model, monkeypatch):
    # the measurable tentpole claim: the fused engine warms FEWER
    # executables (decode grid + rcont ladder collapse to one fused entry
    # per batch bucket) and the warmed set stays closed over a mixed run
    a = make_engine(tiny_model, monkeypatch, fused=True)
    b = make_engine(tiny_model, monkeypatch, fused=False)
    a.warm_executables()
    b.warm_executables()
    assert not a._decode_fns           # decode rides the fused fns
    assert a._fused_fns
    assert a.n_executables < b.n_executables
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    rng = np.random.default_rng(9)
    a.generate([rng.integers(3, 200, n).tolist()
                for n in (4, 20, 40, 70)], sp)
    assert a.obs.recompiles == 0
    assert a.cache.leaked_blocks == 0


def test_fused_requires_ragged(tiny_model, monkeypatch):
    eng = make_engine(tiny_model, monkeypatch, fused=True, ragged=False)
    assert eng._fused is False  # gate, not a crash


@pytest.mark.slow
def test_pad_accounting_phase_split_laddered_engine(tiny_model,
                                                    monkeypatch):
    # the fast fused-path split is asserted in the chunked-parity test
    # above; this covers the LADDERED engine's phase attribution
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    eng = make_engine(tiny_model, monkeypatch, fused=False)
    eng.generate(MIXED + [list(range(3, 73))], sp)
    snap = eng.obs.snapshot()
    by_phase = snap["pad_by_phase"]
    assert {"prefill", "decode", "chunk"} <= set(by_phase)
    assert sum(e["pad"] for e in by_phase.values()) == snap["pad_tokens"]
    assert sum(e["real"] for e in by_phase.values()) == snap["real_tokens"]


# ---------------------------------------------------------------------------
# CoW fan-out: token-exact vs n independent, pool-exact on release
# ---------------------------------------------------------------------------

def _run_to_completion(eng, rids):
    want, done = set(rids), {}
    while want - set(done):
        for f in eng.step():
            done[f.req_id] = f
    return [done[r] for r in rids]


def _submit_fanout(eng, prompt, sp, k):
    rid0 = eng.add_request(prompt, sp, parent_rid=-2)
    return [rid0] + [eng.add_request(prompt, sp, parent_rid=rid0)
                     for _ in range(k - 1)]


@pytest.mark.parametrize("sp", [
    SamplingParams(temperature=0.0, max_new_tokens=8, logprobs=2),
    pytest.param(SamplingParams(temperature=0.9, top_k=5,
                                max_new_tokens=8),
                 marks=pytest.mark.slow),
    pytest.param(SamplingParams(temperature=0.7, top_p=0.8,
                                max_new_tokens=8),
                 marks=pytest.mark.slow),
], ids=["greedy", "topk", "topp"])
def test_cow_fanout_matches_independent(tiny_model, monkeypatch, sp):
    prompt = [7, 3] * 9
    a = make_engine(tiny_model, monkeypatch, cow=True, max_num_seqs=4)
    fa = _run_to_completion(a, _submit_fanout(a, prompt, sp, 3))
    b = make_engine(tiny_model, monkeypatch, cow=False, max_num_seqs=4)
    fb = _run_to_completion(b, [b.add_request(prompt, sp)
                                for _ in range(3)])
    for x, y in zip(fa, fb):
        assert_finished_equal(x, y)
    # the group really shared the prompt blocks and copied lazily
    assert a.cache.cow_forks == 2
    assert a.cache.leaked_blocks == 0 and b.cache.leaked_blocks == 0
    assert pool_balanced(a) and pool_balanced(b)


@pytest.mark.slow
def test_cow_fanout_under_fused_step(tiny_model, monkeypatch):
    # the two tentpole halves compose: fused dispatch + CoW fan-out
    sp = SamplingParams(temperature=0.9, top_k=5, max_new_tokens=8)
    prompt = [7, 3] * 9
    a = make_engine(tiny_model, monkeypatch, fused=True, cow=True,
                    max_num_seqs=4)
    fa = _run_to_completion(a, _submit_fanout(a, prompt, sp, 3))
    b = make_engine(tiny_model, monkeypatch, fused=False, cow=False,
                    max_num_seqs=4)
    fb = _run_to_completion(b, [b.add_request(prompt, sp)
                                for _ in range(3)])
    for x, y in zip(fa, fb):
        assert_finished_equal(x, y)
    assert a.cache.cow_forks == 2 and pool_balanced(a)


@pytest.mark.slow
def test_cow_fanout_pool_exact_under_cancel_evict_fuzz(tiny_model,
                                                       monkeypatch):
    # seeded fuzz: fan-out groups + filler requests on a small pool, with
    # random mid-run cancels of group members — refcounted shared blocks
    # must release pool-exactly whatever order holders die in
    rng = np.random.default_rng(42)
    sp = SamplingParams(temperature=0.8, top_k=4, max_new_tokens=10)
    eng = make_engine(tiny_model, monkeypatch, cow=True, max_num_seqs=4,
                      num_blocks=24)
    live = []
    for _ in range(60):
        if rng.random() < 0.35 and len(live) < 8:
            prompt = rng.integers(3, 200, int(rng.integers(3, 25))).tolist()
            if rng.random() < 0.6:
                live += _submit_fanout(eng, prompt, sp,
                                       int(rng.integers(2, 4)))
            else:
                live.append(eng.add_request(prompt, sp))
        if rng.random() < 0.2 and live:
            eng.cancel(live[int(rng.integers(len(live)))])
        for f in eng.step():
            if f.req_id in live:
                live.remove(f.req_id)
    while eng.has_work:
        eng.step()
    eng.finish_pending()
    assert eng.cache.leaked_blocks == 0
    assert pool_balanced(eng)


def test_fanout_siblings_and_finish_prune(tiny_model, monkeypatch):
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    eng = make_engine(tiny_model, monkeypatch, cow=True, max_num_seqs=4)
    rids = _submit_fanout(eng, [7, 3] * 5, sp, 3)
    assert eng.fanout_siblings(rids[1]) == sorted(rids)
    assert eng.fanout_siblings(12345) == [12345]  # non-member: itself
    _run_to_completion(eng, rids)
    # finish pruned the group maps — no unbounded growth
    assert not eng._fanout_groups and not eng._rid_parent


def test_cancel_of_any_member_aborts_group_via_loop(tiny_model,
                                                    monkeypatch):
    # the satellite-6 regression: one OpenAI n>1 request is one
    # deliverable — cancelling any sibling's future aborts the whole
    # group, pool-exactly
    import time

    sp = SamplingParams(temperature=0.0, max_new_tokens=16)
    eng = make_engine(tiny_model, monkeypatch, cow=True, max_num_seqs=4)
    loop = EngineLoop(eng).start()
    try:
        futs = loop.submit_group([5, 2] * 8, [sp] * 3)
        deadline = time.monotonic() + 10
        while not eng.has_work and time.monotonic() < deadline:
            time.sleep(0.01)  # wait for admission
        loop.cancel(futs[1])
        fins = [f.result(timeout=60) for f in futs]
        assert all(f.stop_reason == "cancelled" for f in fins)
        deadline = time.monotonic() + 10
        while eng.has_work and time.monotonic() < deadline:
            time.sleep(0.01)
        assert eng.cache.leaked_blocks == 0
    finally:
        loop.stop()


@pytest.mark.slow
def test_submit_group_token_exact_vs_n_submits(tiny_model, monkeypatch):
    # the serving seam end-to-end: one group submit == n independent
    # submits, token for token (CoW off here — the seam must be inert
    # without the flag too)
    sp = SamplingParams(temperature=0.9, top_k=5, max_new_tokens=8)
    prompt = [7, 3] * 9
    a = make_engine(tiny_model, monkeypatch, cow=True, max_num_seqs=4)
    la = EngineLoop(a).start()
    try:
        fa = [f.result(timeout=120)
              for f in la.submit_group(prompt, [sp] * 3)]
    finally:
        la.stop()
    b = make_engine(tiny_model, monkeypatch, cow=False, max_num_seqs=4)
    lb = EngineLoop(b).start()
    try:
        fb = [f.result(timeout=120)
              for f in [lb.submit(prompt, sp) for _ in range(3)]]
    finally:
        lb.stop()
    for x, y in zip(fa, fb):
        assert_finished_equal(x, y)


def test_fanout_not_admitted_when_prompts_arrive_split(tiny_model,
                                                       monkeypatch):
    # group admission needs the WHOLE group queued: a straggler sibling
    # arriving after the leader admitted falls back to independent
    # admission (identical-prompt guard) — tokens still exact
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    prompt = [7, 3] * 5
    eng = make_engine(tiny_model, monkeypatch, cow=True, max_num_seqs=4)
    rid0 = eng.add_request(prompt, sp, parent_rid=-2)
    eng.step()  # leader admits alone
    rid1 = eng.add_request(prompt, sp, parent_rid=rid0)
    fins = _run_to_completion(eng, [rid0, rid1])
    assert fins[0].token_ids == fins[1].token_ids  # greedy, same prompt
    assert eng.cache.leaked_blocks == 0
