"""Copy-on-write KV fan-out, and the continuation ladder it admits through.

``SHAI_KV_COW=1`` n>1 fan-out must be TOKEN-EXACT against n independent
requests (threefry's per-row sampling independence makes the tiled one-row
prefill logits sample identically) and POOL-EXACT on release — shared
refcounted prompt blocks, lazy tail copy on first divergent write, zero
leaked blocks under seeded cancel/evict fuzz.
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


def make_engine(tiny_model, monkeypatch, *, cow=False, async_on=True,
                **over):
    cfg, params = tiny_model
    monkeypatch.setenv("SHAI_ASYNC_DECODE", "1" if async_on else "0")
    monkeypatch.setenv("SHAI_KV_QUANT", "")
    monkeypatch.setenv("SHAI_KV_COW", "1" if cow else "0")
    kw = dict(max_model_len=128, max_num_seqs=3, block_size=8,
              context_encoding_buckets=(16, 32), max_new_tokens=16)
    kw.update(over)
    eng = LLMEngine(cfg, params, EngineConfig(**kw))
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
# the continuation ladder: one program a (start, bucket), one pad ledger
# ---------------------------------------------------------------------------

def test_continuation_programs_are_keyed_by_start_and_bucket(tiny_model,
                                                             monkeypatch):
    """A long prompt walks the chunk ladder and a cached admission takes a
    (warm start, bucket) program: every continuation in ``eng._prefill``
    is ``("cont", start_blocks, bucket)``, all of them warmed."""
    eng = make_engine(tiny_model, monkeypatch, enable_prefix_caching=True)
    eng.warm_executables()
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    prompt = np.random.default_rng(5).integers(3, 200, 70).tolist()
    asked = []
    cont_for = eng._cont_for
    monkeypatch.setattr(eng, "_cont_for", lambda *a: (
        asked.append(a), cont_for(*a))[1])
    eng.generate([prompt], sp)           # a prefill and two chunks
    eng.generate([prompt[:40] + [5, 6]], sp)      # admitted from the cache
    assert asked == [(4,), (8,), (4, 16)]
    conts = [k for k in eng._prefill if not isinstance(k[0], int)]
    assert conts and all(
        k[0] == "cont" and len(k) == 3 and k[1] >= 1
        and k[2] in eng.buckets.buckets for k in conts)
    assert not hasattr(eng, "_ragged") and not hasattr(eng, "_fused")
    assert eng.obs.recompiles == 0
    assert eng.cache.leaked_blocks == 0


def test_pad_accounting_phase_split_laddered_engine(tiny_model,
                                                    monkeypatch):
    # the pad ledger splits by phase, and the split sums exactly to the
    # cumulative totals (ONE accounting source)
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    eng = make_engine(tiny_model, monkeypatch)
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
    SamplingParams(temperature=0.9, top_k=5, max_new_tokens=8),
    SamplingParams(temperature=0.7, top_p=0.8, max_new_tokens=8),
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
