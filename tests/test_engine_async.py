"""Differential tests: async pipelined decode vs the lock-step oracle.

``SHAI_ASYNC_DECODE=1`` (the default) restructures the decode hot loop —
device-resident batch state, on-device token feedback, one-step-lookahead
dispatch — but must be TOKEN-EXACT against the lock-step path it replaced:
identical token streams, logprobs, stop reasons, streaming-callback order,
and KV pool balance, across every scheduling shape the engine supports.
The lock-step path (``SHAI_ASYNC_DECODE=0``) is kept alive exactly to be
this oracle.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
from scalable_hw_agnostic_inference_tpu.engine.engine import (
    LLMEngine,
    SamplingParams,
)
from scalable_hw_agnostic_inference_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, params


def make_engine(tiny_model, async_on, monkeypatch, **over):
    cfg, params = tiny_model
    monkeypatch.setenv("SHAI_ASYNC_DECODE", "1" if async_on else "0")
    kw = dict(max_model_len=64, max_num_seqs=3, block_size=8,
              context_encoding_buckets=(16, 32), max_new_tokens=16)
    kw.update(over)
    eng = LLMEngine(cfg, params, EngineConfig(**kw))
    assert eng._async is async_on
    return eng


def pool_balanced(eng) -> bool:
    return eng.cache.allocator.n_free == eng.ecfg.total_blocks - 1


def assert_finished_equal(a, b):
    assert a.req_id == b.req_id
    assert a.token_ids == b.token_ids, (a.req_id, a.token_ids, b.token_ids)
    assert a.stop_reason == b.stop_reason
    if a.logprobs is None or b.logprobs is None:
        assert a.logprobs == b.logprobs
        return
    assert len(a.logprobs) == len(b.logprobs)
    for e1, e2 in zip(a.logprobs, b.logprobs):
        assert e1["token"] == e2["token"]
        assert e1["logprob"] == pytest.approx(e2["logprob"], abs=1e-5)
        assert e1["top_ids"] == e2["top_ids"]


# ---------------------------------------------------------------------------
# vanilla decode parity
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.parametrize("sp", [
    SamplingParams(temperature=0.0, max_new_tokens=8),
    pytest.param(SamplingParams(temperature=0.9, top_k=5, max_new_tokens=8),
                 marks=pytest.mark.slow),
    pytest.param(SamplingParams(temperature=0.7, top_p=0.8,
                                max_new_tokens=8),
                 marks=pytest.mark.slow),
], ids=["greedy", "topk", "topp"])
def test_async_generate_matches_lockstep(tiny_model, monkeypatch, sp):
    prompts = [[1, 5, 9], [1, 200, 300, 400, 17, 23], [2, 2, 7, 7]]
    a = make_engine(tiny_model, True, monkeypatch)
    b = make_engine(tiny_model, False, monkeypatch)
    fa = a.generate(prompts, sp)
    fb = b.generate(prompts, sp)
    for x, y in zip(fa, fb):
        assert_finished_equal(x, y)
    assert pool_balanced(a) and pool_balanced(b)
    # the pipelined path really pipelined: its recorded inter-step gap is
    # the clamped zero of dispatch-before-readback, never the lock-step
    # marshal+bookkeeping gap
    assert a.obs.step_gap.snapshot()["sum"] <= b.obs.step_gap.snapshot()["sum"]


def test_async_logprobs_and_eos_match_lockstep(tiny_model, monkeypatch):
    # pick an EOS id the tiny model actually emits so the eos-pop path
    # (commit pops the pending lp entry) is exercised under the lag
    probe = make_engine(tiny_model, False, monkeypatch)
    [fin] = probe.generate([[1, 5, 9]],
                           SamplingParams(temperature=0.0, max_new_tokens=8))
    eos = fin.token_ids[3]
    sp = SamplingParams(temperature=0.0, max_new_tokens=8, eos_id=eos,
                        logprobs=3)
    a = make_engine(tiny_model, True, monkeypatch)
    b = make_engine(tiny_model, False, monkeypatch)
    [fa] = a.generate([[1, 5, 9]], sp)
    [fb] = b.generate([[1, 5, 9]], sp)
    assert fa.stop_reason == "eos"
    assert_finished_equal(fa, fb)
    assert pool_balanced(a) and pool_balanced(b)


def test_async_streaming_order_matches_lockstep(tiny_model, monkeypatch):
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    streams = {}
    for mode in (True, False):
        eng = make_engine(tiny_model, mode, monkeypatch)
        toks = []
        eng.add_request([3, 4, 5], sp, on_token=toks.append)
        while eng.has_work:
            eng.step()
        streams[mode] = toks
    assert streams[True] == streams[False]
    assert len(streams[True]) == 6


# ---------------------------------------------------------------------------
# composition-changing events: join/finish, preemption, cancel, deadline
# ---------------------------------------------------------------------------

def _run_schedule(eng, schedule, sp_of):
    """Drive ``eng`` through a deterministic (step -> actions) schedule.

    ``schedule``: dict step_idx -> list of ("add", prompt) | ("cancel", idx)
    where idx indexes the order of adds. Returns (finished_by_rid,
    streams_by_rid, rids).
    """
    fins, streams, rids = {}, {}, []
    step = 0
    while True:
        for action in schedule.get(step, ()):
            if action[0] == "add":
                toks = []
                rid = eng.add_request(action[1], sp_of(len(rids)),
                                      on_token=toks.append)
                rids.append(rid)
                streams[rid] = toks
            elif action[1] < len(rids):  # cancel targets only added reqs
                victim = rids[action[1]]
                fin = eng.cancel(victim)
                if fin is not None:
                    fins[fin.req_id] = fin
        if eng.has_work:
            for f in eng.step():
                fins[f.req_id] = f
        step += 1
        if not eng.has_work and step > max(schedule, default=0):
            return fins, streams, rids


@pytest.mark.slow
def test_async_mixed_join_finish_schedule(tiny_model, monkeypatch):
    """Staggered joins + different lengths: every finish/join recomposes
    the batch mid-pipeline; outputs must still be token-exact."""
    schedule = {
        0: [("add", [1, 5, 9]), ("add", [2, 7])],
        3: [("add", [42, 43, 44, 45])],
        6: [("add", [9, 9, 9])],
    }

    def sp_of(i):
        return SamplingParams(temperature=0.0,
                              max_new_tokens=(4, 9, 5, 7)[i])

    out = {}
    for mode in (True, False):
        eng = make_engine(tiny_model, mode, monkeypatch)
        out[mode] = _run_schedule(eng, schedule, sp_of)
        assert pool_balanced(eng)
    fa, sa, ra = out[True]
    fb, sb, rb = out[False]
    assert ra == rb
    for rid in ra:
        assert_finished_equal(fa[rid], fb[rid])
        assert sa[rid] == sb[rid]


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_async_preemption_parity_and_pool_balance(tiny_model, monkeypatch):
    """A pool sized to force recompute-preemption: the async path must
    flush around the preempting grow path and still match token-for-token
    (preemption re-queues generated tokens as prompt suffix)."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=12)
    out = {}
    for mode in (True, False):
        eng = make_engine(tiny_model, mode, monkeypatch, num_blocks=6,
                          max_model_len=64)
        fins = {}
        rids = [eng.add_request([11 + i, 7, 9, 3], sp) for i in range(3)]
        while eng.has_work:
            for f in eng.step():
                fins[f.req_id] = f
        out[mode] = (fins, rids, eng.obs.preemptions)
        assert pool_balanced(eng)
    fa, ra, pa = out[True]
    fb, rb, pb = out[False]
    assert pa == pb and pa > 0, "schedule did not exercise preemption"
    for rid in ra:
        assert_finished_equal(fa[rid], fb[rid])


def test_async_cancel_mid_decode_flush_conserves_blocks(tiny_model,
                                                        monkeypatch):
    """Cancel with the lookahead step in flight: the flush discards the
    extra computed token (never emitted) and frees its blocks the same
    call; emitted partials match a lock-step cancel at the same step."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=14)
    out = {}
    for mode in (True, False):
        eng = make_engine(tiny_model, mode, monkeypatch)
        rid = eng.add_request([3, 4, 5], sp)
        keep = eng.add_request([8, 8, 9], sp)
        for _ in range(5):
            eng.step()
        if mode:
            assert eng._pipe is not None, "lookahead should be in flight"
        fin = eng.cancel(rid)
        assert fin is not None and fin.stop_reason == "cancelled"
        fins = {rid: fin}
        while eng.has_work:
            for f in eng.step():
                fins[f.req_id] = f
        out[mode] = (fins, rid, keep)
        assert pool_balanced(eng)
        if mode:
            assert eng.obs.snapshot()["flush_by_reason"].get("cancelled") == 1
    fa, rid, keep = out[True]
    fb, _, _ = out[False]
    assert_finished_equal(fa[rid], fb[rid])
    assert_finished_equal(fa[keep], fb[keep])


@pytest.mark.slow
def test_async_deadline_expiry_terminal_and_conserved(tiny_model,
                                                      monkeypatch):
    """A deadline passing mid-decode (lookahead in flight) must finish the
    request with stop reason ``timeout`` and conserve the pool. Wall-clock
    decides WHICH step expires, so this asserts invariants, not parity."""
    eng = make_engine(tiny_model, True, monkeypatch)
    sp = SamplingParams(temperature=0.0, max_new_tokens=200)
    rid = eng.add_request([3, 4, 5], sp,
                          deadline_at=time.monotonic() + 0.05)
    survivor = eng.add_request([8, 8, 9],
                               SamplingParams(temperature=0.0,
                                              max_new_tokens=6))
    fins = {}
    t0 = time.monotonic()
    while eng.has_work and time.monotonic() - t0 < 30.0:
        for f in eng.step():
            fins[f.req_id] = f
    assert fins[rid].stop_reason == "timeout"
    assert fins[survivor].stop_reason == "length"
    assert len(fins[survivor].token_ids) == 6
    assert pool_balanced(eng)


# ---------------------------------------------------------------------------
# speculative decoding shares the resident state; entry forces a flush
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_async_speculative_matches_lockstep(tiny_model, monkeypatch):
    over = dict(max_model_len=128, max_new_tokens=24,
                speculative_model="[ngram]", num_speculative_tokens=3)
    base = [5, 6, 7, 8] * 5
    sp = SamplingParams(temperature=0.0, max_new_tokens=16)
    out = {}
    for mode in (True, False):
        eng = make_engine(tiny_model, mode, monkeypatch, **over)
        fins = eng.generate([base, base[2:] + [9]], sp)
        out[mode] = (fins, eng.spec.committed, eng.spec.verify_steps)
        assert pool_balanced(eng)
        assert eng.spec.verify_steps > 0, "workload never drafted"
    for x, y in zip(out[True][0], out[False][0]):
        assert_finished_equal(x, y)
    assert out[True][1:] == out[False][1:]


# ---------------------------------------------------------------------------
# randomized differential fuzz over full schedules
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_async_differential_fuzz(tiny_model, monkeypatch):
    """Seeded random schedules — staggered joins, random lengths and
    sampling knobs (logprobs included), cancels at random steps — replayed
    identically against both disciplines. Request ids are deterministic
    (same add order), so the comparison is exact per request."""
    master = np.random.default_rng(0xA57)
    for round_i in range(4):
        seed = int(master.integers(1 << 30))
        rng = np.random.default_rng(seed)
        n_req = int(rng.integers(3, 7))
        schedule = {}
        params = []
        for i in range(n_req):
            step = int(rng.integers(0, 10))
            prompt = rng.integers(1, 500, int(rng.integers(2, 9))).tolist()
            schedule.setdefault(step, []).append(("add", prompt))
            params.append(SamplingParams(
                temperature=float(rng.choice([0.0, 0.8])),
                top_k=int(rng.choice([0, 5])),
                max_new_tokens=int(rng.integers(3, 12)),
                logprobs=int(rng.choice([0, 2]))))
        for idx in rng.choice(n_req, size=2, replace=False):
            step = int(rng.integers(2, 14))
            schedule.setdefault(step, []).append(("cancel", int(idx)))
        out = {}
        for mode in (True, False):
            eng = make_engine(tiny_model, mode, monkeypatch)
            fins, streams, rids = _run_schedule(
                eng, schedule, lambda i: params[i])
            out[mode] = (fins, streams, rids)
            assert pool_balanced(eng), f"seed {seed} mode {mode}: pool leak"
        fa, sa, ra = out[True]
        fb, sb, rb = out[False]
        assert ra == rb, f"seed {seed}: request ids diverged"
        assert set(fa) == set(fb), f"seed {seed}: finished sets diverged"
        for rid in fa:
            assert_finished_equal(fa[rid], fb[rid])
            assert sa.get(rid) == sb.get(rid), f"seed {seed} rid {rid}"


# ---------------------------------------------------------------------------
# callers beyond the slots: the steady path with a queue behind it
# ---------------------------------------------------------------------------

def _run_closed_loop(eng, n_callers, rounds, sp_of, on_step=None):
    """A closed loop at step granularity: each caller keeps one request
    outstanding and sends its next right after the step its last one
    finished in. Deterministic given the finishes, which both disciplines
    owe on the same step. Returns (finished by add index, the step each
    finished in, every streamed (add index, token) in callback order)."""
    fins, fin_step, events, index_of = {}, {}, [], {}
    sent = [0] * n_callers

    def send(c):
        i = len(index_of)
        rid = eng.add_request(
            [1 + c, 2 + sent[c], 3], sp_of(i),
            on_token=lambda t, i=i: events.append((i, t)))
        index_of[rid] = (i, c)
        sent[c] += 1

    for c in range(n_callers):
        send(c)
    step = 0
    while eng.has_work:
        step += 1
        if on_step is not None:
            on_step(step, index_of)
        for f in eng.step():
            i, c = index_of[f.req_id]
            fins[i], fin_step[i] = f, step
            if sent[c] < rounds:
                send(c)
    return fins, fin_step, events


def _assert_same_run(out_async, out_lock):
    (fa, sa, ea), (fb, sb, eb) = out_async, out_lock
    assert set(fa) == set(fb)
    for i in fa:
        assert_finished_equal(fa[i], fb[i])
    assert sa == sb, "a request finished on another step"
    assert ea == eb, "on_token order diverged"


@pytest.mark.parametrize("temperature,top_k", [(0.0, 0), (0.8, 5)],
                         ids=["greedy", "seeded-temperature"])
def test_async_oversubscribed_matches_lockstep(tiny_model, monkeypatch,
                                               temperature, top_k):
    """Five callers on three slots, answers of mixed length: tokens,
    logprobs, finish steps and the order of ``on_token`` calls equal the
    lock-step oracle's, while the async engine streams through the steps
    that can admit nothing."""
    def sp_of(i):
        return SamplingParams(temperature=temperature, top_k=top_k,
                              max_new_tokens=(7, 12, 9, 15)[i % 4],
                              logprobs=2)

    out = {}
    for mode in (True, False):
        eng = make_engine(tiny_model, mode, monkeypatch)
        out[mode] = _run_closed_loop(eng, 5, 3, sp_of)
        assert pool_balanced(eng)
        if mode:
            snap = eng.obs.snapshot()
    _assert_same_run(out[True], out[False])
    assert len(out[True][0]) == 15
    # every admission behind a full batch is one flush; the steps between
    # them stream (a step that could admit nothing used to flush too)
    assert snap["flush_by_reason"]["admission"] <= 15
    assert snap["pipeline_flushes"] < 0.5 * snap["steps"], snap


def test_waiting_deadline_expires_within_one_step_while_streaming(
        tiny_model, monkeypatch):
    """Slots full, one request queued behind them with a deadline: the
    steps before it is due stream; the first step after it flushes for
    ``deadline`` and finishes the waiter with ``timeout``."""
    eng = make_engine(tiny_model, True, monkeypatch)
    sp = SamplingParams(temperature=0.0, max_new_tokens=40)
    for i in range(3):
        eng.add_request([3 + i, 4, 5], sp)
    for _ in range(3):
        eng.step()
    assert eng._pipe is not None and eng._free_slot() is None
    t_add = time.monotonic()
    waiter = eng.add_request([8, 8, 9], sp, deadline_at=t_add + 0.5)
    before = eng.obs.snapshot()["pipeline_flushes"]
    eng.step()
    eng.step()
    if time.monotonic() < t_add + 0.5:      # not due yet: both streamed
        assert eng.obs.snapshot()["pipeline_flushes"] == before
        assert eng.n_waiting == 1
    time.sleep(max(0.0, t_add + 0.51 - time.monotonic()))
    done = eng.step()
    assert [(f.req_id, f.stop_reason) for f in done] == [(waiter, "timeout")]
    snap = eng.obs.snapshot()
    assert snap["flush_by_reason"].get("deadline") == 1
    assert snap["pipeline_flushes"] == before + 1
    assert eng._pipe is not None            # re-established the same call
    while eng.has_work:
        eng.step()
    assert pool_balanced(eng)


def test_cancel_of_a_waiting_request_while_streaming(tiny_model,
                                                     monkeypatch):
    """Cancelling a request that only queues touches no slot: the
    lookahead stays in flight, and the running rows' tokens, finish steps
    and streams are the lock-step oracle's."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=14)
    out = {}
    for mode in (True, False):
        eng = make_engine(tiny_model, mode, monkeypatch)
        cancelled = []

        def on_step(step, index_of):
            if step == 6:
                # the fourth and fifth callers' first requests still queue
                victim = next(r for r, (i, _) in index_of.items() if i == 4)
                assert eng.n_waiting == 2
                n0 = eng.obs.snapshot()["pipeline_flushes"]
                fin = eng.cancel(victim)
                assert fin.stop_reason == "cancelled" and not fin.token_ids
                cancelled.append(victim)
                assert eng.n_waiting == 1
                if mode:
                    assert eng._pipe is not None
                    assert eng.obs.snapshot()["pipeline_flushes"] == n0

        out[mode] = _run_closed_loop(eng, 5, 1, lambda i: sp, on_step)
        assert len(cancelled) == 1 and 4 not in out[mode][0]
        assert pool_balanced(eng)
    _assert_same_run(out[True], out[False])


def test_pool_pressure_with_a_waiter_flushes_and_preempts(tiny_model,
                                                          monkeypatch):
    """Two full slots, a third caller queued, and a pool too small for
    both rows to grow: the steady step prices the growth, flushes for
    ``kv_pressure`` and takes the preempting grow path; the preempted row
    and the waiter are admitted in the oracle's order, tokens equal, blocks
    conserved."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=20)
    out, snaps = {}, {}
    for mode in (True, False):
        eng = make_engine(tiny_model, mode, monkeypatch, max_num_seqs=2,
                          num_blocks=5)
        out[mode] = _run_closed_loop(eng, 3, 1, lambda i: sp)
        snaps[mode] = eng.obs.snapshot()
        assert pool_balanced(eng)
    _assert_same_run(out[True], out[False])
    assert snaps[True]["preemptions"] == snaps[False]["preemptions"] > 0
    assert snaps[True]["flush_by_reason"].get("kv_pressure", 0) >= 1


# ---------------------------------------------------------------------------
# pipeline mechanics
# ---------------------------------------------------------------------------

def test_finish_pending_retires_trailing_inflight(tiny_model, monkeypatch):
    """When every slot finishes at a commit, the final lookahead dispatch
    stays in flight; finish_pending (the engine-loop idle hook) retires it
    without disturbing state, and is a no-op thereafter."""
    eng = make_engine(tiny_model, True, monkeypatch)
    eng.generate([[1, 2, 3]], SamplingParams(temperature=0.0,
                                             max_new_tokens=5))
    assert eng._pipe is not None
    eng.finish_pending()
    assert eng._pipe is None
    assert pool_balanced(eng)
    flushes = eng.obs.pipeline_flushes
    eng.finish_pending()   # idempotent: nothing in flight
    assert eng.obs.pipeline_flushes == flushes
    # engine still serves after the idle retire
    [fin] = eng.generate([[7, 7, 2]], SamplingParams(temperature=0.0,
                                                     max_new_tokens=4))
    assert len(fin.token_ids) == 4
    assert pool_balanced(eng)


def test_async_gate_env_off_is_lockstep(tiny_model, monkeypatch):
    eng = make_engine(tiny_model, False, monkeypatch)
    eng.generate([[1, 2, 3]], SamplingParams(temperature=0.0,
                                             max_new_tokens=4))
    assert eng._pipe is None
    assert eng.obs.pipeline_flushes == 0


# ---------------------------------------------------------------------------
# the loop thread accounts for its own time (obs.steploop phases)
# ---------------------------------------------------------------------------

class SpanLog:
    """Stands in for ``obs.trace.annotate`` under ``obs.steploop``: every
    annotation it hands out records its enter (name and metadata) and its
    exit, and refuses an enter while another is open on the same thread
    (another test's engine loop may still be polling in this process)."""

    def __init__(self):
        self._here = threading.local()
        self.names = []
        self.meta = []

    @property
    def open(self):
        return getattr(self._here, "open", None)

    def __call__(self, name, **meta):
        log = self

        class Ann:
            def __enter__(self):
                assert log.open is None, (
                    f"{name} entered while {log.open} is open")
                log._here.open = name
                log.names.append(name)
                log.meta.append(meta)

            def __exit__(self, *exc):
                assert log.open == name
                log._here.open = None

        return Ann()


def test_phases_tile_the_loop_thread(tiny_model, monkeypatch):
    """Arrivals, a pause with nothing to run, more arrivals, a finish: the
    loop thread's phases are flat, their seconds add up to the time that
    passed, and each step's record holds no more than the step took."""
    from scalable_hw_agnostic_inference_tpu.engine.loop import EngineLoop
    from scalable_hw_agnostic_inference_tpu.obs import steploop

    spans = SpanLog()
    monkeypatch.setattr(steploop, "annotate", spans)
    eng = make_engine(tiny_model, True, monkeypatch)
    loop = EngineLoop(eng).start()
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    try:
        loop.submit([1, 2, 3], sp).result(120)   # compiles: before the clock
        t0a = time.monotonic()
        before, t0b = eng.obs.snapshot()["phase_s"], time.monotonic()
        futs = [loop.submit([5, 6, 7, 8], sp) for _ in range(4)]  # 3 slots
        for f in futs:
            f.result(120)
        time.sleep(0.05)                          # the loop polls, idle
        futs = [loop.submit([9, 10, 11], sp) for _ in range(2)]
        loop.cancel(futs[1])
        for f in futs:
            f.result(120)
        t1a = time.monotonic()
        after, t1b = eng.obs.snapshot()["phase_s"], time.monotonic()
    finally:
        loop.stop()
    spent = {k: after[k] - before[k] for k in after}
    # each reading lies between two reads of the clock
    assert (t1a - t0b) * 0.98 <= sum(spent.values()) <= (t1b - t0a) * 1.02
    for phase in ("loop.idle", "loop.intake", "loop.resolve", "engine.admit",
                  "engine.prefill", "engine.fetch", "engine.apply",
                  "engine.marshal", "engine.decode", "engine.commit",
                  "engine.record"):
        assert spent[phase] > 0, phase
        assert phase in spans.names
    fields = ("admit_ms", "marshal_ms", "dispatch_ms", "commit_ms",
              "fetch_ms", "apply_ms")
    steps = eng.obs.recent_steps()
    assert steps
    for rec in steps:
        assert sum(rec[f] for f in fields) <= rec["duration_s"] * 1e3 + 0.01
        assert rec["waiting_peak"] >= rec["waiting"]
    assert max(r["waiting_peak"] for r in steps) >= 1   # 4 callers, 3 slots


def test_stepping_with_no_loop_leaves_no_phase_open(tiny_model, monkeypatch):
    eng = make_engine(tiny_model, True, monkeypatch)
    eng.generate([[1, 2, 3]], SamplingParams(temperature=0.0,
                                             max_new_tokens=4))
    a = eng.obs.snapshot()["phase_s"]
    time.sleep(0.02)
    assert eng.obs.snapshot()["phase_s"] == a   # nothing runs on
    assert a["engine.decode"] > 0 and a["loop.idle"] == 0


def test_callers_beyond_the_slots_stream(tiny_model, monkeypatch):
    """A saturated engine streams: with 12 callers on 8 slots about four
    requests always wait, and a step with nothing it can admit takes the
    steady path. The lookahead retires for ``admission`` only when a finish
    has freed a slot for a waiter, so between the first finish and the
    twentieth the admission flushes are bounded by the finishes of that
    span, and most steps are steady. (Until PR 28 this test pinned the
    opposite: every step flushed for ``admission``.)"""
    from scalable_hw_agnostic_inference_tpu.engine.loop import EngineLoop

    eng = make_engine(tiny_model, True, monkeypatch, max_num_seqs=8,
                      max_model_len=64)
    loop = EngineLoop(eng).start()
    sp = SamplingParams(temperature=0.0, max_new_tokens=40)
    marks = []          # snapshots as requests finish, on the loop thread

    def caller(i):
        for j in range(3):
            fut = loop.submit([1 + i, 2 + j, 3], sp)
            fut.add_done_callback(
                lambda _f: marks.append(eng.obs.snapshot()))
            fut.result(300)

    try:
        threads = [threading.Thread(target=caller, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
    finally:
        loop.stop()
    assert len(marks) == 36
    # between the first finish and the twentieth all 12 callers are live
    a, b = marks[0], marks[19]
    steps = b["steps"] - a["steps"]
    finishes = b["requests_finished"] - a["requests_finished"]
    admission = (b["flush_by_reason"].get("admission", 0)
                 - a["flush_by_reason"].get("admission", 0))
    flushes = b["pipeline_flushes"] - a["pipeline_flushes"]
    assert steps > 20, (steps, finishes)
    # one admission flush a freed slot at most (finishes of one step share
    # one), and two of slack for the span's edges
    assert admission <= finishes + 2, (admission, finishes, steps)
    # whatever the reason (admission, the recompose behind a finish): most
    # steps retire nothing early
    assert flushes < 0.5 * steps, (flushes, steps, b["flush_by_reason"])


def test_a_request_submitted_while_a_step_runs_waits_at_intake(
        tiny_model, monkeypatch):
    from scalable_hw_agnostic_inference_tpu.engine.loop import EngineLoop
    from scalable_hw_agnostic_inference_tpu.obs.trace import (
        Trace,
        well_formed_problems,
    )

    eng = make_engine(tiny_model, True, monkeypatch)
    loop = EngineLoop(eng).start()
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    in_step, go_on = threading.Event(), threading.Event()

    def hold_the_step(tok):
        # runs on the loop thread, inside a step's commit
        if not in_step.is_set():
            in_step.set()
            go_on.wait(30)

    try:
        first = loop.submit([1, 2, 3], sp, on_token=hold_the_step)
        assert in_step.wait(120)
        second = loop.submit([4, 5, 6, 7], sp)   # the loop thread is busy
        time.sleep(0.02)
        go_on.set()
        fins = [first.result(120), second.result(120)]
    finally:
        go_on.set()
        loop.stop()
    t = fins[1].timing
    assert t["t_enqueue"] < t["t_submit"] <= t["t_admit"] <= t["t_first"]
    assert t["intake_s"] > 0
    hist = eng.obs.histograms()
    assert hist["intake_wait_seconds"]["count"] == 2
    assert hist["intake_wait_seconds"]["sum"] >= t["intake_s"] * 0.99
    # TTFT starts at the caller's submit; queue wait keeps its meaning
    ttft = sum(f.timing["t_first"] - f.timing["t_enqueue"] for f in fins)
    assert hist["ttft_seconds"]["sum"] == pytest.approx(ttft, rel=1e-6)
    queue = sum(f.timing["t_admit"] - f.timing["t_submit"] for f in fins)
    assert hist["queue_wait_seconds"]["sum"] == pytest.approx(queue,
                                                              rel=1e-6)
    assert (t["t_first"] - t["t_enqueue"]
            > t["t_first"] - t["t_submit"])
    tr = Trace("POST /generate")
    tr.add_phase_spans(t)
    tr.close()
    d = tr.to_dict()
    assert not well_formed_problems(d), well_formed_problems(d)
    names = [s["name"] for s in d["spans"]]
    assert names.index("intake") < names.index("queue") \
        < names.index("prefill") < names.index("decode")
    # a direct caller passes no stamp: both are one, and nothing is observed
    rid = eng.add_request([1, 2], sp)
    req = next(r for r in eng.waiting if r.req_id == rid)
    assert req.t_enqueue == req.t_submit
    assert eng.obs.histograms()["intake_wait_seconds"]["count"] == 2


# ---------------------------------------------------------------------------
# an event step dispatches before it reads
# ---------------------------------------------------------------------------

def record_order(eng, monkeypatch):
    """Recording wrappers round the step programs, the sampler, the feed
    program and the loop's reads: the returned list holds, in call order,
    ``prefill``, ``cont`` and ``decode`` (a program's dispatch), ``sample``,
    ``feed`` (a record's sampled tokens written into the token input on the
    device), ``retire`` (the lookahead's read), ``marshal`` (a full
    ``_marshal_running``), ``first_read`` (a ``_resolve_first_tokens`` with
    something to read) and ``read`` (a ``jax.device_get`` or an
    ``np.asarray`` of a device array anywhere but inside ``retire``)."""
    log = []
    retiring = []

    def noting(name, fn):
        def run(*args, **kw):
            log.append(name)
            return fn(*args, **kw)
        return run

    for attr, name in (("_sample1", "sample"), ("_feed1", "feed"),
                       ("_marshal_running", "marshal")):
        monkeypatch.setattr(eng, attr, noting(name, getattr(eng, attr)))
    retire = eng._retire_pipe

    def noted_retire(pipe):
        log.append("retire")
        retiring.append(1)
        try:
            return retire(pipe)
        finally:
            retiring.pop()

    monkeypatch.setattr(eng, "_retire_pipe", noted_retire)
    device_get, asarray = jax.device_get, np.asarray

    def noted_get(x):
        if not retiring:
            log.append("read")
        retiring.append(1)          # its own ``np.asarray`` is the same read
        try:
            return device_get(x)
        finally:
            retiring.pop()

    def noted_asarray(x, *args, **kw):
        if isinstance(x, jax.Array) and not retiring:
            log.append("read")
        return asarray(x, *args, **kw)

    monkeypatch.setattr(jax, "device_get", noted_get)
    monkeypatch.setattr(np, "asarray", noted_asarray)

    def note_programs(attr, name):
        """``attr`` hands out a program, or (batch bucket, program)."""
        inner = getattr(eng, attr)

        def get(*args, **kw):
            out = inner(*args, **kw)
            if isinstance(out, tuple):
                return out[0], noting(name, out[1])
            return noting(name, out)
        monkeypatch.setattr(eng, attr, get)

    for attr, name in (("_prefill_for", "prefill"), ("_cont_for", "cont"),
                       ("_decode_for", "decode")):
        note_programs(attr, name)
    resolve = eng._resolve_first_tokens

    def noted_resolve():
        if eng._first:
            log.append("first_read")
        resolve()

    monkeypatch.setattr(eng, "_resolve_first_tokens", noted_resolve)
    return log


LONG = [1] + [7, 9, 11] * 23          # 70 tokens: chunks of 32, 32 and 6
ORDER_CASES = {
    # case: (chunk steps taken before the step under test, prompts that
    # arrive for it, the step's calls in order, its reason)
    "batch-admission": (
        None, [[8, 8, 9], [5, 6]],
        ["prefill", "sample", "retire", "marshal", "feed", "decode",
         "first_read", "read"],
        "admission"),
    "long-prompt-admission": (
        None, [LONG], ["prefill", "retire", "decode"], "admission"),
    "intermediate-chunk": (
        0, [], ["cont", "retire", "decode"], "chunking"),
    "final-chunk": (
        1, [],
        ["cont", "sample", "retire", "marshal", "feed", "decode",
         "first_read", "read"],
        "chunking"),
    "admission-beside-a-final-chunk": (
        1, [[8, 8, 9]],
        ["cont", "sample", "prefill", "sample", "retire", "marshal",
         "feed", "feed", "decode", "first_read", "read"],
        "admission"),
}


@pytest.mark.parametrize("case", sorted(ORDER_CASES))
def test_an_event_step_dispatches_before_it_reads(tiny_model, monkeypatch,
                                                  case):
    """With a lookahead in flight, an event step queues its prefill or
    continuation program and the sampler BEFORE step N is read, writes the
    sampler's output into the decode step's token input on the device
    (once a record) and dispatches the decode step BEFORE it reads the
    first tokens: between the sampler's dispatch and the decode's the host
    reads step N (``retire``) and nothing else. It counts itself in
    ``events_dispatched_ahead`` and, where first tokens waited, in
    ``first_token_events`` and ``first_token_events_fed``."""
    chunks_before, arrivals, want, reason = ORDER_CASES[case]
    eng = make_engine(tiny_model, True, monkeypatch, max_model_len=128)
    sp = SamplingParams(temperature=0.0, max_new_tokens=40)
    eng.add_request([3, 4, 5], sp)
    for _ in range(3):
        eng.step()
    if chunks_before is not None:
        eng.add_request(LONG, sp)
        for _ in range(1 + chunks_before):
            eng.step()
        assert eng.n_chunking == 1
    assert eng._pipe is not None, "no lookahead in flight"
    before = eng.obs.snapshot()
    for prompt in arrivals:
        eng.add_request(prompt, sp)
    log = record_order(eng, monkeypatch)
    eng.step()
    assert log == want
    # the reads lie behind every program and sampler of the admission
    last_queued = max(i for i, c in enumerate(log)
                      if c in ("prefill", "cont", "sample"))
    assert last_queued < log.index("retire")
    # no read of the host's but step N's stands before the decode dispatch
    assert "read" not in log[:log.index("decode")]
    snap = eng.obs.snapshot()
    met = 1 if "first_read" in log else 0
    assert log.count("feed") == log.count("sample")
    for key in ("first_token_events", "first_token_events_fed"):
        assert snap[key] == before[key] + met
    assert snap["events_dispatched_ahead"] \
        == before["events_dispatched_ahead"] + 1
    assert snap["ahead_by_reason"].get(reason, 0) \
        == before["ahead_by_reason"].get(reason, 0) + 1
    # a flush still happens, under the same reason; it no longer drains
    assert snap["flush_by_reason"].get(reason, 0) \
        == before["flush_by_reason"].get(reason, 0) + 1
    assert not eng._first and eng._pipe is not None
    assert all(s is None or s.prefill_cursor is not None
               or s.pending_token >= 0 for s in eng.slots)
    while eng.has_work:
        eng.step()
    assert pool_balanced(eng)


def _run_timed(eng, schedule, sp_of, kw_of=lambda i: {}):
    """``_run_schedule`` that also says WHEN: (finished by add index, the
    ``step()`` call each finished in, every streamed (add index, token) in
    callback order, the TTFT count behind each call)."""
    fins, fin_step, events, index_of, ttfts = {}, {}, [], {}, []
    step = 0
    while True:
        for prompt in schedule.get(step, ()):
            # ``(prompt, n)``: a fan-out group of n siblings (SHAI_KV_COW)
            prompt, n = prompt if isinstance(prompt, tuple) else (prompt, 1)
            parent = -2
            for _ in range(n):
                i = len(index_of)
                kw = dict(kw_of(i), parent_rid=parent) if n > 1 \
                    else kw_of(i)
                rid = eng.add_request(
                    prompt, sp_of(i),
                    on_token=lambda t, i=i: events.append((i, t)), **kw)
                index_of[rid] = i
                parent = rid if parent == -2 else parent
        if eng.has_work:
            for f in eng.step():
                fins[index_of[f.req_id]] = f
                fin_step[index_of[f.req_id]] = step
            ttfts.append(eng.ttft.count)
        step += 1
        if not eng.has_work and step > max(schedule, default=0):
            return fins, fin_step, events, ttfts


def _assert_same_timed(out_async, out_lock):
    (fa, sa, ea, ta), (fb, sb, eb, tb) = out_async, out_lock
    assert set(fa) == set(fb)
    for i in fa:
        assert_finished_equal(fa[i], fb[i])
    assert sa == sb, "a request finished on another step() call"
    assert ea == eb, "on_token order diverged"
    assert ta == tb, "a first token reached the host on another call"


def _first_token_of(tiny_model, monkeypatch, prompt):
    probe = make_engine(tiny_model, False, monkeypatch)
    [fin] = probe.generate([prompt], SamplingParams(temperature=0.0,
                                                    max_new_tokens=2))
    return fin.token_ids[0]


JOIN = {0: [[3, 4, 5]], 4: [[8, 8, 9], [5, 6]], 7: [LONG], 9: [[42, 43]]}
SEAM_CASES = {
    "first-token-is-eos": dict(eos_of=[8, 8, 9]),
    "one-new-token": dict(sp=dict(max_new_tokens=1)),
    "seeded-topk": dict(sp=dict(temperature=0.9, top_k=5)),
    "seeded-topp": dict(sp=dict(temperature=0.7, top_p=0.8)),
    "logprobs": dict(sp=dict(logprobs=3)),
    "preempted-in-the-step-that-admits": dict(
        schedule={0: [[11, 7, 7, 7], [12, 7, 7, 7]], 4: [[13] + [9] * 9]},
        sp=dict(max_new_tokens=14), over=dict(num_blocks=6), preempts=True),
    # ``records``: the rows of each ``FirstTokens`` record that the widest
    # event step's decode dispatch met still on the device
    "two-records-in-one-step": dict(     # a final chunk's, then a batch's
        schedule={0: [[3, 4, 5]], 3: [LONG], 5: [[42, 43], [8, 8, 9]]},
        over=dict(max_num_seqs=4), records=[1, 2]),
    "fan-out-of-three": dict(
        schedule={0: [[3, 4, 5]], 4: [([8, 8, 9], 3)]}, cow=True,
        sp=dict(temperature=0.9, top_k=5), over=dict(max_num_seqs=4),
        records=[3]),
    "batch-of-four-one-ends-at-its-first-token": dict(
        schedule={0: [[3, 4, 5]], 4: [[8, 8, 9], [5, 6], [7, 7, 7], [9, 1]]},
        sp_of={3: dict(max_new_tokens=1)}, over=dict(max_num_seqs=5),
        records=[4]),
    "recurrent-state": dict(
        model="tiny_ssm",
        schedule={0: [[3, 4, 5, 6]], 4: [[8, 8, 9], [5, 6, 7, 7, 2]],
                  6: [[1] + [7, 9] * 20]},       # 41 tokens: two programs
        sp=dict(max_new_tokens=7), records=[2]),
}


def _model_of(tiny_model, spec):
    if "model" not in spec:
        return tiny_model
    from scalable_hw_agnostic_inference_tpu.models.llama import (
        geometry_params,
    )

    cfg = getattr(LlamaConfig, spec["model"])()
    return cfg, geometry_params(cfg, dtype=jnp.float32, seed=3)


@pytest.mark.parametrize("case", sorted(SEAM_CASES))
def test_unresolved_first_tokens_match_lockstep(tiny_model, monkeypatch,
                                                case):
    """Rows joining behind a lookahead in flight, where the first token
    decides something at once: tokens, logprobs, finish calls, stream order
    and the call each TTFT lands in are the lock-step oracle's."""
    spec = SEAM_CASES[case]
    knobs = dict(temperature=0.0, max_new_tokens=9)
    knobs.update(spec.get("sp", {}))
    if "eos_of" in spec:
        knobs["eos_id"] = _first_token_of(tiny_model, monkeypatch,
                                          spec["eos_of"])
    over = dict(max_model_len=128)
    over.update(spec.get("over", {}))
    monkeypatch.setenv("SHAI_KV_COW", "1" if spec.get("cow") else "0")
    model = _model_of(tiny_model, spec)
    out, snaps = {}, {}
    for mode in (True, False):
        eng = make_engine(model, mode, monkeypatch, **over)
        preempt, unresolved = eng._preempt_lowest, []
        monkeypatch.setattr(
            eng, "_preempt_lowest",
            lambda: (unresolved.append(bool(eng._first)), preempt())[1])
        dispatch, met = eng._decode_dispatch, []
        monkeypatch.setattr(
            eng, "_decode_dispatch",
            lambda: (met.append([len(r.rows) for r in eng._first]),
                     dispatch())[1])
        out[mode] = _run_timed(
            eng, spec.get("schedule", JOIN),
            lambda i: SamplingParams(
                **dict(knobs, **spec.get("sp_of", {}).get(i, {}))))
        if mode and spec.get("preempts"):
            # the victim was the row this step admitted, token unread
            assert any(unresolved)
        snaps[mode] = eng.obs.snapshot()
        assert pool_balanced(eng) and not eng._first
        if mode and "records" in spec:
            assert max(met, key=lambda m: (len(m), sum(m))) \
                == spec["records"]
        # every dispatch that met first tokens fed them on the device,
        # but where a preemption read them on the way; the oracle reads
        # where it samples and meets none
        events = sum(1 for m in met if m)
        assert snaps[mode]["first_token_events"] == events
        assert events - sum(unresolved) \
            <= snaps[mode]["first_token_events_fed"] <= events
    _assert_same_timed(out[True], out[False])
    assert snaps[True]["events_dispatched_ahead"] >= 1
    assert snaps[False]["events_dispatched_ahead"] == 0
    assert snaps[True]["first_token_events_fed"] >= 1
    if not spec.get("preempts"):
        assert snaps[True]["first_token_events_fed"] \
            == snaps[True]["first_token_events"]
    if "sp_of" in spec:
        (i, _), = spec["sp_of"].items()
        assert len(out[True][0][i].token_ids) == 1
    if "eos_of" in spec:
        assert out[True][0][1].stop_reason == "eos"
        assert out[True][0][1].token_ids == []
    if spec.get("preempts"):
        assert snaps[True]["preemptions"] == snaps[False]["preemptions"] > 0


@pytest.mark.parametrize("leave", ["cancel", "deadline"])
def test_a_row_leaves_with_its_first_token_unresolved(tiny_model,
                                                      monkeypatch, leave):
    """No step ends with a first token still on the device, so the state
    is made by hand (the admission ladder alone, behind a lookahead in
    flight): the teardown reads the token back first, so its TTFT counts
    as the oracle's does, nothing is emitted, and the blocks are
    conserved."""
    sp = SamplingParams(temperature=0.0, max_new_tokens=12)
    out = {}
    for mode in (True, False):
        eng = make_engine(tiny_model, mode, monkeypatch)
        keep = eng.add_request([3, 4, 5], sp)
        for _ in range(3):
            eng.step()
        rid = eng.add_request(
            [8, 8, 9], sp,
            deadline_at=(time.monotonic() + 0.05 if leave == "deadline"
                         else 0.0))
        eng._admit_phase()
        assert bool(eng._first) is mode
        assert (eng._pipe is not None) is mode
        n_ttft = eng.ttft.count
        if leave == "cancel":
            fins = {rid: eng.cancel(rid)}
        else:
            time.sleep(0.06)
            fins = {f.req_id: f for f in eng.step()}
        assert not eng._first
        assert eng.ttft.count == n_ttft + (1 if mode else 0) == 2
        while eng.has_work:
            for f in eng.step():
                fins[f.req_id] = f
        out[mode] = fins
        assert fins[rid].stop_reason == ("cancelled" if leave == "cancel"
                                         else "timeout")
        assert fins[rid].token_ids == []
        assert pool_balanced(eng)
    for rid in out[True]:
        assert_finished_equal(out[True][rid], out[False][rid])
    assert len(out[True][keep].token_ids) == 12


def test_an_admission_behind_a_lookahead_on_recurrent_state(monkeypatch):
    """The same order over a slot arena (the ``tiny-ssm`` stand-in): the
    admitted rows are seated WITH their arena slots while the step in
    flight still steps the others'; tokens and finish calls are the
    oracle's, blocks and slots balance."""
    from scalable_hw_agnostic_inference_tpu.models.llama import (
        geometry_params,
    )

    cfg = LlamaConfig.tiny_ssm()
    model = (cfg, geometry_params(cfg, dtype=jnp.float32, seed=3))
    schedule = {0: [[3, 4, 5, 6]], 4: [[8, 8, 9], [5, 6, 7, 7, 2]],
                6: [[1] + [7, 9] * 20]}       # 41 tokens: two programs
    out = {}
    for mode in (True, False):
        eng = make_engine(model, mode, monkeypatch, max_model_len=128)
        out[mode] = _run_timed(
            eng, schedule,
            lambda i: SamplingParams(temperature=0.0, max_new_tokens=7))
        snap = eng.obs.snapshot()
        assert pool_balanced(eng) and not eng._first
        assert eng.cache.slots_live == 0 and eng.cache.leaked_bytes == 0
        if mode:
            assert snap["ahead_by_reason"].get("admission", 0) >= 2
            assert snap["ahead_by_reason"].get("chunking", 0) >= 1
    _assert_same_timed(out[True], out[False])
