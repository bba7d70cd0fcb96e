"""Observability subsystem: span trees, traceparent propagation, engine
step telemetry, and the flight recorder — across the streaming,
speculative, preemption, and multihost-mirror paths (ISSUE 3 acceptance:
every dumped trace must be well-formed — single root, no orphan/unclosed
spans — and tracing must be off the hot path when disabled)."""

import queue
import threading
import time

import httpx
import pytest

import jax  # noqa: F401  (platform pinned in conftest before backends init)

from scalable_hw_agnostic_inference_tpu.obs import (
    BucketHistogram,
    FlightRecorder,
    StepTelemetry,
)
from scalable_hw_agnostic_inference_tpu.obs import trace as obs_trace
from scalable_hw_agnostic_inference_tpu.obs.trace import (
    Trace,
    well_formed_problems,
)

from test_engine import make_engine, tiny_model  # noqa: F401 (fixture)
from test_engine_async import SpanLog
from test_serve_http import EchoService, make_cfg, make_client, wait_ready


# ---------------------------------------------------------------------------
# trace primitives
# ---------------------------------------------------------------------------

def test_traceparent_parse_and_format():
    tid, sid = "ab" * 16, "cd" * 8
    hdr = obs_trace.format_traceparent(tid, sid)
    assert obs_trace.parse_traceparent(hdr) == (tid, sid)
    assert obs_trace.parse_traceparent(None) is None
    assert obs_trace.parse_traceparent("garbage") is None
    assert obs_trace.parse_traceparent("00-" + "0" * 32 + "-" + sid + "-01") \
        is None  # all-zero trace id is invalid per spec
    assert obs_trace.parse_traceparent(f"00-{tid}-{'0' * 16}-01") is None


def test_span_nesting_builds_tree_via_contextvars():
    tr = obs_trace.Trace("root-op")
    with obs_trace.use_trace(tr):
        with obs_trace.span("outer") as outer:
            with obs_trace.span("inner", k=1) as inner:
                pass
    tr.close()
    d = tr.to_dict()
    assert not well_formed_problems(d), well_formed_problems(d)
    by_name = {s["name"]: s for s in d["spans"]}
    assert by_name["outer"]["parent_id"] == by_name["root-op"]["span_id"]
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["inner"]["attrs"]["k"] == 1
    assert inner.span.closed and outer.span.closed


def test_add_span_from_other_thread_is_safe():
    tr = obs_trace.Trace("op")
    t0 = time.monotonic()

    def engine_side():
        tr.add_span("decode", t0, t0 + 0.01, phase=True)

    t = threading.Thread(target=engine_side)
    t.start()
    t.join()
    tr.close()
    d = tr.to_dict()
    assert not well_formed_problems(d)
    decode = next(s for s in d["spans"] if s["name"] == "decode")
    assert decode["parent_id"] == tr.root.span_id
    assert decode["duration_s"] == pytest.approx(0.01, abs=1e-3)


def test_well_formed_detects_orphans_unclosed_and_multiroot():
    assert well_formed_problems({"spans": []})
    # orphan parent
    bad = {"spans": [
        {"name": "r", "span_id": "a", "parent_id": None, "duration_s": 0.1},
        {"name": "x", "span_id": "b", "parent_id": "zz", "duration_s": 0.1},
    ]}
    assert any("orphan" in p for p in well_formed_problems(bad))
    # unclosed
    bad = {"spans": [
        {"name": "r", "span_id": "a", "parent_id": None, "duration_s": -1.0},
    ]}
    assert any("unclosed" in p for p in well_formed_problems(bad))
    # two roots
    bad = {"spans": [
        {"name": "r", "span_id": "a", "parent_id": None, "duration_s": 0.1},
        {"name": "q", "span_id": "b", "parent_id": None, "duration_s": 0.1},
    ]}
    assert any("one root" in p for p in well_formed_problems(bad))
    # a crashed handler's span is force-closed by Trace.close AND reported
    tr = obs_trace.Trace("op")
    live = tr.span("leaky")
    live.__enter__()  # never exited
    tr.close()
    assert any("force-closed" in p
               for p in well_formed_problems(tr.to_dict()))


def test_span_tree_fuzz_always_well_formed():
    """Randomized span workloads — nested context spans, handler
    exceptions mid-span, concurrent engine-side add_span from worker
    threads, random phase grafts — must ALWAYS dump a well-formed tree
    (single root, no orphans, no unclosed spans)."""
    import random

    rng = random.Random(1337)
    for trial in range(30):
        tr = obs_trace.Trace(f"op-{trial}")

        def nested(depth: int) -> None:
            if depth <= 0 or rng.random() < 0.3:
                return
            try:
                with obs_trace.span(f"d{depth}-{rng.randrange(4)}"):
                    if rng.random() < 0.2:
                        raise ValueError("handler blew up mid-span")
                    nested(depth - 1)
            except ValueError:
                pass  # the span context must still have closed itself

        def engine_side() -> None:
            t0 = time.monotonic()
            for i in range(rng.randrange(1, 4)):
                tr.add_span(f"phase{i}", t0, t0 + rng.random() * 0.01)
            if rng.random() < 0.5:
                tr.add_phase_spans({"t_submit": t0, "t_admit": t0 + 0.001,
                                    "t_first": t0 + 0.002,
                                    "t_done": t0 + 0.003})

        with obs_trace.use_trace(tr):
            threads = [threading.Thread(target=engine_side)
                       for _ in range(rng.randrange(0, 3))]
            for t in threads:
                t.start()
            nested(rng.randrange(1, 6))
            for t in threads:
                t.join()
        tr.close()
        d = tr.to_dict()
        assert not well_formed_problems(d), (trial, well_formed_problems(d))


def test_tracing_disabled_is_off_the_hot_path():
    obs_trace.configure(False)
    try:
        assert obs_trace.begin_request_trace("x") is None
        s = obs_trace.span("y")
        assert s is obs_trace.NOOP  # shared constant: zero allocation
        with s:
            pass
        assert obs_trace.annotate("z") is obs_trace.NOOP
        # and with no active trace (tracing on), span() is STILL the noop
        obs_trace.configure(True)
        assert obs_trace.current_trace() is None
        assert obs_trace.span("y") is obs_trace.NOOP
    finally:
        obs_trace.configure(True)


# ---------------------------------------------------------------------------
# step telemetry + flight recorder primitives
# ---------------------------------------------------------------------------

def test_bucket_histogram_cumulative_shape():
    h = BucketHistogram((0.1, 1.0))
    for v in (0.05, 0.5, 0.7, 5.0):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 4
    assert s["sum"] == pytest.approx(6.25)
    assert s["buckets"] == [(0.1, 1), (1.0, 3), ("+Inf", 4)]


def test_step_telemetry_ring_is_bounded():
    t = StepTelemetry(total_blocks=10, max_steps=4)
    for i in range(9):
        t.record_step(kind="decode", duration_s=0.01, n_running=1,
                      n_waiting=i, n_chunking=0, blocks_free=5)
    recs = t.recent_steps()
    assert len(recs) == 4
    assert recs[-1]["step"] == 9 and recs[-1]["waiting"] == 8
    assert recs[-1]["kv_utilization"] == 0.5
    snap = t.snapshot()
    assert snap["steps"] == 9 and snap["waiting"] == 8.0
    t.count_preemption()
    t.count_recompile()
    snap = t.snapshot()
    assert snap["preemptions"] == 1 and snap["recompiles"] == 1


def test_phases_are_flat_and_tile_the_time(monkeypatch):
    from scalable_hw_agnostic_inference_tpu.obs import steploop

    log = SpanLog()
    monkeypatch.setattr(steploop, "annotate", log)
    t = StepTelemetry()
    t0 = time.monotonic()
    assert t.phase_enter("loop.intake") is None
    time.sleep(0.01)
    with t.phase("loop.idle"):      # interrupts intake, which resumes after
        time.sleep(0.01)
        mid = t.snapshot()["phase_s"]  # the open phase's seconds so far
    time.sleep(0.01)
    assert t.phase_enter(None) == "loop.intake"
    elapsed = time.monotonic() - t0
    secs = t.snapshot()["phase_s"]
    assert log.names == ["loop.intake", "loop.idle", "loop.intake"]
    assert log.open is None
    assert mid["loop.idle"] > 0 and mid["loop.intake"] > 0
    assert secs["loop.intake"] > secs["loop.idle"] > 0
    assert all(v == 0 for k, v in secs.items()
               if k not in ("loop.intake", "loop.idle"))
    # the phases tile the time from the first enter to the close: no less
    # than the three sleeps, no more than the clock reads around them
    assert 0.03 <= sum(secs.values()) <= elapsed
    # closed: nothing runs on, a later reading is the same
    assert t.snapshot()["phase_s"] == secs


def test_phases_count_seconds_with_tracing_off(monkeypatch):
    """``SHAI_TRACE=0``: the phases still account for the time, and no
    profiler annotation is made."""
    def no_annotation(name, **meta):
        raise AssertionError(f"annotation {name} with tracing off")

    monkeypatch.setattr(obs_trace, "_annotation", no_annotation)
    obs_trace.configure(False)
    try:
        t = StepTelemetry()
        t.begin_step(0)
        time.sleep(0.005)
        with t.phase("engine.decode"):
            time.sleep(0.005)
        t.phase_enter(None)
    finally:
        obs_trace.configure(True)
    secs = t.snapshot()["phase_s"]
    assert secs["engine.admit"] > 0 and secs["engine.decode"] > 0


def test_step_record_carries_its_phases_and_the_queue_at_entry(monkeypatch):
    from scalable_hw_agnostic_inference_tpu.obs import steploop

    log = SpanLog()
    monkeypatch.setattr(steploop, "annotate", log)
    t = StepTelemetry(total_blocks=10)
    assert t.begin_step(n_waiting=3) is None      # opens engine.admit
    t0 = t.phase_t0
    with t.phase("engine.prefill"):
        time.sleep(0.002)
    t.phase_enter("engine.marshal")
    with t.phase("engine.decode"):
        time.sleep(0.002)
    t.phase_enter("engine.commit")
    t.phase_enter("engine.record")
    t.record_step(kind="decode", duration_s=t.phase_t0 - t0, n_running=1,
                  n_waiting=2, n_chunking=0, blocks_free=5)
    t.phase_enter("loop.resolve")
    t.phase_enter(None)
    rec = t.recent_steps()[-1]
    assert rec["waiting_peak"] == 3 and rec["waiting"] == 2
    fields = ("admit_ms", "marshal_ms", "dispatch_ms", "commit_ms",
              "fetch_ms", "apply_ms")
    assert rec["dispatch_ms"] > rec["marshal_ms"] >= 0   # both dispatches
    assert rec["record_ms"] > 0      # set when engine.record closed
    # the phases up to the record tile the step's duration (rounding apart)
    assert sum(rec[f] for f in fields) == pytest.approx(
        rec["duration_s"] * 1e3, abs=0.01)
    # engine phases carry the step's number, loop phases none
    assert all(m == ({"step": 1} if n.startswith("engine.") else {})
               for n, m in zip(log.names, log.meta))
    assert "loop.resolve" in log.names


def test_a_steps_flush_is_counted_with_the_step_and_never_ahead_of_it():
    """``pipeline_flushes`` and ``steps`` move together: a snapshot taken
    between a step's flush and its record reads no flush without its step,
    so a window in which every step flushes reads a share of 100, not
    101."""
    t = StepTelemetry()
    for _ in range(3):
        t.begin_step(0)
        t.count_flush("admission")
        mid = t.snapshot()
        assert mid["pipeline_flushes"] == mid["steps"]
        t.record_step(kind="decode", duration_s=0.0, n_running=1,
                      n_waiting=0, n_chunking=0, blocks_free=0)
        t.phase_enter(None)
        snap = t.snapshot()
        assert snap["pipeline_flushes"] == snap["steps"]
    t.count_flush("idle")           # between steps: counted at once
    snap = t.snapshot()
    assert snap["flush_by_reason"] == {"admission": 3, "idle": 1}
    assert snap["pipeline_flushes"] == 4 and snap["steps"] == 3


def test_counters_by_reason_and_phase_ride_the_snapshot():
    t = StepTelemetry()
    t.count_flush("admission")
    t.count_flush("admission")
    t.count_flush("idle")
    t.count_pad(10, 6, phase="prefill")
    t.count_pad(8, 0, phase="decode")
    t.count_pad(8, 8, phase="decode")
    snap = t.snapshot()
    assert snap["flush_by_reason"] == {"admission": 2, "idle": 1}
    assert snap["pipeline_flushes"] == 3
    assert snap["dispatches_by_phase"] == {"prefill": 1, "decode": 2}
    assert snap["pad_by_phase"]["decode"] == {"real": 16, "pad": 8}
    assert set(snap["phase_s"]) >= {"loop.idle", "engine.fetch",
                                    "engine.record", "loop.resolve"}
    assert "intake_wait_seconds" in t.histograms()
    assert snap["intake_wait_count"] == 0


def test_events_dispatched_ahead_ride_the_snapshot_by_reason():
    t = StepTelemetry()
    assert t.snapshot()["events_dispatched_ahead"] == 0
    t.count_ahead("admission")
    t.count_ahead("admission")
    t.count_ahead("chunking")
    snap = t.snapshot()
    assert snap["events_dispatched_ahead"] == 3
    assert snap["ahead_by_reason"] == {"admission": 2, "chunking": 1}
    assert snap["pipeline_flushes"] == 0      # a flush is counted apart


def test_first_token_events_ride_the_snapshot():
    t = StepTelemetry()
    snap = t.snapshot()
    assert snap["first_token_events"] == snap["first_token_events_fed"] == 0
    t.count_first_tokens(fed=True)
    t.count_first_tokens(fed=False)  # met, and read before the dispatch
    snap = t.snapshot()
    assert snap["first_token_events"] == 2
    assert snap["first_token_events_fed"] == 1
    assert snap["events_dispatched_ahead"] == 0      # counted apart


def test_first_token_events_are_all_fed_with_no_drafter(tiny_model):
    """A run of admissions, batches and a long prompt's final chunk among
    them, with no drafter and no preemption: every decode dispatch that
    met first tokens on the device went out before their read."""
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        SamplingParams,
    )

    eng = make_engine(tiny_model)
    assert eng._async and eng._drafter is None
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    prompts = [[3, 4, 5], [8, 8, 9], [5, 6], [1] + [7, 9, 11] * 13, [4, 2]]
    arrivals = {0: prompts[:1], 2: prompts[1:3], 4: prompts[3:], 9: [[7]]}
    step = 0
    while eng.has_work or step <= max(arrivals):
        for prompt in arrivals.get(step, ()):
            eng.add_request(prompt, sp)
        if eng.has_work:
            eng.step()
        step += 1
    snap = eng.obs.snapshot()
    assert snap["preemptions"] == 0
    assert snap["requests_finished"] == 6
    assert snap["first_token_events_fed"] == snap["first_token_events"] >= 4


def _gap(eng):
    snap = eng.obs.step_gap.snapshot()
    return snap["count"], snap["sum"]


@pytest.mark.parametrize("queued", [True, False],
                         ids=["program-still-queued", "device-drained"])
def test_step_gap_of_an_event_step(tiny_model, monkeypatch, queued):
    """``step_gap`` is how long the device had nothing queued before a
    decode dispatch. An event step feeds the first tokens on the device
    and dispatches before it reads them: the gap is nothing where the
    dispatch found a program of the step still running, and otherwise no
    longer than the time since the last blocking read returned, which is
    the flush's of step N now that the first tokens' lies behind the
    dispatch. Such a step counts itself met and fed."""
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        SamplingParams,
    )

    eng = make_engine(tiny_model)
    assert eng._async
    sp = SamplingParams(temperature=0.0, max_new_tokens=30)
    # a step that builds a program observes no gap: build them first
    eng.generate([[3, 4, 5], [8, 8, 9]],
                 SamplingParams(temperature=0.0, max_new_tokens=2))
    eng.finish_pending()
    eng.add_request([3, 4, 5], sp)
    for _ in range(3):
        eng.step()
    assert eng._pipe is not None
    stamps = {}
    retire, resolve = eng._retire_pipe, eng._resolve_first_tokens
    decode_for = eng._decode_for

    def stamped_retire(pipe):
        stamps["retired"] = t = retire(pipe)
        return t

    def stamped_resolve():
        pending = bool(eng._first)
        resolve()
        if pending:
            stamps["first_read"] = eng._t_fetch

    def stamped_decode_for(*a, **kw):
        bb, fn = decode_for(*a, **kw)

        def run(*args):
            stamps["dispatch"] = time.monotonic()
            return fn(*args)
        return bb, run

    monkeypatch.setattr(eng, "_retire_pipe", stamped_retire)
    monkeypatch.setattr(eng, "_resolve_first_tokens", stamped_resolve)
    monkeypatch.setattr(eng, "_decode_for", stamped_decode_for)
    if queued:
        monkeypatch.setattr(eng, "_program_queued", lambda: True)
    eng.add_request([8, 8, 9], sp)
    n0, sum0 = _gap(eng)
    before = eng.obs.snapshot()
    eng.step()                       # admits behind the lookahead
    n1, sum1 = _gap(eng)
    assert n1 == n0 + 1
    assert stamps["retired"] < stamps["dispatch"] <= stamps["first_read"]
    if queued:
        assert sum1 == sum0
    else:
        assert 0.0 <= sum1 - sum0 <= (stamps["dispatch"]
                                      - stamps["retired"]) + 1e-9
    snap = eng.obs.snapshot()
    for key in ("first_token_events", "first_token_events_fed"):
        assert snap[key] == before[key] + 1
    while eng.has_work:
        eng.step()


def test_phase_spans_put_intake_before_queue():
    tr = Trace("req")
    now = time.monotonic()
    tr.add_phase_spans({"t_enqueue": now - 0.5, "t_submit": now - 0.4,
                        "t_admit": now - 0.3, "t_first": now - 0.2,
                        "t_done": now - 0.1})
    tr.close()
    d = tr.to_dict()
    assert not well_formed_problems(d)
    spans = {s["name"]: s for s in d["spans"]}
    names = [s["name"] for s in d["spans"]]
    assert names.index("intake") < names.index("queue")
    assert spans["intake"]["t_start"] + spans["intake"]["duration_s"] == \
        pytest.approx(spans["queue"]["t_start"], abs=1e-4)
    # a direct add_request stamps both at once: no intake span
    tr2 = Trace("req")
    tr2.add_phase_spans({"t_enqueue": now - 0.4, "t_submit": now - 0.4,
                         "t_admit": now - 0.3, "t_first": now - 0.2,
                         "t_done": now - 0.1})
    assert "intake" not in [s.name for s in tr2.spans]


def test_flight_recorder_ring_and_dump():
    fr = FlightRecorder(max_requests=3, max_steps=2)
    for i in range(5):
        fr.record_request({"trace_id": f"t{i}", "spans": []})
    d = fr.dump(step_source=lambda n: [{"step": 1}][:n])
    assert d["recorded_total"] == 5
    assert [r["trace"]["trace_id"] for r in d["requests"]] == \
        ["t2", "t3", "t4"]
    # the trace id rides at the record's top level (the trace-join key)
    assert [r["trace_id"] for r in d["requests"]] == ["t2", "t3", "t4"]
    assert d["engine_steps"] == [{"step": 1}]

    def boom(n):
        raise RuntimeError("engine gone")

    d = fr.dump(step_source=boom)
    assert "engine gone" in d["engine_steps_error"]
    assert d["requests"]  # the request ring still dumps
    # n_requests edge cases: 0 means zero (reqs[-0:] would be ALL), and
    # asking past the ring returns what exists
    assert fr.dump(n_requests=0)["requests"] == []
    assert len(fr.dump(n_requests=99)["requests"]) == 3


# ---------------------------------------------------------------------------
# engine integration: speculative + preemption paths
# ---------------------------------------------------------------------------

def test_spec_engine_emits_timing_and_step_records(tiny_model):
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        SamplingParams,
    )

    eng = make_engine(tiny_model, speculative_model="[ngram]",
                      num_speculative_tokens=3)
    base = [1, 5, 9, 11, 7, 3, 2, 8]
    prompt = (base * 3)[:20]  # repetitive: the n-gram drafter fires
    fins = eng.generate([prompt, prompt],
                        SamplingParams(temperature=0.0, max_new_tokens=10))
    assert all(f.stop_reason == "length" for f in fins)
    for f in fins:
        t = f.timing
        assert t is not None
        assert t["queue_s"] >= 0 and t["prefill_s"] >= 0
        assert t["decode_s"] >= 0
        assert t["total_s"] == pytest.approx(
            t["t_done"] - t["t_submit"], abs=1e-4)
    recs = eng.obs.recent_steps()
    assert recs, "no step records"
    kinds = {r["kind"] for r in recs}
    assert "spec" in kinds, kinds  # the speculative path actually ran
    assert any("spec" in r for r in recs)  # spec counters ride the records
    snap = eng.obs.snapshot()
    assert snap["steps"] == len(recs) == eng._step_count
    assert snap["ttft_count"] == 2 and snap["queue_wait_count"] == 2
    assert snap["spec_acceptance_rate"] >= 0.0


@pytest.mark.slow  # tier-1 preemption coverage: test_engine.py pressure
# test + test_engine_async.py differential (PR 6 budget trade)
def test_preemption_path_counts_and_keeps_timing(tiny_model):
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        SamplingParams,
    )

    # 3 seqs x 3 blocks each at full length = 9 > the 6 usable blocks:
    # growth MUST preempt at least once before all three finish
    eng = make_engine(tiny_model, num_blocks=7)
    prompts = [[1, 5, 9, 11], [1, 200, 300], [2, 7, 9, 13, 15]]
    fins = eng.generate(prompts, SamplingParams(temperature=0.0,
                                                max_new_tokens=16))
    assert [f.stop_reason for f in fins] == ["length"] * 3
    assert all(len(f.token_ids) == 16 for f in fins)
    assert eng.obs.preemptions >= 1
    assert eng.obs.recent_steps()[-1]["preemptions_total"] == \
        eng.obs.preemptions
    for f in fins:  # preempted-and-resumed requests keep ONE timeline
        assert f.timing is not None
        assert f.timing["t_done"] >= f.timing["t_first"] >= \
            f.timing["t_admit"] >= f.timing["t_submit"]


def test_resumed_request_timing_uses_original_first_token(tiny_model):
    """A preemption resume carries the request-level t_first: the timeline
    must book the pre-preemption decode segment (and the re-queue wait)
    under decode_s, not prefill_s — the slot-level t_first passed by the
    finish sites is the RESUMED segment's and would do exactly that."""
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        Request,
        SamplingParams,
    )

    eng = make_engine(tiny_model)
    now = time.monotonic()
    req = Request(0, [1, 2, 3], SamplingParams(max_new_tokens=4),
                  already_generated=[5, 6],  # marks a resume
                  t_submit=now - 10.0, t_admit=now - 9.5, t_first=now - 9.0)
    t = eng._timing_of(req, t_first=now - 1.0)  # resumed segment's stamp
    assert t["t_first"] == req.t_first
    assert t["prefill_s"] == pytest.approx(0.5, abs=0.1)
    assert t["decode_s"] >= 8.9  # segment 1 + re-queue + segment 2


def test_rejected_request_books_wait_as_queue_not_decode(tiny_model):
    """A request finished straight from the waiting queue (never admitted)
    spent its whole life in queue_s — missing stamps must fall FORWARD,
    not book the wait into a decode phase that never ran."""
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        SamplingParams,
    )

    # pool of 4 blocks (3 usable) but a 32-token prompt needs 4 blocks
    eng = make_engine(tiny_model, num_blocks=4, max_num_seqs=1)
    [fin] = eng.generate([[1] * 32], SamplingParams(max_new_tokens=4))
    assert fin.stop_reason == "rejected"
    t = fin.timing
    assert t is not None
    assert t["prefill_s"] == 0.0 and t["decode_s"] == 0.0
    assert t["queue_s"] == pytest.approx(t["total_s"], abs=1e-4)


def test_post_warm_executable_build_counts_as_recompile(tiny_model):
    eng = make_engine(tiny_model)
    eng._decode_for(1)
    assert eng.obs.recompiles == 0  # pre-warm builds are the closed set
    eng._warmed = True
    eng._decode_for(2)
    eng._prefill_for(16, 0, 2)
    assert eng.obs.recompiles == 2


def test_cache_shrink_counts_rollback_tokens():
    import jax.numpy as jnp

    from scalable_hw_agnostic_inference_tpu.engine.cache import PagedKVCache

    c = PagedKVCache(1, {"k": (1, 4), "v": (1, 4)}, total_blocks=8,
                     block_size=4, blocks_per_seq=4, dtype=jnp.float32)
    c.admit(0, 10)  # 3 blocks
    c.extend(0, 4)  # reserve like a spec step would
    c.shrink(0, 3)  # reject 3 drafted tokens
    assert c.rollback_tokens == 3
    assert c.rollback_calls == 1
    c.shrink(0, 0)  # no-op shrink does not count
    assert c.rollback_calls == 1


# ---------------------------------------------------------------------------
# serving integration: vllm unit with speculative decoding
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def spec_app():
    """Tiny engine-backed service with speculative decoding on — ONE
    warmed service shared by every HTTP-level obs test in this module."""
    import dataclasses

    from scalable_hw_agnostic_inference_tpu.models.registry import get_model
    from scalable_hw_agnostic_inference_tpu.serve.app import create_app
    from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig

    cfg = ServeConfig(app="llm-obs", model_id="tiny", device="cpu",
                      max_new_tokens=16, vllm_config="/nonexistent.yaml")
    service = get_model("vllm")(cfg)
    # smallest closed executable set that still exercises every obs path
    # (2 slots batch the concurrent tests; serial prefill halves the warm
    # ladder — this fixture is the costliest compile in the obs suite)
    service.ecfg = dataclasses.replace(
        service.ecfg, speculative_model="[ngram]", num_speculative_tokens=3,
        max_num_seqs=2, max_prefill_batch=1)
    # the load-and-warm time as the app's lane sees it, for the start-up
    # phases to be held against
    load, warmup = service.load, service.warmup

    def timed_load():
        service.t_load0 = time.monotonic()
        load()

    def timed_warmup():
        warmup()
        service.t_warm1 = time.monotonic()

    service.load, service.warmup = timed_load, timed_warmup
    return cfg, service, create_app(cfg, service)


@pytest.mark.asyncio
async def test_startup_phases_are_on_stats_after_ready(spec_app):
    cfg, service, app = spec_app
    async with make_client(app) as c:
        await wait_ready(c, timeout=600.0)
        st = (await c.get("/stats")).json()
    startup = st["startup"]
    phases = {k: v for k, v in startup.items() if k != "total_s"}
    assert set(phases) == {"weights_s", "engine_s", "warm_executables_s",
                           "warmup_s", "other_s"}
    assert all(v >= 0 for v in phases.values())
    # every program of the closed set was compiled (or loaded) in there:
    # it is the longest phase of a boot
    assert phases["warm_executables_s"] == max(phases.values())
    assert sum(phases.values()) == pytest.approx(startup["total_s"],
                                                 abs=0.01)
    load_and_warm = service.t_warm1 - service.t_load0
    assert startup["total_s"] == pytest.approx(load_and_warm, rel=0.05)
    assert startup == service.startup


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.asyncio
async def test_http_traceparent_ingest_emit_and_flight(spec_app):
    cfg, service, app = spec_app
    upstream = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    async with make_client(app) as c:
        r = await wait_ready(c, timeout=600.0)
        assert r.status_code == 200, r.text
        r = await c.post("/generate",
                         json={"prompt": "to be or not to be or not",
                               "temperature": 0.0, "max_new_tokens": 6},
                         headers={"traceparent": upstream})
        assert r.status_code == 200, r.text
        # W3C emit: same trace id, OUR root span id
        tp = r.headers["traceparent"]
        assert tp.split("-")[1] == "ab" * 16
        assert tp.split("-")[2] != "cd" * 8

        r = await c.get("/debug/flight")
        d = r.json()
        traces = [q["trace"] for q in d["requests"]
                  if q["trace"]["name"] == "POST /generate"]
        assert traces, "generate request missing from the flight ring"
        tr = traces[-1]
        assert tr["trace_id"] == "ab" * 16
        assert tr["remote_parent"] == "cd" * 8
        assert not well_formed_problems(tr), well_formed_problems(tr)
        names = {s["name"] for s in tr["spans"]}
        # the full timeline: http root, model lane, tokenize/detokenize,
        # and the engine's queue/prefill/decode phase spans
        assert {"POST /generate", "model_infer", "tokenize", "queue",
                "prefill", "decode", "detokenize"} <= names
        # engine step records ride the same dump
        assert d["engine_steps"], "no engine step records"
        last = d["engine_steps"][-1]
        assert {"kind", "running", "waiting", "kv_utilization",
                "preemptions_total", "recompiles_total"} <= set(last)
        # probes are excluded from the ring (readiness polls above)
        assert all(q["trace"]["name"] != "GET /readiness"
                   for q in d["requests"])


@pytest.mark.asyncio
async def test_streaming_request_trace_is_well_formed(spec_app):
    cfg, service, app = spec_app
    async with make_client(app) as c:
        await wait_ready(c, timeout=600.0)
        async with c.stream(
                "POST", "/v1/completions",
                json={"prompt": "a b c a b c a b", "stream": True,
                      "temperature": 0.0, "max_tokens": 5}) as r:
            assert r.status_code == 200
            body = ""
            async for chunk in r.aiter_text():
                body += chunk
        assert "data: [DONE]" in body

        d = (await c.get("/debug/flight")).json()
        traces = [q["trace"] for q in d["requests"]
                  if q["trace"]["name"] == "POST /v1/completions"]
        assert traces, "streaming request missing from the flight ring"
        tr = traces[-1]
        assert not well_formed_problems(tr), well_formed_problems(tr)
        names = {s["name"] for s in tr["spans"]}
        assert {"queue", "prefill", "decode"} <= names
        # the root span covers the stream DRAIN, so it must be at least as
        # long as the engine's decode phase
        root = next(s for s in tr["spans"] if s["parent_id"] is None)
        decode = next(s for s in tr["spans"] if s["name"] == "decode")
        assert root["duration_s"] >= decode["duration_s"] - 0.05


@pytest.mark.asyncio
async def test_metrics_exposes_engine_histograms_and_gauges(spec_app):
    pytest.importorskip("prometheus_client")
    cfg, service, app = spec_app
    async with make_client(app) as c:
        await wait_ready(c, timeout=600.0)
        await c.post("/generate", json={"prompt": "x y z x y z",
                                        "temperature": 0.0,
                                        "max_new_tokens": 4})
        r = await c.get("/metrics")
        assert r.status_code == 200
        for name in ("shai_ttft_seconds_bucket", "shai_ttft_seconds_sum",
                     "shai_tpot_seconds_bucket",
                     "shai_queue_wait_seconds_bucket",
                     "shai_engine_running", "shai_engine_waiting",
                     "shai_engine_kv_utilization",
                     "shai_engine_preemptions_total",
                     "shai_engine_recompiles_total",
                     "shai_spec_acceptance_rate",
                     "shai_intake_wait_seconds_bucket",
                     'shai_engine_phase_seconds_total{app="llm-obs",'
                     'phase="engine.fetch"}',
                     'phase="loop.idle"}'):
            assert name in r.text, f"{name} missing from /metrics"
        # histogram actually observed something
        assert 'shai_ttft_seconds_count{app="llm-obs"}' in r.text

        st = (await c.get("/stats")).json()
        assert st["engine"]["steps"] > 0
        assert "kv_utilization" in st["engine"]
        # where the loop thread's time went, and the flushes by reason: one
        # place, the engine's snapshot
        assert st["engine"]["phase_s"]["loop.idle"] > 0
        assert st["engine"]["phase_s"]["engine.verify"] > 0
        assert (sum(st["engine"]["flush_by_reason"].values())
                == st["engine"]["pipeline_flushes"]
                >= st["service"]["pipeline_flushes"])
        assert not any(k.startswith("pipeline_flush_")
                       for k in st["service"])
        assert "exports" in st["aot"]


@pytest.mark.asyncio
async def test_events_dispatched_ahead_on_stats_and_metrics(spec_app):
    pytest.importorskip("prometheus_client")
    cfg, service, app = spec_app
    async with make_client(app) as c:
        await wait_ready(c, timeout=600.0)
        await c.post("/generate", json={"prompt": "p q r p q r",
                                        "temperature": 0.0,
                                        "max_new_tokens": 4})
        text = (await c.get("/metrics")).text
        eng = (await c.get("/stats")).json()["engine"]
    # beside the flushes, in both places, and by reason on /stats
    assert "shai_engine_pipeline_flushes_total" in text
    line = next(ln for ln in text.splitlines() if ln.startswith(
        'shai_engine_events_dispatched_ahead_total{app="llm-obs"}'))
    assert float(line.split()[-1]) >= eng["events_dispatched_ahead"] >= 0
    assert (sum(eng["ahead_by_reason"].values())
            == eng["events_dispatched_ahead"])
    assert "flush_by_reason" in eng


@pytest.mark.asyncio
async def test_first_token_events_on_stats_and_metrics(spec_app):
    """Both counters beside ``events_dispatched_ahead``, on ``/stats`` and
    in the Prometheus text. This app runs a drafter, which reads the
    pending token on the host before anything is dispatched: events are
    met, none is fed, and that is what it says."""
    pytest.importorskip("prometheus_client")
    cfg, service, app = spec_app
    async with make_client(app) as c:
        await wait_ready(c, timeout=600.0)
        await c.post("/generate", json={"prompt": "s t u s t u",
                                        "temperature": 0.0,
                                        "max_new_tokens": 4})
        text = (await c.get("/metrics")).text
        eng = (await c.get("/stats")).json()["engine"]

    def scraped(name):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(name + '_total{app="llm-obs"}'))
        return float(line.split()[-1])

    assert scraped("shai_engine_first_token_events") \
        >= eng["first_token_events"] >= 1
    assert scraped("shai_engine_first_token_events_fed") \
        == eng["first_token_events_fed"] == 0


@pytest.mark.asyncio
async def test_disabled_tracing_serves_without_traces(spec_app):
    cfg, service, app = spec_app
    async with make_client(app) as c:
        await wait_ready(c, timeout=600.0)
        before = (await c.get("/debug/flight")).json()["recorded_total"]
        obs_trace.configure(False)
        try:
            r = await c.post("/generate",
                             json={"prompt": "hello hello hello",
                                   "temperature": 0.0, "max_new_tokens": 4})
            assert r.status_code == 200, r.text
            assert "traceparent" not in r.headers
        finally:
            obs_trace.configure(True)
        after = (await c.get("/debug/flight")).json()["recorded_total"]
        assert after == before  # nothing recorded while disabled


# ---------------------------------------------------------------------------
# plain (engine-less) service still traces; multihost mirror propagation
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_engineless_service_traces_and_empty_steps():
    from scalable_hw_agnostic_inference_tpu.serve.app import create_app

    cfg = make_cfg()
    app = create_app(cfg, EchoService(cfg))
    async with make_client(app) as c:
        await wait_ready(c)
        r = await c.post("/predict", json={"text": "hi"})
        assert "traceparent" in r.headers
        await c.get("/stats")  # scrape surface: must stay out of the ring
        # unrouted traffic (scanner 404s) still gets a traceparent but must
        # not turn over the postmortem ring
        r = await c.get("/wp-login.php")
        assert r.status_code == 404 and "traceparent" in r.headers
        d = (await c.get("/debug/flight")).json()
        assert d["engine_steps"] == []  # no engine, no step feed
        assert all(q["trace"]["name"] != "GET /stats" for q in d["requests"])
        assert all("/wp-login" not in q["trace"]["name"]
                   for q in d["requests"])
        tr = [q["trace"] for q in d["requests"]
              if q["trace"]["name"] == "POST /predict"][-1]
        assert not well_formed_problems(tr)
        assert {"POST /predict", "model_infer"} <= \
            {s["name"] for s in tr["spans"]}


def test_mirror_rpc_propagates_traceparent(monkeypatch):
    """Leader → follower over a faked coordination channel: the follower's
    mirrored call runs under the LEADER's trace id, and the follower-side
    trace is well-formed."""
    from scalable_hw_agnostic_inference_tpu.serve import multihost

    chan: "queue.Queue[bytes]" = queue.Queue()

    def fake_broadcast(payload):
        if payload is not None:
            chan.put(payload)
            return payload
        return chan.get(timeout=30)

    monkeypatch.setattr(multihost, "_broadcast_bytes", fake_broadcast)

    class Svc:
        mirror_methods = ("infer",)

        def __init__(self):
            self.seen = []

        def infer(self, payload):
            tr = obs_trace.current_trace()
            self.seen.append((payload,
                              None if tr is None else tr.trace_id))
            return {"ok": True}

    leader_svc, follower_svc = Svc(), Svc()
    follower_traces = []
    leader = multihost.MultihostDriver(leader_svc)
    follower = multihost.MultihostDriver(
        follower_svc, trace_sink=follower_traces.append)
    leader.wrap_leader()
    t = threading.Thread(target=follower.follower_loop, daemon=True)
    t.start()

    tr = obs_trace.Trace("POST /generate")
    with obs_trace.use_trace(tr):
        leader_svc.infer({"prompt": "x"})
    leader_svc.infer({"prompt": "untraced"})  # no active trace: still works
    leader.shutdown()
    t.join(timeout=30)
    assert not t.is_alive()
    tr.close()

    assert [p["prompt"] for p, _ in follower_svc.seen] == ["x", "untraced"]
    assert follower_svc.seen[0][1] == tr.trace_id  # leader's id, propagated
    assert len(follower_traces) == 2
    assert follower_traces[0]["trace_id"] == tr.trace_id
    assert follower_traces[0]["remote_parent"] == tr.root.span_id
    assert follower_traces[1]["trace_id"] != tr.trace_id  # fresh trace
    for ft in follower_traces:
        assert not well_formed_problems(ft), well_formed_problems(ft)
