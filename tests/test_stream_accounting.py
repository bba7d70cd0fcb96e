"""A token's way out and a request's way in, counted where they happen
(``obs/steploop.py`` ``StreamTrack``, ``StepTelemetry.ingress_*``,
``phase_cpu_s``), on the CPU engine behind the real app.

The app is driven through raw ASGI (a ``receive`` and a ``send`` of the
test's own), so that a test decides when the client goes away and how slowly
the socket takes a chunk. The tiny model's byte tokenizer decodes almost no
id to text; the unit gets a tokenizer that renders every id as one letter, as
the benchmark gives it, so that every token yields an event.
"""

import asyncio
import json
import queue
import threading
import time

import pytest

from scalable_hw_agnostic_inference_tpu.models.registry import get_model
from scalable_hw_agnostic_inference_tpu.obs import trace as obs_trace
from scalable_hw_agnostic_inference_tpu.obs.steploop import (
    NON_WAITING_PHASES,
    PHASES,
    StepTelemetry,
)
from scalable_hw_agnostic_inference_tpu.serve import asgi
from scalable_hw_agnostic_inference_tpu.serve.app import create_app
from scalable_hw_agnostic_inference_tpu.serve.units import vllm as vllm_unit
from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig

from test_serve_http import drive_asgi as _drive, make_client, wait_ready

STREAM_HISTOGRAMS = ("stream_wake_seconds", "stream_encode_seconds",
                     "stream_write_seconds", "stream_deliver_seconds")


# -- the accounting alone: no engine, no server ------------------------------

def _put(track, tele, n, t_commit=100.0):
    tele.phase_t0 = t_commit
    for tok in range(n):
        track.put(tok)


def _send_one(track):
    tok, t_commit = track.q.get(timeout=1)
    track.took(t_commit)
    track.hand_on()
    track.sent(10)      # the event loop, behind the write
    track.wrote()       # the generator, resumed behind its yield


def test_a_whole_stream_conserves_its_tokens_and_leaves_nothing_behind():
    tele = StepTelemetry()
    track = tele.stream_open()
    _put(track, tele, 3)
    assert tele.stream_snapshot()["backlog"] == 3
    for _ in range(2):
        _send_one(track)
    # the third is taken and held back (a partial character): it goes with
    # the stream's end
    track.took(track.q.get(timeout=1)[1])
    track.resolved()
    assert tele.stream_snapshot()["draining"] == 1
    track.last()
    track.sent(14)
    track.close()                     # the generator's finally ends it, whole
    track.close()                     # and no second time
    s = tele.stream_snapshot()
    assert s["tokens_put"] == s["tokens_sent"] == 3
    assert s["tokens_dropped"] == s["backlog"] == s["draining"] == 0
    assert (s["streams_started"], s["streams_ended"],
            s["streams_aborted"]) == (1, 1, 0)
    assert s["events_sent"] == 2 and s["bytes_sent"] == 34
    h = tele.histograms()
    assert [h[k]["count"] for k in STREAM_HISTOGRAMS] == [2, 2, 2, 2]
    assert h["stream_finish_lag_seconds"]["count"] == 1


@pytest.mark.parametrize("resolve_first", [True, False],
                         ids=["cancel_lands_then_close",
                              "close_then_cancel_lands"])
def test_an_aborted_stream_drops_what_it_never_sent(resolve_first):
    """The remainder is dropped once BOTH the stream has ended and the
    future has resolved (the engine puts until the cancel lands), whichever
    comes last."""
    tele = StepTelemetry()
    track = tele.stream_open()
    _put(track, tele, 5)
    _send_one(track)
    if resolve_first:
        track.resolved()
        assert tele.stream_snapshot()["draining"] == 1
        track.close()
    else:
        track.close()
        assert tele.stream_snapshot()["tokens_dropped"] == 0
        _put(track, tele, 2)          # put behind the abort, before the cancel
        track.resolved()
    track.close()
    track.resolved()                  # neither counts twice
    s = tele.stream_snapshot()
    put = 5 if resolve_first else 7
    assert s["tokens_put"] == put and s["tokens_sent"] == 1
    assert s["tokens_dropped"] == put - 1
    assert s["backlog"] == s["draining"] == 0
    assert (s["streams_ended"], s["streams_aborted"]) == (0, 1)
    assert tele.histograms()["stream_finish_lag_seconds"]["count"] == 0


def test_a_stream_that_ends_before_its_callback_ran_counts_no_drain():
    """``Future.set_result`` wakes the waiter before it runs the callbacks:
    a stream can write its last byte first."""
    tele = StepTelemetry()
    track = tele.stream_open()
    _put(track, tele, 1)
    _send_one(track)
    track.last()
    track.sent(14)
    track.close()
    track.resolved()
    s = tele.stream_snapshot()
    assert s["draining"] == 0 and s["streams_ended"] == 1
    assert s["tokens_put"] == s["tokens_sent"] == 1


def test_the_hops_of_an_event_sum_to_its_delivery(monkeypatch):
    tele = StepTelemetry()
    seen = {k: [] for k in ("wake", "encode", "write", "deliver")}
    for k, rec in seen.items():
        monkeypatch.setattr(getattr(tele, "stream_" + k), "observe_locked",
                            rec.append)
    track = tele.stream_open()
    for i in range(4):
        _put(track, tele, 1, t_commit=time.monotonic() - 0.01 * i)
        time.sleep(0.002)
        _send_one(track)
    assert len(seen["deliver"]) == 4
    for w, e, x, d in zip(*seen.values()):
        assert w >= 0 and e >= 0 and x >= 0
        assert w + e + x == pytest.approx(d, abs=1e-9)


def _send_all(track, n_bytes=10):
    """A stream's turn: everything its queue holds leaves as one event.
    Returns the tokens it carried (the end mark is left to the caller)."""
    n = 0
    while not track.q.empty():
        tok, t = track.q.get_nowait()
        if tok is None:
            break
        track.took(t)
        n += 1
    track.hand_on()
    track.sent(n_bytes)
    track.wrote()
    return n


@pytest.mark.parametrize("k", [1, 3, 50])
def test_an_event_that_carries_k_tokens_counts_k_tokens_and_one_event(k):
    tele = StepTelemetry()
    track = tele.stream_open()
    _put(track, tele, k, t_commit=time.monotonic())
    assert tele.stream_snapshot()["backlog"] == k
    assert _send_all(track) == k
    s = tele.stream_snapshot()
    assert (s["tokens_put"], s["tokens_sent"], s["events_sent"]) == (k, k, 1)
    assert s["backlog"] == 0
    h = tele.histograms()
    assert [h[n]["count"] for n in STREAM_HISTOGRAMS] == [1, 1, 1, 1]


def test_the_end_mark_stands_behind_the_last_token_and_only_once():
    tele = StepTelemetry()
    track = tele.stream_open()
    _put(track, tele, 2)
    tele.phase_t0 = 123.0
    track.resolved()
    track.resolved()
    got = []
    while not track.q.empty():
        got.append(track.q.get_nowait())
    assert got == [(0, 100.0), (1, 100.0), (None, 123.0)]


class _CountingLoop:
    """What ``stream_flush`` needs of an event loop: it counts the calls."""

    def __init__(self):
        self.calls = []

    def call_soon_threadsafe(self, fn, *args):
        self.calls.append((fn, args))


@pytest.mark.parametrize("n_streams", [1, 8, 64])
def test_one_wake_up_a_step_whatever_the_number_of_streams(n_streams):
    """The engine-loop thread posts ONE ``call_soon_threadsafe`` as a step
    leaves ``engine.commit``, for all the streams the step touched; a step
    that touched none posts none; a put takes no lock and wakes nobody."""
    tele = StepTelemetry()
    loop = _CountingLoop()
    tracks = [tele.stream_open() for _ in range(n_streams)]
    for t in tracks:
        t._loop = loop                 # each is being drained on the loop
    for step in range(3):
        tele.begin_step(0)
        tele.phase_enter("engine.commit")
        for t in tracks:
            t.put(step)
            t.put(step)                # two tokens a stream (a verify step)
        assert loop.calls == []        # nothing leaves inside the commit
        tele.phase_enter("engine.record")
        assert len(loop.calls) == 1
        fn, (woken,) = loop.calls.pop()
        assert sorted(map(id, woken)) == sorted(map(id, tracks))
        tele.phase_enter("loop.resolve")
        assert loop.calls == []
    tele.begin_step(0)                 # a step that commits nothing
    tele.phase_enter("engine.commit")
    tele.phase_enter("engine.record")
    tele.phase_enter(None)
    assert loop.calls == []
    # the loop's own flush, where it resolved requests: again one call
    for t in tracks:
        t.resolved()
    tele.stream_flush()
    assert len(loop.calls) == 1
    tele.stream_flush()
    assert len(loop.calls) == 1        # nothing new: nothing posted


def test_a_stream_nobody_drains_yet_is_not_woken_and_finds_its_tokens():
    tele = StepTelemetry()
    track = tele.stream_open()
    _put(track, tele, 2)
    tele.stream_flush()                # no loop known: nobody to wake

    async def first_look():
        waiter = track.wait(asyncio.get_running_loop())
        assert waiter.done()           # the queue holds something: no wait
        await waiter
        return _send_all(track)

    assert asyncio.run(first_look()) == 2


@pytest.mark.asyncio
async def test_a_waiting_stream_is_woken_by_the_flush_of_another_thread():
    tele = StepTelemetry()
    track = tele.stream_open()
    waiter = track.wait(asyncio.get_running_loop())
    assert not waiter.done()

    def engine_thread():
        tele.phase_t0 = time.monotonic()
        track.put(5)
        tele.stream_flush()

    threading.Thread(target=engine_thread).start()
    await asyncio.wait_for(waiter, timeout=5.0)
    assert track.q.get_nowait()[0] == 5
    # a waiter nobody awaits any more (its stream was closed) is left alone
    waiter = track.wait(asyncio.get_running_loop())
    waiter.cancel()
    track.put(6)
    tele.stream_flush()
    await asyncio.sleep(0.01)


def test_the_summary_span_sits_under_the_requests_root():
    tele = StepTelemetry()
    tr = obs_trace.Trace("POST /v1/completions")
    track = tele.stream_open(trace=tr)
    tele.phase_t0 = time.monotonic()
    track.put(7)
    _send_one(track)
    track.resolved()
    track.last()
    track.sent(14)
    track.close()
    tr.close()
    d = tr.to_dict()
    assert obs_trace.well_formed_problems(d) == []
    (span,) = [s for s in d["spans"] if s["name"] == "stream.deliver"]
    assert span["parent_id"] == tr.root.span_id
    assert span["attrs"]["tokens"] == 1 and span["attrs"]["events"] == 1
    assert (span["attrs"]["deliver_max_ms"]
            >= span["attrs"]["deliver_mean_ms"] >= 0)
    assert "finish_lag_ms" in span["attrs"]


def test_ingress_is_counted_once_and_leaves_with_its_scope():
    tele = StepTelemetry()
    ing = tele.ingress_begin(time.monotonic() - 0.25)
    assert tele.stream_snapshot()["ingress_inflight"] == 1
    tele.ingress_submitted(time.monotonic())
    tele.ingress_submitted(time.monotonic())     # a second submit: nothing
    assert tele.stream_snapshot()["ingress_inflight"] == 0
    tele.ingress_end(ing)
    h = tele.histograms()["ingress_seconds"]
    assert h["count"] == 1 and 0.25 <= h["sum"] < 5
    # one refused on its way in leaves the gauge with its scope, unobserved
    ing = tele.ingress_begin(time.monotonic())
    tele.ingress_end(ing)
    assert tele.stream_snapshot()["ingress_inflight"] == 0
    assert tele.histograms()["ingress_seconds"]["count"] == 1
    # outside any request nothing is counted
    tele.ingress_submitted(time.monotonic())
    assert tele.stream_snapshot()["ingress_inflight"] == 0


def test_every_step_record_says_where_the_callers_stand():
    tele = StepTelemetry()
    tele.begin_step(0)
    tele.record_step(kind="idle", duration_s=0.0, n_running=0, n_waiting=0,
                     n_chunking=0, blocks_free=0, tokens=3)
    tele.phase_enter(None)
    rec = tele.recent_steps()[-1]
    assert (rec["ingress_inflight"], rec["streams_draining"],
            rec["stream_backlog"]) == (0, 0, 0)
    snap = tele.snapshot()
    assert snap["tokens_committed"] == 3
    assert (set(snap["phase_cpu_s"]) == set(snap["phase_cpu_wall_s"])
            == set(snap["phase_s"]) == set(PHASES))
    assert set(NON_WAITING_PHASES) < set(PHASES)
    # nested, so the JSON-line twin (flat numbers only) carries none of it
    assert isinstance(snap["stream"], dict)


# -- StreamingResponse and the drain -----------------------------------------

def _plain_app(response_of):
    app = asgi.App("t")

    @app.get("/s")
    def s(request):
        return response_of()

    return app


@pytest.mark.asyncio
async def test_a_streaming_response_without_on_sent_drains_as_before():
    app = _plain_app(lambda: asgi.StreamingResponse(
        iter(["hello ", "", b"world"])))
    status, chunks = await _drive(app, "/s", method="GET")
    assert status == 200 and chunks == [b"hello ", b"world"]


@pytest.mark.asyncio
async def test_on_sent_hears_of_each_chunk_that_was_written_and_no_other():
    sizes = []
    app = _plain_app(lambda: asgi.StreamingResponse(
        iter(["ab", "", "cde", "f"]), on_sent=sizes.append))
    _, chunks = await _drive(app, "/s", method="GET")
    assert chunks == [b"ab", b"cde", b"f"] and sizes == [2, 3, 1]
    # a write that failed is not a chunk sent
    sizes.clear()
    _, chunks = await _drive(app, "/s", method="GET", fail_after=1)
    assert chunks == [b"ab"] and sizes == [2]


# -- the engine behind the real app ------------------------------------------

@pytest.fixture(scope="module")
def stack():
    cfg = ServeConfig(app="llm", model_id="tiny", device="cpu",
                      max_new_tokens=64, vllm_config="/nonexistent.yaml",
                      warmup=False)
    service = get_model("vllm")(cfg)
    app = create_app(cfg, service)

    async def prime():
        async with make_client(app) as c:
            r = await wait_ready(c, timeout=300.0)
            assert r.status_code == 200, r.text
            r = await c.post("/generate", json={
                "prompt": "hello world", "temperature": 0.0,
                "max_new_tokens": 4})
            assert r.status_code == 200, r.text

    asyncio.run(prime())

    class Visible(type(service.tokenizer)):
        def decode(self, ids) -> str:
            return "".join(chr(97 + int(i) % 26) for i in ids)

    service.tokenizer = Visible()
    yield service, app
    service.loop.stop()


def _stream_body(n, **over):
    return {"prompt": "hello world", "stream": True, "max_tokens": n,
            "temperature": 0.0, **over}


def _text_of(chunks):
    out = ""
    for ch in b"".join(chunks).decode().split("\n\n"):
        if ch.startswith("data: {"):
            out += json.loads(ch[6:])["choices"][0]["text"]
    return out


def _settled(tele, timeout_s=10.0):
    """The stream counters once nothing is on its way any more."""
    deadline = time.monotonic() + timeout_s
    while True:
        s = tele.stream_snapshot()
        if not (s["backlog"] or s["draining"] or s["ingress_inflight"]):
            return s
        assert time.monotonic() < deadline, f"never settled: {s}"
        time.sleep(0.02)


def _delta(after, before):
    return {k: after[k] - before[k] for k in after}


@pytest.mark.asyncio
async def test_a_stream_that_ends_whole_through_the_app(stack):
    service, app = stack
    tele = service.engine_telemetry()
    s0, h0 = _settled(tele), tele.histograms()
    committed0 = tele.snapshot()["tokens_committed"]
    status, chunks = await _drive(app, "/v1/completions", _stream_body(9))
    assert status == 200 and chunks[-1] == b"data: [DONE]\n\n"
    assert len(_text_of(chunks)) == 9
    d = _delta(_settled(tele), s0)
    assert d["tokens_put"] == d["tokens_sent"] == 9
    assert d["tokens_dropped"] == 0
    assert (d["streams_started"], d["streams_ended"],
            d["streams_aborted"]) == (1, 1, 0)
    # an event carries what the queue held at its turn: nine tokens leave in
    # at most nine events, and the finish event and [DONE] follow
    assert 1 <= d["events_sent"] == len(chunks) - 2 <= 9
    assert d["bytes_sent"] == sum(len(c) for c in chunks)
    h1 = tele.histograms()
    for k in STREAM_HISTOGRAMS:
        assert h1[k]["count"] - h0[k]["count"] == len(chunks) - 2
    hops = sum(h1[k]["sum"] - h0[k]["sum"] for k in STREAM_HISTOGRAMS[:3])
    assert hops == pytest.approx(
        h1["stream_deliver_seconds"]["sum"]
        - h0["stream_deliver_seconds"]["sum"], rel=1e-6)
    assert (h1["stream_finish_lag_seconds"]["count"]
            - h0["stream_finish_lag_seconds"]["count"]) == 1
    assert h1["ingress_seconds"]["count"] - h0["ingress_seconds"]["count"] == 1
    assert tele.snapshot()["tokens_committed"] - committed0 >= 9
    rec = tele.recent_steps()[-1]
    assert {"ingress_inflight", "streams_draining",
            "stream_backlog"} <= set(rec)


@pytest.mark.asyncio
async def test_a_stop_sequence_cancels_and_the_rest_is_dropped(stack):
    service, app = stack
    tele = service.engine_telemetry()
    _, chunks = await _drive(app, "/v1/completions", _stream_body(12))
    text = _text_of(chunks)
    stop = text[3]
    s0 = _settled(tele)
    _, chunks = await _drive(app, "/v1/completions",
                             _stream_body(40, stop=stop))
    assert chunks[-1] == b"data: [DONE]\n\n"
    assert _text_of(chunks) == text[:text.index(stop)]
    d = _delta(_settled(tele), s0)
    # what was sent: the text and the stop's own token, which went with the
    # stream's end; whatever the engine put until the cancel landed: dropped
    assert d["tokens_sent"] == text.index(stop) + 1
    assert d["tokens_put"] == d["tokens_sent"] + d["tokens_dropped"]
    assert (d["streams_ended"], d["streams_aborted"]) == (1, 0)


@pytest.mark.asyncio
@pytest.mark.parametrize("how", ["client_goes_away", "write_fails"])
async def test_an_abandoned_stream_balances_through_dropped(stack, how):
    """A slow socket lets the tokens queue; the client then goes away (or a
    write fails). The stream's remainder is dropped, and nothing stays
    counted as on its way."""
    service, app = stack
    tele = service.engine_telemetry()
    s0 = _settled(tele)
    gone = asyncio.Event()

    async def leave():
        while tele.stream_snapshot()["tokens_sent"] - s0["tokens_sent"] < 1:
            await asyncio.sleep(0.005)
        gone.set()

    # the first event is on the socket; the second, which carries what
    # queued behind the slow first write, is being written (or fails)
    leaver = (asyncio.ensure_future(leave()) if how == "client_goes_away"
              else None)
    _, chunks = await _drive(
        app, "/v1/completions", _stream_body(60), disconnect=gone,
        slow_s=0.1, fail_after=1 if how == "write_fails" else None)
    if leaver is not None:
        await asyncio.wait_for(leaver, timeout=10.0)
    assert not any(b"[DONE]" in c for c in chunks)
    d = _delta(_settled(tele), s0)
    assert (d["streams_started"], d["streams_ended"],
            d["streams_aborted"]) == (1, 0, 1)
    assert d["tokens_sent"] >= 1
    assert d["tokens_dropped"] > 0
    assert d["tokens_put"] == d["tokens_sent"] + d["tokens_dropped"]
    # while the socket was slow the ring saw tokens waiting for it
    assert max(r["stream_backlog"] for r in tele.recent_steps()) > 0


@pytest.mark.asyncio
async def test_a_stream_ends_with_its_request_and_not_a_poll_later(stack):
    """The finish lag (future resolved to ``[DONE]`` written) holds no
    0.2 s step: the end mark behind the last token ends the stream."""
    service, app = stack
    tele = service.engine_telemetry()
    h0 = tele.histograms()["stream_finish_lag_seconds"]
    lags = []
    for _ in range(3):
        status, chunks = await _drive(app, "/v1/completions",
                                      _stream_body(5))
        assert status == 200 and chunks[-1] == b"data: [DONE]\n\n"
        _settled(tele)
        h1 = tele.histograms()["stream_finish_lag_seconds"]
        lags.append(h1["sum"] - h0["sum"])
        h0 = h1
    assert h1["count"] >= 3
    # the best of three (a loaded test machine may hold any one up): the
    # poll put 0.2 s into every one of them
    assert min(lags) < 0.05, lags


@pytest.mark.asyncio
async def test_the_counters_balance_after_whole_stopped_and_aborted_streams(
        stack):
    """``tokens_sent + tokens_dropped == tokens_put`` and no more events
    than tokens, whatever became of the streams; behind a slow socket an
    event carries several tokens."""
    service, app = stack
    tele = service.engine_telemetry()
    _, chunks = await _drive(app, "/v1/completions", _stream_body(12))
    text = _text_of(chunks)
    s0 = _settled(tele)
    await _drive(app, "/v1/completions", _stream_body(30))
    await _drive(app, "/v1/completions", _stream_body(40, stop=text[5]))
    _, slow = await _drive(app, "/v1/completions", _stream_body(60),
                           slow_s=0.05)
    await _drive(app, "/v1/completions", _stream_body(60), slow_s=0.05,
                 fail_after=1)
    d = _delta(_settled(tele), s0)
    assert (d["streams_started"], d["streams_ended"],
            d["streams_aborted"]) == (4, 3, 1)
    assert d["tokens_put"] == d["tokens_sent"] + d["tokens_dropped"]
    assert 4 <= d["events_sent"] <= d["tokens_sent"]
    # the slow socket's stream: the same 60 letters in far fewer events
    assert len(_text_of(slow)) == 60 and len(slow) < 50


@pytest.mark.asyncio
async def test_a_request_refused_on_its_way_in_leaves_the_gauge(stack):
    service, app = stack
    tele = service.engine_telemetry()
    n0 = tele.histograms()["ingress_seconds"]["count"]
    status, _ = await _drive(app, "/v1/completions",
                             {"prompt": "x", "stream": True, "logprobs": 1})
    assert status == 400
    assert _settled(tele)["ingress_inflight"] == 0
    assert tele.histograms()["ingress_seconds"]["count"] == n0
    # /generate comes in the same way and streams nothing
    s0 = tele.stream_snapshot()
    committed0 = tele.snapshot()["tokens_committed"]
    status, _ = await _drive(app, "/generate", {
        "prompt": "hello world", "temperature": 0.0, "max_new_tokens": 5})
    assert status == 200
    assert tele.histograms()["ingress_seconds"]["count"] == n0 + 1
    assert _delta(_settled(tele), s0)["tokens_put"] == 0
    assert tele.snapshot()["tokens_committed"] - committed0 >= 5


@pytest.mark.asyncio
async def test_the_loop_threads_cpu_seconds_stay_under_its_wall_seconds(stack):
    """The CPU clock is read in one step of ``CPU_SAMPLE_EVERY``: a phase's
    CPU seconds stand beside the wall seconds of the SAME phases."""
    service, app = stack
    await _drive(app, "/v1/completions", _stream_body(40))
    snap = service.engine_telemetry().snapshot()
    assert set(snap["phase_cpu_s"]) == set(snap["phase_s"])
    for phase, cpu in snap["phase_cpu_s"].items():
        wall = snap["phase_cpu_wall_s"][phase]
        assert 0.0 <= wall <= snap["phase_s"][phase] + 1e-9, phase
        # clock grain: the two clocks are read one behind the other
        assert 0.0 <= cpu <= wall * 1.01 + 1e-3, phase
    assert sum(snap["phase_cpu_s"][p] for p in NON_WAITING_PHASES) > 0
    # sampled, not every step: the seven phases' sampled wall is a part
    assert (sum(snap["phase_cpu_wall_s"][p] for p in NON_WAITING_PHASES)
            < sum(snap["phase_s"][p] for p in NON_WAITING_PHASES))
    # the loop waits in loop.idle: its CPU seconds are a sliver of its wall
    assert (snap["phase_cpu_s"]["loop.idle"]
            < 0.5 * snap["phase_cpu_wall_s"]["loop.idle"])


@pytest.mark.asyncio
async def test_one_stream_in_n_is_annotated_and_never_around_a_wait(
        stack, monkeypatch):
    """``serve.stream.encode`` and ``serve.stream.write`` for sampled
    streams only, around work: no annotation is open on a thread while it
    waits on a queue."""
    service, app = stack
    monkeypatch.setattr(vllm_unit, "STREAM_ANNOTATE_EVERY", 2)
    names, waits_inside = [], []
    open_on = threading.local()

    class Recorded:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            names.append(self.name)
            open_on.depth = getattr(open_on, "depth", 0) + 1
            return self

        def __exit__(self, *exc):
            open_on.depth -= 1
            return False

    monkeypatch.setattr(obs_trace, "annotate",
                        lambda name, **meta: Recorded(name))
    plain_get = queue.Queue.get

    def get(self, *args, **kwargs):
        if getattr(open_on, "depth", 0):
            waits_inside.append(threading.current_thread().name)
        return plain_get(self, *args, **kwargs)

    monkeypatch.setattr(queue.Queue, "get", get)
    n_chunks = []
    for _ in range(2):           # two ids in a row: one of them is sampled
        _, chunks = await _drive(app, "/v1/completions", _stream_body(7))
        n_chunks.append(len(chunks))
    # the sampled stream of the two: a write for each of its chunks, an
    # encode for each turn of the stream: one for each chunk that carries
    # tokens (all but the last two) and, where the end mark came behind
    # the last token's event, one for the turn that took it alone
    writes = names.count("serve.stream.write")
    assert writes in n_chunks and 3 <= writes <= 9
    assert writes - 2 <= names.count("serve.stream.encode") <= writes - 1
    assert set(names) == {"serve.stream.encode", "serve.stream.write"}
    assert waits_inside == []


@pytest.mark.asyncio
async def test_the_flight_dump_holds_the_streams_summary_span(stack):
    service, app = stack
    await _drive(app, "/v1/completions", _stream_body(6))
    status, chunks = await _drive(app, "/debug/flight", method="GET")
    assert status == 200
    dump = json.loads(b"".join(chunks))
    traces = [r["trace"] for r in dump["requests"]]
    assert {"ingress_inflight", "streams_draining",
            "stream_backlog"} <= set(dump["engine_steps"][-1])
    mine = [t for t in traces if t["name"] == "POST /v1/completions"][-1]
    assert obs_trace.well_formed_problems(mine) == []
    by_name = {s["name"]: s for s in mine["spans"]}
    root = next(s for s in mine["spans"] if s["parent_id"] is None)
    span = by_name["stream.deliver"]
    assert span["parent_id"] == root["span_id"]
    assert span["attrs"]["tokens"] == 6 and 1 <= span["attrs"]["events"] <= 6
    assert {"decode", "prefill", "queue"} <= set(by_name)
    # the root starts where the request began, ahead of everything under it
    assert all(s["t_start"] >= root["t_start"] - 1e-6 for s in mine["spans"])
