"""Chaos suite (ISSUE 4): the full serve path under injected faults.

THE invariant, asserted under every fault class: **every accepted request
reaches a terminal state within its deadline** — a 200, a 4xx/5xx, or an
in-band SSE error; never a hang — **and no KV blocks leak** (pool
accounting conserved across the run). Faults come from
``resilience.faults`` (seeded, deterministic); the serve path is the real
one (create_app + engine-backed vllm unit over ASGI).

Covered fault classes: engine step delay/stall (deadline + watchdog),
step crash (engine-loop death), KV reservation failure, cova RPC error
(circuit breaker), client disconnect mid-SSE, and SIGTERM drain.
"""

import asyncio
import threading
import time

import httpx
import pytest

from scalable_hw_agnostic_inference_tpu.models.registry import get_model
from scalable_hw_agnostic_inference_tpu.orchestrate.cova import CovaClient
from scalable_hw_agnostic_inference_tpu.resilience import faults
from scalable_hw_agnostic_inference_tpu.serve.app import create_app
from scalable_hw_agnostic_inference_tpu.serve.asgi import (
    App,
    HTTPError,
    StreamingResponse,
)
from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig

from test_serve_http import make_client, wait_ready


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test leaves the process injector as it found it."""
    faults.reset()
    yield
    faults.reset()


def _build_stack(**cfg_over):
    cfg_over.setdefault("vllm_config", "/nonexistent.yaml")
    cfg = ServeConfig(app="llm", model_id="tiny", device="cpu",
                      max_new_tokens=64, **cfg_over)
    service = get_model("vllm")(cfg)
    app = create_app(cfg, service)
    return cfg, service, app


def _assert_engine_clean(service, timeout_s: float = 15.0):
    """Wait for the engine to drain, then check the no-leak invariant:
    free + cache-retained == total-1 (block 0 is the null block)."""
    eng = service._engine
    deadline = time.monotonic() + timeout_s
    while eng.has_work and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not eng.has_work, "engine still has work (request not terminal)"
    cache_held = len(eng.cache._hash2block)
    total = eng.ecfg.total_blocks
    assert eng.cache.allocator.n_free + cache_held == total - 1, (
        f"KV block leak: free={eng.cache.allocator.n_free} "
        f"cached={cache_held} total={total}")


@pytest.fixture(scope="module")
def stack():
    """One engine stack shared by the non-destructive fault tests.
    Watchdog thresholds are tightened (env read at service build) so the
    stall test can trip liveness in seconds. ``warmup=False`` + a priming
    request: only the shapes these tests actually use compile (tier-1
    budget — the full warm set costs ~1 min on this container)."""
    import os

    old = {k: os.environ.get(k)
           for k in ("SHAI_WATCHDOG_MULT", "SHAI_WATCHDOG_MIN_S")}
    os.environ["SHAI_WATCHDOG_MULT"] = "5"
    os.environ["SHAI_WATCHDOG_MIN_S"] = "0.5"
    try:
        cfg, service, app = _build_stack(warmup=False)

        async def prime():
            async with make_client(app) as c:
                r = await wait_ready(c, timeout=300.0)
                assert r.status_code == 200, r.text
                # compile the hot shapes OUTSIDE any fault schedule, so
                # fault tests measure the fault, not a lazy compile
                for prompt in ("hello world", "aaaa"):
                    r = await c.post("/generate",
                                     json={"prompt": prompt,
                                           "temperature": 0.0,
                                           "max_new_tokens": 4})
                    assert r.status_code == 200, r.text

        asyncio.run(prime())
        yield cfg, service, app
    finally:
        for k, v in old.items():
            os.environ.pop(k, None) if v is None else os.environ.update({k: v})


# ---------------------------------------------------------------------------
# deadlines under slow steps
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.asyncio
async def test_deadline_exceeded_under_step_delay_is_terminal_504(stack):
    """Slow engine steps + a tight per-request deadline: the request must
    come back 504 (stop reason ``timeout``) close to its deadline — not
    decode to max_new_tokens for a caller that gave up — and free its
    blocks."""
    cfg, service, app = stack
    async with make_client(app) as c:
        r = await wait_ready(c, timeout=300.0)
        assert r.status_code == 200, r.text

        faults.configure("engine.step=delay(0.1)")
        t0 = time.monotonic()
        r = await c.post("/generate",
                         json={"prompt": "hello world", "temperature": 0.0,
                               "max_new_tokens": 50},
                         headers={"x-shai-deadline-ms": "400"})
        elapsed = time.monotonic() - t0
        assert r.status_code == 504, r.text
        assert "deadline" in r.json()["detail"]
        # terminal WITHIN the deadline (one step of slack + HTTP overhead)
        assert elapsed < 5.0, f"took {elapsed:.1f}s against a 0.4s deadline"
        _assert_engine_clean(service)

        # the pod is not poisoned: a deadline-less request still completes
        faults.reset()
        r = await c.post("/generate", json={"prompt": "hello world",
                                            "temperature": 0.0,
                                            "max_new_tokens": 4})
        assert r.status_code == 200, r.text
        assert r.json()["stop_reason"] == "length"
        _assert_engine_clean(service)


@pytest.mark.asyncio
async def test_deadline_header_validation(stack):
    cfg, service, app = stack
    async with make_client(app) as c:
        await wait_ready(c, timeout=300.0)
        for bad in ("abc", "-100", "0", "nan", "inf"):
            r = await c.post("/generate",
                             json={"prompt": "x", "max_new_tokens": 2},
                             headers={"x-shai-deadline-ms": bad})
            assert r.status_code == 400, (bad, r.text)


# ---------------------------------------------------------------------------
# KV reservation failure
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_kv_reservation_fault_rejects_terminal(stack):
    """An injected reservation failure reads as a dry pool: with nothing
    running to wait on, the request is rejected-and-finished (503), never
    parked forever."""
    cfg, service, app = stack
    async with make_client(app) as c:
        await wait_ready(c, timeout=300.0)
        faults.configure("engine.kv_reserve=error")
        r = await c.post("/generate", json={"prompt": "hello world",
                                            "temperature": 0.0,
                                            "max_new_tokens": 4})
        assert r.status_code == 503, r.text
        _assert_engine_clean(service)

        faults.reset()
        r = await c.post("/generate", json={"prompt": "hello world",
                                            "temperature": 0.0,
                                            "max_new_tokens": 4})
        assert r.status_code == 200, r.text
        _assert_engine_clean(service)


# ---------------------------------------------------------------------------
# step stall -> watchdog -> liveness
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_step_stall_fails_liveness_then_recovers(stack):
    """A stalled dispatch (no step completing while work is pending) must
    fail ``/health`` so Kubernetes restarts the pod — and a recovered
    engine must pass it again."""
    cfg, service, app = stack
    async with make_client(app) as c:
        await wait_ready(c, timeout=300.0)
        r = await c.get("/health")
        assert r.status_code == 200

        faults.configure("engine.step=stall(3)#1")
        task = asyncio.ensure_future(
            c.post("/generate", json={"prompt": "hello world",
                                      "temperature": 0.0,
                                      "max_new_tokens": 2}))
        # while the step is stalled (work pending, nothing completing),
        # liveness must flip within the tightened threshold
        stuck = None
        for _ in range(40):
            await asyncio.sleep(0.1)
            r = await c.get("/health")
            if r.status_code == 503:
                stuck = r.json()
                break
        assert stuck is not None, "watchdog never tripped during the stall"
        assert stuck["status"] == "stuck" and "stalled" in stuck["error"]

        r = await task             # the stalled request still terminates
        assert r.status_code == 200, r.text
        _assert_engine_clean(service)
        r = await c.get("/health")  # steps flow again: liveness recovers
        assert r.status_code == 200


# ---------------------------------------------------------------------------
# client disconnect mid-SSE (satellite regression: fake ASGI receive)
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_client_disconnect_mid_stream_cancels_engine(stack):
    """A client that goes away mid-SSE must cancel the engine request: the
    generator is closed (its finally runs ``loop.cancel``), the KV blocks
    free, and the engine does NOT decode to max_new_tokens for a dead
    socket. Driven through the real app with a fake ASGI ``receive`` that
    injects ``http.disconnect`` once the ENGINE has taken a few decode
    steps for the request, whether or not a delta has reached the client:
    the tiny model's greedy continuation of this prompt is bytes the
    detokenizer holds back to the end, so no visible chunk flows before
    the disconnect, which is the hard case."""
    import json as _json

    cfg, service, app = stack
    async with make_client(app) as c:
        await wait_ready(c, timeout=300.0)

    max_tokens, after = 60, 5
    faults.configure("engine.step=delay(0.1)")   # 6 s if it ran to the end
    body = _json.dumps({"prompt": "aaaa", "stream": True,
                        "max_tokens": max_tokens,
                        "temperature": 0.0}).encode()
    scope = {"type": "http", "method": "POST", "path": "/v1/completions",
             "query_string": b"", "headers": [
                 (b"content-type", b"application/json"),
                 (b"content-length", str(len(body)).encode())]}
    eng = service._engine
    disconnect = asyncio.Event()
    sent_body = False
    chunks = []
    inflight_seen = []

    async def receive():
        nonlocal sent_body
        if not sent_body:
            sent_body = True
            return {"type": "http.request", "body": body, "more_body": False}
        await disconnect.wait()
        return {"type": "http.disconnect"}

    async def send(message):
        if message["type"] == "http.response.body" and message.get("body"):
            chunks.append(message["body"])

    def generated():
        return max((len(s.generated) for s in eng.slots if s is not None),
                   default=0)

    async def client_goes_away():
        # the engine's progress decides when, not the wall clock
        while generated() < after:
            await asyncio.sleep(0.01)
        # a LIVE stream counts against the in-flight gauge (it holds
        # engine work) — not just until the handler returned
        inflight_seen.append(app.state["status"]["inflight"])
        disconnect.set()

    steps0 = eng.obs.snapshot()["steps"]
    away = asyncio.ensure_future(client_goes_away())
    await asyncio.wait_for(app(scope, receive, send), timeout=30.0)
    await asyncio.wait_for(away, timeout=5.0)
    _assert_engine_clean(service)
    # the request was aborted early: every decode step makes one token of
    # this lone request, and it took far fewer than max_tokens of them
    steps = eng.obs.snapshot()["steps"] - steps0
    assert after <= steps < max_tokens - 10, (
        f"engine decoded to the end for a dead socket? {steps} steps")
    assert not any(b"[DONE]" in ch for ch in chunks)
    assert not any(b"finish_reason\": \"length" in ch for ch in chunks)
    assert inflight_seen == [1]
    # the abort released the in-flight slot (generator finally ran)
    deadline = time.monotonic() + 5.0
    while (app.state["status"]["inflight"] > 0
           and time.monotonic() < deadline):
        await asyncio.sleep(0.05)
    assert app.state["status"]["inflight"] == 0


def test_streaming_disconnect_closes_generator_plain_asgi():
    """ASGI-level regression (no engine): ``http.disconnect`` mid-stream
    must close the chunk generator — the old loop never observed the
    message, leaking a parked stream-pool thread per abandoned client."""
    app = App("t")
    state = {"closed": False, "yielded": 0}

    def gen():
        try:
            while True:
                state["yielded"] += 1
                yield b"data: x\n\n"
                time.sleep(0.01)
        finally:
            state["closed"] = True

    @app.get("/stream")
    def stream(request):
        return StreamingResponse(gen())

    async def drive():
        scope = {"type": "http", "method": "GET", "path": "/stream",
                 "query_string": b"", "headers": []}
        disconnect = asyncio.Event()
        got = {"n": 0}
        sent_body = False

        async def receive():
            nonlocal sent_body
            if not sent_body:
                sent_body = True
                return {"type": "http.request", "body": b"",
                        "more_body": False}
            await disconnect.wait()
            return {"type": "http.disconnect"}

        async def send(message):
            if (message["type"] == "http.response.body"
                    and message.get("body")):
                got["n"] += 1
                if got["n"] >= 2:
                    disconnect.set()

        await asyncio.wait_for(app(scope, receive, send), timeout=10.0)

    asyncio.run(drive())
    deadline = time.time() + 5.0
    while not state["closed"] and time.time() < deadline:
        time.sleep(0.01)
    assert state["closed"], "disconnect did not close the stream generator"
    assert state["yielded"] < 100, "generator kept producing for a dead peer"


# ---------------------------------------------------------------------------
# admission control / backpressure
# ---------------------------------------------------------------------------

@pytest.mark.slow  # own engine build: tier-1 budget (check_tier1_budget.py)
@pytest.mark.asyncio
async def test_admission_gate_sheds_over_inflight_cap():
    """With the in-flight cap at 1 and slow steps, concurrent requests
    must shed 429 + Retry-After at the door (never park), and the sheds
    must be visible on /stats (and /metrics when prometheus is around)."""
    cfg, service, app = _build_stack(max_inflight=1)
    async with make_client(app) as c:
        await wait_ready(c, timeout=300.0)
        faults.configure("engine.step=delay(0.05)")
        payload = {"prompt": "hello world", "temperature": 0.0,
                   "max_new_tokens": 24}
        rs = await asyncio.gather(*[c.post("/generate", json=payload)
                                    for _ in range(3)])
        statuses = sorted(r.status_code for r in rs)
        assert statuses.count(200) >= 1, [r.text for r in rs]
        assert statuses.count(429) >= 1, statuses
        shed = next(r for r in rs if r.status_code == 429)
        assert int(shed.headers["retry-after"]) >= 1
        _assert_engine_clean(service)

        r = await c.get("/stats")
        st = r.json()
        assert st["shed"]["total"] >= 1
        assert st["shed"]["inflight"] >= 1

        r = await c.get("/metrics")
        if r.status_code == 200 and "shai_" in r.text:
            assert "shai_shed_total" in r.text


# ---------------------------------------------------------------------------
# graceful drain (the SIGTERM path, driven without a signal)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # own engine build: tier-1 budget (check_tier1_budget.py)
@pytest.mark.asyncio
async def test_drain_finishes_inflight_rejects_new_then_stops_engine(
        monkeypatch, tmp_path):
    monkeypatch.setenv("SHAI_KVTIER", "1")  # drain must also join the
    monkeypatch.setenv("SHAI_KVTIER_ASYNC", "1")  # copy-out worker
    # the host tier rides the prefix cache (engine gates it off otherwise)
    ecfg_yaml = tmp_path / "ecfg.yaml"
    ecfg_yaml.write_text(
        "max_model_len: 576\n"
        "max_num_seqs: 4\n"
        "block_size: 16\n"
        "context_encoding_buckets: [128, 512]\n"
        "max_new_tokens: 64\n"
        "enable_prefix_caching: true\n")
    cfg, service, app = _build_stack(drain_budget_s=20.0,
                                     vllm_config=str(ecfg_yaml))
    async with make_client(app) as c:
        await wait_ready(c, timeout=300.0)
        # seed one demotion so the lazy copy-out worker thread exists —
        # the drain contract below must JOIN it, not orphan it
        import numpy as np
        tier = service._engine.cache.tier
        assert tier is not None
        blk = np.zeros((tier.n_layers, 1, tier.block_size,
                        tier.n_kv_heads, tier.head_dim), tier.dtype)
        tier.store_batch([0xDEAD], blk, blk.copy(), 1)
        faults.configure("engine.step=delay(0.05)")  # in-flight ~1s
        task = asyncio.ensure_future(
            c.post("/generate", json={"prompt": "hello world",
                                      "temperature": 0.0,
                                      "max_new_tokens": 16}))
        await asyncio.sleep(0.3)                     # it is really in flight

        assert app.state["begin_drain"]()
        assert not app.state["begin_drain"]()        # idempotent

        r = await c.get("/health/ready")             # LB stops routing
        assert r.status_code == 503
        assert r.json()["status"] == "draining"
        r = await c.get("/readiness")
        assert r.status_code == 503

        r = await c.post("/generate", json={"prompt": "x",
                                            "max_new_tokens": 2})
        assert r.status_code == 503                  # new work sheds
        assert int(r.headers["retry-after"]) >= 1
        assert "draining" in r.json()["detail"]

        r = await c.get("/health")                   # draining != dead
        assert r.status_code == 200

        # metadata extra routes bypass the gate: an OpenAI SDK enumerating
        # models must not eat the drain 503 (only inference routes shed)
        r = await c.get("/v1/models")
        assert r.status_code == 200, r.text
        assert r.json()["data"][0]["object"] == "model"

        r = await task                               # in-flight FINISHES
        assert r.status_code == 200, r.text
        assert r.json()["n_tokens"] == 16

        # the engine loop stops once the drain completes
        deadline = time.monotonic() + 15.0
        while service.loop._thread.is_alive() and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        assert not service.loop._thread.is_alive(), "engine loop still up"
        with pytest.raises(RuntimeError):
            service.loop.submit([1, 2, 3])

        # SIGTERM must not orphan an in-flight demotion copy: the drain
        # path closes the tier, bounded-joining the copy-out worker
        w = tier._worker
        assert w is not None, "demotion never spawned the worker?"
        deadline = time.monotonic() + 10.0
        while w.alive and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        assert not w.alive, "copy-out worker orphaned by drain"
        assert tier.has(0xDEAD)  # queued work published before the join


# ---------------------------------------------------------------------------
# engine-loop death (step crash): fail readiness, error every future
# ---------------------------------------------------------------------------

@pytest.mark.slow  # own engine build: tier-1 budget (check_tier1_budget.py)
@pytest.mark.asyncio
async def test_step_crash_fails_requests_and_readiness():
    """An injected step crash kills the engine loop: the in-flight request
    errors (terminal — a 500, not a hang) and readiness goes 503 so the
    pod drains from the LB instead of serving a black hole."""
    cfg, service, app = _build_stack()
    async with make_client(app) as c:
        await wait_ready(c, timeout=300.0)
        faults.configure("engine.step=error#1")
        r = await c.post("/generate", json={"prompt": "hello world",
                                            "temperature": 0.0,
                                            "max_new_tokens": 4})
        assert r.status_code == 500
        r = await c.get("/readiness")
        assert r.status_code == 503
        assert "engine loop" in r.json()["error"]


# ---------------------------------------------------------------------------
# cova RPC faults -> bounded retries + circuit breaker
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_cova_rpc_fault_opens_breaker_fails_fast():
    """Injected connect-phase RPC errors: bounded retries end in a 502;
    the per-backend breaker then opens and subsequent calls fail fast with
    503 + Retry-After (no connect timeout burned per call)."""
    client = CovaClient({"m": {"url": "http://127.0.0.1:9"}})
    faults.configure("cova.rpc=error")
    with pytest.raises(HTTPError) as ei:
        await client.post("m", "/infer", {"x": 1})
    assert ei.value.status == 502
    assert "unreachable" in ei.value.detail

    t0 = time.monotonic()
    with pytest.raises(HTTPError) as ei:
        await client.post("m", "/infer", {"x": 1})
    assert ei.value.status == 503
    assert "circuit open" in ei.value.detail
    assert "retry-after" in ei.value.headers
    assert time.monotonic() - t0 < 0.2     # fail-FAST while open

    # recovery: faults lifted + backoff elapsed -> the half-open probe goes
    # through to the real transport (dead port -> fast ConnectError, still
    # 502, breaker re-opens) — no hang, no crash
    faults.reset()
    br = client.breaker_of("m")
    br._open_until = 0.0                   # fast-forward past the backoff
    with pytest.raises(HTTPError) as ei:
        await client.post("m", "/infer", {"x": 1})
    assert ei.value.status in (502, 503)
    await client.aclose()


# ---------------------------------------------------------------------------
# /debug/faults endpoint gating
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_debug_faults_endpoint_env_gated(stack, monkeypatch):
    cfg, service, app = stack
    monkeypatch.delenv("SHAI_FAULTS", raising=False)
    monkeypatch.delenv("SHAI_FAULTS_ENDPOINT", raising=False)
    async with make_client(app) as c:
        await wait_ready(c, timeout=300.0)
        r = await c.post("/debug/faults", json={"spec": "a=error"})
        assert r.status_code == 403        # no env opt-in: locked

        monkeypatch.setenv("SHAI_FAULTS_ENDPOINT", "1")
        r = await c.post("/debug/faults",
                         json={"spec": "engine.step=delay(0.01)@0.5",
                               "seed": 3})
        assert r.status_code == 200, r.text
        snap = r.json()
        assert snap["seed"] == 3 and snap["active"]

        r = await c.get("/debug/faults")   # introspection: what's armed
        assert r.json()["spec"] == "engine.step=delay(0.01)@0.5"

        r = await c.post("/debug/faults", json={"spec": "not a spec!!"})
        assert r.status_code == 400

        r = await c.post("/debug/faults", json={"spec": ""})
        assert r.status_code == 200        # clearing is always safe
        assert not r.json()["active"]
