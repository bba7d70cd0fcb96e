"""Serving runtime tests: ASGI router, HTTP server, and the app factory.

In-process tests use ``httpx.ASGITransport`` (no sockets); one test boots the
real asyncio HTTP server on a loopback socket to cover the wire path the pods
actually use.
"""

import asyncio
import json
import threading
import time

import httpx
import pytest

from scalable_hw_agnostic_inference_tpu.serve.asgi import App, HTTPError, Response
from scalable_hw_agnostic_inference_tpu.serve.app import ModelService, create_app
from scalable_hw_agnostic_inference_tpu.serve.httpd import Server
from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig


def make_client(app) -> httpx.AsyncClient:
    return httpx.AsyncClient(transport=httpx.ASGITransport(app=app), base_url="http://test")


async def wait_ready(c: httpx.AsyncClient, timeout: float = 10.0) -> httpx.Response:
    """Poll /readiness until it leaves the 503 'loading' state."""
    deadline = time.time() + timeout
    while True:
        r = await c.get("/readiness")
        if r.status_code != 503 or time.time() > deadline:
            return r
        await asyncio.sleep(0.02)


def wait_ready_sync(c: httpx.Client, timeout: float = 10.0) -> httpx.Response:
    deadline = time.time() + timeout
    while True:
        r = c.get("/readiness")
        if r.status_code != 503 or time.time() > deadline:
            return r
        time.sleep(0.02)


# ---------------------------------------------------------------------------
# asgi router
# ---------------------------------------------------------------------------

def build_router_app():
    app = App("t")

    @app.get("/hello/{name}")
    def hello(request, name):
        return {"hello": name}

    @app.get("/sum/{a:int}/{b:int}")
    def sum_(request, a, b):
        return {"sum": a + b}

    @app.post("/echo")
    def echo(request):
        return {"got": request.json(), "q": request.query}

    @app.get("/boom")
    def boom(request):
        raise HTTPError(418, "teapot")

    @app.get("/crash")
    def crash(request):
        raise RuntimeError("internal")

    @app.get("/text")
    def text(request):
        return Response("plain text", media_type="text/plain")

    return app


@pytest.mark.asyncio
async def test_router_paths_and_casts():
    async with make_client(build_router_app()) as c:
        r = await c.get("/hello/world")
        assert r.status_code == 200 and r.json() == {"hello": "world"}
        r = await c.get("/sum/3/4")
        assert r.json() == {"sum": 7}
        # non-int segment -> 404 (cast fails)
        r = await c.get("/sum/x/4")
        assert r.status_code == 404


@pytest.mark.asyncio
async def test_router_json_query_errors():
    async with make_client(build_router_app()) as c:
        r = await c.post("/echo?k=v", json={"a": 1})
        assert r.json() == {"got": {"a": 1}, "q": {"k": "v"}}
        r = await c.post("/echo", content=b"{bad json")
        assert r.status_code == 400
        r = await c.get("/boom")
        assert r.status_code == 418 and r.json()["detail"] == "teapot"
        r = await c.get("/crash")
        assert r.status_code == 500
        r = await c.get("/nope")
        assert r.status_code == 404
        # wrong method on a known path -> 405
        r = await c.get("/echo")
        assert r.status_code == 405
        r = await c.get("/text")
        assert r.text == "plain text"


# ---------------------------------------------------------------------------
# app factory with a fake model service
# ---------------------------------------------------------------------------

class EchoService(ModelService):
    task = "echo"
    infer_route = "/predict"

    def __init__(self, cfg, load_delay=0.0, fail=False):
        super().__init__(cfg)
        self.load_delay = load_delay
        self.fail = fail
        self.loaded = False
        self.warmups = 0

    def load(self):
        time.sleep(self.load_delay)
        if self.fail:
            raise RuntimeError("artifact missing")
        self.loaded = True

    def warmup(self):
        self.warmups += 1
        self.infer(self.example_payload())

    def example_payload(self):
        return {"text": "warmup"}

    def infer(self, payload):
        return {"echo": payload.get("text", "")}

    def extra_routes(self):
        def sentiment(request):
            return {"label": "POSITIVE"}

        return [("/sentiment", ("POST",), sentiment)]


def make_cfg(**kw) -> ServeConfig:
    base = dict(app="echo", nodepool="test-pool", pod_name="pod-0", device="cpu",
                warmup=True)
    base.update(kw)
    return ServeConfig(**base)


@pytest.mark.asyncio
async def test_app_lifecycle_and_infer():
    cfg = make_cfg()
    svc = EchoService(cfg)
    app = create_app(cfg, svc)
    async with make_client(app) as c:
        r = await wait_ready(c)
        assert r.status_code == 200 and r.json() == {"status": "ready"}
        assert svc.loaded and svc.warmups == 1

        r = await c.get("/")
        body = r.json()
        assert body["app"] == "echo" and body["task"] == "echo"
        assert "/predict" in body["endpoints"]
        # the requested tier, and beside it the backend JAX brought up
        import jax

        dev = jax.devices()[0]
        assert body["device"] == cfg.device
        assert (body["platform"], body["device_kind"], body["n_devices"]) \
            == (dev.platform, dev.device_kind, len(jax.devices()))

        r = await c.get("/health")
        assert r.json() == {"status": "ok"}

        r = await c.post("/predict", json={"text": "hi"})
        assert r.json()["echo"] == "hi"
        assert "latency_s" in r.json()

        r = await c.post("/sentiment", json={})
        assert r.json() == {"label": "POSITIVE"}


@pytest.mark.asyncio
async def test_app_benchmark_and_load_endpoints():
    cfg = make_cfg()
    app = create_app(cfg, EchoService(cfg))
    async with make_client(app) as c:
        await wait_ready(c)
        r = await c.post("/benchmark", json={"n_runs": 5})
        rep = r.json()["report"]
        assert rep["n_runs"] == 5 and rep["throughput_rps"] > 0
        assert "p50" in rep

        r = await c.get("/load/2/infer/3")
        body = r.json()
        assert len(body["rounds"]) == 2
        assert body["served_total"] >= 6

        r = await c.get("/load/0/infer/3")
        assert r.status_code == 400

        r = await c.get("/stats")
        assert r.json()["served"] >= 6


@pytest.mark.asyncio
async def test_app_failed_load_reports_not_ready():
    cfg = make_cfg()
    app = create_app(cfg, EchoService(cfg, fail=True))
    async with make_client(app) as c:
        r = await wait_ready(c)
        assert r.status_code == 500
        assert "artifact missing" in r.json()["error"]
        r = await c.post("/predict", json={})
        assert r.status_code == 500
        # liveness stays green: the pod is not crash-looping
        r = await c.get("/health")
        assert r.status_code == 200


@pytest.mark.asyncio
async def test_metrics_endpoint_prometheus():
    pytest.importorskip("prometheus_client")
    cfg = make_cfg()
    app = create_app(cfg, EchoService(cfg))
    async with make_client(app) as c:
        await wait_ready(c)
        await c.post("/predict", json={"text": "x"})
        r = await c.get("/metrics")
        assert r.status_code == 200
        assert "shai_requests_total" in r.text
        assert 'app="echo"' in r.text


# ---------------------------------------------------------------------------
# real socket server
# ---------------------------------------------------------------------------

def test_probes_answer_during_slow_load():
    """Socket binds and /health + /readiness answer while load() is running."""
    cfg = make_cfg()
    svc = EchoService(cfg, load_delay=1.0)
    app = create_app(cfg, svc)
    server = Server(app, host="127.0.0.1", port=0)
    t0 = time.perf_counter()
    host, port = server.start_background()
    bind_dt = time.perf_counter() - t0
    try:
        assert bind_dt < 0.9, f"socket bind waited for model load: {bind_dt:.2f}s"
        with httpx.Client(base_url=f"http://{host}:{port}", timeout=10) as c:
            r = c.get("/health")
            assert r.status_code == 200
            r = c.get("/readiness")
            assert r.status_code == 503 and r.json() == {"status": "loading"}
            assert wait_ready_sync(c).status_code == 200
    finally:
        server.stop()


def test_httpd_over_real_socket():
    cfg = make_cfg()
    app = create_app(cfg, EchoService(cfg))
    server = Server(app, host="127.0.0.1", port=0)
    host, port = server.start_background()
    try:
        base = f"http://{host}:{port}"
        with httpx.Client(base_url=base, timeout=10) as c:
            r = wait_ready_sync(c)
            assert r.status_code == 200
            # keep-alive: several requests on one client
            for i in range(3):
                r = c.post("/predict", json={"text": f"msg{i}"})
                assert r.json()["echo"] == f"msg{i}"
            r = c.get("/load/1/infer/2")
            assert len(r.json()["rounds"]) == 1
            # concurrent probes while a model call runs
            r = c.get("/health")
            assert r.status_code == 200
    finally:
        server.stop()


def test_httpd_http10_gets_unframed_body():
    """An HTTP/1.0 client cannot parse chunked framing: a response without
    content-length must arrive unframed, delimited by connection close
    (ADVICE r3 — previously chunked framing went out regardless)."""
    import socket

    import socket as _socket

    from scalable_hw_agnostic_inference_tpu.serve.asgi import (
        App as AsgiApp,
        StreamingResponse,
    )

    app = AsgiApp()

    @app.get("/stream")
    def stream(request):
        return StreamingResponse(iter(["hello ", "world"]),
                                 media_type="text/plain")

    server = Server(app, host="127.0.0.1", port=0)
    host, port = server.start_background()
    try:
        with socket.create_connection((host, port), timeout=10) as s:
            s.sendall(b"GET /stream HTTP/1.0\r\nhost: x\r\n\r\n")
            raw = b""
            while True:
                b_ = s.recv(65536)
                if not b_:
                    break      # server closed: the HTTP/1.0 delimiter
                raw += b_
        head, _, body = raw.partition(b"\r\n\r\n")
        assert b"200" in head.split(b"\r\n")[0]
        assert b"transfer-encoding" not in head.lower()
        assert b"connection: close" in head.lower()
        assert body == b"hello world"      # unframed, no chunk artifacts
        # HTTP/1.1 on the same route still gets chunked keep-alive framing
        with _socket.create_connection((host, port), timeout=10) as s:
            s.sendall(b"GET /stream HTTP/1.1\r\nhost: x\r\n\r\n")
            raw = b""
            while b"0\r\n\r\n" not in raw:
                raw += s.recv(65536)
        head = raw.lower().partition(b"\r\n\r\n")[0]
        assert b"transfer-encoding: chunked" in head
        assert b"connection: keep-alive" in head
    finally:
        server.stop()


def test_httpd_parallel_probes_during_inference():
    """Health probes answer while the single model lane is busy."""
    cfg = make_cfg()

    class SlowService(EchoService):
        def infer(self, payload):
            time.sleep(0.5)
            return {"echo": "slow"}

    app = create_app(cfg, SlowService(cfg, load_delay=0))
    server = Server(app, host="127.0.0.1", port=0)
    host, port = server.start_background()
    try:
        base = f"http://{host}:{port}"
        with httpx.Client(base_url=base, timeout=10) as warm:
            assert wait_ready_sync(warm).status_code == 200

        results = {}

        def do_infer():
            with httpx.Client(base_url=base, timeout=10) as c:
                results["infer"] = c.post("/predict", json={}).status_code

        t = threading.Thread(target=do_infer)
        t.start()
        time.sleep(0.1)  # inference is now holding the model lane
        t0 = time.perf_counter()
        with httpx.Client(base_url=base, timeout=10) as c:
            assert c.get("/health").status_code == 200
        probe_dt = time.perf_counter() - t0
        t.join()
        assert results["infer"] == 200
        assert probe_dt < 0.4, f"probe blocked behind inference: {probe_dt:.3f}s"
    finally:
        server.stop()


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.asyncio
async def test_serve_ui_and_profile_endpoint(tmp_path):
    """/serve renders the interactive console (reference run-sd.py:203) and
    /profile/{s} captures a jax.profiler trace under the artifact root."""
    import os

    cfg = make_cfg(artifact_root=str(tmp_path))
    service = EchoService(cfg)
    app = create_app(cfg, service)
    async with make_client(app) as c:
        r = await wait_ready(c, timeout=300.0)
        assert r.status_code == 200, r.text

        r = await c.get("/serve")
        assert r.status_code == 200
        assert "text/html" in r.headers["content-type"]
        assert cfg.app in r.text and service.infer_route in r.text

        r = await c.post("/profile/0")
        assert r.status_code == 400
        r = await c.post("/profile/1")
        assert r.status_code == 200, r.text
        trace_dir = r.json()["trace_dir"]
        assert trace_dir.startswith(str(tmp_path))
        # a second trace while one runs is refused
        r2 = await c.post("/profile/5")
        assert r2.status_code == 409
        # trace session closes and leaves artifacts on disk
        for _ in range(80):
            await asyncio.sleep(0.25)
            if os.path.isdir(trace_dir) and any(os.scandir(trace_dir)):
                break
        assert any(os.scandir(trace_dir)), "no trace artifacts written"


def test_server_stop_runs_shutdown_hooks():
    """``Server.request_shutdown`` must run the app's @shutdown hooks
    (cova closes its shared httpx client there) before task teardown —
    the bundled server sends no ASGI lifespan events, so this is the only
    path those hooks have in production."""
    app = App("t")
    ran = {"v": False}

    @app.shutdown
    async def _hook():
        ran["v"] = True

    @app.get("/ping")
    def ping(request):
        return {"ok": True}

    srv = Server(app, host="127.0.0.1", port=0)
    host, port = srv.start_background()
    r = httpx.get(f"http://{host}:{port}/ping")
    assert r.status_code == 200
    srv.stop()
    deadline = time.time() + 5.0
    while not ran["v"] and time.time() < deadline:
        time.sleep(0.01)
    assert ran["v"], "shutdown hooks never ran on server stop"


# ---------------------------------------------------------------------------
# StreamingResponse: an async iterator on the event loop, a sync one pooled
# ---------------------------------------------------------------------------

async def drive_asgi(app, path, body=None, disconnect=None, slow_s=0.0,
                     fail_after=None, method="POST"):
    """One request through ``app`` by raw ASGI, with a ``receive`` and a
    ``send`` of the test's own: the test decides when the client goes away
    (``disconnect``), how slowly the socket takes a chunk and which write
    fails. Returns (status, chunks)."""
    raw = json.dumps(body).encode() if body is not None else b""
    scope = {"type": "http", "method": method, "path": path,
             "query_string": b"", "headers": [
                 (b"content-type", b"application/json"),
                 (b"content-length", str(len(raw)).encode())]}
    asked, out = [False], {"status": None, "chunks": []}
    gone = disconnect or asyncio.Event()

    async def receive():
        if not asked[0]:
            asked[0] = True
            return {"type": "http.request", "body": raw, "more_body": False}
        await gone.wait()
        return {"type": "http.disconnect"}

    async def send(message):
        if message["type"] == "http.response.start":
            out["status"] = message["status"]
        elif message.get("body"):
            if fail_after is not None and len(out["chunks"]) >= fail_after:
                raise ConnectionResetError("the socket died")
            if slow_s:
                await asyncio.sleep(slow_s)
            out["chunks"].append(message["body"])

    await asyncio.wait_for(app(scope, receive, send), timeout=60.0)
    return out["status"], out["chunks"]


async def _drive_stream(app, path, gone=None, fail_after=None):
    _, chunks = await drive_asgi(app, path, disconnect=gone,
                                 fail_after=fail_after, method="GET")
    return chunks


def _stream_app(make_iterator, **kw):
    from scalable_hw_agnostic_inference_tpu.serve.asgi import (
        StreamingResponse,
    )

    app = App("t")

    @app.get("/s")
    def s(request):
        return StreamingResponse(make_iterator(), **kw)

    return app


@pytest.mark.asyncio
@pytest.mark.parametrize("kind", ["async", "sync"])
async def test_an_async_iterator_is_drained_on_the_loop_a_sync_one_pooled(kind):
    """The same chunks either way; the async generator's body runs on the
    event loop's own thread, the sync one's on an ``sse-stream`` thread."""
    ran_on, sizes = [], []

    async def agen():
        for c in ("ab", "", b"cde", "f"):
            ran_on.append(threading.current_thread().name)
            await asyncio.sleep(0)
            yield c

    def gen():
        for c in ("ab", "", b"cde", "f"):
            ran_on.append(threading.current_thread().name)
            yield c

    app = _stream_app(agen if kind == "async" else gen, on_sent=sizes.append)
    chunks = await _drive_stream(app, "/s")
    assert chunks == [b"ab", b"cde", b"f"] and sizes == [2, 3, 1]
    here = threading.current_thread().name
    if kind == "async":
        assert set(ran_on) == {here}
    else:
        assert all(n.startswith("sse-stream") for n in ran_on), ran_on


@pytest.mark.asyncio
@pytest.mark.parametrize("how", ["client_goes_away_while_it_waits",
                                 "write_fails"])
async def test_an_abandoned_async_iterator_is_closed_at_once(how):
    """The generator's ``finally`` is the cancellation seam: it runs as soon
    as the client is gone, also while the generator waits for something
    that never comes, with no thread to wait out."""
    closed, never = [], asyncio.Event()

    async def agen():
        try:
            yield "one"
            if how == "write_fails":
                yield "two"
            await never.wait()
            yield "never"
        finally:
            closed.append(time.monotonic())

    gone = asyncio.Event()
    app = _stream_app(agen)
    call = asyncio.ensure_future(_drive_stream(
        app, "/s", gone=gone, fail_after=1 if how == "write_fails" else None))
    if how != "write_fails":
        await asyncio.sleep(0.05)
        assert not call.done() and closed == []
        t0 = time.monotonic()
        gone.set()
    else:
        t0 = time.monotonic()
    chunks = await asyncio.wait_for(call, timeout=5.0)
    assert chunks == [b"one"]
    assert len(closed) == 1 and closed[0] - t0 < 0.2


@pytest.mark.asyncio
async def test_an_async_iterator_that_raises_fails_the_request_not_the_loop():
    closed = []

    async def agen():
        try:
            yield "one"
            raise RuntimeError("the producer broke")
        finally:
            closed.append(True)

    app = _stream_app(agen)
    with pytest.raises(RuntimeError, match="the producer broke"):
        await _drive_stream(app, "/s")
    assert closed == [True]


def test_httpd_streams_an_async_iterator_chunked():
    """Through the real server: chunked framing, several events in a chunk
    stay one chunk, and the connection is reusable behind the stream."""
    import http.client

    async def agen():
        yield "data: a\n\n"
        await asyncio.sleep(0.01)
        yield "data: b\n\ndata: c\n\n"
        yield "data: [DONE]\n\n"

    server = Server(_stream_app(agen), host="127.0.0.1", port=0)
    host, port = server.start_background()
    try:
        conn = http.client.HTTPConnection(host, port, timeout=10)
        for _ in range(2):          # the second rides the same connection
            conn.request("GET", "/s")
            r = conn.getresponse()
            assert r.status == 200
            assert r.getheader("transfer-encoding") == "chunked"
            assert r.read() == (b"data: a\n\ndata: b\n\ndata: c\n\n"
                                b"data: [DONE]\n\n")
        conn.close()
    finally:
        server.stop()
