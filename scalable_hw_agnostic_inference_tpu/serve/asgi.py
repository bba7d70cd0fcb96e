"""Minimal ASGI micro-framework — the serving runtime's HTTP substrate.

The reference serves every model through FastAPI+uvicorn installed at pod
start (reference ``app/run-sd.sh:3-14``, ``app/run-sd.py:148-151``). This
framework ships its own substrate instead: a dependency-free ASGI-3 router
(this module) plus a stdlib asyncio HTTP server (``serve.httpd``). Apps built
here are standard ASGI apps, so they also run under any external ASGI server
and are unit-testable in-process via ``httpx.ASGITransport``.

Route patterns support ``{name}`` (string) and ``{name:int}`` segments, e.g.
the reference's benchmark surface ``GET /load/{n_runs}/infer/{n_inf}``
(reference ``app/run-sd.py:157-175``).
"""

from __future__ import annotations

import inspect
import json
import logging
import re
import time
import traceback
from typing import Any, Awaitable, Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qsl

from ..obs import trace as obs_trace

log = logging.getLogger(__name__)


class HTTPError(Exception):
    """Raise inside a handler to return a non-200 JSON error.

    ``headers``: extra response headers — the shed/backoff paths use it to
    carry ``Retry-After`` on 429/503 so clients and meshes back off
    instead of hammering a saturated or draining pod."""

    def __init__(self, status: int, detail: str,
                 headers: Optional[Dict[str, str]] = None):
        super().__init__(detail)
        self.status = status
        self.detail = detail
        self.headers = dict(headers or {})


class Request:
    """One HTTP request as seen by a handler."""

    def __init__(self, scope: Dict, body: bytes, t_begin: float = 0.0):
        # monotonic stamp the app took as it began the request, before the
        # body was read: the root span starts here, and so does the
        # request's way in (``ingress_seconds``)
        self.t_begin = t_begin or time.monotonic()
        self.method: str = scope["method"].upper()
        self.path: str = scope["path"]
        self.headers: Dict[str, str] = {
            k.decode("latin-1").lower(): v.decode("latin-1")
            for k, v in scope.get("headers", [])
        }
        self.query: Dict[str, str] = dict(
            parse_qsl(scope.get("query_string", b"").decode("latin-1"))
        )
        self.path_params: Dict[str, Any] = {}
        self.body: bytes = body
        self.route_matched = False  # set by dispatch when a handler runs
        # request-scoped trace (obs.trace), set by the app when tracing is
        # on; handlers may open child spans through the contextvar API
        self.trace: Optional["obs_trace.Trace"] = None

    def json(self) -> Any:
        if not self.body:
            return {}
        try:
            return json.loads(self.body)
        except json.JSONDecodeError as e:
            raise HTTPError(400, f"invalid JSON body: {e}") from e


class Response:
    def __init__(
        self,
        content: Any = None,
        status: int = 200,
        media_type: str = "application/json",
        headers: Optional[Dict[str, str]] = None,
    ):
        self.status = status
        self.headers = dict(headers or {})
        if isinstance(content, (bytes, bytearray)):
            self.body = bytes(content)
            self.headers.setdefault("content-type", media_type)
        elif isinstance(content, str):
            self.body = content.encode()
            self.headers.setdefault(
                "content-type",
                media_type if media_type != "application/json" else "text/plain; charset=utf-8",
            )
        else:
            self.body = json.dumps(content).encode()
            self.headers.setdefault("content-type", "application/json")
        self.headers.setdefault("content-length", str(len(self.body)))


class StreamingResponse(Response):
    """Incrementally-produced body (SSE token streams). ``iterator`` yields
    ``str``/``bytes`` chunks. An ASYNC iterator is driven on the server's
    event loop itself, one ``anext`` a chunk and no thread: it must not
    block (the vllm unit's token stream waits on a waker the engine loop
    sets once a step, and sends everything its queue holds as one event,
    so a delta may carry several tokens when a stream has fallen behind).
    A SYNC iterator is driven on a ``_stream_pool`` thread, a pull a
    chunk, so one that blocks (a queue, a file) does not stall the loop.
    No content-length: the server sends it chunked-encoded.

    ``on_sent``: called by the drain, on the event loop, with the byte
    count of each chunk it wrote (the stream's own accounting: a token's
    way out ends there). ``annotate_write``: the drain writes a
    ``serve.stream.write`` profiler annotation around each send (the
    owner samples: one stream in sixteen)."""

    def __init__(self, iterator, status: int = 200,
                 media_type: str = "text/event-stream",
                 headers: Optional[Dict[str, str]] = None,
                 on_sent: Optional[Callable[[int], None]] = None,
                 annotate_write: bool = False):
        self.on_sent = on_sent
        self.annotate_write = annotate_write
        self.status = status
        self.headers = dict(headers or {})
        self.headers.setdefault("content-type", media_type)
        self.headers.setdefault("cache-control", "no-store")
        self.body = b""
        self.iterator = iterator


_SEGMENT = re.compile(r"\{(\w+)(?::(int|float|path))?\}")
_CASTS = {"int": int, "float": float, None: str, "path": str}

_STREAM_POOL = None


def _stream_pool():
    """Executor for what a stream may block on: the chunk pulls of a
    StreamingResponse over a SYNC iterator (see ``_drain_pooled``), and
    the rare blocking step of an async one (a migration's hand-off). The
    engine's token streams are async iterators and take no thread here."""
    global _STREAM_POOL
    if _STREAM_POOL is None:
        from concurrent.futures import ThreadPoolExecutor

        # one thread per concurrently-live pooled stream; idle threads cost
        # only stack pages
        _STREAM_POOL = ThreadPoolExecutor(max_workers=64,
                                          thread_name_prefix="sse-stream")
    return _STREAM_POOL


def _compile_pattern(pattern: str) -> Tuple[re.Pattern, Dict[str, Callable]]:
    casts: Dict[str, Callable] = {}
    out = []
    last = 0
    for m in _SEGMENT.finditer(pattern):
        out.append(re.escape(pattern[last : m.start()]))
        name, kind = m.group(1), m.group(2)
        casts[name] = _CASTS[kind]
        out.append(f"(?P<{name}>{'.+' if kind == 'path' else '[^/]+'})")
        last = m.end()
    out.append(re.escape(pattern[last:]))
    return re.compile("^" + "".join(out) + "$"), casts


class Route:
    def __init__(self, method: str, pattern: str, handler: Callable):
        self.method = method.upper()
        self.pattern = pattern
        self.regex, self.casts = _compile_pattern(pattern)
        self.handler = handler

    def match_path(self, path: str) -> Optional[Dict[str, Any]]:
        """Params dict when path + casts match, else None (method-agnostic)."""
        m = self.regex.match(path)
        if not m:
            return None
        params: Dict[str, Any] = {}
        for k, v in m.groupdict().items():
            try:
                params[k] = self.casts[k](v)
            except ValueError:
                return None
        return params


class App:
    """ASGI-3 application with decorator routing and startup hooks."""

    def __init__(self, title: str = "shai-tpu"):
        self.title = title
        self.routes: List[Route] = []
        self.on_startup: List[Callable[[], Any]] = []
        self.on_shutdown: List[Callable[[], Any]] = []
        self.state: Dict[str, Any] = {}
        self._started = False
        # completed request traces go here (serve.app points it at the
        # flight recorder); None = drop them after the response
        self.trace_sink: Optional[Callable[[Dict[str, Any]], None]] = None
        # probe/scrape surfaces stay untraced: a kubelet polling /readiness
        # at 2 Hz (or the capacity checker / cova /fleet polling /stats)
        # would evict every real request from the flight ring
        self.trace_exclude = {"/health", "/readiness", "/metrics", "/stats",
                              "/debug/flight"}
        # compiled patterns for parameterized trace_exclude entries
        # ("/trace/{trace_id}"): lazily built, cached per literal
        self._exclude_patterns: Dict[str, re.Pattern] = {}

    # -- registration ------------------------------------------------------
    def route(self, pattern: str, methods: Tuple[str, ...] = ("GET",)):
        def deco(fn):
            for m in methods:
                self.routes.append(Route(m, pattern, fn))
            return fn

        return deco

    def get(self, pattern: str):
        return self.route(pattern, ("GET",))

    def post(self, pattern: str):
        return self.route(pattern, ("POST",))

    def startup(self, fn):
        self.on_startup.append(fn)
        return fn

    def shutdown(self, fn):
        self.on_shutdown.append(fn)
        return fn

    # -- lifecycle ---------------------------------------------------------
    async def _run_startup(self):
        if self._started:
            return
        self._started = True
        for fn in self.on_startup:
            r = fn()
            if inspect.isawaitable(r):
                await r

    async def _run_shutdown(self):
        for fn in self.on_shutdown:
            r = fn()
            if inspect.isawaitable(r):
                await r

    def _trace_excluded(self, path: str) -> bool:
        """Whether ``path`` sits on the untraced poll/bulk surface.
        ``trace_exclude`` entries are literals; entries containing ``{``
        are route patterns (``/trace/{trace_id}``) compiled on first use."""
        if path in self.trace_exclude:
            return True
        for entry in self.trace_exclude:
            if "{" not in entry:
                continue
            rx = self._exclude_patterns.get(entry)
            if rx is None:
                rx = _compile_pattern(entry)[0]
                self._exclude_patterns[entry] = rx
            if rx.match(path):
                return True
        return False

    # -- dispatch ----------------------------------------------------------
    async def _dispatch(self, request: Request) -> Response:
        allowed: List[str] = []
        for route in self.routes:
            params = route.match_path(request.path)
            if params is None:
                continue
            if request.method != route.method:
                allowed.append(route.method)
                continue
            request.path_params = params
            request.route_matched = True
            result = route.handler(request, **params)
            if inspect.isawaitable(result):
                result = await result
            if isinstance(result, Response):
                return result
            return Response(result)
        if allowed:
            return Response({"detail": "method not allowed"}, status=405)
        return Response({"detail": f"not found: {request.path}"}, status=404)

    async def __call__(self, scope: Dict, receive: Callable[[], Awaitable], send: Callable):
        if scope["type"] == "lifespan":
            while True:
                message = await receive()
                if message["type"] == "lifespan.startup":
                    try:
                        await self._run_startup()
                        await send({"type": "lifespan.startup.complete"})
                    except Exception as e:  # pragma: no cover
                        await send({"type": "lifespan.startup.failed", "message": str(e)})
                elif message["type"] == "lifespan.shutdown":
                    await self._run_shutdown()
                    await send({"type": "lifespan.shutdown.complete"})
                    return
            return
        if scope["type"] != "http":  # pragma: no cover
            raise RuntimeError(f"unsupported scope type {scope['type']}")

        # Serving under httpx.ASGITransport (tests) never sends lifespan —
        # run startup lazily so in-process apps behave like served ones.
        await self._run_startup()

        t_begin = time.monotonic()
        body = b""
        while True:
            message = await receive()
            if message["type"] == "http.request":
                body += message.get("body", b"")
                if not message.get("more_body"):
                    break
            elif message["type"] == "http.disconnect":  # pragma: no cover
                return

        request = Request(scope, body, t_begin)
        # W3C trace-context ingest: a valid upstream traceparent continues
        # the caller's trace id; otherwise (or with tracing off → None) a
        # fresh trace roots here. The whole request — dispatch, model call,
        # stream drain — lives under ONE root span.
        tr = None
        tp_header = request.headers.get("traceparent")
        if not self._trace_excluded(request.path):
            tr = obs_trace.begin_request_trace(
                f"{request.method} {request.path}", tp_header,
                t_begin=t_begin, method=request.method, path=request.path)
        elif obs_trace.parse_traceparent(tp_header) is not None:
            # excluded surfaces begin a trace ONLY when the caller sent a
            # valid traceparent: bare poll traffic (kubelet, /stats scrape)
            # stays off the flight ring, while correlated fleet hops
            # (/kv/blocks, /kv/pull, /kv/migrate from a traced request)
            # join the caller's trace as server-side child spans
            tr = obs_trace.begin_request_trace(
                f"{request.method} {request.path}", tp_header,
                t_begin=t_begin, method=request.method, path=request.path)
        request.trace = tr

        def _finish_trace(status: int) -> None:
            if tr is None or tr.root.closed:
                return
            tr.root.attrs["status"] = status
            tr.close()
            # unrouted traffic (scanner 404s, misconfigured probes at 2 Hz)
            # must not turn over the flight ring: the trace still closes
            # (traceparent header, annotations) but only requests a real
            # handler served are sunk for postmortems
            if not getattr(request, "route_matched", False):
                return
            sink = self.trace_sink
            if sink is not None:
                try:
                    sink(tr.to_dict())
                except Exception:  # recorder trouble must not fail requests
                    log.exception("trace sink failed")

        with obs_trace.use_trace(tr):
            try:
                response = await self._dispatch(request)
            except HTTPError as e:
                response = Response({"detail": e.detail}, status=e.status,
                                    headers=e.headers)
            except Exception:
                log.error("handler error on %s %s\n%s", request.method,
                          request.path, traceback.format_exc())
                response = Response({"detail": "internal server error"},
                                    status=500)
        if tr is not None:
            # traceparent emit: downstream hops (and the client) can join
            # their spans to this request's trace id
            response.headers.setdefault("traceparent", tr.traceparent)

        # try/finally: an aborted request (client disconnect mid-stream, a
        # generator raising after headers went out) must STILL close and
        # sink its trace — failed requests are the ones postmortems need
        try:
            await send(
                {
                    "type": "http.response.start",
                    "status": response.status,
                    "headers": [
                        (k.encode("latin-1"), v.encode("latin-1"))
                        for k, v in response.headers.items()
                    ],
                }
            )
            if isinstance(response, StreamingResponse):
                await self._drain_stream(response, receive, send)
                return
            await send({"type": "http.response.body", "body": response.body})
        finally:
            # the root span covers the DRAIN, not just the handler return —
            # an SSE token stream's trace ends with its last token
            _finish_trace(response.status)

    async def _drain_stream(self, response: "StreamingResponse",
                            receive: Callable[[], Awaitable],
                            send: Callable) -> None:
        """Pump a StreamingResponse to the client while watching for
        ``http.disconnect``.

        A client that goes away mid-SSE must not leave its generator
        running and the engine decoding for a dead socket until
        ``max_new_tokens``: when the ASGI disconnect message comes first,
        the generator is CLOSED — its ``finally`` path is the cancellation
        seam every streaming handler already owns (e.g. the vllm unit's
        ``loop.cancel(fut)``), so abandoned requests free their KV blocks
        and slot the same way an explicit stop sequence does. A failed
        socket write is treated identically (the disconnect often shows up
        there first).

        Two ways to pull, by the iterator's kind, one way to write
        (``write``): an async iterator on this event loop
        (``_drain_async``), a sync one through the pool
        (``_drain_pooled``).
        """
        import asyncio

        on_sent, annotated = response.on_sent, response.annotate_write

        async def write(chunk) -> bool:
            """One chunk onto the socket and into the stream's accounts;
            False: the socket died mid-write."""
            if isinstance(chunk, str):
                chunk = chunk.encode()
            if not chunk:
                return True
            message = {"type": "http.response.body", "body": chunk,
                       "more_body": True}
            try:
                if annotated:
                    # the write itself, on the profiler's clock; the send
                    # suspends only behind a full socket buffer
                    with obs_trace.annotate("serve.stream.write"):
                        await send(message)
                else:
                    await send(message)
            except Exception:
                return False
            if on_sent is not None:
                on_sent(len(chunk))
            return True

        async def _until_disconnect():
            # receive() contract after the request body: the next message
            # is http.disconnect once the client actually goes away
            # (serve.httpd blocks until socket EOF; httpx.ASGITransport
            # resolves at response end). A transport error counts too.
            try:
                while True:
                    message = await receive()
                    if message["type"] == "http.disconnect":
                        return
            except Exception:
                return

        gone = asyncio.ensure_future(_until_disconnect())
        try:
            if hasattr(response.iterator, "__aiter__"):
                whole = await self._drain_async(response.iterator, write,
                                                gone)
            else:
                whole = await self._drain_pooled(response.iterator, write,
                                                 gone)
            if whole:
                await send({"type": "http.response.body", "body": b""})
        finally:
            gone.cancel()
            try:
                await gone
            except (asyncio.CancelledError, Exception):
                pass

    @staticmethod
    async def _drain_async(iterator, write, gone) -> bool:
        """Drive an async iterator here, on the event loop: no thread, no
        executor future, no wait set a chunk. The pump is a task of its
        own so that the disconnect can cancel it wherever it waits (in the
        generator's idle wait, or in a write behind a full socket buffer);
        the ``aclose()`` behind it runs the generator's ``finally`` at
        once. True: the stream ended whole."""
        import asyncio

        ait = iterator.__aiter__()

        async def pump() -> bool:
            async for chunk in ait:
                if not await write(chunk):
                    return False    # socket died mid-write
            return True

        task = asyncio.ensure_future(pump())

        def client_gone(_):
            task.cancel()

        gone.add_done_callback(client_gone)
        try:
            return await task
        except asyncio.CancelledError:
            if asyncio.current_task().cancelling():
                raise       # this request's own task was cancelled
            return False    # the client went away mid-stream
        finally:
            gone.remove_done_callback(client_gone)
            aclose = getattr(ait, "aclose", None)
            if aclose is not None:
                try:
                    await aclose()
                except Exception:
                    log.exception("stream iterator close failed")

    @staticmethod
    async def _drain_pooled(iterator, write, gone) -> bool:
        """Drive a sync iterator through ``_stream_pool``, a pull a chunk
        raced against the disconnect. True: the stream ended whole."""
        import asyncio

        loop = asyncio.get_event_loop()
        it = iter(iterator)
        _END = object()

        def _next():
            try:
                return next(it)
            except StopIteration:
                return _END

        def _close():
            close = getattr(it, "close", None)
            if close is not None:
                try:
                    close()
                except Exception:
                    log.exception("stream iterator close failed")

        pull = None
        aborted = False
        try:
            while True:
                # dedicated pool: each live pooled stream parks one thread
                # in _next (possibly for minutes); the default executor is
                # capped at min(32, cpus+4) and shared with asyncio
                # internals (getaddrinfo), so saturating it stalls every
                # OTHER stream and DNS lookup (ADVICE r3)
                pull = loop.run_in_executor(_stream_pool(), _next)
                done, _ = await asyncio.wait(
                    {pull, gone}, return_when=asyncio.FIRST_COMPLETED)
                if gone in done and pull not in done:
                    aborted = True  # client went away mid-stream
                    break
                chunk = pull.result()
                if chunk is _END:
                    break
                if not await write(chunk):
                    aborted = True  # socket died mid-write
                    break
            return not aborted
        finally:
            if aborted:
                # a generator cannot be closed while executing: wait for
                # the in-flight pull (a generator that polls a bounded
                # queue makes this short), then close on a pool thread so
                # the handler's finally-path runs off-loop
                if pull is not None and not pull.done():
                    try:
                        await asyncio.wait_for(
                            asyncio.shield(pull), timeout=5.0)
                    except Exception:
                        # pull is stuck past any sane bound — close as
                        # soon as it returns; the thread is leaked until
                        # then, which the log makes visible
                        log.warning("abandoned stream still pulling; "
                                    "deferring generator close")
                        pull.add_done_callback(lambda f: _close())
                        pull = None
                if pull is not None:
                    await loop.run_in_executor(_stream_pool(), _close)
