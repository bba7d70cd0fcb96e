"""The load generator: one asyncio loop, one connection per request.

Every request is a ``POST /v1/completions`` with ``stream: true`` over a
connection of its own (``connection: close``), so none rides a connection
the server may already have closed. There is no retry: a retry hides a
failure. Every request sent is awaited to its end, after the window if need
be, under a timeout far above any latency seen.

What counts as failed (the rule is here and nowhere else): an attempted
request that did not end in HTTP 200 with a well-formed stream — a shed
(429/503), any other status, an in-band SSE error, a transport error, a
timeout. A request the model ended on EOS before its asked length is
COMPLETE: it counts with the tokens it gave. Each failure writes one line,
with its cause, to stderr and to ``failures.jsonl``.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import sys
import time
from typing import Any, Callable, Dict, List, Optional

from .traffic import Planned


@dataclasses.dataclass
class Record:
    """What the client saw of one request. Times are ``time.monotonic()``."""

    plan: Planned
    due: float = 0.0            # when it was due (open loop) or sent (closed)
    sent: float = 0.0
    token_times: List[float] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    status: int = 0
    done: float = 0.0
    in_window: bool = False
    #: None when complete; else {"cause", "detail", "phase", ...}
    failure: Optional[Dict[str, Any]] = None
    #: the stream ended as the protocol says: a finish event, ``[DONE]``, the
    #: zero chunk
    well_formed: bool = False

    @property
    def n_tokens(self) -> int:
        return len(self.token_times)


class _Malformed(Exception):
    pass


async def _read_head(reader: asyncio.StreamReader):
    line = await reader.readline()
    parts = line.decode("latin-1").split(" ", 2)
    if len(parts) < 2 or not parts[1].isdigit():
        raise _Malformed(f"bad status line {line[:80]!r}")
    headers = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n"):
            break
        if not line:
            raise _Malformed("connection closed inside the headers")
        k, _, v = line.decode("latin-1").partition(":")
        headers[k.strip().lower()] = v.strip()
    return int(parts[1]), headers


async def _chunks(reader: asyncio.StreamReader):
    """Payloads of a chunked body; returns after the zero chunk. EOF before
    it raises ``IncompleteReadError`` or ``_Malformed``."""
    while True:
        size_line = await reader.readline()
        if not size_line:
            raise _Malformed("connection closed before the last chunk")
        try:
            size = int(size_line.split(b";")[0].strip(), 16)
        except ValueError:
            raise _Malformed(f"bad chunk size {size_line[:40]!r}") from None
        if size == 0:
            await reader.readline()
            return
        data = await reader.readexactly(size + 2)
        yield data[:-2]


async def _stream(rec: Record, host: str, port: int, body: bytes) -> None:
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(
            b"POST /v1/completions HTTP/1.1\r\nhost: bench\r\n"
            b"content-type: application/json\r\nconnection: close\r\n"
            b"content-length: " + str(len(body)).encode() + b"\r\n\r\n"
            + body)
        await writer.drain()
        rec.status, headers = await _read_head(reader)
        if rec.status != 200:
            n = int(headers.get("content-length", "0") or 0)
            raw = await (reader.readexactly(n) if n else reader.read(4096))
            rec.failure = {"cause": f"http_{rec.status}",
                           "detail": raw.decode("utf-8", "replace")[:500]}
            return
        if headers.get("transfer-encoding", "").lower() != "chunked":
            raise _Malformed("200 without a chunked event stream")
        buf = b""
        saw_done = False
        async for payload in _chunks(reader):
            now = time.monotonic()
            buf += payload
            while b"\n\n" in buf:
                event, buf = buf.split(b"\n\n", 1)
                if not event.startswith(b"data: "):
                    raise _Malformed(f"not an SSE data event: {event[:60]!r}")
                data = event[6:]
                if data == b"[DONE]":
                    saw_done = True
                    continue
                obj = json.loads(data)
                if "error" in obj or "migrated" in obj:
                    rec.failure = {"cause": "sse_in_band",
                                   "detail": json.dumps(obj)[:500]}
                    continue
                choice = obj["choices"][0]
                # the benchmark's tokenizer renders each id as one character
                rec.token_times.extend([now] * len(choice.get("text", "")))
                if choice.get("finish_reason"):
                    rec.finish_reason = choice["finish_reason"]
        if rec.failure is None:
            if not (saw_done and rec.finish_reason):
                raise _Malformed("stream ended without finish_reason and "
                                 "[DONE]")
            rec.well_formed = True
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionError, OSError):
            pass


class LoadGenerator:
    """Sends a planned schedule at one server and keeps every record."""

    def __init__(self, host: str, port: int, timeout_s: float,
                 on_failure: Callable[[Record], None]):
        self.host, self.port = host, port
        self.timeout_s = timeout_s
        self.on_failure = on_failure
        self.records: List[Record] = []

    async def _one(self, plan: Planned, due: float,
                   in_window: bool) -> Record:
        rec = Record(plan=plan, due=due, in_window=in_window)
        self.records.append(rec)
        body = json.dumps({"prompt": plan.prompt, "max_tokens": plan.n_out,
                           "temperature": 0.0, "stream": True}).encode()
        rec.sent = time.monotonic()
        try:
            await asyncio.wait_for(
                _stream(rec, self.host, self.port, body), self.timeout_s)
        except asyncio.TimeoutError:
            rec.failure = {"cause": "timeout",
                           "detail": f"no end within {self.timeout_s:.0f}s"}
        except (_Malformed, json.JSONDecodeError, KeyError,
                asyncio.IncompleteReadError, ConnectionError, OSError) as e:
            rec.failure = {"cause": f"transport_{type(e).__name__}",
                           "detail": str(e)[:500]}
        rec.done = time.monotonic()
        if rec.failure is not None:
            rec.failure["phase"] = ("mid_stream" if rec.token_times
                                    else "before_first_token")
            self.on_failure(rec)
        return rec

    async def open_loop(self, plans: List[Planned], t0: float, t1: float):
        """Each request goes out when it is due, whatever became of the
        earlier ones; a request due in ``[t0, t1)`` is attempted."""
        loop = asyncio.get_running_loop()
        tasks = []
        for plan in plans:
            due = t0 + plan.due_s
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(loop.create_task(
                self._one(plan, due, 0.0 <= plan.due_s < t1 - t0)))
        await asyncio.gather(*tasks)

    async def closed_loop(self, plans: List[Planned], clients: int,
                          t_start: float, t0: float, t1: float):
        """``clients`` callers, each sending its next request when its last
        one ended; none is sent once the window has closed. A request sent
        in ``[t0, t1)`` is attempted."""
        todo = iter(plans)

        async def caller():
            while True:
                now = time.monotonic()
                if now >= t1:
                    return
                await self._one(next(todo), now, t0 <= now < t1)

        delay = t_start - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        await asyncio.gather(*[caller() for _ in range(clients)])


class FailureLog:
    """One line per failed request, to stderr and to ``failures.jsonl``."""

    def __init__(self, path: str, cell: str, seed: int,
                 waiting_now: Callable[[], Any], t0: Callable[[], float]):
        self.path, self.cell, self.seed = path, cell, seed
        self.waiting_now, self.t0 = waiting_now, t0
        self.lines: List[Dict[str, Any]] = []

    def __call__(self, rec: Record) -> None:
        t0 = self.t0()
        line = {"cell": self.cell, "seed": self.seed,
                "request": rec.plan.index, "in_window": rec.in_window,
                "due_s": round(rec.due - t0, 4),
                "sent_s": round(rec.sent - t0, 4),
                "failed_s": round(rec.done - t0, 4),
                "n_prompt": rec.plan.n_prompt, "n_out": rec.plan.n_out,
                "tokens_seen": rec.n_tokens, "status": rec.status,
                "waiting_at_last_poll": self.waiting_now(), **rec.failure}
        self.lines.append(line)
        text = json.dumps(line)
        print("FAILED REQUEST " + text, file=sys.stderr, flush=True)
        with open(self.path, "a") as f:
            f.write(text + "\n")
