"""What counts as failed, against a stub server that misbehaves on purpose:
a shed (429), an error mid-stream, a closed connection and a stall count as
failed, each with its cause written; an early stop on EOS is complete."""

import asyncio
import json

import pytest

from benchmark import client, stats
from benchmark.traffic import Planned


def _event(text="", finish=None):
    return ("data: " + json.dumps({"choices": [
        {"index": 0, "text": text, "finish_reason": finish}]}) + "\n\n")


def _chunk(payload: str) -> bytes:
    raw = payload.encode()
    return f"{len(raw):x}\r\n".encode() + raw + b"\r\n"


HEAD_200 = (b"HTTP/1.1 200 OK\r\ncontent-type: text/event-stream\r\n"
            b"transfer-encoding: chunked\r\nconnection: close\r\n\r\n")


async def _behave(kind, n_out, writer):
    if kind == "shed":
        body = json.dumps({"detail": "admission queue is full, retry later"})
        writer.write(f"HTTP/1.1 429 Too Many Requests\r\nretry-after: 1\r\n"
                     f"content-length: {len(body)}\r\n\r\n{body}".encode())
        return
    if kind == "draining":
        body = json.dumps({"detail": "pod is draining"})
        writer.write(f"HTTP/1.1 503 Service Unavailable\r\n"
                     f"content-length: {len(body)}\r\n\r\n{body}".encode())
        return
    writer.write(HEAD_200)
    if kind == "stall":
        await writer.drain()
        await asyncio.sleep(1.0)
        return
    n = {"ok": n_out, "eos": 2, "eos_at_once": 0}.get(kind, 2)
    for _ in range(n):
        writer.write(_chunk(_event("a")))
        await writer.drain()
    if kind == "sse_error":
        writer.write(_chunk("data: " + json.dumps({"error": {
            "message": "deadline exceeded", "type": "timeout_error"}})
            + "\n\n"))
        writer.write(_chunk("data: [DONE]\n\n") + b"0\r\n\r\n")
    elif kind == "close":
        pass                               # no finish, no [DONE], no 0 chunk
    elif kind == "no_done":
        writer.write(_chunk(_event("", "length")) + b"0\r\n\r\n")
    else:
        finish = "length" if kind == "ok" else "stop"
        writer.write(_chunk(_event("", finish)))
        writer.write(_chunk("data: [DONE]\n\n") + b"0\r\n\r\n")


async def _serve_and_send(kinds, tmp_path, timeout_s=1.0):
    async def handle(reader, writer):
        head = await reader.readuntil(b"\r\n\r\n")
        n = int([ln for ln in head.split(b"\r\n")
                 if ln.lower().startswith(b"content-length")][0].split(b":")[1])
        body = json.loads(await reader.readexactly(n))
        try:
            await _behave(body["prompt"], body["max_tokens"], writer)
            await writer.drain()
        finally:
            writer.close()

    server = await asyncio.start_server(handle, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    log = client.FailureLog(str(tmp_path / "failures.jsonl"), "cell", 7,
                            lambda: 3, lambda: 0.0)
    gen = client.LoadGenerator("127.0.0.1", port, timeout_s, log)
    async with server:
        for i, kind in enumerate(kinds):
            await gen._one(Planned(i, 0.0, 10, 4, kind), 0.0, True)
    return gen.records, log


CASES = [
    ("ok", None, 4), ("eos", None, 2), ("eos_at_once", None, 0),
    ("shed", "http_429", 0), ("draining", "http_503", 0),
    ("sse_error", "sse_in_band", 2),
    ("close", "transport__Malformed", 2),
    ("no_done", "transport__Malformed", 2), ("stall", "timeout", 0),
]


@pytest.mark.parametrize("kind,cause,n_tokens", CASES)
def test_classification(kind, cause, n_tokens, tmp_path):
    records, log = asyncio.run(_serve_and_send(
        [kind], tmp_path, timeout_s=0.3 if kind == "stall" else 5.0))
    (rec,) = records
    assert rec.n_tokens == n_tokens
    if cause is None:
        assert rec.failure is None and rec.well_formed
        assert stats.accounts_for_its_tokens(rec)
        assert log.lines == []
    else:
        assert rec.failure["cause"] == cause
        assert rec.failure["phase"] == ("mid_stream" if n_tokens
                                        else "before_first_token")
        (line,) = log.lines
        assert line["cell"] == "cell" and line["seed"] == 7
        assert line["request"] == 0 and line["waiting_at_last_poll"] == 3
        assert line["cause"] == cause and line["detail"]
        on_disk = [json.loads(x) for x in
                   (tmp_path / "failures.jsonl").read_text().splitlines()]
        assert on_disk == [line]


def test_the_sheds_detail_is_the_servers_own_words(tmp_path):
    records, log = asyncio.run(_serve_and_send(["shed"], tmp_path))
    assert "admission queue is full" in log.lines[0]["detail"]
    assert log.lines[0]["status"] == 429


def test_counts_over_a_mixed_run(tmp_path):
    kinds = ["ok", "eos", "shed", "sse_error", "close", "ok"]
    records, log = asyncio.run(_serve_and_send(kinds, tmp_path))
    s = stats.summary(records, -1.0, 1e12)
    assert (s["attempted"], s["failed"], s["eos_stops"]) == (6, 3, 1)
    assert len(log.lines) == 3
    # no retry: one record a request, in order
    assert [r.plan.prompt for r in records] == kinds


def test_closed_loop_stops_sending_when_the_window_closes(tmp_path):
    import time

    async def go():
        async def handle(reader, writer):
            head = await reader.readuntil(b"\r\n\r\n")
            n = int([ln for ln in head.split(b"\r\n") if ln.lower()
                     .startswith(b"content-length")][0].split(b":")[1])
            await reader.readexactly(n)
            await _behave("ok", 3, writer)
            await writer.drain()
            writer.close()

        server = await asyncio.start_server(handle, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        gen = client.LoadGenerator("127.0.0.1", port, 5.0, lambda r: None)
        plans = [Planned(i, None, 10, 3, "ok") for i in range(100000)]
        now = time.monotonic()
        async with server:
            await gen.closed_loop(plans, 3, now, now + 0.05, now + 0.25)
        return gen.records, now

    records, now = asyncio.run(go())
    assert records and all(r.failure is None for r in records)
    # the decision to send is taken at ``due``; the bytes follow at once
    assert all(r.due < now + 0.25 for r in records)
    assert any(not r.in_window for r in records)      # the warm-up's
    assert all(r.in_window == (now + 0.05 <= r.due < now + 0.25)
               for r in records)
