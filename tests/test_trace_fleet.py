"""Fleet-scope distributed tracing (PR 18): one request is ONE trace
across cova + pods. Covers the W3C traceparent codec properties, the
flight ring's trace index (vs a walk-based oracle), the poll-route /
trace-exclude regression pins, cross-pod assembly + the per-category
latency autopsy, the per-pod ``GET /trace/{id}`` lookup, the disabled-
tracing no-op contract on every new seam, and the two-pod live
acceptance run (migration handoff under one trace id, ≥ 90% of wall
time attributed)."""

import asyncio
import json
import random
import threading
import time

import pytest

import jax  # noqa: F401  (platform pinned in conftest before backends init)

from scalable_hw_agnostic_inference_tpu.obs import FlightRecorder
from scalable_hw_agnostic_inference_tpu.obs import autopsy as obs_autopsy
from scalable_hw_agnostic_inference_tpu.obs import trace as obs_trace
from scalable_hw_agnostic_inference_tpu.resilience import faults as rz_faults

from test_serve_http import EchoService, make_cfg, make_client, wait_ready
from test_migrate import migrate_pods, _write_vllm_yaml  # noqa: F401


# ---------------------------------------------------------------------------
# W3C traceparent codec: round-trip + malformed-rejection properties
# ---------------------------------------------------------------------------

def test_traceparent_roundtrip_property():
    """format → parse is the identity for every valid (trace, span) id
    pair — randomized over the full hex alphabet, zero-ids excluded."""
    rng = random.Random(20180704)
    hexd = "0123456789abcdef"
    for _ in range(200):
        tid = "".join(rng.choice(hexd) for _ in range(32))
        sid = "".join(rng.choice(hexd) for _ in range(16))
        if set(tid) == {"0"} or set(sid) == {"0"}:
            continue
        hdr = obs_trace.format_traceparent(tid, sid)
        assert hdr == f"00-{tid}-{sid}-01"
        assert obs_trace.parse_traceparent(hdr) == (tid, sid)


def test_traceparent_rejects_malformed():
    tid, sid = "ab" * 16, "cd" * 8
    parse = obs_trace.parse_traceparent
    assert parse(None) is None
    assert parse("") is None
    # wrong field lengths
    assert parse(f"00-{tid[:-1]}-{sid}-01") is None
    assert parse(f"00-{tid}-{sid}0-01") is None
    assert parse(f"0-{tid}-{sid}-01") is None
    # non-hex anywhere
    assert parse(f"00-{'g' * 32}-{sid}-01") is None
    assert parse(f"00-{tid}-{'z' * 16}-01") is None
    assert parse(f"zz-{tid}-{sid}-01") is None
    # uppercase is normalized on ingest (lenient parse: a sloppy caller
    # continues its trace rather than orphaning it)
    assert parse(f"00-{tid.upper()}-{sid}-01") == (tid, sid)
    # all-zero ids are invalid
    assert parse(f"00-{'0' * 32}-{sid}-01") is None
    assert parse(f"00-{tid}-{'0' * 16}-01") is None
    # version ff is forbidden
    assert parse(f"ff-{tid}-{sid}-01") is None
    # version 00 must have EXACTLY four fields — a tail is invalid
    assert parse(f"00-{tid}-{sid}-01-extra") is None
    # ...but a FUTURE version passes through on its leading four fields
    assert parse(f"cc-{tid}-{sid}-01-future-field") == (tid, sid)
    assert parse(f"cc-{tid}-{sid}-01") == (tid, sid)


def test_traceparent_fuzz_never_raises():
    """The parser must reject, never throw, on arbitrary junk."""
    rng = random.Random(7)
    alphabet = "0123456789abcdefXYZ- \t"
    for _ in range(300):
        s = "".join(rng.choice(alphabet)
                    for _ in range(rng.randrange(0, 64)))
        out = obs_trace.parse_traceparent(s)
        assert out is None or (len(out[0]), len(out[1])) == (32, 16)


# ---------------------------------------------------------------------------
# flight ring trace index vs a walk-based oracle
# ---------------------------------------------------------------------------

def _walk_oracle(fr, trace_id):
    return [r["trace"] for r in fr.dump()["requests"]
            if r["trace_id"] == trace_id]


def test_flight_trace_index_matches_walk_oracle():
    """Randomized record workload over a small ring: ``traces_for`` must
    equal a dump walk for EVERY trace id ever recorded — including ids
    fully evicted, ids recorded more than once (retry storms), and
    records with no trace id at all."""
    rng = random.Random(99)
    fr = FlightRecorder(max_requests=4, max_steps=1)
    seen = set()
    for i in range(100):
        tid = rng.choice([f"t{rng.randrange(6)}", None, ""])
        fr.record_request({"trace_id": tid, "spans": [], "n": i})
        if tid:
            seen.add(tid)
        probe = rng.choice(sorted(seen) + ["never-recorded"]) \
            if seen else "never-recorded"
        assert fr.traces_for(probe) == _walk_oracle(fr, probe)
    for tid in sorted(seen) + ["never-recorded"]:
        assert fr.traces_for(tid) == _walk_oracle(fr, tid)
    # the index never outgrows the ring
    assert sum(len(v) for v in fr._by_trace.values()) <= 4


def test_flight_trace_index_eviction_and_zero_capacity():
    fr = FlightRecorder(max_requests=2, max_steps=1)
    for i in range(3):
        fr.record_request({"trace_id": f"t{i}", "spans": []})
    assert fr.traces_for("t0") == []          # evicted → unindexed
    assert [t["trace_id"] for t in fr.traces_for("t2")] == ["t2"]
    # same id resident twice: oldest first, both served
    fr.record_request({"trace_id": "t2", "spans": [], "second": True})
    assert len(fr.traces_for("t2")) == 2
    assert fr.traces_for("t2")[1].get("second") is True
    # a zero-capacity ring records (counts) but never indexes
    z = FlightRecorder(max_requests=0, max_steps=1)
    z.record_request({"trace_id": "x", "spans": []})
    assert z.dump()["recorded_total"] == 1 and z.traces_for("x") == []
    assert z._by_trace == {}


def test_flight_trace_index_thread_safety():
    fr = FlightRecorder(max_requests=8, max_steps=1)

    def writer(k):
        for i in range(200):
            fr.record_request({"trace_id": f"w{k}-{i % 3}", "spans": []})

    threads = [threading.Thread(target=writer, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for k in range(4):
        for m in range(3):
            tid = f"w{k}-{m}"
            assert fr.traces_for(tid) == _walk_oracle(fr, tid)
    assert sum(len(v) for v in fr._by_trace.values()) <= 8


# ---------------------------------------------------------------------------
# trace-exclude / poll-route pins (the PR-14..17 audit regression)
# ---------------------------------------------------------------------------

def test_contract_poll_routes_pin():
    """Every poll-class route added through PR 17 must stay in the lint
    contract's poll_routes — a new scrape/probe route missing here ends
    up churning the flight ring in production."""
    from scalable_hw_agnostic_inference_tpu.analysis.contract import (
        DEFAULT_CONTRACT,
    )

    assert set(DEFAULT_CONTRACT.poll_routes) >= {
        "/profile", "/health", "/readiness", "/health/ready", "/metrics",
        "/stats", "/kv/blocks", "/kv/digests", "/fleet",
        "/trace/{trace_id}",
    }
    assert set(DEFAULT_CONTRACT.trace_files) >= {
        "serve/app.py", "serve/asgi.py", "orchestrate/cova.py"}


def test_pod_app_trace_exclude_covers_probe_routes():
    from scalable_hw_agnostic_inference_tpu.serve.app import create_app

    cfg = make_cfg()
    app = create_app(cfg, EchoService(cfg))
    assert app.trace_exclude >= {
        "/health/ready", "/profile", "/kv/blocks", "/kv/digests",
        "/kv/pull", "/kv/protect", "/kv/migrate", "/trace/{trace_id}"}


def test_cova_app_trace_exclude_covers_probe_routes(tmp_path):
    from scalable_hw_agnostic_inference_tpu.orchestrate.cova import (
        create_cova_app,
    )

    p = tmp_path / "models.json"
    p.write_text(json.dumps({"models": {"m": {"url": "http://x:1"}}}))
    app = create_cova_app(str(p))
    assert app.trace_exclude >= {"/fleet", "/trace/{trace_id}"}


# ---------------------------------------------------------------------------
# disabled tracing stays a true no-op on every new seam
# ---------------------------------------------------------------------------

def test_trace_disabled_noop_on_new_seams():
    # earlier tests may leave a span/trace in this thread's context (the
    # unclosed-span cases do so on purpose) — start from a clean slate
    obs_trace._current_trace.set(None)
    obs_trace._current_span.set(None)
    obs_trace.configure(False)
    try:
        # the shared constant: zero allocation per call on the hot path
        for name in ("kvnet_fetch", "migrate_ship", "migrate_resume",
                     "hop:/generate", "fabric_probe"):
            assert obs_trace.span(name, annotation=False) \
                is obs_trace.NOOP
        assert obs_trace.begin_request_trace("POST /generate") is None
        assert obs_trace.current_trace() is None
        assert obs_trace.current_span() is None
        # the header-propagation seams key off THIS: None → no headers
        # dict is ever built in cova/kvnet/migrate clients
        assert obs_trace.current_traceparent() is None
        # attr writes on the noop are accepted and dropped
        with obs_trace.span("kvnet_fetch", annotation=False) as sp:
            assert sp.set(blocks=3) is sp
    finally:
        obs_trace.configure(True)
    # tracing ON but no active request context (the engine-loop thread's
    # situation): still the shared noop, still no traceparent
    assert obs_trace.span("kvnet_fetch", annotation=False) is obs_trace.NOOP
    assert obs_trace.current_traceparent() is None


def test_engine_request_carries_trace_fields_without_cost():
    """The engine-side seams are data-only: a default Request carries an
    empty traceparent and an empty obs_extra dict, and _timing_of merges
    obs_extra into the timing without requiring tracing to be on."""
    from scalable_hw_agnostic_inference_tpu.engine.types import (
        Request,
        SamplingParams,
    )

    r = Request(0, [1, 2, 3], SamplingParams())
    assert r.traceparent == "" and r.obs_extra == {}


# ---------------------------------------------------------------------------
# autopsy: categorization, assembly, attribution
# ---------------------------------------------------------------------------

def test_categorize_span_names():
    c = obs_autopsy.categorize
    assert c("queue") == "queue"
    assert c("prefill") == "prefill"
    assert c("decode") == "decode"
    for n in ("fabric_probe", "kv_restore", "kvnet_fetch",
              "GET /kv/blocks", "POST /kv/pull", "GET /kv/digests"):
        assert c(n) == "kv-pull", n
    for n in ("migrate_ship", "migrate_cut", "migrate_resume",
              "POST /kv/migrate"):
        assert c(n) == "migration", n
    assert c("hop:/generate") == "network"
    assert c("hop:/kv/migrate") == "network"   # the wire time, not the work
    for n in ("POST /generate", "model_infer", "tokenize", "detokenize"):
        assert c(n) == "admission", n


def _span(name, sid, parent, dur, t0=1000.0):
    return {"name": name, "span_id": sid, "parent_id": parent,
            "t_start": t0, "duration_s": dur}


def _trace_dict(trace_id, spans, remote_parent=None):
    d = {"trace_id": trace_id, "name": spans[0]["name"], "spans": spans}
    if remote_parent:
        d["remote_parent"] = remote_parent
    return d


def test_assemble_rewires_pod_shards_under_cova_hops():
    tid = "ab" * 16
    cova = _trace_dict(tid, [
        _span("POST /generate", "c0", None, 1.0),
        _span("hop:/generate", "c1", "c0", 0.6),
        _span("hop:/generate", "c2", "c0", 0.3),
    ])
    # pod A continued from hop c1, pod B from hop c2 — and pod B's clock
    # is wildly skewed (t_start far in the past): durations-only math
    # must not care
    pod_a = _trace_dict(tid, [
        _span("POST /generate", "a0", None, 0.5),
        _span("decode", "a1", "a0", 0.4),
    ], remote_parent="c1")
    pod_b = _trace_dict(tid, [
        _span("POST /generate", "b0", None, 0.25, t0=-50000.0),
        _span("kv_restore", "b1", "b0", 0.2, t0=-50000.0),
    ], remote_parent="c2")
    asm = obs_autopsy.assemble([cova, pod_a, pod_b])
    assert asm["trace_id"] == tid
    assert asm["root_span_id"] == "c0"
    assert asm["orphan_root_ids"] == []
    by_id = {s["span_id"]: s for s in asm["spans"]}
    assert by_id["a0"]["parent_id"] == "c1"
    assert by_id["b0"]["parent_id"] == "c2"
    rep = obs_autopsy.autopsy(asm)
    assert rep["root"] == "POST /generate"
    assert rep["total_s"] == pytest.approx(1.0)
    cats = rep["categories"]
    # self-times telescope: decode 0.4, kv-pull 0.2, network
    # (0.6-0.5)+(0.3-0.25)=0.15, admission 0.1 (cova) +0.1 (a0) +0.05 (b0)
    assert cats["decode"] == pytest.approx(0.4, abs=1e-6)
    assert cats["kv-pull"] == pytest.approx(0.2, abs=1e-6)
    assert cats["network"] == pytest.approx(0.15, abs=1e-6)
    assert cats["admission"] == pytest.approx(0.25, abs=1e-6)
    assert rep["coverage"] == pytest.approx(1.0)
    assert rep["dominant"] == "decode"


def test_assemble_tolerates_dead_pod_orphans_and_duplicates():
    tid = "cd" * 16
    cova = _trace_dict(tid, [_span("POST /generate", "c0", None, 1.0)])
    # this shard's remote parent (a hop span on a pod that died with its
    # ring) is absent from the merged set: it must surface as an orphan
    # root, counted separately, never under the global root
    orphan = _trace_dict(tid, [
        _span("POST /kv/migrate", "o0", None, 0.2),
        _span("migrate_resume", "o1", "o0", 0.1),
    ], remote_parent="dead0000beef0000")
    asm = obs_autopsy.assemble([cova, orphan, orphan])  # duplicate shard
    assert asm["root_span_id"] == "c0"
    assert asm["orphan_root_ids"] == ["o0"]
    assert len(asm["spans"]) == 3              # duplicates deduped
    rep = obs_autopsy.autopsy(asm)
    assert rep["n_orphan_roots"] == 1
    assert rep["orphan_self_s"] == pytest.approx(0.2)  # 0.1 + 0.1 self
    assert rep["categories"]["migration"] == 0.0       # not double-counted
    assert rep["coverage"] == pytest.approx(1.0)       # root's own self time
    assert obs_autopsy.assemble([]) == {
        "trace_id": None, "spans": [], "root_span_id": None,
        "orphan_root_ids": []}


def test_format_report_flags_dominant_and_orphans():
    rep = obs_autopsy.autopsy(obs_autopsy.assemble([
        _trace_dict("ef" * 16, [
            _span("POST /generate", "r", None, 2.0),
            _span("decode", "d", "r", 1.5),
            _span("kvnet_fetch", "k", "r", 0.3),
        ]),
        _trace_dict("ef" * 16, [_span("GET /kv/blocks", "x", None, 0.1)],
                    remote_parent="gone"),
    ]))
    txt = obs_autopsy.format_report(rep)
    assert "decode" in txt and "<-- dominant" in txt
    assert "kv-pull" in txt
    assert "unrooted subtree" in txt
    assert "coverage" in txt


# ---------------------------------------------------------------------------
# per-pod /trace/{trace_id}: indexed lookup off the flight ring
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_pod_trace_endpoint_serves_from_ring():
    from scalable_hw_agnostic_inference_tpu.serve.app import create_app

    cfg = make_cfg()
    app = create_app(cfg, EchoService(cfg))
    tid, sid = "ab" * 16, "cd" * 8
    async with make_client(app) as c:
        await wait_ready(c)
        r = await c.post("/predict", json={"text": "hi"},
                         headers={"traceparent": f"00-{tid}-{sid}-01"})
        assert r.status_code == 200
        assert r.headers["traceparent"].split("-")[1] == tid
        r = await c.get(f"/trace/{tid}")
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["trace_id"] == tid
        assert len(body["traces"]) == 1
        tr = body["traces"][0]
        assert tr["trace_id"] == tid and tr["remote_parent"] == sid
        assert {s["name"] for s in tr["spans"]} >= {"POST /predict"}
        # unknown trace: 404, not an empty 200
        assert (await c.get("/trace/" + "9" * 32)).status_code == 404
        # the lookup itself must never ring the recorder
        d = (await c.get("/debug/flight")).json()
        assert all("/trace/" not in q["trace"]["name"]
                   for q in d["requests"])


@pytest.mark.asyncio
async def test_excluded_route_opens_hop_trace_only_with_traceparent():
    """Probe-class routes stay OFF the ring for bare polls, but a valid
    inbound traceparent means a fleet hop landed there — that call must
    become a server-side child span (recorded under the caller's id)."""
    from scalable_hw_agnostic_inference_tpu.serve.app import create_app

    cfg = make_cfg()
    app = create_app(cfg, EchoService(cfg))
    tid = "fa" * 16
    async with make_client(app) as c:
        await wait_ready(c)
        # bare poll: excluded, unrecorded
        assert (await c.get("/health")).status_code == 200
        assert (await c.get(f"/trace/{tid}")).status_code == 404
        # same route WITH a traceparent: hop trace, recorded
        r = await c.get("/health",
                        headers={"traceparent": f"00-{tid}-{'cd' * 8}-01"})
        assert r.status_code == 200
        r = await c.get(f"/trace/{tid}")
        assert r.status_code == 200, r.text
        assert r.json()["traces"][0]["trace_id"] == tid
        # a MALFORMED traceparent on an excluded route stays untraced
        before = app.state["flight"].dump()["recorded_total"]
        await c.get("/health", headers={"traceparent": "garbage"})
        assert app.state["flight"].dump()["recorded_total"] == before


# ---------------------------------------------------------------------------
# cova: hop spans + fleet fan-out (offline, faked transport)
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_cova_post_propagates_traceparent_and_opens_hop_span(
        monkeypatch):
    import httpx

    from scalable_hw_agnostic_inference_tpu.orchestrate.cova import (
        CovaClient,
    )

    seen = {}

    class FakeResp:
        status_code = 200

        def json(self):
            return {"ok": True}

    class FakeClient:
        def __init__(self, *a, **kw):
            pass

        async def post(self, url, json=None, headers=None, **kw):
            seen["headers"] = headers
            return FakeResp()

        async def aclose(self):
            pass

    monkeypatch.setattr(httpx, "AsyncClient", FakeClient)
    client = CovaClient({"m": {"url": "http://127.0.0.1:9"}})
    tr = obs_trace.Trace("POST /generate")
    with obs_trace.use_trace(tr):
        await client.post("m", "/generate", {"prompt": "x"})
    tr.close()
    hdr = (seen["headers"] or {}).get("traceparent", "")
    parsed = obs_trace.parse_traceparent(hdr)
    assert parsed is not None and parsed[0] == tr.trace_id
    hops = [s for s in tr.to_dict()["spans"]
            if s["name"] == "hop:/generate"]
    assert len(hops) == 1
    # the pod's server-side span must parent under the HOP, not the root
    assert parsed[1] == hops[0]["span_id"] != tr.root.span_id
    # no active trace → no headers dict at all (the SHAI_TRACE=0 seam)
    seen.clear()
    await client.post("m", "/generate", {"prompt": "y"})
    assert seen["headers"] is None
    await client.aclose()


@pytest.mark.asyncio
async def test_cova_trace_shards_degrades_per_pod(monkeypatch):
    import httpx

    from scalable_hw_agnostic_inference_tpu.orchestrate.cova import (
        CovaClient,
    )

    tid = "ab" * 16

    class Resp:
        def __init__(self, status, body=None):
            self.status_code = status
            self._body = body

        def json(self):
            return self._body

    class FakeClient:
        def __init__(self, *a, **kw):
            pass

        async def get(self, url, **kw):
            if "good" in url:
                return Resp(200, {"trace_id": tid,
                                  "traces": [{"trace_id": tid,
                                              "spans": []}]})
            if "empty" in url:
                return Resp(404)
            if "weird" in url:
                return Resp(200, ["not", "a", "dict"])
            raise httpx.ConnectError("pod is gone")

        async def aclose(self):
            pass

    monkeypatch.setattr(httpx, "AsyncClient", FakeClient)
    client = CovaClient({
        "good": {"url": "http://good:1"}, "empty": {"url": "http://empty:1"},
        "weird": {"url": "http://weird:1"}, "dead": {"url": "http://dead:1"},
    })
    shards = await client.trace_shards(tid)
    assert [t["trace_id"] for t in shards["good"]] == [tid]
    assert shards["empty"] == []            # 404 is normal, not an error
    assert shards["weird"] == []            # junk body degraded to empty
    assert "error" in shards["dead"]        # dead pod isolated
    await client.aclose()


@pytest.mark.asyncio
async def test_cova_trace_endpoint_validates_and_404s(tmp_path):
    from scalable_hw_agnostic_inference_tpu.orchestrate.cova import (
        create_cova_app,
    )

    class Resp404:
        status_code = 404

        def json(self):
            return {}

    class FakeClient:
        async def get(self, url, **kw):
            return Resp404()

        async def aclose(self):
            pass

    p = tmp_path / "models.json"
    p.write_text(json.dumps({"models": {"m": {"url": "http://x:1"}}}))
    app = create_cova_app(str(p))
    # fake only the POD-facing transport (make_client itself rides
    # httpx.AsyncClient over ASGI, so the class can't be monkeypatched)
    app.state["client"]._client = FakeClient()
    async with make_client(app) as c:
        assert (await c.get("/trace/nothex")).status_code == 400
        assert (await c.get("/trace/" + "a" * 31)).status_code == 400
        assert (await c.get("/trace/" + "a" * 32)).status_code == 404


# ---------------------------------------------------------------------------
# THE acceptance run: one trace id across a live two-pod migration
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.asyncio
async def test_one_trace_across_live_migration(migrate_pods, tmp_path):
    """cova + two pods over real sockets: a /generate routed to the
    draining pod migrates to the peer mid-flight; cova's
    ``/trace/{id}`` then returns ONE assembled tree — cova's root + hop
    spans, pod A's serving shard (with the migration cut), pod B's
    resume shard (with migrate_resume and the KV restore) — and the
    autopsy attributes ≥ 90% of the root wall time to named categories
    with kv-pull and migration present as distinct spans."""
    from scalable_hw_agnostic_inference_tpu.orchestrate.cova import (
        create_cova_app,
    )

    urls, services, apps = migrate_pods
    models = {"a": {"url": urls["a"], "weight": 2},
              "b": {"url": urls["b"], "weight": 1}}
    p = tmp_path / "models.json"
    p.write_text(json.dumps({"models": models}))
    app = create_cova_app(str(p))
    prompt = ("a long story about one request whose latency autopsy "
              "must survive a rolling update mid-decode")
    async with make_client(app) as c:
        try:
            rz_faults.configure("engine.step=delay(0.12)", 0)
            task = asyncio.ensure_future(c.post("/generate", json={
                "prompt": prompt, "temperature": 0.0,
                "max_new_tokens": 48}))
            await asyncio.sleep(1.2)
            apps["a"].state["begin_drain"]()
            r = await task
        finally:
            rz_faults.reset()
        assert r.status_code == 200, r.text
        assert r.json()["routed_by"] == "migrated"
        tp = r.headers.get("traceparent", "")
        tid = tp.split("-")[1] if tp.count("-") >= 2 else ""
        assert len(tid) == 32, f"no traceparent on cova's answer: {tp!r}"

        r = await c.get(f"/trace/{tid}")
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["trace_id"] == tid
        asm = body["assembled"]
        assert asm["trace_id"] == tid
        names = {s["name"] for s in asm["spans"]}
        # cova's hop + BOTH pods' serving shards under one id
        assert "POST /generate" in names
        assert any(n.startswith("hop:") for n in names), names
        assert {"queue", "prefill", "decode"} <= names, names
        # migration and kv-pull are distinct, named spans
        assert names & {"migrate_cut", "migrate_ship",
                        "migrate_resume"}, names
        assert names & {"kv_restore", "kvnet_fetch",
                        "fabric_probe"}, names
        # both pods answered the fan-out (no dead-pod degradation here)
        assert all("error" not in (v or {}) for v in body["pods"].values()
                   if isinstance(v, dict)), body["pods"]
        rep = body["autopsy"]
        assert rep["total_s"] > 0
        assert rep["categories"]["migration"] > 0.0
        assert rep["categories"]["kv-pull"] > 0.0
        assert rep["coverage"] >= 0.9, rep
        assert rep["dominant"] in ("decode", "prefill", "network",
                                   "migration", "queue"), rep

        # every shard rewired: a live fleet leaves no orphan subtrees
        assert asm["orphan_root_ids"] == [], asm["orphan_root_ids"]

        # pod A's own /trace/{id} serves its local shard too
        import httpx

        async with httpx.AsyncClient(base_url=urls["a"],
                                     timeout=30) as ac:
            ra = await ac.get(f"/trace/{tid}")
            assert ra.status_code == 200
            assert ra.json()["trace_id"] == tid
