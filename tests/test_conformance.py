"""Conformance observability (ISSUE 7): the live HBM ledger + leak drift
detector, the SLO burn-rate engine + its failover trigger, and the
perf-model sentinel — unit-tested with stub clocks/allocators, then
end-to-end on the tiny engine under injected faults. The core claim in
both directions: each detector FIRES on its synthetic fault and stays
SILENT on a healthy run."""

import time

import pytest

import jax  # noqa: F401  (platform pinned in conftest)

from scalable_hw_agnostic_inference_tpu.obs.hbm import (
    DriftDetector,
    HbmLedger,
)
from scalable_hw_agnostic_inference_tpu.obs.sentinel import (
    PerfSentinel,
    default_projection_key,
)
from scalable_hw_agnostic_inference_tpu.obs.slo import (
    SloEngine,
    SloTargets,
)
from scalable_hw_agnostic_inference_tpu.orchestrate.capacity_checker import (
    ControllerState,
    decide,
    is_overloaded,
    slo_breached,
)
from scalable_hw_agnostic_inference_tpu.resilience import faults as rz_faults

from test_engine import make_engine, tiny_model  # noqa: F401 (fixture)


# ---------------------------------------------------------------------------
# HBM: drift detector + ledger primitives
# ---------------------------------------------------------------------------

def test_drift_detector_flags_monotonic_growth():
    d = DriftDetector(window=2, windows_needed=3, min_growth=10)
    flagged = False
    for v in (0, 0, 100, 100, 200, 200, 300, 300):  # means 0,100,200,300
        flagged = d.feed(("idle",), v)
    assert flagged and d.leak_suspect
    assert d.leak_composition == ("idle",)
    # latched: a pause in growth does not un-flag a suspected leak
    d.feed(("idle",), 300)
    assert d.leak_suspect


def test_drift_detector_silent_on_flat_noise_and_survives_interleaving():
    d = DriftDetector(window=2, windows_needed=3, min_growth=10)
    # flat values never flag; sub-threshold noise never flags
    for v in (50, 50, 51, 49, 55, 45, 50, 50, 52, 48):
        assert not d.feed(("idle",), v)
    # interleaved OTHER compositions do not reset the idle stream: growth
    # across bursts is still caught
    d2 = DriftDetector(window=2, windows_needed=2, min_growth=10)
    seq = [(("idle",), 0), (("idle",), 0),
           (("busy",), 999), (("busy",), 1234),   # a burst in between
           (("idle",), 100), (("idle",), 100)]
    flagged = False
    for comp, v in seq:
        flagged = d2.feed(comp, v)
    assert flagged  # idle means 0 -> 100 with a burst interleaved
    # the busy stream's own (single, incomplete) windows never flagged


def test_hbm_ledger_accounting_and_fallback():
    led = HbmLedger(bytes_limit=1000.0, window=2, windows_needed=2,
                    min_growth=1)
    # accounted fallback (no device stats): used == sum(pools), no frag
    led.sample(pools={"weights": 600, "kv_pool": 200}, composition=(0,),
               drift_value=0.0)
    s = led.snapshot()
    assert s["weights_bytes"] == 600 and s["kv_pool_bytes"] == 200
    assert s["used_bytes"] == 800 and s["attributed_bytes"] == 800
    assert s["headroom_bytes"] == 200
    assert s["device_stats"] == 0.0 and s["unattributed_bytes"] == 0.0
    # device-stats path: unattributed remainder + fragmentation ratio
    led.sample(pools={"weights": 600, "kv_pool": 200}, composition=(0,),
               bytes_in_use=900, largest_free=50, drift_value=100.0,
               extra={"kv_used_bytes": 10})
    s = led.snapshot()
    assert s["device_stats"] == 1.0
    assert s["unattributed_bytes"] == 100
    assert s["headroom_bytes"] == 100
    # free = 100, largest contiguous 50 -> half fragmented
    assert s["fragmentation_ratio"] == pytest.approx(0.5)
    assert s["kv_used_bytes"] == 10
    assert s["leak_suspect"] == 0.0


def test_hbm_ledger_leak_flag_reaches_snapshot():
    led = HbmLedger(bytes_limit=0.0, window=1, windows_needed=2,
                    min_growth=1)
    for drift in (0, 100, 200):
        led.sample(pools={"kv_pool": 100}, composition=(0, 0, 0),
                   drift_value=drift)
    assert led.snapshot()["leak_suspect"] == 1.0


# ---------------------------------------------------------------------------
# SLO: burn-rate engine
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def test_slo_fast_and_slow_burn_breach():
    clk = _Clock()
    # 100ms TTFT target, 1% budget, 5m/1h windows, breach at fast>=14.4
    eng = SloEngine(SloTargets(ttft_ms=100.0, budget_frac=0.01,
                               min_events=10), clock=clk)
    # healthy: 20 fast requests -> burn 0, no breach
    for _ in range(20):
        eng.record_ttft(0.01)
    s = eng.snapshot()
    assert s["ttft_fast_burn"] == 0.0 and s["breach"] == 0.0
    # regression: every request violates -> bad_frac 1.0 / 0.01 = 100x
    for _ in range(20):
        clk.t += 1.0
        eng.record_ttft(0.5)
    s = eng.snapshot()
    assert s["ttft_fast_burn"] == pytest.approx(50.0)   # 20/40 / 0.01
    assert s["ttft_slow_burn"] == pytest.approx(50.0)
    assert s["ttft_breach"] == 1.0 and s["breach"] == 1.0
    # the fast window forgets: 10 minutes later the burn clears while the
    # slow window still remembers -> no breach (multi-window rule)
    clk.t += 600.0
    for _ in range(15):
        eng.record_ttft(0.01)
    s = eng.snapshot()
    assert s["ttft_fast_burn"] == 0.0
    assert s["ttft_slow_burn"] > 1.0
    assert s["breach"] == 0.0


def test_slo_min_events_gate_and_error_objective():
    clk = _Clock()
    eng = SloEngine(SloTargets(error_rate=0.05, min_events=10), clock=clk)
    # 3 straight errors: burn is enormous but 3 < min_events -> no breach
    for _ in range(3):
        eng.record_outcome("timeout")
    s = eng.snapshot()
    assert s["error_fast_burn"] > 1.0 and s["error_breach"] == 0.0
    # cancelled is neither good nor bad
    eng.record_outcome("cancelled")
    assert eng.snapshot()["error_events"] == 3.0
    for _ in range(8):
        eng.record_outcome("rejected")
    assert eng.snapshot()["error_breach"] == 1.0
    for _ in range(300):
        eng.record_outcome("eos")
    assert eng.snapshot()["error_fast_burn"] < 14.4


def test_env_knobs_are_lenient_not_boot_crashes(monkeypatch):
    """A malformed tuning knob degrades to its default — never a pod
    crash-loop (obs.util parsing shared by hbm/slo/sentinel)."""
    monkeypatch.setenv("SHAI_HBM_WINDOW", "8.5")      # non-int: floor to 8
    monkeypatch.setenv("SHAI_HBM_WINDOWS", "oops")    # garbage: default 4
    led = HbmLedger()
    assert led._drift.window == 8 and led._drift.windows_needed == 4
    monkeypatch.setenv("SHAI_SLO_TTFT_MS", "fast")    # garbage: stays off
    assert SloEngine.maybe_from_env(None) is None
    monkeypatch.setenv("SHAI_PERF_PROJECTED_TOK_S", "warp")
    assert PerfSentinel.from_env() is None


def test_slo_targets_env_overrides_unit_config(monkeypatch):
    base = SloTargets(ttft_ms=500.0)
    monkeypatch.setenv("SHAI_SLO_TTFT_MS", "250")
    monkeypatch.setenv("SHAI_SLO_MIN_EVENTS", "3")
    t = SloTargets.from_env(base)
    assert t.ttft_ms == 250.0 and t.min_events == 3
    # nothing configured anywhere -> no engine at all
    monkeypatch.delenv("SHAI_SLO_TTFT_MS")
    monkeypatch.delenv("SHAI_SLO_MIN_EVENTS")
    assert SloEngine.maybe_from_env(None) is None
    assert SloEngine.maybe_from_env(base) is not None


# ---------------------------------------------------------------------------
# SLO -> failover controller (the latency-driven trigger)
# ---------------------------------------------------------------------------

def test_slo_breach_flips_decide_to_failover():
    """A majority of pods burning their SLO budget fails over in cost mode
    — even with empty queues and a cold KV pool (slow ≠ full)."""
    st = ControllerState()
    burning = {"waiting": 0.0, "kv_utilization": 0.1, "slo_breach": 1.0}
    calm = {"waiting": 0.0, "kv_utilization": 0.1, "slo_breach": 0.0}
    assert slo_breached(burning) and not slo_breached(calm)
    assert is_overloaded(burning)        # wired into the shared predicate
    assert not is_overloaded(calm)
    # one burning pod of three: hold (a pod-local problem, not the fleet)
    assert decide(st, [], 10, ("tpu",),
                  engine_stats=[burning, calm, calm]) == "hold"
    # strict majority burning: latency-driven failover, distinct trigger
    assert decide(st, [], 10, ("tpu",),
                  engine_stats=[burning, burning, calm]) == "failover"
    assert "slo burn-rate breach on 2/3 pods" in st.last_trigger
    # pods without the slo field (old image) behave exactly as before
    st2 = ControllerState()
    legacy = {"waiting": 20.0, "kv_utilization": 0.97}
    assert decide(st2, [], 10, ("tpu",),
                  engine_stats=[legacy, legacy, None]) == "failover"
    assert "overload" in st2.last_trigger


def test_fetch_engine_stats_merges_slo_section(monkeypatch):
    import httpx

    from scalable_hw_agnostic_inference_tpu.orchestrate.capacity_checker \
        import fetch_engine_stats

    class _R:
        def __init__(self, payload):
            self._payload = payload

        def json(self):
            return self._payload

    def fake_get(url, timeout=None):
        if "burning" in url:
            return _R({"engine": {"waiting": 0.0, "kv_utilization": 0.1},
                       "slo": {"ttft_fast_burn": 40.0,
                               "ttft_slow_burn": 2.0, "breach": 1.0}})
        return _R({"engine": {"waiting": 0.0, "kv_utilization": 0.1}})

    monkeypatch.setattr(httpx, "get", fake_get)
    out = fetch_engine_stats(["http://burning", "http://noslo"])
    assert out[0]["slo_breach"] == 1.0
    assert out[0]["slo_ttft_fast_burn"] == 40.0
    assert "slo_breach" not in out[1]
    st = ControllerState()
    assert decide(st, [], 10, ("tpu",), engine_stats=out) == "hold"
    assert decide(st, [], 10, ("tpu",),
                  engine_stats=[out[0], out[0], out[1]]) == "failover"


# ---------------------------------------------------------------------------
# perf sentinel
# ---------------------------------------------------------------------------

def test_sentinel_conformance_and_degraded_transition():
    clk = _Clock()
    sen = PerfSentinel(1000.0, min_conformance=0.8, window_s=60.0,
                       min_tokens=8, clock=clk)
    # healthy: 1000 tok/s of busy throughput -> conformance 1.0
    for _ in range(4):
        clk.t += 0.01
        assert not sen.record_step(kind="decode", duration_s=0.004,
                                   tokens=4)
    s = sen.snapshot()
    assert s["conformance"] == pytest.approx(1000 / 1000, rel=0.01)
    assert s["degraded"] == 0.0
    # idle steps never enter the window
    assert not sen.record_step(kind="idle", duration_s=5.0, tokens=0)
    assert sen.snapshot()["window_busy_s"] == pytest.approx(0.016)
    # slowdown: same tokens, 10x the busy time -> conformance ~0.1;
    # the healthy samples age out of the window first
    clk.t += 120.0
    flipped = []
    for _ in range(4):
        clk.t += 0.1
        flipped.append(sen.record_step(kind="spec", duration_s=0.04,
                                       tokens=4))
    assert flipped.count(True) == 1          # ONE transition, not a storm
    s = sen.snapshot()
    assert s["conformance"] == pytest.approx(0.1, rel=0.05)
    assert s["degraded"] == 1.0
    sen.diagnose({"step_gap_mean_ms": 1.0})  # structured log, must not raise
    assert sen.diagnoses == 1
    # the pod drains: the window empties and the stale degraded latch
    # clears — a degraded-then-idle pod must not alarm off zero evidence
    clk.t += 120.0
    s = sen.snapshot()
    assert s["window_tokens"] == 0.0
    assert s["conformance"] == 1.0 and s["degraded"] == 0.0


def test_sentinel_needs_min_tokens_before_degrading():
    clk = _Clock()
    sen = PerfSentinel(1000.0, min_tokens=100, clock=clk)
    clk.t += 1.0
    assert not sen.record_step(kind="decode", duration_s=1.0, tokens=1)
    s = sen.snapshot()
    assert s["degraded"] == 0.0       # 1 token proves nothing...
    assert s["conformance"] == 1.0    # ...and the ratio reads conformant
    assert s["live_per_s"] == 1.0     # the raw rate is still visible


def test_sentinel_from_env_resolution(tmp_path, monkeypatch):
    import json

    # direct rate wins
    monkeypatch.setenv("SHAI_PERF_PROJECTED_TOK_S", "123.5")
    sen = PerfSentinel.from_env()
    assert sen is not None and sen.projected_per_s == 123.5
    monkeypatch.delenv("SHAI_PERF_PROJECTED_TOK_S")
    # projection key through a PERF_MODEL.json
    pm = tmp_path / "PERF_MODEL.json"
    pm.write_text(json.dumps({"projections": {
        "llama1b_gen": {"work_unit": "tokens", "projected_per_s": 377.2}}}))
    monkeypatch.setenv("SHAI_PERF_MODEL", str(pm))
    monkeypatch.setenv("SHAI_PERF_PROJECTION", "llama1b_gen")
    sen = PerfSentinel.from_env()
    assert sen is not None and sen.projected_per_s == pytest.approx(377.2)
    assert sen.key == "llama1b_gen"
    # unresolvable -> no sentinel (unknown key, no default)
    monkeypatch.setenv("SHAI_PERF_PROJECTION", "no_such_key")
    assert PerfSentinel.from_env() is None
    monkeypatch.delenv("SHAI_PERF_PROJECTION")
    assert PerfSentinel.from_env(default_key="") is None


def test_default_projection_key_heuristics():
    assert default_projection_key("meta-llama/Llama-3.2-1B") == "llama1b_gen"
    assert default_projection_key("llama-1b-geometry",
                                  quantized=True) == "llama1b_int8_gen"
    assert default_projection_key("llama-3b-geometry") == "llama3b_gen"
    assert default_projection_key("Llama-3.2-11B-Vision") == \
        "mllama_decode_b1_tpot"
    assert default_projection_key("llama-70b", tp=8) == \
        "vllm_decode_70b_tp8_tpot"
    assert default_projection_key("llama-70b", tp=1) == ""
    assert default_projection_key("tiny") == ""
    # the committed PERF_MODEL.json really has the keys the heuristic maps
    from scalable_hw_agnostic_inference_tpu.obs.sentinel import (
        load_projections,
    )

    proj = load_projections()
    if proj:  # tolerate a stripped checkout
        for key in ("llama1b_gen", "llama3b_int8_gen",
                    "mllama_decode_b1_tpot", "vllm_decode_70b_tp8_tpot"):
            assert key in proj, f"heuristic maps to missing projection {key}"


# ---------------------------------------------------------------------------
# cova /fleet aggregation
# ---------------------------------------------------------------------------

@pytest.mark.asyncio
async def test_fleet_aggregates_conformance_per_backend():
    from scalable_hw_agnostic_inference_tpu.orchestrate.cova import (
        CovaClient,
    )

    stats = {
        "a": {"served": 5, "engine": {"waiting": 0.0, "kv_utilization": 0.1},
              "slo": {"ttft_fast_burn": 33.0, "ttft_slow_burn": 2.0,
                      "breach": 1.0},
              "hbm": {"headroom_bytes": float(4 << 30),
                      "leak_suspect": 1.0},
              "perf": {"conformance": 0.42, "degraded": 1.0}},
        "b": {"served": 9, "engine": {"waiting": 0.0,
                                      "kv_utilization": 0.2}},
    }

    class _Resp:
        def __init__(self, payload):
            self.status_code = 200
            self._payload = payload

        def json(self):
            return self._payload

    class _FakeHttp:
        async def get(self, url, timeout=None):
            name = url.split("//")[1].split("/")[0]
            return _Resp(stats[name])

    client = CovaClient({"a": {"url": "http://a"}, "b": {"url": "http://b"}})
    client._client = _FakeHttp()
    out = await client.fleet()
    conf = out["conformance"]
    assert conf["a"]["slo_breach"] is True
    assert conf["a"]["slo_fast_burn_max"] == 33.0
    assert conf["a"]["hbm_headroom_gib"] == pytest.approx(4.0)
    assert conf["a"]["hbm_leak_suspect"] is True
    assert conf["a"]["perf_conformance"] == 0.42
    assert conf["a"]["perf_degraded"] is True
    assert "a" not in out["overloaded"]  # raw engine gauges are calm...
    assert out["slo_breached"] == ["a"]  # ...but the slo verdict shows
    assert "b" not in conf               # no instruments, no entry


# ---------------------------------------------------------------------------
# engine integration: injected faults vs healthy runs
# ---------------------------------------------------------------------------

def _run_requests(eng, n, prompt=(1, 5, 9, 11), max_new=6,
                  idle_steps=2):
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        SamplingParams,
    )

    for _ in range(n):
        [fin] = eng.generate([list(prompt)],
                             SamplingParams(temperature=0.0,
                                            max_new_tokens=max_new))
        assert fin.stop_reason == "length"
        for _ in range(idle_steps):   # quiescent samples between bursts
            eng.step()


def test_engine_hbm_leak_detector_flags_kv_block_leak(tiny_model,
                                                      monkeypatch):
    """A stubbed allocator that drops one block per released request must
    flip shai_hbm_leak_suspect; the identical healthy run stays silent."""
    monkeypatch.setenv("SHAI_HBM_WINDOW", "2")
    monkeypatch.setenv("SHAI_HBM_WINDOWS", "2")
    monkeypatch.setenv("SHAI_HBM_MIN_GROWTH", "1")

    # healthy control first: same traffic, correct release
    eng = make_engine(tiny_model)
    _run_requests(eng, 3)
    snap = eng.obs.hbm.snapshot()
    assert snap["kv_leaked_bytes"] == 0.0
    assert snap["kv_used_bytes"] == 0.0   # idle + correct release: empty
    assert snap["leak_suspect"] == 0.0
    assert snap["samples"] > 0
    assert snap["weights_bytes"] > 0 and snap["kv_pool_bytes"] > 0

    # leaky engine: cache.release loses the first block of every sequence
    eng = make_engine(tiny_model)
    cache = eng.cache

    def leaky_release(seq_id):
        alloc = cache._seqs.pop(seq_id)
        cache.allocator.free(alloc.blocks[1:])  # block [0] never freed

    monkeypatch.setattr(cache, "release", leaky_release)
    _run_requests(eng, 4)
    snap = eng.obs.hbm.snapshot()
    assert snap["kv_leaked_bytes"] > 0.0
    assert snap["leak_suspect"] == 1.0, snap


def test_engine_sentinel_degrades_under_slowed_step_loop(tiny_model,
                                                         monkeypatch):
    """The fault injector's engine.step delay drops live tok/s below the
    projected rate -> conformance < 1 and the degraded flag (with ONE
    structured diagnosis); the healthy engine at the same projection
    stays conformant (compile steps are excluded from the window)."""
    monkeypatch.setenv("SHAI_PERF_PROJECTED_TOK_S", "50")
    monkeypatch.setenv("SHAI_PERF_MIN_TOKENS", "4")

    eng = make_engine(tiny_model)
    assert eng.obs.sentinel is not None
    _run_requests(eng, 1, max_new=8, idle_steps=0)
    s = eng.obs.sentinel.snapshot()
    assert s["window_tokens"] >= 4
    assert s["conformance"] > 0.8, s     # healthy: well above the floor
    assert s["degraded"] == 0.0

    try:
        rz_faults.configure("engine.step=delay(0.1)")
        eng = make_engine(tiny_model)
        _run_requests(eng, 1, max_new=8, idle_steps=0)
    finally:
        rz_faults.reset()
    s = eng.obs.sentinel.snapshot()
    assert s["window_tokens"] >= 4
    assert s["conformance"] < 1.0, s     # the acceptance bound
    assert s["conformance"] < 0.8        # and actually degraded
    assert s["degraded"] == 1.0
    assert eng.obs.sentinel.diagnoses == 1


def test_engine_slo_wired_end_to_end(tiny_model, monkeypatch):
    """Unit-config SLO targets flow into the engine; an impossible TTFT
    target breaches after real traffic, a generous one stays quiet."""
    monkeypatch.setenv("SHAI_SLO_MIN_EVENTS", "2")
    eng = make_engine(tiny_model, slo_ttft_ms=10_000.0)
    _run_requests(eng, 2, idle_steps=0)
    s = eng.obs.slo.snapshot()
    assert s["ttft_events"] >= 2.0
    assert s["breach"] == 0.0

    eng = make_engine(tiny_model, slo_ttft_ms=0.000001)
    _run_requests(eng, 2, idle_steps=0)
    s = eng.obs.slo.snapshot()
    assert s["ttft_fast_burn"] >= 14.4
    assert s["breach"] == 1.0
    # no targets anywhere -> no SLO state at all
    assert make_engine(tiny_model).obs.slo is None


def test_engine_step_records_carry_finished_ids(tiny_model):
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        SamplingParams,
    )

    eng = make_engine(tiny_model)
    [fin] = eng.generate([[1, 5, 9, 11]],
                         SamplingParams(temperature=0.0, max_new_tokens=4))
    recs = eng.obs.recent_steps()
    finishing = [r for r in recs if r["finished_ids"]]
    assert finishing, "no step record carries the finished request id"
    assert fin.req_id in finishing[-1]["finished_ids"]


# ---------------------------------------------------------------------------
# live over a socket: gauges on /metrics + /stats (CPU tiny vllm unit)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_conformance_gauges_live_on_socket(monkeypatch):
    """The acceptance wire-check: a real tiny vllm pod over a real socket
    exposes the shai_hbm_* / shai_slo_* / shai_perf_* families on
    /metrics, the slo/hbm/perf sections on /stats, the combined
    /debug/conformance verdict, GET /profile, and the flight-recorder
    trace-id/req-id correlation — all healthy (verdict ok)."""
    import http.client
    import json as _json

    pytest.importorskip("prometheus_client")

    from scalable_hw_agnostic_inference_tpu.models.registry import get_model
    from scalable_hw_agnostic_inference_tpu.serve.app import create_app
    from scalable_hw_agnostic_inference_tpu.serve.httpd import Server
    from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig

    monkeypatch.setenv("SHAI_SLO_TTFT_MS", "60000")        # generous: quiet
    monkeypatch.setenv("SHAI_PERF_PROJECTED_TOK_S", "0.001")
    monkeypatch.setenv("SHAI_PERF_MIN_TOKENS", "4")  # 6-token request is
    # enough evidence (the ratio is evidence-gated to 1.0 below this)

    cfg = ServeConfig(app="llm-conf", model_id="tiny", device="cpu",
                      max_new_tokens=8, vllm_config="/nonexistent.yaml")
    service = get_model("vllm")(cfg)
    app = create_app(cfg, service)
    srv = Server(app, host="127.0.0.1", port=0)
    srv.start_background()
    port = srv.port

    def req(method, path, body=None):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        headers = {"Content-Type": "application/json"} if body else {}
        conn.request(method, path,
                     body=_json.dumps(body) if body else None,
                     headers=headers)
        r = conn.getresponse()
        data = r.read()
        conn.close()
        return r.status, data.decode()

    deadline = time.time() + 300
    while True:
        status, _ = req("GET", "/readiness")
        if status == 200:
            break
        assert time.time() < deadline, "service never became ready"
        time.sleep(1.0)

    status, body = req("POST", "/generate",
                       json_body := {"prompt": "hello world",
                                     "temperature": 0.0,
                                     "max_new_tokens": 6})
    assert status == 200, body

    status, body = req("GET", "/stats")
    assert status == 200
    st = _json.loads(body)
    assert st["slo"]["breach"] == 0.0 and "ttft_fast_burn" in st["slo"]
    assert st["hbm"]["leak_suspect"] == 0.0
    assert st["hbm"]["weights_bytes"] > 0
    assert st["hbm"]["kv_pool_bytes"] > 0
    assert st["perf"]["projected_per_s"] == pytest.approx(0.001)
    assert st["perf"]["conformance"] > 1.0   # tiny projection: conformant
    assert st["perf"]["degraded"] == 0.0

    status, body = req("GET", "/metrics")
    assert status == 200
    for name in ("shai_hbm_weights_bytes", "shai_hbm_kv_pool_bytes",
                 "shai_hbm_headroom_bytes", "shai_hbm_fragmentation_ratio",
                 "shai_hbm_leak_suspect", "shai_slo_breach",
                 "shai_slo_ttft_fast_burn", "shai_slo_ttft_slow_burn",
                 "shai_perf_conformance", "shai_perf_live_per_s"):
        assert name in body, f"{name} missing from /metrics"

    status, body = req("GET", "/debug/conformance")
    assert status == 200
    v = _json.loads(body)["verdict"]
    assert v == {"hbm_leak_suspect": False, "slo_breach": False,
                 "perf_degraded": False, "ok": True}

    status, body = req("GET", "/profile")
    assert status == 200
    prof = _json.loads(body)
    assert prof["running"] is False and prof["seconds_left"] == 0.0
    assert prof["trace_dir"] is None

    status, body = req("GET", "/debug/flight")
    d = _json.loads(body)
    recs = [r for r in d["requests"]
            if r["trace"]["name"] == "POST /generate"]
    assert recs, "generate request missing from the flight ring"
    assert recs[-1]["trace_id"] == recs[-1]["trace"]["trace_id"]
    root = next(s for s in recs[-1]["trace"]["spans"]
                if s["parent_id"] is None)
    rid = root["attrs"]["engine_req_id"]
    finishing = [s for s in d["engine_steps"] if rid in s["finished_ids"]]
    assert finishing, "no step record joins to the request's engine id"

    srv.request_shutdown()
