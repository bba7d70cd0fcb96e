"""When the pod did not run (``obs/stops.py``), and the steps that stalled
(``obs/steploop.py``): the causes by injected clocks, the collector's and the
machine's by a real collection and a real ``SIGSTOP``, and where they show
(``snapshot()``, ``/stats``, ``/metrics``, ``/debug/flight``)."""

import gc
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from scalable_hw_agnostic_inference_tpu.obs import StepTelemetry
from scalable_hw_agnostic_inference_tpu.obs import stops as obs_stops
from scalable_hw_agnostic_inference_tpu.obs import trace as obs_trace
from scalable_hw_agnostic_inference_tpu.obs.stops import (
    LATE_S,
    TICK_S,
    ProcessStops,
)
from test_serve_http import EchoService, make_cfg, make_client, wait_ready

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEN2 = {"generation": 2, "collected": 7, "uncollectable": 0}
GEN0 = {"generation": 0, "collected": 1, "uncollectable": 0}


class Clocks:
    """The three clocks of a :class:`ProcessStops`, moved by hand."""

    def __init__(self):
        self.t, self.cpu = 100.0, 5.0

    def make(self) -> ProcessStops:
        p = ProcessStops(clock=lambda: self.t, cpu_clock=lambda: self.cpu,
                         wall=lambda: 1.7e9 + self.t)
        p.started = True    # counted by hand: no thread, no hook
        return p

    def wake(self, p, late, burnt, ticks_before=0, tick_cpu=0.0):
        """``ticks_before`` on-time wakes that each burn ``tick_cpu``, then
        one wake ``late`` behind its due time with ``burnt`` s of CPU."""
        for i in range(ticks_before + 1):
            due, cpu0 = self.t + TICK_S, self.cpu
            last = i == ticks_before
            self.t = due + (late if last else 0.001)
            self.cpu = cpu0 + (burnt if last else tick_cpu)
            assert p.beat(due, cpu0) == (self.t, self.cpu)


def heartbeats():
    return [t for t in threading.enumerate() if t.name == "shai-heartbeat"]


# -- the heartbeat's causes, by injected clocks ------------------------------

@pytest.mark.parametrize("late, burnt, collected, cause", [
    (0.5, 0.001, 0.0, "frozen"),      # nobody in the process ran
    (0.5, 0.45, 0.0, "starved"),      # somebody did, and kept the lock
    (0.5, 0.45, 0.3, "gc"),           # a recorded collection covers it
    (0.5, 0.45, 0.2, "starved"),      # ... under half of it: not the cause
    (0.03, 0.0, 0.0, None),           # a lock handed round: counts nothing
])
def test_a_late_wake_is_a_stop_with_its_cause(late, burnt, collected, cause):
    clk = Clocks()
    p = clk.make()
    p.loop_phase = lambda: "engine.fetch"
    if collected:
        clk.t += TICK_S + 0.01          # inside the stop to come
        p._on_gc("start", GEN2)
        clk.t += collected
        p._on_gc("stop", GEN2)
        clk.t -= TICK_S + 0.01 + collected
    clk.wake(p, late, burnt)
    s = p.snapshot()["stops"]
    ring = [r for r in p.recent() if r["cause"] != "collection"]
    if cause is None:
        assert not ring and s["max_s"] == 0
        assert all(s[f"count_{c}"] == 0 for c in obs_stops.CAUSES)
        return
    assert s[f"{cause}_s"] == pytest.approx(late) == s["max_s"]
    assert s[f"count_{cause}"] == 1
    assert sum(s[f"count_{c}"] for c in obs_stops.CAUSES) == 1
    assert ring == [{"ts": pytest.approx(1.7e9 + clk.t, abs=1e-3),
                     "dur_s": pytest.approx(late), "cause": cause,
                     "generation": None, "loop_phase": "engine.fetch",
                     "cpu_s": pytest.approx(burnt)}]


def test_the_two_constants_leave_room_for_a_fair_round_of_the_lock():
    """Five threads at the 5 ms switch interval are a round of 20 ms, an
    unlucky one twice that; the shortest stop on record is 0.11 s."""
    assert TICK_S == 0.02 and 2 * 4 * sys.getswitchinterval() <= LATE_S < 0.11


def test_a_busy_processs_own_rate_is_not_read_as_somebody_ran():
    """Three threads off the interpreter lock burn 60 ms of CPU a tick: a
    0.12 s stop in which the clock advances by just that much is the
    machine's all the same (the tick's share is taken off)."""
    clk = Clocks()
    p = clk.make()
    clk.wake(p, 0.12, 0.061, ticks_before=40, tick_cpu=0.06)
    assert p.snapshot()["stops"]["count_frozen"] == 1
    # and where the lock's holder ran all through it on top of that rate
    clk.wake(p, 0.12, 0.06 + 0.12, ticks_before=5, tick_cpu=0.06)
    assert p.snapshot()["stops"]["count_starved"] == 1


def test_an_open_collection_counts_as_cover():
    """The heartbeat can be given the lock between a collection's end and
    the callback that records it: the open collection's start is enough."""
    clk = Clocks()
    p = clk.make()
    due, cpu0 = clk.t + TICK_S, clk.cpu
    clk.t = due + 0.01
    p._on_gc("start", GEN2)
    clk.t, clk.cpu = due + 0.3, cpu0 + 0.29
    p.beat(due, cpu0)
    assert p.snapshot()["stops"]["count_gc"] == 1


# -- the collector's callback ------------------------------------------------

def test_a_real_collection_moves_the_counters_and_the_ring():
    p = ProcessStops()
    p.loop_phase = lambda: "loop.idle"
    p.start()
    try:
        before = p.snapshot()["gc"]
        junk = [[i] for i in range(50000)]
        for j in junk:
            j.append(j)     # cycles: something to collect
        del junk, j
        gc.collect()
        after = p.snapshot()["gc"]
    finally:
        p.stop()
    # the explicit one, and whatever the allocations triggered themselves
    assert after["collections_gen2"] >= before["collections_gen2"] + 1
    assert after["full_pause_s"] > before["full_pause_s"]
    assert after["full_pause_s"] == after["pause_s_gen2"]
    assert after["pause_s"] == pytest.approx(
        sum(after[f"pause_s_gen{g}"] for g in range(3)))
    assert after["pause_max_s"] >= after["full_pause_s"] / max(
        1, after["collections_gen2"])
    assert after["collected"] >= before["collected"] + 50000
    # the collector's own record (the heartbeat may add its ``gc`` stop
    # behind it where the collection outlasted 50 ms)
    rec = [r for r in p.recent() if r["cause"] == "collection"][-1]
    assert rec["generation"] == 2
    assert rec["loop_phase"] == "loop.idle"
    assert 0 < rec["dur_s"] <= after["pause_max_s"] + 1e-6
    assert abs(rec["ts"] - time.time()) < 60


def test_young_collections_are_counted_and_leave_no_record():
    clk = Clocks()
    p = clk.make()
    for _ in range(3):
        p._on_gc("start", GEN0)
        clk.t += 0.0004
        p._on_gc("stop", GEN0)
    g = p.snapshot()["gc"]
    assert g["collections_gen0"] == 3 and g["collections_gen2"] == 0
    assert g["pause_s"] == pytest.approx(0.0012) and g["full_pause_s"] == 0
    assert g["collected"] == 3 and p.recent() == []
    # a stop callback with no start (hooked in between) counts nothing
    p._on_gc("stop", GEN0)
    assert p.snapshot()["gc"]["collections_gen0"] == 3


@pytest.mark.parametrize("tracing, names", [(True, ["gc.collect"]),
                                            (False, [])])
def test_a_full_collection_is_one_annotation_where_tracing_is_on(
        monkeypatch, tracing, names):
    seen, open_ = [], []

    class Ann:
        def __init__(self, name, **meta):
            self.name, self.meta = name, meta

        def __enter__(self):
            # an engine loop another test of this worker left polling
            # writes its phases through the same seam: not ours
            if self.name.startswith("gc."):
                seen.append((self.name, self.meta))
                open_.append(self.name)

        def __exit__(self, *exc):
            if self.name.startswith("gc."):
                open_.remove(self.name)

    monkeypatch.setattr(obs_trace, "_annotation", Ann)
    # the one hook in the process, and no collection but the two below: an
    # app an earlier test of this worker started may have left its own
    obs_stops.PROCESS.stop()
    obs_trace.configure(tracing)
    p = ProcessStops()
    gc.disable()
    p.start()
    try:
        gc.collect(0)       # a young one: never annotated
        assert seen == []
        gc.collect()
    finally:
        p.stop()
        gc.enable()
        obs_trace.configure(True)
    assert [n for n, _ in seen] == names and open_ == []
    assert all(m == {"generation": 2} for _, m in seen)


def test_a_collection_under_the_instruments_own_lock_does_not_deadlock():
    """A collection starts at any allocation, also at one made under the
    lock: the callback then runs on the thread that holds it."""
    p = ProcessStops()
    p.start()
    done = []

    def locked():
        with p._lock:
            gc.collect()
            done.append(p.snapshot()["gc"]["collections_gen2"])

    t = threading.Thread(target=locked, daemon=True)
    t.start()
    t.join(10.0)
    p.stop()
    assert not t.is_alive() and done and done[0] >= 1


# -- a real stop -------------------------------------------------------------

CHILD = """
import json, sys, time
sys.path.insert(0, %r)
from scalable_hw_agnostic_inference_tpu.obs.stops import ProcessStops
p = ProcessStops()
p.start()
print("ready", flush=True)
sys.stdin.readline()
print(json.dumps({"stops": p.snapshot()["stops"], "ring": p.recent()}),
      flush=True)
p.stop()
"""


@pytest.mark.skipif(not hasattr(signal, "SIGSTOP"),
                    reason="the platform has no SIGSTOP")
def test_a_stopped_process_reads_frozen():
    """``SIGSTOP`` for 0.3 s: the heartbeat wakes late and the process's
    CPU clock stood still."""
    child = subprocess.Popen(
        [sys.executable, "-c", CHILD % ROOT], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True)
    try:
        assert child.stdout.readline().strip() == "ready"
        time.sleep(0.2)
        os.kill(child.pid, signal.SIGSTOP)
        time.sleep(0.3)
        os.kill(child.pid, signal.SIGCONT)
        time.sleep(0.2)
        out, _ = child.communicate("go\n", timeout=30)
    finally:
        child.kill()
    got = json.loads(out.splitlines()[-1])
    frozen = [r for r in got["ring"] if r["cause"] == "frozen"]
    assert frozen and max(r["dur_s"] for r in frozen) >= 0.2
    assert got["stops"]["frozen_s"] >= 0.2
    assert got["stops"]["count_frozen"] == len(frozen)
    assert all(r["loop_phase"] is None for r in got["ring"])


# -- start and stop ----------------------------------------------------------

def test_start_twice_and_stop_leaves_nothing_behind():
    callbacks, threads = list(gc.callbacks), len(heartbeats())
    p = ProcessStops()
    assert p.snapshot() == {} and not p.started
    p.start()
    thread = p._thread
    p.start()       # a no-op
    assert p._thread is thread and thread.name == "shai-heartbeat"
    assert thread.daemon and len(heartbeats()) == threads + 1
    assert gc.callbacks.count(p._on_gc) == 1
    p.stop()
    assert not thread.is_alive() and len(heartbeats()) == threads
    assert gc.callbacks == callbacks
    p.stop()        # and so is a second stop
    assert set(p.snapshot()) == {"gc", "stops"}   # the counters stay


def test_the_heartbeat_thread_counts_a_real_late_wake():
    """The thread itself, on the real clocks: a sleep that overruns by
    0.2 s and is then back on time."""
    naps = iter([0.0, 0.2])
    p = ProcessStops(sleep=lambda s: time.sleep(s + next(naps, 0.0)))
    p.start()
    try:
        def long_stops():
            return [r for r in p.recent()
                    if r["cause"] != "collection" and r["dur_s"] >= 0.15]

        deadline = time.monotonic() + 10.0
        while not long_stops() and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        p.stop()
    rec = long_stops()[0]
    # ``frozen`` on a quiet worker; threads other tests left may have run
    assert rec["cause"] in ("frozen", "starved")
    assert rec["dur_s"] <= 1.0 and rec["cpu_s"] >= 0


# -- stalled steps -----------------------------------------------------------

def steps(t, n, duration_s, kind="decode", **ms):
    for _ in range(n):
        t.begin_step(0)
        t._step_ms = {f"{k}_ms": v for k, v in ms.items()}
        t.record_step(kind=kind, duration_s=duration_s, n_running=1,
                      n_waiting=0, n_chunking=0, blocks_free=5)


def test_a_step_ten_times_the_median_is_stalled_with_its_longest_phase():
    t = StepTelemetry(total_blocks=10, max_steps=8)
    steps(t, 8, 0.015, fetch=13.0, marshal=2.0)
    assert t.snapshot()["stall"] == {"steps": 0, "excess_s": 0.0,
                                     "steps_by_phase": {}}
    steps(t, 1, 0.515, fetch=13.0, marshal=2.0, commit=500.0)
    steps(t, 1, 0.315, fetch=313.0, marshal=2.0)
    steps(t, 1, 0.16)        # over ten medians, over the floor: no phase
    steps(t, 1, 0.16, kind="idle")      # never judged
    s = t.snapshot()["stall"]
    assert s["steps"] == 3
    assert s["excess_s"] == pytest.approx(0.5 + 0.3 + 0.145)
    assert s["steps_by_phase"] == {"commit": 1, "fetch": 1, "other": 1}
    ring = t.recent_steps()
    assert [r.get("stalled", False) for r in ring[-5:]] == [
        False, True, True, True, False]


def test_a_chunk_step_beside_decode_steps_is_not_stalled():
    """Kimi's 47 ms chunk step among 3 ms decode steps is over ten medians
    and under the floor; nothing is judged before the ring has filled."""
    t = StepTelemetry(total_blocks=10, max_steps=8)
    steps(t, 7, 0.003, fetch=2.5)
    steps(t, 1, 5.0, dispatch=5000.0)    # a compile: no median yet
    steps(t, 1, 0.047, dispatch=44.0)
    steps(t, 1, 0.099, dispatch=96.0)
    assert t.snapshot()["stall"]["steps"] == 0
    steps(t, 1, 0.101, dispatch=98.0)
    s = t.snapshot()["stall"]
    assert s["steps_by_phase"] == {"dispatch": 1}
    assert s["excess_s"] == pytest.approx(0.098)


def test_the_median_is_taken_once_a_rings_length_of_steps():
    t = StepTelemetry(total_blocks=10, max_steps=4)
    steps(t, 4, 0.02)
    assert t._stall_median_s == 0.02
    steps(t, 3, 0.2)
    assert t._stall_median_s == 0.02 and t.snapshot()["stall"]["steps"] == 0
    steps(t, 1, 0.2)
    assert t._stall_median_s == 0.2
    steps(t, 1, 0.5)     # against the new median: not ten times it
    assert t.snapshot()["stall"]["steps"] == 0


# -- where it shows ----------------------------------------------------------

def test_the_snapshot_has_no_gc_or_stops_group_before_a_start():
    t = StepTelemetry()
    assert "stall" in t.snapshot()
    assert not {"gc", "stops"} & set(t.snapshot())
    t.stops = ProcessStops()        # attached, never started
    assert not {"gc", "stops"} & set(t.snapshot())
    t.stops.start()
    t.stops.stop()
    snap = t.snapshot()
    assert {"gc", "stops", "stall"} <= set(snap)
    # nested, so the flat JSON-line twin skips them untouched
    assert all(isinstance(snap[k], dict) for k in ("gc", "stops", "stall"))


def test_the_prometheus_families_render():
    pytest.importorskip("prometheus_client")
    from prometheus_client import CollectorRegistry, generate_latest

    from scalable_hw_agnostic_inference_tpu.serve.metrics import (
        EngineTelemetryCollector,
    )

    clk = Clocks()
    t = StepTelemetry(total_blocks=10, max_steps=4)
    t.stops = clk.make()
    t.stops._on_gc("start", GEN2)
    clk.t += 0.25
    t.stops._on_gc("stop", GEN2)
    clk.wake(t.stops, 0.5, 0.0)
    steps(t, 4, 0.01, fetch=9.0)
    steps(t, 1, 0.61, fetch=9.0, admit=600.0)
    reg = CollectorRegistry()
    reg.register(EngineTelemetryCollector(lambda: t, "pod"))
    text = generate_latest(reg).decode()

    def scraped(line):
        return float(next(ln for ln in text.splitlines()
                          if ln.startswith(line)).split()[-1])

    lab = '{app="pod",'
    assert scraped(f'shai_process_gc_pause_seconds_total{lab}'
                   f'generation="2"}}') == pytest.approx(0.25)
    assert scraped(f'shai_process_gc_pause_seconds_total{lab}'
                   f'generation="0"}}') == 0
    assert scraped(f'shai_process_gc_collections_total{lab}'
                   f'generation="2"}}') == 1
    assert scraped(f'shai_process_stopped_seconds_total{lab}'
                   f'cause="frozen"}}') == pytest.approx(0.5)
    assert scraped(f'shai_process_stops_total{lab}cause="frozen"}}') == 1
    assert scraped(f'shai_process_stops_total{lab}cause="starved"}}') == 0
    assert scraped(f'shai_engine_stalled_steps_total{lab}'
                   f'phase="admit"}}') == 1
    assert scraped('shai_engine_stalled_seconds_total{app="pod"}') == (
        pytest.approx(0.6))
    # an engine with no app around it: the process's families are absent,
    # the telemetry's own are there
    t.stops = None
    text = generate_latest(reg).decode()
    assert "shai_process_" not in text
    assert "shai_engine_stalled_seconds_total" in text


class TelemetryService(EchoService):
    """An engine's telemetry with no engine behind it."""

    def load(self):
        super().load()
        self.tele = StepTelemetry(total_blocks=10)
        self.tele.phase_enter("loop.idle")

    def engine_telemetry(self):
        return getattr(self, "tele", None)


@pytest.mark.asyncio
async def test_stats_metrics_and_debug_flight_carry_them():
    from scalable_hw_agnostic_inference_tpu.serve.app import create_app

    cfg = make_cfg()
    service = TelemetryService(cfg)
    app = create_app(cfg, service)
    proc = obs_stops.PROCESS
    try:
        async with make_client(app) as c:
            await wait_ready(c)
            assert proc._thread is not None and proc._thread.is_alive()
            assert service.tele.stops is proc
            gc.collect()
            eng = (await c.get("/stats")).json()["engine"]
            assert eng["gc"]["collections_gen2"] >= 1
            assert eng["gc"]["full_pause_s"] > 0
            assert {"frozen_s", "starved_s", "gc_s", "count_frozen",
                    "count_starved", "count_gc", "max_s"} <= set(
                        eng["stops"])
            assert eng["stall"] == {"steps": 0, "excess_s": 0.0,
                                    "steps_by_phase": {}}
            text = (await c.get("/metrics")).text
            for family in ("shai_process_gc_pause_seconds_total",
                           "shai_process_gc_collections_total",
                           "shai_process_stopped_seconds_total",
                           "shai_process_stops_total",
                           "shai_engine_stalled_seconds_total"):
                assert family in text, family
            d = (await c.get("/debug/flight")).json()
            assert d["engine_steps"] == []
            recs = [r for r in d["stops"] if r["cause"] == "collection"]
            assert recs and set(recs[-1]) == {
                "ts", "dur_s", "cause", "generation", "loop_phase",
                "cpu_s"}
            # the phase the engine loop had open when it was noticed
            assert recs[-1]["loop_phase"] == "loop.idle"
            await app._run_shutdown()
            assert proc._thread is None and proc._on_gc not in gc.callbacks
            assert proc.loop_phase is None      # nor the app's service kept
    finally:
        proc.stop()
        proc.loop_phase = None


@pytest.mark.asyncio
async def test_an_engineless_pods_flight_dump_has_stops_and_no_phase():
    from scalable_hw_agnostic_inference_tpu.serve.app import create_app

    cfg = make_cfg()
    app = create_app(cfg, EchoService(cfg))
    try:
        async with make_client(app) as c:
            await wait_ready(c)
            gc.collect()
            d = (await c.get("/debug/flight")).json()
            assert "engine" not in (await c.get("/stats")).json()
    finally:
        obs_stops.PROCESS.stop()
        obs_stops.PROCESS.loop_phase = None
    recs = [r for r in d["stops"] if r["cause"] == "collection"]
    assert recs and recs[-1]["loop_phase"] is None


def test_obs_stops_imports_the_standard_library_and_obs_alone():
    import ast

    with open(obs_stops.__file__) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            assert node.level == 1 and node.module == "trace" or (
                node.level == 0 and node.module in sys.stdlib_module_names
                or node.module == "__future__")
        elif isinstance(node, ast.Import):
            assert all(a.name in sys.stdlib_module_names for a in node.names)
