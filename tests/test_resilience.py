"""resilience/ unit tests: fault-spec grammar + determinism, deadlines,
admission gate, circuit breaker, drain controller, step watchdog, and the
capacity-checker's failure backoff. All hermetic (fake clocks, no engine)."""

import threading
import time

import pytest

from scalable_hw_agnostic_inference_tpu.resilience import faults
from scalable_hw_agnostic_inference_tpu.resilience.admission import (
    AdmissionGate,
)
from scalable_hw_agnostic_inference_tpu.resilience.breaker import (
    CircuitBreaker,
)
from scalable_hw_agnostic_inference_tpu.resilience.deadline import (
    DEADLINE_HEADER,
    Deadline,
    current_deadline,
    deadline_from_headers,
    reset_current_deadline,
    set_current_deadline,
)
from scalable_hw_agnostic_inference_tpu.resilience.drain import (
    DrainController,
    StepWatchdog,
)
from scalable_hw_agnostic_inference_tpu.orchestrate.capacity_checker import (
    OverloadThresholds,
    failure_backoff_s,
)


# ---------------------------------------------------------------------------
# faults
# ---------------------------------------------------------------------------

def test_fault_spec_grammar():
    inj = faults.FaultInjector(
        "engine.step=delay(0.01)@0.5#3,cova.rpc=error,x.y=drop#1,"
        "a.b=stall", seed=7)
    snap = inj.snapshot()
    by_site = {c["site"]: c for c in snap["clauses"]}
    assert by_site["engine.step"]["kind"] == "delay"
    assert by_site["engine.step"]["arg"] == 0.01
    assert by_site["engine.step"]["prob"] == 0.5
    assert by_site["engine.step"]["limit"] == 3
    assert by_site["cova.rpc"]["kind"] == "error"
    assert by_site["a.b"]["arg"] == 30.0      # stall default
    assert inj.active


def test_fault_spec_rejects_garbage():
    for bad in ("site", "s=frobnicate", "s=error@1.5", "=error",
                "s=delay(x)"):
        with pytest.raises(ValueError):
            faults.FaultInjector(bad)


def test_fault_determinism_and_limits():
    def pattern(seed):
        inj = faults.FaultInjector("a=error@0.5", seed=seed)
        return [inj.should_fail("a") for _ in range(50)]

    assert pattern(3) == pattern(3)          # same seed → same schedule
    assert pattern(3) != pattern(4)          # seed actually matters
    assert 5 < sum(pattern(3)) < 45          # prob ~ 0.5

    inj = faults.FaultInjector("a=error#2")
    assert [inj.should_fail("a") for _ in range(5)] == [
        True, True, False, False, False]     # limit caps firings


def test_fault_sites_are_independent_streams():
    """A site's firing pattern must not depend on how OTHER sites
    interleave (the chaos suite's reproducibility requirement)."""
    solo = faults.FaultInjector("a=error@0.5", seed=1)
    a_solo = [solo.should_fail("a") for _ in range(20)]
    mixed = faults.FaultInjector("a=error@0.5,b=error@0.5", seed=1)
    a_mixed = []
    for i in range(20):
        mixed.should_fail("b")               # interleaved other-site draws
        a_mixed.append(mixed.should_fail("a"))
    assert a_solo == a_mixed


def test_fault_kind_helpers_do_not_cross_fire():
    inj = faults.FaultInjector("a=error")
    assert inj.sleep_at("a") == 0.0          # no delay clause on a
    assert not inj.should_drop("a")
    assert inj.should_fail("a")
    with pytest.raises(faults.FaultError):
        inj.raise_at("a")


def test_fault_global_configure_and_reset():
    try:
        inj = faults.configure("a=drop")
        assert faults.get() is inj
        assert faults.get().should_drop("a")
    finally:
        faults.reset()
    assert not faults.get().active


def test_fault_endpoint_not_armed_by_spec_env(monkeypatch):
    """SHAI_FAULTS (a benign env fault on a canary) must NOT arm the
    unauthenticated POST /debug/faults write endpoint — only the explicit
    SHAI_FAULTS_ENDPOINT opt-in does, as the README contract states."""
    monkeypatch.delenv("SHAI_FAULTS_ENDPOINT", raising=False)
    monkeypatch.setenv("SHAI_FAULTS", "engine.step=delay(0.01)@0.01")
    assert not faults.endpoint_enabled()
    monkeypatch.setenv("SHAI_FAULTS_ENDPOINT", "1")
    assert faults.endpoint_enabled()
    monkeypatch.setenv("SHAI_FAULTS_ENDPOINT", "0")
    assert not faults.endpoint_enabled()


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------

def test_deadline_from_headers():
    dl = deadline_from_headers({DEADLINE_HEADER: "250"})
    assert 0.0 < dl.remaining_s <= 0.25
    assert not dl.expired
    assert deadline_from_headers({}) is None
    dl = deadline_from_headers({}, default_ms=100)
    assert dl is not None and dl.remaining_s <= 0.1
    # nan slips through both `<= 0` and `min()` (NaN comparisons are all
    # False) and would mint a never-expiring Deadline(at=NaN); inf would
    # defeat the MAX_DEADLINE_MS clamp the same way
    for bad in ("abc", "0", "-5", "nan", "inf", "-inf"):
        with pytest.raises(ValueError):
            deadline_from_headers({DEADLINE_HEADER: bad})
    # the clamp itself still admits large finite budgets
    assert deadline_from_headers({DEADLINE_HEADER: "1e12"}) is not None


def test_deadline_contextvar_roundtrip():
    assert current_deadline() is None
    dl = Deadline.after_ms(1000)
    token = set_current_deadline(dl)
    try:
        assert current_deadline() is dl
        # contextvars propagate onto threads via copy_context — the lane
        # hop the serving layer relies on
        import contextvars

        seen = {}
        ctx = contextvars.copy_context()
        t = threading.Thread(
            target=lambda: seen.update(dl=ctx.run(current_deadline)))
        t.start()
        t.join()
        assert seen["dl"] is dl
    finally:
        reset_current_deadline(token)
    assert current_deadline() is None


def test_deadline_expiry():
    assert Deadline.after_ms(-1).expired
    assert not Deadline.after_ms(60_000).expired


# ---------------------------------------------------------------------------
# admission gate
# ---------------------------------------------------------------------------

def test_admission_gate_thresholds_mirror_controller():
    gate = AdmissionGate(OverloadThresholds(max_queue_depth=2.0,
                                            max_kv_utilization=0.9))
    assert gate.check({"waiting": 1.0, "kv_utilization": 0.5}) is None
    shed = gate.check({"waiting": 5.0, "kv_utilization": 0.5})
    assert (shed.status, shed.reason) == (429, "queue_depth")
    shed = gate.check({"waiting": 0.0, "kv_utilization": 0.95})
    assert (shed.status, shed.reason) == (429, "kv_pressure")
    assert int(shed.headers["retry-after"]) >= 1
    # missing telemetry admits (absence must not refuse traffic)
    assert gate.check(None) is None
    assert gate.check({}) is None
    assert gate.shed_total == 2
    assert gate.shed_by_reason() == {"queue_depth": 1, "kv_pressure": 1}


@pytest.mark.parametrize("waiting,slots_free,reason", [
    (36.0, 32.0, None),             # every row ended at once: 4 in line
    (12.0, 0.0, "queue_depth"),     # full rows: all 12 stand in line
    (12.0, 3.0, "queue_depth"),     # 9 behind the rows that stand free
    (11.0, 3.0, None),
    (4.0, 32.0, None),              # fewer waiting than free rows
])
def test_the_gate_prices_the_line_behind_full_rows(waiting, slots_free,
                                                   reason):
    """A waiting request with a free row is one admission step from
    running, not in line: a closed loop of ``rows + 4`` callers is never
    shed, however many of its answers end in the same step."""
    gate = AdmissionGate(OverloadThresholds(max_queue_depth=8.0))
    shed = gate.check({"waiting": waiting, "slots_free": slots_free,
                       "kv_utilization": 0.5})
    assert (shed and shed.reason) == reason


def test_the_engine_snapshot_carries_its_free_rows():
    """``slots_free`` on the engine's own snapshot: a closed loop of more
    callers than rows never reads deeper than the callers beyond the rows."""
    import jax
    import jax.numpy as jnp

    from scalable_hw_agnostic_inference_tpu.engine import EngineConfig
    from scalable_hw_agnostic_inference_tpu.engine.engine import (
        LLMEngine,
        SamplingParams,
    )
    from scalable_hw_agnostic_inference_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )
    from scalable_hw_agnostic_inference_tpu.orchestrate.capacity_checker \
        import queue_depth

    cfg = LlamaConfig.tiny()
    params = LlamaForCausalLM(cfg, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    eng = LLMEngine(cfg, params, EngineConfig(
        max_model_len=64, max_num_seqs=4, block_size=8,
        context_encoding_buckets=(16,), max_new_tokens=4,
        max_prefill_batch=1))
    assert eng.obs.snapshot().get("slots_free", 0) == 0   # no step yet
    for i in range(6):
        eng.add_request([1, 5 + i, 9], SamplingParams(max_new_tokens=3))
    depths = []
    while eng.has_work:
        eng.step()
        snap = eng.obs.snapshot()
        assert snap["slots_free"] == 4 - snap["running"]
        depths.append(queue_depth(snap))
    # one prompt a step is admitted: 5 wait beside 3 free rows, and so on
    assert depths[0] == 2 and max(depths) == 2
    assert eng.obs.snapshot()["slots_free"] == 4


def test_admission_gate_drain_and_inflight():
    gate = AdmissionGate(max_inflight=2)
    shed = gate.check(None, draining=True)
    assert (shed.status, shed.reason) == (503, "draining")
    assert gate.check(None, inflight=1) is None
    shed = gate.check(None, inflight=2)
    assert (shed.status, shed.reason) == (429, "inflight")


def test_admission_gate_lane_backlog_sheds_blocking_overload():
    """Blocking requests beyond the lane width queue in the executor where
    the engine's 'waiting' gauge can't see them (only lane_width threads
    ever reach add_request at once) — the gate must price that backlog with
    the same queue-depth threshold, with NO opt-in cap configured."""
    gate = AdmissionGate(OverloadThresholds(max_queue_depth=4.0))
    # engine looks idle in every snapshot: the lane is the hidden queue
    idle = {"waiting": 0.0, "kv_utilization": 0.1}
    assert gate.check(idle, lane_pending=5, lane_width=1) is None  # 4 = cap
    shed = gate.check(idle, lane_pending=6, lane_width=1)          # 5 > cap
    assert (shed.status, shed.reason) == (429, "queue_depth")
    # a wider lane absorbs the same backlog without shedding
    assert gate.check(idle, lane_pending=6, lane_width=8) is None
    # live SSE streams hold no lane thread: a pile of open streams (large
    # inflight) with an empty lane must NOT read as executor queue depth
    assert gate.check(idle, inflight=100, lane_pending=0,
                      lane_width=1) is None
    # lane_width=0 (unknown) disables backlog pricing entirely
    assert gate.check(idle, lane_pending=100) is None


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class FixedRng:
    def random(self):
        return 0.0  # no jitter: deterministic assertions


def test_breaker_opens_after_threshold_and_probes():
    clock = FakeClock()
    br = CircuitBreaker(failure_threshold=3, base_backoff_s=1.0,
                        max_backoff_s=8.0, rng=FixedRng(), clock=clock)
    assert br.state == "closed" and br.allow()
    br.record_failure()
    br.record_failure()
    assert br.allow()                      # still closed below threshold
    br.record_failure()
    assert br.state == "open"
    assert not br.allow()                  # fail-fast while open
    assert br.retry_after_s == pytest.approx(1.0)
    clock.t = 1.1
    assert br.state == "half-open"
    assert br.allow()                      # exactly one probe
    assert not br.allow()                  # second caller still blocked
    br.record_success()
    assert br.state == "closed" and br.allow()


def test_breaker_release_probe_frees_slot_without_outcome():
    """A probe whose task is cancelled mid-call never reports back; without
    release_probe the breaker would stay half-open with allow() False
    forever. Releasing must not count as success or failure, and must be
    idempotent after record_success/record_failure already cleared it."""
    clock = FakeClock()
    br = CircuitBreaker(failure_threshold=1, base_backoff_s=1.0,
                        rng=FixedRng(), clock=clock)
    br.record_failure()
    clock.t = 1.1
    assert br.allow()                      # probe slot taken
    assert not br.allow()
    br.release_probe()                     # probe cancelled: slot freed
    assert br.state == "half-open"         # no outcome recorded
    assert br.allow()                      # next caller gets the probe
    br.record_success()
    br.release_probe()                     # idempotent after an outcome
    assert br.state == "closed" and br.allow()


def test_breaker_backoff_escalates_and_caps():
    clock = FakeClock()
    br = CircuitBreaker(failure_threshold=1, base_backoff_s=1.0,
                        max_backoff_s=4.0, rng=FixedRng(), clock=clock)
    waits = []
    for _ in range(4):
        br.record_failure()                # open (or re-open from probe)
        waits.append(br.retry_after_s)
        clock.t += br.retry_after_s + 0.01
        assert br.allow()                  # the half-open probe
    assert waits == [pytest.approx(1.0), pytest.approx(2.0),
                     pytest.approx(4.0), pytest.approx(4.0)]  # capped


def test_breaker_jitter_bounds():
    class MaxRng:
        def random(self):
            return 1.0

    clock = FakeClock()
    br = CircuitBreaker(failure_threshold=1, base_backoff_s=2.0,
                        jitter_frac=0.25, rng=MaxRng(), clock=clock)
    br.record_failure()
    assert br.retry_after_s == pytest.approx(2.5)  # base * (1 + 0.25)


# ---------------------------------------------------------------------------
# drain controller + watchdog
# ---------------------------------------------------------------------------

def test_drain_controller_budget_and_idempotence():
    clock = FakeClock()
    d = DrainController(budget_s=10.0, clock=clock)
    assert not d.draining and d.remaining_s == 10.0
    assert d.begin()
    assert not d.begin()                   # duplicate SIGTERM: no reset
    assert d.draining
    clock.t = 4.0
    assert d.remaining_s == pytest.approx(6.0)
    clock.t = 11.0
    assert d.remaining_s == 0.0
    assert d.wait(lambda: False) is False  # budget exhausted
    assert d.wait(lambda: True) is True


def test_drain_wait_returns_when_idle():
    d = DrainController(budget_s=5.0)
    d.begin()
    box = {"n": 3}

    def idle():
        box["n"] -= 1
        return box["n"] <= 0

    assert d.wait(idle, poll_s=0.001) is True


class FakeTele:
    def __init__(self, age, p99):
        self._age = age
        self._p99 = p99

    def last_step_age_s(self, now=None):
        return self._age

    def step_duration_p99(self):
        return self._p99


def test_watchdog_trips_only_when_busy_and_stale():
    clock = FakeClock()
    tele = FakeTele(age=100.0, p99=0.01)
    busy = {"v": False}
    wd = StepWatchdog(lambda: tele, lambda: busy["v"],
                      multiplier=10.0, min_stall_s=1.0, clock=clock)
    assert wd.check() is None              # idle: never trips
    busy["v"] = True
    # an idle pod's first request must NOT trip on the idle gap: the
    # stall age counts from the idle->busy transition, not the last step
    assert wd.check() is None
    clock.t = 2.0                          # busy 2s, still no step
    assert "stalled" in wd.check()         # busy + stale: trips
    tele._age = 0.5
    assert wd.check() is None              # fresh step: healthy
    # p99 scales the leash: slow-step tiers get a longer one
    tele._age = 5.0
    tele._p99 = 1.0                        # limit = max(1, 10*1.0) = 10
    clock.t = 20.0                         # busy-transition age way past
    assert wd.check() is None
    tele._age = 11.0
    assert wd.check() is not None
    # going idle resets the transition stamp
    busy["v"] = False
    assert wd.check() is None
    busy["v"] = True
    assert wd.check() is None              # fresh transition: healthy again
    # no telemetry yet (engine not loaded): healthy
    wd2 = StepWatchdog(lambda: None, lambda: True)
    assert wd2.check() is None


def test_watchdog_idle_gap_not_counted_as_stall():
    """Regression: the engine loop only steps while it has work, so a pod
    that idled an hour has a huge last-step age the moment a request
    arrives — that must not fail liveness."""
    clock = FakeClock()
    clock.t = 3600.0
    tele = FakeTele(age=3600.0, p99=0.01)  # no step since boot
    busy = {"v": True}                     # request just arrived
    wd = StepWatchdog(lambda: tele, lambda: busy["v"],
                      multiplier=10.0, min_stall_s=1.0, clock=clock)
    assert wd.check() is None              # healthy: just became busy
    clock.t = 3600.5
    assert wd.check() is None              # still inside the leash
    clock.t = 3602.0                       # busy 2s with no step: stuck
    assert wd.check() is not None


def test_fault_async_sleep_shares_draw_stream():
    """asleep_at (event-loop sites: cova RPC) must draw the same schedule
    as sleep_at — the spec/seed fully determines firing either way."""
    import asyncio

    sync = faults.FaultInjector("a=delay(0.001)@0.5", seed=9)
    pattern_sync = [sync.sleep_at("a") > 0 for _ in range(20)]

    ainj = faults.FaultInjector("a=delay(0.001)@0.5", seed=9)

    async def drain():
        return [await ainj.asleep_at("a") > 0 for _ in range(20)]

    assert asyncio.run(drain()) == pattern_sync
    assert 2 < sum(pattern_sync) < 18      # prob actually ~0.5


# ---------------------------------------------------------------------------
# capacity-checker failure backoff (pure)
# ---------------------------------------------------------------------------

def test_failure_backoff_schedule():
    assert failure_backoff_s(0) == 0.0
    assert [failure_backoff_s(k, base_s=2.0, cap_s=300.0)
            for k in (1, 2, 3, 4, 8)] == [2.0, 4.0, 8.0, 16.0, 256.0]
    assert failure_backoff_s(20, base_s=2.0, cap_s=300.0) == 300.0
