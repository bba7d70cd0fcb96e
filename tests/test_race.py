"""shai-race: the concurrency analysis pass (analysis/race.py) and its
dynamic twin, the deterministic interleaving harness (tests/schedutil.py).

Static half: fixture snippets prove each rule (lock-order,
blocking-under-lock, guarded-read) catches a seeded violation and stays
quiet on the legal idiom / a valid allow annotation; the live tree stays
clean; the CLI honors the shared 0/1/2 exit contract with race-rule-only
baseline staleness.

Dynamic half: the REAL ``EngineLoop`` / ``CopyOutWorker`` /
``TenantLedger`` / ``HostKVTier`` seams run under a cooperative scheduler
that replays seeded + boundary interleavings of submit/cancel vs step vs
demotion vs drain vs ledger traffic, asserting no-deadlock,
terminal-exactly-once, pool-exact accounting — and that NO nested lock
acquisition is ever observed (the dynamic mirror of the contract's empty
``lock_order``).

Deviceless: no jax execution anywhere in this file.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from collections import deque

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scalable_hw_agnostic_inference_tpu.analysis import (  # noqa: E402
    core as lint_core,
)
from scalable_hw_agnostic_inference_tpu.analysis import race  # noqa: E402
from scalable_hw_agnostic_inference_tpu.analysis.contract import (  # noqa: E402
    ClassPolicy,
    Contract,
    RaceSpec,
)
from scalable_hw_agnostic_inference_tpu.analysis.core import (  # noqa: E402
    Module,
)
from scalable_hw_agnostic_inference_tpu.engine.loop import (  # noqa: E402
    EngineLoop,
)
from scalable_hw_agnostic_inference_tpu.engine.types import (  # noqa: E402
    Finished,
)
from scalable_hw_agnostic_inference_tpu.obs.steploop import (  # noqa: E402
    StepTelemetry,
)
from scalable_hw_agnostic_inference_tpu.kvtier.pool import (  # noqa: E402
    HostKVTier,
)
from scalable_hw_agnostic_inference_tpu.resilience.qos import (  # noqa: E402
    TenantBudget,
    TenantLedger,
)

import schedutil  # noqa: E402
from schedutil import (  # noqa: E402
    DeadlockError,
    ScheduleExhausted,
    Scheduler,
    TracedLock,
    instrument_engine_loop,
    instrument_tier_worker,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mod(relpath: str, src: str) -> Module:
    return Module(relpath, textwrap.dedent(src))


def live(findings):
    return [f for f in findings if not f.allowed]


RACE = dataclasses.replace(
    Contract(),
    thread_contract={
        "Loop": ClassPolicy(
            lock_guarded={"_futures": "_futures_lock"},
            owning_modules=("engine/loop.py",),
            instance_markers=(".loop.",),
        ),
        "Ledger": ClassPolicy(
            lock_guarded={"_stats": "_lock"},
            owning_modules=("resilience/qos.py",),
            instance_markers=("ledger.", ".ledger."),
        ),
    },
    dict_guards={"serve/app.py": {"state": (("inflight",),
                                            "inflight_lock")}},
    race=RaceSpec(
        module_locks={"serve/app.py": {"inflight_lock":
                                       "app.inflight_lock"}},
        hot_locks=("Loop._futures_lock", "Ledger._lock",
                   "app.inflight_lock"),
        lock_order=(),
    ),
)


# -- lock-order ---------------------------------------------------------------

class TestLockOrder:
    def test_lexical_nesting_undeclared_is_flagged(self):
        m = mod("engine/loop.py", """\
            class Loop:
                def bad(self):
                    with self._futures_lock:
                        with self.ledger._lock:
                            pass
            """)
        found = live(race.check_lock_order([m], RACE))
        assert len(found) == 1
        assert "Loop._futures_lock" in found[0].message
        assert "Ledger._lock" in found[0].message
        assert "undeclared nesting" in found[0].message

    def test_declared_order_edge_is_clean_and_reverse_contradicts(self):
        c = dataclasses.replace(RACE, race=dataclasses.replace(
            RACE.race,
            lock_order=(("Loop._futures_lock", "Ledger._lock"),)))
        ok = mod("engine/loop.py", """\
            class Loop:
                def fine(self):
                    with self._futures_lock:
                        with self.ledger._lock:
                            pass
            """)
        assert live(race.check_lock_order([ok], c)) == []
        inv = mod("resilience/qos.py", """\
            class Ledger:
                def bad(self):
                    with self._lock:
                        with self.loop._futures_lock:
                            pass
            """)
        found = live(race.check_lock_order([inv], c))
        assert len(found) == 1
        assert "contradicts the declared order" in found[0].message

    def test_cross_module_cycle_both_edges_flagged(self):
        a = mod("engine/loop.py", """\
            class Loop:
                def one(self):
                    with self._futures_lock:
                        with self.ledger._lock:
                            pass
            """)
        b = mod("resilience/qos.py", """\
            class Ledger:
                def two(self):
                    with self._lock:
                        with self.loop._futures_lock:
                            pass
            """)
        found = live(race.check_lock_order([a, b], RACE))
        assert len(found) == 2
        assert all("closes an acquisition cycle" in f.message
                   for f in found)

    def test_call_graph_propagation_through_markers(self):
        """A method call made while a lock is held inherits the callee's
        acquisitions (depth 2), resolved through instance markers."""
        ledger = mod("resilience/qos.py", """\
            class Ledger:
                def bump(self):
                    with self._lock:
                        self._stats["n"] = 1
            """)
        looped = mod("engine/loop.py", """\
            class Loop:
                def bad(self, ledger):
                    with self._futures_lock:
                        ledger.bump()
            """)
        found = live(race.check_lock_order([ledger, looped], RACE))
        assert len(found) == 1
        assert "Ledger.bump()" in found[0].message
        assert found[0].path == "engine/loop.py"

    def test_self_reacquisition_is_flagged(self):
        m = mod("resilience/qos.py", """\
            class Ledger:
                def outer(self):
                    with self._lock:
                        self.inner()

                def inner(self):
                    with self._lock:
                        pass
            """)
        found = live(race.check_lock_order([m], RACE))
        assert len(found) == 1
        assert "self-deadlocks" in found[0].message

    def test_multi_item_with_orders_left_to_right(self):
        m = mod("engine/loop.py", """\
            class Loop:
                def bad(self, ledger):
                    with self._futures_lock, ledger._lock:
                        pass
            """)
        found = live(race.check_lock_order([m], RACE))
        assert len(found) == 1

    def test_undeclared_locks_are_ignored(self):
        m = mod("obs/trace.py", """\
            class Tracer:
                def fine(self):
                    with self._lock:
                        with self._other_lock:
                            pass
            """)
        assert live(race.check_lock_order([m], RACE)) == []

    def test_allow_annotation(self):
        m = mod("engine/loop.py", """\
            class Loop:
                def boot(self):
                    with self._futures_lock:
                        # shai-lint: allow(lock-order) boot-time only,
                        # single-threaded
                        with self.ledger._lock:
                            pass
            """)
        found = race.check_lock_order([m], RACE)
        assert len(found) == 1 and found[0].allowed

    def test_cyclic_declared_order_is_a_finding(self):
        c = dataclasses.replace(RACE, race=dataclasses.replace(
            RACE.race,
            lock_order=(("Loop._futures_lock", "Ledger._lock"),
                        ("Ledger._lock", "Loop._futures_lock"))))
        found = live(race.check_lock_order([], c))
        assert len(found) == 1 and found[0].context == "<contract>"


# -- blocking-under-lock ------------------------------------------------------

class TestBlockingUnderLock:
    def test_positive_each_pattern(self):
        m = mod("engine/loop.py", """\
            import time
            import requests

            class Loop:
                def bad(self, fut, q, ev, t, arr):
                    with self._futures_lock:
                        fut.result()
                        q.get()
                        q.put(1)
                        ev.wait()
                        t.join()
                        time.sleep(0.1)
                        requests.post("http://x")
                        arr.block_until_ready()
                        # spelling the unbounded default out loud is
                        # still unbounded
                        fut.result(timeout=None)
                        q.get(block=True)
            """)
        found = live(race.check_blocking([m], RACE))
        assert len(found) == 10
        assert all("Loop._futures_lock" in f.message for f in found)

    def test_bounded_and_nonblocking_forms_are_clean(self):
        m = mod("engine/loop.py", """\
            class Loop:
                def fine(self, fut, q, ev, t):
                    with self._futures_lock:
                        fut.result(timeout=1.0)
                        q.get_nowait()
                        q.put_nowait(1)
                        q.get(timeout=0.1)
                        q.get(block=False)
                        ev.wait(timeout=0.5)
                        t.join(2.0)
                        d = {}
                        d.get("k")        # dict.get: positional arg
                        ", ".join(["a"])  # str.join: positional arg
            """)
        assert live(race.check_blocking([m], RACE)) == []

    def test_deferred_callback_under_lock_is_not_under_lock(self):
        """A nested def/lambda defined inside `with <lock>:` runs AFTER
        the release — its body must not count as lock-held (neither for
        blocking-under-lock nor for the acquisition graph)."""
        m = mod("engine/loop.py", """\
            class Loop:
                def fine(self, q, reg, ledger):
                    with self._futures_lock:
                        def cb():
                            q.get()
                            with ledger._lock:
                                pass
                        reg(cb)
                        pull = lambda: q.get()
                        reg(pull)
            """)
        assert live(race.check_blocking([m], RACE)) == []
        assert live(race.check_lock_order([m], RACE)) == []

    def test_blocking_outside_hot_lock_is_clean(self):
        m = mod("engine/loop.py", """\
            import time

            class Loop:
                def fine(self, q):
                    q.get()
                    time.sleep(1)
                    with self._plain_lock:
                        q.get()
            """)
        assert live(race.check_blocking([m], RACE)) == []

    def test_module_lock_scope_and_allow(self):
        m = mod("serve/app.py", """\
            def create_app(state, inflight_lock, q):
                def bad():
                    with inflight_lock:
                        q.get()

                def excused():
                    with inflight_lock:
                        # shai-lint: allow(blocking-under-lock) bounded by
                        # construction: the queue always holds an item here
                        q.get()
                return bad, excused
            """)
        found = race.check_blocking([m], RACE)
        assert len(found) == 2
        assert sum(f.allowed for f in found) == 1
        assert "app.inflight_lock" in found[0].message


# -- guarded-read -------------------------------------------------------------

class TestGuardedRead:
    def test_in_class_read_outside_lock_flagged(self):
        m = mod("engine/loop.py", """\
            class Loop:
                def __init__(self):
                    self._futures = {}

                def torn(self):
                    return len(self._futures)

                def fine(self):
                    with self._futures_lock:
                        return len(self._futures)
            """)
        found = live(race.check_guarded_reads([m], RACE))
        assert len(found) == 1 and found[0].context == "Loop.torn"

    def test_write_sites_left_to_thread_rule(self):
        # mutator calls and subscript stores are WRITE sites — the thread
        # rule owns them; guarded-read must not double-report
        m = mod("engine/loop.py", """\
            class Loop:
                def writes(self, rid, fut):
                    self._futures[rid] = fut
                    self._futures.clear()
                    del self._futures[rid]
            """)
        assert live(race.check_guarded_reads([m], RACE)) == []

    def test_dict_guard_read_flagged_and_locked_read_clean(self):
        m = mod("serve/app.py", """\
            def create_app(state, inflight_lock):
                def torn():
                    return state["inflight"]

                def fine():
                    with inflight_lock:
                        return state["inflight"]

                def other_key():
                    return state["loaded"]
                return torn, fine, other_key
            """)
        found = live(race.check_guarded_reads([m], RACE))
        assert len(found) == 1 and found[0].context == "create_app.torn"

    def test_deferred_read_under_lexical_lock_is_flagged(self):
        """The inverse of the deferred-callback rule: a guarded READ in a
        callback defined under `with <lock>:` actually runs unlocked —
        the lexical lock must not excuse it."""
        m = mod("engine/loop.py", """\
            class Loop:
                def leak(self, reg):
                    with self._futures_lock:
                        def cb():
                            return len(self._futures)
                        reg(cb)
            """)
        found = live(race.check_guarded_reads([m], RACE))
        assert len(found) == 1 and "_futures" in found[0].message

    def test_marker_read_from_non_owning_module_flagged(self):
        m = mod("serve/handlers.py", """\
            def peek(service):
                return len(service.loop._futures)
            """)
        found = live(race.check_guarded_reads([m], RACE))
        assert len(found) == 1
        assert "snapshot method" in found[0].message

    def test_allow_annotation(self):
        m = mod("engine/loop.py", """\
            class Loop:
                def helper(self):
                    # shai-lint: allow(guarded-read) caller-holds-lock
                    # helper
                    return len(self._futures)
            """)
        found = race.check_guarded_reads([m], RACE)
        assert len(found) == 1 and found[0].allowed


# -- the live tree ------------------------------------------------------------

class TestLiveTree:
    def test_live_tree_is_clean_and_helpers_annotated(self):
        findings = race.run_race()
        fresh = live(findings)
        assert not fresh, "\n".join(f.render() for f in fresh)
        # the caller-holds-lock helpers stay DOCUMENTED, not exempted
        allowed = [f for f in findings if f.allowed]
        assert any(f.rule == "guarded-read"
                   and f.context.startswith("TenantLedger.")
                   for f in allowed)

    def test_fresh_run_matches_committed_baseline_race_rules(self):
        fresh = {f.fingerprint for f in race.run_race() if not f.allowed}
        committed = {fp for fp in lint_core.load_baseline()
                     if fp.split("|", 1)[0] in race.RACE_RULES}
        assert fresh == committed == set(), (
            "the race baseline is expected to stay empty; fix or "
            "annotate new findings instead of inheriting them")


# -- CLI ----------------------------------------------------------------------

class TestCli:
    def test_race_gate_green_json_contract(self):
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "shai_lint.py"),
             "--race", "--json"],
            capture_output=True, text=True, cwd=ROOT, timeout=60)
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(r.stdout)
        assert payload["pass"] == "race"
        assert payload["new"] == []
        assert payload["stale_baseline"] == []
        # acceptance: the full race pass comfortably under 10 s
        assert payload["elapsed_s"] < 10.0
        # the intentional caller-holds-lock annotations reach tooling
        assert any(f["rule"] == "guarded-read" for f in payload["allowed"])

    def test_race_changed_mode_green(self):
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "shai_lint.py"),
             "--race", "--changed", "--json"],
            capture_output=True, text=True, cwd=ROOT, timeout=60)
        assert r.returncode == 0, r.stdout + r.stderr
        assert json.loads(r.stdout)["new"] == []

    def test_race_and_ir_are_mutually_exclusive(self):
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "shai_lint.py"),
             "--race", "--ir"],
            capture_output=True, text=True, cwd=ROOT, timeout=60)
        assert r.returncode == 2
        assert "separate passes" in r.stderr

    def test_partial_race_run_cannot_rewrite_baseline(self):
        r = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "shai_lint.py"),
             "--race", "--changed", "--update-baseline"],
            capture_output=True, text=True, cwd=ROOT, timeout=60)
        assert r.returncode == 2
        assert "full run" in r.stderr


# -- the harness itself -------------------------------------------------------

class TestHarness:
    def test_opposite_order_acquisition_deadlocks_and_is_reported(self):
        sched = Scheduler(seed=1, policy="switch")
        a = TracedLock(sched, "A")
        b = TracedLock(sched, "B")

        def t1():
            with a:
                sched.yield_point("t1-mid")
                with b:
                    pass

        def t2():
            with b:
                sched.yield_point("t2-mid")
                with a:
                    pass

        sched.spawn("t1", t1)
        sched.spawn("t2", t2)
        with pytest.raises(DeadlockError) as ei:
            sched.run()
        assert "seed=1" in str(ei.value)
        # the nesting witness recorded both attempted edges
        assert ("A", "B") in sched.nesting_edges or \
            ("B", "A") in sched.nesting_edges

    def test_coarse_boundary_schedule_avoids_the_same_deadlock(self):
        """`stay` runs each thread to completion — the deadlock above
        needs interleaving to manifest; the harness explores BOTH."""
        sched = Scheduler(seed=0, policy="stay")
        a = TracedLock(sched, "A")
        b = TracedLock(sched, "B")

        def t1():
            with a:
                sched.yield_point("t1-mid")
                with b:
                    pass

        def t2():
            with b:
                sched.yield_point("t2-mid")
                with a:
                    pass

        sched.spawn("t1", t1)
        sched.spawn("t2", t2)
        sched.run()  # completes: serialized execution, no contention
        assert sched.nesting_edges == {("A", "B"), ("B", "A")}

    def test_same_seed_replays_identical_trace(self):
        def build():
            sched = Scheduler(seed=7, policy="random")
            lk = TracedLock(sched, "L")

            def worker(i):
                def body():
                    for _ in range(3):
                        with lk:
                            sched.yield_point(f"w{i}")
                return body

            for i in range(3):
                sched.spawn(f"w{i}", worker(i))
            sched.run()
            return sched.trace

        assert build() == build()

    def test_livelock_trips_event_cap(self):
        sched = Scheduler(seed=0, policy="switch", max_events=200)

        def spin():
            while True:
                sched.yield_point("spin")

        sched.spawn("s1", spin)
        sched.spawn("s2", spin)
        with pytest.raises(ScheduleExhausted):
            sched.run()


# -- the interleaving scenarios ----------------------------------------------

class StubEngine:
    """Deterministic deviceless engine behind the real EngineLoop: each
    request finishes after ``steps_per_req`` steps; every
    ``demote_every``-th step demotes one block into the (real) host
    tier. Yield points at the phase boundaries give the scheduler seams
    inside a step."""

    def __init__(self, sched, tier=None, steps_per_req=2, demote_every=2):
        self.sched = sched
        self.tier = tier
        self.steps_per_req = steps_per_req
        self.demote_every = demote_every
        self.obs = StepTelemetry()   # the loop enters its phases here
        self.waiting = deque()
        self.running = {}
        self.finished_ids = []
        self.cancelled_ids = []
        self.demoted = 0
        self.seen = 0
        self.steps = 0
        self._next_rid = 0

    def add_request(self, prompt_ids, params, **kw):
        rid = self._next_rid
        self._next_rid += 1
        self.seen += 1
        self.waiting.append(rid)
        return rid

    def fanout_siblings(self, rid):
        # engine protocol: a non-fanout request's group is itself (the
        # loop cancels fan-out groups as a unit through this call)
        return [rid]

    @property
    def has_work(self):
        return bool(self.waiting or self.running)

    def step(self):
        self.sched.yield_point("engine:step")
        while self.waiting:
            self.running[self.waiting.popleft()] = self.steps_per_req
        fins = []
        for rid in list(self.running):
            self.running[rid] -= 1
            if self.running[rid] <= 0:
                del self.running[rid]
                self.finished_ids.append(rid)
                fins.append(Finished(req_id=rid, token_ids=[1],
                                     n_prompt=1, stop_reason="length"))
        self.steps += 1
        if self.tier is not None and self.steps % self.demote_every == 0:
            t = self.tier
            blk = np.full((t.n_layers, 1, t.block_size, t.n_kv_heads,
                           t.head_dim), float(self.steps), t.dtype)
            self.sched.yield_point("engine:demote")
            t.store_batch([10_000 + self.steps], blk, blk.copy(), 1)
            self.demoted += 1
        return fins

    def cancel(self, rid):
        if rid in self.running:
            del self.running[rid]
            self.cancelled_ids.append(rid)
            return Finished(req_id=rid, token_ids=[], n_prompt=1,
                            stop_reason="cancelled")
        if rid in self.waiting:
            self.waiting.remove(rid)
            self.cancelled_ids.append(rid)
            return Finished(req_id=rid, token_ids=[], n_prompt=1,
                            stop_reason="cancelled")
        return None  # already terminal

    def finish_pending(self):
        return None


def _run_scenario(policy, seed, drain_early=False):
    """Submit/cancel vs step vs demotion vs drain vs ledger under one
    deterministic interleaving. Returns everything the caller asserts
    on."""
    sched = Scheduler(seed=seed, policy=policy)
    tier = HostKVTier(n_layers=1, block_size=2, n_kv_heads=1, head_dim=2,
                      dtype=np.float32, capacity_bytes=0, async_copy=True)
    tier.capacity_bytes = 3 * tier.block_nbytes  # hold 3 blocks: evictions
    instrument_tier_worker(sched, tier)
    ledger = TenantLedger({"a": TenantBudget(rate=1e6, burst=1e6)})
    ledger._lock = TracedLock(sched, "ledger")
    eng = StubEngine(sched, tier=tier)
    loop = EngineLoop(eng, poll_s=0.0)
    instrument_engine_loop(sched, loop)

    futures = []
    sheds = []
    charged = {"n": 0}
    n_clients, per_client = 2, 2
    submitted = schedutil.TracedEvent(sched, "all-submitted")
    done_clients = {"n": 0}

    def client(i):
        def body():
            for j in range(per_client):
                try:
                    futures.append(loop.submit([1, 2, 3]))
                except RuntimeError:
                    sheds.append((i, j))
                sched.yield_point(f"client{i}:submitted")
            if i == 0 and futures:
                loop.cancel(futures[0])
            done_clients["n"] += 1
            if done_clients["n"] == n_clients:
                submitted.set()
        return body

    def ledger_traffic():
        for _ in range(3):
            if ledger.admit("a") is None:
                ledger.note_start("a")
                sched.yield_point("ledger:inflight")
                ledger.charge("a", 3)
                charged["n"] += 1
                ledger.note_done("a")

    def scraper():
        for _ in range(4):
            snap = tier.snapshot()
            # pool-exact accounting must hold at EVERY observable point,
            # not just quiescence
            assert snap["used_bytes"] == \
                snap["entries"] * tier.block_nbytes
            ledger.snapshot()
            sched.yield_point("scrape")

    def drainer():
        if not drain_early:
            submitted.wait()
        loop.drain(budget_s=30.0)
        assert tier.close(timeout=10.0), "copy-out worker not joined"

    for i in range(n_clients):
        sched.spawn(f"client{i}", client(i))
    sched.spawn("ledger", ledger_traffic)
    sched.spawn("scraper", scraper)
    sched.spawn("drainer", drainer)
    sched.run()
    return sched, eng, loop, tier, ledger, futures, sheds, charged


def _assert_invariants(sched, eng, loop, tier, ledger, futures, sheds,
                       charged):
    # no-deadlock: run() returned. No lock nesting was ever OBSERVED —
    # the dynamic mirror of the contract's empty lock_order table
    assert sched.nesting_edges == set(), sched.nesting_edges
    # terminal-exactly-once: every accepted future resolved exactly once
    # (a double set_result would have raised InvalidStateError in the
    # loop thread and failed the run); engine-side terminal sets are
    # disjoint and duplicate-free
    for fut in futures:
        assert fut.done()
    fins = set(eng.finished_ids)
    cans = set(eng.cancelled_ids)
    assert len(eng.finished_ids) == len(fins)
    assert len(eng.cancelled_ids) == len(cans)
    assert not (fins & cans)
    resolved = sum(1 for f in futures if f.exception() is None)
    failed = sum(1 for f in futures if f.exception() is not None)
    assert resolved + failed == len(futures)
    # pool-exact accounting at quiescence
    snap = tier.snapshot()
    assert snap["used_bytes"] == snap["entries"] * tier.block_nbytes
    assert snap["stores"] == snap["entries"] + snap["evictions"]
    assert snap["stores"] + snap["dropped"] == eng.demoted
    assert snap["errors"] == 0
    # the worker was JOINED, not orphaned
    assert not tier._worker.alive
    # ledger conserved: inflight back to zero, tokens == charges
    lsnap = ledger.snapshot()
    if charged["n"]:
        assert lsnap["a"]["inflight"] == 0
        assert lsnap["a"]["tokens"] == 3 * charged["n"]


@pytest.mark.parametrize("policy,seed", [
    ("stay", 0), ("switch", 0),
    ("random", 0), ("random", 1), ("random", 2), ("random", 3),
])
def test_interleavings_uphold_invariants(policy, seed):
    _assert_invariants(*_run_scenario(policy, seed))


@pytest.mark.parametrize("policy,seed", [("random", 4), ("switch", 1)])
def test_drain_racing_submission_sheds_cleanly(policy, seed):
    """Drain armed while clients are still submitting: late submissions
    shed with RuntimeError, everything accepted still reaches exactly
    one terminal state, accounting stays exact."""
    sched, eng, loop, tier, ledger, futures, sheds, charged = \
        _run_scenario(policy, seed, drain_early=True)
    _assert_invariants(sched, eng, loop, tier, ledger, futures, sheds,
                       charged)
    assert eng.seen == len(futures)  # shed submissions never reached it


@pytest.mark.parametrize("policy,seed", [("switch", 0), ("random", 11)])
def test_flight_recorder_dump_is_not_torn(policy, seed):
    """Regression for the live guarded-read finding in
    FlightRecorder.dump: ``recorded_total`` used to be read AFTER the
    ring copy's lock was released, so a concurrent record_request could
    tear the snapshot (total > the newest seq in the copied ring). Under
    the harness the interleaving that exposes it is deterministic."""
    from scalable_hw_agnostic_inference_tpu.obs.flight import (
        FlightRecorder,
    )

    sched = Scheduler(seed=seed, policy=policy)
    fr = FlightRecorder(max_requests=64)
    fr._lock = TracedLock(sched, "flight")

    def writer():
        for i in range(8):
            fr.record_request({"trace_id": f"t{i}"})
            sched.yield_point("w")

    def reader():
        for _ in range(8):
            out = fr.dump()
            if out["requests"]:
                # the copied ring and the total came from ONE lock hold
                assert out["recorded_total"] == \
                    out["requests"][-1]["seq"], out
            sched.yield_point("r")

    sched.spawn("writer", writer)
    sched.spawn("reader", reader)
    sched.run()
    assert sched.nesting_edges == set()


@pytest.mark.slow  # seed sweep: the fuzz tail beyond the tier-1 seeds
@pytest.mark.parametrize("seed", range(5, 29))
def test_interleaving_seed_sweep(seed):
    _assert_invariants(*_run_scenario("random", seed))
    sched, eng, *rest = _run_scenario("random", seed, drain_early=True)
    _assert_invariants(sched, eng, *rest)
