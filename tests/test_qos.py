"""Multi-tenant QoS: priority classes, weighted-fair scheduling, budgets.

Three layers under one contract:

1. the **scheduler kernel** in isolation (deviceless property tests):
   stride weights respected within tolerance over N rounds of seeded
   randomized arrivals, FIFO within a class, and the aging bound honored
   whatever weights an operator configures;
2. the **engine** dequeue/preemption integration: QoS-off (and uniform-
   priority QoS-on) stays token-exact vs the FIFO baseline across both
   async disciplines, priorities reorder admission and preemption, and a
   seeded adversarial tenant mix (flooder + trickle + cancels + deadlines
   + preemption pressure) keeps terminal-exactly-once, bounded trickle
   delay, and pool-exact accounting;
3. the **serving stack**: the tenant ledger's token buckets, the
   budget-derived ``Retry-After`` at the admission gate, and the live
   429-while-others-serve contract over a real socket with the
   ``shai_shed_total{reason="tenant_budget"}`` / ``shai_tenant_*``
   families on ``/metrics``.
"""

import time
from collections import deque

import numpy as np
import pytest

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
from scalable_hw_agnostic_inference_tpu.resilience import qos
from scalable_hw_agnostic_inference_tpu.resilience.admission import (
    AdmissionGate,
)


# ---------------------------------------------------------------------------
# header / grammar parsing (lenient by contract)
# ---------------------------------------------------------------------------

def test_parse_priority_lenient():
    assert qos.parse_priority("high") == qos.PRIORITY_HIGH
    assert qos.parse_priority("NORMAL") == qos.PRIORITY_NORMAL
    assert qos.parse_priority("low") == qos.PRIORITY_LOW
    assert qos.parse_priority("0") == 0
    assert qos.parse_priority("2") == 2
    assert qos.parse_priority("7") == qos.PRIORITY_LOW      # clamped
    assert qos.parse_priority("-3") == qos.PRIORITY_HIGH    # clamped
    # lenient: a typo degrades to the default, never an error
    assert qos.parse_priority("urgent!!") == qos.PRIORITY_NORMAL
    assert qos.parse_priority(None) == qos.PRIORITY_NORMAL
    assert qos.parse_priority("", default=2) == 2


def test_qos_from_headers_env_defaults(monkeypatch):
    t, p = qos.qos_from_headers({qos.TENANT_HEADER: "acme-Corp.1",
                                 qos.PRIORITY_HEADER: "high"})
    assert (t, p) == ("acme-Corp.1", qos.PRIORITY_HIGH)
    # absent headers: env defaults fill in
    monkeypatch.setenv("SHAI_TENANT_DEFAULT", "pool-a")
    monkeypatch.setenv("SHAI_PRIORITY_DEFAULT", "low")
    t, p = qos.qos_from_headers({})
    assert (t, p) == ("pool-a", qos.PRIORITY_LOW)
    # header beats env; hostile tenant ids sanitize + truncate
    t, p = qos.qos_from_headers(
        {qos.TENANT_HEADER: 'x" } evil\n{' + "y" * 200,
         qos.PRIORITY_HEADER: "zzz"})
    assert t.startswith("x")
    assert '"' not in t and "\n" not in t and " " not in t
    assert len(t) <= qos.MAX_TENANT_CHARS
    assert p == qos.PRIORITY_LOW  # malformed header -> env default


def test_budget_grammar_lenient():
    b = qos.parse_budgets("acme=100:200, free=10 , *=50")
    assert b["acme"] == qos.TenantBudget(rate=100.0, burst=200.0)
    assert b["free"] == qos.TenantBudget(rate=10.0, burst=10.0)
    assert b["*"].rate == 50.0
    # malformed clauses are skipped, never fatal, never partial-applied
    b = qos.parse_budgets("good=5,bad,=3,neg=-1,zero=0,also=x:y")
    assert list(b) == ["good"]
    assert qos.parse_budgets("") == {}


def test_scheduler_from_env_weights(monkeypatch):
    monkeypatch.setenv("SHAI_QOS_WEIGHTS", "high=16,low=2,junk,oops=zz")
    monkeypatch.setenv("SHAI_QOS_AGING_ROUNDS", "7")
    s = qos.WeightedFairScheduler.from_env()
    assert s.weights[qos.PRIORITY_HIGH] == 16.0
    assert s.weights[qos.PRIORITY_LOW] == 2.0
    assert s.weights[qos.PRIORITY_NORMAL] == \
        qos.DEFAULT_WEIGHTS[qos.PRIORITY_NORMAL]  # untouched default
    assert s.aging_rounds == 7


# ---------------------------------------------------------------------------
# tenant ledger: token buckets, debt, bounded cardinality
# ---------------------------------------------------------------------------

def _clocked_ledger(spec, **kw):
    t = [0.0]
    led = qos.TenantLedger(qos.parse_budgets(spec), clock=lambda: t[0],
                           **kw)
    return led, t


def test_ledger_debt_and_budget_derived_retry_after():
    led, t = _clocked_ledger("a=10:20")
    assert led.admit("a") is None           # bucket starts full
    led.charge("a", 50)                     # served work drives it into debt
    ra = led.admit("a")
    assert ra is not None and ra > 0
    # deficit is 30 tokens + 1 headroom at 10 tok/s -> 3.1 s, exactly
    assert ra == pytest.approx((1.0 + 30.0) / 10.0)
    t[0] += ra                              # refill exactly out of debt
    assert led.admit("a") is None
    # burst caps banked credit: a long idle gap is not unlimited tokens
    t[0] += 1e6
    led.charge("a", 21)
    assert led.admit("a") is not None


def test_ledger_unmetered_and_wildcard():
    led, _ = _clocked_ledger("a=5")
    assert led.admit("nobody") is None      # no budget, no wildcard
    led.charge("nobody", 10**6)
    assert led.admit("nobody") is None      # still unmetered
    led, _ = _clocked_ledger("*=5:5")
    led.charge("anyone", 6)
    assert led.admit("anyone") is not None  # wildcard meters everyone
    assert led.metered


def test_ledger_bounded_cardinality_keeps_budgets_enforceable():
    led, _ = _clocked_ledger("vip=5:5", max_tenants=2)
    led.note_start("t1")
    led.note_start("t2")
    # the table is full: later names collapse into "other"...
    assert led.label_of("t3-minted") == qos.OTHER_TENANT
    assert led.label_of("t4-minted") == qos.OTHER_TENANT
    led.note_start("t3-minted")
    snap = led.snapshot()
    assert set(snap) <= {"t1", "t2", qos.OTHER_TENANT, "vip"}
    # ...but a tenant with its OWN configured budget stays enforceable
    led.charge("vip", 6)
    assert led.admit("vip") is not None
    assert led.label_of("vip") == "vip"


def test_ledger_inflight_accounting_thread_counters():
    led, _ = _clocked_ledger("")
    led.note_start("a")
    led.note_start("a")
    led.note_done("a")
    assert led.inflight_of("a") == 1
    led.note_done("a")
    led.note_done("a")                      # floor at zero, never negative
    assert led.inflight_of("a") == 0
    snap = led.snapshot()
    assert snap["a"]["requests"] == 2


# ---------------------------------------------------------------------------
# scheduler kernel in isolation (deviceless property tests)
# ---------------------------------------------------------------------------

class _Item:
    def __init__(self, priority, seq):
        self.priority = priority
        self.seq = seq


def _drive(sched, arrivals, rng, max_backlog=64):
    """Seeded arrival schedule -> the engine's rotate+popleft discipline.
    Returns the popped items in service order."""
    waiting = deque()
    served = []
    seq = 0
    for n_new, classes in arrivals:
        for _ in range(n_new):
            cls = int(classes[int(rng.integers(len(classes)))])
            waiting.append(_Item(cls, seq))
            seq += 1
        if waiting:
            qos.schedule_rotate(waiting, sched)
            served.append(waiting.popleft())
    while waiting:
        qos.schedule_rotate(waiting, sched)
        served.append(waiting.popleft())
    return served


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_scheduler_weight_shares_under_backlog(seed):
    """With every class permanently backlogged, service shares track the
    stride weights within tolerance over N rounds."""
    rng = np.random.default_rng(seed)
    sched = qos.WeightedFairScheduler()  # 8:4:1
    waiting = deque(_Item(c, i) for i, c in enumerate(
        rng.integers(0, 3, 2000)))
    counts = {0: 0, 1: 0, 2: 0}
    for _ in range(1040):
        qos.schedule_rotate(waiting, sched)
        counts[waiting.popleft().priority] += 1
    total = sum(counts.values())
    for cls, w in qos.DEFAULT_WEIGHTS.items():
        share = counts[cls] / total
        want = w / sum(qos.DEFAULT_WEIGHTS.values())
        assert abs(share - want) < 0.05, (cls, share, want, counts)


@pytest.mark.parametrize("seed", [3, 4])
def test_scheduler_fifo_within_class_random_arrivals(seed):
    """Whatever the interleaving, two requests of the SAME class are
    served in arrival order (the weighted-fair dequeue reorders classes,
    never a class's own queue)."""
    rng = np.random.default_rng(seed)
    sched = qos.WeightedFairScheduler()
    arrivals = [(int(rng.integers(0, 4)), [0, 1, 2]) for _ in range(400)]
    served = _drive(sched, arrivals, rng)
    by_class = {}
    for item in served:
        by_class.setdefault(item.priority, []).append(item.seq)
    for cls, seqs in by_class.items():
        assert seqs == sorted(seqs), f"class {cls} served out of order"
    # and nothing was lost or duplicated
    assert sorted(i.seq for i in served) == list(range(len(served)))


def test_scheduler_aging_bound_whatever_the_weights():
    """Anti-starvation: even with a pathological 10^6:1 weight ratio, the
    low class is served at least once every aging_rounds+1 selections —
    delayed, never starved."""
    sched = qos.WeightedFairScheduler({0: 1e6, 2: 1.0}, aging_rounds=8)
    last = -1
    gaps = []
    for i in range(500):
        if sched.select([0, 2]) == 2:
            gaps.append(i - last)
            last = i
    assert gaps, "low class never served at all"
    assert max(gaps) <= sched.aging_rounds + 1
    assert sched.aged_picks > 0
    snap = sched.snapshot()
    assert snap["picks_low"] >= 500 // (sched.aging_rounds + 1)


def test_scheduler_rejoin_banks_no_credit():
    """A class absent for a long stretch re-enters at the current pass
    floor: its backlog does not get to monopolize service as 'owed'
    rounds (stride join-at-minimum semantics)."""
    sched = qos.WeightedFairScheduler()  # 8:4:1
    for _ in range(500):
        assert sched.select([1]) == 1    # only normal present for a while
    picks = {0: 0, 1: 0}
    for _ in range(120):
        picks[sched.select([0, 1])] += 1
    # high (weight 8) should win ~2/3 of rounds; if rejoin banked credit,
    # it would win ~all of them
    assert 60 <= picks[0] <= 100, picks


def test_scheduler_aging_streak_resets_on_absence():
    """"Skipped" means skipped while ELIGIBLE: a class that drains and
    later re-joins must restart its aging streak, not carry the old one
    into an immediate forced pick."""
    sched = qos.WeightedFairScheduler({0: 1e6, 2: 1.0}, aging_rounds=8)
    assert sched.select([0, 2]) == 0        # tie-break: high first
    assert sched.select([0, 2]) == 2        # stride: low's one early pick
    for _ in range(6):                      # low banks a 6-round streak
        assert sched.select([0, 2]) == 0
    for _ in range(3):
        sched.select([0])                   # low's queue drained (absent)
    # re-join: the streak restarted — a FULL fresh aging_rounds of
    # eligible skips must pass before the forced pick (had the banked 6
    # survived, aging would fire on the 2nd round back)
    for i in range(8):
        assert sched.select([0, 2]) == 0, f"aged too early, round {i}"
    assert sched.aged_picks == 0
    assert sched.select([0, 2]) == 2        # fresh streak completes
    assert sched.aged_picks == 1


def test_schedule_rotate_noops():
    sched = qos.WeightedFairScheduler()
    w = deque([_Item(1, 0)])
    qos.schedule_rotate(w, sched)           # single item: untouched
    assert [i.seq for i in w] == [0]
    w = deque([_Item(1, 0), _Item(1, 1), _Item(1, 2)])
    qos.schedule_rotate(w, sched)           # single class: untouched AND
    assert [i.seq for i in w] == [0, 1, 2]  # no stride state consumed
    assert sched.picks == {}


# ---------------------------------------------------------------------------
# admission gate: budget-derived Retry-After (satellite), tenant caps
# ---------------------------------------------------------------------------

def test_gate_budget_derived_retry_after_vs_static():
    led, _ = _clocked_ledger("a=10:10")
    gate = AdmissionGate(ledger=led, retry_after_s=1.0)
    assert gate.check(tenant="a") is None
    led.charge("a", 60)                     # 50 tokens of debt
    shed = gate.check(tenant="a")
    assert shed is not None and shed.status == 429
    assert shed.reason == "tenant_budget"
    # Retry-After derives from the refill deficit, NOT the static hint
    assert shed.retry_after_s == pytest.approx(51.0 / 10.0)
    assert shed.headers["retry-after"] == "5"
    # other tenants keep serving through the same gate
    assert gate.check(tenant="b") is None
    # structural sheds keep the static hint
    gate2 = AdmissionGate(max_inflight=1, retry_after_s=1.0, ledger=led)
    shed2 = gate2.check(inflight=1, tenant="b")
    assert shed2 is not None and shed2.reason == "inflight"
    assert shed2.retry_after_s == 1.0


def test_gate_tenant_inflight_cap():
    led, _ = _clocked_ledger("")
    gate = AdmissionGate(ledger=led, tenant_max_inflight=2)
    led.note_start("a")
    led.note_start("a")
    shed = gate.check(tenant="a")
    assert shed is not None and shed.reason == "tenant_inflight"
    assert gate.check(tenant="b") is None   # cap is per tenant
    led.note_done("a")
    assert gate.check(tenant="a") is None


def test_fleet_tenant_aggregation_pure():
    from scalable_hw_agnostic_inference_tpu.orchestrate.cova import (
        aggregate_tenant_usage,
    )

    results = {
        "pod-a": {"qos": {"tenants": {
            "acme": {"requests": 3, "tokens": 30, "inflight": 1,
                     "budget_balance": -4.0},
            "free": {"requests": 1, "tokens": 5}}}},
        "pod-b": {"qos": {"tenants": {
            "acme": {"requests": 2, "tokens": 20, "shed": 1}}}},
        "pod-dead": {"error": "unreachable"},
        "pod-weird": {"qos": {"tenants": "not-a-dict"}},
    }
    agg = aggregate_tenant_usage(results)
    assert agg["acme"]["requests"] == 5
    assert agg["acme"]["tokens"] == 50
    assert agg["acme"]["backends"] == 2
    assert agg["acme"]["shed"] == 1
    # per-pod bucket state is never summed into fake fleet credit
    assert "budget_balance" not in agg["acme"]
    assert agg["free"]["backends"] == 1
    assert aggregate_tenant_usage({}) == {}
    # non-additive means are dropped too: two pods at 50ms are not 100ms
    agg = aggregate_tenant_usage({
        "a": {"qos": {"tenants": {"t": {"engine_ttft_mean_ms": 50.0,
                                        "engine_ttft_count": 3}}}},
        "b": {"qos": {"tenants": {"t": {"engine_ttft_mean_ms": 50.0,
                                        "engine_ttft_count": 1}}}}})
    assert "engine_ttft_mean_ms" not in agg["t"]
    assert agg["t"]["engine_ttft_count"] == 4


# ---------------------------------------------------------------------------
# engine integration (tiny model, CPU)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    cfg = LlamaConfig.tiny()
    model = LlamaForCausalLM(cfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return cfg, model, params


def make_engine(tiny_model, **over):
    cfg, _, params = tiny_model
    kw = dict(max_model_len=128, max_num_seqs=3, block_size=8,
              context_encoding_buckets=(16, 32), max_new_tokens=16)
    kw.update(over)
    return LLMEngine(cfg, params, EngineConfig(**kw))


def _prompts(cfg, n, rng, lens=(5, 9, 14)):
    return [[int(x) for x in rng.integers(2, cfg.vocab_size,
                                          int(rng.choice(lens)))]
            for _ in range(n)]


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.parametrize("async_on", ["0", "1"])
@pytest.mark.parametrize("sp", [
    SamplingParams(temperature=0.0, max_new_tokens=6),
    SamplingParams(temperature=0.9, top_p=0.8, max_new_tokens=6),
    SamplingParams(temperature=0.7, top_k=12, max_new_tokens=6),
])
def test_qos_off_differential_token_exact(tiny_model, monkeypatch,
                                          async_on, sp):
    """THE differential contract: with no tenant/priority tags, the QoS-on
    engine produces byte-identical tokens to the QoS-off engine — the
    scheduler must be a strict no-op without real class contention, across
    both async disciplines and sampled decoding."""
    cfg, _, _ = tiny_model
    rng = np.random.default_rng(42)
    prompts = _prompts(cfg, 7, rng)
    monkeypatch.setenv("SHAI_ASYNC_DECODE", async_on)
    monkeypatch.delenv("SHAI_QOS", raising=False)
    base = [f.token_ids
            for f in make_engine(tiny_model).generate(prompts, sp)]
    monkeypatch.setenv("SHAI_QOS", "1")
    on = [f.token_ids
          for f in make_engine(tiny_model).generate(prompts, sp)]
    assert on == base


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_qos_off_preemption_differential(tiny_model, monkeypatch):
    """Preemption pressure (tight pool) with QoS on but uniform priority:
    the victim choice key degenerates to the FIFO engine's and tokens stay
    exact."""
    cfg, _, _ = tiny_model
    rng = np.random.default_rng(3)
    prompts = _prompts(cfg, 6, rng, lens=(20, 40, 60))
    sp = SamplingParams(temperature=0.0, max_new_tokens=12)
    monkeypatch.delenv("SHAI_QOS", raising=False)
    eng = make_engine(tiny_model, num_blocks=22)
    base = [f.token_ids for f in eng.generate(prompts, sp)]
    assert eng.obs.preemptions > 0, "schedule did not exercise preemption"
    monkeypatch.setenv("SHAI_QOS", "1")
    eng2 = make_engine(tiny_model, num_blocks=22)
    on = [f.token_ids for f in eng2.generate(prompts, sp)]
    assert on == base


def test_priority_jumps_queue_under_contention(tiny_model, monkeypatch):
    """One slot, a low-priority flood queued first, one high-priority
    arrival last: the weighted-fair dequeue admits the high request ahead
    of the queued flood (it finishes first or immediately after the
    already-running request)."""
    cfg, _, _ = tiny_model
    rng = np.random.default_rng(5)
    prompts = _prompts(cfg, 5, rng)
    sp = SamplingParams(temperature=0.0, max_new_tokens=4)
    monkeypatch.setenv("SHAI_QOS", "1")
    eng = make_engine(tiny_model, max_num_seqs=1)
    lows = [eng.add_request(p, sp, priority=qos.PRIORITY_LOW,
                            tenant="flood") for p in prompts[:4]]
    high = eng.add_request(prompts[4], sp, priority=qos.PRIORITY_HIGH,
                           tenant="vip")
    order = []
    want = set(lows) | {high}
    steps = 0
    while want and steps < 500:
        steps += 1
        for f in eng.step():
            order.append(f.req_id)
            want.discard(f.req_id)
    assert not want
    assert order.index(high) <= 1, order
    snap = eng.obs.tenant_snapshot()
    assert snap["vip"]["requests_high"] == 1
    assert snap["flood"]["requests_low"] == 4
    assert snap["vip"]["ttft_count"] == 1


def test_preemption_evicts_lowest_priority_first(tiny_model, monkeypatch):
    """Pool pressure picks its recompute victim lowest-priority-first (and
    most-recent within a class), not simply most-recent."""
    monkeypatch.setenv("SHAI_QOS", "1")
    eng = make_engine(tiny_model, max_num_seqs=2, num_blocks=64)
    sp = SamplingParams(temperature=0.0, max_new_tokens=12)
    # admit low FIRST (lower req_id), then high — the old most-recent rule
    # would evict the high one
    low = eng.add_request(list(range(2, 12)), sp,
                          priority=qos.PRIORITY_LOW)
    eng.step()
    high = eng.add_request(list(range(2, 14)), sp,
                           priority=qos.PRIORITY_HIGH)
    eng.step()
    running = {s.req.req_id for s in eng.slots if s is not None}
    assert running == {low, high}
    eng._preempt_lowest()
    still = {s.req.req_id for s in eng.slots if s is not None}
    assert still == {high}, "victim must be the low-priority sequence"
    assert eng.waiting and eng.waiting[0].req_id == low
    # drain cleanly — the preempted remainder resumes and finishes once
    done = {}
    steps = 0
    while eng.has_work and steps < 500:
        steps += 1
        for f in eng.step():
            assert f.req_id not in done
            done[f.req_id] = f
    assert set(done) == {low, high}


def test_priority_never_shields_preemption_with_qos_off(tiny_model,
                                                        monkeypatch):
    """With SHAI_QOS unset, an X-SHAI-Priority tag must be inert: the
    preemption victim stays the most-recent sequence even when it claims
    high priority — an unauthenticated header is not an anti-preemption
    lever on a FIFO pod."""
    monkeypatch.delenv("SHAI_QOS", raising=False)
    eng = make_engine(tiny_model, max_num_seqs=2, num_blocks=64)
    sp = SamplingParams(temperature=0.0, max_new_tokens=8)
    low = eng.add_request(list(range(2, 12)), sp, priority=qos.PRIORITY_LOW)
    eng.step()
    high = eng.add_request(list(range(2, 14)), sp,
                           priority=qos.PRIORITY_HIGH)
    eng.step()
    assert {s.req.req_id for s in eng.slots if s is not None} == {low, high}
    eng._preempt_lowest()
    still = {s.req.req_id for s in eng.slots if s is not None}
    assert still == {low}, "QoS off: most-recent rule, priority inert"
    while eng.has_work:
        eng.step()


def test_group_admission_consults_scheduler_per_pick(tiny_model,
                                                     monkeypatch):
    """The batched-prefill group ladder is class-aware beyond the head:
    with a low-priority flood queued FIRST and two high requests behind
    it, the first admission group seats both highs — the flood does not
    get to fill the batch by arrival order."""
    monkeypatch.setenv("SHAI_QOS", "1")
    eng = make_engine(tiny_model, max_num_seqs=4)
    sp = SamplingParams(temperature=0.0, max_new_tokens=6)
    prompt = list(range(2, 12))             # one bucket for everyone
    lows = [eng.add_request(list(prompt), sp, priority=qos.PRIORITY_LOW)
            for _ in range(4)]
    highs = [eng.add_request(list(prompt), sp, priority=qos.PRIORITY_HIGH)
             for _ in range(2)]
    eng.step()
    running = {s.req.req_id for s in eng.slots if s is not None}
    assert set(highs) <= running, (
        f"both high-priority requests must make the first group; "
        f"running={running}, highs={highs}")
    while eng.has_work:
        eng.step()


def test_expired_queued_requests_free_same_step(tiny_model, monkeypatch):
    """Deadline-expiry fairness (satellite): queued requests past their
    deadline are finished in ONE linear pass the same step — an expired
    high-priority request frees its queue slot immediately under QoS, and
    every expiry is terminal exactly once."""
    monkeypatch.setenv("SHAI_QOS", "1")
    eng = make_engine(tiny_model, max_num_seqs=1)
    sp = SamplingParams(temperature=0.0, max_new_tokens=8)
    # occupy the only slot so the queue actually queues
    running = eng.add_request(list(range(2, 10)), sp)
    eng.step()
    past = time.monotonic() - 0.01
    dead = [eng.add_request(list(range(2, 8)), sp,
                            priority=qos.PRIORITY_HIGH, deadline_at=past)
            for _ in range(4)]
    live = eng.add_request(list(range(2, 9)), sp,
                           priority=qos.PRIORITY_HIGH)
    fins = eng.step()
    timed_out = {f.req_id for f in fins if f.stop_reason == "timeout"}
    assert timed_out == set(dead), "all expired queue entries, same step"
    assert all(f.req_id not in timed_out or f.stop_reason == "timeout"
               for f in fins)
    assert eng.n_waiting == 1               # only the live one remains
    done = {f.req_id for f in fins}
    steps = 0
    while eng.has_work and steps < 300:
        steps += 1
        for f in eng.step():
            assert f.req_id not in done, "terminal twice"
            done.add(f.req_id)
    assert {running, live} <= done


def _sched_state(eng):
    sched = eng._sched
    return (dict(sched._pass), dict(sched._skipped), dict(sched.picks),
            sched.aged_picks, [r.req_id for r in eng.waiting])


@pytest.mark.parametrize("async_on", ["0", "1"], ids=["lockstep", "async"])
def test_blocked_steps_leave_the_scheduler_untouched(tiny_model, monkeypatch,
                                                     async_on):
    """Two classes queued behind full slots: a step that can dequeue
    nothing is a no-op for the weighted-fair scheduler in BOTH disciplines
    — no pass advances, no aging streak grows, the queue keeps its order.
    The stride moves only when a dequeue can follow, so the async path may
    stream through such steps and still admit in the oracle's order."""
    monkeypatch.setenv("SHAI_QOS", "1")
    monkeypatch.setenv("SHAI_ASYNC_DECODE", async_on)
    eng = make_engine(tiny_model, max_num_seqs=2)
    long_sp = SamplingParams(temperature=0.0, max_new_tokens=60)
    for i in range(2):
        eng.add_request([2 + i, 5, 7], long_sp)
    eng.step()
    assert eng._free_slot() is None
    for i, prio in enumerate((qos.PRIORITY_LOW, qos.PRIORITY_HIGH,
                              qos.PRIORITY_LOW, qos.PRIORITY_NORMAL)):
        eng.add_request([9, 3 + i], long_sp, priority=prio)
    before = _sched_state(eng)
    flushes = eng.obs.snapshot()["pipeline_flushes"]
    for _ in range(12):
        assert eng.step() == []
    assert _sched_state(eng) == before
    assert eng._free_slot() is None and eng.n_waiting == 4
    # ...and the async engine streamed through all twelve
    assert eng.obs.snapshot()["pipeline_flushes"] == flushes


def test_mixed_class_admission_order_same_in_both_disciplines(tiny_model,
                                                              monkeypatch):
    """Twelve callers of three classes on four slots, closed loop: the
    async engine (which skips blocked steps) and the lock-step oracle admit
    the same requests in the same order on the same steps, and deliver the
    same tokens."""
    monkeypatch.setenv("SHAI_QOS", "1")
    prios = (qos.PRIORITY_HIGH, qos.PRIORITY_NORMAL, qos.PRIORITY_LOW)
    out = {}
    for async_on in ("1", "0"):
        monkeypatch.setenv("SHAI_ASYNC_DECODE", async_on)
        eng = make_engine(tiny_model, max_num_seqs=4)
        admitted, tokens, owner, sent = [], {}, {}, [0] * 12
        note = eng._note_admitted

        def note_admitted(req):
            admitted.append((eng._step_count, req.req_id))
            note(req)

        eng._note_admitted = note_admitted

        def send(c):
            rid = eng.add_request(
                [2 + c, 3 + sent[c], 5],
                SamplingParams(temperature=0.0,
                               max_new_tokens=(5, 9, 7)[(c + sent[c]) % 3]),
                priority=prios[c % 3], tenant=f"t{c % 3}")
            owner[rid] = c
            sent[c] += 1

        for c in range(12):
            send(c)
        while eng.has_work:
            for f in eng.step():
                tokens[f.req_id] = f.token_ids
                if sent[owner[f.req_id]] < 2:
                    send(owner[f.req_id])
        out[async_on] = (admitted, tokens, dict(eng._sched.picks))
        assert len(admitted) == 24
    assert out["1"] == out["0"]
    # the scheduler really arbitrated: every class was picked
    assert set(out["1"][2]) == set(prios)


# ---------------------------------------------------------------------------
# adversarial tenant-mix fuzz: starvation-freedom + exactly-once +
# pool-exact accounting
# ---------------------------------------------------------------------------

def _adversarial_run(tiny_model, seed, *, kvtier=False):
    cfg, _, _ = tiny_model
    rng = np.random.default_rng(seed)
    over = dict(max_num_seqs=2, num_blocks=26,
                enable_prefix_caching=True)
    eng = make_engine(tiny_model, **over)
    total_blocks = eng.ecfg.total_blocks
    sp = lambda mnt: SamplingParams(temperature=0.0, max_new_tokens=mnt)

    done: dict = {}
    meta: dict = {}     # rid -> (tenant, submit_step)
    admit_step: dict = {}
    queued: set = set()
    trickle_left = 6
    flood_left = 22
    steps = 0
    while (flood_left or trickle_left or eng.has_work) and steps < 4000:
        steps += 1
        # the flooding tenant: low priority, bursty, sometimes with an
        # already-tight deadline; the trickle tenant: high priority,
        # occasional, must make progress through the flood
        for _ in range(int(rng.integers(0, 3))):
            if not flood_left:
                break
            flood_left -= 1
            dl = (time.monotonic() + float(rng.uniform(0.05, 0.4))
                  if rng.random() < 0.25 else 0.0)
            n = int(rng.choice([5, 9, 14, 20]))
            rid = eng.add_request(
                [int(x) for x in rng.integers(2, cfg.vocab_size, n)],
                sp(int(rng.choice([3, 6, 9]))),
                priority=qos.PRIORITY_LOW, tenant="flood", deadline_at=dl)
            meta[rid] = ("flood", steps)
            queued.add(rid)
        if trickle_left and rng.random() < 0.12:
            trickle_left -= 1
            rid = eng.add_request(
                [int(x) for x in rng.integers(2, cfg.vocab_size, 7)],
                sp(4), priority=qos.PRIORITY_HIGH, tenant="trickle")
            meta[rid] = ("trickle", steps)
            queued.add(rid)
        # cancel storms against in-flight work
        if rng.random() < 0.08:
            live = [r for r in meta if r not in done]
            if live:
                fin = eng.cancel(live[int(rng.integers(len(live)))])
                if fin is not None:
                    assert fin.req_id not in done, "terminal twice (cancel)"
                    done[fin.req_id] = fin
        for f in eng.step():
            assert f.req_id not in done, "terminal twice (step)"
            done[f.req_id] = f
        # admission-delay tracking: when did each request leave the queue
        still_queued = {r.req_id for r in eng.waiting}
        for rid in list(queued):
            if rid not in still_queued:
                admit_step.setdefault(rid, steps)
                queued.discard(rid)
    return eng, done, meta, admit_step, steps, total_blocks


def _check_adversarial(eng, done, meta, admit_step, steps, total_blocks):
    assert steps < 4000, "engine did not drain (livelock)"
    # terminal-exactly-once for every submitted request
    assert set(done) == set(meta), (
        f"missing terminals: {set(meta) - set(done)}")
    for fin in done.values():
        assert fin.stop_reason in ("eos", "length", "rejected",
                                   "cancelled", "timeout")
    # pool-exact device accounting (block 0 is the reserved null block)
    cache_held = len(eng.cache._hash2block)
    assert eng.cache.allocator.n_free + cache_held == total_blocks - 1, (
        f"block leak: free={eng.cache.allocator.n_free} "
        f"cached={cache_held} total={total_blocks}")
    if eng.cache.tier is not None:
        # host pool accounting stays exact too
        snap = eng.cache.tier.snapshot()
        assert snap["used_bytes"] == snap["entries"] * \
            eng.cache.tier.block_nbytes
    # starvation-freedom: every trickle request that was ADMITTED (not
    # cancelled/expired straight from the queue) left the queue within a
    # bounded number of scheduling rounds despite the flood
    trickle = [rid for rid, (t, _) in meta.items() if t == "trickle"]
    assert trickle
    for rid in trickle:
        if done[rid].stop_reason in ("cancelled", "timeout", "rejected"):
            continue
        assert rid in admit_step, f"trickle req {rid} never admitted"
        delay = admit_step[rid] - meta[rid][1]
        assert delay <= 64, (
            f"trickle req {rid} waited {delay} scheduling rounds")


def test_qos_adversarial_mix_fuzz(tiny_model, monkeypatch):
    monkeypatch.setenv("SHAI_QOS", "1")
    _check_adversarial(*_adversarial_run(tiny_model, seed=0))


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_qos_adversarial_mix_fuzz_more_seeds(tiny_model, monkeypatch, seed):
    monkeypatch.setenv("SHAI_QOS", "1")
    _check_adversarial(*_adversarial_run(tiny_model, seed=seed))


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_qos_adversarial_mix_fuzz_with_kvtier(tiny_model, monkeypatch):
    """Same adversarial mix with the host KV tier on: preemption demotes
    instead of deleting, and BOTH pools must account exactly at drain."""
    monkeypatch.setenv("SHAI_QOS", "1")
    monkeypatch.setenv("SHAI_KVTIER", "1")
    monkeypatch.setenv("SHAI_KVTIER_ASYNC", "0")
    eng, *rest = _adversarial_run(tiny_model, seed=4, kvtier=True)
    assert eng.cache.tier is not None
    _check_adversarial(eng, *rest)


@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_qos_adversarial_mix_fuzz_lockstep(tiny_model, monkeypatch):
    monkeypatch.setenv("SHAI_QOS", "1")
    monkeypatch.setenv("SHAI_ASYNC_DECODE", "0")
    _check_adversarial(*_adversarial_run(tiny_model, seed=5))


# ---------------------------------------------------------------------------
# live budget enforcement over a real socket (acceptance: 429 + finite
# Retry-After for the over-budget tenant WHILE other tenants serve, with
# the tenant metric families on /metrics)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 budget: see scripts/check_tier1_budget.py
def test_tenant_budget_enforced_over_real_socket(monkeypatch):
    import http.client
    import json as _json

    from scalable_hw_agnostic_inference_tpu.models.registry import get_model
    from scalable_hw_agnostic_inference_tpu.serve.app import create_app
    from scalable_hw_agnostic_inference_tpu.serve.httpd import Server
    from scalable_hw_agnostic_inference_tpu.utils.env import ServeConfig

    monkeypatch.setenv("SHAI_QOS", "1")
    # tiny budget: one request (a handful of tokens) exhausts the bucket,
    # and the refill is slow enough that the next call still sheds
    monkeypatch.setenv("SHAI_TENANT_BUDGETS", "greedy=0.5:4")
    cfg = ServeConfig(app="llm", model_id="tiny", device="cpu",
                      max_new_tokens=8, vllm_config="/nonexistent.yaml")
    service = get_model("vllm")(cfg)
    app = create_app(cfg, service)
    srv = Server(app, host="127.0.0.1", port=0)
    srv.start_background()
    port = srv.port
    deadline = time.time() + 300
    while True:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/readiness")
        r = conn.getresponse()
        r.read()
        conn.close()
        if r.status == 200:
            break
        assert time.time() < deadline, "service never became ready"
        time.sleep(1.0)

    def post(tenant, prio="normal"):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request("POST", "/generate",
                     body=_json.dumps({"prompt": "hello world",
                                       "max_new_tokens": 4,
                                       "temperature": 0.0}),
                     headers={"Content-Type": "application/json",
                              "X-SHAI-Tenant": tenant,
                              "X-SHAI-Priority": prio})
        r = conn.getresponse()
        body = r.read().decode()
        headers = {k.lower(): v for k, v in r.getheaders()}
        conn.close()
        return r.status, headers, body

    s1, _, _ = post("greedy")
    assert s1 == 200                         # first request fits the burst
    s2, h2, _ = post("greedy")
    assert s2 == 429                         # bucket in debt now
    ra = float(h2["retry-after"])
    assert ra >= 1.0 and ra < 3600.0         # finite, budget-derived
    # the other tenant keeps serving through the same pod
    s3, _, body3 = post("patient", prio="high")
    assert s3 == 200 and "generated_text" in body3

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", "/metrics")
    metrics = conn.getresponse().read().decode()
    conn.close()
    assert 'shai_shed_total{' in metrics
    assert 'reason="tenant_budget"' in metrics
    assert 'tenant="greedy"' in metrics
    assert "shai_tenant_tokens_total" in metrics
    assert "shai_tenant_budget_balance" in metrics
    assert "shai_tenant_requests_total" in metrics
    assert "shai_tenant_ttft_seconds" in metrics

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", "/stats")
    stats = _json.loads(conn.getresponse().read().decode())
    conn.close()
    assert stats["qos"]["metered"]
    assert stats["qos"]["tenants"]["greedy"]["shed"] >= 1
    assert stats["qos"]["tenants"]["patient"]["requests"] >= 1
    assert "scheduler" in stats["qos"]
    srv.request_shutdown()
