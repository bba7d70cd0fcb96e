"""The one traffic generator: a schedule is a function of (mix, seed)."""

import collections

import pytest

from benchmark import traffic
from benchmark.reference.check import byte_ids
from benchmark.spec import Spec

SPEC = Spec()
MIXES = sorted({w["traffic"] for w in SPEC.bench["workloads"]})


@pytest.mark.parametrize("mix_name", MIXES)
def test_schedule_is_a_function_of_mix_and_seed(mix_name):
    mix = SPEC.traffic(mix_name)
    a = traffic.schedule(mix, 2147483659, 20.0)
    b = traffic.schedule(mix, 2147483659, 20.0)
    c = traffic.schedule(mix, 7, 20.0)
    assert a == b
    assert [(p.n_prompt, p.n_out, p.due_s) for p in a] != \
        [(p.n_prompt, p.n_out, p.due_s) for p in c]
    assert all(p.prompt != q.prompt for p, q in zip(a, c))


@pytest.mark.parametrize("mix_name", MIXES)
def test_every_seed_gets_the_same_sequence_begun_elsewhere(mix_name):
    """A rotation: the same (gap, sizes) in the same cyclic order, so the
    same bursts meet the same long prompts whatever the seed."""
    mix = SPEC.traffic(mix_name)
    a = traffic.schedule(mix, 1, 20.0)
    c = traffic.schedule(mix, 2, 20.0)
    if mix["loop"] == "closed":
        n = traffic.CLOSED_LOOP_CYCLE
        cyc = lambda s: [(p.n_prompt, p.n_out) for p in s[:n]]
        assert [(p.n_prompt, p.n_out) for p in a[n:2 * n]] == cyc(a)
        doubled = cyc(a) + cyc(a)
        assert cyc(a) != cyc(c)
        assert any(doubled[k:k + n] == cyc(c) for k in range(n))
        return
    win = lambda s: [p for p in s if p.due_s >= 0]
    wa, wc = win(a), win(c)
    assert len(wa) == len(wc)
    sizes = lambda s: [(p.n_prompt, p.n_out) for p in s]
    assert collections.Counter(sizes(wa)) == collections.Counter(sizes(wc))
    n = len(wa)
    doubled = sizes(wa) + sizes(wa)
    ks = [k for k in range(1, n) if doubled[k:k + n] == sizes(wc)]
    assert ks, "the second seed's window is not a rotation of the first's"
    # and each request keeps the gap that follows it
    k = ks[0]
    ga = [y.due_s - x.due_s for x, y in zip(wa, wa[1:])]
    gc = [y.due_s - x.due_s for x, y in zip(wc, wc[1:])]
    for i in range(n - 1):
        j = (i + k) % n
        if j < n - 1:
            assert gc[i] == pytest.approx(ga[j], abs=1e-9)


@pytest.mark.parametrize("mix_name", MIXES)
def test_lengths_stay_inside_the_mix_and_prompts_have_them(mix_name):
    mix = SPEC.traffic(mix_name)
    for p in traffic.schedule(mix, 5, 10.0)[:200]:
        assert mix["prompt_tokens"]["lo"] <= p.n_prompt \
            <= mix["prompt_tokens"]["hi"]
        assert mix["output_tokens"]["lo"] <= p.n_out \
            <= mix["output_tokens"]["hi"]
        assert len(byte_ids(p.prompt)) == p.n_prompt


@pytest.mark.parametrize("seconds,seed", [(10.0, 1), (30.0, 2), (30.0, 3)])
def test_open_loop_window_holds_exactly_rate_times_seconds(seconds, seed):
    mix = SPEC.traffic("prefill-rate")
    plans = traffic.schedule(mix, seed, seconds)
    in_window = [p for p in plans if 0.0 <= p.due_s < seconds]
    warm = [p for p in plans if p.due_s < 0]
    assert len(in_window) == round(mix["rate_per_s"] * seconds)
    assert len(warm) == round(mix["rate_per_s"] * mix["warmup_s"])
    assert in_window[0].due_s == 0.0
    assert all(-mix["warmup_s"] <= p.due_s for p in warm)
    assert [p.due_s for p in plans] == sorted(p.due_s for p in plans)


def test_burstier_arrivals_have_the_wider_gaps():
    """Gamma gaps are the generator's own: a mix asks for them as data."""
    bursty = {**SPEC.traffic("prefill-rate"), "rate_per_s": 8.0,
              "arrival": {"process": "gamma", "shape": 0.5}}
    chat = traffic.schedule(bursty, 1, 51.0)
    gaps = [b.due_s - a.due_s for a, b in zip(chat, chat[1:])
            if a.due_s >= 0]
    mean = sum(gaps) / len(gaps)
    cv = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5 / mean
    assert 1.1 < cv < 1.8      # gamma of shape 0.5: sqrt(2)


def test_dry_run_clamp_folds_lengths_into_the_tiny_model():
    clamp = SPEC.harness["dry_run"]["clamp"]
    for p in traffic.schedule(SPEC.traffic("prefill-rate"), 3, 5.0, clamp):
        assert clamp["prompt_lo"] <= p.n_prompt < (clamp["prompt_lo"]
                                                   + clamp["prompt_span"])
        assert clamp["out_lo"] <= p.n_out < clamp["out_lo"] + clamp["out_span"]
