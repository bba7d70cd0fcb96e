"""The one traffic generator. A mix is a data file; this reads any of them.

A schedule is a function of (mix, seed, seconds). Every seed gets the SAME
sequence of (gap, prompt length, output length) — drawn once from the mix's
own ``sizes_seed`` — in another order: the seed ROTATES the sequence (and
writes other prompt text). A rotation keeps which long prompt follows which
short gap, so two seeds offer the same bursts and the same work, and a
spread between runs is the system's, not the draw's. (A free permutation was
tried first: it re-deals the collisions of short gaps with long prompts, and
the 90th percentile of time to first token then swung by a third from seed
to seed at one rate; PERF.md, PR 23.)

Open loop: the window holds exactly ``round(rate * seconds)`` arrivals, the
first at 0 and the gaps scaled to sum to ``seconds``; the warm-up before it
holds ``round(rate * warmup_s)`` more at negative times, a sequence of its
own. Closed loop: each of ``clients`` takes the next entry whenever its
previous request ends, from a cycle of ``CLOSED_LOOP_CYCLE`` entries that
any window walks several times.
"""

from __future__ import annotations

import dataclasses
import math
import random
from typing import Any, Dict, List

#: entries a closed loop can draw; at the shortest request (a few tens of
#: milliseconds) no window of 51 s times 64 clients gets through these
CLOSED_LOOP_ENTRIES = 4096
#: the closed loop's entries repeat with this period, so that every window
#: walks the same sizes whichever entry the seed starts it at
CLOSED_LOOP_CYCLE = 64


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as planned: ``due_s`` is relative to the window's start
    (negative in the warm-up; None in a closed loop, where a request is due
    when its client is free)."""

    index: int
    due_s: Any
    n_prompt: int
    n_out: int
    prompt: str


def _draw(dist: Dict[str, Any], rng: random.Random) -> int:
    lo, hi = dist["lo"], dist["hi"]
    u = rng.random()
    if dist["dist"] == "uniform":
        return int(round(lo + (hi - lo) * u))
    if dist["dist"] == "loguniform":
        return int(round(lo * (hi / lo) ** u))
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def _gap(arrival: Dict[str, Any], rng: random.Random) -> float:
    """One inter-arrival gap of mean 1."""
    if arrival["process"] == "poisson":
        return rng.expovariate(1.0)
    if arrival["process"] == "gamma":
        k = float(arrival["shape"])
        return rng.gammavariate(k, 1.0 / k)
    raise ValueError(f"unknown arrival process {arrival['process']!r}")


def prompt_text(n_tokens: int, tag: str, rng: random.Random) -> str:
    """ASCII text that the byte tokenizer turns into exactly ``n_tokens`` ids
    (BOS and one id per byte), unshared: it opens with ``tag``."""
    words = []
    size = len(tag) + 1
    while size < n_tokens:
        w = "".join(rng.choice("abcdefghijklmnopqrstuvwxyz")
                    for _ in range(rng.randint(2, 9)))
        words.append(w)
        size += len(w) + 1
    return (tag + " " + " ".join(words))[:n_tokens - 1]


def clamp_for_dry_run(n_prompt: int, n_out: int, clamp: Dict[str, int]):
    """The tiny CPU model holds a few hundred positions: fold the lengths
    into what it serves. The dry run proves control flow, never a number."""
    return (clamp["prompt_lo"] + n_prompt % clamp["prompt_span"],
            clamp["out_lo"] + n_out % clamp["out_span"])


def _rotated(items: List[Any], order: random.Random) -> List[Any]:
    """The same cycle, begun elsewhere (never at its own start, so that two
    seeds differ, unless it has one entry)."""
    if len(items) < 2:
        return list(items)
    k = order.randrange(1, len(items))
    return items[k:] + items[:k]


def _phase(mix, fixed: random.Random, order: random.Random, n: int,
           span_s: float, start_s: float):
    """``n`` (due, n_prompt, n_out) in ``[start_s, start_s + span_s)``: the
    mix's own sequence of gaps and sizes, rotated by the seed, the gaps
    scaled to fill the span; the first arrival is at ``start_s``."""
    if n == 0:
        return []
    seq = [(_gap(mix["arrival"], fixed), _draw(mix["prompt_tokens"], fixed),
            _draw(mix["output_tokens"], fixed)) for _ in range(n)]
    seq = _rotated(seq, order)
    scale = span_s / math.fsum(g for g, _, _ in seq)
    t, out = start_s, []
    for g, n_prompt, n_out in seq:
        out.append((t, n_prompt, n_out))
        t += g * scale
    return out


def schedule(mix: Dict[str, Any], seed: int, seconds: float,
             dry_run_clamp: Dict[str, int] = None) -> List[Planned]:
    fixed = random.Random(int(mix["sizes_seed"]))   # the mix's sequence
    order = random.Random(int(seed))                # the seed's rotation
    warmup_s = float(mix["warmup_s"])
    if mix["loop"] == "open":
        rate = float(mix["rate_per_s"])
        n_win = max(1, int(round(rate * seconds)))
        n_wu = int(round(rate * warmup_s))
        # the window first: its sequence does not depend on the warm-up's
        window = _phase(mix, fixed, order, n_win, seconds, 0.0)
        rows = _phase(mix, fixed, order, n_wu, warmup_s, -warmup_s) + window
    elif mix["loop"] == "closed":
        cycle = _rotated(
            [(None, _draw(mix["prompt_tokens"], fixed),
              _draw(mix["output_tokens"], fixed))
             for _ in range(CLOSED_LOOP_CYCLE)], order)
        rows = [cycle[i % len(cycle)] for i in range(CLOSED_LOOP_ENTRIES)]
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    text = random.Random(int(seed) ^ 0x5EED)
    out = []
    for i, (due, n_prompt, n_out) in enumerate(rows):
        if dry_run_clamp:
            n_prompt, n_out = clamp_for_dry_run(n_prompt, n_out,
                                                dry_run_clamp)
        out.append(Planned(i, due, n_prompt, n_out,
                           prompt_text(n_prompt, f"s{seed}r{i}", text)))
    return out
