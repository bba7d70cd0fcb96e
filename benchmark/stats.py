"""From the client's records to numbers: percentiles and the window.

The rules of the window, in one place:

- ``attempted`` is the requests due (open loop) or sent (closed loop) inside
  the window; ``failed`` is those of them with a failure.
- a request's time to first token and its gaps count if it was attempted,
  wherever its tokens fell;
- a token counts towards the rate only if it reached the client inside the
  window, whichever request it belongs to;
- a failed attempted request stands in the tail of time to first token with
  the client's timeout: a failure misses any limit.
"""

from __future__ import annotations

import math
import re
from typing import Dict, Iterable, List, Optional, Sequence

_CLIENT_METRIC = re.compile(r"^(ttft|gap)_p(\d{1,2})_ms$")


def percentile(values: Sequence[float], p: float) -> float:
    """The ``p``-th percentile by linear interpolation between closest
    ranks (numpy's default); ``values`` need not be sorted."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    k = (len(v) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def attempted(records: Iterable) -> List:
    return [r for r in records if r.in_window]


def ttfts_ms(records: Iterable, timeout_s: float) -> List[float]:
    out = []
    for r in attempted(records):
        if r.failure is not None:
            out.append(timeout_s * 1000.0)
        elif r.token_times:
            out.append((r.token_times[0] - r.due) * 1000.0)
    return out


def gaps_ms(records: Iterable) -> List[float]:
    out = []
    for r in attempted(records):
        if r.failure is None:
            t = r.token_times
            out.extend((b - a) * 1000.0 for a, b in zip(t, t[1:]))
    return out


def tokens_in_window(records: Iterable, t0: float, t1: float) -> int:
    return sum(1 for r in records for t in r.token_times if t0 <= t < t1)


def client_metric(name: str, records: List, t0: float, t1: float,
                  timeout_s: float) -> Optional[float]:
    """An end-to-end metric by its name: ``out_tok_per_s``,
    ``ttft_p<NN>_ms`` or ``gap_p<NN>_ms``. None where there is nothing to
    take it from."""
    if name == "out_tok_per_s":
        return tokens_in_window(records, t0, t1) / (t1 - t0)
    m = _CLIENT_METRIC.match(name)
    if not m:
        raise ValueError(f"no client-side metric is called {name!r}")
    values = (ttfts_ms(records, timeout_s) if m.group(1) == "ttft"
              else gaps_ms(records))
    return percentile(values, float(m.group(2))) if values else None


def accounts_for_its_tokens(rec) -> bool:
    """A complete request delivered what its stop reason accounts for: the
    asked number at ``length``, no more than that at ``stop`` (EOS)."""
    if rec.finish_reason == "length":
        return rec.n_tokens == rec.plan.n_out
    if rec.finish_reason == "stop":
        return rec.n_tokens <= rec.plan.n_out
    return False


def summary(records: List, t0: float, t1: float) -> Dict[str, int]:
    att = attempted(records)
    return {
        "sent": len(records),
        "attempted": len(att),
        "failed": sum(r.failure is not None for r in att),
        "failed_outside_window": sum(
            r.failure is not None for r in records if not r.in_window),
        "eos_stops": sum(r.failure is None and r.finish_reason == "stop"
                         for r in att),
        "tokens_in_window": tokens_in_window(records, t0, t1),
        "ended_after_window": sum(r.done > t1 for r in att),
    }
