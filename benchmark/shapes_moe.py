"""Bytes of the streamed expert product (``moe_grouped_ffn_streamed``, the
few-row form of ``ops/moe.py``), from shapes and from what the program's
counters say routing did.

Nothing here reads the program: the sizes follow from the published
configuration's keys and the deployment's weight type. For every expert that
got at least one assignment in a decode dispatch (the counter
``moe.experts_touched``, summed over expert layers and steps; decode
dispatches are exactly the calls that take the streamed form) the kernel
MUST read the expert's three matrices once: ``3 x hidden_size x
moe_intermediate_size`` weights. The rows, the combine matrix and the
float32 sum it writes (a few hundred kilobytes a layer) are not counted, so
the share reads low, never high.
"""

from __future__ import annotations

from typing import Any, Dict


def expert_bytes(m: Dict[str, Any], weight_bytes: float) -> float:
    """One routed expert's gate, up and down matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"] * weight_bytes


def _streamed_bytes(cfg, *, programs, counters):
    return counters["experts_touched"] * expert_bytes(
        cfg, cfg["bytes"]["weight"])


# Found by ``readers/trace_roofline_counted.py`` through a metric file's
# ``shape``: ``work(cfg, programs=..., counters={name: change over the
# traced window})``, the names the metric file's ``counters`` gives.
FUNCTIONS = {
    "streamed_expert_bytes": {"work": _streamed_bytes,
                              "peak": "hbm_bytes_per_s"},
}
