"""A program's share of its roofline, in per cent: the least time the chip
could take for the work (operations over peak FLOP/s, or bytes over peak
bytes/s) over the device time the matching programs took in the trace.

The work is what the engine's own counters say the programs walked between
the trace's start and stop (``pad_by_phase``), priced by a function of
``benchmark/shapes.py``. Parameters: ``pattern`` (program names), ``shape``
(``prefill_flops`` or ``decode_bytes``), ``phases``."""

from benchmark import shapes
from benchmark.readers_util import walked


def read(ctx, params):
    red, before, after = ctx["trace"], ctx["trace_before"], ctx["trace_after"]
    if red is None or before is None or after is None:
        return None
    device_s = red.program_total_s(params["pattern"])
    if not device_s:
        return None
    cfg, peak = ctx["config"], ctx["peak"]
    chips = int(cfg["chips"])
    real, pad = walked(before, after, params["phases"])
    if params["shape"] == "prefill_flops":
        least_s = (shapes.prefill_flops(cfg, real + pad) / chips
                   / peak["bf16_flops_per_s"])
    elif params["shape"] == "decode_bytes":
        # the counters are read as the traced window opens and closes, so
        # they and the device time cover the same steps to within one
        least_s = shapes.decode_bytes(
            cfg, red.program_count(params["pattern"]), real,
            cfg["bytes"]["weight"], cfg["bytes"]["kv"], chips
        ) / peak["hbm_bytes_per_s"]
    else:
        raise ValueError(f"unknown shape function {params['shape']!r}")
    return 100.0 * least_s / device_s
