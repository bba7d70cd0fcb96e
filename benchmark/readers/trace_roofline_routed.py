"""A routed model's decode share of its HBM roofline, in per cent: like
``trace_roofline``, but what a step must read depends on what the program
did, so the work function also gets two of the program's counters, read as
the traced window opens and closes: ``moe.experts_touched`` (distinct
experts that got an assignment, summed over expert layers and steps) and
``window.tokens_visible`` (keys the window layers' queries saw, summed over
those layers, rows and steps). Nothing where the program keeps no such
counter (a parent without expert layers) or the trace holds no such program.
Parameters: ``pattern``, ``module``, ``shape``, ``phases``."""

from benchmark.readers_util import walked


def _delta(before, after, group, key):
    if group not in after["engine"]:
        return None
    return (after["engine"][group].get(key, 0)
            - before["engine"].get(group, {}).get(key, 0))


def read(ctx, params):
    red, before, after = ctx["trace"], ctx["trace_before"], ctx["trace_after"]
    if red is None or before is None or after is None:
        return None
    touched = _delta(before, after, "moe", "experts_touched")
    visible = _delta(before, after, "window", "tokens_visible")
    device_s = red.program_total_s(params["pattern"])
    if touched is None or visible is None or not device_s:
        return None
    priced = ctx["spec"].priced(params)
    cfg = ctx["config"]
    real, pad = walked(before, after, params["phases"])
    least_s = priced["work"](
        cfg, programs=red.program_count(params["pattern"]), real=real,
        pad=pad, chips=int(cfg["chips"]), experts_touched=touched,
        window_visible=visible) / ctx["peak"][priced["peak"]]
    return 100.0 * least_s / device_s
