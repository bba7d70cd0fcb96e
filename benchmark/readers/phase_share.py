"""Share of the window the engine-loop thread spent in one of its phases,
in per cent: the change of the engine's ``phase_s[phase]`` (seconds by phase,
the open phase's so far included) over the seconds between the two readings.
The phases tile the thread, so the shares of all of them sum to 100. Nothing
where the program keeps no phases. Parameters: ``phase``; ``between``:
``window`` (the default: the readings at the window's ends) or ``trace``
(those taken as the profiler's trace starts and stops, so that the share
stands beside the device's over the same seconds)."""


def read(ctx, params):
    if params.get("between", "window") == "trace":
        a, b = ctx["trace_before"], ctx["trace_after"]
    else:
        a, b = ctx["before"], ctx["after"]
    if a is None or b is None:
        return None
    pa, pb = a["engine"].get("phase_s"), b["engine"].get("phase_s")
    secs = b["t"] - a["t"]
    if pa is None or pb is None or secs <= 0:
        return None
    phase = params["phase"]
    return 100.0 * (pb.get(phase, 0.0) - pa.get(phase, 0.0)) / secs
