"""A share of a roofline whose work the PROGRAM counted, in per cent: the
least time the chip could take for the work over the device time in the
trace. The work function gets the change, over the traced window, of the
engine counters the metric's file names (``counters``: argument name ->
``group.key`` of the engine's snapshot) and prices them; the time is that
of ONE op (``ops``: patterns of op names, self time, as ``trace_op_share``
sums them) or of whole programs (``pattern``: program names, as
``trace_roofline``). Nothing where the program keeps no such counter (a
parent without the mechanism) or the trace holds no such op or program.
Parameters: ``ops`` or ``pattern``, ``counters``, ``module``, ``shape``."""


def read(ctx, params):
    red, before, after = ctx["trace"], ctx["trace_before"], ctx["trace_after"]
    if red is None or before is None or after is None:
        return None
    counters = {}
    for name, key in params["counters"].items():
        group, _, entry = key.partition(".")
        if group not in after["engine"]:
            return None
        counters[name] = (after["engine"][group].get(entry, 0)
                          - before["engine"].get(group, {}).get(entry, 0))
    if "ops" in params:
        device_s = red.op_total_s(params["ops"])
    else:
        device_s = red.program_total_s(params["pattern"])
    if not device_s:
        return None
    programs = (red.program_count(params["pattern"])
                if "pattern" in params else 0)
    priced = ctx["spec"].priced(params)
    least_s = priced["work"](ctx["config"], programs=programs,
                             counters=counters) / ctx["peak"][priced["peak"]]
    return 100.0 * least_s / device_s
