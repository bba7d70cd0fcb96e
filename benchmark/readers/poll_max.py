"""Highest value of a polled engine gauge inside the window. Parameters:
``field`` (``waiting``, ``running`` or ``kv_utilization``), ``scale``."""

FIELDS = {"waiting": 1, "running": 2, "kv_utilization": 3}


def read(ctx, params):
    i = FIELDS[params["field"]]
    vals = [s[i] for s in ctx["samples"]]
    return max(vals) * params.get("scale", 1.0) if vals else None
