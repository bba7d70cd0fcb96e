"""A counter's change over the seconds between the two readings at the
window's ends: how fast the program itself counted, beside what the client
saw arrive. A counter is a key of the engine's snapshot, or ``a.b`` for the
entry ``b`` of a nested one. Nothing where the program keeps no such
counter. Parameters: ``counter``, ``scale``."""


def count(engine, key):
    top, _, entry = key.partition(".")
    value = engine.get(top)
    if entry:
        value = value.get(entry) if isinstance(value, dict) else None
    return value


def read(ctx, params):
    a, b = ctx["before"], ctx["after"]
    ca = count(a["engine"], params["counter"])
    cb = count(b["engine"], params["counter"])
    secs = b["t"] - a["t"]
    if ca is None or cb is None or secs <= 0:
        return None
    return (cb - ca) / secs * params.get("scale", 1.0)
