"""Change of one engine counter over the change of another, over the
window: how often one thing happened for each time the other did. A counter
is a key of the engine's snapshot, ``a.b`` for the entry ``b`` of a nested
one (``dispatches_by_phase.chunk``; an entry not there yet counts as 0), or
a list of such, summed. Nothing where the program keeps no such counter.
Parameters: ``numerator``, ``denominator``, ``scale``."""


def count(engine, keys):
    """The sum of the counters ``keys`` name; ``None`` where the snapshot
    lacks one of them at the top level."""
    total = 0
    for key in [keys] if isinstance(keys, str) else keys:
        top, _, entry = key.partition(".")
        if top not in engine:
            return None
        total += engine[top].get(entry, 0) if entry else engine[top]
    return total


def read(ctx, params):
    a, b = ctx["before"]["engine"], ctx["after"]["engine"]
    num, den = count(b, params["numerator"]), count(b, params["denominator"])
    if num is None or den is None:
        return None
    d = den - (count(a, params["denominator"]) or 0)
    if d <= 0:
        return None
    return ((num - (count(a, params["numerator"]) or 0)) / d
            * params.get("scale", 1.0))
