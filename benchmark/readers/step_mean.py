"""Mean of a field of the engine's step records (the ``/debug/flight``
``engine_steps`` ring, collected while the window ran) over the steps of the
given kinds that had work. Parameters: ``field``, ``kinds``."""


def read(ctx, params):
    lo, hi = ctx["wall0"], ctx["wall1"]
    vals = [s[params["field"]] for s in ctx["steps"]
            if s["kind"] in params["kinds"] and lo <= s["ts"] < hi
            and s.get("running", 0) > 0]
    return sum(vals) / len(vals) if vals else None
