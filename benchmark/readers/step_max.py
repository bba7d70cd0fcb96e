"""Highest value of a field of the engine's step records (the
``/debug/flight`` ``engine_steps`` ring, collected while the window ran) over
the steps inside the window. Nothing where no record carries the field.
Parameters: ``field``."""


def read(ctx, params):
    lo, hi, field = ctx["wall0"], ctx["wall1"], params["field"]
    vals = [s[field] for s in ctx["steps"]
            if field in s and lo <= s["ts"] < hi]
    return max(vals) if vals else None
