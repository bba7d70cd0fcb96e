"""A quantile of one of the engine's histograms over the window, as the
bucket bound it falls under: the first bound at which the window's change of
the cumulative counts reaches that share of the change of the count. An upper
bound to a bucket's width (the buckets step by 2 to 2.5). A quantile past
the last bound reads the last bound: a floor, and the histogram's top is 10 s.
Nothing where the program keeps no such histogram, or observed nothing.
Parameters: ``histogram`` (a key of ``StepTelemetry.histograms()``),
``quantile`` (0 to 1), ``scale``."""


def read(ctx, params):
    a = ctx["before"]["histograms"].get(params["histogram"])
    b = ctx["after"]["histograms"].get(params["histogram"])
    if a is None or b is None:
        return None
    n = b["count"] - a["count"]
    if n <= 0:
        return None
    want = float(params["quantile"]) * n
    before = dict((str(le), c) for le, c in a["buckets"])
    bound = None
    for le, c in b["buckets"]:
        if le == "+Inf":
            break
        bound = float(le)
        if c - before.get(str(le), 0) >= want:
            break
    return None if bound is None else bound * params.get("scale", 1.0)
