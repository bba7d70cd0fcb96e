"""Mean of one of the engine's histograms over the window: change of sum
over change of count. Parameters: ``histogram`` (a key of
``StepTelemetry.histograms()``), ``scale``."""


def read(ctx, params):
    a = ctx["before"]["histograms"][params["histogram"]]
    b = ctx["after"]["histograms"][params["histogram"]]
    n = b["count"] - a["count"]
    if n <= 0:
        return None
    return (b["sum"] - a["sum"]) / n * params.get("scale", 1.0)
