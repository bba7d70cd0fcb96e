"""Change of one engine counter over the window. Parameters: ``counter`` (a
key of the engine's snapshot)."""


def read(ctx, params):
    k = params["counter"]
    if k not in ctx["after"]["engine"]:
        return None
    return ctx["after"]["engine"][k] - ctx["before"]["engine"].get(k, 0)
