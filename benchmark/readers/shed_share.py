"""Share of attempted requests the admission gate shed, in per cent: the
change of ``/stats`` ``shed.total`` over the window."""


def read(ctx, params):
    if not ctx["attempted"]:
        return None
    d = (ctx["after"]["shed"].get("total", 0)
         - ctx["before"]["shed"].get("total", 0))
    return 100.0 * d / ctx["attempted"]
