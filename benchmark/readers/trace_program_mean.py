"""Mean device time of one execution of the programs whose name matches, in
ms, from the device's "XLA Modules" line. Parameters: ``pattern``."""


def read(ctx, params):
    if ctx["trace"] is None:
        return None
    s = ctx["trace"].program_mean_s(params["pattern"])
    return None if s is None else s * 1000.0
