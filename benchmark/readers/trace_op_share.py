"""Share of the traced window in which ops matching the patterns ran on a
chip (self time, mean over chips), in per cent. Parameters: ``patterns``."""


def read(ctx, params):
    red = ctx["trace"]
    if red is None or not red.window_s:
        return None
    return 100.0 * red.op_total_s(params["patterns"]) / red.window_s
