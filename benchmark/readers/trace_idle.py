"""Share of the traced window in which no op ran on the device, in per cent:
1 - union of the device's op intervals over the window, mean over chips."""


def read(ctx, params):
    red = ctx["trace"]
    if red is None or not red.window_s:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
