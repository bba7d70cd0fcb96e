"""Share of the traced window a chip spent in collectives with no compute
running on that chip, in per cent (mean over chips). Where the cell runs
tensor-parallel and no op of the trace reads as a collective, the metric is
left out and the run says so on stderr: the pattern in ``benchmark/trace.py``
missed the device's names (the result's ``breakdown`` shows them), and a
share of 0 would be a number nobody measured."""

import sys


def read(ctx, params):
    red = ctx["trace"]
    if red is None or not red.window_s:
        return None
    if not red.collective_s:
        tp = int(ctx["config"]["engine"].get("tensor_parallel_size", 1))
        if tp > 1:
            print(f"benchmark: tensor parallel over {tp} chips, and no op of "
                  f"the trace reads as a collective", file=sys.stderr)
        return None
    return 100.0 * red.collective_exposed_s / red.window_s
