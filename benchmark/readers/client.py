"""A client-side number by its end-to-end name (``ttft_p90_ms``,
``gap_p95_ms``, ``out_tok_per_s``): the per-layer copy of a tail that does
not decide in this cell. Parameters: ``metric``."""

from benchmark import stats


def read(ctx, params):
    return stats.client_metric(params["metric"], ctx["records"], ctx["t0"],
                               ctx["t1"], ctx["timeout_s"])
