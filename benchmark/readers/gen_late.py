"""How late the load generator ran: a percentile of sent minus due, in ms,
over the attempted requests of an open loop. Parameters: ``percentile``."""

from benchmark import stats


def read(ctx, params):
    late = [(r.sent - r.due) * 1000.0 for r in stats.attempted(ctx["records"])]
    return stats.percentile(late, params["percentile"]) if late else None
