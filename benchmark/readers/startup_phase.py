"""Seconds of one phase of the program's start-up, as ``/stats`` shows them
under ``startup`` once the server is ready. Nothing where the program keeps
no such record. Parameters: ``phase`` (a key of that dict)."""

from benchmark.server import http_json


def read(ctx, params):
    status, stats = http_json(ctx["sut"].base, "GET", "/stats", timeout=30)
    if status != 200 or not isinstance(stats, dict):
        return None
    return (stats.get("startup") or {}).get(params["phase"])
