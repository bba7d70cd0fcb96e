"""Seconds XLA spent compiling (or fetching from the persistent cache and
loading) until the server turned ready, from ``jax.monitoring``."""


def read(ctx, params):
    return ctx["sut"].split.get("xla_compile_s_until_ready")
