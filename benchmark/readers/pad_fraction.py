"""Share of the token slots the given phases walked over the window that
were padding, in per cent, from the engine's ``pad_by_phase``. Parameters:
``phases``."""


from benchmark.readers_util import walked


def read(ctx, params):
    real, pad = walked(ctx["before"], ctx["after"], params["phases"])
    return 100.0 * pad / (real + pad) if real + pad else None
