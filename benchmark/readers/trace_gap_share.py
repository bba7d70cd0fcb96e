"""Share of the traced window in which device 0 ran no op, in per cent,
leaving out the idle gaps the trace names by one of the given host spans:
with ``loop.idle`` left out, what remains is about the time the device
waited while the engine had work.

About, because ``benchmark/trace.py`` names a whole gap by ONE host span,
the one that overlaps it most: a gap in which the engine first had nothing
to run and then took 6 ms to admit the request that came counts whole as
waiting, and a long gap with two polls in it counts whole as ``loop.idle``.
So the figure ranks runs and names where to look; it is not an exact share.
The exact one is the device's idle share less the loop thread's own
``loop.idle`` seconds over the same trace (``phase_share`` with ``between:
trace``): the loop idles only with nothing in flight. The gaps tile the idle
time (between programs by name, inside programs as one entry), so this and
the share left out sum to the device's idle share by construction.
Parameters: ``exclude_host`` (host span names)."""

HOST = " | host: "


def host_label(gap_name):
    """The host span a gap is named by; ``None`` for the one entry that has
    none (the idle between the ops of one program)."""
    return gap_name.rsplit(HOST, 1)[1] if HOST in gap_name else None


def read(ctx, params):
    red = ctx["trace"]
    if red is None or not red.window_s:
        return None
    skip = set(params.get("exclude_host", ()))
    secs = sum(s for name, s in red.gaps if host_label(name) not in skip)
    return 100.0 * secs / red.window_s
