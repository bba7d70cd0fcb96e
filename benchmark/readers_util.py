"""What more than one reader needs."""


def walked(before, after, phases):
    """(real, pad) token slots the phases walked between two counter
    readings of the engine's ``pad_by_phase``."""
    real = pad = 0
    for p in phases:
        b = before["engine"]["pad_by_phase"].get(p, {"real": 0, "pad": 0})
        a = after["engine"]["pad_by_phase"].get(p, {"real": 0, "pad": 0})
        real += a["real"] - b["real"]
        pad += a["pad"] - b["pad"]
    return real, pad
