"""Share of the routed (layer, expert) cells served from the hi tier, over
the flight recorder's ``moe_forward`` events in the window (percent)."""


def read(run):
    ev = run.moe_forwards()
    if not ev:
        return None
    hi = sum(e["active_hi"] for e in ev)
    lo = sum(e["active_lo"] for e in ev)
    return 100.0 * hi / (hi + lo) if hi + lo else None
