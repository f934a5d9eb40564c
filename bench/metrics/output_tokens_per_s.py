"""Every output token emitted in the window, divided by the window."""


def read(run):
    n = sum(1 for r in run.requests for t in r.token_t
            if 0.0 <= t <= run.window_s)
    return n / run.window_s if n else None
