"""Rows per decode forward in the window: the tokens that decode forwards
emitted (every token but a request's first, which prefill emits) over the
decode forwards the engine counted."""


def read(run):
    steps = run.counter_delta("steps")
    if not steps:
        return None
    rows = sum(1 for r in run.requests for i, t in enumerate(r.token_t)
               if i > 0 and 0.0 <= t <= run.window_s)
    return rows / steps
