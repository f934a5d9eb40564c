"""95th percentile, over every request due in the window, of the time from
when it was due to its first token. A request with no first token when the
window closes counts with the time it had waited by then."""
import numpy as np


def read(run):
    waits = [min(r.first_token_t, run.window_s) - r.due_s
             if r.first_token_t is not None else run.window_s - r.due_s
             for r in run.measured()]
    return float(np.percentile(waits, 95)) if waits else None
