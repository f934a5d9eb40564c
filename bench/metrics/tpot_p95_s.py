"""95th percentile, over every request that emitted at least two tokens in
the window, of (last token - first token) / (tokens - 1), counting only
the tokens emitted in the window."""
import numpy as np


def read(run):
    vals = []
    for r in run.requests:
        ts = [t for t in r.token_t if 0.0 <= t <= run.window_s]
        if len(ts) >= 2:
            vals.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return float(np.percentile(vals, 95)) if vals else None
