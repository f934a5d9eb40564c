"""95th percentile of how late the harness submitted each request due in
the window (submit time minus due time)."""
import numpy as np


def read(run):
    lags = [r.submit_t - r.due_s for r in run.measured()
            if r.submit_t is not None]
    return float(np.percentile(lags, 95)) if lags else None
