"""Median of the engine's own prefill forward times in the window
(``perf_counter`` around the forward, up to ``block_until_ready``)."""
import numpy as np


def read(run):
    return float(np.median(run.prefill_s)) * 1e3 if run.prefill_s else None
