"""Mean of the engine's own decode forward times in the window
(``perf_counter`` around the forward, up to ``block_until_ready``)."""


def read(run):
    if not run.decode_s:
        return None
    return sum(run.decode_s) / len(run.decode_s) * 1e3
