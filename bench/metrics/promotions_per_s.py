"""Hi-tier promotions the residency backend published in the window, per
second."""


def read(run):
    n = run.stat_delta("promotions")
    return None if n is None else n / run.window_s
