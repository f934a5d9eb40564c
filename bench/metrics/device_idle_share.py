"""Share of the traced interval in which no operation ran on the device
(percent), averaged over the chips used."""


def read(run):
    dev = run.device_trace
    if dev is None or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
