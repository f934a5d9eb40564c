"""Process start to the first request due in the window: weights, bank
build, engine, compilation, warm-up and the lead-in traffic."""


def read(run):
    return run.setup_s
