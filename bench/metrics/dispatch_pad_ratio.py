"""Share of the ragged MoE dispatch's GEMM rows that were padding, over the
MoE layer calls of the window (percent)."""


def read(run):
    layers = run.disp_delta("layers")
    if not layers:
        return None
    return 100.0 * run.disp_delta("pad") / layers
