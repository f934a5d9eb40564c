"""Model FLOP/s utilization of the whole serving step over the window
(percent): the forward FLOPs of every prompt token prefilled and every
token decoded in the window (the cell's architecture module's
``flops_per_token`` at each token's context) over the window times the
chip's bf16 peak."""


def read(run):
    total = 0.0
    for r in run.requests:
        if r.first_token_t is not None and 0.0 <= r.first_token_t \
                <= run.window_s:
            total += run.arch.prompt_flops(run.dims, 0, r.prompt_len)
        for i, t in enumerate(r.token_t):
            if i > 0 and 0.0 <= t <= run.window_s:
                # The decode that emits token i reads the prompt and the
                # i tokens before it.
                total += run.arch.flops_per_token(run.dims, r.prompt_len + i)
    if not total:
        return None
    return 100.0 * total / (run.window_s * run.peaks["bf16_flops"]
                            * run.chips)
