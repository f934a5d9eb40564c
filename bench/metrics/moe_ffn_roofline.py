"""Share of the ragged MoE FFN kernels' roofline (percent): the least time
the traced forwards' expert work needs on this chip (``flops.moe_ffn_work``
over the ``moe_forward`` events inside the traced interval) over the device
time of the two Pallas kernels in the trace."""
from bench import flops


def read(run):
    dev = run.device_trace
    if dev is None:
        return None
    t = dev["kernel_s"].get("moe_ffn", 0.0)
    ev = run.moe_forwards(traced=True)
    if t <= 0.0 or not ev:
        return None
    work = flops.moe_ffn_work(run.dims, ev, run.lo_bits, run.group)
    return 100.0 * flops.least_seconds(work, run.peaks)["seconds"] / t
