"""The paged decode attention kernel's share of its roofline over the
traced stretch: the least time the chip needs for the calls' operations
and bytes (``flops.paged_decode_call``, from the positions the traced
steps fed: one call per layer and pod step, the mixture's K experts in
one call) over the kernel's device time in the trace."""
import flops
import readings

LAYER = "kernels (kernels/decode_attention.py)"
SOURCE = "device_trace"
KERNEL = "paged_decode_attention"
PATTERN = r"^paged_decode_attention(\.\d+)?$"


def read(ctx):
    work = readings.traced_work(ctx)
    if work is None:
        return None
    m = ctx["config"]["model"]
    L, k = m["num_hidden_layers"], readings.expert_factor(ctx)
    calls = []
    for positions in work["decode"].values():
        f, b = flops.paged_decode_call(m, positions)
        calls += [(f * k, b * k)] * L
    return readings.kernel_roofline(ctx, KERNEL, calls)
