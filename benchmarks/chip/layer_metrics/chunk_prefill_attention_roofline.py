"""The chunked-prefill attention kernel's share of its roofline over the
traced stretch: the least time the chip needs for the calls' operations
and bytes (``flops.chunk_prefill_call``, from the chunks' positions: one
call per layer and chunk, the mixture's K experts in one call) over the
kernel's device time in the trace."""
import flops
import readings

LAYER = "kernels (kernels/decode_attention.py)"
SOURCE = "device_trace"
KERNEL = "chunk_prefill_attention"
PATTERN = r"^chunk_prefill_attention(\.\d+)?$"


def read(ctx):
    work = readings.traced_work(ctx)
    if work is None:
        return None
    m = ctx["config"]["model"]
    L, k = m["num_hidden_layers"], readings.expert_factor(ctx)
    calls = []
    for _, _, start, n, _ in work["chunks"]:
        f, b = flops.chunk_prefill_call(m, start, n)
        calls += [(f * k, b * k)] * L
    return readings.kernel_roofline(ctx, KERNEL, calls)
