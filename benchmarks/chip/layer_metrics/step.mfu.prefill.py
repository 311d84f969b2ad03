"""The whole step's share of the chip's bf16 peak over the traced stretch
of the prefill-heavy cell: model operations of the prefill chunks and
decode tokens the traced steps ran, once per expert that computes them,
over the stretch's length times the peak (``flops.py``, ``peaks.json``)."""
import readings

LAYER = "model step (models/model.py, serve/fused.py)"
SOURCE = "device_trace"


def read(ctx):
    return readings.mfu(ctx)
