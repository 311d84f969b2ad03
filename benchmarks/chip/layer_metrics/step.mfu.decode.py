"""The whole step's share of the chip's bf16 peak over the traced stretch
of the decode-heavy cells; the same arithmetic as ``step.mfu.prefill``,
named apart because here it moves ``output_tokens_per_s``."""
import readings

LAYER = "model step (models/model.py, serve/fused.py)"
SOURCE = "device_trace"


def read(ctx):
    return readings.mfu(ctx)
