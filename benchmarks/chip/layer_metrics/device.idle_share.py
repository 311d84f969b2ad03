"""Percent of the traced stretch in which no operation ran on the device
(1 - busy / window, ``trace_reduce``)."""
LAYER = "device (TPU v5e)"
SOURCE = "device_trace"


def read(ctx):
    red = ctx["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
