"""Device busy time per engine step over the traced stretch: the union of
the device's operation intervals divided by the engine steps traced."""
LAYER = "model step (models/model.py, serve/fused.py)"
SOURCE = "device_trace"


def read(ctx):
    red = ctx["trace"]
    if red is None or not red["steps"]:
        return None
    return 1e3 * red["busy_s"] / len(red["steps"])
