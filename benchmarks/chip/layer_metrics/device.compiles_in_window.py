"""Backend compiles (``/jax/core/compile/backend_compile_duration``
events) inside the measured window. Warm-up reaches every program the
window runs, so this reads 0; anything else is compile time in the
window."""
LAYER = "device (TPU v5e)"
SOURCE = "program_counter"


def read(ctx):
    return float(ctx["rec"]["compiles_in_window"])
