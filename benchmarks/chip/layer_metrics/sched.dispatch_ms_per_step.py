"""Host time to build and launch the step dispatch, per engine step: the
program's ``serve_step_dispatch_seconds`` summed over the pods over the
window, divided by the engine steps the window ran."""
import readings

LAYER = "scheduler (serve/scheduler.py)"
SOURCE = "program_counter"


def read(ctx):
    n = readings.window_steps(ctx)
    d = readings.delta_by_pod(ctx, "serve_step_dispatch_seconds", "sum")
    return 1e3 * sum(d.values()) / n if n and d else None
