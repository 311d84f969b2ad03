"""Host time of one front-end routing call, in ms: the program's
``serve_router_seconds`` summed over the pods over the window, divided by
the routing calls it counted there (the Eq. 28 router's dispatch and its
readback, at submission on top-1 pods). A program without the histogram
reads nothing."""
import readings

LAYER = "front-end router (core/router.py)"
SOURCE = "program_counter"


def read(ctx):
    s = readings.delta_by_pod(ctx, "serve_router_seconds", "sum")
    n = readings.delta_by_pod(ctx, "serve_router_seconds", "count")
    calls = sum(n.values())
    return 1e3 * sum(s.values()) / calls if calls else None
