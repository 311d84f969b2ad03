"""The busiest pod's share of the window's submissions, in percent: each
top-1 pod's ``serve_requests_submitted_total`` over the window. 50% is an
even split of K=2; more means one expert queues while the other idles."""
import readings

LAYER = "front-end router (core/router.py)"
SOURCE = "program_counter"


def read(ctx):
    d = readings.delta_by_pod(ctx, "serve_requests_submitted_total")
    total = sum(d.values())
    if len(d) < 2 or total <= 0:
        return None
    return 100.0 * max(d.values()) / total
