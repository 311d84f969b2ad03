"""95th percentile, over the requests due in the window, of the wait from
when a request was due to when the scheduler admitted it into a slot (the
program's ``t_admit`` stamp); a request not admitted by the window's end
enters at the time it has waited so far."""
import numpy as np

import readings

LAYER = "scheduler (serve/scheduler.py)"
SOURCE = "program_span"


def read(ctx):
    t1 = ctx["rec"]["t1"]
    w = [(q["t_admit"] if 0 < q["t_admit"] <= t1 else t1) - q["due"]
         for q in readings.due_in_window(ctx)]
    return float(np.percentile(w, 95)) if w else None
