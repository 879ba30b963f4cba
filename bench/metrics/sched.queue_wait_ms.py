"""Mean wait in the scheduler's queue (admission stamp minus due time) of
the requests due in the window, from ``TraceScheduler``'s own stamps."""
import numpy as np

from harness.readers import due_in_window


def read(run):
    w = [r.t_admit - r.t_due for r in due_in_window(run) if r.t_admit is not None]
    return 1e3 * float(np.mean(w)) if w else None
