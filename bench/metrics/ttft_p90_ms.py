"""90th percentile of time to first token over the requests due in the
window: from the due time to the engine's stamp of the first token on the
host.  A request with no token by the end of the drain counts at that end.
The 90th and not the 95th: of the ~76 requests a window holds, the 95th
percentile is the 4th largest, and it falls on one side or the other of
the few requests that arrive during an admission's step from run to run."""
from harness.readers import due_in_window, nearest_rank


def read(run):
    end = run.window[1] + run.mix.get("drain_s", 0.0)
    reqs = due_in_window(run)
    if not reqs:
        return None
    return 1e3 * nearest_rank([(r.times[0] if r.times else end) - r.t_due
                               for r in reqs], 0.90)
