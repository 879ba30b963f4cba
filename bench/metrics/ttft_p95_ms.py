"""95th percentile of time to first token over the requests due in the
window: from the due time to the engine's stamp of the first token on the
host.  A request with no token by the end of the drain counts at that end."""
from harness.readers import due_in_window, p95


def read(run):
    end = run.window[1] + run.mix.get("drain_s", 0.0)
    reqs = due_in_window(run)
    if not reqs:
        return None
    return 1e3 * p95([(r.times[0] if r.times else end) - r.t_due for r in reqs])
