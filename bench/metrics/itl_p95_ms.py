"""95th percentile of all gaps between consecutive tokens of the requests
due in the window."""
import numpy as np

from harness.readers import due_in_window, p95


def read(run):
    gaps = [g for r in due_in_window(run) for g in np.diff(r.times)]
    v = p95(gaps)
    return None if v is None else 1e3 * v
