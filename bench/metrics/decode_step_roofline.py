"""Share of its roofline that the decode step reaches: the least time of
each decode step in the traced window (from the operations and bytes its
arrays and its slots' positions need, ``harness/cost.py``) over the device
time per run of ``_decode_argmax``."""
import numpy as np

from harness import cost
from harness.readers import module_mean_ms


def read(run):
    dev_ms = module_mean_ms(run, "_decode_argmax")
    steps = [s for s in run.steps_in(run.traced) if s.decode_pos] if run.traced else []
    if dev_ms is None or not steps:
        return None
    least = [cost.least_seconds(*cost.decode_step_cost(
        run.params, run.cache, run.shape, s.decode_pos), run.peaks) for s in steps]
    return 100.0 * float(np.mean(least)) / (dev_ms * 1e-3)
