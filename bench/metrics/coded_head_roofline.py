"""Share of its roofline that the coded head reaches: the program's
``coded_head_matvec`` called by the bench at the cell's batch with the
cell's erasure masks, traced on its own; least time (the ``n_data`` blocks
any decode needs, ``harness/cost.py``) over device time per call."""
import numpy as np

from harness import cost

PROBES = ("coded_head",)


def read(run):
    p = run.probes.get("coded_head")
    if not p or not p["seconds"]:
        return None
    flops, nbytes = cost.coded_head_cost(p["w"], p["batch"], run.shape.n_data,
                                         run.shape.n_parity)
    least = cost.least_seconds(flops, nbytes, run.peaks)
    return 100.0 * least / float(np.mean(p["seconds"]))
