"""Share of its roofline that the coded head reaches inside the decode
step: its least time at the engine's batch (the ``n_data`` blocks any
decode needs, ``harness/cost.py``) over the device time per run of
``_decode_argmax`` of the operations under the model's ``coded_head``
scope."""
from harness import cost
from harness.readers import scope_ms


def read(run):
    ms = scope_ms(run, "_decode_argmax", "coded_head")
    if ms is None:
        return None
    n_parity = run.cfg["coded_parity"]
    flops, nbytes = cost.coded_head_cost(
        run.params["lm_head_coded"], run.cfg["engine"]["n_slots"],
        run.n_blocks - n_parity, n_parity)
    return 100.0 * cost.least_seconds(flops, nbytes, run.peaks) / (ms * 1e-3)
