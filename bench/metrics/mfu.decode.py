"""Model FLOP/s utilisation of the window: the operations of every token
the window decoded and every prompt it prefilled (the model's own work,
the plain head's vocabulary and not the code's redundancy), over the
window's seconds times the chip's peak."""
from harness import cost


def read(run):
    shp = run.shape
    mm = cost.layer_matmul_params(run.params)
    flops = 0.0
    for s in run.steps:
        if run.in_window(s.t1):
            flops += sum(cost.decode_token_flops(shp, mm, p) for p in s.decode_pos)
            flops += sum(cost.prefill_flops(shp, mm, n) for n in s.prefill)
    return 100.0 * flops / (run.window_s * run.peaks.flops)
