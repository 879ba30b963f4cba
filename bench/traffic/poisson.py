"""Open-loop Poisson arrivals at ``rate_per_s``: requests are due whether
or not the server keeps up, as from independent users.

The run is cut into sections, each with its own arrivals: ``warm``
(``warm_s`` seconds before the window), ``window`` (the measured seconds)
and ``tail`` (arrivals that keep the load on while the window's last
requests drain, ``drain_s`` seconds).  Within a section the gaps are the
exponential distribution's quantiles, scaled to the section's length and
permuted by the seed, and so are the lengths.
"""
import numpy as np

from harness.traffic import Request, lengths, prompt, quantiles, rng

DRIVE = "open"


def plan(mix: dict, seed: int, seconds: float, vocab: int,
         t_start: float) -> tuple[list[Request], tuple[float, float]]:
    """Requests due from ``t_start`` on, and the window (t0, t1)."""
    rate = float(mix["rate_per_s"])
    rt = int(mix["prompt"].get("round_to", 1))
    g_order, g_tok = rng(seed, 1), rng(seed, 2)
    out: list[Request] = []
    t = t_start
    window = (0.0, 0.0)
    for section, dur in (("warm", mix["warm_s"]), ("window", seconds),
                         ("tail", mix["drain_s"])):
        n = max(1, round(rate * dur))
        gaps = -np.log1p(-quantiles(n)) / rate
        gaps *= dur / gaps.sum()
        gaps = g_order.permutation(gaps)
        due = t + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        p_len = g_order.permutation(lengths(mix["prompt"], n, rt))
        o_len = g_order.permutation(lengths(mix["output"], n))
        for i in range(n):
            out.append(Request(len(out), prompt(g_tok, p_len[i], vocab),
                               int(o_len[i]), float(due[i]), section))
        if section == "window":
            window = (t, t + dur)
        t += dur
    return out, window
