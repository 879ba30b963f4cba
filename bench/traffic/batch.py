"""An offline batch: a closed loop whose queue is kept ``queue_extra``
requests deeper than the slots, so every slot is refilled the step it
frees.  The first wave is cut to a uniform share of its length, so the
batch is already staggered when the window opens (a batch in progress,
not one that started together).  Lengths come in blocks of
``2 * n_slots`` requests, each block the same quantiles permuted by the
seed.
"""
import math

from harness.traffic import Request, lengths, prompt, quantiles, rng

DRIVE = "closed"


def stream(mix: dict, seed: int, vocab: int, n_slots: int):
    """Endless batch requests; the first ``n_slots`` form the first wave."""
    rt = int(mix["prompt"].get("round_to", 1))
    block = 2 * n_slots
    g_order, g_tok = rng(seed, 1), rng(seed, 2)
    p_set = lengths(mix["prompt"], block, rt)
    o_set = lengths(mix["output"], block)
    cut = g_order.permutation(quantiles(n_slots))
    uid = 0
    while True:
        p_len, o_len = g_order.permutation(p_set), g_order.permutation(o_set)
        for i in range(block):
            n_out = int(o_len[i])
            if uid < n_slots:
                n_out = max(1, math.ceil(cut[uid] * n_out))
            yield Request(uid, prompt(g_tok, p_len[i], vocab), n_out, None, "batch")
            uid += 1
