"""A whole run of each cell at a small size on the CPU, with the timed
path broken underneath: ``correct`` must come out false for each fault
the cells can have, and true with none.  (One chip: there is no exchange
between chips to leave out.)  Also the control: the float8 reference in
the program's place, judged by the same check, comes out not correct."""
import time

import pytest

import jax.numpy as jnp

from harness import cell, cost, spec

# At this size the limit is set as a cell's is, from two readings: sound
# runs of every cell read at most 0.015 here, the control at least 0.058,
# and every fault below at least 0.42.
SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "head_dim": 16, "d_ff": 128,
         "vocab": 512, "engine": {"n_slots": 4, "s_max": 96, "macro_steps": 1,
                                  "head_kernel_mode": None},
         "limits": {"max_logit_gap": 0.04}}
PROMPT = {"median": 24, "sigma": 0.5, "min": 16, "max": 48, "round_to": 16}
OUTPUT = {"median": 24, "sigma": 0.3, "min": 16, "max": 40}
CELLS = {
    "phi3-chat": ({"n_kv_heads": 4},
                  {"rate_per_s": 8.0, "prompt": PROMPT, "output": OUTPUT,
                   "warm_s": 0.3, "drain_s": 5, "check_requests": 12}),
    "glm4-batch-markov": ({"n_kv_heads": 2},
                          {"prompt": PROMPT, "output": OUTPUT, "warm_s": 0.3,
                           "queue_extra": 4, "check_requests": 12}),
    "glm4-batch": ({"n_kv_heads": 2},
                   {"prompt": PROMPT, "output": OUTPUT, "warm_s": 0.3,
                    "queue_extra": 4, "check_requests": 12}),
}


def _stale_state(orig):
    def f(params, cfg, cache, tokens, head_mask=None):
        logits, _ = orig(params, cfg, cache, tokens, head_mask)
        return logits, cache
    return f


def _half_batch(orig):
    def f(params, cfg, cache, tokens, head_mask=None):
        logits, new = orig(params, cfg, cache, tokens, head_mask)
        keep = jnp.arange(logits.shape[0]) >= logits.shape[0] // 2
        return jnp.where(keep[:, None], logits, 0.0), new
    return f


def _altered_token(orig):
    def f(params, cfg, cache, tokens, head_mask=None):
        logits, new = orig(params, cfg, cache, tokens, head_mask)
        return logits.at[0].set(jnp.roll(logits[0], 1)), new
    return f


FAULTS = {"none": None, "stale_state": _stale_state, "half_batch": _half_batch,
          "altered_token": _altered_token}


def _run(workload, monkeypatch, seed=2**35 + 3, control=False):
    monkeypatch.setitem(cost.PEAKS, "cpu", cost.PEAKS["TPU v5 lite"])
    over, mix = CELLS[workload]
    return cell.run(workload, seed, 1.0, False, t_process=time.monotonic(),
                    bench=spec.load(),
                    overrides={"config": {**SMALL, **over}, "traffic": mix},
                    control=control)


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("workload", list(CELLS))
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    from repro.models import transformer

    if FAULTS[fault] is not None:
        monkeypatch.setattr(transformer, "lm_decode_step",
                            FAULTS[fault](transformer.lm_decode_step))
    out = _run(workload, monkeypatch)
    assert out["checks"]["malformed_requests"]["value"] == 0
    assert out["diag"]["checked_tokens"] > 0
    assert out["correct"] is (fault == "none"), out["checks"]


@pytest.mark.parametrize("workload", list(CELLS))
def test_control_reads_above_the_limit(workload, monkeypatch):
    out = _run(workload, monkeypatch, control=True)
    assert out["correct"]
    assert out["diag"]["control_correct"] is False, out["diag"]["control_checks"]
