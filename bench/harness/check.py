"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the longest
one, goes through ``bench/reference.py``: a teacher-forced float32 forward
over each prompt and its served tokens.  The number compared is the widest
gap by which a served token's logit lies below the reference's best at its
position (greedy decoding serves the argmax, so a sound program's gaps are
rounding ties).  Beside it, every finished request must carry exactly its
token budget of in-vocabulary tokens.

Each number has a limit of its own: ``limits`` in the configuration file,
set from the program's readings over a dozen seeds and the control's
(``bench/readings.py``; the readings are in ``PERF.md``).
"""
from __future__ import annotations

import numpy as np

from . import traffic

__all__ = ["sample", "padded_length", "judge", "run_check"]


def sample(finished: list, seed: int, n: int) -> list:
    """The longest finished request and ``n - 1`` others drawn from the
    seed, in uid order."""
    if not finished:
        return []
    by_uid = sorted(finished, key=lambda r: r.uid)
    longest = max(by_uid, key=lambda r: (r.n_prompt + len(r.tokens), -r.uid))
    rest = [r for r in by_uid if r is not longest]
    g = traffic.rng(seed, 5)
    pick = g.choice(len(rest), size=min(n - 1, len(rest)), replace=False) if rest else []
    out = [longest] + [rest[i] for i in pick]
    return sorted(out, key=lambda r: r.uid)


def padded_length(mix: dict) -> int:
    """One length for every check of the mix: its longest prompt and
    longest output (the last served token is never fed back)."""
    rt = int(mix["prompt"].get("round_to", 1))
    return traffic.length_grid(mix["prompt"], rt)[-1] + mix["output"]["max"] - 1


def judge(checks: dict) -> bool:
    """Correct: every number compared at or under its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def run_check(cfg: dict, mix: dict, seed: int, finished: list,
              control: bool = False) -> tuple[dict, bool, dict]:
    """(numbers compared with their limits, correct, diagnostics).  With
    ``control`` the control, the reference one precision step below in the
    program's place, is judged on the same sample by the same limits: the
    diagnostics hold its numbers (``control_checks``) and its verdict
    (``control_correct``), which has to come out false."""
    from reference import logit_gaps

    vocab = cfg["vocab"]
    malformed = sum(1 for r in finished
                    if len(r.tokens) != r.max_new
                    or any(not 0 <= t < vocab for t in r.tokens))
    picked = [r for r in sample(finished, seed, int(mix["check_requests"]))
              if len(r.tokens) == r.max_new]
    diag: dict = {"finished": len(finished), "checked_requests": len(picked),
                  "checked_tokens": sum(len(r.tokens) for r in picked)}
    gap = float("inf")
    if picked:
        gaps, gaps_c = logit_gaps(cfg, seed, [r.prompt for r in picked],
                                  [np.asarray(r.tokens) for r in picked],
                                  padded_length(mix), control=control)
        flat = np.concatenate(gaps)
        gap = float(flat.max())
        diag["off_argmax_share"] = float((flat > 0).mean())
    limits = cfg["limits"]
    checks = {
        "max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"]},
        "malformed_requests": {"value": malformed, "limit": 0},
    }
    if control:
        # the control serves the tokens it puts first, so none is malformed
        gap_c = float(np.concatenate(gaps_c).max()) if picked else float("inf")
        diag["control_checks"] = {
            "max_logit_gap": {"value": gap_c, "limit": limits["max_logit_gap"]},
            "malformed_requests": {"value": 0, "limit": 0},
        }
        diag["control_correct"] = judge(diag["control_checks"])
    return checks, judge(checks), diag
