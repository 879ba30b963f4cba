"""What every traffic mix shares: lengths, token ids, warm groups, seeds.

A mix (``bench/traffic/<name>.json``) states how requests arrive, how long
they are and which erasures the coded head meets.  Its ``kind`` names a
generator module ``bench/traffic/<kind>.py``, found by name as metric
readers are: the module says whether the loop is open (requests due at
times, through the engine's ``TraceScheduler``) or closed (the engine's
own queue kept deeper than the slots), and makes the requests from the
mix and a seed with the arithmetic here.  A mix's ``erasures.model``
names a module the same way; ``none`` (or no ``erasures``) erases nothing.

Every seed gets the same work in another order: lengths and gaps are the
quantiles of their distributions at evenly spaced probabilities, and the
seed only permutes them.  The number of requests due in the window, the
prompt and output tokens they carry and the mean arrival rate are then
the same for every seed; only the order, the token ids and the erasures
differ.

Lengths are lognormal (``median``, ``sigma``) clipped to ``[min, max]``;
prompts are rounded up to a multiple of ``round_to`` (the engine compiles
one prefill per distinct prompt length, so the grid bounds the compiles
and keeps them in set-up).
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

__all__ = [
    "Request",
    "quantiles",
    "length_grid",
    "lengths",
    "prompt",
    "warm_groups",
    "erasure_fn",
    "rng",
]


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream); any whole-number seed."""
    return np.random.default_rng([seed % 2**64, stream])


@dataclass
class Request:
    uid: int
    prompt: np.ndarray      # [L] int32 token ids
    max_new: int
    t_due: float | None     # open loop: trace seconds; closed: None
    section: str            # warm0 | warm | window | tail | batch


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int, round_to: int = 1) -> np.ndarray:
    """n lognormal lengths at evenly spaced quantiles (unpermuted)."""
    z = np.array([NormalDist().inv_cdf(u) for u in quantiles(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    x = np.clip(np.ceil(x), dist["min"], dist["max"]).astype(np.int64)
    return (-(-x // round_to) * round_to).astype(np.int64)


def length_grid(dist: dict, round_to: int) -> list[int]:
    """Every prompt length the mix can produce."""
    lo = -(-dist["min"] // round_to) * round_to
    return list(range(lo, dist["max"] + 1, round_to))


def prompt(g: np.random.Generator, length: int, vocab: int) -> np.ndarray:
    return g.integers(0, vocab, int(length)).astype(np.int32)


def warm_groups(mix: dict, n_slots: int, vocab: int, seed: int,
                max_group: int = 8) -> list[list[Request]]:
    """Groups of 1, 2, ... requests admitted together, with two tokens
    each, covering every prompt length of the mix: each admission count
    and each prefill length the window can meet is compiled in set-up."""
    rt = int(mix["prompt"].get("round_to", 1))
    grid = length_grid(mix["prompt"], rt)
    g_tok = rng(seed, 3)
    groups, uid, i = [], -1, 0
    for k in range(1, min(max_group, n_slots) + 1):
        grp = []
        for _ in range(k):
            grp.append(Request(uid, prompt(g_tok, grid[i % len(grid)], vocab),
                               2, None, "warm0"))
            uid -= 1
            i += 1
        groups.append(grp)
    while i < len(grid):  # more lengths than fit in the groups
        groups.append([Request(uid, prompt(g_tok, grid[i], vocab), 2, None,
                               "warm0")])
        uid -= 1
        i += 1
    return groups


def erasure_fn(bench, mix: dict, n_blocks: int, n_parity: int, seed: int):
    """The mix's per-step mask source for the engine's ``mask_fn``, or
    None: ``erasures.model`` names a module ``bench/traffic/<model>.py``
    whose ``erasures(params, n_blocks, n_parity, g)`` makes it."""
    e = mix.get("erasures") or {"model": "none"}
    if e["model"] == "none":
        return None
    return bench.generator(e["model"]).erasures(e, n_blocks, n_parity, rng(seed, 4))
