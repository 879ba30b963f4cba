"""The paper's straggler as erasures of the coded head's blocks: each
block is a two-state Markov chain, slow with stationary probability
``prob`` and then ``slowdown`` times slower, held for ``persistence``
steps on average; every block's time also carries up to ``NOISE`` of
jitter.  Each step the slowest of the slow blocks are erased, at most the
code's parity, so every step stays decodable."""
import numpy as np

NOISE = 0.1


class MarkovErasures:
    def __init__(self, n_blocks: int, n_parity: int, prob: float, slowdown: float,
                 persistence: float, g: np.random.Generator):
        if not 0.0 <= prob < 1.0 or persistence < 1.0:
            raise ValueError("bad Markov erasure parameters")
        self.n, self.n_parity, self.g = n_blocks, n_parity, g
        self.onset = prob / (persistence * (1.0 - prob))
        self.persistence, self.slowdown = persistence, slowdown
        self.slow = np.zeros(n_blocks, bool)

    def __call__(self) -> np.ndarray:
        mult = 1.0 + NOISE * self.g.random(self.n)
        u = self.g.random(self.n)
        recover = self.slow & (u < 1.0 / self.persistence)
        onset = ~self.slow & (u < self.onset)
        self.slow = (self.slow & ~recover) | onset
        mult = np.where(self.slow, mult * self.slowdown, mult)
        order = np.argsort(-mult, kind="stable")[: self.n_parity]
        mask = np.ones(self.n, np.float32)
        mask[order[self.slow[order]]] = 0.0
        return mask


def erasures(params: dict, n_blocks: int, n_parity: int, g: np.random.Generator):
    return MarkovErasures(n_blocks, n_parity, params["prob"], params["slowdown"],
                          params["persistence"], g)
