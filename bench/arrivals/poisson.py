"""Open loop: Poisson arrivals at a fixed rate.

The window holds ``round(rate * seconds)`` requests.  Their gaps are the
quantiles of the exponential distribution of that rate, shuffled by the
seed: every seed offers the same set of gaps, so the same load, in another
order.  A request is due at its scheduled time whether or not earlier ones
were answered.
"""

from __future__ import annotations

import numpy as np


class Source:
    def __init__(self, rate: float, seconds: float, rng: np.random.Generator):
        n = max(1, round(rate * seconds))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) / rate
        gaps *= seconds / gaps.sum()           # the last request falls at the close
        rng.shuffle(gaps)
        self.offsets = np.cumsum(gaps) - gaps[0]
        self.n = n
        self._next = 0
        self._t0 = 0.0

    def start(self, t0: float) -> None:
        self._t0 = t0

    def poll(self, now: float) -> list[float]:
        """Due times of the requests to send now."""
        due = []
        while self._next < self.n and self._t0 + self.offsets[self._next] <= now:
            due.append(self._t0 + float(self.offsets[self._next]))
            self._next += 1
        return due

    def next_due(self) -> float | None:
        if self._next < self.n:
            return self._t0 + float(self.offsets[self._next])
        return None

    def finished(self, now: float) -> bool:
        return self._next >= self.n


def make(params: dict, seconds: float, rng: np.random.Generator) -> Source:
    return Source(float(params["rate"]), seconds, rng)
