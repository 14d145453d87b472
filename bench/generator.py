"""The one traffic generator: a mix file's parameters and a seed in, the
requests of a run out.

A mix (``bench/traffic/<name>.json``) names its ``mode`` and the grid of
the configuration it draws over:

- ``sweep`` and ``frontier``: back-to-back user sweeps of the grid, each
  with ``scales_per_sweep`` budget scales that no earlier sweep of the run
  used, drawn uniformly from ``scale_range`` on a lattice of
  ``scale_step``;
- ``query``: interactive sizing questions, each one fresh budget scale
  from ``scale_range`` and one target rate drawn log-uniformly from
  ``qps_range``.

The first ``warmup`` requests are for set-up; the rest feed the measured
window, in order, until it closes.  Every seed gives the same number of
points per request; only the values differ.
"""

from __future__ import annotations

import math
from typing import Dict, Iterator, Mapping

import numpy as np

MODES = ("sweep", "frontier", "query")


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent stream of draws for ``seed`` (any integer)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


class Requests:
    """The request stream of one run: ``warmup()`` then ``window()``."""

    def __init__(self, mix: Mapping, seed: int):
        if mix["mode"] not in MODES:
            raise ValueError(f"unknown traffic mode {mix['mode']!r}")
        self.mix = mix
        self.mode = mix["mode"]
        lo, hi = mix["scale_range"]
        step = float(mix["scale_step"])
        self._lo, self._step = float(lo), step
        self._n_lattice = int(round((float(hi) - float(lo)) / step)) + 1
        self._used: set = set()
        self._rng = rng_for(seed, 0)

    def _scales(self, n: int) -> list:
        """``n`` lattice scales no earlier request of this run drew."""
        if len(self._used) + n > self._n_lattice:
            raise RuntimeError("the scale lattice is used up")
        out = []
        while len(out) < n:
            i = int(self._rng.integers(self._n_lattice))
            if i not in self._used:
                self._used.add(i)
                out.append(round(self._lo + i * self._step, 9))
        return out

    def next(self) -> Dict:
        if self.mode == "query":
            lo, hi = self.mix["qps_range"]
            qps = math.exp(self._rng.uniform(math.log(lo), math.log(hi)))
            return {"scales": self._scales(1), "qps": float(qps)}
        return {"scales": self._scales(int(self.mix["scales_per_sweep"]))}

    def warmup(self) -> list:
        return [self.next() for _ in range(int(self.mix["warmup"]))]

    def window(self) -> Iterator[Dict]:
        while True:
            yield self.next()
