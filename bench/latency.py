"""Latency percentiles over every RPC completed in the measured time.

The program counts latency on the device in fabric steps: a histogram
of residencies ``L`` (the steps from the step that injected the request
to the one that completed it, both counted; the last bin = overflow).
The benchmark reads the cumulative histogram after every window, takes
the window's own part (the difference of successive readings), and
turns each part into time with that window's own wall microseconds per
step.  All windows' parts are merged into one distribution, and the
percentiles are read from it.  A window that stalled therefore weighs
its own RPCs with its own slow steps, instead of vanishing into an
average step time.

A request's latency is ``(L + U)`` steps of its window, with ``U``
uniform in [0, 1): the generator draws a Poisson count of arrivals for
each step, and given that count their arrival times are independent and
uniform over the step, so each waited ``U`` of a step before the step
that injected it.  The distribution is therefore the histogram spread
uniformly over ``[L, L + 1)`` steps, and a percentile is read off its
piecewise-linear distribution function (on the histogram alone a
percentile could only take whole steps).
"""
from __future__ import annotations

import numpy as np


class Overflow(RuntimeError):
    """A percentile fell in the histogram's overflow bin, so it has no
    value; the run reports failure instead of a number."""


class Merged:
    """Accumulates each window's histogram part as mass spread over
    ``[L, L + 1)`` steps of that window, in microseconds."""

    def __init__(self, n_bins: int):
        self.n_bins = n_bins
        self._lo, self._hi, self._mass = [], [], []
        self.overflow = 0

    def add(self, hist_delta, us_per_step: float):
        """One window: its histogram part [n_bins] (completions by
        residency in steps) and its microseconds per step."""
        h = np.asarray(hist_delta, np.int64)
        self.overflow += int(h[-1])
        nz = np.nonzero(h[:-1])[0]
        if nz.size:
            self._lo.append(nz * us_per_step)
            self._hi.append((nz + 1) * us_per_step)
            self._mass.append(h[nz])

    @property
    def n(self) -> int:
        return int(sum(m.sum() for m in self._mass)) + self.overflow

    def quantile(self, q: float) -> float:
        n = self.n
        if n == 0:
            raise ValueError("no completions to take a percentile of")
        target = q * n
        finite = n - self.overflow
        if target > finite:
            raise Overflow(f"p{q * 100:g} lies in the overflow bin "
                           f"(>= {self.n_bins - 1} steps)")
        lo, hi = np.concatenate(self._lo), np.concatenate(self._hi)
        mass = np.concatenate(self._mass).astype(np.float64)
        x = np.unique(np.concatenate([lo, hi]))
        cdf = (mass[None, :] * np.clip((x[:, None] - lo[None, :])
                                       / (hi - lo)[None, :], 0.0, 1.0)
               ).sum(axis=1)
        i = int(np.searchsorted(cdf, target, side="left"))
        if i == 0:
            return float(x[0])
        i = min(i, len(x) - 1)
        f0, f1 = cdf[i - 1], cdf[i]
        return float(x[i - 1] + (x[i] - x[i - 1]) * (target - f0)
                     / (f1 - f0))
