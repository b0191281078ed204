"""Poisson-like arrivals with the count and the gaps fixed by the mix.

A phase of length L at rate r holds n = round(r * L) arrivals (at least
one). Their gaps are the n mid-quantiles of the exponential distribution at
rate r, put in an order drawn from the mix's shape seed, then stretched so
that the n arrivals fill the phase; each arrival sits in the middle of its
gap. The gaps are thus exponentially distributed, as a Poisson process's
are, but every run of a mix offers the same count and the same gaps: a
Poisson process proper varies both from run to run, which moves what a
window of some tens of arrivals reads far more than the system does.

    rate_per_s    mean arrival rate, arrivals per second
"""
from __future__ import annotations

import numpy as np

KEYS = ("rate_per_s",)


def times(traffic: dict, start: float, length: float, rng) -> np.ndarray:
    """Due times of one phase ``[start, start + length)``, ascending."""
    rate = float(traffic["rate_per_s"])
    n = max(int(round(rate * length)), 1)
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n) / rate)
    return start + (np.cumsum(gaps) - gaps / 2) * (length / gaps.sum())
