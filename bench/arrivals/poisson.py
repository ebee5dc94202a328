"""Poisson arrivals: exponential gaps with mean ``1 / rate_rps``."""

import numpy as np

CLOSED = False


def gaps(mix: dict, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / float(mix["rate_rps"])
