"""Bursty arrivals: gamma gaps with mean ``1 / rate_rps`` and shape
``gamma_shape`` (coefficient of variation ``1 / sqrt(shape)``; shape 1 is
Poisson, shape 0.25 gives CV 2)."""

import numpy as np
from scipy import stats

CLOSED = False


def gaps(mix: dict, n: int) -> np.ndarray:
    shape = float(mix["gamma_shape"])
    q = (np.arange(n) + 0.5) / n
    return stats.gamma.ppf(q, shape, scale=1.0 / (shape * float(mix["rate_rps"])))
