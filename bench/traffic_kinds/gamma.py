"""Open loop at a fixed mean rate with bursts: Gamma inter-arrival gaps of
shape ``gamma_shape`` (coefficient of variation ``1 / sqrt(shape)``) and
mean ``1 / rate_per_s``, stratified like ``poisson.py``: every block of
``block`` requests holds the same gaps (the means of the distribution's
``block`` equal-probability strata) in its own order, so a block's mean
gap is exactly ``1 / rate_per_s``."""

import numpy as np
from scipy import special

from bench.traffic import block_shuffled


def stratum_means(shape: float, n: int) -> np.ndarray:
    """Means of the unit-mean Gamma of ``shape`` over its ``n``
    equal-probability strata ``[q_i, q_(i+1))``: with x f_k(x) = k f_(k+1)(x)
    on the unit-scale Gamma, a stratum's mean is ``n (P(k+1, q_(i+1)) -
    P(k+1, q_i))``, P the regularised lower incomplete gamma; scaled by
    1/k to a unit mean.  The n means telescope to a total of exactly n."""
    q = special.gammaincinv(shape, np.arange(1, n) / n)
    cdf = np.concatenate([[0.0], special.gammainc(shape + 1, q), [1.0]])
    return np.diff(cdf) * n


def arrivals(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    grid = stratum_means(mix["gamma_shape"], mix["block"]) / mix["rate_per_s"]
    return np.cumsum(block_shuffled(grid, n, rng))
