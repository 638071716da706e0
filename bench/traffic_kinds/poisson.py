"""Open loop at a fixed rate: exponential inter-arrival gaps of mean
``1 / rate_per_s``, stratified like the lengths (every block holds the
same gaps in its own order), summed into due times.  Each of a block's
``block`` equal-probability strata gives its own mean, so a block's mean
gap is exactly ``1 / rate_per_s``, however small the block."""

import numpy as np

from bench.traffic import block_shuffled


def stratum_means(n: int) -> np.ndarray:
    """Means of the unit exponential over its ``n`` equal-probability
    strata ``[-ln(1 - i/n), -ln(1 - (i+1)/n))``."""
    lo = -np.log1p(-np.arange(n) / n)
    hi = lo[1:]
    # the integral of x e^-x over [a, b) is e^-a (a + 1) - e^-b (b + 1);
    # the last stratum's upper end is infinite and adds nothing
    upper = np.append(np.exp(-hi) * (hi + 1), 0.0)
    return (np.exp(-lo) * (lo + 1) - upper) * n


def arrivals(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    grid = stratum_means(mix["block"]) / mix["rate_per_s"]
    return np.cumsum(block_shuffled(grid, n, rng))
