"""Open loop at a fixed mean rate, smoother than Poisson: the gaps are the
``n`` mid-quantiles of the exponential distribution of that rate, each
used once, in an order in which every ``balance_block`` consecutive gaps
hold one from each stratum. The marginal distribution of a gap is the
Poisson process's; what is taken away is its clustering: no long run of
short gaps, no long lull. A mix on this process says so in its ``why``."""

import numpy as np

from benchmark.traffic import balanced, open_loop_count as count  # noqa: F401

OPEN_LOOP = True


def due_times(mix: dict, n: int, rng) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / mix["rate_per_s"]
    return np.cumsum(balanced(gaps, mix["balance_block"], rng))
