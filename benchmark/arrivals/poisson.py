"""Open loop, Poisson: independent exponential gaps at the mix's rate,
drawn from the mix's ``schedule_seed``, bursts and lulls included (as
``mpit_tpu/serve/loadgen.py`` draws them)."""

import numpy as np

from benchmark.traffic import open_loop_count as count  # noqa: F401

OPEN_LOOP = True


def due_times(mix: dict, n: int, rng) -> np.ndarray:
    return np.cumsum(rng.exponential(1.0 / mix["rate_per_s"], size=n))
