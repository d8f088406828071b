"""Closed, offline: every request is due at 0 and the backlog never
empties in a window. The driver runs it from one tick's end to another's."""

import numpy as np

OPEN_LOOP = False


def count(mix: dict, seconds: float) -> int:
    return int(mix["requests"])


def due_times(mix: dict, n: int, rng) -> np.ndarray:
    return np.zeros(n)
