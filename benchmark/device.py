"""The chips a run is on: found or refused, and their peak memory."""

from __future__ import annotations

import sys


def devices_or_exit(chips: int, rehearse: bool):
    """The first ``chips`` TPU devices. Without a TPU, or with fewer chips
    than the cell asks for, the run ends with code 2 and no result."""
    import jax

    devs = jax.devices()
    if not rehearse and devs[0].platform != "tpu":
        print(f"run.py: no TPU (found {devs[0].platform})", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"run.py: the cell needs {chips} chips, found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


def memory_peak(devs, say=None) -> int:
    """Peak bytes on the fullest chip, as the backend reports them: the
    allocator's peak plus the scratch the loaded programs reserve beside
    it (on the v5e a step's temporaries are reserved, not allocated)."""
    stats = [d.memory_stats() for d in devs]  # None on the CPU backend
    if say is not None:
        say("memory_stats", stats=stats)
    return max((int(p.get("peak_bytes_in_use", 0))
                + int(p.get("peak_bytes_reserved", 0)) for p in stats if p),
               default=0)
