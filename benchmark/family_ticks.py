"""The traced window's ticks as a family's cost functions take them.

The driver keeps two lists a tick: ``tick_samples`` (tick end, the
scheduler's slot occupancy, the live cache rows) and ``tick_gauges`` (the
engine's gauges of that tick: the rows its chunk step computed and those
that were prompt tokens). Both get an entry in the same tick, under the
same clock reading; this joins them, one dict a tick: ``rows``,
``live_slots`` (occupancy times the configuration's slots, a prefilling
slot counted with the decoding ones: a little high, never low),
``prefill_rows_computed`` and ``prefill_rows_valid`` where a chunk ran.
"""

from __future__ import annotations


def ticks(ctx) -> list:
    run, slots = ctx["run"], ctx["config"]["serve"]["slots"]
    held = {t: occ for t, occ, _rows in run.get("tick_samples", ())}
    out = []
    for g in run.get("tick_gauges", ()):
        occ = held.get(g["t"])
        out.append({**g, "live_slots": slots if occ is None else occ * slots})
    return out
