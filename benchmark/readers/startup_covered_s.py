"""Seconds of set-up that the program's start-up record covers with spans
of the given names (all of its spans where ``names`` is null), up to the
window's opening: the part of ``setup_s`` that lies inside the program's
calls, where the harness's own timers cannot reach.

The record is ``mpit_tpu.obs.startup`` (always on, on ``perf_counter()``,
the clock of ``t_open``). Seconds are the UNION of the spans' intervals,
never the sum of their durations: a jitted function that calls jitted
functions is traced with the inner traces inside the outer one, and a
``compile`` span holds its children. None where the program has no such
module (a parent from before it) or the record is empty."""


def setup_events(ctx):
    """The record's events that end at or before the window's opening,
    as the record's dicts; None without the module or with no event."""
    try:
        from mpit_tpu.obs import startup
    except ImportError:
        return None
    t_open = ctx["run"]["t_open"]
    events = [e for e in startup.snapshot()["events"] if e["end"] <= t_open]
    return events or None


def union_s(intervals) -> float:
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


def read(ctx, names=None):
    events = setup_events(ctx)
    if events is None:
        return None
    return union_s((e["start"], e["end"]) for e in events
                   if e["end"] > e["start"]
                   and (names is None or e["name"] in names))
