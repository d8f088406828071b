"""Median duration of one of the program's spans in the traced window,
in milliseconds."""

import statistics


def read(ctx, span: str):
    durs = [b - a for name, a, b, _ in ctx["run"]["host_spans"] if name == span]
    return 1e3 * statistics.median(durs) if durs else None
