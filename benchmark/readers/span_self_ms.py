"""Self time of one of the program's spans: its duration less the part
of that interval that the named child spans cover (children may overlap
each other; their union counts once). Median over the span's instances
in the traced window, in milliseconds. Spans nest by time on one thread,
which is how a child is found.

Also says, on a line of its own, where the span's time went: for every
span name found inside its instances, the mean milliseconds per instance
(means add up where medians do not: a tick with a prefill chunk and one
without are two kinds of tick)."""

import statistics

from benchmark import xplane


def read(ctx, span: str, children: list):
    spans = ctx["run"]["host_spans"]
    parents = [(a, b) for name, a, b, _ in spans if name == span]
    if not parents:
        return None
    kids = xplane._union(sorted(
        (a, b) for name, a, b, _ in spans if name in children))
    selfs = [(b - a) - xplane._overlap(kids, [(a, b)]) for a, b in parents]
    inside: dict = {}
    for name, a, b, _ in spans:
        if name != span and any(p[0] <= a and b <= p[1] for p in parents):
            inside[name] = inside.get(name, 0.0) + (b - a)
    if "say" in ctx:
        ctx["say"]("span_budget", span=span, instances=len(parents),
                   mean_ms=1e3 * sum(b - a for a, b in parents) / len(parents),
                   inside_mean_ms={k: 1e3 * v / len(parents)
                                   for k, v in sorted(inside.items())})
    return 1e3 * statistics.median(selfs)
