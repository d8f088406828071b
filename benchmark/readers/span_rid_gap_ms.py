"""Time between a request's consecutive tokens, as the program's spans
show it: for every request id, the gaps between the ends of consecutive
spans of one name that list it under ``rids`` (a tick's end is when its
tokens reach the host). The ``q``-th percentile over all requests' gaps
in the traced window, in milliseconds; None under ``least`` gaps, where
the percentile would rest on a handful of samples. A request that sits
out a tick (preempted, or a prefill tick ran in between) shows the whole
wait as one gap."""


def read(ctx, span: str, q: float, least: int):
    ends: dict = {}
    for name, _, b, attrs in ctx["run"]["host_spans"]:
        if name == span:
            for rid in attrs.get("rids") or ():
                ends.setdefault(rid, []).append(b)
    gaps = sorted(t1 - t0 for ts in ends.values()
                  for t0, t1 in zip(sorted(ts), sorted(ts)[1:]))
    if len(gaps) < least:
        return None
    return 1e3 * gaps[min(len(gaps) - 1, int(q / 100.0 * len(gaps)))]
