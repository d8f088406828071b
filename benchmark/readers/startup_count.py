"""How many events of one name the program's start-up record holds up to
the window's opening, optionally only those whose attributes equal
``where`` (``{"cache_hit": false}``: executables the persistent cache
did not have, so that JAX compiled and wrote them). 0 where the record
has events and none matches; None where the program has no such module
or the record is empty.

With ``say`` = n the reader also prints one ``startup`` note line: the n
longest events of the record as ``[name, fun or module, seconds,
cache_hit]``, so that a run's log carries the names."""

from benchmark.readers.startup_covered_s import setup_events


def read(ctx, name: str, where=None, say: int = 0):
    events = setup_events(ctx)
    if events is None:
        return None
    if say:
        longest = sorted(events, key=lambda e: e["start"] - e["end"])[:say]
        ctx["say"]("startup", events=len(events), longest=[
            [e["name"], e["attrs"].get("fun") or e["attrs"].get("module"),
             round(e["end"] - e["start"], 3), e["attrs"].get("cache_hit")]
            for e in longest])
    where = where or {}
    return float(sum(
        1 for e in events if e["name"] == name
        and all(k in e["attrs"] and e["attrs"][k] == v
                for k, v in where.items())))
