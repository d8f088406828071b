"""``scope_time_pct`` for a family with scope names of its own
(``benchmark/family_scopes.py``): share of the device's busy time under
the named scopes, ``unscoped`` being the operations under none of the
program's or the family's. None where the trace shows no scope."""

from benchmark import family_scopes as fs


def read(ctx, scopes: list):
    got = fs.table(ctx)
    if got is None or not got["busy_s"]:
        return None
    return 100.0 * sum(got["by_scope"].get(s, 0.0) for s in scopes) / got["busy_s"]
