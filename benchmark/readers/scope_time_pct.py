"""Share of the device's busy time that ran under the named program
scopes (``benchmark/scopes.py``; ``unscoped`` is the operations under
none), in percent. None where the trace shows no scope to read."""

from benchmark import scopes as sc


def read(ctx, scopes: list):
    got = sc.table(ctx)
    if got is None or not got["busy_s"]:
        return None
    return 100.0 * sc.seconds(ctx, scopes) / got["busy_s"]
