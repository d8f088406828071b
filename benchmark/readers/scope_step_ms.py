"""Device time per step of the traced window under the named program
scopes (``benchmark/scopes.py``), in milliseconds; a mean over chips."""

from benchmark import scopes as sc


def read(ctx, scopes: list):
    under, steps = sc.seconds(ctx, scopes), ctx["run"].get("steps")
    if under is None or not steps:
        return None
    return 1e3 * under / steps
