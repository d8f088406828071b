"""Device busy time per step of the traced window, in milliseconds."""


def read(ctx):
    traced, steps = ctx["traced"], ctx["run"].get("steps")
    if traced.get("rehearsal") or not steps:
        return None
    return 1e3 * traced["busy_s"] / steps
