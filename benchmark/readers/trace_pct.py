"""One figure of the reduced device trace over another, in percent.
``of_idle`` gives 100 - that, for the share of the window with no
operation running."""


def read(ctx, part: str, whole: str, of_idle: bool = False):
    traced = ctx["traced"]
    if traced.get("rehearsal") or not traced[whole]:
        return None
    pct = 100.0 * traced[part] / traced[whole]
    return 100.0 - pct if of_idle else pct
