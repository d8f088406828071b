"""Mean over the traced window's ticks of one of the gauges the driver
keeps a tick (``tick_gauges``), by its key there; None where no tick set
it."""


def read(ctx, key: str):
    got = [g[key] for g in ctx["run"].get("tick_gauges", ()) if key in g]
    return sum(got) / len(got) if got else None
