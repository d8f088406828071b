"""Mean over the traced window's ticks of the scheduler's
``slot_occupancy`` gauge (a share of the slots), in percent. The harness
reads the gauge after every tick."""


def read(ctx):
    occ = [s[1] for s in ctx["run"].get("tick_samples", ()) if s[1] is not None]
    return 100.0 * sum(occ) / len(occ) if occ else None
