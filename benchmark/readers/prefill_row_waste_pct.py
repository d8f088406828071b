"""Share of the rows the traced window's prefill chunk ticks computed
that were no prompt tokens: 1 - valid / computed, from the engine's
``prefill_rows_computed`` / ``prefill_rows_valid`` gauges, which the
driver keeps a tick. A full-batch chunk tick computes ``slots x chunk``
rows whoever takes part; a compacted one its participants' rows. None
where the program sets no such gauge or no chunk ran."""


def read(ctx):
    ticks = [g for g in ctx["run"].get("tick_gauges", ())
             if g.get("prefill_rows_computed")]
    computed = sum(g["prefill_rows_computed"] for g in ticks)
    if not computed:
        return None
    valid = sum(g.get("prefill_rows_valid", 0.0) for g in ticks)
    return 100.0 * (1.0 - valid / computed)
