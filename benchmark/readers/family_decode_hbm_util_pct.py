"""``decode_hbm_util_pct`` by a family's own least bytes: what a decode
tick cannot avoid reading (``benchmark/families/<family>/costs.py``:
``decode_tick_min_bytes``, from the tick's live rows and the experts its
tokens hit, both kept a tick by the driver) over the device time inside
the program's ``decode`` spans, against the published bytes per second.

Rows are read after each tick, when the slots it retired are gone, so the
bytes are counted a little low, never high. None in a rehearsal, without
a trace, or where the program reports no experts hit."""

import importlib

from benchmark import costs

_BYTES = {"bfloat16": 2, "float32": 4}


def read(ctx):
    traced = ctx["traced"]
    device_s = traced.get("busy_in_span", {}).get("decode")
    ticks = [g for g in ctx["run"].get("tick_gauges", ())
             if g.get("rows") and "experts_hit_decode" in g]
    if traced.get("rehearsal") or not device_s or not ticks:
        return None
    model = ctx["config"]
    family = importlib.import_module(
        f"benchmark.families.{model['family']}.costs")
    width = _BYTES[model["serve"]["weights_dtype"]]
    total = sum(family.decode_tick_min_bytes(
        model, g["rows"], g["experts_hit_decode"], width, width)
        for g in ticks)
    peak = costs.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * total / device_s / peak
