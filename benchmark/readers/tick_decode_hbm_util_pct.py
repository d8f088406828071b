"""``decode_hbm_util_pct`` by a family's own least bytes where they follow
from the tick alone: what a decode tick cannot avoid moving
(``benchmark/families/<family>/costs.py``: ``decode_tick_min_bytes`` of a
tick as ``benchmark/family_ticks.py`` joins it: live rows, held slots)
over the device time inside the program's ``decode`` spans, against the
published bytes per second.

Rows are read after each tick, when the slots it retired are gone, so the
bytes are counted a little low. None in a rehearsal, without a trace, or
where no tick decoded."""

import importlib

from benchmark import costs
from benchmark import family_ticks

_BYTES = {"bfloat16": 2, "float32": 4}


def read(ctx):
    traced = ctx["traced"]
    device_s = traced.get("busy_in_span", {}).get("decode")
    ticks = [t for t in family_ticks.ticks(ctx) if t.get("rows")]
    if traced.get("rehearsal") or not device_s or not ticks:
        return None
    model = ctx["config"]
    family = importlib.import_module(
        f"benchmark.families.{model['family']}.costs")
    width = _BYTES[model["serve"]["weights_dtype"]]
    total = sum(family.decode_tick_min_bytes(model, t, width) for t in ticks)
    peak = costs.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * total / device_s / peak
