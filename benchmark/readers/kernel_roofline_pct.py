"""A kernel's share of its roofline, from the device trace: the least
time the chip could take for the calls of the traced window (the larger
of their least bytes over the published bytes per second and their least
operations over the published bf16 peak; the family's costs module gives
both a call, from the live rows a tick) over the kernel's device time in
that window. ``layers`` calls a decode tick. None in a rehearsal, without
a trace, or where the trace holds no operation of that name."""

import importlib

from benchmark import costs
from benchmark import family_scopes as fs

_BYTES = {"bfloat16": 2, "float32": 4}


def read(ctx, kernel: str, cost: str):
    if ctx["traced"].get("rehearsal"):
        return None
    got = fs.kernel_seconds(ctx, kernel)
    ticks = [g for g in ctx["run"].get("tick_gauges", ()) if g.get("rows")]
    if got is None or not ticks:
        return None
    seconds, _ = got
    model = ctx["config"]
    family = importlib.import_module(
        f"benchmark.families.{model['family']}.costs")
    width = _BYTES[model["serve"]["weights_dtype"]]
    layers = model["num_hidden_layers"]
    nbytes = flops = 0.0
    for g in ticks:
        b, f = getattr(family, cost)(
            model, g["rows"], model["serve"]["slots"], width)
        nbytes += layers * b
        flops += layers * f
    peaks = costs.peaks(ctx["device"]["kind"])
    least = max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["flops_bf16"])
    return 100.0 * least / seconds
