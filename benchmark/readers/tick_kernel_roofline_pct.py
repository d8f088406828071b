"""A kernel's share of its roofline where its calls follow the tick and
not the layer count: the least time the chip could take for the calls of
the traced window (the larger of their least bytes over the published
bytes per second and their least operations over the published bf16
peak) over the device time of the operations named after the kernel in
that window. The family's costs module gives ``(bytes, operations)`` of
all the kernel's calls in a tick, from what the driver kept of it
(``benchmark/family_ticks.py``): a decode tick's held slots, a chunk
tick's prompt tokens. None in a rehearsal, without a trace, where the
trace holds no operation of that name (the parent's program), or where no
tick called it."""

import importlib

from benchmark import costs
from benchmark import family_scopes as fs
from benchmark import family_ticks

_BYTES = {"bfloat16": 2, "float32": 4}


def read(ctx, kernel: str, cost: str):
    if ctx["traced"].get("rehearsal"):
        return None
    got = fs.kernel_seconds(ctx, kernel)
    if got is None:
        return None
    seconds, _ = got
    model = ctx["config"]
    family = importlib.import_module(
        f"benchmark.families.{model['family']}.costs")
    width = _BYTES[model["serve"]["weights_dtype"]]
    nbytes = flops = 0.0
    for tick in family_ticks.ticks(ctx):
        b, f = getattr(family, cost)(model, tick, width)
        nbytes += b
        flops += f
    if not nbytes and not flops:
        return None
    peaks = costs.peaks(ctx["device"]["kind"])
    least = max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["flops_bf16"])
    return 100.0 * least / seconds
