"""A kernel's share of its roofline where its calls are a chunk step's
and their least bytes and operations follow from what the chunk's rows
see: the program's ``prefill`` spans carry ``rows_cached`` (the (query,
cached position) pairs of the step, each row counted with what it finds
cached, itself included) and ``chunks`` (the slots that took part). The
family's costs module gives ``(bytes, operations)`` from the window's
pairs and from the positions the steps' slots hold, which the pairs
bound: a step of ``n`` rows that end at position ``p`` sees ``n p - n (n
- 1) / 2`` pairs, so ``p`` is at least ``pairs / n + (n - 1) / 2``. The
least time (the larger of bytes over the published bytes per second and
operations over the published bf16 peak) over the device time of the
operations named after the kernel. None in a rehearsal, without a trace,
where the trace holds no operation of that name or no span carries the
attribute (the parent's program)."""

import importlib

from benchmark import costs
from benchmark import family_scopes as fs

_BYTES = {"bfloat16": 2, "float32": 4}


def read(ctx, kernel: str, cost: str):
    if ctx["traced"].get("rehearsal"):
        return None
    got = fs.kernel_seconds(ctx, kernel)
    steps = [attrs for name, _a, _b, attrs in ctx["run"].get("host_spans", ())
             if name == "prefill" and attrs.get("rows_cached")]
    if got is None or not steps:
        return None
    seconds, _ = got
    model = ctx["config"]
    rows = model["serve"]["prefill_chunk"]
    pairs = float(sum(a["rows_cached"] for a in steps))
    # A step's rows are at most a chunk a slot; the positions its slots
    # hold at its end, at the least.
    keys = sum(a["rows_cached"] / rows + a["chunks"] * (rows - 1) / 2
               for a in steps)
    family = importlib.import_module(
        f"benchmark.families.{model['family']}.costs")
    width = _BYTES[model["serve"]["weights_dtype"]]
    nbytes, flops = getattr(family, cost)(model, pairs, keys, width)
    peaks = costs.peaks(ctx["device"]["kind"])
    least = max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["flops_bf16"])
    return 100.0 * least / seconds
