"""How near the decode ticks come to the memory roofline: the bytes a
decode tick cannot avoid reading (every weight once, every live cache row
once: ``costs.decode_tick_min_bytes``) over the device time inside the
program's ``decode`` spans, against the published bytes per second.

Live rows are read after each tick, when the slots it retired are
already gone, so the bytes are counted a little low, never high."""

from benchmark import costs

_BYTES = {"bfloat16": 2, "float32": 4, "int8": 1}


def read(ctx):
    traced, samples = ctx["traced"], ctx["run"].get("tick_samples", ())
    device_s = traced.get("busy_in_span", {}).get("decode")
    if traced.get("rehearsal") or not device_s or not samples:
        return None
    serve = ctx["config"]["serve"]
    width = _BYTES[serve["weights_dtype"]]
    total = sum(costs.decode_tick_min_bytes(ctx["config"], rows, width, width)
                for _, _, rows in samples if rows)
    peak = costs.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * total / device_s / peak
