"""From a profiler trace (``.xplane.pb``) to busy, idle, operations and gaps.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. A
device is a plane whose name starts with ``/device:TPU:``; its
operations are the events of its ``XLA Ops`` line. Times inside a trace
are nanoseconds from the trace's own origin; the harness writes a
``TraceAnnotation`` named :data:`MARK` at a host time it knows, which
ties the host's clock (``time.perf_counter``) to the trace's.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

MARK = "bench_window_mark"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SHORT_GAP_S = 20e-6
# An operation's event is named by its HLO text: "%name.7 = shape opcode(...)".
HLO = re.compile(r"^%?(?P<name>[^\s=]+) = .*? (?P<op>[a-z][a-z0-9\-]*)\(")
# XLA names collectives after their HLO opcode (sync, or async -start/-done).
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
# A Pallas kernel is an HLO custom call; XLA's own fusions never are.
CUSTOM_CALL = "custom-call"


def parse_op(text: str) -> tuple[str, str]:
    """``(instruction name without its number, opcode)`` of an event name.
    A name that is not HLO text is its own name, with no opcode."""
    m = HLO.match(text)
    name, op = (m.group("name"), m.group("op")) if m else (text, "")
    return re.sub(r"\.\d+$", "", name), op


@dataclasses.dataclass
class Device:
    name: str
    ops: list  # (name, start_s, dur_s, opcode), sorted by start


@dataclasses.dataclass
class Trace:
    devices: list
    mark_s: float | None  # trace time of MARK's start, seconds


def load(path: str) -> Trace:
    """``path`` is an ``.xplane.pb`` or a directory that holds one."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(
            os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    devices, mark = [], None
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, op = parse_op(e.name)
                    ops.append((name, e.start_ns * 1e-9, e.duration_ns * 1e-9, op))
            devices.append(Device(plane.name, sorted(ops, key=lambda o: o[1])))
        elif plane.name.startswith("/host:") and mark is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARK:
                        mark = e.start_ns * 1e-9
                        break
                if mark is not None:
                    break
    devices.sort(key=lambda d: d.name)
    return Trace(devices, mark)


def _union(intervals):
    """Merged ``[start, end]`` intervals of sorted ``(start, end)`` pairs."""
    merged = []
    for s, e in intervals:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(ops, t0, t1):
    out = []
    for name, s, d, cat in ops:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b, cat))
    return out


def reduce(trace: Trace, t0: float, t1: float, host_spans=()) -> dict:
    """Figures of the window ``[t0, t1]`` (trace seconds).

    ``host_spans`` are ``(name, start, end)`` on the trace's clock; each
    idle gap of :data:`SHORT_GAP_S` or longer is named after the span
    that covers its middle.

    Returns seconds, averaged over devices where a figure is per device:
    ``busy_s``, ``window_s``, ``custom_call_s``, ``collective_s``,
    ``collective_exposed_s`` (a collective runs and nothing else does on
    that device), ``busy_in_span`` (busy seconds inside the host spans of
    each name), and the two lists ``device_ops`` and ``idle_gaps``
    (``[name, seconds]``, longest first, at most ten, summed over
    devices and divided by their number).
    """
    n = len(trace.devices)
    if n == 0:
        raise ValueError("the trace holds no TPU device plane")
    busy = custom = coll = exposed = 0.0
    per_op: dict = {}
    gaps: dict = {}
    in_span: dict = {}
    by_name: dict = {}
    for name, a, b in host_spans:
        by_name.setdefault(name, []).append((a, b))
    for dev in trace.devices:
        ops = _clip(dev.ops, t0, t1)
        merged = _union([(a, b) for _, a, b, _ in ops])
        busy += sum(e - s for s, e in merged)
        for name, ivals in by_name.items():
            in_span[name] = in_span.get(name, 0.0) + _overlap(
                merged, _union(sorted(ivals)))
        for name, a, b, op in ops:
            key = f"{name}__{op}" if op else name
            per_op[key] = per_op.get(key, [0.0, 0])
            per_op[key][0] += b - a
            per_op[key][1] += 1
            if op == CUSTOM_CALL:
                custom += b - a
        is_coll = [(a, b) for name, a, b, op in ops if COLLECTIVE.match(op)]
        others = _union([(a, b) for name, a, b, op in ops
                         if not COLLECTIVE.match(op)])
        coll_merged = _union(is_coll)
        coll += sum(e - s for s, e in coll_merged)
        exposed += sum(e - s for s, e in coll_merged) - _overlap(
            coll_merged, others)
        edges = [t0] + [x for s, e in merged for x in (s, e)] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a <= 0:
                continue
            if b - a < SHORT_GAP_S:
                key = "under_20_us__between_operations"
            else:
                key = _span_at((a + b) / 2, host_spans)
            gaps[key] = gaps.get(key, 0.0) + (b - a)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1][0])[:10]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy / n,
        "window_s": t1 - t0,
        "custom_call_s": custom / n,
        "collective_s": coll / n,
        "collective_exposed_s": exposed / n,
        "busy_in_span": {k: v / n for k, v in in_span.items()},
        "device_ops": [[f"{k}__x{c // n}", s / n] for k, (s, c) in top_ops],
        "idle_gaps": [[k, s / n] for k, s in top_gaps],
    }


def _overlap(a, b) -> float:
    """Total overlap of two lists of disjoint sorted intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _span_at(t: float, host_spans) -> str:
    best = None
    for name, s, e in host_spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)  # the innermost span that covers t
    return f"host_in_span_{best[0]}" if best else "host_outside_spans"
