"""Device time by the program's scope names, from a profiler trace.

The program names what its jitted steps do with ``jax.named_scope``
(``models/gpt2.py``, ``serve/engine.py``, ``train/step.py``,
``opt/sharded.py``), and JAX writes the name stack into every operation's
``op_name``. On the v5e the profiler keeps it as the ``tf_op`` stat of the
event's *metadata* (``jit(decode_paged)/GPT2/block_3/attn/kv_write/scatter:``;
a fusion carries its root's), beside ``program_id``, which the names of
the ``XLA Modules`` line resolve to a module (``jit_decode_paged``).
``jax.profiler.ProfileData`` hands out an event's own stats only, so this
module reads the ``.xplane.pb`` itself: the few fields it needs of
``tsl/profiler/protobuf/xplane.proto``, by the wire format, nothing
imported. An operation belongs to the innermost program scope of its
name stack; one that the compiler made itself (a copy between memory
spaces has no ``op_name``) or that lies outside every scope is
``unscoped``.

One trace is loaded once per run (:func:`table` keeps the result in the
run's ``ctx``) and tied to the host's clock as ``tracing.reduce_run``
does, by the window mark.
"""

from __future__ import annotations

import glob
import os
import re
import struct

from benchmark import xplane

# The scope names the program gives (PERF.md section 3). A reader names
# the ones it sums; ``unscoped`` is the rest.
PROGRAM_SCOPES = (
    "embed", "attn", "kv_write", "kv_gather", "mlp", "lm_head", "sample",
    "loss", "grad_sync", "opt_update", "zero1_gather")
UNSCOPED = "unscoped"
MODULES_LINE = "XLA Modules"
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_MODULE = re.compile(r"^(?P<name>.*)\((?P<id>\d+)\)$")


def scope_of(op_name: str, scopes=PROGRAM_SCOPES):
    """The innermost of ``scopes`` in a name stack, or None. Autodiff
    wraps a scope (``transpose(jvp(loss))``), so components are searched
    word by word."""
    for part in reversed(op_name.split("/")):
        for word in reversed(_WORD.findall(part)):
            if word in scopes:
                return word
    return None


# -- the wire format ---------------------------------------------------------

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field, 8 or 4 raw bytes for the
    fixed kinds."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf) -> tuple:
    """``(stat metadata id, value)`` of an XStat; a reference to another
    stat's name stays ``("ref", id)`` until the names are known."""
    sid, value = 0, None
    for f, v in _fields(buf):
        if f == 1:
            sid = v
        elif f == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif f in (3, 4):
            value = v
        elif f in (5, 6):
            value = _text(v)
        elif f == 7:
            value = ("ref", v)
    return sid, value


def _plane(buf) -> dict:
    """One XPlane: its lines as ``(name, timestamp_ns, [(metadata id,
    offset_ps, duration_ps)])`` and, by event metadata id, the event's
    name and its metadata's stats by name."""
    lines, metas, stat_names = [], {}, {}
    for f, v in _fields(buf):
        if f == 3:
            lines.append(v)
        elif f == 4:  # map<int64, XEventMetadata>
            for ef, ev in _fields(v):
                if ef == 2:
                    mid, mname, stats = 0, "", []
                    for mf, mv in _fields(ev):
                        if mf == 1:
                            mid = mv
                        elif mf == 2:
                            mname = _text(mv)
                        elif mf == 5:
                            stats.append(_stat(mv))
                    metas[mid] = (mname, stats)
        elif f == 5:  # map<int64, XStatMetadata>
            for ef, ev in _fields(v):
                if ef == 2:
                    sid, sname = 0, ""
                    for mf, mv in _fields(ev):
                        if mf == 1:
                            sid = mv
                        elif mf == 2:
                            sname = _text(mv)
                    stat_names[sid] = sname
    resolve = lambda val: (stat_names.get(val[1], "")
                           if isinstance(val, tuple) else val)
    metas = {mid: (mname, {stat_names.get(s, ""): resolve(val)
                           for s, val in stats})
             for mid, (mname, stats) in metas.items()}
    out = []
    for lv in lines:
        lname, t_ns, events = "", 0, []
        for f, v in _fields(lv):
            if f == 2:
                lname = _text(v)
            elif f == 3:
                t_ns = v
            elif f == 4:
                mid = off = dur = 0
                for ef, ev in _fields(v):
                    if ef == 1:
                        mid = ev
                    elif ef == 2:
                        off = ev
                    elif ef == 3:
                        dur = ev
                events.append((mid, off, dur))
        out.append((lname, t_ns, events))
    return {"lines": out, "metas": metas}


def load(path: str) -> dict:
    """``{"devices": [[(start_s, end_s, op_name, module, instruction)]],
    "mark_s": ...}`` of an ``.xplane.pb`` or of a directory that holds
    one. Only the device planes and the host's are decoded."""
    if os.path.isdir(path):
        found = sorted(glob.glob(
            os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices, mark = [], None
    for f, v in _fields(space):
        if f != 1:
            continue
        name = next((_text(x) for g, x in _fields(v) if g == 2), "")
        if name.startswith(xplane.DEVICE_PLANE):
            plane = _plane(v)
            modules = {}
            for lname, _, events in plane["lines"]:
                if lname == MODULES_LINE:
                    for mid, _, _ in events:
                        m = _MODULE.match(plane["metas"][mid][0])
                        if m:
                            modules[int(m.group("id"))] = m.group("name")
            ops = []
            for lname, t_ns, events in plane["lines"]:
                if lname != xplane.OPS_LINE:
                    continue
                for mid, off, dur in events:
                    text, stats = plane["metas"][mid]
                    start = t_ns * 1e-9 + off * 1e-12
                    ops.append((
                        start, start + dur * 1e-12, stats.get("tf_op", ""),
                        modules.get(stats.get("program_id"), ""),
                        xplane.parse_op(text)[0]))
            devices.append((name, sorted(ops)))
        elif name.startswith("/host:") and mark is None:
            plane = _plane(v)
            for _, t_ns, events in plane["lines"]:
                for mid, off, _ in events:
                    if plane["metas"][mid][0] == xplane.MARK:
                        mark = t_ns * 1e-9 + off * 1e-12
                        break
                if mark is not None:
                    break
    return {"devices": [ops for _, ops in sorted(devices)], "mark_s": mark}


# -- the reduction -----------------------------------------------------------

def _union_s(intervals) -> float:
    return sum(e - s for s, e in xplane._union(sorted(intervals)))


def reduce(trace: dict, t0: float, t1: float) -> dict:
    """Seconds of ``[t0, t1]`` (trace clock), a mean over devices:
    ``busy_s`` (the union of all operations), ``by_scope`` (the union of
    each scope's operations, ``unscoped`` among them), ``scoped`` (whether
    any operation carried a program scope) and ``outside`` (the unscoped
    operations by ``module:instruction``, longest first, at most ten)."""
    n = len(trace["devices"])
    if n == 0:
        raise ValueError("the trace holds no TPU device plane")
    busy, by_scope, outside = 0.0, {}, {}
    for ops in trace["devices"]:
        spans: dict = {}
        for a, b, op_name, module, instr in ops:
            a, b = max(a, t0), min(b, t1)
            if b <= a:
                continue
            scope = scope_of(op_name) or UNSCOPED
            spans.setdefault(scope, []).append((a, b))
            if scope == UNSCOPED:
                key = f"{module}:{instr}"
                outside[key] = outside.get(key, 0.0) + (b - a)
        busy += _union_s([iv for ivs in spans.values() for iv in ivs])
        for scope, ivs in spans.items():
            by_scope[scope] = by_scope.get(scope, 0.0) + _union_s(ivs)
    top = sorted(outside.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy / n,
            "by_scope": {k: v / n for k, v in by_scope.items()},
            "scoped": any(k != UNSCOPED for k in by_scope),
            "outside": [[k, v / n] for k, v in top]}


def table(ctx):
    """The run's reduction, made once and kept in ``ctx``; None where
    there is nothing to read (a rehearsal has no device plane, a program
    without the scope names has no scoped operation). The first call
    says it on a line of its own, for PERF.md's device time by scope."""
    if "scopes" not in ctx:
        ctx["scopes"] = None
        run = ctx["run"]
        if not ctx["rehearse"] and run.get("trace_mark") is not None:
            trace = load(ctx["trace_dir"])
            if trace["mark_s"] is None:
                raise RuntimeError("the trace holds no window mark")
            shift = trace["mark_s"] - run["trace_mark"]
            got = reduce(trace, run["trace_t0"] + shift,
                         run["trace_t1"] + shift)
            ctx["say"]("device_time_by_scope", busy_s=got["busy_s"],
                       by_scope=got["by_scope"], outside=got["outside"])
            if got["scoped"]:
                ctx["scopes"] = got
    return ctx["scopes"]


def seconds(ctx, scopes):
    """Device seconds under ``scopes`` in the traced window, or None."""
    got = table(ctx)
    if got is None:
        return None
    return sum(got["by_scope"].get(s, 0.0) for s in scopes)
