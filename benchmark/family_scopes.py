"""Device time by scope for a family that names more scopes than
``scopes.PROGRAM_SCOPES`` lists.

``scopes.py`` attributes an operation to the innermost scope *it knows*,
and its list is GPT-2's. A family brings its own names (hyper-connection
mix, expert routing, absorbed products ...): ``benchmark/families/
<family>/scopes.json`` lists them, and this module makes the same
reduction over both lists together, from the same trace, loaded by
``scopes.load``. An operation under none of them is ``unscoped``. Also
the device time of a kernel, found by its name.
"""

from __future__ import annotations

import json
import os

from benchmark import scopes as sc
from benchmark import xplane

_HERE = os.path.dirname(os.path.abspath(__file__))


def known_scopes(family: str) -> tuple:
    """``(scope names, {instruction prefix: scope})`` of a family. The
    second is for operations the compiler writes without an ``op_name``
    though the program's call is under a scope: XLA's grouped
    matrix-multiplication kernel (``ragged-dot``) is one."""
    with open(os.path.join(_HERE, "families", family, "scopes.json")) as f:
        stated = json.load(f)
    return (tuple(sc.PROGRAM_SCOPES) + tuple(stated["scopes"]),
            dict(stated.get("instructions", {})))


def reduce(trace: dict, t0: float, t1: float, scopes: tuple,
           by_instruction: dict | None = None) -> dict:
    """``scopes.reduce`` over ``scopes``: seconds of ``[t0, t1]``, a mean
    over devices, ``busy_s``, ``by_scope``, ``scoped`` and ``outside``. An
    operation with no scope in its name stack whose instruction's name
    starts with a key of ``by_instruction`` belongs to that key's scope."""
    by_instruction = by_instruction or {}
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
            scope = sc.scope_of(op_name, scopes) or next(
                (v for k, v in by_instruction.items() if instr.startswith(k)),
                sc.UNSCOPED)
            spans.setdefault(scope, []).append((a, b))
            if scope == sc.UNSCOPED:
                key = f"{module}:{instr}"
                outside[key] = outside.get(key, 0.0) + (b - a)
        busy += sc._union_s([iv for ivs in spans.values() for iv in ivs])
        for scope, ivs in spans.items():
            by_scope[scope] = by_scope.get(scope, 0.0) + sc._union_s(ivs)
    top = sorted(outside.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy / n,
            "by_scope": {k: v / n for k, v in by_scope.items()},
            "scoped": any(k != sc.UNSCOPED for k in by_scope),
            "outside": [[k, v / n] for k, v in top]}


def _trace_window(ctx):
    """The run's trace and its window on the trace's clock, loaded once;
    None where there is nothing to read (a rehearsal, an untraced run)."""
    if "family_trace" not in ctx:
        ctx["family_trace"] = None
        run = ctx["run"]
        if not ctx["rehearse"] and run.get("trace_mark") is not None:
            trace = sc.load(ctx["trace_dir"])
            if trace["mark_s"] is None:
                raise RuntimeError("the trace holds no window mark")
            shift = trace["mark_s"] - run["trace_mark"]
            ctx["family_trace"] = (
                trace, run["trace_t0"] + shift, run["trace_t1"] + shift)
    return ctx["family_trace"]


def table(ctx):
    """The run's reduction over the family's scopes, made once; None
    where the trace shows no scope. Says it on a line of its own."""
    if "family_scopes" not in ctx:
        ctx["family_scopes"] = None
        got = _trace_window(ctx)
        family = ctx["config"].get("family")
        if got is not None and family:
            red = reduce(*got, *known_scopes(family))
            ctx["say"]("device_time_by_family_scope", busy_s=red["busy_s"],
                       by_scope=red["by_scope"], outside=red["outside"])
            if red["scoped"]:
                ctx["family_scopes"] = red
    return ctx["family_scopes"]


def kernel_seconds(ctx, kernel: str):
    """``(device seconds, calls)`` of the operations named after
    ``kernel`` (a ``pallas_call``'s ``name=`` shows in the operation's
    name stack or in its instruction's name) in the traced window, a mean
    over devices; None where there is no trace or no such operation."""
    got = _trace_window(ctx)
    if got is None:
        return None
    trace, t0, t1 = got
    total, calls = 0.0, 0
    for ops in trace["devices"]:
        ivs = [(max(a, t0), min(b, t1)) for a, b, op_name, _, instr in ops
               if (kernel in op_name or kernel in instr) and min(b, t1) > max(a, t0)]
        total += sum(e - s for s, e in xplane._union(sorted(ivs)))
        calls += len(ivs)
    n = len(trace["devices"])
    return (total / n, calls // n) if calls else None
