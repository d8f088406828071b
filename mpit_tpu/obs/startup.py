"""The start-up record: what a process did between its start and its
first tick or step, as spans named by executable (ISSUE 36).

Set-up runs before any driver could have enabled a
:class:`~mpit_tpu.obs.core.Recorder`, so this is the part of the one
tracing system that is always on: a bounded list of events in the
recorder's shape on the recorder's clock (``time.perf_counter()``), a
few dozen a process. Where a recorder IS enabled the same events go to
it too (the Chrome trace and ``obs.summary()`` show them as any span).

Three sources feed it:

- :func:`span` — the program's own start-up boundaries (``engine_build``,
  ``cache_alloc``, ``warmup``, ``cost_query``, ``state_init``). A
  thread-local stack of open spans gives every event its ``parent``: the
  innermost start-up span open on its thread, the span that caused it.
- JAX's compile events (:func:`install` registers a duration listener,
  an event listener and a scalar listener on ``jax.monitoring``, once a
  process):
  ``/jax/core/compile/jaxpr_trace_duration`` -> ``jit_trace``,
  ``.../jaxpr_to_mlir_module_duration`` -> ``jit_lower``,
  ``.../backend_compile_duration`` -> ``backend_compile``, each with
  ``fun`` = the event's ``fun_name`` without its ``jit(...)`` wrapper
  (``decode_paged``, ``train_step``, ...). JAX reports a duration at the
  event's end: ``end`` is ``perf_counter()`` at receipt and ``start`` is
  ``end`` less the duration (JAX's own time spans are on ``time.time()``
  and are not listened to: the clocks would mix and every event count
  twice). JAX also says when each BEGINS (a scalar under the same
  name): the count of those open on a thread tells the outermost trace
  or lowering, which is kept, from the traces of the jitted functions
  it calls (every ``jnp`` operation is one), which lie inside it and
  are not. The persistent cache's events (``/jax/compilation_cache/
  cache_hits``, ``cache_misses``, ``cache_retrieval_time_sec``,
  ``compile_time_saved_sec``) arrive inside the backend compile, in its
  thread, before it closes: they are held a thread and written on that
  ``backend_compile`` as ``cache_hit``, ``cache_read_s``, ``saved_s``.
  ``cache_hit`` is True on a hit, False where JAX compiled and wrote the
  entry (its ``cache_misses`` event), None where the cache was not
  asked or does not keep the entry (no directory, or a compile under
  ``jax_persistent_cache_min_compile_time_secs``).
- :class:`Watch` — the seam ``obs.roofline.CompileWatch.call`` and the
  train step use: a provisional ``compile`` span that is recorded only
  if JAX compiled in its thread while it was open, with the three events
  above and ``first_run`` (the last compile's end to the output ready)
  as its children.

:func:`ready` closes a scope's start-up (``"engine"``, ``"train"``).
A backend compile after that, outside any open start-up span, is a
``compile_after_ready`` instant with ``fun``, the three durations and
``cache_hit``, counted in ``compiles_after_ready`` by ``fun``: what an
operator reads when one tick takes 20 s. :func:`report` rolls the record
up (seconds to ready, seconds by phase, the slowest executables, cache
misses); the two CLIs print it as their ``ready`` line and
``Server.stats()["startup"]`` holds it.

Host-pure: no ``jax`` or ``numpy`` import at module level (pinned by
``tests/test_import_hygiene.py``); :func:`install` imports
``jax.monitoring`` when it is called.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time

from mpit_tpu.obs import core as _core

__all__ = [
    "MAX_EVENTS",
    "Watch",
    "install",
    "on_ready",
    "ready",
    "report",
    "reset",
    "say_ready",
    "snapshot",
    "span",
]

MAX_EVENTS = 4096

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_PHASE_OF = {_TRACE: "jit_trace", _LOWER: "jit_lower",
             _BACKEND: "backend_compile"}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"


def _process_start() -> float:
    """The process's start on ``perf_counter()``'s scale: the kernel's
    start time of this process against its boot clock where ``/proc``
    says (imports before this module are start-up too), else now."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - ticks / os.sysconf("SC_CLK_TCK"))
        return now - age if 0.0 <= age < 86400.0 else now
    except (OSError, ValueError, IndexError, AttributeError):
        return now


class _ThreadState(threading.local):
    """What a thread has open: ``stack`` holds the ids of its open
    spans and of its open watch (an event's ``parent`` is the last),
    ``depth`` counts the spans alone, ``held`` what the cache's events
    said since the thread's last backend compile, ``open`` how
    many of JAX's traces, lowerings and compiles have begun on it and
    not ended, ``late`` a trace and a lowering after ready that no
    backend compile has followed yet."""

    def __init__(self):
        self.stack: list[int] = []
        self.depth = 0
        self.watch = None
        self.held: dict = {}
        self.open = 0
        self.late: list[tuple] = []


_LOCK = threading.Lock()
_TLS = _ThreadState()
_T_PROCESS = _process_start()
# (id, name, start, end, parent id or None, attrs or None)
_EVENTS: list[tuple] = []
_DROPPED = 0
_new_id = itertools.count(1).__next__  # atomic; no lock on a step's path
_READY: dict[str, float] = {}
_AFTER_READY: dict[str, int] = {}
_ON_READY: list = []
_INSTALLED = False


def _record(eid, name, start, end, parent, attrs) -> None:
    """One event into the record and, where one is enabled, into the
    calling thread's recorder."""
    global _DROPPED
    with _LOCK:
        if len(_EVENTS) >= MAX_EVENTS:
            _DROPPED += 1
        else:
            _EVENTS.append((eid, name, start, end, parent, attrs))
    if _core.enabled():
        if end > start:
            _core.span_at(name, start, end, **(attrs or {}))
        else:
            _core.instant(name, **(attrs or {}))


class _Span:
    """A start-up boundary of the program: always recorded."""

    __slots__ = ("name", "attrs", "id", "t0")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        self.id = _new_id()
        _TLS.stack.append(self.id)
        _TLS.depth += 1
        self.t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        """Attributes known only inside the span (``bytes`` of a pool)."""
        self.attrs.update(attrs)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        st = _TLS.stack
        st.pop()
        _TLS.depth -= 1
        _record(self.id, self.name, self.t0, t1, st[-1] if st else None,
                self.attrs or None)
        return False


def span(name: str, **attrs) -> _Span:
    """Context manager: a start-up span of the record. Events of this
    thread while it is open carry it as ``parent``."""
    return _Span(name, attrs)


class Watch:
    """A provisional ``compile`` span round one call of a jitted step.

    ``with Watch() as w: out = fn(*args)``; then ``w.compiled`` says
    whether JAX compiled in this thread meanwhile (a
    ``backend_compile`` event arrived). If it did, :meth:`close` records
    the ``compile`` span, from the call's start to the output ready,
    with ``first_run`` as its last child; if not, nothing was recorded
    and nothing is. A watch opened while another is open on the thread
    is passive (``active`` False): the outer one sees the events.
    """

    __slots__ = ("active", "id", "t0", "compiles")

    def __enter__(self):
        self.active = _TLS.watch is None
        if self.active:
            self.compiles = []  # (fun, end) of each backend compile
            self.id = _new_id()
            _TLS.watch = self
            _TLS.stack.append(self.id)
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.active:
            _TLS.watch = None
            _TLS.stack.pop()
        return False

    @property
    def compiled(self) -> bool:
        return self.active and bool(self.compiles)

    def close(self, out=None, **attrs) -> dict:
        """Record the ``compile`` span of a watch that ``compiled``:
        wait for ``out`` (the executable's first execution ends the
        span), then ``first_run`` and ``compile`` with ``attrs`` and
        ``module`` = ``jit_<fun>`` of the last backend compile. Returns
        ``(t0, t1, fun)``."""
        fun, compile_end = self.compiles[-1]
        if out is not None:
            import jax

            jax.block_until_ready(out)
        t1 = time.perf_counter()
        _record(_new_id(), "first_run", compile_end, t1, self.id,
                {"fun": fun})
        st = _TLS.stack
        _record(self.id, "compile", self.t0, t1, st[-1] if st else None,
                {**attrs, "module": "jit_" + fun})
        return self.t0, t1, fun


def _bare(fun_name) -> str:
    """``jit(decode_paged)`` -> ``decode_paged`` (the lowering and the
    backend compile wrap the name, the trace does not)."""
    name = str(fun_name or "")
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    return name


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _TLS.held["cache_hit"] = True
    elif event == _CACHE_MISS:
        _TLS.held.setdefault("cache_hit", False)


def _on_scalar(event: str, _value, **_kw) -> None:
    """JAX says when a trace, a lowering or a backend compile BEGINS
    (a scalar under the event's name, its ``time.time()`` start, which
    is not used): the count of those open on the thread tells the
    outermost from what lies inside it."""
    if event in _PHASE_OF:
        _TLS.open += 1


def _on_duration(event: str, secs: float, **kw) -> None:
    name = _PHASE_OF.get(event)
    if name is None:
        if event == _CACHE_READ:
            _TLS.held["cache_read_s"] = secs
        elif event == _CACHE_SAVED:
            _TLS.held["saved_s"] = secs
        return
    inside = _TLS.open = max(0, _TLS.open - 1)
    if inside and name != "backend_compile":
        # A jitted function that calls jitted functions (every ``jnp``
        # operation is one, in a lowering rule too) is traced with
        # their traces inside its own: the outermost alone is kept,
        # thousands of events a large step otherwise.
        return
    end = time.perf_counter()
    start = end - secs
    attrs = {"fun": _bare(kw.get("fun_name"))}
    st = _TLS.stack
    parent = st[-1] if st else None
    held = _TLS.held
    if name != "backend_compile":
        ev = (_new_id(), name, start, end, parent, attrs)
        if _READY and not st:
            # After ready, under no span and no watch, an eager
            # operation whose executable is cached traces and builds
            # nothing, for as long as the process lives: a trace and
            # the lowering behind it are kept only if a backend compile
            # follows them, and a new trace begins a new story.
            if name == "jit_trace":
                del _TLS.late[:]
                held.pop("jit_trace_s", None)
                held.pop("jit_lower_s", None)
            _TLS.late.append(ev)
        else:
            _record(*ev)
        # What the thread traced and lowered for the backend compile
        # to come: a compile after ready is told with it.
        held[name + "_s"] = held.get(name + "_s", 0.0) + secs
        return
    for ev in _TLS.late:  # the trace and the lowering it follows
        _record(*ev)
    del _TLS.late[:]
    attrs["cache_hit"] = held.pop("cache_hit", None)
    for key in ("cache_read_s", "saved_s"):
        if key in held:
            attrs[key] = held.pop(key)
    _record(_new_id(), name, start, end, parent, attrs)
    if _TLS.watch is not None:
        _TLS.watch.compiles.append((attrs["fun"], end))
    trace_s = held.pop("jit_trace_s", 0.0)
    lower_s = held.pop("jit_lower_s", 0.0)
    # After a scope is ready, and under no start-up span (another
    # engine's warm-up is start-up, a watched step call is not).
    if _READY and not _TLS.depth:
        fun = attrs["fun"]
        with _LOCK:
            _AFTER_READY[fun] = _AFTER_READY.get(fun, 0) + 1
        _record(_new_id(), "compile_after_ready", end, end, None, {
            "fun": fun, "jit_trace_s": trace_s, "jit_lower_s": lower_s,
            "backend_compile_s": secs, "cache_hit": attrs["cache_hit"],
        })
        _core.counter("compiles_after_ready", fun=fun)


def install() -> None:
    """Register the three listeners on ``jax.monitoring`` (durations,
    the cache's events, the scalars that mark a beginning); a process
    registers them once however often this is called."""
    global _INSTALLED
    with _LOCK:
        if _INSTALLED:
            return
        _INSTALLED = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    monitoring.register_scalar_listener(_on_scalar)


def on_ready(callback) -> None:
    """``callback(scope)`` when a scope becomes ready (the CLIs print
    their ``ready`` line from it)."""
    _ON_READY.append(callback)


def ready(scope: str) -> None:
    """Close ``scope``'s start-up: the first call a scope counts, and
    only under no open start-up span of the calling thread (a warm-up's
    throwaway request lands a first token too; the warm-up's end is what
    makes the engine ready)."""
    if scope in _READY or _TLS.depth:
        return
    now = time.perf_counter()
    with _LOCK:
        if scope in _READY:
            return
        _READY[scope] = now
    _record(_new_id(), "ready", now, now, None, {"scope": scope})
    for callback in list(_ON_READY):
        callback(scope)


def say_ready(scope: str) -> None:
    """The CLIs' ``ready`` line, on stderr (their last stdout line is
    their result): ``ready {"scope": ..., <report()>}``. Register with
    :func:`on_ready`."""
    print("ready " + json.dumps({"scope": scope, **report()}),
          file=sys.stderr, flush=True)


def reset() -> None:
    """Empty the record (tests; a process's listeners stay)."""
    global _DROPPED
    with _LOCK:
        _EVENTS.clear()
        _READY.clear()
        _AFTER_READY.clear()
        _DROPPED = 0
    del _ON_READY[:]


def snapshot() -> dict:
    """A consistent copy: ``events`` as dicts (``id``, ``name``,
    ``start``, ``end`` on ``perf_counter()``'s scale, ``parent``,
    ``attrs``), ``dropped``, ``ready`` (scope -> time),
    ``compiles_after_ready`` (fun -> count), ``t_process``."""
    with _LOCK:
        events = list(_EVENTS)
        out = {"dropped": _DROPPED, "ready": dict(_READY),
               "compiles_after_ready": dict(_AFTER_READY),
               "t_process": _T_PROCESS}
    out["events"] = [
        {"id": i, "name": n, "start": a, "end": b, "parent": p,
         "attrs": dict(attrs or {})}
        for i, n, a, b, p, attrs in events
    ]
    return out


def covered_s(intervals) -> float:
    """Seconds the union of ``(start, end)`` intervals covers: a jitted
    function that calls jitted functions traces them inside its own
    trace, and a sum of durations would count that time twice."""
    total, reach = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return total


def report(*, slowest: int = 5) -> dict:
    """The operator's roll-up of start-up: seconds from the process's
    start to each scope's ``ready``; seconds covered by each phase and
    by all of them up to the last ``ready`` (all, while none is);
    executables built, cache hits and misses; the slowest executables by
    ``backend_compile`` seconds; compiles after ready by ``fun``."""
    snap = snapshot()
    until = max(snap["ready"].values(), default=float("inf"))
    spans = [e for e in snap["events"]
             if e["end"] > e["start"] and e["end"] <= until]
    by_name: dict[str, list] = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append((e["start"], e["end"]))
    built = [e for e in spans if e["name"] == "backend_compile"]
    built.sort(key=lambda e: e["start"] - e["end"])
    return {
        "ready_s": {scope: round(t - snap["t_process"], 3)
                    for scope, t in sorted(snap["ready"].items())},
        "program_s": round(
            covered_s(iv for ivs in by_name.values() for iv in ivs), 3),
        "seconds": {name: round(covered_s(ivs), 3)
                    for name, ivs in sorted(by_name.items())},
        "executables": len(built),
        "cache_hits": sum(e["attrs"].get("cache_hit") is True for e in built),
        "cache_misses": sum(
            e["attrs"].get("cache_hit") is False for e in built),
        "slowest": [
            [e["attrs"].get("fun"), round(e["end"] - e["start"], 3),
             e["attrs"].get("cache_hit")]
            for e in built[:slowest]
        ],
        "compiles_after_ready": snap["compiles_after_ready"],
        "events": len(snap["events"]),
        "dropped": snap["dropped"],
    }
