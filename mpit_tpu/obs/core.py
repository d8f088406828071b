"""Event core: spans, counters, gauges, and the process-global Recorder.

Design constraints (ISSUE 1 tentpole):

- **Near-zero overhead when disabled.** The fast path of every primitive
  is one module-global read. :func:`span` returns a shared no-op context
  manager object when disabled — no allocation, no lock, no clock read —
  so instrumenting a hot loop costs nanoseconds until someone calls
  :func:`enable`.
- **Thread-safe.** Spans come from the training thread, the prefetch
  thread, the simulator's rank threads, and bench's watchdog
  concurrently; one lock guards the buffers, taken only when enabled.
- **In-memory buffering.** Events are plain tuples in a list; export is
  a separate, explicit step (``obs.export``). A long run at a
  reasonable instrumentation density (tens of events per step) stays in
  the tens of MB; ``max_events`` caps pathological producers by
  dropping (and counting) the overflow rather than growing unbounded.

Event model:

- a *span* is ``(name, t0, dur, tid, attrs)`` — a named wall-clock
  interval on a thread (``t0`` seconds since the recorder's epoch);
- an *instant* is a zero-duration marker (``dur = 0.0``, kind "i") —
  used e.g. by ``comm.collectives`` to mark trace-time op recording;
- *counters* accumulate ``float`` values keyed by ``(name, attrs)`` —
  monotonic by convention (the exporters render them as Chrome "C"
  events); *gauges* keep the last value instead.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Iterator, Mapping

__all__ = [
    "Recorder",
    "counter",
    "disable",
    "enable",
    "enabled",
    "gap_attribution",
    "gauge",
    "get_recorder",
    "instant",
    "local_recorder",
    "span",
    "span_at",
    "summary",
]


def _attr_key(attrs: Mapping[str, Any] | None) -> tuple:
    """Canonical hashable key for an attribute set."""
    if not attrs:
        return ()
    return tuple(sorted(attrs.items()))


def phase_stats(durations: Mapping[str, Any]) -> dict:
    """``{name: {count, total_s, p50_s, p95_s}}`` from per-phase
    duration lists — the ONE definition of the phase roll-up, shared by
    :meth:`Recorder.summary` (live) and ``python -m mpit_tpu.obs``
    (offline traces), so the two reports cannot drift."""
    # Lazy: keeps this module numpy-free at import, so the pure-host
    # layers built on it (obs.slo, obs.stream consumers) stay cheap to
    # import (pinned by tests/test_import_hygiene.py).
    import numpy as np

    phases = {}
    for name, durs in sorted(durations.items()):
        arr = np.asarray(durs)
        phases[name] = {
            "count": int(arr.size),
            "total_s": float(arr.sum()),
            "p50_s": float(np.percentile(arr, 50)),
            "p95_s": float(np.percentile(arr, 95)),
        }
    return phases


class Recorder:
    """Thread-safe in-memory event buffer.

    One process-global instance is installed by :func:`enable`; library
    code reaches it only through the module-level primitives so the
    disabled fast path stays a single global read.
    """

    def __init__(self, *, max_events: int = 2_000_000):
        self._lock = threading.Lock()
        self._epoch = time.perf_counter()
        self._max_events = max_events
        self.dropped = 0
        # span/instant tuples: (kind, name, t0_s, dur_s, tid, attrs|None)
        self.events: list[tuple] = []
        self.counters: dict[tuple[str, tuple], float] = {}
        self.gauges: dict[tuple[str, tuple], float] = {}
        self._thread_names: dict[int, str] = {}
        # Roofline accounting (ISSUE 8; obs/roofline.py): per-phase
        # registered modeled cost (one dict per phase, set at compile)
        # and accumulated explicit achieved work. Plain floats only —
        # the roll-up math lives in obs.roofline, imported lazily by
        # summary() so this module stays import-light.
        self.costs: dict[str, dict] = {}
        self.work: dict[str, dict] = {}

    # -- recording (called via the module-level primitives) -----------------
    def add_span(
        self, name: str, t0: float, t1: float, attrs: Mapping | None = None
    ) -> None:
        th = threading.current_thread()
        with self._lock:
            if len(self.events) >= self._max_events:
                self.dropped += 1
                return
            self._thread_names.setdefault(th.ident, th.name)
            self.events.append(
                ("X", name, t0 - self._epoch, t1 - t0, th.ident, attrs)
            )

    def add_instant(self, name: str, attrs: Mapping | None = None) -> None:
        th = threading.current_thread()
        with self._lock:
            if len(self.events) >= self._max_events:
                self.dropped += 1
                return
            self._thread_names.setdefault(th.ident, th.name)
            self.events.append(
                ("i", name, time.perf_counter() - self._epoch, 0.0,
                 th.ident, attrs)
            )

    def add_counter(
        self, name: str, value: float, attrs: Mapping | None = None
    ) -> None:
        key = (name, _attr_key(attrs))
        with self._lock:
            self.counters[key] = self.counters.get(key, 0.0) + float(value)

    def add_gauge(
        self, name: str, value: float, attrs: Mapping | None = None
    ) -> None:
        with self._lock:
            self.gauges[(name, _attr_key(attrs))] = float(value)

    def add_cost(self, phase: str, cost: Mapping[str, Any]) -> None:
        """Register a phase's per-execution modeled cost (last write
        wins — re-registration after a recompile is legitimate)."""
        with self._lock:
            self.costs[phase] = dict(cost)

    def add_work(
        self,
        phase: str,
        *,
        flops: float | None = None,
        hbm_bytes: float | None = None,
        ici_bytes: float | None = None,
        n: int = 1,
    ) -> None:
        """Accumulate explicit achieved work for a phase; a component
        ever fed here is marked ``explicit`` and the roll-up uses its
        sum instead of span-count × per-exec modeled cost."""
        with self._lock:
            w = self.work.setdefault(
                phase,
                {"flops": 0.0, "hbm_bytes": 0.0, "ici_bytes": 0.0,
                 "n": 0, "explicit": set()},
            )
            w["n"] += n
            for key, value in (
                ("flops", flops), ("hbm_bytes", hbm_bytes),
                ("ici_bytes", ici_bytes),
            ):
                if value is not None:
                    w[key] += float(value)
                    w["explicit"].add(key)

    # -- reading ------------------------------------------------------------
    def snapshot(self) -> dict:
        """Consistent copy of all buffers (for exporters)."""
        with self._lock:
            return {
                "events": list(self.events),
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "thread_names": dict(self._thread_names),
                "dropped": self.dropped,
                "costs": {k: dict(v) for k, v in self.costs.items()},
                "work": {
                    k: {**v, "explicit": set(v["explicit"])}
                    for k, v in self.work.items()
                },
            }

    def counter_items(self, name: str) -> Iterator[tuple[dict, float]]:
        """(attrs dict, value) pairs for every counter named ``name``."""
        with self._lock:
            items = [
                (dict(k[1]), v) for k, v in self.counters.items()
                if k[0] == name
            ]
        return iter(items)

    def counter_total(self, name: str) -> float:
        """Sum of a counter across all attribute sets."""
        with self._lock:
            return sum(v for k, v in self.counters.items() if k[0] == name)

    def drain(self) -> dict:
        """Snapshot AND clear — bench.py's per-workload phase breakdown
        uses this so each workload's events don't bleed into the next."""
        with self._lock:
            out = {
                "events": self.events,
                "counters": self.counters,
                "gauges": self.gauges,
                "thread_names": dict(self._thread_names),
                "dropped": self.dropped,
                "costs": self.costs,
                "work": self.work,
            }
            self.events = []
            self.counters = {}
            self.gauges = {}
            self.dropped = 0
            self.costs = {}
            self.work = {}
        return out

    def event_count(self) -> int:
        """Current event-buffer length — a cursor for scoped summaries
        (``summary(since=...)``): callers bracketing one sub-run of a
        longer recording (bench's hardened-loop gap window) note the
        count before and roll up only what landed after."""
        with self._lock:
            return len(self.events)

    def summary(self, *, top_collectives: int = 5, since: int = 0) -> dict:
        """Roll events into ``{"phases": {name: {count, total_s, p50_s,
        p95_s}}, "collectives": [...], "counters": {...}}``.

        ``collectives`` lists the top-N ops by accumulated modeled wire
        bytes (the ``collective_bytes`` counter written by
        ``comm.collectives``), most traffic first. ``since`` restricts
        the PHASE roll-up to events recorded at/after that buffer index
        (see :meth:`event_count`); counters are cumulative either way.
        """
        snap = self.snapshot()
        by_name: dict[str, list[float]] = {}
        labels: dict[str, dict[str, set]] = {}
        instants: dict[str, int] = {}
        # Compile-overlay seconds per TRIGGERING phase (the `compile`
        # span's `phase` attr, obs.roofline.CompileWatch): the roofline
        # roll-up excludes them from its utilization denominator — a
        # phase's first call absorbs trace+compile wall that is not
        # steady-state execution.
        compile_s: dict[str, float] = {}
        for kind, name, _t0, dur, _tid, attrs in snap["events"][since:]:
            if kind == "i":
                # Instants (anomaly, slo_breach, slo_recovered, ...) are
                # zero-duration, so the phase table can't carry them —
                # roll their counts up separately: a baseline snapshot
                # must show that a load run TRIPPED its SLO, not just
                # how long its decode ticks took (ISSUE 6).
                instants[name] = instants.get(name, 0) + 1
            if kind == "X":
                by_name.setdefault(name, []).append(dur)
                if name == "compile" and attrs and "phase" in attrs:
                    ph = attrs["phase"]
                    compile_s[ph] = compile_s.get(ph, 0.0) + dur
                # String-valued span attrs are mode LABELS (e.g. the
                # serve path's attention="kernel"|"reference") — roll
                # the distinct values up so a report reader can see
                # which implementation a phase actually ran (ISSUE 5:
                # attributing a serve regression to kernel fallback).
                if attrs:
                    lab = labels.setdefault(name, {})
                    for k, v in attrs.items():
                        if isinstance(v, str):
                            lab.setdefault(k, set()).add(v)
        phases = phase_stats(by_name)
        for name, lab in labels.items():
            if lab and name in phases:
                phases[name]["labels"] = {
                    k: sorted(vs) for k, vs in lab.items()
                }
        colls = [
            ({**dict(k[1])}, v)
            for k, v in snap["counters"].items()
            if k[0] == "collective_bytes"
        ]
        colls.sort(key=lambda kv: kv[1], reverse=True)
        collectives = [
            {**attrs, "wire_bytes": v}
            for attrs, v in colls[:top_collectives]
        ]
        counters = {}
        for (name, _akey), v in snap["counters"].items():
            counters[name] = counters.get(name, 0.0) + v
        out = {"phases": phases, "collectives": collectives,
               "counters": counters}
        if snap["costs"] and since == 0:
            # Roofline roll-up (ISSUE 8): achieved work vs measured
            # span seconds against chip peaks, for every phase whose
            # executable registered its cost; compile-overlay seconds
            # are excluded from the denominator. Lazy import — the math
            # (and its honesty rules) lives in obs.roofline. Only on
            # UNSCOPED summaries: work/cost accumulation is cumulative
            # (not event-indexed), so a `since` slice would divide
            # whole-recording work by a window's seconds and report
            # inflated utilization.
            from mpit_tpu.obs import roofline as _roofline

            out["roofline"] = _roofline.rollup(
                snap["costs"], snap["work"], phases,
                overlay_seconds=compile_s,
            )
        if instants:
            out["instants"] = dict(sorted(instants.items()))
        # ALWAYS present (ISSUE 6 satellite): a consumer deciding
        # whether the percentiles above describe the whole run must not
        # have to know that absence means zero — a truncated buffer
        # reports the spans that fit and silently represents the rest.
        out["dropped_events"] = snap["dropped"]
        return out


# ---------------------------------------------------------------------------
# Process-global switch + the primitives library code calls.
# ---------------------------------------------------------------------------

_RECORDER: Recorder | None = None
_LOCK = threading.Lock()

# Thread-local recorder override (ISSUE 3: the compat simulator's rank
# THREADS each need their own event stream for cross-rank aggregation —
# the process-global recorder would merge every rank into one lane).
# `_TLS_ACTIVE` counts installed overrides so the disabled fast path
# stays two module-global reads when nobody uses the feature.
_TLS = threading.local()
_TLS_ACTIVE = 0


def _current() -> Recorder | None:
    """The recorder the CALLING THREAD should record into: its
    thread-local override when one is installed, else the global."""
    if _TLS_ACTIVE:
        rec = getattr(_TLS, "recorder", None)
        if rec is not None:
            return rec
    return _RECORDER


class _NoopSpan:
    """Shared do-nothing context manager — the disabled fast path. A
    single instance is reused, so a disabled ``span()`` call allocates
    nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    """Live span: times ``__enter__``..``__exit__`` and records on exit.

    Re-checks the global on exit so a recorder swapped out mid-span
    can't resurrect; events land in whichever recorder is installed at
    exit time (good enough for a debugging layer, and lock-free on the
    span object itself)."""

    __slots__ = ("name", "attrs", "t0")

    def __init__(self, name: str, attrs: Mapping | None):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        rec = _current()
        if rec is not None:
            rec.add_span(self.name, self.t0, time.perf_counter(), self.attrs)
        return False


def enable(recorder: Recorder | None = None) -> Recorder:
    """Install (and return) the process-global recorder. Idempotent when
    one is already installed and none is passed."""
    global _RECORDER
    with _LOCK:
        if recorder is not None:
            _RECORDER = recorder
        elif _RECORDER is None:
            _RECORDER = Recorder()
        return _RECORDER


def disable() -> None:
    """Remove the process-global recorder; primitives return to the
    no-op fast path. The recorder object (and its events) survive for
    export if the caller kept a reference."""
    global _RECORDER
    with _LOCK:
        _RECORDER = None


def enabled() -> bool:
    return _current() is not None


def get_recorder() -> Recorder | None:
    """The calling thread's recorder (thread-local override first)."""
    return _current()


def get_global_recorder() -> Recorder | None:
    """The process-global recorder only, IGNORING any thread-local
    override. For code that records on behalf of ANOTHER thread (the
    compat simulator delivers receives on the sender's thread) and must
    not leak events into the delivering thread's per-rank stream."""
    return _RECORDER


@contextlib.contextmanager
def local_recorder(recorder: Recorder | None = None):
    """Install a THREAD-LOCAL recorder for the enclosed block.

    While active, every primitive called on this thread records into it
    instead of the process-global recorder — the per-rank event stream
    the compat simulator's rank threads need for cross-rank aggregation
    (``obs.aggregate``). Other threads are untouched. Nests: the
    previous override (or the global) is restored on exit. Yields the
    recorder so ``with obs.local_recorder() as rec:`` reads naturally.
    """
    global _TLS_ACTIVE
    rec = recorder if recorder is not None else Recorder()
    prev = getattr(_TLS, "recorder", None)
    with _LOCK:
        _TLS_ACTIVE += 1
    _TLS.recorder = rec
    try:
        yield rec
    finally:
        _TLS.recorder = prev
        with _LOCK:
            _TLS_ACTIVE -= 1


def span(name: str, **attrs):
    """Context manager timing a named phase. Disabled: returns the
    shared no-op instance (no allocation)."""
    if _current() is None:
        return _NOOP
    return _Span(name, attrs or None)


def span_at(name: str, t0: float, t1: float, **attrs) -> None:
    """Record a completed span from explicit ``time.perf_counter``
    timestamps — for intervals that are not a ``with`` block on one
    thread: the serve scheduler's per-request ``queue_wait`` / TTFT /
    end-to-end latency intervals span submit→admit→retire across many
    loop ticks. The summary's per-phase p50/p95 roll-up over such spans
    is the latency histogram (ISSUE 4)."""
    rec = _current()
    if rec is not None:
        rec.add_span(name, t0, t1, attrs or None)


def instant(name: str, **attrs) -> None:
    """Zero-duration marker event."""
    rec = _current()
    if rec is not None:
        rec.add_instant(name, attrs or None)


def counter(name: str, value: float = 1.0, **attrs) -> None:
    """Accumulate ``value`` onto the counter keyed by name + attrs."""
    rec = _current()
    if rec is not None:
        rec.add_counter(name, value, attrs or None)


def gauge(name: str, value: float, **attrs) -> None:
    """Set the last-value gauge keyed by name + attrs."""
    rec = _current()
    if rec is not None:
        rec.add_gauge(name, value, attrs or None)


def summary(*, top_collectives: int = 5, since: int = 0) -> dict:
    """Summary of the calling thread's recorder ({} when disabled)."""
    rec = _current()
    if rec is None:
        return {}
    return rec.summary(top_collectives=top_collectives, since=since)


# Loop phases that are host-side wall clock AROUND device dispatch — the
# app-path components `hardened_loop` spans (train/loop.py). "step" is
# the dispatch+compute span itself; everything else is the candidate
# overhead the async pipeline exists to overlap away. The prefetch
# pipeline's own stages run on their OWN threads (they overlap the loop)
# and are reported separately.
_HOST_PHASES = (
    "prefetch_wait",
    "host_fence",
    "checkpoint_save",
    "eval",
    "divergence_restore",
)
_OVERLAPPED_PHASES = ("prefetch_host", "prefetch_device_put")
# Overlay phases NEST inside another phase's span rather than adding
# wall time of their own: a ``compile`` span (obs.roofline.CompileWatch)
# covers the same interval as the step/prefill/decode span whose first
# call triggered the compile. Wall-time reconciliations that sum
# sequential loop phases must exclude these, exactly like the
# pipeline-thread overlapped phases above. The start-up record's spans
# (obs.startup, mirrored into an enabled recorder) are overlays too:
# JAX's compile events and ``first_run`` are the ``compile`` span's
# children, and ``state_init`` / ``engine_build`` / ``cache_alloc`` /
# ``cost_query`` come before a loop's first step or inside ``warmup``.
_OVERLAY_PHASES = (
    "compile", "jit_trace", "jit_lower", "backend_compile", "first_run",
    "state_init", "engine_build", "cache_alloc", "cost_query",
)


def gap_attribution(summ: Mapping | None = None) -> dict:
    """Attribute a training run's app-path wall clock across loop phases.

    Input: a :func:`summary`-shaped dict (default: the installed
    recorder's). Output rolls the ``hardened_loop`` span phases into the
    app-path gap report (ISSUE 2): the loop-thread wall split into
    ``step`` (host dispatch + device wait inside the step span) vs each
    host phase, plus each phase's share of the loop total.

    Interpretation note for the async host path: once the metric fences
    are pipelined, a large ``host_fence`` share means the host is parked
    *waiting for the device to catch up* — overlap working as intended —
    while a large ``prefetch_wait`` share means input starvation. The
    throughput-derived ``app_path_overhead_pct`` (bench.py) is the
    verdict; this roll-up is the attribution of where the wall went.
    ``prefetch_host`` / ``prefetch_device_put`` run on pipeline threads
    (they overlap the loop) and are reported for context, not summed
    into the loop wall.
    """
    if summ is None:
        summ = summary()
    phases = summ.get("phases", {}) if summ else {}
    step_s = phases.get("step", {}).get("total_s", 0.0)
    host = {
        n: phases[n]["total_s"] for n in _HOST_PHASES if n in phases
    }
    overlap = {
        n: round(phases[n]["total_s"], 4)
        for n in _OVERLAPPED_PHASES
        if n in phases
    }
    host_s = sum(host.values())
    loop_s = step_s + host_s
    out = {
        "loop_s": round(loop_s, 4),
        "step_s": round(step_s, 4),
        "host_s": round(host_s, 4),
        "host_phases_s": {n: round(v, 4) for n, v in sorted(host.items())},
        "host_share_pct": round(100.0 * host_s / loop_s, 2) if loop_s else 0.0,
    }
    if overlap:
        out["overlapped_s"] = overlap
    return out
