"""Roofline utilization flight data: measured wall vs modeled work.

The obs stack before this module could say *how long* every phase took
(spans, windows, SLOs, per-rank lanes) but not *how good* that time was:
``utils/profiling.py`` holds the ground truth — ``compiled.
cost_analysis()`` FLOPs/bytes and :class:`~mpit_tpu.utils.profiling.
ChipSpec` peaks — but it was only used for offline bench modeling, never
reconciled against measured time. This module closes the loop (ISSUE 8
tentpole), the same measured-vs-modeled pattern the flight recorder's
P2P matrix established:

- **Cost registration** — a jitted executable's per-execution modeled
  work (``cost_analysis()`` FLOPs / HBM bytes, plus modeled ICI wire
  bytes where the caller knows them) is registered ONCE, at compile,
  under the phase name its spans use (:func:`register_cost`; the serve
  engine and bench wire it through :func:`cost_from_fn`).
- **Work accumulation** — every span close of a registered phase
  accumulates one execution's modeled work; phases whose real work is
  length-dependent feed *explicit* achieved amounts instead
  (:func:`work`) — the flash-decode path feeds HBM bytes derived from
  the kernel's own visited-tile counts (:func:`decode_step_hbm_bytes`),
  because the padded ``cost_analysis`` number is wrong BY DESIGN for a
  tile-skipping kernel.
- **Roll-up** — ``Recorder.summary()`` divides achieved work by the
  phase's measured span seconds and reports ``mfu_pct`` /
  ``hbm_util_pct`` / ``ici_util_pct`` against the chip peaks, plus the
  binding-resource verdict (:func:`rollup` / :func:`utilization`).

Honesty rules: modeled cost and
achieved-work *totals* are recorded on every platform, but utilization
*percentages* — measured seconds against TPU peaks — are only computed
when the recording platform IS the chip (``platform="tpu"``); CPU /
interpret runs carry the platform label and no fabricated MFU. The
binding-resource verdict (``bound_modeled``) is a property of the work
model against the chip peaks, not a measurement, so it is reported
everywhere and labeled modeled.

Compile observability rides along:

- :class:`CompileWatch` — detects XLA compiles of watched jitted
  callables by JAX's own compile events (``obs.startup``): each compile
  emits a ``compile`` span with the executable's ``module``
  (overlaying the phase span that triggered it — excluded from
  sequential wall reconciliation via ``obs.core._OVERLAY_PHASES``), a
  ``compiles`` counter and a ``<scope>_compiles`` gauge; a compile past
  the declared lifetime expectation (the serve engine's "two compiles,
  zero per-request recompiles" claim) emits an ``unexpected_recompile``
  instant and feeds :meth:`~mpit_tpu.obs.sentinel.Sentinel.note`.
- :class:`UtilizationWatch` — the sustained-collapse rule: a
  utilization/throughput series falling below ``drop_ratio`` × its
  rolling median for ``sustained_n`` consecutive observations is an
  anomaly (throughput quietly halving under constant load is exactly
  the regression the sentinel's *duration* detectors can miss when load
  drops with it).

Import-light like the rest of ``mpit_tpu.obs``: jax and the ChipSpec
(``utils.profiling``) are imported lazily, only by the helpers that
extract costs or resolve peaks.
"""

from __future__ import annotations

import statistics
from collections import deque
from typing import Any, Mapping

from mpit_tpu.obs import core as _core
from mpit_tpu.obs import startup as _startup

__all__ = [
    "CompileWatch",
    "UtilizationWatch",
    "chip_peaks",
    "cost_from_compiled",
    "cost_from_fn",
    "cost_properties",
    "decode_step_hbm_bytes",
    "kv_tile_read_bytes",
    "register_cost",
    "rollup",
    "utilization",
    "work",
]

# Work components a phase can accumulate; the utilization keys computed
# from them on-chip, in the same order.
_COMPONENTS = ("flops", "hbm_bytes", "ici_bytes")
UTIL_KEYS = ("mfu_pct", "hbm_util_pct", "ici_util_pct")
_PEAK_BY_COMPONENT = {
    "flops": "peak_flops",
    "hbm_bytes": "peak_hbm",
    "ici_bytes": "peak_ici",
}
_BOUND_BY_COMPONENT = {"flops": "compute", "hbm_bytes": "hbm",
                       "ici_bytes": "ici"}


def chip_peaks(chip=None, *, platform: str = "modeled") -> dict:
    """``{chip, peak_flops, peak_hbm, peak_ici}`` from a
    :class:`~mpit_tpu.utils.profiling.ChipSpec`. With no ``chip``:
    ``platform="tpu"`` means a utilization of the attached device is
    about to be computed, so its peaks come from the one table keyed by
    ``device_kind`` and an unknown device raises; any other platform
    gets the TPU v5e spec as the *modeled* chip (those runs report no
    percentages). Imports are lazy: this module costs nothing at
    import."""
    if chip is None and platform == "tpu":
        import jax

        from mpit_tpu.utils.profiling import chip_spec_for

        chip = chip_spec_for(jax.devices()[0].device_kind)
    elif chip is None:
        from mpit_tpu.utils.profiling import TPU_V5E as chip
    return {
        "chip": chip.name,
        "peak_flops": float(chip.peak_flops_bf16),
        "peak_hbm": float(chip.hbm_bandwidth),
        "peak_ici": float(chip.ici_bandwidth),
    }


# ---------------------------------------------------------------------------
# Cost extraction (the only functions here that touch jax — lazily).
# ---------------------------------------------------------------------------


def cost_properties(compiled) -> Mapping:
    """A compiled executable's ``cost_analysis()`` properties dict,
    envelope-normalized: backends disagree on the wrapper (the CPU
    backend returns a single-element LIST around the dict) — this is
    the ONE place that quirk is handled; ``utils.profiling.
    compiled_cost`` shares it, so the next backend quirk cannot be
    fixed in one copy and missed in the other. ``{}`` when the backend
    reports nothing."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        cost = None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    return cost if isinstance(cost, Mapping) else {}


def cost_from_compiled(compiled) -> dict:
    """``{flops, hbm_bytes}`` from :func:`cost_properties` — absent
    keys become 0.0, never a guess."""
    cost = cost_properties(compiled)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "hbm_bytes": float(cost.get("bytes accessed", 0.0)),
    }


def cost_from_fn(fn, *args, **kwargs) -> dict:
    """Lower + compile ``fn`` (jitted or plain) for ``args`` and return
    :func:`cost_from_compiled`'s dict. This is an EXTRA XLA compile of
    the same HLO the jit cache already holds (there is no public way to
    reach the cached executable); callers pay it once, at registration
    — bench's persistent compile cache makes the replay cheap."""
    import jax

    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    return cost_from_compiled(fn.lower(*args, **kwargs).compile())


# ---------------------------------------------------------------------------
# Registration + accumulation (thin wrappers over the Recorder).
# ---------------------------------------------------------------------------


def register_cost(
    phase: str,
    *,
    flops: float = 0.0,
    hbm_bytes: float = 0.0,
    ici_bytes: float = 0.0,
    platform: str,
    chip=None,
    source: str = "cost_analysis",
) -> None:
    """Register a phase's per-execution modeled work with the calling
    thread's recorder (no-op when obs is disabled). ``platform`` is
    REQUIRED — it is what gates utilization verdicts to real-chip runs,
    so the caller must state where the numbers were recorded."""
    rec = _core.get_recorder()
    if rec is None:
        return
    rec.add_cost(
        phase,
        {
            "flops": float(flops),
            "hbm_bytes": float(hbm_bytes),
            "ici_bytes": float(ici_bytes),
            "platform": str(platform),
            "source": source,
            **chip_peaks(chip, platform=platform),
        },
    )


def work(
    phase: str,
    *,
    flops: float | None = None,
    hbm_bytes: float | None = None,
    ici_bytes: float | None = None,
    n: int = 1,
) -> None:
    """Accumulate EXPLICIT achieved work for a phase. A component fed
    here (even once) switches that component's roll-up from
    ``executions × per-exec modeled`` to the explicit sum — the
    length-aware path for work the padded model over-counts."""
    rec = _core.get_recorder()
    if rec is None:
        return
    rec.add_work(phase, flops=flops, hbm_bytes=hbm_bytes,
                 ici_bytes=ici_bytes, n=n)


# ---------------------------------------------------------------------------
# Roll-up (pure; called by Recorder.summary via lazy import).
# ---------------------------------------------------------------------------


def utilization(
    achieved: Mapping[str, float],
    seconds: float,
    *,
    platform: str,
    peaks: Mapping[str, float],
) -> dict:
    """Achieved rates + (on-chip only) utilization percentages and the
    modeled binding-resource verdict for one phase."""
    out: dict[str, Any] = {}
    if seconds > 0:
        out["achieved_gflops_per_s"] = round(
            achieved.get("flops", 0.0) / seconds / 1e9, 3
        )
        out["achieved_hbm_gbps"] = round(
            achieved.get("hbm_bytes", 0.0) / seconds / 1e9, 3
        )
        if achieved.get("ici_bytes"):
            out["achieved_ici_gbps"] = round(
                achieved["ici_bytes"] / seconds / 1e9, 3
            )
    # Binding resource at peak, from the WORK model alone (time-free:
    # t_x = achieved_x / peak_x) — modeled, so honest on any platform.
    times = {
        comp: achieved.get(comp, 0.0) / peaks[_PEAK_BY_COMPONENT[comp]]
        for comp in _COMPONENTS
        if achieved.get(comp, 0.0) > 0
    }
    if times:
        out["bound_modeled"] = _BOUND_BY_COMPONENT[
            max(times, key=times.get)
        ]
    if platform != "tpu" or seconds <= 0:
        # Measured seconds on a host that is not the chip: recording a
        # percentage of TPU peak would be fabricated. The platform label
        # IS the verdict here.
        return out
    out["mfu_pct"] = round(
        100.0 * achieved.get("flops", 0.0) / seconds / peaks["peak_flops"],
        2,
    )
    out["hbm_util_pct"] = round(
        100.0 * achieved.get("hbm_bytes", 0.0) / seconds / peaks["peak_hbm"],
        2,
    )
    if achieved.get("ici_bytes"):
        out["ici_util_pct"] = round(
            100.0 * achieved["ici_bytes"] / seconds / peaks["peak_ici"], 2
        )
    return out


def rollup(
    costs: Mapping[str, Mapping],
    work_acc: Mapping[str, Mapping],
    phases: Mapping[str, Mapping],
    overlay_seconds: Mapping[str, float] | None = None,
) -> dict:
    """The summary's ``roofline`` section: for every registered phase,
    achieved work (explicit where fed, else span count × per-exec
    modeled) against its measured span seconds. Pure function of the
    recorder snapshot, so the offline/baseline paths can reuse it.

    ``overlay_seconds`` maps a phase to time its spans covered that was
    NOT steady-state execution — the ``compile`` overlay spans a
    phase's first call absorbs (the Recorder passes them, keyed by the
    compile span's ``phase`` attr). That time is excluded from the
    utilization denominator: a cold run would otherwise understate
    utilization vs a warm one and make the ``obs diff`` gate trip on
    compile-cache state instead of real regressions (the excluded
    amount is recorded as ``compile_seconds_excluded``)."""
    overlay_seconds = overlay_seconds or {}
    out_phases: dict[str, dict] = {}
    for phase, cost in sorted(costs.items()):
        ph = phases.get(phase, {})
        w = work_acc.get(phase, {})
        explicit = set(w.get("explicit", ()))
        execs = int(ph.get("count", 0)) or int(w.get("n", 0))
        overlay = float(overlay_seconds.get(phase, 0.0))
        seconds = max(float(ph.get("total_s", 0.0)) - overlay, 0.0)
        achieved = {}
        for comp in _COMPONENTS:
            if comp in explicit:
                achieved[comp] = float(w.get(comp, 0.0))
            else:
                achieved[comp] = execs * float(cost.get(comp, 0.0))
        entry: dict[str, Any] = {
            "executions": execs,
            "seconds": round(seconds, 6),
            "platform": cost.get("platform", "unknown"),
            "chip": cost.get("chip"),
            "modeled_flops_per_exec": cost.get("flops", 0.0),
            "modeled_hbm_bytes_per_exec": cost.get("hbm_bytes", 0.0),
        }
        if cost.get("ici_bytes"):
            entry["modeled_ici_bytes_per_exec"] = cost["ici_bytes"]
        for comp in _COMPONENTS:
            if achieved[comp]:
                entry[f"achieved_{comp}"] = achieved[comp]
        if explicit:
            # Which components came from length-aware measurement
            # instead of count × modeled (the honesty label).
            entry["explicit_components"] = sorted(explicit)
        if overlay:
            entry["compile_seconds_excluded"] = round(overlay, 6)
        entry.update(
            utilization(
                achieved, seconds,
                platform=entry["platform"], peaks=cost,
            )
        )
        out_phases[phase] = entry
    return {"phases": out_phases}


# ---------------------------------------------------------------------------
# Flash-decode achieved bytes (the length-aware correction).
# ---------------------------------------------------------------------------


def kv_tile_read_bytes(
    visited_tiles: float, *, block_k: int, kv_row_bytes: float,
    num_layers: int,
) -> float:
    """HBM bytes the flash-decode k-loop reads for ``visited_tiles``
    total visited tiles (summed over slots, ONE layer's tile count —
    every layer visits the same tiles, so the layer factor rides here):
    a K tile and a V tile of ``block_k`` rows each. Tiles the kernel
    skips are never DMA'd (``ops/decode_attention.py``), which is why
    this — not the padded ``cost_analysis`` buffer size — is the honest
    achieved-bytes figure."""
    return 2.0 * float(visited_tiles) * block_k * kv_row_bytes * num_layers


def decode_step_hbm_bytes(
    visited_tiles: float,
    *,
    block_k: int,
    kv_row_bytes: float,
    num_layers: int,
    param_bytes: float = 0.0,
    appended_rows: int = 0,
) -> float:
    """Modeled HBM traffic of ONE decode tick on the length-aware
    kernel path: every weight read once (T=1 decode re-streams the full
    param tree), the visited K/V tiles, and the K/V rows appended for
    the active slots. Activations/logits are excluded — at T=1 with the
    blocked head they are orders of magnitude below the param read."""
    return (
        float(param_bytes)
        + kv_tile_read_bytes(
            visited_tiles, block_k=block_k, kv_row_bytes=kv_row_bytes,
            num_layers=num_layers,
        )
        + 2.0 * appended_rows * kv_row_bytes * num_layers
    )


# ---------------------------------------------------------------------------
# Compile observability.
# ---------------------------------------------------------------------------


class CompileWatch:
    """Counts the XLA compiles of watched step calls and pins a
    lifetime expectation.

    ``call(phase, fn, *args)`` is the seam that says WHICH engine and
    phase a compile belongs to. Detection is JAX's own events
    (``obs.startup``'s listener): a ``backend_compile`` received in the
    calling thread while the call is open. Nothing is probed before or
    after a call that does not compile. On a compile the start-up
    record (and an enabled recorder) get a ``compile`` span from the
    call's start to its output ready (an OVERLAY of the triggering
    phase's own span, see ``obs.core._OVERLAY_PHASES``) with ``phase``,
    ``scope``, ``module`` (``jit_<name>`` of the executable) and the
    caller's ``attrs``; its children are JAX's ``jit_trace`` /
    ``jit_lower`` / ``backend_compile`` events and ``first_run`` (the
    last compile's end to the output ready: the call waits for it, once,
    only when it compiled). A ``compiles`` counter and the
    ``<scope>_compiles`` gauge (the pinned engine-lifetime metric)
    follow. A compile past ``expected`` additionally emits an
    ``unexpected_recompile`` instant with the function's name and, when
    a sentinel is attached, lands in its anomaly report: the runtime
    guard on "N compiles, zero per-request recompiles" claims.
    ``pinned=False`` records the span of a helper's compile
    (``chunk_rows``) without counting it against the pin.

    No callable is known that compiles without firing the events, so
    there is no second detector; a callable that never reaches JAX
    reads zero compiles.
    """

    def __init__(self, *, expected: int | None = None,
                 scope: str = "engine", sentinel=None):
        _startup.install()  # the listeners, once a process
        self.expected = expected
        self.scope = scope
        self.sentinel = sentinel
        self.compiles = 0
        self.unexpected = 0
        self.events: list[dict] = []

    def call(self, phase: str, fn, *args, pinned: bool = True, **attrs):
        """Invoke ``fn(*args)``, recording a compile event if JAX
        compiled in this thread meanwhile."""
        with _startup.Watch() as watch:
            out = fn(*args)
        if watch.compiled:
            done = watch.close(out, phase=phase, scope=self.scope, **attrs)
            if pinned:
                self.on_compile(phase, *done)
        return out

    def on_compile(self, phase: str, t0: float, t1: float,
                   fun: str = "") -> None:
        self.compiles += 1
        unexpected = (
            self.expected is not None and self.compiles > self.expected
        )
        _core.counter("compiles")
        _core.gauge(f"{self.scope}_compiles", float(self.compiles))
        event = {
            "phase": phase,
            "fun": fun,
            "seconds": round(t1 - t0, 6),
            "count": self.compiles,
            "unexpected": unexpected,
        }
        self.events.append(event)
        if unexpected:
            self.unexpected += 1
            if self.sentinel is not None:
                # note() emits the structured ``anomaly`` instant too.
                self.sentinel.note(
                    "unexpected_recompile", phase, self.compiles,
                    expected=self.expected, scope=self.scope, fun=fun,
                )
            else:
                _core.instant(
                    "unexpected_recompile", phase=phase, scope=self.scope,
                    count=self.compiles, expected=self.expected, fun=fun,
                )


class UtilizationWatch:
    """Sustained utilization collapse: a throughput/utilization series
    (GB/s, MFU %, tokens/s — any higher-is-better rate) dropping below
    ``drop_ratio`` × its rolling median for ``sustained_n`` consecutive
    observations. The duration sentinels can miss this (a tick that
    stays fast while doing half the work looks healthy by wall clock);
    this rule watches the work rate itself. Collapsed values are kept
    OUT of the baseline until an alert fires, then fed in — so a
    permanent step-change alerts a bounded number of times and the
    baseline adapts, mirroring the Sentinel's excursion policy."""

    def __init__(self, *, window: int = 32, warmup: int = 8,
                 drop_ratio: float = 0.5, sustained_n: int = 5,
                 sentinel=None):
        self.window = max(2, window)
        self.warmup = max(2, warmup)
        self.drop_ratio = drop_ratio
        self.sustained_n = max(1, sustained_n)
        self.sentinel = sentinel
        self._windows: dict[str, deque] = {}
        self._streaks: dict[str, int] = {}
        self._counts: dict[str, int] = {}
        self.alerts: list[dict] = []

    def observe(self, metric: str, tick: int, value: float) -> None:
        win = self._windows.get(metric)
        if win is None:
            win = self._windows[metric] = deque(maxlen=self.window)
        self._counts[metric] = self._counts.get(metric, 0) + 1
        if self._counts[metric] <= self.warmup:
            win.append(value)
            return
        med = statistics.median(win)
        if med > 0 and value < self.drop_ratio * med:
            streak = self._streaks.get(metric, 0) + 1
            self._streaks[metric] = streak
            if streak >= self.sustained_n:
                self._streaks[metric] = 0
                win.append(value)  # adapt: a durable collapse re-alerts
                # a bounded number of times, then becomes the baseline.
                alert = {
                    "kind": "utilization_collapse",
                    "metric": metric,
                    "tick": int(tick),
                    "value": round(value, 6),
                    "median": round(med, 6),
                    "consecutive": self.sustained_n,
                }
                self.alerts.append(alert)
                if self.sentinel is not None:
                    self.sentinel.note(
                        "utilization_collapse", metric, tick,
                        value=value, median=med,
                    )
                else:
                    _core.instant("anomaly", **alert)
            return
        self._streaks[metric] = 0
        win.append(value)
