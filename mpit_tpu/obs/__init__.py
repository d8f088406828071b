"""mpit_tpu.obs — unified runtime telemetry: spans, counters, exporters.

The reference's observability is per-rank ``print()`` timers (SURVEY.md
§6); this repo grew better pieces (``utils.profiling.StepTimer``/
``CommModel``, ``train.metrics.MetricLogger``) but nothing that records
*where a step's wall time goes* or attributes comm traffic to individual
operations. This package is that layer:

- :func:`span` — a context manager timing a named phase, with near-zero
  overhead when disabled (a shared no-op object, no allocation beyond
  the call itself);
- :func:`counter` / :func:`gauge` — monotonic accumulators and
  last-value gauges, keyed by name + attributes (thread-safe);
- a process-global :class:`~mpit_tpu.obs.core.Recorder` buffering
  events in memory; :func:`enable` / :func:`disable` install/remove it;
- exporters: :func:`export_chrome_trace` (Chrome-trace/Perfetto JSON,
  loadable in ``ui.perfetto.dev`` — complementing the XPlane capture of
  ``utils.profiling.trace``) and :func:`export_jsonl` (one record per
  event, written through ``MetricLogger`` so the record shape is
  literally the metrics-stream shape);
- :func:`summary` — rolls spans into ``{phase: {count, total_s, p50_s,
  p95_s}}`` plus the top-N collectives by modeled wire bytes;
- :func:`traffic_matrix` — the rank×rank P2P byte matrix accumulated by
  the :mod:`mpit_tpu.compat` simulator for parity runs.

ISSUE 3 grows the recorder distributed, plus an automated verdict pair:

- :mod:`~mpit_tpu.obs.aggregate` — the cross-rank flight recorder:
  per-rank recorders (:func:`local_recorder` thread-local override for
  simulator rank threads) gathered to rank 0 over compat Send/Recv or
  ``World.gather_host_bytes``; ONE merged Chrome trace with a Perfetto
  lane per rank, a per-phase skew report naming the straggler, and a
  measured rank×rank P2P matrix reconciled against the modeled one;
- :class:`Sentinel` (:mod:`~mpit_tpu.obs.sentinel`) — the step-time
  anomaly detector ``hardened_loop`` wires in behind ``sentinel=`` /
  ``--sentinel true``: rolling median/MAD over step wall / prefetch
  wait / host fences, structured ``anomaly`` instants, run-end report;
- :mod:`~mpit_tpu.obs.baseline` — per-phase perf snapshots and the
  regression gate behind ``python -m mpit_tpu.obs diff`` (non-zero exit
  on phase-time regressions beyond ``--tolerance-pct``); ``bench.py``
  writes one per workload into ``BENCH_DETAIL.json``.

ISSUE 6 adds the STREAMING layer for sustained serving runs, where the
Recorder's retained-event model breaks down (``max_events`` exhausts
and percentiles silently cover a truncated prefix — which
``summary()``/the exporters now surface via ``dropped_events``):

- :mod:`~mpit_tpu.obs.stream` — bounded-memory telemetry: a mergeable
  log-bucketed :class:`HistogramSketch` (~1% relative quantile error,
  O(buckets) memory), rolling-window histograms/rates/gauges behind a
  :class:`StreamRegistry` the serve path feeds per request/tick;
- :mod:`~mpit_tpu.obs.slo` — declarative :class:`SLO` targets (p95
  TTFT ≤ X, shed-rate ≤ Z) evaluated over those windows by an
  :class:`SLOMonitor`: ``slo_breach``/``slo_recovered`` instants in
  the trace, breaches fed to the Sentinel, time-in-breach and
  time-to-detect in the roll-up.

ISSUE 8 adds the UTILIZATION layer (:mod:`~mpit_tpu.obs.roofline`):
jitted executables register their ``cost_analysis()`` FLOPs/bytes once
at compile, span closes accumulate achieved work (length-aware for the
tile-skipping flash-decode kernel), and ``summary()`` reports per-phase
``mfu_pct`` / ``hbm_util_pct`` / ``ici_util_pct`` against the ChipSpec
roofline peaks — percentages only on the real chip, platform-labeled
modeled cost everywhere else. Compile observability rides along:
``compile`` spans + counters at every detected lower/compile
(``CompileWatch``), a pinned engine-lifetime compile count, and
sentinel rules for unexpected recompiles and sustained utilization
collapse (``UtilizationWatch``); ``obs diff`` gates on utilization keys
and refuses comparisons whose baseline phases disappeared.

ISSUE 16 adds the REQUEST-FORENSICS layer (:mod:`~mpit_tpu.obs.trace`):
a per-request lifecycle :class:`Ledger` accruing typed causal events at
every serve decision seam (admission verdict with its projection
inputs, slot bind, prefill chunks, decode-tick membership, COW copies,
preemption park/resume, spec draft/accept, retire reason), bounded by
tail-exemplar sampling — aggregate counters always on, full ledgers
kept only for the slowest-k per SLO window, breach/anomaly-pinned
(``Sentinel(on_note=...)``) and errored/truncated requests. A retained
exemplar decomposes its latency into queue-wait / prefill / decode /
parked / scheduler-gap components that reconcile against the
``request_latency`` span; ``python -m mpit_tpu.obs why-slow`` prints
the worst lifeline, and :class:`TraceContext` serializes over compat
Send/Recv (dedicated tags, byte-identical) for the future
disaggregated-fleet router.

ISSUE 18 adds the MEMORY layer (:mod:`~mpit_tpu.obs.memledger`):
a byte-exact device-memory ledger every HBM-holding serve subsystem
registers with — weight store (int8 q + scale blocks at wire width),
KV page pool (per-page grant/free/COW-reserve lifecycle), draft
engine, step buffers — so ``ledger.held()`` decomposes total HBM into
attributed components and ``grants − frees == held`` holds exactly.
Headroom/watermark/fragmentation gauges feed the stream registry,
pool-exhaustion edges dump a ranked top-holders table, eviction
candidates (parked victims / idle tails / sole-reader prefixes) are
ranked by last-touch tick for the tiering hand-off, and ``python -m
mpit_tpu.obs capacity`` prints the offline verdict — on-TPU reconciled
against ``device.memory_stats()``, off-TPU platform-labeled modeled
bytes (never fabricated device numbers).

ISSUE 36 adds the START-UP layer (:mod:`~mpit_tpu.obs.startup`): an
always-on, bounded record of what a process did before its first tick
or step — the program's start-up boundaries (``engine_build``,
``cache_alloc``, ``warmup``, ``state_init``, ``cost_query``) and JAX's
own compile events as ``jit_trace`` / ``jit_lower`` /
``backend_compile`` spans named by executable, with the persistent
cache's verdict on each — mirrored into a recorder where one is
enabled. ``CompileWatch`` detects by the same events;
``startup.report()`` is the CLIs' ``ready`` line and
``Server.stats()["startup"]``; a compile after ``ready`` is a
``compile_after_ready`` instant that names its function.

Instrumented call sites: ``train.loop.hardened_loop`` (prefetch-wait /
step / host-fence / eval / checkpoint / divergence-restore phases),
``comm.collectives`` (per-op modeled wire bytes — recorded at *trace*
time, when the collective's Python wrapper runs), ``compat.simulator``
(per-rank send/recv bytes), ``asyncsgd.actors`` (protocol message
counts), and ``bench.py`` (per-workload phase breakdown in
``BENCH_DETAIL.json``).

Everything is import-light: nothing here touches jax, so the disabled
fast path costs a module-global check and the package can be imported
from anywhere in the stack without cycles.
"""

from mpit_tpu.obs import (
    aggregate,
    baseline,
    memledger,
    roofline,
    slo,
    startup,
    stream,
    trace,
)
from mpit_tpu.obs.core import (
    Recorder,
    counter,
    disable,
    enable,
    enabled,
    gap_attribution,
    gauge,
    get_recorder,
    instant,
    local_recorder,
    span,
    span_at,
    summary,
)
from mpit_tpu.obs.export import (
    export_chrome_trace,
    export_jsonl,
    snapshot_trace_events,
    traffic_matrix,
)
from mpit_tpu.obs.memledger import MemLedger
from mpit_tpu.obs.sentinel import Sentinel
from mpit_tpu.obs.slo import SLO, SLOMonitor
from mpit_tpu.obs.stream import HistogramSketch, StreamRegistry
from mpit_tpu.obs.trace import Ledger, TraceContext

__all__ = [
    "HistogramSketch",
    "Ledger",
    "MemLedger",
    "Recorder",
    "SLO",
    "SLOMonitor",
    "Sentinel",
    "StreamRegistry",
    "TraceContext",
    "aggregate",
    "baseline",
    "counter",
    "disable",
    "enable",
    "enabled",
    "export_chrome_trace",
    "export_jsonl",
    "gap_attribution",
    "gauge",
    "get_recorder",
    "instant",
    "local_recorder",
    "memledger",
    "roofline",
    "slo",
    "snapshot_trace_events",
    "span",
    "span_at",
    "startup",
    "stream",
    "summary",
    "trace",
    "traffic_matrix",
]
