"""Continuous batching: the request loop over the slot-batched engine.

The reference's pserver is a tag-dispatched request-serving loop
(SURVEY.md §3.2 A1) — receive, act, reply, forever. This is that
capability rebuilt for inference: requests queue on the host, are
admitted into freed KV-cache slots BETWEEN decode ticks (no tick waits
for a full batch — a new request rides the next prefill while everyone
else keeps decoding), and retire per-slot on EOS / max-new-tokens /
cache-full, freeing the slot for the next queue entry immediately.

Observability (``mpit_tpu.obs``) is first-class, not bolted on:

- spans: ``prefill`` (per admission batch) and ``decode`` (per tick) —
  each enqueues this tick's step and closes on the host fetch of the
  LAST tick's tokens (one tick of steps is kept in flight, ISSUE 29:
  :meth:`Server._run_tick`), so with a step always queued their wall
  clock is the device's period. They are two nodes of a tree, nested by
  time on the loop's thread: ``tick`` round every iteration, ``admit``,
  ``prefill``, ``gauges``, ``decode`` and ``retire`` inside it in the
  order they run, and the engine's ``decode_dispatch`` /
  ``decode_fetch`` (``prefill_*`` likewise) inside the two. No argument
  of a span is computed when no recorder is installed;
- per-request intervals recorded with explicit timestamps
  (``obs.span_at``): ``queue_wait`` (submit → admit), ``request_ttft``
  (submit → first token) and ``request_latency`` (submit → retire) —
  the summary's per-phase p50/p95 roll-up then IS the latency/TTFT
  histogram, and the Chrome trace shows every request as a bar;
- ``slot_occupancy`` gauge + ``serve_tokens``/``serve_requests``
  counters each tick; ``serve_steps_overlapped`` (a step enqueued while
  an older one was unfetched) and ``serve_steps_drained`` (a fetch with
  nothing enqueued behind it) say how often the overlap engaged;
  ``serve_steps_greedy_head`` / ``serve_steps_sampled_head`` count the
  steps enqueued by what their temperatures asked of the sampler (no
  row samples: the blocked head takes one max a block; any row does:
  noise, sort and candidate merge), and the ``prefill`` / ``decode``
  span that enqueues a step says which in ``sampler_path``.

An optional :class:`mpit_tpu.obs.Sentinel` (``phases=("decode",
"prefill")``) watches the tick stream for spikes/sustained degradation
— the serving analogue of the training loop's step-wall sentinel.

ISSUE 6 grows the loop production-shaped:

- **Streaming telemetry**: ``Server(stream=StreamRegistry())`` feeds
  per-request TTFT/latency/queue-wait into rolling-window histogram
  sketches and per-tick token/arrival rates + queue/occupancy gauges —
  live percentiles over the last N seconds at O(buckets) memory, so a
  sustained run's telemetry never depends on the Recorder's bounded
  event buffer (``obs.stream``).
- **SLO monitoring**: ``Server(slo=SLOMonitor(...))`` evaluates
  declared targets (p95 TTFT ≤ X, shed-rate ≤ Z, ...) against those
  windows once per tick; breach transitions emit ``slo_breach`` /
  ``slo_recovered`` instants and feed the sentinel (``obs.slo``).
- **Timed drive**: :meth:`Server.run_timed` admits an OPEN-loop
  arrival trace (``serve.loadgen``) by its arrival clock — requests
  are submitted when due, never up front, so offered load is a
  property of the trace, not of how fast the server drains.
- **Request lifelines**: per-request spans carry ``rid`` (and
  ``tenant`` when set) and batch spans carry ``rids``, so one
  request's queue-wait → prefill → decode path is filterable in the
  Perfetto export.
- **Bounded intake**: ``Server(max_queue=N)`` sheds arrivals beyond N
  queued (counted in ``serve_shed`` / ``Server.shed`` — the shed-rate
  SLO's numerator); unbounded by default.

ISSUE 8 (roofline): every decode tick feeds the LENGTH-AWARE achieved
HBM bytes — the engine's visited-tile model, pinned against the
kernel's own in-kernel count — into the recorder's work accounting
(``obs.roofline.work``), the rolling stream windows
(``decode_hbm_bytes`` / ``decode_flops`` rates → the CLI's
``hbmbw=``/``mfu=`` fields) and a sustained-collapse watch; the
engine's CompileWatch is wired to this server's sentinel, so an
unexpected mid-service recompile and a collapsing work rate land in
the same anomaly report as tick-duration spikes. ``stats()`` carries
``engine_compiles`` (the pinned lifetime count) and
``decode_hbm_bytes_modeled``.

Admission is a PAGE grant, not just a slot grant — the head of the
queue gets a free slot plus its whole page requirement (fresh pages + shared-prefix mappings + COW reserve,
all-or-nothing) or waits; prompts feed the device ``prefill_chunk``
tokens per tick interleaved with decode (``prefilling`` state — a long
admit cannot head-of-line-block TTFT for live slots), and one chunk more
for every seat the tick's step would otherwise compute for nobody
(``Engine.spare_seats``, :meth:`Server._stage_chunk`); a finished prompt
is registered in the allocator's prefix index so later identical
prefixes map the same pages (refcounted, copy-on-write on divergence —
the scheduler calls ``cow_before_write`` before every prefill-chunk /
decode write and runs the device page copy it returns); retirement
frees the slot's pages back to the pool. ``kv_tokens_cached`` /
``kv_pool_occupancy`` / ``prefix_pages_shared`` gauges land in the
Recorder and the stream windows each tick.

ISSUE 12 (scheduling policy): ``Server(policy=SchedulingPolicy(...))``
replaces the FIFO deque with the policy tier (``serve.policy``) —
priority-ordered tenant-fair queues consulted at every admit boundary,
projected-TTFT admission shedding at submit (``shed_admission``,
distinct from ``max_queue``'s ``shed_queue_full`` in every counter /
instant / stats key), and preemption: when the
best queued tier's head is projected to miss its TTFT target and
nothing frees, a lower-tier live generation is PARKED — pages freed
back to the allocator, generated-so-far tokens kept host-side — and
later resumed through the normal chunked-prefill path with
``feed = prompt + tokens`` (the resume prefill recomputes exactly the
decode tick the eviction displaced, so a preempted-then-resumed greedy
request bit-matches its un-preempted output — test-pinned). The
policy's projector reads ``prefill_tick`` / ``decode_tick`` rolling
windows this server feeds once per tick; per-tier TTFT series
(``request_ttft_tier<p>``) and per-tenant series
(``request_ttft_tenant:<t>``) land in the registry so SLOs and the
``stats()`` tenant roll-up can tell the classes apart. Without a
policy every path below is byte-for-byte the FIFO scheduler.

ISSUE 13 (speculative decoding): on an ``Engine(spec_k=k, ...)`` the
decode tick becomes :meth:`Server._spec_tick` — draft ``k`` tokens per
live slot, verify all ``k+1`` positions in one target pass, append each
slot's emitted prefix and retire exactly as the plain tick would (EOS /
token budget are clamped IN-STEP, so device lengths and the host token
lists never diverge). The tick is spanned ``decode`` with nested
``spec_draft`` / ``spec_verify`` spans (the ``attention=`` idiom on all
three); ``accepted_tokens_per_tick`` (emitted per slot-tick, 1.0 =
plain decode) and ``draft_acceptance_rate`` feed the rolling windows
and ``stats()`` — the ``gpt2_serve`` record line carries the former.
The verify writes ``k+1`` rows at the fill; rows past a slot's mapped
pages are scatter-DROPPED, so a request needs no headroom for them.
"""

from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from typing import Any

import numpy as np

from mpit_tpu import obs
from mpit_tpu.ops.decode_attention import num_kv_blocks

__all__ = ["Request", "Completed", "Server", "warm_engine"]


def warm_engine(engine, *, register_costs: bool = False) -> None:
    """Pay the engine's lifetime XLA compiles (prefill + decode — or
    prefill + spec_draft + spec_verify on a speculative engine) with
    one throwaway request, then reset the cache — call BEFORE any timed
    window so an open-loop harness's first arrivals measure the server,
    not the compiler. Prompt content is irrelevant: the padded
    prefill/decode buffers fix the traced shapes.

    The whole warm run is the ``warmup`` span of the start-up record
    (``obs.startup``; in an enabled recorder too: warmup time is
    attributed, not a silent gap in the trace). Every step call in which
    JAX compiled lands inside it as a ``compile`` span (``phase``,
    ``scope``, ``module`` and, for a compacted chunk step, ``count``)
    whose children are JAX's own ``jit_trace`` / ``jit_lower`` /
    ``backend_compile`` events, named by executable, and ``first_run``
    (the compile's end to the call's output ready: the step's first
    execution), beside the ``engine_compiles`` gauge, via the engine's
    CompileWatch. ``register_costs=True`` additionally registers the
    steps' ``cost_analysis()`` costs with the recorder
    (:meth:`~mpit_tpu.serve.engine.Engine.register_roofline`): opt-in
    because it lowers and compiles each step a second time for the cost
    query, which shows as one ``cost_query`` span a step; bench and the
    serve CLI pass it, parity tests don't pay it. The warm-up's end
    makes the engine ``ready`` (``obs.startup.ready("engine")``)."""
    with obs.startup.span("warmup"):
        warm = Server(engine)
        warm.submit(Request(rid="warm", prompt=[1, 2, 3], max_new_tokens=2))
        warm.run()
        # The COW device copy is its own (tiny) compile — a lone
        # warm request never diverges from a shared page, so pay it
        # here or the first real divergence pays it inside the
        # timed window.
        engine.copy_page(0, 0)
        if engine._prefill_counts:
            # A compacted chunk tick has one step a count of
            # participants; the warm request met the first only.
            engine.warm_prefill_counts()
        if engine.host_pages:
            # The host tier's gather/scatter pair likewise: pay
            # both compiles with a page-0 round trip (restore
            # rewrites exactly what spill read — a semantic no-op).
            engine.spill_page(0, 0)
            engine.drain_spills()
            engine.restore_page(0, 0, release=True, kind="warm")
        if register_costs:
            engine.register_roofline()
    engine.reset()
    obs.startup.ready("engine")


@dataclasses.dataclass
class Request:
    """One generation request. ``temperature <= 0`` = greedy;
    ``top_k = 0`` = full vocab; ``eos_id = None`` = never stop early;
    ``tenant`` labels the requester (multi-tenant load traces) and is
    stamped on the request's spans when non-empty. ``priority`` is the
    scheduling-policy tier (0 = highest / interactive; ignored by the
    FIFO scheduler) and ``ttft_target_s`` the per-request TTFT SLO the
    policy's admission/preemption decisions are made against (<= 0 =
    no target)."""

    rid: Any
    prompt: list[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    eos_id: int | None = None
    tenant: str = ""
    priority: int = 0
    ttft_target_s: float = 0.0


@dataclasses.dataclass
class Completed:
    """A finished request: output + the latency facts the histograms
    aggregate. ``tokens`` includes the EOS token when one stopped it."""

    rid: Any
    prompt: list[int]
    tokens: list[int]
    submit_t: float
    first_token_t: float
    finish_t: float
    truncated: bool = False  # retired by cache-full, not EOS/max-tokens
    tenant: str = ""

    @property
    def ttft_s(self) -> float:
        return self.first_token_t - self.submit_t

    @property
    def latency_s(self) -> float:
        return self.finish_t - self.submit_t


@dataclasses.dataclass
class _Live:
    req: Request
    submit_t: float
    first_token_t: float = 0.0
    tokens: list = dataclasses.field(default_factory=list)
    # Prefill state: ``base`` = prompt tokens
    # already cached (advanced per chunk), ``floor`` = the shared-prefix
    # write floor granted at admission (positions below it live in
    # immutable shared pages).
    base: int = 0
    floor: int = 0
    # Preemption state (ISSUE 12): ``feed`` = the token sequence to
    # (re-)prefill — ``None`` until a preemption parks the request, then
    # prompt + generated-so-far tokens (the resume prefill's last row IS
    # the decode tick the eviction displaced, which is what makes the
    # resumed greedy output bit-match). ``preempts`` bounds thrash.
    feed: list | None = None
    preempts: int = 0
    # Memory-ledger recency (ISSUE 18): the last tick this request's
    # cache bytes were touched (bind / prefill chunk / decode emit) and
    # the tick a preemption parked it — what the eviction-candidate
    # ranking orders by (coldest first).
    last_touch: int = 0
    park_tick: int = 0
    # Host-tier resume telemetry (ISSUE 20): how the last resume
    # rebuilt this slot's cache ("restream" from parked host pages /
    # "recompute" through chunked re-prefill) and when it was
    # re-admitted — cleared once the resume completes (first
    # post-resume token), closing the per-mode duration sample.
    resume_mode: str = ""
    resume_t: float = 0.0
    # One tick of steps in flight (ISSUE 29): ``tokens`` holds the
    # values that have reached the host; ``issued`` counts the tokens
    # asked of the device so far, those of steps still in flight
    # included. Everything the host decides before a step runs (the
    # cache fill, the budget left, retirement by count) reads the count.
    issued: int = 0
    # The slot and its pages went back by count while the last tokens
    # were still in flight: the request stays in ``Server.live`` (its
    # landed tokens stay visible) until the next tick begins or its
    # ``Completed`` is written, whichever comes first.
    released: bool = False
    # ``Completed`` is written (or an EOS stopped it): whatever a step
    # still in flight computed for it is dropped.
    done: bool = False

    def feed_tokens(self) -> list:
        """What prefill feeds the device: the prompt, or the resume
        sequence after a preemption."""
        return self.feed if self.feed is not None else self.req.prompt

    def remaining_new(self) -> int:
        """Output tokens still owed — the page requirement's generation
        term (full ``max_new_tokens`` before the first token; the
        resume admission re-plans with the already-generated tokens
        moved into the feed, so the page watermark is unchanged)."""
        return self.req.max_new_tokens - self.issued

    def cache_fill(self) -> int:
        """Host mirror of the device cache fill for a LIVE slot — THE
        single fill-accounting path (ISSUE 7 satellite: retirement, the
        tile-skip counter, COW write positions and the kv gauges all
        read this; two drifting copies would silently corrupt tile
        skipping). Prefill cached the prompt; each decode tick appends
        ONE token; the newest sampled token is NOT yet written — so the
        fill is ``prompt + generated - 1``, and the next decode append
        lands exactly here. ``generated`` is the count of tokens asked
        for (``issued``): the fill after every step already enqueued,
        whether or not its token has been fetched."""
        return len(self.req.prompt) + self.issued - 1


@dataclasses.dataclass
class _Step:
    """One enqueued step whose tokens are still on the device."""

    kind: str  # "prefill" | "decode": the span its fetch lies inside
    pending: Any  # what the engine's dispatch half returned
    takers: list  # (slot, _Live) pairs that get a token from it
    tick: int  # the tick that enqueued it
    lens: Any = None  # decode: every taker's cache fill before the step
    toks: Any = None  # host numpy, once fetched
    t_land: float = 0.0  # when they reached the host


class Server:
    """The continuous-batching loop around one :class:`~mpit_tpu.serve.Engine`.

    Host-side only: slot bookkeeping, the request queue, retirement and
    telemetry. ``submit()`` enqueues; ``run()`` drives admit/decode
    ticks until the queue and all slots drain (or ``max_ticks``);
    ``run_timed()`` drives an open-loop arrival trace by its clock.

    ``stream`` (a :class:`mpit_tpu.obs.stream.StreamRegistry`) receives
    the rolling-window feed — ``request_ttft`` / ``request_latency`` /
    ``queue_wait`` histograms, ``serve_arrivals`` / ``serve_completed``
    / ``serve_tokens`` / ``serve_shed`` rates, ``queue_depth`` /
    ``slot_occupancy`` gauges; ``slo`` (a
    :class:`mpit_tpu.obs.slo.SLOMonitor` over the same registry) is
    evaluated once per tick. ``max_queue`` bounds the host queue:
    arrivals beyond it are SHED (recorded, not raised — open-loop
    traffic does not stop because the server is full).
    """

    def __init__(self, engine, *, sentinel=None, stream=None, slo=None,
                 max_queue=None, policy=None, ledger=None,
                 worker_id="", role=""):
        self.engine = engine
        self.sentinel = sentinel
        self.policy = policy
        if policy is not None and policy.cfg.preempt:
            # A family whose slots keep what an eviction would drop says
            # so here, by name (``SchedulingPolicy(preempt=False)`` runs).
            engine.model.check_preemption()
        # Fleet identity (ISSUE 19): a stable stamp on stats() and the
        # memory verdict so fleet-merged stats attribute bytes/tokens
        # per worker, not per process-anonymous engine. Standalone
        # servers report the explicit singleton identity.
        self.worker_id = worker_id or "single"
        self.role = role or "standalone"
        # Request lifecycle ledger (ISSUE 16): per-request causal events
        # at every decision seam, tail-exemplar retention, why-slow
        # attribution. ``None`` skips even the guard-site calls — the
        # ledger-disabled arm of the overhead acceptance bar.
        self._ledger = ledger
        if ledger is not None and sentinel is not None:
            # Breach/anomaly joinability (ISSUE 16 satellite): the
            # sentinel's note fan-out pins the in-flight request set at
            # detection time. Chain, don't clobber — a caller-installed
            # callback keeps firing.
            prev = sentinel.on_note

            def _pin(record, _prev=prev, _ledger=ledger):
                if _prev is not None:
                    _prev(record)
                _ledger.pin_inflight(
                    record.get("kind", "anomaly"), step=record.get("step")
                )

            sentinel.on_note = _pin
        if policy is not None and stream is None:
            # The policy's projected-TTFT estimator reads rolling
            # prefill/decode tick windows — when the caller didn't wire
            # a registry, a private one keeps admission evidence-based
            # instead of silently disabled.
            from mpit_tpu.obs.stream import StreamRegistry

            stream = StreamRegistry()
        self.stream = stream
        if policy is not None:
            policy.bind_registry(stream)
        self.slo = slo
        if slo is not None and stream is None:
            raise ValueError(
                "Server(slo=...) needs the stream registry the monitor "
                "evaluates over — pass stream=slo.registry"
            )
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.max_queue = max_queue
        # The attention mode + sampler actually executing — stamped on
        # every prefill/decode span so the flight recorder / sentinel can
        # attribute a serve-path regression to a kernel fallback (ISSUE 5
        # obs satellite). Both labels matter: off-TPU "kernel" mode runs
        # reference ATTENTION but keeps the blocked SAMPLER, so
        # attention=reference alone does not identify the PR 4 path.
        self._attn_mode = engine.decode_attention_mode
        self._sampler = engine.decode_sampler
        # kv_dtype rides decode/prefill spans via the attention= idiom
        # (ISSUE 15 satellite) — but only when the engine's wire dtype
        # was EXPLICITLY chosen: default engines' spans stay
        # byte-identical to HEAD, like grad_sync='s unlabeled psum.
        self._kv_attrs = (
            {"kv_dtype": engine.kv_dtype}
            if engine.kv_dtype_explicit
            else {}
        )
        # weights_dtype rides the same spans under the same rule
        # (ISSUE 17): the int8 weight store halves the decode sweep, so
        # a why-slow trace must say which wire the tick paid for — but
        # only explicitly-chosen engines get the label.
        if engine.weights_dtype_explicit:
            self._kv_attrs = dict(
                self._kv_attrs, weights_dtype=engine.weights_dtype
            )
        # Speculative decoding (ISSUE 13): spec_k > 0 swaps the decode
        # tick for draft-then-verify; the accumulators feed stats()'s
        # accepted_tokens_per_tick / draft_acceptance_rate (what the
        # gpt2_serve record line carries).
        self._spec = engine.spec_k
        # Which form of the attention kernel each kind of step compiled
        # to and the cache rows a step of its loop takes (static per
        # compiled step; empty where no kernel runs): a trace says
        # whether the fast tiling engaged.
        # A prefill / decode span's fixed labels: the same on the span
        # that enqueues a step and on one that only fetches it.
        labels = dict(
            attention=self._attn_mode, sampler=self._sampler,
            **self._kv_attrs,
        )
        self._decode_tiling = engine.attention_tiling(self._spec + 1)
        self._labels = {
            "prefill": dict(
                labels, **engine.attention_tiling(engine.prefill_chunk)
            ),
            "decode": dict(labels, **self._decode_tiling),
        }
        self._spec_emitted = 0
        self._spec_active_ticks = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        # Compile + utilization sentinel rules (ISSUE 8): an unexpected
        # engine recompile and a sustained collapse of the decode HBM
        # rate both land in THIS server's sentinel report, next to the
        # tick-duration findings.
        if sentinel is not None:
            engine.compile_watch.sentinel = sentinel
        self._util_watch = (
            obs.roofline.UtilizationWatch(sentinel=sentinel)
            if sentinel is not None
            else None
        )
        self._decode_hbm_bytes = 0.0  # length-aware modeled bytes moved
        self.queue: deque[_Live] = deque()
        self.live: dict[int, _Live] = {}  # slot -> in-flight request
        # Slots whose prompt is still being written, one
        # prefill_chunk slice per tick (chunked prefill — a 1024-token
        # admit can't head-of-line-block decode for every live slot).
        self.prefilling: dict[int, _Live] = {}
        self.free: list[int] = list(range(engine.slots))[::-1]  # pop() = slot 0 first
        # Steps enqueued whose tokens have not been fetched, in the
        # device's order: at most one tick's (a chunk step, then a decode
        # step) beside the tick being enqueued. A speculative engine
        # keeps none: how many tokens its step yields is known only once
        # it has run, and every count here follows from that.
        self._in_flight: deque[_Step] = deque()
        self.steps_overlapped = 0  # enqueued while an older step was unfetched
        self.steps_drained = 0  # fetched with nothing enqueued behind
        # Steps enqueued, by whether any slot's temperature asked the
        # sampler to sample (``lm_head_sample`` branches on the same).
        self.steps_greedy_head = 0
        self.steps_sampled_head = 0
        self.completed: list[Completed] = []
        self.shed: list[Request] = []
        self.shed_causes: dict[str, int] = {}  # cause -> count (ISSUE 12)
        self.tick = 0
        self.admissions = 0
        self._occupancy_sum = 0.0
        self._kv_occ_sum = 0.0
        self._kv_occ_peak = 0.0
        self._pages_shared_peak = 0
        # The window pool's fullest (an engine whose layout has a window).
        self._window_occ_peak = 0.0
        self._concurrency_peak = 0
        self._truncated = False  # a run stopped with work still pending
        self._pool_exhausted = False  # edge-trigger for the obs instant
        # The HBM memory ledger (ISSUE 18): the engine registered every
        # buffer at construction; the server reads headroom at every
        # admission verdict, tracks the run's peak/min watermarks, and
        # rolls the whole byte decomposition into stats()["memory"].
        self._memledger = engine.memledger
        self._held_peak = 0
        self._headroom_min_pct: float | None = None
        # Host KV tier (ISSUE 20): preemption victims park their pages
        # in host RAM and resume by restreaming instead of recomputing;
        # prefix entries migrate there instead of dying with their HBM
        # pages. Per-mode resume durations feed the p95
        # restream-vs-recompute comparison on the bench record line.
        self._host_tier = engine.host_pages > 0
        self._host_held_peak = 0
        self.resume_durations: dict[str, list] = {
            "restream": [], "recompute": [],
        }
        # Per-slot sampling-control arrays (host; refreshed on admit/retire).
        s = engine.slots
        self._temp = np.zeros((s,), np.float32)
        self._topk = np.zeros((s,), np.int32)

    # -- intake -------------------------------------------------------------
    def _span_attrs(self, req: Request) -> dict:
        """rid (+ tenant when set) for per-request span stamping —
        tenant is a string, so it also rolls up as a summary label."""
        return (
            {"rid": req.rid, "tenant": req.tenant}
            if req.tenant
            else {"rid": req.rid}
        )

    def submit(self, req: Request) -> bool:
        """Enqueue one request; returns False when it was SHED instead
        — ``max_queue`` bounded intake (``shed_queue_full``) or the
        policy's projected-TTFT admission verdict (``shed_admission``)
        (malformed requests still raise — shedding is a LOAD decision,
        validation is a caller bug)."""
        if not req.prompt:
            raise ValueError(f"request {req.rid!r}: empty prompt")
        if req.priority < 0:
            raise ValueError(
                f"request {req.rid!r}: priority must be >= 0 (0 = "
                f"highest tier), got {req.priority}"
            )
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid!r}: max_new_tokens must be >= 1 "
                f"(prefill always samples the first token), got "
                f"{req.max_new_tokens}"
            )
        if len(req.prompt) > self.engine.prefill_len:
            raise ValueError(
                f"request {req.rid!r}: prompt length {len(req.prompt)} > "
                f"engine prefill_len {self.engine.prefill_len}"
            )
        if len(req.prompt) + req.max_new_tokens > self.engine.max_len:
            raise ValueError(
                f"request {req.rid!r}: prompt + max_new_tokens "
                f"({len(req.prompt)} + {req.max_new_tokens}) exceeds the "
                f"engine's max_len {self.engine.max_len}"
            )
        # A request the POOL could never hold is a caller bug, like
        # the max_len checks above — raise at submit, not when the
        # admit loop discovers it can never stop waiting. (The
        # per-slot virtual capacity is already covered: prompt +
        # max_new_tokens <= max_len = pages_per_slot × page_size.)
        alloc = self.engine.allocator
        need = alloc.pages_for(len(req.prompt), req.max_new_tokens)
        if need > alloc.num_pages:
            raise ValueError(
                f"request {req.rid!r}: needs {need} pages of "
                f"{alloc.page_size} tokens but the pool holds only "
                f"{alloc.num_pages}; shrink prompt + max_new_tokens "
                f"or grow Engine(kv_pages=...)"
            )
        k_cap = self.engine.sample_k_cap
        if k_cap is not None and req.top_k > k_cap:
            raise ValueError(
                f"request {req.rid!r}: top_k {req.top_k} exceeds the "
                f"blocked sampler's candidate buffer (sample_k_cap="
                f"{k_cap}); raise Engine(sample_k_cap=...) or use "
                f"top_k=0 (full vocab)"
            )
        if self.stream is not None:
            # Arrivals count BEFORE the shed decision: the shed-rate
            # SLO is shed/arrivals, so both sides of the ratio must see
            # every request that showed up.
            self.stream.inc("serve_arrivals")
        if self._ledger is not None:
            # The ledger opens at intake (post-validation): a SHED
            # request still gets its enqueue + verdict events — the
            # verdict is exactly what why-slow forensics needs.
            self._ledger.begin(
                req.rid, priority=req.priority, tenant=req.tenant,
                prompt_len=len(req.prompt), max_new=req.max_new_tokens,
            )
        # Two distinct shed causes (ISSUE 12 satellite) — bounded intake
        # vs the policy's projected-TTFT verdict — kept apart in the
        # cause-suffixed counters/instants/stats so breach forensics can
        # tell "queue physically full" from "queueing would only
        # manufacture a guaranteed SLO miss". ``serve_shed`` stays the
        # TOTAL: the shed-rate SLO numerator covers both causes.
        cause = None
        if self.max_queue is not None and self._qdepth() >= self.max_queue:
            cause = "queue_full"
        elif self.policy is not None:
            if self.policy.should_shed(req):
                cause = "admission"
                self.policy.shed_admission += 1
            if self._ledger is not None:
                # The admission verdict WITH the projection inputs that
                # produced it (ISSUE 16 tentpole) — the policy records
                # them in ``last_admission`` precisely so a later "the
                # projection lied" forensic can replay the arithmetic.
                self._ledger.event(
                    req.rid, "admission", **self.policy.last_admission
                )
        # Stable reason names for the instant/ledger (ISSUE 16
        # satellite): intake bound vs projection verdict, spelled out.
        reason = {
            "queue_full": "queue_full",
            "admission": "admission_projection",
        }.get(cause)
        if cause is not None:
            self.shed.append(req)
            self.shed_causes[cause] = self.shed_causes.get(cause, 0) + 1
            obs.counter("serve_shed")
            obs.counter(f"serve_shed_{cause}")
            # The headroom numbers at the refusal (ISSUE 18): a shed
            # verdict annotated with the bytes that were (not)
            # available when it was made — the causal event grows the
            # memory dimension the way ISSUE 16 grew the projection one.
            headroom = self._kv_headroom()
            obs.instant("request_shed", cause=cause, reason=reason,
                        queue_depth=self._qdepth(), **headroom,
                        **self._span_attrs(req))
            if self.stream is not None:
                self.stream.inc("serve_shed")
                self.stream.inc(f"serve_shed_{cause}")
            if self._ledger is not None:
                self._ledger.event(
                    req.rid, "shed", reason=reason,
                    queue_depth=self._qdepth(), **headroom,
                )
                self._ledger.retire(req.rid, status="shed", reason=reason)
            return False
        self._enqueue(_Live(req, time.perf_counter()))
        return True

    # -- queue plumbing (FIFO deque vs policy tier) --------------------------
    def _enqueue(self, live: _Live) -> None:
        if self.policy is not None:
            self.policy.enqueue(live)
        else:
            self.queue.append(live)

    def _qdepth(self) -> int:
        return (
            self.policy.pending()
            if self.policy is not None
            else len(self.queue)
        )

    def _next_queued(self) -> _Live | None:
        """Pop the next request to admit — FIFO order, or the policy's
        tier-then-deficit-round-robin choice."""
        if self.policy is not None:
            return self.policy.next()
        return self.queue.popleft() if self.queue else None

    def _restore_queued(self, live: _Live) -> None:
        """Undo one pop (the admission attempt found no pages): back to
        the queue head, order preserved (the policy also refunds the
        spent DRR credit)."""
        if self.policy is not None:
            self.policy.restore(live)
        else:
            self.queue.appendleft(live)

    # -- the loop -----------------------------------------------------------
    def _admit(self) -> None:
        """Admission: grant the next queued request
        (FIFO head, or the policy's tier/DRR choice) a free slot AND
        its whole page requirement (fresh pages + shared-prefix
        mappings + COW reserve, all-or-nothing in the allocator) or
        stop. Stopping on the first request that doesn't fit keeps
        admission fair: a stream of small requests cannot starve a big
        one indefinitely. Admitted requests enter ``prefilling``;
        :meth:`_prefill_chunk_tick` feeds their prompt
        ``prefill_chunk`` tokens per tick.

        With a policy (ISSUE 12), a capacity miss — no free slot, or no
        pages for the chosen request — may PREEMPT instead of stopping:
        when the best queued tier's head is projected to miss its TTFT
        target, a lower-tier live generation is parked (pages freed,
        tokens kept host-side) and the loop retries. Each preemption
        frees one victim; termination is bounded by the live set and
        per-request ``max_preemptions``."""
        alloc = self.engine.allocator
        now = time.perf_counter()
        while True:
            if not self.free:
                if not self._try_preempt(now):
                    break
                continue  # a slot (and its victim's pages) just freed
            live = self._next_queued()
            if live is None:
                break
            slot = self.free[-1]
            feed = live.feed_tokens()
            passed_up = alloc.prefix_hits_passed_up
            plan = alloc.admit(
                slot, feed, live.remaining_new(),
                owner=live.req.rid, tenant=live.req.tenant or None,
                tick=self.tick,
            )
            if alloc.prefix_hits_passed_up > passed_up:
                # A registered prefix the model's layout cannot use (its
                # state at that boundary is not kept): computed anew.
                obs.counter("prefix_hits_passed_up", 1.0)
            if plan is None:
                # Pool full RIGHT NOW (nothing was taken) — back to the
                # queue head; retry after a retirement (or a preemption)
                # frees pages. Instant only on the TRANSITION into
                # exhaustion: a sustained overload would otherwise write
                # one instant per tick into the Recorder's bounded
                # buffer, evicting the spans the percentiles and the
                # obs diff gate read.
                self._restore_queued(live)
                if self._try_preempt(now):
                    continue  # freed pages; the restored head retries
                if self._ledger is not None:
                    # The refused admit's causal event carries the
                    # headroom numbers that refused it (ISSUE 18).
                    self._ledger.event(
                        live.req.rid, "admit_blocked", tick=self.tick,
                        need_pages=alloc.pages_for(
                            len(feed), live.remaining_new()
                        ),
                        free_pages=alloc.free_pages,
                        **self._kv_headroom(),
                    )
                if not self._pool_exhausted:
                    self._pool_exhausted = True
                    # Exhaustion forensics (ISSUE 18 tentpole b): the
                    # ranked top-holders table — who holds the pool the
                    # refused head needed — as a structured instant,
                    # retained on the ledger for the end-of-run
                    # snapshot and the `obs capacity` CLI.
                    dump = self._exhaustion_dump()
                    if self._memledger is not None:
                        self._memledger.note_exhaustion(dump)
                    obs.instant("kv_pool_exhausted", **dump)
                break
            self.free.pop()
            self._pool_exhausted = False  # an admit fit: episode over
            live.last_touch = self.tick
            # The write floor is the shared-token count; the forward
            # re-runs at least the LAST feed token (its logits seed
            # the next output token), so the feed base is capped one
            # below the feed end even on a full-feed prefix hit.
            live.floor = plan.shared_tokens
            live.base = min(plan.shared_tokens, len(feed) - 1)
            self._temp[slot] = live.req.temperature
            self._topk[slot] = live.req.top_k
            if plan.restream:
                # Host-tier prefix hit (ISSUE 20): restream the entry's
                # pages into the freshly granted device pages before the
                # first prefill chunk; the write floor then masks
                # re-writes below shared_tokens exactly as for an HBM
                # hit. The entry stays host-resident (release=False) —
                # it keeps serving hits until promotion frees it.
                for hp, dp in plan.restream:
                    self.engine.restore_page(
                        hp, dp, owner=live.req.rid, tick=self.tick
                    )
            if self._ledger is not None:
                self._ledger.event(
                    live.req.rid, "slot_bind", slot=slot, tick=self.tick,
                    resumed=bool(live.tokens),
                    shared_tokens=plan.shared_tokens,
                    pages=plan.pages_granted,
                    restreamed_pages=len(plan.restream),
                )
            if live.tokens:
                # Resumed after a preemption: queue_wait/TTFT were
                # already delivered in the first stint — re-recording
                # them would double-count the request in the histograms.
                resume_mode = "recompute"
                if self._host_tier:
                    rec = alloc.peek_parked(live.req.rid)
                    if rec is not None:
                        if self._restream_parked(slot, live, plan, rec):
                            resume_mode = "restream"
                        alloc.take_parked(live.req.rid)
                live.resume_mode = resume_mode
                live.resume_t = now
                if self.policy is not None:
                    self.policy.resumes += 1
                obs.instant(
                    "request_resumed", generated=len(live.tokens),
                    **self._span_attrs(live.req),
                )
                if self._ledger is not None:
                    self._ledger.event(
                        live.req.rid, "preempt_resume", slot=slot,
                        tick=self.tick, generated=len(live.tokens),
                        mode=resume_mode if self._host_tier else "recompute",
                    )
            else:
                obs.span_at(
                    "queue_wait", live.submit_t, now,
                    **self._span_attrs(live.req),
                )
                if self.stream is not None:
                    self.stream.observe("queue_wait", now - live.submit_t)
            self.prefilling[slot] = live
            self.admissions += 1

    # -- preemption (ISSUE 12) -------------------------------------------------
    def _try_preempt(self, now: float) -> bool:
        """Park one lower-tier live generation when the policy says the
        best queued tier's head would otherwise miss its TTFT target.
        Returns True when a victim was evicted (a slot + its pages are
        now free)."""
        if self.policy is None:
            return False
        priority = self.policy.wants_preemption(now)
        if priority is None:
            return False
        # The victim is chosen by the tokens it has left and parked with
        # those it has: both are values, so what is in flight lands first.
        self._drain()
        victim = self.policy.pick_victim(self.live, priority)
        if victim is None:
            return False
        self._preempt(victim, for_tier=priority)
        return True

    def _preempt(self, slot: int, *, for_tier: int | None = None) -> None:
        """Evict ``slot``'s live request: free its pages back to the
        allocator (sole-owner pages return to the free list, shared
        pages drop a refcount — exactly what retirement would free, the
        pool-accounting pin), park the request host-side with its
        generated-so-far tokens as the resume feed, and re-queue it at
        the FRONT of its own tier. The resume path is the normal
        chunked prefill over ``prompt + tokens`` — its final row
        recomputes the displaced decode tick, so the resumed greedy
        output bit-matches the un-preempted one (test-pinned).

        The feed is made of values, so every step in flight is fetched
        first; an EOS among them may have retired the slot already."""
        self.engine.model.check_preemption()
        self._drain()
        if slot not in self.live:
            return
        live = self.live.pop(slot)
        alloc = self.engine.allocator
        owned, shared = alloc.slot_page_stats(slot)
        spilled_pages = 0
        if self._host_tier:
            # ISSUE 20: park the victim's filled rows in host RAM
            # BEFORE the pages recycle — the spill gathers dispatch
            # async (the device buffers they read stay pinned even if
            # the very next admit rewrites the pages) and land at the
            # next tick boundary. All-or-nothing: an undersized host
            # tier parks nothing and resume recomputes, as before
            # tiering. Entries dying with the slot migrate too.
            planned = alloc.park_pages(
                live.req.rid, slot, live.cache_fill()
            )
            if planned is not None:
                copies, evicted = planned
                for hp in evicted:
                    self.engine.host_free(hp, kind="host_evict")
                for dp, hp in copies:
                    self.engine.spill_page(
                        dp, hp, owner=live.req.rid, tick=self.tick
                    )
                spilled_pages = len(copies)
            self._spill_dying_prefixes(slot, owner=live.req.rid)
        alloc.free_slot(slot)
        self.free.append(slot)
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        live.preempts += 1
        live.feed = list(live.req.prompt) + [int(t) for t in live.tokens]
        live.base = 0
        live.floor = 0
        live.park_tick = self.tick
        if self._memledger is not None:
            # Parked = cold by definition: the owner stays on the
            # recency index (state flips to "parked") so the eviction
            # ranking can surface it, coldest first (ISSUE 18).
            self._memledger.touch(
                live.req.rid, tick=self.tick,
                tenant=live.req.tenant or None, state="parked",
            )
        # The displacing rid (ISSUE 16): the head whose projected TTFT
        # miss justified this eviction — recorded by wants_preemption,
        # "" when the park came from a direct _preempt call.
        for_rid = (
            getattr(self.policy, "last_preemption_for", "") or ""
            if for_tier is not None
            else ""
        )
        obs.instant(
            "request_preempted",
            tier=live.req.priority,
            for_tier=for_tier if for_tier is not None else -1,
            generated=len(live.tokens),
            pages_freed=owned,
            pages_unshared=shared,
            pages_spilled=spilled_pages,
            **self._span_attrs(live.req),
        )
        if self._ledger is not None:
            self._ledger.event(
                live.req.rid, "preempt_park", tick=self.tick,
                tier=live.req.priority,
                for_tier=for_tier if for_tier is not None else -1,
                for_rid=for_rid, generated=len(live.tokens),
                pages_freed=owned,
            )
        if self.stream is not None:
            self.stream.inc("serve_preemptions")
        if self.policy is not None:
            self.policy.preemptions += 1
            self.policy.requeue_front(live)
        else:
            # Direct preemption on a policy-less server (tests, manual
            # eviction): FIFO resume order, front of the plain queue.
            self.queue.appendleft(live)

    def _spill_dying_prefixes(self, slot: int, *, owner=None) -> None:
        """Migrate prefix entries that would die with ``slot``'s pages
        into the host tier (ISSUE 20) — call immediately BEFORE
        ``free_slot``. Best-effort and all-or-nothing: when the host
        tier cannot hold the migration, the entries die exactly as
        before tiering."""
        if not self._host_tier:
            return
        copies, evicted = self.engine.allocator.spill_prefix_on_free(slot)
        for hp in evicted:
            self.engine.host_free(hp, kind="host_evict")
        for dp, hp in copies:
            self.engine.spill_page(dp, hp, owner=owner, tick=self.tick)

    def _restream_parked(self, slot: int, live: _Live, plan, rec) -> bool:
        """Rebuild a resumed victim's cache rows ``[shared, fill)`` from
        its parked host pages instead of re-prefilling the feed
        (ISSUE 20). Rows below the admission's shared floor are already
        on device (prefix hit — possibly itself a restream); the
        boundary page COWs out first when still shared, and every
        restored page is written WHOLE (parked rows below the floor are
        bit-identical to the resident ones — K/V is a deterministic
        function of tokens and positions — and junk rows past the fill
        stay mask-hidden, exactly as after a normal prefill). On
        success the feed base jumps to the fill watermark, so the next
        prefill chunk is the single displaced decode row: the
        recompute path's bit-match discipline, minus the recompute.
        Returns False when the prefix hit already covers every parked
        row (payloads dropped unused)."""
        eng = self.engine
        alloc = eng.allocator
        ps = alloc.page_size
        s = plan.shared_tokens
        fill = rec.fill
        rid = live.req.rid
        if s >= fill:
            for hp in rec.host_pages:
                eng.host_free(hp, kind="restream_unused", owner=rid)
            return False
        if s % ps:
            # The boundary page holds shared rows below ``s``; a
            # whole-page restore over a still-shared page would corrupt
            # the other readers — COW it out first (admission reserved
            # the free page, the same guarantee a prefill write gets).
            pair = alloc.cow_before_write(slot, s)
            if pair is not None:
                eng.copy_page(*pair)
                if self._ledger is not None:
                    self._ledger.event(
                        rid, "cow_copy", tick=self.tick,
                        src=pair[0], dst=pair[1], phase="restream",
                    )
        bt = alloc.block_tables[slot]
        for pi in range(s // ps, (fill - 1) // ps + 1):
            eng.restore_page(
                int(rec.host_pages[pi]), int(bt[pi]),
                release=True, kind="restream", owner=rid, tick=self.tick,
            )
        for pi in range(0, s // ps):
            # Fully below the shared floor: the device prefix hit
            # already provides these rows — drop the payloads.
            eng.host_free(
                int(rec.host_pages[pi]), kind="restream_unused", owner=rid
            )
        live.base = fill
        live.floor = fill
        return True

    # -- one tick of steps in flight (ISSUE 29) --------------------------------
    def _decoding(self) -> list:
        """``(slot, live)`` of the slots a decode step would serve: what
        is in ``live`` less the entries whose slot has gone back already."""
        return [(s, l) for s, l in self.live.items() if not l.released]

    def _older(self, kind: str, before: int | None = None) -> bool:
        """Whether the oldest step in flight is a ``kind`` step that a
        tick before ``before`` (this one) enqueued: the one this tick's
        ``kind`` phase fetches."""
        head = self._in_flight[0] if self._in_flight else None
        return (
            head is not None and head.kind == kind
            and head.tick < (self.tick if before is None else before)
        )

    def _sampler_path(self) -> str:
        """Which way the temperatures a step is given send the blocked
        sampler: ``lm_head_sample``'s own predicate on the host's copy
        (a compacted chunk step evaluates it over its participants'
        rows alone, so ``sampled`` is an upper bound there)."""
        return "sampled" if np.any(self._temp > 0) else "greedy"

    def _enqueued(self, step: _Step) -> None:
        """``step`` joins what is in flight, behind whatever is unfetched."""
        if self._in_flight:
            self.steps_overlapped += 1
            obs.counter("serve_steps_overlapped")
        if self._sampler_path() == "greedy":
            self.steps_greedy_head += 1
            obs.counter("serve_steps_greedy_head")
        else:
            self.steps_sampled_head += 1
            obs.counter("serve_steps_sampled_head")
        self._in_flight.append(step)

    def _fetch(self) -> _Step:
        """Take the oldest step in flight off the device: wait for its
        tokens and stamp them with the time they reached the host. The
        caller has a span of the step's kind open round this."""
        step = self._in_flight.popleft()
        fetch = (
            self.engine.decode_fetch if step.kind == "decode"
            else self.engine.prefill_fetch
        )
        # A chunk in which no slot finished its prompt is fetched too,
        # for the wait: it is what holds the host to one tick ahead of
        # the device where no decode step does (a lone long prompt would
        # otherwise be enqueued whole, and a later arrival's first chunk
        # would queue behind all of it instead of riding the next one).
        step.toks = fetch(step.pending)
        step.t_land = time.perf_counter()
        step.pending = None
        if not self._in_flight:
            self.steps_drained += 1
            obs.counter("serve_steps_drained")
        return step

    def _land(self, kind: str, before: int) -> list:
        """Fetch the ``kind`` steps at the head of what is in flight that
        ticks before ``before`` enqueued (inside the caller's ``kind``
        span); they are settled by the caller once the span has closed."""
        landed = []
        while self._older(kind, before):
            landed.append(self._fetch())
        return landed

    def _drain(self) -> None:
        """Fetch and settle every step in flight, oldest first, with
        nothing enqueued behind them: what a preemption does first (its
        feed is made of values), and what a tick does at its end when
        nothing is left that could be enqueued. Each fetch lies inside a
        span of its step's kind, as every fetch does."""
        while self._in_flight:
            head = self._in_flight[0]
            attrs = {}
            if obs.enabled():
                attrs = dict(
                    drained=True,
                    rids=[live.req.rid for _, live in head.takers],
                    **self._labels[head.kind],
                )
            t0 = time.perf_counter()
            with obs.span(head.kind, **attrs):
                step = self._fetch()
            if step.kind == "decode":
                with obs.span("retire"):
                    self._settle_decode(step, t0)
            else:
                self._settle_prefill(step)

    def _prefill_chunk_tick(self) -> None:
        """Enqueue one prompt chunk for every prefilling slot, and one
        more for as many as the step has seats to spare (one batched
        call), then fetch the chunk the last tick enqueued.

        Slots whose final prompt token rides this chunk register their
        prompt in the prefix index (only now — an index entry must never
        advertise K/V not yet on the device, which holds in the device's
        order: whatever reads those pages is enqueued behind this step)
        and go live BY COUNT; their first output token is a value and
        arrives with the fetch, a tick later (:meth:`_settle_prefill`).
        A speculative engine fetches its chunk at once."""
        if not self.prefilling and not self._older("prefill"):
            return
        now = time.perf_counter()
        attrs = {}
        chunk = bool(self.prefilling)
        if chunk:
            (seats, tokens, base, chunk_lens, floor, sample_mask,
             finishing) = self._stage_chunk()
            if obs.enabled():  # a disabled span costs a tick nothing
                attrs = dict(
                    admitted=len(finishing),
                    chunks=len(seats),
                    rids=[live.req.rid for live in self.prefilling.values()],
                    sampler_path=self._sampler_path(),
                )
                # As the decode span's: what each of the chunk's rows
                # finds cached, itself included, and what it reads.
                seen = np.concatenate([
                    b + 1 + np.arange(n) for b, n in zip(base, chunk_lens)])
                attrs["rows_cached"] = int(seen.sum())
                attrs["rows_read"] = int(
                    self.engine.model.rows_attended(seen).sum())
        if obs.enabled():
            attrs.update(self._labels["prefill"])
        with obs.span("prefill", **attrs):
            if chunk:
                self._enqueued(_Step(
                    "prefill",
                    self.engine.prefill_dispatch(
                        tokens, base, chunk_lens, floor, sample_mask,
                        self._temp, self._topk, seats,
                    ),
                    finishing, self.tick,
                ))
            landed = self._land("prefill", self.tick + bool(self._spec))
        t_end = time.perf_counter()
        if chunk:
            self._chunk_enqueued(
                np.bincount(seats, chunk_lens, self.engine.slots),
                finishing, t_end - now, t_end,
            )
        for step in landed:
            self._settle_prefill(step)

    def _window_advance(self, slot: int, start: int, end: int) -> None:
        """Before a step of ``slot`` whose rows are positions ``start ..
        end - 1``: the window layers' pages they reach are mapped, those
        behind their window go back to the window pool
        (``PageAllocator.advance_window``; nothing where no layer keeps a
        window), and what came back is counted."""
        back = self.engine.allocator.advance_window(slot, start, end)
        if back:
            obs.counter("kv_window_pages_returned", float(back))

    def _stage_chunk(self):
        """The tick's SEATS as the step's host arrays (a row a seat:
        ``seats[i]`` is the slot whose chunk row ``i`` holds), the page
        copies their writes need enqueued before it, and the slots whose
        final prompt token rides it.

        Every prefilling slot takes a seat: its next chunk. The seats
        the engine's step would then compute for nobody
        (``Engine.spare_seats``) go, one more chunk at a time, to the
        slot with the most prompt left beyond what it holds already.
        A slot's pages were all granted at admission, so a further seat
        needs none; it starts where the seat before it ends, and only on
        a page boundary: the pool's page writer lands whole pages, and
        two seats that met inside one would both write it."""
        eng = self.engine
        alloc = eng.allocator
        w, page = eng.prefill_chunk, eng.page_size
        rows = []  # (slot, first position, tokens) a seat, in step order
        at = {}  # where a slot's next seat would start
        for slot, live in self.prefilling.items():
            n = min(w, len(live.feed_tokens()) - live.base)
            rows.append((slot, live.base, n))
            at[slot] = live.base + n

        def left(slot):
            return len(self.prefilling[slot].feed_tokens()) - at[slot]

        for _ in range(eng.spare_seats(len(rows))):
            slot = max(
                (s for s in at if at[s] % page == 0), key=left, default=None
            )
            if slot is None or left(slot) <= 0:
                break
            n = min(w, left(slot))
            rows.append((slot, at[slot], n))
            at[slot] += n
        seats = np.zeros((len(rows),), np.int32)
        tokens = np.zeros((len(rows), w), np.int32)
        base, chunk_lens, floor = (np.zeros_like(seats) for _ in range(3))
        sample_mask = np.zeros((len(rows),), bool)
        finishing: list[tuple[int, _Live]] = []
        for i, (slot, start, n) in enumerate(rows):
            live = self.prefilling[slot]
            p = live.feed_tokens()
            # First write of this seat: at the floor on a partial-page
            # prefix hit, else where it starts. A write landing in a
            # still-shared page copies it out first (device page copy);
            # the allocator's admission reserve guarantees the free page.
            first_write = max(start, live.floor)
            if first_write < start + n:
                pair = alloc.cow_before_write(slot, first_write)
                if pair is not None:
                    eng.copy_page(*pair)
                    if self._ledger is not None:
                        self._ledger.event(
                            live.req.rid, "cow_copy", tick=self.tick,
                            src=pair[0], dst=pair[1], phase="prefill",
                        )
            self._window_advance(slot, start, start + n)
            tokens[i, :n] = p[start : start + n]
            seats[i], base[i], chunk_lens[i] = slot, start, n
            floor[i] = live.floor
            if start + n == len(p):
                sample_mask[i] = True
                finishing.append((slot, live))
        return seats, tokens, base, chunk_lens, floor, sample_mask, finishing

    def _chunk_enqueued(self, chunk_lens, finishing, dur: float,
                        t_end: float) -> None:
        """What the host knows of a chunk step without its values: every
        slot's progress through its prompt, and for a slot that finished
        it the prefix registration, its place among the live slots and,
        where one token is all it was to have, the slot's return. ``dur``
        is the wall of the ``prefill`` phase (this chunk's staging and
        enqueue and the wait for the last one's tokens): what the
        sentinel, the policy's projector and the ledger take as a chunk
        tick's cost."""
        alloc = self.engine.allocator
        if self.sentinel is not None:
            self.sentinel.observe_phases(self.tick, prefill=dur)
        if self.stream is not None:
            # The policy projector's per-chunk cost basis (ISSUE 12).
            self.stream.observe("prefill_tick", dur)
        for slot, live in self.prefilling.items():
            n = int(chunk_lens[slot])
            live.base += n
            if n:
                live.last_touch = self.tick
                if self._ledger is not None:
                    # One event per slot that actually advanced — the
                    # chunk length and the tick wall feed
                    # prefill_compute_s in the why-slow attribution.
                    self._ledger.event(
                        live.req.rid, "prefill_chunk", tick=self.tick,
                        chunk=n, dur_s=dur, t=t_end,
                    )
        for slot, live in finishing:
            del self.prefilling[slot]
            promoted = alloc.register_prefix(
                slot, live.feed_tokens(), tick=self.tick
            )
            for hp in promoted:
                # ISSUE 20: the prompt's prefix is resident on device
                # again — the allocator promoted its host entries, and
                # the freed host seats drop their payloads here.
                self.engine.host_free(
                    hp, kind="promote", owner=live.req.rid
                )
            # A resumed request's tokens are all on the host (its
            # preemption drained first): this chunk's sampled token is
            # the decode step the eviction displaced.
            live.issued = len(live.tokens) + 1
            self.live[slot] = live
            if self._spent(live):
                self._release(slot, live)

    def _settle_prefill(self, step: _Step) -> None:
        """What a finished prompt owes once its chunk's tokens are on
        the host: the first token and its time (stamped when this
        step's own array landed, not when a step behind it did)."""
        t_first = step.t_land
        for slot, live in step.takers:
            tok = int(step.toks[slot])
            if live.tokens:
                # Resumed after a preemption: append; TTFT was already
                # delivered before the park.
                live.tokens.append(tok)
                if live.resume_mode:
                    # Close the resume: admission → first post-resume
                    # token, by rebuild mode (ISSUE 20 — the p95
                    # restream-vs-recompute comparison's sample).
                    dur = t_first - live.resume_t
                    self.resume_durations.setdefault(
                        live.resume_mode, []
                    ).append(dur)
                    if self.stream is not None:
                        self.stream.observe(
                            f"resume_{live.resume_mode}", dur
                        )
                    live.resume_mode = ""
            else:
                live.first_token_t = t_first
                live.tokens = [tok]
                self._record_ttft(live, t_first)
                # An engine nobody warmed is ready with its first token
                # (ignored inside a warm-up's span, and once ready).
                obs.startup.ready("engine")
            self._token_landed(slot, live, t_first)

    def _record_ttft(self, live: _Live, t_first: float) -> None:
        """First-token bookkeeping: the request_ttft span + rolling
        windows, plus the per-tier series (``request_ttft_tier<p>`` —
        what a tier-scoped SLO target reads) when tiers are in play and
        the per-tenant series behind ``stats()``'s tenant roll-up."""
        req = live.req
        obs.span_at(
            "request_ttft", live.submit_t, t_first,
            **self._span_attrs(req),
        )
        if self.stream is None:
            return
        ttft = t_first - live.submit_t
        self.stream.observe("request_ttft", ttft)
        if self.policy is not None or req.priority or req.ttft_target_s > 0:
            self.stream.observe(f"request_ttft_tier{req.priority}", ttft)
        if req.tenant:
            self.stream.observe(f"request_ttft_tenant:{req.tenant}", ttft)

    def _spent(self, live: _Live) -> bool:
        """No further step will be asked for ``live``, by count: its
        budget of tokens is issued, or the next decode would write at
        ``max_len`` and overrun the slot's mapped pages."""
        return (
            live.issued >= live.req.max_new_tokens
            or live.cache_fill() >= self.engine.max_len
        )

    def _release(self, slot: int, live: _Live) -> None:
        """Give ``slot`` and its pages back. By count this happens in
        the tick that enqueues the request's last step, before that
        step's token is fetched; the request stays in ``live`` (flagged
        ``released``) so that its landed tokens stay visible until its
        ``Completed`` is written."""
        # Unmap the slot's pages: refcounts drop, sole-owner pages
        # return to the free list (recycled WITHOUT zeroing — the
        # mask defines validity), prefix-index entries whose pages
        # died are invalidated — unless the host tier catches them
        # first (ISSUE 20: a sole-reader prefix migrates instead of
        # dying, so the index survives HBM reclaim). A step still in
        # flight may write one more row into these pages: whatever is
        # given them next is enqueued behind it.
        self._spill_dying_prefixes(slot, owner=live.req.rid)
        self.engine.allocator.free_slot(slot)
        if self._memledger is not None:
            self._memledger.forget(live.req.rid)
        self.free.append(slot)
        self._temp[slot] = 0.0
        self._topk[slot] = 0
        live.released = True

    def _token_landed(self, slot: int, live: _Live, now: float) -> None:
        """``live``'s newest token has reached the host at ``now``:
        finish the request if that token is its EOS (found out a tick
        after the step ran, so one more token may be in flight: it is
        dropped) or the last one its count allowed."""
        req = live.req
        eos = req.eos_id is not None and live.tokens[-1] == req.eos_id
        if not eos and not (
            live.released and len(live.tokens) >= live.issued
        ):
            return
        if not live.released:
            self._release(slot, live)
        self._complete(slot, live, now)

    def _complete(self, slot: int, live: _Live, now: float) -> None:
        """Write ``live``'s ``Completed``: its last token is on the host."""
        req = live.req
        tok = live.tokens[-1]
        live.done = True
        if self.live.get(slot) is live:
            del self.live[slot]
        full = len(req.prompt) + len(live.tokens) - 1 >= self.engine.max_len
        obs.span_at(
            "request_latency", live.submit_t, now, **self._span_attrs(req)
        )
        obs.counter("serve_requests")
        if self.stream is not None:
            self.stream.observe("request_latency", now - live.submit_t)
            self.stream.inc("serve_completed")
        truncated = (
            full and tok != req.eos_id and len(live.tokens) < req.max_new_tokens
        )
        if self._ledger is not None:
            reason = (
                "eos"
                if req.eos_id is not None and tok == req.eos_id
                else (
                    "max_tokens"
                    if len(live.tokens) >= req.max_new_tokens
                    else "cache_full"
                )
            )
            self._ledger.event(
                req.rid, "retire", tick=self.tick, reason=reason,
                generated=len(live.tokens), t=now,
            )
            self._ledger.retire(
                req.rid, t=now,
                status="truncated" if truncated else "completed",
                reason=reason,
            )
        self.completed.append(
            Completed(
                rid=req.rid,
                prompt=list(req.prompt),
                tokens=list(live.tokens),
                submit_t=live.submit_t,
                first_token_t=live.first_token_t,
                finish_t=now,
                truncated=truncated,
                tenant=req.tenant,
            )
        )

    def _spec_tick(self) -> None:
        """One speculative decode tick (ISSUE 13): draft k tokens per
        live slot, verify all k+1 positions in ONE target pass, emit
        each slot's longest accepted prefix plus the replacement/bonus
        token — cache lengths advanced in-step by exactly the emitted
        count (the rollback). Spanned as ``decode`` with nested
        ``spec_draft`` / ``spec_verify`` spans (the ``attention=``
        idiom rides all three), so the flight recorder attributes
        draft vs verify work while the decode-phase roll-up — bench
        denominators, the sentinel — still covers the whole tick."""
        eng = self.engine
        k = self._spec
        active = np.zeros((eng.slots,), bool)
        budget = np.ones((eng.slots,), np.int32)
        eos = np.full((eng.slots,), -1, np.int32)
        for slot, live in self.live.items():
            active[slot] = True
            budget[slot] = live.remaining_new()
            if live.req.eos_id is not None:
                eos[slot] = live.req.eos_id
        # Every page the verify span [fill, fill+k] can write must
        # be privately owned BEFORE the step — the plain tick's COW
        # probe, once per page in the span. Only the shared-prefix
        # partial page can actually be shared, so at most one copy
        # runs; the rest are no-op refcount probes.
        ps = eng.page_size
        caps = eng.allocator.mapped_tokens()
        for slot, live in self.live.items():
            fill = live.cache_fill()
            last_pos = min(fill + k, int(caps[slot]) - 1)
            for page_idx in range(fill // ps, last_pos // ps + 1):
                pair = eng.allocator.cow_before_write(
                    slot, max(fill, page_idx * ps)
                )
                if pair is not None:
                    eng.copy_page(*pair)
                    if self._ledger is not None:
                        self._ledger.event(
                            live.req.rid, "cow_copy", tick=self.tick,
                            src=pair[0], dst=pair[1], phase="spec",
                        )
        n_live = int(active.sum())
        rids = (
            [live.req.rid for live in self.live.values()]
            if obs.enabled() else None
        )
        t0 = time.perf_counter()
        with obs.span(
            "decode", active=n_live, attention=self._attn_mode,
            sampler=self._sampler, spec_k=k, rids=rids,
            **self._kv_attrs, **self._decode_tiling,
        ):
            with obs.span(
                "spec_draft", active=n_live, attention=self._attn_mode,
                sampler=self._sampler, rids=rids, **self._kv_attrs,
            ):
                eng.spec_draft(active, self._temp, self._topk)
            t1 = time.perf_counter()
            with obs.span(
                "spec_verify", active=n_live, attention=self._attn_mode,
                sampler=self._sampler, rids=rids, **self._kv_attrs,
                **self._decode_tiling,
            ):
                emit, n_emit, n_acc = eng.spec_verify(
                    active, self._temp, self._topk, budget, eos
                )
        now = time.perf_counter()
        if self.sentinel is not None:
            self.sentinel.observe_phases(self.tick, decode=now - t0)
        emitted = int(n_emit.sum())
        accepted = int(n_acc.sum())
        obs.counter("serve_tokens", float(emitted))
        obs.counter("spec_drafted_tokens", float(k * n_live))
        obs.counter("spec_accepted_tokens", float(accepted))
        self._spec_emitted += emitted
        self._spec_active_ticks += n_live
        self._spec_drafted += k * n_live
        self._spec_accepted += accepted
        if self.stream is not None:
            self.stream.inc("serve_tokens", float(emitted))
            self.stream.observe("decode_tick", now - t0)
            self.stream.observe("spec_draft_tick", t1 - t0)
            self.stream.observe("spec_verify_tick", now - t1)
            if n_live:
                # Tokens emitted per slot-tick (1.0 = plain decode) —
                # the throughput multiplier the record line carries —
                # and the fraction of drafted tokens the target kept.
                self.stream.observe(
                    "accepted_tokens_per_tick", emitted / n_live
                )
                self.stream.observe(
                    "draft_acceptance_rate", accepted / (k * n_live)
                )
        lens = np.asarray(
            [live.cache_fill() for live in self.live.values()]
        )
        if self._attn_mode == "kernel" and obs.enabled():
            # Same single-formula tile accounting as the plain tick,
            # at the verify's T = k+1 query width.
            bk = eng.decode_block_k
            total = eng.max_len // bk
            visited = num_kv_blocks(lens, k + 1, eng.max_len, bk)
            n_free = eng.slots - lens.size
            obs.counter(
                "decode_blocks_skipped",
                float(total * eng.slots - int(visited.sum()) - n_free),
            )
        ach = eng.decode_achieved_hbm_bytes(lens, t_q=k + 1)
        if ach is not None:
            self._decode_hbm_bytes += ach
            obs.roofline.work("spec_verify", hbm_bytes=ach)
            costs = eng.roofline_costs or {}
            flops = costs.get("spec_verify", {}).get("flops", 0.0)
            if self.stream is not None:
                self.stream.inc("decode_hbm_bytes", ach)
                if flops:
                    self.stream.inc("decode_flops", flops)
            if self._util_watch is not None and now > t1:
                # The modeled bytes cover the VERIFY pass only, so the
                # rate divides by the verify wall (t1 = draft/verify
                # boundary) — over the whole tick a slow draft would
                # structurally depress the rate and trip the sustained-
                # collapse watch on a healthy engine.
                self._util_watch.observe(
                    "decode_hbm_gbps", self.tick, ach / (now - t1) / 1e9
                )
        if self._ledger is not None:
            # Per-slot draft/accept accounting (ISSUE 16): the rollback
            # streak a spec-heavy slow request suffered is only visible
            # per request, never in the aggregate acceptance rate.
            for slot, live in self.live.items():
                self._ledger.event(
                    live.req.rid, "spec_tick", tick=self.tick,
                    dur_s=now - t0, drafted=k, t=now,
                    accepted=int(n_acc[slot]), emitted=int(n_emit[slot]),
                )
        # How many tokens the step yields is known only now, so nothing
        # of this tick was enqueued ahead: counts and values coincide.
        self.steps_drained += 1
        obs.counter("serve_steps_drained")
        for slot, live in list(self.live.items()):
            n = int(n_emit[slot])
            live.tokens.extend(int(t) for t in emit[slot, :n])
            live.issued = len(live.tokens)
            live.last_touch = self.tick
            if self._spent(live):
                self._release(slot, live)
            self._token_landed(slot, live, now)

    def _decode_tick(self) -> None:
        """Enqueue this tick's decode step from counts, then fetch the
        one the last tick enqueued: the device has the next step queued
        while the host waits for, and works on, the last one's tokens.
        The ``decode`` span covers both and ends with the older step's
        tokens on the host (``rids`` lists the requests they belong to;
        ``active`` and ``cache_rows`` describe the step enqueued); the
        ``retire`` span after it settles those tokens and gives back the
        slots that the new step finishes by count."""
        decoding = self._decoding()
        if self._spec:
            if decoding:
                self._spec_tick()
            return
        older = self._older("decode")
        if not decoding and not older:
            return
        attrs = {}
        if decoding:
            active = np.zeros((self.engine.slots,), bool)
            # This tick appends one K/V row per live slot at its fill
            # position — a slot whose fill still lands in a SHARED page
            # (full-prompt prefix reuse of a partial last page) must
            # copy it out first; later ticks find the page private and
            # this is a no-op refcount probe.
            for slot, live in decoding:
                active[slot] = True
                fill = live.cache_fill()
                self._window_advance(slot, fill, fill + 1)
                pair = self.engine.allocator.cow_before_write(slot, fill)
                if pair is not None:
                    self.engine.copy_page(*pair)
                    if self._ledger is not None:
                        self._ledger.event(
                            live.req.rid, "cow_copy", tick=self.tick,
                            src=pair[0], dst=pair[1], phase="decode",
                        )
            # Live rows BEFORE the step: what the kernel reads.
            lens = np.asarray([live.cache_fill() for _, live in decoding])
        if obs.enabled():  # a disabled span costs a tick nothing
            attrs = dict(
                active=len(decoding),
                rids=[
                    live.req.rid for _, live in self._in_flight[0].takers
                ] if older else [],
                cache_rows=int(lens.sum()) if decoding else 0,
                **self._labels["decode"],
            )
            if decoding:
                attrs["sampler_path"] = self._sampler_path()
                # Rows a layer's attention finds cached (the new row
                # with them) and those of them it reads: fewer only
                # where the family's attention chooses its rows.
                attrs["rows_cached"] = int((lens + 1).sum())
                attrs["rows_read"] = int(
                    self.engine.model.rows_attended(lens + 1).sum())
        t0 = time.perf_counter()
        span = obs.span("decode", **attrs)
        with span:
            if decoding:
                self._enqueued(_Step(
                    "decode",
                    self.engine.decode_dispatch(
                        active, self._temp, self._topk
                    ),
                    decoding, self.tick, lens=lens,
                ))
                for _, live in decoding:
                    live.issued += 1
                    live.last_touch = self.tick
            landed = self._land("decode", self.tick)
            if landed and attrs:
                # What the fetched step counted on the device.
                span.attrs.update(self.engine.last_counts.get("decode", {}))
        with obs.span("retire"):
            for step in landed:
                self._settle_decode(step, t0)
            for slot, live in decoding:
                if not live.released and self._spent(live):
                    self._release(slot, live)

    def _settle_decode(self, step: _Step, t0: float) -> None:
        """What a decode step owes once its tokens are on the host:
        counters, the rolling windows, the request ledger, the achieved
        HBM bytes and the utilization watch, then each slot's token and,
        with it, the end of a request it finishes. ``t0`` is when the
        phase that fetched it began: with a step always queued behind
        the one being fetched, ``t_land - t0`` is the device's period."""
        now = step.t_land
        dur = now - t0
        # A request an EOS has stopped since was computed one token too
        # many: the token is dropped here and counted nowhere.
        kept = [(slot, live) for slot, live in step.takers if not live.done]
        if self.sentinel is not None:
            self.sentinel.observe_phases(self.tick, decode=dur)
        obs.counter("serve_tokens", float(len(kept)))
        if self.stream is not None:
            self.stream.inc("serve_tokens", float(len(kept)))
            # The policy projector's decode-tick term (ISSUE 12).
            self.stream.observe("decode_tick", dur)
        if self._ledger is not None:
            # Decode-tick MEMBERSHIP: the tick wall is every resident
            # request's latency cost (the tick is shared; the slot is
            # occupied for all of it) — decode_compute_share_s.
            for _, live in kept:
                self._ledger.event(
                    live.req.rid, "decode_tick", tick=step.tick,
                    dur_s=dur, active=len(step.takers), t=now,
                )
        lens = step.lens
        if self._attn_mode == "kernel" and obs.enabled():
            # Read by the recorder alone, so counted only for one.
            # Cache tiles the length-aware kernel skipped this tick —
            # ONE formula, num_kv_blocks, shared with the kernel's own
            # in-kernel bound (pinned against it in
            # tests/test_decode_attention.py), so the counter cannot
            # drift from what the kernel actually visits. A serve
            # regression with this counter flat at 0 = kernel fallback.
            # The decode step runs over ALL slots: free slots' lengths
            # are clamped to 0 in-step, so each one visits exactly 1
            # tile — counted here too, or the counter would understate
            # the skipping the clamp buys.
            bk = self.engine.decode_block_k
            total = self.engine.max_len // bk
            visited = num_kv_blocks(lens, 1, self.engine.max_len, bk)
            n_free = self.engine.slots - lens.size
            obs.counter(
                "decode_blocks_skipped",
                float(
                    total * self.engine.slots
                    - int(visited.sum())
                    - n_free  # 1 visited tile per clamped free slot
                ),
            )
        # Length-aware achieved work (ISSUE 8): the honest HBM figure
        # for a tile-skipping kernel comes from the tiles it VISITS,
        # not the padded cost_analysis buffer — fed as explicit work so
        # the summary's decode utilization uses it, mirrored into the
        # rolling stream windows (the CLI's hbmbw=/mfu= fields) and the
        # sustained-collapse watch.
        ach = self.engine.decode_achieved_hbm_bytes(lens)
        if ach is not None:
            self._decode_hbm_bytes += ach
            obs.roofline.work("decode", hbm_bytes=ach)
            costs = self.engine.roofline_costs or {}
            flops = costs.get("decode", {}).get("flops", 0.0)
            if self.stream is not None:
                self.stream.inc("decode_hbm_bytes", ach)
                if flops:
                    self.stream.inc("decode_flops", flops)
            if self._util_watch is not None and dur > 0:
                self._util_watch.observe(
                    "decode_hbm_gbps", self.tick, ach / dur / 1e9
                )
        for slot, live in kept:
            live.tokens.append(int(step.toks[slot]))
            self._token_landed(slot, live, now)

    def _pending(self) -> bool:
        """Work outstanding: queued (FIFO deque or policy tiers),
        mid-prefill (chunking), live, or enqueued with its tokens still
        to fetch — the loop-termination and truncation predicate."""
        return bool(
            self._qdepth() or self.prefilling or self.live
            or self._in_flight
        )

    def _kv_gauges(self) -> None:
        """Cache-memory efficiency gauges (ISSUE 7 satellite):
        ``kv_tokens_cached`` = tokens actually held device-side (live
        fills + prefill progress — what a token-proportional cache pays
        for), plus pool occupancy and shared-page count. Recorder
        gauges AND the rolling stream windows."""
        kv_tokens = float(
            sum(l.cache_fill() for _, l in self._decoding())
            + sum(l.base for l in self.prefilling.values())
        )
        obs.gauge("kv_tokens_cached", kv_tokens)
        if self.stream is not None:
            self.stream.set_gauge("kv_tokens_cached", kv_tokens)
        self._memory_gauges(kv_tokens)
        alloc = self.engine.allocator
        occ = alloc.occupancy
        shared = alloc.pages_shared
        self._kv_occ_sum += occ
        self._kv_occ_peak = max(self._kv_occ_peak, occ)
        self._pages_shared_peak = max(self._pages_shared_peak, shared)
        obs.gauge("kv_pool_occupancy", occ)
        if alloc.window:
            # The window layers' pool as this tick's steps leave it.
            obs.gauge("kv_window_pool_occupancy", alloc.window_occupancy)
            self._window_occ_peak = max(
                self._window_occ_peak, alloc.window_occupancy)
        obs.gauge("prefix_pages_shared", float(shared))
        if self.stream is not None:
            self.stream.set_gauge("kv_pool_occupancy", occ)
            self.stream.set_gauge("prefix_pages_shared", float(shared))

    def _memory_gauges(self, kv_tokens: float) -> None:
        """Live headroom / watermark / fragmentation gauges (ISSUE 18
        tentpole a): total held bytes, the KV pool's held bytes and
        headroom, and internal fragmentation — granted page capacity
        not covered by cached tokens (tail rows of partially filled
        pages). Recorder gauges AND the rolling stream windows (the
        serve CLI's ``hbm=/held=/headroom=`` fields); the run's peak
        held and minimum headroom are tracked here, once per tick."""
        ml = self._memledger
        if ml is None:
            return
        held = ml.held()
        self._held_peak = max(self._held_peak, int(held))
        head = self._kv_headroom()
        gauges = {"hbm_held_bytes": float(held)}
        if self._host_tier:
            # Host-tier watermark, sampled per tick like the HBM peak
            # (ISSUE 20) — the ``host_held_peak_bytes`` the diff gate
            # compares must not depend on when stats() was last called.
            host_held = int(ml.held("kv_host_pages"))
            self._host_held_peak = max(self._host_held_peak, host_held)
            gauges["host_held_bytes"] = float(host_held)
        kv_held = ml.held("kv_pages") + ml.held("kv_cow_reserve")
        if self.engine.slot_state_bytes:  # the live slots' seats
            kv_held += ml.held("kv_state")
        if self.engine.allocator.window:  # the window layers' pages
            kv_held += ml.held("kv_window_pages")
        gauges["kv_held_bytes"] = float(kv_held)
        if "kv_headroom_pct" in head:
            pct = head["kv_headroom_pct"]
            self._headroom_min_pct = (
                pct
                if self._headroom_min_pct is None
                else min(self._headroom_min_pct, pct)
            )
            gauges["kv_headroom_pct"] = pct
        in_use = self.engine.allocator.pages_in_use
        granted_tokens = in_use * self.engine.page_size
        gauges["kv_frag_pct"] = (
            round(100.0 * (1.0 - kv_tokens / granted_tokens), 2)
            if granted_tokens
            else 0.0
        )
        for name, val in gauges.items():
            obs.gauge(name, val)
            if self.stream is not None:
                self.stream.set_gauge(name, val)

    def _kv_headroom(self) -> dict:
        """KV capacity headroom RIGHT NOW — the bytes an admission
        verdict had to work with (annotated onto sheds and blocked
        admits): free grantable pages × page bytes (COW reserve
        excluded — those bytes are promised). Empty when the engine has
        no ledger."""
        ml = self._memledger
        if ml is None:
            return {}
        cap = ml.capacity("kv_pages")
        if not cap:
            return {}
        held = ml.held("kv_pages") + ml.held("kv_cow_reserve")
        headroom = cap - held
        return {
            "kv_headroom_bytes": int(headroom),
            "kv_headroom_pct": round(100.0 * headroom / cap, 2),
            "hbm_held_bytes": int(ml.held()),
        }

    def _exhaustion_dump(self) -> dict:
        """The ranked top-holders table for a pool-exhaustion edge
        (ISSUE 18 tentpole b): per-request exclusive bytes (what
        evicting each would actually return), per-tenant totals, the
        subsystem decomposition, COW reserve, and the prefix-index
        health counts — everything a "why won't this admit" forensic
        needs, computed from allocator ground truth at the edge."""
        alloc = self.engine.allocator
        pb = self.engine.page_bytes
        holders = []
        for slot, live in self._decoding() + list(
            self.prefilling.items()
        ):
            owned, shared = alloc.slot_page_stats(slot)
            holders.append({
                "rid": live.req.rid,
                "tenant": live.req.tenant or "",
                "bytes": int(owned * pb),
                "shared_pages": shared,
                "last_touch_tick": live.last_touch,
            })
        holders.sort(key=lambda e: (-e["bytes"], str(e["rid"])))
        tenants: dict[str, int] = {}
        for h in holders:
            tenants[h["tenant"]] = tenants.get(h["tenant"], 0) + h["bytes"]
        sole, dead = self._prefix_entry_counts()
        out = {
            "tick": self.tick,
            "free_pages": alloc.free_pages,
            "queued": self._qdepth(),
            "top_holders": holders[:8],
            "tenants": dict(
                sorted(tenants.items(), key=lambda kv: -kv[1])
            ),
            "cow_reserve_bytes": int(alloc.reserved * pb),
            "sole_reader_prefix_entries": sole,
            # 0 by construction (entries die with their pages) —
            # reported so a future allocator change that breaks the
            # invariant shows up as leaked dead entries, not silence.
            "dead_prefix_entries": dead,
        }
        if self._host_tier:
            # Host-tier pressure facts (ISSUE 20): the capacity verdict
            # names whether this exhaustion is HBM-only (host seats
            # still free — spills can relieve) or squeezes both tiers.
            out["host_free_pages"] = len(alloc.host_free)
            out["host_pages"] = alloc.host_pages
            out["host_parked_records"] = len(alloc._parked)
            out["host_resident_entries"] = alloc.host_resident_entries
        out["tier_pressure"] = (
            "both_tiers"
            if self._host_tier and not alloc.host_free
            else "hbm_only"
        )
        if self._memledger is not None:
            out["subsystems"] = self._memledger.decompose()
        out.update(self._kv_headroom())
        return out

    def _prefix_entry_counts(self) -> tuple[int, int]:
        """(sole-reader, dead) prefix-index entry counts: entries whose
        pages are all refcount 1 (only the registrant still maps them —
        reclaimable by retiring one idle slot) and entries citing a
        page at refcount 0 (impossible by construction; counted so a
        regression surfaces). Host-tier entries are excluded — their
        page ids name host seats, not refcounted device pages."""
        alloc = self.engine.allocator
        sole = dead = 0
        for entry in alloc._index.values():
            if entry.tier != "hbm":
                continue
            refs = [int(alloc.refcount[p]) for p in entry.pages]
            if any(r == 0 for r in refs):
                dead += 1
            elif all(r == 1 for r in refs):
                sole += 1
        return sole, dead

    def _run_tick(self) -> None:
        """One loop iteration: admit, enqueue this tick's steps (the
        prefill chunk, then the decode step) from counts and fetch the
        last tick's, gauges, SLO evaluation.

        One tick of steps is kept in flight: what a step needs of the
        host (block tables, ``active``, the chunk's rows) follows from
        counts the host knows before the step runs, and the token a
        decode step feeds on stays on the device, so tick n+1 is enqueued
        before tick n's tokens are fetched and the device does not wait
        for the host. What needs values waits a tick: ``_Live.tokens``,
        first-token and finish times, ``Completed``, an EOS (the token
        computed behind it is dropped). A speculative engine's steps
        decide their own counts, so its ticks fetch what they enqueue.

        Spanned as a tree, by time on this thread: ``tick`` round the
        whole (``in_flight``: the steps unfetched as it begins), and
        inside it, in the order they run, ``admit``, ``prefill`` (the
        chunk), ``gauges``, ``decode`` and ``retire`` (the accounting
        after the decode phase). The engine's ``*_dispatch`` span of this
        tick's step and then the ``*_fetch`` span of the last tick's lie
        inside ``prefill`` and ``decode``; a drain (:meth:`_drain`) is a
        ``prefill`` or ``decode`` span with a fetch alone inside it. The
        engine's page copies (``copy_page``, ``restore_page``,
        ``spill_page``, ``drain_spills``) lie wherever they are enqueued.
        ``tick`` less its children is the loop's own remainder."""
        with obs.span("tick", tick=self.tick, in_flight=len(self._in_flight)):
            if self._host_tier:
                # Land last tick's dispatched spills (ISSUE 20): the
                # device→host copies ran under the decode tick they were
                # dispatched with (the Prefetcher's two-stage overlap);
                # materializing here costs only the memcpy, never the
                # wait.
                self.engine.drain_spills()
            # Requests whose slot went back last tick live on in the
            # steps that hold their last tokens.
            for slot in [s for s, l in self.live.items() if l.released]:
                del self.live[slot]
            with obs.span("admit"):
                self._admit()
            self._prefill_chunk_tick()
            with obs.span("gauges"):
                self._tick_gauges()
            self._decode_tick()
            if self._in_flight and not (self.prefilling or self._decoding()):
                # Nothing is left that could be enqueued behind them.
                self._drain()
            if self.slo is not None:
                transitions = self.slo.evaluate(tick=self.tick)
                if (
                    self._ledger is not None
                    and getattr(self.slo, "sentinel", None) is None
                ):
                    # No sentinel wired: pin the in-flight set from the
                    # monitor's returned transitions directly (with a
                    # sentinel the on_note chain installed in __init__
                    # already did it — never both, or breaches
                    # double-pin).
                    for tr in transitions:
                        if tr.get("event") == "slo_breach":
                            self._ledger.pin_inflight(
                                "slo_breach", step=self.tick
                            )
        self.tick += 1

    def _tick_gauges(self) -> None:
        """Occupancy, queue depth and the cache-memory gauges of a tick,
        after its admissions and before its decode."""
        busy = len(self._decoding()) + len(self.prefilling)
        self._concurrency_peak = max(self._concurrency_peak, busy)
        occupancy = busy / self.engine.slots
        self._occupancy_sum += occupancy
        obs.gauge("slot_occupancy", occupancy)
        if self.stream is not None:
            self.stream.set_gauge("slot_occupancy", occupancy)
            self.stream.set_gauge("queue_depth", float(self._qdepth()))
        if self.policy is not None:
            # Per-tier backlog (ISSUE 12): one gauge per tier the run
            # has seen — zeros included, so an emptied tier reads 0,
            # not its last nonzero value.
            for tier, depth in self.policy.tier_depths().items():
                obs.gauge(f"queue_depth_tier{tier}", float(depth))
                if self.stream is not None:
                    self.stream.set_gauge(
                        f"queue_depth_tier{tier}", float(depth)
                    )
        self._kv_gauges()

    def run(self, *, max_ticks: int = 1_000_000) -> list[Completed]:
        """Drive admit/decode until everything submitted has completed
        (then return ALL completions so far, in finish order). Hitting
        ``max_ticks`` with work still queued/live sets the
        ``truncated`` flag ``stats()`` reports — partial completions
        must not read as a finished run. A run that ends because nothing
        is pending has fetched every step it enqueued; one that stops at
        ``max_ticks`` returns with its last tick's steps in flight, and
        the next call (or a caller that ticks with ``max_ticks=tick +
        1``) fetches them behind the steps it enqueues."""
        # Each call is a fresh verdict: a prior max_ticks-capped run
        # (e.g. a staggered prime before more submits) must not latch
        # ``truncated`` onto a follow-up run that drains everything.
        self._truncated = False
        while self._pending() and self.tick < max_ticks:
            self._run_tick()
        if self._pending():
            self._truncated = True
        if self.slo is not None:
            self.slo.finish()
        return self.completed

    def run_timed(
        self,
        arrivals,
        *,
        duration: float | None = None,
        drain: bool = True,
        max_ticks: int = 1_000_000,
        on_tick=None,
    ) -> list[Completed]:
        """Open-loop drive: submit each :class:`~mpit_tpu.serve.loadgen.
        Arrival` when its clock (seconds from the call) comes due, tick
        the engine in between, and stop admitting at ``duration``
        seconds (``None`` = when the trace is exhausted).

        ``drain=True`` keeps ticking past the admission window until
        queued + live work finishes — every admitted request gets an
        answer (the CLI default). ``drain=False`` stops AT the window's
        end — the honest overload measurement: past saturation the
        queue grows without bound and a drain would never return; what
        completed inside the window is the result, and ``stats()``
        reports ``truncated`` for the rest. ``on_tick(server, now_s)``
        is called once per loop iteration (the CLI's live stats line).
        Requests shed by ``max_queue`` are counted, not raised.
        """
        arrivals = sorted(arrivals, key=lambda a: a.t)
        self._truncated = False  # fresh verdict, as in :meth:`run`
        t0 = time.perf_counter()
        i = 0
        end_t = math.inf if duration is None else duration
        while self.tick < max_ticks:
            now = time.perf_counter() - t0
            while i < len(arrivals) and arrivals[i].t <= min(now, end_t):
                self.submit(arrivals[i].request)
                i += 1
            pending_arrivals = i < len(arrivals) and arrivals[i].t < end_t
            if now >= end_t and not (drain and self._pending()):
                break
            if not pending_arrivals and not self._pending():
                if now >= end_t or i >= len(arrivals):
                    break  # trace exhausted and everything answered
            if not self._pending():
                # Idle: sleep to the next arrival (or the window edge)
                # instead of spinning the host loop dry.
                wake = arrivals[i].t if pending_arrivals else end_t
                delay = min(wake - now, 0.05)
                if delay > 0:
                    time.sleep(delay)
                # An idle stretch still advances SLO time (a breach
                # does not end because traffic paused).
                if self.slo is not None:
                    self.slo.evaluate(tick=self.tick)
                if on_tick is not None:
                    on_tick(self, now)
                continue
            self._run_tick()
            if on_tick is not None:
                on_tick(self, time.perf_counter() - t0)
        if self._pending():
            self._truncated = True
        if self.slo is not None:
            # One closing evaluation: work admitted/shed after the last
            # in-loop evaluate (e.g. the final burst before a
            # drain=False window edge) must still get a verdict.
            self.slo.evaluate(tick=self.tick)
            self.slo.finish()
        return self.completed

    # -- reporting ----------------------------------------------------------
    def _tenant_rollup(self) -> dict:
        """Per-tenant serving facts (ISSUE 12 satellite): completions,
        sheds, and the whole-run p95 TTFT from the stream registry's
        per-tenant sketch — the measurable surface the fairness
        invariant is checked against (tenants were previously only span
        labels). Empty when no request carried a tenant."""
        out: dict[str, dict] = {}
        for c in self.completed:
            if not c.tenant:
                continue
            e = out.setdefault(c.tenant, {"completed": 0, "shed": 0})
            e["completed"] += 1
        for r in self.shed:
            if not r.tenant:
                continue
            e = out.setdefault(r.tenant, {"completed": 0, "shed": 0})
            e["shed"] += 1
        if self.stream is not None:
            for t, e in out.items():
                sk = self.stream.total_sketch(f"request_ttft_tenant:{t}")
                if sk is not None and sk.count:
                    e["ttft_p95_s"] = round(sk.quantile(0.95), 6)
        return dict(sorted(out.items()))

    def _eviction_candidates(self, cap: int = 16) -> list:
        """Ranked list of what an eviction policy SHOULD reclaim first
        (ISSUE 18 tentpole c — the ROADMAP inventory item consumes
        this, ordered coldest-first by last-touch tick):

        - ``parked_victim``: a preempted request sitting in a policy
          queue. Its pages are already free — the bytes figure is the
          claim its re-admission will make (what NOT resuming it
          saves), stamped with the tick the preemption parked it.
        - ``idle_tail``: a live slot's exclusively-owned bytes. Live
          slots touch their cache every decode tick, so these rank
          hottest (last) — correct: evicting a decoding request is the
          most disruptive choice, listed only as the final resort.
        - ``sole_reader_prefix``: a prefix-index entry whose pages are
          all refcount 1 — nobody shares it anymore; retiring its one
          mapper returns the whole run. Nested page-aligned entries of
          the same registration are deduped to the longest.
        - ``host_prefix`` (ISSUE 20): a prefix entry already spilled to
          the host tier. Its bytes are host RAM, not HBM — reclaiming
          it buys host capacity and forfeits a restream hit.

        Every candidate carries its current ``tier`` ("hbm", "host",
        or "none" for parked victims whose pages were spilled/freed).
        """
        pb = self.engine.page_bytes
        out = []
        if self.policy is not None and pb:
            alloc = self.engine.allocator
            parked = getattr(alloc, "_parked", {})
            for st in self.policy._tiers.values():
                for q in st.queues.values():
                    for live in q:
                        if live.feed is None:
                            continue  # fresh submit, holds nothing yet
                        pages = alloc.pages_for(
                            len(live.feed), live.remaining_new()
                        )
                        out.append({
                            "kind": "parked_victim",
                            "rid": live.req.rid,
                            "tenant": live.req.tenant or "",
                            "bytes": int(pages * pb),
                            "last_touch_tick": live.park_tick,
                            "tier": "host" if live.req.rid in parked
                            else "none",
                        })
        if pb:
            alloc = self.engine.allocator
            for slot, live in self._decoding():
                owned, _ = alloc.slot_page_stats(slot)
                out.append({
                    "kind": "idle_tail",
                    "rid": live.req.rid,
                    "tenant": live.req.tenant or "",
                    "bytes": int(owned * pb),
                    "last_touch_tick": live.last_touch,
                    "tier": "hbm",
                })
            best: dict[int, tuple] = {}
            for key, entry in alloc._index.items():
                if not entry.pages:
                    continue
                if entry.tier != "hbm":
                    # Host-resident entry: its page ids index the HOST
                    # namespace — running them through the device
                    # refcount would read the wrong pages. Reported
                    # below as its own candidate kind.
                    continue
                if any(int(alloc.refcount[p]) != 1 for p in entry.pages):
                    continue
                first = entry.pages[0]
                if first not in best or key[0] > best[first][0][0]:
                    best[first] = (key, entry)
            for key, entry in best.values():
                out.append({
                    "kind": "sole_reader_prefix",
                    "key": f"prefix[{key[0]}t]",
                    "bytes": int(len(entry.pages) * pb),
                    "last_touch_tick": alloc._prefix_touch.get(key, 0),
                    "tier": "hbm",
                })
            hbest: dict[int, tuple] = {}
            for key, entry in alloc._index.items():
                if entry.tier != "host" or not entry.pages:
                    continue
                first = entry.pages[0]
                if first not in hbest or key[0] > hbest[first][0][0]:
                    hbest[first] = (key, entry)
            for key, entry in hbest.values():
                out.append({
                    "kind": "host_prefix",
                    "key": f"prefix[{key[0]}t]",
                    "bytes": int(len(entry.pages) * pb),
                    "last_touch_tick": alloc._prefix_touch.get(key, 0),
                    "tier": "host",
                })
        out.sort(key=lambda c: (c["last_touch_tick"],
                                str(c.get("rid", c.get("key", "")))))
        return out[:cap]

    def _memory_stats(self) -> dict:
        """The ``stats()["memory"]`` block (ISSUE 18): byte-exact held
        decomposition + conservation verdict from the ledger, live KV
        headroom, per-request/per-tenant attribution computed from
        allocator ground truth, the eviction-candidate ranking, and the
        device reconciliation (modeled-only off TPU — the roofline
        honesty rule). ``source: memledger`` is the marker the
        ``obs capacity`` CLI keys on."""
        ml = self._memledger
        if ml is None:
            return {}
        out = {
            "source": "memledger",
            "worker_id": self.worker_id,
            "role": self.role,
            "platform": ml.platform,
            "held_bytes": int(ml.held()),
            "held_peak_bytes": int(max(self._held_peak, int(ml.held()))),
            "held_by_subsystem": ml.decompose(),
            "conservation": ml.conservation(),
        }
        cap = ml.capacity("kv_pages")
        if cap:
            out["kv_capacity_bytes"] = int(cap)
            out.update(self._kv_headroom())
            out.pop("hbm_held_bytes", None)  # duplicate of held_bytes
        if self._headroom_min_pct is not None:
            out["kv_headroom_min_pct"] = self._headroom_min_pct
        if self._host_tier:
            # Host-tier ledger view (ISSUE 20). ``restream_bytes`` is
            # the key name the obs diff gate reports on — keep it.
            eng = self.engine
            held = int(ml.held("kv_host_pages"))
            self._host_held_peak = max(self._host_held_peak, held)
            out["host_held_bytes"] = held
            out["host_held_peak_bytes"] = int(self._host_held_peak)
            out["host_capacity_bytes"] = int(
                ml.capacity("kv_host_pages") or 0
            )
            out["spill_bytes_total"] = int(eng.host_spill_bytes)
            out["restream_bytes"] = int(eng.host_restream_bytes)
        per_req: dict[str, dict] = {}
        per_tenant: dict[str, int] = {}
        if self.engine.page_bytes:
            alloc = self.engine.allocator
            pb = self.engine.page_bytes
            for slot, live in self._decoding() + list(
                self.prefilling.items()
            ):
                owned, shared = alloc.slot_page_stats(slot)
                per_req[str(live.req.rid)] = {
                    "bytes": int(owned * pb),
                    "shared_pages": shared,
                    "tenant": live.req.tenant or "",
                }
            shared_pages = int((alloc.refcount >= 2).sum())
            out["shared_bytes"] = int(shared_pages * pb)
        for e in per_req.values():
            t = e["tenant"]
            per_tenant[t] = per_tenant.get(t, 0) + e["bytes"]
        if per_req:
            out["per_request"] = dict(
                sorted(per_req.items(), key=lambda kv: -kv[1]["bytes"])
            )
            out["per_tenant"] = dict(
                sorted(per_tenant.items(), key=lambda kv: -kv[1])
            )
        ev = self._eviction_candidates()
        if ev:
            out["eviction_candidates"] = ev
        device = None
        if self.engine.platform == "tpu":
            import jax

            device = jax.devices()[0]
        out["reconciliation"] = ml.reconcile(device)
        snap = ml.snapshot()
        if "exhaustion" in snap:
            out["exhaustion"] = snap["exhaustion"]
            out["exhaustions"] = snap["exhaustions"]
        return out

    def stats(self) -> dict:
        """Host-side serving roll-up (the obs summary carries the
        span-derived histograms; this is the request-math view)."""
        done = self.completed
        out = {
            "worker_id": self.worker_id,
            "role": self.role,
            "requests_completed": len(done),
            "ticks": self.tick,
            "admissions": self.admissions,
            "generated_tokens": sum(len(c.tokens) for c in done),
            "occupancy_mean": round(
                self._occupancy_sum / max(self.tick, 1), 4
            ),
            # A run that stopped at max_ticks / the timed window with
            # work still queued or live is PARTIAL — indistinguishable
            # from finished without this flag (ISSUE 6 satellite).
            "truncated": self._truncated,
            # Most requests simultaneously resident (live + prefilling).
            "concurrency_peak": self._concurrency_peak,
            # How often a step was enqueued behind one still unfetched,
            # and how often a fetch had nothing enqueued behind it (the
            # first tick, an idle server, a preemption, a speculative
            # engine): the share overlapped is how often the device had
            # work queued while the host handled tokens.
            "steps_overlapped": self.steps_overlapped,
            "steps_drained": self.steps_drained,
            # What the model's steps counted beside tokens (a recorder on).
            "step_counts": dict(self.engine.step_counts),
            # Steps enqueued in which no slot asked for sampling (the
            # blocked head's greedy scan) and in which one did.
            "steps_greedy_head": self.steps_greedy_head,
            "steps_sampled_head": self.steps_sampled_head,
        }
        # The cache's wire dtype (ISSUE 15): what a cached row occupies
        # HBM as — "int8" on the quantized engines, the model dtype
        # otherwise. Always reported: capacity and bandwidth figures
        # are uninterpretable without it.
        out["kv_dtype"] = self.engine.kv_dtype
        # The weight store's wire dtype (ISSUE 17), same rule: "int8"
        # when the matmul weights live as int8+scales, "f32" otherwise.
        out["weights_dtype"] = self.engine.weights_dtype
        # The runtime-guarded compile claim (ISSUE 8): 3 for the
        # engine's lifetime (prefill chunk, decode, copy_page) —
        # anything above is an unexpected recompile the watch also
        # flagged.
        out["engine_compiles"] = self.engine.compile_watch.compiles
        # What the process did before it was ready, and any compile
        # since, by function (obs.startup; the CLI's ``ready`` line).
        out["startup"] = obs.startup.report()
        if self._decode_hbm_bytes:
            out["decode_hbm_bytes_modeled"] = round(
                self._decode_hbm_bytes, 1
            )
        if self._spec:
            # The speculative roll-up (ISSUE 13): tokens emitted per
            # slot-tick (1.0 = plain decode — the throughput
            # multiplier) and the drafted-token acceptance fraction.
            out["spec_k"] = self._spec
            out["spec_drafted_tokens"] = self._spec_drafted
            out["spec_accepted_tokens"] = self._spec_accepted
            if self._spec_active_ticks:
                out["accepted_tokens_per_tick"] = round(
                    self._spec_emitted / self._spec_active_ticks, 4
                )
                out["draft_acceptance_rate"] = round(
                    self._spec_accepted / max(self._spec_drafted, 1), 4
                )
        alloc = self.engine.allocator
        out.update(
            kv_page_size=alloc.page_size,
            kv_pool_pages=alloc.num_pages,
            kv_pool_occupancy_mean=round(
                self._kv_occ_sum / max(self.tick, 1), 4
            ),
            kv_pool_occupancy_peak=round(self._kv_occ_peak, 4),
            prefix_hit_rate=round(alloc.hit_rate, 4),
            prefix_hits=alloc.prefix_hits,
            prefix_pages_shared_peak=self._pages_shared_peak,
            kv_cow_copies=alloc.cow_copies,
        )
        if not alloc.prefix_shareable:
            out["prefix_hits_passed_up"] = alloc.prefix_hits_passed_up
        if alloc.window:
            out.update(
                kv_window_pool_pages=alloc.window_pages,
                kv_window_occupancy_peak=round(self._window_occ_peak, 4),
                kv_window_pages_returned=alloc.window_pages_returned,
            )
        if self._host_tier:
            # Host-tier roll-up (ISSUE 20): tier occupancy plus the
            # spill/restream traffic and where prefix hits landed.
            eng = self.engine
            out.update(
                kv_host_pages=alloc.host_pages,
                kv_host_pages_in_use=alloc.host_pages_in_use,
                host_spilled_pages=eng.host_spilled_pages,
                host_restreamed_pages=eng.host_restreamed_pages,
                host_prefix_hits=alloc.host_prefix_hits,
                parked_spills=alloc.parked_spills,
                spilled_prefix_entries=alloc.spilled_prefix_entries,
                promoted_entries=alloc.promoted_entries,
            )
        # Resume-path p95s (ISSUE 20 headline): recorded for every
        # server — an untiered run yields the recompute p95
        # the bench compares the restream p95 against.
        for mode, durs in sorted(self.resume_durations.items()):
            if durs:
                out[f"resume_{mode}_p95_s"] = round(
                    float(np.percentile(np.asarray(durs), 95)), 6
                )
        if self.shed:
            # Cause breakdown (ISSUE 16 satellite): ``requests_shed``
            # is a dict — total plus the two named reasons (bounded
            # intake vs the projected-TTFT admission verdict), zeros
            # included so a reader never KeyErrors on the quiet cause.
            # The flat ``requests_shed_<cause>`` keys stay for the
            # bench record line and older readers.
            out["requests_shed"] = {
                "total": len(self.shed),
                "shed_queue_full": self.shed_causes.get("queue_full", 0),
                "shed_admission_projection": self.shed_causes.get(
                    "admission", 0
                ),
            }
            for cause, n in sorted(self.shed_causes.items()):
                out[f"requests_shed_{cause}"] = n
        if self.policy is not None:
            pol = self.policy.stats()
            out["preemptions"] = pol["preemptions"]
            out["policy"] = pol
        if self._ledger is not None:
            # Why-slow surfacing (ISSUE 16): the retained tail
            # exemplars, worst first, plus the ledger's aggregate view.
            out["exemplars"] = self._ledger.exemplars()
            out["ledger"] = self._ledger.stats()
        tenants = self._tenant_rollup()
        if tenants:
            out["tenants"] = tenants
        memory = self._memory_stats()
        if memory:
            out["memory"] = memory
        if done:
            lat = np.asarray([c.latency_s for c in done])
            ttft = np.asarray([c.ttft_s for c in done])
            out.update(
                latency_p50_s=round(float(np.percentile(lat, 50)), 6),
                latency_p95_s=round(float(np.percentile(lat, 95)), 6),
                ttft_p50_s=round(float(np.percentile(ttft, 50)), 6),
                ttft_p95_s=round(float(np.percentile(ttft, 95)), 6),
            )
        return out
