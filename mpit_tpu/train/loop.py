"""The training loop: steps, metrics, checkpoints, eval.

The reference's loop is the per-worker ``for each minibatch`` in its
``asyncsgd/`` scripts plus the server's message loop (SURVEY.md §4.2); here
a single :class:`Trainer` drives the jitted SPMD step over a prefetched
sharded data stream.

:func:`hardened_loop` is the production drive loop shared by every
execution path (``runner.run_spmd`` and the gpt2 parallel tiers): one
implementation of prefetch, SIGTERM preemption drain, divergence
guard + older-checkpoint backoff, the profile trace window, periodic
eval, and checkpoint cadence — so the recovery story (RECOVERY.md)
applies to the longest-lived runs (the 3-D/EP tiers on pods), not just
the DP path (round-2 verdict item 4).

Asynchronous host path (ISSUE 2 tentpole): PR 1's spans attributed the
8–10% app-path throughput gap to the loop's synchronous ``float(loss)``
fences — every log/dispatch fence stalled host dispatch until the device
caught up and the value crossed the wire. The fences are now a small
in-loop pipeline: at each fence the loop *starts* a device→host copy
(``copy_to_host_async``) and consumes the value up to ``fetch_lag``
fences later, so the host keeps dispatching while metrics are in flight
— the MXNET-MPI transformation (arXiv:1801.03855) of making host/comm
work an overlapped node in the dispatch graph rather than an epoch
barrier. Consequences, all bounded and documented: divergence DETECTION
is delayed by ≤ ``fetch_lag`` fence intervals (the restore *policy* is
unchanged — ``train/guard.py``); checkpoint/eval/final steps drain the
pipeline first, so a checkpoint is still never written on an unchecked
loss; throughput windows are measured between fence *consumptions*,
which in steady state track device completion exactly like the old
blocking fetches.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Iterator

import jax

from mpit_tpu import obs
from mpit_tpu.data.loader import Prefetcher
from mpit_tpu.train.guard import Diverged, DivergenceGuard
from mpit_tpu.train.metrics import MetricLogger, Throughput
from mpit_tpu.train.step import TrainState


class _MetricFetch:
    """One in-flight async host fetch of a fence step's metrics.

    Construction starts the device→host copies; blocking happens in the
    loop's consume, up to ``fetch_lag`` fences later. ``kind``:

    - ``"log"`` — a log point: guard-check + metric log on consume;
    - ``"save"`` — a pre-checkpoint check (sync path only): guard-check,
      no log record;
    - ``"fence"`` — a dispatch-depth bound: fetch only (same as the old
      ``dispatch_fence`` fetch, which never fed the guard).
    """

    __slots__ = ("step", "metrics", "kind")

    def __init__(self, step: int, metrics: dict, kind: str):
        self.step = step
        self.kind = kind
        # Fence entries only ever need the loss; log entries publish the
        # whole metrics dict, so copy everything they will read.
        self.metrics = (
            dict(metrics) if kind == "log" else {"loss": metrics["loss"]}
        )
        for v in self.metrics.values():
            start = getattr(v, "copy_to_host_async", None)
            if start is not None:
                try:
                    start()
                except Exception:
                    pass  # best-effort: float() below fetches regardless


def hardened_loop(
    world,
    state: Any,
    step_fn: Callable,
    batches: Iterator,
    *,
    steps: int,
    transform: Callable | None = None,
    axis: str = "data",
    items_per_batch: int | None = None,
    log_every: int = 50,
    logger: MetricLogger | None = None,
    ckpt=None,
    ckpt_every: int = 0,
    specs: Callable | None = None,
    max_restores: int = 1,
    spike_factor: float = 0.0,
    profile_dir: str = "",
    final_save: bool = False,
    eval_every: int = 0,
    eval_hook: Callable | None = None,
    dispatch_fence: int = 32,
    fetch_lag: int = 2,
    host_transform: Callable | None = None,
    prefetch_workers: int = 1,
    prefetch_depth: int = 2,
    prefetch_max_depth: int = 8,
    sentinel=None,
    roofline: bool = False,
) -> dict:
    """Drive ``step_fn`` from ``state`` to ``steps`` with full hardening.

    Args:
      state: initial (possibly checkpoint-restored) state; ``state.step``
        is the authoritative resume point.
      step_fn: jitted ``(state, device_batch) -> (state, metrics)``;
        ``metrics`` must contain ``"loss"``.
      batches: host-side batch iterator, already fast-forwarded past
        ``int(state.step)`` consumed batches (seek-based resume is the
        caller's job — it owns the dataset).
      transform: host batch → device batch (slicing + ``shard_batch``
        with the tier's PartitionSpecs). Default: shard the leading dim
        over ``axis``. Runs on the prefetch pipeline's device stage,
        overlapping compute.
      ckpt / ckpt_every / specs: CheckpointManager, save cadence, and a
        zero-arg callable returning the state's PartitionSpecs (needed
        for divergence restore).
      max_restores / spike_factor: divergence policy (train/guard.py) —
        non-finite or spiking loss restores the newest checkpoint OLDER
        than the previous restore target, up to ``max_restores`` times.
      profile_dir: capture a ``jax.profiler`` trace of steps 2..5 of
        this run (clamped into range).
      final_save: checkpoint at the natural end of the run too (the
        tier paths' contract; run_spmd relies on cadence only).
      eval_every / eval_hook: every N steps (and at the last step) call
        ``eval_hook(state) -> dict`` and log it under ``eval_*`` keys —
        the periodic full-val-split sweep hangs off this.
      dispatch_fence: host-fetch the loss at least every N steps even
        between log points, bounding async-dispatch depth. Two reasons:
        the fake-CPU-mesh backend's in-process collectives starve their
        rendezvous when ~60 collective programs are enqueued unfetched
        ("Expected 8 threads to join" aborts — observed at 1 host core),
        and an unbounded host-ahead window makes preemption drain and
        divergence detection arbitrarily stale. With ``fetch_lag > 0``
        the bound is enforced on the host's *fetched watermark*: pending
        fetches are consumed (oldest first) until the last step the host
        has a value from is within ``dispatch_fence`` of the current
        step, falling back to a synchronous fetch of the current loss
        when no in-flight fence can advance it that far (sparse-log
        stretches) — so unfetched dispatch depth never exceeds
        ``dispatch_fence`` plus one fence interval.
      fetch_lag: async metric-fetch window (ISSUE 2). At each fence the
        loop starts a device→host copy and blocks only when more than
        ``fetch_lag`` fetches are in flight — host dispatch overlaps the
        metric wire time instead of stalling on it. ``0`` restores the
        fully synchronous fences. Divergence detection is delayed by at
        most ``fetch_lag`` fence intervals (checkpoint and eval points
        drain the pipeline first and stay exactly as safe as before).
      sentinel: optional :class:`mpit_tpu.obs.Sentinel` (ISSUE 3) — the
        step-time anomaly detector. When given, the loop feeds it the
        host-side step wall, prefetch wait, and host-fence durations
        every iteration; it emits structured ``anomaly`` instant events
        (spike / sustained-degradation / prefetch-starvation) into the
        obs trace and its :meth:`~mpit_tpu.obs.Sentinel.report` is
        attached to the result as ``out["sentinel"]`` — the
        ``DivergenceGuard``-for-throughput hook. ``None`` (default)
        costs nothing.
      roofline: register the step's ``cost_analysis()`` FLOPs/bytes
        with the installed recorder before the first step (ISSUE 8) —
        ``obs.summary()`` then reports the run's ``step`` phase
        mfu/hbm utilization against the chip peaks (on-chip only;
        platform-labeled modeled cost elsewhere). Opt-in: the cost
        query is one extra AOT compile of the step's HLO (a
        persistent-cache replay where bench enabled one). No-op when
        obs is disabled.
      host_transform / prefetch_workers / prefetch_depth /
        prefetch_max_depth: the prefetch pipeline (``data/loader.py``):
        ``host_transform`` runs on ``prefetch_workers`` threads before
        device placement — put decode/augment there to overlap it
        across batches. Device-side depth adapts between
        ``prefetch_depth`` and ``prefetch_max_depth`` while the loop
        observably starves; set them equal to pin the buffer (each unit
        of depth holds one staged device batch — size it against HBM).

    Returns ``{"state", "losses", "restores", "preempted", "steps",
    "eval"}`` (``eval``: the last eval_hook result, or absent).
    """
    if ckpt is not None and specs is None:
        # Fail at configuration time, not deep in the divergence-restore
        # path with an opaque `'NoneType' object is not callable` (round-3
        # advisor finding): restore needs the state's PartitionSpecs.
        raise ValueError(
            "hardened_loop: `ckpt` given without `specs` — divergence "
            "restore re-shards the checkpoint and needs a zero-arg "
            "callable returning the state's PartitionSpecs"
        )
    logger = logger or MetricLogger()
    start_step = int(state.step)
    items = items_per_batch
    log_t: float | None = None  # wall clock at the last consumed log fetch
    log_step = start_step

    prof_window = None
    if profile_dir and steps > start_step:
        last = steps - 1
        prof_window = (min(start_step + 2, last), min(start_step + 5, last))

    # Failure detection (SURVEY.md §6): a non-finite/spiking loss at a
    # checked step triggers a restore (when checkpoints exist) and the run
    # continues — up to max_restores times. Checks run at BOTH log and
    # save points, so a checkpoint is never written on a failing loss —
    # save points drain the async pipeline first, preserving that
    # invariant under fetch_lag > 0. (Residual window: loss at step t
    # certifies the params *entering* t, so the state saved at t could in
    # principle already be poisoned while loss_t is finite — which is why
    # repeat divergence steps back to an OLDER checkpoint instead of
    # reloading the same one.) After a restore the stream keeps its
    # position: an interrupted data order is part of divergence recovery;
    # exact replay is only for clean resume.
    fence_interval = (
        min(log_every, dispatch_fence) if dispatch_fence else log_every
    )
    guard_ = DivergenceGuard(
        spike_factor=spike_factor, lag=fetch_lag, fence=fence_interval
    )
    restores = 0
    restore_before: int | None = None  # ceiling for the next restore target

    # Preemption drain (SURVEY.md §6 recovery row; RECOVERY.md): pod
    # maintenance/eviction delivers SIGTERM with a grace window. Catch it,
    # finish the in-flight step, write a final checkpoint, and exit
    # cleanly so the rescheduled job resumes from it.
    preempted = {"flag": False}

    def _on_term(signum, frame):
        del signum, frame
        preempted["flag"] = True

    prev_handler = None
    handler_installed = False
    try:
        import signal

        prev_handler = signal.signal(signal.SIGTERM, _on_term)
        handler_installed = True
    except ValueError:
        pass  # not the main thread (tests, embedded use): no handler

    loss_trace: list[tuple[int, float]] = []
    rate_trace: list[float] = []
    # Compile observability (ISSUE 8): the first step's XLA compile
    # becomes a visible `compile` span (an overlay of that step's own
    # span — obs.core._OVERLAY_PHASES) + counter; any LATER compile
    # is an unexpected recompile (a shape/dtype leak into the
    # step) — instant + sentinel note. Detection is JAX's own compile
    # events in this thread while the step call is open (obs.startup):
    # a step that does not compile is not probed.
    compile_watch = obs.roofline.CompileWatch(
        expected=1, scope="train", sentinel=sentinel
    )
    # Executed grad-sync mode stamp (ISSUE 9 satellite): label the step
    # spans the way serve stamps ``attention=`` — "ring" off-TPU runs
    # the fallback, and bench/traces must attribute that honestly. The
    # default psum mode stays unlabeled (spans byte-identical to seed).
    gs_mode = getattr(step_fn, "grad_sync_mode", None)
    step_attrs = {"grad_sync": gs_mode} if gs_mode and gs_mode != "psum" else {}
    pending: deque[_MetricFetch] = deque()
    last_eval: dict | None = None
    tracing = False
    trace_done = False
    step = start_step
    sent_prev_t: float | None = None  # sentinel iteration-wall anchor
    # Dispatch-depth watermark: the most recent step whose metrics the
    # host has actually fetched. Consuming a PENDING fetch only syncs
    # the device up to that entry's step, so bounding "oldest pending
    # age" alone would let unfetched dispatch depth reach ~2x
    # dispatch_fence between sparse fences (round-6 review finding —
    # past the fake-CPU-mesh backend's ~60-program rendezvous abort).
    # The loop instead bounds step+1 - synced directly, falling back to
    # a synchronous fetch of the CURRENT step when no in-flight fence
    # can advance the watermark far enough.
    synced = start_step

    def _consume(
        entry: _MetricFetch,
        at_step: int,
        check: bool = True,
        close: bool = True,
    ):
        """Block on one in-flight fetch; guard-check and log it.

        ``at_step`` is where the loop's host side stands now — the
        detection point the guard validates against its lag window.
        ``close``: whether this consume may end a throughput window.
        When a drain consumes several pending fetches back-to-back,
        only the LAST one's wall clock is a real fence time — the
        earlier ones return near-instantly and a per-entry window
        would divide by ~zero. Unclosed entries still log (without
        ``items_per_sec``); the next closing fetch credits their steps
        over the full wall interval, so the rate stays exact.
        """
        nonlocal log_t, log_step, synced
        with obs.span(
            "host_fence", why=entry.kind, lag=at_step - entry.step
        ):
            fence_t0 = time.perf_counter()
            vals = {k: float(v) for k, v in entry.metrics.items()}
        if sentinel is not None:
            sentinel.observe(
                "host_fence", at_step, time.perf_counter() - fence_t0
            )
        synced = max(synced, entry.step)
        if entry.kind == "fence":
            return
        if check:
            guard_.check(entry.step, vals["loss"], detected_step=at_step)
        if entry.kind != "log":
            return
        loss_trace.append((entry.step, vals["loss"]))
        # Interval throughput, measured BETWEEN fence consumptions: the
        # float() above blocked until the device completed entry.step,
        # so in steady state the interval's wall clock covers real
        # device execution — same convention as the old blocking
        # fetches. (A per-step tick would time the host DISPATCH of
        # steps the device hasn't run yet — the round-5 rehearsal
        # measured 52k "img/s" that way.) First interval (compilation)
        # excluded by construction.
        if close:
            now = time.perf_counter()
            if items and log_t is not None:
                rate = items * (entry.step - log_step) / (now - log_t)
                vals["items_per_sec"] = round(rate, 2)
                rate_trace.append(rate)
            log_t, log_step = now, entry.step
        logger.log(entry.step, vals)

    def _drain(at_step: int, check: bool = True, close_last: bool = True):
        """Consume every in-flight fetch, closing the throughput window
        only on the final (really-blocking) one."""
        while pending:
            e = pending.popleft()
            _consume(e, at_step, check=check,
                     close=close_last and not pending)

    try:
        with Prefetcher(
            world,
            batches,
            axis=axis,
            transform=transform,
            host_transform=host_transform,
            host_workers=prefetch_workers,
            depth=prefetch_depth,
            max_depth=prefetch_max_depth,
            adaptive=prefetch_max_depth > prefetch_depth,
        ) as stream:
            while True:
                # Telemetry (mpit_tpu.obs, no-op unless obs.enable()d):
                # the loop's phases are spanned so a Chrome-trace export
                # shows where each step's wall clock went — prefetch
                # wait vs dispatch vs host fence vs eval/checkpoint.
                exhausted = False
                pf_t0 = time.perf_counter()
                with obs.span("prefetch_wait"):
                    try:
                        batch = next(stream)
                    except StopIteration:
                        exhausted = True
                pf_s = time.perf_counter() - pf_t0
                try:
                    if exhausted or step >= steps:
                        # End of the run: consume whatever is still in
                        # flight so the last logged windows (and any
                        # delayed divergence) land before we return.
                        _drain(step)
                        break
                    if preempted["flag"]:
                        # Drain WITH guard checks (round-6 review): up
                        # to fetch_lag fenced losses are in flight here,
                        # and the drain checkpoint must not ship a
                        # trajectory one of them already condemns. A
                        # Diverged lands in the restore handler below —
                        # the next iteration re-enters this branch with
                        # the restored state and saves THAT. (The
                        # current step's own loss stays unchecked,
                        # exactly as in the synchronous loop.)
                        _drain(step)
                        if ckpt:
                            with obs.span("checkpoint_save", reason="preempted"):
                                if ckpt.latest_step() != step:  # cadence saved it
                                    ckpt.save(step, state)
                                ckpt.wait()
                        logger.log(
                            step,
                            {"event": "preempted_checkpoint_and_exit",
                             "resumable": bool(ckpt)},
                        )
                        break
                    if (
                        prof_window
                        and not tracing
                        and not trace_done
                        and step == prof_window[0]
                    ):
                        jax.profiler.start_trace(profile_dir)
                        tracing = True
                    if roofline and step == start_step and obs.enabled():
                        # Register once, BEFORE the first step runs (the
                        # step may donate its input buffers — lowering
                        # afterwards would touch deleted arrays).
                        try:
                            with obs.span("roofline_cost"):
                                cost = obs.roofline.cost_from_fn(
                                    step_fn, state, batch
                                )
                            obs.roofline.register_cost(
                                "step",
                                flops=cost["flops"],
                                hbm_bytes=cost["hbm_bytes"],
                                platform=jax.devices()[0].platform,
                            )
                        except Exception:
                            pass  # cost support is best-effort telemetry
                    step_t0 = time.perf_counter()
                    with obs.span("step", **step_attrs):
                        state, metrics = compile_watch.call(
                            "step", step_fn, state, batch
                        )
                    if step == start_step:
                        # A step that compiled waited for its output
                        # (CompileWatch): the trainer is ready.
                        obs.startup.ready("train")
                    if sentinel is not None:
                        # Host-side wall per iteration (dispatch time on
                        # the async path — spikes here mean the HOST
                        # stalled; device-completion spikes surface at
                        # the fences the sentinel also watches). The
                        # iteration wall (observe-to-observe, covering
                        # the fences in between) is the starvation
                        # check's denominator.
                        now = time.perf_counter()
                        sentinel.observe_step(
                            step,
                            step_s=now - step_t0,
                            prefetch_wait_s=pf_s,
                            iteration_s=(
                                now - sent_prev_t
                                if sent_prev_t is not None else None
                            ),
                        )
                        sent_prev_t = now
                    if tracing and step >= prof_window[1]:
                        with obs.span("host_fence", why="trace_window"):
                            float(metrics["loss"])  # host fetch: trace covers real work
                        synced = step + 1
                        jax.profiler.stop_trace()
                        tracing = False
                        trace_done = True
                    should_log = (step + 1) % log_every == 0 or step + 1 == steps
                    should_save = bool(
                        ckpt and ckpt_every and (step + 1) % ckpt_every == 0
                    )
                    should_eval = bool(
                        eval_hook
                        and eval_every
                        and ((step + 1) % eval_every == 0 or step + 1 == steps)
                    )
                    fence_due = bool(
                        dispatch_fence and (step + 1) % dispatch_fence == 0
                    )
                    # Sync points: checkpoint saves must never race an
                    # unchecked loss; eval blocks on state anyway; the
                    # last step must land in the result synchronously.
                    sync_point = should_save or should_eval or step + 1 == steps
                    if fetch_lag > 0 and not sync_point:
                        if should_log or fence_due:
                            pending.append(_MetricFetch(
                                step + 1, metrics,
                                "log" if should_log else "fence",
                            ))
                        burst: list[_MetricFetch] = []
                        ahead = synced
                        while pending and (
                            len(pending) > fetch_lag
                            or (
                                dispatch_fence
                                and step + 1 - ahead >= dispatch_fence
                            )
                        ):
                            burst.append(pending.popleft())
                            ahead = burst[-1].step
                        for i, e in enumerate(burst):
                            _consume(e, step + 1, close=i == len(burst) - 1)
                        if (
                            dispatch_fence
                            and step + 1 - synced >= dispatch_fence
                        ):
                            # No in-flight fence reaches the bound (a
                            # sparse-log stretch): the old synchronous
                            # dispatch fence on the current step.
                            with obs.span("host_fence", why="dispatch_fence"):
                                float(metrics["loss"])
                            synced = step + 1
                    else:
                        # The synchronous path (fetch_lag=0, or a sync
                        # point): drain the pipeline, then check the
                        # current loss exactly like the pre-async loop.
                        # The drain's wall clock is not a fence time of
                        # its entries (the sync fetch below is about to
                        # block for real), so it closes no window.
                        _drain(step + 1, close_last=False)
                        if should_log or should_save:
                            _consume(
                                _MetricFetch(
                                    step + 1, metrics,
                                    "log" if should_log else "save",
                                ),
                                step + 1,
                            )
                            if should_save:
                                with obs.span("checkpoint_save"):
                                    ckpt.save(step + 1, state)
                                # A new guard-passing checkpoint supersedes
                                # the poisoned-latest suspicion from a past
                                # restore.
                                restore_before = None
                        elif fence_due:
                            with obs.span("host_fence", why="dispatch_fence"):
                                float(metrics["loss"])  # bound async-dispatch depth
                            synced = step + 1
                    if should_eval:
                        with obs.span("eval"):
                            last_eval = eval_hook(state)
                        if last_eval:
                            logger.log(
                                step + 1,
                                {"eval_" + k: v for k, v in last_eval.items()},
                            )
                except Diverged as dvg:
                    candidates = [
                        s
                        for s in (ckpt.all_steps() if ckpt else [])
                        if restore_before is None or s < restore_before
                    ]
                    if not candidates or restores >= max_restores:
                        raise
                    target = max(candidates)
                    restores += 1
                    if tracing:
                        # The step counter jumps backward across the
                        # restore; a window left open would silently
                        # span the rollback discontinuity (round-3
                        # advisor finding). End the capture here.
                        jax.profiler.stop_trace()
                        tracing = False
                        trace_done = True
                    with obs.span("divergence_restore", target=target):
                        state = ckpt.restore(state, specs(), step=target)
                    step = int(state.step)
                    restore_before = target
                    guard_.reset()
                    # In-flight fetches belong to the abandoned (post-
                    # divergence) trajectory; the loss trace rebases to
                    # the restored step — both delayed and synchronous
                    # detection land on the same restore point.
                    pending.clear()
                    synced = step  # the restore itself fetched the state
                    loss_trace = [(s, l) for s, l in loss_trace if s <= step]
                    # Throughput bookkeeping must not straddle the
                    # rollback: the step counter just jumped backward,
                    # so a live log window would compute a NEGATIVE
                    # items_per_sec for the first post-restore log
                    # (round-5 advisor finding). Start a fresh window.
                    log_t, log_step = None, step
                    logger.log(
                        step,
                        {"event": "restored_after_divergence",
                         "bad_loss": dvg.loss, "restores": restores,
                         "diverged_step": dvg.step,
                         "detected_step": dvg.detected_step},
                    )
                    continue
                step += 1
    finally:
        if tracing:  # run ended (or raised) inside the window
            jax.profiler.stop_trace()
        if handler_installed:
            # Restore unconditionally (getsignal-None priors included —
            # prev_handler None means "installed outside Python", and
            # SIG_DFL is the closest restorable equivalent).
            import signal

            signal.signal(
                signal.SIGTERM,
                prev_handler if prev_handler is not None else signal.SIG_DFL,
            )
    if ckpt:
        with obs.span("checkpoint_save", reason="final"):
            if (
                final_save
                and not preempted["flag"]
                and step > start_step
                and ckpt.latest_step() != step  # cadence already saved here
            ):
                ckpt.save(step, state)
            ckpt.wait()

    losses = [l for _, l in loss_trace]
    out = {
        "state": state,
        "steps": int(state.step),
        "losses": losses,
        "final_loss": losses[-1] if losses else float("nan"),
        "restores": restores,
        "preempted": preempted["flag"],
    }
    if rate_trace:
        # Best logged window (same convention as bench.py's best-of-N)
        # — the e2e img/s the rehearsal script reads.
        out["items_per_sec"] = round(max(rate_trace), 2)
        out["items_per_sec_last"] = round(rate_trace[-1], 2)
        # Mean over ALL logged windows: the stable figure for runs whose
        # per-window rate is scheduling-noisy (the elastic tier's
        # replica threads share host cores — ISSUE 11's healthy-vs-
        # straggler throughput comparison reads this, not the max).
        out["items_per_sec_mean"] = round(
            sum(rate_trace) / len(rate_trace), 2
        )
    if compile_watch.compiles:
        # Lifetime compiles this loop observed (expected: 1, the first
        # step); unexpected ones were already flagged live.
        out["compiles"] = compile_watch.compiles
    if last_eval:  # an empty sweep (val split < one batch) records nothing
        out["eval"] = last_eval
    if sentinel is not None:
        # The throughput verdict next to the loss one: anomaly counts +
        # records + per-metric baselines (obs/sentinel.py). Logged so
        # the JSONL stream carries it even when the caller drops `out`.
        out["sentinel"] = sentinel.report()
        logger.log(
            step,
            {"event": "sentinel_report",
             "sentinel_clean": out["sentinel"]["clean"],
             **{f"sentinel_{k}": v
                for k, v in out["sentinel"]["anomaly_counts"].items()}},
        )
    if obs.enabled():
        # End-of-run roll-up (ISSUE 1 tentpole): phase totals + top
        # collectives by modeled wire bytes, logged so the JSONL stream
        # carries the breakdown, and attached to the result for callers
        # (bench, rehearsal scripts) to persist. The full timeline is
        # the caller's to export (obs.export_chrome_trace).
        out["obs"] = obs.summary()
        totals = {
            f"obs_{name}_total_s": round(p["total_s"], 4)
            for name, p in out["obs"]["phases"].items()
        }
        if totals:
            logger.log(step, {"event": "obs_summary", **totals})
    return out


class Trainer:
    """Drive ``step_fn`` over a data stream with logging and checkpoints.

    Args:
      world: communication World.
      state: initial TrainState (from ``make_train_step``'s init_fn, or a
        checkpoint restore).
      step_fn: jitted ``(state, batch) -> (state, metrics)``.
      batches: host-side batch iterator (numpy pytrees); sharded and
        prefetched internally.
      items_per_batch: global batch size, for the items/sec meter.
      log_every: metric log interval (steps).
      logger: MetricLogger (default: stdout only).
      checkpoint: optional (CheckpointManager, save_every) pair.
      hooks: callables ``hook(step, state, metrics)`` run at log points.
    """

    def __init__(
        self,
        world,
        state: TrainState,
        step_fn: Callable,
        batches: Iterator,
        *,
        items_per_batch: int | None = None,
        log_every: int = 50,
        logger: MetricLogger | None = None,
        checkpoint: tuple[Any, int] | None = None,
        hooks: list[Callable] | None = None,
        axis: str = "data",
    ):
        self.world = world
        self.state = state
        self._step_fn = step_fn
        self._batches = batches
        self._items = items_per_batch
        self._log_every = log_every
        self._logger = logger or MetricLogger()
        self._ckpt = checkpoint
        self._hooks = hooks or []
        self._axis = axis
        self._throughput = Throughput()

    @property
    def step(self) -> int:
        return int(self.state.step)

    def train(self, num_steps: int) -> dict[str, float]:
        """Run ``num_steps`` steps; returns the last logged metrics."""
        last: dict[str, float] = {}
        # Host-side step counter: reading state.step every iteration would
        # block dispatch on the just-enqueued step and serialize host/device.
        step = int(self.state.step)
        tick_step = step
        with Prefetcher(self.world, self._batches, axis=self._axis) as stream:
            for _ in range(num_steps):
                batch = next(stream)
                self.state, metrics = self._step_fn(self.state, batch)
                step += 1
                if step % self._log_every == 0 or step == 1:
                    # device sync happens here (float() blocks on the step)
                    last = {k: float(v) for k, v in metrics.items()}
                    if self._items is not None:
                        rate = self._throughput.tick(
                            self._items * (step - tick_step)
                        )
                        tick_step = step
                        if rate is not None:
                            last["items_per_sec"] = rate
                    self._logger.log(step, last)
                    for hook in self._hooks:
                        hook(step, self.state, last)
                if self._ckpt is not None:
                    mgr, every = self._ckpt
                    if step % every == 0:
                        mgr.save(step, self.state)
        return last

    def evaluate(
        self, eval_step: Callable, batches: Iterator, num_batches: int
    ) -> dict[str, float]:
        """Average ``eval_step`` metrics over ``num_batches``."""
        totals: dict[str, float] = {}
        with Prefetcher(self.world, batches, axis=self._axis) as stream:
            for _ in range(num_batches):
                metrics = eval_step(self.state, next(stream))
                for k, v in metrics.items():
                    totals[k] = totals.get(k, 0.0) + float(v)
        return {k: v / num_batches for k, v in totals.items()}
