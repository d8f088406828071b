"""Driver of a ``requests`` cell: the program's paged engine behind its
continuous-batching ``Server``, fed by ``benchmark/traffic.py``.

The system under test is ``mpit_tpu.serve.Engine`` (paged bf16 cache,
chunked prefill, kernel decode, greedy) and ``Server``, built as
``python -m mpit_tpu.serve`` builds them, on weights the benchmark made.
The harness drives the server one tick at a time through its public
``run(max_ticks=tick + 1)``; a tick ends with its tokens on the host, so
a tick's end is a completion.

Two windows, by the traffic file's arrival process (a module under
``benchmark/arrivals/``, which says whether it is an open loop):

- closed (``backlog``, offline): every request is due at 0. Set-up ticks
  until every slot has emitted a token. The window opens at the end of
  that tick and closes at the end of the first tick at or after
  ``--seconds``; ``serve_tokens_per_s`` is the tokens those ticks emitted
  over the time between the two tick ends.
- open loop (``stratified``, ``poisson``): requests are submitted when
  due, never before, at the fixed rate of the file. After an unmeasured
  lead-in the window is ``--seconds`` of the schedule (in a traced run
  the traced part of it, ``tracing.TRACE_CAP_S``); the sample is
  the requests *due* inside it, each timed from its due time (not from
  its submit) to its first token and per output token after it, and the
  run ticks on until they are answered or ``answer_cap_s`` has passed.
  A request with no first token by then has failed.

Every tick is also timed for the host's side of it (``Ticker``), so that
a run that reads far off says on its ``window`` line why: one long tick
or all of them a little longer, this thread busy or waiting meanwhile,
the whole process without a CPU or only this thread.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np


def build_engine(ctx):
    """The engine as the serve CLI builds it, on the benchmark's weights."""
    import jax.numpy as jnp

    from mpit_tpu.models import GPT2Config
    from mpit_tpu.serve import Engine

    from benchmark import weights

    model, serve = ctx["config"], ctx["config"]["serve"]
    mcfg = GPT2Config(
        vocab_size=model["vocab_size"], max_seq_len=model["n_positions"],
        num_layers=model["n_layer"], num_heads=model["n_head"],
        d_model=model["n_embd"], d_ff=model["n_inner"])
    params = weights.to_program_tree(weights.make_stacked(
        model, ctx["seed"], jnp.dtype(serve["weights_dtype"])))
    pages_per_slot = model["n_positions"] // serve["kv_page_size"]
    return Engine(
        mcfg, params, slots=serve["slots"], max_len=model["n_positions"],
        seed=ctx["seed"], kv_pages=serve["slots"] * pages_per_slot,
        kv_page_size=serve["kv_page_size"],
        prefill_chunk=serve["prefill_chunk"])


class Heartbeat(threading.Thread):
    """Wakes every 20 ms and notes when: a gap between two wake-ups far
    over 20 ms means that this whole process got no CPU (a thread that
    waits on the device releases the interpreter, so a slow device alone
    leaves the beat regular)."""

    PERIOD_S = 0.02

    def __init__(self):
        super().__init__(daemon=True)
        self.beats = [time.perf_counter()]
        self._halt = threading.Event()

    def run(self):
        while not self._halt.wait(self.PERIOD_S):
            self.beats.append(time.perf_counter())

    def stop(self):
        self._halt.set()
        self.join()

    def longest_gap(self, t0: float, t1: float) -> float:
        """The longest gap between beats that overlaps ``[t0, t1]``."""
        b = np.asarray(self.beats + [time.perf_counter()])
        gaps = np.diff(b)
        inside = (b[1:] >= t0) & (b[:-1] <= t1)
        return float(gaps[inside].max()) if inside.any() else 0.0


def host_counters() -> dict:
    """CPU seconds so far of this thread, which ticks the server, and of
    the whole process. The same ticks cost this thread 1.1 to 2.1 s of a
    40 s window from run to run on a shared host, and the rate follows
    (PERF.md section 2)."""
    return {"thread_cpu_s": time.thread_time(), "process_cpu_s": time.process_time()}


class Ticker:
    """Ticks the server and keeps the running counts the window needs."""

    def __init__(self, server, recorder):
        self.server, self.recorder = server, recorder
        self._done_seen = 0
        self._done_tokens = 0
        self.samples = []  # (t_end, slot occupancy, live cache rows) per tick
        # Per tick: its start and end, and the CPU seconds this thread
        # spent in it (the rest of the tick it waited: for the device, or
        # for a CPU to run on).
        self.ticks = []
        self.heartbeat = Heartbeat()
        self.heartbeat.start()

    def tick(self) -> bool:
        """One tick; False when the server had nothing to do."""
        before = self.server.tick
        t0, cpu0 = time.perf_counter(), time.thread_time()
        self.server.run(max_ticks=before + 1)
        if self.server.tick == before:
            return False
        self.ticks.append((t0, time.perf_counter(), time.thread_time() - cpu0))
        if self.recorder is not None:
            occ = self.recorder.gauges.get(("slot_occupancy", ()))
            rows = sum(l.cache_fill() for l in self.server.live.values())
            self.samples.append((time.perf_counter(), occ, rows))
        return True

    def longest(self, t0: float, t1: float, k: int = 3) -> list:
        """The ``k`` longest ticks that ended in ``[t0, t1]``: for each,
        when it ended (seconds since ``t0``), its seconds, the CPU
        seconds this thread spent in it, and the longest gap of the
        heartbeat meanwhile."""
        inside = sorted((t for t in self.ticks if t0 <= t[1] <= t1),
                        key=lambda t: t[0] - t[1])[:k]
        return [{"ended_at_s": round(e - t0, 2), "seconds": round(e - s, 3),
                 "thread_cpu_s": round(cpu, 3),
                 "heartbeat_gap_s": round(self.heartbeat.longest_gap(s, e), 3)}
                for s, e, cpu in inside]

    def between_ticks(self, t0: float, t1: float) -> float:
        """The longest time between one tick's end and the next one's
        start inside ``[t0, t1]``: the harness's own share of the loop."""
        ts = [t for t in self.ticks if t0 <= t[1] <= t1]
        return max((b[0] - a[1] for a, b in zip(ts, ts[1:])), default=0.0)

    def host_report(self, t0: float, t1: float, at_open: dict) -> dict:
        """Why a run reads far off, if it does: see the module's docstring."""
        now = host_counters()
        return {"longest_ticks": self.longest(t0, t1),
                "longest_between_ticks_s": round(self.between_ticks(t0, t1), 3),
                "heartbeat_longest_gap_s": round(
                    self.heartbeat.longest_gap(t0, t1), 3),
                **{k: round(now[k] - at_open[k], 3) for k in now}}

    def emitted(self) -> int:
        """Tokens on the host so far: finished requests' and live ones'."""
        done = self.server.completed
        for c in done[self._done_seen:]:
            self._done_tokens += len(c.tokens)
        self._done_seen = len(done)
        return self._done_tokens + sum(
            len(l.tokens) for l in self.server.live.values())


def backlog_window(ctx, server, ticker, stream, as_request, marks, open_trace,
                   seconds) -> dict:
    """Closed, offline: the window runs from one tick's end to another's."""
    from benchmark import tracing
    from benchmark.device import memory_peak

    t_fill = time.perf_counter()
    for a in stream:
        server.submit(as_request(a))
    spoke = set()
    while len(spoke) < server.engine.slots:
        if not ticker.tick():
            raise RuntimeError("the backlog ran dry before every slot spoke")
        spoke.update(server.live)
    ctx["setup"]["fill_slots_s"] = time.perf_counter() - t_fill
    open_trace()
    ticker.tick()  # the window opens at the end of a tick of its own
    t_open, e_open = time.perf_counter(), ticker.emitted()
    host_at_open = host_counters()
    trace_t1 = None
    while True:
        if not ticker.tick():
            raise RuntimeError("the backlog ran dry inside the window")
        t_close = time.perf_counter()
        if ctx["trace"] and trace_t1 is None and (
                t_close - t_open >= min(seconds, tracing.TRACE_CAP_S)):
            tracing.stop(ctx["say"])  # the traced part is the window's first seconds
            trace_t1 = t_close
        if t_close - t_open >= seconds:
            break
    tokens = ticker.emitted() - e_open
    marks["peak"] = memory_peak(ctx["devices"], ctx["say"])
    done = [c for c in server.completed if t_open <= c.finish_t <= t_close]
    rate = tokens / (t_close - t_open)
    ctx["say"]("window", ticks=server.tick, tokens=tokens,
               t_open_to_close_s=t_close - t_open, finished_in_window=len(done),
               serve_tokens_per_s=rate,
               tokens_over_fixed_seconds_per_s=tokens / seconds,
               host=ticker.host_report(t_open, t_close, host_at_open))
    return {"t_open": t_open, "t_close": t_close, "trace_t1": trace_t1,
            "end_to_end": {"serve_tokens_per_s": rate}, "done": done,
            "attempted": len(done) + len(server.live), "failed": 0,
            "late_ms": []}


def open_loop_window(ctx, server, ticker, stream, as_request, marks,
                     open_trace, seconds) -> dict:
    """Open loop: the sample is the requests due inside the window."""
    from benchmark import tracing
    from benchmark.device import memory_peak

    mix = ctx["traffic"]
    lead, cap = mix["lead_in_s"], mix["answer_cap_s"]
    if ctx["trace"]:
        # A traced run's window is the traced seconds, as in training
        # (fewer where the mix says so: a dense one fills the trace):
        # writing the trace takes minutes on this thread, and from a
        # thread of its own ten (the loop goes on feeding the profiler),
        # with the host's cores taken and the queue growing meanwhile.
        seconds = min(seconds, mix.get("trace_seconds", tracing.TRACE_CAP_S))
    t_stream = time.perf_counter()
    t_open = t_stream + lead
    t_close = t_open + seconds
    sample = [a for a in stream if lead <= a.due_s < lead + seconds]
    want = {a.rid for a in sample}
    submit_t, nxt = {}, 0
    answered = seen_done = 0
    host_at_open = None
    while True:
        now = time.perf_counter()
        while nxt < len(stream) and stream[nxt].due_s <= now - t_stream:
            server.submit(as_request(stream[nxt]))
            submit_t[stream[nxt].rid] = time.perf_counter()
            nxt += 1
        # The profiler starts a little before the window opens (the start
        # took 0.05 s on the chip) and not before the stream: under load
        # the lead-in alone filled the device's trace buffer, and the
        # window's seconds held no operation.
        if ctx["trace"] and "mark" not in marks and (
                now >= t_open - min(lead, tracing.START_AHEAD_S)):
            open_trace()
        if now >= t_open and host_at_open is None:
            marks["queued_at_open"] = len(server.queue)
            host_at_open = host_counters()
        if now >= t_close and "peak" not in marks:
            marks["peak"] = memory_peak(ctx["devices"], ctx["say"])
            marks["queued_at_close"] = len(server.queue)
        for c in server.completed[seen_done:]:
            answered += c.rid in want
        seen_done = len(server.completed)
        if now >= t_close and (answered == len(want) or now >= t_close + cap):
            break
        if not ticker.tick():  # idle: sleep to the next arrival
            wake = stream[nxt].due_s + t_stream if nxt < len(stream) else now + 0.02
            time.sleep(max(0.0, min(wake - time.perf_counter(), 0.02)))
    t_end = time.perf_counter()
    if ctx["trace"]:
        tracing.stop(ctx["say"])
    due_t = {a.rid: t_stream + a.due_s for a in sample}
    finished = {c.rid: c for c in server.completed if c.rid in want}
    first = {rid: c.first_token_t for rid, c in finished.items()}
    for l in server.live.values():
        if l.req.rid in want and l.first_token_t:
            first[l.req.rid] = l.first_token_t
    # A request with no first token by the cap has failed; what it has
    # waited so far stands in the tail as its time.
    ttft = [1e3 * (first.get(r, t_end) - due_t[r]) for r in want]
    tpot = [1e3 * (c.finish_t - c.first_token_t) / (len(c.tokens) - 1)
            for c in finished.values() if len(c.tokens) > 1]
    if not first or not tpot:
        raise RuntimeError("no request due in the window was answered")
    late = [1e3 * (submit_t[r] - due_t[r]) for r in want if r in submit_t]
    # Beside the judged 75th percentile: the median and the tail, for
    # ``run_value`` to read (a tail wants some hundreds of requests due).
    end_to_end = {"ttft_p75_ms": percentile(ttft, 75),
                  "ttft_p50_ms": percentile(ttft, 50),
                  "ttft_p95_ms": percentile(ttft, 95),
                  "tpot_p75_ms": percentile(tpot, 75)}
    ctx["say"]("window", due_in_window=len(want), first_tokens=len(first),
               finished=len(finished),
               ttft_max_ms=max(ttft), tpot_p50_ms=percentile(tpot, 50),
               late_p95_ms=percentile(late, 95), **end_to_end,
               ran_past_window_s=t_end - t_close,
               queued_at_open=marks.get("queued_at_open"),
               queued_at_close=marks.get("queued_at_close"),
               still_queued=len(server.queue), still_live=len(server.live),
               host=ticker.host_report(t_open, t_end, host_at_open))
    return {"t_open": t_open, "t_close": t_close,
            # A profiler not yet started as the window opened traces less.
            "trace_t0": max(t_open, marks.get("mark", t_open)),
            "trace_t1": t_close if ctx["trace"] else None,
            "end_to_end": end_to_end, "done": list(finished.values()),
            "attempted": len(want), "failed": len(want) - len(first),
            "late_ms": late}


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def run(ctx) -> dict:
    t_imports = time.perf_counter()
    import jax

    from mpit_tpu import obs
    from mpit_tpu.serve import Request, Server, warm_engine

    from benchmark import checks, tracing
    from benchmark import traffic as tg

    model, mix, setup = ctx["config"], ctx["traffic"], ctx["setup"]
    tracing_on = ctx["trace"]
    setup["import_program_s"] = time.perf_counter() - t_imports
    seconds = ctx["seconds"]

    t0 = time.perf_counter()
    engine = build_engine(ctx)
    jax.block_until_ready(engine.params)
    setup["weights_and_engine_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm_engine(engine)
    setup["warm_engine_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    stream = tg.arrivals(mix, model["vocab_size"], ctx["seed"], ctx["seconds"])
    setup["traffic_s"] = time.perf_counter() - t0

    recorder = obs.enable(obs.Recorder()) if tracing_on else None
    server = Server(engine)
    ticker = Ticker(server, recorder)
    as_request = lambda a: Request(
        rid=a.rid, prompt=a.prompt, max_new_tokens=a.max_new_tokens)
    marks = {}

    def open_trace():
        if tracing_on:
            marks["mark"] = tracing.start(ctx["trace_dir"], ctx["say"])

    window = open_loop_window if tg.process_of(mix).OPEN_LOOP else backlog_window
    w = window(ctx, server, ticker, stream, as_request, marks, open_trace, seconds)
    t_open, t_close, trace_t1 = w["t_open"], w["t_close"], w["trace_t1"]

    trace_t0 = w.get("trace_t0", t_open)
    if recorder is not None:
        obs.span_at("bench_window", t_open, t_close, t_open=t_open)
        spans = tracing.host_spans(recorder, trace_t0, trace_t1)
        obs.disable()
    else:
        spans = []
    samples = [s for s in ticker.samples if trace_t0 <= s[0] <= (trace_t1 or 0)]

    ticker.heartbeat.stop()
    del server, ticker, engine
    gc.collect()
    t0 = time.perf_counter()
    correct = checks.requests(ctx, w["done"])
    setup["check_after_window_s"] = time.perf_counter() - t0
    return {
        "t_open": t_open, "t_close": t_close, "end_to_end": w["end_to_end"],
        "correct": correct, "attempted": w["attempted"], "failed": w["failed"],
        "memory_peak_bytes": marks["peak"],
        "trace_t0": trace_t0, "trace_t1": trace_t1 or t_close,
        "trace_mark": marks.get("mark"), "host_spans": spans,
        "tick_samples": samples, "late_ms": w["late_ms"],
    }
