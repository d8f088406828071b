"""Driver of a ``family_requests`` cell: ``requests.py``'s windows round
an engine of whatever family the configuration names.

``requests.py`` builds a ``GPT2Config`` engine and calls the GPT-2 check.
This driver reuses its ``Ticker``, ``backlog_window`` and
``open_loop_window`` by import and finds what is particular to a family
by the configuration's ``family``, as modules under
``benchmark/families/<family>/``: ``engine`` (the program's engine,
through the program's model interface), ``weights``, ``reference``,
``costs`` and ``check`` (the comparison that decides ``correct``). The
next family is files there too.

The program's symbols this driver needs are imported here, at the top: on
a program that lacks them the cell fails at import, within seconds and
before any device memory is taken.

Beside what ``requests.py`` samples a tick, a traced run keeps the gauges
the engine sets from what its steps count (``tick_gauges``: the rows a
chunk tick computed and those that were prompt tokens, the experts a
decode tick hit and their load), for the family's readers.

A rehearsal takes its tiny sizes from the family too
(``families/<family>/tiny.json``, in the form of ``tests/tiny.json``):
``run.py`` has shrunk the cell by the ``--rehearse`` file before it calls
the driver, and that file holds the cells that were there before the
family was, so the family's sizes go over what it left.
"""

from __future__ import annotations

import gc
import importlib
import json
import os
import time

from mpit_tpu import obs
from mpit_tpu.models.serving import ServeModel  # noqa: F401  (the interface)
from mpit_tpu.serve import Request, Server, warm_engine

from benchmark.drivers.requests import (
    Ticker,
    backlog_window,
    open_loop_window,
)

# Gauges kept a tick, by the name the readers use.
_GAUGES = {
    "prefill_rows_computed": ("prefill_rows_computed", ()),
    "prefill_rows_valid": ("prefill_rows_valid", ()),
    "experts_hit_decode": ("moe_experts_hit", (("phase", "decode"),)),
    "load_max_over_mean_decode": (
        "moe_load_max_over_mean", (("phase", "decode"),)),
}


class GaugeTicker(Ticker):
    """``Ticker`` that also keeps, for every tick, the gauges that tick
    set (a gauge holds its last value, so each is cleared before the tick
    and read after it: a tick with no chunk has no rows)."""

    def __init__(self, server, recorder):
        super().__init__(server, recorder)
        self.tick_gauges = []  # {"t": tick end, "rows": live rows, ...}

    def tick(self) -> bool:
        if self.recorder is None:
            return super().tick()
        for key in _GAUGES.values():
            self.recorder.gauges.pop(key, None)
        if not super().tick():
            return False
        t_end, _, rows = self.samples[-1]
        got = {name: self.recorder.gauges[key]
               for name, key in _GAUGES.items() if key in self.recorder.gauges}
        self.tick_gauges.append({"t": t_end, "rows": rows, **got})
        return True


def family_module(ctx, name: str):
    return importlib.import_module(
        f"benchmark.families.{ctx['config']['family']}.{name}")


def shrink_for_rehearsal(ctx) -> None:
    """The family's tiny sizes over the cell's configuration and traffic."""
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)), "families",
                        ctx["config"]["family"], "tiny.json")
    with open(path) as f:
        tiny = json.load(f)
    cell = ctx["cell"]
    ctx["config"] = {**ctx["config"], **tiny["configs"].get(cell["config"], {})}
    ctx["traffic"] = {**ctx["traffic"],
                      **tiny["traffic"].get(cell["traffic"], {})}


def run(ctx) -> dict:
    import jax

    from benchmark import tracing
    from benchmark import traffic as tg
    from benchmark.device import memory_peak

    if ctx["rehearse"]:
        shrink_for_rehearsal(ctx)
    model, mix, setup = ctx["config"], ctx["traffic"], ctx["setup"]
    tracing_on, seconds = ctx["trace"], ctx["seconds"]

    def held(stage):  # what the device holds after each stage of set-up
        memory_peak(ctx["devices"], lambda _n, stats: ctx["say"](
            "memory_after", stage=stage,
            in_use=[s and s.get("bytes_in_use") for s in stats],
            peak=[s and s.get("peak_bytes_in_use") for s in stats]))

    t0 = time.perf_counter()
    engine = family_module(ctx, "engine").build_engine(ctx)
    jax.block_until_ready(engine.params)
    setup["weights_and_engine_s"] = time.perf_counter() - t0
    held("weights_and_engine")
    t0 = time.perf_counter()
    warm_engine(engine)
    setup["warm_engine_s"] = time.perf_counter() - t0
    held("warm_engine")
    t0 = time.perf_counter()
    stream = tg.arrivals(mix, model["vocab_size"], ctx["seed"], seconds)
    setup["traffic_s"] = time.perf_counter() - t0

    recorder = obs.enable(obs.Recorder()) if tracing_on else None
    server = Server(engine)
    ticker = GaugeTicker(server, recorder)
    as_request = lambda a: Request(
        rid=a.rid, prompt=a.prompt, max_new_tokens=a.max_new_tokens)
    marks = {}

    def open_trace():
        if tracing_on:
            marks["mark"] = tracing.start(ctx["trace_dir"])

    open_loop = tg.process_of(mix).OPEN_LOOP
    if not open_loop and mix.get("lead_in_finished"):
        # A closed backlog starts with every slot at the start of a
        # request; with outputs longer than the traced window no slot
        # would retire or refill inside it. The lead-in (set-up, not
        # measured) ticks until the mix's count of requests has finished,
        # so that the window opens on slots of mixed age.
        t0 = time.perf_counter()
        for a in stream:
            server.submit(as_request(a))
        stream = []  # the window's own fill finds them submitted
        while len(server.completed) < mix["lead_in_finished"]:
            if not ticker.tick():
                raise RuntimeError("the backlog ran dry in the lead-in")
        setup["lead_in_s"] = time.perf_counter() - t0
    window = open_loop_window if open_loop else backlog_window
    w = window(ctx, server, ticker, stream, as_request, marks, open_trace,
               seconds)
    t_open, t_close, trace_t1 = w["t_open"], w["t_close"], w["trace_t1"]

    if recorder is not None:
        obs.span_at("bench_window", t_open, t_close, t_open=t_open)
        spans = tracing.host_spans(recorder, t_open, trace_t1)
        obs.disable()
    else:
        spans = []
    inside = lambda t: t_open <= t <= (trace_t1 or 0)
    samples = [s for s in ticker.samples if inside(s[0])]
    gauges = [g for g in ticker.tick_gauges if inside(g["t"])]

    ticker.heartbeat.stop()
    del server, ticker, engine
    gc.collect()
    t0 = time.perf_counter()
    correct = family_module(ctx, "check").requests(ctx, w["done"])
    setup["check_after_window_s"] = time.perf_counter() - t0
    return {
        "t_open": t_open, "t_close": t_close, "end_to_end": w["end_to_end"],
        "correct": correct, "attempted": w["attempted"], "failed": w["failed"],
        "memory_peak_bytes": marks["peak"],
        "trace_t0": t_open, "trace_t1": trace_t1 or t_close,
        "trace_mark": marks.get("mark"), "host_spans": spans,
        "tick_samples": samples, "tick_gauges": gauges,
        "late_ms": w["late_ms"],
    }
