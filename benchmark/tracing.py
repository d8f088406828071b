"""Start and stop the profiler round a traced window, and reduce what it wrote.

Only a ``--trace 1`` run comes here. The traced window is the first part
of the measured window (``TRACE_CAP_S`` at most, a trace of more is
large and slows the host); the program's own spans are recorded for the
same interval by an ``mpit_tpu.obs`` recorder, which the drivers install.
"""

from __future__ import annotations

import shutil
import time

TRACE_CAP_S = 6.0
# An open loop starts the profiler this long before its window opens
# (``drivers/requests.py``): what queued up behind the start has to drain
# before the window does.
START_AHEAD_S = 0.25


def start(trace_dir: str, say=None) -> float:
    """Begin a trace; returns the host time of the mark that ties the
    host clock to the trace clock. ``say``, where given, is told how long
    the profiler took to start (and by ``stop`` to write the trace)."""
    import jax

    from benchmark import xplane

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the interpreter's calls are not needed
    options.host_tracer_level = 2
    t0 = time.perf_counter()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t_mark = time.perf_counter()
    if say is not None:
        say("trace_started", start_trace_s=t_mark - t0)
    with jax.profiler.TraceAnnotation(xplane.MARK):
        pass
    return t_mark


def stop(say=None) -> None:
    import jax

    t0 = time.perf_counter()
    jax.profiler.stop_trace()
    if say is not None:
        say("trace_stopped", stop_trace_s=time.perf_counter() - t0)


def host_spans(recorder, t0: float, t1: float) -> list:
    """The program's spans that touch ``[t0, t1]`` on the host's clock,
    as ``(name, start, end, attrs)``. The recorder stores starts relative
    to its own epoch; a span of known start, written by the driver at the
    window's opening, gives the epoch back."""
    events = recorder.snapshot()["events"]
    anchor = next(e for e in events if e[1] == "bench_window")
    epoch = anchor[5]["t_open"] - anchor[2]
    out = []
    for kind, name, start, dur, _tid, attrs in events:
        if kind != "X" or name == "bench_window":
            continue
        a, b = start + epoch, start + epoch + dur
        if b >= t0 and a <= t1:
            out.append((name, a, b, attrs or {}))
    return out


def reduce_run(ctx) -> dict:
    """Busy, idle, operations and gaps of the run's traced window."""
    from benchmark import xplane

    run = ctx["run"]
    t0, t1, t_mark = run["trace_t0"], run["trace_t1"], run["trace_mark"]
    if ctx["rehearse"]:
        # No TPU plane in a CPU trace: nothing is read, and nothing is
        # reported under a device's name.
        return {"busy_s": 0.0, "window_s": t1 - t0, "custom_call_s": 0.0,
                "collective_s": 0.0, "collective_exposed_s": 0.0,
                "device_ops": [], "idle_gaps": [], "rehearsal": True}
    trace = xplane.load(ctx["trace_dir"])
    if trace.mark_s is None:
        raise RuntimeError("the trace holds no window mark on the host plane")
    shift = trace.mark_s - t_mark  # host clock -> trace clock
    spans = [(n, a + shift, b + shift) for n, a, b, _ in run["host_spans"]]
    return xplane.reduce(trace, t0 + shift, t1 + shift, spans)
