"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name, as data: its entry in
``BENCHMARK.json``, ``benchmark/configs/<config>.json``,
``benchmark/traffic/<traffic>.json``, and for each per-layer metric
``benchmark/metrics/<metric>.json``, which names a reader under
``benchmark/readers/``. The traffic file's ``kind`` selects the driver
(``drivers/<kind>.py``) that builds the system under test from the
program and places the window's two edges on completions.

The last line of stdout is the result: one JSON object. Earlier lines say
where set-up went, what each compared number read beside its limit, and
what the older window definitions would have read. A run that finds no
TPU, or fewer chips than the cell asks for, exits 2 and prints no result.

``--rehearse <file>`` (tests only) replaces sizes by the file's tiny ones
and lets the run go on without a TPU; its result says ``"rehearsal":
true`` and the exit code is 3, so that it can never pass for a chip run.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(kind: str, **fields) -> None:
    """An earlier line of the output: one JSON object, never the last."""
    print(json.dumps({"note": kind, **fields}), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def find_cell(workload: str) -> tuple[dict, dict]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return bench, cell
    raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")


def metrics_for(bench: dict, group: str, workload: str, reported=None) -> list:
    """Entries of ``end_to_end`` or ``per_layer`` that this cell reports."""
    out = []
    for m in bench[group]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or reported is None or m["moves"] in reported:
            out.append(m)
    return out


def compile_cache() -> str:
    """Keep JAX's persistent cache at a fixed path inside the checkout,
    or where ``JAX_COMPILATION_CACHE_DIR`` already says."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path  # the program honours it
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations (a cache load counts) by JAX's own events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.times: list[float] = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == self.EVENT:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


def read_per_layer(bench, workload, ctx) -> dict:
    out = {}
    for m in metrics_for(bench, "per_layer", workload, ctx["reported"]):
        spec = load_json("metrics", m["name"] + ".json")
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(args) -> tuple[dict, int]:
    bench, cell = find_cell(args.workload)
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    rehearse = bool(args.rehearse)
    if rehearse:
        with open(args.rehearse) as f:
            tiny = json.load(f)
        config = {**config, **tiny.get("configs", {}).get(cell["config"], {})}
        traffic = {**traffic, **tiny.get("traffic", {}).get(cell["traffic"], {})}

    t_import = time.perf_counter()
    import jax

    from benchmark import device

    cache_dir = compile_cache()
    compiles = CompileCounter()
    t_backend = time.perf_counter()
    devs = device.devices_or_exit(cell["chips"], rehearse)
    t_driver = time.perf_counter()
    driver = importlib.import_module("benchmark.drivers." + traffic["kind"])
    ctx = {
        "cell": cell, "config": config, "traffic": traffic, "devices": devs,
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "rehearse": rehearse, "compiles": compiles,
        "control": bool(args.control), "say": say,
        "setup": {"import_jax_s": t_backend - t_import,
                  "backend_s": t_driver - t_backend},
        "trace_dir": os.path.join(ROOT, ".bench_trace", cell["name"]),
    }
    say("start", workload=cell["name"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, compile_cache_dir=cache_dir,
        jax=jax.__version__, rehearsal=rehearse)
    run = driver.run(ctx)  # the window, its edges, what was captured for the check

    on = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": run["memory_peak_bytes"],
    }
    e2e = dict(run["end_to_end"])
    e2e["setup_s"] = run["t_open"] - T_PROCESS  # the check comes after the window
    wanted = metrics_for(bench, "end_to_end", cell["name"])
    missing = [m["name"] for m in wanted if m["name"] not in e2e]
    if missing:
        raise SystemExit(f"run.py: the driver gave no {missing}")
    ctx.update(run=run, end_to_end=e2e, device=on,
               reported={m["name"] for m in wanted})
    say("setup", **{k: round(v, 3) for k, v in ctx["setup"].items()},
        setup_s=round(e2e["setup_s"], 3))

    result = {
        "correct": bool(run["correct"]), "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
    }
    if args.trace:
        from benchmark import tracing

        traced = tracing.reduce_run(ctx)
        ctx["traced"] = traced
        on.update(busy_s=traced["busy_s"], window_s=traced["window_s"])
        result["metrics"] = read_per_layer(bench, cell["name"], ctx)
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    else:
        result["metrics"] = {
            m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
            for m in wanted}
    result["device"] = on
    if rehearse:
        result["rehearsal"] = True
    return result, (3 if rehearse else 0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", default="", help=argparse.SUPPRESS)
    # Also compute the check's control (PERF.md section 2) and print it.
    p.add_argument("--control", type=int, default=0, help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    result, code = run_cell(args)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    raise SystemExit(main())
