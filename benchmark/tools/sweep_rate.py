"""Find the knee of an open-loop cell once, on the chip: offer the cell's
traffic at each of a few fixed rates and print, for each, the queue at the
window's two edges and the tails. The knee is the highest rate at which
the queue at the window's end is no deeper than at its start; the cell's
traffic file then fixes four fifths of it as ``rate_per_s``.

    python3 benchmark/tools/sweep_rate.py <workload> <seconds> <rate> [<rate> ...]

In place of a rate, a JSON object changes any parameters of the mix for
that window (``null`` takes one out), all in one process and one set-up:
``'{"process": "poisson", "balance_block": null}'`` offers the same mix
unsmoothed.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402


def main(workload: str, seconds: float, changes: list) -> None:
    import importlib

    _, cell = bench_run.find_cell(workload)
    config = bench_run.load_json("configs", cell["config"] + ".json")
    traffic = bench_run.load_json("traffic", cell["traffic"] + ".json")
    bench_run.compile_cache()
    from benchmark import device

    devs = device.devices_or_exit(cell["chips"], rehearse=False)
    driver = importlib.import_module("benchmark.drivers." + traffic["kind"])
    for i, change in enumerate(changes):
        if not isinstance(change, dict):
            change = {"rate_per_s": change, "answer_cap_s": 20.0}
        mix = {k: v for k, v in {**traffic, **change}.items() if v is not None}
        window = {}  # the driver's ``window`` line: the queue at both edges

        def say(kind, **f):
            if kind == "window":
                window.update(f)
            bench_run.say(kind, changed=change, **f)

        ctx = {
            "cell": cell, "config": config, "devices": devs, "seed": 9000 + i,
            "traffic": mix, "seconds": seconds, "trace": False,
            "rehearse": False, "control": False, "setup": {}, "compiles": None,
            "trace_dir": "", "say": say,
        }
        run = driver.run(ctx)
        bench_run.say("swept", changed=change, attempted=run["attempted"],
                      failed=run["failed"], correct=run["correct"],
                      **run["end_to_end"],
                      **{k: window.get(k) for k in (
                          "queued_at_open", "queued_at_close", "still_queued",
                          "ttft_max_ms", "late_p95_ms", "ran_past_window_s")})


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]), [json.loads(a) for a in sys.argv[3:]])
