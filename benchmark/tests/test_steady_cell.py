"""The steady cell as committed: its rate stands in a stated relation to
a knee that the file names, the window holds enough requests for the
tail it records, and the open-loop window hands ``run_value`` the
median and the tail beside the judged 75th percentile."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny.json")

from benchmark import run as bench_run  # noqa: E402
from benchmark import traffic as tg  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELL = "gpt2l-serve-prefill-steady"
MIX = bench_run.load_json("traffic", "prefill-steady.json")


def test_the_rate_is_a_stated_share_of_the_knee():
    """Four fifths of the swept knee (or two thirds, where the runs forced
    it): a later cell above the knee takes its rate from ``knee_per_s``."""
    share = MIX["rate_per_s"] / MIX["knee_per_s"]
    assert any(share == pytest.approx(s, abs=0.005) for s in (4 / 5, 2 / 3))


def test_a_window_holds_a_thousand_requests_due():
    seconds = BENCH["run_seconds"]
    stream = tg.arrivals(MIX, 50257, 2**31 + 5, seconds)
    lead = MIX["lead_in_s"]
    due = [a for a in stream if lead <= a.due_s < lead + seconds]
    assert len(due) >= 1000
    # A 95th percentile with fifty samples beyond it.
    assert 0.05 * len(due) >= 50
    # The stream goes on past the window: requests due after its close
    # keep the load on while the last of the sample are answered.
    assert stream[-1].due_s > lead + seconds + 0.9 * MIX["answer_cap_s"]


def test_the_tail_is_recorded_and_not_judged():
    judged = [m for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])]
    assert sorted(m["name"] for m in judged) == ["setup_s", "ttft_p75_ms"]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == "ttft_p95_ms.steady")
    assert entry["moves"] == "ttft_p75_ms" and entry["workloads"] == [CELL]
    spec = bench_run.load_json("metrics", "ttft_p95_ms.steady.json")
    assert spec["reader"] == "run_value" and spec["args"] == {"key": "ttft_p95_ms"}


def test_the_open_loop_window_carries_median_and_tail(capsys, monkeypatch):
    from benchmark.drivers import requests

    seen = {}
    real = requests.open_loop_window

    def watched(*args, **kw):
        seen.update(real(*args, **kw))
        return seen

    monkeypatch.setattr(requests, "open_loop_window", watched)
    code = bench_run.main(["--workload", CELL, "--seed", "20261003", "--seconds",
                           "1", "--trace", "1", "--rehearse", TINY])
    assert code == 3
    e2e = seen["end_to_end"]
    assert {"ttft_p50_ms", "ttft_p75_ms", "ttft_p95_ms", "tpot_p75_ms"} <= set(e2e)
    assert 0 < e2e["ttft_p50_ms"] <= e2e["ttft_p75_ms"] <= e2e["ttft_p95_ms"]
    result = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert result["metrics"]["ttft_p95_ms.steady"]["value"] == e2e["ttft_p95_ms"]


def test_a_traced_run_measures_the_seconds_the_mix_lets_it_trace(capsys):
    """``trace_seconds`` of the mix, not ``--seconds``: writing and reading
    the trace of this load has to fit the run's 360 s."""
    code = bench_run.main(["--workload", CELL, "--seed", "20261004", "--seconds",
                           "5", "--trace", "1", "--rehearse", TINY])
    assert code == 3
    result = json.loads(capsys.readouterr().out.splitlines()[-1])
    with open(TINY) as f:
        rate = json.load(f)["traffic"]["prefill-steady"]["rate_per_s"]
    # 3 s of the schedule, not 5 (a short stretch holds a few more or fewer).
    assert result["attempted"] == pytest.approx(rate * MIX["trace_seconds"], rel=0.15)
    assert result["device"]["window_s"] == pytest.approx(MIX["trace_seconds"])
