"""The harness end to end without a chip: every cell at a tiny size on the
CPU, the last line's contract, and the rule that a window's edges are
completions. A rehearsal says so in its result and exits 3: it can never
pass for a chip run, and nothing it prints is a device number."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny.json")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = {c["name"]: c for c in BENCH["workloads"]}


def run_cell(name, *extra, cwd=ROOT, rehearse=True, devices=None):
    cell = CELLS.get(name, {"chips": 1})
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored",
               XLA_FLAGS="--xla_force_host_platform_device_count=%d"
               % (devices or cell["chips"]))
    cmd = [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
           "--workload", name, "--seed", str(2**31 + 11), "--seconds", "1",
           *extra]
    if rehearse:
        cmd += ["--rehearse", TINY]
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=600)


def expected_metrics(name, group):
    out = set()
    for m in BENCH[group]:
        if name in m.get("workloads", [name]):
            out.add(m["name"])
    return out


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_every_cell_runs_end_to_end_at_a_tiny_size(name, trace):
    done = run_cell(name, "--trace", str(trace))
    assert done.returncode == 3, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
    result = lines[-1]
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["rehearsal"] is True
    device = result["device"]
    assert device["platform"] == "cpu" and device["count"] == CELLS[name]["chips"]
    assert result["attempted"] > 0 and result["failed"] == 0
    if trace:
        # Only what a CPU run can read: spans and counters, never a device time.
        assert set(result["metrics"]) <= expected_metrics(name, "per_layer")
        assert not any(k.split(".")[0] in (
            "step_device_ms", "device_idle_pct", "train_mfu_pct",
            "pallas_time_share_pct", "decode_hbm_util_pct", "peak_hbm_gb",
            "collective_exposed_pct") for k in result["metrics"])
        assert "compiles_in_window" in " ".join(result["metrics"])
        assert {"busy_s", "window_s"} <= set(device)
    else:
        assert set(result["metrics"]) == expected_metrics(name, "end_to_end")
        assert all(m["value"] > 0 for m in result["metrics"].values())
    notes = {l["note"] for l in lines[:-1]}
    assert {"start", "window", "setup", "correct"} <= notes


def test_no_tpu_means_no_result():
    done = run_cell(sorted(CELLS)[0], "--trace", "0", rehearse=False)
    assert done.returncode == 2
    assert not any('"correct"' in l for l in done.stdout.splitlines())


def test_more_chips_asked_than_found_means_no_result():
    four = [n for n, c in CELLS.items() if c["chips"] == 4]
    if not four:
        pytest.skip("no four-chip cell")
    done = run_cell(four[0], "--trace", "0", devices=1)
    assert done.returncode == 2
    assert not any('"correct"' in l for l in done.stdout.splitlines())


def test_the_benchmark_alone_is_not_enough(tmp_path):
    """In a directory that holds only BENCHMARK.json and ``paths`` there
    is no program to measure: non-zero exit and no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = run_cell(sorted(CELLS)[0], "--trace", "0", cwd=str(tmp_path))
    assert done.returncode not in (0, 3)
    assert not any('"correct"' in l for l in done.stdout.splitlines())


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now


@pytest.mark.parametrize("seconds", [0.7, 1.0, 1.33, 2.9])
def test_window_edges_are_completions(monkeypatch, seconds):
    """With a step of known duration, N and the two edge times give the
    exact rate whatever ``--seconds`` is; whole steps counted in fixed
    seconds do not."""
    from benchmark.drivers import pretrain

    clock = FakeClock()
    monkeypatch.setattr(pretrain, "time", clock)
    step_s, tokens = 0.139, 16384

    def step_fn(state, batch):
        clock.now += step_s  # the device finishes one step
        return state + 1, {"loss": 0.0}

    step_fn._cache_size = lambda: 1
    step_fn.grad_sync_mode = "psum"
    edge = pretrain.EdgeStep(step_fn, seconds, lambda: None, lambda: None, {})
    state, calls = 0, 0
    while edge.total is None or calls < edge.total:
        state, _ = edge(state, None)
        calls += 1
    assert edge.n >= pretrain.MIN_STEPS
    assert calls == pretrain.CHECK_STEPS + pretrain.WARM_STEPS + edge.n
    rate = edge.n * tokens / (edge.t_close - edge.t_open)
    assert rate == pytest.approx(tokens / step_s, rel=1e-9)
    whole = int(seconds / step_s) * tokens / seconds
    assert abs(whole - tokens / step_s) / (tokens / step_s) > 1e-3
