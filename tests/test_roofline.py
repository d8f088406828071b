"""Tests for mpit_tpu.obs.roofline — utilization flight data (ISSUE 8).

Covers the tentpole contract: cost registration + span-count work
accumulation → per-phase mfu/hbm/ici utilization in ``summary()``,
explicit length-aware work overriding the padded model, the off-chip
honesty rule (modeled cost recorded, NO fabricated percentages,
platform-labeled), the visited-tile achieved-bytes parity pin against
the kernel's own count, compile watching (expected-count pin, forced
recompile → sentinel anomaly), the sustained-utilization-collapse rule,
and the `obs diff` gate on utilization keys + missing-phase exit 2.
"""

from __future__ import annotations

import json
import subprocess
import sys
import time

import numpy as np
import pytest

from mpit_tpu import obs
from mpit_tpu.obs import roofline as R


@pytest.fixture(autouse=True)
def _obs_disabled_by_default():
    obs.disable()
    yield
    obs.disable()


# Synthetic peaks: round numbers so the expected percentages are exact.
PEAKS = {"chip": "test-chip", "peak_flops": 1e12, "peak_hbm": 1e11,
         "peak_ici": 1e10}


def _spans(rec, name, durs):
    t0 = time.perf_counter()
    for d in durs:
        rec.add_span(name, t0, t0 + d)


class TestRollup:
    def test_span_count_times_modeled_cost_on_tpu(self):
        rec = obs.enable(obs.Recorder())
        rec.add_cost("step", {"flops": 1e9, "hbm_bytes": 1e8,
                              "ici_bytes": 0.0, "platform": "tpu",
                              **PEAKS})
        _spans(rec, "step", [0.01] * 10)  # 0.1 s total
        entry = rec.summary()["roofline"]["phases"]["step"]
        assert entry["executions"] == 10
        assert entry["achieved_flops"] == pytest.approx(1e10)
        assert entry["achieved_hbm_bytes"] == pytest.approx(1e9)
        # 1e10 flops / 0.1 s / 1e12 peak = 10% MFU; hbm the same by
        # construction.
        assert entry["mfu_pct"] == pytest.approx(10.0, rel=0.02)
        assert entry["hbm_util_pct"] == pytest.approx(10.0, rel=0.02)
        assert "ici_util_pct" not in entry  # no ici work registered
        # flops/peak_flops = 1e-2 s > hbm 1e-3 s: compute-bound.
        assert entry["bound_modeled"] == "compute"

    def test_explicit_work_overrides_padded_model(self):
        """The flash-decode correction: hbm bytes fed explicitly
        (length-aware) win over count × padded cost; flops (never fed)
        stay count × modeled."""
        rec = obs.enable(obs.Recorder())
        rec.add_cost("decode", {"flops": 1e9, "hbm_bytes": 1e9,
                                "ici_bytes": 0.0, "platform": "tpu",
                                **PEAKS})
        _spans(rec, "decode", [0.01] * 4)
        for _ in range(4):
            obs.roofline.work("decode", hbm_bytes=1e7)  # ≪ the padded 1e9
        entry = rec.summary()["roofline"]["phases"]["decode"]
        assert entry["achieved_hbm_bytes"] == pytest.approx(4e7)
        assert entry["achieved_flops"] == pytest.approx(4e9)  # modeled
        assert entry["explicit_components"] == ["hbm_bytes"]

    def test_off_chip_records_cost_but_no_percentages(self):
        """The honesty rule: a CPU recording carries the modeled cost,
        achieved totals, rates and the modeled bound — but NO
        mfu/hbm/ici percentages (measured seconds on a host that is not
        the chip), and the platform label says why."""
        rec = obs.enable(obs.Recorder())
        rec.add_cost("step", {"flops": 1e9, "hbm_bytes": 1e8,
                              "ici_bytes": 0.0, "platform": "cpu",
                              **PEAKS})
        _spans(rec, "step", [0.01] * 10)
        entry = rec.summary()["roofline"]["phases"]["step"]
        assert entry["platform"] == "cpu"
        assert entry["achieved_flops"] == pytest.approx(1e10)
        assert entry["bound_modeled"] == "compute"
        for key in R.UTIL_KEYS:
            assert key not in entry, f"fabricated {key} on cpu"

    def test_ici_utilization_and_memory_bound_verdict(self):
        rec = obs.enable(obs.Recorder())
        # hbm-dominated work: 1e9 bytes vs 1e6 flops.
        rec.add_cost("sync", {"flops": 1e6, "hbm_bytes": 1e9,
                              "ici_bytes": 1e7, "platform": "tpu",
                              **PEAKS})
        _spans(rec, "sync", [0.1])
        entry = rec.summary()["roofline"]["phases"]["sync"]
        assert entry["bound_modeled"] == "hbm"
        assert entry["ici_util_pct"] == pytest.approx(
            100.0 * 1e7 / 0.1 / PEAKS["peak_ici"], rel=0.02
        )

    def test_register_and_work_are_noops_when_disabled(self):
        R.register_cost("x", flops=1.0, platform="tpu")
        R.work("x", hbm_bytes=1.0)  # must not raise

    def test_utilization_verdict_helper_requires_platform_label(self):
        with pytest.raises(TypeError):
            R.register_cost("x", flops=1.0)  # platform is keyword-required

    def test_compile_overlay_excluded_from_denominator(self):
        """A phase's first span absorbs trace+compile wall (the
        `compile` overlay span); utilization must divide by steady-state
        seconds, or a cold run understates utilization vs a warm one and
        the obs-diff gate trips on cache state."""
        rec = obs.enable(obs.Recorder())
        rec.add_cost("decode", {"flops": 1e9, "hbm_bytes": 0.0,
                                "ici_bytes": 0.0, "platform": "tpu",
                                **PEAKS})
        t0 = time.perf_counter()
        rec.add_span("decode", t0, t0 + 1.0)  # first call: 0.6 compile
        rec.add_span("compile", t0, t0 + 0.6, {"phase": "decode"})
        rec.add_span("decode", t0, t0 + 0.4)  # a steady-state tick
        entry = rec.summary()["roofline"]["phases"]["decode"]
        assert entry["compile_seconds_excluded"] == pytest.approx(0.6)
        assert entry["seconds"] == pytest.approx(0.8)  # 1.4 - 0.6
        # 2e9 flops / 0.8 s / 1e12 = 0.25% — compile-free denominator.
        assert entry["mfu_pct"] == pytest.approx(0.25, rel=0.02)

    def test_scoped_summary_omits_roofline(self):
        """Work/cost accumulation is cumulative, not event-indexed — a
        since-scoped summary must not divide whole-recording work by a
        window's seconds (inflated utilization); it omits the section."""
        rec = obs.enable(obs.Recorder())
        rec.add_cost("decode", {"flops": 1e9, "hbm_bytes": 1e8,
                                "ici_bytes": 0.0, "platform": "tpu",
                                **PEAKS})
        _spans(rec, "decode", [0.01] * 4)
        n0 = rec.event_count()
        _spans(rec, "decode", [0.01] * 2)
        assert "roofline" not in rec.summary(since=n0)
        assert "roofline" in rec.summary()

    def test_snapshot_and_drain_carry_roofline_state(self):
        rec = obs.enable(obs.Recorder())
        rec.add_cost("step", {"flops": 1.0, "hbm_bytes": 1.0,
                              "ici_bytes": 0.0, "platform": "cpu",
                              **PEAKS})
        obs.roofline.work("step", hbm_bytes=2.0)
        snap = rec.snapshot()
        assert snap["costs"]["step"]["flops"] == 1.0
        assert snap["work"]["step"]["hbm_bytes"] == 2.0
        drained = rec.drain()
        assert drained["costs"] and drained["work"]
        assert rec.snapshot()["costs"] == {}  # drained clean


class TestVisitedTileBytesParity:
    def test_kernel_visited_counts_equal_host_formula_bytes(self):
        """The acceptance pin: achieved KV bytes computed from the
        KERNEL's own visited-tile output == the host formula the
        scheduler feeds, at ragged lengths (0, mid-tile, tile-aligned,
        max)."""
        import jax

        from mpit_tpu.ops.decode_attention import (
            flash_paged_decode_attention,
            num_kv_blocks,
        )

        b, s, h, d, bk = 5, 64, 2, 8, 16
        lengths = np.asarray([0, 3, 16, 33, 63], np.int32)
        key = jax.random.key(0)
        q = jax.random.normal(key, (b, 1, h, d), "float32")
        # A pool of 16-row pages, four a slot, in slot order.
        pool = jax.random.normal(key, (b * s // bk, bk, h * d), "float32")
        bt = np.arange(b * s // bk, dtype=np.int32).reshape(b, s // bk)
        _, visited = flash_paged_decode_attention(
            q, pool, pool, lengths, bt, block_k=bk, interpret=True,
            return_visited=True,
        )
        kernel_bytes = R.kv_tile_read_bytes(
            int(np.asarray(visited).sum()), block_k=bk,
            kv_row_bytes=h * d * 4, num_layers=3,
        )
        host_bytes = R.kv_tile_read_bytes(
            int(num_kv_blocks(lengths, 1, s, bk).sum()), block_k=bk,
            kv_row_bytes=h * d * 4, num_layers=3,
        )
        assert kernel_bytes == host_bytes
        # And the figure is genuinely length-aware: far below the
        # padded full-buffer read.
        padded = R.kv_tile_read_bytes(
            b * (s // bk), block_k=bk, kv_row_bytes=h * d * 4,
            num_layers=3,
        )
        assert kernel_bytes < padded

    def test_decode_step_bytes_composition(self):
        got = R.decode_step_hbm_bytes(
            10, block_k=16, kv_row_bytes=64.0, num_layers=2,
            param_bytes=1000.0, appended_rows=3,
        )
        # params + 2 (K,V) × tiles × block_k × row × layers + appends.
        assert got == 1000.0 + 2 * 10 * 16 * 64.0 * 2 + 2 * 3 * 64.0 * 2


class TestCompileWatch:
    def test_first_compile_spanned_counted_gauged(self):
        import jax
        import jax.numpy as jnp

        rec = obs.enable(obs.Recorder())
        f = jax.jit(lambda x: x * 2)
        w = R.CompileWatch(expected=1, scope="unit")
        out = w.call("step", f, jnp.ones((4,)))
        assert float(out[0]) == 2.0
        assert w.compiles == 1 and w.unexpected == 0
        w.call("step", f, jnp.ones((4,)))  # cached: no new event
        assert w.compiles == 1
        s = rec.summary()
        assert s["phases"]["compile"]["count"] == 1
        assert s["counters"]["compiles"] == 1.0
        assert rec.snapshot()["gauges"][("unit_compiles", ())] == 1.0

    def test_forced_recompile_trips_sentinel(self):
        import jax
        import jax.numpy as jnp

        rec = obs.enable(obs.Recorder())
        sent = obs.Sentinel()
        f = jax.jit(lambda x: x + 1)
        w = R.CompileWatch(expected=1, scope="unit", sentinel=sent)
        w.call("step", f, jnp.ones(()))
        f.clear_cache()  # the injected "unexpected recompile"
        w.call("step", f, jnp.ones(()))
        assert w.compiles == 2 and w.unexpected == 1
        rep = sent.report()
        assert not rep["clean"]
        assert rep["anomaly_counts"]["unexpected_recompile"] == 1
        (a,) = [x for x in rep["anomalies"]
                if x["kind"] == "unexpected_recompile"]
        assert a["metric"] == "step" and a["expected"] == 1
        # The structured instant landed in the trace too (via note()).
        assert rec.summary()["instants"]["anomaly"] >= 1

    def test_unwatchable_callable_degrades_gracefully(self):
        w = R.CompileWatch(expected=1)
        assert w.call("step", lambda x: x + 1, 41) == 42
        assert w.compiles == 0


class TestUtilizationWatch:
    def test_healthy_stream_is_silent(self):
        sent = obs.Sentinel()
        w = R.UtilizationWatch(sentinel=sent, warmup=4, sustained_n=3)
        for i in range(50):
            w.observe("decode_hbm_gbps", i, 100.0 + (i % 5))
        assert w.alerts == [] and sent.report()["clean"]

    def test_sustained_collapse_flagged(self):
        sent = obs.Sentinel()
        w = R.UtilizationWatch(sentinel=sent, warmup=4, sustained_n=3,
                               drop_ratio=0.5)
        for i in range(20):
            w.observe("decode_hbm_gbps", i, 100.0)
        for i in range(20, 26):  # collapse to 20% of baseline
            w.observe("decode_hbm_gbps", i, 20.0)
        assert w.alerts, "collapse not flagged"
        assert w.alerts[0]["metric"] == "decode_hbm_gbps"
        rep = sent.report()
        assert rep["anomaly_counts"]["utilization_collapse"] >= 1

    def test_single_dip_not_flagged(self):
        w = R.UtilizationWatch(warmup=4, sustained_n=3)
        for i in range(20):
            w.observe("m", i, 100.0)
        w.observe("m", 20, 10.0)  # one bad tick
        for i in range(21, 30):
            w.observe("m", i, 100.0)
        assert w.alerts == []


class TestHardenedLoopRoofline:
    def test_loop_registers_step_cost_and_counts_compile(self, world8):
        """hardened_loop(roofline=True): the step's cost_analysis lands
        in the recorder before the first step, the summary's roofline
        section covers the run, and the loop's lifetime compile count
        is exactly 1 (the first step)."""
        import jax
        import jax.numpy as jnp

        from mpit_tpu import opt as gopt
        from mpit_tpu.train import make_train_step
        from mpit_tpu.train.loop import hardened_loop
        from mpit_tpu.train.metrics import MetricLogger

        def loss(p, b):
            return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2), {}

        params = {
            "w": jax.random.normal(jax.random.key(0), (16, 16)) * 0.1
        }
        init_fn, step_fn, _ = make_train_step(
            loss, gopt.goo(0.1, 0.0), world8, zero1=False
        )
        rng = np.random.default_rng(0)

        def batches():
            for _ in range(8):
                x = rng.normal(size=(32, 16)).astype(np.float32)
                yield {"x": x,
                       "y": (x @ np.eye(16, dtype=np.float32))}

        obs.enable(obs.Recorder())
        out = hardened_loop(
            world8, init_fn(params), step_fn, batches(), steps=6,
            log_every=3, logger=MetricLogger(stdout=False),
            roofline=True,
        )
        roof = out["obs"]["roofline"]["phases"]["step"]
        assert roof["executions"] == 6
        assert roof["modeled_flops_per_exec"] > 0
        assert roof["platform"] == jax.devices()[0].platform
        if jax.devices()[0].platform != "tpu":
            assert "mfu_pct" not in roof  # honesty rule, end to end
        assert out["compiles"] == 1
        assert out["obs"]["phases"]["compile"]["count"] == 1


class TestDiffUtilizationGate:
    def _snap(self, mfu, hbm=50.0):
        return {
            "phases": {"step": {"count": 10, "total_s": 1.0,
                                "p50_s": 0.1, "p95_s": 0.12}},
            "counters": {},
            "roofline": {"phases": {"step": {
                "platform": "tpu", "mfu_pct": mfu, "hbm_util_pct": hbm,
            }}},
        }

    def test_utilization_drop_beyond_tolerance_regresses(self):
        d = obs.baseline.diff(self._snap(50.0), self._snap(40.0),
                              tolerance_pct=10.0)
        assert not d["ok"]
        assert d["util_regressions"] == ["step.mfu_pct"]
        assert d["utilization"]["step.mfu_pct"]["drop_pct"] == (
            pytest.approx(20.0)
        )

    def test_within_tolerance_and_improvement_pass(self):
        assert obs.baseline.diff(self._snap(50.0), self._snap(48.0),
                                 tolerance_pct=10.0)["ok"]
        assert obs.baseline.diff(self._snap(50.0), self._snap(60.0),
                                 tolerance_pct=10.0)["ok"]

    def test_platform_labeled_snapshots_never_gate_vacuously(self):
        """Off-chip snapshots record no percentages — the gate must
        compare nothing, not treat absence as zero."""
        cpu = {
            "phases": {"step": {"count": 10, "total_s": 1.0,
                                "p50_s": 0.1, "p95_s": 0.12}},
            "counters": {},
            "roofline": {"phases": {"step": {"platform": "cpu"}}},
        }
        d = obs.baseline.diff(self._snap(50.0), cpu, tolerance_pct=10.0)
        assert d["ok"] and "utilization" not in d

    def test_snapshot_carries_roofline_section(self):
        rec = obs.enable(obs.Recorder())
        rec.add_cost("step", {"flops": 1.0, "hbm_bytes": 1.0,
                              "ici_bytes": 0.0, "platform": "cpu",
                              **PEAKS})
        _spans(rec, "step", [0.01])
        snap = obs.baseline.snapshot(rec.summary())
        assert "step" in snap["roofline"]["phases"]
        assert json.dumps(snap)  # JSON-serializable end to end


class TestDiffMissingPhaseCLI:
    """ISSUE 8 satellite: a baseline phase missing from the current
    snapshot makes the comparison unusable — CLI exit 2, like
    truncated snapshots. New phases stay fine."""

    def _run_cli(self, *argv):
        return subprocess.run(
            [sys.executable, "-m", "mpit_tpu.obs", *argv],
            capture_output=True, text=True, timeout=120,
        )

    def _save(self, path, names):
        return obs.baseline.save(path, {
            "phases": {n: {"count": 4, "total_s": 0.4, "p50_s": 0.1,
                           "p95_s": 0.12} for n in names},
            "counters": {},
        })

    def test_missing_phase_exits_2(self, tmp_path):
        base = self._save(tmp_path / "base.json", ("step", "host_fence"))
        cur = self._save(tmp_path / "cur.json", ("step",))
        out = self._run_cli("diff", str(base), str(cur))
        assert out.returncode == 2
        doc = json.loads(out.stdout)
        assert doc["missing_phases"] == ["host_fence"]
        assert "missing" in doc["error"]

    def test_new_phase_still_gates_normally(self, tmp_path):
        base = self._save(tmp_path / "base.json", ("step",))
        cur = self._save(tmp_path / "cur.json", ("step", "eval"))
        out = self._run_cli("diff", str(base), str(cur))
        assert out.returncode == 0, out.stdout
