"""Tests for the observability toolkit (mpit_tpu.utils.profiling)."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.utils import (
    CommModel,
    StepTimer,
    allreduce_gbps,
    chip_spec_for,
    collective_bytes,
    compiled_cost,
    roofline,
    tree_bytes,
)


class TestStepTimer:
    def test_timing_and_summary(self):
        t = StepTimer(block=False)
        t.start()
        for _ in range(5):
            t.tick()
        s = t.summary(skip_warmup=1)
        assert s["steps"] == 4
        assert s["total_s"] >= 0
        assert s["p95_s"] >= s["p50_s"] >= 0

    def test_tick_before_start_raises(self):
        with pytest.raises(RuntimeError):
            StepTimer().tick()

    def test_block_waits_device_result(self):
        t = StepTimer(block=True)
        t.start()
        x = jax.jit(lambda v: v @ v)(jnp.ones((256, 256)))
        dt = t.tick(x)
        assert dt > 0
        assert np.isfinite(np.asarray(x)).all()  # result materialized


class TestCompiledCost:
    def test_matmul_flops_reported(self):
        a = jnp.ones((128, 128))
        cost = compiled_cost(lambda x: x @ x, a)
        # 2*N^3 MACs; accept any backend-reported positive figure.
        if "flops" in cost:
            assert cost["flops"] >= 128 * 128 * 128
        else:
            pytest.skip("backend reports no flops")


class TestRoofline:
    def test_compute_vs_bandwidth_bound(self):
        # Huge flops, tiny bytes → compute-bound; and vice versa.
        r1 = roofline(1e15, 1e6)
        assert r1["bound"] == "compute" and r1["modeled"] is True
        r2 = roofline(1e6, 1e12)
        assert r2["bound"] == "hbm"
        r3 = roofline(1e6, 1e6, ici_bytes=1e12)
        assert r3["bound"] == "ici"
        assert r1["seconds_lower_bound"] > 0


class TestChipSpecTable:
    """One table of published peaks, keyed by ``device_kind``: a real
    device that is not in it is an error, never a silent v5e default."""

    def test_known_device_kind(self):
        assert chip_spec_for("TPU v5 lite").peak_flops_bf16 == 197e12

    def test_unknown_device_kind_raises(self):
        with pytest.raises(ValueError, match="no published peaks"):
            chip_spec_for("TPU v9 imaginary")

    def test_peaks_for_a_real_device_read_the_table(self, monkeypatch):
        from mpit_tpu.obs import roofline as R

        class _Dev:
            device_kind = "TPU v9 imaginary"

        # CPU runs keep the v5e spec as their *modeled* chip ...
        assert R.chip_peaks()["chip"] == "tpu-v5e"
        # ... a utilization for an attached device must know the device.
        monkeypatch.setattr(jax, "devices", lambda: [_Dev()])
        with pytest.raises(ValueError, match="no published peaks"):
            R.chip_peaks(platform="tpu")


class TestCollectiveModel:
    def test_ring_formulas(self):
        n = 1e9
        assert collective_bytes(n, 1) == 0.0
        np.testing.assert_allclose(collective_bytes(n, 8), 2 * 7 / 8 * n)
        np.testing.assert_allclose(
            collective_bytes(n, 8, "reduce_scatter"), 7 / 8 * n
        )
        np.testing.assert_allclose(collective_bytes(n, 8, "broadcast"), n)
        with pytest.raises(ValueError):
            collective_bytes(n, 8, "gossip")

    def test_zero1_vs_plain_allreduce_equal_wire_bytes(self):
        # reduce-scatter + all-gather == allreduce on the wire.
        params = {"w": jnp.ones((1000, 10)), "b": jnp.ones((10,))}
        z = CommModel(params, 8, zero1=True).grad_sync_bytes()
        a = CommModel(params, 8, zero1=False).grad_sync_bytes()
        np.testing.assert_allclose(z, a)

    def test_tree_bytes(self):
        params = {"w": jnp.ones((10, 10), jnp.float32), "s": jnp.ones((4,), jnp.bfloat16)}
        assert tree_bytes(params) == 10 * 10 * 4 + 4 * 2

    def test_allreduce_gbps(self):
        assert allreduce_gbps(8e9, 8, 2.0) == 4.0

    def test_scaling_projection_shape_and_cliff(self):
        """The 8→256 scaling artifact: labeled modeled, monotone comm
        cost, and a visible DCN cliff when chips exceed the slice size."""
        from mpit_tpu.utils import scaling_projection

        params = {"w": jnp.ones((4 << 20,), jnp.float32)}  # 16 MiB
        proj = scaling_projection(0.1, 1000, params, slice_size=256)
        assert proj["modeled"] is True
        assert [p["chips"] for p in proj["points"]] == [8, 32, 64, 128, 256]
        effs = [p["efficiency_no_overlap"] for p in proj["points"]]
        assert all(0 < e <= 1 for e in effs)
        assert effs == sorted(effs, reverse=True)  # efficiency decays with n
        assert all(p["comm_dcn_s"] == 0 for p in proj["points"])  # one slice
        assert 0 < proj["efficiency_8_to_256_no_overlap"] <= 1
        # Multi-slice variant: crossing the slice boundary costs DCN time,
        # and efficiency at 256 chips drops vs the single-slice layout.
        multi = scaling_projection(0.1, 1000, params, slice_size=64)
        pts = {p["chips"]: p for p in multi["points"]}
        assert pts[64]["comm_dcn_s"] == 0
        assert pts[128]["comm_dcn_s"] > 0 and pts[256]["comm_dcn_s"] > 0
        flat = {p["chips"]: p for p in proj["points"]}
        assert (
            pts[256]["efficiency_no_overlap"]
            < flat[256]["efficiency_no_overlap"]
        )

    def test_hierarchical_dcn_phases(self):
        """Multi-slice grad sync decomposes into ICI + DCN phases; the
        DCN phase moves 1/per_slice of the payload across the slice
        count, and dominates the modeled time at DCN bandwidth."""
        params = {"w": jnp.ones((1 << 20,), jnp.float32)}  # 4 MiB
        n, slices = 256, 4
        m = CommModel(params, n, num_slices=slices)
        ici_b, dcn_b = m.grad_sync_bytes_by_tier()
        nbytes = 4 * (1 << 20)
        per_slice = n // slices
        np.testing.assert_allclose(
            ici_b, 2 * (per_slice - 1) / per_slice * nbytes
        )
        np.testing.assert_allclose(
            dcn_b, 2 * (slices - 1) / slices * nbytes / per_slice
        )
        t = m.grad_sync_seconds()
        assert t["modeled"] is True
        # DCN moves ~64x fewer bytes but is ~15x slower per byte: the
        # phases are within an order of magnitude — the cliff the flat
        # model hides entirely.
        assert t["dcn_s"] > 0 and t["ici_s"] > 0
        flat = CommModel(params, n)
        assert flat.grad_sync_bytes_by_tier()[1] == 0.0
        summ = m.summary()
        assert summ["num_slices"] == slices
        np.testing.assert_allclose(
            summ["grad_sync_bytes_per_step"], ici_b + dcn_b
        )


class TestTraceIntegration:
    @pytest.mark.slow
    def test_app_profile_dir_writes_trace(self, tmp_path):
        from mpit_tpu.asyncsgd import mnist

        out = mnist.main(
            ["--steps", "8", "--batch-size", "16", "--log-every", "8",
             "--profile-dir", str(tmp_path / "prof")]
        )
        assert out["steps"] == 8
        produced = []
        for root, _, files in os.walk(tmp_path / "prof"):
            produced += [os.path.join(root, f) for f in files]
        assert produced, "no trace files written"
