"""Tests for the factored ring collectives + GradSync (ISSUE 9).

Layers under test, innermost out:

- the host-side ring planner (pure geometry — every non-divisible
  payload/axis-size question answered once);
- the quantize/dequantize helpers and their per-chunk error bound;
- the collectives' fallback paths (lax composition — what tier-1
  executes on this container's CPU mesh; the ppermute-spelled q8 ring
  runs the REAL per-hop quantization math);
- the Pallas kernels in TPU interpret mode (the remote-DMA simulator:
  the kernel-vs-fallback parity pin);
- GradSync through ``make_train_step``: grad_sync="ring" BITWISE equal
  to the psum path under ZeRO-1 (the acceptance pin), the plain-DP
  path equal within reduction-order noise, and the quantized mode's
  loss-curve pinned within noise on an MNIST-style accuracy loop;
- the executed-mode stamping (``ring|psum_fallback`` span/instant
  labels) and the quantized-size wire accounting (~¼ bytes into the
  collective counters that feed the roofline/P2P attribution);
- the modeled reduce-scatter/all-gather seconds reconciling EXACTLY
  to the allreduce model (the composition identity).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import mpit_tpu
from mpit_tpu import obs
from mpit_tpu import opt as gopt
from mpit_tpu.ops import ring_collectives as RC
from mpit_tpu.ops import ring_allreduce
from mpit_tpu.train import GradSync, make_train_step
from mpit_tpu.train.grad_sync import GRAD_SYNC_MODES


@pytest.fixture(autouse=True)
def _obs_disabled_by_default():
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------


class TestRingPlan:
    def test_divisible_payload_no_pad(self):
        p = RC.plan_ring(8 * 8 * 128, 8, jnp.float32)
        assert p.chunk_rows == 8 and p.padded_rows == 8
        assert p.chunk_elems == 8 * 128

    @pytest.mark.parametrize(
        "dtype,sub", [(jnp.float32, 8), (jnp.bfloat16, 16), (jnp.int8, 32)]
    )
    def test_sublane_by_wire_dtype(self, dtype, sub):
        assert RC.sublane_for(dtype) == sub
        # 1 row per chunk → padded up to the dtype's tile sublane.
        p = RC.plan_ring(4 * 128, 4, dtype)
        assert p.chunk_rows == 1 and p.padded_rows == sub

    def test_non_divisible_payload(self):
        # 1000 elements over 8 devices: LANE-padded to 8·128, 1 row each.
        p = RC.plan_ring(1000, 8, jnp.float32)
        assert p.chunk_rows == 1 and p.padded_rows == 8
        flat = jnp.arange(1000, dtype=jnp.float32)
        wire = p.to_wire(flat)
        assert wire.shape == (8 * 8, 128)
        # Chunk i covers contiguous elements [i·128, (i+1)·128) with the
        # tile pad at ITS OWN tail — the shard_of-compatible layout.
        chunks = np.asarray(wire).reshape(8, 8, 128)
        np.testing.assert_array_equal(
            chunks[3, 0], np.arange(3 * 128, 4 * 128, dtype=np.float32)
        )
        assert (chunks[:, 1:, :] == 0).all()

    def test_round_trips(self):
        p = RC.plan_ring(777, 4, jnp.int8)
        flat = jnp.arange(777, dtype=jnp.float32)
        wire = p.to_wire(flat)
        back = p.full_from_wire(wire)
        np.testing.assert_array_equal(
            np.asarray(back)[:777], np.asarray(flat)
        )
        shard = jnp.arange(p.chunk_elems, dtype=jnp.float32)
        w2 = p.shard_to_wire(shard)
        assert w2.shape == (p.padded_rows, 128)
        np.testing.assert_array_equal(
            np.asarray(p.shard_from_wire(w2)), np.asarray(shard)
        )

    def test_gathered_from_wire_strips_both_pads(self):
        # Shards of 130 elems (non-divisible by LANE): the gathered
        # flat must be exactly the p source shards, no interleaved pad.
        p = RC.plan_shards(130, 4, jnp.float32)
        full = jnp.stack(
            [p.shard_to_wire(jnp.full((130,), float(i))) for i in range(4)]
        ).reshape(4 * p.padded_rows, 128)
        out = np.asarray(p.gathered_from_wire(full, 130))
        assert out.shape == (4 * 130,)
        for i in range(4):
            np.testing.assert_array_equal(out[i * 130:(i + 1) * 130], i)

    def test_invalid_sizes_rejected(self):
        with pytest.raises(ValueError, match="positive"):
            RC.plan_ring(0, 8, jnp.float32)
        with pytest.raises(ValueError, match="positive"):
            RC.plan_shards(-1, 8, jnp.float32)

    def test_wire_payload_bytes_quantized_quarter(self):
        # The q8 wire is ~¼ the f32 payload (+ one scale block per
        # chunk — negligible once chunks are MBs, visible on small ones).
        n = 8 * 2048 * 128  # 8 MB of f32
        plan_f32 = RC.plan_ring(n, 8, jnp.float32)
        plan_q8 = RC.plan_ring(n, 8, jnp.int8)
        full = plan_f32.wire_payload_bytes(jnp.float32)
        q8 = plan_q8.wire_payload_bytes(jnp.int8, scales=True)
        assert full == n * 4
        assert q8 == n * 1 + 8 * RC.SCALE_BLOCK_BYTES
        assert q8 < full / 3.9


class TestQuantizeChunk:
    def test_round_trip_error_bound(self):
        x = jax.random.normal(jax.random.key(0), (64, 128)) * 3.7
        q, scale = jax.jit(RC.quantize_chunk)(x)
        assert q.dtype == jnp.int8
        err = np.abs(np.asarray(RC.dequantize_chunk(q, scale)) - np.asarray(x))
        # Symmetric round-to-nearest: per-element error ≤ scale/2.
        assert err.max() <= float(scale) / 2 + 1e-7

    def test_all_zero_chunk_exact(self):
        q, scale = RC.quantize_chunk(jnp.zeros((8, 128)))
        assert float(scale) == 1.0
        np.testing.assert_array_equal(
            np.asarray(RC.dequantize_chunk(q, scale)), 0.0
        )

    def test_extremes_hit_127(self):
        x = jnp.array([[1.0, -2.0, 0.5, 2.0]])
        q, scale = RC.quantize_chunk(x)
        assert float(scale) == pytest.approx(2.0 / 127.0)
        assert int(np.abs(np.asarray(q)).max()) == 127


# ---------------------------------------------------------------------------
# Fallback paths (what tier-1 executes; q8 runs the real per-hop math)
# ---------------------------------------------------------------------------


def _run_sharded(world, fn, x, *, out_spec=P("data")):
    f = world.shard_map(
        fn, in_specs=P("data"), out_specs=out_spec, check_vma=False
    )
    return jax.jit(f)(x)


class TestFallbackPaths:
    @pytest.mark.parametrize("shape", [(8, 128), (3, 1000)])
    def test_reduce_scatter_matches_psum(self, world8, shape):
        n = world8.num_devices
        x = jax.random.normal(jax.random.key(0), (n * shape[0], *shape[1:]))
        got = np.asarray(
            _run_sharded(
                world8, lambda v: RC.ring_reduce_scatter(v, "data"), x
            )
        ).ravel()
        want = np.asarray(x).reshape(n, -1).sum(0).ravel()
        want = np.pad(want, (0, got.size - want.size))
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)

    def test_all_gather_concatenates_in_ring_order(self, world8):
        n = world8.num_devices
        x = jnp.arange(n * 37, dtype=jnp.float32).reshape(n, 37)
        got = np.asarray(
            _run_sharded(
                world8, lambda v: RC.ring_all_gather(v, "data"), x,
                out_spec=P(None),
            )
        )
        np.testing.assert_array_equal(got, np.asarray(x).ravel())

    def test_allreduce_qsum_error_bound_and_consistency(self, world8):
        n = world8.num_devices
        x = jax.random.normal(jax.random.key(1), (n * 4, 500))
        got = np.asarray(
            _run_sharded(
                world8, lambda v: ring_allreduce(v, "data", op="qsum"), x
            )
        ).reshape(n, -1)
        want = np.asarray(x).reshape(n, -1).sum(0)
        # Progressive per-hop quantization over 7 hops: a few % relative.
        rel = np.abs(got[0] - want).max() / np.abs(want).max()
        assert rel < 0.05
        # Replica consistency: the quantized all-gather dequantizes the
        # OWN chunk too, so every device holds the bit-identical result.
        for r in range(1, n):
            np.testing.assert_array_equal(got[r], got[0])

    def test_qsum_reduce_scatter_f32_result(self, world8):
        n = world8.num_devices
        x = jax.random.normal(jax.random.key(2), (n, 4 * 128)).astype(
            jnp.bfloat16
        )
        got = _run_sharded(
            world8, lambda v: RC.ring_reduce_scatter(v, "data", op="qsum"), x
        )
        # bf16 in → f32 dequant-accumulate out (the EQuARX receive side).
        assert got.dtype == jnp.float32
        want = np.asarray(x, np.float32).reshape(n, -1).sum(0)
        # The concatenated shards cover the LANE-padded payload; the
        # real elements are its prefix (layout contract).
        got_flat = np.asarray(got).ravel()[: want.size]
        rel = np.abs(got_flat - want).max() / np.abs(want).max()
        assert rel < 0.05

    def test_single_device_axis_is_noop(self, n_devices):
        # p=1 degenerate ring: no wire, no quantization, no kernel
        # (which would deadlock on the drain).
        world = mpit_tpu.init({"data": n_devices, "model": 1},
                              set_default=False)
        x = jnp.arange(n_devices * 8 * 128, dtype=jnp.float32).reshape(
            n_devices * 8, 128
        )
        for fn in (
            lambda v: RC.ring_reduce_scatter(v, "model"),
            lambda v: RC.ring_reduce_scatter(v, "model", op="qsum"),
            lambda v: RC.ring_all_gather(v, "model"),
            lambda v: ring_allreduce(v, "model", op="qsum"),
        ):
            f = world.shard_map(
                fn, in_specs=P(("data", "model")),
                out_specs=P(("data", "model")), check_vma=False,
            )
            got = np.asarray(jax.jit(f)(x)).ravel()
            np.testing.assert_array_equal(got, np.asarray(x).ravel())

    def test_bad_op_rejected(self, world8):
        with pytest.raises(ValueError, match="qsum"):
            _run_sharded(
                world8, lambda v: RC.ring_reduce_scatter(v, "data", op="max"),
                jnp.ones((8, 128)),
            )
        with pytest.raises(ValueError, match="qsum"):
            _run_sharded(
                world8, lambda v: ring_allreduce(v, "data", op="mean"),
                jnp.ones((8, 128)),
            )


# ---------------------------------------------------------------------------
# Interpret-mode kernels (the remote-DMA simulator)
# ---------------------------------------------------------------------------


class TestInterpretKernels:
    """Kernel-vs-fallback parity: the lax composition IS the oracle —
    identical planner geometry and identical per-hop math, so the sum
    forms must match to reduction-order noise and the q8 forms (same
    quantize→ship→dequantize order) essentially exactly."""

    def test_reduce_scatter_parity(self, world8):
        n = world8.num_devices
        x = jax.random.normal(jax.random.key(3), (n * 2, 700))
        kern = np.asarray(
            _run_sharded(
                world8,
                lambda v: RC.ring_reduce_scatter(v, "data", interpret=True),
                x,
            )
        )
        fall = np.asarray(
            _run_sharded(
                world8, lambda v: RC.ring_reduce_scatter(v, "data"), x
            )
        )
        np.testing.assert_allclose(kern, fall, rtol=2e-6, atol=2e-6)

    def test_all_gather_parity_exact(self, world8):
        n = world8.num_devices
        x = jax.random.normal(jax.random.key(4), (n, 300))
        kern = np.asarray(
            _run_sharded(
                world8,
                lambda v: RC.ring_all_gather(v, "data", interpret=True),
                x, out_spec=P(None),
            )
        )
        np.testing.assert_array_equal(kern, np.asarray(x).ravel())

    @pytest.fixture()
    def world4(self):
        """The q8 kernels' two DMA channels starve the 8-device CPU
        client's thread pool in interpret mode (every device thread
        blocks in a buffer-allocation callback; seen as a hang on
        jax 0.9), so their parity pins run on four devices — still a
        multi-hop ring that reuses both mailbox slots."""
        return mpit_tpu.init(
            {"data": 4}, devices=jax.devices()[:4], set_default=False
        )

    def test_q8_reduce_scatter_parity(self, world4):
        n = world4.num_devices
        x = jax.random.normal(jax.random.key(5), (n * 4, 128))
        kern = np.asarray(
            _run_sharded(
                world4,
                lambda v: RC.ring_reduce_scatter(
                    v, "data", op="qsum", interpret=True
                ),
                x,
            )
        )
        fall = np.asarray(
            _run_sharded(
                world4,
                lambda v: RC.ring_reduce_scatter(v, "data", op="qsum"), x,
            )
        )
        np.testing.assert_allclose(kern, fall, rtol=1e-6, atol=1e-6)

    def test_q8_all_gather_parity(self, world4):
        n = world4.num_devices
        x = jax.random.normal(jax.random.key(6), (n, 256))
        kern = np.asarray(
            _run_sharded(
                world4,
                lambda v: RC.ring_all_gather(
                    v, "data", quantized=True, interpret=True
                ),
                x, out_spec=P(None),
            )
        )
        fall = np.asarray(
            _run_sharded(
                world4,
                lambda v: RC.ring_all_gather(v, "data", quantized=True),
                x, out_spec=P(None),
            )
        )
        np.testing.assert_allclose(kern, fall, rtol=1e-6, atol=1e-6)

    def test_allreduce_composition_matches_psum(self, world8):
        n = world8.num_devices
        x = jax.random.normal(jax.random.key(7), (n * 3, 211))
        got = np.asarray(
            _run_sharded(
                world8, lambda v: ring_allreduce(v, "data", interpret=True), x
            )
        )
        want = np.asarray(
            _run_sharded(world8, lambda v: jax.lax.psum(v, "data"), x)
        )
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# GradSync — the training-step integration
# ---------------------------------------------------------------------------


def _mnist_style_loss(params, batch):
    """Tiny MLP softmax-xent — the MNIST-shaped accuracy loop at test
    cost (the convergence-neutrality gate for the quantized wire)."""
    h = jax.nn.relu(batch["x"] @ params["w1"] + params["b1"])
    logits = h @ params["w2"] + params["b2"]
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(
        jnp.take_along_axis(logp, batch["y"][:, None], axis=1)
    )
    acc = jnp.mean((jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32))
    return loss, {"acc": acc}


def _mnist_params(d=36, h=32, classes=10):
    k1, k2 = jax.random.split(jax.random.key(0))
    return {
        "w1": jax.random.normal(k1, (d, h)) * 0.2,
        "b1": jnp.zeros((h,)),
        "w2": jax.random.normal(k2, (h, classes)) * 0.2,
        "b2": jnp.zeros((classes,)),
    }


def _mnist_batch(i, n, d=36, classes=10):
    k = jax.random.key(1000 + i)
    y = jax.random.randint(k, (n * 8,), 0, classes)
    centers = jax.random.normal(jax.random.key(9), (classes, d)) * 2.0
    x = centers[y] + 0.5 * jax.random.normal(jax.random.fold_in(k, 1),
                                             (n * 8, d))
    return {"x": x, "y": y}


def _train(world, mode, *, zero1=True, steps=12, bucket_mb=0.001,
           tx=None, interpret=None):
    """bucket_mb tiny on purpose: the flat MLP gradient splits into
    several buckets, exercising the bucket chaining, not just one."""
    tx = tx or optax.sgd(0.1, momentum=0.9)
    init_fn, step_fn, _ = make_train_step(
        _mnist_style_loss, tx, world, zero1=zero1, grad_sync=mode,
        grad_bucket_mb=bucket_mb, grad_sync_interpret=interpret,
    )
    state = init_fn(_mnist_params())
    losses, accs = [], []
    for i in range(steps):
        state, m = step_fn(state, _mnist_batch(i, world.num_devices))
        losses.append(float(m["loss"]))
        accs.append(float(m["acc"]))
    return state, losses, accs, step_fn


class TestGradSync:
    def test_modes_validated(self):
        assert GRAD_SYNC_MODES == ("psum", "ring", "ring_q8")
        with pytest.raises(ValueError, match="grad_sync"):
            GradSync("data", "q8")
        with pytest.raises(ValueError, match="bucket_mb"):
            GradSync("data", "ring", bucket_mb=0)

    def test_bucket_rows_alignment_and_tail(self):
        gs = GradSync("data", "ring", bucket_mb=1.0)
        rows = gs.bucket_rows(5000, 1)  # 1 MB f32 = 2048 rows
        assert rows[0] == (0, 2048)
        assert rows[-1] == (4096, 5000)  # tail keeps the remainder
        assert all((r1 - r0) % 32 == 0 for r0, r1 in rows[:-1])
        # The bucket is bucket_mb of the FLAT vector: each of n shard
        # views contributes 1/n of it.
        assert gs.bucket_rows(5000, 4)[0] == (0, 512)
        # One bucket when the shard fits.
        assert GradSync("data", "ring", bucket_mb=64).bucket_rows(
            100, 8
        ) == [(0, 100)]

    def test_zero1_ring_bitwise_equals_psum(self, world8):
        """THE acceptance pin: grad_sync="ring" is numerically identical
        to the psum path — bitwise, params AND optimizer state (same
        elementwise sums through lax.psum_scatter on the fallback; the
        same contiguous shard layout by construction)."""
        tx = gopt.goo_adam(1e-2)
        s_psum, l_psum, _, _ = _train(world8, "psum", tx=tx)
        tx2 = gopt.goo_adam(1e-2)
        s_ring, l_ring, _, sf = _train(world8, "ring", tx=tx2)
        assert l_psum == l_ring
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            (s_psum.params, s_psum.opt_state),
            (s_ring.params, s_ring.opt_state),
        )

    def test_plain_dp_ring_matches_psum(self, world8):
        """zero1=False: lax.psum (pmean) vs psum_scatter+all_gather may
        differ in reduction order — pinned to last-bit tolerance, not
        bitwise."""
        s_psum, l_psum, _, _ = _train(world8, "psum", zero1=False)
        s_ring, l_ring, _, _ = _train(world8, "ring", zero1=False)
        np.testing.assert_allclose(l_psum, l_ring, rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            s_psum.params, s_ring.params,
        )

    def test_ring_q8_loss_curve_within_noise(self, world8):
        """Convergence-neutrality gate (ISSUE 9 acceptance): the
        quantized wire's MNIST-style loss curve pins to the f32 sync
        curve within noise — NOT bit-match (lossy by design)."""
        _, l_psum, a_psum, _ = _train(world8, "psum", steps=20)
        _, l_q8, a_q8, _ = _train(world8, "ring_q8", steps=20)
        # Both curves converge...
        assert l_psum[-1] < 0.5 * l_psum[0]
        assert l_q8[-1] < 0.5 * l_q8[0]
        assert a_q8[-1] > 0.9
        # ...and stay within noise of each other at every step.
        for a, b in zip(l_psum, l_q8):
            assert abs(a - b) <= 0.02 + 0.02 * abs(a), (l_psum, l_q8)

    def test_ring_q8_is_actually_lossy(self, world8):
        # The anti-vacuity check for the pin above: the q8 trajectory
        # must DIFFER from f32 sync (identical trajectories would mean
        # the quantization never executed).
        _, l_psum, _, _ = _train(world8, "psum", steps=6)
        _, l_q8, _, _ = _train(world8, "ring_q8", steps=6)
        assert l_psum != l_q8

    def test_exec_mode_labels(self, world8):
        # On this CPU host the compiled ring path is the fallback and
        # the label must say so (ISSUE 9 satellite — no silent fallback).
        on_tpu = jax.devices()[0].platform == "tpu"
        assert GradSync("data", "psum").exec_mode == "psum"
        assert GradSync("data", "ring").exec_mode == (
            "ring" if on_tpu else "psum_fallback"
        )
        assert GradSync("data", "ring_q8").exec_mode == (
            "ring_q8" if on_tpu else "ring_q8_emulated"
        )
        assert GradSync("data", "ring", interpret=True).exec_mode == "ring"
        assert (
            GradSync("data", "ring_q8", interpret=True).exec_mode == "ring_q8"
        )

    def test_step_fn_carries_exec_mode(self, world8):
        _, _, _, step_fn = _train(world8, "ring", steps=1)
        assert step_fn.grad_sync_mode in ("ring", "psum_fallback")
        _, _, _, step_psum = _train(world8, "psum", steps=1)
        assert step_psum.grad_sync_mode == "psum"

    def test_wire_scale(self):
        assert GradSync("data", "psum").wire_scale() == 1.0
        assert GradSync("data", "ring").wire_scale() == 1.0
        assert GradSync("data", "ring_q8").wire_scale(jnp.float32) == 0.25
        assert GradSync("data", "ring_q8").wire_scale(jnp.bfloat16) == 0.5

    def test_obs_wire_bytes_quantized_quarter(self, world8):
        """The accounting pin: tracing a q8 sync charges the collective
        counters at the ACTUAL int8 wire size (~¼ of the f32 payload,
        + scale blocks), with the executed mode stamped — the figures
        the roofline ICI attribution and P2P matrix read."""
        rec = obs.enable(obs.Recorder())
        n = world8.num_devices
        # Per-device flat sized so q8 chunks are whole int8 tiles (512
        # rows each) — the wire expectation below is then EXACT, with
        # no tile-pad term.
        elems = n * (n * 512 * 128)

        def sync(flat, mode):
            gs = GradSync("data", mode, bucket_mb=64)
            return gs.scatter_grads(flat)

        x = jnp.ones((n, elems // n), jnp.float32)
        for mode in ("ring", "ring_q8"):
            jax.jit(world8.shard_map(
                lambda v, m=mode: sync(jnp.ravel(v), m),
                in_specs=P("data"), out_specs=P("data"), check_vma=False,
            ))(x)
        items = list(rec.counter_items("collective_bytes"))
        by_mode = {
            a.get("mode"): v for a, v in items
            if a["op"] == "ring_reduce_scatter"
        }
        # Executed-mode labels present (fallbacks on this CPU host).
        on_tpu = jax.devices()[0].platform == "tpu"
        ring_label = "ring" if on_tpu else "psum_fallback"
        q8_label = "ring" if on_tpu else "lax_emulated"
        assert ring_label in by_mode and q8_label in by_mode
        # Per-device payload is elems/n; q8 wire = int8 + scale blocks.
        per_dev = elems // n
        want_full = (n - 1) / n * (per_dev * 4)
        want_q8 = (n - 1) / n * (per_dev * 1 + n * RC.SCALE_BLOCK_BYTES)
        assert by_mode[ring_label] == pytest.approx(want_full)
        assert by_mode[q8_label] == pytest.approx(want_q8)
        assert by_mode[q8_label] < by_mode[ring_label] / 3.5

    def test_loop_step_spans_stamp_executed_mode(self, world8):
        """The satellite's span-label contract: hardened_loop's step
        spans carry ``grad_sync=<executed mode>`` (the way serve stamps
        ``attention=``), rolled into ``summary()``'s per-phase labels —
        so a fallback run is attributable from the trace alone. The
        default psum mode stays unlabeled (spans byte-identical to
        seed)."""
        from mpit_tpu.train import hardened_loop

        def _run(mode):
            rec = obs.enable(obs.Recorder())
            init_fn, step_fn, _ = make_train_step(
                _mnist_style_loss, optax.sgd(0.05), world8, grad_sync=mode,
            )
            state = init_fn(_mnist_params())
            batches = (
                _mnist_batch(i, world8.num_devices) for i in range(3)
            )
            hardened_loop(
                world8, state, step_fn, batches, steps=3, log_every=10,
            )
            s = rec.summary()
            obs.disable()
            return s["phases"]["step"].get("labels", {})

        ring_labels = _run("ring")
        assert ring_labels.get("grad_sync") in (["ring"], ["psum_fallback"])
        assert "grad_sync" not in _run("psum")

    def test_comm_model_wire_scale(self):
        from mpit_tpu.utils import CommModel

        params = {"w": jnp.zeros((1024, 1024))}
        full = CommModel(params, 8).grad_sync_bytes()
        q8 = CommModel(
            params, 8, wire_scale=GradSync("data", "ring_q8").wire_scale()
        ).grad_sync_bytes()
        assert q8 == pytest.approx(full / 4)
        with pytest.raises(ValueError, match="wire_scale"):
            CommModel(params, 8, wire_scale=0)


class TestModeledSeconds:
    def test_allreduce_is_rs_plus_ag(self):
        from mpit_tpu.utils import (
            modeled_all_gather_seconds,
            modeled_allreduce_seconds,
            modeled_reduce_scatter_seconds,
        )

        for mb in (1, 64, 256):
            payload = mb * 2**20
            for p in (2, 8, 256):
                ar = modeled_allreduce_seconds(payload, p)
                rs = modeled_reduce_scatter_seconds(payload, p)
                ag = modeled_all_gather_seconds(payload, p)
                # The composition identity — the factored collectives
                # reconcile against a model of the right shape.
                assert ar == pytest.approx(rs + ag, rel=1e-12)
        assert modeled_reduce_scatter_seconds(2**20, 1) == 0.0
        assert modeled_all_gather_seconds(2**20, 1) == 0.0

    def test_q8_wire_model_faster(self):
        from bench import _modeled_allreduce_curves

        curves = _modeled_allreduce_curves((64,))
        at = curves["64"]
        assert at["ring"] == at["psum"]
        # ~¼ wire → ~4× algorithm GB/s at bandwidth-bound payloads.
        assert 3.0 < at["q8"] / at["ring"] < 4.1


# ---------------------------------------------------------------------------
# Real-compiler check (no hardware): AOT-compile the ring kernels against
# a described v5e topology (conftest's ``v5e_world``).
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestRingCollectiveCompiles:
    @pytest.fixture(autouse=True)
    def _kernel_path(self, monkeypatch):
        # The collectives pick "kernel or lax" from the attached
        # platform (cpu here); unsteered they compile the lax fallback.
        monkeypatch.setattr(RC, "_use_kernel", lambda _: True)

    @pytest.mark.parametrize(
        "build",
        [
            lambda v: RC.ring_reduce_scatter(v, "data"),
            lambda v: RC.ring_reduce_scatter(v, "data", op="qsum"),
            lambda v: RC.ring_all_gather(v, "data"),
            lambda v: RC.ring_all_gather(v, "data", quantized=True),
            lambda v: ring_allreduce(v, "data", op="qsum"),
        ],
        ids=["rs", "rs_q8", "ag", "ag_q8", "allreduce_q8"],
    )
    def test_kernel_mosaic_compiles(self, v5e_world, build):
        from mpit_tpu.utils.aot import abstractify, aot_compile

        world = v5e_world
        f = jax.jit(
            world.shard_map(
                build, in_specs=P("data"), out_specs=P("data"),
                check_vma=False,
            )
        )
        x = abstractify(
            jax.ShapeDtypeStruct((8, 4096), jnp.float32), world.mesh,
            P("data"),
        )
        compiled = aot_compile(f, x)  # any Mosaic/layout rejection raises
        assert "tpu_custom_call" in compiled.as_text()
