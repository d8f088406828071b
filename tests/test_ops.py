"""Tests for the Pallas native tier (mpit_tpu.ops).

The ring allreduce's semaphore/DMA discipline runs here in TPU interpret
mode on the fake CPU mesh — the "race detection" sanitizer of SURVEY.md §6:
interpret mode simulates the remote DMAs and semaphores across shard_map
"devices", so a protocol bug (clobbered mailbox slot, missing capacity
token) shows up as a wrong sum or a deadlock rather than silent flakiness
on real hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import mpit_tpu
from mpit_tpu.ops import flash_attention, reference_attention, ring_allreduce


def _run_ring(world, x, axis="data", **kw):
    # check_vma=False: the TPU interpreter re-executes the kernel jaxpr with
    # refs as plain arrays, dropping the out_shape's declared vma — the
    # trace-time types are consistent (the compiled TPU path typechecks),
    # but interpret-time re-binding is not. Known jax 0.9 limitation.
    f = world.shard_map(
        lambda v: ring_allreduce(v, axis, interpret=True, **kw),
        in_specs=P(axis),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(f)(x)


@pytest.mark.parametrize("shape", [(8, 128), (8, 4, 131), (3, 1000)])
def test_ring_allreduce_matches_psum(world8, shape):
    n = world8.num_devices
    x = jax.random.normal(jax.random.key(0), (n * shape[0], *shape[1:]))
    got = _run_ring(world8, x)
    want = jax.jit(
        world8.shard_map(
            lambda v: jax.lax.psum(v, "data"), in_specs=P("data"), out_specs=P("data")
        )
    )(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-6)


def test_ring_allreduce_bf16(world8):
    n = world8.num_devices
    x = jax.random.normal(jax.random.key(1), (n * 4, 256)).astype(jnp.bfloat16)
    got = _run_ring(world8, x)
    want = np.asarray(x, np.float32).reshape(n, -1).sum(0)
    got_host = np.asarray(got, np.float32).reshape(n, -1)
    # Every device must hold the same full sum (allreduce, not scatter).
    for r in range(n):
        np.testing.assert_allclose(got_host[r], want, rtol=0.05, atol=0.05)


def test_ring_allreduce_all_devices_identical(world8):
    n = world8.num_devices
    x = jax.random.normal(jax.random.key(2), (n * 8, 128))
    got = np.asarray(_run_ring(world8, x)).reshape(n, -1)
    for r in range(1, n):
        np.testing.assert_allclose(got[r], got[0], rtol=1e-6)


def test_ring_allreduce_subring(n_devices):
    """The kernel on a 2-device subaxis of a 2D mesh (p=2 drain path)."""
    if n_devices % 2:
        pytest.skip("needs an even device count for the 2-wide model axis")
    world = mpit_tpu.init(
        {"data": n_devices // 2, "model": 2}, set_default=False
    )
    x = jnp.arange(2 * 8 * 128, dtype=jnp.float32).reshape(2 * 8, 128)

    f = world.shard_map(
        lambda v: ring_allreduce(v, "model", interpret=True),
        in_specs=P(("data", "model")),
        out_specs=P(("data", "model")),
        check_vma=False,
    )
    got = np.asarray(jax.jit(f)(jnp.tile(x, (n_devices // 2, 1))))
    # Within each data-row, the two model shards must both hold their sum.
    per = x.reshape(2, 8, 128)
    want_pair = (per[0] + per[1])
    got = got.reshape(n_devices // 2, 2, 8, 128)
    for d in range(n_devices // 2):
        np.testing.assert_allclose(got[d, 0], want_pair, rtol=1e-6)
        np.testing.assert_allclose(got[d, 1], want_pair, rtol=1e-6)


class TestFlashAttention:
    """Flash kernel vs the XLA oracle, fwd + custom-VJP bwd (interpret)."""

    def _qkv(self, T=256, B=2, H=4, D=64, dtype=jnp.float32, seed=0):
        ks = jax.random.split(jax.random.key(seed), 3)
        return tuple(jax.random.normal(k, (B, T, H, D), dtype) for k in ks)

    @pytest.mark.parametrize("causal", [True, False])
    def test_forward_matches_reference(self, causal):
        q, k, v = self._qkv()
        out = flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=64, interpret=True
        )
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_gradients_match_reference(self):
        q, k, v = self._qkv(T=128)

        def loss(f):
            return lambda *a: jnp.sum(f(*a) ** 2)

        fl = jax.grad(
            loss(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=True, block_q=64, block_k=64, interpret=True
                )
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        rf = jax.grad(
            loss(lambda q, k, v: reference_attention(q, k, v, causal=True)),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(fl, rf):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            )

    def test_uneven_block_shapes(self):
        # block_q != block_k and blocks spanning several diagonal tiles.
        q, k, v = self._qkv(T=256)
        out = flash_attention(
            q, k, v, causal=True, block_q=128, block_k=64, interpret=True
        )
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_non_tpu_fallback_without_interpret(self):
        # On the CPU mesh, interpret=None must route to the XLA fallback.
        q, k, v = self._qkv(T=64)
        out = flash_attention(q, k, v, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)

    def test_indivisible_seq_rejected(self):
        q, k, v = self._qkv(T=96)
        with pytest.raises(ValueError, match="divisible"):
            flash_attention(
                q, k, v, causal=True, block_q=64, block_k=64, interpret=True
            )

    def test_gpt2_model_integration(self):
        from mpit_tpu.models import GPT2, GPT2Config

        tokens = jax.random.randint(jax.random.key(0), (2, 128), 0, 128)
        # f32 activations: in bf16 the two implementations round differently
        # and the per-layer deltas amplify, which would test the dtype, not
        # the kernel.
        base = GPT2(GPT2Config.tiny(dtype=jnp.float32))
        flash = GPT2(
            GPT2Config.tiny(
                dtype=jnp.float32,
                attention_fn=lambda q, k, v, causal=True: flash_attention(
                    q, k, v, causal=causal, block_q=64, block_k=64, interpret=True
                ),
            )
        )
        variables = base.init(jax.random.key(1), tokens)
        np.testing.assert_allclose(
            np.asarray(base.apply(variables, tokens)),
            np.asarray(flash.apply(variables, tokens)),
            rtol=2e-4,
            atol=2e-4,
        )


class TestFlashBlockAndMerge:
    """Offset-aware block kernel + lse merge (the ring-attention inner)."""

    def _qkv(self, T=128, B=2, H=2, D=64, seed=5):
        ks = jax.random.split(jax.random.key(seed), 3)
        return tuple(jax.random.normal(k, (B, T, H, D)) for k in ks)

    def test_blocks_merge_to_full_attention(self):
        q, k, v = self._qkv(T=128)
        full = reference_attention(q, k, v, causal=True)
        qs, ks_, vs = (jnp.split(x, 2, axis=1) for x in (q, k, v))
        from mpit_tpu.ops import flash_attention_block, merge_attention

        blk = lambda qq, kk, vv, qo, ko: flash_attention_block(
            qq, kk, vv, q_offset=qo, k_offset=ko,
            block_q=64, block_k=64, interpret=True,
        )
        # Second-half queries see both key blocks.
        o_a, l_a = blk(qs[1], ks_[0], vs[0], 64, 0)
        o_b, l_b = blk(qs[1], ks_[1], vs[1], 64, 64)
        got, _ = merge_attention(o_a, l_a, o_b, l_b)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(full[:, 64:]), rtol=3e-5, atol=3e-5
        )
        # First-half queries: the future key block must be a no-op partial.
        o_c, l_c = blk(qs[0], ks_[1], vs[1], 0, 64)
        assert float(jnp.abs(o_c).max()) == 0.0
        o_d, l_d = blk(qs[0], ks_[0], vs[0], 0, 0)
        got0, _ = merge_attention(o_d, l_d, o_c, l_c)
        np.testing.assert_allclose(
            np.asarray(got0), np.asarray(full[:, :64]), rtol=3e-5, atol=3e-5
        )

    @pytest.mark.slow
    def test_block_lse_gradient_path(self):
        """d/dq of a merged pair must match full attention — exercises the
        lse cotangent fold (delta − g_lse) in the Flash-2 backward."""
        q, k, v = self._qkv(T=128)
        from mpit_tpu.ops import flash_attention_block, merge_attention

        def loss_blocks(q, k, v):
            qs, ks_, vs = (jnp.split(x, 2, axis=1) for x in (q, k, v))
            o_a, l_a = flash_attention_block(
                qs[1], ks_[0], vs[0], q_offset=64, k_offset=0,
                block_q=64, block_k=64, interpret=True,
            )
            o_b, l_b = flash_attention_block(
                qs[1], ks_[1], vs[1], q_offset=64, k_offset=64,
                block_q=64, block_k=64, interpret=True,
            )
            o, _ = merge_attention(o_a, l_a, o_b, l_b)
            return jnp.sum(o ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v, causal=True)[:, 64:] ** 2)

        g = jax.grad(loss_blocks, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
            )


class TestFusedLMHead:
    """ops/lm_head.py — streaming vocab-blockwise xent vs the naive path."""

    def _setup(self, B=2, T=9, D=24, V=203, seed=0):
        rng = np.random.RandomState(seed)
        h = jnp.asarray(rng.randn(B, T, D).astype(np.float32))
        head = jnp.asarray(0.2 * rng.randn(V, D).astype(np.float32))
        t = jnp.asarray(rng.randint(0, V, size=(B, T)).astype(np.int32))
        return h, head, t

    @staticmethod
    def _naive(h, head, t):
        logits = jnp.einsum("btd,vd->btv", h, head)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, t[..., None], -1)[..., 0]

    def test_forward_matches_naive(self):
        from mpit_tpu.ops import lm_head_xent

        h, head, t = self._setup()
        # block 64 with V=203: exercises padding of the last block.
        got = lm_head_xent(h, head, t, block_size=64, compute_dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(self._naive(h, head, t)),
            rtol=1e-5, atol=1e-5,
        )

    def test_gradients_match_naive(self):
        from mpit_tpu.ops import lm_head_xent

        h, head, t = self._setup()
        mask = jnp.asarray(
            (np.random.RandomState(1).rand(*t.shape) > 0.3).astype(np.float32)
        )

        def fused_loss(h, w):
            l = lm_head_xent(h, w, t, block_size=64, compute_dtype=jnp.float32)
            return jnp.sum(l * mask) / mask.sum()

        def naive_loss(h, w):
            return jnp.sum(self._naive(h, w, t) * mask) / mask.sum()

        gf = jax.jit(jax.grad(fused_loss, argnums=(0, 1)))(h, head)
        gn = jax.grad(naive_loss, argnums=(0, 1))(h, head)
        for a, b in zip(gf, gn):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
            )

    def test_bf16_compute_close(self):
        from mpit_tpu.ops import lm_head_xent

        h, head, t = self._setup()
        got = lm_head_xent(h, head, t, block_size=64)  # default bf16 operands
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(self._naive(h, head, t)),
            rtol=0.05, atol=0.05,
        )

    @pytest.mark.slow
    def test_gpt2_targets_path_matches_logits_path(self):
        """GPT2(..., targets=) must agree with the materialized-logits loss."""
        from mpit_tpu.models import GPT2, GPT2Config

        cfg = GPT2Config.tiny()  # head_dtype f32 default: exact parity
        model = GPT2(cfg)
        rng = np.random.RandomState(2)
        tokens = jnp.asarray(
            rng.randint(0, cfg.vocab_size, size=(2, 17)).astype(np.int32)
        )
        params = model.init(jax.random.key(0), tokens[:, :-1])["params"]

        def loss_logits(p):
            logits = model.apply({"params": p}, tokens[:, :-1])
            return GPT2.loss_fn(logits, tokens)

        def loss_fused(p):
            return GPT2.fused_loss_fn(model, p, tokens)

        a, ga = jax.value_and_grad(loss_logits)(params)
        b, gb = jax.value_and_grad(loss_fused)(params)
        np.testing.assert_allclose(float(a), float(b), rtol=1e-5)
        jax.tree.map(
            lambda la, lb: np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=5e-5, atol=5e-5
            ),
            ga,
            gb,
        )


class TestHeadGrouping:
    """Round-4 VMEM envelope: the packed flash kernel auto-selects heads
    per program so the resident set fits scoped VMEM (the two calibration
    overflows were caught by the AOT compile check, BENCHMARKS.md)."""

    def test_chooser_selections(self):
        # importlib is load-bearing: `mpit_tpu.ops` re-exports the
        # flash_attention FUNCTION under the submodule's name, so plain
        # `import mpit_tpu.ops.flash_attention as F` binds the function.
        import importlib

        F = importlib.import_module("mpit_tpu.ops.flash_attention")
        pick = F._pick_head_group
        assert pick(512, 12, 64, 512, 512, 2) == 12  # the measured fast path
        assert pick(1024, 12, 64, 512, 512, 2) == 6
        assert pick(2048, 12, 64, 512, 512, 2) == 4
        with pytest.raises(ValueError, match="Shard the sequence"):
            pick(4096, 12, 64, 512, 512, 2)
        # interpret mode has no VMEM: always full heads
        assert pick(8192, 12, 64, 512, 512, 2, interpret=True) == 12
        # no lane-aligned grouping exists -> the error says so
        with pytest.raises(ValueError, match="no lane-aligned"):
            pick(65536, 2, 16, 512, 512, 2)

    def test_grouped_path_parity(self, monkeypatch):
        """Force multi-group execution (ng > 1) and check exact parity —
        the grouped lse/delta lane bookkeeping must match full-head."""
        import importlib  # see test_chooser_selections

        F = importlib.import_module("mpit_tpu.ops.flash_attention")
        monkeypatch.setattr(F, "_pick_head_group", lambda *a, **k: 2)
        rng = jax.random.PRNGKey(0)
        q, k, v = jax.random.normal(rng, (3, 2, 256, 4, 64), jnp.float32)
        out = F.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=128, interpret=True
        )
        ref = F.reference_attention(q, k, v, causal=True)
        assert float(jnp.abs(out - ref).max()) < 1e-5

        def f(q, k, v):
            o, l = F.flash_attention_block(
                q, k, v, q_offset=256, causal=True,
                block_q=128, block_k=128, interpret=True,
            )
            return jnp.sum(o**2) + jnp.sum(jnp.where(l > -1e29, l, 0.0) ** 2)

        def g(q, k, v):
            o, l = F.reference_attention_with_lse(
                q, k, v, q_offset=256, causal=True
            )
            return jnp.sum(o**2) + jnp.sum(jnp.where(l > -1e29, l, 0.0) ** 2)

        ga = jax.grad(f, (0, 1, 2))(q, k, v)
        gb = jax.grad(g, (0, 1, 2))(q, k, v)
        for a, b in zip(ga, gb):
            assert float(jnp.abs(a - b).max()) < 5e-5


@pytest.mark.slow
class TestFlashVmemSweepSubset:
    """3-point subset of ``sweep_flash_vmem.py`` — the regression net the
    sweep's docstring (and flash_attention's ``_GROUP_OVERRIDE`` comment)
    promise: the VMEM head-group estimator's choice must compile fwd+bwd
    through the REAL TPU compiler (AOT against a virtual v5e topology; no
    hardware). Slow-marked: each point is a full Mosaic compile. The full
    grid (24 shapes + rejected-group probes) stays in the standalone
    sweep harness."""

    # One full-heads shape, the round-4 calibration point where grouping
    # engages, and a long-T/wide-D stress point.
    POINTS = [(512, 8, 64), (2048, 12, 64), (4096, 16, 128)]

    @pytest.fixture(scope="class")
    def sweep_world(self, v5e_world):
        import sweep_flash_vmem as sweep

        return sweep, v5e_world

    @pytest.mark.parametrize("t,h,d", POINTS)
    def test_chosen_group_compiles(self, sweep_world, t, h, d):
        sweep, world = sweep_world
        fa = sweep.fa
        bq = fa._pick_block(t, None)
        g = fa._pick_head_group(t, h, d, bq, bq, 2)  # bf16 itemsize
        assert g in ([h] + fa.usable_head_groups(h, d))
        # The estimator's choice must survive the real compiler (an
        # exception here = unsafe estimator, the sweep's "bad_unsafe").
        sweep.compile_shape(world, t, h, d)
