"""Tests for mpit_tpu.parallel — every strategy proven against a
single-device reference computation on the fake 8-device CPU mesh
(SURVEY.md §5.2 parity-test doctrine)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from mpit_tpu import comm
from mpit_tpu.models.gpt2 import GPT2, GPT2Config, default_attention
from mpit_tpu.parallel import (
    MoEMLP,
    expert_parallel_moe,
    gpt2_tp_rules,
    make_pjit_train_step,
    param_partition_specs,
    ring_attention,
    spmd_pipeline,
    tp_mlp,
    ulysses_attention,
)
from mpit_tpu.parallel.pipeline import stack_stage_params
from mpit_tpu.parallel.tp import specs_like_params


def _qkv(key, b=2, t=32, h=4, d=8, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, t, h, d)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        world = comm.init({"seq": 8}, set_default=False)
        q, k, v = _qkv(jax.random.key(0))
        ref = default_attention(q, k, v, causal=causal)

        f = world.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis="seq", causal=causal),
            in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"),
        )
        got = f(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)

    def test_gradients_match(self):
        world = comm.init({"seq": 4}, set_default=False, devices=jax.devices()[:4])
        q, k, v = _qkv(jax.random.key(1), t=16)

        def ref_loss(q, k, v):
            return jnp.sum(default_attention(q, k, v, causal=True) ** 2)

        def ring_loss(q, k, v):
            f = world.shard_map(
                lambda q, k, v: ring_attention(q, k, v, axis="seq", causal=True),
                in_specs=(P(None, "seq"),) * 3,
                out_specs=P(None, "seq"),
            )
            return jnp.sum(f(q, k, v) ** 2)

        g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_ring):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)

    @pytest.mark.slow
    def test_inside_gpt2(self):
        # ring attention as GPT2's attention_fn, seq axis over 4 devices
        world = comm.init({"seq": 4}, set_default=False, devices=jax.devices()[:4])
        cfg_ref = GPT2Config.tiny(dtype=jnp.float32)
        cfg_ring = GPT2Config.tiny(
            dtype=jnp.float32,
            attention_fn=lambda q, k, v, causal=True: ring_attention(
                q, k, v, axis="seq", causal=causal
            ),
        )
        tokens = jax.random.randint(jax.random.key(2), (2, 64), 0, 512)
        params = GPT2(cfg_ref).init(jax.random.key(0), tokens)
        ref = GPT2(cfg_ref).apply(params, tokens)

        t_local = tokens.shape[1] // 4

        def apply_cp(p, t):
            pos = jax.lax.axis_index("seq") * t_local + jnp.arange(t_local)
            return GPT2(cfg_ring).apply(p, t, positions=pos)

        f = world.shard_map(
            apply_cp, in_specs=(P(), P(None, "seq")), out_specs=P(None, "seq")
        )
        got = f(params, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)


class TestUlysses:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        world = comm.init({"seq": 8}, set_default=False)
        q, k, v = _qkv(jax.random.key(3), t=32, h=8)
        ref = default_attention(q, k, v, causal=causal)

        f = world.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis="seq", causal=causal),
            in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"),
        )
        got = f(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)

    def test_rejects_indivisible_heads(self):
        world = comm.init({"seq": 8}, set_default=False)
        q, k, v = _qkv(jax.random.key(4), h=4)  # 4 heads, 8 devices
        f = world.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis="seq"),
            in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq"),
        )
        with pytest.raises(ValueError, match="divisible"):
            f(q, k, v)


class TestMegatronTP:
    def _weights(self, key, d=16, f=32):
        k1, k2 = jax.random.split(key)
        return (
            jax.random.normal(k1, (d, f)) * 0.1,
            jnp.arange(f, dtype=jnp.float32) * 0.01,
            jax.random.normal(k2, (f, d)) * 0.1,
            jnp.ones((d,), jnp.float32) * 0.5,
        )

    def test_tp_mlp_parity(self):
        world = comm.init({"model": 8}, set_default=False)
        fc_k, fc_b, out_k, out_b = self._weights(jax.random.key(5))
        x = jax.random.normal(jax.random.key(6), (2, 8, 16))
        ref = jax.nn.gelu(x @ fc_k + fc_b) @ out_k + out_b

        f = world.shard_map(
            lambda x, a, b, c, d: tp_mlp(x, a, b, c, d, axis="model"),
            in_specs=(P(), P(None, "model"), P("model"), P("model", None), P()),
            out_specs=P(),
        )
        got = f(x, fc_k, fc_b, out_k, out_b)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

    def test_tp_mlp_sequence_parallel(self):
        world = comm.init({"model": 8}, set_default=False)
        fc_k, fc_b, out_k, out_b = self._weights(jax.random.key(7))
        x = jax.random.normal(jax.random.key(8), (2, 16, 16))
        ref = jax.nn.gelu(x @ fc_k + fc_b) @ out_k + out_b

        f = world.shard_map(
            lambda x, a, b, c, d: tp_mlp(
                x, a, b, c, d, axis="model", sequence_parallel=True
            ),
            in_specs=(
                P(None, "model"),  # sequence-sharded residual stream
                P(None, "model"),
                P("model"),
                P("model", None),
                P(),
            ),
            out_specs=P(None, "model"),
        )
        got = f(x, fc_k, fc_b, out_k, out_b)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


class TestPjitTP:
    def test_rules_match_gpt2(self):
        cfg = GPT2Config.tiny()
        tokens = jnp.zeros((1, 8), jnp.int32)
        params = GPT2(cfg).init(jax.random.key(0), tokens)["params"]
        specs = param_partition_specs(params, gpt2_tp_rules("model"))
        flat = {
            "/".join(str(getattr(k, "key", k)) for k in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(specs)[0]
        }
        assert flat["block_0/qkv/kernel"] == P(None, "model")
        assert flat["block_0/proj/kernel"] == P("model", None)
        assert flat["wte"] == P("model", None)
        assert flat["ln_f/scale"] == P()

    def test_fsdp_composition(self):
        cfg = GPT2Config.tiny()
        tokens = jnp.zeros((1, 8), jnp.int32)
        params = GPT2(cfg).init(jax.random.key(0), tokens)["params"]
        specs = param_partition_specs(
            params, gpt2_tp_rules("model"), fsdp_axis="fsdp", fsdp_size=2
        )
        flat = {
            "/".join(str(getattr(k, "key", k)) for k in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(specs)[0]
        }
        # column-parallel kernel gets fsdp on its free (input) dim
        assert flat["block_0/qkv/kernel"] == P("fsdp", "model")
        # replicated params pick up fsdp on dim 0
        assert flat["block_0/ln1/scale"] == P("fsdp")

    def test_opt_state_specs_follow_params(self):
        import optax

        cfg = GPT2Config.tiny()
        tokens = jnp.zeros((1, 8), jnp.int32)
        params = GPT2(cfg).init(jax.random.key(0), tokens)["params"]
        pspecs = param_partition_specs(params, gpt2_tp_rules("model"))
        tx = optax.sgd(0.1, momentum=0.9)
        ospecs = specs_like_params(jax.eval_shape(tx.init, params), params, pspecs)
        flat = jax.tree_util.tree_flatten_with_path(ospecs)[0]
        momentum_specs = {
            "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path): s
            for path, s in flat
        }
        hits = [s for name, s in momentum_specs.items() if "qkv/kernel" in name]
        assert hits and all(s == P(None, "model") for s in hits)

    def test_train_step_dp_tp_loss_decreases(self):
        from mpit_tpu import opt as gopt

        world = comm.init({"data": 2, "model": 4}, set_default=False)
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        model = GPT2(cfg)
        tokens = jax.random.randint(jax.random.key(0), (4, 33), 0, 512)
        params = model.init(jax.random.key(1), tokens[:, :-1])["params"]

        def loss_fn(p, batch):
            logits = model.apply({"params": p}, batch[:, :-1])
            return GPT2.loss_fn(logits, batch), {}

        tx = gopt.goo(0.1, 0.9)
        init_fn, step_fn, _ = make_pjit_train_step(
            loss_fn, tx, world, gpt2_tp_rules("model")
        )
        state = init_fn(params)
        losses = []
        for _ in range(5):
            state, metrics = step_fn(state, tokens)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0]
        assert int(jax.device_get(state.step)) == 5

    @pytest.mark.slow
    def test_tp_matches_single_device_trajectory(self):
        import optax

        world = comm.init({"model": 8}, set_default=False)
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        model = GPT2(cfg)
        tokens = jax.random.randint(jax.random.key(2), (4, 17), 0, 512)
        params = model.init(jax.random.key(3), tokens[:, :-1])["params"]

        def loss_fn(p, batch):
            logits = model.apply({"params": p}, batch[:, :-1])
            return GPT2.loss_fn(logits, batch), {}

        # single-device reference trajectory
        tx = optax.sgd(0.5)
        ref_p, ref_state = params, tx.init(params)
        ref_losses = []
        for _ in range(3):
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(ref_p, tokens)
            u, ref_state = tx.update(g, ref_state, ref_p)
            ref_p = optax.apply_updates(ref_p, u)
            ref_losses.append(float(loss))

        # no "data" axis on this mesh → the step replicates the batch
        init_fn, step_fn, _ = make_pjit_train_step(
            loss_fn, optax.sgd(0.5), world, gpt2_tp_rules("model")
        )
        state = init_fn(params)
        tp_losses = []
        for _ in range(3):
            state, metrics = step_fn(state, tokens)
            tp_losses.append(float(metrics["loss"]))
        np.testing.assert_allclose(tp_losses, ref_losses, rtol=1e-4)


class TestPipeline:
    def test_matches_sequential(self):
        world = comm.init({"pipe": 8}, set_default=False)
        n_stages, m, dim = 8, 4, 16
        keys = jax.random.split(jax.random.key(9), n_stages)
        per_stage = [
            {"w": jax.random.normal(k, (dim, dim)) * 0.3, "b": jnp.ones((dim,)) * 0.01}
            for k in keys
        ]
        stacked = stack_stage_params(per_stage)
        x = jax.random.normal(jax.random.key(10), (m, 2, dim))

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        ref = x
        for p in per_stage:
            ref = stage_fn(p, ref)

        f = world.shard_map(
            lambda sp, mb: spmd_pipeline(stage_fn, sp, mb, axis="pipe"),
            in_specs=(P("pipe"), P()),
            out_specs=P(),
        )
        got = f(stacked, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

    def test_differentiable(self):
        world = comm.init({"pipe": 4}, set_default=False, devices=jax.devices()[:4])
        n_stages, m, dim = 4, 3, 8
        keys = jax.random.split(jax.random.key(11), n_stages)
        per_stage = [{"w": jax.random.normal(k, (dim, dim)) * 0.3} for k in keys]
        stacked = stack_stage_params(per_stage)
        x = jax.random.normal(jax.random.key(12), (m, 2, dim))

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"])

        def ref_loss(stages):
            h = x
            for i in range(n_stages):
                h = stage_fn(jax.tree.map(lambda l: l[i], stages), h)
            return jnp.sum(h ** 2)

        def pipe_loss(stages):
            f = world.shard_map(
                lambda sp, mb: spmd_pipeline(stage_fn, sp, mb, axis="pipe"),
                in_specs=(P("pipe"), P()),
                out_specs=P(),
            )
            return jnp.sum(f(stages, x) ** 2)

        g_ref = jax.grad(ref_loss)(stacked)
        g_pipe = jax.grad(pipe_loss)(stacked)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4
            ),
            g_ref,
            g_pipe,
        )


class TestMoE:
    def _params(self, key, d=8, e=8, f=16):
        ks = jax.random.split(key, 3)
        return {
            "router": jax.random.normal(ks[0], (d, e)) * 0.5,
            "w_in": jax.random.normal(ks[1], (e, d, f)) * 0.2,
            "b_in": jnp.zeros((e, f)),
            "w_out": jax.random.normal(ks[2], (e, f, d)) * 0.2,
            "b_out": jnp.zeros((e, d)),
        }

    def test_ample_capacity_matches_dense_routing(self):
        # With capacity >> tokens, routed MoE == exact top-k mixture.
        params = self._params(jax.random.key(13))
        x = jax.random.normal(jax.random.key(14), (16, 8))
        out, _ = expert_parallel_moe(x, params, k=2, capacity_factor=16.0)

        logits = x @ params["router"]
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
        top2 = jnp.argsort(probs, axis=-1)[:, -2:]
        expected = jnp.zeros_like(x)
        for t in range(x.shape[0]):
            g = probs[t, top2[t]]
            g = g / g.sum()
            acc = jnp.zeros((8,))
            for j, eid in enumerate(top2[t]):
                h = jax.nn.gelu(x[t] @ params["w_in"][eid] + params["b_in"][eid])
                acc += g[j] * (h @ params["w_out"][eid] + params["b_out"][eid])
            expected = expected.at[t].set(acc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)

    def test_expert_parallel_matches_dense(self):
        world = comm.init({"expert": 8}, set_default=False)
        params = self._params(jax.random.key(15))
        # 8 devices × 4 tokens; ample capacity so no drops either path
        x = jax.random.normal(jax.random.key(16), (32, 8))

        dense_out, dense_aux = expert_parallel_moe(
            x, params, k=2, capacity_factor=16.0
        )

        ep_specs = {
            "router": P(),
            "w_in": P("expert"),
            "b_in": P("expert"),
            "w_out": P("expert"),
            "b_out": P("expert"),
        }
        f = world.shard_map(
            lambda x, p: expert_parallel_moe(
                x, p, k=2, capacity_factor=16.0, axis="expert"
            ),
            in_specs=(P("expert"), ep_specs),
            out_specs=(P("expert"), P()),
        )
        ep_out, ep_aux = f(x, params)
        np.testing.assert_allclose(
            np.asarray(ep_out), np.asarray(dense_out), atol=1e-5
        )

    def test_flax_module_trains(self):
        import optax

        model = MoEMLP(num_experts=4, d_ff=16)
        x = jax.random.normal(jax.random.key(17), (8, 4, 8))
        variables = model.init(jax.random.key(18), x)
        out, aux = model.apply(variables, x)
        assert out.shape == x.shape
        # Load-balance loss lower bound is 1 in exact arithmetic; the f32
        # softmax/mean accumulation order differs across jax versions and
        # can land a few 1e-4 under it (0.99950 observed on jax 0.4.37).
        assert float(aux) >= 1.0 - 1e-3

    def test_capacity_drops_tokens(self):
        # Tiny capacity: overflow tokens must come out as zeros (residual
        # passthrough), not garbage.
        params = self._params(jax.random.key(19))
        x = jax.random.normal(jax.random.key(20), (16, 8))
        out, _ = expert_parallel_moe(x, params, k=1, capacity_factor=0.125)
        norms = np.linalg.norm(np.asarray(out), axis=-1)
        assert (norms < 1e-6).any()

    @pytest.mark.parametrize("cf", [0.25, 1.0, 16.0])
    def test_sort_dispatch_matches_einsum_oracle(self, cf):
        """The ragged (argsort/scatter) backend against the one-hot
        oracle: same routing, same queue order, same drops — outputs,
        stats, AND gradients (round-4 verdict item 3). Swept across
        heavy-drop, realistic, and no-drop capacity regimes."""
        params = self._params(jax.random.key(21))
        x = jax.random.normal(jax.random.key(22), (4, 16, 8))

        o1, a1, s1 = expert_parallel_moe(
            x, params, k=2, capacity_factor=cf, with_stats=True,
            dispatch="einsum",
        )
        o2, a2, s2 = expert_parallel_moe(
            x, params, k=2, capacity_factor=cf, with_stats=True,
            dispatch="sort",
        )
        np.testing.assert_allclose(
            np.asarray(o1), np.asarray(o2), rtol=2e-5, atol=2e-6
        )
        np.testing.assert_allclose(float(a1), float(a2), rtol=1e-6)
        np.testing.assert_allclose(
            float(s1["drop_rate"]), float(s2["drop_rate"]), atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(s1["expert_load"]), np.asarray(s2["expert_load"])
        )

        def loss(p, backend):
            o, a = expert_parallel_moe(
                x, p, k=2, capacity_factor=cf, dispatch=backend
            )
            return jnp.sum(o**2) + a

        g1 = jax.grad(loss)(params, "einsum")
        g2 = jax.grad(loss)(params, "sort")
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5
            ),
            g1,
            g2,
        )

    def test_sort_dispatch_expert_parallel_matches_dense(self):
        """The EP all-to-all path with the sort backend (the slot tensor
        layout is backend-independent, so the collective must compose
        identically)."""
        world = comm.init({"expert": 8}, set_default=False)
        params = self._params(jax.random.key(23))
        x = jax.random.normal(jax.random.key(24), (32, 8))

        dense_out, _ = expert_parallel_moe(
            x, params, k=2, capacity_factor=16.0, dispatch="sort"
        )
        ep_specs = {
            "router": P(),
            "w_in": P("expert"),
            "b_in": P("expert"),
            "w_out": P("expert"),
            "b_out": P("expert"),
        }
        f = world.shard_map(
            lambda x, p: expert_parallel_moe(
                x, p, k=2, capacity_factor=16.0, axis="expert",
                dispatch="sort",
            ),
            in_specs=(P("expert"), ep_specs),
            out_specs=(P("expert"), P()),
        )
        ep_out, _ = f(x, params)
        np.testing.assert_allclose(
            np.asarray(ep_out), np.asarray(dense_out), atol=1e-5
        )


class TestRingFlashAttention:
    """CP ring with the fused Pallas block kernel (interpret on CPU mesh)."""

    def _io(self, world, T=256, B=2, H=2, D=64):
        ks = jax.random.split(jax.random.key(7), 3)
        return tuple(jax.random.normal(k, (B, T, H, D)) for k in ks)
    def test_matches_full_attention(self, n_devices):
        import mpit_tpu
        from mpit_tpu.ops import reference_attention
        from mpit_tpu.parallel import ring_flash_attention

        world = mpit_tpu.init({"seq": n_devices}, set_default=False)
        q, k, v = self._io(world, T=n_devices * 32)
        full = reference_attention(q, k, v, causal=True)
        f = jax.jit(
            world.shard_map(
                lambda q, k, v: ring_flash_attention(
                    q, k, v, axis="seq", block_q=32, block_k=32, interpret=True
                ),
                in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
                out_specs=P(None, "seq"),
                check_vma=False,
            )
        )
        np.testing.assert_allclose(
            np.asarray(f(q, k, v)), np.asarray(full), rtol=3e-5, atol=3e-5
        )

    @pytest.mark.slow
    def test_gradients_match_full_attention(self, n_devices):
        import mpit_tpu
        from mpit_tpu.ops import reference_attention
        from mpit_tpu.parallel import ring_flash_attention

        world = mpit_tpu.init({"seq": n_devices}, set_default=False)
        q, k, v = self._io(world, T=n_devices * 32)

        def loss_ring(q, k, v):
            f = world.shard_map(
                lambda q, k, v: ring_flash_attention(
                    q, k, v, axis="seq", block_q=32, block_k=32, interpret=True
                ),
                in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
                out_specs=P(None, "seq"),
                check_vma=False,
            )
            return jnp.sum(f(q, k, v) ** 2)

        g = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(
            lambda q, k, v: jnp.sum(reference_attention(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4
            )


@pytest.mark.slow
class TestContextParallelTraining:
    """The CP train step (parallel.cp): sequence-sharded GPT-2."""

    def _setup(self, mesh_shape):
        import mpit_tpu
        from mpit_tpu.data import SyntheticLM
        from mpit_tpu.models import GPT2, GPT2Config
        from mpit_tpu.opt import goo_adam

        cfg = GPT2Config.tiny(num_heads=2, max_seq_len=128)
        lm = SyntheticLM(vocab_size=cfg.vocab_size, seed=0)
        tx = goo_adam(1e-3)
        world = mpit_tpu.init(mesh_shape, set_default=False)
        model = GPT2(cfg)
        params = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, 128), jnp.int32)
        )["params"]
        return cfg, lm, tx, world, model, params

    @staticmethod
    def _ref_loss(model, p, tokens):
        logits = model.apply({"params": p}, tokens)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        ll = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        mask = jnp.ones_like(ll).at[:, -1].set(0.0)
        return -jnp.sum(ll * mask) / jnp.sum(mask)

    @pytest.mark.parametrize(
        "flash,ulysses",
        [(False, False), (True, False), (False, True), (True, True)],
    )
    def test_matches_single_device_trajectory(self, flash, ulysses):
        import optax
        from mpit_tpu.data import shard_batch
        from mpit_tpu.parallel import make_gpt2_cp_train_step

        # Ulysses needs num_heads (2) divisible by the seq axis size.
        mesh = {"data": 4, "seq": 2} if ulysses else {"data": 2, "seq": 4}
        cfg, lm, tx, world, model, params = self._setup(mesh)
        init_fn, step_fn, _ = make_gpt2_cp_train_step(
            cfg, tx, world, flash=flash, ulysses=ulysses,
            interpret=True if flash else None,
        )
        state = init_fn(params)
        ref_state, ref_params = tx.init(params), params
        stream = lm.batches(4, 128)
        for _ in range(3):
            tokens = next(stream)["tokens"][:, :128]
            state, m = step_fn(
                state, shard_batch(world, {"tokens": tokens}, spec=P("data", "seq"))
            )
            l, g = jax.value_and_grad(
                lambda p: self._ref_loss(model, p, jnp.asarray(tokens))
            )(ref_params)
            up, ref_state = tx.update(g, ref_state, ref_params)
            ref_params = optax.apply_updates(ref_params, up)
            np.testing.assert_allclose(
                float(m["loss"]), float(l), rtol=3e-4, atol=3e-4
            )

    def test_app_cp_tier_trains(self):
        from mpit_tpu.asyncsgd import gpt2 as app

        out = app.main(
            ["--mesh", "data=2,seq=4", "--steps", "12", "--batch-size", "8",
             "--seq-len", "64", "--vocab-size", "128", "--num-layers", "2",
             "--num-heads", "2", "--d-model", "32", "--log-every", "6"]
        )
        assert out["tier"] == "cp-ring"
        assert out["final_loss"] < out["uniform_loss"]


class TestHeadDtype:
    def test_bf16_head_matches_f32_head(self):
        from mpit_tpu.models import GPT2, GPT2Config

        tokens = jax.random.randint(jax.random.key(3), (2, 64), 0, 128)
        base = GPT2(GPT2Config.tiny(dtype=jnp.float32))
        fast = GPT2(
            GPT2Config.tiny(dtype=jnp.float32, head_dtype=jnp.bfloat16)
        )
        variables = base.init(jax.random.key(4), tokens)
        a = np.asarray(base.apply(variables, tokens))
        b = np.asarray(fast.apply(variables, tokens))
        assert b.dtype == np.float32  # f32 accumulation preserved
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)


@pytest.mark.slow
class TestPipelineParallelTraining:
    """The PP train step (parallel.pp): stage-sharded GPT-2 + GPipe ring."""

    def test_matches_single_device_trajectory(self):
        import optax
        import mpit_tpu
        from mpit_tpu.data import SyntheticLM, shard_batch
        from mpit_tpu.models import GPT2
        from mpit_tpu.opt import goo_adam
        from mpit_tpu.parallel import make_gpt2_pp_train_step, split_gpt2_params

        cfg = GPT2Config.tiny(
            num_heads=2, max_seq_len=64, num_layers=4, tie_head=False
        )
        lm = SyntheticLM(vocab_size=cfg.vocab_size, seed=0)
        stream = lm.batches(8, 64)
        tx = goo_adam(1e-3)
        world = mpit_tpu.init({"data": 2, "pipe": 4}, set_default=False)
        model = GPT2(cfg)
        full = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, 64), jnp.int32)
        )["params"]
        split = split_gpt2_params(full, cfg.num_layers, 4)
        init_fn, step_fn, _ = make_gpt2_pp_train_step(
            cfg, tx, world, num_microbatches=4
        )
        state = init_fn(split)

        def ref_loss(p, tokens):
            logits = model.apply({"params": p}, tokens[:, :-1])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            ll = jnp.take_along_axis(logp, tokens[:, 1:][..., None], -1)[..., 0]
            return -jnp.mean(ll)

        ref_state, ref_params = tx.init(full), full
        for _ in range(3):
            toks = next(stream)["tokens"]
            state, m = step_fn(state, shard_batch(world, {"tokens": toks}))
            l, g = jax.value_and_grad(ref_loss)(ref_params, jnp.asarray(toks))
            up, ref_state = tx.update(g, ref_state, ref_params)
            ref_params = optax.apply_updates(ref_params, up)
            np.testing.assert_allclose(float(m["loss"]), float(l), rtol=3e-4)

    def test_requires_untied_head_and_divisible_layers(self):
        import mpit_tpu
        from mpit_tpu.opt import goo_adam
        from mpit_tpu.parallel import make_gpt2_pp_train_step

        world = mpit_tpu.init({"data": 2, "pipe": 4}, set_default=False)
        with pytest.raises(ValueError, match="untied"):
            make_gpt2_pp_train_step(
                GPT2Config.tiny(num_layers=4), goo_adam(1e-3), world
            )
        with pytest.raises(ValueError, match="divide"):
            make_gpt2_pp_train_step(
                GPT2Config.tiny(num_layers=3, tie_head=False),
                goo_adam(1e-3), world,
            )

    def test_app_pp_tier_trains(self):
        from mpit_tpu.asyncsgd import gpt2 as app

        out = app.main(
            ["--mesh", "data=2,pipe=4", "--steps", "12", "--batch-size", "8",
             "--seq-len", "64", "--vocab-size", "128", "--num-layers", "4",
             "--num-heads", "2", "--d-model", "32", "--log-every", "6",
             "--zero1", "false"]
        )
        assert out["tier"] == "pp-gpipe-m4"
        assert out["final_loss"] < out["uniform_loss"]


class TestPipelineZero1:
    """ZeRO-1 x PP (round-2): per-group flat sharding of goo state."""

    def _build(self, zero1):
        import mpit_tpu
        from mpit_tpu.models import GPT2
        from mpit_tpu.opt import goo_adam
        from mpit_tpu.parallel import make_gpt2_pp_train_step, split_gpt2_params

        cfg = GPT2Config.tiny(
            num_heads=2, max_seq_len=64, num_layers=4, tie_head=False
        )
        tx = goo_adam(1e-3)
        world = mpit_tpu.init({"data": 2, "pipe": 4}, set_default=False)
        model = GPT2(cfg)
        full = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, 64), jnp.int32)
        )["params"]
        split = split_gpt2_params(full, cfg.num_layers, 4)
        init_fn, step_fn, _ = make_gpt2_pp_train_step(
            cfg, tx, world, num_microbatches=4, zero1=zero1
        )
        return world, split, init_fn, step_fn

    @pytest.mark.slow
    def test_matches_unsharded_trajectory(self):
        from mpit_tpu.data import SyntheticLM, shard_batch

        lm = SyntheticLM(vocab_size=512, seed=0)
        stream = lm.batches(8, 64)
        world, split, init_a, step_a = self._build(zero1=True)
        _, _, init_b, step_b = self._build(zero1=False)
        sa, sb = init_a(split), init_b(split)
        for _ in range(3):
            batch = shard_batch(world, {"tokens": next(stream)["tokens"]})
            sa, ma = step_a(sa, batch)
            sb, mb = step_b(sb, batch)
            np.testing.assert_allclose(
                float(ma["loss"]), float(mb["loss"]), rtol=2e-5
            )
        # Params stay in lockstep leaf-by-leaf, not just by loss.
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
            ),
            sa.params,
            sb.params,
        )

    def test_state_memory_shards_by_data(self):
        """Every flat goo-state vector is genuinely sharded: per-device
        shard size x (product of its spec's mesh axes) == global size —
        the north-star "goo state sharded across chips" under PP."""
        world, split, init_fn, _ = self._build(zero1=True)
        state = init_fn(split)
        vec = [
            l
            for l in jax.tree.leaves(state.opt_state)
            if getattr(l, "ndim", 0) == 1 and l.size > 1
        ]
        assert vec, "expected flat sharded state vectors"
        for l in vec:
            axes = [
                a
                for part in l.sharding.spec
                if part is not None
                for a in ((part,) if isinstance(part, str) else part)
            ]
            factor = int(np.prod([world.mesh.shape[a] for a in axes]))
            assert factor >= world.axis_size("data"), l.sharding.spec
            shard = next(iter(l.addressable_shards))
            assert shard.data.size * factor == l.size


class Test1F1BSchedule:
    """spmd_pipeline_1f1b (round 2): interleaved fwd/bwd with O(P) memory."""

    def _build(self, schedule, zero1=False):
        import mpit_tpu
        from mpit_tpu.models import GPT2
        from mpit_tpu.opt import goo_adam
        from mpit_tpu.parallel import make_gpt2_pp_train_step, split_gpt2_params

        # f32 activations: the 1f1b backward RECOMPUTES the stage forward
        # while GPipe-AD reuses saved residuals — in bf16 the two round
        # differently on near-zero grads, which adam's sign-normalizing
        # update then amplifies; f32 makes the parity sharp.
        cfg = GPT2Config.tiny(
            num_heads=2, max_seq_len=64, num_layers=4, tie_head=False,
            dtype=jnp.float32,
        )
        # goo SGD+momentum, not adam: adam's sign-normalizing update turns
        # ~1e-7 summation-order noise (1f1b reduces the loss per
        # microbatch, gpipe over the full batch) into ~lr-sized param
        # deltas on near-zero-grad elements; SGD keeps the comparison a
        # direct test of the hand-rolled backward.
        from mpit_tpu.opt import goo

        tx = goo(0.05, 0.9)
        world = mpit_tpu.init({"data": 2, "pipe": 4}, set_default=False)
        model = GPT2(cfg)
        full = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, 64), jnp.int32)
        )["params"]
        split = split_gpt2_params(full, cfg.num_layers, 4)
        init_fn, step_fn, _ = make_gpt2_pp_train_step(
            cfg, tx, world, num_microbatches=4, zero1=zero1,
            schedule=schedule,
        )
        return world, split, init_fn, step_fn

    @pytest.mark.slow
    @pytest.mark.parametrize("zero1", [False, True])
    def test_matches_gpipe_trajectory(self, zero1):
        """1F1B's hand-rolled backward must track the AD oracle exactly:
        per-leaf params after 3 steps, not just losses."""
        from mpit_tpu.data import SyntheticLM, shard_batch

        stream = SyntheticLM(vocab_size=512, seed=0).batches(8, 64)
        world, split, init_a, step_a = self._build("1f1b", zero1=zero1)
        _, _, init_b, step_b = self._build("gpipe", zero1=zero1)
        sa, sb = init_a(split), init_b(split)
        for _ in range(3):
            batch = shard_batch(world, {"tokens": next(stream)["tokens"]})
            sa, ma = step_a(sa, batch)
            sb, mb = step_b(sb, batch)
            np.testing.assert_allclose(
                float(ma["loss"]), float(mb["loss"]), rtol=2e-5
            )
        # Writing this test found a real round-1 bug: the gpipe head ran
        # on broadcast outputs with pipe-varying head params, so the
        # broadcast's AD transpose psum'ed the cotangent — every stage
        # grad scaled by n_pipe (masked by adam's scale invariance; see
        # parallel/pp.py module docstring). With the fix both schedules
        # track single-device AD, so the tolerance here is tight.
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            ),
            sa.params,
            sb.params,
        )

    def test_memory_flat_in_microbatch_count(self):
        """The 1F1B memory bound (VERDICT round-1 item 7 done-criterion):
        compiled temp memory of the 1f1b step is constant in M (the
        stage-input ring is ``live_microbatch_slots(P) = 2P`` slots),
        while GPipe-through-AD's grows linearly with M."""
        import mpit_tpu
        from mpit_tpu.comm import collectives as C
        from mpit_tpu.parallel import (
            live_microbatch_slots,
            spmd_pipeline,
            spmd_pipeline_1f1b,
        )

        assert live_microbatch_slots(4) == 8
        world = mpit_tpu.init(
            {"pipe": 4}, set_default=False, devices=jax.devices()[:4]
        )
        d = 32

        def temp_bytes(m, use_1f1b):
            stage_p = jnp.zeros((4, 1, d, d))
            emb = {"w": jnp.zeros((d, d))}
            head = {"w": jnp.zeros((d, d))}
            xs = jnp.zeros((m, 2, d))
            tg = jnp.zeros((m, 2, d))

            def stage_fn(p, x):
                return jnp.tanh(x @ p[0])

            if use_1f1b:
                def f(stage_p, emb, head, xs, tg):
                    params = {"stages": stage_p, "embed": emb, "head": head}
                    return spmd_pipeline_1f1b(
                        stage_fn,
                        lambda ep, mb: mb @ ep["w"],
                        lambda hp, y, t: jnp.mean((y @ hp["w"] - t) ** 2),
                        params, xs, tg, axis="pipe",
                    )

                out_g = {
                    "stages": jax.tree.map(lambda _: P("pipe"), stage_p),
                    "embed": {"w": P("pipe")},
                    "head": {"w": P("pipe")},
                }
            else:
                def f(stage_p, emb, head, xs, tg):
                    def loss_fn(sp, e, h):
                        xe = xs @ e["w"]
                        y = spmd_pipeline(stage_fn, sp, xe, axis="pipe")
                        return jnp.mean((y @ h["w"] - tg) ** 2)

                    return jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
                        C.vary(stage_p, "pipe"), emb, head
                    )

                out_g = (
                    jax.tree.map(lambda _: P("pipe"), stage_p),
                    {"w": P("pipe")},
                    {"w": P("pipe")},
                )
            g = world.shard_map(
                f,
                in_specs=(P("pipe"), P(), P(), P(), P()),
                out_specs=(P(), out_g),
            )
            comp = jax.jit(g).lower(stage_p, emb, head, xs, tg).compile()
            ma = comp.memory_analysis()
            return getattr(ma, "temp_size_in_bytes", None)

        t1 = [temp_bytes(m, True) for m in (4, 32)]
        tg_ = [temp_bytes(m, False) for m in (4, 32)]
        if t1[0] is None or tg_[0] is None:
            pytest.skip("backend exposes no memory_analysis")
        # 1f1b: flat in M (allow a tiny slack for the index arrays);
        # gpipe: grows by at least the 28 extra microbatch residual sets.
        assert t1[1] <= t1[0] * 1.1 + 4096, (t1, tg_)
        assert tg_[1] > tg_[0] * 3, (t1, tg_)


@pytest.mark.slow
class TestInterleaved1F1B:
    """spmd_pipeline_interleaved_1f1b (round 3): virtual stages — V
    chunks per device, activations circle the ring V times."""

    def _build(self, schedule, n_pipe=2, num_chunks=2):
        import mpit_tpu
        from mpit_tpu.models import GPT2
        from mpit_tpu.opt import goo
        from mpit_tpu.parallel import (
            make_gpt2_pp_train_step,
            split_gpt2_params,
            split_gpt2_params_interleaved,
        )

        # f32 + SGD for sharp parity (same reasoning as Test1F1BSchedule).
        cfg = GPT2Config.tiny(
            num_heads=2, max_seq_len=64, num_layers=4, tie_head=False,
            dtype=jnp.float32,
        )
        tx = goo(0.05, 0.9)
        world = mpit_tpu.init(
            {"data": 2, "pipe": n_pipe}, set_default=False,
            devices=jax.devices()[: 2 * n_pipe],
        )
        model = GPT2(cfg)
        full = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, 64), jnp.int32)
        )["params"]
        if schedule == "interleaved":
            split = split_gpt2_params_interleaved(
                full, cfg.num_layers, n_pipe, num_chunks
            )
        else:
            split = split_gpt2_params(full, cfg.num_layers, n_pipe)
        init_fn, step_fn, _ = make_gpt2_pp_train_step(
            cfg, tx, world, num_microbatches=4, zero1=False,
            schedule=schedule, num_chunks=num_chunks,
        )
        return world, split, init_fn, step_fn

    def test_matches_gpipe_trajectory(self):
        """Virtual-stage schedule vs the AD oracle: per-leaf params after
        3 steps (same dense model, different stage partitioning)."""
        from mpit_tpu.data import SyntheticLM, shard_batch

        stream = SyntheticLM(vocab_size=512, seed=0).batches(8, 64)
        world, split_i, init_a, step_a = self._build("interleaved")
        _, split_g, init_b, step_b = self._build("gpipe")
        sa, sb = init_a(split_i), init_b(split_g)
        for _ in range(3):
            batch = shard_batch(world, {"tokens": next(stream)["tokens"]})
            sa, ma = step_a(sa, batch)
            sb, mb = step_b(sb, batch)
            np.testing.assert_allclose(
                float(ma["loss"]), float(mb["loss"]), rtol=2e-5
            )
        # Same rest leaves directly; stage leaves live in different
        # layouts ([P,V,1,...] vs [P,2,...]) — compare as flat sums of
        # per-leaf reshapes via the rest tree + losses above, and
        # spot-check one kernel end-to-end.
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            ),
            sa.params["rest"],
            sb.params["rest"],
        )
        # interleaved chunk (v=1, i=0) holds global stage 2 = gpipe
        # stage 1's first block (P=2: blocks [2,3] -> stage 1 block 0).
        a = np.asarray(
            jax.tree.leaves(sa.params["stages"])[0]
        )  # [P, V, 1, ...]
        b = np.asarray(jax.tree.leaves(sb.params["stages"])[0])  # [P, 2, ...]
        np.testing.assert_allclose(a[0, 1, 0], b[1, 0], rtol=1e-4, atol=1e-5)

    def test_v1_degenerates_to_1f1b(self):
        """V=1 reproduces the non-interleaved schedule's exact tick
        algebra — trajectories must be bit-comparable to 1f1b."""
        from mpit_tpu.data import SyntheticLM, shard_batch
        from mpit_tpu.parallel import interleaved_ticks

        assert interleaved_ticks(8, 4, 1) == 8 + 2 * 4 - 1
        stream = SyntheticLM(vocab_size=512, seed=1).batches(8, 64)
        world, split_i, init_a, step_a = self._build(
            "interleaved", num_chunks=1
        )
        _, split_g, init_b, step_b = self._build("1f1b")
        # [P, 1, k, ...] vs [P, k, ...]: same leaves, extra unit dim.
        sa, sb = init_a(split_i), init_b(split_g)
        for _ in range(2):
            batch = shard_batch(world, {"tokens": next(stream)["tokens"]})
            sa, ma = step_a(sa, batch)
            sb, mb = step_b(sb, batch)
            np.testing.assert_allclose(
                float(ma["loss"]), float(mb["loss"]), rtol=1e-6
            )

    def test_tick_count_and_bubble(self):
        """The honest bubble accounting (pipeline.interleaved_ticks):
        total ticks m·v + v·p + p − 1 for m % p == 0; the bubble
        (v·p + p − 1 chunk-ticks) beats the non-interleaved eager
        schedule's (2p − 1)·v chunk-tick equivalents for every v >= 2,
        approaching half as v grows."""
        from mpit_tpu.parallel import interleaved_ticks

        for m, p, v in [(8, 4, 2), (16, 4, 4), (8, 2, 2)]:
            assert interleaved_ticks(m, p, v) == m * v + v * p + p - 1
            bubble_int = v * p + p - 1
            bubble_non = (2 * p - 1) * v
            assert bubble_int < bubble_non

    def test_memory_flat_in_microbatch_count(self):
        """Compiled temp memory is constant in M: the [V, 2P] chunk-input
        ring replaces GPipe's M in-flight residual sets."""
        import mpit_tpu
        from mpit_tpu.parallel import spmd_pipeline_interleaved_1f1b

        world = mpit_tpu.init(
            {"pipe": 2}, set_default=False, devices=jax.devices()[:2]
        )
        d = 32

        def temp_bytes(m):
            stage_p = jnp.zeros((2, 2, 1, d, d))  # [P, V, k'=1, d, d]
            emb = {"w": jnp.zeros((d, d))}
            head = {"w": jnp.zeros((d, d))}
            xs = jnp.zeros((m, 2, d))
            tg = jnp.zeros((m, 2, d))

            def stage_fn(p, x):
                return jnp.tanh(x @ p[0])

            def f(stage_p, emb, head, xs, tg):
                params = {"stages": stage_p, "embed": emb, "head": head}
                return spmd_pipeline_interleaved_1f1b(
                    stage_fn,
                    lambda ep, mb: mb @ ep["w"],
                    lambda hp, y, t: jnp.mean((y @ hp["w"] - t) ** 2),
                    params, xs, tg, axis="pipe",
                )

            out_g = {
                "stages": jax.tree.map(lambda _: P("pipe"), stage_p),
                "embed": {"w": P("pipe")},
                "head": {"w": P("pipe")},
            }
            g = world.shard_map(
                f,
                in_specs=(P("pipe"), P(), P(), P(), P()),
                out_specs=(P(), out_g),
            )
            comp = jax.jit(g).lower(stage_p, emb, head, xs, tg).compile()
            ma = comp.memory_analysis()
            return getattr(ma, "temp_size_in_bytes", None)

        t = [temp_bytes(m) for m in (4, 32)]
        if t[0] is None:
            pytest.skip("backend exposes no memory_analysis")
        assert t[1] <= t[0] * 1.1 + 4096, t


@pytest.mark.slow
class TestPerLeafGradientParity:
    """VERDICT round-1 item 8: the tiers' effective gradients checked
    leaf-by-leaf against single-device autodiff (one optimizer step with
    plain goo SGD, so grads map linearly to param deltas — writing the
    PP variant of this test exposed the round-1 broadcast-cotangent bug)."""

    def _ref_step(self, model, full, toks, tx):
        import optax

        def ref_loss(p):
            return jnp.mean(
                model.apply({"params": p}, toks[:, :-1], targets=toks[:, 1:])
            )

        _, g = jax.value_and_grad(ref_loss)(full)
        up, _ = tx.update(g, tx.init(full), full)
        return optax.apply_updates(full, up)

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    def test_pp_step_matches_single_device(self, schedule):
        import mpit_tpu
        from mpit_tpu.data import shard_batch
        from mpit_tpu.opt import goo
        from mpit_tpu.parallel import make_gpt2_pp_train_step, split_gpt2_params

        cfg = GPT2Config.tiny(
            num_heads=2, max_seq_len=64, num_layers=4, tie_head=False,
            dtype=jnp.float32,
        )
        world = mpit_tpu.init({"data": 2, "pipe": 4}, set_default=False)
        model = GPT2(cfg)
        full = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, 64), jnp.int32)
        )["params"]
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, 512, size=(8, 65)).astype(
                np.int32
            )
        )
        ref = split_gpt2_params(
            self._ref_step(model, full, toks, goo(0.05, 0.9)), cfg.num_layers, 4
        )
        split = split_gpt2_params(full, cfg.num_layers, 4)
        init_fn, step_fn, _ = make_gpt2_pp_train_step(
            cfg, goo(0.05, 0.9), world, num_microbatches=4, schedule=schedule
        )
        state, _ = step_fn(init_fn(split), shard_batch(world, {"tokens": toks}))
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6
            ),
            state.params,
            ref,
        )

    def test_cp_step_matches_single_device(self):
        import mpit_tpu
        from mpit_tpu.data import shard_batch
        from mpit_tpu.opt import goo
        from mpit_tpu.parallel import make_gpt2_cp_train_step

        cfg = GPT2Config.tiny(num_heads=2, max_seq_len=64, dtype=jnp.float32)
        world = mpit_tpu.init({"data": 2, "seq": 4}, set_default=False)
        model = GPT2(cfg)
        full = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, 64), jnp.int32)
        )["params"]
        # The cp step trains on [B, T]: T tokens, T-1 supervised positions
        # (the global last has no target). Mirror that exactly in the ref:
        toks = jnp.asarray(
            np.random.RandomState(1).randint(0, 512, size=(8, 64)).astype(
                np.int32
            )
        )
        import optax

        def ref_loss(p):
            losses = model.apply(
                {"params": p}, toks, targets=jnp.pad(toks[:, 1:], ((0, 0), (0, 1)))
            )
            return jnp.sum(losses[:, :-1]) / (toks.shape[0] * (toks.shape[1] - 1))

        _, g = jax.value_and_grad(ref_loss)(full)
        tx = goo(0.05, 0.9)
        up, _ = tx.update(g, tx.init(full), full)
        ref = optax.apply_updates(full, up)

        from jax.sharding import PartitionSpec as P

        init_fn, step_fn, _ = make_gpt2_cp_train_step(
            cfg, goo(0.05, 0.9), world, zero1=False
        )
        batch = shard_batch(world, {"tokens": toks}, spec=P("data", "seq"))
        state, _ = step_fn(init_fn(full), batch)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6
            ),
            state.params,
            ref,
        )


class TestMegatronBlock:
    """Full-block Megatron TP/SP (round-2 item 10): tp_transformer_block
    vs the flax Block, exact numerics."""

    def _setup(self):
        from mpit_tpu.parallel import repack_qkv, unpack_qkv

        cfg = GPT2Config.tiny(num_heads=8, d_model=32, dtype=jnp.float32)
        from mpit_tpu.models.gpt2 import Block

        block = Block(cfg)
        x = jnp.asarray(
            np.random.RandomState(0).randn(2, 16, 32).astype(np.float32)
        )
        params = block.init(jax.random.key(0), x)["params"]
        ref = block.apply({"params": params}, x)
        packed = repack_qkv(params, 8)
        # repack/unpack is a true inverse
        rt = unpack_qkv(packed, 8)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            rt,
            params,
        )
        return packed, x, ref

    def test_tp_block_matches_flax_block(self):
        from mpit_tpu.parallel import tp_block_specs, tp_transformer_block

        packed, x, ref = self._setup()
        world = comm.init({"model": 8}, set_default=False)
        f = world.shard_map(
            lambda p, x: tp_transformer_block(
                p, x, num_heads=8, dtype=jnp.float32
            ),
            in_specs=(tp_block_specs("model"), P()),
            out_specs=P(),
        )
        got = jax.jit(f)(packed, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5
        )

    def test_sequence_parallel_block_matches(self):
        """Megatron-SP: residual stream and LayerNorms stay sequence-
        sharded; all-gather/reduce-scatter bound each TP region."""
        from mpit_tpu.parallel import tp_block_specs, tp_transformer_block

        packed, x, ref = self._setup()
        world = comm.init({"model": 8}, set_default=False)
        f = world.shard_map(
            lambda p, x: tp_transformer_block(
                p, x, num_heads=8, dtype=jnp.float32, sequence_parallel=True
            ),
            in_specs=(tp_block_specs("model"), P(None, "model")),
            out_specs=P(None, "model"),
        )
        got = jax.jit(f)(packed, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5
        )

    def test_rejects_indivisible_heads(self):
        from mpit_tpu.parallel import tp_block_specs, tp_transformer_block

        packed, x, _ = self._setup()
        world = comm.init({"model": 8}, set_default=False)
        f = world.shard_map(
            lambda p, x: tp_transformer_block(
                p, x, num_heads=6, dtype=jnp.float32
            ),
            in_specs=(tp_block_specs("model"), P()),
            out_specs=P(),
        )
        with pytest.raises(ValueError, match="divide"):
            jax.jit(f)(packed, x)


class Test3DComposition:
    """Round-2 item 3: data x model x pipe (and TP inside CP) in one
    jitted step, trajectory-exact vs single-device AD."""

    def _ref_step(self, model, full, loss_fn, tx):
        import optax

        _, g = jax.value_and_grad(loss_fn)(full)
        up, _ = tx.update(g, tx.init(full), full)
        return optax.apply_updates(full, up)

    @pytest.mark.parametrize("zero1", [False, True])
    def test_dp_tp_pp_matches_single_device(self, zero1):
        import mpit_tpu
        from mpit_tpu.data import shard_batch
        from mpit_tpu.opt import goo
        from mpit_tpu.parallel import (
            make_gpt2_dp_tp_pp_train_step,
            split_gpt2_params_3d,
        )

        cfg = GPT2Config.tiny(
            num_heads=4, max_seq_len=64, num_layers=4, tie_head=False,
            dtype=jnp.float32,
        )
        model = GPT2(cfg)
        full = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, 64), jnp.int32)
        )["params"]
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, 512, size=(8, 65)).astype(
                np.int32
            )
        )

        def ref_loss(p):
            return jnp.mean(
                model.apply({"params": p}, toks[:, :-1], targets=toks[:, 1:])
            )

        ref = split_gpt2_params_3d(
            self._ref_step(model, full, ref_loss, goo(0.05, 0.9)),
            cfg.num_layers, 2, 2,
        )
        world = mpit_tpu.init(
            {"data": 2, "model": 2, "pipe": 2}, set_default=False
        )
        split = split_gpt2_params_3d(full, cfg.num_layers, 2, 2)
        init_fn, step_fn, _ = make_gpt2_dp_tp_pp_train_step(
            cfg, goo(0.05, 0.9), world, num_microbatches=4, zero1=zero1
        )
        state, m = step_fn(
            init_fn(split), shard_batch(world, {"tokens": toks})
        )
        assert np.isfinite(float(m["loss"]))
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6
            ),
            state.params,
            ref,
        )
    def test_dp_cp_tp_ulysses_matches_single_device(self):
        """Ulysses all-to-all INSIDE the Megatron block (round-2 verdict
        item 9): same single-device-exact parity as the K/V ring — the
        block's LOCAL heads (4/model=2 → 2) re-shard over seq=2."""
        self.test_dp_cp_tp_matches_single_device(True, ulysses=True)

    @pytest.mark.parametrize("zero1", [False, True])
    def test_dp_cp_tp_matches_single_device(self, zero1, ulysses=False):
        """Ring attention INSIDE the Megatron block: TP x CP."""
        import mpit_tpu
        from mpit_tpu.data import shard_batch
        from mpit_tpu.opt import goo
        from mpit_tpu.parallel import (
            make_gpt2_dp_cp_tp_train_step,
            stack_gpt2_blocks,
        )

        cfg = GPT2Config.tiny(
            num_heads=4, max_seq_len=64, num_layers=2, dtype=jnp.float32
        )
        model = GPT2(cfg)
        full = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, 64), jnp.int32)
        )["params"]
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, 512, size=(4, 64)).astype(
                np.int32
            )
        )

        def ref_loss(p):
            losses = model.apply(
                {"params": p}, toks,
                targets=jnp.pad(toks[:, 1:], ((0, 0), (0, 1))),
            )
            return jnp.sum(losses[:, :-1]) / (
                toks.shape[0] * (toks.shape[1] - 1)
            )

        ref = stack_gpt2_blocks(
            self._ref_step(model, full, ref_loss, goo(0.05, 0.9)),
            cfg.num_layers, 2,
        )
        world = mpit_tpu.init(
            {"data": 2, "seq": 2, "model": 2}, set_default=False
        )
        stacked = stack_gpt2_blocks(full, cfg.num_layers, 2)
        init_fn, step_fn, _ = make_gpt2_dp_cp_tp_train_step(
            cfg, goo(0.05, 0.9), world, zero1=zero1, ulysses=ulysses
        )
        state, m = step_fn(
            init_fn(stacked),
            shard_batch(world, {"tokens": toks}, spec=P("data", "seq")),
        )
        assert np.isfinite(float(m["loss"]))
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6
            ),
            state.params,
            ref,
        )

    def test_zero1_state_is_sharded_per_group(self):
        """Flat goo-state vectors are genuinely sharded per placement
        group (the north-star under 3-D composition)."""
        import mpit_tpu
        from mpit_tpu.opt import goo_adam
        from mpit_tpu.parallel import (
            make_gpt2_dp_tp_pp_train_step,
            split_gpt2_params_3d,
        )

        cfg = GPT2Config.tiny(
            num_heads=4, max_seq_len=32, num_layers=4, tie_head=False
        )
        model = GPT2(cfg)
        full = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, 32), jnp.int32)
        )["params"]
        world = mpit_tpu.init(
            {"data": 2, "model": 2, "pipe": 2}, set_default=False
        )
        split = split_gpt2_params_3d(full, cfg.num_layers, 2, 2)
        init_fn, _, _ = make_gpt2_dp_tp_pp_train_step(
            cfg, goo_adam(1e-3), world, zero1=True
        )
        state = init_fn(split)
        vec = [
            l for l in jax.tree.leaves(state.opt_state)
            if getattr(l, "ndim", 0) == 1 and l.size > 1
        ]
        assert vec
        for l in vec:
            axes = [
                a for part in l.sharding.spec if part is not None
                for a in ((part,) if isinstance(part, str) else part)
            ]
            factor = int(np.prod([world.mesh.shape[a] for a in axes]))
            assert factor >= world.axis_size("data"), l.sharding.spec
            shard = next(iter(l.addressable_shards))
            assert shard.data.size * factor == l.size


class TestMoECapacity:
    """Capacity/overflow behavior at realistic load (round-2 verdict
    item 10): drop rates under skewed routing at cf=1.25, aux-loss
    response to imbalance, and dropped tokens riding the residual."""

    def test_balanced_routing_drops_nothing(self):
        from mpit_tpu.parallel import (
            dispatch_stats,
            moe_capacity,
            top_k_dispatch,
        )

        s, e, k = 256, 8, 2
        cap = moe_capacity(s, e, k, 1.25)  # ceil(2*256*1.25/8) = 80
        assert cap == 80
        # Perfectly balanced: token i prefers experts (i%e, (i+1)%e).
        probs = np.full((s, e), 1e-3, np.float32)
        probs[np.arange(s), np.arange(s) % e] = 0.6
        probs[np.arange(s), (np.arange(s) + 1) % e] = 0.3
        probs /= probs.sum(-1, keepdims=True)
        dispatch, _ = top_k_dispatch(jnp.asarray(probs), k, cap)
        stats = dispatch_stats(dispatch, k)
        assert float(stats["drop_rate"]) == 0.0
        # every expert gets exactly 2*256/8 = 64 <= 80 slots
        np.testing.assert_array_equal(
            np.asarray(stats["expert_load"]), np.full(e, 64.0)
        )

    def test_skewed_routing_drop_rate_is_exact(self):
        """Full skew (every token's top-2 = experts 0 and 1): each hot
        expert keeps exactly its capacity; the analytic drop rate at
        cf=1.25 is 1 − 2·C/(2·S) = 68.75 % — the measured number the
        aux loss exists to drive down."""
        from mpit_tpu.parallel import (
            dispatch_stats,
            moe_capacity,
            top_k_dispatch,
        )

        s, e, k = 256, 8, 2
        cap = moe_capacity(s, e, k, 1.25)
        probs = np.full((s, e), 1e-4, np.float32)
        probs[:, 0] = 0.7
        probs[:, 1] = 0.29
        probs /= probs.sum(-1, keepdims=True)
        dispatch, combine = top_k_dispatch(jnp.asarray(probs), k, cap)
        stats = dispatch_stats(dispatch, k)
        load = np.asarray(stats["expert_load"])
        assert load[0] == cap and load[1] == cap and load[2:].sum() == 0
        expected_drop = 1.0 - 2 * cap / (k * s)
        np.testing.assert_allclose(
            float(stats["drop_rate"]), expected_drop
        )  # 0.6875 at these shapes
        # Fully dropped tokens (both rounds overflowed) have zero combine
        # weight everywhere -> the MoE output row is 0 and the token
        # rides the residual untouched.
        per_token = np.asarray(jnp.sum(dispatch, axis=(1, 2)))
        fully_dropped = per_token == 0
        assert fully_dropped.sum() == s - cap  # tokens past both queues
        cw = np.asarray(jnp.sum(combine, axis=(1, 2)))
        assert (cw[fully_dropped] == 0).all()

    def test_dropped_tokens_pass_through_as_zero(self):
        from mpit_tpu.parallel import expert_parallel_moe

        rng = np.random.RandomState(0)
        d, e, f, s = 16, 4, 32, 64
        params = {
            "router": np.zeros((d, e), np.float32),
            "w_in": rng.randn(e, d, f).astype(np.float32) * 0.1,
            "b_in": np.zeros((e, f), np.float32),
            "w_out": rng.randn(e, f, d).astype(np.float32) * 0.1,
            "b_out": np.zeros((e, d), np.float32),
        }
        # Router biased entirely to expert 0 via the input direction.
        params["router"][:, 0] = 1.0
        x = jnp.asarray(np.abs(rng.randn(s, d)).astype(np.float32))
        out, aux = expert_parallel_moe(
            x, jax.tree.map(jnp.asarray, params), k=1, capacity_factor=0.25
        )
        # capacity = ceil(1*64*0.25/4) = 4: only 4 tokens served.
        served = np.asarray(jnp.any(out != 0, axis=-1))
        assert served.sum() == 4
        assert (np.asarray(out)[~served] == 0).all()

    def test_aux_loss_rises_under_imbalance(self):
        """Balanced routing → aux ≈ 1 (its minimum); full skew → aux ≈ E
        · f0 · p0 ≈ E·1·p0 >> 1. The documented contract: minimizing aux
        pushes the router back toward balance."""
        from mpit_tpu.parallel import expert_parallel_moe

        rng = np.random.RandomState(1)
        d, e, f, s = 16, 8, 32, 256
        base = {
            "w_in": jnp.asarray(rng.randn(e, d, f), jnp.float32) * 0.1,
            "b_in": jnp.zeros((e, f)),
            "w_out": jnp.asarray(rng.randn(e, f, d), jnp.float32) * 0.1,
            "b_out": jnp.zeros((e, d)),
        }
        # Positive inputs so a one-column router reliably drives every
        # token's top-1 to expert 0 (logit_0 = 5·Σ|x|).
        x = jnp.asarray(np.abs(rng.randn(s, d)).astype(np.float32))
        _, aux_balanced = expert_parallel_moe(
            x, {**base, "router": jnp.zeros((d, e))}, k=2
        )
        skew = jnp.zeros((d, e)).at[:, 0].set(5.0)
        _, aux_skew = expert_parallel_moe(x, {**base, "router": skew}, k=2)
        assert float(aux_balanced) == pytest.approx(1.0, abs=0.1)
        assert float(aux_skew) > 3.0


class TestExpertParallelTier:
    """Round-2 item 6: the EP training tier (parallel.ep) — the round-1
    MoE dispatch shelf turned into a usable strategy."""

    def _setup(self, capacity_factor=4.0):
        import mpit_tpu
        from mpit_tpu.models.gpt2_moe import GPT2MoE, MoESettings

        cfg = GPT2Config.tiny(
            num_heads=2, max_seq_len=32, num_layers=2, dtype=jnp.float32
        )
        moe = MoESettings(
            num_experts=8, k=2, capacity_factor=capacity_factor, every=2
        )
        model = GPT2MoE(cfg, moe)
        full = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, 32), jnp.int32)
        )["params"]
        world = mpit_tpu.init({"data": 2, "expert": 4}, set_default=False)
        return cfg, moe, model, full, world

    @pytest.mark.parametrize("zero1", [False, True])
    def test_dense_parity_in_ample_capacity(self, zero1):
        """With ample capacity (no drops) and aux_weight=0, one EP step
        equals the dense single-device step exactly."""
        import optax
        from mpit_tpu.data import shard_batch
        from mpit_tpu.opt import goo
        from mpit_tpu.parallel import make_gpt2_moe_train_step

        cfg, moe, model, full, world = self._setup()
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, 512, size=(8, 33)).astype(
                np.int32
            )
        )

        tx = goo(0.05, 0.9)

        def ref_loss(p):
            losses, _ = model.apply(
                {"params": p}, toks[:, :-1], targets=toks[:, 1:]
            )
            return jnp.mean(losses)

        _, g = jax.value_and_grad(ref_loss)(full)
        up, _ = tx.update(g, tx.init(full), full)
        ref = optax.apply_updates(full, up)

        init_fn, step_fn, _ = make_gpt2_moe_train_step(
            cfg, moe, goo(0.05, 0.9), world, aux_weight=0.0, zero1=zero1
        )
        state, m = step_fn(
            init_fn(full),
            shard_batch(world, {"tokens": toks}, spec=P(("data", "expert"))),
        )
        np.testing.assert_allclose(
            float(m["loss"]), float(ref_loss(full)), rtol=2e-5
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6
            ),
            state.params,
            ref,
        )

    def test_loss_decreases_with_aux(self):
        from mpit_tpu.data import SyntheticLM, shard_batch
        from mpit_tpu.opt import goo_adam
        from mpit_tpu.parallel import make_gpt2_moe_train_step

        cfg, moe, model, full, world = self._setup(capacity_factor=1.25)
        init_fn, step_fn, _ = make_gpt2_moe_train_step(
            cfg, moe, goo_adam(3e-3), world, aux_weight=0.01, zero1=True
        )
        state = init_fn(full)
        stream = SyntheticLM(vocab_size=cfg.vocab_size, seed=0).batches(8, 32)
        losses, auxes = [], []
        for _ in range(10):
            batch = shard_batch(
                world, next(stream), spec=P(("data", "expert"))
            )
            state, m = step_fn(state, batch)
            losses.append(float(m["loss"]))
            auxes.append(float(m["aux"]))
        assert losses[-1] < losses[0], losses
        assert all(np.isfinite(auxes)), auxes
    def test_composes_with_checkpointing(self, tmp_path):
        """Save mid-run, restore into a fresh state, trajectories match —
        the tier's state_specs drive the sharded orbax restore."""
        from mpit_tpu.data import SyntheticLM, shard_batch
        from mpit_tpu.opt import goo_adam
        from mpit_tpu.parallel import make_gpt2_moe_train_step
        from mpit_tpu.train import CheckpointManager

        cfg, moe, model, full, world = self._setup()
        init_fn, step_fn, specs_fn = make_gpt2_moe_train_step(
            cfg, moe, goo_adam(1e-3), world, zero1=True
        )
        state = init_fn(full)
        stream = SyntheticLM(vocab_size=cfg.vocab_size, seed=0).batches(8, 32)
        batches = [
            shard_batch(world, next(stream), spec=P(("data", "expert")))
            for _ in range(4)
        ]
        state, _ = step_fn(state, batches[0])
        state, _ = step_fn(state, batches[1])

        ckpt = CheckpointManager(tmp_path / "ck", world, async_save=False)
        ckpt.save(2, state)

        cont, m_direct = step_fn(state, batches[2])

        restored = ckpt.restore(init_fn(full), specs_fn(full))
        assert int(restored.step) == 2
        resumed, m_resumed = step_fn(restored, batches[2])
        np.testing.assert_allclose(
            float(m_direct["loss"]), float(m_resumed["loss"]), rtol=1e-6
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
            ),
            cont.params,
            resumed.params,
        )

    def test_app_ep_tier_trains(self):
        from mpit_tpu.asyncsgd import gpt2 as app

        out = app.main(
            ["--mesh", "data=2,expert=4", "--steps", "10", "--batch-size",
             "8", "--seq-len", "32", "--vocab-size", "128", "--num-layers",
             "2", "--num-heads", "2", "--d-model", "32", "--moe-experts",
             "8", "--lr", "0.003", "--log-every", "5"]
        )
        assert out["tier"] == "ep-top2-e8"
        assert out["final_loss"] < out["uniform_loss"]


@pytest.mark.slow
class TestMoELoadBalanceTraining:
    """ISSUE 3 satellite (round-5 verdict next-round #7): the load-
    balance aux must actually WORK under training — the per-layer drop
    rate, 36–64% at random init with a tight capacity factor, has to
    fall materially once the router trains. ~50 EP-tier steps on the
    fake mesh, drop rates sampled via probe forwards and recorded
    through obs.gauge (the same instrumentation bench.py's trajectory
    probe uses)."""

    def test_drop_rate_falls_under_training(self):
        import mpit_tpu
        from mpit_tpu import obs
        from mpit_tpu.data import shard_batch
        from mpit_tpu.models.gpt2_moe import GPT2MoE, MoESettings
        from mpit_tpu.opt import goo_adam
        from mpit_tpu.parallel import make_gpt2_moe_train_step

        cfg = GPT2Config.tiny(
            num_heads=2, max_seq_len=32, num_layers=2, dtype=jnp.float32
        )
        moe = MoESettings(
            num_experts=8, k=2, capacity_factor=1.25, every=2
        )
        model = GPT2MoE(cfg, moe)
        full = jax.jit(model.init)(
            jax.random.key(0), jnp.zeros((1, 32), jnp.int32)
        )["params"]
        world = mpit_tpu.init({"data": 2, "expert": 4}, set_default=False)
        # aux_weight 1.0 / lr 3e-4, measured on this exact config: the
        # balance signal has to dominate what random-token xent can
        # teach, and adam at 3e-3 overshoots the tiny router into
        # oscillation (drop rate RISES).
        init_fn, step_fn, _ = make_gpt2_moe_train_step(
            cfg, moe, goo_adam(3e-4), world, aux_weight=1.0
        )
        state = init_fn(full)

        probe_fn = jax.jit(
            lambda p, t: model.apply(
                {"params": p}, t, mutable=["intermediates"]
            )
        )
        rng = np.random.RandomState(1)
        probe = jnp.asarray(
            np.random.RandomState(0).randint(0, 512, size=(16, 32))
            .astype(np.int32)
        )

        def drops(params):
            _, inter = probe_fn(params, probe)
            return [
                float(v)
                for k, v in jax.tree_util.tree_flatten_with_path(
                    inter["intermediates"]
                )[0]
                if "drop_rate" in jax.tree_util.keystr(k) and v.ndim == 0
            ]

        rec = obs.enable(obs.Recorder())
        try:
            initial = drops(state.params)
            # Random-init routing against cf=1.25 drops a sizable
            # fraction of (token, round) slots (~23% at this tiny shape;
            # the bench-size model sits at the verdict's 36–64%).
            assert 0.15 < float(np.mean(initial)) < 0.75, initial
            steps = 50
            for s in range(1, steps + 1):
                toks = rng.randint(0, 512, size=(16, 33)).astype(np.int32)
                state, _m = step_fn(
                    state,
                    shard_batch(
                        world, {"tokens": toks}, spec=P(("data", "expert"))
                    ),
                )
                if s % 10 == 0:
                    for li, d in enumerate(drops(state.params)):
                        obs.gauge("moe_drop_rate", d, layer=li, step=s)
            final = drops(state.params)
        finally:
            obs.disable()
        # Material improvement: the mean drop rate fell by at least a
        # third from random init (it typically approaches ~0 as the
        # router balances; a third is the regression floor, not the
        # expectation).
        assert np.mean(final) < 0.67 * np.mean(initial), (initial, final)
        # The trajectory rode obs.gauge: one series per (layer, step).
        gauges = rec.snapshot()["gauges"]
        series = [k for (name, k) in gauges if name == "moe_drop_rate"]
        assert len(series) == (steps // 10) * len(initial)


@pytest.mark.slow
class TestTierCheckpointing:
    """--ckpt-dir on the hand-driven tiers (round 2): restore against the
    tier's own state_specs + deterministic stream fast-forward."""

    @pytest.mark.parametrize(
        "mesh", ["data=2,pipe=4", "data=4,model=2", "data=2,expert=4",
                 "data=2,seq=4"]
    )
    def test_tier_resume_matches_uninterrupted(self, tmp_path, mesh):
        from mpit_tpu.asyncsgd import gpt2 as app

        args = ["--mesh", mesh, "--batch-size", "8",
                "--seq-len", "32", "--vocab-size", "128", "--num-layers",
                "4", "--num-heads", "2", "--d-model", "32", "--log-every",
                "3"]
        ck = str(tmp_path / "ck")
        first = app.main(args + ["--steps", "6", "--ckpt-dir", ck,
                                 "--ckpt-every", "3"])
        resumed = app.main(args + ["--steps", "12", "--ckpt-dir", ck])
        oracle = app.main(args + ["--steps", "12"])
        assert first["losses"] == oracle["losses"][: len(first["losses"])]
        # resumed run logs only steps 7..12; they must equal the oracle's.
        np.testing.assert_allclose(
            resumed["losses"], oracle["losses"][-len(resumed["losses"]):],
            rtol=1e-6,
        )
