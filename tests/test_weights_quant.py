"""ISSUE 17 acceptance: the quantized int8 weight store + blocked
fused-dequant matmuls.

The done-criteria:

- the shared rounding contract — ``quantize_tensor`` is byte-for-byte
  the ring collectives' ``quantize_chunk`` math per weight row (one
  repo-wide recipe), bf16 sources included, all-zero rows exact;
- **blocked is the serving grain**: the fused-dequant matmuls
  (dispatcher, lax fallback, transposed head form, interpret-mode
  Pallas kernel) agree with the whole-dequant reference on non-128
  tail shapes — and the interpret kernel is BITWISE the lax fallback;
- **quality is gated on a TRAINED checkpoint, not assumed**: int8
  logits sit within a bound of the f32-weight oracle AND differ from
  it (anti-vacuity), greedy agreement vs the f32 engine is 1.0, and
  speculative acceptance is neutral with int8 on BOTH draft and
  target;
- the full step surface bit-matches the whole-dequant reference
  oracle — whole prefill, chunked prefill, speculative, TP (slow) —
  at the unchanged lifetime compile pins;
- the default path stays byte-identical: an engine constructed without
  ``weights_dtype`` holds plain dense params and its spans carry no
  ``weights_dtype`` label;
- wire honesty: ``params_wire_bytes`` through the shared
  ``weight_wire_bytes`` sizing rule prices int8 payload + per-row f32
  scales, and the engine's modeled decode bytes shrink accordingly.

Tier-1 wall guard (the PR 16 ``test_trace`` discipline): ONE
module-scoped trained checkpoint + ONE shared f32/int8 engine pair;
heavy parity soaks are ``slow``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpit_tpu
from mpit_tpu import obs
from mpit_tpu.models import GPT2, GPT2Config
from mpit_tpu.ops.quantized_matmul import (
    QuantizedTensor,
    dequantize_tensor,
    quantize_tensor,
    quantized_matmul,
    quantized_matmul_lax,
    quantized_matmul_t,
    weight_wire_bytes,
)
from mpit_tpu.ops.ring_collectives import quantize_chunk
from mpit_tpu.serve import (
    Engine,
    Request,
    Server,
    draft_from_target,
    params_wire_bytes,
    quantize_gpt2_params,
)

CFG = GPT2Config.tiny(
    vocab_size=64, max_seq_len=64, num_layers=2, num_heads=2, d_model=32,
    dtype=jnp.float32,
)

# Prompts are prefixes of the memorized stream (the trained-checkpoint
# regime): greedy continuations are sharply peaked, so agreement gates
# measure quantization, not sampling noise.
_STREAM = np.random.RandomState(17).randint(0, CFG.vocab_size, 48).tolist()
PROMPTS = [_STREAM[:5], _STREAM[:3], _STREAM[:8], _STREAM[:6]]
MAX_NEW = [6, 4, 8, 3]


@pytest.fixture(scope="module")
def trained():
    """ONE trained checkpoint for the whole module: memorize the
    stream (120 tiny steps — a random init would make every agreement
    gate vacuous). Returns ``(params, final_loss)``."""
    import optax

    from mpit_tpu.opt.goo import goo_adam

    model = GPT2(CFG)
    batch = jnp.asarray([_STREAM], jnp.int32)
    params = jax.jit(model.init)(
        jax.random.key(3), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    opt = goo_adam(3e-3)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(
            lambda p: GPT2.fused_loss_fn(model, p, batch)
        )(params)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    loss = None
    for _ in range(120):
        params, state, loss = step(params, state)
    return params, float(loss)


@pytest.fixture(scope="module")
def engines(trained):
    """ONE shared f32/int8 engine pair (compiles paid once;
    tests ``reset()`` before use — cleared cache, compiled steps
    kept)."""
    params, _ = trained
    return {
        dt: Engine(
            CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=16,
            weights_dtype=dt,
        )
        for dt in ("f32", "int8")
    }


def _run(engine, reqs):
    server = Server(engine)
    for rid, (p, n) in enumerate(reqs):
        server.submit(Request(rid=rid, prompt=p, max_new_tokens=n))
    return {c.rid: c.tokens for c in server.run()}, server


_ORACLE_ENGINE = []
_ORACLE_MEMO: dict = {}


def _isolated_int8w(params, prompt, n):
    """The self-consistency oracle: the same request alone through the
    int8-weight REFERENCE engine (whole-dequant matmuls — the
    parity baseline every blocked path must match token-for-token).
    ONE engine, reset between requests, results memoized (the
    test_kv_quant wall discipline)."""
    key = (tuple(prompt), n)
    if key in _ORACLE_MEMO:
        return _ORACLE_MEMO[key]
    if not _ORACLE_ENGINE:
        _ORACLE_ENGINE.append(Engine(
            CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=16,
            weights_dtype="int8", decode_attention="reference",
        ))
    eng = _ORACLE_ENGINE[0]
    eng.reset()
    out, _ = _run(eng, [(prompt, n)])
    _ORACLE_MEMO[key] = out[0]
    return out[0]


class TestSharedRoundingContract:
    """quantize_tensor IS quantize_chunk's math, one scale per row."""

    def test_rows_match_chunk_oracle_non_128_tail(self):
        x = jnp.asarray(
            np.random.RandomState(0).randn(5, 37) * 2, jnp.float32
        )
        t = quantize_tensor(x)
        assert t.q.dtype == jnp.int8 and t.scale.shape == (5, 1)
        for r in range(5):
            qc, sc = quantize_chunk(x[r])
            np.testing.assert_array_equal(
                np.asarray(qc), np.asarray(t.q[r])
            )
            assert float(sc) == float(t.scale[r, 0])

    def test_bf16_source_matches_chunk_oracle(self):
        """A bf16 checkpoint quantizes through the same contract: each
        row agrees with the scalar oracle on the f32 upcast."""
        x = jnp.asarray(
            np.random.RandomState(1).randn(4, 37) * 2, jnp.bfloat16
        )
        t = quantize_tensor(x)
        assert t.scale.dtype == jnp.float32
        for r in range(4):
            qc, sc = quantize_chunk(jnp.asarray(x[r], jnp.float32))
            np.testing.assert_array_equal(
                np.asarray(qc), np.asarray(t.q[r])
            )
            assert float(sc) == float(t.scale[r, 0])

    def test_all_zero_rows_exact_through_matmul(self):
        t = quantize_tensor(jnp.zeros((6, 9)))
        assert (np.asarray(t.scale) == 1.0).all()
        assert (np.asarray(dequantize_tensor(t)) == 0.0).all()
        y = quantized_matmul_lax(jnp.ones((2, 6)), t, block_rows=4)
        assert (np.asarray(y) == 0.0).all()

    def test_pytree_and_indexing(self):
        t = quantize_tensor(
            jnp.asarray(np.random.RandomState(2).randn(8, 5))
        )
        leaves, treedef = jax.tree.flatten(t)
        assert len(leaves) == 2
        back = jax.tree.unflatten(treedef, leaves)
        assert isinstance(back, QuantizedTensor)
        assert t.shape == (8, 5) and t.ndim == 2
        sub = t[2:6]
        assert sub.q.shape == (4, 5) and sub.scale.shape == (4, 1)

    def test_weight_wire_bytes_rule(self):
        # int8 rows carry one f32 scale each; anything else is dense.
        assert weight_wire_bytes((70, 33), "int8") == 70 * 33 + 70 * 4
        assert weight_wire_bytes((70, 33), jnp.int8) == 70 * 33 + 70 * 4
        assert weight_wire_bytes((70, 33), jnp.float32) == 70 * 33 * 4
        r = weight_wire_bytes
        assert r((70, 33), "int8") / r((70, 33), jnp.float32) < 0.3


class TestBlockedMatmulParity:
    """The blocked forms agree with the whole-dequant reference on
    shapes with non-128 tails (the fallback grain serving runs
    off-TPU)."""

    def setup_method(self):
        rng = np.random.RandomState(3)
        self.w = quantize_tensor(jnp.asarray(rng.randn(70, 33),
                                             jnp.float32))
        self.x = jnp.asarray(rng.randn(3, 70), jnp.float32)

    def test_lax_blocked_matches_reference(self):
        ref = self.x @ dequantize_tensor(self.w)
        for block in (16, 64, None):
            y = quantized_matmul_lax(self.x, self.w, block_rows=block)
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(ref), atol=1e-5
            )

    def test_dispatcher_falls_back_to_lax_off_tpu(self):
        # d=70/f=33 are not 128-multiples — the dispatcher must take
        # the lax fallback and still match the reference.
        y = quantized_matmul(self.x, self.w)
        np.testing.assert_allclose(
            np.asarray(y),
            np.asarray(self.x @ dequantize_tensor(self.w)),
            atol=1e-5,
        )

    def test_transposed_head_form_bitwise(self):
        # The lm-head form (x @ W.T, blocked over vocab rows) is
        # BITWISE the whole-dequant product — blocking only splits the
        # independent output rows, never the contraction.
        w2 = quantize_tensor(jnp.asarray(
            np.random.RandomState(4).randn(33, 70), jnp.float32
        ))
        y = quantized_matmul_t(self.x, w2, block_rows=16)
        np.testing.assert_array_equal(
            np.asarray(y),
            np.asarray(self.x @ dequantize_tensor(w2).T),
        )

    def test_interpret_kernel_bitwise_matches_lax(self):
        """The Pallas kernel (interpret mode, 128-multiple shapes) is
        bit-for-bit the lax fallback — same per-tile dequant, same f32
        accumulation order."""
        rng = np.random.RandomState(5)
        w = quantize_tensor(jnp.asarray(rng.randn(256, 128), jnp.float32))
        x = jnp.asarray(rng.randn(2, 256), jnp.float32)
        yk = quantized_matmul(x, w, block_rows=128, interpret=True)
        yl = quantized_matmul_lax(x, w, block_rows=128)
        np.testing.assert_array_equal(np.asarray(yk), np.asarray(yl))


class TestQuantizedParamStore:
    def test_store_layout_and_idempotence(self, trained):
        params, _ = trained
        qp = quantize_gpt2_params(params)
        for mod in ("qkv", "proj", "fc", "out"):
            assert isinstance(qp["block_0"][mod]["kernel"],
                              QuantizedTensor), mod
            assert qp["block_0"][mod]["bias"].dtype == jnp.float32
        assert isinstance(qp["wte"], QuantizedTensor)
        # LayerNorms and wpe stay dense f32 (a rounding error of the
        # wire; the model sums them in f32 anyway).
        assert not isinstance(qp["block_0"]["ln1"]["scale"],
                              QuantizedTensor)
        assert not isinstance(qp["wpe"], QuantizedTensor)
        # Idempotent AND leaf-sharing: requantizing aliases the same
        # quantized leaves (draft trees alias the target's store).
        qp2 = quantize_gpt2_params(qp)
        assert qp2["wte"] is qp["wte"]
        assert (qp2["block_0"]["qkv"]["kernel"]
                is qp["block_0"]["qkv"]["kernel"])

    def test_params_wire_bytes_ratio(self, trained):
        params, _ = trained
        dense = params_wire_bytes(params)
        q8 = params_wire_bytes(quantize_gpt2_params(params))
        # Dense f32 pricing == the plain itemsize sum.
        want = sum(
            l.size * l.dtype.itemsize for l in jax.tree.leaves(params)
        )
        assert dense == pytest.approx(want)
        # The acceptance bar rides the bench record line at ≤ 0.60;
        # the store itself sits well under it even on this tiny model.
        assert q8 / dense <= 0.60


class TestQuantizedWeightServing:
    @pytest.mark.slow  # tier-1 wall guard (round 18): heavy soak
    def test_greedy_agreement_and_oracle_bitmatch(self, trained,
                                                  engines):
        """The ISSUE 17 quality gate, ONE int8 batch serving both
        pins (the wall discipline): on the trained checkpoint the
        blocked int8-weight engine's greedy outputs (a) equal the f32
        engine's token for token, and (b) bit-match the whole-dequant
        reference oracle per isolated request — at the pinned
        lifetime compile count (prefill + decode, and the page copy
        once one ran; quantized or not)."""
        params, _ = trained
        reqs = list(zip(PROMPTS, MAX_NEW))
        outs = {}
        for dt in ("f32", "int8"):
            engines[dt].reset()
            outs[dt], _ = _run(engines[dt], reqs)
        assert outs["int8"] == outs["f32"]
        for rid, (p, n) in enumerate(reqs):
            assert outs["int8"][rid] == _isolated_int8w(params, p, n), rid
        eng = engines["int8"]
        eng.copy_page(0, 0)
        assert eng.compile_watch.compiles == 3
        assert eng.compile_watch.unexpected == 0

    def test_logit_bound_and_antivacuity(self, trained):
        """Prefill logits through the int8 store sit within a bound of
        the f32-weight oracle — and are NOT identical (the lossy path
        executed). Same (f32) cache both sides: the delta is
        weight quantization and nothing else."""
        params, loss = trained
        assert loss < 0.5  # trained, not random — the gates are real
        model = GPT2(CFG)
        toks = jnp.asarray([_STREAM[:16]], jnp.int32)
        lf = model.apply({"params": params}, toks)[0]
        lq = model.apply(
            {"params": quantize_gpt2_params(params)}, toks
        )[0]
        d = np.abs(np.asarray(lf, np.float32) - np.asarray(lq, np.float32))
        assert d.max() > 0.0, "int8 logits identical to f32 — vacuous"
        assert d.max() < 0.25, f"logit error {d.max()} beyond bound"

    def test_default_engine_unchanged_without_weights_dtype(self,
                                                            trained):
        """weights_dtype unset: plain dense params (no QuantizedTensor
        anywhere), weights_dtype reported but NOT stamped on spans."""
        params, _ = trained
        eng = Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=8)
        assert not eng.weights_quantized
        assert not eng.weights_dtype_explicit
        assert eng.weights_dtype == "f32"
        assert not any(
            isinstance(l, QuantizedTensor)
            for l in jax.tree.leaves(
                eng.params,
                is_leaf=lambda x: isinstance(x, QuantizedTensor),
            )
        )
        rec = obs.Recorder()
        with obs.local_recorder(rec):
            _done, server = _run(eng, [(PROMPTS[0], 3)])
        labels = rec.summary()["phases"]["decode"].get("labels", {})
        assert "weights_dtype" not in labels
        assert server.stats()["weights_dtype"] == "f32"

    def test_explicit_weights_dtype_stamped_on_spans_and_stats(
            self, engines):
        eng = engines["int8"]
        eng.reset()
        rec = obs.Recorder()
        with obs.local_recorder(rec):
            _done, server = _run(eng, [(PROMPTS[0], 3)])
        for phase in ("prefill", "decode"):
            labels = rec.summary()["phases"][phase]["labels"]
            assert labels.get("weights_dtype") == ["int8"], (phase, labels)
        assert server.stats()["weights_dtype"] == "int8"

    def test_rejects_unknown_weights_dtype(self, trained):
        params, _ = trained
        with pytest.raises(ValueError, match="weights_dtype"):
            Engine(CFG, params, slots=1, max_len=40, kv_page_size=8, prefill_len=8,
                   weights_dtype="int4")

    def test_wire_honesty_param_bytes_and_decode_bytes(self, trained,
                                                       engines):
        """The engine prices its store through the shared sizing rule,
        and the modeled decode tick shrinks by exactly the param
        delta (the KV sweep is weight-dtype-independent)."""
        params, _ = trained
        assert engines["int8"]._param_bytes == pytest.approx(
            params_wire_bytes(quantize_gpt2_params(params))
        )
        assert engines["f32"]._param_bytes == pytest.approx(
            params_wire_bytes(params)
        )
        lens = np.asarray([10, 33])
        total = {
            dt: engines[dt].decode_achieved_hbm_bytes(lens)
            for dt in ("f32", "int8")
        }
        sweep = {
            dt: engines[dt].decode_achieved_hbm_bytes(
                lens, include_params=False
            )
            for dt in ("f32", "int8")
        }
        assert sweep["int8"] == pytest.approx(sweep["f32"])
        assert total["int8"] - sweep["int8"] == pytest.approx(
            engines["int8"]._param_bytes
        )
        assert total["int8"] < total["f32"]


class TestQuantizedWeightsPagedSpec:
    """Heavy parity soaks ride the slow tier (the ISSUE's wall-guard
    note); their tier-1 twins are the committed-artifact pins in
    ``test_bench_contract.py::TestQuantizedWeightsArtifact`` (real
    paged-capacity + spec-neutrality numbers from the bench run)."""

    @pytest.mark.slow
    def test_paged_chunked_int8_bitmatch(self, trained):
        """Paged + chunked-prefill with the int8 store bit-matches the
        reference oracle, at the paged compile pin (3: prefill +
        decode + copy_page, quantized or not)."""
        params, _ = trained
        eng = Engine(
            CFG, params, slots=2, max_len=40, prefill_len=16,
            kv_pages=24, kv_page_size=4, prefill_chunk=4,
            weights_dtype="int8",
        )
        reqs = list(zip(PROMPTS[:3], MAX_NEW[:3]))
        done, _ = _run(eng, reqs)
        for rid, (p, n) in enumerate(reqs):
            assert done[rid] == _isolated_int8w(params, p, n), rid
        eng.copy_page(0, 0)
        assert eng.compile_watch.compiles == 3
        assert eng.compile_watch.unexpected == 0

    @pytest.mark.slow
    def test_spec_acceptance_neutral_int8_both_sides(self, trained):
        """Speculative decoding with int8 weights on BOTH draft and
        target (the engine quantizes the draft store too): greedy
        output equals the plain int8 oracle's, and acceptance equals
        the f32 pair's (delta ≈ 0) — at the speculative compile pin
        (prefill + spec_draft + spec_verify, and the page copy once a
        shared page diverged)."""
        params, _ = trained
        dp, dcfg = draft_from_target(params, CFG, 1)
        reqs = list(zip(PROMPTS[:3], MAX_NEW[:3]))
        acc = {}
        for dt in ("f32", "int8"):
            eng = Engine(
                CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=16,
                spec_k=2, draft_params=dp, draft_cfg=dcfg,
                weights_dtype=dt,
            )
            done, server = _run(eng, reqs)
            acc[dt] = server.stats().get("draft_acceptance_rate")
            if dt == "int8":
                assert isinstance(eng.draft_params["wte"],
                                  QuantizedTensor)
                for rid, (p, n) in enumerate(reqs):
                    assert done[rid] == _isolated_int8w(params, p, n), rid
                eng.copy_page(0, 0)
                assert eng.compile_watch.compiles == 4
        assert acc["f32"] is not None and acc["int8"] is not None
        assert abs(acc["int8"] - acc["f32"]) <= 0.05

    @pytest.mark.slow
    def test_paged_spec_int8_weights_and_kv_bitmatch(self, trained):
        """The deepest stack: paged + speculative + int8 WEIGHTS + int8
        KV — both quantization axes at once still bit-match the
        combined oracle."""
        params, _ = trained
        dp, dcfg = draft_from_target(params, CFG, 1)
        reqs = list(zip(PROMPTS[:3], MAX_NEW[:3]))
        eng = Engine(
            CFG, params, slots=2, max_len=40, prefill_len=16,
            kv_pages=24, kv_page_size=8, spec_k=2,
            draft_params=dp, draft_cfg=dcfg,
            weights_dtype="int8", kv_dtype="int8",
        )
        done, _ = _run(eng, reqs)
        oracle = Engine(
            CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=16,
            weights_dtype="int8", kv_dtype="int8",
            decode_attention="reference",
        )
        for rid, (p, n) in enumerate(reqs):
            want, _ = _run(oracle, [(p, n)])
            oracle.reset()
            assert done[rid] == want[0], rid


@pytest.mark.slow
class TestQuantizedWeightsTensorParallel:
    def test_tp_int8_bitmatches_single_device_int8(self, trained):
        """data=4 × model=2 fake mesh: column kernels shard the int8
        payload on the feature axis with REPLICATED scales (rows are
        the replicated contraction dim); row kernels shard payload AND
        scales on rows. Greedy output equals the single-device int8
        engine's."""
        params, _ = trained
        world = mpit_tpu.init({"data": 4, "model": 2}, set_default=False)
        reqs = list(zip(PROMPTS[:3], MAX_NEW[:3]))
        ref, _ = _run(
            Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=16,
                   weights_dtype="int8"),
            reqs,
        )
        eng = Engine(
            CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=16,
            world=world, tp_axis="model", weights_dtype="int8",
        )
        blk = eng.params["block_0"]
        qkv_q = {s.data.shape
                 for s in blk["qkv"]["kernel"].q.addressable_shards}
        qkv_s = {s.data.shape
                 for s in blk["qkv"]["kernel"].scale.addressable_shards}
        d = CFG.d_model
        assert qkv_q == {(d, 3 * d // 2)}      # feature-split payload
        assert qkv_s == {(d, 1)}               # replicated scales
        out_q = {s.data.shape
                 for s in blk["out"]["kernel"].q.addressable_shards}
        out_s = {s.data.shape
                 for s in blk["out"]["kernel"].scale.addressable_shards}
        assert out_q == {(4 * d // 2, d)}      # row-split payload
        assert out_s == {(4 * d // 2, 1)}      # ...and row-split scales
        done, _ = _run(eng, reqs)
        assert done == ref


class TestQuantizedWeightsCLI:
    def test_cli_rejects_unknown_weights_dtype(self):
        from mpit_tpu.serve.__main__ import main

        with pytest.raises(SystemExit, match="expected f32 or int8"):
            main(["--weights-dtype", "int4"])

    def test_cli_rejects_int8_with_reference(self):
        from mpit_tpu.serve.__main__ import main

        with pytest.raises(SystemExit, match="parity oracle"):
            main(["--weights-dtype", "int8",
                  "--decode-attention", "reference"])

    @pytest.mark.slow
    def test_cli_int8_weights_smoke(self):
        from mpit_tpu.serve.__main__ import main

        out = main([
            "--weights-dtype", "int8",
            "--requests", "3", "--max-new-tokens", "3",
            "--slots", "2", "--max-len", "48", "--prefill-len", "8",
        ])
        assert out["weights_dtype"] == "int8"
        assert out["requests_completed"] == 3
        assert out["engine_compiles"] == 2
