"""ISSUE 13 acceptance: speculative decoding on the serving engine.

The pinned invariants:

- **Greedy parity** — per-request speculative greedy output bit-matches
  the NON-speculative engine (itself oracle-pinned against the no-cache
  forward in ``tests/test_serve.py``) over a pool for every slot and a
  small one, with chunked prefill and over the TP engine, with a random draft (correctness must not depend on
  what the draft proposes);
- **Rollback edges (paged)** — reject across a page boundary (the fill
  watermark retreats over a page), reject into a COW-shared page, and
  speculation across a preempt→resume cycle, each bit-matched against
  the un-speculated run;
- **Exact sampling** — the blocked verifier bit-matches the full-logits
  oracle (one-vocab-block configs) and the emitted-token marginal of
  the accept/residual chain equals the target's modified distribution;
- **Discipline** — fixed lifetime compile counts, spec_draft/
  spec_verify spans with the ``attention=`` label idiom,
  accepted-tokens telemetry, and precise submit/construction errors.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpit_tpu
from mpit_tpu import obs
from mpit_tpu.models import GPT2, GPT2Config
from mpit_tpu.serve import Engine, Request, Server, draft_from_target

CFG = GPT2Config.tiny(
    vocab_size=64, max_seq_len=64, num_layers=2, num_heads=2, d_model=32,
    dtype=jnp.float32,
)
DCFG = GPT2Config.tiny(
    vocab_size=64, max_seq_len=64, num_layers=1, num_heads=2, d_model=32,
    dtype=jnp.float32,
)

PROMPTS = [[5, 9, 3], [7], [1, 2, 3, 4, 5], [9, 9], [3, 1], [60, 2, 2, 1]]
MAX_NEW = [6, 4, 8, 3, 5, 7]


@pytest.fixture(scope="module")
def params():
    return jax.jit(GPT2(CFG).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


@pytest.fixture(scope="module")
def dparams():
    return jax.jit(GPT2(DCFG).init)(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def _spec_kw(dparams, k=2):
    return dict(spec_k=k, draft_params=dparams, draft_cfg=DCFG)


def _run_stream(engine, reqs=None):
    server = Server(engine)
    reqs = reqs or [
        Request(rid=i, prompt=p, max_new_tokens=n)
        for i, (p, n) in enumerate(zip(PROMPTS, MAX_NEW))
    ]
    for r in reqs:
        server.submit(r)
    server.run()
    return {c.rid: c.tokens for c in server.completed}, server


@pytest.fixture(scope="module")
def baseline(params):
    """The non-speculative reference outputs (oracle-pinned in
    tests/test_serve.py) every parity test below compares against."""
    out, _ = _run_stream(
        Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=8)
    )
    return out


class TestSpecGreedyParity:
    @pytest.mark.parametrize(
        "pool",
        [
            {},
            # tier-1 wall guard (round 18): the second soak rides slow
            pytest.param(dict(kv_pages=16), marks=pytest.mark.slow),
        ],
        ids=["pool-for-every-slot", "small-pool"],
    )
    def test_staggered_bitmatch(self, params, dparams, baseline, pool):
        """THE tentpole pin: 6 heterogeneous greedy requests through 2
        slots with draft-then-verify — admits, retirements and slot
        reuse interleaved with speculation, identical leading prompts
        mapping shared pages (draft pool included) — equal the plain
        engine's outputs per request, with a RANDOM draft (parity cannot
        depend on the draft's quality, only throughput can); with a
        page for every position and with a pool that recycles pages."""
        out, server = _run_stream(
            Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=8,
                   **pool, **_spec_kw(dparams))
        )
        assert out == baseline
        st = server.stats()
        assert st["spec_k"] == 2
        assert st["accepted_tokens_per_tick"] >= 1.0

    def test_reference_engine_spec_bitmatch(self, params, dparams, baseline):
        out, _ = _run_stream(
            Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=8,
                   decode_attention="reference", **_spec_kw(dparams))
        )
        assert out == baseline

    # Wall-guard demotion (ISSUE 17): heavy parity/e2e soak -> the
    # slow tier; this container replays tier-1 ~13% slower than the
    # PR-16 recording and the guard fired (the PR-14 remedy).
    @pytest.mark.slow
    def test_interpret_kernel_spec_bitmatch(self, params, dparams):
        """One-kernel verification for real: the T=k+1 verify through
        the Pallas flash-decode kernel (interpreter), bit-matching the
        interpreted NON-speculative engine."""
        reqs = lambda: [
            Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(PROMPTS[:3], MAX_NEW[:3]))
        ]
        ref, _ = _run_stream(
            Engine(CFG, params, slots=2, max_len=32, prefill_len=8,
                   decode_attention="interpret"),
            reqs(),
        )
        out, _ = _run_stream(
            Engine(CFG, params, slots=2, max_len=32, prefill_len=8,
                   decode_attention="interpret", **_spec_kw(dparams)),
            reqs(),
        )
        assert out == ref

    def test_paged_chunked_spec_bitmatch(self, params, dparams, baseline):
        out, _ = _run_stream(
            Engine(CFG, params, slots=2, max_len=40, prefill_len=8,
                   kv_pages=16, kv_page_size=8, prefill_chunk=4,
                   **_spec_kw(dparams))
        )
        assert out == baseline

    # Wall-guard demotion (ISSUE 17): heavy parity/e2e soak -> the
    # slow tier; this container replays tier-1 ~13% slower than the
    # PR-16 recording and the guard fired (the PR-14 remedy).
    @pytest.mark.slow
    def test_perfect_draft_sustains_full_acceptance(self, params):
        """A draft that IS the target must accept every drafted token
        on EVERY tick — the draft-cache-integrity pin. Bit-match alone
        cannot catch a corrupted draft context (verify corrects the
        output regardless); sustained acceptance can: a missing K/V row
        after a fully-accepted tick poisons the draft's window and
        collapses acceptance from 1.0 (caught here, over both pools)."""
        for kw in ({"kv_page_size": 8}, {"kv_pages": 16, "kv_page_size": 8}):
            eng = Engine(CFG, params, slots=2, max_len=40, prefill_len=8,
                         spec_k=3, draft_params=params, draft_cfg=CFG,
                         **kw)
            _, server = _run_stream(eng, [
                Request(rid=i, prompt=p, max_new_tokens=10)
                for i, p in enumerate(PROMPTS[:4])
            ])
            st = server.stats()
            assert st["draft_acceptance_rate"] == 1.0, kw

    @pytest.mark.slow  # tier-1 wall guard (round 18): heavy soak
    def test_spec_k3_bitmatch(self, params, dparams, baseline):
        """Parity is k-independent (a different k only changes how much
        is drafted per tick, never what is emitted)."""
        out, _ = _run_stream(
            Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=8,
                   **_spec_kw(dparams, k=3))
        )
        assert out == baseline


@pytest.mark.slow
class TestSpecTPParity:
    """TP engines carry the same pin — heavier (mesh compiles), so the
    e2e rides the slow tier; the single-device pins above stay tier-1."""

    def test_tp_spec_bitmatch(self, params, dparams, baseline, world_2d):
        out, server = _run_stream(
            Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=8,
                   world=world_2d, tp_axis="model", **_spec_kw(dparams))
        )
        assert out == baseline
        assert server.stats()["engine_compiles"] == 3

    def test_tp_paged_spec_bitmatch(
        self, params, dparams, baseline, world_2d
    ):
        out, _ = _run_stream(
            Engine(CFG, params, slots=2, max_len=40, prefill_len=8,
                   world=world_2d, tp_axis="model", kv_pages=16,
                   kv_page_size=8, **_spec_kw(dparams))
        )
        assert out == baseline


class TestPagedRollbackEdges:
    def _paged(self, params, dparams, **kw):
        kw.setdefault("kv_pages", 24)
        kw.setdefault("kv_page_size", 4)
        return Engine(CFG, params, slots=2, max_len=40, prefill_len=24,
                      **_spec_kw(dparams, k=3), **kw)

    # Wall-guard demotion (ISSUE 17): heavy parity/e2e soak -> the
    # slow tier; this container replays tier-1 ~13% slower than the
    # PR-16 recording and the guard fired (the PR-14 remedy).
    @pytest.mark.slow
    def test_reject_retreats_across_page_boundary(self, params, dparams):
        """page_size=4 < k+1=4 writes: every tick's verify span crosses
        a page boundary, so any reject retreats the fill watermark over
        one — outputs still bit-match the un-speculated run."""
        reqs = lambda: [
            Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(PROMPTS, MAX_NEW))
        ]
        ref, _ = _run_stream(
            Engine(CFG, params, slots=2, max_len=40, prefill_len=24,
                   kv_pages=24, kv_page_size=4),
            reqs(),
        )
        out, server = _run_stream(self._paged(params, dparams), reqs())
        assert out == ref
        # The edge actually exercised: rejects happened (acceptance
        # below 100% with a random draft) and ticks wrote across pages.
        assert server._spec_accepted < server._spec_drafted

    # Wall-guard demotion (ISSUE 17): heavy parity/e2e soak -> the
    # slow tier; this container replays tier-1 ~13% slower than the
    # PR-16 recording and the guard fired (the PR-14 remedy).
    @pytest.mark.slow
    def test_reject_on_cow_shared_page(self, params, dparams):
        """Full-prompt prefix reuse: the sharer's first speculative
        writes land in the COW'd partial page; rejects roll the
        watermark back inside it. Output bit-matches, and the copy
        actually ran."""
        rng = np.random.RandomState(3)
        prefix = rng.randint(0, CFG.vocab_size, size=6).tolist()
        reqs = lambda: [
            Request(rid="a", prompt=prefix, max_new_tokens=8),
            Request(rid="b", prompt=prefix, max_new_tokens=8),
        ]
        ref_eng = Engine(CFG, params, slots=2, max_len=40,
                         prefill_len=24, kv_pages=24, kv_page_size=4)
        server = Server(ref_eng)
        server.submit(reqs()[0])
        server.run(max_ticks=2)  # register "a"'s prefix first
        server.submit(reqs()[1])
        server.run()
        ref = {c.rid: c.tokens for c in server.completed}

        eng = self._paged(params, dparams)
        server = Server(eng)
        server.submit(reqs()[0])
        server.run(max_ticks=2)
        server.submit(reqs()[1])
        server.run()
        out = {c.rid: c.tokens for c in server.completed}
        assert out == ref
        assert eng.allocator.cow_copies >= 1

    @pytest.mark.slow  # tier-1 wall guard (round 18): parity soak
    def test_spec_across_preempt_resume(self, params, dparams):
        """Park a mid-generation speculative request (pages freed —
        draft pool rides the same tables), resume through chunked
        prefill: final greedy output equals the un-preempted
        un-speculated run."""
        from mpit_tpu.serve import SchedulingPolicy

        rng = np.random.RandomState(7)
        prompt = rng.randint(0, CFG.vocab_size, size=10).tolist()
        eng = self._paged(params, dparams, prefill_chunk=8)
        server = Server(eng, policy=SchedulingPolicy())
        server.submit(Request(rid="v", prompt=prompt, max_new_tokens=8,
                              priority=1))
        server.run(max_ticks=4)
        assert server.live
        slot = next(iter(server.live))
        assert 0 < len(server.live[slot].tokens) < 8
        server._preempt(slot)
        done = server.run()

        ref_eng = Engine(CFG, params, slots=2, max_len=40,
                         prefill_len=24, kv_pages=24, kv_page_size=4)
        ref_server = Server(ref_eng)
        ref_server.submit(Request(rid="v", prompt=prompt,
                                  max_new_tokens=8))
        ref = ref_server.run()
        assert done[0].tokens == ref[0].tokens


class TestExactSampling:
    @pytest.mark.parametrize(
        "v,block_size",
        [(64, 8192), (256, 128), (200, 64)],
        ids=["under_one_block", "divides", "ragged_tail"],
    )
    def test_blocked_verify_bitmatches_full_logits_oracle(
        self, v, block_size
    ):
        """lm_head_verify (blocked, two-pass) vs verify_reference (full
        logits) across greedy / temperature / top-k rows, at the three
        shapes the blocked head tells apart: a vocabulary under one
        block (the tail alone: bitwise, the shared noise contract), a
        block that divides the vocabulary, and full blocks with a
        ragged tail. Over several blocks the tokens are the oracle's
        bit for bit; ``p_x`` is the oracle's to rounding, because the
        blocked logsumexp is accumulated a block at a time."""
        from mpit_tpu.ops.lm_head import lm_head_verify
        from mpit_tpu.serve.spec import verify_reference

        n, d = 6, 16
        kr = jax.random.key(42)
        h = jax.random.normal(jax.random.fold_in(kr, 0), (n, d), jnp.float32)
        head = jax.random.normal(
            jax.random.fold_in(kr, 1), (v, d), jnp.float32
        )
        q = jax.nn.softmax(
            jax.random.normal(jax.random.fold_in(kr, 2), (n, v)), axis=-1
        )
        q = q.at[-1].set(0.0)  # a bonus row: residual = plain sample
        drafted = jax.random.randint(
            jax.random.fold_in(kr, 3), (n,), 0, v, jnp.int32
        )
        temp = jnp.asarray([0.0, 0.0, 0.7, 0.7, 1.3, 0.9], jnp.float32)
        topk = jnp.asarray([0, 4, 0, 8, 3, 0], jnp.int32)
        vkey = jax.random.fold_in(kr, 4)
        g_b, p_b, r_b = lm_head_verify(
            h, head, drafted, q, vkey, temp, topk, k_cap=16,
            block_size=block_size,
        )
        # The oracle consumes logits computed exactly as the blocked
        # path computes them per block (f32 dot).
        logits = jnp.dot(h, head.T, preferred_element_type=jnp.float32)
        g_o, p_o, r_o = verify_reference(
            logits, drafted, q, vkey, temp, topk, k_cap=16,
            block_size=block_size,
        )
        np.testing.assert_array_equal(np.asarray(g_b), np.asarray(g_o))
        np.testing.assert_array_equal(np.asarray(r_b), np.asarray(r_o))
        if v <= block_size:
            np.testing.assert_array_equal(np.asarray(p_b), np.asarray(p_o))
        else:
            np.testing.assert_allclose(
                np.asarray(p_b), np.asarray(p_o), rtol=1e-5
            )

    def test_proposal_q_is_exactly_the_engine_sampling_distribution(self):
        """The rejection-sampling exactness precondition, pinned
        structurally AND behaviorally (review finding: the proposal's
        top-k/temperature math used to be a copy of sample_tokens'):
        both now read ONE ``modified_logits`` implementation, and a
        categorical draw from ``draft_distribution``'s scaled logits
        reproduces ``sample_tokens`` bit-for-bit on sampled rows."""
        from mpit_tpu.serve.engine import sample_tokens
        from mpit_tpu.serve.spec import draft_distribution, modified_logits

        kr = jax.random.key(5)
        logits = jax.random.normal(kr, (6, 64), jnp.float32) * 3.0
        temp = jnp.asarray([0.3, 0.7, 1.0, 1.3, 2.0, 0.9], jnp.float32)
        topk = jnp.asarray([0, 4, 1, 8, 3, 63], jnp.int32)
        probs, scaled = draft_distribution(logits, temp, topk)
        np.testing.assert_array_equal(
            np.asarray(scaled),
            np.asarray(modified_logits(logits, temp, topk)),
        )
        key = jax.random.fold_in(kr, 1)
        drawn = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(drawn),
            np.asarray(sample_tokens(logits, key, temp, topk)),
        )
        # And q really is the softmax of what the engine draws from.
        np.testing.assert_allclose(
            np.asarray(probs), np.asarray(jax.nn.softmax(scaled, axis=-1))
        )

    def test_verify_never_materializes_full_logits(self):
        """The speculative verifier's jaxpr pin, on the shared
        ``mpit_tpu.analysis.jaxpr_check`` API (ISSUE 14 satellite —
        the serve/decode pins' one audited implementation): with
        ``block_size < vocab`` no full-width ``[N, vocab]`` logits
        matmul runs (qprobs legitimately ENTERS at [N, vocab], so the
        pin is on dot_general outputs), and the one-block trace the
        bitwise oracle test uses DOES produce it — non-vacuous."""
        from mpit_tpu.analysis.jaxpr_check import (
            assert_no_intermediate,
            find_avals,
        )
        from mpit_tpu.ops.lm_head import lm_head_verify

        n, d, v = 6, 16, 64
        h = jnp.zeros((n, d), jnp.float32)
        head = jnp.zeros((v, d), jnp.float32)
        q = jnp.zeros((n, v), jnp.float32)
        drafted = jnp.zeros((n,), jnp.int32)
        temp = jnp.ones((n,), jnp.float32)
        topk = jnp.zeros((n,), jnp.int32)

        def trace(block):
            return jax.make_jaxpr(
                lambda h, w, q: lm_head_verify(
                    h, w, drafted, q, jax.random.key(0), temp, topk,
                    block_size=block, k_cap=8,
                )
            )(h, head, q)

        assert_no_intermediate(
            trace(16), (n, v), what="blocked lm_head_verify",
            prims={"dot_general"},
        )
        # Anti-vacuity: at one vocab block the full-width matmul runs.
        assert find_avals(trace(v), (n, v), prims={"dot_general"})

    def test_emitted_marginal_is_target_distribution(self):
        """The rejection-sampling exactness theorem, measured: drafted
        ~ q, accept u·q(x) < p(x), else residual — the emitted token's
        marginal equals the MODIFIED target distribution p for a draft
        q that genuinely disagrees with it."""
        from mpit_tpu.serve.spec import verify_reference

        v, trials = 16, 20000
        kr = jax.random.key(9)
        logits = jax.random.normal(
            jax.random.fold_in(kr, 0), (1, v), jnp.float32
        ) * 2.0
        qlogits = jax.random.normal(
            jax.random.fold_in(kr, 1), (1, v), jnp.float32
        ) * 2.0
        temp = jnp.asarray([0.8], jnp.float32)
        topk = jnp.asarray([0], jnp.int32)
        q = jax.nn.softmax(qlogits / temp, axis=-1)
        p = np.asarray(jax.nn.softmax(logits / temp, axis=-1))[0]

        def one(key):
            kd, kv, ku = jax.random.split(key, 3)
            x = jax.random.categorical(kd, qlogits / temp, axis=-1)
            _, p_x, repl = verify_reference(
                logits, x, q, kv, temp, topk, k_cap=v
            )
            u = jax.random.uniform(ku, (1,))
            q_x = jnp.take_along_axis(q, x[:, None], axis=1)[:, 0]
            return jnp.where(u * q_x < p_x, x, repl)[0]

        keys = jax.random.split(jax.random.key(123), trials)
        toks = np.asarray(jax.jit(jax.vmap(one))(keys))
        emp = np.bincount(toks, minlength=v) / trials
        assert 0.5 * np.abs(emp - p).sum() < 0.02  # total variation

    def test_greedy_rows_accept_iff_argmax(self):
        from mpit_tpu.serve.spec import accept_emit

        drafted = jnp.asarray([[4, 7], [4, 7]], jnp.int32)
        greedy = jnp.asarray([[4, 9, 1], [4, 7, 2]], jnp.int32)
        zeros = jnp.zeros((2, 2), jnp.float32)
        repl = greedy
        emit, n_emit, n_acc = accept_emit(
            drafted, greedy, zeros, zeros, zeros, repl,
            jnp.asarray([True, True]),
            jnp.asarray([8, 8], jnp.int32),
            jnp.asarray([-1, -1], jnp.int32),
        )
        assert n_acc.tolist() == [1, 2]
        assert n_emit.tolist() == [2, 3]
        assert emit[0, :2].tolist() == [4, 9]
        assert emit[1].tolist() == [4, 7, 2]

    def test_emit_clamps_at_eos_and_budget(self):
        from mpit_tpu.serve.spec import accept_emit

        drafted = jnp.asarray([[4, 7, 5], [4, 7, 5]], jnp.int32)
        greedy = jnp.concatenate([drafted, drafted[:, :1]], axis=1)
        zeros = jnp.zeros((3,), jnp.float32)
        emit, n_emit, n_acc = accept_emit(
            drafted, greedy, jnp.zeros((2, 3)), jnp.zeros((2, 3)),
            jnp.zeros((2, 3)), greedy,
            jnp.asarray([True, True]),
            jnp.asarray([8, 2], jnp.int32),   # slot 1: 2 tokens left
            jnp.asarray([7, -1], jnp.int32),  # slot 0: EOS id 7
        )
        del zeros
        assert n_acc.tolist() == [3, 3]
        # Slot 0 stops WITH its EOS (position 1); slot 1 at its budget.
        assert n_emit.tolist() == [2, 2]
        assert emit[0, :2].tolist() == [4, 7]

    def test_sampled_spec_e2e_bookkeeping(self, params, dparams):
        """Temperature/top-k speculation end to end: token counts,
        device-vs-host fill mirror, and retirement all stay coherent
        (no parity claim — sampling is stochastic by design)."""
        eng = Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=8,
                     **_spec_kw(dparams))
        server = Server(eng)
        server.submit(Request(rid=0, prompt=[5, 9, 3], max_new_tokens=6,
                              temperature=0.8))
        server.submit(Request(rid=1, prompt=[7, 2], max_new_tokens=5,
                              temperature=0.9, top_k=4))
        done = server.run()
        assert sorted(c.rid for c in done) == [0, 1]
        by = {c.rid: c.tokens for c in done}
        assert len(by[0]) == 6 and len(by[1]) == 5
        assert int(eng.lengths().max()) <= 40
        assert (eng.lengths() >= 0).all()


class TestSpecObsAndStats:
    def test_spans_series_and_counters(self, params, dparams):
        from mpit_tpu.obs.stream import StreamRegistry

        rec = obs.Recorder()
        registry = StreamRegistry()
        with obs.local_recorder(rec):
            eng = Engine(CFG, params, slots=2, max_len=40, kv_page_size=8,
                         prefill_len=8,
                         **_spec_kw(dparams))
            server = Server(eng, stream=registry)
            for i, (p, n) in enumerate(zip(PROMPTS[:3], MAX_NEW[:3])):
                server.submit(Request(rid=i, prompt=p, max_new_tokens=n))
            server.run()
            summ = rec.summary()
            drafted = rec.counter_total("spec_drafted_tokens")
            accepted = rec.counter_total("spec_accepted_tokens")
        assert "spec_draft" in summ["phases"]
        assert "spec_verify" in summ["phases"]
        assert "decode" in summ["phases"]  # the outer tick span nests them
        # The attention= label idiom rides the spec spans too — the
        # flight recorder attributes draft vs verify work by name AND
        # can still spot a kernel fallback on either.
        for phase in ("spec_draft", "spec_verify"):
            assert summ["phases"][phase]["labels"]["attention"] == [
                "reference"
            ]
        assert drafted > 0 and accepted >= 0
        ws = registry.window_stats()["histograms"]
        assert "accepted_tokens_per_tick" in ws
        assert "draft_acceptance_rate" in ws
        st = server.stats()
        for k in ("spec_k", "accepted_tokens_per_tick",
                  "draft_acceptance_rate", "spec_drafted_tokens",
                  "spec_accepted_tokens"):
            assert k in st

    def test_compile_pins(self, params, dparams):
        eng = Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=8,
                     **_spec_kw(dparams))
        _, server = _run_stream(eng)
        assert server.stats()["engine_compiles"] == 3
        assert eng.compile_watch.unexpected == 0
        peng = Engine(CFG, params, slots=2, max_len=40, prefill_len=8,
                      kv_pages=16, kv_page_size=8, **_spec_kw(dparams))
        _, pserver = _run_stream(peng)
        assert pserver.stats()["engine_compiles"] <= 4
        assert peng.compile_watch.unexpected == 0

    def test_roofline_registers_spec_steps(self, params, dparams):
        eng = Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=8,
                     **_spec_kw(dparams))
        costs = eng.register_roofline()
        assert set(costs) == {"prefill", "spec_draft", "spec_verify"}


class TestSpecValidation:
    def test_spec_k_requires_draft(self, params):
        with pytest.raises(ValueError, match="draft_params and draft_cfg"):
            Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, spec_k=2)

    def test_draft_without_spec_k(self, params, dparams):
        with pytest.raises(ValueError, match="without spec_k"):
            Engine(CFG, params, slots=2, max_len=40, kv_page_size=8,
                   draft_params=dparams, draft_cfg=DCFG)

    def test_draft_vocab_mismatch(self, params):
        bad_cfg = GPT2Config.tiny(
            vocab_size=32, max_seq_len=64, num_layers=1, num_heads=2,
            d_model=32, dtype=jnp.float32,
        )
        bad = jax.jit(GPT2(bad_cfg).init)(
            jax.random.key(2), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        with pytest.raises(ValueError, match="vocab"):
            Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, spec_k=2,
                   draft_params=bad, draft_cfg=bad_cfg)

    def test_draft_positions_must_cover_max_len(self, params, dparams):
        import dataclasses

        short = dataclasses.replace(DCFG, max_seq_len=16)
        with pytest.raises(ValueError, match="max_seq_len"):
            Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, spec_k=2,
                   draft_params=dparams, draft_cfg=short)

    @pytest.mark.parametrize(
        "pool", [{}, dict(kv_pages=16, kv_page_size=4)],
        ids=["pool-for-every-slot", "small-pages"],
    )
    def test_submit_needs_no_headroom(self, params, dparams, pool):
        """The verify writes k+1 rows at the fill; those past the slot's
        mapped pages are scatter-DROPPED — prompt + max_new == max_len
        stays admissible, runs to its budget and emits what the plain
        engine emits."""
        req = lambda: Request(rid=0, prompt=[1] * 8, max_new_tokens=8)
        eng = Engine(CFG, params, slots=2, max_len=16, prefill_len=8,
                     **pool, **_spec_kw(dparams, k=3))
        server = Server(eng)
        assert server.submit(req())
        (done,) = server.run()
        assert len(done.tokens) == 8
        plain = Server(Engine(CFG, params, slots=2, max_len=16,
                              prefill_len=8, **pool))
        plain.submit(req())
        assert done.tokens == plain.run()[0].tokens

    def test_decode_raises_on_spec_engine(self, params, dparams):
        eng = Engine(CFG, params, slots=2, max_len=40, kv_page_size=8, prefill_len=8,
                     **_spec_kw(dparams))
        with pytest.raises(ValueError, match="spec_draft"):
            eng.decode(np.zeros(2, bool), np.zeros(2), np.zeros(2, np.int32))

    def test_draft_from_target_truncation(self, params):
        dp, dc = draft_from_target(params, CFG, 1)
        assert dc.num_layers == 1
        assert "block_1" not in dp and "block_0" in dp
        assert dp["wte"] is params["wte"]
        with pytest.raises(ValueError, match="num_layers"):
            draft_from_target(params, CFG, 2)

    @pytest.mark.slow  # tier-1 wall guard (round 18): heavy soak
    def test_cli_draft_flag_validation(self):
        from mpit_tpu.serve.__main__ import main

        with pytest.raises(SystemExit, match="--spec-k"):
            main(["--draft-config", "tiny"])
        with pytest.raises(SystemExit, match="needs a draft"):
            main(["--spec-k", "2"])
        with pytest.raises(SystemExit, match="truncate"):
            main(["--spec-k", "2", "--draft-config", "truncate:x"])


class TestSpecCLI:
    @pytest.mark.slow
    def test_cli_spec_smoke(self):
        """End to end through ``python -m mpit_tpu.serve`` with the
        self-speculation draft: spec telemetry lands in the JSON."""
        from mpit_tpu.serve.__main__ import main

        out = main([
            "--requests", "4", "--slots", "2", "--max-len", "64",
            "--spec-k", "2", "--draft-config", "truncate:1",
        ])
        assert out["spec_k"] == 2
        assert out["accepted_tokens_per_tick"] >= 1.0
        assert out["engine_compiles"] == 3
        assert "spec_verify" in out["obs_summary"]
