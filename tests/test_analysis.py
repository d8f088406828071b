"""ISSUE 14 acceptance: the static contract checker.

- every rule demonstrably FIRES on its seeded corpus entry (exactly
  once) and stays silent on the matching known-good idiom;
- the whole-package sweep is clean (tier-1: every future PR is checked
  against every invariant) and fits the < 60 s budget;
- the `_Ring` model check explores P ∈ {2,3,4} with no deadlock /
  slot-reuse state reachable, and each seeded protocol mutation is
  caught;
- the jaxpr-contract library behaves (materialization, anti-vacuity,
  transfer, donation) — the serving tests now import it for their
  pins;
- lockdep finds a seeded lock-order cycle and names it, and stays
  silent on consistent order;
- the CLI exit-code grammar: 0 clean / 1 violations / 2 unusable.
"""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

import pytest

from mpit_tpu import analysis
from mpit_tpu.analysis import jaxpr_check, kernel_check, lint, lockdep
from mpit_tpu.analysis.common import SourceFile
from mpit_tpu.analysis.__main__ import main as cli_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS = os.path.join(REPO, "tests", "analysis_corpus")


def corpus(name):
    return os.path.join(CORPUS, name)


def run_static(paths, rules=None):
    """The analyzer without the traced-contract sweep (corpus files
    have no contracts; the sweep has its own tests)."""
    return analysis.run(paths, rules=rules, jaxpr_sweep=False)


class TestCorpusRulesFire:
    """Each rule fires exactly once on its seeded violation and not at
    all on the matching known-good idiom (false-positive guard)."""

    @pytest.mark.parametrize(
        "bad,ok,rule",
        [
            ("host_sync_bad.py", "host_sync_ok.py", "host-sync-in-hot-seam"),
            ("jit_depth_bad.py", "jit_depth_ok.py", "jit-in-hot-seam"),
            ("determinism_bad.py", "determinism_ok.py", "determinism-seam"),
            ("util_gate_bad.py", "util_gate_ok.py", "unlabeled-utilization"),
            ("thread_bind_bad.py", "thread_bind_ok.py", "thread-bind"),
            ("ledger_seam_bad.py", "ledger_seam_ok.py", "ledger-seam"),
            ("memledger_bad.py", "memledger_ok.py", "memledger-seam"),
            (
                "shipment_seam_bad.py",
                "shipment_seam_ok.py",
                "shipment-seam",
            ),
            ("tier_seam_bad.py", "tier_seam_ok.py", "tier-seam"),
            ("kernel_dma_bad.py", "kernel_dma_ok.py", "kernel-dma-balance"),
            ("kernel_ring_bad.py", None, "kernel-ring-order"),
        ],
    )
    def test_rule_fires_once_and_guards(self, bad, ok, rule):
        code, violations = run_static([corpus(bad)], rules={rule})
        assert code == 1
        assert [v.rule for v in violations] == [rule], violations
        assert violations[0].path.endswith(bad)
        assert violations[0].line > 0
        if ok is not None:
            code, violations = run_static([corpus(ok)], rules={rule})
            assert code == 0, [v.format() for v in violations]

    def test_corpus_bad_lines_point_at_marked_statements(self):
        """The finding lands on the line carrying the VIOLATION marker
        comment — locations are actionable, not function headers."""
        for name, rule in [
            ("host_sync_bad.py", "host-sync-in-hot-seam"),
            ("jit_depth_bad.py", "jit-in-hot-seam"),
            ("determinism_bad.py", "determinism-seam"),
            ("util_gate_bad.py", "unlabeled-utilization"),
            ("thread_bind_bad.py", "thread-bind"),
            ("ledger_seam_bad.py", "ledger-seam"),
            ("memledger_bad.py", "memledger-seam"),
            ("shipment_seam_bad.py", "shipment-seam"),
            ("tier_seam_bad.py", "tier-seam"),
            ("kernel_ring_bad.py", "kernel-ring-order"),
        ]:
            _, violations = run_static([corpus(name)], rules={rule})
            sf = SourceFile(corpus(name))
            marked = [
                i
                for i, line in enumerate(sf.lines, start=1)
                if "VIOLATION" in line
            ]
            assert violations[0].line in marked, (name, violations)

    def test_whole_corpus_exactly_one_violation_per_rule(self):
        """The corpus README pin: analyzing the whole corpus directory
        yields exactly the eleven seeded violations — one per static
        rule, nothing from the ok twins."""
        code, violations = run_static([CORPUS])
        assert code == 1
        by_rule = sorted(v.rule for v in violations)
        assert by_rule == sorted(
            [
                "host-sync-in-hot-seam", "jit-in-hot-seam",
                "determinism-seam", "unlabeled-utilization",
                "thread-bind", "ledger-seam", "memledger-seam",
                "shipment-seam", "tier-seam", "kernel-dma-balance",
                "kernel-ring-order",
            ]
        ), [v.format() for v in violations]
        assert all("_bad.py" in v.path for v in violations)

    def test_thread_bind_sees_bound_method_targets(self):
        """Review finding: ``target=self._beat`` (an Attribute, the
        data/loader idiom) must resolve like a bare name — the rule
        cannot be blind to the exact bug class it exists for."""
        src = (
            "import threading\n"
            "class Client:\n"
            "    def _beat(self):\n"
            "        mpiT.Send(self.buf, dest=0, tag=7, comm=self.comm)\n"
            "    def start(self):\n"
            "        threading.Thread(target=self._beat).start()\n"
        )
        sf = SourceFile("bound.py", text=src)
        violations = lint.lint_file(sf, rules={"thread-bind"})
        assert [v.rule for v in violations] == ["thread-bind"], violations
        bound_ok = src.replace(
            "        mpiT.Send(",
            "        mpiT.bind_thread(self.rank, self.comm)\n"
            "        mpiT.Send(",
        )
        sf = SourceFile("bound_ok.py", text=bound_ok)
        assert lint.lint_file(sf, rules={"thread-bind"}) == []

    def test_suppression_silences_and_unsuppressed_twin_fires(self):
        src_bad = (
            "# analysis: hot-seam\n"
            "def tick(engine):\n"
            "    x = engine.step_jit()\n"
            "    return float(x)\n"
        )
        src_ok = (
            "# analysis: hot-seam\n"
            "def tick(engine):\n"
            "    x = engine.step_jit()\n"
            "    # analysis: allow(host-sync-in-hot-seam) deliberate fence\n"
            "    return float(x)\n"
        )
        sf = SourceFile("inline_bad.py", text=src_bad)
        assert len(lint.lint_file(sf)) == 1
        sf = SourceFile("inline_ok.py", text=src_ok)
        assert lint.lint_file(sf) == []


class TestPackageSweep:
    def test_whole_package_clean_within_budget(self):
        """THE tier-1 gate: every invariant over the whole package,
        exit 0, and the sweep fits the < 60 s budget (it also shows up
        in the conftest wall-time guard's slowest-tests list if it
        ever grows)."""
        t0 = time.time()
        code, violations = analysis.run([os.path.join(REPO, "mpit_tpu")])
        wall = time.time() - t0
        assert code == 0, "\n".join(v.format() for v in violations)
        assert wall < 60, f"analyzer sweep took {wall:.1f}s (budget 60s)"

    def test_rules_registered(self):
        from mpit_tpu.analysis.common import RULES

        for rule in (
            "host-sync-in-hot-seam", "jit-in-hot-seam", "determinism-seam",
            "unlabeled-utilization", "thread-bind", "kernel-dma-balance",
            "kernel-ring-order", "kernel-plan-geometry", "kernel-ring-model",
            "jaxpr-contracts",
        ):
            assert rule in RULES, rule


class TestRingModelCheck:
    def test_protocol_clean_p234_both_variants(self):
        """The acceptance pin: P ∈ {2,3,4}, plain and forwarding
        phases, exhaustively explored — no deadlock, no slot reuse,
        semaphores zero at exit."""
        for p in (2, 3, 4):
            for variant in ("rs", "ag_q8"):
                res = kernel_check.model_check_ring(p, variant)
                assert res["ok"], res["violation"]
                assert res["states"] > 0

    def test_state_space_actually_grows(self):
        """Exhaustiveness sanity: more devices = more interleavings."""
        s2 = kernel_check.model_check_ring(2, "rs")["states"]
        s4 = kernel_check.model_check_ring(4, "rs")["states"]
        assert s4 > 10 * s2

    @pytest.mark.parametrize(
        "mutation,variant,needle",
        [
            ("skip_cap_wait", "rs", "slot reuse"),
            ("release_before_restage", "ag_q8", "stale restage"),
            ("skip_barrier", "rs", "before it entered"),
            ("skip_drain", "rs", "nonzero semaphores"),
        ],
    )
    def test_mutations_detected(self, mutation, variant, needle):
        """The race detector demonstrably detects: every seeded
        protocol mutation reaches a violating state at some P<=4."""
        found = None
        for p in (2, 3, 4):
            res = kernel_check.model_check_ring(
                p, variant, frozenset({mutation})
            )
            if not res["ok"]:
                found = res["violation"]
                break
        assert found is not None and needle in found, found


class TestKernelGeometry:
    def test_plan_geometry_clean(self):
        assert kernel_check.check_plan_geometry() == []

    def test_vmem_estimate_tracks_planner(self):
        """The footprint figure is computed from the REAL scratch
        shapes — a planner change that doubles padded_rows moves it."""
        import jax.numpy as jnp

        from mpit_tpu.ops import ring_collectives as rc

        rows = rc.plan_ring(2 ** 20, 8, jnp.float32).padded_rows
        small = sum(
            kernel_check._spec_bytes(s)
            for s in rc._sum_scratch(rows, jnp.float32)
        )
        big = sum(
            kernel_check._spec_bytes(s)
            for s in rc._sum_scratch(2 * rows, jnp.float32)
        )
        assert small > 0 and big == 2 * small


class TestJaxprLibrary:
    def test_find_avals_and_assertions(self):
        import jax
        import jax.numpy as jnp

        def f(a, b):
            big = a @ b  # (4, 3)
            return big.sum()

        jx = jax.make_jaxpr(f)(jnp.ones((4, 8)), jnp.ones((8, 3)))
        assert jaxpr_check.find_avals(jx, (4, 3))
        jaxpr_check.assert_intermediate(jx, (4, 3))
        jaxpr_check.assert_no_intermediate(jx, (9, 9))
        with pytest.raises(jaxpr_check.JaxprContractError):
            jaxpr_check.assert_no_intermediate(jx, (4, 3))
        with pytest.raises(jaxpr_check.JaxprContractError):
            jaxpr_check.assert_intermediate(jx, (9, 9))

    def test_find_avals_descends_nested_jaxprs(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        def f(a, b):
            def body(c, _):
                return c @ b, ()

            out, _ = lax.scan(body, a, None, length=3)
            return out.sum()

        jx = jax.make_jaxpr(f)(jnp.ones((4, 8)), jnp.ones((8, 8)))
        hits = jaxpr_check.find_avals(jx, (4, 8), prims={"dot_general"})
        assert hits, "matmul inside scan body not found"

    def test_no_transfer_detects_callback(self):
        import jax
        import jax.numpy as jnp

        def clean(x):
            return x * 2

        jx = jax.make_jaxpr(clean)(jnp.ones((4,)))
        jaxpr_check.assert_no_transfer(jx)

        def dirty(x):
            return jax.pure_callback(
                lambda v: v, jax.ShapeDtypeStruct((4,), jnp.float32), x
            )

        jx = jax.make_jaxpr(dirty)(jnp.ones((4,)))
        with pytest.raises(jaxpr_check.JaxprContractError):
            jaxpr_check.assert_no_transfer(jx)

    def test_primitive_pin_descends_nested_jaxprs(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        def f(x):
            def body(c, _):
                return lax.top_k(c, 4)[0], ()

            out, _ = lax.scan(body, x, None, length=2)
            return out / 2.0

        jx = jax.make_jaxpr(f)(jnp.ones((3, 4)))
        assert jaxpr_check.find_primitives(jx, {"top_k", "div"}) == [
            "top_k", "div",
        ]
        jaxpr_check.assert_no_primitive(jx, {"sort", "random_bits"})
        with pytest.raises(jaxpr_check.JaxprContractError, match="top_k"):
            jaxpr_check.assert_no_primitive(jx, {"top_k"})

    @pytest.mark.parametrize("broken", ["greedy_sorts", "no_cond"])
    def test_lm_head_sample_contract_catches_a_lost_greedy_path(
        self, monkeypatch, broken
    ):
        """The ``lm-head-sample`` contract holds on the tree, and fails
        when the sampler goes back to one path that sorts for every
        call, or keeps two that both do."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from mpit_tpu.ops import lm_head

        assert jaxpr_check.sweep(names={"lm-head-sample"}) == []

        def sorts(h, head):
            return lax.top_k(h @ head.T[:, :8], 2)[1][:, 0]

        def one_path(h, head, key, temperature, top_k, **kw):
            if broken == "no_cond":
                return sorts(h, head)
            return lax.cond(
                jnp.any(temperature > 0),
                lambda: sorts(h, head) + 1, lambda: sorts(h, head),
            )

        monkeypatch.setattr(lm_head, "lm_head_sample", one_path)
        (violation,) = jaxpr_check.sweep(names={"lm-head-sample"})
        assert "lm-head-sample" in violation.message
        assert (
            "0 top-level conditionals" if broken == "no_cond"
            else "greedy path contains ['top_k']"
        ) in violation.message

    def test_donation_detection(self):
        import jax
        import jax.numpy as jnp

        def f(x, y):
            return x + y, y

        donated = jax.jit(f, donate_argnums=(0,)).lower(
            jnp.ones((4, 4)), jnp.ones((4, 4))
        )
        jaxpr_check.assert_donation_consumed(donated, min_aliased=1)
        plain = jax.jit(f).lower(jnp.ones((4, 4)), jnp.ones((4, 4)))
        assert jaxpr_check.donation_aliases(plain.as_text()) == 0
        with pytest.raises(jaxpr_check.JaxprContractError):
            jaxpr_check.assert_donation_consumed(plain, min_aliased=1)

    def test_eqn_count_pin(self):
        import jax
        import jax.numpy as jnp

        jx = jax.make_jaxpr(lambda x: x + 1)(jnp.ones((4,)))
        assert jaxpr_check.eqn_count(jx) >= 1
        with pytest.raises(jaxpr_check.JaxprContractError):
            jaxpr_check.max_eqn_count(jx, 0)

    def test_sweep_contract_failure_is_a_violation(self, monkeypatch):
        """A contract that breaks (or errors on API drift) surfaces as
        a violation, never a silent skip."""

        def boom(ctx):
            raise jaxpr_check.JaxprContractError("seeded failure")

        def drift(ctx):
            raise AttributeError("renamed_api")

        monkeypatch.setitem(jaxpr_check.CONTRACTS, "seeded", boom)
        monkeypatch.setitem(jaxpr_check.CONTRACTS, "drifted", drift)
        out = jaxpr_check.sweep(names={"seeded", "drifted"})
        assert {"seeded failure" in v.message for v in out} == {True, False}
        assert any("went dark" in v.message for v in out)
        assert all(v.rule == "jaxpr-contracts" for v in out)

    def test_find_avals_dtype_filter(self):
        """ISSUE 15: the quantized-decode contract needs shape+dtype —
        an int8 buffer legitimately carries the pool shape, and only a
        float32 aval of it means the dequant escaped its tile."""
        import jax
        import jax.numpy as jnp

        def f(q):  # int8 in, f32 out — SAME shape both dtypes
            return q.astype(jnp.float32) * 2.0

        jx = jax.make_jaxpr(f)(jnp.zeros((4, 8), jnp.int8))
        f32 = jnp.dtype(jnp.float32)
        assert jaxpr_check.find_avals(jx, (4, 8), dtype=f32)
        assert not jaxpr_check.find_avals(
            jx, (4, 8), dtype=jnp.dtype(jnp.int16)
        )
        with pytest.raises(jaxpr_check.JaxprContractError):
            jaxpr_check.assert_no_intermediate(jx, (4, 8), dtype=f32)
        # The int8 INPUT is not an eqn output; only produced avals count
        # — and unfiltered behavior is unchanged (back-compat).
        assert jaxpr_check.find_avals(jx, (4, 8))
        jaxpr_check.assert_no_intermediate(jx, (9, 9), dtype=f32)
        with pytest.raises(jaxpr_check.JaxprContractError):
            jaxpr_check.assert_intermediate(
                jx, (4, 8), dtype=jnp.dtype(jnp.bfloat16)
            )


class TestQuantizedDecodeCorpus:
    """ISSUE 15 corpus pair: the traced quantized-decode discipline —
    whole-pool dequant is caught, per-tile dequant passes. (The real
    engine's contract lives in the sweep; this pins the DETECTOR on
    minimal seeded code, like the static rules' corpus.)"""

    def _trace(self, name):
        import importlib.util

        import jax
        import jax.numpy as jnp

        spec = importlib.util.spec_from_file_location(
            name, corpus(f"{name}.py")
        )
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        P, ps, H, D = m.POOL_PAGES, m.PAGE_SIZE, m.HEADS, m.HEAD_DIM
        jx = jax.make_jaxpr(m.attend)(
            jnp.zeros((2, 1, H, D), jnp.float32),
            jnp.zeros((P, ps, H, D), jnp.int8),
            jnp.ones((P, ps, H, 1), jnp.float32),
            jnp.zeros((2, 3), jnp.int32),
            jnp.zeros((2,), jnp.int32),
        )
        return jx, (P, ps, H, D)

    def test_bad_whole_pool_dequant_is_caught(self):
        import jax.numpy as jnp

        jx, pool = self._trace("quantized_decode_bad")
        with pytest.raises(
            jaxpr_check.JaxprContractError, match="materializes"
        ):
            jaxpr_check.assert_no_intermediate(
                jx, pool, what="corpus bad",
                dtype=jnp.dtype(jnp.float32),
            )

    def test_ok_per_tile_dequant_passes(self):
        import jax.numpy as jnp

        jx, pool = self._trace("quantized_decode_ok")
        jaxpr_check.assert_no_intermediate(
            jx, pool, what="corpus ok", dtype=jnp.dtype(jnp.float32)
        )

    def test_corpus_pair_seeds_no_static_violations(self):
        """The pair must not disturb the whole-corpus lint pin (their
        violations are traced, not AST)."""
        for name in ("quantized_decode_bad", "quantized_decode_ok"):
            code, violations = run_static([corpus(f"{name}.py")])
            assert code == 0, [v.format() for v in violations]


class TestQuantizedWeightsCorpus:
    """ISSUE 17 corpus pair: the traced quantized-weights discipline —
    whole-kernel dequant is caught, per-row-block dequant passes. (The
    real engine's contract lives in the sweep; this pins the DETECTOR
    on minimal seeded code, like the ISSUE 15 pair.)"""

    def _trace(self, name):
        import importlib.util

        import jax
        import jax.numpy as jnp

        spec = importlib.util.spec_from_file_location(
            name, corpus(f"{name}.py")
        )
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        d, f = m.ROWS, m.COLS
        jx = jax.make_jaxpr(m.project)(
            jnp.zeros((2, d), jnp.float32),
            jnp.zeros((d, f), jnp.int8),
            jnp.ones((d, 1), jnp.float32),
            jnp.zeros((f,), jnp.float32),
        )
        return jx, (d, f)

    def test_bad_whole_kernel_dequant_is_caught(self):
        import jax.numpy as jnp

        jx, kernel = self._trace("quantized_weights_bad")
        with pytest.raises(
            jaxpr_check.JaxprContractError, match="materializes"
        ):
            jaxpr_check.assert_no_intermediate(
                jx, kernel, what="corpus bad",
                dtype=jnp.dtype(jnp.float32),
            )

    def test_ok_per_block_dequant_passes(self):
        import jax.numpy as jnp

        jx, kernel = self._trace("quantized_weights_ok")
        jaxpr_check.assert_no_intermediate(
            jx, kernel, what="corpus ok", dtype=jnp.dtype(jnp.float32)
        )

    def test_corpus_pair_seeds_no_static_violations(self):
        for name in ("quantized_weights_bad", "quantized_weights_ok"):
            code, violations = run_static([corpus(f"{name}.py")])
            assert code == 0, [v.format() for v in violations]

    def test_registered_in_sweep(self):
        """The real engine's contract is registered (a rename must not
        silently drop the pin)."""
        assert "quantized-weights" in jaxpr_check.CONTRACTS


class TestLockdep:
    def _mk_locks(self, n):
        # Created through the patched factory with package="tests", so
        # this frame (tests/test_analysis.py) is a valid creation site;
        # distinct lines give distinct site identities.
        a = threading.Lock()
        b = threading.Lock()
        return (a, b) if n == 2 else (a, b, threading.Lock())

    def test_cycle_detected_and_named(self):
        lockdep.install(package="tests")
        lockdep.reset()
        try:
            a, b = self._mk_locks(2)
            with a:
                with b:
                    pass
            with b:
                with a:  # the opposite order: a latent deadlock
                    pass
            cycles = lockdep.cycles()
            assert cycles, "A->B and B->A must form a cycle"
            text = lockdep.format_cycles(cycles)
            assert "test_analysis.py" in text
            with pytest.raises(lockdep.LockOrderError):
                lockdep.check()
        finally:
            lockdep.reset()
            lockdep.uninstall()

    def test_consistent_order_is_clean(self):
        lockdep.install(package="tests")
        lockdep.reset()
        try:
            a, b, c = self._mk_locks(3)
            for _ in range(3):
                with a:
                    with b:
                        with c:
                            pass
            assert lockdep.cycles() == []
            lockdep.check()  # no raise
        finally:
            lockdep.reset()
            lockdep.uninstall()

    def test_cross_thread_edges_merge(self):
        """Thread 1 takes A->B, thread 2 takes B->A: the graph is
        global, so the cycle is found even though neither thread saw
        both orders."""
        lockdep.install(package="tests")
        lockdep.reset()
        try:
            a, b = self._mk_locks(2)

            def order(x, y):
                with x:
                    with y:
                        pass

            t1 = threading.Thread(target=order, args=(a, b))
            t1.start()
            t1.join()
            t2 = threading.Thread(target=order, args=(b, a))
            t2.start()
            t2.join()
            assert lockdep.cycles()
        finally:
            lockdep.reset()
            lockdep.uninstall()

    def test_rlock_reentrancy_no_false_cycle(self):
        lockdep.install(package="tests")
        lockdep.reset()
        try:
            r = threading.RLock()
            other = threading.Lock()
            with r:
                with r:  # reentrant: no self edge
                    with other:
                        pass
            assert lockdep.cycles() == []
            assert lockdep.self_nesting() == {}
        finally:
            lockdep.reset()
            lockdep.uninstall()

    def test_condition_wait_keeps_bookkeeping(self):
        """Condition.wait releases and reacquires the underlying lock;
        the held-set must stay coherent (no phantom held locks feeding
        false edges)."""
        lockdep.install(package="tests")
        lockdep.reset()
        try:
            lock = threading.Lock()
            cond = threading.Condition(lock)
            done = []

            def waiter():
                with cond:
                    cond.wait(timeout=5)
                    done.append(True)

            t = threading.Thread(target=waiter)
            t.start()
            time.sleep(0.05)
            with cond:
                cond.notify()
            t.join(5)
            assert done == [True]
            assert lockdep.cycles() == []
        finally:
            lockdep.reset()
            lockdep.uninstall()

    def test_compat_simulator_run_under_lockdep_is_clean(self):
        """The real thing: a 4-rank compat parity run with every
        mpit_tpu lock recorded — no lock-order cycle (this is the hook
        conftest keeps enabled for the threaded suites)."""
        lockdep.install()  # default package="mpit_tpu"
        lockdep.reset()
        try:
            import numpy as np

            from mpit_tpu import compat

            def fn(rank):
                comm = compat.COMM_WORLD
                n = compat.Comm_size(comm)
                me = compat.Comm_rank(comm)
                req = compat.Isend(
                    np.asarray([me], np.int64), dest=(me + 1) % n,
                    tag=1, comm=comm,
                )
                out = np.zeros((1,), np.int64)
                compat.Recv(out, src=(me - 1 + n) % n, tag=1, comm=comm)
                compat.Wait(req)
                return int(out[0])

            res = compat.run(fn, nranks=4, pass_rank=True)
            assert sorted(res) == [0, 1, 2, 3]
            cycles = lockdep.cycles()
            assert cycles == [], lockdep.format_cycles(cycles)
        finally:
            lockdep.reset()
            lockdep.uninstall()


class TestCLI:
    def test_exit_codes_in_process(self):
        assert cli_main(["--list-rules"]) == 0
        assert cli_main([corpus("host_sync_ok.py"), "--no-jaxpr"]) == 0
        assert (
            cli_main([corpus("host_sync_bad.py"), "--no-jaxpr"]) == 1
        )
        assert cli_main(["does/not/exist.py", "--no-jaxpr"]) == 2
        assert cli_main(["--rule", "no-such-rule"]) == 2

    def test_syntax_error_target_is_unusable(self, tmp_path):
        p = tmp_path / "broken.py"
        p.write_text("def broken(:\n")
        assert cli_main([str(p), "--no-jaxpr"]) == 2

    def test_non_utf8_target_is_unusable_not_a_crash(self, tmp_path):
        """Review finding: a legal PEP-263 latin-1 source crashed the
        analyzer (UnicodeDecodeError escaping as a traceback with exit
        1 = 'violations'). It must be the exit-2 unusable verdict."""
        p = tmp_path / "latin1_mod.py"
        p.write_bytes(
            b"# -*- coding: latin-1 -*-\n" b'NAME = "caf\xe9"\n'
        )
        assert cli_main([str(p), "--no-jaxpr"]) == 2

    def test_changed_mode_scopes_to_git_diff(self, tmp_path):
        """--changed (the pre-commit entry point): only touched files
        are analyzed; a clean working tree exits 0 instantly."""
        import shutil

        repo = tmp_path / "r"
        repo.mkdir()
        subprocess.run(
            ["git", "init", "-q"], cwd=repo, check=True,
            env={**os.environ, "HOME": str(tmp_path)},
        )
        shutil.copy(corpus("host_sync_bad.py"), repo / "touched.py")
        (repo / "untouched.py").write_text("x = 1\n")
        env = {**os.environ, "HOME": str(tmp_path)}
        subprocess.run(
            ["git", "add", "untouched.py"], cwd=repo, check=True, env=env
        )
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t",
             "commit", "-qm", "seed"],
            cwd=repo, check=True, env=env,
        )
        # A violating file inside an UNTRACKED DIRECTORY: plain
        # `git status` collapses it to "?? newmod/" — the analyzer must
        # still see the .py inside (-uall; review finding).
        (repo / "newmod").mkdir()
        shutil.copy(corpus("determinism_bad.py"), repo / "newmod" / "d.py")
        # And a name porcelain C-QUOTES (space): left quoted it fails
        # the .py suffix check and silently drops out (review finding).
        shutil.copy(corpus("determinism_bad.py"), repo / "my file.py")
        old = os.getcwd()
        os.chdir(repo)
        try:
            # touched.py and newmod/d.py are untracked => in scope.
            code, violations = analysis.run(
                ["."], changed=True, jaxpr_sweep=False
            )
            assert code == 1
            flagged = {os.path.basename(v.path) for v in violations}
            assert flagged == {"touched.py", "d.py", "my file.py"}, violations
            # Clean tree: nothing in scope.
            subprocess.run(
                ["git", "add", "-A"], cwd=repo, check=True, env=env
            )
            subprocess.run(
                ["git", "-c", "user.email=t@t", "-c", "user.name=t",
                 "commit", "-qm", "all"],
                cwd=repo, check=True, env=env,
            )
            code, violations = analysis.run(
                ["."], changed=True, jaxpr_sweep=False
            )
            assert (code, violations) == (0, [])
        finally:
            os.chdir(old)

    def test_rule_filter_never_leaks_other_rules(self):
        """--rule is a contract for EVERY pass (review finding: the
        kernel AST checker emits both its rules; run() must filter):
        scoping to kernel-dma-balance on a file violating only
        kernel-ring-order reports clean, and vice versa."""
        code, violations = run_static(
            [corpus("kernel_ring_bad.py")], rules={"kernel-dma-balance"}
        )
        assert (code, violations) == (0, [])
        code, violations = run_static(
            [corpus("kernel_dma_bad.py")], rules={"kernel-ring-order"}
        )
        assert (code, violations) == (0, [])

    def test_changed_mode_works_with_absolute_paths(self):
        """Review finding: git names are repo-root-relative; absolute
        target paths (and subdirectory cwds) must still intersect.
        This repo's own working tree has changed .py files while this
        PR is in flight — at minimum, the analyzer must not report an
        EMPTY scope for an absolute path when git sees changes under
        it; and a scratch repo pins the positive case end-to-end."""
        import shutil

        # Positive pin on a scratch repo with an ABSOLUTE target path.
        with __import__("tempfile").TemporaryDirectory() as td:
            repo = os.path.join(td, "r")
            os.mkdir(repo)
            env = {**os.environ, "HOME": td}
            subprocess.run(["git", "init", "-q"], cwd=repo, check=True,
                           env=env)
            shutil.copy(
                corpus("determinism_bad.py"), os.path.join(repo, "t.py")
            )
            # NO chdir: the cwd stays in THIS repo, so the change set
            # must come from the repo that owns the TARGET (review
            # finding: cwd-anchored git made cross-repo targets
            # silently 'clean').
            code, violations = analysis.run(
                [os.path.abspath(repo)], changed=True, jaxpr_sweep=False
            )
            assert code == 1
            assert [os.path.basename(v.path) for v in violations] == [
                "t.py"
            ], violations

    def test_changed_mode_without_git_is_unusable(self, tmp_path):
        """Review finding: a swallowed git failure turned '--changed
        outside a repo' into exit 0 'clean'. The analyzer must refuse
        (exit 2) — it cannot analyze what it cannot scope."""
        (tmp_path / "x.py").write_text("x = 1\n")
        old = os.getcwd()
        os.chdir(tmp_path)  # no .git anywhere above tmp_path
        try:
            code, violations = analysis.run(
                [str(tmp_path)], changed=True, jaxpr_sweep=False
            )
        finally:
            os.chdir(old)
        if code != 2:
            pytest.skip("cwd unexpectedly inside a git worktree")
        assert violations and "--changed" in violations[0].path

    @pytest.mark.slow
    def test_cli_subprocess_smoke(self):
        """The real module entry point, once (subprocess pays the jax
        import; the in-process tests above cover the grammar)."""
        proc = subprocess.run(
            [sys.executable, "-m", "mpit_tpu.analysis", "--no-jaxpr",
             corpus("determinism_bad.py")],
            capture_output=True, text=True, cwd=REPO, timeout=300,
        )
        assert proc.returncode == 1, proc.stderr
        assert "determinism-seam" in proc.stdout


class TestDirectivesAndSuppression:
    def test_module_vs_def_directive(self):
        sf = SourceFile(
            "x.py",
            text=(
                "# analysis: determinism-seam\n"
                "import time\n\n\n"
                "# analysis: hot-seam\n"
                "def f():\n"
                "    pass\n"
            ),
        )
        assert sf.module_role("determinism-seam")
        assert not sf.module_role("hot-seam")  # attached to the def
        assert sf.func_role("hot-seam", 6)

    def test_allow_star_suppresses_everything(self):
        src = (
            "# analysis: determinism-seam\n"
            "import time\n"
            "def f():\n"
            "    return time.time()  # analysis: allow(*) corpus prop\n"
        )
        sf = SourceFile("y.py", text=src)
        assert lint.lint_file(sf) == []
