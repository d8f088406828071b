"""ISSUE 12: the SLO-aware scheduling-policy tier (``serve.policy``).

Pinned invariants:

- **tier order**: priority 0 drains before priority 1 regardless of
  submit order;
- **fairness**: a 10:1 tenant-load skew under deficit round-robin keeps
  the starved tenant's service within its configured weight share, and
  deficit counters stay bounded (``≤ max(quantum × weight, 1)`` + the
  1-credit restore excursion);
- **preempt→resume bit-match**: a preempted-then-resumed greedy request
  produces exactly the tokens of its un-preempted run;
- **pool accounting**: preemption frees exactly the victim's non-shared
  pages;
- **shed causes**: ``shed_admission`` (projected-TTFT breach) and
  ``shed_queue_full`` (bounded intake) are distinct in counters,
  instants and stats, while ``serve_shed`` stays the SLO numerator
  total.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu import obs
from mpit_tpu.models import GPT2, GPT2Config
from mpit_tpu.obs.stream import StreamRegistry
from mpit_tpu.serve import (
    Engine,
    LoadSpec,
    PolicyConfig,
    Request,
    RequestClass,
    SchedulingPolicy,
    Server,
    TTFTProjector,
    generate_arrivals,
    parse_load_spec,
    parse_policy_spec,
)

CFG = GPT2Config.tiny(max_seq_len=128, num_layers=2)


@pytest.fixture(scope="module")
def params():
    return jax.jit(GPT2(CFG).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def _paged_engine(params, *, slots=2, kv_pages=16, page_size=8,
                  max_len=64, chunk=8):
    return Engine(
        CFG, params, slots=slots, max_len=max_len, prefill_len=32,
        kv_pages=kv_pages, kv_page_size=page_size, prefill_chunk=chunk,
        decode_attention="reference",
    )


def _full_pool_engine(params, *, slots=2):
    return Engine(CFG, params, slots=slots, max_len=48, prefill_len=16,
                  decode_attention="reference")


def _req(rid, prompt, *, new=3, priority=0, tenant="", target=0.0):
    return Request(rid=rid, prompt=list(prompt), max_new_tokens=new,
                   priority=priority, tenant=tenant, ttft_target_s=target)


class TestPolicyOrdering:
    @pytest.mark.parametrize(
        "make", [_full_pool_engine, _paged_engine],
        ids=["pool-for-every-slot", "small-pool-chunked"],
    )
    def test_tier_order_beats_submit_order(self, params, make):
        """Priority 0 admits before priority 1 even when submitted
        last — whether or not pages are what admission waits for."""
        pol = SchedulingPolicy(PolicyConfig(preempt=False))
        server = Server(make(params), policy=pol)
        for i in range(4):
            server.submit(_req(f"low{i}", [1 + i] * 4, priority=1))
        server.submit(_req("hi", [9] * 4, priority=0))
        server.run()
        assert pol.admitted[0][0] == "hi", pol.admitted

    def test_fifo_within_tier_single_tenant(self, params):
        engine = _full_pool_engine(params)
        pol = SchedulingPolicy()
        server = Server(engine, policy=pol)
        for i in range(5):
            server.submit(_req(i, [1 + i] * 3))
        server.run()
        assert [rid for rid, _, _ in pol.admitted] == [0, 1, 2, 3, 4]

    def test_policy_outputs_bitmatch_fifo(self, params):
        """Scheduling order must never change WHAT a greedy request
        generates — every completion matches the FIFO run's."""
        engine = _paged_engine(params, slots=2, kv_pages=24)
        rng = np.random.RandomState(3)
        reqs = [
            _req(i, rng.randint(0, CFG.vocab_size, size=6).tolist(),
                 new=4, priority=i % 2, tenant=f"t{i % 3}")
            for i in range(8)
        ]
        server = Server(engine)
        for r in reqs:
            server.submit(Request(**{**r.__dict__}))
        fifo = {c.rid: c.tokens for c in server.run()}
        engine.reset()
        server2 = Server(
            engine, policy=SchedulingPolicy(PolicyConfig(preempt=False))
        )
        for r in reqs:
            server2.submit(r)
        done = server2.run()
        assert len(done) == len(reqs)
        for c in done:
            assert c.tokens == fifo[c.rid], c.rid


class TestFairness:
    def test_skewed_tenant_load_shares_by_weight(self, params):
        """The fairness invariant (ISSUE 12 satellite): tenant A offers
        10× tenant B's load; equal weights ⇒ while B has work queued,
        DRR serves them ~alternately, so B's requests all land in the
        earliest admissions instead of behind A's burst."""
        engine = _full_pool_engine(params, slots=1)  # serialized admits
        pol = SchedulingPolicy(PolicyConfig(quantum=1.0, preempt=False))
        server = Server(engine, policy=pol)
        for i in range(20):
            server.submit(_req(f"a{i}", [1 + (i % 7)] * 3, tenant="A"))
        for i in range(2):
            server.submit(_req(f"b{i}", [11 + i] * 3, tenant="B"))
        server.run()
        order = [rid for rid, _, _ in pol.admitted]
        # B has 2 requests against A's 20; with quantum=1 and equal
        # weights the rotation alternates, so both B requests are
        # served within the first 2 × (2 + 1) admissions — far ahead
        # of A's burst draining.
        for i, rid in enumerate(("b0", "b1")):
            assert order.index(rid) <= 2 * (i + 1) + 1, order

    def test_weight_ratio_bounds_service_share(self, params):
        """With weight 2:1, the heavy tenant gets ~2/3 of admissions
        while both have backlog (the configured ratio, ±1 quantum)."""
        engine = _full_pool_engine(params, slots=1)
        pol = SchedulingPolicy(PolicyConfig(
            quantum=1.0, preempt=False, tenant_weights={"A": 2.0},
        ))
        server = Server(engine, policy=pol)
        for i in range(24):
            server.submit(_req(f"a{i}", [1 + (i % 7)] * 3, tenant="A"))
        for i in range(24):
            server.submit(_req(f"b{i}", [11 + (i % 7)] * 3, tenant="B"))
        server.run()
        # While both are backlogged (first 30 admissions), A's share
        # must track 2/3 within one quantum's slack each way.
        window = list(pol.admitted)[:30]
        a = sum(1 for _, _, t in window if t == "A")
        assert 18 <= a <= 22, (a, window)

    def test_deficit_counters_stay_bounded(self, params):
        """The pinned DRR invariant: no tenant banks more than
        ``max(quantum × weight, 1)`` credits (+1 transiently after a
        restore) no matter how skewed the arrivals."""
        pol = SchedulingPolicy(PolicyConfig(
            quantum=3.0, tenant_weights={"A": 2.0, "B": 0.1},
        ))
        rng = np.random.RandomState(0)

        def check():
            for st in pol._tiers.values():
                for t, d in st.deficit.items():
                    cap = max(pol.cfg.quantum * pol._weight(t), 1.0)
                    assert d <= cap + 1.0, (t, d, cap)

        serial = 0
        for _ in range(300):
            tenant = rng.choice(["A", "A", "A", "B", "C"])
            live = type("L", (), {})()
            live.req = _req(f"r{serial}", [1], tenant=str(tenant))
            live.submit_t = 0.0
            pol.enqueue(live)
            serial += 1
            if rng.rand() < 0.7 and pol.pending():
                item = pol.next()
                if rng.rand() < 0.2:
                    pol.restore(item)
            check()
        while pol.pending():
            pol.next()
            check()


class TestShedCauses:
    def test_queue_full_vs_admission_distinct(self, params):
        engine = _full_pool_engine(params)
        rec = obs.Recorder()
        with obs.local_recorder(rec):
            reg = StreamRegistry()
            pol = SchedulingPolicy(
                PolicyConfig(preempt=False, min_samples=1), reg
            )
            server = Server(engine, stream=reg, policy=pol, max_queue=2)
            # Prime the projector windows with slow ticks so the
            # projection is decisive.
            reg.observe("prefill_tick", 0.5)
            reg.observe("decode_tick", 0.1)
            # Tight target + queue ahead -> admission shed.
            ok = server.submit(_req("adm", [1] * 3, target=1e-4))
            assert ok is False
            # No target -> queued; 2 more fill max_queue; the next is
            # queue-full shed.
            assert server.submit(_req("q1", [2] * 3)) is True
            assert server.submit(_req("q2", [3] * 3)) is True
            assert server.submit(_req("qf", [4] * 3)) is False
        summ = rec.summary()
        assert summ["counters"]["serve_shed"] == 2
        assert summ["counters"]["serve_shed_admission"] == 1
        assert summ["counters"]["serve_shed_queue_full"] == 1
        # Both causes feed the SLO numerator total AND their own rates.
        assert reg.counter_total("serve_shed") == 2.0
        assert reg.counter_total("serve_shed_admission") == 1.0
        assert reg.counter_total("serve_shed_queue_full") == 1.0
        server.run()
        stats = server.stats()
        # The reason breakdown (ISSUE 16 satellite): a dict with the
        # total and both named reasons, zeros never omitted — plus the
        # flat legacy keys the bench record line reads.
        assert stats["requests_shed"] == {
            "total": 2,
            "shed_queue_full": 1,
            "shed_admission_projection": 1,
        }
        assert stats["requests_shed_admission"] == 1
        assert stats["requests_shed_queue_full"] == 1
        # The instants carry the cause AND the stable reason name for
        # breach forensics.
        shed_instants = [
            attrs
            for kind, name, _t0, _dur, _tid, attrs in rec.snapshot()[
                "events"
            ]
            if kind == "i" and name == "request_shed"
        ]
        assert sorted(a["cause"] for a in shed_instants) == [
            "admission", "queue_full",
        ]
        assert sorted(a["reason"] for a in shed_instants) == [
            "admission_projection", "queue_full",
        ]

    def test_admission_abstains_on_cold_windows(self, params):
        """No evidence, no shedding: a cold projector admits even a
        microscopic target."""
        engine = _full_pool_engine(params)
        pol = SchedulingPolicy(SchedulingPolicy().cfg)
        server = Server(engine, policy=pol)
        assert server.submit(_req("r", [1] * 3, target=1e-6)) is True
        server.run()
        assert server.stats()["requests_completed"] == 1


class TestProjector:
    def test_projection_formula_and_abstention(self):
        reg = StreamRegistry(clock=lambda: 100.0)
        proj = TTFTProjector(reg, quantile=0.5, min_samples=4)
        assert proj.projected_ttft_s(3) is None  # cold
        for _ in range(4):
            reg.observe("prefill_tick", 0.2, t=100.0)
        for _ in range(4):
            reg.observe("decode_tick", 0.05, t=100.0)
        got = proj.projected_ttft_s(3)
        # (depth + 1) × prefill + decode, within the sketch's 1% error.
        assert got == pytest.approx(4 * 0.2 + 0.05, rel=0.02)

    def test_registry_autocreated_and_bound(self, params):
        """Server(policy=) without a stream still projects — a private
        registry is created and bound."""
        engine = _full_pool_engine(params)
        pol = SchedulingPolicy()
        server = Server(engine, policy=pol)
        assert server.stream is not None
        assert pol.projector.registry is server.stream


class TestPreemption:
    def _victim_trace(self, rng, n=10):
        return rng.randint(0, CFG.vocab_size, size=n).tolist()

    def test_preempt_resume_bitmatch(self, params):
        """THE pinned invariant: park a mid-generation request (pages
        freed, tokens kept), resume through chunked prefill — the final
        greedy output is byte-identical to the un-preempted run."""
        rng = np.random.RandomState(7)
        engine = _paged_engine(params)
        prompt = self._victim_trace(rng)
        server = Server(engine, policy=SchedulingPolicy())
        server.submit(_req("v", prompt, new=8, priority=1))
        server.run(max_ticks=6)
        assert server.live
        slot = next(iter(server.live))
        generated_at_park = len(server.live[slot].tokens)
        assert 0 < generated_at_park < 8
        server._preempt(slot)
        done = server.run()
        engine.reset()
        ref_server = Server(engine)
        ref_server.submit(_req("v", prompt, new=8))
        ref = ref_server.run()
        assert done[0].tokens == ref[0].tokens
        assert server.policy.preemptions == 1
        assert server.policy.resumes == 1
        assert server.stats()["preemptions"] == 1

    def test_preemption_frees_exactly_nonshared_pages(self, params):
        """Pool-accounting pin: parking a victim returns exactly its
        sole-owner pages to the free list; shared-prefix pages only
        drop a refcount and stay resident for the sharer."""
        rng = np.random.RandomState(11)
        engine = _paged_engine(params, slots=2, kv_pages=24)
        alloc = engine.allocator
        prefix = rng.randint(0, CFG.vocab_size, size=16).tolist()
        server = Server(engine, policy=SchedulingPolicy())
        # "a" first, alone, so its prompt registers in the prefix index
        # BEFORE "b" admits and maps the shared pages.
        server.submit(_req("a", prefix + [1, 2], new=10, priority=1))
        server.run(max_ticks=5)
        server.submit(_req("b", prefix + [3, 4], new=10, priority=1))
        server.run(max_ticks=10)  # max_ticks counts from tick 0
        assert set(server.live) == {0, 1}
        owned, shared = alloc.slot_page_stats(1)  # "b", the sharer
        assert shared > 0  # the prefix really is shared
        free_before = len(alloc.free)
        refcounts_before = alloc.refcount.copy()
        server._preempt(1)
        assert len(alloc.free) - free_before == owned
        # Shared pages: refcount dropped by exactly one, still mapped.
        dropped = refcounts_before - alloc.refcount
        assert int(dropped.sum()) == owned + shared
        assert int((dropped == 1).sum()) == owned + shared
        server.run()
        assert {c.rid for c in server.completed} == {"a", "b"}

    def test_policy_triggers_preemption_for_interactive(self, params):
        """End-to-end: long low-tier generations occupy every slot; an
        interactive arrival with a tight TTFT target preempts one
        (policy-decided, not test-forced), completes first, and the
        victims still finish with bit-exact outputs."""
        rng = np.random.RandomState(5)
        engine = _paged_engine(params, slots=2, kv_pages=20)
        prompts = {
            f"long{i}": self._victim_trace(rng, 8) for i in range(2)
        }
        prompts["hi"] = self._victim_trace(rng, 4)
        refs = {}
        for rid, p in prompts.items():
            engine.reset()
            s = Server(engine)
            s.submit(_req(rid, p, new=20 if rid != "hi" else 3))
            refs[rid] = s.run()[0].tokens
        engine.reset()
        rec = obs.Recorder()
        with obs.local_recorder(rec):
            # admission=False: the tight target must reach the QUEUE to
            # exercise preemption — with admission on, the projector
            # (correctly) sheds a 0.1 ms target on a multi-ms host.
            # min_samples=1: the short prompts here produce exactly one
            # prefill chunk before the interactive arrival.
            pol = SchedulingPolicy(
                PolicyConfig(min_samples=1, admission=False)
            )
            server = Server(engine, policy=pol)
            for i in range(2):
                server.submit(
                    _req(f"long{i}", prompts[f"long{i}"], new=20,
                         priority=1)
                )
            server.run(max_ticks=8)  # both live, windows warm
            assert len(server.live) == 2
            server.submit(_req("hi", prompts["hi"], new=3, priority=0,
                               target=1e-4))
            done = server.run()
        assert pol.preemptions >= 1
        by_rid = {c.rid: c for c in done}
        assert set(by_rid) == set(prompts)
        for rid, c in by_rid.items():
            assert c.tokens == refs[rid][: len(c.tokens)], rid
            assert len(c.tokens) == len(refs[rid]), rid
        # The interactive request finished before at least one victim.
        finish = {c.rid: c.finish_t for c in done}
        assert finish["hi"] < max(finish["long0"], finish["long1"])
        names = [e[1] for e in rec.snapshot()["events"]]
        assert "request_preempted" in names
        assert "request_resumed" in names

    def test_max_preemptions_bounds_thrash(self):
        pol = SchedulingPolicy(PolicyConfig(max_preemptions=0))
        live = {0: type("L", (), {})()}
        live[0].req = _req("v", [1], new=8, priority=1)
        live[0].preempts = 0
        live[0].tokens = [1]
        # max_preemptions=0: nothing is ever eligible.
        assert pol.pick_victim(live, 0) is None
        pol2 = SchedulingPolicy(PolicyConfig(max_preemptions=1))
        assert pol2.pick_victim(live, 0) == 0
        live[0].preempts = 1
        assert pol2.pick_victim(live, 0) is None
        # A victim never outranks its preemptor's tier.
        live[0].preempts = 0
        assert pol2.pick_victim(live, 1) is None

    def test_slot_pressure_preempts_with_pages_to_spare(self, params):
        """A pool that holds every slot at max_len never runs out of
        pages, but its one slot is taken: a starving interactive head
        still parks the batch generation, and the resumed request's
        tokens are those of its un-preempted run."""
        def run(policy):
            server = Server(_full_pool_engine(params, slots=1), policy=policy)
            server.submit(_req("long", [1] * 4, new=12, priority=1))
            server.run(max_ticks=4)
            server.submit(
                _req("hi", [2] * 3, new=2, priority=0, target=1e-6))
            return {c.rid: c.tokens for c in server.run()}

        pol = SchedulingPolicy(
            PolicyConfig(min_samples=1, admission=False)
        )
        done = run(pol)
        assert pol.preemptions >= 1
        assert set(done) == {"long", "hi"}
        assert done == run(None)


class TestLoadgenPolicySatellite:
    def test_class_priority_and_target_stamped(self):
        mix = (
            RequestClass("int", weight=1.0, priority=0, ttft_target_s=0.2),
            RequestClass("bat", weight=1.0, priority=2, ttft_target_s=0.0),
        )
        arr = generate_arrivals(
            LoadSpec(rate=50.0, classes=mix), vocab_size=100,
            duration_s=1.0, seed=0,
        )
        assert arr
        for a in arr:
            want = mix[0] if a.klass == "int" else mix[1]
            assert a.request.priority == want.priority
            assert a.request.ttft_target_s == want.ttft_target_s

    def test_priority_does_not_disturb_pinned_rng_stream(self):
        """The stamped fields consume no rng: the arrival stream (times,
        prompts, tenants) is byte-identical with and without them."""
        base = LoadSpec(rate=40.0, tenants=2)
        stamped = LoadSpec(
            rate=40.0, tenants=2,
            classes=tuple(
                RequestClass(
                    c.name, weight=c.weight, prompt_len=c.prompt_len,
                    max_new_tokens=c.max_new_tokens, priority=1,
                    ttft_target_s=0.5,
                )
                for c in base.classes
            ),
        )
        a = generate_arrivals(base, vocab_size=64, duration_s=1.0, seed=3)
        b = generate_arrivals(stamped, vocab_size=64, duration_s=1.0,
                              seed=3)
        assert [x.t for x in a] == [x.t for x in b]
        assert [x.request.prompt for x in a] == [
            x.request.prompt for x in b
        ]
        assert [x.request.tenant for x in a] == [
            x.request.tenant for x in b
        ]
        assert all(x.request.priority == 1 for x in b)

    def test_parse_load_spec_priority_and_target(self):
        spec = parse_load_spec("rate=8,priority=1,ttft_target=0.25")
        assert all(c.priority == 1 for c in spec.classes)
        assert all(c.ttft_target_s == 0.25 for c in spec.classes)
        # Composes with the single-class range override.
        spec2 = parse_load_spec(
            "rate=8,prompt_min=2,prompt_max=4,priority=2"
        )
        assert len(spec2.classes) == 1
        assert spec2.classes[0].priority == 2
        with pytest.raises(ValueError, match="priority"):
            parse_load_spec("rate=8,priority=-1")

    def test_negative_priority_rejected_at_submit(self, params):
        server = Server(_full_pool_engine(params))
        with pytest.raises(ValueError, match="priority"):
            server.submit(Request(rid=0, prompt=[1], priority=-1))


class TestPolicySpec:
    def test_parse_policy_spec(self):
        cfg = parse_policy_spec(
            "quantum=2,preempt=0,admission_factor=1.5,weight.t0=2,"
            "max_preemptions=5,min_samples=2"
        )
        assert cfg.quantum == 2.0
        assert cfg.preempt is False
        assert cfg.admission_factor == 1.5
        assert cfg.tenant_weights == {"t0": 2.0}
        assert cfg.max_preemptions == 5
        assert cfg.min_samples == 2
        assert parse_policy_spec("on") == PolicyConfig()
        with pytest.raises(ValueError, match="unknown"):
            parse_policy_spec("bogus=1")
        with pytest.raises(ValueError, match="quantum"):
            parse_policy_spec("quantum=0")
        with pytest.raises(ValueError, match="weight"):
            PolicyConfig(tenant_weights={"t": 0.0})


class TestPolicyTelemetry:
    def test_tier_series_and_gauges(self, params):
        """Per-tier TTFT series feed the registry (what a tier-scoped
        SLO reads) and per-tier queue-depth gauges read 0 once a tier
        drains."""
        engine = _full_pool_engine(params)
        reg = StreamRegistry()
        pol = SchedulingPolicy(PolicyConfig(preempt=False), reg)
        server = Server(engine, stream=reg, policy=pol)
        server.submit(_req("a", [1] * 3, priority=0))
        server.submit(_req("b", [2] * 3, priority=1))
        server.run()
        assert reg.total_sketch("request_ttft_tier0").count == 1
        assert reg.total_sketch("request_ttft_tier1").count == 1
        assert reg.gauge("queue_depth_tier0") == 0.0
        assert reg.gauge("queue_depth_tier1") == 0.0

    def test_tenant_rollup_in_stats(self, params):
        engine = _full_pool_engine(params)
        reg = StreamRegistry()
        server = Server(engine, stream=reg, max_queue=1)
        server.submit(_req("a", [1] * 3, tenant="t0"))
        server.submit(_req("b", [2] * 3, tenant="t1"))  # shed: queue full
        server.run()
        tn = server.stats()["tenants"]
        assert tn["t0"]["completed"] == 1
        assert tn["t0"]["ttft_p95_s"] > 0
        assert tn["t1"] == {"completed": 0, "shed": 1}

    @pytest.mark.slow
    def test_cli_policy_smoke(self):
        from mpit_tpu.serve.__main__ import main

        out = main(
            [
                "--slots", "2", "--max-len", "96", "--prefill-len", "32",
                "--kv-pages", "48", "--kv-page-size", "8",
                "--prefill-chunk", "8",
                "--policy", "on",
                "--loadgen",
                "rate=20,tenants=2,priority=0,ttft_target=5.0",
                "--duration", "0.6", "--stats-interval", "0",
            ]
        )
        assert "policy" in out
        assert out["policy"]["preemptions"] >= 0
        assert out["requests_completed"] > 0
        assert "tenants" in out
