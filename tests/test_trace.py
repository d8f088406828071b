"""ISSUE 16: request lifecycle ledger (``obs.trace``) — causal tracing,
tail-exemplar capture, why-slow forensics.

Pinned acceptance bars:

- **attribution reconciles**: the queue-wait / prefill-compute /
  decode-compute-share / parked / scheduler-gap decomposition matches
  the span-measured request latency within 5% for a chunked-prefill
  request, a preempted-and-resumed request, and a spec-decode request;
- **bounded memory**: a 500-request overload with ``exemplar_k=5``
  retains EXACTLY the slowest-5 plus breach-pinned plus
  errored/truncated ledgers — everything else drops at retire;
- **mode guarantees**: ``off`` keeps no state at all, ``aggregate``
  keeps counters but no per-request event lists (the <1% overhead bar
  is structural: there is nothing per-request to pay for);
- **compat propagation**: a trace context survives a 2-rank
  Send/Recv round trip BYTE-identically;
- **joinability**: a sentinel note / SLO breach pins the in-flight
  request set, making the anomaly and its victims one query;
- **Perfetto lifeline**: every span and ledger instant for one rid
  carries the rid attr, so one ``rid`` filter shows the whole life;
- **why-slow exit grammar**: 0 on a usable snapshot / BENCH_DETAIL,
  2 on unusable input (no ledger block, dropped events).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu import obs
from mpit_tpu.models import GPT2, GPT2Config
from mpit_tpu.obs.__main__ import main as obs_cli
from mpit_tpu.obs.stream import StreamRegistry
from mpit_tpu.obs.trace import (
    LEDGER_FORMAT,
    Ledger,
    TraceContext,
    attribute_latency,
    collect_exemplars,
    exemplar_trace_events,
    format_why_slow,
    recv_trace_context,
    send_trace_context,
)
from mpit_tpu.serve import Engine, Request, SchedulingPolicy, Server

CFG = GPT2Config.tiny(max_seq_len=128, num_layers=2)

# Spec decode needs a draft model with the SAME vocab (test_spec idiom).
SCFG = GPT2Config.tiny(
    vocab_size=64, max_seq_len=64, num_layers=2, num_heads=2, d_model=32,
    dtype=jnp.float32,
)
SDCFG = GPT2Config.tiny(
    vocab_size=64, max_seq_len=64, num_layers=1, num_heads=2, d_model=32,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def params():
    return jax.jit(GPT2(CFG).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


@pytest.fixture(scope="module")
def sparams():
    return jax.jit(GPT2(SCFG).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


@pytest.fixture(scope="module")
def sdparams():
    return jax.jit(GPT2(SDCFG).init)(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def _paged_engine(params, *, slots=2, kv_pages=16, page_size=8,
                  max_len=64, chunk=8):
    return Engine(
        CFG, params, slots=slots, max_len=max_len, prefill_len=32,
        kv_pages=kv_pages, kv_page_size=page_size, prefill_chunk=chunk,
        decode_attention="reference",
    )


@pytest.fixture(scope="module")
def paged_engine(params):
    """ONE compiled paged engine shared by every server-integration test
    (each resets it first) — per-test Engine construction recompiles the
    same steps and dominates this module's tier-1 wall otherwise."""
    return _paged_engine(params)


def _req(rid, prompt, *, new=3, priority=0, target=0.0):
    return Request(rid=rid, prompt=list(prompt), max_new_tokens=new,
                   priority=priority, ttft_target_s=target)


def _kinds(exemplar):
    return [e[0] for e in exemplar["events"]]


def _assert_reconciles(exemplar, completed=None):
    """The shared 5% acceptance bar: components sum to the measured
    latency, and the ledger's latency matches the span-measured one."""
    attr = exemplar["attribution"]
    assert attr["reconciliation_pct"] < 5.0
    for comp in obs.trace.ATTRIBUTION_COMPONENTS:
        assert attr[comp] >= 0.0
    total = sum(attr[c] for c in obs.trace.ATTRIBUTION_COMPONENTS)
    assert total == pytest.approx(attr["total_s"])
    if completed is not None:
        span_latency = completed.finish_t - completed.submit_t
        assert attr["request_latency_s"] == pytest.approx(
            span_latency, rel=0.05
        )


# ---------------------------------------------------------------------------
# Trace context: canonical serialization + compat propagation.
# ---------------------------------------------------------------------------


class TestTraceContext:
    def test_byte_identical_round_trip(self):
        ctx = TraceContext(rid="r-7", trace_id="0-00000007", origin_rank=0,
                           seq=7)
        raw = ctx.to_bytes()
        back = TraceContext.from_bytes(raw)
        assert back == ctx
        assert back.to_bytes() == raw  # canonical: re-serialize == original

    def test_rejects_foreign_format(self):
        junk = json.dumps({"format": "not-a-trace", "rid": "x"}).encode()
        with pytest.raises(ValueError, match="not a trace context"):
            TraceContext.from_bytes(junk)

    def test_two_rank_compat_round_trip_byte_identical(self):
        """THE propagation pin: rank 0 ships its context to rank 1 over
        the compat simulator (duplicated comm, dedicated tags); rank 1's
        re-serialization is byte-identical to rank 0's."""
        from mpit_tpu.compat import simulator as sim

        def rank_fn(rank):
            ctx = TraceContext(rid="r-42", trace_id="0-0000002a",
                               origin_rank=0, seq=42)
            if rank == 0:
                send_trace_context(ctx, 1)
                return ctx.to_bytes()
            got = recv_trace_context(0)
            return got.to_bytes()

        out = sim.run(rank_fn, 2, pass_rank=True)
        assert out[0] == out[1]
        assert TraceContext.from_bytes(out[1]).rid == "r-42"

    def test_ledger_assigns_collision_free_trace_ids(self):
        led = Ledger(mode="full")
        ids = [led.begin(i).trace_id for i in range(32)]
        assert len(set(ids)) == 32
        assert all(i.startswith("0-") for i in ids)


# ---------------------------------------------------------------------------
# Latency attribution (synthetic ledgers: exact arithmetic).
# ---------------------------------------------------------------------------


class TestAttribution:
    def test_simple_life_reconciles_exactly(self):
        events = [
            ("enqueue", 0.0, {}),
            ("slot_bind", 1.0, {}),
            ("prefill_chunk", 1.5, {"dur_s": 0.5}),
            ("decode_tick", 2.0, {"dur_s": 0.4}),
            ("decode_tick", 2.5, {"dur_s": 0.4}),
            ("retire", 3.0, {}),
        ]
        attr = attribute_latency(events, submit_t=0.0, retire_t=3.0)
        assert attr["queue_wait_s"] == pytest.approx(1.0)
        assert attr["prefill_compute_s"] == pytest.approx(0.5)
        assert attr["decode_compute_share_s"] == pytest.approx(0.8)
        assert attr["parked_s"] == 0.0
        # resident 2.0s, covered 1.3s -> the residual is EXPLICIT
        assert attr["scheduler_gap_s"] == pytest.approx(0.7)
        assert attr["total_s"] == pytest.approx(3.0)
        assert attr["reconciliation_pct"] == pytest.approx(0.0)

    def test_park_resume_interval_is_parked_not_gap(self):
        events = [
            ("slot_bind", 1.0, {}),
            ("preempt_park", 2.0, {}),
            ("slot_bind", 5.0, {}),
            ("decode_tick", 5.5, {"dur_s": 0.5}),
        ]
        attr = attribute_latency(events, submit_t=0.0, retire_t=6.0)
        assert attr["parked_s"] == pytest.approx(3.0)
        assert attr["queue_wait_s"] == pytest.approx(1.0)
        assert attr["scheduler_gap_s"] == pytest.approx(1.5)
        assert attr["reconciliation_pct"] == pytest.approx(0.0)

    def test_parked_at_retire_counts_until_retire(self):
        events = [("slot_bind", 1.0, {}), ("preempt_park", 2.0, {})]
        attr = attribute_latency(events, submit_t=0.0, retire_t=4.0)
        assert attr["parked_s"] == pytest.approx(2.0)

    def test_never_bound_is_pure_queue_wait(self):
        attr = attribute_latency(
            [("enqueue", 0.0, {})], submit_t=0.0, retire_t=2.0
        )
        assert attr["queue_wait_s"] == pytest.approx(2.0)
        assert attr["scheduler_gap_s"] == 0.0
        assert attr["reconciliation_pct"] == pytest.approx(0.0)


# ---------------------------------------------------------------------------
# Retention: the memory bound under overload.
# ---------------------------------------------------------------------------


def _lat(i):
    # 37 coprime to 500 -> a permutation of 1..500 ms: all distinct.
    return ((i * 37) % 500 + 1) / 1000.0


class TestRetention:
    def test_500_request_overload_retains_exactly_the_tail(self):
        """THE memory-bound pin: 500 sequential requests, k=5. Retained
        set == slowest-5 (of the unpinned, non-errored) ∪ breach-pinned
        ∪ errored/truncated, nothing else; every other ledger dropped."""
        errored = {13: "errored", 77: "truncated"}
        led = Ledger(mode="full", exemplar_k=5, window_s=1e9)
        for i in range(500):
            t0 = float(i)
            led.begin(i, t=t0)
            led.event(i, "slot_bind", t=t0 + 0.001)
            if i == 250:  # breach fires while rid 250 is in flight
                pinned = led.pin_inflight("slo_breach", step=250)
                assert pinned == ["250"]
            led.retire(
                i, t=t0 + _lat(i),
                status=errored.get(i, "completed"),
                reason="max_tokens",
            )
        competitors = [
            i for i in range(500) if i not in errored and i != 250
        ]
        slowest5 = set(
            str(i)
            for i in sorted(competitors, key=_lat, reverse=True)[:5]
        )
        expected = slowest5 | {str(i) for i in errored} | {"250"}
        retained = {e["rid"] for e in led.exemplars()}
        assert retained == expected
        assert led.stats()["exemplars_retained"] == len(expected)  # == 8
        assert led.dropped_ledgers == 500 - len(expected)
        assert led.retired == 500
        # Worst-first ordering, and each exemplar says WHY it survived.
        ex = led.exemplars()
        lats = [e["latency_s"] for e in ex]
        assert lats == sorted(lats, reverse=True)
        by_rid = {e["rid"]: e for e in ex}
        assert by_rid["13"]["retained_because"] == ["errored"]
        assert by_rid["77"]["retained_because"] == ["truncated"]
        assert by_rid["250"]["retained_because"] == ["pinned:slo_breach@250"]
        for rid in slowest5:
            assert by_rid[rid]["retained_because"] == ["slowest_k"]
        assert led.pin_events == [
            {"reason": "slo_breach", "step": 250, "rids": ["250"]}
        ]

    def test_window_rotation_keeps_k_per_window(self):
        led = Ledger(mode="full", exemplar_k=1, window_s=10.0)
        led.begin("a", t=1.0)
        led.retire("a", t=2.0)  # window 0
        led.begin("b", t=11.0)
        led.retire("b", t=12.0)  # window 1: does NOT evict a
        assert {e["rid"] for e in led.exemplars()} == {"a", "b"}

    def test_event_cap_drops_and_counts(self):
        led = Ledger(mode="full", max_events_per_request=4)
        led.begin("r", t=0.0)  # enqueue = event 1
        for i in range(10):
            led.event("r", "decode_tick", t=float(i), dur_s=0.1)
        led.retire("r", t=11.0, status="errored", reason="oom")
        (ex,) = led.exemplars()
        assert ex["n_events"] == 4
        assert ex["n_dropped_events"] == 7
        assert led.dropped_events == 7


class TestModes:
    def test_off_is_stateless(self):
        led = Ledger(mode="off")
        assert led.begin("r") is None
        led.event("r", "decode_tick")
        led.retire("r")
        s = led.stats()
        assert s["counts"] == {} and s["active"] == 0
        assert s["retired"] == 0 and led.exemplars() == []

    def test_aggregate_counts_without_per_request_state(self):
        """The structural <1% overhead bar: aggregate mode keeps NO
        per-request event lists — only the per-kind counters."""
        led = Ledger(mode="aggregate")
        ctx = led.begin("r", t=0.0)
        assert ctx is not None  # identity still assigned (propagation)
        led.event("r", "decode_tick", t=1.0, dur_s=0.1)
        led.retire("r", t=2.0)
        s = led.stats()
        assert s["counts"] == {"enqueue": 1, "decode_tick": 1}
        assert s["active"] == 0 and s["exemplars_retained"] == 0
        assert s["retired"] == 1
        assert led.exemplars() == []
        assert led.pin_inflight("slo_breach") == []

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="mode"):
            Ledger(mode="everything")


# ---------------------------------------------------------------------------
# Server integration: the three attribution-acceptance request shapes.
# ---------------------------------------------------------------------------


class TestServerLedger:
    def test_chunked_prefill_request_reconciles(self, paged_engine):
        """Acceptance shape 1: a prompt spanning 3 prefill chunks. The
        exemplar shows each chunk, and attribution reconciles within 5%
        of the span-measured latency."""
        engine = paged_engine
        engine.reset()
        led = Ledger(mode="full", exemplar_k=8)
        server = Server(engine, ledger=led)
        server.submit(_req("c", list(range(1, 21)), new=4))  # 20 toks, 3 chunks
        done = server.run()
        (ex,) = led.exemplars()
        assert ex["rid"] == "c" and ex["status"] == "completed"
        kinds = _kinds(ex)
        assert kinds.count("prefill_chunk") == 3
        assert kinds[0] == "enqueue" and kinds[-1] == "retire"
        assert "slot_bind" in kinds and "decode_tick" in kinds
        chunks = [a for k, _, a in ex["events"] if k == "prefill_chunk"]
        assert [c["chunk"] for c in chunks] == [8, 8, 4]
        _assert_reconciles(ex, done[0])
        # The causal chain is time-ordered — lifeline rendering relies
        # on it, and the t= plumbing at every seam is what pins it.
        ts = [t for _, t, _ in ex["events"]]
        assert ts == sorted(ts)

    def test_preempted_resumed_request_reconciles(self, paged_engine):
        """Acceptance shape 2: park mid-generation, resume, finish. The
        parked interval is attributed as parked_s (not gap), and the
        ledger shows park -> bind -> resume causally."""
        rng = np.random.RandomState(7)
        engine = paged_engine
        engine.reset()
        led = Ledger(mode="full", exemplar_k=8)
        server = Server(engine, policy=SchedulingPolicy(), ledger=led)
        prompt = rng.randint(0, CFG.vocab_size, size=10).tolist()
        server.submit(_req("v", prompt, new=8, priority=1))
        server.run(max_ticks=6)
        assert server.live
        server._preempt(next(iter(server.live)))
        done = server.run()
        assert len(done) == 1
        (ex,) = [e for e in led.exemplars() if e["rid"] == "v"]
        kinds = _kinds(ex)
        assert kinds.count("slot_bind") == 2
        assert "preempt_park" in kinds and "preempt_resume" in kinds
        assert kinds.index("preempt_park") < kinds.index("preempt_resume")
        park = next(a for k, _, a in ex["events"] if k == "preempt_park")
        assert park["generated"] > 0 and park["pages_freed"] > 0
        assert ex["attribution"]["parked_s"] > 0.0
        _assert_reconciles(ex, done[0])

    @pytest.mark.slow
    def test_spec_decode_request_reconciles(self, sparams, sdparams):
        """Acceptance shape 3: speculative decode. Ticks land as
        spec_tick events carrying drafted/accepted/emitted counts and
        the attribution still reconciles."""
        engine = Engine(
            SCFG, sparams, slots=2, max_len=40, kv_page_size=8, prefill_len=8,
            spec_k=2, draft_params=sdparams, draft_cfg=SDCFG,
        )
        led = Ledger(mode="full", exemplar_k=8)
        server = Server(engine, ledger=led)
        server.submit(_req("s", [5, 9, 3], new=6))
        done = server.run()
        (ex,) = led.exemplars()
        kinds = _kinds(ex)
        assert "spec_tick" in kinds and "decode_tick" not in kinds
        specs = [a for k, _, a in ex["events"] if k == "spec_tick"]
        assert all(s["drafted"] == 2 for s in specs)
        # Prefill emits the first token; spec ticks account for the rest.
        assert sum(s["emitted"] for s in specs) == len(done[0].tokens) - 1
        assert all(0 <= s["accepted"] <= s["drafted"] for s in specs)
        _assert_reconciles(ex, done[0])

    def test_admission_verdict_carries_projection_inputs(self, paged_engine):
        """The admission event records the verdict AND the projected-TTFT
        inputs that produced it — the ledger answers 'why was this
        admitted/shed', not just 'that it was'."""
        engine = paged_engine
        engine.reset()
        led = Ledger(mode="full", exemplar_k=8)
        server = Server(engine, policy=SchedulingPolicy(), ledger=led)
        server.submit(_req("a", [1, 2, 3], new=2, target=5.0))
        server.run()
        (ex,) = led.exemplars()
        adm = next(a for k, _, a in ex["events"] if k == "admission")
        assert adm["verdict"] in ("admit", "abstain_cold")
        for key in ("queue_depth", "ttft_target_s", "admission_factor",
                    "proj_ttft_s"):
            assert key in adm
        assert adm["ttft_target_s"] == pytest.approx(5.0)

    def test_queue_full_shed_is_a_retired_ledger(self, paged_engine):
        """A shed request's ledger closes with status='shed' and the
        reason — the why-slow story covers requests that never ran."""
        engine = paged_engine
        engine.reset()
        led = Ledger(mode="full", exemplar_k=8)
        server = Server(engine, max_queue=1, ledger=led)
        assert server.submit(_req("a", [1, 2], new=2))
        assert not server.submit(_req("b", [3, 4], new=2))
        shed = next(e for e in led.exemplars() if e["rid"] == "b")
        assert shed["status"] == "shed"
        assert shed["retire_reason"] == "queue_full"
        assert _kinds(shed) == ["enqueue", "shed"]
        assert led.counts["shed"] == 1
        server.run()

    def test_stats_surfaces_exemplars_and_ledger(self, paged_engine):
        engine = paged_engine
        engine.reset()
        led = Ledger(mode="full", exemplar_k=8)
        server = Server(engine, ledger=led)
        server.submit(_req("a", [1, 2, 3], new=2))
        server.run()
        stats = server.stats()
        assert stats["exemplars"][0]["rid"] == "a"
        assert stats["ledger"]["mode"] == "full"
        assert stats["ledger"]["retired"] == 1

    def test_no_ledger_server_unchanged(self, paged_engine):
        """ledger=None is the zero-cost arm: stats has no exemplar
        surface and the run completes as before."""
        engine = paged_engine
        engine.reset()
        server = Server(engine)
        server.submit(_req("a", [1, 2, 3], new=2))
        done = server.run()
        assert len(done) == 1
        assert "exemplars" not in server.stats()


# ---------------------------------------------------------------------------
# Pin joinability: sentinel notes and SLO breaches.
# ---------------------------------------------------------------------------


class TestPinJoinability:
    def test_sentinel_note_pins_inflight_set(self, paged_engine):
        """Satellite: Sentinel(on_note=...) — an anomaly note pins every
        in-flight request, so the anomaly and its victims are joinable
        from either side."""
        engine = paged_engine
        engine.reset()
        led = Ledger(mode="full", exemplar_k=1)
        sent = obs.Sentinel(phases=("decode", "prefill"))
        server = Server(engine, sentinel=sent, ledger=led)
        server.submit(_req("fast", [1, 2], new=1))
        server.submit(_req("victim", [3, 4, 5], new=6))
        server.run(max_ticks=3)
        assert "victim" in {lv.req.rid for lv in server.live.values()}
        sent.note("latency_spike", "decode", 3)
        server.run()
        assert led.pin_events[0]["reason"] == "latency_spike"
        assert "victim" in led.pin_events[0]["rids"]
        pinned = next(e for e in led.exemplars() if e["rid"] == "victim")
        assert any(
            w.startswith("pinned:latency_spike")
            for w in pinned["retained_because"]
        )

    def test_on_note_chain_preserves_existing_callback(self):
        seen = []
        sent = obs.Sentinel(phases=("decode",), on_note=seen.append)
        led = Ledger(mode="full")
        engine_free_pin = led.pin_inflight  # wire manually, no server
        prev = sent.on_note

        def chained(record):
            prev(record)
            engine_free_pin(record["kind"], step=record["step"])

        sent.on_note = chained
        led.begin("r", t=0.0)
        sent.note("anomaly", "decode", 7)
        assert seen and seen[0]["kind"] == "anomaly"
        assert led.pin_events[0] == {
            "reason": "anomaly", "step": 7, "rids": ["r"],
        }

    def test_slo_breach_without_sentinel_pins_via_transitions(self, paged_engine):
        """No sentinel wired: _run_tick pins from the monitor's returned
        transitions directly (never both paths — no double pin)."""

        class _BreachOnce:
            sentinel = None

            def __init__(self):
                self.fired = False

            def evaluate(self, now=None, tick=0):
                if not self.fired and tick >= 1:
                    self.fired = True
                    return [{"event": "slo_breach", "slo": "ttft_p95"}]
                return []

            def finish(self):
                return []

        engine = paged_engine
        engine.reset()
        led = Ledger(mode="full", exemplar_k=1)
        server = Server(
            engine, slo=_BreachOnce(), stream=StreamRegistry(), ledger=led
        )
        server.submit(_req("r", [1, 2, 3], new=4))
        server.run()
        assert len(led.pin_events) == 1
        assert led.pin_events[0]["reason"] == "slo_breach"
        assert led.pin_events[0]["rids"] == ["r"]

    def test_pinned_inflight_surfaces_before_retire(self):
        """A pinned request that hasn't retired still shows up in
        exemplars() as in_flight — breach forensics can't wait."""
        led = Ledger(mode="full", clock=lambda: 10.0)
        led.begin("r", t=0.0)
        led.pin_inflight("slo_breach", step=3)
        (ex,) = led.exemplars()
        assert ex["status"] == "in_flight"
        assert ex["retained_because"] == ["pinned:slo_breach@3"]
        assert ex["latency_s"] == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# Perfetto rid lifeline (satellite 3).
# ---------------------------------------------------------------------------


class TestPerfettoLifeline:
    def test_rid_filter_shows_whole_life(self, paged_engine, tmp_path):
        """One rid filter in the exported trace surfaces the request's
        spans AND its ledger instants: the lifeline is one lane."""
        engine = paged_engine
        engine.reset()
        led = Ledger(mode="full", exemplar_k=8)
        server = Server(engine, ledger=led)
        rec = obs.Recorder()
        with obs.local_recorder(rec):
            server.submit(_req("x", list(range(1, 13)), new=3))
            server.run()
        (ex,) = led.exemplars()
        path = tmp_path / "trace.json"
        obs.export_chrome_trace(
            path, rec, extra_events=exemplar_trace_events(ex, tid=99)
        )
        doc = json.loads(path.read_text())
        mine = [
            e for e in doc["traceEvents"]
            if e.get("args", {}).get("rid") == "x"
        ]
        names = {e["name"] for e in mine}
        # The request-scoped spans the serve loop already emitted...
        assert {"queue_wait", "request_ttft", "request_latency"} <= names
        # ...plus one ledger instant per retained event, same lane key.
        ledger_instants = [e for e in mine if e["name"].startswith("ledger:")]
        assert len(ledger_instants) == len(ex["events"])
        assert {e["name"] for e in ledger_instants} == {
            f"ledger:{k}" for k in _kinds(ex)
        }
        for e in ledger_instants:
            assert e["ph"] == "i" and e["cat"] == "ledger"
            assert e["args"]["trace_id"] == ex["trace_id"]
            assert e["tid"] == 99

    def test_instant_timestamps_track_event_order(self):
        ex = {
            "rid": "r", "trace_id": "0-01", "submit_t": 2.0,
            "events": [["enqueue", 0.0, {}], ["retire", 1.5, {"reason": "eos"}]],
        }
        rows = exemplar_trace_events(ex)
        assert [r["ts"] for r in rows] == [2.0e6, 3.5e6]
        assert rows[1]["args"]["reason"] == "eos"


# ---------------------------------------------------------------------------
# why-slow CLI exit grammar (exit 0 usable / exit 2 unusable).
# ---------------------------------------------------------------------------


def _snapshot_with_one_exemplar():
    led = Ledger(mode="full", exemplar_k=2)
    led.begin("slow", t=0.0)
    led.event("slow", "slot_bind", t=0.5)
    led.event("slow", "decode_tick", t=1.0, dur_s=0.4)
    led.retire("slow", t=2.0)
    return led.snapshot()


class TestWhySlowCLI:
    def test_exit_0_on_snapshot(self, tmp_path, capsys):
        p = tmp_path / "snap.json"
        p.write_text(json.dumps(_snapshot_with_one_exemplar()))
        assert obs_cli(["why-slow", str(p)]) == 0
        out = capsys.readouterr().out
        assert "why-slow: rid=slow" in out
        assert "queue_wait_s" in out and "lifeline:" in out

    def test_exit_0_on_bench_detail_shape(self, tmp_path):
        doc = {"workloads": {
            "gpt2_serve": {"trace_forensics": _snapshot_with_one_exemplar()},
            "allreduce": {"bytes": 123},
        }}
        p = tmp_path / "BENCH_DETAIL.json"
        p.write_text(json.dumps(doc))
        assert obs_cli(["why-slow", str(p)]) == 0

    def test_exit_2_on_dropped_events(self, tmp_path, capsys):
        snap = _snapshot_with_one_exemplar()
        snap["dropped_events"] = 3
        p = tmp_path / "snap.json"
        p.write_text(json.dumps(snap))
        assert obs_cli(["why-slow", str(p)]) == 2
        assert "dropped" in capsys.readouterr().out

    def test_exit_2_on_no_ledger_block(self, tmp_path):
        p = tmp_path / "other.json"
        p.write_text(json.dumps({"workloads": {"allreduce": {"bytes": 1}}}))
        assert obs_cli(["why-slow", str(p)]) == 2

    def test_exit_2_on_zero_exemplars(self, tmp_path):
        led = Ledger(mode="full", exemplar_k=1)
        p = tmp_path / "empty.json"
        p.write_text(json.dumps(led.snapshot()))
        assert obs_cli(["why-slow", str(p)]) == 2

    def test_exit_2_on_unreadable_input(self, tmp_path):
        assert obs_cli(["why-slow", str(tmp_path / "missing.json")]) == 2

    def test_top_prints_multiple(self, tmp_path, capsys):
        led = Ledger(mode="full", exemplar_k=4)
        for i, lat in enumerate([2.0, 1.0]):
            led.begin(i, t=0.0)
            led.retire(i, t=lat)
        p = tmp_path / "snap.json"
        p.write_text(json.dumps(led.snapshot()))
        assert obs_cli(["why-slow", str(p), "--top", "2"]) == 0
        out = capsys.readouterr().out
        assert out.count("why-slow: rid=") == 2
        assert out.index("rid=0") < out.index("rid=1")  # worst first

    def test_format_why_slow_renders_attribution_table(self):
        snap = _snapshot_with_one_exemplar()
        text = format_why_slow(snap["exemplars"][0])
        for comp in obs.trace.ATTRIBUTION_COMPONENTS:
            assert comp in text
        assert "reconciles within" in text
        exemplars, err = collect_exemplars(snap)
        assert err is None and exemplars[0]["rid"] == "slow"
