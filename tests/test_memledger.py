"""ISSUE 18 acceptance: the HBM memory ledger.

- byte-exact conservation: ``grants − frees == held`` holds EXACTLY —
  per subsystem and total — after every tick of the full lifecycle
  matrix (paged admission + COW divergence + prefix share + preempt
  park/resume + spec decode + int8 weight store), and a retired cohort
  returns the KV line exactly to its pre-admission baseline (the leak
  pin);
- exhaustion forensics: a refused admit leaves a ranked top-holders
  dump on the ledger (and the refused head's causal event carries the
  headroom that refused it); a bounded-intake shed is annotated the
  same way;
- eviction candidates: parked victims and sole-reader shared prefixes
  rank coldest-first by last-touch tick in ``Server.stats()``;
- the ``obs capacity`` CLI exit grammar (0 verdict / 2 no ledger data)
  and the ``obs diff`` memory gate (peak-held growth trips, absent
  ledger data never gates vacuously);
- reconciliation honesty: off-TPU reports carry the platform label and
  ledger-modeled bytes, never fabricated device numbers.

Wall discipline: ONE compiled engine with int8 weights + ONE spec
engine (a pool for every slot) for the whole module, reset per test (the test_trace
idiom).
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

from mpit_tpu.models import GPT2, GPT2Config
from mpit_tpu.obs import memledger as ml_mod
from mpit_tpu.obs import baseline
from mpit_tpu.obs.memledger import (
    MEMLEDGER_FORMAT,
    MemLedger,
    capacity_report,
    format_capacity,
)
from mpit_tpu.obs.__main__ import main as obs_cli
from mpit_tpu.serve import Engine, Request, SchedulingPolicy, Server
from mpit_tpu.serve.weights import params_wire_bytes

CFG = GPT2Config.tiny(max_seq_len=128, num_layers=2)
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
def paged_engine(params):
    """ONE compiled paged engine — int8 weight store, 3 slots so the
    exhaustion tests can hit "slot free, pages gone", small chunk so
    prefix shares cross chunk boundaries."""
    return Engine(
        CFG, params, slots=3, max_len=64, prefill_len=32,
        kv_pages=16, kv_page_size=8, prefill_chunk=8,
        weights_dtype="int8", decode_attention="reference",
    )


@pytest.fixture(scope="module")
def spec_engine():
    """ONE spec engine, a pool for every slot (separate draft
    checkpoint — its weights are a REAL second store, not an alias)."""
    sparams = jax.jit(GPT2(SCFG).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    sdparams = jax.jit(GPT2(SDCFG).init)(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return Engine(
        SCFG, sparams, slots=2, max_len=40, kv_page_size=8, prefill_len=8,
        spec_k=2, draft_params=sdparams, draft_cfg=SDCFG,
    )


def _req(rid, prompt, *, new=3, priority=0, tenant="", target=0.0):
    return Request(rid=rid, prompt=list(prompt), max_new_tokens=new,
                   priority=priority, tenant=tenant, ttft_target_s=target)


def _drain_checked(server):
    """Drive the server to completion ONE tick at a time, asserting
    the conservation invariant after every tick — "after each e2e
    run" is easy; per-tick is the real pin."""
    while server._pending():
        server._run_tick()
        _assert_conserved(server.engine)
    return server.completed


def _assert_conserved(engine):
    """The tentpole invariant, checked from BOTH sides: the ledger's
    own arithmetic (granted − freed == held, exact) AND the ledger
    against allocator ground truth (held == physical pages × page
    bytes, bitwise)."""
    ml = engine.memledger
    con = ml.conservation()
    assert con["ok"], con
    for name, sub in con["subsystems"].items():
        assert sub["granted_bytes"] - sub["freed_bytes"] == (
            sub["held_bytes"]
        ), (name, sub)
    if getattr(engine, "page_bytes", 0):
        alloc = engine.allocator
        assert ml.held("kv_pages") == alloc.pages_in_use * engine.page_bytes
        assert ml.held("kv_cow_reserve") == (
            alloc.reserved * engine.page_bytes
        )


# ---------------------------------------------------------------------------
# Unit: the ledger object alone (no engine, no jax arrays).
# ---------------------------------------------------------------------------


class TestMemLedgerUnit:
    def test_grant_free_conservation_exact(self):
        ml = MemLedger(platform="cpu")
        ml.register("pool", capacity_bytes=1000)
        ml.grant("pool", 300)
        ml.grant("pool", 200)
        ml.free("pool", 300)
        assert ml.held("pool") == 200
        assert ml.headroom("pool") == 800
        con = ml.conservation()
        assert con["ok"] and con["subsystems"]["pool"]["ok"]
        assert con["subsystems"]["pool"]["granted_bytes"] == 500
        assert con["subsystems"]["pool"]["freed_bytes"] == 300

    def test_over_free_breaks_conservation_loudly(self):
        """No clamping: an over-free goes NEGATIVE and the verdict
        names the violator — silent clamping would hide exactly the
        instrumentation bug conservation exists to catch."""
        ml = MemLedger()
        ml.grant("pool", 100)
        ml.free("pool", 150)
        assert ml.held("pool") == -50
        con = ml.conservation()
        assert not con["subsystems"]["pool"]["ok"]
        assert not con["ok"]

    def test_nested_subsystem_decomposes_without_double_count(self):
        ml = MemLedger()
        ml.grant("kv_pool", 1000)
        ml.register("kv_pages", capacity_bytes=800, nested_in="kv_pool")
        ml.grant("kv_pages", 600)
        assert ml.held() == 1000  # nested view, not additional memory
        assert ml.decompose() == {"kv_pages": 600, "kv_pool": 1000}
        snap = ml.snapshot()
        assert snap["subsystems"]["kv_pages"]["nested_in"] == "kv_pool"

    def test_headroom_none_without_declared_capacity(self):
        ml = MemLedger()
        ml.grant("pool", 10)
        assert ml.headroom("pool") is None

    def test_owner_recency_touch_forget(self):
        ml = MemLedger()
        ml.grant("kv", 64, owner="r1", tenant="acme", tick=3)
        ml.touch("r1", tick=9)
        ml.touch("r1", tick=5)  # stale touch never rewinds recency
        assert ml.owners()["r1"]["last_touch"] == 9
        ml.forget("r1")
        assert "r1" not in ml.owners()

    def test_reset_transients_keeps_byte_accumulators(self):
        ml = MemLedger()
        ml.grant("pool", 100, owner="r1", tick=1)
        ml.note_exhaustion({"tick": 1})
        ml.reset_transients()
        assert ml.owners() == {}
        assert "exhaustion" not in ml.snapshot()
        assert ml.held("pool") == 100  # bytes survive: still held

    def test_watermark_tracks_peak(self):
        ml = MemLedger()
        ml.grant("pool", 500, tick=1)
        ml.free("pool", 400, tick=2)
        ml.grant("pool", 100, tick=3)
        wm = ml.watermark()
        assert wm["held_peak_bytes"] == 500 and wm["tick"] == 1
        assert wm["subsystems"]["pool"] == 500

    def test_reconcile_off_tpu_never_fabricates_device_bytes(self):
        """The roofline honesty rule: a cpu-platform ledger reports
        modeled bytes + platform label even when handed a device
        object that WOULD answer memory_stats()."""

        class FakeDev:
            def memory_stats(self):
                return {"bytes_in_use": 999}

        ml = MemLedger(platform="cpu")
        ml.grant("pool", 100)
        rec = ml.reconcile(FakeDev())
        assert rec["platform"] == "cpu"
        assert rec["ledger_bytes"] == 100
        assert rec["device_bytes"] is None
        assert rec["within_tolerance"] is None

    def test_reconcile_on_tpu_compares_within_tolerance(self):
        class FakeDev:
            def memory_stats(self):
                return {"bytes_in_use": 105}

        ml = MemLedger(platform="tpu")
        ml.grant("pool", 100)
        rec = ml.reconcile(FakeDev(), tolerance_pct=10.0)
        assert rec["device_bytes"] == 105
        assert rec["within_tolerance"] is True
        rec = ml.reconcile(FakeDev(), tolerance_pct=1.0)
        assert rec["within_tolerance"] is False

    def test_snapshot_format_and_exhaustion_retained(self):
        ml = MemLedger(platform="cpu")
        ml.grant("pool", 100)
        ml.note_exhaustion({"tick": 7, "top_holders": []})
        snap = ml.snapshot()
        assert snap["format"] == MEMLEDGER_FORMAT
        assert snap["exhaustion"]["tick"] == 7
        assert snap["exhaustions"] == 1
        json.dumps(snap)  # serializable as-is


# ---------------------------------------------------------------------------
# Offline verdicts: capacity_report + the CLI exit grammar.
# ---------------------------------------------------------------------------


class TestCapacityVerdict:
    def _snap(self):
        ml = MemLedger(platform="cpu")
        ml.register("kv_pages", capacity_bytes=800, nested_in="kv_pool")
        ml.grant("kv_pool", 1000)
        ml.grant("kv_pages", 600)
        ml.grant("weights", 5000)
        return ml.snapshot()

    def test_report_from_raw_snapshot(self):
        rep = capacity_report(self._snap())
        assert rep["held_bytes"] == 6000
        assert rep["kv_capacity_bytes"] == 800
        assert rep["kv_headroom_bytes"] == 200
        assert rep["conservation_ok"]
        text = format_capacity(rep)
        assert "conservation: ok" in text and "weights" in text

    def test_report_names_host_tier_and_pressure(self):
        """ISSUE 20: a tiered stats block yields a host line, tiered
        eviction candidates, and an exhaustion verdict naming whether
        pressure is HBM-only or both tiers; a pre-tiering snapshot
        (``_snap``) keeps reporting with no host line at all."""
        mem = {
            "source": "memledger", "platform": "cpu",
            "held_bytes": 6000, "held_peak_bytes": 6000,
            "held_by_subsystem": {"kv_pages": 600, "weights": 5000},
            "conservation": {"ok": True},
            "kv_capacity_bytes": 800,
            "host_held_bytes": 4096, "host_capacity_bytes": 8192,
            "host_held_peak_bytes": 6144,
            "eviction_candidates": [
                {"kind": "host_prefix", "key": "prefix[16t]",
                 "bytes": 4096, "last_touch_tick": 3, "tier": "host"},
            ],
            "exhaustion": {"tick": 9, "kv_headroom_bytes": 0,
                           "tier_pressure": "both_tiers"},
        }
        rep = capacity_report({"memory": mem})
        assert rep["host_held_bytes"] == 4096
        assert rep["host_capacity_bytes"] == 8192
        assert rep["host_held_peak_bytes"] == 6144
        text = format_capacity(rep)
        assert "host tier held 4.0KiB of 8.0KiB (50.0%)" in text
        assert "tier=host" in text
        assert "pressure=both_tiers" in text
        # Pre-tiering snapshot: no host subsystem, no host line.
        pre = capacity_report(self._snap())
        assert "host_held_bytes" not in pre
        assert "host tier" not in format_capacity(pre)

    def test_report_refuses_docs_without_ledger_data(self):
        with pytest.raises(ValueError):
            capacity_report({"phases": {}})
        with pytest.raises(ValueError):
            capacity_report({"workloads": {"alexnet": {}}})

    def test_cli_exit_0_on_snapshot_2_without_ledger(self, tmp_path,
                                                     capsys):
        good = tmp_path / "snap.json"
        good.write_text(json.dumps(self._snap()))
        assert obs_cli(["capacity", str(good)]) == 0
        assert "capacity verdict" in capsys.readouterr().out
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"workloads": {"alexnet": {}}}))
        assert obs_cli(["capacity", str(bad)]) == 2
        assert "error" in capsys.readouterr().out


class TestBaselineMemoryGate:
    def _snap(self, peak, headroom_min=40.0):
        s = baseline.snapshot(
            {"phases": {"decode": {"count": 1, "total_s": 1.0,
                                   "p50_s": 1.0, "p95_s": 1.0}}},
            memory={"held_peak_bytes": peak,
                    "kv_headroom_min_pct": headroom_min,
                    "platform": "cpu"},
        )
        return s

    def test_peak_growth_beyond_tolerance_trips_gate(self):
        verdict = baseline.diff(
            self._snap(1000), self._snap(1300), tolerance_pct=10.0
        )
        assert not verdict["ok"]
        assert verdict["memory_regressions"] == ["memory.held_peak_bytes"]
        assert verdict["memory"]["held_peak_bytes"]["growth_pct"] == 30.0

    def test_growth_within_tolerance_passes_and_reports(self):
        verdict = baseline.diff(
            self._snap(1000, 40.0), self._snap(1050, 35.0),
            tolerance_pct=10.0,
        )
        assert verdict["ok"] and verdict["memory_regressions"] == []
        assert verdict["memory"]["kv_headroom_min_pct"]["cur"] == 35.0

    def test_snapshot_without_ledger_data_never_gates_vacuously(self):
        """A pre-ledger baseline (no memory section) diffs clean on the
        memory dimension — no section, no vacuous verdict."""
        bare = baseline.snapshot(
            {"phases": {"decode": {"count": 1, "total_s": 1.0,
                                   "p50_s": 1.0, "p95_s": 1.0}}}
        )
        assert "memory" not in bare
        verdict = baseline.diff(bare, self._snap(99999999))
        assert verdict["ok"] and "memory" not in verdict

    def test_snapshot_drops_non_numeric_memory_blocks(self):
        s = baseline.snapshot(
            {"phases": {}}, memory={"held_peak_bytes": None}
        )
        assert "memory" not in s

    # -- host-tier keys (ISSUE 20) ---------------------------------------
    def _host_snap(self, peak, host_peak, restream=4096):
        return baseline.snapshot(
            {"phases": {"decode": {"count": 1, "total_s": 1.0,
                                   "p50_s": 1.0, "p95_s": 1.0}}},
            memory={"held_peak_bytes": peak, "platform": "cpu",
                    "host_held_peak_bytes": host_peak,
                    "restream_bytes": restream},
        )

    def test_host_peak_growth_beyond_tolerance_trips_gate(self):
        """Host-tier peak growth is a spill leak — granted at dispatch,
        never released — and gates exactly like the HBM peak."""
        verdict = baseline.diff(
            self._host_snap(1000, 2000), self._host_snap(1000, 2600),
            tolerance_pct=10.0,
        )
        assert not verdict["ok"]
        assert verdict["memory_regressions"] == [
            "memory.host_held_peak_bytes"
        ]
        assert verdict["memory"]["host_held_peak_bytes"][
            "growth_pct"] == 30.0
        # restream bytes ride along as context, never gate.
        assert verdict["memory"]["restream_bytes"] == {
            "base": 4096, "cur": 4096,
        }

    def test_pre_tiering_baseline_never_gates_host_keys(self):
        """A pre-ISSUE-20 baseline has no host keys: the diff must not
        manufacture a host verdict from one side (the HBM keys' own
        never-gate-vacuously rule, extended)."""
        verdict = baseline.diff(
            self._snap(1000), self._host_snap(1000, 99999999),
            tolerance_pct=10.0,
        )
        assert verdict["ok"]
        assert "host_held_peak_bytes" not in verdict.get("memory", {})
        # And a zero-peak base (tiering on, nothing ever spilled)
        # stays ungated too — growth from 0 is undefined, not infinite.
        verdict = baseline.diff(
            self._host_snap(1000, 0), self._host_snap(1000, 8192),
            tolerance_pct=10.0,
        )
        assert verdict["ok"]


# ---------------------------------------------------------------------------
# The serve stack: conservation across the lifecycle matrix.
# ---------------------------------------------------------------------------


class TestServeConservation:
    def test_weight_store_bytes_exact_int8(self, paged_engine):
        """The int8 weight store's ledger line equals the shared wire
        sizing rule over the quantized tree, bitwise — scale blocks
        included."""
        ml = paged_engine.memledger
        assert ml.held("weights") == params_wire_bytes(paged_engine.params)
        assert ml.held("weights") > 0

    def test_conservation_every_tick_with_cow_and_prefix_share(
        self, paged_engine
    ):
        """The matrix core: cold admit, prefix share (B extends A's
        registered prompt while A is live), COW divergence on the
        shared partial page, retirement — conservation checked after
        EVERY tick, and the retired cohort returns kv bytes exactly to
        the pre-admission baseline (the leak pin)."""
        engine = paged_engine
        engine.reset()
        ml = engine.memledger
        base_held = ml.held()
        assert ml.held("kv_pages") == 0
        server = Server(engine)
        prompt = list(range(1, 11))  # 10 tokens: partial last page
        server.submit(_req("a", prompt, new=8, tenant="acme"))
        server.run(max_ticks=3)  # prefill done, prefixes registered
        server.submit(_req("b", prompt + [11, 12], new=6, tenant="beta"))
        done = _drain_checked(server)
        assert {c.rid for c in done} == {"a", "b"}
        assert engine.allocator.prefix_hits >= 1  # b shared a's pages
        assert engine.allocator.cow_copies >= 1  # divergence copied
        _assert_conserved(engine)
        # Leak pin: everything the cohort held came back, exactly.
        assert ml.held("kv_pages") == 0
        assert ml.held("kv_cow_reserve") == 0
        assert ml.held() == base_held

    def test_preempt_park_resume_conserves_and_ranks_victim(
        self, paged_engine
    ):
        """Preemption parks a victim (pages freed -> ledger frees),
        resume re-admits (re-grant); while parked the victim shows up
        as the COLDEST eviction candidate with its projected
        re-admission claim."""
        engine = paged_engine
        engine.reset()
        ml = engine.memledger
        server = Server(engine, policy=SchedulingPolicy())
        server.submit(_req("v", list(range(1, 11)), new=8, priority=1,
                           tenant="acme"))
        server.run(max_ticks=6)
        assert server.live
        server._preempt(next(iter(server.live)))
        _assert_conserved(engine)
        mem = server.stats()["memory"]
        kinds = [c["kind"] for c in mem["eviction_candidates"]]
        assert "parked_victim" in kinds
        victim = next(c for c in mem["eviction_candidates"]
                      if c["kind"] == "parked_victim")
        assert victim["rid"] == "v" and victim["bytes"] > 0
        ticks = [c["last_touch_tick"] for c in mem["eviction_candidates"]]
        assert ticks == sorted(ticks)  # coldest first
        done = _drain_checked(server)
        assert len(done) == 1 and server.policy.resumes == 1
        assert ml.held("kv_pages") == 0

    def test_sole_reader_prefix_ranks_while_registrant_lives(
        self, paged_engine
    ):
        """A live request's registered prefixes are refcount-1 — the
        sole-reader entries an eviction policy could reclaim by
        retiring one idle mapper."""
        engine = paged_engine
        engine.reset()
        server = Server(engine)
        server.submit(_req("a", list(range(1, 18)), new=12))
        server.run(max_ticks=8)  # prefilled + registered, still live
        assert server.live
        mem = server.stats()["memory"]
        sole = [c for c in mem["eviction_candidates"]
                if c["kind"] == "sole_reader_prefix"]
        assert sole and all(c["bytes"] > 0 for c in sole)
        assert mem["per_request"]["a"]["bytes"] > 0
        assert mem["per_tenant"][""] == mem["per_request"]["a"]["bytes"]
        server.run()

    def test_memory_stats_attribution_matches_ledger(self, paged_engine):
        """Cross-check identity: per-request exclusive bytes + distinct
        shared-page bytes == the kv_pages ledger line, exactly."""
        engine = paged_engine
        engine.reset()
        server = Server(engine)
        prompt = list(range(1, 11))
        server.submit(_req("a", prompt, new=10, tenant="acme"))
        server.run(max_ticks=3)
        server.submit(_req("b", prompt + [11], new=8, tenant="beta"))
        server.run(max_ticks=3)
        mem = server.stats()["memory"]
        exclusive = sum(e["bytes"] for e in mem["per_request"].values())
        assert exclusive + mem["shared_bytes"] == (
            engine.memledger.held("kv_pages")
        )
        assert mem["conservation"]["ok"]
        assert mem["reconciliation"]["platform"] != "tpu"
        assert mem["reconciliation"]["device_bytes"] is None
        server.run()


class TestExhaustionForensics:
    def test_exhaustion_dump_ranks_holders_and_carries_headroom(
        self, paged_engine
    ):
        """Pool exhausted with a slot free: the ledger retains the
        ranked top-holders dump, and the refused head's admit_blocked
        event carries the headroom numbers that refused it."""
        from mpit_tpu.obs.trace import Ledger

        engine = paged_engine
        engine.reset()
        led = Ledger(mode="full", exemplar_k=8)
        server = Server(engine, ledger=led)
        big = list(range(1, 31))  # 30 + 20 - 1 -> 7 pages of 16
        server.submit(_req("h1", big, new=20, tenant="acme"))
        server.submit(_req("h2", big[::-1], new=20, tenant="acme"))
        server.submit(_req("h3", list(range(31, 61)), new=20,
                           tenant="beta"))
        server.run(max_ticks=4)  # h1/h2 hold 14 pages; h3 blocked
        snap = engine.memledger.snapshot()
        assert snap["exhaustions"] >= 1
        dump = snap["exhaustion"]
        assert dump["free_pages"] == 2 and dump["queued"] == 1
        holders = dump["top_holders"]
        assert {h["rid"] for h in holders} == {"h1", "h2"}
        bys = [h["bytes"] for h in holders]
        assert bys == sorted(bys, reverse=True) and bys[0] > 0
        assert dump["tenants"]["acme"] == sum(bys)
        assert "kv_headroom_bytes" in dump and "subsystems" in dump
        headroom_then = 2 * engine.page_bytes
        server.run()  # h1/h2 retire; h3 admits and finishes
        _assert_conserved(engine)
        ex = next(e for e in led.exemplars() if e["rid"] == "h3")
        blocked = next(a for k, _, a in ex["events"]
                       if k == "admit_blocked")
        assert blocked["need_pages"] == 7
        assert blocked["kv_headroom_bytes"] == headroom_then

    def test_queue_full_shed_annotated_with_headroom(self, paged_engine):
        from mpit_tpu.obs.trace import Ledger

        engine = paged_engine
        engine.reset()
        led = Ledger(mode="full", exemplar_k=8)
        server = Server(engine, max_queue=1, ledger=led)
        server.submit(_req("s1", list(range(1, 31)), new=20))
        server.submit(_req("s2", list(range(31, 61)), new=20))
        server.submit(_req("s3", list(range(61, 91)), new=20))
        server.run(max_ticks=2)
        assert server.shed_causes.get("queue_full", 0) >= 1
        ex = next(e for e in led.exemplars() if e["status"] == "shed")
        shed = next(a for k, _, a in ex["events"] if k == "shed")
        assert "kv_headroom_bytes" in shed and "hbm_held_bytes" in shed
        server.run()


class TestSpecAndReset:
    def test_spec_engine_conserves_with_separate_draft_store(
        self, spec_engine
    ):
        """Spec decode (separate draft checkpoint): the draft weights
        are a REAL second ledger line, the kv_pool line covers target +
        draft pools, kv_pages grants/frees conserve across
        accept/rollback, and retirement returns the pages."""
        engine = spec_engine
        engine.reset()
        ml = engine.memledger
        assert ml.held("draft_weights") > 0  # no alias: separate bytes
        assert ml.held("draft_weights") < ml.held("weights")
        server = Server(engine)
        server.submit(_req("s1", [5, 9, 3], new=6))
        server.submit(_req("s2", [7, 2], new=5))
        done = _drain_checked(server)
        assert len(done) == 2
        assert server.stats()["spec_accepted_tokens"] >= 0
        _assert_conserved(engine)
        pages = ml.conservation()["subsystems"]["kv_pages"]
        assert pages["granted_bytes"] > 0 and pages["held_bytes"] == 0

    def test_memory_stats_block_of_the_default_pool(self, spec_engine):
        """No ``kv_pages`` given: the pool holds every slot at max_len,
        a page's bytes count the draft pool too, and a live request
        holds the pages of its prompt + budget."""
        engine = spec_engine
        engine.reset()
        assert engine.num_pages == 2 * (40 // 8)
        server = Server(engine)
        server.submit(_req("d1", [5, 9, 3], new=12))
        server.run(max_ticks=2)
        assert server.live  # still decoding: the page grant is held
        held = 2 * engine.page_bytes  # 15 positions in pages of 8
        mem = server.stats()["memory"]
        assert mem["source"] == "memledger"
        assert mem["held_by_subsystem"]["kv_pages"] == held
        assert mem["kv_capacity_bytes"] == engine.num_pages * engine.page_bytes
        assert mem["per_request"]["d1"]["bytes"] == held
        server.run()
        assert engine.memledger.held("kv_pages") == 0

    def test_engine_reset_returns_every_kv_byte(self, paged_engine):
        """reset() mid-flight conserves: live slots' pages are freed
        through the ledger, not orphaned."""
        engine = paged_engine
        engine.reset()
        server = Server(engine)
        server.submit(_req("r1", list(range(1, 11)), new=10))
        server.run(max_ticks=4)
        assert engine.memledger.held("kv_pages") > 0
        engine.reset()
        assert engine.memledger.held("kv_pages") == 0
        assert engine.memledger.held("kv_cow_reserve") == 0
        assert engine.memledger.conservation()["ok"]
