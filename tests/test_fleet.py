"""ISSUE 19 acceptance: the disaggregated serving fleet.

The done-criteria:

- greedy outputs through the fleet — router → prefill worker → KV
  shipment over ``Comm_dup("fleet-kv")`` → decode worker — bit-match
  the single-engine :class:`~mpit_tpu.serve.scheduler.Server` run for
  EVERY request, including through a mid-job decode-worker kill whose
  in-flight requests re-queue to a survivor;
- shipment bytes ride the flight recorder's merged P2P matrix;
- the int8-quantized cache ships losslessly (q + scale blocks travel
  as separate wire leaves, bit-exact after inject);
- the loadgen shard splitter is deterministic and leaves the arrival
  trace untouched (satellite 2), and the ``Server`` stats carry the
  fleet worker stamp (satellite 1).

All parity runs use the f32 tiny config from ``test_serve`` — exact
token equality, not tolerance.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpit_tpu.compat import FaultPlan
from mpit_tpu.models import GPT2, GPT2Config
from mpit_tpu.obs.trace import Ledger
from mpit_tpu.serve import (
    Engine,
    FleetConfig,
    KVShipment,
    Request,
    Server,
    inject_shipment,
    pack_shipment,
    parse_fleet_spec,
    run_fleet,
    split_arrivals,
    unpack_shipment,
)
from mpit_tpu.serve import fleet as fleet_mod
from mpit_tpu.serve.loadgen import LoadSpec, generate_arrivals

CFG = GPT2Config.tiny(
    vocab_size=64, max_seq_len=64, num_layers=2, num_heads=2, d_model=32,
    dtype=jnp.float32,
)

PROMPTS = [[5, 9, 3], [7], [1, 2, 3, 4, 5], [9, 9], [3, 1], [60, 2, 2, 1]]
MAX_NEW = [6, 4, 8, 3, 5, 7]


def _requests():
    return [
        Request(rid=f"r{i}", prompt=p, max_new_tokens=n)
        for i, (p, n) in enumerate(zip(PROMPTS, MAX_NEW))
    ]


@pytest.fixture(scope="module")
def model_and_params():
    model = GPT2(CFG)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def single_engine_tokens(model_and_params):
    """The oracle: the same request set through ONE engine's
    continuous-batching Server — the run the fleet must bit-match."""
    _, params = model_and_params
    engine = Engine(CFG, params, slots=2, max_len=32, prefill_len=8)
    server = Server(engine)
    for r in _requests():
        server.submit(r)
    return {str(c.rid): list(c.tokens) for c in server.run()}


def _engine_factory(params):
    def factory(role, rank):
        return Engine(CFG, params, slots=2, max_len=32, prefill_len=8)

    return factory


class TestFleetSpec:
    def test_parse_roundtrip(self):
        cfg = parse_fleet_spec("prefill=2,decode=3,lease_s=0.4")
        assert (cfg.prefill, cfg.decode, cfg.lease_s) == (2, 3, 0.4)
        assert cfg.nranks == 6

    def test_parse_rejects_unknown_key_and_bare_field(self):
        with pytest.raises(ValueError, match="unknown fleet spec key"):
            parse_fleet_spec("prefill=1,workers=2")
        with pytest.raises(ValueError, match="not key=value"):
            parse_fleet_spec("prefill")

    def test_topology_and_liveness_validation(self):
        with pytest.raises(ValueError, match=">=1 prefill"):
            FleetConfig(prefill=0, decode=1)
        with pytest.raises(ValueError, match="must exceed heartbeat_s"):
            FleetConfig(heartbeat_s=0.5, lease_s=0.5)

    def test_role_of_partitions_ranks(self):
        cfg = FleetConfig(prefill=2, decode=2)
        roles = [cfg.role_of(r) for r in range(cfg.nranks)]
        assert roles == ["router", "prefill", "prefill", "decode", "decode"]


class TestShipmentWire:
    def test_dense_pack_unpack_bit_roundtrip(self):
        """Descriptor-sliced payload reassembles every leaf bit-exact,
        dtype included (the wire carries no treedefs — order is the
        explicit leaves() contract)."""
        rng = np.random.RandomState(0)
        k = rng.randn(2, 5, 2, 16).astype(np.float32)
        v = rng.randn(2, 5, 2, 16).astype(np.float32)
        ship = KVShipment(
            rid="w0", prompt=[5, 9, 3, 1, 2], first_token=7, length=5,
            max_new_tokens=4, temperature=0.0, top_k=0, eos_id=None,
            quantized=False, k=k, v=v,
        )
        header, meta, payload = pack_shipment(ship)
        assert header.dtype == np.int64 and header.shape == (2,)
        assert int(header[0]) == meta.size
        assert int(header[1]) == payload.size == k.nbytes + v.nbytes
        back = unpack_shipment(meta, payload)
        assert back.rid == "w0" and back.first_token == 7
        np.testing.assert_array_equal(np.asarray(back.k), k)
        np.testing.assert_array_equal(np.asarray(back.v), v)
        assert np.asarray(back.k).dtype == np.float32

    def test_paged_int8_ship_inject_bitmatch(self, model_and_params):
        """Prefill on a paged int8 engine, pack → unpack → inject into
        a second paged int8 engine, decode there: tokens equal the
        SAME engine's own single-server run (q and scale blocks both
        survive the wire bit-exact)."""
        _, params = model_and_params

        def paged_int8():
            return Engine(
                CFG, params, slots=2, max_len=40, prefill_len=8,
                kv_pages=24, kv_page_size=4, kv_dtype="int8",
                decode_attention="reference",
            )

        prompt, n_new = [5, 9, 3, 1], 5
        src = paged_int8()
        ledger = Ledger(mode="aggregate", origin_rank=1)
        ship, _ = fleet_mod._prefill_one(
            src,
            {
                "rid": "q0", "prompt": prompt, "max_new_tokens": n_new,
                "temperature": 0.0, "top_k": 0, "eos_id": None,
            },
            ledger,
        )
        assert ship.quantized
        assert np.asarray(ship.k.q).dtype == np.int8
        header, meta, payload = pack_shipment(ship)
        wire = unpack_shipment(meta, payload)
        np.testing.assert_array_equal(
            np.asarray(wire.k.q), np.asarray(ship.k.q)
        )
        np.testing.assert_array_equal(
            np.asarray(wire.v.scale), np.asarray(ship.v.scale)
        )

        dst = paged_int8()
        plan = dst.allocator.admit(0, prompt, n_new, owner="q0", tick=0)
        assert plan is not None
        inject_shipment(dst, 0, wire)
        tokens = [int(wire.first_token)]
        active = np.zeros((dst.slots,), bool)
        active[0] = True
        temp = np.zeros((dst.slots,), np.float32)
        topk = np.zeros((dst.slots,), np.int32)
        while len(tokens) < n_new:
            tokens.append(int(dst.decode(active, temp, topk)[0]))

        src.reset()
        server = Server(src)
        server.submit(Request(rid="q0", prompt=prompt, max_new_tokens=n_new))
        (done,) = server.run()
        assert tokens == list(done.tokens)
        assert ledger.counts.get("fleet_prefill") == 1


class TestFleetE2E:
    def test_fleet_bitmatches_single_engine(
        self, model_and_params, single_engine_tokens
    ):
        """THE acceptance run: 1 router + 1 prefill + 2 decode workers,
        every request's greedy tokens equal the single-engine Server's,
        and the shipment bytes are visible on the merged P2P matrix."""
        _, params = model_and_params
        # Wide lease: no fault is injected, so eviction latency is
        # irrelevant — but a tight lease would let a host-wide CPU
        # stall (loaded CI box) spuriously evict a LIVE worker and
        # break the strict zero-churn pin below.
        out = run_fleet(
            _engine_factory(params), _requests(), prefill=1, decode=2,
            heartbeat_s=0.05, lease_s=5.0,
        )
        assert out["shed"] == []
        assert set(out["completed"]) == set(single_engine_tokens)
        for rid, toks in single_engine_tokens.items():
            assert out["completed"][rid] == toks, rid
        router = out["router"]
        assert router["evictions"] == 0 and router["requeues"] == 0
        assert router["ledger_counts"]["fleet_assign"] == len(PROMPTS)
        assert router["ledger_counts"]["fleet_done"] == len(PROMPTS)
        pf = next(w for w in out["workers"] if w["role"] == "prefill")
        assert pf["processed"] == len(PROMPTS)
        assert pf["ship_bytes"] > 0
        # Shipment bytes ride the flight recorder: the prefill rank's
        # outbound row to the decode ranks covers at least the KV
        # payload it reported (control frames only add on top).
        matrix = out["flight"]["p2p_bytes"]
        decode_ranks = range(2, 4)
        assert sum(matrix[1][d] for d in decode_ranks) >= pf["ship_bytes"]
        assert sum(w["completed"] for w in out["workers"]
                   if w["role"] == "decode") == len(PROMPTS)

    def test_decode_worker_kill_requeues_and_bitmatches(
        self, model_and_params, single_engine_tokens
    ):
        """Chaos run: a decode worker dies mid-job (FaultPlan), its
        lease expires, the router re-queues its in-flight requests to
        the survivor — every request still completes with bit-identical
        tokens."""
        _, params = model_and_params
        plan = FaultPlan(seed=0, kill_at={3: 2})  # decode rank 3, tick 2
        out = run_fleet(
            _engine_factory(params), _requests(), prefill=1, decode=2,
            heartbeat_s=0.05, lease_s=0.75, fault_plan=plan,
        )
        assert out["fault_events"] == (("kill", 3, 2),)
        killed = next(w for w in out["workers"] if w["rank"] == 3)
        assert killed["killed"] is True
        router = out["router"]
        assert router["evictions"] >= 1
        assert router["requeues"] >= 1
        assert any(e[0] == "evicted" and e[1] == 3 for e in router["events"])
        assert set(out["completed"]) == set(single_engine_tokens)
        for rid, toks in single_engine_tokens.items():
            assert out["completed"][rid] == toks, rid

    def test_unique_rids_enforced(self, model_and_params):
        _, params = model_and_params
        dup = [
            Request(rid="same", prompt=[5], max_new_tokens=2),
            Request(rid="same", prompt=[7], max_new_tokens=2),
        ]
        with pytest.raises(ValueError, match="unique rids"):
            run_fleet(_engine_factory(params), dup, prefill=1, decode=1)


@pytest.mark.slow
class TestFleetHeavy:
    """The paged-int8 full-fleet parity run and the multi-kill chaos
    variant — subprocess-scale e2e, excluded from tier-1."""

    def test_paged_int8_fleet_bitmatches_single_server(
        self, model_and_params
    ):
        _, params = model_and_params

        def factory(role, rank):
            return Engine(
                CFG, params, slots=2, max_len=40, prefill_len=8,
                kv_pages=24, kv_page_size=4, kv_dtype="int8",
                decode_attention="reference", prefill_chunk=4,
            )

        ref_engine = factory("ref", -1)
        server = Server(ref_engine)
        for r in _requests():
            server.submit(r)
        want = {str(c.rid): list(c.tokens) for c in server.run()}

        out = run_fleet(factory, _requests(), prefill=2, decode=2,
                        heartbeat_s=0.05, lease_s=5.0)
        assert set(out["completed"]) == set(want)
        for rid, toks in want.items():
            assert out["completed"][rid] == toks, rid

    def test_prefill_and_decode_kill_chaos(
        self, model_and_params, single_engine_tokens
    ):
        """Kill ONE prefill worker and ONE decode worker in the same
        job; the survivors absorb both inflight sets and every request
        still bit-matches."""
        _, params = model_and_params
        plan = FaultPlan(seed=0, kill_at={1: 1, 4: 3})
        out = run_fleet(
            _engine_factory(params), _requests(), prefill=2, decode=2,
            heartbeat_s=0.05, lease_s=0.75, fault_plan=plan,
        )
        assert set(e[:2] for e in out["fault_events"]) == {
            ("kill", 1), ("kill", 4)
        }
        assert out["router"]["evictions"] >= 2
        assert set(out["completed"]) == set(single_engine_tokens)
        for rid, toks in single_engine_tokens.items():
            assert out["completed"][rid] == toks, rid


class TestSplitArrivals:
    SPEC = LoadSpec(rate=40.0)

    def _trace(self, seed=3):
        return generate_arrivals(
            self.SPEC, vocab_size=64, duration_s=1.0, seed=seed,
        )

    def test_split_is_deterministic_and_partitions(self):
        arrivals = self._trace()
        a = split_arrivals(arrivals, 3, seed=7)
        b = split_arrivals(arrivals, 3, seed=7)
        assert len(a) == 3
        for sa, sb in zip(a, b):
            assert [x.request.rid for x in sa] == [x.request.rid for x in sb]
        # Partition: every arrival lands in exactly one shard, and each
        # shard preserves the trace's arrival order.
        all_rids = [x.request.rid for x in arrivals]
        seen = [x.request.rid for shard in a for x in shard]
        assert sorted(seen) == sorted(all_rids)
        order = {rid: i for i, rid in enumerate(all_rids)}
        for shard in a:
            idx = [order[x.request.rid] for x in shard]
            assert idx == sorted(idx)

    def test_split_consumes_no_trace_rng(self):
        """Splitting is a pure function of (arrivals, seed): the
        generated trace is identical whether or not a split happened
        before regenerating (satellite 2 — the determinism fix)."""
        before = self._trace()
        split_arrivals(before, 4, seed=1)
        after = self._trace()
        assert len(before) == len(after)
        for x, y in zip(before, after):
            assert (x.t, x.request.rid, tuple(x.request.prompt)) == (
                y.t, y.request.rid, tuple(y.request.prompt)
            )

    def test_split_edge_cases(self):
        arrivals = self._trace()
        (only,) = split_arrivals(arrivals, 1)
        assert [x.request.rid for x in only] == [
            x.request.rid for x in arrivals
        ]
        with pytest.raises(ValueError):
            split_arrivals(arrivals, 0)


class TestWorkerStamp:
    def test_stats_carry_fleet_identity(self, model_and_params):
        _, params = model_and_params
        engine = Engine(CFG, params, slots=2, max_len=32, prefill_len=8)
        server = Server(engine, worker_id="decode-3", role="decode")
        server.submit(Request(rid=0, prompt=[5, 9], max_new_tokens=2))
        server.run()
        st = server.stats()
        assert st["worker_id"] == "decode-3" and st["role"] == "decode"
        mem = st.get("memory")
        if mem:
            assert mem["worker_id"] == "decode-3"

    def test_standalone_default_stamp(self, model_and_params):
        _, params = model_and_params
        engine = Engine(CFG, params, slots=2, max_len=32, prefill_len=8)
        st = Server(engine).stats()
        assert st["worker_id"] == "single" and st["role"] == "standalone"
