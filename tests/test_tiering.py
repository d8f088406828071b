"""ISSUE 20: HBM→host KV tiering — spill cold pages to host RAM,
restream on demand.

Pinned invariants (the ROADMAP item 3 headline, the preemption pin
extended):

- **restream bit-match**: evict→spill→restream→resume produces exactly
  the tokens of the never-evicted run — on the paged bf16 cache, on
  the paged int8 cache (payload + scales move as one unit), and for
  the dense cache's whole-slot spill (``export_kv_rows`` →
  ``inject_kv_rows``);
- **COW-shared boundary**: a victim whose parked pages include a
  partially-shared prefix page restreams through a COW copy, never a
  write over the sharer's page;
- **prefix survival**: a sole-reader prefix entry migrates to the host
  tier when its HBM pages are reclaimed and keeps serving admission
  hits by restream — confirmed by full token compare, bit-matched
  against recompute;
- **conservation per tier**: grants − frees == held holds for
  ``kv_host_pages`` exactly as for ``kv_pages``, across the whole
  spill/restream lifecycle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.models import GPT2, GPT2Config
from mpit_tpu.serve import Engine, Request, SchedulingPolicy, Server

CFG = GPT2Config.tiny(max_seq_len=128, num_layers=2)


@pytest.fixture(scope="module")
def params():
    return jax.jit(GPT2(CFG).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def _tiered_engine(params, **kw):
    kw.setdefault("kv_host_pages", 8)
    kw.setdefault("decode_attention", "reference")
    return Engine(
        CFG, params, slots=2, max_len=64, prefill_len=32, kv_pages=16,
        kv_page_size=8, prefill_chunk=8, **kw,
    )


@pytest.fixture(scope="module")
def tiered_engine(params):
    return _tiered_engine(params)


@pytest.fixture(scope="module")
def int8_engine(params):
    return _tiered_engine(params, kv_dtype="int8")


def _req(rid, prompt, *, new=8, priority=0):
    return Request(rid=rid, prompt=list(prompt), max_new_tokens=new,
                   priority=priority)


def _reference_tokens(engine, reqs):
    """The never-evicted run: same engine (reset), no preemption."""
    engine.reset()
    server = Server(engine)
    for r in reqs:
        assert server.submit(r)
    done = server.run()
    return {c.rid: c.tokens for c in done}


def _assert_tier_conservation(server):
    mem = server.stats()["memory"]
    cons = mem["conservation"]
    assert cons["ok"], cons
    sub = cons["subsystems"]["kv_host_pages"]
    assert sub["ok"], sub
    alloc = server.engine.allocator
    assert sub["held_bytes"] == (
        alloc.host_pages_in_use * server.engine.page_bytes
    )


class TestRestreamResumeBitmatch:
    def _preempt_resume_run(self, engine, prompt, *, new=8):
        """Park the victim mid-generation, resume, run to completion.
        Returns (tokens, server)."""
        engine.reset()
        server = Server(engine, policy=SchedulingPolicy())
        server.submit(_req("v", prompt, new=new, priority=1))
        server.run(max_ticks=6)
        assert server.live, "victim should be mid-generation"
        slot = next(iter(server.live))
        assert 0 < len(server.live[slot].tokens) < new
        server._preempt(slot)
        # The park really spilled: host bytes held, record parked.
        assert server.engine.memledger.held("kv_host_pages") > 0
        assert engine.allocator.peek_parked("v") is not None
        done = server.run()
        return done[0].tokens, server

    def test_parked_restream_resume_bitmatch_bf16(self, tiered_engine,
                                                  params):
        rng = np.random.RandomState(7)
        prompt = rng.randint(0, CFG.vocab_size, size=10).tolist()
        got, server = self._preempt_resume_run(tiered_engine, prompt)
        st = server.stats()
        # The resume really took the restream path, not recompute.
        assert st["host_restreamed_pages"] > 0
        assert st["parked_spills"] == 1
        assert server.resume_durations["restream"]
        assert not server.resume_durations["recompute"]
        assert "resume_restream_p95_s" in st
        _assert_tier_conservation(server)
        ref = _reference_tokens(tiered_engine, [_req("v", prompt)])
        assert got == ref["v"]

    def test_parked_restream_resume_bitmatch_int8(self, int8_engine):
        """The quantized cache parks int8 payloads + f32 scale blocks
        as ONE pytree — a restream that dropped or reordered scales
        would break this bit-match immediately."""
        rng = np.random.RandomState(11)
        prompt = rng.randint(0, CFG.vocab_size, size=10).tolist()
        got, server = self._preempt_resume_run(int8_engine, prompt)
        st = server.stats()
        assert st["kv_dtype"] == "int8"
        assert st["host_restreamed_pages"] > 0
        assert server.resume_durations["restream"]
        _assert_tier_conservation(server)
        ref = _reference_tokens(int8_engine, [_req("v", prompt)])
        assert got == ref["v"]

    def test_restream_through_cow_shared_boundary_bitmatch(
        self, params
    ):
        """The victim's parked pages include a partially-shared prefix
        page (another slot still reads it on resume): the restream COWs
        the boundary page out before writing it whole — the sharer's
        rows survive and the victim still bit-matches.

        Host pool is sized to 3 pages on purpose: the park (2 pages)
        fits, but the victim's own full-prompt entry can't ALSO spill
        (all-or-nothing), so the resume admission falls back to the
        partial-page DEVICE share of a's still-live prefix — the only
        admission shape whose restream must COW."""
        engine = _tiered_engine(params, kv_host_pages=3)
        rng = np.random.RandomState(13)
        # a's FULL prompt is the shared prefix and 10 % 8 != 0: the
        # registered full-prompt entry ends mid-page, so b's share is
        # partial-page (boundary-only entries would be COW-free).
        prefix = rng.randint(0, CFG.vocab_size, size=10).tolist()
        req_a = _req("a", prefix, new=20, priority=1)
        req_b = _req("b", prefix + [3, 4], new=8, priority=1)
        server = Server(engine, policy=SchedulingPolicy())
        server.submit(req_a)
        server.run(max_ticks=5)  # a registers its prompt, then decodes
        server.submit(req_b)
        server.run(max_ticks=7)  # max_ticks is the GLOBAL tick bound
        slot_b = next(
            s for s, l in server.live.items() if l.req.rid == "b"
        )
        # Mid-generation, fill still within 2 pages (so the park takes
        # 2 of the 3 host pages).
        assert 0 < len(server.live[slot_b].tokens) <= 4
        cows_before = engine.allocator.cow_copies
        assert cows_before >= 1  # b's own first write already COWed
        server._preempt(slot_b)
        # The park fit; b's full-prompt entry did NOT (all-or-nothing).
        assert engine.allocator.peek_parked("b") is not None
        assert engine.allocator.host_resident_entries == 0
        done = server.run()
        # The resume shared the prefix again (a still live), so the
        # parked boundary page was COWed out before its whole-page
        # restore — the restream path's partial-share discipline.
        assert engine.allocator.cow_copies > cows_before
        assert server.resume_durations["restream"]
        _assert_tier_conservation(server)
        by_rid = {c.rid: c.tokens for c in done}
        ref = _reference_tokens(engine, [req_a, req_b])
        assert by_rid["b"] == ref["b"]
        assert by_rid["a"] == ref["a"]

    def test_prefix_entry_survives_reclaim_serves_restream_hit(
        self, tiered_engine
    ):
        """A retiring request's sole-reader prefix entries migrate to
        the host tier instead of dying with their pages; a later admit
        sharing the prefix hits the HOST tier and restreams — and the
        restreamed K/V bit-matches full recompute."""
        engine = tiered_engine
        engine.reset()
        rng = np.random.RandomState(17)
        prefix = rng.randint(0, CFG.vocab_size, size=16).tolist()  # 2 pages
        req_a = _req("a", prefix + [1, 2], new=4)
        req_b = _req("b", prefix + [3, 4], new=6)
        server = Server(engine)
        server.submit(req_a)
        server.run()  # a completes and retires: entries spill to host
        alloc = engine.allocator
        assert alloc.host_resident_entries > 0
        assert alloc.spilled_prefix_entries > 0
        assert server.stats()["memory"]["host_held_bytes"] > 0
        server.submit(req_b)
        done = server.run()
        assert alloc.host_prefix_hits >= 1
        st = server.stats()
        assert st["host_restreamed_pages"] > 0
        assert st["memory"]["restream_bytes"] > 0
        _assert_tier_conservation(server)
        by_rid = {c.rid: c.tokens for c in done}
        ref = _reference_tokens(engine, [_req("b", prefix + [3, 4],
                                              new=6)])
        assert by_rid["b"] == ref["b"]


class TestWholeSlotSpillRestream:
    def test_export_evict_inject_resume_bitmatch(self, params):
        """The whole slot as the spill unit: export the rows host-side
        mid-generation, evict (reset), map fresh pages, inject, keep
        decoding — the continuation bit-matches the uninterrupted run.
        (This is the fleet shipment path doing tier duty; the
        page-granular tier builds on the same gather-to-host
        discipline.)"""
        eng = Engine(CFG, params, slots=2, max_len=64, prefill_len=32,
                     decode_attention="reference")
        rng = np.random.RandomState(19)
        prompt = rng.randint(0, CFG.vocab_size, size=12).tolist()
        S = eng.slots
        greedy_t = np.zeros((S,), np.float32)
        full_k = np.zeros((S,), np.int32)

        def prefill(prompt):
            assert eng.allocator.admit(0, prompt, 8) is not None
            toks = np.zeros((S, eng.prefill_chunk), np.int32)
            toks[0, : len(prompt)] = prompt
            lens = np.zeros((S,), np.int32)
            lens[0] = len(prompt)
            first = np.zeros((S,), bool)
            first[0] = True
            zeros = np.zeros((S,), np.int32)
            return int(eng.prefill_paged(toks, zeros, lens, zeros, first,
                                         greedy_t, full_k)[0])

        def decode_n(n):
            active = np.zeros((S,), bool)
            active[0] = True
            return [int(eng.decode(active, greedy_t, full_k)[0])
                    for _ in range(n)]

        # Uninterrupted reference: prefill + 6 greedy ticks.
        first = prefill(prompt)
        ref = [first] + decode_n(6)
        # Interrupted: stop after 3 ticks, spill the slot host-side,
        # evict everything, restream, continue.
        eng.reset()
        first2 = prefill(prompt)
        head = [first2] + decode_n(3)
        fill = len(prompt) + 3  # prompt rows + one per decoded tick
        k_rows, v_rows = eng.export_kv_rows(0, fill)
        eng.reset()  # the eviction: pages freed, lengths zeroed
        # Slot 1 takes the first pages now: the rows land elsewhere.
        assert eng.allocator.admit(1, [1, 2, 3], 4) is not None
        assert eng.allocator.admit(0, prompt, 8) is not None
        eng.inject_kv_rows(0, k_rows, v_rows, fill, head[-1])
        tail = decode_n(3)
        assert head + tail == ref


@pytest.mark.slow
class TestPrefixHitRateUnderPressure:
    def test_long_tail_trace_keeps_hit_rate_after_reclaim(self, params):
        """The headline capacity claim: on a long-tail trace (every
        request shares a hot system prefix, arrivals serialized so the
        prefix is sole-reader between requests) a small pool reclaims
        the prefix pages over and over. Without the host tier the
        entry dies at first reclaim and every later admit recomputes;
        with it, the entry survives in host RAM and keeps the hit rate
        up."""
        rng = np.random.RandomState(23)
        prefix = rng.randint(0, CFG.vocab_size, size=16).tolist()
        trace = [
            _req(f"r{i}",
                 prefix + rng.randint(0, CFG.vocab_size, size=4).tolist(),
                 new=4)
            for i in range(8)
        ]

        def run(engine):
            engine.reset()
            server = Server(engine)
            for r in trace:
                server.submit(r)
                server.run()  # serialized: prefix is sole-reader between
            return server.stats()

        tiered = run(_tiered_engine(params))
        untiered = run(
            Engine(CFG, params, slots=2, max_len=64, prefill_len=32,
                   kv_pages=16, kv_page_size=8, prefill_chunk=8,
                   decode_attention="reference")
        )
        # Untiered: the entry dies with its pages at every retire; only
        # same-pool-residency accidents can hit. Tiered: every request
        # after the first hits (host or device).
        assert tiered["host_prefix_hits"] >= 6
        assert tiered["prefix_hit_rate"] > untiered["prefix_hit_rate"]
        assert tiered["prefix_hit_rate"] >= 0.5


class TestDonatedPoolLifetimes:
    """The paged steps donate the page pool (a buffer a layer, updated
    in place). One server run through every step that takes the cache:
    admit, chunked prefill, decode, a copy-on-write remap, a spill with
    its drain and restore, a preempt and resume. What is pinned is the
    buffers' lifetimes: the tokens are the parent commit's for the same
    seed (the greedy request's are the dense reference engine's too),
    and a cache that a step has taken is spent."""

    # Recorded from the parent of the PR that brought donation in
    # (afb8e94), this scenario, engine seed 5: "a" greedy, "b" sampled at
    # temperature 0.8, top-k 20. bf16 and int8 pools read the same here.
    PARENT_TOKENS = {
        "a": [239, 239, 239, 458, 182, 182, 182, 458, 182, 182, 174, 179,
              458, 458, 182, 174, 174, 179, 174, 174],
        "b": [25, 182, 181, 0, 114, 15, 78, 365],
    }

    @staticmethod
    def _run(engine, cfg=CFG):
        """The scenario; returns the server, the finished tokens by rid,
        the shared prefix and the cache the first steps took."""
        rng = np.random.RandomState(13)
        prefix = rng.randint(0, cfg.vocab_size, size=10).tolist()
        server = Server(engine, policy=SchedulingPolicy())
        server.submit(_req("a", prefix, new=20, priority=1))
        server.run(max_ticks=5)  # two prefill chunks, then decode ticks
        spent = engine.cache
        server.submit(Request(
            rid="b", prompt=prefix + [3, 4], max_new_tokens=8, priority=1,
            temperature=0.8, top_k=20,
        ))
        server.run(max_ticks=7)  # b shares a's partial page: a COW remap
        slot_b = next(s for s, l in server.live.items() if l.req.rid == "b")
        server._preempt(slot_b)  # spills b's pages: gathers, not donated
        assert engine._pending_spills or engine._host_store
        done = {c.rid: c.tokens for c in server.run()}  # drain, restore
        assert engine.allocator.cow_copies >= 2
        assert server.stats()["host_restreamed_pages"] >= 1
        assert server.resume_durations["restream"]
        _assert_tier_conservation(server)
        return done, prefix, spent

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_every_donating_step_in_one_run_matches_parent(
        self, params, kv_dtype
    ):
        engine = _tiered_engine(
            params, kv_host_pages=3, decode_attention="interpret", seed=5,
            kv_dtype=kv_dtype,
        )
        done, prefix, spent = self._run(engine)
        # The cache handed to the first steps is gone, every buffer of
        # it, and reading it raises rather than returning old rows.
        leaves = jax.tree.leaves((spent.k, spent.v))
        assert len(leaves) == 2 * CFG.num_layers * (2 if kv_dtype else 1)
        assert all(leaf.is_deleted() for leaf in leaves)
        with pytest.raises(RuntimeError, match="deleted"):
            np.asarray(leaves[0])
        assert not any(
            leaf.is_deleted() for leaf in jax.tree.leaves(engine.cache)
        )
        assert done == self.PARENT_TOKENS
        ref = _reference_tokens(engine, [_req("a", prefix, new=20)])
        assert done["a"] == ref["a"]

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_page_writer_inside_the_donating_steps(
        self, kv_dtype, monkeypatch
    ):
        """The same run with a chunk's rows written as on the chip: the
        ``paged_kv_write`` kernel (through the interpreter; the choice
        steered here, never by a product option) aliasing a buffer that
        the step has been given. Rows of 128 lanes, so that the writer
        takes them; the tokens are those of the row scatter."""
        import functools

        from mpit_tpu.models import gpt2
        from mpit_tpu.ops import decode_attention

        cfg = GPT2Config.tiny(max_seq_len=128, num_layers=2, d_model=128)
        wide = jax.jit(GPT2(cfg).init)(
            jax.random.key(1), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        build = lambda: Engine(
            cfg, wide, slots=2, max_len=64, prefill_len=32, kv_pages=16,
            kv_page_size=8, prefill_chunk=8, kv_host_pages=3, seed=5,
            decode_attention="interpret", kv_dtype=kv_dtype,
        )
        by_rows, _, _ = self._run(build(), cfg)
        monkeypatch.setattr(decode_attention, "_use_kernel", lambda _: True)
        monkeypatch.setattr(
            gpt2, "paged_write_pages",
            functools.partial(decode_attention.paged_write_pages,
                              interpret=True),
        )
        engine = build()
        by_pages, _, spent = self._run(engine, cfg)
        assert all(
            leaf.is_deleted() for leaf in jax.tree.leaves((spent.k, spent.v))
        )
        args = (engine.params, engine.cache, engine.last_token,
                jnp.zeros((2, 8), jnp.int32), *[jnp.zeros((2,), jnp.int32)] * 3,
                jnp.zeros((2,), bool),
                jnp.zeros((2, engine.pages_per_slot), jnp.int32),
                jax.random.key(0), jnp.zeros((2,), jnp.float32),
                jnp.zeros((2,), jnp.int32))
        steps = str(jax.make_jaxpr(engine._prefill_paged_jit)(*args))
        assert "paged_kv_write" in steps
        assert by_pages == by_rows
