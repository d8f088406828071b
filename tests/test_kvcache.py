"""ISSUE 7: the paged KV pool's host-side bookkeeping, in isolation.

The :class:`~mpit_tpu.serve.kvcache.PageAllocator` is pure host python —
every capacity/sharing/COW edge case the engine relies on is pinnable
here without jax (the device-path acceptance — greedy bit-match through
the paged engine — lives in ``tests/test_serve.py``):

- pool exhaustion at admit is ALL-or-nothing (``None``, no partial
  allocation) and never-fits requests raise a precise ValueError;
- freed pages recycle through the free list, and prefix-index entries
  die with their pages (an entry must never advertise recycled K/V);
- partial-page prefix mappings reserve a free page per extra mapper
  (refcount − 1 total), so a copy-on-write can never fail mid-decode —
  admission is the only capacity gate;
- a prefix-hash collision can never alias two prompts: every hit is
  confirmed with a full token compare before any page is mapped.
"""

from __future__ import annotations

import numpy as np
import pytest

from mpit_tpu.serve.kvcache import (
    AdmitPlan,
    PageAllocator,
    _PrefixEntry,
    _prefix_hashes,
    pages_needed,
)


def _alloc(num_pages=16, page_size=4, pages_per_slot=8, slots=4):
    return PageAllocator(num_pages, page_size, pages_per_slot, slots)


class TestPagesNeeded:
    def test_fill_watermark_math(self):
        # Highest written position is prompt + new - 2; the watermark
        # (prompt + new - 1) is what pages must cover.
        assert pages_needed(1, 1, 4) == 1
        assert pages_needed(4, 1, 4) == 1  # watermark 4 -> exactly 1 page
        assert pages_needed(4, 2, 4) == 2
        assert pages_needed(7, 10, 4) == 4  # watermark 16
        assert pages_needed(30, 3, 16) == 2

    def test_admit_maps_exactly_pages_needed(self):
        a = _alloc()
        plan = a.admit(0, list(range(6)), 4)  # watermark 9 -> 3 pages
        assert len(plan.pages) == 3
        assert a.pages_in_use == 3
        assert plan.shared_tokens == 0


class TestCapacity:
    def test_exhaustion_returns_none_with_no_partial_allocation(self):
        a = _alloc(num_pages=4, page_size=4)
        a.admit(0, list(range(8)), 4)  # watermark 11 -> 3 pages
        free_before = list(a.free)
        # Needs 2 pages, only 1 free: nothing may be taken.
        assert a.admit(1, list(range(5)), 3) is None
        assert a.free == free_before
        assert a.pages_in_use == 3
        # A 1-page request still fits.
        assert a.admit(1, [1, 2], 2) is not None

    def test_never_fits_raises_precise_valueerror(self):
        a = _alloc(num_pages=4, page_size=4, pages_per_slot=8)
        with pytest.raises(ValueError, match="pool holds"):
            a.admit(0, list(range(12)), 8)  # 5 pages > 4-page pool
        with pytest.raises(ValueError, match="pages_per_slot"):
            _alloc(num_pages=64, pages_per_slot=2).admit(
                0, list(range(12)), 8
            )
        assert a.pages_in_use == 0  # the raise took nothing either

    def test_freed_pages_recycle_through_free_list(self):
        a = _alloc(num_pages=4, page_size=4)
        plan = a.admit(0, list(range(8)), 4)
        a.free_slot(0)
        assert a.pages_in_use == 0
        plan2 = a.admit(1, list(range(4)), 8)  # needs 3 pages again
        # The recycled pages are handed out again (mask-defined
        # validity: no zeroing, no quarantine).
        assert set(plan2.pages) <= set(plan.pages) | set(range(4))
        assert a.pages_in_use == 3

    def test_admit_clears_stale_block_table_tail(self):
        a = _alloc()
        a.admit(0, list(range(20)), 12)  # 8 pages -> fills the row
        a.free_slot(0)
        a.admit(0, [1, 2], 2)  # 1 page
        assert list(a.block_tables[0][1:]) == [0] * 7


class TestPoolForEverySlot:
    """``Engine(kv_pages=None)`` sizes the pool at ``slots x
    pages_per_slot`` (ISSUE 28): admission then never waits for pages,
    whatever is shared."""

    @pytest.mark.parametrize("page_size", [4, 16, 32])
    def test_every_slot_at_max_len_always_fits(self, page_size):
        """Requests that fill their slot to the last position, with
        prefixes shared at every kind of boundary (whole pages, part of a
        page, the whole prompt), admitted, written to the end and retired
        in turn over many rounds: no admit comes back ``None``, and every
        copy-on-write finds its page."""
        slots, max_len = 4, 64
        pps = max_len // page_size
        a = PageAllocator(slots * pps, page_size, pps, slots)
        rng = np.random.RandomState(page_size)
        system = rng.randint(0, 50, size=40).tolist()
        for round_ in range(12):
            share = [0, page_size, page_size + 3, 40][round_ % 4]
            for slot in range(slots):
                own = rng.randint(50, 99, size=48 - share).tolist()
                prompt = system[:share] + (own if slot % 2 else own[::-1])
                prompt = prompt[: 20 + 7 * slot]
                plan = a.admit(slot, prompt, max_len - len(prompt))
                assert plan is not None, (round_, slot)
                a.register_prefix(slot, prompt)
                # Every position from the floor to the end gets written.
                for pos in range(plan.shared_tokens, max_len - 1):
                    a.cow_before_write(slot, pos)
            assert a.free_pages >= 0 and a.pages_in_use <= a.num_pages
            for slot in rng.permutation(slots):
                a.free_slot(int(slot))
            assert a.pages_in_use == 0 and a.reserved == 0


class TestPrefixSharing:
    def test_registered_prefix_is_mapped_refcounted(self):
        a = _alloc()
        p = list(range(10))
        plan_a = a.admit(0, p, 4)
        a.register_prefix(0, p)
        plan_b = a.admit(1, p + [77, 78], 4)
        # b shares a's full prompt (10 tokens: 2 full pages + the
        # partial third) and allocates only its own tail.
        assert plan_b.shared_tokens == 10
        assert plan_b.pages[:3] == plan_a.pages[:3]
        assert all(a.refcount[pg] == 2 for pg in plan_a.pages[:3])
        assert a.prefix_hits == 1
        assert a.shared_tokens_total == 10
        assert a.pages_shared == 3
        assert 0 < a.hit_rate < 1

    def test_page_aligned_prefix_shares_without_reserve(self):
        a = _alloc()
        p = list(range(8))  # exactly 2 pages
        a.admit(0, p, 4)
        a.register_prefix(0, p)
        before = a.free_pages
        plan = a.admit(1, p + [5], 4)
        assert plan.shared_tokens == 8
        # Full-page mappings are immutable forever: no COW reserve.
        assert a.reserved == 0
        assert a.free_pages == before - (len(plan.pages) - 2)

    def test_entries_die_with_their_pages(self):
        a = _alloc()
        p = list(range(6))
        a.admit(0, p, 4)
        a.register_prefix(0, p)
        a.free_slot(0)  # pages recycled -> the index must forget them
        plan = a.admit(1, p, 4)
        assert plan.shared_tokens == 0
        assert a.prefix_hits == 0

    def test_hash_collision_is_confirmed_by_token_compare(self):
        """Poison the index with an entry whose KEY matches prompt B's
        prefix hash but whose tokens differ — the mandatory full-token
        compare must reject it (collision safety is correctness, not
        probability)."""
        a = _alloc()
        other = tuple(range(100, 104))
        b = [1, 2, 3, 4, 9]
        h = _prefix_hashes(b)[4]  # b's real 4-token prefix hash
        a._index[(4, h)] = _PrefixEntry(tokens=other, pages=(7,))
        a._page_keys[7] = {(4, h)}
        a.refcount[7] = 1
        plan = a.admit(0, b, 2)
        assert plan.shared_tokens == 0  # hit rejected, cold admit
        assert a.prefix_hits == 0

    def test_first_registration_wins(self):
        a = _alloc()
        p = list(range(4))
        a.admit(0, p, 4)
        a.register_prefix(0, p)
        entry = a._index[(4, _prefix_hashes(p)[4])]
        a.admit(1, p + [9], 4)
        a.register_prefix(1, p + [9])
        # The 4-token boundary entry still cites slot 0's page.
        assert a._index[(4, _prefix_hashes(p)[4])] is entry


class TestCopyOnWrite:
    def _shared_partial(self):
        """Slot 0 registered 6 tokens (page_size 4: one full + one
        partial page); slot 1 maps them and reserves a COW page."""
        a = _alloc()
        p = list(range(6))
        a.admit(0, p, 4)
        a.register_prefix(0, p)
        plan = a.admit(1, p + [50, 51], 4)
        assert plan.shared_tokens == 6
        return a, plan

    def test_partial_page_mapping_reserves_cow_page(self):
        a, plan = self._shared_partial()
        assert a.reserved == 1
        # The reserve is excluded from admittable capacity but the page
        # physically stays in the free list (the COW pop source).
        assert a.free_pages == len(a.free) - 1

    def test_cow_moves_writer_consumes_reserve(self):
        a, plan = self._shared_partial()
        partial = plan.pages[1]
        pair = a.cow_before_write(1, 6)  # slot 1 writes position 6
        assert pair is not None and pair[0] == partial
        src, dst = pair
        assert a.block_tables[1][1] == dst
        assert a.refcount[src] == 1 and a.refcount[dst] == 1
        assert a.reserved == 0
        assert a.cow_copies == 1
        # Page now private on both sides: further writes are in place.
        assert a.cow_before_write(1, 7) is None
        assert a.cow_before_write(0, 6) is None

    def test_sole_owner_write_is_in_place(self):
        a = _alloc()
        a.admit(0, list(range(6)), 4)
        assert a.cow_before_write(0, 6) is None
        assert a.cow_copies == 0

    def test_release_on_retire_returns_reserve(self):
        a, plan = self._shared_partial()
        a.free_slot(1)  # the mapper retires without ever diverging
        assert a.reserved == 0
        assert a.pages_shared == 0

    def test_retiring_nonwriter_sharer_releases_its_reserve(self):
        """A sharer of a partial page that retires WITHOUT ever writing
        (full-prompt prefix hit finishing at prefill) must give its COW
        reserve back while the page is still shared by others — a page
        with refcount mappers needs at most refcount-1 future copies,
        so holding more starves admission under sustained overlapping
        shared-prefix traffic."""
        a = _alloc(num_pages=8, page_size=4, slots=4)
        p = list(range(6))  # 1 full + 1 partial page
        a.admit(0, p, 4)
        a.register_prefix(0, p)
        a.admit(1, p, 4)  # full-prompt hit: maps both, reserves 1
        a.admit(2, p, 4)  # second sharer: reserves 1 more
        assert a.reserved == 2
        a.free_slot(1)  # retires having never written the partial page
        assert a.reserved == 1, "non-writing sharer leaked its reserve"
        # The remaining sharer's divergence still cannot fail...
        pair = a.cow_before_write(2, 5)
        assert pair is not None
        assert a.reserved == 0
        # ...and the registrant, now sole owner, writes in place.
        assert a.cow_before_write(0, 5) is None

    def test_cow_cannot_fail_at_pool_exhaustion(self):
        """Admission reserves the COW page, so a full pool can never
        strand a shared-page writer: drain the pool to 0 admittable
        pages, then COW — the reserved page is still there."""
        a = _alloc(num_pages=5, page_size=4, pages_per_slot=4, slots=5)
        p = list(range(6))
        a.admit(0, p, 2)  # 2 fresh pages
        a.register_prefix(0, p)
        a.admit(1, p + [9], 2)  # shares both (partial last) + 1 reserve
        # Drain every admittable page: two one-page requests take the
        # pool to exactly the COW reserve.
        assert a.admit(2, [1], 1) is not None
        assert a.admit(3, [2], 1) is not None
        assert a.free_pages == 0
        assert len(a.free) == 1 and a.reserved == 1  # reserve alone left
        # Nothing more is admittable — the reserve is not for admits.
        assert a.admit(4, [3], 1) is None
        pair = a.cow_before_write(1, 6)
        assert pair is not None  # the reserve made this pop safe
        assert a.reserved == 0 and len(a.free) == 0


class TestAdmitPlanShape:
    def test_plan_is_frozen_and_ordered(self):
        a = _alloc()
        plan = a.admit(0, list(range(5)), 3)
        assert isinstance(plan, AdmitPlan)
        # Pages in position order: page i holds tokens [i*ps, (i+1)*ps).
        assert list(a.block_tables[0][: len(plan.pages)]) == list(plan.pages)
        with pytest.raises(dataclasses_frozen_error()):
            plan.shared_tokens = 3


def dataclasses_frozen_error():
    import dataclasses

    return dataclasses.FrozenInstanceError


def _halloc(num_pages=16, page_size=4, pages_per_slot=8, slots=4,
            host_pages=8):
    return PageAllocator(num_pages, page_size, pages_per_slot, slots,
                         host_pages=host_pages)


class TestHostTier:
    """ISSUE 20 cross-tier edges, allocator-side (device payloads are
    the engine's problem; every id/refcount/reserve transition is
    pinnable here without jax)."""

    def test_park_of_cow_shared_pages_preserves_sharer_state(self):
        """Parking a victim whose mapping includes COW-shared pages
        spills its rows and frees its refcounts/reserve WITHOUT
        touching the surviving sharer: the sharer's pages stay
        refcount 1, its index entries stay device-tier, and the
        victim's COW reserve is returned."""
        a = _halloc()
        p = list(range(10))  # 3 pages at ps=4, last partial
        a.admit(0, p, 4)
        a.register_prefix(0, p)
        plan_b = a.admit(1, p + [90, 91], 4)
        assert plan_b.shared_tokens == 10 and a.reserved == 1
        shared = list(plan_b.pages[:3])
        assert all(a.refcount[pg] == 2 for pg in shared)
        fill = 12  # b's prompt fully prefilled
        copies, evicted = a.park_pages("b", 1, fill)
        assert evicted == [] and len(copies) == 3
        # Spill copies EVERY filled page, shared ones included — the
        # host copy must be self-contained once slot 1's mapping dies.
        assert [dp for dp, _ in copies] == list(plan_b.pages[:3])
        a.free_slot(1)
        assert all(a.refcount[pg] == 1 for pg in shared)
        assert a.reserved == 0  # b's COW reserve returned
        assert a.host_resident_entries == 0  # a's entries untouched
        assert all(e.tier == "hbm" for e in a._index.values())
        rec = a.peek_parked("b")
        assert rec is not None and rec.fill == fill
        assert len(rec.host_pages) == 3
        # take AFTER payload consumption recycles the ids.
        free_before = len(a.host_free)
        a.take_parked("b")
        assert len(a.host_free) == free_before + 3
        assert a.peek_parked("b") is None

    def test_entry_survives_hbm_reclaim_and_confirms_tokens(self):
        """A sole-reader prefix entry migrates to the host tier when
        its pages die, keeps serving admits (restream plan, full pages
        fresh, no cross-tier refcounts), and every host hit is still
        confirmed by FULL token compare — a poisoned entry can never
        alias."""
        import dataclasses as dc

        a = _halloc()
        p = list(range(8))  # page-aligned: 2 pages
        a.admit(0, p, 2)
        a.register_prefix(0, p)
        copies, evicted = a.spill_prefix_on_free(0)
        assert evicted == [] and len(copies) == 2
        a.free_slot(0)
        assert a.pages_in_use == 0  # HBM fully reclaimed
        assert a.host_resident_entries == 2  # 4t + 8t boundaries
        assert a.spilled_prefix_entries == 2
        plan = a.admit(1, p + [80], 2)
        assert plan.shared_tokens == 8
        assert len(plan.restream) == 2  # both prefix pages restream
        # No cross-tier sharing: every mapped page is fresh + private.
        assert all(a.refcount[pg] == 1 for pg in plan.pages)
        assert a.reserved == 0
        assert a.host_prefix_hits == 1
        # Restream targets are the mapping's first pages, in order.
        assert [dp for _, dp in plan.restream] == list(plan.pages[:2])
        # Poison the longest entry: same hash key, different tokens —
        # the token compare must refuse the hit.
        a.free_slot(1)
        key = max(k for k, e in a._index.items() if e.tier == "host")
        a._index[key] = dc.replace(a._index[key],
                                   tokens=tuple(range(100, 108)))
        plan2 = a.admit(1, p + [80], 2)
        assert plan2.shared_tokens == 4  # falls back to the 4t entry
        assert len(plan2.restream) == 1

    def test_promotion_frees_host_copy_on_reregister(self):
        """register_prefix over a host-resident key promotes it: the
        entry returns to device pages and the freed host ids are
        handed back for payload drop."""
        a = _halloc()
        p = list(range(8))
        a.admit(0, p, 2)
        a.register_prefix(0, p)
        a.spill_prefix_on_free(0)
        a.free_slot(0)
        assert a.host_resident_entries == 2
        a.admit(1, p, 2)
        freed = a.register_prefix(1, p)
        assert a.host_resident_entries == 0
        assert a.promoted_entries == 2
        assert len(freed) == 2  # both host pages keyless -> recycled
        assert len(a.host_free) == a.host_pages

    def test_pool_exhaustion_keeps_all_or_nothing_with_host_hit(self):
        """A host hit needs the FULL page count fresh (no shared
        mapping) — when the pool cannot supply it, admit returns None
        with nothing taken and the host entry keeps serving."""
        a = _halloc(num_pages=4)
        p = list(range(8))
        a.admit(0, p, 2)
        a.register_prefix(0, p)
        a.spill_prefix_on_free(0)
        a.free_slot(0)
        a.admit(1, [50, 51, 52, 53] * 3, 4)  # 4 pages: pool now full
        free_before = list(a.free)
        host_before = list(a.host_free)
        assert a.admit(2, p + [80], 2) is None
        assert a.free == free_before
        assert a.host_free == host_before
        assert a.host_resident_entries == 2  # entry intact, still hot

    def test_host_exhaustion_spill_is_all_or_nothing(self):
        """An undersized host tier refuses a park/migration WITHOUT
        evicting anything first (the reachability check precedes any
        eviction), and parked records are never reclaimed."""
        a = _halloc(num_pages=16, host_pages=2)
        # Park a 2-page victim: host tier now full of promised resumes.
        a.admit(0, list(range(8)), 2)
        assert a.park_pages("v", 0, 8) is not None
        a.free_slot(0)
        assert a.host_free == []
        # A second park cannot fit and must not evict the first.
        a.admit(1, list(range(100, 108)), 2)
        assert a.park_pages("w", 1, 8) is None
        assert a.peek_parked("v") is not None
        # A prefix migration is refused the same way, entries die as
        # before tiering.
        a.register_prefix(1, list(range(100, 108)))
        copies, evicted = a.spill_prefix_on_free(1)
        assert copies == [] and evicted == []
        a.free_slot(1)
        assert a.host_resident_entries == 0

    def test_reclaim_evicts_coldest_prefix_entries_only(self):
        """Host pressure reclaims the coldest host-resident prefix
        entries (by last-touch tick) to make room for a park — and
        hands back their page ids so the engine drops the payloads."""
        a = _halloc(num_pages=16, host_pages=2)
        p = list(range(8))
        a.admit(0, p, 2)
        a.register_prefix(0, p, tick=1)
        copies, _ = a.spill_prefix_on_free(0)
        assert len(copies) == 2
        a.free_slot(0)
        assert a.host_resident_entries == 2 and a.host_free == []
        # Parking now must evict the (cold) entries to fit.
        a.admit(1, list(range(50, 58)), 2)
        copies, evicted = a.park_pages("v", 1, 8)
        assert len(copies) == 2 and len(evicted) == 2
        assert a.host_resident_entries == 0
        assert a.parked_spills == 1

    def test_drop_parked_returns_ids_for_payload_drop(self):
        a = _halloc()
        a.admit(0, list(range(8)), 2)
        copies, _ = a.park_pages("v", 0, 8)
        a.free_slot(0)
        freed = a.drop_parked("v")
        assert sorted(freed) == sorted(hp for _, hp in copies)
        assert len(a.host_free) == a.host_pages
        assert a.drop_parked("v") == []  # idempotent
