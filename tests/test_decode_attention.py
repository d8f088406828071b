"""ISSUE 5 acceptance: flash-decode kernel + blocked LM-head sampling.

The serving hot loop's two new ops, tested in isolation (the engine-level
acceptance — greedy bit-match through the kernel on the staggered
continuous-batching run — lives in ``tests/test_serve.py``):

- ``ops/decode_attention.py``: parity of the paged kernel vs the
  ``cached_attention`` math over the gathered view across ragged
  per-slot lengths (including 0 just after
  admit, ``max_len - 1``, and stale retired-slot lengths), odd head
  counts, small-T prefill tails, and the TP head-shard call; the
  per-slot visited-tile count must be length-dependent (the in-kernel
  bound vs the host formula) — THE measurable form of "decode cost
  scales with context, not cache size" on a CPU runner.
- ``ops/lm_head.py::lm_head_sample``: greedy bit-matches ``argmax`` over
  the full logits; top-k/temperature bit-match a full-logits oracle
  that reproduces the per-block folded Gumbel field under a fixed key;
  the ``[rows, vocab]`` f32 logits never appear in the jaxpr.

Interpret-mode tests run in tier-1 on CPU; the serving widths meet the
real compiler in tier-1 too (``tests/test_chip_compile.py``), other
shapes in the slow-marked ``TestDecodeKernelCompiles``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import mpit_tpu
from mpit_tpu.models.gpt2 import (
    cached_attention,
    paged_cache_update,
    paged_cached_attention,
    paged_gather,
)
from mpit_tpu.ops import lm_head_sample
from mpit_tpu.ops.decode_attention import (
    decode_tiling,
    flash_paged_decode_attention,
    num_kv_blocks,
    paged_write_pages,
    pick_block_k,
    reference_paged_decode_attention,
)


def _qkv_cache(B=4, T=1, H=3, D=16, S=40, seed=0, dtype=jnp.float32):
    """Random queries + a FULLY random cache of ``S`` positions a slot —
    rows past each slot's length are garbage on purpose: validity comes
    from the mask, never the buffer contents (the slot-isolation
    invariant)."""
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, H, D), dtype)
    v = jax.random.normal(ks[2], (B, S, H, D), dtype)
    return q, k, v


def _as_pool(k, v, ps):
    """Per-slot buffers ``[B, S, H, D]`` as a page pool of ``ps``-row
    pages ``[B*S/ps, ps, H*D]`` and the block table that maps slot ``b``
    to its own pages, in REVERSED order in the pool (so a kernel that
    ignored the table would read the wrong rows)."""
    B, S, H, D = k.shape
    n = S // ps
    pool = lambda a: a.reshape(B * n, ps, H * D)[::-1]
    bt = (B * n - 1 - jnp.arange(B * n, dtype=jnp.int32)).reshape(B, n)
    return pool(k), pool(v), bt


def _paged_setup(B=3, T=1, H=2, D=16, n_pages=12, ps=8, pages_per_slot=4,
                 seed=0, dtype=jnp.float32):
    """Random queries + a fully random page pool and a SCRAMBLED block
    table (non-contiguous, non-monotonic page ids, plus shared pages
    between slots) — the mapping indirection is the thing under test."""
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    # One layer's pool in the stored form: rows packed head-major.
    kp = jax.random.normal(ks[1], (n_pages, ps, H * D), dtype)
    vp = jax.random.normal(ks[2], (n_pages, ps, H * D), dtype)
    rng = np.random.RandomState(seed)
    bt = rng.randint(0, n_pages, size=(B, pages_per_slot)).astype(np.int32)
    bt[2] = bt[0]  # slot 2 maps slot 0's pages (prefix sharing shape)
    return q, kp, vp, jnp.asarray(bt)


# name: (query rows T, heads of 64 lanes, pool dtype, the form the kernel
# takes). 20 heads are GPT-2 large's row, 10 a TP rank's half of it.
_TILE_CASES = {
    "T1-h20-f32": (1, 20, jnp.float32, "heads_as_rows"),
    "T4-h20-f32": (4, 20, jnp.float32, "heads_as_rows"),
    "T64-h20-f32": (64, 20, jnp.float32, "per_head"),
    "T1-h10-f32": (1, 10, jnp.float32, "heads_as_rows"),
    "T64-h10-f32": (64, 10, jnp.float32, "per_head"),
    "T1-h20-bf16": (1, 20, jnp.bfloat16, "heads_as_rows"),
    "T4-h20-bf16": (4, 20, jnp.bfloat16, "heads_as_rows"),
    "T64-h20-bf16": (64, 20, jnp.bfloat16, "per_head"),
}


class TestPagedFlashDecode:
    """ISSUE 7: the paged kernel vs the gather-dense reference, and the
    paged write/gather primitives vs the dense cache ops."""

    def test_paged_update_and_gather_match_dense(self):
        """Writing through a permuted block table then gathering the
        dense view puts each slot's rows at its positions and nothing
        anywhere else."""
        rng = np.random.RandomState(0)
        B, T, H, D, ps = 2, 3, 2, 4, 4
        bt = jnp.asarray([[3, 1, 6, 0], [2, 5, 7, 4]], jnp.int32)
        pool = jnp.zeros((8, ps, H * D))
        new = jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
        lens = jnp.asarray([2, 13], jnp.int32)
        d2 = np.zeros((B, 16, H, D), np.float32)
        for b, start in enumerate(np.asarray(lens)):
            d2[b, start : start + T] = np.asarray(new[b])
        p2 = paged_cache_update(
            pool, new.reshape(B, T, H * D), lens, bt,
            valid=jnp.ones((B, T), bool),
        )
        assert p2.shape == pool.shape  # written as stored: no reshape
        assert jnp.all(paged_gather(p2, bt, H) == d2)

    def test_masked_rows_are_dropped_not_written(self):
        """A write-masked row must not land ANYWHERE in the pool — the
        guarantee that a padded prefill chunk (or a non-admitted slot)
        can never touch a page another slot owns."""
        B, T, H, D, ps = 2, 4, 2, 4, 4
        bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
        pool = jnp.full((4, ps, H * D), 7.0)
        new = jnp.ones((B, T, H * D))
        valid = jnp.asarray([[True, True, False, False],
                             [False, False, False, False]])
        out = paged_cache_update(
            pool, new, jnp.asarray([0, 0], jnp.int32), bt, valid=valid
        )
        assert jnp.all(out[0, :2] == 1.0)  # the two valid rows landed
        assert jnp.all(out[0, 2:] == 7.0)  # padding dropped
        assert jnp.all(out[1:] == 7.0)  # slot 1 wrote nothing at all

    def test_positions_past_virtual_capacity_dropped(self):
        """lengths + T past pages_per_slot×ps must drop, not wrap into
        the slot's last page."""
        pool = jnp.zeros((4, 4, 2))
        bt = jnp.asarray([[0, 1]], jnp.int32)  # capacity 8
        out = paged_cache_update(
            pool, jnp.ones((1, 2, 2)), jnp.asarray([7], jnp.int32), bt
        )
        assert float(out.sum()) == 2.0  # position 7 landed, 8 dropped

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
    @pytest.mark.parametrize("t", [1, 5, 8, 20])
    def test_page_write_kernel_is_the_scatter(self, t, dtype):
        """The pool's writer a page at a time (the path a prefill chunk
        takes on the chip) lands exactly what the row scatter lands:
        ragged starts, a masked row here and there, starts so late that
        rows run past the slot's table (dropped), whole and part pages."""
        P_, ps, w, B = 24, 8, 128, 3
        rng = np.random.RandomState(t)
        bt = jnp.asarray(rng.permutation(P_)[: B * 6].reshape(B, 6), jnp.int32)
        pool = jnp.asarray(rng.randint(-5, 5, size=(P_, ps, w)), dtype)
        new = jnp.asarray(rng.randint(-100, 100, size=(B, t, w)), dtype)
        ragged = rng.randint(0, 6 * ps - t + 3, size=B)
        some = jnp.asarray(rng.rand(B, t) < 0.8)
        # Starts anywhere, and every start on a page (a chunked prefill's
        # usual case); a mask, and none.
        for lens in (ragged, ragged // ps * ps):
            lens = jnp.asarray(lens, jnp.int32)
            for valid in (some, None):
                want = paged_cache_update(pool, new, lens, bt, valid=valid)
                got = paged_write_pages(
                    pool, new, lens, bt, valid, interpret=True
                )
                assert got.dtype == pool.dtype
                np.testing.assert_array_equal(
                    np.asarray(got, np.float32), np.asarray(want, np.float32)
                )

    def test_update_takes_the_page_writer_for_a_chunk_only(self, monkeypatch):
        """``paged_cache_update`` chooses by what it can see: on a TPU
        (steered here, the writer through the interpreter) a page's
        worth of lane-aligned rows goes by pages (kernel), fewer rows or
        a narrow scale plane by the scatter."""
        from mpit_tpu.models import gpt2
        from mpit_tpu.ops import decode_attention
        from mpit_tpu.ops.kv_quant import QuantizedKV

        ps, w, h = 8, 256, 2
        bt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
        lens = jnp.asarray([3, 0], jnp.int32)
        pool = QuantizedKV(
            q=jnp.zeros((4, ps, w), jnp.int8),
            scale=jnp.zeros((4, ps, h), jnp.float32),
        )

        def kernels(t):
            new = jnp.ones((2, t, w), jnp.float32)
            jx = jax.make_jaxpr(
                lambda p, n: paged_cache_update(p, n, lens, bt)
            )(pool, new)
            return str(jx).count("paged_kv_write")

        new = jnp.asarray(
            np.random.RandomState(0).randn(2, ps, w), jnp.float32
        )
        assert kernels(ps) == 0  # the platform here is the CPU
        by_row = paged_cache_update(pool, new, lens, bt)
        monkeypatch.setattr(decode_attention, "_use_kernel", lambda _: True)
        monkeypatch.setattr(
            gpt2, "paged_write_pages",
            functools.partial(paged_write_pages, interpret=True),
        )
        assert kernels(ps) == 1  # the payload; the scale plane scatters
        assert kernels(ps - 1) == 0
        by_page = paged_cache_update(pool, new, lens, bt)
        assert jnp.all(by_page.q == by_row.q)
        assert jnp.all(by_page.scale == by_row.scale)

    def test_reference_is_cached_attention_over_the_gathered_view(self):
        """The in-module reference IS models.gpt2.cached_attention over
        each slot's gathered pages — pinned bitwise so the two cannot
        drift (the oracle every kernel test below leans on)."""
        q, k, v = _qkv_cache()
        kp, vp, bt = _as_pool(k, v, 8)
        lengths = jnp.asarray([0, 5, 17, 39], jnp.int32)
        a = reference_paged_decode_attention(q, kp, vp, lengths, bt)
        assert jnp.all(a == cached_attention(q, k, v, lengths))

    @pytest.mark.parametrize("block_k", [4, 8, None])
    def test_kernel_matches_reference_ragged_lengths(self, block_k):
        q, kp, vp, bt = _paged_setup()
        lengths = jnp.asarray([0, 13, 31], jnp.int32)
        ref = reference_paged_decode_attention(q, kp, vp, lengths, bt)
        out = flash_paged_decode_attention(
            q, kp, vp, lengths, bt, block_k=block_k, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    # name: (_qkv_cache's shape, page size, lengths, tolerance). Against
    # cached_attention over the slots' own buffers, the pool made of them.
    _SHAPES = {
        # Ragged lengths incl. 0 (just-admitted), S-1 (one free row),
        # page boundaries, and a stale mid value (retired slot).
        "ragged-16-row-pages": (
            dict(B=6, S=32), 16, [0, 7, 8, 9, 31, 13], 2e-5),
        "ragged-one-page-a-slot": (
            dict(B=6, S=32), 32, [0, 7, 8, 9, 31, 13], 2e-5),
        # T > 1 (the prefill-tail trace): query t sees keys <= L + t.
        "tail-T4": (dict(B=3, T=4, S=24), 8, [0, 5, 20], 2e-5),
        "odd-heads-and-head-dim": (
            dict(B=2, H=5, D=12, S=16), 8, [3, 15], 2e-5),
        "bf16": (
            dict(S=32, dtype=jnp.bfloat16), 16, [0, 9, 16, 31], 0.05),
    }

    @pytest.mark.parametrize("case", sorted(_SHAPES))
    def test_kernel_matches_cached_attention_at_shapes(self, case):
        shape, ps, lengths, tol = self._SHAPES[case]
        q, k, v = _qkv_cache(**shape)
        kp, vp, bt = _as_pool(k, v, ps)
        lengths = jnp.asarray(lengths, jnp.int32)
        ref = cached_attention(q, k, v, lengths)
        out = flash_paged_decode_attention(
            q, kp, vp, lengths, bt, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol,
        )

    @pytest.mark.parametrize("nan_pages", [False, True], ids=["", "nan"])
    @pytest.mark.parametrize("case", sorted(_TILE_CASES))
    def test_kernel_matches_reference_on_a_tile_of_pages(
        self, case, nan_pages
    ):
        """A step of the loop gathers several pages into one tile (16
        pages of 16 rows here) and, at few query rows, multiplies all
        heads at once. Contexts that end inside a page, at a page's end,
        inside and at the end of a tile, and in the first page; pages out
        of order and shared between slots; GPT-2 large's row of 1,280
        lanes and a TP rank's 640. With ``nan_pages`` every page that
        holds no visible key of a slot that maps it is NaN: what the
        kernel does not fetch must not reach a product as the buffer
        held it (``0 x NaN``), on the first program least of all, whose
        buffers nothing has written yet."""
        t, h, dtype, form = _TILE_CASES[case]
        ps, pps = 16, 36
        # L + T visible keys: 4 (first program: untouched buffers), a
        # page's end, inside a page, a tile's end, inside the second
        # tile, the second tile's end, the whole table, one key.
        ends = [4, 32, 23, 256, 300, 512, ps * pps, 1]
        lengths = jnp.asarray([max(e - t, 0) for e in ends], jnp.int32)
        q, kp, vp, bt = _paged_setup(
            B=len(ends), T=t, H=h, D=64, n_pages=150, ps=ps,
            pages_per_slot=pps, seed=t, dtype=dtype,
        )
        kp, vp = kp * 0.25, vp  # scores of unit scale over 64 lanes
        if nan_pages:
            seen = np.zeros(kp.shape[0], bool)
            n_vis = -(-(np.asarray(lengths) + t) // ps)
            for row, n in zip(np.asarray(bt), n_vis):
                seen[row[:n]] = True
            hole = jnp.asarray(~seen)[:, None, None]
            kp = jnp.where(hole, jnp.nan, kp)
            vp = jnp.where(hole, jnp.nan, vp)
        tiling = decode_tiling(t, h, dtype, page_size=ps)
        assert (tiling.form, tiling.rows) == (form, 256)
        # The reference gathers whole tables: judge it on a finite pool
        # (the masked rows weigh exactly 0 there, as here).
        ref = reference_paged_decode_attention(
            q, jnp.nan_to_num(kp), jnp.nan_to_num(vp), lengths, bt
        )
        out, visited = flash_paged_decode_attention(
            q, kp, vp, lengths, bt, block_k=8, interpret=True,
            return_visited=True,
        )
        tol = 2e-5 if dtype == jnp.float32 else 0.05
        assert np.isfinite(np.asarray(out, np.float32)).all()
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol,
        )
        host = num_kv_blocks(np.asarray(lengths), t, ps * pps, 8)
        assert list(np.asarray(visited)) == list(host)

    def test_kernel_prefill_tail_small_t(self):
        q, kp, vp, bt = _paged_setup(T=4)
        lengths = jnp.asarray([0, 9, 21], jnp.int32)
        ref = reference_paged_decode_attention(q, kp, vp, lengths, bt)
        out = flash_paged_decode_attention(
            q, kp, vp, lengths, bt, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_shared_pages_attend_identically(self):
        """Two slots mapping the SAME pages at the same length produce
        identical outputs for identical queries — prefix sharing in
        kernel form."""
        q, kp, vp, bt = _paged_setup()
        q = q.at[2].set(q[0])  # same query; bt[2] == bt[0] already
        lengths = jnp.asarray([13, 5, 13], jnp.int32)
        out = flash_paged_decode_attention(
            q, kp, vp, lengths, bt, block_k=4, interpret=True
        )
        assert jnp.all(out[0] == out[2])

    def test_non_tpu_fallback_is_reference_bitwise(self):
        q, kp, vp, bt = _paged_setup()
        lengths = jnp.asarray([2, 11, 27], jnp.int32)
        out = flash_paged_decode_attention(q, kp, vp, lengths, bt)
        ref = paged_cached_attention(q, kp, vp, lengths, bt)
        assert jnp.all(out == ref)

    def test_visited_tiles_length_dependent_and_match_host(self):
        """Tile skipping survives the indirection: the in-kernel bound
        over the VIRTUAL per-slot cache equals the host formula."""
        q, kp, vp, bt = _paged_setup()
        s_virtual = bt.shape[1] * kp.shape[1]  # 32
        lengths = jnp.asarray([0, 13, 31], jnp.int32)
        _, visited = flash_paged_decode_attention(
            q, kp, vp, lengths, bt, block_k=4, interpret=True,
            return_visited=True,
        )
        host = num_kv_blocks(np.asarray(lengths), 1, s_virtual, 4)
        assert list(np.asarray(visited)) == list(host) == [1, 4, 8]

    def test_block_k_must_divide_page_size(self):
        """A tile must never straddle pages — validated HERE, on the
        CPU fallback too, not first at TPU deploy (and the fallback's
        visited-block accounting must never describe a tiling the kernel
        can't run)."""
        q, kp, vp, bt = _paged_setup(ps=8)
        with pytest.raises(ValueError, match="divisible"):
            flash_paged_decode_attention(
                q, kp, vp, jnp.zeros((3,), jnp.int32), bt, block_k=6
            )

    def test_tp_head_shard_call(self, world_2d):
        """The paged kernel on an H/P head shard inside shard_map (the
        TP paged engine's exact call)."""
        q, kp, vp, bt = _paged_setup(H=4)
        lengths = jnp.asarray([2, 19, 30], jnp.int32)
        ref = paged_cached_attention(q, kp, vp, lengths, bt)

        f = world_2d.shard_map(
            lambda q, kp, vp: flash_paged_decode_attention(
                q, kp, vp, lengths, bt, interpret=True
            ),
            in_specs=(P(None, None, "model"), P(None, None, "model"),
                      P(None, None, "model")),
            out_specs=P(None, None, "model"),
            check_vma=False,
        )
        out = jax.jit(f)(q, kp, vp)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )


class TestLengthDependence:
    """THE perf acceptance on a CPU runner: the kernel's k-loop bound —
    written out by the kernel itself — is length-dependent, and short
    contexts execute fewer tiles than max_len/block_k."""

    def test_visited_tiles_scale_with_length_not_cache(self):
        S, bk = 64, 8
        q, k, v = _qkv_cache(B=4, S=S)
        lengths = jnp.asarray([0, 7, 30, 63], jnp.int32)
        kp, vp, bt = _as_pool(k, v, 16)
        _, visited = flash_paged_decode_attention(
            q, kp, vp, lengths, bt, block_k=bk, interpret=True,
            return_visited=True,
        )
        total = S // bk
        want = [1, 1, 4, 8]  # ceil((L+1)/8)
        assert list(np.asarray(visited)) == want
        assert int(visited[0]) < total and int(visited[1]) < total

    @pytest.mark.parametrize("interpret", [True, None],
                             ids=["kernel", "reference"])
    def test_bound_matches_host_formula(self, interpret):
        """The in-kernel bound and, off the TPU, what the reference path
        reports: one host formula."""
        S, bk, T = 48, 8, 3
        q, k, v = _qkv_cache(B=5, T=T, S=S)
        kp, vp, bt = _as_pool(k, v, 16)
        lengths = jnp.asarray([0, 4, 8, 21, 45], jnp.int32)
        _, visited = flash_paged_decode_attention(
            q, kp, vp, lengths, bt, block_k=bk, interpret=interpret,
            return_visited=True,
        )
        host = num_kv_blocks(np.asarray(lengths), T, S, bk)
        assert list(np.asarray(visited)) == list(host)

    def test_pick_block_k(self):
        assert pick_block_k(1024) == 256
        assert pick_block_k(128) == 32
        assert pick_block_k(40) == 8
        assert pick_block_k(8) == 8
        assert pick_block_k(1024, 128) == 128
        # nothing divides: one whole-buffer tile (no skipping, still
        # correct)
        assert pick_block_k(7) == 7


class TestLMHeadSample:
    """Blocked decode head vs full-logits oracles."""

    def _setup(self, S=5, D=24, V=203, seed=0):
        rng = np.random.RandomState(seed)
        h = jnp.asarray(rng.randn(S, D).astype(np.float32))
        head = jnp.asarray(0.3 * rng.randn(V, D).astype(np.float32))
        return h, head

    @staticmethod
    def _gumbel_field(key, S, V, block):
        """The sampling contract: block i draws from fold_in(key, i)."""
        n_blocks = math.ceil(V / block)
        return jnp.concatenate(
            [
                jax.random.gumbel(
                    jax.random.fold_in(key, i), (S, block), jnp.float32
                )
                for i in range(n_blocks)
            ],
            axis=-1,
        )[:, :V]

    @classmethod
    def _oracle(cls, logits, key, temp, topk, block):
        """Full-logits sampler with identical semantics: top-k keeps
        logits >= the k-th largest (ties included), Gumbel-argmax on
        temperature-scaled survivors, greedy for temp <= 0."""
        S, V = logits.shape
        g = cls._gumbel_field(key, S, V, block)
        t = jnp.maximum(temp, 1e-6)[:, None]
        scaled = logits / t + g
        sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
        kidx = jnp.clip(topk - 1, 0, V - 1)
        thr = jnp.take_along_axis(sorted_desc, kidx[:, None], -1)
        masked = jnp.where(
            (topk[:, None] > 0) & (logits < thr), -jnp.inf, scaled
        )
        samp = jnp.argmax(masked, -1).astype(jnp.int32)
        return jnp.where(
            temp <= 0, jnp.argmax(logits, -1).astype(jnp.int32), samp
        )

    # The three shapes the blocked head tells apart (``_block_runner``),
    # as ``(vocab, block_size)``, each under a plain and an int8 head:
    # the block divides the vocabulary (one scan over a reshape, as it
    # always was); full blocks and a ragged tail (the scan reads the
    # table where it lies, the tail's LOGITS are padded); a vocabulary
    # under one block (the tail alone).
    SHAPES = {
        "divides": (256, 64),
        "ragged_tail": (203, 64),  # 3 blocks and 11 rows
        "under_one_block": (50, 8192),  # clamped to 128: 50 rows, no scan
    }
    shapes = pytest.mark.parametrize("shape", list(SHAPES))
    heads = pytest.mark.parametrize("quantized", [False, True],
                                    ids=["plain", "int8"])

    def _case(self, shape, quantized):
        """``(h, head, full logits, resolved block)`` of one shape."""
        from mpit_tpu.ops.quantized_matmul import quantize_tensor

        V, block_size = self.SHAPES[shape]
        h, head = self._setup(V=V)
        if quantized:
            head = quantize_tensor(head)
        full = self._full_logits(h, head, jnp.float32)
        return h, head, full, block_size, min(block_size, V + (-V) % 128)

    @shapes
    @heads
    def test_greedy_bitmatches_full_argmax(self, shape, quantized):
        h, head, full, block_size, _ = self._case(shape, quantized)
        got = lm_head_sample(
            h, head, jax.random.key(3),
            jnp.zeros((5,), jnp.float32), jnp.zeros((5,), jnp.int32),
            block_size=block_size,
        )
        assert jnp.all(got == jnp.argmax(full, -1))

    @shapes
    @heads
    @pytest.mark.parametrize(
        "t_val,k_val", [(1.0, 0), (0.7, 5), (2.5, 1), (1.0, 128), (0.5, 17)]
    )
    def test_topk_temperature_match_oracle_under_fixed_key(
        self, t_val, k_val, shape, quantized
    ):
        h, head, full, block_size, block = self._case(shape, quantized)
        key = jax.random.key(7)
        temp = jnp.full((5,), t_val, jnp.float32)
        topk = jnp.full((5,), k_val, jnp.int32)
        got = lm_head_sample(h, head, key, temp, topk, block_size=block_size)
        want = self._oracle(full, key, temp, topk, block)
        assert jnp.all(got == want)

    @shapes
    @heads
    def test_per_slot_mixed_modes(self, shape, quantized):
        h, head, full, block_size, block = self._case(shape, quantized)
        key = jax.random.key(11)
        temp = jnp.asarray([0.0, 1.0, 0.5, 2.0, -1.0], jnp.float32)
        topk = jnp.asarray([0, 0, 3, 50, 7], jnp.int32)
        got = lm_head_sample(h, head, key, temp, topk, block_size=block_size)
        assert jnp.all(got == self._oracle(full, key, temp, topk, block))

    # -- the greedy path (ISSUE 33): a call in which no row samples takes
    # one max and one argmax a block and nothing else ---------------------
    @staticmethod
    def _exact_setup(S=6, D=24, V=203, seed=0):
        """Small whole numbers: every product and every partial sum is
        exact in bfloat16 operands and float32 accumulation alike, in
        whatever order a backend adds, so logits TIE often and a tie is
        a tie for the blocked head and the oracle both."""
        rng = np.random.RandomState(seed)
        h = jnp.asarray(rng.randint(-3, 4, (S, D)).astype(np.float32))
        head = jnp.asarray(rng.randint(-2, 3, (V, D)).astype(np.float32))
        return h, head

    @staticmethod
    def _full_logits(h, head, cd):
        from mpit_tpu.ops.quantized_matmul import (
            QuantizedTensor, dequantize_tensor,
        )

        if isinstance(head, QuantizedTensor):
            head = dequantize_tensor(head)
        return jnp.dot(
            h.astype(cd), head.astype(cd).T,
            preferred_element_type=jnp.float32,
        )

    @pytest.mark.parametrize("cd", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "case", ["ties", "ragged_vocab", "one_block", "quantized_head"]
    )
    def test_all_greedy_batch_is_full_argmax(self, case, cd):
        """Bit for bit ``argmax`` of the full float32 logits, first
        occurrence on a tie, across and within blocks."""
        from mpit_tpu.ops.quantized_matmul import quantize_tensor

        block = 64
        if case == "ties":
            # Rows 7, 70 (another block) and 71 (the same block) repeat
            # row 3; h's first row is chosen to make them the maximum.
            h, head = self._exact_setup(V=256)
            head = head.at[jnp.asarray([7, 70, 71])].set(head[3])
            h = h.at[0].set(3.0 * jnp.sign(head[3]))
        elif case == "ragged_vocab":
            h, head = self._exact_setup(V=203)  # 3 blocks and 11 rows
        elif case == "one_block":
            h, head = self._exact_setup(V=50)
            block = 8192  # clamped to the vocabulary rounded up to 128
        else:
            h, head = self._exact_setup(V=203)
            head = quantize_tensor(head)
        full = self._full_logits(h, head, cd)
        S = h.shape[0]
        got = lm_head_sample(
            h, head, jax.random.key(3), jnp.zeros((S,), jnp.float32),
            jnp.zeros((S,), jnp.int32), block_size=block, compute_dtype=cd,
        )
        want = jnp.argmax(full, -1).astype(jnp.int32)
        assert got.dtype == jnp.int32 and jnp.all(got == want)
        if case == "ties":
            assert int(got[0]) == 3
            assert int(jnp.sum(full[0] == full[0].max())) >= 4

    @pytest.mark.parametrize("cd", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "temps",
        [
            (0.0, 0.8, 0.0, 0.0, -1.0, 0.0),  # one sampling row
            (0.5, 0.0, 2.0, 0.0, 1.0, 0.7),  # mostly sampling rows
        ],
    )
    def test_greedy_rows_do_not_depend_on_their_neighbours_path(
        self, temps, cd
    ):
        """A sampling row sends the whole call down the general path;
        the greedy rows' tokens are the all-greedy call's same rows, and
        the sampled and top-k rows are the full-logits oracle's under
        the same key (what the function gave before it had two paths)."""
        h, head = self._exact_setup()
        key = jax.random.key(11)
        temp = jnp.asarray(temps, jnp.float32)
        topk = jnp.asarray([0, 0, 3, 50, 7, 0], jnp.int32)
        zeros = jnp.zeros_like(temp)
        all_greedy = lm_head_sample(
            h, head, key, zeros, topk, block_size=64, compute_dtype=cd
        )
        mixed = lm_head_sample(
            h, head, key, temp, topk, block_size=64, compute_dtype=cd
        )
        greedy = np.asarray(temp) <= 0
        assert greedy.any() and not greedy.all()
        assert np.array_equal(
            np.asarray(mixed)[greedy], np.asarray(all_greedy)[greedy]
        )
        full = self._full_logits(h, head, cd)
        assert jnp.all(mixed == self._oracle(full, key, temp, topk, 64))
        assert jnp.all(all_greedy == jnp.argmax(full, -1))

    def test_path_is_chosen_on_the_device_under_jit(self):
        """One compiled function serves both kinds of call: the
        predicate is data, not a trace-time constant."""
        h, head = self._exact_setup()
        key = jax.random.key(5)
        topk = jnp.zeros((6,), jnp.int32)
        f = jax.jit(
            lambda t: lm_head_sample(h, head, key, t, topk, block_size=64)
        )
        full = self._full_logits(h, head, jnp.float32)
        zeros = jnp.zeros((6,), jnp.float32)
        assert jnp.all(f(zeros) == jnp.argmax(full, -1))
        warm = jnp.full((6,), 1.3, jnp.float32)
        assert jnp.all(f(warm) == self._oracle(full, key, warm, topk, 64))
        assert f._cache_size() == 1

    def test_greedy_branch_holds_no_sampling_work(self):
        """The jaxpr of the branch a greedy call takes: no sort, no
        noise, no division, no gather; the other branch has them all."""
        from mpit_tpu.analysis import jaxpr_check as jc

        h, head = self._setup()
        jx = jax.make_jaxpr(
            lambda h, w, t, k: lm_head_sample(
                h, w, jax.random.key(0), t, k, block_size=64
            )
        )(h, head, jnp.zeros((5,), jnp.float32), jnp.zeros((5,), jnp.int32))
        greedy, general = jc.sampler_branches(jx)
        jc.assert_no_primitive(greedy, jc.SAMPLING_PRIMS)
        assert {"top_k", "random_bits", "div", "gather"} <= set(
            jc.find_primitives(general, jc.SAMPLING_PRIMS)
        )
        # One product a block on either path, and the scan's carry on
        # the greedy one is two vectors of a value a row.
        assert "dot_general" in jc.find_primitives(greedy, {"dot_general"})
        (scan,) = [
            e for e in jc._walk_eqns(greedy) if e.primitive.name == "scan"
        ]
        assert scan.params["num_carry"] == 2

    @staticmethod
    def _trace(fn, V, quantized, S=5, D=24):
        """The jaxpr of the blocked sampler or verifier over a head of
        ``V`` rows in blocks of 64."""
        from mpit_tpu.ops.lm_head import lm_head_verify
        from mpit_tpu.ops.quantized_matmul import quantize_tensor

        h = jnp.zeros((S, D), jnp.float32)
        head = jnp.zeros((V, D), jnp.float32)
        if quantized:
            head = quantize_tensor(head)
        temp = jnp.ones((S,), jnp.float32)
        topk = jnp.zeros((S,), jnp.int32)
        if fn == "sample":
            return jax.make_jaxpr(
                lambda h, w: lm_head_sample(
                    h, w, jax.random.key(0), temp, topk, block_size=64
                )
            )(h, head)
        return jax.make_jaxpr(
            lambda h, w, q: lm_head_verify(
                h, w, topk, q, jax.random.key(0), temp, topk,
                block_size=64, k_cap=8,
            )
        )(h, head, jnp.zeros((S, V), jnp.float32))

    @classmethod
    def _products_outside_scans(cls, jaxpr):
        """``dot_general`` equations that no ``scan`` holds: the ticks a
        blocked head runs beside its loop over the full blocks."""
        from mpit_tpu.analysis import jaxpr_check as jc

        n = 0
        for e in jc._as_jaxpr(jaxpr).eqns:
            if e.primitive.name == "dot_general":
                n += 1
            elif e.primitive.name != "scan":
                for p in e.params.values():
                    for sub in jc.sub_jaxprs(p):
                        n += cls._products_outside_scans(sub)
        return n

    @pytest.mark.parametrize("quantized", [False, True], ids=["plain", "int8"])
    @pytest.mark.parametrize("fn", ["sample", "verify"])
    def test_the_table_is_never_rebuilt(self, fn, quantized):
        """A ragged vocabulary is read where it lies: nothing in the
        jaxpr concatenates or pads to an array of ``d_model`` columns
        (the table, or an int8 table's rows; ``[rows, 1]`` would be its
        scales), and the tail is one tick more on each path (the
        sampler's two branches; the verifier's two passes). Where the
        block divides the vocabulary there is no tail tick at all and
        nothing is padded."""
        from mpit_tpu.analysis import jaxpr_check as jc

        D, ticks = 24, 2
        ragged = self._trace(fn, 203, quantized, D=D)
        rebuilt = [
            (e.primitive.name, v.aval.shape)
            for e in jc._walk_eqns(ragged)
            if e.primitive.name in ("concatenate", "pad")
            for v in e.outvars
            if v.aval.shape[-1] in (D, 1)
        ]
        assert not rebuilt, rebuilt
        assert self._products_outside_scans(ragged) == ticks
        assert jc.find_primitives(ragged, {"pad"}) == ["pad"] * ticks
        assert jc.find_primitives(ragged, {"dynamic_slice"})
        divides = self._trace(fn, 256, quantized, D=D)
        assert self._products_outside_scans(divides) == 0
        assert not jc.find_primitives(divides, {"pad", "dynamic_slice"})
        # One block holds the whole vocabulary: the tail alone, no scan.
        alone = self._trace(fn, 50, quantized, D=D)
        assert self._products_outside_scans(alone) == ticks
        assert not jc.find_primitives(alone, {"scan"})

    def test_no_full_logits_in_jaxpr(self):
        """The pin, same style as the training LM-head: no [S, vocab]
        f32 intermediate anywhere in the jaxpr when block < vocab."""
        h, head = self._setup()
        S, V = 5, head.shape[0]
        temp = jnp.ones((S,), jnp.float32)
        topk = jnp.zeros((S,), jnp.int32)
        jx = jax.make_jaxpr(
            lambda h, w: lm_head_sample(
                h, w, jax.random.key(0), temp, topk, block_size=64
            )
        )(h, head)
        assert not _avals_with_shape(jx.jaxpr, (S, V))


# The materialization detector now lives in mpit_tpu.analysis (ISSUE
# 14 satellite): ONE audited implementation shared by these pins, the
# serve pins and the analyzer's whole-package contract sweep. Same
# semantics as the old private helper (recursive over nested
# call/scan/cond jaxprs, returns [(primitive_name, aval), ...]).
from mpit_tpu.analysis.jaxpr_check import find_avals as _avals_with_shape  # noqa: E402


@pytest.mark.slow
class TestDecodeKernelCompiles:
    """Real-compiler check (no hardware): AOT-compile the flash-decode
    kernel at the serving shapes against a described v5e topology
    (conftest's ``v5e_world``)."""

    @pytest.mark.parametrize("t,h", [(1, 12), (64, 12), (1, 6)])
    def test_paged_kernel_compiles_at_serving_shapes(self, v5e_world, t, h):
        """The kernel through the real compiler: SMEM
        block-table indirection + per-tile DMA source resolution at a
        production-ish pool geometry."""
        from mpit_tpu.utils.aot import abstractify

        world = v5e_world
        d, ps, n_pages, per_slot = 64, 64, 2048, 16

        def f(q, kp, vp, lengths, bt):
            return flash_paged_decode_attention(
                q, kp, vp, lengths, bt, interpret=False
            )

        step = jax.jit(
            world.shard_map(
                f,
                in_specs=(P("data"), P(), P(), P("data"), P("data")),
                out_specs=P("data"),
            )
        )
        B = 8
        mk = lambda shp, dt, spec: abstractify(
            jax.ShapeDtypeStruct(shp, dt), world.mesh, spec
        )
        step.lower(
            mk((8 * B, t, h, d), jnp.bfloat16, P("data")),
            mk((n_pages, ps, h * d), jnp.bfloat16, P()),
            mk((n_pages, ps, h * d), jnp.bfloat16, P()),
            mk((8 * B,), jnp.int32, P("data")),
            mk((8 * B, per_slot), jnp.int32, P("data")),
        ).compile()
