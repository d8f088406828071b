"""Learned sparse attention (DSA) over a paged pool: score, choose, attend.

A layer with an *indexer* keeps, beside the latent and the rotary key of
latent attention (``ops/mla_attention.py``), one small **index key** a
cached position, in a third seat of the page pool. For a query the
indexer scores every cached position,

    I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])        (float32)

over its ``index_n_heads`` heads ``j``, and attention then reads the
``index_topk`` positions with the largest ``I[t, s]`` and no others. Three
operations, each with a lax twin that the CPU runs and the tests compare:

- :func:`dsa_index_scores`: a tick's one row a slot, or a chunk's rows,
  against the slot's index-key pages, read in place through the block
  table; relu and the weighted head sum inside; ``[B, T, S]`` float32 out
  with ``-inf`` where the position is not visible to the query. A Pallas
  kernel on the TPU (``dsa_index_scores_tick`` where ``T`` is 1,
  ``dsa_index_scores_chunk`` else).
- :func:`dsa_select`: the SET of the ``k`` largest a row (all visible ones
  while fewer than ``k`` are), as a mask. No sort: the ``k``-th value is
  found by bisection over the scores' bits (32 passes of compare and
  count), then one compare. Equal scores at the threshold go to the
  earlier position, as ``lax.top_k`` puts them. :func:`mask_to_rows` turns
  a tick's mask into the ``k`` row numbers a gather needs, with matrix
  products in place of a sort or a scatter.
- :func:`dsa_sparse_attn`: absorbed latent attention of a decode tick
  over the chosen rows of the pool: gather the rows (``heads`` query rows
  against ``k`` gathered rows of ``C + R``), two products, a softmax.
  Plain XLA under the scope ``dsa_sparse_attn``.

A chunk of a prompt attends in the expanded form a tile at a time
(``mla_attention.mla_paged_prefill_attention``) with the mask as a second
condition on visibility.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.ops import decode_attention as _da

__all__ = [
    "dsa_index_scores",
    "reference_dsa_index_scores",
    "dsa_select",
    "mask_to_rows",
    "dsa_sparse_attn",
]

_NEG_INF = -1e30  # attention logits: exp underflows to exactly 0.0
_Q_TILE = 64  # query rows a kernel step (x index heads = matmul rows)


def _divisor_at_most(n: int, most: int) -> int:
    d = max(1, min(n, most))
    while n % d:
        d -= 1
    return d


# -- index scores ---------------------------------------------------------------


def reference_dsa_index_scores(q, w, key_pool, lengths, block_table, *,
                               tile: int = 1024):
    """The lax twin: ``q`` [B, T, Hi, D] (rotated), ``w`` [B, T, Hi] float32
    (scaled), the index-key pool ``[P, ps, D]``, query ``t`` of slot ``b``
    at position ``lengths[b] + t``. Returns ``[B, T, S]`` float32, ``S`` =
    ``pages_per_slot x ps``, ``-inf`` where ``s`` is past the query's
    position. A tile of positions at a time: the per-head logits of a
    whole slot would be ``Hi`` times the result."""
    b, t, _, _ = q.shape
    ps, npg = key_pool.shape[1], block_table.shape[1]
    tp = _divisor_at_most(npg, max(1, tile // ps))
    tk = tp * ps
    last_page = key_pool.shape[0] - 1
    t_pos = lengths[:, None] + jnp.arange(t)[None, :]
    wf = w.astype(jnp.float32)

    def one(i):
        pages = jnp.clip(
            lax.dynamic_slice_in_dim(block_table, i * tp, tp, axis=1),
            0, last_page)
        keys = key_pool[pages].reshape(b, tk, -1)
        logit = jnp.einsum("bthd,bkd->bthk", q, keys,
                           preferred_element_type=jnp.float32)
        sc = jnp.einsum("bthk,bth->btk", jnp.maximum(logit, 0.0), wf,
                        precision=lax.Precision.HIGHEST)
        k_pos = i * tk + jnp.arange(tk)
        return jnp.where(k_pos[None, None, :] <= t_pos[:, :, None], sc,
                         -jnp.inf)

    out = lax.map(one, jnp.arange(npg // tp))  # [n, B, T, tk]
    return jnp.moveaxis(out, 0, 2).reshape(b, t, npg * ps)


def _index_kernel(lens_ref, bt_ref, q_ref, w_ref, k_ref, o_ref, *, tq, hi,
                  ps):
    """One (slot, query tile, key page): ``q_ref`` [1, 1, hi * tq, D] holds
    the tile's rows head-major (row ``j * tq + t``), ``w_ref`` the weights
    in the same order as a column, ``k_ref`` [1, ps, D] the page the block
    table names. A page wholly past the tile's last position is not
    computed (its DMA is spared by the index map, which stays on the last
    page needed)."""
    del bt_ref
    b, qi, pi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    first = lens_ref[b] + qi * tq
    k0 = pi * ps

    @pl.when(k0 <= first + tq - 1)
    def _compute():
        logit = lax.dot_general(
            q_ref[0, 0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [hi * tq, ps]
        weighed = jnp.maximum(logit, 0.0) * w_ref[0, 0]
        if tq == 1:
            sc = jnp.sum(weighed, axis=0, keepdims=True)
        else:
            sc = weighed[0:tq]
            for j in range(1, hi):
                sc = sc + weighed[j * tq:(j + 1) * tq]
        q_pos = first + lax.broadcasted_iota(jnp.int32, (tq, ps), 0)
        k_pos = k0 + lax.broadcasted_iota(jnp.int32, (tq, ps), 1)
        o_ref[0] = jnp.where(k_pos <= q_pos, sc, -jnp.inf)

    @pl.when(k0 > first + tq - 1)
    def _masked():
        o_ref[0] = jnp.full((tq, ps), -jnp.inf, jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _index_call(q, w, key_pool, lengths, block_table, *, interpret):
    b, t, hi, d = q.shape
    ps, npg = key_pool.shape[1], block_table.shape[1]
    tq = _divisor_at_most(t, _Q_TILE)
    nq = t // tq
    # A tile's rows head-major, so that the sum over heads is a sum of
    # ``hi`` contiguous row blocks (no reshape inside the kernel).
    q2 = q.reshape(b, nq, tq, hi, d).transpose(0, 1, 3, 2, 4).reshape(
        b, nq, hi * tq, d)
    w2 = w.astype(jnp.float32).reshape(b, nq, tq, hi).transpose(
        0, 1, 3, 2).reshape(b, nq, hi * tq, 1)
    bt = jnp.clip(block_table, 0, key_pool.shape[0] - 1).astype(jnp.int32)

    def page(bi, qi, pi, lens, table):
        last = (lens[bi] + (qi + 1) * tq - 1) // ps  # the last page needed
        return (table[bi, jnp.minimum(pi, jnp.minimum(last, npg - 1))], 0, 0)

    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nq, npg),
        in_specs=[
            pl.BlockSpec((1, 1, hi * tq, d),
                         lambda bi, qi, pi, *_: (bi, qi, 0, 0)),
            pl.BlockSpec((1, 1, hi * tq, 1),
                         lambda bi, qi, pi, *_: (bi, qi, 0, 0)),
            pl.BlockSpec((1, ps, d), page),
        ],
        out_specs=pl.BlockSpec((1, tq, ps),
                               lambda bi, qi, pi, *_: (bi, qi, pi)),
    )
    return pl.pallas_call(
        functools.partial(_index_kernel, tq=tq, hi=hi, ps=ps),
        # A tick's calls and a chunk's under names of their own: their
        # least bytes follow from different counts.
        name="dsa_index_scores_tick" if t == 1 else "dsa_index_scores_chunk",
        grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((b, t, npg * ps), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=bool(interpret),
    )(jnp.asarray(lengths, jnp.int32), bt, q2, w2, key_pool)


def dsa_index_scores(q, w, key_pool, lengths, block_table, *,
                     interpret: bool | None = None):
    """Index scores ``[B, T, S]`` float32 of ``q`` [B, T, Hi, D] with head
    weights ``w`` [B, T, Hi] against each slot's index-key pages (see
    :func:`reference_dsa_index_scores`). ``interpret`` as in
    :func:`~mpit_tpu.ops.decode_attention.flash_paged_decode_attention`."""
    if not _da._use_kernel(interpret):
        return reference_dsa_index_scores(q, w, key_pool, lengths,
                                          block_table)
    return _index_call(
        q, w, key_pool, lengths, block_table,
        interpret=bool(interpret) if interpret is not None else False)


# -- the choice -------------------------------------------------------------------


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    bits = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return lax.bitcast_convert_type(bits, jnp.uint32) ^ jnp.uint32(1 << 31)


def dsa_select(scores, k: int):
    """The set of the ``k`` largest of each row of ``scores`` [..., S]
    (``-inf``: never chosen), as a boolean mask; every finite entry where
    a row has ``k`` or fewer. The same set as ``lax.top_k``'s wherever no
    two scores are equal, and where some are, the earlier position wins,
    as it does there."""
    with jax.named_scope("dsa_select"):
        key = _ordered_bits(scores)
        seen = scores > -jnp.inf
        count = lambda m: jnp.sum(m, axis=-1, keepdims=True, dtype=jnp.int32)

        def bit(i, thr):
            cand = thr | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
                jnp.uint32)))
            return jnp.where(count(key >= cand) >= k, cand, thr)

        # The largest value that k entries reach: the k-th largest (0,
        # which every key reaches, where the row has fewer than k).
        thr = lax.fori_loop(0, 32, bit,
                            jnp.zeros((*scores.shape[:-1], 1), jnp.uint32))
        above = (key > thr) & seen
        level = (key == thr) & seen
        room = k - count(above)

        def ties(_):
            return above | (level & (jnp.cumsum(level, axis=-1,
                                                dtype=jnp.int32) <= room))

        # More entries at the threshold than there is room for: equal
        # scores (a scan along the row settles them; all but never run).
        return lax.cond(jnp.any(count(level) > room), ties,
                        lambda _: above | level, None)


def mask_to_rows(mask, k: int, block: int = 128):
    """The positions of the first ``k`` set entries of each row of
    ``mask`` [R, S], ascending: ``(rows [R, k] int32, n [R] int32)``,
    ``n`` = how many of them are real (the rest are 0). The row is cut
    into blocks of ``block``; a one-hot product fetches the block that
    holds the ``j``-th set entry and a triangular product counts inside
    it: matrix products and compares, no sort, no scatter."""
    with jax.named_scope("dsa_select"):
        r, s = mask.shape
        pad = (-s) % block
        m = jnp.pad(mask, ((0, 0), (0, pad))) if pad else mask
        nb = m.shape[1] // block
        m3 = m.reshape(r, nb, block).astype(jnp.bfloat16)  # 0 / 1: exact
        cnt = jnp.sum(m3, axis=-1, dtype=jnp.float32)  # [R, nb]
        cum = jnp.cumsum(cnt, axis=-1)  # set entries up to each block's end
        j = jnp.arange(k, dtype=jnp.float32)
        # The block of the j-th set entry: the blocks that end at or
        # before it, counted.
        b_of = jnp.sum(cum[:, None, :] <= j[None, :, None], axis=-1)
        b_of = jnp.minimum(b_of, nb - 1)
        pick = (b_of[..., None] == jnp.arange(nb)).astype(jnp.bfloat16)
        lanes = jnp.einsum("rkn,rnl->rkl", pick, m3,
                           preferred_element_type=jnp.float32)
        before = jnp.einsum("rkn,rn->rk", pick.astype(jnp.float32),
                            cum - cnt, precision=lax.Precision.HIGHEST)
        tri = jnp.triu(jnp.ones((block, block), jnp.bfloat16))
        upto = jnp.einsum("rkl,lm->rkm", lanes.astype(jnp.bfloat16), tri,
                          preferred_element_type=jnp.float32)
        inside = jnp.sum(upto <= (j[None, :] - before)[..., None], axis=-1)
        n = jnp.minimum(cum[:, -1], k).astype(jnp.int32)
        rows = b_of * block + jnp.minimum(inside, block - 1)
        real = jnp.arange(k)[None, :] < n[:, None]
        return jnp.where(real, rows, 0).astype(jnp.int32), n


# -- attention over the chosen rows ----------------------------------------------------


def dsa_sparse_attn(q_abs, q_rope, ckv_pool, kr_pool, rows, n, block_table,
                    *, scale):
    """Absorbed latent attention over chosen rows: ``q_abs`` [B, H, C],
    ``q_rope`` [B, H, R'] against positions ``rows`` [B, K] of each slot
    (the first ``n`` [B] are real), found in the pools ``[P, ps, C]`` /
    ``[P, ps, R]`` through ``block_table``. Returns the weighted latents
    ``[B, H, C]``. The rows are gathered once (``K x (C + R)`` values a
    slot) and the two products run over the gathered rows."""
    with jax.named_scope("dsa_sparse_attn"):
        ps = ckv_pool.shape[1]
        page = jnp.take_along_axis(
            jnp.clip(block_table, 0, ckv_pool.shape[0] - 1),
            jnp.clip(rows // ps, 0, block_table.shape[1] - 1), axis=1)
        flat = page * ps + rows % ps  # [B, K] rows of the flattened pool
        ckv = ckv_pool.reshape(-1, ckv_pool.shape[-1])[flat]
        kr = kr_pool.reshape(-1, kr_pool.shape[-1])[flat][
            ..., : q_rope.shape[-1]]
        s = jnp.einsum("bhc,bkc->bhk", q_abs, ckv,
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bhr,bkr->bhk", q_rope, kr,
                           preferred_element_type=jnp.float32)
        real = jnp.arange(rows.shape[1])[None, :] < n[:, None]
        p = jax.nn.softmax(
            jnp.where(real[:, None, :], s * scale, _NEG_INF), axis=-1)
        out = jnp.einsum("bhk,bkc->bhc", p.astype(ckv.dtype), ckv,
                         preferred_element_type=jnp.float32)
        return out.astype(q_abs.dtype)
