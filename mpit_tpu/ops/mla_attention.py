"""Latent attention (MLA) against a paged latent pool.

A cached token of a latent-attention layer is one row that all heads
share: the compressed key/value ``c_kv`` (``kv_lora_rank`` values, after
its RMSNorm) and the rotary part of the key ``k_rope`` (after RoPE). The
pool holds them as two buffers a layer, ``[pages, page_size, C]`` and
``[pages, page_size, R]`` with ``R`` the rotary width padded to whole
128-lane tiles (Mosaic DMAs whole tiles only), so a
:class:`~mpit_tpu.serve.kvcache.PagedKVCache` carries them in its ``k``
and ``v`` seats and every page operation of the engine (copy on write,
donation, the page writer) applies unchanged.

Two paths, the same mathematics:

- **Decode, absorbed** (:func:`mla_paged_decode_attention`): the query
  has the key up-projection folded in (``q_abs = q_nope W_UK``), so the
  ``H`` heads of a slot are ``H`` query rows against ONE shared row a
  position. The Pallas kernel reads the pool in place, tile by tile
  through the block table (the paged flash-decode loop with M = heads),
  and returns the attention-weighted latents ``[B, H, C]``; the caller
  folds ``W_UV`` in afterwards. Off the TPU, or in ``reference`` mode,
  :func:`reference_mla_paged_decode_attention` is the same thing over a
  gather of the slots' latents, O(cache) a tick.
- **Prefill by chunks, expanded by tile**
  (:func:`mla_paged_prefill_attention`): a chunk's ``T`` queries attend to
  the slot's cached prefix and to the chunk itself (its rows are written
  first). Keys and values are expanded from the latents a tile of
  positions at a time under an online softmax, so the expanded prefix
  (``H x (d_nope + d_v)`` values a position) never exists whole. The
  Pallas kernel ``mla_paged_chunk_attn`` reads the pool in place and
  expands a tile for its head in VMEM, where the scores, their mask and
  the softmax statistics stay; off the TPU
  :func:`reference_mla_paged_prefill_attention` is the same loop in XLA,
  its ``[B, H, T, tile]`` float32 scores in HBM.
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
    "lane_pad",
    "latent_attention_tiling",
    "mla_paged_decode_attention",
    "mla_paged_prefill_attention",
    "pick_mla_chunk_blocks",
    "reference_mla_paged_decode_attention",
    "reference_mla_paged_prefill_attention",
]

_NEG_INF = -1e30  # as ops.decode_attention: exp underflows to exactly 0.0


def lane_pad(n: int) -> int:
    """``n`` rounded up to whole 128-lane tiles."""
    return -(-n // 128) * 128


def _gathered(pool, block_table):
    """Each slot's rows of one pool buffer, ``[B, pages_per_slot * ps, W]``."""
    g = pool[jnp.clip(block_table, 0, pool.shape[0] - 1)]
    return g.reshape(g.shape[0], -1, g.shape[-1])


def reference_mla_paged_decode_attention(
    q_abs, q_rope, ckv_pool, kr_pool, lengths, block_table, *, scale
):
    """Gather-dense absorbed attention: ``q_abs`` [B, H, C] and ``q_rope``
    [B, H, R'] (R' <= the pool's padded R) against every position of the
    slot's table, key ``j`` visible iff ``j <= lengths``. The kernel's
    oracle and the fallback off the TPU."""
    with jax.named_scope("kv_gather"):
        ckv = _gathered(ckv_pool, block_table)
        kr = _gathered(kr_pool, block_table)[..., : q_rope.shape[-1]]
    s = jnp.einsum("bhc,bkc->bhk", q_abs, ckv,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhr,bkr->bhk", q_rope, kr,
                       preferred_element_type=jnp.float32)
    s = s * scale
    vis = jnp.arange(ckv.shape[1])[None, :] <= lengths[:, None]
    p = jax.nn.softmax(jnp.where(vis[:, None, :], s, _NEG_INF), axis=-1)
    out = jnp.einsum("bhk,bkc->bhc", p.astype(ckv.dtype), ckv,
                     preferred_element_type=jnp.float32)
    return out.astype(q_abs.dtype)


def _mla_decode_kernel(lengths_ref, bt_ref, qa_ref, qr_ref, ckv_hbm, kr_hbm,
                       o_ref, ckv_buf, kr_buf, sem, *, block_k, page_size,
                       scale):
    """One slot: ``H`` query rows against the slot's shared latent rows.

    ``lengths_ref`` [B] and ``bt_ref`` [B, pages_per_slot] in SMEM;
    ``qa_ref`` [1, H, C] and ``qr_ref`` [1, H, R] in VMEM; the pools stay
    in HBM and visited tiles are DMA'd in, double-buffered, each resolved
    through the block table (a tile never straddles pages). The loop is
    ``ops.decode_attention._decode_kernel``'s with one "head" whose keys
    are the concatenation ``[c_kv | k_rope]`` and whose values are
    ``c_kv``."""
    b = pl.program_id(0)
    length = lengths_ref[b]
    s = bt_ref.shape[1] * page_size
    n_k = jnp.clip((length + block_k) // block_k, 1, s // block_k)

    def dma(hbm, buf, row, slot, ki):
        page = bt_ref[b, (ki * block_k) // page_size]
        src = hbm.at[page, pl.ds((ki * block_k) % page_size, block_k)]
        return pltpu.make_async_copy(src, buf.at[slot], sem.at[row, slot])

    channels = [(ckv_hbm, ckv_buf, 0), (kr_hbm, kr_buf, 1)]
    for hbm, buf, row in channels:
        dma(hbm, buf, row, 0, 0).start()

    qa, qr = qa_ref[0], qr_ref[0]
    h_n, c = qa.shape

    def body(ki, carry):
        m, l, acc = carry
        slot = lax.rem(ki, 2)

        @pl.when(ki + 1 < n_k)
        def _prefetch():
            for hbm, buf, row in channels:
                dma(hbm, buf, row, 1 - slot, ki + 1).start()

        for hbm, buf, row in channels:
            dma(hbm, buf, row, slot, ki).wait()
        ckv, kr = ckv_buf[slot], kr_buf[slot]
        sc = lax.dot_general(qa, ckv, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        sc = sc + lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        k_pos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (h_n, block_k), 1)
        sc = jnp.where(k_pos <= length, sc * scale, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)  # masked columns: exactly 0.0
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc_new = alpha * acc + lax.dot_general(
            p.astype(ckv.dtype), ckv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    init = (jnp.full((h_n, 1), _NEG_INF, jnp.float32),
            jnp.zeros((h_n, 1), jnp.float32),
            jnp.zeros((h_n, c), jnp.float32))
    _, l, acc = lax.fori_loop(0, n_k, body, init)
    # Key 0 is visible to every query, so l > 0; the guard keeps a
    # malformed call finite.
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret"))
def _mla_decode_call(q_abs, q_rope, ckv_pool, kr_pool, lengths, block_table,
                     *, scale, block_k, interpret):
    b, h, c = q_abs.shape
    page_size, r = kr_pool.shape[1], kr_pool.shape[2]
    q_rope = jnp.pad(q_rope, ((0, 0), (0, 0), (0, r - q_rope.shape[-1])))
    kern = functools.partial(_mla_decode_kernel, block_k=block_k,
                             page_size=page_size, scale=scale)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    row = lambda w: pl.BlockSpec((1, h, w), lambda i: (i, 0, 0),
                                 memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kern,
        name="mla_paged_decode_attn",
        grid=(b,),
        in_specs=[smem, smem, row(c), row(r), hbm, hbm],
        out_specs=row(c),
        out_shape=jax.ShapeDtypeStruct((b, h, c), q_abs.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, block_k, c), ckv_pool.dtype),
            pltpu.VMEM((2, block_k, r), kr_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        interpret=bool(interpret),
    )(jnp.asarray(lengths, jnp.int32), jnp.asarray(block_table, jnp.int32),
      q_abs, q_rope.astype(q_abs.dtype), ckv_pool, kr_pool)


def pick_mla_block_k(page_size: int, want: int | None = None) -> int:
    """The kernel's tile of positions: a divisor of the page, 512 at most
    (a 512 x 640 bf16 tile is 640 KB, double-buffered 1.3 MB of VMEM)."""
    bk = min(want or 512, page_size)
    while page_size % bk:
        bk -= 1
    return bk


def mla_paged_decode_attention(
    q_abs, q_rope, ckv_pool, kr_pool, lengths, block_table, *, scale,
    block_k: int | None = None, interpret: bool | None = None,
):
    """Absorbed decode attention of one layer: ``q_abs`` [B, H, C],
    ``q_rope`` [B, H, R'] against the pools ``[P, ps, C]`` / ``[P, ps, R]``
    through ``block_table`` [B, pages_per_slot]; returns the weighted
    latents ``[B, H, C]``. ``interpret`` as in
    :func:`~mpit_tpu.ops.decode_attention.flash_paged_decode_attention`."""
    if not _da._use_kernel(interpret):
        return reference_mla_paged_decode_attention(
            q_abs, q_rope, ckv_pool, kr_pool, lengths, block_table,
            scale=scale)
    return _mla_decode_call(
        q_abs, q_rope, ckv_pool, kr_pool, lengths, block_table,
        scale=float(scale),
        block_k=pick_mla_block_k(ckv_pool.shape[1], block_k),
        interpret=bool(interpret) if interpret is not None else False)


# Query rows one step of the chunk kernel's inner loop takes (a block of
# rows that lies wholly before a tile skips it), and cached positions a
# tile. The softmax statistics are reduced along the lanes once a (row,
# tile), so a wide tile keeps those reductions off the products' time: at
# xing4's shape and a prefix of 10,240 a call takes 11.97 / 7.38 / 5.54 /
# 5.77 ms at 256 / 512 / 1,024 / 2,048 positions a tile, and 512 or 1,024
# query rows read the same (TPU v5e, PR 47; chip_smoke.py --phases
# mla_chunk_kernel times today's).
_CHUNK_Q_ROWS = 512
_CHUNK_K_ROWS = 1024


def pick_mla_chunk_blocks(t: int, page_size: int, d_nope: int, d_rope: int,
                          rope_width: int) -> tuple[int, int, int]:
    """``(block_q, block_k, rope_at)`` of the chunk kernel, from the shapes
    alone: query rows a step of its inner loop (a divisor of ``t``,
    :data:`_CHUNK_Q_ROWS` at most), cached positions a tile (whole pages
    up to :data:`_CHUNK_K_ROWS`, or a divisor of a page that holds more;
    the call holds it to the slot's table) and the lane at which the
    key's rotary part lies beside a head's expanded key: straight after
    it where that falls on a lane tile's edge or the part fits the
    rest of the expanded key's last tile (192 + 64 = 256 lanes), else in
    lane tiles of its own."""
    bq = min(_CHUNK_Q_ROWS, t)
    while t % bq:
        bq -= 1
    inside = d_nope % 128
    fits = rope_width == 128 and inside + d_rope <= 128
    rope_at = d_nope if (inside == 0 or fits) else lane_pad(d_nope)
    if page_size >= _CHUNK_K_ROWS:
        bk = pick_mla_block_k(page_size, _CHUNK_K_ROWS)
    else:
        bk = _CHUNK_K_ROWS // page_size * page_size
    return bq, bk, rope_at


def latent_attention_tiling(t_q: int, page_size: int, d_nope: int,
                            d_rope: int) -> dict:
    """What a step of ``t_q`` query rows a slot says of its latent
    attention kernel in its spans: a tick's absorbed kernel and the cache
    rows a step of its loop takes, a chunk's expanded kernel with the
    query rows a step of its inner loop takes beside them."""
    if t_q == 1:
        return {"attention_form": "latent_absorbed",
                "attention_rows": pick_mla_block_k(page_size)}
    bq, bk, _ = pick_mla_chunk_blocks(
        t_q, page_size, d_nope, d_rope, lane_pad(d_rope))
    return {"attention_form": "latent_expanded_kernel",
            "attention_rows": bk, "attention_query_rows": bq}


def _mla_chunk_kernel(lengths_ref, bt_ref, q_ref, wk_ref, wv_ref, ckv_hbm,
                      kr_hbm, *rest, block_q, block_k, page_size, scale,
                      d_rope, rope_at, masked):
    """One participant and head: the chunk's ``T`` query rows against the
    slot's latent rows, a tile of ``block_k`` positions at a time.

    ``lengths_ref`` [B] and ``bt_ref`` [B, pages_per_slot] in SMEM;
    ``q_ref`` [1, 1, T, KW] (the head's ``[q_nope | q_rope]``, zero
    padded) and the head's expansion weights ``wk_ref`` [1, C, KN] /
    ``wv_ref`` [1, C, DV] in VMEM; the pools, and with ``masked`` the
    choice ``sel_hbm`` [B, tiles, T, block_k] int8, stay in HBM and a
    visited tile is DMA'd in, double buffered, through the block table.
    A tile is expanded to the head's keys and values in VMEM, the key's
    rotary part laid beside the expanded key at lane ``rope_at``; scores,
    mask, exponentials and the running statistics (``m_ref``, ``l_ref``,
    ``acc_ref``) never leave VMEM. A block of ``block_q`` rows that lies
    wholly before a tile skips its products."""
    sel_hbm, sel_buf = None, None
    if masked:
        sel_hbm, o_ref, ckv_buf, kr_buf, sel_buf, *stats, sem = rest
    else:
        o_ref, ckv_buf, kr_buf, *stats, sem = rest
    m_ref, l_ref, acc_ref = stats
    b = pl.program_id(0)
    length = lengths_ref[b]
    t, kn = q_ref.shape[2], wk_ref.shape[2]
    s = bt_ref.shape[1] * page_size
    n_k = jnp.clip((length + t + block_k - 1) // block_k, 1,
                   -(-s // block_k))

    # A tile is a part of a page or whole pages, a DMA each; a table
    # entry past the slot's last page may name any page (its positions
    # are masked), so it is held to the pool.
    piece = min(block_k, page_size)
    npg, last_page = bt_ref.shape[1], ckv_hbm.shape[0] - 1

    def dma(hbm, buf, row, slot, ki, j):
        at = ki * block_k + j * piece
        page = jnp.clip(bt_ref[b, jnp.minimum(at // page_size, npg - 1)],
                        0, last_page)
        src = hbm.at[page, pl.ds(at % page_size, piece)]
        return pltpu.make_async_copy(
            src, buf.at[slot, pl.ds(j * piece, piece)], sem.at[row, slot])

    def fetch(slot, ki):
        copies = []
        for j in range(block_k // piece):
            copies += [dma(ckv_hbm, ckv_buf, 0, slot, ki, j),
                       dma(kr_hbm, kr_buf, 1, slot, ki, j)]
        if masked:
            copies.append(pltpu.make_async_copy(
                sel_hbm.at[b, ki], sel_buf.at[slot], sem.at[2, slot]))
        return copies

    for copy in fetch(0, 0):
        copy.start()
    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    wk, wv = wk_ref[0], wv_ref[0]
    nt = (((1,), (1,)), ((), ()))  # contract the last dimension of both

    def body(ki, carry):
        slot = lax.rem(ki, 2)

        @pl.when(ki + 1 < n_k)
        def _prefetch():
            for copy in fetch(1 - slot, ki + 1):
                copy.start()

        for copy in fetch(slot, ki):
            copy.wait()
        ckv, kr = ckv_buf[slot], kr_buf[slot]
        k = jnp.dot(ckv, wk, preferred_element_type=jnp.float32)
        v = jnp.dot(ckv, wv, preferred_element_type=jnp.float32
                    ).astype(ckv.dtype)
        lane = lax.broadcasted_iota(jnp.int32, kr.shape, 1)
        kr = jnp.where(lane < d_rope, kr, jnp.zeros_like(kr))
        if rope_at < kn:  # into the rest of the expanded key's last tile
            last = k[:, kn - 128:] + pltpu.roll(
                kr.astype(jnp.float32), rope_at % 128, 1)
            k = last if kn == 128 else jnp.concatenate(
                [k[:, : kn - 128], last], axis=1)
            k = k.astype(ckv.dtype)
        else:
            k = jnp.concatenate([k.astype(ckv.dtype), kr], axis=1)
        k_pos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        row = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        for qi in range(t // block_q):
            rows = slice(qi * block_q, (qi + 1) * block_q)

            @pl.when(ki * block_k < length + (qi + 1) * block_q)
            def _visible(qi=qi, rows=rows):
                sc = lax.dot_general(q_ref[0, 0, rows], k, nt,
                                     preferred_element_type=jnp.float32)
                vis = k_pos <= length + qi * block_q + row
                if masked:
                    vis &= sel_buf[slot, rows].astype(jnp.int32) != 0
                sc = jnp.where(vis, sc * scale, _NEG_INF)
                m = m_ref[rows]
                m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
                p = jnp.exp(sc - m_new)
                alpha = jnp.exp(m - m_new)
                m_ref[rows] = m_new
                l_ref[rows] = alpha * l_ref[rows] + jnp.sum(
                    p, axis=1, keepdims=True)
                acc_ref[rows] = alpha * acc_ref[rows] + jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32)

        return carry

    lax.fori_loop(0, n_k, body, 0)
    l = l_ref[...]
    o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                   ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "blocks", "interpret"))
def _mla_chunk_call(q_nope, q_rope, ckv_pool, kr_pool, lengths, block_table,
                    w_ukv, select, *, scale, blocks, interpret):
    b, t, h, dn = q_nope.shape
    dr = q_rope.shape[-1]
    dv = w_ukv.shape[-1] - dn
    c, page_size, r = ckv_pool.shape[2], kr_pool.shape[1], kr_pool.shape[2]
    bq, bk, rope_at = blocks
    if bk > page_size:  # whole pages, the slot's table at most
        bk = min(bk // page_size, block_table.shape[1]) * page_size
    kn, dvp = lane_pad(dn), lane_pad(dv)
    kw = kn if rope_at < kn else kn + r
    # [B, T, H, .] -> [B, H, T, KW]: a head's rows together, the rotary
    # part at the lane where the kernel lays the key's.
    q = jnp.concatenate([
        q_nope, jnp.zeros((b, t, h, rope_at - dn), q_nope.dtype), q_rope,
        jnp.zeros((b, t, h, kw - rope_at - dr), q_nope.dtype)], axis=-1)
    q = jnp.transpose(q, (0, 2, 1, 3))
    w = jnp.transpose(w_ukv, (1, 0, 2))  # [H, C, dn + dv]
    wk = jnp.pad(w[..., :dn], ((0, 0), (0, 0), (0, kn - dn)))
    wv = jnp.pad(w[..., dn:], ((0, 0), (0, 0), (0, dvp - dv)))
    masked = select is not None
    kern = functools.partial(
        _mla_chunk_kernel, block_q=bq, block_k=bk, page_size=page_size,
        scale=scale, d_rope=dr, rope_at=rope_at, masked=masked)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    head = lambda *shape: pl.BlockSpec(
        (1, *shape), lambda i, j: (j,) + (0,) * len(shape),
        memory_space=pltpu.VMEM)
    rows = lambda w_: pl.BlockSpec((1, 1, t, w_), lambda i, j: (i, j, 0, 0),
                                   memory_space=pltpu.VMEM)
    operands = [jnp.asarray(lengths, jnp.int32),
                jnp.asarray(block_table, jnp.int32), q, wk, wv, ckv_pool,
                kr_pool]
    in_specs = [smem, smem, rows(kw), head(c, kn), head(c, dvp), hbm, hbm]
    scratch = [pltpu.VMEM((2, bk, c), ckv_pool.dtype),
               pltpu.VMEM((2, bk, r), kr_pool.dtype)]
    if masked:
        # A tile of the choice as one contiguous block: [B, tiles, T, bk].
        n_tiles = -(-block_table.shape[1] * page_size // bk)
        sel = jnp.pad(select, ((0, 0), (0, 0),
                               (0, n_tiles * bk - select.shape[-1])))
        sel = jnp.transpose(sel.astype(jnp.int8).reshape(b, t, n_tiles, bk),
                            (0, 2, 1, 3))
        operands.append(sel)
        in_specs.append(hbm)
        scratch.append(pltpu.VMEM((2, t, bk), jnp.int8))
    scratch += [pltpu.VMEM((t, 1), jnp.float32),
                pltpu.VMEM((t, 1), jnp.float32),
                pltpu.VMEM((t, dvp), jnp.float32),
                pltpu.SemaphoreType.DMA((3 if masked else 2, 2))]
    out = pl.pallas_call(
        kern,
        name="mla_paged_chunk_attn",
        grid=(b, h),
        in_specs=in_specs,
        out_specs=rows(dvp),
        out_shape=jax.ShapeDtypeStruct((b, h, t, dvp), q_nope.dtype),
        scratch_shapes=scratch,
        # As the grouped chunk kernel: queries and results double buffered
        # beside the float32 accumulator, statistics and score blocks.
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=48 * 2**20),
        interpret=interpret,
    )(*operands)
    return jnp.transpose(out[..., :dv], (0, 2, 1, 3))


def mla_paged_prefill_attention(
    q_nope, q_rope, ckv_pool, kr_pool, lengths, block_table, w_ukv, *,
    scale, tile: int = 1024, select=None, interpret: bool | None = None,
):
    """Expanded attention of a chunk: ``q_nope`` [B, T, H, dn] and
    ``q_rope`` [B, T, H, dr], query ``t`` at position ``lengths + t``,
    against positions ``0 .. lengths + T - 1`` of each slot's pages (the
    chunk's own rows already written). ``w_ukv`` [C, H, dn + dv] expands a
    tile of latents to its keys and values; tiles past the last visible
    position are never visited. ``select`` [B, T, S] bool
    (``S`` the slot's positions; None: none) is a second condition on
    visibility: query ``t`` attends to position ``s`` only where it is
    set (learned sparse attention's choice, ``ops/dsa.py``). Returns
    ``[B, T, H, dv]``. On the TPU (``interpret`` as in
    :func:`~mpit_tpu.ops.decode_attention.flash_paged_decode_attention`)
    the kernel ``mla_paged_chunk_attn``; elsewhere its lax twin
    :func:`reference_mla_paged_prefill_attention` in tiles of ``tile``
    positions."""
    if not _da._use_kernel(interpret):
        return reference_mla_paged_prefill_attention(
            q_nope, q_rope, ckv_pool, kr_pool, lengths, block_table, w_ukv,
            scale=scale, tile=tile, select=select)
    blocks = pick_mla_chunk_blocks(
        q_nope.shape[1], kr_pool.shape[1], q_nope.shape[-1],
        q_rope.shape[-1], kr_pool.shape[2])
    with jax.named_scope("mla_expand"):
        return _mla_chunk_call(
            q_nope, q_rope, ckv_pool, kr_pool, lengths, block_table, w_ukv,
            select, scale=float(scale), blocks=blocks,
            interpret=bool(interpret))


def reference_mla_paged_prefill_attention(
    q_nope, q_rope, ckv_pool, kr_pool, lengths, block_table, w_ukv, *,
    scale, tile: int = 1024, select=None,
):
    """:func:`mla_paged_prefill_attention` as an XLA loop over tiles of
    ``tile`` positions under an online softmax, each tile's scores a
    ``[B, H, T, tile]`` float32 array: the chunk kernel's oracle and the
    fallback off the TPU. It visits the tiles up to the longest slot's
    last visible position."""
    b, t, h, dn = q_nope.shape
    dr = q_rope.shape[-1]
    dv = w_ukv.shape[-1] - dn
    ps, npg = ckv_pool.shape[1], block_table.shape[1]
    tp = max(1, min(tile // ps, npg))  # pages a tile
    tk = tp * ps
    n_max = -(-npg // tp)
    n_tiles = jnp.clip((jnp.max(lengths) + t + tk - 1) // tk, 1, n_max)
    t_pos = lengths[:, None] + jnp.arange(t)[None, :]  # [B, T]
    last_page = ckv_pool.shape[0] - 1
    # One product a tile gives the scores: the key's rotary part, which
    # all heads share, is laid beside each head's expanded key. (Two
    # products and their sum were three [H, T, tile] float32 arrays
    # through HBM where this is one.)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    if select is not None:  # whole tiles, so that a tile is one slice
        select = jnp.pad(
            select, ((0, 0), (0, 0), (0, n_max * tk - select.shape[-1])))

    def body(i, carry):
        m, l, acc = carry
        with jax.named_scope("kv_gather"):
            idx = jnp.clip(i * tp + jnp.arange(tp), 0, npg - 1)
            pages = jnp.clip(jnp.take(block_table, idx, axis=1), 0, last_page)
            ckv = ckv_pool[pages].reshape(b, tk, -1)
            kr = kr_pool[pages].reshape(b, tk, -1)[..., :dr]
        with jax.named_scope("mla_expand"):
            kv = jnp.einsum("bkc,chd->bkhd", ckv, w_ukv,
                            preferred_element_type=jnp.float32
                            ).astype(ckv.dtype)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(kr[:, :, None], (b, tk, h, dr))],
            axis=-1)
        s = jnp.einsum("bthd,bkhd->bhtk", q, k,
                       preferred_element_type=jnp.float32)
        k_pos = i * tk + jnp.arange(tk)
        vis = k_pos[None, None, :] <= t_pos[:, :, None]  # [B, T, tk]
        if select is not None:
            vis = vis & lax.dynamic_slice_in_dim(select, i * tk, tk, axis=2)
        s = jnp.where(vis[:, None], s * scale, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "bhtk,bkhd->bhtd", p.astype(kv.dtype), kv[..., dn:],
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((b, h, t, 1), _NEG_INF, jnp.float32),
            jnp.zeros((b, h, t, 1), jnp.float32),
            jnp.zeros((b, h, t, dv), jnp.float32))
    _, l, acc = lax.fori_loop(0, n_tiles, body, init)
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q_nope.dtype)
