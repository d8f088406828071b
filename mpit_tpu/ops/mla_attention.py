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
  (``H x (d_nope + d_v)`` values a position) never exists whole. Plain
  XLA: at ``T`` in the thousands the products are large matrix products.
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
    "mla_paged_decode_attention",
    "mla_paged_prefill_attention",
    "reference_mla_paged_decode_attention",
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


def mla_paged_prefill_attention(
    q_nope, q_rope, ckv_pool, kr_pool, lengths, block_table, w_ukv, *,
    scale, tile: int = 1024, select=None,
):
    """Expanded attention of a chunk: ``q_nope`` [B, T, H, dn] and
    ``q_rope`` [B, T, H, dr], query ``t`` at position ``lengths + t``,
    against positions ``0 .. lengths + T - 1`` of each slot's pages (the
    chunk's own rows already written). ``w_ukv`` [C, H, dn + dv] expands a
    tile of latents to its keys and values; tiles past the longest slot's
    last visible position are never visited. ``select`` [B, T, S] bool
    (``S`` the slot's positions; None: none) is a second condition on
    visibility: query ``t`` attends to position ``s`` only where it is
    set (learned sparse attention's choice, ``ops/dsa.py``). Returns
    ``[B, T, H, dv]``."""
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
