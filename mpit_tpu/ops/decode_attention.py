"""Pallas flash-decode — length-aware attention against the padded KV cache.

The serving hot path (ISSUE 5 tentpole). PR 4's engine decodes with
:func:`mpit_tpu.models.gpt2.cached_attention`: a dense XLA attention that
scores every query against the **entire padded cache buffer**
``[slots, max_len]`` and materializes the f32 ``[B, H, T, S]`` score
tensor — so a decode tick costs O(max_len) HBM traffic and FLOPs even
when the slots hold 30-token contexts. This kernel makes the tick cost
scale with the *context actually cached*:

- **Blocked over the cache length with online softmax.** The kernel
  streams ``block_k``-sized K/V tiles through a ``fori_loop``, carrying
  the flash running max/denominator/accumulator in f32 (the same
  structure as :mod:`mpit_tpu.ops.flash_attention`); the ``[T, S]``
  score matrix never exists — only a ``[T, block_k]`` f32 tile.
- **Per-slot length-aware block skipping.** The k-loop bound is derived
  from the slot's ``lengths`` entry (an SMEM scalar): a slot holding
  ``L`` tokens visits ``ceil((L+T)/block_k)`` tiles, not
  ``max_len/block_k``. Because K/V stay in **HBM** (``memory_space=ANY``)
  and the kernel DMAs tiles in itself (double-buffered, overlap with
  compute), skipped tiles cost neither FLOPs *nor* HBM reads — the
  BlockSpec-prefetch form would have copied the whole padded row.
- **Heads-local.** One grid program per slot computes every head it was
  given (python-unrolled over the packed ``[rows, H·D]`` lane layout of
  the training kernel), so the TP engine calls it unchanged on its
  H/P head shard.
- **Small-T prefill tail.** ``T`` is static per trace; the engine's
  padded prefill (``T = prefill_len``, ``lengths = 0``) and its decode
  tick (``T = 1``) are two traces of the same kernel.

Parity contract: visibility is ``key j visible to query t iff
j <= lengths + t`` — exactly :func:`~mpit_tpu.models.gpt2.cached_attention`
(the reference), whose masked rows contribute exact zeros. Masked
positions inside a visited boundary tile score ``-1e30``; ``exp``
underflows to exactly 0.0 in f32, and tiles past the loop bound are
never read — so the kernel's masked-key contribution is exactly zero
too, and greedy decode through it preserves the PR 4 bit-match
invariant at the token level.

On non-TPU backends (``interpret=None``) the same math runs as the
reference XLA path; ``interpret=True`` forces the kernel through the
Pallas interpreter (the CPU-mesh test path, like the training kernel).

**Paged variant (ISSUE 7).** :func:`flash_paged_decode_attention` runs
the same length-aware flash loop against a PAGED pool
(``[num_pages, page_size, H·D]``) instead of a dense per-slot buffer:
the slot's int32 block table rides in SMEM next to ``lengths`` (scalar
prefetch), and each k-tile's DMA source is resolved per tile —
``page = bt[b, (ki·block_k)//page_size]``, offset ``(ki·block_k) %
page_size`` — so the tile loop indirects through the table with zero
extra HBM traffic (``page_size`` must be a multiple of ``block_k``:
a tile never straddles pages). Skipped tiles still cost neither FLOPs
nor HBM reads, and the heads-local/TP calling convention is unchanged.

**Quantized variant (ISSUE 15).** Passing
:class:`~mpit_tpu.ops.kv_quant.QuantizedKV` buffers (int8 payload +
per-(row, head) f32 scales) selects the FUSED-DEQUANT form of the same
kernel: what crosses HBM→VMEM per visited tile is the int8 K/V tile
plus its ``[block_k, H]`` scale block (two extra DMA channels on the
same double buffer), and the dequant
(:func:`~mpit_tpu.ops.ring_collectives.dequantize_blocks` — the PR 9
rounding contract's inverse) runs in VMEM per tile, per head. The f32
online-softmax m/l/acc structure, the visibility mask, tile skipping
and the in-kernel visited count are byte-for-byte the unquantized
loop's; a full dequantized f32 buffer NEVER materializes on this path
(contract-checked by ``mpit_tpu.analysis``). The off-TPU fallback
dequantizes through the same helpers inside the reference math — the
kernel's numerical oracle, so tier-1 pins the per-tile dequant on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.ops.kv_quant import QuantizedKV
from mpit_tpu.ops.ring_collectives import dequantize_blocks

__all__ = [
    "flash_decode_attention",
    "flash_paged_decode_attention",
    "reference_decode_attention",
    "reference_paged_decode_attention",
    "num_kv_blocks",
    "pick_block_k",
]

_NEG_INF = -1e30  # large-but-finite; exp underflows to exactly 0.0 in f32


def _use_kernel(interpret: bool | None) -> bool:
    if interpret is not None:
        return True
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# Reference (XLA) path — also the non-TPU fallback.
# ---------------------------------------------------------------------------


def reference_decode_attention(q, k, v, lengths):
    """Dense cached attention, [B, T, H, Dh] vs padded [B, S, H, Dh].

    Delegates to :func:`mpit_tpu.models.gpt2.cached_attention` — the
    kernel's oracle and the non-TPU fallback ARE the serving reference,
    one implementation, so a numerics change there cannot silently
    desynchronize this module (the bitwise pin in
    ``tests/test_decode_attention.py`` now guards only the signature).
    Imported lazily: ops sits below models in the layering, and the
    models package must not load just because ops does.
    """
    from mpit_tpu.models.gpt2 import cached_attention

    return cached_attention(q, k, v, lengths)


def reference_paged_decode_attention(q, k_pool, v_pool, lengths, block_table):
    """Gather-dense paged attention — delegates to
    :func:`mpit_tpu.models.gpt2.paged_cached_attention` (one
    implementation, same rationale as the dense reference above). The
    paged kernel's oracle and the non-TPU fallback."""
    from mpit_tpu.models.gpt2 import paged_cached_attention

    return paged_cached_attention(q, k_pool, v_pool, lengths, block_table)


def pick_block_k(s: int, want: int | None = None) -> int:
    """Resolve the cache-length tile: an explicit ``want`` is clamped to
    S; ``None`` auto-picks the largest power of two ≤ 256 dividing S
    (descending, floor 8 — the f32 sublane tile), falling back to S
    itself (one tile, no skipping) when nothing divides. 256 (not the
    training kernel's 512) because decode queries are 1–few rows: the
    per-tile matmul is VPU-bound either way, and a finer tile skips
    more of a short context."""
    if want is not None:
        return min(want, s)
    b = 256
    while b > 8 and (s % b or s // b < 4):
        b //= 2
    return b if s % b == 0 else s


def num_kv_blocks(lengths, t_q: int, s: int, block_k: int):
    """Tiles a slot's k-loop visits: ``ceil((L + T)/block_k)``, clamped
    to the buffer's tile count. Host-side mirror of the in-kernel bound
    — the serve scheduler derives its ``decode_blocks_skipped`` obs
    counter from this, and tests pin it against the kernel's own count.
    Works on numpy or jax int arrays."""
    total = s // block_k
    n = (lengths + t_q + block_k - 1) // block_k
    return jnp.clip(n, 1, total) if hasattr(n, "aval") else n.clip(1, total)


# ---------------------------------------------------------------------------
# Kernel. One grid program per slot; K/V stay in HBM and are DMA'd
# tile-by-tile (double-buffered) so skipped tiles are never read.
# ---------------------------------------------------------------------------


def _decode_kernel(
    *refs,
    block_k,
    num_heads,
    head_dim,
    scale,
    page_size=None,
    quantized=False,
):
    """Flash-decode body, dense or paged, plain or fused-dequant.

    Dense (``page_size=None``) refs: ``lengths_ref`` [B] int32 SMEM,
    ``q_ref`` [1, T, H·D] VMEM, ``k_hbm``/``v_hbm`` [B, S, H·D]
    ANY/HBM, ``o_ref``, ``visited_ref`` (whole [B] int32 SMEM, entry
    ``b`` written by program ``b``), scratch. Paged adds ``bt_ref``
    [B, pages_per_slot] int32 SMEM after ``lengths_ref`` and the HBM
    operands become the [num_pages, page_size, H·D] pool — the ONLY
    other difference is the DMA source: tile ``ki`` is resolved through
    the block table instead of being a contiguous row slice. The flash
    loop, masks and accumulators are byte-for-byte the same code.

    ``quantized`` (ISSUE 15): the HBM operand list interleaves scale
    planes — ``k, k_scale, v, v_scale`` with scales [B, S, Hp] (dense)
    or [num_pages, page_size, Hp] (paged) f32, Hp = H lane-padded to 128
    (:func:`_kv_operands`) — and the scratch grows matching
    [2, block_k, Hp] double buffers on two extra DMA channels.
    Each visited tile dequantizes in VMEM, per head, through the shared
    :func:`~mpit_tpu.ops.ring_collectives.dequantize_blocks`; the rest
    of the loop is identical, in f32 operands.
    """
    refs = list(refs)
    lengths_ref = refs.pop(0)
    bt_ref = refs.pop(0) if page_size is not None else None
    q_ref = refs.pop(0)
    if quantized:
        k_hbm, ks_hbm, v_hbm, vs_hbm = refs[:4]
        del refs[:4]
    else:
        k_hbm, v_hbm = refs[:2]
        del refs[:2]
        ks_hbm = vs_hbm = None
    o_ref, visited_ref = refs[:2]
    del refs[:2]
    if quantized:
        k_buf, ks_buf, v_buf, vs_buf, sem = refs
    else:
        (k_buf, v_buf, sem) = refs
        ks_buf = vs_buf = None
    if page_size is None:
        s = k_hbm.shape[1]
    else:
        s = bt_ref.shape[1] * page_size  # virtual per-slot cache length
    b = pl.program_id(0)
    t_q = q_ref.shape[1]
    h_n, d = num_heads, head_dim
    length = lengths_ref[b]

    # Tiles with >= 1 visible key: ceil((L + T)/block_k), clamped to the
    # buffer (a stale/retired slot's length can never overrun it; in the
    # paged case the clamp also bounds the block-table index, so a stale
    # table entry past the mapped pages is never resolved).
    n_k = jnp.clip((length + t_q + block_k - 1) // block_k, 1, s // block_k)
    visited_ref[b] = n_k

    def dma(which_hbm, which_buf, sem_row, slot, ki):
        if bt_ref is None:
            src = which_hbm.at[b, pl.ds(ki * block_k, block_k)]
        else:
            # page_size % block_k == 0 (validated at the call): a tile
            # never straddles pages, so one SMEM lookup names its page.
            page = bt_ref[b, (ki * block_k) // page_size]
            src = which_hbm.at[page, pl.ds((ki * block_k) % page_size,
                                           block_k)]
        return pltpu.make_async_copy(
            src, which_buf.at[slot], sem.at[sem_row, slot]
        )

    # The per-tile DMA channel set: K and V always; their scale planes
    # ride two more channels of the same double buffer when quantized.
    channels = [(k_hbm, k_buf, 0), (v_hbm, v_buf, 1)]
    if quantized:
        channels += [(ks_hbm, ks_buf, 2), (vs_hbm, vs_buf, 3)]

    for hbm, buf, row in channels:
        dma(hbm, buf, row, 0, 0).start()

    t_pos = length + lax.broadcasted_iota(jnp.int32, (t_q, block_k), 0)

    def body(ki, carry):
        slot = lax.rem(ki, 2)

        @pl.when(ki + 1 < n_k)
        def _prefetch():
            for hbm, buf, row in channels:
                dma(hbm, buf, row, 1 - slot, ki + 1).start()

        for hbm, buf, row in channels:
            dma(hbm, buf, row, slot, ki).wait()

        k_pos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (t_q, block_k), 1
        )
        vis = t_pos >= k_pos  # key j visible to query t iff j <= L + t
        out = []
        for h in range(h_n):
            m, l, acc = carry[3 * h], carry[3 * h + 1], carry[3 * h + 2]
            # Matmul operands stay in the INPUT dtype (bf16 serving path)
            # with f32 accumulation; softmax statistics stay f32 and the
            # scale folds into the f32 scores (training-kernel idiom).
            q = q_ref[0, :, h * d : (h + 1) * d]  # [T, d]
            k_blk = k_buf[slot, :, h * d : (h + 1) * d]  # [bk, d]
            v_blk = v_buf[slot, :, h * d : (h + 1) * d]
            if quantized:
                # Fused per-tile dequant (ISSUE 15): the int8 tile and
                # its [bk, H] scale block are already in VMEM; the f32
                # view exists only at tile size, per head — the shared
                # PR 9 contract's inverse, operands f32 from here on.
                k_blk = dequantize_blocks(
                    k_blk, ks_buf[slot][:, h : h + 1]
                )
                v_blk = dequantize_blocks(
                    v_blk, vs_buf[slot][:, h : h + 1]
                )
                q = q.astype(jnp.float32)
            sc = lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [T, bk] f32
            sc = jnp.where(vis, sc, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1))
            p = jnp.exp(sc - m_new[:, None])  # masked cols: exactly 0.0
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=1)
            acc_new = alpha[:, None] * acc + lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            out += [m_new, l_new, acc_new]
        return tuple(out)

    init = []
    for _ in range(h_n):
        init += [
            jnp.full((t_q,), _NEG_INF, jnp.float32),
            jnp.zeros((t_q,), jnp.float32),
            jnp.zeros((t_q, d), jnp.float32),
        ]
    carry = lax.fori_loop(0, n_k, body, tuple(init))

    for h in range(h_n):
        l = carry[3 * h + 1]
        acc = carry[3 * h + 2]
        # Key 0 is visible to every query (L >= 0), so no row is ever
        # fully masked; the guard only keeps a malformed call finite.
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, h * d : (h + 1) * d] = (
            acc / l_safe[:, None]
        ).astype(o_ref.dtype)


def _vma(x):
    # Inside a VMA-checked shard_map, pallas_call out_shapes must declare
    # how outputs vary across mesh axes; mirror the query operand's vma.
    return getattr(jax.typeof(x), "vma", frozenset()) or frozenset()


def _lane_pad(n: int) -> int:
    return -(-n // 128) * 128


def _kv_operands(k, v, h, pk):
    """The kernel's HBM operand list + matching double-buffer scratch
    for one (K, V) pair — plain buffers or the quantized interleave
    ``k, k_scale, v, v_scale`` (scales packed [.., H] from the stored
    keepdims [.., H, 1] form). One helper serves the dense and paged
    calls, so the operand order and the kernel's unpacking cannot
    drift apart."""
    quantized = isinstance(k, QuantizedKV)
    if not quantized:
        return quantized, [pk(k), pk(v)], [k.dtype, v.dtype]
    # Mosaic DMAs whole 128-lane tiles: a [.., H] f32 plane narrower
    # than a lane tile is refused ("slice shape ... must be aligned to
    # tiling (128)"), so the scale operand is lane-padded here.
    psc = lambda sc: jnp.pad(
        sc.reshape(sc.shape[0], sc.shape[1], h),
        ((0, 0), (0, 0), (0, _lane_pad(h) - h)),
    )
    ops = [pk(k.q), psc(k.scale), pk(v.q), psc(v.scale)]
    return quantized, ops, [jnp.int8, jnp.float32, jnp.int8, jnp.float32]


def _scratch_for(quantized, block_k, hd, h, dtypes):
    """Double-buffer VMEM scratch matching :func:`_kv_operands`' order
    (+ the DMA semaphore array sized to the channel count)."""
    hp = _lane_pad(h)
    widths = [hd, hp, hd, hp] if quantized else [hd, hd]
    bufs = [
        pltpu.VMEM((2, block_k, w), dt) for w, dt in zip(widths, dtypes)
    ]
    return bufs + [pltpu.SemaphoreType.DMA((len(widths), 2))]


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def _decode_call(q, k, v, lengths, *, block_k, interpret):
    b, t, h, d = q.shape
    hd = h * d
    pk = lambda x: x.reshape(x.shape[0], x.shape[1], hd)  # free head-pack
    with jax.named_scope("kv_gather"):
        quantized, kv_ops, kv_dtypes = _kv_operands(k, v, h, pk)
    kern = functools.partial(
        _decode_kernel,
        block_k=block_k,
        num_heads=h,
        head_dim=d,
        scale=1.0 / (d ** 0.5),
        quantized=quantized,
    )
    o, visited = pl.pallas_call(
        kern,
        name="decode_attn",
        grid=(b,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths, whole [B]
            pl.BlockSpec(
                (1, t, hd), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
            ),
        ]
        # K/V (+ scale planes when quantized) stay in HBM; the kernel
        # DMAs visited tiles itself.
        + [pl.BlockSpec(memory_space=pl.ANY) for _ in kv_ops],
        out_specs=[
            pl.BlockSpec(
                (1, t, hd), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
            ),
            # Whole [B] counter in SMEM, each program writing its own
            # entry: Mosaic refuses a blocked (1, 1) SMEM output.
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, hd), q.dtype, vma=_vma(q)),
            jax.ShapeDtypeStruct((b,), jnp.int32, vma=_vma(q)),
        ],
        scratch_shapes=_scratch_for(quantized, block_k, hd, h, kv_dtypes),
        interpret=bool(interpret),
    )(jnp.asarray(lengths, jnp.int32), pk(q), *kv_ops)
    return o.reshape(b, t, h, d), visited


@functools.partial(
    jax.jit, static_argnames=("block_k", "page_size", "interpret")
)
def _paged_decode_call(
    q, k_pool, v_pool, lengths, block_table, *, block_k, page_size,
    interpret,
):
    b, t, h, d = q.shape
    hd = h * d
    pk = lambda x: x.reshape(x.shape[0], x.shape[1], hd)  # free head-pack
    with jax.named_scope("kv_gather"):
        quantized, kv_ops, kv_dtypes = _kv_operands(k_pool, v_pool, h, pk)
    kern = functools.partial(
        _decode_kernel,
        block_k=block_k,
        num_heads=h,
        head_dim=d,
        scale=1.0 / (d ** 0.5),
        page_size=page_size,
        quantized=quantized,
    )
    o, visited = pl.pallas_call(
        kern,
        name="paged_decode_attn",
        grid=(b,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # lengths, whole [B]
            pl.BlockSpec(memory_space=pltpu.SMEM),  # block table [B, n_ps]
            pl.BlockSpec(
                (1, t, hd), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
            ),
        ]
        # K/V pools (+ scale planes when quantized) stay in HBM.
        + [pl.BlockSpec(memory_space=pl.ANY) for _ in kv_ops],
        out_specs=[
            pl.BlockSpec(
                (1, t, hd), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
            ),
            # Whole [B] counter in SMEM, each program writing its own
            # entry: Mosaic refuses a blocked (1, 1) SMEM output.
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, hd), q.dtype, vma=_vma(q)),
            jax.ShapeDtypeStruct((b,), jnp.int32, vma=_vma(q)),
        ],
        scratch_shapes=_scratch_for(quantized, block_k, hd, h, kv_dtypes),
        interpret=bool(interpret),
    )(
        jnp.asarray(lengths, jnp.int32),
        jnp.asarray(block_table, jnp.int32),
        pk(q), *kv_ops,
    )
    return o.reshape(b, t, h, d), visited


def flash_paged_decode_attention(
    q,
    k_pool,
    v_pool,
    lengths,
    block_table,
    *,
    block_k: int | None = None,
    interpret: bool | None = None,
    return_visited: bool = False,
):
    """Length-aware attention against the PAGED KV pool (ISSUE 7):
    ``[B, T, H, Dh]`` queries vs ``[num_pages, page_size, H, Dh]``
    pools, each slot's pages named by ``block_table``
    [B, pages_per_slot] int32.

    Drop-in for :func:`mpit_tpu.models.gpt2.paged_cached_attention`
    (plug in as ``GPT2Config.paged_attention_fn``). The tile loop and
    skipping are exactly :func:`flash_decode_attention`'s over the
    slot's virtual ``pages_per_slot × page_size`` cache; only the DMA
    source indirects through the table. ``block_k`` defaults to the
    largest :func:`pick_block_k` choice for ``page_size`` and must
    divide it (a tile never straddles pages). ``interpret`` /
    ``return_visited`` as in :func:`flash_decode_attention` (the
    non-TPU fallback is the gather-dense reference)."""
    page_size = k_pool.shape[1]
    bk = pick_block_k(page_size, block_k)
    if page_size % bk:
        raise ValueError(
            f"page_size {page_size} must be divisible by block_k={bk}"
        )
    s_virtual = block_table.shape[1] * page_size
    if not _use_kernel(interpret):
        out = reference_paged_decode_attention(
            q, k_pool, v_pool, lengths, block_table
        )
        if return_visited:
            return out, num_kv_blocks(
                jnp.asarray(lengths, jnp.int32), q.shape[1], s_virtual, bk
            )
        return out
    out, visited = _paged_decode_call(
        q, k_pool, v_pool, lengths, block_table,
        block_k=bk, page_size=page_size,
        interpret=bool(interpret) if interpret is not None else False,
    )
    return (out, visited) if return_visited else out


def flash_decode_attention(
    q,
    k,
    v,
    lengths,
    *,
    block_k: int | None = None,
    interpret: bool | None = None,
    return_visited: bool = False,
):
    """Length-aware cached attention: ``[B, T, H, Dh]`` queries (the T
    newest positions, global position ``lengths + t``) against padded
    ``[B, S, H, Dh]`` K/V cache buffers.

    Drop-in for :func:`mpit_tpu.models.gpt2.cached_attention` (plug in
    as ``GPT2Config.cache_attention_fn``). ``block_k`` tiles the cache
    length (default via :func:`pick_block_k`: largest power of two
    ≤ 256 dividing S that yields at least 4 tiles, floor 8); a slot
    holding ``L`` tokens visits ``ceil((L+T)/block_k)`` tiles.

    ``interpret``: ``None`` = Pallas kernel on TPU, reference XLA path
    elsewhere; ``True`` = force the kernel through the interpreter (the
    CPU test path); ``False`` = force it compiled.

    ``return_visited``: also return the per-slot visited-tile count
    ``[B] int32`` — on the kernel path this is written by the kernel
    itself (what the loop actually ran), on the reference path it is the
    host formula :func:`num_kv_blocks`; tests pin the two against each
    other.
    """
    s = k.shape[1]
    bk = pick_block_k(s, block_k)
    if s % bk:
        # Validated on EVERY platform (the reference fallback could run
        # any block_k, but its visited-tile accounting would describe a
        # tiling the kernel can't execute — code passing off-TPU must
        # not first fail at TPU deploy).
        raise ValueError(
            f"cache length {s} must be divisible by block_k={bk}"
        )
    if not _use_kernel(interpret):
        out = reference_decode_attention(q, k, v, lengths)
        if return_visited:
            return out, num_kv_blocks(
                jnp.asarray(lengths, jnp.int32), q.shape[1], s, bk
            )
        return out
    out, visited = _decode_call(
        q, k, v, lengths, block_k=bk,
        interpret=bool(interpret) if interpret is not None else False,
    )
    return (out, visited) if return_visited else out
