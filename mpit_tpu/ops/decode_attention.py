"""Pallas flash-decode — length-aware attention against the paged KV pool.

The serving hot path. The reference,
:func:`mpit_tpu.models.gpt2.paged_cached_attention`, gathers each slot's
whole virtual cache ``[slots, max_len]`` out of the pool and materializes
the f32 ``[B, H, T, S]`` score tensor — so a decode tick costs O(max_len)
HBM traffic and FLOPs even when the slots hold 30-token contexts.
:func:`flash_paged_decode_attention` makes the tick cost scale with the
*context actually cached*:

- **Blocked over the cache length with online softmax.** The kernel
  streams K/V tiles through a ``fori_loop``, carrying the flash running
  max/denominator/accumulator in f32 (the same structure as
  :mod:`mpit_tpu.ops.flash_attention`); the ``[T, S]`` score matrix
  never exists — only a ``[rows of queries, rows of a tile]`` f32 tile.
- **Per-slot length-aware skipping.** The loop bound is derived from
  the slot's ``lengths`` entry (an SMEM scalar): a slot holding ``L``
  tokens reads the rows up to ``L + T`` (to the end of their page), not
  ``max_len``. Because K/V stay in **HBM** (``memory_space=ANY``) and
  the kernel DMAs tiles in itself (double-buffered, overlap with
  compute), skipped rows cost neither FLOPs *nor* HBM reads — the
  BlockSpec-prefetch form would have copied the whole padded row.
  ``block_k`` is the unit the skipping is counted in (``visited``,
  :func:`num_kv_blocks`).
- **The pool is read in place.** The pool is ``[num_pages, page_size,
  H·D]``; the slot's int32 block table rides in SMEM next to ``lengths``
  (scalar prefetch), and a loop step GATHERS consecutive pages of the
  table into one VMEM tile (16 pages of 16 positions: 256 rows), a DMA a
  page and buffer, each source resolved by one SMEM lookup, all of a
  step's DMAs in flight together and the next step's behind them — the
  gather costs zero extra HBM traffic. The slot's last step fetches only
  the pages that hold a visible key and zeroes the V rows of the others
  (a buffer's old content, NaN included, must not reach ``p @ V``).
  Pages larger than the tile are read in equal parts; ``page_size`` must
  be a multiple of ``block_k``, the unit of the visited count.
- **A step does a lane tile's worth of work** (ISSUE 27,
  :func:`decode_tiling`). One grid program per slot computes every head
  it was given over the packed ``[rows, H·D]`` lane layout of the
  training kernel, so the TP engine calls it unchanged on its H/P head
  shard. At few query rows (a decode tick, a speculative verify) the
  heads are the ROWS of one product a step: the block-diagonal query
  ``[T·Hp, H·D]`` against the tile's K gives every head's scores at
  once, one online-softmax update serves all, and ``p @ V`` accumulates
  ``[T·Hp, H·D]`` of which head ``h`` keeps its own lanes. The matrix
  unit multiplies ``H`` times the useful terms, all exact zeros, and
  the step is bound by the tile's bytes. At a prefill chunk's rows
  (``T·Hp`` past a bound) and over an int8 pool the loop is
  python-unrolled over heads, a product a head.
- **Small-T prefill tail.** ``T`` is static per trace; the engine's
  prefill chunk (``T = prefill_chunk``) and its decode tick (``T = 1``)
  are two traces of the same kernel.

Parity contract: visibility is ``key j visible to query t iff
j <= lengths + t`` — exactly :func:`~mpit_tpu.models.gpt2.cached_attention`
(the reference's math), whose masked rows contribute exact zeros. Masked
positions inside a visited boundary tile score ``-1e30``; ``exp``
underflows to exactly 0.0 in f32, and tiles past the loop bound are
never read — so the kernel's masked-key contribution is exactly zero
too, and greedy decode through it keeps the token-level match with the
no-cache forward.

On non-TPU backends (``interpret=None``) the same math runs as the
reference XLA path; ``interpret=True`` forces the kernel through the
Pallas interpreter (the CPU-mesh test path, like the training kernel).

**Quantized variant (ISSUE 15).** Passing
:class:`~mpit_tpu.ops.kv_quant.QuantizedKV` buffers (int8 payload +
per-(row, head) f32 scales) selects the FUSED-DEQUANT form of the same
kernel: what crosses HBM→VMEM per visited tile is the int8 K/V tile
plus its ``[rows, H]`` scale block (two extra DMA channels on the
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
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.ops.kv_quant import QuantizedKV
from mpit_tpu.ops.ring_collectives import dequantize_blocks, sublane_for

__all__ = [
    "flash_paged_decode_attention",
    "grouped_paged_attention",
    "reference_grouped_paged_attention",
    "grouped_rows",
    "grouped_block_k",
    "paged_write_pages",
    "writes_by_pages",
    "reference_paged_decode_attention",
    "num_kv_blocks",
    "pick_block_k",
    "DecodeTiling",
    "decode_tiling",
]

_NEG_INF = -1e30  # large-but-finite; exp underflows to exactly 0.0 in f32


def _use_kernel(interpret: bool | None) -> bool:
    if interpret is not None:
        return True
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# Reference (XLA) path — also the non-TPU fallback.
# ---------------------------------------------------------------------------


def reference_paged_decode_attention(q, k_pool, v_pool, lengths, block_table):
    """Gather-dense paged attention — delegates to
    :func:`mpit_tpu.models.gpt2.paged_cached_attention`: the kernel's
    oracle and the non-TPU fallback ARE the serving reference, one
    implementation, so a numerics change there cannot silently
    desynchronize this module. Imported lazily: ops sits below models in
    the layering, and the models package must not load just because ops
    does."""
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
# Kernel. One grid program per slot; K/V stay in HBM and are DMA'd a tile
# of one or several pieces a step (double-buffered) so skipped rows are
# never read.
# ---------------------------------------------------------------------------

# Cache rows a loop step aims for (a paged step gathers pages). On the v5e
# at GPT-2 large's 1,280 lanes, 16 slots of 64-950 rows, tiles of 128, 256
# and 512 rows run a decode tick's call in 0.111 / 0.110 / 0.112 ms and a
# 64-row chunk's in 0.340 / 0.232 / 0.228 (PERF.md, PR 27): 256 it is,
# 1.25 MB of K and V a step, twice that double-buffered.
_TILE_ROWS = 256
# Most rows (T x heads padded to a VMEM tile) the heads-as-rows product
# takes. Same measurement: T = 1 / 2 / 4 / 5 / 8 (32 to 256 rows) take
# 0.110 / 0.115 / 0.136 / 0.149 / 0.191 ms a call as rows of one product
# and T = 12 / 16 (384 / 512 rows) 0.252 / 0.318, against 0.25-0.28 a head
# at a time whatever T: the forms meet near 430 rows. 256 keeps a
# speculative verify of up to eight rows on the fast side and the float32
# accumulator [rows, H*D] at 1.25 MB.
_HEAD_ROWS = 256


class DecodeTiling(NamedTuple):
    """What one step of the kernel's loop over the cache fetches and how
    it multiplies. ``piece_rows`` cache rows come by one DMA a buffer,
    ``pieces`` of them make a step's tile; ``form`` is ``heads_as_rows``
    (all heads the rows of one product a step) or ``per_head``."""

    form: str
    piece_rows: int
    pieces: int

    @property
    def rows(self) -> int:
        return self.piece_rows * self.pieces


def decode_tiling(t_q: int, num_heads: int, dtype, *, page_size: int,
                  quantized: bool = False) -> DecodeTiling:
    """The tiling the kernel runs for a call of this shape, from the
    shape alone. A step gathers consecutive pages of the slot's block
    table up to :data:`_TILE_ROWS` rows, each page its own DMA into its rows of the
    tile (a DMA's rows in a larger buffer start and end on a VMEM tile,
    8 rows of float32, 16 of bf16, 32 of int8: a pool whose page is not
    whole tiles keeps one page a step; a page
    larger than the tile is read in equal parts). At few query rows the
    heads are the rows of one product (``T`` groups of the heads padded
    to a VMEM tile, :data:`_HEAD_ROWS` rows at most); above that, and for
    an int8 pool (whose scales are per row and head), a product a head."""
    sub = sublane_for(dtype)
    piece = min(page_size, _TILE_ROWS)
    while page_size % piece:
        piece -= 1
    fits = piece == page_size and page_size % sub == 0
    pieces = _TILE_ROWS // piece if fits else 1
    head_rows = t_q * -(-num_heads // sub) * sub
    as_rows = not quantized and head_rows <= _HEAD_ROWS
    return DecodeTiling(
        "heads_as_rows" if as_rows else "per_head", piece, pieces
    )


def _decode_kernel(
    *refs,
    block_k,
    num_heads,
    head_dim,
    scale,
    tiling,
    page_size,
    quantized=False,
):
    """Flash-decode body, plain or fused-dequant.

    Refs: ``lengths_ref`` [B] int32 SMEM, ``bt_ref`` [B, pages_per_slot]
    int32 SMEM, ``q_ref`` [1, T, H·D] VMEM, ``k_hbm``/``v_hbm``
    [num_pages, page_size, H·D] ANY/HBM (the pool), ``o_ref``,
    ``visited_ref`` (whole [B] int32 SMEM, entry ``b`` written by
    program ``b``), scratch.

    A loop step takes one tile of ``tiling.pieces`` pieces of
    ``tiling.piece_rows`` rows, each piece one DMA a buffer, all of a
    step's DMAs in flight together and the next step's behind them
    (double buffer). Pieces past the slot's last visible key are not
    fetched; their rows of the VALUE buffers are zeroed instead (what a
    buffer held before is anything, NaN included, and ``0 x NaN`` in the
    ``p @ V`` product is NaN; their scores are masked, which a select
    does whatever K holds). ``block_k`` only counts: ``visited`` is the
    number of ``block_k``-row blocks with a visible key.

    ``tiling.form`` is ``heads_as_rows``: the block-diagonal query
    ``[T·Hp, H·D]`` (row ``t·Hp + h`` holds ``q[t]``'s head ``h`` in its
    own lanes, zero elsewhere) is built once, and a step is one scores
    product, one online-softmax update and one ``p @ V`` product for all
    heads: the added terms are exact zeros. Or ``per_head``: a product a
    head over the same tile, each with its own statistics.

    ``quantized`` (ISSUE 15): the HBM operand list interleaves scale
    planes — ``k, k_scale, v, v_scale`` with scales
    [num_pages, page_size, Hp] f32, Hp = H lane-padded to 128
    (:func:`_kv_operands`) — and the scratch grows matching double
    buffers on two extra DMA channels. Each visited tile dequantizes in
    VMEM, per head, through the shared
    :func:`~mpit_tpu.ops.ring_collectives.dequantize_blocks`; the rest
    of the loop is identical, in f32 operands.
    """
    refs = list(refs)
    lengths_ref = refs.pop(0)
    bt_ref = refs.pop(0)
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
    s = bt_ref.shape[1] * page_size  # virtual per-slot cache length
    b = pl.program_id(0)
    t_q = q_ref.shape[1]
    h_n, d = num_heads, head_dim
    length = lengths_ref[b]
    piece, g = tiling.piece_rows, tiling.pieces
    rows = piece * g

    def blocks(unit):
        # Units of ``unit`` rows with >= 1 visible key: ceil((L + T)/unit),
        # clamped to the slot's virtual cache (a stale/retired slot's
        # length can never overrun it; the clamp also bounds the
        # block-table index, so a stale table entry past the mapped pages
        # is never resolved).
        return jnp.clip((length + t_q + unit - 1) // unit, 1, s // unit)

    visited_ref[b] = blocks(block_k)
    n_pieces = blocks(piece)
    n_steps = (n_pieces + g - 1) // g

    def dma(which_hbm, which_buf, sem_row, slot, pi, j):
        # Piece ``pi`` of the slot's cache into rows ``j * piece ..`` of
        # the tile. ``piece`` divides the page (a piece never straddles
        # pages), so one SMEM lookup names its page.
        if piece == page_size:
            src = which_hbm.at[bt_ref[b, pi]]
        else:
            src = which_hbm.at[bt_ref[b, (pi * piece) // page_size],
                               pl.ds((pi * piece) % page_size, piece)]
        return pltpu.make_async_copy(
            src, which_buf.at[slot, rows_of(j)], sem.at[sem_row, slot]
        )

    def rows_of(j):
        start = j * piece
        return pl.ds(
            start if g == 1 else pl.multiple_of(start, piece), piece)

    def each_piece(lo, hi, body):
        """``body(j)`` for the tile's pieces ``lo <= j < hi``: a loop on
        the device, or the one piece of a one-piece tile (whose bounds
        are static)."""
        if g == 1:
            for j in range(lo, hi):
                body(j)
            return

        def step(j, carry):
            body(j)
            return carry

        lax.fori_loop(lo, hi, step, 0)

    # The per-piece DMA channel set: K and V always; their scale planes
    # ride two more channels of the same double buffer when quantized.
    channels = [(k_hbm, k_buf, 0), (v_hbm, v_buf, 1)]
    values = [v_buf]  # what reaches the p @ V product as it is
    if quantized:
        channels += [(ks_hbm, ks_buf, 2), (vs_hbm, vs_buf, 3)]
        values += [vs_buf]

    def held(si):
        """Pieces of step ``si`` that hold a visible key (all but in the
        slot's last step)."""
        return 1 if g == 1 else jnp.minimum(g, n_pieces - si * g)

    def fetch(si, slot):
        """Start step ``si``'s DMAs into ``slot``; zero the value rows of
        the pieces it does not fetch."""
        def start(j):
            for hbm, buf, row in channels:
                dma(hbm, buf, row, slot, si * g + j, j).start()

        def zero(j):
            for buf in values:
                buf[slot, rows_of(j), :] = jnp.zeros(
                    (piece, buf.shape[2]), buf.dtype)

        each_piece(0, held(si), start)
        each_piece(held(si), g, zero)

    def arrive(si, slot):
        """Wait for what :func:`fetch` started for step ``si``."""
        def wait(j):
            for hbm, buf, row in channels:
                dma(hbm, buf, row, slot, si * g + j, j).wait()

        each_piece(0, held(si), wait)

    fetch(0, 0)

    def tile(si):
        """The body's first half, shared by both forms: the next step's
        DMAs go out, this step's arrive."""
        slot = lax.rem(si, 2)

        @pl.when(si + 1 < n_steps)
        def _prefetch():
            fetch(si + 1, 1 - slot)

        arrive(si, slot)
        return slot

    def k_pos(si, m):
        return si * rows + lax.broadcasted_iota(jnp.int32, (m, rows), 1)

    def update(m, l, acc, sc, v_blk):
        """One online-softmax step on f32 scores ``sc`` (masked already):
        statistics ``m``, ``l`` [M, 1], accumulator ``acc``."""
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)  # masked cols: exactly 0.0
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc_new = alpha * acc + lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    def stats(m):
        return (jnp.full((m, 1), _NEG_INF, jnp.float32),
                jnp.zeros((m, 1), jnp.float32))

    if tiling.form == "heads_as_rows":
        w = h_n * d
        sub = sublane_for(q_ref.dtype)
        hp = -(-h_n // sub) * sub
        m_rows = t_q * hp
        # own[h, c]: lane c belongs to head h (rows past H own nothing).
        lane = lax.broadcasted_iota(jnp.int32, (hp, w), 1)
        head = lax.broadcasted_iota(jnp.int32, (hp, w), 0)
        own = (lane >= head * d) & (lane < (head + 1) * d)
        # The select runs on 32-bit lanes (a mask has float32's tiling)
        # and is exact; the operand goes back to the input dtype.
        q = q_ref[0].astype(jnp.float32)
        qbd = jnp.concatenate([
            jnp.where(own, jnp.broadcast_to(q[t : t + 1], (hp, w)), 0.0)
            for t in range(t_q)
        ], axis=0).astype(q_ref.dtype)  # [T*Hp, W]
        # Row t*Hp + h is query t: visible keys are j <= L + t.
        row = lax.broadcasted_iota(jnp.int32, (m_rows, rows), 0)
        t_pos = length + sum(
            ((row >= t * hp).astype(jnp.int32) for t in range(1, t_q)),
            jnp.zeros((m_rows, rows), jnp.int32),
        )

        def body(si, carry):
            slot = tile(si)
            # Matmul operands stay in the INPUT dtype (bf16 serving path)
            # with f32 accumulation; softmax statistics stay f32 and the
            # scale folds into the f32 scores (training-kernel idiom).
            sc = lax.dot_general(
                qbd, k_buf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [T*Hp, rows] f32
            sc = jnp.where(t_pos >= k_pos(si, m_rows), sc, _NEG_INF)
            return update(*carry, sc, v_buf[slot])

        init = stats(m_rows) + (jnp.zeros((m_rows, w), jnp.float32),)
        _, l, acc = lax.fori_loop(0, n_steps, body, init)
        # Key 0 is visible to every query (L >= 0), so no row of a real
        # head is ever fully masked; the guard only keeps a malformed
        # call finite.
        l = jnp.where(l == 0.0, 1.0, l)
        for t in range(t_q):
            part = slice(t * hp, (t + 1) * hp)
            # Head h's output is row h of its own lanes: the other rows'
            # entries in those lanes are other heads' weights on this
            # head's values, dropped here.
            num = jnp.sum(jnp.where(own, acc[part], 0.0), axis=0,
                          keepdims=True)
            den = jnp.sum(jnp.where(own, l[part], 0.0), axis=0,
                          keepdims=True)
            o_ref[0, t : t + 1, :] = (num / den).astype(o_ref.dtype)
        return

    t_pos = length + lax.broadcasted_iota(jnp.int32, (t_q, rows), 0)

    def body(si, carry):
        slot = tile(si)
        vis = t_pos >= k_pos(si, t_q)  # key j visible to query t iff j <= L + t
        out = []
        for h in range(h_n):
            q = q_ref[0, :, h * d : (h + 1) * d]  # [T, d]
            k_blk = k_buf[slot, :, h * d : (h + 1) * d]  # [rows, d]
            v_blk = v_buf[slot, :, h * d : (h + 1) * d]
            if quantized:
                # Fused per-tile dequant (ISSUE 15): the int8 tile and
                # its [rows, H] scale block are already in VMEM; the f32
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
            ) * scale  # [T, rows] f32
            sc = jnp.where(vis, sc, _NEG_INF)
            out += update(*carry[3 * h : 3 * h + 3], sc, v_blk)
        return tuple(out)

    init = []
    for _ in range(h_n):
        init += stats(t_q) + (jnp.zeros((t_q, d), jnp.float32),)
    carry = lax.fori_loop(0, n_steps, body, tuple(init))

    for h in range(h_n):
        l, acc = carry[3 * h + 1 : 3 * h + 3]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # as above
        o_ref[0, :, h * d : (h + 1) * d] = (acc / l_safe).astype(o_ref.dtype)


def _vma(x):
    # Inside a VMA-checked shard_map, pallas_call out_shapes must declare
    # how outputs vary across mesh axes; mirror the query operand's vma.
    return getattr(jax.typeof(x), "vma", frozenset()) or frozenset()


def _lane_pad(n: int) -> int:
    return -(-n // 128) * 128


def _kv_operands(k, v):
    """The kernel's HBM operand list + matching double-buffer scratch
    dtypes for one (K, V) pair of pool buffers — plain, or the quantized
    interleave ``k, k_scale, v, v_scale``. The pool is stored packed, as
    the kernel reads it, so its buffers go to the kernel as they are."""
    quantized = isinstance(k, QuantizedKV)
    if not quantized:
        return quantized, [k, v], [k.dtype, v.dtype]
    # Mosaic DMAs whole 128-lane tiles: a [.., H] f32 plane narrower
    # than a lane tile is refused ("slice shape ... must be aligned to
    # tiling (128)"), so the scale operand is lane-padded here.
    def psc(sc):
        return jnp.pad(
            sc, ((0, 0), (0, 0), (0, _lane_pad(sc.shape[-1]) - sc.shape[-1]))
        )

    ops = [k.q, psc(k.scale), v.q, psc(v.scale)]
    return quantized, ops, [jnp.int8, jnp.float32, jnp.int8, jnp.float32]


def _scratch_for(quantized, rows, hd, h, dtypes):
    """Double-buffer VMEM scratch matching :func:`_kv_operands`' order,
    a step's tile of ``rows`` each (+ the DMA semaphore array sized to
    the channel count)."""
    hp = _lane_pad(h)
    widths = [hd, hp, hd, hp] if quantized else [hd, hd]
    bufs = [
        pltpu.VMEM((2, rows, w), dt) for w, dt in zip(widths, dtypes)
    ]
    return bufs + [pltpu.SemaphoreType.DMA((len(widths), 2))]


@functools.partial(
    jax.jit, static_argnames=("block_k", "page_size", "interpret")
)
def _paged_decode_call(
    q, k_pool, v_pool, lengths, block_table, *, block_k, page_size,
    interpret,
):
    b, t, h, d = q.shape
    hd = h * d
    with jax.named_scope("kv_gather"):
        # The pools are stored as the kernel reads them: no repacking.
        quantized, kv_ops, kv_dtypes = _kv_operands(k_pool, v_pool)
    tiling = decode_tiling(
        t, h, kv_dtypes[0], page_size=page_size, quantized=quantized
    )
    kern = functools.partial(
        _decode_kernel,
        block_k=block_k,
        num_heads=h,
        head_dim=d,
        scale=1.0 / (d ** 0.5),
        tiling=tiling,
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
        scratch_shapes=_scratch_for(quantized, tiling.rows, hd, h, kv_dtypes),
        interpret=bool(interpret),
    )(
        jnp.asarray(lengths, jnp.int32),
        jnp.asarray(block_table, jnp.int32),
        q.reshape(b, t, hd), *kv_ops,
    )
    return o.reshape(b, t, h, d), visited


# ---------------------------------------------------------------------------
# The pool's writer. A prefill chunk lands B*T rows in a layer's buffer;
# XLA's scatter takes them a row at a time (146 ns a row on the v5e: 150
# us a buffer for 16 x 64 rows, 72 buffers a step). Rows of one slot are
# consecutive positions, so they fill whole pages but for the two ends:
# this kernel reads the touched pages, lays the new rows over them and
# writes them back, a page a DMA, every page of a group in flight at once.
# ---------------------------------------------------------------------------

_WRITE_GROUP_BYTES = 2 * 2**20  # pages held in VMEM at once, per buffer
_SKIP, _WHOLE, _PART = 0, 1, 2  # what a touched page takes from the rows


def _page_write_kernel(kind_ref, pidx_ref, keep_ref, rows_hbm, pool_hbm,
                       out_hbm, old, new, sem, *, group):
    """One group of touched pages. ``kind_ref`` / ``pidx_ref`` (SMEM, all
    groups) say what each takes and which page it is; ``rows_hbm``
    [pages, ps, W] holds the new rows in their places. A page that the
    rows fill whole goes from there to the pool in one DMA, HBM to HBM;
    a page they fill in part is read, overlaid where ``keep_ref``
    [group, ps, 1] is 0, and written back. ``out_hbm`` is ``pool_hbm``'s
    own buffer (aliased)."""
    del pool_hbm
    g0 = pl.program_id(0) * group

    def whole(i):
        return pltpu.make_async_copy(
            rows_hbm.at[g0 + i], out_hbm.at[pidx_ref[g0 + i]], sem.at[0, i]
        )

    def part(i, what):
        page = out_hbm.at[pidx_ref[g0 + i]]
        src, dst, row = {
            "old": (page, old.at[i], 0),
            "new": (rows_hbm.at[g0 + i], new.at[i], 1),
            "back": (old.at[i], page, 0),
        }[what]
        return pltpu.make_async_copy(src, dst, sem.at[row, i])

    def each(kind, body):
        def step(i, carry):
            pl.when(kind_ref[g0 + i] == kind)(lambda: body(i))
            return carry

        lax.fori_loop(0, group, step, 0)

    each(_WHOLE, lambda i: whole(i).start())

    def fetch(i):
        part(i, "old").start()
        part(i, "new").start()

    each(_PART, fetch)

    def merge(i):
        part(i, "old").wait()
        part(i, "new").wait()
        wide = jnp.float32 if jnp.issubdtype(old.dtype, jnp.floating) else (
            jnp.int32)  # a select on 32-bit lanes: exact for bf16 and int8
        old[i] = jnp.where(
            keep_ref[i] != 0, old[i].astype(wide), new[i].astype(wide)
        ).astype(old.dtype)
        part(i, "back").start()

    each(_PART, merge)
    each(_PART, lambda i: part(i, "back").wait())
    each(_WHOLE, lambda i: whole(i).wait())


def _paged_write_frames(new, lengths, block_table, valid, num_pages,
                        page_size):
    """The pages a write touches, one entry a (slot, page): what each
    takes [B*n] (nothing, every row, some rows), page ids [B*n], the new
    rows in their places of those pages [B*n, ps, W] and a flag
    [B*n, ps] that is 1 where the page's old row stays."""
    b, t = new.shape[0], new.shape[1]
    n = (t + page_size - 2) // page_size + 1  # pages T rows can span
    first, shift = lengths // page_size, lengths % page_size
    idx = first[:, None] + jnp.arange(n)[None, :]
    pidx = jnp.take_along_axis(
        block_table, jnp.clip(idx, 0, block_table.shape[1] - 1), axis=1
    )

    def frame(x):
        # Row r of a slot's frame is x[r - shift]: a window a slot of x
        # padded at both ends (a batched dynamic slice: XLA makes a loop
        # over the slots of it).
        lead = ((0, 0), (page_size, n * page_size - t))
        padded = jnp.pad(x, lead + ((0, 0),) * (x.ndim - 2))
        take = lambda rows, s: lax.dynamic_slice_in_dim(
            rows, page_size - s, n * page_size, axis=0
        )
        return jax.vmap(take)(padded, shift)

    lands = frame(jnp.ones((b, t), bool) if valid is None else valid)
    # Past the slot's table there is no page to write: dropped.
    lands = lands.reshape(b, n, page_size) & (
        idx < block_table.shape[1])[:, :, None]
    kind = jnp.where(
        lands.all(-1), _WHOLE, jnp.where(lands.any(-1), _PART, _SKIP)
    )
    return (
        kind.reshape(-1).astype(jnp.int32),
        jnp.clip(pidx, 0, num_pages - 1).reshape(-1).astype(jnp.int32),
        frame(new).reshape(b * n, page_size, -1),
        (~lands).reshape(b * n, page_size),
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_write_call(pool, new, lengths, block_table, valid, *, interpret):
    num_pages, ps, w = pool.shape
    kind, pidx, rows, keep = _paged_write_frames(
        new.astype(pool.dtype), jnp.asarray(lengths, jnp.int32),
        jnp.asarray(block_table, jnp.int32), valid, num_pages, ps,
    )
    n = pidx.shape[0]
    # The most pages whose rows fit the budget, in equal groups.
    fit = max(1, _WRITE_GROUP_BYTES // (ps * w * pool.dtype.itemsize))
    group = max(g for g in range(1, n + 1) if n % g == 0 and g <= fit)
    kern = functools.partial(_page_write_kernel, group=group)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kern,
        name="paged_kv_write",
        grid=(n // group,),
        in_specs=[
            smem, smem,  # kinds and page ids, whole
            pl.BlockSpec((group, ps, 1), lambda g: (g, 0, 0),
                         memory_space=pltpu.VMEM),
            hbm, hbm,  # the rows and the pool stay in HBM
        ],
        out_specs=hbm,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype, vma=_vma(new)),
        input_output_aliases={4: 0},  # written in place
        scratch_shapes=[
            pltpu.VMEM((group, ps, w), pool.dtype),
            pltpu.VMEM((group, ps, w), pool.dtype),
            pltpu.SemaphoreType.DMA((2, group)),
        ],
        interpret=bool(interpret),
    )(kind, pidx, keep.astype(jnp.int32)[:, :, None], rows, pool)


def writes_by_pages(pool, t: int) -> bool:
    """Whether :func:`paged_write_pages` is the way to land ``t`` rows a
    slot in ``pool`` [P, page_size, W]: on a TPU, for a page's worth of
    rows or more (fewer cost a row scatter less than the pages' round
    trip), into rows of whole lane tiles (an int8 pool's [.., H] scale
    plane cannot be DMA'd)."""
    return (
        _use_kernel(None) and t >= pool.shape[1]
        and pool.shape[-1] % 128 == 0
    )


def paged_write_pages(pool, new, lengths, block_table, valid=None, *,
                      interpret: bool | None = None):
    """Write ``new`` [B, T, W] into one page-pool buffer [P, page_size, W]
    at positions ``lengths .. lengths+T-1`` of each slot's block table,
    rows with ``valid`` False (and positions past the table) dropped:
    what :func:`mpit_tpu.models.gpt2.paged_cache_update`'s scatter does,
    a page at a time. Under a donating jit the pool is updated in place
    (``input_output_aliases``) and only the touched pages move: at most
    ``(T + page_size - 2) // page_size + 1`` a slot, each read, overlaid
    and written back whole. A page is written by one slot alone (a shared
    page is copied before it is written), so whole-page writes of
    different slots never meet; kept rows are rewritten with what was
    just read. ``interpret`` as in :func:`flash_paged_decode_attention`;
    there is no lax twin here, the scatter is it."""
    return _paged_write_call(
        pool, new, lengths, block_table, valid, interpret=bool(interpret)
    )


# ---------------------------------------------------------------------------
# Grouped-query heads, and a window. The pool row of a layer with fewer
# key/value heads than query heads is ``H_kv x D`` lanes; the ``G = H /
# H_kv`` query heads of a group are rows against ONE key/value head's
# lanes, the form ``ops/mla_attention.py`` has for a row all heads share.
# The same loop takes a first position: with ``window`` set a query at
# position ``t`` attends ``t - window < s <= t``, so the loop starts at the
# tile that holds the slot's first visible key and the tiles (and pages)
# before it are not visited.
# ---------------------------------------------------------------------------

# Query rows one call of the grouped kernel takes a slot (a longer chunk
# goes as that many calls' worth of "slots", each at its own fill of the
# same pages, as olmo_hybrid's full layers do): a key/value head's product
# then has ``rows x G`` rows, 384-576 at G = 6 and 9, and the eight heads'
# float32 accumulators stay under 2.5 MB of VMEM.
_GROUP_ROWS = 64


def grouped_rows(t_q: int) -> int:
    """Query rows a call of the grouped kernel takes a slot."""
    return _GROUP_ROWS if t_q % _GROUP_ROWS == 0 else t_q


def grouped_block_k(page_size: int) -> int:
    """Cache positions a step of the grouped kernel's loop takes: a
    divisor of the page, :data:`_TILE_ROWS` at most."""
    bk = min(_TILE_ROWS, page_size)
    while page_size % bk:
        bk -= 1
    return bk


def reference_grouped_paged_attention(q, k_pool, v_pool, lengths,
                                      block_table, *, window: int = 0):
    """Gather-dense attention of ``q`` [B, T, H, D] (query ``t`` at
    position ``lengths + t``) over pools ``[P, ps, H_kv x D]`` through
    ``block_table``: query head ``j`` reads key/value head ``j // (H /
    H_kv)``; with ``window``, positions ``t - window < s <= t`` only (a
    table entry outside a slot's window may name any page). The grouped
    kernel's oracle and the fallback off the TPU."""
    b, t, h, d = q.shape
    h_kv = k_pool.shape[-1] // d
    g = h // h_kv
    with jax.named_scope("kv_gather"):
        take = lambda pool: pool[
            jnp.clip(block_table, 0, pool.shape[0] - 1)
        ].reshape(b, -1, h_kv, d)
        k, v = take(k_pool), take(v_pool)
    qg = q.reshape(b, t, h_kv, g, d)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                   preferred_element_type=jnp.float32) * d ** -0.5
    pos = lengths[:, None] + jnp.arange(t)[None, :]  # [B, T]
    key = jnp.arange(k.shape[1])
    vis = key[None, None, :] <= pos[:, :, None]
    if window:
        vis &= key[None, None, :] > pos[:, :, None] - window
    p = jax.nn.softmax(jnp.where(vis[:, None, None], s, _NEG_INF), axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, t, h, d).astype(q.dtype)


def _grouped_kernel(lengths_ref, bt_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                    v_buf, sem, *, block_k, page_size, window, t_q, head_dim,
                    scale):
    """One slot: for each key/value head, its group's ``M = G x t_q`` query
    rows (row ``g x t_q + t`` is query ``t`` of the group's head ``g``;
    rows past that are padding) against that head's lanes of the slot's
    tiles. ``lengths_ref`` [B] and ``bt_ref`` [B, pages_per_slot] in SMEM,
    ``q_ref`` / ``o_ref`` [1, H_kv, M, D] in VMEM, the pools in HBM, a
    tile of ``block_k`` positions (a divisor of the page) a DMA, double
    buffered. The loop runs from the tile of the first position any of
    the slot's queries sees to the tile of the last."""
    b = pl.program_id(0)
    length = lengths_ref[b]
    s = bt_ref.shape[1] * page_size
    h_kv, m_rows, d = q_ref.shape[1], q_ref.shape[2], head_dim
    n_k = jnp.clip((length + t_q + block_k - 1) // block_k, 1, s // block_k)
    k_0 = (jnp.maximum(length - window + 1, 0) // block_k) if window else 0

    def dma(hbm, buf, row, slot, ki):
        page = bt_ref[b, (ki * block_k) // page_size]
        src = hbm.at[page, pl.ds((ki * block_k) % page_size, block_k)]
        return pltpu.make_async_copy(src, buf.at[slot], sem.at[row, slot])

    channels = [(k_hbm, k_buf, 0), (v_hbm, v_buf, 1)]
    for hbm, buf, row in channels:
        dma(hbm, buf, row, 0, k_0).start()

    # Row r is query r mod t_q, at position length + that.
    row = lax.broadcasted_iota(jnp.int32, (m_rows, block_k), 0)
    t_pos = length + (lax.rem(row, t_q) if t_q > 1 else 0)

    def body(ki, carry):
        slot = lax.rem(ki - k_0, 2)

        @pl.when(ki + 1 < n_k)
        def _prefetch():
            for hbm, buf, row in channels:
                dma(hbm, buf, row, 1 - slot, ki + 1).start()

        for hbm, buf, row in channels:
            dma(hbm, buf, row, slot, ki).wait()
        k_pos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (m_rows, block_k), 1)
        vis = k_pos <= t_pos
        if window:
            vis &= k_pos > t_pos - window
        out = []
        for h in range(h_kv):
            m, l, acc = carry[3 * h : 3 * h + 3]
            k_blk = k_buf[slot, :, h * d : (h + 1) * d]
            v_blk = v_buf[slot, :, h * d : (h + 1) * d]
            sc = lax.dot_general(
                q_ref[0, h], k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            sc = jnp.where(vis, sc, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            # A row whose window starts past this tile has seen nothing
            # yet (its m is still the floor): the select keeps exp(0)
            # out of its sums.
            p = jnp.where(vis, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            out += [
                m_new,
                alpha * l + jnp.sum(p, axis=1, keepdims=True),
                alpha * acc + lax.dot_general(
                    p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32),
            ]
        return tuple(out)

    init = []
    for _ in range(h_kv):
        init += [jnp.full((m_rows, 1), _NEG_INF, jnp.float32),
                 jnp.zeros((m_rows, 1), jnp.float32),
                 jnp.zeros((m_rows, d), jnp.float32)]
    carry = lax.fori_loop(k_0, n_k, body, tuple(init))
    for h in range(h_kv):
        l, acc = carry[3 * h + 1 : 3 * h + 3]
        # Every query sees its own position; the guard keeps a padding
        # row, or a malformed call, finite.
        o_ref[0, h] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_k", "interpret"))
def _grouped_call(q, k_pool, v_pool, lengths, block_table, *, window,
                  block_k, interpret):
    b, t, h, d = q.shape
    page_size, w = k_pool.shape[1], k_pool.shape[2]
    h_kv = w // d
    g = h // h_kv
    # [B, T, H, D] -> [B, H_kv, G x T, D], the group's rows padded to whole
    # sublane tiles (a decode tick's G rows to 16).
    sub = sublane_for(q.dtype)
    m_rows = -(-g * t // sub) * sub
    qg = jnp.transpose(q.reshape(b, t, h_kv, g, d), (0, 2, 3, 1, 4))
    qg = jnp.pad(qg.reshape(b, h_kv, g * t, d),
                 ((0, 0), (0, 0), (0, m_rows - g * t), (0, 0)))
    kern = functools.partial(
        _grouped_kernel, block_k=block_k, page_size=page_size, window=window,
        t_q=t, head_dim=d, scale=1.0 / (d ** 0.5))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    rows = pl.BlockSpec((1, h_kv, m_rows, d), lambda i: (i, 0, 0, 0),
                        memory_space=pltpu.VMEM)
    o = pl.pallas_call(
        kern,
        # A decode tick's calls and a chunk's are told apart by name.
        name="gqa_paged_decode_attn" if t == 1 else "gqa_paged_chunk_attn",
        grid=(b,),
        in_specs=[smem, smem, rows, hbm, hbm],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((b, h_kv, m_rows, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, block_k, w), k_pool.dtype),
            pltpu.VMEM((2, block_k, w), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
        # A chunk's programs hold their queries and results double
        # buffered and a float32 accumulator and statistics a head: 17.6
        # MB at 72 query heads, past the 16 MB a kernel gets unasked.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=(48 if t > 1 else 16) * 2**20),
        interpret=bool(interpret),
    )(jnp.asarray(lengths, jnp.int32), jnp.asarray(block_table, jnp.int32),
      qg, k_pool, v_pool)
    o = o[:, :, : g * t].reshape(b, h_kv, g, t, d)
    return jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(b, t, h, d)


def grouped_paged_attention(q, k_pool, v_pool, lengths, block_table, *,
                            window: int = 0,
                            interpret: bool | None = None):
    """Attention of ``q`` [B, T, H, D] (query ``t`` at position ``lengths
    + t``, its key and value already in the pool) against one layer's
    pools ``[P, page_size, H_kv x D]`` through ``block_table`` [B,
    pages_per_slot]: grouped-query heads (``H`` a multiple of ``H_kv``)
    and, with ``window``, the positions ``t - window < s <= t`` only: the
    slot's pages before its first visible position are not read, and
    their table entries may name any page. Returns ``[B, T, H, D]``.
    A chunk of more than :data:`_GROUP_ROWS` rows goes to the kernel that
    many rows a program. ``interpret`` as in
    :func:`flash_paged_decode_attention`."""
    if not _use_kernel(interpret):
        return reference_grouped_paged_attention(
            q, k_pool, v_pool, lengths, block_table, window=window)
    b, t, h, d = q.shape
    rows = grouped_rows(t)
    parts = t // rows
    lengths = jnp.asarray(lengths, jnp.int32)
    if parts > 1:
        q = q.reshape(b * parts, rows, h, d)
        lengths = (lengths[:, None] + rows * jnp.arange(parts)[None, :]
                   ).reshape(-1)
        block_table = jnp.repeat(block_table, parts, axis=0)
    out = _grouped_call(
        q, k_pool, v_pool, lengths, block_table, window=int(window),
        block_k=grouped_block_k(k_pool.shape[1]),
        interpret=bool(interpret))
    return out.reshape(b, t, h, d)


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
    """Length-aware attention against one layer's paged KV pool:
    ``[B, T, H, Dh]`` queries (the T newest positions, global position
    ``lengths + t``) vs ``[num_pages, page_size, H*Dh]``
    pools (rows packed head-major, the form
    :class:`~mpit_tpu.serve.kvcache.PagedKVCache` stores: the kernel
    DMAs its tiles straight out of the caller's buffer, with no copy or
    relayout of the pool before it), each slot's pages named by
    ``block_table`` [B, pages_per_slot] int32. A quantized pool is a
    :class:`~mpit_tpu.ops.kv_quant.QuantizedKV` of int8 rows in that
    shape and a ``[num_pages, page_size, H]`` scale plane.

    Drop-in for :func:`mpit_tpu.models.gpt2.paged_cached_attention`
    (plug in as ``GPT2Config.paged_attention_fn``). The loop runs over
    the slot's virtual ``pages_per_slot × page_size`` cache; a step's
    tile is gathered through the table, several pages at once
    (:func:`decode_tiling`); a slot holding ``L`` tokens visits
    ``ceil((L+T)/block_k)`` blocks. ``block_k``, the unit ``visited``
    counts in, defaults to the :func:`pick_block_k` choice for
    ``page_size`` and must divide it.

    ``interpret``: ``None`` = Pallas kernel on TPU, the gather-dense
    reference XLA path elsewhere; ``True`` = force the kernel through the
    interpreter (the CPU test path); ``False`` = force it compiled.

    ``return_visited``: also return the per-slot visited-block count
    ``[B] int32`` — on the kernel path this is written by the kernel
    itself (what the loop actually ran), on the reference path it is the
    host formula :func:`num_kv_blocks`; tests pin the two against each
    other."""
    page_size = k_pool.shape[1]
    bk = pick_block_k(page_size, block_k)
    if page_size % bk:
        # Validated on EVERY platform (the reference fallback could run
        # any block_k, but its visited-block accounting would describe a
        # tiling the kernel can't execute — code passing off-TPU must
        # not first fail at TPU deploy).
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
