"""Fused LM-head cross entropy — blockwise over the vocabulary.

The LM head is GPT-2's single biggest matmul: ``[B·T, d_model] x
[vocab, d_model]`` with vocab 50257. The naive path materializes the
``[B, T, vocab]`` float32 logits (B=8, T=512 → 823 MB), reads them back
through ``log_softmax`` and again through ``take_along_axis``, and then
does it all once more transposed in the backward pass — the largest HBM
cost in the whole model (this was the round-1 throughput ceiling; see
BENCHMARKS.md).

TPU-native fix, same trick as flash attention (``ops/flash_attention.py``):
stream over vocabulary blocks with an online logsumexp, so the live logits
tile is ``[B·T, block]`` and the full logits array never exists. The
backward pass recomputes each block's logits and feeds the two MXU matmuls

    dh      = Σ_j (softmax_j − onehot_j)·ct  @  head_j
    dhead_j = ((softmax_j − onehot_j)·ct)ᵀ  @  h

directly — the softmax Jacobian contraction is exact (a ``custom_vjp``
with the per-token logsumexp as the only saved activation), not a
truncation. Savings: O(B·T·V) f32 HBM traffic → O(B·T) residuals, and the
matmuls run with bfloat16 operands (f32 accumulation) at full MXU rate
when ``compute_dtype`` says so.

No reference analogue (the reference predates transformers; SURVEY.md
§3.3) — this enters via the GPT-2 stretch config (BASELINE.json #5) and
the round-1 verdict's perf mandate.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from mpit_tpu.comm import collectives as C
from mpit_tpu.ops.quantized_matmul import (
    QuantizedTensor,
    dequantize_tensor,
)

_NEG_BIG = -1e30  # "-inf" that survives subtraction without NaNs


def _reduce_to_vma(x, primal):
    """psum ``x`` over any mesh axes it varies over but ``primal`` doesn't."""
    have = set(getattr(jax.typeof(x), "vma", frozenset()) or ())
    want = set(getattr(jax.typeof(primal), "vma", frozenset()) or ())
    extra = tuple(sorted(have - want))
    return lax.psum(x, extra) if extra else x


def _match_vma(x, *refs):
    """Retype ``x`` to carry the union of ``refs``' device-varying axes.

    Inside ``shard_map`` the scan carries below start replicated (plain
    ``jnp.zeros``) while the loop body mixes in device-varying operands —
    jax 0.9's VMA checker then rejects the carry-in/carry-out type
    mismatch. No-op outside shard_map (empty vma)."""
    names: set = set()
    for r in refs:
        names |= set(getattr(jax.typeof(r), "vma", frozenset()) or frozenset())
    return C.vary(x, tuple(names)) if names else x


def _block_logits(h, head_block, valid, compute_dtype):
    """[N, D] x [rows, D] -> [N, block] f32 logits (``block`` is
    ``valid``'s length, ``rows <= block``); padded cols -> -big.

    A quantized head block (ISSUE 17) dequantizes HERE, per vocab tile
    inside the scan — the only f32 view of the head that ever exists is
    this [block, D] tile, which is exactly the in-kernel fused-dequant
    discipline the int8 weight store demands of the decode head (the
    single biggest weight in the model)."""
    # Scoped here so that a sampler that streams the head
    # (lm_head_sample, lm_head_verify) still shows the product apart
    # from the sampling round it.
    with jax.named_scope("lm_head"):
        if isinstance(head_block, QuantizedTensor):
            head_block = dequantize_tensor(head_block)
        logits = jnp.dot(
            h.astype(compute_dtype),
            head_block.astype(compute_dtype).T,
            preferred_element_type=jnp.float32,
        )
    short = valid.shape[0] - logits.shape[1]
    if short:
        # The ragged last block of a sampler's head (_block_runner):
        # its [N, tail] logits are padded to the tile, not the table's
        # rows, so the caller sees the tile a zero-padded table gave.
        logits = jnp.pad(
            logits, ((0, 0), (0, short)), constant_values=_NEG_BIG
        )
    return jnp.where(valid[None, :], logits, _NEG_BIG)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _xent2d(h, head, targets, vocab, block, compute_dtype):
    loss, _ = _xent2d_fwd(h, head, targets, vocab, block, compute_dtype)
    return loss


def _xent2d_fwd(h, head, targets, vocab, block, compute_dtype):
    """h [N, D] , head [Vp, D] (padded), targets [N] → per-token loss [N]."""
    n_blocks = head.shape[0] // block
    head_blocks = head.reshape(n_blocks, block, head.shape[1])
    offsets = jnp.arange(n_blocks, dtype=jnp.int32) * block
    n = h.shape[0]

    def tick(carry, xs):
        m, s, tl = carry
        head_b, off = xs
        valid = off + jnp.arange(block, dtype=jnp.int32) < vocab
        logits = _block_logits(h, head_b, valid, compute_dtype)
        bm = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, bm)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1
        )
        # Target logit, if this block covers it.
        lt = targets - off
        in_blk = (lt >= 0) & (lt < block)
        idx = jnp.clip(lt, 0, block - 1)
        cand = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        tl = jnp.where(in_blk, cand, tl)
        return (m_new, s, tl), None

    init = _match_vma(
        (
            jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32),
        ),
        h,
        head,
        targets,
    )
    # Unrolled vocab loop (round-4 chip measurement, B=48/T=512 GPT-2
    # step): a rolled scan serializes the per-block matmuls behind loop
    # plumbing and carries, costing ~15 ms/step; unrolling lets XLA
    # software-pipeline blocks (120.8k -> 130.3k tok/s end to end).
    # 7 blocks at vocab 50257 / block 8192 — full unroll; capped for
    # degenerate tiny-block configs.
    (m, s, tl), _ = lax.scan(
        tick, init, (head_blocks, offsets), unroll=min(n_blocks, 16)
    )
    lse = m + jnp.log(s)
    return lse - tl, (h, head, targets, lse)


def _xent2d_bwd(vocab, block, compute_dtype, res, ct):
    h, head, targets, lse = res
    n_blocks = head.shape[0] // block
    head_blocks = head.reshape(n_blocks, block, head.shape[1])
    offsets = jnp.arange(n_blocks, dtype=jnp.int32) * block

    def tick(dh, xs):
        head_b, off = xs
        valid = off + jnp.arange(block, dtype=jnp.int32) < vocab
        logits = _block_logits(h, head_b, valid, compute_dtype)
        p = jnp.exp(logits - lse[:, None])  # padded cols: exp(-big) == 0
        lt = targets - off
        onehot = (lt[:, None] == jnp.arange(block, dtype=jnp.int32)[None, :])
        g = (p - onehot.astype(p.dtype)) * ct[:, None]  # [N, block] f32
        gc = g.astype(compute_dtype)
        dh = dh + jnp.dot(
            gc, head_b.astype(compute_dtype), preferred_element_type=jnp.float32
        )
        dhead_b = jnp.dot(
            gc.T, h.astype(compute_dtype), preferred_element_type=jnp.float32
        )
        return dh, dhead_b

    dh0 = _match_vma(jnp.zeros(h.shape, jnp.float32), h, head, targets, ct)
    # Unrolled like the forward (see _xent2d_fwd): also lets the stacked
    # dhead blocks write straight to their output slices instead of
    # dynamic-update-slicing through the scan carry machinery.
    dh, dhead_blocks = lax.scan(
        tick, dh0, (head_blocks, offsets), unroll=min(n_blocks, 16)
    )
    dhead = dhead_blocks.reshape(head.shape)
    # Custom-VJP contract: each cotangent must carry exactly its primal's
    # varying type. When the cotangent picked up axes the primal doesn't
    # vary over (e.g. replicated head under a varying loss), the correct
    # cotangent is the psum over those axes — the same reduction VMA-aware
    # AD inserts automatically for ordinary ops.
    return (
        _reduce_to_vma(dh, h).astype(h.dtype),
        _reduce_to_vma(dhead, head).astype(head.dtype),
        None,
    )


_xent2d.defvjp(_xent2d_fwd, _xent2d_bwd)


def lm_head_xent(
    h,
    head,
    targets,
    *,
    block_size: int = 8192,
    compute_dtype=jnp.bfloat16,
):
    """Per-token cross entropy ``-log p(target)`` straight from hiddens.

    Args:
      h: ``[..., d_model]`` final hidden states (any float dtype).
      head: ``[vocab, d_model]`` LM-head / tied-embedding weight.
      targets: ``[...]`` int32 target token ids (same leading shape as h).
      block_size: vocabulary tile width; the live logits tile is
        ``[n_tokens, block_size]`` f32.
      compute_dtype: matmul operand dtype (f32 accumulation regardless) —
        ``bfloat16`` runs the MXU at full rate; pass ``float32`` for
        exact parity with the materialized-logits path.

    Returns:
      ``[...]`` float32 per-token losses (callers apply masks / means —
      the context-parallel tier needs the per-token granularity for its
      cross-shard target masking, ``parallel/cp.py``).
    """
    if isinstance(head, QuantizedTensor):
        raise ValueError(
            "lm_head_xent is the TRAINING head — the int8 weight store "
            "(ISSUE 17) is a serving format with no gradient contract; "
            "train in f32 (or dequantize_tensor explicitly, accepting "
            "the materialized [vocab, d] f32 weight)"
        )
    vocab, d = head.shape
    block = min(block_size, _round_up(vocab, 128))
    pad = (-vocab) % block
    if pad:
        head = jnp.concatenate(
            [head, jnp.zeros((pad, d), head.dtype)], axis=0
        )
    lead = targets.shape
    h2 = h.reshape(-1, d)
    t2 = targets.reshape(-1).astype(jnp.int32)
    loss = _xent2d(h2, head, t2, vocab, block, jnp.dtype(compute_dtype))
    return loss.reshape(lead)


def _round_up(x: int, m: int) -> int:
    return x + (-x) % m


def _block_runner(head, block):
    """``run(tick, init, xs) -> carry``: ``tick(carry, (head_b, *x)) ->
    (carry, None)`` over the head's vocabulary blocks, a scan over the
    ``vocab // block`` full ones and then once more on the ragged last
    ``vocab % block`` rows if there are any. ``xs`` are the arrays that
    ride beside the blocks (offsets, block ids, ``qprobs`` tiles), a row
    a block, the tail's last. ``head`` is a plain array or a
    :class:`~mpit_tpu.ops.quantized_matmul.QuantizedTensor` (``q`` and
    ``scale`` go together: each tick receives one ``(q [rows, d], scale
    [rows, 1])`` pair).

    The table is never padded or copied; the tail's LOGITS are padded
    (:func:`_block_logits`). Which form runs is read from the shapes:

    - the block divides the vocabulary: the tiles are a reshape of the
      table, taken here, and ``run`` is the one scan over ``(tiles,
      *xs)`` that it always was;
    - a ragged table is read where it lies: every tick slices its block
      out of ``head`` itself, inside the loop, so the slice fuses into
      the product. (A prefix of the table tiled by a reshape and handed
      to the scan is a buffer to the compiler, 126 MB for GPT-2: the
      copy this form exists to avoid, under the name ``slice``.)"""
    n_full, tail_rows = divmod(head.shape[0], block)
    unroll = min(n_full, 16)
    if not tail_rows:
        tiles = jax.tree.map(
            lambda x: x.reshape(n_full, block, x.shape[-1]), head
        )
        return lambda tick, init, xs: lax.scan(
            tick, init, (tiles, *xs), unroll=unroll
        )[0]

    def rows(start, size):
        return jax.tree.map(
            lambda x: lax.dynamic_slice_in_dim(x, start, size), head
        )

    def run(tick, carry, xs):
        if n_full:
            starts = jnp.arange(n_full, dtype=jnp.int32) * block
            carry, _ = lax.scan(
                lambda c, x: tick(c, (rows(x[0], block), *x[1:])),
                carry,
                (starts, *(x[:n_full] for x in xs)),
                unroll=unroll,
            )
        tail = rows(n_full * block, tail_rows)
        return tick(carry, (tail, *(x[-1] for x in xs)))[0]

    return run


# ---------------------------------------------------------------------------
# Blocked decode head: greedy / top-k / temperature sampling straight from
# hiddens, streaming over vocab blocks (ISSUE 5). The serving engine's
# decode step used to materialize the full [slots, vocab] f32 logits just
# to pick one token per slot; this computes the pick per vocab block with
# a running top-k merge, so the live tile is [slots, block] — the same
# trick lm_head_xent plays for training, applied to sampling. A call in
# which no row samples runs a scan that keeps the running argmax alone
# (ISSUE 33).
# ---------------------------------------------------------------------------


def _merge_first_max(gv, gi, logits, off):
    """Merge a block's ``(max, argmax)`` into the running pair. Strict
    ``>`` keeps the FIRST max across blocks, as ``jnp.argmax`` does
    within one: the pair ends as ``argmax`` over the whole vocabulary."""
    bm = jnp.max(logits, axis=-1)
    bmi = jnp.argmax(logits, axis=-1).astype(jnp.int32) + off
    upd = bm > gv
    return jnp.where(upd, bm, gv), jnp.where(upd, bmi, gi)


def lm_head_sample(
    h,
    head,
    key,
    temperature,
    top_k,
    *,
    block_size: int = 8192,
    k_cap: int = 128,
    compute_dtype=jnp.float32,
):
    """Sample one token per row from ``softmax(h @ headᵀ)`` without ever
    materializing the ``[rows, vocab]`` logits.

    Args:
      h: ``[S, d_model]`` final hidden states (the decode positions).
      head: ``[vocab, d_model]`` LM-head / tied-embedding weight.
      key: PRNG key; block ``i`` draws its Gumbel noise from
        ``fold_in(key, i)`` — the per-block derivation IS the sampling
        contract (the full-logits oracle in tests reproduces it
        exactly), replacing ``jax.random.categorical``'s monolithic
        ``[S, vocab]`` field which cannot be drawn blockwise.
      temperature: ``[S]`` f32; ``<= 0`` selects greedy for that row.
      top_k: ``[S]`` int32; ``> 0`` restricts sampling to the k
        highest-logit tokens (``0`` = full vocab). Must be ``<= k_cap``
        (the static running-buffer width) — the engine validates at
        submit time.
      block_size / compute_dtype: as :func:`lm_head_xent` — the live
        logits tile is ``[S, block]`` f32, matmul operands in
        ``compute_dtype`` with f32 accumulation.
      k_cap: static width of the running top-k candidate buffer.

    **Two paths, chosen on the device by what the rows ask for**
    (``lax.cond`` on ``jnp.any(temperature > 0)``, outside the scan):

    - *no row samples* (every serving tick of greedy traffic): a scan
      that carries the running ``(max, argmax)`` of the raw logits and
      nothing else — one product, one max and one argmax a vocab block;
      no noise is drawn, nothing is divided, sorted or gathered. Greedy
      bit-matches ``argmax`` over the full logits because the
      strict-``>`` merge keeps the first occurrence, exactly
      ``jnp.argmax``'s tie rule;
    - *some row samples*: the general scan, which carries per vocab
      block (1) the same running argmax, by the same expressions on the
      same tile, so a greedy row's token does not depend on which path
      its neighbours sent the call down; (2) the running argmax of
      ``logit/temp + gumbel`` — exact full-vocab categorical via the
      Gumbel-max trick; (3) the top-``k_cap`` (value, index,
      noised-score) triples merged across blocks — the final top-k draw
      thresholds at the k-th largest value *inside the buffer* and
      Gumbel-argmaxes the survivors, so no second pass over the
      vocabulary is needed. One sampling row sends the whole call down
      this path, at the price every call paid before there were two.

    The predicate is computed from ``temperature`` alone, which a mesh
    replicates, so every device of one takes the same branch.

    Returns ``[S]`` int32 token ids.
    """
    vocab, d = head.shape
    block = min(block_size, _round_up(vocab, 128))
    over_blocks = _block_runner(head, block)
    n_blocks = -(-vocab // block)
    offsets = jnp.arange(n_blocks, dtype=jnp.int32) * block
    blk_ids = jnp.arange(n_blocks, dtype=jnp.int32)
    n = h.shape[0]
    kb = min(k_cap, vocab)
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    cd = jnp.dtype(compute_dtype)
    neg = jnp.full((n,), -jnp.inf, jnp.float32)
    zero_i = jnp.zeros((n,), jnp.int32)

    def block_logits(head_b, off):
        valid = off + jnp.arange(block, dtype=jnp.int32) < vocab
        return _block_logits(h, head_b, valid, cd), valid  # [S, block] f32

    def greedy_path():
        def tick(carry, xs):
            head_b, off = xs
            logits, _ = block_logits(head_b, off)
            return _merge_first_max(*carry, logits, off), None

        _, gi = over_blocks(tick, (neg, zero_i), (offsets,))
        return gi

    def general_path():
        temp = jnp.maximum(temperature, 1e-6)

        def tick(carry, xs):
            gv, gi, sv, si, bv, bi, bs = carry
            head_b, off, blk = xs
            logits, valid = block_logits(head_b, off)
            # (1) greedy: the greedy path's merge.
            gv, gi = _merge_first_max(gv, gi, logits, off)
            # (2) full-vocab Gumbel-max on temperature-scaled logits.
            g = jax.random.gumbel(
                jax.random.fold_in(key, blk), (n, block), jnp.float32
            )
            scaled = jnp.where(
                valid[None, :], logits / temp[:, None] + g, _NEG_BIG
            )
            sm = jnp.max(scaled, axis=-1)
            smi = jnp.argmax(scaled, axis=-1).astype(jnp.int32) + off
            supd = sm > sv
            sv, si = jnp.where(supd, sm, sv), jnp.where(supd, smi, si)
            # (3) running top-k candidates: merge this block's top-kb
            # (value, global index, noised score) into the buffer.
            cv, ci = lax.top_k(logits, min(kb, block))
            cs = jnp.take_along_axis(scaled, ci, axis=-1)
            allv = jnp.concatenate([bv, cv], axis=-1)
            alli = jnp.concatenate([bi, ci + off], axis=-1)
            alls = jnp.concatenate([bs, cs], axis=-1)
            bv, sel = lax.top_k(allv, kb)
            bi = jnp.take_along_axis(alli, sel, axis=-1)
            bs = jnp.take_along_axis(alls, sel, axis=-1)
            return (gv, gi, sv, si, bv, bi, bs), None

        init = (
            neg, zero_i,  # greedy running (max, argmax)
            neg, zero_i,  # full-vocab gumbel running (max, argmax)
            jnp.full((n, kb), _NEG_BIG, jnp.float32),  # top-k values
            jnp.zeros((n, kb), jnp.int32),  # top-k global indices
            jnp.full((n, kb), _NEG_BIG, jnp.float32),  # top-k noised scores
        )
        gv, gi, sv, si, bv, bi, bs = over_blocks(
            tick, init, (offsets, blk_ids)
        )
        # Top-k draw: threshold at the row's k-th largest value inside
        # the buffer (reference semantics: keep logits >= thresh),
        # Gumbel-argmax the survivors.
        kk = jnp.clip(top_k, 1, kb)
        thresh = jnp.take_along_axis(bv, (kk - 1)[:, None], axis=-1)
        kept = jnp.where(bv >= thresh, bs, -jnp.inf)
        tk_tok = jnp.take_along_axis(
            bi, jnp.argmax(kept, axis=-1)[:, None], axis=-1
        )[:, 0]
        sampled = jnp.where(top_k > 0, tk_tok, si)
        return jnp.where(temperature <= 0.0, gi, sampled).astype(jnp.int32)

    return lax.cond(jnp.any(temperature > 0.0), general_path, greedy_path)


# ---------------------------------------------------------------------------
# Blocked speculative verifier (ISSUE 13): score k drafted tokens + the
# bonus position against the target distribution, streaming over vocab
# blocks — the [rows, vocab] f32 logits never exist. Two passes over the
# head blocks: pass A collects the statistics whose normalizers the
# residual needs (greedy argmax, full-support logsumexp, top-k candidate
# buffer, the drafted token's logit); pass B draws the full-vocab
# residual sample with the finalized normalizer. The top-k residual
# never needs pass B: the modified distribution's support lives entirely
# inside the pass-A buffer.
# ---------------------------------------------------------------------------


def lm_head_verify(
    h,
    head,
    drafted,
    qprobs,
    key,
    temperature,
    top_k,
    *,
    block_size: int = 8192,
    k_cap: int = 128,
    compute_dtype=jnp.float32,
):
    """Per-row verify quantities for exact speculative sampling.

    Args:
      h: ``[N, d_model]`` hidden rows — one per (slot, verify position),
        N = slots × (k+1).
      head: ``[vocab, d_model]`` LM-head / tied-embedding weight.
      drafted: ``[N]`` int32 — the drafted token each row scored (any
        value on bonus rows; their ``p_x`` is unused).
      qprobs: ``[N, vocab]`` f32 draft probabilities (ZEROS on bonus
        rows, making their residual a plain target sample).
      key: PRNG key. The noise contract (shared bitwise with
        :func:`mpit_tpu.serve.spec.verify_reference` at one vocab
        block): block ``b`` draws ``gumbel(fold_in(key, b), (N,
        block))``; the buffer residual draws ``gumbel(fold_in(key,
        n_blocks), (N, k_cap))``.
      temperature / top_k: ``[N]`` per-row modifications — the
        ``lm_head_sample`` semantics (threshold at the k-th largest
        logit inside the width-``k_cap`` buffer).

    Returns ``(greedy [N] int32, p_x [N] f32, repl [N] int32)``:
    target argmax (bit-matching ``lm_head_sample``'s greedy rule —
    strict-``>`` first-max merge), the modified-target probability of
    the drafted token, and the residual/bonus sample
    (``norm(max(p − q, 0))`` via Gumbel-argmax).
    """
    vocab, d = head.shape
    block = min(block_size, _round_up(vocab, 128))
    pad = (-vocab) % block
    if pad:
        qprobs = jnp.concatenate(
            [qprobs, jnp.zeros((qprobs.shape[0], pad), qprobs.dtype)],
            axis=1,
        )
    over_blocks = _block_runner(head, block)
    n_blocks = -(-vocab // block)
    offsets = jnp.arange(n_blocks, dtype=jnp.int32) * block
    blk_ids = jnp.arange(n_blocks, dtype=jnp.int32)
    n = h.shape[0]
    kb = min(k_cap, vocab)
    temp = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    drafted = jnp.asarray(drafted, jnp.int32)
    top_k = jnp.asarray(top_k, jnp.int32)
    cd = jnp.dtype(compute_dtype)

    def tick_a(carry, xs):
        gv, gi, m, s, tl, bv, bi = carry
        head_b, off = xs
        valid = off + jnp.arange(block, dtype=jnp.int32) < vocab
        logits = _block_logits(h, head_b, valid, cd)  # [N, block] f32
        gv, gi = _merge_first_max(gv, gi, logits, off)
        # Full-support logsumexp of logits/temp (padded cols: -big).
        scaled = logits / temp[:, None]
        sm = jnp.max(scaled, axis=-1)
        m_new = jnp.maximum(m, sm)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(scaled - m_new[:, None]), axis=-1
        )
        # The drafted token's RAW logit, when this block covers it.
        lt = drafted - off
        in_blk = (lt >= 0) & (lt < block)
        idx = jnp.clip(lt, 0, block - 1)
        cand = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        tl = jnp.where(in_blk, cand, tl)
        # Running top-kb candidate buffer (raw logits + global indices).
        cv, ci = lax.top_k(logits, min(kb, block))
        allv = jnp.concatenate([bv, cv], axis=-1)
        alli = jnp.concatenate([bi, ci + off], axis=-1)
        bv, sel = lax.top_k(allv, kb)
        bi = jnp.take_along_axis(alli, sel, axis=-1)
        return (gv, gi, m_new, s, tl, bv, bi), None

    neg = jnp.full((n,), -jnp.inf, jnp.float32)
    zero_i = jnp.zeros((n,), jnp.int32)
    init = (
        neg, zero_i,  # greedy running (max, argmax)
        neg, jnp.zeros((n,), jnp.float32),  # full-support lse (m, s)
        jnp.full((n,), _NEG_BIG, jnp.float32),  # drafted token's logit
        jnp.full((n, kb), _NEG_BIG, jnp.float32),  # top-k values
        jnp.zeros((n, kb), jnp.int32),  # top-k global indices
    )
    gv, gi, m, s, tl, bv, bi = over_blocks(tick_a, init, (offsets,))
    lse_full = m + jnp.log(s)
    kk = jnp.clip(top_k, 1, kb)
    thresh = jnp.take_along_axis(bv, (kk - 1)[:, None], axis=1)[:, 0]
    keep = bv >= thresh[:, None]
    sc_b = bv / temp[:, None]
    m_b = jnp.max(jnp.where(keep, sc_b, -jnp.inf), axis=1)
    lse_topk = m_b + jnp.log(
        jnp.sum(jnp.where(keep, jnp.exp(sc_b - m_b[:, None]), 0.0), axis=1)
    )
    p_x = jnp.where(
        top_k > 0,
        jnp.where(tl >= thresh, jnp.exp(tl / temp - lse_topk), 0.0),
        jnp.exp(tl / temp - lse_full),
    )
    # Top-k residual: support ⊆ buffer, so the draw never leaves it.
    q_b = jnp.take_along_axis(qprobs, bi, axis=1)
    p_b = jnp.where(keep, jnp.exp(sc_b - lse_topk[:, None]), 0.0)
    res_b = jnp.maximum(p_b - q_b, 0.0)
    g_b = jax.random.gumbel(
        jax.random.fold_in(key, n_blocks), (n, kb), jnp.float32
    )
    buf_tok = jnp.take_along_axis(
        bi, jnp.argmax(jnp.log(res_b) + g_b, axis=1)[:, None], axis=1
    )[:, 0]

    # Pass B: full-vocab residual (top_k == 0 sampling rows) with the
    # finalized normalizer — same blockwise matmul, fresh per-block
    # Gumbel noise. Gated: greedy rows take the argmax replacement and
    # top-k rows the buffer draw, so when NO row samples the full
    # vocabulary the second head sweep is pure waste — skip it (the
    # oracle mirrors the gate, keeping the bitwise pin).
    def _pass_b(_):
        qp_blocks = qprobs.reshape(n, n_blocks, block).transpose(1, 0, 2)

        def tick_b(carry, xs):
            rv, ri = carry
            head_b, off, blk, qp_b = xs
            valid = off + jnp.arange(block, dtype=jnp.int32) < vocab
            logits = _block_logits(h, head_b, valid, cd)
            p = jnp.exp(logits / temp[:, None] - lse_full[:, None])
            res = jnp.maximum(p - qp_b, 0.0)
            g = jax.random.gumbel(
                jax.random.fold_in(key, blk), (n, block), jnp.float32
            )
            score = jnp.where(valid[None, :], jnp.log(res) + g, -jnp.inf)
            sm = jnp.max(score, axis=-1)
            smi = jnp.argmax(score, axis=-1).astype(jnp.int32) + off
            upd = sm > rv
            return (jnp.where(upd, sm, rv), jnp.where(upd, smi, ri)), None

        _, ri = over_blocks(
            tick_b, (neg, zero_i), (offsets, blk_ids, qp_blocks)
        )
        return ri

    need_b = jnp.any(
        (top_k == 0) & (jnp.asarray(temperature, jnp.float32) > 0.0)
    )
    ri = lax.cond(need_b, _pass_b, lambda _: zero_i, None)
    repl = jnp.where(top_k > 0, buf_tok, ri).astype(jnp.int32)
    return gi, p_x, repl
