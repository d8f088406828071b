"""Speculative decoding — the pure math of draft-then-verify (ISSUE 13).

Decode is memory-bound at serving context lengths (PR 8 roofline:
``bound_modeled: hbm``): every tick sweeps params + visited KV tiles to
emit ONE token per slot. Speculation multiplies tokens per sweep: a tiny
draft model proposes ``k`` tokens per slot, the target scores all
``k+1`` positions in ONE cache-aware forward (the flash-decode kernel's
small-T trace), and per slot the longest verified prefix is emitted —
cache lengths simply do not advance past it, which IS the rollback (row
validity comes from ``lengths`` + the attention mask, never from buffer
contents).

This module holds the engine-agnostic pieces:

- :func:`draft_distribution` — the draft's proposal ``q`` under the
  request's temperature/top-k, mirroring the engine's
  ``sample_tokens`` semantics exactly (q is part of the acceptance
  contract, so it is pinned here, not improvised per engine);
- :func:`accept_emit` — longest-accepted-prefix + replacement
  emission with EOS/token-budget clamping, the piece that keeps the
  device cache's ``lengths`` and the host's per-request token list in
  lockstep (``serve.scheduler`` trusts ``n_emit`` blindly);
- :func:`verify_reference` — the FULL-LOGITS verifier: greedy argmax,
  modified-target probability of each drafted token, and the exact
  residual/bonus sample. The reference engine's spec path runs it
  directly on materialized logits; the blocked production path
  (:func:`mpit_tpu.ops.lm_head.lm_head_verify`) is pinned against it
  (bitwise at one vocab block — the test configs — and
  distributionally in general).

Exactness: greedy speculation accepts a drafted token iff it equals the
target argmax, so the emitted sequence is the non-speculative greedy
sequence bit-for-bit (the pinned invariant). Sampling goes through
exact rejection sampling (Leviathan et al., arXiv 2211.17192): accept
``x ~ q`` with probability ``min(1, p(x)/q(x))`` (drawn as
``u·q(x) < p(x)``), on reject draw from the residual
``norm(max(p − q, 0))`` — the emitted marginal is exactly ``p``, the
target's modified (temperature/top-k) distribution, for ANY draft. The
bonus token (all ``k`` accepted) reuses the same residual formula with
``q = 0``: ``max(p − 0, 0) = p`` is a plain target sample.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "accept_emit",
    "draft_distribution",
    "modified_logits",
    "register_draft_store",
    "verify_reference",
]

_NEG_BIG = -1e30  # exp underflows to exactly 0.0 in f32 (kernel idiom)


def register_draft_store(
    memledger, draft_params, *, target_params=None, kv_bytes: float = 0.0
) -> float:
    """Register the speculative engine's HBM footprint with the memory
    ledger (ISSUE 18). The draft is the one subsystem whose weight
    bytes are CONDITIONALLY real: a
    :func:`~mpit_tpu.serve.weights.draft_from_target` draft aliases
    target leaves (0 new bytes — granting them would double-count the
    target store against the device allocator), while a separately
    quantized or separately checkpointed draft holds its own buffers —
    so the grant counts only leaves NOT aliasing ``target_params``.
    The draft KV cache (``kv_bytes``) is always its own buffer — the
    draft pool mirrors the target pool's page geometry (same block tables,
    separate arrays) — and lands on the ``kv_pool`` line, where the
    per-page ``page_bytes`` already carries the draft term. Returns
    the granted draft-weight bytes; ``memledger=None`` is the unwired
    no-op arm."""
    if memledger is None:
        return 0.0
    from mpit_tpu.serve.weights import register_param_store

    granted = register_param_store(
        memledger, draft_params,
        subsystem="draft_weights", alias_of=target_params,
    )
    if kv_bytes:
        memledger.grant("kv_pool", float(kv_bytes), kind="draft_kv")
    return granted


def modified_logits(logits, temperature, top_k):
    """The per-slot top-k/temperature logit modification — ONE
    implementation shared by the engine's sampler
    (:func:`mpit_tpu.serve.engine.sample_tokens`) and the speculative
    proposal q below. Rejection-sampling exactness REQUIRES q to be
    exactly the distribution the engine draws from; sharing the math
    (rather than mirroring it) makes that a structural fact instead of
    a convention. Per slot: threshold at the k-th largest logit when
    ``top_k > 0``, then divide by ``max(temperature, 1e-6)``."""
    vocab = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_k - 1, 0, vocab - 1)
    thresh = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    masked = jnp.where(
        (top_k[:, None] > 0) & (logits < thresh), -jnp.inf, logits
    )
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    return masked / temp


def draft_distribution(logits, temperature, top_k):
    """The proposal distribution ``q``: ``logits`` [S, V] f32 under the
    per-slot ``temperature``/``top_k`` modifications of
    :func:`mpit_tpu.serve.engine.sample_tokens` (top-k threshold at the
    k-th largest logit, temperature floor 1e-6). Returns ``(probs,
    scaled)`` — ``probs`` [S, V] f32 is q itself (what rejection
    sampling integrates against), ``scaled`` the masked/temperature-
    scaled logits ``jax.random.categorical`` draws from (so the drafted
    token is an exact q sample). Greedy rows (``temperature <= 0``) are
    accepted by argmax equality, never through q — their near-delta
    probs are computed but unused."""
    scaled = modified_logits(logits, temperature, top_k)
    probs = jax.nn.softmax(scaled, axis=-1)
    return probs, scaled


def accept_emit(drafted, greedy, p_x, q_x, u, repl, greedy_row, budget, eos):
    """Longest-accepted-prefix emission for one verify pass.

    Args (``S`` slots, ``k`` drafted tokens per slot):
      drafted: [S, k] int32 draft proposals (position ``j`` is the
        candidate for the ``j+1``-th new token this tick).
      greedy: [S, k+1] int32 target argmax per verified position.
      p_x: [S, k] f32 modified-target probability of each drafted token.
      q_x: [S, k] f32 draft probability of each drafted token.
      u: [S, k] f32 uniforms — sampled-row acceptance is
        ``u·q(x) < p(x)`` (the division-free spelling of
        ``u < p/q``; q(x) > 0 because x was drawn from q).
      repl: [S, k+1] int32 residual/bonus samples (position ``n_acc``
        is emitted on the first reject; position ``k`` is the bonus).
      greedy_row: [S] bool — rows accepting by argmax equality.
      budget: [S] int32 tokens the request may still emit
        (``max_new_tokens − generated``; clamped to ≥ 1).
      eos: [S] int32 per-request EOS id, ``-1`` = none — emission stops
        WITH the first EOS, exactly where the non-speculative scheduler
        would have retired the slot.

    Returns ``(emit [S, k+1] int32, n_emit [S] int32, n_acc [S]
    int32)``: slot ``s`` emits ``emit[s, :n_emit[s]]`` and its cache
    length advances by exactly ``n_emit[s]`` — positions past it hold
    junk K/V (rejected drafts) that the mask hides and the next append
    overwrites. ``n_emit >= 1`` always (the replacement/bonus token is
    this tick's guaranteed token, speculation never emits less than
    plain decode). The per-slot ``n_acc``/``n_emit`` split is also the
    request-ledger observable (ISSUE 16): the scheduler's ``spec_tick``
    events record them per request per tick, so a rollback STREAK — the
    per-request pathology the aggregate acceptance rate averages away —
    is visible in a why-slow exemplar lifeline.
    """
    s, k = drafted.shape
    acc_samp = u * q_x < p_x
    acc_greedy = drafted == greedy[:, :k]
    acc = jnp.where(greedy_row[:, None], acc_greedy, acc_samp)
    accp = jnp.cumprod(acc.astype(jnp.int32), axis=1)
    n_acc = accp.sum(axis=1)
    j = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    repl_tok = jnp.where(greedy_row[:, None], greedy, repl)
    drafted_pad = jnp.pad(drafted, ((0, 0), (0, 1)))
    emit = jnp.where(
        j < n_acc[:, None],
        drafted_pad,
        jnp.where(j == n_acc[:, None], repl_tok, 0),
    ).astype(jnp.int32)
    n_prelim = n_acc + 1
    is_eos = (eos[:, None] >= 0) & (emit == eos[:, None]) & (
        j < n_prelim[:, None]
    )
    eos_idx = jnp.min(jnp.where(is_eos, j, k + 1), axis=1)
    n_emit = jnp.minimum(
        n_prelim, jnp.minimum(eos_idx + 1, jnp.maximum(budget, 1))
    )
    return emit, n_emit.astype(jnp.int32), n_acc.astype(jnp.int32)


def verify_reference(
    logits, drafted, qprobs, key, temperature, top_k, *,
    k_cap: int = 128, block_size: int = 8192,
):
    """Full-logits verifier: the oracle the blocked path is pinned to.

    ``logits`` [N, V] f32 target logits (one row per slot×position),
    ``drafted`` [N] int32 (the drafted token each row scored; ignored
    value on bonus rows), ``qprobs`` [N, V] f32 draft probabilities
    (ZEROS on bonus rows — the residual then IS a plain target
    sample). Returns ``(greedy [N] int32, p_x [N] f32, repl [N]
    int32)``.

    Noise contract — shared with
    :func:`mpit_tpu.ops.lm_head.lm_head_verify` so the two are
    BITWISE comparable when the (padded) vocabulary is one block (the
    test configs): the vocab pads to a multiple of the resolved block;
    block ``b``'s residual Gumbel field is ``gumbel(fold_in(key, b),
    (N, block))`` and the top-k buffer's is ``gumbel(fold_in(key,
    n_blocks), (N, k_cap))``. Top-k semantics mirror
    ``lm_head_sample``: threshold at the k-th largest logit INSIDE the
    width-``k_cap`` candidate buffer; the modified distribution's
    support is the buffer entries at or above it.
    """
    n, vocab = logits.shape
    block = min(block_size, vocab + (-vocab) % 128)
    pad = (-vocab) % block
    if pad:
        logits = jnp.concatenate(
            [logits, jnp.full((n, pad), _NEG_BIG, logits.dtype)], axis=1
        )
        qprobs = jnp.concatenate(
            [qprobs, jnp.zeros((n, pad), qprobs.dtype)], axis=1
        )
    n_blocks = logits.shape[1] // block
    temp = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    greedy = jnp.argmax(logits, axis=1).astype(jnp.int32)
    scaled = logits / temp[:, None]
    m = jnp.max(scaled, axis=1)
    lse_full = m + jnp.log(jnp.sum(jnp.exp(scaled - m[:, None]), axis=1))
    kb = min(k_cap, vocab)
    bv, bi = lax.top_k(logits, kb)  # descending — the buffer's order
    kk = jnp.clip(jnp.asarray(top_k, jnp.int32), 1, kb)
    thresh = jnp.take_along_axis(bv, (kk - 1)[:, None], axis=1)[:, 0]
    keep = bv >= thresh[:, None]
    sc_b = bv / temp[:, None]
    m_b = jnp.max(jnp.where(keep, sc_b, -jnp.inf), axis=1)
    lse_topk = m_b + jnp.log(
        jnp.sum(jnp.where(keep, jnp.exp(sc_b - m_b[:, None]), 0.0), axis=1)
    )
    lx = jnp.take_along_axis(
        logits, jnp.asarray(drafted, jnp.int32)[:, None], axis=1
    )[:, 0]
    top_k = jnp.asarray(top_k, jnp.int32)
    p_x = jnp.where(
        top_k > 0,
        jnp.where(lx >= thresh, jnp.exp(lx / temp - lse_topk), 0.0),
        jnp.exp(lx / temp - lse_full),
    )
    # Residual over the top-k support (all inside the buffer):
    q_b = jnp.take_along_axis(qprobs, bi, axis=1)
    p_b = jnp.where(keep, jnp.exp(sc_b - lse_topk[:, None]), 0.0)
    res_b = jnp.maximum(p_b - q_b, 0.0)
    g_b = jax.random.gumbel(
        jax.random.fold_in(key, n_blocks), (n, kb), jnp.float32
    )
    buf_tok = jnp.take_along_axis(
        bi, jnp.argmax(jnp.log(res_b) + g_b, axis=1)[:, None], axis=1
    )[:, 0]
    # Residual over the full vocabulary (top_k == 0 sampling rows),
    # blockwise noise — gated exactly like the blocked path (greedy
    # rows take the argmax, top-k rows the buffer draw; no row needing
    # the full-vocab draw means the sweep is skipped, and the oracle
    # must mirror that to stay bitwise comparable):
    def _pass_b(_):
        best = jnp.full((n,), -jnp.inf, jnp.float32)
        best_i = jnp.zeros((n,), jnp.int32)
        for b in range(n_blocks):
            off = b * block
            sl = slice(off, off + block)
            p_blk = jnp.exp(scaled[:, sl] - lse_full[:, None])
            res = jnp.maximum(p_blk - qprobs[:, sl], 0.0)
            g = jax.random.gumbel(
                jax.random.fold_in(key, b), (n, block), jnp.float32
            )
            valid = off + jnp.arange(block) < vocab
            score = jnp.where(valid[None, :], jnp.log(res) + g, -jnp.inf)
            sm = jnp.max(score, axis=1)
            smi = jnp.argmax(score, axis=1).astype(jnp.int32) + off
            upd = sm > best
            best = jnp.where(upd, sm, best)
            best_i = jnp.where(upd, smi, best_i)
        return best_i

    need_b = jnp.any(
        (top_k == 0) & (jnp.asarray(temperature, jnp.float32) > 0.0)
    )
    full_tok = lax.cond(
        need_b, _pass_b, lambda _: jnp.zeros((n,), jnp.int32), None
    )
    repl = jnp.where(top_k > 0, buf_tok, full_tok).astype(jnp.int32)
    return greedy, p_x, repl
