"""Pallas flash attention — fused blockwise causal attention.

Not a reference capability (Torch7-era, pre-transformer; SURVEY.md §3.3):
this kernel exists for the GPT-2 stretch config (BASELINE.json #5) and as
the per-shard inner kernel under context parallelism
(:func:`mpit_tpu.parallel.ring_attention.ring_flash_attention`).

TPU-first design:

- **Never materializes the [T, T] score matrix.** The forward pass
  processes one ``block_q`` query tile per grid step and streams key/value
  tiles through a ``fori_loop``, maintaining the online-softmax running
  max/denominator/accumulator as loop carries in registers/VMEM — HBM
  traffic is O(T·D), not O(T²).
- **MXU-shaped**: all matmuls are [block_q, D] × [D, block_k] tiles with
  float32 accumulation (``preferred_element_type``), bf16-friendly inputs.
- **Causal block skipping**: the k-loop upper bound is derived from the
  query tile index (and the global offsets, below), so fully-masked key
  tiles are never visited; the diagonal tile applies the triangular mask.
- **Global position offsets**: ``q_offset``/``k_offset`` (traced scalars)
  shift the causal mask, so the same kernel computes one *block* of a
  longer sequence — the per-shard compute of ring attention. A key block
  entirely in this query block's future yields zero output and
  ``lse = -BIG`` (an exact no-op under the lse-merge).
- **Trainable**: ``jax.custom_vjp`` with the Flash-2 backward — the
  forward saves only the per-row logsumexp; the backward recomputes score
  tiles blockwise in two kernels (dq; dk/dv). The kernel's second output
  ``lse`` is differentiable too: its cotangent folds into the backward as
  ``delta → delta − g_lse`` (since ∂lse/∂S = P), which is what makes the
  ring-attention merge differentiable end-to-end with no extra kernels.

Layout contract: public API takes ``[B, T, H, D]`` (the sequence-major,
head-split layout of :mod:`mpit_tpu.models.gpt2` and the parallel layers).
On non-TPU backends the same math runs as a plain-XLA fallback (identical
semantics, used for parity tests and the CPU fake mesh).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30  # large-but-finite: -inf breaks exp-shift when a full row is masked

# Per-row scalars (logsumexp, delta) carry a broadcast 128-lane minor dim so
# their blocks satisfy the TPU (8, 128) tiling rule (the in-tree flash
# kernels use the same trick; MIN_BLOCK_SIZE=128).
_LANES = 128


def _use_kernel(interpret: bool | None) -> bool:
    if interpret is not None:
        return True
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# Reference (XLA) path — also the non-TPU fallback.
# ---------------------------------------------------------------------------


def reference_attention(q, k, v, *, causal: bool = True):
    """Plain attention in XLA, [B, T, H, D]; the parity oracle."""
    o, _ = reference_attention_with_lse(q, k, v, causal=causal)
    return o


def reference_attention_with_lse(q, k, v, *, q_offset=0, k_offset=0, causal=True):
    """XLA attention block returning ``(o [B,T,H,D], lse [B,H,T])``.

    Offset-aware causal masking; fully-masked rows yield ``o = 0`` and
    ``lse = -BIG`` (the merge-neutral element).
    """
    dh = q.shape[-1]
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(dh).astype(jnp.float32)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        q_pos = q_offset + lax.iota(jnp.int32, tq)
        k_pos = k_offset + lax.iota(jnp.int32, tk)
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    empty = m <= _NEG_INF / 2
    p = jnp.where(empty[..., None], 0.0, jnp.exp(s - m[..., None]))
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhqk,bkhd->bqhd", (p / l_safe[..., None]).astype(q.dtype), v)
    lse = jnp.where(empty, _NEG_INF, m + jnp.log(l_safe))
    o = jnp.where(empty.transpose(0, 2, 1)[..., None], 0.0, o).astype(q.dtype)
    return o, lse


# ---------------------------------------------------------------------------
# Kernels. Offsets arrive as (1,) int32 SMEM scalars.
# ---------------------------------------------------------------------------


def _causal_bounds(qoff, koff, qi, bq, bk, t, *, causal):
    """Number of key tiles the k-loop must visit (traced)."""
    n_total = t // bk
    if not causal:
        return n_total
    limit = qoff + qi * bq + bq - koff  # last visible key position + 1
    return jnp.clip((limit + bk - 1) // bk, 0, n_total)


def _mask(s, qoff, koff, qi, bq, ki, bk):
    q_pos = qoff + qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = koff + ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _fwd_kernel(
    qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
    *, block_k, causal, scale, num_heads, head_dim,
):
    """All-heads forward: operands arrive head-PACKED ``[1, rows, H·D]``
    (the model's native sequence-major layout viewed flat over heads —
    round-4 change, see the plumbing comment below). The head loop is
    python-unrolled; every per-head matmul is a static lane-slice of the
    packed VMEM tile."""
    bq = q_ref.shape[1]
    t = k_ref.shape[1]
    h_n, d = num_heads, head_dim
    qi = pl.program_id(1)
    qoff, koff = qoff_ref[0], koff_ref[0]

    n_k = _causal_bounds(qoff, koff, qi, bq, block_k, t, causal=causal)
    lse_cols = []
    for h in range(h_n):
        # Matmul operands stay in the INPUT dtype (bf16 on the training
        # path) with f32 accumulation — an f32xf32 MXU matmul runs at a
        # fraction of the bf16 rate (round-3 finding). Softmax statistics
        # stay f32; the scale folds into the f32 scores.
        q = q_ref[0, :, h * d : (h + 1) * d]  # [bq, d], input dtype

        m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((bq,), jnp.float32)
        acc0 = jnp.zeros((bq, d), jnp.float32)

        def body(ki, carry):
            m, l, acc = carry
            rows = pl.ds(ki * block_k, block_k)
            k_blk = k_ref[0, rows, h * d : (h + 1) * d]
            v_blk = v_ref[0, rows, h * d : (h + 1) * d]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [bq, bk] f32
            if causal:
                s = _mask(s, qoff, koff, qi, bq, ki, block_k)
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=1)
            acc_new = alpha[:, None] * acc + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc_new

        m, l, acc = lax.fori_loop(0, n_k, body, (m0, l0, acc0))
        # Fully-masked rows (empty k-range under offsets): o = 0,
        # lse = -BIG — the exact neutral element of the lse-merge.
        empty = m <= _NEG_INF / 2
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o = jnp.where(empty[:, None], 0.0, acc / l_safe[:, None])
        o_ref[0, :, h * d : (h + 1) * d] = o.astype(o_ref.dtype)
        lse_cols.append(jnp.where(empty, _NEG_INF, m + jnp.log(l_safe)))

    # lse lanes: one column per head, zero-padded to the 128-lane tile.
    lse_mat = jnp.stack(lse_cols, axis=1)  # [bq, H] f32
    if h_n < _LANES:
        lse_mat = jnp.concatenate(
            [lse_mat, jnp.zeros((bq, _LANES - h_n), jnp.float32)], axis=1
        )
    lse_ref[0] = lse_mat


def _p_from_lse(s, lse):
    """exp(s − lse) with the empty-row guard (lse = −BIG would overflow)."""
    return jnp.where(
        (lse <= _NEG_INF / 2)[:, None], 0.0, jnp.exp(s - lse[:, None])
    )


def _bwd_dq_kernel(
    qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, block_k, causal, scale, num_heads, head_dim,
):
    bq = q_ref.shape[1]
    t = k_ref.shape[1]
    h_n, d = num_heads, head_dim
    qi = pl.program_id(1)
    qoff, koff = qoff_ref[0], koff_ref[0]

    n_k = _causal_bounds(qoff, koff, qi, bq, block_k, t, causal=causal)
    for h in range(h_n):
        q = q_ref[0, :, h * d : (h + 1) * d]  # input dtype
        do = do_ref[0, :, h * d : (h + 1) * d]
        lse = lse_ref[0, :, h]
        delta = delta_ref[0, :, h]

        def body(ki, dq):
            rows = pl.ds(ki * block_k, block_k)
            k_blk = k_ref[0, rows, h * d : (h + 1) * d]
            v_blk = v_ref[0, rows, h * d : (h + 1) * d]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if causal:
                s = _mask(s, qoff, koff, qi, bq, ki, block_k)
            p = _p_from_lse(s, lse)  # [bq, bk] f32
            dp = jax.lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta[:, None])  # [bq, bk] f32
            return dq + jax.lax.dot_general(
                ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        dq = lax.fori_loop(0, n_k, body, jnp.zeros((bq, d), jnp.float32))
        dq_ref[0, :, h * d : (h + 1) * d] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
    *, block_q, causal, scale, num_heads, head_dim,
):
    bk = k_ref.shape[1]
    t = q_ref.shape[1]
    h_n, d = num_heads, head_dim
    ki = pl.program_id(1)
    qoff, koff = qoff_ref[0], koff_ref[0]

    n_q = t // block_q
    if causal:
        # First query tile whose rows can see this key tile.
        q_start = jnp.clip((koff + ki * bk - qoff) // block_q, 0, n_q)
    else:
        q_start = 0

    for h in range(h_n):
        k_blk = k_ref[0, :, h * d : (h + 1) * d]  # input dtype
        v_blk = v_ref[0, :, h * d : (h + 1) * d]

        def body(qi, carry):
            dk, dv = carry
            rows = pl.ds(qi * block_q, block_q)
            q = q_ref[0, rows, h * d : (h + 1) * d]
            do = do_ref[0, rows, h * d : (h + 1) * d]
            lse = lse_ref[0, rows, h]
            delta = delta_ref[0, rows, h]
            s = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [bq, bk]
            if causal:
                s = _mask(s, qoff, koff, qi, block_q, ki, bk)
            p = _p_from_lse(s, lse)
            p_lo = p.astype(do.dtype)
            dv_new = dv + jax.lax.dot_general(
                p_lo, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [bk, d]
            dp = jax.lax.dot_general(
                do, v_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta[:, None])
            dk_new = dk + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [bk, d]
            return dk_new, dv_new

        z = jnp.zeros((bk, d), jnp.float32)
        dk, dv = lax.fori_loop(q_start, n_q, body, (z, z))
        # dL/dk = scale · dsᵀ·q_raw — q is UNscaled here (the scale folds
        # into the f32 scores), so apply the factor explicitly.
        dk_ref[0, :, h * d : (h + 1) * d] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0, :, h * d : (h + 1) * d] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing over head-PACKED [B, T, H·D] views.
#
# Round-4 redesign: the kernels used to run on [B·H, T, D] views, forcing
# a physical (0,2,1,3) transpose of every q/k/v/o/do around every call —
# measured 21 ms/step of pure layout copies on the B=48/T=512 GPT-2 step
# (trace, BENCHMARKS.md). The packed form is a FREE reshape of the
# model's native [B, T, H, D]: blocks keep a legal (rows, H·D) trailing
# geometry, the grid drops to (B, row_tiles) (all heads per program,
# python-unrolled in the kernels), and lse/delta store one head per lane
# of the 128-lane minor dim ([B, T, 128], heads 0..H-1) — so nothing in
# the whole path materializes a transpose except the tiny [B, T, H]
# delta/lse relayouts at the custom-vjp boundary.
# ---------------------------------------------------------------------------


def _specs(block_rows: int, gd: int, ng: int):
    """Tile spec on the packed [B, T, H·D] array: a (rows, G·D) lane
    slice; grid index bg decomposes into (batch, head-group)."""
    return pl.BlockSpec(
        (1, block_rows, gd),
        lambda bg, i: (bg // ng, i, bg % ng),
        memory_space=pltpu.VMEM,
    )


def _full_spec(t: int, gd: int, ng: int):
    return pl.BlockSpec(
        (1, t, gd),
        lambda bg, i: (bg // ng, 0, bg % ng),
        memory_space=pltpu.VMEM,
    )


def _row_spec(block_rows: int):
    return pl.BlockSpec(
        (1, block_rows, _LANES), lambda bg, i: (bg, i, 0), memory_space=pltpu.VMEM
    )


def _smem_scalar():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _vma(x):
    # Inside a VMA-checked shard_map, pallas_call out_shapes must declare
    # how outputs vary across mesh axes; mirror the query operand's vma.
    return getattr(jax.typeof(x), "vma", frozenset()) or frozenset()


def _off(x):
    return jnp.asarray(x, jnp.int32).reshape((1,))


def _fwd_packed(q, k, v, qoff, koff, *, g, ng, d, causal, block_q, block_k, interpret):
    """q/k/v ``[B, T, H·D]`` → (o ``[B, T, H·D]``, lse ``[B·NG, T, LANES]``);
    ``g`` heads per program, ``ng`` groups (g·ng = H)."""
    b, t, hd = q.shape
    gd = g * d
    scale = 1.0 / (d ** 0.5)
    grid = (b * ng, t // block_q)
    kern = functools.partial(
        _fwd_kernel, block_k=block_k, causal=causal, scale=scale,
        num_heads=g, head_dim=d,
    )
    o, lse = pl.pallas_call(
        kern,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            _smem_scalar(), _smem_scalar(),
            _specs(block_q, gd, ng), _full_spec(t, gd, ng), _full_spec(t, gd, ng),
        ],
        out_specs=[_specs(block_q, gd, ng), _row_spec(block_q)],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, hd), q.dtype, vma=_vma(q)),
            jax.ShapeDtypeStruct((b * ng, t, _LANES), jnp.float32, vma=_vma(q)),
        ],
        interpret=bool(interpret),
    )(qoff, koff, q, k, v)
    return o, lse


def _bwd_packed(q, k, v, o, lse, do, g_lse, qoff, koff, *, g, ng, d, causal, block_q, block_k, interpret):
    """Packed backward. ``lse`` arrives ``[B·NG, T, LANES]`` (group-local
    head lanes); ``g_lse`` (if any) ``[B, H, T]``."""
    b, t, hd = q.shape
    h = g * ng
    gd = g * d
    scale = 1.0 / (d ** 0.5)
    # Flash-2 delta, with the lse cotangent folded in: ∂lse/∂S = P, so a
    # direct lse cotangent g adds g·P to dS — i.e. delta → delta − g.
    # Per-head delta straight from the packed layout: [B, T, H], then
    # regrouped to group-local lanes [B·NG, T, G] (small f32 relayout).
    delta = jnp.sum(
        (do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(b, t, h, d),
        axis=-1,
    )
    if g_lse is not None:
        delta = delta - g_lse.transpose(0, 2, 1)  # [B, H, T] -> [B, T, H]
    delta = (
        delta.reshape(b, t, ng, g).transpose(0, 2, 1, 3).reshape(b * ng, t, g)
    )
    if g < _LANES:
        delta = jnp.concatenate(
            [delta, jnp.zeros((b * ng, t, _LANES - g), jnp.float32)], axis=-1
        )

    full_row = lambda: pl.BlockSpec(
        (1, t, _LANES), lambda bg, i: (bg, 0, 0), memory_space=pltpu.VMEM
    )

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_k=block_k, causal=causal, scale=scale,
            num_heads=g, head_dim=d,
        ),
        name="flash_bwd_dq",
        grid=(b * ng, t // block_q),
        in_specs=[
            _smem_scalar(), _smem_scalar(),
            _specs(block_q, gd, ng),  # q tile
            _full_spec(t, gd, ng),  # k
            _full_spec(t, gd, ng),  # v
            _specs(block_q, gd, ng),  # do tile
            _row_spec(block_q),  # lse tile (group head lanes)
            _row_spec(block_q),  # delta tile (group head lanes)
        ],
        out_specs=_specs(block_q, gd, ng),
        out_shape=jax.ShapeDtypeStruct((b, t, hd), q.dtype, vma=_vma(q)),
        interpret=bool(interpret),
    )(qoff, koff, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, block_q=block_q, causal=causal, scale=scale,
            num_heads=g, head_dim=d,
        ),
        name="flash_bwd_dkv",
        grid=(b * ng, t // block_k),
        in_specs=[
            _smem_scalar(), _smem_scalar(),
            _full_spec(t, gd, ng),  # q
            _specs(block_k, gd, ng),  # k tile
            _specs(block_k, gd, ng),  # v tile
            _full_spec(t, gd, ng),  # do
            full_row(),  # lse
            full_row(),  # delta
        ],
        out_specs=[_specs(block_k, gd, ng), _specs(block_k, gd, ng)],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, hd), k.dtype, vma=_vma(q)),
            jax.ShapeDtypeStruct((b, t, hd), v.dtype, vma=_vma(q)),
        ],
        interpret=bool(interpret),
    )(qoff, koff, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API with custom VJP, [B, T, H, D].
# ---------------------------------------------------------------------------


def _pack(x):
    b, t, h, d = x.shape
    return x.reshape(b, t, h * d)  # free: contiguous view


# v5e scoped VMEM is 16 MiB/core; budget leaves margin for Mosaic scratch.
_VMEM_BUDGET = 14 * 2**20

# Sweep-validation hook (sweep_flash_vmem.py / tests/test_ops.py): force a
# specific head group instead of the estimator's choice, so the real
# compiler can be asked "does the group the estimator REJECTED actually
# overflow?". Never set outside those harnesses.
_GROUP_OVERRIDE: int | None = None


def _group_resident(t, g, d, block_q, block_k, itemsize):
    """Estimated per-program VMEM for a ``g``-head group. EVERYTHING is
    double-buffered across grid programs — including blocks that are
    "full" along the row dim, since the next (batch, group) program's
    operands prefetch while the current one computes. Calibrated against
    two measured points: T=2048/G=12 overflows 16 MiB by ~1 MiB;
    T=2048/G=6 overflows by 32 KiB; T=512/G=12 compiles and runs."""
    hd = g * d
    full_pair = 2 * 2 * t * hd * itemsize  # k+v (fwd/dq) or q+do (dkv), 2x-buffered
    rows = 2 * 2 * t * _LANES * 4  # lse + delta full f32 rows, 2x-buffered
    fwd_tiles = 4 * block_q * hd * itemsize * 2
    dq_tiles = 3 * block_q * hd * itemsize * 2 + 2 * 2 * block_q * _LANES * 4
    dkv_tiles = 4 * block_k * hd * itemsize * 2 + rows
    score = block_q * block_k * 4 + block_q * d * 4
    return full_pair + max(fwd_tiles, dq_tiles, dkv_tiles) + score


def usable_head_groups(h: int, d: int) -> list:
    """Proper divisors of H usable as head groups, largest first: the
    group's lane width G·D must be a 128-multiple (the block is a lane
    slice ``[1, rows, G·D]`` of the packed array). Shared by the chooser
    below and the sweep validator (``sweep_flash_vmem.py``) so the two
    cannot drift."""
    return [
        g
        for g in range(h - 1, 0, -1)
        if h % g == 0 and (g * d) % _LANES == 0
    ]


def _pick_head_group(t, h, d, block_q, block_k, itemsize, interpret=False):
    """Heads processed per kernel program. All-heads packing is fastest
    (fewest programs, no relayouts) but its resident set grows with T;
    when it no longer fits, fall back to head GROUPS — the block becomes
    a lane slice ``[1, rows, G·D]`` of the packed array (still zero
    transposes; legal when ``G·D`` is a 128-multiple). The smallest
    usable group is the largest-T escape hatch; beyond it, shard the
    sequence (ring attention) or use the XLA path. Interpret mode (the
    CPU fake mesh) has no VMEM — always full-heads there."""
    if interpret:
        return h
    if _GROUP_OVERRIDE is not None:
        return _GROUP_OVERRIDE
    if _group_resident(t, h, d, block_q, block_k, itemsize) <= _VMEM_BUDGET:
        return h
    # Usable groups: proper divisors of H whose lane width is a multiple
    # of 128 (G = H itself is legal regardless — full-dim minor block —
    # but it just failed the budget above).
    candidates = usable_head_groups(h, d)
    for g in candidates:
        if _group_resident(t, g, d, block_q, block_k, itemsize) <= _VMEM_BUDGET:
            return g
    if candidates:
        need = _group_resident(
            t, candidates[-1], d, block_q, block_k, itemsize
        )
        detail = (
            f"needs ~{need / 2**20:.1f} MiB VMEM even at the smallest "
            f"usable head group (G={candidates[-1]})"
        )
    else:
        detail = (
            f"has no lane-aligned head grouping (no proper divisor G of "
            f"H={h} with G*{d} a multiple of {_LANES}) and the full-head "
            "layout exceeds the budget"
        )
    raise ValueError(
        f"flash kernel: T={t} x H={h} x D={d} {detail} (budget "
        f"{_VMEM_BUDGET / 2**20:.0f} MiB). Shard the sequence "
        "(context-parallel ring attention, parallel/ring_attention.py) "
        "or use attention='xla' for this shape."
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, qoff, koff, causal, block_q, block_k, interpret):
    (out, lse), _ = _flash_fwd(
        q, k, v, qoff, koff, causal, block_q, block_k, interpret
    )
    return out, lse


def _flash_fwd(q, k, v, qoff, koff, causal, block_q, block_k, interpret):
    b, t, h, d = q.shape
    if h > _LANES:
        raise ValueError(f"flash kernel supports up to {_LANES} heads, got {h}")
    g = _pick_head_group(
        t, h, d, block_q, block_k, q.dtype.itemsize, interpret=bool(interpret)
    )
    ng = h // g
    op, lsep = _fwd_packed(
        _pack(q), _pack(k), _pack(v), qoff, koff,
        g=g, ng=ng, d=d, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    out = op.reshape(b, t, h, d)
    # [B·NG, T, LANES] group-local head-lane store -> public [B, H, T]
    # (tiny f32 relayout)
    lse = (
        lsep[:, :, :g]
        .reshape(b, ng, t, g)
        .transpose(0, 1, 3, 2)
        .reshape(b, h, t)
    )
    return (out, lse), (q, k, v, out, lsep, qoff, koff)


def _flash_bwd(causal, block_q, block_k, interpret, res, g_ct):
    q, k, v, out, lsep, qoff, koff = res
    g_o, g_lse = g_ct
    b, t, h, d = q.shape
    g = _pick_head_group(
        t, h, d, block_q, block_k, q.dtype.itemsize, interpret=bool(interpret)
    )
    ng = h // g
    # Note: without symbolic_zeros on the custom_vjp, a discarded lse
    # output still arrives as a dense zeros cotangent — the fold below then
    # costs one elementwise subtract on [B, T, H], negligible vs attention.
    dqp, dkp, dvp = _bwd_packed(
        _pack(q), _pack(k), _pack(v), _pack(out), lsep, _pack(g_o), g_lse,
        qoff, koff,
        g=g, ng=ng, d=d, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    f0 = np.zeros((1,), jax.dtypes.float0)  # int offsets: no cotangent
    return (
        dqp.reshape(b, t, h, d),
        dkp.reshape(b, t, h, d),
        dvp.reshape(b, t, h, d),
        f0,
        f0,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def _pick_block(t: int, want: int | None) -> int:
    """Resolve a block size: an explicit ``want`` is clamped to T (the
    caller owns divisibility); ``None`` auto-picks the largest
    power-of-two-descending candidate ≤ 512 that divides T — so every
    T divisible by 128 keeps working while big-T shapes get the fast
    512 tiles (measured round 3: 512-blocks ≈ 1.5× the 128-block
    kernel)."""
    if want is not None:
        return min(want, t)
    b = min(512, t)
    while b > 128 and t % b:
        b //= 2
    return b


def flash_attention_block(
    q,
    k,
    v,
    *,
    q_offset=0,
    k_offset=0,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
):
    """One attention *block* of a longer sequence: ``(o, lse)`` outputs.

    ``q_offset``/``k_offset`` (python ints or traced int scalars — e.g.
    ``axis_index * T_local`` inside shard_map) place this [B, Tq, H, D]
    query block and [B, Tk, H, D] key/value block in the global sequence
    for causal masking. Key blocks wholly in the future produce ``o = 0``
    and ``lse = −BIG``, the neutral element of :func:`merge_attention` —
    which is how ring attention composes blocks. Differentiable in
    q/k/v through both outputs.
    """
    tq, tk = q.shape[1], k.shape[1]
    block_q = _pick_block(tq, block_q)
    block_k = _pick_block(tk, block_k)
    if not _use_kernel(interpret):
        return reference_attention_with_lse(
            q, k, v, q_offset=q_offset, k_offset=k_offset, causal=causal
        )
    if tq % block_q or tk % block_k:
        raise ValueError(
            f"seq lens ({tq}, {tk}) must be divisible by blocks "
            f"({block_q}, {block_k})"
        )
    if tq != tk:
        raise ValueError(
            f"block kernel requires Tq == Tk (ring shards are equal); "
            f"got {tq} vs {tk}"
        )
    if interpret is None:
        interpret = False
    return _flash(
        q, k, v, _off(q_offset), _off(k_offset),
        causal, block_q, block_k, interpret,
    )


def merge_attention(o_a, lse_a, o_b, lse_b):
    """Merge two attention partial results over disjoint key sets.

    Inputs/outputs: ``o [B, T, H, D]`` (normalized within its key set),
    ``lse [B, H, T]``. Exact online-softmax combination; ``lse = −BIG``
    partials (fully-masked blocks) are absorbed as no-ops.
    """
    lse_new = jnp.logaddexp(lse_a, lse_b)
    w_a = jnp.exp(lse_a - lse_new).transpose(0, 2, 1)[..., None]
    w_b = jnp.exp(lse_b - lse_new).transpose(0, 2, 1)[..., None]
    return (o_a * w_a + o_b * w_b).astype(o_a.dtype), lse_new


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> Any:
    """Fused causal attention over ``[B, T, H, D]`` tensors.

    Drop-in for :func:`mpit_tpu.models.gpt2.default_attention` (plug in as
    ``GPT2Config.attention_fn``). ``T`` must be a multiple of the block
    sizes (pad upstream or pick smaller blocks — ``block_q``/``block_k``
    are clamped to ``T``).

    ``interpret``: ``None`` = run the Pallas kernel on TPU, plain-XLA
    fallback elsewhere; ``True`` = force the kernel through the Pallas
    interpreter (CPU-mesh testing); ``False`` = force the kernel compiled.

    Block defaults (512, clamped to T): measured on the v5e chip at
    B32/H12/T512/D64, fwd ms/iter by (block_q, block_k): 128/128 2.81,
    256/256 1.96, **512/512 1.82** (vs XLA 2.47) — small tiles pay loop
    and [bq, 64]-matmul underutilization; the scores tile at 512² is
    1 MB f32, comfortably VMEM-resident (round-3 tuning).
    """
    t = q.shape[1]
    block_q = _pick_block(t, block_q)
    block_k = _pick_block(t, block_k)
    if not _use_kernel(interpret):
        return reference_attention(q, k, v, causal=causal)
    if t % block_q or t % block_k:
        raise ValueError(
            f"seq len {t} must be divisible by block_q={block_q}, block_k={block_k}"
        )
    if interpret is None:
        interpret = False
    o, _ = _flash(
        q, k, v, _off(0), _off(0), causal, block_q, block_k, interpret
    )
    return o
