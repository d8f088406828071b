"""The gated delta rule: a linear-attention layer's recurrence.

A head keeps a matrix ``S`` [d_k, d_v] (float32) a sequence, whatever the
sequence's length. A token with key ``k``, value ``v``, query ``q``, decay
``alpha = exp(g)`` (``g <= 0``) and write strength ``beta`` does::

    S <- alpha S + beta k (v - (alpha S)^T k)^T        o = S^T q

(Gated DeltaNet: the delta rule's rank-one correction of what the decayed
state holds under ``k``; ``beta`` in (1, 2) flips the sign of the state's
component along ``k``: a negative eigenvalue.) Three forms, the same
mathematics:

- :func:`gdn_scan`: the recurrence as written, a ``lax.scan`` over
  tokens. The oracle of the other two.
- :func:`gdn_chunk`: the chunkwise form for a prefill chunk. Over a block
  of ``C`` = 64 tokens with ``G_t`` the decay summed from the block's
  start, ``W`` [C, d_v] (row ``t``: ``beta_t`` times the token's
  correction) solves the unit lower triangular system

      (I + A) W = beta V - (e^G beta K) S_0,
      A[t, i] = beta_t e^(G_t - G_i) (k_t . k_i)   for i < t

  and then ``O = (e^G Q) S_0 + ((Q K^T) . e^(G_t - G_i), i <= t) W`` and
  ``S_C = e^(G_C) S_0 + (e^(G_C - G) K)^T W``: matrix products in place of
  64 dependent rank-one updates. ``(I + A)^-1`` is built by doubling
  (:func:`_unit_lower_inverse`): the inverse of the block diagonal of
  block size ``b`` gives that of size ``2b`` in two products, six times
  over, which is forward substitution by blocks and as steady as it. The
  decays enter as differences ``G_t - G_i <= 0`` only, so nothing
  overflows however strong the decay. A token with ``g = 0`` and
  ``beta = 0`` (the padding behind a chunk's last prompt token) leaves
  the state as it was, exactly. The Pallas kernel runs a program for a
  few heads of a sequence, the state in VMEM from the first block to the
  last; the operands come head-major (``[.., H, T, d]``, a transposition
  the caller's XLA makes), so that a head's 96 or 192 lanes are a whole
  array dimension and are padded to lane tiles in VMEM, not in HBM.
  :func:`gdn_chunk_lax` is the same algorithm in ``jax.numpy``: the
  kernel's twin off the TPU.
- :func:`gdn_step`: one token a sequence, every sequence's state updated
  in place (the buffer is aliased to the result): a decode tick. The
  kernel is a program a sequence over its heads on the vector unit;
  :func:`gdn_step_lax` is its twin.

Shapes: ``q``, ``k`` [B, T, H, d_k], ``v`` [B, T, H, d_v], ``g``, ``beta``
[B, T, H] float32, ``state`` [B, H, d_k, d_v] float32; the step's have no
``T``. Outputs take ``v``'s dtype; the state stays float32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.ops.decode_attention import _use_kernel

__all__ = [
    "BLOCK",
    "gdn_scan",
    "gdn_chunk",
    "gdn_chunk_lax",
    "gdn_step",
    "gdn_step_lax",
]

BLOCK = 64  # tokens a block of the chunkwise form
_HI = lax.Precision.HIGHEST
_F32 = jnp.float32


def gdn_scan(q, k, v, g, beta, state):
    """The recurrence, a token at a time: ``(o [B, T, H, d_v] float32,
    state)``. Float32 at ``highest``."""

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # [B, H, ..]
        s = s * jnp.exp(g_t)[..., None, None]
        r = jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=_HI)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t,
                           b_t[..., None] * (v_t - r), precision=_HI)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HI)

    f = lambda a: jnp.moveaxis(a.astype(_F32), 1, 0)
    state, o = lax.scan(step, state.astype(_F32),
                        (f(q), f(k), f(v), f(g), f(beta)))
    return jnp.moveaxis(o, 0, 1), state


# -- the chunkwise form ---------------------------------------------------------


class _BlockMasks(NamedTuple):
    """What every block of a head shares, made once a call: ``eye``, the
    lower triangle with (``lower``) and without (``strict``) the diagonal,
    ``levels`` (for ``b`` = 1, 2, .. C/2 the lower left ``b x b`` quarter of
    every diagonal block of size ``2b``), and ``last`` [d_k, C], true in
    the block's last column."""

    eye: jax.Array
    lower: jax.Array
    strict: jax.Array
    levels: tuple
    last: jax.Array


def _block_masks(c: int, dk: int) -> _BlockMasks:
    r = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    levels, shift = [], 0
    while (1 << shift) < c:
        same = (r >> (shift + 1)) == (col >> (shift + 1))
        levels.append(same & (((r >> shift) & 1) == 1)
                      & (((col >> shift) & 1) == 0))
        shift += 1
    return _BlockMasks(
        r == col, r >= col, r > col, tuple(levels),
        lax.broadcasted_iota(jnp.int32, (dk, c), 1) == c - 1)


def _unit_lower_inverse(a, masks: _BlockMasks, mm):
    """``(I + a)^-1`` for strictly lower triangular ``a`` [c, c]: ``x``
    starts as the inverse of the diagonal (the identity); with ``x`` the
    inverse of the block diagonal of block size ``b``, that of size ``2b``
    is ``x - x (a . m_b) x``, ``m_b`` the lower left quarters."""
    x = masks.eye.astype(_F32)
    for m in masks.levels:
        x = x - mm(mm(x, jnp.where(m, a, 0.0)), x)
    return x


def _block_update(qb, kb, kt, kbeta, vbeta, grow, s, masks: _BlockMasks, mm,
                  mm_in):
    """One block of ``C`` tokens of one head: ``(o [C, d_v], s_new)``.
    ``kt`` is ``kb`` transposed [d_k, C], ``kbeta`` / ``vbeta`` the keys
    and values times ``beta``, ``grow`` [1, C] the decay summed from the
    block's start, ``s`` [d_k, d_v] the state before the block. ``mm`` is
    the float32 product, ``mm_in`` the product of two operands in the
    input's dtype (exact in float32 accumulation whichever it is)."""
    c, dk = grow.shape[-1], s.shape[0]
    # The same decays down a column: row t of ``gcol`` is G_t.
    gcol = jnp.sum(jnp.where(masks.eye, jnp.broadcast_to(grow, (c, c)), 0.0),
                   axis=1, keepdims=True)
    decay = jnp.exp(jnp.minimum(gcol - grow, 0.0))
    a = jnp.where(masks.strict, mm_in(kbeta, kt) * decay, 0.0)
    t_inv = _unit_lower_inverse(a, masks, mm)
    gam = jnp.exp(gcol)  # [C, 1]
    f32 = lambda x: x.astype(_F32)
    w = mm(t_inv, f32(vbeta) - mm(gam * f32(kbeta), s))
    o = mm(gam * f32(qb), s) + mm(
        jnp.where(masks.lower, mm_in(qb, kt) * decay, 0.0), w)
    # The block's whole decay down a column of d_k rows (Mosaic does not
    # broadcast a [1, 1] along sublanes and lanes at once).
    g_all = jnp.sum(jnp.where(masks.last, jnp.broadcast_to(grow, (dk, c)),
                              0.0), axis=1, keepdims=True)
    g_end = grow[:, c - 1:c]  # [1, 1]
    s_new = jnp.exp(g_all) * s + mm(f32(kt) * jnp.exp(g_end - grow), w)
    return o, s_new


def _chunk_operands(q, k, v, g, beta):
    """The kernel's (and the twin's) operands from the caller's: head
    major, ``beta`` folded into keys and values, the decay summed within
    blocks, the keys a second time transposed by block. ``T`` is padded
    to whole blocks with tokens that change nothing."""
    b, t, h, dk = q.shape
    pad = -t % BLOCK
    if pad:
        z = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, g, beta = z(q), z(k), z(v), z(g), z(beta)
    nb = (t + pad) // BLOCK
    hm = lambda x: jnp.swapaxes(x, 1, 2)  # [B, H, T, d]
    bf = beta.astype(_F32)[..., None]
    kbeta = (k.astype(_F32) * bf).astype(k.dtype)
    vbeta = (v.astype(_F32) * bf).astype(v.dtype)
    gc = jnp.cumsum(g.astype(_F32).reshape(b, nb, BLOCK, h), axis=2)
    gc = jnp.transpose(gc, (0, 3, 1, 2))  # [B, H, NB, C]
    kt = jnp.transpose(k.reshape(b, nb, BLOCK, h, dk), (0, 3, 1, 4, 2))
    return hm(q), hm(k), kt, hm(kbeta), hm(vbeta), gc


def gdn_chunk_lax(q, k, v, g, beta, state):
    """:func:`gdn_chunk` in ``jax.numpy``: the blocks in a ``lax.scan``,
    every sequence and head at once."""
    t = q.shape[1]
    qh, kh, kt, kbeta, vbeta, gc = _chunk_operands(q, k, v, g, beta)
    nb = gc.shape[2]
    masks = _block_masks(BLOCK, q.shape[-1])
    mm = functools.partial(jnp.matmul, precision=_HI)
    mm_in = functools.partial(mm, preferred_element_type=_F32)
    one = lambda *a: _block_update(*a, masks, mm, mm_in)
    every = jax.vmap(jax.vmap(one))  # sequences, heads

    def body(s, i):
        rows = lambda x: lax.dynamic_slice_in_dim(x, i * BLOCK, BLOCK, axis=2)
        o, s = every(rows(qh), rows(kh), kt[:, :, i], rows(kbeta),
                     rows(vbeta), gc[:, :, i][:, :, None, :], s)
        return s, o

    state, o = lax.scan(body, state.astype(_F32), jnp.arange(nb))
    o = jnp.transpose(o, (1, 0, 3, 2, 4))  # [B, NB, C, H, d_v]
    o = o.reshape(o.shape[0], nb * BLOCK, *o.shape[3:])[:, :t]
    return o.astype(v.dtype), state


def _heads_per_program(h: int) -> int:
    """Heads one program of the chunk kernel takes: their chains of
    small dependent products interleave in the matrix unit's pipeline."""
    return next(n for n in (3, 2, 1) if h % n == 0)


def _chunk_kernel(q_ref, k_ref, kt_ref, kbeta_ref, vbeta_ref, gc_ref, s_ref,
                  o_ref, s_out_ref):
    """``hb`` heads of one sequence: blocks ``(1, hb, T, d)`` of the head
    major operands, ``kt_ref`` (1, hb, NB, d_k, C), ``gc_ref`` (1, hb, NB,
    C), the state (1, hb, d_k, d_v) in and out. The state lives in the
    output block from the first block of tokens to the last."""
    hb, nb = gc_ref.shape[1], gc_ref.shape[2]
    c = BLOCK
    masks = _block_masks(c, q_ref.shape[-1])
    dot = lambda x, y, **kw: lax.dot_general(
        x, y, (((1,), (0,)), ((), ())), preferred_element_type=_F32, **kw)
    mm = functools.partial(dot, precision=_HI)
    # Two operands of the input's dtype: bfloat16 products are exact in
    # the float32 accumulator in one pass; float32 ones ask for all.
    mm_in = mm if q_ref.dtype == _F32 else dot
    s_out_ref[...] = s_ref[...]

    def block(i, carry):
        rows = pl.ds(pl.multiple_of(i * c, c), c)
        for h in range(hb):
            o, s_new = _block_update(
                q_ref[0, h, rows, :], k_ref[0, h, rows, :], kt_ref[0, h, i],
                kbeta_ref[0, h, rows, :], vbeta_ref[0, h, rows, :],
                gc_ref[0, h, pl.ds(i, 1), :], s_out_ref[0, h],
                masks, mm, mm_in)
            o_ref[0, h, rows, :] = o.astype(o_ref.dtype)
            s_out_ref[0, h] = s_new
        return carry

    lax.fori_loop(0, nb, block, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_call(q, k, v, g, beta, state, *, interpret):
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    qh, kh, kt, kbeta, vbeta, gc = _chunk_operands(q, k, v, g, beta)
    nb = gc.shape[2]
    tp = nb * BLOCK
    hb = _heads_per_program(h)
    rows = lambda d: pl.BlockSpec((1, hb, tp, d), lambda i, j: (i, j, 0, 0))
    seat = pl.BlockSpec((1, hb, dk, dv), lambda i, j: (i, j, 0, 0))
    o, state = pl.pallas_call(
        _chunk_kernel,
        name="gdn_chunk",
        grid=(b, h // hb),
        in_specs=[
            rows(dk), rows(dk),
            pl.BlockSpec((1, hb, nb, dk, BLOCK), lambda i, j: (i, j, 0, 0, 0)),
            rows(dk), rows(dv),
            pl.BlockSpec((1, hb, nb, BLOCK), lambda i, j: (i, j, 0, 0)),
            seat,
        ],
        out_specs=[rows(dv), seat],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tp, dv), v.dtype),
            jax.ShapeDtypeStruct((b, h, dk, dv), _F32),
        ],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(qh, kh, kt, kbeta, vbeta, gc, state.astype(_F32))
    return jnp.swapaxes(o, 1, 2)[:, :t], state


def gdn_chunk(q, k, v, g, beta, state, *, interpret: bool | None = None):
    """A chunk of ``T`` tokens a sequence from ``state``: ``(o [B, T, H,
    d_v], state)``. ``interpret``: None = the kernel on a TPU and
    :func:`gdn_chunk_lax` elsewhere, True = the kernel under the Pallas
    interpreter, False = the kernel compiled."""
    if not _use_kernel(interpret):
        return gdn_chunk_lax(q, k, v, g, beta, state)
    return _chunk_call(q, k, v, g, beta, state, interpret=bool(interpret))


# -- one token a sequence -------------------------------------------------------


def gdn_step_lax(q, k, v, g, beta, state):
    """:func:`gdn_step` in ``jax.numpy``: ``q``, ``k`` [B, H, d_k], ``v``
    [B, H, d_v], ``g``, ``beta`` [B, H]; ``(o [B, H, d_v], state)``."""
    f32 = lambda x: x.astype(_F32)
    s = state.astype(_F32) * jnp.exp(f32(g))[..., None, None]
    r = jnp.sum(s * f32(k)[..., None], axis=-2)
    w = f32(beta)[..., None] * (f32(v) - r)
    s = s + f32(k)[..., None] * w[..., None, :]
    o = jnp.sum(s * f32(q)[..., None], axis=-2)
    return o.astype(v.dtype), s


def _step_kernel(alpha_ref, beta_ref, q_ref, k_ref, v_ref, s_ref, o_ref,
                 s_out_ref):
    """One sequence, its heads one after the other on the vector unit:
    ``alpha_ref`` / ``beta_ref`` [B, H] in SMEM; ``q_ref`` / ``k_ref`` (1, H,
    d_k), ``v_ref`` (1, H, d_v), float32 all; the state (1, H, d_k, d_v)
    in and out."""
    b = pl.program_id(0)
    h_n, dk = q_ref.shape[1], q_ref.shape[2]
    eye = (lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == lax.broadcasted_iota(jnp.int32, (dk, dk), 1))

    def down(row):  # [1, d_k] -> [d_k, 1]
        return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (dk, dk)), 0.0),
                       axis=1, keepdims=True)

    # Unrolled: a row of a block is taken at a static index (a dynamic
    # one must be proven a multiple of the sublane tile), and the heads'
    # short chains interleave.
    for h in range(h_n):
        one = slice(h, h + 1)
        kcol = down(k_ref[0, one, :])
        qcol = down(q_ref[0, one, :])
        s = s_ref[0, h] * alpha_ref[b, h]
        r = jnp.sum(s * kcol, axis=0, keepdims=True)  # [1, d_v]
        w = beta_ref[b, h] * (v_ref[0, one, :] - r)
        s = s + kcol * w
        s_out_ref[0, h] = s
        o_ref[0, one, :] = jnp.sum(s * qcol, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_call(q, k, v, g, beta, state, *, interpret):
    b, h, dk = q.shape
    dv = v.shape[-1]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    row = lambda d: pl.BlockSpec((1, h, d), lambda i: (i, 0, 0))
    seat = pl.BlockSpec((1, h, dk, dv), lambda i: (i, 0, 0, 0))
    f32 = lambda x: x.astype(_F32)
    o, state = pl.pallas_call(
        _step_kernel,
        name="gdn_step",
        grid=(b,),
        in_specs=[smem, smem, row(dk), row(dk), row(dv), seat],
        out_specs=[row(dv), seat],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, dv), _F32),
            jax.ShapeDtypeStruct((b, h, dk, dv), _F32),
        ],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.exp(f32(g)), f32(beta), f32(q), f32(k), f32(v), f32(state))
    return o.astype(v.dtype), state


def gdn_step(q, k, v, g, beta, state, *, interpret: bool | None = None):
    """One token a sequence: ``(o [B, H, d_v], state)``, the state buffer
    updated in place under a donating jit. A sequence with ``g = 0`` and
    ``beta = 0`` keeps its state as it was. ``interpret`` as in
    :func:`gdn_chunk`."""
    if not _use_kernel(interpret):
        return gdn_step_lax(q, k, v, g, beta, state)
    return _step_call(q, k, v, g, beta, state, interpret=bool(interpret))
