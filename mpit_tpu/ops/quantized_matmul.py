"""Quantized int8 weight store + blocked fused-dequant matmul (ISSUE 17).

PR 15 halved the KV sweep and its own bench honesty note recorded the
real verdict: at serving batch sizes the KV cache is a sliver of tick
bytes (``total_bytes_ratio`` 0.9233) — **params dominate the decode HBM
sweep**. This module points the repo's one rounding contract
(:func:`mpit_tpu.ops.ring_collectives.quantize_blocks`, EQuARX-style
``amax/127`` round-half-to-even) at that dominant stream: matmul
weights stored as **int8 + one f32 scale per row**, dequantized per
row-block *inside* the matmul, so what crosses HBM→VMEM is int8 tiles
plus scale blocks — roughly half the f32 wire — and a full dequantized
weight array never exists.

Grain: one scale per leading row over the trailing features
(``quantize_blocks(w, axis=-1)``). For a projection kernel ``[D, F]``
that is one scale per *contraction* row, so a row-block tile carries its
own scales into the blocked ``x @ W``; for the LM head / embedding
``[V, D]`` it is one scale per vocab row, which is exactly the grain
``ops/lm_head.py``'s streamed vocab blocks consume.

Three matmul forms, one math:

- :func:`quantized_matmul` — ``x @ W`` for ``W`` ``[D, F]``, blocked
  over the contraction dim. On TPU a Pallas kernel DMAs int8 tiles +
  scale blocks HBM→VMEM on two channels (double-buffered, the PR 15
  decode-kernel pattern) and dequantizes per tile in VMEM with f32
  accumulation; off-TPU (and under ``interpret=None`` on CPU) the
  blocked lax path below runs the SAME per-tile dequant math — the
  kernel's numerical oracle, interpret-mode parity pinned (the PR 9/15
  discipline).
- :func:`quantized_matmul_t` — ``x @ W.T`` for ``W`` ``[V, D]`` (the
  in-model head einsum, e.g. the speculative draft's hot head pass),
  blocked over the *output* rows. Each output column still sees the
  full-D contraction, so this is bitwise identical to whole-dequant —
  blocking here is purely an intermediate-footprint discipline.
- :func:`quantized_matmul_reference` — whole-tensor dequant then plain
  matmul. The anti-vacuity oracle: it deliberately materializes the
  f32 weight, which is what the ``quantized-weights`` jaxpr contract
  proves the serving paths never do. Reference engines only.

:class:`QuantizedTensor` is the container — the ``QuantizedKV`` mold
(``ops/kv_quant.py``): a registered pytree ``(q int8 [..., rows, cols],
scale f32 [..., rows, 1])`` that rides through jit / shard_map /
device_put whole and drops into a flax param seat (the model's Dense
modules dispatch on it).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.ops.ring_collectives import (
    dequantize_blocks,
    quantize_blocks,
)

__all__ = [
    "QuantizedTensor",
    "dequantize_tensor",
    "quantize_tensor",
    "quantized_matmul",
    "quantized_matmul_lax",
    "quantized_matmul_reference",
    "quantized_matmul_t",
    "weight_wire_bytes",
]

# f32 scale per weight row: the store's fixed overhead (the
# ``kv_quant.SCALE_BYTES`` sibling at the weight grain).
SCALE_BYTES = 4

# Default contraction row-block. 256 f32 rows of the widest GPT-2 small
# kernel (d_ff 3072) is a ~3 MB f32 tile after dequant — comfortably
# VMEM-resident double-buffered — and a multiple of every TPU lane/
# sublane constraint the kernel needs.
DEFAULT_BLOCK_ROWS = 256

_LANE = 128
_SUBLANE_F32 = 8
# Output rows per kernel program. The [rows, F] f32 accumulator, its
# pipelined output block and the [block, F] dequantized tile share the
# 16 MB scoped VMEM; 128 rows fit at the widest GPT-2 small F (3072),
# 512 do not ("ran out of memory in memory space vmem").
_ROW_TILE = 128


def _round_up(x: int, m: int) -> int:
    return x + (-x) % m


def _use_kernel(interpret: bool | None) -> bool:
    if interpret is not None:
        return True
    return jax.devices()[0].platform == "tpu"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """One quantized weight: ``q`` int8 ``[..., rows, cols]`` plus
    ``scale`` f32 ``[..., rows, 1]`` (keepdims — equal rank, so
    shardings/masks written for the payload broadcast to both leaves).
    A pytree: q and scale ride together through jit / device_put /
    shard_map and through a flax param seat."""

    q: Any
    scale: Any

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)

    # Shape/dtype delegate to the int8 payload — geometry readers
    # (config inference, shape validation) see the logical weight; the
    # wire dtype IS int8.
    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def ndim(self):
        return self.q.ndim

    def __getitem__(self, idx):
        """Index q and scale together (the embedding-gather path:
        ``wte[tokens]`` picks int8 rows AND their scales)."""
        return QuantizedTensor(q=self.q[idx], scale=self.scale[idx])


def quantize_tensor(x) -> QuantizedTensor:
    """Quantize a weight ``[..., rows, cols]`` at one-scale-per-row
    grain through the shared
    :func:`~mpit_tpu.ops.ring_collectives.quantize_blocks` contract
    (amax/127, round-half-to-even, all-zero rows get scale 1.0 so they
    round-trip to exact zeros)."""
    q, scale = quantize_blocks(x, axis=-1)
    return QuantizedTensor(q=q, scale=scale)


def dequantize_tensor(t: QuantizedTensor):
    """Whole-tensor f32 view — oracle/reference use ONLY. Serving paths
    dequantize per row-block; the ``quantized-weights`` jaxpr contract
    fails any engine step that materializes this."""
    return dequantize_blocks(t.q, t.scale)


def weight_wire_bytes(shape, dtype) -> float:
    """HBM bytes one weight actually occupies on the wire — the
    :func:`~mpit_tpu.ops.kv_quant.kv_wire_bytes_per_row` sibling at the
    weight grain, shared by the roofline param term, the engine's
    ``decode_achieved_hbm_bytes`` and the bench capacity math. ``dtype``
    "int8" (or the int8 numpy dtype) = int8 payload + one f32 scale per
    leading row; anything else = the dense tensor in that dtype."""
    n = 1
    for s in shape:
        n *= int(s)
    if dtype == "int8" or jnp.dtype(dtype) == jnp.int8:
        rows = n // int(shape[-1]) if shape else 1
        return float(n + rows * SCALE_BYTES)
    return float(n * jnp.dtype(dtype).itemsize)


def _stamp_path(path: str, rows: int, cols: int) -> None:
    """Trace-time stamp (the ``comm.collectives._rec`` idiom) of which
    path a weight shape took, so that the dispatcher's step-aside to
    the lax path is visible in the obs counters."""
    from mpit_tpu.obs import core as _obs

    _obs.counter(
        "quantized_matmul_calls", 1, path=path, shape=f"{rows}x{cols}"
    )


def _pad_blocks(w: QuantizedTensor, block: int):
    """Pad a quantized weight's rows to a multiple of ``block`` and
    reshape to per-block tiles: ``([n, block, cols] int8, [n, block]
    f32)``. Pad rows are zero with scale 1.0 — they dequantize to exact
    zeros and contribute nothing."""
    rows, cols = w.q.shape
    pad = (-rows) % block
    q, scale = w.q, w.scale
    if pad:
        q = jnp.concatenate([q, jnp.zeros((pad, cols), q.dtype)], axis=0)
        scale = jnp.concatenate(
            [scale, jnp.ones((pad, 1), scale.dtype)], axis=0
        )
    n = q.shape[0] // block
    return q.reshape(n, block, cols), scale.reshape(n, block)


def quantized_matmul_lax(x, w: QuantizedTensor, *, block_rows=None):
    """Blocked ``x @ W`` over the contraction dim, pure lax — the
    kernel's numerical oracle and the off-TPU fallback. Per scan tick
    ONE ``[block, F]`` tile is dequantized (f32) and contracted; the
    full f32 weight never exists. Returns f32 ``[..., F]``."""
    d, f = w.q.shape
    block = min(block_rows or DEFAULT_BLOCK_ROWS, _round_up(d, 8))
    qb, sb = _pad_blocks(w, block)
    n = qb.shape[0]
    pad = n * block - d
    x32 = x.astype(jnp.float32)
    if pad:
        x32 = jnp.concatenate(
            [x32, jnp.zeros((*x32.shape[:-1], pad), jnp.float32)], axis=-1
        )
    # [..., n, block] -> [n, ..., block]: the scan streams row-blocks.
    xb = jnp.moveaxis(
        x32.reshape(*x32.shape[:-1], n, block), -2, 0
    )

    def tick(acc, xs):
        q_i, s_i, x_i = xs
        w_i = dequantize_blocks(q_i, s_i[:, None])  # [block, F] f32
        part = lax.dot_general(
            x_i, w_i, (((x_i.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc + part, None

    acc0 = jnp.zeros((*x.shape[:-1], f), jnp.float32)
    acc, _ = lax.scan(tick, acc0, (qb, sb, xb), unroll=min(n, 8))
    return acc


def quantized_matmul_t(x, w: QuantizedTensor, *, block_rows=None):
    """Blocked ``x @ W.T`` for ``W`` ``[V, D]`` — the in-model head
    einsum (``"btd,vd->btv"``) against a quantized head/embedding.
    Blocks over the OUTPUT rows, so each logit column still sees the
    full-D contraction: bitwise identical to whole-dequant, with only a
    ``[block, D]`` f32 tile live. Returns f32 ``[..., V]``."""
    v, d = w.q.shape
    _stamp_path("lax", v, d)
    block = min(block_rows or DEFAULT_BLOCK_ROWS, _round_up(v, 8))
    qb, sb = _pad_blocks(w, block)
    n = qb.shape[0]
    x32 = x.astype(jnp.float32)

    def tick(_, xs):
        q_i, s_i = xs
        w_i = dequantize_blocks(q_i, s_i[:, None])  # [block, D] f32
        part = lax.dot_general(
            x32, w_i, (((x32.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return None, part

    _, parts = lax.scan(tick, None, (qb, sb), unroll=min(n, 8))
    # [n, ..., block] -> [..., n*block] -> drop pad cols.
    out = jnp.moveaxis(parts, 0, -2).reshape(*x.shape[:-1], n * block)
    return out[..., :v]


def quantized_matmul_reference(x, w: QuantizedTensor, *, block_rows=None):
    """Whole-dequant oracle: materializes the full f32 weight on
    purpose. This is what reference engines run (anti-vacuity for the
    jaxpr contract) and what parity tests pin the blocked paths
    against. Returns f32 ``[..., F]``."""
    del block_rows
    return lax.dot_general(
        x.astype(jnp.float32), dequantize_tensor(w),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


# ---------------------------------------------------------------------------
# Kernel. x and the scale column resident in VMEM; the int8 row-block
# tiles stay in HBM (memory_space=ANY) and are DMA'd in by the kernel,
# double-buffered — the PR 15 quantized decode-attention transfer
# pattern, aimed at weights.
# ---------------------------------------------------------------------------


def _qmm_kernel(x_ref, q_hbm, s_ref, o_ref, q_buf, sem, *, n_blocks):
    """One program: ``o = Σ_i x[i] @ (q[i] · s[i])`` with f32 accumulate.

    ``x_ref`` [n, M, block] f32 VMEM (pre-blocked over the contraction
    dim); ``q_hbm`` [n, block, F] int8 in HBM, double-buffered through
    ``q_buf`` [2, block, F] on ``sem`` [2] DMA semaphores; ``s_ref``
    [D, 1] f32 VMEM, the whole scale column. The scales ride as a
    resident column, not a second DMA channel: Mosaic refuses the
    one-row slice a per-block [block] scale DMA takes ("slice shape
    along dimension 0 must be aligned to tiling"), and a [block, 1]
    column is what the row-wise dequant broadcasts from anyway.
    """
    block = q_buf.shape[1]

    def dma(i, slot):
        return pltpu.make_async_copy(q_hbm.at[i], q_buf.at[slot], sem.at[slot])

    dma(0, 0).start()

    m, f = o_ref.shape

    def body(i, acc):
        slot = lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            dma(i + 1, 1 - slot).start()

        dma(i, slot).wait()

        # Fused dequant in VMEM: the f32 weight exists only as this
        # [block, F] tile.
        s_col = s_ref[pl.ds(pl.multiple_of(i * block, block), block), :]
        w_tile = q_buf[slot].astype(jnp.float32) * s_col
        return acc + jnp.dot(
            x_ref[i], w_tile, preferred_element_type=jnp.float32
        )

    acc = lax.fori_loop(
        0, n_blocks, body, jnp.zeros((m, f), jnp.float32)
    )
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def _qmm_call(x_blocked, q_blocked, scale, *, interpret):
    n, m, block = x_blocked.shape
    f = q_blocked.shape[-1]
    tm = min(m, _ROW_TILE)
    kern = functools.partial(_qmm_kernel, n_blocks=n)
    return pl.pallas_call(
        kern,
        name="quantized_matmul",
        grid=(m // tm,),
        in_specs=[
            pl.BlockSpec(
                (n, tm, block), lambda i: (0, i, 0), memory_space=pltpu.VMEM
            ),
            # int8 tiles stay in HBM; the kernel DMAs them per row-block.
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.VMEM),  # scales, whole [D, 1]
        ],
        out_specs=pl.BlockSpec(
            (tm, f), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((m, f), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, block, f), jnp.int8),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=bool(interpret),
    )(x_blocked, q_blocked, scale)


def quantized_matmul(
    x, w: QuantizedTensor, *, block_rows=None, interpret: bool | None = None
):
    """``x @ W`` against an int8-per-row weight ``[D, F]`` — the serving
    matmul. TPU (or ``interpret=True``): the Pallas fused-dequant kernel
    above. Otherwise: :func:`quantized_matmul_lax`, the same per-tile
    math through the shared dequant helpers (the numerical oracle —
    interpret-mode parity is pinned in tests). Returns f32 ``[..., F]``
    (callers cast to their compute dtype; accumulation is f32 on every
    path)."""
    d, f = w.q.shape
    block = min(block_rows or DEFAULT_BLOCK_ROWS, _round_up(d, 8))
    # Kernel tile constraints: int8 min tile is (32, 128) and the
    # pre-blocked x slabs index the lane dim per block — anything
    # unaligned takes the lax path (same math, same rounding contract).
    aligned = (
        block % _LANE == 0 and f % _LANE == 0 and d % block == 0
    )
    kernel = _use_kernel(interpret) and aligned
    _stamp_path("kernel" if kernel else "lax", d, f)
    if not kernel:
        return quantized_matmul_lax(x, w, block_rows=block)
    n = d // block
    m = 1
    for s in x.shape[:-1]:
        m *= int(s)
    m_pad = _round_up(
        max(m, 1), _SUBLANE_F32 if m <= _ROW_TILE else _ROW_TILE
    )
    x2 = x.reshape(m, d).astype(jnp.float32)
    if m_pad != m:
        x2 = jnp.concatenate(
            [x2, jnp.zeros((m_pad - m, d), jnp.float32)], axis=0
        )
    # [M, D] -> [n, M, block]: each kernel tick reads one slab.
    xb = jnp.moveaxis(x2.reshape(m_pad, n, block), 1, 0)
    qb = w.q.reshape(n, block, f)
    out = _qmm_call(xb, qb, w.scale, interpret=interpret is True)
    return out[:m].reshape(*x.shape[:-1], f)
