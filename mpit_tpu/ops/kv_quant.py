"""Quantized int8 KV-cache storage: the wire format + its one math.

ISSUE 15 tentpole. PR 8's length-aware roofline recorded the decode
verdict — ``bound_modeled: hbm``, every tick dominated by sweeping the
visited K/V tiles out of HBM — and capacity is bounded by bytes per
cached token. This module is the storage half of the fix: K/V rows are
stored as **int8 + per-(row, head) f32 scales** and dequantized per
visited tile inside the decode kernel, so what crosses HBM→VMEM is the
int8 tiles plus their scale blocks (~2× fewer bytes than bf16, ~4× vs
f32), and the same HBM pool holds ~2× the tokens.

The quantization math is NOT new: it is the EQuARX-style (arXiv
2506.17615) ``amax/127`` round-half-to-even recipe the ring collectives
shipped in PR 9, reached through the SAME
:func:`mpit_tpu.ops.ring_collectives.quantize_blocks` /
:func:`~mpit_tpu.ops.ring_collectives.dequantize_blocks` helpers — one
rounding contract repo-wide, so the collectives' determinism and
round-trip-bound pins govern the cache too.

Grain: one scale per **(token row, head)** — for a paged pool the scale
block of page ``p``, head ``h`` is the ``[page_size]`` tile
``scale[p, :, h]``, which is what rides next to the page through
admission, copy-on-write, prefix sharing and preemption (the allocator
never learns about scales: they live in the same pytree as the int8
buffer and every page copy / table indirection applies to both).
Per-row grain is what makes append-only writes exact: a row is
quantized once, when written, and never rescaled by a later append.

:class:`QuantizedKV` is the container: a registered pytree ``(q int8,
scale f32)`` that drops into every ``PagedKVCache.k`` seat. Head-split
rows (what :func:`quantize_kv` takes, what a fleet shipment carries)
keep a trailing size-1 axis on the scale (``[..., H, 1]`` vs the rows'
``[..., H, Dh]``) so both leaves share rank and one ``tree.map`` serves
either. A page pool holds rows PACKED, as the decode kernel reads them
(``q`` ``[pages, page_size, H*Dh]``, ``scale`` ``[pages, page_size,
H]``, equal rank again): :func:`pack_heads` / :func:`unpack_heads` move
between the two forms.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from mpit_tpu.ops.ring_collectives import (
    dequantize_blocks,
    quantize_blocks,
)

__all__ = [
    "QuantizedKV",
    "quantize_kv",
    "dequantize_kv",
    "pack_heads",
    "unpack_heads",
    "kv_wire_bytes_per_row",
]

# f32 scale per (row, head): the storage grain's fixed overhead.
SCALE_BYTES = 4


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedKV:
    """One quantized K (or V) buffer: ``q`` int8 ``[..., H, Dh]`` plus
    ``scale`` f32 ``[..., H, 1]`` (keepdims — equal rank, so masks and
    shardings written for the buffer broadcast/apply to both leaves);
    in a page pool the packed pair ``[..., H*Dh]`` / ``[..., H]``.
    A pytree: it passes through jit/shard_map/device_put whole, and
    ``jax.tree.map`` over a cache touches q and scale together."""

    q: Any
    scale: Any

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)

    # Shape/dtype delegate to the int8 payload — callers sizing slots/
    # pages/rows read the buffer geometry; the wire dtype IS int8.
    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    def __getitem__(self, idx):
        """Index q and scale together (the per-layer ``cache.k[i]``
        view the blocks consume)."""
        return QuantizedKV(q=self.q[idx], scale=self.scale[idx])


def quantize_kv(x):
    """Quantize K/V rows ``[..., H, Dh]`` at the per-(row, head) grain:
    one scale per trailing ``Dh`` slice, via the shared
    :func:`~mpit_tpu.ops.ring_collectives.quantize_blocks` contract."""
    q, scale = quantize_blocks(x, axis=-1)
    return QuantizedKV(q=q, scale=scale)


def dequantize_kv(kv: QuantizedKV):
    """f32 view of a quantized buffer (the reference/oracle path; the
    flash-decode kernel never calls this on a whole buffer — it
    dequantizes per visited tile in VMEM)."""
    return dequantize_blocks(kv.q, kv.scale)


def pack_heads(rows):
    """Head-split rows ``[..., H, Dh]`` (scale leaves ``[..., H, 1]``)
    to the packed row form ``[..., H*Dh]`` (``[..., H]``) a page pool
    stores. Plain arrays and :class:`QuantizedKV` alike."""
    return jax.tree.map(lambda a: a.reshape(*a.shape[:-2], -1), rows)


def unpack_heads(rows, num_heads: int):
    """Inverse of :func:`pack_heads`: ``[..., H*Dh]`` back to ``[..., H,
    Dh]``, and a packed scale plane ``[..., H]`` to ``[..., H, 1]``."""
    return jax.tree.map(
        lambda a: a.reshape(*a.shape[:-1], num_heads, -1), rows
    )


def kv_wire_bytes_per_row(num_heads: int, head_dim: int, dtype) -> float:
    """HBM bytes ONE cached K (or V) row actually occupies on the wire
    — the unit of the length-aware decode-bytes model and the capacity
    math (ISSUE 15 roofline-honesty satellite). ``dtype`` "int8" (or
    the int8 numpy dtype) = int8 payload + one f32 scale per head;
    anything else = the dense row in that dtype."""
    if dtype == "int8" or jnp.dtype(dtype) == jnp.int8:
        return float(num_heads * (head_dim + SCALE_BYTES))
    return float(num_heads * head_dim * jnp.dtype(dtype).itemsize)
