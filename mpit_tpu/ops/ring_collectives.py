"""Composable Pallas ring collectives — the gradient-sync wire, factored.

ISSUE 9 tentpole: the seed ``ops/ring_allreduce.py`` was a monolithic
allreduce demo; gradient sync needs the two halves *separately* (the
ZeRO-1 choreography runs the optimizer between them — reduce-scatter →
shard update → all-gather, cf. arXiv 2112.01075's portable collective
decompositions), plus a quantized wire variant in the EQuARX spirit
(arXiv 2506.17615: int8 payloads with per-chunk scales at ~2× the
wall-clock of the stock allreduce, negligible quality loss).

This module provides:

- :func:`plan_ring` / :class:`RingPlan` — THE host-side planner: every
  non-divisible-shape question (payload not a multiple of ``p·128``,
  chunk rows not a multiple of the wire dtype's tile sublane) is
  answered here, once, for every ring collective. Non-divisible chunks
  are padded **per chunk** (the pad rides at each chunk's tail), so
  chunk ``i`` always covers elements ``[i·c, (i+1)·c)`` of the
  LANE-padded payload — the SAME contiguous layout as
  ``opt.sharded.shard_of``, which is what makes the ring reduce-scatter
  a drop-in for the ZeRO-1 path (and keeps checkpoints interchangeable
  between sync modes).
- :class:`_Ring` — the kernel-side mailbox discipline (neighbor
  barrier, double-buffered receive slots, capacity tokens, drain)
  factored out of the seed kernel so reduce-scatter, all-gather and
  their quantized variants share ONE synchronization implementation.
- :func:`ring_reduce_scatter` / :func:`ring_all_gather` — the
  composable collectives. ``op="qsum"`` / ``quantized=True`` ship int8
  chunks with per-chunk f32 scales (quantize in-kernel on the send
  side, dequantize-accumulate in f32 on the receive side).

Synchronization discipline (inherited from the seed kernel, pinned by
tests/test_ring_collectives.py in TPU interpret mode):

- neighbor barrier before the first remote write;
- remote writes land ONLY in the double-buffered receive mailbox; send
  staging is strictly device-local;
- ``rdma.wait()`` blocks on local send completion AND remote delivery;
- capacity tokens gate landing-slot reuse (slot ``g%2`` reused at step
  ``g+2`` only after the receiver consumed step ``g``'s payload).

SERIALIZATION CONSTRAINT: every kernel here uses ``collective_id=0``
(one shared barrier semaphore). Two ring kernels with no data
dependency between them could be scheduled concurrently by XLA and
interleave their barrier signals — callers issuing multiple independent
rings in one program (the GradSync bucket loop) must chain them with a
token (``lax.optimization_barrier``), which is also what keeps them
from contending for the same ICI links.

Off-TPU (and un-``interpret``-ed) every collective falls back to the
exact ``lax`` composition: ``psum_scatter``/``all_gather`` for the sum
forms, and a ``ppermute``-spelled ring for the quantized forms that
runs the SAME per-hop quantize→ship→dequantize-accumulate math through
the same :func:`quantize_chunk`/:func:`dequantize_chunk` helpers — so
tier-1 exercises the full planner + dequant logic on CPU, and the
fallback is the kernel's numerical oracle.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.comm.collectives import (
    _all_gather_invariant,
    _pvary,
    _rec,
)

_LANE = 128
# Minimal second-minor tile rows by dtype itemsize (pallas guide:
# f32 (8,128), bf16 (16,128), int8 (32,128)).
_SUBLANE_BY_ITEMSIZE = {4: 8, 2: 16, 1: 32}
# Rows of the f32 block carrying one broadcast per-chunk scale on the
# wire (a whole f32 tile — scalar payloads don't ship well over DMA).
SCALE_ROWS = 8
SCALE_BLOCK_BYTES = SCALE_ROWS * _LANE * 4


def sublane_for(dtype) -> int:
    """Tile rows required for ``dtype`` in the [rows, 128] lane view."""
    return _SUBLANE_BY_ITEMSIZE[jnp.dtype(dtype).itemsize]


@dataclasses.dataclass(frozen=True)
class RingPlan:
    """Geometry of one ring collective over ``p`` devices.

    ``chunk_rows`` is the logical per-device chunk in [rows, 128] lane
    rows (the LANE-padded payload split ``p`` ways); ``padded_rows``
    rounds it up to the wire dtype's tile sublane. The pad lives at
    EACH chunk's tail (``to_wire``), never between payload and chunk
    boundaries — so device ``i``'s chunk is always the contiguous
    elements ``[i·chunk_elems, (i+1)·chunk_elems)`` of the LANE-padded
    flat payload, matching ``opt.sharded.shard_of``'s shard layout.
    """

    p: int
    chunk_rows: int
    padded_rows: int

    @property
    def chunk_elems(self) -> int:
        return self.chunk_rows * _LANE

    @property
    def wire_rows(self) -> int:
        """Total [rows, 128] rows crossing the planner (all chunks)."""
        return self.p * self.padded_rows

    def wire_payload_bytes(self, wire_dtype, *, scales: bool = False) -> float:
        """The ACTUAL bytes-on-the-wire-equivalent payload: what the
        ``(P-1)/P·N`` ring formulas should be fed so modeled wire
        traffic reflects the quantized size, not the logical one.
        ``scales=True`` adds one scale block per chunk (the q8 forms)."""
        per_chunk = self.padded_rows * _LANE * jnp.dtype(wire_dtype).itemsize
        if scales:
            per_chunk += SCALE_BLOCK_BYTES
        return float(self.p * per_chunk)

    # ----- host-side chunking (the one place padding happens) -------------

    def to_wire(self, flat):
        """[n] payload → [p·padded_rows, 128] ring input: zero-pad to
        ``p·chunk_elems``, then pad each chunk's tail to ``padded_rows``."""
        x = _pad_1d(flat, self.p * self.chunk_elems)
        x = x.reshape(self.p, self.chunk_rows, _LANE)
        if self.padded_rows != self.chunk_rows:
            x = jnp.pad(
                x, ((0, 0), (0, self.padded_rows - self.chunk_rows), (0, 0))
            )
        return x.reshape(self.p * self.padded_rows, _LANE)

    def shard_to_wire(self, shard):
        """[chunk_elems or fewer] shard → [padded_rows, 128] ring input."""
        x = _pad_1d(jnp.ravel(shard), self.chunk_elems)
        x = x.reshape(self.chunk_rows, _LANE)
        if self.padded_rows != self.chunk_rows:
            x = jnp.pad(x, ((0, self.padded_rows - self.chunk_rows), (0, 0)))
        return x

    def shard_from_wire(self, shard2d):
        """[padded_rows, 128] ring output → [chunk_elems] shard (strips
        the per-chunk tile pad; the LANE pad of the payload tail is part
        of the contiguous-layout contract and stays)."""
        return shard2d[: self.chunk_rows, :].reshape(-1)

    def full_from_wire(self, full2d):
        """[p·padded_rows, 128] gathered output → [p·chunk_elems] flat
        (strips every chunk's tile pad)."""
        x = full2d.reshape(self.p, self.padded_rows, _LANE)
        return x[:, : self.chunk_rows, :].reshape(-1)

    def gathered_from_wire(self, full2d, shard_elems: int):
        """[p·padded_rows, 128] gathered output → [p·shard_elems] flat:
        strips BOTH pads of every chunk (tile pad and the shard's own
        lane pad) so the concatenation is exactly the p source shards."""
        x = full2d.reshape(self.p, self.padded_rows * _LANE)
        return x[:, :shard_elems].reshape(-1)


def plan_ring(payload_elems: int, p: int, wire_dtype) -> RingPlan:
    """Plan a ring moving ``payload_elems`` total elements over ``p``
    devices with ``wire_dtype`` on the wire. Handles BOTH non-divisible
    questions: payload → LANE-padded ``p`` chunks, chunk rows → wire
    tile multiple. ``p == 1`` is a valid degenerate plan (no wire)."""
    if payload_elems <= 0:
        raise ValueError(f"payload_elems must be positive, got {payload_elems}")
    per = payload_elems + (-payload_elems) % (p * _LANE)
    rows = per // (p * _LANE)
    sub = sublane_for(wire_dtype)
    padded = max(rows + (-rows) % sub, sub)
    return RingPlan(p=p, chunk_rows=rows, padded_rows=padded)


def plan_shards(shard_elems: int, p: int, wire_dtype) -> RingPlan:
    """Plan an all-gather ring where every device contributes a
    ``shard_elems`` shard (chunk size is given, not derived)."""
    if shard_elems <= 0:
        raise ValueError(f"shard_elems must be positive, got {shard_elems}")
    rows = -(-shard_elems // _LANE)
    sub = sublane_for(wire_dtype)
    padded = max(rows + (-rows) % sub, sub)
    return RingPlan(p=p, chunk_rows=rows, padded_rows=padded)


def _pad_1d(x, total):
    pad = total - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x


# ---------------------------------------------------------------------------
# Quantization (shared by the kernels AND the lax fallback — one math).
# ---------------------------------------------------------------------------


def _q8_scale(amax):
    """The ONE scale rule: ``amax/127`` (1.0 for an all-zero block so
    dequant stays exact). Shared by the scalar chunk form (ring wire)
    and the blocked form (KV cache) — one rounding contract repo-wide."""
    return jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)


def _q8_round(x, scale):
    """The ONE rounding rule: round-half-to-even (deterministic — the
    loss-curve / greedy-stability pins are the reproducibility
    contract, so no stochastic rounding), clip to ±127."""
    return jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)


def quantize_chunk(x):
    """Symmetric per-chunk int8: one scalar ``scale = amax/127`` over
    the whole chunk (:func:`_q8_scale`), round-half-to-even clip to
    ±127 (:func:`_q8_round`).

    Returns ``(q int8, scale f32 scalar)``; round-trip error is bounded
    by ``scale/2`` per element (pinned in tests)."""
    x = x.astype(jnp.float32)
    scale = _q8_scale(jnp.max(jnp.abs(x)))
    return _q8_round(x, scale), scale


def dequantize_chunk(q, scale):
    """Inverse of :func:`quantize_chunk` (f32 result)."""
    return q.astype(jnp.float32) * scale


def quantize_blocks(x, axis=-1):
    """Blocked form of :func:`quantize_chunk`: one scale per slice
    along ``axis`` (every other axis indexes an independent block) —
    the quantized KV cache's per-(row, head) grain (ISSUE 15). Same
    scale rule, same round-half-to-even, same ±127 clip, via the same
    shared helpers; only the amax reduction axis differs.

    Returns ``(q int8 like x, scale f32 with axis kept at size 1)`` —
    keepdims so the scale broadcasts back over its block for dequant
    and rides pytrees next to ``q`` at equal rank."""
    x = x.astype(jnp.float32)
    scale = _q8_scale(jnp.max(jnp.abs(x), axis=axis, keepdims=True))
    return _q8_round(x, scale), scale


def dequantize_blocks(q, scale):
    """Inverse of :func:`quantize_blocks` (f32 result; ``scale``
    broadcasts — keepdims form or any compatible shape)."""
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# Kernel-side ring discipline (one implementation for every collective)
# ---------------------------------------------------------------------------


class _Ring:
    """The mailbox protocol of the seed ring kernel, reusable.

    ``channels`` is a list of ``(send_buf, recv_buf, send_sem,
    recv_sem)`` tuples shipped together each step (the q8 forms ship a
    data channel and a scale channel); ONE capacity-token array gates
    the paired landing slots, since they are produced and consumed
    together. See the module docstring for the discipline; the drain
    generalizes the seed kernel's to any step count (``p-1`` steps for
    a single phase, ``2(p-1)`` for a fused allreduce).
    """

    def __init__(self, axis, num_devices, channels, cap_sem, *, interpret):
        self.axis = axis
        self.p = num_devices
        self.channels = channels
        self.cap_sem = cap_sem
        self.interpret = interpret
        i = lax.axis_index(axis)
        self.right = lax.rem(i + 1, num_devices)
        self.left = lax.rem(i - 1 + num_devices, num_devices)

    def barrier(self):
        """Both neighbors must have entered the kernel (mailboxes live)
        before any remote write."""
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, inc=1, device_id={self.axis: self.left})
        pltpu.semaphore_signal(barrier, inc=1, device_id={self.axis: self.right})
        pltpu.semaphore_wait(barrier, 2)

    def exchange(self, g, outgoing):
        """Ship ``outgoing`` (one value per channel; ``None`` = the
        caller already staged this channel's send buffer) one hop
        right; return the values arrived from the left. The caller MUST
        call :meth:`consumed` after it is done reading the returned
        values (including any restaging of them) — that signal is what
        lets the left neighbor reuse the landing slot at step ``g+2``."""
        if g >= 2:
            pltpu.semaphore_wait(self.cap_sem.at[g % 2], 1)
        rdmas = []
        for (sbuf, rbuf, ssem, rsem), val in zip(self.channels, outgoing):
            if val is not None:
                sbuf[...] = val
            rdmas.append(
                pltpu.make_async_remote_copy(
                    src_ref=sbuf,
                    dst_ref=rbuf.at[g % 2],
                    send_sem=ssem,
                    recv_sem=rsem.at[g % 2],
                    device_id={self.axis: self.right},
                )
            )
        for r in rdmas:
            r.start()
        # Blocks on BOTH: my outgoing DMAs finished reading the send
        # buffers (safe to restage) AND the left neighbor's payload
        # arrived in slot g%2.
        for r in rdmas:
            r.wait()
        incoming = []
        for _, rbuf, _, _ in self.channels:
            v = rbuf[g % 2]
            if self.interpret:
                # interpret-mode VMA checker only; Mosaic rejects the
                # primitive (seed kernel's pattern, AOT-verified).
                v = _pvary(v, (self.axis,))
            incoming.append(v)
        return tuple(incoming)

    def consumed(self, g):
        """Landing slot ``g%2`` fully read — left may reuse it."""
        pltpu.semaphore_signal(
            self.cap_sem.at[g % 2], inc=1, device_id={self.axis: self.left}
        )

    def drain(self, total):
        """Absorb the trailing read-done tokens (one per slot used in
        the final two steps) so every semaphore returns to zero."""
        for k in range(min(total, 2)):
            pltpu.semaphore_wait(self.cap_sem.at[(total - 1 - k) % 2], 1)


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------


def _rs_kernel(
    x_ref, o_ref, send_buf, recv_buf, send_sem, recv_sem, cap_sem,
    *, axis, num_devices, interpret,
):
    """Reduce-scatter: in [p·rows, 128], out [rows, 128] = this device's
    fully-reduced chunk ``i`` (owner-aligned with the contiguous shard
    layout). Only ONE chunk-sized accumulator is needed — the output
    ref itself: the chunk a device sends at step ``s ≥ 1`` is exactly
    the partial it accumulated at step ``s-1``."""
    p = num_devices
    rows = o_ref.shape[0]
    i = lax.axis_index(axis)
    if p == 1:
        o_ref[...] = x_ref[...]
        return
    ring = _Ring(
        axis, p, [(send_buf, recv_buf, send_sem, recv_sem)], cap_sem,
        interpret=interpret,
    )
    ring.barrier()

    def chunk(c):
        return x_ref[pl.ds(c * rows, rows), :]

    # Device i sends chunk (i-1-s) at step s and folds arriving chunk
    # (i-2-s) into its accumulator; after p-1 steps the accumulator
    # holds chunk (i-p) ≡ i, fully reduced.
    for s in range(p - 1):
        send_c = lax.rem(i - 1 - s + 2 * p, p)
        recv_c = lax.rem(i - 2 - s + 2 * p, p)
        outgoing = chunk(send_c) if s == 0 else o_ref[...]
        (incoming,) = ring.exchange(s, (outgoing,))
        o_ref[...] = incoming + chunk(recv_c)
        ring.consumed(s)
    ring.drain(p - 1)


def _rs_q8_kernel(
    x_ref, o_ref,
    send_q, recv_q, qsend_sem, qrecv_sem,
    send_s, recv_s, ssend_sem, srecv_sem,
    cap_sem,
    *, axis, num_devices, interpret,
):
    """Quantized reduce-scatter: each hop quantizes the outgoing f32
    partial to int8 + one per-chunk scale (computed in-kernel), ships
    both, and the receiver dequant-accumulates in f32. Progressive
    per-hop quantization — lossy by design; the loss-curve pin is the
    contract (EQuARX-style), greedy bit-match is NOT claimed."""
    p = num_devices
    rows = o_ref.shape[0]
    i = lax.axis_index(axis)
    if p == 1:
        o_ref[...] = x_ref[...].astype(jnp.float32)
        return
    ring = _Ring(
        axis, p,
        [(send_q, recv_q, qsend_sem, qrecv_sem),
         (send_s, recv_s, ssend_sem, srecv_sem)],
        cap_sem, interpret=interpret,
    )
    ring.barrier()

    def chunk_f32(c):
        return x_ref[pl.ds(c * rows, rows), :].astype(jnp.float32)

    for s in range(p - 1):
        send_c = lax.rem(i - 1 - s + 2 * p, p)
        recv_c = lax.rem(i - 2 - s + 2 * p, p)
        outgoing = chunk_f32(send_c) if s == 0 else o_ref[...]
        q, scale = quantize_chunk(outgoing)
        inc_q, inc_s = ring.exchange(
            s, (q, jnp.full((SCALE_ROWS, _LANE), scale, jnp.float32))
        )
        o_ref[...] = dequantize_chunk(inc_q, inc_s[0, 0]) + chunk_f32(recv_c)
        ring.consumed(s)
    ring.drain(p - 1)


def _ag_kernel(
    x_ref, o_ref, send_buf, recv_buf, send_sem, recv_sem, cap_sem,
    *, axis, num_devices, interpret,
):
    """All-gather: in [rows, 128] shard (device i owns chunk i), out
    [p·rows, 128]. Chunks circulate; each step forwards the chunk that
    arrived the previous step (staged from the local output, which is
    race-free — remote writes land only in the mailbox)."""
    p = num_devices
    rows = x_ref.shape[0]
    i = lax.axis_index(axis)
    o_ref[pl.ds(i * rows, rows), :] = x_ref[...]
    if p == 1:
        return
    ring = _Ring(
        axis, p, [(send_buf, recv_buf, send_sem, recv_sem)], cap_sem,
        interpret=interpret,
    )
    ring.barrier()
    for s in range(p - 1):
        send_c = lax.rem(i - s + 2 * p, p)
        recv_c = lax.rem(i - 1 - s + 2 * p, p)
        (incoming,) = ring.exchange(s, (o_ref[pl.ds(send_c * rows, rows), :],))
        o_ref[pl.ds(recv_c * rows, rows), :] = incoming
        ring.consumed(s)
    ring.drain(p - 1)


def _ag_q8_kernel(
    x_ref, o_ref,
    send_q, recv_q, qsend_sem, qrecv_sem,
    send_s, recv_s, ssend_sem, srecv_sem,
    cap_sem,
    *, axis, num_devices, interpret,
):
    """Quantized all-gather: the own shard is quantized ONCE and the
    (int8, scale) pair circulates verbatim — one quantization error per
    chunk total, no per-hop requantization. REPLICA CONSISTENCY: the
    own chunk is written DEQUANTIZED too, so every device ends with the
    bit-identical gathered value (an all-gather whose output differed
    per device would silently desynchronize replicated params).

    Forwarding restages the arriving payload into the send buffers at
    consume time (before the capacity token is released) — staging from
    the landing slot a step later would race the left neighbor's slot
    reuse."""
    p = num_devices
    rows = x_ref.shape[0]
    i = lax.axis_index(axis)
    q_own, scale_own = quantize_chunk(x_ref[...].astype(jnp.float32))
    o_ref[pl.ds(i * rows, rows), :] = dequantize_chunk(q_own, scale_own).astype(
        o_ref.dtype
    )
    if p == 1:
        return
    ring = _Ring(
        axis, p,
        [(send_q, recv_q, qsend_sem, qrecv_sem),
         (send_s, recv_s, ssend_sem, srecv_sem)],
        cap_sem, interpret=interpret,
    )
    ring.barrier()
    for s in range(p - 1):
        recv_c = lax.rem(i - 1 - s + 2 * p, p)
        if s == 0:
            outgoing = (q_own, jnp.full((SCALE_ROWS, _LANE), scale_own, jnp.float32))
        else:
            outgoing = (None, None)  # restaged at the previous consume
        inc_q, inc_s = ring.exchange(s, outgoing)
        o_ref[pl.ds(recv_c * rows, rows), :] = dequantize_chunk(
            inc_q, inc_s[0, 0]
        ).astype(o_ref.dtype)
        if s < p - 2:
            # Forward verbatim next step: copy into the send buffers
            # BEFORE releasing the landing slot (exchange already
            # waited out our previous send, so they are free).
            send_q[...] = inc_q
            send_s[...] = inc_s
        ring.consumed(s)
    ring.drain(p - 1)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _interpret_param(interpret: bool):
    # TPU interpret mode (not the generic pallas interpreter): simulates
    # remote DMAs + semaphores across shard_map "devices" on CPU.
    return pltpu.InterpretParams() if interpret else False


def _sum_scratch(rows, dtype):
    return [
        pltpu.VMEM((rows, _LANE), dtype),  # send staging (local-only)
        pltpu.VMEM((2, rows, _LANE), dtype),  # receive mailbox
        pltpu.SemaphoreType.DMA(()),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.REGULAR((2,)),  # per-slot capacity tokens
    ]


def _q8_scratch(rows):
    return [
        pltpu.VMEM((rows, _LANE), jnp.int8),  # int8 send staging
        pltpu.VMEM((2, rows, _LANE), jnp.int8),  # int8 receive mailbox
        pltpu.SemaphoreType.DMA(()),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM((SCALE_ROWS, _LANE), jnp.float32),  # scale send staging
        pltpu.VMEM((2, SCALE_ROWS, _LANE), jnp.float32),  # scale mailbox
        pltpu.SemaphoreType.DMA(()),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.REGULAR((2,)),  # shared capacity tokens
    ]


def _call_ring(kernel, x2d, out_shape, scratch, *, axis, p, interpret):
    kern = functools.partial(
        kernel, axis=axis, num_devices=p, interpret=interpret
    )
    return pl.pallas_call(
        kern,
        name="ring_step",
        out_shape=out_shape,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=0
        ),
        interpret=_interpret_param(interpret),
    )(x2d)


def _rs_2d(x2d, plan: RingPlan, *, axis, quantized, interpret):
    rows = plan.padded_rows
    if quantized:
        out = jax.ShapeDtypeStruct(
            (rows, _LANE), jnp.float32, vma=frozenset({axis})
        )
        return _call_ring(
            _rs_q8_kernel, x2d, out, _q8_scratch(rows),
            axis=axis, p=plan.p, interpret=interpret,
        )
    out = jax.ShapeDtypeStruct((rows, _LANE), x2d.dtype, vma=frozenset({axis}))
    return _call_ring(
        _rs_kernel, x2d, out, _sum_scratch(rows, x2d.dtype),
        axis=axis, p=plan.p, interpret=interpret,
    )


def _ag_2d(x2d, plan: RingPlan, *, axis, quantized, interpret):
    rows = plan.padded_rows
    # The gathered value is identical on every device by construction
    # (the q8 form dequantizes the own chunk too — see _ag_q8_kernel),
    # so the output is declared REPLICATED — the same claim
    # all_gather_invariant makes for its output, and what lets the
    # gathered updates leave shard_map with a replicated out_spec.
    out = jax.ShapeDtypeStruct(
        (plan.p * rows, _LANE), x2d.dtype, vma=frozenset()
    )
    if quantized:
        return _call_ring(
            _ag_q8_kernel, x2d, out, _q8_scratch(rows),
            axis=axis, p=plan.p, interpret=interpret,
        )
    return _call_ring(
        _ag_kernel, x2d, out, _sum_scratch(rows, x2d.dtype),
        axis=axis, p=plan.p, interpret=interpret,
    )


# ---------------------------------------------------------------------------
# lax fallbacks (exact composition; q8 = same math spelled with ppermute)
# ---------------------------------------------------------------------------


def _shift_right(x, axis):
    p = lax.axis_size(axis)
    return lax.ppermute(x, axis, perm=[(i, (i + 1) % p) for i in range(p)])


def _rs_fallback(x2d, plan: RingPlan, *, axis, quantized):
    if not quantized:
        return lax.psum_scatter(x2d, axis, scatter_dimension=0, tiled=True)
    # The SAME ring algorithm as _rs_q8_kernel, one ppermute per hop,
    # through the same quantize/dequantize helpers — per-element
    # identical math, so this is both the production CPU path and the
    # kernel's numerical oracle.
    p, rows = plan.p, plan.padded_rows
    i = lax.axis_index(axis)
    chunks = x2d.reshape(p, rows, _LANE)

    def chunk_f32(c):
        return lax.dynamic_index_in_dim(
            chunks, c, axis=0, keepdims=False
        ).astype(jnp.float32)

    acc = None
    for s in range(p - 1):
        send_c = lax.rem(i - 1 - s + 2 * p, p)
        recv_c = lax.rem(i - 2 - s + 2 * p, p)
        outgoing = chunk_f32(send_c) if s == 0 else acc
        q, scale = quantize_chunk(outgoing)
        inc_q = _shift_right(q, axis)
        inc_s = _shift_right(scale, axis)
        acc = dequantize_chunk(inc_q, inc_s) + chunk_f32(recv_c)
    return acc


def _ag_fallback(x2d, plan: RingPlan, *, axis, quantized):
    if not quantized:
        # Invariant gather: identical everywhere, typed replicated —
        # matching the kernel path's replicated out declaration. The
        # raw primitive, NOT C.allgather: the caller already charged
        # this collective's wire bytes at the ring model.
        return _all_gather_invariant(x2d, axis, axis=0, tiled=True)
    # Quantize once, gather (q, scale) verbatim, dequantize every chunk
    # (the own one included — replica consistency, see kernel). An
    # all-gather has no per-hop math, so the invariant gather of the
    # int8 payload IS the ring's result, bit for bit — and it is typed
    # replicated, which a ppermute-circulated value cannot be.
    p, rows = plan.p, plan.padded_rows
    q_own, scale_own = quantize_chunk(x2d.astype(jnp.float32))
    q_all = _all_gather_invariant(q_own, axis, axis=0, tiled=True)
    s_all = _all_gather_invariant(scale_own[None], axis, axis=0, tiled=True)
    out = dequantize_chunk(
        q_all.reshape(p, rows, _LANE), s_all[:, None, None]
    ).astype(x2d.dtype)
    return out.reshape(p * rows, _LANE)


# ---------------------------------------------------------------------------
# Public collectives
# ---------------------------------------------------------------------------


def _use_kernel(interpret: bool) -> bool:
    return interpret or jax.devices()[0].platform == "tpu"


def executed_mode(op: str, interpret: bool = False) -> str:
    """The mode label a ring collective will stamp on this host —
    ``ring`` when the Pallas kernel runs (TPU or interpret mode), else
    the fallback's name. Bench/traces read this instead of guessing
    (the seed kernel fell back SILENTLY — ISSUE 9 satellite)."""
    if _use_kernel(interpret):
        return "ring"
    return "psum_fallback" if op == "sum" else "lax_emulated"


def _record(name, plan, axis, *, model, wire_dtype, scales, mode):
    _rec(
        name,
        None,
        axis,
        model=model,
        payload_bytes=plan.wire_payload_bytes(wire_dtype, scales=scales),
        mode=mode,
    )


def ring_reduce_scatter(x, axis: str, *, op: str = "sum", interpret: bool = False):
    """Ring reduce-scatter over mesh ``axis`` — call inside shard_map.

    Layout contract (shared with ``opt.sharded.shard_of``): ``x`` is
    raveled and zero-padded to a ``p·128`` multiple; device ``i``
    receives the reduced contiguous elements ``[i·c, (i+1)·c)``
    (``c = padded/p``) as a 1-D array. ``op="sum"`` reduces in ``x``'s
    dtype (the ``lax.psum_scatter`` contract); ``op="qsum"`` ships int8
    chunks with per-chunk scales and dequant-accumulates in f32 — the
    result dtype is f32 and the reduction is lossy by design.

    Off-TPU without ``interpret=True`` the exact ``lax`` composition
    runs instead (same planner, same layout, same quantization math) —
    stamped ``psum_fallback``/``lax_emulated`` in the obs trace.
    """
    if op not in ("sum", "qsum"):
        raise ValueError(f"op must be 'sum' or 'qsum', got {op!r}")
    quantized = op == "qsum"
    p = lax.axis_size(axis)
    flat = jnp.ravel(x)
    out_dtype = jnp.float32 if quantized else x.dtype
    if p == 1:
        # Degenerate ring: the local value IS the reduction (and the
        # whole payload is this device's shard). No wire → no
        # quantization either; entering the kernel would deadlock on
        # the drain (seed kernel's documented p=1 contract).
        return flat.astype(out_dtype)
    wire_dtype = jnp.int8 if quantized else x.dtype
    plan = plan_ring(flat.shape[0], p, wire_dtype)
    mode = executed_mode(op, interpret)
    _record(
        "ring_reduce_scatter", plan, axis,
        model="reduce_scatter", wire_dtype=wire_dtype, scales=quantized,
        mode=mode,
    )
    x2d = plan.to_wire(flat)
    if mode == "ring":
        out2d = _rs_2d(x2d, plan, axis=axis, quantized=quantized,
                       interpret=interpret)
    else:
        out2d = _rs_fallback(x2d, plan, axis=axis, quantized=quantized)
    return plan.shard_from_wire(out2d).astype(out_dtype)


def ring_all_gather(
    x, axis: str, *, quantized: bool = False, interpret: bool = False,
    out_size: int | None = None,
):
    """Ring all-gather over mesh ``axis`` — call inside shard_map.

    Every device contributes an identically-shaped shard; the result is
    the 1-D concatenation in ring order (device ``i``'s shard at
    ``[i·c, (i+1)·c)``), IDENTICAL on every device and typed replicated
    (the ``all_gather_invariant`` contract). ``quantized=True`` ships
    each shard as int8 + one per-chunk scale, quantized once at the
    source and dequantized by every receiver — including the source
    itself, so replicas cannot desynchronize. ``out_size`` trims the
    trailing pad of the final flat result.
    """
    p = lax.axis_size(axis)
    flat = jnp.ravel(x)
    if p == 1:
        # Degenerate ring: nothing crosses a wire, nothing is
        # quantized (mirrors ring_reduce_scatter's p=1 contract).
        return flat if out_size is None else flat[:out_size]
    wire_dtype = jnp.int8 if quantized else x.dtype
    plan = plan_shards(flat.shape[0], p, wire_dtype)
    mode = executed_mode("qcat" if quantized else "sum", interpret)
    _record(
        "ring_all_gather", plan, axis,
        model="all_gather", wire_dtype=wire_dtype, scales=quantized,
        mode=mode,
    )
    x2d = plan.shard_to_wire(flat)
    if mode == "ring":
        out2d = _ag_2d(x2d, plan, axis=axis, quantized=quantized,
                       interpret=interpret)
    else:
        out2d = _ag_fallback(x2d, plan, axis=axis, quantized=quantized)
    out = plan.gathered_from_wire(out2d, flat.shape[0])
    return out if out_size is None else out[:out_size]
