"""GradSync — bucketed gradient synchronization over the ring tier.

ISSUE 9 tentpole (c): the one hot path XLA still owned was the wire —
training kernels are hand-built Pallas but gradient sync was stock
``lax.psum``/``psum_scatter``. :class:`GradSync` makes the sync strategy
a selectable policy of the training step
(``grad_sync="psum"(default) | "ring" | "ring_q8"``):

- ``psum``     — the stock XLA collectives, byte-for-byte the seed
  behavior (this mode exists so the other two have a pinned oracle).
- ``ring``     — the in-kernel Pallas ring (``ops/ring_collectives``),
  issued PER BUCKET: the flat gradient is split into fixed-size buckets
  and each bucket's reduce-scatter is an independent collective, so
  XLA's latency-hiding scheduler can start syncing late-layer gradients
  while the tail of backward still computes early-layer ones (the
  bucket-granularity overlap of the classic DDP design — within one
  jitted step, overlap is the scheduler's to exploit; the buckets give
  it the freedom a single monolithic collective denies). The same
  elementwise sums in ring order: bitwise ``psum`` on the CPU fallback
  (pinned), reduction-order noise on chips (7e-5 on the loss over six
  GPT-2 small steps on four v5e chips, ``chip_smoke.py --chips 4``).
- ``ring_q8``  — the ring with the EQuARX-spirit int8 wire (per-chunk
  scales, dequant-accumulate in f32): ~¼ the wire bytes, lossy by
  design — convergence neutrality is the contract (MNIST/AlexNet
  loss-curve pin vs f32 sync), bit-match is NOT claimed.

LAYOUT INVARIANT (the reason checkpoints stay interchangeable between
modes): every mode produces the SAME contiguous per-device shard —
``opt.sharded.shard_of``'s ``[i·S, (i+1)·S)`` of the ``n·LANE``-padded
flat vector. Buckets are row-ranges OF THE SHARD (boundaries at 32-row
multiples, the int8 tile, so every bucket is wire-aligned for any
dtype; the tail bucket's remainder is tile-padded per chunk inside the
shared ring planner). A bucketed reduce-scatter therefore scatters
bucket ``b`` of every device's chunk to the owner of that chunk, and
the concatenation over buckets IS the contiguous shard — no permuted
layouts, no optimizer-state migration between sync modes.

Buckets are chained with ``lax.optimization_barrier`` tokens: ring
kernels share one ``collective_id`` (barrier semaphore), so two rings
must never be scheduled concurrently (``ops/ring_collectives``
docstring) — and serializing the collectives among themselves is also
what a real wire wants (they contend for the same ICI links; the
overlap win is collectives-under-compute, which the token chain does
not constrain).

Composition (ISSUE 9: "composing with the existing stx sharded-update
path rather than duplicating it"): ``opt.sharded.sharded(tx, axis,
comm=gs)`` delegates its three choreography points (grad
reduce-scatter, param shard select, update all-gather) to this object;
``make_train_step(grad_sync=...)`` builds it and threads it through
both the ZeRO-1 and the plain-DP path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from mpit_tpu.comm import collectives as C

# ONE pad rule (n·LANE) across the ring stack: the checkpoint-
# interchangeability contract depends on every mode agreeing on it, so
# the helper is imported from the layout authority, not re-spelled.
from mpit_tpu.opt.sharded import _pad_to, flat_ravel, shard_of

_LANE = 128
# Bucket boundaries are multiples of the int8 tile (32 rows) so every
# non-tail bucket is wire-aligned for f32, bf16 AND int8 payloads.
_BUCKET_ALIGN_ROWS = 32

GRAD_SYNC_MODES = ("psum", "ring", "ring_q8")


class GradSync:
    """Bucketed gradient-sync policy (see module docstring).

    Built once per training step (cheap, stateless); every method is
    traceable and must be called *inside* ``shard_map`` over ``axis``.

    Args:
      axis: mesh axis the gradients sync over.
      mode: ``"psum" | "ring" | "ring_q8"``.
      bucket_mb: target bucket size in MB of f32 elements (the flat
        vector is split into ``ceil(size / bucket)`` ring collectives;
        one bucket ≡ the monolithic collective). Ignored for ``psum``.
      interpret: run the ring kernels in TPU interpret mode (CPU tests);
        ``None``/``False`` = compiled path, which falls back to the
        exact ``lax`` composition off-TPU (mode-stamped in obs).
    """

    def __init__(
        self,
        axis: str,
        mode: str = "psum",
        *,
        bucket_mb: float = 4.0,
        interpret: bool | None = None,
    ):
        if mode not in GRAD_SYNC_MODES:
            raise ValueError(
                f"grad_sync must be one of {GRAD_SYNC_MODES}, got {mode!r}"
            )
        if bucket_mb <= 0:
            raise ValueError(f"bucket_mb must be positive, got {bucket_mb}")
        self.axis = axis
        self.mode = mode
        self.bucket_mb = float(bucket_mb)
        self.interpret = bool(interpret)

    # ----- host-side labels / models --------------------------------------

    @property
    def quantized(self) -> bool:
        return self.mode == "ring_q8"

    @property
    def exec_mode(self) -> str:
        """What actually executes ON THIS HOST — the span label the
        training loop stamps (the way serve stamps ``attention=``), so
        a fallback run can never be misattributed (ISSUE 9 satellite).
        """
        if self.mode == "psum":
            return "psum"
        on_ring = self.interpret or jax.devices()[0].platform == "tpu"
        if self.mode == "ring":
            return "ring" if on_ring else "psum_fallback"
        return "ring_q8" if on_ring else "ring_q8_emulated"

    def wire_scale(self, dtype=jnp.float32) -> float:
        """Bytes-on-wire per logical payload byte — the factor the
        modeled comm accounting (``utils.CommModel(wire_scale=...)``,
        roofline ICI attribution, P2P matrix) must apply so quantized
        sync is modeled at its ACTUAL size (int8: ¼ of f32, ½ of
        bf16), not the
        logical one. Scale-block overhead is payload-dependent and
        small (one 4 KB block per chunk); it is charged exactly by the
        trace-time ``_rec`` accounting and ignored here."""
        if not self.quantized:
            return 1.0
        return 1.0 / jnp.dtype(dtype).itemsize

    # ----- bucket planner --------------------------------------------------

    def bucket_rows(self, shard_rows: int, n: int) -> list[tuple[int, int]]:
        """Row ranges ``[(r0, r1), ...]`` of the per-device
        ``[shard_rows, LANE]`` shard view, one ring collective each.
        Boundaries are multiples of 32 rows; the tail keeps the
        remainder (its per-chunk tile pad is the ring planner's job).

        A bucket is ``bucket_mb`` of the FLAT vector, so each of the
        ``n`` devices' shard views contributes ``1/n`` of it. The ring
        kernels are VMEM-resident — payload, output, send staging and
        the double mailbox, ``(n + 4)`` chunks — and sizing the bucket
        per shard instead made the default 4 MB ask for 32 MB of the
        chip's 16 MB scoped VMEM at ``n=4`` ("ran out of memory in
        memory space vmem")."""
        per = int(self.bucket_mb * 2**20) // (4 * _LANE * n)  # f32 rows
        per = max(_BUCKET_ALIGN_ROWS, per - per % _BUCKET_ALIGN_ROWS)
        out = []
        r = 0
        while r < shard_rows:
            out.append((r, min(r + per, shard_rows)))
            r += per
        return out

    # ----- the three choreography points (called by opt.sharded) ----------

    def scatter_grads(self, flat):
        """Sum-reduce-scatter the flat local gradient: returns this
        device's contiguous shard of the cross-device sum (the ZeRO-1
        reduce-scatter, ``opt.sharded`` divides by N for the mean)."""
        n = lax.axis_size(self.axis)
        if self.mode == "psum":
            # Byte-for-byte the seed choreography ([rows, LANE] view —
            # see opt.sharded's tile-friendly-layout rules).
            g2 = _pad_to(flat, n * _LANE).reshape(-1, _LANE)
            return C.reduce_scatter(g2, self.axis).reshape(-1)
        padded = _pad_to(flat, n * _LANE)
        rows_s = padded.shape[0] // (n * _LANE)
        x3 = padded.reshape(n, rows_s, _LANE)
        op = "qsum" if self.quantized else "sum"
        from mpit_tpu.ops.ring_collectives import ring_reduce_scatter

        shards, token = [], None
        for r0, r1 in self.bucket_rows(rows_s, n):
            xb = x3[:, r0:r1, :].reshape(-1, _LANE)
            if token is not None:
                # Serialize rings (shared collective_id; see module
                # docstring) without constraining the backward compute
                # they overlap with.
                xb, token = lax.optimization_barrier((xb, token))
            sb = ring_reduce_scatter(
                xb, self.axis, op=op, interpret=self.interpret
            )
            token = sb
            shards.append(sb.astype(flat.dtype))
        return jnp.concatenate(shards) if len(shards) > 1 else shards[0]

    def param_shard(self, flat):
        """This device's contiguous shard of the flat params — the SAME
        layout every mode scatters into (``opt.sharded.shard_of``)."""
        return shard_of(flat, self.axis)

    def gather_updates(self, u_shard, size: int):
        """All-gather the per-shard updates back to the full flat
        vector (replicated-typed, ``[:size]``) — the ZeRO-1 gather."""
        n = lax.axis_size(self.axis)
        if self.mode == "psum":
            return C.allgather(
                u_shard.reshape(-1, _LANE), self.axis, tiled=True,
                invariant=True,
            ).reshape(-1)[:size]
        rows_s = u_shard.shape[0] // _LANE
        u2 = u_shard.reshape(rows_s, _LANE)
        from mpit_tpu.ops.ring_collectives import ring_all_gather

        pieces, token = [], None
        for r0, r1 in self.bucket_rows(rows_s, n):
            xb = u2[r0:r1, :]
            if token is not None:
                xb, token = lax.optimization_barrier((xb, token))
            gb = ring_all_gather(
                xb, self.axis, quantized=self.quantized,
                interpret=self.interpret,
            )
            token = gb
            pieces.append(
                gb.reshape(n, (r1 - r0) * _LANE).astype(u_shard.dtype)
            )
        full = (
            jnp.concatenate(pieces, axis=1) if len(pieces) > 1 else pieces[0]
        )
        return full.reshape(-1)[:size]

    # ----- plain-DP (zero1=False) path ------------------------------------

    def allreduce_grads(self, grads):
        """Mean-allreduce a gradient pytree — the plain-DP sync
        (``lax.pmean`` in psum mode, bucketed ring RS+AG otherwise;
        the ring forms flatten via the lane-aligned ``flat_ravel`` so
        bucket boundaries never split a tile)."""
        if self.mode == "psum":
            return jax.tree.map(lambda g: lax.pmean(g, self.axis), grads)
        n = lax.axis_size(self.axis)
        flat, unravel = flat_ravel(grads)
        shard = self.scatter_grads(flat) / n
        full = self.gather_updates(shard, flat.shape[0])
        return unravel(full)
