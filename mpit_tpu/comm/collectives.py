"""Collective operations — the ``mpiT`` communication API, TPU-native.

Reference capability (SURVEY.md §3.1 C1): the ``mpiT`` Lua module exposes
``Send/Recv``, ``Isend/Irecv`` (+``Wait``/``Test``), ``Barrier``, ``Bcast``,
``Reduce``, ``Allreduce`` over Torch tensor memory, each a call into libmpi
(``MPI_Allreduce`` etc.) crossing a process boundary.

TPU-native redesign: every function here is pure and traceable — it is meant
to be called *inside* ``jit``/``shard_map`` over a named mesh axis, where XLA
lowers it to ICI collectives (ring allreduce, all-gather, collective
permute). Consequences, documented rather than papered over (SURVEY.md §8.4):

- There is no tagged, receiver-driven P2P (``ANY_SOURCE``/``ANY_TAG``): all
  communication patterns are static at trace time. Structured neighbor
  exchange (:func:`permute`, :func:`shift`, :func:`send_to`) covers the
  pipeline/ring cases; the async parameter-server protocol collapses to
  synchronous collectives (see ``mpit_tpu.compat`` and BASELINE.json's
  north-star).
- "Async" (``Isend``/``Irecv``) is the *compiler's* job: XLA overlaps
  collectives with compute automatically; explicit overlap is available via
  the Pallas tier (``mpit_tpu.comm.pallas_ring``).

Every function takes ``axis`` — one mesh-axis name or a sequence of them —
mirroring how an MPI communicator scopes a collective to a process group.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

# Varying→invariant all-gather: the result is identical on every device and
# is *marked* replicated for shard_map's VMA checker (plain lax.all_gather
# returns a varying-typed value). Public in spirit; lives in _src in jax 0.9.
from jax._src.lax.parallel import (
    all_gather_invariant as _all_gather_invariant,
)


def _pvary(x, names):
    """Replicated→varying retype."""
    return lax.pcast(x, names, to="varying")


AxisName = str | Sequence[str]

_REDUCE_OPS = ("sum", "mean", "max", "min", "prod")


def _rec(
    op: str,
    x,
    axis: AxisName,
    *,
    model: str | None = None,
    payload_bytes: float | None = None,
    mode: str | None = None,
) -> None:
    """Trace-time telemetry for a collective (mpit_tpu.obs; no-op when
    obs is disabled — one global read).

    Collectives here are *traceable* wrappers: this Python body runs
    when XLA traces the enclosing jit/shard_map, not per device step —
    so what accumulates is the program's modeled per-op wire traffic
    (``utils.profiling.collective_bytes`` per trace), the trace-time
    analogue of the CommModel accounting. ``model``: the wire-model
    name (default ``op``); ``None`` payload models (permute/shift/
    send_to/recv_from) charge the full buffer — each device forwards
    its whole shard once. ``payload_bytes`` overrides the payload
    derived from ``x`` — the quantized ring collectives charge their
    ACTUAL wire-sized payload (int8 chunks + scale blocks ≈ ¼ the
    logical bytes), never the logical one (ISSUE 9: the roofline ICI
    accounting and the P2P matrix must see the quantized size).
    ``mode`` stamps the executed-mode label (``ring``/``psum_fallback``
    /``lax_emulated``) so a fallback run cannot be misattributed.
    """
    from mpit_tpu.obs import core as _obs

    if not _obs.enabled():
        return
    try:
        names = axis_tuple(axis)
        p = 1
        for a in names:
            p = p * lax.axis_size(a)
        p = int(p)
        payload = (
            float(payload_bytes)
            if payload_bytes is not None
            else sum(
                l.size * l.dtype.itemsize
                for l in jax.tree.leaves(x)
                if hasattr(l, "dtype")
            )
        )
    except Exception:
        return  # outside a mesh context / abstract axis: nothing to charge
    from mpit_tpu.utils.profiling import collective_bytes

    if model == "p2p":
        wire = float(payload)
    else:
        wire = collective_bytes(payload, p, model or op)
    axis_label = ",".join(names)
    extra = {"mode": mode} if mode else {}
    _obs.counter("collective_bytes", wire, op=op, axis=axis_label, **extra)
    _obs.counter("collective_calls", 1, op=op, axis=axis_label, **extra)
    _obs.instant(
        f"collective:{op}", axis=axis_label, payload_bytes=payload,
        wire_bytes_per_device=wire, devices=p, **extra,
    )


def axis_tuple(axis: AxisName) -> tuple[str, ...]:
    """Normalize an axis name or sequence of names to a tuple."""
    return (axis,) if isinstance(axis, str) else tuple(axis)


def rank(axis: str):
    """This device's coordinate along ``axis`` — ``mpiT.Comm_rank`` analogue.

    Only meaningful inside ``shard_map``/``jit`` over a mesh with ``axis``.
    """
    return lax.axis_index(axis)


def size(axis: AxisName):
    """Number of devices along ``axis`` — ``mpiT.Comm_size`` analogue."""
    if isinstance(axis, str):
        return lax.axis_size(axis)
    out = 1
    for a in axis:
        out *= lax.axis_size(a)
    return out


def vary(x, axis: AxisName):
    """Mark a replicated pytree as device-varying along ``axis``.

    Load-bearing for gradient semantics under jax 0.9's VMA-checked
    shard_map: differentiating a *varying* loss with respect to
    *replicated* params makes AD insert an automatic ``psum`` — the grads
    arrive already cross-device summed, and any explicit pmean/
    reduce-scatter then double-counts (observed as exactly N× updates).
    Taking the grad w.r.t. a ``vary``-ed copy of the params keeps grads
    local so the training step controls the one reduction itself.

    Idempotent per leaf: axes a leaf already varies over are skipped, so
    mixed trees (e.g. pipe-sharded stage params next to replicated
    embeddings) can be varied to a common set in one call.
    """
    names = axis_tuple(axis)

    def one(l):
        have = getattr(jax.typeof(l), "vma", frozenset()) or frozenset()
        missing = tuple(a for a in names if a not in have)
        return _pvary(l, missing) if missing else l

    return jax.tree.map(one, x)


def allreduce(x, axis: AxisName, *, op: str = "sum"):
    """All-reduce — the ``mpiT.Allreduce`` analogue (the sync-DP primitive).

    Reference: ``MPI_Allreduce(sendbuf, recvbuf, …, MPI_SUM, comm)``
    (SURVEY.md §4.3). Here: ``lax.psum``/``pmax``/``pmin`` lowered by XLA to
    an ICI ring; everyone receives the reduced value.
    """
    _rec("allreduce", x, axis)
    if op == "sum":
        return lax.psum(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    if op == "prod":
        # No native pprod collective: invariant-gather then reduce locally
        # (identical on every device, typed replicated).
        names = axis_tuple(axis)
        y = x
        for a in names:
            y = jnp.prod(_all_gather_invariant(y, a, axis=0), axis=0)
        return y
    raise ValueError(f"op must be one of {_REDUCE_OPS}, got {op!r}")


def pmean(x, axis: AxisName):
    """Mean-allreduce; the gradient-averaging spelling of :func:`allreduce`."""
    _rec("pmean", x, axis, model="allreduce")
    return lax.pmean(x, axis)


def reduce(x, axis: str, *, root: int = 0, op: str = "sum"):
    """Reduce-to-root — the ``mpiT.Reduce`` analogue.

    MPI leaves non-root buffers undefined; under SPMD every device computes
    the allreduce and non-root devices get **zeros** (a defined, testable
    contract). If every device needs the value, use :func:`allreduce`.
    """
    y = allreduce(x, axis, op=op)  # (charged there as an allreduce)
    is_root = jnp.broadcast_to(rank(axis) == root, y.shape)
    return lax.select(is_root, y, jnp.zeros_like(y))


def broadcast(x, axis: str, *, root: int = 0):
    """Broadcast from ``root`` — the ``mpiT.Bcast`` analogue.

    Reference use: initial parameter sync so every worker starts from
    identical weights (SURVEY.md §4.4; BASELINE.json config #2 "exercises
    mpiT.Bcast/Allreduce"). Under SPMD replication is usually free (same
    init PRNG key), but the explicit op is provided for API parity and for
    genuinely divergent per-device state.

    Implementation: select-then-psum — zero everywhere but ``root``, then
    sum (``lax.select``, not mask-multiply, so garbage NaN/Inf in non-root
    buffers cannot poison the result). ``lax.pbroadcast`` (the
    CollectiveBroadcast HLO) was evaluated and rejected: jax 0.9 has no
    MLIR lowering for it on either the CPU test mesh *or* this TPU stack.
    """
    _rec("broadcast", x, axis)
    is_root = jnp.broadcast_to(rank(axis) == root, x.shape)
    return lax.psum(lax.select(is_root, x, jnp.zeros_like(x)), axis)


def allgather(
    x,
    axis: str,
    *,
    tiled: bool = False,
    gather_axis: int = 0,
    invariant: bool = False,
):
    """All-gather along a mesh axis.

    ``tiled=False`` stacks a new leading dimension of size ``size(axis)``;
    ``tiled=True`` concatenates along ``gather_axis``. ``invariant=True``
    types the (identical-everywhere) result as replicated for shard_map's
    VMA checker — use when the gathered value leaves the shard_map with a
    replicated out_spec.
    """
    _rec("allgather", x, axis, model="all_gather")
    if invariant:
        return _all_gather_invariant(x, axis, axis=gather_axis, tiled=tiled)
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: str, *, scatter_axis: int = 0, tiled: bool = True):
    """Reduce-scatter: the ZeRO-1 gradient-sharding primitive.

    Absent from the reference's API surface but required by the north-star
    ("goo optimizer state sharded across chips", BASELINE.json): each device
    receives one reduced shard of ``x`` along ``scatter_axis``.
    """
    _rec("reduce_scatter", x, axis)
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=tiled)


def alltoall(x, axis: str, *, split_axis: int, concat_axis: int, tiled: bool = False):
    """All-to-all — the Ulysses sequence↔head redistribution primitive."""
    _rec("alltoall", x, axis)
    return lax.all_to_all(
        x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled
    )


def permute(x, axis: str, perm: Sequence[tuple[int, int]]):
    """Collective permute — the static-pattern P2P analogue.

    ``perm`` is a list of ``(source, dest)`` pairs; devices not named as a
    dest receive zeros. This is the XLA-native replacement for the
    reference's tagged ``Send/Recv`` in the *structured* cases (pipeline
    stages, ring neighbors); dynamic ``ANY_SOURCE`` patterns have no SPMD
    equivalent (SURVEY.md §8.4) and collapse at a higher level instead.
    """
    _rec("permute", x, axis, model="p2p")
    return lax.ppermute(x, axis, perm=list(perm))


def shift(x, axis: str, *, offset: int = 1, wrap: bool = True):
    """Ring shift: device ``i`` receives from ``i - offset`` (mod size).

    The building block of ring pipelines (pipeline parallelism, ring
    attention). ``wrap=False`` leaves edge devices holding zeros.
    """
    _rec("shift", x, axis, model="p2p")
    n = lax.axis_size(axis)
    if wrap:
        perm = [(i, (i + offset) % n) for i in range(n)]
    else:
        perm = [(i, i + offset) for i in range(n) if 0 <= i + offset < n]
    return lax.ppermute(x, axis, perm=perm)


def send_to(x, axis: str, dest: Sequence[int]):
    """Static scatter-send: device ``i`` sends its ``x`` to ``dest[i]``.

    A compiled, dense stand-in for ``mpiT.Send`` where the communication
    pattern is known at trace time. ``dest`` must be a permutation of
    ``range(size(axis))``; devices that nobody sends to receive zeros.
    """
    _rec("send_to", x, axis, model="p2p")
    n = len(dest)
    perm = [(i, int(dest[i])) for i in range(n)]
    return lax.ppermute(x, axis, perm=perm)


def recv_from(x, axis: str, src: Sequence[int]):
    """Static gather-receive: device ``i`` receives ``x`` from ``src[i]``."""
    _rec("recv_from", x, axis, model="p2p")
    n = len(src)
    perm = [(int(src[i]), i) for i in range(n)]
    return lax.ppermute(x, axis, perm=perm)


def barrier(axis: AxisName, token=None):
    """Barrier — the ``mpiT.Barrier`` analogue.

    Under SPMD+XLA a standalone barrier is mostly a scheduling fence: this
    performs a tiny psum and ties it into ``token`` (any array) via
    ``optimization_barrier`` so the collective cannot be elided or hoisted.
    Returns ``token`` (or the psum result if no token given).
    """
    _rec("barrier", jnp.ones((), dtype=jnp.int32), axis, model="allreduce")
    fence = lax.psum(jnp.ones((), dtype=jnp.int32), axis)
    if token is None:
        return fence
    token, _ = lax.optimization_barrier((token, fence))
    return token
