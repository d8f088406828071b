"""KV-page shipment: moving finished prefill state between fleet workers.

The disaggregated fleet (ISSUE 19) splits a request's life across two
engines: a prefill worker fills the KV rows, a decode worker streams the
output tokens. The hand-off is a **shipment** — the slot's first
``length`` cached KV rows in the canonical dense row layout
``[L, length, H, Dh]`` (quantized caches ship four leaves: int8 payloads
plus their ``[L, length, H, 1]`` scale blocks — the page pytree already
carries them) plus the request facts the decode side needs (prompt,
first sampled token, sampling params).

Wire format — three length-prefixed messages on the dedicated
``Comm_dup(key="fleet-kv")`` channel, in per-(src, tag) FIFO order:

1. ``TAG_SHIP_HDR``: ``int64[2]`` = ``[meta_len, payload_len]`` — the
   receiver sizes its buffers from this (compat's ``_check_transfer``
   demands exact size + dtype matches, so nothing variable-length goes
   unprefixed).
2. ``TAG_SHIP_META``: ``uint8[meta_len]`` JSON — request facts + one
   shape/dtype descriptor per leaf, in the explicit leaf order
   ``[k, v]`` (or ``[k.q, k.scale, v.q, v.scale]`` quantized). The
   order is part of the wire contract; no pytree treedefs cross the
   wire.
3. ``TAG_SHIP_PAYLOAD``: ``uint8[payload_len]`` — the leaves' raw bytes
   concatenated in that same order.

Every serialize/deserialize site here is a lifecycle-ledger seam
(``analysis/lint.py`` rule ``shipment-seam``): a KV byte crossing the
wire unledgered is invisible to why-slow forensics, so each function
takes an optional ``ledger`` and emits a ``kv_ship_*`` event when given
one. Shipment sends deliberately ride the ambient flight recorder (no
throwaway-recorder trick like the obs gather uses) so shipment bytes
show up on the merged P2P matrix.

On real TPU hardware, :func:`ship_kv_remote` moves a buffer
device-to-device with a ``make_async_remote_copy`` Pallas kernel
instead of bouncing through host memory; off-TPU it refuses rather than
pretend (roofline honesty — no fabricated DMA path on CPU).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any

import numpy as np

from mpit_tpu import compat as mpiT

__all__ = [
    "KVShipment",
    "SHIPMENT_CHANNEL",
    "TAG_SHIP_HDR",
    "TAG_SHIP_META",
    "TAG_SHIP_PAYLOAD",
    "inject_shipment",
    "pack_shipment",
    "recv_shipment",
    "send_shipment",
    "ship_kv_remote",
]

# Dedicated matching space for KV payloads: bulk shipments never race
# the fleet's small control messages for a Probe slot.
SHIPMENT_CHANNEL = "fleet-kv"

# Tag block 61-63 (fleet control uses 41-46, elastic 31-37 — disjoint).
TAG_SHIP_HDR = 61
TAG_SHIP_META = 62
TAG_SHIP_PAYLOAD = 63


def _np_dtype(name: str) -> np.dtype:
    """Resolve a dtype name, pulling in ml_dtypes' numpy registrations
    (bfloat16 et al.) only when a plain lookup fails — keeps this
    module importable without jax on the path."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # noqa: F401  (registers bfloat16/float8 dtypes)

        return np.dtype(name)


@dataclasses.dataclass
class KVShipment:
    """One request's prefill hand-off.

    ``k``/``v`` are host arrays ``[L, length, H, Dh]`` — or, when
    ``quantized``, objects with ``.q`` (int8, same shape) and ``.scale``
    (f32 ``[L, length, H, 1]``) attributes (``QuantizedKV`` fits; the
    wire never sees the container type, only the four leaves).
    ``first_token`` is the token prefill sampled — output token 1, and
    the decode worker's starting ``last_token``.
    """

    rid: str
    prompt: list[int]
    first_token: int
    length: int
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    eos_id: int | None = None
    quantized: bool = False
    k: Any = None
    v: Any = None

    def leaves(self) -> list[tuple[str, np.ndarray]]:
        """The wire leaf order — explicit, not derived from a treedef."""
        if self.quantized:
            return [
                ("k.q", self.k.q),
                ("k.scale", self.k.scale),
                ("v.q", self.v.q),
                ("v.scale", self.v.scale),
            ]
        return [("k", self.k), ("v", self.v)]


@dataclasses.dataclass
class _QuantPair:
    """Wire-side stand-in for a quantized leaf pair. Callers that need
    a real pytree (engine injection) convert via ``QuantizedKV(q=..,
    scale=..)``; the engine's ``inject_kv_rows`` does this itself."""

    q: np.ndarray
    scale: np.ndarray


def pack_shipment(
    ship: KVShipment, *, ledger=None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Serialize to the three wire messages: ``(header int64[2],
    meta uint8[m], payload uint8[n])``."""
    leaves = [
        (name, np.ascontiguousarray(np.asarray(arr)))
        for name, arr in ship.leaves()
    ]
    meta = {
        "rid": str(ship.rid),
        "prompt": [int(t) for t in ship.prompt],
        "first_token": int(ship.first_token),
        "length": int(ship.length),
        "max_new_tokens": int(ship.max_new_tokens),
        "temperature": float(ship.temperature),
        "top_k": int(ship.top_k),
        "eos_id": None if ship.eos_id is None else int(ship.eos_id),
        "quantized": bool(ship.quantized),
        "leaves": [
            {
                "name": name,
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
            }
            for name, arr in leaves
        ],
    }
    meta_buf = np.frombuffer(
        json.dumps(meta, sort_keys=True).encode("utf-8"), np.uint8
    )
    payload = (
        np.concatenate(
            [np.frombuffer(arr.tobytes(), np.uint8) for _, arr in leaves]
        )
        if leaves
        else np.empty((0,), np.uint8)
    )
    header = np.asarray([meta_buf.size, payload.size], np.int64)
    if ledger is not None:
        ledger.event(
            ship.rid, "kv_ship_pack",
            bytes=int(payload.nbytes), rows=int(ship.length),
            quantized=bool(ship.quantized),
        )
    return header, meta_buf, payload


def unpack_shipment(
    meta_buf: np.ndarray, payload: np.ndarray, *, ledger=None
) -> KVShipment:
    """Inverse of :func:`pack_shipment` — slices the payload back into
    leaves by the meta descriptors (same explicit order)."""
    meta = json.loads(np.asarray(meta_buf, np.uint8).tobytes().decode("utf-8"))
    raw = np.asarray(payload, np.uint8).tobytes()
    arrays: list[np.ndarray] = []
    off = 0
    for d in meta["leaves"]:
        dt = _np_dtype(d["dtype"])
        n = int(np.prod(d["shape"], dtype=np.int64)) * dt.itemsize
        arrays.append(
            np.frombuffer(raw[off : off + n], dt).reshape(d["shape"])
        )
        off += n
    if off != len(raw):
        raise ValueError(
            f"shipment payload size mismatch: descriptors cover {off} "
            f"bytes, payload carries {len(raw)}"
        )
    if meta["quantized"]:
        k = _QuantPair(q=arrays[0], scale=arrays[1])
        v = _QuantPair(q=arrays[2], scale=arrays[3])
    else:
        k, v = arrays
    ship = KVShipment(
        rid=meta["rid"],
        prompt=list(meta["prompt"]),
        first_token=int(meta["first_token"]),
        length=int(meta["length"]),
        max_new_tokens=int(meta["max_new_tokens"]),
        temperature=float(meta["temperature"]),
        top_k=int(meta["top_k"]),
        eos_id=meta["eos_id"],
        quantized=bool(meta["quantized"]),
        k=k,
        v=v,
    )
    if ledger is not None:
        ledger.event(
            ship.rid, "kv_ship_unpack",
            bytes=len(raw), rows=int(ship.length),
        )
    return ship


def send_shipment(ship: KVShipment, dest: int, comm, *, ledger=None) -> int:
    """Ship to ``dest`` on the KV channel: header, meta, payload — three
    Sends whose per-(src, tag) FIFO ordering the receiver relies on.
    Returns the payload byte count (what the P2P matrix will show,
    modulo the small header/meta frames)."""
    header, meta_buf, payload = pack_shipment(ship)
    mpiT.Send(header, dest=dest, tag=TAG_SHIP_HDR, comm=comm)
    mpiT.Send(meta_buf, dest=dest, tag=TAG_SHIP_META, comm=comm)
    mpiT.Send(payload, dest=dest, tag=TAG_SHIP_PAYLOAD, comm=comm)
    if ledger is not None:
        ledger.event(
            ship.rid, "kv_ship_send",
            dest=int(dest), bytes=int(payload.nbytes),
            rows=int(ship.length),
        )
    return int(payload.nbytes)


def recv_shipment(
    src: int, comm, *, timeout: float | None = None, ledger=None
) -> KVShipment:
    """Receive one shipment from ``src``: header first (sizes the
    buffers), then meta and payload. ``timeout`` applies to the header
    wait only — once the header is in, the remaining frames are already
    FIFO-queued behind it (compat Send is buffered)."""
    header = np.empty((2,), np.int64)
    kw = {} if timeout is None else {"timeout": timeout}
    mpiT.Recv(header, src=src, tag=TAG_SHIP_HDR, comm=comm, **kw)
    meta_buf = np.empty((int(header[0]),), np.uint8)
    payload = np.empty((int(header[1]),), np.uint8)
    mpiT.Recv(meta_buf, src=src, tag=TAG_SHIP_META, comm=comm)
    mpiT.Recv(payload, src=src, tag=TAG_SHIP_PAYLOAD, comm=comm)
    ship = unpack_shipment(meta_buf, payload)
    if ledger is not None:
        ledger.event(
            ship.rid, "kv_ship_recv",
            src=int(src), bytes=int(payload.nbytes), rows=int(ship.length),
        )
    return ship


def inject_shipment(engine, slot: int, ship: KVShipment, *, ledger=None):
    """Install a received shipment into ``slot`` of a decode engine:
    KV rows, fill length, and ``last_token`` (= the shipped first
    token). The caller has already admitted the slot (an
    all-or-nothing ``allocator.admit`` — no ``register_prefix``;
    injected pages are private, never prefix-shared)."""
    engine.inject_kv_rows(
        slot, ship.k, ship.v, ship.length, ship.first_token
    )
    if ledger is not None:
        ledger.event(
            ship.rid, "kv_ship_inject",
            slot=int(slot), rows=int(ship.length),
        )


def ship_kv_remote(buf, dst_device: int):
    """TPU-only device-to-device KV transfer: a Pallas
    ``make_async_remote_copy`` in the collective-kernel mold — the bulk
    path real hardware uses instead of the host-bounce above. Off-TPU
    this refuses: there is no remote-DMA engine to model, and faking
    one would poison every GB/s figure downstream."""
    import jax

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "ship_kv_remote needs a TPU remote-DMA engine; off-TPU the "
            "fleet ships KV through the compat host path instead"
        )
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _ship_kernel(src_ref, dst_ref, send_sem, recv_sem):
        rdma = pltpu.make_async_remote_copy(
            src_ref=src_ref,
            dst_ref=dst_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=dst_device,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        rdma.wait()

    return pl.pallas_call(
        _ship_kernel,
        name="kv_ship",
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
        ),
    )(jnp.asarray(buf))
