"""Mesh bootstrap and topology discovery — the ``mpiT.Init()`` analogue.

Reference capability (SURVEY.md §3.1 C1, §4.1; BASELINE.json north-star):
``mpiT.Init()`` joins the MPI world started by ``mpirun`` and
``mpiT.Comm_rank``/``Comm_size`` discover the process's place in it; a
rank-role convention then routes each process into ``pserver.lua`` or the
client training loop.

TPU-native redesign: there are no per-rank roles — the program is SPMD. What
``init()`` produces instead is a :class:`World`: a named
``jax.sharding.Mesh`` laid out over the slice's device topology (ICI), plus
process-level info for multi-host launches. "Rank" and "size" survive as
*per-device mesh coordinates* (usable inside ``shard_map`` via
``lax.axis_index``) and as *process* index/count for host-side code.

Multi-host bootstrap: where the reference relied on ``mpirun`` to start P
processes and assign ranks, a JAX multi-host program is started by the TPU
pod runtime (one process per host) and coordinates via
``jax.distributed.initialize()``, which reads slice metadata. ``init()``
calls it automatically when the environment indicates a multi-host launch.
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical mesh-axis names used across the framework. A World may use any
# subset; 'data' is the default (pure-DP, the reference's only strategy).
DATA_AXIS = "data"      # data parallel (the reference's async/sync DP)
FSDP_AXIS = "fsdp"      # parameter/optimizer sharding (ZeRO / goo sharding)
MODEL_AXIS = "model"    # tensor parallel
PIPE_AXIS = "pipe"      # pipeline parallel
SEQ_AXIS = "seq"        # sequence / context parallel (ring attention, Ulysses)
EXPERT_AXIS = "expert"  # expert parallel (MoE)


@dataclasses.dataclass(frozen=True)
class World:
    """A process's view of the distributed machine: the ``MPI_COMM_WORLD``
    analogue, re-expressed as a named device mesh.

    Where the reference exposes ``Comm_rank``/``Comm_size`` per *process*
    (SURVEY.md §4.1), a World exposes:

    - :attr:`mesh` — the named ``jax.sharding.Mesh`` over all addressable
      devices; collectives ride its axes.
    - :attr:`process_index` / :attr:`process_count` — host-level identity
      (what ``mpirun`` rank/size degenerate to under SPMD).
    - per-device coordinates — available *inside* jitted code via
      ``comm.rank(axis)`` (= ``lax.axis_index``).
    """

    mesh: Mesh
    # DCN factorization (hybrid multi-slice worlds, :func:`init_hybrid`):
    # axis name -> how many SLICES that axis spans. An axis absent here is
    # entirely intra-slice (ICI). E.g. {"data": 4} on a 32-device world of
    # 4 slices: the data axis is 4 slices x (per-slice chips), and its
    # collectives cross DCN at the slice boundary. Cost models
    # (utils/profiling.CommModel) read this to price ICI vs DCN hops.
    dcn_axes: Any = None

    # ----- topology queries ------------------------------------------------
    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def shape(self) -> Mapping[str, int]:
        return dict(self.mesh.shape)

    @property
    def num_devices(self) -> int:
        return math.prod(self.mesh.shape.values())

    def axis_size(self, axis: str) -> int:
        return self.mesh.shape[axis]

    def dcn_factor(self, axis: str) -> int:
        """How many slices ``axis`` spans (1 = pure-ICI axis)."""
        return (self.dcn_axes or {}).get(axis, 1)

    @property
    def num_slices(self) -> int:
        out = 1
        for v in (self.dcn_axes or {}).values():
            out *= v
        return out

    @property
    def process_index(self) -> int:
        """Host-process rank (the ``mpirun`` rank analogue for host code)."""
        return jax.process_index()

    @property
    def process_count(self) -> int:
        return jax.process_count()

    @property
    def devices(self) -> np.ndarray:
        return self.mesh.devices

    def local_devices(self) -> list[Any]:
        return [d for d in self.mesh.devices.flat if d.process_index == jax.process_index()]

    # ----- sharding helpers ------------------------------------------------
    def sharding(self, *spec: Any) -> NamedSharding:
        """NamedSharding over this world's mesh for a PartitionSpec."""
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_map(self, fn, in_specs, out_specs, *, check_vma: bool = True):
        """``jax.shard_map`` bound to this world's mesh."""
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
        )

    # ----- convenience eager collectives (host-level tier) -----------------
    # These run a one-off shard_map over the mesh. They exist for tests,
    # benchmarks and the compat facade; hot paths should call the in-jit
    # functions from mpit_tpu.comm.collectives directly.
    def allreduce(self, x, *, axis: str | Sequence[str] | None = None, op: str = "sum"):
        """Reduce a global array whose leading dim is the "rank" dimension.

        ``x.shape[0]`` must be divisible by the total size of the reduce
        axes; it is sharded across all of them so each element is counted
        exactly once.
        """
        from mpit_tpu.comm import collectives as C

        axes = self.axis_names if axis is None else C.axis_tuple(axis)
        f = self.shard_map(
            lambda v: C.allreduce(v, axes, op=op), in_specs=P(axes), out_specs=P()
        )
        return f(x)

    def gather_host_bytes(self, payload: bytes) -> list[bytes]:
        """All-gather an arbitrary host byte string across processes.

        The flight-recorder transport for REAL multi-process runs
        (``obs.aggregate.gather_distributed``): each process contributes
        its serialized telemetry; every process receives the full
        process-ordered list (index = ``process_index``). Variable
        lengths are handled by a size exchange + zero-padding to the
        max. Single-process worlds short-circuit without touching the
        collective machinery.

        This is a COLLECTIVE over processes — every process of the world
        must call it, in the same program order as its other
        cross-process collectives, or the job deadlocks (the standard
        multi-host contract, same as checkpointing).
        """
        if self.process_count == 1:
            return [bytes(payload)]
        from jax.experimental import multihost_utils

        sizes = multihost_utils.process_allgather(
            np.asarray(len(payload), np.int64)
        )
        buf = np.zeros(int(sizes.max()), np.uint8)
        buf[: len(payload)] = np.frombuffer(payload, np.uint8)
        gathered = multihost_utils.process_allgather(buf)
        return [
            bytes(gathered[i, : int(sizes[i])]) for i in range(len(sizes))
        ]

    def __repr__(self) -> str:  # readable in logs
        shape = ",".join(f"{k}={v}" for k, v in self.mesh.shape.items())
        return (
            f"World(mesh=[{shape}], devices={self.num_devices}, "
            f"process={jax.process_index()}/{jax.process_count()})"
        )


_DEFAULT_WORLD: World | None = None
_LOCK = threading.Lock()
_DISTRIBUTED_TRIED = False


def _maybe_distributed_initialize() -> None:
    """Join the multi-host world if the environment indicates one.

    The reference reads rank/size assigned by ``mpirun`` (SURVEY.md §4.1);
    the TPU-native path reads slice metadata via
    ``jax.distributed.initialize()``. Single-host (one chip, one
    four-chip host, CPU fake meshes) skips it.

    Checked via env vars only — ``jax.distributed.initialize()`` must run
    before anything initializes the local XLA backends, so no jax topology
    query may happen first.
    """
    global _DISTRIBUTED_TRIED
    if _DISTRIBUTED_TRIED:
        return
    _DISTRIBUTED_TRIED = True
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "COORDINATOR_ADDRESS"
    )
    n_proc = os.environ.get("JAX_NUM_PROCESSES")
    if coord and n_proc:
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # A multi-PROCESS world on the CPU backend needs a real
            # cross-host collectives transport or the first global
            # computation dies with "Multiprocess computations aren't
            # implemented on the CPU backend" (ISSUE 3: the multi-host
            # e2e only got this far once PYTHONPATH stopped masking it).
            # Gloo TCP is jax's supported CPU implementation; set it
            # before the backend initializes unless the caller chose one
            # (the env var, read at jax import, wins if present).
            if not os.environ.get("JAX_CPU_COLLECTIVES_IMPLEMENTATION"):
                try:
                    jax.config.update(
                        "jax_cpu_collectives_implementation", "gloo"
                    )
                except Exception:
                    pass  # jaxlib without the flag: preserve behavior
        try:
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(n_proc),
                process_id=int(os.environ.get("JAX_PROCESS_ID", "0")),
            )
        except RuntimeError:
            pass  # already initialized (e.g. by the launcher)


def init(
    axis_shapes: Mapping[str, int] | None = None,
    *,
    devices: Sequence[Any] | None = None,
    set_default: bool = True,
) -> World:
    """Bootstrap the communication backend — the ``mpiT.Init()`` analogue.

    Args:
      axis_shapes: ordered mapping of mesh-axis name → size, e.g.
        ``{"data": 4, "model": 2}``. A ``-1`` size (at most one) is
        inferred from the device count. Default: all devices on one
        ``"data"`` axis — the pure data-parallel world matching the
        reference's capability.
      devices: explicit device list (default: all addressable devices, in
        the topology-aware order chosen by ``jax.make_mesh``).
      set_default: install the result as the process-default World
        returned by :func:`get_world`.

    Returns:
      A :class:`World`.
    """
    _maybe_distributed_initialize()
    devs = list(devices) if devices is not None else jax.devices()
    ndev = len(devs)

    if axis_shapes is None:
        axis_shapes = {DATA_AXIS: ndev}
    axis_shapes = dict(axis_shapes)

    # Resolve a single -1 wildcard.
    wild = [k for k, v in axis_shapes.items() if v == -1]
    if len(wild) > 1:
        raise ValueError(f"at most one -1 axis allowed, got {wild}")
    if wild:
        known = math.prod(v for v in axis_shapes.values() if v != -1)
        if ndev % known:
            raise ValueError(
                f"device count {ndev} not divisible by fixed axes product {known}"
            )
        axis_shapes[wild[0]] = ndev // known
    if math.prod(axis_shapes.values()) != ndev:
        raise ValueError(
            f"mesh shape {axis_shapes} does not cover {ndev} devices"
        )

    # AxisType.Auto throughout: this framework is shard_map-centric, and
    # make_mesh's default of Explicit leaks sharding-in-types avals into
    # host-level ops outside a mesh context.
    names = tuple(axis_shapes.keys())
    auto = (jax.sharding.AxisType.Auto,) * len(names)
    if devices is None:
        # Topology-aware layout (ICI-friendly): jax.make_mesh reorders
        # devices so the innermost axes land on physical neighbors.
        mesh = jax.make_mesh(tuple(axis_shapes.values()), names, auto)
    else:
        dev_array = np.asarray(devs).reshape(tuple(axis_shapes.values()))
        mesh = Mesh(dev_array, names, axis_types=auto)

    world = World(mesh=mesh)
    if set_default:
        global _DEFAULT_WORLD
        with _LOCK:
            _DEFAULT_WORLD = world
    return world


def _slice_groups(devs: Sequence[Any], num_slices: int) -> list[list[Any]]:
    """Group devices by slice. Real multi-slice TPU devices carry a
    ``slice_index``; environments without one (the fake CPU mesh, single
    -slice chips) fall back to contiguous equal chunks as *virtual*
    slices — the layout math and cost accounting are identical, which is
    what makes the hybrid path testable on 1 host (SURVEY.md §5.2)."""
    by_slice: dict[int, list[Any]] = {}
    if all(getattr(d, "slice_index", None) is not None for d in devs):
        for d in devs:
            by_slice.setdefault(d.slice_index, []).append(d)
        if len(by_slice) != num_slices:
            raise ValueError(
                f"devices report {len(by_slice)} slices, expected {num_slices}"
            )
        return [by_slice[k] for k in sorted(by_slice)]
    n = len(devs)
    if n % num_slices:
        raise ValueError(
            f"{n} devices not divisible into {num_slices} virtual slices"
        )
    per = n // num_slices
    return [list(devs[i * per : (i + 1) * per]) for i in range(num_slices)]


def init_hybrid(
    axis_shapes: Mapping[str, int],
    dcn_axes: Mapping[str, int],
    *,
    devices: Sequence[Any] | None = None,
    set_default: bool = True,
) -> World:
    """Bootstrap a DCN-aware multi-slice world (SURVEY.md §3.4 transport:
    "ICI (intra-slice) and DCN (cross-slice)").

    The jax ``create_hybrid_device_mesh`` pattern, re-expressed in this
    framework's named-axis vocabulary: each mesh axis ``a`` has total size
    ``axis_shapes[a]``, of which ``dcn_axes.get(a, 1)`` spans slices (the
    slow DCN hops) and the rest stays inside a slice (ICI). Devices are
    laid out slice-major per axis, so e.g. ``data=8`` with
    ``dcn_axes={"data": 4}`` puts 4 DCN groups of 2 ICI-adjacent chips on
    the data axis — gradient allreduce then decomposes into a fast
    intra-slice phase and a small cross-slice phase, which is also
    exactly how the cost model prices it
    (``utils/profiling.CommModel``).

    Model/pipe/seq axes should stay pure-ICI (omit them from
    ``dcn_axes``): their collectives are latency/bandwidth-critical per
    layer, while the data axis syncs once per step — the standard
    slice-topology recipe.
    """
    axis_shapes = dict(axis_shapes)
    dcn_axes = {k: int(v) for k, v in dcn_axes.items() if int(v) != 1}
    unknown = set(dcn_axes) - set(axis_shapes)
    if unknown:
        raise ValueError(f"dcn_axes name unknown mesh axes: {sorted(unknown)}")
    num_slices = math.prod(dcn_axes.values()) if dcn_axes else 1
    for a, f in dcn_axes.items():
        if axis_shapes[a] % f:
            raise ValueError(
                f"axis {a!r} size {axis_shapes[a]} not divisible by its "
                f"DCN factor {f}"
            )

    _maybe_distributed_initialize()
    devs = list(devices) if devices is not None else jax.devices()
    ndev = len(devs)
    if math.prod(axis_shapes.values()) != ndev:
        raise ValueError(
            f"mesh shape {axis_shapes} does not cover {ndev} devices"
        )
    groups = _slice_groups(devs, num_slices)

    # Device array construction: [dcn_a, dcn_b, ..., ici_a, ici_b, ...]
    # (slice grid first, per-slice grid second), then interleave each
    # axis's (dcn, ici) pair adjacently and merge — slice-major ordering
    # per axis.
    names = list(axis_shapes)
    dcn_sizes = [dcn_axes.get(a, 1) for a in names]
    ici_sizes = [axis_shapes[a] // dcn_axes.get(a, 1) for a in names]
    arr = np.empty((num_slices, ndev // max(num_slices, 1)), dtype=object)
    for i, g in enumerate(groups):
        arr[i] = g
    arr = arr.reshape(*dcn_sizes, *ici_sizes)
    k = len(names)
    perm = [x for i in range(k) for x in (i, k + i)]  # (dcn_i, ici_i) pairs
    arr = arr.transpose(perm).reshape(
        tuple(d * c for d, c in zip(dcn_sizes, ici_sizes))
    )
    mesh = Mesh(
        arr, tuple(names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
    )
    world = World(mesh=mesh, dcn_axes=dcn_axes or None)
    if set_default:
        global _DEFAULT_WORLD
        with _LOCK:
            _DEFAULT_WORLD = world
    return world


def get_world() -> World:
    """Return the process-default World, creating a pure-DP one on demand."""
    global _DEFAULT_WORLD
    if _DEFAULT_WORLD is None:
        init()
    assert _DEFAULT_WORLD is not None
    return _DEFAULT_WORLD


def local_mesh(axis_shapes: Mapping[str, int] | None = None) -> Mesh:
    """Shorthand: build a mesh without installing a default World."""
    return init(axis_shapes, set_default=False).mesh
