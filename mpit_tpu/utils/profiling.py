"""Tracing, timing, and cost accounting (SURVEY.md §6 "Tracing/profiling").

The reference's observability is ad-hoc wall timers and prints in its
training scripts (SURVEY.md §6). TPU-natively the toolkit is:

- :func:`trace` — ``jax.profiler`` capture (Perfetto/XPlane) around a code
  region; view with ``xprof``/TensorBoard.
- :class:`StepTimer` — honest per-step wall timing: ``block=True`` inserts
  ``block_until_ready`` so async dispatch can't hide device time.
- :func:`compiled_cost` — XLA's own FLOP/byte estimates for a jitted
  function (``.cost_analysis()``), the ground truth for arithmetic
  intensity.
- :func:`roofline` — time lower bound from chip peaks (defaults: TPU v5e);
  labels a workload compute- vs bandwidth-bound. Multi-chip numbers in
  this 1-chip environment are *estimates* and labeled as such
  (SURVEY.md §8.4.5 "honest perf accounting").
- :func:`collective_bytes` — wire-traffic model for the mpiT-analogue
  collectives (ring allreduce moves 2·(P−1)/P·N bytes per chip, etc.),
  the denominator of the BASELINE "allreduce GB/s" metric.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Iterator, Sequence

import jax
import numpy as np


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a profiler trace of the enclosed region into ``log_dir``."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Wall-clock step timing with device-completion fencing.

    ``block=True`` (default) closes each tick on a **host-value fetch** of
    a scalar derived from the result passed to :meth:`tick` — without a
    fence, async dispatch makes steps look free and the *last* timed
    region absorbs the whole pipeline. (``block_until_ready`` waits just
    as long on the chip: ``chip_smoke.py``'s train phase times one step
    each way.)
    """

    def __init__(self, *, block: bool = True):
        self._block = block
        self._t0: float | None = None
        self.times: list[float] = []

    def start(self) -> None:
        self._t0 = time.perf_counter()

    @staticmethod
    def _fence(result: Any) -> None:
        leaves = [l for l in jax.tree.leaves(result) if hasattr(l, "dtype")]
        if not leaves:
            return
        leaf = leaves[0]
        # Reduce to one scalar on device, fetch it: forces the dependency
        # chain without gathering a whole array to host.
        scalar = leaf if getattr(leaf, "ndim", 0) == 0 else leaf.ravel()[0]
        float(np.asarray(scalar).reshape(()).astype(np.float64))

    def tick(self, result: Any = None) -> float:
        """Record one step; returns its duration in seconds."""
        if self._t0 is None:
            raise RuntimeError("StepTimer.tick() before start()")
        if self._block and result is not None:
            self._fence(result)
        now = time.perf_counter()
        dt = now - self._t0
        self.times.append(dt)
        self._t0 = now
        return dt

    def summary(self, *, skip_warmup: int = 1) -> dict[str, float]:
        ts = self.times[skip_warmup:] or self.times
        arr = np.asarray(ts)
        return {
            "steps": len(arr),
            "mean_s": float(arr.mean()),
            "p50_s": float(np.percentile(arr, 50)),
            "p95_s": float(np.percentile(arr, 95)),
            "total_s": float(arr.sum()),
        }


def compiled_cost(fn: Callable, *args, **kwargs) -> dict[str, float]:
    """XLA's cost analysis for ``jit(fn)(*args)``: flops, bytes accessed.

    Returns ``{}`` keys absent when the backend doesn't report them.
    """
    # The backend-envelope normalization (some backends wrap the
    # properties dict in a single-element list, silently emptying every
    # lookup below) lives in ONE place, shared with the roofline layer.
    from mpit_tpu.obs.roofline import cost_properties

    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    cost = cost_properties(compiled)
    out = {}
    for key in ("flops", "bytes accessed", "optimal_seconds"):
        if key in cost:
            out[key.replace(" ", "_")] = float(cost[key])
    # Memory footprint of the executable, when reported.
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out["output_size_bytes"] = float(
                getattr(mem, "output_size_in_bytes", 0.0)
            )
            out["temp_size_bytes"] = float(getattr(mem, "temp_size_in_bytes", 0.0))
    except Exception:
        pass
    return out


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Peak numbers for a roofline. Defaults: TPU v5e (public figures).

    ``dcn_bandwidth`` is the per-chip cross-slice (data-center network)
    bandwidth — v5e hosts expose ~100 Gbps NICs shared by 4 chips, i.e.
    ~3.1 GB/s/chip (public order-of-magnitude; the "How to Scale Your
    Model" planning figure). It is an ASSUMPTION for modeled multi-slice
    numbers and is labeled as such wherever it is used.
    """

    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12  # FLOP/s
    hbm_bandwidth: float = 819e9  # bytes/s
    ici_bandwidth: float = 4.5e10  # bytes/s per link direction (3 links/chip)
    dcn_bandwidth: float = 3.1e9  # bytes/s per chip across slices (assumed)
    # Per-hop ICI latency (software + link; ~1 µs is the public
    # order-of-magnitude planning figure). An ASSUMPTION, like
    # dcn_bandwidth — it exists so modeled collective figures are
    # payload-SIZED (a latency-free ring model yields the same GB/s for
    # every payload, which round 5's verdict flagged as a constant that
    # "has been identical for four rounds").
    ici_hop_latency: float = 1e-6  # seconds per ring hop (assumed)


TPU_V5E = ChipSpec()

# The one table of published peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819
# GB/s HBM). A utilization computed for a real device reads its peaks
# here; a device that is not in the table is an error, not a default.
CHIP_SPECS: dict[str, ChipSpec] = {"TPU v5 lite": TPU_V5E}


def chip_spec_for(device_kind: str) -> ChipSpec:
    """Published peaks of a real device, by its ``device_kind``."""
    try:
        return CHIP_SPECS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: add it "
            "to utils.profiling.CHIP_SPECS with its source before "
            "reporting a utilization for it"
        ) from None


def roofline(
    flops: float,
    hbm_bytes: float,
    *,
    ici_bytes: float = 0.0,
    chip: ChipSpec = TPU_V5E,
) -> dict[str, Any]:
    """Lower-bound step time from chip peaks; labels the binding resource.

    This is an *estimate* (perfect overlap assumed); on 1-chip
    environments it is the only honest way to discuss multi-chip scaling
    (SURVEY.md §8.4.5), and results should be reported as modeled, not
    measured.
    """
    t_compute = flops / chip.peak_flops_bf16
    t_hbm = hbm_bytes / chip.hbm_bandwidth
    t_ici = ici_bytes / chip.ici_bandwidth if ici_bytes else 0.0
    t = max(t_compute, t_hbm, t_ici)
    bound = {t_compute: "compute", t_hbm: "hbm", t_ici: "ici"}[t]
    return {
        "seconds_lower_bound": t,
        "bound": bound,
        "arithmetic_intensity": flops / hbm_bytes if hbm_bytes else float("inf"),
        "chip": chip.name,
        "modeled": True,  # not a measurement
    }


def tree_bytes(tree: Any) -> int:
    """Total bytes of a pytree of arrays (host or device)."""
    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(tree)
        if hasattr(leaf, "dtype")
    )


def collective_bytes(
    payload_bytes: float, num_devices: int, op: str = "allreduce"
) -> float:
    """Per-chip wire bytes for a collective over ``num_devices`` ring.

    Models (bandwidth-optimal ring algorithms, the ones XLA/ICI and the
    Pallas tier implement):

    - allreduce: 2·(P−1)/P · N   (reduce-scatter + all-gather)
    - reduce_scatter / all_gather: (P−1)/P · N
    - broadcast: N (pipelined ring)
    - alltoall: (P−1)/P · N
    """
    p = num_devices
    if p <= 1:
        return 0.0
    n = float(payload_bytes)
    if op == "allreduce":
        return 2.0 * (p - 1) / p * n
    if op in ("reduce_scatter", "all_gather", "alltoall"):
        return (p - 1) / p * n
    if op == "broadcast":
        return n
    raise ValueError(f"unknown op {op!r}")


def allreduce_gbps(
    payload_bytes: float, num_devices: int, seconds: float
) -> float:
    """The BASELINE "allreduce GB/s" metric: algorithm bandwidth
    (payload / time — the MPI convention), NOT wire bandwidth."""
    del num_devices  # algorithm bandwidth is payload-relative
    return payload_bytes / seconds / 1e9


def modeled_allreduce_seconds(
    payload_bytes: float, num_devices: int, *, chip: ChipSpec = TPU_V5E
) -> float:
    """Ring-allreduce time model WITH per-hop latency — payload-sized.

    ``2·(P−1)`` ring steps (reduce-scatter + all-gather), each paying
    ``chip.ici_hop_latency``, plus the wire bytes at both-directions ICI
    bandwidth. The latency term is what makes the derived GB/s move
    with payload (small payloads are latency-bound, large ones approach
    the bandwidth ceiling) instead of the constant a latency-free model
    produces. Modeled, not measured — label it.

    Identity (pinned in tests): this equals
    ``modeled_reduce_scatter_seconds + modeled_all_gather_seconds`` at
    the same payload — the allreduce IS that composition (ISSUE 9), so
    the factored collectives reconcile against a model of the right
    shape instead of half an allreduce hand-wave.
    """
    p = num_devices
    if p <= 1:
        return 0.0
    wire = collective_bytes(payload_bytes, p, "allreduce")
    return 2.0 * (p - 1) * chip.ici_hop_latency + wire / (
        2.0 * chip.ici_bandwidth
    )


def _modeled_phase_seconds(
    payload_bytes: float, num_devices: int, op: str, chip: ChipSpec
) -> float:
    """One ring phase: ``P−1`` hops of latency + ``(P−1)/P·N`` wire at
    both-directions ICI bandwidth (every chip sends and receives
    simultaneously on a ring — the same assumption the allreduce model
    makes, so the phases sum EXACTLY to it)."""
    p = num_devices
    if p <= 1:
        return 0.0
    wire = collective_bytes(payload_bytes, p, op)
    return (p - 1) * chip.ici_hop_latency + wire / (2.0 * chip.ici_bandwidth)


def modeled_reduce_scatter_seconds(
    payload_bytes: float, num_devices: int, *, chip: ChipSpec = TPU_V5E
) -> float:
    """Ring reduce-scatter time model (ISSUE 9 satellite): the
    payload-sized model the factored ``ring_reduce_scatter`` reconciles
    against. ``payload_bytes`` is the bytes ON THE WIRE — quantized
    callers pass the int8-sized payload (``RingPlan.wire_payload_bytes``),
    never the logical one. Modeled, not measured — label it."""
    return _modeled_phase_seconds(
        payload_bytes, num_devices, "reduce_scatter", chip
    )


def modeled_all_gather_seconds(
    payload_bytes: float, num_devices: int, *, chip: ChipSpec = TPU_V5E
) -> float:
    """Ring all-gather time model — the other half of the allreduce
    composition (see :func:`modeled_reduce_scatter_seconds`)."""
    return _modeled_phase_seconds(
        payload_bytes, num_devices, "all_gather", chip
    )


def scaling_projection(
    step_seconds: float,
    items_per_step_per_chip: float,
    params: Any,
    *,
    chips: Sequence[int] = (8, 32, 64, 128, 256),
    slice_size: int = 256,
    zero1: bool = True,
    chip: ChipSpec = TPU_V5E,
    alltoall_payload_bytes: float = 0.0,
    alltoall_group: int = 0,
    alltoall_passes: int = 1,
) -> dict[str, Any]:
    """The BASELINE "scaling efficiency 8→256 chips" artifact — an
    ANALYTIC projection, labeled ``modeled`` (this environment has one
    chip; SURVEY.md §8.4.5 honest-accounting rule).

    Model (data-parallel weak scaling, fixed per-chip batch):

    - compute time per step = the MEASURED single-chip step time (grad
      compute + goo update are replicated work, constant under weak
      scaling; the measured number already includes the update).
    - comm time = the hierarchical gradient-sync model
      (:class:`CommModel`): ring allreduce inside a slice over ICI, plus
      a cross-slice DCN phase when ``n > slice_size`` (``num_slices =
      n / slice_size``; ``comm.init_hybrid`` is the matching runtime
      layout). Bandwidths are the chip's public peaks — a best-case wire
      model (no congestion/latency), stated in ``assumptions``.
    - two overlap assumptions bracket reality: ``no_overlap`` serializes
      compute and comm (the framework's plain step today);
      ``full_overlap`` hides comm under compute (the backward-pass
      bucketed-overlap limit), i.e. ``t = max(compute, comm)``.

    Efficiency is throughput per chip relative to the measured 1-chip
    run: ``eff_n = (items_n / t_n) / (n · items_1 / t_1)``.

    MoE/EP workloads (ISSUE 3 satellite): pass ``alltoall_payload_bytes``
    (per-chip routed-token bytes crossing the expert shuffle PER STEP,
    summed over every pass — dispatch + return, forward + backward, all
    MoE layers), ``alltoall_group`` (the expert-axis size the tokens
    shuffle across, clamped to the chip count), and ``alltoall_passes``
    (how many distinct all-to-alls that per-step total spans — each pass
    pays the ring-hop LATENCY separately; wire bytes are additive and
    don't care). The dispatch all-to-all sits on the layer's critical
    path — unlike grad sync it cannot hide under backward compute — so
    its modeled time (:func:`collective_bytes` ``alltoall`` wire +
    per-pass ring-hop latency) adds to BOTH overlap brackets. The
    1-chip measured step already contains the local no-op shuffle,
    which this model prices at 0.
    """
    points = []
    t1_throughput = items_per_step_per_chip / step_seconds
    for n in chips:
        num_slices = max(1, -(-n // slice_size))  # ceil
        if n % max(num_slices, 1):
            raise ValueError(f"{n} chips not divisible into {num_slices} slices")
        m = CommModel(params, n, zero1=zero1, num_slices=num_slices)
        t = m.grad_sync_seconds(chip)
        t_a2a = 0.0
        if alltoall_payload_bytes and alltoall_group > 1:
            g = min(alltoall_group, n)
            if g > 1:
                wire = collective_bytes(
                    alltoall_payload_bytes, g, "alltoall"
                )
                t_a2a = (
                    max(1, alltoall_passes) * (g - 1) * chip.ici_hop_latency
                    + wire / chip.ici_bandwidth
                )
        t_none = step_seconds + t["total_s"] + t_a2a
        t_full = max(step_seconds, t["total_s"]) + t_a2a
        thpt_none = n * items_per_step_per_chip / t_none
        thpt_full = n * items_per_step_per_chip / t_full
        point = {
            "chips": n,
            "num_slices": num_slices,
            "comm_ici_s": round(t["ici_s"], 6),
            "comm_dcn_s": round(t["dcn_s"], 6),
            "items_per_sec_no_overlap": round(thpt_none, 1),
            "items_per_sec_full_overlap": round(thpt_full, 1),
            "efficiency_no_overlap": round(thpt_none / (n * t1_throughput), 4),
            "efficiency_full_overlap": round(thpt_full / (n * t1_throughput), 4),
        }
        if alltoall_payload_bytes:
            point["comm_alltoall_s"] = round(t_a2a, 6)
        points.append(point)
    by_chips = {p["chips"]: p for p in points}
    out: dict[str, Any] = {
        "modeled": True,
        "assumptions": {
            "chip": chip.name,
            "ici_bandwidth_Bps": chip.ici_bandwidth,
            "dcn_bandwidth_Bps_per_chip": chip.dcn_bandwidth,
            "slice_size": slice_size,
            "weak_scaling": "fixed per-chip batch",
            "measured_step_seconds_1chip": round(step_seconds, 6),
            "wire_model": "bandwidth-optimal ring, zero latency/congestion",
        },
        "points": points,
    }
    if alltoall_payload_bytes:
        out["assumptions"]["alltoall_payload_bytes_per_chip_per_step"] = (
            float(alltoall_payload_bytes)
        )
        out["assumptions"]["alltoall_group"] = int(alltoall_group)
        out["assumptions"]["alltoall_passes_per_step"] = int(
            max(1, alltoall_passes)
        )
        out["assumptions"]["alltoall_model"] = (
            "ring alltoall (P-1)/P wire + per-pass per-hop latency, on "
            "the critical path (not overlappable)"
        )
    if 8 in by_chips and 256 in by_chips:
        # The headline: how much per-chip efficiency survives 8→256.
        out["efficiency_8_to_256_no_overlap"] = round(
            by_chips[256]["efficiency_no_overlap"]
            / by_chips[8]["efficiency_no_overlap"],
            4,
        )
        out["efficiency_8_to_256_full_overlap"] = round(
            by_chips[256]["efficiency_full_overlap"]
            / by_chips[8]["efficiency_full_overlap"],
            4,
        )
    return out


class CommModel:
    """Per-step communication accounting for a training config.

    Static model of what the SPMD step moves for gradient sync
    (allreduce, or reduce-scatter + all-gather under ZeRO-1) — so logs
    can report comm-bytes alongside measured step time (SURVEY.md §6
    metrics row).

    DCN awareness (SURVEY.md §3.4 transport: "ICI (intra-slice) and DCN
    (cross-slice)"): when ``num_slices > 1``, the data axis is laid out
    slice-major (``comm.init_hybrid``) and the allreduce decomposes
    hierarchically — intra-slice reduce-scatter/all-gather over ICI on
    ``num_devices / num_slices`` chips, plus a cross-slice phase over DCN
    on the slice-sharded 1/c fraction of the gradient. The phases are
    modeled separately so the DCN cliff is visible in scaling
    projections.
    """

    def __init__(
        self,
        params,
        num_devices: int,
        *,
        zero1: bool = True,
        num_slices: int = 1,
        wire_scale: float = 1.0,
    ):
        if num_slices > 1 and num_devices % num_slices:
            raise ValueError(
                f"{num_devices} devices not divisible into {num_slices} slices"
            )
        if wire_scale <= 0:
            raise ValueError(f"wire_scale must be positive, got {wire_scale}")
        self.param_bytes = tree_bytes(params)
        self.num_devices = num_devices
        self.zero1 = zero1
        self.num_slices = num_slices if num_devices > 1 else 1
        # Bytes-on-wire per logical payload byte (ISSUE 9): a quantized
        # gradient sync (grad_sync="ring_q8") ships int8 chunks — ¼ of
        # an f32 payload — and the modeled ICI accounting (roofline
        # attribution, P2P matrix reconciliation) must see the ACTUAL
        # wire size, not the logical one. GradSync.wire_scale() is the
        # matching source of this factor.
        self.wire_scale = float(wire_scale)

    def _phase_bytes(self, payload: float, p: int) -> float:
        """Per-chip wire bytes to allreduce ``payload`` over ``p`` ranks
        (2·(P−1)/P·N: ZeRO-1's RS+AG and the plain allreduce move the
        same total — they differ in where the optimizer runs, not in
        bytes), at the wire-scaled (possibly quantized) size."""
        return collective_bytes(payload * self.wire_scale, p, "allreduce")

    def grad_sync_bytes(self) -> float:
        """Total per-chip wire bytes (both phases; ICI + DCN)."""
        ici, dcn = self.grad_sync_bytes_by_tier()
        return ici + dcn

    def grad_sync_bytes_by_tier(self) -> tuple[float, float]:
        """Per-chip wire bytes split into (ICI, DCN) phases."""
        if self.num_devices <= 1:
            return 0.0, 0.0
        s = self.num_slices
        if s <= 1:
            return self._phase_bytes(self.param_bytes, self.num_devices), 0.0
        per_slice = self.num_devices // s
        intra = self._phase_bytes(self.param_bytes, per_slice)
        # Cross-slice phase: each of the per_slice shard groups allreduces
        # its 1/per_slice fraction across the s slice peers, over DCN.
        inter = self._phase_bytes(self.param_bytes / per_slice, s)
        return intra, inter

    def grad_sync_seconds(self, chip: ChipSpec = TPU_V5E) -> dict[str, float]:
        """Modeled time for the gradient sync (phases serialized —
        conservative; overlap assumptions belong to the caller and must
        be labeled)."""
        ici_b, dcn_b = self.grad_sync_bytes_by_tier()
        t_ici = ici_b / chip.ici_bandwidth
        t_dcn = dcn_b / chip.dcn_bandwidth
        return {
            "ici_s": t_ici,
            "dcn_s": t_dcn,
            "total_s": t_ici + t_dcn,
            "modeled": True,
        }

    def summary(self) -> dict[str, float]:
        ici_b, dcn_b = self.grad_sync_bytes_by_tier()
        out = {
            "param_bytes": float(self.param_bytes),
            "grad_sync_bytes_per_step": ici_b + dcn_b,
            "num_devices": self.num_devices,
        }
        if self.num_slices > 1:
            out["grad_sync_ici_bytes"] = ici_b
            out["grad_sync_dcn_bytes"] = dcn_b
            out["num_slices"] = self.num_slices
        return out
