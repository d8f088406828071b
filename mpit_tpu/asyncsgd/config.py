"""Config/flag system for the asyncsgd application layer.

The reference parses Lua option tables from the command line in its
``asyncsgd/`` scripts (``opt.lr``, ``opt.rank`` conventions; SURVEY.md §6
"Config / flag system") — deliberately lightweight. Matching that: each
workload is configured by a plain dataclass, and the argparse interface is
generated from its fields (``--lr 0.05 --steps 200 --mesh data=4,model=2``).
No heavyweight config framework.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Mapping, Type, TypeVar

T = TypeVar("T")


@dataclasses.dataclass
class TrainConfig:
    """Options shared by every workload script (the ``opt`` table analogue).

    ``mode`` selects the execution model:

    - ``"spmd"`` (default): the TPU-native path — one jitted SPMD step over
      the mesh, goo state sharded when ``zero1`` (the north-star collapse of
      the pserver/pclient protocol).
    - ``"parity"``: the reference-shaped path — 1 parameter-server rank +
      ``nranks-1`` client ranks exchanging tagged messages on the
      :mod:`mpit_tpu.compat` simulator (the ``mpirun -n P`` analogue), for
      semantics/parity work, not performance.
    - ``"elastic"``: the robustness tier (ISSUE 11; ``train/elastic.py``)
      — 1 anchor server + ``nranks-1`` replicas each running the async
      ``hardened_loop`` with EASGD anchor exchanges, heartbeat/lease
      liveness, divergence quarantine, and crash/rejoin recovery over
      per-replica crash-consistent checkpoints (``--ckpt-dir`` enables
      them; ``--ckpt-every`` sets the cadence).
    """

    mode: str = "spmd"  # spmd | parity
    steps: int = 200
    batch_size: int = 64  # global (split across data-parallel devices/clients)
    lr: float = 0.05
    momentum: float = 0.9
    weight_decay: float = 0.0
    # LR schedule (opt/schedules.py): "" = constant (the reference's
    # behavior), "warmup", "warmup_cosine", "step". Warmup fixes the
    # documented AlexNet lr-0.01 divergence (BENCHMARKS.md).
    schedule: str = ""
    warmup_steps: int = 0
    lr_end_scale: float = 0.0  # warmup_cosine: final lr as a fraction of lr
    decay_every: int = 0  # step schedule: decay period
    decay_factor: float = 0.1  # step schedule: decay multiplier
    # Decay horizon for warmup_cosine (0 = this run's --steps). Pin it
    # explicitly when resuming with a different --steps, or the restored
    # GooState.count lands on a reshaped LR curve (RECOVERY.md).
    schedule_horizon: int = 0
    zero1: bool = True  # shard goo state across the data axis (SPMD mode)
    # Gradient-sync wire tier (ISSUE 9; train/grad_sync.py):
    # "psum" = stock XLA collectives (default, seed behavior);
    # "ring" = in-kernel Pallas ring reduce-scatter/all-gather, issued
    # per grad bucket (same sums in ring order: bitwise psum on the CPU
    # fallback, reduction-order noise on chips);
    # "ring_q8" = the ring with the int8 quantized wire (per-chunk
    # scales, ~1/4 the wire bytes) — LOSSY: trajectory differs from
    # f32 sync by design (loss-curve-pinned within noise), so resuming
    # a psum/ring checkpoint under ring_q8 (or back) changes the
    # trajectory like any lossy knob would.
    grad_sync: str = "psum"  # psum | ring | ring_q8
    grad_bucket_mb: float = 4.0  # ring tiers: bucket size (MB of f32)
    easgd: bool = False  # elastic-averaging dynamics instead of Downpour
    easgd_alpha: float = 0.125
    # Elastic tier (mode=elastic): alpha = easgd_beta / N_active when
    # easgd_beta > 0 (the paper's β = N·α spelling — eviction gracefully
    # reshapes the denominator); 0 keeps the fixed easgd_alpha coupling.
    easgd_beta: float = 0.0
    sync_every: int = 1  # parity mode: client steps between server exchanges
    nranks: int = 2  # parity/elastic: 1 server + (nranks-1) clients/replicas
    # Elastic-tier liveness/staleness knobs (train/elastic.py): a
    # replica silent past lease_s is evicted from the averaging
    # denominator; an anchor pull more than staleness_bound center
    # versions stale is flagged (anchor_staleness_exceeded), not fatal.
    lease_s: float = 1.0
    heartbeat_s: float = 0.1
    staleness_bound: int = 8
    mesh: str = ""  # SPMD mesh, e.g. "data=4,model=2"; "" = all-data
    native: bool = False  # C++ data-pipeline core (falls back if unbuilt)
    data_dir: str = ""  # on-disk dataset (data/filedata.py); "" = synthetic
    log_every: int = 50
    profile_dir: str = ""  # capture a jax.profiler trace of steps 2..5
    ckpt_dir: str = ""  # orbax checkpoint directory ("" = no checkpoints)
    ckpt_every: int = 0
    # Elastic rescale via the geometry-free dense .npz (train/convert.py):
    # --save-dense writes it at run end (preemption drain included);
    # --resume-dense restores it onto the CURRENT mesh — any data-axis
    # size, ZeRO-1 shards re-cut. Unlike --ckpt-dir (geometry-pinned
    # in-place resume), this is the preempt -> restore-on-fewer-chips path.
    save_dense: str = ""
    resume_dense: str = ""
    eval_batch: int = 256
    # Periodic full-val-split evaluation (top-1/top-5 sweep): every N
    # steps, iterate the whole val split (runner.run_spmd eval hook);
    # 0 = single held-out-batch eval at the end only.
    eval_every: int = 0
    eval_batches: int = 0  # cap the sweep (0 = full split; synthetic: 8)
    # Input augmentation for the classification pipelines
    # (data/augment.py). The 58% top-1 north star is unreachable
    # without it. --augment-mode shift: random shift-crop (crop_pad) +
    # hflip (MNIST-grade); rrc: random-resized-crop with scale/aspect
    # jitter (ImageNet-grade), training at --train-size (0 = stored
    # image size) with center-cropped eval.
    augment: bool = False
    augment_mode: str = "shift"  # shift | rrc
    crop_pad: int = 4
    train_size: int = 0
    rrc_min_scale: float = 0.08  # min crop-area fraction for rrc
    max_restores: int = 1  # checkpoint restores after a diverged loss
    spike_factor: float = 0.0  # >0: treat loss > factor*EMA as divergence
    # Host-path pipelining (ISSUE 2; train/loop.py + data/loader.py).
    # Perf knobs, not trajectory geometry: deliberately NOT pinned by
    # run_meta — a resume may change them freely.
    fetch_lag: int = 2  # async metric-fetch window, fences (0 = sync)
    # Host-stage threads in the prefetch pipeline. NOTE: parallelism
    # applies to work the loop hands the host stage as a
    # ``host_transform`` (hardened_loop kwarg); the asyncsgd datasets
    # currently do their decode inside the stream iterator (serialized
    # by the source lock), so >1 only helps callers that pass one —
    # moving the datasets' decode/augment into host_transform is the
    # follow-up that makes this knob bite for the imagenet path.
    prefetch_workers: int = 1
    prefetch_depth: int = 2  # staged device batches (floor)
    # Adaptive ceiling: the pipeline grows its device buffer toward this
    # while the loop observably starves on input (each unit = one staged
    # device batch of HBM). Set equal to prefetch_depth to disable.
    prefetch_max_depth: int = 8
    # Step-time anomaly sentinel (ISSUE 3; obs/sentinel.py): a rolling
    # median/MAD detector over step wall / prefetch wait / host fences
    # that emits structured `anomaly` events and a run-end report —
    # DivergenceGuard for throughput. Off by default (zero overhead).
    sentinel: bool = False
    seed: int = 0

    def mesh_shape(self) -> dict[str, int] | None:
        return parse_mesh(self.mesh)


def parse_mesh(mesh: str) -> dict[str, int] | None:
    """Parse ``"data=4,model=2"`` → ``{"data": 4, "model": 2}`` (shared
    by every config dataclass carrying a ``mesh`` flag; ``""`` → None)."""
    if not mesh:
        return None
    out: dict[str, int] = {}
    for part in mesh.split(","):
        k, _, v = part.partition("=")
        out[k.strip()] = int(v)
    return out


def _str2bool(v: str) -> bool:
    if v.lower() in ("1", "true", "yes", "on"):
        return True
    if v.lower() in ("0", "false", "no", "off"):
        return False
    raise argparse.ArgumentTypeError(f"expected a boolean, got {v!r}")


def add_dataclass_args(parser: argparse.ArgumentParser, cls: Type[Any]) -> None:
    """Add one ``--flag`` per dataclass field (bools accept true/false)."""
    for f in dataclasses.fields(cls):
        name = "--" + f.name.replace("_", "-")
        default = (
            f.default
            if f.default is not dataclasses.MISSING
            else f.default_factory()  # type: ignore[misc]
        )
        typ = _str2bool if f.type in (bool, "bool") else type(default)
        parser.add_argument(name, type=typ, default=default, help=f"({default})")


def from_argv(
    cls: Type[T],
    argv: list[str] | None = None,
    *,
    prog: str | None = None,
    overrides: Mapping[str, Any] | None = None,
) -> T:
    """Build a config dataclass from CLI args (+ programmatic overrides)."""
    parser = argparse.ArgumentParser(prog=prog, description=cls.__doc__)
    add_dataclass_args(parser, cls)
    ns = parser.parse_args(argv)
    kw = {f.name: getattr(ns, f.name) for f in dataclasses.fields(cls)}
    if overrides:
        kw.update(overrides)
    return cls(**kw)
