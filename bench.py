"""Benchmark entry point — prints ONE compact JSON line for the driver.

Headline metric (BASELINE.json): AlexNet ImageNet images/sec, measured on
the real SPMD training step (fwd/bwd/goo update, ZeRO-1 sharded state) on
whatever devices are available. Secondary metrics ride in ``detail``:
GPT-2 tokens/sec (the stretch config), ResNet-50 images/sec, the EP-tier
MoE tokens/sec, GPT-2 serving decode tokens/sec + request-latency
p50/p95 on the continuous-batching engine (``mpit_tpu.serve``, ISSUE 4),
and — when >1 device is present — measured allreduce GB/s (modeled
otherwise, labeled as such; SURVEY.md §8.4.5).

Driver contract (round-5 hardening — the round-3 record outgrew the
driver's 2,000-char tail buffer and the round-4 run outgrew its time
budget, so BOTH contract dimensions are now budgeted explicitly):

* **Line budget.** The printed line carries headline value + per-workload
  essentials only and is pinned < 1,500 chars by a unit test
  (``tests/test_bench_contract.py``; target ≤ 1,200). Everything bulky —
  scaling projections, comm-model assumptions, drop-rate lists — goes to
  ``BENCH_DETAIL.json`` next to this file, which the line references.
* **Time budget.** (a) The persistent XLA compilation cache is enabled
  (``mpit_tpu.utils.compile_cache_dir``: where ``JAX_COMPILATION_CACHE_DIR``
  says, else ``.jax_cache/``), so a rerun that finds the cache skips the
  multi-minute compiles an earlier run paid for. (b) Workloads
  run headline-first. (c) An elapsed-time budget (``MPIT_BENCH_BUDGET_S``,
  default 420 s) is checked before each workload; once exceeded, the rest
  are skipped and recorded under ``"truncated"``. (d) A daemon-thread
  watchdog 20% past the soft budget force-prints the record-so-far and
  exits 0 (a thread, not SIGALRM: it fires even while the main thread
  is blocked in a GIL-releasing native call — compile or device fetch).
* **Progressive emission.** The record line is (re)printed after EVERY
  completed workload — each print is a complete, parseable, compact
  record of everything measured so far (later workloads listed in
  ``"pending"``). If the driver kills the process anyway, the last
  complete line is still inside its tail window. Only the final line
  lacks a ``"pending"`` key.

Timing methodology: each timed window ends by fetching a *host value*
derived from the final step (``float(loss)``), which waits for the whole
dependency chain.

Dispatch amortization: steps run in scanned chunks of K inside one
compiled call (``make_train_step(scan_steps=K)``): every step still
executes fully on device over distinct pre-staged batches; the wall
clock is real; only the host round-trips between steps are gone. The
app-path (one dispatch per step) cross-check is reported alongside and
is the headline (round-3 verdict item 10).

App-path gap (ISSUE 2): for the workloads with an app-path cross-check
(AlexNet, GPT-2) the same single-dispatch step also runs under the
production ``hardened_loop`` over the same pre-staged batches;
``app_path_overhead_pct`` = 1 − hardened/raw rides the record line, and
the obs span attribution for exactly that window
(``gap_attribution``) goes to BENCH_DETAIL.json — so the loop's host-
path tax is a first-class, regression-pinned metric rather than an
anecdote.

``vs_baseline``: the reference publishes no benchmark numbers
(BASELINE.json ``"published": {}``; see BASELINE.md), so per the round-1
verdict the *round-1 recorded values* are the cross-round baseline —
``vs_baseline`` is the ratio to the round-1 recorded constants.
"""

from __future__ import annotations

import functools
import json
import os
import threading
import time

_REPO = os.path.dirname(os.path.abspath(__file__))


def _enable_compile_cache() -> None:
    """Persistent XLA compile cache — MUST run before the first trace.

    Called from :func:`main`, NOT at import: tests import this module
    for the record builder, and enabling a process-global cache as an
    import side effect poisoned the whole test process (cache entries
    written by a different jaxlib/backend deserialize into executables
    the host backend crashes on — observed as a segfault in the first
    jitted train step of any test that ran after an `import bench`).
    """
    import jax

    from mpit_tpu.utils import compile_cache_dir

    compile_cache_dir()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _timed_steps(step_fn, state, batches, n):
    """Run n chunk-calls alternating pre-staged (stacked) batches; returns
    (dt, loss, state). The window closes on a host-value fetch (see module
    docstring)."""
    from mpit_tpu import obs

    with obs.span("timed_window", calls=n):
        t0 = time.perf_counter()
        metrics = {}
        for i in range(n):
            state, metrics = step_fn(state, batches[i % 2])
        loss = float(metrics["loss"])  # forces completion of the whole chain
        return time.perf_counter() - t0, loss, state


def _best_window(step_fn, state, batches, steps, repeats=3):
    """Best-of-N timed windows: the fastest window stands for the
    uncontended machine (the host's cores are shared)."""
    best_dt, loss = float("inf"), float("nan")
    for _ in range(repeats):
        dt, loss, state = _timed_steps(step_fn, state, batches, steps)
        best_dt = min(best_dt, dt)
    return best_dt, loss, state


def _measure(step_fn, state, batches, *, calls, scan_steps, warmup):
    """The shared timed-run scaffold (warmup, then best-of-N windows):
    every bench measures through this one path so the methodology cannot
    drift between workloads. Returns ``(dt, steps, final_loss, state)``."""
    from mpit_tpu import obs

    with obs.span("warmup", calls=warmup):
        _, _, state = _timed_steps(step_fn, state, batches, warmup)
    dt, final_loss, state = _best_window(step_fn, state, batches, calls)
    return dt, calls * scan_steps, final_loss, state


def _hardened_gap(
    world, app_step_fn, state, device_batches, *, items, raw_rate,
    steps=24, log_every=4,
):
    """The app-path gap, measured (ISSUE 2 tentpole): run the SAME
    single-dispatch step under the production ``hardened_loop`` over the
    same pre-staged device batches (``transform`` = identity, so no host
    input work rides along) and compare its steady-state items/sec with
    the raw best-window rate. ``app_path_overhead_pct`` is the loop's
    own host-path tax — fences, guard, logging, prefetch plumbing — the
    async metric pipeline (train/loop.py ``fetch_lag``) exists to close.
    The obs span attribution for exactly this window rides along
    (``gap_attribution``), so BENCH_DETAIL.json shows WHERE the
    remaining overhead sits, not just how big it is."""
    from mpit_tpu import obs
    from mpit_tpu.train.loop import hardened_loop
    from mpit_tpu.train.metrics import MetricLogger

    def cycle():
        i = 0
        while True:
            yield device_batches[i % 2]
            i += 1

    rec = obs.get_recorder()
    n0 = rec.event_count() if rec else 0
    with obs.span("hardened_loop", steps=steps):
        out = hardened_loop(
            world,
            state,
            app_step_fn,
            cycle(),
            steps=int(state.step) + steps,
            items_per_batch=items,
            log_every=log_every,
            logger=MetricLogger(stdout=False),
            transform=lambda b: b,  # batches are already placed
        )
    res = {"hardened_items_per_sec": out.get("items_per_sec")}
    if res["hardened_items_per_sec"] and raw_rate:
        res["app_path_overhead_pct"] = round(
            100.0 * (1.0 - res["hardened_items_per_sec"] / raw_rate), 2
        )
    if rec is not None:
        res["gap_attribution"] = obs.gap_attribution(rec.summary(since=n0))
    return res, out["state"]


def _roofline_block(step_fn, args, step_seconds, *, steps_per_call=1,
                    ici_bytes=0.0, phase="step"):
    """Measured-vs-modeled utilization for one training step (ISSUE 8):
    the step's ``cost_analysis()`` FLOPs/bytes (one extra AOT compile —
    a persistent-cache replay of HLO the run already compiled), divided
    down to per-step, registered with the workload's recorder under
    ``phase`` (so BENCH_DETAIL's obs_baseline carries the per-phase
    roofline table), and reconciled against the MEASURED step time.
    Returns ``(block, mfu_pct)`` — percentages only on TPU; off-chip
    the block records modeled cost + platform, never a fabricated MFU.
    ``ici_bytes``: modeled per-step gradient-sync wire bytes at the
    REAL device count (0 on one chip — never a hypothetical pod's)."""
    from mpit_tpu import obs
    from mpit_tpu.obs import roofline as R
    from mpit_tpu.utils import TPU_V5E, roofline as roofline_model

    platform = jax.devices()[0].platform
    try:
        with obs.span("roofline_cost"):
            cost = R.cost_from_fn(step_fn, *args)
    except Exception as e:
        return (
            {"error": f"{type(e).__name__}: {e}"[:160],
             "platform": platform},
            None,
        )
    flops = cost["flops"] / steps_per_call
    hbm = cost["hbm_bytes"] / steps_per_call
    R.register_cost(
        phase, flops=flops, hbm_bytes=hbm, ici_bytes=ici_bytes,
        platform=platform,
    )
    block = {
        "flops_per_step": flops,
        "hbm_bytes_per_step": hbm,
        "ici_bytes_per_step_modeled": ici_bytes,
        "arithmetic_intensity": round(flops / hbm, 2) if hbm else None,
        "measured_step_seconds": round(step_seconds, 6),
        "platform": platform,
        "chip": TPU_V5E.name,
    }
    mfu = None
    if flops or hbm:
        model = roofline_model(flops, hbm, ici_bytes=ici_bytes)
        block["roofline_step_seconds_lower_bound"] = round(
            model["seconds_lower_bound"], 6
        )
        block["bound_modeled"] = model["bound"]
        if platform == "tpu" and step_seconds > 0:
            util = R.utilization(
                {"flops": flops, "hbm_bytes": hbm, "ici_bytes": ici_bytes},
                step_seconds, platform=platform,
                peaks=R.chip_peaks(platform=platform),
            )
            block.update({
                k: util[k]
                for k in ("mfu_pct", "hbm_util_pct", "ici_util_pct")
                if k in util
            })
            block["fraction_of_roofline"] = round(
                block["roofline_step_seconds_lower_bound"] / step_seconds, 4
            )
            mfu = block.get("mfu_pct")
    return block, mfu


def _stack_batches(world, stream, k: int, spec=None):
    """Stage k distinct batches on device as one [k, ...]-stacked chunk."""
    import numpy as np

    from mpit_tpu import obs
    from mpit_tpu.data import shard_batch

    with obs.span("staging", batches=k):
        host = [next(stream) for _ in range(k)]
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *host)
        return shard_batch(world, stacked, spec=spec)


def _device_image_batches(
    world, *, global_batch, hw, classes, spec, k=None, seed=0
):
    """Synthetic image batches generated ON DEVICE (jitted jax.random with
    explicit output shardings).

    Round-5 time-budget fix: host-generating AlexNet-sized batches
    staged ~7 GB over the host link per bench run, which no compile
    cache helps. The timed window is
    input-INDEPENDENT dense compute (it starts after staging), so the
    pixels' provenance doesn't touch the measurement; uniform pixels +
    random labels on device replace the host stream. ``k``: stack depth
    for the scanned path (None = single unstacked batch).
    """
    from jax.sharding import NamedSharding

    from mpit_tpu import obs

    lead = () if k is None else (k,)
    out_shardings = {
        "image": NamedSharding(world.mesh, spec),
        "label": NamedSharding(world.mesh, spec),
    }

    @functools.partial(jax.jit, out_shardings=out_shardings)
    def gen(key):
        ki, kl = jax.random.split(key)
        return {
            "image": jax.random.uniform(
                ki, (*lead, global_batch, hw, hw, 3), jnp.float32
            ),
            "label": jax.random.randint(
                kl, (*lead, global_batch), 0, classes, jnp.int32
            ),
        }

    with obs.span("staging", on_device=True):
        return gen(jax.random.key(seed))


def bench_alexnet(
    batch_per_device: int = 2048,
    calls: int = 4,
    scan_steps: int = 2,
    warmup: int = 1,
):
    """AlexNet headline metric. Round-2 tuning: batch 2048 (512→2048
    measured 18.0k→22.2k img/s, ~52% MFU by the BENCHMARKS.md accounting;
    4096 exceeds what the chip's HBM can stage double-buffered)."""
    import mpit_tpu
    from jax.sharding import PartitionSpec as P
    from mpit_tpu import opt as gopt
    from mpit_tpu.models import AlexNet
    from mpit_tpu.train import make_train_step
    from mpit_tpu.utils import CommModel

    world = mpit_tpu.init()
    n = world.num_devices
    global_batch = batch_per_device * n

    model = AlexNet(num_classes=1000)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 224, 224, 3), jnp.float32)
    )["params"]

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["image"])
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(
            jnp.take_along_axis(logp, batch["label"][:, None], axis=1)
        )
        return loss, {}

    init_fn, step_fn, _ = make_train_step(
        loss_fn, gopt.goo(0.01, 0.9), world, zero1=True, scan_steps=scan_steps
    )
    state = init_fn(params)

    # Two pre-staged stacked chunks (scan_steps distinct batches each),
    # alternated, so no step can be served from a cached/identical-input
    # artifact; successive steps still chain through the state dependency.
    # Batches are generated ON DEVICE (_device_image_batches) — round 5
    # removed the multi-GB host→device staging that dominated the bench's
    # wall clock.
    batches = [
        _device_image_batches(
            world, global_batch=global_batch, hw=224, classes=1000,
            spec=P(None, "data"), k=scan_steps, seed=i,
        )
        for i in range(2)
    ]

    dt, steps, final_loss, state = _measure(
        step_fn, state, batches, calls=calls, scan_steps=scan_steps,
        warmup=warmup,
    )

    # App-path cross-check (round-2 verdict "what's weak" #6): the same
    # step WITHOUT scan-chunking — one host dispatch per step, the shape
    # the application loop actually runs. The gap vs the scanned number
    # is per-dispatch host cost, not device time; reported so the
    # headline can't silently hide an app-path regression.
    _, app_step_fn, _ = make_train_step(
        loss_fn, gopt.goo(0.01, 0.9), world, zero1=True
    )
    single = [
        _device_image_batches(
            world, global_batch=global_batch, hw=224, classes=1000,
            spec=P("data"), seed=10 + i,
        )
        for i in range(2)
    ]
    _, _, state = _timed_steps(app_step_fn, state, single, 1)  # compile
    app_dt, _, state = _best_window(app_step_fn, state, single, 4)
    app_rate = round(global_batch * 4 / app_dt, 2)

    # The production-loop cross-check (ISSUE 2): same app-path step,
    # driven by hardened_loop — the overhead between the two is the
    # loop's own host path, now pipelined (train/loop.py fetch_lag).
    gap, state = _hardened_gap(
        world, app_step_fn, state, single,
        items=global_batch, raw_rate=app_rate,
    )

    comm = CommModel(params, n, zero1=True)
    # Utilization flight data (ISSUE 8): cost_analysis of the SAME
    # app-path step the headline measures, reconciled against its
    # measured per-step wall. mfu_pct rides the record line (None
    # off-TPU — platform-labeled, never fabricated).
    rb, mfu = _roofline_block(
        app_step_fn, (state, single[0]), app_dt / 4,
        ici_bytes=comm.grad_sync_bytes(),
    )
    return {
        "images_per_sec": round(global_batch * steps / dt, 2),
        "ms_per_step": round(dt / steps * 1e3, 2),
        "app_path_images_per_sec": app_rate,
        "mfu_pct": mfu,
        "global_batch": global_batch,
        "batch_per_device": batch_per_device,
        "steps": steps,
        "scan_steps": scan_steps,
        "final_loss": round(final_loss, 4),
        "grad_sync_bytes_per_step_modeled": comm.grad_sync_bytes(),
        "scaling": _scaling(dt / steps, batch_per_device, params),
        "roofline": rb,
        **gap,
    }


def _scaling(step_seconds, items_per_chip, params, **kw):
    """The BASELINE 8→256 scaling-efficiency artifact (analytic, labeled
    ``modeled``; utils/profiling.scaling_projection). Two topologies:
    ``single_slice`` (up to 256 chips of ICI — one v5e pod) and
    ``slice64`` (64-chip slices joined by DCN — the cross-slice cliff).
    Detail-file-only: these blobs are what overflowed the driver's tail
    buffer in round 3. Extra kwargs (the MoE alltoall terms) pass
    through to scaling_projection."""
    from mpit_tpu.utils import scaling_projection

    return {
        "single_slice": scaling_projection(
            step_seconds, items_per_chip, params, slice_size=256, **kw
        ),
        "slice64": scaling_projection(
            step_seconds, items_per_chip, params, slice_size=64, **kw
        ),
    }


def moe_alltoall_payload(cfg, moe, batch_per_device: int, seq: int) -> float:
    """Per-chip routed-token bytes crossing the expert all-to-all per
    STEP (modeled; the scaling projection's ISSUE 3 satellite input):
    each MoE layer shuffles ~k slots per local token, d_model bf16 each,
    over ``moe_alltoall_passes`` distinct all-to-alls."""
    local_tokens = batch_per_device * seq
    return moe_alltoall_passes(cfg, moe) * moe.k * local_tokens \
        * cfg.d_model * 2.0


def moe_alltoall_passes(cfg, moe) -> int:
    """Distinct all-to-alls per step: dispatch + return, forward +
    backward (4), per MoE layer — each pays ring-hop latency separately
    in the scaling model."""
    return 4 * (cfg.num_layers // moe.every)


def bench_resnet(
    batch_per_device: int = 256,
    calls: int = 3,
    scan_steps: int = 2,
    warmup: int = 1,
):
    """ResNet-50 — baseline config #4 (sync allreduce + ZeRO-1 sharded
    goo, BatchNorm riding the stateful step; bf16 conv path). Batch
    sweep on the real chip (round 3): 64→1220, 128→1401, 256→1718,
    512→1753 img/s — 256 is the knee; 512 doubles activation memory
    for +2%. Round 4 (models/resnet.py levers, measured): bf16 BN
    output 1778→2279 img/s (+28% — the f32 normalized activations were
    doubling every block's elementwise HBM traffic), space-to-depth stem
    →2299; batch 512 re-swept, still flat. Remaining gap attributed by
    trace in BENCHMARKS.md."""
    import mpit_tpu
    from jax.sharding import PartitionSpec as P
    from mpit_tpu import opt as gopt
    from mpit_tpu.models import ResNet50
    from mpit_tpu.train import make_train_step

    world = mpit_tpu.init()
    n = world.num_devices
    global_batch = batch_per_device * n

    model = ResNet50(num_classes=1000)
    variables = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((2, 224, 224, 3), jnp.float32)
    )
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(p, stats, batch):
        logits, mutated = model.apply(
            {"params": p, "batch_stats": stats},
            batch["image"],
            mutable=["batch_stats"],
        )
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(
            jnp.take_along_axis(logp, batch["label"][:, None], axis=1)
        )
        return loss, {}, mutated["batch_stats"]

    init_fn, step_fn, _ = make_train_step(
        loss_fn,
        gopt.goo(0.1, 0.9, weight_decay=1e-4),
        world,
        zero1=True,
        stateful=True,
        scan_steps=scan_steps,
    )
    state = init_fn(params, batch_stats)
    batches = [
        _device_image_batches(
            world, global_batch=global_batch, hw=224, classes=1000,
            spec=P(None, "data"), k=scan_steps, seed=i,
        )
        for i in range(2)
    ]

    dt, steps, final_loss, state = _measure(
        step_fn, state, batches, calls=calls, scan_steps=scan_steps,
        warmup=warmup,
    )
    from mpit_tpu.utils import CommModel

    # No app-path variant here: the scanned chunk's cost divides down
    # to per-step (every step inside the scan executes fully).
    rb, mfu = _roofline_block(
        step_fn, (state, batches[0]), dt / steps,
        steps_per_call=scan_steps,
        ici_bytes=CommModel(params, n, zero1=True).grad_sync_bytes(),
    )
    return {
        "images_per_sec": round(global_batch * steps / dt, 2),
        "ms_per_step": round(dt / steps * 1e3, 2),
        "mfu_pct": mfu,
        "global_batch": global_batch,
        "batch_per_device": batch_per_device,
        "steps": steps,
        "scan_steps": scan_steps,
        "final_loss": round(final_loss, 4),
        "scaling": _scaling(dt / steps, batch_per_device, params),
        "roofline": rb,
    }


def bench_gpt2(calls: int = 3, scan_steps: int = 8, warmup: int = 1, seq: int = 512):
    """GPT-2 stretch config: tokens/sec on the shard_map+ZeRO-1 tier.

    Round-2 tuning (all measured on the real chip, see BENCHMARKS.md):
    batch per device 32→48, bf16 head operands with the fused streaming
    LM-head loss (the [B,T,50257] f32 logits array is never
    materialized, ``ops/lm_head.py``). Round 3: the Pallas flash kernel
    now WINS at T=512 (94.4→60 GB/step HBM traffic; the round-2 loss was
    128-block tiles + f32 matmul operands — retuned to 512-blocks with
    bf16 operands/f32 accumulation it measures 110.5k vs XLA's 99.1k
    tok/s), so it is the default on TPU from T=512 up. Round 4
    (trace-driven, BENCHMARKS.md): head-packed flash layout (no q/k/v
    transposes) + unrolled LM-head vocab loops → 127.0–130.3k tok/s.
    Round 5: batch re-sweep — 48→132.5k @56 / 132.2k @64 (plateau),
    119.4k @80 (HBM pressure), compile-OOM @96; 56 is the new default
    (50.0% MFU; the remaining gap is the documented D=64/LM-head bound,
    BENCHMARKS.md §GPT-2 ceiling).
    """
    import mpit_tpu
    from jax.sharding import PartitionSpec as P
    from mpit_tpu.data import SyntheticLM
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.train import make_train_step

    world = mpit_tpu.init()
    n = world.num_devices
    batch = 56 * n
    on_tpu = jax.devices()[0].platform == "tpu"

    kw = dict(max_seq_len=seq, head_dtype=jnp.bfloat16)
    attention = "xla"
    if on_tpu and seq >= 512:
        from mpit_tpu.ops import flash_attention

        kw["attention_fn"] = flash_attention
        attention = "pallas-flash"
    cfg = GPT2Config.small(**kw)
    model = GPT2(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, seq), jnp.int32)
    )["params"]

    def loss_fn(p, b):
        return GPT2.fused_loss_fn(model, p, b["tokens"]), {}

    init_fn, step_fn, _ = make_train_step(
        loss_fn, goo_adam(3e-4), world, zero1=True, scan_steps=scan_steps
    )
    state = init_fn(params)
    stream = SyntheticLM(vocab_size=cfg.vocab_size).batches(batch, seq)
    batches = [
        _stack_batches(world, stream, scan_steps, spec=P(None, "data"))
        for _ in range(2)
    ]

    dt, steps, final_loss, state = _measure(
        step_fn, state, batches, calls=calls, scan_steps=scan_steps,
        warmup=warmup,
    )

    # App-path cross-check (round-3 verdict item 10): the same step with
    # one host dispatch per step — what the application loop delivers.
    from mpit_tpu.data import shard_batch

    _, app_step_fn, _ = make_train_step(
        loss_fn, goo_adam(3e-4), world, zero1=True
    )
    single = [
        shard_batch(world, next(stream)),
        shard_batch(world, next(stream)),
    ]
    _, _, state = _timed_steps(app_step_fn, state, single, 1)  # compile
    app_dt, _, state = _best_window(app_step_fn, state, single, 4)
    app_rate = round(batch * seq * 4 / app_dt, 1)

    gap, state = _hardened_gap(
        world, app_step_fn, state, single,
        items=batch * seq, raw_rate=app_rate,
    )

    from mpit_tpu.utils import CommModel

    rb, mfu = _roofline_block(
        app_step_fn, (state, single[0]), app_dt / 4,
        ici_bytes=CommModel(params, n, zero1=True).grad_sync_bytes(),
    )
    return {
        "tokens_per_sec": round(batch * seq * steps / dt, 1),
        "app_path_tokens_per_sec": app_rate,
        "mfu_pct": mfu,
        "ms_per_step": round(dt / steps * 1e3, 2),
        "batch": batch,
        "seq_len": seq,
        "scan_steps": scan_steps,
        "attention": attention,
        "final_loss": round(final_loss, 4),
        "scaling": _scaling(dt / steps, (batch // n) * seq, params),
        "roofline": rb,
        **gap,
    }


def bench_moe(calls: int = 4, warmup: int = 1, seq: int = 512, batch_per_device: int = 32):
    """GPT-2-MoE throughput on the EP TIER ITSELF (round-3 verdict item
    4): ``parallel/ep.py``'s train step — routed dispatch, capacity
    drops, per-placement-group flat ravel, and ZeRO-1 ON (the round-3
    tile-pad compile-OOM is fixed by opt/sharded.py's barrier-fenced
    lane-aligned layout, verified at this exact 322M shape by
    ``compile_multichip.py``). One chip = ``data=1, expert=1`` mesh; the
    all-to-all is a local no-op, everything else is the pod code path.
    8 experts, top-2, cf=1.25, MoE every 2nd block. Dispatch/drop stats
    come from the model's sown ``dispatch_stats`` on a probe forward
    (high drop rates are expected here: the router is at random init).

    Round 5: the sort (ragged scatter/gather) dispatch replaced the
    one-hot einsum as the default — the [S, E, C] tensors that OOMed
    B=32/T=512 on the 16 GB chip (round-4 cap at B=16) no longer exist,
    so the tier now measures at B=32 (parallel/moe.py docstring).
    """
    import mpit_tpu
    from jax.sharding import PartitionSpec as P
    from mpit_tpu.data import SyntheticLM, shard_batch
    from mpit_tpu.models import GPT2Config
    from mpit_tpu.models.gpt2_moe import GPT2MoE, MoESettings
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.parallel import make_gpt2_moe_train_step

    n = jax.device_count()
    world = mpit_tpu.init({"data": n, "expert": 1})
    batch = batch_per_device * n
    zero1 = True

    kw = dict(max_seq_len=seq, head_dtype=jnp.bfloat16)
    if jax.devices()[0].platform == "tpu" and seq >= 512:
        # Same rule as bench_gpt2: the Pallas flash kernel from T=512 up.
        # Round 5: without it the XLA attention saves [B,H,T,T] scores
        # for backward (~2.4 GB at B=32/T=512) — the other half of the
        # B=32 memory story next to the sort dispatch + expert remat.
        from mpit_tpu.ops import flash_attention

        kw["attention_fn"] = flash_attention
    cfg = GPT2Config.small(**kw)
    moe = MoESettings(num_experts=8, k=2, capacity_factor=1.25, every=2)
    model = GPT2MoE(cfg, moe)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, seq), jnp.int32)
    )["params"]

    init_fn, step_fn, _ = make_gpt2_moe_train_step(
        cfg, moe, goo_adam(3e-4), world, zero1=zero1
    )
    state = init_fn(params)
    stream = SyntheticLM(vocab_size=cfg.vocab_size).batches(batch, seq)
    batches = [
        shard_batch(world, next(stream), spec=P(("data", "expert")))
        for _ in range(2)
    ]
    # App-path measurement (one dispatch per step — the EP tier has no
    # scan chunking; the tier step is heavy enough to amortize the
    # per-dispatch cost). Shared best-of-N scaffold, so the
    # methodology cannot drift between workloads.
    _, _, state = _timed_steps(step_fn, state, batches, 1)  # compile
    steps = 4
    dt, final_loss, state = _best_window(
        step_fn, state, batches, steps, repeats=max(calls - warmup, 1)
    )

    # Routing observability: drop rate / expert load on a probe forward
    # (mutable intermediates; never part of the timed window).
    probe = jnp.asarray(next(stream)["tokens"][: max(batch // 4, 1), :-1])
    probe_fn = jax.jit(
        lambda p, t: model.apply(
            {"params": p}, t, mutable=["intermediates"]
        )
    )

    def _drops(params):
        _, inter = probe_fn(params, probe)
        return [
            float(v)
            for k, v in jax.tree_util.tree_flatten_with_path(
                inter["intermediates"]
            )[0]
            if "drop_rate" in jax.tree_util.keystr(k) and v.ndim == 0
        ]

    drops = _drops(state.params)

    # Load-balance under training (ISSUE 3 satellite): keep training the
    # SAME state ~48 more steps, sampling the per-layer drop rate — the
    # aux loss should pull the random-init 36–64% down materially. Each
    # sample rides obs.gauge so the trajectory lands in the workload's
    # telemetry too; the list goes to BENCH_DETAIL.json (detail-only).
    from mpit_tpu import obs

    trajectory = [{"step": 0, "drop_rate_per_moe_layer":
                   [round(d, 4) for d in drops]}]
    probe_every, probe_steps = 12, 48
    with obs.span("moe_load_balance_probe", steps=probe_steps):
        for s in range(1, probe_steps + 1):
            state, _m = step_fn(state, batches[s % 2])
            if s % probe_every == 0:
                ds = _drops(state.params)
                for li, d in enumerate(ds):
                    obs.gauge("moe_drop_rate", d, layer=li, step=s)
                trajectory.append(
                    {"step": s,
                     "drop_rate_per_moe_layer": [round(d, 4) for d in ds]}
                )

    from mpit_tpu.utils import CommModel

    rb, mfu = _roofline_block(
        step_fn, (state, batches[0]), dt / steps,
        ici_bytes=CommModel(params, n, zero1=zero1).grad_sync_bytes(),
    )
    return {
        "tokens_per_sec": round(batch * seq * steps / dt, 1),
        "ms_per_step": round(dt / steps * 1e3, 2),
        "mfu_pct": mfu,
        "roofline": rb,
        "tier": "ep",
        "dispatch": moe.dispatch,
        "batch": batch,
        "seq_len": seq,
        "experts": moe.num_experts,
        "k": moe.k,
        "capacity_factor": moe.capacity_factor,
        "zero1": zero1,
        "drop_rate_per_moe_layer": [round(d, 4) for d in drops],
        "drop_rate_trajectory": trajectory,
        "final_loss": round(final_loss, 4),
        # The scaling block the round-5 verdict flagged as missing
        # (next-round #6): grad-sync model PLUS the expert all-to-all
        # (collective_bytes "alltoall" wired into scaling_projection).
        "scaling": _scaling(
            dt / steps, batch_per_device * seq, params,
            alltoall_payload_bytes=moe_alltoall_payload(
                cfg, moe, batch_per_device, seq
            ),
            alltoall_group=moe.num_experts,
            alltoall_passes=moe_alltoall_passes(cfg, moe),
        ),
    }


def _serve_stream(
    cfg, params, *, slots, max_len, prompt_len, max_new, requests,
    decode_attention, seed=0,
):
    """One measured request stream through a fresh engine: warmup runs
    ONE request first so the two compiles (prefill + decode — the
    engine's whole compiled surface) never land inside a measured
    request's TTFT/latency; then the engine resets (cache cleared,
    compiled steps kept) and the stream is measured cold-queue: all
    requests submitted up front, so queue-wait and slot-reuse are
    exercised (admissions > slots)."""
    import numpy as np

    from mpit_tpu import obs
    from mpit_tpu.serve import Engine, Request, Server, warm_engine

    engine = Engine(
        cfg, params, slots=slots, max_len=max_len, prefill_len=prompt_len,
        decode_attention=decode_attention,
    )
    rng = np.random.RandomState(seed)
    make_req = lambda i: Request(
        rid=i,
        prompt=rng.randint(0, cfg.vocab_size, size=prompt_len).tolist(),
        max_new_tokens=max_new,
    )
    # warm_engine spans itself as `warmup` (ISSUE 8 satellite) and
    # registers the steps' cost_analysis costs for the roofline roll-up.
    warm_engine(engine, register_costs=True)

    server = Server(engine)
    for i in range(requests):
        server.submit(make_req(i))
    rec = obs.get_recorder()
    n0 = rec.event_count() if rec else 0
    t0 = time.perf_counter()
    server.run()
    wall = time.perf_counter() - t0
    stats = server.stats()
    gen = stats["generated_tokens"]
    # Each request's FIRST token is sampled by prefill; only the rest are
    # decode-path work, so they alone ride the decode-phase denominator.
    decode_tokens = gen - stats["requests_completed"]
    decode_s = wall
    if rec is not None:
        phases = rec.summary(since=n0)["phases"]
        decode_s = phases.get("decode", {}).get("total_s", wall)
    return engine, stats, wall, decode_tokens, decode_s, gen


def _paged_capacity_block(page_size: int = 16):
    """Paged-vs-dense capacity at a FIXED HBM budget (ISSUE 7's pinned
    win). Budget = the dense engine's cache rows (``slots × max_len``);
    the paged pool gets exactly that many rows (``budget/page_size``
    pages) and a wide slot batch (batch width is host arrays + FLOPs,
    not HBM). The stream: page-aligned shared prefix + short tail, short
    generations — tokens actually held per request ≈ 28 of the dense
    path's 128-row reservation, so concurrency stops scaling with
    ``slots × max_len`` and starts scaling with tokens held (and shared
    prefix pages are stored once). Reports measured peak concurrency +
    decode tokens/s for both engines at identical traffic.
    """
    import numpy as np

    from mpit_tpu import obs
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.serve import Engine, Request, Server, warm_engine

    dense_slots, max_len = 4, 128
    budget_rows = dense_slots * max_len  # the HBM the dense cache burns
    num_pages = budget_rows // page_size
    paged_slots = dense_slots * 8
    prefix_len, tail, max_new = page_size, 4, 8
    n_requests = paged_slots + dense_slots * 4

    cfg = GPT2Config.tiny(max_seq_len=max_len)
    params = jax.jit(GPT2(cfg).init)(
        jax.random.key(2), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    rng = np.random.RandomState(3)
    prefix = rng.randint(0, cfg.vocab_size, size=prefix_len).tolist()
    reqs = [
        Request(
            rid=i,
            prompt=prefix
            + rng.randint(0, cfg.vocab_size, size=tail).tolist(),
            max_new_tokens=max_new,
        )
        for i in range(n_requests)
    ]

    def _measure(engine):
        warm_engine(engine)
        server = Server(engine)
        t0 = time.perf_counter()
        # Prime the prefix index before the wave: sharing requires a
        # REGISTERED prefix (registration happens when a prefill
        # completes — same-tick co-admissions are cold by design), so
        # the first request runs two ticks alone. The dense engine gets
        # the identical schedule, so the A/B traffic stays equal.
        server.submit(reqs[0])
        server.run(max_ticks=2)
        for r in reqs[1:]:
            server.submit(r)
        server.run()
        wall = time.perf_counter() - t0
        st = server.stats()
        dtok = st["generated_tokens"] - st["requests_completed"]
        return st, dtok / wall if wall else None

    with obs.span("paged_capacity"):
        d_stats, d_tps = _measure(
            Engine(cfg, params, slots=dense_slots, max_len=max_len,
                   prefill_len=prefix_len + tail)
        )
        p_stats, p_tps = _measure(
            Engine(cfg, params, slots=paged_slots, max_len=max_len,
                   prefill_len=prefix_len + tail,
                   kv_pages=num_pages, kv_page_size=page_size)
        )
    return {
        "hbm_budget_rows": budget_rows,
        "page_size": page_size,
        "request_shape": {"prefix_len": prefix_len, "tail": tail,
                          "max_new": max_new, "requests": n_requests},
        "dense": {
            "slots": dense_slots,
            "max_concurrent": d_stats["concurrency_peak"],
            "decode_tokens_per_sec": round(d_tps, 1) if d_tps else None,
        },
        "paged": {
            "slots": paged_slots,
            "pages": num_pages,
            "max_concurrent": p_stats["concurrency_peak"],
            "decode_tokens_per_sec": round(p_tps, 1) if p_tps else None,
            "pool_occupancy_peak": p_stats["kv_pool_occupancy_peak"],
            "prefix_hit_rate": p_stats["prefix_hit_rate"],
            "pages_shared_peak": p_stats["prefix_pages_shared_peak"],
            "cow_copies": p_stats["kv_cow_copies"],
        },
        "concurrency_ratio": round(
            p_stats["concurrency_peak"]
            / max(d_stats["concurrency_peak"], 1),
            2,
        ),
    }


def _chunked_prefill_block(prefill_chunk: int = 32):
    """Chunked-prefill TTFT under the mixed-length open-loop harness
    (ISSUE 7): the SAME seeded arrival trace (80% short interactive
    prompts, 20% long batch prompts) driven through the paged engine
    with whole-prompt prefills vs ``prefill_chunk``-token slices.

    The long admits are what head-of-line-blocks INTERACTIVE TTFT;
    chunking bounds any tick's prefill work, so the interactive class's
    p95 TTFT is the headline improvement. The long requests' own TTFT
    rises (their prompt now lands over several ticks with decode
    interleaved — that is the trade chunking makes, and why overall
    p95, which sits inside the 20% long class, can move the other way);
    both classes' percentiles are recorded so the trade is explicit.
    """
    import numpy as np

    from mpit_tpu import obs
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.serve import (
        Engine,
        LoadSpec,
        RequestClass,
        Server,
        generate_arrivals,
        warm_engine,
    )

    prefill_len, max_len = 256, 320
    cfg = GPT2Config.tiny(max_seq_len=max_len)
    params = jax.jit(GPT2(cfg).init)(
        jax.random.key(4), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    mix = (
        RequestClass("interactive", weight=0.8, prompt_len=(2, 10),
                     max_new_tokens=(2, 6)),
        RequestClass("batch", weight=0.2,
                     prompt_len=(prefill_len - 64, prefill_len),
                     max_new_tokens=(2, 6)),
    )
    duration = 2.5

    def _measure(chunk):
        engine = Engine(
            cfg, params, slots=4, max_len=max_len,
            prefill_len=prefill_len, kv_pages=96, kv_page_size=16,
            prefill_chunk=chunk,
        )
        warm_engine(engine)
        # Rate calibrated roughly to CPU tiny-model tick cost; the A/B
        # shares ONE trace, so the absolute rate only sets pressure.
        arrivals = generate_arrivals(
            LoadSpec(rate=14.0, classes=mix),
            vocab_size=cfg.vocab_size, duration_s=duration, seed=11,
        )
        server = Server(engine)
        server.run_timed(arrivals, duration=duration, drain=True)
        by_class = {a.request.rid: a.klass for a in arrivals}
        ttft = np.asarray([c.ttft_s for c in server.completed])
        inter = np.asarray(
            [c.ttft_s for c in server.completed
             if by_class[c.rid] == "interactive"]
        )
        batch_t = np.asarray(
            [c.ttft_s for c in server.completed
             if by_class[c.rid] == "batch"]
        )
        pct = lambda a, q: (
            round(float(np.percentile(a, q)), 6) if a.size else None
        )
        return {
            "completed": len(server.completed),
            "ttft_p50_s": pct(ttft, 50),
            "ttft_p95_s": pct(ttft, 95),
            "interactive_ttft_p50_s": pct(inter, 50),
            "interactive_ttft_p95_s": pct(inter, 95),
            "batch_ttft_p95_s": pct(batch_t, 95),
        }

    with obs.span("chunked_prefill_ab"):
        unchunked = _measure(None)
        chunked = _measure(prefill_chunk)
    u, c = (unchunked["interactive_ttft_p95_s"],
            chunked["interactive_ttft_p95_s"])
    imp = (u - c) / u if u and c is not None else None
    return {
        "geometry": {"slots": 4, "prefill_len": prefill_len,
                     "prefill_chunk": prefill_chunk, "kv_pages": 96,
                     "kv_page_size": 16, "duration_s": duration,
                     "rate": 14.0},
        "unchunked": unchunked,
        "chunked": chunked,
        "interactive_ttft_p95_improvement_pct": round(100 * imp, 1)
        if imp is not None
        else None,
    }


def _speculative_block(
    spec_k: int = 3, draft_layers: int = 1, contexts: tuple = (16, 48),
    train_steps: int = 300,
):
    """Speculative-decode A/B (ISSUE 13): the SAME seeded request trace
    through the same engine geometry, spec on vs off, at acceptance
    rates the trace ACTUALLY ACHIEVES — both ends of the bracket:

    - ``trained``: target (4 layers) and draft (``draft_layers``)
      trained to convergence on a memorizable synthetic stream, the
      regime speculation exists for (the draft genuinely predicts the
      target — greedy continuations agree, acceptance is high, and the
      tokens/s improvement is real);
    - ``random_draft``: the same geometry with a random-init target and
      its layer-truncated self-draft (``serve.weights.
      draft_from_target``) — the floor: near-zero acceptance, so every
      tick pays draft + verify for ~1 token and speculation LOSES.
      Recording the loss is the point; a draft that cannot predict the
      target should never be shipped, and the bench must say what that
      costs rather than hide it.

    On CPU these are acceptance/tokens-per-tick/relative-cost facts
    with honest wall clocks — never a chip-speedup claim (the record's
    top-level platform label governs, per BENCHMARKS.md discipline).
    Reduced geometry (vocab 256, d_model 128) keeps the block inside
    the bench budget; the A/B signal is relative cost at achieved
    acceptance, not an absolute rate — geometry rides the entry."""
    import dataclasses

    import numpy as np
    import optax

    from mpit_tpu import obs
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.opt.goo import goo_adam
    from mpit_tpu.serve import (
        Engine,
        Request,
        Server,
        draft_from_target,
        warm_engine,
    )

    cfg = GPT2Config(
        vocab_size=256, max_seq_len=128, num_layers=4, num_heads=4,
        d_model=128, head_dtype=jnp.bfloat16,
    )
    dcfg = dataclasses.replace(cfg, num_layers=draft_layers)
    slots, max_new, requests = 4, 12, 8
    rng = np.random.RandomState(17)
    # The memorizable stream: one fixed token sequence; every prompt is
    # a prefix of it, so the trained pair's greedy continuations are
    # the stream itself — the high-agreement regime.
    stream = rng.randint(0, cfg.vocab_size, size=96).tolist()
    batch = jnp.asarray([stream[:65]], jnp.int32)

    def _train(mcfg, seed):
        model = GPT2(mcfg)
        params = jax.jit(model.init)(
            jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        opt = goo_adam(3e-3)
        state = opt.init(params)

        @jax.jit
        def step(params, state):
            loss, grads = jax.value_and_grad(
                lambda p: GPT2.fused_loss_fn(model, p, batch)
            )(params)
            updates, state = opt.update(grads, state, params)
            return optax.apply_updates(params, updates), state, loss

        loss = None
        for _ in range(train_steps):
            params, state, loss = step(params, state)
        return params, float(loss)

    rec = obs.get_recorder()

    def _measure_pair(tparams, dparams, draft_cfg):
        plain = Engine(cfg, tparams, slots=slots, max_len=128,
                       prefill_len=max(contexts))
        spec = Engine(cfg, tparams, slots=slots, max_len=128,
                      prefill_len=max(contexts), spec_k=spec_k,
                      draft_params=dparams, draft_cfg=draft_cfg)
        warm_engine(plain)
        warm_engine(spec)

        def _stream_run(engine, ctx):
            engine.reset()
            server = Server(engine)
            for i in range(requests):
                plen = ctx - (i % 3)  # same trace both ways, mild skew
                server.submit(Request(
                    rid=i, prompt=stream[:plen], max_new_tokens=max_new,
                ))
            n0 = rec.event_count() if rec else 0
            t0 = time.perf_counter()
            server.run()
            wall = time.perf_counter() - t0
            st = server.stats()
            dtok = st["generated_tokens"] - st["requests_completed"]
            ds = wall
            if rec is not None:
                ph = rec.summary(since=n0)["phases"]
                ds = ph.get("decode", {}).get("total_s", wall)
            return st, (dtok / ds if ds else None)

        points = []
        for ctx in contexts:
            p_st, p_tps = _stream_run(plain, ctx)
            s_st, s_tps = _stream_run(spec, ctx)
            points.append({
                "context_len": ctx,
                "decode_tokens_per_sec": (
                    round(p_tps, 1) if p_tps else None
                ),
                "spec_decode_tokens_per_sec": (
                    round(s_tps, 1) if s_tps else None
                ),
                "spec_speedup": (
                    round(s_tps / p_tps, 3) if p_tps and s_tps else None
                ),
                "accepted_tokens_per_tick": s_st.get(
                    "accepted_tokens_per_tick"
                ),
                "draft_acceptance_rate": s_st.get(
                    "draft_acceptance_rate"
                ),
                "ttft_p95_delta_s": (
                    round(s_st["ttft_p95_s"] - p_st["ttft_p95_s"], 6)
                    if "ttft_p95_s" in s_st and "ttft_p95_s" in p_st
                    else None
                ),
            })
        return points

    with obs.span("speculative_ab"):
        tparams, t_loss = _train(cfg, seed=5)
        dparams_t, d_loss = _train(dcfg, seed=6)
        trained_points = _measure_pair(tparams, dparams_t, dcfg)
        rnd = jax.jit(GPT2(cfg).init)(
            jax.random.key(7), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        rnd_draft, rnd_dcfg = draft_from_target(rnd, cfg, draft_layers)
        random_points = _measure_pair(rnd, rnd_draft, rnd_dcfg)
    att = [p["accepted_tokens_per_tick"] for p in trained_points
           if p["accepted_tokens_per_tick"] is not None]
    return {
        "geometry": dict(
            vocab=cfg.vocab_size, d_model=cfg.d_model,
            num_layers=cfg.num_layers, slots=slots, max_len=128,
            max_new=max_new, requests=requests, spec_k=spec_k,
            draft_layers=draft_layers, train_steps=train_steps,
        ),
        "trained": {
            "target_final_loss": round(t_loss, 4),
            "draft_final_loss": round(d_loss, 4),
            "points": trained_points,
        },
        "random_draft": {"points": random_points},
        "accepted_tokens_per_tick": (
            round(sum(att) / len(att), 4) if att else None
        ),
    }


def _train_tiny_lm(mcfg, batch, train_steps: int, seed: int):
    """Memorize ``batch`` on a fresh tiny GPT-2 — the trained-checkpoint
    regime the quantized-cache/weights quality gates run in (a random
    init would make every agreement gate vacuous). Shared by the
    ISSUE 15 KV block and the ISSUE 17 weights block so the two
    batteries gate the same kind of checkpoint. Returns
    ``(params, final_loss)``."""
    import optax

    from mpit_tpu.models import GPT2
    from mpit_tpu.opt.goo import goo_adam

    model = GPT2(mcfg)
    params = jax.jit(model.init)(
        jax.random.key(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    opt = goo_adam(3e-3)
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(
            lambda p: GPT2.fused_loss_fn(model, p, batch)
        )(params)
        updates, state = opt.update(grads, state, params)
        return optax.apply_updates(params, updates), state, loss

    loss = None
    for _ in range(train_steps):
        params, state, loss = step(params, state)
    return params, float(loss)


def _greedy_stream_run(engine, rec, stream_toks, slots, prompt_len,
                       max_new):
    """One seeded greedy trace: prompts are prefixes of the memorized
    stream (mild length skew), one warm + measured run. Returns
    ``(stats, decode_tokens_per_sec, {rid: tokens})`` — decode tok/s
    from the recorder's decode-phase seconds when available (whole-run
    wall otherwise)."""
    from mpit_tpu.serve import Request, Server, warm_engine

    warm_engine(engine)
    server = Server(engine)
    for i in range(slots):
        plen = prompt_len - (i % 3)
        server.submit(Request(
            rid=i, prompt=stream_toks[:plen], max_new_tokens=max_new,
        ))
    n0 = rec.event_count() if rec else 0
    t0 = time.perf_counter()
    server.run()
    wall = time.perf_counter() - t0
    st = server.stats()
    dtok = st["generated_tokens"] - st["requests_completed"]
    ds = wall
    if rec is not None:
        ph = rec.summary(since=n0)["phases"]
        ds = ph.get("decode", {}).get("total_s", wall)
    outs = {c.rid: c.tokens for c in server.completed}
    return st, (dtok / ds if ds else None), outs


def _quantized_kv_block(train_steps: int = 300, page_size: int = 16):
    """Quantized int8 KV cache A/B + capacity sweep + quality gates
    (ISSUE 15). One head_dim-64 config (the GPT-2 head geometry — the
    byte-ratio claims are head_dim-dependent) serves four sub-blocks:

    - ``ab``: the SAME seeded stream through identical paged engines at
      kv_dtype bf16 vs int8 — measured decode tokens/s (CPU wall,
      platform-labeled, never a chip claim) plus the MODELED
      bytes-per-tick ratios at the stream's lengths: the KV-sweep-only
      ratio (``q8_kv_sweep_ratio`` — the term quantization shrinks;
      int8+scales vs bf16 rows at identical visited tiles) and the
      total ratio including the dtype-independent param read, recorded
      next to it so the tiny-model param share is explicit, not hidden.
    - ``capacity``: the SAME pool HBM byte budget spent on bf16 pages
      vs int8 pages (page counts from the shared
      ``kv_wire_bytes_per_row`` sizing rule), identical traffic —
      measured peak concurrency both ways; ``q8_capacity_ratio`` is
      the headline (admission granularity means the measured ratio can
      sit above the raw row-bytes ratio; both are recorded).
    - ``quality``: gates on a TRAINED checkpoint (the regime a serving
      cache lives in), deltas recorded not assumed — max per-token
      logit error of the int8 cache vs the f32-cache oracle (+ its
      anti-vacuity twin: the error must be nonzero, lossy must
      actually execute), and greedy-output agreement vs the f32-cache
      engine over the stream (bf16 agreement alongside as context).
    - ``speculative``: acceptance-rate neutrality — the trained target
      + its layer-truncated draft, spec_k=3, quantized both pools vs
      unquantized; the acceptance delta is the recorded gate.
    """
    import numpy as np

    from mpit_tpu import obs
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.serve import (
        Engine,
        Request,
        Server,
        alloc_cache,
        draft_from_target,
        kv_wire_bytes_per_row,
        warm_engine,
    )

    cfg = GPT2Config(
        vocab_size=256, max_seq_len=192, num_layers=2, num_heads=4,
        d_model=256, head_dtype=jnp.bfloat16,
    )
    slots, prompt_len, max_new, max_len = 8, 64, 16, 96
    rng = np.random.RandomState(23)
    stream_toks = rng.randint(0, cfg.vocab_size, size=160).tolist()
    batch = jnp.asarray([stream_toks[:129]], jnp.int32)

    rec = obs.get_recorder()

    def _stream_run(engine):
        return _greedy_stream_run(
            engine, rec, stream_toks, slots, prompt_len, max_new
        )

    def _paged(params, kv_dtype, pages, n_slots=slots):
        return Engine(
            cfg, params, slots=n_slots, max_len=max_len,
            prefill_len=prompt_len, kv_pages=pages,
            kv_page_size=page_size, kv_dtype=kv_dtype,
        )

    with obs.span("quantized_kv_ab"):
        tparams, t_loss = _train_tiny_lm(cfg, batch, train_steps, seed=5)

        # -- A/B at identical geometry --------------------------------------
        pages_ab = slots * (max_len // page_size)
        ab = {}
        engines = {}
        for dt in ("f32", "bf16", "int8"):
            eng = _paged(tparams, dt, pages_ab)
            st, tps, outs = _stream_run(eng)
            engines[dt] = (eng, outs)
            ab[dt] = {
                "decode_tokens_per_sec": round(tps, 1) if tps else None,
                "decode_hbm_bytes_modeled": st.get(
                    "decode_hbm_bytes_modeled"
                ),
            }
        # Modeled bytes at the stream's lengths (deterministic: every
        # engine ran the same schedule): one representative tick with
        # all slots at their final fills, KV sweep only vs total.
        lens = np.asarray(
            [prompt_len - (i % 3) + max_new - 1 for i in range(slots)]
        )
        kv_only = {
            dt: engines[dt][0].decode_achieved_hbm_bytes(
                lens, include_params=False
            )
            for dt in engines
        }
        total = {
            dt: engines[dt][0].decode_achieved_hbm_bytes(lens)
            for dt in engines
        }
        ab["q8_kv_sweep_ratio_vs_bf16"] = round(
            kv_only["int8"] / kv_only["bf16"], 4
        )
        ab["q8_kv_sweep_ratio_vs_f32"] = round(
            kv_only["int8"] / kv_only["f32"], 4
        )
        # The tiny bench model's param read dominates a CPU-sized tick;
        # the total ratio records that share honestly instead of letting
        # the sweep ratio imply a whole-tick 2x on this geometry.
        ab["q8_total_bytes_ratio_vs_bf16"] = round(
            total["int8"] / total["bf16"], 4
        )
        ab["kv_row_bytes"] = {
            dt: kv_wire_bytes_per_row(
                cfg.num_heads, cfg.head_dim,
                "int8" if dt == "int8" else
                (jnp.float32 if dt == "f32" else jnp.bfloat16),
            )
            for dt in ("f32", "bf16", "int8")
        }

        # -- capacity at a FIXED pool HBM budget ----------------------------
        row = ab["kv_row_bytes"]
        pages_bf16 = 24
        budget_bytes = pages_bf16 * page_size * row["bf16"]
        pages_int8 = int(budget_bytes // (page_size * row["int8"]))
        cap_slots, cap_requests = 16, 30
        crng = np.random.RandomState(29)
        cap_reqs = [
            Request(
                rid=i,
                prompt=crng.randint(
                    0, cfg.vocab_size, size=prompt_len
                ).tolist(),
                max_new_tokens=max_new,
            )
            for i in range(cap_requests)
        ]

        def _capacity(kv_dtype, pages):
            eng = _paged(tparams, kv_dtype, pages, n_slots=cap_slots)
            warm_engine(eng)
            server = Server(eng)
            for r in cap_reqs:
                server.submit(r)
            t0 = time.perf_counter()
            server.run()
            wall = time.perf_counter() - t0
            st = server.stats()
            dtok = st["generated_tokens"] - st["requests_completed"]
            return {
                "pages": pages,
                "max_concurrent": st["concurrency_peak"],
                "pool_occupancy_peak": st["kv_pool_occupancy_peak"],
                "decode_tokens_per_sec": (
                    round(dtok / wall, 1) if wall else None
                ),
            }

        cap_bf = _capacity("bf16", pages_bf16)
        cap_i8 = _capacity("int8", pages_int8)
        capacity = {
            "pool_budget_bytes": int(budget_bytes),
            "page_size": page_size,
            "request_shape": {
                "prompt_len": prompt_len, "max_new": max_new,
                "pages_per_request": -(-(prompt_len + max_new - 1)
                                       // page_size),
                "requests": cap_requests, "slots": cap_slots,
            },
            "bf16": cap_bf,
            "int8": cap_i8,
            # Measured-concurrency ratio; the raw row-bytes ratio sits
            # beside it (admission is page-granular, so the measured
            # figure can exceed it — both recorded, neither fabricated).
            "q8_capacity_ratio": round(
                cap_i8["max_concurrent"] / max(cap_bf["max_concurrent"], 1),
                2,
            ),
            "row_bytes_ratio_bf16_over_int8": round(
                row["bf16"] / row["int8"], 4
            ),
        }

        # -- quality gates on the trained checkpoint ------------------------
        # Per-token logit error vs the f32-cache oracle: one padded
        # prefill over stream prefixes through an f32 cache and an int8
        # cache, same params, logits compared at every real position.
        model = GPT2(cfg)
        q_slots, q_len = 4, prompt_len
        padded = np.zeros((q_slots, q_len), np.int32)
        for i in range(q_slots):
            padded[i, : q_len - i] = stream_toks[: q_len - i]
        c_f32 = alloc_cache(cfg, slots=q_slots, max_len=q_len,
                            dtype=jnp.float32)
        c_i8 = alloc_cache(cfg, slots=q_slots, max_len=q_len,
                           quantized=True)
        lf, _ = model.apply(
            {"params": tparams}, jnp.asarray(padded),
            cache=(c_f32.k, c_f32.v, c_f32.lengths),
        )
        lq, _ = model.apply(
            {"params": tparams}, jnp.asarray(padded),
            cache=(c_i8.k, c_i8.v, c_i8.lengths),
        )
        # Positional mask: row i holds q_len - i real tokens. (Token id
        # 0 is a valid vocab id — a value mask would silently drop the
        # real positions holding it from the error measurement.)
        mask = (
            np.arange(q_len)[None, :]
            < (q_len - np.arange(q_slots))[:, None]
        )
        delta = np.abs(np.asarray(lf, np.float32)
                       - np.asarray(lq, np.float32))[mask]
        agree = {}
        f32_outs = engines["f32"][1]
        for dt in ("bf16", "int8"):
            outs = engines[dt][1]
            same = sum(
                t == r
                for rid in f32_outs
                for t, r in zip(outs[rid], f32_outs[rid])
            )
            total_toks = sum(len(v) for v in f32_outs.values())
            agree[dt] = round(same / total_toks, 4)
        quality = {
            "target_final_loss": round(t_loss, 4),
            "logit_abs_err_max": round(float(delta.max()), 5),
            "logit_abs_err_mean": round(float(delta.mean()), 6),
            # Anti-vacuity: zero error would mean the lossy path never
            # executed — the gates below would be vacuously green.
            "logit_err_nonzero": bool(delta.max() > 0),
            "greedy_agreement_vs_f32": agree,
        }

        # -- speculative acceptance neutrality ------------------------------
        dparams, dcfg = draft_from_target(tparams, cfg, 1)
        spec_acc = {}
        for dt in (None, "int8"):
            eng = Engine(
                cfg, tparams, slots=slots, max_len=128,
                prefill_len=prompt_len, spec_k=3,
                draft_params=dparams, draft_cfg=dcfg, kv_dtype=dt,
            )
            st, _tps, _outs = _stream_run(eng)
            spec_acc[dt or "bf16"] = {
                "draft_acceptance_rate": st.get("draft_acceptance_rate"),
                "accepted_tokens_per_tick": st.get(
                    "accepted_tokens_per_tick"
                ),
            }
        a0 = spec_acc["bf16"]["draft_acceptance_rate"]
        a8 = spec_acc["int8"]["draft_acceptance_rate"]
        spec = {
            **spec_acc,
            "acceptance_delta": (
                round(a8 - a0, 4) if a0 is not None and a8 is not None
                else None
            ),
        }

    return {
        "geometry": dict(
            vocab=cfg.vocab_size, d_model=cfg.d_model,
            num_layers=cfg.num_layers, num_heads=cfg.num_heads,
            head_dim=cfg.head_dim, slots=slots, max_len=max_len,
            prompt_len=prompt_len, max_new=max_new,
            page_size=page_size, train_steps=train_steps,
        ),
        "ab": ab,
        "capacity": capacity,
        "quality": quality,
        "speculative_neutrality": spec,
        "q8_capacity_ratio": capacity["q8_capacity_ratio"],
        "q8_kv_sweep_ratio": ab["q8_kv_sweep_ratio_vs_bf16"],
    }


def _quantized_weights_block(train_steps: int = 300, page_size: int = 16):
    """Quantized int8 weight store A/B + capacity + quality gates
    (ISSUE 17). The KV block's honesty note is this block's premise: at
    serving batch sizes the PARAM read dominates the decode tick
    (``q8_total_bytes_ratio_vs_bf16`` ≈ 0.92 — the cache is the
    sliver), so the weights are where the bytes are. Four sub-blocks on
    one trained checkpoint:

    - ``ab``: the SAME seeded stream through identical dense engines at
      weights_dtype f32 vs int8 — measured decode tokens/s (CPU wall,
      platform-labeled, never a chip claim) plus the MODELED whole-tick
      decode-bytes ratio at the stream's lengths (``q8w_bytes_ratio``,
      the record-line headline: param read + KV sweep, each at its
      actual wire dtype — the ratio credits quantization with exactly
      the term it shrinks, diluted by the sweep it does not touch) and
      the param-read / wire ratios from the shared
      ``weight_wire_bytes`` sizing rule.
    - ``capacity``: the SAME total HBM budget (param store + KV pool)
      spent with f32 vs int8 weights — freed param bytes convert to KV
      pages; measured peak concurrency both ways. On this tiny geometry
      the int8 page grant is slot-capped; the uncapped modeled grant is
      recorded next to the granted one — neither fabricated.
    - ``quality``: gates on the TRAINED checkpoint — max per-token
      logit error of the int8-weight forward vs the f32-weight oracle
      through the SAME f32 cache (+ anti-vacuity: the error must be
      nonzero, the lossy path must actually execute), and greedy
      agreement vs the f32-weight engine over the stream.
    - ``speculative``: acceptance neutrality with int8 weights on BOTH
      draft and target (the engine quantizes the draft store too) vs
      the unquantized pair; the acceptance delta is the recorded gate.
    """
    import numpy as np

    from mpit_tpu import obs
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.serve import (
        Engine,
        Request,
        Server,
        alloc_cache,
        draft_from_target,
        kv_wire_bytes_per_row,
        params_wire_bytes,
        quantize_gpt2_params,
        warm_engine,
    )

    cfg = GPT2Config(
        vocab_size=256, max_seq_len=192, num_layers=2, num_heads=4,
        d_model=256, head_dtype=jnp.bfloat16,
    )
    slots, prompt_len, max_new, max_len = 8, 64, 16, 96
    rng = np.random.RandomState(31)
    stream_toks = rng.randint(0, cfg.vocab_size, size=160).tolist()
    batch = jnp.asarray([stream_toks[:129]], jnp.int32)
    rec = obs.get_recorder()

    def _stream_run(engine):
        return _greedy_stream_run(
            engine, rec, stream_toks, slots, prompt_len, max_new
        )

    with obs.span("quantized_weights_ab"):
        tparams, t_loss = _train_tiny_lm(cfg, batch, train_steps, seed=7)
        # The shared sizing rule (``weight_wire_bytes`` under
        # ``params_wire_bytes``): what each param store occupies on the
        # wire — int8 payload + per-row f32 scales vs dense f32.
        pw = {
            "f32": params_wire_bytes(tparams),
            "int8": params_wire_bytes(quantize_gpt2_params(tparams)),
        }

        # -- A/B at identical geometry --------------------------------------
        ab = {}
        engines = {}
        for dt in ("f32", "int8"):
            eng = Engine(
                cfg, tparams, slots=slots, max_len=max_len,
                prefill_len=prompt_len, weights_dtype=dt,
            )
            st, tps, outs = _stream_run(eng)
            engines[dt] = (eng, outs)
            ab[dt] = {
                "decode_tokens_per_sec": round(tps, 1) if tps else None,
                "decode_hbm_bytes_modeled": st.get(
                    "decode_hbm_bytes_modeled"
                ),
                "param_wire_bytes": pw[dt],
            }
        # Modeled bytes for one representative tick (all slots at their
        # final fills — deterministic, every engine ran the same
        # schedule): whole tick and the param read it contains.
        lens = np.asarray(
            [prompt_len - (i % 3) + max_new - 1 for i in range(slots)]
        )
        total = {
            dt: engines[dt][0].decode_achieved_hbm_bytes(lens)
            for dt in engines
        }
        kv_sweep = {
            dt: engines[dt][0].decode_achieved_hbm_bytes(
                lens, include_params=False
            )
            for dt in engines
        }
        param_read = {dt: total[dt] - kv_sweep[dt] for dt in engines}
        ab["q8w_bytes_ratio"] = round(total["int8"] / total["f32"], 4)
        ab["q8w_param_read_ratio"] = round(
            param_read["int8"] / param_read["f32"], 4
        )
        ab["param_wire_ratio"] = round(pw["int8"] / pw["f32"], 4)
        # The KV block's honesty note, inverted: how much of the f32
        # tick the param read IS on this geometry — here the dominant
        # term is the one being shrunk.
        ab["param_share_of_f32_tick"] = round(
            param_read["f32"] / total["f32"], 4
        )

        # -- capacity at a FIXED total HBM budget (params + pool) -----------
        # The KV block holds the POOL budget fixed; here the budget
        # covers the param store too — the bytes weight quantization
        # frees are real HBM that converts to KV pages.
        row = kv_wire_bytes_per_row(
            cfg.num_heads, cfg.head_dim, jnp.bfloat16
        )
        page_bytes = 2 * cfg.num_layers * page_size * row  # K+V, all layers
        pages_per_req = -(-(prompt_len + max_new - 1) // page_size)
        pages_f32 = 3 * pages_per_req  # the f32 arm: 3 requests' worth
        budget_bytes = pw["f32"] + pages_f32 * page_bytes
        pages_int8_modeled = int(
            (budget_bytes - pw["int8"]) // page_bytes
        )
        cap_slots, cap_requests = 12, 24
        # The modeled grant dwarfs what the slot batch can touch on this
        # tiny geometry (params >> pool) — grant what the slots can use
        # and record BOTH numbers.
        pages_int8 = min(pages_int8_modeled, cap_slots * pages_per_req)
        crng = np.random.RandomState(37)
        cap_reqs = [
            Request(
                rid=i,
                prompt=crng.randint(
                    0, cfg.vocab_size, size=prompt_len
                ).tolist(),
                max_new_tokens=max_new,
            )
            for i in range(cap_requests)
        ]

        def _capacity(weights_dtype, pages):
            eng = Engine(
                cfg, tparams, slots=cap_slots, max_len=max_len,
                prefill_len=prompt_len, kv_pages=pages,
                kv_page_size=page_size, kv_dtype="bf16",
                weights_dtype=weights_dtype,
            )
            warm_engine(eng)
            server = Server(eng)
            for r in cap_reqs:
                server.submit(r)
            t0 = time.perf_counter()
            server.run()
            wall = time.perf_counter() - t0
            st = server.stats()
            dtok = st["generated_tokens"] - st["requests_completed"]
            return {
                "pages": pages,
                "param_wire_bytes": pw[weights_dtype],
                "max_concurrent": st["concurrency_peak"],
                "pool_occupancy_peak": st["kv_pool_occupancy_peak"],
                "decode_tokens_per_sec": (
                    round(dtok / wall, 1) if wall else None
                ),
            }

        cap_f32 = _capacity("f32", pages_f32)
        cap_i8 = _capacity("int8", pages_int8)
        capacity = {
            "total_budget_bytes": int(budget_bytes),
            "page_bytes": int(page_bytes),
            "page_size": page_size,
            "request_shape": {
                "prompt_len": prompt_len, "max_new": max_new,
                "pages_per_request": pages_per_req,
                "requests": cap_requests, "slots": cap_slots,
            },
            "f32": cap_f32,
            "int8": cap_i8,
            "pages_int8_modeled": pages_int8_modeled,
            "int8_pages_slot_capped": pages_int8 < pages_int8_modeled,
            "q8w_capacity_ratio": round(
                cap_i8["max_concurrent"]
                / max(cap_f32["max_concurrent"], 1),
                2,
            ),
        }

        # -- quality gates on the trained checkpoint ------------------------
        # Same f32 cache BOTH sides — only the weight store differs, so
        # the delta is weight quantization and nothing else.
        model = GPT2(cfg)
        qparams = quantize_gpt2_params(tparams)
        q_slots, q_len = 4, prompt_len
        padded = np.zeros((q_slots, q_len), np.int32)
        for i in range(q_slots):
            padded[i, : q_len - i] = stream_toks[: q_len - i]
        c_f = alloc_cache(cfg, slots=q_slots, max_len=q_len,
                          dtype=jnp.float32)
        c_q = alloc_cache(cfg, slots=q_slots, max_len=q_len,
                          dtype=jnp.float32)
        lf, _ = model.apply(
            {"params": tparams}, jnp.asarray(padded),
            cache=(c_f.k, c_f.v, c_f.lengths),
        )
        lq, _ = model.apply(
            {"params": qparams}, jnp.asarray(padded),
            cache=(c_q.k, c_q.v, c_q.lengths),
        )
        # Positional mask: row i holds q_len - i real tokens (a value
        # mask would drop real positions holding token id 0).
        mask = (
            np.arange(q_len)[None, :]
            < (q_len - np.arange(q_slots))[:, None]
        )
        delta = np.abs(np.asarray(lf, np.float32)
                       - np.asarray(lq, np.float32))[mask]
        f32_outs = engines["f32"][1]
        i8_outs = engines["int8"][1]
        same = sum(
            t == r
            for rid in f32_outs
            for t, r in zip(i8_outs[rid], f32_outs[rid])
        )
        total_toks = sum(len(v) for v in f32_outs.values())
        quality = {
            "target_final_loss": round(t_loss, 4),
            "logit_abs_err_max": round(float(delta.max()), 5),
            "logit_abs_err_mean": round(float(delta.mean()), 6),
            # Anti-vacuity: zero error would mean the quantized store
            # never fed a matmul — the gates would be vacuously green.
            "logit_err_nonzero": bool(delta.max() > 0),
            "greedy_agreement_vs_f32": round(same / total_toks, 4),
        }

        # -- speculative acceptance neutrality ------------------------------
        # int8 weights go on BOTH draft and target (the engine
        # quantizes the draft store too) — acceptance compares two
        # quantized models against each other, the deployed shape.
        dparams, dcfg = draft_from_target(tparams, cfg, 1)
        spec_acc = {}
        for dt in ("f32", "int8"):
            eng = Engine(
                cfg, tparams, slots=slots, max_len=128,
                prefill_len=prompt_len, spec_k=3,
                draft_params=dparams, draft_cfg=dcfg,
                weights_dtype=dt,
            )
            st, _tps, _outs = _stream_run(eng)
            spec_acc[dt] = {
                "draft_acceptance_rate": st.get("draft_acceptance_rate"),
                "accepted_tokens_per_tick": st.get(
                    "accepted_tokens_per_tick"
                ),
            }
        a0 = spec_acc["f32"]["draft_acceptance_rate"]
        a8 = spec_acc["int8"]["draft_acceptance_rate"]
        spec = {
            **spec_acc,
            "acceptance_delta": (
                round(a8 - a0, 4) if a0 is not None and a8 is not None
                else None
            ),
        }

    return {
        "geometry": dict(
            vocab=cfg.vocab_size, d_model=cfg.d_model,
            num_layers=cfg.num_layers, num_heads=cfg.num_heads,
            head_dim=cfg.head_dim, slots=slots, max_len=max_len,
            prompt_len=prompt_len, max_new=max_new,
            page_size=page_size, train_steps=train_steps,
        ),
        "ab": ab,
        "capacity": capacity,
        "quality": quality,
        "speculative_neutrality": spec,
        "q8w_bytes_ratio": ab["q8w_bytes_ratio"],
        "q8w_capacity_ratio": capacity["q8w_capacity_ratio"],
    }


def _trace_forensics_block(
    requests: int = 24, max_new: int = 16, reps: int = 3,
):
    """The request-ledger overhead A/B + forensics snapshot (ISSUE 16).

    Deliberately a TINY-geometry paged engine, not the headline one:
    the ledger's per-event cost is engine-independent (a dict append on
    the host), so millisecond decode ticks make it proportionally
    LARGEST here — the recorded pct is an honest upper bound for the
    production config, measured where the statistics are good instead
    of drowned in a 100ms-tick stream's wall-clock noise. Three arms
    (ledger off / aggregate-only counters / full exemplar capture) on
    identical seeded streams, alternated ``reps`` times, best (min
    decode seconds) per arm — the standard best-of-N noise floor.
    ``trace_overhead_pct`` is the aggregate arm (the always-on
    production configuration; acceptance wants <1% — recorded, never
    asserted here: wall-clock honesty). The full arm's snapshot IS the
    forensics evidence: ``why-slow`` must exit 0 on this BENCH_DETAIL
    block, which ties the CLI's input contract to a real bench run.
    """
    import numpy as np

    from mpit_tpu import obs
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.obs.trace import Ledger
    from mpit_tpu.serve import Engine, Request, Server, warm_engine

    cfg = GPT2Config.tiny(max_seq_len=64)
    params = jax.jit(GPT2(cfg).init)(
        jax.random.key(2), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine = Engine(
        cfg, params, slots=4, max_len=64, prefill_len=32,
        kv_pages=32, kv_page_size=8, prefill_chunk=8,
    )
    warm_engine(engine)

    def _run(ledger):
        engine.reset()
        rng = np.random.RandomState(5)
        server = Server(engine, ledger=ledger)
        for i in range(requests):
            plen = int(rng.randint(4, 28))
            server.submit(Request(
                rid=f"t{i}",
                prompt=rng.randint(0, cfg.vocab_size, size=plen).tolist(),
                max_new_tokens=max_new,
            ))
        rec = obs.get_recorder()
        n0 = rec.event_count() if rec else 0
        t0 = time.perf_counter()
        server.run()
        wall = time.perf_counter() - t0
        stats = server.stats()
        dtok = stats["generated_tokens"] - stats["requests_completed"]
        ds = wall
        if rec is not None:
            ph = rec.summary(since=n0)["phases"]
            ds = ph.get("decode", {}).get("total_s", wall)
        return (dtok / ds if ds else 0.0)

    best = {"off": 0.0, "aggregate": 0.0, "full": 0.0}
    ledger = None
    with obs.span("trace_forensics_ab"):
        for _ in range(reps):
            best["off"] = max(best["off"], _run(None))
            best["aggregate"] = max(
                best["aggregate"], _run(Ledger(mode="aggregate"))
            )
            ledger = Ledger(mode="full", exemplar_k=3)
            best["full"] = max(best["full"], _run(ledger))
    tps_off = best["off"]
    snap = ledger.snapshot()
    overhead = (
        round((tps_off - best["aggregate"]) / tps_off * 100.0, 2)
        if tps_off else None
    )
    overhead_full = (
        round((tps_off - best["full"]) / tps_off * 100.0, 2)
        if tps_off else None
    )
    return {
        **snap,
        "ab": {
            "geometry": {
                "num_layers": cfg.num_layers, "d_model": cfg.d_model,
                "slots": 4, "max_len": 64, "prefill_chunk": 8,
                "requests": requests, "max_new": max_new, "reps": reps,
            },
            "decode_tokens_per_sec_ledger_off": round(best["off"], 1),
            "decode_tokens_per_sec_ledger_aggregate": round(
                best["aggregate"], 1
            ),
            "decode_tokens_per_sec_ledger_full": round(best["full"], 1),
            "trace_overhead_pct": overhead,
            "trace_overhead_full_pct": overhead_full,
        },
        "trace_overhead_pct": overhead,
    }


def bench_gpt2_serve(
    slots: int = 8,
    prompt_len: int = 64,
    max_new: int = 48,
    requests: int = 24,
    max_len: int = 128,
    decode_attention: str = "kernel",
    sweep_lengths: tuple = (64, 256, 1024),
):
    """GPT-2 serving throughput/latency on the continuous-batching
    engine: decode tokens/sec over the KV-cache decode path plus
    per-request latency percentiles, on a synthetic request stream
    saturating ``slots`` concurrent cache slots.

    ISSUE 5 grows two comparisons around the headline stream:

    - the same stream re-measured with ``decode_attention="reference"``
      (the dense PR 4 hot loop) — ``reference_decode_tokens_per_sec``,
      detail-only, the kernel-on/off A-B at identical geometry;
    - a decode-throughput-vs-context-length sweep (detail-only,
      ``decode_sweep``): short generations at prompt lengths
      ``sweep_lengths`` on a reduced-depth config (geometry recorded in
      the entry) — with the length-aware kernel the curve should
      flatten relative to O(max_len) dense decode; ``kv_blocks_*``
      record how many cache tiles a tick actually visits.

    ISSUE 7 pins the paged-cache win on top: ``paged_capacity``
    (detail) measures max concurrent requests at a FIXED HBM budget,
    paged pool vs dense cache, with prefix sharing live; the headline
    ``max_concurrent_at_hbm`` + ``prefix_hit_rate`` + ``kv_page_size``
    ride the record line. ``chunked_prefill`` (detail) A/Bs p95 TTFT on
    one mixed-length open-loop trace, whole-prompt vs chunked admits.

    The record line carries the resolved ``decode_attention`` mode
    (what actually executed — "kernel" falls back to "reference" math
    off-TPU, and the line must say so).
    """
    import numpy as np

    import mpit_tpu
    from mpit_tpu import obs
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.ops.decode_attention import num_kv_blocks
    from mpit_tpu.serve import Engine, Request, Server

    world = mpit_tpu.init()
    del world  # serving is single-replica here; TP variant is test-covered

    cfg = GPT2Config.small(max_seq_len=max_len, head_dtype=jnp.bfloat16)
    params = jax.jit(GPT2(cfg).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine, stats, wall, decode_tokens, decode_s, gen = _serve_stream(
        cfg, params, slots=slots, max_len=max_len, prompt_len=prompt_len,
        max_new=max_new, requests=requests,
        decode_attention=decode_attention,
    )
    out = {
        "decode_tokens_per_sec": (
            round(decode_tokens / decode_s, 1) if decode_s else None
        ),
        "decode_attention": engine.decode_attention_mode,
        # Off-TPU "kernel" mode falls back to reference ATTENTION but
        # keeps the blocked sampler (pure XLA) — this detail key is what
        # distinguishes that engine from a true decode_attention=
        # "reference" run, which is dense end to end.
        "decode_sampler": engine.decode_sampler,
        "serve_tokens_per_sec": round(gen / wall, 1),
        "latency_p50_s": stats.get("latency_p50_s"),
        "latency_p95_s": stats.get("latency_p95_s"),
        "ttft_p50_s": stats.get("ttft_p50_s"),
        "ttft_p95_s": stats.get("ttft_p95_s"),
        "slots": slots,
        "requests": requests,
        "generated_tokens": gen,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new,
        "ticks": stats["ticks"],
        "occupancy_mean": stats["occupancy_mean"],
    }
    # ISSUE 8: the honest decode bandwidth — achieved bytes from the
    # kernel's visited-tile model (accumulated per tick by the
    # scheduler; pinned == the kernel's own visited counts) over the
    # measured decode seconds. A PERCENTAGE of the chip's HBM peak only
    # when the run was ON the chip — off-TPU the line carries null +
    # the platform label (modeled GB/s stays detail-only either way).
    platform = jax.devices()[0].platform
    hbm_bytes = stats.get("decode_hbm_bytes_modeled")
    out["engine_compiles"] = stats.get("engine_compiles")
    out["roofline_platform"] = platform
    out["decode_hbm_util_pct"] = None
    if hbm_bytes and decode_s:
        out["decode_hbm_gbps_modeled"] = round(
            hbm_bytes / decode_s / 1e9, 2
        )
        if platform == "tpu":
            from mpit_tpu.obs.roofline import chip_peaks

            out["decode_hbm_util_pct"] = round(
                100.0 * hbm_bytes / decode_s
                / chip_peaks(platform=platform)["peak_hbm"], 2
            )
    # Kernel-on/off A-B at identical geometry (detail-only). Guard on the
    # RESOLVED mode: off-TPU a requested "kernel" already ran reference
    # ATTENTION, so a second stream could only A-B the blocked-vs-dense
    # sampler — not the kernel claim this number exists to pin — at
    # double the runtime; skip it and let decode_sampler (above) record
    # which head the measured stream actually ran.
    if engine.decode_attention_mode != "reference":
        _, rstats, rwall, rtok, rdecode_s, rgen = _serve_stream(
            cfg, params, slots=slots, max_len=max_len,
            prompt_len=prompt_len, max_new=max_new, requests=requests,
            decode_attention="reference",
        )
        out["reference_decode_tokens_per_sec"] = (
            round(rtok / rdecode_s, 1) if rdecode_s else None
        )
    # Context-length sweep (detail-only): decode cost vs cached context
    # inside ONE long-cache engine — THE tentpole claim ("scale with
    # context, not cache size") in curve form. Every point shares the
    # same engine/cache geometry (max_len fits the longest context), so
    # the dense reference pays the full buffer at every length while
    # the length-aware kernel pays ceil((L+1)/block_k) tiles. Reduced
    # depth keeps the sweep affordable; the CURVE, not the absolute
    # rate, is the signal — geometry is recorded alongside.
    sweep_cfg = GPT2Config.small(
        num_layers=2,
        max_seq_len=max(sweep_lengths) + 32,
        head_dtype=jnp.bfloat16,
    )
    sweep_params = jax.jit(GPT2(sweep_cfg).init)(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    s_len = max(sweep_lengths) + 16
    s_slots, s_new = 4, 8
    sweep_engine = Engine(
        sweep_cfg, sweep_params, slots=s_slots, max_len=s_len,
        prefill_len=max(sweep_lengths),
        decode_attention=decode_attention,
    )
    rng = np.random.RandomState(1)
    bk = sweep_engine.decode_block_k
    rec = obs.get_recorder()

    def _sweep_point(ctx, warm=False):
        sweep_engine.reset()
        server = Server(sweep_engine)
        for i in range(s_slots):
            server.submit(
                Request(
                    rid=i,
                    prompt=rng.randint(
                        0, sweep_cfg.vocab_size, size=ctx
                    ).tolist(),
                    max_new_tokens=2 if warm else s_new,
                )
            )
        n0 = rec.event_count() if rec else 0
        t0 = time.perf_counter()
        server.run()
        wall = time.perf_counter() - t0
        stats = server.stats()
        dtok = stats["generated_tokens"] - stats["requests_completed"]
        ds = wall
        if rec is not None:
            ph = rec.summary(since=n0)["phases"]
            ds = ph.get("decode", {}).get("total_s", wall)
        return dtok, ds

    with obs.span("warmup", calls=1):
        _sweep_point(min(sweep_lengths), warm=True)  # the two compiles
    sweep = []
    for ctx in sweep_lengths:
        dtok, ds = _sweep_point(ctx)
        sweep.append(
            {
                "context_len": ctx,
                "decode_tokens_per_sec": round(dtok / ds, 1) if ds else None,
                "kv_blocks_visited_per_slot": int(
                    num_kv_blocks(np.asarray([ctx]), 1, s_len, bk)[0]
                ),
                "kv_blocks_total": s_len // bk,
            }
        )
    out["decode_sweep"] = {
        "config": {
            "num_layers": sweep_cfg.num_layers,
            "d_model": sweep_cfg.d_model,
            "slots": s_slots,
            "max_new": s_new,
            "max_len": s_len,
            "block_k": bk,
            "decode_attention": sweep_engine.decode_attention_mode,
        },
        "points": sweep,
    }
    # ISSUE 7: the paged-cache capacity win + chunked-prefill TTFT A/B
    # (full blocks detail-only; the line gets the headline triple).
    out["paged_capacity"] = _paged_capacity_block()
    out["chunked_prefill"] = _chunked_prefill_block()
    out["kv_page_size"] = out["paged_capacity"]["page_size"]
    out["prefix_hit_rate"] = out["paged_capacity"]["paged"][
        "prefix_hit_rate"
    ]
    out["max_concurrent_at_hbm"] = out["paged_capacity"]["paged"][
        "max_concurrent"
    ]
    # ISSUE 13: the speculative-decode A/B (same seeded traces, spec
    # on/off, self-speculation draft). The block stays detail-only; the
    # achieved tokens-per-slot-tick multiplier rides the record line.
    out["speculative"] = _speculative_block()
    out["accepted_tokens_per_tick"] = out["speculative"][
        "accepted_tokens_per_tick"
    ]
    # ISSUE 15: the quantized-KV A/B + capacity sweep + quality gates
    # (trained checkpoint). Block detail-only; the line carries the
    # headline stream's wire dtype and the capacity-at-fixed-HBM ratio.
    out["quantized_kv"] = _quantized_kv_block()
    out["kv_dtype"] = engine.kv_dtype
    out["q8_capacity_ratio"] = out["quantized_kv"]["q8_capacity_ratio"]
    # ISSUE 17: the quantized-WEIGHTS A/B + capacity + quality gates
    # (trained checkpoint; the param read is the dominant tick term the
    # KV block's honesty note pointed at). Block detail-only; the line
    # carries the headline stream's weight wire dtype and the modeled
    # int8-vs-f32 whole-tick decode-bytes ratio.
    out["quantized_weights"] = _quantized_weights_block()
    out["weights_dtype"] = engine.weights_dtype
    out["q8w_bytes_ratio"] = out["quantized_weights"]["q8w_bytes_ratio"]
    # ISSUE 16: the request-ledger overhead A/B + forensics snapshot
    # (block detail-only; the line carries the aggregate-arm overhead
    # pct and the exemplar count proving tail capture ran).
    out["trace_forensics"] = _trace_forensics_block()
    out["trace_overhead_pct"] = out["trace_forensics"]["trace_overhead_pct"]
    out["exemplars_retained"] = out["trace_forensics"]["exemplars_retained"]
    # ISSUE 18: the headline stream's byte-exact memory-ledger stats —
    # the dense engine's measured held-bytes peak and the KV headroom
    # floor across the whole stream. The full block (per-subsystem
    # decomposition, per-request/per-tenant attribution, conservation
    # verdict, platform-labeled reconciliation) is detail-only; the
    # peak + headroom floor ride the record line.
    out["memory"] = stats.get("memory", {})
    out["hbm_held_peak_bytes"] = out["memory"].get("held_peak_bytes")
    out["kv_headroom_min_pct"] = out["memory"].get("kv_headroom_min_pct")
    return out


def bench_gpt2_slo(
    slots: int = 4,
    max_len: int = 64,
    prefill_len: int = 16,
    duration_s: float = 2.5,
    rate_fractions: tuple = (0.4, 0.7, 1.0, 1.5),
    ttft_multiple: float = 5.0,
    window_s: float = 1.5,
):
    """The SLO sweep (ISSUE 6; ROADMAP item 4's headline metric): **max
    sustained requests/s at p95 TTFT ≤ target**, measured by driving
    the continuous-batching engine with OPEN-loop Poisson arrivals
    (``serve.loadgen`` + ``Server.run_timed``) at a ladder of rates and
    reading windowed percentiles off the streaming sketch
    (``obs.stream``) — never the Recorder's bounded buffer.

    Self-calibrating so the sweep means the same thing on CPU and TPU:

    - **capacity** — a closed-loop saturation run measures the rate the
      engine drains when arrival timing is no constraint; sweep rates
      are ``rate_fractions`` of it, so the ladder straddles saturation
      by construction and the top point OVERLOADS (its queue grows
      without bound, TTFT explodes, the ``ttft_p95`` SLO trips —
      ``slo_breach`` instants land in this workload's recorder and ride
      its ``obs_baseline`` snapshot into BENCH_DETAIL.json);
    - **ttft target** — ``ttft_multiple`` × the measured unloaded TTFT
      (sequential single-request median): "p95 within 5× of an idle
      server", an SLO that scales with the hardware instead of going
      vacuous on a slow host.

    A rate point is SUSTAINED when its whole-run sketch p95 TTFT meets
    the target and the SLO monitor spent ≤ 20% of the window in breach.
    The record line carries the headline + target + total breaches; the
    rate → (p95 TTFT, tokens/s, breach fraction) curve is detail-only.
    """
    import numpy as np

    import mpit_tpu
    from mpit_tpu import obs
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.obs.slo import SLO, SLOMonitor
    from mpit_tpu.obs.stream import StreamRegistry
    from mpit_tpu.serve import (
        Engine,
        LoadSpec,
        Request,
        RequestClass,
        Server,
        generate_arrivals,
        warm_engine,
    )

    world = mpit_tpu.init()
    del world

    cfg = GPT2Config.tiny(max_seq_len=max_len)
    params = jax.jit(GPT2(cfg).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine = Engine(
        cfg, params, slots=slots, max_len=max_len, prefill_len=prefill_len
    )
    mix = (
        RequestClass("interactive", weight=0.8, prompt_len=(2, 10),
                     max_new_tokens=(3, 8)),
        RequestClass("batch", weight=0.2, prompt_len=(8, prefill_len - 2),
                     max_new_tokens=(8, 20)),
    )
    mean_new = sum(
        c.weight * (c.max_new_tokens[0] + c.max_new_tokens[1]) / 2
        for c in mix
    ) / sum(c.weight for c in mix)
    rng = np.random.RandomState(0)

    def _mk_req(i, klass):
        # Inclusive [lo, hi], same convention as loadgen's sampler —
        # the calibration requests and the sweep traffic must draw
        # from the same distribution.
        plen = int(rng.randint(klass.prompt_len[0], klass.prompt_len[1] + 1))
        return Request(
            rid=f"cal{i}",
            prompt=rng.randint(0, cfg.vocab_size, size=plen).tolist(),
            max_new_tokens=int(
                rng.randint(klass.max_new_tokens[0],
                            klass.max_new_tokens[1] + 1)
            ),
        )

    warm_engine(engine)  # spans itself as `warmup` (ISSUE 8 satellite)

    # Calibration 1 — unloaded TTFT: sequential single requests on an
    # idle engine; the SLO target's basis.
    with obs.span("calibrate_ttft"):
        ttfts = []
        for i in range(5):
            engine.reset()
            s = Server(engine)
            s.submit(_mk_req(i, mix[0]))
            s.run()
            ttfts.append(s.completed[0].ttft_s)
        unloaded_ttft = float(np.median(ttfts))
    ttft_target = ttft_multiple * unloaded_ttft

    # Calibration 2 — closed-loop capacity: saturate the slots, measure
    # the drain rate. Arrival timing can only LOWER throughput, so this
    # is the ceiling the sweep fractions scale from.
    with obs.span("calibrate_capacity"):
        engine.reset()
        s = Server(engine)
        n_cal = slots * 8
        for i in range(n_cal):
            s.submit(_mk_req(i, mix[int(rng.rand() < 0.2)]))
        t0 = time.perf_counter()
        s.run()
        cal_wall = time.perf_counter() - t0
        capacity = n_cal / cal_wall

    sweep = []
    breaches_total = 0
    max_sustained = None
    for frac in rate_fractions:
        rate = frac * capacity
        engine.reset()
        registry = StreamRegistry(window_s=window_s)
        monitor = SLOMonitor(
            [SLO.ttft_p95(ttft_target)], registry, min_count=8
        )
        arrivals = generate_arrivals(
            LoadSpec(rate=rate, classes=mix),
            vocab_size=cfg.vocab_size,
            duration_s=duration_s,
            seed=int(frac * 100),
        )
        server = Server(engine, stream=registry, slo=monitor)
        with obs.span("slo_point", rate=round(rate, 1)):
            t0 = time.perf_counter()
            # drain=False: past saturation the queue never drains — the
            # honest measurement is what completed inside the window.
            server.run_timed(arrivals, duration=duration_s, drain=False)
            wall = time.perf_counter() - t0
        stats = server.stats()
        sk = registry.total_sketch("request_ttft")
        p95 = sk.quantile(0.95) if sk is not None and sk.count else None
        rep = monitor.report()["targets"]["ttft_p95"]
        breach_frac = rep["time_in_breach_s"] / max(wall, 1e-9)
        gen = stats["generated_tokens"]
        sustained = (
            p95 is not None
            and p95 <= ttft_target
            and breach_frac <= 0.2
        )
        offered = len(arrivals) / duration_s
        if sustained:
            max_sustained = max(max_sustained or 0.0, offered)
        breaches_total += rep["breaches"]
        sweep.append(
            {
                "rate_fraction": frac,
                "offered_req_per_s": round(offered, 2),
                "completed_req_per_s": round(
                    stats["requests_completed"] / wall, 2
                ),
                "ttft_p95_s": round(p95, 6) if p95 is not None else None,
                "tokens_per_sec": round(gen / wall, 1),
                "breach_fraction": round(breach_frac, 4),
                "breaches": rep["breaches"],
                "truncated": stats["truncated"],
                "sustained": sustained,
            }
        )
    return {
        "max_sustained_req_per_s": (
            round(max_sustained, 2) if max_sustained is not None else None
        ),
        "ttft_target_s": round(ttft_target, 6),
        "slo_breaches": breaches_total,
        "decode_attention": engine.decode_attention_mode,
        "slots": slots,
        "calibration": {
            "unloaded_ttft_s": round(unloaded_ttft, 6),
            "ttft_multiple": ttft_multiple,
            "closed_loop_capacity_req_per_s": round(capacity, 2),
            "mean_new_tokens": round(mean_new, 2),
        },
        "rate_sweep": sweep,
        "geometry": {
            "num_layers": cfg.num_layers,
            "d_model": cfg.d_model,
            "slots": slots,
            "max_len": max_len,
            "prefill_len": prefill_len,
            "duration_s": duration_s,
            "window_s": window_s,
            "process": "poisson",
        },
    }


def bench_gpt2_policy(
    slots: int = 4,
    max_len: int = 64,
    prefill_len: int = 32,
    kv_pages: int = 20,
    kv_page_size: int = 8,
    prefill_chunk: int = 8,
    duration_s: float = 2.0,
    rate_fractions: tuple = (0.4, 0.7, 1.0, 1.6),
    ttft_multiple: float = 15.0,
    window_s: float = 1.5,
):
    """The scheduling-policy A/B (ISSUE 12; ROADMAP item 4's decision
    layer): the SAME paged engine at the SAME HBM budget driven by the
    SAME seeded mixed 80/20 open-loop traces, FIFO vs the policy tier
    (priority classes + deficit-round-robin tenant fairness +
    projected-TTFT admission + paged-KV preemption), swept over a
    self-calibrating rate ladder like ``gpt2_slo``:

    - **ttft target** — ``ttft_multiple`` × the measured unloaded
      interactive TTFT, stamped on the interactive class (priority 0);
      the batch class (priority 1) carries no target — it is the
      preemption victim pool;
    - **sustained** — a rate point sustains when the INTERACTIVE class's
      exact p95 TTFT (completions, not sketch) meets the target, the
      tier-0 SLO monitor spent ≤ 20% of the window in breach, and ≤ 10%
      of arrivals were shed (a policy that sheds its way to a good p95
      has not sustained the rate);
    - the pool is undersized (``kv_pages < slots × pages_per_slot``) so
      page pressure is real and preemption has work to do.

    Record line: ``max_sustained_req_per_s_policy`` (the headline — the
    FIFO counterpart sits in detail for the ≥ comparison),
    ``interactive_ttft_p95_ms`` (policy, at the top swept rate; FIFO's
    in detail) and ``preemptions``. A per-rate FIFO-vs-policy curve,
    shed-cause splits and the sentinel/SLO wiring evidence are
    detail-only. CPU runs are honest wall-clock measurements of this
    host — platform-labeled via the record's top-level ``platform``, no
    fabricated utilization (roofline honesty rule).
    """
    import dataclasses as _dc

    import numpy as np

    import mpit_tpu
    from mpit_tpu import obs
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.obs.slo import SLO, SLOMonitor
    from mpit_tpu.obs.stream import StreamRegistry
    from mpit_tpu.serve import (
        Engine,
        LoadSpec,
        Request,
        RequestClass,
        SchedulingPolicy,
        Server,
        generate_arrivals,
        warm_engine,
    )
    from mpit_tpu.serve.policy import PolicyConfig

    world = mpit_tpu.init()
    del world

    cfg = GPT2Config.tiny(max_seq_len=max_len)
    params = jax.jit(GPT2(cfg).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine = Engine(
        cfg, params, slots=slots, max_len=max_len, prefill_len=prefill_len,
        kv_pages=kv_pages, kv_page_size=kv_page_size,
        prefill_chunk=prefill_chunk,
    )
    interactive = RequestClass(
        "interactive", weight=0.8, prompt_len=(2, 10),
        max_new_tokens=(3, 8), priority=0,
    )
    batch = RequestClass(
        "batch", weight=0.2, prompt_len=(12, prefill_len - 2),
        max_new_tokens=(12, 24), priority=1,
    )
    rng = np.random.RandomState(0)

    def _mk_req(i, klass):
        plen = int(rng.randint(klass.prompt_len[0], klass.prompt_len[1] + 1))
        return Request(
            rid=f"cal{i}",
            prompt=rng.randint(0, cfg.vocab_size, size=plen).tolist(),
            max_new_tokens=int(
                rng.randint(klass.max_new_tokens[0],
                            klass.max_new_tokens[1] + 1)
            ),
        )

    warm_engine(engine)

    # Calibration 1 — unloaded interactive TTFT: the target's basis.
    with obs.span("calibrate_ttft"):
        ttfts = []
        for i in range(5):
            engine.reset()
            s = Server(engine)
            s.submit(_mk_req(i, interactive))
            s.run()
            ttfts.append(s.completed[0].ttft_s)
        unloaded_ttft = float(np.median(ttfts))
    ttft_target = ttft_multiple * unloaded_ttft
    interactive = _dc.replace(interactive, ttft_target_s=ttft_target)
    mix = (interactive, batch)

    # Calibration 2 — closed-loop capacity (the ladder's 1.0 point).
    with obs.span("calibrate_capacity"):
        engine.reset()
        s = Server(engine)
        n_cal = slots * 8
        for i in range(n_cal):
            s.submit(_mk_req(i, mix[int(rng.rand() < 0.2)]))
        t0 = time.perf_counter()
        s.run()
        capacity = n_cal / (time.perf_counter() - t0)

    def _run_point(arrivals, by_rid, use_policy, ledger=None, eng=None,
                   drain=False):
        eng = engine if eng is None else eng
        eng.reset()
        registry = StreamRegistry(window_s=window_s)
        sentinel = obs.Sentinel(phases=("decode", "prefill"), warmup=4)
        # The SLO watches the INTERACTIVE tier's TTFT series (fed for
        # priority/target-stamped traffic on FIFO runs too, so the A/B
        # reads one metric); breaches land in the sentinel per the
        # ISSUE 12 acceptance wiring.
        monitor = SLOMonitor(
            [SLO(name="interactive_ttft_p95",
                 metric="request_ttft_tier0", max_value=ttft_target)],
            registry, min_count=8, sentinel=sentinel,
        )
        policy = (
            SchedulingPolicy(PolicyConfig(min_samples=4), registry)
            if use_policy
            else None
        )
        server = Server(
            eng, sentinel=sentinel, stream=registry, slo=monitor,
            policy=policy, ledger=ledger,
        )
        t0 = time.perf_counter()
        server.run_timed(arrivals, duration=duration_s, drain=drain)
        wall = time.perf_counter() - t0
        stats = server.stats()
        done = server.completed

        def _class_p95(name):
            vals = [
                c.ttft_s for c in done if by_rid[c.rid].klass == name
            ]
            return (
                float(np.percentile(np.asarray(vals), 95))
                if vals else None
            )

        p95_int = _class_p95("interactive")
        p95_bat = _class_p95("batch")
        rep = monitor.report()["targets"]["interactive_ttft_p95"]
        breach_frac = rep["time_in_breach_s"] / max(wall, 1e-9)
        shed_frac = len(server.shed) / max(len(arrivals), 1)
        sustained = (
            p95_int is not None
            and p95_int <= ttft_target
            and breach_frac <= 0.2
            and shed_frac <= 0.1
        )
        entry = {
            "completed_req_per_s": round(
                stats["requests_completed"] / wall, 2
            ),
            "interactive_ttft_p95_s": (
                round(p95_int, 6) if p95_int is not None else None
            ),
            "batch_ttft_p95_s": (
                round(p95_bat, 6) if p95_bat is not None else None
            ),
            "tokens_per_sec": round(stats["generated_tokens"] / wall, 1),
            "breaches": rep["breaches"],
            "breach_fraction": round(breach_frac, 4),
            "shed_fraction": round(shed_frac, 4),
            "truncated": stats["truncated"],
            "sustained": sustained,
            "sentinel_clean": sentinel.report()["clean"],
        }
        if use_policy:
            entry["preemptions"] = stats["preemptions"]
            entry["shed_admission"] = stats.get(
                "requests_shed_admission", 0
            )
            entry["shed_queue_full"] = stats.get(
                "requests_shed_queue_full", 0
            )
        # ISSUE 20 tiering A/B evidence: the resume-path p95s (present
        # once the mode's resumes have fired — restream on the tiered
        # engine, recompute on the untiered one), the prefix hit rate
        # the host tier is supposed to hold up, and — tiered runs only —
        # the host-tier counters/byte totals.
        for k in ("resume_restream_p95_s", "resume_recompute_p95_s",
                  "prefix_hit_rate"):
            if k in stats:
                entry[k] = stats[k]
        if "host_restreamed_pages" in stats:
            entry["host"] = {
                k: stats[k]
                for k in ("kv_host_pages", "host_spilled_pages",
                          "host_restreamed_pages", "host_prefix_hits",
                          "parked_spills", "spilled_prefix_entries")
            }
            entry["host"]["spill_bytes_total"] = (
                stats["memory"]["spill_bytes_total"]
            )
            entry["host"]["restream_bytes"] = (
                stats["memory"]["restream_bytes"]
            )
            entry["host"]["host_held_peak_bytes"] = (
                stats["memory"]["host_held_peak_bytes"]
            )
        return entry

    sweep = []
    forensics_ledger = None
    max_sustained = {"fifo": None, "policy": None}
    breaches = {"fifo": 0, "policy": 0}
    preemptions_total = 0
    top_p95 = {"fifo": None, "policy": None}
    for frac in rate_fractions:
        rate = frac * capacity
        arrivals = generate_arrivals(
            LoadSpec(rate=rate, classes=mix, tenants=2),
            vocab_size=cfg.vocab_size,
            duration_s=duration_s,
            seed=int(frac * 100),
        )
        by_rid = {a.request.rid: a for a in arrivals}
        offered = len(arrivals) / duration_s
        point = {
            "rate_fraction": frac,
            "offered_req_per_s": round(offered, 2),
        }
        for mode in ("fifo", "policy"):
            # ISSUE 16: the TOP swept rate's policy run carries a full
            # request ledger — past saturation, where sheds / preemption
            # / breach pins all fire, is exactly where why-slow earns
            # its keep. One arm only: the A/B stays ledger-free so the
            # FIFO-vs-policy comparison is untouched.
            ledger = None
            if mode == "policy" and frac == rate_fractions[-1]:
                from mpit_tpu.obs.trace import Ledger

                ledger = forensics_ledger = Ledger(
                    mode="full", exemplar_k=3
                )
            with obs.span("policy_point", rate=round(rate, 1), mode=mode):
                entry = _run_point(
                    arrivals, by_rid, mode == "policy", ledger=ledger
                )
            point[mode] = entry
            breaches[mode] += entry["breaches"]
            if entry["sustained"]:
                max_sustained[mode] = max(
                    max_sustained[mode] or 0.0, offered
                )
            top_p95[mode] = entry["interactive_ttft_p95_s"]
            if mode == "policy":
                preemptions_total += entry["preemptions"]
        sweep.append(point)

    # ISSUE 20 — the HBM→host tiering A/B: the SAME policy engine
    # geometry at the SAME saturated rate (the ladder's top fraction),
    # but on a LONG-TAIL trace — every request opens with a shared
    # 16-token system prefix, so the undersized pool reclaims the
    # prefix pages over and over. Untiered, the reclaim kills the
    # entry and every later admit recomputes (and every preemption
    # resume recomputes its fill); tiered, the entry and parked
    # victims spill to host RAM and restream. Both arms DRAIN so every
    # parked victim actually resumes and the p95s compare the same
    # completed population. CPU honesty: this host's "host tier" is a
    # same-RAM copy through the jitted gather/scatter, so the measured
    # restream p95 is an honest wall-clock for THIS platform but NOT a
    # PCIe/DMA measurement — the modeled per-page figure next to it is
    # the labeled transfer estimate.
    tail_mix = (
        _dc.replace(interactive, prefix_len=16),
        _dc.replace(batch, prompt_len=(4, 14), prefix_len=16),
    )
    # Bursty, not Poisson: the steady saturated stream always has a
    # CONCURRENT reader on the shared prefix, so its entry never goes
    # sole-reader and both arms hit alike. Bursts at 4× the mean rate
    # bring the preemption pressure (parks → restream resumes);
    # the silent off-phases drain the pool, the prefix goes
    # sole-reader, and the reclaim that untiered kills — and the host
    # tier survives — actually happens, burst after burst.
    top_rate = rate_fractions[-1] * capacity
    tail_arrivals = generate_arrivals(
        LoadSpec(rate=top_rate, classes=tail_mix, tenants=2,
                 process="bursty", on_fraction=0.25, mean_on_s=0.25),
        vocab_size=cfg.vocab_size,
        duration_s=duration_s,
        seed=777,
    )
    tail_by_rid = {a.request.rid: a for a in tail_arrivals}
    tiered_engine = Engine(
        cfg, params, slots=slots, max_len=max_len, prefill_len=prefill_len,
        kv_pages=kv_pages, kv_page_size=kv_page_size,
        prefill_chunk=prefill_chunk, kv_host_pages=kv_pages,
    )
    warm_engine(tiered_engine)
    tier_ab = {}
    for tmode, eng_used in (("untiered", engine), ("tiered", tiered_engine)):
        with obs.span("tiering_point", mode=tmode):
            tier_ab[tmode] = _run_point(
                tail_arrivals, tail_by_rid, True, eng=eng_used, drain=True
            )

    def _ms(v):
        return round(v * 1e3, 2) if v is not None else None

    # ISSUE 16: the saturated policy run's ledger snapshot, worst three
    # exemplars only (pinned-or-slowest; dropping exemplars is lossless
    # for why-slow's usability contract — dropping EVENTS is not, and
    # never happens: the event cap is far above a bench request's life).
    forensics = None
    if forensics_ledger is not None:
        forensics = forensics_ledger.snapshot()
        # exemplars_retained stays the TRUE retention count (breach
        # pins under saturation retain the whole in-flight set);
        # exemplars_stored says how many ride the artifact.
        forensics["exemplars"] = forensics["exemplars"][:3]
        forensics["exemplars_stored"] = len(forensics["exemplars"])

    # The line's tiering triple (ISSUE 20): p95 resume-via-restream
    # (tiered arm) vs p95 resume-via-recompute (untiered arm) on the
    # same drained long-tail trace, and the prefix hit rate the host
    # tier held up under pool pressure ("hit_rate" — the untiered
    # counterpart it must beat sits in tiering_detail). A p95 is null
    # until its arm's resumes fired — never fabricated.
    t_ent = tier_ab["tiered"]
    u_ent = tier_ab["untiered"]
    page_bytes = tiered_engine.page_bytes
    host_link_gbps = 16.0  # assumed PCIe gen4-ish effective host link
    tiering_detail = {
        "prefix_hit_rate_tiered": t_ent.get("prefix_hit_rate"),
        "prefix_hit_rate_untiered": u_ent.get("prefix_hit_rate"),
        "kv_host_pages": kv_pages,
        "shared_prefix_len": 16,
        "offered_req_per_s": round(len(tail_arrivals) / duration_s, 2),
        "untiered": u_ent,
        "tiered": t_ent,
        # The labeled transfer model (never passed off as measured):
        # one page over an assumed host link, plus the same-RAM
        # platform note that keeps the measured p95 honest.
        "host_link_gbps_assumed": host_link_gbps,
        "modeled_page_restream_us": round(
            (page_bytes / (host_link_gbps * 1e9) + 10e-6) * 1e6, 2
        ),
        "note": "CPU host tier is a same-RAM copy; measured restream "
                "p95 is wall-clock on this host, not a PCIe/DMA "
                "measurement",
    }

    return {
        "trace_forensics": forensics,
        "tiering": {
            "restream_p95_ms": _ms(t_ent.get("resume_restream_p95_s")),
            "recompute_p95_ms": _ms(u_ent.get("resume_recompute_p95_s")),
            "hit_rate": t_ent.get("prefix_hit_rate"),
        },
        "tiering_detail": tiering_detail,
        "max_sustained_req_per_s_policy": (
            round(max_sustained["policy"], 2)
            if max_sustained["policy"] is not None else None
        ),
        "max_sustained_req_per_s_fifo": (
            round(max_sustained["fifo"], 2)
            if max_sustained["fifo"] is not None else None
        ),
        # The top swept rate's interactive p95 — the mixed 80/20 trace
        # past saturation, where the tiers earn their keep.
        "interactive_ttft_p95_ms": _ms(top_p95["policy"]),
        "interactive_ttft_p95_ms_fifo": _ms(top_p95["fifo"]),
        "preemptions": preemptions_total,
        "ttft_target_s": round(ttft_target, 6),
        "slo_breaches": breaches,
        "decode_attention": engine.decode_attention_mode,
        "calibration": {
            "unloaded_ttft_s": round(unloaded_ttft, 6),
            "ttft_multiple": ttft_multiple,
            "closed_loop_capacity_req_per_s": round(capacity, 2),
        },
        "rate_sweep": sweep,
        "geometry": {
            "num_layers": cfg.num_layers,
            "d_model": cfg.d_model,
            "slots": slots,
            "max_len": max_len,
            "prefill_len": prefill_len,
            "kv_pages": kv_pages,
            "kv_page_size": kv_page_size,
            "prefill_chunk": prefill_chunk,
            "duration_s": duration_s,
            "window_s": window_s,
            "tenants": 2,
            "mix": "interactive 0.8 p0 / batch 0.2 p1",
        },
    }


def _q8_wire_bytes(payload_bytes: int, p: int) -> float:
    """ACTUAL wire-equivalent payload of a quantized (int8 + per-chunk
    scale) ring over an f32 payload — the ring planner's own figure
    (ISSUE 9: modeled q8 numbers use the quantized size, never the
    logical one)."""
    from mpit_tpu.ops.ring_collectives import plan_ring

    plan = plan_ring(payload_bytes // 4, p, "int8")
    return plan.wire_payload_bytes("int8", scales=True)


def _modeled_allreduce_curves(mbs, p: int = 8):
    """Modeled GB/s per payload for the three sync variants (psum and
    ring share the ring-allreduce model — XLA's psum IS a ring; q8 runs
    the same model at its int8 wire size, reported as ALGORITHM GB/s —
    logical payload over wall time, the EQuARX framing where the
    quantized collective looks ~4× faster because it moves ~¼ the
    bytes). Modeled, labeled, never passed off as measured."""
    from mpit_tpu.utils import (
        modeled_all_gather_seconds,
        modeled_allreduce_seconds,
        modeled_reduce_scatter_seconds,
    )

    out = {}
    for mb in mbs:
        payload = mb * 2**20
        t_ring = modeled_allreduce_seconds(payload, p)
        wire_q8 = _q8_wire_bytes(payload, p)
        t_q8 = modeled_reduce_scatter_seconds(
            wire_q8, p
        ) + modeled_all_gather_seconds(wire_q8, p)
        out[str(mb)] = {
            "psum": round(payload / t_ring / 1e9, 2),
            "ring": round(payload / t_ring / 1e9, 2),
            "q8": round(payload / t_q8 / 1e9, 2),
        }
    return out


def bench_allreduce(payload_mb: int = 64, iters: int = 10):
    """The BASELINE "allreduce GB/s" metric — now a three-way record
    (ISSUE 9): stock ``lax.psum`` vs the in-kernel Pallas ring vs the
    quantized (int8 + per-chunk scales) ring.

    Measured only on TPU with >1 device; elsewhere (1 chip, or a CPU
    mesh whose "wire" is memcpy) the latency-aware ICI ring model for 8
    chips is reported and labeled — never passed off as measured
    (SURVEY.md §8.4.5). GB/s is ALGORITHM bandwidth (logical payload /
    time, the MPI convention) for every variant — the q8 figure exceeds
    the wire ceiling by design since its wire bytes are ~¼ the payload.
    """
    import mpit_tpu
    from jax.sharding import PartitionSpec as P
    from mpit_tpu.comm import collectives as C
    from mpit_tpu.utils import TPU_V5E, allreduce_gbps

    world = mpit_tpu.init()
    n = world.num_devices
    platform = jax.devices()[0].platform
    payload = payload_mb * 1024 * 1024
    if n == 1 or platform != "tpu":
        from mpit_tpu.utils import modeled_allreduce_seconds

        # Latency-aware ring model (utils/profiling.py): the derived
        # GB/s MOVES with payload (small payloads latency-bound, large
        # ones approach the 2×ICI wire ceiling). Off-TPU the ring
        # kernels fall back to lax anyway (mode-stamped), so a
        # multi-device CPU "measurement" would time memcpy — the model
        # is the only honest figure here. Still modeled, still labeled.
        modeled = payload / modeled_allreduce_seconds(payload, 8) / 1e9
        curves = _modeled_allreduce_curves((1, 4, 16, 64, 256))
        at = curves[str(payload_mb)] if str(payload_mb) in curves else (
            _modeled_allreduce_curves((payload_mb,))[str(payload_mb)]
        )
        return {
            "gbps": round(modeled, 2),
            # ring == psum by model (both are bandwidth-optimal rings);
            # the MEASURED separation is what a TPU run records.
            "ring_gbps": at["ring"],
            "q8_gbps": at["q8"],
            "modeled": True,
            "platform": platform,
            "payload_mb": payload_mb,
            "by_payload_mb": curves,
            "q8_wire_bytes_at_payload": round(_q8_wire_bytes(payload, 8)),
            "ici_hop_latency_us_assumed": TPU_V5E.ici_hop_latency * 1e6,
            "note": f"{n} device(s) on {platform}: latency-aware ICI "
                    "ring estimate for 8 chips; no GB/s measured off-TPU",
        }
    # Ring variants measure the BUCKETED production path (GradSync,
    # 4 MB buckets — the configuration grad_sync="ring|ring_q8"
    # actually runs): the ring kernels are VMEM-resident, so a
    # monolithic 64 MB payload would not even compile; the bucket loop
    # is the real wire schedule. allreduce_grads is mean-semantics
    # (sum + a scalar multiply) — bandwidth-equivalent to psum.
    from mpit_tpu.train import GradSync

    ring_sync = GradSync("data", "ring")
    q8_sync = GradSync("data", "ring_q8")
    variants = (
        ("psum", lambda v: C.allreduce(v, "data")),
        ("ring", lambda v: ring_sync.allreduce_grads(v)),
        ("q8", lambda v: q8_sync.allreduce_grads(v)),
    )

    def timed(body, xs, reps):
        # MPI convention (and the modeled branch above): each device
        # reduces a payload-sized PER-RANK buffer — n × payload bytes
        # globally, one shard per device.
        f = jax.jit(
            world.shard_map(body, in_specs=P("data"), out_specs=P("data"))
        )
        out = f(xs)
        float(out[0, 0])  # warm + force
        t0 = time.perf_counter()
        for _ in range(reps):
            out = f(out)
        float(out[0, 0])
        return (time.perf_counter() - t0) / reps

    # One pass over the ladder; the headline payload is measured at the
    # full ``iters`` count and its row doubles as the headline figures
    # (no second compile+measurement of the same geometry).
    ladder = {}
    for mb in (1, 4, 16, 64, 256):
        pl_b = mb * 2**20
        xs = jnp.ones((n, pl_b // 4), jnp.float32)
        reps = iters if pl_b == payload else max(3, iters // 2)
        ladder[str(mb)] = {
            name: round(allreduce_gbps(pl_b, n, timed(body, xs, reps)), 2)
            for name, body in variants
        }
    headline = ladder.get(str(payload_mb))
    if headline is None:  # off-ladder payload: measure it directly
        xs = jnp.ones((n, payload // 4), jnp.float32)
        headline = {
            name: round(allreduce_gbps(payload, n, timed(body, xs, iters)), 2)
            for name, body in variants
        }
    return {
        "gbps": headline["psum"],
        "ring_gbps": headline["ring"],
        "q8_gbps": headline["q8"],
        "modeled": False,
        "platform": platform,
        "devices": n,
        "payload_mb": payload_mb,
        "by_payload_mb": ladder,
        "q8_wire_bytes_at_payload": round(_q8_wire_bytes(payload, n)),
    }


def _round1_baselines():
    """Round-1 recorded values — the cross-round baseline per the judge's
    protocol ("the measured single-chip numbers are the cross-round
    baseline now", VERDICT.md round 1): AlexNet img/s, GPT-2 tok/s."""
    return 18007.75, 66687.0


def bench_mnist_easgd(steps: int = 120, replicas: int = 2):
    """The elastic EASGD tier's robustness record (ISSUE 11).

    Four seeded runs on the synthetic-MNIST accuracy loop:

    1. sync-SPMD baseline (the accuracy oracle);
    2. no-fault elastic fleet (1 anchor + ``replicas`` replicas on
       ``hardened_loop``) — ``easgd_acc_delta_vs_sync`` is the pinned
       "matches sync within noise" contract (EQuARX-style accuracy pin);
    3. the same fleet with an injected straggler (``FaultPlan.slowdown``
       on the last replica): ``straggler_healthy_throughput_pct`` =
       healthy replicas' best-window throughput vs the no-fault run —
       the "a straggler delays only its own anchor pulls" claim,
       measured; the flight recorder's skew report names the straggler;
    4. kill-at-step + crash-consistent checkpoint rejoin:
       ``rejoin_steps_to_recover`` = steps re-trained after restoring
       the latest atomic checkpoint.

    All faults come from seeded ``FaultPlan``s — rerunning this workload
    reproduces the same event sequences.
    """
    from mpit_tpu import obs
    from mpit_tpu.asyncsgd import mnist
    from mpit_tpu.compat import FaultPlan, Slowdown

    import tempfile

    batch_size = 32
    base_args = [
        "--steps", str(steps), "--batch-size", str(batch_size),
        "--log-every", "10", "--seed", "0",
    ]
    elastic_args = base_args + [
        "--mode", "elastic", "--nranks", str(replicas + 1),
        "--sync-every", "4", "--easgd-beta", "0.5",
        "--heartbeat-s", "0.05", "--lease-s", "0.4",
    ]
    straggler_rank = replicas  # last replica (ranks are 1..replicas)

    with obs.span("staging", what="sync_baseline"):
        sync = mnist.main(list(base_args))
    sync_acc = sync["eval"]["top1"]

    def _tput(run, ranks):
        # MEAN logged-window items/sec per replica (compile excluded by
        # window construction; the mean, not the best, because replica
        # threads share host cores and per-window rates are scheduling-
        # noisy), averaged over the requested replica indices. No
        # silent fallback: a replica without the figure (fewer than two
        # log windows) would force a different unit basis — fail loudly
        # instead; the workload then records an "error" entry.
        vals = []
        for i in ranks:
            v = run["replica_stats"][i].get("items_per_sec_mean")
            if v is None:
                raise RuntimeError(
                    f"replica {i} recorded no items_per_sec_mean — "
                    "steps_per_replica/log_every leave <2 logged windows"
                )
            vals.append(v)
        return sum(vals) / len(vals)

    with obs.span("timed_window", what="elastic_nofault"):
        nofault = mnist.main(list(elastic_args))
    acc = nofault["eval"]["accuracy"]

    with obs.span("timed_window", what="elastic_straggler"):
        straggler = mnist.main(
            list(elastic_args),
            fault_plan=FaultPlan(
                seed=0, slowdown={straggler_rank: Slowdown(0.03)}
            ),
        )
    healthy = list(range(replicas - 1))  # replica indices, straggler last
    healthy_pct = 100.0 * _tput(straggler, healthy) / _tput(nofault, healthy)
    skew = straggler["flight"]["skew"].get("step", {})

    # Kill OFF the checkpoint cadence (ckpt_every=10): a kill landing
    # exactly on a just-saved step would make rejoin_steps_to_recover a
    # vacuous 0 — the metric is the re-trained gap, so put the kill
    # mid-interval.
    kill_step = max(steps // replicas // 2, 10) + 5
    with obs.span("timed_window", what="elastic_kill_rejoin"):
        with tempfile.TemporaryDirectory() as td:
            kill = mnist.main(
                list(elastic_args)
                + ["--ckpt-dir", td, "--ckpt-every", "10"],
                fault_plan=FaultPlan(
                    seed=0, kill_at={1: kill_step}, rejoin_delay_s=0.6
                ),
            )
    killed = kill["replica_stats"][0]

    return {
        "easgd_acc_delta_vs_sync": round(acc - sync_acc, 4),
        "straggler_healthy_throughput_pct": round(healthy_pct, 1),
        "rejoin_steps_to_recover": killed.get("rejoin_steps_to_recover"),
        # Fleet/fault geometry + per-scenario evidence: detail-only.
        "replicas": replicas,
        "steps_per_replica": nofault["steps_per_replica"],
        "sync_accuracy": round(sync_acc, 4),
        "elastic_accuracy": round(acc, 4),
        "anchor_version": nofault["anchor_version"],
        "straggler": {
            "rank": straggler_rank,
            "slowdown_s_per_step": 0.03,
            "healthy_items_per_sec": round(_tput(straggler, healthy), 1),
            "nofault_items_per_sec": round(_tput(nofault, healthy), 1),
            "straggler_named_by_skew": skew.get("max_rank") == straggler_rank,
            "step_skew_s": skew.get("skew_s"),
            "staleness_events": sum(
                1 for e in straggler["server"]["events"]
                if e[0] == "staleness_exceeded"
            ),
            "accuracy": round(straggler["eval"]["accuracy"], 4),
        },
        "kill_rejoin": {
            "kill_step": kill_step,
            "evictions": kill["server"]["evictions"],
            "rejoins": kill["server"]["rejoins"],
            "crashes": killed["crashes"],
            "completed": killed["completed"],
            "accuracy": round(kill["eval"]["accuracy"], 4),
            "acc_delta_vs_nofault": round(
                kill["eval"]["accuracy"] - acc, 4
            ),
        },
    }


def bench_gpt2_fleet(
    prompt_len: int = 16,
    max_new: int = 48,
    requests: int = 16,
    decode_counts: tuple = (1, 2),
    slots: int = 4,
    max_len: int = 96,
):
    """The disaggregated serving fleet's throughput record (ISSUE 19):
    router + 1 prefill worker + a swept number of decode workers on the
    compat layer, the SAME seeded request set at every point, KV pages
    shipped prefill → decode over ``Comm_dup("fleet-kv")``.

    Record line: ``fleet_req_per_s`` (the headline — requests completed
    per wall second at the LARGEST decode count) and ``workers`` (the
    compact topology stamp, e.g. ``"1p+2d"``, without which the rate is
    uninterpretable). The per-decode-count curve, the scaling ratio vs
    the single-decode point, shipment byte totals and the liveness
    counters are detail-only.

    Each worker's engine is pinned to its OWN device (``rank %
    n_devices``) — the disaggregation analogue: a fleet exists because
    every worker owns an accelerator, and two engines sharing one
    device would serialize in the XLA execution stream by
    construction. The scaling claim is only measurable where that
    pinning buys real parallel silicon: on the CPU simulator the
    decode workers' ticks still serialize on the host (one GIL for
    every dispatch, one shared XLA host threadpool for every fake
    device), so ``req_per_s_scaling`` honestly reads ~1.0 there — a
    measured fact about this host, platform-labeled via the record's
    top-level ``platform``, never extrapolated into a fabricated
    multi-chip figure (roofline honesty rule). Wall time includes each
    worker's engine build; the compiles are paid ONCE up front
    (``warm_engine`` per device + the persistent compile cache) so
    every point replays them identically and the curve compares fleet
    topology, not the compiler.
    """
    import numpy as np

    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.serve import Engine, Request, run_fleet, warm_engine

    cfg = GPT2Config.tiny(
        vocab_size=512, max_seq_len=max_len, num_layers=4, num_heads=4,
        d_model=256,
    )
    params = jax.jit(GPT2(cfg).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    devices = jax.devices()

    def factory(role, rank):
        dev = devices[max(rank, 0) % len(devices)]
        with jax.default_device(dev):
            return Engine(
                cfg, jax.device_put(params, dev), slots=slots,
                max_len=max_len, prefill_len=prompt_len,
            )

    rng = np.random.RandomState(0)
    reqs = [
        Request(
            rid=f"f{i}",
            prompt=[int(t) for t in rng.randint(1, cfg.vocab_size,
                                                size=prompt_len)],
            max_new_tokens=max_new,
        )
        for i in range(requests)
    ]

    # Pay every device's compiles ONCE before any timed point: engines
    # are per-rank and each rank pins its own device, so warm the
    # LARGEST topology's worth of workers (prefill rank 1, decode
    # ranks 2..1+max). Timed points then replay cached executables and
    # the curve compares fleet topology, not the compiler.
    for rank in range(1, 2 + max(decode_counts)):
        warm_engine(factory("warmup", rank))

    curve = {}
    ship_bytes = evictions = 0
    for d in decode_counts:
        t0 = time.perf_counter()
        res = run_fleet(factory, reqs, prefill=1, decode=d)
        wall = time.perf_counter() - t0
        done = len(res["completed"])
        if done != requests:
            raise RuntimeError(
                f"fleet bench point decode={d} completed {done}/{requests}"
            )
        curve[str(d)] = {
            "req_per_s": round(done / wall, 2),
            "wall_s": round(wall, 2),
        }
        ship_bytes = sum(
            w.get("ship_bytes", 0) for w in res["workers"]
            if w["role"] == "prefill"
        )
        evictions = res["router"]["evictions"]
    d_top = str(max(decode_counts))
    d_one = str(min(decode_counts))
    return {
        "fleet_req_per_s": curve[d_top]["req_per_s"],
        "workers": f"1p+{d_top}d",
        "req_per_s_scaling": round(
            curve[d_top]["req_per_s"] / curve[d_one]["req_per_s"], 3
        ),
        "by_decode_workers": curve,
        "requests": requests,
        "generated_tokens": requests * max_new,
        "prompt_len": prompt_len,
        "max_new_tokens": max_new,
        "ship_bytes": ship_bytes,
        "evictions": evictions,
    }


def _phase_breakdown(s: dict) -> dict:
    """Per-workload obs roll-up for BENCH_DETAIL.json (never the record
    line — ``_LINE_KEYS`` whitelists what rides there): where the
    workload's wall clock went, plus the top collectives by modeled
    wire bytes from the trace-time accounting in comm/collectives.
    ``s`` is the workload's ``Recorder.summary()`` (computed once in
    main, shared with the obs_baseline snapshot)."""
    out = {
        name: {"count": p["count"], "total_s": round(p["total_s"], 3)}
        for name, p in s["phases"].items()
    }
    if s["collectives"]:
        out["top_collectives"] = [
            {**c, "wire_bytes": round(c["wire_bytes"], 1)}
            for c in s["collectives"]
        ]
    return out


# ---------------------------------------------------------------------------
# Driver-contract record building (unit-tested: tests/test_bench_contract.py)
# ---------------------------------------------------------------------------

# Per-workload keys that ride ON THE LINE; everything else detail-file-only.
_LINE_KEYS = {
    # app_path_images_per_sec is byte-for-byte the record's headline
    # ``value`` — dropped from the per-workload detail (with gpt2's
    # derivable vs_r1_app_path) to pay for ISSUE 7's serve triple
    # inside the ≤1.2k budget; BENCH_DETAIL.json keeps the full dict.
    # mfu_pct (ISSUE 8): the train workloads' utilization verdict rides
    # the line (null off-TPU — platform-labeled in the detail file's
    # roofline block, never fabricated); the full measured-vs-modeled
    # roofline table stays detail-only. To hold the ≤1.2k budget,
    # ms_per_step moved detail-only everywhere — it is EXACTLY
    # derivable from the line (ms_per_step = items_per_step /
    # items_per_sec × 1e3, both already on the line).
    # ISSUE 11 pays for the mnist_easgd triple by moving more
    # derivable/static echo detail-only: alexnet's global_batch and
    # gpt2/gpt2_moe's batch + seq_len (fixed workload geometry),
    # gpt2's app_path_tokens_per_sec (EXACTLY tokens_per_sec x
    # (1 - app_path_overhead_pct/100), both still on the line), and
    # gpt2_moe's final_loss (in BENCH_DETAIL.json verbatim, with the
    # whole drop-rate trajectory).
    # ISSUE 12 pays for gpt2_policy's triple by moving the remaining
    # train-workload final_loss echoes detail-only (gpt2_moe's went in
    # ISSUE 11; the convergence pins live in tests and the values land
    # in BENCH_DETAIL.json verbatim), gpt2_serve's kv_page_size (static
    # geometry) and gpt2_slo's ttft_target_s (the sweep's calibration
    # context — headline + breach count keep the verdict on the line).
    "alexnet": (
        "images_per_sec", "mfu_pct",
        "error",
    ),
    # To pay for ISSUE 9's allreduce pair inside the ≤1.2k budget,
    # static config echo moved detail-only: resnet50's global_batch and
    # gpt2's seq_len (both fixed workload geometry, in BENCH_DETAIL.json
    # verbatim), plus the allreduce entry's devices (byte-for-byte the
    # record's top-level detail.devices).
    "resnet50": (
        "images_per_sec", "mfu_pct",
        "error",
    ),
    # fleet_req_per_s + workers (ISSUE 19): the disaggregated fleet's
    # throughput headline and the topology stamp that makes it
    # readable. Paid for by demoting gpt2's train-side "attention"
    # label (static engine config — the flash-vs-reference resolution
    # is pinned per-platform by tier-1's fallback tests, the same
    # argument that moved decode_attention off the serve line for
    # ISSUE 17; verbatim in BENCH_DETAIL.json) and gpt2_serve's
    # max_concurrent_at_hbm (the MODELED fixed-budget concurrency
    # experiment — ISSUE 18's measured hbm_held_peak_bytes +
    # kv_headroom_min_pct are the line's capacity verdict now; the
    # experiment stays verbatim in the paged_capacity detail block
    # where its A/B context lives).
    "gpt2": (
        "tokens_per_sec",
        "app_path_overhead_pct", "mfu_pct",
        "error",
    ),
    "gpt2_moe": (
        "tokens_per_sec", "mfu_pct",
        "error",
    ),
    # ISSUE 7 grows the serve line by the paged-cache headline triple:
    # max concurrent requests at the fixed HBM budget, the prefix-hit
    # rate behind it, and the page size defining both; the capacity and
    # chunked-prefill blocks stay detail-only.
    # engine_compiles (ISSUE 8): the pinned engine-lifetime compile
    # count. To pay for it, latency_p50_s (the SLO-relevant p95 stays)
    # and the static slots geometry moved detail-only.
    # accepted_tokens_per_tick (ISSUE 13): the speculative tokens-per-
    # slot-tick multiplier from the A/B block (1.0 = plain decode);
    # paid for by demoting decode_hbm_util_pct detail-only — it is
    # EXACTLY derivable from detail keys (decode_hbm_gbps_modeled /
    # the roofline_platform chip's HBM peak; null off-TPU anyway).
    # kv_dtype + q8_capacity_ratio (ISSUE 15): the headline stream's
    # cache wire dtype (bandwidth/capacity figures are uninterpretable
    # without it) and the int8-vs-bf16 concurrency ratio at the same
    # pool HBM budget; paid for by demoting latency_p95_s (the
    # SLO-relevant p95 verdicts live on the gpt2_slo/gpt2_policy
    # lines) and engine_compiles (its value is PINNED to the engine's
    # lifetime constant by tier-1 — tests/test_serve.py — so the line
    # key carried no information; BENCH_DETAIL.json keeps it verbatim
    # and an unexpected recompile still fails the suite) detail-only.
    # trace_overhead_pct + exemplars_retained (ISSUE 16): the request-
    # ledger's aggregate-arm decode cost (the always-on production
    # config — the acceptance bar is <1%, and the line is where that
    # verdict must be readable) and the exemplar count proving tail
    # capture ran; the forensics snapshot (why-slow's input) is
    # detail-only. Paid for by demoting prefix_hit_rate (the mechanism
    # BEHIND max_concurrent_at_hbm, which keeps the capacity verdict on
    # the line) and kv_dtype (static engine config, pinned by tier-1 —
    # the q8 ratio already names the comparison) — both verbatim in
    # BENCH_DETAIL.json.
    # weights_dtype + q8w_bytes_ratio (ISSUE 17): the headline stream's
    # weight wire dtype (the param read DOMINATES the decode tick, so
    # byte figures are uninterpretable without it) and the modeled
    # int8-vs-f32 whole-tick decode-bytes ratio from the weights A/B.
    # Paid for by demoting decode_attention (static engine config — the
    # kernel-vs-reference resolution is pinned per-platform by tier-1's
    # fallback tests and lands in BENCH_DETAIL.json verbatim, so
    # ISSUE 5's attributability survives in the detail file) and
    # exemplars_retained (its ≥1 pin lives in the artifact test —
    # TestForensicsArtifact — and trace_overhead_pct keeps the ledger
    # verdict on the line) — both verbatim in BENCH_DETAIL.json.
    # hbm_held_peak_bytes + kv_headroom_min_pct (ISSUE 18): the memory
    # ledger's MEASURED held-bytes peak for the headline stream and the
    # KV headroom floor it bottomed out at — the capacity verdict is
    # now byte-exact accounting, not a model. Paid for by demoting
    # the MODELED byte projections the measured ledger supersedes —
    # q8_capacity_ratio and q8w_bytes_ratio (both verbatim in their
    # quantized_kv / quantized_weights detail blocks, where the A/B
    # context that makes them interpretable lives) — plus
    # weights_dtype (static engine config pinned by tier-1, verbatim
    # in BENCH_DETAIL.json).
    "gpt2_serve": (
        "decode_tokens_per_sec",
        "accepted_tokens_per_tick",
        "hbm_held_peak_bytes", "kv_headroom_min_pct",
        "trace_overhead_pct", "error",
    ),
    # The SLO sweep's line is the headline triple only — the sustained
    # rate, the target that defines it, and the breach count proving the
    # ladder actually crossed saturation; the curve, calibration,
    # geometry and engine mode are detail-file-only (the ≤1.2k budget
    # holds with margin; gpt2_moe's dispatch label and gpt2_serve's
    # request count moved detail-only to pay for it — every full dict
    # still lands in BENCH_DETAIL.json verbatim).
    "gpt2_slo": (
        "max_sustained_req_per_s", "slo_breaches",
        "error",
    ),
    # ISSUE 12: the policy A/B's headline pair — max sustained req/s
    # under the POLICY at p95 interactive TTFT ≤ target (the FIFO
    # counterpart it must beat sits in detail) and the policy's
    # interactive-tier p95 at the top swept rate. Curve, calibration,
    # geometry, target and the FIFO numbers are detail-file-only; the
    # budget payment is itemized above the alexnet entry.
    # tiering (ISSUE 20): the HBM→host A/B's verdict object — p95
    # resume-via-restream vs resume-via-recompute on the drained
    # long-tail trace, and the prefix hit rate the host tier held up
    # under pool pressure ("hit_rate"; the untiered counterpart and
    # the byte/counter evidence live in tiering_detail). Paid for by
    # demoting preemptions (a non-null restream_p95_ms REQUIRES the
    # preempt→park→resume path to have run, so the count's
    # proof-of-work role is subsumed; verbatim per-point in detail),
    # alexnet's app_path_overhead_pct (EXACTLY derivable on the line:
    # 100 × (1 − record.value / alexnet.images_per_sec)) and the
    # allreduce ring_gbps (off-TPU it is byte-identical to gbps by the
    # shared ring model; the measured-vs-stock comparison lives in the
    # by_payload_mb detail curve — q8_gbps, the figure with its own
    # information, stays).
    "gpt2_policy": (
        "max_sustained_req_per_s_policy", "interactive_ttft_p95_ms",
        "tiering", "error",
    ),
    # ISSUE 9: the ring and quantized-ring figures ride the line next to
    # the stock one (modeled off-TPU — the `modeled` flag labels all
    # three); the per-payload three-variant curve stays detail-only.
    "allreduce": ("gbps", "q8_gbps", "modeled", "error"),
    # ISSUE 11: the elastic tier's robustness triple — accuracy parity
    # with sync SPMD, healthy-replica throughput under an injected
    # straggler, and steps re-trained after a kill+rejoin. Fleet/fault
    # geometry and the per-scenario evidence blocks are detail-only.
    "mnist_easgd": (
        "easgd_acc_delta_vs_sync", "straggler_healthy_throughput_pct",
        "rejoin_steps_to_recover", "error",
    ),
    # ISSUE 19: the fleet headline + topology stamp only (budget
    # payment itemized above the gpt2 entry); the per-decode-count
    # curve, scaling ratio, shipment bytes and liveness counters are
    # detail-file-only.
    "gpt2_fleet": ("fleet_req_per_s", "workers", "error"),
}


def build_record(results: dict, pending=(), truncated=(), elapsed_s=None,
                 baselines=None):
    """The compact driver record: headline + per-workload essentials.

    ``results`` maps workload name → the full dict its bench_* returned
    (absent = not run). The full dicts belong in BENCH_DETAIL.json; this
    record is the ≤1,200-char line. Pure function of its inputs so the
    contract test can pin the line length with canned numbers.
    """
    r1_alex, r1_gpt2 = baselines if baselines else _round1_baselines()
    detail = {}
    for name, keys in _LINE_KEYS.items():
        if name in results:
            full = results[name]
            detail[name] = {k: full[k] for k in keys if k in full}
    gpt2 = detail.get("gpt2")
    if gpt2 and "tokens_per_sec" in gpt2:
        gpt2["vs_r1"] = round(gpt2["tokens_per_sec"] / r1_gpt2, 3)
    alex = results.get("alexnet", {})
    value = alex.get("app_path_images_per_sec")
    rec = {
        # Headline = the APP-PATH number (round-3 verdict item 10): what
        # the training loop actually delivers, one host dispatch per step.
        # vs_baseline keeps the round-1 scanned recording as denominator
        # (the only cross-round constant): "app path now vs headline then".
        "metric": "alexnet_imagenet_app_path_images_per_sec",
        "value": value,
        "unit": "images/sec",
        "vs_baseline": round(value / r1_alex, 3) if value else None,
        "detail": detail,
    }
    if elapsed_s is not None:
        rec["elapsed_s"] = round(elapsed_s, 1)
    if pending:
        rec["pending"] = list(pending)
    if truncated:
        rec["truncated"] = list(truncated)
    rec["detail_file"] = "BENCH_DETAIL.json"
    return rec


class _Emitter:
    """Writes BENCH_DETAIL.json + prints the compact line after every
    completed workload, so a driver kill at ANY point leaves the last
    complete record inside its 2,000-char tail window."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.results: dict = {}
        self.truncated: list = []
        self.platform = jax.devices()[0].platform
        self.devices = jax.device_count()
        # emit() runs on BOTH the main thread (per-workload) and the
        # watchdog timer thread (timeout path); without mutual exclusion
        # the two interleave the BENCH_DETAIL.json rename with the final
        # record print (round-5 advisor finding). One lock serializes
        # whole emissions; the last writer's line is last in the tail.
        self._lock = threading.Lock()

    def emit(self, pending=(), lock_timeout=None):
        """``lock_timeout`` (watchdog path): best-effort acquire so a
        main thread wedged INSIDE _emit_locked (stalled stdout pipe,
        hung filesystem) cannot keep the watchdog from its os._exit —
        the wedged emitter's already-printed line is the record then."""
        if lock_timeout is None:
            with self._lock:
                return self._emit_locked(pending)
        if self._lock.acquire(timeout=lock_timeout):
            try:
                return self._emit_locked(pending)
            finally:
                self._lock.release()
        return None

    def _emit_locked(self, pending=()):
        elapsed = time.perf_counter() - self.t0
        rec = build_record(
            self.results, pending=pending, truncated=self.truncated,
            elapsed_s=elapsed,
        )
        rec["detail"]["devices"] = self.devices
        rec["detail"]["platform"] = self.platform
        try:
            # tmp + atomic rename (same pattern as train/checkpoint.py's
            # run_meta): a watchdog os._exit mid-dump must never leave a
            # half-written file where the record line points.
            path = os.path.join(_REPO, "BENCH_DETAIL.json")
            with open(path + ".tmp", "w") as f:
                json.dump(
                    {
                        "elapsed_s": round(elapsed, 1),
                        "devices": self.devices,
                        "platform": self.platform,
                        "pending": list(pending),
                        "truncated": self.truncated,
                        "workloads": self.results,
                    },
                    f,
                    indent=1,
                )
            os.replace(path + ".tmp", path)
        except OSError as e:
            rec["detail_file_error"] = str(e)[:80]
        line = json.dumps(rec)
        print(line, flush=True)
        return line


def _errored(results: dict) -> list:
    """Names of the workloads whose result is an error record."""
    return [n for n, r in list(results.items()) if "error" in r]


def main() -> int:
    _enable_compile_cache()  # before the first trace (see its docstring)
    t0 = time.perf_counter()
    budget = float(os.environ.get("MPIT_BENCH_BUDGET_S", "420"))
    em = _Emitter(t0)

    # Headline-first ordering; each entry = (name, fn). The modeled
    # allreduce figure is free, so it rides along from the start.
    workloads = [
        ("allreduce", bench_allreduce),
        ("alexnet", bench_alexnet),
        ("gpt2", bench_gpt2),
        ("resnet50", bench_resnet),
        ("gpt2_moe", bench_moe),
        ("gpt2_serve", bench_gpt2_serve),
        ("gpt2_slo", bench_gpt2_slo),
        ("gpt2_policy", bench_gpt2_policy),
        ("mnist_easgd", bench_mnist_easgd),
        ("gpt2_fleet", bench_gpt2_fleet),
    ]

    def _watchdog():
        # Hard stop: force out the record-so-far and exit clean — runs
        # on a daemon thread so it fires even while the main thread is
        # blocked in a GIL-RELEASING native call (XLA compiles and
        # device fetches, the two ways a workload actually gets stuck
        # here). A native loop that held the GIL would still block it,
        # but then nothing in-process could run; progressive emission
        # (the already-printed lines in the driver's tail) is the
        # backstop for that case.
        try:
            remaining = [n for n, _ in workloads if n not in em.results]
            em.truncated.extend(
                n for n in remaining if n not in em.truncated
            )
            em.emit(lock_timeout=15.0)
        finally:
            # Exit unconditionally: an emit() error here (e.g. a dict
            # mutated concurrently by the main thread) must not leave
            # the process alive past the driver's timeout.
            os._exit(1 if _errored(em.results) else 0)

    watchdog = threading.Timer(budget * 1.2 + 30, _watchdog)
    watchdog.daemon = True
    watchdog.start()

    from mpit_tpu import obs

    for i, (name, fn) in enumerate(workloads):
        elapsed = time.perf_counter() - t0
        if elapsed > budget:
            em.truncated.extend(n for n, _ in workloads[i:])
            break
        t_w = time.perf_counter()
        # Fresh recorder per workload: the phase breakdown attached to
        # BENCH_DETAIL.json covers exactly this workload's events
        # (staging/warmup/timed windows + trace-time collective bytes).
        rec = obs.enable(obs.Recorder())
        try:
            with obs.span("workload", workload=name):
                em.results[name] = fn()
        except Exception as e:  # one workload must not kill the artifact
            em.results[name] = {
                "error": f"{type(e).__name__}: {e}"[:200]
            }
        # Wall seconds the workload took end to end (compile + staging +
        # measurement) — the time-budget diagnostic; detail-file only.
        em.results[name]["wall_s"] = round(time.perf_counter() - t_w, 1)
        summ = rec.summary(top_collectives=3)
        em.results[name]["phases"] = _phase_breakdown(summ)
        # Perf-regression gate input (ISSUE 3; obs/baseline.py): the
        # full per-phase snapshot (count/total/p50/p95) in the shape
        # `python -m mpit_tpu.obs diff BENCH_DETAIL.json <new> --workload
        # <name>` consumes — so two bench rounds diff mechanically.
        # Only for workloads that actually MEASURED: an errored one
        # would snapshot just its enclosing 'workload' span, and a
        # later diff against that gate-passes vacuously (every real
        # phase lands in new_phases, which is reported, not gated).
        if "error" not in em.results[name]:
            em.results[name]["obs_baseline"] = obs.baseline.snapshot(
                summ, meta={"workload": name},
                # ISSUE 18: memory-gate input — held_peak_bytes +
                # headroom floor ride the baseline so two bench rounds
                # diff memory growth mechanically (only stored when the
                # workload actually carried ledger data; never gates
                # vacuously).
                memory=em.results[name].get("memory"),
            )
        em.emit(pending=[n for n, _ in workloads[i + 1:]])

    obs.disable()
    watchdog.cancel()
    em.emit()
    # The record keeps every workload that did measure, but a run in
    # which one errored is not a clean run.
    return 1 if _errored(em.results) else 0


if __name__ == "__main__":
    raise SystemExit(main())
