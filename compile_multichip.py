"""AOT-compile the framework's multi-chip programs for a REAL v5e-8 topology.

The CPU fake-mesh dryrun (``__graft_entry__.dryrun_multichip``) validates
semantics; this check validates what only the real TPU compiler can see —
Mosaic kernel lowering, layout-pass tile padding, per-chip memory. No TPU
pod is needed: ``jax.experimental.topologies`` supplies device proxies and
the installed TPU compiler does the rest (``mpit_tpu/utils/aot.py``).

Run: ``python compile_multichip.py [topology]`` (default ``v5e:2x4``).
Writes ``MULTICHIP_AOT.json`` with per-phase status + compiled-memory
numbers; exits non-zero if any phase fails to compile.

Phases (mirroring the dryrun, plus the memory-regression shape):

1.  ``dp-zero1``        — GPT-2 small DP step, goo state sharded (ZeRO-1).
2.  ``dp-zero1-moe322m``— the 322M-param GPT-2-MoE step with ZeRO-1 ON:
    the exact configuration whose 1-D flat scatter tile-padded 16x and
    compile-OOMed in round 3 (bench.py r3 docstring). Asserts temp memory
    stays under 4x the parameter payload.
3.  ``tp``              — GSPMD tensor-parallel GPT-2 step.
4.  ``pp-1f1b``         — pipeline parallel, 1F1B schedule, ZeRO-1.
5.  ``pp-interleaved-v2`` — interleaved 1F1B, V=2 virtual stages per
    device (round-5: closes the last un-AOT'd schedules).
6.  ``3d-dp-tp-pp``     — Megatron blocks as pipeline stages.
7.  ``3d-dp-cp-tp``     — ring attention inside the TP block (Pallas
    ring-flash kernel compiled by Mosaic for the topology).
8.  ``ulysses-in-tp``   — the Ulysses seq↔head all-to-all inside the
    Megatron block on the dp×cp×tp mesh (round-5).
9.  ``cp-long-context-16k`` — the CP training step at 16,384 global
    tokens over 8 ring shards (per-shard T=2048 under the flash
    kernel's auto head-grouping).
10. ``ep-moe``          — expert-parallel MoE, per-group ZeRO-1 (round 5:
    the sort/ragged dispatch — the one-hot path's [S,E,C] memory is gone).
11. ``hybrid-dcn``      — the slice-major hybrid-mesh DP step over two
    VIRTUAL slices (see phase docstring for the topology-API limitation).
12. ``pallas-ring-allreduce`` — the native-tier DMA kernel.
"""

from __future__ import annotations

import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from mpit_tpu.utils import compile_cache_dir
from mpit_tpu.utils.aot import (
    abstractify,
    aot_compile,
    memory_report,
    topology_world,
)


def _kernel_report(compiled) -> dict:
    """memory_report, for a phase whose point is a Pallas kernel: the
    compiled program must contain one. The ops decide "kernel or lax"
    from the attached platform, which is ``cpu`` wherever this script
    can run, so a kernel phase that is not steered compiles the lax
    composition and would otherwise pass having compiled no kernel."""
    n = compiled.as_text().count("tpu_custom_call")
    if not n:
        raise AssertionError("no tpu_custom_call in the compiled program")
    return {"custom_calls": n, **memory_report(compiled)}


def _params_mb(params) -> float:
    return sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree.leaves(params)
    ) / 2**20


def _abstract_params(model, *init_args):
    out = jax.eval_shape(
        lambda: model.init(jax.random.key(0), *init_args)
    )
    return out["params"]


def phase_dp_zero1(topology):
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.train import make_train_step

    world = topology_world({"data": 8}, topology)
    seq, batch = 512, 48
    cfg = GPT2Config.small(max_seq_len=seq, head_dtype=jnp.bfloat16)
    model = GPT2(cfg)
    params = _abstract_params(model, jnp.zeros((1, seq), jnp.int32))

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["tokens"][:, :-1])
        return GPT2.loss_fn(logits, b["tokens"]), {}

    init_fn, step_fn, state_specs = make_train_step(
        loss_fn, goo_adam(3e-4), world, zero1=True
    )
    state = abstractify(
        jax.eval_shape(init_fn, params), world.mesh, state_specs(params)
    )
    batch_abs = abstractify(
        {"tokens": jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)},
        world.mesh,
        P("data"),
    )
    compiled = aot_compile(step_fn.build(params), state, batch_abs)
    return {"params_mb": round(_params_mb(params), 1), **memory_report(compiled)}


def phase_dp_zero1_moe322m(topology):
    """The round-3 compile-OOM configuration, ZeRO-1 ON."""
    from mpit_tpu.models import GPT2Config
    from mpit_tpu.models.gpt2_moe import GPT2MoE, MoESettings
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.train import make_train_step

    world = topology_world({"data": 8}, topology)
    seq, batch = 256, 64
    cfg = GPT2Config.small(max_seq_len=seq, head_dtype=jnp.bfloat16)
    model = GPT2MoE(cfg, MoESettings(num_experts=8, k=2, capacity_factor=1.25, every=2))
    params = _abstract_params(model, jnp.zeros((1, seq), jnp.int32))

    def loss_fn(p, b):
        losses, aux = model.apply(
            {"params": p}, b["tokens"][:, :-1], targets=b["tokens"][:, 1:]
        )
        return jnp.mean(losses) + 0.01 * aux, {}

    init_fn, step_fn, state_specs = make_train_step(
        loss_fn, goo_adam(3e-4), world, zero1=True, scan_steps=2
    )
    state = abstractify(
        jax.eval_shape(init_fn, params), world.mesh, state_specs(params)
    )
    batch_abs = abstractify(
        {"tokens": jax.ShapeDtypeStruct((2, batch, seq + 1), jnp.int32)},
        world.mesh,
        P(None, "data"),
    )
    compiled = aot_compile(step_fn.build(params), state, batch_abs)
    rep = memory_report(compiled)
    payload = _params_mb(params) * 2**20
    # The regression assertion: round 3's pathology was temp ~16x payload.
    assert rep["temp_bytes"] < 4.0 * payload, (
        f"ZeRO-1 temp memory {rep['temp_bytes']/2**30:.2f} GiB exceeds 4x "
        f"the {payload/2**30:.2f} GiB parameter payload — tile-pad "
        "pathology regressed (opt/sharded.py lane-aligned layout)"
    )
    return {"params_mb": round(payload / 2**20, 1), **rep}


def phase_tp(topology):
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.parallel import gpt2_tp_rules, make_pjit_train_step

    world = topology_world({"data": 4, "model": 2}, topology)
    seq = 512
    # Megatron-style vocab padding: the embedding shards over the model
    # axis, so the vocab must divide by it (50304 = 50257 padded to 128).
    cfg = GPT2Config.small(
        max_seq_len=seq, head_dtype=jnp.bfloat16, vocab_size=50304
    )
    model = GPT2(cfg)
    params = _abstract_params(model, jnp.zeros((1, seq), jnp.int32))

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["tokens"][:, :-1])
        return GPT2.loss_fn(logits, b["tokens"]), {}

    init_fn, step_fn, shardings_fn = make_pjit_train_step(
        loss_fn, goo_adam(3e-4), world, gpt2_tp_rules("model")
    )
    state_shapes = jax.eval_shape(init_fn, params)
    shardings = shardings_fn(params)
    state = jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        state_shapes,
        shardings,
    )
    batch = {"tokens": jax.ShapeDtypeStruct((16, seq + 1), jnp.int32)}
    batch_abs = abstractify(batch, world.mesh, P("data"))
    compiled = aot_compile(step_fn.build(params, batch), state, batch_abs)
    return {"params_mb": round(_params_mb(params), 1), **memory_report(compiled)}


def phase_pp_1f1b(topology):
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.parallel import make_gpt2_pp_train_step, split_gpt2_params

    world = topology_world({"data": 2, "pipe": 4}, topology)
    seq = 256
    cfg = GPT2Config.small(max_seq_len=seq, head_dtype=jnp.bfloat16, tie_head=False)
    model = GPT2(cfg)
    full = _abstract_params(model, jnp.zeros((1, seq), jnp.int32))
    split = jax.eval_shape(
        lambda p: split_gpt2_params(p, cfg.num_layers, 4), full
    )
    init_fn, step_fn, state_specs = make_gpt2_pp_train_step(
        cfg, goo_adam(3e-4), world, num_microbatches=4, zero1=True,
        schedule="1f1b",
    )
    specs = state_specs(split)
    state = abstractify(jax.eval_shape(init_fn, split), world.mesh, specs)
    batch_abs = abstractify(
        {"tokens": jax.ShapeDtypeStruct((8, seq + 1), jnp.int32)},
        world.mesh,
        P("data"),
    )
    compiled = aot_compile(step_fn.build(split), state, batch_abs)
    return {"params_mb": round(_params_mb(full), 1), **memory_report(compiled)}


def phase_pp_interleaved(topology):
    """Interleaved 1F1B (V=2 virtual stages): 4 chunks of 3 layers on a
    pipe=2 mesh — activations circle the ring twice. Round-5 addition:
    the dryrun ran this phase on the CPU mesh only; this is its real-
    compiler certificate (round-4 verdict item 4)."""
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.parallel import (
        make_gpt2_pp_train_step,
        split_gpt2_params_interleaved,
    )

    world = topology_world({"data": 4, "pipe": 2}, topology)
    seq = 256
    cfg = GPT2Config.small(max_seq_len=seq, head_dtype=jnp.bfloat16, tie_head=False)
    model = GPT2(cfg)
    full = _abstract_params(model, jnp.zeros((1, seq), jnp.int32))
    split = jax.eval_shape(
        lambda p: split_gpt2_params_interleaved(p, cfg.num_layers, 2, 2),
        full,
    )
    init_fn, step_fn, state_specs = make_gpt2_pp_train_step(
        cfg, goo_adam(3e-4), world, num_microbatches=4, zero1=True,
        schedule="interleaved", num_chunks=2,
    )
    specs = state_specs(split)
    state = abstractify(jax.eval_shape(init_fn, split), world.mesh, specs)
    batch_abs = abstractify(
        # 32 rows / data=4 → 8 per device = 2 rows × 4 microbatches.
        {"tokens": jax.ShapeDtypeStruct((32, seq + 1), jnp.int32)},
        world.mesh,
        P("data"),
    )
    compiled = aot_compile(step_fn.build(split), state, batch_abs)
    return {
        "virtual_stages": 2,
        "params_mb": round(_params_mb(full), 1),
        **memory_report(compiled),
    }


def phase_3d_dp_tp_pp(topology):
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.parallel import (
        make_gpt2_dp_tp_pp_train_step,
        split_gpt2_params_3d,
    )

    world = topology_world({"data": 2, "model": 2, "pipe": 2}, topology)
    seq = 256
    cfg = GPT2Config.small(max_seq_len=seq, head_dtype=jnp.bfloat16, tie_head=False)
    model = GPT2(cfg)
    full = _abstract_params(model, jnp.zeros((1, seq), jnp.int32))
    split = jax.eval_shape(
        lambda p: split_gpt2_params_3d(p, cfg.num_layers, 2, 2), full
    )
    init_fn, step_fn, state_specs = make_gpt2_dp_tp_pp_train_step(
        cfg, goo_adam(3e-4), world, num_microbatches=2, zero1=True
    )
    specs = state_specs(split)
    state = abstractify(jax.eval_shape(init_fn, split), world.mesh, specs)
    batch_abs = abstractify(
        {"tokens": jax.ShapeDtypeStruct((8, seq + 1), jnp.int32)},
        world.mesh,
        P("data"),
    )
    compiled = aot_compile(step_fn.build(split), state, batch_abs)
    return {"params_mb": round(_params_mb(full), 1), **memory_report(compiled)}


def phase_3d_dp_cp_tp(topology):
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.parallel import (
        make_gpt2_dp_cp_tp_train_step,
        stack_gpt2_blocks,
    )

    world = topology_world({"data": 2, "seq": 2, "model": 2}, topology)
    seq = 512
    cfg = GPT2Config.small(max_seq_len=seq, head_dtype=jnp.bfloat16)
    model = GPT2(cfg)
    full = _abstract_params(model, jnp.zeros((1, seq), jnp.int32))
    stacked = jax.eval_shape(
        lambda p: stack_gpt2_blocks(p, cfg.num_layers, 2), full
    )
    init_fn, step_fn, state_specs = make_gpt2_dp_cp_tp_train_step(
        cfg, goo_adam(3e-4), world, zero1=True, flash=True, interpret=False
    )
    specs = state_specs(stacked)
    state = abstractify(jax.eval_shape(init_fn, stacked), world.mesh, specs)
    batch_abs = abstractify(
        {"tokens": jax.ShapeDtypeStruct((8, seq), jnp.int32)},
        world.mesh,
        P("data", "seq"),
    )
    compiled = aot_compile(step_fn.build(stacked), state, batch_abs)
    return {"params_mb": round(_params_mb(full), 1), **_kernel_report(compiled)}


def phase_ulysses_in_tp(topology):
    """The Ulysses seq↔head all-to-all composed INSIDE the Megatron-TP
    block on the dp×cp×tp mesh (dryrun phase 7b). Round-5 addition: its
    real-compiler certificate (round-4 verdict item 4). GPT-2 small: 12
    heads / model=2 → 6 local heads, divisible by seq=2."""
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.parallel import (
        make_gpt2_dp_cp_tp_train_step,
        stack_gpt2_blocks,
    )

    world = topology_world({"data": 2, "seq": 2, "model": 2}, topology)
    seq = 512
    cfg = GPT2Config.small(max_seq_len=seq, head_dtype=jnp.bfloat16)
    model = GPT2(cfg)
    full = _abstract_params(model, jnp.zeros((1, seq), jnp.int32))
    stacked = jax.eval_shape(
        lambda p: stack_gpt2_blocks(p, cfg.num_layers, 2), full
    )
    init_fn, step_fn, state_specs = make_gpt2_dp_cp_tp_train_step(
        cfg, goo_adam(3e-4), world, zero1=True, ulysses=True
    )
    specs = state_specs(stacked)
    state = abstractify(jax.eval_shape(init_fn, stacked), world.mesh, specs)
    batch_abs = abstractify(
        {"tokens": jax.ShapeDtypeStruct((8, seq), jnp.int32)},
        world.mesh,
        P("data", "seq"),
    )
    compiled = aot_compile(step_fn.build(stacked), state, batch_abs)
    return {"params_mb": round(_params_mb(full), 1), **memory_report(compiled)}


def phase_hybrid_dcn(topology):
    """The slice-major hybrid mesh program (dryrun phase 9), compiled by
    the real TPU compiler. ``jax.experimental.topologies`` describes a
    SINGLE slice, so the two DCN slices here are *virtual* (contiguous
    halves of the v5e:2x4 topology — ``comm.mesh._slice_groups``'s
    documented fallback): the compiled program's mesh layout, collective
    decomposition, and memory are exactly the multi-slice program's; only
    real DCN link latency is invisible at compile time (limitation noted
    in ``utils/aot.py``)."""
    import mpit_tpu
    from mpit_tpu import opt as gopt
    from mpit_tpu.models import LeNet
    from mpit_tpu.train import make_train_step
    from mpit_tpu.utils.aot import topology_devices

    world = mpit_tpu.init_hybrid(
        {"data": 8}, {"data": 2},
        devices=topology_devices(topology), set_default=False,
    )
    model = LeNet()
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)))
    )["params"]

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["image"])
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(
            jnp.take_along_axis(logp, batch["label"][:, None], axis=1)
        )
        return loss, {}

    init_fn, step_fn, state_specs = make_train_step(
        loss_fn, gopt.goo(0.05, 0.9), world, zero1=True
    )
    state = abstractify(
        jax.eval_shape(init_fn, params), world.mesh, state_specs(params)
    )
    batch_abs = abstractify(
        {
            "image": jax.ShapeDtypeStruct((64, 28, 28, 1), jnp.float32),
            "label": jax.ShapeDtypeStruct((64,), jnp.int32),
        },
        world.mesh,
        P("data"),
    )
    compiled = aot_compile(step_fn.build(params), state, batch_abs)
    return {
        "virtual_slices": world.num_slices,
        "params_mb": round(_params_mb(params), 1),
        **memory_report(compiled),
    }


def phase_cp_long_context(topology):
    """Long context for real: 16k global tokens ring-sharded 8 ways
    (per-shard T=2048 — inside the flash kernel's VMEM envelope), the
    Pallas ring-flash + streaming-head CP training step compiled by the
    real TPU compiler. The capability SURVEY §6 long-context row
    promises, proven at a scale one chip could never run."""
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.parallel.cp import make_gpt2_cp_train_step

    world = topology_world({"data": 1, "seq": 8}, topology)
    t_global = 16384
    cfg = GPT2Config.small(max_seq_len=t_global, head_dtype=jnp.bfloat16)
    model = GPT2(cfg)
    params = _abstract_params(model, jnp.zeros((1, 32), jnp.int32))
    init_fn, step_fn, state_specs = make_gpt2_cp_train_step(
        cfg, goo_adam(3e-4), world, zero1=True, flash=True, interpret=False
    )
    specs = state_specs(params)
    state = abstractify(jax.eval_shape(init_fn, params), world.mesh, specs)
    batch_abs = abstractify(
        {"tokens": jax.ShapeDtypeStruct((2, t_global), jnp.int32)},
        world.mesh,
        P("data", "seq"),
    )
    compiled = aot_compile(step_fn.build(params), state, batch_abs)
    return {
        "global_tokens": t_global,
        "seq_shards": 8,
        "params_mb": round(_params_mb(params), 1),
        **_kernel_report(compiled),
    }


def phase_ep_moe(topology):
    from mpit_tpu.models import GPT2Config
    from mpit_tpu.models.gpt2_moe import GPT2MoE, MoESettings
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.parallel import make_gpt2_moe_train_step

    world = topology_world({"data": 2, "expert": 4}, topology)
    seq = 256
    cfg = GPT2Config.small(max_seq_len=seq, head_dtype=jnp.bfloat16)
    moe = MoESettings(num_experts=8, k=2, capacity_factor=1.25, every=2)
    model = GPT2MoE(cfg, moe)
    full = _abstract_params(model, jnp.zeros((1, seq), jnp.int32))
    init_fn, step_fn, state_specs = make_gpt2_moe_train_step(
        cfg, moe, goo_adam(3e-4), world, zero1=True
    )
    specs = state_specs(full)
    state = abstractify(jax.eval_shape(init_fn, full), world.mesh, specs)
    batch_abs = abstractify(
        {"tokens": jax.ShapeDtypeStruct((16, seq + 1), jnp.int32)},
        world.mesh,
        P(("data", "expert")),
    )
    compiled = aot_compile(step_fn.build(full), state, batch_abs)
    return {"params_mb": round(_params_mb(full), 1), **memory_report(compiled)}


def phase_pallas_ring_allreduce(topology):
    from unittest import mock

    from mpit_tpu.ops import ring_allreduce, ring_collectives

    world = topology_world({"data": 8}, topology)
    f = jax.jit(
        world.shard_map(
            lambda v: ring_allreduce(v, "data", interpret=False),
            in_specs=P("data"),
            out_specs=P("data"),
        )
    )
    x = abstractify(
        jax.ShapeDtypeStruct((8, 4 * 2**20 // 4), jnp.float32),  # 4 MiB/device
        world.mesh,
        P("data"),
    )
    with mock.patch.object(ring_collectives, "_use_kernel", lambda _: True):
        compiled = aot_compile(f, x)
    return _kernel_report(compiled)


PHASES = [
    ("dp-zero1", phase_dp_zero1),
    ("dp-zero1-moe322m", phase_dp_zero1_moe322m),
    ("tp", phase_tp),
    ("pp-1f1b", phase_pp_1f1b),
    ("pp-interleaved-v2", phase_pp_interleaved),
    ("3d-dp-tp-pp", phase_3d_dp_tp_pp),
    ("3d-dp-cp-tp", phase_3d_dp_cp_tp),
    ("ulysses-in-tp", phase_ulysses_in_tp),
    ("cp-long-context-16k", phase_cp_long_context),
    ("ep-moe", phase_ep_moe),
    ("hybrid-dcn", phase_hybrid_dcn),
    ("pallas-ring-allreduce", phase_pallas_ring_allreduce),
]


def main(topology: str = "v5e:2x4") -> int:
    compile_cache_dir()
    record = {"topology": topology, "phases": {}}
    failed = []
    for name, fn in PHASES:
        t0 = time.time()
        try:
            info = fn(topology)
            info["compile_seconds"] = round(time.time() - t0, 1)
            record["phases"][name] = {"ok": True, **info}
            print(
                f"compile_multichip {name}: ok "
                f"({info['compile_seconds']}s, temp "
                f"{info.get('temp_bytes', 0)/2**20:.0f} MiB)"
            )
        except Exception as e:  # noqa: BLE001 — record and continue
            failed.append(name)
            record["phases"][name] = {
                "ok": False,
                "error": f"{type(e).__name__}: {e}",
            }
            print(f"compile_multichip {name}: FAIL — {type(e).__name__}: {e}")
            traceback.print_exc()
    record["ok"] = not failed
    with open("MULTICHIP_AOT.json", "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({"ok": record["ok"], "failed": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "v5e:2x4"))
