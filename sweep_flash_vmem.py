"""Sweep-validate the flash kernel's VMEM head-group estimator (round-5).

The ``_pick_head_group`` chooser (``ops/flash_attention.py``) decides how
many attention heads one kernel program packs, from a VMEM model
(`_group_resident`) that round 4 calibrated against just TWO accidental
overflow points. This harness closes the gap the round-4 verdict named
(item 6): sweep (T, H, D) through the chooser AND the real TPU compiler
(AOT against a v5e topology — compile only, no hardware) and verify, for
every shape:

  1. the group the estimator CHOSE actually compiles (fwd+bwd), and
  2. where the estimator engaged grouping (G < H), the next-larger
     candidate it REJECTED actually fails Mosaic's VMEM check — i.e. the
     estimator is neither unsafe nor wastefully conservative;
  3. where it rejected the shape entirely, even the smallest usable
     group fails the real compiler.

Run: ``python sweep_flash_vmem.py`` → per-shape lines + a final JSON
summary; writes ``FLASH_VMEM_SWEEP.json``; exits non-zero if any chosen
group fails to compile (unsafe estimator) or any rejected group/shape
compiles cleanly (over-conservative estimator — tighten ``_VMEM_BUDGET``
instead of shrinking coverage). A 3-point subset runs as a slow-marked
test (``tests/test_ops.py::TestFlashVmemSweepSubset``).
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import importlib

from mpit_tpu.utils.aot import abstractify, topology_world

# The ops package re-exports the flash_attention FUNCTION under the
# module's own name, so a plain ``import`` binds the function; resolve
# the module explicitly.
fa = importlib.import_module("mpit_tpu.ops.flash_attention")

SWEEP_T = (512, 1024, 2048, 4096)
SWEEP_H = (8, 12, 16)
SWEEP_D = (64, 128)
BATCH_PER_DEVICE = 2  # bench/app shapes run >=2 per device


def compile_shape(world, t, h, d, group=None):
    """AOT-compile fwd+bwd of the flash kernel for a per-device
    [B, T, H, D] bf16 block, optionally forcing the head group."""

    def loss(q, k, v):
        return jnp.sum(
            fa.flash_attention(
                q, k, v, causal=True, interpret=False
            ).astype(jnp.float32)
        )

    step = jax.jit(
        world.shard_map(
            jax.grad(loss, argnums=(0, 1, 2)),
            in_specs=(P("data"), P("data"), P("data")),
            out_specs=(P("data"), P("data"), P("data")),
        )
    )
    shape = jax.ShapeDtypeStruct(
        (8 * BATCH_PER_DEVICE, t, h, d), jnp.bfloat16
    )
    args = [abstractify(shape, world.mesh, P("data"))] * 3
    prev = fa._GROUP_OVERRIDE
    fa._GROUP_OVERRIDE = group
    try:
        step.lower(*args).compile()
    finally:
        fa._GROUP_OVERRIDE = prev


def main(topology: str = "v5e:2x4") -> int:
    world = topology_world({"data": 8}, topology)
    results = []
    bad_unsafe, bad_conservative = [], []
    for t in SWEEP_T:
        for h in SWEEP_H:
            for d in SWEEP_D:
                bq = bk = fa._pick_block(t, None)
                key = f"T{t}-H{h}-D{d}"
                try:
                    g = fa._pick_head_group(t, h, d, bq, bk, 2)
                except ValueError:
                    g = None  # estimator rejects the whole shape
                rec = {"t": t, "h": h, "d": d, "block": bq, "chosen": g}
                t0 = time.time()
                if g is not None:
                    try:
                        compile_shape(world, t, h, d)
                        rec["chosen_ok"] = True
                    except Exception as e:  # noqa: BLE001
                        rec["chosen_ok"] = False
                        rec["error"] = f"{type(e).__name__}: {e}"[:160]
                        bad_unsafe.append(key)
                # The candidate one step LARGER than the choice (or the
                # smallest usable group for full rejections): the
                # estimator says it overflows — make the compiler agree.
                reject = None
                if g is not None and g < h:
                    # The candidate one step larger than the choice: G=H
                    # (always usable as the full-dim block) or the next
                    # usable divisor above g — same predicate as the
                    # chooser (fa.usable_head_groups, shared).
                    larger = [h] + [
                        c for c in fa.usable_head_groups(h, d) if c > g
                    ]
                    reject = larger[-1]
                elif g is None:
                    usable = fa.usable_head_groups(h, d)
                    reject = usable[-1] if usable else None
                if reject is not None:
                    try:
                        compile_shape(world, t, h, d, group=reject)
                        rec["rejected_group_compiled"] = reject
                        bad_conservative.append(f"{key}-G{reject}")
                    except Exception:  # noqa: BLE001 — expected overflow
                        rec["rejected_group_overflows"] = reject
                rec["seconds"] = round(time.time() - t0, 1)
                results.append(rec)
                print(f"sweep {key}: chosen G={g} "
                      f"{'ok' if rec.get('chosen_ok', g is None) else 'FAIL'}"
                      + (f", rejected G={reject} "
                         + ("overflows (correct)"
                            if "rejected_group_overflows" in rec
                            else "COMPILED (conservative)")
                         if reject is not None else "")
                      + f" [{rec['seconds']}s]", flush=True)
    summary = {
        "unsafe": bad_unsafe,
        "over_conservative": bad_conservative,
        "shapes": len(results),
    }
    with open("FLASH_VMEM_SWEEP.json", "w") as f:
        json.dump({"summary": summary, "results": results}, f, indent=1)
    print(json.dumps(summary))
    return 1 if (bad_unsafe or bad_conservative) else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "v5e:2x4"))
