"""The program's engine for a ``laguna`` configuration, built through the
program's model interface as ``python -m mpit_tpu.serve --family laguna``
builds it, on weights the benchmark made, every expert layer's router
centred before the run (``weights.calibrate``: set-up). The centred routers
ride in ``ctx`` to the check, whose reference is handed the same ones."""

from __future__ import annotations

from mpit_tpu.models.laguna import LagunaConfig
from mpit_tpu.serve import Engine

from benchmark.families.laguna import weights


def build_engine(ctx):
    import jax.numpy as jnp

    model, serve = ctx["config"], ctx["config"]["serve"]
    dtype = jnp.dtype(serve["weights_dtype"])
    cfg = LagunaConfig.from_dict(
        model, max_seq_len=serve["slot_positions"], dtype=dtype)
    # The tables first: their float32 draft is the largest temporary of
    # set-up, made while the device holds nothing else.
    top = weights.make_top(model, ctx["seed"], dtype)
    layers = [weights.make_layer(model, ctx["seed"], i, dtype)
              for i in range(model["num_hidden_layers"])]
    ctx["routers"] = weights.calibrate(
        model, top, layers, weights.calibration_tokens(model, ctx["seed"]),
        ctx.get("say", lambda *a, **k: None))
    params = weights.to_program_tree(top, layers)
    pages_per_slot = serve["slot_positions"] // serve["kv_page_size"]
    return Engine(
        cfg, params, slots=serve["slots"], max_len=serve["slot_positions"],
        seed=ctx["seed"], kv_pages=serve["slots"] * pages_per_slot,
        kv_page_size=serve["kv_page_size"],
        prefill_chunk=serve["prefill_chunk"],
        sample_block=serve["sample_block"])
