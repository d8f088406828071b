"""The program's engine for an ``olmo_hybrid`` configuration, built through
the program's model interface as ``python -m mpit_tpu.serve --family
olmo_hybrid`` builds it, on weights the benchmark made."""

from __future__ import annotations

from mpit_tpu.models.olmo_hybrid import OlmoHybridConfig
from mpit_tpu.serve import Engine

from benchmark.families.olmo_hybrid import weights


def build_engine(ctx):
    import jax.numpy as jnp

    model, serve = ctx["config"], ctx["config"]["serve"]
    dtype = jnp.dtype(serve["weights_dtype"])
    cfg = OlmoHybridConfig.from_dict(
        model, max_seq_len=serve["slot_positions"], dtype=dtype)
    # The tables first: their float32 draft is the largest temporary of
    # set-up, made while the device holds nothing else.
    top = weights.make_top(model, ctx["seed"], dtype)
    layers = [weights.make_layer(model, ctx["seed"], i, dtype)
              for i in range(model["num_hidden_layers"])]
    params = weights.to_program_tree(top, layers)
    pages_per_slot = serve["slot_positions"] // serve["kv_page_size"]
    return Engine(
        cfg, params, slots=serve["slots"], max_len=serve["slot_positions"],
        seed=ctx["seed"], kv_pages=serve["slots"] * pages_per_slot,
        kv_page_size=serve["kv_page_size"],
        prefill_chunk=serve["prefill_chunk"],
        sample_block=serve["sample_block"])
