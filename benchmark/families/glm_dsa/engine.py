"""The program's engine for a ``glm_dsa`` configuration, built through the
program's model interface as ``python -m mpit_tpu.serve --family glm_dsa``
builds it, on weights the benchmark made, and the selection bias set
before the run (``weights.calibrate``).

The calibration sequences are ids as the traffic draws them (uniform over
the rows of the vocabulary held, from ``--seed``) and the greedy
continuation the engine itself serves them, with the bias still zero: the
hidden states of decode ticks are then among those the bias is balanced
on. All of it is set-up. The biases ride in ``ctx`` to the check, whose
reference is handed the same ones.
"""

from __future__ import annotations

import numpy as np

from mpit_tpu.models.glm_dsa import GlmDsaConfig
from mpit_tpu.serve import Engine, Request, Server, warm_engine

from benchmark.families.glm_dsa import weights

# Calibration: sequences, and of each the prompt and its continuation.
CALIBRATION = {"sequences": 32, "prompt": 384, "continuation": 128}


def calibration_sequences(ctx, engine, sizes=None) -> np.ndarray:
    """``[S, prompt + continuation]``: seeded prompts and what the engine
    (its bias zero) continues them with, greedy."""
    sizes = sizes or ctx["config"].get("calibration", CALIBRATION)
    rng = np.random.default_rng([int(ctx["seed"]), 11])
    prompts = rng.integers(0, ctx["config"]["vocab_size"],
                           size=(sizes["sequences"], sizes["prompt"]))
    server = Server(engine)
    for i, p in enumerate(prompts):
        server.submit(Request(rid=i, prompt=p.tolist(),
                              max_new_tokens=sizes["continuation"]))
    done = {c.rid: c.tokens for c in server.run()}
    engine.reset()
    return np.asarray([list(p) + done[i] for i, p in enumerate(prompts)],
                      np.int32)


def build_engine(ctx):
    import time

    import jax
    import jax.numpy as jnp

    model, serve, setup = ctx["config"], ctx["config"]["serve"], ctx["setup"]
    clock = [time.perf_counter()]

    def lap(name, wait_for=None):  # where this stage of set-up went
        jax.block_until_ready(wait_for)
        clock.append(time.perf_counter())
        setup[name] = clock[-1] - clock[-2]

    dtype = jnp.dtype(serve["weights_dtype"])
    cfg = GlmDsaConfig.from_dict(
        model, max_seq_len=serve["slot_positions"], dtype=dtype)
    # The tables first: their float32 draft is the largest temporary of
    # set-up, made while the device holds nothing else.
    top = weights.make_top(model, ctx["seed"], dtype)
    layers = [weights.make_layer(model, ctx["seed"], i, dtype)
              for i in range(model["num_hidden_layers"])]
    params = weights.to_program_tree(top, layers)
    lap("weights_s", params)
    pages_per_slot = serve["slot_positions"] // serve["kv_page_size"]
    engine = Engine(
        cfg, params, slots=serve["slots"], max_len=serve["slot_positions"],
        seed=ctx["seed"], kv_pages=serve["slots"] * pages_per_slot,
        kv_page_size=serve["kv_page_size"],
        prefill_chunk=serve["prefill_chunk"],
        sample_block=serve["sample_block"])
    warm_engine(engine)
    lap("engine_and_compiles_s")
    sequences = calibration_sequences(ctx, engine)
    lap("calibration_serve_s")
    # In place: the engine's tree holds these very dicts, and a step takes
    # the tree as an argument, so the next step runs with the bias set.
    ctx["selection_bias"] = weights.calibrate(
        model, top, params["layers"], sequences, ctx["say"])
    lap("calibration_balance_s", params)
    return engine
