"""The comparison that decides ``correct`` for an ``olmo_hybrid`` serving
cell.

What ``benchmark/checks.py::requests`` does for GPT-2, against this
family's own plain reference (``reference.py`` beside this file): for
every served token of a sample of the window's finished requests, how far
its reference logit lies below the reference's best at that position. The
reference is float32 at ``precision=highest`` on the served weights'
values, the recurrence a scan over tokens; it runs after the program's
state is freed, a layer at a time (made from the seed, used for every
sampled sequence, freed).

The model is dense, so the two numbers judged are GPT-2's: the widest gap
and the mean gap. The limits and the controls' arithmetic are the
configuration's (``correct.requests``). There are two controls, each the
reference in the program's place one precision below what the
configuration states: ``fp8`` (operands of every matrix product rounded
to e4m3) and ``state_bf16`` (the recurrent state kept in bfloat16 between
tokens where float32 is stated).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import checks
from benchmark.families.olmo_hybrid import reference, weights

Q_BLOCK = 256  # query rows a block of the reference's attention
ARITHMETICS = {
    "f32": {},
    "fp8": {"matmul": "fp8"},
    "state_bf16": {"state_dtype": "bfloat16"},
}


def token_gaps(ctx, sample: list, low: str | None = None) -> np.ndarray:
    """The gap of every served token of ``sample``; with ``low`` set (a
    control) the token judged at each position is the one the reference
    computed in that lower precision puts first."""
    import jax
    import jax.numpy as jnp

    model, seed = ctx["config"], ctx["seed"]
    dtype = jnp.dtype(model["serve"]["weights_dtype"])
    top = weights.make_top(model, seed, dtype)
    longest = max(len(c.prompt) + len(c.tokens) for c in sample) - 1
    t_pad = -(-longest // Q_BLOCK) * Q_BLOCK  # causal: the tail is inert
    step = jax.jit(
        functools.partial(reference.layer_forward, model, q_block=Q_BLOCK),
        static_argnames=("matmul", "state_dtype"))
    arithmetics = ["f32"] + ([low] if low else [])
    streams = {}
    for i, c in enumerate(sample):
        seq = np.zeros((t_pad,), np.int32)
        full = list(c.prompt) + list(c.tokens)
        seq[:len(full) - 1] = full[:-1]  # the last token is never an input
        xs = reference.embed(model, top["embed"], jnp.asarray(seq))
        for a in arithmetics:
            streams[i, a] = xs
    with jax.default_matmul_precision("highest"):
        for layer in range(model["num_hidden_layers"]):
            lw = weights.make_layer(model, seed, layer, dtype)
            for key in streams:
                streams[key] = step(lw, streams[key], **ARITHMETICS[key[1]])
            del lw
        head = jax.jit(functools.partial(reference.head_logits, model),
                       static_argnames=("matmul",))
        out = []
        n_out = max(len(c.tokens) for c in sample)
        for i, c in enumerate(sample):
            n = len(c.tokens)
            at = np.zeros((n_out,), np.int32)
            at[:n] = np.arange(len(c.prompt) - 1, len(c.prompt) - 1 + n)
            logits = head(top, streams[i, "f32"][at])
            judged = jnp.asarray(np.pad(np.asarray(c.tokens, np.int32),
                                        (0, n_out - n)))
            if low:
                judged = jnp.argmax(head(
                    top, streams[i, low][at],
                    matmul=ARITHMETICS[low].get("matmul", "f32")), axis=-1)
            gap = jnp.max(logits, axis=-1) - jnp.take_along_axis(
                logits, judged[:, None], axis=-1)[:, 0]
            out.append(np.asarray(gap)[:n])
    return np.concatenate(out)


def requests(ctx, done: list) -> bool:
    sample = checks.sample_requests(
        done, ctx["seed"], ctx["traffic"]["check_tokens"])
    if not sample:
        ctx["say"]("correct", numbers={}, correct=False,
                   why="the window finished no request")
        return False
    gaps = token_gaps(ctx, sample)
    tail = lambda g: {"p90": float(np.percentile(g, 90)),
                      "p99": float(np.percentile(g, 99))}  # printed only
    ctx["say"]("check_detail", requests=len(sample), tokens=int(gaps.size),
               longest=len(sample[0].prompt) + len(sample[0].tokens),
               tokens_off_the_reference_best=int((gaps > 0).sum()),
               **tail(gaps))
    if ctx["control"]:
        for arithmetic in checks.rules(ctx, "requests")["control"]:
            low = token_gaps(ctx, sample, low=arithmetic)
            ctx["say"]("control", arithmetic=arithmetic,
                       numbers=checks.requests_numbers(low),
                       tokens_off_the_reference_best=int((low > 0).sum()),
                       **tail(low))
    return checks.judge(ctx, "requests", checks.requests_numbers(gaps))
