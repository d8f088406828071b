"""The comparison that decides ``correct`` for a ``laguna`` serving cell.

What ``benchmark/families/glm_dsa/check.py`` does, against this family's
own plain reference (``reference.py`` beside this file): for every served
token of the sampled request, how far its reference logit lies below the
reference's best at that position. The reference is float32 at
``precision=highest`` on the served weights' values, given the same
experts held, the same rows of the vocabulary and the routers the set-up
centred (``weights.calibrate``); it runs after the
program's state is freed, a layer at a time (made from the seed, used,
freed).

The sample is the window's finished request with the fewest positions
among those whose context passes ``check_min_positions`` of the traffic
file (4,096: eight windows, so that a window layer's pages behind the
window have long gone back to the pool and a full layer has read many
pages; the next ones only while fewer than ``check_tokens`` served tokens
are judged), prefill then decode through the cache, its attention in
blocks of query rows.

Routing is discontinuous: a near tie picks another expert in bf16 than in
float32. So the two numbers judged are the mean gap and the 90th
percentile of the gaps, as xing4's; the widest gap is printed, not
judged. Two controls must fail, each the reference in the program's
place: ``fp8`` operands in every product, and ``no_window`` (window layers
attend every cached row). The limits and the controls are the
configuration's (``correct.requests``).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import checks
from benchmark.families.laguna import reference, weights
from benchmark.families.xing4.check import numbers  # the same two numbers

Q_BLOCK = 128  # query rows a block of the reference's attention
ARITHMETICS = {
    "f32": {},
    "fp8": {"matmul": "fp8"},
    "no_window": {"no_window": True},
}


def sample_requests(done: list, want_tokens: int, min_positions: int) -> list:
    """The finished requests with the fewest positions among those of
    ``min_positions`` or more, of some ``want_tokens`` served tokens in
    all."""
    size = lambda c: len(c.prompt) + len(c.tokens)
    picked, total = [], 0
    for c in sorted((c for c in done if size(c) >= min_positions),
                    key=lambda c: (size(c), c.rid)):
        if total >= want_tokens:
            break
        picked.append(c)
        total += len(c.tokens)
    return picked


def token_gaps(ctx, sample: list, low: str | None = None) -> np.ndarray:
    """The gap of every served token of ``sample``; with ``low`` set (a
    control) the token judged at each position is the one the reference
    computed that way puts first."""
    import jax
    import jax.numpy as jnp

    model, seed = ctx["config"], ctx["seed"]
    dtype = jnp.dtype(model["serve"]["weights_dtype"])
    top = weights.make_top(model, seed, dtype)
    longest = max(len(c.prompt) + len(c.tokens) for c in sample) - 1
    t_pad = -(-longest // Q_BLOCK) * Q_BLOCK  # causal: the tail is inert
    positions = jnp.arange(t_pad)
    step = jax.jit(
        functools.partial(reference.layer_forward, model, q_block=Q_BLOCK,
                          held=weights.held(model)),
        static_argnames=("kind", "matmul", "no_window"))
    arithmetics = ["f32"] + ([low] if low else [])
    streams = {}
    for i, c in enumerate(sample):
        seq = np.zeros((t_pad,), np.int32)
        full = list(c.prompt) + list(c.tokens)
        seq[:len(full) - 1] = full[:-1]  # the last token is never an input
        x = reference.embed(top["embed"], jnp.asarray(seq))
        for a in arithmetics:
            streams[i, a] = x
    routers = ctx.get("routers") or [None] * model["num_hidden_layers"]
    for layer, kind in enumerate(model["layer_types"]):
        lw = weights.with_router(
            weights.make_layer(model, seed, layer, dtype), routers[layer])
        for key in streams:
            streams[key] = step(lw, streams[key], positions, kind=kind,
                                **ARITHMETICS[key[1]])
        del lw
    head = jax.jit(functools.partial(reference.head_logits, model),
                   static_argnames=("matmul", "block"))
    block = model["serve"]["sample_block"]
    out = []
    n_out = max(len(c.tokens) for c in sample)
    for i, c in enumerate(sample):
        n = len(c.tokens)
        at = np.zeros((n_out,), np.int32)
        at[:n] = np.arange(len(c.prompt) - 1, len(c.prompt) - 1 + n)
        logits = head(top, streams[i, "f32"][at], matmul="f32", block=block)
        judged = jnp.asarray(np.pad(np.asarray(c.tokens, np.int32),
                                    (0, n_out - n)))
        if low:
            judged = jnp.argmax(head(
                top, streams[i, low][at],
                matmul=ARITHMETICS[low].get("matmul", "f32"), block=block),
                axis=-1)
        gap = jnp.max(logits, axis=-1) - jnp.take_along_axis(
            logits, judged[:, None], axis=-1)[:, 0]
        out.append(np.asarray(gap)[:n])
    return np.concatenate(out)


def requests(ctx, done: list) -> bool:
    mix = ctx["traffic"]
    sample = sample_requests(done, mix["check_tokens"],
                             mix["check_min_positions"])
    if not sample:
        ctx["say"]("correct", numbers={}, correct=False,
                   why="the window finished no request of "
                   f"{mix['check_min_positions']} positions or more")
        return False
    gaps = token_gaps(ctx, sample)
    tail = lambda g: {"p99": float(np.percentile(g, 99)),
                      "widest": float(np.max(g))}  # printed, not judged
    ctx["say"]("check_detail", requests=len(sample), tokens=int(gaps.size),
               longest=max(len(c.prompt) + len(c.tokens) for c in sample),
               finished=len(done),
               tokens_off_the_reference_best=int((gaps > 0).sum()),
               **tail(gaps))
    if ctx["control"]:
        for arithmetic in checks.rules(ctx, "requests")["control"]:
            low = token_gaps(ctx, sample, low=arithmetic)
            ctx["say"]("control", arithmetic=arithmetic, numbers=numbers(low),
                       tokens_off_the_reference_best=int((low > 0).sum()),
                       **tail(low))
    return checks.judge(ctx, "requests", numbers(gaps))
