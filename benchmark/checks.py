"""The comparisons that decide ``correct``, each number beside its limit.

What is compared is what the timed path produced at the timed sizes: the
first steps of the very step-and-state object that the window then
drives, or the tokens the window's requests were served. The other side
is always ``reference.py`` in float32 at ``precision=highest``, run
after the program's state is freed, on weights and inputs that the
benchmark made from the seed.

The limits, and the arithmetic of the control, are data: the cell's
configuration file states them under ``correct.<kind of cell>``, beside
the precision it states, so a configuration in another precision brings
its own. Each was set from two readings on the chip (PERF.md section 2
has the tables): the largest number that sound runs of the program gave
over a dozen seeds or more, and the smallest that the control gave: the
reference put in the program's place and computed one precision below
the one the configuration states (fp8 operands under bf16).
"""

from __future__ import annotations

import numpy as np

LANE = 128  # the ZeRO-1 flat state pads every leaf to a multiple of this


def rules(ctx, kind: str) -> dict:
    """``limits`` (name -> limit; a run is correct when every number is
    at or under its limit) and ``control`` (the arithmetic one precision
    down), as the configuration states them for cells of this kind."""
    stated = ctx["config"].get("correct", {}).get(kind)
    if not stated:
        raise SystemExit(
            f"checks.py: configuration {ctx['cell']['config']!r} states no "
            f"limits for {kind!r} cells under 'correct'")
    return stated


def program_eps(model: dict) -> float:
    """LayerNorm's epsilon as the program computes it, where the
    configuration notes that it departs from the published one."""
    return model.get("assumed", {}).get(
        "program_layer_norm_epsilon", model["layer_norm_epsilon"])


def judge(ctx, kind: str, numbers: dict) -> bool:
    """Print each number beside its limit; True when all hold."""
    ok = True
    lines = {}
    limits = rules(ctx, kind)["limits"]
    for name, value in numbers.items():
        limit = limits.get(name)
        holds = limit is not None and np.isfinite(value) and value <= limit
        ok = ok and holds
        lines[name] = {"value": float(value), "limit": limit, "holds": bool(holds)}
    ctx["say"]("correct", numbers=lines, correct=bool(ok))
    return bool(ok)


# -- training ---------------------------------------------------------------


def _leaf_norms(tree) -> np.ndarray:
    import jax

    return np.asarray([float(np.linalg.norm(np.asarray(l, np.float64)))
                       for l in jax.tree.leaves(tree)])


def _flat_leaf_norms(flat: np.ndarray, like) -> np.ndarray:
    """Per-leaf norms of a ZeRO-1 flat vector laid out like ``like``."""
    import jax

    out, off = [], 0
    for leaf in jax.tree.leaves(like):
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        out.append(float(np.linalg.norm(flat[off:off + size].astype(np.float64))))
        off += size + (-size) % LANE
    return np.asarray(out)


def worst_leaf_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The widest gap between two norms, leaf by leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    floor = np.maximum(want, np.median(want))
    return float(np.max(np.abs(got - want) / floor))


def pretrain_numbers(captured: dict, ref: dict) -> dict:
    """The three numbers of a training cell, program against reference.

    ``captured``: the program's ``losses``, ``grad_norms`` and
    ``update_norms`` per leaf. ``ref``: the same of the reference."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(captured["losses"], ref["losses"]))
    return {
        "pretrain.loss_gap": loss_gap,
        "pretrain.grad_norm_gap": worst_leaf_gap(
            captured["grad_norms"], ref["grad_norms"]),
        "pretrain.update_norm_gap": worst_leaf_gap(
            captured["update_norms"], ref["update_norms"]),
    }


def pretrain_reference(ctx, rows: int, matmul: str = "f32") -> dict:
    """Losses and per-leaf norms of the reference's first steps."""
    import jax

    from benchmark import reference, weights
    from benchmark import traffic as tg
    from benchmark.drivers.pretrain import CHECK_STEPS

    model, mix = ctx["config"], ctx["traffic"]
    w0 = weights.make_stacked(model, ctx["seed"])
    batches = [tg.train_batch(mix, model["vocab_size"], rows, ctx["seed"], s)
               for s in range(CHECK_STEPS)]
    losses, g1, w3 = reference.train_steps(
        w0, batches, n_head=model["n_head"],
        eps=program_eps(model), lr=mix["optimizer"]["lr"],
        matmul=matmul, block_rows=mix["reference_block_rows"],
        devices=ctx["devices"])
    delta = jax.tree.map(lambda a, b: a - b, w3, w0)
    as_leaves = lambda t: _leaf_norms(weights.to_program_tree(t))
    return {"losses": losses, "grad_norms": as_leaves(g1),
            "update_norms": as_leaves(delta)}


def pretrain_program(ctx, captured: dict) -> dict:
    """The program's captured state as losses and per-leaf norms."""
    import jax

    from benchmark import weights

    model = ctx["config"]
    p0 = jax.device_get(
        weights.to_program_tree(weights.make_stacked(model, ctx["seed"])))
    after = captured["params_after"]
    delta = jax.tree.map(
        lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32),
        after, p0)
    # Adam's first moment after one step is (1 - b1) x the gradient it got.
    b1 = ctx["traffic"]["optimizer"]["b1"]
    grads = _flat_leaf_norms(captured["mu_after_1"], p0) / (1.0 - b1)
    return {"losses": captured["losses"], "grad_norms": grads,
            "update_norms": _leaf_norms(delta)}


def pretrain(ctx, captured: dict, rows: int) -> bool:
    program = pretrain_program(ctx, captured)
    ref = pretrain_reference(ctx, rows)
    ctx["say"]("check_detail", program_losses=program["losses"],
               reference_losses=ref["losses"])
    if ctx["control"]:
        arithmetic = rules(ctx, "pretrain")["control"]
        low = pretrain_reference(ctx, rows, matmul=arithmetic)
        ctx["say"]("control", arithmetic=arithmetic,
                   numbers=pretrain_numbers(low, ref), losses=low["losses"])
    return judge(ctx, "pretrain", pretrain_numbers(program, ref))


# -- serving ----------------------------------------------------------------


def sample_requests(done: list, seed: int, want_tokens: int) -> list:
    """A sample of finished requests drawn from the seed, the longest in
    it, of some ``want_tokens`` served tokens in all."""
    if not done:
        return []
    longest = max(done, key=lambda c: (len(c.prompt) + len(c.tokens), c.rid))
    rest = [c for c in done if c is not longest]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    picked, total = [longest], len(longest.tokens)
    for i in order:
        if total >= want_tokens:
            break
        picked.append(rest[i])
        total += len(rest[i].tokens)
    return picked


def token_gaps(ctx, sample: list, matmul: str = "f32",
               low: str | None = None) -> np.ndarray:
    """For each served token of ``sample``: how far its reference logit
    lies below the reference's best at that position. With ``low`` set
    (the control), the token judged at each position is the one that the
    reference computed in that lower precision puts first instead."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference, weights

    model = ctx["config"]
    # The served weights are bfloat16; the reference computes on the same
    # values in float32.
    w = weights.make_stacked(model, ctx["seed"], ctx["config"]["serve"]["weights_dtype"])
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    t_max = model["n_positions"]
    kw = dict(n_head=model["n_head"], eps=program_eps(model))

    @jax.jit
    def gaps_of(w, tokens, positions, served):
        logits = reference.logits_at(w, tokens, positions, matmul=matmul, **kw)
        if low is not None:
            judged = jnp.argmax(reference.logits_at(
                w, tokens, positions, matmul=low, **kw), axis=-1)
        else:
            judged = served
        best = jnp.max(logits, axis=-1)
        return best - jnp.take_along_axis(logits, judged[:, None], axis=-1)[:, 0]

    out = []
    n_out = max(len(c.tokens) for c in sample)
    for c in sample:
        seq = list(c.prompt) + list(c.tokens)
        tokens = np.zeros((t_max,), np.int32)
        tokens[:len(seq) - 1] = seq[:-1]  # the last token is never an input
        positions = np.zeros((n_out,), np.int32)
        served = np.zeros((n_out,), np.int32)
        n = len(c.tokens)
        positions[:n] = np.arange(len(c.prompt) - 1, len(seq) - 1)
        served[:n] = c.tokens
        g = gaps_of(w, jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(served))
        out.append(np.asarray(g)[:n])
    return np.concatenate(out)


def requests_numbers(gaps: np.ndarray) -> dict:
    return {"requests.token_gap_max": float(np.max(gaps)),
            "requests.token_gap_mean": float(np.mean(gaps))}


def requests(ctx, done: list) -> bool:
    sample = sample_requests(done, ctx["seed"], ctx["traffic"]["check_tokens"])
    if not sample:
        ctx["say"]("correct", numbers={}, correct=False,
                   why="the window finished no request")
        return False
    gaps = token_gaps(ctx, sample)
    ctx["say"]("check_detail", requests=len(sample), tokens=int(gaps.size),
               longest=len(sample[0].prompt) + len(sample[0].tokens),
               tokens_off_the_reference_best=int((gaps > 0).sum()))
    if ctx["control"]:
        arithmetic = rules(ctx, "requests")["control"]
        low = token_gaps(ctx, sample, low=arithmetic)
        ctx["say"]("control", arithmetic=arithmetic,
                   numbers=requests_numbers(low),
                   tokens_off_the_reference_best=int((low > 0).sum()))
    return judge(ctx, "requests", requests_numbers(gaps))
