"""Driver of a ``pretrain`` cell: the trainer's step under ``hardened_loop``.

The system under test is the program's data-parallel ZeRO-1 train step
(``make_train_step`` with ``GPT2.fused_loss_fn`` and ``goo_adam``, as
``python -m mpit_tpu.asyncsgd gpt2 --flash true`` builds it), driven by
``hardened_loop`` with its prefetcher and fences. One object, the step
with its state, runs from the seed through ``CHECK_STEPS`` checked steps,
the warm-up, and the window.

**The window's edges are completions.** A wrapper round ``step_fn``
blocks until the output of the last warm-up step is ready, stamps
``t_open``, and blocks again on the output of step N after it for
``t_close``. ``train_tokens_per_s = N x tokens per step / (t_close -
t_open)``: N and the two times belong together, so it does not matter
that N may differ by one between runs. N is what ``--seconds`` buys at
the step time seen in warm-up. Nothing here reads ``items_per_sec``, a
fence consumption, or a count of steps inside fixed seconds.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time

CHECK_STEPS = 3   # the reference follows these
WARM_STEPS = 12   # after them, before the window
TIMING_STEPS = 8  # the last warm-up steps give the step time
MIN_STEPS = 16    # of a window, more than the prefetcher ever runs ahead


class EdgeStep:
    """``step_fn`` with the window's two edges placed on its outputs."""

    def __init__(self, step_fn, seconds, on_open, on_close, capture):
        """``capture`` maps a call's index to a function of the state that
        comes into that call; what it returns is kept in ``captured``."""
        self.step_fn = step_fn
        self.seconds = seconds
        self.on_open, self.on_close, self.capture = on_open, on_close, capture
        self.captured = {}
        self.calls = 0
        self.open_call = CHECK_STEPS + WARM_STEPS - 1
        self.total = None  # calls in all, known once the window opens
        self.losses = []
        self.t_probe = self.t_open = self.t_close = self.step_s = None
        self.n = None
        # What hardened_loop's compile watch and span stamp read.
        self._cache_size = step_fn._cache_size
        self.grad_sync_mode = step_fn.grad_sync_mode

    def __call__(self, state, batch):
        import jax

        i = self.calls
        if i in self.capture:
            self.captured[i] = self.capture[i](state)
        state, metrics = self.step_fn(state, batch)
        if i < CHECK_STEPS:
            self.losses.append(metrics["loss"])
        if i == self.open_call - TIMING_STEPS:
            jax.block_until_ready(state)
            self.t_probe = time.perf_counter()
        elif i == self.open_call:
            jax.block_until_ready(state)
            self.step_s = (time.perf_counter() - self.t_probe) / TIMING_STEPS
            self.n = max(MIN_STEPS, math.ceil(self.seconds / self.step_s))
            self.total = self.open_call + self.n + 1
            self.on_open()
            self.t_open = time.perf_counter()
        elif self.total is not None and i == self.total - 1:
            jax.block_until_ready(state)
            self.t_close = time.perf_counter()
            self.on_close()
        self.calls += 1
        return state, metrics


def adam_mu_of(state):
    """Adam's first moment out of the ZeRO-1 state, as one flat host
    vector (each leaf at a 128-aligned offset, in tree-leaf order)."""
    import jax
    import numpy as np

    found = [s for s in jax.tree.leaves(
        state.opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return np.asarray(jax.device_get(found[0].mu))


def run(ctx) -> dict:
    t_imports = time.perf_counter()
    import jax

    import mpit_tpu
    from mpit_tpu import obs
    from mpit_tpu.asyncsgd.gpt2 import GPT2TrainConfig
    from mpit_tpu.models import GPT2
    from mpit_tpu.opt import goo_adam, schedules
    from mpit_tpu.train import MetricLogger, hardened_loop, make_train_step

    from benchmark import checks, tracing, weights
    from benchmark import traffic as tg
    from benchmark.device import memory_peak

    model, mix, devs = ctx["config"], ctx["traffic"], ctx["devices"]
    setup, seed = ctx["setup"], ctx["seed"]
    setup["import_program_s"] = time.perf_counter() - t_imports
    rows, seq = mix["rows_per_chip"] * len(devs), mix["seq_len"]
    tokens_per_step = rows * seq
    if model["n_inner"] != 4 * model["n_embd"]:
        raise SystemExit("pretrain: the program's GPT-2 has d_ff = 4 d_model")

    t0 = time.perf_counter()
    world = mpit_tpu.init({"data": len(devs)}, devices=devs)
    tcfg = GPT2TrainConfig(
        vocab_size=model["vocab_size"], seq_len=seq,
        num_layers=model["n_layer"], num_heads=model["n_head"],
        d_model=model["n_embd"], flash=True, batch_size=rows,
        lr=mix["optimizer"]["lr"], seed=seed)
    mcfg = dataclasses.replace(
        tcfg.model_config(), max_seq_len=model["n_positions"])
    gpt2 = GPT2(mcfg)

    def loss_fn(params, batch):
        return GPT2.fused_loss_fn(gpt2, params, batch["tokens"]), {}

    tx = goo_adam(schedules.from_config(tcfg), weight_decay=tcfg.weight_decay)
    init_fn, step_fn, _ = make_train_step(
        loss_fn, tx, world, zero1=True, grad_sync=tcfg.grad_sync,
        grad_bucket_mb=tcfg.grad_bucket_mb)
    params = weights.to_program_tree(weights.make_stacked(model, seed))
    state = init_fn(params)
    jax.block_until_ready(state)
    del params
    setup["weights_and_state_s"] = time.perf_counter() - t0

    recorder = obs.enable(obs.Recorder()) if ctx["trace"] else None
    marks = {}

    def on_open():
        setup["first_steps_s"] = time.perf_counter() - t_loop
        if ctx["trace"]:
            marks["mark"] = tracing.start(ctx["trace_dir"])

    def on_close():
        marks["peak"] = memory_peak(devs, ctx["say"])
        if ctx["trace"]:
            tracing.stop()

    seconds = min(ctx["seconds"], tracing.TRACE_CAP_S) if ctx["trace"] else ctx["seconds"]
    edge = EdgeStep(step_fn, seconds, on_open, on_close, {
        1: adam_mu_of,  # after one step Adam's mu is (1 - b1) x the gradient
        CHECK_STEPS: lambda state: jax.device_get(state.params)})
    cdf = tg.token_cdf(model["vocab_size"], mix["tokens"])

    def batches():
        step = 0
        while edge.total is None or step < edge.total:
            yield {"tokens": tg.train_batch(
                mix, model["vocab_size"], rows, seed, step, cdf)}
            step += 1

    t_loop = time.perf_counter()
    result = hardened_loop(
        world, state, edge, batches(), steps=10**9,
        items_per_batch=tokens_per_step, logger=MetricLogger(stdout=False))
    del state
    if edge.t_close is None:
        raise RuntimeError("the loop ended before the window closed")
    if recorder is not None:
        obs.span_at("bench_window", edge.t_open, edge.t_close, t_open=edge.t_open)
        spans = tracing.host_spans(recorder, edge.t_open, edge.t_close)
        obs.disable()
    else:
        spans = []

    wall = edge.t_close - edge.t_open
    rate = edge.n * tokens_per_step / wall
    # What the window definitions this benchmark refuses would have read
    # on this very run: whole steps inside a fixed number of seconds give
    # one of two rates a step apart, by where the edge happens to fall,
    # and the loop's own meter times between fence consumptions.
    whole = seconds / (wall / edge.n)
    ctx["say"](
        "window", steps=edge.n, tokens_per_step=tokens_per_step,
        t_open_to_close_s=wall, step_s_in_warmup=edge.step_s,
        train_tokens_per_s=rate,
        whole_steps_in_fixed_seconds_low=math.floor(whole) * tokens_per_step / seconds,
        whole_steps_in_fixed_seconds_high=math.ceil(whole) * tokens_per_step / seconds,
        loop_items_per_sec_mean=result.get("items_per_sec_mean"),
        loop_items_per_sec_last=result.get("items_per_sec_last"),
        loop_compiles=result.get("compiles"))

    captured = {
        "losses": [float(x) for x in edge.losses],
        "mu_after_1": edge.captured[1], "params_after": edge.captured[CHECK_STEPS],
    }
    del result, edge.step_fn, step_fn
    gc.collect()
    t0 = time.perf_counter()
    correct = checks.pretrain(ctx, captured, rows)
    setup["check_after_window_s"] = time.perf_counter() - t0
    return {
        "t_open": edge.t_open, "t_close": edge.t_close,
        "end_to_end": {"train_tokens_per_s": rate},
        "correct": correct, "attempted": edge.n, "failed": 0,
        "memory_peak_bytes": marks["peak"],
        "trace_t0": edge.t_open, "trace_t1": edge.t_close,
        "trace_mark": marks.get("mark"), "host_spans": spans,
        "steps": edge.n, "tokens_per_step": tokens_per_step,
    }

