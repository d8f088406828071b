"""The quickest proof that the system still starts on the chip.

GPT-2 small at its published widths (12 layers, d_model 768, 12 heads of
64, vocab 50257; random weights from ``--seed``) trains a few steps and
answers a few requests on ONE TPU chip through the normal entry points,
in one process:

    python chip_smoke.py              # needs one chip; ~2-5 min cold
    python chip_smoke.py --chips 4    # the data-parallel phase alone, 4 chips
    python chip_smoke.py --rehearse [--chips 4]   # CPU, tiny, never "ok"

Phases, each printing one JSON line when it is done (any failed assertion
raises — there is no handler that lets the run end 0):

- ``device``     platform, ``device_kind``, count, versions, compile cache.
- ``train``      ``python -m mpit_tpu.asyncsgd gpt2`` (T=1024, flash kernel,
                 ZeRO-1, fused LM head): finite, falling loss; the flash
                 kernel in the compiled step; compile seconds; one step
                 closed with ``block_until_ready`` and one with a host fetch.
- ``serve``      ``python -m mpit_tpu.serve --model small`` (paged bf16 KV,
                 chunked prefill, kernel decode): every request retired with
                 the asked number of tokens; kernel in the decode step;
                 prefill- and decode-step logits against the model's plain
                 full forward pass.
- ``serve_int8`` the same requests on int8 KV + int8 weights; logits against
                 the f32-weight engine; which matmuls ran the Pallas kernel.
- ``serve_xing4`` ``python -m mpit_tpu.serve --family xing4`` at the widths of
                 ``benchmark/configs/xing4-29b-a4b-6of40.json`` (few slots):
                 the latent decode kernel against its gather-dense
                 composition, and a prompt served in chunks and then decoded
                 through the latent page pool against the model's plain
                 forward, logits both times; ``ok`` only if both hold.
- ``gdn_kernels`` the gated delta rule's chunk and step kernels
                 (``ops/gated_delta.py``) against their lax twins, and the
                 paged attention kernel at 30 heads of 128 against the
                 gather-dense reference, at the shapes of
                 ``benchmark/configs/olmo-hybrid-7b-8of32.json``; each
                 kernel's call timed. ``--phases`` picks one-chip phases.
- ``dsa_kernels`` learned sparse attention's three operations
                 (``ops/dsa.py``) alone at the shapes of
                 ``benchmark/configs/glm-5.2-5of78-ep16.json``: the index
                 scores' kernel against its lax twin (a tick's row a slot
                 and a chunk's rows), the choice by bisection against
                 ``lax.top_k`` as sets, the chosen rows' attention against
                 a masked dense one; each call timed.
- ``mla_chunk_kernel`` a chunk's expanded latent attention
                 (``ops/mla_attention.py``, kernel ``mla_paged_chunk_attn``)
                 alone against its lax twin at the shapes of the xing4 cell
                 (1 x 2,048 rows, 32 heads of 192 / 128, prefixes 0, 4,096
                 and 10,240) and of the glm52 cell (4 x 512 rows, 64 heads
                 of 256 / 256, a prefix of 30k, a choice of 2,048 of the
                 visible); each call timed.
- ``chunk_rows`` GPT-2 large at the serving cells' shape (16 slots of 1,024
                 positions, chunks of 64): the compacted chunk step timed
                 alone at 16 to 1,024 rows, against the full-batch step,
                 the 128-row step with its two seats one slot's, two
                 slots' and one slot's beside padding, and the host's side of a chunk tick (``prefill_dispatch``)
                 both ways: the table ``serve/engine.py``'s
                 ``_WEIGHT_BOUND_ROWS`` is read from.
- ``dp4``        (``--chips 4`` only, and then the only phase) ZeRO-1 data
                 parallel over four chips vs the same global batch and seed
                 on one of them; then ``grad_sync=ring`` and ``ring_q8``.

The last line of stdout is ``{"ok": true, "device": {...}}``. Without an
accelerator the script exits non-zero before doing any work and prints no
result. ``--rehearse`` changes sizes and runs the serve kernels in interpret
mode, nothing else; it ends ``"ok": false`` and exit code 3 when every
phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

# Logit tolerances (max abs difference; the logits of a random-init GPT-2
# small have a standard deviation of 0.55). Measured on the v5e, PR 21:
# 0.024 kernel vs plain, 0.056 int8 vs f32 weights.
TOL_KERNEL_VS_PLAIN = 0.05  # same weights, bf16 compute, other op order
TOL_INT8_VS_F32 = 0.25  # tests/test_weights_quant.py's weight-store bound
# Per-step loss tolerances of the four-chip runs (loss is ~10.9). Measured
# on four v5e chips, PR 21: 6e-5 vs one chip, 7e-5 ring, 2e-3 ring_q8.
TOL_DP4_VS_ONE_CHIP = 0.01
TOL_RING_Q8_VS_PSUM = 0.05
RING_TIMEOUT_S = 300.0  # the ring kernels' protocol has only run interpreted
# xing4 (logits of a random-init model: standard deviation 1.2). The
# kernel's weighted latents are of order 0.1-1 in bf16. The served logits
# are judged at the median position: routing is discontinuous, and at a
# position where a near tie between two experts falls the other way in the
# two bf16 orders of operation the logits differ by whole units (3.3 at
# the worst of 704 positions; my chip run, PR 26, call 7).
TOL_X4_KERNEL_VS_GATHER = 0.03
TOL_X4_PAGED_VS_PLAIN = 0.15
# The gated delta rule's kernels against their lax twins on bf16 operands:
# outputs of order 0.1-1, states of order 1 after a chunk from a random
# state. The attention kernel at 30 heads of 128 against the gather-dense
# reference: GPT-2's tolerance, same weights and dtype, other op order.
TOL_GDN_KERNEL_VS_TWIN = 0.02

# The index scores' kernel against its twin: relu-weighted sums of 32
# bf16 dot products of order 10; the chosen rows' attention against the
# masked dense one: weighted latents of order 0.1-1 in bf16.
TOL_DSA_SCORES = 0.05
TOL_DSA_ATTN = 0.03
# The chunk's latent attention kernel against its lax twin: outputs of
# order 0.1-1 in bf16, the same products in another order.
TOL_MLA_CHUNK = 0.03

FULL = dict(
    model=["--num-layers", "12", "--d-model", "768", "--num-heads", "12",
           "--vocab-size", "50257", "--seq-len", "1024"],
    batch=16, steps=6,
    serve=["--model", "small", "--slots", "8", "--max-len", "1024",
           "--prefill-len", "256", "--kv-pages", "512", "--kv-page-size",
           "16", "--prefill-chunk", "64", "--requests", "6", "--prompt-len",
           "200", "--max-new-tokens", "16"],
    decode_attention="kernel", probe_len=96,
    xing4=["--family", "xing4", "--model-config",
           "benchmark/configs/xing4-29b-a4b-6of40.json", "--slots", "2",
           "--max-len", "2048", "--prefill-len", "2048", "--kv-pages", "16",
           "--kv-page-size", "256", "--prefill-chunk", "512", "--requests",
           "3", "--prompt-len", "600", "--max-new-tokens", "8"],
    xing4_probe=(700, 4),  # a prompt of two chunks, then decode ticks
    # The olmoh cell's shapes: heads, key and value widths, a chunk's
    # tokens and participants, the slots of a tick, a slot's positions
    # and the page.
    gdn=dict(h=30, dk=96, dv=192, chunk=512, seqs=2, slots=64,
             positions=4096, page=128, interpret=None),
    # The glm52 cell's shapes: a tick's slots, a chunk's rows, a slot's
    # positions and the page, the indexer's heads and width, the choice,
    # attention's heads against a latent of 512 and a rotary key of 64.
    dsa=dict(slots=16, chunk=512, positions=36864, page=256, hi=32, di=128,
             topk=2048, heads=64, latent=512, rope=64, interpret=None),
    # The two latent cells' chunk steps: participants x rows, heads and
    # their widths, a slot's positions, the prefixes timed, the choice.
    mla_chunk=dict(
        latent=512, page=256, interpret=None,
        xing4=dict(b=1, t=2048, h=32, dn=128, dr=64, dv=128,
                   positions=13312, prefixes=(0, 4096, 10240), topk=0),
        glm52=dict(b=4, t=512, h=64, dn=192, dr=64, dv=256,
                   positions=36864, prefixes=(30000,), topk=2048)),
    # The GPT-2 large cells' shape, and the (participants, chunk width)
    # pairs whose compacted step is timed: 16 to 1,024 rows.
    chunk_rows=dict(layers=36, heads=20, d_model=1280, vocab=50257,
                    slots=16, positions=1024, page=16, chunk=64, prefix=256,
                    steps=((1, 16), (1, 32), (1, 64), (2, 64), (4, 64),
                           (8, 64), (16, 64)), reps=20),
)
TINY = dict(
    model=["--num-layers", "2", "--d-model", "64", "--num-heads", "4",
           "--vocab-size", "512", "--seq-len", "128"],
    batch=8, steps=6,
    serve=["--model", "tiny", "--slots", "4", "--max-len", "128",
           "--prefill-len", "64", "--kv-pages", "32", "--kv-page-size",
           "16", "--prefill-chunk", "16", "--requests", "4", "--prompt-len",
           "40", "--max-new-tokens", "4"],
    decode_attention="interpret", probe_len=24,
    xing4=["--family", "xing4", "--model", "tiny", "--slots", "2",
           "--max-len", "128", "--prefill-len", "128", "--kv-pages", "16",
           "--kv-page-size", "16", "--prefill-chunk", "16", "--requests",
           "3", "--prompt-len", "40", "--max-new-tokens", "4"],
    xing4_probe=(27, 3),
    gdn=dict(h=3, dk=12, dv=24, chunk=100, seqs=2, slots=3, positions=256,
             page=16, interpret=True),
    dsa=dict(slots=3, chunk=16, positions=128, page=16, hi=4, di=128,
             topk=8, heads=4, latent=128, rope=16, interpret=True),
    mla_chunk=dict(
        latent=128, page=16, interpret=True,
        xing4=dict(b=1, t=32, h=2, dn=128, dr=64, dv=128, positions=128,
                   prefixes=(0, 40), topk=0),
        glm52=dict(b=3, t=32, h=2, dn=192, dr=64, dv=256, positions=128,
                   prefixes=(70,), topk=8)),
    chunk_rows=dict(layers=2, heads=4, d_model=64, vocab=512, slots=4,
                    positions=128, page=16, chunk=16, prefix=32,
                    steps=((1, 8), (1, 16), (2, 16), (4, 16)), reps=2),
)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def phase_device(want_chips: int, rehearse: bool) -> dict:
    import jax
    import jaxlib

    from mpit_tpu.utils import compile_cache_dir

    cache = compile_cache_dir()
    devs = jax.devices()
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if not rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: no accelerator ({device})", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) != want_chips:
        print(
            f"chip_smoke: wants {want_chips} device(s), found {len(devs)}",
            file=sys.stderr,
        )
        raise SystemExit(2)
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "unknown"
    emit("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu, compile_cache_dir=cache, rehearse=rehearse)
    return device


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------


def _train_argv(sz, *extra) -> list[str]:
    return [*sz["model"], "--flash", "true", "--batch-size", str(sz["batch"]),
            "--steps", str(sz["steps"]), "--log-every", "1", *extra]


def _build_train(world, tcfg, grad_sync: str):
    """The DP branch of ``asyncsgd.gpt2.main``, by its own pieces, for a
    caller who needs the state and the step function afterwards."""
    import jax
    import jax.numpy as jnp

    from mpit_tpu.asyncsgd import runner
    from mpit_tpu.data import SyntheticLM
    from mpit_tpu.models import GPT2
    from mpit_tpu.opt import goo_adam, schedules
    from mpit_tpu.train import make_train_step

    model = GPT2(tcfg.model_config())

    def loss_fn(params, batch):
        return GPT2.fused_loss_fn(model, params, batch["tokens"]), {}

    tx = goo_adam(schedules.from_config(tcfg), weight_decay=tcfg.weight_decay)
    init_fn, step_fn, _ = make_train_step(
        loss_fn, tx, world, zero1=True, grad_sync=grad_sync,
        grad_bucket_mb=tcfg.grad_bucket_mb,
    )
    params = jax.jit(model.init)(
        jax.random.key(tcfg.seed), jnp.zeros((1, tcfg.seq_len), jnp.int32)
    )["params"]
    stream = runner.make_stream(
        tcfg, SyntheticLM(vocab_size=tcfg.vocab_size, seed=tcfg.seed),
        tcfg.seq_len,
    )
    return init_fn(params), step_fn, stream


def _run_train(world, tcfg, grad_sync: str):
    """``steps`` steps under ``hardened_loop``: (losses, state, step_fn)."""
    from mpit_tpu.train import hardened_loop

    state, step_fn, stream = _build_train(world, tcfg, grad_sync)
    result = hardened_loop(
        world, state, step_fn, stream, steps=tcfg.steps, log_every=1,
        items_per_batch=tcfg.batch_size * tcfg.seq_len,
    )
    return result["losses"], result["state"], step_fn


def _check_losses(losses, steps: int) -> None:
    import math

    assert len(losses) == steps, (len(losses), steps)
    assert all(math.isfinite(x) for x in losses), losses
    assert losses[-1] < losses[0], losses


def phase_train(sz, seed: int, rehearse: bool) -> None:
    import jax

    import mpit_tpu
    from mpit_tpu.asyncsgd import gpt2
    from mpit_tpu.asyncsgd.config import from_argv
    from mpit_tpu.data import native, shard_batch

    # The command line a user runs, native (C++) token stream included:
    # the library is not tracked, so this also proves it builds here.
    argv = _train_argv(sz, "--seed", str(seed))
    t0 = time.perf_counter()
    out = gpt2.main([*argv, "--native", "true"])
    cli_wall = time.perf_counter() - t0
    assert native.available(), "native data core did not build"
    assert out["tier"] == "shard_map+zero1", out["tier"]
    _check_losses(out["losses"], sz["steps"])

    # The same step by its pieces: its compiled text, and two closed steps.
    tcfg = from_argv(gpt2.GPT2TrainConfig, argv)
    world = mpit_tpu.init()
    state, step_fn, stream = _build_train(world, tcfg, "psum")
    batch = shard_batch(world, next(stream))
    t0 = time.perf_counter()
    compiled = step_fn.build(state.params, state.extra).lower(
        state, batch
    ).compile()
    compile_s = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")
    assert rehearse or kernels, "no flash kernel in the compiled train step"

    state, _ = compiled(state, batch)  # warm: first execution
    jax.block_until_ready(state)
    # One step closed with block_until_ready, then a host fetch of its
    # loss (which should then cost nothing); one closed by the fetch alone.
    t0 = time.perf_counter()
    state, metrics = compiled(state, batch)
    jax.block_until_ready((state, metrics))
    t_block = time.perf_counter() - t0
    float(metrics["loss"])
    t_block_then_fetch = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, metrics = compiled(state, batch)
    float(metrics["loss"])
    t_fetch = time.perf_counter() - t0
    emit(
        "train", losses=out["losses"], steps=sz["steps"], batch=sz["batch"],
        seq_len=tcfg.seq_len, native_core=True, cli_wall_s=round(cli_wall, 2),
        compile_s=round(compile_s, 2), custom_calls_in_step=kernels,
        step_s_block_until_ready=t_block,
        step_s_block_then_fetch=t_block_then_fetch,
        step_s_host_fetch_only=t_fetch,
    )


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def _serve_argv(sz, seed: int, *extra) -> list[str]:
    return [*sz["serve"], "--decode-attention", sz["decode_attention"],
            "--seed", str(seed), *extra]


def _decode_step_kernels(engine) -> int:
    """``tpu_custom_call`` count in the engine's compiled decode step."""
    import jax
    import jax.numpy as jnp

    s = engine.slots
    args = (
        engine.params, engine.cache, engine.last_token,
        jnp.zeros((s,), bool),
        jnp.zeros((s, engine.pages_per_slot), jnp.int32),
        jax.random.key(0), jnp.zeros((s,), jnp.float32),
        jnp.zeros((s,), jnp.int32),
    )
    text = engine._decode_paged_jit.lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


def _engine_logits(engine, prompt, first=None):
    """Logits of the model the engine really runs (``engine.cfg``: kernel
    attention, its matmuls) over its own page pool: the prompt's last
    position from one T=len(prompt) prefill, then one T=1 decode step on
    the token that follows (``first``, default the prefill's argmax)."""
    import jax
    import jax.numpy as jnp

    from mpit_tpu.models import GPT2

    model = GPT2(engine.cfg)
    table = jnp.arange(engine.pages_per_slot, dtype=jnp.int32)[None]

    @jax.jit
    def forward(params, tokens, k, v, lengths):
        valid = jnp.ones(tokens.shape, bool)
        return model.apply(
            {"params": params}, tokens,
            paged_cache=(k, v, lengths, table, valid),
        )

    n = len(prompt)
    pre, (k, v) = forward(
        engine.params, jnp.asarray([prompt], jnp.int32),
        engine.cache.k, engine.cache.v, jnp.zeros((1,), jnp.int32),
    )
    if first is None:
        first = int(jnp.argmax(pre[0, n - 1]))
    dec, _ = forward(
        engine.params, jnp.asarray([[first]], jnp.int32), k, v,
        jnp.full((1,), n, jnp.int32),
    )
    return pre[0, n - 1], dec[0, 0], first


def _paged_kernel_vs_reference(engine, seed: int) -> dict:
    """The paged flash-decode kernel alone, at the engine's geometry, on
    random data and ragged lengths, over a pool in the stored form
    ([pages, page, H*Dh]; bf16 rows, and int8 rows with their scale
    plane), for the three query widths the engine traces: a decode tick
    (T=1) and a ``spec_k=4`` verify (T=5), which take all heads as the
    rows of one product a step over a bf16 pool, and a prefill chunk
    (T=64), a head at a time; each call's form and rows a step are in
    the record. Output against the gather-dense reference, and the
    kernel's own visited-tile count against the host formula the
    scheduler's counters use."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpit_tpu.ops.decode_attention import (
        decode_tiling,
        flash_paged_decode_attention,
        num_kv_blocks,
        reference_paged_decode_attention,
    )
    from mpit_tpu.ops.kv_quant import QuantizedKV, pack_heads, quantize_kv

    cfg, b, pps = engine.cfg, engine.slots, engine.pages_per_slot
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    rows = (engine.num_pages, engine.page_size, cfg.num_heads, cfg.head_dim)
    k = jax.random.normal(kk, rows, jnp.bfloat16)
    v = jax.random.normal(kv, rows, jnp.bfloat16)
    pools = {
        "bf16": (pack_heads(k), pack_heads(v)),
        "int8": (pack_heads(quantize_kv(k)), pack_heads(quantize_kv(v))),
    }
    rng = np.random.RandomState(seed)
    table = jnp.asarray(
        rng.permutation(engine.num_pages)[: b * pps].reshape(b, pps), jnp.int32
    )
    errs, forms, visited_t1 = {}, {}, None
    for t in sorted({1, 5, engine.prefill_chunk}):
        q = jax.random.normal(kq, (b, t, *rows[2:]), jnp.bfloat16)
        lengths = jnp.asarray(
            rng.randint(0, engine.max_len - t, size=b), jnp.int32
        )
        tiles = num_kv_blocks(
            np.asarray(lengths), t, engine.max_len, engine.decode_block_k
        )
        for name, (kp, vp) in pools.items():
            out, visited = flash_paged_decode_attention(
                q, kp, vp, lengths, table, block_k=engine.decode_block_k,
                interpret=(
                    True if engine.decode_attention == "interpret" else None
                ),
                return_visited=True,
            )
            want = reference_paged_decode_attention(q, kp, vp, lengths, table)
            errs[f"{name}_T{t}"] = err = _max_abs(out, want)
            quantized = isinstance(kp, QuantizedKV)
            tiling = decode_tiling(
                t, cfg.num_heads, kp.q.dtype if quantized else kp.dtype,
                page_size=engine.page_size, quantized=quantized,
            )
            forms[f"{name}_T{t}"] = [tiling.form, tiling.rows]
            assert np.array_equal(np.asarray(visited), tiles), (visited, tiles)
            assert err <= 0.02, (name, t, err)  # rows of unit variance
        if t == 1:
            visited_t1 = tiles.tolist()
    return {"decode_kernel_err_vs_reference": errs,
            "decode_kernel_form_and_rows_a_step": forms,
            "visited_tiles": visited_t1,
            "page_writer_equals_row_scatter": _page_writer_vs_scatter(
                engine, pools, table, seed)}


def _page_writer_vs_scatter(engine, pools, table, seed: int) -> list:
    """A prefill chunk's rows into a pool buffer by pages (the kernel
    ``paged_cache_update`` takes for them on the chip) against the same
    rows scattered one position at a time, bf16 rows and int8 payload:
    ragged starts, chunks cut short, one slot writing nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpit_tpu.models.gpt2 import paged_cache_update
    from mpit_tpu.ops.decode_attention import paged_write_pages

    b, t = engine.slots, engine.prefill_chunk
    interpret = True if engine.decode_attention == "interpret" else None
    rng = np.random.RandomState(seed + 1)
    starts = jnp.asarray(rng.randint(0, engine.max_len - t, size=b), jnp.int32)
    cut = rng.randint(1, t + 1, size=b)
    cut[0], cut[-1] = t, 0
    valid = jnp.asarray(np.arange(t)[None, :] < cut[:, None])
    checked = []
    for name, pool in (("bf16", pools["bf16"][0]), ("int8", pools["int8"][0].q)):
        new = jax.random.normal(
            jax.random.key(seed + 2), (b, t, pool.shape[-1]), jnp.float32
        ) * 20
        got = paged_write_pages(
            pool, new, starts, table, valid, interpret=interpret
        )
        want = pool
        for i in range(t):  # one row a slot: the scatter's path
            want = paged_cache_update(
                want, new[:, i : i + 1], starts + i, table,
                valid=valid[:, i : i + 1],
            )
        assert got.dtype == pool.dtype and bool(jnp.all(got == want)), name
        assert not bool(jnp.all(got == pool)), name  # something was written
        checked.append(name)
    return checked


def _max_abs(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


def _run_requests(engine, scfg, vocab: int) -> dict:
    from mpit_tpu.serve import Server
    from mpit_tpu.serve.__main__ import synthetic_requests

    server = Server(engine)
    requests = list(synthetic_requests(scfg, vocab))
    for r in requests:
        server.submit(r)
    done = {c.rid: c for c in server.run()}
    assert sorted(done) == [r.rid for r in requests], sorted(done)
    for r in requests:
        c = done[r.rid]
        assert len(c.tokens) == r.max_new_tokens and not c.truncated, r.rid
    return {
        "requests": len(requests),
        "prompt_lens": [len(r.prompt) for r in requests],
        "tokens_each": scfg.max_new_tokens,
    }


def phase_serve(sz, seed: int, rehearse: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpit_tpu.asyncsgd.config import from_argv
    from mpit_tpu.models import GPT2
    from mpit_tpu.serve import __main__ as serve_cli
    from mpit_tpu.serve import warm_engine

    # The command line a user runs.
    argv = _serve_argv(sz, seed)
    scfg = from_argv(serve_cli.ServeConfig, argv)
    out = serve_cli.main(argv)
    assert out["requests_completed"] == scfg.requests, out
    assert out["generated_tokens"] == scfg.requests * scfg.max_new_tokens
    assert out["decode_attention"] == "kernel", out["decode_attention"]
    assert not out["truncated"], out

    # The same engine by the CLI's own builder, to look inside.
    engine, mcfg = serve_cli._build_engine(scfg)
    assert engine.decode_attention_mode == "kernel"
    t0 = time.perf_counter()
    warm_engine(engine)
    compile_s = time.perf_counter() - t0
    kernels = _decode_step_kernels(engine)
    assert rehearse or kernels, "no kernel in the compiled decode step"
    ran = _run_requests(engine, scfg, mcfg.vocab_size)
    ran.update(_paged_kernel_vs_reference(engine, seed))

    prompt = np.random.RandomState(seed).randint(
        0, mcfg.vocab_size, size=sz["probe_len"]
    ).tolist()
    pre, dec, first = _engine_logits(engine, prompt)
    plain = jax.jit(GPT2(mcfg).apply)(
        {"params": engine.params}, jnp.asarray([prompt + [first]], jnp.int32)
    )[0]
    err_pre = _max_abs(pre, plain[len(prompt) - 1])
    err_dec = _max_abs(dec, plain[len(prompt)])
    assert np.isfinite(np.asarray(dec, np.float32)).all()
    assert dec.shape == (mcfg.vocab_size,), dec.shape
    assert max(err_pre, err_dec) <= TOL_KERNEL_VS_PLAIN, (err_pre, err_dec)
    emit(
        "serve", **ran, cli_wall_s=out["wall_s"],
        decode_attention=engine.decode_attention_mode,
        kv_dtype=engine.kv_dtype, weights_dtype=engine.weights_dtype,
        compile_s=round(compile_s, 2), custom_calls_in_decode_step=kernels,
        logit_std=float(jnp.std(plain)), prefill_logit_err=err_pre,
        decode_logit_err=err_dec, tolerance=TOL_KERNEL_VS_PLAIN,
    )
    return prompt, first, dec


def phase_serve_int8(sz, seed: int, rehearse: bool, probe) -> None:
    import numpy as np

    from mpit_tpu import obs
    from mpit_tpu.asyncsgd.config import from_argv
    from mpit_tpu.serve import __main__ as serve_cli
    from mpit_tpu.serve import warm_engine

    prompt, first, dec_f32 = probe
    scfg = from_argv(
        serve_cli.ServeConfig,
        _serve_argv(sz, seed, "--kv-dtype", "int8", "--weights-dtype", "int8"),
    )
    rec = obs.enable(obs.Recorder())  # quantized_matmul stamps its path
    try:
        engine, mcfg = serve_cli._build_engine(scfg)
        assert engine.decode_attention_mode == "kernel"
        assert engine.kv_quantized and engine.weights_quantized
        t0 = time.perf_counter()
        warm_engine(engine)
        compile_s = time.perf_counter() - t0
        kernels = _decode_step_kernels(engine)
        ran = _run_requests(engine, scfg, mcfg.vocab_size)
        _, dec, _ = _engine_logits(engine, prompt, first)
        paths: dict = {"kernel": set(), "lax": set()}
        for attrs, _n in rec.counter_items("quantized_matmul_calls"):
            paths[attrs["path"]].add(attrs["shape"])
    finally:
        obs.disable()
    assert rehearse or (kernels and paths["kernel"]), (kernels, paths)
    err = _max_abs(dec, dec_f32)
    assert np.isfinite(np.asarray(dec, np.float32)).all()
    assert 0.0 < err <= TOL_INT8_VS_F32, err
    emit(
        "serve_int8", **ran, kv_dtype=engine.kv_dtype,
        weights_dtype=engine.weights_dtype, compile_s=round(compile_s, 2),
        custom_calls_in_decode_step=kernels,
        quantized_matmul_kernel=sorted(paths["kernel"]),
        quantized_matmul_lax=sorted(paths["lax"]),
        lm_head_sampler="lm_head_sample streams int8 vocab tiles (lax)",
        decode_logit_err_vs_f32_weights=err, tolerance=TOL_INT8_VS_F32,
    )


# ---------------------------------------------------------------------------
# Four chips: data-parallel training
# ---------------------------------------------------------------------------


def _xing4_kernel_vs_gather(engine, seed: int) -> float:
    """The latent decode kernel on a random pool at the engine's widths
    against the gather-dense composition: max abs difference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpit_tpu.ops import mla_attention as mla

    cfg, b, pps = engine.cfg, engine.slots, engine.pages_per_slot
    ks = jax.random.split(jax.random.key(seed), 4)
    dt = engine.cache.k[0].dtype
    ckv = jax.random.normal(ks[0], engine.cache.k[0].shape, dt)
    kr = jax.random.normal(ks[1], engine.cache.v[0].shape, dt)
    kr = kr.at[..., cfg.qk_rope_head_dim:].set(0)
    h = cfg.num_attention_heads
    qa = jax.random.normal(ks[2], (b, h, cfg.kv_lora_rank), dt)
    qr = jax.random.normal(ks[3], (b, h, cfg.qk_rope_head_dim), dt)
    table = jnp.asarray(np.random.RandomState(seed).permutation(
        engine.num_pages)[: b * pps].reshape(b, pps), jnp.int32)
    lengths = jnp.asarray(
        [engine.max_len - 1] + [engine.page_size + 3] * (b - 1), jnp.int32)
    args = (qa, qr, ckv, kr, lengths, table)
    interp = True if engine.decode_attention == "interpret" else None
    got = mla.mla_paged_decode_attention(
        *args, scale=cfg.softmax_scale, interpret=interp)
    want = mla.reference_mla_paged_decode_attention(
        *args, scale=cfg.softmax_scale)
    return _max_abs(got, want)


def _xing4_paged_vs_plain(engine, seed: int, prompt: int, ticks: int) -> tuple:
    """A prompt in chunks and ``ticks`` decode steps through the engine's
    model and latent pool (the forward the jitted steps run), against the
    model's plain forward of the whole sequence: ``(per-position max abs
    logit difference at the median, the 90th percentile and the worst
    position, the logits' standard deviation)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpit_tpu.models.xing4 import forward_plain
    from mpit_tpu.serve.kvcache import PagedKVCache

    cfg, chunk = engine.cfg, engine.prefill_chunk
    seq = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, size=prompt + ticks)
    engine.reset(seed)
    engine.allocator.admit(0, seq[:prompt].tolist(), ticks + 1)
    table = jnp.asarray(engine.allocator.block_tables, jnp.int32)
    forward = jax.jit(lambda p, t, c, rows: engine.model.forward_paged(
        p, t, c, table, rows, return_hidden=False, row_valid=rows)[:2])
    cache, got = engine.cache, []
    starts = list(range(0, prompt, chunk)) + list(range(prompt, len(seq)))
    for base in starts:
        width = chunk if base < prompt else 1
        n = min(width, prompt - base) if base < prompt else 1
        tokens = np.zeros((engine.slots, width), np.int32)
        tokens[0, :n] = seq[base:base + n]
        rows = np.zeros((engine.slots, width), bool)
        rows[0, :n] = True
        lengths = jnp.zeros((engine.slots,), jnp.int32).at[0].set(base)
        logits, (k, v, _) = forward(
            engine.params, jnp.asarray(tokens),
            PagedKVCache(k=cache.k, v=cache.v, lengths=lengths),
            jnp.asarray(rows))
        cache = PagedKVCache(k=k, v=v, lengths=lengths)
        got.append(logits[0, :n])
    engine.cache = cache
    plain = jax.jit(lambda p, t: forward_plain(p, t, cfg))(
        engine.params, jnp.asarray(seq)[None])[0]
    engine.reset(seed)
    err = np.asarray(jnp.max(jnp.abs(
        jnp.concatenate(got).astype(jnp.float32) - plain), axis=-1))
    return ([float(np.percentile(err, q)) for q in (50, 90, 100)],
            float(jnp.std(plain)))


def phase_serve_xing4(sz, seed: int, rehearse: bool) -> None:
    import gc

    from mpit_tpu.asyncsgd.config import from_argv
    from mpit_tpu.serve import __main__ as serve_cli
    from mpit_tpu.serve import warm_engine

    argv = [*sz["xing4"], "--decode-attention", sz["decode_attention"],
            "--seed", str(seed)]
    scfg = from_argv(serve_cli.ServeConfig, argv)
    engine, mcfg = serve_cli._build_engine(scfg)
    assert engine.model.family == "xing4"
    assert engine.decode_attention_mode == "kernel"
    t0 = time.perf_counter()
    warm_engine(engine)
    compile_s = time.perf_counter() - t0
    kernels = _decode_step_kernels(engine)
    assert rehearse or kernels, "no kernel in the compiled decode step"
    ran = _run_requests(engine, scfg, mcfg.vocab_size)
    kernel_err = _xing4_kernel_vs_gather(engine, seed)
    logit_err, logit_std = _xing4_paged_vs_plain(
        engine, seed, *sz["xing4_probe"])
    emit(
        "serve_xing4", **ran, compile_s=round(compile_s, 2),
        custom_calls_in_decode_step=kernels, layers=mcfg.num_hidden_layers,
        experts=mcfg.n_routed_experts, streams=mcfg.hc_mult,
        page_bytes=engine.page_bytes, prefill_counts=engine._prefill_counts,
        kernel_vs_gather_err=kernel_err, tolerance=TOL_X4_KERNEL_VS_GATHER,
        paged_vs_plain_logit_err_p50_p90_max=logit_err, logit_std=logit_std,
        logit_tolerance_at_the_median=TOL_X4_PAGED_VS_PLAIN,
    )
    assert kernel_err <= TOL_X4_KERNEL_VS_GATHER, kernel_err
    assert logit_err[0] <= TOL_X4_PAGED_VS_PLAIN, logit_err
    del engine
    gc.collect()


def _median_ms(fn, *args, reps: int = 5) -> float:
    """Median wall time of ``fn(*args)`` to completion, after one call
    that compiles."""
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return round(1e3 * sorted(times)[len(times) // 2], 3)


def phase_gdn_kernels(sz, seed: int, rehearse: bool) -> None:
    """The gated delta rule's two kernels against their lax twins, and the
    paged attention kernel at 128-wide heads against the gather-dense
    reference, at the olmoh cell's shapes; each kernel's call timed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpit_tpu.models.gpt2 import paged_cached_attention
    from mpit_tpu.ops import gated_delta as gd
    from mpit_tpu.ops.decode_attention import flash_paged_decode_attention

    g = sz["gdn"]
    h, dk, dv, interp = g["h"], g["dk"], g["dv"], g["interpret"]
    dt = jnp.float32 if rehearse else jnp.bfloat16

    def rule_inputs(key, lead):
        ks = jax.random.split(key, 6)
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        q = unit(jax.random.normal(ks[0], (*lead, h, dk))) * dk ** -0.5
        k = unit(jax.random.normal(ks[1], (*lead, h, dk)))
        v = jax.random.normal(ks[2], (*lead, h, dv))
        gl = -jax.random.uniform(ks[3], (*lead, h), minval=1e-4, maxval=0.1)
        beta = jax.random.uniform(ks[4], (*lead, h), minval=0.0, maxval=2.0)
        state = jax.random.normal(ks[5], (lead[0], h, dk, dv))
        return q.astype(dt), k.astype(dt), v.astype(dt), gl, beta, state

    key = jax.random.key(seed)
    out = {}
    chunk_k = jax.jit(lambda *a: gd.gdn_chunk(*a, interpret=interp))
    args = rule_inputs(jax.random.fold_in(key, 1), (g["seqs"], g["chunk"]))
    got, want = chunk_k(*args), jax.jit(gd.gdn_chunk_lax)(*args)
    out["chunk_o_err"], out["chunk_state_err"] = (
        _max_abs(got[0], want[0]), _max_abs(got[1], want[1]))
    out["chunk_kernel_ms"] = _median_ms(chunk_k, *args)
    # The step's kernel updates the state in place: a fresh copy a call.
    step_k = jax.jit(lambda *a: gd.gdn_step(*a, interpret=interp))
    args = rule_inputs(jax.random.fold_in(key, 2), (g["slots"],))
    got, want = step_k(*args), jax.jit(gd.gdn_step_lax)(*args)
    out["step_o_err"], out["step_state_err"] = (
        _max_abs(got[0], want[0]), _max_abs(got[1], want[1]))
    out["step_kernel_ms"] = _median_ms(step_k, *args)
    out["step_twin_ms"] = _median_ms(jax.jit(gd.gdn_step_lax), *args)
    out["step_state_mb"] = round(args[5].nbytes / 1e6, 1)
    # Attention at 30 heads of 128: a tick's row and a chunk's 64.
    b, hd = g["slots"], 128 if not rehearse else 16
    pps = g["positions"] // g["page"]
    ks = jax.random.split(jax.random.fold_in(key, 3), 3)
    pool = lambda k: jax.random.normal(
        k, (b * pps, g["page"], h * hd), dt)
    k_pool, v_pool = pool(ks[0]), pool(ks[1])
    table = jnp.asarray(np.random.RandomState(seed).permutation(
        b * pps).reshape(b, pps), jnp.int32)
    lengths = jnp.asarray(np.random.RandomState(seed + 1).randint(
        g["positions"] // 4, g["positions"] - 64, size=b), jnp.int32)
    for t in (1, 64):
        q = jax.random.normal(ks[2], (b, t, h, hd), dt)
        kern = jax.jit(lambda *a: flash_paged_decode_attention(
            *a, interpret=interp))
        args = (q, k_pool, v_pool, lengths, table)
        if t == 1 or rehearse:  # the dense view of 64 x 4,096 rows is large
            out[f"attn_t{t}_err"] = _max_abs(
                kern(*args), jax.jit(paged_cached_attention)(*args))
        out[f"attn_t{t}_kernel_ms"] = _median_ms(kern, *args)
    out["attn_rows_visited"] = int(lengths.sum())
    emit("gdn_kernels", **out, shapes=g, tolerance=TOL_GDN_KERNEL_VS_TWIN)
    for name, err in out.items():
        if name.endswith("_err"):
            assert err <= TOL_GDN_KERNEL_VS_TWIN, (name, err)


def phase_dsa_kernels(sz, seed: int, rehearse: bool) -> None:
    """Learned sparse attention's three operations alone, at the glm52
    cell's shapes: each against what it must equal, each call timed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from mpit_tpu.ops import dsa
    from mpit_tpu.ops import mla_attention as mla

    g = sz["dsa"]
    b, ps, k, interp = g["slots"], g["page"], g["topk"], g["interpret"]
    pps = g["positions"] // ps
    dt = jnp.float32 if rehearse else jnp.bfloat16
    key = jax.random.key(seed)
    ks = jax.random.split(key, 8)
    rs = np.random.RandomState(seed)
    table = jnp.asarray(rs.permutation(b * pps).reshape(b, pps), jnp.int32)
    lengths = jnp.asarray(rs.randint(
        g["positions"] // 2, g["positions"] - g["chunk"], size=b), jnp.int32)
    key_pool = jax.random.normal(ks[0], (b * pps, ps, g["di"]), dt)
    out = {"rows_cached": int(lengths.sum()) + b}
    kern = jax.jit(lambda *a: dsa.dsa_index_scores(*a, interpret=interp))
    twin = jax.jit(dsa.reference_dsa_index_scores)
    scores = {}
    for name, rows, t in (("tick", b, 1), ("chunk", 1, g["chunk"])):
        q = jax.random.normal(ks[1], (rows, t, g["hi"], g["di"]), dt)
        w = 0.1 * jax.random.normal(ks[2], (rows, t, g["hi"]), jnp.float32)
        args = (q, w, key_pool, lengths[:rows], table[:rows])
        got, want = kern(*args), twin(*args)
        seen = jnp.isfinite(want)
        assert bool(jnp.all(jnp.isfinite(got) == seen)), name
        out[f"scores_{name}_err"] = _max_abs(
            jnp.where(seen, got, 0.0), jnp.where(seen, want, 0.0))
        out[f"scores_{name}_kernel_ms"] = _median_ms(kern, *args)
        out[f"scores_{name}_twin_ms"] = _median_ms(twin, *args)
        scores[name] = got
        del want
    for name, sc in scores.items():
        select = jax.jit(lambda s: dsa.dsa_select(s, k))
        top = jax.jit(lambda s: lax.top_k(s, k)[1])
        mask, idx = select(sc), top(sc)
        flat = np.asarray(mask).reshape(-1, mask.shape[-1])
        want = np.zeros(flat.shape, bool)
        np.put_along_axis(want, np.asarray(idx).reshape(-1, k), True, -1)
        out[f"select_{name}_rows_differing"] = int(
            (flat != want).any(-1).sum())
        out[f"select_{name}_ms"] = _median_ms(select, sc)
        out[f"top_k_{name}_ms"] = _median_ms(top, sc)
    to_rows = jax.jit(lambda m: dsa.mask_to_rows(m, k))
    mask = jax.jit(lambda s: dsa.dsa_select(s, k))(scores["tick"])[:, 0]
    rows, n = to_rows(mask)
    want = np.stack([np.flatnonzero(r)[:k] for r in np.asarray(mask)])
    out["mask_to_rows_wrong"] = int((np.asarray(rows) != want).sum())
    out["mask_to_rows_ms"] = _median_ms(to_rows, mask)
    ckv = jax.random.normal(ks[3], (b * pps, ps, g["latent"]), dt)
    kr = jax.random.normal(ks[4], (b * pps, ps, mla.lane_pad(g["rope"])), dt)
    qa = jax.random.normal(ks[5], (b, g["heads"], g["latent"]), dt) * 0.05
    qr = jax.random.normal(ks[6], (b, g["heads"], g["rope"]), dt) * 0.05
    attn = jax.jit(lambda *a: dsa.dsa_sparse_attn(*a, scale=1.0))
    got = attn(qa, qr, ckv, kr, rows, n, table)

    def dense(qa, qr, ckv, kr, mask, table):  # every row, the mask applied
        c_all = ckv[table].reshape(b, -1, g["latent"])
        r_all = kr[table].reshape(b, -1, kr.shape[-1])[..., : g["rope"]]
        s = jnp.einsum("bhc,bkc->bhk", qa, c_all,
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bhr,bkr->bhk", qr, r_all,
                           preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), -1)
        return jnp.einsum("bhk,bkc->bhc", p.astype(c_all.dtype), c_all,
                          preferred_element_type=jnp.float32)

    out["sparse_attn_err"] = _max_abs(
        got, jax.jit(dense)(qa, qr, ckv, kr, mask, table))
    out["sparse_attn_ms"] = _median_ms(attn, qa, qr, ckv, kr, rows, n, table)
    out["rows_read"] = int(n.sum())
    emit("dsa_kernels", **out, shapes=g,
         tolerance=dict(scores=TOL_DSA_SCORES, attn=TOL_DSA_ATTN))
    for name, err in out.items():
        if name.startswith("scores_") and name.endswith("_err"):
            assert err <= TOL_DSA_SCORES, (name, err)
        if name.endswith("_differing") or name.endswith("_wrong"):
            assert err == 0, (name, err)
    assert out["sparse_attn_err"] <= TOL_DSA_ATTN, out["sparse_attn_err"]


def phase_mla_chunk_kernel(sz, seed: int, rehearse: bool) -> None:
    """A chunk's expanded latent attention alone at the two latent cells'
    shapes: the kernel against its lax twin, each call timed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpit_tpu.ops import mla_attention as mla

    g = sz["mla_chunk"]
    ps, interp = g["page"], g["interpret"]
    dt = jnp.float32 if rehearse else jnp.bfloat16
    out = {}
    for name in ("xing4", "glm52"):
        c = g[name]
        b, t, h, topk = c["b"], c["t"], c["h"], c["topk"]
        pps = c["positions"] // ps
        ks = jax.random.split(jax.random.fold_in(jax.random.key(seed), h), 6)
        qn = jax.random.normal(ks[0], (b, t, h, c["dn"]), dt)
        qr = jax.random.normal(ks[1], (b, t, h, c["dr"]), dt)
        ckv = jax.random.normal(ks[2], (b * pps, ps, g["latent"]), dt)
        kr = jnp.pad(
            jax.random.normal(ks[3], (b * pps, ps, c["dr"]), dt),
            ((0, 0), (0, 0), (0, mla.lane_pad(c["dr"]) - c["dr"])))
        w = 0.05 * jax.random.normal(
            ks[4], (g["latent"], h, c["dn"] + c["dv"]), dt)
        table = jnp.asarray(np.random.RandomState(seed).permutation(
            b * pps).reshape(b, pps), jnp.int32)
        scale = (c["dn"] + c["dr"]) ** -0.5
        kern = jax.jit(lambda select, *a: mla.mla_paged_prefill_attention(
            *a, scale=scale, select=select, interpret=interp))
        twin = jax.jit(
            lambda select, *a: mla.reference_mla_paged_prefill_attention(
                *a, scale=scale, select=select))
        for prefix in c["prefixes"]:
            # Participants a page apart, as slots that refill together are.
            lengths = jnp.maximum(
                prefix - ps * jnp.arange(b, dtype=jnp.int32), 0)
            select = None
            if topk:  # about topk of each row's visible positions
                pos = lengths[:, None] + jnp.arange(t)[None, :]
                u = jax.random.uniform(ks[5], (b, t, pps * ps))
                select = u * (pos[:, :, None] + 1) < topk
            args = (select, qn, qr, ckv, kr, lengths, table, w)
            got, want = kern(*args), twin(*args)
            assert bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))), name
            tag = f"{name}_prefix{prefix}"
            out[f"{tag}_err"] = _max_abs(got, want)
            del got, want
            out[f"{tag}_kernel_ms"] = _median_ms(kern, *args)
            out[f"{tag}_twin_ms"] = _median_ms(twin, *args)
    emit("mla_chunk_kernel", **out, shapes=g, tolerance=TOL_MLA_CHUNK)
    for name, err in out.items():
        if name.endswith("_err"):
            assert err <= TOL_MLA_CHUNK, (name, err)


def phase_chunk_rows(sz, seed: int, rehearse: bool) -> None:
    """What a chunk step costs by its rows, on GPT-2 large at the serving
    cells' shape: the compacted step alone (its arguments on the device,
    ``reps`` calls enqueued back to back, the last waited for) for each
    ``(participants, width)`` of ``sz["chunk_rows"]["steps"]``, the
    full-batch step with two participants, and the host's time in
    ``Engine.prefill_dispatch`` for one and two participants through the
    compacted and the full-batch step."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.serve import Engine, warm_engine
    from mpit_tpu.serve import engine as engine_module

    c = sz["chunk_rows"]
    cfg = GPT2Config(
        vocab_size=c["vocab"], max_seq_len=c["positions"],
        num_layers=c["layers"], num_heads=c["heads"], d_model=c["d_model"],
        d_ff=4 * c["d_model"])
    shapes = jax.eval_shape(
        lambda: GPT2(cfg).init(jax.random.key(0),
                               jnp.zeros((1, 8), jnp.int32))["params"])
    leaves, tree = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    params = jax.tree.unflatten(tree, [
        (0.02 * jax.random.normal(k, l.shape)).astype(jnp.bfloat16)
        for k, l in zip(keys, leaves)])
    s, w, pps = c["slots"], c["chunk"], c["positions"] // c["page"]

    def build():
        return Engine(
            cfg, params, slots=s, max_len=c["positions"], seed=seed,
            kv_pages=s * pps, kv_page_size=c["page"], prefill_chunk=w,
            decode_attention=sz["decode_attention"])

    def enqueued_ms(call, reps):
        """``reps`` calls back to back, the last waited for: a call's
        time where the device is the slower side."""
        jax.block_until_ready(call())
        t0 = time.perf_counter()
        for _ in range(reps):
            out = call()
        jax.block_until_ready(out)
        return round(1e3 * (time.perf_counter() - t0) / reps, 3)

    def build_under(bound):
        """An engine whose chunk steps start at ``bound`` rows: the
        steered constant is the tests' way, never an option."""
        kept = engine_module._WEIGHT_BOUND_ROWS
        engine_module._WEIGHT_BOUND_ROWS = bound
        try:
            return build()
        finally:
            engine_module._WEIGHT_BOUND_ROWS = kept

    counts = engine_module._chunk_step_counts(s, w)  # the rule's own
    eng = build_under(w)  # a step a count from one participant up
    # Every slot owns its own pages, a prefix of c["prefix"] rows cached.
    table = jnp.arange(s * pps, dtype=jnp.int32).reshape(s, pps)
    key = jax.random.key(seed)
    temp, topk = jnp.zeros((s,), jnp.float32), jnp.zeros((s,), jnp.int32)
    rng = np.random.RandomState(seed)

    def seats_call(idx, base, lens, width):
        """The compacted step over seats of ``width`` rows: seat ``i``
        is slot ``idx[i]``'s ``lens[i]`` tokens from position
        ``base[i]``."""
        idx = list(idx)
        n = len(idx)
        toks = jnp.asarray(rng.randint(0, c["vocab"], (n, width)), jnp.int32)
        # As the scheduler marks them: a slot's last seat samples.
        mask = jnp.asarray([
            lens[k] > 0 and i not in idx[k + 1:] for k, i in enumerate(idx)])
        idx, base, lens = (jnp.asarray(a, jnp.int32) for a in (idx, base, lens))
        floor = jnp.zeros((n,), jnp.int32)

        def call():
            eng.cache, eng.last_token, *_ = eng._prefill_compact_jit(
                eng.params, eng.cache, eng.last_token, idx, toks, base,
                lens, floor, mask, table, key, temp, topk)
            return eng.last_token
        return call

    def compact_call(n, width):
        return seats_call(range(n), [c["prefix"]] * n, [width] * n, width)

    def full_call(takers):
        toks = jnp.asarray(rng.randint(0, c["vocab"], (s, w)), jnp.int32)
        lens = jnp.where(jnp.arange(s) < takers, w, 0).astype(jnp.int32)
        base = jnp.where(lens > 0, c["prefix"], 0).astype(jnp.int32)
        floor = jnp.zeros((s,), jnp.int32)

        def call():
            eng.cache, eng.last_token, *_ = eng._prefill_paged_jit(
                eng.params, eng.cache, eng.last_token, toks, base, lens,
                floor, lens > 0, table, key, temp, topk)
            return eng.last_token
        return call

    steps = [
        dict(participants=n, width=width, rows=n * width,
             step_ms=enqueued_ms(compact_call(n, width), c["reps"]))
        for n, width in c["steps"]]
    full_ms = enqueued_ms(full_call(2), c["reps"])
    # The step of the rule's own count with its seats filled three ways
    # (ISSUE 40): one slot's two next chunks, two slots' chunks, one
    # slot's chunk and padding. Three rounds, the forms in turn.
    n, pre = max(counts[0], 2) if counts else 2, c["prefix"]
    forms = dict(
        one_slot_chained=seats_call(
            [0] * n, [pre + k * w for k in range(n)], [w] * n, w),
        a_slot_a_seat=compact_call(n, w),
        one_seat_and_padding=seats_call(
            [0] + [s] * (n - 1), [pre] + [0] * (n - 1),
            [w] + [0] * (n - 1), w),
    )
    seat_forms = {name: [] for name in forms}
    for _ in range(3):
        for name, call in forms.items():
            seat_forms[name].append(enqueued_ms(call, c["reps"]))
    peak = (jax.local_devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")

    def host_ms(fn, reps=3 * c["reps"]):
        """Median host time of ``fn()`` alone (what it enqueued is waited
        for outside the clock)."""
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            times.append(time.perf_counter() - t0)
            jax.block_until_ready(out)
        return round(1e3 * sorted(times)[len(times) // 2], 3)

    def dispatch_ms(engine, takers):
        toks = rng.randint(0, c["vocab"], (s, w)).astype(np.int32)
        lens = np.where(np.arange(s) < takers, w, 0).astype(np.int32)
        zeros = np.zeros((s,), np.int32)
        return host_ms(lambda: engine.prefill_dispatch(
            toks, zeros, lens, zeros, lens > 0,
            np.zeros((s,), np.float32), zeros)[0])

    # A group's small arguments to the device: the one vector through
    # the jitted chunk_rows, as the compacted dispatch moves them, against
    # a transfer each, as it did before.
    six = [np.zeros((2,), np.int32)] * 4 + [
        np.zeros((2, w), np.int32), np.zeros((2,), bool)]
    vector = np.zeros((2 * (w + 5) + s * pps,), np.int32)
    transfer = dict(
        one_vector_ms=host_ms(lambda: eng._chunk_rows_jit(vector)),
        six_asarray_ms=host_ms(lambda: [jnp.asarray(a) for a in six]),
        split_ms=host_ms(lambda: engine_module._split_pair(key)),
    )
    t0 = time.perf_counter()
    warm_engine(eng)
    warm_s = round(time.perf_counter() - t0, 2)
    compact_host = {k: dispatch_ms(eng, k) for k in (1, 2)}
    del eng
    gc.collect()
    eng = build_under(1 << 30)  # the full-batch step's host side
    assert not eng._prefill_counts
    t0 = time.perf_counter()
    warm_engine(eng)
    full_warm_s = round(time.perf_counter() - t0, 2)
    full_host = {k: dispatch_ms(eng, k) for k in (1, 2)}
    del eng
    gc.collect()
    emit(
        "chunk_rows", layers=c["layers"], d_model=c["d_model"], slots=s,
        chunk=w, prefix_rows=c["prefix"], prefill_counts=counts,
        compact_steps=steps, full_batch_step_ms_two_participants=full_ms,
        seat_forms_step_ms=dict(seats=n, rows=n * w, **seat_forms),
        peak_bytes_in_use=peak,
        prefill_dispatch_host_ms=dict(compact=compact_host, full=full_host),
        group_transfer_host_ms=transfer,
        warm_engine_s=dict(compact=warm_s, full=full_warm_s),
    )


def _holds_a_shard_each(state, devices) -> dict:
    """ZeRO-1 optimizer state must span every device, a shard on each."""
    import jax

    sharded = [
        leaf for leaf in jax.tree.leaves(state.opt_state)
        if hasattr(leaf, "addressable_shards")
        and leaf.addressable_shards[0].data.shape != leaf.shape
    ]
    assert sharded, "no optimizer-state leaf is sharded"
    for leaf in sharded:
        held = {s.device for s in leaf.addressable_shards}
        assert held == set(devices), (leaf.shape, held)
    in_use = []
    for d in devices:
        stats = d.memory_stats()  # None on the CPU backend
        if stats is not None:
            in_use.append(stats["bytes_in_use"])
    return {"sharded_leaves": len(sharded), "bytes_in_use": in_use}


def phase_dp4(sz, seed: int, rehearse: bool) -> bool:
    import gc

    import jax

    import mpit_tpu
    from mpit_tpu.asyncsgd import gpt2
    from mpit_tpu.asyncsgd.config import from_argv

    tcfg = from_argv(gpt2.GPT2TrainConfig, _train_argv(sz, "--seed", str(seed)))
    devices = jax.devices()
    one = mpit_tpu.init({"data": 1}, devices=devices[:1], set_default=False)
    four = mpit_tpu.init({"data": 4})

    ref, _, _ = _run_train(one, tcfg, "psum")
    gc.collect()
    psum, state, _ = _run_train(four, tcfg, "psum")
    _check_losses(psum, sz["steps"])
    placed = _holds_a_shard_each(state, devices)
    if placed["bytes_in_use"]:
        # Params alone are ~0.5 GB on every chip at GPT-2 small.
        assert min(placed["bytes_in_use"]) > 2**28, placed
    del state
    gc.collect()
    diff = max(abs(a - b) for a, b in zip(ref, psum))
    assert diff <= TOL_DP4_VS_ONE_CHIP, (ref, psum)
    emit("dp4", grad_sync="psum", losses=psum, one_chip_losses=ref,
         max_loss_diff_vs_one_chip=diff, tolerance=TOL_DP4_VS_ONE_CHIP,
         global_batch=sz["batch"], **placed)

    # The ring kernels' remote-DMA and semaphore protocol has only ever
    # run in the interpreter; a hang is the likely failure. A watchdog
    # reports it and ends the process (a hung device call cannot be
    # interrupted from Python).
    def hung():
        emit("dp4", ring=f"failed: no result within {RING_TIMEOUT_S:.0f}s")
        os._exit(1)

    ok = True
    for mode, tol in (
        ("ring", TOL_DP4_VS_ONE_CHIP), ("ring_q8", TOL_RING_Q8_VS_PSUM)
    ):
        watchdog = threading.Timer(RING_TIMEOUT_S, hung)
        watchdog.daemon = True
        watchdog.start()
        try:
            losses, state, step_fn = _run_train(four, tcfg, mode)
        finally:
            watchdog.cancel()
        del state
        gc.collect()
        _check_losses(losses, sz["steps"])
        # Off the chip the ring modes run their lax composition, and say so.
        assert rehearse or step_fn.grad_sync_mode == mode, step_fn.grad_sync_mode
        diff = max(abs(a - b) for a, b in zip(psum, losses))
        passed = diff <= tol
        ok = ok and passed
        emit("dp4", grad_sync=mode, executed=step_fn.grad_sync_mode,
             losses=losses, max_loss_diff_vs_psum=diff, tolerance=tol,
             bitwise_equal_to_psum=losses == psum, passed=passed)
    return ok


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true")
    # One-chip phases to run, by name (all: every one, in this order).
    one_chip = ("train", "serve", "serve_xing4", "gdn_kernels", "dsa_kernels",
                "mla_chunk_kernel", "chunk_rows")
    parser.add_argument("--phases", default="all",
                        help="comma list of " + ", ".join(one_chip))
    args = parser.parse_args(argv)
    phases = one_chip if args.phases == "all" else tuple(
        args.phases.split(","))
    if set(phases) - set(one_chip):
        parser.error(f"--phases: expected some of {one_chip}")
    if args.rehearse:
        # Sizes and the platform, before jax starts.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}"
        )
    sz = TINY if args.rehearse else FULL

    device = phase_device(args.chips, args.rehearse)
    if args.chips == 4:
        ok = phase_dp4(sz, args.seed, args.rehearse)
    else:
        if "train" in phases:
            phase_train(sz, args.seed, args.rehearse)
        if "serve" in phases:
            probe = phase_serve(sz, args.seed, args.rehearse)
            phase_serve_int8(sz, args.seed, args.rehearse, probe)
            del probe
        if "serve_xing4" in phases:
            phase_serve_xing4(sz, args.seed, args.rehearse)
        if "gdn_kernels" in phases:
            phase_gdn_kernels(sz, args.seed, args.rehearse)
        if "dsa_kernels" in phases:
            phase_dsa_kernels(sz, args.seed, args.rehearse)
        if "mla_chunk_kernel" in phases:
            phase_mla_chunk_kernel(sz, args.seed, args.rehearse)
        if "chunk_rows" in phases:
            phase_chunk_rows(sz, args.seed, args.rehearse)
        ok = True
    if args.rehearse:
        # A rehearsal is never a pass: it says what it ran on, and 3.
        print(json.dumps({"ok": False, "rehearsal_passed": ok,
                          "device": device}))
        return 3 if ok else 1
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
