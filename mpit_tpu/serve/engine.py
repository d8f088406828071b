"""Batched inference engine over a page pool: ONE jitted prefill chunk +
ONE jitted decode (+ the tiny page copy).

The execution contract:

- **Fixed shapes, no per-request recompiles.** Decode runs on
  ``[slots, 1]``; a prefill chunk on ``[n, prefill_chunk]`` padded
  prompt slices of the ``n`` slots that take part, ``n`` one of a few
  compiled counts (``_chunk_step_counts``: a chunk tick costs what its
  participants cost). A speculative or tensor-parallel engine, and one
  too small for a step over fewer slots to be faster, runs the chunk
  on ``[slots, prefill_chunk]`` and discards the slots without one.
- **The cache is a page pool** (``serve.kvcache.PagedKVCache`` + the host
  ``PageAllocator``): ``kv_pages`` pages of ``kv_page_size`` positions
  shared by all slots, indirected by per-slot block tables. K/V appends
  scatter through the tables (masked rows dropped, so a padded chunk can
  never touch a page the slot does not own), attention runs the paged
  flash-decode kernel (or the gather-dense reference), and ``max_len``
  is a VIRTUAL per-slot capacity — HBM scales with ``kv_pages x
  kv_page_size``. ``kv_pages=None`` sizes the pool so that every slot
  can reach ``max_len``: ``slots x (max_len // kv_page_size)``.
  ``prefill_chunk`` fixes the traced prefill width so the scheduler can
  slice long admits across ticks (chunked prefill). The steps donate the
  pool, so one lives.
- **Beside the pages, a state pool** for a model whose layout has
  layers that keep a fixed state a slot (``PagedKVCache.state``; empty
  otherwise, and the steps are then what they were): the three paged
  steps carry it as they carry the pages, the compacted chunk step tells
  the model which slot each of its rows is, and ``page_bytes`` /
  ``slot_state_bytes`` (the memory ledger's ``kv_pages`` / ``kv_state``)
  come from the layout.
- **A chunk writes the cache** at each participating slot's fill and,
  for the slots whose last prompt token rides it, samples the request's
  FIRST output token; **decode appends one token** per active slot at
  its current length. Greedy outputs match the no-cache ``models.gpt2``
  forward (parity-pinned in ``tests/test_serve.py``).
- **Sampling is jitted with the step**: per-slot greedy / temperature /
  top-k arrays, so heterogeneous requests batch together.

Tensor parallelism: ``Engine(..., world=w, tp_axis="model")`` swaps the
flax forward for a hand-placed shard_map forward that reuses the
``parallel.megatron`` block rules — column-parallel qkv/fc,
row-parallel proj/out closing on a psum, ``repack_qkv`` for contiguous
head shards, ``tp_block_specs`` for the param placement — with each
layer's pool buffer sharded on its packed head axis
(``kvcache.paged_cache_specs``). Embeddings and the LM head stay
replicated (decode is latency-bound on the blocks; the head matmul at
T=1 is negligible).

Speculative decoding (ISSUE 13): ``Engine(spec_k=k, draft_params=...,
draft_cfg=...)`` swaps the decode tick for draft-then-verify — a draft
model (its pool mirrors the target's page geometry and shares its block
tables, so COW/prefix-sharing/preemption carry draft K/V for free)
proposes ``k`` tokens per slot, the target scores all ``k+1`` positions
in ONE T=k+1 pass through the same forward (flash-decode small-T trace
included), and cache lengths advance by the accepted count only (the
rollback). Greedy speculative output bit-matches the plain engine per
request; temperature/top-k go through exact rejection sampling against
the blocked LM head (``ops.lm_head.lm_head_verify``; the reference
engine verifies on materialized logits — the oracle). Compile count
stays fixed for the engine's lifetime: prefill (draft fused),
``spec_draft``, ``spec_verify``, the COW copy.

Host surface: :meth:`Engine.prefill_paged` + :meth:`Engine.copy_page` /
:meth:`Engine.decode`, or :meth:`Engine.spec_draft` +
:meth:`Engine.spec_verify` on a speculative engine — the scheduler
(``serve.scheduler``) owns queueing, admission (page allocation, COW,
prefix registration), retirement and observability around them.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from mpit_tpu.models.gpt2 import paged_cache_update, paged_cached_attention
from mpit_tpu.models.serving import as_serve_model
from mpit_tpu.ops.kv_quant import pack_heads, unpack_heads
from mpit_tpu.ops.quantized_matmul import (
    QuantizedTensor,
    dequantize_tensor,
    quantized_matmul,
    quantized_matmul_reference,
    quantized_matmul_t,
)
from mpit_tpu import obs
from mpit_tpu.obs import roofline as _roofline
from mpit_tpu.obs import startup as _startup
from mpit_tpu.ops.decode_attention import (
    flash_paged_decode_attention,
    num_kv_blocks,
    pick_block_k,
)
from mpit_tpu.ops.lm_head import lm_head_sample, lm_head_verify
from mpit_tpu.obs.memledger import MemLedger
from mpit_tpu.serve.spec import (
    accept_emit,
    draft_distribution,
    modified_logits,
    register_draft_store,
    verify_reference,
)
from mpit_tpu.serve.kvcache import (
    PageAllocator,
    PagedKVCache,
    QuantizedKV,
    alloc_paged_cache,
    paged_cache_specs,
    window_slot_pages,
)
from mpit_tpu.serve.weights import (
    params_wire_bytes,
    quantize_gpt2_params,
    register_param_store,
)

__all__ = ["Engine", "sample_tokens"]

# Engine.kv_dtype values (None = follow cfg.dtype — the default path,
# byte-identical to an engine that never heard of the knob). "int8"
# (ISSUE 15) stores the cache as int8 + per-(row, head) scale blocks:
# writes quantize through the shared ring-collectives rounding
# contract, the flash-decode kernel dequantizes per visited tile in
# VMEM, and the reference path dequantizes through the same helpers
# (the oracle). "f32"/"bf16" simply pin the pool's dtype.
_KV_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": None}
_DTYPE_SHORT = {"float32": "f32", "bfloat16": "bf16", "int8": "int8"}

# Engine.weights_dtype values (None = dense params as loaded — the
# default path, byte-identical to an engine that never heard of the
# knob). "int8" (ISSUE 17) quantizes every matmul weight at
# construction (per-row int8 + f32 scale through the SAME
# ring-collectives rounding contract the int8 KV cache uses) and every
# step runs the blocked fused-dequant matmul — weights dequantize one
# VMEM tile at a time, never as a full f32 array in HBM. Same lifetime
# compile count; the decode HBM sweep's weight term shrinks ~4x.
_WEIGHT_DTYPES = ("f32", "int8")


def _startup_span(name: str):
    """The decorated constructor as one span of the start-up record
    (``obs.startup``; the compile listeners are registered first)."""

    def deco(init):
        @functools.wraps(init)
        def built(self, *args, **kwargs):
            _startup.install()
            with _startup.span(name):
                init(self, *args, **kwargs)

        return built

    return deco


def _jit_as(name: str, step, donate=()):
    """``jax.jit(step)`` under the stable module name ``jit_<name>``
    (a bound method would give ``jit__paged_decode_step``): a device
    trace names every operation's module, and a reduction tells the
    tick's own operations from the RNG split's and the page copies' by
    it. ``donate``: positions of the page-pool caches the step consumes.
    The paged steps donate them, so each layer's buffer is updated in
    place and one pool lives, not two; the caller must drop its
    reference and keep the cache the step returns (``Engine`` assigns
    ``self.cache`` from every such call)."""

    def named(*args):
        return step(*args)

    named.__name__ = named.__qualname__ = name
    return jax.jit(named, donate_argnums=tuple(donate))


@jax.jit
def _split_pair(key):
    """``jax.random.split(key)`` as its two keys, in one dispatch: taking
    the pair apart on the host is two more (0.80 ms against 0.42 on the
    v5e's host, in every tick)."""
    pair = jax.random.split(key)
    return pair[0], pair[1]


def _zeroed(cache):
    """Zeros in the place of ``cache``: its buffers are freed before the
    new ones are made, so that two caches never live at once."""
    # Placed as the old one was (a buffer that was never committed to a
    # device stays so: the jitted steps key their cache on it).
    like = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if a.committed else None
        ),
        cache,
    )
    # A step that still runs holds its buffers: deleted under it they
    # would go only when it ends, after the zeros below were made (a
    # second pool beside the first: 16.2 GB of 16.9 at 32 latent slots).
    jax.block_until_ready(cache)
    for leaf in jax.tree.leaves(cache):
        leaf.delete()
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype, device=s.sharding), like
    )


# Engine.decode_attention values. "kernel" = the Pallas flash-decode path
# (ISSUE 5) where available — on non-TPU backends the kernel call falls
# back to the reference math, and decode_attention_mode says so;
# "interpret" forces the kernel through the Pallas interpreter (the CPU
# parity-test path); "reference" = the gather-dense
# paged_cached_attention + materialized-logits sampling, kept as the
# parity oracle.
_DECODE_MODES = ("kernel", "interpret", "reference")

# A chunk step reads every block's weights whatever its rows, so up to some
# count of rows its time does not rise with them, and past it the step is
# bound by its products: _WEIGHT_BOUND_ROWS, the knee, read from this
# table (197 TFLOP/s over 819 GB/s put it at 240 rows of bf16 weights at the
# MXU's peak, whatever the model). GPT-2 large (36 blocks of width 1,280,
# 1.42 GB of block weights: 1.7 ms at 819 GB/s) on one v5e chip, the
# compacted chunk step alone, a prefix of 256 cached rows a participant,
# 20 steps enqueued back to back (chip_smoke.py --phases chunk_rows; PR 35,
# calls 1 and 3 agree to 0.03 ms):
#
#     rows   16     32     64     128    256    512    1,024
#     ms     2.83   2.81   2.87   2.98   5.04   9.17   16.96
#
# 128 rows cost 4 % more than 64 and 6 % more than 16; 256 rows cost 69 %
# more than 128. (The full-batch step, 1,024 rows with two slots taking
# part: 14.93 ms.) Past the knee a step over twice the slots saves, against
# two steps, the part of a weight read that its products no longer hide:
# 16 % at 256 rows, 8 % at 512 and at 1,024. Every compiled count costs
# set-up: 4.5 s of tracing, loading and a first run at 36 layers with the
# persistent cache warm, 20-25 s cold (PR 35, call 3), and its own temporaries
# beside the weights and the pool; a step has at most _COMPACT_ROWS rows
# (2,048 rows of the widest model served here take 0.5 GB of them).
_WEIGHT_BOUND_ROWS = 128
_COMPACT_ROWS = 2048


def _chunk_step_counts(slots: int, chunk: int) -> tuple:
    """Counts of participants a chunk tick's step is compiled for; a tick
    pads its participants to the next count and takes more of them than
    the largest in several calls.

    The smallest is the first power of two whose ``n x chunk`` rows reach
    ``_WEIGHT_BOUND_ROWS``: up to there more slots ride the same weight
    read for nothing, so no smaller step is worth compiling. Where that
    takes several slots (a chunk narrower than the knee) it is the only
    count: more slots than that refill in one tick too seldom for a
    larger step's 8-16 % to pay a compiled count's set-up. Where one
    slot's chunk is past the knee already, two slots at once are the
    common case and the doublings whose rows fit ``_COMPACT_ROWS`` are
    compiled too. Empty where the smallest step would be the whole slot
    batch: the one full-batch step then serves every tick, as it does on
    a speculative or tensor-parallel engine."""
    n = 1
    while n * chunk < _WEIGHT_BOUND_ROWS:
        n *= 2
    if n >= slots:
        return ()
    counts = [n]
    while n == 1 and 2 * counts[-1] <= min(slots, _COMPACT_ROWS // chunk):
        counts.append(2 * counts[-1])
    return tuple(counts)


def sample_tokens(logits, key, temperature, top_k):
    """Per-slot sampling over ``logits`` [S, V] (float32).

    ``temperature`` [S] float32 — ``<= 0`` selects greedy (argmax) for
    that slot; ``top_k`` [S] int32 — ``> 0`` restricts sampling to the
    k highest-logit tokens (per slot; 0 = full vocab). All slots draw
    from one key (jax.random.categorical is row-independent noise).
    """
    greedy = temperature <= 0.0
    # Per-slot top-k threshold + temperature: the ONE shared
    # modification (serve/spec.py) — the speculative proposal q must be
    # exactly this distribution, so both read the same implementation.
    sampled = jax.random.categorical(
        key, modified_logits(logits, temperature, top_k), axis=-1
    )
    return jnp.where(
        greedy, jnp.argmax(logits, axis=-1), sampled
    ).astype(jnp.int32)


# ---------------------------------------------------------------------------
# TP forward (shard_map body): megatron block rules + head-sharded cache
# ---------------------------------------------------------------------------


def _tp_paged_forward(
    params, tokens, cache: PagedKVCache, block_tables, write_valid, *,
    cfg, axis, attn_fn=None, with_head=True,
):
    """The cache-aware GPT-2 transformer loop INSIDE shard_map over the
    TP axis, against this device's H/P head shard of the page pool (each
    layer's buffer ``[P, ps, H/P * Dh]``: the rank's contiguous slice of
    the packed rows).

    The per-device view: block matmul kernels arrive sharded per
    ``megatron.tp_block_specs`` (qkv in ``repack_qkv`` layout), the pool
    carries this device's H/P heads, embeddings/LayerNorms/head
    replicated. Numerics mirror ``models.gpt2`` block-for-block —
    ``megatron.layernorm`` is the parity-tested nn.LayerNorm
    equivalent; each half closes on a psum (row-parallel proj/out). K/V
    appends scatter through the (replicated) block tables with
    ``write_valid``-masked rows dropped; attention runs ``attn_fn``
    (default the gather-dense :func:`paged_cached_attention`; the
    serving engine plugs the paged flash kernel) against the pool.
    Padding rows past a slot's chunk can push past max_seq_len — their
    positions are clipped; their embeddings are write-masked / never
    attended anyway. Returns replicated logits (or, ``with_head=False``,
    the post-ln_f hiddens the blocked head samples from) + this device's
    updated pool shard."""
    from jax import lax

    from mpit_tpu.parallel import megatron as M

    p = lax.axis_size(axis)
    heads_local = cfg.num_heads // p
    lengths = cache.lengths
    t = tokens.shape[-1]
    positions = jnp.minimum(
        lengths[:, None] + jnp.arange(t)[None, :], cfg.max_seq_len - 1
    )
    with jax.named_scope("embed"):
        emb = params["wte"][tokens]
        if isinstance(emb, QuantizedTensor):
            # int8 weight store (ISSUE 17): the embedding GATHER picks T
            # int8 rows + their scales; only those rows dequantize — never
            # the whole [V, D] table.
            emb = dequantize_tensor(emb)
        x = emb.astype(cfg.dtype) + params["wpe"][positions].astype(
            cfg.dtype
        )

    dt = cfg.dtype
    # Quantized kernels (int8 weight store) keep their int8+scale wire —
    # the megatron dense helpers dequantize per contraction block inside
    # the blocked matmul; plain kernels cast to the compute dtype as
    # before.
    wdt = lambda l: l if isinstance(l, QuantizedTensor) else l.astype(dt)
    split = lambda a: a.reshape(*a.shape[:-1], heads_local, cfg.head_dim)
    new_k, new_v = [], []
    for i in range(cfg.num_layers):
        blk = params[f"block_{i}"]
        # The scope names of models.gpt2's Block: both forwards read
        # alike in a trace.
        with jax.named_scope("attn"):
            h = M.layernorm(
                x, blk["ln1"]["scale"], blk["ln1"]["bias"]
            ).astype(dt)
            qkv = M.column_parallel_dense(
                h, wdt(blk["qkv"]["kernel"]), blk["qkv"]["bias"].astype(dt)
            )
            q, k, v = jnp.split(qkv, 3, axis=-1)
            with jax.named_scope("kv_write"):
                # k, v are already the pool's packed rows [B, T, H/P*Dh].
                k_i = paged_cache_update(
                    cache.k[i], k, lengths, block_tables, valid=write_valid
                )
                v_i = paged_cache_update(
                    cache.v[i], v, lengths, block_tables, valid=write_valid
                )
            attn = (attn_fn or paged_cached_attention)(
                split(q), k_i, v_i, lengths, block_tables
            )
            attn = attn.reshape(*attn.shape[:-2], -1)
            x = x + M.row_parallel_dense(
                attn,
                wdt(blk["proj"]["kernel"]),
                blk["proj"]["bias"].astype(dt),
                axis=axis,
            )
        with jax.named_scope("mlp"):
            h = M.layernorm(
                x, blk["ln2"]["scale"], blk["ln2"]["bias"]
            ).astype(dt)
            h = jax.nn.gelu(
                M.column_parallel_dense(
                    h, wdt(blk["fc"]["kernel"]), blk["fc"]["bias"].astype(dt)
                )
            )
            x = x + M.row_parallel_dense(
                h,
                wdt(blk["out"]["kernel"]),
                blk["out"]["bias"].astype(dt),
                axis=axis,
            )
        new_k.append(k_i)
        new_v.append(v_i)

    new = PagedKVCache(k=tuple(new_k), v=tuple(new_v), lengths=lengths)
    with jax.named_scope("lm_head"):
        x = M.layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    if not with_head:
        # Blocked decode head: the replicated post-ln_f hiddens go back
        # to the jitted step, which samples via lm_head_sample — no
        # [B, T, vocab] logits here either.
        return x, new
    head = params.get("head", params["wte"])
    with jax.named_scope("lm_head"):
        if isinstance(head, QuantizedTensor):
            # Blocked x @ head.T over vocab-row tiles (ISSUE 17) — bitwise
            # equal to the dequantized einsum (full-D contraction per
            # logit), without a [V, D] f32 intermediate.
            logits = quantized_matmul_t(
                x.astype(cfg.head_dtype), head,
                block_rows=cfg.quant_block_rows or None,
            )
        else:
            logits = jnp.einsum(
                "btd,vd->btv",
                x.astype(cfg.head_dtype),
                head.astype(cfg.head_dtype),
                preferred_element_type=jnp.float32,
            )
    return logits, new


def _trimmed_sharding(world, spec):
    """NamedSharding for ``spec`` with trailing Nones dropped. jit keys
    on the canonical form — the steps' outputs come back as
    ``P(..., axis)`` whatever trailing Nones ``paged_cache_specs``
    spells — and a construction-vs-output sharding mismatch is one
    silent recompile on the second admission wave. Deriving from the
    spec (not a hardcoded literal) keeps paged_cache_specs the single
    owner of the pool's sharded-axis position."""
    parts = list(spec)
    while parts and parts[-1] is None:
        parts.pop()
    return world.sharding(*parts)


def _tp_param_specs(cfg, params, axis: str):
    """Spec tree mirroring a dense GPT-2 param tree: ``tp_block_specs``
    per block, everything else replicated.

    int8 weight store (ISSUE 17): a quantized kernel is a TWO-leaf
    pytree (int8 payload + per-row f32 scales), so its block spec
    expands to the matching twin — the payload keeps the kernel's own
    placement; the scales follow the kernel's ROW placement (column-
    parallel ``P(None, axis)`` shards output columns, rows replicated →
    scales replicated; row-parallel ``P(axis, None)`` shards the rows
    the scales describe → scales shard with them). Replicated entries
    (wte/head) need no special case: ``jax.tree.map`` descends into the
    quantized pytree and replicates both leaves."""
    from jax.sharding import PartitionSpec as P

    from mpit_tpu.parallel.megatron import tp_block_specs

    specs: dict[str, Any] = {
        k: jax.tree.map(lambda _: P(), v)
        for k, v in params.items()
        if not str(k).startswith("block_")
    }
    for i in range(cfg.num_layers):
        bspecs = tp_block_specs(axis)
        blk = params[f"block_{i}"]
        for mod in ("qkv", "proj", "fc", "out"):
            if isinstance(blk[mod]["kernel"], QuantizedTensor):
                kspec = bspecs[mod]["kernel"]
                bspecs[mod] = dict(
                    bspecs[mod],
                    kernel=QuantizedTensor(
                        q=kspec,
                        scale=P(kspec[0] if len(kspec) else None, None),
                    ),
                )
        specs[f"block_{i}"] = bspecs
    return specs


class Engine:
    """Slot-batched inference through a page pool, over one model's
    param tree.

    ``model`` is a :class:`~mpit_tpu.models.serving.ServeModel`, or a
    configuration that names one (``GPT2Config`` does): the engine asks
    it for the cache row layout, the forward through the cache, the head
    and what the family cannot do yet, and names no family itself.

    Device state lives on the engine (cache + per-slot last token);
    ``active``/sampling arrays are passed per call by the scheduler.
    ``world``/``tp_axis`` select the tensor-parallel variant; params are
    placed (and qkv repacked) at construction, so per-step host traffic
    is the slot-width control arrays only.
    """

    @_startup_span("engine_build")
    def __init__(
        self,
        model,
        params,
        *,
        slots: int = 8,
        max_len: int | None = None,
        prefill_len: int | None = None,
        world=None,
        tp_axis: str | None = None,
        seed: int = 0,
        decode_attention: str = "kernel",
        decode_block_k: int | None = None,
        sample_block: int = 8192,
        sample_k_cap: int = 128,
        kv_pages: int | None = None,
        kv_page_size: int = 16,
        kv_host_pages: int | None = None,
        prefill_chunk: int | None = None,
        spec_k: int = 0,
        draft_params=None,
        draft_cfg=None,
        kv_dtype: str | None = None,
        weights_dtype: str | None = None,
    ):
        model = as_serve_model(model)
        cfg = model.cfg
        # What this family does not have yet fails here, by name.
        model.check_supported(
            tp=tp_axis is not None,
            kv_dtype=kv_dtype, weights_dtype=weights_dtype,
            spec_k=int(spec_k or 0), host_pages=int(kv_host_pages or 0),
        )
        if decode_attention not in _DECODE_MODES:
            raise ValueError(
                f"decode_attention must be one of {_DECODE_MODES}, got "
                f"{decode_attention!r}"
            )
        self.cfg = cfg
        self.slots = slots
        self.max_len = min(max_len or cfg.max_seq_len, cfg.max_seq_len)
        self.prefill_len = min(prefill_len or self.max_len, self.max_len)
        self.tp_axis = tp_axis
        self._tp_ways = 1
        self._key = jax.random.key(seed)
        self._sub = None  # the next subkey, where it was split ahead
        self._staged = {}  # a decode tick's small inputs as last staged

        # -- KV cache wire dtype (ISSUE 15 tentpole) --------------------------
        # None = the historical default (cache in cfg.dtype) — the path
        # stays byte-identical, pinned by the greedy-parity suite.
        # "int8" = quantized storage + in-kernel fused dequant; the
        # engine's whole step surface (TP/chunked/spec) carries the
        # dtype, still at the pinned lifetime compile count.
        if kv_dtype is not None and kv_dtype not in _KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of "
                f"{sorted(k for k in _KV_DTYPES)} (or None = follow the "
                f"model dtype), got {kv_dtype!r}"
            )
        self.kv_quantized = kv_dtype == "int8"
        self._cache_dtype = (
            _KV_DTYPES[kv_dtype]
            if kv_dtype is not None and not self.kv_quantized
            else None  # None = follow cfg.dtype (alloc default)
        )
        # The wire dtype label (stats / span stamping / bench): what the
        # cache rows actually occupy HBM as. kv_dtype_explicit gates the
        # span label — default engines' spans stay byte-identical (the
        # grad_sync= idiom: the default mode is unlabeled).
        self.kv_dtype_explicit = kv_dtype is not None
        self.kv_dtype = kv_dtype or _DTYPE_SHORT.get(
            jnp.dtype(cfg.dtype).name, jnp.dtype(cfg.dtype).name
        )

        # -- weight wire dtype (ISSUE 17 tentpole) ----------------------------
        # None = the historical default (dense params as loaded) — the
        # path stays byte-identical, pinned by the greedy-parity suite.
        # "int8" quantizes every matmul weight at construction (qkv/
        # proj/fc/out kernels, wte, head — biases and LayerNorms stay
        # f32; they are ~0.1% of the bytes and additive precision is
        # cheap) and runs the blocked fused-dequant matmul everywhere:
        # TP/chunked-prefill/speculative, at the same pinned lifetime
        # compile count.
        if weights_dtype is not None and weights_dtype not in _WEIGHT_DTYPES:
            raise ValueError(
                f"weights_dtype must be one of {list(_WEIGHT_DTYPES)} (or "
                f"None = dense params as loaded), got {weights_dtype!r}"
            )
        self.weights_quantized = weights_dtype == "int8"
        # The label (stats / span stamping / bench): what the matmul
        # weights actually occupy HBM as. weights_dtype_explicit gates
        # the span label — default engines' spans stay byte-identical
        # (the kv_dtype idiom).
        self.weights_dtype_explicit = weights_dtype is not None
        self.weights_dtype = weights_dtype or "f32"

        # -- the page pool ---------------------------------------------------
        # HBM holds a fixed pool of page_size-token pages shared by all
        # slots, indirected by the host allocator's per-slot block
        # tables; max_len is the per-slot VIRTUAL capacity
        # (pages_per_slot × page_size), not an HBM reservation.
        # kv_pages is the pool's capacity and nothing else: None = every
        # slot can reach max_len. prefill_chunk splits long admits into
        # chunk slices interleaved with decode ticks (scheduler-driven).
        if kv_page_size < 1 or self.max_len % kv_page_size:
            raise ValueError(
                f"kv_page_size {kv_page_size} must divide "
                f"max_len={self.max_len} (pages_per_slot must be whole)"
            )
        self.page_size = kv_page_size
        self.pages_per_slot = self.max_len // kv_page_size
        if kv_pages is None:
            kv_pages = slots * self.pages_per_slot
        if kv_pages < 1:
            raise ValueError(f"kv_pages must be >= 1, got {kv_pages}")
        self.num_pages = kv_pages
        # ISSUE 20: host-RAM KV tier — host_pages page-sized spill
        # seats whose payloads live as numpy pytrees on this engine.
        # 0/None = no tier (every path byte-identical to pre-tiering).
        self.host_pages = int(kv_host_pages or 0)
        if self.host_pages < 0:
            raise ValueError(
                f"kv_host_pages must be >= 0, got {kv_host_pages}"
            )
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}"
            )
        # The traced chunk-buffer width: every prefill chunk (including
        # an unchunked whole-prompt admit) runs at this static shape —
        # one compile for the engine's lifetime, as in PR 4.
        self.prefill_chunk = min(
            prefill_chunk or self.prefill_len, self.prefill_len
        )
        # Counts of participants a compacted chunk tick is compiled for
        # (set below; empty = the full-batch step).
        self._prefill_counts: tuple = ()

        # -- speculative decoding (ISSUE 13 tentpole) ------------------------
        # spec_k > 0 swaps the decode tick for per-slot draft-then-
        # verify: a draft model (its own page pool MIRRORING the
        # target's page geometry so block tables, COW remaps and prefix
        # sharing carry draft K/V for free) proposes k tokens per slot,
        # the target scores all k+1 positions in ONE T=k+1 pass through
        # the existing forward
        # (flash-decode small-T trace included), and cache lengths
        # advance by the accepted count only — rejected drafts' rows
        # become junk past the watermark, which the mask hides and the
        # next append overwrites (the rollback). Still a fixed compile
        # count for the engine's lifetime: prefill (draft fused),
        # spec_draft, spec_verify, copy_page.
        self.spec_k = int(spec_k or 0)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if self.spec_k:
            if draft_params is None or draft_cfg is None:
                raise ValueError(
                    "spec_k > 0 requires draft_params and draft_cfg "
                    "(the draft model proposing the k tokens the "
                    "target verifies) — load one via serve.weights."
                    "load_gpt2_params or truncate the target with "
                    "serve.weights.draft_from_target"
                )
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab_size {draft_cfg.vocab_size} != target "
                    f"vocab_size {cfg.vocab_size}: speculation verifies "
                    "draft proposals under the target distribution — "
                    "the vocabularies must be identical"
                )
            if draft_cfg.max_seq_len < self.max_len:
                raise ValueError(
                    f"draft max_seq_len {draft_cfg.max_seq_len} < engine "
                    f"max_len {self.max_len}: the draft's positional "
                    "table must cover every cache position it drafts at"
                )
        elif draft_params is not None or draft_cfg is not None:
            raise ValueError(
                "draft_params/draft_cfg without spec_k: pass "
                "spec_k >= 1 to enable speculation"
            )

        # -- serving hot-loop shape (ISSUE 5): attention kernel + head --
        self.decode_attention = decode_attention
        # block_k, the unit the visited count and the bytes model
        # count in, divides page_size (the kernel's own tile is
        # several whole pages: ops.decode_attention.decode_tiling).
        self.decode_block_k = pick_block_k(self.page_size, decode_block_k)
        if self.page_size % self.decode_block_k:
            raise ValueError(
                f"decode_block_k={self.decode_block_k} does not divide "
                f"kv_page_size={self.page_size}; pick a divisor or omit "
                "it for the auto choice"
            )
        self._sample_block = sample_block
        platform = jax.devices()[0].platform
        # Where this engine's measurements are recorded — the label that
        # gates utilization verdicts (ISSUE 8): modeled costs are
        # recorded on any platform; MFU/bandwidth percentages only when
        # the recording platform IS the chip.
        self.platform = platform
        if decode_attention == "reference":
            attn_fn = None  # the gather-dense paged_cached_attention
            self.decode_attention_mode = "reference"
            self._blocked_head = False
        else:
            interp = True if decode_attention == "interpret" else None
            # The TP forward below takes the kernel by itself; the
            # one-chip forward takes it through the model.
            attn_fn = functools.partial(
                flash_paged_decode_attention,
                block_k=self.decode_block_k,
                interpret=interp,
            )
            # The label obs attaches to decode spans: what actually
            # executes — "kernel" mode off-TPU runs the reference
            # fallback, and the flight recorder must be able to
            # attribute a serve regression to exactly that.
            self.decode_attention_mode = (
                "kernel" if (interp or platform == "tpu") else "reference"
            )
            self._blocked_head = True
        # Blocked sampling bounds top_k by the static candidate-buffer
        # width; the scheduler validates at submit. None = the dense
        # sampler, no bound.
        self.sample_k_cap = sample_k_cap if self._blocked_head else None
        # The head is pure XLA, so off-TPU "kernel" mode keeps the
        # blocked sampler even though attention falls back — the mode
        # label alone does NOT pin the whole hot-loop shape, this does:
        # attention=reference + sampler=blocked is the fallback engine,
        # attention=reference + sampler=dense is the reference engine.
        self.decode_sampler = "blocked" if self._blocked_head else "dense"
        if attn_fn is not None:
            model = model.with_decode_attention(
                block_k=self.decode_block_k, interpret=interp,
                page_size=self.page_size,
            )
            # A family's kernel may tile the cache its own way; the
            # tile-count accounting follows what really runs.
            self.decode_block_k = getattr(
                model, "decode_block_k", self.decode_block_k
            )
            cfg = model.cfg
            self.cfg = cfg  # what the forward really runs, kernel included
        if self.weights_quantized:
            # The quantized matmul the model's dense layers run (the
            # cache_attention_fn injection idiom). Reference engines get
            # the whole-dequant oracle — deliberately materializing the
            # f32 weight, the anti-vacuity baseline the jaxpr contract
            # compares against; kernel/interpret engines run the blocked
            # two-channel-DMA fused-dequant matmul (its lax fallback
            # off-TPU — same blocked numerics, parity-pinned).
            if decode_attention == "reference":
                qmm = functools.partial(
                    quantized_matmul_reference,
                    block_rows=cfg.quant_block_rows or None,
                )
            else:
                qmm = functools.partial(
                    quantized_matmul,
                    block_rows=cfg.quant_block_rows or None,
                    interpret=(
                        True if decode_attention == "interpret" else None
                    ),
                )
            model = model.with_quant_matmul(qmm)
            cfg = model.cfg
            self.cfg = cfg
            if tp_axis is None:
                params = quantize_gpt2_params(params)
            # TP quantizes AFTER repack_qkv below: repack permutes
            # kernel COLUMNS (per-row scales are column-permutation
            # invariant, but the reshape needs plain arrays).

        sharding = None
        if tp_axis is not None:
            if world is None:
                raise ValueError("tp_axis requires a World")
            from mpit_tpu.parallel.megatron import repack_qkv

            p = self._tp_ways = world.axis_size(tp_axis)
            if cfg.num_heads % p:
                raise ValueError(
                    f"num_heads ({cfg.num_heads}) must divide TP={p}"
                )
            params = {
                k: repack_qkv(v, p) if str(k).startswith("block_") else v
                for k, v in params.items()
            }
            if self.weights_quantized:
                params = quantize_gpt2_params(params)
            self._specs = _tp_param_specs(cfg, params, tp_axis)
            params = jax.device_put(
                params,
                jax.tree.map(
                    lambda s: world.sharding(*s), self._specs,
                    is_leaf=lambda s: isinstance(
                        s, jax.sharding.PartitionSpec
                    ),
                ),
            )
            cs = paged_cache_specs(
                tp_axis, num_layers=cfg.num_layers,
                quantized=self.kv_quantized,
            )
            sharding = _trimmed_sharding(
                world, cs.k[0].q if self.kv_quantized else cs.k[0]
            )
            rep = jax.sharding.PartitionSpec()
            fwd = world.shard_map(
                functools.partial(
                    _tp_paged_forward, cfg=cfg, axis=tp_axis,
                    attn_fn=attn_fn, with_head=not self._blocked_head,
                ),
                in_specs=(self._specs, rep, cs, rep, rep),
                out_specs=(rep, cs),
            )
        else:

            def fwd(prms, tokens, cache: PagedKVCache, block_tables,
                    write_valid, row_valid=None, slot_index=None):
                # Blocked head: the forward ends at ln_f and the step
                # samples from hiddens; the reference engine: logits.
                # (k, v, state), and the third seats after them where
                # the family's layout has any.
                out, (k2, v2, *rest), aux = model.forward_paged(
                    prms, tokens, cache, block_tables, write_valid,
                    return_hidden=self._blocked_head, row_valid=row_valid,
                    slot_index=slot_index,
                )
                new = PagedKVCache(k2, v2, cache.lengths, *rest)
                return (out, new) if aux is None else (out, new, aux)

        self.model = model
        self.params = model.place(params) if tp_axis is None else params
        # Draft model + its cache (ISSUE 13). The draft always runs the
        # reference attention and materializes its (tiny) logits — the
        # proposal distribution q is part of the acceptance contract.
        # The draft stays REPLICATED under TP (its per-tick cost is the
        # speculation overhead; sharding a 2-layer draft buys nothing).
        if self.spec_k and self.weights_quantized:
            # The draft rides the SAME weight wire (ISSUE 17): the
            # acceptance-rate contract compares int8-draft proposals to
            # int8-target verification, so both sides quantize. Always
            # the BLOCKED matmul, even on a reference engine — the
            # draft's head runs inside the hot _spec_draft_step, and a
            # whole-dequant there would re-materialize [V, D] f32 every
            # tick (exactly what this PR removes).
            draft_cfg = dataclasses.replace(
                draft_cfg,
                quant_matmul_fn=functools.partial(
                    quantized_matmul,
                    block_rows=draft_cfg.quant_block_rows or None,
                    interpret=(
                        True if decode_attention == "interpret" else None
                    ),
                ),
            )
            draft_params = quantize_gpt2_params(draft_params)
        self.draft_cfg = draft_cfg
        self._spec_state = None  # device-side (drafted, q_x, q_probs)
        if self.spec_k:
            self._draft_model = as_serve_model(draft_cfg)
            drep = None
            if tp_axis is not None:
                # Pin the draft replicated across the mesh AT
                # CONSTRUCTION — otherwise the first mesh step re-lays
                # the arrays out and the second call recompiles,
                # breaking the engine's pinned lifetime compile count.
                drep = world.sharding()
                draft_params = jax.device_put(
                    draft_params,
                    jax.tree.map(lambda _: drep, draft_params),
                )
            # The draft pool mirrors the target's page geometry AND
            # its wire dtype (ISSUE 15): shared block tables carry
            # quantized draft K/V + scales through COW / prefix
            # sharing / preemption exactly as the target's.
            self.draft_cache = alloc_paged_cache(
                draft_cfg, slots, self.num_pages, self.page_size,
                sharding=drep, dtype=self._cache_dtype,
                quantized=self.kv_quantized,
            )
            if drep is not None:
                # lengths too — the alloc shards only K/V, but a later
                # tick hands back mesh-replicated lengths, and a
                # sharding change on ANY prefill operand is a recompile.
                self.draft_cache = jax.device_put(
                    self.draft_cache,
                    jax.tree.map(lambda _: drep, self.draft_cache),
                )
        else:
            self.draft_cache = None
        self.draft_params = draft_params
        # Host-side page bookkeeping: free list, refcounts, prefix
        # index, COW reservations, per-slot block tables (the tables
        # ride into every jitted step as a tiny int32 argument).
        layout = model.cache_layout()
        # The pool of the layers that keep a window of positions (none
        # where every page layer keeps them all): what every slot can
        # hold there at once, whatever max_len is.
        per_slot = window_slot_pages(
            layout.window, self.prefill_chunk, self.page_size
        )
        self.window_pages = slots * per_slot
        self.allocator = PageAllocator(
            self.num_pages, self.page_size, self.pages_per_slot, slots,
            host_pages=self.host_pages,
            prefix_shareable=layout.prefix_shareable,
            window=layout.window, window_pages=self.window_pages,
            window_slot_pages=per_slot,
        )
        # Columns of the block tables a step is handed: a table a
        # lifetime, side by side.
        self._table_cols = self.allocator.block_tables.shape[1]
        with _startup.span("cache_alloc") as alloc:
            self.cache = alloc_paged_cache(
                model, slots, self.num_pages, self.page_size,
                sharding=sharding, dtype=self._cache_dtype,
                quantized=self.kv_quantized, window_pages=self.window_pages,
            )
            # pages, state, third seats
            alloc.set(bytes=sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.cache)
            ))
        # Every step that writes the pool donates it (argument
        # positions of the cache and, on a speculative engine, the
        # draft cache): the scatter of a tick's rows is then the
        # only write the pool sees. The gather of a spill only reads.
        draft = bool(self.spec_k)
        self._prefill_paged_jit = _jit_as(
            "prefill_paged", self._paged_prefill_step,
            donate=(1, 13) if draft else (1,),
        )
        # A chunk tick costs what its participants cost: the step runs
        # over the slots that take part, compiled once for each count
        # of _chunk_step_counts (one rule for every family, read from
        # the shape). The full-batch step above stays for what cannot
        # take the compacted one yet, and for an engine so small that
        # no step over fewer slots would be faster.
        if not self.spec_k and tp_axis is None:
            self._prefill_counts = _chunk_step_counts(
                slots, self.prefill_chunk
            )
        # Where the smallest compiled step holds several seats (a chunk
        # narrower than the knee) and pages are all a slot keeps, a seat
        # no slot took goes to a prompt that is there (spare_seats).
        self._chains = (
            bool(self._prefill_counts) and self._prefill_counts[0] > 1
            and model.keeps_pages_alone
        )
        if self._prefill_counts:
            self._prefill_compact_jit = _jit_as(
                "prefill_paged", self._paged_prefill_compact_step,
                donate=(1,),
            )
            self._chunk_rows_jit = _jit_as(
                "chunk_rows", self._chunk_rows_step
            )
        if self.spec_k:
            self._spec_draft_jit = _jit_as(
                "spec_draft", self._spec_draft_step, donate=(1,)
            )
            self._spec_verify_jit = _jit_as(
                "spec_verify", self._spec_verify_step, donate=(1,)
            )
        else:
            self._decode_paged_jit = _jit_as(
                "decode_paged", self._paged_decode_step, donate=(1,)
            )
        self._copy_page_jit = _jit_as(
            "copy_page", self._copy_page_step,
            donate=(0, 3) if draft else (0,),
        )
        if self.host_pages:
            self._gather_page_jit = _jit_as(
                "gather_page", self._gather_page_step
            )
            self._scatter_page_jit = _jit_as(
                "scatter_page", self._scatter_page_step,
                donate=(0, 3) if draft else (0,),
            )
        self.last_token = jnp.zeros((slots,), jnp.int32)
        if tp_axis is not None:
            # Pin the slot-width control state (lengths, last token)
            # mesh-replicated at construction. The steps return them
            # replicated; leaving the INITIAL arrays single-device made
            # the second admission wave's prefill see a different
            # operand sharding — one silent extra compile per TP
            # engine, caught by the CompileWatch pin.
            rep = world.sharding()
            self.cache = dataclasses.replace(
                self.cache, lengths=jax.device_put(self.cache.lengths, rep)
            )
            self.last_token = jax.device_put(self.last_token, rep)
        self._forward = fwd
        # Engine-lifetime compile accounting (ISSUE 8): the "three
        # compiles (prefill chunk, decode, copy_page), zero per-request
        # recompiles" claim as a runtime-guarded metric.
        # Every jitted-step invocation below routes through the watch;
        # growth past `expected` is an unexpected recompile (instant +
        # sentinel note — the Server attaches its sentinel; with a
        # request ledger wired, that note also pins the in-flight
        # request set, so a mid-serve recompile stall is joinable to
        # exactly the requests whose latency it poisoned — ISSUE 16).
        # Speculation keeps the discipline with ONE extra compile: the
        # decode tick splits into spec_draft + spec_verify (the plain
        # decode step is never built).
        # The host tier adds exactly two more (gather_page +
        # scatter_page — page ids traced, payload shapes fixed), still
        # zero per-request recompiles (ISSUE 20).
        self.compile_watch = _roofline.CompileWatch(
            expected=3
            + (1 if self.spec_k else 0)
            + (2 if self.host_pages else 0)
            # one prefill step a count of participants (compacted ticks)
            + max(0, len(self._prefill_counts) - 1),
            scope="engine",
        )
        # Per-execution modeled costs (set by register_roofline).
        self.roofline_costs: dict | None = None
        # What the model's steps counted beside tokens, while a recorder
        # was on (_note_aux): totals, and the last fetched step's a phase.
        self.step_counts: dict = {}
        self.last_counts: dict = {}
        # WIRE bytes, not logical bytes: an int8 weight store's param
        # read per decode tick is the int8 payload + the f32 scale
        # column (ISSUE 17 — decode_achieved_hbm_bytes must count what
        # the DMA moves, the kv_dtype honesty rule applied to weights).
        self._param_bytes = params_wire_bytes(params)
        # One cached K (or V) row of one layer, at the ACTUAL wire
        # dtype — the unit of the length-aware decode-bytes model.
        # int8 rows carry their scale blocks (ISSUE 15 roofline
        # honesty: the visited-tile sweep DMAs int8 tiles + scales, so
        # that is what decode_hbm_util_pct / GB-s figures must count).
        self._kv_row_bytes = model.kv_row_bytes(
            jax.tree.leaves(self.cache.k)[0].dtype
        )
        # ISSUE 18: the byte-exact HBM ledger. Every buffer this
        # constructor pinned to the device registers ONCE — the weight
        # store (int8 payload + scale rows at wire width), the KV cache
        # buffers (target + draft, K + V + lengths), the draft weights
        # (0 bytes when aliasing target leaves), per-slot step state —
        # and the page allocator emits grant/free at every physical
        # page transition, so `memledger.held()` decomposes total HBM
        # with `grants − frees == held` exact. Buffer sizes come from
        # the arrays' own nbytes (identical to the wire model for int8:
        # q payload + f32 scales), so the ledger measures what was
        # allocated, not what arithmetic predicts.
        self.memledger = MemLedger(platform=platform)
        register_param_store(self.memledger, self.params)
        nbytes = lambda tree: sum(l.nbytes for l in jax.tree.leaves(tree))
        kv_buf = nbytes((self.cache.k, self.cache.v, self.cache.x))
        # The state pool (what the layout's recurrent layers keep a
        # slot; nothing for a model whose layers all keep pages).
        state_buf = nbytes(self.cache.state)
        lengths_bytes = self.cache.lengths.nbytes
        draft_kv = 0
        if self.draft_cache is not None:
            draft_kv = nbytes((self.draft_cache.k, self.draft_cache.v))
            lengths_bytes += self.draft_cache.lengths.nbytes
        self.memledger.register(
            "kv_pool",
            capacity_bytes=kv_buf + state_buf + draft_kv + lengths_bytes,
        )
        self.memledger.grant(
            "kv_pool", kv_buf + state_buf + lengths_bytes,
            kind="cache_buffers",
        )
        if self.spec_k:
            register_draft_store(
                self.memledger, self.draft_params,
                target_params=self.params, kv_bytes=draft_kv,
            )
        self.memledger.grant(
            "step_buffers", self.last_token.nbytes, kind="last_token"
        )
        # What one granted page occupies across ALL layers, K and
        # V, target AND draft pool (shared block tables mean a page
        # grant maps rows in both buffers) — the allocator's unit
        # for the nested kv_pages / kv_cow_reserve decomposition.
        # From the layouts, the draft's beside the target's: what the
        # buffers above hold, a page of them.
        kv_item = jax.tree.leaves(self.cache.k)[0].dtype
        self.page_bytes = layout.page_bytes(
            self.page_size, kv_item, self.kv_quantized
        ) + (
            self._draft_model.cache_layout().page_bytes(
                self.page_size, kv_item, self.kv_quantized
            ) if self.spec_k else 0
        )
        # The same of the window layers' pool (0 where there is none).
        self.window_page_bytes = layout.page_bytes(
            self.page_size, kv_item, self.kv_quantized, window=True
        )
        window_buf = self.window_page_bytes * self.window_pages
        assert (self.page_bytes * self.num_pages + window_buf
                == kv_buf + draft_kv)
        self.memledger.register(
            "kv_pages",
            capacity_bytes=self.num_pages * self.page_bytes,
            nested_in="kv_pool",
        )
        if window_buf:
            self.memledger.register(
                "kv_window_pages", capacity_bytes=window_buf,
                nested_in="kv_pool",
            )
        self.memledger.register("kv_cow_reserve", nested_in="kv_pool")
        # A slot's seat in the state pool, held from admission to
        # release as its pages are.
        self.slot_state_bytes = layout.state_slot_bytes()
        if self.slot_state_bytes:
            assert self.slot_state_bytes * slots == state_buf
            self.memledger.register(
                "kv_state", capacity_bytes=state_buf, nested_in="kv_pool"
            )
        self.allocator.memledger = self.memledger
        self.allocator.page_bytes = self.page_bytes
        self.allocator.window_page_bytes = self.window_page_bytes
        self.allocator.slot_state_bytes = self.slot_state_bytes
        if self.host_pages:
            # ISSUE 20: the host-RAM page store. Charged at spill
            # dispatch, refunded at restream / promotion / cold
            # eviction / reset — the engine's spill/restore seam is
            # the ONLY writer (the tier-seam lint pins this).
            # nested_in="host_ram" keeps host bytes out of held()'s
            # HBM total while per-tier conservation still holds.
            self.memledger.register(
                "kv_host_pages",
                capacity_bytes=self.host_pages * self.page_bytes,
                nested_in="host_ram",
            )
            # host page id -> numpy pytree of one page's rows (K +
            # V, every layer, int8 payload + scale blocks together,
            # draft pool included on a speculative engine).
            self._host_store: dict[int, Any] = {}
            # Dispatched-but-undrained spills: (host_page, device
            # pytree). The gather runs async under the decode tick
            # it overlapped with (the Prefetcher's two-stage
            # discipline); drain_spills() materializes at the next
            # tick boundary or on demand before a restore.
            self._pending_spills: list = []
            self.host_spilled_pages = 0
            self.host_restreamed_pages = 0
            self.host_spill_bytes = 0
            self.host_restream_bytes = 0

    # -- jitted step bodies -------------------------------------------------
    def _sample_last(self, params, out, gather_idx, key, temp, topk):
        """Token per slot from the forward's output at ``gather_idx``
        — blocked path: gather the HIDDEN row and stream the head
        (:func:`lm_head_sample`, no [slots, vocab] array); the reference
        engine: gather the logits row and sample it whole."""
        with jax.named_scope("sample"):
            row = jnp.take_along_axis(
                out, gather_idx[:, None, None], axis=1
            )[:, 0]
            if not self._blocked_head:
                return sample_tokens(
                    row.astype(jnp.float32), key, temp, topk
                )
            head = self.model.head_table(params)
            return lm_head_sample(
                row, head, key, temp, topk,
                block_size=self._sample_block,
                k_cap=self.sample_k_cap,
                compute_dtype=self.cfg.head_dtype,
            )

    # -- draft forwards (ISSUE 13) ------------------------------------------
    def _draft_forward_paged(
        self, dparams, tokens, dcache: PagedKVCache, block_tables,
        write_valid, *, with_head,
    ):
        """The draft model's cache-aware forward — reference attention,
        materialized logits (the draft is small by construction; its
        whole cost is the speculation overhead the acceptance rate must
        beat). ``with_head=False`` (prefill) stops at ln_f: the draft
        never samples at prefill. The draft pool mirrors the target's
        page geometry and indirects through the SAME block tables, so
        prefix sharing, COW remaps and preemption free/remap draft K/V
        together with the target's."""
        out, (k2, v2, _state), _ = self._draft_model.forward_paged(
            dparams, tokens, dcache, block_tables, write_valid,
            return_hidden=not with_head,
        )
        return out, PagedKVCache(k=k2, v=v2, lengths=dcache.lengths)

    def _paged_prefill_step(
        self, params, cache, last, tokens, base, chunk_lens, floor,
        sample_mask, block_tables, key, temp, topk,
        dparams=None, dcache=None,
    ):
        """One prefill CHUNK over the whole slot batch: slot ``s`` feeds
        ``tokens[s, :chunk_lens[s]]`` = its prompt slice starting at
        position ``base[s]`` (tokens already cached per slot — 0 cold,
        the shared-prefix floor on a prefix hit, the running total on
        later chunks of a chunked admit). K/V appends scatter through
        the block tables; rows below ``floor`` (shared pages are
        immutable — the values would be bit-identical anyway), padding
        rows past the chunk, and non-participating slots' rows are all
        DROPPED, never written. ``sample_mask`` marks slots whose final
        prompt token rides this chunk: their first output token is
        sampled from the logits at that position and sticks."""
        t_idx = jnp.arange(tokens.shape[1])[None, :]
        pos = base[:, None] + t_idx
        write_valid = (t_idx < chunk_lens[:, None]) & (pos >= floor[:, None])
        # Non-participants (live/free slots riding the fixed batch
        # shape) attend at length 0 — their compute is discarded and the
        # length-aware kernel pays 1 tile, not their real context.
        participates = chunk_lens > 0
        work = dataclasses.replace(
            cache, lengths=jnp.where(participates, base, 0)
        )
        out, new, *aux = self._forward(
            params, tokens, work, block_tables, write_valid,
            *self._rows_arg(lambda: t_idx < chunk_lens[:, None]),
        )
        tok = self._sample_last(
            params, out, jnp.maximum(chunk_lens - 1, 0), key, temp, topk
        )
        new_cache = dataclasses.replace(
            new,
            lengths=jnp.where(
                participates, base + chunk_lens, cache.lengths
            ),
        )
        new_last = jnp.where(sample_mask, tok, last)
        if not self.spec_k:
            return (new_cache, new_last, *aux)
        # Draft prefill rides the same chunk: same slices, same write
        # mask (floor included — shared pages already hold draft K/V
        # from the slot that registered the prefix), the draft pool's
        # scatter through the same block tables.
        dwork = PagedKVCache(
            k=dcache.k, v=dcache.v, lengths=work.lengths
        )
        _, dnew = self._draft_forward_paged(
            dparams, tokens, dwork, block_tables, write_valid,
            with_head=False,
        )
        return new_cache, new_last, PagedKVCache(
            k=dnew.k, v=dnew.v, lengths=new_cache.lengths
        )

    def _paged_decode_step(
        self, params, cache, last, active, block_tables, key, temp, topk
    ):
        """One decode tick through the page pool: append each active
        slot's last token at its fill position (scatter through its
        block table; inactive rows dropped), attend, sample the next."""
        lens = jnp.where(active, cache.lengths, 0)
        work = dataclasses.replace(cache, lengths=lens)
        out, new, *aux = self._forward(
            params, last[:, None], work, block_tables, active[:, None],
            *self._rows_arg(lambda: active[:, None]),
        )
        tok = self._sample_last(
            params, out,
            jnp.zeros((out.shape[0],), jnp.int32), key, temp, topk,
        )
        return (
            dataclasses.replace(
                new, lengths=jnp.where(active, lens + 1, lens)
            ),
            jnp.where(active, tok, last),
            *aux,
        )

    def _rows_arg(self, rows) -> tuple:
        """The forward's ``row_valid`` argument, for a model that skips
        the rows that are no tokens (padding of a chunk, idle slots);
        nothing, and nothing traced, for one that computes them all."""
        return (rows(),) if self.model.skips_invalid_rows else ()

    def _chunk_rows_step(self, packed):
        """A compacted chunk step's small arguments out of the one
        vector the host moved them in (:meth:`_stage_chunk_rows`): the
        group's ``slot_idx``, ``tokens``, ``base``, ``chunk_lens``,
        ``floor`` and ``sample_mask``, and the block tables. The count of
        participants is the vector's length."""
        w = self.prefill_chunk
        tables = self.slots * self._table_cols
        rows = packed[: packed.shape[0] - tables].reshape(-1, w + 5)
        return (
            rows[:, w], rows[:, :w], rows[:, w + 1], rows[:, w + 2],
            rows[:, w + 3], rows[:, w + 4] != 0,
            packed[packed.shape[0] - tables :].reshape(
                self.slots, self._table_cols
            ),
        )

    def _paged_prefill_compact_step(
        self, params, cache, last, slot_idx, tokens, base, chunk_lens,
        floor, sample_mask, block_tables, key, temp, topk,
    ):
        """:meth:`_paged_prefill_step` over the slots that take part
        only: row ``i`` of ``tokens`` / ``base`` / ``chunk_lens`` /
        ``floor`` / ``sample_mask`` belongs to slot ``slot_idx[i]``, whose
        block table, sampling settings, length and last token are found
        through it. ``slot_idx`` past the slots marks padding up to the
        compiled count (its ``chunk_lens`` is 0): nothing of it is
        written anywhere. The device work follows ``len(slot_idx) x
        chunk`` rows, not ``slots x chunk``.

        A row is a SEAT: a slot may hold several, its prompt's next
        chunks in order (:meth:`spare_seats`). A layer writes every
        seat's rows into the pool before its attention reads it, so a
        later seat's queries (positions ``base + t`` of their own row)
        find the earlier seat's rows there, as they would a tick later;
        the slot's new length is its last seat's end."""
        s = cache.lengths.shape[0]
        at = jnp.minimum(slot_idx, s - 1)
        t_idx = jnp.arange(tokens.shape[1])[None, :]
        pos = base[:, None] + t_idx
        rows = t_idx < chunk_lens[:, None]
        write_valid = rows & (pos >= floor[:, None])
        participates = chunk_lens > 0
        work = dataclasses.replace(
            cache, lengths=jnp.where(participates, base, 0)
        )
        out, new, *aux = self._forward(
            params, tokens, work, block_tables[at], write_valid,
            *self._rows_arg(lambda: rows), slot_index=slot_idx,
        )
        tok = self._sample_last(
            params, out, jnp.maximum(chunk_lens - 1, 0), key, temp[at],
            topk[at],
        )
        # A scatter past the slots is dropped: padding and the slots
        # that sample nothing leave no mark. Every seat of a slot writes
        # the furthest end among them, so it is nothing which lands last.
        idx = jnp.where(participates, slot_idx, s)
        ends = jnp.max(
            jnp.where(
                idx[:, None] == idx[None, :], (base + chunk_lens)[None, :], 0
            ),
            axis=1,
        )
        new_cache = dataclasses.replace(
            new, lengths=cache.lengths.at[idx].set(ends, mode="drop")
        )
        new_last = last.at[jnp.where(sample_mask, slot_idx, s)].set(
            tok, mode="drop"
        )
        return (new_cache, new_last, *aux)

    # -- speculative tick bodies (ISSUE 13) ---------------------------------
    def _spec_draft_step(
        self, dparams, dcache, last, active, key, temp, topk,
        block_tables, write_cap,
    ):
        """Phase 1 of the speculative tick: k unrolled T=1 draft-model
        steps from each active slot's last token through the draft's
        own cache (k is static — one compile for the engine's
        lifetime). Draft proposals are exact samples from q — the
        request's temperature/top-k applied to the draft logits
        (:func:`~mpit_tpu.serve.spec.draft_distribution`); greedy rows
        take the draft argmax. Returns the updated draft cache (K/V
        written at rows ``lengths..lengths+k-1``; LENGTHS UNCHANGED —
        they advance with the target's at verify, which is also the
        draft-side rollback) plus the proposals and their
        q-probabilities for :meth:`_spec_verify_step`."""
        k = self.spec_k
        lens0 = jnp.where(active, dcache.lengths, 0)
        cur = last
        dk, dv = dcache.k, dcache.v
        drafted, qx, qprobs = [], [], []
        for j in range(k):
            lens_j = lens0 + j
            # Rows past the slot's mapped pages are DROPPED (the
            # block table has no entry to scatter them through) and
            # inactive slots' stale tables are never followed.
            wv = active[:, None] & (
                lens_j[:, None] < write_cap[:, None]
            )
            work = PagedKVCache(k=dk, v=dv, lengths=lens_j)
            out, new = self._draft_forward_paged(
                dparams, cur[:, None], work, block_tables, wv,
                with_head=True,
            )
            dk, dv = new.k, new.v
            logits = out[:, 0].astype(jnp.float32)
            probs, scaled = draft_distribution(logits, temp, topk)
            samp = jax.random.categorical(
                jax.random.fold_in(key, j), scaled, axis=-1
            ).astype(jnp.int32)
            tok = jnp.where(
                temp <= 0.0,
                jnp.argmax(logits, axis=-1).astype(jnp.int32),
                samp,
            )
            drafted.append(tok)
            qx.append(
                jnp.take_along_axis(probs, tok[:, None], axis=1)[:, 0]
            )
            qprobs.append(probs)
            cur = tok
        # One head-less append of the LAST drafted token's K/V at row
        # lengths+k: a fully-accepted tick advances lengths to
        # lengths+k+1, and without this row the draft's context keeps a
        # permanent garbage position INSIDE its attended window — output
        # exactness survives (verify corrects everything) but acceptance
        # collapses in exactly the high-acceptance regime speculation
        # exists for (a bit-identical draft measured 0.52, not 1.0).
        # On a rejected tick the row sits past the watermark, masked,
        # like every other rejected draft row.
        lens_k = lens0 + k
        wv = active[:, None] & (lens_k[:, None] < write_cap[:, None])
        work = PagedKVCache(k=dk, v=dv, lengths=lens_k)
        _, new = self._draft_forward_paged(
            dparams, cur[:, None], work, block_tables, wv,
            with_head=False,
        )
        return (
            PagedKVCache(k=new.k, v=new.v, lengths=dcache.lengths),
            jnp.stack(drafted, axis=1),  # [S, k] int32
            jnp.stack(qx, axis=1),       # [S, k] f32
            jnp.stack(qprobs, axis=1),   # [S, k, V] f32
        )

    def _spec_verify_step(
        self, params, cache, last, active, drafted, qx, qprobs, key,
        temp, topk, budget, eos, block_tables, write_cap,
    ):
        """Phase 2: ONE T=k+1 target pass over ``[last, d_1..d_k]``
        (the flash-decode kernel's small-T trace — k+1 query rows, the
        same length-aware tile loop), verify sampling over all k+1
        positions (blocked :func:`~mpit_tpu.ops.lm_head.lm_head_verify`
        or the full-logits reference — whatever the engine's sampler
        is), then longest-accepted-prefix emission. Cache lengths
        advance by the accepted count ONLY: rejected drafts' K/V rows
        sit past the new watermark, masked, overwritten by the next
        append — the rollback."""
        k = self.spec_k
        lens = jnp.where(active, cache.lengths, 0)
        feed = jnp.concatenate([last[:, None], drafted], axis=1)
        pos = lens[:, None] + jnp.arange(k + 1, dtype=jnp.int32)[None]
        wv = active[:, None] & (pos < write_cap[:, None])
        work = PagedKVCache(k=cache.k, v=cache.v, lengths=lens)
        out, new = self._forward(params, feed, work, block_tables, wv)
        s = out.shape[0]
        nrows = s * (k + 1)
        vkey, ukey = jax.random.split(key)
        # Bonus position: q = 0 makes its residual a plain target
        # sample (max(p - 0, 0) = p) — one formula for reject + bonus.
        qpad = jnp.concatenate(
            [qprobs, jnp.zeros_like(qprobs[:, :1])], axis=1
        )
        drafted_pad = jnp.pad(drafted, ((0, 0), (0, 1)))
        temp_rows = jnp.repeat(temp, k + 1)
        topk_rows = jnp.repeat(topk, k + 1)
        if self._blocked_head:
            head = params["head"] if "head" in params else params["wte"]
            g, p_x, repl = lm_head_verify(
                out.reshape(nrows, out.shape[-1]),
                head,
                drafted_pad.reshape(nrows),
                qpad.reshape(nrows, -1),
                vkey, temp_rows, topk_rows,
                block_size=self._sample_block,
                k_cap=self.sample_k_cap,
                compute_dtype=self.cfg.head_dtype,
            )
        else:
            # Reference engine: materialized logits + the full-logits
            # verifier — the parity oracle. k_cap = vocab keeps the
            # reference's top-k semantics unbounded, like its sampler.
            g, p_x, repl = verify_reference(
                out.reshape(nrows, out.shape[-1]).astype(jnp.float32),
                drafted_pad.reshape(nrows),
                qpad.reshape(nrows, -1),
                vkey, temp_rows, topk_rows,
                k_cap=self.cfg.vocab_size,
                block_size=self._sample_block,
            )
        g = g.reshape(s, k + 1)
        p_x = p_x.reshape(s, k + 1)
        repl = repl.reshape(s, k + 1)
        u = jax.random.uniform(ukey, (s, k), jnp.float32)
        emit, n_emit, n_acc = accept_emit(
            drafted, g, p_x[:, :k], qx, u, repl,
            temp <= 0.0, budget, eos,
        )
        n_emit = jnp.where(active, n_emit, 0)
        n_acc = jnp.where(active, n_acc, 0)
        new_last = jnp.where(
            active,
            jnp.take_along_axis(
                emit, jnp.maximum(n_emit - 1, 0)[:, None], axis=1
            )[:, 0],
            last,
        )
        out_cache = PagedKVCache(k=new.k, v=new.v, lengths=lens + n_emit)
        # The fill a second time, for the draft cache: two outputs are
        # two buffers, and a donating step must not find one buffer in
        # both caches.
        return out_cache, new_last, emit, n_emit, n_acc, out_cache.lengths

    def _copy_page_step(self, cache, src, dst, dcache=None):
        """Copy pool page ``src`` → ``dst`` in every layer's buffer, K
        and V — the device half of a copy-on-write remap (the allocator
        already repointed the block table at ``dst``). One page read and
        one written in place a buffer (the cache is donated). A
        speculative engine's draft pool shares the block tables, so the
        same remap copies its page too."""

        def cp1(pl):
            # Per leaf: a quantized layer copies its int8 page AND the
            # page's scale block in the same remap (ISSUE 15 — COW
            # carries the scales with the pages).
            page = jax.lax.dynamic_index_in_dim(
                pl, src, axis=0, keepdims=True
            )
            return jax.lax.dynamic_update_slice_in_dim(
                pl, page, dst, axis=0
            )

        cp = lambda c: dataclasses.replace(
            c, k=jax.tree.map(cp1, c.k), v=jax.tree.map(cp1, c.v),
            x=jax.tree.map(cp1, c.x),
        )
        if not self.spec_k:
            return cp(cache)
        return cp(cache), cp(dcache)

    def _gather_page_step(self, cache, page, dcache=None):
        """Pull pool page ``page`` (all layers, K and V; the draft pool
        too on a speculative engine) into fresh [L, ps, ·] buffers,
        the layers' pages stacked so that a spill is one array a pool to
        fetch — the device half of a spill. The page id rides as a
        traced scalar (one compile serves every spill) and a quantized
        pool gathers its int8 page AND the page's scale block in the
        same pass (ISSUE 20: payload + scales travel as one unit)."""

        def gp(pool):
            return jax.tree.map(
                lambda *layers: jnp.concatenate([
                    jax.lax.dynamic_index_in_dim(
                        pl, page, axis=0, keepdims=True
                    )
                    for pl in layers
                ]),
                *pool,
            )

        out = (gp(cache.k), gp(cache.v))
        if not self.spec_k:
            return out
        return out + (gp(dcache.k), gp(dcache.v))

    def _scatter_page_step(self, cache, dst, payload, dcache=None):
        """Write a previously gathered page payload into pool page
        ``dst`` of every layer's buffer, in place (the cache is
        donated) — the device half of a restream. ``payload`` is the
        tuple :meth:`_gather_page_step` produced (round-tripped through
        host numpy), so shapes/dtypes are fixed and only the page id is
        traced: one compile serves every restore, and int8 payloads
        land with their scale blocks in the same pass."""

        def sp(pool, pay):
            return tuple(
                jax.tree.map(
                    lambda pl, pg: jax.lax.dynamic_update_slice_in_dim(
                        pl, pg[i : i + 1], dst, axis=0
                    ),
                    layer, pay,
                )
                for i, layer in enumerate(pool)
            )

        out = dataclasses.replace(
            cache, k=sp(cache.k, payload[0]), v=sp(cache.v, payload[1])
        )
        if not self.spec_k:
            return out
        return out, PagedKVCache(
            k=sp(dcache.k, payload[2]), v=sp(dcache.v, payload[3]),
            lengths=dcache.lengths,
        )

    # -- host surface (the scheduler's API) ---------------------------------
    def _split(self):
        """The next subkey of the engine's one stream."""
        if self._sub is None:
            self._split_ahead()
        sub, self._sub = self._sub, None
        return sub

    def _split_ahead(self) -> None:
        """Split the next subkey off now. A decode tick does so once its
        step is enqueued: the split's dispatch then passes while the
        device runs the step, not while the device waits for it."""
        if self._sub is None:
            self._key, self._sub = _split_pair(self._key)

    def _stage(self, name: str, value, dtype):
        """``value`` on the device as ``dtype``: the copy staged for the
        last step while the content is the same. ``active``, ``temp``,
        ``topk`` and the block tables change when a slot is admitted,
        retires or takes a page, not from tick to tick, and a transfer
        costs the host 0.27 ms each. The transfer is made from a copy
        the engine keeps and never writes to: the scheduler changes its
        arrays in place while the step that took them is still in flight,
        and ``jnp.asarray`` of host memory may alias it (on the CPU it
        does, wherever the buffer is aligned)."""
        host = np.asarray(value, dtype)
        held = self._staged.get(name)
        if (
            held is None
            or held[0].shape != host.shape
            or not np.array_equal(held[0], host)
        ):
            mine = host.copy()
            held = self._staged[name] = (mine, jnp.asarray(mine))
        return held[1]

    def prefill_paged(
        self, tokens, base, chunk_lens, floor, sample_mask, temp, topk,
        seats=None,
    ) -> np.ndarray:
        """One prefill chunk tick: row ``i`` of ``tokens``
        [rows, prefill_chunk] int32 (padded slices), ``base`` /
        ``chunk_lens`` / ``floor`` [rows] int32 and ``sample_mask`` [rows]
        bool per :meth:`_paged_prefill_step` is a SEAT of slot
        ``seats[i]``: a chunk of that slot's prompt. ``seats`` None: a
        row a slot, row ``i`` slot ``i``'s. A slot may hold as many more
        seats as :meth:`spare_seats` allows, its prompt's next chunks in
        order. ``temp`` / ``topk`` are [slots] always.
        Block tables come from the engine's allocator. Returns the
        per-slot last token (the first OUTPUT token for slots whose
        ``sample_mask`` is set) as host numpy: :meth:`prefill_dispatch`
        and then :meth:`prefill_fetch`, for a caller that wants the
        tokens before it does anything else."""
        return self.prefill_fetch(self.prefill_dispatch(
            tokens, base, chunk_lens, floor, sample_mask, temp, topk, seats
        ))

    def spare_seats(self, takers: int) -> int:
        """Seats that a chunk tick of ``takers`` slots, one seat each,
        computes for nobody: its last step's compiled count less the
        slots in it. They cost the device nothing more (the step is
        compiled at the knee of its time over its rows, ``_chunk_step_
        counts``), so the scheduler gives each to the next chunk of a
        prompt that is there. 0 where the smallest compiled step is one
        seat or the whole batch, and where a slot keeps a state beside
        its pages."""
        if not self._chains:
            return 0
        return sum(self._step_sizes(takers)) - takers

    def _step_sizes(self, seats: int) -> list:
        """The compiled counts of the steps a chunk tick of ``seats``
        seats takes: ``_prefill_counts[-1]`` at a time, what is left
        padded to the next count."""
        most = self._prefill_counts[-1]
        full, rest = divmod(seats, most)
        sizes = [most] * full
        if rest:
            sizes.append(next(c for c in self._prefill_counts if c >= rest))
        return sizes

    def prefill_dispatch(
        self, tokens, base, chunk_lens, floor, sample_mask, temp, topk,
        seats=None,
    ):
        """Stage and enqueue one prefill chunk (arguments as
        :meth:`prefill_paged`) and return what the step left on the
        device, for :meth:`prefill_fetch`: nothing here waits for the
        step, so the caller may enqueue more behind it first."""
        chunk_lens = np.asarray(chunk_lens)
        if self._prefill_counts:
            return self._prefill_compact_dispatch(
                tokens, base, chunk_lens, floor, sample_mask, temp, topk,
                seats,
            )
        if seats is not None:  # the full-batch step: a row a slot
            tokens, base, chunk_lens, floor, sample_mask = (
                self._by_slot(seats, a)
                for a in (tokens, base, chunk_lens, floor, sample_mask)
            )
        aux = ()
        computed = chunk_lens.size * self.prefill_chunk
        valid = int(chunk_lens.sum())
        with obs.span(  # staging and enqueue
            "prefill_dispatch", **self._rows_attrs(computed, valid, 0)
        ):
            args = [
                self.params,
                self.cache,
                self.last_token,
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(base, jnp.int32),
                jnp.asarray(chunk_lens, jnp.int32),
                jnp.asarray(floor, jnp.int32),
                jnp.asarray(sample_mask, bool),
                self._stage(
                    "block_tables", self.allocator.block_tables, np.int32
                ),
                self._split(),
                self._stage("temp", temp, np.float32),
                self._stage("topk", topk, np.int32),
            ]
            if self.spec_k:
                args += [self.draft_params, self.draft_cache]
                self.cache, self.last_token, self.draft_cache = (
                    self.compile_watch.call(
                        "prefill", self._prefill_paged_jit, *args
                    )
                )
            else:
                self.cache, self.last_token, *aux = self.compile_watch.call(
                    "prefill", self._prefill_paged_jit, *args
                )
        return self.last_token, [aux], (computed, valid, 0)

    def _by_slot(self, seats, rows) -> np.ndarray:
        """``rows`` a seat as rows a slot (no slot holds two: the
        full-batch step gives no seat away), zeros for the others."""
        rows = np.asarray(rows)
        out = np.zeros((self.slots, *rows.shape[1:]), rows.dtype)
        out[np.asarray(seats)] = rows
        return out

    def _prefill_compact_dispatch(
        self, tokens, base, chunk_lens, floor, sample_mask, temp, topk,
        seats,
    ):
        """A chunk tick over its participants only (see
        :meth:`_paged_prefill_compact_step`): the seats with a chunk are
        taken ``_prefill_counts[-1]`` at a time, in their order (a slot's
        later seat never before its earlier one), each group padded to
        the next compiled count. Every group's step is enqueued; the last
        one's tokens hold them all."""
        takers = np.flatnonzero(chunk_lens > 0)
        slot_of = (
            np.arange(len(chunk_lens)) if seats is None else np.asarray(seats)
        )
        most = self._prefill_counts[-1]
        groups = [takers[g : g + most] for g in range(0, len(takers), most)]
        sizes = self._step_sizes(len(takers))
        computed, aux_all = sum(sizes) * self.prefill_chunk, []
        valid = int(chunk_lens.sum())
        # Prompt tokens in a seat that is not its slot's first.
        _, first = np.unique(slot_of[takers], return_index=True)
        rows = computed, valid, valid - int(chunk_lens[takers[first]].sum())
        with obs.span(  # staging and enqueue
            "prefill_dispatch", **self._rows_attrs(*rows)
        ):
            temp = self._stage("temp", temp, np.float32)
            topk = self._stage("topk", topk, np.int32)
            for group, n in zip(groups, sizes):
                staged = self._stage_chunk_rows(
                    n, slot_of[group], group, tokens, base, chunk_lens,
                    floor, sample_mask,
                )
                self.cache, self.last_token, *aux = self.compile_watch.call(
                    "prefill", self._prefill_compact_jit,
                    self.params, self.cache, self.last_token, *staged,
                    self._split(), temp, topk, count=n,
                )
                aux_all.append(aux)
        return self.last_token, aux_all, rows

    def _stage_chunk_rows(self, n, slots, group, tokens, base, chunk_lens,
                          floor, sample_mask) -> tuple:
        """A compacted step's arguments from ``slot_idx`` to
        ``block_tables``, on the device, for the seats ``group`` (rows of
        the caller's arrays) of the slots ``slots``, padded to ``n`` rows
        (a padding row names the slot past the last and holds zeros).
        The seven ride in ONE int32 vector, moved as the one
        argument of the jitted :meth:`_chunk_rows_step`, which takes it
        apart again: on the chip's host a transfer costs 0.2-0.3 ms and
        a small jitted call 0.3-0.7 whatever they carry, so this is 0.7 ms
        where six transfers and the tables' were 1.5-1.8 (PR 35, call 2),
        beside 1.6 ms for the enqueue of the step itself. The vector is
        made anew for every step, so nothing the scheduler writes to
        again reaches one (:meth:`_stage`'s rule), and its tables are
        what :meth:`_stage` keeps for the decode step behind."""
        w, g = self.prefill_chunk, len(group)
        tables = self.allocator.block_tables
        packed = np.zeros((n * (w + 5) + tables.size,), np.int32)
        rows = packed[: n * (w + 5)].reshape(n, w + 5)
        rows[:, w] = self.slots
        rows[:g, w] = slots
        rows[:g, :w] = np.asarray(tokens)[group]
        for col, a in enumerate((base, chunk_lens, floor, sample_mask), 1):
            rows[:g, w + col] = np.asarray(a)[group]
        held = packed[n * (w + 5) :].reshape(tables.shape)
        held[:] = tables
        # Its compile is a span of the record and no count of the pin.
        *staged, on_device = self.compile_watch.call(
            "prefill", self._chunk_rows_jit, packed, pinned=False, count=n
        )
        self._staged["block_tables"] = (held, on_device)
        return (*staged, on_device)

    def prefill_fetch(self, step) -> np.ndarray:
        """The tokens of a chunk :meth:`prefill_dispatch` enqueued, as
        host numpy: the wait for the step and the copy back."""
        last, aux_all, rows = step
        with obs.span("prefill_fetch"):  # the wait and the copy back
            # The step's one deliberate completion fence (docstring
            # contract: the fetch closes the caller's span).
            # analysis: allow(host-sync-in-hot-seam)
            toks = np.asarray(last)
        if obs.enabled():
            self._note_prefill_rows(*rows)
            for aux in aux_all:
                self._note_aux("prefill", aux)
        return toks

    def warm_prefill_counts(self) -> None:
        """Compile the compacted prefill step for every count of
        participants a tick can meet (``warm_engine`` calls this), with
        padding alone: nothing is written."""
        none = np.zeros((0,), np.int32)
        empty = np.zeros((self.slots, self.prefill_chunk), np.int32)
        zeros = np.zeros((self.slots,), np.int32)
        for n in self._prefill_counts:
            self.cache, self.last_token, *_ = self.compile_watch.call(
                "prefill", self._prefill_compact_jit,
                self.params, self.cache, self.last_token,
                *self._stage_chunk_rows(
                    n, none, none, empty, zeros, zeros, zeros, zeros
                ),
                self._split(), jnp.zeros((self.slots,), jnp.float32),
                jnp.zeros((self.slots,), jnp.int32), count=n,
            )

    @staticmethod
    def _rows_attrs(computed: int, valid: int, chained: int) -> dict:
        """What a chunk tick's ``prefill_dispatch`` span says of its
        rows: those its steps compute, those of them that are no prompt
        tokens (padding of a chunk, of a count, idle slots), and the
        prompt tokens that rode a seat no slot had taken
        (:meth:`spare_seats`). Nothing while no recorder is on."""
        if not obs.enabled():
            return {}
        return dict(
            rows_computed=computed, rows_wasted=computed - valid,
            rows_chained=chained,
        )

    @staticmethod
    def _note_prefill_rows(computed: int, valid: int, chained: int) -> None:
        """Rows a chunk tick computed, those of them that were prompt
        tokens and those of these in a slot's second seat (gauges; the
        benchmark reads the waste from them)."""
        obs.gauge("prefill_rows_computed", float(computed))
        obs.gauge("prefill_rows_valid", float(valid))
        obs.gauge("prefill_rows_chained", float(chained))

    def _note_aux(self, phase: str, aux) -> None:
        """What the model counted in a step, as counters and gauges:
        per-layer, per-expert token counts ``[layers, experts]`` give
        ``moe_expert_tokens`` (by layer), ``moe_experts_hit`` (experts with
        a token, mean over layers) and ``moe_load_max_over_mean``. A
        family that counts more hands a dict: the token counts under
        ``expert_tokens`` and every other entry a counter of its own
        name, summed (``step_counts`` keeps their totals and
        ``last_counts`` what the last fetched step of a phase said, for
        the scheduler's span). Called after the step's tokens are on the
        host, so it waits for nothing."""
        if not aux:
            return
        counts, more = aux[0], {}
        if isinstance(counts, dict):
            got = jax.device_get(counts)  # one fetch for all of them
            counts = got.pop("expert_tokens", None)
            more = {k: float(np.sum(v)) for k, v in got.items()}
        for name, value in more.items():
            obs.counter(name, value, phase=phase)
            self.step_counts[name] = self.step_counts.get(name, 0.0) + value
        self.last_counts[phase] = more
        if counts is None:
            return
        counts = np.asarray(counts)
        for layer, row in enumerate(counts):
            obs.counter("moe_expert_tokens", float(row.sum()), layer=layer)
        obs.gauge("moe_experts_hit",
                  float((counts > 0).sum(axis=1).mean()), phase=phase)
        mean = np.maximum(counts.mean(axis=1), 1e-9)
        obs.gauge("moe_load_max_over_mean",
                  float((counts.max(axis=1) / mean).mean()), phase=phase)

    def copy_page(self, src: int, dst: int) -> None:
        """Device half of a COW remap: copy pool page ``src`` → ``dst``
        (all layers, K and V; the draft pool too on a speculative
        engine — same block tables, same remap). Page ids ride as
        traced scalars — one compile serves every copy."""
        with obs.span("copy_page"):
            args = [
                self.cache,
                jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32),
            ]
            if self.spec_k:
                self.cache, self.draft_cache = self.compile_watch.call(
                    "copy_page", self._copy_page_jit, *args,
                    self.draft_cache,
                )
            else:
                self.cache = self.compile_watch.call(
                    "copy_page", self._copy_page_jit, *args
                )

    # -- host KV tier (ISSUE 20) --------------------------------------------
    def spill_page(self, device_page: int, host_page: int, *,
                   owner=None, tick: int = 0) -> None:
        """DISPATCH the spill of pool page ``device_page`` into host
        seat ``host_page``. The jitted gather runs asynchronously —
        JAX's functional update pins the gathered buffers, so the
        device page may be recycled (even rewritten by the very next
        prefill) before the copy completes without corrupting the
        payload. Materialization to host numpy happens at
        :meth:`drain_spills` (the next tick boundary — the Prefetcher's
        overlap discipline) or on demand before a restore. The host
        tier's ledger bytes are charged HERE: dispatch is the
        commitment."""
        with obs.span("spill_page"):
            args = [self.cache, jnp.asarray(device_page, jnp.int32)]
            if self.spec_k:
                args.append(self.draft_cache)
            payload = self.compile_watch.call(
                "gather_page", self._gather_page_jit, *args
            )
        self._pending_spills.append((int(host_page), payload))
        self.memledger.grant(
            "kv_host_pages", self.page_bytes,
            owner=owner, tick=tick, kind="spill",
        )
        self.host_spilled_pages += 1
        self.host_spill_bytes += self.page_bytes

    def drain_spills(self) -> int:
        """Materialize every dispatched spill into the host store.
        Called at tick boundaries so the device→host copies overlap
        the decode tick they were dispatched under; a restore of a
        still-pending page drains early instead of reading stale data.
        Returns the number of pages landed."""
        if not self._pending_spills:
            return 0
        pending, self._pending_spills = self._pending_spills, []
        with obs.span("drain_spills"):
            for host_page, payload in pending:
                self._host_store[host_page] = jax.tree.map(
                    np.asarray, payload
                )
        return len(pending)

    def restore_page(self, host_page: int, device_page: int, *,
                     release: bool = False, kind: str = "restream",
                     owner=None, tick: int = 0) -> None:
        """Restream host seat ``host_page`` into pool page
        ``device_page`` (whole-page write: all layers, K and V, scale
        blocks and draft pool included). ``release=True`` consumes the
        payload and refunds its ledger bytes (a parked victim's resume);
        ``release=False`` leaves the seat resident (a prefix entry keeps
        serving hits until promotion frees it)."""
        if any(hp == host_page for hp, _ in self._pending_spills):
            self.drain_spills()
        payload = self._host_store[host_page]
        with obs.span("restore_page"):
            args = [
                self.cache, jnp.asarray(device_page, jnp.int32), payload
            ]
            if self.spec_k:
                self.cache, self.draft_cache = self.compile_watch.call(
                    "scatter_page", self._scatter_page_jit, *args,
                    self.draft_cache,
                )
            else:
                self.cache = self.compile_watch.call(
                    "scatter_page", self._scatter_page_jit, *args
                )
        self.host_restreamed_pages += 1
        self.host_restream_bytes += self.page_bytes
        if release:
            del self._host_store[host_page]
            self.memledger.free(
                "kv_host_pages", self.page_bytes,
                owner=owner, kind=kind,
            )

    def host_free(self, host_page: int, *, kind: str,
                  owner=None, tick: int = 0) -> None:
        """Drop host seat ``host_page``'s payload without restoring it
        (promotion made it redundant, cold eviction reclaimed it, or a
        resume's prefix hit covered it) and refund its ledger bytes."""
        if self._pending_spills and any(
            hp == host_page for hp, _ in self._pending_spills
        ):
            self._pending_spills = [
                (hp, p) for hp, p in self._pending_spills if hp != host_page
            ]
        else:
            self._host_store.pop(host_page, None)
        self.memledger.free(
            "kv_host_pages", self.page_bytes, owner=owner, kind=kind,
        )

    def spec_draft(self, active, temp, topk) -> None:
        """Phase 1 of a speculative tick: draft ``spec_k`` tokens per
        active slot (``_spec_draft_step``). Proposals and their
        q-probabilities stay DEVICE-side for :meth:`spec_verify`; the
        fence (``block_until_ready``) makes the caller's span wall
        clock cover real draft completion."""
        if not self.spec_k:
            raise ValueError("spec_draft requires Engine(spec_k=...)")
        args = [
            self.draft_params,
            self.draft_cache,
            self.last_token,
            jnp.asarray(active, bool),
            self._split(),
            jnp.asarray(temp, jnp.float32),
            jnp.asarray(topk, jnp.int32),
        ]
        args += [
            jnp.asarray(self.allocator.block_tables, jnp.int32),
            jnp.asarray(self.allocator.mapped_tokens(), jnp.int32),
        ]
        self.draft_cache, drafted, qx, qprobs = self.compile_watch.call(
            "spec_draft", self._spec_draft_jit, *args
        )
        # The draft phase's deliberate fence (span wall must cover
        # real draft work).
        # analysis: allow(host-sync-in-hot-seam)
        jax.block_until_ready(drafted)
        self._spec_state = (drafted, qx, qprobs)

    def spec_verify(self, active, temp, topk, budget, eos):
        """Phase 2: one T=k+1 target pass + verify sampling + rollback
        (``_spec_verify_step``) over the pending :meth:`spec_draft`
        proposals. ``budget`` [slots] int32 = tokens each request may
        still emit; ``eos`` [slots] int32 per-request EOS id (-1 =
        none). Returns host numpy ``(emit [S, k+1], n_emit [S], n_acc
        [S])`` — slot ``s`` emitted ``emit[s, :n_emit[s]]`` this tick
        (the fetch is the step's completion fence)."""
        if self._spec_state is None:
            raise ValueError("spec_verify without a pending spec_draft")
        drafted, qx, qprobs = self._spec_state
        self._spec_state = None
        args = [
            self.params,
            self.cache,
            self.last_token,
            jnp.asarray(active, bool),
            drafted,
            qx,
            qprobs,
            self._split(),
            jnp.asarray(temp, jnp.float32),
            jnp.asarray(topk, jnp.int32),
            jnp.asarray(budget, jnp.int32),
            jnp.asarray(eos, jnp.int32),
        ]
        args += [
            jnp.asarray(self.allocator.block_tables, jnp.int32),
            jnp.asarray(self.allocator.mapped_tokens(), jnp.int32),
        ]
        self.cache, self.last_token, emit, n_emit, n_acc, fill = (
            self.compile_watch.call(
                "spec_verify", self._spec_verify_jit, *args
            )
        )
        # The draft cache's fill mirrors the target's — ONE lengths
        # assignment applies the acceptance rollback to both.
        dc = self.draft_cache
        self.draft_cache = PagedKVCache(k=dc.k, v=dc.v, lengths=fill)
        # The verify step's deliberate completion fence (docstring
        # contract).
        # analysis: allow(host-sync-in-hot-seam)
        return np.asarray(emit), np.asarray(n_emit), np.asarray(n_acc)

    def decode(self, active=None, temp=None, topk=None, *,
               step=None) -> np.ndarray:
        """One decode tick over the slot batch; returns the per-slot
        next token (host numpy; stale for inactive slots):
        :meth:`decode_dispatch`, then the wait for the step and the copy
        back. Every decode token the engine hands out comes through this
        call: a caller that enqueued the step ahead gives it back as
        ``step`` (:meth:`decode_fetch`) and gets that second half alone."""
        if step is None:
            step = self.decode_dispatch(active, temp, topk)
        last, aux = step
        with obs.span("decode_fetch"):  # the wait and the copy back
            # The step's one deliberate completion fence (docstring
            # contract: the fetch closes the caller's span).
            # analysis: allow(host-sync-in-hot-seam)
            toks = np.asarray(last)
        if aux and obs.enabled():
            self._note_aux("decode", aux)
        return toks

    def decode_dispatch(self, active, temp, topk):
        """Stage and enqueue one decode tick and return what the step
        left on the device, for :meth:`decode_fetch`. Nothing here waits
        for the step: the next one reads ``last_token`` on the device,
        so the caller may enqueue it before this one's tokens are
        fetched."""
        if self.spec_k:
            raise ValueError(
                "a speculative engine ticks through spec_draft + "
                "spec_verify (there is no plain decode step to run)"
            )
        with obs.span("decode_dispatch"):  # staging and enqueue
            args = [
                self.params,
                self.cache,
                self.last_token,
                self._stage("active", active, bool),
                self._stage(
                    "block_tables", self.allocator.block_tables, np.int32
                ),
                self._split(),
                self._stage("temp", temp, np.float32),
                self._stage("topk", topk, np.int32),
            ]
            self.cache, self.last_token, *aux = self.compile_watch.call(
                "decode", self._decode_paged_jit, *args
            )
            self._split_ahead()
        return self.last_token, aux

    def decode_fetch(self, step) -> np.ndarray:
        """The tokens of a tick :meth:`decode_dispatch` enqueued, as host
        numpy: the second half of :meth:`decode`, and through it, so that
        whatever wraps ``decode`` sees every served token."""
        return self.decode(step=step)

    # -- roofline accounting (ISSUE 8) --------------------------------------
    def register_roofline(self) -> dict:
        """Register the jitted steps' ``cost_analysis()`` per-execution
        FLOPs / HBM bytes with the installed obs recorder, under the
        span names the scheduler uses (``prefill`` / ``decode``) — the
        "register once at compile" half of the measured-vs-modeled
        utilization loop (``obs.roofline``).

        This AOT-lowers+compiles each step a second time (there is no
        public way to reach the jit cache's executable), each inside a
        ``cost_query`` span of the start-up record, which gives its
        price; callers pay it once, after warmup —
        ``warm_engine(register_costs=True)``, the serve CLI and bench
        do. The modeled decode cost is the PADDED number by construction; the scheduler corrects the HBM side
        per tick with :meth:`decode_achieved_hbm_bytes`. Returns
        ``{phase: {flops, hbm_bytes}}`` (zeros + ``error`` when a
        backend can't report costs)."""
        s = self.slots
        key = jax.random.key(0)
        f32 = jnp.zeros((s,), jnp.float32)
        i32 = jnp.zeros((s,), jnp.int32)
        msk = jnp.zeros((s,), bool)
        spec_tail = (
            [self.draft_params, self.draft_cache] if self.spec_k else []
        )
        toks = jnp.zeros((s, self.prefill_chunk), jnp.int32)
        bt = jnp.zeros((s, self._table_cols), jnp.int32)
        steps = {
            "prefill": (
                self._prefill_paged_jit,
                (self.params, self.cache, self.last_token, toks, i32,
                 i32, i32, msk, bt, key, f32, i32, *spec_tail),
            ),
        }
        if self._prefill_counts:  # the step of one participant
            steps["prefill"] = (
                self._prefill_compact_jit,
                (self.params, self.cache, self.last_token, i32[:1],
                 toks[:1], i32[:1], i32[:1], i32[:1], msk[:1], bt,
                 key, f32, i32),
            )
        if self.spec_k:
            k = self.spec_k
            steps["spec_draft"] = (
                self._spec_draft_jit,
                (self.draft_params, self.draft_cache,
                 self.last_token, msk, key, f32, i32, bt, i32),
            )
            steps["spec_verify"] = (
                self._spec_verify_jit,
                (self.params, self.cache, self.last_token, msk,
                 jnp.zeros((s, k), jnp.int32),
                 jnp.zeros((s, k), jnp.float32),
                 jnp.zeros((s, k, self.cfg.vocab_size), jnp.float32),
                 key, f32, i32, i32, i32, bt, i32),
            )
        else:
            steps["decode"] = (
                self._decode_paged_jit,
                (self.params, self.cache, self.last_token, msk, bt,
                 key, f32, i32),
            )
        out = {}
        for phase, (fn, args) in steps.items():
            try:
                with _startup.span("cost_query", phase=phase):
                    cost = _roofline.cost_from_fn(fn, *args)
            except Exception as e:  # a backend without AOT cost support
                if self.platform == "tpu":
                    raise  # on the chip this is a fault, not a backend gap
                cost = {"flops": 0.0, "hbm_bytes": 0.0,
                        "error": f"{type(e).__name__}: {e}"[:120]}
            _roofline.register_cost(
                phase,
                flops=cost["flops"],
                hbm_bytes=cost["hbm_bytes"],
                platform=self.platform,
            )
            out[phase] = cost
        self.roofline_costs = out
        return out

    def attention_tiling(self, t_q: int) -> dict:
        """Span attributes of a step of ``t_q`` query rows a slot: which
        form of the decode kernel it compiled to (``attention_form``:
        ``heads_as_rows`` or ``per_head``) and the cache rows one step of
        its loop takes (``attention_rows``). Static per compiled step.
        Empty where no kernel runs (the reference attention, the lax twin
        off the TPU) or the family's kernel does not say."""
        if self.decode_attention_mode != "kernel":
            return {}
        return self.model.attention_tiling(
            t_q, page_size=self.page_size,
            kv_dtype=jnp.int8 if self.kv_quantized else (
                self._cache_dtype or self.cfg.dtype),
            tp=self._tp_ways,
        )

    def decode_achieved_hbm_bytes(
        self, live_lens, t_q: int = 1, *, include_params: bool = True
    ):
        """Length-aware modeled HBM bytes for ONE decode tick:
        ``live_lens`` are the live slots' cache fills (host mirror) at
        tick start. Visited K/V tiles come from the host formula
        :func:`~mpit_tpu.ops.decode_attention.num_kv_blocks` — pinned
        bitwise against the kernel's own in-kernel visited count — plus
        one tile per clamped free slot, the param read, and the
        appended rows, all at the cache's ACTUAL wire dtype
        (``kv_dtype``: int8 tiles + scale blocks under quantization —
        ISSUE 15 honesty: utilization figures must count what the DMA
        moves, not the logical f32 view). ``t_q`` is the tick's query
        width (1 plain; ``spec_k + 1`` for a speculative verify — its
        tile bound is ``ceil((L + k + 1)/block_k)``).
        ``include_params=False`` drops the (dtype-independent) param
        read — the KV-sweep-only figure the bench's kv-dtype A/B
        ratios, since the sweep is the term quantization shrinks.
        ``None`` on the reference engine (no tiling claim to
        account); on the off-TPU kernel fallback the figure is the
        MODEL of the kernel path (the platform label on the registered
        cost marks it modeled)."""
        if self.decode_attention == "reference":
            return None
        lens = np.asarray(live_lens)
        visited = num_kv_blocks(
            lens, t_q, self.max_len, self.decode_block_k
        )
        total_tiles = int(visited.sum()) + (self.slots - lens.size)
        return _roofline.decode_step_hbm_bytes(
            total_tiles,
            block_k=self.decode_block_k,
            kv_row_bytes=self._kv_row_bytes,
            num_layers=len(self.cache.k),  # the layers that keep pages
            param_bytes=self._param_bytes if include_params else 0.0,
            appended_rows=lens.size * t_q,
        ) + 2.0 * lens.size * self.slot_state_bytes  # seats read, written

    def lengths(self) -> np.ndarray:
        return np.asarray(self.cache.lengths)

    def reset(self, seed: int = 0) -> None:
        """Clear all slots (bench warmup path); compiled steps survive."""
        self.cache = _zeroed(self.cache)
        if self.draft_cache is not None:
            self.draft_cache = _zeroed(self.draft_cache)
        self.last_token = jnp.zeros_like(self.last_token)
        self._key, self._sub = jax.random.key(seed), None
        self._spec_state = None
        if self.host_pages:
            # The host tier empties with the pool: drop payloads
            # (pending dispatches included) and refund every byte
            # still charged, keeping per-tier conservation exact.
            self._pending_spills.clear()
            self._host_store.clear()
            held = self.memledger.held("kv_host_pages")
            if held:
                self.memledger.free("kv_host_pages", held, kind="reset")
            self.host_spilled_pages = 0
            self.host_restreamed_pages = 0
            self.host_spill_bytes = 0
            self.host_restream_bytes = 0
        self.allocator.reset()
        # Owner recency and exhaustion forensics describe the LAST run;
        # static buffer grants persist (the buffers do too).
        self.memledger.reset_transients()

    def export_kv_rows(self, slot: int, length: int):
        """Host copy of ``slot``'s first ``length`` cached KV rows in
        the canonical row layout ``[L, length, H, Dh]`` (scale leaves
        ``[L, length, H, 1]`` on a quantized cache — jax.tree.map
        descends the QuantizedKV pair): the slot's block-table pages,
        gathered, with the tail pad trimmed. Whatever the page geometry,
        identical fills yield identical arrays, so a fleet shipment
        packed on one engine injects into any other. Returns
        ``(k_rows, v_rows)``."""
        self.model.check_shipment()
        if length <= 0:
            raise ValueError(f"export_kv_rows needs length > 0, got {length}")
        ps = self.page_size
        npages = -(-length // ps)
        pages = np.asarray(
            self.allocator.block_tables[slot, :npages], np.int32
        )

        def rows(*layers):
            # The slot's pages of every layer, stacked on the device
            # so that one array comes to the host: [L, npages, ps, ·].
            arr = np.asarray(jnp.stack([buf[pages] for buf in layers]))
            arr = arr.reshape(len(layers), npages * ps, -1)
            return arr[:, :length].copy()

        return tuple(
            unpack_heads(jax.tree.map(rows, *pool), self.cfg.num_heads)
            for pool in (self.cache.k, self.cache.v)
        )

    def inject_kv_rows(
        self, slot: int, k_rows, v_rows, length: int, first_token: int
    ) -> None:
        """Inverse of :meth:`export_kv_rows`: install ``length`` rows of
        shipped KV state into ``slot`` and arm it for decode —
        ``lengths[slot] = length``, ``last_token[slot] = first_token``
        (the token the shipping side sampled at prefill end). The
        caller has already run ``allocator.admit`` for the slot
        (all-or-nothing, no ``register_prefix`` — injected pages are private, never prefix-shared); rows scatter into the
        slot's mapped pages. ``k_rows``/``v_rows`` match the export
        layout — raw arrays, or objects with ``.q``/``.scale`` for a
        quantized cache (any container with those attributes works;
        leaves are rebuilt positionally)."""
        self.model.check_shipment()
        if self.kv_quantized:
            # Rebuild as the cache's own pytree type so tree.map pairs
            # leaves positionally whatever container shipped them.
            k_rows = QuantizedKV(q=k_rows.q, scale=k_rows.scale)
            v_rows = QuantizedKV(q=v_rows.q, scale=v_rows.scale)
        ps = self.page_size
        npages = -(-length // ps)
        pages = np.asarray(
            self.allocator.block_tables[slot, :npages], np.int32
        )

        def put(pool, rows):
            # Canonical rows [L, length, H, ·] to the pool's packed
            # form, then whole pages (the tail page as far as filled)
            # into each layer's buffer. Not a jitted step: a
            # shipment lands once a request, not once a tick.
            rows = jax.tree.map(np.asarray, pack_heads(rows))

            def put1(buf, layer_rows):
                layer_rows = jnp.asarray(layer_rows, buf.dtype)
                for i in range(npages):
                    n = min(ps, length - i * ps)
                    buf = buf.at[int(pages[i]), :n].set(
                        layer_rows[i * ps : i * ps + n]
                    )
                return buf

            return tuple(
                jax.tree.map(
                    put1, layer, jax.tree.map(lambda r: r[i], rows)
                )
                for i, layer in enumerate(pool)
            )

        self.cache = dataclasses.replace(
            self.cache,
            k=put(self.cache.k, k_rows),
            v=put(self.cache.v, v_rows),
            lengths=self.cache.lengths.at[slot].set(int(length)),
        )
        self.last_token = self.last_token.at[slot].set(int(first_token))
