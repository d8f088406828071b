"""GPT-2 — the transformer stretch workload (baseline config #5).

Not in the reference (Torch7-era, pre-transformer; SURVEY.md §3.3); enters
via the acceptance ladder ("GPT-2 small — stretch", BASELINE.json). Pre-LN
GPT-2 architecture: learned positional embeddings, causal self-attention,
GELU MLP, weight-tied LM head.

Built TPU-first and parallelism-aware:

- module names (``qkv``/``proj``/``fc``/``out``) are the stable hooks the
  tensor-parallel sharding rules in :mod:`mpit_tpu.parallel` match on
  (Megatron pattern: column-shard qkv/fc, row-shard proj/out);
- the attention inner function is pluggable (``attention_fn``) so context
  parallelism (ring attention) and Pallas flash kernels substitute without
  touching the module tree;
- bfloat16 activations/matmuls (MXU-native), float32 params, logits and
  layernorms in float32;
- ``jax.named_scope`` at the seams a device trace is read by
  (``embed``; per block ``attn`` with ``kv_write`` / ``kv_gather`` inside
  it on the cache path, and ``mlp``; ``lm_head``): an operation's
  ``op_name`` carries them beside the flax module names, so a reduction
  of the profiler's trace can sum device time by what the code does.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen.dtypes import promote_dtype
from jax import lax

from mpit_tpu.models.serving import CacheLayout, PageLayer, ServeModel
from mpit_tpu.ops.decode_attention import paged_write_pages, writes_by_pages
from mpit_tpu.ops.kv_quant import (
    QuantizedKV,
    dequantize_kv,
    pack_heads,
    quantize_kv,
    unpack_heads,
)
from mpit_tpu.ops.quantized_matmul import (
    QuantizedTensor,
    dequantize_tensor,
    quantized_matmul,
    quantized_matmul_t,
)

AttentionFn = Callable[..., jax.Array]  # (q, k, v, *, causal) -> out


def default_attention(q, k, v, *, causal: bool = True):
    """Plain causal attention: softmax(QKᵀ/√d)V, f32 softmax accumulators.

    Shapes: [B, T, H, Dh] throughout (sequence-major, head-split), the
    layout ring attention and Ulysses expect.
    """
    dh = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(dh)
    if causal:
        t_q, t_k = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((t_q, t_k), bool), k=t_k - t_q)
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def paged_cache_update(pool, new, lengths, block_table, valid=None):
    """Write ``new`` [B, T, H*Dh] (rows packed as the projection made
    them) into one layer's page pool [P, page_size, H*Dh] at sequence
    positions ``lengths .. lengths+T-1``, indirected through
    ``block_table`` [B, pages_per_slot] int32 (ISSUE 7).

    The KV-cache append, a scatter: each (b, t) resolves to pool row
    ``[bt[b, pos//ps], pos % ps]``. A prefill chunk calls it with T =
    the chunk width at the slot's fill, decode with T = 1. The scatter
    indexes the pool as it is stored (no reshape of the pool on either side), so under a
    donating jit it writes ``B*T`` rows of the caller's buffer in place
    and moves no other byte. XLA's scatter lands a row at a time (146
    ns a row on the v5e), which a decode tick's B rows do not feel and a
    prefill chunk's B*64 do: on a TPU a write of a page's worth of rows
    or more into lane-aligned rows goes through
    :func:`~mpit_tpu.ops.decode_attention.paged_write_pages`, the same
    write a page at a time. ``valid`` [B, T] bool masks
    rows that must NOT land (prefill padding past the real prompt, and
    positions below a shared-prefix write floor — shared pages are
    immutable); masked rows scatter to an out-of-bounds page and are
    DROPPED, so a padded prefill can never touch a page the slot does
    not own.

    A :class:`~mpit_tpu.ops.kv_quant.QuantizedKV` pool (ISSUE 15)
    quantizes on write, per (row, head), and scatters the scale plane
    [P, page_size, H] through the SAME indices — the scale scatter
    rides the existing block-table path, so COW/prefix/preemption
    semantics cover scales by construction.
    """
    p, ps = pool.shape[0], pool.shape[1]
    b, t = new.shape[0], new.shape[1]
    pos = lengths[:, None] + jnp.arange(t)[None, :]  # [B, T]
    page = jnp.take_along_axis(
        block_table, jnp.clip(pos // ps, 0, block_table.shape[1] - 1),
        axis=1,
    )
    # A position past the slot's virtual capacity must be DROPPED, not
    # clipped into its last page (padding rows can reach here even
    # before any explicit mask).
    page = jnp.where(pos < block_table.shape[1] * ps, page, p)
    if valid is not None:
        page = jnp.where(valid, page, p)  # OOB -> dropped
    page, off = page.reshape(-1), (pos % ps).reshape(-1)

    def scatter(pl, rows):
        if writes_by_pages(pl, t):
            return paged_write_pages(pl, rows, lengths, block_table, valid)
        return pl.at[page, off].set(
            rows.astype(pl.dtype).reshape(b * t, -1), mode="drop"
        )

    if isinstance(pool, QuantizedKV):
        qn = pack_heads(quantize_kv(unpack_heads(new, pool.scale.shape[-1])))
        return QuantizedKV(
            q=scatter(pool.q, qn.q), scale=scatter(pool.scale, qn.scale)
        )
    return scatter(pool, new)


def paged_gather(pool, block_table, num_heads):
    """Materialize each slot's dense cache view from one layer's pool:
    [P, page_size, H*Dh] gathered through [B, pages_per_slot] →
    [B, pages_per_slot·page_size, H, Dh]. Rows past a slot's fill are
    whatever the mapped (or stale) pages hold — garbage by design; the
    attention mask defines validity. A quantized pool gathers q and
    scale together (tree-mapped; the scale comes back in the keepdims
    form ``[..., H, 1]``)."""

    def g1(pl):
        g = pl[block_table]  # [B, n_ps, ps, H*Dh]
        return g.reshape(g.shape[0], -1, g.shape[-1])

    with jax.named_scope("kv_gather"):
        return unpack_heads(jax.tree.map(g1, pool), num_heads)


def paged_cached_attention(q, k_pool, v_pool, lengths, block_table):
    """Reference paged attention: gather the dense per-slot view, then
    the exact :func:`cached_attention` math: masked keys contribute
    exact zeros, so greedy decode through this path matches the
    no-cache forward. The serving kernel path
    (:func:`mpit_tpu.ops.decode_attention.flash_paged_decode_attention`)
    never materializes this view — it DMAs only visited tiles, resolved
    per-tile through the block table."""
    h = q.shape[2]
    return cached_attention(
        q,
        paged_gather(k_pool, block_table, h),
        paged_gather(v_pool, block_table, h),
        lengths,
    )


def cached_attention(q, k, v, lengths):
    """Causal attention of new queries against a padded KV cache.

    ``q`` [B, T, H, Dh] are the T newest positions (global position of
    row ``t`` is ``lengths + t``); ``k``/``v`` [B, S, H, Dh] are a
    slot's whole cache view (new tokens already written; the paged path
    gathers it through :func:`paged_gather`).
    Key ``j`` is visible to query ``t`` iff ``j <= lengths + t`` — the
    same causal rule :func:`default_attention` applies, extended over the
    padded buffer, with the identical einsum/f32-softmax structure so
    cached and uncached forwards agree numerically (masked keys
    contribute exact zeros). Heads-local by construction: the TP engine
    calls this on its H/P head shard unchanged.

    Quantized buffers (ISSUE 15) dequantize here through the shared
    per-(row, head) helpers — this dense view is the flash kernel's
    numerical oracle AND the off-TPU fallback, so tier-1 exercises the
    exact per-tile dequant math on CPU (the PR 9 oracle pattern). The
    serving kernel never materializes it: int8 tiles + scale blocks are
    what cross HBM→VMEM there.
    """
    if isinstance(k, QuantizedKV):
        with jax.named_scope("kv_gather"):
            k = dequantize_kv(k)
            v = dequantize_kv(v)
    dh = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / jnp.sqrt(dh)
    t_q, s_max = q.shape[1], k.shape[1]
    q_pos = lengths[:, None] + jnp.arange(t_q)[None, :]  # [B, T]
    valid = jnp.arange(s_max)[None, None, :] <= q_pos[:, :, None]  # [B,T,S]
    scores = jnp.where(valid[:, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int | None = None  # default 4*d_model
    dtype: Any = jnp.bfloat16
    attention_fn: AttentionFn = default_attention
    remat: bool = False  # jax.checkpoint each block (HBM for FLOPs)
    # LayerNorm OUTPUT dtype; None = follow ``dtype``. Statistics always
    # accumulate in f32 (flax upcasts internally); the historical
    # hard-coded f32 output made every bf16 block bounce activations
    # f32->bf16 around both LNs — measured ~15 ms/step of convert/copy
    # fusions at B=48/T=512 (round-4 trace, BENCHMARKS.md). f32 configs
    # (parity tests) stay exactly f32 via the follow-``dtype`` default.
    ln_dtype: Any = None
    # LM-head matmul operand dtype. The [T, d_model] x [vocab, d_model]
    # logits einsum is the single biggest matmul in the model; bf16
    # operands with f32 accumulation run it at full MXU rate. f32 default
    # preserves exact logits for parity tests.
    head_dtype: Any = jnp.float32
    # Weight-tied LM head (GPT-2's default). Pipeline parallelism unties
    # it: under a pipe mesh the embedding's wte gradient lives only on
    # stage 0 while a tied head's would live on every stage, and the two
    # contributions cannot be combined per-leaf after AD.
    tie_head: bool = True
    # Attention on the cache path (serving): ``(q, k_pool, v_pool,
    # lengths, block_table)``. None = the gather-dense reference
    # :func:`paged_cached_attention`; the serving engine plugs in
    # :func:`mpit_tpu.ops.decode_attention.flash_paged_decode_attention`.
    # The training path (``attention_fn``) is untouched by this field.
    paged_attention_fn: Any = None
    # Matmul used when a Dense kernel seat holds a
    # :class:`~mpit_tpu.ops.quantized_matmul.QuantizedTensor` (ISSUE
    # 17): ``(x, qtensor) -> f32 [..., F]``. None = the blocked
    # :func:`~mpit_tpu.ops.quantized_matmul.quantized_matmul` (Pallas
    # fused-dequant kernel on TPU, blocked lax oracle elsewhere); the
    # serving engine injects its interpret/reference choice here — the
    # ``paged_attention_fn`` idiom. Irrelevant (never called) while
    # params are plain arrays.
    quant_matmul_fn: Any = None
    # Contraction/vocab row-block for the quantized matmuls; 0 = the
    # module default (256). Tests/contracts shrink it so tiny configs
    # still exercise real multi-block tiling.
    quant_block_rows: int = 0

    @property
    def ln_out_dtype(self):
        """Resolved LayerNorm output dtype (see ``ln_dtype``)."""
        return self.dtype if self.ln_dtype is None else self.ln_dtype

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def ff_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    def serve_model(self) -> "GPT2ServeModel":
        """This configuration as the serving engine takes it
        (:mod:`mpit_tpu.models.serving`)."""
        return GPT2ServeModel(self)

    @staticmethod
    def small(**kw) -> "GPT2Config":
        """GPT-2 small (124M)."""
        return GPT2Config(**kw)

    @staticmethod
    def tiny(**kw) -> "GPT2Config":
        """Test-sized config for CI and fake-mesh runs."""
        defaults = dict(
            vocab_size=512, max_seq_len=128, num_layers=2, num_heads=4, d_model=64
        )
        defaults.update(kw)
        return GPT2Config(**defaults)


class QuantDense(nn.Module):
    """``nn.Dense`` drop-in whose kernel seat also accepts a
    :class:`~mpit_tpu.ops.quantized_matmul.QuantizedTensor` (ISSUE 17).

    Plain-array path: byte-identical jaxpr to ``nn.Dense`` (same
    lecun-normal/zeros init, same ``promote_dtype`` + ``dot_general``
    structure) — the ``weights_dtype=None`` default MUST stay
    bit-identical, compile pins included. Quantized path: the int8
    payload + scale rows flow through ``quant_matmul_fn`` (default the
    blocked fused-dequant matmul), f32 accumulate, bias added in f32,
    then cast to ``dtype`` — the full dequantized kernel never
    materializes."""

    features: int
    dtype: Any = jnp.float32
    quant_matmul_fn: Any = None
    block_rows: int = 0

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (x.shape[-1], self.features),
            jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,),
            jnp.float32,
        )
        if isinstance(kernel, QuantizedTensor):
            if self.quant_matmul_fn is not None:
                y = self.quant_matmul_fn(x, kernel)
            else:
                y = quantized_matmul(
                    x, kernel, block_rows=self.block_rows or None
                )
            return (y + bias).astype(self.dtype)
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=self.dtype)
        y = lax.dot_general(
            x, kernel, (((x.ndim - 1,), (0,)), ((), ()))
        )
        return y + jnp.reshape(bias, (1,) * (y.ndim - 1) + (-1,))


class Block(nn.Module):
    cfg: GPT2Config

    @nn.compact
    def __call__(self, x, layer_cache=None):
        """``layer_cache`` (serving): ``(k_pool, v_pool, lengths,
        block_table, write_valid)``, the pools this layer's own
        [P, page_size, H*Dh] buffers and lengths [B] — the new tokens'
        K/V are appended at ``lengths`` through the block table
        (:func:`paged_cache_update`, ``write_valid`` [B, T] masking
        padding/shared-prefix rows) and attention runs against the pool
        (``cfg.paged_attention_fn``, default the gather-dense
        :func:`paged_cached_attention`) instead of ``cfg.attention_fn``;
        returns ``(x, (k_pool, v_pool))`` with the updated buffers.
        ``None`` (training): the historical single-output signature,
        untouched.
        """
        cfg = self.cfg
        dense = lambda features, name: QuantDense(
            features,
            dtype=cfg.dtype,
            quant_matmul_fn=cfg.quant_matmul_fn,
            block_rows=cfg.quant_block_rows,
            name=name,
        )
        split = lambda t: t.reshape(*t.shape[:-1], cfg.num_heads, cfg.head_dim)
        with jax.named_scope("attn"):
            h = nn.LayerNorm(dtype=cfg.ln_out_dtype, name="ln1")(x)
            qkv = dense(3 * cfg.d_model, "qkv")(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            if layer_cache is None:
                attn = cfg.attention_fn(
                    split(q), split(k), split(v), causal=True
                )
                new_cache = None
            else:
                k_pool, v_pool, lengths, block_table, write_valid = layer_cache
                with jax.named_scope("kv_write"):
                    # k, v are already the pool's packed rows [B, T, H*Dh].
                    k_pool = paged_cache_update(
                        k_pool, k, lengths, block_table, valid=write_valid
                    )
                    v_pool = paged_cache_update(
                        v_pool, v, lengths, block_table, valid=write_valid
                    )
                attn_fn = cfg.paged_attention_fn or paged_cached_attention
                attn = attn_fn(split(q), k_pool, v_pool, lengths, block_table)
                new_cache = (k_pool, v_pool)
            attn = attn.reshape(*attn.shape[:-2], cfg.d_model)
            x = x + dense(cfg.d_model, "proj")(attn)

        with jax.named_scope("mlp"):
            h = nn.LayerNorm(dtype=cfg.ln_out_dtype, name="ln2")(x)
            h = dense(cfg.ff_dim, "fc")(h)
            h = nn.gelu(h)
            x = x + dense(cfg.d_model, "out")(h)
        return x if layer_cache is None else (x, new_cache)


class GPT2(nn.Module):
    cfg: GPT2Config = GPT2Config()

    @nn.compact
    def __call__(
        self, tokens, positions=None, targets=None, paged_cache=None,
        return_hidden=False,
    ):
        """tokens [B, T] int32 → logits [B, T, vocab] float32.

        ``positions`` ([T] or [B, T] int32) overrides the default
        ``0..T-1`` — required under context parallelism, where each
        device's T is a *slice* of the global sequence (pass
        ``axis_index('seq') * T_local + arange(T_local)``).

        ``targets`` ([B, T] int32) switches the head to the fused
        streaming cross entropy (:func:`mpit_tpu.ops.lm_head.lm_head_xent`)
        and returns **per-token losses** [B, T] float32 instead of logits
        — the [B, T, vocab] f32 logits array is never materialized.
        Matmul operand dtype follows ``cfg.head_dtype`` on both paths.

        ``paged_cache`` (serving; :mod:`mpit_tpu.serve`): ``(k_pools,
        v_pools, lengths, block_tables, write_valid)`` with pools a
        sequence of ``num_layers`` buffers ``[num_pages, page_size,
        H*Dh]`` (layer ``i`` writes and reads ``k_pools[i]`` and nothing
        else, so a caller that donates them gets each back updated in
        place), ``lengths`` [B] int32 the per-slot token count already
        cached, ``block_tables`` [B, pages_per_slot] int32 and
        ``write_valid`` [B, T] bool. The T new tokens are appended at
        ``lengths`` — K/V appends scatter through each slot's block
        table (rows with ``write_valid`` False are dropped, never
        written) — and attended causally against the cache
        (``cfg.paged_attention_fn``, default the gather-dense
        reference); positions default to ``lengths + arange(T)``; the
        return becomes ``(logits_or_hidden, (new_k_pools,
        new_v_pools))``, tuples of per-layer buffers again. A prefill
        chunk = call with T = the chunk width; decode = call with T = 1.
        Mutually exclusive with ``targets``.

        ``return_hidden`` (serving; requires ``paged_cache``):
        skip the LM-head matmul and return the final post-``ln_f``
        hidden states ``[B, T, d_model]`` in place of logits — the
        blocked decode head (:func:`mpit_tpu.ops.lm_head.lm_head_sample`)
        samples straight from these, so the ``[B, T, vocab]`` f32
        logits array never exists in the decode step.
        """
        cfg = self.cfg
        if return_hidden and paged_cache is None:
            raise ValueError(
                "return_hidden is the serving decode-head path; it "
                "requires paged_cache="
            )
        if paged_cache is not None and targets is not None:
            raise ValueError(
                "paged_cache and targets are mutually exclusive: the fused "
                "xent head never materializes the logits decode needs"
            )
        if paged_cache is not None:
            pool_k, pool_v, cache_lengths, block_tables, write_valid = (
                paged_cache
            )
            if positions is None:
                # Junk rows (prefill padding past a slot's chunk) can
                # push past the table — clip; their embeddings are
                # discarded by the write mask / gather index anyway.
                positions = jnp.minimum(
                    cache_lengths[:, None]
                    + jnp.arange(tokens.shape[-1])[None, :],
                    cfg.max_seq_len - 1,
                )
        wte = self.param(
            "wte",
            nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.d_model),
            jnp.float32,
        )
        wpe = self.param(
            "wpe",
            nn.initializers.normal(0.01),
            (cfg.max_seq_len, cfg.d_model),
            jnp.float32,
        )
        t = tokens.shape[-1]
        with jax.named_scope("embed"):
            pe = wpe[:t] if positions is None else wpe[positions]
            emb = wte[tokens]
            if isinstance(emb, QuantizedTensor):
                # Gather picked int8 rows AND their scales; dequantize the
                # gathered [B, T, D] view — activation-sized, never the
                # [vocab, D] table.
                emb = dequantize_tensor(emb)
            x = emb.astype(cfg.dtype) + pe.astype(cfg.dtype)
        block = Block
        if cfg.remat:
            block = nn.remat(Block)
        new_k, new_v = [], []
        for i in range(cfg.num_layers):
            if paged_cache is not None:
                x, (k_i, v_i) = block(cfg, name=f"block_{i}")(
                    x,
                    (pool_k[i], pool_v[i], cache_lengths, block_tables,
                     write_valid),
                )
                new_k.append(k_i)
                new_v.append(v_i)
            else:
                x = block(cfg, name=f"block_{i}")(x)
        if paged_cache is not None:
            # One buffer a layer, each written by its own layer alone:
            # nothing to take out of a stack and nothing to stack again.
            new_kv = (tuple(new_k), tuple(new_v))
        with jax.named_scope("lm_head"):
            x = nn.LayerNorm(dtype=cfg.ln_out_dtype, name="ln_f")(x)
        if return_hidden:
            return x, new_kv
        # LM head (f32 accumulation regardless of operand dtype); tied to
        # wte by default, separate under tie_head=False (see GPT2Config).
        head = (
            wte
            if cfg.tie_head
            else self.param(
                "head",
                nn.initializers.normal(0.02),
                (cfg.vocab_size, cfg.d_model),
                jnp.float32,
            )
        )
        if targets is not None:
            from mpit_tpu.ops.lm_head import lm_head_xent

            with jax.named_scope("lm_head"):
                return lm_head_xent(
                    x, head, targets, compute_dtype=cfg.head_dtype
                )
        if isinstance(head, QuantizedTensor):
            # Blocked x @ head.T — ALWAYS, even for reference engines:
            # the speculative draft runs this head pass inside a hot
            # jitted step (``_spec_draft_step``), so a whole-dequant
            # here would put a [vocab, D] f32 intermediate into a
            # serving jaxpr. Blocking over vocab rows is bitwise
            # identical to whole-dequant (full-D contraction per
            # logit), so nothing is lost.
            with jax.named_scope("lm_head"):
                logits = quantized_matmul_t(
                    x.astype(cfg.head_dtype), head,
                    block_rows=cfg.quant_block_rows or None,
                )
        else:
            with jax.named_scope("lm_head"):
                logits = jnp.einsum(
                    "btd,vd->btv",
                    x.astype(cfg.head_dtype),
                    head.astype(cfg.head_dtype),
                    preferred_element_type=jnp.float32,
                )
        if paged_cache is not None:
            return logits, new_kv
        return logits

    @staticmethod
    def loss_fn(logits, tokens):
        """Next-token cross entropy: logits [B,T,V] vs tokens [B,T+1]."""
        targets = tokens[:, 1:]
        logits = logits[:, : targets.shape[1]]
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return -jnp.mean(ll)

    @staticmethod
    def fused_loss_fn(model: "GPT2", params, tokens):
        """Mean next-token xent via the fused head: tokens [B, T+1]."""
        losses = model.apply(
            {"params": params}, tokens[:, :-1], targets=tokens[:, 1:]
        )
        return jnp.mean(losses)


class GPT2ServeModel(ServeModel):
    """GPT-2 behind the serving engine's model interface: the flax
    forward above, asked through :class:`~mpit_tpu.models.serving.ServeModel`.
    Every engine mode is supported; the arithmetic is the module's own."""

    family = "gpt2"

    def __init__(self, cfg: GPT2Config):
        self.cfg = cfg
        self._module = GPT2(cfg)

    def cache_layout(self) -> CacheLayout:
        cfg = self.cfg
        width = cfg.num_heads * cfg.head_dim
        return CacheLayout((PageLayer((width, width)),) * cfg.num_layers,
                           cfg.dtype, scale_width=cfg.num_heads)

    def kv_row_bytes(self, dtype) -> float:
        from mpit_tpu.ops.kv_quant import kv_wire_bytes_per_row

        return kv_wire_bytes_per_row(
            self.cfg.num_heads, self.cfg.head_dim, dtype)

    def with_decode_attention(self, *, block_k, interpret, page_size):
        del page_size  # the engine's tile is the kernel's
        from mpit_tpu.ops.decode_attention import flash_paged_decode_attention

        attn_fn = functools.partial(
            flash_paged_decode_attention, block_k=block_k, interpret=interpret
        )
        return GPT2ServeModel(
            dataclasses.replace(self.cfg, paged_attention_fn=attn_fn))

    def attention_tiling(self, t_q, *, page_size, kv_dtype, tp=1):
        from mpit_tpu.ops.decode_attention import decode_tiling

        tiling = decode_tiling(
            t_q, self.cfg.num_heads // tp, kv_dtype, page_size=page_size,
            quantized=jnp.dtype(kv_dtype) == jnp.int8,
        )
        return {"attention_form": tiling.form, "attention_rows": tiling.rows}

    def with_quant_matmul(self, fn):
        return GPT2ServeModel(dataclasses.replace(self.cfg, quant_matmul_fn=fn))

    def forward_paged(self, params, tokens, cache, block_tables, write_valid,
                      *, return_hidden, row_valid=None, slot_index=None):
        del row_valid, slot_index  # every row is computed; no slot's state
        out, (k, v) = self._module.apply(
            {"params": params},
            tokens,
            paged_cache=(cache.k, cache.v, cache.lengths,
                         block_tables, write_valid),
            return_hidden=return_hidden,
        )
        return out, (k, v, cache.state), None

    def head_table(self, params):
        return params["head"] if "head" in params else params["wte"]
