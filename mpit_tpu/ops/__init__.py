"""mpit_tpu.ops — Pallas TPU kernels: the framework's native tier.

Where the reference's native stratum is a C binding handing Torch tensor
pointers to libmpi (SURVEY.md §2 L0), this framework's native stratum is
hand-scheduled TPU kernels below the XLA tier:

- :mod:`mpit_tpu.ops.ring_collectives` — composable ring
  reduce-scatter / all-gather over ICI via double-buffered
  ``make_async_remote_copy`` (shared host-side planner for
  non-divisible shapes, shared mailbox discipline), plus the
  EQuARX-spirit quantized variants (int8 wire with per-chunk scales) —
  the gradient-sync building blocks (ISSUE 9).
- :mod:`mpit_tpu.ops.ring_allreduce` — their composition: the
  ``MPI_Allreduce`` hot path (SURVEY.md §4.3; the "allreduce GB/s"
  metric), ``op="qsum"`` for the quantized wire.
- :mod:`mpit_tpu.ops.flash_attention` — fused blockwise causal attention
  (online softmax; never materializes the [T, T] score matrix) with a
  Flash-2 custom-VJP backward, the GPT-2 inner kernel and the per-shard
  block under ring attention.
- :mod:`mpit_tpu.ops.lm_head` — fused LM-head cross entropy (the same
  online-logsumexp trick applied over the vocabulary axis; never
  materializes the [B, T, vocab] f32 logits), plus the blocked decode
  head ``lm_head_sample`` (greedy/top-k/temperature sampling with a
  running top-k merge across vocab blocks — the serving analogue; a
  call in which no row samples takes a greedy scan of one max a block).
- :mod:`mpit_tpu.ops.decode_attention` — flash-decode against the paged
  KV pool: blocked over the cache length with online softmax and
  per-slot length-aware skipping (K/V stay in HBM and are read in place
  through the block table; a slot holding L tokens pays the rows up to
  L+T, not max_len) — the serving hot-loop kernel.

Every kernel has an ``interpret`` path so its semantics are testable on
the CPU fake mesh (SURVEY.md §6 "race detection" row), and an XLA
fallback for non-TPU backends.
"""

from mpit_tpu.ops.decode_attention import (
    flash_paged_decode_attention,
    num_kv_blocks,
    reference_paged_decode_attention,
)
from mpit_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_block,
    merge_attention,
    reference_attention,
)
from mpit_tpu.ops.kv_quant import (
    QuantizedKV,
    dequantize_kv,
    kv_wire_bytes_per_row,
    quantize_kv,
)
from mpit_tpu.ops.lm_head import lm_head_sample, lm_head_xent
from mpit_tpu.ops.ring_allreduce import ring_allreduce
from mpit_tpu.ops.ring_collectives import (
    RingPlan,
    dequantize_blocks,
    dequantize_chunk,
    plan_ring,
    plan_shards,
    quantize_blocks,
    quantize_chunk,
    ring_all_gather,
    ring_reduce_scatter,
)

__all__ = [
    "flash_attention",
    "flash_attention_block",
    "flash_paged_decode_attention",
    "merge_attention",
    "num_kv_blocks",
    "reference_attention",
    "reference_paged_decode_attention",
    "lm_head_sample",
    "lm_head_xent",
    "ring_allreduce",
    "RingPlan",
    "QuantizedKV",
    "dequantize_blocks",
    "dequantize_chunk",
    "dequantize_kv",
    "kv_wire_bytes_per_row",
    "plan_ring",
    "plan_shards",
    "quantize_blocks",
    "quantize_chunk",
    "quantize_kv",
    "ring_all_gather",
    "ring_reduce_scatter",
]
