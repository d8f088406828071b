"""mpit_tpu.serve — TPU-native continuous-batching inference (ISSUE 4).

The reference's pserver is a request-serving loop — receive a tagged
message, act on shared state, reply (SURVEY.md §3.2 A1). Training
collapsed that protocol into SPMD steps (``mpit_tpu.train``); serving
re-grows it as the north star demands ("serves heavy traffic"): a
batched inference engine where the shared state is a page pool of
cached K/V and the request loop is continuous batching.

- :mod:`~mpit_tpu.serve.kvcache` — the page pool: per layer one K and
  one V buffer ``[pages, page_size, heads*head_dim]`` + per-slot
  lengths, the host ``PageAllocator`` (block tables, refcounts, prefix
  index, copy-on-write, the host tier); head-axis sharding specs for
  tensor parallelism.
- :mod:`~mpit_tpu.serve.engine` — ONE jitted prefill-chunk step + ONE
  jitted decode step over the whole slot batch (fixed shapes, three
  compiles with the page copy for the engine's lifetime); per-slot
  greedy/temperature/top-k sampling jitted with the step; a TP variant
  reusing the ``parallel.megatron`` block rules. Greedy outputs match
  the no-cache ``models.gpt2`` forward. The hot loop is kernel-shaped:
  attention runs the Pallas flash-decode kernel
  (:mod:`mpit_tpu.ops.decode_attention` — the pool read in place,
  blocked over the cache length, per-slot length-aware skipping) and
  sampling streams the LM head per vocab block
  (:func:`mpit_tpu.ops.lm_head.lm_head_sample`) — the decode step
  never materializes ``[slots, vocab]`` logits or ``[slots, H, T,
  max_len]`` scores. The blocked head takes the path its rows ask for,
  chosen on the device inside the one compiled step: a step in which
  no slot's ``temperature`` is above 0 takes one max and one argmax a
  vocab block; one sampling slot sends that step down the general scan
  (Gumbel noise, the top-k sorts and the candidate merge), whose greedy
  rows carry the same tokens. ``Server`` counts the steps of each kind
  (``stats()["steps_greedy_head"]`` / ``["steps_sampled_head"]``) and
  labels the span that enqueues one (``sampler_path``).
  ``Engine(decode_attention="reference")`` runs the
  gather-dense attention and the whole-logits sampler as the parity
  oracle.
- :mod:`~mpit_tpu.serve.scheduler` — the continuous-batching loop:
  queue → admit into freed slots between decode ticks → per-slot
  retirement (EOS / max tokens / cache full), with full ``obs``
  integration (prefill/decode spans, per-request queue-wait/TTFT/
  latency intervals, slot-occupancy gauge).
- :mod:`~mpit_tpu.serve.loadgen` — open-loop load generation (ISSUE
  6): seeded Poisson / bursty arrival traces with mixed prompt/output-
  length classes and tenant IDs, driven by ``Server.run_timed`` on the
  arrival clock; paired with ``obs.stream`` rolling-window telemetry
  and ``obs.slo`` SLO monitoring, this is the "heavy traffic" harness
  the ``gpt2_slo`` bench sweep measures.
- :mod:`~mpit_tpu.serve.policy` — the scheduling-policy tier (ISSUE
  12): priority classes drained in tier order, deficit-weighted
  round-robin tenant fairness within a tier (bounded deficit counters),
  projected-TTFT admission shedding (``shed_admission`` vs
  ``shed_queue_full`` kept apart), and paged-KV preemption — park a
  low-tier generation (pages freed, tokens kept host-side), resume it
  through chunked prefill with a pinned greedy bit-match. Plug in via
  ``Server(policy=SchedulingPolicy(...))``; without one the scheduler
  is the FIFO loop unchanged.
- :mod:`~mpit_tpu.serve.spec` — speculative decoding (ISSUE 13): the
  exact draft-then-verify math (proposal distribution, longest-
  accepted-prefix emission with EOS/budget clamps, the full-logits
  verify oracle). ``Engine(spec_k=k, draft_params=, draft_cfg=)``
  drafts ``k`` tokens per slot and verifies them in ONE T=k+1 target
  pass; cache lengths advance by the accepted count only (the
  rollback). Greedy output bit-matches the plain engine; sampling is
  exact rejection sampling through the blocked LM head
  (``ops.lm_head.lm_head_verify``).
- :mod:`~mpit_tpu.serve.weights` — dense-checkpoint ingestion: a
  ``train.convert --save-dense`` ``.npz`` from ANY training tier serves
  directly (leaf contract pinned in ``tests/test_convert.py``);
  ``draft_from_target`` cuts an early-exit self-speculation draft from
  the target's own first N blocks.
- :mod:`~mpit_tpu.serve.fleet` / :mod:`~mpit_tpu.serve.shipment` —
  the disaggregated serving fleet (ISSUE 19): a router admits and
  routes requests with the policy tier's projected-TTFT math, prefill
  workers run chunked prefill and ship finished KV pages (int8
  payloads + scale blocks included) to decode workers as
  length-prefixed shipments on a dedicated ``Comm_dup("fleet-kv")``
  channel, and liveness rides the EASGD anchor machinery — heartbeat
  threads, a router-side lease sweep, dead-worker re-queue — with
  greedy outputs bit-matching the single-engine run per request.

CLI: ``python -m mpit_tpu.serve`` — load a dense checkpoint (or
random-init), serve a synthetic request stream, print the obs summary.
"""

from mpit_tpu.serve.engine import Engine, sample_tokens
from mpit_tpu.serve.fleet import (
    FleetConfig,
    parse_fleet_spec,
    run_fleet,
)
from mpit_tpu.serve.kvcache import (
    PageAllocator,
    PagedKVCache,
    QuantizedKV,
    alloc_paged_cache,
    kv_wire_bytes_per_row,
    paged_cache_specs,
    pages_needed,
)
from mpit_tpu.serve.loadgen import (
    Arrival,
    LoadSpec,
    RequestClass,
    generate_arrivals,
    parse_load_spec,
    split_arrivals,
)
from mpit_tpu.serve.policy import (
    PolicyConfig,
    SchedulingPolicy,
    TTFTProjector,
    parse_policy_spec,
)
from mpit_tpu.serve.scheduler import Completed, Request, Server, warm_engine
from mpit_tpu.serve.shipment import (
    KVShipment,
    inject_shipment,
    pack_shipment,
    recv_shipment,
    send_shipment,
    unpack_shipment,
)
from mpit_tpu.serve.weights import (
    draft_from_target,
    expected_param_shapes,
    infer_config,
    load_gpt2_params,
    params_wire_bytes,
    quantize_gpt2_params,
    weight_wire_bytes,
)

__all__ = [
    "Arrival",
    "Completed",
    "Engine",
    "FleetConfig",
    "KVShipment",
    "LoadSpec",
    "PageAllocator",
    "PagedKVCache",
    "PolicyConfig",
    "QuantizedKV",
    "Request",
    "RequestClass",
    "SchedulingPolicy",
    "Server",
    "TTFTProjector",
    "parse_fleet_spec",
    "parse_policy_spec",
    "run_fleet",
    "alloc_paged_cache",
    "paged_cache_specs",
    "pages_needed",
    "draft_from_target",
    "expected_param_shapes",
    "generate_arrivals",
    "infer_config",
    "kv_wire_bytes_per_row",
    "load_gpt2_params",
    "params_wire_bytes",
    "quantize_gpt2_params",
    "weight_wire_bytes",
    "inject_shipment",
    "pack_shipment",
    "parse_load_spec",
    "recv_shipment",
    "sample_tokens",
    "send_shipment",
    "split_arrivals",
    "unpack_shipment",
    "warm_engine",
]
