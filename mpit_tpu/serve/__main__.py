"""Serving entry point: ``python -m mpit_tpu.serve [options]``.

Loads a trained dense checkpoint (``--ckpt state.npz``, the
``train.convert --save-dense`` format) or random-inits a model
(``--model tiny|small``), serves a request stream through the
continuous-batching engine, and prints one JSON result: the serving
stats (tokens/s, TTFT and latency percentiles, occupancy) plus the obs
phase summary. ``--mesh model=2`` selects the tensor-parallel engine.

Two drive modes (ISSUE 6):

- default — the closed-loop synthetic stream: ``--requests N`` all
  submitted up front, run to drain;
- ``--loadgen "rate=8,process=bursty,tenants=4"`` — the OPEN-loop
  production harness: a seeded ``serve.loadgen`` arrival trace driven
  by its own clock through ``Server.run_timed`` for ``--duration``
  seconds, with a live windowed stats line on stderr every
  ``--stats-interval`` seconds (rolling p50/p95 TTFT and latency,
  req/s, tokens/s, occupancy, queue depth) fed from the
  ``obs.stream`` registry — not from the Recorder's bounded buffer.

``--kv-pages N`` sizes the page pool: N pages of ``--kv-page-size``
tokens shared by all slots (HBM scales with the pool, not slots ×
max-len; ``--kv-pages 0``, the default, is a pool for every slot at
``--max-len``: slots × max-len / page-size pages), copy-on-write prefix
sharing keyed on prompt prefixes (drive it with ``--loadgen
"...,prefix=32"``), and ``--prefill-chunk`` slicing long admits across
decode ticks; the live stats line grows ``kv=`` (pool occupancy),
``kvtok=`` (tokens cached) and ``shr=`` (pages stored once, mapped by
several requests).

``--kv-dtype int8`` (ISSUE 15) quantizes the KV cache: int8 rows +
per-(row, head) scale blocks in HBM, dequantized per visited tile
inside the decode kernel — the dominant decode HBM sweep shrinks ~2×
vs bf16 and the same pool budget holds ~2× the tokens. The stats line
shows the wire dtype (``kvd=``); ``--kv-dtype f32|bf16`` simply pin
the pool's dtype. Rejected with ``--decode-attention reference``
(the oracle path dequantizes the whole cache per tick).

``--weights-dtype int8`` (ISSUE 17) quantizes the OTHER ~92% of the
decode sweep: every matmul weight (qkv/proj/fc/out kernels, wte, the
head) stored as int8 + per-row f32 scales, dequantized one block at a
time inside the blocked matmuls — never a full f32 weight in HBM. The
stats line shows ``wd=``; composes freely with ``--kv-dtype int8``
(together they quantize essentially the whole decode sweep). Rejected
with ``--decode-attention reference`` for the same reason as the KV
flag: the reference path materializes whole dequantized weights (the
parity oracle, not a serving path).

Roofline flight data (ISSUE 8): the engine's jitted steps register
their ``cost_analysis()`` costs at warm, every decode tick feeds the
length-aware achieved HBM bytes (visited-tile model) into the recorder
and the rolling windows, the live stats line gains ``hbmbw=`` (windowed
achieved GB/s) and ``mfu=`` (on-TPU only — off-chip it reads ``-``,
never a fabricated percentage), and the final JSON carries the
per-phase ``roofline`` roll-up plus ``engine_compiles`` (pinned
lifetime compile count; an unexpected recompile lands in the sentinel).

Start-up (ISSUE 36): one ``ready`` line on stderr when the engine takes
traffic (the warm-up's end, or a server nobody warmed: its first token)
— ``ready {"scope": "engine", "ready_s": ..., "seconds": {<phase>:
...}, "executables": ..., "cache_misses": ..., "slowest": [...]}``,
``obs.startup.report()``: seconds since the process's start, seconds
covered by each start-up phase (``warmup``, ``compile``, ``jit_trace``,
``jit_lower``, ``backend_compile``, ``first_run``, ``cost_query``: the
price of the cost query's second compile of each step), the slowest
executables by name. The final JSON's ``startup`` holds the same and
``compiles_after_ready`` by function: a compile in a tick has a name.

``--slo-ttft-p95 / --slo-latency-p95 / --slo-shed-rate`` declare SLO
targets; an ``obs.slo.SLOMonitor`` evaluates them over the rolling
windows each tick, breaches land in the trace / the sentinel, and the
final JSON carries the monitor's report (time in breach, time to
detect). ``--max-queue`` bounds intake (excess arrivals shed).

``--policy`` (ISSUE 12) swaps the FIFO scheduler for the scheduling-
policy tier (``serve.policy``): ``--policy on`` takes the defaults, or
a spec like ``"quantum=4,preempt=1,admission_factor=1.2,weight.t0=2"``.
Priority classes and per-class TTFT targets ride the load spec
(``--loadgen "...,priority=1,ttft_target=0.2"`` stamps every class; the
programmatic mixture sets them per class). The live stats line grows
``pre=`` (preemptions) under a policy, and the final JSON carries the
policy block (preemptions, resumes, admission sheds, tier depths) plus
the per-tenant roll-up and cause-split shed counts from
``Server.stats()``.

``--family xing4`` (ISSUE 26) serves the second model family behind the
engine's model interface (``models/serving.py``): latent attention over
a latent page pool, sigmoid-routed experts with no dropped token,
hyper-connected residual streams (``models/xing4.py``), on random
weights from ``--seed``: ``--model tiny`` or ``published``, or
``--model-config FILE`` with the keys of a published ``config.json``. It
raises, by name, for what the family lacks: ``--mesh``,
``--kv-dtype int8``, ``--weights-dtype int8``, ``--spec-k``, ``--kv-host-pages``, ``--ckpt``.

``--family olmo_hybrid`` (ISSUE 32) serves the third: gated-delta
linear-attention layers that keep a fixed state a slot (the engine's state
pool) beside full-attention layers that keep pages
(``models/olmo_hybrid.py``), on random weights from ``--seed``: ``--model
tiny`` or ``published``, or ``--model-config FILE``. It raises, by name,
for what would move or roll back a slot's state: ``--mesh``, ``--kv-dtype
int8``, ``--weights-dtype int8``, ``--spec-k``, ``--kv-host-pages``, a
``--policy`` that preempts (``preempt=0`` runs), ``--fleet`` shipment,
``--ckpt``. A prompt that repeats an earlier one is computed whole (the
prefix hit is passed up and counted: ``prefix_hits_passed_up``).
``--sample-block`` is the blocked sampler's tile of the head's rows (7168
divides this family's 100,352). Since PR 45 a tile that does not divide
the vocabulary costs no copy of the table: the full blocks are read where
they lie and the ragged last block's logits are padded.

``--family glm_dsa`` (ISSUE 34) serves the fourth: latent attention
under learned sparse attention (``models/glm_dsa.py``, ``ops/dsa.py``): a
layer with an indexer keeps an index key a cached position in a third
seat of the page pool, scores every cached position for the query and
attends to the ``index_topk`` best only; the layers marked ``shared``
reuse the choice of the layer before them. ``--model tiny`` or
``published``, or ``--model-config FILE`` (a file cut to one chip's share
of an expert-parallel deployment gives the experts held under
``n_routed_experts`` and the router's width under ``published``). It
raises, by name, for ``--mesh``, ``--kv-dtype int8``, ``--weights-dtype
int8``, ``--spec-k``, ``--kv-host-pages``, a ``--policy`` that preempts,
``--fleet`` shipment, ``--ckpt``; a repeated prompt is computed whole.

``--family laguna`` (ISSUE 46) serves the fifth: grouped-query heads
whose count differs by layer (48 in a full layer, 72 in a window layer,
over 8 cached heads), window layers whose pages keep the last
``sliding_window`` positions beside full layers whose pages keep them
all, in one allocator with a block table a lifetime (``models/laguna.py``,
``serve/kvcache.py``); a sigmoid gate a head on the attention output;
softmax-routed experts with a shared one. ``--model tiny`` or
``published``, or ``--model-config FILE`` (a file cut to one chip's share
gives the experts held under ``num_experts`` and the router's width under
``published``). It raises, by name, for ``--mesh``, ``--kv-dtype int8``,
``--weights-dtype int8``, ``--spec-k``, ``--kv-host-pages``, a
``--policy`` that preempts, ``--fleet`` shipment, ``--ckpt``; a repeated
prompt is computed whole (a window layer's pages of the prefix are gone).

Config follows the ``asyncsgd.config`` pattern: one dataclass, argparse
generated from its fields.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

import numpy as np

from mpit_tpu.asyncsgd.config import from_argv


@dataclasses.dataclass
class ServeConfig:
    """Options for the serving CLI (the ``opt`` table analogue)."""

    ckpt: str = ""  # dense .npz from --save-dense ("" = random init)
    family: str = "gpt2"  # gpt2 | xing4 | olmo_hybrid | glm_dsa | laguna
    model: str = "tiny"  # random-init size: tiny | small (families: published)
    # xing4: a JSON file with the keys of the published config.json (as
    # benchmark/configs/xing4-29b-a4b-6of40.json holds them); "" = --model.
    model_config: str = ""
    num_heads: int = 0  # ckpt head-count override (0 = d_model//64)
    slots: int = 4  # concurrent KV-cache slots
    max_len: int = 96  # per-slot cache length (prompt + generation)
    prefill_len: int = 32  # padded prompt buffer width
    requests: int = 16  # synthetic stream size
    prompt_len: int = 8  # max synthetic prompt length (uniform 1..N)
    max_new_tokens: int = 16
    temperature: float = 0.0  # <=0 greedy
    top_k: int = 0  # 0 = full vocab
    # Serving hot-loop implementation (ISSUE 5): kernel = Pallas
    # flash-decode + blocked LM-head sampling (reference fallback off
    # TPU); reference = gather-dense attention + whole-logits
    # sampling, the parity oracle; interpret = force the
    # kernel through the Pallas interpreter (CPU testing).
    decode_attention: str = "kernel"
    # Blocked sampler's candidate-buffer width — bounds --top-k under
    # kernel/interpret modes (submit rejects top_k > this). Grown here
    # so the remedy the rejection names is reachable from the CLI.
    sample_k_cap: int = 128
    # The page pool. HBM holds kv_pages × kv_page_size cache rows
    # shared by all slots (max_len is a per-slot VIRTUAL capacity;
    # kv_pages 0 = a pool for every slot at max_len: slots × max_len /
    # kv_page_size pages), prompts sharing a prefix map the same pages
    # copy-on-write, and prefill_chunk > 0 slices long admits across
    # ticks so they can't head-of-line-block decode (0 = chunks of
    # prefill_len: whole prompts).
    kv_pages: int = 0
    kv_page_size: int = 16
    prefill_chunk: int = 0
    sample_block: int = 8192  # rows of the head a step of the sampler takes
    # Host KV tier (ISSUE 20). kv_host_pages > 0 gives the engine a
    # host-RAM page store: preemption victims park their pages there
    # (resume restreams instead of re-prefilling) and dying
    # sole-reader prefix entries migrate there (admission hits keep
    # working after their HBM pages are reclaimed).
    kv_host_pages: int = 0
    # KV cache wire dtype (ISSUE 15). "" = the model dtype (default
    # path, byte-identical); f32|bf16 pin the cache dtype; int8 stores
    # quantized rows + per-(row, head) scales and fuses the dequant
    # into the decode kernel's per-tile DMA loop — ~2x fewer decode
    # HBM bytes than bf16, ~2x tokens at the same pool budget.
    # Rejected with --decode-attention reference: the reference
    # path dequantizes the WHOLE cache per tick (it exists as the
    # parity oracle, not a serving path — the perf the flag buys needs
    # the fused per-tile dequant of kernel/interpret).
    kv_dtype: str = ""
    # Weight store wire dtype (ISSUE 17). "" = dense params as loaded
    # (default path, byte-identical); "int8" quantizes every matmul
    # weight (per-row int8 + f32 scale through the shared rounding
    # contract) and runs the blocked fused-dequant matmuls — the param
    # term of the decode HBM sweep shrinks ~4x, with the same engine
    # step surface and compile pins. Rejected with --decode-attention
    # reference (the whole-dequant parity oracle, not a serving path).
    weights_dtype: str = ""
    # Speculative decoding (ISSUE 13). spec_k > 0 swaps the decode tick
    # for draft-then-verify (k drafted tokens per slot, one T=k+1 target
    # verify, longest-prefix acceptance with cache rollback). The draft
    # comes from --draft-ckpt (a dense .npz, any tier's export) or
    # --draft-config ("tiny" = random-init tiny config at the target's
    # vocab; "truncate:N" = the target's own first N blocks — the
    # self-speculation draft, no second checkpoint needed).
    spec_k: int = 0
    draft_ckpt: str = ""
    draft_config: str = ""
    draft_num_heads: int = 0  # --draft-ckpt head-count override
    mesh: str = ""  # e.g. "model=2" -> TP engine over that axis
    sentinel: bool = False  # decode/prefill tick anomaly sentinel
    trace: str = ""  # write a Chrome trace of the run here
    seed: int = 0
    # Open-loop load harness (ISSUE 6). loadgen = "" keeps the
    # closed-loop synthetic stream; otherwise a serve.loadgen spec
    # ("rate=8,process=poisson|bursty,on_fraction=0.25,tenants=4,
    # prompt_min=..,prompt_max=..,new_min=..,new_max=..").
    loadgen: str = ""
    duration: float = 10.0  # loadgen admission window, seconds
    drain: bool = True  # keep ticking past the window until drained
    max_queue: int = 0  # shed arrivals beyond this queue depth (0 = inf)
    window_s: float = 5.0  # rolling-window span for live stats / SLOs
    stats_interval: float = 2.0  # live stats line cadence (0 = silent)
    # SLO targets (0 = not declared). Evaluated over the rolling
    # windows; breaches emit slo_breach instants + sentinel notes.
    slo_ttft_p95: float = 0.0
    slo_latency_p95: float = 0.0
    slo_shed_rate: float = 0.0
    # Scheduling policy (ISSUE 12). "" = FIFO; "on" = defaults; or a
    # serve.policy spec: "quantum=4,preempt=1,admission_factor=1.2,
    # weight.<tenant>=2". Pair with --loadgen priority=/ttft_target=.
    policy: str = ""
    # Disaggregated fleet (ISSUE 19). "" = single-process server;
    # otherwise a serve.fleet spec ("prefill=2,decode=2[,lease_s=0.5,
    # heartbeat_s=0.05,admission_ttft_s=0.3]"): a router + prefill
    # workers shipping KV pages to decode workers over the compat
    # layer, driven by the closed-loop synthetic stream. Worker count
    # excludes the router; every worker builds its own engine from
    # THIS config's geometry flags.
    fleet: str = ""

    def mesh_shape(self) -> dict[str, int] | None:
        from mpit_tpu.asyncsgd.config import parse_mesh

        return parse_mesh(self.mesh)


# The families that serve random weights from --seed: the module of each
# under ``mpit_tpu.models`` and its configuration class there. Each serves
# on one chip in bf16 or f32; the engine and the server raise, by name,
# for what a family lacks (``--mesh``, ``--kv-dtype int8``,
# ``--weights-dtype int8``, ``--spec-k``, ``--kv-host-pages``, and for a
# family that keeps more than two-seat pages a preempting ``--policy``).
_FAMILIES = {
    "xing4": ("xing4", "Xing4Config"),
    "olmo_hybrid": ("olmo_hybrid", "OlmoHybridConfig"),
    "glm_dsa": ("glm_dsa", "GlmDsaConfig"),
    "laguna": ("laguna", "LagunaConfig"),
}


def _family_model(cfg: ServeConfig):
    """Random weights of a family that has no checkpoint loader
    (``_FAMILIES``): the published sizes, those of ``--model-config``'s
    file, or the tiny preset."""
    import importlib

    import jax

    module, config_name = _FAMILIES[cfg.family]
    module = importlib.import_module(f"mpit_tpu.models.{module}")
    config_cls, init_params = getattr(module, config_name), module.init_params

    if cfg.ckpt:
        raise SystemExit(
            f"--family {cfg.family} has no checkpoint loader yet: it serves "
            "random weights from --seed")
    longest = max(cfg.max_len, 128)
    if cfg.model_config:
        with open(cfg.model_config) as f:
            mcfg = config_cls.from_dict(json.load(f), max_seq_len=longest)
    elif cfg.model == "tiny":
        mcfg = config_cls.tiny(max_seq_len=longest)
    else:
        mcfg = config_cls(max_seq_len=longest)
    return init_params(mcfg, jax.random.key(cfg.seed)), mcfg


def _build_engine(cfg: ServeConfig):
    import jax
    import jax.numpy as jnp

    import mpit_tpu
    from mpit_tpu.models import GPT2, GPT2Config
    from mpit_tpu.serve import Engine, load_gpt2_params

    world, tp_axis = None, None
    shape = cfg.mesh_shape()
    if shape:
        world = mpit_tpu.init(shape, set_default=False)
        tp_axis = "model" if "model" in shape else next(iter(shape))

    # Pure-flag rejections FIRST — before the checkpoint load / random
    # init pays a compile a doomed invocation never needed.
    if cfg.kv_dtype and cfg.kv_dtype not in ("f32", "bf16", "int8"):
        raise SystemExit(
            f"--kv-dtype {cfg.kv_dtype!r}: expected f32, bf16 or int8"
        )
    if cfg.kv_dtype == "int8" and cfg.decode_attention == "reference":
        # Precise submit-time rejection (ISSUE 15 satellite): the
        # reference engine HAS the dequant hooks (it is the parity
        # oracle) but dequantizes the whole cache every tick — serving
        # int8 through it pays quantization error for MORE bytes moved,
        # the opposite of what the flag promises.
        raise SystemExit(
            "--kv-dtype int8 with --decode-attention reference: the "
            "reference path materializes the full dequantized cache "
            "per tick (it is the parity oracle, not a serving path); "
            "use --decode-attention kernel (or interpret) for the "
            "fused per-tile dequant"
        )
    if cfg.weights_dtype and cfg.weights_dtype not in ("f32", "int8"):
        raise SystemExit(
            f"--weights-dtype {cfg.weights_dtype!r}: expected f32 or int8"
        )
    if cfg.weights_dtype == "int8" and cfg.decode_attention == "reference":
        # Same rule as --kv-dtype (ISSUE 17): the reference engine runs
        # the whole-dequant matmul oracle — quantization error for MORE
        # bytes moved, the opposite of the flag's promise.
        raise SystemExit(
            "--weights-dtype int8 with --decode-attention reference: "
            "the reference path materializes whole dequantized weights "
            "(it is the parity oracle, not a serving path); use "
            "--decode-attention kernel (or interpret) for the blocked "
            "fused-dequant matmuls"
        )

    if cfg.family in _FAMILIES:
        params, mcfg = _family_model(cfg)
    elif cfg.family != "gpt2":
        raise SystemExit(
            f"--family {cfg.family!r}: expected gpt2, "
            + ", ".join(_FAMILIES))
    elif cfg.ckpt:
        params, mcfg = load_gpt2_params(cfg.ckpt, num_heads=cfg.num_heads)
    else:
        mcfg = (
            GPT2Config.small()
            if cfg.model == "small"
            else GPT2Config.tiny(max_seq_len=max(cfg.max_len, 128))
        )
        params = jax.jit(GPT2(mcfg).init)(
            jax.random.key(cfg.seed), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    # Speculative-decode draft resolution + submit-time validation of
    # incompatible combinations (ISSUE 13 satellite): every rejection
    # here is a precise SystemExit BEFORE the first jitted step — never
    # a shape error (or silent corruption) inside one.
    draft_params, draft_cfg = None, None
    if cfg.spec_k:
        from mpit_tpu.serve import draft_from_target

        if cfg.draft_ckpt and cfg.draft_config:
            raise SystemExit(
                "--draft-ckpt and --draft-config are mutually "
                "exclusive: one draft model per engine"
            )
        if cfg.draft_ckpt:
            draft_params, draft_cfg = load_gpt2_params(
                cfg.draft_ckpt, num_heads=cfg.draft_num_heads
            )
        elif cfg.draft_config.startswith("truncate:"):
            try:
                n = int(cfg.draft_config.split(":", 1)[1])
            except ValueError:
                raise SystemExit(
                    f"--draft-config {cfg.draft_config!r}: expected "
                    "truncate:<num_layers>"
                )
            if not 1 <= n < mcfg.num_layers:
                raise SystemExit(
                    f"--draft-config truncate:{n}: need 1 <= N < the "
                    f"target's {mcfg.num_layers} layers (an equal-depth "
                    "draft costs what the target costs)"
                )
            draft_params, draft_cfg = draft_from_target(params, mcfg, n)
        elif cfg.draft_config == "tiny":
            draft_cfg = GPT2Config.tiny(
                vocab_size=mcfg.vocab_size,
                max_seq_len=mcfg.max_seq_len,
                dtype=mcfg.dtype,
            )
            draft_params = jax.jit(GPT2(draft_cfg).init)(
                jax.random.key(cfg.seed + 1), jnp.zeros((1, 8), jnp.int32)
            )["params"]
        else:
            raise SystemExit(
                f"--spec-k {cfg.spec_k} needs a draft: --draft-ckpt "
                f"state.npz, --draft-config tiny, or --draft-config "
                f"truncate:N (got draft_config={cfg.draft_config!r})"
            )
    elif cfg.draft_ckpt or cfg.draft_config:
        raise SystemExit(
            "--draft-ckpt/--draft-config require --spec-k >= 1"
        )
    engine = Engine(
        mcfg,
        params,
        slots=cfg.slots,
        max_len=cfg.max_len,
        prefill_len=cfg.prefill_len,
        world=world,
        tp_axis=tp_axis,
        seed=cfg.seed,
        decode_attention=cfg.decode_attention,
        sample_k_cap=max(cfg.sample_k_cap, cfg.top_k),
        sample_block=cfg.sample_block,
        kv_pages=cfg.kv_pages or None,
        kv_page_size=cfg.kv_page_size,
        kv_host_pages=cfg.kv_host_pages or None,
        prefill_chunk=cfg.prefill_chunk or None,
        spec_k=cfg.spec_k,
        draft_params=draft_params,
        draft_cfg=draft_cfg,
        kv_dtype=cfg.kv_dtype or None,
        weights_dtype=cfg.weights_dtype or None,
    )
    return engine, mcfg


def synthetic_requests(cfg: ServeConfig, vocab_size: int):
    """A reproducible request stream: uniform prompt lengths 1..N,
    uniform token ids, the CLI's sampling settings."""
    from mpit_tpu.serve import Request

    rng = np.random.RandomState(cfg.seed)
    for i in range(cfg.requests):
        plen = int(rng.randint(1, cfg.prompt_len + 1))
        yield Request(
            rid=i,
            prompt=rng.randint(0, vocab_size, size=plen).tolist(),
            max_new_tokens=cfg.max_new_tokens,
            temperature=cfg.temperature,
            top_k=cfg.top_k,
        )


def _slo_targets(cfg: ServeConfig):
    from mpit_tpu.obs.slo import SLO

    targets = []
    if cfg.slo_ttft_p95 > 0:
        targets.append(SLO.ttft_p95(cfg.slo_ttft_p95))
    if cfg.slo_latency_p95 > 0:
        targets.append(SLO.latency_p95(cfg.slo_latency_p95))
    if cfg.slo_shed_rate > 0:
        targets.append(SLO.shed_rate(cfg.slo_shed_rate))
    return targets


def _live_line(registry, monitor, server, now: float) -> str:
    """One windowed stats line — everything on it comes from the
    rolling windows (O(buckets)), never from the Recorder's buffer."""
    ws = registry.window_stats()
    h, r, g = ws["histograms"], ws["rates"], ws["gauges"]

    def ms(name, k):
        v = h.get(name, {}).get(k)
        return f"{v * 1000:.0f}" if v is not None else "-"

    line = (
        f"[t={now:6.1f}s] "
        f"ttft p50/p95={ms('request_ttft', 'p50')}/"
        f"{ms('request_ttft', 'p95')}ms "
        f"lat p95={ms('request_latency', 'p95')}ms "
        f"req/s={r.get('serve_arrivals', {}).get('rate_per_s', 0.0):.1f} "
        f"tok/s={r.get('serve_tokens', {}).get('rate_per_s', 0.0):.0f} "
        f"occ={g.get('slot_occupancy', 0.0):.2f} "
        f"q={g.get('queue_depth', 0.0):.0f} "
        f"done={len(server.completed)} shed={len(server.shed)}"
    )
    if server.policy is not None:
        line += f" pre={server.policy.preemptions}"
    if server.engine.kv_dtype_explicit:
        # The cache WIRE dtype (ISSUE 15): what the decode sweep
        # actually moves — shown whenever it was explicitly chosen, so
        # an int8 run's hbmbw= figure is attributable from the line.
        line += f" kvd={server.engine.kv_dtype}"
    if server.engine.weights_dtype_explicit:
        # The weight store's wire dtype (ISSUE 17): the param term of
        # the same sweep.
        line += f" wd={server.engine.weights_dtype}"
    if "kv_pool_occupancy" in g:
        # Cache-MEMORY efficiency next to slot occupancy (ISSUE 7):
        # pool fill, tokens actually held, pages stored once but
        # mapped by multiple requests.
        line += (
            f" kv={g['kv_pool_occupancy']:.2f}"
            f" kvtok={g.get('kv_tokens_cached', 0.0):.0f}"
            f" shr={g.get('prefix_pages_shared', 0.0):.0f}"
        )
    if "hbm_held_bytes" in g:
        # Byte-exact memory view (ISSUE 18): total ledger-held HBM,
        # the KV pool's held share, and the admission headroom — the
        # same numbers a refused admit is annotated with.
        line += (
            f" hbm={g['hbm_held_bytes'] / 1e6:.1f}MB"
            f" held={g.get('kv_held_bytes', 0.0) / 1e6:.1f}MB"
            f" headroom={g.get('kv_headroom_pct', 0.0):.0f}%"
        )
    bw = r.get("decode_hbm_bytes", {}).get("rate_per_s", 0.0)
    if bw:
        # Windowed utilization (ISSUE 8): the length-aware decode HBM
        # rate from the rolling window (visited-tile bytes, not the
        # padded model). MFU only when the platform IS the chip —
        # off-TPU the flops rate against a TPU peak would be fiction,
        # so the field shows "-" and the final JSON carries the
        # platform-labeled roofline block instead.
        line += f" hbmbw={bw / 1e9:.2f}GB/s"
        fl = r.get("decode_flops", {}).get("rate_per_s", 0.0)
        if fl and server.engine.platform == "tpu":
            from mpit_tpu.obs.roofline import chip_peaks

            peak = chip_peaks(platform="tpu")["peak_flops"]
            line += f" mfu={100.0 * fl / peak:.1f}%"
        else:
            line += " mfu=-"
    if monitor is not None:
        breached = [
            name
            for name, t in monitor.report()["targets"].items()
            if t["in_breach"]
        ]
        if breached:
            line += " SLO-BREACH:" + ",".join(breached)
    return line


def _run_fleet_cli(cfg: ServeConfig) -> dict:
    """``--fleet prefill=P,decode=D``: the disaggregated serving fleet
    over the closed-loop synthetic stream. One JSON result: completion
    counts, per-worker roll-ups, fleet req/s, and the flight block's
    P2P matrix (KV shipment bytes visible per (src, dst))."""
    from mpit_tpu.serve.fleet import parse_fleet_spec, run_fleet

    fcfg = parse_fleet_spec(cfg.fleet)
    engine0, mcfg = _build_engine(cfg)
    seed_engines = [engine0]

    def factory(role, rank):
        # Same config + same seed → identical params on every worker
        # (the bit-match precondition); the probe engine built for the
        # vocab lookup serves the first worker instead of leaking.
        if seed_engines:
            return seed_engines.pop()
        engine, _ = _build_engine(cfg)
        return engine

    requests = list(synthetic_requests(cfg, mcfg.vocab_size))
    t0 = time.perf_counter()
    out = run_fleet(
        factory,
        requests,
        prefill=fcfg.prefill,
        decode=fcfg.decode,
        heartbeat_s=fcfg.heartbeat_s,
        lease_s=fcfg.lease_s,
        admission_ttft_s=fcfg.admission_ttft_s,
        job_timeout_s=fcfg.job_timeout_s,
    )
    wall = time.perf_counter() - t0
    completed = out["completed"]
    result = {
        "model": {
            "layers": mcfg.num_layers,
            "d_model": mcfg.d_model,
            "vocab": mcfg.vocab_size,
            "source": cfg.ckpt or f"random-init {cfg.model}",
        },
        "fleet": {"prefill": fcfg.prefill, "decode": fcfg.decode},
        "wall_s": round(wall, 4),
        "requests_completed": len(completed),
        "requests_shed": len(out["shed"]),
        "fleet_req_per_s": round(len(completed) / wall, 2) if wall else None,
        "generated_tokens": sum(len(t) for t in completed.values()),
        "router": {
            k: v
            for k, v in out["router"].items()
            if k not in ("completed", "role")
        },
        "workers": out["workers"],
    }
    flight = out.get("flight")
    if flight is not None:
        result["p2p_bytes"] = np.asarray(flight["p2p_bytes"]).tolist()
    return result


def main(argv: list[str] | None = None) -> dict:
    cfg = from_argv(ServeConfig, argv, prog="python -m mpit_tpu.serve")
    if cfg.fleet:
        return _run_fleet_cli(cfg)
    from mpit_tpu import obs
    from mpit_tpu.obs.slo import SLOMonitor
    from mpit_tpu.obs.stream import StreamRegistry
    from mpit_tpu.serve import (
        SchedulingPolicy,
        Server,
        generate_arrivals,
        parse_load_spec,
        parse_policy_spec,
        warm_engine,
    )

    rec = obs.enable(obs.Recorder())
    sentinel = (
        obs.Sentinel(phases=("decode", "prefill"), warmup=4)
        if cfg.sentinel
        else None
    )
    engine, mcfg = _build_engine(cfg)
    registry = StreamRegistry(window_s=cfg.window_s)
    targets = _slo_targets(cfg)
    monitor = (
        SLOMonitor(targets, registry, sentinel=sentinel) if targets else None
    )
    policy = (
        SchedulingPolicy(parse_policy_spec(cfg.policy), registry)
        if cfg.policy
        else None
    )
    spec = parse_load_spec(cfg.loadgen) if cfg.loadgen else None
    if spec is not None:
        # Fail BEFORE the timed window, not on whichever arrival first
        # draws a long prompt mid-trace: submit() treats an oversized
        # request as a caller bug, and for the CLI the caller is the
        # spec/geometry pair given right here.
        for klass in spec.classes:
            if klass.max_prompt_total > cfg.prefill_len:
                raise SystemExit(
                    f"--loadgen class {klass.name!r}: prefix + prompt_max "
                    f"{klass.max_prompt_total} > --prefill-len "
                    f"{cfg.prefill_len}"
                )
            need = klass.max_prompt_total + klass.max_new_tokens[1]
            if need > cfg.max_len:
                raise SystemExit(
                    f"--loadgen class {klass.name!r}: prefix + prompt_max "
                    f"+ new_max = {need} > --max-len {cfg.max_len}"
                )
        # Warm the engine's compiles OUTSIDE the timed window — an
        # open-loop harness that pays multi-second XLA compiles inside
        # its first arrivals' TTFT measures the compiler, not the
        # server. register_costs: the steps' cost_analysis lands in the
        # recorder so the final JSON (and the live mfu=/hbmbw= fields)
        # carry the roofline view (ISSUE 8).
        warm_engine(engine, register_costs=True)
        arrivals = generate_arrivals(
            spec,
            vocab_size=mcfg.vocab_size,
            duration_s=cfg.duration,
            seed=cfg.seed,
        )
        server = Server(
            engine,
            sentinel=sentinel,
            stream=registry,
            slo=monitor,
            max_queue=cfg.max_queue or None,
            policy=policy,
        )
        last_line = [0.0]

        def on_tick(srv, now):
            if cfg.stats_interval <= 0:
                return
            if now - last_line[0] < cfg.stats_interval:
                return
            last_line[0] = now
            print(
                _live_line(registry, monitor, srv, now),
                file=sys.stderr,
                flush=True,
            )

        t0 = time.perf_counter()
        server.run_timed(
            arrivals,
            duration=cfg.duration,
            drain=cfg.drain,
            on_tick=on_tick,
        )
        wall = time.perf_counter() - t0
    else:
        server = Server(
            engine,
            sentinel=sentinel,
            stream=registry,
            slo=monitor,
            max_queue=cfg.max_queue or None,
            policy=policy,
        )
        for req in synthetic_requests(cfg, mcfg.vocab_size):
            server.submit(req)
        t0 = time.perf_counter()
        server.run()
        wall = time.perf_counter() - t0

    if engine.roofline_costs is None:
        # Closed-loop path (no warm): register the step costs now —
        # registration is time-independent, so doing it after the run
        # still yields the full roofline roll-up below.
        try:
            engine.register_roofline()
        except Exception:
            if engine.platform == "tpu":
                raise
            # backends without AOT cost support: phases-only output
    summ = rec.summary()
    stats = server.stats()
    decode_s = summ["phases"].get("decode", {}).get("total_s", 0.0)
    gen = stats["generated_tokens"]
    # First tokens come from prefill; decode throughput counts the rest.
    decode_tokens = gen - stats["requests_completed"]
    out = {
        "model": {
            "layers": mcfg.num_layers,
            "d_model": mcfg.d_model,
            "vocab": mcfg.vocab_size,
            "source": cfg.ckpt or f"random-init {cfg.model}",
        },
        "wall_s": round(wall, 4),
        "decode_tokens_per_sec": (
            round(decode_tokens / decode_s, 2) if decode_s else None
        ),
        "decode_attention": engine.decode_attention_mode,
        "decode_sampler": engine.decode_sampler,
        **stats,
        "obs_summary": {
            name: {
                k: round(v, 6) if isinstance(v, float) else v
                for k, v in p.items()
            }
            for name, p in summ["phases"].items()
        },
    }
    if summ.get("roofline"):
        # Per-phase measured-vs-modeled utilization (ISSUE 8):
        # platform-labeled; percentage verdicts only on the real chip.
        out["roofline"] = summ["roofline"]
    if spec is not None:
        out["load"] = {
            "rate": spec.rate,
            "process": spec.process,
            "tenants": spec.tenants,
            "duration_s": cfg.duration,
            "arrivals": len(arrivals),
            "shed": len(server.shed),
        }
        out["window_stats"] = registry.window_stats()
    if monitor is not None:
        out["slo"] = monitor.report()
    if sentinel is not None:
        out["sentinel"] = sentinel.report()
    if cfg.trace:
        obs.export_chrome_trace(cfg.trace, recorder=rec)
        out["trace"] = cfg.trace
    obs.disable()
    return out


if __name__ == "__main__":
    from mpit_tpu.obs import startup
    from mpit_tpu.utils import compile_cache_dir

    compile_cache_dir()
    # One ``ready`` line on stderr when the engine takes traffic (the
    # warm-up's end, or the first token of a server nobody warmed).
    startup.install()
    startup.on_ready(startup.say_ready)
    print(json.dumps(main(sys.argv[1:])))
