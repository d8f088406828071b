"""Laguna (``model_type: laguna``): grouped-query heads whose count differs
by layer, window layers beside full ones, a gate a head, softmax experts.

The serving model of the family of ``poolside/Laguna-S-2.1``'s public
``config.json``. A block is pre-norm, ``h = x + Attn(RMSNorm(x))``, ``y = h
+ F(RMSNorm(h))``, ``F`` a gated MLP (``mlp_layer_types`` says ``dense``) or
the expert layer of ``parallel/moe_serve.py`` with softmax scores. What the
layers differ in, by ``layer_types``:

- a **full_attention** layer: ``num_attention_heads_per_layer[l]`` (48)
  query heads over ``num_key_value_heads`` (8) cached heads of
  ``head_dim``, rotary over the first half of a head with YaRN; its pages
  keep every position of the sequence;
- a **sliding_attention** layer: 72 query heads over the same 8, plain
  rotary over the whole head at its own base; position ``t`` attends ``t -
  sliding_window < s <= t``, so its pages keep the last ``sliding_window``
  positions (``models/serving.py::PageLayer.window``): they live in the
  window layers' pool under the window block table, and go back to it
  behind the window (``serve/kvcache.py``).

Both read their pages through ``ops/decode_attention.py``'s grouped kernel
(a decode tick's row a slot and a chunk's rows alike: a chunk's window
rows reach back across the chunk boundary into the pages an earlier step
wrote). The attention output of head ``j`` is scaled by ``sigmoid(u
W_g)[j]`` before the output projection.

The equations, and what the published keys leave open, are those of
``models/laguna_reference.py``; this file computes them in the
configuration's dtype with float32 statistics, a chunk or a tick at a time
through the two lifetimes of pages.

The parameter tree (``init_params`` makes one; the names are the
benchmark's and the reference's too)::

    embed [V, d]   head [V, d]   final_norm [d]
    layers[i]: attn_norm, mlp_norm [d]
               attn: w_q [d, H_l D], w_k, w_v [d, H_kv D], w_g [d, H_l],
                     w_o [H_l D, d]
               mlp: w_gate, w_up [d, f], w_down [f, d]         (dense)
               moe: router [d, E], bias [E] (zeros: none is published),
                    w_gate, w_up [E_held, d, fe], w_down [E_held, fe, d],
                    shared: w_gate, w_up, w_down               (sparse)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from mpit_tpu.models import laguna_reference as _ref
from mpit_tpu.models.serving import CacheLayout, PageLayer, ServeModel
from mpit_tpu.models.xing4 import _dot, _normal, apply_rope, rms_norm

__all__ = ["LagunaConfig", "LagunaServeModel", "init_params",
           "forward_plain"]

FULL, SLIDING = _ref.FULL, _ref.SLIDING
_PERIOD = (FULL, SLIDING, SLIDING, SLIDING)


def _frozen(d: dict) -> tuple:
    return tuple(sorted(d.items()))


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    # One entry a layer, as the published lists are; () = the published
    # pattern (a full layer and three sliding ones, over and over; 48 and
    # 72 heads; a leading dense layer).
    layer_types: tuple = ()
    mlp_layer_types: tuple = ()
    num_attention_heads_per_layer: tuple = ()
    sliding_window: int = 512
    num_experts: int = 256  # what the router routes over
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    # ``rope_parameters`` of the two kinds of layer, as sorted item tuples
    # (a frozen dataclass hashes its fields).
    rope_full: tuple = _frozen(dict(
        rope_theta=500000.0, rope_type="yarn", factor=128.0,
        original_max_position_embeddings=8192, beta_slow=1.0, beta_fast=32.0,
        attention_factor=1.4852030263919618, partial_rotary_factor=0.5))
    rope_sliding: tuple = _frozen(dict(
        rope_theta=10000.0, rope_type="default", partial_rotary_factor=1.0))
    max_seq_len: int = 1048576
    dtype: Any = jnp.bfloat16
    # The routed experts this chip holds, by global id, in the order of
    # the parameter tree's expert axis; None = every one.
    experts_held: tuple | None = None

    def __post_init__(self):
        n = self.num_hidden_layers
        fill = lambda given, make: tuple(given) or tuple(
            make(i) for i in range(n))
        set_ = lambda name, v: object.__setattr__(self, name, v)
        set_("layer_types", fill(self.layer_types, lambda i: _PERIOD[i % 4]))
        set_("mlp_layer_types", fill(
            self.mlp_layer_types, lambda i: "sparse" if i else "dense"))
        set_("num_attention_heads_per_layer", fill(
            self.num_attention_heads_per_layer,
            lambda i: 48 if self.layer_types[i] == FULL else 72))
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            if len(getattr(self, name)) != n:
                raise ValueError(f"{name} has one entry a layer ({n})")
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(
                f"layer_types name {FULL!r} or {SLIDING!r}, got "
                f"{self.layer_types}")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError(
                "every layer's query heads divide into num_key_value_heads "
                f"groups, got {self.num_attention_heads_per_layer}")

    @staticmethod
    def from_dict(d: dict, **overrides) -> "LagunaConfig":
        """From the keys of the published ``config.json``. A file cut to
        one chip's share of an expert-parallel deployment gives the
        experts HELD under ``num_experts`` and the router's width under
        ``published``; the share is ``ep_rank``'s (0) block."""
        rp = d.get("rope_parameters") or {}
        kw = dict(max_seq_len=d.get("max_position_embeddings", 1048576))
        for field, kind in (("rope_full", FULL), ("rope_sliding", SLIDING)):
            if kind in rp:
                kw[field] = _frozen(rp[kind])
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            if name in d:
                kw[name] = tuple(d[name])
        routed = (d.get("published") or {}).get("num_experts")
        if routed and routed != d["num_experts"]:
            here, rank = d["num_experts"], d.get("ep_rank", 0)
            kw.update(num_experts=routed, experts_held=tuple(
                range(rank * here, (rank + 1) * here)))
        names = {f.name for f in dataclasses.fields(LagunaConfig)}
        kw.update({k: v for k, v in d.items() if k in names and k not in kw})
        kw.update(overrides)
        return LagunaConfig(**kw)

    def to_dict(self) -> dict:
        """The published keys, as ``models/laguna_reference.py`` reads
        them (``num_experts`` the router's width)."""
        out = {f.name: getattr(self, f.name)
               for f in dataclasses.fields(self)
               if f.name not in ("rope_full", "rope_sliding", "dtype",
                                 "experts_held", "max_seq_len")}
        out["rope_parameters"] = {FULL: dict(self.rope_full),
                                  SLIDING: dict(self.rope_sliding)}
        return out

    @staticmethod
    def tiny(**kw) -> "LagunaConfig":
        """Test-sized: one period and a full layer, groups of 6 and 9 over
        2 cached heads of 8, a window of 8, 8 experts, 3 a token."""
        defaults = dict(
            vocab_size=256, hidden_size=48, intermediate_size=96,
            num_hidden_layers=5, num_key_value_heads=2, head_dim=8,
            num_attention_heads_per_layer=(12, 18, 18, 18, 12),
            sliding_window=8, num_experts=8, num_experts_per_tok=3,
            moe_intermediate_size=24, shared_expert_intermediate_size=24,
            rope_full=_frozen({**dict(LagunaConfig.rope_full),
                               "original_max_position_embeddings": 16,
                               "factor": 8.0}),
            max_seq_len=256, dtype=jnp.float32)
        defaults.update(kw)
        return LagunaConfig(**defaults)

    # What the engine reads of any model's configuration.
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def d_model(self) -> int:
        return self.hidden_size

    @property
    def head_dtype(self):
        return self.dtype

    @property
    def kv_width(self) -> int:
        """Values a cached position keeps in one seat of a layer."""
        return self.num_key_value_heads * self.head_dim

    def serve_model(self) -> "LagunaServeModel":
        return LagunaServeModel(self)


# -- pieces --------------------------------------------------------------------


def rope_tables(cfg: LagunaConfig, kind: str, positions):
    """``cos, sin`` [..., rot / 2] float32 of a layer of ``kind`` at
    ``positions``, YaRN's attention factor folded in."""
    freq, factor = _ref.inv_freq(
        dict(cfg.rope_full if kind == FULL else cfg.rope_sliding),
        cfg.head_dim)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(freq)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def rotate(x, cos, sin):
    """Rotate the first ``2 x cos.shape[-1]`` values of each head of ``x``
    [B, T, H, D] (rotate-half pairs); the rest pass through."""
    rot = 2 * cos.shape[-1]
    turned = apply_rope(x[..., :rot], cos[..., None, :], sin[..., None, :])
    return turned if rot == x.shape[-1] else jnp.concatenate(
        [turned, x[..., rot:]], axis=-1)


def attention_project(ap, u, cfg: LagunaConfig, cos, sin):
    """``q`` [B, T, H_l, D] and ``k``, ``v`` [B, T, H_kv, D] of one layer
    from its normed input ``u`` [B, T, d], ``q`` and ``k`` rotated."""
    d = cfg.head_dim
    heads = lambda a: a.reshape(*a.shape[:-1], -1, d)
    q, k = heads(_dot(u, ap["w_q"])), heads(_dot(u, ap["w_k"]))
    with jax.named_scope("rope"):
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    return q, k, heads(_dot(u, ap["w_v"]))


def gate_and_project(ap, u, o):
    """``(g x o) W_o`` in float32: ``o`` [B, T, H_l, D] scaled a head by
    ``g = sigmoid(u W_g)``."""
    with jax.named_scope("attn_gate"):
        g = jax.nn.sigmoid(_dot(u, ap["w_g"], jnp.float32))
        o = (o.astype(jnp.float32) * g[..., None]).astype(o.dtype)
    return _dot(o.reshape(*o.shape[:-2], -1), ap["w_o"], jnp.float32)


def mlp_or_experts(lp, x, cfg: LagunaConfig, valid):
    """The layer's second sublayer on the stream ``x`` [B, T, d] float32:
    ``(y, counts)``, ``counts`` [E] int32 or None."""
    from mpit_tpu.parallel.moe_serve import expert_layer, gated_mlp

    h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    flat = h.reshape(-1, h.shape[-1])
    if "mlp" in lp:
        with jax.named_scope("mlp"):
            return gated_mlp(flat.astype(cfg.dtype), **lp["mlp"],
                             out_dtype=jnp.float32).reshape(h.shape), None
    y, counts = expert_layer(
        flat.astype(cfg.dtype), lp["moe"], top_k=cfg.num_experts_per_tok,
        scale=cfg.moe_routed_scaling_factor, n_experts=cfg.num_experts,
        held=cfg.experts_held, normalise=cfg.norm_topk_prob,
        valid=None if valid is None else valid.reshape(-1),
        router_input=flat, out_dtype=jnp.float32, score="softmax")
    return y.reshape(h.shape), counts


def _kind_scope(kind: str) -> str:
    return "attn_full" if kind == FULL else "attn_window"


def forward_plain(params, tokens, cfg: LagunaConfig):
    """Logits ``[B, T, V]`` float32 of whole sequences through the
    program's own layers with no cache: dense causal attention, the
    window as a mask."""
    b, t = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    tables = {kind: rope_tables(cfg, kind, pos) for kind in (FULL, SLIDING)}
    x = params["embed"][tokens].astype(jnp.float32)
    key = jnp.arange(t)
    for lp, kind in zip(params["layers"], cfg.layer_types):
        u = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
        q, k, v = attention_project(lp["attn"], u, cfg, *tables[kind])
        h_kv = cfg.num_key_value_heads
        qg = q.reshape(b, t, h_kv, -1, cfg.head_dim)
        s = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                       preferred_element_type=jnp.float32
                       ) * cfg.head_dim ** -0.5
        vis = key[None, :] <= key[:, None]
        if kind == SLIDING:
            vis &= key[None, :] > key[:, None] - cfg.sliding_window
        p = jax.nn.softmax(jnp.where(vis, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        x = x + gate_and_project(
            lp["attn"], u, o.reshape(q.shape).astype(cfg.dtype))
        y, _ = mlp_or_experts(lp, x, cfg, None)
        x = x + y
    h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
    return jnp.einsum("btd,vd->btv", h, params["head"],
                      preferred_element_type=jnp.float32)


# -- parameters ------------------------------------------------------------------


def init_layer(cfg: LagunaConfig, key, layer: int, dtype=None) -> dict:
    """One layer's parameters from ``fold_in(key, layer)``: normal(0.02)
    matrices and router, unit norm gains, no selection bias."""
    dt = jnp.dtype(dtype or cfg.dtype)
    d, hd = cfg.hidden_size, cfg.head_dim
    h_l, kv = cfg.num_attention_heads_per_layer[layer], cfg.kv_width
    keys = iter(jax.random.split(jax.random.fold_in(key, layer), 16))
    mat = lambda *shape: _normal(next(keys), shape, dt)
    ones = lambda w: jnp.ones((w,), jnp.float32)

    def mlp(width, experts=None):
        lead = () if experts is None else (experts,)
        return {"w_gate": mat(*lead, d, width), "w_up": mat(*lead, d, width),
                "w_down": mat(*lead, width, d)}

    lp = {
        "attn_norm": ones(d), "mlp_norm": ones(d),
        "attn": {"w_q": mat(d, h_l * hd), "w_k": mat(d, kv),
                 "w_v": mat(d, kv), "w_g": mat(d, h_l),
                 "w_o": mat(h_l * hd, d)},
    }
    if cfg.mlp_layer_types[layer] == "dense":
        lp["mlp"] = mlp(cfg.intermediate_size)
    else:
        held = (cfg.num_experts if cfg.experts_held is None
                else len(cfg.experts_held))
        lp["moe"] = {
            "router": 0.02 * jax.random.normal(
                next(keys), (d, cfg.num_experts), jnp.float32),
            "bias": jnp.zeros((cfg.num_experts,), jnp.float32),
            **mlp(cfg.moe_intermediate_size, held),
            "shared": mlp(cfg.shared_expert_intermediate_size),
        }
    return lp


def init_params(cfg: LagunaConfig, key, dtype=None) -> dict:
    """A random parameter tree, made a layer at a time on the device."""
    dt = jnp.dtype(dtype or cfg.dtype)
    k_e, k_h = jax.random.split(jax.random.fold_in(key, 10_000))
    table = jax.jit(lambda k: _normal(
        k, (cfg.vocab_size, cfg.hidden_size), dt))
    layer = jax.jit(lambda k, i: init_layer(cfg, k, i, dt), static_argnums=1)
    return {
        "embed": table(k_e), "head": table(k_h),
        "final_norm": jnp.ones((cfg.hidden_size,), jnp.float32),
        "layers": [layer(key, i) for i in range(cfg.num_hidden_layers)],
    }


# -- the serving model -----------------------------------------------------------


class LagunaServeModel(ServeModel):
    """The family behind the engine's model interface: one chip, bf16 or
    f32, greedy / temperature / top-k. What would have to move, share or
    re-create a window layer's pages is not built and raises by name:
    tensor parallelism, int8 weights or cache, speculative steps, the host
    tier, fleet shipment, preemption; a registered prefix is found,
    counted and computed anew (``CacheLayout.prefix_shareable``)."""

    family = "laguna"
    skips_invalid_rows = True  # padding rows are routed to no expert
    # A chunk's next seat would need window pages beyond what a slot is
    # promised (the window pool is sized for one chunk a slot a step).
    keeps_pages_alone = False

    def __init__(self, cfg: LagunaConfig, *, attn_fn=None):
        # The reference engine: gather-dense attention;
        # ``with_decode_attention`` puts the grouped kernel in.
        from mpit_tpu.ops.decode_attention import (
            reference_grouped_paged_attention,
        )

        self.cfg = cfg
        self._attn_fn = attn_fn or reference_grouped_paged_attention

    def cache_layout(self) -> CacheLayout:
        cfg = self.cfg
        seats = (cfg.kv_width, cfg.kv_width)
        return CacheLayout(
            tuple(PageLayer(seats, 0 if kind == FULL else cfg.sliding_window)
                  for kind in cfg.layer_types), cfg.dtype)

    def kv_row_bytes(self, dtype) -> float:
        return self.cfg.kv_width * jnp.dtype(dtype).itemsize

    def rows_attended(self, cached):
        """A mean over the layers: a window layer reads its window."""
        kinds = self.cfg.layer_types
        full = kinds.count(FULL) / len(kinds)
        return (full * cached + (1 - full) * np.minimum(
            cached, self.cfg.sliding_window))

    def check_supported(self, *, tp, kv_dtype, weights_dtype, spec_k,
                        host_pages) -> None:
        lacks = [
            (tp, "tensor parallelism (tp_axis)"),
            (kv_dtype == "int8", "an int8 cache (kv_dtype='int8')"),
            (weights_dtype == "int8", "int8 weights (weights_dtype='int8')"),
            (bool(spec_k), "speculative decoding (spec_k): a verify step's "
             "rows would outrun the window pool's promise"),
            (bool(host_pages), "the host KV tier (kv_host_pages): a parked "
             "slot's window pages are not carried"),
        ]
        for lacking, what in lacks:
            if lacking:
                raise ValueError(
                    f"the laguna family does not have {what} yet: it "
                    "serves on one chip (ROADMAP.md B4)")

    def check_shipment(self) -> None:
        raise ValueError(
            "the laguna family's slots cannot be shipped between engines "
            "yet: export_kv_rows / inject_kv_rows move one table's pages, "
            "and a slot has a window table beside it (ROADMAP.md B4)")

    def check_preemption(self) -> None:
        raise ValueError(
            "a live slot of the laguna family cannot be evicted and resumed "
            "yet: its window layers' pages behind the window are gone, and "
            "a resume that re-prefills is not wired to the window pool "
            "(ROADMAP.md B4)")

    def with_decode_attention(self, *, block_k, interpret, page_size):
        del block_k, page_size  # the grouped kernel's tile is a page's
        from mpit_tpu.ops.decode_attention import grouped_paged_attention

        return LagunaServeModel(self.cfg, attn_fn=functools.partial(
            grouped_paged_attention, interpret=interpret))

    def attention_tiling(self, t_q, *, page_size, kv_dtype, tp=1):
        from mpit_tpu.ops.decode_attention import (
            grouped_block_k,
            grouped_rows,
        )

        del kv_dtype, tp
        return {"attention_form": "grouped",
                "attention_rows": grouped_block_k(page_size),
                "attention_query_rows": grouped_rows(t_q)}

    def head_table(self, params):
        return params["head"]

    def forward_paged(self, params, tokens, cache, block_tables, write_valid,
                      *, return_hidden, row_valid=None, slot_index=None):
        del slot_index  # no layer keeps a slot's state
        # Late: models sits below serve, and gpt2 owns the pool's writer.
        from mpit_tpu.models.gpt2 import paged_cache_update

        cfg = self.cfg
        b, t = tokens.shape
        lengths = cache.lengths
        pos = lengths[:, None] + jnp.arange(t)[None, :]
        # In a fixed order: the lowered text, and with it the compile
        # cache's key, must not turn on a set's iteration order.
        tables = {kind: rope_tables(cfg, kind, pos)
                  for kind in (FULL, SLIDING) if kind in cfg.layer_types}
        # A table a lifetime, side by side in the one array the engine
        # hands every step (serve/kvcache.py).
        pps = block_tables.shape[1] // 2
        bt = {FULL: block_tables[:, :pps], SLIDING: block_tables[:, pps:]}
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(jnp.float32)
        ks, vs, counts = [], [], []
        for i, (lp, kind) in enumerate(zip(params["layers"],
                                           cfg.layer_types)):
            with jax.named_scope("attn"), jax.named_scope(_kind_scope(kind)):
                ap = lp["attn"]
                u = rms_norm(x, lp["attn_norm"],
                             cfg.rms_norm_eps).astype(cfg.dtype)
                q, k, v = attention_project(ap, u, cfg, *tables[kind])
                with jax.named_scope("kv_write"):
                    k_pool = paged_cache_update(
                        cache.k[i], k.reshape(b, t, -1), lengths, bt[kind],
                        valid=write_valid)
                    v_pool = paged_cache_update(
                        cache.v[i], v.reshape(b, t, -1), lengths, bt[kind],
                        valid=write_valid)
                o = self._attn_fn(
                    q, k_pool, v_pool, lengths, bt[kind],
                    window=cfg.sliding_window if kind == SLIDING else 0)
                x = x + gate_and_project(ap, u, o)
            ks.append(k_pool)
            vs.append(v_pool)
            y, cnt = mlp_or_experts(lp, x, cfg, row_valid)
            x = x + y
            if cnt is not None:
                counts.append(cnt)
        with jax.named_scope("lm_head"):
            h = rms_norm(x, params["final_norm"],
                         cfg.rms_norm_eps).astype(cfg.dtype)
            if not return_hidden:
                h = jnp.einsum("btd,vd->btv", h, params["head"],
                               preferred_element_type=jnp.float32)
        aux = None
        if counts:
            counts = jnp.stack(counts)
            held = (jnp.arange(cfg.num_experts) if cfg.experts_held is None
                    else jnp.asarray(cfg.experts_held))
            aux = {
                "expert_tokens": counts,
                "moe_choices": jnp.sum(counts, dtype=jnp.float32),
                "moe_choices_here": jnp.sum(counts[:, held],
                                            dtype=jnp.float32),
            }
        return h, (tuple(ks), tuple(vs), cache.state), aux
