"""GLM-5.2 (``glm_moe_dsa``): latent attention that reads chosen rows only.

The serving model of the family of ``zai-org/GLM-5.2``'s public
``config.json``. A block is pre-norm, ``h = x + Attn(RMSNorm(x))``, ``y =
h + F(RMSNorm(h))``, ``F`` a gated MLP (the leading dense layers) or the
sigmoid-routed expert layer of ``parallel/moe_serve.py``. Attention is MLA
(the projections and the absorbed form are ``models/xing4.py``'s, shared by
import) under **learned sparse attention** (DeepSeek-V3.2's lightning
indexer, which ``glm_moe_dsa`` adopts; ``ops/dsa.py``): a layer whose
``indexer_types`` entry is ``full`` scores every cached position for the
query,

    q_I[t, j] = W_Iq c_q[t]   (j = 1..index_n_heads; first d_rope rotated)
    k_I[s]    = LayerNorm(W_Ik u[s])              (first d_rope rotated)
    w[t]      = W_Iw u[t] x index_n_heads^-0.5 x index_head_dim^-0.5
    I[t, s]   = sum_j w[t, j] relu(q_I[t, j] . k_I[s])      (float32)

and attends to the ``index_topk`` positions with the largest ``I[t, s]``
only (all of them while there are no more). A ``shared`` layer has no
indexer: it attends to the set the nearest ``full`` layer before it chose.

What a cached position is: the latent and the rotary key (two seats, as
xing4's) and, in a ``full`` layer, the index key in a third seat
(``models/serving.py::PageLayer``).

A decode tick scores one row a slot, chooses, turns the choice into row
numbers and attends in the absorbed form over those rows gathered from the
pool (``dsa.dsa_sparse_attn``); while every slot's context fits
``index_topk`` the choice is "every row" and the tick takes xing4's dense
latent kernel. A chunk of a prompt attends in the expanded form a tile at
a time with the choice as a mask, and the mask is what a ``full`` layer
hands the ``shared`` layers after it.

Rotary: ``rope_theta`` with no scaling, pairs ``(2i, 2i + 1)``
(``rope_interleave``; the indexer's too).

The parameter tree (``init_params`` makes one; the benchmark's and the
reference's names too)::

    embed [V, d]   head [V, d]   final_norm [d]
    layers[i]: attn_norm, mlp_norm [d]
               attn: w_dq, q_norm, w_uq, w_dkv, kv_norm, w_ukv, w_o
               indexer: wq_b, wk, k_norm_g, k_norm_b, w_proj   (a full layer)
               mlp: w_gate, w_up, w_down                 (a dense layer)
               moe: router, bias, w_gate, w_up, w_down [E_held, ..], shared
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mpit_tpu.models.serving import CacheLayout, PageLayer, ServeModel
from mpit_tpu.models.xing4 import (
    _dot,
    _normal,
    _w_ukv,
    mla_absorbed,
    mla_expanded_dense,
    mla_project,
    mlp_or_experts,
    rms_norm,
)
from mpit_tpu.ops import dsa
from mpit_tpu.ops import mla_attention as mla

__all__ = ["GlmDsaConfig", "GlmDsaServeModel", "init_params", "forward_plain"]

_LN_EPS = 1e-6  # the indexer's LayerNorm (DeepSeek-V3.2's)


@dataclasses.dataclass(frozen=True)
class GlmDsaConfig:
    vocab_size: int = 154880
    hidden_size: int = 6144
    num_hidden_layers: int = 78
    # One entry a layer, as the published lists are.
    mlp_layer_types: tuple = ("dense",) * 3 + ("sparse",) * 75
    indexer_types: tuple = ("full",) * 3 + ("shared", "shared", "shared",
                                             "full") * 18 + ("shared",) * 3
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256  # what the router routes over
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    rms_norm_eps: float = 1e-5
    rope_theta: float = 8e6
    max_seq_len: int = 1048576
    dtype: Any = jnp.bfloat16
    # The routed experts this chip holds, by global id, in the order of
    # the parameter tree's expert axis; None = every one.
    experts_held: tuple | None = None

    def __post_init__(self):
        n = self.num_hidden_layers
        if len(self.mlp_layer_types) != n or len(self.indexer_types) != n:
            raise ValueError(
                "mlp_layer_types and indexer_types have one entry a layer")
        if self.indexer_types[0] != "full":
            raise ValueError("the first layer has no layer to share with")

    @staticmethod
    def from_dict(d: dict, **overrides) -> "GlmDsaConfig":
        """From the keys of the published ``config.json``. A file cut to
        one chip's share of an expert-parallel deployment gives the
        experts HELD under ``n_routed_experts`` and the router's width
        under ``published``; the share is ``ep_rank``'s (0) block."""
        rp = d.get("rope_parameters") or {}
        kw = dict(
            max_seq_len=d.get("max_position_embeddings", 1048576),
            rope_theta=float(rp.get("rope_theta", d.get("rope_theta", 8e6))),
            mlp_layer_types=tuple(d["mlp_layer_types"]),
            indexer_types=tuple(d["indexer_types"]),
        )
        routed = (d.get("published") or {}).get("n_routed_experts")
        if routed and routed != d["n_routed_experts"]:
            here, rank = d["n_routed_experts"], d.get("ep_rank", 0)
            kw.update(n_routed_experts=routed, experts_held=tuple(
                range(rank * here, (rank + 1) * here)))
        names = {f.name for f in dataclasses.fields(GlmDsaConfig)}
        kw.update({k: v for k, v in d.items() if k in names and k not in kw})
        kw.update(overrides)
        return GlmDsaConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "GlmDsaConfig":
        """Test-sized: 1 dense + 4 expert layers in the published order
        of one indexer period, 8 experts top-2, a choice of 8 rows."""
        defaults = dict(
            vocab_size=512, hidden_size=64, num_hidden_layers=5,
            mlp_layer_types=("dense",) + ("sparse",) * 4,
            indexer_types=("full", "shared", "shared", "shared", "full"),
            intermediate_size=160, moe_intermediate_size=32,
            n_routed_experts=8, num_experts_per_tok=2,
            num_attention_heads=4, q_lora_rank=48, kv_lora_rank=128,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
            index_n_heads=4, index_head_dim=32, index_topk=8,
            rope_theta=10000.0, max_seq_len=256, dtype=jnp.float32,
        )
        defaults.update(kw)
        return GlmDsaConfig(**defaults)

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
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def serve_model(self) -> "GlmDsaServeModel":
        return GlmDsaServeModel(self)


# -- pieces --------------------------------------------------------------------


def rope_tables(cfg: GlmDsaConfig, positions):
    """``cos, sin`` [..., d_rope / 2] float32 at ``positions``."""
    dim = cfg.qk_rope_head_dim
    inv = 1.0 / cfg.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64)
                                   / dim)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv.astype(np.float32))
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope_interleaved(x, cos, sin):
    """Rotate ``x`` [..., d_rope]: the pair of frequency ``i`` is
    ``(x[2i], x[2i + 1])``. ``cos`` / ``sin`` broadcast against
    ``x[..., 0::2]``."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def index_project(ip, h, c_q, cfg: GlmDsaConfig, cos, sin):
    """The indexer's query rows, key and head weights from the layer's
    normalised input ``h`` [B, T, d] and the query latent ``c_q``:
    ``q_I`` [B, T, Hi, Di], ``k_I`` [B, T, Di], ``w`` [B, T, Hi] float32."""
    hi, di, dr = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = _dot(c_q, ip["wq_b"]).reshape(*h.shape[:-1], hi, di)
    q = jnp.concatenate([
        apply_rope_interleaved(q[..., :dr], cos[..., None, :],
                               sin[..., None, :]), q[..., dr:]], axis=-1)
    k = _dot(h, ip["wk"], jnp.float32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * lax.rsqrt(jnp.mean(jnp.square(k), axis=-1, keepdims=True)
                      + _LN_EPS)
    k = (k * ip["k_norm_g"] + ip["k_norm_b"]).astype(h.dtype)
    k = jnp.concatenate([
        apply_rope_interleaved(k[..., :dr], cos, sin), k[..., dr:]], axis=-1)
    w = _dot(h, ip["w_proj"], jnp.float32) * (hi ** -0.5 * di ** -0.5)
    return q, k, w


def _index_scores_dense(q, k, w):
    """``I`` [B, T, T] of whole sequences, nothing cached, causal."""
    logit = jnp.einsum("bthd,bkd->bthk", q, k,
                       preferred_element_type=jnp.float32)
    sc = jnp.einsum("bthk,bth->btk", jnp.maximum(logit, 0.0), w,
                    precision=lax.Precision.HIGHEST)
    t = sc.shape[-1]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), sc, -jnp.inf)


def forward_plain(params, tokens, cfg: GlmDsaConfig, *, with_sets=False):
    """Logits ``[B, T, V]`` float32 of whole sequences through the
    program's own layers with no cache: what the paged path must equal.
    ``with_sets`` also returns each layer's choice, ``[B, T, T]`` bool."""
    b, t = tokens.shape
    cos, sin = rope_tables(cfg, jnp.broadcast_to(jnp.arange(t), (b, t)))
    x = params["embed"][tokens].astype(jnp.float32)
    chosen, sets = None, []
    for lp, kind in zip(params["layers"], cfg.indexer_types):
        ap = lp["attn"]
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
        qn, qr, c_kv, k_rope, c_q = mla_project(
            ap, h, cfg, cos, sin, rope=apply_rope_interleaved, with_cq=True)
        if kind == "full":
            chosen = dsa.dsa_select(_index_scores_dense(
                *index_project(lp["indexer"], h, c_q, cfg, cos, sin)),
                cfg.index_topk)
        sets.append(chosen)
        o = mla_expanded_dense(ap, qn, qr, c_kv, k_rope, cfg, select=chosen)
        x = x + _dot(o, ap["w_o"], jnp.float32)
        x = x + mlp_or_experts(lp, x, cfg, None)[0]
    h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
    logits = jnp.einsum("btd,vd->btv", h, params["head"],
                        preferred_element_type=jnp.float32)
    return (logits, sets) if with_sets else logits


# -- parameters ------------------------------------------------------------------


def init_layer(cfg: GlmDsaConfig, key, layer: int, dtype=None) -> dict:
    """One layer's parameters from ``fold_in(key, layer)``: normal(0.02)
    matrices, unit norm gains but those of the q and kv latents (drawn
    round 2.5, so that attention logits spread: near-uniform attention
    would hide whether a choice was applied), a zero selection bias."""
    dt = jnp.dtype(dtype or cfg.dtype)
    d = cfg.hidden_size
    hn, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim, cfg.v_head_dim)
    keys = iter(jax.random.split(jax.random.fold_in(key, layer), 24))
    mat = lambda *shape: _normal(next(keys), shape, dt)
    ones = lambda w: jnp.ones((w,), jnp.float32)
    gain = lambda w: jnp.abs(
        2.5 + 0.25 * jax.random.normal(next(keys), (w,), jnp.float32))

    def mlp(width, experts=None):
        lead = () if experts is None else (experts,)
        return {"w_gate": mat(*lead, d, width), "w_up": mat(*lead, d, width),
                "w_down": mat(*lead, width, d)}

    lp = {
        "attn_norm": ones(d), "mlp_norm": ones(d),
        "attn": {
            "w_dq": mat(d, cfg.q_lora_rank), "q_norm": gain(cfg.q_lora_rank),
            "w_uq": mat(cfg.q_lora_rank, hn * (dn + dr)),
            "w_dkv": mat(d, cfg.kv_lora_rank + dr),
            "kv_norm": gain(cfg.kv_lora_rank),
            "w_ukv": mat(cfg.kv_lora_rank, hn * (dn + dv)),
            "w_o": mat(hn * dv, d),
        },
    }
    if cfg.indexer_types[layer] == "full":
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        lp["indexer"] = {
            "wq_b": mat(cfg.q_lora_rank, hi * di), "wk": mat(d, di),
            "k_norm_g": ones(di), "k_norm_b": jnp.zeros((di,), jnp.float32),
            "w_proj": mat(d, hi),
        }
    if cfg.mlp_layer_types[layer] == "dense":
        lp["mlp"] = mlp(cfg.intermediate_size)
    else:
        held = (cfg.n_routed_experts if cfg.experts_held is None
                else len(cfg.experts_held))
        lp["moe"] = {
            "router": 0.02 * jax.random.normal(
                next(keys), (d, cfg.n_routed_experts), jnp.float32),
            "bias": jnp.zeros((cfg.n_routed_experts,), jnp.float32),
            **mlp(cfg.moe_intermediate_size, held),
        }
        if cfg.n_shared_experts:
            lp["moe"]["shared"] = mlp(
                cfg.moe_intermediate_size * cfg.n_shared_experts)
    return lp


def init_params(cfg: GlmDsaConfig, key, dtype=None) -> dict:
    """A random parameter tree, made a layer at a time on the device."""
    dt = jnp.dtype(dtype or cfg.dtype)
    k_e, k_h = jax.random.split(jax.random.fold_in(key, 10_000))
    table = jax.jit(lambda k: _normal(
        k, (cfg.vocab_size, cfg.hidden_size), dt))
    layer = jax.jit(lambda k, i: init_layer(cfg, k, i, dt),
                    static_argnums=1)
    return {
        "embed": table(k_e), "head": table(k_h),
        "final_norm": jnp.ones((cfg.hidden_size,), jnp.float32),
        "layers": [layer(key, i) for i in range(cfg.num_hidden_layers)],
    }


# -- the serving model -----------------------------------------------------------


class GlmDsaServeModel(ServeModel):
    """The family behind the engine's model interface: one chip, bf16 or
    f32, greedy / temperature / top-k. Tensor parallelism, int8 weights
    or cache, speculative steps, the host tier, preemption and fleet
    shipment are not built for it and raise."""

    family = "glm_dsa"
    skips_invalid_rows = True

    def __init__(self, cfg: GlmDsaConfig, *, kernel: bool = False,
                 interpret=None):
        self.cfg = cfg
        self._kernel, self._interpret = kernel, interpret
        self.decode_block_k = None  # the dense kernel's own choice

    def cache_layout(self) -> CacheLayout:
        # The latent, the rotary key in a lane tile of its own, and in a
        # layer that runs an indexer the index key in a third seat.
        cfg = self.cfg
        two = (cfg.kv_lora_rank, mla.lane_pad(cfg.qk_rope_head_dim))
        three = two + (mla.lane_pad(cfg.index_head_dim),)
        return CacheLayout(
            tuple(PageLayer(three if kind == "full" else two)
                  for kind in cfg.indexer_types), cfg.dtype)

    def kv_row_bytes(self, dtype) -> float:
        row = self.cache_layout().layers[-1]
        return (row.k_width + row.v_width) / 2 * jnp.dtype(dtype).itemsize

    def rows_attended(self, cached):
        return np.minimum(cached, self.cfg.index_topk)

    def check_supported(self, *, tp, kv_dtype, weights_dtype, spec_k,
                        host_pages) -> None:
        lacks = [
            (tp, "tensor parallelism (tp_axis)"),
            (kv_dtype == "int8", "an int8 cache (kv_dtype='int8')"),
            (weights_dtype == "int8", "int8 weights (weights_dtype='int8')"),
            (bool(spec_k), "speculative decoding (spec_k)"),
            (bool(host_pages), "the host KV tier (kv_host_pages)"),
        ]
        for lacking, what in lacks:
            if lacking:
                raise ValueError(
                    f"the glm_dsa family does not have {what} yet: it "
                    "serves on one chip (ROADMAP.md B1)")

    def check_shipment(self) -> None:
        raise ValueError(
            "the glm_dsa family's cache rows (latent, rotary key, index "
            "key) cannot be shipped between engines yet (export_kv_rows / "
            "inject_kv_rows; ROADMAP.md B1)")

    def check_preemption(self) -> None:
        raise ValueError(
            "a live slot of the glm_dsa family cannot be evicted and "
            "resumed yet: the host tier that would park its pages does not "
            "carry a third seat (ROADMAP.md B4)")

    def with_decode_attention(self, *, block_k, interpret, page_size):
        del block_k
        model = GlmDsaServeModel(self.cfg, kernel=True, interpret=interpret)
        model.decode_block_k = mla.pick_mla_block_k(page_size)
        return model

    def attention_tiling(self, t_q, *, page_size, kv_dtype, tp=1):
        del kv_dtype, tp
        return mla.latent_attention_tiling(
            t_q, page_size, self.cfg.qk_nope_head_dim,
            self.cfg.qk_rope_head_dim)

    def head_table(self, params):
        return params["head"]

    def _attend_chunk(self, *args, **kw):
        if self._kernel:
            return mla.mla_paged_prefill_attention(
                *args, interpret=self._interpret, **kw)
        return mla.reference_mla_paged_prefill_attention(*args, **kw)

    def _scores(self, q, w, key_pool, lengths, block_tables):
        if self._kernel:
            return dsa.dsa_index_scores(q, w, key_pool, lengths,
                                        block_tables,
                                        interpret=self._interpret)
        return dsa.reference_dsa_index_scores(q, w, key_pool, lengths,
                                              block_tables)

    def _attend_decode(self, ckv_pool, kr_pool, lengths, block_tables,
                       rows, n):
        """A tick's attention: over the chosen rows, or, while every
        slot's rows fit the choice, xing4's dense latent kernel."""
        cfg = self.cfg
        scale = cfg.softmax_scale

        def dense(qa, qr):
            if self._kernel:
                return mla.mla_paged_decode_attention(
                    qa, qr, ckv_pool, kr_pool, lengths, block_tables,
                    scale=scale, block_k=self.decode_block_k,
                    interpret=self._interpret)
            return mla.reference_mla_paged_decode_attention(
                qa, qr, ckv_pool, kr_pool, lengths, block_tables,
                scale=scale)

        def sparse(qa, qr):
            return dsa.dsa_sparse_attn(qa, qr, ckv_pool, kr_pool, rows, n,
                                       block_tables, scale=scale)

        return lambda qa, qr: lax.cond(
            jnp.max(lengths) < cfg.index_topk, dense, sparse, qa, qr)

    def forward_paged(self, params, tokens, cache, block_tables, write_valid,
                      *, return_hidden, row_valid=None, slot_index=None):
        del slot_index  # no layer keeps a slot's state
        # Late: models sits below serve, and gpt2 owns the pool's writer.
        from mpit_tpu.models.gpt2 import paged_cache_update

        cfg = self.cfg
        b, t = tokens.shape
        lengths = cache.lengths
        cos, sin = rope_tables(
            cfg, lengths[:, None] + jnp.arange(t)[None, :])
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(jnp.float32)
        pad_r = mla.lane_pad(cfg.qk_rope_head_dim) - cfg.qk_rope_head_dim
        pad_i = mla.lane_pad(cfg.index_head_dim) - cfg.index_head_dim
        write = lambda pool, rows: paged_cache_update(
            pool, rows, lengths, block_tables, valid=write_valid)
        live = (jnp.ones((b, t), bool) if row_valid is None else row_valid)
        ks, vs, xs, counts = [], [], [], []
        chosen = None  # a tick: (rows [B, K], n [B]); a chunk: [B, T, S]
        picked = jnp.zeros((), jnp.float32)  # rows attention read, a layer
        read = jnp.zeros((), jnp.float32)
        for i, (lp, kind) in enumerate(zip(params["layers"],
                                           cfg.indexer_types)):
            with jax.named_scope("attn"):
                ap = lp["attn"]
                h = rms_norm(x, lp["attn_norm"],
                             cfg.rms_norm_eps).astype(cfg.dtype)
                qn, qr, c_kv, k_rope, c_q = mla_project(
                    ap, h, cfg, cos, sin, rope=apply_rope_interleaved,
                    with_cq=True)
                with jax.named_scope("kv_write"):
                    ckv_pool = write(cache.k[i], c_kv)
                    kr_pool = write(cache.v[i], jnp.pad(
                        k_rope, ((0, 0), (0, 0), (0, pad_r))))
                key_pool = None
                if kind == "full":
                    with jax.named_scope("dsa_index"):
                        q_i, k_i, w_i = index_project(
                            lp["indexer"], h, c_q, cfg, cos, sin)
                        key_pool = write(cache.x[i], jnp.pad(
                            k_i, ((0, 0), (0, 0), (0, pad_i))))
                        scores = self._scores(
                            jnp.pad(q_i, ((0, 0),) * 3 + ((0, pad_i),)),
                            w_i, key_pool, lengths, block_tables)
                    mask = dsa.dsa_select(scores, cfg.index_topk)
                    with jax.named_scope("dsa_select"):
                        picked = jnp.sum(jnp.where(
                            live, jnp.sum(mask, axis=-1), 0), dtype=jnp.float32)
                    chosen = (dsa.mask_to_rows(mask[:, 0], cfg.index_topk)
                              if t == 1 else mask)
                read = read + picked
                if t == 1:
                    o = mla_absorbed(
                        ap, qn[:, 0], qr[:, 0],
                        self._attend_decode(ckv_pool, kr_pool, lengths,
                                            block_tables, *chosen),
                        cfg)[:, None]
                else:
                    o = self._attend_chunk(
                        qn, qr, ckv_pool, kr_pool, lengths, block_tables,
                        _w_ukv(ap, cfg), scale=cfg.softmax_scale,
                        select=chosen).reshape(b, t, -1)
                x = x + _dot(o, ap["w_o"], jnp.float32)
            ks.append(ckv_pool)
            vs.append(kr_pool)
            xs.append(key_pool)
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
        held = (jnp.arange(cfg.n_routed_experts) if cfg.experts_held is None
                else jnp.asarray(cfg.experts_held))
        cached = jnp.sum(jnp.where(
            live, lengths[:, None] + 1 + jnp.arange(t)[None, :], 0),
            dtype=jnp.float32) * cfg.num_hidden_layers
        aux = {"dsa_rows_read": read, "dsa_rows_cached": cached}
        if counts:
            counts = jnp.stack(counts)
            aux.update(
                expert_tokens=counts,
                moe_choices=jnp.sum(counts, dtype=jnp.float32),
                moe_choices_here=jnp.sum(counts[:, held], dtype=jnp.float32))
        return h, (tuple(ks), tuple(vs), cache.state, tuple(xs)), aux
