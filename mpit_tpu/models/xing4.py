"""Xing4.0: latent attention, sigmoid-routed experts, hyper-connected streams.

The serving model of the ``xing4_0`` family (public ``config.json`` of
``XingChen-AGI/Xing4.0-29B-A4B``; its key names are DeepSeek-V3's, whose
modeling code fixes the conventions, and the ``hc_*`` / ``mhc_*`` keys are
the manifold-constrained hyper-connections of DeepSeek's mHC paper).
Pure functions over a parameter tree; :class:`Xing4ServeModel` puts them
behind :class:`~mpit_tpu.models.serving.ServeModel` for the engine.

A layer is two sublayers ``F`` (attention, then a gated MLP or an expert
layer), each wrapped in the hyper-connection mix over ``n = hc_mult``
residual streams ``X`` [n, d]::

    x'     = RMSNorm(vec(X))                      (no gain, eps hc_eps)
    H_pre  = sigmoid(a_pre  x' phi_pre  + b_pre)            [n]
    H_post = 2 sigmoid(a_post x' phi_post + b_post)         [n]
    H_res  = Sinkhorn(clip(a_res mat(x' phi_res) + b_res))  [n, n]
    X     <- H_res X + H_post^T F(H_pre X)

Attention is MLA (``ops/mla_attention.py``): a cached position is the
normalised latent ``c_kv`` and the rotated ``k_rope``, one row all heads
share; a chunk of a prompt attends in the expanded form, a decode tick in
the absorbed one. Experts are ``parallel/moe_serve.py``. What the layer
counts, depth and experts are is read from the configuration.

The parameter tree (``init_params`` makes one; the names are the
benchmark's and the reference's too)::

    embed [V, d]   head [V, d]   final_norm [d]
    layers[i]: attn_norm, mlp_norm [d]
               hc_attn, hc_mlp: phi [n d, 2n + n n], a [3], b [2n + n n]
               attn: w_dq, q_norm, w_uq, w_dkv, kv_norm, w_ukv, w_o
               mlp: w_gate, w_up, w_down                (a leading dense layer)
               moe: router, bias, w_gate, w_up, w_down [E, ..], shared{...}
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mpit_tpu.models.serving import CacheLayout, PageLayer, ServeModel
from mpit_tpu.ops import mla_attention as mla
from mpit_tpu.parallel.moe_serve import expert_layer, gated_mlp

__all__ = ["Xing4Config", "Xing4ServeModel", "init_params", "forward_plain"]

_HI = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    vocab_size: int = 131072
    hidden_size: int = 3584
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    # The routed experts this chip holds, by global id, in the order of
    # the parameter tree's expert axis; None = every one.
    experts_held: tuple | None = None

    @staticmethod
    def from_dict(d: dict, **overrides) -> "Xing4Config":
        """From the keys of the published ``config.json``."""
        rs = d.get("rope_scaling") or {}
        kw = dict(
            max_seq_len=d.get("max_position_embeddings", 262144),
            rope_theta=float(d.get("rope_theta", 10000.0)),
            rope_factor=float(rs.get("factor", 1.0)),
            rope_original_max=int(rs.get(
                "original_max_position_embeddings",
                d.get("max_position_embeddings", 4096))),
            rope_beta_fast=float(rs.get("beta_fast", 32)),
            rope_beta_slow=float(rs.get("beta_slow", 1)),
            rope_mscale=float(rs.get("mscale", 1)),
            rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0)),
            hc_clamp_min=float(d.get("mhc_h_res_clamp_min", -30)),
            hc_clamp_max=float(d.get("mhc_h_res_clamp_max", 30)),
        )
        names = {f.name for f in dataclasses.fields(Xing4Config)}
        kw.update({k: v for k, v in d.items() if k in names and k not in kw})
        kw.update(overrides)
        return Xing4Config(**kw)

    @staticmethod
    def tiny(**kw) -> "Xing4Config":
        """Test-sized: 1 dense + 2 expert layers, 8 experts top-2, 2
        streams, every mechanism on."""
        defaults = dict(
            vocab_size=512, hidden_size=64, num_hidden_layers=3,
            first_k_dense_replace=1, intermediate_size=160,
            moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=2, num_attention_heads=4, q_lora_rank=48,
            kv_lora_rank=128, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, rope_factor=4.0, rope_original_max=32,
            hc_mult=2, max_seq_len=256, dtype=jnp.float32,
        )
        defaults.update(kw)
        return Xing4Config(**defaults)

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
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        """``d_qk^-0.5 m^2``, ``m`` YaRN's attention factor over all
        dimensions (DeepSeek-V3's ``yarn_get_mscale``)."""
        d = self.qk_nope_head_dim + self.qk_rope_head_dim
        m = 1.0
        if self.rope_factor > 1 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1
        return d ** -0.5 * m * m

    def serve_model(self) -> "Xing4ServeModel":
        return Xing4ServeModel(self)


# -- pieces --------------------------------------------------------------------


def rms_norm(x, gain, eps):
    """RMSNorm with float32 statistics; ``gain`` None = none."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    if gain is not None:
        y = y * gain.astype(jnp.float32)
    return y.astype(x.dtype)


def yarn_inv_freq(cfg: Xing4Config) -> np.ndarray:
    """Rotary frequencies ``[d_rope / 2]``: DeepSeek's YaRN blend of the
    interpolated (``/ factor``) and the extrapolated frequencies, by a
    linear ramp between the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times over the original context."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return extra.astype(np.float32)

    def turns_dim(turns):
        return dim * math.log(cfg.rope_original_max / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(turns_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(turns_dim(cfg.rope_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp  # 1 where the extrapolated frequency stays
    return (extra / cfg.rope_factor * (1 - keep) + extra * keep).astype(
        np.float32)


def rope_tables(cfg: Xing4Config, positions):
    """``cos, sin`` [..., d_rope / 2] float32 at ``positions``. The cosine
    and sine carry no factor: ``mscale / mscale_all_dim`` is 1 here."""
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        yarn_inv_freq(cfg))
    ratio = 1.0
    if cfg.rope_factor > 1 and cfg.rope_mscale_all_dim:
        get = lambda m: 0.1 * m * math.log(cfg.rope_factor) + 1 if m else 1.0
        ratio = get(cfg.rope_mscale) / get(cfg.rope_mscale_all_dim)
    return jnp.cos(ang) * ratio, jnp.sin(ang) * ratio


def apply_rope(x, cos, sin):
    """Rotate ``x`` [..., d_rope]: the pair of dimension ``i`` is
    ``(x[i], x[i + d/2])`` (half-split; the reference's layout too).
    ``cos`` / ``sin`` broadcast against ``x[..., : d/2]``."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def sinkhorn(m, iters: int, eps: float):
    """``exp(m)`` [.., n, n] with its columns, then its rows, normalised
    ``iters`` times: all but doubly stochastic."""
    m = jnp.exp(m)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def hc_coefficients(hp, x_streams, cfg: Xing4Config):
    """``H_pre`` [.., n], ``H_post`` [.., n], ``H_res`` [.., n, n] (float32)
    from the streams ``x_streams`` [.., n, d]."""
    n = cfg.hc_mult
    flat = x_streams.reshape(*x_streams.shape[:-2], -1).astype(jnp.float32)
    flat = rms_norm(flat, None, cfg.hc_eps)
    h = jnp.dot(flat, hp["phi"].astype(jnp.float32), precision=_HI)
    a, b = hp["a"].astype(jnp.float32), hp["b"].astype(jnp.float32)
    pre = jax.nn.sigmoid(a[0] * h[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * h[..., n:2 * n] + b[n:2 * n])
    res = (a[2] * h[..., 2 * n:] + b[2 * n:]).reshape(*h.shape[:-1], n, n)
    res = sinkhorn(jnp.clip(res, cfg.hc_clamp_min, cfg.hc_clamp_max),
                   cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return pre, post, res


def hc_sublayer(hp, x_streams, cfg: Xing4Config, f):
    """``X <- H_res X + H_post^T f(H_pre X)``; ``f`` maps ``[.., d]``
    float32 to ``(y [.., d], extra)``. The streams, the sublayer's input
    and its result stay float32: the matrix products inside ``f`` take
    the model's dtype."""
    with jax.named_scope("hc_mix"):
        pre, post, res = hc_coefficients(hp, x_streams, cfg)
        u = jnp.sum(pre[..., None] * x_streams, axis=-2)
    y, extra = f(u)
    with jax.named_scope("hc_mix"):
        mixed = jnp.einsum("...ij,...jd->...id", res, x_streams,
                           precision=_HI)
        out = mixed + post[..., None] * y.astype(jnp.float32)[..., None, :]
    return out, extra


def _dot(x, w, out_dtype=None):
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(
        out_dtype or x.dtype)


def mla_project(ap, h, cfg, cos, sin, *, rope=None, with_cq: bool = False):
    """Queries and the row to cache from ``h`` [B, T, d] (normalised):
    ``q_nope`` [B, T, H, dn], ``q_rope`` [B, T, H, dr] (rotated), ``c_kv``
    [B, T, C] (normalised), ``k_rope`` [B, T, dr] (rotated). ``cfg`` is
    any configuration with the latent-attention keys (``models/glm_dsa.py``
    shares this); ``rope`` rotates in another pair layout than
    :func:`apply_rope`'s, and ``with_cq`` appends the normalised query
    latent ``c_q`` [B, T, q_lora_rank] (an indexer projects from it)."""
    rope = rope or apply_rope
    hn, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                  cfg.qk_rope_head_dim)
    c_q = rms_norm(_dot(h, ap["w_dq"]), ap["q_norm"], cfg.rms_norm_eps)
    q = _dot(c_q, ap["w_uq"]).reshape(*h.shape[:-1], hn, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    kv = _dot(h, ap["w_dkv"])
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], ap["kv_norm"],
                    cfg.rms_norm_eps)
    k_rope = rope(kv[..., cfg.kv_lora_rank:], cos, sin)
    q_rope = rope(q_rope, cos[..., None, :], sin[..., None, :])
    if with_cq:
        return q_nope, q_rope, c_kv, k_rope, c_q
    return q_nope, q_rope, c_kv, k_rope


def _w_ukv(ap, cfg):
    return ap["w_ukv"].reshape(
        cfg.kv_lora_rank, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.v_head_dim)


def mla_absorbed(ap, q_nope, q_rope, attend, cfg):
    """Attention with ``W_UK`` folded into the query and ``W_UV`` into the
    output: ``attend(q_abs [B, H, C], q_rope [B, H, dr]) -> [B, H, C]``
    (the weighted latents). One query position a slot."""
    w = _w_ukv(ap, cfg)
    dn = cfg.qk_nope_head_dim
    with jax.named_scope("mla_absorb"):
        q_abs = jnp.einsum("bhd,chd->bhc", q_nope, w[..., :dn],
                           preferred_element_type=jnp.float32
                           ).astype(q_nope.dtype)
    lat = attend(q_abs, q_rope)
    with jax.named_scope("mla_absorb"):
        o = jnp.einsum("bhc,chd->bhd", lat, w[..., dn:],
                       preferred_element_type=jnp.float32).astype(lat.dtype)
    return o.reshape(o.shape[0], -1)


def mla_expanded_dense(ap, q_nope, q_rope, c_kv, k_rope, cfg, select=None):
    """Causal expanded attention of whole sequences, nothing cached:
    ``[B, T, H * dv]``. The plain forward's, for tests. ``select``
    [B, T, T] bool: the positions a query may attend to beside being
    causal (a sparse-attention layer's choice)."""
    dn = cfg.qk_nope_head_dim
    kv = jnp.einsum("bkc,chd->bkhd", c_kv, _w_ukv(ap, cfg),
                    preferred_element_type=jnp.float32).astype(c_kv.dtype)
    s = jnp.einsum("bthd,bkhd->bhtk", q_nope, kv[..., :dn],
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bthd,bkd->bhtk", q_rope, k_rope,
                       preferred_element_type=jnp.float32)
    t = s.shape[-1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    if select is not None:
        causal = causal & select[:, None]
    p = jax.nn.softmax(jnp.where(causal, s * cfg.softmax_scale, -jnp.inf), -1)
    o = jnp.einsum("bhtk,bkhd->bthd", p.astype(kv.dtype), kv[..., dn:],
                   preferred_element_type=jnp.float32).astype(c_kv.dtype)
    return o.reshape(*o.shape[:2], -1)


def mlp_or_experts(lp, u, cfg, valid):
    """The layer's second sublayer on ``u`` [B, T, d]: the gated MLP of a
    leading dense layer, else the expert layer. Returns ``(y, counts)``,
    ``counts`` [E] int32 or None."""
    h = rms_norm(u, lp["mlp_norm"], cfg.rms_norm_eps)  # float32, as u is
    flat = h.reshape(-1, h.shape[-1])
    if "mlp" in lp:
        with jax.named_scope("mlp"):
            return gated_mlp(flat.astype(cfg.dtype), **lp["mlp"],
                             out_dtype=jnp.float32).reshape(h.shape), None
    y, counts = expert_layer(
        flat.astype(cfg.dtype), lp["moe"], top_k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, n_experts=cfg.n_routed_experts,
        held=cfg.experts_held, normalise=cfg.norm_topk_prob,
        valid=None if valid is None else valid.reshape(-1),
        router_input=flat, out_dtype=jnp.float32)
    return y.reshape(h.shape), counts


def _embed(params, tokens, cfg: Xing4Config):
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.float32)
        return jnp.broadcast_to(
            x[..., None, :], (*x.shape[:-1], cfg.hc_mult, x.shape[-1]))


def _final(params, x_streams, cfg: Xing4Config):
    with jax.named_scope("lm_head"):
        return rms_norm(jnp.sum(x_streams, axis=-2), params["final_norm"],
                        cfg.rms_norm_eps).astype(cfg.dtype)


def forward_plain(params, tokens, cfg: Xing4Config):
    """Logits ``[B, T, V]`` float32 of whole sequences through the
    program's own layers with no cache: what the paged path must equal."""
    b, t = tokens.shape
    cos, sin = rope_tables(cfg, jnp.broadcast_to(jnp.arange(t), (b, t)))
    xs = _embed(params, tokens, cfg)
    for lp in params["layers"]:
        def attn(u, lp=lp):
            h = rms_norm(u, lp["attn_norm"], cfg.rms_norm_eps).astype(
                cfg.dtype)
            qn, qr, c_kv, k_rope = mla_project(lp["attn"], h, cfg, cos, sin)
            o = mla_expanded_dense(lp["attn"], qn, qr, c_kv, k_rope, cfg)
            return _dot(o, lp["attn"]["w_o"], jnp.float32), None

        xs, _ = hc_sublayer(lp["hc_attn"], xs, cfg, attn)
        xs, _ = hc_sublayer(lp["hc_mlp"], xs, cfg,
                            lambda u, lp=lp: mlp_or_experts(lp, u, cfg, None))
    h = _final(params, xs, cfg)
    return jnp.einsum("btd,vd->btv", h, params["head"],
                      preferred_element_type=jnp.float32)


# -- parameters ------------------------------------------------------------------


def _normal(key, shape, dtype, blocks: int = 16):
    """``0.02 x normal(shape)`` as ``dtype``, drawn in float32 a block of
    the leading axis at a time: the float32 draft of a whole vocabulary
    table (1.88 GB at the published sizes) would stay reserved on the
    device by the program that made it."""
    lead = shape[0]
    n = lead if len(shape) == 3 else (blocks if lead % blocks == 0 else 1)
    part = (lead // n, *shape[1:])
    draw = lambda k: (0.02 * jax.random.normal(k, part, jnp.float32)).astype(
        dtype)
    return lax.map(draw, jax.random.split(key, n)).reshape(shape)


def init_layer(cfg: Xing4Config, key, layer: int, dtype=None) -> dict:
    """One layer's parameters from ``fold_in(key, layer)``: normal(0.02)
    matrices, unit norm gains, a selection bias normal(0.01), and the
    hyper-connection such that ``H_res`` starts near the identity."""
    dt = jnp.dtype(dtype or cfg.dtype)
    d, n = cfg.hidden_size, cfg.hc_mult
    hn, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim, cfg.v_head_dim)
    keys = iter(jax.random.split(jax.random.fold_in(key, layer), 24))
    mat = lambda *shape: _normal(next(keys), shape, dt)
    ones = lambda w: jnp.ones((w,), jnp.float32)

    def hc():
        b_res = 8.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1)
        return {
            "phi": 0.02 * jax.random.normal(
                next(keys), (n * d, 2 * n + n * n), jnp.float32),
            "a": jnp.full((3,), 0.01, jnp.float32),
            "b": jnp.concatenate([jnp.zeros((2 * n,), jnp.float32), b_res]),
        }

    def mlp(width, experts=None):
        lead = () if experts is None else (experts,)
        return {"w_gate": mat(*lead, d, width), "w_up": mat(*lead, d, width),
                "w_down": mat(*lead, width, d)}

    lp = {
        "attn_norm": ones(d), "mlp_norm": ones(d),
        "hc_attn": hc(), "hc_mlp": hc(),
        "attn": {
            "w_dq": mat(d, cfg.q_lora_rank), "q_norm": ones(cfg.q_lora_rank),
            "w_uq": mat(cfg.q_lora_rank, hn * (dn + dr)),
            "w_dkv": mat(d, cfg.kv_lora_rank + dr),
            "kv_norm": ones(cfg.kv_lora_rank),
            "w_ukv": mat(cfg.kv_lora_rank, hn * (dn + dv)),
            "w_o": mat(hn * dv, d),
        },
    }
    if layer < cfg.first_k_dense_replace:
        lp["mlp"] = mlp(cfg.intermediate_size)
    else:
        held = (cfg.n_routed_experts if cfg.experts_held is None
                else len(cfg.experts_held))
        lp["moe"] = {
            "router": 0.02 * jax.random.normal(
                next(keys), (d, cfg.n_routed_experts), jnp.float32),
            "bias": 0.01 * jax.random.normal(
                next(keys), (cfg.n_routed_experts,), jnp.float32),
            **mlp(cfg.moe_intermediate_size, held),
        }
        if cfg.n_shared_experts:
            lp["moe"]["shared"] = mlp(
                cfg.moe_intermediate_size * cfg.n_shared_experts)
    return lp


def init_params(cfg: Xing4Config, key, dtype=None) -> dict:
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


class Xing4ServeModel(ServeModel):
    """The family behind the engine's model interface: one chip, bf16 or
    f32, greedy / temperature / top-k. Tensor parallelism, int8 weights
    or cache, speculative steps, the host tier and fleet shipment are not
    built for it and raise."""

    family = "xing4"
    skips_invalid_rows = True

    def __init__(self, cfg: Xing4Config, *, kernel: bool = False,
                 interpret=None):
        self.cfg = cfg
        self._kernel, self._interpret = kernel, interpret
        self.decode_block_k = None  # the kernel's own choice

    def cache_layout(self) -> CacheLayout:
        # The latent and, in a buffer of its own padded to whole lane
        # tiles, the key's rotary part: 512 + 128 values a position.
        cfg = self.cfg
        row = PageLayer(
            (cfg.kv_lora_rank, mla.lane_pad(cfg.qk_rope_head_dim)))
        return CacheLayout((row,) * cfg.num_hidden_layers, cfg.dtype)

    def kv_row_bytes(self, dtype) -> float:
        row = self.cache_layout().layers[0]
        return (row.k_width + row.v_width) / 2 * jnp.dtype(dtype).itemsize

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
                    f"the xing4 family does not have {what} yet: it serves "
                    "on one chip (ROADMAP.md B1)")

    def check_shipment(self) -> None:
        raise ValueError(
            "the xing4 family's latent cache rows cannot be shipped between "
            "engines yet (export_kv_rows / inject_kv_rows; ROADMAP.md B1)")

    def with_decode_attention(self, *, block_k, interpret, page_size):
        del block_k
        # The engine's tile (GPT-2's: 64 positions of a 256-position
        # page) would make 80 KB DMAs of the latent rows: the kernel
        # takes whole pages up to 512 positions, and says so.
        model = Xing4ServeModel(self.cfg, kernel=True, interpret=interpret)
        model.decode_block_k = mla.pick_mla_block_k(page_size)
        return model

    def attention_tiling(self, t_q, *, page_size, kv_dtype, tp=1):
        del kv_dtype, tp
        return mla.latent_attention_tiling(
            t_q, page_size, self.cfg.qk_nope_head_dim,
            self.cfg.qk_rope_head_dim)

    def head_table(self, params):
        return params["head"]

    def _attend_decode(self, ckv_pool, kr_pool, lengths, block_table):
        scale = self.cfg.softmax_scale
        if self._kernel:
            return lambda qa, qr: mla.mla_paged_decode_attention(
                qa, qr, ckv_pool, kr_pool, lengths, block_table, scale=scale,
                block_k=self.decode_block_k, interpret=self._interpret)
        return lambda qa, qr: mla.reference_mla_paged_decode_attention(
            qa, qr, ckv_pool, kr_pool, lengths, block_table, scale=scale)

    def _attend_chunk(self, *args, **kw):
        if self._kernel:
            return mla.mla_paged_prefill_attention(
                *args, interpret=self._interpret, **kw)
        return mla.reference_mla_paged_prefill_attention(*args, **kw)

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
        xs = _embed(params, tokens, cfg)
        pad = mla.lane_pad(cfg.qk_rope_head_dim) - cfg.qk_rope_head_dim
        ks, vs, counts = [], [], []
        for i, lp in enumerate(params["layers"]):
            def attn(u, lp=lp, i=i):
                with jax.named_scope("attn"):
                    ap = lp["attn"]
                    h = rms_norm(u, lp["attn_norm"],
                                 cfg.rms_norm_eps).astype(cfg.dtype)
                    qn, qr, c_kv, k_rope = mla_project(ap, h, cfg, cos, sin)
                    with jax.named_scope("kv_write"):
                        k_rope = jnp.pad(k_rope, ((0, 0), (0, 0), (0, pad)))
                        ckv_pool = paged_cache_update(
                            cache.k[i], c_kv, lengths, block_tables,
                            valid=write_valid)
                        kr_pool = paged_cache_update(
                            cache.v[i], k_rope, lengths, block_tables,
                            valid=write_valid)
                    if t == 1:
                        o = mla_absorbed(
                            ap, qn[:, 0], qr[:, 0],
                            self._attend_decode(ckv_pool, kr_pool, lengths,
                                                block_tables), cfg)[:, None]
                    else:
                        o = self._attend_chunk(
                            qn, qr, ckv_pool, kr_pool, lengths, block_tables,
                            _w_ukv(ap, cfg), scale=cfg.softmax_scale)
                        o = o.reshape(b, t, -1)
                    return (_dot(o, ap["w_o"], jnp.float32),
                            (ckv_pool, kr_pool))

            xs, (k_i, v_i) = hc_sublayer(lp["hc_attn"], xs, cfg, attn)
            ks.append(k_i)
            vs.append(v_i)
            xs, cnt = hc_sublayer(
                lp["hc_mlp"], xs, cfg,
                lambda u, lp=lp: mlp_or_experts(lp, u, cfg, row_valid))
            if cnt is not None:
                counts.append(cnt)
        h = _final(params, xs, cfg)
        if not return_hidden:
            with jax.named_scope("lm_head"):
                h = jnp.einsum("btd,vd->btv", h, params["head"],
                               preferred_element_type=jnp.float32)
        aux = jnp.stack(counts) if counts else None
        return h, (tuple(ks), tuple(vs), cache.state), aux
