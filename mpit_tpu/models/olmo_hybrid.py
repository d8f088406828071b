"""Olmo-Hybrid: gated-delta linear-attention layers beside full attention.

The serving model of the ``olmo_hybrid`` family (public ``config.json`` of
``allenai/Olmo-Hybrid-7B``): ``layer_types`` names each layer
``linear_attention`` or ``full_attention`` (three and then one, over and
over), and the two kinds keep different things a sequence:

- a **full-attention** layer keeps pages: plain multi-head attention, keys
  and values of ``heads x head_dim`` a position in the engine's page pool,
  read by ``ops/decode_attention.py`` as GPT-2's are;
- a **linear-attention** layer (the gated delta rule,
  ``ops/gated_delta.py``) keeps a fixed state a slot in the engine's state
  pool, whatever the sequence's length: the matrix ``S`` [d_k, d_v] of
  every head in float32, and the last ``taps - 1`` rows that went into its
  depthwise causal convolution.

Pure functions over a parameter tree; :class:`OlmoHybridServeModel` puts
them behind :class:`~mpit_tpu.models.serving.ServeModel`. The equations
are those of ``models/olmo_hybrid_reference.py`` (which also lists what
the published keys leave open and how it was settled); this file computes
them in the configuration's dtype with float32 statistics, a chunk or a
tick at a time through the two caches.

What a step does to a slot's state. A row that is no token (the padding
behind a chunk's last prompt token, an idle slot, a padding slot of the
compacted step) has ``alpha = 1`` and ``beta = 0`` and does not shift the
convolution's tail: the state is left as it was, exactly. A slot's first
chunk (the step sees the slot's fill at 0) starts from zeros whatever its
seat holds, so a seat is never cleared between sequences.

The parameter tree (``init_params`` makes one; the names are the
benchmark's and the reference's too)::

    embed [V, d]   head [V, d]   final_norm [d]
    layers[i]: mixer_norm, mlp_norm [d]
               mlp: w_gate, w_up [d, f], w_down [f, d]
               attn: w_q, w_k, w_v, w_o [d, d], q_norm, k_norm [d]   (full)
               lin: w_qkv [d, 2 H d_k + H d_v], conv [taps, the same],
                    w_ab [d, 2 H], A_log, dt_bias [H], w_g [d, H d_v],
                    o_norm [d_v], w_o [H d_v, d]                    (linear)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mpit_tpu.models.serving import (
    CacheLayout,
    PageLayer,
    ServeModel,
    StateLayer,
)
from mpit_tpu.models.xing4 import _dot, _normal
from mpit_tpu.ops import gated_delta as gd

__all__ = ["OlmoHybridConfig", "OlmoHybridServeModel", "init_params",
           "forward_plain"]

LINEAR, FULL = "linear_attention", "full_attention"
_L2_EPS = 1e-6
# Query rows one call of the paged attention kernel takes a slot: GPT-2
# large's chunk, where the kernel was measured (PERF.md, PR 27). A longer
# chunk goes as that many queries of a row each, every one at its own
# fill of the same pages: at 3,840 lanes the kernel's tiles of keys and
# values are 7.9 MB of its 16 MB, and 512 query rows would not fit beside
# them.
_ATTN_ROWS = 64


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    rms_norm_eps: float = 1e-6
    layer_types: tuple = ()  # () = three linear and then one full, repeated
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    max_seq_len: int = 65536
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        types = tuple(self.layer_types) or tuple(
            FULL if i % 4 == 3 else LINEAR
            for i in range(self.num_hidden_layers))
        object.__setattr__(self, "layer_types", types)
        if len(types) != self.num_hidden_layers or set(types) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers "
                f"{LINEAR!r} or {FULL!r}, got {types}")
        if self.num_key_value_heads != self.num_attention_heads:
            raise NotImplementedError(
                "olmo_hybrid: grouped key/value heads (num_key_value_heads "
                "!= num_attention_heads) are not built (ROADMAP.md B2)")
        if self.linear_num_value_heads != self.linear_num_key_heads:
            raise NotImplementedError(
                "olmo_hybrid: linear_num_value_heads != linear_num_key_heads "
                "is not built (ROADMAP.md B2)")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide by num_attention_heads")

    @staticmethod
    def from_dict(d: dict, **overrides) -> "OlmoHybridConfig":
        """From the keys of the published ``config.json``; ``layer_types``
        is kept as the list it is."""
        names = {f.name for f in dataclasses.fields(OlmoHybridConfig)}
        kw = {k: v for k, v in d.items() if k in names}
        kw["max_seq_len"] = d.get("max_position_embeddings", 65536)
        if "layer_types" in kw:
            kw["layer_types"] = tuple(kw["layer_types"])
        kw.update(overrides)
        return OlmoHybridConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "OlmoHybridConfig":
        """Test-sized: two periods, three heads (no power of two), key and
        value widths 1 : 2 as published."""
        defaults = dict(
            vocab_size=256, hidden_size=48, intermediate_size=96,
            num_hidden_layers=8, num_attention_heads=3,
            num_key_value_heads=3, linear_num_key_heads=3,
            linear_num_value_heads=3, linear_key_head_dim=12,
            linear_value_head_dim=24, max_seq_len=256, dtype=jnp.float32,
        )
        defaults.update(kw)
        return OlmoHybridConfig(**defaults)

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
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def conv_channels(self) -> int:
        return self.linear_num_key_heads * (
            2 * self.linear_key_head_dim + self.linear_value_head_dim)

    def serve_model(self) -> "OlmoHybridServeModel":
        return OlmoHybridServeModel(self)


# -- pieces --------------------------------------------------------------------


def rms_norm(x, gain, eps):
    """RMSNorm over the last axis, float32 statistics and result."""
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                          + eps) * gain.astype(jnp.float32)


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + _L2_EPS)


def conv_silu(pre, tail, taps_w, n_valid):
    """The depthwise causal convolution and ``silu`` over ``pre`` [B, T, C]
    with ``tail`` [B, taps - 1, C] the rows before it: ``(mixed [B, T, C]
    float32, new tail)``. The new tail is the last ``taps - 1`` rows up to
    the slot's ``n_valid``-th of this call: with none valid, the old."""
    t, taps = pre.shape[1], taps_w.shape[0]
    rows = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
    w = taps_w.astype(jnp.float32)
    mixed = sum(w[j] * rows[:, j:j + t].astype(jnp.float32)
                for j in range(taps))
    new_tail = jax.vmap(
        lambda r, n: lax.dynamic_slice_in_dim(r, n, taps - 1, axis=0)
    )(rows, n_valid)
    return jax.nn.silu(mixed), new_tail.astype(tail.dtype)


def gdn_inputs(lp, xd, mixed, cfg: OlmoHybridConfig, valid):
    """``q, k, v`` (heads split, normalised, in ``xd``'s dtype) and the
    float32 ``g``, ``beta`` of the delta rule from the convolved rows
    ``mixed`` [B, T, C] and the layer's input ``xd``; rows not ``valid``
    get ``g = 0`` and ``beta = 0``."""
    hn, dk, dv = (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                  cfg.linear_value_head_dim)
    lead = mixed.shape[:-1]
    q = mixed[..., :hn * dk].reshape(*lead, hn, dk)
    k = mixed[..., hn * dk:2 * hn * dk].reshape(*lead, hn, dk)
    v = mixed[..., 2 * hn * dk:].reshape(*lead, hn, dv)
    q, k = _l2norm(q) * dk ** -0.5, _l2norm(k)
    ab = _dot(xd, lp["w_ab"], jnp.float32)
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ab[..., :hn] + lp["dt_bias"].astype(jnp.float32))
    beta = jax.nn.sigmoid(ab[..., hn:])
    if cfg.linear_allow_neg_eigval:
        beta = 2.0 * beta
    keep = valid[..., None]
    g, beta = jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0)
    dt = xd.dtype
    return q.astype(dt), k.astype(dt), v.astype(dt), g, beta


def gdn_output(lp, xd, o, cfg: OlmoHybridConfig):
    """The mixer's result from the rule's ``o`` [B, T, H, d_v]: a norm a
    head, the output gate, the output projection (float32)."""
    o = rms_norm(o, lp["o_norm"], cfg.rms_norm_eps)
    gate = jax.nn.silu(_dot(xd, lp["w_g"], jnp.float32))
    o = o.reshape(*o.shape[:-2], -1) * gate
    return _dot(o.astype(xd.dtype), lp["w_o"], jnp.float32)


def attention_project(ap, xd, cfg: OlmoHybridConfig):
    """``q, k, v`` [B, T, d] of a full-attention layer: queries and keys
    normalised over the whole width, no rotary embedding."""
    eps, dt = cfg.rms_norm_eps, xd.dtype
    q = rms_norm(_dot(xd, ap["w_q"], jnp.float32), ap["q_norm"], eps)
    k = rms_norm(_dot(xd, ap["w_k"], jnp.float32), ap["k_norm"], eps)
    return q.astype(dt), k.astype(dt), _dot(xd, ap["w_v"])


def _close_layer(lp, x, mix, cfg: OlmoHybridConfig):
    """``h = x + RMSNorm(mix)``, ``y = h + RMSNorm(mlp(h))``; the residual
    stream is float32."""
    from mpit_tpu.parallel.moe_serve import gated_mlp

    eps = cfg.rms_norm_eps
    h = x + rms_norm(mix, lp["mixer_norm"], eps)
    with jax.named_scope("mlp"):
        flat = h.reshape(-1, h.shape[-1]).astype(cfg.dtype)
        ff = gated_mlp(flat, **lp["mlp"], out_dtype=jnp.float32)
        return h + rms_norm(ff.reshape(h.shape), lp["mlp_norm"], eps)


def _embed(params, tokens):
    with jax.named_scope("embed"):
        return params["embed"][tokens].astype(jnp.float32)


def _final(params, x, cfg: OlmoHybridConfig):
    with jax.named_scope("lm_head"):
        return rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(
            cfg.dtype)


def forward_plain(params, tokens, cfg: OlmoHybridConfig):
    """Logits ``[B, T, V]`` float32 of whole sequences through the
    program's own layers with no cache: dense causal attention, the delta
    rule from a zero state over the whole length."""
    b, t = tokens.shape
    hn, hd = cfg.num_attention_heads, cfg.head_dim
    x = _embed(params, tokens)
    valid = jnp.ones((b, t), bool)
    for lp in params["layers"]:
        xd = x.astype(cfg.dtype)
        if "attn" in lp:
            q, k, v = attention_project(lp["attn"], xd, cfg)
            heads = lambda a: a.reshape(b, t, hn, hd)
            s = jnp.einsum("bqhd,bkhd->bhqk", heads(q), heads(k),
                           preferred_element_type=jnp.float32) * hd ** -0.5
            causal = jnp.tril(jnp.ones((t, t), bool))
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), heads(v),
                           preferred_element_type=jnp.float32)
            mix = _dot(o.reshape(b, t, -1).astype(cfg.dtype),
                       lp["attn"]["w_o"], jnp.float32)
        else:
            lin = lp["lin"]
            taps = cfg.linear_conv_kernel_dim
            mixed, _ = conv_silu(
                _dot(xd, lin["w_qkv"]),
                jnp.zeros((b, taps - 1, cfg.conv_channels), cfg.dtype),
                lin["conv"], jnp.full((b,), t, jnp.int32))
            q, k, v, g, beta = gdn_inputs(lin, xd, mixed, cfg, valid)
            o, _ = gd.gdn_chunk_lax(
                q, k, v, g, beta,
                jnp.zeros((b, cfg.linear_num_key_heads,
                           cfg.linear_key_head_dim,
                           cfg.linear_value_head_dim), jnp.float32))
            mix = gdn_output(lin, xd, o, cfg)
        x = _close_layer(lp, x, mix, cfg)
    h = _final(params, x, cfg)
    return jnp.einsum("btd,vd->btv", h, params["head"],
                      preferred_element_type=jnp.float32)


# -- parameters ------------------------------------------------------------------


def decay_init(key, heads: int):
    """``A_log`` and ``dt_bias`` [H] such that ``alpha`` at a zero
    projection is spread over (0.9, 0.9999), log-uniformly in ``1 -
    alpha``: slow heads and fast ones, so the decay path is exercised."""
    k_a, k_d = jax.random.split(key)
    one_minus = jnp.exp(jax.random.uniform(
        k_a, (heads,), jnp.float32, np.log(1e-4), np.log(0.1)))
    g0 = -jnp.log1p(-one_minus)  # the decay wanted: -log(alpha)
    dt_bias = jax.random.uniform(k_d, (heads,), jnp.float32, -1.0, 1.0)
    return jnp.log(g0 / jax.nn.softplus(dt_bias)), dt_bias


def init_layer(cfg: OlmoHybridConfig, key, layer: int, dtype=None) -> dict:
    """One layer's parameters from ``fold_in(key, layer)``: normal(0.02)
    matrices, normal(0.5) convolution taps (float32), unit norm gains,
    :func:`decay_init`."""
    dt = jnp.dtype(dtype or cfg.dtype)
    d, f = cfg.hidden_size, cfg.intermediate_size
    hn, dv = cfg.linear_num_key_heads, cfg.linear_value_head_dim
    keys = iter(jax.random.split(jax.random.fold_in(key, layer), 16))
    mat = lambda *shape: _normal(next(keys), shape, dt)
    ones = lambda w: jnp.ones((w,), jnp.float32)
    lp = {
        "mixer_norm": ones(d), "mlp_norm": ones(d),
        "mlp": {"w_gate": mat(d, f), "w_up": mat(d, f), "w_down": mat(f, d)},
    }
    if cfg.layer_types[layer] == FULL:
        lp["attn"] = {"w_q": mat(d, d), "w_k": mat(d, d), "w_v": mat(d, d),
                      "w_o": mat(d, d), "q_norm": ones(d), "k_norm": ones(d)}
    else:
        a_log, dt_bias = decay_init(next(keys), hn)
        lp["lin"] = {
            "w_qkv": mat(d, cfg.conv_channels),
            "conv": 0.5 * jax.random.normal(
                next(keys), (cfg.linear_conv_kernel_dim, cfg.conv_channels),
                jnp.float32),
            "w_ab": mat(d, 2 * hn), "A_log": a_log, "dt_bias": dt_bias,
            "w_g": mat(d, hn * dv), "o_norm": ones(dv),
            "w_o": mat(hn * dv, d),
        }
    return lp


def init_params(cfg: OlmoHybridConfig, key, dtype=None) -> dict:
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


class OlmoHybridServeModel(ServeModel):
    """The family behind the engine's model interface: one chip, bf16 or
    f32, greedy / temperature / top-k. What would move or roll back a
    slot's recurrent state is not built and raises by name: tensor
    parallelism, int8 weights or cache, speculative steps, the host tier,
    fleet shipment, preemption (ROADMAP.md B6)."""

    family = "olmo_hybrid"
    skips_invalid_rows = True  # the state must know which rows are tokens

    def __init__(self, cfg: OlmoHybridConfig, *, attn_fn=None,
                 chunk_fn=gd.gdn_chunk_lax, step_fn=gd.gdn_step_lax):
        # The reference engine: gather-dense attention and the rule's lax
        # twins; ``with_decode_attention`` puts the kernels in.
        self.cfg = cfg
        self._attn_fn, self._chunk_fn, self._step_fn = (
            attn_fn, chunk_fn, step_fn)

    def cache_layout(self) -> CacheLayout:
        cfg = self.cfg
        seat = StateLayer((
            ("s", (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                   cfg.linear_value_head_dim), jnp.float32),
            # The tail's rows side by side: [slots, 3, C] would pad its 3
            # rows to a sublane tile of 16 on the device.
            ("conv", ((cfg.linear_conv_kernel_dim - 1) * cfg.conv_channels,),
             cfg.dtype),
        ))
        page = PageLayer((cfg.hidden_size, cfg.hidden_size))
        return CacheLayout(
            tuple(page if kind == FULL else seat for kind in cfg.layer_types),
            cfg.dtype)

    def kv_row_bytes(self, dtype) -> float:
        return self.cfg.hidden_size * jnp.dtype(dtype).itemsize

    def check_supported(self, *, tp, kv_dtype, weights_dtype, spec_k,
                        host_pages) -> None:
        lacks = [
            (tp, "tensor parallelism (tp_axis)"),
            (kv_dtype == "int8", "an int8 cache (kv_dtype='int8')"),
            (weights_dtype == "int8", "int8 weights (weights_dtype='int8')"),
            (bool(spec_k), "speculative decoding (spec_k): a rejected "
             "draft's state cannot be rolled back"),
            (bool(host_pages), "the host KV tier (kv_host_pages): a parked "
             "slot's state cannot be moved"),
        ]
        for lacking, what in lacks:
            if lacking:
                raise ValueError(
                    f"the olmo_hybrid family does not have {what} yet: it "
                    "serves on one chip (ROADMAP.md B6)")

    def check_shipment(self) -> None:
        raise ValueError(
            "the olmo_hybrid family's slots cannot be shipped between "
            "engines yet: a slot is pages and a recurrent state, and "
            "export_kv_rows / inject_kv_rows move pages (ROADMAP.md B6)")

    def check_preemption(self) -> None:
        raise ValueError(
            "the olmo_hybrid family's slots cannot be preempted or parked "
            "yet: evicting one would drop its recurrent state "
            "(ROADMAP.md B6)")

    def with_decode_attention(self, *, block_k, interpret, page_size):
        del page_size  # the engine's tile is the kernel's
        from mpit_tpu.ops.decode_attention import flash_paged_decode_attention

        return OlmoHybridServeModel(
            self.cfg,
            attn_fn=functools.partial(
                flash_paged_decode_attention, block_k=block_k,
                interpret=interpret),
            chunk_fn=functools.partial(gd.gdn_chunk, interpret=interpret),
            step_fn=functools.partial(gd.gdn_step, interpret=interpret))

    def attention_tiling(self, t_q, *, page_size, kv_dtype, tp=1):
        from mpit_tpu.ops.decode_attention import decode_tiling

        del tp
        tiling = decode_tiling(
            min(t_q, _ATTN_ROWS), self.cfg.num_attention_heads, kv_dtype,
            page_size=page_size)
        return {"attention_form": tiling.form, "attention_rows": tiling.rows}

    def head_table(self, params):
        return params["head"]

    # -- the two mixers through their caches ------------------------------------
    def _full_mixer(self, ap, xd, k_pool, v_pool, lengths, block_tables,
                    write_valid):
        from mpit_tpu.models.gpt2 import (
            paged_cache_update,
            paged_cached_attention,
        )

        cfg = self.cfg
        b, t, _ = xd.shape
        hn, hd = cfg.num_attention_heads, cfg.head_dim
        with jax.named_scope("attn"):
            q, k, v = attention_project(ap, xd, cfg)
            with jax.named_scope("kv_write"):
                k_pool = paged_cache_update(
                    k_pool, k, lengths, block_tables, valid=write_valid)
                v_pool = paged_cache_update(
                    v_pool, v, lengths, block_tables, valid=write_valid)
            q = q.reshape(b, t, hn, hd)
            parts = 1 if t % _ATTN_ROWS else max(t // _ATTN_ROWS, 1)
            if parts > 1:  # see _ATTN_ROWS
                q = q.reshape(b * parts, _ATTN_ROWS, hn, hd)
                lengths = (lengths[:, None] + _ATTN_ROWS
                           * jnp.arange(parts)[None, :]).reshape(-1)
                block_tables = jnp.repeat(block_tables, parts, axis=0)
            o = (self._attn_fn or paged_cached_attention)(
                q, k_pool, v_pool, lengths, block_tables)
            return (_dot(o.reshape(b, t, -1), ap["w_o"], jnp.float32),
                    k_pool, v_pool)

    def _linear_mixer(self, lin, xd, seat, fresh, valid, slot_index):
        cfg = self.cfg
        b, t, _ = xd.shape
        with jax.named_scope("state_pool_move"):
            if slot_index is None:
                s_in, tail = seat["s"], seat["conv"]
            else:
                at = jnp.minimum(slot_index, seat["s"].shape[0] - 1)
                s_in, tail = seat["s"][at], seat["conv"][at]
            tail = tail.reshape(b, -1, cfg.conv_channels)
            if t > 1:
                # A slot's first chunk starts from zeros whatever its
                # seat holds (a decode tick is never a slot's first step).
                s_in = jnp.where(fresh[:, None, None, None], 0.0, s_in)
                tail = jnp.where(fresh[:, None, None], 0, tail)
        with jax.named_scope("linear_attn"):
            with jax.named_scope("gdn_conv"):
                mixed, tail = conv_silu(
                    _dot(xd, lin["w_qkv"]), tail, lin["conv"],
                    jnp.sum(valid, axis=1, dtype=jnp.int32))
            q, k, v, g, beta = gdn_inputs(lin, xd, mixed, cfg, valid)
            if t == 1:
                with jax.named_scope("gdn_step"):
                    o, s_out = self._step_fn(
                        q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s_in)
                    o = o[:, None]
            else:
                with jax.named_scope("gdn_chunk"):
                    o, s_out = self._chunk_fn(q, k, v, g, beta, s_in)
            mix = gdn_output(lin, xd, o, cfg)
        with jax.named_scope("state_pool_move"):
            tail = tail.reshape(b, -1)
            if slot_index is None:
                seat = {"s": s_out, "conv": tail}
            else:  # a padding row's slot is past the pool: dropped
                seat = {"s": seat["s"].at[slot_index].set(s_out, mode="drop"),
                        "conv": seat["conv"].at[slot_index].set(
                            tail, mode="drop")}
        return mix, seat

    def forward_paged(self, params, tokens, cache, block_tables, write_valid,
                      *, return_hidden, row_valid=None, slot_index=None):
        cfg = self.cfg
        lengths = cache.lengths
        valid = (jnp.ones(tokens.shape, bool) if row_valid is None
                 else row_valid)
        fresh = valid[:, 0] & (lengths == 0)
        x = _embed(params, tokens)
        ks, vs, seats = [], [], []
        for lp in params["layers"]:
            xd = x.astype(cfg.dtype)
            if "attn" in lp:
                i = len(ks)
                mix, k_i, v_i = self._full_mixer(
                    lp["attn"], xd, cache.k[i], cache.v[i], lengths,
                    block_tables, write_valid)
                ks.append(k_i)
                vs.append(v_i)
            else:
                mix, seat = self._linear_mixer(
                    lp["lin"], xd, cache.state[len(seats)], fresh, valid,
                    slot_index)
                seats.append(seat)
            x = _close_layer(lp, x, mix, cfg)
        h = _final(params, x, cfg)
        if not return_hidden:
            with jax.named_scope("lm_head"):
                h = jnp.einsum("btd,vd->btv", h, params["head"],
                               preferred_element_type=jnp.float32)
        return h, (tuple(ks), tuple(vs), tuple(seats)), None
