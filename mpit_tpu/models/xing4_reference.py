"""The plain reference of the ``xing4_0`` family: straightforward jax.numpy.

float32 throughout at ``precision=highest``; no kernel, no cache, no
batching, nothing of the program's model, dispatch or engine code. One
sequence at a time, one layer at a time (``layer_forward``), so that a
caller can make, use and free a layer's weights: 4.79 B parameters in
float32 do not fit beside anything.

It follows the family's published conventions (DeepSeek-V3's modeling
code for the keys that configuration carries; DeepSeek's mHC paper for
``hc_mult`` / ``hc_sinkhorn_iters`` / ``mhc_h_res_clamp_*``). Where those
leave a choice open it is taken here and listed under ``assumed`` in the
configuration file: RMSNorm of the flattened streams without gain and
with ``hc_eps``; ``hc_eps`` added to each Sinkhorn denominator; columns
normalised before rows; the embedding replicated to the streams and the
streams summed before the final norm; the half-split rotary pair layout;
``mscale`` as DeepSeek computes it. The multi-token-prediction module is
not part of the main model (discarded at inference).

Attention is computed in blocks of query rows and the experts one after
the other over every token with the routing weights as a mask, so no
token can be dropped and nothing is sorted: that changes memory and
operation count, not arithmetic.

``matmul`` selects the arithmetic of every matrix product, for the
control a check must see fail: ``"f32"`` (the reference) or ``"fp8"``
(operands scaled per tensor and rounded to float8_e4m3fn).

``cfg`` is a plain dict with the published key names.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _round_operand(a, matmul: str):
    if matmul == "f32":
        return a
    if matmul != "fp8":
        raise ValueError(f"unknown matmul arithmetic {matmul!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, matmul: str):
    return jnp.matmul(_round_operand(x.astype(jnp.float32), matmul),
                      _round_operand(w.astype(jnp.float32), matmul),
                      precision=HIGHEST)


def _rms_norm(x, gain, eps):
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y if gain is None else y * gain


def softmax_scale(cfg: dict) -> float:
    rs = cfg.get("rope_scaling") or {}
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    m = 1.0
    if rs.get("factor", 1) > 1 and rs.get("mscale_all_dim", 0):
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return d ** -0.5 * m * m


def inv_freq(cfg: dict) -> np.ndarray:
    """YaRN frequencies ``[d_rope / 2]`` (DeepSeek-V3's
    ``DeepseekV3YarnRotaryEmbedding``)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg.get("rope_scaling") or {}
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = rs.get("factor", 1)
    if factor <= 1:
        return extra.astype(np.float32)
    orig = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (extra / factor * (1 - mask) + extra * mask).astype(np.float32)


def _rope(x, positions, cfg):
    """Rotate ``x`` [T, ..., d_rope] at ``positions`` [T]; pairs are
    ``(x[i], x[i + d/2])``."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq(cfg))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _sinkhorn(m, iters, eps):
    m = jnp.exp(m)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)  # columns
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)  # rows
    return m


def hyper_connection(hp, xs, cfg, f):
    """``X <- H_res X + H_post^T f(H_pre X)`` on ``xs`` [T, n, d]. The
    coefficients are float32 whatever ``matmul`` is: they are no matrix
    product of the model's width."""
    n, eps = cfg["hc_mult"], cfg["hc_eps"]
    flat = _rms_norm(xs.reshape(xs.shape[0], -1), None, eps)
    h = jnp.matmul(flat, hp["phi"], precision=HIGHEST)
    a, b = hp["a"], hp["b"]
    pre = jax.nn.sigmoid(a[0] * h[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * h[:, n:2 * n] + b[n:2 * n])
    res = (a[2] * h[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    res = _sinkhorn(
        jnp.clip(res, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]),
        cfg["hc_sinkhorn_iters"], eps)
    y = f(jnp.einsum("tn,tnd->td", pre, xs, precision=HIGHEST))
    return (jnp.einsum("tij,tjd->tid", res, xs, precision=HIGHEST)
            + post[:, :, None] * y[:, None, :])


def attention(ap, u, positions, cfg, matmul="f32", q_block=256):
    """MLA on one sequence ``u`` [T, d], expanded form, causal."""
    t = u.shape[0]
    hn, dn, dr, dv, c = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                         cfg["kv_lora_rank"])
    eps = cfg["rms_norm_eps"]
    c_q = _rms_norm(_mm(u, ap["w_dq"], matmul), ap["q_norm"], eps)
    q = _mm(c_q, ap["w_uq"], matmul).reshape(t, hn, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], positions, cfg)
    kv = _mm(u, ap["w_dkv"], matmul)
    c_kv = _rms_norm(kv[:, :c], ap["kv_norm"], eps)
    k_rope = _rope(kv[:, c:], positions, cfg)  # [T, dr], shared by heads
    kvu = _mm(c_kv, ap["w_ukv"], matmul).reshape(t, hn, dn + dv)
    k = jnp.concatenate(
        [kvu[..., :dn], jnp.broadcast_to(k_rope[:, None], (t, hn, dr))], -1)
    v = kvu[..., dn:]
    qf = jnp.concatenate([q_nope, q_rope], -1)
    scale = softmax_scale(cfg)
    k = _round_operand(k, matmul)
    v = _round_operand(v, matmul)

    def block(args):
        qb, pos_b = args  # [qb, H, dq], [qb]
        s = jnp.einsum("qhd,khd->hqk", _round_operand(qb, matmul), k,
                       precision=HIGHEST) * scale
        vis = positions[None, :] <= pos_b[:, None]
        p = jax.nn.softmax(jnp.where(vis[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _round_operand(p, matmul), v,
                          precision=HIGHEST)

    pad = (-t) % q_block
    qp = jnp.pad(qf, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, q_block, hn, dn + dr)
    pp = jnp.pad(positions, (0, pad)).reshape(-1, q_block)
    o = lax.map(block, (qp, pp)).reshape(-1, hn * dv)[:t]
    return _mm(o, ap["w_o"], matmul)


def gated_mlp(x, w, matmul="f32"):
    h = jax.nn.silu(_mm(x, w["w_gate"], matmul)) * _mm(x, w["w_up"], matmul)
    return _mm(h, w["w_down"], matmul)


def routing_weights(x, mw, cfg, matmul="f32"):
    """``[T, E]``: a token's weight on each expert, 0 where not chosen."""
    s = jax.nn.sigmoid(_mm(x, mw["router"], matmul))
    k = cfg["num_experts_per_tok"]
    _, idx = lax.top_k(s + mw["bias"], k)  # n_group = topk_group = 1
    g = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    g = g * cfg["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(g)


def experts(x, mw, cfg, matmul="f32", held=None):
    """The expert layer on ``x`` [T, d]: every routed token computed.
    ``held``: the global ids of the experts ``mw`` holds (None = all, in
    order); an absent expert adds nothing."""
    w = routing_weights(x, mw, cfg, matmul)
    ids = jnp.arange(mw["w_gate"].shape[0]) if held is None else jnp.asarray(
        held)

    def one(acc, ew):
        e, wg, wu, wd = ew
        y = gated_mlp(x, {"w_gate": wg, "w_up": wu, "w_down": wd}, matmul)
        return acc + w[:, e][:, None] * y, None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (ids, mw["w_gate"], mw["w_up"], mw["w_down"]))
    if "shared" in mw:
        y = y + gated_mlp(x, mw["shared"], matmul)
    return y


def layer_forward(cfg, lw, xs, positions, matmul="f32", q_block=256,
                  held=None):
    """One layer on the streams ``xs`` [T, n, d] of one sequence."""
    eps = cfg["rms_norm_eps"]
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    xs = hyper_connection(
        lw["hc_attn"], xs, cfg,
        lambda u: attention(lw["attn"], _rms_norm(u, lw["attn_norm"], eps),
                            positions, cfg, matmul, q_block))

    def second(u):
        h = _rms_norm(u, lw["mlp_norm"], eps)
        if "mlp" in lw:
            return gated_mlp(h, lw["mlp"], matmul)
        return experts(h, lw["moe"], cfg, matmul, held)

    return hyper_connection(lw["hc_mlp"], xs, cfg, second)


def embed(cfg, table, tokens):
    """The token's row replicated to the streams: ``[T, n, d]``."""
    x = table[tokens].astype(jnp.float32)
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], cfg["hc_mult"],
                                            x.shape[1]))


def head_logits(cfg, w_top, xs, matmul="f32", block=8192):
    """Logits ``[rows, V]`` of the streams ``xs`` [rows, n, d]: streams
    summed, final RMSNorm, the untied head a block of rows at a time."""
    h = _rms_norm(jnp.sum(xs, axis=1),
                  w_top["final_norm"].astype(jnp.float32), cfg["rms_norm_eps"])
    head = w_top["head"]
    v = head.shape[0]
    block = min(block, v)
    if v % block:
        return _mm(h, head.T, matmul)
    h = _round_operand(h, matmul)
    if matmul == "f32":
        part = lambda hb: jnp.matmul(h, hb.astype(jnp.float32).T,
                                     precision=HIGHEST)
    else:
        # Per-tensor rounding needs the whole table's scale.
        scale = jnp.maximum(jnp.max(jnp.abs(head.astype(jnp.float32))),
                            1e-30) / 448.0
        part = lambda hb: jnp.matmul(
            h, ((hb.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
                .astype(jnp.float32) * scale).T, precision=HIGHEST)
    out = lax.map(part, head.reshape(v // block, block, -1))
    return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], v)


def logits_at(cfg, w_top, layers, tokens, positions, matmul="f32",
              q_block=256, held=None):
    """Logits ``[len(positions), V]`` of one sequence ``tokens`` [T].
    ``layers``: the layers' weights, in order (any iterable: a generator
    may make each when it is asked for and let it go afterwards)."""
    xs = embed(cfg, w_top["embed"], tokens)
    pos = jnp.arange(tokens.shape[0])
    for lw in layers:
        xs = layer_forward(cfg, lw, xs, pos, matmul, q_block, held)
    return head_logits(cfg, w_top, xs[positions], matmul)
