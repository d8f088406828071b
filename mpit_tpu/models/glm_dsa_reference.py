"""The plain reference of the ``glm_moe_dsa`` family: straightforward jax.numpy.

float32 throughout at ``precision=highest``; no kernel, no cache, no
batching, nothing of the program's model, dispatch or engine code; the
choice of rows is ``lax.top_k``. One sequence at a time, one layer at a
time (``layer_forward``), so that a caller can make, use and free a
layer's weights.

It follows the family's published conventions: the keys of
``zai-org/GLM-5.2``'s ``config.json``, DeepSeek-V3's modeling code for the
latent attention and the ``noaux_tc`` router those keys name, and
DeepSeek-V3.2-Exp's lightning indexer for ``index_*`` / ``indexer_types``:

    q_I[t, j] = W_Iq c_q[t], the first qk_rope_head_dim of each head rotated
    k_I[s]    = LayerNorm(W_Ik u[s]), its first qk_rope_head_dim rotated
    w[t]      = W_Iw u[t] x index_n_heads^-0.5 x index_head_dim^-0.5
    I[t, s]   = sum_j w[t, j] relu(q_I[t, j] . k_I[s]),  s <= t
    S_t       = the index_topk positions with the largest I[t, s]
                (ties to the earlier position; all while t < index_topk)

A ``full`` layer attends over ``S_t``; a ``shared`` layer over the ``S_t``
of the nearest ``full`` layer before it. Departures, listed under
``assumed`` in the configuration file: the published code's Hadamard
rotation of ``q_I`` and ``k_I`` and their fp8 storage are left out (the
rotation is orthogonal and changes no score; the configuration states
bf16); the indexer's LayerNorm has a gain and a bias and eps 1e-6; rotary
pairs are ``(2i, 2i + 1)`` (``rope_interleave``), left in place. The
multi-token-prediction module is not part of the main model.

Attention is computed in blocks of query rows and the experts one after
the other over every token with the routing weights as a mask: that
changes memory and operation count, not arithmetic. ``held`` gives the
global ids of the experts the weights hold (one chip's share of an
expert-parallel layer): an absent expert adds nothing.

Two controls a check must see fail: ``matmul="fp8"`` (operands of every
product scaled per tensor and rounded to float8_e4m3fn) and
``dense_attention=True`` (the choice ignored: every cached row attended).

``cfg`` is a plain dict with the published key names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
LN_EPS = 1e-6


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
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * gain


def _layer_norm(x, gain, bias):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + LN_EPS) * gain + bias


def softmax_scale(cfg: dict) -> float:
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5


def _rope(x, positions, cfg):
    """Rotate ``x`` [T, ..., d_rope] at ``positions`` [T]; the pair of
    frequency ``i`` is ``(x[2i], x[2i + 1])``."""
    dim = cfg["qk_rope_head_dim"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    inv = (1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
           ).astype(np.float32)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _blocks(fn, t, q_block, *rows):
    """``fn`` over blocks of ``q_block`` rows of each of ``rows`` (padded
    with zeros), the results' rows concatenated and cut to ``t``."""
    pad = (-t) % q_block
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)
                            ).reshape(-1, q_block, *a.shape[1:])
    out = lax.map(fn, tuple(cut(a) for a in rows))
    return jax.tree.map(lambda o: o.reshape(-1, *o.shape[2:])[:t], out)


def index_choice(ip, u, c_q, positions, cfg, matmul="f32", q_block=256):
    """``S_t`` of every row of one sequence as ``[T, k]`` int32 position
    numbers (``k`` = ``index_topk``, or ``T`` if that is less): entries
    past a row's visible positions are positions it cannot see, which the
    causal mask removes."""
    t = u.shape[0]
    hi, di, dr = (cfg["index_n_heads"], cfg["index_head_dim"],
                  cfg["qk_rope_head_dim"])
    q = _mm(c_q, ip["wq_b"], matmul).reshape(t, hi, di)
    q = jnp.concatenate([_rope(q[..., :dr], positions, cfg), q[..., dr:]], -1)
    k = _layer_norm(_mm(u, ip["wk"], matmul), ip["k_norm_g"], ip["k_norm_b"])
    k = jnp.concatenate([_rope(k[:, :dr], positions, cfg), k[:, dr:]], -1)
    w = _mm(u, ip["w_proj"], matmul) * (hi ** -0.5 * di ** -0.5)
    k = _round_operand(k, matmul)
    kk = min(cfg["index_topk"], t)

    def block(args):
        qb, wb, pos_b = args
        logit = jnp.einsum("qhd,kd->qhk", _round_operand(qb, matmul), k,
                           precision=HIGHEST)
        score = jnp.einsum("qhk,qh->qk", jnp.maximum(logit, 0.0), wb,
                           precision=HIGHEST)
        score = jnp.where(positions[None, :] <= pos_b[:, None], score,
                          -jnp.inf)
        return lax.top_k(score, kk)[1]

    return _blocks(block, t, q_block, q, w, positions)


def attention(ap, u, positions, cfg, chosen, matmul="f32", q_block=256,
              ip=None, dense_attention=False):
    """MLA on one sequence ``u`` [T, d], expanded form, causal, each row
    over its chosen positions. With ``ip`` (a ``full`` layer's indexer)
    the choice is made here, else ``chosen`` [T, k] is the one handed on.
    Returns ``(out [T, d], chosen)``."""
    t = u.shape[0]
    hn, dn, dr, dv, c = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                         cfg["kv_lora_rank"])
    eps = cfg["rms_norm_eps"]
    c_q = _rms_norm(_mm(u, ap["w_dq"], matmul), ap["q_norm"], eps)
    if ip is not None:
        chosen = index_choice(ip, u, c_q, positions, cfg, matmul, q_block)
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
        qb, pos_b, rows_b = args  # [qb, H, dq], [qb], [qb, k]
        s = jnp.einsum("qhd,khd->hqk", _round_operand(qb, matmul), k,
                       precision=HIGHEST) * scale
        vis = positions[None, :] <= pos_b[:, None]
        if not dense_attention:
            picked = jnp.zeros(vis.shape, bool).at[
                jnp.arange(rows_b.shape[0])[:, None], rows_b].set(True)
            vis = vis & picked
        p = jax.nn.softmax(jnp.where(vis[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _round_operand(p, matmul), v,
                          precision=HIGHEST)

    o = _blocks(block, t, q_block, qf, positions, chosen).reshape(t, hn * dv)
    return _mm(o, ap["w_o"], matmul), chosen


def gated_mlp(x, w, matmul="f32"):
    h = jax.nn.silu(_mm(x, w["w_gate"], matmul)) * _mm(x, w["w_up"], matmul)
    return _mm(h, w["w_down"], matmul)


def routing_weights(x, mw, cfg, matmul="f32"):
    """``[T, E]``: a token's weight on each expert, 0 where not chosen."""
    s = jax.nn.sigmoid(_mm(x, mw["router"], matmul))
    _, idx = lax.top_k(s + mw["bias"], cfg["num_experts_per_tok"])
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


def layer_forward(cfg, lw, x, positions, chosen, matmul="f32", q_block=256,
                  held=None, dense_attention=False):
    """One layer on ``x`` [T, d] of one sequence; ``chosen`` is what the
    layer before handed on (anything for a ``full`` layer, which has
    ``indexer`` weights and chooses anew). Returns ``(x, chosen)``."""
    eps = cfg["rms_norm_eps"]
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    a, chosen = attention(
        lw["attn"], _rms_norm(x, lw["attn_norm"], eps), positions, cfg,
        chosen, matmul, q_block, lw.get("indexer"), dense_attention)
    x = x + a
    h = _rms_norm(x, lw["mlp_norm"], eps)
    if "mlp" in lw:
        return x + gated_mlp(h, lw["mlp"], matmul), chosen
    return x + experts(h, lw["moe"], cfg, matmul, held), chosen


def embed(cfg, table, tokens):
    del cfg
    return table[tokens].astype(jnp.float32)


def no_choice(cfg, t: int):
    """What the first layer is handed: a choice it replaces."""
    return jnp.zeros((t, min(cfg["index_topk"], t)), jnp.int32)


def head_logits(cfg, w_top, x, matmul="f32"):
    """Logits ``[rows, V]`` of ``x`` [rows, d]: final RMSNorm, the untied
    head."""
    h = _rms_norm(x, w_top["final_norm"].astype(jnp.float32),
                  cfg["rms_norm_eps"])
    return _mm(h, w_top["head"].astype(jnp.float32).T, matmul)


def logits_at(cfg, w_top, layers, tokens, positions, matmul="f32",
              q_block=256, held=None, dense_attention=False):
    """Logits ``[len(positions), V]`` of one sequence ``tokens`` [T].
    ``layers``: the layers' weights, in order (any iterable)."""
    x = embed(cfg, w_top["embed"], tokens)
    pos = jnp.arange(tokens.shape[0])
    chosen = no_choice(cfg, tokens.shape[0])
    for lw in layers:
        x, chosen = layer_forward(cfg, lw, x, pos, chosen, matmul, q_block,
                                  held, dense_attention)
    return head_logits(cfg, w_top, x[positions], matmul)
