"""The plain reference of the ``laguna`` family: straightforward jax.numpy.

float32 throughout at ``precision=highest``; no kernel, no cache, no
batching, nothing of the program's model, dispatch or engine code (it
imports nothing of ``mpit_tpu``). One sequence at a time, one layer at a
time (``layer_forward``), so that a caller can make, use and free a
layer's weights.

The equations (public ``config.json`` of ``poolside/Laguna-S-2.1``,
``model_type: laguna``; ``h`` the residual stream, RMSNorm with
``rms_norm_eps``, pre-norm, no biases; layer ``l`` has type
``layer_types[l]`` and ``H_l = num_attention_heads_per_layer[l]`` query
heads over ``num_key_value_heads`` key/value heads of ``head_dim``):

- ``u = RMSNorm(h)``; ``q = u W_q`` as ``[H_l, D]``, ``k = u W_k``, ``v = u
  W_v`` as ``[H_kv, D]``; ``g = sigmoid(u W_g)`` as ``[H_l]``.
- Rotary, rotate-half pairs ``(i, i + rot / 2)`` over the first ``rot =
  D x partial_rotary_factor`` values of a head. A ``full_attention`` layer:
  YaRN as the transformers library computes it (inverse frequencies blended
  between interpolated and extrapolated by the linear ramp between the two
  correction dimensions, cos and sin times ``attention_factor``); a
  ``sliding_attention`` layer: plain rotary at its own ``rope_theta``.
- Query head ``j`` reads key/value head ``j // (H_l / H_kv)``. Scores ``q .
  k x D^-0.5``, causal; a sliding layer's position ``t`` attends ``t -
  sliding_window < s <= t``. ``a_j = g_j x sum_s p_js v_s``; ``h +=
  concat(a) W_o``.
- ``u = RMSNorm(h)``; a ``dense`` layer: ``h += W_down(silu(W_gate u) x
  W_up u)``; a ``sparse`` layer: ``p = softmax(u W_r)`` over the router's
  experts, the ``num_experts_per_tok`` largest, weights
  ``moe_routed_scaling_factor x p_i / sum_chosen p`` (``norm_topk_prob``),
  ``h += sum_i w_i E_i(u) + E_shared(u)``.
- Final RMSNorm, untied head.

What the published keys leave open, settled here and listed under
``assumed`` in the benchmark's configuration file: the gate is a sigmoid
of the layer's normed input, a value a head (``gating: per-head``); the
router's scores are a softmax and the shared expert is added ungated; no
query or key normalisation.

Attention is computed in blocks of query rows and the experts one after
the other over every token with the routing weights as a mask, so no
token can be dropped and nothing is sorted: that changes memory and
operation count, not arithmetic. ``held`` names the experts the weights
hold (the chip's share): an absent expert adds nothing.

``matmul`` selects the arithmetic of every matrix product, for a control
a check must see fail: ``"f32"`` (the reference) or ``"fp8"`` (operands
scaled per tensor and rounded to float8_e4m3fn). ``no_window`` is the
other control: sliding layers attend every earlier position.

``cfg`` is a plain dict with the published key names.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FULL, SLIDING = "full_attention", "sliding_attention"


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
    return x * lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gain


def rope_of(cfg: dict, kind: str) -> dict:
    """The rotary parameters of a layer of ``kind``."""
    return cfg["rope_parameters"][kind]


def inv_freq(rp: dict, head_dim: int) -> tuple:
    """``(inverse frequencies [rot / 2], factor on cos and sin)`` of one
    kind of layer, as transformers' ``_compute_yarn_parameters`` and
    ``_compute_default_rope_parameters`` give them."""
    dim = int(head_dim * rp.get("partial_rotary_factor", 1.0))
    base = float(rp["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp.get("rope_type", "default") != "yarn":
        return extra.astype(np.float32), 1.0
    factor, orig = rp["factor"], rp["original_max_position_embeddings"]
    attention = rp.get("attention_factor") or (0.1 * math.log(factor) + 1.0)

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(rp.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(rp.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp  # 1 where the extrapolated frequency stays
    return ((extra / factor * (1 - keep) + extra * keep).astype(np.float32),
            float(attention))


def rope(x, positions, rp: dict):
    """Rotate the first ``rot`` values of ``x`` [T, H, D] at ``positions``
    [T]; the rest pass through."""
    freq, factor = inv_freq(rp, x.shape[-1])
    rot = 2 * freq.shape[0]
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freq)
    cos, sin = (jnp.cos(ang) * factor)[:, None], (jnp.sin(ang) * factor)[:, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def attention(ap, u, positions, cfg, kind, matmul="f32", q_block=256,
              no_window=False):
    """One layer's gated grouped-query attention on ``u`` [T, d]."""
    t = u.shape[0]
    d, h_kv = cfg["head_dim"], cfg["num_key_value_heads"]
    h = ap["w_q"].shape[1] // d
    rp = rope_of(cfg, kind)
    q = rope(_mm(u, ap["w_q"], matmul).reshape(t, h, d), positions, rp)
    k = rope(_mm(u, ap["w_k"], matmul).reshape(t, h_kv, d), positions, rp)
    v = _mm(u, ap["w_v"], matmul).reshape(t, h_kv, d)
    gate = jax.nn.sigmoid(_mm(u, ap["w_g"], matmul))  # [T, H]
    window = cfg["sliding_window"] if kind == SLIDING and not no_window else 0
    k, v = _round_operand(k, matmul), _round_operand(v, matmul)

    def block(args):
        qb, pos_b = args  # [qb, H, D], [qb]
        qg = _round_operand(qb, matmul).reshape(-1, h_kv, h // h_kv, d)
        s = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=HIGHEST) * d ** -0.5
        vis = positions[None, :] <= pos_b[:, None]
        if window:
            vis &= positions[None, :] > pos_b[:, None] - window
        p = jax.nn.softmax(jnp.where(vis[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", _round_operand(p, matmul), v,
                       precision=HIGHEST)
        return o.reshape(-1, h, d)

    pad = (-t) % q_block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, q_block, h, d)
    pp = jnp.pad(positions, (0, pad)).reshape(-1, q_block)
    o = lax.map(block, (qp, pp)).reshape(-1, h, d)[:t]
    return _mm((o * gate[:, :, None]).reshape(t, h * d), ap["w_o"], matmul)


def gated_mlp(x, w, matmul="f32"):
    h = jax.nn.silu(_mm(x, w["w_gate"], matmul)) * _mm(x, w["w_up"], matmul)
    return _mm(h, w["w_down"], matmul)


def routing_weights(x, mw, cfg, matmul="f32"):
    """``[T, E]``: a token's weight on each of the router's experts, 0
    where not chosen."""
    p = jax.nn.softmax(_mm(x, mw["router"], matmul), axis=-1)
    _, idx = lax.top_k(p, cfg["num_experts_per_tok"])
    g = jnp.take_along_axis(p, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    g = g * cfg["moe_routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, idx].set(g)


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


def layer_forward(cfg, lw, x, positions, kind, matmul="f32", q_block=256,
                  held=None, no_window=False):
    """One layer of ``kind`` (its ``layer_types`` entry) on the residual
    stream ``x`` [T, d] of one sequence."""
    eps = cfg["rms_norm_eps"]
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    x = x + attention(lw["attn"], _rms_norm(x, lw["attn_norm"], eps),
                      positions, cfg, kind, matmul, q_block, no_window)
    u = _rms_norm(x, lw["mlp_norm"], eps)
    if "mlp" in lw:
        return x + gated_mlp(u, lw["mlp"], matmul)
    return x + experts(u, lw["moe"], cfg, matmul, held)


def embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def head_logits(cfg, w_top, x, matmul="f32", block=8192):
    """Logits ``[rows, V]`` of the stream's rows ``x`` [rows, d]: final
    RMSNorm, the untied head a block of its rows at a time."""
    h = _rms_norm(x, w_top["final_norm"].astype(jnp.float32),
                  cfg["rms_norm_eps"])
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
              q_block=256, held=None, no_window=False):
    """Logits ``[len(positions), V]`` of one sequence ``tokens`` [T].
    ``layers``: the layers' weights, in order (any iterable: a generator
    may make each when it is asked for and let it go afterwards)."""
    x = embed(w_top["embed"], tokens)
    pos = jnp.arange(tokens.shape[0])
    for kind, lw in zip(cfg["layer_types"], layers):
        x = layer_forward(cfg, lw, x, pos, kind, matmul, q_block, held,
                          no_window)
    return head_logits(cfg, w_top, x[positions], matmul)
