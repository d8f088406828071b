"""The plain reference of the ``olmo_hybrid`` family: straightforward jax.numpy.

The benchmark's own copy (``mpit_tpu/models/olmo_hybrid_reference.py`` is
the program's, which its tier-1 tests import): no later change to the
program's side moves the yardstick. Imports nothing of ``mpit_tpu`` and is
handed nothing the program made.

float32 throughout at ``precision=highest``; no kernel, no cache, no
chunks, no batching, nothing of the program's model, ops or engine code.
One sequence at a time, one layer at a time (``layer_forward``), so that a
caller can make, use and free a layer's weights.

The keys are those of the public ``config.json`` of
``allenai/Olmo-Hybrid-7B`` (``model_type`` ``olmo_hybrid``): ``layer_types``
says which layers are ``full_attention`` and which ``linear_attention``;
the ``linear_*`` keys are those of the public Gated DeltaNet layer. Where
the configuration leaves a choice open it is taken here and listed under
``assumed`` in the benchmark's configuration file:

- the block is the Olmo 2/3 family's reordered norm, both kinds of layer:
  ``h = x + RMSNorm(mixer(x))``, ``y = h + RMSNorm(mlp(h))``; a final
  RMSNorm, an untied head;
- full attention normalises queries and keys over the whole hidden width
  (the family's QK-norm) and has NO rotary embedding: the published
  ``rope_parameters.rope_theta`` is null, so there is no base to rotate
  by; position reaches the model through the recurrent layers and the
  causal mask;
- a linear layer's state is float32, from zeros; the output norm's gain
  is one vector of ``linear_value_head_dim`` shared over heads; ``l2norm``
  is ``x rsqrt(sum x^2 + 1e-6)``; the convolution's tap ``j`` multiplies
  the row ``3 - j`` positions back.

The recurrence is a ``lax.scan`` over tokens::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T
    o_t = S_t^T q_t

``matmul`` selects the arithmetic of every matrix product, for the
controls a check must see fail: ``"f32"`` (the reference) or ``"fp8"``
(operands scaled per tensor and rounded to float8_e4m3fn).
``state_dtype`` is the recurrent state's between tokens: ``float32`` (the
reference) or ``bfloat16`` (the second control: a state kept in the
precision below the stated one).

``cfg`` is a plain dict with the published key names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
L2_EPS = 1e-6


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
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * gain.astype(jnp.float32)


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + L2_EPS)


def embed(cfg: dict, table, tokens):
    del cfg
    return table[tokens].astype(jnp.float32)


def full_attention(cfg: dict, ap: dict, x, *, matmul: str, q_block: int):
    """Causal softmax attention of ``x`` [T, d], plain multi-head, queries
    and keys normalised over the whole width, no rotary embedding."""
    t, d = x.shape
    hn = cfg["num_attention_heads"]
    if cfg.get("num_key_value_heads", hn) != hn:
        raise NotImplementedError("grouped key/value heads")
    hd = d // hn
    eps = cfg["rms_norm_eps"]
    q = _rms_norm(_mm(x, ap["w_q"], matmul), ap["q_norm"], eps)
    k = _rms_norm(_mm(x, ap["w_k"], matmul), ap["k_norm"], eps)
    v = _mm(x, ap["w_v"], matmul)
    heads = lambda a: jnp.swapaxes(a.reshape(t, hn, hd), 0, 1)  # [H, T, hd]
    q, k, v = heads(q), heads(k), heads(v)
    kr, vr = _round_operand(k, matmul), _round_operand(v, matmul)
    out = []
    for q0 in range(0, t, q_block):
        qb = _round_operand(q[:, q0:q0 + q_block], matmul)
        s = jnp.einsum("hqd,hkd->hqk", qb, kr, precision=HIGHEST) * hd ** -0.5
        vis = (jnp.arange(t)[None, :]
               <= (q0 + jnp.arange(qb.shape[1]))[:, None])
        p = jax.nn.softmax(jnp.where(vis[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,hkd->hqd", _round_operand(p, matmul), vr,
                              precision=HIGHEST))
    o = jnp.swapaxes(jnp.concatenate(out, axis=1), 0, 1).reshape(t, d)
    return _mm(o, ap["w_o"], matmul)


def linear_attention(cfg: dict, lp: dict, x, *, matmul: str,
                     state_dtype: str = "float32"):
    """The gated delta rule layer on ``x`` [T, d], a token at a time."""
    t = x.shape[0]
    hn, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    dv = cfg["linear_value_head_dim"]
    if cfg["linear_num_value_heads"] != hn:
        raise NotImplementedError("grouped linear-attention heads")
    taps = cfg["linear_conv_kernel_dim"]
    pre = _mm(x, lp["w_qkv"], matmul)  # [T, 2 H dk + H dv]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, pre.shape[1]), jnp.float32), pre])
    conv = lp["conv"].astype(jnp.float32)  # [taps, channels]
    mixed = sum(conv[j] * padded[j:j + t] for j in range(taps))
    mixed = jax.nn.silu(mixed)
    q = mixed[:, :hn * dk].reshape(t, hn, dk)
    k = mixed[:, hn * dk:2 * hn * dk].reshape(t, hn, dk)
    v = mixed[:, 2 * hn * dk:].reshape(t, hn, dv)
    q, k = _l2norm(q) * dk ** -0.5, _l2norm(k)
    ab = _mm(x, lp["w_ab"], matmul)  # [T, 2 H]
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ab[:, :hn] + lp["dt_bias"].astype(jnp.float32))
    beta = jax.nn.sigmoid(ab[:, hn:])
    if cfg.get("linear_allow_neg_eigval", False):
        beta = 2.0 * beta
    sdt = jnp.dtype(state_dtype)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s.astype(jnp.float32) * jnp.exp(g_t)[:, None, None]
        r = jnp.einsum("hkv,hk->hv", s, k_t, precision=HIGHEST)
        s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - r),
                           precision=HIGHEST)
        s = s.astype(sdt)
        return s, jnp.einsum("hkv,hk->hv", s.astype(jnp.float32), q_t,
                             precision=HIGHEST)

    _, o = lax.scan(step, jnp.zeros((hn, dk, dv), sdt), (q, k, v, g, beta))
    o = _rms_norm(o, lp["o_norm"], cfg["rms_norm_eps"])  # over d_v, a head
    o = o.reshape(t, hn * dv) * jax.nn.silu(_mm(x, lp["w_g"], matmul))
    return _mm(o, lp["w_o"], matmul)


def layer_forward(cfg: dict, lw: dict, x, *, matmul: str = "f32",
                  state_dtype: str = "float32", q_block: int = 256):
    """One layer on ``x`` [T, d]: a full-attention layer where ``lw`` has
    ``attn``, a linear-attention layer where it has ``lin``."""
    eps = cfg["rms_norm_eps"]
    if "attn" in lw:
        mix = full_attention(cfg, lw["attn"], x, matmul=matmul,
                             q_block=q_block)
    else:
        mix = linear_attention(cfg, lw["lin"], x, matmul=matmul,
                               state_dtype=state_dtype)
    h = x + _rms_norm(mix, lw["mixer_norm"], eps)
    mp = lw["mlp"]
    ff = _mm(jax.nn.silu(_mm(h, mp["w_gate"], matmul))
             * _mm(h, mp["w_up"], matmul), mp["w_down"], matmul)
    return h + _rms_norm(ff, lw["mlp_norm"], eps)


def head_logits(cfg: dict, top: dict, x, *, matmul: str = "f32"):
    """Logits ``[.., V]`` of final hidden states ``x`` [.., d]."""
    h = _rms_norm(x, top["final_norm"], cfg["rms_norm_eps"])
    return _mm(h, top["head"].T, matmul)


def forward(cfg: dict, params: dict, tokens, **how):
    """Logits ``[T, V]`` of one whole sequence ``tokens`` [T]."""
    with jax.default_matmul_precision("highest"):
        x = embed(cfg, params["embed"], tokens)
        for lw in params["layers"]:
            x = layer_forward(cfg, lw, x, **how)
        return head_logits(cfg, params, x,
                           matmul=how.get("matmul", "f32"))
