"""Weights of the ``glm_moe_dsa`` family from ``--seed``, a layer at a time.

The benchmark makes the weights, not the program. A layer is made on the
device in one jitted call from ``fold_in(key(seed), layer)``, so that the
float32 reference can make, use and free one layer where all of them
would not fit, and the program is handed the same values in the tree its
model declares.

Initialisation (the configuration file lists it under ``assumed``):
normal(0.02) matrices, router included; unit RMSNorm gains except those of
the query and key-value latents, drawn ``|2.5 + 0.25 normal|`` so that
attention logits spread (a standard deviation near 2.8 at the published
widths: near-uniform attention would hide whether the choice of rows was
applied); the indexer's LayerNorm gain 1 and bias 0. Matrices take
``dtype``; gains, the router and the bias stay float32.

The experts HELD here are ``n_routed_experts`` of the configuration's file
(the chip's share), the router's width ``published.n_routed_experts``:
expert ``e`` of the deployment is drawn from ``fold_in(layer key, e)``, so
another share of the same seed would hold other experts of the same model.

**The selection bias** (``e_score_correction_bias``) is a weight of the
published model that exists to level the experts' load, and a random
router with a zero bias loads them unevenly: how many of a tick's choices
fall on the 16 experts held would then turn on the seed. :func:`balance`
sets it by the published rule (auxiliary-loss-free load balancing,
DeepSeek-V3 section 2.1.2) from the router's scores on calibration hidden
states, and :func:`calibrate` makes those states: a plain forward of the
calibration sequences (``reference.py``'s, float32), layer by layer in
order, each expert layer's bias set before its output goes on to the next.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.glm_dsa import reference
# The seed's key (the device's own generator), the blockwise normal draw,
# the two tables and the program's tree are xing4's: one way to make them.
from benchmark.families.xing4.weights import (  # noqa: F401
    _key,
    _normal,
    make_top,
    to_program_tree,
)

BALANCE_WITHIN = 0.15  # an expert's load against the mean, at the most


def router_width(model: dict) -> int:
    return model.get("published", {}).get(
        "n_routed_experts", model["n_routed_experts"])


def held(model: dict):
    """Global ids of the experts held; None where all are."""
    here, every = model["n_routed_experts"], router_width(model)
    if here == every:
        return None
    rank = model.get("ep_rank", 0)
    return tuple(range(rank * here, (rank + 1) * here))


def _sizes(model: dict) -> tuple:
    return tuple(model[k] for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
        "intermediate_size", "moe_intermediate_size", "n_shared_experts",
        "index_n_heads", "index_head_dim")) + (router_width(model),)


@functools.partial(
    jax.jit, static_argnames=("sizes", "dense", "indexer", "experts", "dtype"))
def _layer(key, sizes, dense, indexer, experts, dtype):
    d, hn, dn, dr, dv, rq, rkv, ff, fe, ns, hi, di, ne = sizes
    keys = iter(jax.random.split(key, 24))
    ones = lambda w: jnp.ones((w,), jnp.float32)
    gain = lambda w: jnp.abs(
        2.5 + 0.25 * jax.random.normal(next(keys), (w,), jnp.float32))
    mat = lambda *shape: _normal(next(keys), shape, dtype)

    def mlp(width, k=None):
        ks = iter(jax.random.split(k, 3)) if k is not None else keys
        m = lambda *shape: _normal(next(ks), shape, dtype)
        return {"w_gate": m(d, width), "w_up": m(d, width),
                "w_down": m(width, d)}

    out = {
        "attn_norm": ones(d), "mlp_norm": ones(d),
        "attn": {
            "w_dq": mat(d, rq), "q_norm": gain(rq),
            "w_uq": mat(rq, hn * (dn + dr)),
            "w_dkv": mat(d, rkv + dr), "kv_norm": gain(rkv),
            "w_ukv": mat(rkv, hn * (dn + dv)),
            "w_o": mat(hn * dv, d),
        },
    }
    if indexer:
        out["indexer"] = {
            "wq_b": mat(rq, hi * di), "wk": mat(d, di),
            "k_norm_g": ones(di), "k_norm_b": jnp.zeros((di,), jnp.float32),
            "w_proj": mat(d, hi),
        }
    if dense:
        out["mlp"] = mlp(ff)
        return out
    k_experts = next(keys)
    one = lambda e: mlp(fe, jax.random.fold_in(k_experts, e))
    out["moe"] = {
        "router": 0.02 * jax.random.normal(next(keys), (d, ne), jnp.float32),
        "bias": jnp.zeros((ne,), jnp.float32),
        **jax.lax.map(one, jnp.asarray(experts, jnp.int32)),
    }
    if ns:
        out["moe"]["shared"] = mlp(fe * ns)
    return out


def make_layer(model: dict, seed: int, layer: int, dtype=jnp.float32,
               bias=None) -> dict:
    """Layer ``layer``'s weights: ``mlp`` where ``mlp_layer_types`` says
    dense, else ``moe`` with the experts held; ``indexer`` where
    ``indexer_types`` says full. ``bias`` [E]: the expert layer's
    selection bias (zeros until :func:`calibrate` has set it)."""
    key = jax.random.fold_in(_key(seed), layer)
    dense = model["mlp_layer_types"][layer] == "dense"
    here = held(model) or tuple(range(router_width(model)))
    lw = _layer(key, _sizes(model), dense,
                model["indexer_types"][layer] == "full", here,
                jnp.dtype(dtype))
    if bias is not None and not dense:
        lw["moe"]["bias"] = jnp.asarray(bias, jnp.float32)
    return lw


# -- the selection bias ------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("top_k", "rounds"))
def balance(scores, top_k: int, rounds: int = 800):
    """The published rule on router scores ``[N, E]`` (float32 sigmoid
    outputs): ``b_i += u x sign(mean load - load_i)``, the load being the
    tokens whose ``top_k`` of ``score + b`` hold expert ``i``, ``u``
    decaying from 0.05 to 0.0001. Returns ``(bias [E], worst)``: the bias
    of the round whose most uneven expert was nearest the mean load, and
    how far off the mean it was (a share of the mean)."""
    n, e = scores.shape
    mean = n * top_k / e

    def load(b):
        _, idx = jax.lax.top_k(scores + b, top_k)
        return jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)

    def step(carry, i):
        b, best_b, best = carry
        got = load(b)
        off = jnp.max(jnp.abs(got - mean)) / mean
        best_b = jnp.where(off < best, b, best_b)
        best = jnp.minimum(off, best)
        u = 0.05 * 0.002 ** (i / rounds)
        return (b + u * jnp.sign(mean - got), best_b, best), None

    zero = jnp.zeros((e,), jnp.float32)
    (_, bias, worst), _ = jax.lax.scan(
        step, (zero, zero, jnp.float32(jnp.inf)),
        jnp.arange(rounds, dtype=jnp.float32))
    return bias - jnp.mean(bias), worst


def _calibration_layer(model, lw, x, positions, chosen, matmul):
    """``reference.layer_forward`` in two halves, so that the bias can be
    set between them: returns the attention half's output, the
    normalised input of the second sublayer, and the choice."""
    eps = model["rms_norm_eps"]
    # The weights as they are: the reference's products cast an operand
    # at a time, where a float32 copy of a whole layer would not fit
    # beside the engine.
    a, chosen = reference.attention(
        lw["attn"], reference._rms_norm(x, lw["attn_norm"], eps),
        positions, model, chosen, matmul, 256, lw.get("indexer"))
    x = x + a
    return x, reference._rms_norm(x, lw["mlp_norm"], eps), chosen


def calibrate(model: dict, top: dict, layers: list, sequences, say=None):
    """Set every expert layer's selection bias, in place in ``layers``
    (the program's tree holds the same dicts), from the calibration
    ``sequences`` [S, T] int32: the plain forward of
    ``reference.py`` a sequence at a time, one layer after the other, the
    layer's bias balanced on the router's scores over all sequences before
    its output goes on. Returns the biases, one a layer (None: dense)."""
    seqs = jnp.asarray(np.asarray(sequences, np.int32))
    t = seqs.shape[1]
    positions = jnp.arange(t)
    held_ids = held(model)
    half = jax.jit(functools.partial(_calibration_layer, model),
                   static_argnames=("matmul",))
    second = jax.jit(
        lambda lw, h: reference.gated_mlp(h, lw["mlp"]) if "mlp" in lw
        else reference.experts(h, lw["moe"], model, held=held_ids))
    xs = [reference.embed(model, top["embed"], s) for s in seqs]
    chosen = [reference.no_choice(model, t) for _ in seqs]
    biases = []
    for i, lw in enumerate(layers):
        hs = []
        for j in range(len(xs)):
            xs[j], h, chosen[j] = half(lw, xs[j], positions, chosen[j],
                                       matmul="f32")
            hs.append(h)
        bias = None
        if "moe" in lw:
            scores = jax.nn.sigmoid(jnp.matmul(
                jnp.concatenate(hs), lw["moe"]["router"],
                precision=jax.lax.Precision.HIGHEST))
            bias, worst = balance(scores, model["num_experts_per_tok"])
            lw["moe"]["bias"] = bias
            if say is not None:
                _, idx = jax.lax.top_k(scores + bias,
                                       model["num_experts_per_tok"])
                here = (jnp.isin(idx, jnp.asarray(held_ids)).mean()
                        if held_ids is not None else 1.0)
                say("moe_balance", layer=i, tokens=int(scores.shape[0]),
                    worst_off_mean=float(worst),
                    balanced=bool(worst <= BALANCE_WITHIN),
                    choices_here_pct=100.0 * float(here),
                    bias_abs_max=float(jnp.max(jnp.abs(bias))))
        biases.append(None if bias is None else np.asarray(bias))
        for j in range(len(xs)):
            xs[j] = xs[j] + second(lw, hs[j])
    return biases
