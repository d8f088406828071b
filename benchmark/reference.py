"""The plain reference: GPT-2 in straightforward jax.numpy.

Pre-LN GPT-2 as published (Radford et al. 2019; the ``gpt2`` config.json
fields): learned positions, causal softmax attention, tanh-GELU MLP,
tied output head, next-token cross entropy, and Adam as Kingma & Ba give
it. float32 throughout with ``precision=highest``; no kernels, no cache,
no batching tricks. Rows are processed in blocks and layers under
``lax.scan`` with recomputation so that it fits beside nothing else on a
chip; that changes memory, not arithmetic.

Imports nothing of the program and is handed nothing the program made.
Departure from the publication: LayerNorm's epsilon is the caller's
(the program hard-codes 1e-6 where GPT-2 has 1e-5, and the configuration
file notes it).

``matmul`` selects the arithmetic of every matrix product, for the
controls that ``checks.py`` must see fail:

- ``"f32"``   float32 operands, ``precision=highest`` (the reference);
- ``"fp8"``   operands scaled per tensor and rounded to float8_e4m3fn.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _round_operand(a, matmul: str):
    """``a`` as the lower precision would hold it, back in float32. The
    rounding is invisible to differentiation (straight through), so a
    backward pass sees rounded operands and exact cotangents."""
    if matmul == "f32":
        return a
    if matmul == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
        r = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(f"unknown matmul arithmetic {matmul!r}")
    return a + lax.stop_gradient(r - a)


def _mm(x, w, matmul: str):
    """``x [..., k] @ w [k, n]`` on operands as ``matmul`` holds them."""
    x = _round_operand(x, matmul)
    w = _round_operand(w, matmul)
    return jnp.matmul(x, w, precision=HIGHEST)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def _block(x, lw, n_head: int, eps: float, matmul: str):
    """One transformer block on ``x [rows, T, d]``."""
    r, t, d = x.shape
    h = _layer_norm(x, lw["ln1_g"], lw["ln1_b"], eps)
    qkv = _mm(h, lw["qkv_w"], matmul) + lw["qkv_b"]
    q, k, v = (a.reshape(r, t, n_head, d // n_head) for a in jnp.split(qkv, 3, -1))
    q = _round_operand(q, matmul)
    k = _round_operand(k, matmul)
    scores = jnp.einsum("rqhd,rkhd->rhqk", q, k, precision=HIGHEST)
    scores = scores / jnp.sqrt(jnp.float32(d // n_head))
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    probs = _round_operand(probs, matmul)
    v = _round_operand(v, matmul)
    attn = jnp.einsum("rhqk,rkhd->rqhd", probs, v, precision=HIGHEST)
    x = x + _mm(attn.reshape(r, t, d), lw["proj_w"], matmul) + lw["proj_b"]
    h = _layer_norm(x, lw["ln2_g"], lw["ln2_b"], eps)
    h = _gelu_tanh(_mm(h, lw["fc_w"], matmul) + lw["fc_b"])
    return x + _mm(h, lw["out_w"], matmul) + lw["out_b"]


_PER_LAYER = ("ln1_g", "ln1_b", "ln2_g", "ln2_b", "qkv_w", "qkv_b",
              "proj_w", "proj_b", "fc_w", "fc_b", "out_w", "out_b")


def hidden(w: dict, tokens, *, n_head: int, eps: float, matmul: str = "f32"):
    """Final-LayerNorm hidden states ``[rows, T, d]`` for ``tokens [rows, T]``."""
    t = tokens.shape[-1]
    x = w["wte"][tokens] + w["wpe"][:t]
    layers = {k: w[k] for k in _PER_LAYER}

    @jax.checkpoint
    def step(x, lw):
        return _block(x, lw, n_head, eps, matmul), None

    x, _ = lax.scan(step, x, layers)
    return _layer_norm(x, w["lnf_g"], w["lnf_b"], eps)


def logits_at(w: dict, tokens, positions, *, n_head, eps, matmul="f32"):
    """Logits ``[len(positions), vocab]`` of one sequence ``tokens [T]``."""
    h = hidden(w, tokens[None], n_head=n_head, eps=eps, matmul=matmul)[0]
    return _mm(h[positions], w["wte"].T, matmul)


def loss(w: dict, tokens, *, n_head, eps, matmul="f32"):
    """Mean next-token cross entropy of ``tokens [rows, T+1]``."""
    h = hidden(w, tokens[:, :-1], n_head=n_head, eps=eps, matmul=matmul)
    logits = _mm(h, w["wte"].T, matmul)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def loss_and_grads(w, tokens, *, n_head, eps, matmul="f32", block_rows=2):
    """Loss and gradients over all rows, accumulated block by block."""
    rows = tokens.shape[0]
    if rows % block_rows:
        raise ValueError(f"{rows} rows do not split into blocks of {block_rows}")
    blocks = tokens.reshape(rows // block_rows, block_rows, -1)
    vg = jax.value_and_grad(
        functools.partial(loss, n_head=n_head, eps=eps, matmul=matmul))

    def add(acc, blk):
        l, g = vg(w, blk)
        return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, w))
    (l, g), _ = lax.scan(add, zero, blocks)
    n = rows // block_rows
    return l / n, jax.tree.map(lambda a: a / n, g)


def adam_step(w, m, v, g, count, *, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam (Kingma & Ba 2015, algorithm 1), bias-corrected."""
    count = count + 1
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count
    w = jax.tree.map(
        lambda w_, m_, v_: w_ - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps),
        w, m, v)
    return w, m, v, count


def train_steps(w, batches, *, n_head, eps, lr, matmul="f32", block_rows=2,
                devices=None):
    """Follow ``len(batches)`` Adam steps from ``w``.

    Returns ``(losses, first_grads, final_w)``. With several ``devices``
    the rows of a batch are split among them and the gradients averaged,
    which is the same mean over the whole batch.
    """
    devices = list(devices or jax.devices()[:1])
    n = len(devices)
    lg = functools.partial(loss_and_grads, n_head=n_head, eps=eps,
                           matmul=matmul, block_rows=block_rows)
    if n == 1:
        grad_fn = jax.jit(lg)
    else:
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(devices, ("rows",))

        def per_device(w_, tok):
            l, g = lg(w_, tok)
            return lax.pmean(l, "rows"), jax.tree.map(
                lambda a: lax.pmean(a, "rows"), g)

        grad_fn = jax.jit(jax.shard_map(
            per_device, mesh=mesh, in_specs=(P(), P("rows")), out_specs=P(),
            check_vma=False))  # gradients stay per device until the pmean
    step = jax.jit(functools.partial(adam_step, lr=lr))
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    count = jnp.float32(0)
    losses, first = [], None
    for tokens in batches:
        l, g = grad_fn(w, jnp.asarray(tokens))
        if first is None:
            first = g
        w, m, v, count = step(w, m, v, g, count)
        losses.append(float(l))
    return losses, first, w
