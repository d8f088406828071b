"""Weights of the ``olmo_hybrid`` family from ``--seed``, a layer at a time.

The benchmark makes the weights, not the program. A layer is made on the
device in one jitted call from ``fold_in(key(seed), layer)``, so that the
float32 reference can make, use and free one layer, and the program is
handed the same values in the tree its model declares.

Initialisation (the configuration file lists it under ``assumed``):
normal(0.02) matrices; unit RMSNorm gains; the convolution's taps
normal(0.5) in float32; ``dt_bias`` uniform(-1, 1) and ``A_log`` such that
a head's decay ``alpha`` at a zero projection is log-uniform in ``1 -
alpha`` over (1e-4, 0.1): heads that forget in ten tokens beside heads
that remember ten thousand, so that the decay path is exercised. Matrices
take ``dtype``; norm gains, taps, ``A_log`` and ``dt_bias`` stay float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

TOP_LAYER = 10_000  # the fold-in of the embedding and the head
FULL = "full_attention"


def _key(seed: int):
    """The seed's key, its bits from the device's own generator (``rbg``):
    threefry took a minute and a half of set-up for 4.8 billion normals
    (PERF.md, PR 26)."""
    return jax.random.key(int(seed), impl="rbg")


def conv_channels(model: dict) -> int:
    return model["linear_num_key_heads"] * (
        2 * model["linear_key_head_dim"] + model["linear_value_head_dim"])


def _normal(key, shape, dtype, blocks: int = 16):
    """``0.02 x normal(shape)`` as ``dtype``, drawn in float32 a block of
    the leading axis at a time (the float32 draft of a whole table would
    stay reserved on the device: PERF.md, PR 26)."""
    n = blocks if shape[0] % blocks == 0 else 1
    part = (shape[0] // n, *shape[1:])
    draw = lambda k: (0.02 * jax.random.normal(k, part, jnp.float32)).astype(
        dtype)
    return jax.lax.map(draw, jax.random.split(key, n)).reshape(shape)


@functools.partial(jax.jit, static_argnames=("sizes", "full", "dtype"))
def _layer(key, sizes, full, dtype):
    d, f, hn, dv, taps, channels = sizes
    keys = iter(jax.random.split(key, 16))
    ones = lambda w: jnp.ones((w,), jnp.float32)
    mat = lambda *shape: _normal(next(keys), shape, dtype)
    out = {
        "mixer_norm": ones(d), "mlp_norm": ones(d),
        "mlp": {"w_gate": mat(d, f), "w_up": mat(d, f), "w_down": mat(f, d)},
    }
    if full:
        out["attn"] = {"w_q": mat(d, d), "w_k": mat(d, d), "w_v": mat(d, d),
                       "w_o": mat(d, d), "q_norm": ones(d), "k_norm": ones(d)}
        return out
    k_a, k_d = jax.random.split(next(keys))
    one_minus = jnp.exp(jax.random.uniform(
        k_a, (hn,), jnp.float32, math.log(1e-4), math.log(0.1)))
    dt_bias = jax.random.uniform(k_d, (hn,), jnp.float32, -1.0, 1.0)
    out["lin"] = {
        "w_qkv": mat(d, channels),
        "conv": 0.5 * jax.random.normal(next(keys), (taps, channels),
                                        jnp.float32),
        "w_ab": mat(d, 2 * hn),
        "A_log": jnp.log(-jnp.log1p(-one_minus) / jax.nn.softplus(dt_bias)),
        "dt_bias": dt_bias,
        "w_g": mat(d, hn * dv), "o_norm": ones(dv), "w_o": mat(hn * dv, d),
    }
    return out


def make_layer(model: dict, seed: int, layer: int, dtype=jnp.float32) -> dict:
    """Layer ``layer``'s weights: ``attn`` where ``layer_types`` says full
    attention, ``lin`` where it says linear."""
    key = jax.random.fold_in(_key(seed), layer)
    sizes = (model["hidden_size"], model["intermediate_size"],
             model["linear_num_key_heads"], model["linear_value_head_dim"],
             model["linear_conv_kernel_dim"], conv_channels(model))
    return _layer(key, sizes, model["layer_types"][layer] == FULL,
                  jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _table(key, shape, dtype):
    return _normal(key, shape, dtype)


def make_top(model: dict, seed: int, dtype=jnp.float32) -> dict:
    """The embedding, the untied head and the final norm's gain."""
    k_e, k_h = jax.random.split(jax.random.fold_in(_key(seed), TOP_LAYER))
    shape = (model["vocab_size"], model["hidden_size"])
    return {"embed": _table(k_e, shape, jnp.dtype(dtype)),
            "head": _table(k_h, shape, jnp.dtype(dtype)),
            "final_norm": jnp.ones((model["hidden_size"],), jnp.float32)}


def to_program_tree(top: dict, layers: list) -> dict:
    """The same arrays as the tree ``mpit_tpu.models.olmo_hybrid`` declares."""
    return {**top, "layers": list(layers)}
