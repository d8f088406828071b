"""Weights of the ``xing4_0`` family from ``--seed``, a layer at a time.

The benchmark makes the weights, not the program. A layer is made on the
device in one jitted call from ``fold_in(key(seed), layer)``, so that the
float32 reference can make, use and free one layer (2.98 GB at the
published widths) where all of them (19.2 GB) would not fit, and the
program is handed the same values in the tree its model declares.

Initialisation (the configuration file lists it under ``assumed``):
normal(0.02) matrices, router included; unit RMSNorm gains; the
selection bias normal(0.01), so that its path is exercised; the
hyper-connection's ``phi`` normal(0.02), its scalars ``a`` 0.01, ``b_pre``
and ``b_post`` 0, and ``b_res`` 8 on the diagonal, so that ``H_res``
starts near the identity. Matrices take ``dtype``; norm gains, the router,
the bias and the hyper-connection stay float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

TOP_LAYER = 10_000  # the fold-in of the embedding and the head


def _key(seed: int):
    """The seed's key. Its bits come from the device's own generator
    (``rbg``): 4.8 billion normals through threefry took a minute and a
    half of set-up on the v5e (my chip run, PR 26, call 1). Program and
    reference are handed the same arrays either way."""
    return jax.random.key(int(seed), impl="rbg")


def _sizes(model: dict) -> tuple:
    return tuple(model[k] for k in (
        "hidden_size", "hc_mult", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
        "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "n_shared_experts"))


def _normal(key, shape, dtype, blocks: int = 16):
    """``0.02 x normal(shape)`` as ``dtype``, drawn in float32 a block of
    the leading axis at a time (an expert, a sixteenth of a table's rows):
    the float32 draft of a whole table is 1.88 GB, which the program that
    made it kept reserved on the device for the life of the process (my
    chip run, PR 26, call 4: ``bytes_reserved`` 1,879,064,576)."""
    lead = shape[0]
    n = lead if len(shape) == 3 else (blocks if lead % blocks == 0 else 1)
    part = (lead // n, *shape[1:])
    draw = lambda k: (0.02 * jax.random.normal(k, part, jnp.float32)).astype(
        dtype)
    return jax.lax.map(draw, jax.random.split(key, n)).reshape(shape)


@functools.partial(jax.jit, static_argnames=("sizes", "dense", "dtype"))
def _layer(key, sizes, dense, dtype):
    d, n, hn, dn, dr, dv, rq, rkv, ff, fe, ne, ns = sizes
    keys = iter(jax.random.split(key, 24))
    ones = lambda w: jnp.ones((w,), jnp.float32)

    def mat(*shape):
        return _normal(next(keys), shape, dtype)

    def hc():
        return {
            "phi": 0.02 * jax.random.normal(
                next(keys), (n * d, 2 * n + n * n), jnp.float32),
            "a": jnp.full((3,), 0.01, jnp.float32),
            "b": jnp.concatenate([
                jnp.zeros((2 * n,), jnp.float32),
                8.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1)]),
        }

    def mlp(width, *lead):
        return {"w_gate": mat(*lead, d, width), "w_up": mat(*lead, d, width),
                "w_down": mat(*lead, width, d)}

    out = {
        "attn_norm": ones(d), "mlp_norm": ones(d),
        "hc_attn": hc(), "hc_mlp": hc(),
        "attn": {
            "w_dq": mat(d, rq), "q_norm": ones(rq),
            "w_uq": mat(rq, hn * (dn + dr)),
            "w_dkv": mat(d, rkv + dr), "kv_norm": ones(rkv),
            "w_ukv": mat(rkv, hn * (dn + dv)),
            "w_o": mat(hn * dv, d),
        },
    }
    if dense:
        out["mlp"] = mlp(ff)
    else:
        out["moe"] = {
            "router": 0.02 * jax.random.normal(next(keys), (d, ne),
                                               jnp.float32),
            "bias": 0.01 * jax.random.normal(next(keys), (ne,), jnp.float32),
            **mlp(fe, ne),
        }
        if ns:
            out["moe"]["shared"] = mlp(fe * ns)
    return out


def make_layer(model: dict, seed: int, layer: int, dtype=jnp.float32) -> dict:
    """Layer ``layer``'s weights; a leading dense layer has ``mlp``, the
    others ``moe``."""
    key = jax.random.fold_in(_key(seed), layer)
    return _layer(key, _sizes(model), layer < model["first_k_dense_replace"],
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
    """The same arrays as the tree ``mpit_tpu.models.xing4`` declares."""
    return {**top, "layers": list(layers)}
