"""GPT-2 weights made from ``--seed`` on the device, in one jitted call.

The benchmark makes the weights, not the program: the program is handed
them in the parameter tree its model declares, and the plain reference
(``reference.py``) is handed the same arrays stacked by layer. Values
follow GPT-2's own initialisation: normal(0.02) matrices, residual
projections scaled by 1/sqrt(2 L), normal(0.01) positions, zero biases,
unit LayerNorm gains.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# name -> (shape builder, std builder); d = d_model, f = d_ff, L = layers.
_STACKED = {
    "qkv_w": (lambda d, f: (d, 3 * d), lambda L: 0.02),
    "proj_w": (lambda d, f: (d, d), lambda L: 0.02 / (2 * L) ** 0.5),
    "fc_w": (lambda d, f: (d, f), lambda L: 0.02),
    "out_w": (lambda d, f: (f, d), lambda L: 0.02 / (2 * L) ** 0.5),
}
_BIASES = {"qkv_b": lambda d, f: 3 * d, "proj_b": lambda d, f: d,
           "fc_b": lambda d, f: f, "out_b": lambda d, f: d}


@functools.partial(jax.jit, static_argnames=("cfg_key", "dtype"))
def _make(key, cfg_key, dtype):
    vocab, positions, L, d, f = cfg_key
    keys = jax.random.split(key, 2 + len(_STACKED))
    out = {
        "wte": 0.02 * jax.random.normal(keys[0], (vocab, d), jnp.float32),
        "wpe": 0.01 * jax.random.normal(keys[1], (positions, d), jnp.float32),
        "ln1_g": jnp.ones((L, d)), "ln1_b": jnp.zeros((L, d)),
        "ln2_g": jnp.ones((L, d)), "ln2_b": jnp.zeros((L, d)),
        "lnf_g": jnp.ones((d,)), "lnf_b": jnp.zeros((d,)),
    }
    for k, (name, (shape, std)) in zip(keys[2:], _STACKED.items()):
        out[name] = std(L) * jax.random.normal(k, (L, *shape(d, f)), jnp.float32)
    for name, width in _BIASES.items():
        out[name] = jnp.zeros((L, width(d, f)))
    return jax.tree.map(lambda a: a.astype(dtype), out)


def make_stacked(model: dict, seed: int, dtype=jnp.float32) -> dict:
    """Weights stacked by layer (``[L, ...]`` leaves), the reference's form."""
    cfg_key = (model["vocab_size"], model["n_positions"], model["n_layer"],
               model["n_embd"], model["n_inner"])
    # jax.random.key takes any non-negative seed below 2**63.
    return _make(jax.random.key(int(seed)), cfg_key, jnp.dtype(dtype))


@jax.jit
def to_program_tree(stacked: dict) -> dict:
    """The same arrays as the parameter tree ``mpit_tpu.models.GPT2`` declares."""
    L = stacked["qkv_w"].shape[0]
    tree = {"wte": stacked["wte"], "wpe": stacked["wpe"],
            "ln_f": {"scale": stacked["lnf_g"], "bias": stacked["lnf_b"]}}
    for i in range(L):
        tree[f"block_{i}"] = {
            "ln1": {"scale": stacked["ln1_g"][i], "bias": stacked["ln1_b"][i]},
            "ln2": {"scale": stacked["ln2_g"][i], "bias": stacked["ln2_b"][i]},
            **{n: {"kernel": stacked[f"{n}_w"][i], "bias": stacked[f"{n}_b"][i]}
               for n in ("qkv", "proj", "fc", "out")},
        }
    return tree

