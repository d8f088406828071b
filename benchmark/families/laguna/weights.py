"""Weights of the ``laguna`` family from ``--seed``, a layer at a time.

The benchmark makes the weights, not the program. A layer is made on the
device in one jitted call from ``fold_in(key(seed), layer)``, so that the
float32 reference can make, use and free one layer where all of them
would not fit, and the program is handed the same values in the tree its
model declares.

Initialisation (the configuration file lists it under ``assumed``):
normal(0.02) matrices, router included, except the query and key
projections, normal(0.03), so that attention logits spread (a standard
deviation near 2.8 in a window layer and 4.7 in a full one, whose rotated
half carries YaRN's attention factor twice, by the published widths:
near-uniform attention would hide whether the window was applied); unit
RMSNorm gains; no selection bias (the family publishes none: zeros, the
expert layer's argument). Matrices take ``dtype``; gains and the router
stay float32.

The experts HELD here are ``num_experts`` of the configuration's file (the
chip's share), the router's width ``published.num_experts``.

**The router is centred at set-up** (:func:`calibrate`). A trained router
loads its experts evenly; a random one does not: the hidden states of a
random model share a common direction (the mean of a gated MLP's output
is not zero), every expert's logit carries that direction's projection on
its router column as a fixed offset, and which experts the offsets favour,
hence how many of a tick's choices fall on the 64 held and how many of
them a tick reads, turns on the seed (the cell's rate read 1,252-1,272
over seven seeds before this, 1.6 %, against a bound of 1 %). The family
publishes no selection bias to balance with, so the balance is put where
training would put it, into the router's weights: a layer at a time in
order, on the normed hidden states ``u`` of seeded calibration sequences
through ``reference.py``, ``W_r <- W_r - m (m^T W_r) / (m^T m)`` with ``m``
the mean of ``u``: every expert's mean logit is then equal (zero). The
model's equations are untouched; the program and the check's reference are
handed the same centred routers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.laguna import reference

TOP_LAYER = 10_000  # the fold-in of the embedding and the head
QK_STD = 0.03


def _key(seed: int):
    """The seed's key, bits from the device's own generator (``rbg``), as
    the other families' (threefry took a minute and a half of set-up for
    4.8 billion normals: PR 26)."""
    return jax.random.key(int(seed), impl="rbg")


def router_width(model: dict) -> int:
    return (model.get("published") or {}).get(
        "num_experts", model["num_experts"])


def held(model: dict) -> tuple | None:
    """Global ids of the experts the weights hold; None = every one."""
    here, rank = model["num_experts"], model.get("ep_rank", 0)
    if router_width(model) == here:
        return None
    return tuple(range(rank * here, (rank + 1) * here))


def _normal(key, shape, dtype, std: float = 0.02, blocks: int = 16):
    """``std x normal(shape)`` as ``dtype``, drawn in float32 a block of
    the leading axis at a time (an expert, a sixteenth of a table's rows),
    so that no table's float32 draft exists whole."""
    lead = shape[0]
    n = lead if len(shape) == 3 else (blocks if lead % blocks == 0 else 1)
    part = (lead // n, *shape[1:])
    draw = lambda k: (std * jax.random.normal(k, part, jnp.float32)).astype(
        dtype)
    return jax.lax.map(draw, jax.random.split(key, n)).reshape(shape)


@functools.partial(jax.jit, static_argnames=("sizes", "dense", "dtype"))
def _layer(key, sizes, dense, dtype):
    d, h_l, kv, hd, ff, fe, fs, ne, nr = sizes
    keys = iter(jax.random.split(key, 16))
    ones = lambda w: jnp.ones((w,), jnp.float32)

    def mat(*shape, std=0.02):
        return _normal(next(keys), shape, dtype, std)

    def mlp(width, *lead):
        return {"w_gate": mat(*lead, d, width), "w_up": mat(*lead, d, width),
                "w_down": mat(*lead, width, d)}

    out = {
        "attn_norm": ones(d), "mlp_norm": ones(d),
        "attn": {"w_q": mat(d, h_l * hd, std=QK_STD),
                 "w_k": mat(d, kv, std=QK_STD), "w_v": mat(d, kv),
                 "w_g": mat(d, h_l), "w_o": mat(h_l * hd, d)},
    }
    if dense:
        out["mlp"] = mlp(ff)
    else:
        out["moe"] = {
            "router": 0.02 * jax.random.normal(next(keys), (d, nr),
                                               jnp.float32),
            "bias": jnp.zeros((nr,), jnp.float32),
            **mlp(fe, ne), "shared": mlp(fs),
        }
    return out


def make_layer(model: dict, seed: int, layer: int, dtype=jnp.float32) -> dict:
    """Layer ``layer``'s weights: ``mlp`` where ``mlp_layer_types`` says
    dense, else ``moe``; its own count of query heads."""
    sizes = (
        model["hidden_size"], model["num_attention_heads_per_layer"][layer],
        model["num_key_value_heads"] * model["head_dim"], model["head_dim"],
        model["intermediate_size"], model["moe_intermediate_size"],
        model["shared_expert_intermediate_size"], model["num_experts"],
        router_width(model))
    key = jax.random.fold_in(_key(seed), layer)
    return _layer(key, sizes, model["mlp_layer_types"][layer] == "dense",
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
    """The same arrays as the tree ``mpit_tpu.models.laguna`` declares."""
    return {**top, "layers": list(layers)}


# Calibration: sequences of ids drawn as the traffic draws them.
CALIBRATION = {"sequences": 16, "tokens": 512}


def calibration_tokens(model: dict, seed: int, sizes=None) -> np.ndarray:
    sizes = sizes or model.get("calibration", CALIBRATION)
    rng = np.random.default_rng([int(seed), 11])
    return rng.integers(0, model["vocab_size"],
                        size=(sizes["sequences"], sizes["tokens"]))


@jax.jit
def centre_router(router, u):
    """``router`` [d, E] less its component along the mean ``m`` of the
    normed hidden states ``u`` [N, d]: ``m^T W_r = 0`` afterwards."""
    m = jnp.mean(u.astype(jnp.float32), axis=0)
    return router - jnp.outer(m, jnp.dot(
        m, router, precision=jax.lax.Precision.HIGHEST)) / jnp.dot(m, m)


def calibrate(model: dict, top: dict, layers: list, sequences, say) -> list:
    """Centre every expert layer's router, in place (the program's tree
    holds these very dicts), a layer at a time in order, each on the
    hidden states the layers before it, already centred, give. Returns
    the routers (None for a dense layer), for the check's reference."""
    eps = model["rms_norm_eps"]
    positions = jnp.arange(sequences.shape[1])
    attend = jax.jit(
        lambda lw, x, kind: x + reference.attention(
            jax.tree.map(lambda a: a.astype(jnp.float32), lw["attn"]),
            reference._rms_norm(x, lw["attn_norm"], eps), positions, model,
            kind, q_block=128),
        static_argnames=("kind",))
    normed = jax.jit(lambda lw, x: reference._rms_norm(
        x, lw["mlp_norm"], eps))
    second = jax.jit(lambda lw, x, u: x + (
        reference.gated_mlp(u, lw["mlp"]) if "mlp" in lw else
        reference.experts(u, jax.tree.map(
            lambda a: a.astype(jnp.float32), lw["moe"]), model,
            held=held(model))))
    xs = [reference.embed(top["embed"], jnp.asarray(seq))
          for seq in sequences]
    routers = []
    for lw, kind in zip(layers, model["layer_types"]):
        xs = [attend(lw, x, kind) for x in xs]
        us = [normed(lw, x) for x in xs]
        if "moe" in lw:
            before = lw["moe"]["router"]
            lw["moe"]["router"] = centre_router(before, jnp.concatenate(us))
            mean = jnp.mean(jnp.concatenate(us), axis=0)
            say("router_centred", layer=len(routers), tokens=int(
                sequences.size), common_share=float(
                    jnp.linalg.norm(mean) / jnp.sqrt(jnp.mean(jnp.sum(
                        jnp.square(jnp.concatenate(us)), axis=-1)))),
                offset_std_before=float(jnp.std(jnp.dot(mean, before))),
                offset_std_after=float(jnp.std(jnp.dot(
                    mean, lw["moe"]["router"]))))
            routers.append(lw["moe"]["router"])
        else:
            routers.append(None)
        xs = [second(lw, x, u) for x, u in zip(xs, us)]
    return routers


def with_router(lw: dict, router) -> dict:
    """``lw`` with the centred ``router`` (None: as it is)."""
    if router is None:
        return lw
    return {**lw, "moe": {**lw["moe"], "router": router}}
