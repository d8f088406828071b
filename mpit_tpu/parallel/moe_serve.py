"""A serving expert layer: sigmoid or softmax routing, no capacity, no
dropped token.

``parallel/moe.py`` trains with a capacity: ``top_k_dispatch`` fills
``[S, E, C]`` slots and a token past an expert's capacity is dropped. A
decode tick cannot drop a token, so this layer has no slots to fill:

1. **route** in float32: ``s = sigmoid(x W_r)`` (or a softmax over the
   router's experts, ``score="softmax"``), the ``k`` experts with
   the largest ``s + b`` (``b`` a selection bias that moves the choice and
   never the weight: DeepSeek-V3's ``noaux_tc``), weights ``scale x
   s[chosen] / sum s[chosen]``;
2. **dispatch**: the ``M x k`` (token, choice) rows are sorted by expert,
   which gives each expert a contiguous group of whatever size the
   routing made it;
3. **experts**: one grouped product a matrix over the groups
   (``jax.lax.ragged_dot``, which the TPU compiler lowers to a grouped
   matrix-multiplication kernel that visits a group's tiles only: an
   expert no token chose is never read);
4. **shared** expert on every token, beside it;
5. **combine**: rows back to token order, weighted and summed.

The layer is told which experts it holds (``held``; ``None`` = all) and
routes over all of them all the same: a choice that falls on an absent
expert adds nothing here, which is one chip's share of an expert-parallel
layer without its exchange. The shares of all chips, with the shared
expert counted once, add up to the whole layer
(``tests/test_xing4.py::test_expert_shares_add_up``).

``valid`` [M] marks the rows that are real tokens: padding rows of a
chunk and idle slots of a tick are routed nowhere, so they neither read
an expert's weights nor count in the load.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["route", "gated_mlp", "expert_layer"]


def route(x, w_router, bias, *, top_k: int, scale: float,
          normalise: bool = True, score: str = "sigmoid"):
    """``x`` [M, D] -> chosen experts ``[M, k]`` int32 and their weights
    ``[M, k]`` float32. Scores and the choice are float32 at
    ``precision=highest``: a near tie must not depend on bf16 rounding of
    the router's own product. ``score`` is ``"sigmoid"`` of each logit, or
    ``"softmax"`` over all the router's experts."""
    logits = jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST)
    if score == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"score must be 'sigmoid' or 'softmax', not {score!r}")
    _, idx = lax.top_k(s + bias.astype(jnp.float32), top_k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    if normalise:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), g * scale


def gated_mlp(x, w_gate, w_up, w_down, out_dtype=None):
    """``W_down(silu(W_gate x) * W_up x)`` on ``x`` [M, D]; the last
    product's float32 accumulator is returned as ``out_dtype`` (None:
    ``x``'s)."""
    dt = x.dtype
    g = jnp.dot(x, w_gate, preferred_element_type=jnp.float32)
    u = jnp.dot(x, w_up, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(dt)
    return jnp.dot(h, w_down, preferred_element_type=jnp.float32).astype(
        out_dtype or dt)


def expert_layer(x, mp, *, top_k: int, scale: float, n_experts: int,
                 held=None, valid=None, normalise: bool = True,
                 router_input=None, out_dtype=None, score: str = "sigmoid"):
    """The whole layer on ``x`` [M, D]. ``router_input`` [M, D] is what the
    router scores where the caller has ``x`` in more than its matrix
    products' precision (a choice between two experts all but tied should
    not turn on the rounding of ``x`` to bf16); ``score`` is
    :func:`route`'s; ``out_dtype`` is the
    result's (None: ``x``'s; the sum is float32). ``mp`` holds ``router`` [D, E],
    ``bias`` [E], the held experts' ``w_gate`` / ``w_up`` [E_held, D, F]
    and ``w_down`` [E_held, F, D] in the order of ``held``, and the shared
    expert under ``shared`` (absent: none). Returns ``(y [M, D],
    counts [E] int32)``: ``counts`` is the tokens each of the ``E``
    experts was chosen by, held or not."""
    m, _ = x.shape
    e_held = mp["w_gate"].shape[0]
    with jax.named_scope("moe_route"):
        idx, gates = route(x if router_input is None else router_input,
                           mp["router"], mp["bias"], top_k=top_k,
                           scale=scale, normalise=normalise, score=score)
        if valid is not None:
            idx = jnp.where(valid[:, None], idx, n_experts)  # nowhere
        counts = jnp.zeros((n_experts,), jnp.int32).at[idx.reshape(-1)].add(
            1, mode="drop")
    with jax.named_scope("moe_dispatch"):
        if held is None:
            local = idx
        else:
            # Global expert id -> its place among the held ones; an
            # absent expert (and "nowhere") sorts after every group.
            table = jnp.full((n_experts + 1,), e_held, jnp.int32).at[
                jnp.asarray(held, jnp.int32)].set(
                    jnp.arange(e_held, dtype=jnp.int32))
            local = table[idx]
        flat = local.reshape(-1)  # [M * k], row r is token r // k
        order = jnp.argsort(flat, stable=True)
        group_sizes = jnp.zeros((e_held,), jnp.int32).at[flat].add(
            1, mode="drop")
        xs = x[order // top_k]
        here = (flat < e_held)[order]  # rows that some held expert takes
    with jax.named_scope("moe_experts"):
        g = lax.ragged_dot(xs, mp["w_gate"], group_sizes,
                           preferred_element_type=jnp.float32)
        u = lax.ragged_dot(xs, mp["w_up"], group_sizes,
                           preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        ys = lax.ragged_dot(h, mp["w_down"], group_sizes,
                            preferred_element_type=jnp.float32)
    with jax.named_scope("moe_shared"):
        shared = (gated_mlp(x, **mp["shared"], out_dtype=jnp.float32)
                  if "shared" in mp else 0.0)
    with jax.named_scope("moe_combine"):
        # Rows past the last group hold whatever the product left there.
        ys = jnp.where(here[:, None], ys, 0.0)
        # Back to token order by a gather through the inverse of the
        # sort (a scatter lands a row at a time on the TPU).
        back = (ys * gates.reshape(-1)[order][:, None])[jnp.argsort(order)]
        y = back.reshape(m, top_k, -1).sum(axis=1) + shared
    return y.astype(out_dtype or x.dtype), counts
