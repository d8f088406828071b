"""Parameters, and the bytes and operations a call cannot avoid, of the
``glm_moe_dsa`` family, from the configuration's numbers alone.

A cached position is counted at its STORED width: the latent
(``kv_lora_rank``), the key's rotary part padded to whole 128-lane tiles,
and in a layer that runs an indexer the index key. A decode tick's
attention reads ``index_topk`` rows a slot and layer at the most: the
driver keeps a tick's live rows in all and its held slots, so the rows
read are the lesser of the live rows and ``slots x index_topk`` (exact
where every slot's context is past ``index_topk``, as in the cell).
"""

from __future__ import annotations

# Latent attention and an expert are the same arithmetic as xing4's.
from benchmark.families.xing4.costs import (  # noqa: F401
    attention_params,
    expert_params,
    lane_pad,
)


def indexer_params(m: dict) -> int:
    hi, di = m["index_n_heads"], m["index_head_dim"]
    return (m["q_lora_rank"] * hi * di + m["hidden_size"] * di + 2 * di
            + m["hidden_size"] * hi)


def router_width(m: dict) -> int:
    return m.get("published", {}).get(
        "n_routed_experts", m["n_routed_experts"])


def full_layers(m: dict) -> int:
    return m["indexer_types"].count("full")


def moe_layers(m: dict) -> int:
    return m["mlp_layer_types"].count("sparse")


def params_outside_routed(m: dict) -> int:
    """Every layer's parameters that a tick reads whatever the routing:
    attention, the indexers, both norms, and either the dense MLP or the
    router, its bias and the shared experts."""
    d, n = m["hidden_size"], m["num_hidden_layers"]
    dense = n - moe_layers(m)
    return (n * (attention_params(m) + 2 * d)
            + full_layers(m) * indexer_params(m)
            + dense * 3 * d * m["intermediate_size"]
            + moe_layers(m) * (d * router_width(m) + router_width(m)
                               + m["n_shared_experts"] * expert_params(m)))


def params(m: dict) -> int:
    """Every parameter held: untied embedding and head, the experts held."""
    d = m["hidden_size"]
    return (2 * m["vocab_size"] * d + d + params_outside_routed(m)
            + moe_layers(m) * m["n_routed_experts"] * expert_params(m))


def kv_values_per_token_layer(m: dict) -> int:
    return m["kv_lora_rank"] + lane_pad(m["qk_rope_head_dim"])


def kv_bytes_per_token(m: dict, width: int) -> int:
    """One cached position, all layers and seats, at the stored width."""
    return width * (m["num_hidden_layers"] * kv_values_per_token_layer(m)
                    + full_layers(m) * lane_pad(m["index_head_dim"]))


def rows_read(m: dict, tick: dict) -> float:
    """Rows a layer's attention reads in a decode tick, all slots."""
    return min(tick["rows"], tick["live_slots"] * m["index_topk"])


def decode_tick_min_bytes(m: dict, tick: dict, width: int) -> float:
    """Bytes a decode tick cannot avoid reading: the head (the embedding
    is a gather of the tick's rows), every weight outside the routed
    experts, the experts held that the tick's tokens hit (the tick's
    experts with a token, a mean over the expert layers, times the share
    of the router's experts held here), every live row's index key in the
    layers that run an indexer, and the rows attention reads."""
    d = m["hidden_size"]
    hit = (tick.get("experts_hit_decode", 0.0)
           * m["n_routed_experts"] / router_width(m))
    weights = (m["vocab_size"] * d + d + params_outside_routed(m)
               + moe_layers(m) * hit * expert_params(m))
    cache = (tick["rows"] * full_layers(m) * lane_pad(m["index_head_dim"])
             + rows_read(m, tick) * m["num_hidden_layers"]
             * kv_values_per_token_layer(m))
    return (weights + cache) * width


def dsa_index_scores_tick_min(m: dict, tick: dict, width: int) -> tuple:
    """``dsa_index_scores_tick`` in a tick that decodes: a call a layer
    with an indexer; every live row's index key read once and its score
    written in float32; per row and index head a dot product over the
    key's width, a relu and a weighted sum."""
    if not tick["rows"]:
        return 0.0, 0.0
    di, hi = lane_pad(m["index_head_dim"]), m["index_n_heads"]
    calls = full_layers(m)
    return (calls * tick["rows"] * (di * width + 4),
            calls * tick["rows"] * hi * (2.0 * m["index_head_dim"] + 3))


def dsa_sparse_attn_min(m: dict, tick: dict, width: int) -> tuple:
    """``dsa_sparse_attn`` in a tick that decodes: a call a layer; the
    chosen rows read once at their stored width, the absorbed queries in
    and the weighted latents out; per row and head a score over the
    stored width and a weighted sum over the latent."""
    if not tick["rows"]:
        return 0.0, 0.0
    c, hn = m["kv_lora_rank"], m["num_attention_heads"]
    row = kv_values_per_token_layer(m)
    rows = rows_read(m, tick)
    io = tick["live_slots"] * hn * (row + c) * width
    return (m["num_hidden_layers"] * (rows * row * width + io),
            m["num_hidden_layers"] * 2.0 * rows * hn * (row + c))


def dsa_index_scores_chunk_min(m: dict, pairs: float, keys: float,
                               width: int) -> tuple:
    """``dsa_index_scores_chunk`` over chunk steps whose rows see
    ``pairs`` (query, cached position) pairs in all and whose slots hold
    ``keys`` positions when the step ends: a call a layer with an
    indexer; every key read once a step, every visible pair's score
    written in float32; per pair and index head a dot product, a relu
    and a weighted sum."""
    di, hi = lane_pad(m["index_head_dim"]), m["index_n_heads"]
    calls = full_layers(m)
    return (calls * (keys * di * width + pairs * 4),
            calls * pairs * hi * (2.0 * m["index_head_dim"] + 3))
