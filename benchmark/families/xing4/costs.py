"""Parameters, and the bytes and operations a call cannot avoid, of the
``xing4_0`` family, from the configuration's numbers alone.

The cached position of a layer is counted at its STORED width: the latent
(``kv_lora_rank``) and the key's rotary part padded to whole 128-lane
tiles, as the program's pool holds them.
"""

from __future__ import annotations


def lane_pad(n: int) -> int:
    return -(-n // 128) * 128


def attention_params(m: dict) -> int:
    d, hn = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    rq, rkv = m["q_lora_rank"], m["kv_lora_rank"]
    return (d * rq + rq + rq * hn * (dn + dr) + d * (rkv + dr) + rkv
            + rkv * hn * (dn + dv) + hn * dv * d)


def hc_params(m: dict) -> int:
    n = m["hc_mult"]
    width = 2 * n + n * n
    return n * m["hidden_size"] * width + 3 + width


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def layer_params_outside_routed(m: dict, dense: bool) -> int:
    """A layer's parameters that every tick reads whatever the routing:
    attention, both hyper-connections, both norms, and either the dense
    MLP or the router, its bias and the shared experts."""
    d = m["hidden_size"]
    n = attention_params(m) + 2 * hc_params(m) + 2 * d
    if dense:
        return n + 3 * d * m["intermediate_size"]
    return (n + d * m["n_routed_experts"] + m["n_routed_experts"]
            + m["n_shared_experts"] * expert_params(m))


def dense_layers(m: dict) -> int:
    return min(m["first_k_dense_replace"], m["num_hidden_layers"])


def moe_layers(m: dict) -> int:
    return m["num_hidden_layers"] - dense_layers(m)


def params(m: dict) -> int:
    """Every parameter held: untied embedding and head, all experts."""
    d = m["hidden_size"]
    return (2 * m["vocab_size"] * d + d
            + dense_layers(m) * layer_params_outside_routed(m, True)
            + moe_layers(m) * (layer_params_outside_routed(m, False)
                               + m["n_routed_experts"] * expert_params(m)))


def kv_values_per_token_layer(m: dict) -> int:
    return m["kv_lora_rank"] + lane_pad(m["qk_rope_head_dim"])


def kv_bytes_per_token(m: dict, bytes_per_value: int) -> int:
    """One cached position, all layers, at the stored width."""
    return (m["num_hidden_layers"] * kv_values_per_token_layer(m)
            * bytes_per_value)


def decode_tick_min_bytes(m: dict, live_rows: int, experts_hit: float,
                          weight_bytes: int, kv_value_bytes: int) -> float:
    """Bytes a decode tick cannot avoid reading: the head (the embedding
    is a gather of the tick's rows), every weight outside the routed
    experts, the routed experts the tick's tokens hit (``experts_hit``: a
    mean over the expert layers), and every live cache row once."""
    d = m["hidden_size"]
    weights = (m["vocab_size"] * d + d
               + dense_layers(m) * layer_params_outside_routed(m, True)
               + moe_layers(m) * (layer_params_outside_routed(m, False)
                                  + experts_hit * expert_params(m)))
    return (weights * weight_bytes
            + live_rows * kv_bytes_per_token(m, kv_value_bytes))


def mla_decode_attn_min(m: dict, live_rows: int, slots: int,
                        kv_value_bytes: int) -> tuple:
    """``(bytes, operations)`` one call of the latent decode kernel
    (``mla_paged_decode_attn``; one layer, one tick) cannot avoid: every
    live row read once at its stored width, the absorbed queries in and
    the weighted latents out; per row and head a score over the stored
    width and a weighted sum over the latent."""
    c, hn = m["kv_lora_rank"], m["num_attention_heads"]
    row = kv_values_per_token_layer(m)
    io = slots * hn * (row + c) * kv_value_bytes
    return (live_rows * row * kv_value_bytes + io,
            2.0 * live_rows * hn * (row + c))
