"""Parameters, and the bytes and operations a call cannot avoid, of the
``laguna`` family, from the configuration's numbers alone.

A cached position of a layer is ``num_key_value_heads x head_dim`` values
in each of two seats (key and value). A full layer's attention reads every
live row; a window layer's the last ``sliding_window`` of a slot's. The
driver keeps a tick's live rows in all and its held slots, so the rows a
window layer reads are the lesser of the live rows and ``slots x
sliding_window`` (exact where every slot's context is past the window, as
in the cell: the shortest prompt is 512).
"""

from __future__ import annotations

FULL, SLIDING = "full_attention", "sliding_attention"


def router_width(m: dict) -> int:
    return (m.get("published") or {}).get("num_experts", m["num_experts"])


def kv_width(m: dict) -> int:
    return m["num_key_value_heads"] * m["head_dim"]


def heads(m: dict, kind: str) -> list:
    """The query heads of each layer of ``kind``."""
    return [h for h, k in zip(m["num_attention_heads_per_layer"],
                              m["layer_types"]) if k == kind]


def attention_params(m: dict, h_l: int) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    return d * h_l * hd * 2 + 2 * d * kv_width(m) + d * h_l


def expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def params_outside_routed(m: dict) -> int:
    """Every layer's parameters that a tick reads whatever the routing."""
    d = m["hidden_size"]
    n = sum(attention_params(m, h) + 2 * d
            for h in m["num_attention_heads_per_layer"])
    for kind in m["mlp_layer_types"]:
        n += (3 * d * m["intermediate_size"] if kind == "dense" else
              d * router_width(m)
              + 3 * d * m["shared_expert_intermediate_size"])
    return n


def params(m: dict) -> int:
    """Every parameter held: untied embedding and head, the experts held."""
    d = m["hidden_size"]
    return (2 * m["vocab_size"] * d + d + params_outside_routed(m)
            + m["mlp_layer_types"].count("sparse") * m["num_experts"]
            * expert_params(m))


def kv_bytes_per_token(m: dict, width: int, kind: str = FULL) -> int:
    """One cached position, both seats, all layers of ``kind``."""
    return 2 * kv_width(m) * width * m["layer_types"].count(kind)


def window_rows(m: dict, tick: dict) -> float:
    """Rows a window layer's attention reads in a decode tick, all slots."""
    return min(tick["rows"], tick["live_slots"] * m["sliding_window"])


def gqa_decode_attn_min(m: dict, tick: dict, width: int) -> tuple:
    """``gqa_paged_decode_attn`` in a tick that decodes: a call a layer;
    the rows a query must read, once, key and value (every live row in a
    full layer, the window's in a window layer), the queries in and the
    weighted values out; per row and query head a score and a weighted sum
    over the head's width."""
    if not tick["rows"]:
        return 0.0, 0.0
    hd, row = m["head_dim"], 2 * kv_width(m) * width
    nbytes = flops = 0.0
    for kind, rows in ((FULL, tick["rows"]), (SLIDING, window_rows(m, tick))):
        for h_l in heads(m, kind):
            nbytes += rows * row + 2 * tick["live_slots"] * h_l * hd * width
            flops += 4.0 * rows * h_l * hd
    return nbytes, flops


def gqa_chunk_attn_min(m: dict, pairs: float, keys: float,
                       width: int) -> tuple:
    """``gqa_paged_chunk_attn`` over chunk steps whose rows see ``pairs``
    (query, cached position) pairs in all and whose slots hold ``keys``
    positions when the step ends: a call a layer; per visible pair and
    query head a score and a weighted sum over the head's width; every key
    and value a step's rows see read once, the queries in and the results
    out. A window layer's row sees its window at the most: the steps'
    seats (``keys`` less what the pairs account for, over a half chunk)
    times a chunk's rows times the window bound its pairs; its keys are a
    window and a chunk a seat."""
    hd, chunk = m["head_dim"], m["serve"]["prefill_chunk"]
    seats = max(keys - pairs / chunk, 0.0) / ((chunk - 1) / 2)
    rows = seats * chunk
    window_pairs = min(pairs, rows * m["sliding_window"])
    window_keys = min(keys, seats * (m["sliding_window"] + chunk))
    row = 2 * kv_width(m) * width
    nbytes = flops = 0.0
    for kind, p, k in ((FULL, pairs, keys),
                       (SLIDING, window_pairs, window_keys)):
        for h_l in heads(m, kind):
            nbytes += k * row + 2 * rows * h_l * hd * width
            flops += 4.0 * p * h_l * hd
    return nbytes, flops
