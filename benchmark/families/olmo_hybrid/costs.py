"""Parameters, and the bytes and operations a call cannot avoid, of the
``olmo_hybrid`` family, from the configuration's numbers alone.

A tick is what the driver kept of one (``benchmark/family_ticks.py``):
``rows`` the live cache rows, ``live_slots`` the slots held,
``prefill_rows_valid`` the prompt tokens its chunk step took. A kernel's
cost is ``(bytes, operations)`` of ALL its calls in that tick (one a
linear-attention layer), or zeros where the tick does not call it.
"""

from __future__ import annotations

FULL = "full_attention"
STATE_BYTES = 4  # the recurrent state is float32, whatever the weights are


def linear_layers(m: dict) -> int:
    return sum(1 for kind in m["layer_types"] if kind != FULL)


def full_layers(m: dict) -> int:
    return sum(1 for kind in m["layer_types"] if kind == FULL)


def conv_channels(m: dict) -> int:
    return m["linear_num_key_heads"] * (
        2 * m["linear_key_head_dim"] + m["linear_value_head_dim"])


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def full_layer_params(m: dict) -> int:
    d = m["hidden_size"]
    return 4 * d * d + 2 * d + mlp_params(m) + 2 * d


def linear_layer_params(m: dict) -> int:
    d, hn = m["hidden_size"], m["linear_num_key_heads"]
    dv, c = m["linear_value_head_dim"], conv_channels(m)
    mixer = (d * c + m["linear_conv_kernel_dim"] * c + d * 2 * hn + 2 * hn
             + 2 * d * hn * dv + dv)
    return mixer + mlp_params(m) + 2 * d


def params(m: dict) -> int:
    """Every parameter held: the untied embedding and head too."""
    d = m["hidden_size"]
    return (2 * m["vocab_size"] * d + d
            + linear_layers(m) * linear_layer_params(m)
            + full_layers(m) * full_layer_params(m))


def kv_bytes_per_token(m: dict, bytes_per_value: int) -> int:
    """One cached position: a key and a value in every full layer."""
    return full_layers(m) * 2 * m["hidden_size"] * bytes_per_value


def state_values_per_head(m: dict) -> int:
    return m["linear_key_head_dim"] * m["linear_value_head_dim"]


def state_bytes_per_slot(m: dict, tail_bytes: int) -> int:
    """What a slot keeps in the state pool: every linear layer's matrices
    and the tail of its convolution."""
    s = m["linear_num_key_heads"] * state_values_per_head(m) * STATE_BYTES
    tail = (m["linear_conv_kernel_dim"] - 1) * conv_channels(m) * tail_bytes
    return linear_layers(m) * (s + tail)


def decode_tick_min_bytes(m: dict, tick: dict, width: int) -> float:
    """Bytes a decode tick cannot avoid moving: every weight but the
    embedding table (a gather of the tick's rows) read once, every live
    cache row read once, every held slot's state and tail read and
    written."""
    weights = params(m) - m["vocab_size"] * m["hidden_size"]
    return (weights * width + tick["rows"] * kv_bytes_per_token(m, width)
            + 2 * tick["live_slots"] * state_bytes_per_slot(m, width))


def _rule_flops_per_token(m: dict) -> float:
    """The recurrence a token and layer: the state decayed, read under
    ``k``, corrected by an outer product, read under ``q``."""
    return 7.0 * m["linear_num_key_heads"] * state_values_per_head(m)


def _rule_io_per_token(m: dict, width: int) -> int:
    """``q``, ``k``, ``v`` in, ``o`` out at the weights' width; ``g`` and
    ``beta`` in float32."""
    hn, dk = m["linear_num_key_heads"], m["linear_key_head_dim"]
    dv = m["linear_value_head_dim"]
    return hn * ((2 * dk + 2 * dv) * width + 2 * 4)


def gdn_step_min(m: dict, tick: dict, width: int) -> tuple:
    """``gdn_step`` in a tick that decodes: a call a linear layer, every
    held slot's matrices read and written once."""
    if not tick["rows"]:
        return 0.0, 0.0
    slots = tick["live_slots"]
    state = m["linear_num_key_heads"] * state_values_per_head(m) * STATE_BYTES
    per_call = slots * (2 * state + _rule_io_per_token(m, width))
    return (linear_layers(m) * per_call,
            linear_layers(m) * slots * _rule_flops_per_token(m))


def gdn_chunk_min(m: dict, tick: dict, width: int) -> tuple:
    """``gdn_chunk`` in a tick with a chunk step: a call a linear layer
    over the tick's prompt tokens, and the state of a sequence in and out
    for every ``prefill_chunk`` of them (the least a tick's participants
    can be)."""
    tokens = tick.get("prefill_rows_valid", 0.0)
    if not tokens:
        return 0.0, 0.0
    seqs = -(-tokens // m["serve"]["prefill_chunk"])
    state = m["linear_num_key_heads"] * state_values_per_head(m) * STATE_BYTES
    per_call = tokens * _rule_io_per_token(m, width) + seqs * 2 * state
    return (linear_layers(m) * per_call,
            linear_layers(m) * tokens * _rule_flops_per_token(m))
