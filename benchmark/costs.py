"""Published peaks and the operations and bytes a call needs, from shapes.

A copy of ``mpit_tpu.utils.profiling.CHIP_SPECS`` lives in ``peaks.json``
so that no later change to the program moves the yardstick. A device
that is not in the table is an error, never a default.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: add it to "
            "benchmark/peaks.json with its source")
    return table[device_kind]


def gpt2_params(m: dict) -> int:
    """Parameters of a GPT-2, the tied embedding counted once."""
    d, f, L = m["n_embd"], m["n_inner"], m["n_layer"]
    per_layer = (d * 3 * d + 3 * d) + (d * d + d) + (d * f + f) + (f * d + d) + 4 * d
    return m["vocab_size"] * d + m["n_positions"] * d + L * per_layer + 2 * d


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward: 6 N for the matrices (N with the tied
    embedding once, its lookup counted as the head's product) plus
    6 L T d for causal attention (12 L T d counted at half). No
    recomputation is counted."""
    return 6.0 * gpt2_params(m) + 6.0 * m["n_layer"] * seq_len * m["n_embd"]


def kv_bytes_per_token(m: dict, bytes_per_value: int) -> int:
    """Key and value of one cached position, all layers."""
    return 2 * m["n_layer"] * m["n_embd"] * bytes_per_value


def decode_tick_min_bytes(m: dict, live_rows: int, weight_bytes: int,
                          kv_value_bytes: int) -> float:
    """Bytes a decode tick cannot avoid reading: every weight once and
    every live cache row once."""
    return gpt2_params(m) * weight_bytes + live_rows * kv_bytes_per_token(
        m, kv_value_bytes)
