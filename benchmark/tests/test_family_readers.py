"""The readers a family brings, on hand-made ticks and a hand-made trace;
the family's costs from its configuration file; its reference and
weights at a tiny size."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import family_scopes as fs  # noqa: E402
from benchmark.families.xing4 import costs  # noqa: E402
from benchmark.readers import (  # noqa: E402
    family_decode_hbm_util_pct,
    family_scope_time_pct,
    kernel_roofline_pct,
    prefill_row_waste_pct,
    tick_gauge_mean,
)

with open(os.path.join(ROOT, "benchmark", "configs",
                       "xing4-29b-a4b-6of40.json")) as f:
    CONFIG = json.load(f)


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    assert CONFIG["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers"]
    assert CONFIG["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "num_nextn_predict_layers": 1}
    widths = dict(hidden_size=3584, intermediate_size=9216,
                  moe_intermediate_size=1024, n_routed_experts=64,
                  num_experts_per_tok=4, num_attention_heads=32,
                  q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, vocab_size=131072,
                  hc_mult=4, hc_sinkhorn_iters=20)
    assert {k: CONFIG[k] for k in widths} == widths
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "xing4-29b-a4b-6of40")
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]


def test_costs_count_what_the_issue_counted():
    assert costs.attention_params(CONFIG) == 28_411_136  # 28.41 M a layer
    assert costs.expert_params(CONFIG) == 3 * 3584 * 1024
    assert costs.params(CONFIG) == pytest.approx(4.793e9, rel=1e-3)
    assert costs.kv_bytes_per_token(CONFIG, 2) == 6 * 640 * 2  # padded row
    none_hit = costs.decode_tick_min_bytes(CONFIG, 0, 0, 2, 2)
    all_hit = costs.decode_tick_min_bytes(CONFIG, 0, 64, 2, 2)
    assert all_hit - none_hit == 5 * 64 * costs.expert_params(CONFIG) * 2
    # Every weight but the embedding table, once.
    assert all_hit == (costs.params(CONFIG) - 131072 * 3584) * 2
    rows = costs.decode_tick_min_bytes(CONFIG, 1000, 0, 2, 2) - none_hit
    assert rows == 1000 * 7680
    nbytes, flops = costs.mla_decode_attn_min(CONFIG, 1000, 32, 2)
    assert nbytes == 1000 * 640 * 2 + 32 * 32 * (640 + 512) * 2
    assert flops == 2 * 1000 * 32 * (640 + 512)


def ticks(*rows):
    return {"run": {"tick_gauges": [dict(t=float(i), **r)
                                    for i, r in enumerate(rows)]}}


def test_row_waste_is_over_the_chunk_ticks_only():
    ctx = ticks({"rows": 10, "prefill_rows_computed": 2048.0,
                 "prefill_rows_valid": 2048.0},
                {"rows": 11},  # a tick with no chunk
                {"rows": 12, "prefill_rows_computed": 4096.0,
                 "prefill_rows_valid": 1024.0})
    assert prefill_row_waste_pct.read(ctx) == pytest.approx(50.0)
    assert prefill_row_waste_pct.read(ticks({"rows": 1})) is None
    assert prefill_row_waste_pct.read({"run": {}}) is None  # no such gauge


def test_gauge_mean_skips_the_ticks_that_did_not_set_it():
    ctx = ticks({"load_max_over_mean_decode": 2.0}, {"rows": 3},
                {"load_max_over_mean_decode": 4.0})
    assert tick_gauge_mean.read(ctx, "load_max_over_mean_decode") == 3.0
    assert tick_gauge_mean.read(ctx, "absent") is None


def traced_ctx(**run):
    return {"config": CONFIG, "rehearse": False, "say": lambda *a, **k: None,
            "device": {"kind": "TPU v5 lite"},
            "traced": {"busy_in_span": {"decode": 0.05}}, "run": run}


def test_decode_util_is_the_family_s_least_bytes_over_decode_time():
    gauges = [{"t": 0.0, "rows": 200_000, "experts_hit_decode": 56.0},
              {"t": 1.0, "rows": 0, "experts_hit_decode": 50.0},  # no rows
              {"t": 2.0, "rows": 100}]  # no decode step in this tick
    ctx = traced_ctx(tick_gauges=gauges)
    want = costs.decode_tick_min_bytes(CONFIG, 200_000, 56.0, 2, 2)
    assert family_decode_hbm_util_pct.read(ctx) == pytest.approx(
        100 * want / 0.05 / 819e9)
    ctx["traced"]["rehearsal"] = True
    assert family_decode_hbm_util_pct.read(ctx) is None
    assert family_decode_hbm_util_pct.read(traced_ctx(tick_gauges=[])) is None


def fake_trace():
    """One device: (start, end, op_name, module, instruction)."""
    ops = [
        (0.0, 1.0, "jit(decode_paged)/attn/mla_paged_decode_attn",
         "jit_decode_paged", "mla_paged_decode_attn"),
        (1.0, 1.5, "jit(decode_paged)/attn/mla_absorb/dot_general",
         "jit_decode_paged", "fusion"),
        (1.5, 4.5, "", "jit_decode_paged", "ragged-dot-none"),
        (4.5, 5.0, "jit(decode_paged)/hc_mix/mul", "jit_decode_paged",
         "fusion"),
        (5.0, 5.5, "", "jit_decode_paged", "copy-done"),
        (5.5, 6.0, "jit(decode_paged)/attn/kv_write/scatter",
         "jit_decode_paged", "scatter"),
        (9.0, 10.0, "jit(decode_paged)/attn/x", "jit_decode_paged", "late"),
    ]
    return {"devices": [ops], "mark_s": 0.0}


def test_family_scopes_and_the_instruction_fallback():
    scopes, by_instr = fs.known_scopes("xing4")
    assert "hc_mix" in scopes and "attn" in scopes
    got = fs.reduce(fake_trace(), 0.0, 6.0, scopes, by_instr)
    assert got["busy_s"] == pytest.approx(6.0)
    assert got["by_scope"] == pytest.approx({
        "attn": 1.0, "mla_absorb": 0.5, "moe_experts": 3.0, "hc_mix": 0.5,
        "unscoped": 0.5, "kv_write": 0.5})
    assert got["outside"] == [["jit_decode_paged:copy-done", 0.5]]
    # Without the fallback the grouped product has no scope.
    bare = fs.reduce(fake_trace(), 0.0, 6.0, scopes)
    assert bare["by_scope"]["unscoped"] == pytest.approx(3.5)


def test_scope_share_and_kernel_roofline_from_a_trace():
    ctx = traced_ctx(tick_gauges=[{"t": 0.0, "rows": 300_000}])
    ctx["family_trace"] = (fake_trace(), 0.0, 6.0)
    share = family_scope_time_pct.read(ctx, ["moe_experts", "moe_shared"])
    assert share == pytest.approx(50.0)
    assert family_scope_time_pct.read(ctx, ["unscoped"]) == pytest.approx(
        100 * 0.5 / 6.0)
    seconds, calls = fs.kernel_seconds(ctx, "mla_paged_decode_attn")
    assert (seconds, calls) == (pytest.approx(1.0), 1)
    assert fs.kernel_seconds(ctx, "no_such_kernel") is None
    nbytes, flops = costs.mla_decode_attn_min(CONFIG, 300_000, 32, 2)
    least = max(6 * nbytes / 819e9, 6 * flops / 197e12)
    got = kernel_roofline_pct.read(
        ctx, "mla_paged_decode_attn", "mla_decode_attn_min")
    assert got == pytest.approx(100 * least / 1.0)
    ctx["traced"]["rehearsal"] = True
    assert kernel_roofline_pct.read(
        ctx, "mla_paged_decode_attn", "mla_decode_attn_min") is None


def test_readers_find_nothing_on_a_program_without_the_family():
    """What the parent's program gives a traced run: no gauges, no
    scopes of the family; every reader returns None and raises nothing."""
    ctx = traced_ctx()
    ctx["family_trace"] = None
    ctx["family_scopes"] = None
    assert prefill_row_waste_pct.read(ctx) is None
    assert tick_gauge_mean.read(ctx, "load_max_over_mean_decode") is None
    assert family_decode_hbm_util_pct.read(ctx) is None
    assert family_scope_time_pct.read(ctx, ["hc_mix"]) is None
    assert kernel_roofline_pct.read(
        ctx, "mla_paged_decode_attn", "mla_decode_attn_min") is None


def test_weights_are_the_seed_s_and_the_reference_runs_on_them():
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families.xing4 import reference, weights

    with open(os.path.join(os.path.dirname(HERE), "families", "xing4",
                           "tiny.json")) as f:
        model = {**CONFIG, **json.load(f)["configs"]["xing4-29b-a4b-6of40"]}
    big = 2**31 + 11
    a = weights.make_layer(model, big, 1, jnp.float32)
    b = weights.make_layer(model, big, 1, jnp.float32)
    c = weights.make_layer(model, big + 1, 1, jnp.float32)
    assert "moe" in a and "mlp" in weights.make_layer(model, big, 0)
    np.testing.assert_array_equal(a["moe"]["w_up"], b["moe"]["w_up"])
    assert not np.array_equal(a["moe"]["w_up"], c["moe"]["w_up"])
    top = weights.make_top(model, big)
    layers = [weights.make_layer(model, big, i)
              for i in range(model["num_hidden_layers"])]
    tokens = jnp.arange(12) % model["vocab_size"]
    logits = reference.logits_at(model, top, layers, tokens, jnp.arange(12),
                                 q_block=5)
    assert logits.shape == (12, model["vocab_size"])
    assert np.isfinite(np.asarray(logits)).all()
    # Causal: a later token does not move an earlier position's logits.
    again = reference.logits_at(model, top, layers, tokens.at[-1].set(7),
                                jnp.arange(12), q_block=5)
    np.testing.assert_allclose(logits[:-1], again[:-1], rtol=1e-5, atol=1e-6)
    low = reference.logits_at(model, top, layers, tokens, jnp.arange(12),
                              matmul="fp8", q_block=5)
    assert float(jnp.max(jnp.abs(low - logits))) > 1e-3


def test_a_rehearsal_takes_the_family_s_own_tiny_sizes():
    """``tests/tiny.json`` holds the cells that were there before the
    family; the driver shrinks a family's cell from the family's file,
    widths and traffic alike, and every cell of the family has sizes."""
    from benchmark.drivers import family_requests

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "offline-decode-long.json")) as f:
        mix = json.load(f)
    cells = [c for c in bench["workloads"]
             if c["config"] == "xing4-29b-a4b-6of40"]
    assert cells
    for cell in cells:
        ctx = {"cell": cell, "config": CONFIG, "traffic": mix}
        family_requests.shrink_for_rehearsal(ctx)
        assert ctx["config"]["hidden_size"] < 128
        assert ctx["config"]["serve"]["slot_positions"] <= 256
        assert ctx["config"]["rope_scaling"]["type"] == "yarn"
        assert ctx["traffic"]["prompt_len"]["max"] < 128
        assert ctx["traffic"]["process"] == "backlog"  # what stays the mix's
        assert CONFIG["hidden_size"] == 3584  # the published file untouched
