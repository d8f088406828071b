"""What the ``olmo_hybrid`` family brings to the benchmark: its
configuration file against the published keys, its costs against the
issue's arithmetic, the readers that follow the tick on hand-made ticks
and a hand-made trace, its weights and reference at a tiny size, and its
rehearsal sizes. (The cell itself runs under ``--rehearse`` with every
other cell in ``test_rehearsal.py``, which reads ``BENCHMARK.json``.)"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import family_scopes as fs  # noqa: E402
from benchmark import family_ticks  # noqa: E402
from benchmark.families.olmo_hybrid import costs  # noqa: E402
from benchmark.readers import (  # noqa: E402
    family_scope_time_pct,
    tick_decode_hbm_util_pct,
    tick_kernel_roofline_pct,
)

NAME, CELL = "olmo-hybrid-7b-8of32", "olmoh-serve-offline-decode"
with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types"]
    assert CONFIG["published"]["num_hidden_layers"] == 32
    assert CONFIG["num_hidden_layers"] == len(CONFIG["layer_types"]) == 8
    assert CONFIG["layer_types"] == (
        ["linear_attention"] * 3 + ["full_attention"]) * 2
    widths = dict(hidden_size=3840, intermediate_size=11008,
                  num_attention_heads=30, num_key_value_heads=30,
                  linear_num_key_heads=30, linear_num_value_heads=30,
                  linear_key_head_dim=96, linear_value_head_dim=192,
                  linear_conv_kernel_dim=4, vocab_size=100352)
    assert {k: CONFIG[k] for k in widths} == widths
    assert CONFIG["rope_parameters"] == {"rope_theta": None}
    for key in ("norm_placement", "qk_norm", "no_rotary_embedding",
                "state_dtype", "gate_norm_gain", "initialisation",
                "kv_page_size", "prefill_chunk"):
        assert CONFIG["assumed"][key]
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "offline-decode-mid", 1)
    tokens = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert CELL in tokens["workloads"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_key_is_at_its_published_value():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    assert row["config"]["layer_types"][:8] == CONFIG["layer_types"]


def test_the_traffic_is_the_issue_s_letter_for_letter():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "offline-decode-mid.json")) as f:
        mix = json.load(f)
    want = dict(kind="family_requests", process="backlog", requests=1024,
                prompt_len={"dist": "uniform", "min": 1024, "max": 3072},
                output_len={"dist": "uniform", "min": 256, "max": 1024},
                schedule_seed=31, balance_block=64, lead_in_finished=8,
                check_tokens=512)
    assert {k: mix[k] for k in want} == want
    serve = CONFIG["serve"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= (
        serve["slot_positions"])
    assert serve["slot_positions"] % serve["kv_page_size"] == 0
    assert serve["prefill_chunk"] % 64 == 0
    assert serve["prefill_chunk"] % serve["kv_page_size"] == 0
    assert CONFIG["vocab_size"] % serve["sample_block"] == 0


def test_costs_count_what_the_issue_counted():
    assert costs.linear_layers(CONFIG) == 6 and costs.full_layers(CONFIG) == 2
    assert costs.conv_channels(CONFIG) == 11520
    assert costs.linear_layer_params(CONFIG) == pytest.approx(215.5e6, rel=2e-3)
    assert costs.full_layer_params(CONFIG) == pytest.approx(185.8e6, rel=1e-3)
    assert costs.params(CONFIG) == pytest.approx(2.435e9, rel=1e-3)
    assert costs.kv_bytes_per_token(CONFIG, 2) == 30720
    assert costs.state_bytes_per_slot(CONFIG, 2) == 6 * (2211840 + 69120)
    idle = {"rows": 0, "live_slots": 0}
    weights = costs.decode_tick_min_bytes(CONFIG, idle, 2)
    assert weights == (costs.params(CONFIG) - 100352 * 3840) * 2
    tick = {"rows": 160_000, "live_slots": 64.0}
    assert costs.decode_tick_min_bytes(CONFIG, tick, 2) - weights == (
        160_000 * 30720 + 2 * 64 * 6 * (2211840 + 69120))
    nbytes, flops = costs.gdn_step_min(CONFIG, tick, 2)
    assert nbytes == 6 * 64 * (2 * 2211840 + 30 * (576 * 2 + 8))
    assert flops == 6 * 64 * 7 * 30 * 96 * 192
    assert costs.gdn_step_min(CONFIG, idle, 2) == (0.0, 0.0)
    chunk = {"rows": 1, "live_slots": 64.0, "prefill_rows_valid": 700.0}
    nbytes, flops = costs.gdn_chunk_min(CONFIG, chunk, 2)
    assert nbytes == 6 * (700 * 30 * (576 * 2 + 8) + 2 * 2 * 2211840)
    assert flops == 6 * 700 * 7 * 30 * 96 * 192
    assert costs.gdn_chunk_min(CONFIG, tick, 2) == (0.0, 0.0)


def traced_ctx(samples=(), gauges=()):
    return {"config": CONFIG, "rehearse": False, "say": lambda *a, **k: None,
            "device": {"kind": "TPU v5 lite"},
            "traced": {"busy_in_span": {"decode": 0.05}},
            "run": {"tick_samples": list(samples), "tick_gauges": list(gauges)}}


def test_ticks_join_the_driver_s_two_lists():
    ctx = traced_ctx(
        samples=[(1.0, 0.5, 900), (2.0, 1.0, 1000)],
        gauges=[{"t": 1.0, "rows": 900},
                {"t": 2.0, "rows": 1000, "prefill_rows_valid": 300.0},
                {"t": 3.0, "rows": 7}])  # a tick the sampler did not see
    got = family_ticks.ticks(ctx)
    assert [t["live_slots"] for t in got] == [32.0, 64.0, 64]
    assert got[1]["prefill_rows_valid"] == 300.0
    assert family_ticks.ticks(traced_ctx()) == []


def fake_trace():
    """One device: (start, end, op_name, module, instruction)."""
    ops = [
        (0.0, 2.0, "jit(decode_paged)/linear_attn/gdn_step/jit(_step_call)/"
         "gdn_step", "jit_decode_paged", "gdn_step.1"),
        (2.0, 3.0, "jit(decode_paged)/attn/paged_decode_attn",
         "jit_decode_paged", "paged_decode_attn"),
        (3.0, 3.5, "jit(decode_paged)/linear_attn/gdn_conv/mul",
         "jit_decode_paged", "fusion"),
        (3.5, 4.0, "jit(prefill_paged)/state_pool_move/gather",
         "jit_prefill_paged", "gather"),
        (4.0, 5.0, "jit(prefill_paged)/linear_attn/gdn_chunk/transpose",
         "jit_prefill_paged", "fusion.7"),
        (5.0, 5.5, "", "jit_prefill_paged", "while.3"),
        (5.5, 6.0, "", "jit_prefill_paged", "copy-done"),
    ]
    return {"devices": [ops], "mark_s": 0.0}


def test_family_scopes_and_the_kernels_by_name():
    scopes, by_instr = fs.known_scopes("olmo_hybrid")
    assert {"linear_attn", "gdn_step", "state_pool_move", "attn"} <= set(scopes)
    got = fs.reduce(fake_trace(), 0.0, 6.0, scopes, by_instr)
    assert got["by_scope"] == pytest.approx({
        "gdn_step": 2.0, "attn": 1.0, "gdn_conv": 0.5,
        "state_pool_move": 0.5, "gdn_chunk": 1.0, "kv_write": 0.5,
        "unscoped": 0.5})
    ctx = traced_ctx()
    ctx["family_trace"] = (fake_trace(), 0.0, 6.0)
    linear = ["linear_attn", "gdn_conv", "gdn_chunk", "gdn_step"]
    assert family_scope_time_pct.read(ctx, linear) == pytest.approx(
        100 * 3.5 / 6.0)
    assert fs.kernel_seconds(ctx, "gdn_step") == (pytest.approx(2.0), 1)
    assert fs.kernel_seconds(ctx, "gdn_chunk") == (pytest.approx(1.0), 1)


def test_rooflines_follow_the_tick():
    tick = {"t": 1.0, "rows": 160_000}
    chunk = {"t": 2.0, "rows": 160_000, "prefill_rows_valid": 512.0}
    ctx = traced_ctx(samples=[(1.0, 1.0, 0), (2.0, 1.0, 0)],
                     gauges=[tick, chunk])
    ctx["family_trace"] = (fake_trace(), 0.0, 6.0)
    full = {"rows": 160_000, "live_slots": 64.0}
    nbytes, _ = costs.gdn_step_min(CONFIG, full, 2)
    got = tick_kernel_roofline_pct.read(ctx, "gdn_step", "gdn_step_min")
    assert got == pytest.approx(100 * (2 * nbytes / 819e9) / 2.0)
    nbytes, _ = costs.gdn_chunk_min(
        CONFIG, {**full, "prefill_rows_valid": 512.0}, 2)
    got = tick_kernel_roofline_pct.read(ctx, "gdn_chunk", "gdn_chunk_min")
    assert got == pytest.approx(100 * (nbytes / 819e9) / 1.0)
    want = sum(costs.decode_tick_min_bytes(CONFIG, full, 2) for _ in range(2))
    assert tick_decode_hbm_util_pct.read(ctx) == pytest.approx(
        100 * want / 0.05 / 819e9)
    ctx["traced"]["rehearsal"] = True
    assert tick_kernel_roofline_pct.read(ctx, "gdn_step", "gdn_step_min") is None
    assert tick_decode_hbm_util_pct.read(ctx) is None


def test_readers_find_nothing_on_a_program_without_the_family():
    """What the parent's program gives a traced run of an older cell: no
    such kernel, no such scope; every reader returns None and raises
    nothing."""
    ctx = traced_ctx(samples=[(1.0, 1.0, 5)], gauges=[{"t": 1.0, "rows": 5}])
    ctx["family_trace"] = ({"devices": [[
        (0.0, 1.0, "jit(decode_paged)/attn/x", "jit_decode_paged", "fusion"),
    ]], "mark_s": 0.0}, 0.0, 1.0)
    assert tick_kernel_roofline_pct.read(ctx, "gdn_step", "gdn_step_min") is None
    ctx["family_trace"] = None
    ctx["family_scopes"] = None
    assert tick_kernel_roofline_pct.read(ctx, "gdn_chunk", "gdn_chunk_min") is None
    assert family_scope_time_pct.read(ctx, ["state_pool_move"]) is None
    assert tick_decode_hbm_util_pct.read(traced_ctx()) is None


def tiny_model():
    with open(os.path.join(os.path.dirname(HERE), "families", "olmo_hybrid",
                           "tiny.json")) as f:
        return {**CONFIG, **json.load(f)["configs"][NAME]}


def test_weights_are_the_seed_s_and_the_reference_runs_on_them():
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families.olmo_hybrid import reference, weights

    model = tiny_model()
    big = 2**31 + 11
    a = weights.make_layer(model, big, 1, jnp.float32)
    b = weights.make_layer(model, big, 1, jnp.float32)
    c = weights.make_layer(model, big + 1, 1, jnp.float32)
    assert "lin" in a and "attn" in weights.make_layer(model, big, 3)
    np.testing.assert_array_equal(a["lin"]["w_qkv"], b["lin"]["w_qkv"])
    assert not np.array_equal(a["lin"]["w_qkv"], c["lin"]["w_qkv"])
    alpha = np.exp(-np.exp(a["lin"]["A_log"])
                   * np.log1p(np.exp(a["lin"]["dt_bias"])))
    assert ((alpha > 0.9) & (alpha < 0.9999)).all()
    top = weights.make_top(model, big)
    params = weights.to_program_tree(top, [
        weights.make_layer(model, big, i)
        for i in range(model["num_hidden_layers"])])
    tokens = jnp.arange(12) % model["vocab_size"]
    logits = reference.forward(model, params, tokens)
    assert logits.shape == (12, model["vocab_size"])
    assert np.isfinite(np.asarray(logits)).all()
    # Causal: a later token does not move an earlier position's logits.
    again = reference.forward(model, params, tokens.at[-1].set(7))
    np.testing.assert_allclose(logits[:-1], again[:-1], rtol=1e-5, atol=1e-6)
    for how in ({"matmul": "fp8"}, {"state_dtype": "bfloat16"}):
        low = reference.forward(model, params, tokens, **how)
        assert float(jnp.max(jnp.abs(low - logits))) > 1e-4, how


def test_the_benchmark_s_reference_is_the_program_s():
    """Two files, one text below the header: the program's tier-1 tests
    hold the program to the one, the cell's check to the other."""
    body = lambda path: open(os.path.join(ROOT, path)).read().split(
        "float32 throughout at", 1)[1]
    assert body("benchmark/families/olmo_hybrid/reference.py") == body(
        "mpit_tpu/models/olmo_hybrid_reference.py")


def test_a_rehearsal_takes_the_family_s_own_tiny_sizes():
    from benchmark.drivers import family_requests

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "offline-decode-mid.json")) as f:
        mix = json.load(f)
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    ctx = {"cell": cell, "config": CONFIG, "traffic": mix}
    family_requests.shrink_for_rehearsal(ctx)
    assert ctx["config"]["hidden_size"] < 128
    assert ctx["config"]["serve"]["slot_positions"] <= 256
    assert len(ctx["config"]["layer_types"]) == 8  # the pattern stays
    assert ctx["traffic"]["prompt_len"]["max"] < 128
    assert ctx["traffic"]["process"] == "backlog"
    assert CONFIG["hidden_size"] == 3840  # the published file untouched
