"""What the ``glm_dsa`` family brings to the benchmark: its configuration
file against the published keys, its costs against the issue's arithmetic,
the readers that follow the spans' attributes and the tick on hand-made
runs, the balancing rule, its weights and reference at a tiny size, and
its rehearsal sizes. (The cell itself runs under ``--rehearse`` with every
other cell in ``test_rehearsal.py``, which reads ``BENCHMARK.json``.)"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.families.glm_dsa import costs  # noqa: E402
from benchmark.readers import (  # noqa: E402
    chunk_kernel_roofline_pct,
    span_attr_ratio_pct,
    tick_decode_hbm_util_pct,
    tick_kernel_roofline_pct,
)

NAME, CELL = "glm-5.2-5of78-ep16", "glm52-serve-offline-longctx"
with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = ["num_hidden_layers", "first_k_dense_replace", "mlp_layer_types",
       "indexer_types", "n_routed_experts", "vocab_size",
       "num_nextn_predict_layers"]


def test_the_configuration_is_the_published_one_cut_to_a_chip_s_share():
    assert CONFIG["reduced"] == CUT
    assert set(CONFIG["published"]) == set(CUT) == set(CONFIG["reduced_why"])
    assert CONFIG["published"]["n_routed_experts"] == 256
    assert CONFIG["published"]["vocab_size"] == 154880 == 8 * CONFIG[
        "vocab_size"]
    assert CONFIG["n_routed_experts"] == 16 and CONFIG["ep_rank"] == 0
    assert CONFIG["indexer_types"] == ["full"] + ["shared"] * 3 + ["full"]
    assert CONFIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert CONFIG["num_hidden_layers"] == 5
    widths = dict(hidden_size=6144, intermediate_size=12288,
                  moe_intermediate_size=2048, num_attention_heads=64,
                  q_lora_rank=2048, kv_lora_rank=512, qk_nope_head_dim=192,
                  qk_rope_head_dim=64, v_head_dim=256, index_n_heads=32,
                  index_head_dim=128, index_topk=2048, num_experts_per_tok=8,
                  routed_scaling_factor=2.5)
    assert {k: CONFIG[k] for k in widths} == widths
    assert "16 chips share each layer" in CONFIG["deployment"]
    for key in ("indexer", "indexer_left_out", "shared_layers", "rope",
                "routing", "weights", "selection_bias", "kv_page_size",
                "prefill_chunk", "sample_block"):
        assert CONFIG["assumed"][key]
    serve = CONFIG["serve"]
    assert (serve["slots"], serve["slot_positions"]) == (16, 36864)
    assert CONFIG["vocab_size"] % serve["sample_block"] == 0
    assert CONFIG["correct"]["requests"]["control"] == [
        "fp8", "dense_attention"]
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CUT and entry["source"] == CONFIG["source"]
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "offline-longctx-32k", 1)
    tokens = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][-1] == CELL
    ours = [m for m in BENCH["per_layer"] if m["name"].endswith(".glm52")]
    assert len(ours) == 27 and all(m["workloads"] == [CELL] for m in ours)
    for m in ours:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".json")), m["name"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_key_is_at_its_published_value():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5.2")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
        elif isinstance(value, (int, float)):
            assert CONFIG["published"][key] == value, key
    # One whole period in its published order, the full layer before it.
    assert row["config"]["indexer_types"][2:7] == CONFIG["indexer_types"]
    assert row["config"]["mlp_layer_types"][2:7] == CONFIG["mlp_layer_types"]


def test_the_traffic_is_the_issue_s_letter_for_letter():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "offline-longctx-32k.json")) as f:
        mix = json.load(f)
    want = dict(kind="family_requests", process="backlog", requests=96,
                prompt_len={"dist": "uniform", "min": 16384, "max": 32768},
                output_len={"dist": "uniform", "min": 1024, "max": 4096},
                schedule_seed=37, balance_block=16, lead_in_finished=2,
                check_tokens=512)
    assert {k: mix[k] for k in want} == want
    serve = CONFIG["serve"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= (
        serve["slot_positions"])
    assert serve["slot_positions"] % serve["kv_page_size"] == 0
    assert serve["prefill_chunk"] % serve["kv_page_size"] == 0
    assert serve["prefill_chunk"] == 512  # a chunk tick is 0.3 % of a window
    assert mix["prompt_len"]["min"] >= 8 * CONFIG["index_topk"]


def test_costs_count_what_the_issue_counted():
    assert costs.attention_params(CONFIG) == pytest.approx(165.0e6, rel=1e-3)
    assert costs.indexer_params(CONFIG) == pytest.approx(9.37e6, rel=1e-3)
    assert costs.expert_params(CONFIG) == 37748736
    assert costs.params(CONFIG) == pytest.approx(3.881e9, rel=1e-3)
    assert costs.full_layers(CONFIG) == 2 and costs.moe_layers(CONFIG) == 4
    assert costs.kv_bytes_per_token(CONFIG, 2) == 6912
    assert 16 * 36864 * 6912 == pytest.approx(4.08e9, rel=1e-3)
    idle = {"rows": 0, "live_slots": 0}
    outside = costs.decode_tick_min_bytes(CONFIG, idle, 2)
    assert outside == (19360 * 6144 + 6144
                       + costs.params_outside_routed(CONFIG)) * 2
    assert outside == pytest.approx(2.69e9, rel=5e-3)  # the issue's 2.7 GB
    tick = {"rows": 400_000, "live_slots": 16.0, "experts_hit_decode": 100.0}
    more = costs.decode_tick_min_bytes(CONFIG, tick, 2) - outside
    assert more == (4 * 100 / 16 * 37748736 + 400_000 * 2 * 128
                    + 16 * 2048 * 5 * 640) * 2
    assert costs.rows_read(CONFIG, {"rows": 3000, "live_slots": 16.0}) == 3000
    nbytes, flops = costs.dsa_index_scores_tick_min(CONFIG, tick, 2)
    assert nbytes == 2 * 400_000 * (256 + 4)
    assert flops == 2 * 400_000 * 32 * (2 * 128 + 3)
    nbytes, flops = costs.dsa_sparse_attn_min(CONFIG, tick, 2)
    assert nbytes == 5 * (32768 * 1280 + 16 * 64 * (640 + 512) * 2)
    assert flops == 5 * 2 * 32768 * 64 * (640 + 512)
    assert costs.dsa_sparse_attn_min(CONFIG, idle, 2) == (0.0, 0.0)
    assert costs.dsa_index_scores_tick_min(CONFIG, idle, 2) == (0.0, 0.0)
    nbytes, flops = costs.dsa_index_scores_chunk_min(CONFIG, 1e6, 2e4, 2)
    assert nbytes == 2 * (2e4 * 256 + 1e6 * 4)
    assert flops == 2 * 1e6 * 32 * (2 * 128 + 3)


def traced_ctx(spans=(), samples=(), gauges=()):
    return {"config": CONFIG, "rehearse": False, "say": lambda *a, **k: None,
            "device": {"kind": "TPU v5 lite"},
            "traced": {"busy_in_span": {"decode": 0.05}},
            "run": {"host_spans": list(spans), "tick_samples": list(samples),
                    "tick_gauges": list(gauges)}}


def fake_trace():
    ops = [
        (0.0, 2.0, "jit(decode_paged)/attn/dsa_index/dsa_index_scores_tick",
         "jit_decode_paged", "custom-call.1"),
        (2.0, 3.0, "jit(decode_paged)/attn/dsa_sparse_attn/gather",
         "jit_decode_paged", "fusion.2"),
        (3.0, 4.0, "jit(prefill_paged)/attn/dsa_index/dsa_index_scores_chunk",
         "jit_prefill_paged", "custom-call.3"),
    ]
    return {"devices": [ops], "mark_s": 0.0}


def test_span_attributes_give_the_shares():
    spans = [("decode", 0.0, 1.0, {"rows_cached": 1000, "rows_read": 100,
                                   "moe_choices": 512.0,
                                   "moe_choices_here": 32.0}),
             ("decode", 1.0, 2.0, {"rows_cached": 3000, "rows_read": 300}),
             ("prefill", 1.0, 2.0, {"rows_cached": 99, "rows_read": 9}),
             ("decode", 2.0, 3.0, {"active": 0})]
    ctx = traced_ctx(spans)
    assert span_attr_ratio_pct.read(
        ctx, "decode", "rows_read", "rows_cached") == pytest.approx(10.0)
    assert span_attr_ratio_pct.read(
        ctx, "decode", "moe_choices_here", "moe_choices") == pytest.approx(
            6.25)
    # The parent's program writes no such attribute: nothing, no raise.
    old = traced_ctx([("decode", 0.0, 1.0, {"cache_rows": 5})])
    assert span_attr_ratio_pct.read(
        old, "decode", "rows_read", "rows_cached") is None
    assert span_attr_ratio_pct.read(
        {"run": {}}, "decode", "rows_read", "rows_cached") is None


def test_rooflines_follow_the_tick_and_the_chunk_s_span():
    tick = {"t": 1.0, "rows": 400_000, "experts_hit_decode": 100.0}
    ctx = traced_ctx(samples=[(1.0, 1.0, 0)], gauges=[tick])
    ctx["family_trace"] = (fake_trace(), 0.0, 4.0)
    full = {**tick, "live_slots": 16.0}
    nbytes, flops = costs.dsa_index_scores_tick_min(CONFIG, full, 2)
    least = max(nbytes / 819e9, flops / 197e12)
    assert tick_kernel_roofline_pct.read(
        ctx, "dsa_index_scores_tick", "dsa_index_scores_tick_min"
    ) == pytest.approx(100 * least / 2.0)
    nbytes, flops = costs.dsa_sparse_attn_min(CONFIG, full, 2)
    assert tick_kernel_roofline_pct.read(
        ctx, "dsa_sparse_attn", "dsa_sparse_attn_min") == pytest.approx(
            100 * max(nbytes / 819e9, flops / 197e12) / 1.0)
    assert tick_decode_hbm_util_pct.read(ctx) == pytest.approx(
        100 * costs.decode_tick_min_bytes(CONFIG, full, 2) / 0.05 / 819e9)
    # A chunk of 512 rows that ends at position 20,480.
    pairs = 512 * 20480 - 512 * 511 // 2
    ctx["run"]["host_spans"] = [
        ("prefill", 3.0, 4.0, {"rows_cached": pairs, "chunks": 1})]
    nbytes, flops = costs.dsa_index_scores_chunk_min(
        CONFIG, float(pairs), 20480.0, 2)
    got = chunk_kernel_roofline_pct.read(
        ctx, "dsa_index_scores_chunk", "dsa_index_scores_chunk_min")
    assert got == pytest.approx(
        100 * max(nbytes / 819e9, flops / 197e12) / 1.0, rel=1e-3)
    ctx["run"]["host_spans"] = []
    assert chunk_kernel_roofline_pct.read(
        ctx, "dsa_index_scores_chunk", "dsa_index_scores_chunk_min") is None
    ctx["family_trace"] = ({"devices": [[]], "mark_s": 0.0}, 0.0, 4.0)
    assert tick_kernel_roofline_pct.read(
        ctx, "dsa_sparse_attn", "dsa_sparse_attn_min") is None


def tiny_model():
    with open(os.path.join(os.path.dirname(HERE), "families", "glm_dsa",
                           "tiny.json")) as f:
        return {**CONFIG, **json.load(f)["configs"][NAME]}


def test_the_balancing_rule_levels_a_skewed_router():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families.glm_dsa import weights

    k1, k2 = jax.random.split(jax.random.key(0))
    # A common direction in the hidden states loads a random router
    # unevenly: some experts twice the mean and more with a zero bias.
    h = jax.random.normal(k1, (4096, 64)) + 2.0 * jax.random.normal(k2, (64,))
    router = 0.2 * jax.random.normal(jax.random.key(1), (64, 32))
    scores = jax.nn.sigmoid(h @ router)
    load = lambda b: np.bincount(np.asarray(
        jax.lax.top_k(scores + b, 4)[1]).reshape(-1), minlength=32)
    mean = 4096 * 4 / 32
    assert load(0.0).max() > 1.5 * mean
    bias, worst = weights.balance(scores, 4)
    assert float(worst) <= weights.BALANCE_WITHIN
    got = load(bias)
    assert np.abs(got - mean).max() / mean == pytest.approx(float(worst),
                                                            abs=1e-6)
    assert abs(float(jnp.mean(bias))) < 1e-6


def test_weights_are_the_seed_s_and_the_reference_runs_on_them():
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families.glm_dsa import reference, weights

    model = tiny_model()
    assert weights.held(model) == (0, 1) and weights.router_width(model) == 8
    assert weights.held(CONFIG) == tuple(range(16))
    big = 2**31 + 11
    a = weights.make_layer(model, big, 1, jnp.float32)
    b = weights.make_layer(model, big, 1, jnp.float32)
    c = weights.make_layer(model, big + 1, 1, jnp.float32)
    assert "moe" in a and "indexer" not in a
    assert a["moe"]["w_gate"].shape[0] == 2 and a["moe"]["router"].shape == (
        64, 8)
    first = weights.make_layer(model, big, 0, jnp.float32)
    assert "mlp" in first and "indexer" in first
    np.testing.assert_array_equal(a["attn"]["w_o"], b["attn"]["w_o"])
    assert not np.array_equal(a["attn"]["w_o"], c["attn"]["w_o"])
    assert float(jnp.min(a["attn"]["q_norm"])) > 1.0  # drawn round 2.5
    # Another share of the same seed holds other experts of the same model.
    other = weights.make_layer({**model, "ep_rank": 1}, big, 1, jnp.float32)
    np.testing.assert_array_equal(other["moe"]["router"], a["moe"]["router"])
    assert not np.array_equal(other["moe"]["w_up"], a["moe"]["w_up"])
    top = weights.make_top(model, big)
    layers = [weights.make_layer(model, big, i)
              for i in range(model["num_hidden_layers"])]
    seqs = np.random.default_rng(0).integers(0, model["vocab_size"], (3, 24))
    said = []
    biases = weights.calibrate(model, top, layers, seqs,
                               lambda kind, **kw: said.append(kw))
    assert [b is None for b in biases] == [True, False, False, False, False]
    assert len(said) == 4 and all(s["tokens"] == 72 for s in said)
    np.testing.assert_array_equal(layers[2]["moe"]["bias"], biases[2])
    again = weights.make_layer(model, big, 2, bias=biases[2])
    np.testing.assert_array_equal(again["moe"]["bias"], biases[2])
    tokens = jnp.arange(20) % model["vocab_size"]
    held = weights.held(model)
    logits = reference.logits_at(model, top, layers, tokens, jnp.arange(20),
                                 q_block=8, held=held)
    assert logits.shape == (20, model["vocab_size"])
    assert np.isfinite(np.asarray(logits)).all()
    again = reference.logits_at(model, top, layers, tokens.at[-1].set(7),
                                jnp.arange(20), q_block=8, held=held)
    np.testing.assert_allclose(logits[:-1], again[:-1], rtol=1e-5, atol=1e-6)
    for how in ({"matmul": "fp8"}, {"dense_attention": True}):
        low = reference.logits_at(model, top, layers, tokens, jnp.arange(20),
                                  q_block=8, held=held, **how)
        assert float(jnp.max(jnp.abs(low[12:] - logits[12:]))) > 1e-4, how


def test_the_benchmark_s_reference_is_the_program_s():
    """Two files, one text below the header: the program's tier-1 tests
    hold the program to the one, the cell's check to the other."""
    body = lambda path: open(os.path.join(ROOT, path)).read().split(
        "float32 throughout at", 1)[1]
    assert body("benchmark/families/glm_dsa/reference.py") == body(
        "mpit_tpu/models/glm_dsa_reference.py")


def test_the_check_samples_the_shortest_finished_requests():
    from types import SimpleNamespace as R

    from benchmark.families.glm_dsa import check

    done = [R(rid=i, prompt=[0] * p, tokens=[0] * n)
            for i, (p, n) in enumerate([(30, 9), (10, 5), (12, 4), (20, 3)])]
    assert [c.rid for c in check.sample_requests(done, 5)] == [1]
    assert [c.rid for c in check.sample_requests(done, 8)] == [1, 2]
    assert check.sample_requests([], 8) == []


def test_a_rehearsal_takes_the_family_s_own_tiny_sizes():
    from benchmark.drivers import family_requests

    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "offline-longctx-32k.json")) as f:
        mix = json.load(f)
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    ctx = {"cell": cell, "config": CONFIG, "traffic": mix}
    family_requests.shrink_for_rehearsal(ctx)
    assert ctx["config"]["hidden_size"] < 128
    assert ctx["config"]["serve"]["slot_positions"] <= 256
    assert ctx["config"]["indexer_types"] == CONFIG["indexer_types"]
    assert ctx["config"]["index_topk"] < ctx["traffic"]["prompt_len"]["max"]
    assert ctx["traffic"]["process"] == "backlog"
    assert CONFIG["hidden_size"] == 6144  # the published file untouched
