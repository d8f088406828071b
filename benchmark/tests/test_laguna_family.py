"""What the ``laguna`` family brings to the benchmark: its configuration
file against the published keys, its costs against the issue's arithmetic,
the two roofline metrics on hand-made runs, its weights, reference and
check at a tiny size (a sound program passes the limits the two controls
fail), and a rehearsal of the cell through ``run.py --rehearse``."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.families.laguna import costs  # noqa: E402
from benchmark.readers import (  # noqa: E402
    chunk_kernel_roofline_pct,
    tick_kernel_roofline_pct,
)

NAME, CELL = "laguna-s-2.1-5of48-ep4", "laguna-serve-offline-mixedlen"
TRAFFIC = "offline-mixedlen-20k"
with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmark", "traffic", TRAFFIC + ".json")) as f:
    MIX = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = ["num_hidden_layers", "layer_types", "mlp_layer_types", "gating_types",
       "num_attention_heads_per_layer", "num_experts", "vocab_size"]
FULL, SLIDING = "full_attention", "sliding_attention"


def test_the_configuration_is_the_published_one_cut_to_a_chip_s_share():
    assert CONFIG["reduced"] == CUT
    assert set(CONFIG["published"]) == set(CUT) == set(CONFIG["reduced_why"])
    assert CONFIG["published"]["num_experts"] == 256
    assert CONFIG["published"]["vocab_size"] == 100352 == 4 * CONFIG[
        "vocab_size"]
    assert CONFIG["num_experts"] == 64 and CONFIG["ep_rank"] == 0
    assert CONFIG["layer_types"] == [FULL] + [SLIDING] * 3 + [FULL]
    assert CONFIG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert CONFIG["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert CONFIG["num_hidden_layers"] == 5
    widths = dict(hidden_size=3072, intermediate_size=12288,
                  moe_intermediate_size=1024,
                  shared_expert_intermediate_size=1024,
                  num_key_value_heads=8, head_dim=128, sliding_window=512,
                  num_experts_per_tok=10, moe_routed_scaling_factor=2.5)
    assert {k: CONFIG[k] for k in widths} == widths
    assert "4 chips share each layer" in CONFIG["deployment"]
    for key in ("gate", "routing", "qk_norm", "rope", "softmax_scale",
                "weights", "router_centring", "kv_page_size", "prefill_chunk",
                "sample_block"):
        assert CONFIG["assumed"][key]
    serve = CONFIG["serve"]
    assert (serve["slots"], serve["slot_positions"], serve["kv_page_size"],
            serve["prefill_chunk"], serve["sample_block"]) == (
        32, 21504, 256, 512, 6272)
    assert CONFIG["vocab_size"] == 4 * serve["sample_block"]
    assert CONFIG["correct"]["requests"]["control"] == ["fp8", "no_window"]
    limits = CONFIG["correct"]["requests"]["limits"]
    assert set(limits) == {"requests.token_gap_mean",
                           "requests.token_gap_p90"}
    assert set(CONFIG["correct"]["requests"]["read_from"]) == set(limits)
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == CUT and entry["source"] == CONFIG["source"]
    assert BENCH["configs"][-1] is entry and len(BENCH["configs"]) == 6
    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    assert BENCH["workloads"][-1] is cell and len(BENCH["workloads"]) == 8
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, TRAFFIC, 1)
    tokens = next(m for m in BENCH["end_to_end"]
                  if m["name"] == "serve_tokens_per_s")
    assert tokens["workloads"][-1] == CELL
    ours = [m for m in BENCH["per_layer"] if m["name"].endswith(".laguna")]
    assert [m["name"] for m in ours] == [
        "gqa_decode_attn_roofline_pct.laguna",
        "gqa_chunk_attn_roofline_pct.laguna"]
    assert BENCH["per_layer"][-2:] == ours and len(BENCH["per_layer"]) <= 128
    for m in ours:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".json")), m["name"]
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert len(entry["why"]) <= 200, entry["name"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_published_key_is_at_its_published_value():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-S-2.1")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
        elif isinstance(value, (int, float)):
            assert CONFIG["published"][key] == value, key
        else:  # a list a layer: layers 0-4 of it, in its published order
            assert CONFIG[key] == value[:5], key


def test_the_traffic_is_the_issue_s_letter_for_letter():
    want = dict(kind="family_requests", process="backlog", requests=512,
                prompt_len={"dist": "loguniform", "min": 512, "max": 20480},
                output_len={"dist": "loguniform", "min": 256, "max": 1024},
                check_min_positions=4096)
    assert {k: MIX[k] for k in want} == want
    serve = CONFIG["serve"]
    assert MIX["prompt_len"]["max"] + MIX["output_len"]["max"] == (
        serve["slot_positions"])
    assert serve["slot_positions"] % serve["kv_page_size"] == 0
    assert serve["prefill_chunk"] % serve["kv_page_size"] == 0
    assert MIX["prompt_len"]["min"] >= CONFIG["sliding_window"]
    assert MIX["check_min_positions"] == 8 * CONFIG["sliding_window"]
    assert MIX["balance_block"] == serve["slots"]
    assert MIX["lead_in_finished"] and MIX["lead_in_why"]


def test_costs_count_what_the_issue_counted():
    m = CONFIG
    assert costs.attention_params(m, 48) == pytest.approx(44.2e6, rel=2e-3)
    assert costs.attention_params(m, 72) == pytest.approx(63.1e6, rel=2e-3)
    assert costs.expert_params(m) == 3 * 3072 * 1024 == 9437184
    assert costs.params(m) == pytest.approx(3.002e9, rel=2e-3)  # 6.00 GB
    assert costs.kv_bytes_per_token(m, 2) == 2 * 4096
    assert costs.kv_bytes_per_token(m, 2, SLIDING) == 3 * 4096
    full = 32 * 21504 * costs.kv_bytes_per_token(m, 2)
    assert full == pytest.approx(5.64e9, rel=1e-3)
    window = 32 * 1280 * costs.kv_bytes_per_token(m, 2, SLIDING)
    assert window == pytest.approx(0.50e9, rel=1e-2)
    # One lifetime for all five layers: what the cell could not load.
    assert 32 * 21504 * 5 * 4096 + 2 * costs.params(m) == pytest.approx(
        20.1e9, rel=1e-2)
    idle = {"rows": 0, "live_slots": 0}
    assert costs.gqa_decode_attn_min(m, idle, 2) == (0.0, 0.0)
    tick = {"rows": 185_600, "live_slots": 32.0}  # a mean context of 5.8k
    assert costs.window_rows(m, tick) == 32 * 512
    nbytes, flops = costs.gqa_decode_attn_min(m, tick, 2)
    rows = 2 * 185_600 + 3 * 32 * 512
    io = 2 * 32 * 128 * 2 * (2 * 48 + 3 * 72)
    assert nbytes == rows * 4096 + io
    assert nbytes == pytest.approx(1.72e9, rel=1e-2)  # 1.5 + 0.2 GB
    assert flops == 4.0 * 128 * (2 * 185_600 * 48 + 3 * 32 * 512 * 72)
    assert costs.window_rows(m, {"rows": 600, "live_slots": 2.0}) == 600
    # A chunk of 512 rows that ends at position 20,480, one seat.
    pairs = 512 * 20480 - 512 * 511 // 2
    nbytes, flops = costs.gqa_chunk_attn_min(m, float(pairs), 20480.0, 2)
    assert flops == pytest.approx(
        4.0 * 128 * (2 * 48 * pairs + 3 * 72 * 512 * 512), rel=1e-6)
    assert nbytes == pytest.approx(
        (2 * 20480 + 3 * 1024) * 4096
        + 2 * 512 * 128 * 2 * (2 * 48 + 3 * 72), rel=1e-6)


def traced_ctx(spans=(), samples=(), gauges=()):
    return {"config": CONFIG, "rehearse": False, "say": lambda *a, **k: None,
            "device": {"kind": "TPU v5 lite"}, "traced": {},
            "run": {"host_spans": list(spans), "tick_samples": list(samples),
                    "tick_gauges": list(gauges)}}


def fake_trace():
    ops = [
        (0.0, 2.0, "jit(decode_paged)/attn/attn_full/gqa_paged_decode_attn",
         "jit_decode_paged", "custom-call.1"),
        (2.0, 2.5, "jit(decode_paged)/attn/attn_window/gqa_paged_decode_attn",
         "jit_decode_paged", "custom-call.2"),
        (3.0, 4.0, "jit(prefill_paged)/attn/attn_full/gqa_paged_chunk_attn",
         "jit_prefill_paged", "custom-call.3"),
    ]
    return {"devices": [ops], "mark_s": 0.0}


def test_the_two_rooflines_follow_the_tick_and_the_chunk_s_span():
    tick = {"t": 1.0, "rows": 185_600}
    ctx = traced_ctx(samples=[(1.0, 1.0, 0)], gauges=[tick])
    ctx["family_trace"] = (fake_trace(), 0.0, 4.0)
    spec = lambda name: json.load(open(os.path.join(
        ROOT, "benchmark", "metrics", name + ".json")))
    decode = spec("gqa_decode_attn_roofline_pct.laguna")
    assert decode["reader"] == "tick_kernel_roofline_pct"
    nbytes, flops = costs.gqa_decode_attn_min(
        CONFIG, {**tick, "live_slots": 32.0}, 2)
    assert tick_kernel_roofline_pct.read(ctx, **decode["args"]) == (
        pytest.approx(100 * max(nbytes / 819e9, flops / 197e12) / 2.5))
    chunk = spec("gqa_chunk_attn_roofline_pct.laguna")
    assert chunk["reader"] == "chunk_kernel_roofline_pct"
    pairs = 512 * 20480 - 512 * 511 // 2
    ctx["run"]["host_spans"] = [
        ("prefill", 3.0, 4.0, {"rows_cached": pairs, "chunks": 1})]
    nbytes, flops = costs.gqa_chunk_attn_min(CONFIG, float(pairs), 20480.0, 2)
    assert chunk_kernel_roofline_pct.read(ctx, **chunk["args"]) == (
        pytest.approx(100 * max(nbytes / 819e9, flops / 197e12) / 1.0,
                      rel=1e-3))
    # The parent's program has no such kernel: nothing, no raise.
    ctx["family_trace"] = ({"devices": [[]], "mark_s": 0.0}, 0.0, 4.0)
    assert tick_kernel_roofline_pct.read(ctx, **decode["args"]) is None
    assert chunk_kernel_roofline_pct.read(ctx, **chunk["args"]) is None


def tiny_model():
    with open(os.path.join(os.path.dirname(HERE), "families", "laguna",
                           "tiny.json")) as f:
        return {**CONFIG, **json.load(f)["configs"][NAME]}


def test_weights_are_the_seed_s_and_the_reference_runs_on_them():
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families.laguna import reference, weights

    model = tiny_model()
    assert weights.held(model) == (0, 1, 2, 3)
    assert weights.router_width(model) == 8
    assert weights.held(CONFIG) == tuple(range(64))
    assert weights.held({**model, "published": {}}) is None
    big = 2**31 + 11
    a = weights.make_layer(model, big, 1, jnp.float32)
    b = weights.make_layer(model, big, 1, jnp.float32)
    c = weights.make_layer(model, big + 1, 1, jnp.float32)
    assert "moe" in a and a["moe"]["w_gate"].shape[0] == 4
    assert a["moe"]["router"].shape == (64, 8)
    assert a["attn"]["w_q"].shape == (64, 18 * 16)  # a sliding layer's heads
    first = weights.make_layer(model, big, 0, jnp.float32)
    assert "mlp" in first and first["attn"]["w_q"].shape == (64, 12 * 16)
    np.testing.assert_array_equal(a["attn"]["w_o"], b["attn"]["w_o"])
    assert not np.array_equal(a["attn"]["w_o"], c["attn"]["w_o"])
    # Queries and keys are drawn wider, so that attention logits spread.
    assert float(jnp.std(a["attn"]["w_q"])) == pytest.approx(0.03, rel=0.05)
    assert float(jnp.std(a["attn"]["w_v"])) == pytest.approx(0.02, rel=0.1)
    # Another share of the same seed holds other experts of the same model.
    other = weights.make_layer({**model, "ep_rank": 1}, big, 1, jnp.float32)
    np.testing.assert_array_equal(other["moe"]["router"], a["moe"]["router"])
    top = weights.make_top(model, big)
    layers = [weights.make_layer(model, big, i)
              for i in range(model["num_hidden_layers"])]
    tokens = jnp.arange(40) % model["vocab_size"]
    held = weights.held(model)
    logits = reference.logits_at(model, top, layers, tokens, jnp.arange(40),
                                 q_block=8, held=held)
    assert logits.shape == (40, model["vocab_size"])
    assert np.isfinite(np.asarray(logits)).all()
    again = reference.logits_at(model, top, layers, tokens.at[-1].set(7),
                                jnp.arange(40), q_block=8, held=held)
    np.testing.assert_allclose(logits[:-1], again[:-1], rtol=1e-5, atol=1e-6)
    for how in ({"matmul": "fp8"}, {"no_window": True}):
        low = reference.logits_at(model, top, layers, tokens, jnp.arange(40),
                                  q_block=8, held=held, **how)
        assert float(jnp.max(jnp.abs(low[20:] - logits[20:]))) > 1e-4, how
    wide = reference.logits_at(model, top, layers, tokens, jnp.arange(40),
                               q_block=8, held=held, no_window=True)
    w = model["sliding_window"]  # before the window is full nothing moves
    np.testing.assert_allclose(wide[:w], logits[:w], rtol=1e-5, atol=1e-6)


def test_the_routers_are_centred_in_place_and_the_check_gets_them():
    """After ``calibrate`` every expert's mean logit over the calibration
    tokens is equal, a layer at a time on what the centred layers before
    it give; the tree holds the centred routers and the dense layer none."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.families.laguna import reference, weights

    model = tiny_model()
    seed, said = 2**31 + 3, []
    top = weights.make_top(model, seed)
    layers = [weights.make_layer(model, seed, i) for i in range(5)]
    before = [None if "mlp" in lw else np.asarray(lw["moe"]["router"])
              for lw in layers]
    seqs = weights.calibration_tokens(model, seed)
    assert seqs.shape == (4, 48) and seqs.max() < model["vocab_size"]
    routers = weights.calibrate(model, top, layers, seqs,
                                lambda kind, **kw: said.append(kw))
    assert [r is None for r in routers] == [True] + [False] * 4
    assert [s["layer"] for s in said] == [1, 2, 3, 4]
    for s in said:
        assert s["tokens"] == 4 * 48 and 0 < s["common_share"] < 1
        assert s["offset_std_after"] < 1e-6 < s["offset_std_before"]
    for lw, was, now in zip(layers, before, routers):
        if now is not None:
            assert lw["moe"]["router"] is now  # in place: the program's tree
            assert not np.array_equal(was, now)
            assert np.linalg.norm(now - was) < 0.5 * np.linalg.norm(was)
    # The mean logit a layer, recomputed from the centred tree: all equal.
    x = [reference.embed(top["embed"], jnp.asarray(q)) for q in seqs]
    pos = jnp.arange(48)
    for lw, kind in zip(layers, model["layer_types"]):
        f32 = {k: v for k, v in lw.items()}
        mid = [xi + reference.attention(
            lw["attn"], reference._rms_norm(xi, lw["attn_norm"], 1e-6), pos,
            model, kind, q_block=16) for xi in x]
        u = [reference._rms_norm(m, lw["mlp_norm"], 1e-6) for m in mid]
        if "moe" in lw:
            mean_logit = jnp.mean(jnp.concatenate(u), 0) @ lw["moe"]["router"]
            assert float(jnp.max(jnp.abs(mean_logit))) < 1e-5
        x = [reference.layer_forward(model, f32, xi, pos, kind, q_block=16,
                                     held=weights.held(model)) for xi in x]
    same = weights.make_layer(model, seed, 2)
    assert weights.with_router(same, None) is same
    swapped = weights.with_router(same, routers[2])
    assert swapped["moe"]["router"] is routers[2]
    assert swapped["moe"]["w_up"] is same["moe"]["w_up"]


def test_the_benchmark_s_reference_is_the_program_s():
    """Two files, one text below the header: the program's tier-1 tests
    hold the program to the one, the cell's check to the other."""
    body = lambda path: open(os.path.join(ROOT, path)).read().split(
        "float32 throughout at", 1)[1]
    assert body("benchmark/families/laguna/reference.py") == body(
        "mpit_tpu/models/laguna_reference.py")
    text = open(os.path.join(
        ROOT, "benchmark/families/laguna/reference.py")).read()
    assert "mpit_tpu" not in text.split('"""', 2)[2]  # imports nothing of it


def test_the_check_samples_the_shortest_request_past_eight_windows():
    from types import SimpleNamespace as R

    from benchmark.families.laguna import check

    done = [R(rid=i, prompt=[0] * p, tokens=[0] * n)
            for i, (p, n) in enumerate(
                [(30, 9), (10, 5), (12, 4), (20, 3), (60, 6)])]
    pick = lambda want, least: [
        c.rid for c in check.sample_requests(done, want, least)]
    assert pick(5, 0) == [1] and pick(8, 0) == [1, 2]
    assert pick(5, 20) == [3, 0] and pick(5, 39) == [0]
    assert pick(5, 100) == [] and check.sample_requests([], 8, 0) == []


def test_a_sound_program_passes_where_both_controls_fail():
    """The check at the tiny size, in float32 so that the program's only
    distance from the reference is the order of its sums: a sound run's
    two numbers are under limits that both controls pass."""
    import jax.numpy as jnp
    import numpy as np

    from mpit_tpu.models.laguna import LagunaConfig
    from mpit_tpu.serve import Engine, Request, Server

    from benchmark.families.laguna import check, weights
    from benchmark.families.xing4.check import numbers

    model = tiny_model()
    model = {**model, "serve": {**model["serve"], "weights_dtype": "float32"}}
    seed, said = 2**31 + 5, []
    ctx = {"config": model, "seed": seed, "control": True,
           "cell": {"config": NAME}, "routers": None,
           "traffic": {"check_tokens": 60, "check_min_positions": 48},
           "say": lambda kind, **kw: said.append((kind, kw))}
    cfg = LagunaConfig.from_dict(model, max_seq_len=128, dtype=jnp.float32)
    top = weights.make_top(model, seed, jnp.float32)
    layers = [weights.make_layer(model, seed, i, jnp.float32)
              for i in range(5)]
    eng = Engine(cfg, weights.to_program_tree(top, layers), slots=2,
                 max_len=128, kv_page_size=16, prefill_chunk=16,
                 sample_block=128, decode_attention="interpret")
    server = Server(eng)
    rng = np.random.default_rng(1)
    for rid, n in enumerate((70, 10, 90)):
        server.submit(Request(
            rid=rid, prompt=rng.integers(0, 512, n).tolist(),
            max_new_tokens=30))
    done = server.run()
    sample = check.sample_requests(done, 60, 48)
    assert [c.rid for c in sample] == [0, 2]  # 10 + 30 positions: too short
    sound = numbers(check.token_gaps(ctx, sample))
    assert sound["requests.token_gap_mean"] < 1e-4
    lows = {low: numbers(check.token_gaps(ctx, sample, low=low))
            for low in ("fp8", "no_window")}
    for low, got in lows.items():
        assert got["requests.token_gap_mean"] > 100 * max(
            sound["requests.token_gap_mean"], 1e-5), (low, got)
    model["correct"] = {"requests": {
        "control": ["fp8", "no_window"],
        "limits": {k: min(lows[low][k] for low in lows) / 2
                   for k in sound}}}
    assert check.requests(ctx, done) is True
    controls = [kw for kind, kw in said if kind == "control"]
    assert [c["arithmetic"] for c in controls] == ["fp8", "no_window"]
    limits = model["correct"]["requests"]["limits"]
    assert all(c["numbers"]["requests.token_gap_mean"]
               > limits["requests.token_gap_mean"] for c in controls)


def test_a_rehearsal_takes_the_family_s_own_tiny_sizes():
    from benchmark.drivers import family_requests

    cell = next(c for c in BENCH["workloads"] if c["name"] == CELL)
    ctx = {"cell": cell, "config": CONFIG, "traffic": MIX}
    family_requests.shrink_for_rehearsal(ctx)
    assert ctx["config"]["hidden_size"] < 128
    assert ctx["config"]["serve"]["slot_positions"] <= 256
    assert ctx["config"]["layer_types"] == CONFIG["layer_types"]
    assert ctx["config"]["sliding_window"] * 2 < (
        ctx["traffic"]["prompt_len"]["max"])
    assert ctx["traffic"]["process"] == "backlog"
    assert CONFIG["hidden_size"] == 3072  # the published file untouched


def test_the_cell_rehearses_through_run_py():
    """``run.py --rehearse`` end to end on the CPU, traced: both lifetimes
    of pages, the check and its controls; exit code 3, never a chip run's."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    got = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 46), "--seconds", "1",
         "--trace", "1", "--control", "1", "--rehearse",
         os.path.join(HERE, "tiny.json")],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert got.returncode == 3, got.stderr[-2000:]
    lines = [json.loads(l) for l in got.stdout.splitlines()
             if l.startswith("{")]
    result = lines[-1]
    assert result["rehearsal"] is True and result["failed"] == 0
    notes = {l["note"]: l for l in lines[:-1] if "note" in l}
    assert notes["window"]["serve_tokens_per_s"] > 0
    assert notes["check_detail"]["longest"] >= 48
    controls = [l for l in lines[:-1] if l.get("note") == "control"]
    assert [c["arithmetic"] for c in controls] == ["fp8", "no_window"]
