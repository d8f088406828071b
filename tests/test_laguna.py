"""The laguna family on the CPU at a tiny size (a window of 8 positions,
groups of 6 and 9 query heads over 2 cached heads): the grouped kernel
against the gather-dense reference with and without a first position, the
two page lifetimes in one allocator, the engine's prefill-then-decode
logits against the reference's full forward for prompts shorter than,
equal to and several times the window with chunk boundaries inside a
window, the expert shares, what the family raises for."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.models import laguna as lg
from mpit_tpu.models import laguna_reference as ref
from mpit_tpu.models.laguna import (
    FULL,
    SLIDING,
    LagunaConfig,
    LagunaServeModel,
    init_params,
)
from mpit_tpu.ops import decode_attention as da
from mpit_tpu.parallel.moe_serve import expert_layer, route
from mpit_tpu.serve import Engine, Request, Server, warm_engine
from mpit_tpu.serve import engine as engine_module
from mpit_tpu.serve.kvcache import (
    PageAllocator,
    PagedKVCache,
    alloc_paged_cache,
    window_slot_pages,
)

# float32 program against a float32 reference on the CPU: what is left is
# the order of summation, a few ulp of values of order 1.
TOL = dict(rtol=2e-4, atol=3e-5)
PAGE, CHUNK, MAX_LEN = 4, 8, 64


@pytest.fixture(scope="module")
def tiny():
    cfg = LagunaConfig.tiny()
    return cfg, init_params(cfg, jax.random.key(3))


def _top(params):
    return {k: params[k] for k in ("embed", "head", "final_norm")}


def _ref_logits(cfg, params, seq, positions=None, **how):
    seq = jnp.asarray(seq)
    at = jnp.arange(len(seq)) if positions is None else positions
    return ref.logits_at(cfg.to_dict(), _top(params), params["layers"], seq,
                         at, q_block=8, held=cfg.experts_held, **how)


# -- the configuration ----------------------------------------------------------


def test_published_config_and_its_share():
    pub = LagunaConfig()
    assert pub.layer_types[:5] == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert pub.layer_types.count(FULL) == 12
    assert pub.num_attention_heads_per_layer[:5] == (48, 72, 72, 72, 48)
    assert pub.mlp_layer_types[:2] == ("dense", "sparse")
    assert pub.kv_width == 1024 and pub.sliding_window == 512
    d = {"num_hidden_layers": 5, "num_experts": 64, "vocab_size": 25088,
         "published": {"num_experts": 256},
         "layer_types": [FULL] + [SLIDING] * 3 + [FULL],
         "mlp_layer_types": ["dense"] + ["sparse"] * 4,
         "num_attention_heads_per_layer": [48, 72, 72, 72, 48],
         "rope_parameters": {
             FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                    "original_max_position_embeddings": 8192,
                    "beta_slow": 1, "beta_fast": 32,
                    "attention_factor": 1.4852030263919618,
                    "partial_rotary_factor": 0.5},
             SLIDING: {"rope_type": "default", "rope_theta": 10000,
                       "partial_rotary_factor": 1}}}
    cfg = LagunaConfig.from_dict(d)
    assert cfg.num_experts == 256 and cfg.experts_held == tuple(range(64))
    assert dict(cfg.rope_full)["factor"] == 128
    assert LagunaConfig.from_dict({**d, "ep_rank": 3}).experts_held == tuple(
        range(192, 256))
    assert cfg.to_dict()["rope_parameters"][SLIDING]["rope_theta"] == 10000
    with pytest.raises(ValueError, match="one entry a layer"):
        LagunaConfig.from_dict({**d, "num_hidden_layers": 4})
    with pytest.raises(ValueError, match="groups"):
        LagunaConfig.tiny(num_attention_heads_per_layer=(12, 18, 18, 18, 11))


@pytest.mark.parametrize("kind", [FULL, SLIDING])
def test_rotary_matches_the_reference_and_yarn(kind):
    """Partial rotary with YaRN on a full layer, plain rotary over the
    whole head on a sliding one; the published full-layer frequencies run
    from extrapolated (fast dimensions) to interpolated by 128 (slow)."""
    cfg = LagunaConfig.tiny()
    x = jax.random.normal(jax.random.key(0), (1, 7, 3, cfg.head_dim))
    pos = jnp.arange(7)[None] * 5
    got = lg.rotate(x, *lg.rope_tables(cfg, kind, pos))
    want = ref.rope(x[0], pos[0], ref.rope_of(cfg.to_dict(), kind))
    np.testing.assert_allclose(got[0], want, **TOL)
    rot = cfg.head_dim // 2 if kind == FULL else cfg.head_dim
    if kind == FULL:  # the unrotated half passes through
        np.testing.assert_array_equal(got[..., rot:], x[..., rot:])
    freq, factor = ref.inv_freq(dict(LagunaConfig().rope_full), 128)
    plain = 1.0 / 500000.0 ** (np.arange(0, 64, 2) / 64)
    assert freq.shape == (32,) and factor == 1.4852030263919618
    np.testing.assert_allclose(freq[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(freq[-1], plain[-1] / 128, rtol=1e-6)
    assert np.all(np.diff(freq / plain) <= 1e-6)  # the ramp only falls


def test_softmax_scores_are_a_keyword_and_sigmoid_stays_the_default():
    x = jax.random.normal(jax.random.key(1), (16, 12))
    w = jax.random.normal(jax.random.key(2), (12, 8))
    zero = jnp.zeros((8,))
    idx, g = route(x, w, zero, top_k=3, scale=2.5, score="softmax")
    p = jax.nn.softmax(jnp.dot(x, w, precision="highest"), axis=-1)
    want = jnp.take_along_axis(p, idx, axis=-1)
    np.testing.assert_allclose(
        g, 2.5 * want / want.sum(-1, keepdims=True), **TOL)
    np.testing.assert_allclose(g.sum(-1), 2.5, rtol=1e-5)
    same_i, same_g = route(x, w, zero, top_k=3, scale=2.5)
    again_i, again_g = route(x, w, zero, top_k=3, scale=2.5, score="sigmoid")
    np.testing.assert_array_equal(same_i, again_i)
    np.testing.assert_array_equal(same_g, again_g)
    with pytest.raises(ValueError, match="score"):
        route(x, w, zero, top_k=3, scale=1.0, score="tanh")


# -- the grouped kernel -----------------------------------------------------------


@pytest.mark.parametrize("window", [0, 16], ids=["all", "window"])
@pytest.mark.parametrize("t, lens", [(1, (5, 40, 77)), (16, (0, 40, 64)),
                                     (128, (0, 0, 0))],
                         ids=["tick", "chunk", "parts"])
@pytest.mark.parametrize("group", [6, 9])
def test_grouped_kernel_matches_gather_dense(group, t, lens, window):
    """Groups of 6 and 9 query heads a cached head, a tick's row and a
    chunk's rows (128 of them go 64 a program), with and without a first
    position, in interpret mode, against the gather-dense reference."""
    rng = np.random.default_rng(group * t + window)
    b, h_kv, d, ps, pps = 3, 2, 128, 8, 32
    pages = b * pps
    k = jnp.asarray(rng.normal(size=(pages, ps, h_kv * d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(pages, ps, h_kv * d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, t, h_kv * group, d)), jnp.float32)
    bt = jnp.asarray(rng.permutation(pages).reshape(b, pps), jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    want = da.reference_grouped_paged_attention(q, k, v, lens, bt,
                                                window=window)
    got = da.grouped_paged_attention(q, k, v, lens, bt, window=window,
                                     interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert da.grouped_rows(t) == (64 if t == 128 else t)


def test_grouped_kernel_never_reads_behind_the_window():
    """Pages wholly before a slot's first visible position hold NaN: the
    kernel with a window neither reads them nor lets them into a sum."""
    rng = np.random.default_rng(0)
    b, h_kv, d, ps, pps, window = 2, 2, 128, 8, 16, 16
    k = rng.normal(size=(b * pps, ps, h_kv * d)).astype(np.float32)
    v = rng.normal(size=(b * pps, ps, h_kv * d)).astype(np.float32)
    bt = np.arange(b * pps, dtype=np.int32).reshape(b, pps)
    lens = np.asarray([50, 90], np.int32)
    clean = da.grouped_paged_attention(
        jnp.ones((b, 1, h_kv * 6, d)), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(lens), jnp.asarray(bt), window=window, interpret=True)
    for slot, fill in enumerate(lens):
        gone = (fill - window + 1) // ps  # pages wholly behind the window
        k[bt[slot, :gone]] = np.nan
        v[bt[slot, :gone]] = np.nan
    got = da.grouped_paged_attention(
        jnp.ones((b, 1, h_kv * 6, d)), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(lens), jnp.asarray(bt), window=window, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, clean)


# -- two lifetimes in one allocator ----------------------------------------------------


def test_layout_and_pool_have_two_lifetimes(tiny):
    cfg, params = tiny
    layout = LagunaServeModel(cfg).cache_layout()
    assert [l.window for l in layout.layers] == [0, 8, 8, 8, 0]
    assert layout.window == 8 and not layout.prefix_shareable
    row = 2 * cfg.kv_width * 4  # float32, key and value
    assert layout.page_bytes(PAGE, jnp.float32, False) == 2 * PAGE * row
    assert layout.page_bytes(PAGE, jnp.float32, False,
                             window=True) == 3 * PAGE * row
    per_slot = window_slot_pages(8, CHUNK, PAGE)
    assert per_slot == 5  # window + chunk positions and a page
    assert window_slot_pages(512, 512, 256) == 5
    assert window_slot_pages(0, 512, 256) == 0
    pool = alloc_paged_cache(cfg, slots=3, num_pages=48, page_size=PAGE,
                             window_pages=3 * per_slot)
    assert [a.shape[0] for a in pool.k] == [48, 15, 15, 15, 48]
    assert pool.num_pages == 48
    eng = _engine(cfg, params)
    assert eng.window_pages == 15 and eng.allocator.block_tables.shape == (
        3, 2 * MAX_LEN // PAGE)
    assert (eng.page_bytes * eng.num_pages
            + eng.window_page_bytes * eng.window_pages) == sum(
        l.nbytes for l in jax.tree.leaves((eng.cache.k, eng.cache.v)))
    # What the window layers would hold under the full layers' lifetime.
    one_lifetime = 5 * eng.num_pages * PAGE * row
    assert sum(l.nbytes for l in jax.tree.leaves(
        (eng.cache.k, eng.cache.v))) < 0.6 * one_lifetime


def test_window_pages_return_to_the_pool_behind_the_window():
    """A slot of any length never holds more than ``window + chunk +
    page`` positions in the window pool; pages wholly behind the window go
    back; admission counts both lifetimes."""
    window, per_slot = 8, window_slot_pages(8, CHUNK, PAGE)
    alloc = PageAllocator(32, PAGE, 16, 2, window=window,
                          window_pages=2 * per_slot - 2,
                          window_slot_pages=per_slot)
    assert alloc.admit(0, list(range(40)), 20) is not None
    assert alloc.window_free_pages == per_slot - 2  # promised, not yet held
    # The second request's most (5 pages) is more than what is left.
    assert alloc.admit(1, list(range(40)), 20) is None
    assert 1 not in alloc._slot_pages  # nothing was taken
    assert alloc.admit(1, list(range(6)), 3) is not None  # 2 pages in all
    returned = 0
    for base in range(0, 40, CHUNK):  # the chunks of a 40-token prompt
        returned += alloc.advance_window(0, base, base + CHUNK)
        held = alloc._slot_window[0]
        assert len(held) <= per_slot
        assert (len(held) - 1) * PAGE < window + CHUNK + PAGE
        assert min(held) * PAGE <= max(0, base - window + 1)
        np.testing.assert_array_equal(
            sorted(alloc.window_tables[0][alloc.window_tables[0] > 0]),
            sorted(p for p in held.values() if p > 0))
    for fill in range(40, 59):  # decode ticks
        returned += alloc.advance_window(0, fill, fill + 1)
        assert len(alloc._slot_window[0]) <= 3  # a window and its two ends
    assert returned == alloc.window_pages_returned == (59 - window) // PAGE
    assert alloc.window_occupancy == alloc.window_pages_in_use / (
        2 * per_slot - 2)
    alloc.free_slot(0)
    assert alloc.window_pages_in_use == 0 and 0 not in alloc._window_promised
    assert alloc.window_free_pages == 2 * per_slot - 2 - 2  # slot 1's promise
    with pytest.raises(RuntimeError, match="promised"):
        alloc.advance_window(1, 0, 6 * PAGE)  # a step longer than promised


def test_an_allocator_without_a_window_is_what_it_was():
    alloc = PageAllocator(8, 4, 4, 2)
    assert alloc.block_tables.shape == (2, 4) and alloc.window == 0
    assert alloc.admit(0, [1, 2, 3], 2) is not None
    assert alloc.advance_window(0, 0, 4) == 0
    assert alloc.window_pages_in_use == 0 and alloc.window_occupancy == 0.0


# -- the model through the cache -------------------------------------------------------


def _engine(cfg, params, *, slots=3, chunk=CHUNK, mode="reference", **kw):
    return Engine(cfg, params, slots=slots, max_len=MAX_LEN, seed=0,
                  kv_page_size=PAGE, prefill_chunk=chunk,
                  decode_attention=mode, **kw)


def _through_the_cache(cfg, params, seq, prompt, mode, chunk=CHUNK):
    """Logits at every position of ``seq``: ``prompt`` tokens in chunks,
    the rest in decode ticks, in slot 1 of three, the window pages mapped
    and given back as the scheduler does."""
    eng = _engine(cfg, params, mode=mode, chunk=chunk)
    model, alloc = eng.model, eng.allocator
    alloc.admit(1, seq[:prompt].tolist(), len(seq) - prompt + 1)
    cache, got, most = eng.cache, [], 0
    forward = jax.jit(lambda *a: model.forward_paged(
        *a[:-1], return_hidden=False, row_valid=a[-1]))
    for base in list(range(0, prompt, chunk)) + list(range(prompt, len(seq))):
        n = min(chunk, prompt - base) if base < prompt else 1
        width = chunk if base < prompt else 1
        alloc.advance_window(1, base, base + n)
        most = max(most, len(alloc._slot_window[1]))
        tokens = np.zeros((eng.slots, width), np.int32)
        tokens[1, :n] = seq[base:base + n]
        rows = (np.arange(width)[None] < n) & (np.arange(eng.slots) == 1)[
            :, None]
        lengths = jnp.asarray([0, base, 0], jnp.int32)
        logits, (k, v, state), aux = forward(
            params, jnp.asarray(tokens),
            dataclasses.replace(cache, lengths=lengths),
            jnp.asarray(alloc.block_tables.copy()), jnp.asarray(rows),
            jnp.asarray(rows))
        cache = PagedKVCache(k, v, lengths, state)
        got.append(np.asarray(logits[1, :n]))
        moe = cfg.mlp_layer_types.count("sparse")
        assert aux["expert_tokens"].shape == (moe, cfg.num_experts)
        assert float(aux["moe_choices"]) == n * cfg.num_experts_per_tok * moe
    return np.concatenate(got), most, alloc


@pytest.mark.parametrize("mode", ["interpret", "reference"])
@pytest.mark.parametrize("prompt, total", [(5, 12), (8, 20), (29, 45)],
                         ids=["shorter", "equal", "several"])
def test_paged_prefill_then_decode_matches_the_reference_logits(
        tiny, mode, prompt, total):
    """Prompts shorter than, equal to and several times the window (8),
    chunk boundaries (every 8) inside later rows' windows, decode ticks
    across page and window boundaries: the logits at every position are
    the reference's full forward, and a slot never holds more than
    ``window + chunk + page`` positions of the window pool."""
    cfg, params = tiny
    seq = np.random.default_rng(prompt).integers(0, cfg.vocab_size, total)
    got, most, alloc = _through_the_cache(cfg, params, seq, prompt, mode)
    want = _ref_logits(cfg, params, seq)
    np.testing.assert_allclose(got, want, **TOL)
    np.testing.assert_allclose(
        lg.forward_plain(params, jnp.asarray(seq)[None], cfg)[0], want, **TOL)
    assert most <= alloc.window_slot_pages == 5
    if total > 16 + PAGE:
        assert alloc.window_pages_returned > 0


def test_the_window_and_the_gate_are_applied(tiny):
    """With the window ignored (the check's ``no_window`` control) the
    logits past the window move; before it nothing does. A gate of one
    half everywhere is not what the weights give."""
    cfg, params = tiny
    seq = np.random.default_rng(9).integers(0, cfg.vocab_size, 40)
    want = _ref_logits(cfg, params, seq)
    wide = _ref_logits(cfg, params, seq, no_window=True)
    np.testing.assert_allclose(wide[:8], want[:8], **TOL)
    assert float(jnp.max(jnp.abs(wide[20:] - want[20:]))) > 1e-3
    flat = jax.tree.map(lambda a: a, params)
    flat["layers"] = [
        {**lp, "attn": {**lp["attn"], "w_g": jnp.zeros_like(lp["attn"]["w_g"])}}
        for lp in params["layers"]]
    assert float(jnp.max(jnp.abs(
        _ref_logits(cfg, flat, seq) - want))) > 1e-4


def _serve(engine, prompts, new=6):
    server = Server(engine)
    for i, p in enumerate(prompts):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=new))
    return {c.rid: c.tokens for c in server.run()}, server


def _prompts(cfg, lens=(5, 19, 11, 30, 8)):
    rng = np.random.default_rng(2)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]


@pytest.mark.parametrize("mode", ["interpret", "reference"])
def test_served_tokens_are_the_reference_argmax(tiny, mode):
    """Through ``Server``: admission, window pages mapped before each step
    and given back behind it, slots reused by later requests."""
    cfg, params = tiny
    eng = _engine(cfg, params, mode=mode)
    warm_engine(eng)
    prompts = _prompts(cfg)
    served, server = _serve(eng, prompts, new=9)
    assert eng.compile_watch.unexpected == 0
    for rid, p in enumerate(prompts):
        seq = p + served[rid]
        logits = _ref_logits(cfg, params, seq,
                             jnp.arange(len(p) - 1, len(seq) - 1))
        gap = logits.max(-1) - logits[jnp.arange(9),
                                      jnp.asarray(served[rid])]
        assert float(gap.max()) < 1e-4, (rid, gap)
    stats = server.stats()
    assert stats["kv_window_pool_pages"] == 15
    assert stats["kv_window_pages_returned"] > 0
    assert 0 < stats["kv_window_occupancy_peak"] <= 1
    assert eng.allocator.window_pages_in_use == 0  # every slot gave back
    assert eng.memledger.held("kv_window_pages") == 0
    assert eng.memledger.conservation()["ok"]


def test_compacted_chunk_tick_equals_the_full_batch_one(tiny, monkeypatch):
    cfg, params = tiny
    full = _engine(cfg, params, slots=4)
    monkeypatch.setattr(engine_module, "_WEIGHT_BOUND_ROWS", 8)
    monkeypatch.setattr(engine_module, "_COMPACT_ROWS", 16)
    compact = _engine(cfg, params, slots=4)
    assert compact._prefill_counts == (1, 2)
    assert compact.spare_seats(1) == 0  # no second seat a slot: one chunk
    warm_engine(compact)
    assert compact.compile_watch.compiles == compact.compile_watch.expected
    want, _ = _serve(full, _prompts(cfg))
    got, _ = _serve(compact, _prompts(cfg))
    assert got == want
    assert compact.compile_watch.unexpected == 0


def test_spans_counters_and_the_ledger_count_both_lifetimes(tiny):
    from mpit_tpu import obs

    cfg, params = tiny
    eng = _engine(cfg, params)
    rec = obs.enable(obs.Recorder())
    try:
        _, server = _serve(eng, _prompts(cfg, lens=(19, 30)), new=7)
    finally:
        obs.disable()
    events = rec.snapshot()["events"]
    ticks = [e[5] for e in events if e[1] == "decode" and e[5].get("active")]
    # A window layer reads its window: fewer rows than are cached.
    assert ticks and any(t["rows_read"] < t["rows_cached"] for t in ticks)
    assert rec.counter_total("kv_window_pages_returned") == (
        eng.allocator.window_pages_returned) > 0
    assert ("kv_window_pool_occupancy", ()) in rec.gauges
    assert rec.counter_total("moe_choices") == rec.counter_total(
        "moe_expert_tokens") > 0
    assert rec.counter_total("moe_choices_here") == rec.counter_total(
        "moe_choices")
    held = eng.memledger.decompose()
    assert "kv_window_pages" in held or eng.memledger.held(
        "kv_window_pages") == 0
    assert server.stats()["step_counts"]["moe_choices"] > 0


# -- the share of the experts ---------------------------------------------------------


def test_expert_shares_add_up(tiny):
    """The shares of four chips, two experts each, the shared expert
    counted once, give the uncut reference's whole layer: this family's
    softmax scores, its top-k, its normalisation and its scale."""
    cfg, params = tiny
    mp = params["layers"][2]["moe"]
    x = jax.random.normal(jax.random.key(6), (24, cfg.hidden_size))
    kw = dict(top_k=cfg.num_experts_per_tok,
              scale=cfg.moe_routed_scaling_factor, n_experts=cfg.num_experts,
              score="softmax")
    assert cfg.moe_routed_scaling_factor == 2.5
    total, here = 0.0, 0
    for share in range(4):
        held = (2 * share, 2 * share + 1)
        part = {k: mp[k] for k in ("router", "bias")}
        part.update({k: mp[k][jnp.asarray(held)]
                     for k in ("w_gate", "w_up", "w_down")})
        if share == 0:
            part["shared"] = mp["shared"]
        y, counts = expert_layer(x, part, held=held, **kw)
        assert int(counts.sum()) == 24 * cfg.num_experts_per_tok  # of all 8
        here += int(counts[jnp.asarray(held)].sum())
        total = total + y
        np.testing.assert_allclose(
            y, ref.experts(x, part, cfg.to_dict(), held=held), **TOL)
    assert here == 24 * cfg.num_experts_per_tok
    np.testing.assert_allclose(total, ref.experts(x, mp, cfg.to_dict()), **TOL)


def test_a_share_of_the_experts_through_the_cache(tiny):
    """An engine that holds two of the eight experts serves what the
    reference given the same share computes."""
    cfg, params = tiny
    held = (2, 5)
    share = dataclasses.replace(cfg, experts_held=held)
    cut = {**params, "layers": [
        lp if "mlp" in lp else {**lp, "moe": {
            **lp["moe"], **{k: lp["moe"][k][jnp.asarray(held)]
                            for k in ("w_gate", "w_up", "w_down")}}}
        for lp in params["layers"]]}
    eng = _engine(share, cut)
    prompts = _prompts(cfg, lens=(19, 12))
    served, server = _serve(eng, prompts, new=5)
    for rid, p in enumerate(prompts):
        seq = p + served[rid]
        logits = _ref_logits(share, cut, seq,
                             jnp.arange(len(p) - 1, len(seq) - 1))
        gap = logits.max(-1) - logits[jnp.arange(5), jnp.asarray(served[rid])]
        assert float(gap.max()) < 1e-4, (rid, gap)


# -- what the family lacks ------------------------------------------------------------


@pytest.mark.parametrize("kw, what", [
    (dict(kv_dtype="int8"), "int8 cache"),
    (dict(weights_dtype="int8"), "int8 weights"),
    (dict(kv_host_pages=4), "host KV tier"),
    (dict(spec_k=2), "speculative"),
])
def test_what_the_family_lacks_raises_at_construction(tiny, kw, what):
    cfg, params = tiny
    args = dict(slots=2, max_len=MAX_LEN, kv_page_size=PAGE)
    args.update(kw)
    with pytest.raises(ValueError, match=what):
        Engine(cfg, params, **args)


def test_shipment_preemption_and_a_mapped_prefix_raise_or_pass_up(tiny):
    cfg, params = tiny
    eng = _engine(LagunaServeModel(cfg), params)
    assert eng.model.family == "laguna" and eng.cfg is cfg
    with pytest.raises(ValueError, match="shipped"):
        eng.model.check_shipment()
    with pytest.raises(ValueError, match="evicted"):
        eng.model.check_preemption()
    with pytest.raises(ValueError, match="tensor parallelism"):
        eng.model.check_supported(tp=True, kv_dtype=None, weights_dtype=None,
                                  spec_k=0, host_pages=0)
    # A repeated prompt is found, counted and computed whole.
    prompt = _prompts(cfg, lens=(19,))[0]
    server = Server(eng)
    server.submit(Request(rid=0, prompt=prompt, max_new_tokens=12))
    server.run(max_ticks=6)  # prefilled and registered, still decoding
    server.submit(Request(rid=1, prompt=prompt, max_new_tokens=12))
    served = {c.rid: c.tokens for c in server.run()}
    assert served[0] == served[1]
    assert server.stats()["prefix_hits_passed_up"] >= 1
    assert server.stats()["prefix_hits"] == 0


def _pallas_names(jaxpr) -> set:
    found = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.add(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= _pallas_names(sub)
    return found


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_the_steps_lower_with_their_scope_and_kernel_names(tiny, step):
    cfg, params = tiny
    eng = _engine(cfg, params, mode="interpret")
    s = eng.slots
    i32, f32 = jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32)
    bt, key = jnp.asarray(eng.allocator.block_tables.copy()), jax.random.key(0)
    scopes = ["embed", "attn", "attn_full", "attn_window", "attn_gate",
              "rope", "kv_write", "moe_route", "moe_dispatch", "moe_experts",
              "moe_shared", "moe_combine", "mlp", "lm_head", "sample"]
    if step == "decode":
        jit, args = eng._decode_paged_jit, (
            eng.params, eng.cache, eng.last_token, jnp.ones((s,), bool), bt,
            key, f32, i32)
    else:
        jit, args = eng._prefill_paged_jit, (
            eng.params, eng.cache, eng.last_token,
            jnp.zeros((s, eng.prefill_chunk), jnp.int32), i32, i32, i32,
            jnp.zeros((s,), bool), bt, key, f32, i32)
    text = jit.lower(*args).as_text(debug_info=True)
    assert f"module @jit_{step}_paged " in text
    for scope in scopes:
        assert re.search(rf'["/(]{scope}[/)]', text), scope
    assert ("gqa_paged_decode_attn" if step == "decode"
            else "gqa_paged_chunk_attn") in _pallas_names(
        jax.make_jaxpr(jit)(*args).jaxpr)
    out = jax.eval_shape(jit, *args)
    assert set(out[2]) == {"expert_tokens", "moe_choices", "moe_choices_here"}


def test_the_cli_serves_the_family():
    from mpit_tpu.serve import __main__ as cli

    assert cli._FAMILIES["laguna"] == ("laguna", "LagunaConfig")
    cfg = cli.ServeConfig(family="laguna", model="tiny", max_len=64, seed=1)
    params, mcfg = cli._family_model(cfg)
    assert isinstance(mcfg, LagunaConfig) and mcfg.max_seq_len == 128
    assert params["layers"][1]["attn"]["w_q"].shape == (48, 18 * 8)
