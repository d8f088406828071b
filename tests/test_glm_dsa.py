"""The glm_dsa family on the CPU at a tiny size, ``index_topk`` smaller than
the contexts so that the choice really drops rows: the three operations of
``ops/dsa.py`` against their twins and ``lax.top_k``, the paged pool with
its third seat against the reference's full forward (logits, not tokens),
what a ``shared`` layer attends over, the expert shares."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from mpit_tpu.models import glm_dsa as gd
from mpit_tpu.models import glm_dsa_reference as ref
from mpit_tpu.models.glm_dsa import (
    GlmDsaConfig,
    GlmDsaServeModel,
    init_params,
)
from mpit_tpu.models.serving import CacheLayout, PageLayer
from mpit_tpu.ops import dsa
from mpit_tpu.ops import mla_attention as mla
from mpit_tpu.parallel.moe_serve import expert_layer
from mpit_tpu.serve import Engine, Request, Server, warm_engine
from mpit_tpu.serve import engine as engine_module
from mpit_tpu.serve.kvcache import PagedKVCache, alloc_paged_cache

# float32 program against a float32 reference on the CPU: what is left is
# the order of summation, a few ulp of values of order 1.
TOL = dict(rtol=2e-4, atol=3e-5)


def ref_cfg(cfg: GlmDsaConfig) -> dict:
    """The reference's plain dict, with the published key names."""
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d["rope_parameters"] = {"rope_theta": cfg.rope_theta,
                            "rope_type": "default"}
    return d


@pytest.fixture(scope="module")
def tiny():
    cfg = GlmDsaConfig.tiny()
    return cfg, init_params(cfg, jax.random.key(3))


# -- the configuration ----------------------------------------------------------


def test_published_config_and_its_share():
    pub = GlmDsaConfig()
    assert pub.indexer_types[:7] == ("full",) * 3 + ("shared",) * 3 + (
        "full",)
    assert pub.indexer_types.count("full") == 3 + 18
    assert pub.softmax_scale == 256 ** -0.5
    d = {"mlp_layer_types": ["dense"] + ["sparse"] * 4,
         "indexer_types": ["full", "shared", "shared", "shared", "full"],
         "num_hidden_layers": 5, "n_routed_experts": 16,
         "published": {"n_routed_experts": 256},
         "rope_parameters": {"rope_theta": 8000000}, "vocab_size": 19360}
    cfg = GlmDsaConfig.from_dict(d)
    assert cfg.n_routed_experts == 256 and cfg.experts_held == tuple(range(16))
    assert cfg.rope_theta == 8e6 and cfg.vocab_size == 19360
    assert GlmDsaConfig.from_dict({**d, "ep_rank": 3}).experts_held == tuple(
        range(48, 64))
    with pytest.raises(ValueError, match="one entry a layer"):
        GlmDsaConfig.from_dict({**d, "num_hidden_layers": 4})


def test_interleaved_rotary_matches_the_reference(tiny):
    cfg, _ = tiny
    x = jax.random.normal(jax.random.key(0), (7, 3, cfg.qk_rope_head_dim))
    pos = jnp.arange(7) * 5
    cos, sin = gd.rope_tables(cfg, pos)
    got = gd.apply_rope_interleaved(x, cos[:, None], sin[:, None])
    np.testing.assert_allclose(got, ref._rope(x, pos, ref_cfg(cfg)), **TOL)


# -- the three operations ----------------------------------------------------------


@pytest.mark.parametrize("s, k", [(5, 8), (8, 8), (200, 8), (300, 64)])
def test_select_is_top_k_as_sets(s, k):
    scores = jax.random.normal(jax.random.key(s), (3, 4, s))
    scores = scores.at[1, 2, : s // 2].set(-jnp.inf)  # a row half unseen
    mask = np.asarray(dsa.dsa_select(scores, k))
    kk = min(k, s)
    _, idx = lax.top_k(scores, kk)
    want = np.zeros(mask.shape, bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    want &= np.isfinite(np.asarray(scores))
    np.testing.assert_array_equal(mask, want)
    assert mask.sum(-1).max() <= k


def test_select_gives_ties_to_the_earlier_position():
    scores = jnp.asarray([[1.0, 3.0, 2.0, 2.0, 2.0, 0.5, 2.0, -1.0]])
    np.testing.assert_array_equal(
        dsa.dsa_select(scores, 3)[0],
        [False, True, True, True, False, False, False, False])
    _, idx = lax.top_k(scores, 3)
    assert set(np.asarray(idx[0]).tolist()) == {1, 2, 3}
    # Negative values and zeros keep their order through the bit trick.
    scores = jnp.asarray([[-3.0, -0.5, 0.0, -2.0, -1.0]])
    np.testing.assert_array_equal(
        dsa.dsa_select(scores, 2)[0], [False, True, True, False, False])


@pytest.mark.parametrize("s, k", [(40, 8), (128, 16), (300, 32)])
def test_mask_to_rows_lists_the_set_bits(s, k):
    rng = np.random.default_rng(s)
    mask = rng.random((5, s)) < 0.1
    mask[1] = False  # nothing chosen
    mask[2, :3] = True
    rows, n = dsa.mask_to_rows(jnp.asarray(mask), k)
    for r in range(5):
        want = np.flatnonzero(mask[r])[:k]
        assert int(n[r]) == len(want)
        np.testing.assert_array_equal(np.asarray(rows[r, : len(want)]), want)
        assert (np.asarray(rows[r, len(want):]) == 0).all()


def _index_inputs(cfg, t, slots=3, ps=16, pps=4, pages=16):
    keys = jax.random.split(jax.random.key(t), 3)
    hi, di = cfg.index_n_heads, mla.lane_pad(cfg.index_head_dim)
    q = jax.random.normal(keys[0], (slots, t, hi, di))
    w = jax.random.normal(keys[1], (slots, t, hi))
    pool = jax.random.normal(keys[2], (pages, ps, di))
    bt = np.random.default_rng(0).permutation(pages)[: slots * pps]
    return q, w, pool, jnp.asarray(bt.reshape(slots, pps), jnp.int32)


@pytest.mark.parametrize("t", [1, 8])
def test_index_scores_kernel_and_twin_match_a_plain_sum(tiny, t):
    cfg, _ = tiny
    q, w, pool, bt = _index_inputs(cfg, t)
    lengths = jnp.asarray([0, 17, 40], jnp.int32)
    twin = dsa.reference_dsa_index_scores(q, w, pool, lengths, bt, tile=32)
    got = dsa.dsa_index_scores(q, w, pool, lengths, bt, interpret=True)
    keys = pool[bt].reshape(3, -1, pool.shape[-1])
    plain = jnp.einsum(
        "bthk,bth->btk",
        jnp.maximum(jnp.einsum("bthd,bkd->bthk", q, keys,
                               precision=lax.Precision.HIGHEST), 0.0), w,
        precision=lax.Precision.HIGHEST)
    seen = (jnp.arange(keys.shape[1])[None, None, :]
            <= lengths[:, None, None] + jnp.arange(t)[None, :, None])
    for out in (twin, got):
        assert out.dtype == jnp.float32
        np.testing.assert_array_equal(np.isfinite(out), seen)
        np.testing.assert_allclose(jnp.where(seen, out, 0.0),
                                   jnp.where(seen, plain, 0.0), **TOL)


def test_sparse_attention_reads_the_chosen_rows_only(tiny):
    cfg, _ = tiny
    slots, ps, pps, pages, k = 3, 16, 4, 16, 8
    k1, k2, k3, k4 = jax.random.split(jax.random.key(7), 4)
    r = mla.lane_pad(cfg.qk_rope_head_dim)
    ckv = jax.random.normal(k1, (pages, ps, cfg.kv_lora_rank))
    kr = jax.random.normal(k2, (pages, ps, r)).at[
        ..., cfg.qk_rope_head_dim:].set(0.0)
    bt = jnp.asarray(np.random.default_rng(1).permutation(pages)[
        : slots * pps].reshape(slots, pps), jnp.int32)
    h = cfg.num_attention_heads
    qa = jax.random.normal(k3, (slots, h, cfg.kv_lora_rank))
    qr = jax.random.normal(k4, (slots, h, cfg.qk_rope_head_dim))
    lengths = np.asarray([2, 17, 63])
    rng = np.random.default_rng(2)
    mask = np.zeros((slots, pps * ps), bool)
    for b, n in enumerate(lengths):
        pick = rng.permutation(n + 1)[:k]
        mask[b, pick] = True
    rows, n = dsa.mask_to_rows(jnp.asarray(mask), k)
    got = dsa.dsa_sparse_attn(qa, qr, ckv, kr, rows, n, bt,
                              scale=cfg.softmax_scale)
    c_all = ckv[bt].reshape(slots, -1, cfg.kv_lora_rank)
    r_all = kr[bt].reshape(slots, -1, r)[..., : cfg.qk_rope_head_dim]
    s = (jnp.einsum("bhc,bkc->bhk", qa, c_all)
         + jnp.einsum("bhr,bkr->bhk", qr, r_all)) * cfg.softmax_scale
    p = jax.nn.softmax(jnp.where(jnp.asarray(mask)[:, None], s, -jnp.inf), -1)
    np.testing.assert_allclose(got, jnp.einsum("bhk,bkc->bhc", p, c_all),
                               **TOL)


# -- the third seat -------------------------------------------------------------------


def test_page_layer_seats_and_page_bytes(tiny):
    cfg, _ = tiny
    layout = GlmDsaServeModel(cfg).cache_layout()
    assert [len(l.widths) for l in layout.layers] == [3, 2, 2, 2, 3]
    assert layout.third_seats and not layout.prefix_shareable
    assert layout.page_bytes(16, jnp.float32, False) == 16 * 4 * (
        5 * (128 + 128) + 2 * 128)
    assert PageLayer((4, 8)).x_width == 0 and PageLayer((4, 8, 2)).x_width == 2
    with pytest.raises(ValueError, match="two or three seats"):
        PageLayer((4,))
    two = CacheLayout((PageLayer((4, 8)),) * 2, jnp.float32)
    assert not two.third_seats and two.prefix_shareable
    assert two.page_bytes(16, jnp.float32, False) == 16 * 4 * 2 * 12
    assert two.page_bytes(16, jnp.int8, True) == 16 * 2 * (12 + 2 * 4)


def test_pool_allocates_copies_and_frees_as_the_layout_says(tiny):
    cfg, params = tiny
    pool = alloc_paged_cache(cfg, slots=2, num_pages=6, page_size=16)
    assert [None if a is None else a.shape for a in pool.x] == [
        (6, 16, 128), None, None, None, (6, 16, 128)]
    assert len(jax.tree.leaves(pool)) == 5 + 5 + 1 + 2
    eng = _engine(cfg, params)
    assert eng.page_bytes * eng.num_pages == sum(
        l.nbytes for l in jax.tree.leaves(
            (eng.cache.k, eng.cache.v, eng.cache.x)))
    # A page copy carries the third seat with the other two.
    mark = lambda c: dataclasses.replace(
        c, k=tuple(a.at[2].set(1.0) for a in c.k),
        v=tuple(a.at[2].set(2.0) for a in c.v),
        x=tuple(a if a is None else a.at[2].set(3.0) for a in c.x))
    eng.cache = mark(eng.cache)
    eng.copy_page(2, 5)
    for seat, want in ((eng.cache.k, 1.0), (eng.cache.v, 2.0),
                       (eng.cache.x, 3.0)):
        for a in seat:
            if a is not None:
                assert float(a[5].min()) == want and float(a[4].max()) == 0.0
    # Pages come and go by the block table alone, whatever the seats.
    free = eng.allocator.free_pages
    eng.allocator.admit(0, list(range(20)), 4)
    assert eng.allocator.free_pages == free - 2
    eng.allocator.free_slot(0)
    assert eng.allocator.free_pages == free


# -- the model through the cache -------------------------------------------------------


def _engine(cfg, params, *, slots=3, chunk=8, mode="reference", **kw):
    return Engine(cfg, params, slots=slots, max_len=64, seed=0,
                  kv_pages=slots * 4, kv_page_size=16, prefill_chunk=chunk,
                  decode_attention=mode, **kw)


def _through_the_cache(cfg, params, seq, prompt, mode, chunk=8):
    """Logits at every position of ``seq``: ``prompt`` tokens in chunks,
    the rest in decode ticks, in slot 1 of three; and each step's counts."""
    eng = _engine(cfg, params, mode=mode, chunk=chunk)
    model = eng.model
    eng.allocator.admit(1, seq[:prompt].tolist(), len(seq) - prompt + 1)
    bt = jnp.asarray(eng.allocator.block_tables, jnp.int32)
    cache, got, auxes = eng.cache, [], []
    forward = jax.jit(lambda *a: model.forward_paged(
        *a[:-1], return_hidden=False, row_valid=a[-1]))
    for base in list(range(0, prompt, chunk)) + list(range(prompt, len(seq))):
        n = min(chunk, prompt - base) if base < prompt else 1
        width = chunk if base < prompt else 1
        tokens = np.zeros((eng.slots, width), np.int32)
        tokens[1, :n] = seq[base:base + n]
        rows = (np.arange(width)[None] < n) & (np.arange(eng.slots) == 1)[
            :, None]
        lengths = jnp.asarray([0, base, 0], jnp.int32)
        logits, (k, v, state, x), aux = forward(
            params, jnp.asarray(tokens),
            dataclasses.replace(cache, lengths=lengths), bt,
            jnp.asarray(rows), jnp.asarray(rows))
        cache = PagedKVCache(k, v, lengths, state, x)
        got.append(np.asarray(logits[1, :n]))
        auxes.append((base, n, aux))
    return np.concatenate(got), auxes


@pytest.mark.parametrize("mode", ["interpret", "reference"])
@pytest.mark.parametrize("prompt, total", [(19, 27), (5, 16)])
def test_paged_prefill_then_decode_matches_the_reference_logits(
        tiny, mode, prompt, total):
    """A context that crosses ``index_topk`` (8) during its chunks, and
    one that crosses it during decode: the logits at every position are
    the reference's full forward, and the step counts what it read."""
    cfg, params = tiny
    seq = np.random.default_rng(prompt).integers(0, cfg.vocab_size, total)
    got, auxes = _through_the_cache(cfg, params, seq, prompt, mode)
    want = ref.logits_at(ref_cfg(cfg), params, params["layers"],
                         jnp.asarray(seq), jnp.arange(len(seq)), q_block=8)
    np.testing.assert_allclose(got, want, **TOL)
    np.testing.assert_allclose(
        gd.forward_plain(params, jnp.asarray(seq)[None], cfg)[0], want, **TOL)
    moe = cfg.mlp_layer_types.count("sparse")
    for base, n, aux in auxes:
        assert aux["expert_tokens"].shape == (moe, cfg.n_routed_experts)
        assert float(aux["moe_choices"]) == n * cfg.num_experts_per_tok * moe
        assert float(aux["moe_choices_here"]) == float(aux["moe_choices"])
        seen = base + 1 + np.arange(n)
        assert float(aux["dsa_rows_cached"]) == seen.sum() * 5
        assert float(aux["dsa_rows_read"]) == np.minimum(seen, 8).sum() * 5


def test_dropping_rows_changes_the_logits(tiny):
    """The choice is applied: with it ignored (the reference's
    ``dense_attention`` control) the logits past ``index_topk`` differ."""
    cfg, params = tiny
    seq = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size,
                                                        24))
    args = (ref_cfg(cfg), params, params["layers"], seq, jnp.arange(24))
    sparse = ref.logits_at(*args, q_block=8)
    dense = ref.logits_at(*args, q_block=8, dense_attention=True)
    np.testing.assert_allclose(sparse[:8], dense[:8], **TOL)
    assert float(jnp.abs(sparse[12:] - dense[12:]).max()) > 1e-2


def test_a_shared_layer_attends_over_the_full_layers_set(tiny):
    """Layers 1-3 reuse layer 0's choice and layer 4 chooses anew: in the
    program's plain forward, and row for row in the reference's."""
    cfg, params = tiny
    seq = jnp.asarray(np.random.default_rng(4).integers(0, cfg.vocab_size,
                                                        30))
    _, sets = gd.forward_plain(params, seq[None], cfg, with_sets=True)
    sets = [np.asarray(s[0]) for s in sets]
    for shared in sets[1:4]:
        np.testing.assert_array_equal(shared, sets[0])
    assert (sets[4] != sets[0]).any()
    assert (sets[0].sum(-1) == np.minimum(np.arange(30) + 1, 8)).all()
    # The reference's choices, layer by layer, are the same sets.
    rc = ref_cfg(cfg)
    x, pos = ref.embed(rc, params["embed"], seq), jnp.arange(30)
    chosen = ref.no_choice(rc, 30)
    causal = np.tril(np.ones((30, 30), bool))
    for lw, ours in zip(params["layers"], sets):
        x, chosen = ref.layer_forward(rc, lw, x, pos, chosen, q_block=8)
        theirs = np.zeros((30, 30), bool)
        np.put_along_axis(theirs, np.asarray(chosen), True, axis=-1)
        np.testing.assert_array_equal(theirs & causal, ours)


def test_a_tick_under_the_choice_takes_the_dense_kernel(tiny):
    """While every slot's rows fit ``index_topk`` the tick runs xing4's
    latent decode kernel; past it the chosen rows' gather. Both are in
    the lowered step, and the dense one alone is a kernel."""
    cfg, params = tiny
    eng = _engine(cfg, params, mode="interpret")
    s = eng.slots
    i32, f32 = jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32)
    args = (eng.params, eng.cache, eng.last_token, jnp.ones((s,), bool),
            jnp.asarray(eng.allocator.block_tables, jnp.int32),
            jax.random.key(0), f32, i32)
    names = _pallas_names(jax.make_jaxpr(eng._decode_paged_jit)(*args).jaxpr)
    assert {"mla_paged_decode_attn", "dsa_index_scores_tick"} <= names



def _serve(engine, prompts, new=6):
    server = Server(engine)
    for i, p in enumerate(prompts):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=new))
    return {c.rid: c.tokens for c in server.run()}, server


def _prompts(cfg, lens=(5, 19, 11, 26, 8)):
    rng = np.random.default_rng(2)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]


def test_served_tokens_are_the_reference_argmax(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params)
    warm_engine(eng)
    prompts = _prompts(cfg, lens=(5, 19, 26))
    served, _ = _serve(eng, prompts, new=4)
    assert eng.compile_watch.unexpected == 0
    for rid, p in enumerate(prompts):
        seq = jnp.asarray(p + served[rid])
        pos = jnp.arange(len(p) - 1, len(seq) - 1)
        logits = ref.logits_at(ref_cfg(cfg), params, params["layers"], seq,
                               pos, q_block=8)
        gap = logits.max(-1) - logits[jnp.arange(len(pos)),
                                      jnp.asarray(served[rid])]
        assert float(gap.max()) < 1e-4, (rid, gap)


def test_compacted_chunk_tick_equals_the_full_batch_one(tiny, monkeypatch):
    cfg, params = tiny
    full = _engine(cfg, params, slots=4)
    monkeypatch.setattr(engine_module, "_WEIGHT_BOUND_ROWS", 8)
    monkeypatch.setattr(engine_module, "_COMPACT_ROWS", 16)
    compact = _engine(cfg, params, slots=4)
    assert compact._prefill_counts == (1, 2)
    warm_engine(compact)
    assert compact.compile_watch.compiles == compact.compile_watch.expected
    want, _ = _serve(full, _prompts(cfg))
    got, _ = _serve(compact, _prompts(cfg))
    assert got == want
    assert compact.compile_watch.unexpected == 0


def test_spans_and_counters_say_what_attention_read(tiny):
    from mpit_tpu import obs

    cfg, params = tiny
    eng = _engine(cfg, params)
    rec = obs.enable(obs.Recorder())
    try:
        _, server = _serve(eng, _prompts(cfg, lens=(19, 26)), new=5)
    finally:
        obs.disable()
    events = rec.snapshot()["events"]
    ticks = [e[5] for e in events if e[1] == "decode" and e[5].get("active")]
    assert ticks and all(t["rows_read"] <= t["rows_cached"] for t in ticks)
    assert any(t["rows_read"] < t["rows_cached"] for t in ticks)
    landed = [t for t in ticks if "dsa_rows_read" in t]
    assert landed and all(
        t["moe_choices_here"] == t["moe_choices"] > 0 for t in landed)
    chunks = [e[5] for e in events if e[1] == "prefill" and e[5].get("chunks")]
    assert chunks and all(0 < c["rows_read"] <= c["rows_cached"]
                          for c in chunks)
    for name in ("dsa_rows_read", "dsa_rows_cached", "moe_choices",
                 "moe_choices_here"):
        assert rec.counter_total(name) > 0
        assert server.stats()["step_counts"][name] > 0
    assert (rec.counter_total("dsa_rows_read")
            < rec.counter_total("dsa_rows_cached"))
    assert rec.counter_total("moe_expert_tokens") == rec.counter_total(
        "moe_choices")


# -- the share of the experts ---------------------------------------------------------


def test_expert_shares_add_up(tiny):
    """The shares of four chips, two experts each, the shared expert
    counted once, give the uncut reference's whole layer: this family's
    top-k, its weights' normalisation and its scale."""
    cfg, params = tiny
    mp = params["layers"][2]["moe"]
    mp = {**mp, "bias": mp["bias"].at[2].add(0.5)}
    x = jax.random.normal(jax.random.key(6), (24, cfg.hidden_size))
    kw = dict(top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
              n_experts=cfg.n_routed_experts)
    assert cfg.routed_scaling_factor == 2.5
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
            y, ref.experts(x, part, ref_cfg(cfg), held=held), **TOL)
    assert here == 24 * cfg.num_experts_per_tok
    np.testing.assert_allclose(total, ref.experts(x, mp, ref_cfg(cfg)), **TOL)


def test_a_share_of_the_experts_through_the_cache(tiny):
    """An engine that holds two of the eight experts serves what the
    reference given the same share computes, and counts how many of its
    choices fell on them."""
    cfg, params = tiny
    held = (2, 5)
    part = dataclasses.replace(cfg, experts_held=held)
    cut = {**params, "layers": [
        lp if "moe" not in lp else {**lp, "moe": {
            **lp["moe"], **{k: lp["moe"][k][jnp.asarray(held)]
                            for k in ("w_gate", "w_up", "w_down")}}}
        for lp in params["layers"]]}
    seq = np.random.default_rng(9).integers(0, cfg.vocab_size, 22)
    got, auxes = _through_the_cache(part, cut, seq, 15, "reference")
    want = ref.logits_at(ref_cfg(part), cut, cut["layers"], jnp.asarray(seq),
                         jnp.arange(len(seq)), q_block=8, held=held)
    np.testing.assert_allclose(got, want, **TOL)
    here = sum(float(a["moe_choices_here"]) for _, _, a in auxes)
    every = sum(float(a["moe_choices"]) for _, _, a in auxes)
    assert 0 < here < every


# -- what the family lacks ---------------------------------------------------------------


@pytest.mark.parametrize("kw, what", [
    (dict(kv_dtype="int8"), "int8 cache"),
    (dict(weights_dtype="int8"), "int8 weights"),
    (dict(kv_host_pages=2), "host KV tier"),
    (dict(spec_k=2), "speculative"),
])
def test_what_the_family_lacks_raises_at_construction(tiny, kw, what):
    cfg, params = tiny
    args = dict(slots=2, max_len=64, kv_pages=8, kv_page_size=16)
    args.update(kw)
    with pytest.raises(ValueError, match=what):
        Engine(cfg, params, **args)


def test_shipment_and_preemption_raise_by_name(tiny):
    cfg, params = tiny
    eng = _engine(GlmDsaServeModel(cfg), params)
    assert eng.model.family == "glm_dsa" and eng.cfg is cfg
    with pytest.raises(ValueError, match="shipped"):
        eng.export_kv_rows(0, 4)
    with pytest.raises(ValueError, match="evicted"):
        eng.model.check_preemption()
    with pytest.raises(ValueError, match="tensor parallelism"):
        eng.model.check_supported(tp=True, kv_dtype=None, weights_dtype=None,
                                  spec_k=0, host_pages=0)


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
    import re

    cfg, params = tiny
    eng = _engine(cfg, params, mode="interpret")
    s = eng.slots
    i32, f32 = jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32)
    bt, key = jnp.asarray(eng.allocator.block_tables, jnp.int32), jax.random.key(0)
    scopes = ["embed", "attn", "kv_write", "dsa_index", "dsa_select",
              "moe_route", "moe_dispatch", "moe_experts", "moe_shared",
              "moe_combine", "mlp", "lm_head", "sample"]
    if step == "decode":
        jit, args = eng._decode_paged_jit, (
            eng.params, eng.cache, eng.last_token, jnp.ones((s,), bool), bt,
            key, f32, i32)
        scopes += ["mla_absorb", "dsa_sparse_attn"]
        kernels = {"dsa_index_scores_tick", "mla_paged_decode_attn"}
        form = "latent_absorbed"
    else:
        jit, args = eng._prefill_paged_jit, (
            eng.params, eng.cache, eng.last_token,
            jnp.zeros((s, eng.prefill_chunk), jnp.int32), i32, i32, i32,
            jnp.zeros((s,), bool), bt, key, f32, i32)
        # The chunk's kernel reads the pages in place: it and the
        # layouts round it are the scope mla_expand, and kv_gather is
        # the lax twin's alone.
        scopes.append("mla_expand")
        kernels = {"dsa_index_scores_chunk", "mla_paged_chunk_attn"}
        form = "latent_expanded_kernel"
    text = jit.lower(*args).as_text(debug_info=True)
    assert f"module @jit_{step}_paged " in text
    for scope in scopes:
        assert re.search(rf'["/(]{scope}[/)]', text), scope
    assert kernels <= _pallas_names(jax.make_jaxpr(jit)(*args).jaxpr)
    rows = 1 if step == "decode" else eng.prefill_chunk
    assert eng.attention_tiling(rows)["attention_form"] == form
    out = jax.eval_shape(jit, *args)
    assert set(out[2]) == {"dsa_rows_read", "dsa_rows_cached",
                           "expert_tokens", "moe_choices", "moe_choices_here"}
