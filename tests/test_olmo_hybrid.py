"""The olmo_hybrid family on the CPU at a tiny size: two periods of three
linear-attention layers and a full one, three heads, key and value widths
1 : 2. The gated delta rule's three forms against each other, the program
against the plain reference (logits, not tokens), the state pool beside
the page pool through the engine's three steps, and what the family
refuses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.models import olmo_hybrid as oh
from mpit_tpu.models import olmo_hybrid_reference as ref
from mpit_tpu.models.olmo_hybrid import (
    OlmoHybridConfig,
    OlmoHybridServeModel,
    init_params,
)
from mpit_tpu.ops import gated_delta as gd
from mpit_tpu.serve import Engine, Request, Server, warm_engine
from mpit_tpu.serve import engine as engine_module
from mpit_tpu.serve.kvcache import PagedKVCache
from mpit_tpu.serve.policy import PolicyConfig, SchedulingPolicy

# float32 program against a float32 reference on the CPU: what is left is
# the order of summation (the chunkwise form against the scan, the blocked
# softmax), a few ulp of values of order 1.
TOL = dict(rtol=2e-4, atol=2e-5)


def ref_cfg(cfg: OlmoHybridConfig) -> dict:
    """The reference's plain dict, with the published key names."""
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d["layer_types"] = list(cfg.layer_types)
    return d


@pytest.fixture(scope="module")
def tiny():
    cfg = OlmoHybridConfig.tiny()
    return cfg, init_params(cfg, jax.random.key(3))


# -- the rule's three forms --------------------------------------------------------


def _rule_inputs(seed, b=2, t=100, h=3, dk=12, dv=24, beta_lo=1.0):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jax.random.uniform(ks[3], (b, t, h), minval=1e-4, maxval=0.3)
    beta = jax.random.uniform(ks[4], (b, t, h), minval=beta_lo, maxval=2.0)
    state = jax.random.normal(ks[5], (b, h, dk, dv))  # not from zeros
    return q, k, v, g, beta, state


CHUNK_FORMS = {
    "lax": gd.gdn_chunk_lax,
    "kernel": lambda *a: gd.gdn_chunk(*a, interpret=True),
}


@pytest.mark.parametrize("form", sorted(CHUNK_FORMS))
@pytest.mark.parametrize("t", [1, 37, 64, 100, 130])
def test_chunk_form_matches_the_scan(form, t):
    """From a state that is not zero, with ``beta`` in (1, 2) (negative
    eigenvalues), at lengths that are no multiple of the block of 64."""
    args = _rule_inputs(t, t=t)
    want_o, want_s = gd.gdn_scan(*args)
    got_o, got_s = CHUNK_FORMS[form](*args)
    np.testing.assert_allclose(got_o, want_o, **TOL)
    np.testing.assert_allclose(got_s, want_s, **TOL)


@pytest.mark.parametrize("form", sorted(CHUNK_FORMS))
def test_padded_tail_leaves_the_state_untouched(form):
    """Rows with ``g = 0`` and ``beta = 0`` behind the valid ones: the
    state after 70 rows of which 41 are tokens is the state after the 41,
    bit for bit what the same form gives for the 41 alone padded by its
    own means."""
    q, k, v, g, beta, state = _rule_inputs(7, t=70, beta_lo=0.0)
    keep = (jnp.arange(70) < 41)[None, :, None]
    g, beta = jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0)
    _, got = CHUNK_FORMS[form](q, k, v, g, beta, state)
    cut = lambda x: x[:, :41]
    _, want = gd.gdn_scan(cut(q), cut(k), cut(v), cut(g), cut(beta), state)
    np.testing.assert_allclose(got, want, **TOL)
    _, alone = CHUNK_FORMS[form](cut(q), cut(k), cut(v), cut(g), cut(beta),
                                 state)
    np.testing.assert_array_equal(got, alone)
    # No token at all: the state as it was, exactly.
    zero = jnp.zeros_like(g)
    _, same = CHUNK_FORMS[form](q, k, v, zero, zero, state)
    np.testing.assert_array_equal(same, state)


@pytest.mark.parametrize("form", ["lax", "kernel"])
def test_step_form_matches_the_scan(form):
    q, k, v, g, beta, state = _rule_inputs(11, t=1)
    want_o, want_s = gd.gdn_scan(q, k, v, g, beta, state)
    step = gd.gdn_step_lax if form == "lax" else (
        lambda *a: gd.gdn_step(*a, interpret=True))
    # The second sequence is idle: its state must come back as it was.
    g, beta = g.at[1].set(0.0), beta.at[1].set(0.0)
    got_o, got_s = step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
    np.testing.assert_allclose(got_o[0], want_o[0, 0], **TOL)
    np.testing.assert_allclose(got_s[0], want_s[0], **TOL)
    np.testing.assert_array_equal(got_s[1], state[1])


def test_unit_lower_inverse_is_steady_where_the_series_is_not():
    """Keys all alike and ``beta`` = 2: ``A`` is 2 below the diagonal,
    whose powers reach 1e18; the inverse's entries are +-2. Doubling by
    blocks is forward substitution and finds them."""
    c = gd.BLOCK
    a = 2.0 * jnp.tril(jnp.ones((c, c), jnp.float32), -1)
    eye = jnp.eye(c, dtype=jnp.float32)
    mm = lambda x, y: jnp.matmul(x, y, precision="highest")
    inv = gd._unit_lower_inverse(a, gd._block_masks(c, 8), mm)
    np.testing.assert_allclose(mm(eye + a, inv), eye, atol=1e-5)
    assert float(jnp.abs(inv).max()) == 2.0


# -- the program against the reference -------------------------------------------------


def test_configuration_keeps_the_published_keys():
    cfg = OlmoHybridConfig.from_dict({
        "num_hidden_layers": 8, "max_position_embeddings": 4096,
        "layer_types": [oh.LINEAR] * 3 + [oh.FULL] + [oh.LINEAR] * 3
        + [oh.FULL], "rope_parameters": {"rope_theta": None},
        "model_type": "olmo_hybrid"})
    assert cfg.layer_types == OlmoHybridConfig(num_hidden_layers=8).layer_types
    assert cfg.max_seq_len == 4096 and cfg.head_dim == 128
    assert cfg.conv_channels == 11520
    lay = cfg.serve_model().cache_layout()
    assert len(lay.page_layers) == 2 and len(lay.state_layers) == 6
    assert not lay.prefix_shareable
    # The issue's arithmetic: 30,720 B a cached position, 2,211,840 +
    # 69,120 B a slot and linear layer.
    assert lay.page_bytes(1, jnp.bfloat16, False) == 30720
    assert lay.state_slot_bytes() == 6 * (2211840 + 69120)
    with pytest.raises(NotImplementedError, match="grouped"):
        OlmoHybridConfig(num_key_value_heads=10)
    with pytest.raises(ValueError, match="layer_types"):
        OlmoHybridConfig(num_hidden_layers=4, layer_types=("full",) * 4)


def test_whole_prompt_logits_match_the_reference(tiny):
    cfg, params = tiny
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 70))
    got = oh.forward_plain(params, jnp.asarray(toks), cfg)
    for row, seq in zip(got, toks):
        want = ref.forward(ref_cfg(cfg), params, jnp.asarray(seq))
        np.testing.assert_allclose(row, want, **TOL)


def test_decay_init_spans_slow_and_fast_heads():
    a_log, dt_bias = oh.decay_init(jax.random.key(0), 512)
    alpha = jnp.exp(-jnp.exp(a_log) * jax.nn.softplus(dt_bias))
    assert 0.9 <= float(alpha.min()) < 0.92
    assert 0.9995 < float(alpha.max()) <= 0.9999


def _engine(cfg, params, mode="kernel", slots=3, **kw):
    args = dict(slots=slots, max_len=128, kv_page_size=16, prefill_chunk=16,
                decode_attention=mode)
    args.update(kw)
    return Engine(cfg, params, **args)


@pytest.mark.parametrize("mode", ["reference", "interpret"])
def test_paged_prefill_then_decode_matches_the_reference_logits(tiny, mode):
    """A prompt of 43 tokens in chunks of 16 (the last one ragged) in slot
    1 of 3, then decode ticks, through both caches: the logits of every
    served position against the reference's full forward. The other slots
    ride along as rows that are no tokens; their seats must not move."""
    cfg, params = tiny
    eng = _engine(cfg, params, mode=mode)
    model, chunk = eng.model, eng.prefill_chunk
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, 52)
    prompt = 43
    eng.allocator.admit(1, seq[:prompt].tolist(), len(seq) - prompt + 1)
    bt = jnp.asarray(eng.allocator.block_tables, jnp.int32)
    # The seats start as noise: a first chunk must not read them.
    noise = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(5), a.shape, a.dtype),
        eng.cache.state)
    cache, got = dataclasses.replace(eng.cache, state=noise), []
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
        logits, (k, v, state), aux = forward(
            params, jnp.asarray(tokens),
            dataclasses.replace(cache, lengths=lengths), bt,
            jnp.asarray(rows), jnp.asarray(rows))
        cache = PagedKVCache(k=k, v=v, lengths=lengths, state=state)
        got.append(np.asarray(logits[1, :n]))
        assert aux is None
    want = ref.forward(ref_cfg(cfg), params, jnp.asarray(seq))
    np.testing.assert_allclose(np.concatenate(got), want, **TOL)
    for seat, was in zip(cache.state, noise):
        for name in seat:
            np.testing.assert_array_equal(seat[name][0], was[name][0])
            np.testing.assert_array_equal(seat[name][2], was[name][2])


def test_a_long_chunk_attends_in_parts(tiny):
    """A chunk of 128 rows goes to the attention kernel as two queries of
    64, each at its own fill of the same pages: the same logits."""
    cfg, params = tiny
    assert oh._ATTN_ROWS == 64
    eng = _engine(cfg, params, mode="interpret", slots=2, max_len=256,
                  prefill_chunk=128)
    seq = np.random.default_rng(2).integers(0, cfg.vocab_size, 150)
    eng.allocator.admit(0, seq.tolist(), 2)
    bt = jnp.asarray(eng.allocator.block_tables, jnp.int32)
    cache, got = eng.cache, []
    for base, n in ((0, 128), (128, 22)):
        tokens = np.zeros((2, 128), np.int32)
        tokens[0, :n] = seq[base:base + n]
        rows = jnp.asarray((np.arange(128)[None] < n)
                           & (np.arange(2) == 0)[:, None])
        lengths = jnp.asarray([base, 0], jnp.int32)
        logits, (k, v, state), _ = eng.model.forward_paged(
            params, jnp.asarray(tokens),
            dataclasses.replace(cache, lengths=lengths), bt, rows,
            return_hidden=False, row_valid=rows)
        cache = PagedKVCache(k=k, v=v, lengths=lengths, state=state)
        got.append(np.asarray(logits[0, :n]))
    want = ref.forward(ref_cfg(cfg), params, jnp.asarray(seq))
    np.testing.assert_allclose(np.concatenate(got), want, **TOL)


def _prompts(cfg, lens=(5, 37, 16, 50, 23, 9), seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]


def _serve(engine, prompts, new=6):
    server = Server(engine)
    for i, p in enumerate(prompts):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=new))
    return {c.rid: list(c.tokens) for c in server.run()}


def _gaps(cfg, params, prompts, served) -> float:
    """The widest gap between a served token's reference logit and the
    reference's best at its position."""
    worst = 0.0
    for rid, p in enumerate(prompts):
        toks = served[rid]
        logits = ref.forward(ref_cfg(cfg), params, jnp.asarray(p + toks[:-1]))
        at = np.arange(len(p) - 1, len(p) - 1 + len(toks))
        gap = jnp.max(logits[at], -1) - logits[at, jnp.asarray(toks)]
        worst = max(worst, float(gap.max()))
    return worst


def _compacting(monkeypatch):
    monkeypatch.setattr(engine_module, "_WEIGHT_BOUND_ROWS", 16)
    monkeypatch.setattr(engine_module, "_COMPACT_ROWS", 32)


@pytest.mark.parametrize("step", ["full-batch", "compacted"])
def test_a_refilled_slot_carries_nothing_over(tiny, step, monkeypatch):
    """Six requests through three slots (every slot retires and is
    refilled, and no seat is ever cleared): each is served what the
    reference puts first, which is what a fresh engine serves it."""
    cfg, params = tiny
    if step == "compacted":
        _compacting(monkeypatch)
    eng = _engine(cfg, params)
    assert eng._prefill_counts == ((1, 2) if step == "compacted" else ())
    if step == "compacted":
        warm_engine(eng)  # every count of participants, before the first tick
    prompts = _prompts(cfg)
    served = _serve(eng, prompts)
    assert _gaps(cfg, params, prompts, served) < 1e-4
    assert eng.compile_watch.unexpected == 0
    # The last refill, alone in an engine that served nothing.
    assert _serve(_engine(cfg, params), [prompts[5]])[0] == served[5]


def test_compacted_tick_equals_the_full_batch_one(tiny, monkeypatch):
    cfg, params = tiny
    want = _serve(_engine(cfg, params), _prompts(cfg))
    _compacting(monkeypatch)
    assert _serve(_engine(cfg, params), _prompts(cfg)) == want


def test_one_prompt_twice_passes_the_prefix_hit_up(tiny):
    """The second request finds the first one's registered prefix; pages
    without the state at that boundary would serve wrong tokens, so the
    hit is counted and passed up, and both are computed whole."""
    from mpit_tpu import obs

    cfg, params = tiny
    eng = _engine(cfg, params, slots=2)
    prompt = _prompts(cfg, lens=(37,))[0]
    rec = obs.enable(obs.Recorder())
    try:
        server = Server(eng)
        server.submit(Request(rid=0, prompt=prompt, max_new_tokens=12))
        server.run(max_ticks=5)  # the first is past its prefill
        server.submit(Request(rid=1, prompt=prompt, max_new_tokens=6))
        served = {c.rid: list(c.tokens) for c in server.run()}
        stats = server.stats()
    finally:
        obs.disable()
    assert eng.allocator.prefix_hits_passed_up == 1
    assert eng.allocator.prefix_hits == 0
    assert stats["prefix_hits_passed_up"] == 1 and stats["prefix_hits"] == 0
    assert rec.counter_total("prefix_hits_passed_up") == 1
    assert _gaps(cfg, params, [prompt, prompt], served) < 1e-4
    assert served[1] == served[0][:6]


def test_memory_lines_count_the_state_pool(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params)
    lay = eng.model.cache_layout()
    seat = lay.state_slot_bytes()
    assert seat == 6 * (3 * 12 * 24 * 4 + 3 * cfg.conv_channels * 4)
    assert eng.slot_state_bytes == seat
    assert eng.page_bytes == 16 * 2 * 2 * cfg.hidden_size * 4
    ml = eng.memledger
    assert ml.capacity("kv_state") == seat * eng.slots
    server = Server(eng)
    server.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4))
    server.run(max_ticks=1)
    assert ml.held("kv_state") == seat
    assert ml.held("kv_pages") == eng.page_bytes
    server.run()
    assert ml.held("kv_state") == 0
    assert ml.conservation()["ok"]
    eng.reset()
    assert all(not bool(jnp.any(leaf)) for leaf in
               jax.tree.leaves(eng.cache.state))
    assert ml.conservation()["ok"]


# -- what the family refuses, by name --------------------------------------------------


@pytest.mark.parametrize("kw, what", [
    (dict(kv_dtype="int8"), "int8 cache"),
    (dict(weights_dtype="int8"), "int8 weights"),
    (dict(kv_host_pages=2), "host KV tier"),
    (dict(spec_k=2), "speculative"),
    (dict(tp_axis="model"), "tensor parallelism"),
])
def test_what_the_family_lacks_raises_at_construction(tiny, kw, what):
    cfg, params = tiny
    args = dict(slots=2, max_len=64, kv_pages=8, kv_page_size=16)
    args.update(kw)
    with pytest.raises(ValueError, match=f"olmo_hybrid.*{what}"):
        Engine(cfg, params, **args)


def test_moving_a_slot_is_refused_by_name(tiny):
    cfg, params = tiny
    eng = _engine(OlmoHybridServeModel(cfg), params)
    assert eng.model.family == "olmo_hybrid" and eng.cfg is cfg
    with pytest.raises(ValueError, match="shipped"):
        eng.export_kv_rows(0, 4)
    with pytest.raises(ValueError, match="preempted or parked"):
        Server(eng, policy=SchedulingPolicy(PolicyConfig(preempt=True)))
    server = Server(eng, policy=SchedulingPolicy(PolicyConfig(preempt=False)))
    server.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=8))
    server.run(max_ticks=1)
    with pytest.raises(ValueError, match="preempted or parked"):
        server._preempt(next(iter(server.prefilling or server.live)))
    assert [c.rid for c in server.run()] == [0]


@pytest.mark.parametrize("family", ["gpt2", "xing4"])
def test_the_other_families_have_no_state_pool(family):
    """Their caches flatten to the leaves they had (the lowered steps are
    the parent's text: ``lowered_hashes.py``), and nothing of the state
    pool's bookkeeping shows in their ledgers."""
    if family == "gpt2":
        from mpit_tpu.models import GPT2, GPT2Config

        cfg = GPT2Config.tiny(vocab_size=64, max_seq_len=64, num_layers=2,
                              num_heads=2, d_model=32, dtype=jnp.float32)
        params = GPT2(cfg).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    else:
        from mpit_tpu.models import xing4

        cfg = xing4.Xing4Config.tiny(max_seq_len=64)
        params = xing4.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, slots=2, max_len=64, kv_pages=8,
                 kv_page_size=16)
    assert eng.cache.state == () and eng.slot_state_bytes == 0
    assert len(jax.tree.leaves(eng.cache)) == 2 * cfg.num_layers + 1
    assert eng.allocator.prefix_shareable
    assert "kv_state" not in eng.memledger.decompose()
    assert "prefix_hits_passed_up" not in Server(eng).stats()


# -- names on the device's clock -----------------------------------------------------


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
    bt = jnp.asarray(eng.allocator.block_tables, jnp.int32)
    key = jax.random.key(0)
    scopes = ["embed", "attn", "kv_write", "linear_attn", "gdn_conv",
              "state_pool_move", "mlp", "lm_head", "sample"]
    if step == "decode":
        jit, args = eng._decode_paged_jit, (
            eng.params, eng.cache, eng.last_token, jnp.ones((s,), bool), bt,
            key, f32, i32)
        scopes.append("gdn_step")
        kernels = {"gdn_step", "paged_decode_attn"}
    else:
        jit, args = eng._prefill_paged_jit, (
            eng.params, eng.cache, eng.last_token,
            jnp.zeros((s, eng.prefill_chunk), jnp.int32), i32, i32, i32,
            jnp.zeros((s,), bool), bt, key, f32, i32)
        scopes.append("gdn_chunk")
        kernels = {"gdn_chunk", "paged_decode_attn"}
    text = jit.lower(*args).as_text(debug_info=True)
    for scope in scopes:
        assert re.search(rf'["/(]{scope}[/)]', text), scope
    assert kernels <= _pallas_names(jax.make_jaxpr(jit)(*args).jaxpr)
