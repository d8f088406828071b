"""The xing4 family on the CPU at a tiny size, every mechanism on: each
sublayer against the plain reference, the paged latent pool against the
reference's full forward (logits, not tokens), the expert shares, the
latent decode kernel in interpret mode, the compacted chunk tick."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu.models import xing4 as x4
from mpit_tpu.models import xing4_reference as ref
from mpit_tpu.models.xing4 import Xing4Config, Xing4ServeModel, init_params
from mpit_tpu.ops import mla_attention as mla
from mpit_tpu.parallel.moe_serve import expert_layer
from mpit_tpu.serve import Engine, Request, Server, warm_engine
from mpit_tpu.serve import engine as engine_module
from mpit_tpu.serve.kvcache import PagedKVCache

# float32 program against a float32 reference on the CPU: what is left is
# the order of summation (blocked softmax, absorbed products, the grouped
# product against a masked sum), a few ulp of values of order 1.
TOL = dict(rtol=2e-4, atol=2e-5)


def ref_cfg(cfg: Xing4Config) -> dict:
    """The reference's plain dict, with the published key names."""
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    d.update(
        mhc_h_res_clamp_min=cfg.hc_clamp_min,
        mhc_h_res_clamp_max=cfg.hc_clamp_max,
        rope_scaling={
            "factor": cfg.rope_factor, "beta_fast": cfg.rope_beta_fast,
            "beta_slow": cfg.rope_beta_slow, "mscale": cfg.rope_mscale,
            "mscale_all_dim": cfg.rope_mscale_all_dim,
            "original_max_position_embeddings": cfg.rope_original_max,
        })
    return d


@pytest.fixture(scope="module")
def tiny():
    cfg = Xing4Config.tiny()
    return cfg, init_params(cfg, jax.random.key(3))


def test_sinkhorn_is_doubly_stochastic():
    m = jax.random.normal(jax.random.key(0), (5, 4, 4))
    out = x4.sinkhorn(m, 20, 1e-6)
    np.testing.assert_allclose(out.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(out.sum(-2), 1.0, atol=1e-4)
    assert (np.asarray(out) > 0).all()


def test_rotary_frequencies_match_the_reference(tiny):
    cfg, _ = tiny
    np.testing.assert_array_equal(x4.yarn_inv_freq(cfg),
                                  ref.inv_freq(ref_cfg(cfg)))
    pub = Xing4Config()  # the published sizes: factor 64 over 4096
    np.testing.assert_array_equal(x4.yarn_inv_freq(pub),
                                  ref.inv_freq(ref_cfg(pub)))
    assert abs(pub.softmax_scale - 192 ** -0.5 * 1.4159 ** 2) < 1e-4
    assert pub.softmax_scale == pytest.approx(
        ref.softmax_scale(ref_cfg(pub)))


def test_hyper_connection_matches_the_reference(tiny):
    cfg, params = tiny
    hp = params["layers"][1]["hc_attn"]
    hp = {**hp, "a": jnp.asarray([0.7, -0.5, 1.3])}  # away from the init
    xs = jax.random.normal(jax.random.key(1), (6, cfg.hc_mult,
                                               cfg.hidden_size))
    f = lambda u: jnp.tanh(u) * 0.5
    got, _ = x4.hc_sublayer(hp, xs, cfg, lambda u: (f(u), None))
    want = ref.hyper_connection(hp, xs, ref_cfg(cfg), f)
    np.testing.assert_allclose(got, want, **TOL)


def test_mla_expanded_absorbed_and_reference_agree(tiny):
    cfg, params = tiny
    ap, t = params["layers"][0]["attn"], 12
    u = jax.random.normal(jax.random.key(2), (1, t, cfg.hidden_size))
    pos = jnp.arange(t)[None]
    cos, sin = x4.rope_tables(cfg, pos)
    qn, qr, c_kv, k_rope = x4.mla_project(ap, u, cfg, cos, sin)
    expanded = x4._dot(
        x4.mla_expanded_dense(ap, qn, qr, c_kv, k_rope, cfg), ap["w_o"])

    def attend_last(q_abs, q_rope):  # every position is visible to the last
        s = jnp.einsum("bhc,bkc->bhk", q_abs, c_kv) + jnp.einsum(
            "bhr,bkr->bhk", q_rope, k_rope)
        return jnp.einsum("bhk,bkc->bhc",
                          jax.nn.softmax(s * cfg.softmax_scale, -1), c_kv)

    absorbed = x4._dot(
        x4.mla_absorbed(ap, qn[:, -1], qr[:, -1], attend_last, cfg),
        ap["w_o"])
    want = ref.attention(ap, u[0], pos[0], ref_cfg(cfg), q_block=5)
    np.testing.assert_allclose(expanded[0], want, **TOL)
    np.testing.assert_allclose(absorbed[0], want[-1], **TOL)


def _skewed(mp, expert=5, by=4.0):
    """A selection bias that sends most tokens to one expert."""
    return {**mp, "bias": mp["bias"].at[expert].add(by)}


def test_expert_layer_drops_nothing_under_skew(tiny):
    cfg, params = tiny
    mp = _skewed(params["layers"][1]["moe"])
    x = jax.random.normal(jax.random.key(4), (40, cfg.hidden_size))
    y, counts = expert_layer(
        x, mp, top_k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, n_experts=cfg.n_routed_experts)
    assert int(counts[5]) == 40  # every token chose the favoured expert
    assert int(counts.sum()) == 40 * cfg.num_experts_per_tok
    want = ref.experts(x, mp, ref_cfg(cfg))
    np.testing.assert_allclose(y, want, **TOL)


def test_expert_layer_skips_rows_that_are_no_tokens(tiny):
    cfg, params = tiny
    mp = params["layers"][1]["moe"]
    x = jax.random.normal(jax.random.key(5), (10, cfg.hidden_size))
    valid = jnp.arange(10) < 7
    kw = dict(top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
              n_experts=cfg.n_routed_experts)
    y, counts = expert_layer(x, mp, valid=valid, **kw)
    y7, counts7 = expert_layer(x[:7], mp, **kw)
    np.testing.assert_allclose(y[:7], y7, **TOL)
    np.testing.assert_array_equal(counts, counts7)


def test_expert_shares_add_up(tiny):
    """Two chips' shares of four experts each, the shared expert counted
    once, give the uncut reference's whole layer."""
    cfg, params = tiny
    mp = _skewed(params["layers"][2]["moe"], expert=2, by=0.5)
    x = jax.random.normal(jax.random.key(6), (24, cfg.hidden_size))
    kw = dict(top_k=cfg.num_experts_per_tok, scale=cfg.routed_scaling_factor,
              n_experts=cfg.n_routed_experts)
    total = 0.0
    for share, held in enumerate(((0, 2, 4, 6), (1, 3, 5, 7))):
        part = {k: mp[k] for k in ("router", "bias")}
        part.update({k: mp[k][jnp.asarray(held)]
                     for k in ("w_gate", "w_up", "w_down")})
        if share == 0:
            part["shared"] = mp["shared"]
        y, counts = expert_layer(x, part, held=held, **kw)
        assert int(counts.sum()) == 24 * cfg.num_experts_per_tok  # of all 8
        total = total + y
        # The reference is given the same share and agrees with it.
        np.testing.assert_allclose(
            y, ref.experts(x, part, ref_cfg(cfg), held=held), **TOL)
    np.testing.assert_allclose(total, ref.experts(x, mp, ref_cfg(cfg)), **TOL)


def _pool(cfg, key, pages, ps, slots, pps):
    """A random latent pool and block tables over disjoint pages."""
    k1, k2 = jax.random.split(key)
    r = mla.lane_pad(cfg.qk_rope_head_dim)
    ckv = jax.random.normal(k1, (pages, ps, cfg.kv_lora_rank))
    kr = jax.random.normal(k2, (pages, ps, r))
    kr = kr.at[..., cfg.qk_rope_head_dim:].set(0.0)
    bt = np.random.default_rng(0).permutation(pages)[: slots * pps]
    return ckv, kr, jnp.asarray(bt.reshape(slots, pps), jnp.int32)


@pytest.mark.parametrize("block_k", [8, 16])
def test_decode_kernel_matches_gather_dense(tiny, block_k):
    cfg, _ = tiny
    slots, ps, pps = 3, 16, 4
    ckv, kr, bt = _pool(cfg, jax.random.key(7), 16, ps, slots, pps)
    k1, k2 = jax.random.split(jax.random.key(8))
    h = cfg.num_attention_heads
    qa = jax.random.normal(k1, (slots, h, cfg.kv_lora_rank))
    qr = jax.random.normal(k2, (slots, h, cfg.qk_rope_head_dim))
    lengths = jnp.asarray([0, 17, 63], jnp.int32)
    args = (qa, qr, ckv, kr, lengths, bt)
    want = mla.reference_mla_paged_decode_attention(
        *args, scale=cfg.softmax_scale)
    got = mla.mla_paged_decode_attention(
        *args, scale=cfg.softmax_scale, block_k=block_k, interpret=True)
    np.testing.assert_allclose(got, want, **TOL)


def _engine(cfg, params, *, slots=3, chunk=8, mode="reference", **kw):
    return Engine(cfg, params, slots=slots, max_len=64, seed=0,
                  kv_pages=slots * 4, kv_page_size=16, prefill_chunk=chunk,
                  decode_attention=mode, **kw)


@pytest.mark.parametrize("mode", ["interpret", "reference"])
def test_paged_prefill_then_decode_matches_the_reference_logits(tiny, mode):
    """A prompt in chunks, then decode ticks, through the latent page
    pool: the logits at every position are the reference's full forward."""
    cfg, params = tiny
    eng = _engine(cfg, params, mode=mode)
    model, chunk = eng.model, 8
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, 27)
    prompt = 19
    eng.allocator.admit(1, seq[:prompt].tolist(), len(seq) - prompt + 1)
    bt = jnp.asarray(eng.allocator.block_tables, jnp.int32)
    cache, got = eng.cache, []
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
        logits, (k, v, _), counts = forward(
            params, jnp.asarray(tokens),
            PagedKVCache(k=cache.k, v=cache.v, lengths=lengths), bt,
            jnp.asarray(rows), jnp.asarray(rows))
        cache = PagedKVCache(k=k, v=v, lengths=lengths)
        got.append(np.asarray(logits[1, :n]))
        assert counts.shape == (cfg.num_moe_layers, cfg.n_routed_experts)
        assert int(counts.sum()) == (
            n * cfg.num_experts_per_tok * cfg.num_moe_layers)
    want = ref.logits_at(
        ref_cfg(cfg), params, params["layers"], jnp.asarray(seq),
        jnp.arange(len(seq)), q_block=8)
    np.testing.assert_allclose(np.concatenate(got), want, **TOL)
    np.testing.assert_allclose(
        x4.forward_plain(params, jnp.asarray(seq)[None], cfg)[0], want, **TOL)


def _serve(engine, prompts, new=6):
    server = Server(engine)
    for i, p in enumerate(prompts):
        server.submit(Request(rid=i, prompt=p, max_new_tokens=new))
    return {c.rid: c.tokens for c in server.run()}


def _prompts(cfg, lens=(5, 19, 11, 26, 8)):
    rng = np.random.default_rng(2)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lens]


def test_served_tokens_are_the_reference_argmax(tiny):
    cfg, params = tiny
    eng = _engine(cfg, params)
    warm_engine(eng)
    prompts = _prompts(cfg, lens=(5, 19, 26))
    served = _serve(eng, prompts, new=4)
    assert eng.compile_watch.unexpected == 0
    for rid, p in enumerate(prompts):
        seq = jnp.asarray(p + served[rid])
        pos = jnp.arange(len(p) - 1, len(seq) - 1)
        logits = ref.logits_at(ref_cfg(cfg), params, params["layers"], seq,
                               pos, q_block=8)
        gap = logits.max(-1) - logits[jnp.arange(len(pos)),
                                      jnp.asarray(served[rid])]
        assert float(gap.max()) < 1e-4, (rid, gap)


def test_the_spans_say_which_latent_kernel_a_step_ran(tiny):
    """A traced window counts the chunk steps the expanded kernel ran in:
    every ``prefill`` span carries its form and blocks, every ``decode``
    span the absorbed kernel's."""
    from mpit_tpu import obs

    cfg, params = tiny
    rec = obs.Recorder()
    with obs.local_recorder(rec):
        _serve(_engine(cfg, params, mode="interpret"),
               _prompts(cfg, lens=(19,)), new=3)
    events = rec.snapshot()["events"]
    for name, want in (
            ("prefill", {"attention_form": "latent_expanded_kernel",
                         "attention_rows": 1024, "attention_query_rows": 8}),
            ("decode", {"attention_form": "latent_absorbed",
                        "attention_rows": 16})):
        spans = [e[5] for e in events if e[1] == name]
        assert spans
        for attrs in spans:
            assert want.items() <= attrs.items()
            assert ("attention_query_rows" in attrs) == (name == "prefill")


def test_compacted_chunk_tick_equals_the_full_batch_one(tiny, monkeypatch):
    """Past the row rule the chunk tick runs over its participants only,
    in steps compiled for a power-of-two count of them: same tokens, same
    pool rows as the full-batch step."""
    cfg, params = tiny
    full = _engine(cfg, params, slots=4)
    assert not full._prefill_counts  # 4 x 8 rows: the one step stays
    monkeypatch.setattr(engine_module, "_WEIGHT_BOUND_ROWS", 8)
    monkeypatch.setattr(engine_module, "_COMPACT_ROWS", 16)
    compact = _engine(cfg, params, slots=4)
    assert compact._prefill_counts == (1, 2)  # 8 and 16 rows of 16
    warm_engine(compact)
    assert compact.compile_watch.compiles == compact.compile_watch.expected
    want = _serve(full, _prompts(cfg))
    got = _serve(compact, _prompts(cfg))
    assert got == want
    assert compact.compile_watch.unexpected == 0
    # The pools agree wherever a live page was written.
    for a, b in zip(jax.tree.leaves((full.cache.k, full.cache.v)),
                    jax.tree.leaves((compact.cache.k, compact.cache.v))):
        np.testing.assert_allclose(a, b, **TOL)


def test_compacted_tick_reports_its_rows(tiny, monkeypatch):
    from mpit_tpu import obs

    cfg, params = tiny
    monkeypatch.setattr(engine_module, "_WEIGHT_BOUND_ROWS", 8)
    eng = _engine(cfg, params, slots=4)
    rec = obs.enable(obs.Recorder())
    try:
        _serve(eng, _prompts(cfg, lens=(5,)), new=2)
    finally:
        obs.disable()
    gauges = {k[0]: v for k, v in rec.gauges.items()}
    assert gauges["prefill_rows_computed"] == 8  # one slot's chunk, not four
    assert gauges["prefill_rows_valid"] == 5
    assert gauges["moe_experts_hit"] >= 1
    assert gauges["moe_load_max_over_mean"] >= 1
    assert rec.counter_total("moe_expert_tokens") > 0


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


def test_engine_takes_the_model_or_its_configuration(tiny):
    cfg, params = tiny
    eng = _engine(Xing4ServeModel(cfg), params)
    assert eng.model.family == "xing4" and eng.cfg is cfg
    # 128 + 128 values a position and layer: the latent and the padded
    # rotary part, counted with the padding.
    per_page = 16 * (cfg.kv_lora_rank + 128) * 4 * cfg.num_hidden_layers
    assert eng.page_bytes == per_page
    with pytest.raises(ValueError, match="shipped"):
        eng.export_kv_rows(0, 4)


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
    """Every layer of the family has a name on the device's clock."""
    import re

    cfg, params = tiny
    eng = _engine(cfg, params, mode="interpret")
    s = eng.slots
    i32, f32 = jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32)
    bt, key = jnp.asarray(eng.allocator.block_tables, jnp.int32), jax.random.key(0)
    scopes = ["embed", "hc_mix", "attn", "kv_write", "moe_route",
              "moe_dispatch", "moe_experts", "moe_shared", "moe_combine",
              "mlp", "lm_head", "sample"]
    if step == "decode":
        jit, args = eng._decode_paged_jit, (
            eng.params, eng.cache, eng.last_token, jnp.ones((s,), bool), bt,
            key, f32, i32)
        scopes.append("mla_absorb")
        kernels = {"mla_paged_decode_attn"}
    else:
        jit, args = eng._prefill_paged_jit, (
            eng.params, eng.cache, eng.last_token,
            jnp.zeros((s, eng.prefill_chunk), jnp.int32), i32, i32, i32,
            jnp.zeros((s,), bool), bt, key, f32, i32)
        # The chunk's kernel reads the pages in place: it and the
        # layouts round it are the scope mla_expand, and kv_gather is
        # the lax twin's alone.
        scopes.append("mla_expand")
        kernels = {"mla_paged_chunk_attn"}
    lowered = jit.lower(*args)
    text = lowered.as_text(debug_info=True)
    assert f"module @jit_{step}_paged " in text
    for scope in scopes:
        assert re.search(rf'["/(]{scope}[/)]', text), scope
    assert kernels <= _pallas_names(jax.make_jaxpr(jit)(*args).jaxpr)
    # Beside the cache and the tokens the step hands back what it counted.
    out = jax.eval_shape(jit, *args)
    assert out[2].shape == (cfg.num_moe_layers, cfg.n_routed_experts)
    assert out[2].dtype == jnp.int32
