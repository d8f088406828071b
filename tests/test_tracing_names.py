"""Every layer has a name on both clocks (ISSUE 24).

Host: a serving tick is one ``tick`` span with its phases inside it, in
the order they run, and the engine's ``*_dispatch`` / ``*_fetch`` inside
``decode`` and ``prefill``: the dispatch of this tick's step, then the
fetch of the last tick's (ISSUE 29), and a drain as a span with a fetch
alone; with no recorder installed a tick computes no span argument. Device: the jitted steps lower to text that holds each
scope name, and every ``pallas_call`` carries its ``name``.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import mpit_tpu
from mpit_tpu import obs
from mpit_tpu.models import GPT2, GPT2Config
from mpit_tpu.obs import core as obs_core
from mpit_tpu.serve import Engine, Request, Server
from mpit_tpu.serve import engine as engine_mod
from mpit_tpu.serve import scheduler as scheduler_mod

CFG = GPT2Config.tiny(
    vocab_size=64, max_seq_len=64, num_layers=2, num_heads=2, d_model=32,
    dtype=jnp.float32,
)
ENGINES = {
    # A pool for every slot, prompts whole in one chunk; a small pool of
    # small pages, prompts in chunks of 4.
    "whole-prompt": dict(slots=2, max_len=32, prefill_len=8),
    "chunked": dict(slots=2, max_len=32, kv_pages=8, kv_page_size=8,
                    prefill_chunk=4),
}


@pytest.fixture(autouse=True)
def _obs_disabled_by_default():
    # A recorder another module's test left on in this worker would make
    # every span here a real one (the idiom of test_obs / test_roofline).
    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def params():
    return GPT2(CFG).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def _server(params, kind, **kw):
    server = Server(Engine(CFG, params, **ENGINES[kind], **kw))
    server.submit(Request(rid=1, prompt=[5, 9, 3, 7, 2, 8], max_new_tokens=4))
    server.submit(Request(rid=2, prompt=[7, 4], max_new_tokens=3))
    return server


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_every_tick_is_one_span_tree(params, kind):
    rec = obs.Recorder()
    with obs.local_recorder(rec):
        server = _server(params, kind)
        server.run()
    spans = [(name, t0, t0 + dur, attrs or {})
             for k, name, t0, dur, _tid, attrs in rec.snapshot()["events"]
             if k == "X"]
    ticks = [s for s in spans if s[0] == "tick"]
    assert [s[3]["tick"] for s in ticks] == list(range(server.tick))
    # One tick of steps in flight: none as the first tick begins, some
    # as every tick after it does, none when ``run()`` returns.
    assert ticks[0][3]["in_flight"] == 0
    assert all(t[3]["in_flight"] >= 1 for t in ticks[1:])
    assert not server._in_flight
    phases = ("admit", "prefill", "gauges", "decode", "retire")
    decodes = drains = 0
    fetched = {"prefill": 0, "decode": 0}
    dispatched = {"prefill": 0, "decode": 0}
    for tick in ticks:
        kids = sorted((s for s in spans if s[0] in phases and _inside(s, tick)),
                      key=lambda s: s[1])
        # Siblings, one after another.
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        # The phases in the order they run; then, where nothing is left
        # to enqueue, the drain: spans with a fetch alone, oldest first.
        first_drain = next((i for i, s in enumerate(kids)
                            if s[3].get("drained")), len(kids))
        names = [s[0] for s in kids[:first_drain]]
        assert names.count("admit") == names.count("gauges") == 1
        assert names == [p for p in phases if p in names]
        tail = [s for s in kids[first_drain:] if s[0] != "retire"]
        assert all(s[3].get("drained") for s in tail)
        drains += len(tail)
        for outer in ("decode", "prefill"):
            for span in (s for s in kids if s[0] == outer):
                inner = [s[0] for s in sorted(
                    (s for s in spans if _inside(s, span)
                     and s[0] in (outer + "_dispatch", outer + "_fetch")),
                    key=lambda s: s[1])]
                # Enqueue this tick's step, then fetch the last tick's.
                assert inner in (
                    [outer + "_dispatch", outer + "_fetch"],
                    [outer + "_dispatch"], [outer + "_fetch"]), inner
                if span[3].get("drained"):
                    assert inner == [outer + "_fetch"]
                dispatched[outer] += inner.count(outer + "_dispatch")
                fetched[outer] += inner.count(outer + "_fetch")
        for d in (s for s in kids[:first_drain] if s[0] == "decode"):
            decodes += 1
            # ``active`` and ``cache_rows`` describe the step enqueued,
            # ``rids`` the requests whose tokens the span's end brings.
            assert d[3]["cache_rows"] >= d[3]["active"] >= 0
            assert "retire" in names
    assert decodes >= 2 and drains >= 1
    # Every step enqueued is fetched, once (a chunk in which no slot
    # finished its prompt too: the wait holds the host a tick ahead).
    assert fetched["decode"] == dispatched["decode"] >= 2
    assert fetched["prefill"] == dispatched["prefill"] >= 1
    # The first decode step has nothing older to fetch; every later one
    # brings the tokens of the requests the step before it served.
    plain = [s for s in spans if s[0] == "decode" and not s[3].get("drained")]
    assert plain[0][3]["rids"] == [] and plain[0][3]["active"] >= 1
    assert all(len(b[3]["rids"]) == a[3]["active"]
               for a, b in zip(plain, plain[1:]))
    # The two counters of the overlap: how often it engaged, how often not.
    steps = dispatched["decode"] + dispatched["prefill"]
    overlapped = rec.counter_total("serve_steps_overlapped")
    drained = rec.counter_total("serve_steps_drained")
    assert overlapped == server.steps_overlapped >= 1
    assert drained == server.steps_drained >= 1
    assert overlapped < steps and drained < steps
    assert server.stats()["steps_overlapped"] == server.steps_overlapped


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_disabled_tick_computes_no_span_argument(params, kind, monkeypatch):
    assert not obs.enabled()
    asked = []

    def spy(name, **attrs):
        asked.append((name, attrs, obs_core.span(name, **attrs)))
        return asked[-1][2]

    server = _server(params, kind)
    monkeypatch.setattr(scheduler_mod.obs, "span", spy)
    assert engine_mod.obs is scheduler_mod.obs
    server.run()
    names = {name for name, _, _ in asked}
    assert {"tick", "admit", "prefill", "gauges", "decode", "retire",
            "decode_dispatch", "decode_fetch", "prefill_dispatch",
            "prefill_fetch"} <= names
    noop = obs_core.span("x")
    for name, attrs, got in asked:
        assert got is noop, name
        assert "rids" not in attrs and "cache_rows" not in attrs, name
    assert all(not attrs for name, attrs, _ in asked if name != "tick")


def _lowered_serve_step(params, step):
    eng = Engine(CFG, params, **ENGINES["chunked"],
                 decode_attention="interpret", sample_block=32,
                 sample_k_cap=16)
    s = eng.slots
    i32, mask = jnp.zeros((s,), jnp.int32), jnp.ones((s,), bool)
    tail = [jnp.asarray(eng.allocator.block_tables, jnp.int32),
            jax.random.key(0), jnp.zeros((s,), jnp.float32), i32]
    head = [eng.params, eng.cache, eng.last_token]
    if step == "decode":
        return eng._decode_paged_jit, head + [mask] + tail
    toks = jnp.zeros((s, eng.prefill_chunk), jnp.int32)
    return eng._prefill_paged_jit, head + [toks, i32, i32 + 2, i32, mask] + tail


def _lowered_train():
    from mpit_tpu.opt import goo_adam
    from mpit_tpu.train import make_train_step

    cfg = GPT2Config.tiny(
        vocab_size=64, max_seq_len=16, num_layers=1, num_heads=2, d_model=32,
        dtype=jnp.float32,
    )
    model = GPT2(cfg)
    prm = model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32))["params"]
    world = mpit_tpu.init({"data": -1})
    loss = lambda p, batch: (GPT2.fused_loss_fn(model, p, batch), {})
    init_fn, step_fn, _ = make_train_step(loss, goo_adam(1e-3), world)
    state = init_fn(prm)
    batch = jnp.zeros((world.axis_size("data") * 2, 17), jnp.int32)
    return step_fn.build(state.params, state.extra), [state, batch]


STEPS = {
    "train": (_lowered_train, "jit_train_step",
              ("loss", "grad_sync", "opt_update", "zero1_gather", "attn",
               "mlp", "lm_head", "embed"), (), ()),
    # The page pool is stored as the kernel reads it: nothing is left to
    # lower under kv_gather (an int8 pool still pads its scale plane there).
    "decode_paged": (lambda p: _lowered_serve_step(p, "decode"),
                     "jit_decode_paged",
                     ("kv_write", "attn", "mlp", "lm_head", "sample",
                      "embed"), ("paged_decode_attn",), ("kv_gather",)),
    "prefill_paged": (lambda p: _lowered_serve_step(p, "prefill"),
                      "jit_prefill_paged",
                      ("kv_write", "attn", "mlp", "lm_head", "sample",
                       "embed"), ("paged_decode_attn",), ("kv_gather",)),
}


def _pallas_names(jaxpr) -> set:
    """The ``name`` of every ``pallas_call`` a jaxpr reaches."""
    found = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.add(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found |= _pallas_names(sub)
    return found


@pytest.mark.parametrize("step", sorted(STEPS))
def test_a_step_lowers_with_its_scope_and_kernel_names(params, step):
    build, module, scopes, kernels, absent = STEPS[step]
    jit, args = build() if step == "train" else build(params)
    text = jit.lower(*args).as_text(debug_info=True)
    assert f"module @{module} " in text
    for scope in scopes:
        # A location is a name stack: "jit(decode)/GPT2/block_0/attn/..."
        # or, inside a nested jit, one that starts with the scope.
        assert re.search(rf'["/(]{scope}[/)]', text), scope
    for scope in absent:
        assert not re.search(rf'["/(]{scope}[/)]', text), scope
    if step == "train":
        assert "transpose(jvp(loss))" in text
    assert set(kernels) <= _pallas_names(jax.make_jaxpr(jit)(*args).jaxpr)


def _flash_jaxpr():
    # Outside shard_map: the kernel's interpreter does not type-check
    # under one, and the step above needs no kernel to show its scopes.
    from mpit_tpu.ops import flash_attention

    q = jnp.ones((1, 16, 2, 16), jnp.float32)
    loss = lambda q, k, v: flash_attention(q, k, v, interpret=True).sum()
    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)


def _qmm_jaxpr():
    from mpit_tpu.ops.quantized_matmul import quantize_tensor, quantized_matmul

    w = quantize_tensor(jnp.ones((256, 128), jnp.float32))
    return jax.make_jaxpr(functools.partial(
        quantized_matmul, block_rows=128, interpret=True))(
            jnp.ones((8, 256), jnp.float32), w)


def _ring_jaxpr():
    from mpit_tpu.ops.ring_collectives import ring_reduce_scatter

    world = mpit_tpu.init(
        {"data": 4}, devices=jax.devices()[:4], set_default=False)
    f = world.shard_map(
        lambda x: ring_reduce_scatter(x, "data", interpret=True),
        in_specs=P("data"), out_specs=P("data"))
    return jax.make_jaxpr(f)(jnp.ones((4 * 4 * 32 * 128,), jnp.float32))


def _ship_jaxpr(monkeypatch):
    from mpit_tpu.serve.shipment import ship_kv_remote

    # The refusal off-TPU is the product's; tracing needs no device.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return jax.make_jaxpr(functools.partial(ship_kv_remote, dst_device=0))(
        jnp.ones((8, 128), jnp.float32))


def _gdn_jaxpr():
    from mpit_tpu.ops import gated_delta as gd

    q = jnp.ones((1, 8, 3, 12), jnp.float32)
    v, g = jnp.ones((1, 8, 3, 24), jnp.float32), jnp.zeros((1, 8, 3))
    s = jnp.zeros((1, 3, 12, 24), jnp.float32)

    def both(q, v, g, s):
        o, s = gd.gdn_chunk(q, q, v, g, g, s, interpret=True)
        return gd.gdn_step(q[:, 0], q[:, 0], v[:, 0], g[:, 0], g[:, 0], s,
                           interpret=True)

    return jax.make_jaxpr(both)(q, v, g, s)


KERNELS = {
    "gated_delta": (_gdn_jaxpr, {"gdn_chunk", "gdn_step"}),
    "flash": (_flash_jaxpr, {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    "quantized_matmul": (_qmm_jaxpr, {"quantized_matmul"}),
    "ring_step": (_ring_jaxpr, {"ring_step"}),
    "kv_ship": (_ship_jaxpr, {"kv_ship"}),
}


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_the_other_kernels_carry_their_names(kernel, monkeypatch):
    build, names = KERNELS[kernel]
    jaxpr = build(monkeypatch) if kernel == "kv_ship" else build()
    assert names <= _pallas_names(jaxpr.jaxpr)


def test_a_state_keeping_family_names_its_layers_and_its_passed_up_hits():
    """The third family's steps lower with the scopes its per-layer
    metrics read (``benchmark/families/olmo_hybrid/scopes.json`` lists the
    same names), and a prefix hit it cannot use is a counter of that
    name on the host's clock."""
    import json
    import os

    from mpit_tpu.models.olmo_hybrid import OlmoHybridConfig, init_params

    cfg = OlmoHybridConfig.tiny(num_hidden_layers=4, max_seq_len=64)
    engine = Engine(cfg, init_params(cfg, jax.random.key(0)), slots=2,
                    max_len=64, kv_page_size=16, prefill_chunk=16)
    s = engine.slots
    i32, f32 = jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32)
    bt = jnp.asarray(engine.allocator.block_tables, jnp.int32)
    key = jax.random.key(0)
    texts = [
        engine._decode_paged_jit.lower(
            engine.params, engine.cache, engine.last_token,
            jnp.ones((s,), bool), bt, key, f32, i32
        ).as_text(debug_info=True),
        engine._prefill_paged_jit.lower(
            engine.params, engine.cache, engine.last_token,
            jnp.zeros((s, 16), jnp.int32), i32, i32, i32,
            jnp.zeros((s,), bool), bt, key, f32, i32
        ).as_text(debug_info=True),
    ]
    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "families",
            "olmo_hybrid", "scopes.json")) as f:
        stated = json.load(f)["scopes"]
    assert set(stated) == {"linear_attn", "gdn_conv", "gdn_chunk", "gdn_step",
                           "state_pool_move"}
    for scope in stated:
        assert any(re.search(rf'["/(]{scope}[/)]', t) for t in texts), scope
    rec = obs.Recorder()
    with obs.local_recorder(rec):
        server = Server(engine)
        for rid in (1, 2):
            server.submit(Request(rid=rid, prompt=[5, 9, 3, 7, 2, 8, 1, 4, 6],
                                  max_new_tokens=12 if rid == 1 else 2))
            server.run(max_ticks=server.tick + 3)
        server.run()
    assert rec.counter_total("prefix_hits_passed_up") == 1


def test_a_choosing_family_names_its_layers_and_counts_what_it_reads():
    """The fourth family's steps lower with the scopes its per-layer
    metrics read (``benchmark/families/glm_dsa/scopes.json`` lists the
    same names) and its index-scores kernel under its two names; its
    ``decode`` and ``prefill`` spans carry the rows attention found
    cached and those it read, and the counters of what the steps counted
    on the device land on the ``decode`` span."""
    import json
    import os

    from mpit_tpu.models.glm_dsa import GlmDsaConfig, init_params

    cfg = GlmDsaConfig.tiny(max_seq_len=64)
    engine = Engine(cfg, init_params(cfg, jax.random.key(0)), slots=2,
                    max_len=64, kv_page_size=16, prefill_chunk=16,
                    decode_attention="interpret")
    s = engine.slots
    i32, f32 = jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.float32)
    bt = jnp.asarray(engine.allocator.block_tables, jnp.int32)
    key = jax.random.key(0)
    decode = (engine._decode_paged_jit, (
        engine.params, engine.cache, engine.last_token,
        jnp.ones((s,), bool), bt, key, f32, i32))
    prefill = (engine._prefill_paged_jit, (
        engine.params, engine.cache, engine.last_token,
        jnp.zeros((s, 16), jnp.int32), i32, i32, i32,
        jnp.zeros((s,), bool), bt, key, f32, i32))
    texts = [jit.lower(*args).as_text(debug_info=True)
             for jit, args in (decode, prefill)]
    with open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "families",
            "glm_dsa", "scopes.json")) as f:
        stated = json.load(f)["scopes"]
    assert {"dsa_index", "dsa_select", "dsa_sparse_attn"} <= set(stated)
    for scope in stated:
        assert any(re.search(rf'["/(]{scope}[/)]', t) for t in texts), scope
    assert "dsa_index_scores_tick" in _pallas_names(
        jax.make_jaxpr(decode[0])(*decode[1]).jaxpr)
    assert "dsa_index_scores_chunk" in _pallas_names(
        jax.make_jaxpr(prefill[0])(*prefill[1]).jaxpr)
    rec = obs.Recorder()
    with obs.local_recorder(rec):
        server = Server(engine)
        server.submit(Request(rid=1, prompt=list(range(1, 20)),
                              max_new_tokens=6))
        server.run()
    spans = {n: [e[5] for e in rec.snapshot()["events"] if e[1] == n]
             for n in ("decode", "prefill")}
    ticks = [a for a in spans["decode"] if a.get("active")]
    # 19 rows and the new one cached, the 8 chosen read, and so on up.
    assert [(a["rows_cached"], a["rows_read"]) for a in ticks] == [
        (20 + i, 8) for i in range(len(ticks))]
    chunks = [a for a in spans["prefill"] if a.get("chunks")]
    assert [a["rows_cached"] for a in chunks] == [
        sum(range(1, 17)), sum(range(17, 20))]
    assert [a["rows_read"] for a in chunks] == [
        sum(min(r, 8) for r in range(1, 17)), 8 * 3]
    landed = [a for a in spans["decode"] if "dsa_rows_read" in a]
    assert landed and all(
        a["dsa_rows_read"] == 8 * cfg.num_hidden_layers for a in landed)
    for name in ("dsa_rows_read", "dsa_rows_cached", "moe_choices",
                 "moe_choices_here"):
        assert rec.counter_total(name) > 0
