"""A chunk tick costs what its participants cost (ISSUE 35).

Which step a chunk tick takes follows from the shape, for GPT-2 as for
the other families: ``serve.engine._chunk_step_counts`` gives the counts
of participants a step is compiled for, or none where the one full-batch
step stays. Pinned here, on a tiny GPT-2 on the CPU:

- the rule itself, as a table over the benchmark's four serving shapes,
  a rehearsal engine and the rule's edges, and a speculative and a
  tensor-parallel engine;
- the compacted tick serves the full-batch tick's tokens and leaves the
  same pool rows, for one, two and all slots taking part and for a group
  larger than the largest compiled count;
- ``warm_engine`` pays every compile and a served backlog adds none;
- the host's side: a group's small arguments reach the device in one
  transfer, and nothing the scheduler writes to again reaches a step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpit_tpu
from mpit_tpu.models import GPT2, GPT2Config
from mpit_tpu.serve import Engine, Request, Server, warm_engine
from mpit_tpu.serve import engine as engine_module

CFG = GPT2Config.tiny(
    vocab_size=64, max_seq_len=64, num_layers=2, num_heads=2, d_model=32,
    dtype=jnp.float32,
)
SLOTS, CHUNK = 4, 8
PROMPTS = (5, 19, 26, 11)  # tokens: one to four chunks of 8


@pytest.fixture(scope="module")
def params():
    return jax.jit(GPT2(CFG).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def _engine(params, **kw):
    kw = dict(slots=SLOTS, max_len=64, kv_page_size=8, prefill_chunk=CHUNK,
              decode_attention="reference") | kw
    return Engine(CFG, params, **kw)


def _steer(monkeypatch, bound, most):
    """The rule's two constants, as a test steers them: never an option."""
    monkeypatch.setattr(engine_module, "_WEIGHT_BOUND_ROWS", bound)
    monkeypatch.setattr(engine_module, "_COMPACT_ROWS", most)


def _requests(n, new=4):
    rng = np.random.RandomState(7)
    return [
        Request(rid=i, prompt=rng.randint(1, CFG.vocab_size, size=t).tolist(),
                max_new_tokens=new)
        for i, t in enumerate(PROMPTS[:n])
    ]


def _serve(eng, requests):
    server = Server(eng)
    for r in requests:
        server.submit(r)
    return {c.rid: list(c.tokens) for c in server.run()}


@pytest.mark.parametrize("slots,chunk,want", [
    (16, 64, (2,)),  # gpt2-large: two slots' 128 rows ride one weight read
    (32, 2048, (1,)),  # xing4: a chunk is _COMPACT_ROWS already
    (64, 512, (1, 2, 4)),  # olmo-hybrid: a slot's chunk is past the knee
    (16, 512, (1, 2, 4)),  # glm-5.2
    (4, 8, ()),  # a rehearsal: 32 rows, no step over fewer is faster
    (16, 8, ()),  # the first step of 128 rows is the whole batch
    (32, 8, (16,)),  # nothing under the knee is compiled, nothing over it
    (12, 64, (2,)),  # slots need be no power of two
    (3, 512, (1, 2)),  # doublings stop at the slots
    (2, 4096, (1,)),  # a chunk wider than a step is still one
    (1, 512, ()),  # one slot is the whole batch
], ids=["gpt2l", "xing4", "olmoh", "glm52", "rehearsal", "whole-batch",
        "narrow-chunk", "twelve-slots", "three-slots", "wide-chunk",
        "one-slot"])
def test_counts_follow_from_the_shape(slots, chunk, want):
    assert engine_module._chunk_step_counts(slots, chunk) == want


@pytest.mark.parametrize("kind", ["plain", "speculative", "tensor-parallel"])
def test_who_takes_the_compacted_step(params, kind):
    """Four slots of 64-row chunks: the rule gives (2,), so the engine's
    steps are three as before; a speculative or tensor-parallel engine
    keeps the full-batch step."""
    kw = dict(prefill_chunk=64)
    if kind == "speculative":
        kw.update(spec_k=2, draft_params=params, draft_cfg=CFG)
    if kind == "tensor-parallel":
        world = mpit_tpu.init({"data": 4, "model": 2}, set_default=False)
        kw.update(world=world, tp_axis="model")
    eng = _engine(params, **kw)
    assert eng._prefill_counts == ((2,) if kind == "plain" else ())
    assert eng.compile_watch.expected == 3 + (kind == "speculative")


@pytest.mark.parametrize("taking_part,bound,most,counts", [
    (1, 8, 32, (1, 2, 4)), (2, 8, 32, (1, 2, 4)), (4, 8, 32, (1, 2, 4)),
    (3, 8, 16, (1, 2)), (4, 8, 16, (1, 2)),
    (1, 16, 32, (2,)), (3, 16, 32, (2,)), (4, 16, 32, (2,)),
], ids=["one", "two", "all-slots", "three-over-counts-of-two",
        "four-over-counts-of-two", "one-padded-to-the-only-count",
        "three-over-the-only-count", "four-over-the-only-count"])
def test_compacted_tick_equals_the_full_batch_one(
        params, monkeypatch, taking_part, bound, most, counts):
    """Same tokens, exactly, and the same pool rows: the rows left out
    were computed and dropped."""
    full = _engine(params)
    assert not full._prefill_counts  # 4 x 8 rows: the one step stays
    _steer(monkeypatch, bound, most)
    compact = _engine(params)
    assert compact._prefill_counts == counts
    want = _serve(full, _requests(taking_part))
    got = _serve(compact, _requests(taking_part))
    assert got == want
    assert len(got) == taking_part
    for a, b in zip(jax.tree.leaves((full.cache.k, full.cache.v)),
                    jax.tree.leaves((compact.cache.k, compact.cache.v))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(full.lengths(), compact.lengths())


def test_warm_engine_pays_every_count_and_a_backlog_adds_none(
        params, monkeypatch):
    _steer(monkeypatch, CHUNK, 32)
    eng = _engine(params)
    warm_engine(eng)
    assert eng.compile_watch.expected == 3 + 2  # counts (1, 2, 4)
    assert eng.compile_watch.compiles == eng.compile_watch.expected
    rows, steps = eng._chunk_rows_jit, eng._prefill_compact_jit
    sizes = rows._cache_size(), steps._cache_size()
    assert sizes == (3, 3)
    served = _serve(eng, _requests(4) + [
        Request(rid=9, prompt=[3, 1, 4, 1, 5, 9, 2, 6, 5], max_new_tokens=3)])
    assert len(served) == 5
    assert eng.compile_watch.unexpected == 0
    assert eng.compile_watch.compiles == eng.compile_watch.expected
    assert (rows._cache_size(), steps._cache_size()) == sizes


def _chunk_arguments(eng, taking_part):
    """A first chunk for slots ``0..taking_part-1`` as the scheduler
    stages it: host arrays it goes on writing to."""
    rng = np.random.RandomState(3)
    tokens = np.zeros((SLOTS, CHUNK), np.int32)
    lens = np.zeros((SLOTS,), np.int32)
    for slot in range(taking_part):
        assert eng.allocator.admit(slot, list(range(CHUNK)), CHUNK + 4)
        lens[slot] = CHUNK - slot
        tokens[slot, : lens[slot]] = rng.randint(1, 64, size=lens[slot])
    zeros = np.zeros((SLOTS,), np.int32)
    return [tokens, zeros.copy(), lens, zeros.copy(), lens > 0,
            np.zeros((SLOTS,), np.float32), zeros.copy()]


def test_a_group_reaches_the_device_in_one_transfer(params, monkeypatch):
    """Three slots over counts of (1, 2): two groups, so two calls of
    ``chunk_rows`` with one host vector each, and no other transfer
    (``temp`` and ``topk`` are ``_stage``'s, unchanged since the warm
    run; the tables ride in the vector)."""
    _steer(monkeypatch, CHUNK, 16)
    eng = _engine(params)
    warm_engine(eng)
    args = _chunk_arguments(eng, 3)
    eng._stage("temp", args[5], np.float32)
    eng._stage("topk", args[6], np.int32)
    moved, real = [], eng._chunk_rows_jit

    def chunk_rows(packed):
        moved.append(packed)
        return real(packed)

    def no_transfer(*a, **kw):
        raise AssertionError("a transfer beside the group's vector")

    monkeypatch.setattr(eng, "_chunk_rows_jit", chunk_rows)
    monkeypatch.setattr(engine_module.jnp, "asarray", no_transfer)
    monkeypatch.setattr(engine_module.jax, "device_put", no_transfer)
    step = eng.prefill_dispatch(*args)
    monkeypatch.undo()
    assert [type(p) for p in moved] == [np.ndarray, np.ndarray]
    tables = eng.allocator.block_tables.size
    assert [p.size for p in moved] == [
        2 * (CHUNK + 5) + tables, 1 * (CHUNK + 5) + tables]
    assert step[2:] == (3 * CHUNK, int(args[2].sum()))  # computed, valid
    # The decode step behind finds the tables staged: the vector's own.
    held = eng._staged["block_tables"]
    assert np.shares_memory(held[0], moved[-1])
    assert eng._stage(
        "block_tables", eng.allocator.block_tables, np.int32) is held[1]
    eng.prefill_fetch(step)


def test_no_step_reads_what_the_scheduler_writes_to_again(
        params, monkeypatch):
    """On the CPU a transfer may alias host memory: the scheduler's
    arrays are overwritten as soon as the chunk is enqueued, and the
    step must have read what they held."""
    _steer(monkeypatch, CHUNK, 32)
    outcomes = []
    for scribble in (False, True):
        eng = _engine(params)
        warm_engine(eng)
        args = _chunk_arguments(eng, 2)
        step = eng.prefill_dispatch(*args)
        if scribble:
            for a in args[:5]:
                a[...] = 1
            eng.allocator.block_tables[...] = 0
        outcomes.append((
            eng.prefill_fetch(step),
            eng.lengths(),
            [np.asarray(leaf) for leaf in jax.tree.leaves(eng.cache.k)],
        ))
    (toks, lens, pool), (toks2, lens2, pool2) = outcomes
    np.testing.assert_array_equal(toks, toks2)
    np.testing.assert_array_equal(lens, lens2)
    assert lens[:2].tolist() == [CHUNK, CHUNK - 1]
    for a, b in zip(pool, pool2):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("step", ["compacted", "full-batch"])
def test_the_dispatch_span_says_what_its_rows_were(params, monkeypatch, step):
    """``prefill_dispatch`` carries the rows its steps compute and those
    of them that are no prompt tokens (the benchmark's
    ``prefill_row_waste_pct.decode`` / ``.steady`` read the two), and the
    gauges of ``prefill_fetch`` agree."""
    from mpit_tpu import obs

    if step == "compacted":
        _steer(monkeypatch, CHUNK, 32)
    eng = _engine(params)
    rec = obs.enable(obs.Recorder())
    try:
        _serve(eng, _requests(1, new=2))  # five tokens: one chunk
    finally:
        obs.disable()
    spans = [e[5] for e in rec.snapshot()["events"]
             if e[1] == "prefill_dispatch"]
    assert len(spans) == 1
    computed = CHUNK * (1 if step == "compacted" else SLOTS)
    assert spans[0] == dict(
        rows_computed=computed, rows_wasted=computed - PROMPTS[0])
    gauges = {k[0]: v for k, v in rec.gauges.items()}
    assert gauges["prefill_rows_computed"] == computed
    assert gauges["prefill_rows_valid"] == PROMPTS[0]
