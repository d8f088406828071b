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
  transfer, and nothing the scheduler writes to again reaches a step;
- seat chaining (ISSUE 40): a seat of the compiled step that no slot took
  goes to the next chunk of a prompt that is there. Same tokens, lengths
  and pool as a chunk a tick; slots first, then the longest prompt; never
  across a boundary inside a page, never where the smallest step is one
  seat or a slot keeps a state; no compile.
"""

from __future__ import annotations

import functools

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
    if not compact.spare_seats(1):
        # (A retired slot's length is what the last step left of it: the
        # same only where both engines' ticks were the same ticks, and a
        # prompt that takes a spare seat finishes in fewer.)
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
    # computed, valid, chained
    assert step[2] == (3 * CHUNK, int(args[2].sum()), 0)
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
        rows_computed=computed, rows_wasted=computed - PROMPTS[0],
        rows_chained=0)
    gauges = {k[0]: v for k, v in rec.gauges.items()}
    assert gauges["prefill_rows_computed"] == computed
    assert gauges["prefill_rows_valid"] == PROMPTS[0]


# -- seat chaining (ISSUE 40) ---------------------------------------------------

# Four slots of 64-row chunks: the rule gives (2,), as for GPT-2 large's
# cell. Rows of 128 lanes, so that the pool's page writer takes a chunk.
WIDE = GPT2Config.tiny(
    vocab_size=64, max_seq_len=512, num_layers=2, num_heads=2, d_model=128,
    dtype=jnp.float32,
)
LONG = dict(slots=4, max_len=512, kv_page_size=16, prefill_chunk=64)


@pytest.fixture(scope="module")
def wide_params():
    return jax.jit(GPT2(WIDE).init)(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]


@pytest.fixture(scope="module", params=["reference", "interpret"])
def pair(request, wide_params):
    """Two warm engines of one build: one that gives its spare seats
    away and one steered to give none (a chunk a slot a tick, as before
    the rule). ``interpret``: attention through the Pallas interpreter
    and a chunk's rows through the pool's page writer, as on the chip."""
    from mpit_tpu.models import gpt2
    from mpit_tpu.ops import decode_attention

    with pytest.MonkeyPatch.context() as mp:
        if request.param == "interpret":
            mp.setattr(decode_attention, "_use_kernel", lambda _: True)
            mp.setattr(gpt2, "paged_write_pages", functools.partial(
                decode_attention.paged_write_pages, interpret=True))
        chained, plain = (
            Engine(WIDE, wide_params, decode_attention=request.param, **LONG)
            for _ in range(2))
        assert chained._prefill_counts == (2,)
        warm_engine(chained)  # traced under the steering
        warm_engine(plain)
    plain.spare_seats = lambda takers: 0
    return chained, plain


def _prompt(tokens, seed=0):
    return np.random.RandomState(1000 * seed + tokens).randint(
        1, WIDE.vocab_size, size=tokens).tolist()


def _pool(eng):
    return [np.asarray(leaf)
            for leaf in jax.tree.leaves((eng.cache.k, eng.cache.v))]


def _dispatch_spans(rec):
    return [e[5] for e in rec.snapshot()["events"]
            if e[1] == "prefill_dispatch"]


@pytest.mark.parametrize("tokens", [1, 64, 65, 128, 129, 463])
def test_a_lone_prompt_chained_equals_a_chunk_a_tick(pair, tokens):
    """Every prompt token is computed once, by the same step, at the same
    position, against the same cached rows: only the tick changes."""
    runs = []
    for eng in pair:
        eng.reset()
        server = Server(eng)
        server.submit(Request(
            rid=0, prompt=_prompt(tokens), max_new_tokens=3))
        (done,) = server.run()
        runs.append((list(done.tokens), eng.lengths(), _pool(eng),
                     server.tick))
    (toks, lens, pool, ticks), (toks2, lens2, pool2, ticks2) = runs
    assert toks == toks2
    np.testing.assert_array_equal(lens, lens2)
    assert int(lens.max()) == tokens + 3 - 1
    for a, b in zip(pool, pool2):
        np.testing.assert_array_equal(a, b)
    # Two seats a tick: half the chunk ticks, rounded up.
    assert ticks2 - ticks == -(-tokens // 64) - -(-tokens // 128)


def test_a_chained_tick_meets_no_compile(pair):
    """``warm_engine`` compiled the step of two seats with padding alone;
    the first tick that fills both with one slot's chunks is that step."""
    chained, _ = pair
    chained.reset()
    watch = chained.compile_watch
    assert watch.compiles == watch.expected == 3
    sizes = (chained._chunk_rows_jit._cache_size(),
             chained._prefill_compact_jit._cache_size())
    assert sizes == (1, 1)
    server = Server(chained)
    server.submit(Request(rid=0, prompt=_prompt(129), max_new_tokens=2))
    server.run()
    assert watch.unexpected == 0 and watch.compiles == watch.expected
    assert (chained._chunk_rows_jit._cache_size(),
            chained._prefill_compact_jit._cache_size()) == sizes


def _staged_groups(eng, monkeypatch):
    """Every compacted step's ``(compiled count, slots, bases)`` as
    ``_stage_chunk_rows`` is asked for them."""
    calls, real = [], eng._stage_chunk_rows

    def spy(n, slots, group, tokens, base, *rest):
        calls.append((n, [int(x) for x in slots],
                      [int(base[g]) for g in group]))
        return real(n, slots, group, tokens, base, *rest)

    monkeypatch.setattr(eng, "_stage_chunk_rows", spy)
    return calls


@pytest.mark.parametrize("prompts,first_tick", [
    ((200, 300), [(2, "ab", [0, 0])]),
    ((100, 150, 400), [(2, "ab", [0, 0]), (2, "cc", [0, 64])]),
    ((400, 150, 100), [(2, "ab", [0, 0]), (2, "ca", [0, 64])]),
], ids=["two-slots-a-seat-each", "three-slots-the-third-chains",
        "three-slots-the-longest-chains"])
def test_seats_go_to_slots_first_then_to_the_longest_prompt(
        pair, monkeypatch, prompts, first_tick):
    """Every prefilling slot takes a seat; what the last step's compiled
    count leaves goes to the slot with the most prompt left, whichever
    step its first seat is in (a later step runs behind an earlier one)."""
    from mpit_tpu import obs

    chained, _ = pair
    chained.reset()
    calls = _staged_groups(chained, monkeypatch)
    server = Server(chained)
    for i, t in enumerate(prompts):
        server.submit(Request(
            rid="abc"[i], prompt=_prompt(t, seed=i), max_new_tokens=2))
    server.run(max_ticks=1)
    slot_of = {live.req.rid: slot for slot, live in server.prefilling.items()}
    assert calls == [
        (n, [slot_of[r] for r in rids], bases)
        for n, rids, bases in first_tick]
    rec = obs.enable(obs.Recorder())
    try:
        done = {c.rid: list(c.tokens) for c in server.run()}
    finally:
        obs.disable()
    assert len(done) == len(prompts)
    chained_rows = sum(a["rows_chained"] for a in _dispatch_spans(rec))
    if len(prompts) == 2:
        # 200 and 300 tokens: both prefill for four ticks, then the
        # second has 44 tokens left, one seat's worth.
        assert chained_rows == 0
    else:
        assert chained_rows > 0


def test_a_seat_that_would_start_inside_a_page_is_not_chained(
        pair, monkeypatch):
    """A partial-page prefix hit feeds from position 70: every seat
    after it would start inside a page, where the page writer's
    whole-page writes of two seats would meet. Such a slot takes one seat
    a tick, and the pool is the unchained run's."""
    runs = []
    head = _prompt(70, seed=5)
    for eng in pair:
        eng.reset()
        server = Server(eng)
        server.submit(Request(rid="a", prompt=head, max_new_tokens=40))
        server.run(max_ticks=4)  # "a" is live and its prompt registered
        calls = _staged_groups(eng, monkeypatch)
        server.submit(Request(
            rid="b", prompt=head + _prompt(200, seed=6), max_new_tokens=3))
        done = {c.rid: list(c.tokens) for c in server.run()}
        monkeypatch.undo()
        assert eng.allocator.prefix_hits == 1
        assert eng.allocator.shared_tokens_total == 70
        # 200 tokens from position 70: four seats, a tick each.
        assert [bases for _, _, bases in calls] == [[70], [134], [198], [262]]
        runs.append((done, _pool(eng)))
    (done, pool), (done2, pool2) = runs
    assert done == done2
    for a, b in zip(pool, pool2):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("why", ["one-seat-steps", "state-beside-pages"])
def test_who_never_chains(wide_params, monkeypatch, why):
    """A chunk at the knee or past it compiles a step of one seat first,
    so no tick has a seat to spare; a model that keeps a state a slot
    cannot take two chunks in one step whatever its counts."""
    from mpit_tpu import obs

    if why == "one-seat-steps":
        eng = Engine(WIDE, wide_params, decode_attention="reference",
                     **(LONG | dict(prefill_chunk=128)))
        assert eng._prefill_counts == (1, 2, 4)
        prompt = _prompt(300)
    else:
        from mpit_tpu.models.olmo_hybrid import OlmoHybridConfig, init_params

        cfg = OlmoHybridConfig.tiny()
        _steer(monkeypatch, 32, 2048)
        eng = Engine(cfg, init_params(cfg, jax.random.key(3)), slots=3,
                     max_len=128, kv_page_size=16, prefill_chunk=16,
                     decode_attention="reference")
        assert eng._prefill_counts == (2,)  # two seats, one always empty
        assert not eng.model.keeps_pages_alone
        prompt = np.random.RandomState(2).randint(
            1, cfg.vocab_size, size=70).tolist()
    assert [eng.spare_seats(n) for n in (1, 2, 3)] == [0, 0, 0]
    rec = obs.enable(obs.Recorder())
    try:
        server = Server(eng)
        server.submit(Request(rid=0, prompt=prompt, max_new_tokens=2))
        server.run()
    finally:
        obs.disable()
    spans = _dispatch_spans(rec)
    assert len(spans) == -(-len(prompt) // eng.prefill_chunk)
    assert all(a["rows_chained"] == 0 for a in spans)
    assert {k[0]: v for k, v in rec.gauges.items()}[
        "prefill_rows_chained"] == 0


def test_a_prompt_that_ends_in_a_chained_seat(pair, monkeypatch):
    """100 tokens: one tick, seats of 64 and 36. The seat that holds the
    final prompt token samples and finishes the slot, once; the slot's
    progress and its ledger event are the sum of its seats; the span and
    the gauges say which rows rode the spare seat."""
    from mpit_tpu import obs
    from mpit_tpu.obs.trace import Ledger

    chained, _ = pair
    chained.reset()
    staged, real = [], chained.prefill_dispatch

    def spy(tokens, base, chunk_lens, floor, sample_mask, temp, topk, seats):
        staged.append([a.tolist() for a in
                       (seats, base, chunk_lens, floor, sample_mask)])
        return real(tokens, base, chunk_lens, floor, sample_mask, temp,
                    topk, seats)

    monkeypatch.setattr(chained, "prefill_dispatch", spy)
    led = Ledger(mode="full", exemplar_k=4)
    server = Server(chained, ledger=led)
    rec = obs.enable(obs.Recorder())
    try:
        server.submit(Request(rid="r", prompt=_prompt(100), max_new_tokens=2))
        (done,) = server.run()
    finally:
        obs.disable()
    slot = staged[0][0][0]
    assert staged == [[[slot, slot], [0, 64], [64, 36], [0, 0],
                       [False, True]]]
    assert len(done.tokens) == 2
    (ex,) = led.exemplars()
    chunks = [e[2] for e in ex["events"] if e[0] == "prefill_chunk"]
    assert [c["chunk"] for c in chunks] == [100]
    assert _dispatch_spans(rec) == [dict(
        rows_computed=128, rows_wasted=28, rows_chained=36)]
    gauges = {k[0]: v for k, v in rec.gauges.items()}
    assert (gauges["prefill_rows_computed"], gauges["prefill_rows_valid"],
            gauges["prefill_rows_chained"]) == (128, 100, 36)


@pytest.mark.parametrize("family", ["xing4", "glm_dsa"])
def test_a_family_of_pages_alone_chains_as_gpt2_does(monkeypatch, family):
    """Latents, rotary keys and index keys are rows of pages too: a later
    seat reads the earlier one's from the pool, through the expanded
    attention's tiles and the indexer's scores alike."""
    import importlib

    mod = importlib.import_module(f"mpit_tpu.models.{family}")
    config = mod.Xing4Config if family == "xing4" else mod.GlmDsaConfig
    cfg = config.tiny(max_seq_len=64)
    params = mod.init_params(cfg, jax.random.key(3))
    _steer(monkeypatch, 32, 2048)
    prompt = np.random.RandomState(4).randint(
        1, cfg.vocab_size, size=53).tolist()
    runs = []
    for chains in (True, False):
        eng = Engine(cfg, params, slots=3, max_len=64, seed=0,
                     kv_page_size=16, prefill_chunk=16,
                     decode_attention="reference")
        assert eng._prefill_counts == (2,) and eng.spare_seats(1) == 1
        if not chains:
            eng.spare_seats = lambda takers: 0
        server = Server(eng)
        server.submit(Request(rid=0, prompt=prompt, max_new_tokens=3))
        (done,) = server.run()
        runs.append((list(done.tokens), eng.lengths(), server.tick,
                     [np.asarray(leaf) for leaf in jax.tree.leaves(
                         (eng.cache.k, eng.cache.v, eng.cache.x))]))
    (toks, lens, ticks, pool), (toks2, lens2, ticks2, pool2) = runs
    assert toks == toks2
    np.testing.assert_array_equal(lens, lens2)
    assert ticks2 - ticks == 2  # chunk ticks: four, and two
    for a, b in zip(pool, pool2):
        np.testing.assert_array_equal(a, b)
