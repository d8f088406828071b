"""One tick of steps in flight (ISSUE 29).

The server enqueues tick n+1 before it fetches tick n's tokens. What the
host decides from counts (slot release, refill, the cache fill) happens
in the tick it happened in when every step was fetched at once; what
needs values (``_Live.tokens``, first-token and finish times,
``Completed``, an EOS) comes a tick later. Pinned here:

- the ``Completed`` tokens of a mixed backlog equal those of the loop
  that fetches every step before it enqueues the next, which this module
  keeps as plain ``engine.prefill_paged`` / ``engine.decode`` calls;
- an EOS stops a request's tokens where it stood although one more token
  was computed behind it, and the slot's successor is untouched by it;
- the order of enqueues and fetches, the depth of one tick, and the
  times stamped on a request, through a recording stand-in engine;
- a preemption fetches what is in flight first;
- a speculative engine keeps nothing in flight.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpit_tpu import obs
from mpit_tpu.models import GPT2, GPT2Config
from mpit_tpu.serve import Engine, Request, Server

CFG = GPT2Config.tiny(
    vocab_size=64, max_seq_len=64, num_layers=2, num_heads=2, d_model=32,
    dtype=jnp.float32,
)
SHARED = [11, 12, 13, 14, 15, 16, 17, 18, 19]  # a page and a row of another


@pytest.fixture(autouse=True)
def _obs_disabled_by_default():
    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def engine():
    params = jax.jit(GPT2(CFG).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    # Three slots of 32 positions over a pool that recycles pages;
    # prompts go in chunks of 4.
    return Engine(CFG, params, slots=3, max_len=32, kv_pages=10,
                  kv_page_size=8, prefill_chunk=4, prefill_len=24)


def backlog():
    """Refills (seven requests over three slots), a prefix hit (rid 3
    has rid 0's prompt and is admitted once that is registered: a whole
    page and a partial one, which it copies out before its first
    write), a request of one token, and one that fills its slot."""
    return [
        Request(rid=0, prompt=SHARED + [3], max_new_tokens=8),
        Request(rid=1, prompt=[7, 4], max_new_tokens=4),
        Request(rid=2, prompt=[5, 9, 3, 7, 2], max_new_tokens=9),
        Request(rid=3, prompt=SHARED + [3], max_new_tokens=5),
        Request(rid=4, prompt=list(range(20, 40)), max_new_tokens=12),
        Request(rid=5, prompt=[1], max_new_tokens=1),
        Request(rid=6, prompt=[2, 6, 2, 6, 2, 6], max_new_tokens=4),
    ]


def synchronous_loop(eng, reqs):
    """The loop as it was before the overlap: admit, a chunk, a decode,
    each step's tokens on the host before anything else is decided.
    Returns ``{rid: tokens}`` and the number of ticks."""
    alloc, s, w = eng.allocator, eng.slots, eng.prefill_chunk
    queue, free = list(reqs), list(range(s))[::-1]
    pre, live, out, tick = {}, {}, {}, 0
    temp, topk = np.zeros(s, np.float32), np.zeros(s, np.int32)

    def retire(slot):
        r, toks = live[slot]
        if (len(toks) >= r.max_new_tokens or toks[-1] == r.eos_id
                or len(r.prompt) + len(toks) - 1 >= eng.max_len):
            out[r.rid] = toks
            del live[slot]
            alloc.free_slot(slot)
            free.append(slot)

    def private(slot, pos):
        pair = alloc.cow_before_write(slot, pos)
        if pair is not None:
            eng.copy_page(*pair)

    while queue or pre or live:
        while free and queue:
            r = queue[0]
            plan = alloc.admit(free[-1], r.prompt, r.max_new_tokens)
            if plan is None:
                break
            shared = plan.shared_tokens
            pre[free.pop()] = [queue.pop(0), min(shared, len(r.prompt) - 1),
                               shared]
        if pre:
            toks = np.zeros((s, w), np.int32)
            base, lens, floor = (np.zeros(s, np.int32) for _ in range(3))
            mask = np.zeros(s, bool)
            for slot, (r, b, f) in pre.items():
                n = min(w, len(r.prompt) - b)
                if max(b, f) < b + n:
                    private(slot, max(b, f))
                toks[slot, :n] = r.prompt[b : b + n]
                base[slot], lens[slot], floor[slot] = b, n, f
                mask[slot] = b + n == len(r.prompt)
            first = eng.prefill_paged(toks, base, lens, floor, mask, temp, topk)
            for slot in list(pre):
                pre[slot][1] += int(lens[slot])
                if mask[slot]:
                    r = pre.pop(slot)[0]
                    alloc.register_prefix(slot, r.prompt)
                    live[slot] = (r, [int(first[slot])])
                    retire(slot)
        if live:
            active = np.zeros(s, bool)
            for slot, (r, toks) in live.items():
                active[slot] = True
                private(slot, len(r.prompt) + len(toks) - 1)
            nxt = eng.decode(active, temp, topk)
            for slot in list(live):
                live[slot][1].append(int(nxt[slot]))
                retire(slot)
        tick += 1
    return out, tick


def serve(eng, reqs, **kw):
    eng.reset()
    server = Server(eng, **kw)
    for r in reqs:
        server.submit(r)
    server.run()
    return server


def test_mixed_backlog_matches_the_synchronous_loop(engine):
    engine.reset()
    want, ticks = synchronous_loop(engine, backlog())
    hits = engine.allocator.prefix_hits
    server = serve(engine, backlog())
    got = {c.rid: c.tokens for c in server.completed}
    assert got == want
    assert len(want[5]) == 1  # one token, from its chunk alone
    # Its last position is the slot's last: the pages ``admit`` reserved.
    assert len(backlog()[4].prompt) + len(want[4]) == engine.max_len
    assert engine.allocator.prefix_hits == hits >= 1
    assert engine.allocator.cow_copies >= 1
    # Slots go back, and are refilled, in the tick they were before; only
    # the last ``Completed`` may need the tick after.
    assert server.tick in (ticks, ticks + 1)
    assert not server._in_flight and not server.live
    assert server.steps_overlapped > server.steps_drained >= 1
    assert engine.allocator.pages_in_use == 0


def test_eos_is_found_a_tick_late_and_leaves_no_mark(engine):
    long = Request(rid="a", prompt=[5, 9, 3, 7, 2], max_new_tokens=12)
    free_run = serve(engine, [long]).completed[0].tokens
    # Stop it at a token it has not shown before, well inside its budget.
    k = next(i for i in range(3, 10) if free_run[i] not in free_run[:i])
    nxt = Request(rid="b", prompt=[7, 4, 1], max_new_tokens=6)
    alone = serve(engine, [nxt]).completed[0].tokens

    rec = obs.Recorder()
    with obs.local_recorder(rec):
        engine.reset()
        server = Server(engine)
        server.submit(Request(rid="a", prompt=long.prompt, max_new_tokens=12,
                              eos_id=free_run[k]))
        server.run()
        done = server.completed[0]
        assert done.tokens == free_run[: k + 1] and not done.truncated
        # A step was enqueued behind the one that produced the EOS: its
        # token (``free_run[k + 1]``) is counted and kept nowhere.
        assert server.stats()["generated_tokens"] == k + 1
        assert rec.counter_total("serve_tokens") == k  # decode tokens kept
        steps = [e for e in rec.snapshot()["events"]
                 if e[0] == "X" and e[1] == "decode_dispatch"]
        assert len(steps) == k + 1
        assert engine.allocator.pages_in_use == 0 and len(server.free) == 3
        # The successor takes the same slot, whose pages hold the row the
        # dropped step wrote; nothing of it reaches the successor.
        server.submit(nxt)
        server.run()
    assert server.completed[1].tokens == alone


class Recording:
    """The engine, with every enqueue and fetch of a step written down:
    the step's kind, the requests it serves (read off the server as the
    step is enqueued) and the time the call began and returned."""

    def __init__(self, engine):
        self._engine = engine
        self.server = None
        self.log = []  # (event, kind, step id, rids, t_begin, t_end)
        self._ids, self._held = {}, []  # held: no ``id`` is used twice

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def _note(self, event, kind, step, rids, t0):
        self._held.append(step)
        sid = self._ids.setdefault(id(step), len(self._ids))
        self.log.append((event, kind, sid, rids, t0, time.perf_counter()))

    def prefill_dispatch(self, tokens, base, chunk_lens, floor, sample_mask,
                         temp, topk, seats):
        t0 = time.perf_counter()
        rids = [self.server.prefilling[s].req.rid
                for s in seats[sample_mask]]
        step = self._engine.prefill_dispatch(
            tokens, base, chunk_lens, floor, sample_mask, temp, topk, seats)
        self._note("enqueue", "prefill", step, rids, t0)
        return step

    def decode_dispatch(self, active, temp, topk):
        t0 = time.perf_counter()
        rids = [self.server.live[s].req.rid for s in np.flatnonzero(active)]
        step = self._engine.decode_dispatch(active, temp, topk)
        self._note("enqueue", "decode", step, rids, t0)
        return step

    def prefill_fetch(self, step):
        t0 = time.perf_counter()
        out = self._engine.prefill_fetch(step)
        self._note("fetch", "prefill", step, None, t0)
        return out

    def decode_fetch(self, step):
        t0 = time.perf_counter()
        out = self._engine.decode_fetch(step)
        self._note("fetch", "decode", step, None, t0)
        return out


def test_order_depth_and_stamps_through_a_recording_engine(engine):
    engine.reset()
    eng = Recording(engine)
    server = eng.server = Server(eng)
    for r in backlog():
        server.submit(r)
    per_tick = []
    while server._pending():
        at = len(eng.log)
        server.run(max_ticks=server.tick + 1)
        per_tick.append(eng.log[at:])
        # Never more than one tick in flight, and that tick the last.
        assert {s.tick for s in server._in_flight} <= {server.tick - 1}
    assert not server._in_flight  # ``run()`` returns with none
    enqueued = {sid: i for i, t in enumerate(per_tick)
                for ev, _, sid, *_ in t if ev == "enqueue"}
    for i, events in enumerate(per_tick):
        order = [(ev, kind) for ev, kind, *_ in events]
        for kind in ("prefill", "decode"):
            # A phase enqueues this tick's step before it fetches.
            if ("enqueue", kind) in order and ("fetch", kind) in order:
                assert order.index(("enqueue", kind)) < order.index(
                    ("fetch", kind))
        for ev, kind, sid, *_ in events:
            if ev == "fetch":
                # What a tick fetches the tick before enqueued, unless
                # nothing could be enqueued behind it any more (a drain).
                assert enqueued[sid] in (i - 1, i)
                if enqueued[sid] == i:
                    assert order[-1][0] == "fetch"
        if i:
            # ... and fetches every step the tick before enqueued.
            for kind in ("prefill", "decode"):
                if any(e[:2] == ("enqueue", kind) for e in per_tick[i - 1]):
                    assert ("fetch", kind) in order
    # Every step enqueued is fetched, once.
    log = eng.log
    assert sorted(sid for ev, _, sid, *_ in log if ev == "fetch") == sorted(
        sid for ev, _, sid, *_ in log if ev == "enqueue")
    fetch = {sid: (t0, t1) for ev, _, sid, _, t0, t1 in log if ev == "fetch"}
    done = {c.rid: c for c in server.completed}
    assert len(done) == len(backlog())
    for ev, kind, sid, rids, *_ in log:
        if ev != "enqueue":
            continue
        if kind == "prefill":
            behind = next((s for e, k, s, *_ in log
                           if e == "enqueue" and k == "decode" and s > sid),
                          None)
            for rid in rids:
                # Stamped when its own chunk's tokens landed: not before,
                # and not as late as the decode step behind the chunk.
                assert done[rid].first_token_t >= fetch[sid][1]
                if behind is not None and len(done[rid].tokens) > 1:
                    assert done[rid].first_token_t <= fetch[behind][0]
        for rid in rids:
            assert done[rid].finish_t >= done[rid].first_token_t
    last_step = {}
    for ev, kind, sid, rids, *_ in log:
        for rid in rids or ():
            last_step[rid] = sid
    for rid, c in done.items():
        assert c.finish_t >= fetch[last_step[rid]][1]


def test_a_preemption_fetches_what_is_in_flight_first(engine):
    req = Request(rid="v", prompt=[5, 9, 3, 7, 2], max_new_tokens=10)
    want = serve(engine, [req]).completed[0].tokens
    engine.reset()
    server = Server(engine)
    server.submit(req)
    server.run(max_ticks=5)
    (slot, live), = server.live.items()
    assert server._in_flight and live.issued == len(live.tokens) + 1
    drained = server.steps_drained
    server._preempt(slot)
    assert not server._in_flight and not server.live
    assert server.steps_drained == drained + 1
    # The feed is the prompt and every token asked for so far.
    assert live.feed == req.prompt + want[: live.issued]
    assert len(live.tokens) == live.issued
    server.run()
    assert server.completed[0].tokens == want


def test_a_speculative_engine_keeps_nothing_in_flight():
    from mpit_tpu.serve import draft_from_target  # noqa: F401  (the tier)

    dcfg = GPT2Config.tiny(
        vocab_size=64, max_seq_len=64, num_layers=1, num_heads=2, d_model=32,
        dtype=jnp.float32,
    )
    params = jax.jit(GPT2(CFG).init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    dparams = jax.jit(GPT2(dcfg).init)(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = Engine(CFG, params, slots=2, max_len=32, kv_page_size=8,
                 prefill_len=8, spec_k=2, draft_params=dparams,
                 draft_cfg=dcfg)
    server = Server(eng)
    server.submit(Request(rid=0, prompt=[5, 9, 3], max_new_tokens=6))
    server.submit(Request(rid=1, prompt=[7], max_new_tokens=4))
    while server._pending():
        server.run(max_ticks=server.tick + 1)
        assert not server._in_flight
    assert server.steps_overlapped == 0
    # Every step that had a token for someone: fetched in its own tick.
    assert server.steps_drained >= server.tick
    assert {c.rid: len(c.tokens) for c in server.completed} == {0: 6, 1: 4}
