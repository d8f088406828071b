"""Static verifier for the Pallas kernels (ISSUE 14 pass 3).

Four rules over ``ops/ring_collectives.py`` / ``ops/decode_attention.py``
/ ``ops/flash_attention.py`` / ``ops/ring_allreduce.py``:

- ``kernel-dma-balance`` (AST): every async copy started is waited.
  The repo's two disciplines are both recognized — the
  descriptor-recreation idiom (``dma(...).start()`` … ``dma(...).wait()``
  with matching source operands, the flash-decode double buffer) and
  the list idiom (``rdmas.append(make_async_remote_copy(...))`` then
  ``for r in rdmas: r.start()`` / ``r.wait()``, the ``_Ring`` mailbox).
  A copy group with a ``.start()`` and no ``.wait()`` anywhere in the
  function (or vice versa) is the bug class this catches — an
  unwaited DMA is a use-after-free of the landing buffer on real
  hardware and a silent nothing in interpret mode.
- ``kernel-ring-order`` (AST): the ``_Ring`` call discipline — a
  ``barrier()`` before the first ``exchange``, every loop body pairs
  one ``exchange`` with one ``consumed`` AFTER it, any restaging write
  into a send buffer (``send_*[...] = ...``) happens BEFORE the
  ``consumed`` that releases the landing slot (the documented
  "restage-before-token-release" ordering of ``_ag_q8_kernel``), and a
  ``drain`` follows the steps so every semaphore returns to zero.
- ``kernel-plan-geometry`` (host math, no tracing): the planner's tile
  answers hold over a sweep of payload sizes, device counts and wire
  dtypes (``padded_rows`` a sublane multiple, chunk layout contiguous
  and covering, ``pick_block_k`` always divides the cache length, the
  decode kernel's tile of pages is whole pages of whole VMEM tiles and
  fits), the divisibility preconditions the kernels rely on are
  actually raised by the host wrappers, and the VMEM footprint of one
  ring call at the default GradSync bucket — input + output + the ``_sum_scratch`` /
  ``_q8_scratch`` staging buffers, computed from the very shapes the
  ``pallas_call`` passes — fits the chip's VMEM with the planner's
  own numbers (the tile math and the scratch shapes cannot drift
  apart silently).
- ``kernel-ring-model`` (model check): the ``_Ring`` mailbox protocol
  as an explicit state machine — P devices, double-buffered landing
  slots, capacity tokens, barrier, drain — exhaustively explored over
  every interleaving (including arbitrarily delayed DMA deliveries)
  for P ∈ {2, 3, 4}, both the plain phase and the forwarding (AG-q8
  restage) phase. Checked: no deadlock, no delivery into an
  unconsumed landing slot, no delivery before the receiver entered
  the kernel, no stale read at the forwarding restage, and all
  semaphores zero at exit. Mutations (skip the capacity wait, release
  the token before restaging, skip the barrier, skip the drain) are
  the seeded-violation corpus: each reaches a violating state, so the
  race detector demonstrably detects (tests pin this).
"""

from __future__ import annotations

import ast
import itertools
from collections import deque

from mpit_tpu.analysis.common import (
    SourceFile,
    Violation,
    qualname_visit,
    register_rule,
)

R_DMA = register_rule(
    "kernel-dma-balance",
    "async copy started without a matching wait (or waited without a "
    "start) in a Pallas kernel body",
)
R_RING_ORDER = register_rule(
    "kernel-ring-order",
    "_Ring discipline broken: barrier/exchange/restage/consumed/drain "
    "out of order",
)
R_GEOMETRY = register_rule(
    "kernel-plan-geometry",
    "host planner tile math violated (sublane padding, chunk layout, "
    "block divisibility, VMEM footprint)",
)
R_MODEL = register_rule(
    "kernel-ring-model",
    "_Ring protocol model check found deadlock/slot-reuse (runtime "
    "exploration, P in {2,3,4})",
)

KERNEL_FILES = (
    "mpit_tpu/ops/ring_collectives.py",
    "mpit_tpu/ops/decode_attention.py",
    "mpit_tpu/ops/flash_attention.py",
    "mpit_tpu/ops/ring_allreduce.py",
)

_MAKERS = {"make_async_copy", "make_async_remote_copy"}


def _leaf(call: ast.Call) -> str | None:
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


# ---------------------------------------------------------------------------
# kernel-dma-balance
# ---------------------------------------------------------------------------


def _is_maker_call(call: ast.Call, helpers: set) -> str | None:
    """Return a group key when ``call`` constructs an async copy:
    a direct ``make_async_*`` call or a call of a local helper that
    returns one. Key = callee plus the dump of the first argument
    (the source operand distinguishes the k/v double buffers)."""
    leaf = _leaf(call)
    if leaf in _MAKERS or leaf in helpers:
        first = ast.dump(call.args[0]) if call.args else ""
        return f"{leaf}({first})"
    return None


def _local_copy_helpers(fn: ast.AST) -> set:
    """Nested defs that return a ``make_async_*`` call (the flash
    kernels' ``dma(...)`` descriptor factory)."""
    helpers = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.FunctionDef) and node is not fn:
            for sub in ast.walk(node):
                if (
                    isinstance(sub, ast.Return)
                    and isinstance(sub.value, ast.Call)
                    and _leaf(sub.value) in _MAKERS
                ):
                    helpers.add(node.name)
    return helpers


def check_dma_balance(sf: SourceFile, fn_qual: str, fn: ast.AST) -> list:
    helpers = _local_copy_helpers(fn)
    starts: dict[str, int] = {}
    waits: dict[str, int] = {}
    # Variables holding copies: name -> group key. List vars map to a
    # synthetic group per list.
    var_group: dict[str, str] = {}
    list_vars: set[str] = set()

    for node in ast.walk(fn):
        # rdmas.append(make_async_remote_copy(...))
        if (
            isinstance(node, ast.Call)
            and _leaf(node) == "append"
            and isinstance(node.func, ast.Attribute)
            and isinstance(node.func.value, ast.Name)
            and node.args
            and isinstance(node.args[0], ast.Call)
            and _leaf(node.args[0]) in _MAKERS
        ):
            lname = node.func.value.id
            list_vars.add(lname)
            var_group.setdefault(lname, f"list:{lname}")
        # r = make_async_copy(...)
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            key = _is_maker_call(node.value, helpers)
            if key:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        var_group[t.id] = key

    # Loop targets over copy lists inherit the list's group.
    for node in ast.walk(fn):
        if (
            isinstance(node, ast.For)
            and isinstance(node.iter, ast.Name)
            and node.iter.id in list_vars
            and isinstance(node.target, ast.Name)
        ):
            var_group[node.target.id] = var_group[node.iter.id]

    first_line: dict[str, int] = {}
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Call) and _leaf(node) in ("start", "wait")):
            continue
        recv = node.func.value if isinstance(node.func, ast.Attribute) else None
        if recv is None:
            continue
        key = None
        if isinstance(recv, ast.Call):
            key = _is_maker_call(recv, helpers)
        elif isinstance(recv, ast.Name) and recv.id in var_group:
            key = var_group[recv.id]
        if key is None:
            continue
        first_line.setdefault(key, node.lineno)
        (starts if _leaf(node) == "start" else waits)[key] = (
            (starts if _leaf(node) == "start" else waits).get(key, 0) + 1
        )

    out = []
    for key in sorted(set(starts) | set(waits)):
        if starts.get(key, 0) and not waits.get(key, 0):
            v = sf.violation(
                R_DMA, first_line.get(key, fn.lineno),
                f"{fn_qual}: async copy group {key} is started but never "
                "waited — the landing buffer can be read before the DMA "
                "completes",
            )
            if v:
                out.append(v)
        elif waits.get(key, 0) and not starts.get(key, 0):
            v = sf.violation(
                R_DMA, first_line.get(key, fn.lineno),
                f"{fn_qual}: async copy group {key} is waited but never "
                "started — the wait deadlocks",
            )
            if v:
                out.append(v)
    return out


# ---------------------------------------------------------------------------
# kernel-ring-order
# ---------------------------------------------------------------------------


def check_ring_order(sf: SourceFile, fn_qual: str, fn: ast.AST) -> list:
    """One violation max per function (first discipline break found)."""

    def calls_with_leaf(node, leaf):
        return [
            n
            for n in ast.walk(node)
            if isinstance(n, ast.Call) and _leaf(n) == leaf
        ]

    exchanges = calls_with_leaf(fn, "exchange")
    if not exchanges:
        return []
    first_ex = min(c.lineno for c in exchanges)

    def emit(line, msg):
        v = sf.violation(R_RING_ORDER, line, f"{fn_qual}: {msg}")
        return [v] if v else []

    barriers = calls_with_leaf(fn, "barrier")
    if not barriers or min(b.lineno for b in barriers) > first_ex:
        return emit(
            first_ex,
            "exchange before (or without) the neighbor barrier — a "
            "remote write may land in a mailbox that is not live yet",
        )

    # Per innermost loop containing an exchange: consumed after it,
    # restage writes before consumed.
    for node in ast.walk(fn):
        if not isinstance(node, ast.For):
            continue
        loop_ex = [
            c for c in exchanges
            if any(c is n for n in ast.walk(node))
        ]
        if not loop_ex:
            continue
        consumed = calls_with_leaf(node, "consumed")
        if not consumed:
            return emit(
                loop_ex[0].lineno,
                "exchange without consumed in the same loop — the left "
                "neighbor's capacity token is never released (deadlock "
                "at step s+2)",
            )
        consumed_line = min(c.lineno for c in consumed)
        if consumed_line < min(c.lineno for c in loop_ex):
            return emit(
                consumed_line,
                "consumed before exchange in the loop body — the token "
                "releases a slot that has not been read",
            )
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Assign):
                continue
            for t in sub.targets:
                if (
                    isinstance(t, ast.Subscript)
                    and isinstance(t.value, ast.Name)
                    and t.value.id.startswith("send")
                    and sub.lineno > consumed_line
                ):
                    return emit(
                        sub.lineno,
                        f"restage into {t.value.id} AFTER consumed() "
                        "released the landing slot — races the left "
                        "neighbor's slot reuse (the _ag_q8_kernel "
                        "ordering contract)",
                    )
    drains = calls_with_leaf(fn, "drain")
    if not drains or max(d.lineno for d in drains) < max(
        c.lineno for c in exchanges
    ):
        return emit(
            max(c.lineno for c in exchanges),
            "no drain after the ring steps — trailing capacity tokens "
            "leave semaphores nonzero at kernel exit",
        )
    return []


def check_kernels_ast(sf: SourceFile) -> list:
    """Run both AST kernel rules over every function in the file that
    uses async copies or the ring discipline (plus any function marked
    ``# analysis: pallas-kernel``)."""
    if sf.tree is None:
        return []
    out = []
    for qual, fn in qualname_visit(sf.tree):
        body_src = ast.dump(fn)
        marked = sf.func_role("pallas-kernel", fn.lineno)
        if marked or "make_async" in body_src:
            out.extend(check_dma_balance(sf, qual, fn))
        if marked or "exchange" in body_src:
            out.extend(check_ring_order(sf, qual, fn))
    return out


# ---------------------------------------------------------------------------
# kernel-plan-geometry (host math against the real planner)
# ---------------------------------------------------------------------------

# v5e VMEM per core; one ring call must fit input + output + scratch
# with headroom for the compiler's own temporaries.
_VMEM_BYTES = 16 * 2 ** 20
_VMEM_FILL_CAP = 0.75


def _spec_bytes(spec) -> int:
    shape = getattr(spec, "shape", None)
    dtype = getattr(spec, "dtype", None)
    if not shape or dtype is None:
        return 0  # semaphores
    import numpy as np

    n = 1
    for d in shape:
        n *= int(d)
    try:
        return n * np.dtype(dtype).itemsize
    except TypeError:
        return 0  # semaphore dtypes carry no VMEM payload


def check_plan_geometry() -> list:
    """Import the planner and pin its tile math (no kernels run)."""
    import numpy as np

    from mpit_tpu.ops import ring_collectives as rc

    out = []
    path = rc.__file__

    def bad(msg):
        out.append(Violation(R_GEOMETRY, path, 0, msg))

    payloads = [1, 127, 128, 129, 8191, 65536, 1_000_003, 2 ** 20]
    for payload, p, dt in itertools.product(
        payloads, (1, 2, 3, 4, 8), ("float32", "bfloat16", "int8")
    ):
        plan = rc.plan_ring(payload, p, dt)
        sub = rc.sublane_for(dt)
        if plan.padded_rows % sub or plan.padded_rows < plan.chunk_rows:
            bad(
                f"plan_ring({payload}, p={p}, {dt}): padded_rows="
                f"{plan.padded_rows} not a {sub}-sublane multiple >= "
                f"chunk_rows={plan.chunk_rows}"
            )
        if plan.p * plan.chunk_elems < payload:
            bad(
                f"plan_ring({payload}, p={p}, {dt}): chunks cover "
                f"{plan.p * plan.chunk_elems} < payload {payload}"
            )
        shards = rc.plan_shards(max(1, payload // max(1, p)), p, dt)
        if shards.padded_rows % sub:
            bad(
                f"plan_shards(..., p={p}, {dt}): padded_rows="
                f"{shards.padded_rows} not a sublane multiple"
            )

    # pick_block_k must divide the cache length it tiles — the kernel's
    # loop bound and the host num_kv_blocks mirror both assume it.
    from mpit_tpu.ops.decode_attention import num_kv_blocks, pick_block_k

    for s in (8, 16, 40, 56, 64, 128, 384, 1024, 4096):
        bk = pick_block_k(s)
        if s % bk:
            bad(f"pick_block_k({s}) = {bk} does not divide the cache")
        n = num_kv_blocks(np.asarray([0, s - 1, s * 3]), 1, s, bk)
        if int(np.min(n)) < 1 or int(np.max(n)) > s // bk:
            bad(
                f"num_kv_blocks out of [1, {s // bk}] at s={s}, bk={bk}: "
                f"{n} — the kernel clamp and host mirror disagree"
            )

    # The decode kernel's tile (a paged step gathers pages): a piece
    # divides the page, several pieces a step only of whole pages that
    # are whole VMEM tiles of the pool's dtype (a DMA lands on a tile
    # boundary of a larger buffer), heads as rows only within the bound
    # and never over an int8 pool, and what a step holds in VMEM (the
    # double-buffered K and V tiles with their scale planes, computed
    # from the scratch shapes the pallas_call passes, plus the float32
    # accumulator of the heads-as-rows form) fits, GPT-2 XL's row wide.
    import jax.numpy as jnp

    from mpit_tpu.ops import decode_attention as da

    for page, dt, t, h, quant in itertools.product(
        (4, 8, 16, 32, 64, 256, 1024), ("float32", "bfloat16", "int8"),
        (1, 5, 8, 64), (10, 20, 25), (False, True),
    ):
        if quant != (dt == "int8"):
            continue
        til = da.decode_tiling(t, h, dt, page_size=page, quantized=quant)
        what = f"decode_tiling(T={t}, H={h}, {dt}, page={page}) = {til}"
        sub = rc.sublane_for(dt)
        if page % til.piece_rows or til.pieces < 1:
            bad(f"{what}: a piece does not divide the page")
        if til.pieces > 1 and (til.piece_rows != page or page % sub):
            bad(f"{what}: several pieces a step that are not whole pages "
                f"of whole {sub}-row tiles")
        if til.rows > max(da._TILE_ROWS, til.piece_rows):
            bad(f"{what}: a step of {til.rows} rows, past the tile")
        as_rows = til.form == "heads_as_rows"
        head_rows = t * -(-h // sub) * sub
        if as_rows and (quant or head_rows > da._HEAD_ROWS):
            bad(f"{what}: heads as rows over {head_rows} rows"
                + (" of an int8 pool" if quant else ""))
        dts = (
            [jnp.int8, jnp.float32] * 2 if quant else [jnp.dtype(dt)] * 2
        )
        held = sum(
            _spec_bytes(spec)
            for spec in da._scratch_for(quant, til.rows, h * 64, h, dts)
        ) + (head_rows * h * 64 * 4 if as_rows else 0)
        if held > _VMEM_FILL_CAP * _VMEM_BYTES:
            bad(f"{what}: {held} B of VMEM a step exceeds the cap")

    # VMEM footprint of one ring call at the default GradSync bucket
    # (4 MB, f32 wire and q8 wire), computed from the ACTUAL scratch
    # shapes the pallas_call would allocate.
    bucket_elems = (4 * 2 ** 20) // 4
    for p in (4, 8):
        plan = rc.plan_ring(bucket_elems, p, jnp.float32)
        rows = plan.padded_rows
        io = (plan.p * rows + rows + rows) * rc._LANE * 4  # in + out + ...
        scratch = sum(_spec_bytes(s) for s in rc._sum_scratch(rows, jnp.float32))
        total = io + scratch
        if total > _VMEM_FILL_CAP * _VMEM_BYTES:
            bad(
                f"sum-ring VMEM footprint {total} B at the default 4 MB "
                f"bucket (p={p}) exceeds {_VMEM_FILL_CAP:.0%} of VMEM"
            )
        qplan = rc.plan_ring(bucket_elems, p, jnp.int8)
        qrows = qplan.padded_rows
        # q8 ring: f32 input [p·rows, 128] and f32 output [rows, 128].
        qio = (qplan.p * qrows + qrows) * rc._LANE * 4
        qscratch = sum(_spec_bytes(s) for s in rc._q8_scratch(qrows))
        if qio + qscratch > _VMEM_FILL_CAP * _VMEM_BYTES:
            bad(
                f"q8-ring VMEM footprint {qio + qscratch} B at the "
                f"default 4 MB bucket (p={p}) exceeds the cap"
            )

    # The host wrapper actually raises the divisibility precondition
    # the kernel relies on (a tile must never straddle a page).
    import inspect

    from mpit_tpu.ops import decode_attention as da

    src = inspect.getsource(da.flash_paged_decode_attention)
    has_guard = any(
        isinstance(n, ast.If)
        and any(isinstance(r, ast.Raise) for r in ast.walk(n))
        and any(
            isinstance(b, ast.BinOp) and isinstance(b.op, ast.Mod)
            for b in ast.walk(n.test)
        )
        for n in ast.walk(ast.parse(src))
    )
    if not has_guard:
        bad(
            "flash_paged_decode_attention no longer raises on a "
            "non-dividing block_k — the kernel's tile loop would "
            "straddle pages"
        )
    return out


# ---------------------------------------------------------------------------
# kernel-ring-model: the _Ring mailbox protocol as a state machine
# ---------------------------------------------------------------------------


def _ring_program(i, p, variant, mutations):
    """The per-device action sequence modeling the kernel bodies'
    _Ring usage (see ops/ring_collectives.py): barrier, then per step
    [cap-wait] send / recv-wait / [restage] / consume, then drain."""
    steps = p - 1
    prog = [("enter",)]
    if "skip_barrier" not in mutations:
        prog += [("sig_barrier",), ("wait_barrier",)]
    for s in range(steps):
        if s >= 2 and "skip_cap_wait" not in mutations:
            prog.append(("wait_cap", s % 2))
        prog.append(("send", s))
        prog.append(("wait_recv", s))
        forward = variant == "ag_q8" and s < steps - 1
        if forward and "release_before_restage" not in mutations:
            prog.append(("restage", s))
        prog.append(("consume", s))
        if forward and "release_before_restage" in mutations:
            prog.append(("restage", s))
    if "skip_drain" not in mutations:
        for k in range(min(steps, 2)):
            prog.append(("wait_cap", (steps - 1 - k) % 2))
    prog.append(("done",))
    return tuple(prog)


def model_check_ring(
    p: int, variant: str = "rs", mutations: frozenset = frozenset()
) -> dict:
    """Exhaustively explore every interleaving of device actions and
    DMA deliveries. Returns ``{"ok", "violation", "states"}`` —
    ``violation`` names the first reachable bad state (None when the
    protocol is clean). ``variant``: "rs" (plain phase) or "ag_q8"
    (forwarding phase with the restage read)."""
    progs = [_ring_program(i, p, variant, mutations) for i in range(p)]

    # State: (pcs, mailboxes, caps, barriers, entered, inflight)
    #   mailboxes: p × 2 slot contents (None or step)
    #   caps / barriers: semaphore counters
    #   inflight: sorted tuple of (dest, slot, step)
    init = (
        (0,) * p,
        ((None, None),) * p,
        ((0, 0),) * p,
        (0,) * p,
        (False,) * p,
        (),
    )
    seen = {init}
    stack = deque([init])
    explored = 0

    def left(i):
        return (i - 1) % p

    def right(i):
        return (i + 1) % p

    while stack:
        state = stack.pop()
        explored += 1
        pcs, boxes, caps, bars, entered, inflight = state
        succs = []
        all_done = all(pcs[i] >= len(progs[i]) for i in range(p))

        # Deliveries: any in-flight message may land now.
        for mi, (dest, slot, step) in enumerate(inflight):
            if not entered[dest]:
                return {
                    "ok": False, "states": explored,
                    "violation": (
                        f"P={p} {variant}: remote write (step {step}) "
                        f"delivered to device {dest} before it entered "
                        "the kernel (mailbox not live)"
                    ),
                }
            if boxes[dest][slot] is not None:
                return {
                    "ok": False, "states": explored,
                    "violation": (
                        f"P={p} {variant}: slot reuse — step {step} "
                        f"delivered into device {dest} slot {slot} still "
                        f"holding unconsumed step {boxes[dest][slot]}"
                    ),
                }
            nb = list(map(list, boxes))
            nb[dest][slot] = step
            nf = inflight[:mi] + inflight[mi + 1:]
            succs.append((
                pcs, tuple(map(tuple, nb)), caps, bars, entered, nf
            ))

        for i in range(p):
            if pcs[i] >= len(progs[i]):
                continue
            op = progs[i][pcs[i]]
            kind = op[0]
            adv = lambda **kw: _advance(state, i, p, **kw)
            if kind == "enter":
                ne = list(entered)
                ne[i] = True
                succs.append(adv(entered=tuple(ne)))
            elif kind == "sig_barrier":
                nbars = list(bars)
                nbars[left(i)] += 1
                nbars[right(i)] += 1
                succs.append(adv(bars=tuple(nbars)))
            elif kind == "wait_barrier":
                if bars[i] >= 2:
                    nbars = list(bars)
                    nbars[i] -= 2
                    succs.append(adv(bars=tuple(nbars)))
                continue
            elif kind == "wait_cap":
                slot = op[1]
                if caps[i][slot] >= 1:
                    nc = list(map(list, caps))
                    nc[i][slot] -= 1
                    succs.append(adv(caps=tuple(map(tuple, nc))))
                continue
            elif kind == "send":
                s = op[1]
                nf = tuple(sorted(inflight + ((right(i), s % 2, s),)))
                succs.append(adv(inflight=nf))
            elif kind == "wait_recv":
                s = op[1]
                if boxes[i][s % 2] == s:
                    succs.append(adv())
                continue
            elif kind == "restage":
                s = op[1]
                if boxes[i][s % 2] != s:
                    return {
                        "ok": False, "states": explored,
                        "violation": (
                            f"P={p} {variant}: stale restage — device "
                            f"{i} forwards from landing slot {s % 2} at "
                            f"step {s} but the slot now holds "
                            f"{boxes[i][s % 2]} (released before "
                            "restaging)"
                        ),
                    }
                succs.append(adv())
            elif kind == "consume":
                s = op[1]
                nb = list(map(list, boxes))
                nb[i][s % 2] = None
                nc = list(map(list, caps))
                nc[left(i)][s % 2] += 1
                succs.append(adv(
                    boxes=tuple(map(tuple, nb)),
                    caps=tuple(map(tuple, nc)),
                ))
            elif kind == "done":
                succs.append(adv())

        if not succs:
            if not all_done:
                waiting = [
                    (i, progs[i][pcs[i]])
                    for i in range(p)
                    if pcs[i] < len(progs[i])
                ]
                return {
                    "ok": False, "states": explored,
                    "violation": (
                        f"P={p} {variant}: deadlock — no action enabled, "
                        f"devices blocked at {waiting}"
                    ),
                }
            if any(c for row in caps for c in row) or any(bars) or inflight:
                return {
                    "ok": False, "states": explored,
                    "violation": (
                        f"P={p} {variant}: protocol ends with nonzero "
                        f"semaphores (caps={caps}, barrier={bars}, "
                        f"inflight={inflight}) — the drain contract"
                    ),
                }
            continue
        for s2 in succs:
            if s2 not in seen:
                seen.add(s2)
                stack.append(s2)
    return {"ok": True, "violation": None, "states": explored}


def _advance(state, i, p, **kw):
    pcs, boxes, caps, bars, entered, inflight = state
    npcs = list(pcs)
    npcs[i] += 1
    return (
        tuple(npcs),
        kw.get("boxes", boxes),
        kw.get("caps", caps),
        kw.get("bars", bars),
        kw.get("entered", entered),
        kw.get("inflight", inflight),
    )


def check_ring_model() -> list:
    out = []
    from mpit_tpu.ops import ring_collectives as rc

    for p, variant in itertools.product((2, 3, 4), ("rs", "ag_q8")):
        res = model_check_ring(p, variant)
        if not res["ok"]:
            out.append(Violation(R_MODEL, rc.__file__, 0, res["violation"]))
    return out


def check_kernels_dynamic(rules=None) -> list:
    """The import-the-planner half (geometry pins + model check)."""
    out = []
    if rules is None or R_GEOMETRY in rules:
        out.extend(check_plan_geometry())
    if rules is None or R_MODEL in rules:
        out.extend(check_ring_model())
    return out
