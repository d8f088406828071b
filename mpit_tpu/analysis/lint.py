"""AST lint: the repo's host-side invariants as named, suppressible rules.

Thirteen PRs of informal discipline, encoded (ISSUE 14 tentpole):

- ``host-sync-in-hot-seam`` — no blocking host sync (``float()`` /
  ``.item()`` / ``np.asarray`` on a device value, ``block_until_ready``,
  ``jax.device_get``) inside the hot seams: the ``hardened_loop`` step
  body, the scheduler tick functions, the engine step wrappers. The
  ONE deliberate fence per seam is either inside a
  ``with obs.span("host_fence", ...)`` block (the loop's labeled-fence
  convention) or carries an ``# analysis: allow(...)`` suppression that
  states the contract (the engine wrappers' "the fetch is the step's
  completion fence" docstrings, now machine-checked).
- ``jit-in-hot-seam`` — no ``jax.jit`` construction at per-request /
  per-tick depth (a recompile hazard: jitted steps must be cached at
  module or engine scope — the "two compiles for the engine's
  lifetime" discipline).
- ``determinism-seam`` — no wall clock (``time.time`` & friends), no
  global ``random.*`` draws, no unseeded ``np.random.*`` in the
  determinism-pinned seams (``serve/loadgen.py``, ``compat/faults.py``,
  ``serve/spec.py``): "same (spec, seed) ⇒ same trace" is a test-pinned
  contract, and a wall-clock read anywhere in those modules silently
  breaks it for every caller.
- ``unlabeled-utilization`` — a function that writes a utilization
  percentage (``mfu_pct`` / ``hbm_util_pct`` / ``ici_util_pct``) must
  contain a ``platform`` gate: percentages of TPU peak are fabrication
  on any other backend (the ISSUE 8 honesty rule, now enforced at
  every writer, not just the one that remembered).
- ``thread-bind`` — a helper thread whose target touches compat
  messaging (``Send``/``Recv``/...) must ``bind_thread`` first, or its
  traffic is attributed to whatever rank last ran on that thread (the
  elastic heartbeat bug class, fixed in PR 10 round-2 review).
- ``ledger-seam`` — every scheduler/policy decision seam named in
  ``DEFAULT_CONFIG.ledger_seams`` must emit a request-ledger event (a
  call through an attr chain containing "ledger") or carry an
  ``# analysis: allow(ledger-seam)`` suppression stating where the
  decision IS ledgered: a new decision point that silently skips the
  ledger makes exactly the requests it touches invisible to why-slow
  forensics (ISSUE 16).
- ``memledger-seam`` — every allocation/free seam named in
  ``DEFAULT_CONFIG.memledger_seams`` (the page allocator's grant/free
  transitions, the weight/draft store registrations) must emit a
  memory-ledger event (a call through an attr chain containing
  "memledger") or carry an ``# analysis: allow(memledger-seam)``
  suppression stating where the bytes ARE accounted: one silent seam
  and the conservation invariant (grants − frees == held) breaks for
  every capacity verdict downstream (ISSUE 18).
- ``shipment-seam`` — every KV-page serialize/deserialize site named
  in ``DEFAULT_CONFIG.shipment_seams`` (the fleet's pack/unpack/send/
  recv/inject functions) must emit a ledger event (a call through an
  attr chain containing "ledger") or carry an
  ``# analysis: allow(shipment-seam)`` suppression stating where the
  shipment IS ledgered: KV bytes crossing the wire unledgered are
  invisible to fleet why-slow forensics and the P2P attribution
  (ISSUE 19).
- ``tier-seam`` — every device↔host page-copy site named in
  ``DEFAULT_CONFIG.tier_seams`` (the engine's spill/restore/host-free
  wrappers) must emit a memory-ledger event (a call through an attr
  chain containing "memledger" or "ledger") or carry an
  ``# analysis: allow(tier-seam)`` suppression stating where the
  transfer IS charged: a page crossing the HBM↔host boundary outside
  the ledger-charged seam makes the per-tier conservation invariant
  and the spill/restream byte counters lie to every capacity verdict
  (ISSUE 20).

Device-value tracking for ``host-sync-in-hot-seam`` is a local taint
pass: seeds are calls into ``jnp.*`` / ``jax.*``, jitted handles
(``*_jit`` attributes), configured device callables (``step_fn``), and
any call that receives one of those as an argument (the
``compile_watch.call("step", step_fn, ...)`` idiom); taint propagates
through assignment, tuple unpack, subscripts, attributes and
arithmetic. ``float()`` on a genuinely host value (a numpy percentile,
a python scalar) is NOT flagged — pinned by the corpus false-positive
guards.
"""

from __future__ import annotations

import ast
import dataclasses

from mpit_tpu.analysis.common import (
    SourceFile,
    Violation,
    qualname_visit,
    register_rule,
)

R_HOST_SYNC = register_rule(
    "host-sync-in-hot-seam",
    "blocking host sync on a device value inside a hot seam (outside a "
    "labeled host_fence span)",
)
R_JIT_DEPTH = register_rule(
    "jit-in-hot-seam",
    "jax.jit construction at per-request/per-tick depth (recompile "
    "hazard; cache jitted steps at module/engine scope)",
)
R_DETERMINISM = register_rule(
    "determinism-seam",
    "wall clock / global RNG / unseeded np.random in a "
    "determinism-pinned seam",
)
R_UTIL_GATE = register_rule(
    "unlabeled-utilization",
    "utilization percentage written without a platform gate in the "
    "same function",
)
R_THREAD_BIND = register_rule(
    "thread-bind",
    "helper thread touches compat messaging without bind_thread",
)
R_LEDGER_SEAM = register_rule(
    "ledger-seam",
    "scheduler/policy decision seam emits no request-ledger event — "
    "new decision points must not go dark in why-slow forensics",
)
R_MEMLEDGER_SEAM = register_rule(
    "memledger-seam",
    "allocation/free seam emits no memory-ledger event — one silent "
    "seam breaks byte conservation for every capacity verdict",
)
R_SHIPMENT_SEAM = register_rule(
    "shipment-seam",
    "KV-page serialize/deserialize site emits no ledger event — "
    "shipped bytes go dark in fleet forensics and P2P attribution",
)
R_TIER_SEAM = register_rule(
    "tier-seam",
    "device<->host page copy outside the ledger-charged spill/restore "
    "seam — cross-tier bytes go dark and per-tier conservation lies",
)


@dataclasses.dataclass
class LintConfig:
    """What the rules consider a seam. Defaults name the repo's own
    seams centrally (package files need no markers); in-file
    ``# analysis: hot-seam`` / ``determinism-seam`` directives extend
    the sets for new modules and the test corpus."""

    # path suffix -> set of function qualnames forming the hot seams
    hot_seams: dict = dataclasses.field(default_factory=dict)
    # names treated as device-returning callables when seen as a call
    # target OR as a call argument (the wrapped-step idiom)
    device_fns: frozenset = frozenset({"step_fn"})
    # path suffixes of determinism-pinned modules
    determinism_modules: frozenset = frozenset()
    # obs.span names that label a deliberate host fence
    fence_spans: frozenset = frozenset({"host_fence"})
    # path suffix -> qualnames of request-lifecycle decision seams:
    # each must emit a ledger event (a call through an attr chain
    # containing "ledger") or carry # analysis: allow(ledger-seam)
    ledger_seams: dict = dataclasses.field(default_factory=dict)
    # path suffix -> qualnames of HBM allocation/free seams: each must
    # emit a memory-ledger event (attr chain containing "memledger")
    # or carry # analysis: allow(memledger-seam)
    memledger_seams: dict = dataclasses.field(default_factory=dict)
    # path suffix -> qualnames of KV-shipment serialize/deserialize
    # seams: each must emit a ledger event (attr chain containing
    # "ledger") or carry # analysis: allow(shipment-seam)
    shipment_seams: dict = dataclasses.field(default_factory=dict)
    # path suffix -> qualnames of device<->host page-copy seams: each
    # must emit a memory-ledger event (attr chain containing
    # "memledger"/"ledger") or carry # analysis: allow(tier-seam)
    tier_seams: dict = dataclasses.field(default_factory=dict)


DEFAULT_CONFIG = LintConfig(
    hot_seams={
        "mpit_tpu/train/loop.py": {"hardened_loop"},
        "mpit_tpu/serve/scheduler.py": {
            "Server._decode_tick",
            "Server._spec_tick",
            "Server._prefill_chunk_tick",
            "Server._run_tick",
            # One tick of steps in flight (ISSUE 29): what enqueues,
            # fetches and settles a step. The fences themselves are the
            # engine's two fetch halves.
            "Server._stage_chunk",
            "Server._chunk_enqueued",
            "Server._fetch",
            "Server._land",
            "Server._drain",
            "Server._settle_prefill",
            "Server._settle_decode",
        },
        "mpit_tpu/serve/engine.py": {
            "Engine.prefill_paged",
            "Engine.prefill_dispatch",
            "Engine._prefill_compact_dispatch",
            "Engine.prefill_fetch",
            "Engine.decode",
            "Engine.decode_dispatch",
            "Engine.decode_fetch",
            "Engine.spec_draft",
            "Engine.spec_verify",
            "Engine.copy_page",
        },
    },
    determinism_modules=frozenset(
        {
            "mpit_tpu/serve/loadgen.py",
            "mpit_tpu/compat/faults.py",
            "mpit_tpu/serve/spec.py",
        }
    ),
    # Request-lifecycle decision seams (ISSUE 16): every site that
    # decides a request's fate must show up in its why-slow ledger.
    ledger_seams={
        "mpit_tpu/serve/scheduler.py": {
            "Server.submit",
            "Server._admit",
            "Server._preempt",
            "Server._stage_chunk",
            "Server._chunk_enqueued",
            "Server._decode_tick",
            "Server._settle_decode",
            "Server._spec_tick",
            "Server._complete",
        },
        "mpit_tpu/serve/policy.py": {"SchedulingPolicy.should_shed"},
    },
    # HBM allocation/free seams (ISSUE 18): every physical byte
    # transition must hit the memory ledger, or conservation breaks.
    memledger_seams={
        "mpit_tpu/serve/kvcache.py": {
            "PageAllocator.admit",
            "PageAllocator.free_slot",
            "PageAllocator.cow_before_write",
            "PageAllocator._trim_reserve",
            "PageAllocator.reset",
        },
        "mpit_tpu/serve/weights.py": {"register_param_store"},
        "mpit_tpu/serve/spec.py": {"register_draft_store"},
    },
    # KV-shipment serialize/deserialize seams (ISSUE 19): every site
    # where KV pages cross the wire must show up in a ledger.
    shipment_seams={
        "mpit_tpu/serve/shipment.py": {
            "pack_shipment",
            "unpack_shipment",
            "send_shipment",
            "recv_shipment",
            "inject_shipment",
        },
    },
    # Device<->host page-copy seams (ISSUE 20): every spill/restore/
    # host-free transition must charge the memory ledger at dispatch
    # or release. (``Engine.drain_spills`` is deliberately absent —
    # it only materializes payloads whose bytes were charged when
    # ``spill_page`` dispatched the copy.)
    tier_seams={
        "mpit_tpu/serve/engine.py": {
            "Engine.spill_page",
            "Engine.restore_page",
            "Engine.host_free",
        },
    },
)

_UTIL_KEYS = {"mfu_pct", "hbm_util_pct", "ici_util_pct"}
_COMPAT_OPS = {
    "Send", "Recv", "Probe", "Wait", "Sendrecv", "Isend", "Irecv",
    "Barrier", "Bcast", "Reduce", "Allreduce", "Gather", "Scatter",
}
# Seeded-constructor allowlist for the determinism rule.
_SEEDED_RANDOM = {"Random", "SystemRandom"}
_SEEDED_NP_RANDOM = {
    "RandomState", "default_rng", "SeedSequence", "Generator",
    "PCG64", "Philox", "MT19937",
}
_WALL_CLOCK = {
    ("time", "time"), ("time", "time_ns"), ("time", "monotonic"),
    ("time", "monotonic_ns"), ("time", "perf_counter"),
    ("time", "perf_counter_ns"),
}


def _attr_chain(node: ast.AST) -> list[str]:
    """``a.b.c`` -> ["a", "b", "c"]; non-chains -> []."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return []


def _target_keys(node: ast.AST):
    """Taint keys for an assignment target: Name -> its id,
    ``self.x`` -> "self.x"; tuples/lists recurse; starred unwraps."""
    if isinstance(node, ast.Name):
        yield node.id
    elif isinstance(node, ast.Attribute):
        chain = _attr_chain(node)
        if chain:
            yield ".".join(chain)
    elif isinstance(node, (ast.Tuple, ast.List)):
        for elt in node.elts:
            yield from _target_keys(elt)
    elif isinstance(node, ast.Starred):
        yield from _target_keys(node.value)
    elif isinstance(node, ast.Subscript):
        yield from _target_keys(node.value)


class _Taint:
    """Local device-value taint for one seam function (ordered walk;
    flow approximation is fine at the granularity these seams are
    written at — straight-line bodies with loops)."""

    def __init__(self, device_fns: frozenset):
        self.device_fns = device_fns
        self.tainted: set[str] = set()

    def is_device_call(self, call: ast.Call) -> bool:
        chain = _attr_chain(call.func)
        if chain:
            root, leaf = chain[0], chain[-1]
            if root in ("jnp", "jax"):
                return True
            if leaf.endswith("_jit") or leaf in self.device_fns:
                return True
        # A call that RECEIVES a device callable or tainted value
        # returns device values (compile_watch.call("step", step_fn, …)).
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            if self.expr_tainted(arg):
                return True
            achain = _attr_chain(arg)
            if achain and (
                achain[-1].endswith("_jit") or achain[-1] in self.device_fns
            ):
                return True
        return False

    def expr_tainted(self, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and sub.id in self.tainted:
                return True
            if isinstance(sub, ast.Attribute):
                chain = _attr_chain(sub)
                if chain and ".".join(chain) in self.tainted:
                    return True
            if isinstance(sub, ast.Call) and self.is_device_call(sub):
                return True
        return False

    def assign(self, targets, value) -> None:
        if value is not None and self.expr_tainted(value):
            for t in targets:
                for key in _target_keys(t):
                    self.tainted.add(key)


def _span_name(with_item: ast.withitem):
    """The literal first argument of an ``obs.span(...)`` /
    ``span_at(...)`` context manager, or None."""
    ctx = with_item.context_expr
    if not isinstance(ctx, ast.Call):
        return None
    chain = _attr_chain(ctx.func)
    if not chain or chain[-1] not in ("span", "span_at"):
        return None
    if ctx.args and isinstance(ctx.args[0], ast.Constant):
        return ctx.args[0].value
    return None


def _module_matches(path: str, suffixes) -> bool:
    p = path.replace("\\", "/")
    return any(p.endswith(s) for s in suffixes)


def _sync_kind(call: ast.Call):
    """Classify a call as a host-sync sink: returns (kind, arg) or
    None. Kinds: 'float', 'item', 'asarray', 'block_until_ready',
    'device_get'."""
    chain = _attr_chain(call.func)
    if not chain:
        return None
    leaf = chain[-1]
    if chain == ["float"] and call.args:
        return ("float", call.args[0])
    if leaf == "item" and len(chain) >= 2:
        # x.item() — the receiver is the argument.
        return ("item", call.func.value)
    if leaf in ("asarray", "array") and chain[0] in ("np", "numpy") and call.args:
        return ("asarray", call.args[0])
    if leaf == "block_until_ready":
        arg = call.args[0] if call.args else (
            call.func.value if isinstance(call.func, ast.Attribute) else None
        )
        return ("block_until_ready", arg)
    if leaf == "device_get" and chain[0] == "jax":
        return ("device_get", call.args[0] if call.args else None)
    return None


def _is_jit_construction(call: ast.Call) -> bool:
    chain = _attr_chain(call.func)
    if chain == ["jax", "jit"]:
        return True
    # functools.partial(jax.jit, ...) — still a construction site.
    if chain and chain[-1] == "partial" and call.args:
        inner = _attr_chain(call.args[0])
        if inner == ["jax", "jit"]:
            return True
    return False


def _lint_hot_seam(
    sf: SourceFile, qualname: str, fn: ast.AST, cfg: LintConfig,
    out: list[Violation],
) -> None:
    taint = _Taint(cfg.device_fns)
    _STMT_EXPR_FIELDS = ("value", "test", "iter", "exc", "items")

    def walk(node, in_fence: bool):
        # Nested defs inherit the seam (the loop's _consume helper) but
        # not its taint seeds beyond closed-over names — good enough.
        if isinstance(node, ast.With):
            fence = in_fence or any(
                _span_name(item) in cfg.fence_spans for item in node.items
            )
            _check_exprs([i.context_expr for i in node.items], in_fence)
            for child in node.body:
                walk(child, fence)
            return
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            value = getattr(node, "value", None)
            targets = (
                node.targets
                if isinstance(node, ast.Assign)
                else [node.target]
            )
            if value is not None:
                _check_exprs([value], in_fence)
            taint.assign(targets, value)
            return
        # Compound statements: check their own expressions, then walk
        # child statements (so each expression is checked exactly once).
        exprs = []
        for field in _STMT_EXPR_FIELDS:
            val = getattr(node, field, None)
            if isinstance(val, ast.expr):
                exprs.append(val)
        _check_exprs(exprs, in_fence)
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.stmt) or isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                walk(child, in_fence)
            elif isinstance(child, ast.ExceptHandler):
                for c in child.body:
                    walk(c, in_fence)

    def _check_exprs(exprs, in_fence):
        for expr in exprs:
            for sub in ast.walk(expr):
                if not isinstance(sub, ast.Call):
                    continue
                _check_call(sub, in_fence)

    def _check_call(sub, in_fence):
        if _is_jit_construction(sub):
            v = sf.violation(
                R_JIT_DEPTH, sub,
                f"jax.jit constructed inside hot seam {qualname} — "
                "per-tick compile hazard; cache the jitted step at "
                "module/engine scope",
            )
            if v:
                out.append(v)
        kind = _sync_kind(sub)
        if kind is None or in_fence:
            return
        what, arg = kind
        if what in ("block_until_ready", "device_get"):
            v = sf.violation(
                R_HOST_SYNC, sub,
                f"{what} inside hot seam {qualname} outside a "
                "host_fence span",
            )
            if v:
                out.append(v)
        elif arg is not None and taint.expr_tainted(arg):
            v = sf.violation(
                R_HOST_SYNC, sub,
                f"{what}() on a device value inside hot seam "
                f"{qualname} outside a host_fence span",
            )
            if v:
                out.append(v)

    for stmt in fn.body:
        walk(stmt, False)


def _lint_determinism(sf: SourceFile, out: list[Violation]) -> None:
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if len(chain) < 2:
            continue
        pair = (chain[-2], chain[-1])
        if pair in _WALL_CLOCK or (
            chain[0] == "datetime" and chain[-1] in ("now", "utcnow")
        ):
            v = sf.violation(
                R_DETERMINISM, node,
                f"wall-clock read {'.'.join(chain)}() in a "
                "determinism-pinned seam — traces must be a pure "
                "function of (spec, seed)",
            )
            if v:
                out.append(v)
        elif chain[0] == "random" and len(chain) == 2 and (
            chain[1] not in _SEEDED_RANDOM
        ):
            v = sf.violation(
                R_DETERMINISM, node,
                f"global random.{chain[1]}() in a determinism-pinned "
                "seam — use a seeded random.Random instance",
            )
            if v:
                out.append(v)
        elif (
            len(chain) >= 3
            and chain[-2] == "random"
            and chain[0] in ("np", "numpy")
            and chain[-1] not in _SEEDED_NP_RANDOM
        ):
            v = sf.violation(
                R_DETERMINISM, node,
                f"unseeded np.random.{chain[-1]}() in a "
                "determinism-pinned seam — use np.random.RandomState("
                "seed) / default_rng(seed)",
            )
            if v:
                out.append(v)


def _writes_util_key(node: ast.AST):
    """Yield (lineno, key) for writes of a utilization percentage:
    ``x["mfu_pct"] = ...``, dict literals, and ``mfu_pct=`` keywords."""
    for sub in ast.walk(node):
        if isinstance(sub, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = (
                sub.targets if isinstance(sub, ast.Assign) else [sub.target]
            )
            for t in targets:
                if (
                    isinstance(t, ast.Subscript)
                    and isinstance(t.slice, ast.Constant)
                    and t.slice.value in _UTIL_KEYS
                ):
                    yield sub.lineno, t.slice.value
        elif isinstance(sub, ast.Dict):
            for k in sub.keys:
                if isinstance(k, ast.Constant) and k.value in _UTIL_KEYS:
                    yield k.lineno, k.value
        elif isinstance(sub, ast.Call):
            for kw in sub.keywords:
                if kw.arg in _UTIL_KEYS:
                    yield sub.lineno, kw.arg


def _has_platform_gate(fn: ast.AST) -> bool:
    """A test anywhere in the function that mentions ``platform``
    (name, attribute or string-keyed subscript) — the reachability
    approximation of "percentages only behind a platform gate"."""
    for sub in ast.walk(fn):
        tests = []
        if isinstance(sub, ast.If):
            tests.append(sub.test)
        elif isinstance(sub, ast.IfExp):
            tests.append(sub.test)
        elif isinstance(sub, ast.Assert):
            tests.append(sub.test)
        for t in tests:
            for n in ast.walk(t):
                if isinstance(n, ast.Name) and "platform" in n.id:
                    return True
                if isinstance(n, ast.Attribute) and "platform" in n.attr:
                    return True
                if isinstance(n, ast.Constant) and n.value == "tpu":
                    return True
                if (
                    isinstance(n, ast.Subscript)
                    and isinstance(n.slice, ast.Constant)
                    and n.slice.value == "platform"
                ):
                    return True
    return False


def _lint_util_gate(sf: SourceFile, out: list[Violation]) -> None:
    for qualname, fn in qualname_visit(sf.tree):
        writes = list(_writes_util_key(fn))
        if not writes:
            continue
        if _has_platform_gate(fn):
            continue
        line, key = writes[0]
        v = sf.violation(
            R_UTIL_GATE, line,
            f"{qualname} writes {key} with no platform gate in the "
            "function — utilization percentages are fabrication off-TPU "
            "(obs honesty rule)",
        )
        if v:
            out.append(v)


def _lint_thread_bind(sf: SourceFile, out: list[Violation]) -> None:
    # Collect every function def by name (module, class and nested
    # scope) — thread targets are resolved by bare name.
    defs: dict[str, ast.AST] = {}
    for qualname, fn in qualname_visit(sf.tree):
        defs.setdefault(fn.name, fn)

    def body_calls(fn: ast.AST, leaves: set) -> bool:
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call):
                chain = _attr_chain(sub.func)
                if chain and chain[-1] in leaves:
                    return True
        return False

    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if chain[-2:] != ["threading", "Thread"] and chain != ["Thread"]:
            continue
        target = None
        for kw in node.keywords:
            if kw.arg != "target":
                continue
            if isinstance(kw.value, ast.Name):
                target = kw.value.id
            elif isinstance(kw.value, ast.Attribute):
                # Bound-method targets (target=self._beat) resolve by
                # bare method name — the repo's loader idiom; a rule
                # blind to them misses the exact bug class it exists
                # for (review finding).
                target = kw.value.attr
        if target is None or target not in defs:
            continue
        tfn = defs[target]
        if body_calls(tfn, _COMPAT_OPS) and not body_calls(
            tfn, {"bind_thread"}
        ):
            v = sf.violation(
                R_THREAD_BIND, node,
                f"thread target {target} calls compat messaging ops "
                "without bind_thread — its traffic would be attributed "
                "to whatever rank last ran on the thread",
            )
            if v:
                out.append(v)


def _lint_ledger_seam(sf: SourceFile, qualname: str, fn, out) -> None:
    """A configured decision seam must emit at least one ledger event —
    any call whose attribute chain passes through a name containing
    "ledger" (``self._ledger.event(...)``, ``ledger.retire(...)``)
    counts; guard sites (``if self._ledger is not None:``) keep the
    call visible even when the ledger is disabled at runtime."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if any("ledger" in part for part in chain):
                return
    v = sf.violation(
        R_LEDGER_SEAM, fn,
        f"decision seam {qualname} emits no request-ledger event — a "
        "request deciding its fate here is invisible to why-slow "
        "forensics; emit one or suppress with "
        "# analysis: allow(ledger-seam)",
    )
    if v:
        out.append(v)


def _lint_memledger_seam(sf: SourceFile, qualname: str, fn, out) -> None:
    """A configured allocation/free seam must emit at least one
    memory-ledger event — any call whose attribute chain passes through
    a name containing "memledger" (``self.memledger.grant(...)``,
    ``memledger.register(...)``) counts; guard sites
    (``if self.memledger is not None:``) keep the seam wired even when
    the ledger is absent at runtime."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if any("memledger" in part for part in chain):
                return
    v = sf.violation(
        R_MEMLEDGER_SEAM, fn,
        f"allocation/free seam {qualname} emits no memory-ledger event "
        "— bytes moving here are unattributed and the conservation "
        "invariant (grants - frees == held) breaks; emit one or "
        "suppress with # analysis: allow(memledger-seam)",
    )
    if v:
        out.append(v)


def _lint_shipment_seam(sf: SourceFile, qualname: str, fn, out) -> None:
    """A configured KV serialize/deserialize seam must emit at least
    one ledger event — any call whose attribute chain passes through a
    name containing "ledger" (``ledger.event(...)``,
    ``self._ledger.event(...)``) counts; guard sites (``if ledger is
    not None:``) keep the seam wired even when no ledger rides the
    call."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if any("ledger" in part for part in chain):
                return
    v = sf.violation(
        R_SHIPMENT_SEAM, fn,
        f"shipment seam {qualname} emits no ledger event — KV bytes "
        "crossing the wire here are invisible to fleet why-slow "
        "forensics and P2P attribution; emit one or suppress with "
        "# analysis: allow(shipment-seam)",
    )
    if v:
        out.append(v)


def _lint_tier_seam(sf: SourceFile, qualname: str, fn, out) -> None:
    """A configured device<->host page-copy seam must emit at least one
    memory-ledger event — any call whose attribute chain passes through
    a name containing "memledger" or "ledger"
    (``self.memledger.grant(...)``) counts; guard sites (conditional
    frees on the release path) keep the seam wired even when the
    transfer is a no-op at runtime."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if any("ledger" in part for part in chain):
                return
    v = sf.violation(
        R_TIER_SEAM, fn,
        f"tier seam {qualname} moves pages across the HBM<->host "
        "boundary without a memory-ledger event — cross-tier bytes go "
        "dark and per-tier conservation (grants - frees == held) lies "
        "to every capacity verdict; charge the ledger or suppress with "
        "# analysis: allow(tier-seam)",
    )
    if v:
        out.append(v)


def lint_file(
    sf: SourceFile, cfg: LintConfig = DEFAULT_CONFIG,
    rules: set | None = None,
) -> list[Violation]:
    """Run every lint rule (or the ``rules`` subset) over one parsed
    file. The caller surfaces parse errors (``sf.tree is None``)."""
    if sf.tree is None:
        return []
    out: list[Violation] = []

    def on(rule):
        return rules is None or rule in rules

    # Hot seams: central config + in-file directives.
    seam_quals = set()
    for suffix, quals in cfg.hot_seams.items():
        if _module_matches(sf.path, [suffix]):
            seam_quals |= set(quals)
    if on(R_HOST_SYNC) or on(R_JIT_DEPTH):
        for qualname, fn in qualname_visit(sf.tree):
            marked = sf.func_role("hot-seam", fn.lineno) or sf.module_role(
                "hot-seam"
            )
            if qualname in seam_quals or marked:
                _lint_hot_seam(sf, qualname, fn, cfg, out)

    if on(R_LEDGER_SEAM):
        ledger_quals = set()
        for suffix, quals in cfg.ledger_seams.items():
            if _module_matches(sf.path, [suffix]):
                ledger_quals |= set(quals)
        for qualname, fn in qualname_visit(sf.tree):
            marked = sf.func_role("ledger-seam", fn.lineno)
            if qualname in ledger_quals or marked:
                _lint_ledger_seam(sf, qualname, fn, out)

    if on(R_MEMLEDGER_SEAM):
        memledger_quals = set()
        for suffix, quals in cfg.memledger_seams.items():
            if _module_matches(sf.path, [suffix]):
                memledger_quals |= set(quals)
        for qualname, fn in qualname_visit(sf.tree):
            marked = sf.func_role("memledger-seam", fn.lineno)
            if qualname in memledger_quals or marked:
                _lint_memledger_seam(sf, qualname, fn, out)

    if on(R_SHIPMENT_SEAM):
        shipment_quals = set()
        for suffix, quals in cfg.shipment_seams.items():
            if _module_matches(sf.path, [suffix]):
                shipment_quals |= set(quals)
        for qualname, fn in qualname_visit(sf.tree):
            marked = sf.func_role("shipment-seam", fn.lineno)
            if qualname in shipment_quals or marked:
                _lint_shipment_seam(sf, qualname, fn, out)

    if on(R_TIER_SEAM):
        tier_quals = set()
        for suffix, quals in cfg.tier_seams.items():
            if _module_matches(sf.path, [suffix]):
                tier_quals |= set(quals)
        for qualname, fn in qualname_visit(sf.tree):
            marked = sf.func_role("tier-seam", fn.lineno)
            if qualname in tier_quals or marked:
                _lint_tier_seam(sf, qualname, fn, out)

    if on(R_DETERMINISM) and (
        _module_matches(sf.path, cfg.determinism_modules)
        or sf.module_role("determinism-seam")
    ):
        _lint_determinism(sf, out)

    if on(R_UTIL_GATE):
        _lint_util_gate(sf, out)

    if on(R_THREAD_BIND) and "mpit_tpu/compat/" not in sf.path.replace(
        "\\", "/"
    ):
        # compat's own rank-thread bootstrap IS the binding machinery.
        _lint_thread_bind(sf, out)

    if rules is not None:
        out = [v for v in out if v.rule in rules]
    return out
