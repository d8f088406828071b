"""Reusable jaxpr-contract assertions + the whole-package sweep.

ISSUE 14 pass 2: the repo pinned its "never materializes X" invariants
with per-test string/aval greps (``tests/test_serve.py``,
``tests/test_decode_attention.py`` each carried a private
``_avals_with_shape``). This module is the ONE audited implementation —
the tests now import it — plus a sweep that traces every registered
jitted step and checks its declared contracts, so a new code path that
re-materializes the ``[slots, vocab]`` logits fails tier-1 even if its
author never read the serving tests.

Library (works on a jaxpr, a ClosedJaxpr, or a callable + args):

- :func:`find_avals` — recursively collect eqn OUTPUT avals of a given
  shape (nested call/scan/cond/pallas jaxprs included); byte-compatible
  with the old test helpers.
- :func:`assert_no_intermediate` / :func:`assert_intermediate` — the
  materialization pin and its anti-vacuity twin ("the reference DOES
  materialize, so the pin means something").
- :func:`assert_no_transfer` — no ``device_put`` / host-callback
  primitives inside a step's jaxpr (a jitted hot-path step must not
  smuggle host round-trips).
- :func:`find_primitives` / :func:`assert_no_primitive` — the named
  primitives anywhere in a jaxpr, nested ones included ("the greedy
  branch of the sampler neither sorts nor draws noise").
- :func:`max_eqn_count` / :func:`eqn_count` — growth pin.
- :func:`donation_aliases` / :func:`assert_donation_consumed` — count
  ``tf.aliasing_output`` annotations in lowered StableHLO: donation
  that silently stopped applying (a dtype/shape change upstream) shows
  up as 2× transient HBM on the real chip.

The sweep (:func:`sweep`) builds tiny-config engines/steps on whatever
backend is present (tracing only — ``jax.make_jaxpr`` and ``.lower()``,
no kernel execution) and reports violations in the shared
:class:`~mpit_tpu.analysis.common.Violation` shape. Contracts are
REGISTERED (name → check) so ``--rule jaxpr-contracts`` can list and
subset them.
"""

from __future__ import annotations

from mpit_tpu.analysis.common import Violation, register_rule

R_JAXPR = register_rule(
    "jaxpr-contracts",
    "a registered jitted step violates its declared jaxpr contract "
    "(materialization / transfer / donation)",
)

__all__ = [
    "sub_jaxprs",
    "find_avals",
    "assert_no_intermediate",
    "assert_intermediate",
    "assert_no_transfer",
    "find_primitives",
    "assert_no_primitive",
    "eqn_count",
    "max_eqn_count",
    "donation_aliases",
    "assert_donation_consumed",
    "sweep",
    "CONTRACTS",
]


class JaxprContractError(AssertionError):
    """A declared contract does not hold on the traced step."""


def _as_jaxpr(j):
    """Accept a ClosedJaxpr, a jaxpr, or anything carrying ``.jaxpr``."""
    return getattr(j, "jaxpr", j)


def sub_jaxprs(p):
    """Yield nested jaxprs reachable from an eqn param (closed jaxprs,
    raw jaxprs, and lists/tuples of either — scan/cond/pallas params)."""
    if hasattr(p, "jaxpr"):
        yield p.jaxpr
    elif hasattr(p, "eqns"):
        yield p
    elif isinstance(p, (list, tuple)):
        for q in p:
            yield from sub_jaxprs(q)


def find_avals(jaxpr, shape, prims=None, dtype=None):
    """Recursively collect eqn output avals of ``shape`` (incl. nested
    call/scan/cond jaxprs) — the materialization detector. Returns
    ``[(primitive_name, aval), ...]`` (the old test helpers' shape).
    ``prims`` optionally restricts to outputs of those primitives
    (e.g. ``{"dot_general"}`` pins "the logits matmul never runs at
    full width" while tolerating a full-width INPUT flowing through
    elementwise ops). ``dtype`` optionally restricts by element type —
    the quantized-decode contract (ISSUE 15) needs it: the int8 pool
    ITSELF legitimately has the pool shape, and only a float32 aval of
    that shape means the dequant escaped its tile."""
    jaxpr = _as_jaxpr(jaxpr)
    found = []
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and getattr(aval, "shape", None) == shape:
                if dtype is not None and getattr(
                    aval, "dtype", None
                ) != dtype:
                    continue
                if prims is None or eqn.primitive.name in prims:
                    found.append((eqn.primitive.name, aval))
        for p in eqn.params.values():
            for sub in sub_jaxprs(p):
                found.extend(find_avals(sub, shape, prims, dtype))
    return found


def assert_no_intermediate(jaxpr, *shapes, what="step", prims=None,
                           dtype=None):
    """No eqn output of any of ``shapes`` (of ``dtype``, when given)
    anywhere in the jaxpr."""
    for shape in shapes:
        hits = find_avals(jaxpr, tuple(shape), prims, dtype)
        if hits:
            raise JaxprContractError(
                f"{what} materializes {tuple(shape)}"
                f"{f' ({dtype})' if dtype is not None else ''}: "
                f"{[(p, str(a)) for p, a in hits[:4]]}"
            )


def assert_intermediate(jaxpr, shape, what="reference", dtype=None):
    """Anti-vacuity: the shape IS produced somewhere (so the matching
    ``assert_no_intermediate`` on the optimized path means something)."""
    if not find_avals(jaxpr, tuple(shape), None, dtype):
        raise JaxprContractError(
            f"{what} no longer materializes {tuple(shape)} — the "
            "no-materialization pin on the optimized path is vacuous"
        )


_TRANSFER_PRIMS = {
    "device_put",
    "pure_callback",
    "io_callback",
    "host_callback",
    "outside_call",
}


def _walk_eqns(jaxpr):
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in sub_jaxprs(p):
                yield from _walk_eqns(sub)


def assert_no_transfer(jaxpr, what="step"):
    """No host-transfer / callback primitives inside the step."""
    bad = find_primitives(jaxpr, _TRANSFER_PRIMS)
    if bad:
        raise JaxprContractError(
            f"{what} contains host-transfer primitives {sorted(set(bad))} "
            "— a jitted hot-path step must not smuggle host round-trips"
        )


def find_primitives(jaxpr, prims) -> list:
    """Names of the eqns (nested jaxprs included) whose primitive is
    one of ``prims``, in the order they are met."""
    return [
        e.primitive.name for e in _walk_eqns(jaxpr)
        if e.primitive.name in prims
    ]


def assert_no_primitive(jaxpr, prims, what="step"):
    """None of the primitives ``prims`` anywhere in the jaxpr."""
    bad = find_primitives(jaxpr, prims)
    if bad:
        raise JaxprContractError(
            f"{what} contains {sorted(set(bad))} ({len(bad)} eqns)"
        )


def eqn_count(jaxpr) -> int:
    return sum(1 for _ in _walk_eqns(jaxpr))


def max_eqn_count(jaxpr, limit: int, what="step"):
    n = eqn_count(jaxpr)
    if n > limit:
        raise JaxprContractError(
            f"{what} grew to {n} eqns (pin: <= {limit}) — check for an "
            "unrolled loop or a duplicated subgraph"
        )


def donation_aliases(lowered_text: str) -> int:
    """Count donated inputs in lowered StableHLO. Two spellings on jax
    0.4.x: ``tf.aliasing_output`` when aliasing is resolved at lowering
    (single-device), ``jax.buffer_donor`` when it is deferred to
    compile (SPMD mesh) — both mean the input buffer is donated."""
    return lowered_text.count("tf.aliasing_output") + lowered_text.count(
        "jax.buffer_donor"
    )


def assert_donation_consumed(lowered_or_text, min_aliased: int = 1,
                             what="step"):
    txt = (
        lowered_or_text
        if isinstance(lowered_or_text, str)
        else lowered_or_text.as_text()
    )
    n = donation_aliases(txt)
    if n < min_aliased:
        raise JaxprContractError(
            f"{what} aliases only {n} donated inputs (pin: >= "
            f"{min_aliased}) — donation silently stopped applying "
            "(2x transient HBM for the state on chip)"
        )


# ---------------------------------------------------------------------------
# The whole-package sweep: registered steps × declared contracts.
# ---------------------------------------------------------------------------


def _tiny_model():
    import jax
    import jax.numpy as jnp

    from mpit_tpu.models import GPT2, GPT2Config

    cfg = GPT2Config.tiny(
        vocab_size=64, max_seq_len=64, num_layers=2, num_heads=2,
        d_model=32, dtype=jnp.float32,
    )
    model = GPT2(cfg)
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return cfg, params


def _contract_decode_blocked(ctx):
    """Blocked head + flash decode: the [slots, vocab] f32 logits and
    the gathered [slots, H, 1, max_len] score tensor never exist in the
    decode jaxpr — and the reference engine DOES produce the logits (the
    pin is non-vacuous). Also: no host-transfer primitives in the step."""
    import jax
    import jax.numpy as jnp

    from mpit_tpu.serve import Engine

    cfg, params = ctx["model"]
    slots = 2

    def decode_jaxpr(eng):
        return jax.make_jaxpr(eng._paged_decode_step)(
            eng.params, eng.cache, eng.last_token,
            jnp.ones((slots,), bool),
            jnp.zeros((slots, eng.pages_per_slot), jnp.int32),
            jax.random.key(0),
            jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.int32),
        )

    eng = Engine(
        cfg, params, slots=slots, max_len=40, prefill_len=8,
        kv_pages=24, kv_page_size=8, decode_attention="interpret",
        sample_block=32, sample_k_cap=16,
    )
    jx = decode_jaxpr(eng)
    assert_no_intermediate(
        jx,
        (slots, cfg.vocab_size),
        (slots, 1, cfg.vocab_size),
        (slots, cfg.num_heads, 1, eng.max_len),
        what="blocked decode step",
    )
    assert_no_transfer(jx, what="blocked decode step")
    ref = Engine(
        cfg, params, slots=slots, max_len=40, prefill_len=8,
        kv_pages=24, kv_page_size=8, decode_attention="reference",
    )
    assert_intermediate(
        decode_jaxpr(ref), (slots, 1, cfg.vocab_size),
        what="reference decode",
    )


def _contract_quantized_decode(ctx):
    """ISSUE 15: the int8 KV cache's dequant stays PER-TILE inside the
    decode kernel — no full dequantized f32 pool (or per-slot dense
    view) intermediate may materialize in the quantized decode step's
    jaxpr. The int8 pool itself legitimately carries the pool shape, so
    the pin is dtype-filtered to float32. Anti-vacuity: the reference
    engine (the parity oracle) DOES materialize the dequantized f32
    view — the pin means something."""
    import jax
    import jax.numpy as jnp

    from mpit_tpu.serve import Engine

    cfg, params = ctx["model"]
    slots, pages, ps = 2, 24, 8
    eng = Engine(
        cfg, params, slots=slots, max_len=40, prefill_len=8,
        kv_pages=pages, kv_page_size=ps, decode_attention="interpret",
        sample_block=32, sample_k_cap=16, kv_dtype="int8",
    )
    bt = jnp.zeros((slots, eng.pages_per_slot), jnp.int32)
    jx = jax.make_jaxpr(eng._paged_decode_step)(
        eng.params, eng.cache, eng.last_token,
        jnp.ones((slots,), bool), bt, jax.random.key(0),
        jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.int32),
    )
    f32 = jnp.dtype(jnp.float32)
    assert_no_intermediate(
        jx,
        # One layer's dequantized pool, as stored (rows packed) and
        # head-split.
        (pages, ps, cfg.num_heads * cfg.head_dim),
        (pages, ps, cfg.num_heads, cfg.head_dim),
        (slots, eng.pages_per_slot * ps,   # a slot's gathered dense view
         cfg.num_heads, cfg.head_dim),
        what="quantized paged decode step",
        dtype=f32,
    )
    # The [slots, vocab] pin survives quantization too.
    assert_no_intermediate(
        jx, (slots, cfg.vocab_size), (slots, 1, cfg.vocab_size),
        what="quantized paged decode step",
    )
    ref = Engine(
        cfg, params, slots=slots, max_len=40, prefill_len=8,
        kv_pages=pages, kv_page_size=ps, decode_attention="reference",
        kv_dtype="int8",
    )
    jx_ref = jax.make_jaxpr(ref._paged_decode_step)(
        ref.params, ref.cache, ref.last_token,
        jnp.ones((slots,), bool), bt, jax.random.key(0),
        jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.int32),
    )
    assert_intermediate(
        jx_ref,
        (slots, eng.pages_per_slot * ps, cfg.num_heads, cfg.head_dim),
        what="quantized reference decode (dequant oracle)",
        dtype=f32,
    )


def _contract_quantized_weights(ctx):
    """ISSUE 17: the int8 weight store's dequant stays PER-BLOCK inside
    the blocked matmuls — no full dequantized f32 weight (qkv/proj/fc/
    out kernel, wte / tied head) may materialize in any int8 engine
    step's jaxpr. The contract shrinks the tile grain
    (``quant_block_rows=16``, ``sample_block=16``) so a LEGITIMATE
    dequantized tile can never collide with a pinned full-weight shape
    on the tiny config (e.g. a 32-row head tile would equal the 32x32
    proj kernel). Both hot traces are pinned: the plain decode step and
    the speculative draft step (whose head runs INSIDE the hot tick —
    the trace a whole-dequant shortcut would most plausibly sneak back
    through). Anti-vacuity: the reference engine (the whole-dequant
    parity oracle) DOES materialize the f32 qkv kernel."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from mpit_tpu.serve import Engine
    from mpit_tpu.serve.weights import draft_from_target

    cfg, params = ctx["model"]
    cfg16 = dataclasses.replace(cfg, quant_block_rows=16)
    slots, max_len = 2, 32
    f32 = jnp.dtype(jnp.float32)
    weights = (
        (cfg.d_model, 3 * cfg.d_model),  # qkv kernel
        (cfg.d_model, cfg.d_model),      # proj kernel
        (cfg.d_model, cfg.ff_dim),       # fc kernel
        (cfg.ff_dim, cfg.d_model),       # out kernel
        (cfg.vocab_size, cfg.d_model),   # wte / tied head
    )

    block_tables = lambda eng: jnp.zeros(
        (slots, eng.pages_per_slot), jnp.int32)

    def decode_jaxpr(eng):
        return jax.make_jaxpr(eng._paged_decode_step)(
            eng.params, eng.cache, eng.last_token,
            jnp.ones((slots,), bool), block_tables(eng), jax.random.key(0),
            jnp.zeros((slots,), jnp.float32),
            jnp.zeros((slots,), jnp.int32),
        )

    eng = Engine(
        cfg16, params, slots=slots, max_len=max_len, prefill_len=8,
        decode_attention="interpret", sample_block=16, sample_k_cap=16,
        weights_dtype="int8",
    )
    assert_no_intermediate(
        decode_jaxpr(eng), *weights,
        what="int8-weights decode step", dtype=f32,
    )
    dp, dcfg = draft_from_target(params, cfg16, 1)
    spec = Engine(
        cfg16, params, slots=slots, max_len=max_len, prefill_len=8,
        decode_attention="interpret", sample_block=16, sample_k_cap=16,
        spec_k=2, draft_params=dp, draft_cfg=dcfg, weights_dtype="int8",
    )
    jxd = jax.make_jaxpr(spec._spec_draft_step)(
        spec.draft_params, spec.draft_cache, spec.last_token,
        jnp.ones((slots,), bool), jax.random.key(0),
        jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.int32),
        block_tables(spec), jnp.zeros((slots,), jnp.int32),
    )
    assert_no_intermediate(
        jxd, *weights, what="int8-weights spec_draft step", dtype=f32
    )
    ref = Engine(
        cfg, params, slots=slots, max_len=max_len, prefill_len=8,
        decode_attention="reference", weights_dtype="int8",
    )
    assert_intermediate(
        decode_jaxpr(ref), (cfg.d_model, 3 * cfg.d_model),
        what="int8-weights reference decode (whole-dequant oracle)",
        dtype=f32,
    )


# What only a sampling row needs of the blocked sampler: the top-k
# sorts, the Gumbel field, the division by the temperature and the
# gathers that merge candidates.
SAMPLING_PRIMS = frozenset({
    "sort", "top_k", "random_bits", "random_fold_in", "threefry2x32",
    "div", "gather",
})


def sampler_branches(jaxpr):
    """``(greedy, general)`` jaxprs of ``lm_head_sample``'s one
    conditional: ``lax.cond`` keeps the false branch first, and the
    predicate is "some row samples"."""
    conds = [
        e for e in _as_jaxpr(jaxpr).eqns if e.primitive.name == "cond"
    ]
    if len(conds) != 1 or len(conds[0].params["branches"]) != 2:
        raise JaxprContractError(
            f"lm_head_sample holds {len(conds)} top-level conditionals, "
            "not its one of two branches — the greedy path went away"
        )
    return conds[0].params["branches"]


def _contract_lm_head_sample(ctx):
    """The blocked sampler never runs the full-width logits matmul, on
    either path; its greedy path holds nothing that only a sampling row
    needs, and its general path still does (anti-vacuity)."""
    import jax
    import jax.numpy as jnp

    from mpit_tpu.ops.lm_head import lm_head_sample

    del ctx
    S, V, D = 5, 256, 16
    h = jnp.zeros((S, D), jnp.float32)
    head = jnp.zeros((V, D), jnp.float32)
    jx = jax.make_jaxpr(
        lambda h, w, temp, topk: lm_head_sample(
            h, w, jax.random.key(0), temp, topk, block_size=64
        )
    )(h, head, jnp.ones((S,), jnp.float32), jnp.zeros((S,), jnp.int32))
    assert_no_intermediate(jx, (S, V), what="lm_head_sample")
    greedy, general = sampler_branches(jx)
    assert_no_primitive(
        greedy, SAMPLING_PRIMS, what="lm_head_sample's greedy path"
    )
    if not {"top_k", "random_bits"} <= set(
        find_primitives(general, SAMPLING_PRIMS)
    ):
        raise JaxprContractError(
            "lm_head_sample's general path no longer sorts and draws "
            "noise — the pin on the greedy path is vacuous"
        )


def _contract_lm_head_verify(ctx):
    """The speculative verifier's logits matmul never runs at full
    vocab width (qprobs legitimately ENTERS at [N, vocab]; the pin is
    on dot_general outputs — the blocked two-pass contract)."""
    import jax
    import jax.numpy as jnp

    from mpit_tpu.ops.lm_head import lm_head_verify

    del ctx
    N, V, D = 4, 256, 16
    jx = jax.make_jaxpr(
        lambda h, w, q: lm_head_verify(
            h, w, jnp.zeros((N,), jnp.int32), q, jax.random.key(0),
            jnp.ones((N,), jnp.float32), jnp.zeros((N,), jnp.int32),
            block_size=64, k_cap=8,
        )
    )(
        jnp.zeros((N, D), jnp.float32),
        jnp.zeros((V, D), jnp.float32),
        jnp.zeros((N, V), jnp.float32),
    )
    assert_no_intermediate(
        jx, (N, V), what="lm_head_verify", prims={"dot_general"}
    )


def _contract_train_step_donation(ctx):
    """The production train step still donates (and aliases) its state
    buffers — the in-place-update contract that keeps peak HBM at 1x
    state. Lowering only; nothing is compiled or run."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from mpit_tpu import comm
    from mpit_tpu.train.step import make_train_step

    del ctx
    world = comm.init(set_default=False)

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    init_fn, step_fn, _specs = make_train_step(
        loss_fn, optax.sgd(1e-2), world, zero1=False
    )
    n = world.axis_size("data")
    params = {"w": jnp.zeros((8, 4), jnp.float32)}
    state = init_fn(params)
    batch = {
        "x": np.zeros((2 * n, 8), np.float32),
        "y": np.zeros((2 * n, 4), np.float32),
    }
    from mpit_tpu.data.loader import shard_batch

    device_batch = shard_batch(world, batch, axis="data")
    jitted = step_fn.build(state.params, state.extra)
    lowered = jitted.lower(state, device_batch)
    assert_donation_consumed(lowered, min_aliased=2, what="train step")


CONTRACTS = {
    "decode-blocked": _contract_decode_blocked,
    "quantized-decode": _contract_quantized_decode,
    "quantized-weights": _contract_quantized_weights,
    "lm-head-sample": _contract_lm_head_sample,
    "lm-head-verify": _contract_lm_head_verify,
    "train-step-donation": _contract_train_step_donation,
}


def sweep(names=None) -> list:
    """Trace every registered step and check its contracts. Shared
    tiny-model context is built once. Returns Violations (one per
    failed contract; a contract that ERRORS — API drift, import
    failure — is also a violation: the pin went dark, which is exactly
    what the sweep exists to catch)."""
    out = []
    ctx: dict = {}
    try:
        ctx["model"] = _tiny_model()
    except Exception as e:  # pragma: no cover - environment failure
        return [
            Violation(
                R_JAXPR, __file__, 0,
                f"sweep context failed to build: {type(e).__name__}: {e}",
            )
        ]
    for name, fn in CONTRACTS.items():
        if names is not None and name not in names:
            continue
        try:
            fn(ctx)
        except JaxprContractError as e:
            out.append(Violation(R_JAXPR, __file__, 0, f"{name}: {e}"))
        except Exception as e:
            out.append(
                Violation(
                    R_JAXPR, __file__, 0,
                    f"{name}: contract errored ({type(e).__name__}: {e}) "
                    "— the pin went dark; update the contract with the "
                    "API it pins",
                )
            )
    return out
