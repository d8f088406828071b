"""The SPMD training step — the collapsed pserver/pclient protocol.

Reference hot loop (SURVEY.md §4.2): each worker computes fwd/bwd, Isends
its gradient to the server, Irecvs fresh params; the server Recvs from
ANY_SOURCE, applies goo, Sends params back. TPU-native (BASELINE.json
north-star): one jitted function per step over the whole mesh —

    grads = ∇loss(params, local_batch)
    combine: pmean(grads, 'data')            (plain sync DP), or
             reduce-scatter into shards      (ZeRO-1 sharded goo)
    updates, opt_state = goo.update(...)
    params ← params + updates                (all-gather under ZeRO-1)

No messages, no tags, no server rank: the parameter server is now a
collective + sharded state.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from mpit_tpu import opt as gopt
from mpit_tpu.comm import collectives as C
from mpit_tpu.obs import startup as _startup
from mpit_tpu.opt.sharded import state_partition_specs


class TrainState(NamedTuple):
    """Replicated params + (optionally sharded) goo state + step counter.

    ``extra`` carries non-gradient model state (e.g. BatchNorm batch_stats),
    replicated.
    """

    step: jax.Array
    params: Any
    opt_state: Any
    extra: Any = ()


def zero1_state_fns(
    tx: optax.GradientTransformation,
    world,
    *,
    axis: str = "data",
    zero1: bool = True,
    stx: optax.GradientTransformation | None = None,
):
    """The state plumbing shared by every train-step tier.

    Returns ``(stx, state_specs, init_fn)``:

    - ``stx``: the ZeRO-1-wrapped transform (or the one passed in, for
      tiers that need non-default reduce semantics), ``None`` when
      ``zero1=False``;
    - ``state_specs(params, extra=()) -> TrainState`` of PartitionSpecs;
    - ``init_fn(params, extra=()) -> TrainState`` (host-level, jitted
      shard_map over ``world``).
    """
    n = world.axis_size(axis)
    if zero1 and stx is None:
        stx = gopt.sharded(tx, axis)

    def state_specs(params, extra=()):
        if zero1:
            opt_specs = state_partition_specs(tx, params, n, axis)
        else:
            opt_specs = jax.tree.map(
                lambda _: P(), jax.eval_shape(tx.init, params)
            )
        return TrainState(
            step=P(),
            params=jax.tree.map(lambda _: P(), params),
            opt_state=opt_specs,
            extra=jax.tree.map(lambda _: P(), extra),
        )

    def _per_device_init(params, extra):
        opt_state = stx.init(params) if zero1 else tx.init(params)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
            extra=extra,
        )

    def init_fn(params, extra=()) -> TrainState:
        specs = state_specs(params, extra)
        f = world.shard_map(
            _per_device_init, in_specs=(P(), specs.extra), out_specs=specs
        )
        with _startup.span("state_init"):
            return jax.jit(f)(params, extra)

    return stx, state_specs, init_fn


def make_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    world,
    *,
    axis: str = "data",
    zero1: bool = True,
    stateful: bool = False,
    donate: bool = True,
    scan_steps: int | None = None,
    grad_sync: str = "psum",
    grad_bucket_mb: float = 4.0,
    grad_sync_interpret: bool | None = None,
):
    """Build ``(init_fn, step_fn, state_specs)`` for SPMD data-parallel
    training over ``world``'s ``axis``.

    Args:
      loss_fn: ``loss_fn(params, batch) -> (loss, aux)`` — or, when
        ``stateful=True``, ``loss_fn(params, extra, batch) -> (loss, aux,
        new_extra)`` (for models with BatchNorm-style mutable state; the
        new extra is pmean-synced across replicas).
      tx: the goo transformation (any optax transform).
      world: the communication World.
      axis: mesh data axis name.
      zero1: shard optimizer state across ``axis`` (reduce-scatter/
        all-gather path); False = replicated state + plain pmean DP.
      donate: donate the input state buffers to the step (in-place update).
      grad_sync: the gradient-sync wire tier (ISSUE 9;
        ``train/grad_sync.py``): ``"psum"`` (default) keeps the stock
        XLA collectives byte-for-byte; ``"ring"`` issues the in-kernel
        Pallas ring reduce-scatter/all-gather per fixed-size gradient
        bucket (the same sums in ring order: bitwise psum on the CPU
        fallback, reduction-order noise on chips); ``"ring_q8"``
        adds the EQuARX-spirit int8 wire with per-chunk scales (~¼ the
        wire bytes; lossy — the MNIST/AlexNet loss-curve pin is the
        contract). Off-TPU the ring modes fall back to the exact
        ``lax`` composition, and the EXECUTED mode is stamped on the
        loop's step spans as ``grad_sync=`` (the way serve stamps
        ``attention=``), exposed here as ``step_fn.grad_sync_mode``.
      grad_bucket_mb / grad_sync_interpret: bucket size and interpret-
        mode flag for the ring tiers (see ``GradSync``).
      scan_steps: when set, ``step_fn`` consumes a *stacked* batch (every
        leaf carries a leading ``[scan_steps, ...]`` axis) and runs that
        many optimizer steps inside one compiled call via ``lax.scan`` —
        one host→device dispatch per K steps instead of per step. This is
        the TPU-native answer to dispatch latency (no host round-trip
        between steps). Metrics are those of the **last** scanned step.

    Returns:
      ``init_fn(params, extra=()) -> TrainState`` (host-level),
      ``step_fn(state, sharded_batch) -> (state, metrics)`` (jitted),
      ``state_specs(params, extra=()) -> TrainState`` of PartitionSpecs.
    """
    from mpit_tpu.train.grad_sync import GradSync

    _startup.install()
    gs = (
        grad_sync
        if isinstance(grad_sync, GradSync)
        else GradSync(
            axis, grad_sync, bucket_mb=grad_bucket_mb,
            interpret=grad_sync_interpret,
        )
    )
    # psum mode passes stx=None so zero1_state_fns builds the seed
    # gopt.sharded(tx, axis) — byte-for-byte the pre-ISSUE-9 path.
    ring_stx = (
        gopt.sharded(tx, axis, comm=gs)
        if zero1 and gs.mode != "psum"
        else None
    )
    stx, state_specs, init_fn = zero1_state_fns(
        tx, world, axis=axis, zero1=zero1, stx=ring_stx
    )

    def _per_device_step(state: TrainState, batch):
        # Grads must be taken w.r.t. a device-varying view of the params:
        # otherwise jax's VMA-aware AD auto-inserts a psum (grads arrive
        # pre-summed) and the explicit reduction below would double-count.
        # See comm.collectives.vary.
        local_params = C.vary(state.params, axis)

        # The step's scope names (a device trace is summed by them):
        # ``loss`` inside the differentiated function, so that autodiff
        # writes ``jvp(loss)`` forward and ``transpose(jvp(loss))``
        # backward; ``opt_update`` round the optimizer with, inside it,
        # ``grad_sync`` and ``zero1_gather`` round the collectives
        # (opt.sharded names its own).
        def lf(p):
            with jax.named_scope("loss"):
                if not stateful:
                    return loss_fn(p, batch)
                loss, aux, new_extra = loss_fn(p, state.extra, batch)
                return loss, (aux, new_extra)

        if stateful:
            (loss, (aux, new_extra)), grads = jax.value_and_grad(
                lf, has_aux=True
            )(local_params)
            new_extra = jax.tree.map(lambda e: lax.pmean(e, axis), new_extra)
        else:
            (loss, aux), grads = jax.value_and_grad(lf, has_aux=True)(
                local_params
            )
            new_extra = state.extra

        with jax.named_scope("opt_update"):
            if zero1:
                # local grads in; reduce-scatter + shard-update +
                # all-gather inside (mean semantics — stx was built with
                # mean_grads=True).
                updates, opt_state = stx.update(
                    grads, state.opt_state, state.params
                )
            else:
                # Plain-DP sync — GradSync's pluggable wire (psum mode IS
                # the seed lax.pmean, the ring modes flatten + bucket).
                with jax.named_scope("grad_sync"):
                    grads = gs.allreduce_grads(grads)
                updates, opt_state = tx.update(
                    grads, state.opt_state, state.params
                )
            params = optax.apply_updates(state.params, updates)

        metrics = {"loss": loss, **aux}
        metrics = jax.tree.map(lambda m: lax.pmean(m, axis), metrics)
        new_state = TrainState(
            step=state.step + 1, params=params, opt_state=opt_state, extra=new_extra
        )
        return new_state, metrics

    def _per_device_multi(state: TrainState, stacked):
        new_state, metrics = lax.scan(_per_device_step, state, stacked)
        return new_state, jax.tree.map(lambda m: m[-1], metrics)

    def build_step(params, extra=()):
        specs = state_specs(params, extra)
        if scan_steps:
            body, batch_spec = _per_device_multi, P(None, axis)
        else:
            body, batch_spec = _per_device_step, P(axis)
        f = world.shard_map(
            body,
            in_specs=(specs, batch_spec),
            out_specs=(specs, P()),
        )

        # One stable module name, ``jit_train_step``, whatever the body:
        # a trace's reduction tells the step's operations by it.
        def train_step(state, batch):
            return f(state, batch)

        return jax.jit(train_step, donate_argnums=(0,) if donate else ())

    # step_fn lazily builds (and caches) the compiled step on first call,
    # keyed by state/batch structure.
    compiled: dict = {}

    def step_fn(state: TrainState, batch):
        key = (
            jax.tree_util.tree_structure((state, batch)),
            tuple(
                (l.shape, str(l.dtype)) for l in jax.tree.leaves((state, batch))
            ),
        )
        f = compiled.get(key)
        if f is not None:
            return f(state, batch)
        f = compiled[key] = build_step(state.params, state.extra)
        # The first call of a structure compiles: the start-up record's
        # ``compile`` span (jit_train_step) with JAX's events and
        # ``first_run`` as children, unless a caller's CompileWatch is
        # open round this call and records it (hardened_loop's). The
        # watch waits for the first step's output, once; with it ready
        # the trainer is.
        with _startup.Watch() as watch:
            out = f(state, batch)
        if watch.compiled:
            watch.close(out, phase="step", scope="train")
            _startup.ready("train")
        return out

    # AOT seam: the raw jax.jit object, for `.lower()` against abstract
    # args on a topology mesh (utils/aot.py compile_multichip).
    step_fn.build = build_step

    def _cache_size():
        # The jit-cache population summed over the per-structure
        # compiled steps: growth across a call means an XLA compile
        # happened. The benchmark's EdgeStep carries it along;
        # obs.roofline.CompileWatch reads JAX's events, not this.
        return sum(f._cache_size() for f in compiled.values())

    step_fn._cache_size = _cache_size
    # Executed-mode stamp (ISSUE 9 satellite): hardened_loop attaches
    # this to its step spans so traces attribute fallback runs honestly.
    step_fn.grad_sync_mode = gs.exec_mode
    return init_fn, step_fn, state_specs


def make_eval_step(eval_fn: Callable, world, *, axis: str = "data"):
    """Build a jitted SPMD eval step: ``eval_fn(params, extra, batch) ->
    metrics`` (pytree of scalars), pmean-reduced across replicas.

    Exact-count contract: when ``eval_fn`` returns a ``"_weight"`` entry
    (its local count of real — non-pad — rows, see the val sweep's
    ``valid`` mask), every other metric is treated as a weighted mean and
    combined as ``psum(m*w)/psum(w)``; the returned ``"_weight"`` is the
    global real-row count so the host sweep can weight batches the same
    way. Without ``"_weight"`` the old plain-pmean contract applies.
    """

    def _per_device(params, extra, batch):
        metrics = dict(eval_fn(params, extra, batch))
        w = metrics.pop("_weight", None)
        if w is None:
            return jax.tree.map(lambda m: lax.pmean(m, axis), metrics)
        wsum = lax.psum(w, axis)
        out = {
            k: lax.psum(m * w, axis) / jnp.maximum(wsum, 1.0)
            for k, m in metrics.items()
        }
        out["_weight"] = wsum
        return out

    compiled: dict = {}

    def step(state: TrainState, batch):
        key = (
            jax.tree_util.tree_structure((state.params, state.extra, batch)),
            tuple(
                (l.shape, str(l.dtype))
                for l in jax.tree.leaves((state.params, state.extra, batch))
            ),
        )
        f = compiled.get(key)
        if f is None:
            f = jax.jit(
                world.shard_map(
                    _per_device,
                    in_specs=(P(), P(), P(axis)),
                    out_specs=P(),
                )
            )
            compiled[key] = f
        return f(state.params, state.extra, batch)

    return step
