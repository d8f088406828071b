"""ZeRO-1-style cross-replica sharding of the optimizer update.

The north-star requirement (BASELINE.json): "the goo optimizer state sharded
across chips". The reference's pserver holds the full flattened parameter
vector and optimizer state on one process (SURVEY.md §3.1 A1/A3); here every
device holds ``1/N`` of the flattened state and the update choreography is
(cf. arXiv:2004.13336, PAPERS.md):

    reduce-scatter(grads) → update own shard (params + opt state) →
    all-gather(params)

which costs the same bandwidth as a plain allreduce (reduce-scatter +
all-gather IS a ring allreduce, split around the update) while dividing
optimizer memory by N.

Like the reference's flat-tensor design (Torch's flattened parameters), the
pytree is raveled to one 1-D vector, padded to a multiple of
``axis_size * LANE``, and sharded contiguously. The update rule is
elementwise, so flat layout costs nothing on the MXU and keeps shard
boundaries trivial.

TILE-FRIENDLY FLAT LAYOUT (round-4 fix, verified by the v5e-8 AOT
compile check ``compile_multichip.py``): the 322M-param MoE model
compile-OOMed in round 3 because the TPU compiler materialised a
``f32[total/8, 8]`` view of the flat vector, which the layout pass
tile-pads 16× (20.6 GB on a 16 GB chip). Two structural causes, two
rules:

1. **Collectives see ``[rows, LANE]``, never 1-D.** A scatter/gather on
   a flat ``[total]`` makes the lowering reshape ``[total/n, n]`` —
   minor dim = axis size, tile-padded ``LANE/n``×. The 2-D lane view
   keeps the internal reshape at ``[n, rows/n, LANE]`` — zero pad.
2. **Every leaf starts at a LANE-aligned offset** (:func:`flat_ravel`,
   replacing ``ravel_pytree``). The stock unravel (``jnp.split`` at
   arbitrary offsets) made XLA extract a ``[768, 8]`` router leaf by
   reshaping the WHOLE flat vector to ``[total/8, 8]`` (minor dim = the
   leaf's own trailing dim) — the exact 20.6 GB allocation, reachable
   from any weirdly-shaped leaf. With per-leaf padding to a LANE
   multiple, every leaf extraction is whole rows of the ``[rows, LANE]``
   view: slice + reshape, no narrow intermediate. Alignment waste is
   < LANE elements per leaf — noise.

The per-device state stays a 1-D ``[padded_total/n]`` vector;
``train/convert.py`` imports the same :func:`flat_ravel`/:func:`shard_of`
choreography, so checkpoints and conversions can never drift from the
update path.

All functions here run *inside* ``shard_map`` (state is per-device = truly
sharded). :func:`sharded_init`/:func:`sharded_update` are host-level
conveniences that wrap the shard_map for you.
"""

from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from mpit_tpu.comm import collectives as C


# TPU vector lane width: the minor dim of every tile is 128 wide for f32.
# Collectives are fed [rows, LANE] views (see module docstring) so the SPMD
# lowering's internal reshape never creates a narrow, tile-padded minor dim.
LANE = 128


def _pad_to(x: jax.Array, multiple: int) -> jax.Array:
    rem = (-x.shape[0]) % multiple
    if rem:
        x = jnp.concatenate([x, jnp.zeros((rem,), x.dtype)])
    return x


def padded_len(size: int, n: int) -> int:
    """Length of the flat vector after padding for an ``n``-way shard: the
    single source of truth for the ZeRO-1 pad multiple (``n * LANE``)."""
    return size + ((-size) % (n * LANE))


def _leaf_padded(size: int) -> int:
    return size + ((-size) % LANE)


def flat_len(tree) -> int:
    """Length of :func:`flat_ravel`'s output for ``tree`` (sum of
    per-leaf LANE-padded sizes) — computable from shapes alone."""
    return sum(
        _leaf_padded(int(np.prod(l.shape)) if l.shape else 1)
        for l in jax.tree.leaves(tree)
    )


def flat_ravel(tree):
    """Lane-aligned ``ravel_pytree`` (module docstring rule 2): each leaf
    is raveled and zero-padded to a LANE multiple before concatenation, so
    every leaf lives at a LANE-aligned offset of the flat vector and the
    unravel is whole-row slice+reshape on the ``[rows, LANE]`` view.

    Returns ``(flat, unravel)`` like ``ravel_pytree``; the elementwise goo
    family is indifferent to the interleaved zero padding (padded slots
    carry zero grads, so their state stays zero). THE single flat-layout
    authority — ``train/convert.py`` imports it for conversions.

    Every per-leaf slice/ravel is fenced with ``optimization_barrier``:
    XLA's algebraic simplifier otherwise canonicalises a leaf extraction
    ``reshape(slice(flat), leaf_shape)`` into ``slice(reshape(flat,
    [total/k, k]))`` with the leaf's own trailing dim as the minor dim —
    and for a narrow leaf (the MoE router's ``[768, 8]``) the TPU layout
    pass tile-pads that whole-vector intermediate ``LANE/k``×: the
    measured 20.6 GB round-3 compile-OOM at 322M params. The barrier
    pins the rewrite at the leaf boundary, where the worst
    materialisation is the leaf itself. (Found and verified with the
    v5e-8 AOT compile check, ``compile_multichip.py``.)
    """
    leaves, treedef = jax.tree.flatten(tree)
    parts = []
    for leaf in leaves:
        flat = lax.optimization_barrier(jnp.ravel(leaf))
        pad = (-flat.shape[0]) % LANE
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        parts.append(flat)
    flat_all = (
        jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.float32)
    )

    def unravel(v):
        out, off = [], 0
        for leaf in leaves:
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            seg = lax.optimization_barrier(
                lax.slice(v, (off,), (off + size,))
            )
            out.append(seg.reshape(leaf.shape).astype(leaf.dtype))
            off += _leaf_padded(size)
        return jax.tree.unflatten(treedef, out)

    return flat_all, unravel


def shard_of(flat: jax.Array, axis: str) -> jax.Array:
    """This device's contiguous shard of a flat vector (pad to
    ``axis_size * LANE``, slice by axis index) — THE shard choreography
    every ZeRO-1 layout shares; ``train/convert.py``'s cross-tier
    conversion imports it so checkpoint conversion can never drift from
    the update path."""
    n = lax.axis_size(axis)
    padded = _pad_to(flat, n * LANE)
    s = padded.shape[0] // n
    return lax.dynamic_slice(padded, (lax.axis_index(axis) * s,), (s,))


def sharded(
    tx: optax.GradientTransformation,
    axis: str,
    *,
    mean_grads: bool = True,
    comm=None,
) -> optax.GradientTransformation:
    """Wrap ``tx`` so its state lives sharded along mesh ``axis``.

    PRECONDITION: ``tx`` must be **elementwise** — its update for element i
    may depend only on grad/param/state element i (true of the goo family:
    SGD/momentum/Nesterov/Adam/AdamW, and of elastic_average). A
    transformation using *global* statistics (``optax.clip_by_global_norm``,
    adafactor's row/column factors, …) would compute them over each
    device's 1/N shard and silently produce inconsistently-scaled update
    blocks. Wrap such transforms OUTSIDE the sharded step, or compute their
    statistics with explicit collectives first.

    Both ``init`` and ``update`` must be called inside ``shard_map`` over
    ``axis``:

    - ``init(params)`` (params replicated) → per-device state = ``tx.init``
      of this device's contiguous shard of the flat parameter vector.
    - ``update(grads, state, params)`` takes the *local, unreduced* grads:
      the cross-replica sum rides the reduce-scatter (one collective doing
      both the reduction and the sharding — cheaper than psum-then-slice).
      Returns full (replicated) updates via all-gather, optax-style.

    ``mean_grads=True`` averages (divides the scattered sum by the axis
    size) — the sync-DP convention; ``False`` sums, matching the
    reference's gradient-push accumulation semantics.

    ``comm`` (ISSUE 9): a :class:`mpit_tpu.train.grad_sync.GradSync`
    delegating the three communication choreography points — grad
    reduce-scatter, param shard selection, update all-gather — to the
    selected wire tier (bucketed Pallas ring / quantized ring). ``None``
    keeps the stock XLA collectives, byte-for-byte the seed behavior.
    Every GradSync mode produces the SAME contiguous shard layout as
    :func:`shard_of`, so optimizer state (and checkpoints) are
    interchangeable across ``comm`` choices.
    """

    def init(params):
        flat, _ = flat_ravel(params)
        return tx.init(shard_of(flat, axis))

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("sharded(tx) requires params")
        n = lax.axis_size(axis)
        flat_g, unravel = flat_ravel(grads)
        size = flat_g.shape[0]
        # ``grad_sync`` and ``zero1_gather`` are the scope names a device
        # trace is summed by: the two collectives apart from the update
        # between them, whatever opcode the compiler or the ring tier
        # gives them.
        with jax.named_scope("grad_sync"):
            if comm is None:
                # reduce-scatter: each device receives the summed shard
                # it owns. [rows, LANE] view keeps the lowering's minor
                # dim lane-aligned (see module docstring: the 1-D form
                # tile-pads 16x at 300M+).
                g2 = _pad_to(flat_g, n * LANE).reshape(-1, LANE)
                g_shard = C.reduce_scatter(g2, axis).reshape(-1)
            else:
                g_shard = comm.scatter_grads(flat_g)
            if mean_grads:
                g_shard = g_shard / n
        flat_p, _ = flat_ravel(params)
        p_shard = shard_of(flat_p, axis) if comm is None else comm.param_shard(flat_p)
        u_shard, new_state = tx.update(g_shard, state, p_shard)
        with jax.named_scope("zero1_gather"):
            if comm is None:
                # invariant gather: updates are identical everywhere and
                # typed replicated, so they can exit shard_map with a
                # replicated spec.
                flat_u = C.allgather(
                    u_shard.reshape(-1, LANE), axis, tiled=True,
                    invariant=True,
                ).reshape(-1)[:size]
            else:
                flat_u = comm.gather_updates(u_shard, size)
        # Barrier before unravel: without it, XLA's algebraic simplifier
        # rewrites a leaf extraction (1-D slice + reshape to e.g. the MoE
        # router's [768, 8]) into a reshape of the WHOLE flat vector to
        # [total/8, 8], whose 8-wide minor dim the TPU layout pass
        # tile-pads 16x — a 20.6 GB allocation at 322M params (the round-3
        # compile-OOM, reproduced and fixed via the v5e-8 AOT check).
        # Materializing the 1-D flat vector here costs its plain size once.
        flat_u = lax.optimization_barrier(flat_u)
        return unravel(flat_u), new_state

    return optax.GradientTransformation(init, update)


def grouped_state_specs(
    tx: optax.GradientTransformation,
    params,
    n: int,
    data_axis: str,
    axes,
):
    """:func:`state_partition_specs` for one *placement group* of a
    multi-axis tier: the flat per-shard vectors live per coordinate of
    ``axes`` (e.g. ``('pipe', 'model', 'data')``), so the vector-leaf spec
    is ``P(axes)`` instead of ``P(data_axis)``. Shared by the per-group
    ZeRO-1 tiers (``parallel.pp`` / ``parallel.threed`` / ``parallel.ep``)
    — one place to fix the remapping."""
    from jax.sharding import PartitionSpec as _P

    specs = state_partition_specs(tx, params, n, data_axis)
    return jax.tree.map(
        lambda s: _P(tuple(axes)) if s == _P(data_axis) else s, specs
    )


def state_partition_specs(
    tx: optax.GradientTransformation, params, n: int, axis: str
):
    """PartitionSpecs for the sharded state of ``tx`` over ``n`` devices.

    Per-shard vector leaves → ``P(axis)``; scalar leaves (step counts etc.,
    identical on every device) → replicated. Computed by abstract-evaluating
    one device's ``tx.init`` on a zero shard — no mesh required.
    """

    def one_device_init(p):
        leaves = jax.tree.leaves(p)
        dtype = jnp.result_type(*(l.dtype for l in leaves)) if leaves else jnp.float32
        return tx.init(jnp.zeros((padded_len(flat_len(p), n) // n,), dtype))

    shapes = jax.eval_shape(one_device_init, params)
    return jax.tree.map(
        lambda l: P(axis) if getattr(l, "ndim", 0) >= 1 else P(), shapes
    )


# Compiled-update cache for the host-level helpers: a fresh shard_map per
# call would retrace/recompile every step (observed: 200 eager steps taking
# minutes on the fake mesh). Keyed by (mesh, axis, tx identity, arg shapes)
# — so CONSTRUCT THE TRANSFORMATION ONCE AND REUSE IT across steps; a fresh
# goo(...) per call defeats the cache (optax transformations carry their
# config in closures, leaving id() as the only usable identity). Bounded
# LRU so per-call construction degrades to recompilation, not a leak.
_COMPILED: OrderedDict = OrderedDict()
_COMPILED_MAX = 32


def _cache_key(world, tx, axis, *trees):
    shapes = tuple(
        (jax.tree_util.tree_structure(t) if t is not None else None,
         tuple((l.shape, str(l.dtype)) for l in jax.tree.leaves(t)))
        for t in trees
    )
    return (world.mesh, id(tx), axis, shapes)


def sharded_init(
    world, tx: optax.GradientTransformation, params, *, axis: str = "data"
):
    """Host-level: build optimizer state sharded along ``axis`` of
    ``world``'s mesh (params replicated in)."""
    stx = sharded(tx, axis)
    specs = state_partition_specs(tx, params, world.axis_size(axis), axis)
    return world.shard_map(stx.init, in_specs=P(), out_specs=specs)(params)


def sharded_update(
    world,
    tx: optax.GradientTransformation,
    grads,
    state,
    params,
    *,
    axis: str = "data",
):
    """Host-level: one sharded update step on a *global* (replicated) grad.

    Semantics: apply ``tx`` to exactly the given grads (the reduce-scatter
    sums N replicated copies; the default ``mean_grads`` divides them back).
    The in-jit training step should use :func:`sharded` directly with local
    per-device grads instead — that is the bandwidth-efficient path.

    Returns ``(updates, new_state)`` with updates replicated, optax-style.
    """
    key = _cache_key(world, tx, axis, grads, params)
    f = _COMPILED.get(key)
    if f is None:
        stx = sharded(tx, axis, mean_grads=True)
        specs = state_partition_specs(tx, params, world.axis_size(axis), axis)
        f = jax.jit(
            world.shard_map(
                stx.update, in_specs=(P(), specs, P()), out_specs=(P(), specs)
            )
        )
        _COMPILED[key] = f
        while len(_COMPILED) > _COMPILED_MAX:
            _COMPILED.popitem(last=False)
    else:
        _COMPILED.move_to_end(key)
    return f(grads, state, params)
