"""Batch sharding and host→device prefetch.

The reference's input pipeline is synchronous Torch dataset loading inside
the training loop (SURVEY.md §4.2 "data load + preprocess"). TPU-natively,
input must overlap with device compute or it becomes the bottleneck
(HBM-fed cores starve on host IO):

- :func:`shard_batch` lays a global host batch out across the mesh's data
  axis (device i gets rows ``[i·B/N, (i+1)·B/N)``) as one sharded
  ``jax.Array`` — the SPMD analogue of each worker rank loading its own
  partition.
- :class:`Prefetcher` is a two-stage pipeline (ISSUE 2 tentpole): a
  multi-thread **host stage** (pull + decode/transform, ``host_workers``
  threads) feeding a single ordered **device stage** (``device_put``),
  keeping up to ``depth`` batches in flight on device so step N's compute
  overlaps step N+1's host work and transfer. PR 1's ``prefetch_wait``
  spans showed the single-thread version serializing host decode against
  device dispatch — the app-path gap's second component next to the
  blocking metric fences (train/loop.py).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Iterator

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from mpit_tpu import obs


def shard_batch(world, batch, *, axis: str = "data", spec: P | None = None):
    """Place a global host batch sharded over the mesh.

    Default layout: leading dimension sharded along ``axis``. Pass ``spec``
    for multi-dim layouts (e.g. ``P("data", "seq")`` shards batch over
    data and sequence over the seq axis — the context-parallel input).
    Sharded dims must divide by their axis sizes. Returns a pytree of
    committed ``jax.Array``s.
    """
    sharding = NamedSharding(world.mesh, spec if spec is not None else P(axis))

    def put(x):
        x = np.asarray(x)
        for dim, name in enumerate(sharding.spec):
            if name is None:
                continue
            if dim >= x.ndim:
                raise ValueError(
                    f"spec {sharding.spec} names dim {dim} but batch leaf "
                    f"has only {x.ndim} dims (shape {x.shape})"
                )
            names = (name,) if isinstance(name, str) else name
            size = 1
            for a in names:
                size *= world.axis_size(a)
            if x.shape[dim] % size:
                raise ValueError(
                    f"batch dim {dim} ({x.shape[dim]}) not divisible by "
                    f"{names}={size}"
                )
        return jax.device_put(x, sharding)

    return jax.tree.map(put, batch)


class _Failure:
    """Reorder-buffer slot holding the exception that produced it, so it
    surfaces to the consumer *in sequence order* — after every earlier
    batch was delivered, exactly like the single-thread pipeline."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class Prefetcher:
    """Pipelined host→device prefetch of sharded device batches.

    Two stages:

    - **host stage** — ``host_workers`` threads pull items from the
      source iterator (one at a time, under a lock that also assigns the
      sequence index) and run ``host_transform`` (decode / augment /
      slicing) in parallel, outside the lock. This is the CPU-bound work
      that serialized against device dispatch when it shared one thread.
    - **device stage** — a single thread reassembles sequence order from
      the host stage's reorder buffer and runs ``transform`` (default:
      :func:`shard_batch` over ``axis``). ``device_put`` stays ordered
      and single-threaded so device buffers land in iteration order.

    ``depth`` bounds how many device batches sit ready ahead of the
    consumer. Passing ``max_depth > depth`` (opt-in; the default keeps
    the buffer fixed at ``depth``) lets the bound grow adaptively while
    the consumer keeps blocking in ``__next__`` (the time inside the
    loop's ``prefetch_wait`` span) and shrink back to ``depth`` when it
    never blocks — HBM is only spent on pipeline slack that observably
    buys wall clock.

    Semantics preserved from the single-thread version: iteration order;
    exceptions (source or either transform) surface on the consumer's
    ``__next__`` after all earlier batches were delivered; ``close()``
    (or exhaustion) joins the threads; context-manager use. Contract:
    batches must be OWNED buffers — ``device_put``'s host-side read has
    no completion signal (even ``block_until_ready`` can return before
    the transfer thread reads the buffer), so a source or
    ``host_transform`` that recycles yielded memory (e.g. the native
    slot ring with ``copy=False``) cannot be made safe here — which is
    why the native loader copies at its boundary by default.
    """

    _SENTINEL = object()

    def __init__(
        self,
        world,
        it: Iterator,
        *,
        axis: str = "data",
        depth: int = 2,
        transform: Callable | None = None,
        host_transform: Callable | None = None,
        host_workers: int = 1,
        max_depth: int | None = None,
        adaptive: bool | None = None,
    ):
        """``transform`` overrides the host→device placement (default:
        ``shard_batch`` over ``axis``) — the parallel tiers pass their own
        slice-and-shard (custom PartitionSpecs) and get prefetch for
        free. ``host_transform`` runs on the (possibly multi-thread) host
        stage BEFORE placement; put decode/augment/slice work there so
        ``host_workers > 1`` can overlap it."""
        if depth < 1:
            raise ValueError(f"Prefetcher: depth must be >= 1, got {depth}")
        if host_workers < 1:
            raise ValueError(
                f"Prefetcher: host_workers must be >= 1, got {host_workers}"
            )
        self._it = it
        self._host_tf = host_transform
        self._device_tf = transform or (
            lambda b: shard_batch(world, b, axis=axis)
        )
        self._depth0 = depth
        self._depth = depth
        # Adaptive growth is OPT-IN: max_depth defaults to depth (fixed
        # buffer, the legacy behavior — a bare Prefetcher(world, it)
        # must not grow its device footprint on callers sized against
        # depth=2; round-6 review). hardened_loop passes max_depth
        # explicitly to enable it.
        self._max_depth = max(max_depth or depth, depth)
        self._adaptive = (
            self._max_depth > depth if adaptive is None else adaptive
        )
        self._host_workers = host_workers

        self._stop = threading.Event()
        self._cond = threading.Condition()
        self._src_lock = threading.Lock()
        # Host-stage state (``_cond`` guards everything below).
        self._staged: dict[int, object] = {}  # idx -> host batch | _Failure
        self._next_alloc = 0  # next sequence index to hand a host worker
        self._src_done = False
        self._end: int | None = None  # first index that will never exist
        # Device-stage / consumer state.
        self._next_idx = 0  # next index the device stage will place
        self._out: deque = deque()
        self._exc: BaseException | None = None
        self._finished = False  # consumer saw the sentinel
        # Adaptive-depth bookkeeping (consumer thread only).
        self._served = 0
        self._blocked = 0

        self._threads = [
            threading.Thread(
                target=self._host_worker, daemon=True, name=f"prefetch-host-{i}"
            )
            for i in range(host_workers)
        ]
        self._threads.append(
            threading.Thread(
                target=self._device_worker, daemon=True, name="prefetch-device"
            )
        )
        for t in self._threads:
            t.start()

    # -- host stage ---------------------------------------------------------
    def _inflight_cap(self) -> int:
        # Host stage may run ahead of device placement by the CURRENT
        # output depth plus one item per HOST worker — enough to keep
        # every stage busy, without buffering max_depth batches of host
        # RAM while the adaptive depth sits at its floor (round-6
        # review: image batches are ~100 MB; the cap must track the
        # depth the pipeline has actually earned, and the device-stage
        # thread holds no host batch of its own).
        return self._depth + self._host_workers

    def _host_worker(self) -> None:
        while True:
            with self._src_lock:
                if self._src_done or self._stop.is_set():
                    return
                idx = self._next_alloc
                try:
                    item = next(self._it)
                except StopIteration:
                    self._src_done = True
                    with self._cond:
                        self._end = idx
                        self._cond.notify_all()
                    return
                except BaseException as e:
                    # A failing source ends the sequence at idx: earlier
                    # batches deliver, then the consumer sees the error.
                    self._src_done = True
                    with self._cond:
                        self._staged[idx] = _Failure(e)
                        self._end = idx + 1
                        self._cond.notify_all()
                    return
                self._next_alloc = idx + 1
            # Backpressure OUTSIDE the source lock: holding one pulled
            # item per worker while the device stage catches up.
            with self._cond:
                while (
                    not self._stop.is_set()
                    and idx - self._next_idx >= self._inflight_cap()
                ):
                    self._cond.wait(0.1)
                if self._stop.is_set():
                    return
            try:
                if self._host_tf is not None:
                    with obs.span("prefetch_host"):
                        item = self._host_tf(item)
            except BaseException as e:
                with self._src_lock:
                    self._src_done = True  # stop pulling past the failure
                with self._cond:
                    self._staged[idx] = _Failure(e)
                    if self._end is None or self._end > idx + 1:
                        self._end = idx + 1
                    self._cond.notify_all()
                return
            with self._cond:
                self._staged[idx] = item
                self._cond.notify_all()

    # -- device stage -------------------------------------------------------
    def _device_worker(self) -> None:
        while True:
            with self._cond:
                while (
                    not self._stop.is_set()
                    and self._next_idx not in self._staged
                    and (self._end is None or self._next_idx < self._end)
                ):
                    self._cond.wait(0.1)
                if self._stop.is_set():
                    return
                if (
                    self._next_idx not in self._staged
                    and self._end is not None
                    and self._next_idx >= self._end
                ):
                    self._out.append(self._SENTINEL)
                    self._cond.notify_all()
                    return
                idx = self._next_idx
                item = self._staged.pop(idx)
            if isinstance(item, _Failure):
                self._finish_with(item.exc)
                return
            try:
                with obs.span("prefetch_device_put"):
                    dev = self._device_tf(item)
            except BaseException as e:
                self._finish_with(e)
                return
            with self._cond:
                while (
                    not self._stop.is_set() and len(self._out) >= self._depth
                ):
                    self._cond.wait(0.1)
                if self._stop.is_set():
                    return
                self._out.append(dev)
                self._next_idx = idx + 1
                self._cond.notify_all()

    def _finish_with(self, exc: BaseException) -> None:
        """Deliver the sentinel carrying ``exc`` and release every other
        stage: host workers blocked in backpressure must not outlive the
        pipeline once nothing will ever drain them."""
        with self._cond:
            self._exc = exc
            self._out.append(self._SENTINEL)
            self._stop.set()
            self._cond.notify_all()

    # -- consumer -----------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._finished:
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        waited = 0.0
        with self._cond:
            while not self._out:
                if self._stop.is_set():
                    # close()d under the consumer: end the stream rather
                    # than block forever on a pipeline that was torn down.
                    self._finished = True
                    raise StopIteration
                t0 = time.perf_counter()
                self._cond.wait(0.1)
                waited += time.perf_counter() - t0
            item = self._out.popleft()
            self._cond.notify_all()
        if item is self._SENTINEL:
            self._finished = True
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        if self._adaptive:
            self._adapt(waited)
        return item

    def _adapt(self, waited: float) -> None:
        """Grow ``depth`` toward ``max_depth`` while the consumer keeps
        blocking (>100µs) in ``__next__`` — i.e. while the loop's
        ``prefetch_wait`` span is observably nonzero — and shrink back
        toward the configured floor when it never blocks."""
        self._served += 1
        if waited > 1e-4:
            self._blocked += 1
        if self._served < 8:
            return
        blocked, self._served, self._blocked = self._blocked, 0, 0
        with self._cond:
            if blocked >= 4 and self._depth < self._max_depth:
                self._depth += 1
                self._cond.notify_all()  # device stage may be waiting on depth
            elif blocked == 0 and self._depth > self._depth0:
                self._depth -= 1

    @property
    def depth(self) -> int:
        """Current (possibly adapted) output-queue bound."""
        return self._depth

    def close(self):
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
