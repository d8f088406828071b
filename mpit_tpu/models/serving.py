"""What the serving engine asks of a model.

``Engine`` used to be written to GPT-2: its configuration class in the
constructor's signature, ``GPT2(cfg).apply`` inside the jitted steps, a
cache row of ``heads x head_dim``. It now takes a :class:`ServeModel` and
asks it, and nothing else, for what differs between families:

- the **cache layout** (:meth:`ServeModel.cache_layout`): for each of
  the model's layers, whether it keeps pages (how many seats a cached
  position has in that layer's pool, and their widths: two everywhere
  but where a sparse-attention layer keeps an index key beside its
  latent and its rotary key) or a fixed state a slot (the shapes and
  dtypes of what a sequence keeps between steps). GPT-2 caches a key and
  a value of ``heads x head_dim`` each; a latent-attention model caches
  one latent row all heads share and the rotary part of its key; a
  linear-attention layer keeps a matrix a head and the tail of its
  convolution, whatever the sequence's length;
- the **forward through the cache**: embed, then per layer attention
  given that layer's cache handle and the MLP, then the final norm
  (:meth:`ServeModel.forward_paged`). It returns the hidden states the
  head samples from, the layers' updated buffers (pages, and the state
  pool where the layout has one), and whatever the
  family counts a step (``aux``; ``None`` for a family that counts
  nothing, and the step's outputs are then as they always were);
- the **head** (:meth:`ServeModel.head_table`): the ``[vocab, d]`` table
  the blocked sampler streams;
- the **parameter tree's placement** and what the family cannot do yet
  (:meth:`ServeModel.check_supported`, which raises at construction).

A model's configuration object may be handed to ``Engine`` in the model's
place when it knows its serving model (``serve_model()``):
``Engine(GPT2Config.small(), params)`` still works, and the engine names
no family.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import numpy as np

__all__ = ["CacheLayout", "PageLayer", "StateLayer", "ServeModel",
           "as_serve_model"]


@dataclasses.dataclass(frozen=True)
class PageLayer:
    """A layer that keeps pages: ``widths`` says how many seats a cached
    position has in this layer's pool (two or three) and the values it
    keeps in each, under one block table and one lifetime. What a seat
    holds is the family's business; the pool calls the first two ``k``
    and ``v`` and a third ``x``. ``window`` is the lifetime: 0 keeps
    every position of the sequence; ``w`` keeps the last ``w`` (a query at
    position ``t`` reads ``t - w < s <= t``), and the pages behind them go
    back to the pool of the window layers, which has its own block
    table."""

    widths: tuple
    window: int = 0

    def __post_init__(self):
        if len(self.widths) not in (2, 3):
            raise ValueError(
                f"a page layer keeps two or three seats, not {self.widths}")

    @property
    def k_width(self) -> int:
        return self.widths[0]

    @property
    def v_width(self) -> int:
        return self.widths[1]

    @property
    def x_width(self) -> int:
        """The third seat's width; 0 where the layer keeps two."""
        return self.widths[2] if len(self.widths) == 3 else 0


@dataclasses.dataclass(frozen=True)
class StateLayer:
    """A layer that keeps a fixed state a slot, whatever the sequence's
    length: ``buffers`` is ``((name, shape, dtype), ...)`` of what ONE
    slot keeps between steps; the pool holds each as ``[slots, *shape]``."""

    buffers: tuple

    def slot_bytes(self) -> int:
        return sum(math.prod(shape) * np.dtype(dtype).itemsize
                   for _, shape, dtype in self.buffers)


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """What a sequence keeps, a model layer at a time: ``layers[i]`` is a
    :class:`PageLayer` or a :class:`StateLayer`. ``dtype`` is the page
    rows' unless the engine pins another, ``scale_width`` the scale
    columns a row of an int8 pool."""

    layers: tuple
    dtype: Any
    scale_width: int = 1

    @property
    def page_layers(self) -> tuple:
        return tuple(l for l in self.layers if isinstance(l, PageLayer))

    @property
    def state_layers(self) -> tuple:
        return tuple(l for l in self.layers if isinstance(l, StateLayer))

    @property
    def window(self) -> int:
        """The window of the layers that keep a window of positions (one
        for all of them); 0 where every page layer keeps them all."""
        windows = {l.window for l in self.page_layers if l.window}
        if len(windows) > 1:
            raise ValueError(
                f"window layers of one model share a window, not {windows}")
        return windows.pop() if windows else 0

    @property
    def prefix_shareable(self) -> bool:
        """Whether pages mapped from another sequence's prefix are all
        the sequence needs: not where a layer's state at that boundary
        would have to be restored too, not yet where a layer
        keeps a third seat (untested with a mapped prefix), and not
        where a layer keeps a window (its pages of the prefix are
        gone)."""
        return not (self.state_layers or self.third_seats or self.window)

    def page_bytes(self, page_size: int, dtype, quantized: bool, *,
                   window: bool = False) -> int:
        """One page across every page-holding layer of one lifetime (the
        layers that keep every position, or with ``window`` those that
        keep a window) and every seat it keeps, as the pool stores it (an
        int8 pool: payload and float32 scales a seat)."""
        item = 1 if quantized else np.dtype(dtype).itemsize
        scales = 4 * self.scale_width if quantized else 0
        return page_size * sum(
            w * item + scales for l in self.page_layers
            if bool(l.window) == window for w in l.widths)

    @property
    def third_seats(self) -> bool:
        """Whether any page layer keeps a third seat."""
        return any(l.x_width for l in self.page_layers)

    def state_slot_bytes(self) -> int:
        """What one slot keeps in the state pool, every layer."""
        return sum(l.slot_bytes() for l in self.state_layers)


class ServeModel:
    """Base of the serving models; a family overrides what it has."""

    family = ""
    cfg: Any = None
    # True for a model whose forward skips the rows ``row_valid`` marks
    # as no tokens; the engine then traces and passes that mask.
    skips_invalid_rows = False

    @property
    def keeps_pages_alone(self) -> bool:
        """Whether pages are all a slot keeps from one chunk of its
        prompt to the next: a later chunk then needs nothing of an
        earlier one but its rows in the pool, and the two may share a
        step (``Engine.spare_seats``). Not where a layer carries a state
        a slot, which a step reads once and leaves once."""
        return not self.cache_layout().state_layers

    # -- geometry ----------------------------------------------------------
    def cache_layout(self) -> CacheLayout:
        raise NotImplementedError

    def kv_row_bytes(self, dtype) -> float:
        """Bytes of one cached position in ONE seat of one layer, at the
        pool's stored width (the unit of the decode-bytes model)."""
        raise NotImplementedError

    # -- what the family cannot do yet ----------------------------------------
    def check_supported(self, **modes) -> None:
        """Raise ``ValueError`` for an engine mode this family lacks.
        ``modes``: ``tp``, ``kv_dtype``, ``weights_dtype``, ``spec_k``,
        ``host_pages``."""

    def check_shipment(self) -> None:
        """Raise if cache rows of this family cannot be exported."""

    def check_preemption(self) -> None:
        """Raise if a live slot of this family cannot be evicted and
        resumed (what it keeps beside pages would be lost)."""

    def rows_attended(self, cached):
        """Of ``cached`` rows a slot holds (an integer array), those a
        decode tick's attention reads a layer: all of them unless the
        family's attention chooses (the ``decode`` span's ``rows_read``)."""
        return cached

    # -- the injected kernels -------------------------------------------------
    def with_decode_attention(self, *, block_k: int, interpret,
                              page_size: int) -> "ServeModel":
        """This model with its cache attention through the kernel path
        (``interpret``: None = kernel on a TPU and the lax twin elsewhere,
        True = the Pallas interpreter). ``block_k`` is the engine's tile
        of cache positions; a family whose kernel tiles otherwise says so
        in ``decode_block_k`` of what it returns."""
        raise NotImplementedError

    def attention_tiling(self, t_q: int, **how) -> dict:
        """What the spans of a step of ``t_q`` query rows say about the
        injected attention kernel (``how``: the engine's ``page_size``,
        ``kv_dtype``, ``tp``): static per compiled step. Nothing for a
        family that does not say."""
        return {}

    def with_quant_matmul(self, fn) -> "ServeModel":
        raise NotImplementedError

    # -- the forward ------------------------------------------------------------
    def forward_paged(self, params, tokens, cache, block_tables, write_valid,
                      *, return_hidden, row_valid=None, slot_index=None):
        """``(out, (k, v, state), aux)``: the page buffers and the state
        pool as the step leaves them (``cache.state`` where the layout
        has no state layer); a family whose layout has third seats
        returns ``(k, v, state, x)``, ``x`` as ``cache.x`` holds it. ``row_valid`` [B, T] marks the rows that are
        real tokens (a family may skip the others). ``slot_index`` [B]
        says which slot's seat in the state pool each batch row reads
        and leaves (an index past the slots: a padding row, nothing
        written); None where row ``i`` is slot ``i``."""
        raise NotImplementedError

    def head_table(self, params):
        """The ``[vocab, d_model]`` output table."""
        raise NotImplementedError

    def place(self, params):
        """The parameter tree as the steps take it (one chip: as given)."""
        return params


def as_serve_model(obj) -> ServeModel:
    """``obj`` itself when it is a serving model, else the serving model
    its configuration names (``obj.serve_model()``)."""
    if isinstance(obj, ServeModel):
        return obj
    make = getattr(obj, "serve_model", None)
    if make is None:
        raise TypeError(
            f"{type(obj).__name__} is neither a ServeModel nor a "
            "configuration with serve_model()")
    return make()
