"""What the serving engine asks of a model.

``Engine`` used to be written to GPT-2: its configuration class in the
constructor's signature, ``GPT2(cfg).apply`` inside the jitted steps, a
cache row of ``heads x head_dim``. It now takes a :class:`ServeModel` and
asks it, and nothing else, for what differs between families:

- the **cache row layout** of a layer (:meth:`ServeModel.cache_layout`):
  the widths of the two buffers a page pool keeps a layer, and their
  dtype. GPT-2 caches a key and a value of ``heads x head_dim`` each; a
  latent-attention model caches one latent row all heads share and the
  rotary part of its key;
- the **forward through the cache**: embed, then per layer attention
  given that layer's cache handle and the MLP, then the final norm
  (:meth:`ServeModel.forward_paged`). It returns the hidden states the
  head samples from, the layers' updated buffers, and whatever the
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
from typing import Any

__all__ = ["CacheLayout", "ServeModel", "as_serve_model"]


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """A layer's cache row: ``k_width`` and ``v_width`` values a cached
    position in the pool's two seats (what they hold is the family's
    business), ``dtype`` unless the engine pins another, and for an int8
    pool the scale columns a row."""

    k_width: int
    v_width: int
    num_layers: int
    dtype: Any
    scale_width: int = 1


class ServeModel:
    """Base of the serving models; a family overrides what it has."""

    family = ""
    cfg: Any = None
    # True for a model whose forward skips the rows ``row_valid`` marks
    # as no tokens; the engine then traces and passes that mask.
    skips_invalid_rows = False

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
                      *, return_hidden, row_valid=None):
        """Page pool: ``(out, (k, v), aux)``. ``row_valid`` [B, T] marks
        the rows that are real tokens (a family may skip the others)."""
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
