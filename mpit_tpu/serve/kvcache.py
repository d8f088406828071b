"""The serving engine's KV cache: a page pool and its host bookkeeping.

HBM cost should scale with the tokens that exist, not with ``slots ×
max_len``: a slot holding 30 cached tokens must not pay for 1024, slot
count must not be the hard concurrency ceiling, and two requests sharing
a system prompt must not store identical K/V twice.
:class:`PagedKVCache` is a fixed pool of ``page_size``-token pages
indirected by a per-slot int32 block table: HBM scales with tokens
actually held, and a page mapped into two block tables IS prefix
sharing. The pool is held as the decode kernel reads it and as a step
can update it in place: ONE BUFFER PER LAYER (``k`` and ``v`` are tuples
of ``num_layers`` arrays), each ``[num_pages, page_size,
heads*head_dim]`` — rows packed head-major to full 128-lane tiles, so a
cached token costs its logical bytes on the device and the kernel DMAs
tiles straight out of the buffer. The jitted steps DONATE the cache:
each layer's buffer has one writer (the scatter of the new rows) and one
reader (that layer's kernel call) per step, and comes back as the same
memory. The device side stays dumb — pages are just rows, the pool never
moves — while :class:`PageAllocator` (pure host) owns the free list,
per-page refcounts, the rolling-hash prefix index and the copy-on-write
bookkeeping. ``lengths`` [slots] int32 is the single source of truth for
both the append position and the attention visibility mask (key ``j``
visible iff ``j <= lengths + t``): validity comes from ``lengths`` + the
mask, never from buffer contents, so a slot's history can never leak
into another request and freed pages are recycled without zeroing.

Under tensor parallelism each layer's buffer shards its packed head axis
over the TP axis (:func:`paged_cache_specs`) — each device holds its H/P
heads' rows, matching the Megatron column-sharded qkv layout
(``parallel.megatron``).

QUANTIZED pools (ISSUE 15). ``quantized=True`` on the alloc/specs
builders puts a :class:`~mpit_tpu.ops.kv_quant.QuantizedKV` (int8
payload + per-(row, head) f32 scale blocks, equal rank) in every K/V
seat (of every layer, in a pool): the page's scale block
``[page_size, H]`` lives in the same
pytree as its int8 rows, so the allocator, COW remaps, prefix sharing
and preemption carry scales with the pages WITHOUT learning about them
— a block-table indirection or page copy applies to both leaves. Bytes
per cached token drop ~2× vs bf16 (~4× vs f32); capacity at fixed HBM
roughly doubles (:func:`~mpit_tpu.ops.kv_quant.kv_wire_bytes_per_row`
is the sizing rule the roofline model and the bench capacity sweep
share). Recycled pages need no scale scrubbing for the same reason
rows need no zeroing: the mask defines validity, and every valid row's
scale was written by that row's own quantize-on-write.

THE STATE POOL (ISSUE 32). A model's layout
(:class:`~mpit_tpu.models.serving.CacheLayout`) says, a layer at a time,
whether the layer keeps pages or a fixed state a slot (a recurrent or
linear-attention layer: a matrix a head and the tail of its convolution,
whatever the sequence's length). ``k`` / ``v`` hold a buffer for each
page-holding layer; ``state`` holds, for each state-holding layer, a dict
of ``[slots, ...]`` buffers, donated and returned by the steps as the
pages are. A slot's seat is never cleared: a slot's first chunk starts
from zeros (the step sees its fill at 0) and rows that are no tokens
leave it as it was. Nothing moves a seat yet, so the allocator of such a
model finds a registered prefix, counts it (``prefix_hits_passed_up``)
and maps nothing shared: pages without the state at that boundary would
serve wrong tokens with no error.

TWO LIFETIMES (ISSUE 46). A page layer keeps every position of a
sequence, or the last ``window`` of them
(:class:`~mpit_tpu.models.serving.PageLayer`). The window layers have a
pool of their own, sized to what the slots can hold there at once
(:func:`window_slot_pages` a slot: ``window + prefill_chunk`` positions
and a page), and a block table of their own, which rides beside the
other one in the columns past ``pages_per_slot`` of the allocator's
``block_tables`` (``window_tables`` is that view): the same page index
means the same positions in both. A window page is mapped just before the
step that writes it and goes back to the window pool once every later
query's window has passed it (:meth:`PageAllocator.advance_window`);
admission promises a slot its most and refuses a request the window pool
could not serve. Such a layout maps no shared prefix: a window layer's
pages of the prefix are gone.

HOST TIER (ISSUE 20). HBM pages are the scarce resource; host RAM is
the next 10×. ``host_pages > 0`` gives the allocator a second page
namespace — host page ids are bookkeeping handles whose PAYLOADS live
on the engine as numpy pytrees (int8 payload + scale blocks travel as
one unit, like every other page move). Cold K/V spills there instead
of dying: a preempted victim's filled pages park (:meth:`park_pages`)
so resume restreams them instead of re-prefilling the whole feed, and
prefix-index entries whose last HBM reader frees migrate
(:meth:`spill_prefix_on_free`) so the index survives pool reclaim — a
later admit hits the host tier and the plan carries ``restream`` pairs
instead of shared-page mappings. Tiers never share refcounts: a host
hit maps only fresh private device pages (no COW reserve), and the
entry stays host-resident until :meth:`register_prefix` promotes it
back onto the re-prefilled device pages. All host grants are
all-or-nothing, exactly like admission; when the host tier is full,
:meth:`_reclaim_host` evicts the coldest host prefix entries (never
parked records) or the spill simply does not happen and behaviour
degrades to pre-tiering recompute.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from mpit_tpu.models.serving import as_serve_model
from mpit_tpu.ops.kv_quant import QuantizedKV, kv_wire_bytes_per_row

__all__ = [
    "PagedKVCache",
    "alloc_paged_cache",
    "paged_cache_specs",
    "PageAllocator",
    "AdmitPlan",
    "pages_needed",
    "window_slot_pages",
    "QuantizedKV",
    "kv_wire_bytes_per_row",
]


def _alloc_kv(shape, dtype, quantized, kw, scale_width):
    """One K (or V) buffer of a layer: a zeroed array, or the quantized
    pair (int8 payload + an f32 scale plane of ``scale_width`` columns a
    row — zero scales dequantize the zeroed payload to exact zeros)."""
    if not quantized:
        return jnp.zeros(shape, dtype, **kw)
    return QuantizedKV(
        q=jnp.zeros(shape, jnp.int8, **kw),
        scale=jnp.zeros(shape[:-1] + (scale_width,), jnp.float32, **kw),
    )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """The engine's decode state: one shared page pool + per-slot fill
    counts. A pytree, so it passes through jit/shard_map boundaries whole.

    ``k``/``v``: tuples of per-layer buffers, one for each layer that
    keeps pages (``num_layers`` of them where every layer does), each
    ``[num_pages, page_size, heads*head_dim]`` (a
    :class:`~mpit_tpu.ops.kv_quant.QuantizedKV` layer holds int8 rows of
    that shape plus a ``[num_pages, page_size, heads]`` f32 scale
    plane); ``lengths``: [slots] int32. Page ``p`` is row-block ``p`` of
    every layer's buffer. A jitted step that takes the cache donates
    it, so a ``PagedKVCache`` handed to a step is spent: use the one the
    step returns. The per-slot page→position mapping (the block table)
    is NOT device state — it lives host-side on the
    :class:`PageAllocator` and rides into each jitted step as a tiny
    [slots, pages_per_slot] int32 argument, so COW remaps and admissions
    never touch the pool.
    """

    k: Any
    v: Any
    lengths: Any
    # The state pool: for each state-holding layer of the model's layout
    # a dict of ``[slots, ...]`` buffers (what a recurrent layer keeps a
    # sequence whatever its length), donated and returned by the steps
    # as the pages are. Empty for a model whose layers all keep pages.
    state: Any = ()
    # The third seats: ``()`` where no page layer of the layout keeps
    # one (the leaves are then what they always were), else one entry a
    # page layer, a buffer ``[num_pages, page_size, width]`` under the
    # same block table as ``k`` / ``v``, or None for a two-seat layer.
    x: Any = ()

    def tree_flatten(self):
        return (self.k, self.v, self.lengths, self.state, self.x), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)

    @property
    def num_layers(self) -> int:
        return len(self.k)

    @property
    def num_pages(self) -> int:
        """Pages of the pool of the layers that keep every position."""
        return max(jax.tree.leaves(k)[0].shape[0] for k in self.k)

    @property
    def page_size(self) -> int:
        return self.k[0].shape[1]

    @property
    def slots(self) -> int:
        return self.lengths.shape[0]


def alloc_paged_cache(
    cfg,
    slots: int,
    num_pages: int,
    page_size: int,
    *,
    dtype=None,
    sharding=None,
    quantized: bool = False,
    window_pages: int = 0,
) -> PagedKVCache:
    """Allocate the zeroed page pool: per layer one K and one V buffer
    ``[num_pages, page_size, heads*head_dim]`` (and a third seat where
    the model's layout says the layer keeps one). HBM cost is ``num_pages ×
    page_size`` cache rows — chosen by budget, independent of ``slots``
    (the batch width) and of any per-slot ``max_len`` — and the packed
    rows are whole 128-lane tiles at GPT-2's widths, so the device holds
    the bytes ``.nbytes`` counts and no more. ``quantized`` (ISSUE 15):
    int8 pages + per-(row, head) scale planes ``[num_pages, page_size,
    heads]`` — a page costs ``page_size × kv_wire_bytes_per_row(H, Dh,
    "int8")`` bytes, so the same budget holds ~2× the pages of a bf16
    pool. A layer that keeps a window of positions gets ``window_pages``
    pages instead: the window layers' pool."""
    # The row a layer caches is the model's to say: GPT-2's K and V of
    # ``heads*head_dim`` each, a latent-attention model's shared latent
    # and its key's rotary part (``models.serving.CacheLayout``).
    layout = as_serve_model(cfg).cache_layout()
    dt = dtype or layout.dtype
    kw = {"device": sharding} if sharding is not None else {}
    seat = lambda l, width: _alloc_kv(
        (window_pages if l.window else num_pages, page_size, width), dt,
        quantized, kw, layout.scale_width)
    return PagedKVCache(
        k=tuple(seat(l, l.k_width) for l in layout.page_layers),
        v=tuple(seat(l, l.v_width) for l in layout.page_layers),
        x=tuple(seat(l, l.x_width) if l.x_width else None
                for l in layout.page_layers) if layout.third_seats else (),
        lengths=jnp.zeros((slots,), jnp.int32),
        # A recurrent layer's seats, a slot each: zeros, though a slot's
        # first chunk starts from zeros whatever its seat holds.
        state=tuple(
            {name: jnp.zeros((slots, *shape), sdt)
             for name, shape, sdt in l.buffers}
            for l in layout.state_layers
        ),
    )


def paged_cache_specs(
    axis: str = "model", *, num_layers: int, quantized: bool = False
) -> PagedKVCache:
    """TP PartitionSpecs for the pool: each layer's buffer shards its
    packed last axis (``[P, ps, H*Dh]`` is head-major, so a rank's
    ``H/P`` heads are one contiguous ``H/P * Dh`` slice of it, exactly
    its column-sharded qkv heads); pages are
    replicated-id shared state, lengths replicated. Quantized pools
    shard the scale plane ``[P, ps, H]`` on the same axis."""
    kv = P(None, None, axis)
    if quantized:
        kv = QuantizedKV(q=kv, scale=kv)
    return PagedKVCache(
        k=(kv,) * num_layers, v=(kv,) * num_layers, lengths=P()
    )


def pages_needed(prompt_len: int, max_new_tokens: int, page_size: int) -> int:
    """Pages a request can ever touch. The scheduler's write sequence
    (see ``serve.scheduler``): prefill writes positions
    ``0..prompt_len-1``; decode tick ``t`` appends ONE K/V row at
    position ``prompt_len + t - 1``, and the slot retires once
    ``len(tokens) == max_new_tokens`` — so the highest written position
    is ``prompt_len + max_new_tokens - 2`` and the fill watermark is
    ``prompt_len + max_new_tokens - 1``."""
    return -(-(prompt_len + max_new_tokens - 1) // page_size)


def window_slot_pages(window: int, chunk: int, page_size: int) -> int:
    """The most pages one slot holds in a window layer: a step of up to
    ``chunk`` rows reads from ``window - 1`` positions before its first
    row to its last, ``window + chunk - 1`` positions that start anywhere
    in a page."""
    return -(-(window + chunk) // page_size) + 1 if window else 0


@dataclasses.dataclass(frozen=True)
class AdmitPlan:
    """What :meth:`PageAllocator.admit` decided for one admission:
    ``shared_tokens`` prompt tokens whose K/V is already resident in
    mapped shared pages (0 = cold), which doubles as the slot's write
    floor — prefill K/V writes below it are masked (shared pages are
    immutable; the masked values would be bit-identical anyway)."""

    shared_tokens: int
    pages: tuple
    # ISSUE 20: ``(host_page, device_page)`` pairs to restream before
    # the first prefill chunk — non-empty iff the prefix hit landed on
    # a host-tier entry. The device pages are fresh private pages from
    # ``pages`` (position order); the engine restores the host payload
    # into them and the write floor masks re-writes exactly as for an
    # HBM hit.
    restream: tuple = ()

    @property
    def pages_granted(self) -> int:
        """Total pages this admission mapped (fresh + shared + COW
        reserve) — the slot-bind cost figure the request ledger records
        (ISSUE 16): a why-slow trace needs the grant size without
        holding the page tuple alive in every retained exemplar."""
        return len(self.pages)


def _prefix_hashes(tokens) -> list:
    """Rolling polynomial hash of every prefix: ``out[i]`` covers
    ``tokens[:i]``. One O(n) pass at admit/registration time; the
    prefix index is keyed on ``(n_tokens, out[n_tokens])`` and every
    hit is confirmed with a full token compare before any page is
    mapped (collision safety is correctness, not probability)."""
    h = 0
    out = [0] * (len(tokens) + 1)
    for i, t in enumerate(tokens):
        h = (h * 1000003 + int(t) + 1) & 0x7FFFFFFFFFFFFFFF
        out[i + 1] = h
    return out


@dataclasses.dataclass
class _PrefixEntry:
    tokens: tuple     # the exact prefix (full compare before mapping)
    pages: tuple      # pages covering it, in position order
    # ISSUE 20: which namespace ``pages`` indexes — "hbm" page ids are
    # device pool rows (refcounted, block-table mappable); "host" page
    # ids name engine-held numpy payloads and are NEVER refcounted or
    # mapped — a hit restreams them into fresh device pages instead.
    tier: str = "hbm"


@dataclasses.dataclass(frozen=True)
class _ParkedKV:
    """A preemption victim's spilled K/V: ``host_pages`` (position
    order) hold rows ``[0, fill)`` where ``fill`` was the victim's
    device fill watermark (``prompt + generated - 1``) at eviction.
    Resume restreams these instead of re-prefilling the feed."""

    host_pages: tuple
    fill: int


class PageAllocator:
    """Host-side page bookkeeping for one :class:`PagedKVCache`.

    - **Free-list reuse**: freed pages go back to the pool and are
      handed out again without zeroing (mask-defined validity).
    - **Prefix sharing**: once a request's prompt is fully prefilled,
      its page-aligned prefixes (and the full prompt, partial last page
      included) are registered in a rolling-hash index. A later admit
      whose prompt extends a registered prefix maps those pages
      (refcount++) instead of allocating + recomputing — full token
      compare before mapping, so a hash collision can never alias two
      prompts. Entries die with their pages (sharing is between
      temporally overlapping requests; the index holds no refs).
    - **Copy-on-write**: shared pages (refcount > 1) are immutable. Any
      write landing in one first copies it to a private page
      (:meth:`cow_before_write` returns the (src, dst) pair for the
      engine's device copy). Only the partially-filled last page of a
      shared prefix can ever be written while shared, and each mapper
      of one RESERVES a free page at admit — so a COW can never fail
      mid-decode; admission is the only capacity gate.
    - **No partial allocation**: :meth:`admit` checks the whole
      requirement (fresh pages + COW reserve) before taking anything;
      an insufficient pool returns ``None`` and the request stays
      queued.
    """

    def __init__(self, num_pages: int, page_size: int,
                 pages_per_slot: int, slots: int, *,
                 host_pages: int = 0, prefix_shareable: bool = True,
                 window: int = 0, window_pages: int = 0,
                 window_slot_pages: int = 0):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        if host_pages < 0:
            raise ValueError(f"host_pages must be >= 0, got {host_pages}")
        self.host_pages = host_pages
        # False for a model that keeps more than pages a sequence (the
        # layout says: ``CacheLayout.prefix_shareable``): pages mapped
        # from another sequence's prefix would come without the state at
        # that boundary, so a hit is found, counted and passed up.
        self.prefix_shareable = prefix_shareable
        self.num_pages = num_pages
        self.page_size = page_size
        self.pages_per_slot = pages_per_slot
        self.slots = slots
        # The second lifetime (0: none): layers that keep the last
        # ``window`` positions, ``window_pages`` pages in a pool of their
        # own, ``window_slot_pages`` of them a slot at the most. Their
        # table is the columns past ``pages_per_slot`` of the one array
        # the steps are handed.
        self.window = window
        self.window_pages = window_pages if window else 0
        self.window_slot_pages = window_slot_pages if window else 0
        self.block_tables = np.zeros(
            (slots, pages_per_slot * (2 if window else 1)), np.int32)
        self.window_tables = self.block_tables[:, pages_per_slot:]
        self.window_page_bytes = 0.0
        self.window_free: list[int] = list(range(self.window_pages))[::-1]
        # ISSUE 18: the engine binds its MemLedger + the wire bytes one
        # page occupies (all layers, K+V, target + draft pool) after
        # construction; every PHYSICAL page transition below then emits
        # a grant/free so ``kv_pages``/``kv_cow_reserve`` held-bytes
        # track ``pages_in_use``/``reserved`` exactly. None = unwired
        # (standalone allocator tests) — a no-op, not a crash.
        self.memledger = None
        self.page_bytes = 0.0
        # What a slot's seat in the state pool holds (0: no state pool);
        # granted with the slot's pages and freed with them.
        self.slot_state_bytes = 0
        self.reset()

    def reset(self) -> None:
        if self.memledger is not None and self.pages_in_use:
            # Return everything still held before the wipe — a reset
            # mid-ledger must conserve, not orphan bytes (ISSUE 18).
            self.memledger.free(
                "kv_pages", self.pages_in_use * self.page_bytes,
                kind="reset",
            )
            if self.reserved:
                self.memledger.free(
                    "kv_cow_reserve", self.reserved * self.page_bytes,
                    kind="reset",
                )
        if self.memledger is not None and self.slot_state_bytes:
            seated = len(self._slot_pages)
            if seated:
                self.memledger.free(
                    "kv_state", seated * self.slot_state_bytes, kind="reset"
                )
        if self.memledger is not None and self.window_pages_in_use:
            self.memledger.free(
                "kv_window_pages",
                self.window_pages_in_use * self.window_page_bytes,
                kind="reset",
            )
        self.window_free = list(range(self.window_pages))[::-1]
        self._slot_window: dict[int, dict[int, int]] = {}  # index -> page
        self._window_promised: dict[int, int] = {}  # slot -> its most
        self.window_pages_returned = 0  # behind the window, since reset
        self.block_tables[:] = 0
        self.refcount = np.zeros(self.num_pages, np.int64)
        self.free: list[int] = list(range(self.num_pages))[::-1]  # pop()=0 first
        self.reserved = 0  # free pages promised to future COW copies
        self._cow_reserve: dict[int, int] = {}  # page -> outstanding reserves
        self._slot_pages: dict[int, list[int]] = {}
        self._index: dict[tuple[int, int], _PrefixEntry] = {}
        self._page_keys: dict[int, set] = {}  # page -> index keys citing it
        # ISSUE 18 attribution inputs: who maps each slot and when each
        # prefix entry was last used — query-time ground truth for the
        # per-request/per-tenant roll-up and the eviction ranking.
        self._slot_owner: dict[int, tuple] = {}  # slot -> (rid, tenant)
        self._prefix_touch: dict[tuple, int] = {}  # index key -> tick
        # ISSUE 20 host tier: an independent page-id namespace. The
        # allocator owns the ids; the ENGINE owns the payloads (numpy
        # pytrees) and the ledger charges — so these structures carry
        # no ledger wiring of their own.
        self.host_free: list[int] = list(range(self.host_pages))[::-1]
        self._host_page_keys: dict[int, set] = {}  # host page -> keys
        self._parked: dict[Any, _ParkedKV] = {}    # rid -> parked record
        # Stats (the scheduler's kv gauges + bench's prefix_hit_rate).
        self.cow_copies = 0
        self.prefix_hits = 0
        self.prefix_hits_passed_up = 0  # found, not usable (a state pool)
        self.admissions = 0
        self.shared_tokens_total = 0
        self.prompt_tokens_total = 0
        self.host_prefix_hits = 0       # admits served from the host tier
        self.parked_spills = 0          # preemption victims parked to host
        self.spilled_prefix_entries = 0  # entries migrated HBM -> host
        self.promoted_entries = 0       # entries promoted host -> HBM

    # -- capacity -----------------------------------------------------------
    @property
    def free_pages(self) -> int:
        """Pages admittable RIGHT NOW (free minus the COW reserve)."""
        return len(self.free) - self.reserved

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self.free)

    @property
    def occupancy(self) -> float:
        return self.pages_in_use / self.num_pages

    @property
    def pages_shared(self) -> int:
        """Pages mapped by more than one slot — each unit here is one
        page of K/V a per-slot cache would have stored twice."""
        return int(np.maximum(self.refcount - 1, 0).sum())

    @property
    def hit_rate(self) -> float:
        """Fraction of admitted prompt tokens served from shared pages."""
        return (
            self.shared_tokens_total / self.prompt_tokens_total
            if self.prompt_tokens_total
            else 0.0
        )

    @property
    def host_pages_in_use(self) -> int:
        return self.host_pages - len(self.host_free)

    @property
    def host_resident_entries(self) -> int:
        """Prefix-index entries whose K/V lives only in host RAM."""
        return sum(1 for e in self._index.values() if e.tier == "host")

    def pages_for(self, prompt_len: int, max_new_tokens: int) -> int:
        return pages_needed(prompt_len, max_new_tokens, self.page_size)

    # -- the window layers' pool ----------------------------------------------
    @property
    def window_pages_in_use(self) -> int:
        return self.window_pages - len(self.window_free)

    @property
    def window_occupancy(self) -> float:
        return (self.window_pages_in_use / self.window_pages
                if self.window_pages else 0.0)

    @property
    def window_free_pages(self) -> int:
        """Window pages admittable RIGHT NOW: free, less what the slots
        already admitted may still take of their promise."""
        owed = sum(n - len(self._slot_window[s])
                   for s, n in self._window_promised.items())
        return len(self.window_free) - owed

    def window_pages_for(self, prompt_len: int, max_new_tokens: int) -> int:
        """The most pages a request holds in the window pool at once."""
        return min(self.window_slot_pages,
                   self.pages_for(prompt_len, max_new_tokens))

    def advance_window(self, slot: int, start: int, end: int) -> int:
        """Before a step of ``slot`` whose rows are positions ``start ..
        end - 1``: its first row reads back to ``first = start - window +
        1``, so map a window page for every page index from there to the
        last row's that has none, and give back the pages wholly before
        ``first`` (no later query's window reaches them: positions only
        grow). Returns the pages given back; 0 where no layer keeps a
        window. The promise of admission covers what is taken."""
        if not self.window:
            return 0
        ps, held = self.page_size, self._slot_window[slot]
        lo, hi = max(0, start - self.window + 1) // ps, (end - 1) // ps
        gone = [i for i in held if i < lo]
        for i in gone:
            self.window_free.append(held.pop(i))
            self.window_tables[slot, i] = 0
        took = [i for i in range(lo, hi + 1) if i not in held]
        if len(held) + len(took) > self._window_promised[slot]:
            raise RuntimeError(
                f"slot {slot} would hold {len(held) + len(took)} window "
                f"pages, promised {self._window_promised[slot]} "
                "(a step longer than the chunk the pool was sized for)")
        for i in took:
            held[i] = self.window_tables[slot, i] = self.window_free.pop()
        self.window_pages_returned += len(gone)
        if self.memledger is not None and len(took) != len(gone):
            owner, tenant = self._slot_owner.get(slot, (None, None))
            delta = (len(took) - len(gone)) * self.window_page_bytes
            if delta > 0:
                self.memledger.grant("kv_window_pages", delta, owner=owner,
                                     tenant=tenant, kind="window")
            else:
                self.memledger.free("kv_window_pages", -delta, owner=owner,
                                    kind="window")
        return len(gone)

    # -- admission ----------------------------------------------------------
    def _find_shared_prefix(self, prompt: tuple):
        """Longest registered prefix of ``prompt``, every length probed
        descending (O(plen) dict lookups — the index holds page-aligned
        boundaries plus full prompts, so this finds a partial-page entry
        even when ``prompt`` EXTENDS the registered prompt: the
        system-prompt case COW sharing exists for). Returns
        (n_tokens, entry) or (0, None)."""
        hashes = _prefix_hashes(prompt)
        for n in range(len(prompt), 0, -1):
            entry = self._index.get((n, hashes[n]))
            if entry is not None and entry.tokens == tuple(prompt[:n]):
                return n, entry
        return 0, None

    def admit(self, slot: int, prompt, max_new_tokens: int, *,
              owner=None, tenant=None, tick: int = 0):
        """Map pages for one request into ``slot``'s block table.

        Returns an :class:`AdmitPlan`, or ``None`` when the pool cannot
        hold the request right now (nothing is taken — the caller keeps
        it queued and retries after a retirement frees pages). Raises
        only on requests that could NEVER fit (caller bug — validated
        at submit). ``owner``/``tenant``/``tick`` annotate the memory
        ledger's grants (ISSUE 18) — attribution metadata only, never
        part of the capacity decision."""
        prompt = tuple(int(t) for t in prompt)
        need_total = self.pages_for(len(prompt), max_new_tokens)
        if need_total > self.pages_per_slot:
            raise ValueError(
                f"request needs {need_total} pages > pages_per_slot "
                f"{self.pages_per_slot} (prompt + max_new_tokens exceeds "
                f"the per-slot max_len)"
            )
        if need_total > self.num_pages:
            raise ValueError(
                f"request needs {need_total} pages but the pool holds "
                f"only {self.num_pages} (page_size {self.page_size}); "
                f"shrink prompt + max_new_tokens or grow num_pages"
            )
        shared_tokens, entry = self._find_shared_prefix(prompt)
        if entry is not None and not self.prefix_shareable:
            self.prefix_hits_passed_up += 1
            shared_tokens, entry = 0, None
        # ISSUE 20: a host-tier hit maps NO shared pages — the prefix
        # K/V restreams into fresh private pages (refcounts and COW
        # never span tiers), so the full page count is an "own" need
        # and no COW reserve is taken (restored pages have one mapper).
        host_hit = entry is not None and entry.tier == "host"
        shared_pages = (
            [] if host_hit else list(entry.pages) if entry is not None else []
        )
        partial_shared = bool(shared_tokens % self.page_size) and not host_hit
        own_needed = need_total - len(shared_pages)
        # The whole requirement up front — fresh pages now, plus one
        # reserved free page per mapped partial page (its future COW
        # copy) — or nothing: no partial allocation.
        if self.free_pages < own_needed + (1 if partial_shared else 0):
            return None
        if self.window:
            # Both lifetimes or nothing: the window pool must be able to
            # serve this slot's most beside what it has promised already.
            need_window = self.window_pages_for(len(prompt), max_new_tokens)
            if self.window_free_pages < need_window:
                return None
            self._window_promised[slot] = need_window
            self._slot_window[slot] = {}
        fresh = [self.free.pop() for _ in range(own_needed)]
        for p in fresh:
            self.refcount[p] = 1
        for p in shared_pages:
            self.refcount[p] += 1
        if partial_shared:
            last = shared_pages[-1]
            self._cow_reserve[last] = self._cow_reserve.get(last, 0) + 1
            self.reserved += 1
        mapping = shared_pages + fresh
        self._slot_pages[slot] = mapping
        self._slot_owner[slot] = (owner, tenant)
        self.block_tables[slot] = 0  # no stale entries from the last tenant
        self.block_tables[slot, : len(mapping)] = mapping
        if self.memledger is not None:
            # Only the FRESH pops are new physical occupancy; a shared
            # mapping is a refcount on pages already granted. The COW
            # reserve is held capacity too — it gates admission.
            if fresh:
                self.memledger.grant(
                    "kv_pages", len(fresh) * self.page_bytes,
                    owner=owner, tenant=tenant, tick=tick, kind="admit",
                )
            elif owner is not None:
                self.memledger.touch(
                    owner, tick=tick, tenant=tenant, state="admit"
                )
            if partial_shared:
                self.memledger.grant(
                    "kv_cow_reserve", self.page_bytes,
                    owner=owner, tenant=tenant, tick=tick,
                    kind="cow_reserve",
                )
            if self.slot_state_bytes:
                self.memledger.grant(
                    "kv_state", self.slot_state_bytes,
                    owner=owner, tenant=tenant, tick=tick, kind="admit",
                )
        self.admissions += 1
        restream = ()
        if shared_tokens:
            self.prefix_hits += 1
            # A hit refreshes the entry's recency — a prefix actively
            # being re-mapped is NOT an eviction candidate (ISSUE 18),
            # on either tier (a warm host entry must not be reclaimed
            # by the next park while it is still paying for itself).
            hashes = _prefix_hashes(prompt[:shared_tokens])
            self._prefix_touch[(shared_tokens, hashes[-1])] = tick
        if host_hit:
            # The hit's pages restream (engine restore) into the first
            # ``len(entry.pages)`` fresh device pages, position order.
            # The entry STAYS host-resident — it keeps serving hits
            # until register_prefix promotes it onto device pages.
            restream = tuple(
                (int(h), int(mapping[i])) for i, h in enumerate(entry.pages)
            )
            self.host_prefix_hits += 1
        self.shared_tokens_total += shared_tokens
        self.prompt_tokens_total += len(prompt)
        return AdmitPlan(
            shared_tokens=shared_tokens, pages=tuple(mapping),
            restream=restream,
        )

    def register_prefix(self, slot: int, prompt, *, tick: int = 0) -> list:
        """Index ``slot``'s now-fully-prefilled prompt so later admits
        can share it: one entry per page-aligned prefix plus the full
        prompt (covering its partially-filled last page). Call only
        AFTER the final prefill chunk executed — an entry must never
        advertise K/V that is not on the device yet.

        ISSUE 20: a host-tier entry for the same key is PROMOTED — the
        prefix is resident on device again (this slot just prefilled or
        restreamed it), so the host copy is redundant. Returns the host
        page ids freed by promotion (the engine drops their payloads);
        pre-tiering callers may ignore the (empty) list."""
        prompt = tuple(int(t) for t in prompt)
        mapping = self._slot_pages.get(slot)
        if mapping is None:
            return []
        hashes = _prefix_hashes(prompt)
        ps = self.page_size
        plen = len(prompt)
        boundaries = [k * ps for k in range(1, plen // ps + 1)]
        if plen % ps:
            boundaries.append(plen)
        freed_host: list[int] = []
        for n in boundaries:
            key = (n, hashes[n])
            prev = self._index.get(key)
            if prev is not None:
                if prev.tier != "host":
                    continue  # first registration wins; content identical
                freed_host += self._evict_host_entry(key, prev)
                self.promoted_entries += 1
            pages = tuple(mapping[: -(-n // ps)])
            self._index[key] = _PrefixEntry(
                tokens=prompt[:n], pages=pages
            )
            self._prefix_touch[key] = tick
            for p in pages:
                self._page_keys.setdefault(p, set()).add(key)
        return freed_host

    # -- host tier (ISSUE 20) ----------------------------------------------
    def spill_prefix_on_free(self, slot: int):
        """Plan the host migration of prefix entries about to die with
        ``slot``'s pages. Call BEFORE :meth:`free_slot`: entries citing
        a sole-reader (refcount 1) page of ``slot`` would be
        invalidated by the free — instead, every device page those
        entries cite (still-shared pages included, so the host copy is
        self-contained) gets a host page, the entries are rewritten
        tier="host", and the device bookkeeping for them is dropped so
        the eventual free of a surviving shared page cannot kill them.

        Returns ``(copies, evicted)``: ``copies`` is the
        ``[(device_page, host_page)]`` list the engine must gather
        BEFORE the device pages are recycled (all-or-nothing — an
        undersized host tier returns ``([], evicted)`` and the entries
        die exactly as before tiering); ``evicted`` is host pages freed
        by cold-entry reclaim, whose payloads the engine must drop."""
        if not self.host_pages:
            return [], []
        dying = [
            p for p in self._slot_pages.get(slot, [])
            if self.refcount[p] == 1 and self._page_keys.get(p)
        ]
        if not dying:
            return [], []
        keys: set = set()
        for p in dying:
            keys |= self._page_keys[p]
        entries = [(k, self._index[k]) for k in sorted(keys)
                   if k in self._index]
        pages: list[int] = []
        seen: set = set()
        for _k, e in entries:
            for p in e.pages:
                if p not in seen:
                    seen.add(p)
                    pages.append(int(p))
        evicted = self._reclaim_host(len(pages))
        if evicted is None:
            return [], []
        mapping = {p: self.host_free.pop() for p in pages}
        for k, e in entries:
            for p in e.pages:
                s = self._page_keys.get(p)
                if s is not None:
                    s.discard(k)
                    if not s:
                        del self._page_keys[p]
            moved = _PrefixEntry(
                tokens=e.tokens,
                pages=tuple(mapping[int(p)] for p in e.pages),
                tier="host",
            )
            self._index[k] = moved
            for h in moved.pages:
                self._host_page_keys.setdefault(h, set()).add(k)
        self.spilled_prefix_entries += len(entries)
        return [(p, mapping[p]) for p in pages], evicted

    def park_pages(self, rid, slot: int, fill: int):
        """Reserve host pages for a preemption victim's filled rows
        ``[0, fill)`` — all-or-nothing, after evicting cold host prefix
        entries if needed (parked records are never evicted: they are
        promised resumes, not opportunistic caches). Call BEFORE
        :meth:`free_slot`. Returns ``(copies, evicted)`` like
        :meth:`spill_prefix_on_free`, or ``None`` when the host tier
        cannot hold the spill (caller falls back to recompute)."""
        if not self.host_pages or fill <= 0:
            return None
        mapping = self._slot_pages.get(slot)
        npages = -(-fill // self.page_size)
        if mapping is None or npages > len(mapping):
            return None
        evicted = self._reclaim_host(npages)
        if evicted is None:
            return None
        host = [self.host_free.pop() for _ in range(npages)]
        self._parked[rid] = _ParkedKV(host_pages=tuple(host), fill=fill)
        self.parked_spills += 1
        return [(int(mapping[i]), host[i]) for i in range(npages)], evicted

    def peek_parked(self, rid):
        """The parked record for ``rid`` (or None), ids still owned."""
        return self._parked.get(rid)

    def take_parked(self, rid):
        """Pop ``rid``'s parked record, recycling its host page ids.
        Call only AFTER the payloads were consumed (engine restore or
        drop) — the ids become reusable by the next spill immediately."""
        rec = self._parked.pop(rid, None)
        if rec is not None:
            self.host_free.extend(rec.host_pages)
        return rec

    def drop_parked(self, rid) -> list:
        """Discard ``rid``'s parked record (shed / superseded request).
        Returns the freed host page ids so the engine can drop their
        payloads."""
        rec = self._parked.pop(rid, None)
        if rec is None:
            return []
        self.host_free.extend(rec.host_pages)
        return list(rec.host_pages)

    def _reclaim_host(self, need: int):
        """Free host pages until ``need`` are available by evicting the
        coldest host-tier prefix entries (by ``_prefix_touch``; parked
        records are untouchable). Returns the evicted host page ids
        ([] when already satisfied) or ``None`` when ``need`` is
        unreachable — in which case NOTHING was evicted (the
        reachability check precedes any eviction, keeping spills
        all-or-nothing)."""
        if need <= len(self.host_free):
            return []
        if len(self.host_free) + len(self._host_page_keys) < need:
            return None
        order = sorted(
            {k for ks in self._host_page_keys.values() for k in ks},
            key=lambda k: (self._prefix_touch.get(k, 0), k[0]),
        )
        freed: list[int] = []
        for key in order:
            if len(self.host_free) >= need:
                break
            entry = self._index.get(key)
            if entry is None or entry.tier != "host":
                continue
            freed += self._evict_host_entry(key, entry)
        return freed

    def _evict_host_entry(self, key, entry) -> list:
        """Drop one host-tier entry; host pages left keyless return to
        ``host_free``. Returns them (payload owners must drop them)."""
        self._index.pop(key, None)
        self._prefix_touch.pop(key, None)
        freed: list[int] = []
        for h in entry.pages:
            s = self._host_page_keys.get(h)
            if s is None:
                continue
            s.discard(key)
            if not s:
                del self._host_page_keys[h]
                self.host_free.append(h)
                freed.append(int(h))
        return freed

    def mapped_tokens(self) -> np.ndarray:
        """Per-slot writable capacity (mapped pages × page_size) as an
        int32 [slots] array — the speculative steps' write cap (ISSUE
        13): junk rows inside OWNED pages are mask-hidden, but a row
        past the mapping would scatter through a zeroed table entry
        into page 0, which another slot may own — those writes must be
        DROPPED, and this array is where the in-step mask learns the
        boundary."""
        out = np.zeros((self.slots,), np.int32)
        for slot, pages in self._slot_pages.items():
            out[slot] = len(pages) * self.page_size
        return out

    # -- write path ---------------------------------------------------------
    def cow_before_write(self, slot: int, position: int):
        """Make the page holding ``position`` privately writable by
        ``slot``. Returns ``(src, dst)`` when a copy-on-write remap
        happened (the caller must copy page ``src`` → ``dst`` on the
        device BEFORE the write executes), else ``None``."""
        idx = position // self.page_size
        page = int(self.block_tables[slot, idx])
        if self.refcount[page] <= 1:
            return None
        # Reservation accounting guarantees this pop succeeds: every
        # mapper of a shared partial page reserved one free page, and
        # only partial pages are ever written while shared.
        if not self.free:
            raise RuntimeError(
                "COW with an empty free list — reservation accounting bug"
            )
        dst = self.free.pop()
        if self.memledger is not None:
            # The copy's destination is new physical occupancy, paid
            # for by the reservation this mapper made at admit.
            owner, tenant = self._slot_owner.get(slot, (None, None))
            self.memledger.grant(
                "kv_pages", self.page_bytes,
                owner=owner, tenant=tenant, kind="cow_copy",
            )
        if self._cow_reserve.get(page, 0) > 0:
            self._cow_reserve[page] -= 1
            self.reserved -= 1
            if self.memledger is not None:
                self.memledger.free(
                    "kv_cow_reserve", self.page_bytes, kind="cow_copy"
                )
        self.refcount[page] -= 1
        self.refcount[dst] = 1
        self._trim_reserve(page)
        self.block_tables[slot, idx] = dst
        self._slot_pages[slot][idx] = dst
        self.cow_copies += 1
        return page, dst

    def _trim_reserve(self, page: int) -> None:
        """Release COW reserves a page can no longer need. A page with
        ``refcount`` mappers needs at most ``refcount - 1`` future
        copies (the last owner writes in place), so any excess goes
        back to the admittable pool — including the reserve of a
        sharer that RETIRED without ever writing (full-prompt prefix
        hit finishing at prefill): without this, sustained overlapping
        shared-prefix traffic leaks one reserve per such request until
        the whole cohort drains, and ``free_pages`` starves admission
        with a nearly empty pool."""
        keep = max(int(self.refcount[page]) - 1, 0)
        excess = self._cow_reserve.get(page, 0) - keep
        if excess > 0:
            self._cow_reserve[page] -= excess
            self.reserved -= excess
            if self.memledger is not None:
                self.memledger.free(
                    "kv_cow_reserve", excess * self.page_bytes,
                    kind="trim_reserve",
                )

    # -- release ------------------------------------------------------------
    def slot_page_stats(self, slot: int) -> tuple:
        """``(owned, shared)`` pages currently mapped by ``slot``:
        ``owned`` = sole-owner pages :meth:`free_slot` would return to
        the free list, ``shared`` = pages that would merely drop a
        refcount. The preemption path's pool-accounting observable
        (ISSUE 12): evicting a victim must free exactly its non-shared
        pages — test-pinned."""
        pages = self._slot_pages.get(slot, [])
        owned = sum(1 for p in pages if self.refcount[p] == 1)
        return owned, len(pages) - owned

    def free_slot(self, slot: int) -> None:
        """Unmap ``slot``'s pages; pages at refcount 0 return to the
        free list and any prefix-index entries citing them die (their
        advertised K/V is about to be recycled)."""
        owner, _ = self._slot_owner.pop(slot, (None, None))
        released = 0
        if self.window and slot in self._slot_window:
            back = list(self._slot_window.pop(slot).values())
            del self._window_promised[slot]
            self.window_free.extend(back)
            if self.memledger is not None and back:
                self.memledger.free(
                    "kv_window_pages", len(back) * self.window_page_bytes,
                    owner=owner, kind="free_slot",
                )
        if (self.memledger is not None and self.slot_state_bytes
                and slot in self._slot_pages):
            self.memledger.free(
                "kv_state", self.slot_state_bytes, owner=owner,
                kind="free_slot",
            )
        for p in self._slot_pages.pop(slot, []):
            self.refcount[p] -= 1
            self._trim_reserve(p)
            if self.refcount[p] == 0:
                for key in self._page_keys.pop(p, ()):  # invalidate
                    entry = self._index.pop(key, None)
                    self._prefix_touch.pop(key, None)
                    if entry is not None:
                        for q in entry.pages:
                            if q != p and q in self._page_keys:
                                self._page_keys[q].discard(key)
                self.free.append(p)
                released += 1
        if self.memledger is not None and released:
            # Only pages hitting refcount 0 return physical occupancy;
            # dropping a refcount on a still-shared page frees nothing.
            self.memledger.free(
                "kv_pages", released * self.page_bytes,
                owner=owner, kind="free_slot",
            )
