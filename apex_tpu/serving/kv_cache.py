"""Paged KV cache: a page-table block allocator over a preallocated pool.

Serving holds one KV entry per (layer, head, past token) for every live
sequence, and the sequences are ragged, growing, and replaced
mid-flight.  The dense answer — ``(slots, layers, heads, max_len, d)``
— sizes every slot for the longest conversation the server will ever
see; vLLM-style paging sizes the pool for the TRAFFIC instead: a single
preallocated pool of fixed ``page_size``-token pages, a per-slot
logical→physical page table, and a host-side free-list allocator.
A request holds exactly ``ceil((prompt + budget) / page_size)`` pages
and returns them on retirement; nothing is ever copied or compacted.

Split of responsibilities:

- **host side** (:class:`PageAllocator`, :class:`PagedKVCache`):
  allocation, free-list reuse, the page-table and length mirrors.
  Pure Python, no device sync — tables ship to the device as small
  int32 arrays each step.
- **device side** (:func:`init_pools`, :func:`write_tokens`): the
  pools themselves and the jit-friendly scatter that writes new tokens
  at ``(physical_page, offset)`` — shape-stable for any batch, so the
  decode step never recompiles as sequences come and go.

Physical page 0 is RESERVED as the null page: unallocated page-table
entries (and the write targets of idle slots) point at it, so every
address the decode kernel's scalar-prefetch walk can form is valid and
garbage lands where nothing reads it
(:mod:`apex_tpu.ops.attention_decode`).

``kv_dtype=jnp.int8`` stores pages quantized with per-``(token,
kv_block)`` fp32 scales (``ops/quantization.py``'s row-block
machinery — the EQuARX block format applied to storage instead of
wire).  The decode kernel dequantizes pages in VMEM; at decode's ~2
FLOPs/byte arithmetic intensity the halved (vs bf16) HBM stream is the
throughput win, and the tolerance band is gated in
``tests/test_attention_decode.py`` and the ``_dryrun_decode`` config.

**Prefix caching** rides the same allocator: pages are refcounted,
:class:`PagedKVCache` keeps a cumulative-hash index of full prompt
pages, and admissions share matched pages read-only instead of
recomputing them (:class:`AdmitResult`; :func:`copy_pages` is the
copy-on-write for a match ending mid-page).  docs/serving.md spells
out the contract — what is hashed, when pages are copied, and that
eviction is pure refcount GC.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "KVCacheConfig",
    "PageClass",
    "SlotState",
    "CacheOutOfPages",
    "AdmitResult",
    "PageAllocator",
    "PagedKVCache",
    "HostOffloadPool",
    "prompt_page_hashes",
    "init_pools",
    "write_tokens",
    "write_latent_tokens",
    "write_class_rows",
    "write_class_pages",
    "write_targets",
    "copy_pages",
    "export_pages",
    "import_pages",
    "staged_nbytes",
]


class CacheOutOfPages(RuntimeError):
    """The pool has fewer free pages than an admission needs.  The
    serving driver treats this as backpressure (the request waits in
    the queue), not an error."""


def prompt_page_hashes(prompt_tokens, page_size: int) -> List[bytes]:
    """Cumulative SHA-1 of a prompt's FULL pages — the prefix-cache
    identity (``h_i = sha1(h_{i-1} || page_i tokens)``) and, because it
    depends only on token ids and ``page_size``, the fleet router's
    replica-independent routing key: every replica of one cache config
    computes the same hashes for the same prompt."""
    import hashlib

    toks = [int(t) for t in prompt_tokens]
    hashes, h = [], hashlib.sha1()
    for i in range(len(toks) // page_size):
        h.update(np.asarray(toks[i * page_size: (i + 1) * page_size],
                            np.int64).tobytes())
        hashes.append(h.digest())
    return hashes


@dataclasses.dataclass(frozen=True)
class PageClass:
    """The state ONE KIND of layer keeps: which of the model's layers
    (``layers``, in the order of the class's pool axis), what a token's
    entry is (``num_heads`` x ``head_dim`` keys and values, or for
    ``kind="latent"`` one ``latent_dim`` row and an ``index_dim`` key),
    how many physical pages the class's pool has (page 0 reserved) and
    how many a slot may hold (``pages_per_seq``).

    ``window > 0`` makes the class a RING a slot owns: the layers attend
    to the last ``window`` positions only, the token at position ``p``
    lives in the slot's table column ``(p // page_size) %
    pages_per_seq``, and a page is overwritten once the slot has moved a
    whole ring past it.  A slot therefore holds at most ``pages_per_seq``
    pages of the class whatever its context, and its table row never
    changes after admission (docs/serving.md says why a ring and not
    pages handed back).  ``pages_per_seq * page_size`` has to cover the
    window, the longest run of tokens written before they are read (a
    prefill chunk) and one page of slack for an unaligned window."""

    name: str
    layers: Tuple[int, ...]
    num_pages: int
    pages_per_seq: int
    num_heads: int
    head_dim: int
    window: int = 0
    kind: str = "kv"
    latent_dim: int = 0
    index_dim: int = 0

    def pages_for(self, n_tokens: int, page_size: int) -> int:
        """Pages a sequence of ``n_tokens`` holds of this class."""
        n = -(-n_tokens // page_size)
        return min(n, self.pages_per_seq) if self.window else n


@dataclasses.dataclass(frozen=True)
class SlotState:
    """A state of FIXED size that every slot keeps per layer beside its
    pages (a state-space layer's recurrent state, its convolution
    window): ``init_pools`` builds ``name`` as ``(layers, max_seqs) +
    shape`` in ``dtype``, zeroed, in the same donated pools dict as the
    pages.  Nothing about it is paged: a slot owns its row from
    admission to retirement, and the model's first prompt chunk (start
    0) starts from zeros whatever the row holds, so admission costs no
    device work.  A cache with such a state refuses everything that
    would move a slot's pages without it: the prefix index, a page
    export and import."""

    name: str
    layers: int
    shape: Tuple[int, ...]
    dtype: Any = jnp.float32


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Shape and dtype of one paged cache.

    A cache is a tuple of :class:`PageClass` (``page_classes``): each
    class has its own pool, allocator and page-table columns, one slot
    numbering and one ``page_size`` serve them all.  The flat fields
    below describe the ONE-class cache (every layer keeps the same entry
    for the whole context: ``kind="kv"`` and ``kind="latent"``);
    :meth:`of_classes` builds a cache of several.

    ``num_pages`` counts PHYSICAL pool pages (page 0 is the reserved
    null page, so ``num_pages - 1`` are allocatable).  ``max_seqs`` is
    the fixed slot count of the serving batch; ``pages_per_seq`` bounds
    one sequence's logical length at ``pages_per_seq * page_size``
    tokens.  ``kv_dtype=None`` stores pages in ``dtype``;
    ``jnp.int8`` stores quantized pages with per-``(token, kv_block)``
    fp32 scales.

    ``kind="latent"`` is a pool whose per-token entry is NOT per head
    (multi-head latent attention): ``latent_dim`` values shared by all
    heads (the normalised latent and the rotated shared key side by
    side), per layer, plus an ``index_dim``-wide index key where the
    model selects its context with a sparse indexer (DeepSeek-V3.2).
    ``index_dim=0`` is the latent pool WITHOUT index keys, for a model
    whose attention reads the whole context (Xing4.0): ``init_pools``
    builds ``ckv`` alone, no ``kidx``.  ``num_heads`` / ``head_dim`` do
    not describe such an entry and must be left at 1 / ``latent_dim``;
    pages, tables, the allocator and admission are the same.  Nothing
    refuses a latent cache by its kind: ``admit(prompt_tokens=)`` /
    ``prefix_cache=True`` and ``export_request`` refuse a cache of
    several page classes or with a window class, and a latent cache is
    one whole-context class whose pages ``copy_pages`` /
    ``export_pages`` move pool by pool, with or without ``kidx``.  A
    latent row is stored
    ``latent_row_dim`` wide, ``latent_dim`` rounded up to whole 128-lane
    tiles (the tail is zero): the device tiles a row that way in any
    case, and a pool whose last axis is NOT a multiple of 128 (576) is
    re-laid out — copied whole, twice a step — around every scatter
    into it (v5e compiler, 1.47 GB of temporaries for a 1.3 GB pool)."""

    num_layers: int
    num_heads: int
    head_dim: int
    num_pages: int
    page_size: int = 64
    max_seqs: int = 8
    pages_per_seq: int = 16
    dtype: Any = jnp.bfloat16
    kv_dtype: Optional[Any] = None
    kv_block: int = 128
    kind: str = "kv"
    latent_dim: int = 0
    index_dim: int = 0
    classes: Tuple[PageClass, ...] = ()
    #: per-slot states of fixed size beside the pages
    #: (:class:`SlotState`, e.g. a state-space layer's); () for none
    slot_states: Tuple[SlotState, ...] = ()

    @classmethod
    def of_classes(cls, classes, *, page_size: int, max_seqs: int,
                   dtype: Any = jnp.bfloat16) -> "KVCacheConfig":
        """A cache of several page classes.  The slot's token bound
        (``max_len``) is that of its classes that keep the whole
        context; the flat fields take the first class's entry shape and
        the totals."""
        classes = tuple(classes)
        whole = [c.pages_per_seq for c in classes if not c.window]
        if not whole:
            raise ValueError(
                "a cache of window classes only has no token bound: "
                "give it one class with window=0")
        first = classes[0]
        return cls(
            num_layers=sum(len(c.layers) for c in classes),
            num_heads=first.num_heads, head_dim=first.head_dim,
            num_pages=sum(c.num_pages for c in classes),
            page_size=page_size, max_seqs=max_seqs,
            pages_per_seq=min(whole), dtype=dtype, classes=classes)

    def __post_init__(self):
        if self.classes:
            self._check_classes()
        if self.slot_states:
            self._check_states()
        if self.num_pages < 2:
            raise ValueError(
                "num_pages must be >= 2 (page 0 is the reserved null "
                "page)")
        if self.page_size < 1 or self.pages_per_seq < 1:
            raise ValueError("page_size and pages_per_seq must be >= 1")
        if self.kv_dtype is not None and \
                jnp.dtype(self.kv_dtype) != jnp.dtype(jnp.int8):
            raise ValueError(
                f"kv_dtype must be None or int8, got {self.kv_dtype!r}")
        if self.kind not in ("kv", "latent"):
            raise ValueError(
                f"kind must be 'kv' or 'latent', got {self.kind!r}")
        if self.kind == "latent":
            if self.latent_dim < 1 or self.index_dim < 0:
                raise ValueError(
                    "a latent pool needs latent_dim and index_dim: "
                    "latent_dim >= 1; index_dim >= 0 is the width of the "
                    "sparse indexer's key a token (DeepSeek-V3.2), 0 for "
                    "a model whose attention reads the whole context and "
                    "keeps no index keys (no kidx pool is built)")
            if (self.num_heads, self.head_dim) != (1, self.latent_dim):
                raise ValueError(
                    "a latent entry is shared by all heads: pass "
                    "num_heads=1, head_dim=latent_dim")
            if self.quantized:
                raise ValueError("latent pools are not quantized")

    def _check_states(self):
        names = [st.name for st in self.slot_states]
        pages = ("k", "v", "k_scales", "v_scales", "ckv", "kidx")
        if len(set(names)) != len(names) or any(
                n in pages or n.endswith((".k", ".v")) for n in names):
            raise ValueError(f"slot states need distinct names that no "
                             f"page pool has: {names}")
        if any(st.layers < 1 for st in self.slot_states):
            raise ValueError("a slot state needs >= 1 layer")

    def _check_classes(self):
        names = [c.name for c in self.classes]
        layers = sorted(l for c in self.classes for l in c.layers)
        if len(set(names)) != len(names) or any("." in n for n in names):
            raise ValueError(f"page classes need distinct plain names: "
                             f"{names}")
        if layers != list(range(len(layers))) \
                or len(layers) != self.num_layers:
            raise ValueError(
                f"page classes must hold each of the {self.num_layers} "
                f"layers once, got {layers}")
        if self.quantized or self.kind != "kv":
            raise ValueError(
                "a cache of page classes stores plain K/V entries")
        for c in self.classes:
            if c.kind != "kv":
                raise ValueError(
                    f"class {c.name!r}: a latent class beside others is "
                    "not built (its pools have other keys)")
            if c.num_pages < 2 or c.pages_per_seq < 1:
                raise ValueError(f"class {c.name!r}: needs >= 2 pages "
                                 "and >= 1 page a slot")
            if c.window and c.pages_per_seq * self.page_size \
                    < c.window + self.page_size:
                raise ValueError(
                    f"class {c.name!r}: a ring of {c.pages_per_seq} "
                    f"pages cannot hold a window of {c.window} tokens "
                    "and one page of slack")

    @property
    def page_classes(self) -> Tuple[PageClass, ...]:
        """The cache's classes; a flat config is its one class."""
        if self.classes:
            return self.classes
        return (PageClass(
            name=self.kind, layers=tuple(range(self.num_layers)),
            num_pages=self.num_pages, pages_per_seq=self.pages_per_seq,
            num_heads=self.num_heads, head_dim=self.head_dim,
            kind=self.kind, latent_dim=self.latent_dim,
            index_dim=self.index_dim),)

    @property
    def table_columns(self) -> Tuple[Tuple[int, int], ...]:
        """Each class's ``[lo, hi)`` columns of the page table (the
        classes' rows side by side, so that ONE int32 table ships to the
        device a step, as before)."""
        out, lo = [], 0
        for c in self.page_classes:
            out.append((lo, lo + c.pages_per_seq))
            lo += c.pages_per_seq
        return tuple(out)

    @property
    def quantized(self) -> bool:
        return self.kv_dtype is not None

    @property
    def has_state(self) -> bool:
        """Whether a slot keeps state beside its pages (``slot_states``):
        its pages alone do not resume it."""
        return bool(self.slot_states)

    @property
    def latent_row_dim(self) -> int:
        return -(-self.latent_dim // 128) * 128

    @property
    def scale_blocks(self) -> int:
        return -(-self.head_dim // self.kv_block)

    @property
    def max_len(self) -> int:
        return self.page_size * self.pages_per_seq

    def tokens_to_pages(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)


# ---------------------------------------------------------------------------
# Host side: allocator + per-slot bookkeeping
# ---------------------------------------------------------------------------


class PageAllocator:
    """Refcounted free-list page allocator.  Page 0 is never handed out.

    Invariants (tests/test_serving.py): ``free`` rejects pages not
    currently allocated (double-free) and page 0; freed pages are
    reusable immediately — the free list is LIFO, so a hot slot's pages
    stay cache-warm.  Prefix caching shares pages READ-ONLY across
    holders: ``share`` adds a reference, ``free`` drops one, and a page
    returns to the free list only at refcount zero — so a slot retiring
    while another slot (or the prefix index) still reads its pages can
    never recycle them out from under the reader."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refcount: Dict[int, int] = {}

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_shared(self) -> int:
        """Pages with more than one holder — live prefix sharing (the
        ``pages_shared`` telemetry gauge; pure host state)."""
        return sum(1 for c in self._refcount.values() if c > 1)

    def refcount(self, page: int) -> int:
        """Current holders of ``page`` (0 = free)."""
        return self._refcount.get(int(page), 0)

    def alloc(self, n: int) -> List[int]:
        """``n`` pages at refcount 1, or :class:`CacheOutOfPages` —
        all-or-nothing, so a failed admission never leaks a partial
        allocation."""
        if n > len(self._free):
            raise CacheOutOfPages(
                f"need {n} pages, {len(self._free)} free "
                f"(pool {self.num_pages}, 1 reserved)")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refcount[p] = 1
        return pages

    def share(self, pages) -> None:
        """Add one reference to each of ``pages`` (all must be
        allocated).  The sharer promises READ-ONLY use: nothing in the
        allocator stops a write, the serving layer's write-target
        masking does (shared pages cover only positions below every
        sharer's first write position)."""
        pages = [int(p) for p in pages]
        for p in pages:
            if p not in self._refcount:
                raise ValueError(
                    f"page {p} is not allocated — cannot share")
        for p in pages:
            self._refcount[p] += 1

    def free(self, pages) -> None:
        """Drop one reference per page; refcount-zero pages return to
        the free list."""
        for p in pages:
            p = int(p)
            if p == 0:
                raise ValueError("page 0 is the reserved null page")
            if p not in self._refcount:
                raise ValueError(f"page {p} is not allocated "
                                 "(double free?)")
            self._refcount[p] -= 1
            if self._refcount[p] == 0:
                del self._refcount[p]
                self._free.append(p)


@dataclasses.dataclass
class AdmitResult:
    """What an admission reused from the prefix cache.

    ``matched_tokens`` of the prompt are already present in shared
    pages (prefill compute for whole chunks below this mark can be
    skipped); ``shared_pages`` of the slot's table row point at
    read-only pages other holders also reference; ``copied_page`` is
    the ``(src, dst)`` physical pair the caller must copy on device
    (:func:`copy_pages`) when the match ended mid-page — the
    copy-on-write tail."""

    slot: int
    matched_tokens: int = 0
    shared_pages: int = 0
    copied_page: Optional[Tuple[int, int]] = None
    #: the prompt's full-page cumulative hashes, computed during the
    #: match — hand them back to :meth:`PagedKVCache.register_prefix`
    #: so registration does not re-hash the prompt
    page_hashes: Optional[List[bytes]] = None


class PagedKVCache:
    """Host-side view of one serving cache: the allocator plus the
    page-table and length mirrors the driver ships to the device each
    step.  Device pools live separately (:func:`init_pools`) — they are
    step-function state, threaded through jit; this object is the
    bookkeeping that decides WHERE in those pools each slot writes.

    **Prefix caching**: the cache keeps a prefix index — a cumulative
    hash of token ids per FULL page (``h_i = sha1(h_{i-1} || page_i
    tokens)``) mapping to the physical page that holds those tokens'
    K/V.  ``admit(prompt_tokens=...)`` longest-matches the new prompt
    against it: matched full pages are SHARED read-only (refcount++),
    only the remainder is freshly allocated, and the returned
    :class:`AdmitResult` tells the scheduler which prefill chunks it
    may skip.  The last prompt token is never matched — its logits
    seed generation — so a whole-prompt match shares all pages but the
    one holding that token, which is COPIED instead (``copied_page``).
    ``register_prefix`` (call after prefill has written the prompt)
    adds a slot's full prompt pages to the index with the index itself
    holding one reference, so registered pages survive the slot's
    retirement as reusable cache; eviction is pure refcount GC — when
    an admission runs short of pages, leaf index entries whose ONLY
    holder is the index are unregistered oldest-first and their pages
    freed."""

    def __init__(self, config: KVCacheConfig):
        self.config = config
        classes = config.page_classes
        #: one allocator a page class (physical page ids are a class's
        #: own: they index ITS pool); ``allocator`` is the first one's,
        #: the whole cache's where there is one class
        self.allocators = [PageAllocator(c.num_pages) for c in classes]
        self.allocator = self.allocators[0]
        self.page_table = np.zeros(
            (config.max_seqs, config.table_columns[-1][1]), np.int32)
        self.lengths = np.zeros((config.max_seqs,), np.int32)
        # per class: slot -> the pages it holds, in table order
        self._class_pages: List[Dict[int, List[int]]] = [
            {} for _ in classes]
        self._slot_pages = self._class_pages[0]
        #: per window class, pages that retired slots had OVERWRITTEN in
        #: their ring (a page is written again once the slot is a whole
        #: ring past it): what the class did not have to hold
        self.overwritten_pages = {c.name: 0 for c in classes if c.window}
        # cumulative page hash -> {"page", "parent" hash, "children"}
        self._prefix: Dict[bytes, Dict[str, Any]] = {}
        # slot -> pages the slot references WITHOUT owning a table-row
        # entry for (the copy-on-write SOURCE page: it must stay
        # allocated until the device copy has certainly happened, i.e.
        # the slot's lifetime — eviction or reuse before the copy would
        # silently corrupt the clone)
        self._extra_refs: Dict[int, List[int]] = {}
        # the offload seam: called ONCE per GC burst as
        # ``evict_hook(victims)`` with the list of ``(hash,
        # parent_hash, page)`` index-only entries the refcount GC is
        # about to free, BEFORE any page is freed — device content is
        # still valid, so the hook may stage the whole batch to a host
        # tier (:class:`HostOffloadPool`) with one device->host
        # transfer.  The hook must not allocate or evict (it runs
        # inside ``_evict_prefix``).  When a hook is attached the GC
        # over-evicts to ``evict_batch`` victims per burst (the extras
        # are recoverable from the host tier) so staging amortizes.
        self.evict_hook: Optional[Callable[
            [List[Tuple[bytes, Optional[bytes], int]]], None]] = None
        self.evict_batch: int = 8

    # ------------------------------------------------------ prefix index
    def _page_hashes(self, prompt_tokens) -> List[bytes]:
        """Cumulative hashes of the prompt's FULL pages (page i's hash
        covers tokens ``[0, (i+1) * page_size)`` — a page's identity is
        its whole history, so two pages hash equal iff every token
        before and inside them matches)."""
        return prompt_page_hashes(prompt_tokens, self.config.page_size)

    @property
    def prefix_index_size(self) -> int:
        return len(self._prefix)

    def match_len(self, hashes: List[bytes]) -> int:
        """Tokens of a prompt already resident in this cache's prefix
        index: the longest run of leading ``hashes``
        (:func:`prompt_page_hashes`) the index holds, in tokens.  A
        read-only probe — no allocation, no refcounts, no device sync —
        the fleet router's prefix-affinity score
        (:mod:`apex_tpu.fleet.router`)."""
        n = 0
        for h in hashes:
            if h not in self._prefix:
                break
            n += 1
        return n * self.config.page_size

    def _evict_prefix(self, n: int, protect=()) -> int:
        """Refcount GC: unregister up to ``n`` index entries whose page
        the index is the ONLY holder of (leaf entries first — an inner
        entry stays while a longer chain built on it survives), freeing
        their pages.  Returns how many pages were freed.

        ``protect`` is a collection of hashes the GC must skip — the
        fault-in path uses it so re-adopting page ``k`` of a chain can
        never evict pages ``< k`` it just brought back.  The victim
        batch is offered to :attr:`evict_hook` (one call per burst)
        before any page is freed; with a hook attached the burst is
        padded up to :attr:`evict_batch` victims so the hook's
        device->host staging amortizes — the extras live on in the
        host tier, not lost."""
        if self.evict_hook is not None:
            n = max(n, self.evict_batch)
        freed, progress = 0, True
        protect = set(protect)
        victims: List[Tuple[bytes, Optional[bytes], int]] = []
        while freed < n and progress:
            progress = False
            for h in list(self._prefix):
                if h in protect:
                    continue
                e = self._prefix[h]
                if e["children"] == 0 and \
                        self.allocator.refcount(e["page"]) == 1:
                    victims.append((h, e["parent"], e["page"]))
                    del self._prefix[h]
                    if e["parent"] is not None:
                        self._prefix[e["parent"]]["children"] -= 1
                    freed += 1
                    progress = True
                    if freed >= n:
                        break
        if victims:
            if self.evict_hook is not None:
                self.evict_hook(victims)
            self.allocator.free([p for _, _, p in victims])
        return freed

    def adopt_prefix_page(self, h: bytes, parent: Optional[bytes],
                          protect=()) -> int:
        """Allocate one page and register it in the prefix index under
        hash ``h`` with the index as its only holder — the fault-in
        half of the offload tier: the caller then scatters the staged
        host bytes into the returned physical page
        (:func:`import_pages`), after which the chain is
        indistinguishable from one that never left the device.  Runs
        the refcount GC (honoring ``protect``) when the pool is out of
        free pages; raises :class:`CacheOutOfPages` if nothing can be
        evicted.  ``parent`` must already be indexed (fault in a chain
        oldest-first) or ``None`` for the chain head."""
        if h in self._prefix:
            raise ValueError("hash already indexed — probe before "
                             "adopting")
        if parent is not None and parent not in self._prefix:
            raise ValueError("parent hash not indexed — fault a chain "
                             "in oldest-first")
        short = 1 - self.allocator.num_free
        if short > 0:
            self._evict_prefix(short, protect=protect)
        page = self.allocator.alloc(1)[0]
        self._prefix[h] = {"page": page, "parent": parent, "children": 0}
        if parent is not None:
            self._prefix[parent]["children"] += 1
        return page

    def register_prefix(self, slot: int, prompt_tokens,
                        hashes: Optional[List[bytes]] = None) -> int:
        """Add ``slot``'s full prompt pages to the prefix index (call
        AFTER prefill has written them — the index vouches that the
        page holds those tokens' K/V).  The index takes one reference
        per newly registered page, so the pages outlive the slot.
        Pages whose hash is already indexed are skipped (first writer
        wins; the content is bit-identical by construction).  Returns
        the number of pages newly registered.  ``hashes`` (the
        ``AdmitResult.page_hashes`` from this slot's admission) skips
        re-hashing the prompt."""
        if slot not in self._slot_pages:
            raise ValueError(f"slot {slot} is not admitted")
        pages = self._slot_pages[slot]
        if hashes is None:
            hashes = self._page_hashes(prompt_tokens)
        added, parent = 0, None
        for i, h in enumerate(hashes):
            if h not in self._prefix:
                self.allocator.share([pages[i]])
                self._prefix[h] = {"page": pages[i], "parent": parent,
                                   "children": 0}
                if parent is not None:
                    self._prefix[parent]["children"] += 1
                added += 1
            parent = h
        return added

    # ------------------------------------------------------------- admit
    def admit(self, slot: int, total_tokens: int,
              prompt_tokens=None) -> AdmitResult:
        """Reserve pages for a sequence of up to ``total_tokens``
        (prompt + generation budget) in ``slot``.  Raises
        :class:`CacheOutOfPages` (backpressure) without allocating
        anything (a failed admission may still have GC'd index-only
        cache pages — that is the eviction working, not a leak); a
        previously retired slot's row is guaranteed null-paged.

        With ``prompt_tokens``, the prompt is longest-matched against
        the prefix index and matched full pages are shared instead of
        allocated (see the class docstring); the result reports what
        was reused.  The caller MUST honor the contract: no writes at
        positions below ``matched_tokens``, and the ``copied_page``
        device copy happens before any attend touches the slot.  The
        copy's SOURCE page is referenced by the slot until retirement,
        so no later admission or eviction can recycle it out from
        under a pending copy."""
        cfg = self.config
        classes = cfg.page_classes
        if slot in self._slot_pages:
            raise ValueError(f"slot {slot} is already admitted")
        if total_tokens > cfg.max_len:
            raise ValueError(
                f"sequence of {total_tokens} tokens exceeds the slot "
                f"bound {cfg.max_len} (pages_per_seq * page_size)")
        if prompt_tokens is not None and cfg.has_state:
            raise ValueError(
                "the prefix index shares pages, and this cache keeps a "
                "per-slot state beside them (slot_states) that a shared "
                "prefix would also need: prefix caching over a cache with "
                "state is not built (ROADMAP, R queue)")
        if prompt_tokens is not None and (
                len(classes) > 1 or classes[0].window):
            raise ValueError(
                "the prefix index shares whole-context pages of ONE "
                "class: a hit over a window class would also need that "
                "class's last `window` tokens (ROADMAP, R queue)")
        need = [c.pages_for(total_tokens, cfg.page_size) for c in classes]
        for c, n, alloc in zip(classes[1:], need[1:], self.allocators[1:]):
            # all-or-nothing over the classes: nothing is allocated
            # below unless every further class has its pages too
            if n > alloc.num_free:
                raise CacheOutOfPages(
                    f"class {c.name!r} needs {n} pages, {alloc.num_free} "
                    f"free (pool {alloc.num_pages}, 1 reserved)")
        n_pages = need[0]

        matched_pages: List[int] = []
        matched_tokens, cow_src, hashes = 0, None, None
        if prompt_tokens is not None:
            plen = len(prompt_tokens)
            hashes = self._page_hashes(prompt_tokens)
            for h in hashes:
                e = self._prefix.get(h)
                if e is None:
                    break
                matched_pages.append(e["page"])
            matched_tokens = len(matched_pages) * cfg.page_size
            if matched_tokens >= plen:
                # never match the whole prompt: the last token's logits
                # seed generation, so it is always recomputed — the
                # page holding it is copied, not shared
                matched_tokens = plen - 1
                cow_src = matched_pages.pop()

        # matched pages AND the CoW source are referenced FIRST so the
        # eviction below can never free (and the alloc never re-issue)
        # a page this admission is about to read
        protect = matched_pages + (
            [cow_src] if cow_src is not None else [])
        self.allocator.share(protect)
        n_fresh = n_pages - len(matched_pages)
        try:
            short = n_fresh - self.allocator.num_free
            if short > 0:
                self._evict_prefix(short)
            fresh = self.allocator.alloc(n_fresh)
        except CacheOutOfPages:
            self.allocator.free(protect)
            raise
        pages = matched_pages + fresh
        copied = (cow_src, fresh[0]) if cow_src is not None else None
        if cow_src is not None:
            # the slot keeps its source reference until retirement:
            # the device copy is guaranteed a live, unrecycled source
            # for as long as the slot exists
            self._extra_refs[slot] = [cow_src]
        self._slot_pages[slot] = pages
        row = np.zeros((self.page_table.shape[1],), np.int32)
        row[: len(pages)] = pages
        for i, (lo, _) in enumerate(cfg.table_columns[1:], 1):
            more = self.allocators[i].alloc(need[i])
            self._class_pages[i][slot] = more
            row[lo: lo + len(more)] = more
        self.page_table[slot] = row
        self.lengths[slot] = 0
        return AdmitResult(
            slot=slot, matched_tokens=matched_tokens,
            shared_pages=len(matched_pages), copied_page=copied,
            page_hashes=hashes)

    def retire(self, slot: int) -> None:
        """Drop the slot's references (refcount-zero pages return to
        the pool — shared pages other slots or the prefix index still
        hold stay allocated) and null its table row (so a stale read
        through the old row hits the null page, never another
        request's data)."""
        cfg = self.config
        for c, alloc, held in zip(cfg.page_classes, self.allocators,
                                  self._class_pages):
            alloc.free(held.pop(slot))
            if c.window:
                self.overwritten_pages[c.name] += max(
                    cfg.tokens_to_pages(int(self.lengths[slot]))
                    - c.pages_per_seq, 0)
        self.allocator.free(self._extra_refs.pop(slot, []))
        self.page_table[slot] = 0
        self.lengths[slot] = 0

    def active_slots(self) -> List[int]:
        return sorted(self._slot_pages)

    def pages_in_use(self) -> Dict[str, int]:
        """Allocated pages of each class, by its name."""
        return {c.name: a.num_pages - 1 - a.num_free
                for c, a in zip(self.config.page_classes, self.allocators)}

    def compat_key(self) -> Tuple:
        """The cache-config family two pools must share for pages to
        move between them (:func:`export_pages` /
        :func:`import_pages`): everything that shapes a page's bytes.
        ``num_pages`` / ``max_seqs`` / ``pages_per_seq`` are per-replica
        capacity, not page layout, so they may differ."""
        cfg = self.config
        key = (cfg.num_layers, cfg.num_heads, cfg.head_dim,
               cfg.page_size, str(jnp.dtype(cfg.dtype)),
               None if cfg.kv_dtype is None
               else str(jnp.dtype(cfg.kv_dtype)),
               cfg.kv_block)
        if cfg.kind != "kv":
            key += (cfg.kind, cfg.latent_dim, cfg.index_dim)
        for c in cfg.classes:
            # a class's pages move only into the same class: its layers,
            # its entry, and for a ring its size (a position's column)
            key += ((c.name, c.layers, c.num_heads, c.head_dim, c.window,
                     c.pages_per_seq if c.window else 0),)
        for st in cfg.slot_states:
            key += (("state", st.name, st.layers, tuple(st.shape),
                     str(jnp.dtype(st.dtype))),)
        return key

    def device_tables(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(page_table, lengths) as device arrays — a few KB per step."""
        return (jnp.asarray(self.page_table),
                jnp.asarray(self.lengths))


# ---------------------------------------------------------------------------
# Device side: pools + the token scatter
# ---------------------------------------------------------------------------


def init_pools(config: KVCacheConfig) -> Dict[str, jnp.ndarray]:
    """Zeroed device pools: ``k``/``v`` of shape ``(num_layers,
    num_pages, num_heads, page_size, head_dim)`` (the decode kernel's
    pool layout with a leading layer axis the model's layer scan
    slices), plus fp32 ``k_scales``/``v_scales`` when quantized.

    ``kind="latent"``: ``ckv`` of shape ``(num_layers, num_pages,
    page_size, latent_row_dim)`` and, where ``index_dim > 0``, ``kidx``
    of ``(..., index_dim)`` — a token's entry is one row, shared by all
    heads.

    Each of ``slot_states`` (:class:`SlotState`) adds its own zeroed
    ``(layers, max_seqs) + shape`` entry under its name."""
    cfg = config
    states = {st.name: jnp.zeros((st.layers, cfg.max_seqs) + tuple(st.shape),
                                 st.dtype) for st in cfg.slot_states}
    return dict(_page_pools(cfg), **states)


def _page_pools(cfg: KVCacheConfig) -> Dict[str, jnp.ndarray]:
    if cfg.classes:
        # a class's pool under its own name: "<class>.k" / "<class>.v",
        # (its layers, ITS pages, heads, page_size, head_dim)
        return {f"{c.name}.{kv}": jnp.zeros(
            (len(c.layers), c.num_pages, c.num_heads, cfg.page_size,
             c.head_dim), cfg.dtype)
            for c in cfg.classes for kv in ("k", "v")}
    if cfg.kind == "latent":
        lead = (cfg.num_layers, cfg.num_pages, cfg.page_size)
        pools = {"ckv": jnp.zeros(lead + (cfg.latent_row_dim,), cfg.dtype)}
        if cfg.index_dim:
            pools["kidx"] = jnp.zeros(lead + (cfg.index_dim,), cfg.dtype)
        return pools
    shape = (cfg.num_layers, cfg.num_pages, cfg.num_heads,
             cfg.page_size, cfg.head_dim)
    dt = cfg.kv_dtype if cfg.quantized else cfg.dtype
    pools = {
        "k": jnp.zeros(shape, dt),
        "v": jnp.zeros(shape, dt),
    }
    if cfg.quantized:
        sshape = shape[:-1] + (cfg.scale_blocks,)
        pools["k_scales"] = jnp.ones(sshape, jnp.float32)
        pools["v_scales"] = jnp.ones(sshape, jnp.float32)
    return pools


def copy_pages(
    pools: Dict[str, jnp.ndarray],
    src: jnp.ndarray,
    dst: jnp.ndarray,
) -> Dict[str, jnp.ndarray]:
    """Copy physical pages ``src -> dst`` across every layer and every
    pool buffer (K, V and, when quantized, their scales) — the
    copy-on-write an admission whose prefix match ended mid-page needs:
    the shared source page stays read-only for its other holders while
    the destination becomes the new slot's private tail.

    ``pools`` is the full :func:`init_pools` dict (leading layer axis);
    ``src``/``dst`` are ``(n,)`` int32 physical page ids.  Shape-stable
    and pure — jit it once; the per-admission cost is one ``n``-page
    gather+scatter."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return {k: v.at[:, dst].set(v[:, src]) for k, v in pools.items()}


def export_pages(
    pools: Dict[str, jnp.ndarray],
    pages,
) -> Dict[str, np.ndarray]:
    """Gather physical ``pages`` out of every pool buffer into HOST
    numpy arrays — :func:`copy_pages` generalized across pools: the
    device→host half of a cross-replica KV handoff or a page offload.
    The staged dict has shape ``(num_layers, n_pages, heads, page_size,
    head_dim)`` per buffer and is the wire/staging representation:
    int8 pools stage int8 values plus their fp32 scales (a quarter of
    the fp32 K/V bytes), bf16 stages as bf16 via ml_dtypes — no dtype
    ever widens, so a round trip through :func:`import_pages` is
    bit-identical."""
    idx = jnp.asarray([int(p) for p in pages], jnp.int32)
    # one batched device_get for the whole dict: the gathers dispatch
    # async, then a single transfer/sync drains them together (a
    # per-pool np.asarray would sync once per buffer)
    return jax.device_get({k: v[:, idx] for k, v in pools.items()})


def import_pages(
    pools: Dict[str, jnp.ndarray],
    staged: Dict[str, np.ndarray],
    pages: jnp.ndarray,
) -> Dict[str, jnp.ndarray]:
    """Scatter a :func:`export_pages` staging dict into physical
    ``pages`` of (usually another replica's) ``pools`` — the
    host→device half of a handoff or a fault-in.  Pure and
    shape-stable in everything but the page count; jit with the pools
    donated.  The staged buffers must come from a pool of the same
    :meth:`PagedKVCache.compat_key` family — same page layout and
    dtypes — so the set is a bit-exact move, never a cast."""
    idx = jnp.asarray(pages, jnp.int32)
    return {k: v.at[:, idx].set(jnp.asarray(staged[k], v.dtype))
            for k, v in pools.items()}


def staged_nbytes(staged: Dict[str, np.ndarray]) -> int:
    """Wire bytes of a staging dict — the handoff/offload telemetry
    estimate (int8 pools: int8 payload + fp32 scales, exactly what
    would cross a ring/DCN link)."""
    return int(sum(np.asarray(v).nbytes for v in staged.values()))


class HostOffloadPool:
    """Bounded LRU host-RAM tier for evicted prefix pages.

    Hangs off :attr:`PagedKVCache.evict_hook`: when the refcount GC
    would free an index-only page, the serving layer stages its bytes
    here instead of letting them die, keyed by the page's cumulative
    prefix hash — so the prefix cache outlives one chip's HBM.  A
    later admission whose prompt chains onto an offloaded hash faults
    the page back (:meth:`take` + :meth:`PagedKVCache.adopt_prefix_page`
    + :func:`import_pages`) bit-identically.

    Entries are whole staged pages (``(layers, 1, heads, page_size,
    head_dim)`` per pool buffer) plus the parent hash needed to relink
    the chain.  ``max_pages`` bounds host RAM; beyond it the least
    recently touched entry is dropped (at that point the tokens really
    do need recompute).  ``take`` POPS — a faulted page lives on the
    device again and the index, not this pool, owns it from then on.
    Host-only and synchronous; stats feed the ``offload_*`` gauges."""

    def __init__(self, max_pages: int):
        if max_pages < 1:
            raise ValueError("max_pages must be >= 1")
        self.max_pages = int(max_pages)
        self._entries: "collections.OrderedDict[bytes, Dict[str, Any]]" \
            = collections.OrderedDict()
        self.stats = {"offloaded": 0, "faulted": 0, "lru_evicted": 0,
                      "hits": 0, "misses": 0,
                      "bytes_in": 0, "bytes_out": 0}

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, h: bytes) -> bool:
        return h in self._entries

    def put(self, h: bytes, parent: Optional[bytes],
            staged: Dict[str, np.ndarray]) -> None:
        """Stage one page under hash ``h`` (re-staging an existing hash
        refreshes its LRU position and content), evicting the coldest
        entries past ``max_pages``."""
        if h in self._entries:
            self._entries.pop(h)
        self._entries[h] = {"parent": parent, "data": staged}
        self.stats["offloaded"] += 1
        self.stats["bytes_in"] += staged_nbytes(staged)
        while len(self._entries) > self.max_pages:
            self._entries.popitem(last=False)
            self.stats["lru_evicted"] += 1

    def parent(self, h: bytes) -> Optional[bytes]:
        return self._entries[h]["parent"]

    def take(self, h: bytes) -> Optional[Dict[str, Any]]:
        """Pop hash ``h``'s entry (``{"parent", "data"}``) for a
        fault-in, or ``None`` (and a recorded miss) when the page was
        never offloaded or has been LRU-dropped — the caller falls back
        to recompute."""
        e = self._entries.pop(h, None)
        if e is None:
            self.stats["misses"] += 1
            return None
        self.stats["hits"] += 1
        self.stats["faulted"] += 1
        self.stats["bytes_out"] += staged_nbytes(e["data"])
        return e


def write_targets(
    page_table: jnp.ndarray,
    positions: jnp.ndarray,
    valid: jnp.ndarray,
    page_size: int,
    ring: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Physical ``(pages, offsets)`` for token ``positions``.  With
    ``ring`` the table (a window class's columns, ``ring`` wide) is
    walked modulo its width: position ``p`` lives in column ``(p //
    page_size) % ring``.

    ``page_table`` is one slot's row ``(pages_per_seq,)`` (prefill:
    ``positions`` are the prompt's ``(n,)`` token indices) or the full
    ``(slots, pages_per_seq)`` table, with ``positions`` either
    ``(slots,)`` (decode: slot ``i``'s current position) or
    ``(slots, rows)`` (a verify step: each slot writes its current
    token plus k draft rows at consecutive positions).  Invalid entries
    (padding, idle slots, draft rows past the slot's real draft length)
    are redirected to the null page; a position past the slot's last
    logical page clamps (jax gather semantics) — by construction that
    only happens to finished slots decoding out a harvest window, whose
    writes are garbage by contract (speculative callers additionally
    mask ``valid`` at the table's logical extent so an overrun draft
    row can never clamp INTO a live slot's committed pages)."""
    positions = positions.astype(jnp.int32)
    idx = positions // page_size
    if ring:
        idx = idx % ring
    if page_table.ndim == 1:
        phys = jnp.take(page_table, idx)
    elif idx.ndim == 1:
        phys = jnp.take_along_axis(page_table, idx[:, None], axis=1)[:, 0]
    else:
        phys = jnp.take_along_axis(page_table, idx, axis=1)
    zero = jnp.zeros_like(phys)
    return (
        jnp.where(valid, phys, zero).astype(jnp.int32),
        jnp.where(valid, positions % page_size, zero).astype(jnp.int32),
    )


def write_tokens(
    layer_pools: Dict[str, jnp.ndarray],
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    pages: jnp.ndarray,
    offsets: jnp.ndarray,
    *,
    quantized: bool = False,
    kv_block: int = 128,
) -> Dict[str, jnp.ndarray]:
    """Scatter ``n`` new tokens into ONE layer's pools.

    ``layer_pools``: ``{"k", "v"[, "k_scales", "v_scales"]}`` with the
    layer axis already sliced off (``(num_pages, h, page_size, d)``).
    ``k_new``/``v_new``: ``(n, h, d)`` token rows — a decode step's one
    token per slot (``n = slots``) or a prefill's whole prompt
    (``n = prompt_len``).  ``pages``/``offsets``: ``(n,)`` int32
    physical targets (idle or padded entries point at the null page 0).
    Shape-stable and pure — jit it once; duplicate targets (only ever
    the null page) resolve last-writer-wins, which is exactly what a
    garbage page wants.

    K is expected "attention-ready" (RoPE already applied): the decode
    kernel rotates only q, so a cached key is rotated exactly once, at
    write time."""
    pages = pages.astype(jnp.int32)
    offsets = offsets.astype(jnp.int32)
    # the flag must agree with the pools' own layout: astype-truncating
    # float K/V into int8 pages while fmha_decode keeps dequantizing
    # with the stale scales would be silent garbage attention
    if quantized != ("k_scales" in layer_pools):
        raise ValueError(
            f"quantized={quantized} but the pools "
            f"{'carry' if 'k_scales' in layer_pools else 'lack'} "
            "k_scales/v_scales — pass quantized=config.quantized "
            "for the config that built these pools")
    out = dict(layer_pools)
    if quantized:
        from apex_tpu.ops.quantization import quantize_rows

        n, h, d = k_new.shape

        def quant(x):
            vals, scales = quantize_rows(
                x.reshape(n * h, d).astype(jnp.float32), kv_block)
            return (vals.reshape(n, h, d),
                    scales.reshape(n, h, -1))

        kq, ks = quant(k_new)
        vq, vs = quant(v_new)
        out["k"] = out["k"].at[pages, :, offsets, :].set(
            kq.astype(out["k"].dtype))
        out["v"] = out["v"].at[pages, :, offsets, :].set(
            vq.astype(out["v"].dtype))
        out["k_scales"] = out["k_scales"].at[pages, :, offsets, :].set(ks)
        out["v_scales"] = out["v_scales"].at[pages, :, offsets, :].set(vs)
    else:
        out["k"] = out["k"].at[pages, :, offsets, :].set(
            k_new.astype(out["k"].dtype))
        out["v"] = out["v"].at[pages, :, offsets, :].set(
            v_new.astype(out["v"].dtype))
    return out


def write_latent_tokens(
    pools: Dict[str, jnp.ndarray],
    layer,
    ckv_new: jnp.ndarray,
    kidx_new: Optional[jnp.ndarray],
    pages: jnp.ndarray,
    offsets: jnp.ndarray,
) -> Dict[str, jnp.ndarray]:
    """Scatter ``n`` new tokens into layer ``layer`` (a traced scalar
    is fine) of a WHOLE latent pool dict, leading layer axis included.

    ``ckv_new`` (n, latent_dim), zero-padded here to the pool's row
    width, and ``kidx_new`` (n, index_dim; None for a pool without index
    keys) are the token rows, ``pages``/``offsets`` (n,) their physical targets
    (:func:`write_targets`; idle or padded entries point at the null
    page).  Unlike :func:`write_tokens` this takes and returns the
    stacked pools: inside a layer scan that carries them, and a jit
    that donates them, each write is one scatter INTO the buffer and no
    layer's pool is ever sliced out, stacked back or copied."""
    layer = jnp.asarray(layer, jnp.int32)
    pages = pages.astype(jnp.int32)
    offsets = offsets.astype(jnp.int32)
    out = dict(pools)
    pad = pools["ckv"].shape[-1] - ckv_new.shape[-1]
    out["ckv"] = pools["ckv"].at[layer, pages, offsets].set(
        jnp.pad(ckv_new, ((0, 0), (0, pad))).astype(pools["ckv"].dtype))
    if kidx_new is not None:
        out["kidx"] = pools["kidx"].at[layer, pages, offsets].set(
            kidx_new.astype(pools["kidx"].dtype))
    return out


def write_class_rows(
    pool: jnp.ndarray,
    base,
    new: jnp.ndarray,
    pages: jnp.ndarray,
    offsets: jnp.ndarray,
) -> jnp.ndarray:
    """One token a slot into ONE stacked pool of a page class
    (``(layers, pages, heads, page_size, d)``; :func:`init_pools` with
    ``classes``): row ``new[i]`` (heads, d) goes to page ``base +
    pages[i]``, offset ``offsets[i]``, where ``base`` is the layer's
    first page in the pool seen as one run of pages (``layer_in_class *
    num_pages``) — no layer's pool is sliced out of the stack.

    The slot's current page is read, the one row replaced and the page
    written back: a gather and a scatter along the page axis alone.  A
    scatter indexed at ``[page, :, offset, :]`` makes the v5e compiler
    re-lay the WHOLE pool out around it and back for the decode kernel
    (four copies of a 2 GB pool a step); 24 pages a layer are 6 MB.
    Idle slots all target the class's null page: last writer wins."""
    flat = pool.reshape((-1,) + pool.shape[2:])
    idx = base + pages.astype(jnp.int32)
    here = (jnp.arange(pool.shape[3], dtype=jnp.int32)[None]
            == offsets.astype(jnp.int32)[:, None])[:, None, :, None]
    page = jnp.where(here, new.astype(pool.dtype)[:, :, None, :], flat[idx])
    return flat.at[idx].set(page).reshape(pool.shape)


def write_class_pages(
    pool: jnp.ndarray,
    base,
    new: jnp.ndarray,
    pages: jnp.ndarray,
) -> jnp.ndarray:
    """WHOLE pages into one stacked pool of a page class: ``new``
    (n * page_size, heads, d) are ``n`` pages' tokens in order (a
    prefill chunk, page-aligned), ``pages`` (n,) their physical targets
    (the null page for a page that holds no real token).  One scatter
    along the page axis.  Rows of a page past the prompt's end are
    written too: they sit at positions no query sees before a decode
    step has written them."""
    flat = pool.reshape((-1,) + pool.shape[2:])
    h, ps, d = pool.shape[2:]
    blocks = jnp.moveaxis(new.astype(pool.dtype).reshape(-1, ps, h, d), 2, 1)
    return flat.at[base + pages.astype(jnp.int32)].set(blocks).reshape(
        pool.shape)
