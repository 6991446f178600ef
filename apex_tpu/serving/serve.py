"""Continuous batching: admit/retire requests per step into fixed-shape
slots, so the decode step compiles ONCE and never again.

The driver's contract with XLA is the whole design: every device
computation it issues — the prefill step (monolithic or chunked) and
the decode step — has a single static shape (``max_seqs`` slots,
``max_prompt_len`` prompt window / ``prefill_chunk`` tokens per chunk,
one paged cache), and request churn only changes CONTENTS (page-table
rows, length counters, per-slot budgets, chunk offsets).  Admissions
and retirements therefore cost a few small host→device transfers,
never a recompile — ``tests/test_serving.py`` proves it with a
compile-counting spy across request generations, chunk counts and
prefix-hit patterns.

Two prompt-ingestion modes:

- **monolithic** (``prefill_chunk=None``, the PR 9 behavior): an
  admission runs ONE prefill over the whole padded prompt through the
  training attention ladder.  Simple, but every decoding slot stalls
  for the full prompt — the stop-the-world cost chunking exists to
  bound.
- **chunked** (``prefill_chunk=C`` + the model's chunk step): prompt
  ingestion is split into fixed ``C``-token chunks driven through
  ``fmha_decode``'s small-s_q path, and each serving step composes a
  token budget of [one decode token for every active slot + at most
  ONE prefill chunk] — Sarathi-style, so a new request's TTFT and the
  running requests' inter-token latency are BOTH bounded by the chunk
  size instead of the prompt length.  Chunk boundaries are absolute
  (chunk k covers positions ``[k*C, (k+1)*C)``), which is what makes
  prefix-cache hits bit-identical to cold admissions (see
  ``GPTModel.prefill_chunk``).

**Prefix caching** (``prefix_cache=True``, chunked mode only): the
cache's prefix index (``kv_cache.py``) longest-matches each admitted
prompt's full pages against previously served prompts; matched pages
are SHARED read-only into the new slot's page table (the decode kernel
takes arbitrary page tables — sharing is free at kernel level), fully
matched chunks are skipped outright, and a match ending mid-page is
resolved by one device page copy (copy-on-write at admit).  The last
prompt token is never matched — its logits seed generation.  Retired
slots drop their references; registered pages survive as reusable
cache until the refcount GC evicts them for a page-starved admission.

Loop anatomy (:meth:`ContinuousBatcher.run`):

1. **admit** — while a slot is free, a request is queued, and the page
   allocator has room (``CacheOutOfPages`` is backpressure, not an
   error): reserve pages for prompt + budget (sharing prefix-matched
   pages), then either run the monolithic prefill now or queue the
   slot for chunked ingestion.
2. **window** — up to ``harvest_every`` serving steps.  Each step runs
   at most one prefill chunk (oldest admission first) and, when any
   slot has decode budget, one fused decode step for ALL live slots.
   A slot whose last chunk completes joins the decode of that SAME
   serving step (its ``since_step`` marks the join, so the harvest
   counts exactly its own tokens).  Per-slot state (current token, length, budget, done
   flag, sampling key) lives ON DEVICE and the step updates it
   functionally: sampled ids feed the next embedding lookup directly,
   finished slots freeze (their writes target the null page), nothing
   touches the host.
3. **harvest** — ONE batched ``device_get`` per window (the PR 6
   async-harvest discipline: the window's token stack and the pending
   first-token futures resolve together).  The host then truncates
   each slot's stream at EOS/budget, retires finished slots (pages
   return to the pool / stay shared), and goes back to 1.

The trade is explicit: a slot that finishes mid-window decodes garbage
until the window closes (bounded by ``harvest_every``, and its writes
stay inside its own reserved pages), in exchange for a decode loop with
zero per-token host syncs.  Time-to-first-token is likewise quantized
to the harvest cadence — ``harvest_every=1`` recovers per-step
reporting at per-step sync cost, the same knob ``MetricsLogger``'s
``flush_every`` is — while under chunked prefill ADMISSION progress is
chunk-granular (TTFT grows with interleaved decode steps but decoding
slots never stall for a whole prompt).

Telemetry: every scheduler turn writes host spans into the profiler's
trace (:func:`apex_tpu.telemetry.spans.host_span`, on the device
trace's clock, recorded only while a profiler session runs):
``tlm.serve.pump`` holds ``admit`` (with a ``dispatch_prefill`` per
admission on the monolithic path), a ``dispatch_prefill`` per chunk and
a ``dispatch_decode`` per step (``draft`` before a verify step),
``harvest`` around each ``device_get``, ``commit`` with a
``first_token`` per request, and ``retire`` — names, stats and the
metric each is read by are in docs/observability.md "Serving spans".
The device side is segmented by the ``tlm.prefill`` / ``tlm.decode``
scopes opened inside the traced step bodies
(``GPTModel.decode_fns``).  Independently, ``span`` (``prefill`` /
``prefill_chunk`` / ``decode``) / ``request_admitted`` / ``prefix_hit``
/ ``request_done`` events land in the metrics stream —
``tools/metrics_report.py``'s serving section reads them.
``measure_stall=True`` additionally blocks on each
prefill dispatch to measure real decode-stall time (``decode_stall_s``
total / ``max_prefill_stall_s`` worst single stall while decode slots
were live) — the number the ``_dryrun_chunked_prefill`` gate and the
bench mixed-load rows compare across modes.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.serving.kv_cache import (
    CacheOutOfPages,
    HostOffloadPool,
    PagedKVCache,
    copy_pages,
    export_pages,
    import_pages,
    prompt_page_hashes,
    staged_nbytes,
)
from apex_tpu.telemetry import programs as _programs
from apex_tpu.telemetry.spans import host_span

__all__ = ["Request", "Completion", "HandoffPacket",
           "ContinuousBatcher", "init_carry"]

# shared across batchers: the CoW copy compiles once per pools shape
# (donated — without donation XLA must preserve the input pools, so a
# copy-on-write admission would rewrite EVERY pool buffer, GBs at real
# shapes, instead of one page; self.pools is rebound to the result, the
# old reference is dead.  Donation is a warning-level no-op on CPU
# backends; the copy is still correct.)
_copy_pages_jit = jax.jit(copy_pages, donate_argnums=0)

# the handoff/fault-in scatter, same donation discipline; retraces per
# distinct page count — handoffs are scheduling events, not the decode
# hot loop, and the dryrun gate counts only the serving step caches
_import_pages_jit = jax.jit(import_pages, donate_argnums=0)


def _import_state(pools, carry, staged, pages, slot, last, written,
                  steps_left, done, skey):
    """The whole import-side state flip in ONE dispatch: page scatter
    plus every per-slot carry field.  Op-by-op this is ~7 host
    dispatches per handoff — on a host-overhead-bound fleet the fusion
    is most of the handoff's cost."""
    pools = import_pages(pools, staged, pages)
    carry = {
        **carry,
        "tokens": carry["tokens"].at[slot].set(last),
        "lengths": carry["lengths"].at[slot].set(written),
        "steps_left": carry["steps_left"].at[slot].set(steps_left),
        "done": carry["done"].at[slot].set(done),
        "sample_keys": carry["sample_keys"].at[slot].set(skey),
    }
    return pools, carry


_import_state_jit = jax.jit(_import_state, donate_argnums=(0, 1))

# the three programs above are this module's; a scheduler turn reads
# the process's ledger of executables to say when it recompiled
_programs.own(copy_pages.__name__, import_pages.__name__,
              _import_state.__name__, layer="serving entry")
_ledger = _programs.ledger

#: the harvest-resolve seam: both windows pull device results through
#: this module alias, so the resilience tier can inject a hanging
#: harvest (``resilience.faults.hanging_harvests``) at the exact
#: host-sync boundary a real wedged device manifests at
_device_get = jax.device_get


@dataclasses.dataclass
class Request:
    """One generation request.  ``prompt`` is token ids; generation
    stops after ``max_new_tokens`` or at the server's ``eos_id``.
    ``seed`` (optional) pins the request's sampling stream: every draw
    folds the request's own key, so a seeded request reproduces its
    sampled tokens regardless of admission order or slot assignment.
    ``arrival_s`` (optional) is when the request reached the server, on
    the ``time.perf_counter()`` clock, stamped by whoever received it:
    with it the batcher reports the request's queue wait
    (``Completion.queue_wait_s``, ``queue_wait_us`` on its
    ``tlm.serve.dispatch_prefill`` spans); without it nothing changes."""

    uid: Any
    prompt: Sequence[int]
    max_new_tokens: int
    seed: Optional[int] = None
    arrival_s: Optional[float] = None

    def __post_init__(self):
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(self.prompt) < 1:
            raise ValueError("prompt must be non-empty")


@dataclasses.dataclass
class Completion:
    """``tokens`` are the generated ids (EOS included when hit).
    ``ttft_s`` and ``duration_s`` start at ADMISSION (the slot was
    assigned) and end at the harvest that showed the first / last
    token.  ``queue_wait_s`` is ``Request.arrival_s`` → admission, None
    when the request carried no arrival time; the TTFT a client sees,
    anchored at arrival, is ``queue_wait_s + ttft_s``."""

    uid: Any
    tokens: List[int]
    prompt_len: int
    reason: str                 # "eos" | "budget"
    ttft_s: Optional[float] = None
    duration_s: Optional[float] = None
    queue_wait_s: Optional[float] = None


@dataclasses.dataclass
class HandoffPacket:
    """One request's decode state in flight between replicas: the
    committed tokens plus the staged bytes of every KV page written so
    far (:func:`~apex_tpu.serving.kv_cache.export_pages` layout — int8
    pools stage int8 values + fp32 scales).  Built by
    :meth:`ContinuousBatcher.export_request` on the prefill replica,
    consumed by :meth:`ContinuousBatcher.import_request` on the decode
    replica; because the sampling-key schedule folds ABSOLUTE context
    length, the continued stream is token-identical to one that never
    moved (greedy always; sampled when the request is seeded — the
    same precondition fleet failover replay has)."""

    req: Request
    #: tokens committed on the source before export — the destination
    #: seeds its host stream with exactly these, so fleet progress
    #: accounting continues without a gap
    tokens: List[int]
    staged: Dict[str, np.ndarray]
    n_pages: int
    #: KV positions written on the source: ``prompt + len(tokens) - 1``
    #: (the newest token's K/V is written by the NEXT decode step)
    written: int
    wire_bytes: int
    #: the source cache's page-layout family
    #: (:meth:`~apex_tpu.serving.kv_cache.PagedKVCache.compat_key`) —
    #: import refuses a mismatch rather than corrupt pages
    compat_key: tuple
    #: the prompt's cumulative page hashes, so the destination's prefix
    #: index adopts the imported pages without re-hashing
    hashes: Optional[List[bytes]] = None


def init_carry(max_seqs: int, key: Optional[jnp.ndarray] = None,
               sharding: Optional[Any] = None,
               extras: Optional[Dict[str, Any]] = None
               ) -> Dict[str, jnp.ndarray]:
    """The decode step's per-slot device state: all slots idle.
    ``sample_keys`` holds one PRNG key row per slot (overwritten at
    admission — from ``Request.seed`` when given).  ``sharding`` places
    it once where the compiled steps return it
    (``GPTDecodeFns.carry_sharding``): a carry that starts off the mesh
    makes the second decode step compile again.  ``extras`` are further
    entries a decode step keeps in its carry beside the per-slot five
    (``decode_fn.carry_extras``: their initial values): the batcher
    threads them through untouched."""
    s = max_seqs
    base = jnp.asarray(
        key if key is not None else jax.random.PRNGKey(0), jnp.uint32)
    carry = {
        "tokens": jnp.zeros((s,), jnp.int32),
        "lengths": jnp.zeros((s,), jnp.int32),
        "steps_left": jnp.zeros((s,), jnp.int32),
        "done": jnp.ones((s,), bool),
        "sample_keys": jnp.broadcast_to(base[None], (s,) + base.shape),
        **(extras or {}),
    }
    return carry if sharding is None else jax.device_put(carry, sharding)


class ContinuousBatcher:
    """Drive the serving step functions over a paged cache.

    ``prefill_fn(pools, tokens (1, max_prompt_len) i32, length () i32,
    page_row (pages_per_seq,) i32, key) -> (pools, first_token ()
    i32)`` — writes the prompt's K/V and samples the first token (the
    key is the request's slot key; greedy servers ignore it).

    ``decode_fn(pools, carry, page_table (max_seqs, pages_per_seq) i32)
    -> (pools, carry)`` — one token for every live slot; must freeze
    slots whose ``done`` is set (null-page writes, unchanged token /
    length / budget) and maintain ``done |= sampled == eos or budget
    exhausted``.  A step may keep entries of its own in the carry beside
    the per-slot five (``decode_fn.carry_extras`` gives their initial
    values): they are threaded through untouched, and one named
    ``counters`` is fetched with every harvest (``step_counters``).

    ``chunk_fn(pools, tokens (C,) i32, start, prompt_len, write_from,
    page_row, key) -> (pools, first_token, logits)`` — one
    ``prefill_chunk``-token ingestion step (chunked mode only); the
    first token / logits are meaningful on the chunk containing the
    last prompt token.  :func:`apex_tpu.models.gpt.GPTModel.decode_fns`
    builds the canonical set.  Where the cache keeps a state a slot
    beside its pages (``KVCacheConfig.slot_states``), ``chunk_fn`` is
    also given ``slot=`` (the row of the state it reads and writes), and
    ``decode_fn`` must leave the state of a slot that is not decoding
    (``done``: empty, or still between its prompt's chunks) as it was.

    All are expected to be jitted ONCE outside; the driver never
    changes a shape.  ``logger`` is an optional
    :class:`~apex_tpu.telemetry.MetricsLogger` for span/request events.
    ``prefix_cache=True`` (chunked mode only) shares identical prompt
    prefixes across requests through the cache's refcounted prefix
    index.  ``measure_stall=True`` blocks on prefill dispatches to
    fill the ``decode_stall_s`` / ``max_prefill_stall_s`` counters
    (real wall time, for the bench/dryrun comparisons; off by default
    to keep dispatches async).

    **Speculative decoding** (``spec_fn`` + ``speculate_k``, built by
    ``decode_fns(speculate_k=K)``): each serving step drafts up to K
    tokens per live slot from a host-side ``draft_source`` (default
    :class:`~apex_tpu.serving.speculate.NGramDraftSource`; a
    :class:`~apex_tpu.serving.speculate.NullDraftSource` degrades to
    plain one-token decode), runs the verify-and-commit step
    (``spec_fn(pools, carry, page_table, drafts (S, K) i32, draft_len
    (S,) i32) -> (pools, carry, targets (S, K+1) i32, n_commit (S,)
    i32)``), and commits a VARIABLE number of tokens per slot under the
    same fixed shapes — zero recompiles across every acceptance
    pattern.  Because drafting needs the committed context, the
    speculative window resolves each step's commits on the spot (one
    small sync per verify step, ``harvest_every`` bounds steps per
    window as usual); budget accounting is exact by host count, so
    harvest/:meth:`progress`/fleet failover see multi-token advances
    correctly.
    """

    def __init__(
        self,
        prefill_fn: Callable,
        decode_fn: Callable,
        cache: PagedKVCache,
        pools: Dict[str, jnp.ndarray],
        *,
        max_prompt_len: int,
        harvest_every: int = 8,
        eos_id: Optional[int] = None,
        key: Optional[jnp.ndarray] = None,
        logger: Optional[Any] = None,
        chunk_fn: Optional[Callable] = None,
        prefill_chunk: Optional[int] = None,
        prefix_cache: bool = False,
        measure_stall: bool = False,
        spec_fn: Optional[Callable] = None,
        speculate_k: Optional[int] = None,
        draft_source: Optional[Any] = None,
        offload: Optional[HostOffloadPool] = None,
    ):
        if harvest_every < 1:
            raise ValueError("harvest_every must be >= 1")
        if offload is not None and not prefix_cache:
            raise ValueError(
                "offload requires prefix_cache=True (the offload tier "
                "keys staged pages by prefix hash — without the index "
                "nothing could ever fault them back)")
        # the device step freezes slots at ITS eos id; the host
        # truncates at THIS one.  A decode_fn that declares its freeze
        # id (GPTModel.decode_fns stamps decode.eos_id) must agree, or
        # frozen slots would replay their EOS token every harvest step
        # while the host keeps appending it.
        _unset = object()
        fn_eos = getattr(decode_fn, "eos_id", _unset)
        if fn_eos is not _unset and fn_eos != eos_id:
            raise ValueError(
                f"eos_id mismatch: decode_fn freezes slots at "
                f"{fn_eos!r} but the batcher truncates at {eos_id!r} — "
                "pass the same eos_id to decode_fns() and "
                "ContinuousBatcher()")
        if (prefill_chunk is None) != (chunk_fn is None):
            raise ValueError(
                "chunked prefill needs BOTH chunk_fn and prefill_chunk "
                "(decode_fns(prefill_chunk=C) builds the pair)")
        if prefill_chunk is not None and int(prefill_chunk) < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        fn_chunk = getattr(chunk_fn, "prefill_chunk", _unset)
        if chunk_fn is not None and fn_chunk is not _unset and \
                int(fn_chunk) != int(prefill_chunk):
            raise ValueError(
                f"prefill_chunk mismatch: chunk_fn was compiled for "
                f"{fn_chunk}-token chunks but the batcher schedules "
                f"{prefill_chunk}-token chunks")
        if prefix_cache and prefill_chunk is None:
            raise ValueError(
                "prefix_cache requires chunked prefill (the monolithic "
                "prefill recomputes every position and cannot skip "
                "matched chunks)")
        if cache.config.has_state and prefill_chunk is None:
            raise ValueError(
                "a cache with per-slot state (slot_states) is filled "
                "chunk by chunk: the chunk step is told its slot, the "
                "monolithic prefill is not (decode_fns(prefill_chunk=C))")
        if prefix_cache and cache.config.has_state:
            raise ValueError(
                "prefix_cache over a cache with per-slot state is not "
                "built: a hit would also need the state after the shared "
                "prefix, which the prefix index does not keep (ROADMAP, R "
                "queue)")
        if prefix_cache and any(c.window
                                for c in cache.config.page_classes):
            raise ValueError(
                "prefix_cache over a cache with a window class is not "
                "built: a hit would also need the window layers' last "
                "`window` tokens, which the prefix index does not keep "
                "(ROADMAP, R queue)")
        if (spec_fn is None) != (speculate_k is None):
            raise ValueError(
                "speculative decoding needs BOTH spec_fn and "
                "speculate_k (decode_fns(speculate_k=K) builds the "
                "pair)")
        if spec_fn is not None:
            if int(speculate_k) < 1:
                raise ValueError(
                    f"speculate_k must be >= 1, got {speculate_k}")
            fn_k = getattr(spec_fn, "speculate_k", _unset)
            if fn_k is not _unset and int(fn_k) != int(speculate_k):
                raise ValueError(
                    f"speculate_k mismatch: spec_fn was compiled for "
                    f"k={fn_k} drafts but the batcher schedules "
                    f"k={speculate_k}")
            fn_spec_eos = getattr(spec_fn, "eos_id", _unset)
            if fn_spec_eos is not _unset and fn_spec_eos != eos_id:
                raise ValueError(
                    f"eos_id mismatch: spec_fn freezes slots at "
                    f"{fn_spec_eos!r} but the batcher truncates at "
                    f"{eos_id!r}")
        if draft_source is not None and spec_fn is None:
            raise ValueError(
                "draft_source without spec_fn — pass "
                "decode_fns(speculate_k=K)'s spec step too")
        self.spec_fn = spec_fn
        self.speculate_k = (None if speculate_k is None
                            else int(speculate_k))
        #: static candidate-tree parents when spec_fn was compiled for
        #: TREE verification (decode_fns(spec_tree=...)); None = chain
        self.spec_tree = getattr(spec_fn, "spec_tree", None)
        self._tree_chain_rows: tuple = ()
        if self.spec_tree is not None:
            from apex_tpu.serving.speculate import tree_chain_rows

            self.spec_tree = tuple(int(p) for p in self.spec_tree)
            self._tree_chain_rows = tree_chain_rows(self.spec_tree)
        if spec_fn is not None and draft_source is None:
            # a draft model bound at decode_fns(draft_model=...) rides
            # the compiled step into the batcher; n-gram
            # self-speculation stays the fallback
            draft_source = getattr(spec_fn, "draft_source", None)
        if spec_fn is not None and draft_source is None:
            from apex_tpu.serving.speculate import NGramDraftSource

            draft_source = NGramDraftSource(self.speculate_k)
        if draft_source is not None:
            ds_tree = getattr(draft_source, "tree", None)
            if ds_tree is not None and self.spec_tree is not None and \
                    tuple(int(p) for p in ds_tree) != self.spec_tree:
                raise ValueError(
                    "draft_source drafts for a different candidate "
                    f"tree ({tuple(ds_tree)}) than spec_fn verifies "
                    f"({self.spec_tree}) — rebuild one of them")
            if ds_tree is not None and self.spec_tree is None:
                raise ValueError(
                    "draft_source drafts a candidate tree but spec_fn "
                    "verifies a chain — pass the same tree to "
                    "decode_fns(spec_tree=...)")
        self.draft_source = draft_source
        #: host-side speculation scoreboard (the bench rows and the
        #: accepted-tokens/step gates read it): per-verify-step totals
        #: plus per-draft-source hit counts, off-ramp (non-first-child
        #: tree path) commits, and host draft wall-time
        self.spec_stats = {
            "steps": 0, "slot_steps": 0, "drafted": 0, "accepted": 0,
            "committed": 0, "by_source": {}, "offramp": 0,
            "draft_s": 0.0,
        }
        self.prefill_fn = prefill_fn
        self.decode_fn = decode_fn
        self.chunk_fn = chunk_fn
        #: active weight width + per-step weight-stream bytes, stamped
        #: on the decode callable by GPTModel.decode_fns — ride on the
        #: decode span events so tools/metrics_report.py can put
        #: weight-stream GB/s next to decode tokens/s without ever
        #: seeing the params
        self.weight_dtype = getattr(decode_fn, "weight_dtype", None)
        self.weight_stream_bytes = getattr(
            decode_fn, "weight_stream_bytes", None)
        self.tp = getattr(decode_fn, "tp", None)
        self.prefill_chunk = (None if prefill_chunk is None
                              else int(prefill_chunk))
        #: brownout levers (the fleet's degradation ladder drives
        #: both — :class:`apex_tpu.fleet.router.BrownoutPolicy`):
        #: ``speculation_enabled=False`` falls back to plain one-token
        #: windows without touching the compiled steps (spec_fn stays
        #: warm for recovery); ``chunk_throttle=N`` runs an
        #: interleaved prefill chunk on every Nth window iteration
        #: instead of every one (N=1 = no throttle).  Both change
        #: SCHEDULING only — streams stay token-identical, because
        #: the key schedule folds context length, not step timing.
        self.speculation_enabled = True
        self.chunk_throttle = 1
        self._chunk_tick = 0
        self.prefix_cache = bool(prefix_cache)
        self.measure_stall = bool(measure_stall)
        self.cache = cache
        self.pools = pools
        #: host-RAM tier for evicted prefix pages: wired into the
        #: cache's refcount-GC seam — index-only pages the GC would
        #: free are staged to host instead, and admissions fault them
        #: back bit-identically (:meth:`_fault_in`)
        self.offload = offload
        if offload is not None:
            cache.evict_hook = self._stage_to_offload
        #: the disaggregation lever: a PREFILL-role replica's batcher
        #: runs chunks and resolves first tokens but never dispatches a
        #: decode/verify step — prompt-complete slots wait in
        #: ``_meta`` for the fleet's handoff sweep to export them.
        #: Scheduling-only, like the brownout levers: flipping it back
        #: on (decode-replica-loss fallback) needs no recompile and
        #: changes no stream's tokens.
        self.decode_enabled = True
        self.max_prompt_len = int(max_prompt_len)
        self.harvest_every = int(harvest_every)
        self.eos_id = eos_id
        self.logger = logger
        self.carry = init_carry(
            cache.config.max_seqs, key,
            sharding=getattr(decode_fn, "carry_sharding", None),
            extras=getattr(decode_fn, "carry_extras", None))
        #: host copy of ``carry["counters"]`` (a step's own running
        #: counts, where its carry has that entry) as of the last
        #: harvest: it comes over in the harvest's one transfer
        self.step_counters: Optional[np.ndarray] = None
        self._base_key = (key if key is not None
                          else jax.random.PRNGKey(0))
        self._n_admits = 0
        self._meta: Dict[int, dict] = {}      # slot -> request meta
        self._prefilling: "collections.OrderedDict[int, dict]" = \
            collections.OrderedDict()         # slot -> chunk progress
        self._first_tok: Dict[int, jnp.ndarray] = {}
        self.completions: Dict[Any, Completion] = {}
        self.steps = 0
        self.windows = 0
        self.turns = 0          # scheduler turns (``pump`` calls)
        #: turns in which jax obtained an executable (a shape that was
        #: not warmed: every slot waited for it), and the last such
        #: turn's number; the process's ledger says what it obtained
        #: (``apex_tpu.telemetry.programs``)
        self.compiled_turns = 0
        self.last_compiled_turn: Optional[int] = None
        self.prefill_chunks = 0
        #: prefill wall time spent while >= 1 decoding slot was live
        #: (total, and the worst single stall) — meaningful when
        #: ``measure_stall`` blocked on the dispatches
        self.decode_stall_s = 0.0
        self.max_prefill_stall_s = 0.0
        #: logits of the most recent completed prefill's last prompt
        #: token (chunked mode) — the bit-identity seam the prefix-hit
        #: gates compare across cold/hit admissions
        self.last_prefill_logits: Optional[jnp.ndarray] = None
        self.prefix_stats = {
            "admissions": 0, "hits": 0, "matched_tokens": 0,
            "shared_pages": 0, "tokens_skipped": 0, "copied_pages": 0,
        }

    # ------------------------------------------------------------ events
    def _event(self, kind: str, **fields) -> None:
        if self.logger is not None:
            self.logger.event(kind, **fields)

    def _weight_fields(self) -> dict:
        """The decode-span weight-stream fields (only when the decode
        step declared its pool): the width label plus the bytes ONE
        CHIP streams per step — ``steps * weight_bytes / dur_s`` is
        the window's per-chip weight-stream GB/s — and the
        tensor-parallel degree the step was compiled for, stamped
        exactly like ``weight_dtype``."""
        if self.weight_dtype is None:
            return {}
        f = {"weight_dtype": self.weight_dtype}
        if self.weight_stream_bytes is not None:
            f["weight_bytes"] = int(self.weight_stream_bytes)
        if self.tp is not None:
            f["tp"] = int(self.tp)
        return f

    def _emit_gauges(self, queue_depth: int) -> None:
        """The serving load gauges (``pages_free`` / ``pages_shared`` /
        ``live_slots`` / ``queue_depth``): pure host mirrors, no device
        sync — the same signals the fleet router scores replicas by,
        exported so a single-replica operator sees them too."""
        if self.logger is None:
            return
        self.logger.gauge("pages_free", self.cache.allocator.num_free)
        self.logger.gauge("pages_shared",
                          self.cache.allocator.num_shared)
        self.logger.gauge("live_slots", self.live_slots)
        self.logger.gauge("queue_depth", int(queue_depth))

    # ------------------------------------------------------ host mirrors
    @property
    def live_slots(self) -> int:
        """Slots currently decoding or prefilling — host state only."""
        return len(self._meta) + len(self._prefilling)

    def progress(self) -> Dict[Any, List[int]]:
        """Harvested tokens so far for every in-flight request (uid ->
        committed tokens; a still-prefilling request maps to ``[]``).
        Harvest is the commit point: tokens a later window would
        surface are NOT included — exactly the replayable state the
        fleet failover log records."""
        out: Dict[Any, List[int]] = {
            m["req"].uid: list(m["tokens"])
            for m in self._meta.values()
        }
        for st in self._prefilling.values():
            out[st["req"].uid] = []
        return out

    def _note_stall(self, dur_s: float) -> None:
        """Account prefill work that ran while decode slots were live
        — the stall the chunk budget exists to bound."""
        if any(m["finished"] is None for m in self._meta.values()):
            self.decode_stall_s += dur_s
            self.max_prefill_stall_s = max(
                self.max_prefill_stall_s, dur_s)

    def _slot_key(self, req: Request) -> jnp.ndarray:
        """The request's sampling key: its own seed when given, else a
        fold of the server key by admission index."""
        if req.seed is not None:
            return jax.random.PRNGKey(int(req.seed))
        return jax.random.fold_in(self._base_key, self._n_admits)

    def _prefill_span(self, req: Request, slot: int, chunk: int,
                      t_admit: float):
        """The ``dispatch_prefill`` span of one prefill / chunk call
        (``chunk`` -1 on the monolithic path).  Where the chunk function
        knows what its positions leave of its attention
        (``chunk_fn.k_blocks``), the span says so: key blocks computed
        and key blocks of the context read."""
        span = host_span("serve.dispatch_prefill", uid=req.uid, slot=slot,
                         prompt_tokens=len(req.prompt), chunk=chunk)
        if req.arrival_s is not None:
            span.set_metadata(
                queue_wait_us=int(1e6 * (t_admit - req.arrival_s)))
        if chunk >= 0 and self.cache.config.has_state:
            # where the chunk's state came from: zeros at the prompt's
            # start, else the slot's row
            span.set_metadata(ssm_state_in="carried" if chunk else "zero")
        k_blocks = getattr(self.chunk_fn, "k_blocks", None)
        if chunk >= 0 and k_blocks is not None:
            run, extent = k_blocks(chunk * self.prefill_chunk)
            span.set_metadata(k_blocks_run=run, k_blocks_extent=extent)
        return span

    def _slot_live(self, slot: int, first, req: Request, plen: int,
                   t_admit: float, skey) -> None:
        """Prefill finished: flip the slot into the decoding set."""
        budget_left = req.max_new_tokens - 1
        c = self.carry
        self.carry = {
            **c,
            "tokens": c["tokens"].at[slot].set(first),
            "lengths": c["lengths"].at[slot].set(plen),
            "steps_left": c["steps_left"].at[slot].set(budget_left),
            "done": c["done"].at[slot].set(budget_left <= 0),
            "sample_keys": c["sample_keys"].at[slot].set(
                jnp.asarray(skey, jnp.uint32)),
        }
        self._first_tok[slot] = first
        self._meta[slot] = {
            "req": req, "tokens": [], "t_admit": t_admit,
            "t_first": None, "finished": None,
            # decode steps before this mark predate the slot's join —
            # the harvest must not read them (mid-window chunked joins)
            "since_step": self.steps,
        }

    # ------------------------------------------------------------- admit
    def _admit(self, queue) -> None:
        with host_span("serve.admit") as span:
            admitted, backpressured = self._admit_free_slots(queue)
            span.set_metadata(admitted=admitted,
                              backpressured=backpressured)
        self._emit_gauges(len(queue))

    def _admit_free_slots(self, queue):
        """Returns (requests admitted, 1 if the page allocator refused
        the next one else 0)."""
        cfg = self.cache.config
        free = [s for s in range(cfg.max_seqs)
                if s not in self._meta and s not in self._prefilling]
        admitted = 0
        for slot in free:
            if not queue:
                break
            req = queue[0]
            plen = len(req.prompt)
            if plen > self.max_prompt_len:
                raise ValueError(
                    f"prompt of {plen} tokens exceeds max_prompt_len "
                    f"{self.max_prompt_len}")
            if self.offload is not None and len(self.offload):
                # fault offloaded prefix pages back BEFORE the match,
                # so admit() sees them as resident and shares them —
                # the chunks they cover are skipped, not recomputed
                self._fault_in(req.prompt)
            try:
                res = self.cache.admit(
                    slot, plen + req.max_new_tokens,
                    prompt_tokens=(req.prompt if self.prefix_cache
                                   else None))
            except CacheOutOfPages:
                return admitted, 1          # backpressure: wait for pages
            queue.popleft()
            admitted += 1
            skey = self._slot_key(req)
            self._n_admits += 1
            t_admit = time.perf_counter()
            page_row = jnp.asarray(self.cache.page_table[slot])
            self._event("request_admitted", uid=req.uid, slot=slot,
                        prompt_tokens=plen,
                        budget=req.max_new_tokens)
            if self.prefill_chunk is not None:
                self._admit_chunked(slot, req, res, skey, t_admit,
                                    page_row)
                continue
            # ---- monolithic PR 9 path: one prefill over the padded
            # prompt, the slot joins decode immediately
            toks = np.zeros((1, self.max_prompt_len), np.int32)
            toks[0, :plen] = np.asarray(req.prompt, np.int32)
            with self._prefill_span(req, slot, -1, t_admit):
                if self.measure_stall:
                    # drain the in-order device queue first, so the
                    # measured stall is THIS prefill's work, not the
                    # previously dispatched steps it queued behind
                    jax.block_until_ready(self.carry["tokens"])
                t0 = time.perf_counter()
                self.pools, first = self.prefill_fn(
                    self.pools, jnp.asarray(toks),
                    jnp.int32(plen), page_row, skey)
                if self.measure_stall:
                    jax.block_until_ready(first)
                dispatch_s = time.perf_counter() - t0
            self._note_stall(dispatch_s)
            self.cache.lengths[slot] = plen
            self._slot_live(slot, first, req, plen, t_admit, skey)
            self._event("span", span="prefill", slot=slot,
                        tokens=plen, dispatch_s=round(dispatch_s, 6))
        return admitted, 0

    def _admit_chunked(self, slot, req, res, skey, t_admit,
                       page_row) -> None:
        C = self.prefill_chunk
        plen = len(req.prompt)
        if res.copied_page is not None:
            # copy-on-write: the prefix match ended inside this page —
            # the shared source stays read-only for its other holders,
            # the copy becomes the slot's private tail
            src, dst = res.copied_page
            self.pools = _copy_pages_jit(
                self.pools, jnp.asarray([src], jnp.int32),
                jnp.asarray([dst], jnp.int32))
        n_chunks = -(-plen // C)
        toks = np.zeros((n_chunks * C,), np.int32)
        toks[:plen] = np.asarray(req.prompt, np.int32)
        first_chunk = res.matched_tokens // C
        self._prefilling[slot] = {
            "req": req, "toks": toks, "plen": plen,
            "next_chunk": first_chunk,
            "write_from": res.matched_tokens,
            "skipped": first_chunk * C,
            # admission already hashed the prompt; registration reuses
            "hashes": res.page_hashes,
            "key": skey, "t_admit": t_admit, "chunk_s": 0.0,
            "page_row": page_row,
        }
        if self.prefix_cache:
            st = self.prefix_stats
            st["admissions"] += 1
            if res.matched_tokens:
                st["hits"] += 1
            st["matched_tokens"] += res.matched_tokens
            st["shared_pages"] += res.shared_pages
            st["tokens_skipped"] += first_chunk * C
            if res.copied_page is not None:
                st["copied_pages"] += 1
            self._event(
                "prefix_hit", uid=req.uid, slot=slot,
                matched_tokens=res.matched_tokens,
                shared_pages=res.shared_pages,
                tokens_skipped=first_chunk * C,
                copied=res.copied_page is not None)

    # ----------------------------------------------------- prefill chunk
    def _prefill_step(self, slot: int) -> float:
        """Run ONE chunk of the oldest in-flight admission; on the last
        chunk the slot joins the decoding set with the sampled first
        token.  Returns the chunk's dispatch wall time so the window
        can keep it OUT of the decode span's duration."""
        st = self._prefilling[slot]
        C = self.prefill_chunk
        c0 = st["next_chunk"] * C
        with self._prefill_span(st["req"], slot, st["next_chunk"],
                                st["t_admit"]):
            if self.measure_stall:
                # drain the queue (see _admit): attribute only this
                # chunk's work to the stall, not the decode step it
                # queued behind
                jax.block_until_ready(self.carry["tokens"])
            t0 = time.perf_counter()
            self.pools, tok, logits = self.chunk_fn(
                self.pools, st["toks"][c0:c0 + C], c0, st["plen"],
                st["write_from"], st["page_row"], st["key"],
                **({"slot": slot} if self.cache.config.has_state else {}))
            if self.measure_stall:
                jax.block_until_ready(tok)
            dur = time.perf_counter() - t0
        self._note_stall(dur)
        st["chunk_s"] += dur
        st["next_chunk"] += 1
        self.prefill_chunks += 1
        self._event("span", span="prefill_chunk", slot=slot,
                    chunk=st["next_chunk"] - 1, start=c0,
                    tokens=min(C, st["plen"] - c0),
                    dispatch_s=round(dur, 6))
        if st["next_chunk"] * C < st["plen"]:
            return dur
        # last chunk: the prompt is fully ingested
        req = st["req"]
        del self._prefilling[slot]
        self.cache.lengths[slot] = st["plen"]
        if self.prefix_cache:
            self.cache.register_prefix(slot, req.prompt,
                                       hashes=st["hashes"])
        self.last_prefill_logits = logits
        self._slot_live(slot, tok, req, st["plen"], st["t_admit"],
                        st["key"])
        self._event("span", span="prefill", slot=slot,
                    tokens=st["plen"] - st["skipped"],
                    dispatch_s=round(st["chunk_s"], 6))
        return dur

    # ------------------------------------------------------------ decode
    def _window_budget(self, base: int) -> int:
        """Decode steps someone can still use: the longest remaining
        budget among live slots, net of the steps each already took
        this window (generated-so-far counts the admit-time first
        token while it is still an unharvested future).  This is
        one-token-per-step arithmetic — the PLAIN window's invariant;
        the speculative window commits a variable count per step and
        does its budget math by exact host count instead
        (:meth:`_spec_window`)."""
        budget = 0
        for s, m in self._meta.items():
            if m["finished"] is not None:
                continue
            taken = self.steps - max(m.get("since_step", base), base)
            rem = (m["req"].max_new_tokens - len(m["tokens"])
                   - (1 if s in self._first_tok else 0) - taken)
            budget = max(budget, rem)
        return budget

    def _absorb_firsts(self, firsts_h, t_h: float) -> None:
        """Fold resolved admit-time first tokens into the host streams
        (shared by the plain harvest and the speculative window)."""
        for slot, tok in firsts_h.items():
            m = self._meta[slot]
            with host_span("serve.first_token", uid=m["req"].uid,
                           slot=slot):
                m["tokens"].append(int(tok))
                m["t_first"] = t_h
                if self.eos_id is not None and int(tok) == self.eos_id:
                    m["finished"] = "eos"
                elif len(m["tokens"]) >= m["req"].max_new_tokens:
                    m["finished"] = "budget"

    def _retire(self, done_h, t_h: float) -> None:
        """Retire finished slots: device ``done`` and host finish
        detection agree by construction (same eos/budget rules); host
        is authoritative for truncation, device for freezing."""
        finished = [s for s, m in self._meta.items()
                    if m["finished"] is not None or bool(done_h[s])]
        with host_span("serve.retire", retired=len(finished)):
            self._retire_slots(finished, t_h)

    def _retire_slots(self, finished, t_h: float) -> None:
        for slot in finished:
            m = self._meta[slot]
            reason = m["finished"] or (
                "eos" if (self.eos_id is not None and m["tokens"]
                          and m["tokens"][-1] == self.eos_id)
                else "budget")
            req = m["req"]
            comp = Completion(
                uid=req.uid, tokens=m["tokens"],
                prompt_len=len(req.prompt), reason=reason,
                ttft_s=(None if m["t_first"] is None
                        else m["t_first"] - m["t_admit"]),
                duration_s=t_h - m["t_admit"],
                queue_wait_s=(None if req.arrival_s is None
                              else m["t_admit"] - req.arrival_s),
            )
            self.completions[req.uid] = comp
            self.cache.retire(slot)
            c = self.carry
            self.carry = {**c, "done": c["done"].at[slot].set(True)}
            del self._meta[slot]
            self._event("request_done", uid=req.uid, slot=slot,
                        new_tokens=len(comp.tokens), reason=reason,
                        ttft_s=(None if comp.ttft_s is None
                                else round(comp.ttft_s, 6)),
                        duration_s=round(comp.duration_s, 6))

    def _draft(self, live):
        """Host-side drafts for one verify step: (drafts (S, cols),
        draft lengths (S,), slot -> source name, seconds spent in the
        draft source)."""
        k = self.speculate_k
        S = self.cache.config.max_seqs
        tree = self.spec_tree
        # chain mode offers k draft columns; tree mode offers one per
        # non-root node (rows 1..R-1 of the static parents tuple)
        n_cols = k if tree is None else len(tree) - 1
        chain_rows = self._tree_chain_rows
        draft_s = 0.0
        drafts = np.zeros((S, n_cols), np.int32)
        dlens = np.zeros((S,), np.int32)
        sources: Dict[int, str] = {}
        for s, m in live:
            # exact multi-token budget: cap the draft under the
            # slot's remaining tokens (the +1 verify bonus row
            # fills the rest), so the device can never be offered
            # more rows than the budget admits
            rem = m["req"].max_new_tokens - len(m["tokens"])
            cap = min(k, rem - 1)
            if cap <= 0:
                continue
            td = time.perf_counter()
            toks, src = self.draft_source.draft(
                list(m["req"].prompt) + m["tokens"],
                len(m["req"].prompt))
            draft_s += time.perf_counter() - td
            if tree is not None and len(toks) == n_cols:
                # tree-aware source: one token per non-root node,
                # already laid out in row order; the device's
                # depth-vs-draft_len mask trims anything past cap
                drafts[s, :] = toks
                dlens[s] = min(k, cap)
                sources[s] = src
                continue
            toks = toks[:cap]
            if toks:
                if tree is None:
                    drafts[s, :len(toks)] = toks
                else:
                    # chain-shaped source under a tree verify:
                    # place the chain on the tree's first-child
                    # spine, leave sibling rows padded (pad rows
                    # only commit when they EQUAL the coupled
                    # target draw, which is the identical token)
                    for i, row in enumerate(chain_rows[:len(toks)]):
                        drafts[s, row - 1] = toks[i]
                dlens[s] = len(toks)
                sources[s] = src
        return drafts, dlens, sources, draft_s

    def _commit_verified(self, live, out_h, nc_h, path_h, dlens,
                         sources) -> int:
        """Fold one verify step's resolved commits into the host
        streams and the speculation scoreboard; returns the tokens
        committed."""
        drafted = accepted = committed = offramp = 0
        commits: List[int] = []
        ev_src: Dict[str, Dict[str, int]] = {}
        chain_set = set(self._tree_chain_rows)
        for s, m in live:
            nc = int(nc_h[s])
            for j in range(nc):
                tok = int(out_h[s, j])
                m["tokens"].append(tok)
                # host length mirror follows the device's commit
                self.cache.lengths[s] += 1
                if self.eos_id is not None and tok == self.eos_id:
                    m["finished"] = "eos"
                elif len(m["tokens"]) >= m["req"].max_new_tokens:
                    m["finished"] = "budget"
            dl = int(dlens[s])
            acc = max(min(nc - 1, dl), 0)
            if path_h is not None:
                # committed tree nodes off the first-child spine =
                # tokens a chain verify would have rejected
                offramp += sum(
                    1 for t in range(1, acc + 1)
                    if int(path_h[s, t]) not in chain_set)
            drafted += dl
            accepted += acc
            committed += nc
            commits.append(nc)
            src = sources.get(s)
            if src is not None:
                rec = ev_src.setdefault(
                    src, {"drafted": 0, "accepted": 0})
                rec["drafted"] += dl
                rec["accepted"] += acc
        st = self.spec_stats
        st["steps"] += 1
        st["slot_steps"] += len(live)
        st["drafted"] += drafted
        st["accepted"] += accepted
        st["committed"] += committed
        st["offramp"] += offramp
        for src, rec in ev_src.items():
            tot = st["by_source"].setdefault(
                src, {"drafted": 0, "accepted": 0})
            tot["drafted"] += rec["drafted"]
            tot["accepted"] += rec["accepted"]
        # one spec_accept event per verify step, built entirely
        # from the commit resolve this loop already performs — no
        # host syncs beyond the per-step one the draft seam needs
        self._event("spec_accept", slots=len(live),
                    drafted=drafted, accepted=accepted,
                    committed=committed, commits=commits,
                    by_source=ev_src, offramp=offramp)
        return committed

    def _spec_window(self) -> None:
        """One harvest window of speculative serving steps: draft on
        the host, verify-and-commit on device, resolve the commits.

        The plain window stacks ``harvest_every`` one-token steps and
        resolves them in ONE device_get; here each verify step's
        commits resolve immediately, because the NEXT step's host-side
        draft needs them (the pure-host draft seam's cost — one small
        sync per verify step, amortized over up to k+1 committed
        tokens).  Budget accounting is exact by host count
        (``max_new_tokens - len(tokens)``), not by step arithmetic —
        the one-token-per-step assumption ``_window_budget`` encodes
        does not survive multi-token advances.  The draft length is
        additionally capped at remaining-budget − 1 so no live row is
        ever written past the slot's reserved pages."""
        tree = self.spec_tree
        page_table = jnp.asarray(self.cache.page_table)
        t0 = time.perf_counter()
        chunk_s = 0.0
        draft_s = 0.0
        steps = kept = 0
        done_h = None
        for _ in range(self.harvest_every):
            did_chunk = False
            if self._prefilling:
                self._chunk_tick += 1
                if self._chunk_tick % max(1, self.chunk_throttle) == 0:
                    chunk_s += self._prefill_step(
                        next(iter(self._prefilling)))
                    did_chunk = True
            # resolve pending admit-time first tokens NOW: the draft
            # source needs the full committed context, and this window
            # syncs per verify step anyway
            if self._first_tok:
                firsts = {s: self._first_tok.pop(s)
                          for s in list(self._first_tok)}
                with host_span("serve.harvest", steps=0,
                               firsts=len(firsts)):
                    firsts_h = _device_get(firsts)
                with host_span("serve.commit", tokens=len(firsts_h)):
                    self._absorb_firsts(firsts_h, time.perf_counter())
            # a prefill-role replica stops here: chunks ran, firsts
            # resolved, but no verify step — slots await handoff
            if not self.decode_enabled:
                if not did_chunk:
                    break
                continue
            live = [(s, m) for s, m in self._meta.items()
                    if m["finished"] is None]
            if not live:
                if not did_chunk:
                    break
                continue
            with host_span("serve.draft", slots=len(live)):
                drafts, dlens, sources, dt = self._draft(live)
            draft_s += dt
            path_h = None
            with host_span("serve.dispatch_decode", step=self.steps,
                           live_slots=len(live)):
                if tree is None:
                    self.pools, self.carry, out, n_commit = \
                        self.spec_fn(self.pools, self.carry,
                                     page_table, drafts, dlens)
                else:
                    (self.pools, self.carry, out, n_commit,
                     path) = self.spec_fn(self.pools, self.carry,
                                          page_table, drafts, dlens)
            with host_span("serve.harvest", steps=1, firsts=0):
                if tree is None:
                    out_h, nc_h, done_h = _device_get(
                        (out, n_commit, self.carry["done"]))
                else:
                    out_h, nc_h, path_h, done_h = _device_get(
                        (out, n_commit, path, self.carry["done"]))
            self.steps += 1
            steps += 1
            with host_span("serve.commit") as span:
                committed = self._commit_verified(
                    live, out_h, nc_h, path_h, dlens, sources)
                span.set_metadata(tokens=committed)
            kept += committed
        t_h = time.perf_counter()
        self.windows += 1
        self.spec_stats["draft_s"] += draft_s
        if done_h is None:
            with host_span("serve.harvest", steps=0, firsts=0):
                done_h = _device_get(self.carry["done"])
        self._event(
            "span", span="decode", steps=steps,
            slots=len(self._meta), tokens=kept,
            dur_s=round(max(t_h - t0 - chunk_s, 0.0), 6),
            draft_s=round(draft_s, 6),
            **self._weight_fields(),
        )
        self._retire(done_h, t_h)

    def _decode_window(self) -> None:
        if self.spec_fn is not None and self.speculation_enabled:
            return self._spec_window()
        base = self.steps
        page_table = jnp.asarray(self.cache.page_table)
        window: List[jnp.ndarray] = []
        t0 = time.perf_counter()
        chunk_s = 0.0          # interleaved prefill time, kept OUT of
        for _ in range(self.harvest_every):  # the decode span's dur_s
            # the step's token budget: at most ONE prefill chunk
            # (every chunk_throttle-th iteration under brownout) ...
            did_chunk = False
            if self._prefilling:
                self._chunk_tick += 1
                if self._chunk_tick % max(1, self.chunk_throttle) == 0:
                    chunk_s += self._prefill_step(
                        next(iter(self._prefilling)))
                    did_chunk = True
            # ... plus one decode token for every live slot (a
            # prefill-role replica never dispatches one: its
            # prompt-complete slots wait for the handoff sweep)
            if self.decode_enabled and self._window_budget(base) > 0:
                with host_span("serve.dispatch_decode", step=self.steps,
                               live_slots=len(self._meta)):
                    self.pools, self.carry = self.decode_fn(
                        self.pools, self.carry, page_table)
                window.append(self.carry["tokens"])
                self.steps += 1
            elif not did_chunk:
                break
        # ---- harvest: ONE batched resolve for the whole window plus
        # every pending admit-time first token
        steps = len(window)
        firsts = {s: self._first_tok.pop(s) for s in list(self._first_tok)}
        stacked = jnp.stack(window) if window else None
        with host_span("serve.harvest", steps=steps, firsts=len(firsts)):
            harvested, firsts_h, done_h, self.step_counters = _device_get(
                (stacked, firsts, self.carry["done"],
                 self.carry.get("counters")))
        t_h = time.perf_counter()
        self.windows += 1

        with host_span("serve.commit") as span:
            self._absorb_firsts(firsts_h, t_h)
            kept = 0
            for i in range(steps):
                for slot, m in self._meta.items():
                    if m["finished"] is not None:
                        continue
                    if base + i < m.get("since_step", base):
                        continue    # slot joined mid-window, later step
                    tok = int(harvested[i, slot])
                    m["tokens"].append(tok)
                    kept += 1
                    # host length mirror follows the device's write
                    # position
                    self.cache.lengths[slot] += 1
                    if self.eos_id is not None and tok == self.eos_id:
                        m["finished"] = "eos"
                    elif len(m["tokens"]) >= m["req"].max_new_tokens:
                        m["finished"] = "budget"
            span.set_metadata(tokens=kept + len(firsts_h))
        # tokens = KEPT tokens only: slots that finish (or freeze)
        # mid-window decode garbage for the rest of it, and counting
        # that would inflate the serving summary's tokens/s exactly in
        # the ragged-finish steady state the metric exists to measure
        # dur_s excludes the interleaved chunk dispatches: the serving
        # summary's decode tokens/s and inter-token-latency fields are
        # computed from this span, and charging prefill work to them
        # would skew exactly the chunked-vs-monolithic comparison they
        # exist to make (the chunk time is its own prefill_chunk span)
        self._event(
            "span", span="decode", steps=steps,
            slots=len(self._meta), tokens=kept,
            dur_s=round(max(t_h - t0 - chunk_s, 0.0), 6),
            **self._weight_fields(),
        )

        self._retire(done_h, t_h)

    # ----------------------------------------------------- offload tier
    def _stage_to_offload(self, victims) -> None:
        """The cache's ``evict_hook``: the refcount GC is about to free
        a burst of index-only pages — stage their bytes to the host
        tier in ONE device->host transfer instead of letting the
        prefixes die (the pages themselves are still freed; their
        CONTENT survives, keyed by hash, until LRU pressure).  Each
        entry is copied out of the batch buffer so the pool holds one
        page's bytes, not a view pinning the whole burst."""
        staged = export_pages(self.pools, [p for _, _, p in victims])
        for i, (h, parent, _) in enumerate(victims):
            self.offload.put(h, parent, {
                k: np.ascontiguousarray(v[:, i:i + 1])
                for k, v in staged.items()})
        self._event("page_offload", pages=len(victims),
                    bytes=staged_nbytes(staged))

    def _fault_in(self, prompt) -> None:
        """Bring a prompt's offloaded prefix pages back on device:
        walk the cumulative hash chain, and for each hash that is not
        resident but IS staged in the host tier, adopt a fresh page
        into the prefix index and scatter the staged bytes into it —
        bit-identical to a page that never left.  Stops at the first
        hash neither tier holds (the chain beyond it needs recompute).
        The walked chain protects itself from the GC the adoption may
        trigger, so faulting page k can never evict page j < k."""
        cache = self.cache
        hashes = prompt_page_hashes(prompt, cache.config.page_size)
        chain: set = set()
        prev = None
        batch: List[Any] = []
        n_bytes = misses = 0
        t0 = time.perf_counter()
        for h in hashes:
            chain.add(h)
            if h in cache._prefix:
                prev = h
                continue
            if h not in self.offload:
                misses += 1
                self.offload.stats["misses"] += 1
                break
            try:
                page = cache.adopt_prefix_page(h, prev, protect=chain)
            except CacheOutOfPages:
                break               # HBM truly full of live pages
            entry = self.offload.take(h)
            batch.append((page, entry["data"]))
            n_bytes += staged_nbytes(entry["data"])
            prev = h
        pages_in = len(batch)
        if batch:
            # one bucketed import for the whole chain instead of a
            # dispatch per page; padding repeats the last page (same
            # bytes at a duplicate index — order-independent), so the
            # jit sees at most log2(pages_per_seq) page-count shapes
            pages = [p for p, _ in batch]
            staged = {k: np.concatenate([d[k] for _, d in batch],
                                        axis=1)
                      for k in batch[0][1]}
            bucket = min(1 << (len(pages) - 1).bit_length(),
                         cache.config.pages_per_seq)
            if bucket > len(pages):
                pad = bucket - len(pages)
                pages = pages + [pages[-1]] * pad
                staged = {
                    k: np.concatenate(
                        [v, np.repeat(v[:, -1:], pad, axis=1)], axis=1)
                    for k, v in staged.items()}
            self.pools = _import_pages_jit(
                self.pools, staged, jnp.asarray(pages, jnp.int32))
        if pages_in or misses:
            self._event(
                "page_faultin", pages=pages_in, bytes=n_bytes,
                tokens=pages_in * cache.config.page_size,
                misses=misses,
                dur_s=round(time.perf_counter() - t0, 6))

    # ------------------------------------------------- handoff (fleet)
    @property
    def pending_prefill_chunks(self) -> int:
        """Prefill chunks still to run for in-flight admissions — the
        fleet router's prefill-pressure signal (host state only)."""
        if self.prefill_chunk is None:
            return len(self._prefilling)
        C = self.prefill_chunk
        return sum(max(-(-st["plen"] // C) - st["next_chunk"], 0)
                   for st in self._prefilling.values())

    def handoff_ready(self) -> List[Any]:
        """Uids exportable RIGHT NOW: prompt fully ingested, first
        token committed to the host stream (no pending future — the
        packet must carry real tokens), stream unfinished."""
        return [m["req"].uid for s, m in self._meta.items()
                if m["finished"] is None and m["tokens"]
                and s not in self._first_tok]

    def _refuse_stateful_handoff(self, what: str) -> None:
        if self.cache.config.has_state:
            raise ValueError(
                f"cannot {what} a request of a cache with per-slot state "
                "(slot_states): a handoff moves pages, and the slot's "
                "state would stay behind (ROADMAP, R queue)")

    def export_request(self, uid: Any) -> Optional[HandoffPacket]:
        """Package an in-flight request's decode state for another
        replica: stage every KV page written so far to host and
        release the slot (like :meth:`cancel`, no :class:`Completion`
        is recorded — ownership MOVES).  Returns ``None`` when ``uid``
        is not exportable (:meth:`handoff_ready`).  The caller owns
        durability: journal the transfer BEFORE calling this — after
        it, the pages live only in the returned packet."""
        self._refuse_stateful_handoff("export")
        slot = next((s for s, m in self._meta.items()
                     if m["req"].uid == uid), None)
        if slot is None:
            return None
        m = self._meta[slot]
        if m["finished"] is not None or not m["tokens"] \
                or slot in self._first_tok:
            return None
        req = m["req"]
        cfg = self.cache.config
        if cfg.classes:
            raise ValueError(
                "handoff stages ONE class's pages: a cache of several "
                "page classes cannot export a request yet")
        # host length mirror == positions written on device:
        # prompt + committed - 1 (the newest token's K/V lands on the
        # next decode step — the destination runs that step instead)
        written = int(self.cache.lengths[slot])
        n_pages = cfg.tokens_to_pages(written)
        pages = list(self.cache._slot_pages[slot][:n_pages])
        # pad the staged block to a power-of-two page count so the
        # import scatter compiles once per BUCKET, not once per page
        # count — pad entries repeat the last real page, and the
        # import repeats its destination the same way, so duplicate
        # scatter indices carry identical bytes (order-independent)
        bucket = min(1 << (n_pages - 1).bit_length(),
                     cfg.pages_per_seq)
        pages += [pages[-1]] * (bucket - n_pages)
        staged = export_pages(self.pools, pages)
        packet = HandoffPacket(
            req=req, tokens=list(m["tokens"]), staged=staged,
            n_pages=n_pages, written=written,
            wire_bytes=staged_nbytes(staged) * n_pages // len(pages),
            compat_key=self.cache.compat_key(),
            hashes=(prompt_page_hashes(req.prompt, cfg.page_size)
                    if self.prefix_cache else None))
        del self._meta[slot]
        self.cache.retire(slot)
        c = self.carry
        self.carry = {**c, "done": c["done"].at[slot].set(True)}
        self._event("request_exported", uid=req.uid, slot=slot,
                    pages=n_pages, bytes=packet.wire_bytes,
                    tokens=len(packet.tokens))
        return packet

    def import_request(self, packet: HandoffPacket) -> bool:
        """Adopt a :class:`HandoffPacket` into a free slot: allocate
        pages for the full prompt+budget, scatter the staged bytes into
        the leading ``n_pages`` of them, and resume decoding from the
        packet's last token at the absolute position the source left
        off — no recompute, and (greedy/seeded) token-identical
        continuation by the key-schedule argument.  Returns ``False``
        on backpressure (no free slot / no pages) — the packet stays
        valid and the caller retries later."""
        self._refuse_stateful_handoff("import")
        if packet.compat_key != self.cache.compat_key():
            raise ValueError(
                f"handoff across incompatible cache families: packet "
                f"{packet.compat_key} vs pool "
                f"{self.cache.compat_key()} — pages cannot move "
                "between different page layouts")
        req = packet.req
        plen = len(req.prompt)
        if plen > self.max_prompt_len:
            raise ValueError(
                f"prompt of {plen} tokens exceeds max_prompt_len "
                f"{self.max_prompt_len}")
        cfg = self.cache.config
        slot = next((s for s in range(cfg.max_seqs)
                     if s not in self._meta
                     and s not in self._prefilling), None)
        if slot is None:
            return False
        try:
            self.cache.admit(slot, plen + req.max_new_tokens)
        except CacheOutOfPages:
            return False
        pages = list(self.cache._slot_pages[slot][:packet.n_pages])
        # mirror the export-side padding: the staged block's pad pages
        # are copies of the last real page, landed on the last real
        # destination page again (identical bytes, duplicate index)
        staged_n = next(iter(packet.staged.values())).shape[1]
        pages += [pages[-1]] * (staged_n - packet.n_pages)
        written = packet.written
        n_tok = len(packet.tokens)
        last = int(packet.tokens[-1])
        budget_left = req.max_new_tokens - n_tok
        finished = None
        if self.eos_id is not None and last == self.eos_id:
            finished = "eos"
        elif budget_left <= 0:
            finished = "budget"
        self.cache.lengths[slot] = written
        skey = self._slot_key(req)
        self._n_admits += 1
        self.pools, self.carry = _import_state_jit(
            self.pools, self.carry, packet.staged,
            jnp.asarray(pages, jnp.int32), slot, last, written,
            budget_left, finished is not None,
            jnp.asarray(skey, jnp.uint32))
        now = time.perf_counter()
        self._meta[slot] = {
            "req": req, "tokens": list(packet.tokens),
            # TTFT already happened on the source; the fleet log owns
            # end-to-end timing for handed-off requests
            "t_admit": now, "t_first": now, "finished": finished,
            "since_step": self.steps,
        }
        if self.prefix_cache and packet.hashes:
            # the imported pages carry the hashes they were registered
            # under on the source — adopt them into THIS replica's
            # index, so followers of the same prompt share them here
            self.cache.register_prefix(slot, req.prompt,
                                       hashes=packet.hashes)
        self._event("request_imported", uid=req.uid, slot=slot,
                    pages=packet.n_pages, bytes=packet.wire_bytes,
                    tokens=n_tok)
        return True

    # ------------------------------------------------------------ cancel
    def cancel(self, uid: Any) -> Optional[List[int]]:
        """Evict an in-flight request: release its slot, drop its page
        refcounts (shared prefix pages other holders keep stay
        allocated), freeze the slot on device, and emit a
        ``request_cancelled`` event.  Returns the tokens harvested so
        far (``[]`` for a still-prefilling request), or ``None`` when
        ``uid`` is not in flight — no :class:`Completion` is recorded,
        so the uid can be re-served later (the fleet migration path
        replays exactly these tokens as a prompt suffix).

        An unharvested window may already have produced more tokens on
        device; they are dropped — harvest is the commit point, and a
        seeded (or greedy) request regenerates them identically."""
        for slot, m in self._meta.items():
            if m["req"].uid != uid:
                continue
            self._first_tok.pop(slot, None)
            tokens = list(m["tokens"])
            del self._meta[slot]
            self.cache.retire(slot)
            c = self.carry
            self.carry = {**c, "done": c["done"].at[slot].set(True)}
            self._event("request_cancelled", uid=uid, slot=slot,
                        new_tokens=len(tokens))
            return tokens
        for slot, st in self._prefilling.items():
            if st["req"].uid != uid:
                continue
            del self._prefilling[slot]
            self.cache.retire(slot)
            self._event("request_cancelled", uid=uid, slot=slot,
                        new_tokens=0)
            return []
        return None

    # -------------------------------------------------------------- pump
    def pump(self, queue) -> bool:
        """ONE scheduler turn over an external queue: admit while slots
        and pages allow, then run one harvest window.  Returns True
        while the batcher still holds or awaits work — the fleet
        router's unit of interleaving (it pumps every replica once per
        fleet step, so no replica's window blocks another's
        admissions).  ``queue`` is a ``collections.deque`` of
        :class:`Request`; admitted entries are popped, backpressured
        ones stay."""
        obtained, obtain_s = _ledger.count, _ledger.obtain_s_total
        with host_span("serve.pump", turn=self.turns, queued=len(queue),
                       live_slots=self.live_slots) as span:
            self.turns += 1
            self._admit(queue)
            if not self._meta and not self._prefilling:
                if queue:
                    raise CacheOutOfPages(
                        "no slot can ever admit the next request "
                        f"(prompt+budget needs more pages than the "
                        f"pool holds: {queue[0].uid!r})")
                return False
            self._decode_window()
            if self.cache.config.classes:
                # per page class: pages held now, and pages retired
                # slots had overwritten in their rings so far
                span.set_metadata(
                    **{f"pages_in_use_{k}": v for k, v in
                       self.cache.pages_in_use().items()},
                    **{f"pages_overwritten_{k}": v for k, v in
                       self.cache.overwritten_pages.items()})
            if _ledger.count != obtained:
                # this turn met a shape nobody warmed: say so where the
                # stall stands, on the trace's clock and on the host
                self.compiled_turns += 1
                self.last_compiled_turn = self.turns - 1
                names = dict.fromkeys(
                    r.name for r in _ledger.records_from(obtained))
                span.set_metadata(
                    executables=_ledger.count - obtained,
                    obtain_us=int(1e6 * (_ledger.obtain_s_total
                                         - obtain_s)),
                    obtained=",".join(names))
        return bool(self._meta or self._prefilling or queue)

    # --------------------------------------------------------------- run
    def run(self, requests: Sequence[Request]) -> Dict[Any, Completion]:
        """Serve ``requests`` to completion; returns ``uid ->``
        :class:`Completion`.  Re-entrant: call again with more
        requests — the cache, pools, prefix index and compiled steps
        are reused."""
        queue = collections.deque(requests)
        while queue or self._meta or self._prefilling:
            self.pump(queue)
        return self.completions
