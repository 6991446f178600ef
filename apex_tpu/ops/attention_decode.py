"""Decode-tier attention (fmha-decode): tiny-q against a paged KV cache.

The fourth rung of the measured attention ladder (short / mid / flash /
**decode** — docs/attention.md).  The first three rungs are built for
training shapes: s_q == s_k, both large, FLOP-bound.  Generation
inverts every one of those assumptions — s_q is 1 (or a small
speculative/chunked-prefill handful), s_k is the whole conversation so
far, and the arithmetic intensity collapses to ~2 FLOPs per KV byte, so
the kernel's job is to stream the cache at HBM bandwidth while the
elementwise chain (RoPE rotation, online-softmax bookkeeping, the
normalization tail) hides under the dots ("LLM Inference Acceleration
via Efficient Operation Fusion", PAPERS.md — the same fusion discipline
PRs 1/5/7 applied to training).

Why **paged**: a serving batch holds sequences of wildly different
lengths that grow, finish and get replaced mid-flight.  A dense
``(b, h, max_len, d)`` cache wastes HBM on every short sequence and
forces a copy whenever a slot is reused; a page pool
(``apex_tpu/serving/kv_cache.py``) allocates fixed-size token pages on
demand and maps each sequence's logical positions to physical pages
through a small int32 table.  The kernel consumes that layout directly:

- **pool layout** ``(num_pages, h, page_size, d)`` — one page holds
  ``page_size`` consecutive tokens of ONE sequence for ALL heads, so a
  single page DMA feeds every head's dot (the per-head trailing
  ``(page_size, d)`` tile is Mosaic-native);
- **scalar-prefetch page walk, several pages a grid step** — the grid
  is ``(b, h_blocks, steps)`` and a step covers ``P`` consecutive
  logical pages of the sequence; the page table is read from SMEM
  (``pltpu.PrefetchScalarGridSpec``), so the data-dependent gather is a
  DMA address computation, never a materialized ``take``.  A grid step
  costs ~0.3-0.9 us whatever it fetches, so at ONE 64-key page a step
  the walk is bound by the grid, not by HBM (PERF.md section 6, PR 32
  and PR 34).  The pools stay in HBM and the kernel copies a step's
  LIVE pages itself into ONE ``(P, block_h, page_size, d)`` VMEM tile
  (the next live step's copies in flight under this step's work, across
  sequences too; pages past a sequence's length are neither fetched nor
  worked on), so a head does one ``(rows, d) x (d, P * page_size)``
  product, one online-softmax update and one ``(rows, P * page_size) x
  (P * page_size, d)`` product a step.  ``P`` is computed from the
  shapes (:func:`_pages_per_step`: up to ``FMHA_DECODE_STEP_KEYS`` keys,
  at least ``FMHA_DECODE_MIN_STEPS`` steps a table, within
  ``FMHA_DECODE_TILE_BYTES``); the traced body and its lowering are the
  same size at every ``P`` (the copies are loops over the step's pages,
  not ``P`` copies of anything).  Two kinds of pool walk ONE page a
  step, handed to the same body by the pipeline's block specs as
  before: heads narrower than 128 lanes (gpt2's 64: Mosaic cuts a
  copy's source out of an HBM array only along whole lane tiles) and
  int8 pools (their scale planes are narrower still);
- **head packing** (PR 1/PR 5's ``block_bh`` trick at decode shapes):
  all of a sequence's heads (grouped ``block_h`` at a time) ride one
  program and one tile, the per-head body a loop over ``block_h``
  traced ONCE (the query is prepared, the step's mask built and the
  tile fetched for all of them together) -- the s_q=1 grid that would
  otherwise idle the VPU stays saturated;
- **ONE kernel for fp32/bf16 and int8 pages**: int8 pools carry per
  ``(token, kv_block)`` fp32 scales (``ops/quantization.py``'s
  row-block machinery) and the kernel dequantizes each page in VMEM
  right before its dot — int8 halves (vs bf16) the bytes streamed, which
  is the whole game at decode intensity;
- **fused RoPE**: the query rotation for the current positions happens
  inside the kernel (``q*cos + rotate_half(q)*sin`` — the wrapper
  ships the pre-shuffled ``rotate_half(q)`` companion so the in-kernel
  work is pure elementwise multiply-add under the page stream; K is
  rotated once at cache-write time and never again);
- **partially-filled pages**: per-sequence ``lengths`` mask the tail
  page exactly, and steps past a sequence's length are skipped
  (``pl.when``).  A tile's places past the last live page keep what an
  earlier step left there: their scores are masked by position, and the
  V tiles start as zeros so that a masked weight's zero never meets a
  non-finite row.  On the one-page path a step past the length repeats
  the last page's block (no further fetch); unallocated table entries
  point at physical page 0, so every formable address is valid.

Dispatch: serving callers hold a page table and call :func:`fmha_decode`
directly; ``flash_attention(implementation="decode")`` routes contiguous
``(b, h, s_k, d)`` K/V here by viewing it as trivially-paged storage
(``page_table[b] = b*pages + arange``) — the A/B seam
``tools/kernel_validation.py``'s ``validate_fmha_decode`` sweep times.
There is no auto-dispatch window: decode callers know they are decoding
(they hold a cache), and the training ladder's crossover measurements
stay untouched.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops.attention import _NEG_INF, _interpret
from apex_tpu.ops.common import shape_struct
from apex_tpu.telemetry.spans import kernel_name

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "fmha_decode",
    "paged_attention_reference",
    "decode_contiguous",
    "FMHA_DECODE_BLOCK_H",
    "FMHA_DECODE_MAX_ROWS",
]

_LANES = 128

#: How many heads one grid program packs (the decode analog of the
#: short/mid kernels' block_bh): each program holds block_h heads' q
#: resident and runs their per-step dots back-to-back over one tile of
#: pages.  16 matches FMHA_SHORT_MAX_BLOCK_BH's measured code-size bound.
FMHA_DECODE_BLOCK_H = 16

#: VMEM-residency bound on the per-program query rows (block_h * sq):
#: the prepared q and the acc scratch are (block_h, sq, d) (m and l one
#: lane-padded column a row), so at the chunked-prefill sq's (64/256)
#: the s_q=1 head packing must shrink — 512 rows keeps them under ~1 MB
#: at d=128 while leaving the s_q=1 default (block_h=16) untouched.
FMHA_DECODE_MAX_ROWS = 512

#: Keys one grid step of the page walk covers at most.  A grid step
#: costs ~0.3-0.9 us whatever it fetches, so a step of ONE 64-key page
#: is bound by the grid and not by HBM; at 512 keys (8 pages of 64) the
#: walk reaches the knee (docs/attention.md "Pages a grid step").
FMHA_DECODE_STEP_KEYS = 512

#: Steps a table is cut into at least, however short it is: a slot's
#: last step computes over all its places, live or not, so the fewer
#: pages a batch's slots hold of a short table (gpt2's 16) the smaller
#: the step that wastes least.
FMHA_DECODE_MIN_STEPS = 8

#: VMEM the K and V page tiles may take: 2 operands x 2 tiles (one read,
#: one in flight) x pages x block_h x page_size x d x itemsize, beside the residents the row budget above bounds (q, its
#: rope planes, the output block, the prepared q, acc, m, l: under 3 MB
#: at 512 rows of d=128).  Half of the 16 MB a v5e kernel gets.
FMHA_DECODE_TILE_BYTES = 8 * 2**20


class _DecodeConfig(NamedTuple):
    """Static kernel configuration."""

    sm_scale: float
    causal: bool
    sq: int
    block_h: int
    page_size: int
    num_pages: int      # logical pages per sequence (grid extent)
    kv_block: int       # scale block width along d (int8 pages only)
    has_scales: bool
    has_rope: bool
    ancestor: Optional[tuple] = None  # (sq, sq) static tree mask rows
    group: int = 1          # query heads a K/V head serves
    has_first: bool = False  # per-sequence first position, ring table
    table_pages: int = 0    # the page table's width (has_first only)
    pages: int = 1          # logical pages one grid step covers
    copies: bool = False    # the kernel copies a step's pages itself


# ---------------------------------------------------------------------------
# XLA reference path (also the CPU fallback and the validation anchor)
# ---------------------------------------------------------------------------


def _dequant_pages(pages, scales, kv_block):
    """(num_pages, h, page_size, d) int8 + (num_pages, h, page_size, nb)
    fp32 scales -> fp32, per-(token, kv_block) dequantization."""
    d = pages.shape[-1]
    expand = jnp.repeat(scales, kv_block, axis=-1)[..., :d]
    return pages.astype(jnp.float32) * expand


def paged_attention_reference(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    kv_block: int = _LANES,
    ancestor: Optional[tuple] = None,
    first: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Plain-XLA paged decode attention — the correctness reference.

    Materializes the per-sequence gather (``take`` over the page table)
    and computes masked softmax attention in fp32.  Query token ``i`` of
    sequence ``b`` sits at position ``lengths[b] - sq + i`` and attends
    to cache positions ``<= `` its own (``causal=True``) or to all
    ``lengths[b]`` positions.  The cache is expected to already contain
    the query tokens' own K/V (write-before-attend, so a decode token
    attends to itself).

    ``ancestor`` replaces the in-window causal triangle with a static
    (sq, sq) boolean matrix over the FRESH rows (cache positions
    ``lengths[b] - sq + j``): query row ``i`` attends fresh row ``j``
    iff ``ancestor[i][j]`` — tree speculation's per-branch visibility.
    The committed prefix (positions ``< lengths[b] - sq``) stays fully
    visible to every row.

    The pool may hold FEWER heads than ``q`` has (grouped-query
    attention): query head ``i`` reads K/V head ``i // (h / h_kv)``, and
    K/V are not repeated.  ``first (b,)`` masks cache positions below it
    and makes the table a RING: position ``p`` lives in column ``(p //
    page_size) % width``, each column holding the newest page written
    to it (see :func:`fmha_decode`).
    """
    b, h, sq, d = q.shape
    num_pages = page_table.shape[1]
    page_size = k_pages.shape[2]
    h_kv = k_pages.shape[1]
    scale = (1.0 / d**0.5) if sm_scale is None else float(sm_scale)

    def gather(pages, scales):
        x = jnp.take(pages, page_table, axis=0)  # (b, np, h, ps, d)
        if scales is not None:
            s = jnp.take(scales, page_table, axis=0)
            x = _dequant_pages(x, s, kv_block)
        x = jnp.moveaxis(x, 2, 1)
        return x.reshape(b, h_kv, num_pages * page_size, d)

    k = gather(k_pages, k_scales)
    v = gather(v_pages, v_scales)
    if h_kv != h or first is not None:
        return _grouped_window_reference(
            q, k, v, lengths, first, causal, scale, page_size)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    k_pos = jnp.arange(num_pages * page_size)[None, None, None, :]
    if ancestor is not None:
        amat = jnp.asarray(ancestor, dtype=bool)       # (sq, sq)
        fresh = k_pos - (lengths[:, None, None, None] - sq)
        in_window = (fresh >= 0) & (fresh < sq)
        q_i = jnp.arange(sq)[None, None, :, None]
        tree = amat[q_i, jnp.clip(fresh, 0, sq - 1)]
        mask = (fresh < 0) | (in_window & tree)
    elif causal:
        q_pos = (lengths[:, None, None, None] - sq
                 + jnp.arange(sq)[None, None, :, None])
        mask = k_pos <= q_pos
    else:
        mask = k_pos < lengths[:, None, None, None]
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def _grouped_window_reference(q, k, v, lengths, first, causal, scale,
                              page_size):
    """The reference for grouped heads and/or a ring table: ``k``/``v``
    ``(b, h_kv, columns * page_size, d)`` in TABLE order.  With
    ``first`` column ``c`` holds the newest logical page ``<=`` the
    sequence's last one that is congruent to ``c``."""
    b, h, sq, d = q.shape
    h_kv, S = k.shape[1], k.shape[2]
    col = jnp.arange(S, dtype=jnp.int32) // page_size
    off = jnp.arange(S, dtype=jnp.int32) % page_size
    ln = lengths.astype(jnp.int32)[:, None]
    if first is None:
        k_pos = jnp.broadcast_to(col * page_size + off, (b, S))
        lo = jnp.zeros((b, 1), jnp.int32)
    else:
        width = S // page_size
        last = (jnp.maximum(ln, 1) - 1) // page_size
        page = last - (last - col[None]) % width
        k_pos = page * page_size + off[None]
        lo = first.astype(jnp.int32)[:, None]
    k_pos = k_pos[:, None, None, None]                   # (b,1,1,1,S)
    lo, ln = lo[:, None, None, None], ln[:, None, None, None]
    s = jnp.einsum(
        "bkgqd,bksd->bkgqs",
        q.reshape(b, h_kv, h // h_kv, sq, d).astype(jnp.float32),
        k.astype(jnp.float32), preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = ln - sq + jnp.arange(sq)[None, None, None, :, None]
        mask = (k_pos <= q_pos) & (k_pos >= lo)
    else:
        mask = (k_pos < ln) & (k_pos >= lo)
    s = jnp.where(mask, s, _NEG_INF)
    p = jnp.where(mask, jax.nn.softmax(s, axis=-1), 0.0)
    out = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, sq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _walk(cfg, len_ref, first_ref, bb):
    """Slot ``bb``'s walk: the logical page it starts at and how many
    pages from there hold something a query may see."""
    ln = len_ref[bb]
    start = first_ref[bb] // cfg.page_size if cfg.has_first else 0
    last = (jnp.maximum(ln, 1) - 1) // cfg.page_size
    return start, jnp.where(ln > 0, jnp.maximum(last - start + 1, 0), 0)


def _stream_tiles(cfg, walk, at, grid, pt_ref, pools, tiles, sem, state,
                  cut=None):
    """The copies of a step's pages (``cfg.copies``) of program
    ``at = (b, hb, step)`` in ``grid``: wait for this step's tile, with
    the next live step's in flight under it.  Returns the tile to read.
    ``state`` (SMEM): [the tile the next live step reads, whether its
    copies are already in flight].  ``cut(pool, page, hb)`` is what a
    place of a tile is copied from (default: pool page ``page``'s
    ``block_h`` heads of block ``hb``)."""
    (b, hb, step), (n_b, n_hb) = at, grid[:2]
    P, bh = cfg.pages, cfg.block_h
    if cut is None:
        cut = lambda pool, page, hh: pool.at[page, pl.ds(hh * bh, bh)]

    def page_copies(hh, page, buf, j):
        # what ``cut`` takes of pool page ``page`` -> place j of tile buf
        return [pltpu.make_async_copy(
            cut(pool, page, hh), tile.at[buf, j], sem.at[i, buf])
            for i, (pool, tile) in enumerate(zip(pools, tiles))]

    def live_pages(bb, st):
        start, live = walk(bb)
        return start + st * P, jnp.clip(live - st * P, 0, P)

    def fetch(bb, hh, st, buf):
        # a step's live pages only: a table entry past them is never
        # read, and a dead place keeps what an earlier step left there
        first_page, n = live_pages(bb, st)

        def one(j, carry):
            page = first_page + j
            if cfg.has_first:
                page = page % cfg.table_pages       # the ring's column
            for copy in page_copies(hh, pt_ref[bb, page], buf, j):
                copy.start()
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    cur = state[0]

    @pl.when(state[1] == 0)
    def _():
        fetch(b, hb, step, cur)

    # next: this walk's next step, or the first step of the next program
    # (the next head block, then the next slot) if that one has any
    more = live_pages(b, step + 1)[1] > 0
    wrap = hb + 1 == n_hb
    nb = jnp.where(more | ~wrap, b, jnp.minimum(b + 1, n_b - 1))
    nh = jnp.where(more, hb, jnp.where(wrap, 0, hb + 1))
    ns = jnp.where(more, step + 1, 0)
    go = more | ((~wrap | (b + 1 < n_b)) & (live_pages(nb, 0)[1] > 0))

    @pl.when(go)
    def _():
        fetch(nb, nh, ns, 1 - cur)

    state[0] = 1 - cur
    state[1] = go.astype(jnp.int32)

    def wait(j, carry):
        for copy in page_copies(hb, 0, cur, j):
            copy.wait()
        return carry

    jax.lax.fori_loop(0, live_pages(b, step)[1], wait, 0)
    return cur


def _decode_kernel(*refs, cfg: _DecodeConfig):
    pt_ref, len_ref = refs[:2]
    rest = list(refs[2:])
    first_ref = rest.pop(0) if cfg.has_first else None
    q_ref = rest.pop(0)
    qrot_ref = cos_ref = sin_ref = None
    if cfg.has_rope:
        qrot_ref, cos_ref, sin_ref = rest.pop(0), rest.pop(0), rest.pop(0)
    # one page's block from the pipeline, or (copies) the pool in HBM
    k_ref, v_ref = rest.pop(0), rest.pop(0)
    ks_ref = vs_ref = None
    if cfg.has_scales:
        ks_ref, vs_ref = rest.pop(0), rest.pop(0)
    o_ref, qs_ref, acc_ref, m_ref, l_ref = rest[:5]

    at = b, hb, step = tuple(pl.program_id(i) for i in range(3))
    grid = tuple(pl.num_programs(i) for i in range(3))
    sq, ps, P = cfg.sq, cfg.page_size, cfg.pages
    # a K/V head's rows are its ``group`` query heads' sq rows each
    rows = sq * cfg.group
    native = cfg.group > 1 or cfg.has_first
    walk = functools.partial(_walk, cfg, len_ref, first_ref)
    start, live = walk(b)
    ln = len_ref[b]

    if cfg.copies:
        k_tile, v_tile, sem, state = rest[5:]

        @pl.when((b == 0) & (hb == 0) & (step == 0))
        def _first_program():
            # a dead place's scores are masked whatever it holds; its V
            # rows have to be finite for the masked weights' zeros
            state[0] = 0
            state[1] = 0
            v_tile[...] = jnp.zeros_like(v_tile)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        # the query once a walk, not once a page: q*cos +
        # rotate_half(q)*sin (the rotation's FLOPs run in-kernel; the
        # half-swap data shuffle happened once in the wrapper, XLA fuses
        # it into the q projection epilogue), the softmax scale, and the
        # score product's operand type
        qh = q_ref[0].astype(jnp.float32)                # (bh, rows, d)
        if cfg.has_rope:
            qh = (qh * cos_ref[0].astype(jnp.float32)
                  + qrot_ref[0].astype(jnp.float32)
                  * sin_ref[0].astype(jnp.float32))
        qs_ref[...] = (qh * cfg.sm_scale).astype(qs_ref.dtype)

    # steps past the slot's last live page do no work and fetch nothing
    # (with variable lengths in a batch the grid covers the longest
    # walk and short ones skip the difference)
    @pl.when(step * P < live)
    def _body():
        d = q_ref.shape[-1]
        if cfg.copies:
            cur = _stream_tiles(cfg, walk, at, grid, pt_ref, (k_ref, v_ref),
                                (k_tile, v_tile), sem, state)
            # the step's pages as ONE (P * ps, d) tile a head
            page_rows = lambda hi: (
                k_tile[cur, :, hi].reshape(P * ps, d),
                v_tile[cur, :, hi].reshape(P * ps, d))
        else:
            page_rows = lambda hi: (k_ref[0, hi], v_ref[0, hi])

        # the step's keys are P consecutive logical pages: one mask for
        # every head
        shape = (rows, P * ps)
        k_pos = (start + step * P) * ps + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1)
        if cfg.ancestor is not None:
            # tree verify: the last sq cache slots are the candidate
            # rows; row i sees fresh slot j iff the STATIC ancestor
            # matrix says so, plus the whole committed prefix.  Each
            # row's allowed-column set is packed into an int32 bitmask
            # selected by row iota (Pallas kernels cannot capture
            # constant arrays), so the mask is sq scalar selects + one
            # variable shift.
            fresh = k_pos - (ln - sq)
            row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            bits = jnp.zeros_like(row)
            for i in range(sq):
                rb = sum(int(cfg.ancestor[i][j]) << j for j in range(sq))
                bits = jnp.where(row == i, rb, bits)
            fr = jnp.clip(fresh, 0, sq - 1)
            tree = (jnp.right_shift(bits, fr) & 1) == 1
            mask = (fresh < 0) | ((fresh >= 0) & (fresh < sq) & tree)
        elif cfg.causal:
            q_row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            if cfg.group > 1:
                q_row = q_row % sq          # rows are (query head, token)
            mask = k_pos <= ln - sq + q_row
        else:
            mask = k_pos < ln
        if cfg.has_first:
            mask = mask & (k_pos >= first_ref[b])

        def head(hi, carry):
            qh = qs_ref[hi]                                  # (rows, d)
            kh, vh = page_rows(hi)
            if not native:
                # (grouped / windowed walks hand the MXU the pages as
                # they are stored, fp32 accumulation: no widening pass
                # on the VPU under a 2-FLOPs-a-byte stream)
                kh, vh = kh.astype(jnp.float32), vh.astype(jnp.float32)
            if cfg.has_scales:
                kh = kh * jnp.repeat(
                    ks_ref[0, hi], cfg.kv_block, axis=1)[:, :d]
                vh = vh * jnp.repeat(
                    vs_ref[0, hi], cfg.kv_block, axis=1)[:, :d]
            s = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # (rows, P * ps)
            s = jnp.where(mask, s, _NEG_INF)
            m_prev, l_prev = m_ref[hi], l_ref[hi]            # (rows, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[hi] = l_prev * corr + jnp.sum(pexp, axis=-1, keepdims=True)
            m_ref[hi] = m_new
            acc_ref[hi] = acc_ref[hi] * corr + jax.lax.dot_general(
                pexp.astype(vh.dtype) if native else pexp, vh,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return carry

        # one traced body; unrolled when lowered, so that Mosaic's
        # scheduler overlaps the heads (rolled, Trinity's walks took
        # 1.3x as long: tools/paged_decode_ablation.py, heads_rolled)
        jax.lax.fori_loop(0, cfg.block_h, head, 0, unroll=True)

    @pl.when(step == grid[2] - 1)
    def _finalize():
        # the softmax-normalization tail, fused (the operation-fusion
        # paper's point: this divide never round-trips through HBM).
        # A zero-length sequence (an idle serving slot) clamps l and
        # writes garbage the caller masks.
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def _decode_pallas(q, q_rot, cos, sin, k_pages, v_pages, k_scales,
                   v_scales, page_table, lengths, cfg: _DecodeConfig,
                   first=None):
    """``q`` (and the rope planes) come as ``(b, h_kv, group * sq, d)``:
    a K/V head's query heads are further ROWS of its program."""
    b, h, rows, d = q.shape
    ps, P, bh = cfg.page_size, cfg.pages, cfg.block_h
    nb = k_scales.shape[-1] if cfg.has_scales else 0
    native = cfg.group > 1 or cfg.has_first

    def qmap(bb, hb, p, *scalars):
        return (bb, hb, 0, 0)

    def kvmap(bb, hb, p, pt, ln, *fs):
        # one page a step: logical page (first // ps +) p, held back at
        # the sequence's last page (steps past it repeat that block: no
        # further fetch), in the ring column it lives in
        start = fs[0][bb] // ps if cfg.has_first else 0
        page = jnp.minimum(start + p, (jnp.maximum(ln[bb], 1) - 1) // ps)
        if cfg.has_first:
            page = page % cfg.table_pages
        return (pt[bb, page], hb, 0, 0)

    in_specs = [pl.BlockSpec((1, bh, rows, d), qmap)]
    inputs = [q]
    if cfg.has_rope:
        in_specs += [pl.BlockSpec((1, bh, rows, d), qmap)] * 3
        inputs += [q_rot, cos, sin]
    scratch = [
        pltpu.VMEM((bh, rows, d), k_pages.dtype if native else jnp.float32),
        pltpu.VMEM((bh, rows, d), jnp.float32),
        pltpu.VMEM((bh, rows, 1), jnp.float32),
        pltpu.VMEM((bh, rows, 1), jnp.float32),
    ]
    if cfg.copies:
        # the pools stay in HBM: the kernel copies a step's live pages
        # into one of two tiles itself
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        scratch += [
            pltpu.VMEM((2, P, bh, ps, d), k_pages.dtype),
            pltpu.VMEM((2, P, bh, ps, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
        ]
    else:
        in_specs += [pl.BlockSpec((1, bh, ps, d), kvmap)] * 2
    inputs += [k_pages, v_pages]
    if cfg.has_scales:
        in_specs += [pl.BlockSpec((1, bh, ps, nb), kvmap)] * 2
        inputs += [k_scales, v_scales]

    scalars = [page_table.astype(jnp.int32), lengths.astype(jnp.int32)]
    if cfg.has_first:
        scalars.append(first.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, h // bh, -(-cfg.num_pages // P)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, rows, d), qmap),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, cfg=cfg),
        grid_spec=grid_spec,
        out_shape=shape_struct((b, h, rows, d), q.dtype, q, k_pages,
                               v_pages),
        # a step's copies are started by the step before it, whichever
        # program that was in: that grid runs in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            ("arbitrary",) * 3 if cfg.copies
            else ("parallel", "parallel", "arbitrary"))),
        interpret=_interpret(),
        name=kernel_name("paged_decode"),
    )(*scalars, *inputs)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def _rotate_half(x):
    d = x.shape[-1]
    return jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)


def _rope_operands(q, rope: Tuple[jnp.ndarray, jnp.ndarray]):
    """Expand (cos, sin) half-tables to full-width per-(b, h, sq) planes
    plus the rotate_half(q) companion the kernel's elementwise form
    needs.  ``rope`` is ``(cos, sin)`` of shape ``(b, sq, d/2)`` (the
    per-sequence decode positions, ``ops/rope.py rope_cos_sin``)."""
    b, h, sq, d = q.shape
    cos, sin = rope
    if cos.shape != (b, sq, d // 2):
        raise ValueError(
            f"rope tables must be (b, sq, d/2) = ({b}, {sq}, {d // 2}), "
            f"got {cos.shape}"
        )
    full = lambda t: jnp.broadcast_to(
        jnp.concatenate([t, t], axis=-1)[:, None], (b, h, sq, d)
    ).astype(jnp.float32)
    return _rotate_half(q.astype(jnp.float32)), full(cos), full(sin)


def _pick_block_h(h: int, sq: int = 1) -> int:
    """Largest head packing that divides ``h``, capped by the code-size
    bound AND the VMEM row budget (``block_h * sq <=
    FMHA_DECODE_MAX_ROWS``): a chunked-prefill ``sq`` of 256 packs
    fewer heads per program than the s_q=1 decode default so the
    fp32 accumulator scratch stays resident."""
    bh = max(1, min(h, FMHA_DECODE_BLOCK_H,
                    FMHA_DECODE_MAX_ROWS // max(sq, 1)))
    while h % bh:
        bh -= 1
    return bh


def _kernel_copies(d: int, has_scales: bool) -> bool:
    """Whether the kernel copies a step's pages out of the pools itself
    (or the pipeline hands it one page's block a step, as it always did):
    Mosaic cuts a copy's source out of an HBM array only along whole
    128-lane tiles, so a narrower head (gpt2's 64) and an int8 pool's
    scale planes (``ceil(d / kv_block)`` wide) stay with the pipeline."""
    return not has_scales and d % _LANES == 0


def _pages_per_step(page_size: int, d: int, block_h: int, itemsize: int,
                    num_pages: int, has_scales: bool) -> int:
    """Logical pages one grid step of the walk covers, from the shapes:
    up to ``FMHA_DECODE_STEP_KEYS`` keys, at least
    ``FMHA_DECODE_MIN_STEPS`` steps over the table, within
    ``FMHA_DECODE_TILE_BYTES`` of VMEM; one where the pipeline brings the
    pages (:func:`_kernel_copies`)."""
    if not _kernel_copies(d, has_scales):
        return 1
    page_bytes = block_h * page_size * d * itemsize
    return max(1, min(FMHA_DECODE_STEP_KEYS // page_size,
                      -(-num_pages // FMHA_DECODE_MIN_STEPS),
                      FMHA_DECODE_TILE_BYTES // (4 * page_bytes)))


def fmha_decode(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,
    lengths: jnp.ndarray,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    kv_block: int = _LANES,
    rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    block_h: Optional[int] = None,
    implementation: Optional[str] = None,
    ancestor: Optional[tuple] = None,
    num_kv_heads: Optional[int] = None,
    first: Optional[jnp.ndarray] = None,
    max_pages: Optional[int] = None,
) -> jnp.ndarray:
    """Decode attention: ``q (b, h, sq, d)`` against a paged KV cache.

    ``k_pages``/``v_pages`` are the ``(num_pages, h, page_size, d)``
    pool (fp32/bf16, or int8 with ``k_scales``/``v_scales`` per-
    ``(token, kv_block)`` fp32 scales of shape ``(num_pages, h,
    page_size, ceil(d/kv_block))`` — ``serving/kv_cache.py`` writes
    both layouts).  ``page_table (b, logical_pages)`` maps each
    sequence's logical page to a physical pool page (unallocated
    entries MUST hold a valid index — the allocator's reserved null
    page 0); ``lengths (b,)`` counts valid tokens per sequence
    INCLUDING the query tokens (write-before-attend: a decode token
    attends to itself).

    ``sq`` is 1 for plain decode; small ``sq > 1`` serves speculative
    verification and chunked prefill, with ``causal=True`` masking each
    query token at its own position ``lengths[b] - sq + i``.  ``rope``
    fuses the query-side rotation for those positions into the kernel
    (K is rotated at cache-write time).  Forward-only by design — the
    generation loop never differentiates through the cache.

    ``implementation``: None = platform default (Pallas on TPU, XLA
    reference otherwise), ``"pallas"`` strict, ``"xla"`` reference.

    ``ancestor`` (static (sq, sq) rows of 0/1, lower-triangular with a
    unit diagonal) switches the in-window causal triangle to TREE
    visibility: query row ``i`` attends candidate row ``j`` iff
    ``ancestor[i][j]`` — several speculative branches verified against
    one committed prefix in one cache pass.  Requires ``causal=True``
    (the committed prefix stays fully visible either way).

    ``num_kv_heads`` (grouped-query attention): the pool holds that many
    heads and query head ``i`` reads K/V head ``i // (h /
    num_kv_heads)``.  A K/V head's ``h / num_kv_heads`` query heads ride
    its program as further query ROWS, so one page fetch serves them all
    and K/V are never repeated.  ``None`` is ``h``: one K/V head a query
    head, today's kernel.

    ``first (b,)``: the first cache position a sequence's queries may
    see (a window layer: ``lengths - window``, floored at 0).  Positions
    below it are masked, and the page walk STARTS at the page holding
    it: pages wholly before it are never fetched.  With ``first`` the
    table is a RING of its width: the token at position ``p`` lives in
    column ``(p // page_size) % width`` (a table that holds every
    logical page is the ring that never wraps).  ``max_pages`` (static)
    bounds the pages a walk can span (``window // page_size + 1`` for an
    unaligned window) and is the grid's extent; default the table's
    width.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("int8 pages need BOTH k_scales and v_scales")
    if k_pages.dtype == jnp.int8 and k_scales is None:
        raise ValueError("int8 pages require k_scales/v_scales")
    if k_pages.dtype != jnp.int8 and k_scales is not None:
        raise ValueError(
            f"scales passed with {k_pages.dtype} pages — scales belong "
            "to int8 pools only (stale scales would silently rescale "
            "full-precision K/V)")
    h_kv = q.shape[1] if num_kv_heads is None else int(num_kv_heads)
    if h_kv != k_pages.shape[1] or q.shape[1] % h_kv:
        raise ValueError(
            f"q heads {q.shape[1]} (over {h_kv} K/V heads) != pool heads "
            f"{k_pages.shape[1]}"
        )
    group = q.shape[1] // h_kv
    if (group > 1 or first is not None) and (
            ancestor is not None or k_scales is not None):
        raise ValueError(
            "grouped heads and a first position are built for plain "
            "pages and the causal mask (no int8 scales, no tree mask)")
    if first is not None and first.shape != (q.shape[0],):
        raise ValueError(
            f"first must be (batch,) = ({q.shape[0]},), got {first.shape}")
    if q.shape[-1] != k_pages.shape[-1]:
        raise ValueError(
            f"q head_dim {q.shape[-1]} != pool head_dim "
            f"{k_pages.shape[-1]}"
        )
    if page_table.ndim != 2 or page_table.shape[0] != q.shape[0]:
        raise ValueError(
            f"page_table must be (batch, logical_pages), got "
            f"{page_table.shape} for batch {q.shape[0]}"
        )
    b, h, sq, d = q.shape
    if block_h is not None and h_kv % int(block_h):
        raise ValueError(f"block_h {block_h} must divide heads {h_kv}")
    if rope is not None and rope[0].shape != (b, sq, d // 2):
        raise ValueError(
            f"rope tables must be (b, sq, d/2) = ({b}, {sq}, {d // 2}), "
            f"got {rope[0].shape}"
        )
    scale = (1.0 / d**0.5) if sm_scale is None else float(sm_scale)

    if ancestor is not None:
        if not causal:
            raise ValueError(
                "ancestor mask requires causal=True — tree rows refine "
                "the causal window, they do not replace the length mask")
        ancestor = tuple(
            tuple(bool(x) for x in row) for row in ancestor)
        if len(ancestor) != sq or any(len(r) != sq for r in ancestor):
            raise ValueError(
                f"ancestor must be ({sq}, {sq}) to match s_q, got "
                f"({len(ancestor)}, "
                f"{len(ancestor[0]) if ancestor else 0})")
        if sq > 31:
            raise ValueError(
                f"ancestor s_q {sq} > 31 — the kernel packs each "
                "row's visibility into an int32 bitmask; speculative "
                "trees are a small handful of rows by design")
        for i, row in enumerate(ancestor):
            if not row[i]:
                raise ValueError(
                    f"ancestor diagonal must be 1 (row {i} attends "
                    "itself — write-before-attend)")
            if any(row[i + 1:]):
                raise ValueError(
                    f"ancestor row {i} attends a later row — the tree "
                    "must be topologically ordered (lower-triangular)")

    from apex_tpu.ops.common import run_kernel
    from apex_tpu.utils.platform import default_implementation

    if implementation not in (None, "pallas", "xla", "decode"):
        raise ValueError(
            f"unknown implementation {implementation!r}; expected None, "
            "'pallas'/'decode', or 'xla'"
        )
    if implementation == "decode":
        implementation = "pallas"
    impl = implementation or default_implementation()

    def _xla_path():
        qq = q
        if rope is not None:
            from apex_tpu.ops.rope import apply_rope_tables

            qq = apply_rope_tables(q, rope[0][:, None], rope[1][:, None])
        return paged_attention_reference(
            qq, k_pages, v_pages, page_table, lengths, causal=causal,
            sm_scale=scale, k_scales=k_scales, v_scales=v_scales,
            kv_block=kv_block, ancestor=ancestor, first=first,
        )

    def _pallas_path():
        rows = group * sq
        bh = _pick_block_h(h_kv, rows) if block_h is None else int(block_h)
        if h_kv % bh:
            raise ValueError(f"block_h {bh} must divide heads {h_kv}")
        if bh * rows > FMHA_DECODE_MAX_ROWS:
            # the per-program fp32 scratch is (block_h*sq) rows — past
            # the budget even block_h=1 cannot honor it, and lowering
            # failures at serve time are opaque.  Decode s_q is "1 or
            # a small chunk" by design; bigger tiles belong to the
            # training ladder (or implementation="xla").
            raise ValueError(
                f"block_h*sq = {bh}*{rows} exceeds the decode kernel's "
                f"per-program row budget (FMHA_DECODE_MAX_ROWS="
                f"{FMHA_DECODE_MAX_ROWS}); chunk the query (sq <= "
                f"{FMHA_DECODE_MAX_ROWS}) or use implementation='xla'")
        width = page_table.shape[1]
        num_pages = (width if first is None or max_pages is None
                     else min(width, int(max_pages)))
        cfg = _DecodeConfig(
            sm_scale=scale, causal=causal, sq=sq, block_h=bh,
            page_size=k_pages.shape[2], num_pages=num_pages,
            kv_block=int(kv_block), has_scales=k_scales is not None,
            has_rope=rope is not None, ancestor=ancestor, group=group,
            has_first=first is not None,
            table_pages=width if first is not None else 0,
            pages=_pages_per_step(
                k_pages.shape[2], d, bh, k_pages.dtype.itemsize,
                num_pages, k_scales is not None),
            copies=_kernel_copies(d, k_scales is not None),
        )
        planes = [q]
        if rope is not None:
            planes += _rope_operands(q, rope)
        if group > 1:
            # (b, h, sq, d) -> (b, h_kv, group * sq, d): a plain reshape
            planes = [t.reshape(b, h_kv, rows, d) for t in planes]
        out = _decode_pallas(
            *(planes + [None] * (4 - len(planes))), k_pages, v_pages,
            k_scales, v_scales, page_table, lengths, cfg, first=first,
        )
        return out.reshape(b, h, sq, d) if group > 1 else out

    return run_kernel(
        "fmha_decode", _pallas_path, _xla_path, impl
    )


def decode_contiguous(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    page_size: int = 128,
    implementation: Optional[str] = None,
) -> jnp.ndarray:
    """Run :func:`fmha_decode` over CONTIGUOUS ``(b, h, s_k, d)`` K/V by
    viewing it as trivially-paged storage — the
    ``flash_attention(implementation="decode")`` seam, and the A/B
    comparator ``validate_fmha_decode`` times against the XLA reference.

    ``causal=True`` requires ``sq <= sk`` and places query token ``i``
    at position ``sk - sq + i`` (the decode convention: the cache's
    tail IS the query window — for ``sq == sk`` this is exactly the
    training ladder's causal mask).
    """
    b, h, sk, d = k.shape
    sq = q.shape[2]
    if causal and sq > sk:
        raise ValueError(
            f"decode causal needs sq <= sk (query positions are the "
            f"cache tail), got sq={sq} sk={sk}"
        )
    ps = min(page_size, sk)
    pad = (-sk) % ps
    if pad:
        padw = ((0, 0), (0, 0), (0, pad), (0, 0))
        k, v = jnp.pad(k, padw), jnp.pad(v, padw)
    num_pages = (sk + pad) // ps
    # (b, h, np*ps, d) -> (b*np, h, ps, d): sequence b's logical page p
    # is physical page b*np + p
    pagify = lambda x: jnp.moveaxis(
        x.reshape(b, h, num_pages, ps, d), 2, 1
    ).reshape(b * num_pages, h, ps, d)
    page_table = (
        jnp.arange(b, dtype=jnp.int32)[:, None] * num_pages
        + jnp.arange(num_pages, dtype=jnp.int32)[None, :]
    )
    lengths = jnp.full((b,), sk, jnp.int32)
    return fmha_decode(
        q, pagify(k), pagify(v), page_table, lengths, causal=causal,
        sm_scale=sm_scale, implementation=implementation,
    )
