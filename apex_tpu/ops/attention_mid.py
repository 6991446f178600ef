"""Mid-sequence pipelined attention (fmha-mid): streamed K/V + bh packing.

The middle tier of the attention dispatch ladder
(``docs/attention.md``), covering 512 < s <= ~2048 — the band the
flagship actually trains in.  KERNELS_TPU.json measured the flash kernel
at 10.2 TF/s fwd at s=1024 causal vs ~50 TF/s at s>=4096: with the
measured-optimal 1024x1024 blocks the whole K/V sequence sits in ONE
block, so the streamed-K/V design degenerates to one fused attention
per (b, h) — and causal costs the same wall time as full (0.843 vs
0.857 ms) because there are no blocks to skip.

This kernel does three things the flash kernel's shape degeneracy
loses:

- **k-blocks smaller than the sequence**: the kb grid axis streams K/V
  through VMEM with Mosaic's revolving-buffer (double-buffered)
  pipelining;
- **bh packing above s=512** (PR 1's ``block_bh`` trick lifted past the
  short-kernel window): each program holds several (batch*head) tiles
  and issues their dots from one unrolled body, so one tile's dots can
  run under another's softmax chain and a grid step's fixed cost is
  shared;
- **causal block-skipping that actually fires**: the per-q-block upper
  bound on the kb loop now has num_k > 1 blocks to skip, and the K/V
  index maps are clamped to it, so a skipped block is not copied
  either.

**The forward's schedule** (PR 29; ``_mid_fwd_kernel``).  What the
v5e measured on the forward alone (``tools/fmha_fwd_ablation.py``,
PERF.md section 6): it was not waiting for the MXU (bf16 operands
instead of float32 ones changed nothing) nor for memory, but for the
cross-lane reductions of the online softmax — with the row max and sum
stubbed a call fell from 2.45 to 1.58 ms, with every other bookkeeping
cost stubbed by under 0.2 ms.  So the scores are held TRANSPOSED,
``(block_k, block_q)`` with keys on sublanes and queries on lanes: a
row's max and sum are elementwise folds across vregs plus one
8-sublane fold, the running max and sum are lane-dense ``(1,
block_q)`` rows (two vregs, not a lane-broadcast ``(block_q, 128)``
scratch), ``lse`` leaves in the layout its output has, and ``acc`` is
``(d, block_q)``, transposed once a q block.  The forward has its own,
wider blocks (``default_mid_fwd_blocks``: 512x512 where the extents
allow), so that ``acc`` is rescaled once per 512 keys; bf16 operands
go to the MXU as they are with float32 accumulation, the statistics
stay float32; and where the head width is padded (64 -> 128 lanes,
192 -> 256) V's first pad lane holds ones, so the row sum comes out of
the PV product in that lane of ``acc`` and is not computed at all.

The backward is ONE fused kernel emitting dq/dk/dv (and dbias) per the
PR 1 contract — the flash split (dkv + dq kernels) exists to bound
residency across long-sequence block loops, which the mid band does
not need: dq lives whole in a VMEM scratch (``block_bh_bwd`` is sized
so it fits) while dk/dv accumulate per k-block, so q/k/v/do are read
once and the score replay (s, p, dp, dz) happens once.

Feature parity with the flash and short kernels is total: additive
bias (all broadcast batchings) with a real bias gradient, segment-id
varlen masking, and counter-based dropout replayed from the SAME hash
(``attention._keep_mask``) with the SAME (bh, q, k) indexing — so for
a given seed all three kernels and the XLA reference drop bit-identical
entries.

``return_lse=True`` additionally returns the per-row log-sum-exp, with
a real lse cotangent in the fused backward (``dz = p*(dp - delta +
dlse)``) — this is what lets ``ops/ring_attention.py`` run its
per-shard inner attention through this kernel and merge ring blocks by
lse outside it.

Dispatch: ``flash_attention(implementation=None)`` auto-routes here for
short-crossover < s <= ``FMHA_MID_MAX_SEQ`` (env-overridable via
``APEX_TPU_FMHA_MID_MAX_SEQ``, 0 disables — pinning the ladder back to
the flash kernel bit-identically); ``implementation="mid"`` forces this
kernel (strict — lowering failures raise).  The crossover default is
PROVISIONAL until the next TPU capture: ``tools/kernel_validation.py``
sweeps mid-vs-flash-vs-XLA across the band and GATES on this constant
agreeing with the measurement, plus a causal-beats-full gate at s=1024
(the block-skip proof).
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops.attention import (
    _LANES,
    _NEG_INF,
    _interpret,
    _keep_mask,
    _keep_threshold,
    _mask_specialized,
    _pad_seq,
    _prec,
    BIAS_PER_BATCH,
    BIAS_PER_HEAD,
    mha_reference,
)
from apex_tpu.ops.common import name_attention_residuals, shape_struct
from apex_tpu.telemetry.spans import kernel_name
from apex_tpu.utils.platform import default_implementation

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "fmha_mid", "FMHA_MID_MAX_SEQ", "mid_seq_threshold",
    "default_mid_blocks", "default_mid_block_bh",
]

#: Auto-dispatch crossover: ``flash_attention`` routes to this kernel
#: when max(sq, sk) is above the short-kernel window and at or below
#: this bound.  2048 brackets the band where the flash kernel's
#: measured-optimal 1024x1024 blocks leave it with <= 2 k-blocks to
#: pipeline (10-20 TF/s, KERNELS_TPU.json) while s>=4096 already
#: streams at ~50 TF/s.  PROVISIONAL until the next TPU window:
#: tools/kernel_validation.py measures mid-vs-flash across the band and
#: the capture gates on this constant agreeing with the measurement
#: (the same record-don't-hand-pick contract as FMHA_SHORT_MAX_SEQ).
FMHA_MID_MAX_SEQ = 2048

#: Per-program score-space budget (elements): block_bh is sized so
#: block_bh * block_q * block_k stays at or under this — the same
#: 512*1024 area bound as FLASH_FP32_MAX_BLOCK_AREA and
#: FMHA_SHORT_BLOCK_ELEMS, keeping the worst-case fp32 temporaries near
#: the flash backward's proven-compiling footprint.
FMHA_MID_BLOCK_ELEMS = 512 * 1024

#: Fused-backward dq residency budget (elements): the single backward
#: kernel holds the WHOLE dq extent for its bh block in fp32 VMEM
#: scratch (that is what makes one fused pass possible), so
#: block_bh_bwd * sq_padded * d_padded is capped here (512K elements =
#: 2 MB fp32) and the backward runs with a (possibly smaller) divisor
#: of the forward's block_bh.
FMHA_MID_BWD_DQ_ELEMS = 512 * 1024

#: What ``default_mid_fwd_blocks`` lets a forward grid step keep in VMEM
#: by its own estimate, and the limit handed to Mosaic (the estimate
#: does not see all of the compiler's temporaries; the default scoped
#: limit of 16 MiB is too small for eight 512-row tiles of 128 lanes).
FMHA_MID_FWD_VMEM_BUDGET = 20 * 1024 * 1024
FMHA_MID_FWD_VMEM_LIMIT = 32 * 1024 * 1024

#: Unroll bound, same rationale as the short kernel: the bh block is an
#: unrolled python loop of 2-D MXU dots; 16 copies bounds code size.
FMHA_MID_MAX_BLOCK_BH = 16

#: Default block sizes.  256x256 at lane-multiple-of-256 sequence
#: lengths (s=1024 causal then runs 10/16 blocks = 0.625x the full
#: work), 128x128 otherwise (halves the q/k padding waste at ragged
#: lengths like 576/640 and skips even harder: 36/64 at s=1024).
#: kernel_validation.py sweeps alternatives; these are the shipped
#: pre-capture defaults.
MID_BLOCK_Q = 256
MID_BLOCK_K = 256


def mid_seq_threshold() -> int:
    """The mid-tier auto-dispatch crossover, env-overridable so an ops
    rollout can move the boundary without a code change
    (``APEX_TPU_FMHA_MID_MAX_SEQ=0`` disables mid dispatch, pinning the
    ladder's upper tiers back to the flash kernel)."""
    v = os.environ.get("APEX_TPU_FMHA_MID_MAX_SEQ")
    return int(v) if v is not None and v != "" else FMHA_MID_MAX_SEQ


def default_mid_blocks(sq_p: int, sk_p: int):
    """The backward's (block_q, block_k) for padded sequence extents,
    and the unit q and k/v are padded to.

    Prefers the 256x256 default; drops to 128 along an axis whose
    lane-rounded extent is not a 256 multiple (ragged mid lengths like
    576/640) so block padding stays at most one 128 tile.
    """
    bq = MID_BLOCK_Q if sq_p % MID_BLOCK_Q == 0 else 128
    bk = MID_BLOCK_K if sk_p % MID_BLOCK_K == 0 else 128
    return min(bq, sq_p), min(bk, sk_p)


def default_mid_block_bh(block_q: int, block_k: int, bh: int) -> int:
    """How many (batch*head) tiles the bh axis is padded to a multiple
    of; the backward packs a divisor of it (``_bwd_block_bh``), the
    forward another (``default_mid_fwd_blocks``)."""
    by_area = max(1, FMHA_MID_BLOCK_ELEMS // (block_q * block_k))
    return max(1, min(by_area, FMHA_MID_MAX_BLOCK_BH, bh))


def _fwd_vmem_bytes(bb, bq, bk, d_p, itemsize, bias_rows):
    """What a forward grid step keeps in VMEM: the q/out/k/v (and
    bias) blocks, each double-buffered, the float32 ``acc`` scratch,
    and the score-sized float32 temporaries of two tiles in flight."""
    blocks = 2 * bb * (2 * bq + 2 * bk) * d_p * itemsize
    bias = 2 * bias_rows * bq * bk * 4
    acc = bb * d_p * bq * 4
    work = 2 * 4 * bq * bk * 4
    return blocks + bias + acc + work


def default_mid_fwd_blocks(sq_p: int, sk_p: int, d_p: int, itemsize: int,
                           bias_batch: int, bh_unit: int):
    """The forward's (block_q, block_k, block_bh) from what its input
    shows: padded extents, padded head width, operand width, and the
    bias that rides along (``_MidConfig.bias_batch``: 0 for none).

    A wider block folds more vregs elementwise before each 8-sublane
    fold and rescales ``acc`` less often a score, so the blocks are the
    largest of 512/256/128 that divide the padded extents; the packing
    is the largest divisor of ``bh_unit`` that the VMEM budget holds
    (one tile's dots run under another's softmax chain, and a grid
    step's fixed cost is shared: 8 tiles 0.918 ms a call, 4 tiles
    0.953, 2 tiles 1.03 at ``bf16[256,1024,128]`` on the v5e)."""
    def largest(n):
        return next(b for b in (512, 256, 128) if n % b == 0)

    def fits(bb):
        bias_rows = bb if bias_batch == BIAS_PER_HEAD else min(bias_batch, 1)
        return _fwd_vmem_bytes(bb, bq, bk, d_p, itemsize,
                               bias_rows) <= FMHA_MID_FWD_VMEM_BUDGET

    bq, bk = largest(sq_p), largest(sk_p)
    bb = bh_unit
    while bb > 1 and (bh_unit % bb or not fits(bb)):
        bb -= 1
    return bq, bk, bb


def _bwd_block_bh(block_bh: int, sq_p: int, d_p: int) -> int:
    """Largest divisor of the forward ``block_bh`` whose whole-dq
    scratch fits the backward residency budget."""
    cap = max(1, FMHA_MID_BWD_DQ_ELEMS // (sq_p * d_p))
    bb = block_bh
    while bb > 1 and (bb > cap or block_bh % bb):
        bb -= 1
    return max(1, bb)


class _MidConfig(NamedTuple):
    """Static kernel configuration (hashable for custom_vjp)."""

    sm_scale: float
    causal: bool
    dropout_rate: float
    # the backward's blocks; the wrapper pads q, k/v and bh to them
    block_q: int
    block_k: int
    block_bh: int        # bh padding unit (a multiple of both packings)
    block_bh_bwd: int    # divisor of block_bh, sized by dq residency
    # the forward's own blocks (``default_mid_fwd_blocks``): divisors of
    # the padded extents, and of block_bh
    fwd_block_q: int
    fwd_block_k: int
    fwd_block_bh: int
    q_len: int           # unpadded
    kv_len: int          # unpadded
    heads: int           # heads per batch entry (per-batch bias maps)
    # flattened-bias batching, same encoding as the flash kernel:
    # 0 = no bias, 1 = one shared (sq, sk) bias, BIAS_PER_BATCH /
    # BIAS_PER_HEAD as in ops/attention.py
    bias_batch: int
    bias_grad: bool
    hi_precision: bool = False
    # whether the primal returns (out, lse) and the backward consumes a
    # real dlse cotangent (the ring-attention merge path)
    with_lse: bool = False
    # the pad lane of V the wrapper filled with ones, so that the row
    # sum comes out of the PV product in that lane of the accumulator;
    # -1: V has no such lane and the sum is taken on the VPU
    sum_lane: int = -1


def _dot2(a, b, contract, cfg):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        preferred_element_type=jnp.float32,
        precision=_prec(cfg),
    )


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_last_kb(cfg, num_k, j):
    """The last k block a q block attends (the causal bound)."""
    if not cfg.causal:
        return num_k - 1
    return jnp.minimum(
        num_k - 1, ((j + 1) * cfg.fwd_block_q - 1) // cfg.fwd_block_k)


def _mid_fwd_kernel(
    *refs, cfg: _MidConfig, num_k: int, has_bias, has_segs, has_dropout,
):
    """One (bh tile, q block, k block) a grid step.

    The scores are held TRANSPOSED, ``(block_k, block_q)``: keys on
    sublanes, queries on lanes.  A row's max and sum are then
    elementwise folds across vregs plus one 8-sublane fold (no
    cross-lane reduction a row group), the running max and sum are
    lane-dense ``(1, block_q)`` rows, and ``lse`` leaves in the layout
    its output has.  ``acc`` is ``(d, block_q)`` and is transposed
    once, when the q block is written."""
    (q_ref, k_ref, v_ref), rest = refs[:3], refs[3:]
    bias_ref = qseg_ref = kseg_ref = seed_ref = None
    if has_bias:
        bias_ref, rest = rest[0], rest[1:]
    if has_segs:
        (qseg_ref, kseg_ref), rest = rest[:2], rest[2:]
    if has_dropout:
        seed_ref, rest = rest[0], rest[1:]
    o_ref, lse_ref, acc_ref, m_ref = rest[:4]
    l_ref = rest[4] if cfg.sum_lane < 0 else None

    i, j, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block_q, block_k = cfg.fwd_block_q, cfg.fwd_block_k
    last_kb = _fwd_last_kb(cfg, num_k, j)
    # bf16 operands go to the MXU as they are (float32 accumulation);
    # anything else is computed in float32 as before
    cdt = q_ref.dtype if q_ref.dtype == jnp.bfloat16 else jnp.float32
    kv_padded = cfg.kv_len < num_k * block_k
    # a masked weight is exp(-1e30 - m) == 0 by itself once its row has
    # seen one live key, which a causal-only mask guarantees from block
    # 0 on; any other mask can leave a row without one, so p is zeroed
    zero_masked_p = has_segs or has_bias or kv_padded

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        if l_ref is not None:
            l_ref[...] = jnp.zeros_like(l_ref)

    def _body(masked):
        if masked or has_dropout:
            k_idx = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0
            )
            q_idx = j * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1
            )
        for bi in range(cfg.fwd_block_bh):
            q = (q_ref[bi].astype(jnp.float32) * cfg.sm_scale).astype(cdt)
            s = _dot2(k_ref[bi].astype(cdt), q, ((1,), (1,)), cfg)  # (bk, bq)
            if has_bias:
                s = s + bias_ref[
                    bi if cfg.bias_batch == BIAS_PER_HEAD else 0
                ].astype(jnp.float32).T
            if masked:
                live = []
                if kv_padded:
                    live.append(k_idx < cfg.kv_len)
                if cfg.causal:
                    live.append(k_idx <= q_idx)
                if has_segs:
                    live.append(
                        kseg_ref[bi, 0][:, None] == qseg_ref[bi, 0][None, :])
                mask = functools.reduce(jnp.logical_and, live)
                s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[bi]                                  # (1, bq)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked and zero_masked_p:
                p = jnp.where(mask, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            if l_ref is not None:
                l_ref[bi] = l_ref[bi] * corr + jnp.sum(
                    p, axis=0, keepdims=True)
            if has_dropout:
                keep = _keep_mask(
                    seed_ref[0, 0], i * cfg.fwd_block_bh + bi, q_idx, k_idx,
                    jnp.uint32(_keep_threshold(cfg.dropout_rate)),
                )
                p = jnp.where(keep, p, 0.0) * (
                    1.0 / (1.0 - cfg.dropout_rate))
            acc_ref[bi] = acc_ref[bi] * corr + _dot2(
                v_ref[bi].astype(cdt), p.astype(cdt), ((0,), (0,)), cfg
            )                                                   # (d, bq)
            m_ref[bi] = m_new

    conds = []
    if cfg.causal:
        conds.append(kb * block_k + (block_k - 1) > j * block_q)
    if kv_padded:
        conds.append(kb == num_k - 1)
    _mask_specialized(kb <= last_kb, conds, has_segs, _body)

    @pl.when(kb == last_kb)
    def _finalize():
        for bi in range(cfg.fwd_block_bh):
            acc = acc_ref[bi]
            if l_ref is None:
                l = acc[cfg.sum_lane:cfg.sum_lane + 1, :]
            else:
                l = l_ref[bi]
            l = jnp.maximum(l, 1e-30)
            o_ref[bi] = (acc / l).T.astype(o_ref.dtype)
            lse_ref[bi] = m_ref[bi] + jnp.log(l)


# ---------------------------------------------------------------------------
# Fused backward kernel (dq + dk + dv + optional dbias in one pass)
# ---------------------------------------------------------------------------


def _mid_bwd_kernel(
    *refs, cfg: _MidConfig, num_q: int, num_k: int, has_bias, has_segs,
    has_dropout,
):
    bb = cfg.block_bh_bwd
    (q_ref, k_ref, v_ref), rest = refs[:3], refs[3:]
    bias_ref = qseg_ref = kseg_ref = seed_ref = None
    if has_bias:
        bias_ref, rest = rest[0], rest[1:]
    if has_segs:
        (qseg_ref, kseg_ref), rest = rest[:2], rest[2:]
    if has_dropout:
        seed_ref, rest = rest[0], rest[1:]
    do_ref, lse_ref, delta_ref = rest[:3]
    rest = rest[3:]
    dlse_ref = None
    if cfg.with_lse:
        dlse_ref, rest = rest[0], rest[1:]
    emit_dbias = has_bias and cfg.bias_grad
    if emit_dbias:
        dq_ref, dk_ref, dv_ref, dbias_ref = rest[:4]
        rest = rest[4:]
    else:
        (dq_ref, dk_ref, dv_ref), rest = rest[:3], rest[3:]
        dbias_ref = None
    dq_acc, dk_acc, dv_acc = rest

    i, kb, jq = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block_q, block_k = cfg.block_q, cfg.block_k
    # under causal masking, q blocks strictly above the diagonal band
    # contribute nothing to this k block — but with a bias gradient
    # every (jq, kb) dbias block must still be written, so the skip only
    # applies when dbias is not emitted (flash-kernel contract)
    first_jq = (kb * block_k) // block_q if (
        cfg.causal and not emit_dbias) else 0

    @pl.when(jnp.logical_and(kb == 0, jq == 0))
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(jq == 0)
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body(masked):
        if masked or has_dropout:
            q_idx = jq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_idx = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
        for bi in range(bb):
            qblk = q_ref[bi].astype(jnp.float32)             # (bq, d)
            kblk = k_ref[bi].astype(jnp.float32)             # (bk, d)
            vblk = v_ref[bi].astype(jnp.float32)
            doblk = do_ref[bi].astype(jnp.float32)
            lse = lse_ref[bi, 0][:, None]                    # (bq, 1)
            delta = delta_ref[bi, 0][:, None]
            s = _dot2(qblk, kblk, ((1,), (1,)), cfg) * cfg.sm_scale
            if has_bias:
                s = s + bias_ref[
                    bi if cfg.bias_batch == BIAS_PER_HEAD else 0
                ].astype(jnp.float32)
            p = jnp.exp(s - lse)
            if masked:
                mask = jnp.logical_and(
                    q_idx < cfg.q_len, k_idx < cfg.kv_len
                )
                if cfg.causal:
                    mask = jnp.logical_and(mask, k_idx <= q_idx)
                if has_segs:
                    mask = jnp.logical_and(
                        mask,
                        qseg_ref[bi, 0][:, None] == kseg_ref[bi, 0][None, :],
                    )
                p = jnp.where(mask, p, 0.0)
            dp = _dot2(doblk, vblk, ((1,), (1,)), cfg)       # (bq, bk)
            if has_dropout:
                keep = _keep_mask(
                    seed_ref[0, 0], i * bb + bi, q_idx, k_idx,
                    jnp.uint32(_keep_threshold(cfg.dropout_rate)),
                )
                inv_kp = 1.0 / (1.0 - cfg.dropout_rate)
                p_drop = jnp.where(keep, p, 0.0) * inv_kp
                dp = jnp.where(keep, dp, 0.0) * inv_kp
            else:
                p_drop = p
            dv_acc[bi] += _dot2(p_drop, doblk, ((0,), (0,)), cfg)
            resid = dp - delta                               # grad wrt s
            if cfg.with_lse:
                # lse cotangent: d lse_i / d s_ij = p_ij (the normalized
                # softmax), independent of dropout — one extra row add
                resid = resid + dlse_ref[bi, 0][:, None]
            dz = p * resid                                   # grad wrt s+bias
            if emit_dbias:
                if cfg.bias_batch == BIAS_PER_HEAD:
                    dbias_ref[bi] = dz.astype(dbias_ref.dtype)
                elif bi == 0:
                    dbias_ref[0] = dz.astype(dbias_ref.dtype)
                else:
                    dbias_ref[0] += dz.astype(dbias_ref.dtype)
            dk_acc[bi] += _dot2(dz * cfg.sm_scale, qblk, ((0,), (0,)), cfg)
            dq_acc[bi, pl.ds(jq * block_q, block_q), :] += _dot2(
                dz * cfg.sm_scale, kblk, ((1,), (0,)), cfg
            )

    # a (jq, kb) block needs masking iff it intersects the causal
    # diagonal, is the padded q tail (garbage lse/delta rows would
    # pollute dk/dv), or the padded kv tail (garbage k cols would
    # pollute dq)
    conds = []
    if cfg.causal:
        conds.append(kb * block_k + (block_k - 1) > jq * block_q)
    if cfg.q_len < num_q * block_q:                          # q padding
        conds.append(jq == num_q - 1)
    if cfg.kv_len < num_k * block_k:                         # kv padding
        conds.append(kb == num_k - 1)
    if emit_dbias:
        # every block runs so every dbias block is written; the mask
        # keeps skippable blocks' contributions at exactly zero
        run = jq <= num_q - 1
    else:
        run = jq >= first_jq
    _mask_specialized(run, conds, has_segs, _body)

    @pl.when(jq == num_q - 1)
    def _write_dkv():
        for bi in range(bb):
            dk_ref[bi] = dk_acc[bi].astype(dk_ref.dtype)
            dv_ref[bi] = dv_acc[bi].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(kb == num_k - 1, jq == num_q - 1))
    def _write_dq():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------


def _bwd_in_specs(cfg, bb, d_p, has_bias, has_segs, has_dropout):
    """The backward's BlockSpecs for q/k/v (+bias/segs/seed), on its
    (i, kb, jq) grid."""
    block_q, block_k, heads = cfg.block_q, cfg.block_k, cfg.heads
    specs = [
        pl.BlockSpec((bb, block_q, d_p), lambda i, kb, jq: (i, jq, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bb, block_k, d_p), lambda i, kb, jq: (i, kb, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bb, block_k, d_p), lambda i, kb, jq: (i, kb, 0),
                     memory_space=pltpu.VMEM),
    ]
    if has_bias:
        specs.append(pl.BlockSpec(
            (bb if cfg.bias_batch == BIAS_PER_HEAD else 1, block_q, block_k),
            lambda i, kb, jq: (_bias_row(cfg, bb, i), jq, kb),
            memory_space=pltpu.VMEM))
    if has_segs:
        # (bh, 1, s) layout: the middle singleton keeps the trailing
        # two block dims Mosaic-tileable, same trick as flash/short
        specs.append(pl.BlockSpec((bb, 1, block_q),
                                  lambda i, kb, jq: (i, 0, jq)))
        specs.append(pl.BlockSpec((bb, 1, block_k),
                                  lambda i, kb, jq: (i, 0, kb)))
    if has_dropout:
        specs.append(pl.BlockSpec((1, 1), lambda i, kb, jq: (0, 0),
                                  memory_space=pltpu.SMEM))
    return specs


def _bias_row(cfg, bb, i):
    """Leading block index of program ``i``'s bias."""
    if cfg.bias_batch == BIAS_PER_HEAD:
        return i
    if cfg.bias_batch == BIAS_PER_BATCH:
        # bb divides heads (wrapper invariant), so program i covers bh
        # rows of exactly one batch entry
        return (i * bb) // cfg.heads
    return 0


def _bwd_compiler_params():
    # both block axes are serialized: dq accumulates across kb AND jq
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary")
    )


def _mid_fwd_pallas(q, k, v, bias, qseg, kseg, seed, cfg: _MidConfig):
    bh_p, psq, d_p = q.shape
    psk = k.shape[1]
    bq, bk, bb = cfg.fwd_block_q, cfg.fwd_block_k, cfg.fwd_block_bh
    assert psq % bq == 0 and psk % bk == 0 and bh_p % bb == 0
    assert psk - cfg.kv_len < bk
    num_k = psk // bk
    has_bias = bias is not None
    has_segs = qseg is not None
    has_dropout = cfg.dropout_rate > 0.0

    def kv(j, kb):
        # a block above the causal bound is not computed: its step keeps
        # the block index of the last one that was, so nothing is copied
        return jnp.minimum(kb, _fwd_last_kb(cfg, num_k, j))

    in_specs = [
        pl.BlockSpec((bb, bq, d_p), lambda i, j, kb: (i, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bb, bk, d_p), lambda i, j, kb: (i, kv(j, kb), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bb, bk, d_p), lambda i, j, kb: (i, kv(j, kb), 0),
                     memory_space=pltpu.VMEM),
    ]
    inputs = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (bb if cfg.bias_batch == BIAS_PER_HEAD else 1, bq, bk),
            lambda i, j, kb: (_bias_row(cfg, bb, i), j, kv(j, kb)),
            memory_space=pltpu.VMEM))
        inputs.append(bias)
    if has_segs:
        in_specs.append(pl.BlockSpec((bb, 1, bq),
                                     lambda i, j, kb: (i, 0, j)))
        in_specs.append(pl.BlockSpec((bb, 1, bk),
                                     lambda i, j, kb: (i, 0, kv(j, kb))))
        inputs.extend([qseg, kseg])
    if has_dropout:
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j, kb: (0, 0),
                                     memory_space=pltpu.SMEM))
        inputs.append(seed)
    scratch_shapes = [
        pltpu.VMEM((bb, d_p, bq), jnp.float32),    # acc, transposed
        pltpu.VMEM((bb, 1, bq), jnp.float32),      # running max
    ]
    if cfg.sum_lane < 0:
        scratch_shapes.append(pltpu.VMEM((bb, 1, bq), jnp.float32))
    out, lse = pl.pallas_call(
        functools.partial(
            _mid_fwd_kernel, cfg=cfg, num_k=num_k, has_bias=has_bias,
            has_segs=has_segs, has_dropout=has_dropout,
        ),
        grid=(bh_p // bb, psq // bq, num_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bb, bq, d_p), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 1, bq), lambda i, j, kb: (i, 0, j)),
        ],
        out_shape=[
            shape_struct((bh_p, psq, d_p), q.dtype, q, k, v),
            shape_struct((bh_p, 1, psq), jnp.float32, q, k, v),
        ],
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=FMHA_MID_FWD_VMEM_LIMIT,
        ),
        interpret=_interpret(),
        name=kernel_name("fmha_mid.fwd"),
    )(*inputs)
    return out, lse


def _mid_bwd_pallas(q, k, v, bias, qseg, kseg, seed, out, lse, do, dlse,
                    cfg: _MidConfig):
    bh_p, psq, d_p = q.shape
    psk = k.shape[1]
    num_q, num_k = psq // cfg.block_q, psk // cfg.block_k
    assert psk - cfg.kv_len < cfg.block_k and psq - cfg.q_len < cfg.block_q
    has_bias = bias is not None
    has_segs = qseg is not None
    has_dropout = cfg.dropout_rate > 0.0
    emit_dbias = has_bias and cfg.bias_grad
    bb = cfg.block_bh_bwd
    # delta = rowsum(do * o) — cheap, XLA fuses it
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )[:, None, :]

    inputs = [q, k, v]
    if has_bias:
        inputs.append(bias)
    if has_segs:
        inputs.extend([qseg, kseg])
    if has_dropout:
        inputs.append(seed)
    inputs.extend([do, lse, delta])
    if cfg.with_lse:
        inputs.append(dlse.astype(jnp.float32)[:, None, :])

    in_specs = _bwd_in_specs(cfg, bb, d_p, has_bias, has_segs, has_dropout)
    in_specs.extend([
        pl.BlockSpec((bb, cfg.block_q, d_p), lambda i, kb, jq: (i, jq, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bb, 1, cfg.block_q), lambda i, kb, jq: (i, 0, jq)),
        pl.BlockSpec((bb, 1, cfg.block_q), lambda i, kb, jq: (i, 0, jq)),
    ])
    if cfg.with_lse:
        in_specs.append(
            pl.BlockSpec((bb, 1, cfg.block_q), lambda i, kb, jq: (i, 0, jq))
        )

    out_specs = [
        # dq flushes ONCE per bh block (constant index map over the two
        # serialized axes) from the whole-extent scratch
        pl.BlockSpec((bb, psq, d_p), lambda i, kb, jq: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bb, cfg.block_k, d_p), lambda i, kb, jq: (i, kb, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bb, cfg.block_k, d_p), lambda i, kb, jq: (i, kb, 0),
                     memory_space=pltpu.VMEM),
    ]
    out_shape = [
        shape_struct((bh_p, psq, d_p), q.dtype, q, k, v, do),
        shape_struct((bh_p, psk, d_p), k.dtype, q, k, v, do),
        shape_struct((bh_p, psk, d_p), v.dtype, q, k, v, do),
    ]
    if emit_dbias:
        if cfg.bias_batch == BIAS_PER_HEAD:
            out_specs.append(pl.BlockSpec(
                (bb, cfg.block_q, cfg.block_k),
                lambda i, kb, jq: (i, jq, kb), memory_space=pltpu.VMEM))
            out_shape.append(
                shape_struct((bh_p, psq, psk), jnp.float32, q, k, v, do))
        else:
            # shared/per_batch: per-PROGRAM partial sums over the bh
            # block; the vjp folds the program axis back in XLA
            n_prog = bh_p // bb
            out_specs.append(pl.BlockSpec(
                (1, cfg.block_q, cfg.block_k),
                lambda i, kb, jq: (i, jq, kb), memory_space=pltpu.VMEM))
            out_shape.append(
                shape_struct((n_prog, psq, psk), jnp.float32, q, k, v, do))
    res = pl.pallas_call(
        functools.partial(
            _mid_bwd_kernel, cfg=cfg, num_q=num_q, num_k=num_k,
            has_bias=has_bias, has_segs=has_segs, has_dropout=has_dropout,
        ),
        grid=(bh_p // bb, num_k, num_q),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bb, psq, d_p), jnp.float32),
            pltpu.VMEM((bb, cfg.block_k, d_p), jnp.float32),
            pltpu.VMEM((bb, cfg.block_k, d_p), jnp.float32),
        ],
        compiler_params=_bwd_compiler_params(),
        interpret=_interpret(),
        name=kernel_name("fmha_mid.bwd"),
    )(*inputs)
    if emit_dbias:
        dq, dk, dv, dbias = res
    else:
        (dq, dk, dv), dbias = res, None
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# custom_vjp wrapper (flattened, padded (bh_p, s_p, d_p) layout)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _mid(q, k, v, bias, qseg, kseg, seed, cfg):
    out, lse = _mid_fwd_pallas(q, k, v, bias, qseg, kseg, seed, cfg)
    if cfg.with_lse:
        return out, lse[:, 0]
    return out


def _mid_fwd(q, k, v, bias, qseg, kseg, seed, cfg):
    out, lse = name_attention_residuals(
        *_mid_fwd_pallas(q, k, v, bias, qseg, kseg, seed, cfg))
    res = (q, k, v, bias, qseg, kseg, seed, out, lse)
    if cfg.with_lse:
        return (out, lse[:, 0]), res
    return out, res


def _int_zero(x):
    return (
        None if x is None
        else np.zeros(x.shape, jax.dtypes.float0)
    )


def _mid_bwd(cfg, res, ct):
    q, k, v, bias, qseg, kseg, seed, out, lse = res
    if cfg.with_lse:
        do, dlse = ct
    else:
        do, dlse = ct, None
    dq, dk, dv, dbias = _mid_bwd_pallas(
        q, k, v, bias, qseg, kseg, seed, out, lse, do, dlse, cfg
    )
    if bias is not None and not cfg.bias_grad:
        # constant-mask contract: caller declared the bias non-trainable
        dbias = jnp.zeros_like(bias)
    elif bias is not None:
        if cfg.bias_batch == 1:
            # fold the per-program partial sums back to the one shared
            # (1, sq, sk) bias block the primal consumed
            dbias = jnp.sum(dbias, axis=0, keepdims=True)
        elif cfg.bias_batch == BIAS_PER_BATCH:
            # (n_prog, sq, sk) partial sums, heads//block_bh_bwd
            # programs per batch entry → (b, sq, sk), the primal's shape
            n_prog, psq, psk = dbias.shape
            per_batch = cfg.heads // cfg.block_bh_bwd
            dbias = dbias.reshape(
                n_prog // per_batch, per_batch, psq, psk).sum(axis=1)
        dbias = dbias.astype(bias.dtype)
    return (dq, dk, dv, dbias, _int_zero(qseg), _int_zero(kseg),
            _int_zero(seed))


_mid.defvjp(_mid_fwd, _mid_bwd)


# ---------------------------------------------------------------------------
# XLA fallback with lse (the reference path for return_lse callers)
# ---------------------------------------------------------------------------


def _xla_with_lse(q, k, v, causal, sm_scale, bias, q_segment_ids,
                  kv_segment_ids, dropout_rate, dropout_seed):
    """``mha_reference`` plus the per-row log-sum-exp.

    The output comes from ``mha_reference`` itself (ONE reference
    implementation of the masking/dropout/normalization semantics —
    the cross-kernel dropout-mask and ring-merge parity contracts both
    lean on it staying singular); only the lse is computed here, from
    the same masked-score formula every kernel uses.
    """
    out = mha_reference(
        q, k, v, causal=causal, sm_scale=sm_scale, bias=bias,
        q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed,
    )
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = (1.0 / d**0.5) if sm_scale is None else sm_scale
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    mask = jnp.ones((1, 1, sq, sk), bool)
    if causal:
        q_idx = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_idx = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        mask = mask & (k_idx <= q_idx)[None, None]
    if q_segment_ids is not None:
        mask = mask & (
            q_segment_ids[:, None, :, None]
            == kv_segment_ids[:, None, None, :]
        )
    s = jnp.where(mask, s, _NEG_INF)
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(jnp.broadcast_to(mask, s.shape), jnp.exp(s - m), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    lse = m[..., 0] + jnp.log(l[..., 0])
    return out, lse


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------


def fmha_mid(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    bias: Optional[jnp.ndarray] = None,
    q_segment_ids: Optional[jnp.ndarray] = None,
    kv_segment_ids: Optional[jnp.ndarray] = None,
    dropout_rate: float = 0.0,
    dropout_seed=None,
    bias_requires_grad: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_bh: Optional[int] = None,
    implementation: Optional[str] = None,
    return_lse: bool = False,
):
    """Pipelined mid-sequence attention over ``(b, h, s, d)``.

    Same contract as :func:`~apex_tpu.ops.attention.flash_attention`
    (bias / segment ids / counter-hash dropout, identical masks for a
    given seed), specialized for the band where K/V still fits a few
    streamed blocks: k-block streaming + (batch*head) packing + causal
    block-skipping, with ONE fused backward.  ``block_q``/``block_k``/
    ``block_bh`` override the measured defaults.

    ``return_lse=True`` returns ``(out, lse)`` with ``lse`` of shape
    ``(b, h, sq)`` — differentiable (the fused backward consumes a real
    lse cotangent), which is what the ring-attention merge needs.

    Most callers should not call this directly: ``flash_attention``
    auto-routes here inside the measured window, and accepts
    ``implementation="mid"`` to force this kernel.
    """
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("segment ids must be given for both q and kv")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    if bias is not None and bias.ndim < 4:
        bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
    from apex_tpu.ops.common import run_kernel

    if implementation == "mid":
        # the flash_attention-facing spelling: forcing "mid" on the mid
        # entry point itself means the strict kernel path
        implementation = "pallas"
    if implementation not in (None, "pallas", "xla"):
        raise ValueError(
            f"unknown implementation {implementation!r}; expected None, "
            "'pallas'/'mid', or 'xla'"
        )
    impl = implementation or default_implementation()

    def _xla_path():
        if return_lse:
            return _xla_with_lse(
                q, k, v, causal, sm_scale, bias, q_segment_ids,
                kv_segment_ids, dropout_rate, dropout_seed,
            )
        return mha_reference(
            q, k, v, causal=causal, sm_scale=sm_scale, bias=bias,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        )

    def _pallas_path():
        return _fmha_mid_pallas(
            q, k, v, causal, sm_scale, bias, q_segment_ids,
            kv_segment_ids, dropout_rate, dropout_seed,
            bias_requires_grad, block_q, block_k, block_bh, return_lse,
        )

    return run_kernel(
        "fmha_mid", _pallas_path, _xla_path, impl
    )


def _fmha_mid_pallas(
    q, k, v, causal, sm_scale, bias, q_segment_ids, kv_segment_ids,
    dropout_rate, dropout_seed, bias_requires_grad, block_q, block_k,
    block_bh, return_lse,
):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = (1.0 / d**0.5) if sm_scale is None else float(sm_scale)
    # lane-round the sequence extents first (they are lse lane dims and
    # score sublane/lane dims), then round up to the block sizes
    sq_l = sq + (-sq) % _LANES
    sk_l = sk + (-sk) % _LANES
    explicit_blocks = block_q is not None or block_k is not None
    if block_q is None or block_k is None:
        dbq, dbk = default_mid_blocks(sq_l, sk_l)
        block_q = dbq if block_q is None else min(int(block_q), sq_l)
        block_k = dbk if block_k is None else min(int(block_k), sk_l)
    else:
        block_q = min(int(block_q), sq_l)
        block_k = min(int(block_k), sk_l)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    pad_d = (-d) % _LANES
    d_p = d + pad_d
    # where the head width is padded, V's first pad lane holds ones and
    # the forward reads the row sum out of the PV product there; the
    # backward never sees it (dO is zero in the pad lanes).  Dropout
    # sums the undropped weights, so it keeps the VPU sum
    sum_lane = d if pad_d and dropout_rate == 0.0 else -1
    if pad_d:
        padd = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        q, k = padd(q), padd(k)
        if sum_lane >= 0:
            v = jnp.concatenate([
                v, jnp.ones(v.shape[:-1] + (1,), v.dtype),
                jnp.zeros(v.shape[:-1] + (pad_d - 1,), v.dtype)], axis=-1)
        else:
            v = padd(v)

    bh = b * h
    if block_bh is None:
        bb = default_mid_block_bh(block_q, block_k, bh)
    else:
        bb = max(1, min(int(block_bh), bh))
    bias_batch = 0
    if bias is not None:
        if bias.shape[0] > 1 and bias.shape[1] == 1:
            # per-batch bias rides its native (b, sq, sk) layout; each
            # program must then stay inside one batch entry, so clamp
            # block_bh to a divisor of heads
            bias_batch = BIAS_PER_BATCH
            while h % bb:
                bb -= 1
        elif bias.shape[0] == 1 and bias.shape[1] == 1:
            bias_batch = 1
        else:
            bias_batch = BIAS_PER_HEAD
    pad_bh = (-bh) % bb
    bh_p = bh + pad_bh

    def flat(x, pad_s):
        x = _pad_seq(x.reshape(bh, x.shape[2], x.shape[3]), pad_s)
        return jnp.pad(x, ((0, pad_bh), (0, 0), (0, 0))) if pad_bh else x

    qf, kf, vf = flat(q, pad_q), flat(k, pad_k), flat(v, pad_k)

    bias_flat = None
    if bias is not None:
        if bias_batch == BIAS_PER_BATCH:
            bias_flat = jnp.broadcast_to(
                bias, (b, 1, sq, sk)).reshape(b, sq, sk)
        elif bias_batch == 1:
            bias_flat = jnp.broadcast_to(
                bias, (1, 1, sq, sk)).reshape(1, sq, sk)
        else:
            bias_flat = jnp.broadcast_to(
                bias, (b, h, sq, sk)).reshape(bh, sq, sk)
        bias_flat = _pad_seq(_pad_seq(bias_flat, pad_q, axis=1),
                             pad_k, axis=2)
        if bias_batch == BIAS_PER_HEAD and pad_bh:
            bias_flat = jnp.pad(bias_flat, ((0, pad_bh), (0, 0), (0, 0)))

    qseg = kseg = None
    if q_segment_ids is not None:
        # per-bh segment rows (short-kernel layout): padded q rows keep
        # id 0 (their lse stays finite), padded kv ids get -1 so they
        # never match a real segment
        def seg_flat(ids, pad_s, pad_value):
            ids = jnp.broadcast_to(
                ids.astype(jnp.int32)[:, None, None, :],
                (b, h, 1, ids.shape[1]),
            ).reshape(bh, 1, ids.shape[1])
            if pad_s:
                ids = jnp.pad(ids, ((0, 0), (0, 0), (0, pad_s)),
                              constant_values=pad_value)
            if pad_bh:
                ids = jnp.pad(ids, ((0, pad_bh), (0, 0), (0, 0)),
                              constant_values=pad_value)
            return ids

        qseg = seg_flat(q_segment_ids, pad_q, 0)
        kseg = seg_flat(kv_segment_ids, pad_k, -1)

    seed_arr = None
    if dropout_rate > 0.0:
        seed_arr = jnp.asarray(dropout_seed, jnp.uint32).reshape(1, 1)

    # blocks the caller gave are both passes'; otherwise the forward
    # sizes its own
    fbq, fbk, fbb = default_mid_fwd_blocks(
        sq + pad_q, sk + pad_k, d_p, q.dtype.itemsize, bias_batch, bb)
    if explicit_blocks:
        fbq, fbk = block_q, block_k
    if explicit_blocks or block_bh is not None:
        fbb = bb
    cfg = _MidConfig(
        sm_scale=scale, causal=causal, dropout_rate=float(dropout_rate),
        block_q=block_q, block_k=block_k, block_bh=bb,
        block_bh_bwd=_bwd_block_bh(bb, sq + pad_q, d_p),
        fwd_block_q=fbq, fwd_block_k=fbk, fwd_block_bh=fbb,
        q_len=sq, kv_len=sk, heads=h, bias_batch=bias_batch,
        bias_grad=bool(bias_requires_grad),
        hi_precision=(q.dtype == jnp.float32),
        with_lse=bool(return_lse), sum_lane=sum_lane,
    )
    res = _mid(qf, kf, vf, bias_flat, qseg, kseg, seed_arr, cfg)
    if return_lse:
        out, lse = res
    else:
        out, lse = res, None
    out = out[:bh, :sq].reshape(b, h, sq, d_p)
    if pad_d:
        out = out[..., :d]
    if return_lse:
        lse = lse[:bh, :sq].reshape(b, h, sq)
        return out, lse
    return out
