"""Short-sequence attention (fmha-short): single-pass Pallas kernels.

The flash kernel in ``ops/attention.py`` is built for long sequences:
a 3-D grid with an ``arbitrary`` (serialized) k-block reduction axis and
online-softmax (m, l) carries in VMEM scratch.  At short sequence
lengths that machinery IS the cost — the r5 profile measured 10.2 TF/s
fwd at s=1024 causal (~5% of v5e peak) vs 45-50 TF/s at s=4096-8192,
because each grid step does a tiny dot and the correction multiplies /
scratch round-trips dominate.  The reference ships per-seqlen
{128,256,384,512} SM80 kernels for exactly this reason
(apex/contrib/csrc/fmha/, setup.py:405-415).

This module is the TPU analog of that seqlen-specialized family, as ONE
kernel pair instead of four: when the whole kv sequence fits a single
k-block, compute the exact softmax in one pass —

- **no online softmax**: no (m, l) scratch, no correction multiplies,
  no ``arbitrary`` grid axis; every grid dimension is ``parallel``;
- **bh packing**: the grid is 1-D over blocked ``batch*heads``; each
  program holds ``block_bh`` heads' q/k/v resident in VMEM and issues
  their dots back-to-back from one unrolled body, so the MXU pipeline
  stays full instead of draining between b*h tiny programs;
- **one fused backward**: a single kernel emits dq, dk, dv (and dbias)
  in one pass, reading q/k/v/do once and computing the score replay
  (s, p, dp, dz) once — the flash split (dkv + dq kernels) exists only
  to bound residency across k/q block loops, which a short sequence
  does not have.

Feature parity with the flash kernel is total: additive bias (all
broadcast batchings) with a real bias gradient, segment-id varlen
masking, and counter-based dropout replayed from the SAME hash
(``attention._keep_mask``), so for a given seed the flash kernel, this
kernel, and the XLA reference drop bit-identical entries.

Dispatch: ``flash_attention(implementation=None)`` auto-routes here
below the measured crossover (``FMHA_SHORT_MAX_SEQ``, overridable via
``APEX_TPU_FMHA_SHORT_MAX_SEQ``); ``implementation="short"`` forces
this kernel (strict — lowering failures raise).  The crossover default
is provisional until the next TPU capture: ``tools/kernel_validation.py``
sweeps s∈{128,256,384,512,1024} for short-vs-flash-vs-XLA and records
the measured boundary into KERNELS_TPU.json.
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
    _pad_seq,
    _prec,
    mha_reference,
)
from apex_tpu.ops.common import name_attention_residuals, shape_struct
from apex_tpu.telemetry.spans import kernel_name
from apex_tpu.utils.platform import default_implementation

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fmha_short", "FMHA_SHORT_MAX_SEQ", "short_seq_threshold"]

#: Auto-dispatch crossover: ``flash_attention`` routes to this kernel
#: when both sq and sk are at or below this bound.  512 matches the
#: reference's fmhalib window ({128,256,384,512}) and keeps the fused
#: backward's score-space temporaries comfortably inside Mosaic's 16 MB
#: scoped-vmem budget at every block_bh the auto-sizer picks.  The value
#: is PROVISIONAL until the next TPU window: tools/kernel_validation.py
#: measures short-vs-flash at s∈{128,256,384,512,1024} and the capture
#: gates on this constant agreeing with the measurement (the same
#: record-don't-hand-pick contract as FLASH_FP32_XLA_MAX_SEQ).
FMHA_SHORT_MAX_SEQ = 512

#: Per-program score-space budget (elements): block_bh is sized so
#: block_bh * sq_p * sk_p stays at or under this.  512*1024 is the same
#: area bound the fp32 flash blocks are clamped to
#: (attention.FLASH_FP32_MAX_BLOCK_AREA) — the fused backward keeps ~4
#: (sq, sk) fp32 temporaries live per unrolled head, so this keeps the
#: worst case near the flash backward's proven-compiling footprint.
FMHA_SHORT_BLOCK_ELEMS = 512 * 1024

#: Unroll bound: the bh block is an unrolled python loop of 2-D MXU
#: dots (the guaranteed Mosaic lowering path — batched 3-D dots are
#: not); 16 copies of the body bounds code size while still amortizing
#: grid-step overhead 16x at s=128.
FMHA_SHORT_MAX_BLOCK_BH = 16


def short_seq_threshold() -> int:
    """The auto-dispatch crossover, env-overridable so an ops rollout
    can move the boundary without a code change
    (``APEX_TPU_FMHA_SHORT_MAX_SEQ=0`` disables short dispatch)."""
    v = os.environ.get("APEX_TPU_FMHA_SHORT_MAX_SEQ")
    return int(v) if v else FMHA_SHORT_MAX_SEQ


def default_block_bh(sq_p: int, sk_p: int, bh: int) -> int:
    """How many (batch*head) programs one grid step packs."""
    by_area = max(1, FMHA_SHORT_BLOCK_ELEMS // (sq_p * sk_p))
    return max(1, min(by_area, FMHA_SHORT_MAX_BLOCK_BH, bh))


class _ShortConfig(NamedTuple):
    """Static kernel configuration (hashable for custom_vjp)."""

    sm_scale: float
    causal: bool
    dropout_rate: float
    block_bh: int
    q_len: int       # unpadded
    kv_len: int      # unpadded
    heads: int       # heads per batch entry (per-batch bias index map)
    # "shared": one (1, sq, sk) bias block for every program;
    # "per_batch": (b, sq, sk), one block per batch entry — block_bh is
    #   then constrained to divide heads so each program's bh block
    #   stays inside a single batch (no h-times broadcast in HBM);
    # "per_head": (bh_p, sq, sk), one row per (batch*head)
    bias_mode: str
    bias_grad: bool
    hi_precision: bool = False


def _dot2(a, b, contract, cfg):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        preferred_element_type=jnp.float32,
        precision=_prec(cfg),
    )


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _short_fwd_kernel(*refs, cfg: _ShortConfig, has_bias, has_segs,
                      has_dropout):
    (q_ref, k_ref, v_ref), rest = refs[:3], refs[3:]
    bias_ref = qseg_ref = kseg_ref = seed_ref = None
    if has_bias:
        bias_ref, rest = rest[0], rest[1:]
    if has_segs:
        (qseg_ref, kseg_ref), rest = rest[:2], rest[2:]
    if has_dropout:
        seed_ref, rest = rest[0], rest[1:]
    o_ref, lse_ref = rest

    i = pl.program_id(0)
    sq_p, sk_p = q_ref.shape[1], k_ref.shape[1]
    # q padding needs no forward mask (padded rows are sliced off by the
    # caller and replayed under an explicit q-row mask in the backward)
    needs_mask = cfg.causal or has_segs or cfg.kv_len < sk_p
    if needs_mask or has_dropout:
        q_idx = jax.lax.broadcasted_iota(jnp.int32, (sq_p, sk_p), 0)
        k_idx = jax.lax.broadcasted_iota(jnp.int32, (sq_p, sk_p), 1)
    base_mask = None
    if needs_mask:
        base_mask = k_idx < cfg.kv_len
        if cfg.causal:
            base_mask = jnp.logical_and(base_mask, k_idx <= q_idx)

    for bi in range(cfg.block_bh):
        q = q_ref[bi].astype(jnp.float32) * cfg.sm_scale    # (sq_p, d)
        s = _dot2(q, k_ref[bi].astype(jnp.float32),
                  ((1,), (1,)), cfg)                        # (sq_p, sk_p)
        if has_bias:
            # shared/per_batch blocks carry one (sq, sk) slab for the
            # whole program; per_head carries one per bi
            s = s + bias_ref[
                bi if cfg.bias_mode == "per_head" else 0
            ].astype(jnp.float32)
        mask = base_mask
        if has_segs:
            seg = qseg_ref[bi, 0][:, None] == kseg_ref[bi, 0][None, :]
            mask = seg if mask is None else jnp.logical_and(mask, seg)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        if has_dropout:
            keep = _keep_mask(
                seed_ref[0, 0], i * cfg.block_bh + bi, q_idx, k_idx,
                jnp.uint32(_keep_threshold(cfg.dropout_rate)),
            )
            p_v = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - cfg.dropout_rate))
        else:
            p_v = p
        acc = _dot2(p_v, v_ref[bi].astype(jnp.float32), ((1,), (0,)), cfg)
        l = jnp.maximum(l, 1e-30)
        o_ref[bi] = (acc / l).astype(o_ref.dtype)
        lse_ref[bi, 0] = m[:, 0] + jnp.log(l[:, 0])


# ---------------------------------------------------------------------------
# Fused backward kernel (dq + dk + dv + optional dbias in one pass)
# ---------------------------------------------------------------------------


def _short_bwd_kernel(*refs, cfg: _ShortConfig, has_bias, has_segs,
                      has_dropout):
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
    emit_dbias = has_bias and cfg.bias_grad
    if emit_dbias:
        dq_ref, dk_ref, dv_ref, dbias_ref = rest
    else:
        (dq_ref, dk_ref, dv_ref), dbias_ref = rest, None

    i = pl.program_id(0)
    sq_p, sk_p = q_ref.shape[1], k_ref.shape[1]
    # unlike the forward, padded q ROWS must be masked here: their lse
    # is garbage (fully-masked rows clamp l), and dk/dv sum over sq
    needs_mask = (cfg.causal or has_segs or cfg.kv_len < sk_p
                  or cfg.q_len < sq_p)
    if needs_mask or has_dropout:
        q_idx = jax.lax.broadcasted_iota(jnp.int32, (sq_p, sk_p), 0)
        k_idx = jax.lax.broadcasted_iota(jnp.int32, (sq_p, sk_p), 1)
    base_mask = None
    if needs_mask:
        base_mask = jnp.logical_and(q_idx < cfg.q_len, k_idx < cfg.kv_len)
        if cfg.causal:
            base_mask = jnp.logical_and(base_mask, k_idx <= q_idx)

    db_acc = None
    for bi in range(cfg.block_bh):
        qblk = q_ref[bi].astype(jnp.float32)               # (sq_p, d)
        kblk = k_ref[bi].astype(jnp.float32)               # (sk_p, d)
        vblk = v_ref[bi].astype(jnp.float32)
        doblk = do_ref[bi].astype(jnp.float32)
        lse = lse_ref[bi, 0][:, None]                      # (sq_p, 1)
        delta = delta_ref[bi, 0][:, None]
        s = _dot2(qblk, kblk, ((1,), (1,)), cfg) * cfg.sm_scale
        if has_bias:
            s = s + bias_ref[
                bi if cfg.bias_mode == "per_head" else 0
            ].astype(jnp.float32)
        mask = base_mask
        if has_segs:
            seg = qseg_ref[bi, 0][:, None] == kseg_ref[bi, 0][None, :]
            mask = seg if mask is None else jnp.logical_and(mask, seg)
        p = jnp.exp(s - lse)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = _dot2(doblk, vblk, ((1,), (1,)), cfg)         # (sq_p, sk_p)
        if has_dropout:
            keep = _keep_mask(
                seed_ref[0, 0], i * cfg.block_bh + bi, q_idx, k_idx,
                jnp.uint32(_keep_threshold(cfg.dropout_rate)),
            )
            inv_kp = 1.0 / (1.0 - cfg.dropout_rate)
            p_drop = jnp.where(keep, p, 0.0) * inv_kp
            dp = jnp.where(keep, dp, 0.0) * inv_kp
        else:
            p_drop = p
        dv_ref[bi] = _dot2(p_drop, doblk, ((0,), (0,)), cfg).astype(
            dv_ref.dtype)
        dz = p * (dp - delta)                              # grad wrt s+bias
        if emit_dbias:
            if cfg.bias_mode == "per_head":
                dbias_ref[bi] = dz.astype(dbias_ref.dtype)
            else:
                # shared/per_batch: one partial sum per program; the
                # vjp folds the program axis back in XLA
                db_acc = dz if db_acc is None else db_acc + dz
        dk_ref[bi] = _dot2(dz * cfg.sm_scale, qblk, ((0,), (0,)),
                           cfg).astype(dk_ref.dtype)
        dq_ref[bi] = _dot2(dz * cfg.sm_scale, kblk, ((1,), (0,)),
                           cfg).astype(dq_ref.dtype)
    if emit_dbias and cfg.bias_mode != "per_head":
        dbias_ref[0] = db_acc.astype(dbias_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------


def _in_specs(cfg, sq_p, sk_p, d_p, has_bias, has_segs, has_dropout):
    bb = cfg.block_bh
    specs = [
        pl.BlockSpec((bb, sq_p, d_p), lambda i: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bb, sk_p, d_p), lambda i: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((bb, sk_p, d_p), lambda i: (i, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    if has_bias:
        if cfg.bias_mode == "per_head":
            specs.append(pl.BlockSpec((bb, sq_p, sk_p),
                                      lambda i: (i, 0, 0),
                                      memory_space=pltpu.VMEM))
        elif cfg.bias_mode == "per_batch":
            # block_bh divides heads (wrapper invariant), so program i
            # covers bh rows of exactly one batch entry: (i*bb)//heads
            heads = cfg.heads
            specs.append(pl.BlockSpec(
                (1, sq_p, sk_p), lambda i: ((i * bb) // heads, 0, 0),
                memory_space=pltpu.VMEM))
        else:
            specs.append(pl.BlockSpec((1, sq_p, sk_p),
                                      lambda i: (0, 0, 0),
                                      memory_space=pltpu.VMEM))
    if has_segs:
        # (bh, 1, s): the middle singleton keeps the trailing two block
        # dims Mosaic-tileable, same trick as the flash kernel
        specs.append(pl.BlockSpec((bb, 1, sq_p), lambda i: (i, 0, 0)))
        specs.append(pl.BlockSpec((bb, 1, sk_p), lambda i: (i, 0, 0)))
    if has_dropout:
        specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0),
                                  memory_space=pltpu.SMEM))
    return specs


def _compiler_params():
    # every axis parallel: no serialized reduction dimension exists
    return pltpu.CompilerParams(dimension_semantics=("parallel",))


def _short_fwd_pallas(q, k, v, bias, qseg, kseg, seed, cfg: _ShortConfig):
    bh_p, sq_p, d_p = q.shape
    sk_p = k.shape[1]
    has_bias = bias is not None
    has_segs = qseg is not None
    has_dropout = cfg.dropout_rate > 0.0
    inputs = [q, k, v]
    if has_bias:
        inputs.append(bias)
    if has_segs:
        inputs.extend([qseg, kseg])
    if has_dropout:
        inputs.append(seed)
    out, lse = pl.pallas_call(
        functools.partial(
            _short_fwd_kernel, cfg=cfg, has_bias=has_bias,
            has_segs=has_segs, has_dropout=has_dropout,
        ),
        grid=(bh_p // cfg.block_bh,),
        in_specs=_in_specs(cfg, sq_p, sk_p, d_p, has_bias, has_segs,
                           has_dropout),
        out_specs=[
            pl.BlockSpec((cfg.block_bh, sq_p, d_p), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((cfg.block_bh, 1, sq_p), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            shape_struct((bh_p, sq_p, d_p), q.dtype, q, k, v),
            shape_struct((bh_p, 1, sq_p), jnp.float32, q, k, v),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name=kernel_name("fmha_short.fwd"),
    )(*inputs)
    return out, lse


def _short_bwd_pallas(q, k, v, bias, qseg, kseg, seed, out, lse, do,
                      cfg: _ShortConfig):
    bh_p, sq_p, d_p = q.shape
    sk_p = k.shape[1]
    has_bias = bias is not None
    has_segs = qseg is not None
    has_dropout = cfg.dropout_rate > 0.0
    emit_dbias = has_bias and cfg.bias_grad
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

    in_specs = _in_specs(cfg, sq_p, sk_p, d_p, has_bias, has_segs,
                         has_dropout)
    in_specs.extend([
        pl.BlockSpec((cfg.block_bh, sq_p, d_p), lambda i: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((cfg.block_bh, 1, sq_p), lambda i: (i, 0, 0)),
        pl.BlockSpec((cfg.block_bh, 1, sq_p), lambda i: (i, 0, 0)),
    ])
    out_specs = [
        pl.BlockSpec((cfg.block_bh, sq_p, d_p), lambda i: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((cfg.block_bh, sk_p, d_p), lambda i: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((cfg.block_bh, sk_p, d_p), lambda i: (i, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    out_shape = [
        shape_struct((bh_p, sq_p, d_p), q.dtype, q, k, v, do),
        shape_struct((bh_p, sk_p, d_p), k.dtype, q, k, v, do),
        shape_struct((bh_p, sk_p, d_p), v.dtype, q, k, v, do),
    ]
    if emit_dbias:
        if cfg.bias_mode == "per_head":
            out_specs.append(pl.BlockSpec(
                (cfg.block_bh, sq_p, sk_p), lambda i: (i, 0, 0),
                memory_space=pltpu.VMEM))
            out_shape.append(
                shape_struct((bh_p, sq_p, sk_p), jnp.float32, q, k, v, do))
        else:
            # shared/per_batch: per-PROGRAM partial sums — "parallel"
            # grid steps cannot accumulate into one shared block, so
            # each program writes its bh-block's sum and the vjp folds
            # the grid axis in XLA
            n_prog = bh_p // cfg.block_bh
            out_specs.append(pl.BlockSpec(
                (1, sq_p, sk_p), lambda i: (i, 0, 0),
                memory_space=pltpu.VMEM))
            out_shape.append(
                shape_struct((n_prog, sq_p, sk_p), jnp.float32,
                             q, k, v, do))
    res = pl.pallas_call(
        functools.partial(
            _short_bwd_kernel, cfg=cfg, has_bias=has_bias,
            has_segs=has_segs, has_dropout=has_dropout,
        ),
        grid=(bh_p // cfg.block_bh,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name=kernel_name("fmha_short.bwd"),
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
def _short(q, k, v, bias, qseg, kseg, seed, cfg):
    out, _ = _short_fwd_pallas(q, k, v, bias, qseg, kseg, seed, cfg)
    return out


def _short_fwd(q, k, v, bias, qseg, kseg, seed, cfg):
    out, lse = name_attention_residuals(
        *_short_fwd_pallas(q, k, v, bias, qseg, kseg, seed, cfg))
    return out, (q, k, v, bias, qseg, kseg, seed, out, lse)


def _int_zero(x):
    return (
        None if x is None
        else np.zeros(x.shape, jax.dtypes.float0)
    )


def _short_bwd(cfg, res, do):
    q, k, v, bias, qseg, kseg, seed, out, lse = res
    dq, dk, dv, dbias = _short_bwd_pallas(
        q, k, v, bias, qseg, kseg, seed, out, lse, do, cfg
    )
    if bias is not None and not cfg.bias_grad:
        # constant-mask contract: caller declared the bias non-trainable
        dbias = jnp.zeros_like(bias)
    elif bias is not None:
        if cfg.bias_mode == "shared":
            # fold the per-program partial sums back to the one shared
            # (1, sq, sk) bias block the primal consumed
            dbias = jnp.sum(dbias, axis=0, keepdims=True)
        elif cfg.bias_mode == "per_batch":
            # (n_prog, sq, sk) partial sums, heads//block_bh programs
            # per batch entry → (b, sq, sk), the primal's bias shape
            n_prog, psq, psk = dbias.shape
            per_batch = cfg.heads // cfg.block_bh
            dbias = dbias.reshape(
                n_prog // per_batch, per_batch, psq, psk).sum(axis=1)
        dbias = dbias.astype(bias.dtype)
        # per-head bias needs no fold: the kernel input was already
        # (bh_p, sq, sk), and the wrapper's broadcast_to transpose
        # sums heads/batches back to the user's bias shape
    return (dq, dk, dv, dbias, _int_zero(qseg), _int_zero(kseg),
            _int_zero(seed))


_short.defvjp(_short_fwd, _short_bwd)


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------


def fmha_short(
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
    block_bh: Optional[int] = None,
    implementation: Optional[str] = None,
) -> jnp.ndarray:
    """Single-pass short-sequence attention over ``(b, h, s, d)``.

    Same contract as :func:`~apex_tpu.ops.attention.flash_attention`
    (bias / segment ids / counter-hash dropout, identical masks for a
    given seed), specialized for sequences where the whole kv fits one
    block.  ``block_bh`` overrides how many (batch*head) programs one
    grid step packs (default: sized by ``FMHA_SHORT_BLOCK_ELEMS``).

    Most callers should not call this directly: ``flash_attention``
    auto-routes here below the measured crossover, and accepts
    ``implementation="short"`` to force this kernel.
    """
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("segment ids must be given for both q and kv")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    if bias is not None and bias.ndim < 4:
        bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
    from apex_tpu.ops.common import run_kernel

    if implementation == "short":
        # the flash_attention-facing spelling: forcing "short" on the
        # short entry point itself means the strict kernel path (NOT a
        # silent XLA resolve, which run_kernel would otherwise do for
        # any non-"pallas" string)
        implementation = "pallas"
    if implementation not in (None, "pallas", "xla"):
        raise ValueError(
            f"unknown implementation {implementation!r}; expected None, "
            "'pallas'/'short', or 'xla'"
        )
    impl = implementation or default_implementation()

    def _xla_path():
        return mha_reference(
            q, k, v, causal=causal, sm_scale=sm_scale, bias=bias,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        )

    def _pallas_path():
        return _fmha_short_pallas(
            q, k, v, causal, sm_scale, bias, q_segment_ids,
            kv_segment_ids, dropout_rate, dropout_seed,
            bias_requires_grad, block_bh,
        )

    return run_kernel(
        "fmha_short", _pallas_path, _xla_path, impl
    )


def _fmha_short_pallas(
    q, k, v, causal, sm_scale, bias, q_segment_ids, kv_segment_ids,
    dropout_rate, dropout_seed, bias_requires_grad, block_bh,
):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = (1.0 / d**0.5) if sm_scale is None else float(sm_scale)
    # pad every in-kernel dimension to the 128-lane tile: seq lengths
    # become both sublane (scores) and lane (lse) extents, and zero
    # k/v columns do not change q@k^T
    pad_q = (-sq) % _LANES
    pad_k = (-sk) % _LANES
    pad_d = (-d) % _LANES
    sq_p, sk_p, d_p = sq + pad_q, sk + pad_k, d + pad_d
    if pad_d:
        padd = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        q, k, v = padd(q), padd(k), padd(v)

    bh = b * h
    if block_bh is None:
        bb = default_block_bh(sq_p, sk_p, bh)
    else:
        bb = max(1, min(int(block_bh), bh))
    bias_mode = "shared"
    if bias is not None and bias.shape[0] > 1 and bias.shape[1] == 1:
        # per-batch bias rides its native (b, sq, sk) layout; each
        # program must then stay inside one batch entry, so clamp
        # block_bh to a divisor of heads (heads are small powers of
        # two in practice — the clamp rarely bites)
        bias_mode = "per_batch"
        while h % bb:
            bb -= 1
    pad_bh = (-bh) % bb
    bh_p = bh + pad_bh

    def flat(x, pad_s):
        x = _pad_seq(x.reshape(bh, x.shape[2], x.shape[3]), pad_s)
        return jnp.pad(x, ((0, pad_bh), (0, 0), (0, 0))) if pad_bh else x

    qf, kf, vf = flat(q, pad_q), flat(k, pad_k), flat(v, pad_k)

    bias_flat = None
    if bias is not None:
        if bias_mode == "per_batch":
            bias_flat = jnp.broadcast_to(
                bias, (b, 1, sq, sk)).reshape(b, sq, sk)
        elif bias.shape[0] == 1 and bias.shape[1] == 1:
            bias_flat = jnp.broadcast_to(
                bias, (1, 1, sq, sk)).reshape(1, sq, sk)
        else:
            bias_mode = "per_head"
            bias_flat = jnp.broadcast_to(
                bias, (b, h, sq, sk)).reshape(bh, sq, sk)
        bias_flat = _pad_seq(_pad_seq(bias_flat, pad_q, axis=1),
                             pad_k, axis=2)
        if bias_mode == "per_head" and pad_bh:
            bias_flat = jnp.pad(bias_flat, ((0, pad_bh), (0, 0), (0, 0)))

    qseg = kseg = None
    if q_segment_ids is not None:
        # per-bh segment rows keep the 1-D grid's index maps trivial;
        # padded q rows keep id 0 (flash convention — their lse stays
        # finite), padded kv ids get -1 so they never match a real
        # segment
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

    cfg = _ShortConfig(
        sm_scale=scale, causal=causal, dropout_rate=float(dropout_rate),
        block_bh=bb, q_len=sq, kv_len=sk, heads=h, bias_mode=bias_mode,
        bias_grad=bool(bias_requires_grad),
        hi_precision=(q.dtype == jnp.float32),
    )
    out = _short(qf, kf, vf, bias_flat, qseg, kseg, seed_arr, cfg)
    out = out[:bh, :sq].reshape(b, h, sq, d_p)
    if pad_d:
        out = out[..., :d]
    return out
