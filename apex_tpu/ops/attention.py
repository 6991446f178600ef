"""Flash attention for TPU (Pallas), plus a reference XLA path.

Capability match — and supersession — of the reference's attention stack:
``fmhalib`` (apex/contrib/csrc/fmha/, fp16, seqlen<=512, SM80-only) and the
eight ``fast_*_multihead_attn`` extensions
(apex/contrib/csrc/multihead_attn/).  Those kernels materialise the
(sq, sk) score matrix per head; flash attention never does, so the TPU
design has no seqlen window: one online-softmax kernel covers every
sequence length, causal or not, bf16-first.  Beyond the reference's
kernels this one also supports, *in kernel*:

- **segment ids** (varlen): the TPU-native form of the reference's
  ``cu_seqlens`` packed-batch API (apex/contrib/fmha/fmha.py:33-80) —
  tokens attend only within equal segment ids;
- **additive bias** with a real bias gradient;
- **probability dropout** replayed exactly in the backward pass from a
  counter-based hash (the role Philox plays in the reference,
  apex/contrib/csrc/multihead_attn/philox.h) — the same hash evaluates
  in plain XLA, so the reference path produces bit-identical masks and
  the two implementations stay directly comparable.

Layout: ``(batch, heads, seq, head_dim)``.  Softmax statistics are fp32;
the accumulator is fp32; output matches the input dtype.

Kernel strategy (chosen for VMEM residency, see pallas_guide): all three
kernels run a 3-D grid with the reduction dimension innermost and carry
running state in VMEM scratch, so **no kernel ever holds a whole
sequence of K/V** — per-program residency is O(block_q·d + block_k·d)
and long sequences (32k+) compile:

- forward: grid ``(batch*heads, q_blocks, k_blocks)``; online-softmax
  (m, l, acc) scratch accumulates across the k-block dimension.
- backward dK/dV: grid ``(batch*heads, k_blocks, q_blocks)``; dK/dV
  scratch accumulates across the q-block dimension.
- backward dQ (+dBias): grid ``(batch*heads, q_blocks, k_blocks)``;
  dQ scratch accumulates across the k-block dimension.  Scores are
  replayed from the saved log-sum-exp (flash-attention-2 split).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops.common import name_attention_residuals, shape_struct
from apex_tpu.telemetry.spans import kernel_name
from apex_tpu.utils.platform import default_implementation, is_tpu

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "mha_reference", "k_block_bounds",
           "k_blocks_run"]

_NEG_INF = -1e30
_LANES = 128


# ---------------------------------------------------------------------------
# Counter-based dropout hash (shared by the Pallas kernels and the XLA
# reference so both paths draw the *same* mask for a given seed)
# ---------------------------------------------------------------------------


def _mix32(x: jnp.ndarray) -> jnp.ndarray:
    """32-bit finalizer (lowrey/murmur-style avalanche), uint32 in/out."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _keep_mask(seed, bh, q_idx, k_idx, keep_threshold):
    """Deterministic keep mask for dropout.

    ``seed``: uint32 scalar; ``bh``: flattened batch*head index (scalar or
    array); ``q_idx``/``k_idx``: broadcastable int32 position arrays;
    ``keep_threshold``: uint32 in [0, 2^24] = keep_prob * 2^24.
    """
    seed = seed.astype(jnp.uint32)
    bh = jnp.asarray(bh).astype(jnp.uint32)
    h = _mix32(seed ^ (bh * jnp.uint32(0x9E3779B1)))
    r = _mix32(
        (h + q_idx.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
        ^ (k_idx.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D))
    )
    return (r >> 8) < keep_threshold


def _keep_threshold(dropout_rate: float) -> int:
    return int(round((1.0 - dropout_rate) * (1 << 24)))


# ---------------------------------------------------------------------------
# XLA reference path
# ---------------------------------------------------------------------------


def mha_reference(
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
    q_offset=None,
    q_period: Optional[int] = None,
    window: int = 0,
) -> jnp.ndarray:
    """Plain XLA attention with fp32 softmax — the correctness reference,
    playing the role of the reference's pure-PyTorch ``impl='default'``
    path (apex/contrib/multihead_attn/self_multihead_attn_func.py).

    Dropout uses the same counter-based hash as the Pallas kernel, so for
    a given ``dropout_seed`` both implementations drop the same entries.
    ``q_offset`` / ``q_period`` / ``window``: :func:`flash_attention`'s
    positions of a causal call; the mask they stand for is built here.
    """
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("segment ids must be given for both q and kv")
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
        if _positioned(q_offset, q_period, window):
            # row r sits at key column q_offset + (r mod q_period)
            q_idx = (0 if q_offset is None else q_offset) + (
                q_idx if q_period is None else q_idx % q_period)
        seen = k_idx <= q_idx
        if window:
            seen &= q_idx - k_idx < window
        mask = mask & seen[None, None]
    if q_segment_ids is not None:
        mask = mask & (
            q_segment_ids[:, None, :, None] == kv_segment_ids[:, None, None, :]
        )
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.broadcast_to(mask, p.shape), p, 0.0)
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        seed = jnp.asarray(dropout_seed, jnp.uint32)
        bh_idx = jnp.arange(b * h, dtype=jnp.int32).reshape(b, h, 1, 1)
        q_idx = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)[None, None]
        k_idx = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)[None, None]
        keep = _keep_mask(seed, bh_idx, q_idx, k_idx,
                          jnp.uint32(_keep_threshold(dropout_rate)))
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def _interpret() -> bool:
    """Interpreter-mode Pallas off-TPU: the kernel bodies still run (and
    are testable) on CPU, at interpreter speed."""
    return not is_tpu()


class _FAConfig(NamedTuple):
    """Static kernel configuration (hashable for custom_vjp)."""

    sm_scale: float
    causal: bool
    dropout_rate: float
    block_q: int
    block_k: int
    q_len: int       # unpadded
    kv_len: int      # unpadded
    heads: int       # heads per batch entry (for segment-id index maps)
    # flattened-bias batching: 0 = no bias, 1 = one (sq, sk) bias shared by
    # all programs, BIAS_PER_BATCH = one per batch entry (b, sq, sk),
    # BIAS_PER_HEAD = one per program (b*h, sq, sk)
    bias_batch: int
    # whether the backward pass materialises dbias (False for constant
    # masks keeps the causal block-skip and avoids a (b*h, sq, sk) buffer)
    bias_grad: bool
    # full-precision MXU passes for the in-kernel dots: set for fp32
    # inputs, where the default (single bf16 pass) loses ~3 decimal
    # digits vs the XLA path at long sequence lengths (KERNELS_TPU gate)
    hi_precision: bool = False
    # the causal form that is told where its rows sit (``q_offset`` /
    # ``q_period`` / ``window`` of :func:`flash_attention`): the key
    # blocks of each q block come from :func:`k_block_bounds`, as
    # prefetched scalars
    positioned: bool = False
    window: int = 0


BIAS_PER_BATCH = -2
BIAS_PER_HEAD = -1

#: fp32 auto mode routes to XLA at or below this sequence length
#: (measured crossover, KERNELS_TPU.json; also read by
#: tools/kernel_validation.py so the recorded auto_impl cannot drift
#: from the actual dispatch)
FLASH_FP32_XLA_MAX_SEQ = 1024


def _prec(cfg):
    return jax.lax.Precision.HIGHEST if cfg.hi_precision else None


# ---------------------------------------------------------------------------
# A causal call that is told where its rows sit
# ---------------------------------------------------------------------------


def _positioned(q_offset, q_period, window) -> bool:
    return q_offset is not None or q_period is not None or bool(window)


def k_block_bounds(q_len: int, kv_len: int, q_offset, q_period: int,
                   window: int, block_q: int, block_k: int):
    """THE arithmetic of the position-bounded causal form, for every q
    block at once: ``(first_kb, last_kb, lo)``, each ``(q blocks,)``
    int32.  Query row ``r`` sits at key column ``q_offset + (r mod
    q_period)`` (``q_offset >= 0``; ``block_q`` divides ``q_period`` or
    one period holds every row, so a block's rows are consecutive
    columns from ``lo``) and sees column ``c`` iff ``c <= t`` and, with
    a ``window``, ``t - c < window``.  Key blocks outside ``first_kb ..
    last_kb`` hold nothing any row of the q block sees: above its last
    row's column, below its first row's window, or past the keys.

    ``q_offset`` may be traced (then the three are ``jax.lax``
    expressions, the kernel's prefetched scalars) or a Python int (then
    numpy: :func:`k_blocks_run` counts with the same lines)."""
    num_q, num_k = -(-q_len // block_q), -(-kv_len // block_k)
    if isinstance(q_offset, jax.Array):
        div, least, most = jax.lax.div, jax.lax.min, jax.lax.max
    else:
        div, least, most = np.floor_divide, np.minimum, np.maximum
    lo = q_offset + (np.arange(num_q, dtype=np.int32) * block_q) % q_period
    last = least(div(lo + (block_q - 1), block_k), num_k - 1)
    if not window:
        return np.zeros((num_q,), np.int32), last, lo
    first = least(div(most(lo - (window - 1), 0), block_k), last)
    return first, last, lo


def k_blocks_run(q_len: int, kv_len: int, q_offset: int = 0,
                 q_period: Optional[int] = None, window: int = 0,
                 block_q: Optional[int] = None, block_k: Optional[int] = None,
                 dtype=jnp.bfloat16):
    """``(key blocks whose body runs, key blocks of the extent)`` of one
    program (one head) of ``flash_attention(causal=True, q_offset=...,
    q_period=..., window=...)`` at these lengths: how much of the
    ``(q blocks, k blocks)`` grid the positions leave.  Host arithmetic
    on Python ints; the blocks are clamped as the call clamps them."""
    q_period = q_len if q_period is None else q_period
    block_q, block_k = _block_sizes(dtype, q_len, kv_len, block_q, block_k,
                                    True, q_period)
    first, last, _ = k_block_bounds(q_len, kv_len, int(q_offset), q_period,
                                    window, block_q, block_k)
    return (int(np.sum(last - first + 1)),
            -(-q_len // block_q) * -(-kv_len // block_k))


# ---------------------------------------------------------------------------
# Pallas forward
# ---------------------------------------------------------------------------


def _fa_fwd_kernel(
    *refs, cfg: _FAConfig, num_k: int, has_bias, has_segs, has_dropout,
):
    if cfg.positioned:
        # k_block_bounds' table, prefetched: (first_kb,) last_kb, lo
        n = 3 if cfg.window else 2
        bounds, refs = refs[:n], refs[n:]
    (q_ref, k_ref, v_ref), rest = refs[:3], refs[3:]
    bias_ref = qseg_ref = kseg_ref = seed_ref = None
    if has_bias:
        bias_ref, rest = rest[0], rest[1:]
    if has_segs:
        (qseg_ref, kseg_ref), rest = rest[:2], rest[2:]
    if has_dropout:
        seed_ref, rest = rest[0], rest[1:]
    if cfg.positioned:
        (o_ref, acc_ref, m_ref, l_ref), lse_ref = rest, None
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = rest

    i, j, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block_q, block_k = cfg.block_q, cfg.block_k
    if cfg.positioned:
        last_kb, lo = bounds[-2][j], bounds[-1][j]
    elif cfg.causal:
        last_kb = jnp.minimum(
            num_k - 1, ((j + 1) * block_q - 1) // block_k
        )
    else:
        last_kb = num_k - 1

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _body(masked):
        q = q_ref[0].astype(jnp.float32) * cfg.sm_scale    # (block_q, d)
        kblk = k_ref[0].astype(jnp.float32)                # (block_k, d)
        vblk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(cfg),
        )                                                  # (block_q, block_k)
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if masked or has_dropout:
            # a row's key column: its own index, or where it was said
            # to sit
            q_global = (lo if cfg.positioned else j * block_q) + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_global = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
        if masked:
            mask = k_global < cfg.kv_len
            if cfg.causal:
                mask = jnp.logical_and(mask, k_global <= q_global)
            if cfg.window:
                mask = jnp.logical_and(
                    mask, q_global - k_global < cfg.window)
            if has_segs:
                mask = jnp.logical_and(
                    mask, qseg_ref[0, 0][:, None] == kseg_ref[0, 0][None, :]
                )
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:, 0:1]
        l_prev = l_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        if has_dropout:
            keep = _keep_mask(
                seed_ref[0, 0], i, q_global, k_global,
                jnp.uint32(_keep_threshold(cfg.dropout_rate)),
            )
            p_acc = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - cfg.dropout_rate))
        else:
            p_acc = p
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p_acc, vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(cfg),
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    # blocks the diagonal, the window's edge or the padded tail cuts
    # take the masked body, the others the mask-free one
    conds = []
    if cfg.positioned:
        k0 = kb * block_k
        conds.append(k0 + (block_k - 1) > lo)
        if cfg.window:
            conds.append(k0 + cfg.window <= lo + (block_q - 1))
    elif cfg.causal:
        conds.append(kb * block_k + (block_k - 1) > j * block_q)
    if cfg.kv_len < num_k * block_k:                        # kv padding
        conds.append(kb == num_k - 1)
    run = kb <= last_kb
    if cfg.window:
        run = jnp.logical_and(run, kb >= bounds[0][j])
    _mask_specialized(run, conds, has_segs, _body)

    @pl.when(kb == last_kb)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = m_ref[:, 0] + jnp.log(l[:, 0])


def _fwd_in_specs(cfg, d, psq, psk, has_bias, has_segs, has_dropout,
                  swap_grid=False):
    """Input BlockSpecs shared by forward and dq kernels.

    ``swap_grid``: dkv kernel uses grid (i, kb, jq); forward/dq use
    (i, jq, kb).  Index maps below are written for (i, jq, kb) and
    wrapped when swapped.
    """
    block_q, block_k, heads = cfg.block_q, cfg.block_k, cfg.heads

    def w(f):  # rewire grid axes for the dkv kernel
        if not swap_grid:
            return f
        return lambda i, kb, jq: f(i, jq, kb)

    def kv_map(i, j, kb, *bounds):
        if bounds:
            # a step that runs no body asks for the block it holds: the
            # nearest one that is read, so nothing is fetched for it
            kb = jax.lax.min(kb, bounds[-2][j])
            if cfg.window:
                kb = jax.lax.max(kb, bounds[0][j])
        return (i, kb, 0)

    specs = [
        pl.BlockSpec((1, block_q, d), w(lambda i, j, kb, *_: (i, j, 0)),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), w(kv_map), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), w(kv_map), memory_space=pltpu.VMEM),
    ]
    if has_bias:
        if cfg.bias_batch == 1:
            bmap = lambda i, j, kb: (0, j, kb)
        elif cfg.bias_batch == BIAS_PER_BATCH:
            bmap = lambda i, j, kb: (i // heads, j, kb)
        else:  # BIAS_PER_HEAD
            bmap = lambda i, j, kb: (i, j, kb)
        specs.append(
            pl.BlockSpec((1, block_q, block_k), w(bmap),
                         memory_space=pltpu.VMEM)
        )
    if has_segs:
        # (b, 1, s) layout: the middle singleton keeps the trailing
        # two block dims Mosaic-tileable ((1, block) vs the (8, 128) rule)
        specs.append(pl.BlockSpec(
            (1, 1, block_q), w(lambda i, j, kb: (i // heads, 0, j))
        ))
        specs.append(pl.BlockSpec(
            (1, 1, block_k), w(lambda i, j, kb: (i // heads, 0, kb))
        ))
    if has_dropout:
        specs.append(pl.BlockSpec(
            (1, 1), w(lambda i, j, kb: (0, 0)), memory_space=pltpu.SMEM
        ))
    return specs


#: the limit handed to Mosaic for a call with positions: its default
#: 1024 x 1024 blocks keep ~17 MB of float32 score-space temporaries at
#: key width 256, over the default scoped limit of 16 MiB once the call
#: sits in a whole chunk program (the same limit as ``fmha_mid``'s
#: forward takes)
FLASH_POSITIONED_VMEM_LIMIT = 32 * 1024 * 1024


def _compiler_params(vmem_limit_bytes=None):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes,
    )


def _mask_specialized(run, conds, has_segs, body):
    """Emit ``body(masked=...)`` under ``pl.when`` with mask
    specialization: blocks matching no condition in ``conds`` (causal
    diagonal, padded tail) take the mask-free path — skipping the
    iota/compare/where chain that bounds kernel throughput on the VPU.
    Segment ids force the masked path everywhere; an empty ``conds``
    (non-causal, unpadded) makes every block mask-free."""
    if has_segs or not conds:
        pl.when(run)(lambda: body(masked=bool(has_segs)))
    else:
        need = functools.reduce(jnp.logical_or, conds)
        pl.when(jnp.logical_and(run, need))(lambda: body(masked=True))
        pl.when(jnp.logical_and(run, jnp.logical_not(need)))(
            lambda: body(masked=False))


def _fa_fwd_pallas(q, k, v, bias, qseg, kseg, seed, cfg: _FAConfig,
                   bounds=()):
    """``bounds``: :func:`k_block_bounds`' table of a positioned call
    (its prefetched scalars; such a call returns no ``lse``)."""
    bh, psq, d = q.shape
    psk = k.shape[1]
    num_q, num_k = psq // cfg.block_q, psk // cfg.block_k
    # mask specialization assumes padding is confined to the final block
    assert psk - cfg.kv_len < cfg.block_k and psq - cfg.q_len < cfg.block_q
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
    kernel = functools.partial(
        _fa_fwd_kernel, cfg=cfg, num_k=num_k, has_bias=has_bias,
        has_segs=has_segs, has_dropout=has_dropout,
    )
    grid = dict(
        grid=(bh, num_q, num_k),
        in_specs=_fwd_in_specs(cfg, d, psq, psk, has_bias, has_segs,
                               has_dropout),
        scratch_shapes=[
            pltpu.VMEM((cfg.block_q, d), jnp.float32),
            pltpu.VMEM((cfg.block_q, _LANES), jnp.float32),
            pltpu.VMEM((cfg.block_q, _LANES), jnp.float32),
        ],
    )
    o_spec = pl.BlockSpec((1, cfg.block_q, d), lambda i, j, kb, *_: (i, j, 0),
                          memory_space=pltpu.VMEM)
    o_shape = shape_struct((bh, psq, d), q.dtype, q, k, v)
    if cfg.positioned:
        spec = dict(
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(bounds), out_specs=o_spec, **grid),
            out_shape=o_shape,
            compiler_params=_compiler_params(FLASH_POSITIONED_VMEM_LIMIT))
    else:
        spec = dict(
            out_specs=[
                o_spec,
                pl.BlockSpec((1, 1, cfg.block_q), lambda i, j, kb: (i, 0, j)),
            ],
            out_shape=[
                o_shape,
                shape_struct((bh, 1, psq), jnp.float32, q, k, v),
            ],
            compiler_params=_compiler_params(), **grid)
    res = pl.pallas_call(
        kernel, interpret=_interpret(), name=kernel_name("fmha_flash.fwd"),
        **spec,
    )(*bounds, *inputs)
    if cfg.positioned:
        return res, None
    out, lse = res
    return out, lse[:, 0]


# ---------------------------------------------------------------------------
# Pallas backward
# ---------------------------------------------------------------------------


def _fa_bwd_dkv_kernel(
    *refs, cfg: _FAConfig, num_q: int, has_bias, has_segs, has_dropout,
):
    (q_ref, k_ref, v_ref), rest = refs[:3], refs[3:]
    bias_ref = qseg_ref = kseg_ref = seed_ref = None
    if has_bias:
        bias_ref, rest = rest[0], rest[1:]
    if has_segs:
        (qseg_ref, kseg_ref), rest = rest[:2], rest[2:]
    if has_dropout:
        seed_ref, rest = rest[0], rest[1:]
    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest

    i, kb, jq = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block_q, block_k = cfg.block_q, cfg.block_k
    # under causal masking, q blocks strictly above the diagonal band
    # contribute nothing to this k block
    first_jq = (kb * block_k) // block_q if cfg.causal else 0

    @pl.when(jq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _body(masked):
        kblk = k_ref[0].astype(jnp.float32)                # (block_k, d)
        vblk = v_ref[0].astype(jnp.float32)
        qblk = q_ref[0].astype(jnp.float32)                # (block_q, d)
        doblk = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            qblk, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(cfg),
        ) * cfg.sm_scale                                   # (block_q, block_k)
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if masked or has_dropout:
            q_global = jq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            k_global = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
        p = jnp.exp(s - lse)
        if masked:
            mask = jnp.logical_and(
                q_global < cfg.q_len, k_global < cfg.kv_len
            )
            if cfg.causal:
                mask = jnp.logical_and(mask, k_global <= q_global)
            if has_segs:
                mask = jnp.logical_and(
                    mask, qseg_ref[0, 0][:, None] == kseg_ref[0, 0][None, :]
                )
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            doblk, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(cfg),
        )
        if has_dropout:
            keep = _keep_mask(
                seed_ref[0, 0], i, q_global, k_global,
                jnp.uint32(_keep_threshold(cfg.dropout_rate)),
            )
            inv_kp = 1.0 / (1.0 - cfg.dropout_rate)
            p_drop = jnp.where(keep, p, 0.0) * inv_kp
            dp = jnp.where(keep, dp, 0.0) * inv_kp
        else:
            p_drop = p
        dv_acc[...] += jax.lax.dot_general(
            p_drop, doblk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(cfg),
        )
        dz = p * (dp - delta)                              # grad wrt s+bias
        dk_acc[...] += jax.lax.dot_general(
            dz * cfg.sm_scale, qblk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(cfg),
        )

    # Grid roles swapped vs fwd/dq: a q block needs masking iff it
    # intersects the causal diagonal (k_max > q_min) or is the
    # (actually padded) q tail block.  The q-padding mask is
    # load-bearing here — padded q rows carry garbage lse/delta and
    # would otherwise pollute the dk/dv sums — so the tail condition
    # uses jq, not kb.  Padded *k* rows only produce garbage in dk/dv
    # rows that the caller slices off, so kv padding needs no condition
    # in this kernel.
    conds = []
    if cfg.causal:
        conds.append(kb * block_k + (block_k - 1) > jq * block_q)
    if cfg.q_len < num_q * block_q:                         # q padding
        conds.append(jq == num_q - 1)
    _mask_specialized(jq >= first_jq, conds, has_segs, _body)

    @pl.when(jq == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(
    *refs, cfg: _FAConfig, num_k: int, has_bias, has_segs, has_dropout,
):
    (q_ref, k_ref, v_ref), rest = refs[:3], refs[3:]
    bias_ref = qseg_ref = kseg_ref = seed_ref = None
    if has_bias:
        bias_ref, rest = rest[0], rest[1:]
    if has_segs:
        (qseg_ref, kseg_ref), rest = rest[:2], rest[2:]
    if has_dropout:
        seed_ref, rest = rest[0], rest[1:]
    if has_bias and cfg.bias_grad:
        do_ref, lse_ref, delta_ref, dq_ref, dbias_ref, dq_acc = rest
    else:
        do_ref, lse_ref, delta_ref, dq_ref, dq_acc = rest
        dbias_ref = None

    i, j, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block_q, block_k = cfg.block_q, cfg.block_k
    if cfg.causal:
        last_kb = jnp.minimum(num_k - 1, ((j + 1) * block_q - 1) // block_k)
    else:
        last_kb = num_k - 1

    @pl.when(kb == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    # with a bias gradient every block must be written, so the causal
    # block-skip optimization only applies when dbias is not emitted
    # (masking keeps the skipped blocks' contributions at exactly zero
    # either way)
    emit_dbias = dbias_ref is not None
    run = (kb <= last_kb) if not emit_dbias else (kb <= num_k - 1)

    def _body(masked):
        qblk = q_ref[0].astype(jnp.float32)
        kblk = k_ref[0].astype(jnp.float32)
        vblk = v_ref[0].astype(jnp.float32)
        doblk = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            qblk, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(cfg),
        ) * cfg.sm_scale
        if has_bias:
            s = s + bias_ref[0].astype(jnp.float32)
        if masked or has_dropout:
            q_global = j * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            k_global = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
        p = jnp.exp(s - lse)
        if masked:
            mask = k_global < cfg.kv_len
            if cfg.causal:
                mask = jnp.logical_and(mask, k_global <= q_global)
            if has_segs:
                mask = jnp.logical_and(
                    mask, qseg_ref[0, 0][:, None] == kseg_ref[0, 0][None, :]
                )
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            doblk, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(cfg),
        )
        if has_dropout:
            keep = _keep_mask(
                seed_ref[0, 0], i, q_global, k_global,
                jnp.uint32(_keep_threshold(cfg.dropout_rate)),
            )
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - cfg.dropout_rate))
        dz = p * (dp - delta)
        if emit_dbias:
            dbias_ref[0] = dz.astype(dbias_ref.dtype)
        dq_acc[...] += jax.lax.dot_general(
            dz * cfg.sm_scale, kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(cfg),
        )

    # The emit_dbias path runs above-diagonal blocks too, where the mask
    # is what zeroes dz — those blocks stay on the masked path via the
    # diagonal condition (their k exceeds q).
    conds = []
    if cfg.causal:
        conds.append(kb * block_k + (block_k - 1) > j * block_q)
    if cfg.kv_len < num_k * block_k:                        # kv padding
        conds.append(kb == num_k - 1)
    _mask_specialized(run, conds, has_segs, _body)

    write_kb = (num_k - 1) if emit_dbias else last_kb

    @pl.when(kb == write_kb)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fa_bwd_pallas(q, k, v, bias, qseg, kseg, seed, out, lse, do,
                   cfg: _FAConfig):
    bh, psq, d = q.shape
    psk = k.shape[1]
    num_q, num_k = psq // cfg.block_q, psk // cfg.block_k
    # mask specialization assumes padding is confined to the final block
    assert psk - cfg.kv_len < cfg.block_k and psq - cfg.q_len < cfg.block_q
    has_bias = bias is not None
    has_segs = qseg is not None
    has_dropout = cfg.dropout_rate > 0.0
    # delta = rowsum(do * o) — cheap, XLA fuses it
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )
    lse3 = lse[:, None, :]
    delta3 = delta[:, None, :]

    common = [q, k, v]
    if has_bias:
        common.append(bias)
    if has_segs:
        common.extend([qseg, kseg])
    if has_dropout:
        common.append(seed)

    def dkv_specs():
        specs = _fwd_in_specs(cfg, d, psq, psk, has_bias, has_segs,
                              has_dropout, swap_grid=True)
        specs.extend([
            pl.BlockSpec((1, cfg.block_q, d), lambda i, kb, jq: (i, jq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, cfg.block_q), lambda i, kb, jq: (i, 0, jq)),
            pl.BlockSpec((1, 1, cfg.block_q), lambda i, kb, jq: (i, 0, jq)),
        ])
        return specs

    dk, dv = pl.pallas_call(
        functools.partial(
            _fa_bwd_dkv_kernel, cfg=cfg, num_q=num_q, has_bias=has_bias,
            has_segs=has_segs, has_dropout=has_dropout,
        ),
        grid=(bh, num_k, num_q),
        in_specs=dkv_specs(),
        out_specs=[
            pl.BlockSpec((1, cfg.block_k, d), lambda i, kb, jq: (i, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cfg.block_k, d), lambda i, kb, jq: (i, kb, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            shape_struct((bh, psk, d), k.dtype, q, k, v, do),
            shape_struct((bh, psk, d), v.dtype, q, k, v, do),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.block_k, d), jnp.float32),
            pltpu.VMEM((cfg.block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name=kernel_name("fmha_flash.bwd_dkv"),
    )(*common, do, lse3, delta3)

    emit_dbias = has_bias and cfg.bias_grad
    dq_out_specs = [
        pl.BlockSpec((1, cfg.block_q, d), lambda i, j, kb: (i, j, 0),
                     memory_space=pltpu.VMEM),
    ]
    dq_out_shape = [shape_struct((bh, psq, d), q.dtype, q, k, v, do)]
    if emit_dbias:
        dq_out_specs.append(
            pl.BlockSpec((1, cfg.block_q, cfg.block_k),
                         lambda i, j, kb: (i, j, kb),
                         memory_space=pltpu.VMEM)
        )
        dq_out_shape.append(
            shape_struct((bh, psq, psk), jnp.float32, q, k, v, do)
        )

    dq_specs = _fwd_in_specs(cfg, d, psq, psk, has_bias, has_segs,
                             has_dropout)
    dq_specs.extend([
        pl.BlockSpec((1, cfg.block_q, d), lambda i, j, kb: (i, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, cfg.block_q), lambda i, j, kb: (i, 0, j)),
        pl.BlockSpec((1, 1, cfg.block_q), lambda i, j, kb: (i, 0, j)),
    ])
    res = pl.pallas_call(
        functools.partial(
            _fa_bwd_dq_kernel, cfg=cfg, num_k=num_k, has_bias=has_bias,
            has_segs=has_segs, has_dropout=has_dropout,
        ),
        grid=(bh, num_q, num_k),
        in_specs=dq_specs,
        out_specs=dq_out_specs if emit_dbias else dq_out_specs[0],
        out_shape=dq_out_shape if emit_dbias else dq_out_shape[0],
        compiler_params=_compiler_params(),
        scratch_shapes=[pltpu.VMEM((cfg.block_q, d), jnp.float32)],
        interpret=_interpret(),
        name=kernel_name("fmha_flash.bwd_dq"),
    )(*common, do, lse3, delta3)
    if emit_dbias:
        dq, dbias = res
    else:
        dq, dbias = res, None
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# custom_vjp wrapper (flattened (b*h, s, d) layout)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _flash(q, k, v, bias, qseg, kseg, seed, cfg):
    out, _ = _fa_fwd_pallas(q, k, v, bias, qseg, kseg, seed, cfg)
    return out


def _flash_fwd(q, k, v, bias, qseg, kseg, seed, cfg):
    out, lse = name_attention_residuals(
        *_fa_fwd_pallas(q, k, v, bias, qseg, kseg, seed, cfg))
    return out, (q, k, v, bias, qseg, kseg, seed, out, lse)


def _int_zero(x):
    return (
        None if x is None
        else np.zeros(x.shape, jax.dtypes.float0)
    )


def _flash_bwd(cfg, res, do):
    q, k, v, bias, qseg, kseg, seed, out, lse = res
    dq, dk, dv, dbias = _fa_bwd_pallas(
        q, k, v, bias, qseg, kseg, seed, out, lse, do, cfg
    )
    if bias is not None and not cfg.bias_grad:
        # constant-mask contract: caller declared the bias non-trainable
        dbias = jnp.zeros_like(bias)
    elif bias is not None:
        # the kernel emits per-(b*h) score grads; fold back to the
        # flattened-bias batching the primal used
        bh, psq, psk = dbias.shape
        if cfg.bias_batch == 1:
            dbias = jnp.sum(dbias, axis=0, keepdims=True)
        elif cfg.bias_batch == BIAS_PER_BATCH:
            dbias = dbias.reshape(
                bh // cfg.heads, cfg.heads, psq, psk
            ).sum(axis=1)
        dbias = dbias.astype(bias.dtype)
    return (dq, dk, dv, dbias, _int_zero(qseg), _int_zero(kseg),
            _int_zero(seed))


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash_positioned(q, k, v, bounds, cfg):
    return _fa_fwd_pallas(q, k, v, None, None, None, None, cfg, bounds)[0]


def _no_positioned_grad(*_):
    raise NotImplementedError(
        "flash_attention with q_offset / q_period / window is forward-only: "
        "the backward kernels have not learned the positions (prefill "
        "chunks do not differentiate; a training call gives none of them)")


_flash_positioned.defvjp(_no_positioned_grad, _no_positioned_grad)
# a program's layers make this call at the same shapes: as a jitted
# function it is traced and lowered ONCE a program and called from each
# (ROADMAP S10: a kernel costs set-up what is lowered for it)
_flash_positioned_once = jax.jit(_flash_positioned, static_argnums=(4,))


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------


def _pad_seq(x, pad, axis=1):
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(
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
    implementation: Optional[str] = None,
    q_offset=None,
    q_period: Optional[int] = None,
    window: int = 0,
) -> jnp.ndarray:
    """Flash attention over ``(batch, heads, seq, head_dim)``.

    ``implementation`` is ``"pallas"`` (the streamed flash kernel),
    ``"xla"`` (reference path, also the CPU fallback), ``"short"`` (the
    single-pass short-sequence kernel family in
    ``ops/attention_short.py`` — the analog of the reference's
    per-seqlen {128,256,384,512} fmha kernels), or ``"mid"`` (the
    pipelined mid-sequence kernel in ``ops/attention_mid.py``: smaller
    streamed k-blocks + batch*head packing + causal block-skipping for
    the 512 < s <= ~2048 band), or ``"decode"`` (the fourth rung,
    ``ops/attention_decode.py``: tiny-q generation attention against a
    long cache — explicit-only, forward-only, no bias/segments/dropout;
    serving callers with a paged cache call ``fmha_decode`` directly);
    default picks by platform and the
    measured three-tier dispatch ladder short → mid → flash
    (crossovers ``FMHA_SHORT_MAX_SEQ`` / ``FMHA_MID_MAX_SEQ``,
    env-overridable — see ``docs/attention.md``).
    ``block_q``/``block_k`` only apply to the flash kernel (the short
    kernel holds the whole sequence and blocks the batch*heads
    dimension instead; the mid kernel sizes its own blocks).

    ``bias`` is an additive score bias broadcastable from
    ``(1|b, 1|h, sq, sk)``; it is differentiable by default (the backward
    pass then materialises per-head score-grad blocks, so prefer
    ``segment_ids`` over huge bias masks for long-sequence varlen).
    Pass ``bias_requires_grad=False`` for constant masks: the bias
    cotangent is then hard zero and the backward keeps the pure
    flash-attention memory profile.
    ``q_segment_ids``/``kv_segment_ids`` are ``(b, sq)``/``(b, sk)``
    int32 tokens-attend-within-equal-id masks — the TPU-native varlen
    API (reference: cu_seqlens, apex/contrib/fmha/fmha.py:33-80).
    ``dropout_rate``/``dropout_seed`` apply probability dropout inside
    the kernel with a counter-based hash (reference: philox.h) that the
    backward pass replays exactly; the same seed on the XLA path draws
    the identical mask.

    Default block sizes (``block_q`` / ``block_k`` None) come from the
    on-chip sweep in KERNELS_TPU.json (v5e: 1024x1024 is fastest,
    512x1024 is within ~5% with more VMEM headroom for the bias/dropout
    variants, so that is the default) — and 1024x1024 for a call with
    positions, which has neither operand (6-16 % faster at the prefill
    chunks' shapes, docs/attention.md); both are clamped to the sequence
    lengths.

    **Positions** (``causal=True`` only; forward only; no bias, segment
    ids or dropout beside them).  Without them a causal call's row ``r``
    sees key columns ``<= r``.  ``q_offset`` (an int ``>= 0``, or a
    TRACED int32 scalar: one executable for every offset) says the rows
    sit further along the keys, ``q_period`` (static, default the query
    length; a multiple of 8) that they repeat, ``window`` (static, 0 =
    none) that they see only so far back: row ``r`` sits at key column
    ``t = q_offset + (r mod q_period)`` and sees column ``c`` iff ``c <=
    t`` and ``t - c < window``.  That is what a prefill chunk at
    ``start`` against its cached context is (``q_offset=start``), and
    what a grouped-query chunk whose query heads ride as further rows of
    their K/V head is (``q_period`` = the chunk).  The kernel runs no
    body for a key block that no row of its q block sees, fetches
    nothing for such a step and masks only the blocks an edge cuts
    (:func:`k_block_bounds`; :func:`k_blocks_run` counts them); no
    ``(sq, sk)`` mask exists anywhere.  A call with positions takes this
    kernel (or XLA) at every length: the short and mid kernels have not
    learned them.
    """
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("segment ids must be given for both q and kv")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    positioned = _positioned(q_offset, q_period, window)
    if positioned:
        if not causal or bias is not None or q_segment_ids is not None \
                or dropout_rate > 0.0:
            raise ValueError(
                "q_offset / q_period / window place the rows of a "
                "causal=True call, and go with no bias, segment ids or "
                "dropout")
        if implementation in ("short", "mid", "decode"):
            raise ValueError(
                f"implementation={implementation!r} takes no q_offset / "
                "q_period / window: only the flash kernel ('pallas') and "
                "'xla' know a row's position")
        if q_period is not None and q.shape[2] % q_period:
            raise ValueError(
                f"q_period {q_period} does not divide the {q.shape[2]} "
                "query rows")
    if bias is not None and bias.ndim < 4:
        bias = bias.reshape((1,) * (4 - bias.ndim) + bias.shape)
    from apex_tpu.ops.common import run_kernel

    def _short_path(forced: bool):
        from apex_tpu.ops.attention_short import fmha_short

        return fmha_short(
            q, k, v, causal=causal, sm_scale=sm_scale, bias=bias,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            bias_requires_grad=bias_requires_grad,
            implementation="pallas" if forced else None,
        )

    def _mid_path(forced: bool):
        from apex_tpu.ops.attention_mid import fmha_mid

        return fmha_mid(
            q, k, v, causal=causal, sm_scale=sm_scale, bias=bias,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            bias_requires_grad=bias_requires_grad,
            implementation="pallas" if forced else None,
        )

    if implementation == "decode":
        # the fourth rung (ops/attention_decode.py): tiny-q against a
        # long cache, here over contiguous K/V viewed as trivially-paged
        # storage.  Decode callers hold no trainable bias/segments and
        # never differentiate through the cache, so the rung is
        # explicit-only — the training ladder's measured crossovers
        # stay untouched.  Serving callers with a real page table call
        # fmha_decode directly.
        if (bias is not None or q_segment_ids is not None
                or dropout_rate > 0.0):
            raise ValueError(
                "implementation='decode' supports plain (optionally "
                "causal) attention only — no bias/segments/dropout"
            )
        from apex_tpu.ops.attention_decode import decode_contiguous

        return decode_contiguous(q, k, v, causal=causal, sm_scale=sm_scale)
    if implementation == "short":
        return _short_path(forced=True)
    if implementation == "mid":
        return _mid_path(forced=True)
    impl = implementation or default_implementation()
    if (
        implementation is None
        and impl == "pallas"
        and q.dtype == jnp.float32
        and q.shape[2] <= FLASH_FP32_XLA_MAX_SEQ
    ):
        # measured dispatch window (KERNELS_TPU.json, fp32 entries):
        # fp32 inputs run the kernel dots at Precision.HIGHEST for
        # parity, which loses to XLA at s=1024 (0.8x fwd) and wins by
        # s=4096 (>2x fwd, growing with s); the boundary is the largest
        # measured losing shape.  Auto mode routes accordingly — the
        # analog of the reference's kernel-availability windows
        # (apex/transformer/functional/fused_softmax.py:151-171)
        impl = "xla"
    if implementation is None and impl == "pallas" and not positioned:
        from apex_tpu.ops.attention_short import short_seq_threshold

        thr = short_seq_threshold()
        if q.shape[2] <= thr and k.shape[2] <= thr:
            # short-sequence window: the whole kv fits one k-block, so
            # the single-pass fmha-short kernel drops the online-softmax
            # machinery and packs (batch*heads) programs per grid step
            # (crossover constant FMHA_SHORT_MAX_SEQ, recorded/gated by
            # tools/kernel_validation.py).  Note ordering: the fp32→XLA
            # window above fires first, so fp32 short sequences keep
            # their measured XLA routing until a capture says otherwise.
            return _short_path(forced=False)
        from apex_tpu.ops.attention_mid import mid_seq_threshold

        mthr = mid_seq_threshold()
        if max(q.shape[2], k.shape[2]) <= mthr:
            # mid-sequence window (short crossover < s <= mid
            # crossover): the flash kernel's measured-optimal
            # 1024x1024 blocks degenerate to <= 2 k-blocks here — no
            # software pipelining, no causal block-skip (PROFILE_r05:
            # 10.2 TF/s at s=1024 causal vs ~50 at s>=4096) — so the
            # pipelined mid kernel streams smaller k-blocks with
            # batch*head packing instead (crossover constant
            # FMHA_MID_MAX_SEQ, recorded/gated by kernel_validation;
            # APEX_TPU_FMHA_MID_MAX_SEQ=0 pins this window back to
            # the flash kernel bit-identically)
            return _mid_path(forced=False)

    def _xla_path():
        return mha_reference(
            q, k, v, causal=causal, sm_scale=sm_scale, bias=bias,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            q_offset=q_offset, q_period=q_period, window=window,
        )

    def _pallas_path():
        return _flash_attention_pallas(
            q, k, v, causal, sm_scale, bias, q_segment_ids,
            kv_segment_ids, dropout_rate, dropout_seed,
            bias_requires_grad, block_q, block_k,
            q_offset, q_period, window,
        )

    return run_kernel(
        "flash_attention", _pallas_path, _xla_path, impl
    )


# fp32 block-area cap shared with the kernel-validation sweep (which
# must skip configs the wrapper would clamp, or it double-times the
# clamped program under multiple labels)
FLASH_FP32_MAX_BLOCK_AREA = 512 * 1024


def _clamp_blocks(dtype, block_q: int, block_k: int):
    """Clamp the (block_q, block_k) area for fp32 inputs.

    The backward kernels keep several (block_q, block_k) fp32 score-space
    temporaries live at once (s, p, dp, dz); at 1024x1024 fp32 blocks
    that stack reaches ~18.3 MB and exceeds Mosaic's 16 MB scoped-vmem
    limit (measured compile failure, r5 kernel sweep).  512x1024 — the
    shipped default and the area every committed fp32 sweep row was
    measured at — halves each temporary to 2 MB and compiles at every
    benchmarked shape, so fp32 requests above that area are clamped
    rather than left to fail in the compiler.  bf16 keeps the caller's
    blocks: its temporaries stay fp32 in-kernel but the sweep shows
    1024x1024 compiling and winning there (KERNELS_TPU.json).
    """
    if dtype == jnp.float32:
        while block_q * block_k > FLASH_FP32_MAX_BLOCK_AREA:
            if block_q >= block_k:
                block_q //= 2
            else:
                block_k //= 2
    return block_q, block_k


def _block_sizes(dtype, sq: int, sk: int, block_q: Optional[int],
                 block_k: Optional[int], positioned: bool = False,
                 q_period: Optional[int] = None):
    """The blocks a call runs with: the caller's or the default for its
    kind (a call with positions holds no bias block and takes the
    larger q block), clamped to the dtype and the lengths, and a q
    block inside one period of the rows."""
    if block_q is None:
        block_q = 1024 if positioned else 512
    if block_k is None:
        block_k = 1024
    block_q, block_k = _clamp_blocks(dtype, block_q, block_k)
    block_q = min(block_q, max(sq, 1))
    block_k = min(block_k, max(sk, 1))
    if q_period is not None and q_period < sq:
        block_q = math.gcd(block_q, q_period)
    return block_q, block_k


def _flash_attention_pallas(
    q, k, v, causal, sm_scale, bias, q_segment_ids, kv_segment_ids,
    dropout_rate, dropout_seed, bias_requires_grad, block_q, block_k,
    q_offset=None, q_period=None, window=0,
):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = (1.0 / d**0.5) if sm_scale is None else float(sm_scale)
    positioned = _positioned(q_offset, q_period, window)
    block_q, block_k = _block_sizes(q.dtype, sq, sk, block_q, block_k,
                                    positioned, q_period)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    # pad head_dim to the 128-lane tile; zero columns do not change
    # q@k^T, and padded output columns are sliced off
    pad_d = (-d) % _LANES
    if pad_d:
        padd = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad_d)))
        q, k, v = padd(q), padd(k), padd(v)

    flat = lambda x: x.reshape(b * h, x.shape[2], x.shape[3])
    qf = _pad_seq(flat(q), pad_q)
    kf = _pad_seq(flat(k), pad_k)
    vf = _pad_seq(flat(v), pad_k)

    bias_flat = None
    bias_batch = 0
    if bias is not None:
        bb, bs_h, bsq, bsk = bias.shape
        bias4 = jnp.broadcast_to(bias, (bb, bs_h, sq, sk))
        if bb == 1 and bs_h == 1:
            bias_flat, bias_batch = bias4.reshape(1, sq, sk), 1
        elif bs_h == 1:
            bias_flat, bias_batch = bias4.reshape(b, sq, sk), BIAS_PER_BATCH
        else:
            bias4 = jnp.broadcast_to(bias, (b, h, sq, sk))
            bias_flat = bias4.reshape(b * h, sq, sk)
            bias_batch = BIAS_PER_HEAD
        bias_flat = _pad_seq(_pad_seq(bias_flat, pad_q, axis=1), pad_k, axis=2)

    qseg = kseg = None
    if q_segment_ids is not None:
        qseg = _pad_seq(q_segment_ids.astype(jnp.int32), pad_q, axis=1)
        # padded kv positions are masked by kv_len already; pad ids with -1
        # so they also never match a real segment
        kseg = jnp.pad(
            kv_segment_ids.astype(jnp.int32), ((0, 0), (0, pad_k)),
            constant_values=-1,
        ) if pad_k else kv_segment_ids.astype(jnp.int32)
        # (b, 1, s): the singleton keeps the trailing block dims tileable
        qseg, kseg = qseg[:, None, :], kseg[:, None, :]

    seed_arr = None
    if dropout_rate > 0.0:
        seed_arr = jnp.asarray(dropout_seed, jnp.uint32).reshape(1, 1)

    cfg = _FAConfig(
        sm_scale=scale, causal=causal, dropout_rate=float(dropout_rate),
        block_q=block_q, block_k=block_k, q_len=sq, kv_len=sk, heads=h,
        bias_batch=bias_batch, bias_grad=bool(bias_requires_grad),
        hi_precision=(q.dtype == jnp.float32),
    )
    if positioned:
        first, last, lo = k_block_bounds(
            sq, sk, jnp.asarray(0 if q_offset is None else q_offset,
                                jnp.int32),
            sq + pad_q if q_period is None else q_period, window,
            block_q, block_k)
        out = _flash_positioned_once(
            qf, kf, vf, ((first,) if window else ()) + (last, lo),
            cfg._replace(positioned=True, window=int(window)))
    else:
        out = _flash(qf, kf, vf, bias_flat, qseg, kseg, seed_arr, cfg)
    if pad_q:
        out = out[:, :sq]
    out = out.reshape(b, h, sq, d + pad_d)
    if pad_d:
        out = out[..., :d]
    return out
