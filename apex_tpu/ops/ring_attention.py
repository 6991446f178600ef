"""Ring attention: exact attention over a context-parallel mesh axis.

This is new TPU-first capability beyond the reference (SURVEY.md §2.3:
"No ring-attention / Ulysses / context parallelism exists in this
snapshot" — the nearest analog is the SpatialBottleneck halo exchange,
reference: apex/contrib/bottleneck/bottleneck.py:218-385).  The sequence
dimension is sharded over the "cp" axis; K/V shards rotate around the
ring with ``ppermute`` while every rank accumulates its queries' online
softmax — after ``cp`` steps each query has attended to the full global
sequence, with per-chip memory O(S/cp) and the K/V transfer overlapping
the attention compute of the previous block (XLA's latency-hiding
scheduler handles the overlap; the ring pattern rides neighbour ICI
links by construction).

Causality uses global position ids, so rank boundaries are invisible to
the math: the result equals dense causal attention on the gathered
sequence (tested to 1e-5).

Backward falls out of autodiff through the scan: cotangents ride the
reverse ring.  ``remat=True`` recomputes each block's scores in the
backward pass instead of saving cp score matrices.

Per-shard inner attention (``attention_impl``): the default inline XLA
walk materialises (s_local, block_k) score chunks on the VPU.
``attention_impl`` routes each ring step's block attention through the
kernel dispatch family instead (``ops/attention_mid.py`` with
``return_lse=True`` — the pipelined kernel whose fused backward carries
a real lse cotangent), merging the per-block (out, lse) pairs by
log-sum-exp outside the kernel.  A ring block is globally either fully
visible (source shard strictly before this rank), exactly causal (the
diagonal shard), or fully masked (after this rank) — so causality needs
no global position plumbing into the kernel, and fully-masked shards
are SKIPPED outright (the ring-granularity analog of the kernel's
causal block-skip; the inline path computes and masks them).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.transformer.parallel_state import CONTEXT_PARALLEL_AXIS

__all__ = ["ring_attention", "ring_attention_reference"]

_NEG = -1e30


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str = CONTEXT_PARALLEL_AXIS,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    remat: bool = True,
    block_k: int = 512,
    attention_impl: Optional[str] = None,
) -> jnp.ndarray:
    """Attention over the global sequence from per-rank shards.

    ``q``, ``k``, ``v``: (batch, heads, s_local, head_dim) — the local
    contiguous shard of a sequence of length ``cp * s_local``.  Call
    inside ``shard_map`` with the sequence dim sharded over ``axis_name``.
    Returns the local shard of the attention output.

    ``block_k`` chunks the inner K walk of each ring step so peak score
    memory is (s_local × block_k), not (s_local × s_local) — the
    flash-attention trade, expressed in XLA, which keeps long-context
    shards (s_local ≫ 1k) inside VMEM-friendly working sets.

    ``attention_impl``: ``None`` keeps the inline XLA walk (bit-exact
    with previous releases).  ``"mid"``/``"short"``/``"pallas"`` run
    each ring block through the pipelined fmha-mid kernel (per-shard
    lengths sit squarely in its window) and ``"xla"`` through its
    reference path — an A/B comparator for the merge math that
    materializes (s_local, s_local) scores per ring step, so prefer
    ``None`` for production XLA runs — both via the lse-merge
    formulation, which also
    SKIPS fully-masked source shards under causal (``block_k`` is then
    unused; the kernel blocks internally).
    """
    b, h, s_local, d = q.shape
    scale = (1.0 / d**0.5) if sm_scale is None else float(sm_scale)
    if attention_impl is not None:
        return _ring_attention_merge(
            q, k, v, axis_name, causal, scale, remat, attention_impl
        )
    cp = jax.lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]
    bk = min(block_k, s_local)
    if s_local % bk:
        bk = s_local  # irregular shard: fall back to one chunk
    n_chunks = s_local // bk

    q32 = q.astype(jnp.float32) * scale
    qpos = rank * s_local + jnp.arange(s_local)

    def attend(i, k_blk, v_blk, acc, m, l):
        src = (rank - i) % cp  # whose K/V shard we currently hold

        def kchunk(carry, j):
            acc, m, l = carry
            kc = lax.dynamic_slice_in_dim(k_blk, j * bk, bk, axis=2)
            vc = lax.dynamic_slice_in_dim(v_blk, j * bk, bk, axis=2)
            kpos = src * s_local + j * bk + jnp.arange(bk)
            s = jnp.einsum(
                "bhqd,bhkd->bhqk", q32, kc.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            if causal:
                s = jnp.where(kpos[None, None, None, :] >
                              qpos[None, None, :, None], _NEG, s)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * corr + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            return (acc_new, m_new, l_new), None

        if n_chunks == 1:
            (acc, m, l), _ = kchunk((acc, m, l), 0)
        else:
            (acc, m, l), _ = lax.scan(
                kchunk, (acc, m, l), jnp.arange(n_chunks)
            )
        return acc, m, l

    attend_fn = jax.checkpoint(attend) if remat else attend

    def block(carry, i):
        k_blk, v_blk, acc, m, l = carry
        acc, m, l = attend_fn(i, k_blk, v_blk, acc, m, l)
        # rotate K/V one step around the ring
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, acc, m, l), None

    # build the accumulators from q so they carry its varying-axes type
    # (a plain zeros constant would mismatch the scan carry under
    # shard_map's vma checking)
    zero_q = q32 * 0
    acc0 = zero_q
    m0 = jnp.sum(zero_q, axis=-1, keepdims=True) + _NEG
    l0 = jnp.sum(zero_q, axis=-1, keepdims=True)
    # scan the first cp-1 blocks (each ends with a rotation), then attend
    # the final block outside the loop — a rotation there would only
    # carry K/V back to where they started, and XLA cannot DCE a
    # collective inside the loop body
    (k_last, v_last, acc, m, l), _ = lax.scan(
        block, (k, v, acc0, m0, l0), jnp.arange(cp - 1)
    )
    acc, m, l = attend_fn(cp - 1, k_last, v_last, acc, m, l)
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def _ring_attention_merge(q, k, v, axis_name, causal, scale, remat, impl):
    """Kernel-backed ring attention: per-shard (out, lse) blocks merged
    by log-sum-exp.

    Each ring step attends the local queries against one source shard's
    K/V via :func:`apex_tpu.ops.attention_mid.fmha_mid` with
    ``return_lse=True`` — globally the block is fully visible, exactly
    causal (diagonal shard, i == 0), or fully masked (skipped), so the
    kernel's own ``causal`` flag expresses the mask without global
    position plumbing.  Gradients flow through the merge weights and
    the kernel's fused backward (which consumes the real lse
    cotangent); the ring itself unrolls over the static ``cp``.
    """
    from apex_tpu.ops.attention_mid import fmha_mid

    if impl not in ("mid", "short", "pallas", "xla"):
        raise ValueError(
            f"unknown ring attention_impl {impl!r}; expected None, "
            "'mid'/'short'/'pallas', or 'xla'"
        )
    kernel_impl = "xla" if impl == "xla" else "pallas"
    cp = jax.lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    def attend(q, k_blk, v_blk, causal_blk):
        out, lse = fmha_mid(
            q, k_blk, v_blk, causal=causal_blk, sm_scale=scale,
            implementation=kernel_impl, return_lse=True,
        )
        return out.astype(jnp.float32), lse

    if remat:
        attend = jax.checkpoint(attend, static_argnums=(3,))

    def skip_block(q, k_blk, v_blk):
        # zero contribution with lse = -inf-ish; built from the real
        # operands (times zero) so both cond branches carry the same
        # mesh-varying type under shard_map's vma checking
        pad = (jnp.sum(k_blk.astype(jnp.float32))
               + jnp.sum(v_blk.astype(jnp.float32))) * 0.0
        z = q.astype(jnp.float32) * 0.0 + pad
        return z, jnp.sum(z, axis=-1) + _NEG

    acc = q.astype(jnp.float32) * 0.0                 # (b, h, s, d)
    lse_acc = jnp.sum(acc, axis=-1) + _NEG            # (b, h, s)
    k_blk, v_blk = k, v
    for i in range(cp):
        if causal and i > 0:
            # source shard is rank - i mod cp: globally before this
            # rank's rows iff rank >= i (fully visible), else after
            # (fully masked — skip the block outright)
            out_i, lse_i = lax.cond(
                rank >= i,
                lambda q, kb, vb: attend(q, kb, vb, False),
                skip_block,
                q, k_blk, v_blk,
            )
        else:
            out_i, lse_i = attend(q, k_blk, v_blk, causal and i == 0)
        m = jnp.maximum(lse_acc, lse_i)
        w_acc = jnp.exp(lse_acc - m)
        w_new = jnp.exp(lse_i - m)
        tot = w_acc + w_new
        acc = (acc * w_acc[..., None] + out_i * w_new[..., None]) \
            / tot[..., None]
        lse_acc = m + jnp.log(tot)
        if i != cp - 1:
            # rotate K/V one step around the ring; the final block's
            # rotation would only return them to their origin
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
    return acc.astype(q.dtype)


def ring_attention_reference(q, k, v, causal=False, sm_scale=None):
    """Dense single-device reference (for tests): plain attention on the
    full gathered sequence."""
    from apex_tpu.ops.attention import mha_reference

    return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)
