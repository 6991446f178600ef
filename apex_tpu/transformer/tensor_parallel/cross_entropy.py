"""Vocab-parallel softmax cross-entropy.

Same math as the reference autograd function
(reference: apex/transformer/tensor_parallel/cross_entropy.py:23-103):
max-logit all-reduce → stable exp → sum-exp all-reduce → masked target
logit all-reduce → loss = log(sum_exp) − target_logit.  The backward
(softmax minus one-hot, reference :78-103) falls out of autodiff through
the psums; the max is stop-gradiented exactly as the reference treats it
as a constant.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.transformer.parallel_state import TENSOR_PARALLEL_AXIS
from apex_tpu.transformer.tensor_parallel.utils import VocabUtility

__all__ = [
    "vocab_parallel_cross_entropy",
    "vocab_parallel_cross_entropy_from_hidden",
    "lm_head_cross_entropy",
]


# one measured default for BOTH fused-CE entry points (v5e bench config:
# −1.6 ms/step at 8192, chip run before PR 1); ADVICE r3: the two
# signatures previously disagreed (8192 vs 4096)
FUSED_CE_DEFAULT_CHUNK = 8192

# fused=None auto rule: below this materialized-logits size the two-step
# path (one unchunked head einsum, logits live as a bwd residual) beats
# the chunked online-logsumexp scan — the scan serializes the head
# matmul into chunk-sized pieces and re-derives logits in the backward,
# which only pays off once the (tokens, vocab_local) fp32 residual is
# big enough to hit the HBM wall.  Measured on TPU v5 lite at the
# flagship GPT config (8192 tokens x 32768 vocab = 1.07 GB residual):
# two-step 107.4 ms/step vs fused@8192 110.1 — reproduced across two
# chip sessions before PR 1 (PROFILE_r05.json: 103.53 vs 106.07).
FUSED_CE_AUTO_BYTES = int(
    os.environ.get("APEX_TPU_FUSED_CE_BYTES", str(2 << 30))
)


def fused_ce_auto(tokens_local: int, vocab_local: int) -> bool:
    """The ``fused=None`` decision rule, exported so measurement
    harnesses predict the dispatcher's choice from the SAME arithmetic
    (shard_map-local token and vocab-shard counts) instead of
    re-deriving it from global shapes and drifting."""
    return tokens_local * vocab_local * 4 > FUSED_CE_AUTO_BYTES


def _largest_chunk_divisor(v_local: int, chunk: int) -> int:
    """Largest divisor of ``v_local`` that is <= ``chunk`` — the fused
    CE walks equal weight slices, and common vocab shards (32000/tp)
    rarely divide by a power-of-two chunk; shrinking to the nearest
    divisor (32000 → 8000) keeps the fused path engaged instead of
    silently materializing the full logits (ADVICE r3)."""
    for d in range(min(chunk, v_local), 0, -1):
        if v_local % d == 0:
            return d
    return 1


def lm_head_cross_entropy(
    hidden: jnp.ndarray,
    weight: jnp.ndarray,
    targets: jnp.ndarray,
    *,
    axis_name: str = TENSOR_PARALLEL_AXIS,
    fused: "bool | None" = None,
    chunk: int = FUSED_CE_DEFAULT_CHUNK,
    bias: "jnp.ndarray | None" = None,
    smoothing: float = 0.0,
) -> jnp.ndarray:
    """Per-token CE through a tied, vocab-sharded LM head — the one
    dispatch shared by the GPT / BERT / T5 loss paths: the fused
    chunked path (:func:`vocab_parallel_cross_entropy_from_hidden`,
    logits never materialized) when ``fused``, else explicit logits +
    :func:`vocab_parallel_cross_entropy`.

    ``fused=None`` (default) picks by the materialized-logits residual
    size against ``FUSED_CE_AUTO_BYTES``: small logits take the faster
    two-step path, large ones the memory-bounded fused scan.  All
    shapes here are the shard_map-local shard, so the rule composes
    with tp (vocab/tp local shard) and dp/cp (local token count)."""
    if fused is None:
        fused = fused_ce_auto(math.prod(hidden.shape[:-1]), weight.shape[0])
    if fused:
        return vocab_parallel_cross_entropy_from_hidden(
            hidden, weight, targets,
            axis_name=axis_name, chunk=chunk, bias=bias,
            smoothing=smoothing,
        )
    logits = jnp.einsum("...h,vh->...v", hidden, weight.astype(hidden.dtype))
    if bias is not None:
        logits = logits + bias.astype(logits.dtype)
    return vocab_parallel_cross_entropy(
        logits, targets, axis_name, smoothing=smoothing
    )


def vocab_parallel_cross_entropy(
    vocab_parallel_logits: jnp.ndarray,
    target: jnp.ndarray,
    axis_name: str = TENSOR_PARALLEL_AXIS,
    smoothing: float = 0.0,
) -> jnp.ndarray:
    """Per-token CE loss from vocab-sharded logits — call inside shard_map.

    ``vocab_parallel_logits``: (..., vocab/tp) local shard.
    ``target``: (...) int ids in the *global* vocab.
    ``smoothing``: uniform label smoothing over the global vocab
    (contrib.xentropy semantics).
    Returns (...) float32 losses.
    """
    logits = vocab_parallel_logits.astype(jnp.float32)
    world = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    per = logits.shape[-1]
    start, end = VocabUtility.vocab_range_from_per_partition_vocab_size(
        per, rank, world
    )

    # global max for stability, treated as a constant like the reference
    # (reference :31-39) — pmax has no JVP rule, so stop-gradient first
    local_max = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    global_max = jax.lax.pmax(local_max, axis_name)
    logits = logits - global_max[..., None]

    # log-sum-exp over the global vocab (reference :55-63)
    exp_logits = jnp.exp(logits)
    sum_exp = jax.lax.psum(jnp.sum(exp_logits, axis=-1), axis_name)

    # target logit: only the owning shard contributes (reference :41-53)
    in_range = (target >= start) & (target < end)
    local_target = jnp.where(in_range, target - start, 0)
    picked = jnp.take_along_axis(logits, local_target[..., None], axis=-1)[..., 0]
    picked = jnp.where(in_range, picked, 0.0)

    if smoothing > 0.0:
        # one stacked psum for target logit + logit sum (3 collectives
        # total, with or without smoothing)
        vocab_global = per * world
        target_logit, logit_sum = jax.lax.psum(
            jnp.stack([picked, jnp.sum(logits, axis=-1)]), axis_name
        )
        mean_logit = logit_sum / vocab_global
        return (
            jnp.log(sum_exp)
            - (1.0 - smoothing) * target_logit
            - smoothing * mean_logit
        )
    target_logit = jax.lax.psum(picked, axis_name)
    return jnp.log(sum_exp) - target_logit


# ---------------------------------------------------------------------------
# fused CE from hidden states (logits never materialized)
# ---------------------------------------------------------------------------


def _varying_like(arr, axis_name, *refs):
    """Mark ``arr`` varying over ``axis_name`` plus every mesh axis any of
    ``refs`` varies over — scan carries must enter with exactly the vma
    the body's output has (e.g. dp-varying hidden × tp-varying weight
    makes the running statistics (dp, tp)-varying)."""
    need = {axis_name}
    for r in refs:
        try:
            need |= set(jax.typeof(r).vma)
        except AttributeError:  # not an array type / no vma (outside shard_map)
            pass
    try:
        have = set(jax.typeof(arr).vma)
    except AttributeError:
        have = set()
    for ax in sorted(need - have):
        arr = jax.lax.pcast(arr, ax, to="varying")
    return arr


def _vocab_range(weight, axis_name):
    world = jax.lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    return VocabUtility.vocab_range_from_per_partition_vocab_size(
        weight.shape[0], rank, world
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ce_from_hidden(x, weight, bias, target, axis_name, chunk, smoothing):
    loss, _ = _ce_fwd_scan(x, weight, bias, target, axis_name, chunk,
                           smoothing)
    return loss


def _ce_fwd_scan(x, weight, bias, target, axis_name, chunk, smoothing):
    """Online log-sum-exp over vocab chunks; returns (loss, residuals)."""
    n = x.shape[0]
    num_chunks = weight.shape[0] // chunk
    start, end = _vocab_range(weight, axis_name)
    in_range = (target >= start) & (target < end)
    local_target = jnp.where(in_range, target - start, 0)

    def body(carry, c):
        m, se, tl, sl = carry
        w_c = lax.dynamic_slice_in_dim(weight, c * chunk, chunk, axis=0)
        logits_c = jnp.einsum(
            "nh,vh->nv", x, w_c.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        logits_c = logits_c + lax.dynamic_slice_in_dim(
            bias, c * chunk, chunk, axis=0
        ).astype(jnp.float32)[None, :]
        m_c = jnp.max(logits_c, axis=-1)
        m_new = jnp.maximum(m, m_c)
        se = se * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits_c - m_new[:, None]), axis=-1
        )
        idx = local_target - c * chunk
        in_chunk = (idx >= 0) & (idx < chunk)
        picked = jnp.take_along_axis(
            logits_c, jnp.clip(idx, 0, chunk - 1)[:, None], axis=-1
        )[:, 0]
        tl = jnp.where(in_chunk, picked, tl)
        if smoothing > 0.0:  # static: no dead logit-sum on the usual path
            sl = sl + jnp.sum(logits_c, axis=-1)
        return (m_new, se, tl, sl), None

    init = jax.tree.map(
        lambda a: _varying_like(a, axis_name, x, weight, target),
        (
            jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32),
        ),
    )
    (m, se, tl, sl), _ = lax.scan(body, init, jnp.arange(num_chunks))

    # identical 3-collective math to vocab_parallel_cross_entropy: the
    # max is a stop-gradient constant, sum-exp and the owning shard's
    # target logit are psum'd
    global_max = lax.pmax(lax.stop_gradient(m), axis_name)
    sum_exp = lax.psum(se * jnp.exp(m - global_max), axis_name)
    picked = jnp.where(in_range, tl - global_max, 0.0)
    if smoothing > 0.0:
        # label smoothing over the GLOBAL vocab (contrib.xentropy
        # semantics): loss = lse - (1-s)*target - s*mean(logits).
        # One stacked psum carries both the target logit and the logit
        # sum, keeping the collective count at three.
        vocab_global = weight.shape[0] * jax.lax.axis_size(axis_name)
        target_logit, sl_g = lax.psum(
            jnp.stack([picked, sl]), axis_name
        )
        mean_logit = sl_g / vocab_global - global_max
        loss = (
            jnp.log(sum_exp)
            - (1.0 - smoothing) * target_logit
            - smoothing * mean_logit
        )
    else:
        target_logit = lax.psum(picked, axis_name)
        loss = jnp.log(sum_exp) - target_logit
    residuals = (x, weight, bias, local_target, in_range, global_max,
                 sum_exp)
    return loss, residuals


def _ce_fwd(x, weight, bias, target, axis_name, chunk, smoothing):
    return _ce_fwd_scan(x, weight, bias, target, axis_name, chunk, smoothing)


def _ce_bwd(axis_name, chunk, smoothing, residuals, g):
    """dlogits = softmax − one-hot, re-derived chunk-by-chunk (logits are
    recomputed, never stored); dx accumulates across chunks, dW stacks."""
    x, weight, bias, local_target, in_range, global_max, sum_exp = residuals
    num_chunks = weight.shape[0] // chunk
    vocab_global = weight.shape[0] * jax.lax.axis_size(axis_name)
    gf = g.astype(jnp.float32)

    def body(dx, c):
        w_c = lax.dynamic_slice_in_dim(weight, c * chunk, chunk, axis=0)
        logits_c = jnp.einsum(
            "nh,vh->nv", x, w_c.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        logits_c = logits_c + lax.dynamic_slice_in_dim(
            bias, c * chunk, chunk, axis=0
        ).astype(jnp.float32)[None, :]
        p_c = jnp.exp(logits_c - global_max[:, None]) / sum_exp[:, None]
        idx = local_target - c * chunk
        in_chunk = in_range & (idx >= 0) & (idx < chunk)
        onehot = (
            jax.nn.one_hot(jnp.clip(idx, 0, chunk - 1), chunk,
                           dtype=jnp.float32)
            * in_chunk[:, None]
        )
        # d loss/d logits = softmax - (1-s)*onehot - s/V (kernel bprop
        # form, matching contrib.xentropy)
        dlogits = (
            p_c - (1.0 - smoothing) * onehot - smoothing / vocab_global
        ) * gf[:, None]
        dx = dx + jnp.einsum(
            "nv,vh->nh", dlogits.astype(x.dtype), w_c.astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        dw_c = jnp.einsum(
            "nv,nh->vh", dlogits.astype(x.dtype), x,
            preferred_element_type=jnp.float32,
        )
        db_c = jnp.sum(dlogits, axis=0)
        return dx, (dw_c, db_c)

    dx, (dw, db) = lax.scan(
        body,
        _varying_like(jnp.zeros(x.shape, jnp.float32), axis_name,
                      x, weight, g),
        jnp.arange(num_chunks),
    )
    dw = dw.reshape(weight.shape).astype(weight.dtype)
    db = db.reshape(bias.shape).astype(bias.dtype)
    # every vocab shard holds part of the softmax row: the hidden grad is
    # the sum of the per-shard contributions (the two-step path gets this
    # psum from the einsum transpose automatically)
    dx = lax.psum(dx, axis_name)
    # same story for the weight grad over the *other* mesh axes (e.g. a
    # dp-varying hidden makes dw (dp, tp)-varying; the primal weight is
    # tp-varying only, and the einsum transpose would psum over dp)
    dx = _psum_down_to(dx, x)
    dw = _psum_down_to(dw, weight)
    db = _psum_down_to(db, bias)
    return dx.astype(x.dtype), dw, db, None


def _psum_down_to(val, primal):
    """psum ``val`` over every mesh axis it varies over beyond the
    primal's vma — custom_vjp cotangents must type-match their primals."""
    try:
        extra = set(jax.typeof(val).vma) - set(jax.typeof(primal).vma)
    except AttributeError:
        return val
    for ax in sorted(extra):
        val = lax.psum(val, ax)
    return val


_ce_from_hidden.defvjp(_ce_fwd, _ce_bwd)


def vocab_parallel_cross_entropy_from_hidden(
    hidden: jnp.ndarray,
    weight: jnp.ndarray,
    target: jnp.ndarray,
    axis_name: str = TENSOR_PARALLEL_AXIS,
    chunk: int = FUSED_CE_DEFAULT_CHUNK,
    bias: "jnp.ndarray | None" = None,
    smoothing: float = 0.0,
) -> jnp.ndarray:
    """Fused LM-head + vocab-parallel CE: per-token loss straight from
    hidden states and the (tied, vocab-sharded) embedding weight, with
    the (..., vocab) logits **never materialized** in HBM.

    The fp32 logits tensor the two-step path stores is (tokens × vocab) —
    1 GB at b=8/s=1024/V=32k — and is pure bandwidth cost; here an online
    log-sum-exp walks (vocab/tp)/chunk weight slices and the backward
    re-derives each chunk's softmax from the saved (max, sum-exp) row
    statistics, the same recompute-over-store trade as flash attention
    (capability superset of the reference's fused xentropy kernel,
    apex/contrib/csrc/xentropy/ + apex/transformer/tensor_parallel/
    cross_entropy.py, which still materializes logits).

    ``hidden``: (..., h); ``weight``: (vocab/tp, h); ``target``: (...)
    global ids; optional ``bias``: (vocab/tp,) per-vocab logit bias (the
    BERT MLM head's); ``smoothing``: uniform label smoothing over the
    global vocab (contrib.xentropy semantics).  Returns (...) fp32
    losses.  When vocab/tp does not divide by ``chunk``, the chunk
    auto-shrinks to the largest divisor so the fused path stays
    engaged; only a near-prime shard (best divisor < 512) falls back to
    the two-step logits path.
    """
    lead = hidden.shape[:-1]
    h = hidden.shape[-1]
    if weight.shape[0] % chunk:
        chunk = _largest_chunk_divisor(weight.shape[0], chunk)
        if chunk < min(512, weight.shape[0]):
            # near-prime shard: the only dividing chunks are tiny and
            # the scan overhead would swamp the fusion win.  An
            # explicitly-passed small chunk that DIVIDES is honored —
            # the fallback only fires when the auto-shrink degraded it.
            logits = jnp.einsum(
                "...h,vh->...v", hidden, weight.astype(hidden.dtype)
            )
            if bias is not None:
                logits = logits + bias.astype(logits.dtype)
            return vocab_parallel_cross_entropy(
                logits, target, axis_name, smoothing=smoothing
            )
    if bias is None:
        bias = jnp.zeros((weight.shape[0],), jnp.float32)
    x = hidden.reshape(-1, h)
    t = target.reshape(-1)
    return _ce_from_hidden(
        x, weight, bias, t, axis_name, chunk, float(smoothing)
    ).reshape(lead)
