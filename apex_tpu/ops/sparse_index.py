"""Lightning-indexer sparse selection (DeepSeek sparse attention).

Every layer scores each query against every earlier token with a small
side network — ``index_n_heads`` heads of width ``index_head_dim``,
ReLU, per-head weights, summed — and attention then runs over the
``top_k`` best-scored tokens only:

    I[t, s] = sum_j w[t, j] * relu(q[t, j, :] . k[s, :])

(the caller folds the ``index_head_dim ** -0.5`` and ``n_heads ** -0.5``
factors into ``w``).  The index key ``k`` is one vector per token and
lives in the paged cache beside the latent.

The selection is EXACT: ``S_t`` is the true ``top_k`` of ``I[t, :]``
over the tokens ``valid`` allows, ties to the lower position — the same
set a stable descending sort gives.  Two forms of one selection:

- :func:`topk_mask` (prefill chunks: attention runs under a mask) finds
  the k-th largest score of each row by bisection on the score's bits —
  32 counting passes, no sort — and breaks ties at the threshold by a
  running count;
- :func:`topk_indices` (decode) returns the positions themselves
  through ``lax.top_k``, which orders equal values by index, and the
  k-th largest score: :func:`mask_at` turns that threshold into the
  same set as a mask, elementwise and one running count (a decode step
  whose attention walks the pages under a mask).  Which of the k
  positions are real is counted from the row's valid count, not
  gathered from ``valid``: ``lax.top_k`` puts every valid position
  ahead of every filler, provided each valid score is above -inf (as
  :func:`index_scores` gives for finite inputs).

``lax.approx_max_k`` would be a different model (recall < 1).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.telemetry.spans import phase

__all__ = ["index_scores", "topk_mask", "topk_indices", "mask_at"]

#: the finite stand-in for minus infinity in a masked softmax
NEG = -1e30


def index_scores(q: jnp.ndarray, w: jnp.ndarray, k: jnp.ndarray,
                 *, q_block: int = 256) -> jnp.ndarray:
    """``q`` (..., n, heads, d), ``w`` (..., n, heads) fp32 (all scale
    factors folded in), ``k`` (..., S, d) -> fp32 scores (..., n, S).

    The (n, heads, S) product before the head sum is the large
    temporary; more than ``q_block`` queries are walked in blocks of
    that many so it stays bounded (a 2048-token chunk against 7k keys
    would otherwise hold 3.8 GB)."""
    def block(qb, wb):
        s = jnp.einsum("...nhd,...sd->...nhs", qb, k,
                       preferred_element_type=jnp.float32)
        # + 0.0 turns a -0.0 sum into +0.0: both forms of the selection
        # then order zeros alike
        return jnp.sum(jax.nn.relu(s) * wb[..., None], axis=-2) + 0.0

    with phase("attn.index.core"):
        n = q.shape[-3]
        if q.ndim != 3 or n <= q_block or n % q_block:
            return block(q, w)
        return lax.map(
            lambda qw: block(*qw),
            (q.reshape(n // q_block, q_block, *q.shape[1:]),
             w.reshape(n // q_block, q_block, w.shape[-1])),
        ).reshape(n, k.shape[-2])


def _ordered_bits(scores: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """fp32 scores -> uint32 keys with the same order; 0 where not
    ``valid`` (below every real score's key)."""
    bits = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    u = lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)
    return jnp.where(valid, u, jnp.uint32(0))


def topk_mask(scores: jnp.ndarray, k: int, valid: jnp.ndarray
              ) -> jnp.ndarray:
    """Boolean (rows, S): each row's ``k`` largest ``scores`` among the
    ``valid`` entries (all of them where fewer than ``k`` are valid),
    ties to the lower position."""
    with phase("attn.select"):
        u = _ordered_bits(scores, valid)

        def bit(i, tau):
            cand = tau | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
                jnp.uint32)))
            enough = jnp.sum(u >= cand[:, None], axis=-1) >= k
            return jnp.where(enough, cand, tau)

        # the largest tau with at least k keys >= tau: the k-th largest
        # key (0 where the row has fewer than k valid entries)
        tau = lax.fori_loop(0, 32, bit,
                            jnp.zeros(u.shape[:-1], jnp.uint32))[:, None]
        return mask_at(u, tau, k, valid)


def mask_at(keys: jnp.ndarray, tau: jnp.ndarray, k: int, valid: jnp.ndarray
            ) -> jnp.ndarray:
    """Boolean (rows, S): every ``valid`` key above the row's ``tau``
    (rows, 1), then keys EQUAL to it, lower positions first, until ``k``
    are marked.  With ``tau`` the k-th largest key of the row this is the
    row's top ``k``, ties to the lower position."""
    above, at = keys > tau, keys == tau
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    first = jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= room
    return (above | (at & first)) & valid


def topk_indices(scores: jnp.ndarray, k: int, valid: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(positions (rows, k) int32, chosen (rows, k) bool, the k-th
    largest of ``where(valid, scores, -inf)`` (rows, 1)): the same set
    :func:`topk_mask` marks, as positions; ``chosen`` is false on the
    filler entries of a row with fewer than ``k`` valid tokens, whose
    k-th largest is -inf.  ``mask_at(where(valid, scores, -inf), <the
    third>, k, valid)`` marks the positions the first two give.

    Every valid score must be above -inf.  The valid positions then
    come first in ``lax.top_k``'s order, so ``chosen`` is counted — the
    first ``sum(valid)`` entries of the row — not gathered."""
    with phase("attn.select"):
        k = min(k, scores.shape[-1])
        top, idx = lax.top_k(jnp.where(valid, scores, -jnp.inf), k)
        chosen = jnp.arange(k)[None] < jnp.sum(valid, -1, keepdims=True)
        return idx.astype(jnp.int32), chosen, top[:, k - 1:]
