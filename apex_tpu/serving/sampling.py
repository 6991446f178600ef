"""Fused on-device token sampling: greedy / temperature / top-k / top-p.

The per-token host round-trip is the decode-loop analog of the per-step
``float(loss)`` sync PR 6 removed from the trainers: sampling on the
host would serialize every generated token behind a device→host→device
bounce.  Everything here is pure ``jnp`` running INSIDE the jitted
decode step — the sampled ids stay on device, feed the next step's
embedding lookup directly, and reach the host only at the serving
driver's harvest cadence (``serve.py``), a batched transfer amortized
over the whole window.

The chain is one fused elementwise pass over the logits (the
operation-fusion discipline again — no intermediate materializes):
temperature scale → top-k floor → top-p (nucleus) floor → Gumbel-max
draw.  ``temperature=0`` short-circuits to pure argmax, and the greedy
path is BIT-identical to ``jnp.argmax`` (tests/test_serving.py pins it
— the ``_dryrun_decode`` greedy-parity gate depends on that).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["greedy", "sample", "advance_slots", "spec_accept",
           "spec_accept_tree"]

_NEG_INF = -1e30


def greedy(logits: jnp.ndarray) -> jnp.ndarray:
    """Argmax over the last axis, int32.  THE greedy definition — the
    sampling chain below routes ``temperature=0`` here, so "greedy
    sampling" and "argmax" cannot drift apart."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _top_k_floor(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """Mask everything below the k-th largest logit.  Ties AT the
    threshold all survive (the draw then splits them) — cheaper than a
    strict-k tie-break and distributionally identical for continuous
    logits."""
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits >= kth, logits, _NEG_INF)


def _top_p_floor(logits: jnp.ndarray, p: float) -> jnp.ndarray:
    """Nucleus floor: keep the smallest prefix of the
    descending-probability ordering whose mass reaches ``p`` (the
    crossing token included, so at least the argmax always survives)."""
    sorted_logits = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    probs = jax.nn.softmax(sorted_logits.astype(jnp.float32), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < p
    thresh = jnp.min(
        jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
    ).astype(logits.dtype)
    return jnp.where(logits >= thresh, logits, _NEG_INF)


def sample(
    logits: jnp.ndarray,
    key: Optional[jnp.ndarray] = None,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> jnp.ndarray:
    """One token id per row of ``logits (..., vocab)``, int32, on
    device.

    ``temperature=0`` (the default) is greedy and ignores
    ``key``/``top_k``/``top_p``.  Otherwise logits are scaled by
    ``1/temperature``, floored by ``top_k`` and/or ``top_p``, and drawn
    by Gumbel-max (``argmax(logits + G)`` — one fused pass, no explicit
    softmax or cumulative inversion on the hot path).
    """
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not (0.0 < top_p <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if temperature == 0.0:
        return greedy(logits)
    if key is None:
        raise ValueError("temperature > 0 requires a PRNG key")
    x = logits.astype(jnp.float32) / temperature
    if top_k is not None and top_k < logits.shape[-1]:
        x = _top_k_floor(x, int(top_k))
    if top_p is not None and top_p < 1.0:
        x = _top_p_floor(x, float(top_p))
    g = jax.random.gumbel(key, x.shape, jnp.float32)
    # floored entries sit at -1e30; a Gumbel draw cannot bridge that
    return jnp.argmax(x + g, axis=-1).astype(jnp.int32)


def advance_slots(carry, logits: jnp.ndarray, active: jnp.ndarray, *,
                  temperature: float = 0.0, top_k: Optional[int] = None,
                  top_p: Optional[float] = None,
                  eos_id: Optional[int] = None):
    """The tail of EVERY model's one-token decode step: sample a token
    for each live slot from ``logits (slots, vocab)`` and advance the
    batcher's five per-slot carry entries (``tokens``, ``lengths``,
    ``steps_left``, ``done``, ``sample_keys``; returned as a dict of
    just those).  ``active`` is ``~carry["done"]``, which the step has
    already (it masked its cache writes with it); a slot that is not
    active is frozen: token, length and budget unchanged.  Sampled slots draw from their own key with
    the context length AFTER this token folded in — the key schedule the
    prefill steps share, so a seeded request samples the same stream in
    any slot at any admission order."""
    if temperature == 0.0:
        sampled = sample(logits, None, 0.0)
    else:
        ctx = jnp.where(active, carry["lengths"] + 1, 0)
        subs = jax.vmap(jax.random.fold_in)(carry["sample_keys"], ctx)
        sampled = jax.vmap(
            lambda l, k: sample(l[None], k, temperature, top_k, top_p)[0]
        )(logits, subs)
    ai = active.astype(jnp.int32)
    tokens = jnp.where(active, sampled, carry["tokens"])
    steps_left = carry["steps_left"] - ai
    eos_hit = ((tokens == eos_id) if eos_id is not None
               else jnp.zeros_like(active))
    done = carry["done"] | (active & (eos_hit | (steps_left <= 0)))
    return {
        "tokens": tokens,
        "lengths": carry["lengths"] + ai,
        "steps_left": steps_left,
        "done": done,
        "sample_keys": carry["sample_keys"],
    }


def spec_accept(
    logits: jnp.ndarray,
    drafts: jnp.ndarray,
    draft_len: jnp.ndarray,
    keys: Optional[jnp.ndarray],
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
):
    """Fused speculative accept/commit for ONE slot's verify step.

    ``logits (R, vocab)`` are the verify step's R = k+1 rows (row j
    predicts the token after j committed drafts), ``drafts (R-1,)`` the
    proposed tokens, ``draft_len ()`` how many are real, and
    ``keys (R, ...)`` the per-row PRNG keys — the slot key folded with
    the row's ABSOLUTE context length, i.e. exactly the key the plain
    one-token decode loop would use for that position.  Returns
    ``(targets (R,) int32, n_accept () int32)``: the per-row target
    draws and the length of the accepted draft prefix.  The caller
    commits ``targets[:n_accept + 1]`` — the accepted drafts plus one
    bonus/correction token, all from a single weight stream.

    **Why this is distribution-preserving.**  The textbook rule
    (accept draft d_j w.p. ``min(1, p(d_j)/q(d_j))``, else resample the
    residual ``max(p − q, 0)``) preserves the target distribution p for
    ANY draft distribution q.  Here the draft is a deterministic
    function of the committed context (n-gram lookup: q is a point
    mass at d_j), and we couple the accept/reject coin and the residual
    resample to the SAME Gumbel draw the plain sampler would make:
    ``targets[j] = argmax(x_j + G_j)`` with ``G_j`` keyed by absolute
    position.  Row j commits the draft iff ``d_j == targets[j]`` — for
    a point-mass q that IS ``min(1, p/q)`` acceptance (the event has
    probability p(d_j)), and on rejection the committed correction
    ``targets[j]`` is distributed as p restricted to ≠ d_j... which is
    the residual ``max(p − q, 0)`` renormalized.  So acceptance is
    distribution-preserving AND the committed stream is token-identical
    to the plain sampler under the same key schedule (each committed
    position's token is ``argmax(x + G)`` for the same x and same G in
    both paths) — which is what keeps fleet failover migration and the
    cross-replica determinism contract exact under variable-length
    advances, and makes the dryrun's sampled-equality gate a bitwise
    comparison instead of a statistical test.

    ``temperature=0`` reduces to exact greedy prefix match: accept
    while the draft equals the argmax, then commit the argmax row.
    Temperature / top-k / top-p all apply per row BEFORE the draw, so
    their semantics survive speculation unchanged.
    """
    if logits.ndim != 2:
        raise ValueError(f"logits must be (rows, vocab), got {logits.shape}")
    rows = logits.shape[0]
    if drafts.shape != (rows - 1,):
        raise ValueError(
            f"drafts must be ({rows - 1},) for {rows} logit rows, got "
            f"{drafts.shape}")
    if temperature == 0.0:
        targets = greedy(logits)
    else:
        if keys is None:
            raise ValueError("temperature > 0 requires per-row PRNG keys")
        targets = jax.vmap(
            lambda l, kk: sample(l[None], kk, temperature, top_k, top_p)[0]
        )(logits, keys)
    j = jnp.arange(rows - 1, dtype=jnp.int32)
    match = (drafts.astype(jnp.int32) == targets[:-1]) & (j < draft_len)
    # longest accepted PREFIX: one mismatch rejects everything after it
    n_accept = jnp.sum(jnp.cumprod(match.astype(jnp.int32)))
    return targets, n_accept.astype(jnp.int32)


def spec_accept_tree(
    logits: jnp.ndarray,
    drafts: jnp.ndarray,
    parents: tuple,
    valid: jnp.ndarray,
    keys: Optional[jnp.ndarray],
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
):
    """Coupled accept/commit over a candidate TREE for one slot.

    ``logits (R, vocab)`` are the verify step's rows for R tree nodes
    in topological order — node 0 is the last committed token (the
    root), node ``r >= 1`` carries draft token ``drafts[r-1]`` and
    hangs off ``parents[r] < r`` (``parents`` is STATIC: tree shape is
    part of the jit signature, contents are not).  ``valid (R-1,)``
    masks which draft nodes are real this step (depth within the
    drafted length, physical cache room).  ``keys (R, ...)`` are the
    per-node PRNG keys folded at each node's ABSOLUTE token position
    ``ctx = lengths + 1 + depth(node)`` — depth-keyed, so every node at
    one depth shares the exact key the plain one-token schedule would
    use for that position.

    Returns ``(out (R,) int32, n_accept () int32, path (R,) int32)``:
    ``out[t]`` is the token committed at new-position ``t``,
    ``n_accept`` the depth of the deepest accepted node, ``path[t]``
    the row index of the committed node at depth ``t`` (the caller
    commits ``out[:n_accept + 1]`` and rewrites accepted rows' K/V from
    their physical slots to their depth positions).

    **Why the tree stays distribution-preserving and token-identical.**
    Each node ``p`` gets ONE target draw ``targets[p] = argmax(x_p +
    G)`` with ``G`` keyed by the absolute position of ``p``'s children
    — the same draw the plain sampler would make after committing the
    path to ``p``.  A child ``r`` is accepted iff ``drafts[r-1] ==
    targets[parents[r]]``: siblings are point-mass draft candidates
    tested against that single shared draw, so at most one DISTINCT
    sibling token can match (equal-token siblings resolve
    first-in-row-order — they commit the same token either way), and
    the committed root-to-leaf path is exactly the chain the plain
    schedule would have produced, just discovered k-at-a-time.  On
    rejection the bonus ``targets[last path node]`` IS the plain
    sampler's token for that position.  A chain-shaped ``parents``
    reduces this to :func:`spec_accept` bit-for-bit.
    """
    if logits.ndim != 2:
        raise ValueError(f"logits must be (rows, vocab), got {logits.shape}")
    rows = logits.shape[0]
    parents = tuple(int(p) for p in parents)
    if len(parents) != rows:
        raise ValueError(
            f"parents must have {rows} entries (one per logit row), got "
            f"{len(parents)}")
    if parents[0] != -1:
        raise ValueError(f"parents[0] must be -1 (root), got {parents[0]}")
    for r in range(1, rows):
        if not 0 <= parents[r] < r:
            raise ValueError(
                f"parents[{r}] = {parents[r]} must be in [0, {r}) — "
                "topological order")
    if drafts.shape != (rows - 1,):
        raise ValueError(
            f"drafts must be ({rows - 1},) for {rows} logit rows, got "
            f"{drafts.shape}")
    if valid.shape != (rows - 1,):
        raise ValueError(
            f"valid must be ({rows - 1},), got {valid.shape}")
    depth = [0] * rows
    for r in range(1, rows):
        depth[r] = depth[parents[r]] + 1
    if temperature == 0.0:
        targets = greedy(logits)
    else:
        if keys is None:
            raise ValueError("temperature > 0 requires per-node PRNG keys")
        targets = jax.vmap(
            lambda l, kk: sample(l[None], kk, temperature, top_k, top_p)[0]
        )(logits, keys)
    ok = jnp.concatenate(
        [jnp.ones((1,), bool), valid.astype(bool)])
    cur = jnp.zeros((), jnp.int32)
    n_acc = jnp.zeros((), jnp.int32)
    out_rows, path_rows = [], []
    # greedy root-to-leaf walk, statically unrolled per depth level (R
    # is a small speculative handful): at the current path node, the
    # first valid child whose draft equals that node's single target
    # draw extends the path; no child matching ends it — the stalled
    # node's draw is the bonus/correction token.  A stalled walk can
    # never resume: level t+1 nodes hang off depth-t parents only.
    for t in range(rows):
        path_rows.append(cur)
        out_rows.append(jnp.take(targets, cur))
        level = [r for r in range(1, rows) if depth[r] == t + 1]
        if not level:
            continue
        tgt_cur = jnp.take(targets, cur)
        found = jnp.zeros((), bool)
        nxt = cur
        for r in level:
            hit = ((~found) & ok[r]
                   & (jnp.int32(parents[r]) == cur)
                   & (drafts[r - 1].astype(jnp.int32) == tgt_cur))
            nxt = jnp.where(hit, jnp.int32(r), nxt)
            found = found | hit
        n_acc = n_acc + found.astype(jnp.int32)
        cur = jnp.where(found, nxt, cur)
    return (jnp.stack(out_rows), n_acc.astype(jnp.int32),
            jnp.stack(path_rows))
