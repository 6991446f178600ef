"""Pipeline-parallel schedules, compiled.

The reference drives 1F1B with an imperative Python loop of per-microbatch
isend/irecv and ``torch.autograd.backward`` calls
(reference: apex/transformer/pipeline_parallel/schedules/
fwd_bwd_pipelining_without_interleaving.py:22-170).  That host-driven
schedule is the single biggest design divergence for TPU (SURVEY.md §7):
under XLA the whole pipeline must be ONE compiled program.

Design here: the *forward* pipeline is a ``lax.scan`` over
``num_microbatches + pp - 1`` ticks inside ``shard_map``; each tick every
stage applies its stage function and the activations rotate one stage
forward with ``ppermute``.  Differentiating the scanned program yields the
reverse pipeline automatically — ``ppermute``'s transpose is the opposite
rotation — so backward needs no schedule code at all.  Memory behaves
like GPipe (all microbatch activations live until backward); wrapping the
stage function in ``jax.checkpoint`` (``remat=True``) recovers the
1F1B-like activation footprint by keeping only per-tick stage inputs and
recomputing the rest, which is the standard TPU trade (FLOPs are cheaper
than HBM).

The user-facing surface mirrors the reference:
- :func:`forward_backward_no_pipelining`    (fwd_bwd_no_pipelining.py:29-91)
- :func:`forward_backward_pipelining_without_interleaving`
- :func:`get_forward_backward_func`         (schedules/__init__.py:1-39)

but each returns a **loss function** to differentiate, because on TPU
"forward+backward" is ``jax.grad`` of the compiled loss, not a schedule.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.transformer.parallel_state import PIPELINE_PARALLEL_AXIS
from apex_tpu.transformer.pipeline_parallel.p2p_communication import (
    send_forward,
    send_forward_recv_backward,
)

__all__ = [
    "pipeline",
    "pipeline_1f1b",
    "pipeline_1f1b_interleaved",
    "pipeline_encdec",
    "pipeline_encdec_fused",
    "pipeline_encdec_fused_1f1b",
    "forward_backward_no_pipelining",
    "forward_backward_pipelining_without_interleaving",
    "forward_backward_pipelining_with_interleaving",
    "get_forward_backward_func",
]



def _ensure_varying(tree: Any, axis_name: str) -> Any:
    """pcast to varying over ``axis_name`` only where not already so —
    pcast rejects a no-op cast."""

    def cast(x):
        try:
            if axis_name in jax.typeof(x).vma:
                return x
        except Exception:
            pass
        return jax.lax.pcast(x, axis_name, to="varying")

    return jax.tree.map(cast, tree)


def _vma_union(*trees) -> set:
    """Union of the varying-manual-axes of every leaf of every tree."""
    axes: set = set()
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            try:
                axes |= set(jax.typeof(leaf).vma)
            except AttributeError:
                pass
    return axes


def _cast_varying(tree: Any, axes: set) -> Any:
    """pcast every leaf to be varying over all of ``axes``."""

    def cast(x):
        try:
            have = set(jax.typeof(x).vma)
        except AttributeError:
            have = set()
        for ax in sorted(axes - have):
            x = jax.lax.pcast(x, ax, to="varying")
        return x

    return jax.tree.map(cast, tree)

def _soften_int_ct(ct_tree: Any, primal_tree: Any) -> Any:
    """Replace cotangents of integer/bool primals with ``float0`` zeros
    — the cotangent type ``jax.vjp`` expects for non-differentiable
    leaves (the 1F1B carries hold real int zeros instead, because scan
    carries and ppermute need concrete arrays)."""
    import numpy as np

    def f(p, c):
        if jnp.issubdtype(jnp.result_type(p), jnp.inexact):
            return c
        return np.zeros(jnp.shape(p), jax.dtypes.float0)

    return jax.tree.map(f, primal_tree, ct_tree)


def _harden_float0(ct_tree: Any, primal_tree: Any) -> Any:
    """Inverse of :func:`_soften_int_ct`: ``float0`` leaves become
    concrete zeros of the primal dtype so they can ride scan carries,
    ``jnp.where`` selects, and the ppermute ring."""

    def f(p, c):
        if getattr(c, "dtype", None) == jax.dtypes.float0:
            return jnp.zeros_like(p)
        return c

    return jax.tree.map(f, primal_tree, ct_tree)


def _index_microbatch(microbatches: Any, i) -> Any:
    return jax.tree.map(
        lambda x: lax.dynamic_index_in_dim(x, i, 0, keepdims=False),
        microbatches,
    )


def _where_tree(cond, a: Any, b: Any) -> Any:
    return jax.tree.map(lambda x, y: jnp.where(cond, x, y), a, b)


def _make_stash(zeros_state: Any, num_micro: int) -> Any:
    """(num_micro, ...) exit-activation stash with the carry's vma."""
    return jax.tree.map(
        lambda a: jnp.zeros((num_micro,) + a.shape, a.dtype) + a * 0,
        zeros_state,
    )


def _stash_add(stash: Any, value: Any, idx, take) -> Any:
    """Accumulate ``value`` into slot ``idx`` where ``take`` holds."""
    return jax.tree.map(
        lambda s, v: s.at[idx].add(jnp.where(take, v, jnp.zeros_like(v))),
        stash, value,
    )


def _head_pass(last_fn, stash, microbatches, is_exit_stage, axis_name):
    """Run the pipeline exit exactly once per microbatch over the stashed
    exit activations (sequential scan keeps a single head's intermediates
    live at a time), mask to the exit stage, replicate over the axis."""

    def head(_, ym):
        y, mb = ym
        return (), last_fn(y, mb)

    _, results = lax.scan(head, (), (stash, microbatches))
    results = jnp.where(
        is_exit_stage, results, jnp.zeros_like(results)
    )
    return lax.psum(results, axis_name)


def pipeline(
    first_fn: Callable[[Any], Any],
    stage_fn: Callable[[Any], Any],
    last_fn: Callable[[Any, Any], jnp.ndarray],
    microbatches: Any,
    *,
    axis_name: str = PIPELINE_PARALLEL_AXIS,
    remat: bool = True,
) -> jnp.ndarray:
    """Run the compiled SPMD pipeline; returns per-microbatch results.

    - ``first_fn(mb)``: the pipeline entry (e.g. embedding) — logically
      stage 0's preamble.  Must return the activation pytree that flows
      through stages; every stage's output must have the same structure
      (homogeneous stages, as in a transformer stack).
    - ``stage_fn(x)``: one pipeline stage.  Close over the *local* stage
      params (sharded ``P("pp", ...)`` so each rank holds its own stage).
    - ``last_fn(y, mb)``: the pipeline exit on the final stage (e.g. LM
      head + loss against the microbatch's targets).  Must return a
      scalar or fixed-shape array per microbatch.
    - ``microbatches``: pytree with a leading ``num_microbatches`` dim,
      replicated over the pipeline axis.

    Returns the stacked ``last_fn`` results, one per microbatch,
    replicated over the pipeline axis.  Differentiate through this for
    the backward pipeline.
    """
    pp = jax.lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    num_micro = jax.tree.leaves(microbatches)[0].shape[0]
    ticks = num_micro + pp - 1

    mb0 = _index_microbatch(microbatches, 0)
    # the carry must match the loop body's type exactly, including its
    # varying-across-mesh axes: derive it from a real entry activation
    # (multiply-by-zero keeps the vma) and mark it varying over the
    # pipeline axis, which ppermute introduces inside the loop
    zeros_state = _ensure_varying(
        jax.tree.map(lambda a: a * 0, first_fn(mb0)), axis_name
    )

    body = stage_fn
    if remat:
        body = jax.checkpoint(stage_fn)

    # exit activations accumulate into a (num_micro, ...) stash so the
    # pipeline exit (LM head + loss — the most expensive single op) runs
    # exactly num_micro times AFTER the ring scan, not once per tick
    # (the reference's 1F1B likewise runs loss once per microbatch,
    # fwd_bwd_pipelining_without_interleaving.py:112-149)
    stash0 = _make_stash(zeros_state, num_micro)

    def tick(carry, t):
        state, stash = carry
        # fresh microbatch enters at stage 0 (clamped index; the tail
        # ticks feed stage 0 garbage that never reaches the exit stash)
        mb_in = _index_microbatch(
            microbatches, jnp.minimum(t, num_micro - 1)
        )
        entry = first_fn(mb_in)
        x = _where_tree(stage == 0, entry, state)
        y = body(x)
        # exit at the last stage: microbatch index t-(pp-1); ticks before
        # the fill add zeros to slot 0
        out_idx = jnp.maximum(t - (pp - 1), 0)
        take = (stage == pp - 1) & (t >= pp - 1)
        stash = _stash_add(stash, y, out_idx, take)
        # rotate activations to the next stage
        state = send_forward(y, axis_name)
        return (state, stash), None

    (_, stash), _ = lax.scan(
        tick, (zeros_state, stash0), jnp.arange(ticks)
    )
    return _head_pass(last_fn, stash, microbatches, stage == pp - 1,
                      axis_name)


def _head_vjp(params, last_fn, y_rec, mb_b, pred, bwd_valid,
              loss_probe, loss_seed, axis_name):
    """Gated LM-head vjp shared by the whole 1F1B family: on ``pred``
    ticks, run ``last_fn``'s vjp seeded with ``loss_seed`` and return
    ``(loss_m, dparams_head, dy_head)``; otherwise type-matched zeros.
    Safe in SPMD: ``pred`` depends only on (t, pipeline rank), so every
    device in a tp group takes the same branch and the head's tp
    collectives stay consistent within their groups."""

    def head_branch(prm, yy, mb):
        loss_m, head_vjp = jax.vjp(
            lambda p_, y_: last_fn(p_, y_, mb), prm, yy
        )
        # the seed value is always loss_seed here (the cond predicate
        # includes bwd_valid); the union with bwd_valid's vma keeps the
        # branch outputs' types identical to head_zero's
        seed = _cast_varying(
            jnp.float32(loss_seed), _vma_union(loss_m, bwd_valid)
        )
        dprm, dy_h = head_vjp(seed)
        return loss_m, dprm, _harden_float0(dy_h, yy)

    def head_zero(prm, yy, mb):
        return (
            # the live branch's loss varies over the pipeline axis
            # (y_rec does); the probe was computed outside the ring
            _cast_varying(
                loss_probe * 0, _vma_union(loss_probe) | {axis_name}
            ),
            jax.tree.map(lambda p_: p_ * 0, prm),
            jax.tree.map(lambda a: a * 0, yy),
        )

    return lax.cond(pred, head_branch, head_zero, params, y_rec, mb_b)


def _entry_vjp(params, entry_fn, ct, mb_b, pred, zeros_x):
    """Gated pipeline-entry (embedding) vjp shared by the 1F1B family:
    on ``pred`` ticks, pull the entry cotangent ``ct`` into parameter
    grads; otherwise zeros."""

    def emb_branch(prm, ct_, mb):
        _, emb_vjp = jax.vjp(lambda p_: entry_fn(p_, mb), prm)
        (dprm,) = emb_vjp(_soften_int_ct(ct_, zeros_x))
        return dprm

    def emb_zero(prm, ct_, mb):
        return jax.tree.map(lambda p_: p_ * 0, prm)

    return lax.cond(pred, emb_branch, emb_zero, params, ct, mb_b)


def _bwd_tick(
    *,
    params: Any,
    apply_fn: Callable,
    first_fn: Callable,
    last_fn: Callable,
    x_saved: Any,
    mb_b: Any,
    bwd_valid,
    is_exit,
    is_entry,
    bwd_ct: Any,
    loss_probe,
    loss_seed,
    zeros_x: Any,
    axis_name: str,
) -> tuple:
    """One backward micro-step, shared by :func:`pipeline_1f1b` and
    :func:`pipeline_1f1b_interleaved`: re-derive the stage/chunk
    activations from the saved input (per-stage remat), seed the exit
    cotangent from the loss head, pull the cotangent through one
    ``jax.vjp``, and feed the pipeline-entry cotangent to the embedding.

    The head and embedding vjps ride ``lax.cond``s gated on
    ``bwd_valid`` too, so each runs exactly M times per schedule —
    matching the reference's per-microbatch count (the old exit-stage predicate paid one head per tick).  Safe in
    SPMD: the predicates depend only on (t, pipeline rank), so every
    device in a tp group takes the same branch and the head's tp
    collectives stay consistent within their groups.

    Returns ``(loss_m, dparams, dx)``: the microbatch loss (exit ticks
    only), the summed parameter cotangents (stage + head + embedding),
    and the input cotangent to ride the reverse ring.
    """
    y_rec, stage_vjp = jax.vjp(apply_fn, params, x_saved)
    loss_m, dparams_head, dy_head = _head_vjp(
        params, last_fn, y_rec, mb_b, is_exit & bwd_valid, bwd_valid,
        loss_probe, loss_seed, axis_name,
    )

    dy = _where_tree(is_exit, dy_head, bwd_ct)
    dy = _where_tree(bwd_valid, dy, jax.tree.map(jnp.zeros_like, dy))
    dparams_stage, dx = stage_vjp(_soften_int_ct(dy, y_rec))
    dx = _harden_float0(dx, x_saved)

    dparams_emb = _entry_vjp(
        params, first_fn, dx, mb_b, is_entry & bwd_valid, zeros_x
    )

    dparams = jax.tree.map(
        lambda a, b, c: a + b + c,
        dparams_stage, dparams_head, dparams_emb,
    )
    return loss_m, dparams, dx


def pipeline_1f1b(
    first_fn: Callable[[Any, Any], Any],
    stage_fn: Callable[[Any, Any], Any],
    last_fn: Callable[[Any, Any, Any], jnp.ndarray],
    params: Any,
    microbatches: Any,
    *,
    axis_name: str = PIPELINE_PARALLEL_AXIS,
) -> tuple:
    """True 1F1B: forward and backward interleave inside ONE compiled
    scan, and in-flight activation state is bounded by the pipeline
    depth — not by the microbatch count
    (reference: apex/transformer/pipeline_parallel/schedules/
    fwd_bwd_pipelining_without_interleaving.py:112-149 steady state).

    Unlike :func:`pipeline` (which is differentiated from outside and
    therefore scans all ``num_micro`` microbatches' residuals into the
    autodiff tape), this schedule IS the fwd+bwd: it returns the
    per-microbatch losses and the gradient of their **mean** w.r.t.
    ``params`` directly.  Memory: a circular buffer of ``2*pp`` saved
    stage *inputs* per stage; each backward tick re-derives its stage
    activations from the saved input (per-stage remat — recompute over
    store, the standard TPU trade) and one ``jax.vjp`` pulls the
    cotangent through.  Peak activation memory is O(pp), independent of
    gradient-accumulation depth, which is the entire point of 1F1B.

    Schedule coordinates (tick ``t``, stage ``p``, ``pp`` stages,
    ``M`` microbatches, ``T = M + 2*pp - 2`` ticks):

    - forward of microbatch ``t - p`` (when in range);
    - backward of microbatch ``t - (2*pp - 2 - p)`` — the last stage
      runs a microbatch's backward in the SAME tick as its forward,
      stage 0 runs it ``2*(pp-1)`` ticks later;
    - activations ride ``ppermute`` +1, cotangents ride ``ppermute``
      −1, both per tick (the reference's send_forward_recv_backward
      pair, p2p_communication.py:183-404).

    Functions take ``params`` explicitly (the schedule differentiates
    through them): ``first_fn(params, mb) -> x``,
    ``stage_fn(params, x) -> y``, ``last_fn(params, y, mb) -> scalar``.
    ``params["..."]`` leaves that are stage-local must be sharded over
    the pipeline axis by the caller exactly as for :func:`pipeline`;
    apply ``sync_replicated_grads`` to the returned grads for shared
    (replicated) params, as usual.

    Returns ``(losses, grads)``: the (M,) per-microbatch losses
    (replicated over the pipeline axis) and ``d(mean losses)/d params``.
    """
    pp = jax.lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    num_micro = jax.tree.leaves(microbatches)[0].shape[0]
    ticks = num_micro + 2 * pp - 2
    nbuf = 2 * pp

    mb0 = _index_microbatch(microbatches, 0)
    # mark the params varying over the data axes (dp/cp, whatever the
    # microbatches vary over) and the pipeline axis: the vjps then
    # return grads that are data-shard-local (the same contract as
    # differentiating the GPipe pipeline from outside — the caller
    # pmean's over "dp") and per-stage (sync_replicated_grads psums the
    # shared ones, as usual).  Model axes like "tp" are deliberately NOT
    # cast: the vjp transpose inserts the tp psums tp-replicated params
    # need, exactly as plain autodiff would.
    data_axes = _vma_union(microbatches)
    params = _cast_varying(params, data_axes | {axis_name})
    # carry vmas come from probes of the actual functions — cotangents
    # type-match their primals, so grads0 = params*0 is exact, and the
    # activation stream/cotangent/buffer all share the entry
    # activation's vma (+ the pipeline axis the ppermutes introduce)
    x_probe = first_fn(params, mb0)
    zeros_x = _cast_varying(
        jax.tree.map(lambda a: a * 0, x_probe), {axis_name}
    )
    # stage output cotangent carries the same structure as the stage
    # input (homogeneous stages)
    zeros_ct = zeros_x
    buffer0 = _make_stash(zeros_x, nbuf)
    grads0 = jax.tree.map(lambda p_: p_ * 0, params)
    loss_probe = last_fn(
        params, jax.tree.map(lambda a: a * 0, x_probe), mb0
    )
    losses0 = _cast_varying(
        jnp.zeros((num_micro,), jnp.float32),
        _vma_union(loss_probe) | {axis_name},
    )
    loss_seed = jnp.float32(1.0 / num_micro)

    def tick(carry, t):
        fwd_state, bwd_ct, buffer, grads, losses = carry

        # ---- forward: microbatch t - p enters/advances ----------------
        mf = t - stage
        fwd_valid = (mf >= 0) & (mf < num_micro)
        mb_f = _index_microbatch(
            microbatches, jnp.clip(mf, 0, num_micro - 1)
        )
        x_in = _where_tree(stage == 0, first_fn(params, mb_f), fwd_state)
        y = stage_fn(params, x_in)
        slot_f = jnp.clip(mf, 0, num_micro - 1) % nbuf
        buffer = jax.tree.map(
            lambda b, xi: b.at[slot_f].set(
                jnp.where(fwd_valid, xi, b[slot_f])
            ),
            buffer, x_in,
        )

        # ---- backward: microbatch t - (2pp - 2 - p) retires -----------
        mb_idx = t - (2 * pp - 2 - stage)
        bwd_valid = (mb_idx >= 0) & (mb_idx < num_micro)
        mb_c = jnp.clip(mb_idx, 0, num_micro - 1)
        mb_b = _index_microbatch(microbatches, mb_c)
        slot_b = mb_c % nbuf
        x_saved = jax.tree.map(lambda b: b[slot_b], buffer)

        is_exit = stage == pp - 1
        loss_m, dparams, dx = _bwd_tick(
            params=params, apply_fn=stage_fn, first_fn=first_fn,
            last_fn=last_fn, x_saved=x_saved, mb_b=mb_b,
            bwd_valid=bwd_valid, is_exit=is_exit, is_entry=stage == 0,
            bwd_ct=bwd_ct, loss_probe=loss_probe, loss_seed=loss_seed,
            zeros_x=zeros_x, axis_name=axis_name,
        )
        grads = jax.tree.map(lambda g, d: g + d, grads, dparams)
        losses = losses.at[mb_c].add(
            jnp.where(is_exit & bwd_valid, loss_m, 0.0)
        )

        fwd_state, bwd_ct = send_forward_recv_backward(y, dx, axis_name)
        return (fwd_state, bwd_ct, buffer, grads, losses), None

    (_, _, _, grads, losses), _ = lax.scan(
        tick,
        (zeros_x, zeros_ct, buffer0, grads0, losses0),
        jnp.arange(ticks),
    )
    # only the exit stage accumulated real losses
    losses = lax.psum(losses, axis_name)
    return losses, grads


def pipeline_1f1b_interleaved(
    first_fn: Callable[[Any, Any], Any],
    chunk_fn: Callable[[Any, Any, Any], Any],
    last_fn: Callable[[Any, Any, Any], jnp.ndarray],
    params: Any,
    microbatches: Any,
    num_model_chunks: int,
    *,
    axis_name: str = PIPELINE_PARALLEL_AXIS,
) -> tuple:
    """Interleaved (virtual-pipeline) 1F1B: V model chunks per rank AND
    forward/backward in one compiled scan with O(pp·V) activation memory
    (reference: apex/transformer/pipeline_parallel/schedules/
    fwd_bwd_pipelining_with_interleaving.py:22-308 — the reference's
    interleaved schedule is a full fwd/bwd 1F1B; this is its compiled
    SPMD counterpart, combining :func:`pipeline_1f1b`'s fwd+bwd scan
    with the chunk coordinates of
    :func:`forward_backward_pipelining_with_interleaving`).

    Schedule.  Chunk ``v`` of rank ``p`` is global stage ``v*pp + p``;
    a microbatch rides the ``ppermute`` ring V times.  With
    ``M = num_microbatches`` (divisible by pp) and phase
    ``τ = t - p``:

    - **forward** at tick ``t``: chunk ``v = (τ % (V*pp)) // pp``,
      microbatch ``(τ // (V*pp))*pp + τ % pp``  (valid for
      ``0 ≤ τ < M*V``) — the standard interleaved order: groups of pp
      microbatches cycle through the chunks;
    - **backward** is the time-and-microbatch-reversed forward wave:
      with ``τ_r = (T-1-t) - p``, the same coordinate extraction gives
      chunk ``v_b`` and reversed microbatch ``mbr``; the tick handles
      the backward of chunk ``v_b`` for microbatch ``M-1-mbr``.  This
      reversal makes every cotangent hop a ``ppermute(-1)`` — including
      the chunk-boundary hop from rank 0 back to rank pp-1 — so the
      whole backward rides the same send_forward_recv_backward pair as
      :func:`pipeline_1f1b`, and each rank retires exactly one chunk
      backward per tick.

    Total ticks ``T = M*V + (V+1)*pp - 2``: the exit global stage
    (rank pp-1, chunk V-1) runs a microbatch's backward ``pp-1`` ticks
    after its forward, every other (p, v) earlier by
    ``2·((V-v)·pp - p - 1)`` ticks (derivation: b - f of the reversed
    wave).  Bubble in stage-time units: ``((V+1)·pp - 2)/V`` vs the
    non-interleaved schedule's ``2·pp - 2`` — smaller for every V ≥ 2
    (e.g. pp=4: V=2 → 5 vs 6 stage-times; the reference's irregular
    depth-first ordering reaches 2·(pp-1)/V but does not map to a
    regular compiled scan; the gap is documented, not hidden).

    Memory: a (V, 2·pp) circular buffer of saved chunk *inputs* per
    rank; backward re-derives chunk activations from the saved input
    (per-chunk remat) and one ``jax.vjp`` pulls the cotangent through.
    Slot reuse is safe because a (v, mb) input lives at most
    ``2·V·pp - 2`` ticks while same-chunk microbatches ``2·pp`` apart
    start ``2·V·pp`` ticks apart.

    Functions: ``first_fn(params, mb) -> x``;
    ``chunk_fn(params, x, v) -> y`` applies model chunk ``v`` (a traced
    index — select chunk params with ``lax.dynamic_index_in_dim``);
    ``last_fn(params, y, mb) -> scalar``.  Same contracts as
    :func:`pipeline_1f1b` otherwise (params varying over data + pp
    axes; apply ``sync_replicated_grads`` to the returned grads).

    Returns ``(losses, grads)`` exactly like :func:`pipeline_1f1b`.
    """
    pp = jax.lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    V = num_model_chunks
    num_micro = jax.tree.leaves(microbatches)[0].shape[0]
    if num_micro % pp:
        raise ValueError(
            f"number of microbatches ({num_micro}) is not divisible by "
            f"pipeline-parallel size ({pp}) as required by the "
            "interleaved schedule"
        )
    ticks = num_micro * V + (V + 1) * pp - 2
    nbuf = 2 * pp
    period = V * pp

    mb0 = _index_microbatch(microbatches, 0)
    data_axes = _vma_union(microbatches)
    params = _cast_varying(params, data_axes | {axis_name})
    x_probe = first_fn(params, mb0)
    zeros_x = _cast_varying(
        jax.tree.map(lambda a: a * 0, x_probe), {axis_name}
    )
    zeros_ct = zeros_x
    # (V, nbuf, ...) saved chunk inputs
    buffer0 = jax.tree.map(
        lambda a: jnp.zeros((V, nbuf) + a.shape, a.dtype) + a * 0, zeros_x
    )
    grads0 = jax.tree.map(lambda p_: p_ * 0, params)
    loss_probe = last_fn(
        params, jax.tree.map(lambda a: a * 0, x_probe), mb0
    )
    losses0 = _cast_varying(
        jnp.zeros((num_micro,), jnp.float32),
        _vma_union(loss_probe) | {axis_name},
    )
    loss_seed = jnp.float32(1.0 / num_micro)

    def coords(tau):
        """(chunk, microbatch, in-range) from an interleaved phase."""
        valid = (tau >= 0) & (tau < num_micro * V)
        phase = jnp.maximum(tau, 0)
        m = phase % pp
        v = (phase % period) // pp
        g = phase // period
        mb = jnp.clip(g * pp + m, 0, num_micro - 1)
        return v, mb, valid

    def tick(carry, t):
        fwd_state, bwd_ct, buffer, grads, losses = carry

        # ---- forward: one chunk application ---------------------------
        v_f, mb_f, fwd_valid = coords(t - stage)
        mb_in = _index_microbatch(microbatches, mb_f)
        is_entry = (stage == 0) & (v_f == 0)
        x_in = _where_tree(is_entry, first_fn(params, mb_in), fwd_state)
        y = chunk_fn(params, x_in, v_f)
        slot_f = mb_f % nbuf
        buffer = jax.tree.map(
            lambda b, xi: b.at[v_f, slot_f].set(
                jnp.where(fwd_valid, xi, b[v_f, slot_f])
            ),
            buffer, x_in,
        )

        # ---- backward: the reversed forward wave ----------------------
        v_b, mbr, bwd_valid = coords((ticks - 1 - t) - stage)
        mb_c = num_micro - 1 - mbr
        mb_b = _index_microbatch(microbatches, mb_c)
        slot_b = mb_c % nbuf
        x_saved = jax.tree.map(lambda b: b[v_b, slot_b], buffer)

        is_exit = (stage == pp - 1) & (v_b == V - 1)
        loss_m, dparams, dx = _bwd_tick(
            params=params,
            apply_fn=lambda p_, x_: chunk_fn(p_, x_, v_b),
            first_fn=first_fn, last_fn=last_fn,
            x_saved=x_saved, mb_b=mb_b, bwd_valid=bwd_valid,
            is_exit=is_exit, is_entry=(stage == 0) & (v_b == 0),
            bwd_ct=bwd_ct, loss_probe=loss_probe, loss_seed=loss_seed,
            zeros_x=zeros_x, axis_name=axis_name,
        )
        grads = jax.tree.map(lambda g, d: g + d, grads, dparams)
        losses = losses.at[mb_c].add(
            jnp.where(is_exit & bwd_valid, loss_m, 0.0)
        )

        fwd_state, bwd_ct = send_forward_recv_backward(y, dx, axis_name)
        return (fwd_state, bwd_ct, buffer, grads, losses), None

    (_, _, _, grads, losses), _ = lax.scan(
        tick,
        (zeros_x, zeros_ct, buffer0, grads0, losses0),
        jnp.arange(ticks),
    )
    losses = lax.psum(losses, axis_name)
    return losses, grads


def pipeline_encdec(
    enc_entry_fn: Callable[[Any], Any],
    enc_stage_fn: Callable[[Any], Any],
    dec_entry_fn: Callable[[Any], Any],
    dec_stage_fn: Callable[[Any, Any], Any],
    last_fn: Callable[[Any, Any], jnp.ndarray],
    microbatches: Any,
    split_stage: int,
    *,
    axis_name: str = PIPELINE_PARALLEL_AXIS,
    remat: bool = True,
) -> jnp.ndarray:
    """Encoder-and-decoder pipeline (reference: ModelType.encoder_and_decoder
    scheduling in apex/transformer/pipeline_parallel/schedules/common.py:18-108
    with ``pipeline_model_parallel_split_rank``).

    Stages ``[0, split_stage)`` run the encoder, ``[split_stage, pp)`` the
    decoder.  Three streams ride the ``ppermute`` ring together:

    - ``xe``: the encoder activation — entered by ``enc_entry_fn`` at
      stage 0, transformed by ``enc_stage_fn`` on encoder stages, passed
      through on decoder stages;
    - ``mem``: the finished encoder output (cross-attention memory) —
      captured from the incoming ``xe`` at ``split_stage`` and carried
      alongside its microbatch through the decoder stages;
    - ``xd``: the decoder activation — entered by ``dec_entry_fn`` at
      ``split_stage``, transformed by ``dec_stage_fn(xd, mem)``.

    SPMD note: every stage executes both ``enc_stage_fn`` and
    ``dec_stage_fn`` each tick and keeps its own branch (single compiled
    program; lax.cond on a mesh-varying predicate lowers to select
    anyway).  Encoder stages therefore burn the decoder stage's FLOPs
    and vice versa — the cost of the reference's heterogeneous
    per-process schedule becoming one compiled SPMD program.  pp and the
    per-stage layer count are small where this matters (the reference's
    own enc-dec splits are 2-4 stages per side).

    Microbatch ``m`` exits at stage pp-1 at tick ``m + pp - 1`` exactly
    as in :func:`pipeline`; the LM head (``last_fn``) runs once per
    microbatch after the ring scan.  Differentiate through the result
    for the reverse pipeline.
    """
    pp = jax.lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    if not (1 <= split_stage < pp):
        raise ValueError(
            f"split_stage ({split_stage}) must be in [1, pp) — at least "
            f"one encoder and one decoder stage (pp={pp})"
        )
    num_micro = jax.tree.leaves(microbatches)[0].shape[0]
    ticks = num_micro + pp - 1

    mb0 = _index_microbatch(microbatches, 0)
    zeros_xe = _ensure_varying(
        jax.tree.map(lambda a: a * 0, enc_entry_fn(mb0)), axis_name
    )
    zeros_xd = _ensure_varying(
        jax.tree.map(lambda a: a * 0, dec_entry_fn(mb0)), axis_name
    )
    zeros_mem = zeros_xe

    enc_body = jax.checkpoint(enc_stage_fn) if remat else enc_stage_fn
    dec_body = jax.checkpoint(dec_stage_fn) if remat else dec_stage_fn

    stash0 = _make_stash(zeros_xd, num_micro)

    def tick(carry, t):
        xe, xd, mem, stash = carry
        # encoder stream: fresh microbatch enters at stage 0
        mb_enc = _index_microbatch(
            microbatches, jnp.minimum(t, num_micro - 1)
        )
        xe_in = _where_tree(stage == 0, enc_entry_fn(mb_enc), xe)
        # the microbatch arriving at the split stage this tick entered
        # the ring split_stage ticks ago
        dec_mb_idx = jnp.clip(t - split_stage, 0, num_micro - 1)
        mb_dec = _index_microbatch(microbatches, dec_mb_idx)
        at_split = stage == split_stage
        # capture the finished encoder output as this microbatch's
        # cross-attention memory and admit its decoder embedding
        mem = _where_tree(at_split, xe, mem)
        xd_in = _where_tree(at_split, dec_entry_fn(mb_dec), xd)

        ye = enc_body(xe_in)
        yd = dec_body(xd_in, mem)
        is_enc = stage < split_stage
        ye = _where_tree(is_enc, ye, xe_in)
        yd = _where_tree(is_enc, xd_in, yd)

        out_idx = jnp.maximum(t - (pp - 1), 0)
        take = (stage == pp - 1) & (t >= pp - 1)
        stash = _stash_add(stash, yd, out_idx, take)

        xe = send_forward(ye, axis_name)
        xd = send_forward(yd, axis_name)
        mem = send_forward(mem, axis_name)
        return (xe, xd, mem, stash), None

    (_, _, _, stash), _ = lax.scan(
        tick, (zeros_xe, zeros_xd, zeros_mem, stash0), jnp.arange(ticks)
    )
    return _head_pass(last_fn, stash, microbatches, stage == pp - 1,
                      axis_name)


def pipeline_encdec_fused(
    enc_entry_fn: Callable[[Any], Any],
    dec_entry_fn: Callable[[Any], Any],
    stage_fn: Callable[[Any, Any, jnp.ndarray], Any],
    last_fn: Callable[[Any, Any], jnp.ndarray],
    microbatches: Any,
    split_stage: int,
    *,
    axis_name: str = PIPELINE_PARALLEL_AXIS,
    remat: bool = True,
) -> jnp.ndarray:
    """Encoder-decoder pipeline with ONE stage body per tick — the
    collapse of :func:`pipeline_encdec`'s double-FLOPs cost (reference:
    the heterogeneous per-rank enc/dec schedule, apex/transformer/
    pipeline_parallel/schedules/fwd_bwd_pipelining_without_interleaving
    .py:22-170, which never runs both bodies on one rank).

    :func:`pipeline_encdec` keeps two activation streams and runs BOTH
    ``enc_stage_fn`` and ``dec_stage_fn`` on every stage every tick,
    because a mesh-varying ``lax.cond`` lowers to compute-both-and-
    select.  This schedule instead rides a SINGLE activation stream
    through one homogeneous ``stage_fn(x, mem, stage)`` whose per-stage
    *parameters* (already device-varying data under "pp" sharding)
    select the behaviour:

    - stages ``[0, split_stage)`` hold encoder weights; the model's
      stage body gates its cross-attention off (multiply by
      ``stage >= split``) and selects a non-causal mask — both data
      selects, no second body;
    - the activation arriving AT ``split_stage`` is the finished
      encoder output: it is captured as the cross-attention ``mem``
      stream and the stream is re-entered with ``dec_entry_fn``;
    - stages ``[split_stage, pp)`` transform the decoder stream against
      the riding ``mem``.

    Per-tick cost is therefore ONE superset stage body (decoder-shaped:
    self-attn + gated cross-attn + MLP) instead of encoder body PLUS
    decoder body, and the ring carries two streams (x, mem) instead of
    three (xe, xd, mem).  The requirement bought by that: both entry
    functions must produce the SAME pytree structure/shapes (pad the
    shorter sequence and mask via the attention's segment ids — the
    model owns that, e.g. ``T5Model`` with ``fused_pipeline=True``).

    Timing is identical to :func:`pipeline_encdec`: microbatch ``m``
    enters stage 0 at tick ``m``, is captured/re-entered at
    ``split_stage`` at tick ``m + split_stage``, and exits stage
    ``pp - 1`` at tick ``m + pp - 1``; the head runs once per
    microbatch after the scan.  Differentiate through the result for
    the reverse pipeline.
    """
    pp = jax.lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    if not (1 <= split_stage < pp):
        raise ValueError(
            f"split_stage ({split_stage}) must be in [1, pp) — at least "
            f"one encoder and one decoder stage (pp={pp})"
        )
    num_micro = jax.tree.leaves(microbatches)[0].shape[0]
    ticks = num_micro + pp - 1

    mb0 = _index_microbatch(microbatches, 0)
    ze = enc_entry_fn(mb0)
    zd = dec_entry_fn(mb0)
    e_shapes = [(a.shape, a.dtype) for a in jax.tree.leaves(ze)]
    d_shapes = [(a.shape, a.dtype) for a in jax.tree.leaves(zd)]
    if e_shapes != d_shapes:
        raise ValueError(
            "pipeline_encdec_fused needs enc_entry_fn and dec_entry_fn "
            f"to emit identical pytrees (got {e_shapes} vs {d_shapes}); "
            "pad the shorter stream to a common shape and mask via "
            "attention segment ids, or use pipeline_encdec"
        )
    zeros_x = _ensure_varying(jax.tree.map(lambda a: a * 0, ze), axis_name)
    zeros_mem = zeros_x

    body = jax.checkpoint(stage_fn) if remat else stage_fn
    stash0 = _make_stash(zeros_x, num_micro)

    def tick(carry, t):
        x, mem, stash = carry
        mb_enc = _index_microbatch(
            microbatches, jnp.minimum(t, num_micro - 1)
        )
        x_in = _where_tree(stage == 0, enc_entry_fn(mb_enc), x)
        # the microbatch arriving at the split stage this tick entered
        # the ring split_stage ticks ago
        dec_mb_idx = jnp.clip(t - split_stage, 0, num_micro - 1)
        mb_dec = _index_microbatch(microbatches, dec_mb_idx)
        at_split = stage == split_stage
        # the incoming activation at the split IS the finished encoder
        # output: capture it as this microbatch's cross-attention
        # memory, then re-enter the stream with the decoder embedding
        mem = _where_tree(at_split, x_in, mem)
        x_in = _where_tree(at_split, dec_entry_fn(mb_dec), x_in)

        y = body(x_in, mem, stage)

        out_idx = jnp.maximum(t - (pp - 1), 0)
        take = (stage == pp - 1) & (t >= pp - 1)
        stash = _stash_add(stash, y, out_idx, take)

        x = send_forward(y, axis_name)
        mem = send_forward(mem, axis_name)
        return (x, mem, stash), None

    (_, _, stash), _ = lax.scan(
        tick, (zeros_x, zeros_mem, stash0), jnp.arange(ticks)
    )
    return _head_pass(last_fn, stash, microbatches, stage == pp - 1,
                      axis_name)


def pipeline_encdec_fused_1f1b(
    enc_entry_fn: Callable[[Any, Any], Any],
    dec_entry_fn: Callable[[Any, Any], Any],
    stage_fn: Callable[[Any, Any, Any, jnp.ndarray], Any],
    last_fn: Callable[[Any, Any, Any], jnp.ndarray],
    params: Any,
    microbatches: Any,
    split_stage: int,
    *,
    axis_name: str = PIPELINE_PARALLEL_AXIS,
) -> tuple:
    """True 1F1B for the fused encoder-decoder pipeline: O(pp)
    activation memory for enc-dec models (the reference schedules
    enc-dec ONLY without 1F1B steady-state memory bounds —
    schedules/common.py:18-108; this goes beyond it).

    Builds on :func:`pipeline_encdec_fused`'s single activation stream
    (one homogeneous ``stage_fn(params, x, mem, stage)`` body, memory
    captured at ``split_stage``) and :func:`pipeline_1f1b`'s schedule
    coordinates (fwd of microbatch ``t - p``, bwd of microbatch
    ``t - (2pp - 2 - p)``, ``T = M + 2pp - 2`` ticks).  The enc-dec
    specifics:

    - the saved-state circular buffer holds the full stage input PAIR
      ``{x, mem}`` (2*pp of them), so each backward tick can re-derive
      its stage activations by remat exactly as the plain schedule does;
    - the reverse ring carries the cotangent PAIR ``{dx, dmem}``:
      ``mem`` passes through decoder stages unchanged, so its cotangent
      ACCUMULATES stage-by-stage on the way back (each stage adds its
      local cross-attention contribution);
    - at the split stage the accumulated ``dmem`` IS the cotangent of
      the incoming encoder output: it crosses over to ride the ring as
      ``dx`` into the encoder stages (whose own ``dmem`` is identically
      zero — their cross-attention is gated off), and the stage's local
      ``dx`` (the decoder-embedding cotangent) feeds the decoder
      entry's vjp — the second pipeline entry point, mirroring stage
      0's encoder-embedding vjp.

    Same contract as :func:`pipeline_1f1b`: returns ``(losses, grads)``
    with grads = d(mean losses)/d params, shard-local in the data axes,
    shared-param pp-sync NOT yet applied.
    """
    pp = jax.lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    if not (1 <= split_stage < pp):
        raise ValueError(
            f"split_stage ({split_stage}) must be in [1, pp) (pp={pp})"
        )
    num_micro = jax.tree.leaves(microbatches)[0].shape[0]
    ticks = num_micro + 2 * pp - 2
    nbuf = 2 * pp

    mb0 = _index_microbatch(microbatches, 0)
    data_axes = _vma_union(microbatches)
    params = _cast_varying(params, data_axes | {axis_name})

    x_probe = enc_entry_fn(params, mb0)
    d_probe = dec_entry_fn(params, mb0)
    e_shapes = [(a.shape, a.dtype) for a in jax.tree.leaves(x_probe)]
    d_shapes = [(a.shape, a.dtype) for a in jax.tree.leaves(d_probe)]
    if e_shapes != d_shapes:
        raise ValueError(
            "fused enc-dec 1F1B needs identical entry pytrees (got "
            f"{e_shapes} vs {d_shapes}); pad the shorter stream (see "
            "pipeline_encdec_fused)"
        )
    zeros_x = _cast_varying(
        jax.tree.map(lambda a: a * 0, x_probe), {axis_name}
    )
    zeros_pair = {"x": zeros_x, "mem": zeros_x}
    buffer0 = _make_stash(zeros_pair, nbuf)
    grads0 = jax.tree.map(lambda p_: p_ * 0, params)
    loss_probe = last_fn(
        params, jax.tree.map(lambda a: a * 0, x_probe), mb0
    )
    losses0 = _cast_varying(
        jnp.zeros((num_micro,), jnp.float32),
        _vma_union(loss_probe) | {axis_name},
    )
    loss_seed = jnp.float32(1.0 / num_micro)
    at_split = stage == split_stage

    def apply_pair(prm, pair):
        return stage_fn(prm, pair["x"], pair["mem"], stage)

    def tick(carry, t):
        fwd_pair, bwd_pair, buffer, grads, losses = carry

        # ---- forward: microbatch t - p enters/advances ----------------
        mf = t - stage
        fwd_valid = (mf >= 0) & (mf < num_micro)
        mb_f = _index_microbatch(
            microbatches, jnp.clip(mf, 0, num_micro - 1)
        )
        x_in = _where_tree(
            stage == 0, enc_entry_fn(params, mb_f), fwd_pair["x"]
        )
        # the split stage's incoming x IS the finished encoder output:
        # capture it as this microbatch's memory, re-enter with the
        # decoder embedding (microbatch index is mf at both entries —
        # the fused forward puts microbatch m at stage p at tick m + p)
        mem_in = _where_tree(at_split, x_in, fwd_pair["mem"])
        x_in = _where_tree(
            at_split, dec_entry_fn(params, mb_f), x_in
        )
        pair_in = {"x": x_in, "mem": mem_in}
        y = apply_pair(params, pair_in)
        slot_f = jnp.clip(mf, 0, num_micro - 1) % nbuf
        buffer = jax.tree.map(
            lambda b, xi: b.at[slot_f].set(
                jnp.where(fwd_valid, xi, b[slot_f])
            ),
            buffer, pair_in,
        )

        # ---- backward: microbatch t - (2pp - 2 - p) retires -----------
        mb_idx = t - (2 * pp - 2 - stage)
        bwd_valid = (mb_idx >= 0) & (mb_idx < num_micro)
        mb_c = jnp.clip(mb_idx, 0, num_micro - 1)
        mb_b = _index_microbatch(microbatches, mb_c)
        slot_b = mb_c % nbuf
        pair_saved = jax.tree.map(lambda b: b[slot_b], buffer)

        y_rec, stage_vjp = jax.vjp(apply_pair, params, pair_saved)
        is_exit = stage == pp - 1
        loss_m, dparams_head, dy_head = _head_vjp(
            params, last_fn, y_rec, mb_b, is_exit & bwd_valid,
            bwd_valid, loss_probe, loss_seed, axis_name,
        )

        dy = _where_tree(is_exit, dy_head, bwd_pair["x"])
        dy = _where_tree(bwd_valid, dy, jax.tree.map(jnp.zeros_like, dy))
        dparams_stage, dpair = stage_vjp(_soften_int_ct(dy, y_rec))
        dpair = _harden_float0(dpair, pair_saved)
        dx_local, dmem_local = dpair["x"], dpair["mem"]
        # mem passes through stages unchanged, so its cotangent is the
        # local cross-attention contribution PLUS whatever accumulated
        # downstream (gated like dy: the arriving pair belongs to the
        # same retiring microbatch)
        dmem_in = _where_tree(
            bwd_valid, bwd_pair["mem"],
            jax.tree.map(jnp.zeros_like, bwd_pair["mem"]),
        )
        dmem_total = jax.tree.map(
            lambda a, b: a + b, dmem_local, dmem_in
        )

        # entry vjps: encoder embedding at stage 0, decoder embedding
        # at the split — each seeded with the LOCAL x-cotangent
        dparams_enc = _entry_vjp(
            params, enc_entry_fn, dx_local, mb_b,
            (stage == 0) & bwd_valid, zeros_x,
        )
        dparams_dec = _entry_vjp(
            params, dec_entry_fn, dx_local, mb_b,
            at_split & bwd_valid, zeros_x,
        )

        # ring crossover at the split: the accumulated mem cotangent is
        # the encoder output's cotangent — it becomes the dx riding
        # into the encoder stages; the mem channel resets below
        dx_out = _where_tree(at_split, dmem_total, dx_local)
        dmem_out = _where_tree(
            at_split, jax.tree.map(jnp.zeros_like, dmem_total),
            dmem_total,
        )

        grads = jax.tree.map(
            lambda g, a, b, c_, d: g + a + b + c_ + d,
            grads, dparams_stage, dparams_head, dparams_enc, dparams_dec,
        )
        losses = losses.at[mb_c].add(
            jnp.where(is_exit & bwd_valid, loss_m, 0.0)
        )

        fwd_x, bwd_x = send_forward_recv_backward(
            y, dx_out, axis_name
        )
        fwd_mem, bwd_mem = send_forward_recv_backward(
            mem_in, dmem_out, axis_name
        )
        return ({"x": fwd_x, "mem": fwd_mem},
                {"x": bwd_x, "mem": bwd_mem},
                buffer, grads, losses), None

    (_, _, _, grads, losses), _ = lax.scan(
        tick,
        (dict(zeros_pair), dict(zeros_pair), buffer0, grads0, losses0),
        jnp.arange(ticks),
    )
    losses = lax.psum(losses, axis_name)
    return losses, grads


def forward_backward_no_pipelining(
    first_fn: Callable,
    stage_fn: Callable,
    last_fn: Callable,
    microbatches: Any,
    *,
    remat: bool = True,
) -> jnp.ndarray:
    """Sequential microbatch loop, no pipeline axis involved
    (reference: fwd_bwd_no_pipelining.py:29-91 — its grad-sync context
    manager is unnecessary here: grads of a scanned loss accumulate by
    construction)."""
    body = stage_fn
    if remat:
        body = jax.checkpoint(stage_fn)

    def one(mb):
        return last_fn(body(first_fn(mb)), mb)

    def step(carry, mb):
        return carry, one(mb)

    _, results = lax.scan(step, (), microbatches)
    return results


def forward_backward_pipelining_without_interleaving(
    first_fn: Callable,
    stage_fn: Callable,
    last_fn: Callable,
    microbatches: Any,
    *,
    axis_name: str = PIPELINE_PARALLEL_AXIS,
    remat: bool = True,
) -> jnp.ndarray:
    """Reference-parity name for :func:`pipeline`
    (reference: fwd_bwd_pipelining_without_interleaving.py:22-170)."""
    return pipeline(
        first_fn, stage_fn, last_fn, microbatches,
        axis_name=axis_name, remat=remat,
    )


def forward_backward_pipelining_with_interleaving(
    first_fn: Callable,
    chunk_fn: Callable,
    last_fn: Callable,
    microbatches: Any,
    num_model_chunks: int,
    *,
    axis_name: str = PIPELINE_PARALLEL_AXIS,
    remat: bool = True,
) -> jnp.ndarray:
    """Interleaved (virtual-pipeline) schedule, compiled
    (reference: fwd_bwd_pipelining_with_interleaving.py:22-308).

    Each rank holds ``num_model_chunks`` model chunks; chunk v of rank p
    is global stage ``v*pp + p``, and a microbatch rides the ring V
    times.  One tick = one *chunk* application per rank, so the fill
    bubble is ``(pp-1)`` chunk-times — V× smaller than the
    non-interleaved schedule's, which is the entire point of virtual
    pipelining.  Groups of ``pp`` microbatches cycle in flight;
    ``num_microbatches`` must divide by pp (same restriction as the
    reference, fwd_bwd_pipelining_with_interleaving.py asserts it).

    - ``chunk_fn(x, v)``: apply model chunk ``v`` (a traced index —
      select chunk params with ``lax.dynamic_index_in_dim``).
    - ``first_fn`` / ``last_fn`` / ``microbatches`` as in
      :func:`pipeline`.
    Returns per-microbatch ``last_fn`` results, replicated over pp.
    """
    pp = jax.lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    V = num_model_chunks
    num_micro = jax.tree.leaves(microbatches)[0].shape[0]
    if num_micro % pp:
        raise ValueError(
            f"number of microbatches ({num_micro}) is not divisible by "
            f"pipeline-parallel size ({pp}) as required by the "
            "interleaved schedule"
        )
    ticks = num_micro * V + pp - 1

    mb0 = _index_microbatch(microbatches, 0)
    zeros_state = _ensure_varying(
        jax.tree.map(lambda a: a * 0, first_fn(mb0)), axis_name
    )

    body = chunk_fn
    if remat:
        body = jax.checkpoint(chunk_fn)

    # exit activations stash (see `pipeline`): the LM head runs exactly
    # num_micro times after the ring scan instead of once per tick
    stash0 = _make_stash(zeros_state, num_micro)

    def tick(carry, t):
        state, stash = carry
        # schedule coordinates: rank p at tick t handles microbatch
        # g*pp + m on chunk v, where t - p = g*(V*pp) + v*pp + m
        tau = t - rank
        phase = jnp.maximum(tau, 0)
        m = phase % pp
        v = (phase % (V * pp)) // pp
        g = phase // (V * pp)
        mb = g * pp + m
        mb_c = jnp.clip(mb, 0, num_micro - 1)
        mb_in = _index_microbatch(microbatches, mb_c)

        entry = first_fn(mb_in)
        is_entry = (rank == 0) & (v == 0)
        x = _where_tree(is_entry, entry, state)
        y = body(x, v)

        is_exit = (rank == pp - 1) & (v == V - 1) & (tau >= 0) & (
            mb < num_micro
        )
        stash = _stash_add(stash, y, mb_c, is_exit)

        state = send_forward(y, axis_name)
        return (state, stash), None

    (_, stash), _ = lax.scan(
        tick, (zeros_state, stash0), jnp.arange(ticks)
    )
    # only the exit stage stashed real activations
    return _head_pass(last_fn, stash, microbatches, rank == pp - 1,
                      axis_name)


def _fwd_bwd_no_pipelining(
    first_fn: Callable,
    stage_fn: Callable,
    last_fn: Callable,
    params: Any,
    microbatches: Any,
    *,
    remat: bool = True,
) -> tuple:
    """No-pipelining schedule in the dispatched ``(losses, grads)``
    contract (reference: fwd_bwd_no_pipelining.py:29-91): sequential
    microbatch scan, grads of the mean loss pulled through one vjp.

    Params are cast varying over the data axes first, so the grads are
    shard-local contributions — the SAME dp convention as
    :func:`pipeline_1f1b` (without the cast, autodiff would psum over
    dp for dp-invariant params, making the dispatched pp=1 grads dp×
    larger than the pp>1 ones under the callers' shared pmean)."""
    body = jax.checkpoint(stage_fn) if remat else stage_fn
    params = _cast_varying(params, _vma_union(microbatches))

    def losses_of(prm):
        def step(carry, mb):
            return carry, last_fn(prm, body(prm, first_fn(prm, mb)), mb)

        _, res = lax.scan(step, (), microbatches)
        return res

    losses, vjp = jax.vjp(losses_of, params)
    n = losses.shape[0]
    # seed built from losses itself so it carries the same
    # varying-mesh-axes type (plain constants are mesh-invariant)
    (grads,) = vjp(losses * 0 + jnp.asarray(1.0 / n, losses.dtype))
    return losses, grads


def _fwd_bwd_encdec(
    enc_entry_fn: Callable,
    enc_stage_fn: Callable,
    dec_entry_fn: Callable,
    dec_stage_fn: Callable,
    last_fn: Callable,
    params: Any,
    microbatches: Any,
    split_stage: int,
    *,
    axis_name: str = PIPELINE_PARALLEL_AXIS,
    remat: bool = True,
    fused_stage_fn: Optional[Callable] = None,
) -> tuple:
    """Encoder-decoder pipeline in the dispatched ``(losses, grads)``
    contract.  The two-stream fallback is :func:`pipeline_encdec`
    differentiated through one vjp (GPipe-memory, matching the
    reference's non-interleaved enc-dec scheduling,
    schedules/common.py:18-108); the fused route below runs TRUE
    enc-dec 1F1B.  Params are cast varying over the data axes so grads
    are shard-local, the family's shared dp convention
    (see :func:`_fwd_bwd_no_pipelining`).

    ``fused_stage_fn(params, x, mem, stage)``, if given, routes through
    the fused one-body-per-tick family — :func:`pipeline_encdec_fused_
    1f1b`, true 1F1B memory (O(pp) saved stage-input pairs instead of
    the vjp-through-GPipe tape); ``enc_stage_fn``/``dec_stage_fn`` are
    then ignored (pass ``None``), and so is ``remat`` — the 1F1B
    schedule ALWAYS recomputes stage activations from its saved stage
    inputs (per-stage remat is the schedule's memory contract, not an
    option; any ``jax.checkpoint`` INSIDE the model's stage body still
    applies).  The two-stream fallback below keeps GPipe-memory vjp
    semantics."""
    if fused_stage_fn is not None:
        return pipeline_encdec_fused_1f1b(
            enc_entry_fn, dec_entry_fn, fused_stage_fn, last_fn,
            params, microbatches, split_stage, axis_name=axis_name,
        )
    params = _cast_varying(params, _vma_union(microbatches))

    def losses_of(prm):
        return pipeline_encdec(
            lambda mb: enc_entry_fn(prm, mb),
            lambda x: enc_stage_fn(prm, x),
            lambda mb: dec_entry_fn(prm, mb),
            lambda x, mem: dec_stage_fn(prm, x, mem),
            lambda y, mb: last_fn(prm, y, mb),
            microbatches, split_stage,
            axis_name=axis_name, remat=remat,
        )

    losses, vjp = jax.vjp(losses_of, params)
    n = losses.shape[0]
    # seed built from losses itself so it carries the same
    # varying-mesh-axes type (plain constants are mesh-invariant)
    (grads,) = vjp(losses * 0 + jnp.asarray(1.0 / n, losses.dtype))
    return losses, grads


def get_forward_backward_func(
    virtual_pipeline_model_parallel_size: Optional[int] = None,
    pipeline_model_parallel_size: int = 1,
    model_type: Optional[Any] = None,
):
    """(reference: schedules/__init__.py:1-39 + ModelType routing in
    schedules/common.py:18-108)

    Every dispatched callable shares ONE contract, the 1F1B family's —
    ``fn(first_fn, stage_fn, last_fn, params, microbatches, **kw)``
    returning ``(losses, grads)`` where ``losses`` is the (M,)
    per-microbatch losses and ``grads`` is ``d(mean losses)/d params``
    — and every stage/entry/exit function takes ``params`` explicitly
    (``first_fn(params, mb)``, ``stage_fn(params, x)``,
    ``last_fn(params, y, mb)``), exactly as the reference's dispatcher
    always hands out a forward-backward function (not a forward-only
    one, schedules/__init__.py:1-39):

    - pp == 1 → sequential scan + vjp (:func:`_fwd_bwd_no_pipelining`);
    - pp > 1 → :func:`pipeline_1f1b` — the production schedule, O(pp)
      activation memory;
    - pp > 1 with ``virtual_pipeline_model_parallel_size`` → the
      interleaved :func:`pipeline_1f1b_interleaved` with
      ``num_model_chunks`` pre-bound; ``stage_fn`` is then called as
      ``stage_fn(params, x, chunk_idx)`` (select chunk params with
      ``lax.dynamic_index_in_dim``);
    - ``model_type=ModelType.encoder_and_decoder`` and pp > 1 → the
      enc-dec schedule pre-bound to the installed
      ``pipeline_model_parallel_split_rank``; its signature is
      ``fn(enc_entry_fn, enc_stage_fn, dec_entry_fn, dec_stage_fn,
      last_fn, params, microbatches, **kw)``.  Pass
      ``fused_stage_fn=...`` to run the fused one-body-per-tick
      schedule with true 1F1B memory
      (:func:`pipeline_encdec_fused_1f1b` — what
      ``T5Model(fused_pipeline=True)`` does); without it the
      two-stream GPipe-vjp fallback runs.

    Apply ``sync_replicated_grads`` to the returned grads for shared
    (pp-replicated) params, as with :func:`pipeline_1f1b`.  The GPipe
    forward-only schedules (:func:`pipeline`,
    :func:`forward_backward_pipelining_without_interleaving`, …) stay
    available as explicit opt-ins for differentiate-from-outside use.
    """
    from apex_tpu.transformer.enums import ModelType

    if (
        model_type == ModelType.encoder_and_decoder
        and pipeline_model_parallel_size <= 1
    ):
        raise ValueError(
            "ModelType.encoder_and_decoder has no no-pipelining schedule "
            "(the sequential path is the model's own loss, e.g. "
            "T5Model.loss); use pipeline_model_parallel_size > 1"
        )
    if pipeline_model_parallel_size > 1:
        import functools

        if model_type == ModelType.encoder_and_decoder:
            if virtual_pipeline_model_parallel_size is not None:
                raise ValueError(
                    "encoder_and_decoder pipelines do not support virtual "
                    "(interleaved) pipeline stages"
                )
            from apex_tpu.transformer import parallel_state

            split = parallel_state.get_pipeline_model_parallel_split_rank()
            if split is None:
                raise RuntimeError(
                    "ModelType.encoder_and_decoder needs "
                    "pipeline_model_parallel_split_rank_ at "
                    "initialize_model_parallel time"
                )
            return functools.partial(_fwd_bwd_encdec, split_stage=split)
        if virtual_pipeline_model_parallel_size is not None:
            return functools.partial(
                pipeline_1f1b_interleaved,
                num_model_chunks=virtual_pipeline_model_parallel_size,
            )
        return pipeline_1f1b
    if virtual_pipeline_model_parallel_size is not None:
        raise ValueError(
            "virtual (interleaved) pipeline stages need "
            "pipeline_model_parallel_size > 1 — with pp == 1 the chunked "
            "params/stage_fn contract has no schedule to run on"
        )
    return _fwd_bwd_no_pipelining
