"""Fused optimizer tail: ONE multi-tensor pass over bucketed buffers.

PROFILE_r05.json (a chip run before PR 1) puts the flagship's optimizer
tail at 41.32 − 29.47 = 11.85 ms, ~440 GB/s against the chip's
~819 GB/s paper bandwidth (6.35 ms ideal), the single biggest non-attention step-time hole left.  The gap
is pass structure, not math: the seed chain runs the scaler's unscale
as its own read+write over every gradient (``amp/scaler.py``), a
separate finiteness reduction, and then the per-leaf ``upd`` chain in
``fused_adam.py`` — hundreds of small fused loops whose launch padding
and re-reads XLA does not collapse across the pytree.  The fused tail
makes the single-pass structure explicit, the way the reference's
``multi_tensor_apply`` kernels did for CUDA launches:

- the optimizer STATE (moments, fp32 masters) lives as the PR 4 bucket
  plans' contiguous single-dtype flat buffers
  (:class:`~apex_tpu.parallel.overlap.GradientBuckets`, ``dtype=f32``),
  keyed ``bucket_000``... — no per-step pack/unpack of state;
- one step reads the gradients exactly once (folding the scaler's
  unscale and the finiteness check into that same read —
  ``FusedOptimizer.step_scaled``), runs
  unscale → global-norm clip → moment update → master→model-dtype cast
  as one elementwise chain, and writes params/moments once — into the
  contiguous buffers (XLA fuses the concatenate into the buffer
  write, so the packing costs no extra pass);
- numerics are BIT-IDENTICAL to the per-leaf chain at default settings
  (test-enforced).  The elementwise math is evaluated on per-LEAF
  views of the buffers, in the leaves' own shapes: identical formulas
  in identical loop shapes resolve backend FMA-contraction choices
  identically (a bucket-shaped loop measurably drifts by 1 ulp on
  some hosts), norms reduce in the per-leaf order, and the unscale
  reproduces the seed's intermediate downcast to the grad dtype.  So
  ``fused_tail=True`` is a pure layout change until the opt-in
  sub-fp32 second-moment mode (``exp_avg_sq_dtype=jnp.bfloat16``) is
  engaged.

The scheduling argument is the operation-fusion one ("LLM Inference
Acceleration via Efficient Operation Fusion", PAPERS.md): elementwise
chains are bandwidth-bound, so every extra pass over params+grads+
moments is pure wall time; collapsing them targets the measured
11.85 → 6.35 ms gap directly.  ``tools/kernel_validation.py
validate_opt_tail`` gates the fused pass against the
``optimization_barrier``-unfused reference chain on real hardware and
records the achieved GB/s.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from apex_tpu.parallel.overlap import DEFAULT_BUCKET_BYTES, GradientBuckets
from apex_tpu.telemetry import events as _events

__all__ = [
    "TailContext",
    "tail_plan",
    "pack_tree",
    "fold_grads",
    "unpack_bufs",
    "time_opt_tail",
]


def tail_plan(params: Any,
              bucket_bytes: int = DEFAULT_BUCKET_BYTES) -> GradientBuckets:
    """The bucket plan the fused tail packs state into: contiguous
    single-dtype (fp32) buffers in reverse tree order, deterministic
    from (leaf shapes, bucket_bytes) — the same
    :class:`GradientBuckets` contract the overlapped gradient sync
    uses, so a host-built plan and a trace-time one always agree."""
    return GradientBuckets.for_tree(params, bucket_bytes,
                                    dtype=jnp.float32)


def pack_tree(plan: GradientBuckets, leaves: Sequence[Any],
              dtype: Any = jnp.float32) -> Dict[str, jnp.ndarray]:
    """Pack leaves (flatten order) into the plan's named flat buffers."""
    bufs = plan.pack([jnp.asarray(l).astype(dtype) for l in leaves])
    return dict(zip(plan.names, bufs))


def fold_grads(
    leaves: Sequence[Any],
    inv_scale: Optional[jnp.ndarray] = None,
):
    """Per-leaf fp32 gradients with the scaler's unscale and the
    finiteness check folded into the same single read — no packing
    (grads are inputs; only the STATE lives in buffers).

    Bit-compat contract: the finiteness flag checks the INCOMING
    (still-scaled) values — the seed order, ``all_finite`` before
    ``scale_gradients`` — and the unscale reproduces the seed's
    round-trip through the gradient's own dtype
    (``amp.scaler.unscale`` returns grad-dtype values that the
    optimizer re-casts to fp32), so folding changes no bits.

    Returns ``(per_leaf_fp32_list, all_finite_scalar)``."""
    flags = []
    out: List[jnp.ndarray] = []
    for leaf in leaves:
        g = jnp.asarray(leaf)
        gf = g.astype(jnp.float32)
        if g.size:
            flags.append(jnp.all(jnp.isfinite(gf)))
        if inv_scale is not None:
            gf = (gf * inv_scale).astype(g.dtype).astype(jnp.float32)
        out.append(gf)
    finite = (jnp.stack(flags).all() if flags else jnp.bool_(True))
    return out, finite


def unpack_bufs(plan: GradientBuckets, bufs: Dict[str, jnp.ndarray],
                like: Sequence[Any]) -> List[Any]:
    """Slice named buffers back into leaves shaped/typed like ``like``."""
    return plan.unpack([bufs[n] for n in plan.names], like)


@dataclasses.dataclass
class TailContext:
    """What a ``_tail_update`` hook works with: the plan, the leaf
    shapes, and the view/pack pair between buffers and leaves.

    ``views`` slices each leaf back out of the packed buffers AND
    reshapes it to the leaf's original shape; ``pack_views`` is the
    inverse (concatenate per bucket).  XLA cancels a concat/slice
    pair, and evaluating the elementwise math in the LEAF shapes keeps
    loop shapes — hence backend FMA-contraction choices, hence bits —
    identical to the per-leaf chain's."""

    plan: GradientBuckets
    shapes: tuple

    def views(self, bufs: Dict[str, jnp.ndarray]) -> List[jnp.ndarray]:
        out: List[Any] = [None] * self.plan.n_leaves
        for b, name in zip(self.plan.buckets, self.plan.names):
            buf, off = bufs[name], 0
            for i, size in zip(b.leaf_ids, b.sizes):
                out[i] = buf[off:off + size].reshape(self.shapes[i])
                off += size
        return out

    def pack_views(self, views: Sequence[jnp.ndarray],
                   dtype: Any = jnp.float32) -> Dict[str, jnp.ndarray]:
        bufs = {}
        for b, name in zip(self.plan.buckets, self.plan.names):
            parts = [views[i].reshape(-1).astype(dtype)
                     for i in b.leaf_ids]
            bufs[name] = (parts[0] if len(parts) == 1
                          else jnp.concatenate(parts))
        return bufs

    def global_norm(self, views: Sequence[jnp.ndarray]) -> jnp.ndarray:
        """``multi_tensor_l2norm``'s exact order: per-leaf square sums
        (flatten order, zero-size leaves contributing their empty-sum
        0.0 exactly like the per-leaf path) stacked and summed, then
        one sqrt."""
        sq = [jnp.sum(jnp.square(v)) for v in views]
        if not sq:
            return jnp.float32(0.0)
        return jnp.sqrt(jnp.stack(sq).sum())


def emit_opt_tail_event(opt, plan: GradientBuckets, *,
                        unscale_folded: bool,
                        self_ms: Optional[float] = None,
                        gbs: Optional[float] = None) -> None:
    """Trace-time (or measurement-time) ``opt_tail`` telemetry event:
    static host fields only — free when no sink listens, and never a
    device sync.  ``self_ms``/``gbs`` are set by :func:`time_opt_tail`
    (a standalone dispatch CAN self-time; the in-step pass cannot
    without breaking the jit boundary, so its event carries the static
    shape of the pass and the measured numbers ride the validation/
    bench records)."""
    if not _events.have_sinks():
        return
    total = sum(b.size for b in plan.buckets)
    fields = dict(
        fused=True,
        buffers=len(plan.buckets),
        elements=int(total),
        buffer_bytes=int(total) * 4,
        moment_dtype=str(jnp.dtype(
            getattr(opt, "exp_avg_sq_dtype", jnp.float32)).name),
        master_weights=bool(getattr(opt, "master_weights", False)),
        unscale_folded=bool(unscale_folded),
    )
    if self_ms is not None:
        fields["self_ms"] = round(float(self_ms), 4)
    if gbs is not None:
        fields["gbs"] = round(float(gbs), 2)
    _events.emit("opt_tail", **fields)


def tail_traffic_bytes(params: Any, opt) -> int:
    """HBM bytes one fused tail step moves under the paper model: read
    grads + moments (+ master), write params + moments (+ master) —
    the denominator of the achieved-GB/s number
    (the 440-vs-819 GB/s framing above)."""
    total = 0
    master = bool(getattr(opt, "master_weights", False))
    v_itemsize = jnp.dtype(
        getattr(opt, "exp_avg_sq_dtype", jnp.float32)).itemsize
    for leaf in jax.tree.leaves(params):
        n = int(jnp.size(leaf))
        p_item = jnp.asarray(leaf).dtype.itemsize
        total += n * p_item          # read grads (grad dtype ~ param)
        total += n * p_item          # write params
        total += 2 * n * 4           # read+write exp_avg
        total += 2 * n * v_itemsize  # read+write exp_avg_sq
        if master:
            total += 2 * n * 4       # read+write fp32 master
        else:
            total += n * p_item      # read params
    return total


def time_opt_tail(opt, state, grads, params, inv_scale=None,
                  iters: int = 10, warmup: int = 2) -> dict:
    """Self-time the fused tail as a standalone dispatch: jit just the
    optimizer step, run it ``iters`` times, and emit the ``opt_tail``
    event with the measured ms + achieved GB/s.  Used by ``bench.py
    --child opttail`` and the tests; on-TPU gating lives in
    ``tools/kernel_validation.py validate_opt_tail``."""
    import time

    if inv_scale is None:
        fn = jax.jit(lambda s, g, p: opt.step(s, g, p))
        args = (state, grads, params)
    else:
        fn = jax.jit(lambda s, g, p, inv: opt.step_scaled(s, g, p, inv))
        args = (state, grads, params, jnp.float32(inv_scale))
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / iters * 1e3
    nbytes = tail_traffic_bytes(params, opt)
    gbs = nbytes / (ms * 1e-3) / 1e9 if ms > 0 else 0.0
    plan = tail_plan(params, getattr(opt, "bucket_bytes", None)
                     or DEFAULT_BUCKET_BYTES)
    emit_opt_tail_event(opt, plan, unscale_folded=inv_scale is not None,
                        self_ms=ms, gbs=gbs)
    return {"ms": ms, "bytes": nbytes, "gbs": gbs}
