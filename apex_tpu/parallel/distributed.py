"""Data-parallel gradient synchronization.

The reference DDP (reference: apex/parallel/distributed.py:129-640) does
four jobs: broadcast params at init, discover grad buckets in backward
order, allreduce buckets on side streams overlapped with backward, and
optionally keep flat allreduce buffers for amp.  Under SPMD:

- param broadcast   → params are replicated by sharding (``NamedSharding``
  with no 'dp' axis in the spec);
- flat buffers      → jit's problem, not ours;
- bucketing/streams → NOT automatic.  A single ``psum`` of the whole
  grad pytree issued AFTER the accumulation loop (the deferred
  ``Reducer`` pattern below) leaves XLA's latency-hiding scheduler no
  independent compute to hide the collective behind — the whole
  reduce latency is exposed.  The overlap the reference hand-built
  with side streams is restored by :mod:`apex_tpu.parallel.overlap`:
  ``overlap_grad_sync=True`` assembles size-targeted buckets in
  reverse-layer (backward-ready) order and, in the pipelined
  accumulate-and-reduce loop, issues microbatch *i*'s bucket reduces
  while microbatch *i+1*'s fwd/bwd computes, so the scheduler can emit
  async ``all-reduce-start``/``-done`` pairs with real compute between
  them.  ``bucket_bytes`` is the TPU analog of the reference's
  ``message_size``/``allreduce_communicators`` knobs; the trade
  (per-microbatch reduces cost K× the bytes of one deferred reduce,
  in exchange for hiding the latency) is documented in
  docs/distributed.md.  ``overlap_grad_sync=False`` (default) is the
  unchanged deferred path, and single-shot bucketed reduces at
  ``compression=None`` are bit-identical to the unbucketed ones
  (collectives are elementwise — packing changes no per-element
  summation order).

What survives as *semantics* are the knobs, reproduced here exactly:
``gradient_average`` (divide by world size), ``gradient_predivide_factor``
(divide by f before the reduce and by world/f after,
reference: distributed.py:463-476), and ``allreduce_always_fp32``.

Compressed collectives: with a hierarchical ``(dcn_axis, ici_axis)``
axis pair, ``compression="int8"`` block-quantizes the DCN leg of
the reduce (:mod:`apex_tpu.ops.quantization`): the ici-reduced chunk is
quantized once, exchanged over dcn as int8 values + per-block fp32
scales, dequantized once — by default the ICI reduce-scatter/
all-gather legs and the returned gradient dtype are untouched, and
``compression=None`` is bit-identical to the uncompressed path.
``CompressionConfig(ici_legs=True)`` additionally runs BOTH ICI legs
int8 (EQuARX's ICI half — ~4x fewer bytes on the fast links too,
chunk boundaries preserved so nothing else moves).  Error feedback
(on by default) carries the per-device quantization residual as
explicit state: build it with :func:`init_comm_state` (it sizes the
extra ``ici_push``/``ici_pull`` buffers from the config), thread it
through ``all_reduce_gradients(..., comm_state=...)`` (or the
``DistributedDataParallel``/``Reducer`` equivalents), and checkpoint it
with the rest of the training state.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.telemetry import events as _events

__all__ = [
    "data_parallel_mesh",
    "hierarchical_data_parallel_mesh",
    "all_reduce_gradients",
    "init_comm_state",
    "comm_state_specs",
    "DistributedDataParallel",
    "Reducer",
]


def data_parallel_mesh(
    devices: Optional[Sequence] = None, axis_name: str = "dp"
) -> Mesh:
    """A 1-D mesh over all (or the given) devices — the analog of the
    default NCCL world process group."""
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.asarray(devices), (axis_name,))


def hierarchical_data_parallel_mesh(
    ici_size: int,
    devices: Optional[Sequence] = None,
    dcn_axis: str = "dcn",
    ici_axis: str = "ici",
) -> Mesh:
    """A 2-D ("dcn", "ici") data-parallel mesh: ``ici_size`` devices per
    fast-interconnect group, the rest across the slow axis — the TPU
    analog of the reference's ``dwu_group_size`` intra/inter-group split
    (reference: apex/contrib/optimizers/distributed_fused_adam.py:115-116).
    Devices within a physical pod slice should be contiguous so the ici
    axis rides ICI links."""
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) % ici_size:
        raise ValueError(
            f"device count ({len(devices)}) not divisible by ici group "
            f"size ({ici_size})"
        )
    grid = np.asarray(devices).reshape(-1, ici_size)
    return Mesh(grid, (dcn_axis, ici_axis))


def _hierarchical_psum(g: jnp.ndarray, dcn_axis: str, ici_axis: str,
                       compression=None, residual=None, step=None,
                       key=None):
    """All-reduce over both data axes as RS(ici) → AR(dcn) → AG(ici):
    mathematically ``psum`` over (dcn, ici), but each DCN message is only
    1/ici of the tensor (the reference's 2-level reduce,
    distributed_fused_adam.py:106-160).

    With ``compression`` given, the AR(dcn) middle leg runs as an int8
    block-quantized all-reduce (:func:`apex_tpu.ops.quantization.
    quantized_psum`) — by default the ICI legs and the output dtype are
    untouched, and ``compression=None`` takes the exact uncompressed
    path.  With ``compression.ici_legs`` the RS/AG legs ALSO go int8
    (EQuARX's ICI half): :func:`~apex_tpu.ops.quantization.
    quantized_reduce_scatter` replaces the full-width ``psum_scatter``
    (chunk boundaries preserved, so the dcn leg and its residual sizes
    are unchanged) and :func:`~apex_tpu.ops.quantization.
    quantized_all_gather` replaces the gather, each with its own
    error-feedback buffer (``ici_push``/``ici_pull`` in the residual
    dict).  Returns ``(out, new_residual)``; ``new_residual`` is None
    unless an error-feedback ``residual`` dict was passed."""
    from apex_tpu.transformer.tensor_parallel.mappings import (
        all_gather_invariant,
    )

    n = g.size
    ici = jax.lax.axis_size(ici_axis)
    flat = g.reshape(-1)
    pad = (-n) % ici
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    ici_legs = compression is not None and compression.ici_legs
    if ici_legs and residual is not None and "ici_push" not in residual:
        raise ValueError(
            "compression.ici_legs=True but the comm state has no "
            "ici_push/ici_pull residuals: rebuild it with "
            "init_comm_state(..., compression=<the ici_legs config>)"
        )
    if not ici_legs and residual is not None and "ici_push" in residual:
        # the opposite mismatch would silently DROP the ici residuals
        # from the returned state (an opaque out_specs/pytree error at
        # best) — refuse with the same rebuild message
        raise ValueError(
            "the comm state carries ici_push/ici_pull residuals but "
            "compression.ici_legs is False: rebuild it with "
            "init_comm_state(..., compression=<this config>) or turn "
            "ici_legs back on"
        )
    # one base dither key per (leaf, step), decorrelated per leg —
    # sharing the caller's key across the three quantization sites
    # would re-roll the same noise on different data
    leg_key = lambda i: None
    if compression is not None and compression.rounding == "stochastic":
        base = key
        if base is None and step is not None:
            import jax as _jax

            base = _jax.random.fold_in(_jax.random.PRNGKey(0), step)
        if base is not None:
            import jax as _jax

            leg_key = lambda i: _jax.random.fold_in(base, i)
    new_residual = None
    new_ici_push = new_ici_pull = None
    if ici_legs:
        from apex_tpu.ops.quantization import quantized_reduce_scatter

        chunk, new_ici_push = quantized_reduce_scatter(
            flat.astype(jnp.float32), ici_axis, compression,
            residual=None if residual is None else residual["ici_push"],
            step=step, key=leg_key(1),
        )
    else:
        chunk = jax.lax.psum_scatter(flat, ici_axis, tiled=True)
    if compression is None:
        chunk = jax.lax.psum(chunk, dcn_axis)
    else:
        from apex_tpu.ops.quantization import quantized_psum

        dcn_residual = None
        if residual is not None:
            dcn_residual = {"push": residual["push"],
                            "pull": residual["pull"]}
        chunk, new_dcn = quantized_psum(
            chunk, dcn_axis, compression, residual=dcn_residual,
            step=step, key=leg_key(0) if ici_legs else key,
        )
        if residual is not None:
            new_residual = dict(new_dcn)
    if ici_legs:
        from apex_tpu.ops.quantization import quantized_all_gather

        out, new_ici_pull = quantized_all_gather(
            chunk.astype(jnp.float32), ici_axis, compression,
            residual=None if residual is None else residual["ici_pull"],
            step=step, key=leg_key(2),
        )
        out = out.astype(flat.dtype)
    else:
        # invariant-typed gather: every ici rank receives the identical
        # dcn-reduced chunk, so the result is replicated over both data
        # axes and downstream P() out_specs typecheck (same HLO either
        # way)
        out = all_gather_invariant(chunk, ici_axis, axis=0, tiled=True)
    if new_residual is not None and new_ici_push is not None:
        new_residual["ici_push"] = new_ici_push
        new_residual["ici_pull"] = new_ici_pull
    if pad:
        out = out[:n]
    return out.reshape(g.shape), new_residual


def all_reduce_gradients(
    grads: Any,
    axis_name: Any = "dp",
    gradient_average: bool = True,
    gradient_predivide_factor: float = 1.0,
    allreduce_always_fp32: bool = False,
    compression: Any = None,
    comm_state: Optional[dict] = None,
    overlap_grad_sync: bool = False,
    bucket_bytes: Optional[int] = None,
) -> Any:
    """psum the grad pytree over ``axis_name`` (call inside shard_map/pmap).

    ``axis_name`` may also be a nested ``(dcn_axis, ici_axis)`` pair: the
    all-reduce is then decomposed into reduce-scatter within ici,
    all-reduce across dcn and all-gather within ici, so only 1/ici of the
    gradient bytes cross the slow interconnect (the reference's 2-level
    hierarchy, apex/contrib/optimizers/distributed_fused_adam.py:106-160).

    ``compression`` (None | "int8" |
    :class:`~apex_tpu.ops.quantization.CompressionConfig`) additionally
    quantizes the DCN leg of the hierarchical pair to int8 + per-block
    fp32 scales; it requires a hierarchical ``axis_name``, leaves the
    ICI legs and gradient dtypes untouched, and ``None`` is
    bit-identical to the uncompressed reduce.  With error feedback (the
    config default) pass ``comm_state`` (from :func:`init_comm_state`);
    the call then returns ``(grads, new_comm_state)`` instead of just
    ``grads`` — thread the new state into the next step and checkpoint
    it with the training state.

    ``overlap_grad_sync=True`` reduces size-targeted BUCKETS of leaves
    (reverse-layer order, ``bucket_bytes`` per bucket — see
    :mod:`apex_tpu.parallel.overlap`) instead of one collective per
    leaf, giving the scheduler separately-overlappable collectives; at
    ``compression=None`` the result is bit-identical to the unbucketed
    reduce.  With compression the ``comm_state`` must then be BUCKETED
    too: build it with ``init_comm_state(..., bucket_bytes=...)`` using
    the same bucket size and leaf dtypes.

    Matches the reference's scaling semantics
    (reference: apex/parallel/distributed.py:463-476): grads are divided
    by ``predivide_factor`` before the reduction and by
    ``world_size / predivide_factor`` after, which in exact arithmetic is
    a mean over the axis but controls intermediate magnitude in fp16.
    """
    from apex_tpu.ops.quantization import as_compression_config

    cfg = as_compression_config(compression)
    hierarchical = isinstance(axis_name, (tuple, list))
    if cfg is not None and not hierarchical:
        raise ValueError(
            "compression quantizes the DCN leg of a hierarchical "
            "reduce: pass axis_name=(dcn_axis, ici_axis)"
        )
    if cfg is not None and comm_state is None and (
        cfg.error_feedback or cfg.rounding == "stochastic"
    ):
        raise ValueError(
            "this compression config needs explicit comm state (error "
            "feedback carries residuals; stochastic rounding derives "
            "its per-step key from the state's counter): build it with "
            "init_comm_state(...) and pass comm_state="
        )
    if comm_state is not None and cfg is None:
        raise ValueError("comm_state given without compression")
    from apex_tpu.parallel.overlap import is_bucketed_residuals

    bucketed_state = comm_state is not None and is_bucketed_residuals(
        comm_state["residuals"]
    )
    if bucketed_state and not overlap_grad_sync:
        raise ValueError(
            "comm state was built with bucket_bytes= (per-bucket "
            "residuals): pass overlap_grad_sync=True"
        )
    if overlap_grad_sync and comm_state is not None \
            and not bucketed_state:
        raise ValueError(
            "overlap_grad_sync with compression needs a BUCKETED "
            "comm state: build it with init_comm_state(..., "
            "bucket_bytes=<the same bucket size>)"
        )
    if hierarchical:
        dcn_axis, ici_axis = axis_name
        world = jax.lax.axis_size(dcn_axis) * jax.lax.axis_size(ici_axis)
    else:
        world = jax.lax.axis_size(axis_name)

    step = None if comm_state is None else comm_state["step"]

    from apex_tpu.telemetry.spans import phase as _phase

    def sync(g, residual, key):
        # tlm.grad_sync: every collective this reduce issues carries
        # the phase in its HLO metadata, so xprof segments the step's
        # comm time from its compute (docs/observability.md)
        with _phase("grad_sync"):
            orig_dtype = g.dtype
            if allreduce_always_fp32:
                g = g.astype(jnp.float32)
            if gradient_predivide_factor != 1.0:
                g = g / gradient_predivide_factor
            if hierarchical:
                g, new_residual = _hierarchical_psum(
                    g, dcn_axis, ici_axis, compression=cfg,
                    residual=residual, step=step, key=key,
                )
            else:
                g = jax.lax.psum(g, axis_name)
                new_residual = None
            if gradient_average:
                post = world / gradient_predivide_factor
                if post != 1.0:
                    g = g / post
            elif gradient_predivide_factor != 1.0:
                g = g * gradient_predivide_factor
            return g.astype(orig_dtype), new_residual

    from apex_tpu.parallel.overlap import dither_key

    def leaf_key(i):
        return dither_key(cfg, step, i)

    leaves, treedef = jax.tree_util.tree_flatten(grads)

    if overlap_grad_sync:
        from apex_tpu.parallel.overlap import (
            DEFAULT_BUCKET_BYTES,
            GradientBuckets,
            reduce_bucketed,
        )

        plan = GradientBuckets.for_tree(
            grads,
            DEFAULT_BUCKET_BYTES if bucket_bytes is None
            else bucket_bytes,  # 0 reaches the >=1 validation, not
        )                       # the default
        emit_bucket_comm_events(plan, axis_name, cfg,
                                where="all_reduce_gradients")
        bufs = plan.pack(leaves)
        if comm_state is None:
            out, _ = reduce_bucketed(plan, bufs, cfg, None, None, sync)
            return jax.tree_util.tree_unflatten(
                treedef, plan.unpack(out, leaves)
            )
        _check_bucketed_state(plan, comm_state, cfg, dcn_axis, ici_axis)
        out_bufs, new_residuals = reduce_bucketed(
            plan, bufs, cfg, comm_state["residuals"], step, sync
        )
        return jax.tree_util.tree_unflatten(
            treedef, plan.unpack(out_bufs, leaves)
        ), {"residuals": new_residuals, "step": comm_state["step"] + 1}

    if comm_state is None:
        out = [sync(g, None, None)[0] for g in leaves]
        return jax.tree_util.tree_unflatten(treedef, out)
    residuals = treedef.flatten_up_to(comm_state["residuals"])
    use_ef = cfg.error_feedback
    synced = [
        sync(g, r if use_ef else None, leaf_key(i))
        for i, (g, r) in enumerate(zip(leaves, residuals))
    ]
    new_state = {
        # error_feedback=False: the state only feeds the step counter,
        # residuals pass through untouched
        "residuals": jax.tree_util.tree_unflatten(
            treedef, [r for _, r in synced]
        ) if use_ef else comm_state["residuals"],
        "step": comm_state["step"] + 1,
    }
    return jax.tree_util.tree_unflatten(
        treedef, [g for g, _ in synced]
    ), new_state


def emit_bucket_comm_events(plan, axis_name, cfg, where: str) -> None:
    """Trace-time telemetry for a bucketed reduce: one ``comm_bucket``
    event per bucket, carrying per-leg bytes-on-wire ESTIMATES under
    the ring model (:func:`apex_tpu.telemetry.events.ring_wire_bytes`
    — the same formulas ``tools/comm_audit.py`` applies to parsed HLO;
    the audit's measured JSON stays the ground truth, these events are
    the live stream's cheap approximation of it).

    Fires while the step is being TRACED — once per compile, with every
    field a static host int — so the compiled program and the step's
    wall time are untouched.  Free when no telemetry sink is
    registered."""
    if not _events.have_sinks():
        return
    from apex_tpu.telemetry.events import ring_wire_bytes

    hierarchical = isinstance(axis_name, (tuple, list))
    if hierarchical:
        dcn_axis, ici_axis = axis_name
        dcn, ici = jax.lax.axis_size(dcn_axis), jax.lax.axis_size(ici_axis)
    else:
        world = jax.lax.axis_size(axis_name)
    for name, b in zip(plan.names, plan.buckets):
        itemsize = int(np.dtype(b.dtype).itemsize)
        fields = {
            "where": where,
            "bucket": name,
            "elements": int(b.size),
            "dtype": str(np.dtype(b.dtype).name),
            "bytes": int(b.size) * itemsize,
            "compression": (cfg.method if cfg is not None else "none"),
        }
        if hierarchical:
            # the reduce's actual decomposition: RS(ici) -> AR(dcn,
            # int8-quantized when compressed) -> AG(ici), over the
            # ici-padded flat buffer (see _hierarchical_psum)
            padded = b.size + (-b.size) % ici
            chunk = padded // ici
            padded_bytes = padded * itemsize
            if cfg is None:
                ar_payload = chunk * itemsize
            else:
                # int8 values + one fp32 scale per block (block-padded)
                qpad = chunk + (-chunk) % cfg.block_size
                ar_payload = qpad + (qpad // cfg.block_size) * 4
            if cfg is not None and cfg.ici_legs:
                # int8 legs: values at 1 byte + the per-row scale
                # sidecar (one fp32 scale per block of each rank's
                # chunk — quantize_rows keeps blocks inside chunks)
                nb = max(-(-chunk // cfg.block_size), 1)
                leg_payload = padded + ici * nb * 4
                rs_bytes, ag_bytes = leg_payload, leg_payload
            else:
                rs_bytes, ag_bytes = padded_bytes, padded_bytes
            fields.update(
                dcn_size=int(dcn), ici_size=int(ici),
                ici_compressed=bool(cfg is not None and cfg.ici_legs),
                rs_ici_wire_bytes=round(
                    ring_wire_bytes("reduce-scatter", ici, rs_bytes)),
                ar_dcn_wire_bytes=round(
                    ring_wire_bytes("all-reduce", dcn, ar_payload)),
                ag_ici_wire_bytes=round(
                    ring_wire_bytes("all-gather", ici, ag_bytes,
                                    result_bytes=ag_bytes)),
            )
        else:
            fields.update(
                world_size=int(world),
                ar_wire_bytes=round(
                    ring_wire_bytes("all-reduce", world,
                                    b.size * itemsize)),
            )
        _events.emit("comm_bucket", **fields)


def _check_bucketed_state(plan, comm_state, cfg, dcn_axis,
                          ici_axis) -> None:
    """Fail with an actionable message when the per-bucket residual
    sizes do not match the trace-time bucket plan (the shapes would
    otherwise error deep inside quantized_psum)."""
    from apex_tpu.ops.quantization import hierarchical_residual_sizes

    residuals = comm_state["residuals"]
    if set(residuals) != set(plan.names):
        raise ValueError(
            f"bucketed comm state has {len(residuals)} buckets, the "
            f"grads bucket into {len(plan.buckets)}: init_comm_state "
            "must use the same bucket_bytes and see the same leaf "
            "shapes/dtypes as the reduce"
        )
    if not cfg.error_feedback:
        return
    dcn, ici = jax.lax.axis_size(dcn_axis), jax.lax.axis_size(ici_axis)
    for name, b in zip(plan.names, plan.buckets):
        sizes = hierarchical_residual_sizes(
            b.size, dcn, ici, cfg.block_size, cfg.ici_legs
        )
        if set(sizes) != set(residuals[name]):
            raise ValueError(
                f"residual '{name}' has keys "
                f"{sorted(residuals[name])}, this compression config "
                f"needs {sorted(sizes)}: the comm state was built for "
                "a different config (ici_legs?) — rebuild with "
                "init_comm_state"
            )
        push = residuals[name]["push"]
        if push.size != sizes["push"]:
            raise ValueError(
                f"residual '{name}' has {push.size} elements, the "
                f"bucket's padded chunk is {sizes['push']}: "
                "init_comm_state must use the same bucket_bytes and "
                "leaf dtypes as the reduce"
            )


def init_comm_state(
    tree: Any,
    axis_name: Tuple[str, str],
    compression: Any = "int8",
    mesh: Optional[Mesh] = None,
    param_specs: Any = None,
    bucket_bytes: Optional[int] = None,
    buckets: Any = None,
) -> dict:
    """Zero error-feedback state for compressed hierarchical reduces of
    a grad pytree shaped like ``tree``.

    With ``bucket_bytes`` (or a prebuilt ``buckets`` plan) the state is
    sized for the BUCKETED reduce (``overlap_grad_sync=True``): one
    push/pull residual pair per bucket instead of per leaf, keyed
    ``bucket_000``... — pass the SAME bucket size the reduce will use
    (and, for model-sharded params, the same ``param_specs``) so the
    host-built plan matches the trace-time one.

    Residuals are sized from the PER-DEVICE gradient shapes the reduce
    will see inside shard_map.  For the usual DDP setup (replicated
    params, per-device grads of the same shape) that is simply the
    params pytree; params sharded over MODEL axes (pp/tp stacks) have
    smaller per-device leaves — pass their ``param_specs`` so the
    host-side path can divide each dimension by the mesh axes that
    shard it.

    With ``mesh`` given this runs host-side and returns GLOBAL arrays
    (place them with :func:`comm_state_specs`); without it, it must run
    inside ``shard_map`` (axis sizes come from the bound axes, leaf
    shapes are already local) and returns the per-device residuals
    directly.  The state is ordinary checkpointable data: save/restore
    it with the training state so a resumed run keeps its compensation
    instead of restarting the quantization bias from zero."""
    from apex_tpu.ops.quantization import (
        as_compression_config,
        hierarchical_residual_sizes,
    )

    cfg = as_compression_config(compression)
    if cfg is None:
        raise ValueError("init_comm_state needs a compression config")
    if bucket_bytes is not None or buckets is not None:
        from apex_tpu.parallel.overlap import (
            GradientBuckets,
            bucket_comm_state,
        )

        plan = buckets or GradientBuckets.for_tree(
            tree, bucket_bytes, param_specs=param_specs, mesh=mesh
        )
        return bucket_comm_state(plan, axis_name, cfg, mesh=mesh)
    dcn_axis, ici_axis = axis_name
    if mesh is not None:
        dcn, ici = mesh.shape[dcn_axis], mesh.shape[ici_axis]
        replicas = dcn * ici
    else:
        dcn, ici = jax.lax.axis_size(dcn_axis), jax.lax.axis_size(ici_axis)
        replicas = 1

    def local_size(leaf, spec) -> int:
        # the ONE per-device-shape derivation, shared with the bucket
        # plan builder so bucketed and per-leaf residual sizing can
        # never disagree about what "local" means
        from apex_tpu.parallel.overlap import _local_shape

        n = 1
        for d in _local_shape(leaf, spec, mesh):
            n *= int(d)
        return n

    def one(leaf, spec):
        sizes = hierarchical_residual_sizes(
            local_size(leaf, spec), dcn, ici, cfg.block_size,
            cfg.ici_legs,
        )
        # a leaf sharded over MODEL axes (pp/tp stacks) carries a
        # DISTINCT residual per model-axis position as well — the
        # global buffer must hold every one of them
        reps = replicas * _model_axis_extent(spec, mesh)
        return {
            k: jnp.zeros((reps * n,), jnp.float32)
            for k, n in sizes.items()
        }

    if param_specs is None:
        residuals = jax.tree.map(lambda l: one(l, None), tree)
    else:
        residuals = jax.tree.map(one, tree, param_specs)
    return {
        "residuals": residuals,
        "step": jnp.zeros((), jnp.int32),
    }


def _model_axis_extent(spec, mesh: Optional[Mesh]) -> int:
    """Product of the mesh-axis sizes a leaf's spec shards it over."""
    if spec is None or mesh is None:
        return 1
    from apex_tpu.transformer.parallel_state import spec_axis_names

    extent = 1
    for ax in spec_axis_names(spec):
        extent *= mesh.shape[ax]
    return extent


def comm_state_specs(comm_state: dict,
                     axis_name: Tuple[str, str],
                     param_specs: Any = None,
                     buckets: Any = None) -> dict:
    """shard_map / device_put specs for :func:`init_comm_state` output:
    residuals are device-varying over both data axes (sharded along
    axis 0), the step counter is replicated.

    Pass the same ``param_specs`` given to :func:`init_comm_state` when
    params are sharded over model axes: a pp/tp-sharded leaf's residual
    varies over those axes too, and declaring it replicated there would
    be rejected (or silently wrong) under shard_map.  For BUCKETED
    state over model-sharded params, pass the ``buckets`` plan (built
    with the same ``param_specs``/``mesh``) instead — each bucket's
    residual varies over the union of its member leaves' model axes."""
    from apex_tpu.parallel.overlap import is_bucketed_residuals

    dcn_axis, ici_axis = axis_name
    if is_bucketed_residuals(comm_state.get("residuals")):
        if buckets is not None:
            rs = {
                name: {
                    # key set follows the state (push/pull, plus the
                    # ici_push/ici_pull pair when ici_legs sized them)
                    k: P((dcn_axis, ici_axis, *b.model_axes))
                    for k in comm_state["residuals"][name]
                }
                for name, b in zip(buckets.names, buckets.buckets)
            }
        elif param_specs is not None:
            # silently emitting P((dcn, ici)) here would mis-shard
            # residuals whose buckets were sized with model-axis reps
            raise ValueError(
                "bucketed comm state over model-sharded params needs "
                "the bucket plan to spec each bucket's model axes: "
                "pass buckets=GradientBuckets.for_tree(params, "
                "bucket_bytes, param_specs=..., mesh=...) — the same "
                "plan init_comm_state used"
            )
        else:
            rs = jax.tree.map(
                lambda _: P((dcn_axis, ici_axis)),
                comm_state["residuals"],
            )
        return {"residuals": rs, "step": P()}
    if param_specs is None:
        specs = jax.tree.map(
            lambda _: P((dcn_axis, ici_axis)), comm_state
        )
        specs["step"] = P()
        return specs

    from apex_tpu.transformer.parallel_state import spec_axis_names

    def leaf_spec(spec, res):
        axes = (dcn_axis, ici_axis, *spec_axis_names(spec))
        return {k: P(axes) for k in res}

    return {
        "residuals": jax.tree.map(
            leaf_spec, param_specs, comm_state["residuals"],
            is_leaf=lambda x: isinstance(x, P),
        ),
        "step": P(),
    }


class DistributedDataParallel:
    """Configuration object for DP gradient sync.

    Use either as a callable on a grad pytree inside an SPMD context::

        ddp = DistributedDataParallel(axis_name="dp")
        grads = ddp(grads)          # inside shard_map

    or let it build the whole sharded value-and-grad for you::

        grad_fn = ddp.value_and_grad(loss_fn, mesh)
        (loss, grads) = grad_fn(params, batch)   # batch sharded over dp

    The constructor knobs mirror the reference's
    (reference: apex/parallel/distributed.py:139-206).  The reference's
    ``message_size``/stream knobs map to ``overlap_grad_sync=True`` +
    ``bucket_bytes`` (bucketed reduces the scheduler can overlap — see
    :mod:`apex_tpu.parallel.overlap`); the legacy spellings are still
    accepted-and-ignored for source compatibility.

    ``compression`` (with a hierarchical ``axis_name=(dcn, ici)``
    pair) quantizes the DCN leg of the reduce to int8; with error
    feedback (the default) build residual state once with
    :meth:`init_comm_state` and call ``ddp(grads, comm_state)``, which
    then returns ``(grads, new_comm_state)``.
    """

    def __init__(
        self,
        axis_name: str = "dp",
        gradient_average: bool = True,
        gradient_predivide_factor: float = 1.0,
        allreduce_always_fp32: bool = False,
        compression: Any = None,
        overlap_grad_sync: bool = False,
        bucket_bytes: Optional[int] = None,
        # accepted for source compat; meaningless under XLA:
        message_size: int = 10000000,
        delay_allreduce: bool = False,
        num_allreduce_streams: int = 1,
        retain_allreduce_buffers: bool = False,
    ):
        from apex_tpu.ops.quantization import as_compression_config
        from apex_tpu.parallel.overlap import DEFAULT_BUCKET_BYTES

        self.axis_name = axis_name
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.compression = as_compression_config(compression)
        self.overlap_grad_sync = overlap_grad_sync
        self.bucket_bytes = (DEFAULT_BUCKET_BYTES if bucket_bytes is None
                             else bucket_bytes)
        if self.bucket_bytes < 1:
            raise ValueError("bucket_bytes must be >= 1")
        if self.compression is not None and not isinstance(
            axis_name, (tuple, list)
        ):
            raise ValueError(
                "compression quantizes the DCN leg of a hierarchical "
                "reduce: pass axis_name=(dcn_axis, ici_axis)"
            )

    def __call__(self, grads: Any,
                 comm_state: Optional[dict] = None) -> Any:
        return all_reduce_gradients(
            grads,
            axis_name=self.axis_name,
            gradient_average=self.gradient_average,
            gradient_predivide_factor=self.gradient_predivide_factor,
            allreduce_always_fp32=self.allreduce_always_fp32,
            compression=self.compression,
            comm_state=comm_state,
            overlap_grad_sync=self.overlap_grad_sync,
            bucket_bytes=self.bucket_bytes,
        )

    def init_comm_state(self, params: Any,
                        mesh: Optional[Mesh] = None,
                        param_specs: Any = None) -> dict:
        """Zero error-feedback state for :meth:`__call__` — host-side
        global arrays with ``mesh`` given (place with
        :meth:`comm_state_specs`), per-device inside shard_map
        otherwise.  Pass ``param_specs`` when params are sharded over
        model axes so residuals are sized from per-device shapes.
        With ``overlap_grad_sync`` the state is bucketed to match, and
        the bucket plan is remembered so :meth:`comm_state_specs` can
        emit per-bucket model-axis specs without the caller rebuilding
        it."""
        if self.overlap_grad_sync:
            from apex_tpu.parallel.overlap import GradientBuckets

            self._bucket_plan = GradientBuckets.for_tree(
                params, self.bucket_bytes, param_specs=param_specs,
                mesh=mesh,
            )
            return init_comm_state(
                params, self.axis_name, self.compression, mesh=mesh,
                param_specs=param_specs, buckets=self._bucket_plan,
            )
        return init_comm_state(
            params, self.axis_name, self.compression, mesh=mesh,
            param_specs=param_specs,
        )

    def comm_state_specs(self, comm_state: dict,
                         param_specs: Any = None,
                         buckets: Any = None) -> dict:
        return comm_state_specs(
            comm_state, self.axis_name, param_specs=param_specs,
            buckets=buckets or getattr(self, "_bucket_plan", None),
        )

    def value_and_grad(
        self,
        loss_fn: Callable,
        mesh: Mesh,
        has_aux: bool = False,
    ) -> Callable:
        """Build ``(params, batch) -> (loss, grads)`` with params replicated,
        batch sharded over ``axis_name``, and grads synced."""
        from jax.sharding import PartitionSpec as P

        shard_map = jax.shard_map

        axis = self.axis_name

        def local_step(params, batch):
            out, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(
                params, batch
            )
            grads = self(grads)
            if has_aux:
                loss, aux = out
                return jax.lax.pmean(loss, axis), aux, grads
            return jax.lax.pmean(out, axis), grads

        batch_spec = P(axis)
        rep = P()
        out_specs = (rep, rep, rep) if has_aux else (rep, rep)
        return jax.jit(
            shard_map(
                local_step,
                mesh=mesh,
                in_specs=(rep, batch_spec),
                out_specs=out_specs,
                check_vma=False,
            )
        )


class Reducer:
    """Deferred, user-triggered gradient reduction — the functional
    analog of the reference's manual-control DDP alternative
    (reference: apex/parallel/distributed.py:89-126, whose point is
    that unlike DDP nothing syncs during backward; the user calls
    ``reduce()`` when ready, e.g. every K accumulation steps).

    Usage inside a shard_map'd step::

        red = Reducer(axis_name="dp")             # static config
        acc = red.init(params)                    # zeros pytree
        w_local = jax.lax.pcast(params, "dp", to="varying")  # see below
        for k in ...:                             # K times, NO collective
            acc = red.accumulate(acc, jax.grad(local_loss)(w_local, mb[k]))
        mean_grads, acc = red.reduce(acc)         # ONE psum-mean + reset

    The varying-cast is load-bearing: under shard_map, differentiating a
    device-LOCAL (varying) loss with respect to REPLICATED params makes
    JAX insert the reduction itself (the transpose of the replicated→
    varying broadcast is a psum), so "the local gradient before
    reduction" would not exist to defer.  Marking the params varying
    first keeps the per-device gradients local until ``reduce`` — which
    is the entire point of the reference's Reducer (delaying the
    allreduce across accumulation steps).

    Scaling semantics — a DELIBERATE DEVIATION from the reference: the
    reference's Reducer averages only over the world size
    (apex/parallel/distributed.py), returning the SUM over the K
    locally accumulated microbatches.  Here ``gradient_average=True``
    (default) also divides by K, yielding the mean gradient over
    (axis world x K local steps) — so the effective learning rate does
    not silently scale with the accumulation count.  Pass
    ``average_over_microbatches=False`` to reproduce the reference
    scaling exactly (mean over world, sum over K — what you want when
    porting a reference training recipe whose lr schedule was tuned
    against that convention); with ``gradient_average=False`` both
    flags yield the raw sum over both.  ``allreduce_always_fp32`` is
    accepted for signature parity but meaningless here — the
    accumulator is ALWAYS fp32 (see :meth:`init`), so the reduction
    already runs in fp32 regardless.

    ``overlap_grad_sync=True`` switches to the PIPELINED
    accumulate-and-reduce loop (:mod:`apex_tpu.parallel.overlap`): the
    state carries the last microbatch's gradients bucketed but
    un-reduced (``state["pending"]``), and each ``accumulate`` issues
    the previous microbatch's per-bucket reduces — independent of the
    new microbatch's fwd/bwd, so the scheduler overlaps them —
    accumulating the REDUCED sums; ``reduce()`` flushes the final
    pending microbatch and applies the scaling.  Semantics: the result
    is ``Σ_k psum(g_k)`` scaled exactly as the deferred
    ``psum(Σ_k g_k)`` would be — the same mean, a different (per-
    microbatch) summation order, bit-identical to the deferred path at
    K=1 and within accumulation rounding for K>1.  Each microbatch's
    reduce costs wire bytes, so K microbatches move K× the deferred
    mode's bytes — the reference DDP's own default trade (latency
    hidden, bytes multiplied); ``compression="int8"`` composes, with
    per-bucket error-feedback residuals updated every microbatch.  The
    state stays an ordinary pytree: prime it with one ``accumulate``
    and the rest of the loop can be a ``lax.scan``.
    """

    def __init__(
        self,
        axis_name: Any = "dp",
        gradient_average: bool = True,
        gradient_predivide_factor: float = 1.0,
        allreduce_always_fp32: bool = False,
        average_over_microbatches: bool = True,
        compression: Any = None,
        overlap_grad_sync: bool = False,
        bucket_bytes: Optional[int] = None,
    ):
        from apex_tpu.ops.quantization import as_compression_config
        from apex_tpu.parallel.overlap import DEFAULT_BUCKET_BYTES

        self.axis_name = axis_name
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.average_over_microbatches = average_over_microbatches
        # quantize the DCN leg of the reduce (hierarchical axis pairs
        # only); the error-feedback residual rides the accumulator
        # state dict as state["comm"] and PERSISTS across reduce()
        # cycles — only "sum"/"count" reset
        self.compression = as_compression_config(compression)
        self.overlap_grad_sync = overlap_grad_sync
        self.bucket_bytes = (DEFAULT_BUCKET_BYTES if bucket_bytes is None
                             else bucket_bytes)
        if self.bucket_bytes < 1:
            raise ValueError("bucket_bytes must be >= 1")
        if self.compression is not None and not isinstance(
            axis_name, (tuple, list)
        ):
            raise ValueError(
                "compression quantizes the DCN leg of a hierarchical "
                "reduce: pass axis_name=(dcn_axis, ici_axis)"
            )

    def _needs_comm_state(self) -> bool:
        return self.compression is not None and (
            self.compression.error_feedback
            or self.compression.rounding == "stochastic"
        )

    def init(self, params: Any) -> dict:
        """Zero accumulator state (fp32 buffers — accumulation across
        microbatches in bf16 loses low-order contributions).  With
        compression + error feedback the state also carries the
        quantization residuals (``"comm"``, per BUCKET in overlap
        mode); init must then run inside shard_map (residual shapes
        come from the bound axis sizes)."""
        state = {
            "sum": jax.tree.map(
                lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params
            ),
            "count": jnp.zeros((), jnp.int32),
        }
        if self._needs_comm_state():
            if self.overlap_grad_sync:
                from apex_tpu.parallel.overlap import (
                    GradientBuckets,
                    bucket_comm_state,
                )

                plan = GradientBuckets.for_tree(
                    params, self.bucket_bytes, dtype=jnp.float32
                )
                state["comm"] = bucket_comm_state(
                    plan, self.axis_name, self.compression
                )
            else:
                state["comm"] = init_comm_state(
                    params, self.axis_name, self.compression
                )
        return state

    def accumulate(self, state: dict, grads: Any) -> dict:
        """Add one microbatch's grads.  Deferred mode: a local add, no
        collective.  Overlap mode: the PREVIOUS microbatch's buckets
        are reduced here (their collectives and this microbatch's
        fwd/bwd are mutually independent — the scheduler's overlap
        window) and the new grads become the in-flight ``pending``."""
        if not self.overlap_grad_sync:
            new = {
                "sum": jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32),
                    state["sum"], grads
                ),
                "count": state["count"] + 1,
            }
            if "comm" in state:
                new["comm"] = state["comm"]
            return new
        new = {"count": state["count"] + 1, "sum": state["sum"]}
        if "comm" in state:
            new["comm"] = state["comm"]
        if "pending" in state:
            reduced, new_comm = self._overlap_reduce_once(
                state["pending"], state.get("comm")
            )
            new["sum"] = jax.tree.map(
                lambda a, r: a + r, state["sum"], reduced
            )
            if new_comm is not None:
                new["comm"] = new_comm
        new["pending"] = jax.tree.map(
            lambda g: jnp.asarray(g).astype(jnp.float32), grads
        )
        return new

    def _overlap_reduce_once(self, tree: Any, comm: Optional[dict]):
        """Per-bucket SUM-reduce of one microbatch's fp32 grads:
        predivide, RS(ici) → AR(dcn, compressed) → AG(ici) per bucket
        (plain psum on a flat axis).  Averaging is deferred to
        :meth:`reduce` so the scaling ops match the deferred path's
        exactly."""
        from apex_tpu.parallel.overlap import (
            GradientBuckets,
            reduce_bucketed,
        )

        f = self.gradient_predivide_factor
        cfg = self.compression
        hierarchical = isinstance(self.axis_name, (tuple, list))
        plan = GradientBuckets.for_tree(
            tree, self.bucket_bytes, dtype=jnp.float32
        )
        emit_bucket_comm_events(plan, self.axis_name, cfg,
                                where="reducer")
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        bufs = plan.pack(leaves)
        step = None if comm is None else comm["step"]

        from apex_tpu.telemetry.spans import phase as _phase

        def reduce_one(buf, residual, key):
            with _phase("grad_sync"):
                if f != 1.0:
                    buf = buf / f
                if hierarchical:
                    dcn_axis, ici_axis = self.axis_name
                    return _hierarchical_psum(
                        buf, dcn_axis, ici_axis, compression=cfg,
                        residual=residual, step=step, key=key,
                    )
                return jax.lax.psum(buf, self.axis_name), None

        out_bufs, new_residuals = reduce_bucketed(
            plan, bufs, cfg,
            None if comm is None else comm["residuals"], step,
            reduce_one,
        )
        new_comm = None
        if comm is not None:
            new_comm = {"residuals": new_residuals,
                        "step": comm["step"] + 1}
        return jax.tree_util.tree_unflatten(
            treedef, plan.unpack(out_bufs, leaves)
        ), new_comm

    def reduce(self, state: dict) -> tuple:
        """One collective over everything accumulated (deferred mode) or
        the flush of the final in-flight microbatch (overlap mode);
        returns ``(grads, fresh_state)`` — the mean over (world x
        count) when ``gradient_average`` (over world only when
        ``average_over_microbatches=False``, the reference scaling),
        the raw sum otherwise."""
        if self.overlap_grad_sync:
            return self._overlap_reduce(state)
        if self.gradient_average and self.average_over_microbatches:
            n = jnp.maximum(state["count"], 1).astype(jnp.float32)
            grads = jax.tree.map(lambda a: a / n, state["sum"])
        else:
            grads = state["sum"]
        comm = state.get("comm")
        out = all_reduce_gradients(
            grads,
            axis_name=self.axis_name,
            gradient_average=self.gradient_average,
            gradient_predivide_factor=self.gradient_predivide_factor,
            allreduce_always_fp32=self.allreduce_always_fp32,
            compression=self.compression,
            comm_state=comm,
        )
        fresh = {
            "sum": jax.tree.map(jnp.zeros_like, state["sum"]),
            "count": jnp.zeros((), jnp.int32),
        }
        if comm is not None:
            grads, fresh["comm"] = out
        else:
            grads = out
        return grads, fresh

    def _overlap_reduce(self, state: dict) -> tuple:
        comm = state.get("comm")
        done = state["sum"]
        if "pending" in state:
            # the final microbatch's reduce — the one round with no
            # following compute to hide behind (same as the reference
            # DDP's trailing bucket)
            reduced, comm = self._overlap_reduce_once(
                state["pending"], comm
            )
            done = jax.tree.map(lambda a, r: a + r, done, reduced)
        if isinstance(self.axis_name, (tuple, list)):
            world = 1
            for ax in self.axis_name:
                world *= jax.lax.axis_size(ax)
        else:
            world = jax.lax.axis_size(self.axis_name)
        # the exact scaling ops of the deferred path (sync()'s post
        # divide, then the microbatch mean), so K=1 is bit-identical
        if self.gradient_average:
            post = world / self.gradient_predivide_factor
            if post != 1.0:
                done = jax.tree.map(lambda a: a / post, done)
            if self.average_over_microbatches:
                n = jnp.maximum(state["count"], 1).astype(jnp.float32)
                done = jax.tree.map(lambda a: a / n, done)
        elif self.gradient_predivide_factor != 1.0:
            done = jax.tree.map(
                lambda a: a * self.gradient_predivide_factor, done
            )
        fresh = {
            "sum": jax.tree.map(jnp.zeros_like, state["sum"]),
            "count": jnp.zeros((), jnp.int32),
        }
        if comm is not None:
            fresh["comm"] = comm
        return done, fresh
