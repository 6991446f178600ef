"""Full-parameter sharding (ZeRO-3 / FSDP): the shard layout and the
gather-on-use / reduce-scatter-into-shard collectives.

The ZeRO optimizers (:mod:`apex_tpu.contrib.optimizers.distributed`)
shard the *optimizer state* over the data axis but keep a replicated
copy of every parameter on every device — which is exactly what caps
the flagship at h≈1024 on 16 GB HBM (the roofline argument, not yet
measured — ROADMAP S2 — is that MFU 0.55+ needs h≥4096, and the
replicated layout cannot hold that model).
"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (PAPERS.md, arXiv 2004.13336) is the TPU design this module
implements: parameters live *permanently* as 1-D fp32 shards, are
all-gathered to model dtype **per bucket on use**, and gradients
reduce-scatter straight into the shard — no replicated master, no
full-size gradient buffer, no tail all-gather.

Layout (:class:`Zero3Layout`): the param pytree is bucketed by the PR 4
:class:`~apex_tpu.parallel.overlap.GradientBuckets` plan — size-
targeted, single-dtype buckets in REVERSE tree order, so the
*first-used* buckets (embeddings, early layers) sit at the END of the
shard and their gathers are issued last, closest to their consumers
(prefetch-friendly under a latency-hiding scheduler).  Each bucket is
padded to the shard axis extent and split; the per-device shard is the
fp32 concatenation of the per-bucket chunks.  That flat shard IS the
fp32 master: the sharded optimizer update runs on it in place (one
contiguous single-dtype buffer — the PR 7 fused-tail memory pattern
for free), and LAMB's per-parameter trust ratios survive via the same
segment-id machinery as the state-sharded path.

Collectives:

- :meth:`Zero3Layout.gather` — per-bucket all-gather of the params on
  use.  The fp32 chunk is cast to the bucket's MODEL dtype before the
  gather (bf16 params move half the bytes; cast-then-gather equals
  gather-then-cast bit for bit), or — with
  ``CompressionConfig(ici_legs=True)`` — quantized to int8 + per-block
  fp32 scales (:func:`~apex_tpu.ops.quantization.quantized_all_gather`,
  ~4× fewer bytes on the wire), with an optional per-bucket ``ag``
  error-feedback residual.  Each bucket's gather is wrapped in the
  ``tlm.param_gather`` phase and reported to the telemetry stream as a
  ``param_gather`` event with ring-model wire-byte estimates.
- :meth:`Zero3Layout.reduce_scatter_grads` — per-bucket RS(ici) →
  AR(dcn) of the gradients, landing each device exactly its shard's
  elements (the hierarchical legs and their int8 variants are the PR 7
  chunk-preserving ones, so compression never moves a shard boundary).
  There is no grad all-gather: the reduced chunk feeds the sharded
  update directly.

Memory model (why this unlocks h≥4096): replicated DDP holds, per
device, the model-dtype params + fp32 master + two fp32 moments ≈
14–16 bytes/param *persistently*.  Under ZeRO-3 the persistent
footprint is (4 + 8)/world bytes/param (fp32 shard + moments), and the
full-width weights exist only transiently while the step uses them —
bounded by the model-dtype param bytes, with per-bucket gathers giving
the scheduler independently-placeable live ranges instead of one
monolithic materialization.  ``tools/memory_audit.py`` proves the
per-device bytes from the compiled program's ``memory_analysis()``.

Everything here must be called inside ``shard_map`` (or ``pmap``) with
the axes bound, except the host-side constructor/`unshard` paths which
take a ``mesh``.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.parallel.overlap import (
    DEFAULT_BUCKET_BYTES,
    GradientBuckets,
    _local_shape,
)
from apex_tpu.telemetry import events as _events

__all__ = ["Zero3Layout", "zero3_comm_state", "zero3_comm_specs"]


def _split_axes(axis_name) -> Tuple[Optional[str], str]:
    """(dcn_axis_or_None, shard_axis) from a flat name or (dcn, ici)."""
    if isinstance(axis_name, (tuple, list)):
        return axis_name[0], axis_name[1]
    return None, axis_name


class Zero3Layout:
    """The deterministic shard layout for one param pytree.

    A pure function of (local leaf shapes, model dtypes, bucket_bytes,
    world) — the same determinism contract as
    :class:`~apex_tpu.parallel.overlap.GradientBuckets`, which is what
    lets the host-side construction (``param_specs``/``mesh`` for
    model-sharded leaves) and the trace-time one inside ``shard_map``
    agree, so shard/state placement can be computed outside the
    compiled step.
    """

    def __init__(self, params_like: Any, world: int,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 param_specs: Any = None, mesh=None):
        if world < 1:
            raise ValueError(f"world must be >= 1, got {world}")
        leaves, treedef = jax.tree_util.tree_flatten(params_like)
        if param_specs is not None:
            specs = treedef.flatten_up_to(param_specs)
        else:
            specs = [None] * len(leaves)
        self.treedef = treedef
        self.shapes = [
            tuple(_local_shape(l, s, mesh))
            for l, s in zip(leaves, specs)
        ]
        # canonicalized like the bucket plan's: a numpy float64
        # template must describe the float32 the traced step sees
        self.dtypes = [
            jax.dtypes.canonicalize_dtype(l.dtype)
            if hasattr(l, "dtype")
            else jnp.asarray(l).dtype for l in leaves
        ]
        self.world = int(world)
        # model-dtype buckets (dtype=None): single-dtype by assembly, so
        # the uncompressed gather can move model-dtype bytes; for_tree
        # already derives LOCAL shapes under param_specs/mesh, matching
        # what shard_map will see
        self.plan = GradientBuckets.for_tree(
            params_like, bucket_bytes, param_specs=param_specs,
            mesh=mesh,
        )
        self.padded = [
            b.size + (-b.size) % self.world for b in self.plan.buckets
        ]
        self.chunk_sizes = [p // self.world for p in self.padded]
        self.offsets = list(np.cumsum([0] + self.chunk_sizes[:-1]))
        self.shard_size = int(sum(self.chunk_sizes))
        self.num_leaves = len(leaves)

    # ------------------------------------------------------------ host
    @property
    def names(self) -> List[str]:
        return self.plan.names

    def segment_ids(self) -> np.ndarray:
        """Flat shard-layout index → leaf id (host constant); bucket
        padding gets the extra id ``num_leaves`` so it never
        contaminates a real parameter (the LAMB trust-ratio contract
        of ``_FlatMeta.segment_ids``, in bucket order).  Built from
        the ONE per-bucket id construction (:meth:`_bucket_id_vectors`)
        so it can never diverge from the per-rank slices."""
        parts = self._bucket_id_vectors()
        return (np.concatenate([np.asarray(v) for v in parts])
                if parts else np.zeros((0,), np.int32))

    def local_segment_ids(self, rank) -> jnp.ndarray:
        """This rank's ``(shard_size,)`` slice of :meth:`segment_ids`
        (``rank`` may be a traced ``lax.axis_index``)."""
        # per-bucket dynamic_slice of the bucket's own id vector: the
        # shard concatenates per-bucket chunks, so one global slice
        # would pick the wrong elements
        parts = []
        full = self._bucket_id_vectors()
        for i, chunk in enumerate(self.chunk_sizes):
            parts.append(jax.lax.dynamic_slice(
                full[i], (rank * chunk,), (chunk,)
            ))
        return (jnp.concatenate(parts) if parts
                else jnp.zeros((0,), jnp.int32))

    def _bucket_id_vectors(self) -> List[jnp.ndarray]:
        out = []
        for b, padded in zip(self.plan.buckets, self.padded):
            ids = np.concatenate(
                [np.full((s,), i, np.int32)
                 for i, s in zip(b.leaf_ids, b.sizes)]
                if b.leaf_ids else [np.zeros((0,), np.int32)]
            )
            ids = np.concatenate([
                ids,
                np.full((padded - b.size,), self.num_leaves, np.int32),
            ])
            out.append(jnp.asarray(ids))
        return out

    def unshard(self, global_shards: np.ndarray) -> Any:
        """Host-side: rebuild the full replicated param pytree from the
        ``device_get`` of the sharded flat buffer (global shape
        ``(world * shard_size,)``, rank-major — the shape a
        ``P(shard_axis)``-placed shard array materializes to).  The
        inverse of ``shard_params``+time: use it to resume a ZeRO-3
        checkpoint into a replicated-eval setup; values are the exact
        fp32 masters cast to model dtype — bit-identical to a
        FULL-WIDTH :meth:`gather` (under int8 gathers the on-device
        view is the lossy wire format; this rebuild is the exact
        source of truth, i.e. at least as accurate)."""
        flat = np.asarray(global_shards).reshape(-1)
        expect = self.world * self.shard_size
        if flat.size != expect:
            raise ValueError(
                f"global shards have {flat.size} elements, the layout "
                f"expects world({self.world}) x shard({self.shard_size})"
                f" = {expect}: was the checkpoint written at a "
                "different world size or bucket_bytes?"
            )
        per_rank = flat.reshape(self.world, self.shard_size)
        out: List[Any] = [None] * self.num_leaves
        for i, b in enumerate(self.plan.buckets):
            off, chunk = self.offsets[i], self.chunk_sizes[i]
            full = np.concatenate(
                [per_rank[r, off:off + chunk] for r in range(self.world)]
            )[: b.size]
            pos = 0
            for leaf_id, size in zip(b.leaf_ids, b.sizes):
                out[leaf_id] = full[pos:pos + size].reshape(
                    self.shapes[leaf_id]
                ).astype(self.dtypes[leaf_id])
                pos += size
        return jax.tree_util.tree_unflatten(self.treedef, out)

    # ------------------------------------------------- inside shard_map
    def shard_params(self, params: Any, rank) -> jnp.ndarray:
        """This rank's permanent ``(shard_size,)`` fp32 shard of a
        (replicated) param pytree — call once at init inside
        ``shard_map`` (``rank = lax.axis_index(shard_axis)``)."""
        leaves = self.treedef.flatten_up_to(params)
        bufs = self.plan.pack(leaves)  # model-dtype flat buffers
        parts = []
        for i, (buf, padded) in enumerate(zip(bufs, self.padded)):
            buf = buf.astype(jnp.float32)
            if padded != buf.size:
                buf = jnp.concatenate(
                    [buf, jnp.zeros((padded - buf.size,), jnp.float32)]
                )
            chunk = self.chunk_sizes[i]
            parts.append(jax.lax.dynamic_slice(
                buf, (rank * chunk,), (chunk,)
            ))
        return (jnp.concatenate(parts) if parts
                else jnp.zeros((0,), jnp.float32))

    def bucket_chunk(self, shard: jnp.ndarray, i: int) -> jnp.ndarray:
        """Bucket *i*'s slice of the flat shard."""
        return shard[self.offsets[i]: self.offsets[i]
                     + self.chunk_sizes[i]]

    def _unpack_bucket(self, i: int, full: jnp.ndarray) -> List[Any]:
        """Bucket *i*'s gathered (padded) flat buffer → its leaves in
        model shape/dtype; returns [(leaf_id, leaf), ...]."""
        b = self.plan.buckets[i]
        out = []
        pos = 0
        for leaf_id, size in zip(b.leaf_ids, b.sizes):
            out.append((leaf_id, full[pos:pos + size].reshape(
                self.shapes[leaf_id]).astype(self.dtypes[leaf_id])))
            pos += size
        return out

    def gather(
        self,
        shard: jnp.ndarray,
        axis_name: Any,
        compression: Any = None,
        residuals: Optional[dict] = None,
        step=None,
    ) -> Tuple[Any, Optional[dict]]:
        """Gather-on-use: per-bucket all-gather of the full weights in
        model dtype.  ``axis_name`` is the flat shard axis or the
        hierarchical ``(dcn, ici)`` pair (the gather rides the ici leg
        only — shards are replicated across dcn, so no parameter bytes
        ever cross the slow axis).  With ``compression.ici_legs`` the
        AG payload is int8 + per-block fp32 scales
        (:func:`~apex_tpu.ops.quantization.quantized_all_gather`), with
        a per-bucket ``ag`` error-feedback residual when ``residuals``
        is given.  Returns ``(params, new_residuals_or_None)``;
        ``new_residuals`` echoes the untouched grad-leg residuals so
        the caller can thread one state dict."""
        from apex_tpu.ops.quantization import as_compression_config
        from apex_tpu.telemetry.spans import phase as _phase

        cfg = as_compression_config(compression)
        _, shard_axis = _split_axes(axis_name)
        quantize = cfg is not None and cfg.ici_legs
        use_ef = (quantize and cfg is not None and cfg.error_feedback
                  and residuals is not None)
        self._emit_gather_events(axis_name, cfg)
        out: List[Any] = [None] * self.num_leaves
        new_residuals: Optional[dict] = (
            {k: dict(v) for k, v in residuals.items()}
            if residuals is not None else None
        )
        base_key = None
        if (quantize and cfg.rounding == "stochastic"
                and step is not None):
            # leg 2 of the PR 7 per-leg decorrelation scheme (0 = dcn,
            # 1 = grad RS), then per bucket — re-deriving the grad
            # legs' keys here would re-roll their dither on the params
            base_key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(0), step), 2
            )
        for i, name in enumerate(self.names):
            chunk = self.bucket_chunk(shard, i)
            if chunk.size == 0:
                for leaf_id, leaf in self._unpack_bucket(
                    i, jnp.zeros((0,), jnp.float32)
                ):
                    out[leaf_id] = leaf
                continue
            with _phase("param_gather"):
                if quantize:
                    from apex_tpu.ops.quantization import (
                        quantized_all_gather,
                    )

                    res = (residuals[name]["ag"] if use_ef else None)
                    key = (jax.random.fold_in(base_key, i)
                           if base_key is not None else None)
                    full, new_ag = quantized_all_gather(
                        chunk, shard_axis, cfg, residual=res,
                        step=step, key=key,
                    )
                else:
                    from apex_tpu.transformer.tensor_parallel.mappings \
                        import all_gather_invariant

                    # cast BEFORE the gather: elementwise, so the
                    # result is bit-identical to gathering fp32 and
                    # casting after — at half the wire bytes for bf16
                    full = all_gather_invariant(
                        chunk.astype(self.plan.buckets[i].dtype),
                        shard_axis, axis=0, tiled=True,
                    )
                    new_ag = None
            if new_ag is not None and new_residuals is not None:
                new_residuals[name]["ag"] = new_ag
            for leaf_id, leaf in self._unpack_bucket(i, full):
                out[leaf_id] = leaf
        params = jax.tree_util.tree_unflatten(self.treedef, out)
        return params, (new_residuals if residuals is not None else None)

    def reduce_scatter_grads(
        self,
        grads: Any,
        axis_name: Any,
        compression: Any = None,
        residuals: Optional[dict] = None,
        step=None,
    ) -> Tuple[jnp.ndarray, Optional[dict]]:
        """Per-bucket RS(ici) → AR(dcn) of the gradients, straight into
        the shard layout: returns the raw SUM over the data axes of
        this rank's ``(shard_size,)`` gradient chunk (callers divide by
        the world for the mean — the ZeRO step's convention) plus the
        updated grad-leg residuals.  With ``compression`` the dcn leg
        runs int8 (and, under ``ici_legs``, the RS leg too); with a
        flat ``axis_name`` the reduce is one ``psum_scatter`` per
        bucket and compression must be None."""
        from apex_tpu.ops.quantization import as_compression_config

        cfg = as_compression_config(compression)
        dcn_axis, shard_axis = _split_axes(axis_name)
        if cfg is not None and dcn_axis is None:
            raise ValueError(
                "compression quantizes the DCN leg of the hierarchical "
                "reduce: pass axis_name=(dcn_axis, ici_axis)"
            )
        use_ef = (cfg is not None and cfg.error_feedback
                  and residuals is not None)
        leaves = self.treedef.flatten_up_to(grads)
        bufs = self.plan.pack(leaves)
        base_keys = [None, None]
        if (cfg is not None and cfg.rounding == "stochastic"
                and step is not None):
            base = jax.random.fold_in(jax.random.PRNGKey(0), step)
            base_keys = [jax.random.fold_in(base, 0),   # dcn leg
                         jax.random.fold_in(base, 1)]   # grad RS leg
        new_residuals: Optional[dict] = (
            {k: dict(v) for k, v in residuals.items()}
            if residuals is not None else None
        )
        from apex_tpu.telemetry.spans import phase as _phase

        parts = []
        for i, name in enumerate(self.names):
            buf = bufs[i].astype(jnp.float32)
            if buf.size == 0:
                parts.append(jnp.zeros((0,), jnp.float32))
                continue
            padded = self.padded[i]
            if padded != buf.size:
                buf = jnp.concatenate(
                    [buf, jnp.zeros((padded - buf.size,), jnp.float32)]
                )
            with _phase("grad_sync"):
                if cfg is not None and cfg.ici_legs:
                    from apex_tpu.ops.quantization import (
                        quantized_reduce_scatter,
                    )

                    res = (residuals[name]["ici_push"] if use_ef
                           else None)
                    key = (jax.random.fold_in(base_keys[1], i)
                           if base_keys[1] is not None else None)
                    chunk, new_rs = quantized_reduce_scatter(
                        buf, shard_axis, cfg, residual=res,
                        step=step, key=key,
                    )
                    if new_rs is not None and new_residuals is not None:
                        new_residuals[name]["ici_push"] = new_rs
                else:
                    chunk = jax.lax.psum_scatter(
                        buf, shard_axis, tiled=True
                    )
                if dcn_axis is not None:
                    if cfg is not None:
                        from apex_tpu.ops.quantization import (
                            quantized_psum,
                        )

                        res = None
                        if use_ef:
                            res = {"push": residuals[name]["push"],
                                   "pull": residuals[name]["pull"]}
                        key = (jax.random.fold_in(base_keys[0], i)
                               if base_keys[0] is not None else None)
                        chunk, new_dcn = quantized_psum(
                            chunk, dcn_axis, cfg, residual=res,
                            step=step, key=key,
                        )
                        if (new_dcn is not None
                                and new_residuals is not None):
                            new_residuals[name]["push"] = \
                                new_dcn["push"]
                            new_residuals[name]["pull"] = \
                                new_dcn["pull"]
                    else:
                        chunk = jax.lax.psum(chunk, dcn_axis)
            parts.append(chunk)
        shard = (jnp.concatenate(parts) if parts
                 else jnp.zeros((0,), jnp.float32))
        return shard, (new_residuals if residuals is not None else None)

    # ------------------------------------------------------- telemetry
    def _emit_gather_events(self, axis_name, cfg) -> None:
        """One ``param_gather`` event per bucket at trace time — static
        host ints only, free with no sink registered (the comm_bucket
        convention from PR 4/6); wire bytes are ring-model ESTIMATES of
        the AG leg, int8 payload + fp32 scale sidecar when compressed,
        model-dtype payload otherwise."""
        if not _events.have_sinks():
            return
        from apex_tpu.telemetry.events import ring_wire_bytes

        _, shard_axis = _split_axes(axis_name)
        ici = jax.lax.axis_size(shard_axis)
        quantize = cfg is not None and cfg.ici_legs
        for i, (name, b) in enumerate(
            zip(self.names, self.plan.buckets)
        ):
            padded, chunk = self.padded[i], self.chunk_sizes[i]
            itemsize = int(np.dtype(b.dtype).itemsize)
            if quantize:
                nb = max(-(-chunk // cfg.block_size), 1)
                result_bytes = ici * (chunk + nb * 4)
            else:
                result_bytes = padded * itemsize
            _events.emit(
                "param_gather",
                where="zero3",
                bucket=name,
                elements=int(b.size),
                dtype=str(np.dtype(b.dtype).name),
                bytes=int(b.size) * itemsize,
                ici_size=int(ici),
                compressed=bool(quantize),
                ag_ici_wire_bytes=round(ring_wire_bytes(
                    "all-gather", ici, result_bytes,
                    result_bytes=result_bytes,
                )),
            )

    # ------------------------------------------------------- residuals
    def residual_sizes(self, dcn: int, ici: int, cfg) -> dict:
        """Per-bucket error-feedback buffer lengths for this layout
        under ``cfg`` (the ONE sizing, from
        :func:`~apex_tpu.ops.quantization.zero3_residual_sizes`)."""
        from apex_tpu.ops.quantization import zero3_residual_sizes

        return {
            name: zero3_residual_sizes(
                b.size, dcn, ici, cfg.block_size, cfg.ici_legs
            )
            for name, b in zip(self.names, self.plan.buckets)
        }


def zero3_comm_state(layout: Zero3Layout, axis_name, compression,
                     mesh=None) -> dict:
    """Zero per-bucket error-feedback residuals for a ZeRO-3 layout:
    grad legs (``push``/``pull`` for the dcn all-reduce, ``ici_push``
    for the int8 RS) plus the ``ag`` param-gather residual under
    ``ici_legs``.  Host-side with ``mesh`` (global buffers, one slice
    per (dcn, ici) position — ``ag`` rides ici only, it is invariant
    over dcn like the shard it compensates); per-device inside
    ``shard_map`` without."""
    from apex_tpu.ops.quantization import as_compression_config

    cfg = as_compression_config(compression)
    if cfg is None:
        raise ValueError("zero3_comm_state needs a compression config")
    dcn_axis, ici_axis = _split_axes(axis_name)
    if dcn_axis is None:
        raise ValueError(
            "compressed ZeRO-3 comm state needs the hierarchical "
            "(dcn, ici) axis pair"
        )
    if mesh is not None:
        dcn, ici = mesh.shape[dcn_axis], mesh.shape[ici_axis]
    else:
        dcn, ici = jax.lax.axis_size(dcn_axis), jax.lax.axis_size(ici_axis)
    sizes = layout.residual_sizes(dcn, ici, cfg)
    residuals = {}
    for name, per in sizes.items():
        residuals[name] = {}
        for k, n in per.items():
            reps = 1
            if mesh is not None:
                # ag is replicated across dcn (it compensates the
                # dcn-invariant shard); everything else varies over
                # both data axes
                reps = ici if k == "ag" else dcn * ici
            residuals[name][k] = jnp.zeros((reps * n,), jnp.float32)
    return residuals


def zero3_comm_specs(layout: Zero3Layout, axis_name, compression,
                     model_axes: Sequence[str] = ()) -> dict:
    """shard_map / device_put specs for :func:`zero3_comm_state`."""
    from apex_tpu.ops.quantization import as_compression_config

    from jax.sharding import PartitionSpec as P

    cfg = as_compression_config(compression)
    dcn_axis, ici_axis = _split_axes(axis_name)
    sizes = layout.residual_sizes(2, 2, cfg)  # key sets only
    out = {}
    for name, per in sizes.items():
        out[name] = {
            k: (P((*model_axes, ici_axis)) if k == "ag"
                else P((*model_axes, dcn_axis, ici_axis)))
            for k in per
        }
    return out
