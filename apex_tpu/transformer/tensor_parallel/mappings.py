"""TP collective regions.

The four Megatron region primitives
(reference: apex/transformer/tensor_parallel/mappings.py:23-159):

=========  ==================  ==================
region     forward             backward
=========  ==================  ==================
copy_to    identity            all-reduce
reduce     all-reduce          identity
scatter    split (my chunk)    all-gather
gather     all-gather          split (my chunk)
=========  ==================  ==================

The reference implements these as hand-written autograd.Functions because
torch cannot differentiate through NCCL calls.  JAX can: under
``shard_map`` with varying-manual-axes (vma) typing, the transpose rules
of ``psum`` / ``all_gather_invariant`` / rank-indexed ``dynamic_slice``
produce *exactly* the table above — an invariant (replicated) input used
in device-varying compute gets its cotangents psum'd automatically, psum's
transpose is the identity, and ``all_gather_invariant`` transposes to the
local slice.  So these functions are thin named wrappers that (a) document
the region semantics at call sites and (b) pin the collective choice
(all-gather-invariant rather than a vma-varying all-gather, so the result
is typed replicated and can cross a ``shard_map`` boundary with spec P()).

All assume they are called inside ``shard_map`` with a "tp" mesh axis and
vma checking ON (the default `check_vma=True`); disabling vma checking
silently changes psum's transpose and breaks gradient correctness.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax._src.lax import parallel as _lax_parallel
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.transformer.parallel_state import TENSOR_PARALLEL_AXIS

__all__ = [
    "copy_to_tensor_model_parallel_region",
    "reduce_from_tensor_model_parallel_region",
    "scatter_to_tensor_model_parallel_region",
    "gather_from_tensor_model_parallel_region",
    "all_gather_invariant",
    "TP_REDUCED_NAME",
]

#: ``checkpoint_name`` tag of a row-parallel layer's output AFTER its sum
#: over tp.  The dots policies keep the dot's output, which is the
#: partial sum, and ``psum`` is not a dot: the backward would all-reduce
#: the same bytes a second time to rebuild what the sum fed.  A remat
#: policy that saves this name (``tensor_parallel.random.
#: CHECKPOINT_POLICIES``) keeps the reduced tensor instead; under any
#: other policy, and outside ``jax.checkpoint``, the tag is the identity.
TP_REDUCED_NAME = "tp_reduced"


def all_gather_invariant(x, axis_name, *, axis: int = 0, tiled: bool = False):
    """All-gather producing a vma-*invariant* (replicated-typed) result.

    Single shim point for the private JAX symbol (no public export in
    the installed jax); everything in apex_tpu gathers through here.
    """
    return _lax_parallel.all_gather_invariant(
        x, axis_name, axis=axis, tiled=tiled
    )


def copy_to_tensor_model_parallel_region(x, axis_name=TENSOR_PARALLEL_AXIS):
    """Identity forward; backward all-reduces the cotangent
    (reference: apex/transformer/tensor_parallel/mappings.py:79-93).

    Under vma typing the backward psum is inserted by JAX's transpose of
    invariant→varying use, so the forward really is the identity.
    """
    return x


def reduce_from_tensor_model_parallel_region(x, axis_name=TENSOR_PARALLEL_AXIS):
    """All-reduce forward, identity backward
    (reference: apex/transformer/tensor_parallel/mappings.py:96-110).
    The sum carries :data:`TP_REDUCED_NAME`, so remat keeps it."""
    return checkpoint_name(jax.lax.psum(x, axis_name), TP_REDUCED_NAME)


def scatter_to_tensor_model_parallel_region(x, axis_name=TENSOR_PARALLEL_AXIS):
    """Keep this rank's chunk of the last dim; backward all-gathers
    (reference: apex/transformer/tensor_parallel/mappings.py:113-127)."""
    world = jax.lax.axis_size(axis_name)
    if x.shape[-1] % world != 0:
        raise ValueError(
            f"scatter_to_tensor_model_parallel_region: last dim "
            f"({x.shape[-1]}) is not divisible by the '{axis_name}' axis "
            f"size ({world})"
        )
    rank = jax.lax.axis_index(axis_name)
    chunk = x.shape[-1] // world
    return jax.lax.dynamic_slice_in_dim(x, rank * chunk, chunk, axis=x.ndim - 1)


def gather_from_tensor_model_parallel_region(x, axis_name=TENSOR_PARALLEL_AXIS):
    """All-gather along the last dim into a replicated (vma-invariant)
    value; backward takes the local slice
    (reference: apex/transformer/tensor_parallel/mappings.py:130-144)."""
    return all_gather_invariant(x, axis_name, axis=x.ndim - 1, tiled=True)
