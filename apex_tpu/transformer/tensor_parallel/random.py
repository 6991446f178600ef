"""RNG bookkeeping + activation checkpointing, TPU-native.

The reference maintains a ``CudaRNGStatesTracker`` so dropout can be
*different* across tensor-parallel ranks for sharded activations yet
*identical* for replicated ones, and its ``CheckpointFunction`` snapshots
and restores RNG state around recomputation
(reference: apex/transformer/tensor_parallel/random.py:113-294).

JAX's explicit PRNG keys make both trivial and deterministic:

- per-rank streams are ``fold_in(key, axis_index(axis))`` — no mutable
  tracker, no capture/restore;
- recompute-exactness under rematerialization is automatic because the
  key is an ordinary value.

The reference's optional pre-allocated activation buffer
(reference: apex/transformer/tensor_parallel/memory.py:34-136) is
subsumed by XLA's allocator; what the user actually controls is the
remat *policy*, exposed here as named presets.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax

from apex_tpu.ops.common import ATTENTION_RESIDUAL_NAMES
from apex_tpu.transformer.parallel_state import (
    DATA_PARALLEL_AXIS,
    TENSOR_PARALLEL_AXIS,
)
from apex_tpu.transformer.tensor_parallel.mappings import TP_REDUCED_NAME

__all__ = ["model_parallel_key", "data_parallel_key", "checkpoint", "CHECKPOINT_POLICIES"]


def model_parallel_key(key, axis_name: str = TENSOR_PARALLEL_AXIS):
    """A PRNG key distinct per tensor-parallel rank — the analog of the
    tracker's "model-parallel-rng" state
    (reference: apex/transformer/tensor_parallel/random.py:142-154).
    Call inside shard_map."""
    return jax.random.fold_in(key, jax.lax.axis_index(axis_name))


def data_parallel_key(key, axis_name: str = DATA_PARALLEL_AXIS):
    """A PRNG key distinct per data-parallel rank (for per-shard dropout on
    data-sharded activations)."""
    return jax.random.fold_in(key, jax.lax.axis_index(axis_name))


CHECKPOINT_POLICIES = {
    # recompute everything (reference CheckpointFunction default)
    "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
    # keep matmul outputs, recompute elementwise — usually the best
    # FLOPs/HBM trade on TPU
    "dots_saveable": jax.checkpoint_policies.dots_saveable,
    "dots_with_no_batch_dims_saveable": (
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    ),
    "everything_saveable": jax.checkpoint_policies.everything_saveable,
    # the models' default: the dots policy above, plus the two residuals
    # the attention kernels' forward rules name (out, lse) and a
    # row-parallel layer's output after its tp sum.  A Mosaic call is
    # not a dot, so without the first the backward runs the forward
    # kernel a second time; neither is psum, so without the second it
    # all-reduces the dot's partial sum a second time.  The XLA
    # attention path carries no tag and compiles as under the dots
    # policy alone
    "dots_with_no_batch_dims_and_attention_saveable": (
        jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                *ATTENTION_RESIDUAL_NAMES, TP_REDUCED_NAME),
        )
    ),
}


def checkpoint(
    fn: Callable,
    policy: Optional[str] = "nothing_saveable",
    prevent_cse: bool = True,
) -> Callable:
    """Activation checkpointing (reference:
    apex/transformer/tensor_parallel/random.py:224-294).

    ``policy`` is a named remat policy from :data:`CHECKPOINT_POLICIES`
    (or None for the jax default).  RNG state restore is implicit: keys
    are values.
    """
    pol = CHECKPOINT_POLICIES[policy] if isinstance(policy, str) else policy
    return functools.wraps(fn)(
        jax.checkpoint(fn, policy=pol, prevent_cse=prevent_cse)
    )
