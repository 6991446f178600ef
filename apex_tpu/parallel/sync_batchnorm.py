"""SyncBatchNorm — cross-replica batch normalization.

The reference needs 1.7k lines of Welford CUDA kernels plus an
all_gather/merge dance (reference: csrc/welford.cu,
apex/parallel/optimized_sync_batchnorm_kernel.py:1-119).  On TPU the
whole thing is a single fused ``psum`` of the sufficient statistics
(count, Σx, Σx²) over the 'dp' mesh axis — numerically equivalent to
parallel Welford merging, and it supports different per-replica batch
sizes the same way (counts are summed, not assumed equal).

Matches reference semantics:
- biased variance for normalization, unbiased for running stats
  (reference: apex/parallel/sync_batchnorm.py:105-117),
- eval mode uses running stats (falls back to plain batch_norm,
  reference: optimized_sync_batchnorm.py:9-85),
- optional fused ReLU epilogue (``fuse_relu``),
- channels-last is the native layout here (feature axis defaults to -1).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

__all__ = ["sync_batch_norm", "SyncBatchNorm"]


def sync_batch_norm(
    x: jnp.ndarray,
    weight: Optional[jnp.ndarray],
    bias: Optional[jnp.ndarray],
    running_mean: Optional[jnp.ndarray],
    running_var: Optional[jnp.ndarray],
    training: bool = True,
    momentum: float = 0.1,
    eps: float = 1e-5,
    axis_name: Optional[str] = None,
    process_group_size: int = 0,
    fuse_relu: bool = False,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """Functional SyncBN over the trailing feature axis.

    Returns ``(out, new_running_mean, new_running_var)``.  When
    ``axis_name`` is given and we're inside an SPMD context, statistics
    are reduced across that mesh axis.  ``process_group_size`` reproduces
    ``create_syncbn_process_group`` (reference:
    apex/parallel/__init__.py:35-95): stats are reduced within groups of
    that size instead of the whole axis (0 = whole axis).
    """
    feat = x.shape[-1]
    reduce_axes = tuple(range(x.ndim - 1))

    if not training:
        mean, var = running_mean, running_var
        xf = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
        out = (xf - mean.astype(jnp.float32)) * inv
        if weight is not None:
            out = out * weight.astype(jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        out = out.astype(x.dtype)
        if fuse_relu:
            out = jax.nn.relu(out)
        return out, running_mean, running_var

    xf = x.astype(jnp.float32)
    local_count = jnp.float32(xf.size // feat)
    local_sum = jnp.sum(xf, axis=reduce_axes)
    local_sumsq = jnp.sum(jnp.square(xf), axis=reduce_axes)

    if axis_name is not None:
        if process_group_size and process_group_size > 0:
            # group-limited reduction: psum over contiguous index groups
            idx = jax.lax.axis_index(axis_name)
            group = idx // process_group_size
            stacked_c = jax.lax.all_gather(local_count, axis_name)
            stacked_s = jax.lax.all_gather(local_sum, axis_name)
            stacked_q = jax.lax.all_gather(local_sumsq, axis_name)
            world = jax.lax.axis_size(axis_name)
            members = (jnp.arange(world) // process_group_size) == group
            count = jnp.sum(jnp.where(members, stacked_c, 0.0))
            total_sum = jnp.sum(
                jnp.where(members[:, None], stacked_s, 0.0), axis=0
            )
            total_sumsq = jnp.sum(
                jnp.where(members[:, None], stacked_q, 0.0), axis=0
            )
        else:
            count = jax.lax.psum(local_count, axis_name)
            total_sum = jax.lax.psum(local_sum, axis_name)
            total_sumsq = jax.lax.psum(local_sumsq, axis_name)
    else:
        count, total_sum, total_sumsq = local_count, local_sum, local_sumsq

    mean = total_sum / count
    var = total_sumsq / count - jnp.square(mean)  # biased, for normalization

    inv = jax.lax.rsqrt(var + eps)
    out = (xf - mean) * inv
    if weight is not None:
        out = out * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    out = out.astype(x.dtype)
    if fuse_relu:
        out = jax.nn.relu(out)

    new_rm, new_rv = running_mean, running_var
    if running_mean is not None:
        unbiased = var * (count / jnp.maximum(count - 1.0, 1.0))
        new_rm = (1 - momentum) * running_mean + momentum * mean
        new_rv = (1 - momentum) * running_var + momentum * unbiased
    return out, new_rm, new_rv


class SyncBatchNorm(nn.Module):
    """flax module form (reference: apex/parallel/optimized_sync_batchnorm.py).

    Running stats live in the 'batch_stats' collection; pass
    ``use_running_average=True`` (or ``deterministic``) for eval.
    """

    # None → inferred from the input's trailing (channel) dim at call
    num_features: Optional[int] = None
    eps: float = 1e-5
    momentum: float = 0.1
    affine: bool = True
    track_running_stats: bool = True
    axis_name: Optional[str] = None
    process_group_size: int = 0
    fuse_relu: bool = False
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, use_running_average: bool = False):
        num_features = (
            self.num_features if self.num_features is not None
            else x.shape[-1]
        )
        weight = bias = None
        if self.affine:
            weight = self.param(
                "weight", nn.initializers.ones, (num_features,),
                self.param_dtype,
            )
            bias = self.param(
                "bias", nn.initializers.zeros, (num_features,),
                self.param_dtype,
            )
        ra_mean = self.variable(
            "batch_stats", "running_mean",
            lambda: jnp.zeros((num_features,), jnp.float32),
        )
        ra_var = self.variable(
            "batch_stats", "running_var",
            lambda: jnp.ones((num_features,), jnp.float32),
        )
        training = not use_running_average
        out, new_rm, new_rv = sync_batch_norm(
            x,
            weight,
            bias,
            ra_mean.value if self.track_running_stats else None,
            ra_var.value if self.track_running_stats else None,
            training=training,
            momentum=self.momentum,
            eps=self.eps,
            axis_name=self.axis_name,
            process_group_size=self.process_group_size,
            fuse_relu=self.fuse_relu,
        )
        if training and self.track_running_stats and not self.is_initializing():
            ra_mean.value = new_rm
            ra_var.value = new_rv
        return out
