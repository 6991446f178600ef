"""Fused LayerNorm / RMSNorm kernels.

Capability match for the reference's ``fused_layer_norm_cuda`` and
``fast_layer_norm`` extensions (reference: csrc/layer_norm_cuda_kernel.cu,
apex/contrib/csrc/layer_norm/) re-designed for TPU:

- statistics in fp32 regardless of input dtype (the kernels' accumulation
  contract),
- one ``custom_vjp`` shared by the Pallas TPU kernel and the XLA fallback
  so both paths are numerically interchangeable,
- the "mixed dtype" Megatron variant (input dtype ≠ param dtype, output
  follows the input, reference: csrc/layer_norm_cuda.cpp
  ``forward_affine_mixed_dtypes``).

The Pallas forward tiles rows into VMEM blocks and keeps the (mean,
invvar) residuals for the backward; dgamma/dbeta are column reductions
XLA already does optimally, so only dx runs in Pallas.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from apex_tpu.ops.common import run_kernel, shape_struct
from apex_tpu.telemetry.spans import kernel_name

from apex_tpu.utils.platform import is_tpu

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "fused_layer_norm",
    "fused_layer_norm_affine",
    "fused_rms_norm",
    "fused_rms_norm_affine",
    "mixed_dtype_fused_layer_norm_affine",
]


def _norm_size(normalized_shape: Union[int, Sequence[int]]) -> int:
    if isinstance(normalized_shape, int):
        return normalized_shape
    size = 1
    for s in normalized_shape:
        size *= int(s)
    return size


def _as_2d(x: jnp.ndarray, hidden: int) -> jnp.ndarray:
    return x.reshape(-1, hidden)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _ln_fwd_kernel(x_ref, o_ref, mean_ref, invvar_ref, *, eps, rms):
    x = x_ref[:].astype(jnp.float32)
    if rms:
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
    else:
        mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    invvar = jax.lax.rsqrt(var + eps)
    o_ref[:] = ((x - mean) * invvar).astype(o_ref.dtype)
    # stats are written as (grid, 1, block_rows) — the singleton keeps
    # the trailing block dims equal to the array dims, which frees
    # block_rows from the 128-lane tiling/alignment rules so large
    # hidden sizes can use small row blocks without blowing VMEM
    mean_ref[0, 0, :] = mean[:, 0]
    invvar_ref[0, 0, :] = invvar[:, 0]


def _ln_fwd_pallas(x2d: jnp.ndarray, eps: float, rms: bool):
    rows, hidden = x2d.shape
    # block sized so in+out+fp32 intermediates stay well under the 16 MB
    # VMEM scope: ~2 MB of fp32 per block buffer
    cap = max(8, (512 * 1024) // max(hidden, 1) // 8 * 8)
    block_rows = max(8, min(cap, min(256, rows)))
    # pad rows to a multiple of block_rows
    pad = (-rows) % block_rows
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    padded_rows = rows + pad
    grid = (padded_rows // block_rows,)
    out, mean, invvar = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps, rms=rms),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, hidden), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
        ],
        out_specs=[
            pl.BlockSpec((block_rows, hidden), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_rows), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, block_rows), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            shape_struct((padded_rows, hidden), x2d.dtype, x2d),
            shape_struct((grid[0], 1, block_rows), jnp.float32, x2d),
            shape_struct((grid[0], 1, block_rows), jnp.float32, x2d),
        ],
        # interpreter mode off-TPU so the kernel body stays testable
        interpret=not is_tpu(),
        name=kernel_name("layer_norm.fwd"),
    )(x2d)
    mean = mean.reshape(padded_rows)
    invvar = invvar.reshape(padded_rows)
    if pad:
        out, mean, invvar = out[:rows], mean[:rows], invvar[:rows]
    return out, mean, invvar


def _ln_fwd_xla(x2d: jnp.ndarray, eps: float, rms: bool):
    xf = x2d.astype(jnp.float32)
    if rms:
        mean = jnp.zeros((xf.shape[0],), jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1)
    else:
        mean = jnp.mean(xf, axis=-1)
        var = jnp.mean(jnp.square(xf - mean[:, None]), axis=-1)
    invvar = jax.lax.rsqrt(var + eps)
    xhat = (xf - mean[:, None]) * invvar[:, None]
    return xhat.astype(x2d.dtype), mean, invvar


def _ln_fwd(x2d, eps, rms, implementation: Optional[str]):
    # Auto mode routes to XLA *by measurement*: layernorm is bandwidth-
    # bound and XLA's fused mean/var/normalize pipeline beats the Pallas
    # tile kernel on every swept shape (0.7-1.0x, KERNELS_TPU.json).
    # The kernel stays available via implementation='pallas' for the
    # cross-check tier.
    return run_kernel(
        "fused_layer_norm",
        lambda: _ln_fwd_pallas(x2d, eps, rms),
        lambda: _ln_fwd_xla(x2d, eps, rms),
        implementation or "xla",
    )


# ---------------------------------------------------------------------------
# custom_vjp core (normalize-only; affine applied outside so one vjp serves
# affine / non-affine / mixed-dtype variants)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _normalize(x2d, eps: float, rms: bool, implementation: Optional[str]):
    out, _, _ = _ln_fwd(x2d, eps, rms, implementation)
    return out


def _normalize_fwd(x2d, eps, rms, implementation):
    out, mean, invvar = _ln_fwd(x2d, eps, rms, implementation)
    return out, (x2d, mean, invvar)


def _normalize_bwd(eps, rms, implementation, res, dxhat):
    x2d, mean, invvar = res
    xf = x2d.astype(jnp.float32)
    dy = dxhat.astype(jnp.float32)
    xhat = (xf - mean[:, None]) * invvar[:, None]
    n = xf.shape[-1]
    if rms:
        # dx = invvar*(dy - xhat * mean(dy*xhat))
        c2 = jnp.mean(dy * xhat, axis=-1, keepdims=True)
        dx = invvar[:, None] * (dy - xhat * c2)
    else:
        c1 = jnp.mean(dy, axis=-1, keepdims=True)
        c2 = jnp.mean(dy * xhat, axis=-1, keepdims=True)
        dx = invvar[:, None] * (dy - c1 - xhat * c2)
    return (dx.astype(x2d.dtype),)


_normalize.defvjp(_normalize_fwd, _normalize_bwd)


# ---------------------------------------------------------------------------
# public functional API
# ---------------------------------------------------------------------------


def fused_layer_norm(
    x: jnp.ndarray,
    normalized_shape: Union[int, Sequence[int]],
    eps: float = 1e-5,
    implementation: Optional[str] = None,
) -> jnp.ndarray:
    """Non-affine fused layer norm (reference: ``FusedLayerNormFunction``)."""
    hidden = _norm_size(normalized_shape)
    shape = x.shape
    xhat = _normalize(_as_2d(x, hidden), eps, False, implementation)
    return xhat.reshape(shape)


def fused_layer_norm_affine(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    bias: jnp.ndarray,
    normalized_shape: Union[int, Sequence[int]],
    eps: float = 1e-5,
    implementation: Optional[str] = None,
) -> jnp.ndarray:
    """Affine fused layer norm (reference: ``FusedLayerNormAffineFunction``).

    Output dtype follows the input; affine math runs in fp32.
    """
    hidden = _norm_size(normalized_shape)
    shape = x.shape
    xhat = _normalize(_as_2d(x, hidden), eps, False, implementation)
    out = (
        xhat.astype(jnp.float32) * weight.reshape(-1).astype(jnp.float32)
        + bias.reshape(-1).astype(jnp.float32)
    )
    return out.astype(x.dtype).reshape(shape)


def mixed_dtype_fused_layer_norm_affine(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    bias: jnp.ndarray,
    normalized_shape: Union[int, Sequence[int]],
    eps: float = 1e-5,
    implementation: Optional[str] = None,
) -> jnp.ndarray:
    """Megatron "mixed dtypes" variant: input dtype may differ from param
    dtype; output follows the *weight* dtype (reference:
    apex/normalization/fused_layer_norm.py ``MixedFusedLayerNorm`` via
    ``forward_affine_mixed_dtypes``)."""
    hidden = _norm_size(normalized_shape)
    shape = x.shape
    xhat = _normalize(_as_2d(x, hidden), eps, False, implementation)
    out = (
        xhat.astype(jnp.float32) * weight.reshape(-1).astype(jnp.float32)
        + bias.reshape(-1).astype(jnp.float32)
    )
    return out.astype(weight.dtype).reshape(shape)


def fused_rms_norm(
    x: jnp.ndarray,
    normalized_shape: Union[int, Sequence[int]],
    eps: float = 1e-5,
    implementation: Optional[str] = None,
) -> jnp.ndarray:
    hidden = _norm_size(normalized_shape)
    shape = x.shape
    xhat = _normalize(_as_2d(x, hidden), eps, True, implementation)
    return xhat.reshape(shape)


def fused_rms_norm_affine(
    x: jnp.ndarray,
    weight: jnp.ndarray,
    normalized_shape: Union[int, Sequence[int]],
    eps: float = 1e-5,
    implementation: Optional[str] = None,
) -> jnp.ndarray:
    hidden = _norm_size(normalized_shape)
    shape = x.shape
    xhat = _normalize(_as_2d(x, hidden), eps, True, implementation)
    out = xhat.astype(jnp.float32) * weight.reshape(-1).astype(jnp.float32)
    return out.astype(x.dtype).reshape(shape)
