"""Fused scale + mask + softmax kernels.

Capability match for the reference's Megatron softmax extensions
``scaled_masked_softmax_cuda`` and ``scaled_upper_triang_masked_softmax_cuda``
(reference: csrc/megatron/scaled_masked_softmax.h,
csrc/megatron/scaled_upper_triang_masked_softmax.h, python dispatch at
apex/transformer/functional/fused_softmax.py:21-199), re-designed for TPU:

- softmax statistics always in fp32 (the kernels' accumulation contract),
- one ``custom_vjp`` shared by the Pallas TPU kernel and the XLA fallback,
  with the fused backward ``dx = scale * y * (dy - sum(dy * y))`` the CUDA
  backward kernels compute in one pass,
- masking semantics match the reference: mask entries that are *True* are
  masked **out** (filled with -10000 before softmax), and the causal
  variant masks the strict upper triangle.

Unlike the CUDA kernels there is no shape eligibility window
(16 < sk <= 2048, sq % 4 == 0, ...): the Pallas kernel tiles any shape and
the XLA path handles the rest, so ``is_kernel_available`` is about
platform, not shape.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.ops.common import run_kernel, shape_struct
from apex_tpu.telemetry.spans import kernel_name

__all__ = [
    "scaled_softmax",
    "scaled_masked_softmax",
    "scaled_upper_triang_masked_softmax",
]

_MASK_FILL = -10000.0


# ---------------------------------------------------------------------------
# Pallas forward kernel (causal / unmasked; rows tiled into VMEM)
# ---------------------------------------------------------------------------


def _softmax_fwd_kernel(x_ref, o_ref, *, scale, causal, block_q):
    """One (1, block_q, sk) tile: scale, optional causal mask, softmax.

    Rows are query positions; the causal mask for global query row ``q``
    keeps keys ``k <= q``, matching the reference's upper-triangular fill
    (reference: csrc/megatron/scaled_upper_triang_masked_softmax.h).
    """
    j = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32) * scale  # (block_q, sk)
    if causal:
        q_idx = j * block_q + jax.lax.broadcasted_iota(
            jnp.int32, x.shape, 0
        )
        k_idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(k_idx > q_idx, _MASK_FILL, x)
    x = x - jnp.max(x, axis=-1, keepdims=True)
    ex = jnp.exp(x)
    o_ref[0] = (ex / jnp.sum(ex, axis=-1, keepdims=True)).astype(o_ref.dtype)


from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    """Run Pallas kernels in interpreter mode off-TPU so the kernel body
    is still exercised (and testable) on CPU."""
    from apex_tpu.utils.platform import is_tpu

    return not is_tpu()


def _softmax_fwd_pallas(x3d: jnp.ndarray, scale: float, causal: bool):
    m, sq, sk = x3d.shape
    block_q = max(8, min(256, sq))
    pad = (-sq) % block_q
    if pad:
        x3d = jnp.pad(x3d, ((0, 0), (0, pad), (0, 0)))
    padded_sq = sq + pad
    grid = (m, padded_sq // block_q)
    out = pl.pallas_call(
        functools.partial(
            _softmax_fwd_kernel, scale=scale, causal=causal, block_q=block_q
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, block_q, sk), lambda i, j: (i, j, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, sk), lambda i, j: (i, j, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=shape_struct((m, padded_sq, sk), x3d.dtype, x3d),
        interpret=_interpret(),
        name=kernel_name("softmax.fwd"),
    )(x3d)
    if pad:
        out = out[:, :sq]
    return out


def _softmax_fwd_xla(
    x3d: jnp.ndarray,
    scale: float,
    causal: bool,
    mask: Optional[jnp.ndarray],
):
    x = x3d.astype(jnp.float32) * scale
    if causal:
        sq, sk = x.shape[-2:]
        q_idx = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_idx = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        x = jnp.where(k_idx > q_idx, _MASK_FILL, x)
    if mask is not None:
        x = jnp.where(mask, _MASK_FILL, x)
    x = x - jnp.max(x, axis=-1, keepdims=True)
    ex = jnp.exp(x)
    return (ex / jnp.sum(ex, axis=-1, keepdims=True)).astype(x3d.dtype)


def _softmax_fwd(x3d, mask, scale, causal, implementation):
    from apex_tpu.ops.common import KernelLoweringError

    if implementation == "pallas" and mask is not None:
        # no pallas kernel exists for the arbitrary-mask variant — honor
        # the no-silent-degradation contract by saying so loudly
        raise KernelLoweringError(
            "the masked softmax variant has no Pallas kernel (mask fusion "
            "is already optimal in XLA, and the in-kernel masked fast "
            "path is flash attention's segment-id/bias support); use "
            "implementation='xla' or drop the explicit request"
        )
    # Auto mode routes to XLA *by measurement*: standalone softmax is
    # bandwidth-bound and XLA's fused max/exp/sum pipeline beats the
    # Pallas tile kernel by ~1.3x on v5e (see KERNELS_TPU.json).  The
    # kernel stays available via implementation='pallas' for the
    # cross-check tier; the fast path that matters for attention is the
    # flash kernel, which supersedes this op entirely.
    impl = implementation or "xla"
    if mask is not None:
        # the padded-mask variant is XLA-only by design: XLA fuses the
        # mask+softmax chain optimally, and the arbitrary-mask fast path
        # in this library is the flash-attention kernel's segment-id /
        # bias support, not this op
        impl = "xla"
    return run_kernel(
        "scaled_softmax",
        lambda: _softmax_fwd_pallas(x3d, scale, causal),
        lambda: _softmax_fwd_xla(x3d, scale, causal, mask),
        impl,
    )


# ---------------------------------------------------------------------------
# custom_vjp core.  mask is a (differentiation-constant) positional arg so
# one vjp serves the causal, padded and unmasked variants.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_softmax(x3d, mask, scale: float, causal: bool,
                   implementation: Optional[str]):
    return _softmax_fwd(x3d, mask, scale, causal, implementation)


def _fused_softmax_fwd(x3d, mask, scale, causal, implementation):
    y = _softmax_fwd(x3d, mask, scale, causal, implementation)
    return y, y


def _fused_softmax_bwd(scale, causal, implementation, y, dy):
    """Fused softmax backward: ``dx = scale * y * (dy - sum(dy*y))``
    (reference: csrc/megatron/scaled_masked_softmax.h backward kernel)."""
    yf = y.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    inner = jnp.sum(dyf * yf, axis=-1, keepdims=True)
    dx = (scale * yf * (dyf - inner)).astype(y.dtype)
    return (dx, None)


_fused_softmax.defvjp(_fused_softmax_fwd, _fused_softmax_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _as_3d(x: jnp.ndarray):
    sq, sk = x.shape[-2:]
    return x.reshape(-1, sq, sk)


def scaled_softmax(
    x: jnp.ndarray,
    scale: float = 1.0,
    implementation: Optional[str] = None,
) -> jnp.ndarray:
    """``softmax(scale * x)`` over the last dim, fp32 internals
    (reference: ``scaled_softmax_cuda`` path of
    apex/transformer/functional/fused_softmax.py:98-112)."""
    shape = x.shape
    return _fused_softmax(
        _as_3d(x), None, float(scale), False, implementation
    ).reshape(shape)


def scaled_masked_softmax(
    x: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    scale: float = 1.0,
    causal: bool = False,
    implementation: Optional[str] = None,
) -> jnp.ndarray:
    """``softmax(scale * x + mask_fill)`` where True mask entries are
    masked out (reference: ``ScaledMaskedSoftmax``,
    apex/transformer/functional/fused_softmax.py:67-95).

    ``x`` is (..., sq, sk); ``mask`` broadcasts against ``x`` (the
    reference uses (b, 1, sq, sk) against (b, np, sq, sk)).
    ``causal=True`` additionally masks the strict upper triangle — the
    composition the reference cannot express in one kernel.
    """
    if mask is None:
        if causal:
            return scaled_upper_triang_masked_softmax(
                x, scale, implementation
            )
        return scaled_softmax(x, scale, implementation)
    shape = x.shape
    mask_b = jnp.broadcast_to(mask, shape).reshape(-1, *shape[-2:])
    return _fused_softmax(
        _as_3d(x), mask_b, float(scale), causal, implementation
    ).reshape(shape)


def scaled_upper_triang_masked_softmax(
    x: jnp.ndarray,
    scale: float = 1.0,
    implementation: Optional[str] = None,
) -> jnp.ndarray:
    """Causal ``softmax(scale * x)`` masking the strict upper triangle
    (reference: ``ScaledUpperTriangMaskedSoftmax``,
    apex/transformer/functional/fused_softmax.py:21-49)."""
    shape = x.shape
    return _fused_softmax(
        _as_3d(x), None, float(scale), True, implementation
    ).reshape(shape)
