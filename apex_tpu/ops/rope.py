"""Rotary position embeddings (RoPE), fused by XLA.

Closes the reference fork's mentioned-but-absent rope capability
(reference: SURVEY.md §2.1 "transformer.layers (fused RoPE note)" — the
fork's BASELINE mentions rope, but csrc/megatron ships only softmax
kernels).  TPU design note: RoPE is a pure elementwise rotation of the
(q, k) projections, so the right "fused kernel" on TPU is none at all —
XLA fuses the rotate into the projection epilogue / attention prologue,
and a hand-written Pallas kernel could only add launch overhead (same
decision record as layer norm / softmax, docs/kernels.md).

Convention: half-split rotate (Llama/NeoX style) — the head dim is
split into two halves forming (x1, x2) pairs rotated by
position-dependent angles; frequencies follow the original RoPE
geometric ladder ``base**(-2i/d)``.  Trig runs in fp32 regardless of
the activation dtype (bf16 angles visibly drift past ~2k positions),
and the rotation is applied in fp32 then cast back.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "rope_cos_sin", "apply_rope", "apply_rope_tables", "rope_table",
    "apply_rope_at", "yarn_inv_freq", "yarn_mscale", "yarn_table",
]


def rope_cos_sin(
    positions: jnp.ndarray, head_dim: int, base: float = 10000.0
):
    """(cos, sin) tables for ``positions`` (any shape, int), each of
    shape ``positions.shape + (head_dim // 2,)``, fp32."""
    if head_dim % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {head_dim}")
    half = head_dim // 2
    inv_freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(
    x: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
    *,
    base: float = 10000.0,
    position_offset: int = 0,
) -> jnp.ndarray:
    """Rotate ``x`` of shape (..., seq, head_dim) by its positions.

    ``positions`` defaults to ``offset + arange(seq)`` —
    ``position_offset`` is the context-parallel hook: cp rank r passes
    ``r * local_seq`` so its sequence chunk is rotated by GLOBAL
    positions (the same contract as the learned table's ``_pos_slice``,
    models/gpt.py).  Output dtype matches the input.
    """
    seq, d = x.shape[-2], x.shape[-1]
    if positions is None:
        positions = position_offset + jnp.arange(seq, dtype=jnp.int32)
    cos, sin = rope_cos_sin(positions, d, base)  # (seq, d/2) fp32
    return apply_rope_tables(x, cos, sin)


def apply_rope_tables(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Rotate by PRECOMPUTED (cos, sin) tables of shape (seq, d/2).

    Separate entry so callers scanning over layers (models/gpt.py) can
    compute the trig once and close over the tables — a scan body can't
    hoist the iota+trig itself, so the fused form would re-run it every
    layer and again in the remat backward."""
    d = x.shape[-1]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Incremental decode: position-indexed application + cached tables
# ---------------------------------------------------------------------------

#: (max_len, head_dim, dtype_name, base) -> (cos, sin) tables.  Decode
#: calls rotate ONE position per sequence per step; recomputing the
#: trig ladder every step would put an iota+cos+sin chain in front of
#: every cache write, so the full table is built once per
#: (max_len, dim, dtype) and the per-step work is a row gather.
_TABLE_CACHE: dict = {}


def rope_table(
    max_len: int, head_dim: int, dtype: Any = jnp.float32,
    base: float = 10000.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cached ``(cos, sin)`` tables of shape ``(max_len, head_dim//2)``,
    keyed by ``(max_len, head_dim, dtype, base)``.  Rows are computed by
    the same formula :func:`rope_cos_sin` evaluates, so gathering row
    ``p`` is BIT-identical to computing position ``p`` directly (the
    incremental-vs-full-sequence identity tests/test_rope.py pins).

    ``dtype`` below fp32 trades table bytes for the documented >2k-
    position drift (module docstring) — fp32 is the default for a
    reason."""
    key = (int(max_len), int(head_dim), jnp.dtype(dtype).name,
           float(base))
    hit = _TABLE_CACHE.get(key)
    if hit is None:
        # eager even under an active jit trace (GPTModel.decode_step
        # calls this while being traced): without the escape the cached
        # values would be TRACERS, poisoning every later trace that
        # reads the cache (UnexpectedTracerError)
        with jax.ensure_compile_time_eval():
            cos, sin = rope_cos_sin(
                jnp.arange(max_len, dtype=jnp.int32), head_dim, base
            )
            hit = (cos.astype(dtype), sin.astype(dtype))
        _TABLE_CACHE[key] = hit
    return hit


def apply_rope_at(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    base: float = 10000.0,
    max_len: Optional[int] = None,
    tables: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> jnp.ndarray:
    """Rotate ``x`` at ARBITRARY per-sequence positions — the
    incremental-decode entry: each serving slot sits at its own offset
    and advances one position per step, so the full-sequence
    ``apply_rope`` (whole-table recompute, shared positions) does not
    fit.

    ``positions`` is ``(s,)`` (shared across the batch, any ``x``
    layout ``(..., s, d)``) or ``(b, s)`` (per-sequence, ``x`` then
    ``(b, h, s, d)``).  Tables come from ``tables=`` or the
    :func:`rope_table` cache when ``max_len`` is given; with neither,
    the trig is computed directly for just these positions
    (:func:`rope_cos_sin`) — all three sources are bit-identical."""
    d = x.shape[-1]
    positions = jnp.asarray(positions)
    if tables is None and max_len is not None:
        tables = rope_table(max_len, d, base=base)
    if tables is not None:
        cos = jnp.take(tables[0], positions, axis=0).astype(jnp.float32)
        sin = jnp.take(tables[1], positions, axis=0).astype(jnp.float32)
    else:
        cos, sin = rope_cos_sin(positions, d, base)
    if positions.ndim == 2:
        if x.ndim != 4:
            raise ValueError(
                f"per-sequence (b, s) positions need x of shape "
                f"(b, h, s, d), got {x.shape}"
            )
        cos, sin = cos[:, None], sin[:, None]   # broadcast over heads
    return apply_rope_tables(x, cos, sin)


# ---------------------------------------------------------------------------
# YaRN: frequencies blended for a context stretched past the trained one
# ---------------------------------------------------------------------------


def yarn_inv_freq(
    head_dim: int, *, base: float = 10000.0, factor: float,
    beta_fast: float, beta_slow: float, original_max_position: int,
):
    """The ``head_dim // 2`` rotary frequencies under YaRN
    (arXiv:2309.00071 as DeepSeek-V2/V3 apply it): ``theta_i`` below
    the correction dimension of ``beta_fast`` (wavelengths that fit the
    trained context many times), ``theta_i / factor`` above that of
    ``beta_slow``, a linear ramp between the two.  A numpy float64
    array: it is a constant of the model, computed once."""
    import math

    import numpy as np

    d = int(head_dim)
    theta = 1.0 / float(base) ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def correction_dim(rotations: float) -> float:
        return d * math.log(original_max_position
                            / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return theta * (1.0 - ramp) + theta / float(factor) * ramp


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``m = 0.1 * mscale * ln(factor) +
    1``; MLA multiplies its softmax scale by ``m ** 2``."""
    import math

    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_table(max_len: int, inv_freq) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """fp32 ``(cos, sin)`` tables ``(max_len, len(inv_freq))`` for
    :func:`apply_rope_tables`, rows gathered by position."""
    angles = (jnp.arange(max_len, dtype=jnp.float32)[:, None]
              * jnp.asarray(inv_freq, jnp.float32))
    return jnp.cos(angles), jnp.sin(angles)
