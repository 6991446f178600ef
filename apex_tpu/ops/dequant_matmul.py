"""Weight-dequantizing matmul: stream int8/int4 weights, dequantize in
VMEM, never materialize the wide matrix in HBM.

Decode at small batch is weight-streaming-bound: every generated token
reads every matmul weight of the model once, so the projection/FFN dots
run at HBM bandwidth and their cost is simply *bytes of weights*.  The
paged-attention kernel already streams its K/V pool as int8 and
rescales per block inside the tile (``attention_decode._decode_kernel``
— ``kh * repeat(ks, kv_block)`` right before the dot); this module
lifts exactly that pattern to the QKV / output-projection / FFN dots:

- weights live in HBM as block-wise int8 (:func:`quantize_rows`) or
  packed int4 (:func:`quantize_rows_int4` — two nibbles per byte,
  halves layout, per-block fp32 scales);
- each kernel program DMAs ONE narrow weight tile into VMEM,
  dequantizes it there (``q * repeat(scales, block)``, plus the
  shift-free nibble sign-extend for int4) and feeds the MXU;
- the fp32/bf16 weight never exists anywhere — not in HBM, not as a
  whole in VMEM — so the decode roofline drops to 1/4 (int8) or 1/8
  (int4) of the fp32 byte stream, and the same drop applies to the
  largest model a chip can SERVE (tools/memory_audit.py --serve).

The XLA fallback is the literal dequantize-then-dot (the reference the
kernel-validation gate compares against): same math, but it
materializes the wide matrix as an XLA temp.  Dispatch follows the
package's kernel contract (:func:`apex_tpu.ops.common.run_kernel`):
auto mode falls back with a logged warning, explicit
``implementation="pallas"`` raises on lowering failure.

Layout contract (what the tiling assumes, validated loudly):

- int8: ``qweight (k, n) int8``, ``scales (k, n / block) fp32`` —
  blocks along the OUTPUT features, whole blocks only (the
  ``quantize_rows(leaf=...)`` strict mode enforces this at the
  weight-pool seam);
- int4: ``qweight (k, n / 2) int8`` packed bytes (:func:`pack_int4`'s
  halves layout: low nibble = output column ``c``, high nibble =
  column ``c + n/2``), ``scales (k, n / block) fp32``, ``n`` a
  multiple of ``2 * block`` so each half holds whole scale blocks.
  The kernel writes a ``(2, m, n/2)`` output — one slab per nibble
  half — and the wrapper concatenates them back to ``(m, n)``, so no
  lane-dim interleave ever happens on device.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.attention import _interpret
from apex_tpu.ops.common import run_kernel, shape_struct
from apex_tpu.ops.quantization import (
    dequantize_rows,
    quantize_rows,
    quantize_rows_int4,
    unpack_int4,
)
from apex_tpu.telemetry.spans import kernel_name
from apex_tpu.utils.platform import default_implementation

__all__ = [
    "dequant_matmul",
    "dequant_matmul_reference",
    "quantize_weight",
    "dequantize_weight",
    "weight_pool_dtype",
    "weight_pool_block",
]

#: per-program f32 dequant-tile budget (elements): bounds the widest
#: output tile so k x bn x 4 bytes of dequantized weight stays well
#: under the ~16 MB VMEM core budget next to x, the int tile and the
#: accumulator
_TILE_ELEMS = 1 << 20


def _pick_bn(n: int, bs: int, k: int) -> int:
    """Output-tile width: the largest multiple of ``bs`` that divides
    ``n`` and keeps the dequantized f32 tile under the VMEM budget
    (floor: one scale block per program)."""
    cap = max(bs, (_TILE_ELEMS // max(k, 1)) // bs * bs)
    bn = bs
    m = n // bs
    for t in range(1, m + 1):
        w = t * bs
        if w > cap:
            break
        if n % w == 0:
            bn = w
    return bn


# ------------------------------------------------------------ kernels
def _int8_kernel(x_ref, w_ref, s_ref, o_ref, *, block_size):
    xi = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    w = w * jnp.repeat(s_ref[...], block_size, axis=1)
    o_ref[...] = jax.lax.dot_general(
        xi, w, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _int4_kernel(x_ref, p_ref, s_ref, o_ref, *, block_size):
    xi = x_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.int32) & 0xFF
    lo = (((p & 0xF) ^ 8) - 8).astype(jnp.float32)
    hi = ((((p >> 4) & 0xF) ^ 8) - 8).astype(jnp.float32)
    s = s_ref[...]                       # (k, 2, nb_tile)
    dot = lambda a, b: jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = dot(xi, lo * jnp.repeat(s[:, 0], block_size, axis=1))
    o_ref[1] = dot(xi, hi * jnp.repeat(s[:, 1], block_size, axis=1))


def _int8_pallas(x, qw, scales, block_size):
    m, k = x.shape
    _, n = qw.shape
    bn = _pick_bn(n, block_size, k)
    nbt = bn // block_size
    out = pl.pallas_call(
        functools.partial(_int8_kernel, block_size=block_size),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),
            pl.BlockSpec((k, bn), lambda j: (0, j)),
            pl.BlockSpec((k, nbt), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j: (0, j)),
        out_shape=shape_struct((m, n), jnp.float32, x, qw, scales),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=_interpret(),
        name=kernel_name("dequant_matmul.int8"),
    )(x, qw, scales)
    return out.astype(x.dtype)


def _int4_pallas(x, qp, scales, block_size):
    m, k = x.shape
    _, n2 = qp.shape
    nb = scales.shape[1]
    bn = _pick_bn(n2, block_size, k)
    nbt = bn // block_size
    s3 = scales.reshape(k, 2, nb // 2)
    out = pl.pallas_call(
        functools.partial(_int4_kernel, block_size=block_size),
        grid=(n2 // bn,),
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),
            pl.BlockSpec((k, bn), lambda j: (0, j)),
            pl.BlockSpec((k, 2, nbt), lambda j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((2, m, bn), lambda j: (0, 0, j)),
        out_shape=shape_struct((2, m, n2), jnp.float32, x, qp, scales),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=_interpret(),
        name=kernel_name("dequant_matmul.int4"),
    )(x, qp, s3)
    # the halves layout: slab 0 = output columns [0, n/2), slab 1 =
    # [n/2, n) — one concat restores the original order
    return jnp.concatenate([out[0], out[1]], axis=-1).astype(x.dtype)


# ----------------------------------------------------------- XLA path
def dequant_matmul_reference(x, qweight, scales, *, weight_dtype,
                             block_size):
    """The dequantize-then-dot reference: materialize the wide matrix
    (as an XLA temp) and run a plain dot — the baseline the
    never-lose-to-XLA kernel-validation gate compares against, and the
    auto-mode fallback off-TPU."""
    if weight_dtype == "int8":
        w = dequantize_rows(qweight, scales, block_size)
    else:
        w = dequantize_rows(unpack_int4(qweight), scales, block_size)
    out = jax.lax.dot_general(
        x.astype(jnp.float32), w,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return out.astype(x.dtype)


# ------------------------------------------------------- public entry
def dequant_matmul(
    x: jnp.ndarray,
    qweight: jnp.ndarray,
    scales: jnp.ndarray,
    *,
    weight_dtype: str,
    block_size: Optional[int] = None,
    implementation: Optional[str] = None,
) -> jnp.ndarray:
    """``x @ W`` where ``W`` lives as block-quantized int8 or packed
    int4 and is dequantized inside the matmul tiles.

    ``x (..., k)`` activations (fp32/bf16); ``qweight`` int8 — shape
    ``(k, n)`` for ``weight_dtype="int8"``, ``(k, n / 2)`` packed for
    ``"int4"``; ``scales (k, n / block_size)`` fp32.  ``block_size``
    defaults to the value the scale shape implies.  Returns
    ``(..., n)`` in ``x``'s dtype.  ``implementation``: None = auto
    (Pallas on TPU, XLA elsewhere), ``"pallas"``/``"xla"`` force."""
    if weight_dtype not in ("int8", "int4"):
        raise ValueError(
            f"weight_dtype must be 'int8' or 'int4', got "
            f"{weight_dtype!r}")
    if qweight.dtype != jnp.int8:
        raise ValueError(
            f"qweight must be int8 storage, got {qweight.dtype}")
    if qweight.ndim != 2 or scales.ndim != 2:
        raise ValueError(
            f"qweight/scales must be 2-D, got {qweight.shape} / "
            f"{scales.shape}")
    k = x.shape[-1]
    if qweight.shape[0] != k or scales.shape[0] != k:
        raise ValueError(
            f"contraction mismatch: x (..., {k}) vs qweight "
            f"{tuple(qweight.shape)} / scales {tuple(scales.shape)}")
    nb = scales.shape[1]
    n = qweight.shape[1] * (2 if weight_dtype == "int4" else 1)
    if nb < 1 or n % nb:
        raise ValueError(
            f"scales ({nb} blocks) do not tile the {n} output "
            f"features evenly")
    bs = n // nb
    if block_size is not None and int(block_size) != bs:
        raise ValueError(
            f"block_size={block_size} disagrees with the scale shape "
            f"({nb} blocks over {n} features imply {bs})")
    if weight_dtype == "int4" and (nb % 2 or (n // 2) % bs):
        raise ValueError(
            f"int4 halves layout needs whole scale blocks per half: "
            f"n={n} features, block_size={bs} "
            f"({nb} blocks — need an even count per half)")

    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    impl = implementation or default_implementation()

    def _pallas():
        if weight_dtype == "int8":
            return _int8_pallas(x2, qweight, scales, bs)
        return _int4_pallas(x2, qweight, scales, bs)

    def _xla():
        return dequant_matmul_reference(
            x2, qweight, scales, weight_dtype=weight_dtype,
            block_size=bs)

    out = run_kernel("dequant_matmul", _pallas, _xla, impl)
    return out.reshape(*lead, n)


# ----------------------------------------------- weight-pool builders
def quantize_weight(w: jnp.ndarray, weight_dtype: str,
                    block_size: int = 128, *,
                    leaf: str = "weight") -> Dict[str, jnp.ndarray]:
    """ONE ``(k, n)`` weight matrix → its quantized-pool leaf: ``{"q8":
    values, "scales": ...}`` for int8, ``{"q4": packed, "scales": ...}``
    for int4.  The dict KEY is the static type marker — the serving
    forward dispatches on pytree structure, so quantized and
    full-width params trace to different (correct) programs with no
    dynamic flag threading.  ``leaf`` names the weight in the strict
    block-validation errors."""
    if weight_dtype == "int8":
        q, s = quantize_rows(w, block_size, leaf=leaf)
        return {"q8": q, "scales": s}
    if weight_dtype == "int4":
        q, s = quantize_rows_int4(w, block_size, leaf=leaf)
        return {"q4": q, "scales": s}
    raise ValueError(
        f"weight_dtype must be 'int8' or 'int4', got {weight_dtype!r}")


def weight_pool_dtype(wq: Dict[str, Any]) -> str:
    """``"int8"`` / ``"int4"`` from a quantized-pool leaf's marker key."""
    if "q8" in wq:
        return "int8"
    if "q4" in wq:
        return "int4"
    raise ValueError(
        f"not a quantized weight leaf (no 'q8'/'q4' key): "
        f"{sorted(wq)}")


def weight_pool_block(wq: Dict[str, Any]) -> int:
    """The block size a quantized-pool leaf was built with, recovered
    from its shapes (the static info rides in the pytree, never as a
    side-channel flag)."""
    wd = weight_pool_dtype(wq)
    q = wq["q8"] if wd == "int8" else wq["q4"]
    n = q.shape[-1] * (2 if wd == "int4" else 1)
    return n // wq["scales"].shape[-1]


def dequantize_weight(wq: Dict[str, Any],
                      dtype: Any = jnp.float32) -> jnp.ndarray:
    """Materialize a quantized-pool leaf back to a wide matrix — the
    reference/debug path only; the serving forward never calls this."""
    wd = weight_pool_dtype(wq)
    bs = weight_pool_block(wq)
    q = wq["q8"] if wd == "int8" else unpack_int4(wq["q4"])
    return dequantize_rows(q, wq["scales"], bs, dtype)
