"""Block-wise int8 quantization for compressed gradient collectives.

The hierarchical RS(ici) → AR(dcn) → AG(ici) decomposition
(:func:`apex_tpu.parallel.distributed._hierarchical_psum`) already cuts
DCN traffic to 1/ici of the gradient — but every byte that does cross
the slow axis is still full-width.  EQuARX (PAPERS.md) shows a
block-quantized all-reduce recovers most of that bandwidth on XLA/TPU
with negligible quality loss, and the adaptive-summation line of work
(Maleki et al.) is why any lossy reduction here carries an explicit
error-feedback residual: the quantization error of step *t* is added
back into the gradient of step *t+1*, so the bias is compensated
instead of accumulated.

This module is the numeric core plus the one compressed collective:

- :func:`quantize_blockwise` / :func:`dequantize_blockwise` — flat
  int8 values with one fp32 scale per ``block_size`` elements,
  deterministic (round-half-even) or stochastic rounding, bf16/fp32
  in/out;
- :class:`CompressionConfig` — the ``compression=`` knob's value
  (the string ``"int8"`` is accepted everywhere as the default config);
- :func:`quantized_psum` — an int8 all-reduce over ONE mesh axis,
  built for the DCN leg: quantize once, exchange int8 + scales with
  ``all_to_all`` (the reduce-scatter phase), accumulate the exact
  int8 x fp32-scale products, re-quantize the reduced shard once, and
  ``all_gather`` int8 + scales back.  Only the tiny fp32 scale
  sidecar (``4 / block_size`` bytes per element) crosses the axis at
  full width, so bytes-on-wire drop ~4x vs an fp32 psum;
- :func:`quantized_reduce_scatter` / :func:`quantized_all_gather` —
  the EQuARX ICI half: the same int8-values + fp32-scales wire format
  applied to ONE leg each, chunk-preserving (rank *r* receives exactly
  the elements ``lax.psum_scatter(tiled)`` would give it, for any
  chunk size — blocks never straddle row boundaries, so enabling
  compression never moves a shard boundary).  ``CompressionConfig(
  ici_legs=True)`` makes the hierarchical reduce run BOTH its ICI
  legs through these (see ``_hierarchical_psum``), with their own
  error-feedback residuals (``ici_push`` / ``ici_pull``) beside the
  DCN pair.

Deviation from the ISSUE's "(int32-accumulated values, scales)"
sketch: each sender keeps its OWN per-block scales (no extra
max-scale collective on the slow axis, and a small-magnitude sender
is not coarsened by a large-magnitude peer's amax); the receiver then
accumulates ``int8 * fp32_scale`` products, which is at least as
accurate as sharing scales and summing in int32, for any axis size
that fits training practice.

Everything here is pure ``jnp``/``lax`` — the collective must be
called inside ``shard_map`` (or ``pmap``) with the axis bound.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp

__all__ = [
    "CompressionConfig",
    "as_compression_config",
    "quantize_blockwise",
    "dequantize_blockwise",
    "quantize_rows",
    "dequantize_rows",
    "pack_int4",
    "unpack_int4",
    "quantize_rows_int4",
    "dequantize_rows_int4",
    "comm_residual_sizes",
    "hierarchical_residual_sizes",
    "zero3_residual_sizes",
    "init_residual",
    "quantized_psum",
    "quantized_reduce_scatter",
    "quantized_all_gather",
]

_INT8_MAX = 127.0
_INT4_MAX = 7.0


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Configuration for compressed (quantized) collectives.

    ``method``: only ``"int8"`` today.  ``block_size``: elements per
    fp32 scale (wire overhead = 4/block_size bytes per element).
    ``rounding``: ``"nearest"`` (deterministic, round-half-even) or
    ``"stochastic"`` (unbiased; pass a fresh ``key`` per step, or
    thread comm state so the built-in step counter derives one).
    ``error_feedback``: carry the per-device quantization residual as
    explicit state and add it back next step (strongly recommended for
    training; requires the caller to thread a state pytree).
    ``ici_legs``: ALSO compress the reduce-scatter/all-gather legs of
    the hierarchical reduce (EQuARX's ICI half) — default off, which
    leaves those legs full-width exactly as before; with error
    feedback the residual state then carries two extra buffers
    (``ici_push``/``ici_pull``) and must be rebuilt with the same
    config (:func:`~apex_tpu.parallel.distributed.init_comm_state`
    sizes them from the config automatically).
    """

    method: str = "int8"
    block_size: int = 256
    rounding: str = "nearest"
    error_feedback: bool = True
    ici_legs: bool = False

    def __post_init__(self):
        if self.method != "int8":
            raise ValueError(
                f"unsupported compression method {self.method!r} "
                "(only 'int8')"
            )
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        if self.rounding not in ("nearest", "stochastic"):
            raise ValueError(
                f"rounding must be 'nearest' or 'stochastic', got "
                f"{self.rounding!r}"
            )


def as_compression_config(
    compression: Union[None, str, CompressionConfig]
) -> Optional[CompressionConfig]:
    """Normalize the ``compression=`` knob: None | "int8" | config."""
    if compression is None:
        return None
    if isinstance(compression, CompressionConfig):
        return compression
    if isinstance(compression, str):
        return CompressionConfig(method=compression)
    raise ValueError(
        f"compression must be None, 'int8' or a CompressionConfig, got "
        f"{compression!r}"
    )


def _blocks(flat: jnp.ndarray, block_size: int) -> jnp.ndarray:
    n = flat.size
    pad = (-n) % block_size
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.zeros((pad,), flat.dtype)]
        )
    return flat.reshape(-1, block_size)


def quantize_blockwise(
    x: jnp.ndarray,
    block_size: int = 256,
    rounding: str = "nearest",
    key: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize to int8 with one fp32 scale per block.

    ``x`` (any shape, bf16/fp32) is flattened; blocks of
    ``block_size`` elements share ``scale = max|block| / 127``
    (all-zero blocks get scale 1 so dequantization is exact).
    Returns ``(values, scales)``: ``values`` int8 with ``x``'s shape,
    ``scales`` fp32 of shape ``(ceil(x.size / block_size),)``.

    ``rounding="nearest"`` is deterministic (ties to even);
    ``"stochastic"`` computes ``floor(v + u)``, ``u ~ U[0, 1)`` from
    ``key`` (required), which is unbiased: ``E[q] = v``.
    """
    shape = x.shape
    xf = _blocks(x.reshape(-1).astype(jnp.float32), block_size)
    amax = jnp.max(jnp.abs(xf), axis=1)
    scales = jnp.where(amax > 0.0, amax / _INT8_MAX, 1.0)
    v = jnp.clip(xf / scales[:, None], -_INT8_MAX, _INT8_MAX)
    if rounding == "stochastic":
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        u = jax.random.uniform(key, v.shape, jnp.float32)
        q = jnp.floor(v + u)
    else:
        q = jnp.round(v)
    q = jnp.clip(q, -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
    return q.reshape(-1)[: int(jnp.size(x))].reshape(shape), scales


def dequantize_blockwise(
    values: jnp.ndarray,
    scales: jnp.ndarray,
    block_size: int = 256,
    dtype: Any = jnp.float32,
) -> jnp.ndarray:
    """Inverse of :func:`quantize_blockwise` (up to rounding error)."""
    shape = values.shape
    q = _blocks(values.reshape(-1).astype(jnp.float32), block_size)
    out = q * scales[:, None]
    return out.reshape(-1)[: int(jnp.size(values))].reshape(shape).astype(
        dtype
    )


def _check_row_blocks(n: int, block_size: int, leaf: Optional[str],
                      shape) -> None:
    """The weight-pool seam's block validation: a block size that does
    not divide the row length would silently pad (fine for the
    collectives, which own both ends of the wire) but corrupts a
    weight whose kernel tiles assume whole blocks.  Callers that name
    their ``leaf`` opt into the strict contract and get an actionable
    error instead of a reshape traceback deep inside a jit."""
    if leaf is None:
        return
    if block_size < 1 or n % block_size:
        raise ValueError(
            f"block_size={block_size} does not divide the row length "
            f"of leaf {leaf!r} (shape {tuple(shape)}, rows of "
            f"{n} elements): the in-kernel dequant tiles need whole "
            f"blocks — pick a block_size that divides {n} (e.g. a "
            f"power of two that divides the hidden/ffn width)")


def quantize_rows(
    x: jnp.ndarray,
    block_size: int = 256,
    rounding: str = "nearest",
    key: Optional[jnp.ndarray] = None,
    *,
    leaf: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-ROW block-wise quantize of a 2-D ``(rows, n)`` array: blocks
    never straddle row boundaries, so each row can be exchanged (and
    dequantized) independently of its neighbours — the property the
    chunk-preserving RS/AG legs need.  Same per-block math as
    :func:`quantize_blockwise`; a single row is bit-identical to it.
    Returns ``(values int8 (rows, n), scales fp32 (rows,
    ceil(n/block_size)))``.

    ``leaf`` (the weight-pool seam): when given, ``block_size`` MUST
    divide ``n`` exactly — a violation raises a :class:`ValueError`
    naming the leaf and its shape (the silent zero-padding the
    collectives rely on would desynchronize an in-kernel dequant's
    block tiling)."""
    rows, n = x.shape
    _check_row_blocks(n, block_size, leaf, x.shape)
    nb = max(-(-n // block_size), 1)
    pad = nb * block_size - n
    xf = x.astype(jnp.float32)
    if pad:
        xf = jnp.concatenate(
            [xf, jnp.zeros((rows, pad), jnp.float32)], axis=1
        )
    xb = xf.reshape(rows, nb, block_size)
    amax = jnp.max(jnp.abs(xb), axis=2)
    scales = jnp.where(amax > 0.0, amax / _INT8_MAX, 1.0)
    v = jnp.clip(xb / scales[:, :, None], -_INT8_MAX, _INT8_MAX)
    if rounding == "stochastic":
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        u = jax.random.uniform(key, v.shape, jnp.float32)
        q = jnp.floor(v + u)
    else:
        q = jnp.round(v)
    q = jnp.clip(q, -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
    return q.reshape(rows, nb * block_size)[:, :n], scales


def dequantize_rows(
    values: jnp.ndarray,
    scales: jnp.ndarray,
    block_size: int = 256,
    dtype: Any = jnp.float32,
) -> jnp.ndarray:
    """Inverse of :func:`quantize_rows` (up to rounding error)."""
    rows, n = values.shape
    expand = jnp.repeat(scales, block_size, axis=1)[:, :n]
    return (values.astype(jnp.float32) * expand).astype(dtype)


# --------------------------------------------------------------- int4
def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """Pack int4 values (int8 storage, each in ``[-8, 7]``) two nibbles
    per byte: packed column ``c`` holds column ``c`` in its LOW nibble
    and column ``c + n/2`` in its HIGH nibble (the halves layout).
    Pairing across the row's halves — rather than adjacent columns —
    means :func:`unpack_int4` reassembles the original column order
    with ONE concatenation, no interleave: exactly the shape of op a
    Pallas kernel can run on the lane dimension in VMEM.  Returns int8
    ``(rows, n // 2)``; ``n`` must be even."""
    rows, n = q.shape
    if n % 2:
        raise ValueError(
            f"pack_int4 needs an even row length to pair nibbles, got "
            f"shape {tuple(q.shape)}")
    x = q.astype(jnp.int32)
    lo = x[:, : n // 2] & 0xF
    hi = x[:, n // 2:] & 0xF
    p = lo | (hi << 4)
    # two's-complement re-interpretation into int8 storage (values
    # 128..255 map to -128..-1) — kept deterministic instead of
    # relying on astype overflow behavior
    return jnp.where(p < 128, p, p - 256).astype(jnp.int8)


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_int4`: int8 ``(rows, n/2)`` packed bytes
    → int8 ``(rows, n)`` values in ``[-8, 7]``, exact for every
    nibble.  Sign extension is the shift-free ``(x ^ 8) - 8`` form —
    pure elementwise int ops, VMEM-friendly."""
    x = packed.astype(jnp.int32) & 0xFF
    lo = ((x & 0xF) ^ 8) - 8
    hi = (((x >> 4) & 0xF) ^ 8) - 8
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.int8)


def quantize_rows_int4(
    x: jnp.ndarray,
    block_size: int = 128,
    *,
    leaf: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row block-wise int4 quantize of a 2-D ``(rows, n)`` array:
    the :func:`quantize_rows` discipline at 4-bit width (``scale =
    max|block| / 7``, round-half-even, all-zero blocks get scale 1),
    packed two nibbles per byte by :func:`pack_int4`.  Returns
    ``(packed int8 (rows, n // 2), scales fp32 (rows, n /
    block_size))``.

    Constraints (checked loudly): ``block_size`` must be EVEN — an odd
    block leaves one nibble of every block unpaired, which the
    two-per-byte packing cannot represent; ``n`` must be a multiple of
    ``2 * block_size`` so the packed halves layout keeps whole scale
    blocks inside each half (the in-kernel dequant's tiling contract).
    ``leaf`` names the owning weight in the error message."""
    rows, n = x.shape
    at = "" if leaf is None else f" of leaf {leaf!r}"
    if block_size < 2 or block_size % 2:
        raise ValueError(
            f"int4 block_size must be even (two nibbles per byte — an "
            f"odd block cannot pair its last nibble), got "
            f"{block_size}{at}")
    if n % 2:
        raise ValueError(
            f"int4 quantization needs an even row length{at}, got "
            f"shape {tuple(x.shape)}")
    if n % (2 * block_size):
        raise ValueError(
            f"block_size={block_size} does not tile the int4 halves "
            f"layout{at} (shape {tuple(x.shape)}): the row length "
            f"must be a multiple of 2 * block_size = {2 * block_size} "
            f"so each packed half holds whole scale blocks — pick a "
            f"smaller even block_size that divides {n // 2}")
    nb = n // block_size
    xb = x.astype(jnp.float32).reshape(rows, nb, block_size)
    amax = jnp.max(jnp.abs(xb), axis=2)
    scales = jnp.where(amax > 0.0, amax / _INT4_MAX, 1.0)
    v = jnp.clip(xb / scales[:, :, None], -_INT4_MAX, _INT4_MAX)
    q = jnp.clip(jnp.round(v), -_INT4_MAX, _INT4_MAX).astype(jnp.int8)
    return pack_int4(q.reshape(rows, n)), scales


def dequantize_rows_int4(
    packed: jnp.ndarray,
    scales: jnp.ndarray,
    block_size: int = 128,
    dtype: Any = jnp.float32,
) -> jnp.ndarray:
    """Inverse of :func:`quantize_rows_int4` (up to rounding error)."""
    return dequantize_rows(unpack_int4(packed), scales, block_size,
                           dtype)


def comm_residual_sizes(
    n: int, world: int, block_size: int
) -> Tuple[int, int]:
    """Per-device error-feedback buffer lengths for a
    :func:`quantized_psum` over an ``n``-element array on a
    ``world``-wide axis: ``(padded_total, shard)`` — the ``push``
    residual covers the locally quantized (padded) array, the ``pull``
    residual the re-quantized reduced shard this rank owns."""
    padded = n + (-n) % (world * block_size)
    return padded, padded // world


def hierarchical_residual_sizes(
    n: int, dcn: int, ici: int, block_size: int, ici_legs: bool = False
) -> dict:
    """Per-device error-feedback buffer lengths for ONE leaf of ``n``
    local elements through the hierarchical RS(ici) → AR(dcn) →
    AG(ici) reduce: ``push``/``pull`` compensate the DCN all-reduce's
    two quantization events (unchanged from the DCN-only design), and
    — with ``ici_legs`` — ``ici_push`` covers the full ici-padded
    local buffer quantized before the reduce-scatter while
    ``ici_pull`` covers the owned chunk quantized before the
    all-gather.  The ONE sizing shared by ``init_comm_state``,
    ``bucket_comm_state`` and the trace-time validation."""
    chunk = (n + (-n) % ici) // ici
    padded, shard = comm_residual_sizes(chunk, dcn, block_size)
    sizes = {"push": padded, "pull": shard}
    if ici_legs:
        sizes["ici_push"] = ici * chunk
        sizes["ici_pull"] = chunk
    return sizes


def zero3_residual_sizes(
    n: int, dcn: int, ici: int, block_size: int, ici_legs: bool = False
) -> dict:
    """Per-device error-feedback buffer lengths for ONE ZeRO-3 bucket of
    ``n`` local elements.  The bucket's gradient reduces as RS(ici) →
    AR(dcn) *into the shard* (no grad all-gather — the shard is where
    the update runs), and its PARAMETERS all-gather from the shard on
    use: ``push``/``pull`` compensate the DCN all-reduce of the owned
    chunk exactly as in :func:`hierarchical_residual_sizes`; with
    ``ici_legs``, ``ici_push`` covers the padded local grads quantized
    before the reduce-scatter and ``ag`` covers the param chunk
    quantized before the gather-on-use all-gather (the param-AG leg has
    no analog in the gradient path — it replaces the ZeRO-1 tail
    gather)."""
    chunk = (n + (-n) % ici) // ici
    padded, shard = comm_residual_sizes(chunk, dcn, block_size)
    sizes = {"push": padded, "pull": shard}
    if ici_legs:
        sizes["ici_push"] = ici * chunk
        sizes["ag"] = chunk
    return sizes


def init_residual(
    n: int, world: int, block_size: int = 256
) -> dict:
    """Zero error-feedback state for ONE flat array of ``n`` elements
    reduced over a ``world``-wide axis.  ``push`` compensates the
    first quantization (this rank's contribution), ``pull`` the
    second (the reduced shard this rank re-broadcasts)."""
    padded, shard = comm_residual_sizes(n, world, block_size)
    return {
        "push": jnp.zeros((padded,), jnp.float32),
        "pull": jnp.zeros((shard,), jnp.float32),
    }


def _rounding_key(
    cfg: CompressionConfig,
    axis_name,
    key: Optional[jnp.ndarray],
    step: Optional[jnp.ndarray],
) -> Optional[jnp.ndarray]:
    if cfg.rounding != "stochastic":
        return None
    if key is None:
        if step is None:
            # a constant key would re-roll the SAME dither every step,
            # turning "unbiased in expectation" into a fixed systematic
            # bias — refuse rather than silently degrade
            raise ValueError(
                "stochastic rounding needs per-step randomness: pass "
                "key= or thread comm state (its step counter derives "
                "one)"
            )
        key = jax.random.fold_in(jax.random.PRNGKey(0), step)
    return jax.random.fold_in(key, jax.lax.axis_index(axis_name))


def quantized_psum(
    x: jnp.ndarray,
    axis_name,
    compression: Union[str, CompressionConfig] = "int8",
    residual: Optional[dict] = None,
    key: Optional[jnp.ndarray] = None,
    step: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Approximate ``lax.psum(x, axis_name)`` with int8 bytes on wire.

    Three collectives replace the one full-width all-reduce, all over
    ``axis_name`` only (call this on the SLOW axis):

    1. each rank block-quantizes its (padded) array and ``all_to_all``s
       int8 values + fp32 scales — the reduce-scatter phase, 1 byte +
       4/block per element;
    2. each rank accumulates its shard from the received
       ``int8 x fp32-scale`` products (exact in fp32) — no bytes;
    3. the reduced shard is re-quantized and ``all_gather``-ed back,
       again 1 byte + 4/block per element.

    With ``residual`` (from :func:`init_residual`), both quantization
    events run with error feedback: the residual is added before
    quantizing and the new rounding error is returned as fresh state —
    pass it back next step.  Without it the call is stateless (and
    lossier over many steps).

    Non-finite inputs quantize to garbage (an inf amax zeroes the
    block): run overflow detection on the *inputs* (the loss-scaler
    consensus) and discard the returned residual for skipped steps.

    Returns ``(psum_approx, new_residual)`` — ``new_residual`` is None
    when ``residual`` is None; the output has ``x``'s shape and dtype.
    """
    cfg = as_compression_config(compression)
    world = jax.lax.axis_size(axis_name)
    block = cfg.block_size
    shape, dtype, n = x.shape, x.dtype, int(jnp.size(x))
    padded, shard = comm_residual_sizes(n, world, block)

    flat = x.reshape(-1).astype(jnp.float32)
    if padded != n:
        flat = jnp.concatenate(
            [flat, jnp.zeros((padded - n,), jnp.float32)]
        )
    rkey = _rounding_key(cfg, axis_name, key, step)
    k1 = k2 = None
    if rkey is not None:
        k1, k2 = jax.random.split(rkey)

    if residual is not None:
        flat = flat + residual["push"]
    q, s = quantize_blockwise(flat, block, cfg.rounding, k1)
    new_residual = None
    if residual is not None:
        new_push = flat - dequantize_blockwise(q, s, block)

    # reduce-scatter phase: row r of the (world, shard) layout belongs
    # to rank r; exchange rows (and their scales) as int8/fp32
    qt = jax.lax.all_to_all(q.reshape(world, shard), axis_name, 0, 0)
    st = jax.lax.all_to_all(
        s.reshape(world, shard // block), axis_name, 0, 0
    )
    contrib = qt.astype(jnp.float32) * jnp.repeat(st, block, axis=1)
    y = jnp.sum(contrib, axis=0)

    if residual is not None:
        y = y + residual["pull"]
    q2, s2 = quantize_blockwise(y, block, cfg.rounding, k2)
    if residual is not None:
        new_pull = y - dequantize_blockwise(q2, s2, block)
        new_residual = {"push": new_push, "pull": new_pull}

    # invariant-typed gather (every rank receives identical bytes, so
    # the reconstruction is replicated over the axis)
    from apex_tpu.transformer.tensor_parallel.mappings import (
        all_gather_invariant,
    )

    gq = all_gather_invariant(q2, axis_name, axis=0, tiled=True)
    gs = all_gather_invariant(s2, axis_name, axis=0, tiled=True)
    out = dequantize_blockwise(gq, gs, block)[:n]
    return out.reshape(shape).astype(dtype), new_residual


def quantized_reduce_scatter(
    x: jnp.ndarray,
    axis_name,
    compression: Union[str, CompressionConfig] = "int8",
    residual: Optional[jnp.ndarray] = None,
    key: Optional[jnp.ndarray] = None,
    step: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Approximate ``lax.psum_scatter(x, axis_name, tiled=True)`` with
    int8 bytes on wire — the EQuARX ICI reduce-scatter leg.

    ``x`` is a flat ``(n,)`` fp32 array with ``n % world == 0``
    (callers pad to the ici extent exactly as the uncompressed path
    does).  Chunk boundaries are PRESERVED: rank *r* receives the sum
    of every rank's elements ``[r*n/world, (r+1)*n/world)`` — the
    per-row quantization (:func:`quantize_rows`) keeps blocks inside
    row boundaries for any chunk size, so turning compression on never
    moves a shard.  Each sender quantizes its whole (local) buffer
    once, ``all_to_all``s int8 values + fp32 scales, and the receiver
    accumulates exact ``int8 x fp32-scale`` products.

    ``residual`` is the flat ``(n,)`` ``ici_push`` error-feedback
    buffer (added before quantizing; the fresh rounding error comes
    back as ``new_residual``).  Returns ``(chunk (n/world,),
    new_residual_or_None)``."""
    cfg = as_compression_config(compression)
    world = jax.lax.axis_size(axis_name)
    n = int(jnp.size(x))
    if n % world:
        raise ValueError(
            f"quantized_reduce_scatter needs size % world == 0 "
            f"(got {n} over {world}): pad like the uncompressed path"
        )
    shard = n // world
    flat = x.reshape(-1).astype(jnp.float32)
    rkey = _rounding_key(cfg, axis_name, key, step)
    if residual is not None:
        flat = flat + residual
    q, s = quantize_rows(
        flat.reshape(world, shard), cfg.block_size, cfg.rounding, rkey
    )
    new_residual = None
    if residual is not None:
        new_residual = flat - dequantize_rows(
            q, s, cfg.block_size
        ).reshape(-1)
    qt = jax.lax.all_to_all(q, axis_name, 0, 0)
    st = jax.lax.all_to_all(s, axis_name, 0, 0)
    chunk = jnp.sum(dequantize_rows(qt, st, cfg.block_size), axis=0)
    return chunk, new_residual


def quantized_all_gather(
    x: jnp.ndarray,
    axis_name,
    compression: Union[str, CompressionConfig] = "int8",
    residual: Optional[jnp.ndarray] = None,
    key: Optional[jnp.ndarray] = None,
    step: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Approximate a tiled ``all_gather(x, axis_name)`` with int8 bytes
    on wire — the EQuARX ICI all-gather leg.

    Each rank quantizes its ``(shard,)`` chunk once and gathers int8
    values + fp32 scales; every rank dequantizes the identical gathered
    bytes, so the result is replicated over the axis (invariant-typed,
    like the uncompressed ``all_gather_invariant`` it replaces).
    ``residual`` is the ``(shard,)`` ``ici_pull`` error-feedback
    buffer.  Returns ``(full (world*shard,), new_residual_or_None)``."""
    cfg = as_compression_config(compression)
    world = jax.lax.axis_size(axis_name)
    shard = int(jnp.size(x))
    flat = x.reshape(-1).astype(jnp.float32)
    rkey = _rounding_key(cfg, axis_name, key, step)
    if residual is not None:
        flat = flat + residual
    q, s = quantize_blockwise(flat, cfg.block_size, cfg.rounding, rkey)
    new_residual = None
    if residual is not None:
        new_residual = flat - dequantize_blockwise(q, s, cfg.block_size)

    from apex_tpu.transformer.tensor_parallel.mappings import (
        all_gather_invariant,
    )

    gq = all_gather_invariant(q, axis_name, axis=0, tiled=True)
    gs = all_gather_invariant(s, axis_name, axis=0, tiled=True)
    nb = int(s.shape[0])
    out = dequantize_rows(
        gq.reshape(world, shard), gs.reshape(world, nb), cfg.block_size
    ).reshape(-1)
    return out, new_residual
