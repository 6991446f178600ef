"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606): the residual state of a block is
``n`` streams, and every sub-layer ``F`` is wrapped by three mappings
that are functions of the token::

    x~     = vec(X) / sqrt(mean(vec(X)^2) + rms_eps)
    H~pre  = a_pre  * (x~ phi_pre)  + b_pre        (n)
    H~post = a_post * (x~ phi_post) + b_post       (n)
    H~res  = a_res  * mat(x~ phi_res) + b_res      (n, n)
    H_pre = sigmoid(H~pre)     H_post = 2 sigmoid(H~post)
    M_0 = exp(clip(H~res, lo, hi));  M_t = rows(cols(M_{t-1}))
    H_res = M_iters            (Sinkhorn: doubly stochastic in the limit)
    X <- H_res X + H_post^T F(H_pre X)

``cols`` / ``rows`` divide each column / row by its sum plus ``eps``.
Everything here runs in float32.  The streams are laid out ``(n, T,
C)``: the device tiles the last two axes, and four streams of 3584 in
the second-minor place would be stored eight high.

Three functions, each reading the streams ONCE:

- :func:`hc_mapping` — the streams to ``(H_pre, H_post, H_res)``.  On a
  TPU it is ONE Mosaic kernel over a tile of tokens: the projection
  ``x~ phi`` (with the sum of squares beside it: the norm is a scale of
  the projected values, so no normalised copy of the streams is made),
  then the affine, the sigmoids and the Sinkhorn iterations on ``n`` x
  ``n`` values a token with the TOKENS on the lanes, so that an
  iteration is a handful of full-width vector operations and nothing
  ``n`` wide ever reaches HBM.
- :func:`hc_read` — ``H_pre X``, the sub-layer's input.
- :func:`hc_mix` — ``H_res X + H_post^T y``, the new streams.

The last two are written as sums over the streams, not as products of
``n``-wide matrices: XLA makes one elementwise fusion of each.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.attention import _interpret
from apex_tpu.ops.common import largest_tile, run_kernel, shape_struct
from apex_tpu.telemetry.spans import kernel_name

__all__ = ["hc_mapping", "hc_read", "hc_mix", "sinkhorn"]

_LANES = 128
#: tokens one grid step of the mapping kernel holds at most, and the
#: bytes of its block of the streams (two in flight): four streams of
#: 512 tokens x 512 columns in float32; a decode step's 128 tokens take
#: 1792 columns a step
HC_MAP_TOKENS = 512
HC_MAP_BLOCK_BYTES = 4 * 2**20


def sinkhorn(rows, iters: int, eps: float):
    """``rows``: a tuple of ``n`` arrays, row ``i`` of every token's
    matrix with its ``n`` entries on the FIRST axis (``(n, ...)``).
    ``iters`` times: every column divided by its sum + ``eps``, then
    every row by its sum + ``eps``."""

    def one(_, rows):
        cols = functools.reduce(jnp.add, rows) + eps
        rows = tuple(r / cols for r in rows)
        return tuple(r / (jnp.sum(r, axis=0, keepdims=True) + eps)
                     for r in rows)

    return lax.fori_loop(0, iters, one, tuple(rows))


def _finish(proj, alpha, bias, n, *, iters, eps, clamp):
    """``x~ phi``, the ``n (n + 2)`` projected values a token with the
    tokens on the last axis -> (H_pre (n, T), H_post (n, T), the rows of
    H_res, n x (n, T)); ``alpha`` holds the three gains, ``bias`` is an
    ``(n (n + 2), 1)`` column."""
    t = lambda gain, lo: gain * proj[lo:lo + n] + bias[lo:lo + n]
    pre = jax.nn.sigmoid(t(alpha[0], 0))
    post = 2.0 * jax.nn.sigmoid(t(alpha[1], n))
    rows = tuple(jnp.exp(jnp.clip(t(alpha[2], (2 + i) * n), *clamp))
                 for i in range(n))
    return pre, post, sinkhorn(rows, iters, eps)


def _mapping_kernel(x_ref, phi_ref, alpha_ref, bias_ref, pre_ref, post_ref,
                    res_ref, raw_ref, ss_ref, *, n, width, rms_eps, kw):
    col = pl.program_id(1)

    @pl.when(col == 0)
    def _init():
        raw_ref[...] = jnp.zeros_like(raw_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    raw, ss = raw_ref[...], ss_ref[...]
    K, tc = phi_ref.shape[1:]
    idle = jnp.zeros((raw.shape[1] - K, tc), jnp.float32)
    for i in range(n):
        x = x_ref[i]                                    # (tokens, columns)
        # the K outputs on whole lanes: (tokens, columns) x (lanes,
        # columns)^T, the form of a score product
        raw = raw + lax.dot_general(
            x, jnp.concatenate([phi_ref[i], idle], axis=0),
            (((1,), (1,)), ((), ())), precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        ss = ss + jnp.sum(x * x, axis=-1, keepdims=True)
    raw_ref[...], ss_ref[...] = raw, ss

    @pl.when(col == pl.num_programs(1) - 1)
    def _finalize():
        # the norm is a scale of a token's projected values; then the
        # TOKENS go on the lanes, where n x n values a token are n + 2
        # short stacks of full-width vectors
        proj = (raw * lax.rsqrt(ss / width + rms_eps)).T[:K]
        pre, post, rows = _finish(proj, alpha_ref, bias_ref[...], n, **kw)
        pre_ref[...], post_ref[...] = pre, post
        for i, r in enumerate(rows):
            res_ref[i * n:(i + 1) * n] = r


def _mapping_pallas(streams, phi, alpha, bias, rms_eps, kw):
    n, T, C = streams.shape
    K = phi.shape[1]
    pad_t, pad_c = (-T) % _LANES, (-C) % _LANES
    if pad_t or pad_c:
        # a decode step's handful of tokens: whole lanes of them (zero
        # tokens map to something finite and are cut off below)
        streams = jnp.pad(streams, ((0, 0), (0, pad_t), (0, pad_c)))
        phi = jnp.pad(phi, ((0, 0), (0, 0), (0, pad_c)))
    Tp, Cp, Kp = T + pad_t, C + pad_c, -(-K // _LANES) * _LANES
    tt = largest_tile(Tp, HC_MAP_TOKENS)
    tc = largest_tile(Cp, HC_MAP_BLOCK_BYTES // (4 * n * tt))
    tokens = lambda t, c: (0, t)
    fixed = lambda t, c: (0, 0)
    out = lambda rows: shape_struct((rows, Tp), jnp.float32, streams)
    pre, post, res = pl.pallas_call(
        functools.partial(_mapping_kernel, n=n, width=n * C,
                          rms_eps=rms_eps, kw=kw),
        grid=(Tp // tt, Cp // tc),
        in_specs=[pl.BlockSpec((n, tt, tc), lambda t, c: (0, t, c)),
                  pl.BlockSpec((n, K, tc), lambda t, c: (0, 0, c)),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((K, 1), fixed)],
        out_specs=[pl.BlockSpec((n, tt), tokens),
                   pl.BlockSpec((n, tt), tokens),
                   pl.BlockSpec((n * n, tt), tokens)],
        out_shape=[out(n), out(n), out(n * n)],
        scratch_shapes=[pltpu.VMEM((tt, Kp), jnp.float32),
                        pltpu.VMEM((tt, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name=kernel_name("hc_map"),
    )(streams, phi, alpha, bias)
    return pre[:, :T], post[:, :T], res[:, :T].reshape(n, n, T)


def _mapping_xla(streams, phi, alpha, bias, rms_eps, kw):
    n, T, C = streams.shape
    raw = jnp.sum(jnp.einsum(
        "nkc,ntc->nkt", phi, streams, precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32), axis=0)
    ss = jnp.sum(streams * streams, axis=(0, 2))[None]
    pre, post, rows = _finish(
        raw * lax.rsqrt(ss / (n * C) + rms_eps), alpha, bias, n, **kw)
    return pre, post, jnp.stack(rows)


def hc_mapping(streams, phi, alpha, bias, *, sinkhorn_iters: int,
               eps: float, clamp: Tuple[float, float], rms_eps: float,
               implementation: Optional[str] = None):
    """``streams`` (n, T, C) float32; ``phi`` (n, n (n + 2), C): stream
    ``i``'s rows of ``[phi_pre | phi_post | phi_res]``, transposed (the
    output index before the stream's column); ``alpha`` (3,) the gains
    of the three dynamic terms; ``bias`` (n (n + 2),) ``[b_pre | b_post
    | b_res row-major]`` -> (H_pre (n, T), H_post (n, T), H_res (n, n,
    T)), the TOKEN last.

    ``implementation``: None = the Mosaic kernel on a TPU and XLA
    elsewhere, ``"pallas"`` / ``"xla"`` strict."""
    from apex_tpu.utils.platform import default_implementation

    n = streams.shape[0]
    streams = streams.astype(jnp.float32)
    phi = phi.astype(jnp.float32)
    alpha = alpha.astype(jnp.float32)
    bias = bias.astype(jnp.float32)[:, None]
    kw = dict(iters=int(sinkhorn_iters), eps=float(eps),
              clamp=(float(clamp[0]), float(clamp[1])))
    args = (streams, phi, alpha, bias, float(rms_eps), kw)
    return run_kernel(
        "hc_map", lambda: _mapping_pallas(*args),
        lambda: _mapping_xla(*args),
        implementation or default_implementation())


def hc_read(streams, h_pre):
    """``H_pre X``: ``streams`` (n, T, C), ``h_pre`` (n, T) -> (T, C)."""
    return functools.reduce(jnp.add, (
        h_pre[i][:, None] * streams[i] for i in range(streams.shape[0])))


def hc_mix(streams, h_res, h_post, y):
    """``H_res X + H_post^T y``: ``h_res`` (n, n, T), ``h_post`` (n, T),
    ``y`` (T, C) the sub-layer's output -> the new streams (n, T, C)."""
    n = streams.shape[0]
    y = y.astype(jnp.float32)
    return jnp.stack([functools.reduce(jnp.add, (
        h_res[i, j][:, None] * streams[j] for j in range(n)),
        h_post[i][:, None] * y) for i in range(n)])
