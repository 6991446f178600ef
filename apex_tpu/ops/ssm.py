"""The Mamba-2 state-space mixer's three serving operations, forward
only: the causal depthwise convolution with its carried window, the
chunked SSD scan of a prefill chunk and the decode step's state update.

The recurrence, per head ``h`` (``P`` channels, state width ``N``; head
``h`` reads group ``g = h // (H / G)`` of ``B`` and ``C``)::

    a_t = exp(dt_t A_h)                                   (dt_t >= 0, A_h < 0)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T                    (P, N)
    y_t = S_t C_t + D_h x_t

Everything here is float32: the state, its decay and its accumulation.

- :func:`causal_conv` / :func:`causal_conv_step` — ``out_t = b + sum_k
  w_k in_{t - K + 1 + k}`` over the channels, the ``K - 1`` inputs before
  the first new one coming from the slot's carried window.
- :func:`ssd_chunk_scan` — a chunk of ``T`` tokens in blocks of
  ``chunk`` (Mamba-2's state-space duality): inside a block the outputs
  are matrix products over its tokens (``C B^T`` under the decay
  between each pair), the block's contribution to the state is one
  more, and the states between blocks are a short scan.  It starts
  from a given state and returns the state after the last token: a
  token with ``dt = 0`` (padding past the prompt) leaves it unchanged.
  XLA's products, scoped ``tlm.kernel.ssd_chunk_scan``.
- :func:`ssm_state_update` — one token for every slot: each slot's
  state is read once and written once, IN PLACE inside the stacked pool
  ``(layers, slots, H, P, N)``; a slot that is not live keeps its state
  bit for bit.  On a TPU one Mosaic kernel (``tlm.kernel.
  ssm_state_update``) over (slot, block of heads): an XLA fusion reads
  the state twice, once for the new state and once more for ``y``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.attention import _interpret
from apex_tpu.ops.common import run_kernel
from apex_tpu.telemetry.spans import kernel_name

__all__ = ["causal_conv", "causal_conv_step", "ssd_chunk_scan",
           "ssm_state_update"]

#: heads one grid step of the state update holds: 8 x 128 x 256 fp32 is
#: 1 MiB of state, four of them (in and out, two in flight) in VMEM
STATE_BLOCK_HEADS = 8
_HIGHEST = lax.Precision.HIGHEST


# ------------------------------------------------------------ convolution
def causal_conv(x, window, w, b):
    """``x`` (T, Ch) the new inputs, ``window`` (K - 1, Ch) the inputs
    before them, ``w`` (K, Ch), ``b`` (Ch,) -> the convolution (T, Ch)
    fp32, before its activation."""
    K, T = w.shape[0], x.shape[0]
    full = jnp.concatenate([window, x.astype(window.dtype)]).astype(
        jnp.float32)
    wf = w.astype(jnp.float32)
    out = b.astype(jnp.float32)
    for k in range(K):
        out = out + wf[k] * full[k:k + T]
    return out


def causal_conv_step(x, window, w, b):
    """One input a slot: ``x`` (S, Ch), ``window`` (S, K - 1, Ch) ->
    (the convolution (S, Ch) fp32, the window that ends with ``x``)."""
    full = jnp.concatenate([window, x.astype(window.dtype)[:, None]], axis=1)
    out = jnp.einsum("skc,kc->sc", full.astype(jnp.float32),
                     w.astype(jnp.float32), precision=_HIGHEST)
    return out + b.astype(jnp.float32), full[:, 1:]


# ------------------------------------------------------------- the scan
def ssd_chunk_scan(x, dt, A, B, C, D, state0, *, chunk: int = 128):
    """``x`` (T, H, P), ``dt`` (T, H) (after its softplus; 0 where a
    token must not enter the state), ``A`` (H,) negative, ``B``/``C``
    (T, G, N), ``D`` (H,), ``state0`` (H, P, N) -> (y (T, H, P), the
    state after the last token (H, P, N)), all fp32.  ``T`` is padded up
    to whole blocks of ``chunk`` with ``dt = 0``."""
    with jax.named_scope(kernel_name("ssd_chunk_scan")):
        return _ssd(x, dt, A, B, C, D, state0, chunk)


def _ssd(x, dt, A, B, C, D, state0, L):
    f32 = jnp.float32
    T, H, P = x.shape
    G, N = B.shape[1:]
    R = H // G
    pad = (-T) % L
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                       for a in (x, dt, B, C))
    nc = (T + pad) // L
    xs = x.astype(f32).reshape(nc, L, G, R, P)
    dts = dt.astype(f32).reshape(nc, L, G, R)
    Bs = B.astype(f32).reshape(nc, L, G, N)
    Cs = C.astype(f32).reshape(nc, L, G, N)
    cum = jnp.cumsum(dts * A.astype(f32).reshape(G, R), axis=1)   # <= 0
    # inside a block: y_i += sum_{j <= i} exp(cum_i - cum_j) (C_i.B_j) dt_j x_j
    causal = jnp.tril(jnp.ones((L, L), bool))
    seg = cum[:, :, None] - cum[:, None]                  # (nc, i, j, G, R)
    decay = jnp.where(causal[None, :, :, None, None],
                      jnp.exp(jnp.where(causal[None, :, :, None, None],
                                        seg, 0.0)), 0.0)
    cb = jnp.einsum("cign,cjgn->cijg", Cs, Bs, precision=_HIGHEST)
    weights = decay * cb[..., None] * dts[:, None]        # (nc, i, j, G, R)
    y = jnp.einsum("cijgr,cjgrp->cigrp", weights, xs, precision=_HIGHEST)
    # each block's own contribution to the state at its end
    to_end = jnp.exp(cum[:, -1:] - cum) * dts             # (nc, L, G, R)
    block_states = jnp.einsum("cjgr,cjgrp,cjgn->cgrpn", to_end, xs, Bs,
                              precision=_HIGHEST)

    def carry(s, inputs):
        a, own = inputs
        return a[..., None, None] * s + own, s

    final, entering = lax.scan(
        carry, state0.astype(f32).reshape(G, R, P, N),
        (jnp.exp(cum[:, -1]), block_states))
    # the state entering the block, read by every query of it
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "cign,cgrpn->cigrp", Cs, entering, precision=_HIGHEST)
    y = y + D.astype(f32).reshape(G, R)[:, :, None] * xs
    return y.reshape(nc * L, H, P)[:T], final.reshape(H, P, N)


# ---------------------------------------------------- the decode update
def _update_math(state, x, dt, A, Bh, Ch, D):
    """Shared by both forms.  ``state`` (..., P, N), ``x`` (..., P),
    ``dt``/``A``/``D`` (..., 1), ``Bh``/``Ch`` (..., 1, N) ->
    (new state, y (..., P))."""
    decay = jnp.exp(dt * A)[..., None]
    new = decay * state + (dt * x)[..., None] * Bh
    return new, jnp.sum(new * Ch, axis=-1) + D * x


def _update_kernel(live_ref, dt_ref, x_ref, b_ref, c_ref, a_ref, d_ref,
                   s_ref, y_ref, o_ref):
    new, y = _update_math(s_ref[0, 0].astype(jnp.float32), x_ref[0],
                          dt_ref[0], a_ref[...], b_ref[0], c_ref[0],
                          d_ref[...])                    # (hb, P, N), (hb, P)
    y_ref[0] = y
    live = live_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _():
        o_ref[0, 0] = new.astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[0, 0] = s_ref[0, 0]


def _block_heads(H: int, per_group: int) -> int:
    """Heads a grid step: divides a group's heads (one B/C row a step)
    and tiles the second-minor axis (a multiple of 8, or all heads)."""
    for hb in range(min(STATE_BLOCK_HEADS, per_group), 0, -1):
        if per_group % hb == 0 and (hb % 8 == 0 or hb == H):
            return hb
    return per_group


def _update_pallas(pool, layer, x, dt, A, B, C, D, live):
    _, S, H, P, N = pool.shape
    G = B.shape[1]
    R = H // G
    hb = _block_heads(H, R)
    per = lambda s, h, live: (s, h, 0)
    group = lambda s, h, live: (s, (h * hb) // R, 0, 0)
    head = lambda s, h, live: (h, 0)
    state = pl.BlockSpec((1, 1, hb, P, N),
                         lambda s, h, live: (layer, s, h, 0, 0))
    y, pool = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, H // hb),
            in_specs=[pl.BlockSpec((1, hb, 1), per),
                      pl.BlockSpec((1, hb, P), per),
                      pl.BlockSpec((1, 1, 1, N), group),
                      pl.BlockSpec((1, 1, 1, N), group),
                      pl.BlockSpec((hb, 1), head),
                      pl.BlockSpec((hb, 1), head),
                      state],
            out_specs=[pl.BlockSpec((1, hb, P), per), state]),
        out_shape=[jax.ShapeDtypeStruct((S, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name=kernel_name("ssm_state_update"),
    )(live.astype(jnp.int32), dt[..., None], x,
      B[:, :, None, :], C[:, :, None, :], A[:, None], D[:, None], pool)
    return y, pool


def _update_xla(pool, layer, x, dt, A, B, C, D, live):
    H = pool.shape[2]
    R = H // B.shape[1]
    old = pool[layer]
    by_head = lambda t: jnp.repeat(t, R, axis=1)[:, :, None]
    new, y = _update_math(old.astype(jnp.float32), x, dt[..., None],
                          A[:, None], by_head(B), by_head(C), D[:, None])
    keep = jnp.where(live[:, None, None, None], new.astype(old.dtype), old)
    return y, pool.at[layer].set(keep)


def ssm_state_update(pool, layer: int, x, dt, A, B, C, D, live, *,
                     implementation: Optional[str] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token for every slot of layer ``layer`` (static) of the
    stacked state ``pool`` (layers, slots, H, P, N; fp32, or a
    narrower type it is rounded to when written): ``x`` (slots,
    H, P), ``dt`` (slots, H) after its softplus, ``A``/``D`` (H,),
    ``B``/``C`` (slots, G, N), ``live`` (slots,) bool -> (y (slots, H,
    P) fp32, the pool with the live slots' states advanced and every
    other state as it was).  Take the pool donated: it is updated in
    place.

    ``implementation``: None = the Mosaic kernel on a TPU and XLA
    elsewhere, ``"pallas"`` / ``"xla"`` strict."""
    from apex_tpu.utils.platform import default_implementation

    f32 = jnp.float32
    args = (pool, int(layer), x.astype(f32), dt.astype(f32), A.astype(f32),
            B.astype(f32), C.astype(f32), D.astype(f32), live)
    return run_kernel(
        "ssm_state_update", lambda: _update_pallas(*args),
        lambda: _update_xla(*args),
        implementation or default_implementation())
