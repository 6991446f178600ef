"""Multi-head latent attention (MLA): two forms of one attention.

The cache holds, per token and layer, ONE entry shared by all heads: the
normalised latent ``c_kv`` (``kv_lora_rank`` wide) and the rotated
shared key ``k_r`` (``qk_rope_head_dim``), stored side by side as one
row ``[c_kv | k_r | 0...]`` (the pool pads a row to whole 128-lane
tiles; whatever follows ``k_r`` is zero and is never read as a value).  ``W_kvb`` gives each head its up-projections
``W_UK`` (latent -> key) and ``W_UV`` (latent -> value).

- **expanded** (:func:`mla_expanded`, prefill): keys and values are
  made from the latents, ``k_h = [c_kv W_UK,h | k_r]``, ``v_h = c_kv
  W_UV,h``, and attention runs under the selection mask.  Cheapest per
  score (192 wide) when many queries share the same keys.
- **absorbed** (:func:`mla_absorbed`, decode): ``W_UK`` moves into the
  query (``q~_h = q_nope,h W_UK,h^T``), scores and the weighted sum run
  on the cached rows themselves (576 and 512 wide), ``W_UV`` is applied
  to the result.  No per-head key or value is ever built, so a decode
  step reads each selected row once for all heads.

Both are the same mathematics (``tests/test_deepseek_v32.py`` holds
them to each other).  Masked entries take a finite ``-1e30``, so a row
with nothing valid (an idle slot) gives garbage, not NaN.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.sparse_index import NEG
from apex_tpu.telemetry.spans import phase

__all__ = ["mla_expanded", "mla_absorbed"]


def _masked_softmax(scores, mask):
    scores = jnp.where(mask, scores, NEG)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def mla_expanded(q_nope, q_rope, rows, w_uk, w_uv, mask, scale: float,
                 *, head_block: int = 32,
                 implementation: Optional[str] = None):
    """``q_nope`` (n, H, dn), ``q_rope`` (n, H, dr); ``rows`` (S, >= dc
    + dr) cached entries ``[c_kv | k_r | 0...]``; ``w_uk`` (dc, H, dn),
    ``w_uv`` (dc, H, dv); ``mask`` (n, S) bool -> (n, H, dv).

    The attention itself is :func:`apex_tpu.ops.attention.
    flash_attention` with the selection mask as an additive bias shared
    by all heads (``implementation`` is handed through: None picks the
    Mosaic kernels on a TPU and XLA elsewhere), so the (heads, n, S)
    scores never reach HBM — as plain fusions their softmax was 85 % of
    a 2048-token chunk's time on the v5e.  The kernels take one width
    for q, k and v: keys are ``[k_nope | k_r]`` (dn + dr), values are
    zero-padded up to it.  Heads are walked ``head_block`` at a time to
    bound the expanded keys and values."""
    n, H, dn = q_nope.shape
    dr, dv, dc = q_rope.shape[-1], w_uv.shape[-1], w_uk.shape[0]
    c_kv, k_r = rows[:, :dc], rows[:, dc:dc + dr]
    G = head_block if H % head_block == 0 else H
    S = rows.shape[0]
    bias = jnp.where(mask, 0.0, NEG).astype(jnp.float32)[None, None]
    pad = max(dn + dr - dv, 0)

    def block(args):
        qn, qr, uk, uv = args            # (n, G, .), (dc, G, .)
        q = jnp.concatenate([qn, qr], axis=-1)
        k = jnp.concatenate([
            jnp.einsum("sc,chd->shd", c_kv, uk),
            jnp.broadcast_to(k_r[:, None], (S, G, dr))], axis=-1)
        v = jnp.pad(jnp.einsum("sc,chd->shd", c_kv, uv),
                    ((0, 0), (0, 0), (0, pad)))
        heads_first = lambda t: jnp.moveaxis(t, 1, 0)[None]
        out = flash_attention(
            heads_first(q), heads_first(k), heads_first(v), causal=False,
            sm_scale=scale, bias=bias, bias_requires_grad=False,
            implementation=implementation)
        return jnp.moveaxis(out[0], 0, 1)[..., :dv]      # (n, G, dv)

    with phase("attn.mla.core"):
        split_q = lambda t: jnp.moveaxis(
            t.reshape(n, H // G, G, t.shape[-1]), 1, 0)
        split_w = lambda t: jnp.moveaxis(
            t.reshape(dc, H // G, G, t.shape[-1]), 1, 0)
        out = lax.map(block, (split_q(q_nope), split_q(q_rope),
                              split_w(w_uk), split_w(w_uv)))
        return jnp.moveaxis(out, 0, 1).reshape(n, H, -1)


def mla_absorbed(q_nope, q_rope, rows, chosen, w_uk, w_uv, scale: float):
    """``q_nope`` (B, H, dn), ``q_rope`` (B, H, dr); ``rows`` (B, K, >=
    dc + dr): each query's OWN gathered cache rows; ``chosen`` (B, K) bool
    (false on filler rows) -> (B, H, dv)."""
    dc = w_uk.shape[0]
    q_abs = jnp.einsum("bhd,chd->bhc", q_nope, w_uk)
    q = jnp.concatenate([q_abs.astype(rows.dtype), q_rope], axis=-1)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, rows.shape[-1] - q.shape[-1])))
    with phase("attn.mla.core"):
        s = jnp.einsum("bhc,bkc->bhk", q, rows,
                       preferred_element_type=jnp.float32) * scale
        p = _masked_softmax(s, chosen[:, None, :]).astype(rows.dtype)
        o = jnp.einsum("bhk,bkc->bhc", p, rows[..., :dc])
    return jnp.einsum("bhc,chd->bhd", o, w_uv)
