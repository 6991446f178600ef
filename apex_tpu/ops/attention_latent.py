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
- **absorbed, over the pages** (:func:`mla_paged`, decode): the same
  form over the context of every slot, read where it lies.  A Mosaic
  kernel walks the slot's page-table row, several pages a grid step
  (:mod:`apex_tpu.ops.attention_decode`'s walk: the pool stays in HBM, a
  step's LIVE pages are copied into one of two VMEM tiles with the next
  step's copies in flight), and every row serves all heads from that one
  fetch: key = the whole row, value = its first ``kv_lora_rank``
  columns.  No gathered copy of the context exists, and the pool is
  never sliced by layer.  Without a selection a query sees its slot's
  whole context; with one (``selected``, a sparse selection as a mask)
  the walk still reads every live row and masks out those not chosen —
  cheaper than gathering the chosen rows while they are a large share
  of the context (``models/deepseek_v32.py`` says where it turns).

Both are the same mathematics (``tests/test_deepseek_v32.py`` holds
them to each other).  Masked entries take a finite ``-1e30``, so a row
with nothing valid (an idle slot) gives garbage, not NaN.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.attention import _NEG_INF, _interpret, flash_attention
from apex_tpu.ops.attention_decode import (
    _DecodeConfig, _pages_per_step, _stream_tiles, _walk,
)
from apex_tpu.ops.common import run_kernel, shape_struct
from apex_tpu.ops.sparse_index import NEG
from apex_tpu.telemetry.spans import kernel_name, phase

__all__ = ["mla_expanded", "mla_absorbed", "mla_paged"]


def _masked_softmax(scores, mask):
    scores = jnp.where(mask, scores, NEG)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores)
    return p / jnp.sum(p, axis=-1, keepdims=True)


def mla_expanded(q_nope, q_rope, rows, w_uk, w_uv, mask, scale: float,
                 *, head_block: int = 32,
                 implementation: Optional[str] = None, q_offset=None):
    """``q_nope`` (n, H, dn), ``q_rope`` (n, H, dr); ``rows`` (S, >= dc
    + dr) cached entries ``[c_kv | k_r | 0...]``; ``w_uk`` (dc, H, dn),
    ``w_uv`` (dc, H, dv) -> (n, H, dv).  What a query sees is EITHER
    ``mask`` (n, S) bool, any selection, OR (``mask`` None) ``q_offset``,
    a traced int32 scalar is fine: query ``i`` sits at row ``q_offset +
    i`` and sees the rows up to its own — the causal form, which builds
    no mask.

    The attention itself is :func:`apex_tpu.ops.attention.
    flash_attention`, a selection mask as an additive bias shared by all
    heads, the causal form as the kernel's own positions
    (``implementation`` is handed through: None picks the Mosaic kernels
    on a TPU and XLA elsewhere), so the (heads, n, S)
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
    if (mask is None) == (q_offset is None):
        raise ValueError("give a mask or a q_offset, one of them")
    if mask is None:
        seen = dict(causal=True, q_offset=q_offset)
    else:
        seen = dict(causal=False, bias_requires_grad=False, bias=jnp.where(
            mask, 0.0, NEG).astype(jnp.float32)[None, None])
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
            heads_first(q), heads_first(k), heads_first(v), sm_scale=scale,
            implementation=implementation, **seen)
        return jnp.moveaxis(out[0], 0, 1)[..., :dv]      # (n, G, dv)

    with phase("attn.mla.core"):
        split_q = lambda t: jnp.moveaxis(
            t.reshape(n, H // G, G, t.shape[-1]), 1, 0)
        split_w = lambda t: jnp.moveaxis(
            t.reshape(dc, H // G, G, t.shape[-1]), 1, 0)
        out = lax.map(block, (split_q(q_nope), split_q(q_rope),
                              split_w(w_uk), split_w(w_uv)))
        return jnp.moveaxis(out, 0, 1).reshape(n, H, -1)


def _absorbed_query(q_nope, q_rope, w_uk, rows):
    """``[q_nope W_UK^T | q_rope | 0...]`` (B, H, width of a cached
    row), in the rows' dtype."""
    q_abs = jnp.einsum("bhd,chd->bhc", q_nope, w_uk)
    q = jnp.concatenate([q_abs.astype(rows.dtype), q_rope], axis=-1)
    return jnp.pad(q, ((0, 0), (0, 0), (0, rows.shape[-1] - q.shape[-1])))


def mla_absorbed(q_nope, q_rope, rows, chosen, w_uk, w_uv, scale: float):
    """``q_nope`` (B, H, dn), ``q_rope`` (B, H, dr); ``rows`` (B, K, >=
    dc + dr): each query's OWN gathered cache rows; ``chosen`` (B, K) bool
    (false on filler rows) -> (B, H, dv)."""
    dc = w_uk.shape[0]
    q = _absorbed_query(q_nope, q_rope, w_uk, rows)
    with phase("attn.mla.core"):
        s = jnp.einsum("bhc,bkc->bhk", q, rows,
                       preferred_element_type=jnp.float32) * scale
        p = _masked_softmax(s, chosen[:, None, :]).astype(rows.dtype)
        o = jnp.einsum("bhk,bkc->bhc", p, rows[..., :dc])
    return jnp.einsum("bhc,chd->bhd", o, w_uv)


# ---------------------------------------------------------------------------
# The absorbed form over a slot's pages
# ---------------------------------------------------------------------------


def _latent_walk_kernel(pt_ref, len_ref, layer_ref, q_ref, *refs,
                        cfg: _DecodeConfig, dc: int, selected: bool):
    """Program ``(slot, 0, step)``: ``cfg.pages`` logical pages of the
    slot's context against all heads' absorbed queries.  The online
    softmax is :func:`apex_tpu.ops.attention_decode._decode_kernel`'s;
    keys and values are ONE tile.  With ``selected`` the step's block of
    the slot's selection (1, 1, 1, pages x page_size) int32 comes before
    the pool, and a row it holds 0 for is masked like a row past the
    slot's length."""
    sel_ref = refs[0] if selected else None
    (pool_ref, o_ref, qs_ref, acc_ref, m_ref, l_ref, tile, sem,
     state) = refs[1:] if selected else refs
    at = b, _, step = tuple(pl.program_id(i) for i in range(3))
    grid = tuple(pl.num_programs(i) for i in range(3))
    ps, P = cfg.page_size, cfg.pages
    walk = functools.partial(_walk, cfg, len_ref, None)
    _, live = walk(b)

    @pl.when((b == 0) & (step == 0))
    def _first_program():
        # a dead place's scores are masked whatever it holds, but as a
        # value it has to be finite for the masked weight's zero
        state[0] = 0
        state[1] = 0
        tile[...] = jnp.zeros_like(tile)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        qs_ref[...] = (q_ref[0].astype(jnp.float32) * cfg.sm_scale
                       ).astype(qs_ref.dtype)

    @pl.when(step * P < live)
    def _body():
        layer = layer_ref[0]
        cur = _stream_tiles(
            cfg, walk, at, grid, pt_ref, (pool_ref,), (tile,), sem, state,
            cut=lambda pool, page, hb: pool.at[layer, page])
        rows = tile[cur].reshape(P * ps, tile.shape[-1])
        s = lax.dot_general(qs_ref[...], rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        mask = step * P * ps + lax.broadcasted_iota(
            jnp.int32, s.shape, 1) < len_ref[b]
        if selected:
            mask = mask & (sel_ref[0, 0] != 0)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pexp, -1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            pexp.astype(rows.dtype), rows[:, :dc],
            preferred_element_type=jnp.float32)

    @pl.when(step == grid[2] - 1)
    def _finalize():
        # an idle slot (length 0) walks nothing and writes zeros
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _latent_walk(q, pool, layer, page_table, lengths, scale: float, dc: int,
                 selected=None):
    """``q`` (B, H, W) absorbed queries as wide as a pool row; ``pool``
    (layers, pages, page_size, W); ``selected`` None or (B, pages a slot
    x page_size) bool -> (B, H, dc)."""
    B, H, W = q.shape
    ps, width = pool.shape[2], page_table.shape[1]
    cfg = _DecodeConfig(
        sm_scale=float(scale), causal=False, sq=1, block_h=1, page_size=ps,
        num_pages=width, kv_block=0, has_scales=False, has_rope=False,
        pages=_pages_per_step(ps, W, 1, pool.dtype.itemsize, width, False),
        copies=True)
    steps = -(-width // cfg.pages)
    per_slot = lambda b, h, p, *scalars: (b, 0, 0)
    sel_spec, sel = [], ()
    if selected is not None:
        # int32 rows a slot and step, each a whole lane-dense block: a
        # step's selection arrives with the step, as its pages do
        n = cfg.pages * ps
        sel = (jnp.pad(selected.astype(jnp.int32),
                       ((0, 0), (0, steps * n - selected.shape[1]))
                       ).reshape(B, steps, 1, n),)
        sel_spec = [pl.BlockSpec((1, 1, 1, n),
                                 lambda b, h, p, *scalars: (b, p, 0, 0))]
    return pl.pallas_call(
        functools.partial(_latent_walk_kernel, cfg=cfg, dc=dc,
                          selected=selected is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, 1, steps),
            in_specs=[pl.BlockSpec((1, H, W), per_slot), *sel_spec,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, dc), per_slot),
            scratch_shapes=[
                pltpu.VMEM((H, W), pool.dtype),
                pltpu.VMEM((H, dc), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((2, cfg.pages, ps, W), pool.dtype),
                pltpu.SemaphoreType.DMA((1, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ]),
        out_shape=shape_struct((B, H, dc), q.dtype, q, pool),
        # a step's copies are started by the step before it, whichever
        # slot that was in: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=_interpret(),
        name=kernel_name("latent_walk"),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, *sel, pool)


def mla_paged(q_nope, q_rope, pool, layer, page_table, lengths, w_uk, w_uv,
              scale: float, *, selected=None,
              implementation: Optional[str] = None):
    """The absorbed form of one query a slot over the slot's paged
    context: ``q_nope`` (B, H, dn), ``q_rope`` (B, H, dr); ``pool``
    (layers, pages, page_size, >= dc + dr) the stacked latent pool and
    ``layer`` (a traced scalar is fine) the layer read; ``page_table``
    (B, pages a slot) physical pages (unallocated entries hold a valid
    page, the null page 0); ``lengths`` (B,) the rows a slot's query
    sees, its own included (0: an idle slot, whose output is zeros on
    the kernel's path) -> (B, H, dv).  ``selected`` (B, pages a slot x
    page_size) bool, or None for all: of those rows, the ones the query
    sees (a sparse selection; every live row is still read).

    ``implementation``: None = the Mosaic walk on a TPU and XLA
    elsewhere (the rows gathered through the table, then
    :func:`mla_absorbed`); ``"pallas"`` / ``"xla"`` strict."""
    from apex_tpu.utils.platform import default_implementation

    dc = w_uk.shape[0]
    B = q_nope.shape[0]

    def xla():
        rows = pool[layer, page_table].reshape(B, -1, pool.shape[-1])
        seen = jnp.arange(rows.shape[1], dtype=jnp.int32)[None] \
            < lengths[:, None]
        if selected is not None:
            seen = seen & selected
        return mla_absorbed(q_nope, q_rope, rows, seen, w_uk, w_uv, scale)

    def walk():
        q = _absorbed_query(q_nope, q_rope, w_uk, pool)
        with phase("attn.mla.core"):
            o = _latent_walk(q, pool, layer, page_table, lengths, scale, dc,
                             selected)
        return jnp.einsum("bhc,chd->bhd", o, w_uv)

    return run_kernel("latent_walk", walk, xla,
                      implementation or default_implementation())
