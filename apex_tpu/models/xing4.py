"""Xing4.0 (``model_type`` ``xing4_0``) on the serving path: DeepSeek-V3's
latent attention and ``noaux_tc`` expert layer inside a block whose
residual state is ``hc_mult`` streams mixed by manifold-constrained
hyper-connections, and attention that reads the WHOLE paged context.

The block (:mod:`apex_tpu.ops.hyper_connections` has the equations):
the state is ``X`` (``hc_mult``, tokens, hidden) in float32, ``X_0`` the
token's embedding in every stream; each layer applies the wrapper twice,
around attention and around the FFN, each ``F`` with its own pre-RMSNorm::

    (H_pre, H_post, H_res) = mapping(X)             tlm.resid.hc_map
    X <- H_res X + H_post^T F(H_pre X)              tlm.resid.hc_mix

and after the last layer the streams are summed, normalised and
projected onto the vocabulary.

:class:`Xing4Model` is :class:`apex_tpu.models.deepseek_v32.
DeepSeekV32Model` with that walk and WITHOUT the indexer: the
projections, YaRN, the expert layer (every routed expert held), the
chunked ingestion, ``decode_fns`` and its carry are inherited.  What
differs in the three walks of the attention:

- ``apply`` and a prefill CHUNK run the expanded form causally from
  the first query's position (no selection, no mask built);
- a DECODE step runs the absorbed form over every live row of the
  slot's pages, read where they lie (:func:`apex_tpu.ops.
  attention_latent.mla_paged`): no row is gathered, no index key is kept
  (``KVCacheConfig(kind="latent", index_dim=0)``).

The decode step's counters keep ``deepseek_v32.COUNTER_NAMES``: with
every expert held ``decode_choices_held`` equals ``decode_choices``, and
without a selection ``decode_selected_rows`` equals
``decode_context_rows`` (the rows the walk read); ``last_selected`` in
the carry is empty.  The multi-token-prediction module is not held.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.models.deepseek_v32 import (
    COUNTER_NAMES, DeepSeekV32Config, DeepSeekV32Model,
)
from apex_tpu.ops.attention import k_blocks_run
from apex_tpu.ops.attention_latent import mla_expanded, mla_paged
from apex_tpu.ops.hyper_connections import hc_mapping, hc_mix, hc_read
from apex_tpu.ops.rope import apply_rope_tables
from apex_tpu.telemetry.spans import phase
from apex_tpu.transformer.moe import HeldExpertsMLP

__all__ = ["Xing4Config", "Xing4Model", "COUNTER_NAMES"]


@dataclasses.dataclass(frozen=True)
class Xing4Config(DeepSeekV32Config):
    """The published keys (``XingChen-AGI/Xing4.0-29B-A4B``
    ``config.json``); the indexer's are 0 (there is none)."""

    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0

    @classmethod
    def from_hf(cls, cfg: dict, *, params_dtype: Any = jnp.bfloat16):
        """From a ``config.json``-shaped dict; every routed expert is
        held."""
        E = int(cfg["n_routed_experts"])
        return super().from_hf(
            {**cfg, "index_n_heads": 0, "index_head_dim": 0,
             "index_topk": 0},
            n_routed_experts=E, held_experts=range(E),
            params_dtype=params_dtype)


class Xing4Model(DeepSeekV32Model):
    def __init__(self, config: Xing4Config):
        super().__init__(config)
        if config.hc_mult < 1:
            raise ValueError("hc_mult must be >= 1")

    # ----------------------------------------------------------- params
    def _init_attn(self, key):
        return {k: v for k, v in super()._init_attn(key).items()
                if not k.startswith("idx_")}

    def _init_hc(self, key):
        """One wrapper's mapping.  Every dynamic term is non-zero
        (``alpha`` 0.5 on projections of unit variance), ``b_pre`` and
        ``b_post`` are N(0, 1) so that the streams are read and written
        UNEQUALLY (with equal read-outs the sum of the streams is all a
        sub-layer sees, and a doubly stochastic ``H_res`` keeps that sum
        whatever it is), and ``b_res`` favours the diagonal (2 on it),
        so that ``H_res`` is neither uniform nor the identity."""
        c = self.config
        n, width = c.hc_mult, c.hc_mult * c.hidden_size
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "phi": width ** -0.5 * jax.random.normal(
                k1, (n, n * (n + 2), c.hidden_size), jnp.float32),
            "alpha": jnp.full((3,), 0.5, jnp.float32),
            "bias": jnp.concatenate([
                jax.random.normal(k2, (2 * n,), jnp.float32),
                (2.0 * jnp.eye(n) + 0.3 * jax.random.normal(
                    k3, (n, n), jnp.float32)).reshape(-1)]),
        }

    def _init_layer(self, key, dense: bool):
        k0, k1, k2 = jax.random.split(key, 3)
        return {**super()._init_layer(k0, dense),
                "hc_attn": self._init_hc(k1), "hc_ffn": self._init_hc(k2)}

    # ---------------------------------------------------------- pieces
    def _project(self, ap, h, cos, sin):
        """``h`` (n, hidden) at positions whose rotary rows are
        ``cos``/``sin`` -> (q_nope (n, H, dn), q_rope (n, H, dr) rotated,
        the new cache row (n, dc + dr))."""
        c = self.config
        n = h.shape[0]
        H, dn, dr = (c.num_attention_heads, c.qk_nope_head_dim,
                     c.qk_rope_head_dim)
        with phase("attn.mla"):
            c_q = self._rms(jnp.matmul(h, ap["wq_a"]), ap["q_norm"])
            q = jnp.matmul(c_q, ap["wq_b"]).reshape(n, H, dn + dr)
            kv = jnp.matmul(h, ap["wkv_a"])
            row = jnp.concatenate([
                self._rms(kv[:, :c.kv_lora_rank], ap["kv_norm"]),
                apply_rope_tables(kv[:, c.kv_lora_rank:], cos, sin)], -1)
            return (q[..., :dn], apply_rope_tables(
                q[..., dn:], cos[:, None], sin[:, None]), row)

    def _streams(self, params, tokens):
        """``X_0``: the token's embedding in every stream."""
        x = self._embed(params, tokens)
        return jnp.broadcast_to(x, (self.config.hc_mult,) + x.shape)

    def _wrapped(self, hp, X, F):
        """One wrapper: ``F(h) -> (y, extra)``; returns (X, extra)."""
        c = self.config
        with phase("resid.hc_map"):
            pre, post, res = hc_mapping(
                X, hp["phi"], hp["alpha"], hp["bias"],
                sinkhorn_iters=c.hc_sinkhorn_iters, eps=c.hc_eps,
                clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max),
                rms_eps=c.rms_norm_eps)
        with phase("resid.hc_mix"):
            h = hc_read(X, pre)
        y, extra = F(h)
        with phase("resid.hc_mix"):
            return hc_mix(X, res, post, y), extra

    def _walk(self, params, X, attend, pools, token_valid):
        """THE layer walk: the streams ``X`` (hc_mult, n, hidden)
        through the dense layers and the expert layers.  ``attend(ap,
        h, layer, pools) -> (attention output, (pools, context rows
        read fp32 ()))`` is the one thing the three callers differ in.
        Returns (X, pools, fp32 (6,): the expert layers' four counters,
        then the rows attention read, twice — ``deepseek_v32``'s
        order, selected and in context)."""
        c = self.config

        def block(ffn, carry, layer_in):
            X, pools, stats = carry
            lp, layer = layer_in
            X, (pools, rows) = self._wrapped(
                lp["hc_attn"], X, lambda h: attend(
                    lp["attn"], self._norm(h, lp["norm1"]), layer, pools))
            X, counted = self._wrapped(
                lp["hc_ffn"], X, lambda h: ffn(
                    lp, self._rms(h, lp["norm2"])))
            return (X, pools, stats + jnp.concatenate(
                [counted[:4], jnp.stack([rows, rows])])), None

        def dense(lp, normed):
            m = lp["mlp"]
            return (HeldExpertsMLP._swiglu(
                normed.astype(c.params_dtype), m["w_gate"], m["w_up"],
                m["w_down"]), jnp.zeros((4,), jnp.float32))

        def experts(lp, normed):
            # the experts' stack stays whole, outside the scan's slices
            ffn, j = lp["ffn"]
            return self.moe.apply(
                {**ffn, "experts": params["moe"]["ffn"]["experts"]},
                normed, c.held_experts, token_valid=token_valid,
                expert_layer=j)

        carry = (X, pools, jnp.zeros((6,), jnp.float32))
        if self.n_dense:
            carry, _ = lax.scan(
                functools.partial(block, dense), carry,
                (params["dense"], jnp.arange(self.n_dense, dtype=jnp.int32)))
        if self.n_moe:
            j = jnp.arange(self.n_moe, dtype=jnp.int32)
            sliced = dict(params["moe"])
            sliced["ffn"] = ({k: v for k, v in sliced["ffn"].items()
                              if k != "experts"}, j)
            carry, _ = lax.scan(functools.partial(block, experts), carry,
                                (sliced, self.n_dense + j))
        return carry

    def _attend_expanded(self, ap, q_nope, q_rope, rows, positions, real):
        """Expanded attention of the queries at ``positions`` (one after
        another from ``positions[0]``) over ``rows``, the cached rows of
        positions 0..S-1, causal."""
        w_uk, w_uv = self._w_kvb(ap)
        with phase("attn.mla"):
            o = mla_expanded(q_nope, q_rope, rows, w_uk, w_uv, None,
                             self.config.softmax_scale,
                             q_offset=positions[0])
        return self._out(ap, o), jnp.sum(
            jnp.where(real, positions + 1, 0)).astype(jnp.float32)

    # ------------------------------------------------------ whole forward
    def apply(self, params: Dict[str, Any], tokens: jnp.ndarray
              ) -> jnp.ndarray:
        """``tokens`` (T,) -> fp32 logits (T, vocab): the whole sequence
        at once, no cache."""
        T = tokens.shape[0]
        positions = jnp.arange(T, dtype=jnp.int32)
        cos, sin = self.rope_table(T)
        valid = jnp.ones((T,), bool)

        def attend(ap, h, layer, pools):
            q_nope, q_rope, row = self._project(ap, h, cos, sin)
            out, rows = self._attend_expanded(
                ap, q_nope, q_rope, row, positions, valid)
            return out, (pools, rows)

        X = self._walk(params, self._streams(params, tokens), attend,
                       None, valid)[0]
        return self._logits(params, jnp.sum(X, axis=0))

    # ------------------------------------------------------ serving steps
    def chunk_step(self, params, pools, toks, start, plen, write_from,
                   page_row, *, ctx_len: int, page_size: int, table):
        """One prefill chunk, as :meth:`DeepSeekV32Model.chunk_step`:
        the chunk's rows are written through ``page_row`` and attention
        reads the slot's first ``ctx_len`` cached positions, all of
        them that are not in a query's future."""
        from apex_tpu.serving.kv_cache import (
            write_latent_tokens, write_targets,
        )

        C = toks.shape[0]
        positions = start + jnp.arange(C, dtype=jnp.int32)
        real = positions < plen
        cos, sin = self._rope_rows(table, positions)
        pages, offsets = write_targets(
            page_row, positions, real & (positions >= write_from), page_size)
        ctx_pages = page_row[:ctx_len // page_size]

        def attend(ap, h, layer, pools):
            q_nope, q_rope, row = self._project(ap, h, cos, sin)
            with phase("attn.mla"):
                pools = write_latent_tokens(
                    pools, layer, row, None, pages, offsets)
                rows = pools["ckv"][layer, ctx_pages].reshape(ctx_len, -1)
            out, read = self._attend_expanded(
                ap, q_nope, q_rope, rows, positions, real)
            return out, (pools, read)

        X, pools, _ = self._walk(
            params, self._streams(params, toks), attend, pools, real)
        last = jnp.sum(jnp.take(
            X, jnp.clip(plen - 1 - start, 0, C - 1), axis=1), axis=0)
        return self._logits(params, last[None])[0], pools

    def decode_fns(self, params, mesh, cache_config, **kw):
        """:meth:`DeepSeekV32Model.decode_fns`, and the chunk function
        says what its positions leave of its attention:
        ``chunk.k_blocks(start)`` -> (key blocks the chunk at ``start``
        computes, key blocks of the extent it reads), one head's, summed
        over the layers (``ops.attention.k_blocks_run``, the kernel's
        own bounds)."""
        fns = super().decode_fns(params, mesh, cache_config, **kw)
        C, page = fns.prefill_chunk, cache_config.page_size
        layers, dtype = self.config.num_hidden_layers, self.config.params_dtype

        @functools.lru_cache(maxsize=None)
        def k_blocks(start: int):
            # the extent ``chunk`` picks its program by
            ctx_len = min(-(-(start + C) // page) * page, cache_config.max_len)
            run, extent = k_blocks_run(C, ctx_len, start, dtype=dtype)
            return layers * run, layers * extent

        fns.chunk.k_blocks = k_blocks
        return fns

    def decode_step(self, params, pools, tokens, positions, active,
                    page_table, *, page_size: int, table):
        """One token for every slot: each layer writes the new row and
        walks the slot's pages up to it (``mla_paged``).  Returns what
        :meth:`DeepSeekV32Model.decode_step` does, the selection
        empty."""
        from apex_tpu.serving.kv_cache import (
            write_latent_tokens, write_targets,
        )

        c = self.config
        cos, sin = self._rope_rows(table, positions)
        pages, offsets = write_targets(page_table, positions, active,
                                       page_size)
        lengths = jnp.where(active, positions + 1, 0)
        read = jnp.sum(lengths).astype(jnp.float32)

        def attend(ap, h, layer, pools):
            q_nope, q_rope, row = self._project(ap, h, cos, sin)
            w_uk, w_uv = self._w_kvb(ap)
            with phase("attn.mla"):
                pools = write_latent_tokens(
                    pools, layer, row, None, pages, offsets)
                o = mla_paged(q_nope, q_rope, pools["ckv"], layer,
                              page_table, lengths, w_uk, w_uv,
                              c.softmax_scale)
            return self._out(ap, o), (pools, read)

        X, pools, stats = self._walk(
            params, self._streams(params, tokens), attend, pools, active)
        none = jnp.zeros((c.num_hidden_layers, tokens.shape[0], 0), jnp.int32)
        return (self._logits(params, jnp.sum(X, axis=0)), pools, stats,
                (none, none.astype(bool)))
