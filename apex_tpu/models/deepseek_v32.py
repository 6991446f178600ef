"""DeepSeek-V3.2 on the serving path: latent attention over a paged
latent cache, lightning-indexer sparse selection, and a no-drop expert
layer that is told which experts it holds.

The block (pre-norm residual, RMSNorm, no biases)::

    x += Attn(rms(x)),  x += FFN(rms(x))

**Attention** is multi-head latent attention (:mod:`apex_tpu.ops.
attention_latent`): per token and layer the cache holds ONE row shared
by all heads, ``[c_kv | k_r]`` (``kv_lora_rank + qk_rope_head_dim``
wide), and one index key (``index_head_dim``).  The lightning indexer
(:mod:`apex_tpu.ops.sparse_index`) scores every earlier token for each
query and attention runs over the exact ``index_topk`` best.  One
attention, three walks of it:

- ``apply`` — a whole sequence, no cache (tests, small sizes);
- a prefill CHUNK — the chunk's rows are written into the paged pools,
  the context is read back through the slot's page row, and attention
  runs in the EXPANDED form under the selection mask (2048 queries
  share their keys, so building per-head keys once is the cheap form;
  gathering 2048 rows for each of 2048 queries would move 4.8 GB a
  layer);
- a DECODE step — one query a slot: the index keys of the slot's pages
  are scored and attention runs in the ABSORBED form over the exact
  ``index_topk`` best: the latent page walk reads every live row of the
  slot where it lies and masks out the rows not chosen.

**FFN**: ``first_k_dense`` leading SwiGLU layers, then
:class:`apex_tpu.transformer.moe.HeldExpertsMLP` layers.  The router
keeps its published width; ``held_experts`` says which experts this chip
computes (the chip's share of an expert-parallel deployment,
docs/models.md).

``decode_fns`` returns the :class:`apex_tpu.models.gpt.GPTDecodeFns`
contract, so ``ContinuousBatcher``, ``PagedKVCache`` and ``sampling``
run this model unedited.  The pools are DONATED to every step and
updated in place: the layer scans carry the stacked pools and each
layer's write is one scatter into them.  The decode step keeps three
things of its own in the carry (``decode.carry_extras``): its running
``counters`` (``COUNTER_NAMES``), and the step's own ``last_logits`` and
``last_selected`` / ``last_selected_valid`` — what it computed, for
whoever wants to hold the served path to a reference.

YaRN: frequencies from :func:`apex_tpu.ops.rope.yarn_inv_freq`, softmax
scale ``(dn + dr) ** -0.5 * m ** 2``.  Rotary pairs are half-split
``(x_i, x_{i + d/2})`` in MLA and in the indexer; the indexer runs in
the model's dtype (no FP8, no Hadamard rotation: it is orthogonal and
leaves ``q . k`` unchanged).  The multi-token-prediction module is not
held: the served logits do not depend on it.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.models.gpt import GPTDecodeFns
from apex_tpu.ops.attention_latent import mla_expanded, mla_paged
from apex_tpu.ops.layer_norm import (
    fused_layer_norm_affine, fused_rms_norm_affine,
)
from apex_tpu.ops.rope import (
    apply_rope_tables, yarn_inv_freq, yarn_mscale, yarn_table,
)
from apex_tpu.ops.sparse_index import (
    index_scores, mask_at, topk_indices, topk_mask,
)
from apex_tpu.telemetry import programs as _programs
from apex_tpu.telemetry.spans import phase
from apex_tpu.transformer.moe import HeldExpertsMLP

__all__ = ["DeepSeekV32Config", "DeepSeekV32Model", "COUNTER_NAMES"]

#: the fp32 vector every decode step adds to (``carry["counters"]``),
#: summed over the layers.  ``decode_choices`` counts (token, expert)
#: choices of live slots, ``decode_choices_held`` those that landed on
#: held experts, ``decode_experts_touched`` distinct held experts with
#: at least one row (per expert layer, summed), ``decode_load_max`` the
#: largest load among them (per expert layer, summed);
#: ``decode_selected_rows`` / ``decode_context_rows`` the rows attention
#: read / the tokens in context, per (live slot, layer).
COUNTER_NAMES = (
    "decode_steps", "decode_choices", "decode_choices_held",
    "decode_experts_touched", "decode_load_max", "decode_selected_rows",
    "decode_context_rows", "decode_slot_layers",
)


@dataclasses.dataclass(frozen=True)
class DeepSeekV32Config:
    """The published keys (``deepseek-ai/DeepSeek-V3.2`` ``config.json``)
    plus the share: ``vocab_size`` is the rows of the vocabulary held
    here, ``held_experts`` the routed experts computed here (ids into
    the router's ``n_routed_experts`` outputs)."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    held_experts: Tuple[int, ...]
    num_experts_per_tok: int
    n_group: int = 1
    topk_group: int = 1
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rope_original_max_position: int = 4096
    params_dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, cfg: dict, *, n_routed_experts: int,
                held_experts, params_dtype: Any = jnp.bfloat16):
        """From a ``config.json``-shaped dict.  ``n_routed_experts`` is
        the router's PUBLISHED width (a cut configuration's own key of
        that name counts the experts held)."""
        rs = cfg["rope_scaling"]
        names = [f.name for f in dataclasses.fields(cls)]
        return cls(
            **{k: cfg[k] for k in names if k in cfg and k not in (
                "n_routed_experts", "held_experts", "params_dtype")},
            n_routed_experts=int(n_routed_experts),
            held_experts=tuple(int(e) for e in held_experts),
            rope_factor=rs["factor"], rope_beta_fast=rs["beta_fast"],
            rope_beta_slow=rs["beta_slow"],
            rope_mscale_all_dim=rs["mscale_all_dim"],
            rope_original_max_position=rs[
                "original_max_position_embeddings"],
            params_dtype=params_dtype)

    @property
    def latent_dim(self) -> int:
        """Width of a cached row: ``[c_kv | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


class _Projected(NamedTuple):
    """What every form of the attention starts from (``_project``)."""

    q_nope: jnp.ndarray     # (n, H, dn)
    q_rope: jnp.ndarray     # (n, H, dr), rotated
    row: jnp.ndarray        # (n, dc + dr): the new cache row [c_kv | k_r]
    q_idx: jnp.ndarray      # (n, Hi, di)
    w_idx: jnp.ndarray      # (n, Hi) fp32, both scale factors folded in
    k_idx: jnp.ndarray      # (n, di): the new index key


class DeepSeekV32Model:
    def __init__(self, config: DeepSeekV32Config):
        c = self.config = config
        if not 0 <= c.first_k_dense_replace <= c.num_hidden_layers:
            raise ValueError("first_k_dense_replace outside the layers")
        self.n_dense = c.first_k_dense_replace
        self.n_moe = c.num_hidden_layers - self.n_dense
        self.moe = HeldExpertsMLP(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            top_k=c.num_experts_per_tok, n_group=c.n_group,
            topk_group=c.topk_group,
            routed_scaling_factor=c.routed_scaling_factor,
            n_shared_experts=c.n_shared_experts,
            params_dtype=c.params_dtype)
        self.inv_freq = yarn_inv_freq(
            c.qk_rope_head_dim, base=c.rope_theta, factor=c.rope_factor,
            beta_fast=c.rope_beta_fast, beta_slow=c.rope_beta_slow,
            original_max_position=c.rope_original_max_position)

    # ----------------------------------------------------------- params
    def _init_attn(self, key):
        c = self.config
        H, h = c.num_attention_heads, c.hidden_size
        ks = jax.random.split(key, 10)
        w = lambda k, shape, fan_in, gain=1.0: (
            gain * fan_in ** -0.5 * jax.random.normal(k, shape, jnp.float32)
        ).astype(c.params_dtype)
        norm = lambda k, n: 1.0 + 0.1 * jax.random.normal(
            k, (n,), jnp.float32)
        return {
            "wq_a": w(ks[0], (h, c.q_lora_rank), h),
            "q_norm": norm(ks[1], c.q_lora_rank),
            "wq_b": w(ks[2], (c.q_lora_rank, H * (
                c.qk_nope_head_dim + c.qk_rope_head_dim)), c.q_lora_rank),
            "wkv_a": w(ks[3], (h, c.latent_dim), h),
            "kv_norm": norm(ks[4], c.kv_lora_rank),
            "wkv_b": w(ks[5], (c.kv_lora_rank, H * (
                c.qk_nope_head_dim + c.v_head_dim)), c.kv_lora_rank),
            # x3: with N(0, 1/fan_in) everywhere a softmax average over
            # many random values would leave attention a small part of
            # the residual; this keeps it comparable to the FFN's
            "wo": w(ks[6], (H * c.v_head_dim, h), H * c.v_head_dim, 3.0),
            "idx_wq": w(ks[7], (c.q_lora_rank,
                                c.index_n_heads * c.index_head_dim),
                        c.q_lora_rank),
            "idx_wk": w(ks[8], (h, c.index_head_dim), h),
            "idx_knorm_w": jnp.ones((c.index_head_dim,), jnp.float32),
            "idx_knorm_b": jnp.zeros((c.index_head_dim,), jnp.float32),
            "idx_ww": w(ks[9], (h, c.index_n_heads), h),
        }

    def _init_layer(self, key, dense: bool):
        c = self.config
        h, f = c.hidden_size, c.intermediate_size
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        w = lambda k, shape, fan_in: (
            fan_in ** -0.5 * jax.random.normal(k, shape, jnp.float32)
        ).astype(c.params_dtype)
        out = {"attn": self._init_attn(k1),
               "norm1": jnp.ones((h,), jnp.float32),
               "norm2": jnp.ones((h,), jnp.float32)}
        if dense:
            out["mlp"] = {"w_gate": w(k2, (h, f), h), "w_up": w(k3, (h, f), h),
                          "w_down": w(k4, (f, h), f)}
        else:
            out["ffn"] = self.moe.init(k5, len(c.held_experts))
        return out

    def init(self, key) -> Dict[str, Any]:
        """Seeded weights, N(0, 1/fan_in) (``wo`` x3), in
        ``params_dtype`` with fp32 norms and router bias.  Layers are
        stacked: the leading dense ones under ``dense``, the expert
        layers under ``moe``."""
        c = self.config
        ke, kh, kd, km = jax.random.split(key, 4)
        stack = lambda k, n, dense: jax.vmap(
            lambda kk: self._init_layer(kk, dense))(jax.random.split(k, n))
        params = {
            "embedding": {"weight": jax.random.normal(
                ke, (c.vocab_size, c.hidden_size), jnp.float32
            ).astype(c.params_dtype)},
            "head": {"weight": (c.hidden_size ** -0.5 * jax.random.normal(
                kh, (c.hidden_size, c.vocab_size), jnp.float32)
            ).astype(c.params_dtype)},
            "final_norm": {"weight": jnp.ones((c.hidden_size,), jnp.float32)},
        }
        if self.n_dense:
            params["dense"] = stack(kd, self.n_dense, True)
        if self.n_moe:
            params["moe"] = stack(km, self.n_moe, False)
        return params

    def param_specs(self) -> Dict[str, Any]:
        """Everything replicated: attention and the indexer are
        data-parallel in the deployment this serves, and the experts
        held here are this chip's own."""
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), shapes)

    # ---------------------------------------------------------- pieces
    def _rms(self, x, w):
        return fused_rms_norm_affine(
            x, w, x.shape[-1], eps=self.config.rms_norm_eps,
            implementation="xla")

    def _norm(self, x, w):
        """The fp32 residual stream, normalised, in the weights' dtype
        (what every matrix product of a block reads)."""
        return self._rms(x, w).astype(self.config.params_dtype)

    def _embed(self, params, tokens):
        """The residual stream is carried in fp32 through the layers (a
        bf16 stream costs a bf16 rounding of the whole sum at every
        sub-layer; the products still run in the weights' dtype)."""
        return jnp.take(params["embedding"]["weight"], tokens,
                        axis=0).astype(jnp.float32)

    def _rope_first(self, x, cos, sin):
        """Rotate the first ``qk_rope_head_dim`` of the last axis."""
        dr = self.config.qk_rope_head_dim
        return jnp.concatenate(
            [apply_rope_tables(x[..., :dr], cos, sin), x[..., dr:]], -1)

    def _project(self, ap, h, cos, sin):
        """``h`` (n, hidden) at positions whose rotary rows are
        ``cos``/``sin`` (n, dr/2) -> :class:`_Projected`."""
        c = self.config
        n = h.shape[0]
        H, dn, dr = (c.num_attention_heads, c.qk_nope_head_dim,
                     c.qk_rope_head_dim)
        with phase("attn.mla"):
            c_q = self._rms(jnp.matmul(h, ap["wq_a"]), ap["q_norm"])
            q = jnp.matmul(c_q, ap["wq_b"]).reshape(n, H, dn + dr)
            q_nope = q[..., :dn]
            q_rope = apply_rope_tables(q[..., dn:], cos[:, None],
                                       sin[:, None])
            kv = jnp.matmul(h, ap["wkv_a"])
            row = jnp.concatenate([
                self._rms(kv[:, :c.kv_lora_rank], ap["kv_norm"]),
                apply_rope_tables(kv[:, c.kv_lora_rank:], cos, sin)], -1)
        with phase("attn.index"):
            q_i = jnp.matmul(c_q, ap["idx_wq"]).reshape(
                n, c.index_n_heads, c.index_head_dim)
            q_i = self._rope_first(q_i, cos[:, None], sin[:, None])
            k_i = fused_layer_norm_affine(
                jnp.matmul(h, ap["idx_wk"]), ap["idx_knorm_w"],
                ap["idx_knorm_b"], c.index_head_dim, eps=c.rms_norm_eps,
                implementation="xla")
            k_i = self._rope_first(k_i, cos, sin)
            w_i = jnp.matmul(h, ap["idx_ww"],
                             preferred_element_type=jnp.float32) * (
                c.index_n_heads ** -0.5 * c.index_head_dim ** -0.5)
        return _Projected(q_nope, q_rope, row, q_i, w_i, k_i)

    def _w_kvb(self, ap):
        c = self.config
        w = ap["wkv_b"].reshape(c.kv_lora_rank, c.num_attention_heads,
                                c.qk_nope_head_dim + c.v_head_dim)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def _out(self, ap, o):
        with phase("attn.mla"):
            return jnp.matmul(o.reshape(o.shape[0], -1), ap["wo"],
                              preferred_element_type=jnp.float32)

    def _attend_expanded(self, ap, proj, positions, ctx_rows, ctx_kidx,
                         token_valid):
        """Expanded attention of ``n`` queries at ``positions`` over a
        context of ``S`` cached rows at positions 0..S-1, under the
        selection mask."""
        c = self.config
        S = ctx_rows.shape[0]
        causal = jnp.arange(S, dtype=jnp.int32)[None] <= positions[:, None]
        with phase("attn.index"):
            scores = index_scores(proj.q_idx, proj.w_idx, ctx_kidx)
        mask = topk_mask(scores, c.index_topk, causal)
        w_uk, w_uv = self._w_kvb(ap)
        with phase("attn.mla"):
            o = mla_expanded(proj.q_nope, proj.q_rope, ctx_rows, w_uk,
                             w_uv, mask, c.softmax_scale)
        real = token_valid[:, None]
        sel = jnp.stack([
            jnp.sum(mask & real).astype(jnp.float32),
            jnp.sum(jnp.where(token_valid, positions + 1, 0)
                    ).astype(jnp.float32)])
        return self._out(ap, o), sel

    def _walk(self, params, x, attend, pools, token_valid):
        """THE layer walk: ``x`` (n, hidden) through the dense layers
        and the expert layers.  ``attend(ap, h, layer, pools) ->
        (attention output, pools, fp32 (2,) [rows attention read,
        tokens in context])`` is the one thing the three callers
        differ in; a fourth thing it returns is stacked per layer and
        handed back (None where a caller wants nothing).  Returns (x,
        pools, the expert layers' counters (4,) and the selection's (2,)
        summed over the layers, the stacked fourth returns)."""
        c = self.config

        def block(ffn, carry, layer_in):
            x, pools, stats = carry
            lp, layer = layer_in
            a, pools, sel, kept = attend(
                lp["attn"], self._norm(x, lp["norm1"]), layer, pools)
            x = x + a
            y, counted = ffn(lp, self._rms(x, lp["norm2"]))     # fp32 in
            return (x + y, pools,
                    stats + jnp.concatenate([counted[:4], sel])), kept

        def dense(lp, normed):
            m = lp["mlp"]
            return (HeldExpertsMLP._swiglu(
                normed.astype(c.params_dtype), m["w_gate"], m["w_up"],
                m["w_down"]),
                jnp.zeros((4,), jnp.float32))

        def experts(lp, normed):
            # the experts' stack stays whole, outside the scan's slices
            # (a layer's 1.4 GB would otherwise be copied out a step)
            ffn, j = lp["ffn"]
            return self.moe.apply(
                {**ffn, "experts": params["moe"]["ffn"]["experts"]},
                normed, c.held_experts, token_valid=token_valid,
                expert_layer=j)

        carry = (x, pools, jnp.zeros((6,), jnp.float32))
        kept = []
        if self.n_dense:
            carry, ys = lax.scan(
                functools.partial(block, dense), carry,
                (params["dense"], jnp.arange(self.n_dense, dtype=jnp.int32)))
            kept.append(ys)
        if self.n_moe:
            j = jnp.arange(self.n_moe, dtype=jnp.int32)
            sliced = dict(params["moe"])
            sliced["ffn"] = ({k: v for k, v in sliced["ffn"].items()
                              if k != "experts"}, j)
            carry, ys = lax.scan(functools.partial(block, experts), carry,
                                 (sliced, self.n_dense + j))
            kept.append(ys)
        return (*carry, jax.tree.map(
            lambda *a: jnp.concatenate(a, axis=0), *kept))

    def _logits(self, params, x):
        return jnp.matmul(self._norm(x, params["final_norm"]["weight"]),
                          params["head"]["weight"],
                          preferred_element_type=jnp.float32)

    def rope_table(self, max_len: int):
        """fp32 (cos, sin) rows for positions ``0 .. max_len - 1``."""
        return yarn_table(max_len, self.inv_freq)

    def _rope_rows(self, table, positions):
        last = table[0].shape[0] - 1
        p = jnp.minimum(positions, last)
        return jnp.take(table[0], p, axis=0), jnp.take(table[1], p, axis=0)

    # ------------------------------------------------------ whole forward
    def apply(self, params: Dict[str, Any], tokens: jnp.ndarray
              ) -> jnp.ndarray:
        """``tokens`` (T,) -> fp32 logits (T, vocab rows held): the
        whole sequence at once, no cache (the expanded form under the
        selection mask)."""
        T = tokens.shape[0]
        positions = jnp.arange(T, dtype=jnp.int32)
        cos, sin = self.rope_table(T)
        valid = jnp.ones((T,), bool)

        def attend(ap, h, layer, pools):
            proj = self._project(ap, h, cos, sin)
            out, sel = self._attend_expanded(
                ap, proj, positions, proj.row, proj.k_idx, valid)
            return out, pools, sel, None

        x = self._embed(params, tokens)
        x = self._walk(params, x, attend, None, valid)[0]
        return self._logits(params, x)

    # ------------------------------------------------------ serving steps
    def _check_cache(self, cfg, prefill_chunk):
        c = self.config
        if getattr(cfg, "kind", "kv") != "latent":
            raise ValueError(
                "this model caches one latent row a token: build the "
                "cache with KVCacheConfig(kind='latent', num_heads=1, "
                "head_dim=latent_dim, latent_dim=..., index_dim=...)")
        want = (c.num_hidden_layers, c.latent_dim, c.index_head_dim)
        got = (cfg.num_layers, cfg.latent_dim, cfg.index_dim)
        if want != got:
            raise ValueError(
                f"cache config (layers, latent_dim, index_dim) = {got} "
                f"does not match the model's {want}")
        if prefill_chunk is None or int(prefill_chunk) < 1 \
                or int(prefill_chunk) % cfg.page_size:
            raise ValueError(
                "this model ingests prompts in chunks: pass "
                "prefill_chunk, a multiple of the page size")

    def chunk_step(self, params, pools, toks, start, plen, write_from,
                   page_row, *, ctx_len: int, page_size: int, table):
        """One prefill chunk: ``toks`` (C,) at positions ``start ..``;
        rows at positions in [``write_from``, ``plen``) are written
        through ``page_row``; attention reads the slot's first
        ``ctx_len`` cached positions (static: ``start + C`` rounded up to
        pages).  Returns (logits of position ``plen - 1`` (vocab,),
        pools)."""
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
            proj = self._project(ap, h, cos, sin)
            with phase("attn.mla"):
                pools = write_latent_tokens(
                    pools, layer, proj.row, proj.k_idx, pages, offsets)
                rows = pools["ckv"][layer, ctx_pages].reshape(ctx_len, -1)
            with phase("attn.index"):
                kidx = pools["kidx"][layer, ctx_pages].reshape(ctx_len, -1)
            out, sel = self._attend_expanded(
                ap, proj, positions, rows, kidx, real)
            return out, pools, sel, None

        x = self._embed(params, toks)
        x, pools, _, _ = self._walk(params, x, attend, pools, real)
        last = jnp.take(x, jnp.clip(plen - 1 - start, 0, C - 1), axis=0)
        return self._logits(params, last[None])[0], pools

    def decode_step(self, params, pools, tokens, positions, active,
                    page_table, *, page_size: int, table):
        """One token for every slot: ``tokens`` (B,) at ``positions``
        (B,) (the slot's context length), ``active`` (B,) bool.  Each
        layer writes the new row, scores the slot's cached index keys,
        takes the exact top ``index_topk`` positions and attends over
        them in the absorbed form, walking the slot's pages with every
        row not chosen masked out (the mask is the same set as the
        positions, from the K-th score: ``sparse_index.mask_at``).
        Returns (fp32 logits (B, vocab), pools, counters (6,), the
        chosen positions (layers, B, K) int32 and which of them are real
        (layers, B, K) bool)."""
        from apex_tpu.serving.kv_cache import (
            write_latent_tokens, write_targets,
        )

        c = self.config
        max_len = page_table.shape[1] * page_size
        cos, sin = self._rope_rows(table, positions)
        pages, offsets = write_targets(page_table, positions, active,
                                       page_size)
        in_ctx = (jnp.arange(max_len, dtype=jnp.int32)[None]
                  <= positions[:, None]) & active[:, None]
        K = min(c.index_topk, max_len)
        lengths = jnp.where(active, positions + 1, 0)

        def attend(ap, h, layer, pools):
            proj = self._project(ap, h, cos, sin)
            with phase("attn.mla"):
                pools = write_latent_tokens(
                    pools, layer, proj.row, proj.k_idx, pages, offsets)
            with phase("attn.index"):
                kidx = pools["kidx"][layer, page_table].reshape(
                    h.shape[0], max_len, -1)
                scores = index_scores(proj.q_idx[:, None],
                                      proj.w_idx[:, None], kidx)[:, 0]
            idx, chosen, kth = topk_indices(scores, K, in_ctx)
            w_uk, w_uv = self._w_kvb(ap)
            with phase("attn.mla"):
                with phase("attn.mla.core"):
                    selected = mask_at(jnp.where(in_ctx, scores, -jnp.inf),
                                       kth, K, in_ctx)
                o = mla_paged(proj.q_nope, proj.q_rope, pools["ckv"], layer,
                              page_table, lengths, w_uk, w_uv,
                              c.softmax_scale, selected=selected)
            sel = jnp.stack([jnp.sum(chosen).astype(jnp.float32),
                             jnp.sum(lengths).astype(jnp.float32)])
            return self._out(ap, o), pools, sel, (idx, chosen)

        x = self._embed(params, tokens)
        x, pools, stats, selected = self._walk(
            params, x, attend, pools, active)
        return self._logits(params, x), pools, stats, selected

    def decode_fns(
        self,
        params: Dict[str, Any],
        mesh,
        cache_config,
        *,
        max_prompt_len: int,
        prefill_chunk: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_id: Optional[int] = None,
    ) -> GPTDecodeFns:
        """The serving step functions, in ``GPTModel.decode_fns``'s
        contract: ``chunk`` (one ``prefill_chunk``-token ingestion
        step), ``decode`` (one token for every live slot) and
        ``prefill`` (the monolithic signature, served by running the
        chunks one after another).  ``params`` and the
        ``init_pools(cache_config)`` dict are expected on ``mesh``,
        replicated (``param_specs`` / ``pool_specs``).

        Each step takes the pools DONATED and returns them updated in
        place.  ``decode`` keeps in the carry, beside the batcher's five
        per-slot entries (``decode.carry_extras``): ``counters`` grown by
        ``COUNTER_NAMES`` a step, and the step's ``last_logits`` (slots,
        vocab) and ``last_selected`` / ``last_selected_valid`` (layers,
        slots, K).  ``chunk`` compiles once per context extent (``start +
        prefill_chunk`` rounded to pages, at most ``ceil(max_len /
        prefill_chunk)`` programs): a chunk reads and scores only the
        pages that can hold its context."""
        from apex_tpu.serving.kv_cache import init_pools
        from apex_tpu.serving.sampling import advance_slots, sample

        c, cfg = self.config, cache_config
        self._check_cache(cfg, prefill_chunk)
        if max_prompt_len > cfg.max_len:
            raise ValueError(
                f"max_prompt_len {max_prompt_len} exceeds the slot bound "
                f"{cfg.max_len} (pages_per_seq * page_size)")
        C, page = int(prefill_chunk), cfg.page_size
        max_len = cfg.max_len
        table = self.rope_table(max_len)
        S, K = cfg.max_seqs, min(c.index_topk, max_len)
        carry_extras = {
            "counters": jnp.zeros((len(COUNTER_NAMES),), jnp.float32),
            "last_logits": jnp.zeros((S, c.vocab_size), jnp.float32),
            "last_selected": jnp.zeros(
                (c.num_hidden_layers, S, K), jnp.int32),
            "last_selected_valid": jnp.zeros(
                (c.num_hidden_layers, S, K), bool),
        }

        @phase("prefill")
        def _chunk(params, pools, toks, start, plen, write_from, page_row,
                   key, *, ctx_len):
            logits, pools = self.chunk_step(
                params, pools, toks[0], start, plen, write_from, page_row,
                ctx_len=ctx_len, page_size=page, table=table)
            tok = sample(logits[None], jax.random.fold_in(key, plen),
                         temperature, top_k, top_p)[0]
            return pools, tok, logits

        @phase("decode")
        def _decode(params, pools, carry, page_table):
            active = jnp.logical_not(carry["done"])
            positions = carry["lengths"]
            logits, pools, stats, (idx, chosen) = self.decode_step(
                params, pools, carry["tokens"], positions, active,
                page_table, page_size=page, table=table)
            counted = jnp.stack([
                jnp.float32(1), *stats,
                jnp.sum(active.astype(jnp.int32)).astype(jnp.float32)
                * c.num_hidden_layers])
            return pools, {
                **advance_slots(carry, logits, active,
                                temperature=temperature, top_k=top_k,
                                top_p=top_p, eos_id=eos_id),
                "counters": carry["counters"] + counted,
                "last_logits": logits, "last_selected": idx,
                "last_selected_valid": chosen}

        _programs.own(_chunk.__name__, _decode.__name__,
                      layer="serving steps")
        cj = jax.jit(_chunk, donate_argnums=(1,), static_argnames=("ctx_len",))
        dj = jax.jit(_decode, donate_argnums=(1,))

        def chunk(pools, toks, start, plen, write_from, row, key):
            start = int(start)
            ctx_len = min(-(-(start + C) // page) * page, max_len)
            return cj(params, pools,
                      jnp.asarray(toks, jnp.int32).reshape(1, C),
                      jnp.int32(start), jnp.int32(plen),
                      jnp.int32(write_from), row, key, ctx_len=ctx_len)

        def prefill(pools, toks, length, page_row, key):
            toks = np.asarray(toks, np.int32).reshape(-1)
            n_chunks = -(-len(toks) // C)
            toks = np.pad(toks, (0, n_chunks * C - len(toks)))
            first = jnp.int32(0)
            for i in range(n_chunks):
                pools, tok, _ = chunk(pools, toks[i * C:(i + 1) * C], i * C,
                                      length, 0, page_row, key)
                first = jnp.where((length > i * C) & (length <= (i + 1) * C),
                                  tok, first)
            return pools, first

        decode = lambda pools, carry, pt: dj(params, pools, carry, pt)
        chunk.prefill_chunk = C
        decode.eos_id = eos_id
        carry_sharding = NamedSharding(mesh, P())
        decode.carry_sharding = carry_sharding
        decode.carry_extras = carry_extras
        # no ``weight_stream_bytes``: a step streams the experts its
        # tokens touched, not the pool (the counters say which)
        decode.weight_dtype = jnp.dtype(c.params_dtype).name
        decode.tp = 1
        return GPTDecodeFns(
            prefill=prefill, decode=decode, prefill_jit=cj, decode_jit=dj,
            eos_id=eos_id, chunk=chunk, chunk_jit=cj, prefill_chunk=C,
            weight_dtype=decode.weight_dtype, tp=1,
            carry_sharding=carry_sharding,
            param_specs=self.param_specs(),
            pool_specs=jax.tree.map(
                lambda _: P(), jax.eval_shape(lambda: init_pools(cfg))))
