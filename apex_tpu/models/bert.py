"""Megatron-style BERT — bidirectional encoder with MLM + binary heads.

Capability match of the reference's standalone test BERT
(reference: apex/transformer/testing/standalone_bert.py, 217 LoC on the
Megatron toolkit): vocab-parallel embeddings (word + position +
tokentype), tensor-parallel encoder layers with padding-mask attention,
a tied-embedding masked-LM head and a binary (NSP/SOP) head.  Shares the
scanned-layer design of :class:`~apex_tpu.models.gpt.GPTModel`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.layer_norm import fused_layer_norm_affine
from apex_tpu.transformer.parallel_state import (
    DATA_PARALLEL_AXIS,
    TENSOR_PARALLEL_AXIS,
)
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    vocab_parallel_cross_entropy,
)

__all__ = ["BertConfig", "BertModel"]


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 32000
    num_layers: int = 4
    hidden_size: int = 512
    num_attention_heads: int = 8
    max_position_embeddings: int = 512
    num_tokentypes: int = 2
    ffn_hidden_size: Optional[int] = None
    layernorm_epsilon: float = 1e-5
    init_method_std: float = 0.02
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    # an amp.Policy drives both dtypes (one-kwarg O0..O5 switch)
    policy: Optional[Any] = None
    remat: bool = True
    # same chip-measured defaults as GPTConfig (fused_ce None = auto
    # by logits size, see GPTConfig)
    remat_policy: Optional[str] = (
        "dots_with_no_batch_dims_and_attention_saveable")
    fused_ce: Optional[bool] = None
    fused_ce_chunk: int = 8192
    add_binary_head: bool = True
    # "short" | "mid" | "pallas" | "xla" | None = auto via the measured
    # dispatch ladder (docs/attention.md): BERT's typical s<=512
    # encoder runs the single-pass fmha-short kernel; longer-context
    # fine-tunes land in the pipelined fmha-mid window
    attention_impl: Optional[str] = None

    def __post_init__(self):
        if self.policy is not None:
            self.params_dtype = self.policy.param_dtype
            self.compute_dtype = self.policy.compute_dtype
        if self.ffn_hidden_size is None:
            self.ffn_hidden_size = 4 * self.hidden_size
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(
                "hidden_size must be divisible by num_attention_heads"
            )

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def norm_dtype(self):
        if self.policy is not None and self.policy.keep_norm_fp32:
            return jnp.float32
        return self.params_dtype


def _normal(std):
    def init(key, shape, dtype):
        return std * jax.random.normal(key, shape, dtype)

    return init


class BertModel:
    """Encoder LM over a tp-sharded mesh (factory convention:
    init / param_specs / apply / loss)."""

    def __init__(self, config: BertConfig, axis_name: str = TENSOR_PARALLEL_AXIS):
        self.config = config
        self.axis_name = axis_name
        c = config
        init = _normal(c.init_method_std)
        out_init = _normal(c.init_method_std / (2.0 * c.num_layers) ** 0.5)
        self.embedding = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, init_method=init,
            params_dtype=c.params_dtype, axis_name=axis_name,
        )
        self.qkv = ColumnParallelLinear(
            c.hidden_size, 3 * c.hidden_size, gather_output=False,
            init_method=init, params_dtype=c.params_dtype,
            axis_name=axis_name,
        )
        self.attn_proj = RowParallelLinear(
            c.hidden_size, c.hidden_size, input_is_parallel=True,
            init_method=out_init, params_dtype=c.params_dtype,
            axis_name=axis_name,
        )
        self.fc1 = ColumnParallelLinear(
            c.hidden_size, c.ffn_hidden_size, gather_output=False,
            init_method=init, params_dtype=c.params_dtype,
            axis_name=axis_name,
        )
        self.fc2 = RowParallelLinear(
            c.ffn_hidden_size, c.hidden_size, input_is_parallel=True,
            init_method=out_init, params_dtype=c.params_dtype,
            axis_name=axis_name,
        )

    # ---------------------------------------------------------------- init
    def _ln(self):
        c = self.config
        return {
            "scale": jnp.ones((c.hidden_size,), c.norm_dtype),
            "bias": jnp.zeros((c.hidden_size,), c.norm_dtype),
        }

    def _init_one_layer(self, key) -> Dict[str, Any]:
        ks = jax.random.split(key, 4)
        return {
            "ln1": self._ln(),
            "qkv": self.qkv.init(ks[0]),
            "attn_proj": self.attn_proj.init(ks[1]),
            "ln2": self._ln(),
            "fc1": self.fc1.init(ks[2]),
            "fc2": self.fc2.init(ks[3]),
        }

    def init(self, key) -> Dict[str, Any]:
        c = self.config
        ks = jax.random.split(key, 7)
        layers = jax.vmap(self._init_one_layer)(
            jax.random.split(ks[2], c.num_layers)
        )
        init = _normal(c.init_method_std)
        params = {
            "embedding": self.embedding.init(ks[0]),
            "pos_embedding": init(
                ks[1], (c.max_position_embeddings, c.hidden_size),
                c.params_dtype,
            ),
            "tokentype_embedding": init(
                ks[3], (c.num_tokentypes, c.hidden_size), c.params_dtype
            ),
            "layers": layers,
            "final_ln": self._ln(),
            # MLM head: dense + LN + tied-embedding logits + bias
            "lm_head": {
                "dense": {
                    "weight": init(
                        ks[4], (c.hidden_size, c.hidden_size), c.params_dtype
                    ),
                    "bias": jnp.zeros((c.hidden_size,), c.params_dtype),
                },
                "ln": self._ln(),
                # vocab-sharded output bias, like the reference's
                # parallel lm-logits bias
                "bias": jnp.zeros((c.vocab_size,), c.params_dtype),
            },
        }
        if c.add_binary_head:
            params["pooler"] = {
                "weight": init(
                    ks[5], (c.hidden_size, c.hidden_size), c.params_dtype
                ),
                "bias": jnp.zeros((c.hidden_size,), c.params_dtype),
            }
            params["binary_head"] = {
                "weight": init(ks[6], (c.hidden_size, 2), c.params_dtype),
                "bias": jnp.zeros((2,), c.params_dtype),
            }
        return params

    def param_specs(self) -> Dict[str, Any]:
        c = self.config
        rep = {"scale": P(), "bias": P()}
        layer = {
            "ln1": rep,
            "qkv": self.qkv.param_specs(),
            "attn_proj": self.attn_proj.param_specs(),
            "ln2": rep,
            "fc1": self.fc1.param_specs(),
            "fc2": self.fc2.param_specs(),
        }
        stacked = jax.tree.map(
            lambda s: P(None, *s), layer, is_leaf=lambda x: isinstance(x, P)
        )
        specs = {
            "embedding": self.embedding.param_specs(),
            "pos_embedding": P(),
            "tokentype_embedding": P(),
            "layers": stacked,
            "final_ln": dict(rep),
            "lm_head": {
                "dense": {"weight": P(), "bias": P()},
                "ln": dict(rep),
                "bias": P(self.axis_name),
            },
        }
        if c.add_binary_head:
            specs["pooler"] = {"weight": P(), "bias": P()}
            specs["binary_head"] = {"weight": P(), "bias": P()}
        return specs

    # ------------------------------------------------------------- forward
    def _layer(self, lp, x, segs):
        c = self.config
        world = jax.lax.axis_size(self.axis_name)
        heads_local = c.num_attention_heads // world
        b, s, h = x.shape

        residual = x
        y = fused_layer_norm_affine(
            x, lp["ln1"]["scale"], lp["ln1"]["bias"], (h,),
            eps=c.layernorm_epsilon,
        ).astype(c.compute_dtype)
        qkv = self.qkv.apply(lp["qkv"], y)
        qkv = qkv.reshape(b, s, heads_local, 3, c.head_dim)
        q, k, v = (
            jnp.moveaxis(qkv[:, :, :, i], 2, 1) for i in range(3)
        )
        # padding exclusion via segment ids keeps the flash kernel on its
        # fast path (a dense additive bias would force dbias accumulation)
        q_seg, kv_seg = segs if segs is not None else (None, None)
        attn = flash_attention(
            q, k, v, causal=False, q_segment_ids=q_seg,
            kv_segment_ids=kv_seg, implementation=c.attention_impl,
        )
        attn = jnp.moveaxis(attn, 1, 2).reshape(b, s, heads_local * c.head_dim)
        out = self.attn_proj.apply(lp["attn_proj"], attn)
        x = residual + out.astype(residual.dtype)

        residual = x
        y = fused_layer_norm_affine(
            x, lp["ln2"]["scale"], lp["ln2"]["bias"], (h,),
            eps=c.layernorm_epsilon,
        ).astype(c.compute_dtype)
        y = self.fc1.apply(lp["fc1"], y)
        y = jax.nn.gelu(y, approximate=True)
        y = self.fc2.apply(lp["fc2"], y)
        return residual + y.astype(residual.dtype)

    def _embed(self, params, tokens, tokentype_ids=None) -> jnp.ndarray:
        """word + position (+ tokentype) embedding sum in compute dtype —
        one definition shared by the sequential and pipeline paths."""
        c = self.config
        s = tokens.shape[1]
        x = self.embedding.apply(params["embedding"], tokens)
        x = x + params["pos_embedding"][:s][None].astype(x.dtype)
        if tokentype_ids is not None:
            x = x + jnp.take(
                params["tokentype_embedding"], tokentype_ids, axis=0
            ).astype(x.dtype)
        return x.astype(c.compute_dtype)

    def _final_ln(self, params, x) -> jnp.ndarray:
        """Final encoder layernorm (fp32 math, compute-dtype out) — one
        definition shared by the sequential and pipeline paths."""
        c = self.config
        return fused_layer_norm_affine(
            x.astype(jnp.float32),
            params["final_ln"]["scale"], params["final_ln"]["bias"],
            (c.hidden_size,), eps=c.layernorm_epsilon,
        ).astype(c.compute_dtype)

    @staticmethod
    def _kv_segments(attention_mask) -> jnp.ndarray:
        """keep-tokens form segment 0; masked keys get a sentinel that
        never matches a query segment, so they are excluded exactly like
        the reference's additive -inf mask."""
        return jnp.where(attention_mask, 0, -2).astype(jnp.int32)

    def encode(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        attention_mask: Optional[jnp.ndarray] = None,
        tokentype_ids: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """tokens (b, s); attention_mask (b, s) True=keep.  Returns
        (b, s, h) final-layernormed hidden states."""
        c = self.config
        x = self._embed(params, tokens, tokentype_ids)

        segs = None
        if attention_mask is not None:
            kv_seg = self._kv_segments(attention_mask)
            segs = (jnp.zeros_like(kv_seg), kv_seg)

        def body(carry, lp):
            return self._layer(lp, carry, segs), None

        scan_body = body
        if c.remat:
            from apex_tpu.transformer.tensor_parallel.random import checkpoint

            scan_body = checkpoint(body, policy=c.remat_policy)
        x, _ = jax.lax.scan(scan_body, x, params["layers"])
        return self._final_ln(params, x)

    def mlm_hidden(self, params, hidden) -> jnp.ndarray:
        """MLM head transform (dense + GELU + LN) before the tied vocab
        projection."""
        c = self.config
        hd = params["lm_head"]
        h = jnp.matmul(hidden, hd["dense"]["weight"].astype(hidden.dtype))
        h = jax.nn.gelu(
            h + hd["dense"]["bias"].astype(h.dtype), approximate=True
        )
        return fused_layer_norm_affine(
            h.astype(jnp.float32), hd["ln"]["scale"], hd["ln"]["bias"],
            (c.hidden_size,), eps=c.layernorm_epsilon,
        ).astype(hidden.dtype)

    def lm_logits(self, params, hidden) -> jnp.ndarray:
        """MLM head → vocab-parallel logits (b, s, vocab/tp)."""
        h = self.mlm_hidden(params, hidden)
        w = params["embedding"]["weight"].astype(h.dtype)  # (vocab/tp, h)
        logits = jnp.einsum("bsh,vh->bsv", h, w)
        return logits + params["lm_head"]["bias"].astype(logits.dtype)

    def _per_token_ce(self, params, hidden, labels) -> jnp.ndarray:
        """Per-token MLM CE through the tied head incl. its per-vocab
        bias (fused or two-step, by ``config.fused_ce``)."""
        from apex_tpu.transformer.tensor_parallel.cross_entropy import (
            lm_head_cross_entropy,
        )

        return lm_head_cross_entropy(
            self.mlm_hidden(params, hidden),
            params["embedding"]["weight"], labels,
            axis_name=self.axis_name, fused=self.config.fused_ce,
            chunk=self.config.fused_ce_chunk,
            bias=params["lm_head"]["bias"],
        )

    def binary_logits(self, params, hidden) -> jnp.ndarray:
        """Pooled [CLS] → 2-way head (reference: NSP/SOP head)."""
        pooled = jnp.tanh(
            hidden[:, 0] @ params["pooler"]["weight"].astype(hidden.dtype)
            + params["pooler"]["bias"].astype(hidden.dtype)
        )
        return (
            pooled @ params["binary_head"]["weight"].astype(pooled.dtype)
            + params["binary_head"]["bias"].astype(pooled.dtype)
        ).astype(jnp.float32)

    def apply(self, params, tokens, attention_mask=None, tokentype_ids=None):
        hidden = self.encode(params, tokens, attention_mask, tokentype_ids)
        lm = self.lm_logits(params, hidden)
        if self.config.add_binary_head:
            return lm, self.binary_logits(params, hidden)
        return lm, None

    def loss(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        lm_labels: jnp.ndarray,
        loss_mask: jnp.ndarray,
        attention_mask: Optional[jnp.ndarray] = None,
        binary_labels: Optional[jnp.ndarray] = None,
        tokentype_ids: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """Masked-LM CE averaged over masked positions (+ binary CE),
        pmean over dp (reference: standalone BERT's loss_func)."""
        hidden = self.encode(params, tokens, attention_mask, tokentype_ids)
        binary = (
            self.binary_logits(params, hidden)
            if self.config.add_binary_head else None
        )
        per_token = self._per_token_ce(params, hidden, lm_labels)
        mask = loss_mask.astype(jnp.float32)
        # global masked mean: psum numerator and denominator separately —
        # a pmean of per-shard ratios would weight shards with different
        # mask counts unequally
        num = jax.lax.psum(jnp.sum(per_token * mask), DATA_PARALLEL_AXIS)
        den = jax.lax.psum(jnp.sum(mask), DATA_PARALLEL_AXIS)
        loss = num / jnp.maximum(den, 1.0)
        if binary is not None and binary_labels is not None:
            logp = jax.nn.log_softmax(binary, axis=-1)
            sop = -jnp.mean(
                jnp.take_along_axis(logp, binary_labels[:, None], 1)[:, 0]
            )
            loss = loss + jax.lax.pmean(sop, DATA_PARALLEL_AXIS)
        return loss

    # ------------------------------------------------------ pipeline path
    def pipeline_param_specs(self) -> Dict[str, Any]:
        """Param specs with the stacked-layer dim sharded over "pp"
        (same contract as GPT/T5)."""
        from apex_tpu.transformer.pipeline_parallel import (
            pipeline_stage_specs,
        )

        specs = self.param_specs()
        specs["layers"] = pipeline_stage_specs(specs["layers"])
        return specs

    def pipeline_loss(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        lm_labels: jnp.ndarray,
        loss_mask: jnp.ndarray,
        num_microbatches: int,
        attention_mask: Optional[jnp.ndarray] = None,
        binary_labels: Optional[jnp.ndarray] = None,
        tokentype_ids: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """Masked-LM (+ binary) loss through the compiled pipeline
        schedule (reference: run_bert_minimal_test.py drives the
        standalone BERT through the pipeline schedules).  Same placement
        contract as :meth:`pipeline_param_specs`.  The padding mask
        rides the carried state as segment ids; the masked-mean's
        numerator/denominator ride the per-microbatch result vector so
        the global mean weights every masked position equally."""
        from apex_tpu.transformer.pipeline_parallel import pipeline

        c = self.config
        b, s = tokens.shape
        if b % num_microbatches:
            raise ValueError(
                f"local batch ({b}) must be divisible by "
                f"num_microbatches ({num_microbatches})"
            )
        mb = b // num_microbatches

        def shard(x):
            return (
                None if x is None
                else x.reshape(num_microbatches, mb, *x.shape[1:])
            )

        mbs = {
            "tokens": shard(tokens),
            "lm_labels": shard(lm_labels),
            "loss_mask": shard(loss_mask),
        }
        if attention_mask is not None:
            mbs["attention_mask"] = shard(attention_mask)
        if tokentype_ids is not None:
            mbs["tokentype_ids"] = shard(tokentype_ids)
        use_binary = c.add_binary_head and binary_labels is not None
        if use_binary:
            mbs["binary_labels"] = shard(binary_labels)

        def first_fn(m):
            state = {"x": self._embed(
                params, m["tokens"], m.get("tokentype_ids")
            )}
            if "attention_mask" in m:
                state["kv_seg"] = self._kv_segments(m["attention_mask"])
            return state

        def stage_fn(state):
            segs = None
            if "kv_seg" in state:
                segs = (jnp.zeros_like(state["kv_seg"]), state["kv_seg"])

            def body(carry, lp):
                return self._layer(lp, carry, segs), None

            out, _ = jax.lax.scan(body, state["x"], params["layers"])
            return {**state, "x": out}

        def last_fn(state, m):
            x = self._final_ln(params, state["x"])
            per_token = self._per_token_ce(params, x, m["lm_labels"])
            mask = m["loss_mask"].astype(jnp.float32)
            num = jnp.sum(per_token * mask)
            den = jnp.sum(mask)
            if use_binary:
                logp = jax.nn.log_softmax(
                    self.binary_logits(params, x), axis=-1
                )
                sop_num = -jnp.sum(jnp.take_along_axis(
                    logp, m["binary_labels"][:, None], 1
                )[:, 0])
                rows = jnp.float32(mb)
            else:
                sop_num = jnp.float32(0.0)
                rows = jnp.float32(0.0)
            return jnp.stack([num, den, sop_num, rows])

        per = pipeline(first_fn, stage_fn, last_fn, mbs, remat=c.remat)
        num, den, sop_num, rows = per.sum(axis=0)
        loss = jax.lax.psum(num, DATA_PARALLEL_AXIS) / jnp.maximum(
            jax.lax.psum(den, DATA_PARALLEL_AXIS), 1.0
        )
        if use_binary:
            loss = loss + (
                jax.lax.psum(sop_num, DATA_PARALLEL_AXIS)
                / jnp.maximum(jax.lax.psum(rows, DATA_PARALLEL_AXIS), 1.0)
            )
        return loss

    def pipeline_grads(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        lm_labels: jnp.ndarray,
        loss_mask: jnp.ndarray,
        num_microbatches: int,
        attention_mask: Optional[jnp.ndarray] = None,
        binary_labels: Optional[jnp.ndarray] = None,
        tokentype_ids: Optional[jnp.ndarray] = None,
    ) -> tuple:
        """Masked-LM (+ binary) fwd+bwd through the production 1F1B
        schedule dispatched by ``get_forward_backward_func`` — returns
        ``(loss, grads)`` with O(pp) activation memory.

        The 1F1B contract needs a *scalar* per-microbatch loss, but the
        masked mean's denominator spans all microbatches and dp shards.
        Both denominators are functions of the data only, so they are
        psum'd *before* the schedule and folded into each microbatch's
        scalar: ``loss_m = M*(num_m/D + sop_m/R)`` makes
        ``mean_m loss_m`` exactly the global objective of
        :meth:`pipeline_loss`, with exact gradients.

        Grad semantics: the returned grads are already psum'd over dp
        (the objective's denominators are global, so the dp reduction is
        a sum, not a mean) — step a replicated optimizer with them
        directly; do not reduce over dp again."""
        from apex_tpu.transformer.parallel_state import (
            PIPELINE_PARALLEL_AXIS,
        )
        from apex_tpu.transformer.pipeline_parallel import (
            get_forward_backward_func,
            sync_replicated_grads,
        )

        c = self.config
        b, s = tokens.shape
        if b % num_microbatches:
            raise ValueError(
                f"local batch ({b}) must be divisible by "
                f"num_microbatches ({num_microbatches})"
            )
        mb = b // num_microbatches

        def shard(x):
            return (
                None if x is None
                else x.reshape(num_microbatches, mb, *x.shape[1:])
            )

        mbs = {
            "tokens": shard(tokens),
            "lm_labels": shard(lm_labels),
            "loss_mask": shard(loss_mask),
        }
        if attention_mask is not None:
            mbs["attention_mask"] = shard(attention_mask)
        if tokentype_ids is not None:
            mbs["tokentype_ids"] = shard(tokentype_ids)
        use_binary = c.add_binary_head and binary_labels is not None
        if use_binary:
            mbs["binary_labels"] = shard(binary_labels)

        M = jnp.float32(num_microbatches)
        den_global = jnp.maximum(jax.lax.psum(
            jnp.sum(loss_mask.astype(jnp.float32)), DATA_PARALLEL_AXIS
        ), 1.0)
        rows_global = jnp.maximum(jax.lax.psum(
            jnp.float32(b), DATA_PARALLEL_AXIS
        ), 1.0)

        def first_fn(prm, m):
            state = {"x": self._embed(
                prm, m["tokens"], m.get("tokentype_ids")
            )}
            if "attention_mask" in m:
                state["kv_seg"] = self._kv_segments(m["attention_mask"])
            return state

        def stage_fn(prm, state):
            segs = None
            if "kv_seg" in state:
                segs = (jnp.zeros_like(state["kv_seg"]), state["kv_seg"])

            def body(carry, lp):
                return self._layer(lp, carry, segs), None

            out, _ = jax.lax.scan(body, state["x"], prm["layers"])
            return {**state, "x": out}

        def last_fn(prm, state, m):
            x = self._final_ln(prm, state["x"])
            per_token = self._per_token_ce(prm, x, m["lm_labels"])
            mask = m["loss_mask"].astype(jnp.float32)
            loss_m = jnp.sum(per_token * mask) / den_global
            if use_binary:
                logp = jax.nn.log_softmax(
                    self.binary_logits(prm, x), axis=-1
                )
                sop = -jnp.sum(jnp.take_along_axis(
                    logp, m["binary_labels"][:, None], 1
                )[:, 0])
                loss_m = loss_m + sop / rows_global
            return M * loss_m

        fwd_bwd = get_forward_backward_func(
            pipeline_model_parallel_size=jax.lax.axis_size(
                PIPELINE_PARALLEL_AXIS
            ),
        )
        losses, grads = fwd_bwd(first_fn, stage_fn, last_fn, params, mbs)
        grads = sync_replicated_grads(grads, self.pipeline_param_specs())
        # each shard's mean(losses) — and each shard's grads — is its
        # local contribution to the already-globally-normalized
        # objective; psum over dp completes both
        loss = jax.lax.psum(jnp.mean(losses), DATA_PARALLEL_AXIS)
        grads = jax.tree.map(
            lambda g: jax.lax.psum(g, DATA_PARALLEL_AXIS), grads
        )
        return loss, grads
