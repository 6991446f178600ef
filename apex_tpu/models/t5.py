"""T5-style encoder-decoder transformer over the tp-sharded mesh.

The reference supports encoder-and-decoder models at the *scheduling*
level — ``ModelType.encoder_and_decoder`` with
``pipeline_model_parallel_split_rank`` splits the pipeline into encoder
and decoder stages (reference: apex/transformer/pipeline_parallel/
schedules/common.py:18-108, apex/transformer/parallel_state.py split-rank
plumbing) — but ships no standalone enc-dec test model.  This module
provides the model that exercises that capability end to end:

- bidirectional encoder (non-causal flash attention) and causal decoder
  with cross-attention over the encoder output;
- Megatron-style tensor parallelism throughout: fused-qkv column-parallel
  self-attention, column-parallel cross q/kv, row-parallel projections,
  vocab-parallel tied embedding + cross entropy;
- layers stacked and iterated with ``lax.scan`` (one compiled layer body),
  remat via ``jax.checkpoint``;
- a pipeline path through :func:`~apex_tpu.transformer.pipeline_parallel.
  pipeline_encdec` where stages before the split run encoder layers and
  stages after it run decoder layers, cross-attention memory riding the
  ring with its microbatch.

Architectural notes vs the original T5: learned absolute position
embeddings and GELU MLPs (matching this package's GPT/BERT family) stand
in for relative position biases and ReLU — the parallelism and pipeline
capabilities, not checkpoint compatibility, are the point.

Layer-struct homogeneity: encoder and decoder layers share ONE param
structure (self-attn + cross-attn + MLP); encoder layers never apply
their cross-attention weights, which stay at init and receive zero
gradient.  This keeps the stacked-layer pytree scannable and lets the
pipeline path shard a single ``(total_layers, ...)`` stack over "pp".
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.layer_norm import fused_layer_norm_affine
from apex_tpu.transformer.parallel_state import (
    DATA_PARALLEL_AXIS,
    PIPELINE_PARALLEL_AXIS,
    TENSOR_PARALLEL_AXIS,
)
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    vocab_parallel_cross_entropy,
)

__all__ = ["T5Config", "T5Model"]


@dataclasses.dataclass
class T5Config:
    vocab_size: int = 32000
    num_encoder_layers: int = 2
    num_decoder_layers: int = 2
    hidden_size: int = 256
    num_attention_heads: int = 4
    max_position_embeddings: int = 512
    ffn_hidden_size: Optional[int] = None  # defaults to 4*hidden
    layernorm_epsilon: float = 1e-5
    init_method_std: float = 0.02
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    # an amp.Policy drives the dtypes, as in GPTConfig/BertConfig
    policy: Optional[Any] = None
    remat: bool = True
    # same chip-measured defaults as GPTConfig (fused_ce None = auto
    # by logits size, see GPTConfig)
    remat_policy: Optional[str] = (
        "dots_with_no_batch_dims_and_attention_saveable")
    fused_ce: Optional[bool] = None
    fused_ce_chunk: int = 8192
    # "short" | "mid" | "pallas" | "xla" | None = auto via the measured
    # dispatch ladder (docs/attention.md) — the short-decoder /
    # short-encoder shapes T5 trains at sit inside the fmha-short
    # dispatch window (ops/attention_short.py), including both
    # self-attention and the sq!=sk cross-attention calls below;
    # longer contexts route to the pipelined fmha-mid kernel (the
    # ladder keys on max(sq, sk) for cross-attention)
    attention_impl: Optional[str] = None
    # route the pipeline path through pipeline_encdec_fused: ONE
    # homogeneous stage body per tick (gated cross-attention +
    # data-selected causal bias) instead of running both the encoder and
    # decoder bodies on every stage and selecting — collapses the
    # two-stream schedule's 2x per-tick FLOPs to ~1 decoder body.
    # False keeps the original two-stream pipeline_encdec.
    fused_pipeline: bool = True

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
    def norm_dtype(self) -> Any:
        if self.policy is not None and self.policy.keep_norm_fp32:
            return jnp.float32
        return self.params_dtype


def _normal(std):
    def init(key, shape, dtype):
        return std * jax.random.normal(key, shape, dtype)

    return init


class T5Model:
    """Encoder-decoder transformer; one unified layer struct serves both
    sides (see module docstring)."""

    def __init__(self, config: T5Config, axis_name: str = TENSOR_PARALLEL_AXIS):
        self.config = config
        self.axis_name = axis_name
        c = config
        depth = c.num_encoder_layers + c.num_decoder_layers
        init = _normal(c.init_method_std)
        out_init = _normal(c.init_method_std / (2.0 * depth) ** 0.5)
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
        # cross-attention: queries from the decoder stream, keys/values
        # from the encoder memory
        self.cross_q = ColumnParallelLinear(
            c.hidden_size, c.hidden_size, gather_output=False,
            init_method=init, params_dtype=c.params_dtype,
            axis_name=axis_name,
        )
        self.cross_kv = ColumnParallelLinear(
            c.hidden_size, 2 * c.hidden_size, gather_output=False,
            init_method=init, params_dtype=c.params_dtype,
            axis_name=axis_name,
        )
        self.cross_proj = RowParallelLinear(
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
        keys = jax.random.split(key, 6)
        return {
            "ln1": self._ln(),
            "qkv": self.qkv.init(keys[0]),
            "attn_proj": self.attn_proj.init(keys[1]),
            "ln_cross": self._ln(),
            "cross_q": self.cross_q.init(keys[2]),
            "cross_kv": self.cross_kv.init(keys[3]),
            "cross_proj": self.cross_proj.init(keys[4]),
            "ln2": self._ln(),
            "fc1": self.fc1.init(keys[5]),
            "fc2": self.fc2.init(jax.random.fold_in(key, 6)),
        }

    def init(self, key) -> Dict[str, Any]:
        c = self.config
        k_emb, k_pos_e, k_pos_d, k_enc, k_dec = jax.random.split(key, 5)
        enc_keys = jax.random.split(k_enc, c.num_encoder_layers)
        dec_keys = jax.random.split(k_dec, c.num_decoder_layers)
        pos = _normal(c.init_method_std)
        return {
            "embedding": self.embedding.init(k_emb),
            "enc_pos_embedding": pos(
                k_pos_e, (c.max_position_embeddings, c.hidden_size),
                c.params_dtype,
            ),
            "dec_pos_embedding": pos(
                k_pos_d, (c.max_position_embeddings, c.hidden_size),
                c.params_dtype,
            ),
            "enc_layers": jax.vmap(self._init_one_layer)(enc_keys),
            "dec_layers": jax.vmap(self._init_one_layer)(dec_keys),
            "enc_final_ln": self._ln(),
            "dec_final_ln": self._ln(),
        }

    def param_specs(self) -> Dict[str, Any]:
        rep = {"scale": P(), "bias": P()}
        layer = {
            "ln1": rep,
            "qkv": self.qkv.param_specs(),
            "attn_proj": self.attn_proj.param_specs(),
            "ln_cross": rep,
            "cross_q": self.cross_q.param_specs(),
            "cross_kv": self.cross_kv.param_specs(),
            "cross_proj": self.cross_proj.param_specs(),
            "ln2": rep,
            "fc1": self.fc1.param_specs(),
            "fc2": self.fc2.param_specs(),
        }
        stacked = jax.tree.map(
            lambda s: P(None, *s), layer, is_leaf=lambda x: isinstance(x, P)
        )
        return {
            "embedding": self.embedding.param_specs(),
            "enc_pos_embedding": P(),
            "dec_pos_embedding": P(),
            "enc_layers": stacked,
            "dec_layers": stacked,
            "enc_final_ln": dict(rep),
            "dec_final_ln": dict(rep),
        }

    # ------------------------------------------------------------- forward
    def _split_heads(self, x: jnp.ndarray, n: int) -> tuple:
        """(b, s, n*heads_local*d) → n arrays of (b, heads_local, s, d),
        head-grouped layout as in GPT (tp-invariant slices)."""
        c = self.config
        world = jax.lax.axis_size(self.axis_name)
        heads_local = c.num_attention_heads // world
        b, s, _ = x.shape
        x = x.reshape(b, s, heads_local, n, c.head_dim)
        return tuple(jnp.moveaxis(x[:, :, :, i], 2, 1) for i in range(n))

    def _merge_heads(self, x: jnp.ndarray) -> jnp.ndarray:
        b, h, s, d = x.shape
        return jnp.moveaxis(x, 1, 2).reshape(b, s, h * d)

    def _self_attention(self, lp, x, causal: bool, bias=None,
                        q_seg=None, kv_seg=None):
        c = self.config
        y = fused_layer_norm_affine(
            x, lp["ln1"]["scale"], lp["ln1"]["bias"],
            (c.hidden_size,), eps=c.layernorm_epsilon,
        ).astype(c.compute_dtype)
        q, k, v = self._split_heads(self.qkv.apply(lp["qkv"], y), 3)
        attn = flash_attention(
            q, k, v, causal=causal, bias=bias,
            q_segment_ids=q_seg, kv_segment_ids=kv_seg,
            bias_requires_grad=False,
            implementation=c.attention_impl,
        )
        out = self.attn_proj.apply(lp["attn_proj"], self._merge_heads(attn))
        return x + out.astype(x.dtype)

    def _cross_attention(self, lp, x, memory, gate=None,
                         q_seg=None, kv_seg=None):
        c = self.config
        y = fused_layer_norm_affine(
            x, lp["ln_cross"]["scale"], lp["ln_cross"]["bias"],
            (c.hidden_size,), eps=c.layernorm_epsilon,
        ).astype(c.compute_dtype)
        (q,) = self._split_heads(self.cross_q.apply(lp["cross_q"], y), 1)
        k, v = self._split_heads(
            self.cross_kv.apply(lp["cross_kv"], memory.astype(c.compute_dtype)),
            2,
        )
        attn = flash_attention(
            q, k, v, causal=False,
            q_segment_ids=q_seg, kv_segment_ids=kv_seg,
            implementation=c.attention_impl,
        )
        out = self.cross_proj.apply(lp["cross_proj"], self._merge_heads(attn))
        if gate is not None:
            # fused-pipeline encoder stages: the whole cross-attention
            # contribution (and its weight gradients) is scaled to zero
            # by the stage-varying gate — the FLOPs run (that is the
            # SPMD deal) but the math and grads match _enc_layer exactly
            out = out * gate
        return x + out.astype(x.dtype)

    def _mlp(self, lp, x):
        c = self.config
        y = fused_layer_norm_affine(
            x, lp["ln2"]["scale"], lp["ln2"]["bias"],
            (c.hidden_size,), eps=c.layernorm_epsilon,
        ).astype(c.compute_dtype)
        y = self.fc1.apply(lp["fc1"], y)
        y = jax.nn.gelu(y, approximate=True)
        y = self.fc2.apply(lp["fc2"], y)
        return x + y.astype(x.dtype)

    def _enc_layer(self, lp, x):
        return self._mlp(lp, self._self_attention(lp, x, causal=False))

    def _dec_layer(self, lp, x, memory):
        x = self._self_attention(lp, x, causal=True)
        x = self._cross_attention(lp, x, memory)
        return self._mlp(lp, x)

    def _embed(self, params, tokens, pos_name):
        c = self.config
        s = tokens.shape[1]
        x = self.embedding.apply(params["embedding"], tokens)
        x = x + params[pos_name][:s][None, :, :].astype(x.dtype)
        return x.astype(c.compute_dtype)

    def _scan_layers(self, layers, x, body):
        if self.config.remat:
            from apex_tpu.transformer.tensor_parallel.random import (
                checkpoint,
            )

            body = checkpoint(body, policy=self.config.remat_policy)

        def step(h, lp):
            return body(lp, h), None

        out, _ = jax.lax.scan(step, x, layers)
        return out

    def encode(self, params, enc_tokens) -> jnp.ndarray:
        """(b, s_enc) → encoder memory (b, s_enc, h) in compute dtype."""
        c = self.config
        x = self._embed(params, enc_tokens, "enc_pos_embedding")
        x = self._scan_layers(params["enc_layers"], x, self._enc_layer)
        x = fused_layer_norm_affine(
            x.astype(jnp.float32),
            params["enc_final_ln"]["scale"],
            params["enc_final_ln"]["bias"],
            (c.hidden_size,), eps=c.layernorm_epsilon,
        )
        return x.astype(c.compute_dtype)

    def decode(self, params, dec_tokens, memory) -> jnp.ndarray:
        """(b, s_dec), memory → decoder hidden (b, s_dec, h)."""
        c = self.config
        x = self._embed(params, dec_tokens, "dec_pos_embedding")
        x = self._scan_layers(
            params["dec_layers"], x,
            lambda lp, h: self._dec_layer(lp, h, memory),
        )
        x = fused_layer_norm_affine(
            x.astype(jnp.float32),
            params["dec_final_ln"]["scale"],
            params["dec_final_ln"]["bias"],
            (c.hidden_size,), eps=c.layernorm_epsilon,
        )
        return x.astype(c.compute_dtype)

    def logits(self, params, hidden) -> jnp.ndarray:
        w = params["embedding"]["weight"].astype(hidden.dtype)
        return jnp.einsum("bsh,vh->bsv", hidden, w)

    def apply(self, params, enc_tokens, dec_tokens) -> jnp.ndarray:
        """Forward to vocab-parallel logits — call inside shard_map."""
        memory = self.encode(params, enc_tokens)
        return self.logits(params, self.decode(params, dec_tokens, memory))

    def _per_token_ce(self, params, hidden, targets) -> jnp.ndarray:
        """Per-token CE through the tied LM head (fused or two-step, by
        ``config.fused_ce``)."""
        from apex_tpu.transformer.tensor_parallel.cross_entropy import (
            lm_head_cross_entropy,
        )

        return lm_head_cross_entropy(
            hidden, params["embedding"]["weight"], targets,
            axis_name=self.axis_name, fused=self.config.fused_ce,
            chunk=self.config.fused_ce_chunk,
        )

    def loss(self, params, enc_tokens, dec_tokens, targets) -> jnp.ndarray:
        memory = self.encode(params, enc_tokens)
        hidden = self.decode(params, dec_tokens, memory)
        per_token = self._per_token_ce(params, hidden, targets)
        return jax.lax.pmean(jnp.mean(per_token), DATA_PARALLEL_AXIS)

    # ------------------------------------------------------ pipeline path
    def pipeline_params(self, params) -> Dict[str, Any]:
        """Re-pack for the pipeline path: one (enc+dec, ...) layer stack
        whose leading dim shards over "pp" — encoder layers land on the
        stages before the split, decoder layers after it."""
        packed = dict(params)
        packed["layers"] = jax.tree.map(
            lambda e, d: jnp.concatenate([e, d], axis=0),
            packed.pop("enc_layers"), packed.pop("dec_layers"),
        )
        return packed

    def pipeline_param_specs(self) -> Dict[str, Any]:
        from apex_tpu.transformer.pipeline_parallel import (
            pipeline_stage_specs,
        )

        specs = dict(self.param_specs())
        specs["layers"] = pipeline_stage_specs(specs.pop("enc_layers"))
        del specs["dec_layers"]
        return specs

    def pipeline_split_stage(self) -> int:
        """Encoder/decoder boundary for the current pp size: stages split
        proportionally to depth (reference: pipeline_model_parallel_
        split_rank, apex/transformer/parallel_state.py)."""
        from apex_tpu.transformer import parallel_state

        c = self.config
        pp = parallel_state.get_pipeline_model_parallel_world_size()
        split = parallel_state.get_pipeline_model_parallel_split_rank()
        if split is None:
            total = c.num_encoder_layers + c.num_decoder_layers
            split = max(1, round(pp * c.num_encoder_layers / total))
        n_enc, n_dec = split, pp - split
        if n_dec < 1:
            raise ValueError(
                f"split rank {split} leaves no decoder stage (pp={pp})"
            )
        if c.num_encoder_layers % n_enc or c.num_decoder_layers % n_dec:
            raise ValueError(
                f"encoder/decoder layers ({c.num_encoder_layers}/"
                f"{c.num_decoder_layers}) must divide the encoder/decoder "
                f"stage counts ({n_enc}/{n_dec})"
            )
        per_stage = c.num_encoder_layers // n_enc
        if c.num_decoder_layers // n_dec != per_stage:
            raise ValueError(
                "pipeline stages must hold equally many layers on both "
                f"sides of the split (enc {per_stage} vs dec "
                f"{c.num_decoder_layers // n_dec} per stage)"
            )
        return split

    def _fused_pipeline_fns(self, split: int, s_enc: int, s_dec: int):
        """Entry/stage/exit functions for the one-body-per-tick
        :func:`~apex_tpu.transformer.pipeline_parallel.
        pipeline_encdec_fused` schedule.

        Both streams are padded to ``S = max(s_enc, s_dec)`` so one
        activation shape serves encoder and decoder stages; pad lanes
        are isolated by attention segment ids (valid=1, pad=0 — pad
        keys never reach valid queries; pad-query rows attend only
        other pad positions, so they carry garbage that is sliced off
        before the loss, never mixed in).  Stage behaviour is pure
        data selection on the device-varying stage index:

        - causality: a ``(S, S)`` additive bias that is the causal mask
          on decoder stages and exactly zero on encoder stages
          (``bias_requires_grad=False`` keeps the flash backward free
          of dbias blocks);
        - cross-attention: computed on every stage (the single-program
          SPMD cost) but scaled by ``gate = stage >= split``, so
          encoder math and gradients match ``_enc_layer`` exactly;
        - the last encoder stage emits the encoder-final-layernormed
          memory, as in the two-stream schedule.
        """
        c = self.config
        S = max(s_enc, s_dec)
        need_segs = (s_enc != S) or (s_dec != S)
        pos = jnp.arange(S)
        enc_valid = (pos < s_enc).astype(jnp.int32)
        dec_valid = (pos < s_dec).astype(jnp.int32)
        qi = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        causal_neg = jnp.where(ki <= qi, 0.0, -1e30).astype(jnp.float32)

        def pad(x):
            if x.shape[1] == S:
                return x
            return jnp.pad(x, ((0, 0), (0, S - x.shape[1]), (0, 0)))

        def enc_entry(prm, m):
            return pad(self._embed(prm, m["enc_tokens"], "enc_pos_embedding"))

        def dec_entry(prm, m):
            return pad(self._embed(prm, m["dec_tokens"], "dec_pos_embedding"))

        def stage_fn(prm, x, mem, stage):
            is_dec = stage >= split
            bias = causal_neg * is_dec.astype(jnp.float32)
            gate = is_dec.astype(c.compute_dtype)
            if need_segs:
                b = x.shape[0]
                self_valid = jnp.where(is_dec, dec_valid, enc_valid)
                self_seg = jnp.broadcast_to(self_valid[None], (b, S))
                mem_seg = jnp.broadcast_to(enc_valid[None], (b, S))
            else:
                self_seg = mem_seg = None

            def body(h, lp):
                h = self._self_attention(
                    lp, h, causal=False, bias=bias,
                    q_seg=self_seg, kv_seg=self_seg,
                )
                h = self._cross_attention(
                    lp, h, mem, gate=gate,
                    q_seg=self_seg, kv_seg=mem_seg,
                )
                return self._mlp(lp, h), None

            out, _ = jax.lax.scan(body, x, prm["layers"])
            normed = fused_layer_norm_affine(
                out.astype(jnp.float32),
                prm["enc_final_ln"]["scale"],
                prm["enc_final_ln"]["bias"],
                (c.hidden_size,), eps=c.layernorm_epsilon,
            ).astype(out.dtype)
            return jnp.where(stage == split - 1, normed, out)

        def last_fn(prm, y, m):
            x = fused_layer_norm_affine(
                y[:, :s_dec].astype(jnp.float32),
                prm["dec_final_ln"]["scale"],
                prm["dec_final_ln"]["bias"],
                (c.hidden_size,), eps=c.layernorm_epsilon,
            ).astype(c.compute_dtype)
            per_token = self._per_token_ce(prm, x, m["targets"])
            return jnp.mean(per_token)

        return enc_entry, dec_entry, stage_fn, last_fn

    def pipeline_loss(
        self,
        params: Dict[str, Any],
        enc_tokens: jnp.ndarray,
        dec_tokens: jnp.ndarray,
        targets: jnp.ndarray,
        num_microbatches: int,
    ) -> jnp.ndarray:
        """Mean CE through the compiled encoder-decoder pipeline — call
        inside shard_map with params from :meth:`pipeline_params` placed
        by :meth:`pipeline_param_specs` (``params["layers"]`` is then the
        local stage's layer stack).  ``config.fused_pipeline`` routes
        through the one-body-per-tick fused schedule (default)."""
        from apex_tpu.transformer.pipeline_parallel import (
            pipeline_encdec,
            pipeline_encdec_fused,
        )

        c = self.config
        split = self.pipeline_split_stage()
        b = enc_tokens.shape[0]
        if b % num_microbatches:
            raise ValueError(
                f"local batch ({b}) must be divisible by "
                f"num_microbatches ({num_microbatches})"
            )
        mb = b // num_microbatches
        mbs = {
            "enc_tokens": enc_tokens.reshape(num_microbatches, mb, -1),
            "dec_tokens": dec_tokens.reshape(num_microbatches, mb, -1),
            "targets": targets.reshape(num_microbatches, mb, -1),
        }

        if c.fused_pipeline:
            f_enc, f_dec, f_stage, f_last = self._fused_pipeline_fns(
                split, enc_tokens.shape[1], dec_tokens.shape[1]
            )
            per_micro = pipeline_encdec_fused(
                lambda m: f_enc(params, m),
                lambda m: f_dec(params, m),
                lambda x, mem, stage: f_stage(params, x, mem, stage),
                lambda y, m: f_last(params, y, m),
                mbs, split, remat=c.remat,
            )
            return jax.lax.pmean(jnp.mean(per_micro), DATA_PARALLEL_AXIS)

        def enc_entry(m):
            return self._embed(params, m["enc_tokens"], "enc_pos_embedding")

        def dec_entry(m):
            return self._embed(params, m["dec_tokens"], "dec_pos_embedding")

        def enc_stage(x):
            def body(h, lp):
                return self._enc_layer(lp, h), None

            out, _ = jax.lax.scan(body, x, params["layers"])
            # the last encoder stage emits the finished memory: apply the
            # encoder final layernorm here so the value captured at the
            # split matches the sequential :meth:`encode` exactly
            normed = fused_layer_norm_affine(
                out.astype(jnp.float32),
                params["enc_final_ln"]["scale"],
                params["enc_final_ln"]["bias"],
                (c.hidden_size,), eps=c.layernorm_epsilon,
            ).astype(out.dtype)
            is_last_enc = jax.lax.axis_index(PIPELINE_PARALLEL_AXIS) == split - 1
            return jnp.where(is_last_enc, normed, out)

        def dec_stage(x, memory):
            def body(h, lp):
                return self._dec_layer(lp, h, memory), None

            out, _ = jax.lax.scan(body, x, params["layers"])
            return out

        def last_fn(x, m):
            x = fused_layer_norm_affine(
                x.astype(jnp.float32),
                params["dec_final_ln"]["scale"],
                params["dec_final_ln"]["bias"],
                (c.hidden_size,), eps=c.layernorm_epsilon,
            ).astype(c.compute_dtype)
            per_token = self._per_token_ce(params, x, m["targets"])
            return jnp.mean(per_token)

        per_micro = pipeline_encdec(
            enc_entry, enc_stage, dec_entry, dec_stage, last_fn, mbs,
            split, remat=c.remat,
        )
        return jax.lax.pmean(jnp.mean(per_micro), DATA_PARALLEL_AXIS)

    def pipeline_grads(
        self,
        params: Dict[str, Any],
        enc_tokens: jnp.ndarray,
        dec_tokens: jnp.ndarray,
        targets: jnp.ndarray,
        num_microbatches: int,
    ) -> tuple:
        """Fwd+bwd through the enc-dec schedule dispatched by
        ``get_forward_backward_func(model_type=encoder_and_decoder)``
        (reference: schedules/__init__.py:1-39 + common.py ModelType
        routing) — returns ``(mean loss, grads)``; grads already carry
        the shared-param sync and the dp pmean, so step the optimizer
        with them directly.  Falls back to the model's proportional
        split when no ``pipeline_model_parallel_split_rank_`` was
        installed at ``initialize_model_parallel`` time."""
        c = self.config
        split = self.pipeline_split_stage()
        b = enc_tokens.shape[0]
        if b % num_microbatches:
            raise ValueError(
                f"local batch ({b}) must be divisible by "
                f"num_microbatches ({num_microbatches})"
            )
        mb = b // num_microbatches
        mbs = {
            "enc_tokens": enc_tokens.reshape(num_microbatches, mb, -1),
            "dec_tokens": dec_tokens.reshape(num_microbatches, mb, -1),
            "targets": targets.reshape(num_microbatches, mb, -1),
        }

        if c.fused_pipeline:
            enc_entry, dec_entry, f_stage, last_fn = self._fused_pipeline_fns(
                split, enc_tokens.shape[1], dec_tokens.shape[1]
            )
            return self._run_encdec_fwd_bwd(
                enc_entry, None, dec_entry, None, last_fn,
                params, mbs, split, fused_stage_fn=f_stage,
            )

        def enc_entry(prm, m):
            return self._embed(prm, m["enc_tokens"], "enc_pos_embedding")

        def dec_entry(prm, m):
            return self._embed(prm, m["dec_tokens"], "dec_pos_embedding")

        def enc_stage(prm, x):
            def body(h, lp):
                return self._enc_layer(lp, h), None

            out, _ = jax.lax.scan(body, x, prm["layers"])
            normed = fused_layer_norm_affine(
                out.astype(jnp.float32),
                prm["enc_final_ln"]["scale"],
                prm["enc_final_ln"]["bias"],
                (c.hidden_size,), eps=c.layernorm_epsilon,
            ).astype(out.dtype)
            is_last_enc = (
                jax.lax.axis_index(PIPELINE_PARALLEL_AXIS) == split - 1
            )
            return jnp.where(is_last_enc, normed, out)

        def dec_stage(prm, x, memory):
            def body(h, lp):
                return self._dec_layer(lp, h, memory), None

            out, _ = jax.lax.scan(body, x, prm["layers"])
            return out

        def last_fn(prm, x, m):
            x = fused_layer_norm_affine(
                x.astype(jnp.float32),
                prm["dec_final_ln"]["scale"],
                prm["dec_final_ln"]["bias"],
                (c.hidden_size,), eps=c.layernorm_epsilon,
            ).astype(c.compute_dtype)
            per_token = self._per_token_ce(prm, x, m["targets"])
            return jnp.mean(per_token)

        return self._run_encdec_fwd_bwd(
            enc_entry, enc_stage, dec_entry, dec_stage, last_fn,
            params, mbs, split,
        )

    def _run_encdec_fwd_bwd(self, enc_entry, enc_stage, dec_entry,
                            dec_stage, last_fn, params, mbs, split,
                            fused_stage_fn=None):
        """Dispatch the enc-dec fwd+bwd schedule and normalise the grads
        to the optimizer-ready convention (shared tail of
        :meth:`pipeline_grads` for the fused and two-stream paths)."""
        import functools

        from apex_tpu.transformer import parallel_state
        from apex_tpu.transformer.enums import ModelType
        from apex_tpu.transformer.pipeline_parallel import (
            get_forward_backward_func,
            sync_replicated_grads,
        )
        from apex_tpu.transformer.pipeline_parallel.schedules import (
            _fwd_bwd_encdec,
        )

        c = self.config
        pp = jax.lax.axis_size(PIPELINE_PARALLEL_AXIS)
        if parallel_state.get_pipeline_model_parallel_split_rank() is not None:
            fwd_bwd = get_forward_backward_func(
                pipeline_model_parallel_size=pp,
                model_type=ModelType.encoder_and_decoder,
            )
        else:
            fwd_bwd = functools.partial(_fwd_bwd_encdec, split_stage=split)
        kw = ({"fused_stage_fn": fused_stage_fn}
              if fused_stage_fn is not None else {})
        losses, grads = fwd_bwd(
            enc_entry, enc_stage, dec_entry, dec_stage, last_fn,
            params, mbs, remat=c.remat, **kw,
        )
        grads = sync_replicated_grads(grads, self.pipeline_param_specs())
        loss = jax.lax.pmean(jnp.mean(losses), DATA_PARALLEL_AXIS)
        # the schedule's grads are shard-local contributions (the 1F1B
        # family's shared dp convention); pmean makes them the gradient
        # of the dp-mean loss — the same optimizer-ready convention as
        # GPTModel.pipeline_1f1b_grads
        grads = jax.tree.map(
            lambda g: jax.lax.pmean(g, DATA_PARALLEL_AXIS), grads
        )
        return loss, grads
