"""Megatron-style GPT — the flagship model of the framework.

Capability parity with the reference's standalone test GPT
(reference: apex/transformer/testing/standalone_gpt.py, 1504 LoC of
torch modules driven by global args), redesigned TPU-first:

- one ``jax.sharding.Mesh`` with ("dp","pp","cp","tp") axes instead of
  process groups; every parallel dimension of the model is expressed as a
  ``PartitionSpec`` over those axes;
- layers are **stacked** (leading ``num_layers`` dim) and iterated with
  ``lax.scan`` so XLA compiles ONE layer body regardless of depth —
  compile time and HBM code size stay flat where the reference re-traces
  every nn.Module;
- activation rematerialisation via ``jax.checkpoint`` per scanned layer
  (the reference's tensor_parallel.random.CheckpointFunction);
- attention is the Pallas flash-attention kernel (supersedes the
  reference's scaled-upper-triangular fused softmax, SURVEY.md §7);
- the LM head is tied to the vocab-parallel embedding and the loss is the
  vocab-parallel cross entropy, identical math to the reference's
  ``parallel_lm_logits`` + ``vocab_parallel_cross_entropy``.

The model object follows the package's factory convention:
``init(key)`` → full logical params, ``param_specs()`` → matching
PartitionSpecs, ``apply(params, tokens, ...)`` → forward written for the
local shard view inside ``shard_map``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.layer_norm import (
    fused_layer_norm_affine,
    fused_rms_norm_affine,
)
from apex_tpu.telemetry import programs as _programs
from apex_tpu.telemetry.spans import phase
from apex_tpu.transformer.parallel_state import (
    DATA_PARALLEL_AXIS,
    TENSOR_PARALLEL_AXIS,
)
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    gather_from_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.random import (
    data_parallel_key,
    model_parallel_key,
)

__all__ = ["GPTConfig", "GPTModel", "GPTDecodeFns",
           "quantize_gpt_weights", "QUANTIZED_WEIGHT_LEAVES",
           "COLUMN_PARALLEL_LEAVES", "ROW_PARALLEL_LEAVES"]


@dataclasses.dataclass
class GPTDecodeFns:
    """The compiled serving step functions :meth:`GPTModel.decode_fns`
    returns.  ``prefill``/``decode`` are params-bound callables matching
    :class:`apex_tpu.serving.serve.ContinuousBatcher`'s contract;
    ``prefill_jit``/``decode_jit`` are the underlying ``jax.jit``
    objects (their ``_cache_size()`` is what the no-recompile tests
    assert on).  ``chunk``/``chunk_jit`` are the chunked-prefill step
    (present only when ``decode_fns(prefill_chunk=C)`` asked for it)
    and ``prefill_chunk`` its chunk size.  ``spec``/``spec_jit`` are
    the speculative verify-and-commit step (present only when
    ``decode_fns(speculate_k=K)`` asked for it) and ``speculate_k``
    its fixed draft budget per step."""

    prefill: Any
    decode: Any
    prefill_jit: Any
    decode_jit: Any
    #: the EOS id the compiled decode step freezes slots at.  Mirrored
    #: as ``decode.eos_id`` so :class:`ContinuousBatcher` (which only
    #: sees the callables) can reject a mismatched truncation id — the
    #: device's freeze rule and the host's truncation rule must agree.
    eos_id: Any = None
    chunk: Any = None
    chunk_jit: Any = None
    prefill_chunk: Any = None
    spec: Any = None
    spec_jit: Any = None
    speculate_k: Any = None
    #: static candidate-tree shape (a ``parents`` tuple, see
    #: ``apex_tpu.serving.speculate``) the verify step was compiled
    #: for; None = classic chain verification.  Mirrored as
    #: ``spec.spec_tree`` so the batcher lays node tokens out for the
    #: same shape the device expects.
    spec_tree: Any = None
    #: the draft source handed to ``decode_fns(draft_model=...)`` (a
    #: ``ModelDraftSource`` — real serving state: its own weight pool
    #: and KV slice).  Mirrored as ``spec.draft_source`` so the
    #: batcher picks it up as the default drafter.
    draft_source: Any = None
    #: the active weight width of the pool every step streams —
    #: "float32"/"bf16" for plain weights, "int8"/"int4" for quantized
    #: pools (``decode_fns(weight_dtype=...)``).  Mirrored as
    #: ``decode.weight_dtype`` so the batcher's telemetry can report
    #: the width without seeing the params.
    weight_dtype: Any = None
    #: bytes of model parameters ONE CHIP streams per decode step (its
    #: own shard of the pool: sharded projections/scales/embedding at
    #: 1/tp, replicated norms in full).  Mirrored as
    #: ``decode.weight_stream_bytes``; with the span durations this is
    #: the serving per-chip weight-stream GB/s headline
    #: (tools/metrics_report.py).
    weight_stream_bytes: Any = None
    #: tensor-parallel degree the steps were compiled for (1 =
    #: dp-replicated serving).  Mirrored as ``decode.tp`` so the
    #: batcher's telemetry can stamp it on decode spans.
    tp: Any = None
    #: where the decode carry lives (replicated on the steps' mesh).
    #: Mirrored as ``decode.carry_sharding`` so the batcher creates its
    #: carry there (``init_carry(sharding=...)``) and the first decode
    #: step compiles for the carry every later step sees.
    carry_sharding: Any = None
    #: the partition specs the steps were built from — the SAME at
    #: every tp (size 1 included).  Place the params and the
    #: ``init_pools`` dict under ``NamedSharding(mesh, spec)`` with
    #: these and the first call compiles for the layout every later
    #: call sees.
    param_specs: Any = None
    pool_specs: Any = None


#: the projection weight leaves :func:`quantize_gpt_weights` converts —
#: the wide matrices decode streams every token.  Embedding (tied LM
#: head), position table, norms and biases stay full precision: they
#: are a rounding error of the stream and the head's logit quality is
#: disproportionately sensitive.
QUANTIZED_WEIGHT_LEAVES = ("qkv", "attn_proj", "fc1", "fc_gate", "fc2")

#: how each quantized leaf shards over "tp": COLUMN leaves slice the
#: OUTPUT features (their scale blocks ride along), ROW leaves slice
#: the contraction dim (blocks along n are untouched) — the exact
#: mirror of the ColumnParallelLinear / RowParallelLinear param specs
#: the full-width path uses.
COLUMN_PARALLEL_LEAVES = ("qkv", "fc1", "fc_gate")
ROW_PARALLEL_LEAVES = ("attn_proj", "fc2")


def _check_quantized_tp(name: str, k: int, n: int, weight_dtype: str,
                        block_size: int, tp: int) -> None:
    """Loud build-time divisibility for a tp-sharded quantized leaf:
    every shard must hold whole scale blocks (column leaves slice the
    output features, row leaves the contraction rows) and — for int4 —
    whole packed halves, or the in-kernel dequant tiling desyncs."""
    if name in ROW_PARALLEL_LEAVES:
        if k % tp:
            raise ValueError(
                f"layers/{name}: contraction dim {k} is not divisible "
                f"by tp={tp}")
        return
    if n % tp:
        raise ValueError(
            f"layers/{name}: output dim {n} is not divisible by "
            f"tp={tp}")
    n_local = n // tp
    if n_local % block_size:
        raise ValueError(
            f"layers/{name}: per-shard output width {n_local} "
            f"(= {n} / tp={tp}) is not a multiple of "
            f"block_size={block_size} — shard boundaries must align "
            f"with scale blocks; pick a smaller block_size")
    if weight_dtype == "int4" and n_local % (2 * block_size):
        raise ValueError(
            f"layers/{name}: the int4 halves layout needs the "
            f"per-shard width {n_local} (= {n} / tp={tp}) to be a "
            f"multiple of 2 * block_size = {2 * block_size}; pick a "
            f"smaller even block_size")


def quantize_gpt_weights(
    params: Dict[str, Any],
    weight_dtype: str,
    block_size: int = 128,
    tp: int = 1,
) -> Dict[str, Any]:
    """Convert a GPT param tree's projection weights to a quantized
    weight pool — ONCE, at checkpoint load.

    Each leaf in :data:`QUANTIZED_WEIGHT_LEAVES` swaps its ``"weight"``
    array ``(L, k, n)`` for ``{"q8": int8, "scales": fp32}``
    (``weight_dtype="int8"``) or ``{"q4": packed int8, "scales": fp32}``
    (``"int4"`` — two nibbles per byte, :func:`pack_int4` halves
    layout), block-quantized along the OUTPUT features with
    ``block_size``-wide fp32 scales — the same
    :func:`~apex_tpu.ops.quantization.quantize_rows` discipline the
    wire collectives use.  The dict KEY is the static width marker:
    the decode forward dispatches on pytree structure
    (:meth:`GPTModel._apply_linear`), so one set of step functions
    serves any width with zero recompiles ACROSS widths only at build
    time — each width is its own (fixed-shape) compilation.

    Quantization is deterministic (pure function of the weight bits),
    so quantizing an ``unshard()``-rebuilt ZeRO-3 checkpoint is
    bit-identical to quantizing the replicated weights directly
    (pinned in tests/test_weight_quant.py), and ONE pool can be built
    host-side and shared read-only by every fleet replica.

    ``tp``: the tensor-parallel degree the pool will SERVE at.  Scale
    values and int8 bytes are tp-independent (shard boundaries align
    with whole scale blocks — validated loudly), but int4 COLUMN leaves
    pack their nibbles per tp shard: a contiguous slice of globally
    packed bytes would pair nibbles from two non-contiguous column
    ranges, so each shard's columns are packed among themselves and the
    GSPMD slice of the packed array is exactly that shard's own halves
    layout.  At tp=1 this IS the historical whole-row layout; the
    dequantized values are bit-identical at every tp.  A pre-built int4
    pool handed to :meth:`GPTModel.decode_fns` at tp>1 must have been
    packed with the SAME tp (the bytes carry no marker — int8 pools
    are tp-agnostic)."""
    from apex_tpu.ops.dequant_matmul import quantize_weight

    if weight_dtype not in ("int8", "int4"):
        raise ValueError(
            f"weight_dtype must be 'int8' or 'int4', got "
            f"{weight_dtype!r}")
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    out = dict(params)
    layers = dict(params["layers"])
    for name in QUANTIZED_WEIGHT_LEAVES:
        if name not in layers:
            continue
        leaf = dict(layers[name])
        w = leaf.pop("weight")
        L, k, n = w.shape
        lname = f"layers/{name}.weight"
        if tp > 1:
            _check_quantized_tp(name, k, n, weight_dtype, block_size,
                                tp)
        # rows are independent: the stacked (L, k, n) quantizes as
        # L*k rows of n, bit-identical to a per-layer loop
        w2 = jnp.reshape(w, (L * k, n))
        if (weight_dtype == "int4" and tp > 1
                and name in COLUMN_PARALLEL_LEAVES):
            shards = [
                quantize_weight(
                    w2[:, r * (n // tp):(r + 1) * (n // tp)],
                    weight_dtype, block_size, leaf=lname)
                for r in range(tp)
            ]
            wq = {key: jnp.concatenate([s[key] for s in shards], axis=1)
                  for key in shards[0]}
        else:
            wq = quantize_weight(w2, weight_dtype, block_size,
                                 leaf=lname)
        qkey = "q8" if "q8" in wq else "q4"
        leaf[qkey] = jnp.reshape(wq[qkey], (L, k, -1))
        leaf["scales"] = jnp.reshape(wq["scales"], (L, k, -1))
        layers[name] = leaf
    out["layers"] = layers
    return out


def _quantized_layer_specs(lspecs: Dict[str, Any],
                           layers: Dict[str, Any],
                           axis_name: str) -> Dict[str, Any]:
    """Partition specs for the quantized-pool leaves, mirroring the
    pytree structure :func:`quantize_gpt_weights` built — the same
    specs at every tp: column leaves shard ``q8``/``q4``/``scales`` on
    the stacked OUTPUT dim (axis 2 of ``(L, k, ·)``) with the bias
    riding along, and row leaves shard on the contraction dim (axis 1)
    with a replicated bias — so each chip streams exactly 1/tp of the
    quantized pool."""
    out = dict(lspecs)
    for name in QUANTIZED_WEIGHT_LEAVES:
        if name not in out or name not in layers:
            continue
        leaf = layers[name]
        if "q8" not in leaf and "q4" not in leaf:
            continue
        col = name in COLUMN_PARALLEL_LEAVES
        spec = {}
        for key in leaf:
            if key == "bias":
                spec[key] = P(None, axis_name) if col else P(None, None)
            elif col:
                spec[key] = P(None, None, axis_name)
            else:
                spec[key] = P(None, axis_name, None)
        out[name] = spec
    return out


def _per_chip_param_bytes(params: Dict[str, Any], specs: Dict[str, Any],
                          mesh) -> int:
    """Bytes of model parameters ONE device holds — and one decode step
    streams — under ``specs``: each leaf's nbytes divided by the
    product of its spec's mesh-axis extents (replicated leaves count in
    full).  The per-chip numerator of the serving weight-stream GB/s
    headline."""
    extents = dict(mesh.shape)

    def denom(spec):
        d = 1
        for entry in tuple(spec):
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            for a in names:
                d *= int(extents.get(a, 1))
        return d

    p_leaves = jax.tree.leaves(params)
    s_leaves = jax.tree.leaves(specs,
                               is_leaf=lambda t: isinstance(t, P))
    if len(p_leaves) != len(s_leaves):
        raise ValueError(
            f"param/spec tree mismatch: {len(p_leaves)} param leaves "
            f"vs {len(s_leaves)} specs")
    return int(sum(x.nbytes // denom(s)
                   for x, s in zip(p_leaves, s_leaves)))


@dataclasses.dataclass
class GPTConfig:
    """Hyperparameters (the subset of the reference's 806-line argparse
    clone that defines the network, reference:
    apex/transformer/testing/arguments.py)."""

    vocab_size: int = 32000
    num_layers: int = 4
    hidden_size: int = 512
    num_attention_heads: int = 8
    max_position_embeddings: int = 1024
    # "learned" = trained absolute-position table (the reference GPT's
    # scheme, standalone_gpt.py); "rope" = rotary embeddings applied to
    # (q, k) in every layer (ops/rope.py — the fork's mentioned-but-
    # absent rope capability, SURVEY.md §2.1).  rope models carry no
    # position table, so max_position_embeddings only bounds nothing —
    # any sequence length runs.
    position_embedding: str = "learned"
    rope_base: float = 10000.0
    # "gelu" (reference GPT) or "swiglu" (gated SiLU MLP); with
    # position_embedding="rope" and normalization="rmsnorm" the same
    # model expresses the modern Llama-style decoder family
    activation: str = "gelu"
    # "layernorm" (scale+bias, reference) or "rmsnorm" (scale only)
    normalization: str = "layernorm"
    # defaults to 4*hidden for BOTH activations.  NOTE for swiglu
    # users: swiglu carries 3 FFN matrices (gate/up/down) vs gelu's 2,
    # so at equal ffn_hidden_size a swiglu model has 1.5x the FFN
    # params.  For parameter-matched comparisons with gelu models set
    # ffn_hidden_size ≈ int(8 * hidden_size / 3), rounded to a multiple
    # of the tp width x 128 lanes (the Llama convention; docs/models.md)
    ffn_hidden_size: Optional[int] = None
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0
    layernorm_epsilon: float = 1e-5
    init_method_std: float = 0.02
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    # an amp.Policy drives the dtypes (and, via policy.master_weights /
    # policy.loss_scale, the train-loop wiring) — the initialize-and-
    # forget UX of the reference's amp.initialize
    # (apex/amp/_initialize.py:145-265): one kwarg switches the model
    # across O0..O5
    policy: Optional[Any] = None
    remat: bool = True
    # batch-dim dot outputs are cheap to recompute and expensive to
    # keep resident, so the dots_with_no_batch_dims policy is the base.
    # A Mosaic call is not a dot: under that policy alone the attention
    # kernel's (out, lse) are thrown away and the backward runs the
    # forward kernel a second time.  The default keeps the two under
    # the names the kernels' forward rules give them (ops/common.py).
    # Measured in the train-345m cell (24L/h1024, 16 x 1024 tokens a
    # step on one v5e; PERF.md section 6, PR 27): 487.3 ms a step
    # against 532.6 with the dots policy alone, for 64 MB a layer kept.
    # A job at its memory limit still has "nothing_saveable"
    # (tensor_parallel/random.py CHECKPOINT_POLICIES)
    remat_policy: Optional[str] = (
        "dots_with_no_batch_dims_and_attention_saveable")
    # LM-head/CE dispatch: None = auto by materialized-logits size
    # (tensor_parallel.cross_entropy.FUSED_CE_AUTO_BYTES) — small logits
    # take the two-step path (faster: 107.4 vs 110.1 ms/step at the v5e
    # flagship, BENCH r4+r5 A/B), large ones the fused online-logsumexp
    # scan that never materializes logits.  True/False forces a path.
    fused_ce: Optional[bool] = None
    fused_ce_chunk: int = 8192
    # None → platform + the measured three-tier dispatch ladder
    # (short sequences run the single-pass fmha-short kernel, the
    # 512 < s <= ~2048 band — the flagship shape — runs the pipelined
    # fmha-mid kernel, longer sequences the streamed flash kernel;
    # docs/attention.md); "short"/"mid"/"pallas"/"xla" force one
    # attention kernel everywhere
    attention_impl: Optional[str] = None
    # shard the sequence dim over the "cp" mesh axis and use ring
    # attention — long-context training (new capability vs the reference,
    # SURVEY.md §2.3); tokens then arrive as the local (b, s/cp) shard
    context_parallel: bool = False
    # Mixture-of-Experts: replace every dense MLP block with an
    # expert-parallel Switch MLP of this many experts (None = dense).
    # Experts shard over "dp"; the Switch aux loss is added to the LM
    # loss with moe_aux_weight.
    num_experts: Optional[int] = None
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    moe_router_z_loss_weight: float = 0.0

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
        if self.context_parallel and self.attention_dropout > 0.0:
            raise ValueError(
                "attention_dropout is not supported with context_parallel "
                "(the explicit-softmax dropout path is not ring-aware)"
            )
        if self.position_embedding not in ("learned", "rope"):
            raise ValueError(
                f"position_embedding must be 'learned' or 'rope', got "
                f"{self.position_embedding!r}"
            )
        if self.position_embedding == "rope" and self.head_dim % 2:
            raise ValueError("rope needs an even head_dim")
        if self.activation not in ("gelu", "swiglu"):
            raise ValueError(
                f"activation must be 'gelu' or 'swiglu', got "
                f"{self.activation!r}"
            )
        if self.normalization not in ("layernorm", "rmsnorm"):
            raise ValueError(
                f"normalization must be 'layernorm' or 'rmsnorm', got "
                f"{self.normalization!r}"
            )
        if self.activation == "swiglu" and self.num_experts is not None:
            raise ValueError("swiglu is the dense-MLP path; MoE experts "
                             "keep their own activation")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def norm_dtype(self) -> Any:
        """LayerNorm parameter dtype: fp32 under a keep-norm-fp32 policy
        (the reference's keep_batchnorm_fp32 / convert_network contract,
        apex/fp16_utils/fp16util.py:60)."""
        if self.policy is not None and self.policy.keep_norm_fp32:
            return jnp.float32
        return self.params_dtype


def _normal(std):
    def init(key, shape, dtype):
        return std * jax.random.normal(key, shape, dtype)

    return init


def _scaled_normal(std, num_layers):
    # Megatron output-layer init: std / sqrt(2*L)
    return _normal(std / (2.0 * num_layers) ** 0.5)


class GPTModel:
    """Decoder-only transformer LM over a tp-sharded mesh."""

    def __init__(self, config: GPTConfig, axis_name: str = TENSOR_PARALLEL_AXIS):
        self.config = config
        self.axis_name = axis_name
        c = config
        init = _normal(c.init_method_std)
        out_init = _scaled_normal(c.init_method_std, c.num_layers)
        self.embedding = VocabParallelEmbedding(
            c.vocab_size,
            c.hidden_size,
            init_method=init,
            params_dtype=c.params_dtype,
            axis_name=axis_name,
        )
        self.qkv = ColumnParallelLinear(
            c.hidden_size,
            3 * c.hidden_size,
            gather_output=False,
            init_method=init,
            params_dtype=c.params_dtype,
            axis_name=axis_name,
        )
        self.attn_proj = RowParallelLinear(
            c.hidden_size,
            c.hidden_size,
            input_is_parallel=True,
            init_method=out_init,
            params_dtype=c.params_dtype,
            axis_name=axis_name,
        )
        self.fc1 = ColumnParallelLinear(
            c.hidden_size,
            c.ffn_hidden_size,
            gather_output=False,
            init_method=init,
            params_dtype=c.params_dtype,
            axis_name=axis_name,
        )
        self.fc_gate = None
        if c.activation == "swiglu":
            # TWO column-parallel projections, not one 2x-wide fused
            # weight: a tp shard of a fused [gate | up] layout would be
            # all-gate on low ranks (the contiguous-slice hazard the
            # fused qkv avoids by per-head grouping); separate weights
            # are correct at any tp and XLA fuses the twin GEMMs on the
            # shared input anyway
            self.fc_gate = ColumnParallelLinear(
                c.hidden_size,
                c.ffn_hidden_size,
                gather_output=False,
                init_method=init,
                params_dtype=c.params_dtype,
                axis_name=axis_name,
            )
        self.fc2 = RowParallelLinear(
            c.ffn_hidden_size,
            c.hidden_size,
            input_is_parallel=True,
            init_method=out_init,
            params_dtype=c.params_dtype,
            axis_name=axis_name,
        )
        self.moe = None
        if c.num_experts is not None:
            from apex_tpu.transformer.moe import MoEMLP

            self.moe = MoEMLP(
                c.hidden_size,
                c.ffn_hidden_size,
                c.num_experts,
                top_k=c.moe_top_k,
                capacity_factor=c.moe_capacity_factor,
                router_z_loss_weight=c.moe_router_z_loss_weight,
                tp_axis=axis_name,
                params_dtype=c.params_dtype,
                init_std=c.init_method_std,
            )

    # ---------------------------------------------------------------- init
    def _init_one_layer(self, key) -> Dict[str, Any]:
        keys = jax.random.split(key, 5)
        c = self.config
        ln = self._norm_init
        layer = {
            "ln1": ln(),
            "qkv": self.qkv.init(keys[0]),
            "attn_proj": self.attn_proj.init(keys[1]),
            "ln2": ln(),
        }
        if self.moe is not None:
            layer["moe"] = self.moe.init(keys[2])
        else:
            layer["fc1"] = self.fc1.init(keys[2])
            layer["fc2"] = self.fc2.init(keys[3])
            if self.fc_gate is not None:
                layer["fc_gate"] = self.fc_gate.init(keys[4])
        return layer

    def _norm_init(self) -> Dict[str, Any]:
        c = self.config
        p = {"scale": jnp.ones((c.hidden_size,), c.norm_dtype)}
        if c.normalization == "layernorm":
            p["bias"] = jnp.zeros((c.hidden_size,), c.norm_dtype)
        return p

    def _norm(self, p: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray:
        """ln1/ln2/final_ln dispatch: fused layer norm (scale+bias) or
        RMSNorm (scale only) per ``config.normalization``; fp32 math
        either way (the norm-in-fp32 contract of the amp policies)."""
        c = self.config
        if c.normalization == "rmsnorm":
            return fused_rms_norm_affine(
                x, p["scale"], (c.hidden_size,), eps=c.layernorm_epsilon
            )
        return fused_layer_norm_affine(
            x, p["scale"], p["bias"], (c.hidden_size,),
            eps=c.layernorm_epsilon,
        )

    def init(self, key) -> Dict[str, Any]:
        c = self.config
        k_emb, k_pos, k_layers = jax.random.split(key, 3)
        layer_keys = jax.random.split(k_layers, c.num_layers)
        # stacked layer params: every leaf gets a leading num_layers dim
        layers = jax.vmap(self._init_one_layer)(layer_keys)
        params = {
            "embedding": self.embedding.init(k_emb),
            "layers": layers,
            "final_ln": self._norm_init(),
        }
        if c.position_embedding == "learned":
            params["pos_embedding"] = _normal(c.init_method_std)(
                k_pos, (c.max_position_embeddings, c.hidden_size),
                c.params_dtype,
            )
        return params

    def param_specs(self) -> Dict[str, Any]:
        rep = {"scale": P()}
        if self.config.normalization == "layernorm":
            rep["bias"] = P()
        layer = {
            "ln1": rep,
            "qkv": self.qkv.param_specs(),
            "attn_proj": self.attn_proj.param_specs(),
            "ln2": rep,
        }
        if self.moe is not None:
            layer["moe"] = self.moe.param_specs()
        else:
            layer["fc1"] = self.fc1.param_specs()
            layer["fc2"] = self.fc2.param_specs()
            if self.fc_gate is not None:
                layer["fc_gate"] = self.fc_gate.param_specs()
        # prepend the stacked-layer dim (replicated) to each layer spec
        stacked = jax.tree.map(
            lambda s: P(None, *s), layer, is_leaf=lambda x: isinstance(x, P)
        )
        specs = {
            "embedding": self.embedding.param_specs(),
            "layers": stacked,
            "final_ln": dict(rep),
        }
        if self.config.position_embedding == "learned":
            specs["pos_embedding"] = P()
        return specs

    # ------------------------------------------------------------- forward
    @staticmethod
    def _apply_linear(mod, p: Dict[str, Any], y: jnp.ndarray):
        """ONE projection dot, dispatched on the param leaf's
        STRUCTURE.  A plain ``{"weight", ...}`` leaf runs the
        tensor-parallel module unchanged (training and full-width
        serving).  A quantized-pool leaf (``{"q8"/"q4", "scales", ...}``
        — :func:`quantize_gpt_weights`) streams the int8/int4 weights
        through :func:`~apex_tpu.ops.dequant_matmul.dequant_matmul`,
        which dequantizes inside the matmul tiles so the wide matrix
        never materializes in HBM.  Structure is static at trace time,
        so the width costs no dynamic flag threading and each width
        compiles to its own fixed-shape program.  The quantized branch
        mirrors the module's tp collectives: a column-parallel leaf's
        local dot IS its output shard (bias shards with it), a
        row-parallel leaf's local dot is a partial sum over its slice
        of the contraction dim — psum exactly like
        ``RowParallelLinear.apply``, then add the replicated bias once
        (the psum is free at tp=1 and is what types the sum
        replicated)."""
        if "weight" in p:
            return mod.apply(p, y)
        from apex_tpu.ops.dequant_matmul import (
            dequant_matmul, weight_pool_dtype,
        )

        out = dequant_matmul(
            y, p["q8"] if "q8" in p else p["q4"], p["scales"],
            weight_dtype=weight_pool_dtype(p))
        if isinstance(mod, RowParallelLinear):
            out = reduce_from_tensor_model_parallel_region(
                out, mod.axis_name)
        if "bias" in p:
            out = out + p["bias"].astype(out.dtype)
        return out

    def _weight_pool_dtype(self, params: Dict[str, Any]) -> str:
        """The active weight width a param tree's STRUCTURE implies:
        ``"int8"``/``"int4"`` when the projection leaves are quantized
        pools, the storage dtype name (``"float32"``/``"bf16"``)
        otherwise — the ground truth the ``weight_dtype=`` declaration
        is validated against."""
        layers = params["layers"]
        for name in QUANTIZED_WEIGHT_LEAVES:
            leaf = layers.get(name)
            if leaf is None:
                continue
            if "q8" in leaf:
                return "int8"
            if "q4" in leaf:
                return "int4"
            d = leaf["weight"].dtype
            return "bf16" if d == jnp.bfloat16 else str(d)
        return "float32"

    def _check_weight_dtype(self, params: Dict[str, Any],
                            weight_dtype: Optional[str]):
        """Declared-width validation for the serving steps: the params
        structure IS the active width; a step invoked with a
        ``weight_dtype=`` claim that disagrees raises at trace time
        instead of silently serving the wrong numerics contract."""
        if weight_dtype is None:
            return
        want = {"fp32": "float32", "bfloat16": "bf16"}.get(
            weight_dtype, weight_dtype)
        have = self._weight_pool_dtype(params)
        if want != have:
            raise ValueError(
                f"weight_dtype={weight_dtype!r} declared but the "
                f"params carry {have} weights — quantize with "
                f"quantize_gpt_weights (or drop the declaration)")

    def _qkv_heads(self, lp: Dict[str, Any], y: jnp.ndarray):
        """(b, s, h) normed activations -> (q, k, v), each
        ``(b, heads_local, s, head_dim)``.  The output dim of the fused
        qkv weight is grouped per head — [h0_q h0_k h0_v h1_q …] — so a
        contiguous tp slice holds whole (q,k,v) triplets and the math
        is identical for every tp size (the reference relies on
        per-rank weight init for the same property,
        apex/transformer/testing/standalone_gpt.py).  Called from
        :meth:`_block` alone, so the cache can never hold a different K
        than training computed."""
        c = self.config
        world = jax.lax.axis_size(self.axis_name)
        heads_local = c.num_attention_heads // world
        b, s, _ = y.shape
        qkv = self._apply_linear(self.qkv, lp["qkv"], y)  # (b, s, 3h/tp)
        qkv = qkv.reshape(b, s, heads_local, 3, c.head_dim)
        return tuple(
            jnp.moveaxis(qkv[:, :, :, i], 2, 1) for i in range(3)
        )

    def _dense_mlp(self, lp: Dict[str, Any], y: jnp.ndarray) -> jnp.ndarray:
        """The dense-MLP math on normed activations: SwiGLU
        (silu(gate(x)) * up(x) — both column-parallel on the same
        input, elementwise gate on the local shard) or fc1+gelu, then
        the row-parallel fc2 (from :meth:`_block` alone, like
        :meth:`_qkv_heads`)."""
        if self.fc_gate is not None:
            y = (jax.nn.silu(self._apply_linear(
                    self.fc_gate, lp["fc_gate"], y))
                 * self._apply_linear(self.fc1, lp["fc1"], y))
        else:
            y = self._apply_linear(self.fc1, lp["fc1"], y)
            y = jax.nn.gelu(y, approximate=True)
        return self._apply_linear(self.fc2, lp["fc2"], y)

    def _block(self, lp: Dict[str, Any], x: jnp.ndarray, attend,
               key=None):
        """THE transformer layer on the local shard, written once:
        norm -> :meth:`_qkv_heads` -> ``attend`` -> ``attn_proj`` ->
        residual -> norm -> MLP (dense or expert-parallel MoE) ->
        residual.  ``x``: (b, s, h) replicated over tp; ``lp``: this
        layer's param shards.  ``attend(q, k, v) -> (attention output
        (b, heads_local, s, d), extra)`` is the ONE thing the entry
        points differ in — how the layer reads and writes its sequence
        state: the dense one of :meth:`_layer` (training, monolithic
        prefill) or the paged one of :meth:`_paged_rows` (every serving
        step).  ``key`` (training only) turns the hidden dropouts on.
        Returns ``(x_out, MoE aux loss — 0.0 for dense layers, extra)``."""
        c = self.config
        b, s, _ = x.shape

        # -- attention block ------------------------------------------
        residual = x
        y = self._norm(lp["ln1"], x).astype(c.compute_dtype)
        q, k, v = self._qkv_heads(lp, y)  # each (b, heads_local, s, d)
        attn, extra = attend(q, k, v)
        attn = jnp.moveaxis(attn, 1, 2).reshape(b, s, -1)
        out = self._apply_linear(
            self.attn_proj, lp["attn_proj"], attn)  # psum inside
        if c.hidden_dropout > 0.0 and key is not None:
            # replicated activations ⇒ mask must agree across tp ranks:
            # fold in only the dp rank (reference keeps this on the
            # default rng state, apex/transformer/tensor_parallel/random.py)
            hkey = data_parallel_key(jax.random.fold_in(key, 1))
            keep = jax.random.bernoulli(hkey, 1.0 - c.hidden_dropout, out.shape)
            out = jnp.where(keep, out / (1.0 - c.hidden_dropout), 0.0)
        x = residual + out.astype(residual.dtype)

        # -- MLP block (dense or expert-parallel MoE) -------------------
        residual = x
        y = self._norm(lp["ln2"], x).astype(c.compute_dtype)
        if self.moe is not None:
            y, aux = self.moe.apply(lp["moe"], y)
        else:
            y = self._dense_mlp(lp, y)
            aux = jnp.float32(0.0)
        if c.hidden_dropout > 0.0 and key is not None:
            hkey = data_parallel_key(jax.random.fold_in(key, 2))
            keep = jax.random.bernoulli(hkey, 1.0 - c.hidden_dropout, y.shape)
            y = jnp.where(keep, y / (1.0 - c.hidden_dropout), 0.0)
        return residual + y.astype(residual.dtype), aux, extra

    def _layer(self, lp: Dict[str, Any], x: jnp.ndarray, key,
               rope=None):
        """One layer over a whole in-hand sequence (s_q == s_k):
        :meth:`_block` under the DENSE ``attend`` — rotate q and k by
        ``rope`` (precomputed (cos, sin) tables from
        :meth:`_rope_tables`; None for learned positions), then causal
        attention through the training ladder.  Returns ``(x_out, aux,
        (k, v))``, the K/V attention-ready (:meth:`prefill_forward`
        writes them into the cache)."""
        c = self.config

        def attend(q, k, v):
            if rope is not None:
                from apex_tpu.ops.rope import apply_rope_tables

                q = apply_rope_tables(q, *rope)
                k = apply_rope_tables(k, *rope)
            if c.attention_dropout > 0.0 and key is not None:
                # Megatron semantics: dropout on the softmax
                # *probabilities* (reference: standalone_gpt.py
                # attention_probs dropout), kept INSIDE the flash kernel
                # via its counter-based hash (the role philox.h plays in
                # the reference's fused MHA).  The seed is drawn after
                # folding in mesh axes, so the attention / hidden dropout
                # streams can never collide across ranks.
                akey = model_parallel_key(
                    data_parallel_key(jax.random.fold_in(key, 0)),
                    self.axis_name)
                seed = jax.random.bits(akey, dtype=jnp.uint32)
                attn = flash_attention(
                    q, k, v, causal=True,
                    dropout_rate=c.attention_dropout, dropout_seed=seed,
                    implementation=c.attention_impl,
                )
            elif c.context_parallel:
                from apex_tpu.ops.ring_attention import ring_attention

                # config attention_impl threads into the per-shard inner
                # attention.  "xla" maps to None: the inline ring walk IS
                # the XLA implementation here, and unlike the lse-merge
                # formulation it keeps the documented (s_local, block_k)
                # score bound (the merge's "xla" mode materializes
                # (s_local, s_local) per ring step — an A/B reference,
                # not a production path)
                attn = ring_attention(
                    q, k, v, causal=True,
                    attention_impl=(
                        None if c.attention_impl == "xla"
                        else c.attention_impl
                    ),
                )
            else:
                attn = flash_attention(
                    q, k, v, causal=True, implementation=c.attention_impl
                )
            return attn, (k, v)

        return self._block(lp, x, attend, key)

    def _embed(self, params: Dict[str, Any], tokens: jnp.ndarray):
        """Token embedding + (learned-table) position add, in compute
        dtype — the one entry shared by the sequential and both pipeline
        paths so the position_embedding mode can't diverge between them.
        rope models add nothing here; their rotation happens on (q, k)
        inside every layer (:meth:`_layer`, :meth:`_paged_rows`)."""
        c = self.config
        x = self.embedding.apply(params["embedding"], tokens)
        if c.position_embedding == "learned":
            s = tokens.shape[1]
            x = x + self._pos_slice(params, s)[None, :, :].astype(x.dtype)
        return x.astype(c.compute_dtype)

    def _chunk_offset(self, s: int):
        """Global start position of the local (b, s) sequence chunk —
        cp_rank * s under context parallelism, 0 otherwise.  The ONE
        definition of the cp chunking contract, shared by the learned
        table (:meth:`_pos_slice`) and rope (:meth:`_rope_tables`) so
        the two position modes can never disagree about where a chunk
        sits."""
        if self.config.context_parallel:
            from apex_tpu.transformer.parallel_state import (
                CONTEXT_PARALLEL_AXIS,
            )

            return jax.lax.axis_index(CONTEXT_PARALLEL_AXIS) * s
        return 0

    def _rope_tables(self, s: int):
        """(cos, sin) rotation tables for the local chunk's GLOBAL
        positions (None for learned positions), computed ONCE per
        forward — the layer scan closes over them (a scan body cannot
        hoist the iota+trig, so computing inside :meth:`_layer` would
        redo it num_layers times and again in the remat backward)."""
        from apex_tpu.ops.rope import rope_cos_sin

        if self.config.position_embedding != "rope":
            return None
        positions = self._chunk_offset(s) + jnp.arange(s, dtype=jnp.int32)
        return rope_cos_sin(positions, self.config.head_dim,
                            self.config.rope_base)

    def _pos_slice(self, params: Dict[str, Any], s: int) -> jnp.ndarray:
        """Local slice of the position table: under context parallelism
        the (b, s) tokens are the cp-rank's sequence chunk, so positions
        start at ``cp_rank * s``."""
        if self.config.context_parallel:
            return jax.lax.dynamic_slice_in_dim(
                params["pos_embedding"], self._chunk_offset(s), s, axis=0
            )
        return params["pos_embedding"][:s]

    def hidden_states(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        rng: Optional[jax.Array] = None,
    ) -> jnp.ndarray:
        """Embed + run all layers + final layernorm. tokens: (b, s) local
        (dp-sharded) batch; returns ((b, s, h) hidden in compute dtype,
        summed MoE aux loss — 0.0 for dense models)."""
        c = self.config
        b, s = tokens.shape
        x = self._embed(params, tokens)

        use_rng = rng is not None
        rope = self._rope_tables(s)

        def body(carry, scanned):
            lp, key = scanned
            out, aux, _kv = self._layer(
                lp, carry, key if use_rng else None, rope=rope)
            return out, aux

        if c.remat:
            from apex_tpu.transformer.tensor_parallel.random import checkpoint

            body = checkpoint(body, policy=c.remat_policy)

        keys = (
            jax.random.split(rng, c.num_layers)
            if use_rng
            # dummy keys keep the scanned-pytree structure static
            else jnp.zeros((c.num_layers, 2), jnp.uint32)
        )
        x, aux = jax.lax.scan(body, x, (params["layers"], keys))

        x = self._norm(params["final_ln"], x.astype(jnp.float32))
        return x.astype(c.compute_dtype), jnp.sum(aux)

    def logits(self, params: Dict[str, Any], hidden: jnp.ndarray) -> jnp.ndarray:
        """Tied-embedding LM head → vocab-parallel logits (b, s, vocab/tp)
        (reference: standalone GPT's parallel_lm_logits)."""
        w = params["embedding"]["weight"].astype(hidden.dtype)  # (vocab/tp, h)
        return jnp.einsum("bsh,vh->bsv", hidden, w)

    def apply(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        rng: Optional[jax.Array] = None,
    ) -> jnp.ndarray:
        """Forward to vocab-parallel logits — call inside shard_map."""
        hidden, _ = self.hidden_states(params, tokens, rng)
        return self.logits(params, hidden)

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

    def loss(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        targets: jnp.ndarray,
        rng: Optional[jax.Array] = None,
    ) -> jnp.ndarray:
        """Mean next-token CE over the local batch; psum-mean over dp so
        every device returns the same scalar."""
        hidden, aux = self.hidden_states(params, tokens, rng)
        per_token = self._per_token_ce(params, hidden, targets)
        loss = jnp.mean(per_token)
        if self.moe is not None:
            loss = loss + self.config.moe_aux_weight * aux
        loss = jax.lax.pmean(loss, DATA_PARALLEL_AXIS)
        if self.config.context_parallel:
            from apex_tpu.transformer.parallel_state import (
                CONTEXT_PARALLEL_AXIS,
            )

            loss = jax.lax.pmean(loss, CONTEXT_PARALLEL_AXIS)
        return loss

    # ------------------------------------------------- serving / decode
    def prefill_forward(
        self, params: Dict[str, Any], tokens: jnp.ndarray
    ):
        """Prompt ingestion: full forward over ``tokens (b, s)`` through
        the TRAINING attention ladder (prefill is a compute-bound
        s_q == s_k problem — exactly what rungs 1–3 are measured for),
        additionally returning the attention-ready per-layer K/V for
        the cache write.  Returns ``(hidden (b, s, h), k, v)`` with
        k/v ``(num_layers, b, heads_local, s, head_dim)`` — K already
        RoPE-rotated where the config says so, so a cached key is
        rotated exactly once and the decode kernel rotates only q.

        Every layer is :meth:`_layer` itself (key=None — the inference
        path), which hands back the K/V it attended over; sharing the
        block is what makes the paged generation bit-comparable to the
        full-recompute reference."""
        c = self.config
        if c.context_parallel:
            raise NotImplementedError(
                "prefill_forward is the serving path — context-parallel "
                "decode is not supported")
        x = self._embed(params, tokens)
        rope = self._rope_tables(tokens.shape[1])

        def body(x, lp):
            out, _aux, kv = self._layer(lp, x, None, rope=rope)
            return out, kv

        x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
        x = self._norm(params["final_ln"], x.astype(jnp.float32))
        return x.astype(c.compute_dtype), ks, vs

    def _paged_rows(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        logical: jnp.ndarray,
        positions: jnp.ndarray,
        writev: jnp.ndarray,
        page_table: jnp.ndarray,
        attend_len: jnp.ndarray,
        pools: Dict[str, jnp.ndarray],
        *,
        ancestor=None,
        logits_row=None,
        quantized: bool = False,
        kv_block: int = 128,
        weight_dtype: Optional[str] = None,
    ):
        """THE paged walk — call inside shard_map.  ``R`` token rows for
        each of ``B`` sequences through every layer against the paged
        KV cache; :meth:`decode_step` (``B = S, R = 1``),
        :meth:`prefill_chunk` (``B = 1, R = C``) and :meth:`verify_step`
        (``B = S, R = k + 1``) are shape adapters over it.

        ``tokens (B, R)`` embed at their LOGICAL positions ``logical``
        (learned table or RoPE rows); their K/V land at PHYSICAL
        ``positions`` of ``page_table (B, pages_per_seq)`` where
        ``writev`` holds, on the null page elsewhere — each ``(B, R)``,
        or ``(B,)`` at one row a sequence.  The two positions differ
        only under a candidate tree, where siblings share a logical one.
        Each layer is :meth:`_block` under the PAGED ``attend``: rotate
        K at the rows' positions, write the rows into the layer's pool
        slice FIRST (a row attends to itself and to the rows written
        with it), then
        :func:`~apex_tpu.ops.attention_decode.fmha_decode` over the
        first ``attend_len (B,)`` cache positions, the q-side rotation
        fused into the kernel — per-row causal with row ``i`` at
        ``attend_len - R + i``, or under a tree's static ``ancestor``
        matrix.  Shapes are fixed by ``(B, R)`` and the cache config
        alone: no admission, retirement, chunk offset or acceptance
        pattern recompiles a step built on this.

        Returns ``(logits, new_pools, kv)``: vocab-parallel logits
        ``(B, R, vocab/tp)`` — of row ``logits_row`` (a scalar index)
        alone, ``(B, 1, vocab/tp)``, when it is given — and, under a
        tree (its caller moves the accepted path's rows), the per-layer
        attention-ready K/V rows ``(L, B, h_local, R, d)``; None
        otherwise."""
        from apex_tpu.ops.attention_decode import fmha_decode
        from apex_tpu.serving.kv_cache import write_targets, write_tokens

        c = self.config
        self._check_weight_dtype(params, weight_dtype)
        B, R = tokens.shape
        page_size = pools["k"].shape[3]

        def per_row(table, pos):
            # table rows at ``pos``, (B, R, width): where the caller
            # gave one position a sequence, the row axis goes in
            rows = jnp.take(table, pos, axis=0)
            return jax.lax.expand_dims(rows, range(rows.ndim - 1, 2))

        x = self.embedding.apply(params["embedding"], tokens)
        if c.position_embedding == "learned":
            pos = jnp.clip(logical, 0, c.max_position_embeddings - 1)
            x = x + per_row(params["pos_embedding"], pos).astype(x.dtype)
        x = x.astype(c.compute_dtype)

        rope_cs = None
        if c.position_embedding == "rope":
            from apex_tpu.ops.rope import apply_rope_tables, rope_table

            # (B, R, d/2): the rows' rotations, gathered from the cached
            # full table (ops/rope.py) instead of re-running the trig
            # ladder on dynamic positions every step — the table covers
            # the cache's whole logical extent and its rows are
            # bit-identical to direct computation (pinned in
            # tests/test_rope.py), so prefill, decode and verify
            # rotations cannot drift.  Closed over by the layer scan
            # (same hoisting argument as _rope_tables).
            max_len = page_table.shape[1] * page_size
            cos_t, sin_t = rope_table(max_len, c.head_dim,
                                      base=c.rope_base)
            pos = jnp.clip(logical, 0, max_len - 1)
            rope_cs = (per_row(cos_t, pos), per_row(sin_t, pos))

        wp, wo = write_targets(page_table, positions, writev, page_size)
        wp, wo = wp.reshape(-1), wo.reshape(-1)
        decode_impl = "xla" if c.attention_impl == "xla" else None

        def token_rows(t):
            # (B, hl, R, d) -> (B*R, hl, d), row-major to match wp/wo
            return jnp.moveaxis(t, 1, 2).reshape(B * R, -1, t.shape[-1])

        def body(x, scanned):
            lp, pool_l = scanned

            def attend(q, k, v):
                if rope_cs is not None:
                    k = apply_rope_tables(
                        k, rope_cs[0][:, None], rope_cs[1][:, None])
                new_pool = write_tokens(
                    pool_l, token_rows(k), token_rows(v), wp, wo,
                    quantized=quantized, kv_block=kv_block)
                attn = fmha_decode(
                    q, new_pool["k"], new_pool["v"], page_table,
                    attend_len, causal=True,
                    k_scales=new_pool.get("k_scales"),
                    v_scales=new_pool.get("v_scales"), kv_block=kv_block,
                    rope=rope_cs, implementation=decode_impl,
                    ancestor=ancestor)
                return attn, (new_pool,
                              (k, v) if ancestor is not None else None)

            x, _aux, extra = self._block(lp, x, attend)
            return x, extra

        x, (new_pools, kv) = jax.lax.scan(
            body, x, (params["layers"], pools))
        x = self._norm(params["final_ln"], x.astype(jnp.float32))
        if logits_row is not None:
            x = jnp.take(x, logits_row, axis=1)[:, None]
        return self.logits(params, x.astype(c.compute_dtype)), new_pools, kv

    def prefill_chunk(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        start: jnp.ndarray,
        prompt_len: jnp.ndarray,
        write_from: jnp.ndarray,
        page_row: jnp.ndarray,
        pools: Dict[str, jnp.ndarray],
        *,
        quantized: bool = False,
        kv_block: int = 128,
        weight_dtype: Optional[str] = None,
    ):
        """ONE fixed-size prompt-ingestion chunk for a single serving
        slot — the Sarathi-style alternative to :meth:`prefill_forward`
        that lets the scheduler interleave prompt work with decode
        steps: :meth:`_paged_rows` at ``B = 1, R = C``.  ``tokens (1,
        C)`` are prompt ids at global positions ``start .. start + C``
        (rows at or past ``prompt_len`` are padding); each layer writes
        the chunk's K/V into the slot's pages (positions below
        ``write_from`` — a prefix-cache hit's already-shared region —
        are masked to the null page, never recomputed onto shared
        pages) and attends over the cache INCLUDING its own
        just-written pages, per-row causal at position ``start + i``.
        Chunk boundaries are absolute and attention reads K/V from the
        POOLS, so a hit admission that skips fully-matched chunks gives
        BIT-identical logits to a cold one (docs/serving.md).

        Returns ``(logits (vocab/tp,), new_pools)`` — the logits of the
        LAST VALID prompt row (position ``prompt_len - 1``, clipped into
        this chunk); the caller samples the first generated token from
        the chunk that contains it and ignores the rest."""
        if self.moe is not None:
            self.moe.decode()    # raises: expert-parallel decode note
        C = tokens.shape[-1]
        start = jnp.asarray(start, jnp.int32)
        prompt_len = jnp.asarray(prompt_len, jnp.int32)
        write_from = jnp.asarray(write_from, jnp.int32)
        positions = (start + jnp.arange(C, dtype=jnp.int32))[None]
        writev = (positions < prompt_len) & (positions >= write_from)
        # the chunk attends over start + C cache positions: padding
        # rows past prompt_len see (and produce) garbage, but a valid
        # row's causal mask stops at its own position, which its own
        # just-written page covers
        attend = jnp.reshape(start + C, (1,)).astype(jnp.int32)
        logits, new_pools, _ = self._paged_rows(
            params, tokens.reshape(1, C), positions, positions, writev,
            page_row[None], attend, pools,
            logits_row=jnp.clip(prompt_len - 1 - start, 0, C - 1),
            quantized=quantized, kv_block=kv_block,
            weight_dtype=weight_dtype)
        return logits[0, 0], new_pools

    def decode_step(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        positions: jnp.ndarray,
        active: jnp.ndarray,
        page_table: jnp.ndarray,
        pools: Dict[str, jnp.ndarray],
        *,
        quantized: bool = False,
        kv_block: int = 128,
        weight_dtype: Optional[str] = None,
    ):
        """ONE fused decode step for a fixed batch of serving slots —
        call inside shard_map.  ``tokens (S,)`` are the current tokens
        (each sitting at 0-based ``positions[s]``), ``active (S,)``
        masks live slots (idle slots compute garbage and write to the
        null page).  Every layer writes its new K/V into its pool slice
        (write-before-attend: the token attends to itself) and attends
        over the paged cache: :meth:`_paged_rows` at ``B = S, R = 1``.
        Returns ``(logits (S, vocab/tp), new_pools)`` — the
        shapes never change, so the serving driver's admissions and
        retirements cannot recompile this."""
        if self.moe is not None:
            self.moe.decode()    # raises: expert-parallel decode note
        positions = positions.astype(jnp.int32)
        attend = jnp.where(active, positions + 1, 0).astype(jnp.int32)
        logits, new_pools, _ = self._paged_rows(
            params, tokens[:, None], positions, positions, active,
            page_table, attend, pools, quantized=quantized,
            kv_block=kv_block, weight_dtype=weight_dtype)
        return logits[:, 0], new_pools

    def verify_step(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        lengths: jnp.ndarray,
        active: jnp.ndarray,
        valid: jnp.ndarray,
        page_table: jnp.ndarray,
        pools: Dict[str, jnp.ndarray],
        *,
        quantized: bool = False,
        kv_block: int = 128,
        weight_dtype: Optional[str] = None,
        tree: Optional[tuple] = None,
    ):
        """ONE speculative verify step: :meth:`decode_step` widened to
        ``R = k + 1`` token rows per slot, ONE weight stream for all of
        them (:meth:`_paged_rows` at ``B = S, R = k + 1``).  ``tokens
        (S, R)`` is each slot's current token followed by its k draft
        tokens, sitting at absolute positions ``lengths[s] ..
        lengths[s] + R - 1``; ``valid (S, R)`` masks the real rows (row
        0 plus the slot's actual draft length — shapes stay fixed at R
        for every acceptance pattern, padding rows write to the null
        page).  Row i sees the committed cache plus draft rows 0..i,
        exactly the autoregressive prefix.  Returns ``(logits (S, R,
        vocab/tp), new_pools)``: row j's logits predict the token AFTER
        j committed drafts, so the caller can accept a draft prefix and
        take its correction/bonus token from the same pass.  Rejection
        needs no cleanup: the caller advances ``lengths`` by the
        accepted count, the kernel never attends past a slot's length,
        and the next step's write range covers the stale rows.

        ``tree`` (a static ``parents`` tuple of length R,
        ``apex_tpu.serving.speculate``) makes the R rows a candidate
        TREE: row r embeds at its LOGICAL position ``lengths +
        depth(r)`` while its K/V lands at the collision-free PHYSICAL
        slot ``lengths + r``, and each row sees the committed cache
        plus exactly its root-to-node path.  Returns ``(logits,
        new_pools, (ks, vs))`` — the per-layer post-RoPE K/V rows ``(L,
        S, h_local, R, d)``, so the caller can rewrite the ACCEPTED
        path's rows to their depth positions (the pass-2 commit) from
        the original full-precision values (re-quantizing a dequantized
        page would not be bit-stable)."""
        if self.moe is not None:
            self.moe.decode()    # raises: expert-parallel decode note
        R = tokens.shape[1]
        lengths = lengths.astype(jnp.int32)
        positions = lengths[:, None] + jnp.arange(R, dtype=jnp.int32)[None]
        # rows past the slot's logical page extent go to the null page:
        # a clamped gather would wrap them into the LAST real page, over
        # committed data (the driver also caps drafts under the budget)
        max_len = page_table.shape[1] * pools["k"].shape[3]
        writev = valid & active[:, None] & (positions < max_len)

        ancestor = None
        logical = positions
        if tree is not None:
            from apex_tpu.serving.speculate import (
                tree_ancestors, tree_depths,
            )

            tree = tuple(int(p) for p in tree)
            if len(tree) != R:
                raise ValueError(
                    f"tree has {len(tree)} rows but tokens carry {R} — "
                    "the parents tuple must cover every verify row")
            ancestor = tree_ancestors(tree)
            depths = jnp.asarray(tree_depths(tree), jnp.int32)
            # siblings share a LOGICAL position (the token position the
            # row claims) while their K/V lands at distinct PHYSICAL
            # slots — depth drives rotation/embedding, row drives the
            # write target
            logical = lengths[:, None] + depths[None]

        # the kernel's per-row causal mask sits at lengths - R + i
        # relative to attend = lengths + R, i.e. row i attends through
        # position lengths + i — write-before-attend covers it (the
        # ancestor mask replaces the in-window triangle with the
        # tree's visibility, over the same window)
        attend = jnp.where(active, lengths + R, 0).astype(jnp.int32)
        logits, new_pools, kv = self._paged_rows(
            params, tokens, logical, positions, writev, page_table,
            attend, pools, ancestor=ancestor, quantized=quantized,
            kv_block=kv_block, weight_dtype=weight_dtype)
        if tree is not None:
            return logits, new_pools, kv
        return logits, new_pools

    def decode_fns(
        self,
        params: Dict[str, Any],
        mesh,
        cache_config,
        *,
        max_prompt_len: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_id: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        speculate_k: Optional[int] = None,
        spec_tree: Optional[tuple] = None,
        draft_model: Optional[Any] = None,
        weight_dtype: Optional[str] = None,
        weight_block: int = 128,
        tp: Optional[int] = None,
    ):
        """Build the jitted serving step functions the
        continuous-batching driver
        (:class:`apex_tpu.serving.serve.ContinuousBatcher`) runs:
        ``(prefill, decode)``, plus a chunked-prefill step when
        ``prefill_chunk`` (a chunk size in tokens) is given — the
        :meth:`prefill_chunk` path the stall-free scheduler drives —
        plus a speculative verify-and-commit step when ``speculate_k``
        (the per-step draft budget) is given: :meth:`verify_step` at
        ``s_q = k + 1`` followed by the fused Gumbel-coupled
        acceptance rule (:func:`apex_tpu.serving.sampling.spec_accept`)
        and an in-jit multi-token commit (lengths/steps_left/done all
        advance by the accepted count).  ``draft_model`` takes a
        :class:`apex_tpu.serving.speculate.ModelDraftSource` (a small
        shared-tokenizer draft GPT with its own paged KV slice and
        quantized weight pool); it is validated against ``speculate_k``
        / ``spec_tree`` and mirrored onto the returned struct as
        ``draft_source`` so the batcher picks it up without extra
        wiring — self-speculation (host n-gram drafting,
        :mod:`apex_tpu.serving.speculate`) stays the default source.

        ``spec_tree`` (a static ``parents`` tuple — see
        :func:`apex_tpu.serving.speculate.offramp_tree`) upgrades the
        chain verify to TREE verification: ``R = len(spec_tree)``
        candidate rows attend under the tree's static ancestor matrix
        in the same single weight stream, acceptance walks the tree
        root-to-leaf with the SAME per-position key fold
        (:func:`apex_tpu.serving.sampling.spec_accept_tree`), and the
        accepted path's K/V rows are rewritten in-jit from their
        collision-free physical slots to the committed depth positions
        (pass-2), so the cache the next step attends over is exactly
        what plain decode would have written.  Shapes stay fixed per
        (width, tp, k, tree) — ONE compile covers every acceptance
        pattern.

        All close over nothing dynamic: params ride as an argument
        through ONE jit each, every other shape comes from
        ``cache_config``/``max_prompt_len``/``prefill_chunk``, so each
        compiles once for the server's lifetime.  Returns a
        :class:`GPTDecodeFns` carrying the bound callables plus the raw
        jitted functions (``prefill_jit``/``decode_jit``/``chunk_jit``)
        — the seam the compile-counting tests spy on.

        Sampling keys are PER SLOT: the decode carry holds a
        ``sample_keys`` row per slot (set at admission — from
        ``Request.seed`` when given) and every draw folds in the
        slot's current context length, so a seeded request's sampled
        stream is reproducible regardless of admission order or slot
        assignment (tests/test_serving.py pins it).

        ``weight_dtype`` sets the width of the weight pool every step
        streams: ``"int8"``/``"int4"`` convert the projection weights
        ONCE here via :func:`quantize_gpt_weights` (block size
        ``weight_block``) and the steps dequantize inside the matmul
        tiles; ``"bf16"`` casts the same leaves; ``None`` serves the
        params as given — INCLUDING an already-quantized pool, which is
        how fleet replicas share one read-only pool (quantize once,
        call ``decode_fns`` per replica with the shared tree).  The
        active width and the per-step weight-stream bytes are stamped
        on the returned struct and on ``decode`` for the batcher's
        telemetry.

        Tensor-parallel decode: when the mesh carries a "tp" extent
        > 1 the whole stack shards over it — KV pools head-shard on
        pool axis 2 (each shard owns its head slice of every layer's
        pool; page tables and the host allocator stay replicated, so
        ONE free list drives every shard and prefix cache / CoW /
        refcount GC work verbatim), quantized weight pools shard
        column/row-wise through ``dequant_matmul`` (each chip streams
        1/tp of the pool, scales with their blocks), and the
        vocab-parallel logits all-gather ONLY at the sampling seam so
        the fused sampler, Gumbel-coupled acceptance and per-slot key
        schedule are untouched and the output is token-identical to
        the tp=1 replicated reference.  ``tp=`` is an optional
        cross-check against the mesh (the mesh is the source of
        truth); one warmup compile per (width, tp) pair, zero
        recompiles after.  Pipeline/context-parallel decode stays
        rejected loudly."""
        from apex_tpu.serving.kv_cache import (
            init_pools, write_targets, write_tokens,
        )
        from apex_tpu.serving.sampling import (
            advance_slots, sample, spec_accept,
        )
        from apex_tpu.transformer import parallel_state

        c = self.config
        if self.moe is not None:
            self.moe.decode()    # raises: expert-parallel decode note
        if draft_model is not None:
            if speculate_k is None:
                raise ValueError(
                    "draft_model given without speculate_k — the draft "
                    "model drafts k tokens per verify window; pass "
                    "speculate_k=K")
            if not callable(getattr(draft_model, "draft", None)):
                raise TypeError(
                    "draft_model must be a DraftSource (a .draft "
                    "method) — build one with "
                    "apex_tpu.serving.speculate.ModelDraftSource")
            dk = getattr(draft_model, "k", None)
            if dk is not None and int(dk) != int(speculate_k):
                raise ValueError(
                    f"draft_model drafts k={dk} but speculate_k="
                    f"{speculate_k} — the draft budget and the verify "
                    "row count must agree")
            dtree = getattr(draft_model, "tree", None)
            if dtree is not None and spec_tree is not None and \
                    tuple(int(p) for p in dtree) != \
                    tuple(int(p) for p in spec_tree):
                raise ValueError(
                    "draft_model was built for a different candidate "
                    f"tree ({tuple(dtree)}) than spec_tree="
                    f"{tuple(spec_tree)} — the drafter's row layout "
                    "and the verify step's ancestor mask must match")
        if parallel_state.get_pipeline_model_parallel_world_size() > 1:
            raise NotImplementedError(
                "serving decode does not pipeline: initialize the mesh "
                "with pp=1 (decode shards over tp — see decode_fns(tp=))")
        tp_size = int(dict(mesh.shape).get(self.axis_name, 1))
        if tp is not None and int(tp) != tp_size:
            raise ValueError(
                f"decode_fns(tp={tp}) disagrees with the mesh's "
                f"'{self.axis_name}' extent ({tp_size}) — the mesh is "
                f"the source of truth; build a mesh with tp={tp}")
        if c.num_attention_heads % tp_size:
            raise ValueError(
                f"tensor-parallel decode head-shards the KV pools: "
                f"num_attention_heads={c.num_attention_heads} must be "
                f"divisible by tp={tp_size}")
        cfg = cache_config
        if (cfg.num_layers != c.num_layers
                or cfg.num_heads != c.num_attention_heads
                or cfg.head_dim != c.head_dim):
            raise ValueError(
                f"cache config (L={cfg.num_layers}, h={cfg.num_heads}, "
                f"d={cfg.head_dim}) does not match the model "
                f"(L={c.num_layers}, h={c.num_attention_heads}, "
                f"d={c.head_dim})")
        if c.position_embedding == "learned" and \
                cfg.max_len > c.max_position_embeddings:
            raise ValueError(
                f"cache holds up to {cfg.max_len} positions but the "
                f"learned table stops at {c.max_position_embeddings}")

        if weight_dtype is not None and weight_dtype not in (
                "bf16", "int8", "int4"):
            raise ValueError(
                f"weight_dtype must be None, 'bf16', 'int8' or "
                f"'int4', got {weight_dtype!r}")
        wd_in = self._weight_pool_dtype(params)
        if weight_dtype in ("int8", "int4"):
            if wd_in in ("int8", "int4"):
                if wd_in != weight_dtype:
                    raise ValueError(
                        f"weight_dtype={weight_dtype!r} requested but "
                        f"the params already carry a {wd_in} pool")
            else:
                # the ONE conversion — at build (= checkpoint-load)
                # time, never per step; packed for THIS tp degree
                params = quantize_gpt_weights(
                    params, weight_dtype, weight_block, tp=tp_size)
        elif weight_dtype == "bf16" and wd_in == "float32":
            layers = dict(params["layers"])
            for name in QUANTIZED_WEIGHT_LEAVES:
                if name in layers:
                    leaf = dict(layers[name])
                    leaf["weight"] = leaf["weight"].astype(jnp.bfloat16)
                    layers[name] = leaf
            params = {**params, "layers": layers}
        wd_active = self._weight_pool_dtype(params)
        if wd_active in ("int8", "int4") and tp_size > 1:
            # divisibility is checkable after the fact (pre-built pools
            # included); int4 packing tp is NOT — the bytes carry no
            # marker, so a pre-built int4 pool must have been packed
            # with quantize_gpt_weights(tp=tp) (docstring there)
            from apex_tpu.ops.dequant_matmul import weight_pool_block

            for name in QUANTIZED_WEIGHT_LEAVES:
                leaf = params["layers"].get(name)
                if leaf is None:
                    continue
                blk = weight_pool_block(leaf)
                n = leaf["scales"].shape[-1] * blk
                _check_quantized_tp(name, leaf["scales"].shape[1], n,
                                    wd_active, blk, tp_size)

        specs = self.param_specs()
        if wd_active in ("int8", "int4"):
            # the spec tree must mirror the quantized pytree structure:
            # column/row sharded so each chip streams 1/tp of the pool
            specs["layers"] = _quantized_layer_specs(
                specs["layers"], params["layers"], self.axis_name)
        pool_tmpl = jax.eval_shape(lambda: init_pools(cfg))
        # KV pools (L, num_pages, h, page_size, d) head-shard on axis 2
        # at EVERY tp (size 1 included — one layout, one set of specs):
        # each shard owns its head slice of every layer's pool, while
        # page tables / write targets / the host allocator stay
        # replicated — ONE shared free list drives every shard, so
        # tables are identical across shards by construction
        pool_specs = jax.tree.map(
            lambda _: P(None, None, self.axis_name, None, None),
            pool_tmpl)
        rep = lambda tree: jax.tree.map(lambda _: P(), tree)
        # the ONE sampling seam: vocab-parallel logits all-gather to
        # the full (replicated) vocab right before the sampler, so
        # sample / spec_accept / the per-slot key schedule see the same
        # tensors at every tp.  The gather is what types the sampled
        # tokens replicated for the P() out_specs; at tp=1 it is free.
        _full_logits = functools.partial(
            gather_from_tensor_model_parallel_region,
            axis_name=self.axis_name)

        @phase("prefill")
        def _prefill(params, pools, toks, length, page_row, key):
            hidden, ks, vs = self.prefill_forward(params, toks)
            pos = jnp.arange(toks.shape[1], dtype=jnp.int32)
            valid = pos < length
            wp, wo = write_targets(page_row, pos, valid, cfg.page_size)

            def write_layer(pool_l, kl, vl):
                # (1, hl, s, d) -> (s, hl, d) token rows
                return write_tokens(
                    pool_l, jnp.moveaxis(kl[0], 1, 0),
                    jnp.moveaxis(vl[0], 1, 0), wp, wo,
                    quantized=cfg.quantized, kv_block=cfg.kv_block)

            pools = jax.vmap(write_layer)(pools, ks, vs)
            last = jnp.take(hidden[0], length - 1, axis=0)  # (h,)
            logits = _full_logits(
                self.logits(params, last[None, None])[0, 0])
            # the draw after L context tokens folds L into the slot key
            # — the ONE key schedule shared with _chunk and _decode, so
            # chunked and monolithic prefill sample identically
            tok = sample(logits[None], jax.random.fold_in(key, length),
                         temperature, top_k, top_p)[0]
            return pools, tok

        @phase("prefill")
        def _chunk(params, pools, toks, start, plen, write_from,
                   page_row, key):
            logits, pools = self.prefill_chunk(
                params, toks, start, plen, write_from, page_row,
                pools, quantized=cfg.quantized, kv_block=cfg.kv_block,
                weight_dtype=wd_active)
            logits = _full_logits(logits)
            tok = sample(logits[None], jax.random.fold_in(key, plen),
                         temperature, top_k, top_p)[0]
            return pools, tok, logits

        @phase("decode")
        def _decode(params, pools, carry, page_table):
            active = jnp.logical_not(carry["done"])
            logits, pools = self.decode_step(
                params, carry["tokens"], carry["lengths"], active,
                page_table, pools, quantized=cfg.quantized,
                kv_block=cfg.kv_block, weight_dtype=wd_active)
            logits = _full_logits(logits)
            return pools, advance_slots(
                carry, logits, active, temperature=temperature, top_k=top_k,
                top_p=top_p, eos_id=eos_id)

        @phase("decode")
        def _spec(params, pools, carry, page_table, drafts, draft_len):
            # verify-and-commit: k+1 rows through ONE weight stream,
            # then the fused acceptance rule, then a multi-token carry
            # advance — all inside the jit, fixed shapes for every
            # draft length and acceptance pattern
            K = int(speculate_k)
            R = K + 1
            active = jnp.logical_not(carry["done"])
            lengths = carry["lengths"]
            jrow = jnp.arange(R, dtype=jnp.int32)[None]       # (1, R)
            rows = jnp.concatenate(
                [carry["tokens"][:, None], drafts.astype(jnp.int32)],
                axis=1)                                        # (S, R)
            valid = jrow <= draft_len[:, None]
            logits, pools = self.verify_step(
                params, rows, lengths, active, valid, page_table,
                pools, quantized=cfg.quantized, kv_block=cfg.kv_block,
                weight_dtype=wd_active)
            logits = _full_logits(logits)
            # row j's draw sits after lengths + 1 + j context tokens —
            # fold exactly what the plain one-token loop would fold at
            # that position, so the committed stream is key-schedule
            # identical to non-speculative sampling (and to a failover
            # replay that re-enters anywhere in the stream)
            ctx = jnp.where(active[:, None], lengths[:, None] + 1 + jrow,
                            0)
            keys = jax.vmap(
                jax.vmap(jax.random.fold_in, in_axes=(None, 0))
            )(carry["sample_keys"], ctx)
            targets, n_acc = jax.vmap(
                lambda l, dr, dl, kk: spec_accept(
                    l, dr, dl, kk, temperature, top_k, top_p)
            )(logits, drafts, draft_len, keys)
            # commit = accepted drafts + the correction/bonus row, cut
            # at the first committed EOS and capped at the slot's
            # remaining budget — the same freeze rules as _decode,
            # applied to a variable-length advance
            raw = n_acc + 1
            is_eos = ((targets == eos_id) if eos_id is not None
                      else jnp.zeros_like(targets, dtype=bool))
            eos_run = is_eos & (jrow < raw[:, None])
            any_eos = jnp.any(eos_run, axis=1)
            first_eos = jnp.argmax(eos_run, axis=1).astype(jnp.int32)
            n_c = jnp.where(any_eos, first_eos + 1, raw)
            n_c = jnp.minimum(n_c, carry["steps_left"])
            n_c = jnp.where(active, n_c, 0).astype(jnp.int32)
            last = jnp.take_along_axis(
                targets, jnp.clip(n_c - 1, 0, R - 1)[:, None],
                axis=1)[:, 0]
            tokens = jnp.where(active, last, carry["tokens"])
            steps_left = carry["steps_left"] - n_c
            eos_committed = jnp.any(
                is_eos & (jrow < n_c[:, None]), axis=1)
            done = carry["done"] | (
                active & (eos_committed | (steps_left <= 0)))
            new_carry = {
                "tokens": tokens,
                "lengths": carry["lengths"] + n_c,
                "steps_left": steps_left,
                "done": done,
                "sample_keys": carry["sample_keys"],
            }
            return pools, new_carry, targets, n_c

        @phase("decode")
        def _spec_tree(params, pools, carry, page_table, drafts,
                       draft_len):
            # tree verify-and-commit: R candidate rows (a static
            # parents tree) through ONE weight stream under the
            # ancestor mask, the coupled tree walk, then the pass-2
            # rewrite that moves the ACCEPTED path's K/V rows from
            # their collision-free physical slots (lengths + row) to
            # the committed depth positions (lengths + depth) — all
            # inside the jit, fixed shapes for every draft pattern
            from apex_tpu.serving.kv_cache import (
                write_targets, write_tokens,
            )
            from apex_tpu.serving.sampling import spec_accept_tree
            from apex_tpu.serving.speculate import tree_depths

            tree = _tree
            R = len(tree)
            jd = jnp.asarray(tree_depths(tree), jnp.int32)[None]
            jrow = jnp.arange(R, dtype=jnp.int32)[None]       # (1, R)
            active = jnp.logical_not(carry["done"])
            lengths = carry["lengths"]
            max_len = page_table.shape[1] * cfg.page_size
            rows = jnp.concatenate(
                [carry["tokens"][:, None], drafts.astype(jnp.int32)],
                axis=1)                                        # (S, R)
            phys = lengths[:, None] + jrow
            # a node is live when its depth fits the drafted length AND
            # its physical scratch slot fits the slot's page extent —
            # the second guard keeps acceptance away from rows whose
            # K/V was masked to the null page near the capacity edge
            valid = (jd <= draft_len[:, None]) & (phys < max_len)
            logits, pools, (ks, vs) = self.verify_step(
                params, rows, lengths, active, valid, page_table,
                pools, quantized=cfg.quantized, kv_block=cfg.kv_block,
                weight_dtype=wd_active, tree=tree)
            logits = _full_logits(logits)
            # node r's children draw at absolute position lengths + 1 +
            # depth(r): depth-keyed, NOT row-keyed, so every draw folds
            # exactly what the plain one-token loop folds there and the
            # committed stream stays key-schedule identical
            ctx = jnp.where(active[:, None], lengths[:, None] + 1 + jd,
                            0)
            keys = jax.vmap(
                jax.vmap(jax.random.fold_in, in_axes=(None, 0))
            )(carry["sample_keys"], ctx)
            outs, n_acc, path = jax.vmap(
                lambda l, dr, v, kk: spec_accept_tree(
                    l, dr, tree, v, kk, temperature, top_k, top_p)
            )(logits, drafts, valid[:, 1:], keys)
            # commit = accepted path + the correction/bonus draw, cut
            # at the first committed EOS and capped at the slot's
            # remaining budget — identical freeze rules to _spec
            raw = n_acc + 1
            is_eos = ((outs == eos_id) if eos_id is not None
                      else jnp.zeros_like(outs, dtype=bool))
            eos_run = is_eos & (jrow < raw[:, None])
            any_eos = jnp.any(eos_run, axis=1)
            first_eos = jnp.argmax(eos_run, axis=1).astype(jnp.int32)
            n_c = jnp.where(any_eos, first_eos + 1, raw)
            n_c = jnp.minimum(n_c, carry["steps_left"])
            n_c = jnp.where(active, n_c, 0).astype(jnp.int32)
            # pass-2: depth d's committed node (row path[d]) moves to
            # position lengths + d.  Chain-shaped paths rewrite rows
            # onto themselves (same post-RoPE values, same quantizer →
            # same bytes); dead depths past n_acc land beyond the new
            # length where the next step's writes cover them
            dst = lengths[:, None] + jrow
            rw = (active[:, None] & (jrow >= 1)
                  & (jrow <= n_acc[:, None]) & (dst < max_len))
            wp2, wo2 = write_targets(page_table, dst, rw,
                                     cfg.page_size)

            def rewrite(pool_l, kl, vl):
                # (S, hl, R, d) --gather path rows--> (S*R, hl, d)
                kl = jnp.take_along_axis(
                    kl, path[:, None, :, None], axis=2)
                vl = jnp.take_along_axis(
                    vl, path[:, None, :, None], axis=2)
                S = kl.shape[0]
                return write_tokens(
                    pool_l,
                    jnp.moveaxis(kl, 1, 2).reshape(
                        S * R, -1, kl.shape[-1]),
                    jnp.moveaxis(vl, 1, 2).reshape(
                        S * R, -1, vl.shape[-1]),
                    wp2.reshape(-1), wo2.reshape(-1),
                    quantized=cfg.quantized, kv_block=cfg.kv_block)

            pools = jax.vmap(rewrite)(pools, ks, vs)
            last = jnp.take_along_axis(
                outs, jnp.clip(n_c - 1, 0, R - 1)[:, None],
                axis=1)[:, 0]
            tokens = jnp.where(active, last, carry["tokens"])
            steps_left = carry["steps_left"] - n_c
            eos_committed = jnp.any(
                is_eos & (jrow < n_c[:, None]), axis=1)
            done = carry["done"] | (
                active & (eos_committed | (steps_left <= 0)))
            new_carry = {
                "tokens": tokens,
                "lengths": carry["lengths"] + n_c,
                "steps_left": steps_left,
                "done": done,
                "sample_keys": carry["sample_keys"],
            }
            return pools, new_carry, outs, n_c, path

        from apex_tpu.serving.serve import init_carry

        carry_tmpl = init_carry(cfg.max_seqs)
        # the names jax gives the executables made below
        _programs.own(*(f.__name__ for f in (
            _prefill, _chunk, _decode, _spec, _spec_tree)),
            layer="serving steps")
        pf = jax.jit(jax.shard_map(
            _prefill, mesh=mesh,
            in_specs=(specs, pool_specs, P(), P(), P(), P()),
            out_specs=(pool_specs, P()),
        ))
        df = jax.jit(jax.shard_map(
            _decode, mesh=mesh,
            in_specs=(specs, pool_specs, rep(carry_tmpl), P()),
            out_specs=(pool_specs, rep(carry_tmpl)),
        ))
        prefill = lambda pools, toks, ln, row, key: pf(
            params, pools, toks, ln, row, key)
        decode = lambda pools, carry, pt: df(params, pools, carry, pt)
        # the batcher only sees the callables; stamp the freeze id so
        # it can reject a host truncation id the device disagrees with
        decode.eos_id = eos_id
        # every step returns the carry on the mesh; a carry created
        # there (``init_carry(sharding=...)``) types the first step like
        # every later one — one trace, one compile
        carry_sharding = NamedSharding(mesh, P())
        decode.carry_sharding = carry_sharding
        # ONE decode step streams this chip's OWN slice of the pool:
        # sharded projections (at the active width, + their fp32
        # scales) and the vocab-sharded embedding at 1/tp, replicated
        # norms in full — the per-chip numerator of the serving
        # weight-stream GB/s headline (at tp=1 this is the whole pool,
        # byte-identical to the historical stamp)
        wbytes = _per_chip_param_bytes(params, specs, mesh)
        decode.weight_dtype = wd_active
        decode.weight_stream_bytes = wbytes
        decode.tp = tp_size
        chunk = cj = None
        if prefill_chunk is not None:
            from apex_tpu.ops.attention_decode import (
                FMHA_DECODE_MAX_ROWS,
            )

            if int(prefill_chunk) < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}")
            if int(prefill_chunk) > FMHA_DECODE_MAX_ROWS:
                # past the row budget even block_h=1 cannot keep the
                # kernel's fp32 scratch inside the VMEM bound — fail at
                # build time, not with an opaque lowering error at
                # serve time
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} exceeds the decode "
                    f"kernel's per-program row budget "
                    f"(FMHA_DECODE_MAX_ROWS={FMHA_DECODE_MAX_ROWS}); "
                    "use a smaller chunk — serving stalls shrink with "
                    "it anyway (docs/serving.md)")
            cj = jax.jit(jax.shard_map(
                _chunk, mesh=mesh,
                in_specs=(specs, pool_specs, P(), P(), P(), P(), P(),
                          P()),
                out_specs=(pool_specs, P(), P()),
            ))
            C = int(prefill_chunk)

            def chunk(pools, toks, start, plen, write_from, row, key,
                      _cj=cj, _C=C):
                toks = jnp.asarray(toks, jnp.int32).reshape(1, _C)
                return _cj(params, pools, toks,
                           jnp.int32(start), jnp.int32(plen),
                           jnp.int32(write_from), row, key)

            # stamped like decode.eos_id: the batcher schedules chunks
            # of ITS size and must reject a step compiled for another
            chunk.prefill_chunk = C

        spec = sj = None
        _tree = None
        if spec_tree is not None and speculate_k is None:
            raise ValueError(
                "spec_tree given without speculate_k — the tree's max "
                "depth IS the draft budget; pass speculate_k=K")
        if speculate_k is not None:
            from apex_tpu.ops.attention_decode import (
                FMHA_DECODE_MAX_ROWS,
            )

            K = int(speculate_k)
            if K < 1:
                raise ValueError(
                    f"speculate_k must be >= 1, got {speculate_k}")
            if K + 1 > FMHA_DECODE_MAX_ROWS:
                raise ValueError(
                    f"speculate_k {K} puts the verify step at "
                    f"{K + 1} rows, past the decode kernel's "
                    f"per-program row budget "
                    f"(FMHA_DECODE_MAX_ROWS={FMHA_DECODE_MAX_ROWS}); "
                    "acceptance saturates long before that anyway "
                    "(docs/serving.md, k-selection)")
            if spec_tree is not None:
                from apex_tpu.serving.speculate import (
                    tree_max_depth, validate_tree,
                )

                _tree = validate_tree(spec_tree)
                if tree_max_depth(_tree) != K:
                    raise ValueError(
                        f"spec_tree has max depth "
                        f"{tree_max_depth(_tree)} but speculate_k="
                        f"{K} — the deepest root-to-leaf path is the "
                        "draft budget; they must agree")
                R = len(_tree)
                if R > FMHA_DECODE_MAX_ROWS:
                    raise ValueError(
                        f"spec_tree has {R} rows, past the decode "
                        f"kernel's per-program row budget "
                        f"(FMHA_DECODE_MAX_ROWS="
                        f"{FMHA_DECODE_MAX_ROWS}); prune the tree")
                sj = jax.jit(jax.shard_map(
                    _spec_tree, mesh=mesh,
                    in_specs=(specs, pool_specs, rep(carry_tmpl), P(),
                              P(), P()),
                    out_specs=(pool_specs, rep(carry_tmpl), P(), P(),
                               P()),
                ))

                def spec(pools, carry, pt, drafts, draft_len, _sj=sj,
                         _R=R):
                    drafts = jnp.asarray(drafts, jnp.int32).reshape(
                        cfg.max_seqs, _R - 1)
                    draft_len = jnp.asarray(
                        draft_len, jnp.int32).reshape(cfg.max_seqs)
                    return _sj(params, pools, carry, pt, drafts,
                               draft_len)
            else:
                sj = jax.jit(jax.shard_map(
                    _spec, mesh=mesh,
                    in_specs=(specs, pool_specs, rep(carry_tmpl), P(),
                              P(), P()),
                    out_specs=(pool_specs, rep(carry_tmpl), P(), P()),
                ))

                def spec(pools, carry, pt, drafts, draft_len, _sj=sj,
                         _K=K):
                    drafts = jnp.asarray(drafts, jnp.int32).reshape(
                        cfg.max_seqs, _K)
                    draft_len = jnp.asarray(
                        draft_len, jnp.int32).reshape(cfg.max_seqs)
                    return _sj(params, pools, carry, pt, drafts,
                               draft_len)

            # stamped like decode.eos_id / chunk.prefill_chunk: the
            # batcher drafts at ITS k and must reject a verify step
            # compiled for another, or for a different freeze id /
            # tree shape
            spec.eos_id = eos_id
            spec.speculate_k = K
            spec.spec_tree = _tree
            spec.draft_source = draft_model

        return GPTDecodeFns(
            prefill=prefill,
            decode=decode,
            prefill_jit=pf,
            decode_jit=df,
            eos_id=eos_id,
            chunk=chunk,
            chunk_jit=cj,
            prefill_chunk=(None if prefill_chunk is None
                           else int(prefill_chunk)),
            spec=spec,
            spec_jit=sj,
            speculate_k=(None if speculate_k is None
                         else int(speculate_k)),
            spec_tree=_tree,
            draft_source=draft_model,
            weight_dtype=wd_active,
            weight_stream_bytes=wbytes,
            tp=tp_size,
            carry_sharding=carry_sharding,
            param_specs=specs,
            pool_specs=pool_specs,
        )

    def generate(
        self,
        params: Dict[str, Any],
        prompts,
        prompt_lengths,
        max_new_tokens: int,
        *,
        mesh,
        page_size: int = 64,
        kv_dtype: Optional[Any] = None,
        kv_block: int = 128,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        eos_id: Optional[int] = None,
        key: Optional[jax.Array] = None,
        harvest_every: int = 8,
        max_seqs: Optional[int] = None,
        num_pages: Optional[int] = None,
        logger: Optional[Any] = None,
        prefill_chunk: Optional[int] = None,
        prefix_cache: bool = False,
        speculate_k: Optional[int] = None,
        draft_source: Optional[Any] = None,
        weight_dtype: Optional[str] = None,
        weight_block: int = 128,
    ):
        """Generate from ``prompts (b, s)`` (right-padded; real lengths
        in ``prompt_lengths``) through the full serving stack — paged
        KV cache, fused decode kernel, on-device sampling, continuous
        batching.  ``max_seqs`` (default ``b``) bounds concurrent
        slots, so ``b > max_seqs`` exercises real admit/retire churn.
        ``kv_dtype=jnp.int8`` stores the cache quantized;
        ``weight_dtype="bf16"/"int8"/"int4"`` additionally serves from
        a reduced-width weight pool (in-kernel dequant,
        docs/serving.md).
        ``prefill_chunk`` switches prompt ingestion to the stall-free
        chunked scheduler (docs/serving.md) and ``prefix_cache``
        additionally shares identical prompt prefixes across requests.
        ``speculate_k`` turns on draft-and-verify speculative decoding
        (k host-drafted tokens verified per weight stream; the token
        streams stay identical — docs/serving.md), drafting from
        ``draft_source`` (default n-gram self-speculation).  Returns
        the per-prompt generated token lists (EOS included when
        hit)."""
        import numpy as np

        from apex_tpu.serving.kv_cache import (
            KVCacheConfig, PagedKVCache, init_pools,
        )
        from apex_tpu.serving.serve import ContinuousBatcher, Request

        c = self.config
        prompts = np.asarray(prompts)
        prompt_lengths = np.asarray(prompt_lengths)
        b, s = prompts.shape
        max_seqs = int(max_seqs or b)
        pages_per_seq = -(-(s + max_new_tokens) // page_size)
        num_pages = int(num_pages
                        or 1 + max_seqs * pages_per_seq)
        ccfg = KVCacheConfig(
            num_layers=c.num_layers,
            num_heads=c.num_attention_heads,
            head_dim=c.head_dim,
            num_pages=num_pages,
            page_size=page_size,
            max_seqs=max_seqs,
            pages_per_seq=pages_per_seq,
            dtype=c.compute_dtype,
            kv_dtype=kv_dtype,
            kv_block=kv_block,
        )
        fns = self.decode_fns(
            params, mesh, ccfg, max_prompt_len=s,
            temperature=temperature, top_k=top_k, top_p=top_p,
            eos_id=eos_id, prefill_chunk=prefill_chunk,
            speculate_k=speculate_k, weight_dtype=weight_dtype,
            weight_block=weight_block)
        batcher = ContinuousBatcher(
            fns.prefill, fns.decode, PagedKVCache(ccfg),
            init_pools(ccfg), max_prompt_len=s,
            harvest_every=harvest_every, eos_id=eos_id, key=key,
            logger=logger, chunk_fn=fns.chunk,
            prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
            spec_fn=fns.spec, speculate_k=fns.speculate_k,
            draft_source=draft_source)
        reqs = [
            Request(uid=i,
                    prompt=[int(t) for t in
                            prompts[i, : int(prompt_lengths[i])]],
                    max_new_tokens=max_new_tokens)
            for i in range(b)
        ]
        comps = batcher.run(reqs)
        return [comps[i].tokens for i in range(b)]

    def generate_reference(
        self,
        params: Dict[str, Any],
        prompts,
        prompt_lengths,
        max_new_tokens: int,
        *,
        mesh,
    ):
        """Naive full-recompute GREEDY reference: every step re-runs the
        whole forward (the training attention ladder, no cache) over
        the growing padded sequence and argmaxes the last valid
        position.  O(steps * s^2) — exists to GATE the paged path
        (``validate_fmha_decode`` / ``_dryrun_decode`` assert the
        serving stack's greedy tokens match this exactly), never to
        serve.  Learned-position models need ``s + max_new_tokens <=
        max_position_embeddings``."""
        import numpy as np

        c = self.config
        prompts = np.asarray(prompts)
        prompt_lengths = np.asarray(prompt_lengths)
        b, s = prompts.shape
        total = s + max_new_tokens
        if c.position_embedding == "learned" and \
                total > c.max_position_embeddings:
            raise ValueError(
                f"reference needs {total} positions but the learned "
                f"table stops at {c.max_position_embeddings}")
        specs = self.param_specs()

        def step(p, buf, lens):
            logits = self.apply(p, buf)                    # (b, T, V/tp)
            idx = jnp.clip(lens - 1, 0, total - 1)
            last = jnp.take_along_axis(
                logits, idx[:, None, None], axis=1)[:, 0]  # (b, V/tp)
            # full vocab before the argmax: a local argmax over V/tp is
            # a different token at tp>1, and the gather types the result
            # replicated for the P() out_specs
            last = gather_from_tensor_model_parallel_region(
                last, axis_name=self.axis_name)
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
            buf = buf.at[jnp.arange(b), lens].set(nxt)
            return buf, lens + 1, nxt

        fn = jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(specs, P(), P()),
            out_specs=(P(), P(), P()),
        ))
        buf = jnp.zeros((b, total), jnp.int32)
        buf = buf.at[:, :s].set(jnp.asarray(prompts, jnp.int32))
        lens = jnp.asarray(prompt_lengths, jnp.int32)
        outs = []
        for _ in range(max_new_tokens):
            buf, lens, nxt = fn(params, buf, lens)
            outs.append(nxt)
        return np.asarray(jax.device_get(jnp.stack(outs))).T  # (b, new)

    # ------------------------------------------------------ pipeline path
    def pipeline_param_specs(
        self, num_model_chunks: Optional[int] = None
    ) -> Dict[str, Any]:
        """Param specs with the stacked-layer dim sharded over "pp", so
        each pipeline stage holds its own num_layers/pp layers.  With
        ``num_model_chunks`` (virtual pipeline), specs match
        :meth:`pipeline_chunk_params`'s (V, pp, per, ...) layer layout,
        sharded over "pp" on axis 1."""
        from jax.sharding import PartitionSpec as P

        from apex_tpu.transformer.pipeline_parallel import (
            pipeline_stage_specs,
        )

        specs = self.param_specs()
        if num_model_chunks is None:
            specs["layers"] = pipeline_stage_specs(specs["layers"])
        else:
            specs["layers"] = jax.tree.map(
                lambda s: P(None, "pp", *s),
                specs["layers"],
                is_leaf=lambda x: isinstance(x, P),
            )
        return specs

    def pipeline_chunk_params(
        self, params: Dict[str, Any], num_model_chunks: int
    ) -> Dict[str, Any]:
        """Rearrange stacked layer params (L, ...) into the interleaved
        (V, pp, per, ...) chunk layout: chunk v of rank p is global
        stage ``v*pp + p`` and holds layers ``(v*pp+p)*per + k`` — a
        plain reshape, because ``l = v*(pp*per) + p*per + k``
        (reference: model-chunk construction in
        fwd_bwd_pipelining_with_interleaving.py:22-70)."""
        from apex_tpu.transformer import parallel_state

        pp = parallel_state.get_pipeline_model_parallel_world_size()
        V = num_model_chunks
        L = self.config.num_layers
        if L % (V * pp):
            raise ValueError(
                f"num_layers ({L}) must divide into num_model_chunks * "
                f"pp ({V}*{pp}) equal chunks"
            )
        per = L // (V * pp)
        return {
            **params,
            "layers": jax.tree.map(
                lambda x: x.reshape(V, pp, per, *x.shape[1:]),
                params["layers"],
            ),
        }

    def _pp_stack(self, x, layers):
        """Run one stacked-layer slice over the pipeline activation
        stream — shared by the GPipe (:meth:`pipeline_loss`) and
        1F1B/interleaved (:meth:`pipeline_1f1b_grads`) stage bodies so
        the aux-threading semantics cannot diverge.  The stream is
        ``{"h": hidden, "aux": scalar}`` for MoE models (the aux-loss
        accumulator rides the ppermute ring with its microbatch), plain
        hidden otherwise."""
        s = (x["h"] if self.moe is not None else x).shape[1]
        rope = self._rope_tables(s)

        def body(h, lp):
            out, aux, _kv = self._layer(lp, h, None, rope=rope)
            return out, aux

        if self.moe is not None:
            out, auxs = jax.lax.scan(body, x["h"], layers)
            return {"h": out, "aux": x["aux"] + jnp.sum(auxs)}
        out, _ = jax.lax.scan(body, x, layers)
        return out

    def pipeline_loss(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        targets: jnp.ndarray,
        num_microbatches: int,
    ) -> jnp.ndarray:
        """Mean next-token CE through the compiled pipeline schedule —
        call inside shard_map with params placed by
        :meth:`pipeline_param_specs`.  ``params["layers"]`` is then the
        local stage's layer stack.  After ``jax.grad`` of this, apply
        ``pipeline_parallel.sync_replicated_grads`` for the tied
        embedding / shared-param grad sync."""
        from apex_tpu.transformer.pipeline_parallel import pipeline

        c = self.config
        b, s = tokens.shape
        if b % num_microbatches:
            raise ValueError(
                f"local batch ({b}) must be divisible by "
                f"num_microbatches ({num_microbatches})"
            )
        mb = b // num_microbatches
        mbs = {
            "tokens": tokens.reshape(num_microbatches, mb, s),
            "targets": targets.reshape(num_microbatches, mb, s),
        }

        moe = self.moe is not None

        def first_fn(m):
            x = self._embed(params, m["tokens"])
            # MoE: the activation stream carries a per-microbatch aux
            # accumulator (schedules are pytree-generic, so the scalar
            # rides the ppermute ring with its microbatch for free).
            # Derive the zero from x so it carries x's varying-mesh-axes
            # type: a plain 0.0 constant is mesh-invariant and the
            # backward would reject the varying cotangent
            return ({"h": x, "aux": jnp.sum(x).astype(jnp.float32) * 0}
                    if moe else x)

        def stage_fn(x):
            return self._pp_stack(x, params["layers"])

        def last_fn(x, m):
            x, aux = (x["h"], x["aux"]) if moe else (x, None)
            x = self._norm(params["final_ln"], x.astype(jnp.float32)).astype(c.compute_dtype)
            per_token = self._per_token_ce(params, x, m["targets"])
            loss = jnp.mean(per_token)
            if moe:
                # same weighting as the sequential path (loss():
                # ce + moe_aux_weight * summed aux), per microbatch
                loss = loss + c.moe_aux_weight * aux
            return loss

        per_micro = pipeline(
            first_fn, stage_fn, last_fn, mbs, remat=c.remat
        )
        loss = jnp.mean(per_micro)
        return jax.lax.pmean(loss, DATA_PARALLEL_AXIS)

    def pipeline_1f1b_grads(
        self,
        params: Dict[str, Any],
        tokens: jnp.ndarray,
        targets: jnp.ndarray,
        num_microbatches: int,
        num_model_chunks: Optional[int] = None,
    ) -> tuple:
        """Fwd+bwd through the production pipeline schedule dispatched
        by ``get_forward_backward_func`` (reference:
        schedules/__init__.py:1-39): 1F1B, or interleaved 1F1B when
        ``num_model_chunks`` is given (params then placed by
        ``pipeline_param_specs(num_model_chunks)`` in the
        :meth:`pipeline_chunk_params` layout).  Returns
        ``(mean loss, grads)`` directly — in-flight activation memory is
        bounded by the pipeline depth, not ``num_microbatches``
        (PIPELINE_MEMORY.json: flat temp memory from 2 to 32
        microbatches).  Prefer this over ``jax.grad(pipeline_loss)``
        for deep gradient accumulation.  Same placement contract as
        :meth:`pipeline_loss`; the returned grads already have the
        shared-param sync AND the dp pmean applied — step the optimizer
        with them directly (do not psum over dp again).

        MoE: the activation stream carries a per-microbatch aux-loss
        accumulator through the ring (the schedules are pytree-generic),
        so the router load-balance aux and z-loss DO reach the loss and
        the router gradients under pp>1 — per-microbatch accumulation
        semantics, same as grad accumulation (each microbatch's
        balance statistics are its own; the sequential whole-batch
        ``loss()`` computes one global statistic instead)."""
        from apex_tpu.transformer.pipeline_parallel import (
            get_forward_backward_func,
            sync_replicated_grads,
        )
        from apex_tpu.transformer.parallel_state import (
            PIPELINE_PARALLEL_AXIS,
        )

        c = self.config
        b, s = tokens.shape
        if b % num_microbatches:
            raise ValueError(
                f"local batch ({b}) must be divisible by "
                f"num_microbatches ({num_microbatches})"
            )
        mb = b // num_microbatches
        mbs = {
            "tokens": tokens.reshape(num_microbatches, mb, s),
            "targets": targets.reshape(num_microbatches, mb, s),
        }

        moe = self.moe is not None

        def first_fn(prm, m):
            x = self._embed(prm, m["tokens"])
            # MoE: per-microbatch aux accumulator rides the stream; the
            # zero derives from x to carry its varying-mesh-axes type
            # (see pipeline_loss)
            return ({"h": x, "aux": jnp.sum(x).astype(jnp.float32) * 0}
                    if moe else x)

        def stage_fn(prm, x):
            return self._pp_stack(x, prm["layers"])

        def chunk_fn(prm, x, v):
            # local chunk v: (V, 1, per, ...) sliced at [v, 0]
            chunk = jax.tree.map(
                lambda l: jax.lax.dynamic_index_in_dim(l, v, 0, False)[0],
                prm["layers"],
            )
            return self._pp_stack(x, chunk)

        def last_fn(prm, x, m):
            x, aux = (x["h"], x["aux"]) if moe else (x, None)
            x = self._norm(prm["final_ln"], x.astype(jnp.float32)).astype(c.compute_dtype)
            per_token = self._per_token_ce(prm, x, m["targets"])
            loss = jnp.mean(per_token)
            if moe:
                loss = loss + c.moe_aux_weight * aux
            return loss

        fwd_bwd = get_forward_backward_func(
            virtual_pipeline_model_parallel_size=num_model_chunks,
            pipeline_model_parallel_size=jax.lax.axis_size(
                PIPELINE_PARALLEL_AXIS
            ),
        )
        losses, grads = fwd_bwd(
            first_fn,
            stage_fn if num_model_chunks is None else chunk_fn,
            last_fn,
            params,
            mbs,
        )
        specs = self.pipeline_param_specs(num_model_chunks)
        grads = sync_replicated_grads(grads, specs)
        loss = jax.lax.pmean(jnp.mean(losses), DATA_PARALLEL_AXIS)

        from apex_tpu.transformer.parallel_state import spec_axis_names

        def data_reduce(s, g, axis):
            # the schedule's grads are this data shard's contribution to
            # ITS local mean loss; the global objective is the
            # data-axis mean.  Replicated leaves: average the shard
            # contributions (pmean).  Leaves SHARDED over the data axis
            # (MoE experts ride "dp" as the ep axis): the all_to_all
            # transpose already accumulated every shard's contribution
            # into the owner, so the mean is just the 1/n scale.
            n = jax.lax.axis_size(axis)
            if axis in spec_axis_names(s):
                return g / n
            return jax.lax.pmean(g, axis)

        def reduce_tree(grads, axis):
            return jax.tree.map(
                lambda s, g: data_reduce(s, g, axis), specs, grads,
                is_leaf=lambda x: isinstance(x, P),
            )

        grads = reduce_tree(grads, DATA_PARALLEL_AXIS)
        if self.config.context_parallel:
            # sequence shards each saw only their chunk of every
            # microbatch: average over cp exactly like :meth:`loss`
            from apex_tpu.transformer.parallel_state import (
                CONTEXT_PARALLEL_AXIS,
            )

            loss = jax.lax.pmean(loss, CONTEXT_PARALLEL_AXIS)
            grads = reduce_tree(grads, CONTEXT_PARALLEL_AXIS)
        return loss, grads
