"""The plain reference: GPT-2's forward pass and next-token loss.

Written from the published description (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners", and the public GPT-2 code):
token plus learned position embeddings; per block a pre-LayerNorm causal
multi-head attention and a pre-LayerNorm two-layer MLP with the tanh
approximation of GELU ("gelu_new"), each added to the residual stream; a
final LayerNorm; logits through the transposed token embedding.  Plain
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``
(on a TPU a float32 product otherwise runs in bfloat16 passes): no
kernels, no cache, no sharding, and nothing imported from the program.

Weights are taken one layer at a time from the stacked leaves, so a
float32 copy of the whole model never has to exist beside a trainer's
optimizer state.  ``from_stacked`` is the one place that knows how the
program lays its leaves out: matrices are (in, out), every layer leaf has
a leading layer axis, and the fused qkv output is grouped per head
([h0_q h0_k h0_v h1_q ...]), which is undone here into the published
[Q | K | V] order.

Departures from the published model: the vocabulary rows are whatever
the embedding holds (the program pads 50257 up by Megatron's rule; the
pad rows take part in the softmax exactly as they do in the program).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(x, w, heads: int, eps: float):
    """One transformer block on (batch, seq, hidden) float32."""
    b, s, h = x.shape
    d = h // heads
    y = _layer_norm(x, w["ln_1_g"], w["ln_1_b"], eps)
    qkv = y @ w["c_attn_w"] + w["c_attn_b"]           # [Q | K | V]
    q, k, v = (t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(F32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    ctx = jax.nn.softmax(scores, axis=-1) @ v
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
    x = x + ctx @ w["attn_proj_w"] + w["attn_proj_b"]
    y = _layer_norm(x, w["ln_2_g"], w["ln_2_b"], eps)
    y = _gelu_new(y @ w["c_fc_w"] + w["c_fc_b"])
    return x + y @ w["mlp_proj_w"] + w["mlp_proj_b"]


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _layer_step(x, stacked, index, *, heads: int, eps: float):
    """Block ``index`` of the stacked leaves applied to ``x``; the
    layer's weights become float32 here, one layer at a time."""
    with jax.default_matmul_precision("highest"):
        w = {k: jax.lax.dynamic_index_in_dim(v, index, keepdims=False)
             .astype(F32) for k, v in stacked.items()}
        h = x.shape[-1]
        d = h // heads
        # per-head [q k v] grouping -> [Q | K | V]
        w["c_attn_w"] = w["c_attn_w"].reshape(h, heads, 3, d) \
            .transpose(0, 2, 1, 3).reshape(h, 3 * h)
        w["c_attn_b"] = w["c_attn_b"].reshape(heads, 3, d) \
            .transpose(1, 0, 2).reshape(3 * h)
        return _block(x, w, heads, eps)


@jax.jit
def _embed(tokens, wte, wpe):
    return wte.astype(F32)[tokens] + wpe.astype(F32)[:tokens.shape[1]]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, wte, g, b, *, eps: float):
    with jax.default_matmul_precision("highest"):
        x = _layer_norm(x, g.astype(F32), b.astype(F32), eps)
        return x @ wte.astype(F32).T


def from_stacked(params: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree under the reference's names."""
    L = params["layers"]
    return {
        "wte": params["embedding"]["weight"],
        "wpe": params["pos_embedding"],
        "ln_f_g": params["final_ln"]["scale"],
        "ln_f_b": params["final_ln"]["bias"],
        "stacked": {
            "ln_1_g": L["ln1"]["scale"], "ln_1_b": L["ln1"]["bias"],
            "c_attn_w": L["qkv"]["weight"], "c_attn_b": L["qkv"]["bias"],
            "attn_proj_w": L["attn_proj"]["weight"],
            "attn_proj_b": L["attn_proj"]["bias"],
            "ln_2_g": L["ln2"]["scale"], "ln_2_b": L["ln2"]["bias"],
            "c_fc_w": L["fc1"]["weight"], "c_fc_b": L["fc1"]["bias"],
            "mlp_proj_w": L["fc2"]["weight"], "mlp_proj_b": L["fc2"]["bias"],
        },
    }


def logits(weights: Dict[str, Any], tokens, *, heads: int, layers: int,
           eps: float = 1e-5):
    """(batch, seq) token ids -> (batch, seq, vocabulary rows) float32."""
    x = _embed(jnp.asarray(tokens), weights["wte"], weights["wpe"])
    for i in range(layers):
        x = _layer_step(x, weights["stacked"], jnp.int32(i),
                        heads=heads, eps=eps)
    return _head(x, weights["wte"], weights["ln_f_g"], weights["ln_f_b"],
                 eps=eps)


def loss(weights: Dict[str, Any], tokens, targets, *, heads: int,
         layers: int, eps: float = 1e-5):
    """Mean next-token cross-entropy over every position."""
    lg = logits(weights, tokens, heads=heads, layers=layers, eps=eps)
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(targets)[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)
