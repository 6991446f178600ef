"""The plain reference: the forward pass of Arcee's ``afmoe`` (Trinity),
for one chip's share of an expert-parallel deployment.

Written from the public ``config.json`` of
``arcee-ai/Trinity-Large-Preview`` and, where its keys do not spell a
thing, from the published ``afmoe`` model code as the configuration's
``assumed`` entries record it.  Plain ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense masks, no kernels, no
cache, no paging, one full forward over the whole sequence, and nothing
imported from the program.

``x0 = E[token] * sqrt(hidden)`` (``mup_enabled``).  Layer ``l`` of type
``layer_types[l]``, ``N`` RMSNorm with ``rms_norm_eps``::

    a  = N(h; w_in)
    q  = a W_q -> (Hq, d)    k = a W_k -> (Hkv, d)    v = a W_v -> (Hkv, d)
    gate = a W_g -> (Hq * d)
    q  = N_head(q; w_qn)     k = N_head(k; w_kn)      RMSNorm over d, per head
    sliding_attention:  q, k = RoPE(q, k; rope_theta, position)
    full_attention:     no rotation, no position signal
    s_ij = q_i . k_j / sqrt(d),  j <= i,  and i - j < sliding_window on
           sliding layers;  query head i reads K/V head i // (Hq / Hkv)
    o  = softmax_j(s) v;   o = o * sigmoid(gate);   h = h + N(o W_o; w_post_attn)
    m  = N(h; w_pre_mlp)
    F  = SwiGLU(m)                                    l < num_dense_layers
    F  = SwiGLU_shared(m) + sum_{e in T} g_e SwiGLU_e(m)         otherwise
         s = sigmoid(m W_r);  T = the num_experts_per_tok largest of s + b;
         g_e = route_scale * s_e / (sum_{T} s + 1e-20)
    h  = h + N(F; w_post_mlp)
    logits = N(h_L; w_f) W_head                                   (untied)

``SwiGLU(x) = (silu(x W_gate) * x W_up) W_down``.  Rotary pairs are
half-split ``(x_i, x_{i + d/2})``.

Departures, each on purpose:

- *The share.*  ``held`` names the routed experts this chip holds; the
  router still scores all ``num_experts`` and normalises ``g`` over all
  of ``T``, held or not.  What the absent experts would have added is
  left out, here and in the program alike; the shared expert is always
  added.  The embedding and the head hold a slice of the vocabulary's
  rows; logits are over the slice.
- *Norm gains are whatever the weights hold.*  "Depth-scaled" sandwich
  norm is an initialisation of the gains, not an operation.

Weights arrive in the dtype they are served in (bfloat16) and each
matrix is raised to float32 where it is used, a piece at a time, so that
a float32 copy of the model never exists.  The layers are
``params["layers"]``, a list, matrices (in, out).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
SLIDING = "sliding_attention"


def _f32(w):
    return jnp.asarray(w).astype(F32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, positions, theta: float):
    """Rotate the last axis of ``x`` (seq, heads, d) at ``positions``
    (seq,): half-split pairs, frequencies ``theta^(-i / (d/2))``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ------------------------------------------------------------- attention
def attention_inputs(a, w, positions, cfg: dict, sliding: bool):
    """The whole sequence's per-head queries, keys and values and the
    output gate, from the normed input ``a`` (seq, hidden)."""
    T, d = a.shape[0], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    q = _rms((a @ _f32(w["wq"])).reshape(T, Hq, d), _f32(w["q_norm"]), eps)
    k = _rms((a @ _f32(w["wk"])).reshape(T, Hkv, d), _f32(w["k_norm"]), eps)
    if sliding:
        q = _rope(q, positions, float(cfg["rope_theta"]))
        k = _rope(k, positions, float(cfg["rope_theta"]))
    return {"q": q, "k": k, "v": (a @ _f32(w["wv"])).reshape(T, Hkv, d),
            "gate": a @ _f32(w["wg"])}


def attention_block(qkv, t0, cfg: dict, q_block: int, window: int):
    """Queries ``t0 .. t0 + q_block`` against the whole sequence under
    the dense mask -> (q_block, Hq * d), before the gate."""
    T, Hkv, d = qkv["k"].shape
    Hq = cfg["num_attention_heads"]
    q = jax.lax.dynamic_slice_in_dim(qkv["q"], t0, q_block, 0)
    i = (t0 + jnp.arange(q_block))[:, None]
    j = jnp.arange(T)[None, :]
    seen = j <= i
    if window:
        seen &= i - j < window
    # query head h reads K/V head h // (Hq / Hkv)
    q = q.reshape(q_block, Hkv, Hq // Hkv, d)
    s = jnp.einsum("tkgd,skd->kgts", q, qkv["k"]) * d ** -0.5
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
    return jnp.einsum("kgts,skd->tkgd", p, qkv["v"]).reshape(q_block, Hq * d)


def gated_output(o, gate, wo):
    return (o * jax.nn.sigmoid(gate)) @ _f32(wo)


def _identity(f):
    return f


def attention(a, w, positions, cfg: dict, sliding: bool, q_block: int,
              wrap=_identity):
    """(seq, hidden) normed input -> (attention's addition to the
    stream before its post-norm (seq, hidden), the heads' output before
    the gate (seq, Hq * d)).  Walked in blocks of ``q_block`` queries
    (it must divide the length) so that the (heads, block, seq) scores
    fit."""
    T = a.shape[0]
    q_block = min(q_block, T)
    if T % q_block:
        raise ValueError(f"q_block {q_block} does not divide {T} tokens")
    qkv = wrap(attention_inputs)(a, w, positions, cfg, sliding)
    window = int(cfg["sliding_window"]) if sliding else 0
    o = jnp.concatenate([
        wrap(attention_block)(qkv, jnp.int32(t0), cfg, q_block, window)
        for t0 in range(0, T, q_block)], 0)
    return wrap(gated_output)(o, qkv["gate"], w["wo"]), o


# ------------------------------------------------------------------- FFN
def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def route(x, router, cfg: dict):
    """(seq, hidden) -> (chosen experts (seq, k) int, their weights
    ``g`` (seq, k)), over ALL routed experts: the bias moves the choice
    only."""
    s = jax.nn.sigmoid(x @ _f32(router["weight"]))
    chosen = jax.lax.top_k(s + _f32(router["bias"]),
                           cfg["num_experts_per_tok"])[1]
    weight = jnp.take_along_axis(s, chosen, axis=1)
    g = cfg["route_scale"] * weight / (
        jnp.sum(weight, -1, keepdims=True) + 1e-20)
    return chosen, g


def _expert_share(x, chosen, g, e, wg, wu, wd):
    """Expert ``e``'s part: its output, weighted where it was chosen."""
    gate = jnp.sum(jnp.where(chosen == e, g, 0.0), -1, keepdims=True)
    return gate * _swiglu(x, wg, wu, wd)


def moe(x, ffn, cfg: dict, held: Sequence[int], wrap=_identity):
    """Shared expert plus this share's part of the routed experts:
    ``ffn['experts']`` stacks the experts ``held`` names, in that
    order."""
    chosen, g = wrap(route)(x, ffn["router"], cfg)
    sh, ex = ffn["shared"], ffn["experts"]
    y = wrap(_swiglu)(x, sh["w_gate"], sh["w_up"], sh["w_down"])
    for i, e in enumerate(held):
        y = y + wrap(_expert_share)(
            x, chosen, g, jnp.int32(e), ex["w_gate"][i], ex["w_up"][i],
            ex["w_down"][i])
    return y


# ----------------------------------------------------------------- model
def layer(x, w, positions, cfg: dict, sliding: bool, held: Sequence[int],
          q_block: int = 256, wrap=_identity):
    """One block on (seq, hidden) float32 -> (x, the heads' attention
    output before the gate).  ``wrap`` is applied to each piece before
    it is called: a caller on a device hands in ``jax.jit`` (``cfg`` and
    the flags static) so that a piece's temporaries are freed before the
    next one runs."""
    eps = cfg["rms_norm_eps"]
    a, o = attention(_rms(x, _f32(w["norm_in"]), eps), w["attn"], positions,
                     cfg, sliding, q_block, wrap)
    x = x + _rms(a, _f32(w["norm_post_attn"]), eps)
    m = _rms(x, _f32(w["norm_pre_mlp"]), eps)
    if "ffn" in w:
        f = moe(m, w["ffn"], cfg, held, wrap)
    else:
        f = wrap(_swiglu)(m, w["mlp"]["w_gate"], w["mlp"]["w_up"],
                          w["mlp"]["w_down"])
    return x + _rms(f, _f32(w["norm_post_mlp"]), eps), o


def forward(params: Dict[str, Any], tokens, cfg: dict, held: Sequence[int],
            *, positions: Sequence[int], q_block: int = 256,
            wrap=_identity, token_positions=None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(logits at ``positions`` over the rows the head holds (n, vocab),
    per layer the heads' attention output before the gate at those
    positions (layers, n, Hq * d)).  ``cfg`` holds the configuration's
    keys as the cell's file gives them (``num_hidden_layers``,
    ``num_dense_layers`` and ``layer_types`` as held).
    ``token_positions`` (seq,) default ``0 .. seq - 1``: what RoPE
    rotates by (the causal order is the sequence's own)."""
    at = jnp.asarray(list(positions))
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"]["weight"][jnp.asarray(tokens)])
        if cfg.get("mup_enabled", False):
            x = x * cfg["hidden_size"] ** 0.5
        where = (jnp.arange(x.shape[0]) if token_positions is None
                 else jnp.asarray(token_positions))
        kept = []
        for i in range(cfg["num_hidden_layers"]):
            x, o = layer(x, params["layers"][i], where, cfg,
                         cfg["layer_types"][i] == SLIDING, held, q_block,
                         wrap)
            kept.append(o[at])
        x = _rms(x[at], _f32(params["final_norm"]["weight"]),
                 cfg["rms_norm_eps"])
        return x @ _f32(params["head"]["weight"]), jnp.stack(kept)


class Config(dict):
    """The keys the functions above read; hashable by identity, so that
    a caller can hand it to ``jax.jit`` as a static argument."""

    __hash__ = object.__hash__
    __eq__ = object.__eq__


#: which positional arguments of a piece are not arrays (for a caller's
#: ``wrap=lambda f: jax.jit(f, static_argnums=STATIC_ARGNUMS.get(...))``)
STATIC_ARGNUMS = {"attention_inputs": (3, 4), "attention_block": (2, 3, 4),
                  "route": (2,)}


def from_hf(config: dict) -> Config:
    """From a configuration file of the benchmark (the published keys)."""
    keys = ("hidden_size", "head_dim", "num_attention_heads",
            "num_key_value_heads", "num_hidden_layers", "num_dense_layers",
            "layer_types", "sliding_window", "num_experts_per_tok",
            "route_scale", "rms_norm_eps", "rope_theta", "mup_enabled")
    return Config({k: config[k] for k in keys})
