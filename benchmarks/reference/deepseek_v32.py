"""The plain reference: DeepSeek-V3.2's forward pass, for one chip's
share of an expert-parallel deployment.

Written from the published description (DeepSeek-V3 technical report,
arXiv:2412.19437, sections 2.1.1-2.1.2; the DeepSeek-V3.2-Exp report's
"DeepSeek Sparse Attention"; the public ``config.json`` of
``deepseek-ai/DeepSeek-V3.2``).  Plain ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
paging, no sharding, expanded attention only, one full forward over the
whole sequence, and nothing imported from the program.

Per layer (pre-norm residual, RMSNorm, no biases in the projections)::

    x += Attn(rms(x)),  x += FFN(rms(x))

**Attention (MLA).**  ``c_q = rms(x W_qa)``; ``q = c_q W_qb`` gives each
head ``[q_nope (128) | q_rope (64)]``.  ``[c_kv | k_r] = x W_kva``;
``c_kv = rms(c_kv)`` (512), ``k_r = rope(k_r)`` (64) is ONE key shared
by all heads.  ``c_kv W_kvb`` gives each head ``[k_nope (128) | v
(128)]``.  ``o_h = softmax(scale * (q_nope k_nope^T + q_rope k_r^T) +
mask) v_h``; output ``concat(o_h) W_o``.  ``scale = 192^-0.5 * m^2``
with YaRN's ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; the rotary
frequencies are YaRN's blend (``yarn_inv_freq``).

**Lightning indexer.**  ``q_I = c_q W_Iq`` (64 heads x 128, the first
64 of each rotated), ``k_I = LayerNorm(x W_Ik)`` (128, one per token,
first 64 rotated), ``w = x W_Iw * 64^-0.5``.  Index score of query t on
token s <= t: ``I_ts = sum_j w_tj relu(q_I,tj . k_I,s) * 128^-0.5``.
``S_t`` is the ``min(index_topk, t + 1)`` tokens of largest ``I_ts``,
ties to the lower position; the attention mask is 0 on ``S_t`` and
-inf elsewhere.

**FFN.**  Dense layers: ``(silu(x W_g) * x W_u) W_d``.  Expert layers:
``s = sigmoid(x W_r)``; ``c = s + b``; a group's score is the sum of
its two largest ``c``; the ``topk_group`` best of ``n_group`` groups
are kept; ``T`` = the ``num_experts_per_tok`` largest ``c`` inside
them; ``g_e = routed_scaling_factor * s_e / (sum_{e' in T} s_e' +
1e-20)``; ``y = Shared(x) + sum_{e in T and HELD} g_e Expert_e(x)``.

Departures from the published model, each on purpose:

- *The share.*  ``held`` names the routed experts this chip holds; the
  router still scores all ``n_routed_experts`` and normalises ``g``
  over all of ``T``, held or not.  What the absent experts would have
  added is left out, here and in the program alike.  The embedding and
  the head hold a slice of the vocabulary's rows; logits are over the
  slice.
- *The indexer runs in the precision of everything else.*  The
  published code rotates ``q_I`` and ``k_I`` by a Hadamard matrix and
  quantises them to FP8; the rotation is orthogonal and leaves
  ``q . k`` unchanged, so both are left out.
- *Multi-token prediction is not held.*  The module is a training
  objective and an optional draft head; the main model's logits do not
  depend on it.
- *Rotary pair layout.*  Half-split pairs ``(x_i, x_{i + d/2})`` in
  MLA and in the indexer.  The published MLA code pairs ``(x_{2i},
  x_{2i+1})``; the two differ by a fixed permutation of the rotary
  columns of ``W_qb`` and ``W_kva``, which seeded weights cannot tell
  apart.

Weights arrive in the dtype they are served in (bfloat16) and each
matrix is raised to float32 where it is used, one layer at a time, so
that a float32 copy of the model never exists.  ``LayerWeights`` is
the one place that knows how the program lays its leaves out.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ---------------------------------------------------------------- rotary
def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies under YaRN:
    ``theta_i`` below the correction dimension of ``beta_fast``,
    ``theta_i / factor`` above that of ``beta_slow``, a linear ramp
    between."""
    d, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    theta = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def correction_dim(rotations: float) -> float:
        return d * math.log(rs["original_max_position_embeddings"]
                            / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return theta * (1.0 - ramp) + theta / rs["factor"] * ramp


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, positions, inv_freq):
    """Rotate the last axis of ``x`` (..., seq, [heads,] d) at
    ``positions`` (seq,): half-split pairs."""
    angles = positions.astype(F32)[:, None] * jnp.asarray(inv_freq, F32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if x.ndim == 3:                                 # (seq, heads, d)
        cos, sin = cos[:, None], sin[:, None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ----------------------------------------------------------------- norms
def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _f32(w):
    return jnp.asarray(w).astype(F32)


# ------------------------------------------------------------- selection
def select(scores, k: int):
    """(rows, S) scores with -inf where a token may not be chosen ->
    boolean mask of each row's ``k`` largest, ties to the lower
    position (a stable sort)."""
    order = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]
    rows = jnp.arange(scores.shape[0])[:, None]
    chosen = jnp.zeros(scores.shape, bool).at[rows, order].set(True)
    return chosen & (scores > -jnp.inf)


# ------------------------------------------------------------- attention
def attention_inputs(x, w, cfg: dict):
    """Everything attention needs of the whole sequence: per-head
    queries, keys and values (the EXPANDED form), the index queries,
    their weights and the index keys."""
    T = x.shape[0]
    H = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    dc, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    Hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    pos = jnp.arange(T)
    inv_freq = yarn_inv_freq(cfg)

    c_q = _rms(x @ _f32(w["wq_a"]), _f32(w["q_norm"]), eps)
    q = (c_q @ _f32(w["wq_b"])).reshape(T, H, dn + dr)
    kv = x @ _f32(w["wkv_a"])
    c_kv = _rms(kv[:, :dc], _f32(w["kv_norm"]), eps)
    kvb = (c_kv @ _f32(w["wkv_b"])).reshape(T, H, dn + dv)

    q_i = (c_q @ _f32(w["idx_wq"])).reshape(T, Hi, di)
    k_i = _layer_norm(x @ _f32(w["idx_wk"]), _f32(w["idx_knorm_w"]),
                      _f32(w["idx_knorm_b"]), eps)
    return {
        "q_nope": q[..., :dn], "q_rope": _rope(q[..., dn:], pos, inv_freq),
        "k_nope": kvb[..., :dn], "k_rope": _rope(kv[:, dc:], pos, inv_freq),
        "v": kvb[..., dn:],
        "q_i": jnp.concatenate(
            [_rope(q_i[..., :dr], pos, inv_freq), q_i[..., dr:]], -1),
        "k_i": jnp.concatenate(
            [_rope(k_i[:, :dr], pos, inv_freq), k_i[:, dr:]], -1),
        "w_i": x @ _f32(w["idx_ww"]) * Hi ** -0.5 * di ** -0.5,
    }


def attention_block(a, t0, cfg: dict, q_block: int):
    """Queries ``t0 .. t0 + q_block`` against the whole sequence ->
    (per-head outputs (q_block, H, dv), their selection masks (q_block,
    seq) bool)."""
    T = a["k_i"].shape[0]
    cut = lambda t: jax.lax.dynamic_slice_in_dim(t, t0, q_block, 0)
    pos = jnp.arange(T)
    causal = pos[None, :] <= (t0 + jnp.arange(q_block))[:, None]
    index = jnp.einsum("thd,sd->ths", cut(a["q_i"]), a["k_i"])
    index = jnp.sum(jax.nn.relu(index) * cut(a["w_i"])[:, :, None], axis=1)
    chosen = select(jnp.where(causal, index, -jnp.inf), cfg["index_topk"])
    scores = (jnp.einsum("thd,shd->hts", cut(a["q_nope"]), a["k_nope"])
              + jnp.einsum("thd,sd->hts", cut(a["q_rope"]), a["k_rope"])
              ) * softmax_scale(cfg)
    probs = jax.nn.softmax(jnp.where(chosen[None], scores, -jnp.inf), -1)
    return jnp.einsum("hts,shd->thd", probs, a["v"]), chosen


def attention(x, w, cfg: dict, q_block: int = 256, wrap=lambda f: f,
              last: int = 1):
    """(seq, hidden) float32 -> (attention output, selection masks of
    the ``last`` final positions (last, seq) bool; at most a block of
    them).  The sequence is walked in blocks of ``q_block`` queries (it
    must divide the length), so that the (heads, block, seq) scores
    fit."""
    T = x.shape[0]
    q_block = min(q_block, T)
    if T % q_block:
        raise ValueError(f"q_block {q_block} does not divide {T} tokens")
    a = wrap(attention_inputs)(x, w, cfg)
    out, chosen = [], None
    for t0 in range(0, T, q_block):
        o, chosen = wrap(attention_block)(a, jnp.int32(t0), cfg, q_block)
        out.append(o)
    o = jnp.concatenate(out, 0).reshape(T, -1)
    return o @ _f32(w["wo"]), chosen[-last:]


# ------------------------------------------------------------------- FFN
def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ _f32(wg)) * (x @ _f32(wu))) @ _f32(wd)


def route(x, w, cfg: dict):
    """(seq, hidden) -> (chosen experts (seq, k) int, their weights
    ``g`` (seq, k)), over ALL routed experts."""
    E, k = w["router_w"].shape[-1], cfg["num_experts_per_tok"]
    G, keep = cfg["n_group"], cfg["topk_group"]
    s = jax.nn.sigmoid(x @ _f32(w["router_w"]))
    c = s + _f32(w["router_b"])
    grouped = c.reshape(-1, G, E // G)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    kept = jax.lax.top_k(group_score, keep)[1]                  # (seq, keep)
    group_ok = jnp.any(kept[:, :, None] == jnp.arange(G)[None, None], 1)
    c = jnp.where(jnp.repeat(group_ok, E // G, axis=1), c, -jnp.inf)
    chosen = jax.lax.top_k(c, k)[1]
    weight = jnp.take_along_axis(s, chosen, axis=1)
    g = cfg["routed_scaling_factor"] * weight / (
        jnp.sum(weight, -1, keepdims=True) + 1e-20)
    return chosen, g


def _expert_share(x, chosen, g, e, wg, wu, wd):
    """Expert ``e``'s part: its output, weighted where it was chosen."""
    gate = jnp.sum(jnp.where(chosen == e, g, 0.0), -1, keepdims=True)
    return gate * _swiglu(x, wg, wu, wd)


def _identity(f):
    return f


def moe(x, w, cfg: dict, held: Sequence[int], wrap=_identity):
    """Shared expert plus this share's part of the routed experts:
    ``w['experts_*']`` hold the experts ``held`` names, in that order."""
    chosen, g = wrap(route)(
        x, {k: w[k] for k in ("router_w", "router_b")}, cfg)
    y = wrap(_swiglu)(x, w["shared_gate"], w["shared_up"], w["shared_down"])
    for i, e in enumerate(held):
        y = y + wrap(_expert_share)(
            x, chosen, g, jnp.int32(e), w["experts_gate"][i],
            w["experts_up"][i], w["experts_down"][i])
    return y


# ----------------------------------------------------------------- model
def layer(x, w, cfg: dict, held: Sequence[int], q_block: int = 256,
          wrap=_identity, last: int = 1):
    """One block on (seq, hidden) float32; ``w`` is a dense layer's or
    an expert layer's weights (it has ``router_w`` or not).  ``wrap`` is
    applied to each piece before it is called: a caller on a device
    hands in ``jax.jit`` (with ``cfg`` bound) so that a piece's
    temporaries are freed before the next one runs."""
    eps = cfg["rms_norm_eps"]
    a, selected = attention(
        _rms(x, _f32(w["norm1"]), eps),
        w.only(*ATTENTION_WEIGHTS) if hasattr(w, "only") else w, cfg,
        q_block, wrap, last)
    x = x + a
    h = _rms(x, _f32(w["norm2"]), eps)
    if "router_w" in w:
        return x + moe(h, w, cfg, held, wrap), selected
    return x + wrap(_swiglu)(
        h, w["mlp_gate"], w["mlp_up"], w["mlp_down"]), selected


class LayerWeights:
    """Layer ``i``'s weights out of the program's leaves, each sliced
    out only when it is asked for: the leading dense layers are stacked
    under ``params['dense']``, the expert layers under ``params['moe']``;
    matrices are (in, out)."""

    NAMES = {
        "mlp_gate": ("mlp", "w_gate"), "mlp_up": ("mlp", "w_up"),
        "mlp_down": ("mlp", "w_down"),
        "router_w": ("ffn", "router", "weight"),
        "router_b": ("ffn", "router", "bias"),
        "shared_gate": ("ffn", "shared", "w_gate"),
        "shared_up": ("ffn", "shared", "w_up"),
        "shared_down": ("ffn", "shared", "w_down"),
        "experts_gate": ("ffn", "experts", "w_gate"),
        "experts_up": ("ffn", "experts", "w_up"),
        "experts_down": ("ffn", "experts", "w_down"),
        "norm1": ("norm1",), "norm2": ("norm2",),
    }

    def __init__(self, params: Dict[str, Any], i: int, n_dense: int):
        self.stack, self.j = ((params["dense"], i) if i < n_dense
                              else (params["moe"], i - n_dense))

    def _path(self, name: str):
        return self.NAMES.get(name, ("attn", name))

    def __contains__(self, name: str) -> bool:
        node = self.stack
        for key in self._path(name):
            if key not in node:
                return False
            node = node[key]
        return True

    def __getitem__(self, name: str):
        node = self.stack
        for key in self._path(name):
            node = node[key]
        return node[self.j]

    def only(self, *names: str) -> Dict[str, Any]:
        """A plain dict of the named leaves (what a jitted piece is
        handed)."""
        return {n: self[n] for n in names}


ATTENTION_WEIGHTS = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b",
                     "wo", "idx_wq", "idx_wk", "idx_knorm_w", "idx_knorm_b",
                     "idx_ww")


def forward(params: Dict[str, Any], tokens, cfg: dict, held: Sequence[int],
            *, positions: Sequence[int], q_block: int = 256,
            wrap=_identity, last: int = 1
            ) -> Tuple[jnp.ndarray, List[jnp.ndarray]]:
    """(logits at ``positions`` over the rows the head holds, per layer
    the selection masks of the ``last`` final positions (last, seq)
    bool).  ``cfg`` holds the
    configuration's keys as the cell's file gives them
    (``num_hidden_layers`` and ``first_k_dense_replace`` as held)."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"]["weight"][jnp.asarray(tokens)])
        selections = []
        for i in range(cfg["num_hidden_layers"]):
            w = LayerWeights(params, i, cfg["first_k_dense_replace"])
            x, selected = layer(x, w, cfg, held, q_block, wrap, last)
            selections.append(selected)
        x = _rms(x[jnp.asarray(list(positions))],
                 _f32(params["final_norm"]["weight"]), cfg["rms_norm_eps"])
        return x @ _f32(params["head"]["weight"]), selections


class Config(dict):
    """The keys the functions above read; hashable by identity, so that
    a caller can hand it to ``jax.jit`` as a static argument."""

    __hash__ = object.__hash__
    __eq__ = object.__eq__


#: which positional arguments of a piece are not arrays (for a caller's
#: ``wrap=lambda f: jax.jit(f, static_argnums=STATIC_ARGNUMS.get(...))``)
STATIC_ARGNUMS = {"attention_inputs": (2,), "attention_block": (2, 3),
                  "route": (2,)}


def from_hf(config: dict) -> Config:
    """From a configuration file of the benchmark (the published keys,
    ``rope_scaling`` nested)."""
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
            "num_hidden_layers", "first_k_dense_replace", "n_group",
            "topk_group", "num_experts_per_tok", "routed_scaling_factor",
            "rms_norm_eps", "rope_theta", "rope_scaling")
    return Config({k: config[k] for k in keys})
