"""The plain reference: Xing4.0's forward pass (``model_type`` ``xing4_0``,
``XingChen-AGI/Xing4.0-29B-A4B``), every routed expert held.

Written from the equations: latent attention, the ``noaux_tc`` router,
the shared expert and YaRN are DeepSeek-V3's (arXiv:2412.19437, sections
2.1.1-2.1.2) and are taken from ``reference/deepseek_v32.py`` where that
file has them without its indexer; the residual path is
manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606).  Plain ``jax.numpy`` in float32
under ``jax.default_matmul_precision("highest")``: no kernels, no cache,
no paging, no batching, one full forward over the whole sequence, and
nothing imported from the program.

The state of a token is ``X`` (n, C), ``n = hc_mult`` streams of ``C =
hidden_size``; ``X_0`` is the token's embedding in every stream.  Each
layer applies the wrapper twice, first with ``F`` = attention, then with
``F`` = FFN, each ``F`` with its own pre-RMSNorm::

    x~     = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)        (1, nC)
    H~pre  = a_pre  (x~ phi_pre)  + b_pre                        (n)
    H~post = a_post (x~ phi_post) + b_post                       (n)
    H~res  = a_res  mat(x~ phi_res) + b_res                      (n, n)
    H_pre = sigmoid(H~pre)      H_post = 2 sigmoid(H~post)
    M_0 = exp(clip(H~res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    M_t = rows(cols(M_{t-1})),  t = 1 .. hc_sinkhorn_iters
    X <- M_iters X + H_post^T F(H_pre X)

``cols`` divides every column by its sum + ``hc_eps``, ``rows`` every row
by its sum + ``hc_eps``.  After the last layer the streams are summed,
then ``final_norm`` and the head.

**Attention** is ``deepseek_v32``'s MLA with a causal mask over the
WHOLE context (no indexer, no selection).  **FFN**: the leading
``first_k_dense_replace`` layers are SwiGLU; the others are the shared
expert plus the ``num_experts_per_tok`` routed experts the ``noaux_tc``
router chooses, ``n_group`` 1.

Departures from the published model, each on purpose (the configuration
file's ``assumed`` says the same):

- *What ``config.json`` does not fix.*  The published ``modeling`` code
  is not in the repository and nobody is to fetch it; the keys name the
  mechanism (``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
  ``mhc_h_res_clamp_min/max``) and the papers give its form.  Taken from
  the papers: the mapping reads the RMS-normalised flattened streams
  (mHC eq. 5-7; the norm's weight folded into ``phi``), ``H_pre`` a
  sigmoid and ``H_post`` twice a sigmoid (mHC section 4.2), ``H_res``
  the Sinkhorn-Knopp projection of ``exp(.)`` (section 4.2), the
  embedding copied into every stream and the streams summed at the end
  (hyper-connections section 3).  Assumed: ``hc_eps`` is added to each
  sum the iteration divides by; the clamp acts on ``H~res`` before the
  exponential; an iteration normalises columns, then rows.
- *Multi-token prediction is not held*: the served logits do not depend
  on it.
- *Rotary pair layout*: half-split pairs, as ``deepseek_v32.py`` says.

Weights arrive in the dtype they are served in and each matrix is raised
to float32 where it is used, one piece at a time.  ``LayerWeights`` is
the one place that knows how the program lays its leaves out (the
mapping's ``phi`` is stored per stream, output index first).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from reference import deepseek_v32 as base
from reference.deepseek_v32 import (
    Config, _f32, _identity, _rms, _rope, _swiglu, moe, softmax_scale,
    yarn_inv_freq,
)


# ------------------------------------------------------ hyper-connections
def mapping(X, w, cfg: dict):
    """``X`` (seq, n, C) -> (H_pre (seq, n), H_post (seq, n), H_res
    (seq, n, n)).  ``w['phi']`` (nC, n (n + 2)) is ``[phi_pre | phi_post
    | phi_res]``, ``w['alpha']`` (3,), ``w['bias']`` (n (n + 2),)."""
    T, n, C = X.shape
    x = X.reshape(T, n * C)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    t = x @ _f32(w["phi"])
    a, b = _f32(w["alpha"]), _f32(w["bias"])
    h_pre = jax.nn.sigmoid(a[0] * t[:, :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * t[:, n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(
        a[2] * t[:, 2 * n:].reshape(T, n, n) + b[2 * n:].reshape(n, n),
        cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        M = M / (jnp.sum(M, axis=1, keepdims=True) + cfg["hc_eps"])
        M = M / (jnp.sum(M, axis=2, keepdims=True) + cfg["hc_eps"])
    return h_pre, h_post, M


def read(X, h_pre):
    """``H_pre X``: (seq, n, C), (seq, n) -> (seq, C)."""
    return jnp.einsum("tn,tnc->tc", h_pre, X)


def mix(X, h_res, h_post, y):
    """``H_res X + H_post^T y`` -> (seq, n, C)."""
    return jnp.einsum("tij,tjc->tic", h_res, X) \
        + h_post[:, :, None] * y[:, None, :]


def wrapped(X, w, cfg: dict, F, wrap=_identity):
    """One wrapper around the sub-layer ``F`` ((seq, C) -> (seq, C))."""
    h_pre, h_post, h_res = wrap(mapping)(X, w, cfg)
    return wrap(mix)(X, h_res, h_post, F(wrap(read)(X, h_pre)))


# ------------------------------------------------------------- attention
def attention_inputs(x, w, cfg: dict):
    """Per-head queries, keys and values of the whole sequence (the
    expanded form)."""
    T = x.shape[0]
    H = cfg["num_attention_heads"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    dc, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(T)
    inv_freq = yarn_inv_freq(cfg)
    c_q = _rms(x @ _f32(w["wq_a"]), _f32(w["q_norm"]), eps)
    q = (c_q @ _f32(w["wq_b"])).reshape(T, H, -1)
    kv = x @ _f32(w["wkv_a"])
    kvb = (_rms(kv[:, :dc], _f32(w["kv_norm"]), eps)
           @ _f32(w["wkv_b"])).reshape(T, H, dn + dv)
    return {"q_nope": q[..., :dn], "q_rope": _rope(q[..., dn:], pos, inv_freq),
            "k_nope": kvb[..., :dn], "k_rope": _rope(kv[:, dc:], pos, inv_freq),
            "v": kvb[..., dn:]}


def attention_block(a, t0, cfg: dict, q_block: int):
    """Queries ``t0 .. t0 + q_block`` against every token not in their
    future -> per-head outputs (q_block, H, dv)."""
    T = a["v"].shape[0]
    cut = lambda t: jax.lax.dynamic_slice_in_dim(t, t0, q_block, 0)
    causal = jnp.arange(T)[None, :] <= (t0 + jnp.arange(q_block))[:, None]
    scores = (jnp.einsum("thd,shd->hts", cut(a["q_nope"]), a["k_nope"])
              + jnp.einsum("thd,sd->hts", cut(a["q_rope"]), a["k_rope"])
              ) * softmax_scale(cfg)
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    return jnp.einsum("hts,shd->thd", probs, a["v"])


def attention(x, w, cfg: dict, q_block: int = 256, wrap=_identity):
    """(seq, hidden) float32 -> attention output, the sequence walked in
    blocks of ``q_block`` queries (it must divide the length)."""
    T = x.shape[0]
    q_block = min(q_block, T)
    if T % q_block:
        raise ValueError(f"q_block {q_block} does not divide {T} tokens")
    a = wrap(attention_inputs)(x, w, cfg)
    o = jnp.concatenate([
        wrap(attention_block)(a, jnp.int32(t0), cfg, q_block)
        for t0 in range(0, T, q_block)], 0)
    return wrap(_project_out)(o.reshape(T, -1), w["wo"])


def _project_out(o, wo):
    return o @ _f32(wo)


# ----------------------------------------------------------------- model
class LayerWeights(base.LayerWeights):
    """``deepseek_v32.LayerWeights`` plus the two wrappers' mappings:
    the program stores ``phi`` as (n, n (n + 2), C), stream ``i``'s rows
    of ``[phi_pre | phi_post | phi_res]`` transposed."""

    def mapping(self, which: str) -> Dict[str, Any]:
        hc = {k: v[self.j] for k, v in self.stack[which].items()}
        n, K, C = hc["phi"].shape
        return dict(hc, phi=jnp.transpose(hc["phi"], (0, 2, 1)
                                          ).reshape(n * C, K))


def layer(X, w, cfg: dict, held: Sequence[int], q_block: int = 256,
          wrap=_identity):
    """One block on the streams (seq, n, C) float32."""
    eps = cfg["rms_norm_eps"]
    X = wrapped(X, w.mapping("hc_attn"), cfg, lambda h: attention(
        _rms(h, _f32(w["norm1"]), eps), w.only(*ATTENTION_WEIGHTS), cfg,
        q_block, wrap), wrap)
    if "router_w" in w:
        ffn = lambda h: moe(_rms(h, _f32(w["norm2"]), eps), w, cfg, held,
                            wrap)
    else:
        ffn = lambda h: wrap(_swiglu)(
            _rms(h, _f32(w["norm2"]), eps), w["mlp_gate"], w["mlp_up"],
            w["mlp_down"])
    return wrapped(X, w.mapping("hc_ffn"), cfg, ffn, wrap)


ATTENTION_WEIGHTS = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b",
                     "wo")


def forward(params: Dict[str, Any], tokens, cfg: dict, *,
            positions: Sequence[int], q_block: int = 256, wrap=_identity):
    """Logits at ``positions``, float32.  ``cfg`` holds the
    configuration's keys as the cell's file gives them."""
    held = tuple(range(cfg["n_routed_experts"]))
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"]["weight"][jnp.asarray(tokens)])
        X = jnp.broadcast_to(x[:, None], (x.shape[0], cfg["hc_mult"],
                                          x.shape[1]))
        for i in range(cfg["num_hidden_layers"]):
            X = layer(X, LayerWeights(params, i, cfg["first_k_dense_replace"]),
                      cfg, held, q_block, wrap)
        x = _rms(jnp.sum(X, axis=1)[jnp.asarray(list(positions))],
                 _f32(params["final_norm"]["weight"]), cfg["rms_norm_eps"])
        return x @ _f32(params["head"]["weight"])


#: which positional arguments of a piece are not arrays
STATIC_ARGNUMS = {"attention_inputs": (2,), "attention_block": (2, 3),
                  "route": (2,), "mapping": (2,)}


def from_hf(config: dict) -> Config:
    """From a configuration file of the benchmark."""
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "num_hidden_layers", "first_k_dense_replace",
            "n_group", "topk_group", "n_routed_experts",
            "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps",
            "rope_theta", "rope_scaling", "hc_mult", "hc_sinkhorn_iters",
            "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
    return Config({k: config[k] for k in keys})
