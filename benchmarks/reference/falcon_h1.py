"""The plain reference: the forward pass of Falcon-H1 (``falcon_h1``).

Written from the public ``config.json`` of
``tiiuae/Falcon-H1-34B-Instruct`` and the published ``falcon_h1``
modelling code of ``transformers`` (its ``torch_forward`` path).  Plain
``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``: a dense causal mask, no kernels, no cache, no paging, no
batching, one full forward over the whole sequence, and nothing imported
from the program.  The state-space recurrence runs TOKEN BY TOKEN
(``lax.scan``), not in the chunked form the program uses.

``x0 = E[token] * embedding_multiplier``.  Every layer is the same
parallel hybrid (``N`` RMSNorm with ``rms_norm_eps``)::

    u  = N(h; w_in)
    h  = h + ssm_out_multiplier * Mamba2(u)
           + attention_out_multiplier * Attn(attention_in_multiplier * u)
    h  = h + MLP(N(h; w_mlp))
    logits = lm_head_multiplier * N(h_L; w_f) W_head          (untied)

``Attn``: q = a W_q (Hq heads), k = key_multiplier * a W_k, v = a W_v
(Hkv heads), half-split RoPE on q and k at ``rope_theta``, softmax of
``q.k / sqrt(d)`` over j <= i, query head i reads K/V head i // (Hq /
Hkv), then W_o.

``Mamba2(u)``, ``H`` heads of ``P`` channels, state width ``N``, ``G``
groups of B and C, a depthwise causal convolution of width ``K``::

    z | xBC | dt = (ssm_in_multiplier * u W_in) * mup
        (mup: ssm_multipliers[0..4] over z, x, B, C, dt)
    xBC = silu(conv(xBC) + conv_bias)          (window K, zeros before t=0)
    x | B | C = xBC                            (H P, G N, G N)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t + D x_t
    y = GroupRMSNorm(y * silu(z); w_norm, G groups)     (norm after the gate)
    Mamba2(u) = y W_out

``MLP(m) = mlp_multipliers[1] * (m W_up * silu(mlp_multipliers[0] * m
W_gate)) W_down``.

Departures, each on purpose:

- The published code clamps ``dt`` to ``time_step_limit = (0, inf)``:
  a softplus is never below 0, so no clamp is written.
- Nothing is dropped for padding: the sequence is one prompt, whole.

Weights arrive in the dtype they are served in (bfloat16) and each
matrix is raised to float32 where it is used, so that a float32 copy of
the model never exists; the head is applied a block of its columns at a
time.  ``params["layers"]`` is a list, matrices (in, out).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(w):
    return jnp.asarray(w).astype(F32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, positions, theta: float):
    """Rotate the last axis of ``x`` (seq, heads, d): half-split pairs,
    frequencies ``theta^(-i / (d/2))``."""
    half = x.shape[-1] // 2
    inv_freq = float(theta) ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mup_vector(cfg: dict):
    """The published ``compute_mup_vector``: one multiplier a column of
    the input projection, z | x | B | C | dt."""
    d_ssm = cfg["mamba_d_ssm"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    widths = (d_ssm, d_ssm, gn, gn, cfg["mamba_n_heads"])
    return jnp.concatenate([jnp.full((n,), m, F32) for n, m in zip(
        widths, cfg["ssm_multipliers"])])


def attention(u, w, positions, cfg: dict):
    """The attention branch of a layer, before its output multiplier."""
    T, d = u.shape[0], cfg["head_dim"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a = u * cfg["attention_in_multiplier"]
    q = (a @ _f32(w["wq"])).reshape(T, Hq, d)
    k = (a @ _f32(w["wk"])).reshape(T, Hkv, d) * cfg["key_multiplier"]
    v = (a @ _f32(w["wv"])).reshape(T, Hkv, d)
    q = _rope(q, positions, cfg["rope_theta"])
    k = _rope(k, positions, cfg["rope_theta"])
    k, v = (jnp.repeat(t, Hq // Hkv, axis=1) for t in (k, v))
    s = jnp.einsum("ihd,jhd->hij", q, k) / d ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hij,jhd->ihd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(T, Hq * d) @ _f32(w["wo"])


def recurrence(x, dt, A, B, C, D, state0, state_at: int):
    """Token by token: ``x`` (T, H, P), ``dt`` (T, H), ``B``/``C`` (T, H,
    N) (each head's group already picked) -> (y (T, H, P), the state
    after the last token, the state after the first ``state_at``
    tokens (the initial one where that is 0))."""

    def step(carry, inputs):
        s, kept, t = carry
        xt, dtt, bt, ct = inputs
        s = (jnp.exp(dtt * A)[:, None, None] * s
             + (dtt[:, None] * xt)[..., None] * bt[:, None, :])
        kept = jnp.where(t + 1 == state_at, s, kept)
        y = jnp.sum(s * ct[:, None, :], -1) + D[:, None] * xt
        return (s, kept, t + 1), y

    (final, kept, _), y = jax.lax.scan(
        step, (state0, state0, jnp.int32(0)), (x, dt, B, C))
    return y, final, kept


def mamba(u, w, cfg: dict, state_at: int = 0):
    """The state-space branch of a layer, before its output multiplier:
    (its output (T, hidden), the final SSM state (H, P, N), the state
    after ``state_at`` tokens)."""
    T = u.shape[0]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N, K = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    d_ssm = cfg["mamba_d_ssm"]
    proj = ((u * cfg["ssm_in_multiplier"]) @ _f32(w["in_proj"])) \
        * mup_vector(cfg)
    z, xbc, dt = jnp.split(proj, [d_ssm, 2 * d_ssm + 2 * G * N], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    cw = _f32(w["conv_w"])
    conv = sum(cw[k] * padded[k:k + T] for k in range(K)) + _f32(w["conv_b"])
    xbc = jax.nn.silu(conv)
    x, B, C = jnp.split(xbc, [d_ssm, d_ssm + G * N], axis=-1)
    by_head = lambda t: jnp.repeat(t.reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + _f32(w["dt_bias"]))
    y, final, kept = recurrence(
        x.reshape(T, H, P), dt, -jnp.exp(_f32(w["A_log"])), by_head(B),
        by_head(C), _f32(w["D"]), jnp.zeros((H, P, N), F32), state_at)
    g = (y.reshape(T, H * P) * jax.nn.silu(z)).reshape(T, G, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    g = g.reshape(T, H * P) * _f32(w["norm"])
    return g @ _f32(w["out_proj"]), final, kept


def mlp(m, w, cfg: dict):
    gate_m, down_m = cfg["mlp_multipliers"]
    y = (m @ _f32(w["w_up"])) * jax.nn.silu(gate_m * (m @ _f32(w["w_gate"])))
    return (y @ _f32(w["w_down"])) * down_m


def layer(x, w, positions, cfg: dict, state_at: int = 0, ssm: bool = True):
    """One layer: (x after it, per branch what it added to the residual
    (attention, state space), the final SSM state, the state after
    ``state_at`` tokens).  ``ssm=False`` leaves the state-space branch
    out (a control of how much it moves the logits)."""
    eps = cfg["rms_norm_eps"]
    u = _rms(x, _f32(w["norm_in"]), eps)
    attn = attention(u, w["attn"], positions, cfg) \
        * cfg["attention_out_multiplier"]
    out, final, kept = mamba(u, w["ssm"], cfg, state_at)
    out = out * cfg["ssm_out_multiplier"]
    x = x + attn + (out if ssm else 0.0)
    x = x + mlp(_rms(x, _f32(w["norm_mlp"]), eps), w["mlp"], cfg)
    return x, (attn, out), final, kept


def _identity(f):
    return f


def logits(params: Dict[str, Any], x, cfg: dict, block: int = 0):
    """The head over the final-normed ``x`` (n, hidden), ``block``
    columns of it at a time (0: all at once)."""
    x = _rms(x, _f32(params["final_norm"]["weight"]), cfg["rms_norm_eps"])
    head = params["head"]["weight"]
    V = head.shape[1]
    block = block or V
    return jnp.concatenate([
        x @ _f32(head[:, i:i + block]) for i in range(0, V, block)],
        axis=-1) * cfg["lm_head_multiplier"]


def forward(params: Dict[str, Any], tokens, cfg: dict, *,
            positions: Optional[Sequence[int]] = None, state_at: int = 0,
            head_block: int = 0, ssm: bool = True, wrap=_identity
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(logits at ``positions`` (default all) (n, vocab), per layer the
    final SSM state (layers, H, P, N), per layer the state after the
    first ``state_at`` tokens).  ``wrap`` may jit the per-layer piece
    (``wrap(layer)``; its static arguments are ``STATIC_ARGNUMS``)."""
    T = len(tokens)
    at = jnp.arange(T) if positions is None else jnp.asarray(list(positions))
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embedding"]["weight"][jnp.asarray(tokens)]) \
            * cfg["embedding_multiplier"]
        where = jnp.arange(T, dtype=jnp.int32)
        finals, kept = [], []
        for w in params["layers"]:
            x, _, final, k = wrap(layer)(x, w, where, cfg, state_at, ssm)
            finals.append(final)
            kept.append(k)
        out = wrap(logits)(params, x[at], cfg, head_block)
    return out, jnp.stack(finals), jnp.stack(kept)


class Config(dict):
    """The keys the functions above read; hashable by identity, so that
    a caller can hand it to ``jax.jit`` as a static argument."""

    __hash__ = object.__hash__
    __eq__ = object.__eq__


#: which positional arguments of a piece are not arrays
STATIC_ARGNUMS = {"layer": (3, 4, 5), "logits": (2, 3)}

KEYS = ("hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "num_hidden_layers", "rms_norm_eps",
        "rope_theta", "embedding_multiplier", "lm_head_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
        "ssm_multipliers", "mlp_multipliers", "mamba_d_ssm", "mamba_n_heads",
        "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv")


def from_hf(config: dict) -> Config:
    """From a configuration file of the benchmark (the published keys)."""
    return Config({k: (tuple(config[k]) if isinstance(config[k], list)
                       else config[k]) for k in KEYS})
