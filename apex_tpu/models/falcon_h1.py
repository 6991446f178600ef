"""Falcon-H1 (``falcon_h1``) on the serving path: every layer is a
PARALLEL hybrid, a Mamba-2 state-space mixer beside grouped-query
attention, both reading one normed input and both added to the residual
stream, then a SwiGLU MLP.  The published muP multipliers are applied
where the published code applies them.

The block (``N`` RMSNorm; ``benchmarks/reference/falcon_h1.py`` writes
it out in full)::

    x0 = E[token] * embedding_multiplier
    u  = N(h; w_in)
    h  = h + ssm_out_multiplier * Mamba2(u)
           + attention_out_multiplier * Attn(attention_in_multiplier * u)
    h  = h + m1 * SwiGLU(N(h; w_mlp); the gate scaled by m0)
         (m0, m1 = mlp_multipliers)
    logits = lm_head_multiplier * N(h_L; w_f) W_head

``Attn`` is grouped-query attention with K scaled by ``key_multiplier``
and half-split RoPE on q and k.  ``Mamba2`` projects the input into z,
x, B, C and dt (each part scaled by its ``ssm_multipliers`` entry),
runs x | B | C through a causal depthwise convolution of width
``mamba_d_conv`` and a SiLU, then the recurrence of
:mod:`apex_tpu.ops.ssm` (``S_t = exp(dt A) S_{t-1} + dt x B^T``, ``y =
S C + D x``), a gated RMSNorm over ``mamba_n_groups`` groups of ``y *
silu(z)`` and ``out_proj``.

**Two kinds of state a slot** (:class:`apex_tpu.serving.kv_cache.
KVCacheConfig` built by :meth:`FalconH1Model.cache_config`): the
attention's K/V in pages (one ``kv`` class, the whole context) and,
beside them, a FIXED-size state every slot keeps per layer
(``slot_states``): ``ssm.state`` (layers, slots, H, P, N), the
recurrent state, and ``ssm.conv`` (layers, slots, d_conv - 1,
conv channels), the convolution's last inputs.  Both ride in the one
donated pools dict.

- a prefill CHUNK is given its ``slot``: the chunk at position 0 starts
  from zero state and an empty window (admission costs no device work),
  a later chunk from what the slot's rows hold; the chunk's K/V go to
  its pages, the SSD scan (:func:`apex_tpu.ops.ssm.ssd_chunk_scan`)
  runs over its tokens (those past the prompt enter the state with
  ``dt = 0``), and the state after its last real token and the window
  of the inputs before it are written back to the slot's rows;
- a DECODE step advances every live slot's state in place
  (:func:`apex_tpu.ops.ssm.ssm_state_update`) and leaves every other
  slot's rows, an empty slot's or one still between its prompt's
  chunks, bit for bit as they were.

``decode_fns`` returns the :class:`apex_tpu.models.gpt.GPTDecodeFns`
contract; the pools are donated to every step and updated in place.  The
decode step keeps in the carry (``decode.carry_extras``) its running
``counters`` (``COUNTER_NAMES``) and the step's ``last_logits``.

Device scopes: ``tlm.attn.full`` (and ``.core``, the page walk),
``tlm.ssm.in_proj``, ``tlm.ssm.conv``, ``tlm.ssm.scan`` (a chunk),
``tlm.ssm.state_update`` (a decode step), ``tlm.ssm.out`` (the gated
norm and ``out_proj``) and ``tlm.mlp``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.models.gpt import GPTDecodeFns
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.attention_decode import fmha_decode
from apex_tpu.ops.layer_norm import fused_rms_norm_affine
from apex_tpu.ops.rope import apply_rope_tables, rope_cos_sin, rope_table
from apex_tpu.ops.ssm import (
    causal_conv, causal_conv_step, ssd_chunk_scan, ssm_state_update,
)
from apex_tpu.telemetry import programs as _programs
from apex_tpu.telemetry.spans import phase

__all__ = ["FalconH1Config", "FalconH1Model", "COUNTER_NAMES",
           "STATE", "CONV", "BUILT"]

#: the pool keys of the two per-slot states
STATE, CONV = "ssm.state", "ssm.conv"

#: how a slot's recurrent state is stored: every decode step multiplies
#: it by ``exp(dt A)``, within 1e-3 of 1 a token for a long-memory head,
#: and a state kept in bfloat16 would be rounded at every step
STATE_DTYPE = jnp.float32

#: the fp32 vector every decode step adds to (``carry["counters"]``):
#: ``decode_full_rows`` the K/V rows the page walks read (per live slot
#: and layer: its whole context), ``decode_context_rows`` the tokens in
#: context per live slot, ``decode_slot_layers`` live slots x layers,
#: ``ssm_state_bytes`` the bytes of recurrent state the step has to read
#: and write (each live slot's, every layer, once each way)
COUNTER_NAMES = ("decode_steps", "decode_full_rows", "decode_context_rows",
                 "decode_slot_layers", "ssm_state_bytes")

#: the published switches and the one value of each this block builds:
#: the conv's bias and the gated group norm on, the norm AFTER the gate,
#: no projection biases, an untied head
BUILT = {"mamba_conv_bias": True, "mamba_rms_norm": True,
         "mamba_norm_before_gate": False, "mamba_proj_bias": False,
         "projectors_bias": False, "attention_bias": False,
         "mlp_bias": False, "tie_word_embeddings": False}


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """The published keys (``tiiuae/Falcon-H1-34B-Instruct``
    ``config.json``).  ``mamba_d_ssm`` is the mixer's width, which need
    not be ``mamba_expand * hidden_size``."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    mamba_d_ssm: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_n_groups: int
    mamba_d_conv: int
    mamba_chunk_size: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    params_dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, cfg: dict, *, params_dtype: Any = jnp.bfloat16):
        """From a ``config.json``-shaped dict (every key above it has).
        The published keys that switch parts of the block on or off must
        hold the values this block is built for (``BUILT``)."""
        unbuilt = {k: cfg[k] for k, v in BUILT.items()
                   if k in cfg and cfg[k] != v}
        if unbuilt:
            raise ValueError(f"not built: {unbuilt} (built: {BUILT})")
        names = {f.name for f in dataclasses.fields(cls)} - {"params_dtype"}
        kw = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in cfg.items() if k in names}
        return cls(**kw, params_dtype=params_dtype)

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide over the K/V heads")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm:
            raise ValueError("mamba_n_heads * mamba_d_head must be "
                             "mamba_d_ssm")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("the SSM heads must divide over the groups")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has 5 entries (z, x, B, C, "
                             "dt), mlp_multipliers 2 (gate, down)")

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: x | B | C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def proj_dim(self) -> int:
        """Width of the input projection: z | x | B | C | dt."""
        return self.mamba_d_ssm + self.conv_dim + self.mamba_n_heads

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5


class FalconH1Model:
    def __init__(self, config: FalconH1Config):
        self.config = config

    # ----------------------------------------------------------- params
    def _mup(self) -> np.ndarray:
        """The published muP vector (z | x | B | C | dt) times
        ``ssm_in_multiplier``: both scale the input projection's output
        columns (a scale of its input is one of its output)."""
        c = self.config
        gn = c.mamba_n_groups * c.mamba_d_state
        widths = (c.mamba_d_ssm, c.mamba_d_ssm, gn, gn, c.mamba_n_heads)
        return c.ssm_in_multiplier * np.concatenate([
            np.full((n,), m, np.float32)
            for n, m in zip(widths, c.ssm_multipliers)])

    def _init_layer(self, key):
        c = self.config
        h, d, f = c.hidden_size, c.head_dim, c.intermediate_size
        Hq, Hkv, H = c.num_attention_heads, c.num_key_value_heads, \
            c.mamba_n_heads
        K = c.mamba_d_conv
        ks = jax.random.split(key, 13)
        m0, m1 = c.mlp_multipliers
        ain = c.attention_in_multiplier

        def w(k, shape, fan_in, scale=1.0):
            return (jax.random.normal(k, shape, jnp.float32)
                    * (scale * fan_in ** -0.5)).astype(c.params_dtype)

        ones = lambda n: jnp.ones((n,), jnp.float32)
        # Mamba-2's own draws: dt log-uniform in [1e-3, 1e-1] (the bias is
        # its inverse softplus), A uniform in [1, 16]
        dt = jnp.exp(jax.random.uniform(ks[9], (H,), jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return {
            "norm_in": ones(h), "norm_mlp": ones(h),
            "attn": {
                "wq": w(ks[0], (h, Hq * d), h, 1 / ain),
                "wk": w(ks[1], (h, Hkv * d), h, 1 / (ain * c.key_multiplier)),
                "wv": w(ks[2], (h, Hkv * d), h, 1 / ain),
                "wo": w(ks[3], (Hq * d, h), Hq * d,
                        1 / c.attention_out_multiplier),
            },
            "ssm": {
                "in_proj": (jax.random.normal(
                    ks[4], (h, c.proj_dim), jnp.float32) * h ** -0.5
                    / self._mup()).astype(c.params_dtype),
                "conv_w": w(ks[5], (K, c.conv_dim), K),
                "conv_b": w(ks[6], (c.conv_dim,), K).astype(jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    ks[10], (H,), jnp.float32, 1.0, 16.0)),
                "D": ones(H),
                "norm": ones(c.mamba_d_ssm),
                "out_proj": w(ks[7], (c.mamba_d_ssm, h), c.mamba_d_ssm,
                              1 / c.ssm_out_multiplier),
            },
            "mlp": {
                "w_gate": w(ks[8], (h, f), h, 1 / m0),
                "w_up": w(ks[11], (h, f), h),
                "w_down": w(ks[12], (f, h), f, 1 / m1),
            },
        }

    def init(self, key) -> Dict[str, Any]:
        """Seeded weights in ``params_dtype`` (norm gains, the conv bias
        and the SSM's per-head vectors fp32), drawn so that EVERY branch
        reaches the residual stream at unit scale despite the published
        multipliers: each matrix is N(0, 1/fan_in) divided by the
        multiplier its output is scaled by (the embedding by
        ``embedding_multiplier``, ``W_o`` by ``attention_out_multiplier``,
        ``out_proj`` by ``ssm_out_multiplier``, each input-projection
        column by its muP factor, ...).  Drawn plainly, a unit embedding
        times 5.66 would bury two mixers scaled by 0.0375 and 0.088, and
        the logits would hardly see either.  The layers are a LIST."""
        c = self.config
        ke, kh, kl = jax.random.split(key, 3)
        keys = jax.random.split(kl, c.num_hidden_layers)
        return {
            "embedding": {"weight": (jax.random.normal(
                ke, (c.vocab_size, c.hidden_size), jnp.float32)
                / c.embedding_multiplier).astype(c.params_dtype)},
            "head": {"weight": (jax.random.normal(
                kh, (c.hidden_size, c.vocab_size), jnp.float32)
                * c.hidden_size ** -0.5 / c.lm_head_multiplier
            ).astype(c.params_dtype)},
            "final_norm": {"weight": jnp.ones((c.hidden_size,), jnp.float32)},
            "layers": [self._init_layer(keys[i])
                       for i in range(c.num_hidden_layers)],
        }

    def param_specs(self) -> Dict[str, Any]:
        """Everything replicated: every layer is whole on its chip."""
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return jax.tree.map(lambda _: P(), shapes)

    # ---------------------------------------------------------- pieces
    def _rms(self, x, w):
        return fused_rms_norm_affine(
            x, w, x.shape[-1], eps=self.config.rms_norm_eps,
            implementation="xla")

    def _norm(self, x, w):
        """The fp32 residual stream, normalised, in the weights' dtype."""
        return self._rms(x, w).astype(self.config.params_dtype)

    def _embed(self, params, tokens):
        x = jnp.take(params["embedding"]["weight"], tokens,
                     axis=0).astype(jnp.float32)
        return x * self.config.embedding_multiplier

    def _qkv(self, ap, u):
        """``u`` (n, hidden) normed -> q (n, Hq, d), k (n, Hkv, d) (NOT
        rotated), v, in the weights' dtype, every multiplier applied."""
        c = self.config
        n, d, dt = u.shape[0], c.head_dim, c.params_dtype
        ain = c.attention_in_multiplier
        proj = lambda w, scale, heads: (jnp.matmul(
            u, w, preferred_element_type=jnp.float32) * scale).astype(
                dt).reshape(n, heads, d)
        return (proj(ap["wq"], ain, c.num_attention_heads),
                proj(ap["wk"], ain * c.key_multiplier, c.num_key_value_heads),
                proj(ap["wv"], ain, c.num_key_value_heads))

    def _attn_out(self, ap, o):
        return jnp.matmul(o.astype(self.config.params_dtype), ap["wo"],
                          preferred_element_type=jnp.float32) \
            * self.config.attention_out_multiplier

    def _in_proj(self, sp, u):
        """-> z (n, d_ssm) fp32, x | B | C (n, conv_dim) in the weights'
        dtype (what the convolution's window keeps), dt before its
        softplus (n, H) fp32."""
        c = self.config
        proj = jnp.matmul(u, sp["in_proj"], preferred_element_type=jnp.float32
                          ) * jnp.asarray(self._mup())
        z, xbc, dt = jnp.split(proj, [c.mamba_d_ssm,
                                      c.mamba_d_ssm + c.conv_dim], axis=-1)
        return z, xbc.astype(c.params_dtype), dt

    def _ssm_parts(self, sp, conv):
        """The convolution's output (n, conv_dim), before its SiLU ->
        x (n, H, P), B, C (n, G, N), fp32."""
        c = self.config
        n = conv.shape[0]
        xbc = jax.nn.silu(conv)
        x, B, C = jnp.split(xbc, [c.mamba_d_ssm, c.mamba_d_ssm
                                  + c.mamba_n_groups * c.mamba_d_state],
                            axis=-1)
        g = lambda t: t.reshape(n, c.mamba_n_groups, c.mamba_d_state)
        return x.reshape(n, c.mamba_n_heads, c.mamba_d_head), g(B), g(C)

    @staticmethod
    def _dt(sp, dt_raw):
        return jax.nn.softplus(dt_raw + sp["dt_bias"])

    def _ssm_out(self, sp, y, z):
        """The gated RMSNorm over the groups of ``y * silu(z)`` (the norm
        AFTER the gate) and ``out_proj`` -> (n, hidden) fp32."""
        c = self.config
        n = y.shape[0]
        g = (y.reshape(n, -1) * jax.nn.silu(z)).reshape(
            n, c.mamba_n_groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                              + c.rms_norm_eps)
        g = (g.reshape(n, -1) * sp["norm"]).astype(c.params_dtype)
        return jnp.matmul(g, sp["out_proj"],
                          preferred_element_type=jnp.float32) \
            * c.ssm_out_multiplier

    def _mlp(self, mp, m):
        c = self.config
        m0, m1 = c.mlp_multipliers
        gate = jnp.matmul(m, mp["w_gate"], preferred_element_type=jnp.float32)
        up = jnp.matmul(m, mp["w_up"], preferred_element_type=jnp.float32)
        y = (up * jax.nn.silu(gate * m0)).astype(c.params_dtype)
        return jnp.matmul(y, mp["w_down"],
                          preferred_element_type=jnp.float32) * m1

    def _walk(self, params, x, attend, mix, pools):
        """THE layer walk: ``x`` (n, hidden) through the layers,
        unrolled.  ``attend(q, k, v, layer, pools) -> (attention output
        (n, Hq * d), pools)`` and ``mix(sp, xbc, dt_raw, layer, pools)
        -> (y (n, H, P) fp32, pools)`` (the convolution and the
        recurrence) are what the callers differ in.  Returns (x, pools,
        per layer what the attention and the state-space branch added
        to the residual stream)."""
        kept = []
        for layer, lp in enumerate(params["layers"]):
            u = self._norm(x, lp["norm_in"])
            with phase("attn.full"):
                q, k, v = self._qkv(lp["attn"], u)
                o, pools = attend(q, k, v, layer, pools)
                a = self._attn_out(lp["attn"], o)
            sp = lp["ssm"]
            with phase("ssm.in_proj"):
                z, xbc, dt_raw = self._in_proj(sp, u)
            y, pools = mix(sp, xbc, dt_raw, layer, pools)
            with phase("ssm.out"):
                s = self._ssm_out(sp, y, z)
            kept.append((a, s))
            x = x + a + s
            with phase("mlp"):
                x = x + self._mlp(lp["mlp"], self._norm(x, lp["norm_mlp"]))
        return x, pools, kept

    def _logits(self, params, x):
        return jnp.matmul(self._norm(x, params["final_norm"]["weight"]),
                          params["head"]["weight"],
                          preferred_element_type=jnp.float32) \
            * self.config.lm_head_multiplier

    def rope_table(self, max_len: int):
        return rope_table(max_len, self.config.head_dim, jnp.float32,
                          float(self.config.rope_theta))

    @staticmethod
    def _rope_rows(table, positions):
        last = table[0].shape[0] - 1
        p = jnp.minimum(positions, last)
        return jnp.take(table[0], p, axis=0), jnp.take(table[1], p, axis=0)

    def _attend_rows(self, q, k, v, offset):
        """``q`` (n, Hq, d) one position after another against ``k``/
        ``v`` (S, Hkv, d), the first query ``offset`` positions after the
        first key, causal: a K/V head's query heads ride as further
        query rows (K/V not repeated, no mask built) -> (n, Hq * d)."""
        n, Hq, d = q.shape
        Hkv = k.shape[1]
        G = Hq // Hkv
        qg = jnp.moveaxis(q.reshape(n, Hkv, G, d), 0, 2).reshape(
            1, Hkv, G * n, d)
        out = flash_attention(
            qg, jnp.moveaxis(k, 0, 1)[None], jnp.moveaxis(v, 0, 1)[None],
            causal=True, sm_scale=self.config.softmax_scale, q_offset=offset,
            q_period=n)
        return jnp.moveaxis(out[0].reshape(Hkv, G, n, d), 2, 0).reshape(
            n, Hq * d)

    def _ssm_vectors(self, sp):
        return -jnp.exp(sp["A_log"]), sp["D"]

    # ------------------------------------------------------ whole forward
    def apply(self, params: Dict[str, Any], tokens: jnp.ndarray,
              branches: bool = False):
        """``tokens`` (T,) -> fp32 logits (T, vocab): the whole sequence
        at once, no cache, the scan from zero state.  ``branches``: also
        per layer (what attention added, what the state-space branch
        added), each (T, hidden)."""
        c = self.config
        T = tokens.shape[0]
        cos, sin = rope_cos_sin(jnp.arange(T, dtype=jnp.int32), c.head_dim,
                                float(c.rope_theta))
        K, H = c.mamba_d_conv, c.mamba_n_heads

        def attend(q, k, v, layer, pools):
            q = apply_rope_tables(q, cos[:, None], sin[:, None])
            k = apply_rope_tables(k, cos[:, None], sin[:, None])
            return self._attend_rows(q, k, v, 0), pools

        def mix(sp, xbc, dt_raw, layer, pools):
            conv = causal_conv(xbc, jnp.zeros((K - 1, xbc.shape[1]),
                                              xbc.dtype),
                               sp["conv_w"], sp["conv_b"])
            x, B, C = self._ssm_parts(sp, conv)
            A, D = self._ssm_vectors(sp)
            y, _ = ssd_chunk_scan(
                x, self._dt(sp, dt_raw), A, B, C, D,
                jnp.zeros((H, c.mamba_d_head, c.mamba_d_state), jnp.float32),
                chunk=c.mamba_chunk_size)
            return y, pools

        x, _, kept = self._walk(params, self._embed(params, tokens), attend,
                                mix, None)
        logits = self._logits(params, x)
        return (logits, kept) if branches else logits

    # ------------------------------------------------------ serving steps
    def cache_config(self, *, slots: int, pages_per_seq: int,
                     page_size: int, num_pages: Optional[int] = None,
                     dtype: Any = jnp.bfloat16):
        """The model's cache for ``slots`` slots of at most
        ``pages_per_seq`` pages: K/V of every layer in one whole-context
        page class (``num_pages`` default ``1 + slots * pages_per_seq``)
        and the two per-slot states."""
        from apex_tpu.serving.kv_cache import KVCacheConfig, SlotState

        c = self.config
        L = c.num_hidden_layers
        return KVCacheConfig(
            num_layers=L, num_heads=c.num_key_value_heads,
            head_dim=c.head_dim,
            num_pages=num_pages or 1 + slots * pages_per_seq,
            page_size=page_size, max_seqs=slots, pages_per_seq=pages_per_seq,
            dtype=dtype, slot_states=(
                SlotState(STATE, L, (c.mamba_n_heads, c.mamba_d_head,
                                     c.mamba_d_state), STATE_DTYPE),
                SlotState(CONV, L, (c.mamba_d_conv - 1, c.conv_dim),
                          c.params_dtype)))

    def _check_cache(self, cfg, prefill_chunk):
        want = self.cache_config(slots=cfg.max_seqs,
                                 pages_per_seq=cfg.pages_per_seq,
                                 page_size=cfg.page_size,
                                 num_pages=cfg.num_pages, dtype=cfg.dtype)
        if cfg != want:
            raise ValueError(
                f"this model keeps K/V pages and two per-slot states; the "
                f"cache is {cfg}: build it with model.cache_config(...)")
        if prefill_chunk is None or int(prefill_chunk) < 1 \
                or int(prefill_chunk) % cfg.page_size:
            raise ValueError(
                "this model ingests prompts in chunks: pass "
                "prefill_chunk, a multiple of the page size")

    @staticmethod
    def _flat(pools):
        return tuple(pools[kv].reshape((-1,) + pools[kv].shape[2:])
                     for kv in ("k", "v"))

    def chunk_step(self, params, pools, toks, start, plen, write_from,
                   page_row, slot, *, ctx_len: int, cache_config, table):
        """One prefill chunk of slot ``slot``: ``toks`` (C,) at positions
        ``start ..``; K/V written through ``page_row`` as WHOLE pages
        (those that hold a position in [``write_from``, ``plen``)),
        attention over the slot's first ``ctx_len`` cached positions
        (static, ``>= start + C``); the recurrence from zero state where
        ``start`` is 0, else from the slot's row, and the state after
        position ``plen - 1`` (or the chunk's last) written back with
        the convolution's window of the inputs before it.  Returns
        (logits of position ``min(plen, start + C) - 1`` (vocab,),
        pools)."""
        from apex_tpu.serving.kv_cache import write_class_pages, write_targets

        c, cfg = self.config, cache_config
        page, C = cfg.page_size, toks.shape[0]
        n_pages, K = cfg.num_pages, c.mamba_d_conv
        positions = start + jnp.arange(C, dtype=jnp.int32)
        real = positions < plen
        cos, sin = self._rope_rows(table, positions)
        fresh = start == 0
        # rows of the convolution's input that end at the chunk's last
        # real token: the window the next chunk or decode step reads
        tail = jnp.minimum(plen - start, C)

        def attend(q, k, v, layer, pools):
            q = apply_rope_tables(q, cos[:, None], sin[:, None])
            k = apply_rope_tables(k, cos[:, None], sin[:, None])
            p0 = start + jnp.arange(0, C, page, dtype=jnp.int32)
            pages, _ = write_targets(
                page_row, p0, (p0 < plen) & (p0 + page > write_from), page)
            pools = dict(pools, **{
                kv: write_class_pages(pools[kv], layer * n_pages, new, pages)
                for kv, new in (("k", k), ("v", v))})
            with phase("attn.full.core"):
                n_ctx = ctx_len // page
                # a bucket may reach past the table: the null page there,
                # at positions no query of the chunk sees
                ctx_pages = jnp.take(
                    page_row, jnp.arange(n_ctx, dtype=jnp.int32),
                    mode="fill", fill_value=0)
                fk, fv = self._flat(pools)
                rows = lambda f: jnp.moveaxis(
                    f[layer * n_pages + ctx_pages], 1, 2).reshape(
                        n_ctx * page, c.num_key_value_heads, c.head_dim)
                o = self._attend_rows(q, rows(fk), rows(fv), start)
            return o, pools

        def mix(sp, xbc, dt_raw, layer, pools):
            with phase("ssm.conv"):
                window = jnp.where(fresh, 0, pools[CONV][layer, slot]).astype(
                    xbc.dtype)
                conv = causal_conv(xbc, window, sp["conv_w"], sp["conv_b"])
                inputs = jnp.concatenate([window, xbc])
                keep = jax.lax.dynamic_slice_in_dim(inputs, tail, K - 1)
                pools = dict(pools, **{CONV: pools[CONV].at[layer, slot].set(
                    keep.astype(pools[CONV].dtype))})
            with phase("ssm.scan"):
                x, B, C_ = self._ssm_parts(sp, conv)
                A, D = self._ssm_vectors(sp)
                s0 = jnp.where(fresh, 0.0, pools[STATE][layer, slot].astype(
                    jnp.float32))
                dt = jnp.where(real[:, None], self._dt(sp, dt_raw), 0.0)
                y, final = ssd_chunk_scan(x, dt, A, B, C_, D, s0,
                                          chunk=c.mamba_chunk_size)
                pools = dict(pools, **{STATE: pools[STATE].at[layer, slot].set(
                    final.astype(pools[STATE].dtype))})
            return y, pools

        x = self._embed(params, toks)
        x, pools, _ = self._walk(params, x, attend, mix, pools)
        last = jnp.take(x, jnp.clip(plen - 1 - start, 0, C - 1), axis=0)
        return self._logits(params, last[None])[0], pools

    def decode_step(self, params, pools, tokens, positions, active,
                    page_table, *, cache_config, table):
        """One token for every slot: ``tokens`` (B,) at ``positions``
        (B,) (the slot's context length), ``active`` (B,) bool.  Each
        layer writes the new K/V and walks the slot's pages through the
        paged decode kernel, runs its convolution over the slot's window
        and advances the slot's recurrent state in place; a slot that is
        not ``active`` keeps both states as they were.  Returns (fp32
        logits (B, vocab), pools, counters (4,): full rows, context
        rows, live slots x layers, state bytes)."""
        from apex_tpu.serving.kv_cache import write_class_rows, write_targets

        c, cfg = self.config, cache_config
        page, n_pages = cfg.page_size, cfg.num_pages
        B = tokens.shape[0]
        cos, sin = self._rope_rows(table, positions)
        lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)

        def attend(q, k, v, layer, pools):
            k = apply_rope_tables(k, cos[:, None], sin[:, None])
            pages, offsets = write_targets(page_table, positions, active, page)
            pools = dict(pools, **{
                kv: write_class_rows(pools[kv], layer * n_pages, new, pages,
                                     offsets)
                for kv, new in (("k", k), ("v", v))})
            with phase("attn.full.core"):
                fk, fv = self._flat(pools)
                o = fmha_decode(
                    q[:, :, None, :], fk, fv, page_table + layer * n_pages,
                    lengths, causal=True, sm_scale=c.softmax_scale,
                    rope=(cos[:, None], sin[:, None]),
                    num_kv_heads=c.num_key_value_heads)
            return o.reshape(B, -1), pools

        def mix(sp, xbc, dt_raw, layer, pools):
            with phase("ssm.conv"):
                old = pools[CONV][layer]
                conv, window = causal_conv_step(xbc, old, sp["conv_w"],
                                                sp["conv_b"])
                pools = dict(pools, **{CONV: pools[CONV].at[layer].set(
                    jnp.where(active[:, None, None], window.astype(old.dtype),
                              old))})
            with phase("ssm.state_update"):
                x, B_, C_ = self._ssm_parts(sp, conv)
                A, D = self._ssm_vectors(sp)
                y, state = ssm_state_update(
                    pools[STATE], layer, x, self._dt(sp, dt_raw), A, B_, C_,
                    D, active)
            return y, dict(pools, **{STATE: state})

        x = self._embed(params, tokens)
        x, pools, _ = self._walk(params, x, attend, mix, pools)
        live = jnp.sum(active).astype(jnp.float32)
        L = c.num_hidden_layers
        per_slot = 2.0 * L * c.mamba_n_heads * c.mamba_d_head \
            * c.mamba_d_state * jnp.dtype(STATE_DTYPE).itemsize
        stats = jnp.stack([
            L * jnp.sum(lengths).astype(jnp.float32),
            jnp.sum(lengths).astype(jnp.float32), live * L, live * per_slot])
        return self._logits(params, x), pools, stats

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
        contract: ``chunk`` (one ``prefill_chunk``-token ingestion step,
        told its slot: ``chunk(pools, toks, start, plen, write_from,
        row, key, *, slot)``) and ``decode`` (one token for every live
        slot).  ``prefill`` (the monolithic signature) is not served: it
        has no slot to keep the state in.  ``cache_config`` is
        ``self.cache_config(...)``; ``params`` and its ``init_pools``
        dict are expected on ``mesh``, replicated.

        Each step takes the pools DONATED and returns them updated in
        place.  ``decode`` keeps in the carry, beside the batcher's five
        per-slot entries (``decode.carry_extras``): ``counters`` grown by
        ``COUNTER_NAMES`` a step and the step's ``last_logits`` (slots,
        vocab).  ``chunk`` compiles once per context BUCKET
        (``prefill_chunk`` times a power of two, and the longest
        prompt)."""
        from apex_tpu.serving.kv_cache import init_pools
        from apex_tpu.serving.sampling import advance_slots, sample

        c, cfg = self.config, cache_config
        self._check_cache(cfg, prefill_chunk)
        if max_prompt_len > cfg.max_len:
            raise ValueError(
                f"max_prompt_len {max_prompt_len} exceeds the slot bound "
                f"{cfg.max_len} (pages_per_seq * page_size)")
        C = int(prefill_chunk)
        table = self.rope_table(cfg.max_len)
        S = cfg.max_seqs
        top = -(-int(max_prompt_len) // C) * C
        buckets = sorted({min(C << i, top)
                          for i in range((top // C).bit_length() + 1)})
        carry_extras = {
            "counters": jnp.zeros((len(COUNTER_NAMES),), jnp.float32),
            "last_logits": jnp.zeros((S, c.vocab_size), jnp.float32),
        }

        @phase("prefill")
        def _chunk(params, pools, toks, start, plen, write_from, page_row,
                   key, slot, *, ctx_len):
            logits, pools = self.chunk_step(
                params, pools, toks[0], start, plen, write_from, page_row,
                slot, ctx_len=ctx_len, cache_config=cfg, table=table)
            tok = sample(logits[None], jax.random.fold_in(key, plen),
                         temperature, top_k, top_p)[0]
            return pools, tok, logits

        @phase("decode")
        def _decode(params, pools, carry, page_table):
            active = jnp.logical_not(carry["done"])
            logits, pools, stats = self.decode_step(
                params, pools, carry["tokens"], carry["lengths"], active,
                page_table, cache_config=cfg, table=table)
            counted = jnp.concatenate([jnp.ones((1,), jnp.float32), stats])
            return pools, {
                **advance_slots(carry, logits, active,
                                temperature=temperature, top_k=top_k,
                                top_p=top_p, eos_id=eos_id),
                "counters": carry["counters"] + counted,
                "last_logits": logits}

        _programs.own(_chunk.__name__, _decode.__name__,
                      layer="serving steps")
        cj = jax.jit(_chunk, donate_argnums=(1,), static_argnames=("ctx_len",))
        dj = jax.jit(_decode, donate_argnums=(1,))

        def bucket(start: int) -> int:
            return next(b for b in buckets if b >= min(start + C, top))

        def chunk(pools, toks, start, plen, write_from, row, key, *, slot):
            start = int(start)
            return cj(params, pools,
                      jnp.asarray(toks, jnp.int32).reshape(1, C),
                      jnp.int32(start), jnp.int32(plen),
                      jnp.int32(write_from), row, key, jnp.int32(slot),
                      ctx_len=bucket(start))

        def prefill(*_):
            raise ValueError(
                "Falcon-H1 keeps a state a slot: prompts go in through "
                "chunk(..., slot=), which ContinuousBatcher calls when "
                "the cache has slot_states")

        decode = lambda pools, carry, pt: dj(params, pools, carry, pt)
        chunk.prefill_chunk = C
        chunk.ctx_buckets = tuple(buckets)
        decode.eos_id = eos_id
        carry_sharding = NamedSharding(mesh, P())
        decode.carry_sharding = carry_sharding
        decode.carry_extras = carry_extras
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
