"""Arcee ``afmoe`` (Trinity) on the serving path: grouped-query heads,
window and full attention layers over TWO page classes, a gated
attention output, RoPE on the window layers only, and the held-experts
layer.

The block (``layer_types[l]`` is ``sliding_attention`` or
``full_attention``; ``N`` is RMSNorm; four norms a layer)::

    x0 = E[token] * sqrt(hidden)                            (mup_enabled)
    a  = N(h; w_in)
    q, k, v, gate = a W_q, a W_k, a W_v, a W_g      (Hq, Hkv, Hkv, Hq heads)
    q, k = N_head(q; w_qn), N_head(k; w_kn)          RMSNorm over head_dim
    sliding: q, k = RoPE(q, k; theta, position)      full: NO position signal
    s_ij = q_i . k_j / sqrt(d),  j <= i,  i - j < window on sliding layers
    head i reads K/V head i // (Hq / Hkv)
    o  = softmax(s) v;   o = o * sigmoid(gate);   h = h + N(o W_o; w_post_attn)
    m  = N(h; w_pre_mlp)
    F  = SwiGLU(m)                                        l < num_dense_layers
    F  = Shared(m) + sum_{e in T and held} g_e Expert_e(m)       otherwise
    h  = h + N(F; w_post_mlp)
    logits = N(h_L; w_f) W_head                                      (untied)

The expert layer IS :class:`apex_tpu.transformer.moe.HeldExpertsMLP` at
``n_group=1`` (sigmoid scores, the bias in the choice only, weights
normalised over the chosen set, ``route_scale``); RoPE is
:mod:`apex_tpu.ops.rope` (half-split pairs).

**Two page classes** (:class:`apex_tpu.serving.kv_cache.PageClass`): the
full layers keep the whole context (class ``full``), the window layers a
ring of ``window + chunk`` tokens and one page a slot (class
``window``).  One attention, three walks of it:

- ``apply`` — a whole sequence, no cache (tests, small sizes);
- a prefill CHUNK — the chunk's K/V are written through the slot's page
  row (a window layer's modulo its ring), the context is read back in
  POSITION order (a full layer's pages ``[0, start + C)``, a window
  layer's ``[start + C - window - C, start + C)`` out of the ring) and
  attention is :func:`apex_tpu.ops.attention.flash_attention` told
  where the chunk sits among those keys (``q_offset``, ``window``: no
  mask is built, key blocks no row sees are not computed), a K/V head's
  query heads riding as further query rows (``q_period``; K/V are not
  repeated);
- a DECODE step — :func:`apex_tpu.ops.attention_decode.fmha_decode` with
  ``num_kv_heads``: a full layer walks its slot's pages from 0, a window
  layer from the page holding ``length - window`` (``first``) round its
  ring, with the query rotation fused into the kernel.

``decode_fns`` returns the :class:`apex_tpu.models.gpt.GPTDecodeFns`
contract; the pools are donated to every step and updated in place.  The
decode step keeps in the carry (``decode.carry_extras``) its running
``counters`` (``COUNTER_NAMES``), the step's ``last_logits`` and
``last_attn``: the attention output (before the gate) of the last window
layer and of the last full layer, for whoever holds the served path to a
reference.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.models.gpt import GPTDecodeFns
from apex_tpu.ops.attention import flash_attention, k_blocks_run
from apex_tpu.ops.attention_decode import fmha_decode
from apex_tpu.ops.layer_norm import fused_rms_norm_affine
from apex_tpu.ops.rope import apply_rope_tables, rope_cos_sin, rope_table
from apex_tpu.telemetry import programs as _programs
from apex_tpu.telemetry.spans import phase
from apex_tpu.transformer.moe import HeldExpertsMLP

__all__ = ["AfmoeConfig", "AfmoeModel", "COUNTER_NAMES", "SLIDING", "FULL"]

SLIDING, FULL = "sliding_attention", "full_attention"

#: the fp32 vector every decode step adds to (``carry["counters"]``),
#: summed over the layers.  The four ``decode_choices`` .. ``load_max``
#: are ``HeldExpertsMLP.COUNTERS`` summed over the expert layers;
#: ``decode_window_rows`` / ``decode_full_rows`` the K/V rows the window /
#: full layers' page walks read (per live slot and layer: whole pages
#: from the one holding the first position up to the length);
#: ``decode_context_rows`` the tokens in context, per live slot.
COUNTER_NAMES = (
    "decode_steps", "decode_choices", "decode_choices_held",
    "decode_experts_touched", "decode_load_max", "decode_window_rows",
    "decode_full_rows", "decode_context_rows", "decode_slot_layers",
)


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The published keys (``arcee-ai/Trinity-Large-Preview``
    ``config.json``) plus the share: ``vocab_size`` is the rows of the
    vocabulary held here, ``held_experts`` the routed experts computed
    here (ids into the router's ``num_experts`` outputs)."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_dense_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int
    held_experts: Tuple[int, ...]
    num_experts_per_tok: int
    layer_types: Tuple[str, ...]
    sliding_window: int
    num_shared_experts: int = 1
    route_scale: float = 1.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    mup_enabled: bool = True
    params_dtype: Any = jnp.bfloat16

    @classmethod
    def from_hf(cls, cfg: dict, *, num_experts: int, held_experts,
                params_dtype: Any = jnp.bfloat16):
        """From a ``config.json``-shaped dict.  ``num_experts`` is the
        router's PUBLISHED width (a cut configuration's own key of that
        name counts the experts held)."""
        names = [f.name for f in dataclasses.fields(cls)]
        return cls(
            **{k: cfg[k] for k in names if k in cfg and k not in (
                "num_experts", "held_experts", "layer_types",
                "params_dtype")},
            num_experts=int(num_experts),
            held_experts=tuple(int(e) for e in held_experts),
            layer_types=tuple(cfg["layer_types"]),
            params_dtype=params_dtype)

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers or any(
                t not in (SLIDING, FULL) for t in self.layer_types):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {SLIDING!r} or {FULL!r}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide over the K/V heads")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers outside the layers")

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5


class AfmoeModel:
    def __init__(self, config: AfmoeConfig):
        c = self.config = config
        self.n_dense = c.num_dense_layers
        self.n_moe = c.num_hidden_layers - c.num_dense_layers
        self.window_layers = tuple(
            i for i, t in enumerate(c.layer_types) if t == SLIDING)
        self.full_layers = tuple(
            i for i, t in enumerate(c.layer_types) if t == FULL)
        self.moe = HeldExpertsMLP(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            top_k=c.num_experts_per_tok, n_group=1, topk_group=1,
            routed_scaling_factor=c.route_scale,
            n_shared_experts=c.num_shared_experts,
            params_dtype=c.params_dtype)

    # ----------------------------------------------------------- params
    def _init_layer(self, key, dense: bool):
        c = self.config
        h, d = c.hidden_size, c.head_dim
        Hq, Hkv = c.num_attention_heads, c.num_key_value_heads
        ks = jax.random.split(key, 9)
        w = lambda k, shape, fan_in: (
            fan_in ** -0.5 * jax.random.normal(k, shape, jnp.float32)
        ).astype(c.params_dtype)
        ones = lambda n: jnp.ones((n,), jnp.float32)
        out = {
            "attn": {
                "wq": w(ks[0], (h, Hq * d), h),
                "wk": w(ks[1], (h, Hkv * d), h),
                "wv": w(ks[2], (h, Hkv * d), h),
                "wg": w(ks[3], (h, Hq * d), h),
                "wo": w(ks[4], (Hq * d, h), Hq * d),
                "q_norm": ones(d), "k_norm": ones(d),
            },
            "norm_in": ones(h), "norm_post_attn": ones(h),
            "norm_pre_mlp": ones(h), "norm_post_mlp": ones(h),
        }
        if dense:
            f = c.intermediate_size
            out["mlp"] = {"w_gate": w(ks[5], (h, f), h),
                          "w_up": w(ks[6], (h, f), h),
                          "w_down": w(ks[7], (f, h), f)}
        else:
            out["ffn"] = self.moe.init(ks[8], len(c.held_experts))
        return out

    def init(self, key) -> Dict[str, Any]:
        """Seeded weights: matrices N(0, 1/fan_in) in ``params_dtype``,
        the embedding N(0, 1/hidden) so that the muP input scale gives a
        unit stream (the sandwich norms add unit terms to it: one drawn
        at N(0, 1) would be 55 times every layer's), norm gains 1 and
        the router's bias in fp32.  The layers are a LIST: they differ
        in kind, and the steps walk them unrolled."""
        c = self.config
        ke, kh, kl = jax.random.split(key, 3)
        keys = jax.random.split(kl, c.num_hidden_layers)
        return {
            "embedding": {"weight": (
                c.hidden_size ** -0.5 * jax.random.normal(
                    ke, (c.vocab_size, c.hidden_size), jnp.float32)
            ).astype(c.params_dtype)},
            "head": {"weight": (c.hidden_size ** -0.5 * jax.random.normal(
                kh, (c.hidden_size, c.vocab_size), jnp.float32)
            ).astype(c.params_dtype)},
            "final_norm": {"weight": jnp.ones((c.hidden_size,), jnp.float32)},
            "layers": [self._init_layer(keys[i], i < self.n_dense)
                       for i in range(c.num_hidden_layers)],
        }

    def param_specs(self) -> Dict[str, Any]:
        """Everything replicated: attention is data-parallel in the
        deployment this serves, and the experts held here are this
        chip's own."""
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
        """The residual stream is carried in fp32 through the layers;
        the products run in the weights' dtype."""
        c = self.config
        x = jnp.take(params["embedding"]["weight"], tokens,
                     axis=0).astype(jnp.float32)
        return x * c.hidden_size ** 0.5 if c.mup_enabled else x

    def _qkvg(self, ap, a):
        """``a`` (n, hidden), normed -> q (n, Hq, d) and k (n, Hkv, d)
        after their head norms (NOT rotated), v (n, Hkv, d), the output
        gate (n, Hq * d) fp32."""
        c = self.config
        n, d = a.shape[0], c.head_dim
        dt = c.params_dtype
        q = jnp.matmul(a, ap["wq"]).reshape(n, c.num_attention_heads, d)
        k = jnp.matmul(a, ap["wk"]).reshape(n, c.num_key_value_heads, d)
        v = jnp.matmul(a, ap["wv"]).reshape(n, c.num_key_value_heads, d)
        gate = jnp.matmul(a, ap["wg"], preferred_element_type=jnp.float32)
        return (self._rms(q, ap["q_norm"]).astype(dt),
                self._rms(k, ap["k_norm"]).astype(dt), v, gate)

    def _out(self, ap, o, gate):
        """``o`` (n, Hq * d) under its gate, through ``W_o``: fp32."""
        o = (o.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(
            self.config.params_dtype)
        return jnp.matmul(o, ap["wo"], preferred_element_type=jnp.float32)

    def _attend_rows(self, q, k, v, offset, window: int):
        """``q`` (n, Hq, d), one position after another, against
        ``k``/``v`` (S, Hkv, d), likewise, the first query ``offset``
        positions (a traced scalar is fine) after the first key: causal,
        within ``window`` where that is not 0.  A K/V head's query heads
        ride as further query rows, so K/V are not repeated, and no mask
        is built: the kernel is told where its rows sit -> (n, Hq * d)."""
        c = self.config
        n, Hq, d = q.shape
        Hkv = k.shape[1]
        G = Hq // Hkv
        # (n, Hkv, G, d) -> (1, Hkv, G * n, d): row g * n + i
        qg = jnp.moveaxis(q.reshape(n, Hkv, G, d), 0, 2).reshape(
            1, Hkv, G * n, d)
        out = flash_attention(
            qg, jnp.moveaxis(k, 0, 1)[None], jnp.moveaxis(v, 0, 1)[None],
            causal=True, sm_scale=c.softmax_scale, q_offset=offset,
            q_period=n, window=window)
        return jnp.moveaxis(out[0].reshape(Hkv, G, n, d), 2, 0).reshape(
            n, Hq * d)

    def _walk(self, params, x, attend, pools, token_valid):
        """THE layer walk: ``x`` (n, hidden) through the layers,
        unrolled.  ``attend(ap, a, layer, pools) -> (attention output
        before the gate (n, Hq * d), pools)`` is the one thing the three
        callers differ in.  Returns (x, pools, the expert layers'
        counters (4,) summed, per layer the attention outputs)."""
        c = self.config
        counted = jnp.zeros((4,), jnp.float32)
        kept = []
        for layer, lp in enumerate(params["layers"]):
            kind = "window" if c.layer_types[layer] == SLIDING else "full"
            with phase(f"attn.{kind}"):
                a = self._norm(x, lp["norm_in"])
                q, k, v, gate = self._qkvg(lp["attn"], a)
                o, pools = attend(q, k, v, layer, pools)
                kept.append(o)
                x = x + self._rms(self._out(lp["attn"], o, gate),
                                  lp["norm_post_attn"])
            m = self._rms(x, lp["norm_pre_mlp"])            # fp32
            if "mlp" in lp:
                w = lp["mlp"]
                y = HeldExpertsMLP._swiglu(
                    m.astype(c.params_dtype), w["w_gate"], w["w_up"],
                    w["w_down"])
            else:
                y, more = self.moe.apply(
                    lp["ffn"], m, c.held_experts, token_valid=token_valid)
                counted = counted + more[:4]
            x = x + self._rms(y.astype(jnp.float32), lp["norm_post_mlp"])
        return x, pools, counted, kept

    def _logits(self, params, x):
        return jnp.matmul(self._norm(x, params["final_norm"]["weight"]),
                          params["head"]["weight"],
                          preferred_element_type=jnp.float32)

    def rope_table(self, max_len: int):
        """fp32 (cos, sin) rows for positions ``0 .. max_len - 1``."""
        return rope_table(max_len, self.config.head_dim, jnp.float32,
                          self.config.rope_theta)

    @staticmethod
    def _rope_rows(table, positions):
        last = table[0].shape[0] - 1
        p = jnp.minimum(positions, last)
        return jnp.take(table[0], p, axis=0), jnp.take(table[1], p, axis=0)

    def _window(self, layer: int) -> int:
        """How far back layer ``layer`` sees; 0: the whole context."""
        c = self.config
        return c.sliding_window if c.layer_types[layer] == SLIDING else 0

    def _rotates(self, layer: int) -> bool:
        """Window layers rotate q and k; a full layer has no position
        signal at all."""
        return self.config.layer_types[layer] == SLIDING

    # ------------------------------------------------------ whole forward
    def apply(self, params: Dict[str, Any], tokens: jnp.ndarray,
              positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """``tokens`` (T,) -> fp32 logits (T, vocab rows held): the
        whole sequence at once, no cache.  ``positions`` (T,) default
        ``0 .. T - 1`` (a test shifts them: only the window layers may
        notice)."""
        T = tokens.shape[0]
        if positions is None:
            positions = jnp.arange(T, dtype=jnp.int32)
        cos, sin = rope_cos_sin(positions, self.config.head_dim,
                                self.config.rope_theta)

        def attend(q, k, v, layer, pools):
            if self._rotates(layer):
                q = apply_rope_tables(q, cos[:, None], sin[:, None])
                k = apply_rope_tables(k, cos[:, None], sin[:, None])
            return self._attend_rows(q, k, v, 0, self._window(layer)), pools

        x = self._embed(params, tokens)
        x = self._walk(params, x, attend, None, jnp.ones((T,), bool))[0]
        return self._logits(params, x)

    # ------------------------------------------------------ serving steps
    def cache_classes(self, *, slots: int, pages_per_seq: int,
                      page_size: int, prefill_chunk: int):
        """The model's two page classes for ``slots`` slots of at most
        ``pages_per_seq`` pages of context: every slot's pages are there
        (a pool of ``1 + slots * pages`` pages a class); the window
        class's ring holds ``window + prefill_chunk`` tokens and one
        page."""
        from apex_tpu.serving.kv_cache import PageClass

        c = self.config
        ring = -(-(c.sliding_window + prefill_chunk) // page_size) + 1
        ring = min(ring, pages_per_seq)
        entry = dict(num_heads=c.num_key_value_heads, head_dim=c.head_dim)
        classes = []
        if self.full_layers:
            classes.append(PageClass(
                name="full", layers=self.full_layers,
                num_pages=1 + slots * pages_per_seq,
                pages_per_seq=pages_per_seq, **entry))
        if self.window_layers:
            classes.append(PageClass(
                name="window", layers=self.window_layers,
                num_pages=1 + slots * ring, pages_per_seq=ring,
                window=c.sliding_window, **entry))
        return tuple(classes)

    def _check_cache(self, cfg, prefill_chunk):
        c = self.config
        by_name = {cl.name: cl for cl in cfg.classes}
        want = {"full": (self.full_layers, 0),
                "window": (self.window_layers, c.sliding_window)}
        want = {k: v for k, v in want.items() if v[0]}
        got = {k: (cl.layers, cl.window) for k, cl in by_name.items()}
        if got != want or any(
                (cl.num_heads, cl.head_dim, cl.kind) != (
                    c.num_key_value_heads, c.head_dim, "kv")
                for cl in cfg.classes):
            raise ValueError(
                f"this model keeps K/V of {c.num_key_value_heads} heads x "
                f"{c.head_dim} in page classes {want} (name: layers, "
                f"window); the cache has {got}: build it with "
                "KVCacheConfig.of_classes(model.cache_classes(...))")
        if prefill_chunk is None or int(prefill_chunk) < 1 \
                or int(prefill_chunk) % cfg.page_size:
            raise ValueError(
                "this model ingests prompts in chunks: pass "
                "prefill_chunk, a multiple of the page size")
        ring = by_name.get("window")
        if ring is not None and ring.pages_per_seq < cfg.pages_per_seq \
                and ring.pages_per_seq * cfg.page_size < (
                    c.sliding_window + int(prefill_chunk) + cfg.page_size):
            raise ValueError(
                f"the window class's ring of {ring.pages_per_seq} pages "
                f"cannot hold window {c.sliding_window} + chunk "
                f"{prefill_chunk} + one page")

    def _class_view(self, cfg):
        """layer -> (its class's pool-key prefix, its index in the class,
        the class's pages, its table columns, ring width or 0)."""
        view = {}
        for cl, (lo, hi) in zip(cfg.classes, cfg.table_columns):
            for i, layer in enumerate(cl.layers):
                view[layer] = (cl.name, i, cl.num_pages, lo, hi,
                               cl.pages_per_seq if cl.window else 0)
        return view

    @staticmethod
    def _chunk_keys(window: int, end_page, C: int, ctx_len: int, page: int):
        """The keys a chunk of ``C`` tokens that ends on page
        ``end_page`` (traced or not) reads in a layer that sees
        ``window`` back (0: everything): (their first page, how many
        pages): a full layer's bucket from 0, a window layer's last
        ``window + C``."""
        if not window:
            return 0, ctx_len // page
        n_ctx = min(ctx_len // page, -(-(window + C) // page))
        return jnp.maximum(end_page - n_ctx, 0), n_ctx

    @staticmethod
    def _flat(pools, name):
        return tuple(pools[name + kv].reshape(
            (-1,) + pools[name + kv].shape[2:]) for kv in (".k", ".v"))

    def chunk_step(self, params, pools, toks, start, plen, write_from,
                   page_row, *, ctx_len: int, cache_config, table):
        """One prefill chunk: ``toks`` (C,) at positions ``start ..``;
        the chunk's K/V are written through ``page_row`` (both classes'
        columns side by side) as WHOLE pages, those that hold a position
        in [``write_from``, ``plen``) (``start`` and the chunk are
        page-aligned; a page's rows past ``plen`` are written too and
        seen by no query before a decode step has rewritten them);
        a full layer reads the slot's first ``ctx_len`` cached positions
        (static, ``>= start + C``), a window layer the last ``window +
        C`` of ``start + C``, out of its ring in position order.
        Returns (logits of position ``plen - 1`` (vocab,), pools)."""
        from apex_tpu.serving.kv_cache import (
            write_class_pages, write_targets,
        )

        c, cfg = self.config, cache_config
        page, C = cfg.page_size, toks.shape[0]
        view = self._class_view(cfg)
        positions = start + jnp.arange(C, dtype=jnp.int32)
        real = positions < plen
        cos, sin = self._rope_rows(table, positions)
        end_page = (start + C) // page                  # traced

        def attend(q, k, v, layer, pools):
            name, i, n_pages, lo, hi, ring = view[layer]
            window = self._window(layer)
            if self._rotates(layer):
                q = apply_rope_tables(q, cos[:, None], sin[:, None])
                k = apply_rope_tables(k, cos[:, None], sin[:, None])
            row = page_row[lo:hi]
            # the chunk is page-aligned: whole pages, those that hold a
            # token to write (the others go to the null page)
            p0 = start + jnp.arange(0, C, page, dtype=jnp.int32)
            pages, _ = write_targets(
                row, p0, (p0 < plen) & (p0 + page > write_from), page,
                ring=ring)
            pools = dict(pools, **{
                name + kv: write_class_pages(
                    pools[name + kv], i * n_pages, new, pages)
                for kv, new in ((".k", k), (".v", v))})
            with phase(f"attn.{name}.core"):
                first_page, n_ctx = self._chunk_keys(
                    window, end_page, C, ctx_len, page)
                if ring:
                    logical = first_page + jnp.arange(n_ctx, dtype=jnp.int32)
                    ctx_pages = jnp.take(row, logical % ring)
                else:
                    # a bucket may reach past the table: the null page
                    # there, at positions no query of the chunk sees
                    ctx_pages = jnp.take(
                        row, jnp.arange(n_ctx, dtype=jnp.int32),
                        mode="fill", fill_value=0)
                fk, fv = self._flat(pools, name)
                # (pages, Hkv, page, d) -> (S, Hkv, d) in position order
                rows = lambda f: jnp.moveaxis(
                    f[i * n_pages + ctx_pages], 1, 2).reshape(
                        n_ctx * page, c.num_key_value_heads, c.head_dim)
                o = self._attend_rows(q, rows(fk), rows(fv),
                                      start - first_page * page, window)
            return o, pools

        x = self._embed(params, toks)
        x, pools, _, _ = self._walk(params, x, attend, pools, real)
        last = jnp.take(x, jnp.clip(plen - 1 - start, 0, C - 1), axis=0)
        return self._logits(params, last[None])[0], pools

    def decode_step(self, params, pools, tokens, positions, active,
                    page_table, *, cache_config, table):
        """One token for every slot: ``tokens`` (B,) at ``positions``
        (B,) (the slot's context length), ``active`` (B,) bool.  Each
        layer writes the new K/V (a window layer's into its ring) and
        attends through the paged decode kernel: a full layer over the
        slot's pages from 0, a window layer from the page that holds
        ``length - window``.  Returns (fp32 logits (B, vocab), pools,
        counters (8,): the expert layers' four, window rows, full rows,
        context rows, live slots x layers; the attention outputs of the
        last window and the last full layer (2, B, Hq * d) fp32)."""
        from apex_tpu.serving.kv_cache import (
            write_class_rows, write_targets,
        )

        c, cfg = self.config, cache_config
        page = cfg.page_size
        view = self._class_view(cfg)
        B = tokens.shape[0]
        cos, sin = self._rope_rows(table, positions)
        lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)
        rows_read = {"window": jnp.float32(0), "full": jnp.float32(0)}

        def attend(q, k, v, layer, pools):
            name, i, n_pages, lo, hi, ring = view[layer]
            window, rotates = self._window(layer), self._rotates(layer)
            if rotates:
                # the kernel rotates q (after its head norm); a key is
                # rotated once, here, before it is written
                k = apply_rope_tables(k, cos[:, None], sin[:, None])
            tbl = page_table[:, lo:hi]
            pages, offsets = write_targets(tbl, positions, active, page,
                                           ring=ring)
            pools = dict(pools, **{
                name + kv: write_class_rows(
                    pools[name + kv], i * n_pages, new, pages, offsets)
                for kv, new in ((".k", k), (".v", v))})
            first = jnp.maximum(lengths - window, 0) if window else None
            with phase(f"attn.{name}.core"):
                fk, fv = self._flat(pools, name)
                o = fmha_decode(
                    q[:, :, None, :], fk, fv, tbl + i * n_pages, lengths,
                    causal=True, sm_scale=c.softmax_scale,
                    rope=(cos[:, None], sin[:, None]) if rotates else None,
                    num_kv_heads=c.num_key_value_heads, first=first,
                    max_pages=window // page + 1 if window else None)
            read = lengths - (first // page * page if window else 0)
            rows_read[name] = rows_read[name] + jnp.sum(read).astype(
                jnp.float32)
            return o.reshape(B, -1), pools

        x = self._embed(params, tokens)
        x, pools, counted, kept = self._walk(params, x, attend, pools, active)
        live = jnp.sum(active).astype(jnp.float32)
        stats = jnp.concatenate([counted, jnp.stack([
            rows_read["window"], rows_read["full"],
            jnp.sum(lengths).astype(jnp.float32),
            live * c.num_hidden_layers])])
        shown = jnp.stack([
            kept[(self.window_layers or (0,))[-1]],
            kept[(self.full_layers or (0,))[-1]]]).astype(jnp.float32)
        return self._logits(params, x), pools, stats, shown

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
        chunks one after another).  ``cache_config`` is
        ``KVCacheConfig.of_classes(self.cache_classes(...))``; ``params``
        and its ``init_pools`` dict are expected on ``mesh``, replicated.

        Each step takes the pools DONATED and returns them updated in
        place.  ``decode`` keeps in the carry, beside the batcher's five
        per-slot entries (``decode.carry_extras``): ``counters`` grown by
        ``COUNTER_NAMES`` a step, the step's ``last_logits`` (slots,
        vocab) and ``last_attn`` (2, slots, Hq * d).  ``chunk`` compiles
        once per context BUCKET (``prefill_chunk`` times a power of two,
        and the slot bound): a full layer reads the bucket's pages and
        skips what lies past the chunk."""
        from apex_tpu.serving.kv_cache import init_pools
        from apex_tpu.serving.sampling import advance_slots, sample

        c, cfg = self.config, cache_config
        self._check_cache(cfg, prefill_chunk)
        if max_prompt_len > cfg.max_len:
            raise ValueError(
                f"max_prompt_len {max_prompt_len} exceeds the slot bound "
                f"{cfg.max_len} (pages_per_seq * page_size)")
        C, page, max_len = int(prefill_chunk), cfg.page_size, cfg.max_len
        table = self.rope_table(max_len)
        S = cfg.max_seqs
        # the longest context a chunk reads: the last chunk of the
        # longest prompt (whole chunks: it may reach past the table)
        top = -(-int(max_prompt_len) // C) * C
        buckets = sorted({min(C << i, top)
                          for i in range((top // C).bit_length() + 1)})
        carry_extras = {
            "counters": jnp.zeros((len(COUNTER_NAMES),), jnp.float32),
            "last_logits": jnp.zeros((S, c.vocab_size), jnp.float32),
            "last_attn": jnp.zeros(
                (2, S, c.num_attention_heads * c.head_dim), jnp.float32),
        }

        @phase("prefill")
        def _chunk(params, pools, toks, start, plen, write_from, page_row,
                   key, *, ctx_len):
            logits, pools = self.chunk_step(
                params, pools, toks[0], start, plen, write_from, page_row,
                ctx_len=ctx_len, cache_config=cfg, table=table)
            tok = sample(logits[None], jax.random.fold_in(key, plen),
                         temperature, top_k, top_p)[0]
            return pools, tok, logits

        @phase("decode")
        def _decode(params, pools, carry, page_table):
            active = jnp.logical_not(carry["done"])
            logits, pools, stats, shown = self.decode_step(
                params, pools, carry["tokens"], carry["lengths"], active,
                page_table, cache_config=cfg, table=table)
            counted = jnp.concatenate([jnp.ones((1,), jnp.float32), stats])
            return pools, {
                **advance_slots(carry, logits, active,
                                temperature=temperature, top_k=top_k,
                                top_p=top_p, eos_id=eos_id),
                "counters": carry["counters"] + counted,
                "last_logits": logits, "last_attn": shown}

        _programs.own(_chunk.__name__, _decode.__name__,
                      layer="serving steps")
        cj = jax.jit(_chunk, donate_argnums=(1,), static_argnames=("ctx_len",))
        dj = jax.jit(_decode, donate_argnums=(1,))

        def bucket(start: int) -> int:
            return next(b for b in buckets if b >= min(start + C, top))

        def chunk(pools, toks, start, plen, write_from, row, key):
            start = int(start)
            return cj(params, pools,
                      jnp.asarray(toks, jnp.int32).reshape(1, C),
                      jnp.int32(start), jnp.int32(plen),
                      jnp.int32(write_from), row, key, ctx_len=bucket(start))

        @functools.lru_cache(maxsize=None)
        def k_blocks(start: int):
            """(key blocks the chunk at ``start`` computes, key blocks
            of the contexts it reads), one K/V head's, summed over the
            layers: what the chunk's positions leave of its attention
            (``ops.attention.k_blocks_run``, the kernel's own bounds)."""
            G = c.num_attention_heads // c.num_key_value_heads
            counts = np.zeros((2,), np.int64)
            for layer in range(c.num_hidden_layers):
                window = self._window(layer)
                first_page, n_ctx = self._chunk_keys(
                    window, (start + C) // page, C, bucket(start), page)
                counts += k_blocks_run(
                    G * C, n_ctx * page, start - int(first_page) * page, C,
                    window, dtype=c.params_dtype)
            return int(counts[0]), int(counts[1])

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
        chunk.ctx_buckets = tuple(buckets)
        chunk.k_blocks = k_blocks
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

