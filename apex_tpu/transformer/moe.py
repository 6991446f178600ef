"""Mixture-of-experts with expert parallelism (EP).

Beyond-reference capability: SURVEY.md §2.3 records expert parallelism
as **absent** from the reference snapshot.  TPU-native design:

- Switch-style top-1 routing, or GShard/Mixtral-style top-k (renormalized
  gates, choice-major capacity priority, optional ST-MoE router z-loss),
  with a fixed per-(expert, source-rank) capacity — static shapes, so
  the whole layer jits;
- experts sharded over an **expert-parallel mesh axis** (default "dp",
  the usual Megatron choice: expert weights ride the data-parallel
  ranks); tokens travel to their expert's rank and back with two
  ``lax.all_to_all`` collectives over ICI;
- the ffn dim of each expert is additionally **tensor-parallel** over
  "tp" (column-then-row pattern with a psum, exactly like the dense
  MLP);
- gradients need no special handling: expert params are ep-varying in
  shard_map's vma type system, so autodiff yields per-expert grads while
  replicated router grads come back already summed across dp.

Returns the Switch auxiliary load-balance loss alongside the output.

:class:`HeldExpertsMLP` is the SERVING expert layer: no capacity, no
dropped token, the DeepSeek-V3 router (sigmoid scores, bias-corrected
group-limited choice), a shared expert, and an argument that says which
experts this chip holds.  It is the same function in prefill chunks and
in decode.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.moe_grouped import grouped_swiglu
from apex_tpu.telemetry.spans import phase
from apex_tpu.transformer.parallel_state import (
    DATA_PARALLEL_AXIS,
    TENSOR_PARALLEL_AXIS,
)
from apex_tpu.utils.platform import default_implementation

__all__ = ["MoEMLP", "HeldExpertsMLP"]

#: rows an expert can expect (``n * top_k // num_experts``) from which
#: :meth:`HeldExpertsMLP.apply` multiplies its tiles in one grouped
#: Mosaic product, not in a loop: a whole 128-row tile.  From there the
#: grouped form won every measurement (PERF.md section 6, PR 38: by 41-45
#: % at Xing4's widths with every expert held, by 4-10 % at
#: DeepSeek-V3.2's with 16 of 256); at 64 it depends on the share held
GROUPED_MIN_ROWS = 128


class MoEMLP:
    """Expert-parallel Switch MLP.

    ``num_experts`` must divide by the expert-parallel axis size; each
    rank hosts ``num_experts/ep`` experts.  ``capacity_factor`` scales
    the per-(expert, source-rank) token budget; overflow tokens are
    dropped (their output is zero — the caller's residual carries them),
    the standard Switch behaviour.
    """

    def __init__(
        self,
        hidden_size: int,
        ffn_hidden_size: int,
        num_experts: int,
        *,
        top_k: int = 1,
        capacity_factor: float = 1.25,
        router_z_loss_weight: float = 0.0,
        ep_axis: str = DATA_PARALLEL_AXIS,
        tp_axis: str = TENSOR_PARALLEL_AXIS,
        params_dtype: Any = jnp.float32,
        init_std: float = 0.02,
    ):
        if not 1 <= top_k <= num_experts:
            raise ValueError(
                f"top_k ({top_k}) must be in [1, num_experts={num_experts}]"
            )
        self.hidden_size = hidden_size
        self.ffn_hidden_size = ffn_hidden_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.router_z_loss_weight = router_z_loss_weight
        self.ep_axis = ep_axis
        self.tp_axis = tp_axis
        self.params_dtype = params_dtype
        self.init_std = init_std

    def init(self, key) -> Dict[str, Any]:
        k1, k2, k3 = jax.random.split(key, 3)
        std = self.init_std
        return {
            "router": {
                "weight": std * jax.random.normal(
                    k1, (self.hidden_size, self.num_experts),
                    self.params_dtype,
                )
            },
            "w1": std * jax.random.normal(
                k2,
                (self.num_experts, self.hidden_size, self.ffn_hidden_size),
                self.params_dtype,
            ),
            "w2": std * jax.random.normal(
                k3,
                (self.num_experts, self.ffn_hidden_size, self.hidden_size),
                self.params_dtype,
            ),
        }

    def param_specs(self) -> Dict[str, Any]:
        return {
            "router": {"weight": P()},
            "w1": P(self.ep_axis, None, self.tp_axis),
            "w2": P(self.ep_axis, self.tp_axis, None),
        }

    def apply(
        self, params: Dict[str, Any], x: jnp.ndarray
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """x: (b, s, h) local tokens — call inside shard_map.  Returns
        (output (b, s, h), aux load-balance loss scalar).

        Dispatch uses the one-hot + cumsum position assignment and
        one-hot-einsum send/return contractions — the standard
        static-shape TPU MoE pattern (Mesh-TensorFlow/Switch): no
        scatters or gathers, everything rides the MXU.  The dispatch
        mask is (n, E, cap) ≈ cf·k·n² entries (cap ≈ cf·k·n/E), e.g.
        ~50 MB bf16 at n=4096 per-rank tokens for top-1 at cf=1.25, and
        k× that for top-k (plus the transient (k, n, E, cap) ``mask_k``
        buffer, another k× before it collapses); n here is the
        *per-rank* token count under dp/ep sharding, not the global
        batch."""
        b, s, h = x.shape
        n = b * s
        E = self.num_experts
        k = self.top_k
        ep = jax.lax.axis_size(self.ep_axis)
        e_local = E // ep
        # expected assignments per expert: k*n/E (each token makes k
        # choices — GShard/ST-MoE convention)
        cap = max(1, int(self.capacity_factor * k * n / E))

        flat = x.reshape(n, h)
        logits = jnp.matmul(
            flat.astype(jnp.float32),
            params["router"]["weight"].astype(jnp.float32),
        )
        probs = jax.nn.softmax(logits, axis=-1)          # (n, E)
        topk_probs, topk_idx = lax.top_k(probs, k)       # (n, k)
        if k == 1:
            # Switch convention: the gate IS the chosen prob (pushes the
            # router toward confident assignments)
            gates = topk_probs
        else:
            # GShard/Mixtral convention: renormalize over the k chosen
            gates = topk_probs / jnp.sum(topk_probs, -1, keepdims=True)

        one_hot_k = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)

        # load-balance aux (Switch for k=1, its k-choice generalization
        # otherwise): E * Σ_e (fraction of the n*k assignments to e) ·
        # (mean router prob of e)
        frac = jnp.sum(one_hot_k, axis=(0, 1)) / (n * k)
        mean_prob = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(frac * mean_prob)
        if self.router_z_loss_weight:
            # ST-MoE router z-loss: keeps router logits small so the
            # fp32 softmax stays well-conditioned
            z = jax.scipy.special.logsumexp(logits, axis=-1)
            aux = aux + self.router_z_loss_weight * jnp.mean(z * z)

        # capacity positions with choice-major priority (every token's
        # 1st choice outranks all 2nd choices — GShard): flatten the
        # (k, n) assignment grid and cumsum down it
        oh = jnp.moveaxis(one_hot_k, 1, 0).reshape(k * n, E)
        pos = jnp.cumsum(oh, axis=0) * oh                # (k*n, E)
        pos = jnp.sum(pos, axis=-1).astype(jnp.int32) - 1
        keep = pos < cap

        # dispatch buffers: (E, cap, h), one slot per routed assignment.
        # Built with a one-hot einsum, not scatter-add: scatters serialize
        # on TPU while the (n,E,cap)x(n,h) contraction rides the MXU —
        # the Mesh-TensorFlow/Switch dispatch pattern
        safe_pos = jnp.where(keep, pos, 0)
        # masks built directly in compute dtype: (k, n, E, cap), then the
        # k choices collapse — a token's k experts are distinct, so the
        # summed masks never collide in a slot
        mask_k = (
            oh.astype(x.dtype)[:, :, None]
            * jax.nn.one_hot(safe_pos, cap, dtype=x.dtype)[:, None, :]
            * keep[:, None, None].astype(x.dtype)
        ).reshape(k, n, E, cap)
        dispatch_mask = jnp.sum(mask_k, axis=0)          # (n, E, cap)
        gates_k = jnp.moveaxis(gates, 1, 0).astype(x.dtype)  # (k, n)
        combine_mask = jnp.sum(
            mask_k * gates_k[:, :, None, None], axis=0
        )                                                # (n, E, cap)
        dispatch = jnp.einsum("nec,nh->ech", dispatch_mask, flat)

        # tokens → expert ranks: tiled all_to_all over the expert dim.
        # received block i holds source-rank i's tokens for MY experts
        recv = lax.all_to_all(
            dispatch, self.ep_axis, split_axis=0, concat_axis=0, tiled=True
        )                                                # (ep*e_local, cap, h)
        recv = recv.reshape(ep, e_local, cap, h)
        recv = jnp.moveaxis(recv, 0, 1).reshape(e_local, ep * cap, h)

        # local experts, ffn dim tensor-parallel (column then row + psum)
        w1 = params["w1"].astype(x.dtype)                # (e_local, h, f/tp)
        w2 = params["w2"].astype(x.dtype)                # (e_local, f/tp, h)
        h1 = jnp.einsum("ech,ehf->ecf", recv, w1)
        h1 = jax.nn.gelu(h1, approximate=True)
        h2 = jnp.einsum("ecf,efh->ech", h1, w2)
        h2 = lax.psum(h2, self.tp_axis)

        # expert ranks → tokens: inverse all_to_all
        back = h2.reshape(e_local, ep, cap, h)
        back = jnp.moveaxis(back, 1, 0).reshape(ep * e_local, cap, h)
        combined = lax.all_to_all(
            back, self.ep_axis, split_axis=0, concat_axis=0, tiled=True
        )                                                # (E, cap, h)

        # gather-back is the transposed one-hot contraction (MXU, no
        # gather); combine_mask carries each assignment's gate and
        # already zeroes capacity-dropped ones, so the k expert outputs
        # mix as Σ_i gate_i · expert_i(x) exactly
        out = jnp.einsum(
            "nec,ech->nh", combine_mask, combined.astype(x.dtype)
        )
        return out.reshape(b, s, h), aux

    def decode(self, *args, **kwargs):
        """Serving through THIS layer kind is refused, loudly.

        ``MoEMLP`` is the training layer: a fixed per-(expert,
        source-rank) capacity that DROPS overflow tokens (a training
        regulariser, a corrupted generation when serving) and two
        ``all_to_all`` hops sized for whole sequences.  Which expert
        layers serve:

        - :class:`HeldExpertsMLP` (sigmoid scores, group-limited top-k,
          shared expert, no capacity) serves prefill chunks and decode
          on the chip that holds some of the experts; it is what
          ``models/deepseek_v32.py`` builds its ``decode_fns`` from.
        - ``MoEMLP`` (softmax top-k with capacity) does not, and
          ``GPTModel.decode_fns`` calls this method to say so at build
          time.  Serving it needs the same no-drop grouped computation
          behind its own router, plus the token exchange between
          expert-parallel ranks that neither layer has yet
          (ROADMAP.md, "what the system still cannot run").
        """
        raise NotImplementedError(
            "MoEMLP.decode: the capacity-bounded training layer drops "
            "tokens and cannot serve.  The no-drop serving expert layer "
            "is apex_tpu.transformer.moe.HeldExpertsMLP (used by "
            "apex_tpu.models.deepseek_v32); GPTModel has no serving "
            "path for its softmax/capacity expert layer."
        )


class HeldExpertsMLP:
    """No-drop expert layer for the chip that holds SOME of the experts.

    The router scores all ``num_experts`` with the published rule
    (DeepSeek-V3, ``noaux_tc``): ``s = sigmoid(x W_r)`` in fp32; the
    choice runs on ``c = s + b`` (``b`` the load-balancing correction
    bias) — a group's score is the sum of its two largest ``c``, the
    ``topk_group`` best of ``n_group`` groups stay, the ``top_k``
    largest ``c`` inside them are the chosen set ``T``; the weights
    come from ``s``: ``g_e = routed_scaling_factor * s_e / (sum_{T} s +
    1e-20)``, normalised over ALL of ``T`` whether held here or not.

    ``held`` (a static tuple of expert ids, in the order of the stacked
    expert weights) is an argument of :meth:`apply`: the layer computes
    ``Shared(x) + sum_{e in T and held} g_e Expert_e(x)``.  Summed over
    a partition of the experts, with the shared expert counted once,
    the shares give the whole layer (tested).  There is no capacity and
    no token is dropped; on one chip there is no exchange and nothing
    stands in for the absent chips.

    **Grouped computation.**  The (token, choice) pairs that landed on
    held experts are sorted by expert and laid out in tiles of
    ``tile_rows`` rows, each tile one expert's; the tiles IN USE (a
    dynamic count) are multiplied by their experts' three matrices, and
    every token then gathers and weights its own rows.  Work and
    weight traffic follow the pairs that exist: an expert nobody chose
    is not read, and there is no (n, E, capacity) dispatch mask.

    One layout, two forms of the products, told apart by a shape: the
    rows an expert can expect, ``n * top_k // num_experts``.

    - Under 128 (``GROUPED_MIN_ROWS``) — every decode step (tiles of 16
      rows, under 32) and a chunk whose experts expect less than a
      128-row tile each: an XLA loop over the tiles that slices each
      tile's expert out of the stack.  It reads exactly the experts
      touched, once each while an expert owns one tile, and that read
      is what bounds a step.
    - 128 or more (a long prefill chunk over few experts: experts own
      two tiles and more, the hot ones of a skewed router many).  The
      loop would read an expert's weights again for EVERY tile, and a
      128-row tile does 128 FLOPs a weight byte where a v5e's ridge is
      240: each tile is bound by the re-read.  On a TPU, for weights
      narrower than float32, the tiles go through
      :mod:`apex_tpu.ops.moe_grouped` instead: one Mosaic product that
      copies an expert's weights once however many tiles it owns (FLOPs
      a weight byte then grow with the expert's rows), the same
      arithmetic in the same precisions.  Off the TPU and for float32
      (the references) the 128-row loop stays.

    The grouped form gathers every row of the static bound of tiles,
    live or not, so where few of the experts are held (the bound is for
    all pairs landing here) its layout costs more than its product
    saves at small loads: at 64 rows an expert it gains 21 % with all 64
    of 64 held and loses 21 % with 16 of 256.
    """

    #: what :meth:`apply` counts, in this order; the load of each held
    #: expert (rows it was given) follows them
    COUNTERS = ("choices", "choices_held", "experts_touched", "load_max")

    def __init__(
        self,
        hidden_size: int,
        ffn_hidden_size: int,
        num_experts: int,
        *,
        top_k: int,
        n_group: int = 1,
        topk_group: int = 1,
        routed_scaling_factor: float = 1.0,
        n_shared_experts: int = 1,
        params_dtype: Any = jnp.bfloat16,
        init_std: Optional[float] = None,
    ):
        if num_experts % n_group:
            raise ValueError(
                f"num_experts ({num_experts}) must divide into n_group "
                f"({n_group}) groups")
        if not 1 <= topk_group <= n_group:
            raise ValueError(
                f"topk_group ({topk_group}) must be in [1, {n_group}]")
        if top_k > topk_group * (num_experts // n_group):
            raise ValueError(
                f"top_k ({top_k}) exceeds the experts in {topk_group} "
                f"groups of {num_experts // n_group}")
        self.hidden_size = hidden_size
        self.ffn_hidden_size = ffn_hidden_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.n_group = n_group
        self.topk_group = topk_group
        self.routed_scaling_factor = routed_scaling_factor
        self.n_shared_experts = n_shared_experts
        self.params_dtype = params_dtype
        self.init_std = init_std

    # ----------------------------------------------------------- params
    def init(self, key, num_held: int) -> Dict[str, Any]:
        """Seeded weights for ``num_held`` experts.  The correction bias
        is NON-zero (uniform in +-0.1: it moves the choice without
        deciding it) so that a test tells the choice scores ``c`` from
        the weights' ``s``."""
        h, f, E = self.hidden_size, self.ffn_hidden_size, self.num_experts
        fs = f * self.n_shared_experts
        ks = jax.random.split(key, 8)
        std = lambda fan_in: self.init_std or fan_in ** -0.5
        w = lambda k, shape, fan_in: (
            std(fan_in) * jax.random.normal(k, shape, jnp.float32)
        ).astype(self.params_dtype)
        return {
            "router": {
                "weight": w(ks[0], (h, E), h),
                "bias": jax.random.uniform(
                    ks[1], (E,), jnp.float32, -0.1, 0.1),
            },
            "experts": {
                "w_gate": w(ks[2], (num_held, h, f), h),
                "w_up": w(ks[3], (num_held, h, f), h),
                "w_down": w(ks[4], (num_held, f, h), f),
            },
            "shared": {
                "w_gate": w(ks[5], (h, fs), h),
                "w_up": w(ks[6], (h, fs), h),
                "w_down": w(ks[7], (fs, h), fs),
            },
        }

    def param_specs(self) -> Dict[str, Any]:
        return {
            "router": {"weight": P(), "bias": P()},
            "experts": {"w_gate": P(), "w_up": P(), "w_down": P()},
            "shared": {"w_gate": P(), "w_up": P(), "w_down": P()},
        }

    # ------------------------------------------------------------ route
    def route(self, params: Dict[str, Any], x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """``x`` (n, h) -> (chosen experts (n, top_k) int32, weights
        ``g`` (n, top_k) fp32), over all ``num_experts``."""
        E, G = self.num_experts, self.n_group
        with phase("moe.route"):
            # fp32 THROUGHOUT when the caller hands fp32 in (six bf16
            # passes on a TPU, for num_experts outputs): a score rounded
            # to bf16 flips a choice at the k-th place now and then, and
            # a flipped expert is a visibly different token
            s = jax.nn.sigmoid(jnp.matmul(
                x, params["router"]["weight"].astype(x.dtype),
                precision=(lax.Precision.HIGHEST
                           if x.dtype == jnp.float32 else None),
                preferred_element_type=jnp.float32))
            c = s + params["router"]["bias"].astype(jnp.float32)
            if G > 1:
                group_score = jnp.sum(
                    lax.top_k(c.reshape(-1, G, E // G), 2)[0], axis=-1)
                kept = lax.top_k(group_score, self.topk_group)[1]
                group_ok = jnp.any(
                    kept[:, :, None] == jnp.arange(G)[None, None], axis=1)
                c = jnp.where(jnp.repeat(group_ok, E // G, axis=1),
                              c, -jnp.inf)
            chosen = lax.top_k(c, self.top_k)[1]
            weight = jnp.take_along_axis(s, chosen, axis=1)
            g = self.routed_scaling_factor * weight / (
                jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
            return chosen.astype(jnp.int32), g

    # ---------------------------------------------------------- experts
    @staticmethod
    def _swiglu(x, w_gate, w_up, w_down):
        a = jnp.matmul(x, w_gate, preferred_element_type=jnp.float32)
        b = jnp.matmul(x, w_up, preferred_element_type=jnp.float32)
        return jnp.matmul((jax.nn.silu(a) * b).astype(x.dtype), w_down,
                          preferred_element_type=jnp.float32)

    def _experts(self, experts, x, chosen, g, held, token_valid,
                 tile_rows: int, layer=None, grouped: bool = False):
        """Gathers and dynamic slices only, no scatter: rows find their
        tokens through the sorted order, tokens find their rows through
        a running count per expert.  ``grouped``: the tiles go to
        :func:`~apex_tpu.ops.moe_grouped.grouped_swiglu` in one call
        and not through the loop one by one."""
        n, h = x.shape
        k, nh = self.top_k, len(held)
        T = tile_rows
        lookup = np.full((self.num_experts,), nh, np.int32)
        lookup[list(held)] = np.arange(nh, dtype=np.int32)
        # one entry per (token, choice) pair; nh marks "not held here"
        expert = jnp.where(token_valid[:, None],
                           jnp.asarray(lookup)[chosen], nh).reshape(-1)
        onehot = expert[:, None] == jnp.arange(nh, dtype=jnp.int32)[None]
        sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)
        tiles = -(-sizes // T)
        row_end = jnp.cumsum(tiles) * T         # padded layout, per expert
        row_start = row_end - tiles * T
        src_start = jnp.cumsum(sizes) - sizes   # sorted layout
        n_tiles = -(-(n * k) // T) + nh         # static bound
        order = jnp.argsort(expert, stable=True)
        # tile t belongs to the expert whose padded range holds it
        tile_expert = jnp.minimum(jnp.searchsorted(
            row_end, jnp.arange(n_tiles, dtype=jnp.int32) * T,
            side="right"), nh - 1).astype(jnp.int32)

        def weight(w, e):
            # ONE slice out of the stack(s): a layer's experts are never
            # cut out whole (1.4 GB a layer at the published widths)
            if layer is None:
                return lax.dynamic_index_in_dim(w, e, 0, False)
            return lax.dynamic_slice(
                w, (layer, e, 0, 0), (1, 1) + w.shape[2:])[0, 0]

        def tile(t, buf):
            e = tile_expert[t]
            offset = t * T - row_start[e] + jnp.arange(T, dtype=jnp.int32)
            pair = order[jnp.clip(src_start[e] + offset, 0, n * k - 1)]
            rows = jnp.where((offset < sizes[e])[:, None], x[pair // k], 0)
            out = self._swiglu(rows, *(
                weight(experts[name], e)
                for name in ("w_gate", "w_up", "w_down")))
            return lax.dynamic_update_slice(
                buf, out.astype(buf.dtype), (t * T, 0))

        if grouped:
            # every tile's rows in ONE gather, every expert's weights
            # fetched once.  Rows past an expert's pairs are not zeroed
            # as ``tile`` zeroes them (a pass over all the rows, 0.5 ms
            # a layer at Xing4's chunk): they hold some token's row, so
            # what is computed for them is finite, and no pair reads it.
            # Tile 0 always runs, so that row 0, which the pairs not
            # held here read (weighted by 0), is written.
            e = jnp.repeat(tile_expert, T)
            offset = jnp.arange(n_tiles * T, dtype=jnp.int32) - row_start[e]
            pair = order[jnp.clip(src_start[e] + offset, 0, n * k - 1)]
            buf = grouped_swiglu(
                x[pair // k],
                *(experts[name] for name in ("w_gate", "w_up", "w_down")),
                tile_expert, jnp.maximum(jnp.sum(tiles), 1),
                0 if layer is None else layer)
        else:
            buf = lax.fori_loop(0, jnp.sum(tiles), tile,
                                jnp.zeros((n_tiles * T, h), x.dtype))
        # a pair's row: its expert's first row plus how many earlier
        # pairs chose the same expert (the stable sort's order)
        within = jnp.sum(jnp.where(
            onehot, jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1, 0),
            axis=1)
        live = expert < nh
        row = jnp.where(live, row_start[jnp.minimum(expert, nh - 1)]
                        + within, 0)
        gate = jnp.where(live, g.reshape(-1), 0.0)
        y = jnp.sum((buf[row].astype(jnp.float32) * gate[:, None]
                     ).reshape(n, k, h), axis=1)
        counters = jnp.concatenate([jnp.stack([
            jnp.sum(token_valid).astype(jnp.float32) * k,
            jnp.sum(sizes).astype(jnp.float32),
            jnp.sum(sizes > 0).astype(jnp.float32),
            jnp.max(sizes).astype(jnp.float32),
        ]), sizes.astype(jnp.float32)])
        return y, counters

    def apply(
        self,
        params: Dict[str, Any],
        x: jnp.ndarray,
        held: Tuple[int, ...],
        *,
        token_valid: Optional[jnp.ndarray] = None,
        tile_rows: Optional[int] = None,
        expert_layer=None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """``x`` (n, h) -> (output (n, h) in the weights' dtype, counters
        fp32 (4 + len(held),): in the order of :attr:`COUNTERS` the
        choices made by the valid tokens, those that landed on ``held``
        experts, distinct held experts touched, the largest load among
        them; then each held expert's load).

        ``held`` names the experts whose weights ``params['experts']``
        stacks, in that order.  ``token_valid`` (n,) marks padding rows
        of a prefill chunk and idle decode slots: they route nothing and
        count nothing.  ``tile_rows`` defaults to 128 where an expert
        can expect 32 rows or more, else 16; which form multiplies the
        tiles is decided here and is no argument (class docstring,
        "Grouped computation").  With ``expert_layer`` (a traced scalar is fine) the leaves of
        ``params['experts']`` keep a leading layer axis and that layer's
        experts are used: a model that scans over its layers hands the
        whole stack in, so that no layer's experts are sliced out of it.
        The ROUTER reads ``x`` as it comes (fp32 in, fp32 scores); the
        experts read it rounded to the weights' dtype.
        """
        n = x.shape[0]
        held = tuple(int(e) for e in held)
        stacked = params["experts"]["w_gate"].shape[
            0 if expert_layer is None else 1]
        if len(held) != stacked:
            raise ValueError(
                f"held names {len(held)} experts but the weights stack "
                f"{stacked}")
        if token_valid is None:
            token_valid = jnp.ones((n,), bool)
        dtype = params["experts"]["w_gate"].dtype
        expected = n * self.top_k // self.num_experts
        grouped = (expected >= GROUPED_MIN_ROWS and dtype != jnp.float32
                   and default_implementation() == "pallas")
        if tile_rows is None:
            tile_rows = 128 if expected >= 32 else 16
        chosen, g = self.route(params, x)
        x = x.astype(dtype)
        with phase("moe.experts"):
            y, counters = self._experts(
                params["experts"], x, chosen, g, held, token_valid,
                tile_rows, expert_layer, grouped)
        with phase("moe.shared"):
            sh = params["shared"]
            y = y + self._swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"])
        return y.astype(x.dtype), counters
