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
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer.parallel_state import (
    DATA_PARALLEL_AXIS,
    TENSOR_PARALLEL_AXIS,
)

__all__ = ["MoEMLP"]


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
        """Single-token serving decode through the expert layer —
        NOT implemented; raises loudly rather than silently serving a
        dense approximation.

        The training path above is built around fixed per-(expert,
        source-rank) capacity and two ``lax.all_to_all`` hops sized for
        full sequences; a decode step routes ONE token per slot, so
        the same capacity math degenerates (cap rounds up to 1 and the
        all_to_all moves mostly padding).  A real expert-parallel
        decode wants: (a) slot-major top-k routing with no capacity
        drops (a dropped token is a corrupted generation, not a
        training regularizer), (b) expert weights resident per ep rank
        with the token batch gathered to its experts — an all_to_all
        over at most ``max_seqs`` rows, or replicated experts below
        the memory crossover, and (c) the page-table/sampler contract
        untouched (routing is per-token state-free, so the paged KV
        pool and the per-slot key schedule need no changes).  That is
        its own PR; until then the serving stack refuses MoE models at
        decode_fns-build time via this error.
        """
        raise NotImplementedError(
            "MoEMLP.decode: expert-parallel serving decode is not "
            "implemented — the training path's capacity-bounded "
            "all_to_all does not degenerate safely to one token per "
            "slot (see the design note in MoEMLP.decode's docstring). "
            "Serve a dense-MLP model, or distill the experts before "
            "deployment."
        )
