"""FusedAdam — Adam/AdamW over the whole parameter pytree.

Math matches the reference kernel exactly
(reference: apex/optimizers/fused_adam.py:4-173,
csrc/multi_tensor_adam.cu): fp32 moments, optional bias correction,
``adam_w_mode`` toggling decoupled (AdamW) vs L2 (classic Adam) weight
decay.  The reference's per-dtype kernel grouping
(fused_adam.py:134-145) is unnecessary here — XLA fuses the pytree
update regardless of leaf dtypes.

Two TPU-native extensions beyond the reference surface (both default
off / parity-preserving):

- ``fused_tail=True`` packs moments + fp32 masters into the PR 4
  bucket plans' contiguous buffers and runs the whole
  unscale → clip → moment update → cast chain as ONE multi-tensor
  pass per buffer (:mod:`apex_tpu.optimizers.fused_tail`) —
  bit-identical at default settings, targeting the measured
  440 → 819 GB/s optimizer-tail bandwidth gap (PROFILE_r05.json);
- ``exp_avg_sq_dtype=jnp.bfloat16`` stores the second moment sub-fp32
  (math stays fp32; only the storage rounds).  Halves the
  ``exp_avg_sq`` bytes the tail reads and writes; safe for typical
  LLM pretraining where ``sqrt(v)`` tolerates ~3 decimal digits, but
  opt-in because it breaks the fp32-parity contract with the
  reference ``csrc/multi_tensor_adam.cu`` math (docs/optimizers.md).
- ``max_grad_norm`` folds a global-norm gradient clip into the same
  pass (the clip FusedLAMB always had; None = reference parity).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from apex_tpu.multi_tensor_apply import global_l2norm
from apex_tpu.optimizers.base import FusedOptimizer, f32

__all__ = ["FusedAdam"]


class FusedAdam(FusedOptimizer):
    def __init__(
        self,
        lr: float = 1e-3,
        bias_correction: bool = True,
        betas=(0.9, 0.999),
        eps: float = 1e-8,
        adam_w_mode: bool = True,
        weight_decay: float = 0.0,
        amsgrad: bool = False,
        master_weights: bool = False,
        max_grad_norm: Optional[float] = None,
        fused_tail: bool = False,
        bucket_bytes: Optional[int] = None,
        exp_avg_sq_dtype: Any = jnp.float32,
    ):
        if amsgrad:
            raise RuntimeError("FusedAdam does not support the AMSGrad variant.")
        super().__init__(lr=lr, master_weights=master_weights,
                         fused_tail=fused_tail, bucket_bytes=bucket_bytes)
        self.bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay
        self.max_grad_norm = max_grad_norm
        self.exp_avg_sq_dtype = jnp.dtype(exp_avg_sq_dtype)
        if not jnp.issubdtype(self.exp_avg_sq_dtype, jnp.floating):
            raise ValueError(
                f"exp_avg_sq_dtype must be floating, got "
                f"{self.exp_avg_sq_dtype}"
            )

    def _init_extra(self, params: Any) -> dict:
        zeros = lambda p, dt: jnp.zeros(jnp.shape(p), dt)
        return {
            "exp_avg": jax.tree.map(
                lambda p: zeros(p, jnp.float32), params),
            "exp_avg_sq": jax.tree.map(
                lambda p: zeros(p, self.exp_avg_sq_dtype), params),
        }

    def _coeffs(self, step):
        b1, b2 = f32(self.beta1), f32(self.beta2)
        stepf = step.astype(jnp.float32)
        if self.bias_correction:
            bc1 = 1.0 - b1 ** stepf
            bc2 = 1.0 - b2 ** stepf
        else:
            bc1 = bc2 = jnp.float32(1.0)
        return b1, b2, bc1, bc2, f32(self.weight_decay)

    def _clip_factor(self, gnorm):
        return jnp.where(
            gnorm > self.max_grad_norm, self.max_grad_norm / gnorm, 1.0
        )

    def _adam_elementwise(self, g, p, m, v, bc1, bc2, lr):
        """The ONE Adam formula both the per-leaf and the fused-tail
        paths run — elementwise, so packing cannot change a bit."""
        b1, b2 = f32(self.beta1), f32(self.beta2)
        wd = f32(self.weight_decay)
        if not self.adam_w_mode and self.weight_decay != 0.0:
            g = g + wd * p
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        denom = jnp.sqrt(v / bc2) + self.eps
        update = (m / bc1) / denom
        if self.adam_w_mode and self.weight_decay != 0.0:
            update = update + wd * p
        return p - lr * update, m, v

    def _update(self, extra, step, grads, params, lr):
        _, _, bc1, bc2, _ = self._coeffs(step)
        clip = None
        if self.max_grad_norm is not None and self.max_grad_norm > 0:
            clip = self._clip_factor(global_l2norm(grads))

        def upd(p, g, m, v):
            if clip is not None:
                g = g * clip
            return self._adam_elementwise(
                g, p, m, v.astype(jnp.float32), bc1, bc2, lr
            )

        out = jax.tree.map(upd, params, grads, extra["exp_avg"], extra["exp_avg_sq"])
        # unzip the 3-tuples back into parallel pytrees
        treedef = jax.tree.structure(params)
        flat = jax.tree.leaves(out, is_leaf=lambda x: isinstance(x, tuple))
        new_p = jax.tree.unflatten(treedef, [t[0] for t in flat])
        new_m = jax.tree.unflatten(treedef, [t[1] for t in flat])
        new_v = jax.tree.unflatten(
            treedef,
            [t[2].astype(self.exp_avg_sq_dtype) for t in flat],
        )
        return new_p, {"exp_avg": new_m, "exp_avg_sq": new_v}

    # ----------------------------------------------------- fused tail
    def _tail_state_dtypes(self) -> dict:
        return {"exp_avg": jnp.float32,
                "exp_avg_sq": self.exp_avg_sq_dtype}

    def _tail_update(self, extra, step, g_views, p_views, lr, ctx):
        _, _, bc1, bc2, _ = self._coeffs(step)
        clip = None
        if self.max_grad_norm is not None and self.max_grad_norm > 0:
            clip = self._clip_factor(ctx.global_norm(g_views))
        new_p, new_m, new_v = [], [], []
        for g, p, m, v in zip(g_views, p_views, extra["exp_avg"],
                              extra["exp_avg_sq"]):
            if clip is not None:
                g = g * clip
            np_, nm, nv = self._adam_elementwise(
                g, p, m, v, bc1, bc2, lr
            )
            new_p.append(np_)
            new_m.append(nm)
            new_v.append(nv)
        return new_p, {"exp_avg": new_m, "exp_avg_sq": new_v}
