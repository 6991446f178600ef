"""Shared helpers for the Pallas kernels."""

from __future__ import annotations

import jax
from jax.ad_checkpoint import checkpoint_name

__all__ = ["shape_struct", "largest_tile", "run_kernel",
           "KernelLoweringError", "ATTENTION_RESIDUAL_NAMES",
           "name_attention_residuals"]

#: ``checkpoint_name`` tags of the two residuals that only the forward
#: kernel can produce.  A remat policy that saves these names
#: (``tensor_parallel.random.CHECKPOINT_POLICIES``) keeps them across
#: ``jax.checkpoint``, so the backward does not run the forward kernel
#: a second time; under any other policy the tags do nothing.
ATTENTION_RESIDUAL_NAMES = ("fmha_out", "fmha_lse")


def name_attention_residuals(out, lse):
    """Tag a training attention kernel's ``(out, lse)``, in its
    ``custom_vjp`` forward rule, BEFORE they go into the residual tuple:
    the backward reads the residuals, so a tag on the primal output
    alone would still leave ``lse`` to be recomputed."""
    out_name, lse_name = ATTENTION_RESIDUAL_NAMES
    return checkpoint_name(out, out_name), checkpoint_name(lse, lse_name)


class KernelLoweringError(RuntimeError):
    """A Pallas kernel the dispatcher selected failed to trace/lower."""


def run_kernel(name, pallas_fn, xla_fn, resolved_impl):
    """Dispatch between a Pallas kernel and its XLA twin.

    ``resolved_impl`` is the dispatcher's choice (``"xla"`` off the TPU
    unless the caller forced ``implementation="pallas"``).  A kernel
    that was selected either runs or RAISES ``KernelLoweringError``
    naming it: rerouting to XLA would hide from the user that the
    device is running a slower program than the one they configured
    (the assertable contract the reference gets from its import-time
    extension probing, apex/parallel/distributed.py:13-23).
    """
    if resolved_impl != "pallas":
        return xla_fn()
    try:
        return pallas_fn()
    except Exception as e:  # trace-time shape/lowering rejection
        raise KernelLoweringError(
            f"pallas kernel {name!r} was selected and failed to "
            f"lower: {e}"
        ) from e


def shape_struct(shape, dtype, *varying_like) -> jax.ShapeDtypeStruct:
    """A ``ShapeDtypeStruct`` whose ``vma`` (varying-across-mesh axes) is
    the union of the given operands' — required so ``pallas_call`` results
    type-check under ``shard_map(check_vma=True)``, e.g. when a kernel
    runs on dp-sharded activations inside a tensor-parallel region."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in varying_like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def largest_tile(size: int, most: int) -> int:
    """The largest multiple of 128 (a lane's width) that divides ``size``
    (a multiple of 128) and is at most ``most`` (at least 128)."""
    return max(t for t in range(128, max(most, 128) + 1, 128)
               if size % t == 0)
