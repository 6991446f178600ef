"""Stage-to-stage transfer primitives: ``ppermute`` ring shifts.

The reference implements pipeline p2p with batched NCCL isend/irecv plus
a mandatory ``torch.cuda.synchronize()`` per call
(reference: apex/transformer/pipeline_parallel/p2p_communication.py:31-69,
161-162) and a scatter-gather optimization that splits activations over
the TP group for transport (:116-178).  On TPU both concerns disappear:
``lax.ppermute`` is an async XLA collective scheduled by the compiler
(no host sync), and activations are already sharded over "tp" inside
shard_map, so only the local shard ever rides the ICI link — the
scatter/gather optimization is the *default* representation.

These helpers are the building blocks of the compiled schedules in
:mod:`apex_tpu.transformer.pipeline_parallel.schedules`; they are also
usable directly for custom schedules.  All must be called inside
``shard_map`` over a mesh with the pipeline axis.
"""

from __future__ import annotations

from typing import Any

import jax
from jax import lax

from apex_tpu.transformer.parallel_state import PIPELINE_PARALLEL_AXIS

__all__ = [
    "send_forward",
    "send_backward",
    "send_forward_recv_backward",
    "send_backward_recv_forward",
]


def _ring_perm(size: int, shift: int):
    return [(i, (i + shift) % size) for i in range(size)]


def _shift(tree: Any, axis_name: str, shift: int) -> Any:
    size = jax.lax.axis_size(axis_name)
    perm = _ring_perm(size, shift)
    return jax.tree.map(lambda x: lax.ppermute(x, axis_name, perm), tree)


def send_forward(tree: Any, axis_name: str = PIPELINE_PARALLEL_AXIS) -> Any:
    """Rotate activations one stage forward (stage i → i+1); every rank
    *receives* its predecessor's value (recv_forward is the same op seen
    from the other side — SPMD collapses the reference's 8 send/recv
    combinators, p2p_communication.py:183-404, into two shifts)."""
    return _shift(tree, axis_name, +1)


def send_backward(tree: Any, axis_name: str = PIPELINE_PARALLEL_AXIS) -> Any:
    """Rotate gradients one stage backward (stage i → i-1)."""
    return _shift(tree, axis_name, -1)


def send_forward_recv_backward(
    fwd_tree: Any, bwd_tree: Any, axis_name: str = PIPELINE_PARALLEL_AXIS
):
    """Both directions in one step; XLA overlaps the two ppermutes."""
    return _shift(fwd_tree, axis_name, +1), _shift(bwd_tree, axis_name, -1)


def send_backward_recv_forward(
    bwd_tree: Any, fwd_tree: Any, axis_name: str = PIPELINE_PARALLEL_AXIS
):
    return _shift(bwd_tree, axis_name, -1), _shift(fwd_tree, axis_name, +1)
