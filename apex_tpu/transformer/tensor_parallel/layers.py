"""Tensor-parallel layers: column/row-parallel linear, vocab-parallel embedding.

Design (TPU-native, not a port): each layer is a small factory object with

- ``init(key)``        → the **full logical** parameter pytree (what you'd
  have with tp=1).  Placement onto the mesh is done by the caller with
  ``jax.device_put(params, NamedSharding(mesh, spec))`` using
- ``param_specs()``    → a matching pytree of ``PartitionSpec``s, and
- ``apply(params, x)`` → the forward math, written for the *local shard*
  view inside ``shard_map`` (the in_spec for the params is exactly
  ``param_specs()``, so GSPMD hands each device its shard).

This replaces the reference's "initialize master weight on every rank,
scatter, keep the shard" dance
(reference: apex/transformer/tensor_parallel/layers.py:66-124) — the full
array is only ever materialized logically; XLA shards it at placement.

The reference's async-allreduce backward trick
(reference: apex/transformer/tensor_parallel/layers.py:206-240) needs no
analog: XLA's latency-hiding scheduler overlaps the psum with the
weight-gradient matmul automatically.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer.parallel_state import TENSOR_PARALLEL_AXIS
from apex_tpu.transformer.tensor_parallel.utils import VocabUtility
from apex_tpu.transformer.tensor_parallel.mappings import (
    copy_to_tensor_model_parallel_region,
    gather_from_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
    scatter_to_tensor_model_parallel_region,
)

__all__ = ["ColumnParallelLinear", "RowParallelLinear", "VocabParallelEmbedding", "state_specs_like"]


def _normal_init(std: float = 0.02) -> Callable:
    def init(key, shape, dtype=jnp.float32):
        return std * jax.random.normal(key, shape, dtype)

    return init


def _kaiming_init():
    def init(key, shape, dtype=jnp.float32):
        fan_in = shape[0]
        bound = math.sqrt(1.0 / fan_in)
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


def _check_tp_divisible(value: int, what: str) -> None:
    """Raise a friendly error instead of a placement-time GSPMD failure
    when a sharded dimension doesn't divide by the tp world size.
    Only possible once the mesh exists; a tp=1 mesh never fails."""
    from apex_tpu.transformer import parallel_state

    if parallel_state.model_parallel_is_initialized():
        tp = parallel_state.get_tensor_model_parallel_world_size()
        if value % tp != 0:
            raise ValueError(
                f"{what} ({value}) must be divisible by the tensor-parallel "
                f"world size ({tp})"
            )


def state_specs_like(param_specs: Any, state: Any) -> Any:
    """Derive shard_map in/out specs for an optimizer-state pytree whose
    leaves mirror the params (e.g. Adam moments): any state subtree with
    the params' structure gets ``param_specs``, scalars get ``P()``."""
    import jax.tree_util as jtu

    param_treedef = jtu.tree_structure(param_specs)

    def derive(sub):
        if jtu.tree_structure(sub) == param_treedef:
            return param_specs
        return jax.tree.map(lambda _: P(), sub)

    if isinstance(state, dict):
        return {k: derive(v) for k, v in state.items()}
    return derive(state)


class ColumnParallelLinear:
    """Y = XA + b with A split along its output (column) dimension
    (reference: apex/transformer/tensor_parallel/layers.py:243-364).

    Weight layout is (in, out) — row-major matmul friendly on the MXU —
    sharded ``P(None, "tp")``.  ``gather_output=True`` all-gathers Y so
    downstream sees the full output (reference default); the usual
    Megatron pattern keeps it False and feeds a RowParallelLinear.
    """

    def __init__(
        self,
        input_size: int,
        output_size: int,
        *,
        bias: bool = True,
        gather_output: bool = True,
        init_method: Optional[Callable] = None,
        params_dtype: Any = jnp.float32,
        axis_name: str = TENSOR_PARALLEL_AXIS,
    ):
        _check_tp_divisible(output_size, "ColumnParallelLinear output_size")
        self.input_size = input_size
        self.output_size = output_size
        self.use_bias = bias
        self.gather_output = gather_output
        self.init_method = init_method or _kaiming_init()
        self.params_dtype = params_dtype
        self.axis_name = axis_name

    def init(self, key) -> Dict[str, jnp.ndarray]:
        wkey, _ = jax.random.split(key)
        params = {
            "weight": self.init_method(
                wkey, (self.input_size, self.output_size), self.params_dtype
            )
        }
        if self.use_bias:
            # zero-init like the reference (layers.py:341-344)
            params["bias"] = jnp.zeros((self.output_size,), self.params_dtype)
        return params

    def param_specs(self) -> Dict[str, P]:
        specs = {"weight": P(None, self.axis_name)}
        if self.use_bias:
            specs["bias"] = P(self.axis_name)
        return specs

    def apply(self, params: Dict[str, jnp.ndarray], x: jnp.ndarray) -> jnp.ndarray:
        """Forward on the local shard — call inside shard_map."""
        x = copy_to_tensor_model_parallel_region(x, self.axis_name)
        y = jnp.matmul(x, params["weight"].astype(x.dtype))
        if self.use_bias:
            y = y + params["bias"].astype(y.dtype)
        if self.gather_output:
            y = gather_from_tensor_model_parallel_region(y, self.axis_name)
        return y


class RowParallelLinear:
    """Y = XA + b with A split along its input (row) dimension
    (reference: apex/transformer/tensor_parallel/layers.py:365-477).

    Weight sharded ``P("tp", None)``; the partial products are summed with
    an all-reduce and the (replicated) bias is added after the reduction,
    exactly like the reference.
    """

    def __init__(
        self,
        input_size: int,
        output_size: int,
        *,
        bias: bool = True,
        input_is_parallel: bool = False,
        init_method: Optional[Callable] = None,
        params_dtype: Any = jnp.float32,
        axis_name: str = TENSOR_PARALLEL_AXIS,
    ):
        _check_tp_divisible(input_size, "RowParallelLinear input_size")
        self.input_size = input_size
        self.output_size = output_size
        self.use_bias = bias
        self.input_is_parallel = input_is_parallel
        self.init_method = init_method or _kaiming_init()
        self.params_dtype = params_dtype
        self.axis_name = axis_name

    def init(self, key) -> Dict[str, jnp.ndarray]:
        wkey, _ = jax.random.split(key)
        params = {
            "weight": self.init_method(
                wkey, (self.input_size, self.output_size), self.params_dtype
            )
        }
        if self.use_bias:
            params["bias"] = jnp.zeros((self.output_size,), self.params_dtype)
        return params

    def param_specs(self) -> Dict[str, P]:
        specs = {"weight": P(self.axis_name, None)}
        if self.use_bias:
            specs["bias"] = P()
        return specs

    def apply(self, params: Dict[str, jnp.ndarray], x: jnp.ndarray) -> jnp.ndarray:
        if not self.input_is_parallel:
            x = scatter_to_tensor_model_parallel_region(x, self.axis_name)
        y = jnp.matmul(x, params["weight"].astype(x.dtype))
        y = reduce_from_tensor_model_parallel_region(y, self.axis_name)
        if self.use_bias:
            y = y + params["bias"].astype(y.dtype)
        return y


class VocabParallelEmbedding:
    """Embedding table sharded along the vocab dimension
    (reference: apex/transformer/tensor_parallel/layers.py:127-203).

    Each device looks up only the ids that fall inside its vocab slice,
    zeroes the rest, and the partial embeddings are summed with psum —
    identical math to the reference's mask-and-allreduce.
    """

    def __init__(
        self,
        num_embeddings: int,
        embedding_dim: int,
        *,
        init_method: Optional[Callable] = None,
        params_dtype: Any = jnp.float32,
        axis_name: str = TENSOR_PARALLEL_AXIS,
    ):
        _check_tp_divisible(num_embeddings, "VocabParallelEmbedding num_embeddings")
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.init_method = init_method or _normal_init()
        self.params_dtype = params_dtype
        self.axis_name = axis_name

    def init(self, key) -> Dict[str, jnp.ndarray]:
        return {
            "weight": self.init_method(
                key, (self.num_embeddings, self.embedding_dim), self.params_dtype
            )
        }

    def param_specs(self) -> Dict[str, P]:
        return {"weight": P(self.axis_name, None)}

    def apply(self, params: Dict[str, jnp.ndarray], ids: jnp.ndarray) -> jnp.ndarray:
        w = params["weight"]
        world = jax.lax.axis_size(self.axis_name)
        rank = jax.lax.axis_index(self.axis_name)
        start, end = VocabUtility.vocab_range_from_per_partition_vocab_size(
            self.num_embeddings // world, rank, world
        )
        # mask + shift (reference: layers.py:177-196)
        in_range = (ids >= start) & (ids < end)
        local_ids = jnp.where(in_range, ids - start, 0)
        out = jnp.take(w, local_ids, axis=0)
        out = jnp.where(in_range[..., None], out, jnp.zeros_like(out))
        return jax.lax.psum(out, self.axis_name)
