"""Small shared helpers (reference: apex/transformer/utils.py)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "ensure_divisibility",
    "divide",
    "split_tensor_into_1d_equal_chunks",
    "gather_split_1d_tensor",
]


def ensure_divisibility(numerator: int, denominator: int) -> None:
    """(reference: apex/transformer/utils.py:11-14)"""
    if numerator % denominator != 0:
        raise ValueError(f"{numerator} is not divisible by {denominator}")


def divide(numerator: int, denominator: int) -> int:
    """(reference: apex/transformer/utils.py:17-21)"""
    ensure_divisibility(numerator, denominator)
    return numerator // denominator


def split_tensor_into_1d_equal_chunks(x: jnp.ndarray, axis_name: str = "tp"):
    """Return this rank's 1-D chunk of ``x`` (flattened), for use inside
    shard_map — the scatter half of the pipeline scatter/gather
    optimization (reference: apex/transformer/utils.py:19-27)."""
    flat = x.reshape(-1)
    world = jax.lax.axis_size(axis_name)
    ensure_divisibility(flat.shape[0], world)
    rank = jax.lax.axis_index(axis_name)
    chunk = flat.shape[0] // world
    return jax.lax.dynamic_slice_in_dim(flat, rank * chunk, chunk)


def gather_split_1d_tensor(chunk: jnp.ndarray, axis_name: str = "tp"):
    """All-gather 1-D chunks back into the full (replicated) flat tensor
    (reference: apex/transformer/utils.py:28-36)."""
    from apex_tpu.transformer.tensor_parallel.mappings import all_gather_invariant

    return all_gather_invariant(chunk, axis_name, axis=0, tiled=True)
