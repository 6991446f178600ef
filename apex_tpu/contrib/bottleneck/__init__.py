"""Fused ResNet bottleneck block, plus the spatially-parallel variant.

Capability match of ``apex.contrib.bottleneck``
(reference: apex/contrib/bottleneck/bottleneck.py — ``Bottleneck``
:112-217 on cudnn-frontend fused kernels, ``SpatialBottleneck`` :386-520
with halo exchange over a communicator).  XLA fuses conv+BN+ReLU chains
natively, so ``Bottleneck`` is the plain math; ``SpatialBottleneck``
shards the image height across a mesh axis and exchanges 1-row halos
with ``ppermute`` before the 3x3 conv — the reference's
spatial-parallel-conv capability (an early form of context parallelism)
expressed as an XLA collective.

Layout: NHWC (TPU-native; the reference also prefers channels-last).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.parallel.sync_batchnorm import sync_batch_norm
from apex_tpu.utils.convnet import conv_nhwc as _conv, he_init as _he

__all__ = ["Bottleneck", "SpatialBottleneck", "halo_exchange"]


def _bn(x, scale, bias, eps=1e-5, axis_name=None):
    """Per-batch BN via the shared SyncBN math; with ``axis_name`` the
    stats are psum-ed over that mesh axis so an H-sharded block
    normalizes exactly like its dense counterpart."""
    out, _, _ = sync_batch_norm(
        x, scale, bias, None, None, training=True, eps=eps,
        axis_name=axis_name,
    )
    return out


class Bottleneck:
    """conv1x1-BN-ReLU → conv3x3-BN-ReLU → conv1x1-BN + residual, ReLU
    (reference: bottleneck.py:112-217; the cudnn-frontend fusion graph is
    XLA's automatic conv-epilogue fusion here)."""

    expansion = 4

    def __init__(self, in_channels: int, bottleneck_channels: int,
                 out_channels: int, stride: int = 1,
                 params_dtype: Any = jnp.float32):
        self.in_channels = in_channels
        self.bottleneck_channels = bottleneck_channels
        self.out_channels = out_channels
        self.stride = stride
        self.use_proj = stride != 1 or in_channels != out_channels
        self.params_dtype = params_dtype

    def init(self, key) -> dict:
        ks = jax.random.split(key, 4)
        c_in, c_mid, c_out = (
            self.in_channels, self.bottleneck_channels, self.out_channels
        )
        bn = lambda c: {"scale": jnp.ones((c,), self.params_dtype),
                        "bias": jnp.zeros((c,), self.params_dtype)}
        params = {
            "conv1": _he(ks[0], (1, 1, c_in, c_mid), self.params_dtype),
            "bn1": bn(c_mid),
            "conv2": _he(ks[1], (3, 3, c_mid, c_mid), self.params_dtype),
            "bn2": bn(c_mid),
            "conv3": _he(ks[2], (1, 1, c_mid, c_out), self.params_dtype),
            "bn3": bn(c_out),
        }
        if self.use_proj:
            params["conv_proj"] = _he(
                ks[3], (1, 1, c_in, c_out), self.params_dtype
            )
            params["bn_proj"] = bn(c_out)
        return params

    def _conv2(self, params, x):
        return _conv(x, params["conv2"], stride=self.stride)

    _bn_axis = None  # SpatialBottleneck reduces stats over its axis

    def apply(self, params: dict, x: jnp.ndarray) -> jnp.ndarray:
        ax = self._bn_axis
        h = jax.nn.relu(_bn(_conv(x, params["conv1"]), **params["bn1"],
                            axis_name=ax))
        h = jax.nn.relu(_bn(self._conv2(params, h), **params["bn2"],
                            axis_name=ax))
        h = _bn(_conv(h, params["conv3"]), **params["bn3"], axis_name=ax)
        if self.use_proj:
            x = _bn(_conv(x, params["conv_proj"], stride=self.stride),
                    **params["bn_proj"], axis_name=ax)
        return jax.nn.relu(h + x)


def halo_exchange(x: jnp.ndarray, axis_name: str, halo: int = 1) -> jnp.ndarray:
    """Concatenate ``halo`` rows from the spatial neighbours onto a
    height-sharded NHWC tensor (reference: SpatialBottleneck's peer halo
    buffers, bottleneck.py:218-385).  Edge ranks get zero rows, matching
    conv zero padding at the true image border."""
    world = jax.lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    down = [(i, (i + 1) % world) for i in range(world)]
    up = [(i, (i - 1) % world) for i in range(world)]
    top_halo = lax.ppermute(x[:, -halo:], axis_name, down)  # from rank-1
    bot_halo = lax.ppermute(x[:, :halo], axis_name, up)     # from rank+1
    zeros = jnp.zeros_like(top_halo)
    top_halo = jnp.where(rank == 0, zeros, top_halo)
    bot_halo = jnp.where(rank == world - 1, zeros, bot_halo)
    return jnp.concatenate([top_halo, x, bot_halo], axis=1)


class SpatialBottleneck(Bottleneck):
    """Bottleneck with the image height sharded over ``axis_name``
    (reference: bottleneck.py:386-520): the 3x3 conv sees one halo row
    from each neighbour; all other ops are pointwise in H.  Only
    stride=1 keeps the H-sharding aligned (the reference has the same
    restriction on its spatial group)."""

    def __init__(self, *args, axis_name: str = "cp", **kw):
        super().__init__(*args, **kw)
        if self.stride != 1:
            raise NotImplementedError(
                "SpatialBottleneck supports stride=1 (H-sharding must stay "
                "aligned across the spatial group)"
            )
        self.axis_name = axis_name

    @property
    def _bn_axis(self):
        return self.axis_name

    def _conv2(self, params, x):
        x = halo_exchange(x, self.axis_name, halo=1)
        return lax.conv_general_dilated(
            x, params["conv2"].astype(x.dtype),
            window_strides=(1, 1),
            padding=((0, 0), (1, 1)),  # H handled by halos, W zero-padded
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
