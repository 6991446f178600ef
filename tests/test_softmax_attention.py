"""Fused softmax + flash attention vs analytic references.

Mirrors the reference's test style: fused path compared against a
composed naive implementation, values and gradients
(reference: tests/L0/run_transformer/test_fused_softmax.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import (
    flash_attention,
    mha_reference,
    scaled_masked_softmax,
    scaled_softmax,
    scaled_upper_triang_masked_softmax,
)
from apex_tpu.transformer.enums import AttnMaskType
from apex_tpu.transformer.functional import FusedScaleMaskSoftmax


def naive_softmax(x, mask=None, scale=1.0, causal=False):
    x = x.astype(jnp.float32) * scale
    sq, sk = x.shape[-2:]
    if causal:
        tri = np.triu(np.ones((sq, sk), bool), k=1)
        x = jnp.where(jnp.asarray(tri), -10000.0, x)
    if mask is not None:
        x = jnp.where(mask, -10000.0, x)
    return jax.nn.softmax(x, axis=-1)


class TestScaledSoftmax:
    def test_matches_naive(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 8, 16))
        got = scaled_softmax(x, scale=0.5)
        want = naive_softmax(x, scale=0.5)
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_causal(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 16, 16))
        got = scaled_upper_triang_masked_softmax(x, scale=2.0)
        want = naive_softmax(x, scale=2.0, causal=True)
        np.testing.assert_allclose(got, want, atol=1e-6)
        # strictly-upper entries ~0
        assert float(got[0, 0, 0, 1]) < 1e-4

    def test_padding_mask(self):
        key = jax.random.PRNGKey(2)
        x = jax.random.normal(key, (2, 4, 8, 12))
        mask = jax.random.bernoulli(key, 0.3, (2, 1, 8, 12))
        got = scaled_masked_softmax(x, mask, scale=1.5)
        want = naive_softmax(x, mask=mask, scale=1.5)
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_gradient_matches_naive(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 2, 8, 8))

        def loss_fused(x):
            return jnp.sum(
                scaled_upper_triang_masked_softmax(x, 1.7) ** 2
            )

        def loss_naive(x):
            return jnp.sum(naive_softmax(x, scale=1.7, causal=True) ** 2)

        g1 = jax.grad(loss_fused)(x)
        g2 = jax.grad(loss_naive)(x)
        np.testing.assert_allclose(g1, g2, atol=1e-5)

    def test_bf16_output_dtype(self):
        x = jax.random.normal(
            jax.random.PRNGKey(4), (1, 2, 8, 8)
        ).astype(jnp.bfloat16)
        y = scaled_softmax(x)
        assert y.dtype == jnp.bfloat16


class TestFusedScaleMaskSoftmax:
    def test_causal_module(self):
        m = FusedScaleMaskSoftmax(
            attn_mask_type=AttnMaskType.causal, scale=0.125
        )
        x = jax.random.normal(jax.random.PRNGKey(5), (2, 4, 16, 16))
        got = m(x.astype(jnp.bfloat16), None)
        want = naive_softmax(x.astype(jnp.bfloat16), scale=0.125,
                             causal=True)
        np.testing.assert_allclose(
            got.astype(jnp.float32), want, atol=1e-2
        )

    def test_padding_module_with_mask_func(self):
        m = FusedScaleMaskSoftmax(
            attn_mask_type=AttnMaskType.padding,
            mask_func=lambda s, mask: jnp.where(mask, -10000.0, s),
        )
        key = jax.random.PRNGKey(6)
        x = jax.random.normal(key, (2, 2, 8, 8))
        mask = jax.random.bernoulli(key, 0.2, (2, 1, 8, 8))
        got = m(x, mask)
        want = naive_softmax(x, mask=mask)
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_causal_composes_with_padding_mask(self):
        m = FusedScaleMaskSoftmax(attn_mask_type=AttnMaskType.causal)
        key = jax.random.PRNGKey(30)
        x = jax.random.normal(key, (2, 2, 8, 8))
        mask = jax.random.bernoulli(key, 0.3, (2, 1, 8, 8))
        got = m(x, mask)
        want = naive_softmax(x, mask=mask, causal=True)
        np.testing.assert_allclose(got, want, atol=1e-6)
        # the mask must actually matter
        assert not np.allclose(got, m(x, None))

    def test_flag_conflict(self):
        with pytest.raises(RuntimeError):
            FusedScaleMaskSoftmax(input_in_fp16=True, input_in_bf16=True)
        with pytest.raises(RuntimeError):
            FusedScaleMaskSoftmax(softmax_in_fp32=False, scale=2.0)


class TestPallasKernelsInterpreted:
    """Force implementation='pallas' on CPU — interpret mode runs the real
    kernel bodies, so the Pallas code paths have coverage off-TPU."""

    def test_softmax_kernel_body(self):
        x = jax.random.normal(jax.random.PRNGKey(20), (2, 16, 128))
        got = scaled_softmax(x, 0.7, implementation="pallas")
        want = scaled_softmax(x, 0.7, implementation="xla")
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_causal_softmax_kernel_body(self):
        x = jax.random.normal(jax.random.PRNGKey(21), (2, 16, 128))
        got = scaled_upper_triang_masked_softmax(
            x, 1.3, implementation="pallas"
        )
        want = scaled_upper_triang_masked_softmax(
            x, 1.3, implementation="xla"
        )
        np.testing.assert_allclose(got, want, atol=1e-6)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_kernels_fwd_bwd(self, causal):
        key = jax.random.PRNGKey(22)
        kq, kk, kv = jax.random.split(key, 3)
        shape = (1, 2, 128, 128)
        q = jax.random.normal(kq, shape)
        k = jax.random.normal(kk, shape)
        v = jax.random.normal(kv, shape)

        def f_pallas(q, k, v):
            return jnp.sum(
                flash_attention(
                    q, k, v, causal=causal, block_q=64, block_k=64,
                    implementation="pallas",
                ) ** 2
            )

        def f_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

        v1, g1 = jax.value_and_grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
        v2, g2 = jax.value_and_grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(v1, v2, rtol=1e-5)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_flash_kernel_unpadded_seq(self):
        # seq not a multiple of the block size exercises the pad+mask path
        key = jax.random.PRNGKey(23)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (1, 1, 100, 128))
        k = jax.random.normal(kk, (1, 1, 72, 128))
        v = jax.random.normal(kv, (1, 1, 72, 128))
        got = flash_attention(
            q, k, v, block_q=64, block_k=64, implementation="pallas"
        )
        want = mha_reference(q, k, v)
        np.testing.assert_allclose(got, want, atol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        key = jax.random.PRNGKey(7)
        kq, kk, kv = jax.random.split(key, 3)
        shape = (2, 3, 32, 16)
        q = jax.random.normal(kq, shape)
        k = jax.random.normal(kk, shape)
        v = jax.random.normal(kv, shape)
        got = flash_attention(q, k, v, causal=causal)
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_gradients_match_reference(self):
        key = jax.random.PRNGKey(8)
        kq, kk, kv = jax.random.split(key, 3)
        shape = (1, 2, 16, 8)
        q = jax.random.normal(kq, shape)
        k = jax.random.normal(kk, shape)
        v = jax.random.normal(kv, shape)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-5)

    def test_cross_attention_lengths(self):
        key = jax.random.PRNGKey(9)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (2, 2, 8, 16))
        k = jax.random.normal(kk, (2, 2, 24, 16))
        v = jax.random.normal(kv, (2, 2, 24, 16))
        got = flash_attention(q, k, v)
        want = mha_reference(q, k, v)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_bias_path(self):
        key = jax.random.PRNGKey(10)
        kq, kk, kv, kb = jax.random.split(key, 4)
        shape = (1, 2, 8, 8)
        q = jax.random.normal(kq, shape)
        k = jax.random.normal(kk, shape)
        v = jax.random.normal(kv, shape)
        bias = jax.random.normal(kb, (1, 2, 8, 8))
        got = flash_attention(q, k, v, bias=bias)
        want = mha_reference(q, k, v, bias=bias)
        np.testing.assert_allclose(got, want, atol=1e-5)


class TestFlashAttentionExtras:
    """New in-kernel capabilities: segment ids (varlen), differentiable
    additive bias, and counter-based dropout — each checked pallas-vs-xla
    in interpret mode (the two paths share the dropout hash, so dropout
    comparisons are exact, not statistical)."""

    def _qkv(self, key, shape):
        kq, kk, kv = jax.random.split(key, 3)
        return (jax.random.normal(kq, shape), jax.random.normal(kk, shape),
                jax.random.normal(kv, shape))

    @pytest.mark.parametrize("causal", [False, True])
    def test_segment_ids_match_reference(self, causal):
        q, k, v = self._qkv(jax.random.PRNGKey(30), (2, 2, 96, 128))
        # two packed sequences of 40 + 56 tokens per batch row
        seg = jnp.concatenate(
            [jnp.zeros((2, 40), jnp.int32), jnp.ones((2, 56), jnp.int32)],
            axis=1,
        )
        got = flash_attention(
            q, k, v, causal=causal, q_segment_ids=seg, kv_segment_ids=seg,
            block_q=64, block_k=64, implementation="pallas",
        )
        want = mha_reference(
            q, k, v, causal=causal, q_segment_ids=seg, kv_segment_ids=seg
        )
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_segment_ids_gradients(self):
        q, k, v = self._qkv(jax.random.PRNGKey(31), (1, 2, 64, 128))
        seg = (jnp.arange(64) // 24).astype(jnp.int32)[None, :]

        def f(impl):
            def loss(q, k, v):
                return jnp.sum(flash_attention(
                    q, k, v, causal=True, q_segment_ids=seg,
                    kv_segment_ids=seg, block_q=32, block_k=32,
                    implementation=impl,
                ) ** 2)
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        for a, b in zip(f("pallas"), f("xla")):
            np.testing.assert_allclose(a, b, atol=1e-4)

    @pytest.mark.parametrize(
        "bias_shape", [(1, 1, 64, 64), (2, 1, 64, 64), (2, 2, 64, 64)]
    )
    def test_bias_broadcast_and_grad(self, bias_shape):
        q, k, v = self._qkv(jax.random.PRNGKey(32), (2, 2, 64, 128))
        bias = jax.random.normal(jax.random.PRNGKey(33), bias_shape)

        def loss(impl):
            def f(q, k, v, bias):
                return jnp.sum(flash_attention(
                    q, k, v, bias=bias, block_q=32, block_k=32,
                    implementation=impl,
                ) ** 2)
            return f

        got = flash_attention(q, k, v, bias=bias, block_q=32, block_k=32,
                              implementation="pallas")
        want = mha_reference(q, k, v, bias=bias)
        np.testing.assert_allclose(got, want, atol=1e-5)

        g1 = jax.grad(loss("pallas"), argnums=(0, 1, 2, 3))(q, k, v, bias)
        g2 = jax.grad(loss("xla"), argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b in zip(g1, g2):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_bias_with_causal_grad(self):
        q, k, v = self._qkv(jax.random.PRNGKey(34), (1, 2, 48, 128))
        bias = jax.random.normal(jax.random.PRNGKey(35), (1, 2, 48, 48))

        def loss(impl):
            def f(q, k, v, bias):
                return jnp.sum(flash_attention(
                    q, k, v, bias=bias, causal=True, block_q=16, block_k=16,
                    implementation=impl,
                ) ** 2)
            return f

        g1 = jax.grad(loss("pallas"), argnums=(0, 1, 2, 3))(q, k, v, bias)
        g2 = jax.grad(loss("xla"), argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_dropout_exact_parity_and_rate(self):
        q, k, v = self._qkv(jax.random.PRNGKey(36), (2, 2, 64, 128))
        got = flash_attention(
            q, k, v, dropout_rate=0.3, dropout_seed=1234,
            block_q=32, block_k=32, implementation="pallas",
        )
        want = flash_attention(
            q, k, v, dropout_rate=0.3, dropout_seed=1234,
            implementation="xla",
        )
        # same hash, same seed → identical mask → near-identical values
        np.testing.assert_allclose(got, want, atol=1e-5)
        # deterministic given the seed
        again = flash_attention(
            q, k, v, dropout_rate=0.3, dropout_seed=1234,
            block_q=32, block_k=32, implementation="pallas",
        )
        np.testing.assert_allclose(got, again, atol=0)
        # different seed → different output
        other = flash_attention(
            q, k, v, dropout_rate=0.3, dropout_seed=99,
            block_q=32, block_k=32, implementation="pallas",
        )
        assert float(jnp.max(jnp.abs(got - other))) > 1e-3

    def test_dropout_mask_statistics(self):
        from apex_tpu.ops.attention import _keep_mask, _keep_threshold

        q_idx = jax.lax.broadcasted_iota(jnp.int32, (256, 256), 0)
        k_idx = jax.lax.broadcasted_iota(jnp.int32, (256, 256), 1)
        keep = _keep_mask(jnp.uint32(5), jnp.int32(3), q_idx, k_idx,
                          jnp.uint32(_keep_threshold(0.25)))
        frac = float(jnp.mean(keep.astype(jnp.float32)))
        assert abs(frac - 0.75) < 0.02

    def test_dropout_gradients_match_reference(self):
        q, k, v = self._qkv(jax.random.PRNGKey(37), (1, 2, 64, 128))

        def loss(impl):
            def f(q, k, v):
                return jnp.sum(flash_attention(
                    q, k, v, causal=True, dropout_rate=0.2, dropout_seed=7,
                    block_q=32, block_k=32, implementation=impl,
                ) ** 2)
            return f

        g1 = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_everything_composes(self):
        # segments + bias + dropout + causal + ragged seq in one call
        q, k, v = self._qkv(jax.random.PRNGKey(38), (2, 2, 50, 128))
        seg = (jnp.arange(50) // 20).astype(jnp.int32)[None, :].repeat(2, 0)
        bias = 0.1 * jax.random.normal(jax.random.PRNGKey(39), (2, 1, 50, 50))
        kwargs = dict(
            causal=True, bias=bias, q_segment_ids=seg, kv_segment_ids=seg,
            dropout_rate=0.1, dropout_seed=42,
        )
        got = flash_attention(q, k, v, block_q=16, block_k=16,
                              implementation="pallas", **kwargs)
        want = flash_attention(q, k, v, implementation="xla", **kwargs)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_large_uint32_seed(self):
        q, k, v = self._qkv(jax.random.PRNGKey(40), (1, 1, 32, 128))
        got = flash_attention(q, k, v, dropout_rate=0.2,
                              dropout_seed=0xDEADBEEF, block_q=16,
                              block_k=16, implementation="pallas")
        want = flash_attention(q, k, v, dropout_rate=0.2,
                               dropout_seed=0xDEADBEEF,
                               implementation="xla")
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_sub_4d_bias(self):
        q, k, v = self._qkv(jax.random.PRNGKey(41), (2, 2, 16, 128))
        bias = jax.random.normal(jax.random.PRNGKey(42), (16, 16))
        got = flash_attention(q, k, v, bias=bias, block_q=16, block_k=16,
                              implementation="pallas")
        want = mha_reference(q, k, v, bias=bias[None, None])
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_constant_mask_bias_skips_dbias(self):
        q, k, v = self._qkv(jax.random.PRNGKey(43), (1, 2, 32, 128))
        # keep the diagonal unmasked: a q row with NO live causal entry
        # is degenerate — the kernel's single-pass softmax and the
        # reference's spread-then-zero convention legitimately differ
        # there, and this test is about dbias skipping, not dead rows
        keep = jnp.logical_or(
            jax.random.bernoulli(jax.random.PRNGKey(44), 0.8, (1, 1, 32, 32)),
            jnp.eye(32, dtype=bool),
        )
        bias = jnp.where(keep, 0.0, -1e30)

        def loss(q, k, v, bias):
            return jnp.sum(flash_attention(
                q, k, v, bias=bias, bias_requires_grad=False,
                causal=True, block_q=16, block_k=16,
                implementation="pallas",
            ) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, bias)
        # q/k/v grads match the XLA path; bias cotangent is hard zero
        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, bias=bias, causal=True) ** 2)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g[:3], gr):
            np.testing.assert_allclose(a, b, atol=1e-4)
        np.testing.assert_allclose(g[3], 0.0, atol=0)

    def test_explicit_pallas_raises_without_pallas(self, monkeypatch):
        from apex_tpu.ops import attention as attn_mod
        from apex_tpu.ops.common import KernelLoweringError

        q = k = v = jnp.ones((1, 1, 8, 8))
        monkeypatch.setattr(attn_mod, "pl", None)
        with pytest.raises(KernelLoweringError):
            attn_mod.flash_attention(q, k, v, implementation="pallas")
        # auto mode still degrades gracefully
        out = attn_mod.flash_attention(q, k, v)
        assert out.shape == (1, 1, 8, 8)


def test_masked_softmax_explicit_pallas_raises():
    """No silent degradation: the masked variant has no pallas kernel,
    so an explicit request errors instead of silently running XLA."""
    from apex_tpu.ops.common import KernelLoweringError

    x = jnp.zeros((1, 8, 8))
    mask = jnp.zeros((1, 8, 8), bool)
    with pytest.raises(KernelLoweringError):
        scaled_masked_softmax(x, mask, implementation="pallas")
    # auto + explicit xla still fine
    out = scaled_masked_softmax(x, mask)
    assert out.shape == (1, 8, 8)


class TestFp32DispatchWindow:
    """fp32 short-seq auto mode routes to XLA (measured window,
    KERNELS_TPU.json); bf16 and explicit requests are unaffected."""

    def _spy(self, monkeypatch):
        from apex_tpu.ops import attention as attn_mod
        from apex_tpu.utils import platform as plat

        calls = []

        def fake_pallas(q, k, v, *a, **kw):
            calls.append(q.dtype)
            return jnp.zeros(q.shape, q.dtype)

        from apex_tpu.ops import attention_mid as mid_mod

        monkeypatch.setattr(attn_mod, "_flash_attention_pallas", fake_pallas)
        # the mid tier is part of the pallas kernel family: these tests
        # pin the fp32-vs-kernel WINDOW, not which tier takes the shape
        # (tier routing has its own tests in test_attention_mid.py)
        monkeypatch.setattr(
            mid_mod, "_fmha_mid_pallas",
            lambda q, *a, **kw: fake_pallas(q, None, None))
        monkeypatch.setattr(plat, "_current_platform", lambda: "tpu")
        monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
        monkeypatch.delenv("APEX_TPU_FMHA_MID_MAX_SEQ", raising=False)
        return attn_mod, calls

    def test_fp32_short_seq_auto_routes_to_xla(self, monkeypatch):
        attn_mod, calls = self._spy(monkeypatch)
        q = jnp.ones((1, 1, 8, 8), jnp.float32)
        attn_mod.flash_attention(q, q, q, implementation=None)
        assert calls == []  # window fired: no pallas attempt
        # inclusive boundary: seq == FLASH_FP32_XLA_MAX_SEQ also routes
        s = attn_mod.FLASH_FP32_XLA_MAX_SEQ
        qb = jnp.ones((1, 1, s, 8), jnp.float32)
        attn_mod.flash_attention(qb, qb, qb, implementation=None)
        assert calls == []

    def test_bf16_and_explicit_fp32_still_hit_pallas(self, monkeypatch):
        from apex_tpu.ops.attention_short import FMHA_SHORT_MAX_SEQ

        attn_mod, calls = self._spy(monkeypatch)
        # above the short-kernel window so the FLASH kernel is what
        # auto mode must pick (the short window has its own dispatch
        # tests in test_attention_short.py)
        s = FMHA_SHORT_MAX_SEQ + 128
        qb = jnp.ones((1, 1, s, 8), jnp.bfloat16)
        attn_mod.flash_attention(qb, qb, qb, implementation=None)
        assert len(calls) == 1  # bf16 auto stays on pallas
        qf = jnp.ones((1, 1, 8, 8), jnp.float32)
        attn_mod.flash_attention(qf, qf, qf, implementation="pallas")
        assert len(calls) == 2  # explicit request honored for fp32

    def test_fp32_long_seq_auto_stays_pallas(self, monkeypatch):
        attn_mod, calls = self._spy(monkeypatch)
        s = attn_mod.FLASH_FP32_XLA_MAX_SEQ + 128
        q = jnp.ones((1, 1, s, 8), jnp.float32)
        attn_mod.flash_attention(q, q, q, implementation=None)
        assert len(calls) == 1  # beyond the window: pallas


class TestFp32BlockClamp:
    """fp32 blocks are clamped to the 512*1024 area before the kernel is
    built: the bwd kernels hold ~4 (block_q, block_k) fp32 temporaries
    live, and 1024x1024 fp32 blocks measured 18.3 MB of scoped vmem
    against Mosaic's 16 MB stack limit (r5 sweep compile failure)."""

    def test_fp32_oversize_blocks_clamped(self):
        from apex_tpu.ops.attention import _clamp_blocks

        assert _clamp_blocks(jnp.float32, 1024, 1024) == (512, 1024)
        assert _clamp_blocks(jnp.float32, 2048, 1024) == (512, 1024)
        assert _clamp_blocks(jnp.float32, 512, 2048) == (512, 1024)
        # at or under the area: untouched
        assert _clamp_blocks(jnp.float32, 512, 1024) == (512, 1024)
        assert _clamp_blocks(jnp.float32, 256, 512) == (256, 512)

    def test_bf16_blocks_untouched(self):
        from apex_tpu.ops.attention import _clamp_blocks

        assert _clamp_blocks(jnp.bfloat16, 1024, 1024) == (1024, 1024)
        assert _clamp_blocks(jnp.bfloat16, 2048, 2048) == (2048, 2048)
