"""fmha-short (single-pass short-sequence attention) vs mha_reference.

The short kernel's parity contract matches the flash kernel's: values
and gradients within the existing flash tolerances, and BIT-IDENTICAL
dropout masks (both paths draw from the same counter-based hash).
Interpret mode runs the real kernel bodies on CPU.

Also pins the measured auto-dispatch: ``flash_attention`` routes to the
short kernel at/below the crossover (``FMHA_SHORT_MAX_SEQ``), to the
flash kernel above it, and keeps fp32 short sequences on their
measured XLA window.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import flash_attention, fmha_short, mha_reference
from apex_tpu.ops.attention_short import (
    FMHA_SHORT_MAX_BLOCK_BH,
    FMHA_SHORT_MAX_SEQ,
    default_block_bh,
    short_seq_threshold,
)


def _qkv(key, shape):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, shape), jax.random.normal(kk, shape),
            jax.random.normal(kv, shape))


class TestShortParity:
    """Sweep of the reference's fmha seqlen window {128,256,384,512}
    (+1024 in the slow tier) across causal/bias/segments/dropout."""

    @pytest.mark.parametrize("s", [128, 256, 384, 512])
    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_parity_swept_seqlens(self, s, causal):
        q, k, v = _qkv(jax.random.PRNGKey(s), (1, 2, s, 64))
        got = fmha_short(q, k, v, causal=causal, implementation="pallas")
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(got, want, atol=1e-5)

    @pytest.mark.parametrize("s", [128, 256])
    def test_grads_match_reference(self, s):
        q, k, v = _qkv(jax.random.PRNGKey(50 + s), (1, 2, s, 64))

        def f_short(q, k, v):
            return jnp.sum(fmha_short(
                q, k, v, causal=True, implementation="pallas", block_bh=2
            ) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

        g1 = jax.grad(f_short, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4)

    @pytest.mark.slow
    def test_parity_s1024(self):
        # above the default dispatch window but must still be correct
        # (the validation sweep times this shape to find the crossover)
        q, k, v = _qkv(jax.random.PRNGKey(1024), (1, 1, 1024, 64))

        def f_short(q, k, v):
            return jnp.sum(fmha_short(
                q, k, v, causal=True, implementation="pallas") ** 2)

        def f_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

        v1, g1 = jax.value_and_grad(f_short, argnums=(0, 1, 2))(q, k, v)
        v2, g2 = jax.value_and_grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(v1, v2, rtol=1e-5)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_unpadded_seq_and_head_dim(self):
        # seq not a lane multiple + head_dim < 128 exercises every pad
        # path (q rows, kv cols, lanes)
        q, _, _ = _qkv(jax.random.PRNGKey(23), (1, 2, 100, 40))
        _, k, v = _qkv(jax.random.PRNGKey(24), (1, 2, 72, 40))
        got = fmha_short(q, k, v, implementation="pallas")
        want = mha_reference(q, k, v)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_bh_packing_and_ragged_bh(self):
        # bh=6 with block_bh=4 pads the bh axis; results must match the
        # unpacked (block_bh=1) kernel bit-for-bit and the reference
        q, k, v = _qkv(jax.random.PRNGKey(25), (2, 3, 128, 64))
        packed = fmha_short(q, k, v, causal=True, implementation="pallas",
                            block_bh=4)
        single = fmha_short(q, k, v, causal=True, implementation="pallas",
                            block_bh=1)
        np.testing.assert_allclose(packed, single, atol=0)
        np.testing.assert_allclose(
            packed, mha_reference(q, k, v, causal=True), atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_segment_ids(self, causal):
        q, k, v = _qkv(jax.random.PRNGKey(26), (2, 2, 96, 64))
        seg = jnp.concatenate(
            [jnp.zeros((2, 40), jnp.int32), jnp.ones((2, 56), jnp.int32)],
            axis=1,
        )
        got = fmha_short(q, k, v, causal=causal, q_segment_ids=seg,
                         kv_segment_ids=seg, implementation="pallas",
                         block_bh=2)
        want = mha_reference(q, k, v, causal=causal, q_segment_ids=seg,
                             kv_segment_ids=seg)
        np.testing.assert_allclose(got, want, atol=1e-5)

    @pytest.mark.parametrize(
        "bias_shape", [(1, 1, 64, 64), (2, 1, 64, 64), (2, 2, 64, 64)]
    )
    def test_bias_broadcast_and_grad(self, bias_shape):
        q, k, v = _qkv(jax.random.PRNGKey(27), (2, 2, 64, 64))
        bias = jax.random.normal(jax.random.PRNGKey(28), bias_shape)

        def loss(fn, **kw):
            def f(q, k, v, bias):
                return jnp.sum(fn(q, k, v, bias=bias, **kw) ** 2)
            return f

        got = fmha_short(q, k, v, bias=bias, implementation="pallas",
                         block_bh=2)
        np.testing.assert_allclose(
            got, mha_reference(q, k, v, bias=bias), atol=1e-5)
        g1 = jax.grad(loss(fmha_short, implementation="pallas", block_bh=2),
                      argnums=(0, 1, 2, 3))(q, k, v, bias)
        g2 = jax.grad(loss(mha_reference), argnums=(0, 1, 2, 3))(
            q, k, v, bias)
        for a, b in zip(g1, g2):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_per_batch_bias_native_mode_odd_heads(self):
        # (b, 1, sq, sk) bias rides its native per-batch layout (no
        # h-times broadcast); h=5 forces the block_bh-divides-heads
        # clamp, and the dbias fold must return the (b, 1, sq, sk) shape
        q, k, v = _qkv(jax.random.PRNGKey(70), (3, 5, 64, 32))
        bias = jax.random.normal(jax.random.PRNGKey(71), (3, 1, 64, 64))

        def loss(fn, **kw):
            def f(q, k, v, bias):
                return jnp.sum(fn(q, k, v, bias=bias, causal=True,
                                  **kw) ** 2)
            return f

        got = fmha_short(q, k, v, bias=bias, causal=True,
                         implementation="pallas", block_bh=4)
        np.testing.assert_allclose(
            got, mha_reference(q, k, v, bias=bias, causal=True), atol=1e-5)
        g1 = jax.grad(loss(fmha_short, implementation="pallas",
                           block_bh=4), argnums=(0, 1, 2, 3))(q, k, v, bias)
        g2 = jax.grad(loss(mha_reference), argnums=(0, 1, 2, 3))(
            q, k, v, bias)
        for a, b in zip(g1, g2):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_constant_mask_bias_skips_dbias(self):
        q, k, v = _qkv(jax.random.PRNGKey(29), (1, 2, 32, 64))
        # keep the diagonal unmasked: a row with NO live causal entry is
        # degenerate (grad through it is convention-dependent, and the
        # single-pass and spread-then-zero softmaxes legitimately differ)
        keep = jnp.logical_or(
            jax.random.bernoulli(jax.random.PRNGKey(30), 0.8, (1, 1, 32, 32)),
            jnp.eye(32, dtype=bool),
        )
        bias = jnp.where(keep, 0.0, -1e30)

        def loss(q, k, v, bias):
            return jnp.sum(fmha_short(
                q, k, v, bias=bias, bias_requires_grad=False, causal=True,
                implementation="pallas",
            ) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, bias)

        def loss_ref(q, k, v):
            return jnp.sum(
                mha_reference(q, k, v, bias=bias, causal=True) ** 2)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g[:3], gr):
            np.testing.assert_allclose(a, b, atol=1e-4)
        np.testing.assert_allclose(g[3], 0.0, atol=0)

    def test_dropout_bit_identical_mask(self):
        # same hash, same seed → identical mask across short / flash /
        # XLA — the mha_reference parity contract from the flash kernel
        # carried over bit-for-bit
        q, k, v = _qkv(jax.random.PRNGKey(31), (2, 2, 64, 64))
        kw = dict(dropout_rate=0.3, dropout_seed=1234)
        got = fmha_short(q, k, v, implementation="pallas", block_bh=4, **kw)
        want = mha_reference(q, k, v, **kw)
        np.testing.assert_allclose(got, want, atol=1e-5)
        again = fmha_short(q, k, v, implementation="pallas", block_bh=1, **kw)
        np.testing.assert_allclose(got, again, atol=1e-5)
        other = fmha_short(q, k, v, implementation="pallas", block_bh=4,
                           dropout_rate=0.3, dropout_seed=99)
        assert float(jnp.max(jnp.abs(got - other))) > 1e-3

    def test_dropout_gradients(self):
        q, k, v = _qkv(jax.random.PRNGKey(32), (1, 2, 64, 64))

        def loss(fn, **kw):
            def f(q, k, v):
                return jnp.sum(fn(
                    q, k, v, causal=True, dropout_rate=0.2, dropout_seed=7,
                    **kw) ** 2)
            return f

        g1 = jax.grad(loss(fmha_short, implementation="pallas", block_bh=2),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-4)

    @pytest.mark.slow
    def test_everything_composes(self):
        # segments + bias + dropout + causal + ragged seq + ragged bh
        q, k, v = _qkv(jax.random.PRNGKey(33), (2, 3, 50, 64))
        seg = (jnp.arange(50) // 20).astype(jnp.int32)[None, :].repeat(2, 0)
        bias = 0.1 * jax.random.normal(jax.random.PRNGKey(34), (2, 1, 50, 50))
        kwargs = dict(
            causal=True, bias=bias, q_segment_ids=seg, kv_segment_ids=seg,
            dropout_rate=0.1, dropout_seed=42,
        )
        got = fmha_short(q, k, v, implementation="pallas", block_bh=4,
                         **kwargs)
        want = mha_reference(q, k, v, **kwargs)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_explicit_pallas_raises_without_pallas(self, monkeypatch):
        from apex_tpu.ops import attention_short as mod
        from apex_tpu.ops.common import KernelLoweringError

        q = jnp.ones((1, 1, 8, 8))
        monkeypatch.setattr(mod, "pl", None)
        with pytest.raises(KernelLoweringError):
            mod.fmha_short(q, q, q, implementation="pallas")
        out = mod.fmha_short(q, q, q)  # auto degrades gracefully
        assert out.shape == (1, 1, 8, 8)


class TestBlockBhSizing:
    def test_budgeted_by_score_area(self):
        assert default_block_bh(128, 128, 64) == FMHA_SHORT_MAX_BLOCK_BH
        assert default_block_bh(512, 512, 64) == 2
        assert default_block_bh(1024, 1024, 64) == 1
        # never exceeds the actual bh
        assert default_block_bh(128, 128, 3) == 3


class TestShortDispatch:
    """Auto mode picks the short kernel at/below the measured crossover
    and the flash kernel above it; fp32 keeps its XLA window."""

    def _spy(self, monkeypatch):
        from apex_tpu.ops import attention as attn_mod
        from apex_tpu.ops import attention_short as short_mod
        from apex_tpu.utils import platform as plat

        calls = []

        def fake(tag):
            def f(q, *a, **kw):
                calls.append(tag)
                return jnp.zeros(q.shape, q.dtype)
            return f

        from apex_tpu.ops import attention_mid as mid_mod

        monkeypatch.setattr(attn_mod, "_flash_attention_pallas",
                            fake("flash"))
        monkeypatch.setattr(short_mod, "_fmha_short_pallas", fake("short"))
        monkeypatch.setattr(mid_mod, "_fmha_mid_pallas", fake("mid"))
        monkeypatch.setattr(plat, "_current_platform", lambda: "tpu")
        monkeypatch.delenv("APEX_TPU_DISABLE_PALLAS", raising=False)
        monkeypatch.delenv("APEX_TPU_FMHA_SHORT_MAX_SEQ", raising=False)
        monkeypatch.delenv("APEX_TPU_FMHA_MID_MAX_SEQ", raising=False)
        return calls

    def test_bf16_below_crossover_picks_short(self, monkeypatch):
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 2, 256, 64), jnp.bfloat16)
        flash_attention(q, q, q)
        assert calls == ["short"]

    def test_crossover_boundary_inclusive(self, monkeypatch):
        calls = self._spy(monkeypatch)
        s = FMHA_SHORT_MAX_SEQ
        q = jnp.ones((1, 1, s, 64), jnp.bfloat16)
        flash_attention(q, q, q)
        assert calls == ["short"]

    def test_bf16_above_crossover_leaves_short(self, monkeypatch):
        # just above the short window the ladder's NEXT tier (the
        # pipelined mid kernel) takes over — never short
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 1, FMHA_SHORT_MAX_SEQ + 128, 64), jnp.bfloat16)
        flash_attention(q, q, q)
        assert calls == ["mid"]

    def test_long_kv_disqualifies_short(self, monkeypatch):
        # cross-attention with short q but long kv: the whole-kv-in-one-
        # block premise fails, so a streaming tier must run (the mid
        # kernel here — kv sits at its window edge)
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 1, 256, 64), jnp.bfloat16)
        kv = jnp.ones((1, 1, 2048, 64), jnp.bfloat16)
        flash_attention(q, kv, kv)
        assert calls == ["mid"]

    def test_fp32_short_keeps_xla_window(self, monkeypatch):
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 1, 256, 64), jnp.float32)
        flash_attention(q, q, q)
        assert calls == []  # measured fp32 window still routes to XLA

    def test_explicit_short_honored_any_dtype(self, monkeypatch):
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 1, 256, 64), jnp.float32)
        flash_attention(q, q, q, implementation="short")
        assert calls == ["short"]

    def test_env_override_moves_crossover(self, monkeypatch):
        calls = self._spy(monkeypatch)
        monkeypatch.setenv("APEX_TPU_FMHA_SHORT_MAX_SEQ", "128")
        assert short_seq_threshold() == 128
        q = jnp.ones((1, 1, 256, 64), jnp.bfloat16)
        flash_attention(q, q, q)
        # shapes pushed out of the short window fall to the next tier
        assert calls == ["mid"]

    def test_explicit_pallas_still_means_flash(self, monkeypatch):
        # the strict flash request must not be silently re-routed
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 1, 256, 64), jnp.bfloat16)
        flash_attention(q, q, q, implementation="pallas")
        assert calls == ["flash"]


class TestContribWiring:
    """The short kernel is reachable through the reference-parity
    wrappers: contrib.fmha (packed varlen — the reference's exact
    seqlen window) and contrib.multihead_attn (attention_impl knob)."""

    def test_fmha_varlen_short_kernel(self):
        from apex_tpu.contrib.fmha import fmha

        key = jax.random.PRNGKey(60)
        lens = [24, 40]
        total, heads, d = sum(lens), 2, 64
        qkv = jax.random.normal(key, (total, 3, heads, d))
        cu = jnp.asarray(np.cumsum([0] + lens), jnp.int32)
        got = fmha(qkv, cu, max_seq_len=64, causal=True,
                   implementation="short")
        want = fmha(qkv, cu, max_seq_len=64, causal=True,
                    implementation="xla")
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_self_mha_attention_impl_short(self):
        from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn

        x = jax.random.normal(jax.random.PRNGKey(61), (48, 2, 64))
        mha_s = SelfMultiheadAttn(64, 4, impl="fast",
                                  attention_impl="short")
        mha_d = SelfMultiheadAttn(64, 4, impl="default")
        params = mha_s.init(jax.random.PRNGKey(62))
        got = mha_s.apply(params, x, causal=True)
        want = mha_d.apply(params, x, causal=True)
        np.testing.assert_allclose(got, want, atol=1e-5)
