"""fmha-mid (pipelined mid-sequence attention) vs flash and XLA.

The mid kernel's parity contract matches the flash/short kernels':
values and all four gradients (dq/dk/dv/dbias) within the existing
tolerances against BOTH the streamed flash kernel and the XLA
reference, and BIT-IDENTICAL dropout masks across every implementation
for a given seed.  Interpret mode runs the real kernel bodies on CPU.

Also pins the three-tier dispatch ladder: short at/below its crossover,
mid inside (short, FMHA_MID_MAX_SEQ], flash above — with the env knobs
moving/disabling each window (APEX_TPU_FMHA_MID_MAX_SEQ=0 pins the mid
band back to the flash kernel's exact code path, the default-off
safety of the acceptance contract).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import flash_attention, fmha_mid, mha_reference
from apex_tpu.ops.attention import BIAS_PER_HEAD
from apex_tpu.ops.attention_mid import (
    FMHA_MID_MAX_SEQ,
    _bwd_block_bh,
    _xla_with_lse,
    default_mid_block_bh,
    default_mid_blocks,
    default_mid_fwd_blocks,
    mid_seq_threshold,
)
from apex_tpu.ops.attention_short import FMHA_SHORT_MAX_SEQ


def _qkv(key, shape):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, shape), jax.random.normal(kk, shape),
            jax.random.normal(kv, shape))


def _grads(fn, *args, argnums=None):
    argnums = tuple(range(len(args))) if argnums is None else argnums

    def loss(*a):
        return jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    return jax.value_and_grad(loss, argnums=argnums)(*args)


class TestMidParity:
    """The satellite matrix: s ∈ {576, 640, 1024, 2048} × causality ×
    feature, value + all grads vs flash AND XLA.  The 576/640 rows are
    the fast tier; 1024/2048 ride the slow tier (interpret-mode block
    loops grow with s²)."""

    @pytest.mark.parametrize("s", [576, 640])
    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_parity_ragged_band(self, s, causal):
        q, k, v = _qkv(jax.random.PRNGKey(s), (1, 2, s, 64))
        got = fmha_mid(q, k, v, causal=causal, implementation="pallas")
        flash = flash_attention(q, k, v, causal=causal,
                                implementation="pallas")
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(got, want, atol=2e-5)
        np.testing.assert_allclose(got, flash, atol=2e-5)

    @pytest.mark.parametrize("feature", ["plain", "bias", "segments",
                                         "dropout"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_vs_flash_and_xla_s576(self, feature, causal):
        s = 576
        q, k, v = _qkv(jax.random.PRNGKey(60 + causal), (1, 2, s, 64))
        kw = dict(causal=causal)
        args = (q, k, v)
        if feature == "bias":
            bias = 0.1 * jax.random.normal(jax.random.PRNGKey(61),
                                           (1, 2, s, s))
            args = (q, k, v, bias)

            def wrap(impl):
                return lambda q, k, v, bias: _impl_call(
                    impl, q, k, v, bias=bias, **kw)
        else:
            if feature == "segments":
                seg = (jnp.arange(s) // 200).astype(jnp.int32)[None]
                kw.update(q_segment_ids=seg, kv_segment_ids=seg)
            elif feature == "dropout":
                kw.update(dropout_rate=0.2, dropout_seed=7)

            def wrap(impl):
                return lambda q, k, v: _impl_call(impl, q, k, v, **kw)

        vals, grads = {}, {}
        for impl in ("mid", "flash", "xla"):
            vals[impl], grads[impl] = _grads(wrap(impl), *args)
        for other in ("flash", "xla"):
            np.testing.assert_allclose(vals["mid"], vals[other], rtol=1e-4)
            for a, b in zip(grads["mid"], grads[other]):
                assert a.shape == b.shape
                np.testing.assert_allclose(a, b, atol=5e-3)

    @pytest.mark.parametrize("causal", [False, True])
    def test_s1024_fwd_and_grads(self, causal):
        q, k, v = _qkv(jax.random.PRNGKey(1024), (1, 1, 1024, 64))
        v_m, g_m = _grads(lambda q, k, v: _impl_call(
            "mid", q, k, v, causal=causal), q, k, v)
        v_x, g_x = _grads(lambda q, k, v: _impl_call(
            "xla", q, k, v, causal=causal), q, k, v)
        np.testing.assert_allclose(v_m, v_x, rtol=1e-5)
        for a, b in zip(g_m, g_x):
            np.testing.assert_allclose(a, b, atol=1e-3)

    @pytest.mark.slow
    @pytest.mark.parametrize("s", [1024, 2048])
    @pytest.mark.parametrize("causal", [False, True])
    def test_everything_composes_big(self, s, causal):
        # bias + segments + dropout + causality at the band's top —
        # value and all FOUR grads vs flash and XLA
        q, k, v = _qkv(jax.random.PRNGKey(s + causal), (1, 1, s, 64))
        bias = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (1, 1, s, s))
        seg = (jnp.arange(s) // (s // 3)).astype(jnp.int32)[None]
        kw = dict(causal=causal, q_segment_ids=seg, kv_segment_ids=seg,
                  dropout_rate=0.1, dropout_seed=42)
        vals, grads = {}, {}
        for impl in ("mid", "flash", "xla"):
            vals[impl], grads[impl] = _grads(
                lambda q, k, v, bias, impl=impl: _impl_call(
                    impl, q, k, v, bias=bias, **kw),
                q, k, v, bias)
        for other in ("flash", "xla"):
            np.testing.assert_allclose(vals["mid"], vals[other], rtol=1e-4)
            for a, b in zip(grads["mid"], grads[other]):
                np.testing.assert_allclose(a, b, atol=5e-3)

    def test_dropout_bit_identical_mask_across_impls(self):
        # same hash, same seed → identical masks on mid / flash / XLA;
        # and the mask must not depend on block configuration
        q, k, v = _qkv(jax.random.PRNGKey(31), (2, 2, 576, 64))
        kw = dict(dropout_rate=0.3, dropout_seed=1234, causal=True)
        m = fmha_mid(q, k, v, implementation="pallas", **kw)
        m2 = fmha_mid(q, k, v, implementation="pallas", block_q=128,
                      block_k=256, block_bh=1, **kw)
        f = flash_attention(q, k, v, implementation="pallas", block_q=256,
                            block_k=256, **kw)
        x = mha_reference(q, k, v, **kw)
        np.testing.assert_allclose(m, m2, atol=1e-5)
        np.testing.assert_allclose(m, f, atol=1e-5)
        np.testing.assert_allclose(m, x, atol=1e-5)
        other = fmha_mid(q, k, v, implementation="pallas", causal=True,
                         dropout_rate=0.3, dropout_seed=99)
        assert float(jnp.max(jnp.abs(m - other))) > 1e-3

    @pytest.mark.parametrize(
        "bias_shape", [(1, 1), (2, 1), (2, 3)]
    )
    def test_bias_broadcast_batchings_and_dbias(self, bias_shape):
        # all three flattened-bias batchings incl. the per-batch mode's
        # block_bh-divides-heads clamp (h=3)
        s = 192
        q, k, v = _qkv(jax.random.PRNGKey(70), (2, 3, s, 32))
        bias = jax.random.normal(jax.random.PRNGKey(71),
                                 bias_shape + (s, s))
        g1 = _grads(lambda q, k, v, bias: fmha_mid(
            q, k, v, bias=bias, causal=True, implementation="pallas",
            block_q=128, block_k=128, block_bh=3), q, k, v, bias)[1]
        g2 = _grads(lambda q, k, v, bias: mha_reference(
            q, k, v, bias=bias, causal=True), q, k, v, bias)[1]
        for a, b in zip(g1, g2):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_constant_mask_bias_skips_dbias(self):
        q, k, v = _qkv(jax.random.PRNGKey(29), (1, 2, 160, 64))
        keep = jnp.logical_or(
            jax.random.bernoulli(jax.random.PRNGKey(30), 0.8,
                                 (1, 1, 160, 160)),
            jnp.eye(160, dtype=bool),
        )
        bias = jnp.where(keep, 0.0, -1e30)
        _, g = _grads(lambda q, k, v, bias: fmha_mid(
            q, k, v, bias=bias, bias_requires_grad=False, causal=True,
            implementation="pallas", block_q=128, block_k=128),
            q, k, v, bias)
        _, gr = _grads(lambda q, k, v: mha_reference(
            q, k, v, bias=bias, causal=True), q, k, v)
        for a, b in zip(g[:3], gr):
            np.testing.assert_allclose(a, b, atol=1e-4)
        np.testing.assert_allclose(g[3], 0.0, atol=0)

    def test_cross_attention_sq_ne_sk(self):
        q, _, _ = _qkv(jax.random.PRNGKey(23), (1, 2, 200, 40))
        _, k, v = _qkv(jax.random.PRNGKey(24), (1, 2, 600, 40))
        got = fmha_mid(q, k, v, implementation="pallas")
        np.testing.assert_allclose(got, mha_reference(q, k, v), atol=2e-5)

    def test_return_lse_value_and_cotangent(self):
        q, k, v = _qkv(jax.random.PRNGKey(40), (1, 2, 320, 64))
        out_p, lse_p = fmha_mid(q, k, v, causal=True, return_lse=True,
                                implementation="pallas")
        out_x, lse_x = fmha_mid(q, k, v, causal=True, return_lse=True,
                                implementation="xla")
        np.testing.assert_allclose(out_p, out_x, atol=2e-5)
        np.testing.assert_allclose(lse_p, lse_x, atol=2e-5)

        def loss(impl):
            def f(q, k, v):
                o, l = fmha_mid(q, k, v, causal=True, return_lse=True,
                                implementation=impl)
                return jnp.sum(o ** 2) + jnp.sum(jnp.sin(l))
            return f

        gp = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_packed_vs_unpacked_bit_identical(self):
        q, k, v = _qkv(jax.random.PRNGKey(25), (2, 3, 160, 64))
        packed = fmha_mid(q, k, v, causal=True, implementation="pallas",
                          block_bh=3, block_q=128, block_k=128)
        single = fmha_mid(q, k, v, causal=True, implementation="pallas",
                          block_bh=1, block_q=128, block_k=128)
        np.testing.assert_allclose(packed, single, atol=0)

    def test_bf16(self):
        q, k, v = (x.astype(jnp.bfloat16)
                   for x in _qkv(jax.random.PRNGKey(5), (1, 2, 640, 128)))
        got = fmha_mid(q, k, v, causal=True, implementation="pallas")
        want = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32), atol=3e-2)

    def test_explicit_pallas_raises_without_pallas(self, monkeypatch):
        from apex_tpu.ops import attention_mid as mod
        from apex_tpu.ops.common import KernelLoweringError

        q = jnp.ones((1, 1, 8, 8))
        monkeypatch.setattr(mod, "pl", None)
        with pytest.raises(KernelLoweringError):
            mod.fmha_mid(q, q, q, implementation="pallas")
        out = mod.fmha_mid(q, q, q)  # auto degrades gracefully
        assert out.shape == (1, 1, 8, 8)

    def test_unknown_implementation_rejected(self):
        q = jnp.ones((1, 1, 8, 8))
        with pytest.raises(ValueError, match="unknown implementation"):
            fmha_mid(q, q, q, implementation="short")


class TestCellCallShapes:
    """The benchmark cells' own calls of the mid band, scaled down in
    batch only and in the cells' dtype (bf16): forward and gradients
    against the XLA reference, with and without ``return_lse``."""

    CELLS = {
        # train-345m: bf16[256,1024,64->128], causal
        "train-345m": dict(shape=(1, 2, 1024, 64), causal=True),
        # train-1.3b-dp2tp2: bf16[32,2048,128], causal
        "train-1.3b": dict(shape=(1, 1, 2048, 128), causal=True),
        # gpt2 jit__prefill: one padded prompt of 960
        "gpt2-prefill": dict(shape=(1, 2, 960, 64), causal=True),
        # the latent chunk through mla_expanded: 192-wide keys, values
        # zero from 128 on, one selection mask shared by the heads
        "latent-chunk": dict(shape=(1, 1, 2048, 192), causal=False,
                             mask=True),
    }

    @pytest.mark.parametrize("return_lse", [False, True])
    @pytest.mark.parametrize("cell", list(CELLS))
    def test_fwd_and_grads_match_reference(self, cell, return_lse):
        spec = self.CELLS[cell]
        q, k, v = (x.astype(jnp.bfloat16)
                   for x in _qkv(jax.random.PRNGKey(29), spec["shape"]))
        kw = dict(causal=spec["causal"])
        if spec.get("mask"):
            s = spec["shape"][2]
            keep = jax.random.bernoulli(jax.random.PRNGKey(30), 0.5, (s, s))
            keep = jnp.logical_or(keep, jnp.eye(s, dtype=bool))
            kw["bias"] = jnp.where(keep, 0.0, -1e30)[None, None]
            v = v.at[..., 128:].set(0)

        def loss(impl):
            def f(q, k, v):
                if impl == "pallas":
                    res = fmha_mid(q, k, v, implementation="pallas",
                                   bias_requires_grad=False,
                                   return_lse=return_lse, **kw)
                elif return_lse:
                    res = _xla_with_lse(q, k, v, kw["causal"], None,
                                        kw.get("bias"), None, None, 0.0,
                                        None)
                else:
                    res = mha_reference(q, k, v, **kw)
                out, lse = res if return_lse else (res, None)
                val = jnp.sum(out.astype(jnp.float32) ** 2)
                if return_lse:
                    val = val + jnp.sum(jnp.sin(lse))
                return val, res
            return f

        (_, got), g_got = jax.value_and_grad(
            loss("pallas"), argnums=(0, 1, 2), has_aux=True)(q, k, v)
        (_, want), g_want = jax.value_and_grad(
            loss("xla"), argnums=(0, 1, 2), has_aux=True)(q, k, v)
        if return_lse:
            np.testing.assert_allclose(got[1], want[1], atol=1e-2)
            got, want = got[0], want[0]
        np.testing.assert_allclose(
            got.astype(jnp.float32), want.astype(jnp.float32), atol=3e-2)
        for a, b in zip(g_got, g_want):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            assert float(jnp.max(jnp.abs(a - b))) <= 3e-2 * max(
                1.0, float(jnp.max(jnp.abs(b))))


def _impl_call(impl, q, k, v, **kw):
    if impl == "mid":
        return fmha_mid(q, k, v, implementation="pallas", **kw)
    if impl == "flash":
        return flash_attention(q, k, v, implementation="pallas",
                               block_q=256, block_k=256, **kw)
    return mha_reference(q, k, v, **kw)


class TestBlockSizing:
    def test_default_blocks_prefer_256_else_128(self):
        assert default_mid_blocks(1024, 1024) == (256, 256)
        assert default_mid_blocks(2048, 2048) == (256, 256)
        assert default_mid_blocks(640, 640) == (128, 128)
        assert default_mid_blocks(640, 1024) == (128, 256)
        # never exceeds the (padded) extent
        assert default_mid_blocks(128, 128) == (128, 128)

    def test_block_bh_budgeted_by_score_area(self):
        assert default_mid_block_bh(256, 256, 64) == 8
        assert default_mid_block_bh(128, 128, 64) == 16   # unroll cap
        assert default_mid_block_bh(512, 512, 64) == 2
        assert default_mid_block_bh(256, 256, 3) == 3     # bh bound

    def test_forward_blocks_widest_that_divide_and_fit(self):
        # (sq_p, sk_p, d_p, operand bytes, bias_batch, bh unit)
        fwd = default_mid_fwd_blocks
        # the cells: train-345m, train-1.3b-dp2tp2, the latent chunk
        # (256 lanes and a shared bias block halve the packing)
        assert fwd(1024, 1024, 128, 2, 0, 8) == (512, 512, 8)
        assert fwd(2048, 2048, 128, 2, 0, 8) == (512, 512, 8)
        assert fwd(2048, 2048, 256, 2, 1, 8) == (512, 512, 4)
        # ragged extents fall to the widest block that divides them
        assert fwd(640, 640, 128, 2, 0, 16) == (128, 128, 16)
        assert fwd(768, 1024, 128, 2, 0, 8) == (256, 512, 8)
        # never wider than the extent
        assert fwd(256, 128, 128, 2, 0, 16) == (256, 128, 16)
        # a bias a head and float32 operands cost VMEM: fewer tiles
        assert fwd(1024, 1024, 128, 2, BIAS_PER_HEAD, 8) == (512, 512, 2)
        assert fwd(1024, 1024, 128, 4, 0, 8) == (512, 512, 4)
        # the packing divides the unit bh is padded to
        assert fwd(1024, 1024, 128, 2, 0, 3) == (512, 512, 3)
        assert fwd(1024, 1024, 128, 4, 0, 6) == (512, 512, 3)

    def test_forward_blocks_leave_the_backwards_alone(self):
        # the backward still runs (256, 256, block_bh_bwd) at the
        # cells' sizes, whatever the forward chose
        assert default_mid_blocks(1024, 1024) == (256, 256)
        assert _bwd_block_bh(default_mid_block_bh(256, 256, 256),
                             1024, 128) == 4
        assert _bwd_block_bh(default_mid_block_bh(256, 256, 32),
                             2048, 128) == 2

    def test_bwd_block_bh_divides_and_fits(self):
        # dq scratch budget: bb * sq_p * d_p <= 512K elements
        assert _bwd_block_bh(8, 1024, 128) == 4
        assert _bwd_block_bh(8, 2048, 128) == 2
        assert _bwd_block_bh(3, 640, 128) == 3
        assert _bwd_block_bh(8, 8192, 128) == 1
        for bb in (1, 2, 3, 4, 6, 8, 16):
            assert bb % _bwd_block_bh(bb, 2048, 128) == 0


class TestLadderDispatch:
    """Auto mode walks short → mid → flash by the measured crossovers;
    each window is env-movable and env-disableable."""

    def _spy(self, monkeypatch):
        from apex_tpu.ops import attention as attn_mod
        from apex_tpu.ops import attention_mid as mid_mod
        from apex_tpu.ops import attention_short as short_mod
        from apex_tpu.utils import platform as plat

        calls = []

        def fake(tag):
            def f(q, *a, **kw):
                calls.append(tag)
                return jnp.zeros(q.shape, q.dtype)
            return f

        monkeypatch.setattr(attn_mod, "_flash_attention_pallas",
                            fake("flash"))
        monkeypatch.setattr(short_mod, "_fmha_short_pallas", fake("short"))
        monkeypatch.setattr(mid_mod, "_fmha_mid_pallas", fake("mid"))
        monkeypatch.setattr(plat, "_current_platform", lambda: "tpu")
        for var in ("APEX_TPU_DISABLE_PALLAS",
                    "APEX_TPU_FMHA_SHORT_MAX_SEQ",
                    "APEX_TPU_FMHA_MID_MAX_SEQ"):
            monkeypatch.delenv(var, raising=False)
        return calls

    def test_short_window_unchanged(self, monkeypatch):
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 2, FMHA_SHORT_MAX_SEQ, 64), jnp.bfloat16)
        flash_attention(q, q, q)
        assert calls == ["short"]

    def test_mid_window_above_short(self, monkeypatch):
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 2, FMHA_SHORT_MAX_SEQ + 64, 64), jnp.bfloat16)
        flash_attention(q, q, q)
        assert calls == ["mid"]

    def test_mid_boundary_inclusive(self, monkeypatch):
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 1, FMHA_MID_MAX_SEQ, 64), jnp.bfloat16)
        flash_attention(q, q, q)
        assert calls == ["mid"]

    def test_above_mid_picks_flash(self, monkeypatch):
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 1, FMHA_MID_MAX_SEQ + 128, 64), jnp.bfloat16)
        flash_attention(q, q, q)
        assert calls == ["flash"]

    def test_cross_attention_keys_on_max_extent(self, monkeypatch):
        # short q + mid-band kv: short disqualified (whole-kv premise),
        # mid takes it (its window keys on max(sq, sk))
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 1, 256, 64), jnp.bfloat16)
        kv = jnp.ones((1, 1, 1024, 64), jnp.bfloat16)
        flash_attention(q, kv, kv)
        assert calls == ["mid"]

    def test_env_override_moves_mid_crossover(self, monkeypatch):
        calls = self._spy(monkeypatch)
        monkeypatch.setenv("APEX_TPU_FMHA_MID_MAX_SEQ", "1024")
        assert mid_seq_threshold() == 1024
        q = jnp.ones((1, 1, 1536, 64), jnp.bfloat16)
        flash_attention(q, q, q)
        assert calls == ["flash"]

    def test_env_zero_pins_ladder_to_flash(self, monkeypatch):
        # the acceptance contract's default-off safety: with the mid
        # window disabled, auto mode runs the EXACT flash path HEAD ran
        calls = self._spy(monkeypatch)
        monkeypatch.setenv("APEX_TPU_FMHA_MID_MAX_SEQ", "0")
        q = jnp.ones((1, 1, 1024, 64), jnp.bfloat16)
        flash_attention(q, q, q)
        assert calls == ["flash"]

    def test_fp32_keeps_xla_window_then_mid(self, monkeypatch):
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 1, 1024, 64), jnp.float32)
        flash_attention(q, q, q)
        assert calls == []  # measured fp32 window still routes to XLA
        q = jnp.ones((1, 1, 1536, 64), jnp.float32)
        flash_attention(q, q, q)
        assert calls == ["mid"]

    def test_explicit_mid_honored_any_shape(self, monkeypatch):
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 1, 256, 64), jnp.float32)
        flash_attention(q, q, q, implementation="mid")
        assert calls == ["mid"]

    def test_explicit_pallas_still_means_flash(self, monkeypatch):
        calls = self._spy(monkeypatch)
        q = jnp.ones((1, 1, 1024, 64), jnp.bfloat16)
        flash_attention(q, q, q, implementation="pallas")
        assert calls == ["flash"]

    def test_pinned_flash_numerics_identical(self, monkeypatch):
        # numeric half of the default-off safety: on this (CPU) host
        # the pinned ladder and HEAD both resolve to the same XLA
        # reference path — assert bit-identity end to end
        monkeypatch.setenv("APEX_TPU_FMHA_MID_MAX_SEQ", "0")
        q, k, v = _qkv(jax.random.PRNGKey(90), (1, 2, 1024, 64))
        pinned = flash_attention(q, k, v, causal=True)
        head = mha_reference(q, k, v, causal=True)
        np.testing.assert_array_equal(np.asarray(pinned), np.asarray(head))


class TestRingInnerImpl:
    """ring_attention(attention_impl=...): the per-shard inner
    attention through the kernel family via the lse merge, with
    fully-masked source shards skipped under causal."""

    @pytest.fixture
    def mesh(self):
        from apex_tpu.transformer import parallel_state

        m = parallel_state.initialize_model_parallel(
            context_parallel_size_=4)
        yield m
        parallel_state.destroy_model_parallel()

    def _run(self, mesh, fn, *args):
        from jax.sharding import PartitionSpec as P

        spec = P(None, None, "cp")
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(spec,) * len(args),
            out_specs=spec,
        ))(*args)

    @pytest.mark.parametrize("impl", ["mid", "xla"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, mesh, impl, causal):
        from apex_tpu.ops.ring_attention import ring_attention

        q, k, v = _qkv(jax.random.PRNGKey(0), (2, 2, 64, 16))
        ref = mha_reference(q, k, v, causal=causal)
        out = self._run(mesh, lambda q, k, v: ring_attention(
            q, k, v, causal=causal, attention_impl=impl), q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("remat", [False, True])
    def test_grads_match_dense(self, mesh, remat):
        from apex_tpu.ops.ring_attention import ring_attention

        q, k, v = _qkv(jax.random.PRNGKey(1), (2, 2, 64, 16))

        def ring_loss(q, k, v):
            return jnp.sum(ring_attention(
                q, k, v, causal=True, attention_impl="mid",
                remat=remat) ** 2)

        from jax.sharding import PartitionSpec as P

        spec = P(None, None, "cp")
        rg = jax.jit(jax.shard_map(
            jax.grad(ring_loss, argnums=(0, 1, 2)), mesh=mesh,
            in_specs=(spec,) * 3, out_specs=(spec,) * 3,
        ))(q, k, v)
        dg = jax.grad(
            lambda q, k, v: jnp.sum(
                mha_reference(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(rg, dg):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)

    def test_default_path_untouched(self, mesh):
        # attention_impl=None must keep the legacy inline walk
        from apex_tpu.ops.ring_attention import ring_attention

        q, k, v = _qkv(jax.random.PRNGKey(2), (2, 2, 64, 16))
        legacy = self._run(mesh, lambda q, k, v: ring_attention(
            q, k, v, causal=True), q, k, v)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(legacy), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)

    def test_bad_impl_rejected(self, mesh):
        from apex_tpu.ops.ring_attention import ring_attention

        q, k, v = _qkv(jax.random.PRNGKey(3), (2, 2, 64, 16))
        with pytest.raises(ValueError, match="attention_impl"):
            self._run(mesh, lambda q, k, v: ring_attention(
                q, k, v, causal=True, attention_impl="nope"), q, k, v)


class TestContribWiring:
    """The mid kernel is reachable through the reference-parity
    wrappers, same as PR 1 proved for the short kernel."""

    def test_fmha_varlen_mid_kernel(self):
        from apex_tpu.contrib.fmha import fmha

        key = jax.random.PRNGKey(60)
        lens = [300, 420]
        total, heads, d = sum(lens), 2, 64
        qkv = jax.random.normal(key, (total, 3, heads, d))
        cu = jnp.asarray(np.cumsum([0] + lens), jnp.int32)
        got = fmha(qkv, cu, max_seq_len=576, causal=True,
                   implementation="mid")
        want = fmha(qkv, cu, max_seq_len=576, causal=True,
                    implementation="xla")
        np.testing.assert_allclose(got, want, atol=2e-5)

    def test_self_mha_attention_impl_mid(self):
        from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn

        x = jax.random.normal(jax.random.PRNGKey(61), (576, 1, 64))
        mha_m = SelfMultiheadAttn(64, 4, impl="fast",
                                  attention_impl="mid")
        mha_d = SelfMultiheadAttn(64, 4, impl="default")
        params = mha_m.init(jax.random.PRNGKey(62))
        got = mha_m.apply(params, x, causal=True)
        want = mha_d.apply(params, x, causal=True)
        np.testing.assert_allclose(got, want, atol=2e-5)
