"""Decode-tier attention (fmha_decode): paged-cache parity + dispatch.

Suite philosophy: the Pallas kernel (interpret mode on CPU) is checked
against the XLA paged reference at every cache layout a serving batch
can produce — shuffled physical pages, ragged lengths ending on
partially-filled pages, idle zero-length slots, int8 pages with
per-block scales, fused q-RoPE — and the contiguous
``flash_attention(implementation="decode")`` seam is pinned against
``mha_reference`` (the training ladder's ground truth).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.attention import flash_attention, mha_reference
from apex_tpu.ops.attention_decode import (
    decode_contiguous,
    fmha_decode,
    paged_attention_reference,
)
from apex_tpu.ops.quantization import quantize_rows
from apex_tpu.ops.rope import apply_rope_tables, rope_cos_sin


def make_cache(key, pool_pages, h, ps, d, b, npp, dtype=jnp.float32,
               shuffle=True):
    """Pools + a shuffled page table: physical layout uncorrelated with
    logical order, like a real allocator's reuse pattern."""
    k0, k1, k2, k3 = jax.random.split(key, 4)
    k_pages = jax.random.normal(k0, (pool_pages, h, ps, d), dtype)
    v_pages = jax.random.normal(k1, (pool_pages, h, ps, d), dtype)
    q = jax.random.normal(k2, (b, h, 1, d), dtype)
    ids = jnp.arange(1, pool_pages, dtype=jnp.int32)
    if shuffle:
        ids = jax.random.permutation(k3, ids)
    page_table = ids[: b * npp].reshape(b, npp)
    return q, k_pages, v_pages, page_table


def quant_pages(pages, kv_block):
    d = pages.shape[-1]
    vals, scales = quantize_rows(
        pages.reshape(-1, d).astype(jnp.float32), kv_block)
    return vals.reshape(pages.shape), scales.reshape(
        *pages.shape[:-1], -1)


class TestPagedParity:
    @pytest.mark.parametrize("sq", [1, 4])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_pallas_matches_xla_ragged_lengths(self, sq, dtype):
        h, ps, d, b, npp = 4, 8, 32, 5, 4
        q, kp, vp, pt = make_cache(
            jax.random.PRNGKey(0), 1 + b * npp, h, ps, d, b, npp, dtype)
        q = jax.random.normal(jax.random.PRNGKey(9), (b, h, sq, d),
                              dtype)
        # every layout class: full, partial tail page, exactly one
        # page, barely past a boundary, minimum (sq tokens)
        lengths = jnp.array(
            [npp * ps, 2 * ps + 3, ps, ps + 1, max(sq, 2)], jnp.int32)
        out_p = fmha_decode(q, kp, vp, pt, lengths,
                            implementation="pallas")
        out_x = fmha_decode(q, kp, vp, pt, lengths,
                            implementation="xla")
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(out_p, np.float32), np.asarray(out_x, np.float32),
            atol=tol)

    def test_matches_dense_reference_exactly_where_defined(self):
        """The paged gather + masking reproduces plain dense causal
        attention over the valid prefix."""
        h, ps, d, b, npp = 2, 8, 16, 3, 3
        q, kp, vp, pt = make_cache(
            jax.random.PRNGKey(1), 1 + b * npp, h, ps, d, b, npp)
        lengths = jnp.array([20, 24, 9], jnp.int32)
        out = fmha_decode(q, kp, vp, pt, lengths,
                          implementation="pallas")
        # dense per-sequence reference from the gathered pages
        for i in range(int(pt.shape[0])):
            n = int(lengths[i])
            kd = jnp.moveaxis(
                kp[pt[i]], 1, 0).reshape(1, h, npp * ps, d)[:, :, :n]
            vd = jnp.moveaxis(
                vp[pt[i]], 1, 0).reshape(1, h, npp * ps, d)[:, :, :n]
            want = mha_reference(q[i:i + 1], kd, vd, causal=False)
            np.testing.assert_allclose(
                np.asarray(out[i:i + 1]), np.asarray(want), atol=1e-5,
                err_msg=f"seq {i}")

    def test_small_sq_causal_masks_each_row(self):
        """sq=4 chunked-prefill rows: row i attends exactly
        lengths - sq + i + 1 positions."""
        h, ps, d, b, npp, sq = 2, 8, 16, 2, 3, 4
        _, kp, vp, pt = make_cache(
            jax.random.PRNGKey(2), 1 + b * npp, h, ps, d, b, npp)
        q = jax.random.normal(jax.random.PRNGKey(3), (b, h, sq, d))
        lengths = jnp.array([19, 11], jnp.int32)
        out = fmha_decode(q, kp, vp, pt, lengths, causal=True,
                          implementation="pallas")
        for i in range(b):
            for r in range(sq):
                n = int(lengths[i]) - sq + r + 1
                kd = jnp.moveaxis(
                    kp[pt[i]], 1, 0).reshape(1, h, npp * ps, d)[:, :, :n]
                vd = jnp.moveaxis(
                    vp[pt[i]], 1, 0).reshape(1, h, npp * ps, d)[:, :, :n]
                want = mha_reference(
                    q[i:i + 1, :, r:r + 1], kd, vd, causal=False)
                np.testing.assert_allclose(
                    np.asarray(out[i:i + 1, :, r:r + 1]),
                    np.asarray(want), atol=1e-5,
                    err_msg=f"seq {i} row {r}")

    def test_noncausal_attends_full_length(self):
        h, ps, d, b, npp = 2, 8, 16, 2, 2
        q, kp, vp, pt = make_cache(
            jax.random.PRNGKey(4), 1 + b * npp, h, ps, d, b, npp)
        lengths = jnp.array([13, 16], jnp.int32)
        out_p = fmha_decode(q, kp, vp, pt, lengths, causal=False,
                            implementation="pallas")
        out_x = fmha_decode(q, kp, vp, pt, lengths, causal=False,
                            implementation="xla")
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                                   atol=1e-5)
        # at sq=1, causal and non-causal are the same mask
        out_c = fmha_decode(q, kp, vp, pt, lengths, causal=True,
                            implementation="pallas")
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_c),
                                   atol=1e-6)

    def test_block_h_grouping_is_bit_identical(self):
        """Head packing is a scheduling choice: every block_h produces
        the SAME bits (per-head state never crosses heads)."""
        h, ps, d, b, npp = 8, 8, 16, 2, 2
        q, kp, vp, pt = make_cache(
            jax.random.PRNGKey(5), 1 + b * npp, h, ps, d, b, npp)
        lengths = jnp.array([12, 16], jnp.int32)
        outs = [
            np.asarray(fmha_decode(q, kp, vp, pt, lengths,
                                   block_h=bh, implementation="pallas"))
            for bh in (1, 2, 4, 8)
        ]
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)

    def test_idle_zero_length_slot_is_finite_and_isolated(self):
        """A zero-length slot (an idle serving slot, table all null
        pages) must produce finite garbage and not perturb live
        slots."""
        h, ps, d, b, npp = 2, 8, 16, 3, 2
        q, kp, vp, pt = make_cache(
            jax.random.PRNGKey(6), 1 + b * npp, h, ps, d, b, npp)
        lengths = jnp.array([12, 0, 16], jnp.int32)
        pt = pt.at[1].set(0)
        out = fmha_decode(q, kp, vp, pt, lengths,
                          implementation="pallas")
        assert bool(jnp.all(jnp.isfinite(out)))
        # live slots bit-match a run where slot 1 holds real pages
        q2, kp2, vp2, pt2 = make_cache(
            jax.random.PRNGKey(6), 1 + b * npp, h, ps, d, b, npp)
        out2 = fmha_decode(q2, kp2, vp2, pt2,
                           jnp.array([12, 16, 16], jnp.int32),
                           implementation="pallas")
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(out2[0]))
        np.testing.assert_array_equal(np.asarray(out[2]),
                                      np.asarray(out2[2]))


class TestInt8Pages:
    @pytest.mark.parametrize("kv_block", [8, 16, 32])
    def test_int8_pallas_matches_int8_xla(self, kv_block):
        h, ps, d, b, npp = 4, 8, 32, 3, 3
        q, kp, vp, pt = make_cache(
            jax.random.PRNGKey(7), 1 + b * npp, h, ps, d, b, npp)
        k8, ks = quant_pages(kp, kv_block)
        v8, vs = quant_pages(vp, kv_block)
        lengths = jnp.array([24, 17, 8], jnp.int32)
        out_p = fmha_decode(q, k8, v8, pt, lengths, k_scales=ks,
                            v_scales=vs, kv_block=kv_block,
                            implementation="pallas")
        out_x = fmha_decode(q, k8, v8, pt, lengths, k_scales=ks,
                            v_scales=vs, kv_block=kv_block,
                            implementation="xla")
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                                   atol=1e-5)

    def test_int8_round_trip_band_vs_fp32(self):
        """int8 pages with per-block scales stay inside the documented
        band of the full-precision cache: per-element error <= a few
        ulp of the block amax, attention output well under 5e-2 for
        unit-scale data."""
        h, ps, d, b, npp = 4, 8, 32, 3, 3
        q, kp, vp, pt = make_cache(
            jax.random.PRNGKey(8), 1 + b * npp, h, ps, d, b, npp)
        k8, ks = quant_pages(kp, 16)
        v8, vs = quant_pages(vp, 16)
        lengths = jnp.array([24, 17, 8], jnp.int32)
        out_fp = fmha_decode(q, kp, vp, pt, lengths,
                             implementation="pallas")
        out_i8 = fmha_decode(q, k8, v8, pt, lengths, k_scales=ks,
                             v_scales=vs, kv_block=16,
                             implementation="pallas")
        err = float(jnp.max(jnp.abs(out_fp - out_i8)))
        assert err < 5e-2, err
        assert err > 0.0     # it IS quantized (the band is not a no-op)

    def test_int8_requires_both_scales(self):
        h, ps, d, b, npp = 2, 8, 16, 1, 1
        q, kp, vp, pt = make_cache(
            jax.random.PRNGKey(9), 1 + b * npp, h, ps, d, b, npp)
        k8, ks = quant_pages(kp, 16)
        with pytest.raises(ValueError, match="BOTH"):
            fmha_decode(q, k8, vp, pt, jnp.array([8]), k_scales=ks)
        with pytest.raises(ValueError, match="int8 pages require"):
            fmha_decode(q, k8, k8, pt, jnp.array([8]))


class TestFusedRope:
    def test_fused_rope_matches_prerotated_q(self):
        h, ps, d, b, npp = 4, 8, 32, 3, 2
        q, kp, vp, pt = make_cache(
            jax.random.PRNGKey(10), 1 + b * npp, h, ps, d, b, npp)
        lengths = jnp.array([12, 16, 5], jnp.int32)
        pos = (lengths[:, None] - 1).astype(jnp.int32)      # sq=1
        cos, sin = rope_cos_sin(pos, d)                     # (b, 1, d/2)
        fused = fmha_decode(q, kp, vp, pt, lengths, rope=(cos, sin),
                            implementation="pallas")
        q_pre = apply_rope_tables(q, cos[:, None], sin[:, None])
        pre = fmha_decode(q_pre, kp, vp, pt, lengths,
                          implementation="pallas")
        np.testing.assert_allclose(np.asarray(fused), np.asarray(pre),
                                   atol=1e-5)
        # and the XLA path applies the same rotation
        xla = fmha_decode(q, kp, vp, pt, lengths, rope=(cos, sin),
                          implementation="xla")
        np.testing.assert_allclose(np.asarray(fused), np.asarray(xla),
                                   atol=1e-5)

    def test_rope_shape_validated(self):
        h, ps, d, b, npp = 2, 8, 16, 2, 1
        q, kp, vp, pt = make_cache(
            jax.random.PRNGKey(11), 1 + b * npp, h, ps, d, b, npp)
        bad = jnp.zeros((b, 2, d // 2))                     # sq=1 != 2
        with pytest.raises(ValueError, match="rope tables"):
            fmha_decode(q, kp, vp, pt, jnp.array([8, 8]),
                        rope=(bad, bad), implementation="pallas")


class TestContiguousSeam:
    def test_flash_attention_decode_matches_reference_causal(self):
        b, h, s, d = 2, 4, 64, 32
        ks = jax.random.split(jax.random.PRNGKey(12), 3)
        q = jax.random.normal(ks[0], (b, h, s, d))
        k = jax.random.normal(ks[1], (b, h, s, d))
        v = jax.random.normal(ks[2], (b, h, s, d))
        out = flash_attention(q, k, v, causal=True,
                              implementation="decode")
        want = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5)

    def test_sq1_tail_matches_full_attention_row(self):
        b, h, s, d = 2, 4, 50, 32
        ks = jax.random.split(jax.random.PRNGKey(13), 3)
        q = jax.random.normal(ks[0], (b, h, s, d))
        k = jax.random.normal(ks[1], (b, h, s, d))
        v = jax.random.normal(ks[2], (b, h, s, d))
        full = mha_reference(q, k, v, causal=True)
        tail = flash_attention(q[:, :, -1:], k, v, causal=True,
                               implementation="decode")
        np.testing.assert_allclose(
            np.asarray(tail), np.asarray(full[:, :, -1:]), atol=1e-5)

    def test_page_size_is_a_scheduling_choice(self):
        # ragged split (s not a page multiple) and different page
        # sizes agree
        b, h, s, d = 2, 2, 50, 16
        ks = jax.random.split(jax.random.PRNGKey(14), 3)
        q = jax.random.normal(ks[0], (b, h, 1, d))
        k = jax.random.normal(ks[1], (b, h, s, d))
        v = jax.random.normal(ks[2], (b, h, s, d))
        outs = [
            np.asarray(decode_contiguous(q, k, v, page_size=ps))
            for ps in (8, 16, 64, 128)
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(outs[0], o, atol=1e-5)

    def test_decode_rejects_bias_segments_dropout(self):
        x = jnp.zeros((1, 1, 8, 16))
        with pytest.raises(ValueError, match="decode"):
            flash_attention(x, x, x, implementation="decode",
                            bias=jnp.zeros((1, 1, 8, 8)))
        with pytest.raises(ValueError, match="decode"):
            flash_attention(x, x, x, implementation="decode",
                            q_segment_ids=jnp.zeros((1, 8), jnp.int32),
                            kv_segment_ids=jnp.zeros((1, 8), jnp.int32))
        with pytest.raises(ValueError, match="decode"):
            flash_attention(x, x, x, implementation="decode",
                            dropout_rate=0.1, dropout_seed=0)

    def test_causal_sq_gt_sk_rejected(self):
        q = jnp.zeros((1, 1, 9, 16))
        k = jnp.zeros((1, 1, 8, 16))
        with pytest.raises(ValueError, match="sq <= sk"):
            decode_contiguous(q, k, k, causal=True)


class TestValidation:
    def test_head_and_dim_mismatch(self):
        q = jnp.zeros((1, 4, 1, 16))
        pool = jnp.zeros((2, 2, 8, 16))
        with pytest.raises(ValueError, match="heads"):
            fmha_decode(q, pool, pool, jnp.zeros((1, 1), jnp.int32),
                        jnp.array([4]))
        pool = jnp.zeros((2, 4, 8, 32))
        with pytest.raises(ValueError, match="head_dim"):
            fmha_decode(q, pool, pool, jnp.zeros((1, 1), jnp.int32),
                        jnp.array([4]))

    def test_page_table_shape(self):
        q = jnp.zeros((2, 2, 1, 16))
        pool = jnp.zeros((3, 2, 8, 16))
        with pytest.raises(ValueError, match="page_table"):
            fmha_decode(q, pool, pool, jnp.zeros((1, 1), jnp.int32),
                        jnp.array([4, 4]))

    def test_unknown_implementation(self):
        q = jnp.zeros((1, 2, 1, 16))
        pool = jnp.zeros((2, 2, 8, 16))
        with pytest.raises(ValueError, match="implementation"):
            fmha_decode(q, pool, pool, jnp.zeros((1, 1), jnp.int32),
                        jnp.array([4]), implementation="fast")

    def test_block_h_must_divide(self):
        q = jnp.zeros((1, 4, 1, 16))
        pool = jnp.zeros((2, 4, 8, 16))
        with pytest.raises(ValueError, match="block_h"):
            fmha_decode(q, pool, pool, jnp.zeros((1, 1), jnp.int32),
                        jnp.array([4]), block_h=3,
                        implementation="pallas")


class TestChunkedPrefill:
    """The s_q-chunk path the stall-free scheduler drives: a chunk
    attends over the prior cache AND its own just-written pages, and
    the head packing shrinks with s_q so the VMEM accumulator scratch
    stays bounded (kernel_validation sweeps the timed s_q in {64, 256}
    cells on TPU; here the semantics are pinned cheaply)."""

    def test_pick_block_h_caps_rows_by_sq(self):
        from apex_tpu.ops.attention_decode import (
            FMHA_DECODE_BLOCK_H,
            FMHA_DECODE_MAX_ROWS,
            _pick_block_h,
        )

        # the s_q = 1 decode default is untouched
        assert _pick_block_h(16) == FMHA_DECODE_BLOCK_H
        assert _pick_block_h(16, 1) == FMHA_DECODE_BLOCK_H
        # chunk s_q's shrink the packing to the row budget
        assert _pick_block_h(16, 64) == FMHA_DECODE_MAX_ROWS // 64
        assert _pick_block_h(16, 256) == FMHA_DECODE_MAX_ROWS // 256
        for h in (3, 6, 12):
            bh = _pick_block_h(h, 256)
            assert bh >= 1 and h % bh == 0
        # past the budget the PALLAS path refuses (even block_h=1
        # cannot honor the scratch bound) — surfaced through
        # run_kernel's runs-or-raises contract; the XLA path still
        # serves when asked for
        from apex_tpu.ops.common import KernelLoweringError

        sq = FMHA_DECODE_MAX_ROWS + 1
        q = jnp.zeros((1, 2, sq, 16))
        pool = jnp.zeros((1 + sq // 8 + 1, 2, 8, 16))
        pt = jnp.arange(1, 2 + sq // 8, dtype=jnp.int32)[None]
        with pytest.raises(KernelLoweringError, match="row budget"):
            fmha_decode(q, pool, pool, pt, jnp.array([sq]),
                        implementation="pallas")
        out = fmha_decode(q, pool, pool, pt, jnp.array([sq]),
                          implementation="xla")
        assert out.shape == q.shape

    def test_chunk_attends_over_own_just_written_pages(self):
        """Write-before-attend: scatter a chunk's K/V into tail pages
        through the serving write path, then attend with s_q = chunk —
        pallas and XLA must match the dense reference over [hist +
        chunk]."""
        from apex_tpu.serving.kv_cache import write_targets, write_tokens

        h, ps, d, npp, hist, chunk = 2, 8, 16, 4, 11, 8
        b = 1
        key = jax.random.PRNGKey(5)
        kh, kv_, kc, kq = jax.random.split(key, 4)
        # history already in the cache
        k_hist = jax.random.normal(kh, (hist, h, d))
        v_hist = jax.random.normal(kv_, (hist, h, d))
        # the chunk's own K/V, written before the attend
        k_chunk = jax.random.normal(kc, (chunk, h, d))
        v_chunk = -k_chunk
        q = jax.random.normal(kq, (b, h, chunk, d))
        pools = {
            "k": jnp.zeros((1 + npp, h, ps, d)),
            "v": jnp.zeros((1 + npp, h, ps, d)),
        }
        row = jnp.arange(1, npp + 1, dtype=jnp.int32)
        pos_h = jnp.arange(hist, dtype=jnp.int32)
        wp, wo = write_targets(row, pos_h, pos_h < hist, ps)
        pools = write_tokens(pools, k_hist, v_hist, wp, wo)
        pos_c = hist + jnp.arange(chunk, dtype=jnp.int32)
        wp, wo = write_targets(row, pos_c, pos_c < hist + chunk, ps)
        pools = write_tokens(pools, k_chunk, v_chunk, wp, wo)
        lengths = jnp.array([hist + chunk], jnp.int32)
        out_p = fmha_decode(q, pools["k"], pools["v"], row[None],
                            lengths, implementation="pallas")
        out_x = fmha_decode(q, pools["k"], pools["v"], row[None],
                            lengths, implementation="xla")
        # dense reference: chunk token i sits at position hist + i
        k_all = jnp.concatenate([k_hist, k_chunk]).transpose(1, 0, 2)
        v_all = jnp.concatenate([v_hist, v_chunk]).transpose(1, 0, 2)
        s = jnp.einsum("bhqd,hkd->bhqk", q, k_all) / d**0.5
        k_pos = jnp.arange(hist + chunk)[None, None, None, :]
        q_pos = (hist + jnp.arange(chunk))[None, None, :, None]
        s = jnp.where(k_pos <= q_pos, s, -1e30)
        ref = jnp.einsum("bhqk,hkd->bhqd", jax.nn.softmax(s, axis=-1),
                         v_all)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(ref),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(out_x), np.asarray(ref),
                                   atol=2e-5)

    def test_large_sq_block_h_auto_shrink_matches_explicit(self):
        """At an s_q past the row budget the auto pick must equal an
        explicitly shrunken block_h, bitwise."""
        h, ps, d, npp, sq = 4, 8, 16, 8, 64
        q, kp, vp, pt = make_cache(
            jax.random.PRNGKey(7), 1 + npp, h, ps, d, 1, npp)
        q = jax.random.normal(jax.random.PRNGKey(8), (1, h, sq, d))
        lengths = jnp.array([ps * npp], jnp.int32)
        auto = fmha_decode(q, kp, vp, pt, lengths,
                           implementation="pallas")
        explicit = fmha_decode(q, kp, vp, pt, lengths, block_h=4,
                               implementation="pallas")
        np.testing.assert_array_equal(np.asarray(auto),
                                      np.asarray(explicit))


# ---------------------------------------------------------------------------
# Several pages a grid step (PR 34)
# ---------------------------------------------------------------------------

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from apex_tpu.ops import attention_decode as ad
from tools.paged_decode_ablation import parent_call

TREE = ((1, 0, 0, 0), (1, 1, 0, 0), (1, 0, 1, 0), (1, 1, 0, 1))


def _variant(name, d, npp, ps=16, b=3, h=2):
    """A call of every kind ``fmha_decode`` serves beside plain decode:
    (arguments, keywords), the table ``npp`` pages wide."""
    key = jax.random.PRNGKey(len(name))
    q, kp, vp, pt = make_cache(key, 1 + b * npp, h, ps, d, b, npp)
    top = npp * ps
    lengths = jnp.array([top - 3, 0, top // 2 + 1], jnp.int32)
    kw = {}
    if name in ("sq4", "tree", "rope_sq4"):
        q = jax.random.normal(key, (b, h, 4, d), jnp.float32)
        lengths = jnp.maximum(lengths, jnp.array([0, 4, 0]))
    if name == "tree":
        kw["ancestor"] = TREE
    if name in ("rope", "rope_sq4"):
        pos = lengths[:, None] - q.shape[2] + jnp.arange(q.shape[2])[None]
        kw["rope"] = rope_cos_sin(jnp.maximum(pos, 0), d)
    if name == "noncausal":
        kw["causal"] = False
    if name == "int8":
        (kp, ks), (vp, vs) = quant_pages(kp, 32), quant_pages(vp, 32)
        kw.update(k_scales=ks, v_scales=vs, kv_block=32)
    return (q, kp, vp, pt, lengths), kw


class TestPagesAStep:
    """The rule's pages a step, and every kind of call at the pages it
    gives them."""

    @pytest.mark.parametrize("shape,want", [
        # page, d, block_h, itemsize, table pages, scales -> pages a step
        ((64, 128, 8, 2, 65, False), 8),     # trinity's window walk
        ((64, 128, 8, 2, 200, False), 8),    # trinity's full walk
        ((64, 64, 16, 2, 16, False), 1),     # gpt2: 64 lanes, the pipeline
        ((64, 128, 16, 2, 16, False), 2),    # a short table, small steps
        ((64, 128, 8, 2, 20, False), 3),
        ((64, 128, 8, 1, 200, True), 1),     # int8: scale planes a page
        ((128, 128, 8, 2, 200, False), 4),   # 512 keys a step
        ((16, 128, 8, 4, 24, False), 3),
        ((512, 256, 16, 4, 64, False), 1),   # VMEM: 8 MiB a page block
        ((64, 256, 16, 4, 200, False), 2),   # VMEM before the key bound
    ])
    def test_rule(self, shape, want):
        assert ad._pages_per_step(*shape) == want
        page, d, bh, itemsize, _, scales = shape
        if want > 1:
            tiles = 2 * 2 * want * bh * page * d * itemsize
            assert tiles <= ad.FMHA_DECODE_TILE_BYTES
            assert want * page <= ad.FMHA_DECODE_STEP_KEYS

    @pytest.mark.parametrize("npp", [8, 16, 24])        # 1, 2, 3 pages
    @pytest.mark.parametrize("name", [
        "sq4", "tree", "rope", "rope_sq4", "noncausal", "int8"])
    def test_every_kind_at_the_rules_pages(self, name, npp):
        args, kw = _variant(name, 128, npp)
        want = 1 if name == "int8" else npp // 8
        assert ad._pages_per_step(
            16, 128, 2, args[1].dtype.itemsize, npp, name == "int8") == want
        out = fmha_decode(*args, implementation="pallas", **kw)
        ref = fmha_decode(*args, implementation="xla", **kw)
        live = np.asarray(args[4]) > 0
        np.testing.assert_allclose(np.asarray(out)[live],
                                   np.asarray(ref)[live], atol=2e-5)
        assert np.isfinite(np.asarray(out)).all()

    @pytest.mark.parametrize("d", [32, 128])
    @pytest.mark.parametrize("name", [
        "plain", "sq4", "tree", "rope", "noncausal", "int8"])
    def test_one_page_a_step_is_the_walk_through_pr33_to_the_bit(
            self, name, d):
        """At one page a step the kernel computes what the kernel it
        replaced computed (kept in tools/paged_decode_ablation.py), bit
        for bit, in interpret mode."""
        args, kw = _variant(name, d, 4)
        assert ad._pages_per_step(
            16, d, 2, args[1].dtype.itemsize, 4, name == "int8") == 1
        out = fmha_decode(*args, implementation="pallas", **kw)
        was = parent_call(*args, **kw)
        if name == "rope":
            # the rotation is the same three products and one sum, over
            # the head block at once and not a head at a time: XLA:CPU
            # contracts a multiply-add or not by the shape it is handed
            np.testing.assert_allclose(np.asarray(out), np.asarray(was),
                                       rtol=0, atol=5e-7)
        else:
            np.testing.assert_array_equal(np.asarray(out), np.asarray(was))
