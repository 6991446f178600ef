"""Quantized weight pools: int4 packing, the in-tile dequant matmul,
the checkpoint-load conversion seam, and width threading through
``decode_fns``.

The load-bearing claims, each pinned here:

- ``pack_int4``/``unpack_int4`` round-trip every nibble exactly for
  random shapes, and the halves layout is pinned bit-for-bit (packed
  column ``c`` = column ``c`` LOW nibble, column ``c + n/2`` HIGH) —
  the kernel's single-concat unpack depends on that exact pairing;
- the strict block validation names the offending leaf: odd int4
  blocks, rows that 2*block does not tile, and non-dividing int8
  blocks all raise actionable errors instead of silently padding;
- ``dequant_matmul`` (Pallas, interpreted on CPU) is bit-identical to
  the XLA fallback and to dequantize-then-dot for both widths, with
  leading batch dims flattened and the block size recoverable from the
  scales' shape alone;
- ZeRO-3 checkpoint -> ``unshard_params(transform=quantize)`` produces
  BIT-identical pools to quantizing the replicated weights directly
  (the quantize-at-load seam: the rebuild is exact, quantization is a
  pure function of the weight bits);
- ``decode_fns`` converts once and stamps the width: a pre-quantized
  tree is accepted (the fleet's share-don't-copy seam) and generates
  token-identically to the quantize-inside path, a mismatched declared
  width raises, and the quantized pool streams fewer bytes than fp32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.dequant_matmul import (
    dequant_matmul,
    dequant_matmul_reference,
    dequantize_weight,
    quantize_weight,
    weight_pool_block,
    weight_pool_dtype,
)
from apex_tpu.ops.quantization import (
    dequantize_rows_int4,
    pack_int4,
    quantize_rows,
    quantize_rows_int4,
    unpack_int4,
)


class TestInt4Pack:
    def test_round_trip_property(self):
        """Exact nibble round trip over random shapes — every value in
        [-8, 7] must survive pack -> unpack bit-for-bit."""
        rng = np.random.RandomState(0)
        for rows, n in [(1, 2), (3, 8), (5, 64), (7, 130), (16, 256)]:
            q = rng.randint(-8, 8, (rows, n)).astype(np.int8)
            packed = np.asarray(pack_int4(jnp.asarray(q)))
            assert packed.shape == (rows, n // 2)
            assert packed.dtype == np.int8
            np.testing.assert_array_equal(
                np.asarray(unpack_int4(jnp.asarray(packed))), q)

    def test_halves_layout_pinned(self):
        """Packed column c = column c (LOW) | column c + n/2 (HIGH) —
        the layout the kernel's single-concat unpack assumes."""
        q = jnp.asarray([[1, -2, 3, -4]], jnp.int8)
        packed = np.asarray(pack_int4(q)).astype(np.int32) & 0xFF
        lo = ((packed & 0xF) ^ 8) - 8
        hi = (((packed >> 4) & 0xF) ^ 8) - 8
        np.testing.assert_array_equal(lo, [[1, -2]])
        np.testing.assert_array_equal(hi, [[3, -4]])

    def test_odd_row_length_rejected(self):
        with pytest.raises(ValueError, match="even row length"):
            pack_int4(jnp.zeros((2, 5), jnp.int8))

    def test_quantize_rows_int4_band(self):
        """Each dequantized element stays within half a quantization
        step (amax/7/2) of its source, per block."""
        rng = np.random.RandomState(1)
        x = rng.randn(6, 64).astype(np.float32)
        bs = 16
        packed, scales = quantize_rows_int4(jnp.asarray(x), bs)
        back = np.asarray(dequantize_rows_int4(packed, scales, bs))
        amax = np.abs(x.reshape(6, -1, bs)).max(axis=2)
        tol = (amax / 7.0 / 2.0 + 1e-7)[:, :, None]
        assert (np.abs((back - x).reshape(6, -1, bs)) <= tol).all()

    def test_strict_block_errors_name_the_leaf(self):
        x = jnp.zeros((2, 96), jnp.float32)
        with pytest.raises(ValueError, match="must be even"):
            quantize_rows_int4(x, 3, leaf="layers/qkv.weight")
        # 96 % (2*32) != 0: a nibble half would straddle a block
        with pytest.raises(ValueError, match="layers/qkv.weight"):
            quantize_rows_int4(x, 32, leaf="layers/qkv.weight")
        with pytest.raises(ValueError, match="layers/fc1.weight"):
            quantize_rows(x, 36, leaf="layers/fc1.weight")
        # without a leaf the legacy padding contract stands
        v, s = quantize_rows(x, 36)
        assert v.shape == (2, 96)


class TestDequantMatmul:
    @pytest.mark.parametrize("weight_dtype", ["int8", "int4"])
    def test_pallas_matches_xla_and_reference(self, weight_dtype):
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(4, 32).astype(np.float32))
        w = jnp.asarray(rng.randn(32, 64).astype(np.float32))
        wq = quantize_weight(w, weight_dtype, 16)
        qv = wq["q8"] if weight_dtype == "int8" else wq["q4"]
        ref = dequant_matmul_reference(
            x, qv, wq["scales"], weight_dtype=weight_dtype,
            block_size=16)
        for impl in ("pallas", "xla"):
            out = dequant_matmul(
                x, qv, wq["scales"], weight_dtype=weight_dtype,
                implementation=impl)
            np.testing.assert_array_equal(
                np.asarray(out), np.asarray(ref))

    def test_leading_batch_dims_flattened(self):
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(2, 3, 32).astype(np.float32))
        w = jnp.asarray(rng.randn(32, 64).astype(np.float32))
        wq = quantize_weight(w, "int8", 16)
        out = dequant_matmul(x, wq["q8"], wq["scales"],
                             weight_dtype="int8")
        assert out.shape == (2, 3, 64)
        flat = dequant_matmul(x.reshape(6, 32), wq["q8"], wq["scales"],
                              weight_dtype="int8")
        np.testing.assert_array_equal(
            np.asarray(out).reshape(6, 64), np.asarray(flat))

    def test_block_size_recovered_from_scales(self):
        w = jnp.asarray(np.random.RandomState(4)
                        .randn(32, 64).astype(np.float32))
        wq = quantize_weight(w, "int4", 16)
        assert weight_pool_dtype(wq) == "int4"
        assert weight_pool_block(wq) == 16
        wq8 = quantize_weight(w, "int8", 32)
        assert weight_pool_dtype(wq8) == "int8"
        assert weight_pool_block(wq8) == 32

    def test_dequantize_weight_round_trip_band(self):
        rng = np.random.RandomState(5)
        w = rng.randn(32, 64).astype(np.float32)
        wq = quantize_weight(jnp.asarray(w), "int8", 16)
        back = np.asarray(dequantize_weight(wq))
        amax = np.abs(w.reshape(32, -1, 16)).max(axis=2)
        tol = (amax / 127.0 / 2.0 + 1e-7)[:, :, None]
        assert (np.abs((back - w).reshape(32, -1, 16)) <= tol).all()

    def test_validation_errors(self):
        x = jnp.zeros((4, 32), jnp.float32)
        w = jnp.asarray(np.random.RandomState(6)
                        .randn(32, 64).astype(np.float32))
        wq = quantize_weight(w, "int8", 16)
        with pytest.raises(ValueError, match="weight_dtype"):
            dequant_matmul(x, wq["q8"], wq["scales"],
                           weight_dtype="fp8")
        with pytest.raises(ValueError):
            dequant_matmul(jnp.zeros((4, 16), jnp.float32), wq["q8"],
                           wq["scales"], weight_dtype="int8")
        with pytest.raises(ValueError):
            dequant_matmul(x, wq["q8"], wq["scales"],
                           weight_dtype="int8", block_size=24)


# ---------------------------------------------------------------------------
# The quantize-at-load seam: ZeRO-3 checkpoint -> unshard -> pools
# ---------------------------------------------------------------------------


def _tiny_gpt():
    from apex_tpu.models import GPTConfig, GPTModel

    model = GPTModel(GPTConfig(
        vocab_size=64, num_layers=2, hidden_size=32,
        num_attention_heads=4, max_position_embeddings=64,
        compute_dtype=jnp.float32, remat=False, attention_impl="xla",
    ))
    return model, model.init(jax.random.PRNGKey(0))


class TestUnshardQuantizeSeam:
    def test_unshard_transform_bit_identical_to_direct(self):
        """quantize(unshard(shard(params))) == quantize(params) for
        both widths — the full-width tree never needs to exist on
        device to build the serving pools from a ZeRO-3 checkpoint."""
        from apex_tpu.contrib.optimizers import DistributedFusedAdam
        from apex_tpu.models.gpt import quantize_gpt_weights
        from apex_tpu.transformer import parallel_state

        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel()
        try:
            model, params = _tiny_gpt()
            opt = DistributedFusedAdam(lr=1e-2, shard_params=True,
                                       bucket_bytes=4096)
            opt.build_layout(params, mesh=mesh)
            pspec = jax.tree.map(lambda _: P(), params)
            shards = jax.jit(jax.shard_map(
                opt.init_shards, mesh=mesh, in_specs=(pspec,),
                out_specs=opt.shard_spec()))(params)
            ckpt = np.asarray(jax.device_get(shards))
            for wd in ("int8", "int4"):
                pools = opt.unshard_params(
                    ckpt,
                    transform=lambda p: quantize_gpt_weights(
                        p, wd, 16))
                direct = quantize_gpt_weights(params, wd, 16)
                jax.tree.map(
                    lambda a, b: np.testing.assert_array_equal(
                        np.asarray(a), np.asarray(b)),
                    pools, direct)
        finally:
            parallel_state.destroy_model_parallel()


# ---------------------------------------------------------------------------
# Width threading through decode_fns (single-device serving mesh)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serve_setup():
    from apex_tpu.transformer import parallel_state

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        devices=jax.devices()[:1])
    model, params = _tiny_gpt()
    rng = np.random.RandomState(7)
    prompts = rng.randint(1, 64, (4, 10)).astype(np.int32)
    plens = np.array([10, 8, 6, 9], np.int32)
    yield mesh, model, params, prompts, plens
    parallel_state.destroy_model_parallel()


def _run_batcher(serve_setup, fns_src, weight_dtype=None, new=8):
    from apex_tpu.serving.kv_cache import (
        KVCacheConfig, PagedKVCache, init_pools,
    )
    from apex_tpu.serving.serve import ContinuousBatcher, Request

    mesh, model, params, prompts, plens = serve_setup
    page = 4
    pps = -(-(10 + new) // page)
    ccfg = KVCacheConfig(
        num_layers=2, num_heads=4, head_dim=8,
        num_pages=1 + 2 * pps, page_size=page, max_seqs=2,
        pages_per_seq=pps, dtype=jnp.float32)
    fns = model.decode_fns(fns_src, mesh, ccfg, max_prompt_len=10,
                           weight_dtype=weight_dtype, weight_block=16)
    batcher = ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), init_pools(ccfg),
        max_prompt_len=10, harvest_every=4)
    comps = batcher.run([
        Request(uid=i, prompt=[int(t) for t in prompts[i, :plens[i]]],
                max_new_tokens=new)
        for i in range(4)])
    return fns, comps


class TestDecodeFnsWidths:
    def test_convert_once_and_stamp(self, serve_setup):
        _, _, params, _, _ = serve_setup
        fp_bytes = int(sum(x.nbytes for x in jax.tree.leaves(params)))
        fns, comps = _run_batcher(serve_setup, params,
                                  weight_dtype="int8")
        assert fns.weight_dtype == "int8"
        assert 0 < fns.weight_stream_bytes < fp_bytes
        assert all(len(comps[i].tokens) == 8 for i in range(4))

    def test_prequantized_pool_shared_not_requantized(self, serve_setup):
        """The fleet seam: a pre-quantized tree with a MATCHING
        declared width is accepted as-is and generates exactly what
        the quantize-inside path generates."""
        from apex_tpu.models.gpt import quantize_gpt_weights

        _, _, params, _, _ = serve_setup
        qp = quantize_gpt_weights(params, "int8", 16)
        _, inside = _run_batcher(serve_setup, params,
                                 weight_dtype="int8")
        fns, shared = _run_batcher(serve_setup, qp,
                                   weight_dtype="int8")
        assert fns.weight_dtype == "int8"
        for i in range(4):
            assert shared[i].tokens == inside[i].tokens
        # declaring nothing infers the width from the structure
        fns2, inferred = _run_batcher(serve_setup, qp)
        assert fns2.weight_dtype == "int8"
        for i in range(4):
            assert inferred[i].tokens == inside[i].tokens

    def test_mismatched_width_rejected(self, serve_setup):
        from apex_tpu.models.gpt import quantize_gpt_weights

        _, _, params, _, _ = serve_setup
        qp = quantize_gpt_weights(params, "int8", 16)
        with pytest.raises(ValueError, match="int8"):
            _run_batcher(serve_setup, qp, weight_dtype="int4")

    def test_int4_band_wider_but_bounded(self, serve_setup):
        """int4 weights still complete generation; its logits ride a
        wider band (gated in the dryrun, not re-measured here)."""
        fns, comps = _run_batcher(serve_setup, serve_setup[2],
                                  weight_dtype="int4")
        assert fns.weight_dtype == "int4"
        assert all(len(comps[i].tokens) == 8 for i in range(4))
