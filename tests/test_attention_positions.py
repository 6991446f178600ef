"""``flash_attention`` told where its rows sit (``q_offset`` /
``q_period`` / ``window``): the position-bounded causal form that the
prefill chunks of ``models/xing4.py`` and ``models/afmoe.py`` call
instead of building an ``(n, S)`` mask.

- the kernel (Pallas interpret mode) and the XLA path against
  ``mha_reference`` under the mask the positions stand for, built here
  explicitly;
- what the kernel lowers to, in equations, beside the bias variant it
  replaces, and every variant that takes no positions at the count it
  had before the form existed;
- ``k_blocks_run`` (the kernel's own bounds, ``k_block_bounds``) against
  a brute-force count over the explicit mask, and the
  ``tlm.serve.dispatch_prefill`` span that carries it for the chunk
  functions that offer it.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.attention import (
    flash_attention, k_block_bounds, k_blocks_run, mha_reference,
)
from test_attention_decode_grouped import _equations

BQ, BK, D = 16, 32, 16

# name: (q rows, keys, q_offset, q_period, window)
CASES = {
    "offset-0": (32, 96, 0, None, 0),
    "offset-inside-a-block": (32, 96, 37, None, 0),
    "offset-on-a-block-edge": (32, 96, 64, None, 0),
    "offset-keys-minus-rows": (32, 96, 64, None, 7),
    "window-under-a-block": (32, 96, 40, None, 5),
    "window-over-blocks": (32, 128, 80, None, 50),
    "window-over-the-extent": (32, 96, 50, None, 500),
    "window-from-offset-0": (32, 96, 0, None, 9),
    "grouped-rows": (48, 96, 30, 16, 0),
    "grouped-rows-in-a-window": (48, 128, 70, 16, 40),
    "padded-key-tail": (32, 100, 68, None, 0),
    "padded-key-tail-in-a-window": (32, 100, 68, None, 40),
    "bucket-past-the-last-column": (32, 160, 20, None, 0),
    "padded-rows": (40, 96, 50, None, 0),
}


def _seen(sq, sk, offset, period, window):
    """The mask the positions stand for, (sq, sk) bool."""
    t = offset + np.arange(sq) % (period or sq)
    c = np.arange(sk)
    seen = c[None] <= t[:, None]
    if window:
        seen &= t[:, None] - c[None] < window
    return seen


def _qkv(sq, sk, dtype, heads=2):
    ks = jax.random.split(jax.random.PRNGKey(sq * 1000 + sk), 3)
    return (jax.random.normal(ks[0], (1, heads, sq, D), dtype),
            jax.random.normal(ks[1], (1, heads, sk, D), dtype),
            jax.random.normal(ks[2], (1, heads, sk, D), dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CASES))
def test_positions_match_the_mask_they_stand_for(case, dtype):
    sq, sk, offset, period, window = CASES[case]
    q, k, v = _qkv(sq, sk, dtype)
    seen = _seen(sq, sk, offset, period, window)
    assert seen.any(axis=1).all()
    want = mha_reference(
        q, k, v, bias=jnp.where(seen, 0.0, -1e30)[None, None])
    told = dict(causal=True, q_offset=offset, q_period=period, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for impl in ("pallas", "xla"):
        got = flash_attention(q, k, v, block_q=BQ, block_k=BK,
                              implementation=impl, **told)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol, err_msg=impl)


def test_one_executable_serves_every_offset():
    sq, sk = 32, 128
    q, k, v = _qkv(sq, sk, jnp.float32)
    f = jax.jit(lambda q, k, v, at: flash_attention(
        q, k, v, causal=True, q_offset=at, window=40, block_q=BQ,
        block_k=BK, implementation="pallas"))
    for offset in (11, 96):
        want = mha_reference(q, k, v, bias=jnp.where(
            _seen(sq, sk, offset, None, 40), 0.0, -1e30)[None, None])
        np.testing.assert_allclose(
            np.asarray(f(q, k, v, jnp.int32(offset))), np.asarray(want),
            rtol=2e-5, atol=2e-5)
    assert f._cache_size() == 1


@pytest.mark.parametrize("what", [
    "differentiated", "short", "mid", "decode", "not-causal", "with-a-bias",
    "a-period-that-does-not-divide"])
def test_what_the_positions_do_not_go_with_raises(what):
    q, k, v = _qkv(32, 96, jnp.float32)
    call = lambda q, **kw: flash_attention(
        q, k, v, **{**dict(causal=True, q_offset=40, block_q=BQ, block_k=BK,
                           implementation="pallas"), **kw})
    if what == "differentiated":
        with pytest.raises(RuntimeError, match="forward-only"):
            jax.grad(lambda q: call(q).sum())(q)
    elif what in ("short", "mid", "decode"):
        with pytest.raises(ValueError, match="takes no q_offset"):
            call(q, implementation=what)
    elif what == "not-causal":
        with pytest.raises(ValueError, match="causal=True"):
            call(q, causal=False)
    elif what == "with-a-bias":
        with pytest.raises(ValueError, match="no bias"):
            call(q, bias=jnp.zeros((32, 96)))
    else:
        with pytest.raises(ValueError, match="does not divide"):
            call(q, q_period=24)


# ---------------------------------------------------------------------------
# what the kernel lowers to
# ---------------------------------------------------------------------------
_SDS = jax.ShapeDtypeStruct
_BF = jnp.bfloat16


def _count(f, *args):
    return _equations(jax.make_jaxpr(f)(*args))


def _pallas(traced=None, **kw):
    """``flash_attention`` forced to the kernel; a fourth argument, if
    any, goes to the keyword ``traced`` names."""
    return lambda q, k, v, *more: flash_attention(
        q, k, v, implementation="pallas", **kw,
        **({traced: more[0]} if traced else {}))


def _trinity_shaped_fns():
    """``decode_fns`` of a small model with Trinity's head width, group,
    window, chunk and layer kinds (no weights: the steps close over
    none), and its cache config."""
    from jax.sharding import Mesh

    from apex_tpu.models.afmoe import FULL, SLIDING, AfmoeConfig, AfmoeModel
    from apex_tpu.serving.kv_cache import KVCacheConfig

    model = AfmoeModel(AfmoeConfig.from_hf(dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=5,
        num_dense_layers=1, num_attention_heads=12, num_key_value_heads=2,
        head_dim=128, intermediate_size=128, moe_intermediate_size=32,
        num_experts_per_tok=2, num_shared_experts=1, route_scale=2.448,
        rms_norm_eps=1e-5, rope_theta=10000.0, mup_enabled=True,
        sliding_window=4096, layer_types=[SLIDING] * 4 + [FULL]),
        num_experts=16, held_experts=(1, 4, 6, 11),
        params_dtype=jnp.bfloat16))
    ccfg = KVCacheConfig.of_classes(
        model.cache_classes(slots=2, pages_per_seq=200, page_size=64,
                            prefill_chunk=1024),
        page_size=64, max_seqs=2, dtype=jnp.bfloat16)
    fns = model.decode_fns(
        None, Mesh(np.array(jax.devices()[:1]), ("tp",)), ccfg,
        max_prompt_len=12288, prefill_chunk=1024)
    return model, ccfg, fns


# the chunk shapes: (heads, q rows, keys, width, q_period, window)
CHUNKS = {
    "xing4": (32, 4096, 20480, 192, None, 0),
    "trinity-full": (8, 6144, 13312, 128, 1024, 0),
    "trinity-window": (8, 6144, 5120, 128, 1024, 4096),
}
# equations of one flash_attention call: under the float32 bias (the
# parent's, unchanged), and told its positions
BIAS_EQUATIONS = {"xing4": 81, "trinity-full": 71, "trinity-window": 71}
POSITIONED_EQUATIONS = {"xing4": 135, "trinity-full": 125,
                        "trinity-window": 143}


@pytest.mark.parametrize("chunk", list(CHUNKS))
def test_the_positioned_kernel_lowers_to_no_more_than_a_causal_one(chunk):
    """ROADMAP S10: a kernel costs set-up what it lowers to, in every
    chunk program once a layer.  The bias variant these calls replace is
    ONE body with a load and an add for its mask (81 / 71 equations a
    call); the positioned form is the causal form's TWO bodies (the
    blocks an edge cuts, the others) and so lowers to what a causal call
    does (140), less its ``lse``.  One lowered body with the cut blocks'
    mask under a ``cond`` was built and counted 92 / 82 / 100 — and ran
    1.35-1.55x SLOWER than two on the chip, slower than the bias variant
    itself (PERF.md section 6, PR 36), so the count is held here at what
    the faster form costs."""
    h, sq, sk, d, period, window = CHUNKS[chunk]
    q, k = _SDS((1, h, sq, d), _BF), _SDS((1, h, sk, d), _BF)
    bias = _count(_pallas("bias", causal=False, sm_scale=0.1,
                          bias_requires_grad=False),
                  q, k, k, _SDS((1, 1, sq, sk), jnp.float32))
    assert bias == BIAS_EQUATIONS[chunk]
    told = _count(_pallas("q_offset", causal=True, sm_scale=0.1,
                          q_period=period, window=window),
                  q, k, k, _SDS((), jnp.int32))
    assert told <= POSITIONED_EQUATIONS[chunk], told
    # a window is a few equations of mask and a bound more than the
    # plain causal call at these shapes; nothing else is
    assert told <= UNTOLD_EQUATIONS["causal"] + 3


def test_a_programs_layers_lower_the_positioned_kernel_once():
    """What keeps the two bodies from costing set-up: the call is a
    jitted function, so the layers of a program that make it at the same
    shapes share ONE traced and lowered kernel.  A Trinity-shaped chunk
    program (four window layers, one full) lowers two flash kernels, not
    five (trace + lowering of the cell's five chunk programs: 3.4 s at
    the parent, 3.9 s with five kernels a program, 2.8 s with two; off
    the chip, PERF.md section 6, PR 36)."""
    from apex_tpu.serving.kv_cache import init_pools
    from apex_tpu.utils import platform

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(platform, "_current_platform", lambda: "tpu")
        model, ccfg, fns = _trinity_shaped_fns()
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        text = fns.chunk_jit.trace(
            jax.eval_shape(model.init, jax.random.PRNGKey(0)),
            jax.eval_shape(lambda: init_pools(ccfg)), i32(1, 1024), i32(),
            i32(), i32(), i32(ccfg.table_columns[-1][1]),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)), ctx_len=8192,
        ).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "tlm.kernel.fmha_flash.fwd"') == 2
    # the toy's experts expect 128 rows a chunk (Trinity's 16), so since
    # PR 38 its four expert layers take the grouped product, a jitted
    # call as well: each of its two kernels lowered once
    assert text.count("tpu_custom_call") == 4


# every variant that takes no positions, at the count it had before
# the positioned form existed (the parent's, commit fd48b03)
UNTOLD_EQUATIONS = {
    "plain": 68, "causal": 140, "causal-padded": 152, "segments": 137,
    "dropout": 213, "bias-per-batch": 83, "causal-grad": 373,
}


@pytest.mark.parametrize("variant", list(UNTOLD_EQUATIONS))
def test_calls_without_positions_lower_to_what_they_did(variant):
    q = _SDS((2, 4, 4096, 128), _BF)
    short = _SDS((2, 4, 4000, 128), _BF)
    seg = _SDS((2, 4096), jnp.int32)
    count = {
        "plain": lambda: _count(_pallas(), q, q, q),
        "causal": lambda: _count(_pallas(causal=True), q, q, q),
        "causal-padded": lambda: _count(
            _pallas(causal=True), short, short, short),
        "segments": lambda: _count(
            lambda q, k, v, s: flash_attention(
                q, k, v, causal=True, q_segment_ids=s, kv_segment_ids=s,
                implementation="pallas"), q, q, q, seg),
        "dropout": lambda: _count(
            _pallas(causal=True, dropout_rate=0.1, dropout_seed=3), q, q, q),
        "bias-per-batch": lambda: _count(
            _pallas("bias", bias_requires_grad=False), q, q, q,
            _SDS((2, 1, 4096, 4096), jnp.float32)),
        "causal-grad": lambda: _count(
            lambda q, k, v: jax.grad(lambda q: flash_attention(
                q, k, v, causal=True, implementation="pallas").astype(
                    jnp.float32).sum())(q), q, q, q),
    }[variant]()
    assert count == UNTOLD_EQUATIONS[variant]


# ---------------------------------------------------------------------------
# the counter
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", list(CASES))
def test_blocks_run_are_the_blocks_the_mask_leaves(case):
    """Block (j, kb) has to run iff the mask has a true entry in it;
    the bounds are a RANGE of kb a q block, so they may also hold a
    block between two that run — never one outside them."""
    sq, sk, offset, period, window = CASES[case]
    run, extent = k_blocks_run(sq, sk, offset, period, window, BQ, BK,
                               jnp.float32)
    num_q, num_k = -(-sq // BQ), -(-sk // BK)
    assert extent == num_q * num_k
    seen = np.zeros((num_q * BQ, num_k * BK), bool)
    # padded rows sit where their index says (one period holds them)
    seen[:, :sk] = _seen(num_q * BQ, sk, offset, period or num_q * BQ, window)
    blocks = seen.reshape(num_q, BQ, num_k, BK).any(axis=(1, 3))
    first, last, lo = k_block_bounds(sq, sk, offset, period or sq, window,
                                     BQ, BK)
    kb = np.arange(num_k)
    inside = (kb[None] >= first[:, None]) & (kb[None] <= last[:, None])
    assert not (blocks & ~inside).any()         # nothing seen is skipped
    assert run == int(inside.sum())
    # the range is tight: its two ends are blocks the mask needs
    for j in range(num_q):
        assert blocks[j, first[j]] and blocks[j, last[j]]
    np.testing.assert_array_equal(
        lo, offset + (np.arange(num_q) * BQ) % (period or num_q * BQ))


def test_the_bounds_are_one_arithmetic_traced_or_not():
    args = (6144, 5120, 1024, 4096, 512, 1024)
    host = k_block_bounds(*args[:2], 4096, *args[2:])
    traced = jax.jit(lambda at: k_block_bounds(*args[:2], at, *args[2:]))(
        jnp.int32(4096))
    for a, b in zip(host, traced):
        np.testing.assert_array_equal(a, np.asarray(b))
    # Trinity's window layer two windows deep: all five blocks run
    assert k_blocks_run(6144, 5120, 4096, 1024, 4096) == (30, 30)
    # Xing4's first chunk: the triangle's half, as far as 1,024 x 1,024
    # blocks cut it; its fifth: under a tenth
    assert k_blocks_run(4096, 4096, 0) == (10, 16)
    assert k_blocks_run(4096, 20480, 16384) == (74, 80)
    assert k_blocks_run(4096, 20480, 16384, block_q=512) == (148, 160)


# ---------------------------------------------------------------------------
# the span that carries the count
# ---------------------------------------------------------------------------
def _gpt_fns(mesh):
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving.kv_cache import KVCacheConfig

    model = GPTModel(GPTConfig(
        vocab_size=64, num_layers=2, hidden_size=32, num_attention_heads=4,
        max_position_embeddings=64, compute_dtype=jnp.float32, remat=False,
        attention_impl="xla"))
    ccfg = KVCacheConfig(
        num_layers=2, num_heads=4, head_dim=8, num_pages=1 + 2 * 8,
        page_size=4, max_seqs=2, pages_per_seq=8, dtype=jnp.float32)
    fns = model.decode_fns(model.init(jax.random.PRNGKey(0)), mesh, ccfg,
                           max_prompt_len=24, prefill_chunk=8)
    return ccfg, fns, 8


def _afmoe_fns(mesh):
    from apex_tpu.models.afmoe import FULL, SLIDING, AfmoeConfig, AfmoeModel
    from apex_tpu.serving.kv_cache import KVCacheConfig

    model = AfmoeModel(AfmoeConfig.from_hf(dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=3,
        num_dense_layers=1, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        num_experts_per_tok=2, num_shared_experts=1, route_scale=2.448,
        rms_norm_eps=1e-5, rope_theta=10000.0, mup_enabled=True,
        sliding_window=8, layer_types=[SLIDING, SLIDING, FULL]),
        num_experts=8, held_experts=(1, 4, 6), params_dtype=jnp.float32))
    ccfg = KVCacheConfig.of_classes(
        model.cache_classes(slots=2, pages_per_seq=8, page_size=4,
                            prefill_chunk=8),
        page_size=4, max_seqs=2, dtype=jnp.float32)
    fns = model.decode_fns(model.init(jax.random.PRNGKey(1)), mesh, ccfg,
                           max_prompt_len=24, prefill_chunk=8)
    return ccfg, fns, 8


def _xing4_fns(mesh):
    from apex_tpu.models.xing4 import Xing4Config, Xing4Model
    from apex_tpu.serving.kv_cache import KVCacheConfig

    cfg = Xing4Config.from_hf(dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        n_shared_experts=1, n_routed_experts=8, num_experts_per_tok=2,
        n_group=1, topk_group=1, routed_scaling_factor=2.0,
        rms_norm_eps=1e-6, rope_theta=10000.0, hc_mult=4,
        hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
        mhc_h_res_clamp_max=30,
        rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                          mscale_all_dim=1,
                          original_max_position_embeddings=16, type="yarn")),
        params_dtype=jnp.float32)
    model = Xing4Model(cfg)
    ccfg = KVCacheConfig(
        num_layers=3, num_heads=1, head_dim=cfg.latent_dim,
        num_pages=1 + 2 * 8, page_size=4, max_seqs=2, pages_per_seq=8,
        dtype=jnp.float32, kind="latent", latent_dim=cfg.latent_dim,
        index_dim=0)
    fns = model.decode_fns(model.init(jax.random.PRNGKey(2)), mesh, ccfg,
                           max_prompt_len=24, prefill_chunk=8)
    return ccfg, fns, 8


@pytest.fixture(scope="module")
def prefill_spans(tmp_path_factory):
    """model -> (its chunk function, the stats of the
    ``dispatch_prefill`` spans of one 20-token prompt served in chunks),
    all three inside one profiler session."""
    from jax.sharding import Mesh

    from apex_tpu.serving.kv_cache import PagedKVCache, init_pools
    from apex_tpu.serving.serve import ContinuousBatcher, Request
    from apex_tpu.transformer import parallel_state

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    tp_mesh = parallel_state.initialize_model_parallel(
        devices=jax.devices()[:1])
    one = Mesh(np.array(jax.devices()[:1]), ("tp",))
    built = {"gpt": _gpt_fns(tp_mesh), "afmoe": _afmoe_fns(one),
             "xing4": _xing4_fns(one)}
    directory = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        for name, (ccfg, fns, chunk) in built.items():
            batcher = ContinuousBatcher(
                fns.prefill, fns.decode, PagedKVCache(ccfg),
                init_pools(ccfg), max_prompt_len=24, chunk_fn=fns.chunk,
                prefill_chunk=chunk)
            batcher.run([Request(uid=name, prompt=list(range(1, 21)),
                                 max_new_tokens=2)])
    finally:
        jax.profiler.stop_trace()
        parallel_state.destroy_model_parallel()
    (path,) = glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb"))
    spans = {name: [] for name in built}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name == "tlm.serve.dispatch_prefill":
                    stats = dict(event.stats)
                    spans[stats["uid"]].append(stats)
    return {name: (built[name][1].chunk, sorted(
        spans[name], key=lambda s: s["chunk"])) for name in built}


@pytest.mark.parametrize("model", ["gpt", "afmoe", "xing4"])
def test_dispatch_prefill_says_what_the_positions_left(prefill_spans, model):
    chunk_fn, spans = prefill_spans[model]
    assert [s["chunk"] for s in spans] == [0, 1, 2]
    if model == "gpt":
        # GPTModel's chunk function offers no count: the span has none
        assert not hasattr(chunk_fn, "k_blocks")
        assert not any("k_blocks_run" in s or "k_blocks_extent" in s
                       for s in spans)
        return
    for s in spans:
        run, extent = chunk_fn.k_blocks(8 * s["chunk"])
        assert (s["k_blocks_run"], s["k_blocks_extent"]) == (run, extent)
        assert 0 < run <= extent
    # three layers, one key block each at this size; Trinity's two query
    # heads a K/V head are two q blocks (one a period of the rows)
    assert spans[0]["k_blocks_extent"] == (6 if model == "afmoe" else 3)


def test_the_chunk_functions_count_at_the_cells_sizes():
    """Trinity's own sums at its cell's chunk geometry, from
    shapes alone (``decode_fns`` needs no weights to count)."""
    fns = _trinity_shaped_fns()[2]
    assert fns.chunk.ctx_buckets == (1024, 2048, 4096, 8192, 12288)
    # start 0: four window layers and the full one, 6 q blocks x 1 key
    # block each, and every block holds a row's own key
    assert fns.chunk.k_blocks(0) == (30, 30)
    # start 4096 (bucket 8192): a window layer reads 5 key blocks and
    # runs them all, the full layer 5 of its bucket's 8
    run, extent = fns.chunk.k_blocks(4096)
    assert extent == 4 * 6 * 5 + 6 * 8
    assert run == 4 * 6 * 5 + 6 * 5
