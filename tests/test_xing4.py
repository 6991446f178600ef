"""Xing4.0 on the serving path, at a small size on the CPU, against the
benchmark's plain float32 reference (``benchmarks/reference/xing4.py``,
imported, nothing of the program in it): the whole forward, chunked
prefill and paged decode through a latent pool WITHOUT index keys, the
hyper-connection wrapper's properties, the paged latent walk against
the absorbed form over gathered rows, and the batcher's path."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import xing4 as ref  # noqa: E402

from apex_tpu.models.xing4 import (  # noqa: E402
    COUNTER_NAMES, Xing4Config, Xing4Model,
)
from apex_tpu.ops.attention_latent import mla_absorbed, mla_paged  # noqa: E402
from apex_tpu.ops.hyper_connections import (  # noqa: E402
    hc_mapping, hc_mix, hc_read,
)
from apex_tpu.serving.kv_cache import (  # noqa: E402
    KVCacheConfig, PagedKVCache, init_pools, write_latent_tokens,
)
from apex_tpu.serving.serve import ContinuousBatcher, Request  # noqa: E402

HF = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, n_shared_experts=1,
    n_routed_experts=8, num_experts_per_tok=2, n_group=1, topk_group=1,
    routed_scaling_factor=2.0, rms_norm_eps=1e-6, rope_theta=10000.0,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=16,
                      type="yarn"))
PAGE, CHUNK, PAGES_PER_SEQ, SLOTS = 4, 8, 8, 4
HC = dict(sinkhorn_iters=20, eps=1e-6, clamp=(-30.0, 30.0), rms_eps=1e-6)


@pytest.fixture(scope="module")
def built():
    cfg = Xing4Config.from_hf(HF, params_dtype=jnp.float32)
    model = Xing4Model(cfg)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                            NamedSharding(mesh, P()))
    ccfg = KVCacheConfig(
        num_layers=3, num_heads=1, head_dim=cfg.latent_dim,
        num_pages=1 + SLOTS * PAGES_PER_SEQ, page_size=PAGE, max_seqs=SLOTS,
        pages_per_seq=PAGES_PER_SEQ, dtype=jnp.float32, kind="latent",
        latent_dim=cfg.latent_dim, index_dim=0)
    fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=24,
                           prefill_chunk=CHUNK)
    fresh = lambda: jax.device_put(init_pools(ccfg),
                                   NamedSharding(mesh, P()))
    return model, params, ccfg, fns, fresh


@pytest.fixture(scope="module")
def decode_step(built):
    model, _, ccfg = built[:3]
    table = model.rope_table(ccfg.max_len)
    return jax.jit(lambda p, pools, tok, pos, act, pt: model.decode_step(
        p, pools, tok, pos, act, pt, page_size=PAGE, table=table))


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


# ------------------------------------------------------------ whole forward
@pytest.mark.parametrize("seed,length", [(1, 40), (2, 7), (3, 24)])
def test_forward_matches_reference(built, seed, length):
    model, params = built[:2]
    tokens = _tokens(seed, length)
    got = np.asarray(jax.jit(model.apply)(params, jnp.asarray(tokens)))
    want = np.asarray(ref.forward(
        params, tokens, ref.from_hf(HF), positions=range(length),
        q_block=length))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


def test_config_reads_the_published_keys_and_holds_every_expert():
    cfg = Xing4Config.from_hf(HF)
    assert cfg.held_experts == tuple(range(8))
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps) == (4, 20, 1e-6)
    assert (cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max) == (-30, 30)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) \
        == (0, 0, 0)
    assert cfg.rope_factor == 64 and cfg.params_dtype == jnp.bfloat16
    params = jax.eval_shape(Xing4Model(cfg).init, jax.random.PRNGKey(0))
    assert not [k for k in params["moe"]["attn"] if k.startswith("idx_")]
    assert params["moe"]["hc_ffn"]["phi"].shape == (2, 4, 24, 64)
    assert params["moe"]["hc_ffn"]["phi"].dtype == jnp.float32
    with pytest.raises(ValueError, match="hc_mult"):
        Xing4Model(Xing4Config.from_hf(dict(HF, hc_mult=0)))


# ---------------------------------------------- paged serving = whole forward
@pytest.mark.parametrize("length,steps", [
    (3, 2),     # inside the first page
    (5, 4),     # across a page boundary (page 4)
    (9, 8),     # across a chunk boundary (chunk 8)
    (17, 7),    # third chunk, the decode steps run to the slot's bound
])
def test_chunks_then_decode_steps_match_apply_at_every_position(
        built, decode_step, length, steps):
    """``length`` tokens through the chunk program into the latent pool,
    then ``steps`` paged decode steps, teacher-forced: the chunk's logits
    and every step's against ``apply`` on the whole sequence."""
    model, params, ccfg, fns, fresh = built
    tokens = _tokens(10 + length, length + steps)
    want = np.asarray(jax.jit(model.apply)(params, jnp.asarray(tokens)))
    cache = PagedKVCache(ccfg)
    cache.admit(0, length + steps)
    row = jnp.asarray(cache.page_table[0])
    pools = fresh()
    padded = np.zeros((-(-length // CHUNK) * CHUNK,), np.int32)
    padded[:length] = tokens[:length]
    for c0 in range(0, length, CHUNK):
        pools, _, chunk_logits = fns.chunk(
            pools, padded[c0:c0 + CHUNK], c0, length, 0, row,
            jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(chunk_logits), want[length - 1],
                               atol=2e-4, rtol=0)
    slot0 = np.arange(SLOTS) == 0
    for at in range(length, length + steps):
        logits, pools, stats, (idx, chosen) = decode_step(
            params, pools, jnp.asarray(np.where(slot0, tokens[at], 0)),
            jnp.asarray(np.where(slot0, at, 0)), jnp.asarray(slot0),
            jnp.asarray(cache.page_table))
        np.testing.assert_allclose(np.asarray(logits[0]), want[at],
                                   atol=2e-4, rtol=0)
    # the walk read every row of the context, in every layer; 2 choices
    # a token in each of the 2 expert layers, all on held experts
    assert idx.shape == chosen.shape == (3, SLOTS, 0)
    choices, held, touched, load, read, context = np.asarray(stats)
    assert (choices, held) == (4, 4) and touched == 4 and load == 2
    assert read == context == 3 * (length + steps)


def test_decode_counters_keep_the_latent_models_names(built):
    fns = built[3]
    assert COUNTER_NAMES[:2] == ("decode_steps", "decode_choices")
    assert fns.decode.carry_extras["counters"].shape == (len(COUNTER_NAMES),)
    assert fns.decode.carry_extras["last_selected"].shape == (3, SLOTS, 0)


def test_the_batcher_serves_what_apply_would_generate(built):
    """Three requests of ragged lengths through ``ContinuousBatcher``
    (chunked prefill, the decode program, slots reused): greedy tokens
    equal to a greedy loop over ``apply``."""
    model, params, ccfg, fns, fresh = built
    batcher = ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), fresh(),
        max_prompt_len=24, chunk_fn=fns.chunk, prefill_chunk=CHUNK)
    prompts = {i: _tokens(40 + i, n).tolist()
               for i, n in enumerate((5, 11, 18))}
    done = batcher.run([Request(uid=i, prompt=p, max_new_tokens=4)
                        for i, p in prompts.items()])
    apply = jax.jit(model.apply)
    for i, prompt in prompts.items():
        seq = list(prompt)
        for _ in range(4):
            seq.append(int(np.argmax(np.asarray(
                apply(params, jnp.asarray(seq, jnp.int32)))[-1])))
        assert list(done[i].tokens) == seq[len(prompt):]
    assert batcher.step_counters[0] > 0          # decode steps counted


# ------------------------------------------------------------- the wrapper
def _mapping_inputs(n, T, C, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (n, T, C)),
            (n * C) ** -0.5 * jax.random.normal(k[1], (n, n * (n + 2), C)),
            jnp.asarray([0.5, 0.4, 0.6]),
            jnp.concatenate([jax.random.normal(k[2], (2 * n,)),
                             (2 * jnp.eye(n)).reshape(-1)]))


@pytest.mark.parametrize("n,T,C", [(4, 24, 256), (4, 300, 128), (2, 5, 64)])
def test_mapping_kernel_matches_xla_and_the_reference(n, T, C):
    """The Mosaic kernel (interpreted here: tokens padded to whole
    lanes, several token tiles, several column steps) against the XLA
    form and against the reference's (seq, n, C) equations."""
    X, phi, alpha, bias = _mapping_inputs(n, T, C)
    xla = hc_mapping(X, phi, alpha, bias, **HC, implementation="xla")
    kernel = hc_mapping(X, phi, alpha, bias, **HC, implementation="pallas")
    for a, b in zip(xla, kernel):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)
    cfg = dict(rms_norm_eps=1e-6, hc_sinkhorn_iters=20, hc_eps=1e-6,
               mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
    with jax.default_matmul_precision("highest"):
        pre, post, res = ref.mapping(
            jnp.moveaxis(X, 0, 1),
            dict(phi=jnp.transpose(phi, (0, 2, 1)).reshape(n * C, -1),
                 alpha=alpha, bias=bias), cfg)
    np.testing.assert_allclose(np.asarray(xla[0]).T, pre, atol=2e-6)
    np.testing.assert_allclose(np.asarray(xla[1]).T, post, atol=2e-6)
    np.testing.assert_allclose(np.moveaxis(np.asarray(xla[2]), 2, 0), res,
                               atol=2e-6)


def test_h_res_is_doubly_stochastic_within_the_iterations_error():
    X, phi, alpha, bias = _mapping_inputs(4, 64, 128, seed=3)
    pre, post, res = hc_mapping(X, phi, alpha, bias, **HC)
    res = np.asarray(res)
    assert res.min() > 0
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=1e-5)   # rows
    np.testing.assert_allclose(res.sum(axis=0), 1.0, atol=1e-3)   # columns
    assert 0 < np.asarray(pre).min() and np.asarray(pre).max() < 1
    assert 0 < np.asarray(post).min() and np.asarray(post).max() < 2
    # neither uniform nor the identity, and a function of the token
    assert 0.3 < res[0, 0].mean() < 0.95 and res[0, 0].std() > 0.01
    # after one iteration the columns are visibly off: the 20 do work
    once = np.asarray(hc_mapping(X, phi, alpha, bias,
                                 **dict(HC, sinkhorn_iters=1))[2])
    assert np.abs(once.sum(axis=0) - 1).max() > 10 * np.abs(
        res.sum(axis=0) - 1).max()


def test_read_and_mix_are_the_matrix_products():
    X, phi, alpha, bias = _mapping_inputs(4, 10, 32, seed=5)
    pre, post, res = hc_mapping(X, phi, alpha, bias, **HC)
    y = jax.random.normal(jax.random.PRNGKey(9), (10, 32))
    np.testing.assert_allclose(
        hc_read(X, pre), jnp.einsum("nt,ntc->tc", pre, X), atol=1e-5)
    np.testing.assert_allclose(
        hc_mix(X, res, post, y),
        jnp.einsum("ijt,jtc->itc", res, X) + post[:, :, None] * y[None],
        atol=1e-5)
    # a doubly stochastic mix keeps the sum of the streams
    np.testing.assert_allclose(
        jnp.sum(hc_mix(X, res, post, 0 * y), 0), jnp.sum(X, 0), atol=1e-3)


def test_one_stream_read_and_written_whole_is_the_plain_block():
    """``hc_mult`` 1 with the mapping forced to ``H_pre`` = ``H_post`` =
    1 (``H_res`` of one stream is 1 by itself): the block is the plain
    pre-norm block ``x += Attn(rms(x)); x += FFN(rms(x))``, built here
    from the reference's pieces."""
    hf = dict(HF, hc_mult=1)
    model = Xing4Model(Xing4Config.from_hf(hf, params_dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(1))
    forced = lambda hc: dict(
        hc, alpha=jnp.zeros_like(hc["alpha"]),
        bias=jnp.broadcast_to(jnp.asarray([30.0, 0.0, 0.0]),
                              hc["bias"].shape))
    for stack in ("dense", "moe"):
        for which in ("hc_attn", "hc_ffn"):
            params[stack][which] = forced(params[stack][which])
    tokens = _tokens(7, 20)
    got = np.asarray(jax.jit(model.apply)(params, jnp.asarray(tokens)))

    cfg, eps = ref.from_hf(hf), hf["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = ref._f32(params["embedding"]["weight"][jnp.asarray(tokens)])
        for i in range(3):
            w = ref.LayerWeights(params, i, 1)
            x = x + ref.attention(
                ref._rms(x, w["norm1"], eps),
                w.only(*ref.ATTENTION_WEIGHTS), cfg, 20)
            h = ref._rms(x, w["norm2"], eps)
            x = x + (ref.moe(h, w, cfg, tuple(range(8))) if "router_w" in w
                     else ref._swiglu(h, w["mlp_gate"], w["mlp_up"],
                                      w["mlp_down"]))
        want = ref._rms(x, params["final_norm"]["weight"], eps) \
            @ params["head"]["weight"]
    np.testing.assert_allclose(got, np.asarray(want), atol=5e-4, rtol=0)


# ----------------------------------------------------------------- the walk
@pytest.mark.parametrize("lengths", [
    (37, 0, 48),        # ragged, a slot idle, a slot at its bound
    (1, 8, 9),          # one row; a whole page; one row into the next
    (48, 48, 48),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_walk_matches_absorbed_over_gathered_rows(lengths, dtype):
    """The Mosaic walk (interpreted) over the stacked pool, through a
    shuffled page table, against ``mla_absorbed`` over the rows gathered
    by hand; idle slots come out zero."""
    B, H, dn, dr, dc, dv = 3, 4, 16, 8, 32, 16
    W, ps, L, width = 128, 8, 2, 6
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    pool = jax.random.normal(k[0], (L, 1 + B * width, ps, W), dtype)
    pool = pool.at[..., dc + dr:].set(0)
    qn = jax.random.normal(k[1], (B, H, dn), dtype)
    qr = jax.random.normal(k[2], (B, H, dr), dtype)
    w_uk = (dn ** -0.5 * jax.random.normal(k[3], (dc, H, dn))).astype(dtype)
    w_uv = (dc ** -0.5 * jax.random.normal(k[4], (dc, H, dv))).astype(dtype)
    table = np.random.default_rng(0).permutation(
        np.arange(1, 1 + B * width)).reshape(B, width).astype(np.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    table[np.asarray(lengths) == 0] = 0       # an idle slot: the null page
    table = jnp.asarray(table)
    for layer in range(L):
        rows = pool[layer][table].reshape(B, width * ps, W)
        seen = jnp.arange(width * ps)[None] < lengths[:, None]
        want = np.asarray(mla_absorbed(qn, qr, rows, seen, w_uk, w_uv, 0.2),
                          np.float32)
        walk = jax.jit(lambda l: mla_paged(
            qn, qr, pool, l, table, lengths, w_uk, w_uv, 0.2,
            implementation="pallas"))(jnp.int32(layer))
        xla = mla_paged(qn, qr, pool, layer, table, lengths, w_uk, w_uv, 0.2,
                        implementation="xla")
        live = np.asarray(lengths) > 0
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(
            np.asarray(walk, np.float32)[live], want[live], atol=tol)
        np.testing.assert_allclose(
            np.asarray(xla, np.float32)[live], want[live], atol=tol)
        assert not np.asarray(walk, np.float32)[~live].any()


# ------------------------------------------------- the pool without index keys
def test_latent_pool_without_index_keys():
    ccfg = KVCacheConfig(
        num_layers=2, num_heads=1, head_dim=24, num_pages=5, page_size=4,
        max_seqs=2, pages_per_seq=2, dtype=jnp.float32, kind="latent",
        latent_dim=24, index_dim=0)
    pools = init_pools(ccfg)
    assert set(pools) == {"ckv"}
    assert pools["ckv"].shape == (2, 5, 4, 128)       # whole lanes a row
    rows = jnp.arange(3 * 24, dtype=jnp.float32).reshape(3, 24)
    out = write_latent_tokens(pools, 1, rows, None, jnp.asarray([1, 1, 3]),
                              jnp.asarray([0, 2, 1]))
    assert set(out) == {"ckv"}
    np.testing.assert_array_equal(out["ckv"][1, 1, 2, :24], rows[1])
    assert not np.asarray(out["ckv"][0]).any()
    assert not np.asarray(out["ckv"][1, 3, 1, 24:]).any()
    # the allocator, the tables and the pages' family are the same
    cache = PagedKVCache(ccfg)
    cache.admit(0, 7)
    assert cache.compat_key()[-3:] == ("latent", 24, 0)
    # with index keys the pool is what it was
    with_keys = init_pools(KVCacheConfig(
        num_layers=2, num_heads=1, head_dim=24, num_pages=5, page_size=4,
        max_seqs=2, pages_per_seq=2, dtype=jnp.float32, kind="latent",
        latent_dim=24, index_dim=16))
    assert set(with_keys) == {"ckv", "kidx"}
    assert with_keys["kidx"].shape == (2, 5, 4, 16)
    with pytest.raises(ValueError, match="no index keys"):
        KVCacheConfig(num_layers=2, num_heads=1, head_dim=24, num_pages=5,
                      kind="latent", latent_dim=24, index_dim=-1)


def test_the_model_refuses_a_pool_with_index_keys(built):
    model, params, ccfg = built[:3]
    import dataclasses
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    with pytest.raises(ValueError, match="does not match"):
        model.decode_fns(params, mesh,
                         dataclasses.replace(ccfg, index_dim=16),
                         max_prompt_len=24, prefill_chunk=CHUNK)
