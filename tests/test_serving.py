"""Serving stack: allocator invariants, cache writes, fused sampling,
and continuous batching end-to-end on the tiny GPT.

The load-bearing claims, each pinned here:

- the page allocator never double-books, reuses freed pages, and
  reserves page 0 (unallocated table entries must stay addressable);
  shared pages survive until their LAST holder frees them (refcounts);
- cache writes round-trip (fp exactly, int8 within the block-scale
  band) and idle writes land on the null page; a copy-on-write tail
  page is bitwise-isolated from its source;
- greedy sampling is BIT-identical to argmax (the dryrun's
  generation-parity gate rests on this);
- the continuous-batching driver sustains admit/retire across >= 3
  request generations with ragged (EOS) finishes, produces
  per-request output identical to the single-request reference, and
  NEVER recompiles the decode step (compile-counting spy);
- chunked prefill is token-identical to the monolithic path under
  slot churn, a prefix-cache hit's logits are BIT-identical to a cold
  admission, chunk counts / hit patterns add zero jit entries, and a
  seeded request's sampled stream is reproducible regardless of
  admission order or slot assignment;
- cancel(uid) frees the slot without recording a Completion (the uid
  re-serves from scratch) and the load gauges the fleet router scores
  by reach the metrics jsonl.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving.kv_cache import (
    CacheOutOfPages,
    KVCacheConfig,
    PageAllocator,
    PagedKVCache,
    copy_pages,
    init_pools,
    write_targets,
    write_tokens,
)
from apex_tpu.serving.sampling import greedy, sample


class TestPageAllocator:
    def test_page_zero_reserved(self):
        a = PageAllocator(8)
        assert a.num_free == 7
        got = a.alloc(7)
        assert 0 not in got
        assert sorted(got) == list(range(1, 8))

    def test_alloc_is_all_or_nothing(self):
        a = PageAllocator(8)
        a.alloc(5)
        before = a.num_free
        with pytest.raises(CacheOutOfPages):
            a.alloc(3)
        assert a.num_free == before        # failed alloc leaked nothing

    def test_reuse_after_free(self):
        a = PageAllocator(4)
        p1 = a.alloc(3)
        a.free(p1)
        p2 = a.alloc(3)
        assert sorted(p1) == sorted(p2)    # the pool is fully reusable

    def test_lifo_reuse(self):
        a = PageAllocator(16)
        pages = a.alloc(4)
        a.free(pages)
        assert a.alloc(1) == [pages[-1]]   # hottest page comes back first

    def test_double_free_rejected(self):
        a = PageAllocator(4)
        p = a.alloc(1)
        a.free(p)
        with pytest.raises(ValueError, match="not allocated"):
            a.free(p)
        with pytest.raises(ValueError, match="null page"):
            a.free([0])

    def test_share_keeps_page_allocated_until_last_free(self):
        a = PageAllocator(4)
        p = a.alloc(1)
        a.share(p)                          # rc 2
        a.free(p)                           # rc 1: still allocated
        assert a.refcount(p[0]) == 1
        assert a.num_free == 2              # not back on the free list
        a.free(p)                           # rc 0: now free
        assert a.refcount(p[0]) == 0
        assert a.num_free == 3

    def test_double_share_needs_double_free(self):
        a = PageAllocator(4)
        p = a.alloc(1)
        a.share(p)
        a.share(p)                          # rc 3
        for want in (2, 1):
            a.free(p)
            assert a.refcount(p[0]) == want
        a.free(p)
        with pytest.raises(ValueError, match="not allocated"):
            a.free(p)                       # the classic double free

    def test_share_unallocated_or_freed_rejected(self):
        a = PageAllocator(4)
        with pytest.raises(ValueError, match="cannot share"):
            a.share([1])
        p = a.alloc(1)
        a.free(p)
        with pytest.raises(ValueError, match="cannot share"):
            a.share(p)

    def test_free_while_shared_preserves_other_holder(self):
        """Slot A retires while slot B still reads the shared page: the
        page must stay allocated and B's later free releases it."""
        a = PageAllocator(8)
        shared = a.alloc(2)
        a.share(shared)                     # B's reference
        a.free(shared)                      # A retires
        assert all(a.refcount(p) == 1 for p in shared)
        got = a.alloc(5)                    # the pool can't hand them out
        assert not (set(got) & set(shared))
        a.free(shared)                      # B retires
        assert a.num_free == 2

    def test_fragmentation_interleave_conserves_pool(self):
        """Interleaved alloc/free of ragged sizes: the free count is
        always pool-1 minus live pages and nothing is ever lost —
        paging has no external fragmentation by construction."""
        a = PageAllocator(32)
        live = []
        rng = np.random.RandomState(0)
        for step in range(50):
            if live and (rng.rand() < 0.5 or a.num_free < 5):
                a.free(live.pop(rng.randint(len(live))))
            else:
                live.append(a.alloc(int(rng.randint(1, 5))))
            n_live = sum(len(p) for p in live)
            assert a.num_free == 31 - n_live, step
        for p in live:
            a.free(p)
        assert a.num_free == 31


class TestPagedKVCache:
    def cfg(self, **kw):
        base = dict(num_layers=1, num_heads=2, head_dim=8,
                    num_pages=16, page_size=4, max_seqs=3,
                    pages_per_seq=4, dtype=jnp.float32)
        base.update(kw)
        return KVCacheConfig(**base)

    def test_admit_allocates_exactly_and_retire_returns(self):
        c = PagedKVCache(self.cfg())
        c.admit(0, 9)                       # ceil(9/4) = 3 pages
        assert c.allocator.num_free == 15 - 3
        row = c.page_table[0]
        assert (row[:3] > 0).all() and (row[3:] == 0).all()
        c.retire(0)
        assert c.allocator.num_free == 15
        assert (c.page_table[0] == 0).all()

    def test_double_admit_and_overlength_rejected(self):
        c = PagedKVCache(self.cfg())
        c.admit(1, 4)
        with pytest.raises(ValueError, match="already admitted"):
            c.admit(1, 4)
        with pytest.raises(ValueError, match="exceeds the slot bound"):
            c.admit(2, 17)                  # > 4*4

    def test_backpressure_has_no_side_effects(self):
        c = PagedKVCache(self.cfg(num_pages=6))
        c.admit(0, 16)                      # 4 of 5 free pages
        before = (c.allocator.num_free, c.page_table.copy())
        with pytest.raises(CacheOutOfPages):
            c.admit(1, 9)
        assert c.allocator.num_free == before[0]
        assert (c.page_table == before[1]).all()

    def test_config_validation(self):
        with pytest.raises(ValueError, match="null page"):
            self.cfg(num_pages=1)
        with pytest.raises(ValueError, match="int8"):
            self.cfg(kv_dtype=jnp.float16)
        assert self.cfg(kv_dtype=jnp.int8).quantized


class TestPrefixIndex:
    def cfg(self, **kw):
        base = dict(num_layers=1, num_heads=2, head_dim=8,
                    num_pages=32, page_size=4, max_seqs=4,
                    pages_per_seq=6, dtype=jnp.float32)
        base.update(kw)
        return KVCacheConfig(**base)

    def test_cold_admission_matches_nothing(self):
        c = PagedKVCache(self.cfg())
        res = c.admit(0, 12, prompt_tokens=[1, 2, 3, 4, 5, 6, 7, 8])
        assert res.matched_tokens == 0 and res.shared_pages == 0
        assert res.copied_page is None

    def test_register_then_hit_shares_full_pages(self):
        c = PagedKVCache(self.cfg())
        prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]     # 2 full pages + 2
        c.admit(0, 14, prompt_tokens=prompt)
        assert c.register_prefix(0, prompt) == 2
        pages0 = list(c.page_table[0][:2])
        res = c.admit(1, 14, prompt_tokens=prompt)
        assert res.matched_tokens == 8 and res.shared_pages == 2
        assert res.copied_page is None
        assert list(c.page_table[1][:2]) == pages0    # same phys pages
        # shared pages survive BOTH retirements (the index holds them)
        c.retire(0)
        c.retire(1)
        assert all(c.allocator.refcount(p) == 1 for p in pages0)
        # ... and a later admission still hits
        res = c.admit(2, 14, prompt_tokens=prompt)
        assert res.matched_tokens == 8

    def test_last_token_never_matched_cow_instead(self):
        """A whole-prompt full-page match caps at plen - 1: the last
        page is COPIED (its final token must be recomputed for
        logits), the rest shared."""
        c = PagedKVCache(self.cfg())
        prompt = [5, 6, 7, 8, 1, 2, 3, 4]            # exactly 2 pages
        c.admit(0, 12, prompt_tokens=prompt)
        c.register_prefix(0, prompt)
        res = c.admit(1, 12, prompt_tokens=prompt)
        assert res.matched_tokens == 7               # plen - 1
        assert res.shared_pages == 1
        src, dst = res.copied_page
        assert src == c.page_table[0][1]
        assert dst == c.page_table[1][1]
        assert src != dst

    def test_prefix_of_registered_prompt_hits(self):
        c = PagedKVCache(self.cfg())
        long = list(range(1, 17))                    # 4 full pages
        c.admit(0, 20, prompt_tokens=long)
        c.register_prefix(0, long)
        res = c.admit(1, 14, prompt_tokens=long[:10])
        assert res.matched_tokens == 8 and res.shared_pages == 2

    def test_divergent_prompt_stops_at_divergence(self):
        c = PagedKVCache(self.cfg())
        a = [1, 2, 3, 4, 5, 6, 7, 8]
        c.admit(0, 12, prompt_tokens=a)
        c.register_prefix(0, a)
        b = [1, 2, 3, 4, 9, 9, 9, 9, 1, 1]           # page 1 differs
        res = c.admit(1, 14, prompt_tokens=b)
        assert res.matched_tokens == 4 and res.shared_pages == 1

    def test_eviction_is_refcount_gc(self):
        """When an admission runs short, index-only pages are evicted
        leaf-first; pages a live slot still shares are untouchable."""
        c = PagedKVCache(self.cfg(num_pages=8, pages_per_seq=7))  # 7 free
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        c.admit(0, 8, prompt_tokens=prompt)           # 2 pages
        c.register_prefix(0, prompt)
        c.retire(0)                                   # index-held only
        assert c.prefix_index_size == 2
        assert c.allocator.num_free == 5
        # needs 7 pages -> evicts both cached pages
        c.admit(1, 25)
        assert c.prefix_index_size == 0
        c.retire(1)
        # now pin the pages with a LIVE sharer: eviction cannot free
        c.admit(0, 8, prompt_tokens=prompt)
        c.register_prefix(0, prompt)
        with pytest.raises(CacheOutOfPages):
            c.admit(1, 25)                            # 2 live + 2... short
        assert c.prefix_index_size == 2               # nothing evicted

    def test_failed_hit_admission_unshares(self):
        c = PagedKVCache(self.cfg(num_pages=6, pages_per_seq=6))
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        c.admit(0, 8, prompt_tokens=prompt)
        c.register_prefix(0, prompt)
        rc_before = [c.allocator.refcount(p) for p in c.page_table[0][:2]]
        with pytest.raises(CacheOutOfPages):
            # matches 2 pages but the 4 fresh pages don't fit (3 free)
            c.admit(1, 24, prompt_tokens=prompt + [9, 9])
        assert [c.allocator.refcount(p)
                for p in c.page_table[0][:2]] == rc_before

    def test_cow_source_protected_from_eviction_and_reuse(self):
        """The CoW source is referenced by the admitting slot until it
        retires: eviction pressure can neither free it (backpressure
        instead) nor re-issue it as one of the same admission's fresh
        pages (which would alias the pending device copy)."""
        # success case: enough room — the source must not alias fresh
        c = PagedKVCache(self.cfg(num_pages=5, pages_per_seq=3))
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        c.admit(0, 8, prompt_tokens=prompt)
        c.register_prefix(0, prompt)
        c.retire(0)
        res = c.admit(1, 12, prompt_tokens=prompt)
        src, dst = res.copied_page
        assert src not in list(c.page_table[1])
        assert c.allocator.refcount(src) == 2    # index + slot's ref
        c.retire(1)
        assert c.allocator.refcount(src) == 1    # index only again
        # pressure case: the only evictable candidate IS the source —
        # the admission must backpressure, not corrupt
        c2 = PagedKVCache(self.cfg(num_pages=4, pages_per_seq=3))
        c2.admit(0, 8, prompt_tokens=prompt)
        c2.register_prefix(0, prompt)
        c2.retire(0)
        rc_before = {p: c2.allocator.refcount(p)
                     for e in c2._prefix.values() for p in [e["page"]]}
        with pytest.raises(CacheOutOfPages):
            c2.admit(1, 12, prompt_tokens=prompt)
        assert c2.prefix_index_size == 2         # nothing evicted
        for p, rc in rc_before.items():
            assert c2.allocator.refcount(p) == rc

    def test_cow_tail_isolation_bitwise(self):
        """Writes into the CoW destination page never leak into the
        shared source page (and the copy itself is bit-exact)."""
        cfg = self.cfg()
        pools = init_pools(cfg)
        rng = jax.random.PRNGKey(3)
        k_new = jax.random.normal(rng, (4, 2, 8))
        layer0 = jax.tree.map(lambda x: x[0], pools)
        layer0 = write_tokens(
            layer0, k_new, k_new, jnp.full((4,), 5, jnp.int32),
            jnp.arange(4, dtype=jnp.int32))
        pools = jax.tree.map(lambda full, l0: full.at[0].set(l0),
                             pools, layer0)
        copied = jax.jit(copy_pages)(
            pools, jnp.asarray([5], jnp.int32),
            jnp.asarray([7], jnp.int32))
        np.testing.assert_array_equal(
            np.asarray(copied["k"][0, 7]), np.asarray(pools["k"][0, 5]))
        # overwrite one token in the copy; the source must not move
        src_before = np.asarray(copied["k"][0, 5]).copy()
        l0 = jax.tree.map(lambda x: x[0], copied)
        l0 = write_tokens(
            l0, k_new[:1] * 100.0, k_new[:1] * 100.0,
            jnp.asarray([7], jnp.int32), jnp.asarray([3], jnp.int32))
        np.testing.assert_array_equal(np.asarray(l0["k"][5]),
                                      src_before)
        assert not np.array_equal(np.asarray(l0["k"][7]),
                                  np.asarray(copied["k"][0, 7]))


class TestWrites:
    def test_fp_write_round_trip(self):
        cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=8,
                            num_pages=8, page_size=4, max_seqs=1,
                            pages_per_seq=3, dtype=jnp.float32)
        pools = jax.tree.map(lambda x: x[0], init_pools(cfg))  # layer 0
        row = jnp.array([5, 2, 7], jnp.int32)
        n = 10                                     # partial last page
        k_new = jax.random.normal(jax.random.PRNGKey(0), (n, 2, 8))
        v_new = jax.random.normal(jax.random.PRNGKey(1), (n, 2, 8))
        pos = jnp.arange(n, dtype=jnp.int32)
        wp, wo = write_targets(row, pos, pos < n, cfg.page_size)
        pools = write_tokens(pools, k_new, v_new, wp, wo)
        # read back through the page table
        got = jnp.moveaxis(pools["k"][row], 2, 1).reshape(-1, 2, 8)[:n]
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(k_new))

    def test_int8_write_round_trip_band(self):
        from apex_tpu.ops.quantization import dequantize_rows

        cfg = KVCacheConfig(num_layers=1, num_heads=2, head_dim=16,
                            num_pages=8, page_size=4, max_seqs=1,
                            pages_per_seq=2, dtype=jnp.float32,
                            kv_dtype=jnp.int8, kv_block=8)
        pools = jax.tree.map(lambda x: x[0], init_pools(cfg))
        row = jnp.array([3, 1], jnp.int32)
        n = 6
        k_new = jax.random.normal(jax.random.PRNGKey(2), (n, 2, 16))
        pos = jnp.arange(n, dtype=jnp.int32)
        wp, wo = write_targets(row, pos, pos < n, cfg.page_size)
        pools = write_tokens(pools, k_new, k_new, wp, wo,
                             quantized=True, kv_block=8)
        vals = jnp.moveaxis(pools["k"][row], 2, 1).reshape(-1, 2, 16)[:n]
        scales = jnp.moveaxis(
            pools["k_scales"][row], 2, 1).reshape(-1, 2, 2)[:n]
        deq = dequantize_rows(vals.reshape(n * 2, 16).astype(jnp.float32),
                              scales.reshape(n * 2, 2), 8)
        err = np.max(np.abs(np.asarray(deq).reshape(n, 2, 16)
                            - np.asarray(k_new)))
        # per-block amax/127 rounding bound for unit-normal data
        assert err < 4.0 / 127.0, err

    def test_invalid_positions_hit_null_page(self):
        row = jnp.array([5, 6], jnp.int32)
        pos = jnp.arange(8, dtype=jnp.int32)
        wp, wo = write_targets(row, pos, pos < 3, page_size=4)
        assert (np.asarray(wp)[3:] == 0).all()
        assert (np.asarray(wo)[3:] == 0).all()
        assert (np.asarray(wp)[:3] == 5).all()


class TestSampling:
    def test_greedy_is_argmax_bitwise(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (7, 33))
        np.testing.assert_array_equal(
            np.asarray(greedy(logits)),
            np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32)))
        # temperature=0 routes THROUGH greedy: same bits, key ignored
        np.testing.assert_array_equal(
            np.asarray(sample(logits, None, temperature=0.0)),
            np.asarray(greedy(logits)))

    def test_temperature_needs_key(self):
        with pytest.raises(ValueError, match="PRNG key"):
            sample(jnp.zeros((1, 4)), None, temperature=1.0)

    def test_top_k_restricts_support(self):
        logits = jnp.array([[3.0, 2.9, 2.8, -1.0, -2.0, -3.0]])
        top3 = {0, 1, 2}
        for i in range(40):
            t = int(sample(logits, jax.random.PRNGKey(i),
                           temperature=1.0, top_k=3)[0])
            assert t in top3, (i, t)

    def test_top_p_keeps_nucleus_only(self):
        # one token holds ~0.95 mass: any top_p <= 0.9 is greedy
        logits = jnp.array([[8.0, 2.0, 1.0, 0.0]])
        for i in range(20):
            t = int(sample(logits, jax.random.PRNGKey(i),
                           temperature=1.0, top_p=0.9)[0])
            assert t == 0, (i, t)
        # top_p=1.0 leaves the support alone — other tokens reachable
        seen = {int(sample(logits * 0.0, jax.random.PRNGKey(i),
                           temperature=1.0, top_p=1.0)[0])
                for i in range(60)}
        assert len(seen) > 1

    def test_validation(self):
        with pytest.raises(ValueError, match="temperature"):
            sample(jnp.zeros((1, 4)), temperature=-1.0)
        with pytest.raises(ValueError, match="top_k"):
            sample(jnp.zeros((1, 4)), jax.random.PRNGKey(0),
                   temperature=1.0, top_k=0)
        with pytest.raises(ValueError, match="top_p"):
            sample(jnp.zeros((1, 4)), jax.random.PRNGKey(0),
                   temperature=1.0, top_p=0.0)


# ---------------------------------------------------------------------------
# Continuous batching end-to-end (tiny GPT)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gpt_setup():
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.transformer import parallel_state

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        devices=jax.devices()[:1])
    yield from _gpt_setup_body(mesh)
    # leave global parallel state the way later test modules expect it
    parallel_state.destroy_model_parallel()


def _gpt_setup_body(mesh):
    from apex_tpu.models import GPTConfig, GPTModel
    model = GPTModel(GPTConfig(
        vocab_size=64, num_layers=2, hidden_size=32,
        num_attention_heads=4, max_position_embeddings=64,
        compute_dtype=jnp.float32, remat=False, attention_impl="xla",
    ))
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    prompts = rng.randint(1, 64, (6, 10)).astype(np.int32)
    plens = np.array([10, 8, 6, 4, 9, 5], np.int32)
    for i in range(6):
        prompts[i, plens[i]:] = 0
    new = 12
    ref = model.generate_reference(params, prompts, plens, new,
                                   mesh=mesh)
    yield mesh, model, params, prompts, plens, new, ref


from apex_tpu.serving.serve import ContinuousBatcher, Request  # noqa: E402


def _serve(gpt_setup, n_req, max_seqs, harvest_every, eos_id=None,
           logger=None, kv_dtype=None):
    mesh, model, params, prompts, plens, new, ref = gpt_setup
    page = 4
    pps = -(-(10 + new) // page)
    ccfg = KVCacheConfig(
        num_layers=2, num_heads=4, head_dim=8,
        num_pages=1 + max_seqs * pps, page_size=page,
        max_seqs=max_seqs, pages_per_seq=pps, dtype=jnp.float32,
        kv_dtype=kv_dtype, kv_block=8)
    fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=10,
                           eos_id=eos_id)
    batcher = ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), init_pools(ccfg),
        max_prompt_len=10, harvest_every=harvest_every, eos_id=eos_id,
        logger=logger)
    reqs = [
        Request(uid=i,
                prompt=[int(t) for t in prompts[i, : plens[i]]],
                max_new_tokens=new)
        for i in range(n_req)
    ]
    return batcher, fns, batcher.run(reqs)


def test_decode_fns_builds_from_the_same_specs_at_every_tp(gpt_setup):
    """One layout for every tp: the single-chip (1,1,1,1) mesh and a
    tp=2 mesh build their steps from the SAME partition specs — pools
    head-sharded on "tp", logits gathered — so there is no tp=1 branch
    to rot, and a quantized pool shards the same way too."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer import parallel_state

    mesh1, model, params = gpt_setup[:3]
    assert dict(mesh1.shape) == {"dp": 1, "pp": 1, "cp": 1, "tp": 1}
    ccfg = KVCacheConfig(
        num_layers=2, num_heads=4, head_dim=8, num_pages=9, page_size=4,
        max_seqs=2, pages_per_seq=4, dtype=jnp.float32)

    def specs_on(mesh, **kw):
        fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=10,
                               **kw)
        return fns.param_specs, fns.pool_specs

    one = specs_on(mesh1)
    one_q = specs_on(mesh1, weight_dtype="int8", weight_block=8)
    parallel_state.destroy_model_parallel()
    try:
        mesh2 = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=2, devices=jax.devices()[:2])
        two = specs_on(mesh2)
        two_q = specs_on(mesh2, weight_dtype="int8", weight_block=8)
    finally:
        # hand the module-scoped fixture its single-device mesh back
        parallel_state.destroy_model_parallel()
        parallel_state.initialize_model_parallel(
            devices=jax.devices()[:1])
    assert one == two and one_q == two_q
    pool_spec = P(None, None, "tp", None, None)
    assert all(s == pool_spec for s in one[1].values())
    assert one_q[0]["layers"]["qkv"]["q8"] == P(None, None, "tp")
    assert one_q[0]["layers"]["fc2"]["q8"] == P(None, "tp", None)


class TestContinuousBatching:
    def test_three_generations_ragged_finishes_no_recompile(
            self, gpt_setup):
        """6 requests through 2 slots = 3 admit/retire generations; an
        eos_id chosen to finish some requests mid-window makes the
        finish steps ragged; every completion must match the
        single-request reference and the decode step must not
        recompile after the first generation."""
        mesh, model, params, prompts, plens, new, ref = gpt_setup
        # pick an eos that actually appears mid-generation for SOME
        # requests (and not at all for others) — ragged by construction
        flat = [t for i in range(6) for t in map(int, ref[i])]
        eos = max(set(flat), key=flat.count)
        batcher, fns, comps = _serve(
            gpt_setup, n_req=6, max_seqs=2, harvest_every=3,
            eos_id=eos)
        assert len(comps) == 6
        reasons = {c.reason for c in comps.values()}
        finishes = {len(c.tokens) for c in comps.values()}
        assert "eos" in reasons                      # some finished early
        assert len(finishes) > 1                     # ... raggedly
        for i in range(6):
            want = list(map(int, ref[i]))
            if eos in want:
                want = want[: want.index(eos) + 1]
                assert comps[i].reason == "eos"
            else:
                assert comps[i].reason == "budget"
            assert comps[i].tokens == want, i
        # compile-count spy: generations 2 and 3 added ZERO entries
        # beyond generation 1's (the one-time uncommitted-vs-resident
        # pair); run a FOURTH generation to be sure
        from apex_tpu.serving.serve import Request

        size = fns.decode_jit._cache_size()
        assert size <= 2, size
        again = batcher.run([
            Request(uid="again", prompt=[1, 2, 3], max_new_tokens=4)
        ])
        assert len(again["again"].tokens) <= 4
        assert fns.decode_jit._cache_size() == size
        assert fns.prefill_jit._cache_size() <= 2

    def test_matches_reference_exactly_all_budget(self, gpt_setup):
        mesh, model, params, prompts, plens, new, ref = gpt_setup
        _, _, comps = _serve(gpt_setup, n_req=4, max_seqs=4,
                             harvest_every=5)
        for i in range(4):
            assert comps[i].tokens == list(map(int, ref[i])), i

    def test_int8_kv_generates_full_budget(self, gpt_setup):
        _, _, comps = _serve(gpt_setup, n_req=2, max_seqs=2,
                             harvest_every=4, kv_dtype=jnp.int8)
        for i in range(2):
            assert len(comps[i].tokens) == 12
            assert comps[i].reason == "budget"

    def test_backpressure_serializes_then_completes(self, gpt_setup):
        """A pool with room for ONE sequence still serves 3 requests —
        admissions wait for pages instead of failing."""
        from apex_tpu.serving.serve import ContinuousBatcher, Request

        mesh, model, params, prompts, plens, new, ref = gpt_setup
        page = 4
        pps = -(-(10 + new) // page)
        ccfg = KVCacheConfig(
            num_layers=2, num_heads=4, head_dim=8,
            num_pages=1 + pps, page_size=page, max_seqs=2,
            pages_per_seq=pps, dtype=jnp.float32)
        fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=10)
        batcher = ContinuousBatcher(
            fns.prefill, fns.decode, PagedKVCache(ccfg),
            init_pools(ccfg), max_prompt_len=10, harvest_every=4)
        comps = batcher.run([
            Request(uid=i, prompt=[int(t) for t in
                                   prompts[i, : plens[i]]],
                    max_new_tokens=new)
            for i in range(3)
        ])
        for i in range(3):
            assert comps[i].tokens == list(map(int, ref[i])), i

    def test_impossible_request_raises_not_hangs(self, gpt_setup):
        from apex_tpu.serving.serve import ContinuousBatcher, Request

        mesh, model, params, prompts, plens, new, ref = gpt_setup
        ccfg = KVCacheConfig(
            num_layers=2, num_heads=4, head_dim=8,
            num_pages=2, page_size=4, max_seqs=1,
            pages_per_seq=6, dtype=jnp.float32)
        fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=10)
        batcher = ContinuousBatcher(
            fns.prefill, fns.decode, PagedKVCache(ccfg),
            init_pools(ccfg), max_prompt_len=10)
        with pytest.raises(CacheOutOfPages, match="no slot"):
            batcher.run([Request(uid=0, prompt=[1, 2, 3, 4, 5],
                                 max_new_tokens=8)])

    def test_serving_telemetry_reaches_metrics_report(
            self, gpt_setup, tmp_path):
        from apex_tpu.telemetry.metrics import MetricsLogger

        jsonl = str(tmp_path / "serve.jsonl")
        logger = MetricsLogger(jsonl_path=jsonl, console=False)
        _, _, comps = _serve(gpt_setup, n_req=3, max_seqs=2,
                             harvest_every=4, logger=logger)
        logger.close()

        import tools.metrics_report as mr

        records = mr.load_records(jsonl)
        summary = mr.summarize(records)
        sv = summary["serving"]
        assert sv["requests"]["completed"] == 3
        assert sv["requests"]["by_reason"] == {"budget": 3}
        assert sv["prefill_spans"] == 3
        assert sv["decode_windows"], sv
        assert "decode_tokens_per_sec" in sv
        assert "inter_token_latency_ms" in sv
        assert set(sv["inter_token_latency_ms"]) >= {"p50", "p90",
                                                     "p99"}
        assert "ttft_s" in sv and sv["ttft_s"]["p50"] >= 0
        # the formatted report renders the section without crashing
        text = mr.format_report(summary)
        assert "serving summary" in text
        assert "time-to-first-token" in text

    def test_cancel_releases_slot_and_uid_is_reservable(
            self, gpt_setup, tmp_path):
        """cancel(uid) mid-flight: returns the HARVESTED prefix of the
        stream (a prefix of the reference — harvest is the commit
        point), frees the slot for new admissions, records no
        Completion (the uid can be re-served from scratch), and emits
        a ``request_cancelled`` event."""
        import collections

        from apex_tpu.telemetry.metrics import MetricsLogger

        mesh, model, params, prompts, plens, new, ref = gpt_setup
        jsonl = str(tmp_path / "cancel.jsonl")
        logger = MetricsLogger(jsonl_path=jsonl, console=False)
        page = 4
        pps = -(-(10 + new) // page)
        ccfg = KVCacheConfig(
            num_layers=2, num_heads=4, head_dim=8,
            num_pages=1 + 2 * pps, page_size=page, max_seqs=2,
            pages_per_seq=pps, dtype=jnp.float32)
        fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=10)
        b = ContinuousBatcher(
            fns.prefill, fns.decode, PagedKVCache(ccfg),
            init_pools(ccfg), max_prompt_len=10, harvest_every=2,
            logger=logger)
        reqs = [
            Request(uid=i,
                    prompt=[int(t) for t in prompts[i, : plens[i]]],
                    max_new_tokens=new)
            for i in range(2)
        ]
        q = collections.deque(reqs)
        b.pump(q)                       # admit both, one harvest window
        assert b.live_slots == 2
        free_before = b.cache.allocator.num_free
        got = b.cancel(0)
        want0 = list(map(int, ref[0]))
        assert got and got == want0[: len(got)]
        assert b.cancel("never-admitted") is None
        assert b.live_slots == 1
        assert b.cache.allocator.num_free > free_before
        assert 0 not in b.completions   # cancelled, not completed
        # the uid is free again: re-serve it from scratch to the full
        # reference while request 1 keeps decoding undisturbed
        q2 = collections.deque([reqs[0]])
        while b.pump(q2):
            pass
        assert b.completions[0].tokens == want0
        assert b.completions[1].tokens == list(map(int, ref[1]))
        logger.close()

        import tools.metrics_report as mr

        cancels = [r for r in mr.load_records(jsonl)
                   if r.get("event") == "request_cancelled"]
        assert len(cancels) == 1
        assert cancels[0]["uid"] == 0
        assert cancels[0]["new_tokens"] == len(got)

    def test_load_gauges_reach_metrics_jsonl(self, gpt_setup,
                                             tmp_path):
        """The serving load gauges (pages_free / pages_shared /
        live_slots / queue_depth) — the quantities the fleet router
        scores replicas by — land in the jsonl meters stream and the
        report summary."""
        from apex_tpu.telemetry.metrics import MetricsLogger

        jsonl = str(tmp_path / "gauges.jsonl")
        logger = MetricsLogger(jsonl_path=jsonl, console=False)
        _serve(gpt_setup, n_req=3, max_seqs=2, harvest_every=4,
               logger=logger)
        logger.close()

        import tools.metrics_report as mr

        summary = mr.summarize(mr.load_records(jsonl))
        gauges = summary["meters"]["gauges"]
        assert {"pages_free", "pages_shared", "live_slots",
                "queue_depth"} <= set(gauges)
        assert all(v >= 0 for v in gauges.values())

    def test_request_validation(self):
        from apex_tpu.serving.serve import Request

        with pytest.raises(ValueError, match="max_new_tokens"):
            Request(uid=0, prompt=[1], max_new_tokens=0)
        with pytest.raises(ValueError, match="prompt"):
            Request(uid=0, prompt=[], max_new_tokens=1)


def _chunked_setup(gpt_setup, chunk, *, prefix=False, temperature=0.0,
                   slots=2, logger=None, new=12):
    """decode_fns + batcher wired for chunked prefill on the tiny GPT."""
    mesh, model, params, prompts, plens, _new, ref = gpt_setup
    page = 4
    pps = -(-(10 + new) // page)
    ccfg = KVCacheConfig(
        num_layers=2, num_heads=4, head_dim=8,
        num_pages=1 + (slots + 4) * pps, page_size=page,
        max_seqs=slots, pages_per_seq=pps, dtype=jnp.float32)
    fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=10,
                           temperature=temperature,
                           top_k=(20 if temperature else None),
                           prefill_chunk=chunk)
    batcher = ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(ccfg), init_pools(ccfg),
        max_prompt_len=10, harvest_every=3, chunk_fn=fns.chunk,
        prefill_chunk=chunk, prefix_cache=prefix, logger=logger)
    return fns, batcher


class TestChunkedPrefillServing:
    def test_chunked_matches_monolithic_and_reference_under_churn(
            self, gpt_setup):
        """6 requests through 2 slots, varying prompt lengths (1 to 3
        chunks each): the chunked scheduler's greedy output must equal
        BOTH the monolithic path's and the full-recompute reference,
        with and without the prefix cache."""
        mesh, model, params, prompts, plens, new, ref = gpt_setup
        for prefix in (False, True):
            fns, batcher = _chunked_setup(gpt_setup, chunk=4,
                                          prefix=prefix)
            comps = batcher.run([
                Request(uid=i,
                        prompt=[int(t) for t in prompts[i, : plens[i]]],
                        max_new_tokens=new)
                for i in range(6)
            ])
            for i in range(6):
                assert comps[i].tokens == list(map(int, ref[i])), \
                    (prefix, i)

    def test_prefix_hit_logits_bit_identical_to_cold(self, gpt_setup):
        """Same prompt admitted cold, then as a hit (and twice more
        through the copy-on-write whole-prompt-match path): the
        last-prompt-token logits must agree BITWISE — shared pages
        hold the same bits a cold prefill would write."""
        mesh, model, params, prompts, plens, new, ref = gpt_setup
        fns, batcher = _chunked_setup(gpt_setup, chunk=4, prefix=True)
        prompt = [int(t) for t in prompts[0, :10]]

        def logits_of(uid, pr):
            batcher.run([Request(uid=uid, prompt=pr,
                                 max_new_tokens=new)])
            return np.asarray(
                jax.device_get(batcher.last_prefill_logits))

        cold = logits_of("cold", prompt)
        hit = logits_of("hit", prompt)
        np.testing.assert_array_equal(cold, hit)
        assert batcher.prefix_stats["hits"] == 1
        assert batcher.prefix_stats["shared_pages"] == 2
        # whole-prompt full-page match -> CoW tail; the cold baseline
        # comes from a FRESH batcher — on the shared one prompt[:8]
        # already prefix-matches, so both sides would take the CoW
        # path and a deterministic copy bug could hide
        fns2, fresh = _chunked_setup(gpt_setup, chunk=4, prefix=True)
        fresh.run([Request(uid="cc", prompt=prompt[:8],
                           max_new_tokens=new)])
        assert fresh.prefix_stats["hits"] == 0       # genuinely cold
        cow_cold = np.asarray(
            jax.device_get(fresh.last_prefill_logits))
        cow_hit = logits_of("ch", prompt[:8])
        np.testing.assert_array_equal(cow_cold, cow_hit)
        assert batcher.prefix_stats["copied_pages"] >= 1
        assert (fresh.completions["cc"].tokens
                == batcher.completions["ch"].tokens)

    def test_zero_new_jit_entries_across_chunk_counts_and_hits(
            self, gpt_setup):
        """The compile-count spy for the chunk path: prompts of 1, 2
        and 3 chunks, cold and hit admissions, a CoW admission — all
        reuse the same compiled chunk/decode steps."""
        mesh, model, params, prompts, plens, new, ref = gpt_setup
        fns, batcher = _chunked_setup(gpt_setup, chunk=4, prefix=True)
        p0 = [int(t) for t in prompts[0, :10]]
        batcher.run([Request(uid=0, prompt=p0, max_new_tokens=new)])
        chunk_size = int(fns.chunk_jit._cache_size())
        decode_size = int(fns.decode_jit._cache_size())
        assert chunk_size <= 2, chunk_size
        batcher.run([
            Request(uid=1, prompt=p0[:3], max_new_tokens=4),   # 1 chunk
            Request(uid=2, prompt=p0[:7], max_new_tokens=4),   # 2 chunks
            Request(uid=3, prompt=p0, max_new_tokens=new),     # full hit
            Request(uid=4, prompt=p0[:8], max_new_tokens=4),   # CoW hit
        ])
        assert int(fns.chunk_jit._cache_size()) == chunk_size
        assert int(fns.decode_jit._cache_size()) == decode_size
        assert batcher.prefix_stats["hits"] >= 2

    def test_seeded_requests_reproducible_across_order_and_slots(
            self, gpt_setup):
        """A seeded request samples the same stream no matter the
        admission order, slot assignment, scheduler mode or server
        key (test-pinned satellite contract)."""
        mesh, model, params, prompts, plens, new, ref = gpt_setup

        def serve(order, chunk, server_seed):
            if chunk is None:
                page = 4
                pps = -(-(10 + new) // page)
                ccfg = KVCacheConfig(
                    num_layers=2, num_heads=4, head_dim=8,
                    num_pages=1 + 2 * pps, page_size=page, max_seqs=2,
                    pages_per_seq=pps, dtype=jnp.float32)
                fns = model.decode_fns(
                    params, mesh, ccfg, max_prompt_len=10,
                    temperature=0.7, top_k=20)
                batcher = ContinuousBatcher(
                    fns.prefill, fns.decode, PagedKVCache(ccfg),
                    init_pools(ccfg), max_prompt_len=10,
                    harvest_every=3,
                    key=jax.random.PRNGKey(server_seed))
            else:
                fns, batcher = _chunked_setup(
                    gpt_setup, chunk=chunk, temperature=0.7)
            reqs = [Request(uid=i,
                            prompt=[int(t) for t in
                                    prompts[i, : plens[i]]],
                            max_new_tokens=new, seed=100 + i)
                    for i in order]
            return batcher.run(reqs)

        a = serve([0, 1, 2], None, 0)
        b = serve([2, 1, 0], None, 7)       # order + server key moved
        c = serve([1, 2, 0], 4, 0)          # chunked scheduler
        for i in range(3):
            assert a[i].tokens == b[i].tokens, i
            assert a[i].tokens == c[i].tokens, i
        # and an unseeded request does NOT promise this
        assert len(a[0].tokens) > 0

    def test_chunked_telemetry_reaches_metrics_report(
            self, gpt_setup, tmp_path):
        from apex_tpu.telemetry.metrics import MetricsLogger

        mesh, model, params, prompts, plens, new, ref = gpt_setup
        jsonl = str(tmp_path / "chunked.jsonl")
        logger = MetricsLogger(jsonl_path=jsonl, console=False)
        fns, batcher = _chunked_setup(gpt_setup, chunk=4, prefix=True,
                                      logger=logger)
        p0 = [int(t) for t in prompts[0, :10]]
        # sequential: "b" admits after "a" registered the prefix (two
        # identical prompts admitted CONCURRENTLY both miss — the
        # first has not finished prefilling when the second matches)
        batcher.run([Request(uid="a", prompt=p0, max_new_tokens=new)])
        batcher.run([Request(uid="b", prompt=p0, max_new_tokens=new)])
        logger.close()

        import tools.metrics_report as mr

        summary = mr.summarize(mr.load_records(jsonl))
        sv = summary["serving"]
        assert sv["prefill_chunks"]["count"] == batcher.prefill_chunks
        px = sv["prefix_cache"]
        assert px["admissions"] == 2 and px["hits"] == 1
        assert px["hit_rate"] == 0.5
        assert px["pages_shared"] == 2
        assert px["prefill_tokens_skipped"] == 8
        text = mr.format_report(summary)
        assert "prefix cache" in text
        assert "chunk-granularity admission" in text

    def test_chunked_rope_model_matches_reference(self, gpt_setup):
        """The chunk step's rope rows come from the same cached table
        decode uses — a rotary (Llama-style) model must be chunk/
        monolithic/reference token-identical too."""
        from apex_tpu.models import GPTConfig, GPTModel

        mesh, *_ = gpt_setup
        model = GPTModel(GPTConfig(
            vocab_size=64, num_layers=2, hidden_size=32,
            num_attention_heads=4, max_position_embeddings=64,
            position_embedding="rope", normalization="rmsnorm",
            compute_dtype=jnp.float32, remat=False,
            attention_impl="xla"))
        params = model.init(jax.random.PRNGKey(2))
        rng = np.random.RandomState(9)
        prompts = rng.randint(1, 64, (3, 9)).astype(np.int32)
        plens = np.array([9, 6, 4], np.int32)
        for i in range(3):
            prompts[i, plens[i]:] = 0
        ref = model.generate_reference(params, prompts, plens, 8,
                                       mesh=mesh)
        got = model.generate(params, prompts, plens, 8, mesh=mesh,
                             page_size=4, max_seqs=2, harvest_every=3,
                             prefill_chunk=4, prefix_cache=True)
        for i in range(3):
            assert got[i] == list(map(int, ref[i])), i

    def test_batcher_validation(self, gpt_setup):
        mesh, model, params, prompts, plens, new, ref = gpt_setup
        page = 4
        pps = -(-(10 + new) // page)
        ccfg = KVCacheConfig(
            num_layers=2, num_heads=4, head_dim=8,
            num_pages=1 + 2 * pps, page_size=page, max_seqs=2,
            pages_per_seq=pps, dtype=jnp.float32)
        fns = model.decode_fns(params, mesh, ccfg, max_prompt_len=10,
                               prefill_chunk=4)
        kw = dict(cache=PagedKVCache(ccfg), pools=init_pools(ccfg))
        with pytest.raises(ValueError, match="BOTH chunk_fn"):
            ContinuousBatcher(fns.prefill, fns.decode, kw["cache"],
                              kw["pools"], max_prompt_len=10,
                              chunk_fn=fns.chunk)
        with pytest.raises(ValueError, match="prefill_chunk mismatch"):
            ContinuousBatcher(fns.prefill, fns.decode, kw["cache"],
                              kw["pools"], max_prompt_len=10,
                              chunk_fn=fns.chunk, prefill_chunk=8)
        with pytest.raises(ValueError, match="prefix_cache requires"):
            ContinuousBatcher(fns.prefill, fns.decode, kw["cache"],
                              kw["pools"], max_prompt_len=10,
                              prefix_cache=True)
        with pytest.raises(ValueError, match="prefill_chunk must be"):
            model.decode_fns(params, mesh, ccfg, max_prompt_len=10,
                             prefill_chunk=0)
        # past the kernel's per-program row budget: fail at build
        # time, not with a VMEM lowering error at serve time
        from apex_tpu.ops.attention_decode import FMHA_DECODE_MAX_ROWS

        with pytest.raises(ValueError, match="row budget"):
            model.decode_fns(params, mesh, ccfg, max_prompt_len=10,
                             prefill_chunk=FMHA_DECODE_MAX_ROWS + 1)

    def test_decode_fns_rejects_mismatched_cache(self, gpt_setup):
        mesh, model, params, *_ = gpt_setup
        bad = KVCacheConfig(num_layers=2, num_heads=8, head_dim=8,
                            num_pages=4, page_size=4, max_seqs=1,
                            pages_per_seq=2)
        with pytest.raises(ValueError, match="does not match"):
            model.decode_fns(params, mesh, bad, max_prompt_len=8)

    def test_decode_fns_rejects_learned_overflow(self, gpt_setup):
        mesh, model, params, *_ = gpt_setup
        big = KVCacheConfig(num_layers=2, num_heads=4, head_dim=8,
                            num_pages=64, page_size=32, max_seqs=1,
                            pages_per_seq=4)   # 128 > 64 positions
        with pytest.raises(ValueError, match="learned table"):
            model.decode_fns(params, mesh, big, max_prompt_len=8)
