"""The latent pool kind of the paged cache: a per-token entry that is
not per head, written in place through the same page tables."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving.kv_cache import (
    KVCacheConfig, PagedKVCache, copy_pages, export_pages,
    import_pages, init_pools, write_latent_tokens, write_targets,
)


def _config(**kw):
    args = dict(num_layers=3, num_heads=1, head_dim=24, num_pages=9,
                page_size=4, max_seqs=2, pages_per_seq=4, dtype=jnp.float32,
                kind="latent", latent_dim=24, index_dim=8)
    args.update(kw)
    return KVCacheConfig(**args)


def test_pools_hold_three_per_token_entries_in_lane_padded_rows():
    cfg = _config(latent_dim=576, head_dim=576, index_dim=128)
    assert cfg.latent_row_dim == 640
    shapes = jax.eval_shape(lambda: init_pools(cfg))
    assert shapes["ckv"].shape == (3, 9, 4, 640)
    assert shapes["kidx"].shape == (3, 9, 4, 128)
    assert set(shapes) == {"ckv", "kidx"}


@pytest.mark.parametrize("bad,match", [
    (dict(kind="rows"), "kind must be"),
    (dict(latent_dim=0), "latent_dim and index_dim"),
    (dict(num_heads=4), "shared by all heads"),
    (dict(head_dim=16), "shared by all heads"),
    (dict(kv_dtype=jnp.int8), "not quantized"),
])
def test_latent_config_is_validated(bad, match):
    with pytest.raises(ValueError, match=match):
        _config(**bad)


def test_plain_kv_config_is_unchanged():
    cfg = KVCacheConfig(num_layers=2, num_heads=4, head_dim=16, num_pages=5)
    assert cfg.kind == "kv" and set(init_pools(cfg)) == {"k", "v"}
    assert init_pools(cfg)["k"].shape == (2, 5, 4, 64, 16)
    assert len(PagedKVCache(cfg).compat_key()) == 7
    assert PagedKVCache(_config()).compat_key()[-3:] == ("latent", 24, 8)


@pytest.mark.parametrize("layer", [0, 2])
def test_written_tokens_read_back_through_the_page_table(layer):
    cfg = _config()
    cache = PagedKVCache(cfg)
    cache.admit(0, 11)
    cache.admit(1, 6)
    pools = init_pools(cfg)
    rows = jax.random.normal(jax.random.PRNGKey(0), (11, 24))
    keys = jax.random.normal(jax.random.PRNGKey(1), (11, 8))
    positions = jnp.arange(11)
    pages, offsets = write_targets(jnp.asarray(cache.page_table[0]),
                                   positions, positions < 11, 4)
    out = jax.jit(write_latent_tokens)(pools, jnp.int32(layer), rows, keys,
                                       pages, offsets)
    got = out["ckv"][layer, cache.page_table[0]].reshape(16, -1)[:11]
    np.testing.assert_array_equal(np.asarray(got[:, :24]), np.asarray(rows))
    assert not np.asarray(got[:, 24:]).any()        # the lane padding
    got = out["kidx"][layer, cache.page_table[0]].reshape(16, -1)[:11]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(keys))
    # nothing else moved: other layers, the other slot's pages, page 0
    others = [l for l in range(3) if l != layer]
    assert not np.asarray(out["ckv"][jnp.asarray(others)]).any()
    assert not np.asarray(out["ckv"][layer, cache.page_table[1]]).any()
    assert not np.asarray(out["ckv"][layer, 0]).any()


def test_invalid_rows_land_on_the_null_page():
    cfg = _config()
    cache = PagedKVCache(cfg)
    cache.admit(0, 8)
    positions = jnp.arange(8)
    pages, offsets = write_targets(jnp.asarray(cache.page_table[0]),
                                   positions, positions < 3, 4)
    out = write_latent_tokens(init_pools(cfg), 1, jnp.ones((8, 24)),
                              jnp.ones((8, 8)), pages, offsets)
    live = out["ckv"][1, cache.page_table[0][:2]].reshape(8, -1)
    assert float(live[:3, :24].sum()) == 3 * 24
    assert not np.asarray(live[3:]).any()
    assert np.asarray(out["ckv"][1, 0]).any()       # the garbage page


def test_a_donated_write_is_in_place():
    cfg = _config()
    write = jax.jit(write_latent_tokens, donate_argnums=(0,))
    args = (jnp.int32(1), jnp.ones((2, 24)), jnp.ones((2, 8)),
            jnp.asarray([1, 2]), jnp.asarray([0, 3]))
    pools = init_pools(cfg)
    compiled = write.lower(pools, *args).compile()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in pools.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    out = write(pools, *args)
    assert all(a.is_deleted() for a in pools.values())
    assert float(out["ckv"][1, 2, 3, :24].sum()) == 24


def test_page_movers_move_both_entries():
    cfg = _config()
    pools = init_pools(cfg)
    pools["ckv"] = pools["ckv"].at[:, 3].set(7.0)
    pools["kidx"] = pools["kidx"].at[:, 3].set(5.0)
    out = copy_pages(pools, jnp.asarray([3]), jnp.asarray([5]))
    assert float(out["ckv"][:, 5].min()) == 7.0
    staged = export_pages(out, [5])
    assert set(staged) == {"ckv", "kidx"}
    back = import_pages(init_pools(cfg), staged, jnp.asarray([2]))
    assert float(back["ckv"][:, 2].min()) == 7.0
    assert float(back["kidx"][:, 2].min()) == 5.0
