"""The cache's page classes: a class of its own pool, allocator and
table columns; a window class as a ring a slot owns; one-class configs
unchanged."""

import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving.kv_cache import (
    CacheOutOfPages, KVCacheConfig, PageClass, PagedKVCache, init_pools,
    write_targets,
)

PAGE = 64


def _classes(slots=2, full_pages=200, ring=81, pool_full=None,
             pool_window=None):
    entry = dict(num_heads=8, head_dim=128)
    return (
        PageClass(name="full", layers=(4,), pages_per_seq=full_pages,
                  num_pages=pool_full or 1 + slots * full_pages, **entry),
        PageClass(name="window", layers=(0, 1, 2, 3), pages_per_seq=ring,
                  num_pages=pool_window or 1 + slots * ring, window=4096,
                  **entry))


def _cache(**kwargs):
    return PagedKVCache(KVCacheConfig.of_classes(
        _classes(**kwargs), page_size=PAGE, max_seqs=2))


def test_a_12800_token_sequence_holds_81_window_pages():
    cache = _cache()
    cache.admit(0, 12800)
    assert cache.pages_in_use() == {"full": 200, "window": 81}
    row = cache.page_table[0]
    assert row.shape == (281,) and np.all(row > 0)
    # the two classes number their pages apart: both start at 1
    assert sorted(row[:200]) == list(range(1, 201))
    assert sorted(row[200:]) == list(range(1, 82))
    # position p lives in ring column (p // 64) % 81 of the window class
    pos = jnp.asarray([0, 63, 64, 81 * 64 - 1, 81 * 64, 12799])
    pages, offsets = write_targets(
        jnp.asarray(row[200:]), pos, jnp.ones((6,), bool), PAGE, ring=81)
    want = [0, 0, 1, 80, 0, (12799 // 64) % 81]
    assert list(np.asarray(pages)) == [int(row[200 + c]) for c in want]
    assert list(np.asarray(offsets)) == [0, 63, 0, 63, 0, 63]
    cache.lengths[0] = 12800
    cache.retire(0)
    assert cache.pages_in_use() == {"full": 0, "window": 0}
    assert cache.overwritten_pages == {"window": 200 - 81}
    assert np.all(cache.page_table[0] == 0)


def test_a_short_sequence_holds_its_own_pages_only():
    cache = _cache()
    cache.admit(1, 384)
    assert cache.pages_in_use() == {"full": 6, "window": 6}
    cache.lengths[1] = 384
    cache.retire(1)
    assert cache.overwritten_pages == {"window": 0}


@pytest.mark.parametrize("short", ["full", "window"])
def test_out_of_pages_in_either_class_allocates_nothing(short):
    cache = _cache(**{f"pool_{short}": 1 + 100})
    cache.admit(0, 64 * 60)
    before = cache.pages_in_use()
    with pytest.raises(CacheOutOfPages):
        cache.admit(1, 64 * 60)
    assert cache.pages_in_use() == before
    assert cache.active_slots() == [0]
    cache.retire(0)
    cache.admit(1, 64 * 60)             # and fits once the pages are back


def test_prefix_match_is_refused_over_classes():
    cache = _cache()
    with pytest.raises(ValueError, match="prefix index"):
        cache.admit(0, 256, prompt_tokens=list(range(128)))


def test_compat_key_tells_the_classes_apart():
    a = _cache()
    b = _cache(ring=82)
    flat = PagedKVCache(KVCacheConfig(
        num_layers=5, num_heads=8, head_dim=128, num_pages=9))
    assert a.compat_key() != b.compat_key()
    assert a.compat_key() != flat.compat_key()
    assert a.compat_key() == _cache(slots=2, pool_full=500).compat_key()


def test_class_checks():
    entry = dict(num_heads=2, head_dim=16)
    window = PageClass(name="w", layers=(0,), num_pages=9, pages_per_seq=3,
                       window=256, **entry)
    with pytest.raises(ValueError, match="no token bound"):
        KVCacheConfig.of_classes((window,), page_size=PAGE, max_seqs=1)
    full = PageClass(name="f", layers=(1,), num_pages=9, pages_per_seq=8,
                     **entry)
    with pytest.raises(ValueError, match="cannot hold a window"):
        KVCacheConfig.of_classes((full, window), page_size=PAGE, max_seqs=1)
    twice = PageClass(name="g", layers=(1,), num_pages=9, pages_per_seq=8,
                      **entry)
    with pytest.raises(ValueError, match="each of the"):
        KVCacheConfig.of_classes((full, twice), page_size=PAGE, max_seqs=1)


@pytest.mark.parametrize("kind", ["kv", "latent"])
def test_a_flat_config_is_its_one_class(kind):
    extra = (dict(num_heads=1, head_dim=24, kind="latent", latent_dim=24,
                  index_dim=16) if kind == "latent"
             else dict(num_heads=4, head_dim=16))
    cfg = KVCacheConfig(num_layers=3, num_pages=17, page_size=4, max_seqs=2,
                        pages_per_seq=8, dtype=jnp.float32, **extra)
    (only,) = cfg.page_classes
    assert (only.name, only.layers, only.num_pages, only.pages_per_seq,
            only.window) == (kind, (0, 1, 2), 17, 8, 0)
    assert cfg.table_columns == ((0, 8),)
    cache = PagedKVCache(cfg)
    assert cache.page_table.shape == (2, 8)
    assert cache.allocators == [cache.allocator]
    cache.admit(0, 20)
    assert cache.pages_in_use() == {kind: 5}
    assert sorted(init_pools(cfg)) == (
        ["ckv", "kidx"] if kind == "latent" else ["k", "v"])
