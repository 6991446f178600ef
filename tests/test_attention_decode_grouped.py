"""``fmha_decode`` with grouped-query heads and a per-sequence first
position over a ring table, against a dense ``jnp`` attention that knows
nothing of pages; and its one-K/V-head-a-query-head case, bit-equal to
the walk from position 0."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.attention_decode import fmha_decode

PS, D = 4, 16


def _ring_cache(key, lengths, h_kv, ring, window):
    """Dense K/V (b, h_kv, L, d) and the SAME tokens laid out as a ring
    cache: position p of sequence b in column (p // PS) % ring of its
    row, every page of the pool another one."""
    b, L = len(lengths), max(lengths)
    kk, kv = jax.random.split(key)
    k = jax.random.normal(kk, (b, h_kv, L, D), jnp.float32)
    v = jax.random.normal(kv, (b, h_kv, L, D), jnp.float32)
    table = 1 + np.arange(b * ring, dtype=np.int32).reshape(b, ring)
    table = table[:, np.random.default_rng(0).permutation(ring)]
    kp = np.zeros((1 + b * ring, h_kv, PS, D), np.float32)
    vp = np.zeros_like(kp)
    for i, ln in enumerate(lengths):
        for p in range(ln):             # later positions overwrite
            page = table[i, (p // PS) % ring]
            kp[page, :, p % PS] = np.asarray(k[i, :, p])
            vp[page, :, p % PS] = np.asarray(v[i, :, p])
    return k, v, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table)


def _dense(q, k, v, lengths, window, group):
    """softmax(q k / sqrt(d)) v over positions [ln - window, ln) of each
    sequence, query head i on K/V head i // group."""
    outs = []
    for i, ln in enumerate(lengths):
        lo = max(ln - window, 0)
        ki = jnp.repeat(k[i, :, lo:ln], group, axis=0)
        vi = jnp.repeat(v[i, :, lo:ln], group, axis=0)
        s = jnp.einsum("hqd,hkd->hqk", q[i], ki) / D ** 0.5
        outs.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), vi))
    return jnp.stack(outs)


@pytest.mark.parametrize("implementation", ["xla", "pallas"])
@pytest.mark.parametrize("group,window", [(3, 8), (1, 8), (2, 10 ** 6)])
def test_grouped_windowed_matches_dense(implementation, group, window):
    h_kv, ring = 2, 4                   # ring: window 8 + two pages
    lengths = [3, 8, 21, 38]            # inside the window .. 2 wraps
    if window > 100:
        ring = 10                       # the whole context, never wraps
    k, v, kp, vp, table = _ring_cache(
        jax.random.PRNGKey(1), lengths, h_kv, ring, window)
    q = jax.random.normal(jax.random.PRNGKey(2),
                          (len(lengths), h_kv * group, 1, D), jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    got = fmha_decode(
        q, kp, vp, table, ln, num_kv_heads=h_kv,
        first=jnp.maximum(ln - window, 0),
        max_pages=min(window // PS + 1, ring),
        implementation=implementation)
    np.testing.assert_allclose(
        got, _dense(q, k, v, lengths, window, group), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("implementation", ["xla", "pallas"])
def test_grouped_from_zero_without_first(implementation):
    """Grouped heads over a plain table (no ``first``): the full layers'
    call."""
    h_kv, group, lengths = 2, 3, [5, 17]
    k, v, kp, vp, table = _ring_cache(
        jax.random.PRNGKey(3), lengths, h_kv, 5, 10 ** 6)
    q = jax.random.normal(jax.random.PRNGKey(4),
                          (2, h_kv * group, 1, D), jnp.float32)
    got = fmha_decode(q, kp, vp, table, jnp.asarray(lengths, jnp.int32),
                      num_kv_heads=h_kv, implementation=implementation)
    np.testing.assert_allclose(
        got, _dense(q, k, v, lengths, 10 ** 6, group), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("implementation", ["xla", "pallas"])
def test_mha_case_bit_equal(implementation):
    """One K/V head a query head, walked from 0: the extended entry
    (``num_kv_heads == heads``; and ``first == 0`` over a table that
    never wraps) gives today's kernel's bits."""
    h, lengths = 4, [6, 19, 40]
    k, v, kp, vp, table = _ring_cache(
        jax.random.PRNGKey(5), lengths, h, 10, 10 ** 6)
    q = jax.random.normal(jax.random.PRNGKey(6), (3, h, 1, D), jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    today = fmha_decode(q, kp, vp, table, ln,
                        implementation=implementation)
    same = fmha_decode(q, kp, vp, table, ln, num_kv_heads=h,
                       implementation=implementation)
    from_zero = fmha_decode(q, kp, vp, table, ln, num_kv_heads=h,
                            first=jnp.zeros_like(ln),
                            implementation=implementation)
    assert np.array_equal(today, same)
    if implementation == "pallas":      # the XLA path sums in another order
        assert np.array_equal(today, from_zero)
    else:
        np.testing.assert_allclose(today, from_zero, rtol=1e-6, atol=1e-6)


def test_fused_rotation_with_grouped_rows():
    """The kernel rotates q itself (``q cos + rotate_half(q) sin`` on
    the packed rows): the same as rotating q first."""
    from apex_tpu.ops.rope import apply_rope_tables, rope_cos_sin

    h_kv, group, lengths = 2, 3, [5, 17, 38]
    k, v, kp, vp, table = _ring_cache(
        jax.random.PRNGKey(7), lengths, h_kv, 4, 8)
    q = jax.random.normal(jax.random.PRNGKey(8),
                          (3, h_kv * group, 1, D), jnp.float32)
    ln = jnp.asarray(lengths, jnp.int32)
    cos, sin = rope_cos_sin(ln - 1, D)
    kw = dict(num_kv_heads=h_kv, first=jnp.maximum(ln - 8, 0), max_pages=3)
    fused = fmha_decode(q, kp, vp, table, ln, implementation="pallas",
                        rope=(cos[:, None], sin[:, None]), **kw)
    rotated = apply_rope_tables(q, cos[:, None, None], sin[:, None, None])
    for impl in ("pallas", "xla"):
        np.testing.assert_allclose(
            fused, fmha_decode(rotated, kp, vp, table, ln,
                               implementation=impl, **kw),
            rtol=2e-5, atol=2e-5)


def test_refusals():
    q = jnp.zeros((1, 4, 1, D))
    kp = jnp.zeros((3, 3, PS, D))
    with pytest.raises(ValueError, match="pool heads"):
        fmha_decode(q, kp, kp, jnp.zeros((1, 2), jnp.int32),
                    jnp.ones((1,), jnp.int32), num_kv_heads=3)
    kp = jnp.zeros((3, 2, PS, D))
    with pytest.raises(ValueError, match="first must be"):
        fmha_decode(q, kp, kp, jnp.zeros((1, 2), jnp.int32),
                    jnp.ones((1,), jnp.int32), num_kv_heads=2,
                    first=jnp.zeros((2,), jnp.int32))
