"""The latent page walk under a sparse selection: the Mosaic kernel (in
interpret mode) and its XLA twin against ``mla_absorbed`` over the
chosen rows gathered through the page table, and the threshold mask
against the positions ``lax.top_k`` gives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.attention_latent import mla_absorbed, mla_paged
from apex_tpu.ops.sparse_index import mask_at, topk_indices, topk_mask

# toy widths: heads, nope, rope, latent, value, pool row (lane-padded)
H, DN, DR, DC, DV, ROW = 4, 16, 8, 32, 16, 128
PAGE, WIDTH, K, LAYERS = 8, 6, 6, 2
# one slot each: many ties at the threshold, fewer valid rows than K, an
# idle slot, a selection inside one page, a slot filled to its extent
LENGTHS = (40, 3, 0, 30, 48)


def _selection(seed, k=K):
    """Index scores on a coarse grid (ties everywhere), slot 3's best
    ``k`` rows all in its third page; -> (scores, valid)."""
    rng = np.random.default_rng(seed)
    scores = rng.integers(0, 4, (len(LENGTHS), WIDTH * PAGE)).astype(
        np.float32)
    scores[3, 2 * PAGE:2 * PAGE + k] = 10.0
    valid = np.arange(WIDTH * PAGE)[None] < np.asarray(LENGTHS)[:, None]
    return jnp.asarray(scores), jnp.asarray(valid)


def _scattered(idx, chosen, shape):
    rows = np.arange(shape[0])[:, None]
    out = np.zeros(shape, bool)
    np.logical_or.at(out, (rows, np.asarray(idx)), np.asarray(chosen))
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("k", [1, K, 20])
def test_threshold_mask_is_the_set_top_k_gives(seed, k):
    """``mask_at`` from the K-th value ``topk_indices`` returns marks
    exactly the positions it returns (ties to the lower position, a slot
    with fewer valid rows than K keeps them all, an idle slot none), and
    the chunk's bisection form marks the same set."""
    scores, valid = _selection(seed, k=min(k, PAGE))
    idx, chosen, kth = jax.jit(topk_indices, static_argnums=1)(
        scores, k, valid)
    got = np.asarray(jax.jit(lambda s, v, t: mask_at(
        jnp.where(v, s, -jnp.inf), t, k, v))(scores, valid, kth))
    want = _scattered(idx, chosen, scores.shape)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, np.asarray(topk_mask(scores, k,
                                                            valid)))
    assert (got.sum(-1) == np.minimum(k, np.asarray(LENGTHS))).all()
    # the selection of slot 3 lies in one page
    if k <= PAGE:
        assert set(np.flatnonzero(got[3]) // PAGE) == {2}


def _attention_inputs(seed):
    B = len(LENGTHS)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n_pages = 1 + B * WIDTH
    pool = jax.random.normal(ks[0], (LAYERS, n_pages, PAGE, DC + DR))
    pool = jnp.pad(pool, ((0, 0), (0, 0), (0, 0), (0, ROW - DC - DR)))
    perm = np.random.default_rng(seed).permutation(B * WIDTH) + 1
    table = jnp.asarray(perm.reshape(B, WIDTH), jnp.int32)
    return (jax.random.normal(ks[1], (B, H, DN)),
            jax.random.normal(ks[2], (B, H, DR)), pool, table,
            jax.random.normal(ks[3], (DC, H, DN)) * DC ** -0.5,
            jax.random.normal(ks[4], (DC, H, DV)) * DC ** -0.5)


@pytest.mark.parametrize("implementation", ["pallas", "xla"])
@pytest.mark.parametrize("seed", [0, 1])
def test_masked_walk_equals_absorbed_attention_over_gathered_rows(
        implementation, seed):
    """The walk of every live row with the rows not chosen masked out is
    the absorbed form over the chosen rows alone: the kernel (interpret
    mode) and the XLA path both, on every live slot; the idle slot walks
    nothing (zeros on the kernel's path, finite on both)."""
    qn, qr, pool, table, w_uk, w_uv = _attention_inputs(seed)
    scores, valid = _selection(seed)
    idx, chosen, kth = topk_indices(scores, K, valid)
    selected = mask_at(jnp.where(valid, scores, -jnp.inf), kth, K, valid)
    layer = 1
    rows = pool[layer, jnp.take_along_axis(table, idx // PAGE, axis=1),
                idx % PAGE]
    want = np.asarray(mla_absorbed(qn, qr, rows, chosen, w_uk, w_uv, 0.3))
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    got = np.asarray(mla_paged(qn, qr, pool, jnp.int32(layer), table,
                               lengths, w_uk, w_uv, 0.3, selected=selected,
                               implementation=implementation))
    live = np.asarray(LENGTHS) > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=0)
    assert np.isfinite(got).all()
    if implementation == "pallas":
        assert (got[~live] == 0).all()
    # the selection matters: without it the walk is another attention
    whole = np.asarray(mla_paged(qn, qr, pool, jnp.int32(layer), table,
                                 lengths, w_uk, w_uv, 0.3,
                                 implementation=implementation))
    assert np.abs(whole[0] - want[0]).max() > 1e-2

