"""The lightning indexer's selection is EXACT: both forms give the true
top-k of the valid scores, ties to the lower position — the set a stable
descending sort (the reference's ``select``) gives."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import deepseek_v32 as ref  # noqa: E402

from apex_tpu.ops.sparse_index import (  # noqa: E402
    index_scores, topk_indices, topk_mask,
)


def _reference_mask(scores, k, valid):
    return np.asarray(ref.select(
        jnp.where(valid, scores, -jnp.inf), k))


def _as_mask(idx, chosen, width):
    mask = np.zeros((idx.shape[0], width), bool)
    for r in range(idx.shape[0]):
        mask[r, np.asarray(idx[r])[np.asarray(chosen[r])]] = True
    return mask


def _causal(n, width, offset):
    return jnp.arange(width)[None] <= (offset + jnp.arange(n))[:, None]


@pytest.mark.parametrize("seed,n,width,k", [
    (0, 6, 50, 8), (1, 16, 257, 64), (2, 3, 2048, 512), (3, 9, 40, 40),
    (4, 5, 33, 1)])
def test_both_forms_select_the_reference_set(seed, n, width, k):
    scores = jax.random.normal(jax.random.PRNGKey(seed), (n, width)) * 3
    valid = _causal(n, width, width - n)
    want = _reference_mask(scores, k, valid)
    assert (np.asarray(jax.jit(topk_mask, static_argnums=1)(
        scores, k, valid)) == want).all()
    idx, chosen, _ = jax.jit(topk_indices, static_argnums=1)(scores, k, valid)
    assert (_as_mask(idx, chosen, width) == want).all()
    assert want.sum(1).tolist() == [min(k, int(v)) for v in valid.sum(1)]


def test_ties_go_to_the_lower_position():
    """Quantised scores: many equal values straddle the k-th place."""
    scores = jnp.round(jax.random.normal(jax.random.PRNGKey(5), (12, 96)))
    valid = jnp.ones((12, 96), bool)
    want = _reference_mask(scores, 20, valid)
    got = np.asarray(topk_mask(scores, 20, valid))
    assert (got == want).all()
    idx, chosen, _ = topk_indices(scores, 20, valid)
    assert (_as_mask(idx, chosen, 96) == want).all()
    # the rule itself, by hand: among equal scores the first ones win
    row = jnp.asarray([[1.0, 3.0, 1.0, 1.0, 2.0, 1.0]])
    assert topk_mask(row, 3, jnp.ones((1, 6), bool)).tolist() == [
        [True, True, False, False, True, False]]
    assert topk_mask(row, 4, jnp.ones((1, 6), bool)).tolist() == [
        [True, True, True, False, True, False]]


def test_zeros_of_either_sign_and_negative_scores_order_correctly():
    row = jnp.asarray([[-0.0, 0.0, -1.0, -2.5, 0.0, -1e-30, 1e-30, -3.0]])
    valid = jnp.ones((1, 8), bool)
    for k in range(1, 9):
        want = _reference_mask(row + 0.0, k, valid)
        assert (np.asarray(topk_mask(row + 0.0, k, valid)) == want).all(), k


@pytest.mark.parametrize("predecessors", [1, 3, 7])
def test_a_query_with_fewer_than_k_predecessors_attends_to_all(predecessors):
    scores = jax.random.normal(jax.random.PRNGKey(6), (1, 30))
    valid = jnp.arange(30)[None] < predecessors
    assert (np.asarray(topk_mask(scores, 8, valid))
            == np.asarray(valid)).all()
    idx, chosen, _ = topk_indices(scores, 8, valid)
    assert int(chosen.sum()) == predecessors
    assert sorted(np.asarray(idx)[0][np.asarray(chosen)[0]].tolist()) == \
        list(range(predecessors))


K_CHOSEN, WIDTH_CHOSEN = 16, 48


def _chosen_case_scores(kind, rng):
    if kind == "normal":
        return rng.standard_normal((4, WIDTH_CHOSEN)).astype(np.float32)
    if kind == "ties":  # few levels: equal scores straddle the k-th place
        return np.round(rng.standard_normal((4, WIDTH_CHOSEN))).astype(
            np.float32)
    return rng.choice(np.asarray([0.0, -0.0, 1.0, -1.0], np.float32),
                      (4, WIDTH_CHOSEN))


@pytest.mark.parametrize("kind", ["normal", "ties", "zeros"])
@pytest.mark.parametrize("layout", ["contiguous", "scattered"])
@pytest.mark.parametrize("count", [0, 1, K_CHOSEN - 1, K_CHOSEN,
                                   K_CHOSEN + 1, WIDTH_CHOSEN])
def test_chosen_is_the_gathered_validity_of_the_positions(count, layout,
                                                          kind):
    """``chosen``, counted from the row's valid count, equals the
    validity of each selected position gathered from ``valid``."""
    rng = np.random.default_rng(
        [count, ["contiguous", "scattered"].index(layout),
         ["normal", "ties", "zeros"].index(kind)])
    scores = _chosen_case_scores(kind, rng)
    valid = np.zeros((4, WIDTH_CHOSEN), bool)
    for r in range(4):
        at = (np.arange(count) if layout == "contiguous"
              else rng.permutation(WIDTH_CHOSEN)[:count])
        valid[r, at] = True
    idx, chosen, _ = jax.jit(topk_indices, static_argnums=1)(
        jnp.asarray(scores), K_CHOSEN, jnp.asarray(valid))
    gathered = np.take_along_axis(valid, np.asarray(idx), axis=-1)
    np.testing.assert_array_equal(np.asarray(chosen), gathered)
    assert np.asarray(chosen).sum(1).tolist() == [min(K_CHOSEN, count)] * 4


def test_topk_indices_gathers_nothing_at_the_decode_shape():
    """At the DeepSeek-V3.2 cell's decode shape (32 slots x 7,168
    positions, K = 2,048) the lowered selection holds no gather."""
    text = jax.jit(topk_indices, static_argnums=1).lower(
        jax.ShapeDtypeStruct((32, 7168), jnp.float32), 2048,
        jax.ShapeDtypeStruct((32, 7168), jnp.bool_)).as_text()
    assert "top_k" in text
    assert "gather" not in text


def test_topk_indices_gives_the_kth_largest_valid_score():
    """The third value is the k-th largest valid score of each row, -inf
    in a row with fewer than k valid entries."""
    scores = jnp.asarray([[5., 1., 4., 4., 2.], [3., 9., 8., 7., 6.]])
    valid = jnp.asarray([[True] * 5, [True, False, False, True, False]])
    _, _, kth = topk_indices(scores, 3, valid)
    np.testing.assert_array_equal(np.asarray(kth), [[4.], [-np.inf]])


def test_nothing_valid_selects_nothing():
    scores = jnp.ones((2, 10))
    valid = jnp.zeros((2, 10), bool)
    assert not np.asarray(topk_mask(scores, 4, valid)).any()
    assert not np.asarray(topk_indices(scores, 4, valid)[1]).any()


@pytest.mark.parametrize("n,block", [(8, 4), (12, 4), (5, 256)])
def test_index_scores_match_the_formula_blocked_or_not(n, block):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (n, 3, 16))
    w = jax.random.normal(ks[1], (n, 3))
    k = jax.random.normal(ks[2], (21, 16))
    want = jnp.sum(jax.nn.relu(jnp.einsum("nhd,sd->nhs", q, k))
                   * w[:, :, None], axis=1)
    np.testing.assert_allclose(
        np.asarray(index_scores(q, w, k, q_block=block)), np.asarray(want),
        atol=1e-5)
    batched = index_scores(q[:, None], w[:, None],
                           jnp.broadcast_to(k, (n, 21, 16)))[:, 0]
    np.testing.assert_allclose(np.asarray(batched), np.asarray(want),
                               atol=1e-5)
