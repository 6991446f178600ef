"""``HeldExpertsMLP``: the published router (choice on ``s + b``,
weights from ``s``, group-limited), the share of an expert-parallel
deployment, and no dropped token."""

import functools
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

from apex_tpu.transformer.moe import HeldExpertsMLP  # noqa: E402

H, F, E = 32, 16, 16
CFG = dict(num_experts_per_tok=4, n_group=4, topk_group=2,
           routed_scaling_factor=2.5)


def _layer(**kw):
    args = dict(top_k=4, n_group=4, topk_group=2, routed_scaling_factor=2.5,
                params_dtype=jnp.float32)
    args.update(kw)
    return HeldExpertsMLP(H, F, E, **args)


def _reference_weights(params):
    return dict(
        router_w=params["router"]["weight"], router_b=params["router"]["bias"],
        shared_gate=params["shared"]["w_gate"],
        shared_up=params["shared"]["w_up"],
        shared_down=params["shared"]["w_down"],
        experts_gate=params["experts"]["w_gate"],
        experts_up=params["experts"]["w_up"],
        experts_down=params["experts"]["w_down"])


def _shared(params, x):
    sh = params["shared"]
    return (jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])) @ sh["w_down"]


def _routed(layer, params, x, held, **kw):
    """The layer's output without the shared expert's (which ``apply``
    always adds), and its counters."""
    y, counted = layer.apply(params, x, held, **kw)
    return y - _shared(params, x), counted


def _router_from_scores(layer, s, b):
    """Router weights that give ``sigmoid(x W) = s`` for x = e_0."""
    logits = np.log(np.asarray(s) / (1 - np.asarray(s)))
    w = np.zeros((H, E), np.float32)
    w[0] = logits
    x = np.zeros((1, H), np.float32)
    x[0, 0] = 1.0
    params = {"router": {"weight": jnp.asarray(w),
                         "bias": jnp.asarray(b, jnp.float32)}}
    return params, jnp.asarray(x)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_route_matches_reference(seed):
    layer = _layer()
    params = layer.init(jax.random.PRNGKey(seed), E)
    x = jax.random.normal(jax.random.PRNGKey(seed + 10), (50, H))
    chosen, g = layer.route(params, x)
    want_chosen, want_g = ref.route(x, _reference_weights(params), CFG)
    assert (np.sort(chosen, 1) == np.sort(np.asarray(want_chosen), 1)).all()
    order, want_order = np.argsort(chosen, 1), np.argsort(want_chosen, 1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(g), order, 1),
        np.take_along_axis(np.asarray(want_g), want_order, 1), rtol=1e-5)


def test_choice_runs_on_biased_scores_and_weights_on_plain_scores():
    """Expert 3 has the lower score but the larger bias: it is chosen
    over expert 2, and its weight still comes from its plain score."""
    layer = _layer(top_k=1, n_group=1, topk_group=1)
    s = np.full(E, 0.1, np.float32)
    s[2], s[3] = 0.8, 0.6
    b = np.zeros(E, np.float32)
    b[3] = 0.5
    params, x = _router_from_scores(layer, s, b)
    chosen, g = layer.route(params, x)
    assert chosen.tolist() == [[3]]
    # one chosen expert: g = 2.5 * s / s, whatever the bias
    np.testing.assert_allclose(np.asarray(g), [[2.5]], rtol=1e-6)
    layer2 = _layer(top_k=2, n_group=1, topk_group=1)
    chosen, g = layer2.route(params, x)
    assert sorted(chosen[0].tolist()) == [2, 3]
    by_expert = dict(zip(chosen[0].tolist(), np.asarray(g)[0].tolist()))
    assert by_expert[3] == pytest.approx(2.5 * 0.6 / 1.4, rel=1e-5)
    assert by_expert[2] == pytest.approx(2.5 * 0.8 / 1.4, rel=1e-5)


def test_group_limit_differs_from_plain_top_k():
    """4 groups of 4, 2 kept, top-2.  Expert 0 has the single largest
    score but its group's two best add up to less than two other
    groups': plain top-k would take it, the published rule may not."""
    layer = _layer(top_k=2, n_group=4, topk_group=2)
    s = np.full(E, 0.05, np.float32)
    s[0], s[1] = 0.9, 0.05            # group 0: 0.95
    s[4], s[5] = 0.6, 0.58            # group 1: 1.18
    s[8], s[9] = 0.55, 0.5            # group 2: 1.05
    params, x = _router_from_scores(layer, s, np.zeros(E))
    chosen, g = layer.route(params, x)
    assert sorted(chosen[0].tolist()) == [4, 5]
    assert 0 in np.argsort(-s)[:2]    # what plain top-k would have chosen
    np.testing.assert_allclose(np.sort(np.asarray(g)[0]),
                               np.sort(2.5 * s[[4, 5]] / (s[4] + s[5])),
                               rtol=1e-5)


@pytest.mark.parametrize("tile_rows", [4, 16])
@pytest.mark.parametrize("partition", [
    [tuple(range(16))],
    [tuple(range(0, 8)), tuple(range(8, 16))],
    [(0, 5, 9, 14), (1, 2, 3, 4), (6, 7, 8, 15), (10, 11, 12, 13)],
])
def test_shares_of_a_layer_add_up_to_the_uncut_reference(partition, tile_rows):
    """The outputs of all the shares of one expert layer (every ``held``
    of a partition of the experts), the shared expert counted once, are
    what the uncut reference gives for the whole layer."""
    layer = _layer()
    full = layer.init(jax.random.PRNGKey(3), E)
    x = jax.random.normal(jax.random.PRNGKey(4), (37, H))
    want = ref.moe(x, _reference_weights(full), CFG, tuple(range(E)))
    total = 0.0
    choices = held = 0.0
    for i, share in enumerate(partition):
        part = dict(full, experts=jax.tree.map(
            lambda w: w[jnp.asarray(share)], full["experts"]))
        # every share adds the shared expert: count it once
        y, counted = (layer.apply if i == 0 else functools.partial(
            _routed, layer))(part, x, share, tile_rows=tile_rows)
        total = total + y
        choices, held = float(counted[0]), held + float(counted[1])
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=0)
    assert choices == 37 * 4 and held == 37 * 4     # every choice, once


@pytest.mark.parametrize("n", [5, 64, 200])
def test_no_token_is_dropped_under_a_routing_skewed_onto_one_expert(n):
    layer = _layer()
    params = layer.init(jax.random.PRNGKey(5), 2)
    bias = np.zeros(E, np.float32)
    bias[6] = 10.0                      # every token chooses expert 6
    params["router"]["bias"] = jnp.asarray(bias)
    x = jax.random.normal(jax.random.PRNGKey(6), (n, H))
    y, counted = _routed(layer, params, x, (6, 9), tile_rows=16)
    chosen, g = layer.route(params, x)
    assert (np.asarray(chosen) == 6).any(axis=1).all()
    want = 0.0
    for i, e in enumerate((6, 9)):
        gate = jnp.sum(jnp.where(chosen == e, g, 0.0), -1, keepdims=True)
        ex = jax.tree.map(lambda w: w[i], params["experts"])
        want = want + gate * (
            (jax.nn.silu(x @ ex["w_gate"]) * (x @ ex["w_up"])) @ ex["w_down"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    assert float(counted[3]) == n                   # expert 6's load
    assert float(counted[1]) >= n
    assert float(jnp.min(jnp.sum(jnp.abs(y), axis=1))) > 0


def test_padding_rows_route_nothing_and_count_nothing():
    layer = _layer()
    params = layer.init(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(jax.random.PRNGKey(8), (12, H))
    valid = jnp.arange(12) < 7
    y, counted = _routed(layer, params, x, (0, 1, 2, 3), token_valid=valid)
    y7, counted7 = _routed(layer, params, x[:7], (0, 1, 2, 3))
    np.testing.assert_allclose(np.asarray(y[:7]), np.asarray(y7), atol=1e-6)
    np.testing.assert_allclose(np.asarray(y[7:]), 0.0, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(counted), np.asarray(counted7))


def test_stacked_experts_are_indexed_by_layer():
    layer = _layer()
    a = layer.init(jax.random.PRNGKey(9), 4)
    b = layer.init(jax.random.PRNGKey(10), 4)
    x = jax.random.normal(jax.random.PRNGKey(11), (9, H))
    stacked = dict(b, experts=jax.tree.map(
        lambda u, v: jnp.stack([u, v]), a["experts"], b["experts"]))
    want, _ = layer.apply(b, x, (2, 3, 5, 7))
    got, _ = jax.jit(lambda p, x, j: layer.apply(
        p, x, (2, 3, 5, 7), expert_layer=j))(stacked, x, jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_arguments_are_checked():
    with pytest.raises(ValueError, match="n_group"):
        HeldExpertsMLP(H, F, 10, top_k=2, n_group=4, topk_group=2)
    with pytest.raises(ValueError, match="top_k"):
        HeldExpertsMLP(H, F, E, top_k=9, n_group=4, topk_group=2)
    layer = _layer()
    params = layer.init(jax.random.PRNGKey(0), 4)
    with pytest.raises(ValueError, match="held names 3 experts"):
        layer.apply(params, jnp.zeros((2, H)), (0, 1, 2))
