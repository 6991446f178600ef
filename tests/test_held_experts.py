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


# ---------------------------------------------------------------------------
# the grouped product (``ops/moe_grouped.py``) beside the tile loop
# ---------------------------------------------------------------------------
def _bf16_layer():
    return _layer(params_dtype=jnp.bfloat16)


def _stacked(layer, seeds, held):
    """One set of parameters whose experts stack a layer of each seed."""
    each = [layer.init(jax.random.PRNGKey(s), held) for s in seeds]
    return dict(each[-1], experts=jax.tree.map(
        lambda *w: jnp.stack(w), *(p["experts"] for p in each)))


def _pairs(sizes, n, k, not_held):
    """(n, k) choices that give held expert ``i`` ``sizes[i]`` pairs, in
    a shuffled order; the rest land on ``not_held``."""
    flat = np.concatenate([np.full(s, i) for i, s in enumerate(sizes)]
                          + [np.full(n * k - sum(sizes), not_held)])
    return jnp.asarray(np.random.default_rng(0).permutation(flat).reshape(
        n, k).astype(np.int32))


def _both_forms(layer, experts, x, chosen, held, valid, tile_rows,
                index=None):
    g = jax.random.uniform(jax.random.PRNGKey(2), chosen.shape)
    return [layer._experts(experts, x.astype(jnp.bfloat16), chosen, g, held,
                           valid, tile_rows, index, grouped)
            for grouped in (False, True)]


def _assert_same_bits(loop, grouped):
    for a, b in zip(loop, grouped):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("sizes", [
    (3, 150, 2, 5),          # one expert owns most rows: many tiles of it
    (16, 0, 5, 8, 0, 11),    # experts with no rows; whole and cut tiles
    (0, 0, 0, 7),            # tile 0 is the LAST expert's
    (0, 0, 0),               # no pair is held: one tile of zeros runs
    (8, 8, 8, 8),            # every tile whole
], ids=["skewed", "empty-and-ragged", "first-empty", "none-held",
        "whole-tiles"])
def test_grouped_equals_the_loop_bit_for_bit_on_given_sizes(sizes):
    """The same sorted layout through ONE Mosaic call (interpret mode
    here) and through the loop over tiles: float32-accumulated bfloat16
    products of the same rows, so the same bits, whatever the experts'
    loads are."""
    layer, n, k = _bf16_layer(), 48, 4
    held = tuple(range(1, 2 * len(sizes), 2))
    experts = layer.init(jax.random.PRNGKey(1), len(sizes))["experts"]
    lookup = np.asarray(held + (0,))         # expert 0 is not held
    chosen = jnp.asarray(lookup)[_pairs(sizes, n, k, len(sizes))]
    x = jax.random.normal(jax.random.PRNGKey(3), (n, H))
    loop, grouped = _both_forms(layer, experts, x, chosen, held,
                                jnp.ones((n,), bool), 8)
    _assert_same_bits(loop, grouped)
    assert np.asarray(loop[1])[4:].tolist() == list(map(float, sizes))
    assert np.isfinite(np.asarray(grouped[0])).all()
    assert (np.abs(np.asarray(grouped[0])).sum() > 0) == (sum(sizes) > 0)


def _forced(monkeypatch):
    """form -> a context in which ``apply`` takes it whatever the call's
    shape: ``apply`` has no argument for it, it reads the platform and
    compares the rows an expert expects with ``GROUPED_MIN_ROWS``."""
    from apex_tpu.transformer import moe

    def force(form):
        monkeypatch.setattr(moe, "default_implementation", lambda: form)
        monkeypatch.setattr(moe, "GROUPED_MIN_ROWS", 0)
    return force


@pytest.mark.parametrize("how", ["padding-rows", "stacked-layer",
                                 "partition"])
def test_grouped_equals_the_loop_bit_for_bit_through_apply(how, monkeypatch):
    """``apply`` with the form forced: padding rows route and count
    nothing, a traced ``expert_layer`` indexes the stack inside the
    kernel's block index, and the shares of a partition of the experts
    still add up to the uncut layer — each in the loop's bits, with the
    loop's counters."""
    layer = _bf16_layer()
    x = jax.random.normal(jax.random.PRNGKey(12), (50, H))
    force = _forced(monkeypatch)

    def both(run):
        out = []
        for form in ("xla", "pallas"):
            force(form)
            out.append(run())
        return out                      # the grouped form stays forced

    if how == "padding-rows":
        params = layer.init(jax.random.PRNGKey(7), 4)
        valid = jnp.arange(50) < 29
        loop, grouped = both(lambda: layer.apply(
            params, x, (0, 1, 2, 3), token_valid=valid, tile_rows=8))
        y29, counted29 = layer.apply(params, x[:29], (0, 1, 2, 3),
                                     tile_rows=8)
        # the shared expert's rows are the padding rows' only output
        assert np.array_equal(np.asarray(grouped[0][:29]), np.asarray(y29))
        assert np.array_equal(np.asarray(grouped[1]), np.asarray(counted29))
    elif how == "stacked-layer":
        params = _stacked(layer, (9, 10, 11), 4)
        loop, grouped = both(lambda: jax.jit(lambda p, x, j: layer.apply(
            p, x, (2, 3, 5, 7), expert_layer=j, tile_rows=8))(
                params, x, jnp.int32(1)))
        one = dict(params, experts=jax.tree.map(
            lambda w: w[1], params["experts"]))
        alone = layer.apply(one, x, (2, 3, 5, 7), tile_rows=8)
        _assert_same_bits(alone, grouped)
    else:
        full = layer.init(jax.random.PRNGKey(3), E)
        shares = [(0, 5, 9, 14), (1, 2, 3, 4), (6, 7, 8, 15),
                  (10, 11, 12, 13)]
        part = lambda share: dict(full, experts=jax.tree.map(
            lambda w: w[jnp.asarray(share)], full["experts"]))
        per_form = both(lambda: [
            _routed(layer, part(share), x, share, tile_rows=8)
            for share in shares])
        for a, b in zip(*per_form):
            _assert_same_bits(a, b)
        total = sum(y.astype(jnp.float32) for y, _ in per_form[1])
        uncut, counted = _routed(layer, full, x, tuple(range(E)),
                                 tile_rows=8)
        # bfloat16 outputs: a share's rounding each
        np.testing.assert_allclose(
            np.asarray(total), np.asarray(uncut, np.float32), atol=0.1)
        assert sum(float(c[1]) for _, c in per_form[1]) == float(
            counted[1]) == 50 * 4
        return
    _assert_same_bits(loop, grouped)
    # the forced call did go through the kernels
    names = _kernel_names(jax.make_jaxpr(lambda: layer.apply(
        layer.init(jax.random.PRNGKey(7), 4), x, (0, 1, 2, 3),
        tile_rows=8))().jaxpr)
    assert names == ["tlm.kernel.moe_grouped.gate_up",
                     "tlm.kernel.moe_grouped.down"]


def _kernel_names(jaxpr) -> list:
    """Names of the ``pallas_call`` equations of a jaxpr, at any depth."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _kernel_names(sub)
    return names


# Xing4's widths and choices; tokens a call, dtype, grouped on a TPU?
_CALLS = {
    "xing4-chunk": (4096, jnp.bfloat16, True),            # 256 rows an expert
    "xing4-chunk-float32": (4096, jnp.float32, False),
    "a-tile-an-expert-chunk": (2048, jnp.bfloat16, True),        # 128 rows
    "under-a-tile-an-expert-chunk": (2032, jnp.bfloat16, False),  # 127 rows
    "decode-step": (16, jnp.bfloat16, False),
}


@pytest.mark.parametrize("call", list(_CALLS))
def test_which_calls_lower_to_the_grouped_product(call, monkeypatch):
    """Adapting on what the call sees, no knob: on a TPU the bfloat16
    chunk whose experts expect a whole 128-row tile or more (n * top_k
    // num_experts >= ``GROUPED_MIN_ROWS``) lowers to the two
    ``moe_grouped`` kernels; a chunk below that (DeepSeek-V3.2's expects
    64 rows an expert), a decode step and every float32 call (the
    references) lower to the loop as they did; and off the TPU
    everything does."""
    from apex_tpu.transformer import moe

    n, dtype, grouped = _CALLS[call]
    layer = HeldExpertsMLP(3584, 1024, 64, top_k=4, params_dtype=dtype)
    params = jax.eval_shape(lambda: _stacked(layer, (0, 1), 64))
    trace = lambda: _kernel_names(jax.make_jaxpr(
        lambda p, x, j: layer.apply(p, x, tuple(range(64)), expert_layer=j)
    )(params, jax.ShapeDtypeStruct((n, 3584), dtype),
      jax.ShapeDtypeStruct((), jnp.int32)).jaxpr)
    assert trace() == []                      # this process: no TPU
    monkeypatch.setattr(moe, "default_implementation", lambda: "pallas")
    assert trace() == (["tlm.kernel.moe_grouped.gate_up",
                        "tlm.kernel.moe_grouped.down"] if grouped else [])


def test_the_grouped_product_lowers_to_a_few_equations():
    """ROADMAP S10: a kernel costs set-up what is lowered for it, in
    each of a cell's chunk programs.  Both kernels (one body each: a
    tile's products and the three places a run's copies are started or
    waited for), their index maps, the plan of runs and the jitted call
    around them, at Xing4's chunk: 145 equations, about the flash
    forward these programs also hold (135)."""
    from test_attention_decode_grouped import _equations

    from apex_tpu.ops.moe_grouped import grouped_swiglu

    sds = jax.ShapeDtypeStruct
    tiles, T, h, f = 192, 128, 3584, 1024
    w = lambda c, width: sds((5, 64, c, width), jnp.bfloat16)
    count = _equations(jax.make_jaxpr(grouped_swiglu)(
        sds((tiles * T, h), jnp.bfloat16), w(h, f), w(h, f), w(f, h),
        sds((tiles,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32)))
    assert count <= 150, count
