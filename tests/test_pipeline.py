"""Pipeline-parallel schedule tests on the 8-device virtual CPU mesh.

Philosophy (SURVEY.md §4): the reference tests its schedules with a tiny
linear model and analytic/serial expectations
(tests/L0/run_transformer/run_pipeline_parallel_test.py); here the
compiled pp=4 pipeline (and its autodiff backward) is compared against
the identical serial computation on one device.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.pipeline_parallel import (
    ConstantNumMicroBatches,
    RampupBatchsizeNumMicroBatches,
    build_num_microbatches_calculator,
    forward_backward_no_pipelining,
    get_forward_backward_func,
    pipeline,
    pipeline_stage_specs,
)

NUM_LAYERS = 4
HIDDEN = 16
MICRO = 8  # microbatches
MB = 2     # rows per microbatch (per dp shard)


def make_params(key):
    """Stacked dense layers: (L, h, h) weights + (L, h) biases."""
    kw, kb = jax.random.split(key)
    return {
        "w": 0.3 * jax.random.normal(kw, (NUM_LAYERS, HIDDEN, HIDDEN)),
        "b": 0.01 * jax.random.normal(kb, (NUM_LAYERS, HIDDEN)),
    }


def serial_loss(params, x, y):
    """Dense single-device reference: all layers, full batch, MSE."""
    h = x
    for l in range(NUM_LAYERS):
        h = jnp.tanh(h @ params["w"][l] + params["b"][l])
    return jnp.mean((h - y) ** 2)


def _stage_scan(local_params, x):
    def body(h, lp):
        return jnp.tanh(h @ lp["w"] + lp["b"]), None

    out, _ = jax.lax.scan(body, x, local_params)
    return out


@pytest.mark.parametrize("remat", [False, True])
def test_pipeline_matches_serial(remat):
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=4
    )
    try:
        params = make_params(jax.random.PRNGKey(0))
        layer_specs = {"w": P(None, None, None), "b": P(None, None)}
        stage_specs = pipeline_stage_specs(layer_specs)
        dp = mesh.shape["dp"]
        x = jax.random.normal(jax.random.PRNGKey(1), (MICRO * MB * dp, HIDDEN))
        y = jax.random.normal(jax.random.PRNGKey(2), (MICRO * MB * dp, HIDDEN))

        def pp_loss(params, x, y):
            # local dp shard → microbatches
            mbs = {
                "x": x.reshape(MICRO, MB, HIDDEN),
                "y": y.reshape(MICRO, MB, HIDDEN),
            }
            per_micro = pipeline(
                first_fn=lambda mb: mb["x"],
                stage_fn=lambda h: _stage_scan(params, h),
                last_fn=lambda h, mb: jnp.mean((h - mb["y"]) ** 2),
                microbatches=mbs,
                remat=remat,
            )
            return jax.lax.pmean(jnp.mean(per_micro), "dp")

        grad_fn = jax.jit(
            jax.shard_map(
                jax.value_and_grad(pp_loss),
                mesh=mesh,
                in_specs=(stage_specs, P("dp"), P("dp")),
                out_specs=(P(), stage_specs),
            )
        )
        placed = jax.device_put(
            params,
            jax.tree.map(lambda s: NamedSharding(mesh, s), stage_specs,
                         is_leaf=lambda x: isinstance(x, P)),
        )
        loss, grads = grad_fn(placed, x, y)

        ref_loss, ref_grads = jax.value_and_grad(serial_loss)(params, x, y)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )
    finally:
        parallel_state.destroy_model_parallel()


@pytest.mark.parametrize("num_chunks", [2, 4])
def test_interleaved_pipeline_matches_serial(num_chunks):
    """pp=4 x V chunks circular schedule == serial dense math, fwd+grads.
    Layers are assigned chunk-major: chunk v holds layers
    [v*pp*Lc + p*Lc, ...) — i.e. the stacked dim is reshaped
    (V, pp, Lc) so global stage v*pp+p gets its contiguous slice."""
    pp = 4
    per_chunk = 2 if num_chunks == 2 else 1
    NUM_L = pp * num_chunks * per_chunk
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=4
    )
    try:
        kw, kb = jax.random.split(jax.random.PRNGKey(0))
        params = {
            "w": 0.3 * jax.random.normal(kw, (NUM_L, HIDDEN, HIDDEN)),
            "b": 0.01 * jax.random.normal(kb, (NUM_L, HIDDEN)),
        }

        def serial(params, x, y):
            h = x
            for l in range(NUM_L):
                h = jnp.tanh(h @ params["w"][l] + params["b"][l])
            return jnp.mean((h - y) ** 2)

        # chunk-major layout: (L,) → (V, pp, per_chunk) → shard dim 1
        def to_stages(p):
            return jax.tree.map(
                lambda a: a.reshape(
                    (num_chunks, pp, per_chunk) + a.shape[1:]
                ),
                p,
            )

        stage_specs = {
            "w": P(None, "pp", None, None, None),
            "b": P(None, "pp", None, None),
        }
        dp = mesh.shape["dp"]
        x = jax.random.normal(jax.random.PRNGKey(1), (MICRO * MB * dp, HIDDEN))
        y = jax.random.normal(jax.random.PRNGKey(2), (MICRO * MB * dp, HIDDEN))

        from apex_tpu.transformer.pipeline_parallel import (
            forward_backward_pipelining_with_interleaving,
        )

        def pp_loss(sp, x, y):
            # sp leaves: (V, 1, per_chunk, ...) local → (V, per_chunk, ...)
            sp = jax.tree.map(lambda a: a[:, 0], sp)
            mbs = {
                "x": x.reshape(MICRO, MB, HIDDEN),
                "y": y.reshape(MICRO, MB, HIDDEN),
            }

            def chunk_fn(h, v):
                lp = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, v, 0, keepdims=False
                    ),
                    sp,
                )
                return _stage_scan(lp, h)

            per_micro = forward_backward_pipelining_with_interleaving(
                first_fn=lambda mb: mb["x"],
                chunk_fn=chunk_fn,
                last_fn=lambda h, mb: jnp.mean((h - mb["y"]) ** 2),
                microbatches=mbs,
                num_model_chunks=num_chunks,
            )
            return jax.lax.pmean(jnp.mean(per_micro), "dp")

        grad_fn = jax.jit(
            jax.shard_map(
                jax.value_and_grad(pp_loss),
                mesh=mesh,
                in_specs=(stage_specs, P("dp"), P("dp")),
                out_specs=(P(), stage_specs),
            )
        )
        staged = to_stages(params)
        placed = jax.device_put(
            staged,
            jax.tree.map(lambda s: NamedSharding(mesh, s), stage_specs,
                         is_leaf=lambda x: isinstance(x, P)),
        )
        loss, grads = grad_fn(placed, x, y)
        ref_loss, ref_grads = jax.value_and_grad(serial)(params, x, y)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        got = jax.tree.map(
            lambda a: np.asarray(a).reshape((NUM_L,) + a.shape[3:]),
            jax.device_get(grads),
        )
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(a, np.asarray(b), rtol=1e-4,
                                       atol=1e-6)
    finally:
        parallel_state.destroy_model_parallel()


def test_interleaved_requires_divisible_microbatches():
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=4
    )
    try:
        from apex_tpu.transformer.pipeline_parallel import (
            forward_backward_pipelining_with_interleaving,
        )

        def run(x):
            return forward_backward_pipelining_with_interleaving(
                first_fn=lambda mb: mb,
                chunk_fn=lambda h, v: h,
                last_fn=lambda h, mb: jnp.mean(h),
                microbatches=x,
                num_model_chunks=2,
            )

        with pytest.raises(ValueError, match="not divisible"):
            jax.jit(
                jax.shard_map(
                    run, mesh=mesh, in_specs=(P(),), out_specs=P()
                )
            )(jnp.ones((6, 2, HIDDEN)))  # 6 % 4 != 0
    finally:
        parallel_state.destroy_model_parallel()


def test_no_pipelining_matches_serial():
    mesh = parallel_state.initialize_model_parallel()
    try:
        params = make_params(jax.random.PRNGKey(0))
        dp = mesh.shape["dp"]
        x = jax.random.normal(jax.random.PRNGKey(1), (MICRO * MB * dp, HIDDEN))
        y = jax.random.normal(jax.random.PRNGKey(2), (MICRO * MB * dp, HIDDEN))

        def loss_fn(params, x, y):
            mbs = {
                "x": x.reshape(MICRO, MB, HIDDEN),
                "y": y.reshape(MICRO, MB, HIDDEN),
            }
            per_micro = forward_backward_no_pipelining(
                first_fn=lambda mb: mb["x"],
                stage_fn=lambda h: _stage_scan(params, h),
                last_fn=lambda h, mb: jnp.mean((h - mb["y"]) ** 2),
                microbatches=mbs,
            )
            return jax.lax.pmean(jnp.mean(per_micro), "dp")

        specs = {"w": P(), "b": P()}
        grad_fn = jax.jit(
            jax.shard_map(
                jax.value_and_grad(loss_fn),
                mesh=mesh,
                in_specs=(specs, P("dp"), P("dp")),
                out_specs=(P(), specs),
            )
        )
        loss, grads = grad_fn(params, x, y)
        ref_loss, ref_grads = jax.value_and_grad(serial_loss)(params, x, y)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )
    finally:
        parallel_state.destroy_model_parallel()


def test_get_forward_backward_func_dispatch():
    # pp>1 dispatches the 1F1B family, never the forward-only schedules
    assert (
        get_forward_backward_func(None, 4)
        is not forward_backward_no_pipelining
    )
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        _fwd_bwd_no_pipelining,
    )

    assert get_forward_backward_func(None, 1) is _fwd_bwd_no_pipelining


class TestMicrobatchCalculators:
    def test_constant(self):
        calc = build_num_microbatches_calculator(64, 4, 2)
        assert isinstance(calc, ConstantNumMicroBatches)
        assert calc.get() == 8
        assert calc.get_current_global_batch_size() == 64
        calc.update(10_000)
        assert calc.get() == 8

    def test_constant_indivisible_raises(self):
        with pytest.raises(ValueError):
            ConstantNumMicroBatches(10, 4, 2)

    def test_rampup(self):
        calc = build_num_microbatches_calculator(
            64, 4, 2, rampup_batch_size=[8, 8, 700]
        )
        assert isinstance(calc, RampupBatchsizeNumMicroBatches)
        assert calc.get_current_global_batch_size() == 8
        assert calc.get() == 1
        calc.update(100)  # one increment per 100 samples
        assert calc.get_current_global_batch_size() == 16
        calc.update(700)
        assert calc.get_current_global_batch_size() == 64
        calc.update(10_000)
        assert calc.get_current_global_batch_size() == 64
        assert calc.get() == 8

    def test_rampup_bad_increment(self):
        with pytest.raises(ValueError):
            build_num_microbatches_calculator(
                64, 4, 2, rampup_batch_size=[8, 9, 700]
            )


def test_lm_head_runs_once_per_microbatch():
    """The pipeline exit (head + loss) must execute exactly num_micro
    times per device, not once per tick (the old
    schedule paid (num_micro+pp-1) head applications).  Executions are
    counted with a host callback on the virtual mesh."""
    pp_size = 4
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=pp_size
    )
    try:
        params = make_params(jax.random.PRNGKey(0))
        stage_specs = pipeline_stage_specs(
            {"w": P(None, None, None), "b": P(None, None)}
        )
        x = jnp.ones((MICRO, MB, HIDDEN))
        count = [0]

        def cb():
            count[0] += 1
            return jnp.int32(0)

        def loss(params, x):
            def last_fn(h, mb):
                tok = jax.experimental.io_callback(
                    cb, jax.ShapeDtypeStruct((), jnp.int32)
                )
                return jnp.sum(h) + 0.0 * tok

            return jnp.mean(pipeline(
                first_fn=lambda mb: mb,
                stage_fn=lambda h: _stage_scan(params, h),
                last_fn=last_fn,
                microbatches=x,
                remat=False,
            ))

        f = jax.jit(jax.shard_map(
            loss, mesh=mesh, in_specs=(stage_specs, P()), out_specs=P()
        ))
        jax.block_until_ready(f(params, x))
        n_dev = len(mesh.devices.flatten())
        per_device = count[0] / n_dev
        assert per_device == MICRO, (
            f"head executed {per_device}x per device, expected {MICRO} "
            f"(old tax: {MICRO + pp_size - 1})"
        )
    finally:
        parallel_state.destroy_model_parallel()


@pytest.mark.parametrize("micro", [1, 2, 4, 8])
def test_1f1b_matches_serial(micro):
    """True 1F1B (fwd/bwd interleaved in one scan, O(pp) activation
    state) == serial dense math, losses and grads (reference:
    fwd_bwd_pipelining_without_interleaving.py:112-149 steady state).
    micro < pp (1, 2) exercises the pure-bubble regime."""
    from apex_tpu.transformer.pipeline_parallel import pipeline_1f1b

    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=4
    )
    try:
        params = make_params(jax.random.PRNGKey(0))
        layer_specs = {"w": P(None, None, None), "b": P(None, None)}
        stage_specs = pipeline_stage_specs(layer_specs)
        dp = mesh.shape["dp"]
        x = jax.random.normal(jax.random.PRNGKey(1), (micro * MB * dp, HIDDEN))
        y = jax.random.normal(jax.random.PRNGKey(2), (micro * MB * dp, HIDDEN))

        def fb(params, x, y):
            mbs = {
                "x": x.reshape(micro, MB, HIDDEN),
                "y": y.reshape(micro, MB, HIDDEN),
            }
            losses, grads = pipeline_1f1b(
                first_fn=lambda prm, mb: mb["x"],
                stage_fn=lambda prm, h: _stage_scan(prm, h),
                last_fn=lambda prm, h, mb: jnp.mean((h - mb["y"]) ** 2),
                params=params,
                microbatches=mbs,
            )
            # mean over microbatches and dp, like the GPipe-path test
            loss = jax.lax.pmean(jnp.mean(losses), "dp")
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
            return loss, grads

        fb_fn = jax.jit(
            jax.shard_map(
                fb, mesh=mesh,
                in_specs=(stage_specs, P("dp"), P("dp")),
                out_specs=(P(), stage_specs),
            )
        )
        placed = jax.device_put(
            params,
            jax.tree.map(lambda s: NamedSharding(mesh, s), stage_specs,
                         is_leaf=lambda x: isinstance(x, P)),
        )
        loss, grads = fb_fn(placed, x, y)

        ref_loss, ref_grads = jax.value_and_grad(serial_loss)(params, x, y)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )
    finally:
        parallel_state.destroy_model_parallel()


@pytest.mark.parametrize("V,micro", [(2, 4), (2, 8), (3, 4), (3, 8)])
def test_1f1b_interleaved_matches_serial(V, micro):
    """Interleaved 1F1B (V chunks/rank, fwd+bwd in one scan, O(pp·V)
    activation state) == serial dense math, losses and grads
    (reference: fwd_bwd_pipelining_with_interleaving.py:22-308).
    micro ∈ {pp, 2pp} covers the minimum and a multi-group schedule."""
    from apex_tpu.transformer.pipeline_parallel import (
        pipeline_1f1b_interleaved,
    )

    pp_size = 4
    L = V * pp_size  # one layer per (chunk, rank) global stage
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=pp_size
    )
    try:
        kw, kb = jax.random.split(jax.random.PRNGKey(0))
        params = {
            "w": 0.3 * jax.random.normal(kw, (V, pp_size, HIDDEN, HIDDEN)),
            "b": 0.01 * jax.random.normal(kb, (V, pp_size, HIDDEN)),
        }
        # chunk v of rank p is global stage v*pp + p → shard axis 1
        stage_specs = {"w": P(None, "pp", None, None), "b": P(None, "pp", None)}
        dp = mesh.shape["dp"]
        x = jax.random.normal(jax.random.PRNGKey(1), (micro * MB * dp, HIDDEN))
        y = jax.random.normal(jax.random.PRNGKey(2), (micro * MB * dp, HIDDEN))

        def serial(params, x, y):
            h = x
            for v in range(V):
                for p in range(pp_size):
                    h = jnp.tanh(h @ params["w"][v, p] + params["b"][v, p])
            return jnp.mean((h - y) ** 2)

        def fb(params, x, y):
            mbs = {
                "x": x.reshape(micro, MB, HIDDEN),
                "y": y.reshape(micro, MB, HIDDEN),
            }

            def chunk_fn(prm, h, v):
                w = jax.lax.dynamic_index_in_dim(prm["w"], v, 0, False)[0]
                b = jax.lax.dynamic_index_in_dim(prm["b"], v, 0, False)[0]
                return jnp.tanh(h @ w + b)

            losses, grads = pipeline_1f1b_interleaved(
                first_fn=lambda prm, mb: mb["x"],
                chunk_fn=chunk_fn,
                last_fn=lambda prm, h, mb: jnp.mean((h - mb["y"]) ** 2),
                params=params,
                microbatches=mbs,
                num_model_chunks=V,
            )
            loss = jax.lax.pmean(jnp.mean(losses), "dp")
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
            return loss, grads

        fb_fn = jax.jit(
            jax.shard_map(
                fb, mesh=mesh,
                in_specs=(stage_specs, P("dp"), P("dp")),
                out_specs=(P(), stage_specs),
            )
        )
        placed = jax.device_put(
            params,
            jax.tree.map(lambda s: NamedSharding(mesh, s), stage_specs,
                         is_leaf=lambda x: isinstance(x, P)),
        )
        loss, grads = fb_fn(placed, x, y)

        ref_loss, ref_grads = jax.value_and_grad(serial)(params, x, y)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )
    finally:
        parallel_state.destroy_model_parallel()


def test_1f1b_interleaved_rejects_indivisible_micro():
    from apex_tpu.transformer.pipeline_parallel import (
        pipeline_1f1b_interleaved,
    )

    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=4
    )
    try:
        params = {"w": jnp.zeros((2, 4, HIDDEN, HIDDEN))}
        with pytest.raises(ValueError, match="not divisible"):
            jax.shard_map(
                lambda prm, mbs: pipeline_1f1b_interleaved(
                    lambda p_, m: m, lambda p_, h, v: h,
                    lambda p_, h, m: jnp.sum(h),
                    prm, mbs, num_model_chunks=2,
                ),
                mesh=mesh,
                in_specs=({"w": P(None, "pp", None, None)}, P()),
                out_specs=(P(), {"w": P(None, "pp", None, None)}),
            )(params, jnp.ones((6, MB, HIDDEN)))
    finally:
        parallel_state.destroy_model_parallel()


def test_dispatcher_returns_1f1b_family():
    """get_forward_backward_func hands out the production fwd+bwd
    schedules — 1F1B for pp>1, interleaved 1F1B with virtual stages,
    the sequential (losses, grads) wrapper for pp=1 (reference:
    schedules/__init__.py:1-39 always returns a forward-backward
    function)."""
    import functools

    from apex_tpu.transformer.pipeline_parallel import (
        get_forward_backward_func,
        pipeline_1f1b,
        pipeline_1f1b_interleaved,
    )

    fn = get_forward_backward_func(pipeline_model_parallel_size=4)
    assert fn is pipeline_1f1b
    fn = get_forward_backward_func(
        virtual_pipeline_model_parallel_size=2,
        pipeline_model_parallel_size=4,
    )
    assert isinstance(fn, functools.partial)
    assert fn.func is pipeline_1f1b_interleaved
    assert fn.keywords == {"num_model_chunks": 2}


def test_dispatcher_no_pipelining_losses_grads():
    """The pp=1 dispatch obeys the same (losses, grads) contract."""
    from apex_tpu.transformer.pipeline_parallel import (
        get_forward_backward_func,
    )

    fn = get_forward_backward_func(pipeline_model_parallel_size=1)
    params = make_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (MICRO, MB, HIDDEN))
    y = jax.random.normal(jax.random.PRNGKey(2), (MICRO, MB, HIDDEN))
    losses, grads = fn(
        lambda prm, mb: mb["x"],
        lambda prm, h: _stage_scan(prm, h),
        lambda prm, h, mb: jnp.mean((h - mb["y"]) ** 2),
        params,
        {"x": x, "y": y},
    )
    ref_loss, ref_grads = jax.value_and_grad(serial_loss)(
        params, x.reshape(-1, HIDDEN), y.reshape(-1, HIDDEN)
    )
    np.testing.assert_allclose(
        float(jnp.mean(losses)), float(ref_loss), rtol=1e-5
    )
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )


def test_dispatcher_no_pipelining_dp_convention():
    """On a dp>1 mesh the pp=1 dispatch returns shard-local grads (the
    1F1B family's convention): caller pmean over dp == true gradient of
    the reported dp-mean loss (regression: without the data-axis cast,
    autodiff psums over dp and the dispatched grads come out dp× too
    large)."""
    from apex_tpu.transformer.pipeline_parallel import (
        get_forward_backward_func,
    )

    mesh = parallel_state.initialize_model_parallel()
    try:
        dp = mesh.shape["dp"]
        params = make_params(jax.random.PRNGKey(0))
        x = jax.random.normal(
            jax.random.PRNGKey(1), (2 * MB * dp, HIDDEN))
        y = jax.random.normal(
            jax.random.PRNGKey(2), (2 * MB * dp, HIDDEN))

        def fb(params, x, y):
            fn = get_forward_backward_func(pipeline_model_parallel_size=1)
            losses, grads = fn(
                lambda prm, mb: mb["x"],
                lambda prm, h: _stage_scan(prm, h),
                lambda prm, h, mb: jnp.mean((h - mb["y"]) ** 2),
                params,
                {"x": x.reshape(2, MB, HIDDEN),
                 "y": y.reshape(2, MB, HIDDEN)},
            )
            loss = jax.lax.pmean(jnp.mean(losses), "dp")
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
            return loss, grads

        specs = {"w": P(None, None, None), "b": P(None, None)}
        loss, grads = jax.jit(jax.shard_map(
            fb, mesh=mesh, in_specs=(specs, P("dp"), P("dp")),
            out_specs=(P(), specs),
        ))(params, x, y)
        ref_loss, ref_grads = jax.value_and_grad(serial_loss)(params, x, y)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
            )
    finally:
        parallel_state.destroy_model_parallel()


def test_dispatcher_rejects_virtual_without_pp():
    from apex_tpu.transformer.pipeline_parallel import (
        get_forward_backward_func,
    )

    with pytest.raises(ValueError, match="pipeline_model_parallel_size"):
        get_forward_backward_func(
            virtual_pipeline_model_parallel_size=2,
            pipeline_model_parallel_size=1,
        )


def test_get_forward_backward_func_encdec_dispatch():
    """ModelType.encoder_and_decoder routes to the enc-dec schedule with
    the installed split rank pre-bound (reference: ModelType routing)."""
    import functools

    from apex_tpu.transformer.enums import ModelType
    from apex_tpu.transformer.pipeline_parallel import (
        get_forward_backward_func,
    )
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        _fwd_bwd_encdec,
    )

    parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=4,
        pipeline_model_parallel_split_rank_=2,
    )
    try:
        fn = get_forward_backward_func(
            pipeline_model_parallel_size=4,
            model_type=ModelType.encoder_and_decoder,
        )
        assert isinstance(fn, functools.partial)
        assert fn.func is _fwd_bwd_encdec
        assert fn.keywords["split_stage"] == 2
    finally:
        parallel_state.destroy_model_parallel()
    # without a split rank installed: clear error
    parallel_state.initialize_model_parallel(pipeline_model_parallel_size_=4)
    try:
        with pytest.raises(RuntimeError):
            get_forward_backward_func(
                pipeline_model_parallel_size=4,
                model_type=ModelType.encoder_and_decoder,
            )
    finally:
        parallel_state.destroy_model_parallel()


def test_encdec_fused_1f1b_grads_match_gpipe_pp4():
    """Enc-dec 1F1B at pp=4 / split=2: TWO decoder stages, so the mem
    cotangent genuinely accumulates across stages before the split
    crossover — vs jax.grad through the fused GPipe schedule (the pp=2
    T5 test has one decoder stage and cannot catch a broken dmem sum)."""
    from apex_tpu.transformer.pipeline_parallel import (
        pipeline_encdec_fused,
        pipeline_encdec_fused_1f1b,
        pipeline_stage_specs,
        sync_replicated_grads,
    )

    PP, H, ROWS, M = 4, 16, 4, 4
    split = 2
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=PP
    )
    try:
        k = jax.random.PRNGKey(0)
        params = {
            "w": 0.3 * jax.random.normal(k, (PP, H, H)),
            "cross": 0.3 * jax.random.normal(
                jax.random.fold_in(k, 1), (PP, H, H)),
            "head": 0.3 * jax.random.normal(
                jax.random.fold_in(k, 2), (H, H)),
        }
        specs = {**pipeline_stage_specs(
            {"w": P(None, None, None), "cross": P(None, None, None)}
        ), "head": P()}
        x = jax.random.normal(jax.random.fold_in(k, 3), (M, ROWS, H))
        y = jax.random.normal(jax.random.fold_in(k, 4), (M, ROWS, H))
        mbs = {"x": x, "y": y}

        def stage_fn(prm, h, mem, stage_idx):
            # self part + gated "cross-attention" consuming mem: every
            # decoder stage contributes a mem cotangent
            gate = (stage_idx >= split).astype(h.dtype)
            h = jnp.tanh(h @ prm["w"][0])
            return h + gate * jnp.tanh(mem @ prm["cross"][0])

        def enc_entry(prm, mb):
            return mb["x"]

        def dec_entry(prm, mb):
            return mb["x"] * 0.5

        def last_fn(prm, h, mb):
            return jnp.mean((h @ prm["head"] - mb["y"]) ** 2)

        def fb_1f1b(params, mbs):
            losses, grads = pipeline_encdec_fused_1f1b(
                enc_entry, dec_entry, stage_fn, last_fn, params, mbs,
                split,
            )
            return jnp.mean(losses), sync_replicated_grads(grads, specs)

        def fb_gpipe(params, mbs):
            def loss(prm):
                per = pipeline_encdec_fused(
                    lambda mb: enc_entry(prm, mb),
                    lambda mb: dec_entry(prm, mb),
                    lambda h, mem, s: stage_fn(prm, h, mem, s),
                    lambda h, mb: last_fn(prm, h, mb),
                    mbs, split, remat=False,
                )
                return jnp.mean(per)

            l, grads = jax.value_and_grad(loss)(params)
            return l, sync_replicated_grads(grads, specs)

        run = lambda f: jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(specs, P()), out_specs=(P(), specs),
        ))
        placed = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P)))
        l1, g1 = run(fb_1f1b)(placed, mbs)
        l2, g2 = run(fb_gpipe)(placed, mbs)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
        for key in ("w", "cross", "head"):
            np.testing.assert_allclose(
                np.asarray(g1[key]), np.asarray(g2[key]),
                rtol=1e-5, atol=1e-6, err_msg=key,
            )
        # the cross grads on decoder stages must be nonzero (mem path
        # live) and zero on encoder stages (gate off)
        g_cross = np.asarray(g1["cross"])
        assert np.abs(g_cross[split:]).max() > 1e-6
        np.testing.assert_allclose(g_cross[:split], 0.0, atol=1e-7)
    finally:
        parallel_state.destroy_model_parallel()
