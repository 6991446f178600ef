"""Data-parallel runtime tests on the 8-device virtual CPU mesh
(reference analog: tests/distributed/DDP/ddp_race_condition_test.py and
tests/distributed/synced_batchnorm/ — same philosophy: smallest real
mesh, analytic expectations)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.parallel import (
    DistributedDataParallel,
    all_reduce_gradients,
    data_parallel_mesh,
    sync_batch_norm,
)


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "tests require 8 virtual devices"
    return data_parallel_mesh()


class TestAllReduce:
    def test_grad_mean(self, mesh):
        grads = {"w": jnp.arange(8.0).reshape(8, 1)}

        f = jax.shard_map(
            lambda g: all_reduce_gradients(g, "dp"),
            mesh=mesh,
            in_specs=(P("dp"),),
            out_specs=P("dp"),
        )
        out = f(grads)
        # every shard gets the mean over the axis: mean(0..7) = 3.5
        np.testing.assert_allclose(np.asarray(out["w"]), 3.5)

    def test_no_average(self, mesh):
        grads = {"w": jnp.ones((8, 1))}
        f = jax.shard_map(
            lambda g: all_reduce_gradients(g, "dp", gradient_average=False),
            mesh=mesh,
            in_specs=(P("dp"),),
            out_specs=P("dp"),
        )
        out = f(grads)
        np.testing.assert_allclose(np.asarray(out["w"]), 8.0)

    def test_predivide_factor_is_mean_in_exact_arithmetic(self, mesh):
        grads = {"w": jnp.arange(8.0).reshape(8, 1)}
        f = jax.shard_map(
            lambda g: all_reduce_gradients(g, "dp", gradient_predivide_factor=2.0),
            mesh=mesh,
            in_specs=(P("dp"),),
            out_specs=P("dp"),
        )
        out = f(grads)
        np.testing.assert_allclose(np.asarray(out["w"]), 3.5, rtol=1e-6)

    def test_fp32_allreduce_of_bf16(self, mesh):
        grads = {"w": jnp.full((8, 1), 0.1, jnp.bfloat16)}
        f = jax.shard_map(
            lambda g: all_reduce_gradients(g, "dp", allreduce_always_fp32=True),
            mesh=mesh,
            in_specs=(P("dp"),),
            out_specs=P("dp"),
        )
        out = f(grads)
        assert out["w"].dtype == jnp.bfloat16


class TestDDP:
    def test_value_and_grad_matches_single_device(self, mesh):
        # analytic: loss = mean((x@w - y)^2); DP over batch must equal
        # the full-batch gradient computed on one device.
        rng = np.random.RandomState(0)
        w0 = rng.randn(4, 2).astype(np.float32)
        x = rng.randn(16, 4).astype(np.float32)
        y = rng.randn(16, 2).astype(np.float32)

        def loss_fn(params, batch):
            xb, yb = batch
            pred = xb @ params["w"]
            return jnp.mean(jnp.square(pred - yb))

        ddp = DistributedDataParallel(axis_name="dp")
        grad_fn = ddp.value_and_grad(loss_fn, mesh)
        params = {"w": jnp.asarray(w0)}
        loss, grads = grad_fn(params, (jnp.asarray(x), jnp.asarray(y)))

        ref_loss, ref_grads = jax.value_and_grad(loss_fn)(
            params, (jnp.asarray(x), jnp.asarray(y))
        )
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(grads["w"]), np.asarray(ref_grads["w"]), rtol=1e-5,
            atol=1e-6,
        )


class TestSyncBatchNorm:
    def test_matches_full_batch_bn(self, mesh):
        # SyncBN over 8 shards == plain BN over the concatenated batch
        rng = np.random.RandomState(1)
        x = rng.randn(16, 6).astype(np.float32)
        w = rng.rand(6).astype(np.float32) + 0.5
        b = rng.randn(6).astype(np.float32)

        def local(xs):
            out, _, _ = sync_batch_norm(
                xs, jnp.asarray(w), jnp.asarray(b), None, None,
                training=True, axis_name="dp",
            )
            return out

        f = jax.shard_map(
            local, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp")
        )
        out = np.asarray(f(jnp.asarray(x)))

        mean = x.mean(0)
        var = x.var(0)
        ref = (x - mean) / np.sqrt(var + 1e-5) * w + b
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)

    def test_different_per_rank_batches_via_masking(self, mesh):
        # the stats use summed counts, matching the reference's support for
        # unequal per-rank batch sizes
        rng = np.random.RandomState(2)
        x = rng.randn(8, 3, 4).astype(np.float32)  # 8 ranks x 3 rows

        def local(xs):
            out, _, _ = sync_batch_norm(
                xs, None, None, None, None, training=True, axis_name="dp"
            )
            return out

        f = jax.shard_map(
            local, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp")
        )
        out = np.asarray(f(jnp.asarray(x))).reshape(24, 4)
        flat = x.reshape(24, 4)
        ref = (flat - flat.mean(0)) / np.sqrt(flat.var(0) + 1e-5)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)

    def test_group_size(self, mesh):
        # group_size=4: ranks 0-3 share stats, ranks 4-7 share stats
        x = np.zeros((8, 2, 2), np.float32)
        x[:4] = 1.0  # group 0 constant 1 → normalized output 0
        x[4:] = np.linspace(0, 1, 16).reshape(4, 2, 2)

        def local(xs):
            out, _, _ = sync_batch_norm(
                xs, None, None, None, None, training=True,
                axis_name="dp", process_group_size=4,
            )
            return out

        f = jax.shard_map(
            local, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp")
        )
        out = np.asarray(f(jnp.asarray(x)))
        np.testing.assert_allclose(out[:4], 0.0, atol=1e-5)
        # group 1 normalized within itself
        g1 = x[4:].reshape(8, 2)
        ref = (g1 - g1.mean(0)) / np.sqrt(g1.var(0) + 1e-5)
        np.testing.assert_allclose(out[4:].reshape(8, 2), ref, rtol=1e-4, atol=1e-4)

    def test_running_stats_update(self):
        x = jnp.asarray(np.random.RandomState(3).randn(10, 4).astype(np.float32))
        rm = jnp.zeros((4,))
        rv = jnp.ones((4,))
        _, new_rm, new_rv = sync_batch_norm(
            x, None, None, rm, rv, training=True, momentum=0.1
        )
        xn = np.asarray(x)
        np.testing.assert_allclose(
            np.asarray(new_rm), 0.1 * xn.mean(0), rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(new_rv),
            0.9 * 1.0 + 0.1 * xn.var(0, ddof=1),
            rtol=1e-5,
        )

    def test_eval_uses_running_stats(self):
        x = jnp.ones((4, 2))
        rm = jnp.asarray([1.0, 1.0])
        rv = jnp.asarray([1.0, 1.0])
        out, _, _ = sync_batch_norm(
            x, None, None, rm, rv, training=False
        )
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-5)


class TestConvertSyncBN:
    """Recursive BatchNorm -> SyncBatchNorm conversion
    (reference: apex/parallel/__init__.py:21-95)."""

    def _model(self):
        import flax.linen as nn

        class Block(nn.Module):
            feats: int
            norm: nn.Module = None

            @nn.compact
            def __call__(self, x, train):
                x = nn.Dense(self.feats)(x)
                x = self.norm(x, use_running_average=not train) \
                    if self.norm is not None else x
                return jax.nn.relu(x)

        class Net(nn.Module):
            block: nn.Module

            @nn.compact
            def __call__(self, x, train):
                x = self.block(x, train)
                return nn.Dense(4)(x)

        import flax.linen as nn2
        bn = nn2.BatchNorm(momentum=0.9, epsilon=1e-5)
        return Net(block=Block(feats=8, norm=bn))

    def test_recursive_swap_preserves_hparams(self):
        from apex_tpu.parallel import SyncBatchNorm, convert_syncbn_model

        net = self._model()
        conv = convert_syncbn_model(net, process_group_size=2)
        sbn = conv.block.norm
        assert isinstance(sbn, SyncBatchNorm)
        assert sbn.eps == 1e-5
        # flax momentum (ra decay) 0.9 -> torch-style update weight 0.1
        assert abs(sbn.momentum - 0.1) < 1e-9
        assert sbn.process_group_size == 2
        # untouched parts survive
        assert conv.block.feats == 8

    def test_converted_model_matches_full_batch_bn(self):
        """SyncBN over dp shards == plain BN over the full batch."""
        import flax.linen as nn

        from apex_tpu.parallel import convert_syncbn_model
        from apex_tpu.transformer import parallel_state

        mesh = parallel_state.initialize_model_parallel()
        try:
            net = self._model()
            conv = convert_syncbn_model(net)
            x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))

            ref_vars = net.init(jax.random.PRNGKey(1), x, train=True)
            out_ref, _ = net.apply(
                ref_vars, x, train=True, mutable=["batch_stats"]
            )

            conv_vars = conv.init(jax.random.PRNGKey(1), x, train=False)

            def fwd(v, xs):
                out, upd = conv.apply(
                    v, xs, train=True, mutable=["batch_stats"]
                )
                return out

            sharded = jax.jit(jax.shard_map(
                fwd, mesh=mesh,
                in_specs=(P(), P("dp")), out_specs=P("dp"),
                check_vma=False,
            ))
            out_sync = sharded(conv_vars, x)
            np.testing.assert_allclose(
                np.asarray(out_sync), np.asarray(out_ref),
                rtol=1e-5, atol=1e-5,
            )
        finally:
            parallel_state.destroy_model_parallel()

    def test_variables_rename(self):
        from apex_tpu.parallel import convert_syncbn_variables

        vars_in = {
            "params": {
                "bn": {"scale": jnp.ones((4,)), "bias": jnp.zeros((4,))},
                # LayerNorm also has a 'scale' param but no running stats:
                # it must NOT be renamed
                "ln": {"scale": jnp.ones((4,)), "bias": jnp.zeros((4,))},
                "dense": {"kernel": jnp.ones((4, 4)), "bias": jnp.zeros((4,))},
            },
            "batch_stats": {
                "bn": {"mean": jnp.zeros((4,)), "var": jnp.ones((4,))},
            },
        }
        out = convert_syncbn_variables(vars_in)
        assert "weight" in out["params"]["bn"]
        assert "bias" in out["params"]["bn"]
        assert "scale" in out["params"]["ln"]      # LayerNorm untouched
        assert "weight" not in out["params"]["ln"]
        assert "kernel" in out["params"]["dense"]  # untouched
        assert "running_mean" in out["batch_stats"]["bn"]
        assert "running_var" in out["batch_stats"]["bn"]

    def test_scale_only_bn_refused(self):
        import flax.linen as nn

        from apex_tpu.parallel import convert_syncbn_model

        with pytest.raises(ValueError, match="use_scale"):
            convert_syncbn_model(nn.BatchNorm(use_scale=True, use_bias=False))


class TestReducer:
    """Deferred manual reduction (reference:
    apex/parallel/distributed.py:89-126): accumulating K microbatches
    locally then reducing once must equal the mean gradient over the
    full (axis world x K) batch."""

    def test_accumulate_then_reduce_matches_big_batch(self, mesh):
        from apex_tpu.parallel import Reducer

        w = jnp.asarray([[2.0], [1.0]])  # (2, 1)
        # per-device data: 8 devices x K=3 microbatches x 4 rows
        rng = np.random.default_rng(0)
        xs = jnp.asarray(rng.normal(size=(8, 3, 4, 2)), jnp.float32)
        ys = jnp.asarray(rng.normal(size=(8, 3, 4, 1)), jnp.float32)

        def loss(w, x, y):
            return jnp.mean((x @ w - y) ** 2)

        red = Reducer(axis_name="dp")

        def step(w, xs, ys):
            # xs: (1, 3, 4, 2) local shard.  pvary keeps per-device
            # grads LOCAL (grad wrt replicated w would already psum —
            # the transpose of the replicated->varying broadcast), so
            # there is something left to defer (Reducer docstring)
            w_local = jax.lax.pcast(w, "dp", to="varying")
            acc = red.init(w)
            for k in range(3):
                g = jax.grad(loss)(w_local, xs[0, k], ys[0, k])
                acc = red.accumulate(acc, g)
            mean_g, fresh = red.reduce(acc)
            # reset really is zero
            resid = sum(jnp.sum(jnp.abs(l))
                        for l in jax.tree.leaves(fresh["sum"]))
            return mean_g, jax.lax.pmax(resid, "dp")

        mean_g, resid = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), P("dp"), P("dp")), out_specs=(P(), P()),
        ))(w, xs, ys)

        # reference: gradient of the mean loss over all 8*3 microbatches
        ref = jax.grad(
            lambda w: jnp.mean(jnp.stack([
                loss(w, xs[d, k], ys[d, k])
                for d in range(8) for k in range(3)
            ]))
        )(w)
        np.testing.assert_allclose(
            np.asarray(mean_g), np.asarray(ref), rtol=1e-5, atol=1e-6)
        assert float(resid) == 0.0

    def test_no_collective_during_accumulate(self, mesh):
        """accumulate is local: per-device sums differ across ranks
        until reduce runs."""
        from apex_tpu.parallel import Reducer

        red = Reducer(axis_name="dp")

        def step(x):
            acc = red.init(x[0])
            acc = red.accumulate(acc, x[0])
            # local sum equals the local shard — no cross-device mixing
            return jnp.sum(jnp.abs(acc["sum"] - x[0]))

        out = jax.jit(jax.shard_map(
            lambda x: jax.lax.psum(step(x), "dp"), mesh=mesh,
            in_specs=(P("dp"),), out_specs=P(),
        ))(jnp.arange(8.0).reshape(8, 1))
        assert float(out) == 0.0

    def test_gradient_average_false_returns_sum(self, mesh):
        """gradient_average=False: raw sum over (world x K) — the
        all_reduce_gradients sum semantics extended to accumulation."""
        from apex_tpu.parallel import Reducer

        red = Reducer(axis_name="dp", gradient_average=False)

        def step(x):
            acc = red.init(x[0])
            acc = red.accumulate(acc, x[0])
            acc = red.accumulate(acc, x[0])
            g, _ = red.reduce(acc)
            return g

        out = jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(P("dp"),), out_specs=P(),
        ))(jnp.arange(8.0).reshape(8, 1))
        # sum over devices (0+..+7 = 28) x 2 accumulations
        assert float(out[0]) == 56.0

    def test_reference_scaling_flag(self, mesh):
        """average_over_microbatches=False reproduces the reference
        Reducer's scaling: mean over world, SUM over the K accumulated
        microbatches (the default deliberately deviates by also
        dividing by K — Reducer docstring)."""
        from apex_tpu.parallel import Reducer

        ours = Reducer(axis_name="dp")
        ref = Reducer(axis_name="dp", average_over_microbatches=False)

        def step(x):
            outs = []
            for red in (ours, ref):
                acc = red.init(x[0])
                for _ in range(4):  # K=4 identical microbatches
                    acc = red.accumulate(acc, x[0])
                g, _ = red.reduce(acc)
                outs.append(g)
            return tuple(outs)

        g_ours, g_ref = jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=(P("dp"),), out_specs=(P(), P()),
        ))(jnp.arange(8.0).reshape(8, 1))
        # mean over world of the per-device value 0..7 is 3.5
        assert float(g_ours[0]) == 3.5        # also averaged over K
        assert float(g_ref[0]) == 3.5 * 4     # reference: sum over K
