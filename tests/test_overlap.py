"""Overlapped bucketed gradient sync on the 8-device virtual mesh.

Covers the bucket-assembly invariants (every leaf exactly once,
reverse-layer order, size targets), the bit-identity guarantees
(bucketed single-shot reduce vs unbucketed at ``compression=None``;
pipelined loop vs the per-microbatch reference, and vs the deferred
seed path at K=1), int8+error-feedback parity within the PR 3
tolerance, the bucketed residual state's checkpoint round-trip, a GPT
accumulation-loop numerics test against the unbucketed seed path, and
the scheduled-HLO overlap audit (async start/done pair counting +
dataflow overlappability).
"""

import importlib.util
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.quantization import CompressionConfig
from apex_tpu.parallel import (
    GradientBuckets,
    all_reduce_gradients,
    data_parallel_mesh,
    hierarchical_data_parallel_mesh,
)
from apex_tpu.parallel.distributed import (
    Reducer,
    comm_state_specs,
    init_comm_state,
)
from apex_tpu.parallel.overlap import (
    bucket_comm_state,
    is_bucketed_residuals,
)


def smap(f, mesh, in_specs, out_specs):
    """The vma checker is off: every test here reduces explicitly (the
    DDP.value_and_grad convention), so the autodiff-inserted psum the
    checker enables is never relied on, and the per-device comm
    residuals are written with data-axis-only specs."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


DCN, ICI = 2, 4
AXES = ("dcn", "ici")


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "tests require 8 virtual devices"
    return hierarchical_data_parallel_mesh(ici_size=ICI)


@pytest.fixture(scope="module")
def flat_mesh():
    return data_parallel_mesh()


def _grads(key=5):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return {"w": jax.random.normal(ks[0], (8, 33, 7)),
            "b": jax.random.normal(ks[1], (8, 9)),
            "h": jax.random.normal(ks[2], (8, 129)).astype(jnp.bfloat16)}


# ---------------------------------------------------------------- assembly


class TestBucketAssembly:
    def test_every_leaf_exactly_once(self):
        shapes = [(5, 7), (3,), (64,), (2, 2), (100,)]
        dtypes = [jnp.float32] * 5
        plan = GradientBuckets.from_shapes(shapes, dtypes, 256)
        seen = sorted(i for b in plan.buckets for i in b.leaf_ids)
        assert seen == list(range(5))
        sizes = {i: 1 for i in range(5)}
        for b in plan.buckets:
            for i, s in zip(b.leaf_ids, b.sizes):
                expected = int(np.prod(shapes[i]))
                assert s == expected
                sizes.pop(i, None)

    def test_reverse_layer_order(self):
        """Concatenating the bucket order must give exactly the
        REVERSED tree order — the backward-ready order the reference
        discovers its buckets in."""
        shapes = [(4,)] * 6
        plan = GradientBuckets.from_shapes(
            shapes, [jnp.float32] * 6, 2 * 4 * 4)
        flat = [i for b in plan.buckets for i in b.leaf_ids]
        assert flat == [5, 4, 3, 2, 1, 0]

    def test_size_target_closes_buckets(self):
        # 6 leaves of 16 bytes each, target 40 bytes -> 2 per bucket
        plan = GradientBuckets.from_shapes(
            [(4,)] * 6, [jnp.float32] * 6, 40)
        assert [len(b.leaf_ids) for b in plan.buckets] == [2, 2, 2]
        for b in plan.buckets:
            assert b.size * 4 <= 40

    def test_oversized_leaf_gets_own_bucket(self):
        plan = GradientBuckets.from_shapes(
            [(4,), (1000,), (4,)], [jnp.float32] * 3, 64)
        by_len = [b.leaf_ids for b in plan.buckets]
        assert (1,) in by_len  # the big leaf rides alone

    def test_dtype_never_mixes(self):
        plan = GradientBuckets.from_shapes(
            [(4,), (4,), (4,)],
            [jnp.float32, jnp.bfloat16, jnp.bfloat16],
            1 << 20,
        )
        for b in plan.buckets:
            assert len({str(b.dtype)}) == 1
        # bf16 leaves (ids 2,1) share; the f32 leaf is separate
        assert [b.leaf_ids for b in plan.buckets] == [(2, 1), (0,)]

    def test_forced_dtype_merges_everything(self):
        plan = GradientBuckets.for_tree(
            {"a": jnp.ones((4,), jnp.bfloat16),
             "b": jnp.ones((4,), jnp.float32)},
            bucket_bytes=1 << 20, dtype=jnp.float32)
        assert len(plan.buckets) == 1

    def test_pack_unpack_roundtrip_bit_exact(self):
        grads = _grads()
        leaves = jax.tree.leaves(grads)
        plan = GradientBuckets.for_tree(grads, bucket_bytes=300)
        back = plan.unpack(plan.pack(leaves), leaves)
        for a, b in zip(leaves, back):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))
            assert a.dtype == b.dtype

    def test_validation(self):
        with pytest.raises(ValueError, match="bucket_bytes"):
            GradientBuckets.from_shapes([(4,)], [jnp.float32], 0)
        with pytest.raises(ValueError, match="exactly once"):
            GradientBuckets(
                GradientBuckets.from_shapes(
                    [(4,)], [jnp.float32], 64).buckets, 2)
        plan = GradientBuckets.from_shapes([(4,)], [jnp.float32], 64)
        with pytest.raises(ValueError, match="leaves"):
            plan.pack([jnp.ones(4), jnp.ones(4)])

    def test_zero_element_and_scalar_leaves(self, mesh):
        """A zero-element leaf must occupy 0 buffer slots (not 1) so
        unpack offsets stay aligned, and a scalar occupies exactly 1;
        the bucketed reduce stays bit-identical with both present."""
        grads = {"a": jnp.ones((3,)) * 2.0,
                 "s": jnp.float32(5.0),
                 "z": jnp.zeros((0,))}
        leaves = jax.tree.leaves(grads)
        plan = GradientBuckets.for_tree(grads, bucket_bytes=1 << 20)
        assert sum(b.size for b in plan.buckets) == 4
        back = plan.unpack(plan.pack(leaves), leaves)
        for a, b in zip(leaves, back):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the BUCKETED reduce handles zero-element leaves (the seed
        # per-leaf hierarchical path cannot — psum_scatter rejects
        # empty operands), so compare against the analytic mean
        g8 = {"a": jax.random.normal(jax.random.PRNGKey(3), (8, 3)),
              "s": jax.random.normal(jax.random.PRNGKey(4), (8,)),
              "z": jnp.zeros((8, 0)),
              # bf16 + empty: forms an entirely-empty bucket (dtype
              # split), exercising the zero-size-bucket pass-through
              "y": jnp.zeros((8, 0), jnp.bfloat16)}
        spec = jax.tree.map(lambda _: P(AXES), g8)
        bucketed = jax.jit(smap(
            lambda g: all_reduce_gradients(
                g, AXES, overlap_grad_sync=True, bucket_bytes=8),
            mesh, (spec,), spec))(g8)
        assert bucketed["z"].shape == (8, 0)
        assert bucketed["y"].dtype == jnp.bfloat16
        for k in ("a", "s"):
            ref = np.broadcast_to(
                np.mean(np.asarray(g8[k]), axis=0, keepdims=True),
                g8[k].shape)
            np.testing.assert_allclose(
                np.asarray(bucketed[k]), ref, rtol=1e-6, atol=1e-7)

    def test_model_axis_union(self):
        from jax.sharding import Mesh

        devs = np.asarray(jax.devices()).reshape(2, 2, 2)
        mesh3 = Mesh(devs, ("dcn", "ici", "pp"))
        params = {"stack": jnp.zeros((2, 40)), "norm": jnp.zeros((24,))}
        pspecs = {"stack": P("pp"), "norm": P()}
        plan = GradientBuckets.for_tree(
            params, bucket_bytes=1 << 20, param_specs=pspecs,
            mesh=mesh3)
        (b,) = plan.buckets
        assert b.model_axes == ("pp",)
        # the pp-sharded leaf is sized PER DEVICE: (2//2, 40) = 40
        assert dict(zip(b.leaf_ids, b.sizes)) == {0: 24, 1: 40}


# ------------------------------------------------------------ bit identity


class TestBitIdentity:
    def test_bucketed_reduce_bit_identical_hierarchical(self, mesh):
        grads = _grads()
        spec = jax.tree.map(lambda _: P(AXES), grads)
        plain = jax.jit(smap(
            lambda g: all_reduce_gradients(g, AXES),
            mesh, (spec,), spec))(grads)
        for bb in (64, 300, 1 << 20):
            bucketed = jax.jit(smap(
                lambda g: all_reduce_gradients(
                    g, AXES, overlap_grad_sync=True, bucket_bytes=bb),
                mesh, (spec,), spec))(grads)
            for k in grads:
                np.testing.assert_array_equal(
                    np.asarray(plain[k], np.float32),
                    np.asarray(bucketed[k], np.float32))

    def test_bucketed_reduce_bit_identical_flat_axis(self, flat_mesh):
        grads = _grads()
        spec = jax.tree.map(lambda _: P("dp"), grads)
        plain = jax.jit(smap(
            lambda g: all_reduce_gradients(g, "dp"),
            flat_mesh, (spec,), spec))(grads)
        bucketed = jax.jit(smap(
            lambda g: all_reduce_gradients(
                g, "dp", overlap_grad_sync=True, bucket_bytes=256),
            flat_mesh, (spec,), spec))(grads)
        for k in grads:
            np.testing.assert_array_equal(
                np.asarray(plain[k], np.float32),
                np.asarray(bucketed[k], np.float32))

    def test_pipelined_k1_bit_identical_to_seed(self, mesh):
        def run(red):
            def step(x):
                acc = red.init(x)
                acc = red.accumulate(acc, x)
                g, _ = red.reduce(acc)
                return g

            return jax.jit(smap(step, mesh, (P(AXES),), P(AXES)))(
                jax.random.normal(jax.random.PRNGKey(7), (8, 57)))

        seed = run(Reducer(axis_name=AXES))
        over = run(Reducer(axis_name=AXES, overlap_grad_sync=True,
                           bucket_bytes=64))
        np.testing.assert_array_equal(np.asarray(seed), np.asarray(over))

    def test_pipelined_matches_per_microbatch_reference(self, mesh):
        """The pipelined loop's documented semantics — Σ_k psum(g_k),
        then the deferred path's exact scaling ops — reproduced inline
        and compared BIT-exactly."""
        x = jax.random.normal(jax.random.PRNGKey(8), (8, 100))

        def overlapped(xs):
            red = Reducer(axis_name=AXES, overlap_grad_sync=True,
                          bucket_bytes=160)
            acc = red.init(xs)
            for k in range(3):
                acc = red.accumulate(acc, (k + 1.0) * xs)
            g, _ = red.reduce(acc)
            return g

        def reference(xs):
            tot = None
            for k in range(3):
                r = all_reduce_gradients(
                    (k + 1.0) * xs, AXES, gradient_average=False)
                tot = r if tot is None else tot + r
            return tot / 8.0 / 3.0

        go = jax.jit(smap(overlapped, mesh, (P(AXES),), P(AXES)))(x)
        gr = jax.jit(smap(reference, mesh, (P(AXES),), P(AXES)))(x)
        np.testing.assert_array_equal(np.asarray(go), np.asarray(gr))

    def test_pipelined_close_to_deferred_k3(self, mesh):
        """Different summation order, same mean: the pipelined result
        tracks the deferred one to fp32 reduction-order noise."""
        x = jax.random.normal(jax.random.PRNGKey(9), (8, 210))

        def run(red):
            def step(xs):
                acc = red.init(xs)
                for k in range(3):
                    acc = red.accumulate(acc, (1.0 + 0.1 * k) * xs)
                g, _ = red.reduce(acc)
                return g

            return jax.jit(smap(step, mesh, (P(AXES),), P(AXES)))(x)

        deferred = run(Reducer(axis_name=AXES))
        pipelined = run(Reducer(axis_name=AXES, overlap_grad_sync=True,
                                bucket_bytes=256))
        np.testing.assert_allclose(
            np.asarray(pipelined), np.asarray(deferred),
            rtol=1e-6, atol=1e-6)

    def test_pipelined_scan_matches_python_loop(self, mesh):
        """After priming with one accumulate the state structure is
        stable, so the rest of the loop can be a lax.scan carry — and
        produces bit-identical results to the unrolled loop."""
        gs = jax.random.normal(jax.random.PRNGKey(10), (4, 8, 90))

        def python_loop(gs):
            red = Reducer(axis_name=AXES, overlap_grad_sync=True,
                          bucket_bytes=128)
            acc = red.init(gs[0])
            for k in range(4):
                acc = red.accumulate(acc, gs[k])
            g, _ = red.reduce(acc)
            return g

        def scan_loop(gs):
            red = Reducer(axis_name=AXES, overlap_grad_sync=True,
                          bucket_bytes=128)
            acc = red.init(gs[0])
            acc = red.accumulate(acc, gs[0])  # prime: adds "pending"
            acc, _ = jax.lax.scan(
                lambda st, g: (red.accumulate(st, g), None),
                acc, gs[1:])
            g, _ = red.reduce(acc)
            return g

        spec = P(None, AXES)
        a = jax.jit(smap(python_loop, mesh, (spec,), P(AXES)))(gs)
        b = jax.jit(smap(scan_loop, mesh, (spec,), P(AXES)))(gs)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_predivide_and_raw_sum_semantics(self, mesh):
        x = jax.random.normal(jax.random.PRNGKey(11), (8, 40))

        def run(**kw):
            red = Reducer(axis_name=AXES, overlap_grad_sync=True,
                          bucket_bytes=128, **kw)

            def step(xs):
                acc = red.init(xs)
                acc = red.accumulate(acc, xs)
                acc = red.accumulate(acc, xs)
                g, _ = red.reduce(acc)
                return g

            return np.asarray(jax.jit(smap(
                step, mesh, (P(AXES),), P(AXES)))(x))

        mean_ref = np.broadcast_to(
            np.mean(np.asarray(x), axis=0, keepdims=True), x.shape)
        np.testing.assert_allclose(
            run(gradient_predivide_factor=4.0), mean_ref,
            rtol=1e-5, atol=1e-6)
        # raw sum over world x K
        np.testing.assert_allclose(
            run(gradient_average=False),
            np.broadcast_to(
                2.0 * np.sum(np.asarray(x), axis=0, keepdims=True),
                x.shape),
            rtol=1e-5, atol=1e-5)
        # reference scaling: mean over world, SUM over microbatches
        np.testing.assert_allclose(
            run(average_over_microbatches=False), 2.0 * mean_ref,
            rtol=1e-5, atol=1e-6)


# --------------------------------------------------------- compression


class TestBucketedCompression:
    def test_bucketed_int8_ef_tracks_exact_mean(self, mesh):
        grads = {"w": _grads()["w"], "b": _grads()["b"]}
        spec = jax.tree.map(lambda _: P(AXES), grads)
        local = jax.tree.map(
            lambda g: jnp.zeros((1,) + g.shape[1:], g.dtype), grads)
        cfg = CompressionConfig(block_size=64)
        state = init_comm_state(local, AXES, cfg, mesh=mesh,
                                bucket_bytes=300)
        assert is_bucketed_residuals(state["residuals"])
        cspecs = comm_state_specs(state, AXES)
        step = jax.jit(smap(
            lambda g, st: all_reduce_gradients(
                g, AXES, compression=cfg, comm_state=st,
                overlap_grad_sync=True, bucket_bytes=300),
            mesh, (spec, cspecs), (spec, cspecs)))
        out, state = step(grads, state)
        assert int(state["step"]) == 1
        for k in grads:
            ref = np.broadcast_to(
                np.mean(np.asarray(grads[k]), axis=0, keepdims=True),
                grads[k].shape)
            amax = np.max(np.abs(ref))
            assert np.max(np.abs(np.asarray(out[k]) - ref)) \
                < 0.05 * amax
        # a second step consumes and refreshes the bucketed residuals
        out2, state = step(grads, state)
        assert int(state["step"]) == 2
        assert any(
            float(jnp.sum(jnp.abs(l))) > 0
            for l in jax.tree.leaves(
                jax.device_get(state)["residuals"])
        )

    def test_pipelined_int8_ef_parity_with_exact(self, mesh):
        """int8+EF through the PIPELINED loop tracks the exact
        pipelined reduce within the PR 3 tolerance."""
        x = jax.random.normal(jax.random.PRNGKey(12), (8, 300))

        def run(comp):
            red = Reducer(axis_name=AXES, overlap_grad_sync=True,
                          bucket_bytes=256, compression=comp)

            def step(xs):
                acc = red.init(xs)
                for k in range(2):
                    acc = red.accumulate(acc, xs)
                g, fresh = red.reduce(acc)
                resid = jnp.float32(0.0)
                if "comm" in fresh:
                    resid = sum(
                        jnp.sum(jnp.abs(l)) for l in
                        jax.tree.leaves(fresh["comm"]["residuals"]))
                return g, resid

            return jax.jit(smap(
                step, mesh, (P(AXES),), (P(AXES), P())))(x)

        exact, _ = run(None)
        quant, resid = run(CompressionConfig(block_size=64))
        amax = np.max(np.abs(np.asarray(exact)))
        np.testing.assert_allclose(
            np.asarray(quant), np.asarray(exact), atol=3e-2 * amax)
        # residuals persisted in the fresh state for the next cycle
        assert float(resid) > 0.0

    def test_mismatched_bucketed_state_raises(self, mesh):
        grads = {"w": jnp.ones((8, 64))}
        spec = {"w": P(AXES)}
        cfg = CompressionConfig(block_size=4)
        # state sized for HALF the local leaf the reduce will see
        local = {"w": jnp.zeros((1, 32))}
        state = init_comm_state(local, AXES, cfg, mesh=mesh,
                                bucket_bytes=1 << 20)
        cspecs = comm_state_specs(state, AXES)
        with pytest.raises(ValueError, match="bucket"):
            jax.jit(smap(
                lambda g, st: all_reduce_gradients(
                    g, AXES, compression=cfg, comm_state=st,
                    overlap_grad_sync=True, bucket_bytes=1 << 20),
                mesh, (spec, cspecs), (spec, cspecs)))(grads, state)

    def test_bucketed_state_without_overlap_raises(self, mesh):
        cfg = CompressionConfig(block_size=16)
        local = {"w": jnp.zeros((1, 64))}
        state = init_comm_state(local, AXES, cfg, mesh=mesh,
                                bucket_bytes=64)
        with pytest.raises(ValueError, match="overlap_grad_sync"):
            all_reduce_gradients(
                {"w": jnp.ones((8, 64))}, AXES, compression=cfg,
                comm_state=state)

    def test_leaf_state_with_overlap_raises(self, mesh):
        cfg = CompressionConfig(block_size=16)
        local = {"w": jnp.zeros((1, 64))}
        state = init_comm_state(local, AXES, cfg, mesh=mesh)
        with pytest.raises(ValueError, match="BUCKETED"):
            all_reduce_gradients(
                {"w": jnp.ones((8, 64))}, AXES, compression=cfg,
                comm_state=state, overlap_grad_sync=True)

    def test_bucketed_specs_with_model_axes(self):
        from jax.sharding import Mesh

        devs = np.asarray(jax.devices()).reshape(2, 2, 2)
        mesh3 = Mesh(devs, ("dcn", "ici", "pp"))
        params = {"stack": jnp.zeros((2, 40)), "norm": jnp.zeros((24,))}
        pspecs = {"stack": P("pp"), "norm": P()}
        cfg = CompressionConfig(block_size=16)
        plan = GradientBuckets.for_tree(
            params, bucket_bytes=1 << 20, param_specs=pspecs,
            mesh=mesh3)
        state = init_comm_state(params, AXES, cfg, mesh=mesh3,
                                param_specs=pspecs, buckets=plan)
        specs = comm_state_specs(state, AXES, buckets=plan)
        (name,) = state["residuals"].keys()
        assert specs["residuals"][name]["push"] == \
            P(("dcn", "ici", "pp"))
        # bucket holds 64 local elems -> chunk 32 over ici=2 -> padded
        # to dcn*block = 32; x (2 dcn x 2 ici x 2 pp) positions
        assert state["residuals"][name]["push"].shape == (8 * 32,)

    def test_ddp_remembers_bucket_plan_for_specs(self):
        """DistributedDataParallel must hand its own bucket plan to
        comm_state_specs — otherwise model-sharded bucketed residuals
        get replicated-over-model-axes specs and mis-shard."""
        from jax.sharding import Mesh

        from apex_tpu.parallel.distributed import (
            DistributedDataParallel,
        )

        devs = np.asarray(jax.devices()).reshape(2, 2, 2)
        mesh3 = Mesh(devs, ("dcn", "ici", "pp"))
        params = {"stack": jnp.zeros((2, 40)), "norm": jnp.zeros((24,))}
        pspecs = {"stack": P("pp"), "norm": P()}
        ddp = DistributedDataParallel(
            axis_name=AXES, compression=CompressionConfig(block_size=16),
            overlap_grad_sync=True, bucket_bytes=1 << 20)
        state = ddp.init_comm_state(params, mesh=mesh3,
                                    param_specs=pspecs)
        specs = ddp.comm_state_specs(state)
        (name,) = state["residuals"].keys()
        assert specs["residuals"][name]["push"] == \
            P(("dcn", "ici", "pp"))


# ------------------------------------------------------- checkpointing


class TestCheckpointRoundTrip:
    def test_bucketed_residuals_round_trip(self, mesh, tmp_path):
        """Save the bucketed comm state mid-run, restore, and the
        resumed reduce must be BIT-identical to the uninterrupted
        one — the same guarantee PR 3 gave per-leaf residuals."""
        from apex_tpu import checkpoint

        grads = {"w": _grads()["w"]}
        spec = {"w": P(AXES)}
        local = {"w": jnp.zeros((1,) + grads["w"].shape[1:])}
        cfg = CompressionConfig(block_size=64)
        state0 = init_comm_state(local, AXES, cfg, mesh=mesh,
                                 bucket_bytes=256)
        cspecs = comm_state_specs(state0, AXES)
        step = jax.jit(smap(
            lambda g, st: all_reduce_gradients(
                g, AXES, compression=cfg, comm_state=st,
                overlap_grad_sync=True, bucket_bytes=256),
            mesh, (spec, cspecs), (spec, cspecs)))

        # uninterrupted: 3 steps
        st = state0
        for _ in range(2):
            _, st = step(grads, st)
        out_ref, st_ref = step(grads, st)

        # interrupted: 2 steps, checkpoint, restore, third step
        st = state0
        for _ in range(2):
            _, st = step(grads, st)
        path = os.path.join(str(tmp_path), "comm")
        checkpoint.save(path, jax.device_get(st))
        restored = checkpoint.restore(path, target=jax.device_get(st))
        out_res, st_res = step(grads, restored)
        np.testing.assert_array_equal(
            np.asarray(out_ref["w"]), np.asarray(out_res["w"]))
        for a, b in zip(jax.tree.leaves(jax.device_get(st_ref)),
                        jax.tree.leaves(jax.device_get(st_res))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------------------- GPT


VOCAB, LAYERS, HIDDEN, HEADS, SEQ = 64, 2, 32, 4, 8


@pytest.fixture(scope="module")
def gpt_mesh():
    from apex_tpu.transformer import parallel_state

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        data_parallel_ici_size_=ICI)
    yield mesh
    parallel_state.destroy_model_parallel()


def test_gpt_accumulation_loop_matches_seed_path(gpt_mesh):
    """The pipelined accumulate-and-reduce loop on a real GPT fwd/bwd
    tracks the unbucketed deferred seed path: same microbatch stream,
    grads equal to fp32 reduction-order noise, and a short training
    run's loss curve indistinguishable at 1e-4."""
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state

    cfg = GPTConfig(
        vocab_size=VOCAB, num_layers=LAYERS, hidden_size=HIDDEN,
        num_attention_heads=HEADS, max_position_embeddings=SEQ,
        compute_dtype=jnp.float32, remat=False, attention_impl="xla",
    )
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    specs = model.param_specs()
    data_axes = parallel_state.data_parallel_axis_names()
    rng = np.random.default_rng(0)
    micro = [jnp.asarray(rng.integers(0, VOCAB, (8, SEQ)), jnp.int32)
             for _ in range(2)]

    def make_step(red):
        from apex_tpu.transformer.tensor_parallel.layers import (
            state_specs_like,
        )

        opt = FusedAdam(lr=1e-2)
        opt_state = opt.init(params)
        opt_specs = state_specs_like(specs, opt_state)

        def step(p, s, t0, g0, t1, g1):
            acc = red.init(p)
            losses = []
            for tok, tgt in ((t0, g0), (t1, g1)):
                loss, grads = jax.value_and_grad(model.loss)(
                    p, tok, tgt)
                losses.append(jax.lax.pmean(loss, data_axes))
                acc = red.accumulate(acc, grads)
            grads, _ = red.reduce(acc)
            p, s = opt.step(s, grads, p)
            return p, s, (losses[0] + losses[1]) / 2.0, grads

        dspec = P(data_axes)
        jstep = jax.jit(smap(
            step, gpt_mesh,
            (specs, opt_specs, dspec, dspec, dspec, dspec),
            (specs, opt_specs, P(), specs)))
        return jstep, opt_state

    def train(red, steps=4):
        jstep, opt_state = make_step(red)
        p, s = params, opt_state
        losses, last_grads = [], None
        for i in range(steps):
            tok = micro[i % 2]
            tgt = jnp.roll(tok, -1, axis=1)
            tok2 = micro[(i + 1) % 2]
            tgt2 = jnp.roll(tok2, -1, axis=1)
            p, s, loss, last_grads = jstep(p, s, tok, tgt, tok2, tgt2)
            losses.append(float(loss))
        return losses, last_grads

    seed_losses, seed_grads = train(Reducer(axis_name=data_axes))
    over_losses, over_grads = train(Reducer(
        axis_name=data_axes, overlap_grad_sync=True,
        bucket_bytes=16 * 1024))
    np.testing.assert_allclose(over_losses, seed_losses, atol=1e-4)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(jax.device_get(seed_grads)),
        jax.tree_util.tree_leaves_with_path(jax.device_get(over_grads)),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
            err_msg=str(path))


# ----------------------------------------------------------- audit tool


def _load_comm_audit():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "comm_audit", os.path.join(root, "tools", "comm_audit.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ASYNC_HLO = """\
HloModule test, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(f32[] %a, f32[] %b)
}

ENTRY %main (p0: f32[256,256], p1: f32[4096]) -> (f32[4096], f32[256,256]) {
  %p0 = f32[256,256]{1,0} parameter(0)
  %p1 = f32[4096]{0} parameter(1)
  %ars = f32[4096]{0} all-reduce-start(f32[4096]{0} %p1), replica_groups={{0,4},{1,5},{2,6},{3,7}}, use_global_device_ids=true, to_apply=%add
  %dot = f32[256,256]{1,0} dot(f32[256,256]{1,0} %p0, f32[256,256]{1,0} %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ard = f32[4096]{0} all-reduce-done(f32[4096]{0} %ars)
  ROOT %t = (f32[4096]{0}, f32[256,256]{1,0}) tuple(f32[4096]{0} %ard, f32[256,256]{1,0} %dot)
}
"""

_SYNC_HLO = """\
HloModule test2, is_scheduled=true

ENTRY %main (p0: f32[256,256], p1: f32[4096]) -> (f32[4096], f32[256,256]) {
  %p0 = f32[256,256]{1,0} parameter(0)
  %p1 = f32[4096]{0} parameter(1)
  %dot = f32[256,256]{1,0} dot(f32[256,256]{1,0} %p0, f32[256,256]{1,0} %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[4096]{0} all-reduce(f32[4096]{0} %p1), replica_groups={{0,1,2,3},{4,5,6,7}}, use_global_device_ids=true, to_apply=%add
  %use = f32[4096]{0} add(f32[4096]{0} %ar, f32[4096]{0} %ar)
  ROOT %t = (f32[4096]{0}, f32[256,256]{1,0}) tuple(f32[4096]{0} %use, f32[256,256]{1,0} %dot)
}
"""


class TestOverlapAudit:
    def test_async_pair_counted_with_window_compute(self, mesh):
        ca = _load_comm_audit()
        records, summary = ca.analyze_overlap(_ASYNC_HLO, mesh)
        assert summary["n_collectives"] == 1
        assert summary["n_async_pairs"] == 1
        (rec,) = records
        assert rec["async_pair"] and rec["op"] == "all-reduce"
        assert rec["axis"] == "dcn"  # groups span the dcn axis
        assert rec["independent_compute_s"] > 0  # the dot in the window
        assert rec["overlappable"]

    def test_sync_collective_independent_compute(self, mesh):
        ca = _load_comm_audit()
        records, summary = ca.analyze_overlap(_SYNC_HLO, mesh)
        assert summary["n_async_pairs"] == 0
        (rec,) = records
        assert rec["axis"] == "ici"  # groups stay inside each slice
        # the dot neither feeds nor consumes the all-reduce
        assert rec["overlappable"]
        assert rec["independent_compute_s"] > 0

    def test_descendants_and_ancestors_excluded(self, mesh):
        ca = _load_comm_audit()
        # make the dot CONSUME the reduce: no independent compute left
        hlo = _SYNC_HLO.replace(
            "dot(f32[256,256]{1,0} %p0, f32[256,256]{1,0} %p0)",
            "dot(f32[256,256]{1,0} %p0, f32[256,256]{1,0} %dep)",
        ).replace(
            "%p1 = f32[4096]{0} parameter(1)",
            "%p1 = f32[4096]{0} parameter(1)\n"
            "  %dep = f32[256,256]{1,0} bitcast(f32[4096]{0} %ar)",
        )
        records, _ = ca.analyze_overlap(hlo, mesh)
        (rec,) = records
        assert not rec["overlappable"]

    def test_compiled_pipelined_loop_fully_overlappable(self, mesh):
        """The real thing: compile the 2-microbatch pipelined loop and
        every grad collective must have independent compute; the
        deferred loop must have strictly less of it in total."""
        ca = _load_comm_audit()
        txt, m = ca.compile_grad_sync_loop(
            True, None, ici_size=ICI, bucket_bytes=48 * 1024,
            num_micro=2)
        _, over = ca.analyze_overlap(txt, m)
        assert over["n_collectives"] > 0
        assert over["overlappable_frac"] == 1.0
        txt_d, m_d = ca.compile_grad_sync_loop(
            False, None, ici_size=ICI, bucket_bytes=48 * 1024,
            num_micro=2)
        _, deferred = ca.analyze_overlap(txt_d, m_d)
        assert over["independent_compute_ms"] > \
            deferred["independent_compute_ms"]
