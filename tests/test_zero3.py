"""Full-parameter sharding (ZeRO-3/FSDP) tests.

The contract under test (apex_tpu/parallel/zero3.py +
contrib/optimizers/distributed.py shard_params mode): parameters live
as 1-D fp32 shards in the bucket-shaped flat layout, gather-on-use
reconstructs the model-dtype tree BIT-identically, the sharded update
matches the state-sharding ZeRO path bitwise at compression=None
(Adam; LAMB within reduction-order ulps — its segment norms group
partial sums at different shard boundaries), the int8 gather/RS legs
track the exact path within quantization tolerance with checkpointable
error-feedback residuals, and a ZeRO-3 checkpoint resumes into a
replicated-eval setup with bit-identical weights.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.contrib.optimizers import (
    DistributedFusedAdam,
    DistributedFusedLAMB,
    reestablish_replicated,
)
from apex_tpu.ops.quantization import (
    CompressionConfig,
    zero3_residual_sizes,
)
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import (
    Zero3Layout,
    hierarchical_data_parallel_mesh,
)
from apex_tpu.transformer import parallel_state


@pytest.fixture
def mesh():
    m = parallel_state.initialize_model_parallel()
    yield m
    parallel_state.destroy_model_parallel()


@pytest.fixture
def hier_mesh():
    yield hierarchical_data_parallel_mesh(ici_size=4)


def make_params_grads(key, bf16_leaf=False):
    ks = jax.random.split(key, 6)
    params = {
        "w": jax.random.normal(ks[0], (13, 7)),   # odd sizes: padding
        "b": jax.random.normal(ks[1], (5,)),
        "h": jax.random.normal(ks[2], (3, 11)),
    }
    grads = {
        "w": 0.1 * jax.random.normal(ks[3], (13, 7)),
        "b": 0.1 * jax.random.normal(ks[4], (5,)),
        "h": 0.1 * jax.random.normal(ks[5], (3, 11)),
    }
    if bf16_leaf:
        params["h"] = params["h"].astype(jnp.bfloat16)
        grads["h"] = grads["h"].astype(jnp.bfloat16)
    return params, grads


def zero3_roundtrip(mesh, opt, params, grads, steps=3,
                    finite_seq=None, axes_spec=None):
    """Run `steps` ZeRO-3 steps (gather-on-use inside the same compiled
    program) and return (gathered_params, shards, state)."""
    opt.build_layout(params, mesh=mesh)
    pspec = jax.tree.map(lambda _: P(), params)
    sspec, stspecs = opt.shard_spec(), opt.state_specs()
    init_sh = jax.jit(jax.shard_map(
        opt.init_shards, mesh=mesh, in_specs=(pspec,), out_specs=sspec))
    shards = init_sh(params)
    state = jax.jit(jax.shard_map(
        opt.init, mesh=mesh, in_specs=(sspec,), out_specs=stspecs
    ))(shards)

    def train(sh, st, g, fin):
        p, st = opt.gather_params(sh, st)
        del p  # the gathered weights feed fwd/bwd in a real step
        return opt.step(st, g, sh, grads_finite=fin)

    step = jax.jit(jax.shard_map(
        train, mesh=mesh,
        in_specs=(sspec, stspecs, pspec, P()),
        out_specs=(sspec, stspecs),
    ))
    for i in range(steps):
        fin = jnp.array(True if finite_seq is None else finite_seq[i])
        shards, state = step(shards, state, grads, fin)
    gather = jax.jit(jax.shard_map(
        lambda s, t: opt.gather_params(s, t)[0], mesh=mesh,
        in_specs=(sspec, stspecs), out_specs=pspec))
    return gather(shards, state), shards, state


def zero1_reference(mesh, make_opt, params, grads, steps=3):
    opt = make_opt()
    specs = opt.state_specs()
    pspec = jax.tree.map(lambda _: P(), params)
    init = jax.jit(jax.shard_map(
        opt.init, mesh=mesh, in_specs=(pspec,), out_specs=specs))
    state = init(params)
    step = jax.jit(jax.shard_map(
        lambda st, g, p: opt.step(st, g, p), mesh=mesh,
        in_specs=(specs, pspec, pspec), out_specs=(pspec, specs)))
    p = params
    for _ in range(steps):
        p, state = step(state, grads, p)
    return p


class TestLayout:
    def test_plan_invariants(self):
        params, _ = make_params_grads(jax.random.PRNGKey(0))
        lay = Zero3Layout(params, world=8, bucket_bytes=128)
        # every leaf exactly once; reverse-tree bucket order
        seen = [i for b in lay.plan.buckets for i in b.leaf_ids]
        assert sorted(seen) == list(range(lay.num_leaves))
        first_ids = [b.leaf_ids[0] for b in lay.plan.buckets]
        assert first_ids == sorted(first_ids, reverse=True)
        # per-bucket padding to the world, concatenated chunk layout
        for b, padded, chunk in zip(lay.plan.buckets, lay.padded,
                                    lay.chunk_sizes):
            assert padded % 8 == 0 and padded >= b.size
            assert chunk == padded // 8
        assert lay.shard_size == sum(lay.chunk_sizes)
        assert lay.offsets[0] == 0

    def test_segment_ids_cover_leaves_and_padding(self):
        params, _ = make_params_grads(jax.random.PRNGKey(0))
        lay = Zero3Layout(params, world=8, bucket_bytes=128)
        ids = lay.segment_ids()
        counts = np.bincount(ids, minlength=lay.num_leaves + 1)
        sizes = [int(np.prod(jnp.shape(l)))
                 for l in jax.tree.leaves(params)]
        for i, s in enumerate(sizes):
            assert counts[i] == s
        assert counts[lay.num_leaves] == sum(lay.padded) - sum(sizes)

    def test_shard_unshard_roundtrip(self, mesh):
        params, _ = make_params_grads(jax.random.PRNGKey(1),
                                      bf16_leaf=True)
        lay = Zero3Layout(params, world=8, bucket_bytes=64)
        pspec = jax.tree.map(lambda _: P(), params)
        shard = jax.jit(jax.shard_map(
            lambda p: lay.shard_params(p, jax.lax.axis_index("dp")),
            mesh=mesh, in_specs=(pspec,), out_specs=P("dp")))(params)
        rebuilt = lay.unshard(np.asarray(jax.device_get(shard)))
        for a, b in zip(jax.tree.leaves(rebuilt),
                        jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert a.dtype == b.dtype

    def test_unshard_wrong_world_rejected(self):
        params, _ = make_params_grads(jax.random.PRNGKey(1))
        lay = Zero3Layout(params, world=8, bucket_bytes=64)
        with pytest.raises(ValueError, match="world"):
            lay.unshard(np.zeros((lay.shard_size * 4,), np.float32))

    def test_residual_sizes_shared_definition(self):
        params, _ = make_params_grads(jax.random.PRNGKey(0))
        lay = Zero3Layout(params, world=4, bucket_bytes=128)
        cfg = CompressionConfig(block_size=32, ici_legs=True)
        sizes = lay.residual_sizes(2, 4, cfg)
        for name, b in zip(lay.names, lay.plan.buckets):
            assert sizes[name] == zero3_residual_sizes(
                b.size, 2, 4, 32, True)
            assert set(sizes[name]) == {"push", "pull", "ici_push",
                                        "ag"}
        no_legs = lay.residual_sizes(2, 4, CompressionConfig(
            block_size=32))
        assert set(no_legs[lay.names[0]]) == {"push", "pull"}


class TestZero3Adam:
    def test_gather_is_bit_identical(self, mesh):
        params, grads = make_params_grads(jax.random.PRNGKey(0),
                                          bf16_leaf=True)
        opt = DistributedFusedAdam(lr=1e-2, shard_params=True,
                                   bucket_bytes=64)
        opt.build_layout(params, mesh=mesh)
        pspec = jax.tree.map(lambda _: P(), params)
        sspec = opt.shard_spec()
        shards = jax.jit(jax.shard_map(
            opt.init_shards, mesh=mesh, in_specs=(pspec,),
            out_specs=sspec))(params)
        gathered = jax.jit(jax.shard_map(
            lambda s: opt.gather_params(s)[0], mesh=mesh,
            in_specs=(sspec,), out_specs=pspec))(shards)
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(gathered[k]), np.asarray(params[k]))
            assert gathered[k].dtype == params[k].dtype

    def test_matches_zero1_bitwise(self, mesh):
        """The load-bearing parity: parameter sharding changes the
        storage layout, not one bit of the Adam math."""
        params, grads = make_params_grads(jax.random.PRNGKey(0))
        opt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                   shard_params=True, bucket_bytes=64)
        p3, _, _ = zero3_roundtrip(mesh, opt, params, grads)
        p1 = zero1_reference(
            mesh, lambda: DistributedFusedAdam(lr=1e-2,
                                               weight_decay=0.01),
            params, grads)
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(p3[k]), np.asarray(p1[k]))

    def test_matches_unsharded_fusedadam(self, mesh):
        params, grads = make_params_grads(jax.random.PRNGKey(0))
        opt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                   shard_params=True, bucket_bytes=64)
        p3, _, _ = zero3_roundtrip(mesh, opt, params, grads)
        ref = FusedAdam(lr=1e-2, weight_decay=0.01,
                        master_weights=True)
        rstate = ref.init(params)
        rp = params
        for _ in range(3):
            rp, rstate = ref.step(rstate, grads, rp)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p3[k]), np.asarray(rp[k]),
                rtol=1e-6, atol=1e-7)

    def test_hier_matches_flat(self, hier_mesh):
        params, grads = make_params_grads(jax.random.PRNGKey(2))
        hopt = DistributedFusedAdam(
            lr=1e-2, weight_decay=0.01, axis_name=("dcn", "ici"),
            shard_params=True, bucket_bytes=64)
        hp, _, _ = zero3_roundtrip(hier_mesh, hopt, params, grads)
        fmesh = parallel_state.initialize_model_parallel()
        try:
            fopt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                        shard_params=True,
                                        bucket_bytes=64)
            fp, _, _ = zero3_roundtrip(fmesh, fopt, params, grads)
        finally:
            parallel_state.destroy_model_parallel()
        for k in params:
            np.testing.assert_allclose(
                np.asarray(hp[k]), np.asarray(fp[k]),
                rtol=1e-6, atol=1e-7)

    def test_bf16_params_stay_bf16_masters_fp32(self, mesh):
        params, grads = make_params_grads(jax.random.PRNGKey(3),
                                          bf16_leaf=True)
        opt = DistributedFusedAdam(lr=1e-2, shard_params=True,
                                   bucket_bytes=64)
        p3, shards, state = zero3_roundtrip(mesh, opt, params, grads,
                                            steps=1)
        assert p3["h"].dtype == jnp.bfloat16
        assert shards.dtype == jnp.float32
        assert state["exp_avg"].dtype == jnp.float32
        assert "master" not in state  # the shard IS the master

    def test_overflow_skip_freezes_shards_and_state(self, mesh):
        params, grads = make_params_grads(jax.random.PRNGKey(4))
        opt = DistributedFusedAdam(lr=1e-2, shard_params=True,
                                   bucket_bytes=64)
        p3, shards, state = zero3_roundtrip(
            mesh, opt, params, grads, steps=2,
            finite_seq=[True, False])
        ref_p, ref_sh, ref_st = zero3_roundtrip(
            mesh, DistributedFusedAdam(lr=1e-2, shard_params=True,
                                       bucket_bytes=64),
            params, grads, steps=1)
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(p3[k]), np.asarray(ref_p[k]))
        np.testing.assert_array_equal(np.asarray(shards),
                                      np.asarray(ref_sh))
        assert int(state["step"]) == 1

    def test_state_specs_have_no_master(self, mesh):
        params, _ = make_params_grads(jax.random.PRNGKey(0))
        opt = DistributedFusedAdam(lr=1e-2, shard_params=True,
                                   bucket_bytes=64)
        opt.build_layout(params, mesh=mesh)
        specs = opt.state_specs()
        assert "master" not in specs
        assert specs["exp_avg"] == P("dp")
        assert specs["step"] == P()


class TestZero3Lamb:
    def test_matches_zero1_lamb(self, mesh):
        """Trust ratios are assembled from per-bucket segment sums —
        same math, different partial-sum grouping than the tree-order
        flat buffer, so ulp-level (not bitwise) agreement."""
        params, grads = make_params_grads(jax.random.PRNGKey(5))
        kw = dict(lr=1e-2, weight_decay=0.01, max_grad_norm=0.05)
        opt = DistributedFusedLAMB(shard_params=True, bucket_bytes=64,
                                   **kw)
        p3, _, _ = zero3_roundtrip(mesh, opt, params, grads)
        p1 = zero1_reference(
            mesh, lambda: DistributedFusedLAMB(**kw), params, grads)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p3[k]), np.asarray(p1[k]),
                rtol=1e-5, atol=1e-7)


class TestZero3Compression:
    def test_dcn_only_int8_leaves_param_gather_untouched(self,
                                                         hier_mesh):
        """ici_legs=False compresses ONLY the grad dcn leg: the param
        gather must stay full-width model dtype, pinned by comparing
        the gathered params against the uncompressed optimizer's after
        identical (compressed-grad) steps would diverge — so compare
        the GATHER itself on the same shards."""
        params, _ = make_params_grads(jax.random.PRNGKey(6))
        cfg = CompressionConfig(block_size=64, error_feedback=False)
        opt = DistributedFusedAdam(
            lr=1e-2, axis_name=("dcn", "ici"), shard_params=True,
            bucket_bytes=64, compression=cfg)
        opt.build_layout(params, mesh=hier_mesh)
        pspec = jax.tree.map(lambda _: P(), params)
        sspec = opt.shard_spec()
        shards = jax.jit(jax.shard_map(
            opt.init_shards, mesh=hier_mesh, in_specs=(pspec,),
            out_specs=sspec))(params)
        gathered = jax.jit(jax.shard_map(
            lambda s: opt.gather_params(s)[0], mesh=hier_mesh,
            in_specs=(sspec,), out_specs=pspec))(shards)
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(gathered[k]), np.asarray(params[k]))

    def test_ici_legs_tracks_exact_within_band(self, hier_mesh):
        params, grads = make_params_grads(jax.random.PRNGKey(7))
        exact = DistributedFusedAdam(
            lr=1e-2, axis_name=("dcn", "ici"), shard_params=True,
            bucket_bytes=128)
        pe, _, _ = zero3_roundtrip(hier_mesh, exact, params, grads)
        cfg = CompressionConfig(block_size=64, ici_legs=True)
        quant = DistributedFusedAdam(
            lr=1e-2, axis_name=("dcn", "ici"), shard_params=True,
            bucket_bytes=128, compression=cfg)
        pq, _, state = zero3_roundtrip(hier_mesh, quant, params, grads)
        for k in params:
            amax = float(np.max(np.abs(np.asarray(pe[k]))))
            np.testing.assert_allclose(
                np.asarray(pq[k]), np.asarray(pe[k]),
                atol=max(0.05 * amax, 1e-3))
        for name, res in state["comm"].items():
            assert set(res) == {"push", "pull", "ici_push", "ag"}

    def test_residual_checkpoint_roundtrip_bit_identical(self,
                                                         hier_mesh):
        """Save shards + state after 2 steps, rebuild host-side arrays
        (the checkpoint path), resume 2 more: bit-identical to the
        uninterrupted 4-step run — the EF residuals (incl. the ``ag``
        param-gather one) survive the round trip."""
        params, grads = make_params_grads(jax.random.PRNGKey(8))
        cfg = CompressionConfig(block_size=64, ici_legs=True)

        def make():
            return DistributedFusedAdam(
                lr=1e-2, axis_name=("dcn", "ici"), shard_params=True,
                bucket_bytes=128, compression=cfg)

        opt = make()
        opt.build_layout(params, mesh=hier_mesh)
        pspec = jax.tree.map(lambda _: P(), params)
        sspec, stspecs = opt.shard_spec(), opt.state_specs()
        place = lambda t, sp: jax.device_put(
            t, jax.tree.map(lambda s: NamedSharding(hier_mesh, s), sp,
                            is_leaf=lambda x: isinstance(x, P)))
        init_sh = jax.jit(jax.shard_map(
            opt.init_shards, mesh=hier_mesh, in_specs=(pspec,),
            out_specs=sspec))
        shards = init_sh(params)
        state = jax.jit(jax.shard_map(
            opt.init, mesh=hier_mesh, in_specs=(sspec,),
            out_specs=stspecs))(shards)

        def train(sh, st, g):
            p, st = opt.gather_params(sh, st)
            del p
            return opt.step(st, g, sh)

        step = jax.jit(jax.shard_map(
            train, mesh=hier_mesh,
            in_specs=(sspec, stspecs, pspec), out_specs=(sspec, stspecs)))
        for _ in range(2):
            shards, state = step(shards, state, grads)
        # checkpoint: host round trip, then place anew
        saved = (jax.device_get(shards), jax.device_get(state))
        shards2 = place(saved[0], sspec)
        state2 = place(saved[1], stspecs)
        for _ in range(2):
            shards, state = step(shards, state, grads)
            shards2, state2 = step(shards2, state2, grads)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(shards)),
            np.asarray(jax.device_get(shards2)))
        for a, b in zip(jax.tree.leaves(jax.device_get(state)),
                        jax.tree.leaves(jax.device_get(state2))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_stochastic_rounding_runs(self, hier_mesh):
        params, grads = make_params_grads(jax.random.PRNGKey(9))
        cfg = CompressionConfig(block_size=64, ici_legs=True,
                                rounding="stochastic")
        opt = DistributedFusedAdam(
            lr=1e-2, axis_name=("dcn", "ici"), shard_params=True,
            bucket_bytes=128, compression=cfg)
        p, _, _ = zero3_roundtrip(hier_mesh, opt, params, grads,
                                  steps=2)
        for k in params:
            assert bool(np.all(np.isfinite(np.asarray(p[k]))))


class TestZero3Validation:
    def test_build_layout_requires_shard_params(self):
        opt = DistributedFusedAdam(lr=1e-2)
        with pytest.raises(ValueError, match="shard_params"):
            opt.build_layout({"w": jnp.zeros((4,))}, world=8)

    def test_layout_required_before_use(self):
        opt = DistributedFusedAdam(lr=1e-2, shard_params=True)
        with pytest.raises(ValueError, match="build_layout"):
            opt.gather_params(jnp.zeros((8,)))

    def test_compressed_allgather_rejected(self):
        with pytest.raises(ValueError, match="compressed_allgather"):
            DistributedFusedAdam(lr=1e-2, shard_params=True,
                                 compressed_allgather="bf16")

    def test_data_axis_sharded_leaves_rejected(self):
        with pytest.raises(NotImplementedError, match="ZeRO-3"):
            DistributedFusedAdam(
                lr=1e-2, shard_params=True,
                param_specs={"w": P(), "e": P("dp")})

    def test_init_rejects_replicated_tree(self, mesh):
        params, _ = make_params_grads(jax.random.PRNGKey(0))
        opt = DistributedFusedAdam(lr=1e-2, shard_params=True,
                                   bucket_bytes=64)
        opt.build_layout(params, mesh=mesh)
        with pytest.raises(ValueError, match="flat"):
            jax.jit(jax.shard_map(
                opt.init, mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P(), params),),
                out_specs=opt.state_specs()))(params)


class TestZero3Telemetry:
    def test_param_gather_events_and_phase(self, mesh):
        from apex_tpu.telemetry import events as tlm_events

        params, _ = make_params_grads(jax.random.PRNGKey(0))
        opt = DistributedFusedAdam(lr=1e-2, shard_params=True,
                                   bucket_bytes=64)
        opt.build_layout(params, mesh=mesh)
        pspec = jax.tree.map(lambda _: P(), params)
        sspec = opt.shard_spec()
        shards = jax.jit(jax.shard_map(
            opt.init_shards, mesh=mesh, in_specs=(pspec,),
            out_specs=sspec))(params)

        got = []

        class Sink:
            def event(self, kind, **fields):
                got.append((kind, fields))

        sink = Sink()
        tlm_events.add_sink(sink)
        try:
            fn = jax.jit(jax.shard_map(
                lambda s: opt.gather_params(s)[0], mesh=mesh,
                in_specs=(sspec,), out_specs=pspec))
            txt = fn.lower(shards).compile().as_text()
        finally:
            tlm_events.remove_sink(sink)
        # (a sink hears every kind: the compile also reports itself,
        # ``program_obtained``)
        got = [(k, f) for k, f in got if k == "param_gather"]
        names = [f["bucket"] for k, f in got]
        assert names == opt.layout.names
        for k, f in got:
            assert f["ag_ici_wire_bytes"] > 0
            assert f["compressed"] is False
        assert "tlm.param_gather" in txt

    def test_int8_gather_event_estimates_shrink(self, hier_mesh):
        from apex_tpu.telemetry import events as tlm_events

        params = {"w": jnp.zeros((64, 16))}
        cfgs = [None, CompressionConfig(block_size=64, ici_legs=True,
                                        error_feedback=False)]
        wire = []
        for cfg in cfgs:
            opt = DistributedFusedAdam(
                lr=1e-2, axis_name=("dcn", "ici"), shard_params=True,
                bucket_bytes=1 << 20, compression=cfg)
            opt.build_layout(params, mesh=hier_mesh)
            got = []

            class Sink:
                def event(self, kind, **fields):
                    got.append((kind, fields))

            sink = Sink()
            tlm_events.add_sink(sink)
            try:
                pspec = jax.tree.map(lambda _: P(), params)
                sspec = opt.shard_spec()
                shards = jax.jit(jax.shard_map(
                    opt.init_shards, mesh=hier_mesh, in_specs=(pspec,),
                    out_specs=sspec))(params)
                jax.jit(jax.shard_map(
                    lambda s: opt.gather_params(s)[0], mesh=hier_mesh,
                    in_specs=(sspec,), out_specs=pspec))(shards)
            finally:
                tlm_events.remove_sink(sink)
            got = [(k, f) for k, f in got if k == "param_gather"]
            assert got, "no param_gather events"
            wire.append(sum(f["ag_ici_wire_bytes"] for _, f in got))
        assert wire[0] / wire[1] > 3.0, (
            f"int8 param-AG estimate only {wire[0] / wire[1]:.2f}x "
            "smaller")


class TestReplicatedResume:
    """Satellite: resume a ZeRO-3 checkpoint into a replicated-eval
    setup — ``unshard_params`` of the checkpointed flat shard buffer
    must be bit-identical to the on-device gather, and a replicated
    forward must reproduce the sharded step's loss exactly."""

    def test_checkpoint_to_replicated_eval_bit_identical(self, mesh):
        params, grads = make_params_grads(jax.random.PRNGKey(11),
                                          bf16_leaf=True)
        opt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                   shard_params=True, bucket_bytes=64)
        gathered, shards, state = zero3_roundtrip(
            mesh, opt, params, grads, steps=2)
        # the "checkpoint": the device_get of the placed shard buffer
        ckpt = np.asarray(jax.device_get(shards))
        replicated = opt.unshard_params(ckpt)
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(replicated[k]), np.asarray(gathered[k]))
            assert replicated[k].dtype == params[k].dtype

        # replicated eval: a plain forward on the unsharded weights
        # equals the same forward on the gathered weights
        x = jax.random.normal(jax.random.PRNGKey(12), (4, 13))

        def fwd(p):
            h = jnp.tanh(x @ p["w"])
            return jnp.sum(h * h)

        np.testing.assert_array_equal(
            np.asarray(fwd(replicated)), np.asarray(fwd(gathered)))


class TestZero3GPTTraining:
    """End-to-end: a small GPT trains under ZeRO-3 (gather-on-use
    inside the compiled step) and tracks the replicated-FusedAdam run
    within the established band; bit-identical to the ZeRO-1
    state-sharding path at compression=None."""

    def _train(self, mode, steps=8, compression=None):
        from apex_tpu.models import GPTConfig, GPTModel

        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel()
        try:
            model = GPTModel(GPTConfig(
                vocab_size=64, num_layers=2, hidden_size=32,
                num_attention_heads=4, max_position_embeddings=16,
                compute_dtype=jnp.float32, remat=False,
                attention_impl="xla"))
            specs = model.param_specs()
            params = model.init(jax.random.PRNGKey(0))
            pspec = specs
            tokens = jax.random.randint(
                jax.random.PRNGKey(1), (8, 16), 0, 64)
            targets = jax.random.randint(
                jax.random.PRNGKey(2), (8, 16), 0, 64)
            losses = []
            if mode == "replicated":
                opt = FusedAdam(lr=1e-2, master_weights=True)
                st = opt.init(params)
                from apex_tpu.transformer.tensor_parallel.layers \
                    import state_specs_like

                stspecs = state_specs_like(specs, st)

                def train(p, s, tok, tgt):
                    loss, grads = jax.value_and_grad(model.loss)(
                        p, tok, tgt)
                    grads = jax.tree.map(
                        lambda g: jax.lax.pmean(g, "dp"), grads)
                    p, s = opt.step(s, grads, p)
                    return p, s, loss

                step = jax.jit(jax.shard_map(
                    train, mesh=mesh,
                    in_specs=(pspec, stspecs, P("dp"), P("dp")),
                    out_specs=(pspec, stspecs, P())))
                p, s = params, st
                for _ in range(steps):
                    p, s, loss = step(p, s, tokens, targets)
                    losses.append(float(loss))
                return losses, p
            opt = DistributedFusedAdam(
                lr=1e-2, shard_params=(mode == "zero3"),
                bucket_bytes=16 * 1024, compression=compression)
            # the GPT specs name "tp" (and the mesh carries pp): the
            # flat buffers then live per (pp, tp) position, which the
            # ZeRO specs must say — the trainer's composition rule
            # (examples/gpt_pretrain.py)
            maxes = ("pp", "tp")
            if mode == "zero3":
                opt.build_layout(params, mesh=mesh)
                sspec = opt.shard_spec(model_axes=maxes)
                stspecs = opt.state_specs(model_axes=maxes)
                shards = jax.jit(jax.shard_map(
                    opt.init_shards, mesh=mesh, in_specs=(pspec,),
                    out_specs=sspec))(params)
                st = jax.jit(jax.shard_map(
                    opt.init, mesh=mesh, in_specs=(sspec,),
                    out_specs=stspecs))(shards)

                def train(sh, s, tok, tgt):
                    p, s = opt.gather_params(sh, s)
                    p = reestablish_replicated(p, specs)
                    loss, grads = jax.value_and_grad(model.loss)(
                        p, tok, tgt)
                    sh, s = opt.step(s, grads, sh)
                    return sh, s, loss

                step = jax.jit(jax.shard_map(
                    train, mesh=mesh,
                    in_specs=(sspec, stspecs, P("dp"), P("dp")),
                    out_specs=(sspec, stspecs, P())))
                for _ in range(steps):
                    shards, st, loss = step(shards, st, tokens,
                                            targets)
                    losses.append(float(loss))
                gather = jax.jit(jax.shard_map(
                    lambda s, t: reestablish_replicated(
                        opt.gather_params(s, t)[0], specs),
                    mesh=mesh, in_specs=(sspec, stspecs),
                    out_specs=pspec))
                return losses, gather(shards, st)
            # zero1
            stspecs = opt.state_specs(model_axes=maxes)
            st = jax.jit(jax.shard_map(
                opt.init, mesh=mesh, in_specs=(pspec,),
                out_specs=stspecs))(params)

            def train(p, s, tok, tgt):
                loss, grads = jax.value_and_grad(model.loss)(
                    p, tok, tgt)
                p, s = opt.step(s, grads, p)
                return reestablish_replicated(p, specs), s, loss

            step = jax.jit(jax.shard_map(
                train, mesh=mesh,
                in_specs=(pspec, stspecs, P("dp"), P("dp")),
                out_specs=(pspec, stspecs, P())))
            p = params
            for _ in range(steps):
                p, st, loss = step(p, st, tokens, targets)
                losses.append(float(loss))
            return losses, p
        finally:
            parallel_state.destroy_model_parallel()

    def test_gpt_zero3_matches_zero1_bitwise_and_band(self):
        l3, p3 = self._train("zero3")
        l1, p1 = self._train("zero1")
        assert l3 == l1, (l3, l1)  # compression=None: bit-identical
        for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(p3),
            jax.tree_util.tree_leaves_with_path(p1),
        ):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=str(path))
        lr, _ = self._train("replicated")
        assert abs(l3[-1] - lr[-1]) < 3e-2, (l3[-1], lr[-1])
