"""int8 ICI gather-leg compression parity suite (EQuARX's ICI half).

Covers the new ``CompressionConfig(ici_legs=True)`` surface end to
end on the 8-device virtual (dcn=2 x ici=4) mesh: row-wise quantize
numerics, the chunk-preserving quantized reduce-scatter / all-gather
legs, the hierarchical reduce with both ICI legs compressed (stateless
and with error feedback), the DEFAULT-PATH pin (``ici_legs=False``
stays bit-identical to an inlined copy of the dcn-only int8 reduce),
bucketed/Reducer threading, ZeRO's compressed RS leg, and the residual
state's checkpoint round trip.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.quantization import (
    CompressionConfig,
    dequantize_rows,
    hierarchical_residual_sizes,
    quantize_blockwise,
    quantize_rows,
    quantized_all_gather,
    quantized_psum,
    quantized_reduce_scatter,
)
from apex_tpu.parallel import (
    all_reduce_gradients,
    hierarchical_data_parallel_mesh,
)
from apex_tpu.parallel.distributed import (
    Reducer,
    comm_state_specs,
    init_comm_state,
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


def _grads():
    return {
        "w": jax.random.normal(jax.random.PRNGKey(0), (8, 41, 3)),
        "b": jax.random.normal(jax.random.PRNGKey(1), (8, 17)),
    }


def _mean_ref(g):
    return np.broadcast_to(
        np.mean(np.asarray(g), axis=0, keepdims=True), g.shape)


# ---------------------------------------------------------------- numerics


class TestQuantizeRows:
    def test_single_row_matches_blockwise(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 100))
        q1, s1 = quantize_rows(x, 32)
        q2, s2 = quantize_blockwise(x[0], 32)
        np.testing.assert_array_equal(np.asarray(q1[0]), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1[0]), np.asarray(s2))

    def test_blocks_never_straddle_rows(self):
        # rows quantized together vs separately must agree exactly —
        # the chunk-preservation property the RS/AG legs rely on
        x = jax.random.normal(jax.random.PRNGKey(3), (4, 37))
        q, s = quantize_rows(x, 16)
        assert q.shape == (4, 37) and s.shape == (4, 3)
        for r in range(4):
            qr, sr = quantize_rows(x[r:r + 1], 16)
            np.testing.assert_array_equal(np.asarray(q[r]),
                                          np.asarray(qr[0]))
            np.testing.assert_array_equal(np.asarray(s[r]),
                                          np.asarray(sr[0]))

    def test_roundtrip_error_bounded(self):
        x = jax.random.normal(jax.random.PRNGKey(4), (3, 128)) * 5.0
        q, s = quantize_rows(x, 64)
        back = dequantize_rows(q, s, 64)
        err = np.abs(np.asarray(x - back))
        bound = np.repeat(np.asarray(s), 64, axis=1) / 2 + 1e-7
        assert np.all(err <= bound)

    def test_stochastic_needs_key(self):
        x = jnp.ones((2, 8))
        with pytest.raises(ValueError, match="key"):
            quantize_rows(x, 4, rounding="stochastic")


class TestResidualSizes:
    def test_dcn_only_sizes_unchanged(self):
        # ici_legs=False must size exactly like the PR 3 layout
        sizes = hierarchical_residual_sizes(100, DCN, ICI, 16)
        chunk = (100 + 3) // 4  # ici-padded chunk
        padded = chunk + (-chunk) % (DCN * 16)
        assert sizes == {"push": padded, "pull": padded // DCN}

    def test_ici_legs_adds_leg_buffers(self):
        sizes = hierarchical_residual_sizes(100, DCN, ICI, 16,
                                            ici_legs=True)
        chunk = (100 + 3) // 4
        assert sizes["ici_push"] == ICI * chunk
        assert sizes["ici_pull"] == chunk

    def test_init_comm_state_sizes_from_config(self, mesh):
        local = {"w": jnp.zeros((1, 41, 3)), "b": jnp.zeros((1, 17))}
        cfg = CompressionConfig(block_size=64, ici_legs=True)
        state = init_comm_state(local, AXES, cfg, mesh=mesh)
        for k, leaf in local.items():
            sizes = hierarchical_residual_sizes(
                int(jnp.size(leaf)), DCN, ICI, 64, True)
            res = state["residuals"][k]
            assert set(res) == set(sizes)
            for name, n in sizes.items():
                assert res[name].size == 8 * n, (k, name)


# ------------------------------------------------------------- collectives


class TestLegCollectives:
    def test_quantized_rs_preserves_chunks(self, mesh):
        g = jax.random.normal(jax.random.PRNGKey(5), (8, 120))
        cfg = CompressionConfig(block_size=16, error_feedback=False)

        def rs(x):
            c, _ = quantized_reduce_scatter(x.reshape(-1), "ici", cfg)
            return c

        def rs_ref(x):
            return jax.lax.psum_scatter(x.reshape(-1), "ici",
                                        tiled=True)

        out = jax.jit(smap(rs, mesh, (P(AXES),), P(AXES)))(g)
        ref = jax.jit(smap(rs_ref, mesh, (P(AXES),), P(AXES)))(g)
        amax = np.max(np.abs(np.asarray(ref)))
        assert np.max(np.abs(np.asarray(out) - np.asarray(ref))) \
            < 0.05 * amax

    def test_quantized_rs_rejects_undivisible(self, mesh):
        cfg = CompressionConfig(error_feedback=False)

        def bad(x):
            # local (1, 7) -> 7 elements, not divisible by ici=4
            c, _ = quantized_reduce_scatter(x.reshape(-1), "ici", cfg)
            return c

        with pytest.raises(ValueError, match="size % world"):
            jax.jit(smap(bad, mesh, (P(AXES),), P(AXES))
                    )(jnp.ones((8, 7)))

    def test_quantized_ag_matches_gather(self, mesh):
        x = jax.random.normal(jax.random.PRNGKey(6), (8, 25))
        cfg = CompressionConfig(block_size=8, error_feedback=False)

        def ag(c):
            out, _ = quantized_all_gather(c.reshape(-1), "ici", cfg)
            return out

        out = jax.jit(smap(
            lambda c: ag(c),
            mesh, (P((*AXES,)),), P(("dcn",)),
        ))(x.reshape(8, 25))
        # each dcn group gathers its own 4 ici chunks: compare against
        # the exact concatenation
        got = np.asarray(out).reshape(DCN, ICI * 25)
        ref = np.asarray(x).reshape(DCN, ICI * 25)
        amax = np.max(np.abs(ref))
        assert np.max(np.abs(got - ref)) < 0.02 * amax


class TestHierarchicalICILegs:
    def test_default_path_bit_identical_to_inlined_seed(self, mesh):
        """ici_legs=False must not move a bit of the dcn-only int8
        reduce: pinned against an inlined copy of its seed semantics."""
        from apex_tpu.transformer.tensor_parallel.mappings import (
            all_gather_invariant,
        )

        grads = _grads()
        spec = jax.tree.map(lambda _: P(AXES), grads)
        cfg = CompressionConfig(block_size=64, error_feedback=False)

        def seed(g):
            def one(x):
                n = x.size
                flat = x.reshape(-1)
                pad = (-n) % ICI
                if pad:
                    flat = jnp.concatenate(
                        [flat, jnp.zeros((pad,), flat.dtype)])
                chunk = jax.lax.psum_scatter(flat, "ici", tiled=True)
                chunk, _ = quantized_psum(chunk, "dcn", cfg)
                out = all_gather_invariant(chunk, "ici", axis=0,
                                           tiled=True)
                if pad:
                    out = out[:n]
                return out.reshape(x.shape) / 8.0
            return jax.tree.map(one, g)

        ours = jax.jit(smap(
            lambda g: all_reduce_gradients(g, AXES, compression=cfg),
            mesh, (spec,), spec))(grads)
        ref = jax.jit(smap(seed, mesh, (spec,), spec))(grads)
        for k in grads:
            np.testing.assert_array_equal(
                np.asarray(ours[k]), np.asarray(ref[k]))

    def test_ici_legs_stateless_tracks_mean(self, mesh):
        grads = _grads()
        spec = jax.tree.map(lambda _: P(AXES), grads)
        cfg = CompressionConfig(block_size=64, error_feedback=False,
                                ici_legs=True)
        out = jax.jit(smap(
            lambda g: all_reduce_gradients(g, AXES, compression=cfg),
            mesh, (spec,), spec))(grads)
        for k in grads:
            ref = _mean_ref(grads[k])
            amax = np.max(np.abs(ref))
            # three quantization events instead of two: a wider but
            # still small band
            assert np.max(np.abs(np.asarray(out[k]) - ref)) \
                < 0.15 * amax

    def test_error_feedback_improves_time_average(self, mesh):
        grads = _grads()
        local = jax.tree.map(
            lambda g: jnp.zeros((1,) + g.shape[1:]), grads)
        spec = jax.tree.map(lambda _: P(AXES), grads)
        cfg = CompressionConfig(block_size=64, ici_legs=True)
        state = init_comm_state(local, AXES, cfg, mesh=mesh)
        cspecs = comm_state_specs(state, AXES)
        step = jax.jit(smap(
            lambda g, st: all_reduce_gradients(
                g, AXES, compression=cfg, comm_state=st),
            mesh, (spec, cspecs), (spec, cspecs)))
        outs = []
        for _ in range(20):
            out, state = step(grads, state)
            outs.append(np.asarray(out["w"]))
        assert int(state["step"]) == 20
        ref = _mean_ref(grads["w"])
        single = np.max(np.abs(outs[0] - ref))
        averaged = np.max(np.abs(np.mean(outs, axis=0) - ref))
        assert averaged < single / 3

    def test_stale_comm_state_rejected(self, mesh):
        # a comm state built WITHOUT ici_legs cannot silently feed the
        # ici-compressed reduce
        grads = _grads()
        local = jax.tree.map(
            lambda g: jnp.zeros((1,) + g.shape[1:]), grads)
        spec = jax.tree.map(lambda _: P(AXES), grads)
        old = init_comm_state(local, AXES,
                              CompressionConfig(block_size=64),
                              mesh=mesh)
        cfg = CompressionConfig(block_size=64, ici_legs=True)
        cspecs = comm_state_specs(old, AXES)
        with pytest.raises(ValueError, match="ici_push"):
            jax.jit(smap(
                lambda g, st: all_reduce_gradients(
                    g, AXES, compression=cfg, comm_state=st),
                mesh, (spec, cspecs), (spec, cspecs)))(grads, old)
        # ...and the opposite direction: an ici-sized state with
        # ici_legs=False would silently drop the leg residuals from
        # the returned state — refused, not mis-shaped
        new = init_comm_state(local, AXES, cfg, mesh=mesh)
        nspecs = comm_state_specs(new, AXES)
        off = CompressionConfig(block_size=64)
        with pytest.raises(ValueError, match="ici_legs"):
            jax.jit(smap(
                lambda g, st: all_reduce_gradients(
                    g, AXES, compression=off, comm_state=st),
                mesh, (spec, nspecs), (spec, nspecs)))(grads, new)

    def test_bucketed_reduce_with_ici_state(self, mesh):
        grads = _grads()
        local = jax.tree.map(
            lambda g: jnp.zeros((1,) + g.shape[1:]), grads)
        spec = jax.tree.map(lambda _: P(AXES), grads)
        cfg = CompressionConfig(block_size=64, ici_legs=True)
        state = init_comm_state(local, AXES, cfg, mesh=mesh,
                                bucket_bytes=256)
        for res in state["residuals"].values():
            assert {"push", "pull", "ici_push", "ici_pull"} == set(res)
        cspecs = comm_state_specs(state, AXES)
        step = jax.jit(smap(
            lambda g, st: all_reduce_gradients(
                g, AXES, compression=cfg, comm_state=st,
                overlap_grad_sync=True, bucket_bytes=256),
            mesh, (spec, cspecs), (spec, cspecs)))
        out, state = step(grads, state)
        for k in grads:
            ref = _mean_ref(grads[k])
            assert np.max(np.abs(np.asarray(out[k]) - ref)) \
                < 0.15 * np.max(np.abs(ref))

    def test_reducer_pipelined_with_ici_compression(self, mesh):
        x = jax.random.normal(jax.random.PRNGKey(7), (8, 120))

        def run_loop(red):
            def stp(xs):
                acc = red.init(xs)
                for k in range(3):
                    acc = red.accumulate(acc, (1.0 + 0.5 * k) * xs)
                g, _ = red.reduce(acc)
                return g
            return jax.jit(smap(stp, mesh, (P(AXES),), P(AXES)))(x)

        deferred = run_loop(Reducer(axis_name=AXES))
        pip = run_loop(Reducer(
            axis_name=AXES, overlap_grad_sync=True, bucket_bytes=256,
            compression=CompressionConfig(block_size=64,
                                          ici_legs=True)))
        amax = np.max(np.abs(np.asarray(deferred)))
        assert np.max(np.abs(np.asarray(pip) - np.asarray(deferred))) \
            < 0.1 * amax

    def test_residual_checkpoint_roundtrip_bit_identical(
            self, mesh, tmp_path):
        from apex_tpu import checkpoint

        grads = _grads()
        local = jax.tree.map(
            lambda g: jnp.zeros((1,) + g.shape[1:]), grads)
        spec = jax.tree.map(lambda _: P(AXES), grads)
        cfg = CompressionConfig(block_size=64, ici_legs=True)
        cstate = init_comm_state(local, AXES, cfg, mesh=mesh)
        cspecs = comm_state_specs(cstate, AXES)
        step = jax.jit(smap(
            lambda g, st: all_reduce_gradients(
                g, AXES, compression=cfg, comm_state=st),
            mesh, (spec, cspecs), (spec, cspecs)))

        def run(resume_at=None):
            state = jax.tree.map(jnp.array, cstate)
            outs = []
            for i in range(6):
                out, state = step(grads, state)
                outs.append(np.asarray(out["w"]))
                if resume_at is not None and i == resume_at:
                    path = str(tmp_path / f"ck{i}")
                    saved = {"comm": jax.device_get(state)}
                    checkpoint.save(path, saved)
                    state = checkpoint.restore(
                        path, target=saved,
                        verify_integrity=True)["comm"]
            return outs

        a = run()
        b = run(resume_at=2)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)


class TestZeroICILegs:
    @pytest.fixture()
    def zmesh(self):
        from apex_tpu.transformer import parallel_state

        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            data_parallel_ici_size_=ICI)
        yield mesh
        parallel_state.destroy_model_parallel()

    def test_adam_ici_tracks_uncompressed(self, zmesh):
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        params = {"w": jax.random.normal(jax.random.PRNGKey(8),
                                         (37, 5)),
                  "b": jnp.zeros((11,))}
        pspec = jax.tree.map(lambda _: P(), params)
        g = jax.tree.map(
            lambda p: 0.1 * jax.random.normal(
                jax.random.PRNGKey(9), jnp.shape(p)), params)

        def run(comp):
            opt = DistributedFusedAdam(lr=1e-3, axis_name=AXES,
                                       compression=comp)
            sspecs = opt.state_specs()
            if comp is not None and comp.ici_legs:
                assert "ici_push" in sspecs["comm"]
            st = jax.jit(smap(opt.init, zmesh, (pspec,), sspecs)
                         )(params)
            newp, st = jax.jit(smap(
                lambda s, gg, p: opt.step(s, gg, p),
                zmesh, (sspecs, pspec, pspec), (pspec, sspecs)))(
                    st, g, params)
            return newp, st

        base, _ = run(None)
        comp, st = run(CompressionConfig(block_size=32, ici_legs=True))
        assert st["comm"]["ici_push"].size > 0
        for k in params:
            # Adam's sign-normalized update can flip where a gradient
            # sits at quantization-noise scale: bound by the 2*lr that
            # one flipped element can move
            np.testing.assert_allclose(
                np.asarray(comp[k]), np.asarray(base[k]), atol=2.5e-3)

    def test_lamb_ici_runs(self, zmesh):
        from apex_tpu.contrib.optimizers import DistributedFusedLAMB

        params = {"w": jax.random.normal(jax.random.PRNGKey(10),
                                         (24, 6))}
        pspec = jax.tree.map(lambda _: P(), params)
        g = jax.tree.map(
            lambda p: 0.1 * jax.random.normal(
                jax.random.PRNGKey(11), jnp.shape(p)), params)
        opt = DistributedFusedLAMB(
            lr=1e-3, axis_name=AXES,
            compression=CompressionConfig(block_size=32,
                                          ici_legs=True))
        sspecs = opt.state_specs()
        st = jax.jit(smap(opt.init, zmesh, (pspec,), sspecs))(params)
        newp, st = jax.jit(smap(
            lambda s, gg, p: opt.step(s, gg, p),
            zmesh, (sspecs, pspec, pspec), (pspec, sspecs)))(
                st, g, params)
        assert np.all(np.isfinite(np.asarray(newp["w"])))


class TestCommEvents:
    def test_bucket_events_report_compressed_ici_estimates(self, mesh):
        from apex_tpu.telemetry import events as tlm_events

        captured = []

        class Sink:
            def event(self, kind, **fields):
                if kind == "comm_bucket":
                    captured.append(fields)

        grads = _grads()
        spec = jax.tree.map(lambda _: P(AXES), grads)

        def trace_with(cfg):
            captured.clear()
            sink = Sink()
            tlm_events.add_sink(sink)
            try:
                jax.jit(smap(
                    lambda g: all_reduce_gradients(
                        g, AXES, compression=cfg,
                        overlap_grad_sync=True, bucket_bytes=256),
                    mesh, (spec,), spec)).lower(grads)
            finally:
                tlm_events.remove_sink(sink)
            return list(captured)

        plain = trace_with(CompressionConfig(block_size=64,
                                             error_feedback=False))
        ici = trace_with(CompressionConfig(block_size=64,
                                           error_feedback=False,
                                           ici_legs=True))
        assert plain and ici
        for a, b in zip(plain, ici):
            assert not a["ici_compressed"] and b["ici_compressed"]
            # every bucket shrinks; the ~4x asymptote needs the chunk
            # to amortize the fp32 scale sidecar (tiny buckets pay
            # one scale per block regardless)
            assert b["rs_ici_wire_bytes"] < a["rs_ici_wire_bytes"]
            assert b["ag_ici_wire_bytes"] < a["ag_ici_wire_bytes"]
            assert b["ar_dcn_wire_bytes"] == a["ar_dcn_wire_bytes"]
            if a["elements"] >= 100:
                assert b["rs_ici_wire_bytes"] \
                    < a["rs_ici_wire_bytes"] / 3
                assert b["ag_ici_wire_bytes"] \
                    < a["ag_ici_wire_bytes"] / 3
