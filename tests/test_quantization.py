"""Compressed-collectives parity suite on the 8-device virtual mesh.

Covers the quantized hierarchical gradient collectives end to end:
block-wise int8 quantize/dequantize numerics, ``compression=None``
bit-identity with the uncompressed hierarchical psum, int8 accuracy
with and without error feedback, the DDP/Reducer/ZeRO threading, a GPT
short-training run whose int8+error-feedback loss curve must track the
fp32-comms baseline within documented tolerance, and the residual
state's round-trip through the checkpoint layer.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.quantization import (
    CompressionConfig,
    as_compression_config,
    comm_residual_sizes,
    dequantize_blockwise,
    init_residual,
    quantize_blockwise,
)
from apex_tpu.parallel import (
    all_reduce_gradients,
    hierarchical_data_parallel_mesh,
)
from apex_tpu.parallel.distributed import (
    DistributedDataParallel,
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


# ---------------------------------------------------------------- numerics


class TestQuantizeBlockwise:
    def test_roundtrip_error_bounded_per_block(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1024,)) * 10.0
        q, s = quantize_blockwise(x, 64)
        assert q.dtype == jnp.int8 and s.dtype == jnp.float32
        assert s.shape == (16,)
        back = dequantize_blockwise(q, s, 64)
        err = np.abs(np.asarray(x - back)).reshape(16, 64)
        # nearest rounding: error <= scale/2 per block
        bound = np.asarray(s)[:, None] / 2 + 1e-7
        assert np.all(err <= bound)

    def test_partial_block_and_shape_preserved(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (7, 13))  # 91 elems
        q, s = quantize_blockwise(x, 32)
        assert q.shape == x.shape
        assert s.shape == (3,)  # ceil(91/32)
        back = dequantize_blockwise(q, s, 32)
        assert back.shape == x.shape
        amax = float(jnp.max(jnp.abs(x)))
        assert float(jnp.max(jnp.abs(x - back))) <= amax / 127

    def test_bf16_in_out(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (256,), jnp.bfloat16)
        q, s = quantize_blockwise(x, 128)
        back = dequantize_blockwise(q, s, 128, dtype=jnp.bfloat16)
        assert back.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(x, jnp.float32), np.asarray(back, jnp.float32),
            atol=float(jnp.max(jnp.abs(x))) / 100,
        )

    def test_zero_block_exact(self):
        x = jnp.zeros((128,))
        q, s = quantize_blockwise(x, 64)
        assert np.all(np.asarray(q) == 0)
        np.testing.assert_array_equal(
            np.asarray(dequantize_blockwise(q, s, 64)), 0.0
        )

    def test_deterministic_rounding_is_deterministic(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (512,))
        q1, s1 = quantize_blockwise(x, 64)
        q2, s2 = quantize_blockwise(x, 64)
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))

    def test_stochastic_rounding_unbiased(self):
        x = jax.random.normal(jax.random.PRNGKey(4), (256,))
        outs = []
        for i in range(64):
            q, s = quantize_blockwise(
                x, 64, "stochastic", jax.random.PRNGKey(i)
            )
            outs.append(np.asarray(dequantize_blockwise(q, s, 64)))
        single_err = np.max(np.abs(outs[0] - np.asarray(x)))
        mean_err = np.max(np.abs(np.mean(outs, axis=0) - np.asarray(x)))
        # the average over keys converges on the true value — the
        # defining property deterministic rounding lacks
        assert mean_err < single_err / 3

    def test_stochastic_requires_key(self):
        with pytest.raises(ValueError, match="PRNG key"):
            quantize_blockwise(jnp.ones((8,)), 8, "stochastic")

    def test_config_validation(self):
        assert as_compression_config(None) is None
        cfg = as_compression_config("int8")
        assert cfg.block_size == 256 and cfg.error_feedback
        assert as_compression_config(cfg) is cfg
        with pytest.raises(ValueError, match="method"):
            CompressionConfig(method="fp4")
        with pytest.raises(ValueError, match="rounding"):
            CompressionConfig(rounding="up")
        with pytest.raises(ValueError, match="block_size"):
            CompressionConfig(block_size=0)
        with pytest.raises(ValueError, match="compression must be"):
            as_compression_config(8)

    def test_residual_sizes(self):
        padded, shard = comm_residual_sizes(100, 2, 64)
        assert padded == 128 and shard == 64
        res = init_residual(100, 2, 64)
        assert res["push"].shape == (128,)
        assert res["pull"].shape == (64,)


# ------------------------------------------------------ hierarchical reduce


def _grads(key=5):
    ks = jax.random.split(jax.random.PRNGKey(key), 2)
    return {"w": jax.random.normal(ks[0], (8, 13, 7)),
            "b": jax.random.normal(ks[1], (8, 5))}


def _seed_hierarchical_mean(g, ici=ICI):
    """The pre-compression hierarchical psum, inlined verbatim from the
    seed (RS(ici) -> AR(dcn) -> AG(ici), then /world): the bit-identity
    reference for compression=None."""
    def one(g):
        n = g.size
        flat = g.reshape(-1)
        pad = (-n) % ici
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        chunk = jax.lax.psum_scatter(flat, "ici", tiled=True)
        chunk = jax.lax.psum(chunk, "dcn")
        out = jax.lax.all_gather(chunk, "ici", axis=0, tiled=True)
        if pad:
            out = out[:n]
        return (out.reshape(g.shape) / (DCN * ICI)).astype(g.dtype)

    return jax.tree.map(one, g)


class TestCompressedAllReduce:
    def test_compression_none_bit_identical_to_seed(self, mesh):
        grads = _grads()
        spec = jax.tree.map(lambda _: P(AXES), grads)
        ours = jax.jit(smap(
            lambda g: all_reduce_gradients(g, AXES),
            mesh, (spec,), spec))(grads)
        seed = jax.jit(smap(
            _seed_hierarchical_mean, mesh, (spec,), spec))(grads)
        for k in grads:
            np.testing.assert_array_equal(
                np.asarray(ours[k]), np.asarray(seed[k]))

    def test_int8_stateless_tracks_exact_mean(self, mesh):
        grads = _grads()
        spec = jax.tree.map(lambda _: P(AXES), grads)
        cfg = CompressionConfig(block_size=64, error_feedback=False)
        out = jax.jit(smap(
            lambda g: all_reduce_gradients(g, AXES, compression=cfg),
            mesh, (spec,), spec))(grads)
        for k in grads:
            ref = np.broadcast_to(
                np.mean(np.asarray(grads[k]), axis=0, keepdims=True),
                grads[k].shape)
            amax = np.max(np.abs(ref))
            assert np.max(np.abs(np.asarray(out[k]) - ref)) < 0.05 * amax

    def test_output_dtype_untouched(self, mesh):
        grads = {"w": jnp.ones((8, 16), jnp.bfloat16)}
        spec = {"w": P(AXES)}
        cfg = CompressionConfig(error_feedback=False)
        out = jax.jit(smap(
            lambda g: all_reduce_gradients(g, AXES, compression=cfg),
            mesh, (spec,), spec))(grads)
        assert out["w"].dtype == jnp.bfloat16

    def test_error_feedback_improves_time_average(self, mesh):
        grads = _grads()
        # per-device grad shapes (what the reduce sees inside shard_map)
        local = jax.tree.map(
            lambda g: jnp.zeros((1,) + g.shape[1:]), grads)
        spec = jax.tree.map(lambda _: P(AXES), grads)
        cfg = CompressionConfig(block_size=64)
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
        ref = np.broadcast_to(
            np.mean(np.asarray(grads["w"]), axis=0, keepdims=True),
            grads["w"].shape)
        single = np.max(np.abs(outs[0] - ref))
        averaged = np.max(np.abs(np.mean(outs, axis=0) - ref))
        # the residual compensates the rounding bias over steps
        assert averaged < single / 3

    def test_stochastic_rounding_in_collective(self, mesh):
        grads = _grads()
        local = jax.tree.map(
            lambda g: jnp.zeros((1,) + g.shape[1:]), grads)
        spec = jax.tree.map(lambda _: P(AXES), grads)
        cfg = CompressionConfig(block_size=64, rounding="stochastic",
                                error_feedback=False)
        # stochastic without a step source would re-roll the SAME
        # dither forever (a fixed bias): stateless use is refused
        with pytest.raises(ValueError, match="comm state"):
            all_reduce_gradients(grads, AXES, compression=cfg)
        state = init_comm_state(local, AXES, cfg, mesh=mesh)
        cspecs = comm_state_specs(state, AXES)
        step = jax.jit(smap(
            lambda g, st: all_reduce_gradients(
                g, AXES, compression=cfg, comm_state=st),
            mesh, (spec, cspecs), (spec, cspecs)))
        out1, state = step(grads, state)
        out2, state = step(grads, state)
        ref = np.broadcast_to(
            np.mean(np.asarray(grads["w"]), axis=0, keepdims=True),
            grads["w"].shape)
        amax = np.max(np.abs(ref))
        for out in (out1, out2):
            assert np.max(np.abs(np.asarray(out["w"]) - ref)) < 0.1 * amax
        # the step counter advanced the key: fresh dither each step
        assert np.any(np.asarray(out1["w"]) != np.asarray(out2["w"]))
        # EF off: residuals pass through untouched (zeros)
        assert all(
            float(jnp.sum(jnp.abs(l))) == 0.0
            for l in jax.tree.leaves(
                jax.device_get(state)["residuals"])
        )

    def test_model_axis_sharded_residual_specs(self, mesh):
        """pp/tp-sharded params carry per-model-axis-position residuals:
        the specs must declare them varying there and the global buffer
        must hold every copy (review finding repro)."""
        import numpy as _np

        devs = _np.asarray(jax.devices()).reshape(2, 2, 2)
        from jax.sharding import Mesh

        mesh3 = Mesh(devs, ("dcn", "ici", "pp"))
        # one pp-sharded leaf, one replicated leaf
        params = {"stack": jnp.zeros((2, 40)), "norm": jnp.zeros((24,))}
        pspecs = {"stack": P("pp"), "norm": P()}
        cfg = CompressionConfig(block_size=16)
        state = init_comm_state(params, AXES, cfg, mesh=mesh3,
                                param_specs=pspecs)
        cspecs = comm_state_specs(state, AXES, param_specs=pspecs)
        assert cspecs["residuals"]["stack"]["push"] == \
            P(("dcn", "ici", "pp"))
        assert cspecs["residuals"]["norm"]["push"] == P(("dcn", "ici"))
        # pp-sharded leaf: local rows = 40 elems -> chunk 20 -> padded
        # 32 per device, x (2 dcn x 2 ici x 2 pp) positions globally
        assert state["residuals"]["stack"]["push"].shape == (8 * 32,)
        # replicated leaf: 24 -> chunk 12 -> padded 32, x (dcn x ici)
        assert state["residuals"]["norm"]["push"].shape == (4 * 32,)

        def step(g, st):
            return all_reduce_gradients(
                g, AXES, compression=cfg, comm_state=st)

        # per-device grads mirror the param locals: stack (1, 40) per
        # (dcn, ici, pp) position, norm (24,) varying over data only
        gspecs = {"stack": P(("dcn", "ici", "pp")),
                  "norm": P(("dcn", "ici"))}
        grads = {"stack": jax.random.normal(jax.random.PRNGKey(9),
                                            (8, 40)),
                 "norm": jax.random.normal(jax.random.PRNGKey(10),
                                           (192,))}
        out, new_state = jax.jit(smap(
            step, mesh3, (gspecs, cspecs), (gspecs, cspecs)))(
            grads, state)
        assert int(new_state["step"]) == 1
        for k in out:
            assert np.all(np.isfinite(np.asarray(out[k])))

    def test_validation_errors(self):
        with pytest.raises(ValueError, match="hierarchical"):
            all_reduce_gradients({}, "dp", compression="int8")
        with pytest.raises(ValueError, match="comm state"):
            all_reduce_gradients({}, AXES, compression="int8")
        with pytest.raises(ValueError, match="without compression"):
            all_reduce_gradients({}, AXES, comm_state={"residuals": {},
                                                       "step": 0})
        with pytest.raises(ValueError, match="hierarchical"):
            DistributedDataParallel(axis_name="dp", compression="int8")
        with pytest.raises(ValueError, match="hierarchical"):
            Reducer(axis_name="dp", compression="int8")

    def test_ddp_call_threads_state(self, mesh):
        grads = _grads()
        local = jax.tree.map(
            lambda g: jnp.zeros((1,) + g.shape[1:]), grads)
        spec = jax.tree.map(lambda _: P(AXES), grads)
        ddp = DistributedDataParallel(axis_name=AXES, compression="int8")
        state = ddp.init_comm_state(local, mesh=mesh)
        cspecs = ddp.comm_state_specs(state)
        step = jax.jit(smap(ddp, mesh, (spec, cspecs), (spec, cspecs)))
        out, state = step(grads, state)
        assert int(state["step"]) == 1
        ref = np.broadcast_to(
            np.mean(np.asarray(grads["w"]), axis=0, keepdims=True),
            grads["w"].shape)
        np.testing.assert_allclose(np.asarray(out["w"]), ref, atol=0.05)

    def test_reducer_compressed_accumulate_reduce(self, mesh):
        red = Reducer(axis_name=AXES, compression="int8")
        exact = Reducer(axis_name=AXES)

        def run(reducer):
            def step(x):
                acc = reducer.init(x[0])
                acc = reducer.accumulate(acc, x[0])
                acc = reducer.accumulate(acc, 2.0 * x[0])
                g, _ = reducer.reduce(acc)
                return g

            return jax.jit(smap(
                step, mesh, (P(AXES),), P(AXES)))(
                jax.random.normal(jax.random.PRNGKey(7), (8, 24)))

        g_c = run(red)
        g_e = run(exact)
        amax = np.max(np.abs(np.asarray(g_e)))
        np.testing.assert_allclose(
            np.asarray(g_c), np.asarray(g_e), atol=0.05 * amax)

    def test_reducer_comm_state_persists_across_cycles(self, mesh):
        red = Reducer(axis_name=AXES, compression="int8")

        def step(x):
            acc = red.init(x[0])
            acc = red.accumulate(acc, x[0])
            _, fresh = red.reduce(acc)
            # the accumulator resets, the residual does not
            zeroed = sum(jnp.sum(jnp.abs(l))
                         for l in jax.tree.leaves(fresh["sum"]))
            resid = sum(jnp.sum(jnp.abs(l)) for l in jax.tree.leaves(
                fresh["comm"]["residuals"]))
            count = fresh["comm"]["step"].astype(jnp.float32)
            return jax.lax.pmax(
                jnp.stack([zeroed, resid, count]), AXES)

        out = np.asarray(jax.jit(smap(
            step, mesh, (P(AXES),), P()))(
            jax.random.normal(jax.random.PRNGKey(8), (8, 40)) * 3.0))
        assert out[0] == 0.0
        assert out[1] > 0.0  # a real residual carried over
        assert int(out[2]) == 1


# ---------------------------------------------------------------- ZeRO


def _zero_params_grads():
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    params = {"a": jax.random.normal(ks[0], (37, 5)),
              "b": jax.random.normal(ks[1], (16,))}
    grads = jax.tree.map(
        lambda p: jax.random.normal(ks[2], p.shape), params)
    return params, grads


def _run_zero(mesh, opt, params, grads, steps=3):
    pspec = jax.tree.map(lambda _: P(), params)
    ss = opt.state_specs()
    init = jax.jit(smap(opt.init, mesh, (pspec,), ss))
    stepf = jax.jit(smap(lambda s, g, p: opt.step(s, g, p),
                         mesh, (ss, pspec, pspec), (pspec, ss)))
    st = init(params)
    p = params
    for _ in range(steps):
        p, st = stepf(st, grads, p)
    return p, st


class TestZeroCompressed:
    def test_adam_int8_tracks_uncompressed(self, mesh):
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        params, grads = _zero_params_grads()
        exact, st_e = _run_zero(mesh, DistributedFusedAdam(
            lr=1e-2, weight_decay=0.01, axis_name=AXES), params, grads)
        comp, st_c = _run_zero(mesh, DistributedFusedAdam(
            lr=1e-2, weight_decay=0.01, axis_name=AXES,
            compression="int8"), params, grads)
        assert "comm" not in st_e and "comm" in st_c
        for a, b in zip(jax.tree.leaves(exact), jax.tree.leaves(comp)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-2)

    def test_lamb_int8_tracks_uncompressed(self, mesh):
        from apex_tpu.contrib.optimizers import DistributedFusedLAMB

        params, grads = _zero_params_grads()
        exact, _ = _run_zero(mesh, DistributedFusedLAMB(
            lr=1e-2, weight_decay=0.01, max_grad_norm=0.05,
            axis_name=AXES), params, grads)
        comp, _ = _run_zero(mesh, DistributedFusedLAMB(
            lr=1e-2, weight_decay=0.01, max_grad_norm=0.05,
            axis_name=AXES, compression="int8"), params, grads)
        for a, b in zip(jax.tree.leaves(exact), jax.tree.leaves(comp)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-2)

    def test_compression_requires_hierarchy(self):
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        with pytest.raises(ValueError, match="hierarchical"):
            DistributedFusedAdam(axis_name="dp", compression="int8")

    def test_comm_state_specs_cover_both_axes(self, mesh):
        from apex_tpu.contrib.optimizers import DistributedFusedAdam

        opt = DistributedFusedAdam(axis_name=AXES, compression="int8")
        specs = opt.state_specs()
        assert specs["comm"]["push"] == P(("dcn", "ici"))
        assert specs["comm"]["pull"] == P(("dcn", "ici"))


# ------------------------------------------------- GPT training parity


VOCAB, LAYERS, HIDDEN, HEADS, SEQ = 64, 2, 32, 4, 8

# documented tolerance for the acceptance criterion: int8 + error
# feedback must track the fp32-comms loss curve within this absolute
# gap at every one of the 8 short-training steps (measured headroom on
# the virtual mesh is ~10x tighter)
GPT_LOSS_ATOL = 3e-2


@pytest.fixture(scope="module")
def gpt_mesh():
    from apex_tpu.transformer import parallel_state

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        data_parallel_ici_size_=ICI)
    yield mesh
    parallel_state.destroy_model_parallel()


def _gpt_setup():
    from apex_tpu.models.gpt import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam

    cfg = GPTConfig(
        vocab_size=VOCAB, num_layers=LAYERS, hidden_size=HIDDEN,
        num_attention_heads=HEADS, max_position_embeddings=SEQ,
        compute_dtype=jnp.float32, remat=False, attention_impl="xla",
    )
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-2)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, VOCAB, (8, SEQ)), jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    return model, params, opt, tokens, targets


def _gpt_step_fn(mesh, model, opt, comp):
    from apex_tpu.transformer import parallel_state

    data_axes = parallel_state.data_parallel_axis_names()
    use_comm = comp is not None and comp.error_feedback

    def step(p, s, comm, tok, tgt):
        loss, grads = jax.value_and_grad(model.loss)(p, tok, tgt)
        loss = jax.lax.pmean(loss, data_axes)
        if comp is None:
            grads = all_reduce_gradients(grads, data_axes)
        elif use_comm:
            grads, comm = all_reduce_gradients(
                grads, data_axes, compression=comp, comm_state=comm)
        else:
            grads = all_reduce_gradients(
                grads, data_axes, compression=comp)
        p, s = opt.step(s, grads, p)
        return p, s, comm, loss

    return step, data_axes


def _train_gpt(mesh, comp, steps=8, resume_via_checkpoint_at=None,
               tmp_path=None):
    from apex_tpu.transformer.tensor_parallel.layers import (
        state_specs_like,
    )

    model, params, opt, tokens, targets = _gpt_setup()
    specs = model.param_specs()
    opt_state = opt.init(params)
    opt_specs = state_specs_like(specs, opt_state)
    step, data_axes = _gpt_step_fn(mesh, model, opt, comp)
    use_comm = comp is not None and comp.error_feedback
    if use_comm:
        comm = init_comm_state(params, data_axes, comp, mesh=mesh)
        cspecs = comm_state_specs(comm, data_axes)
    else:
        comm, cspecs = {}, {}
    dspec = P(data_axes)
    jstep = jax.jit(smap(
        step, mesh,
        (specs, opt_specs, cspecs, dspec, dspec),
        (specs, opt_specs, cspecs, P()),
    ))
    p, s = params, opt_state
    trace = []
    for i in range(steps):
        p, s, comm, loss = jstep(p, s, comm, tokens, targets)
        trace.append(float(loss))
        if resume_via_checkpoint_at is not None \
                and i == resume_via_checkpoint_at:
            # full save/restore round trip mid-run, residuals included
            from apex_tpu import checkpoint

            path = str(tmp_path / "ck")
            state = {"params": jax.device_get(p),
                     "opt": jax.device_get(s),
                     "comm": jax.device_get(comm)}
            checkpoint.save(path, state)
            restored = checkpoint.restore(path, target=state,
                                          verify_integrity=True)
            p = restored["params"]
            s = restored["opt"]
            comm = restored["comm"]
    return np.asarray(trace)


class TestGPTTrainingParity:
    def test_int8_error_feedback_matches_fp32_comms(self, gpt_mesh):
        base = _train_gpt(gpt_mesh, None)
        comp = _train_gpt(gpt_mesh, CompressionConfig())
        assert np.all(np.isfinite(base)) and base[-1] < base[0]
        np.testing.assert_allclose(comp, base, atol=GPT_LOSS_ATOL)

    def test_residual_state_roundtrips_through_checkpoint(
            self, gpt_mesh, tmp_path):
        uninterrupted = _train_gpt(gpt_mesh, CompressionConfig())
        resumed = _train_gpt(gpt_mesh, CompressionConfig(),
                             resume_via_checkpoint_at=3,
                             tmp_path=tmp_path)
        # deterministic rounding + full state capture -> bit-identical
        np.testing.assert_array_equal(uninterrupted, resumed)

    def test_data_parallel_helpers(self, gpt_mesh):
        from apex_tpu.transformer import parallel_state

        assert parallel_state.data_parallel_axis_names() == AXES
        assert parallel_state.hierarchical_data_parallel_axes() == AXES
        assert parallel_state.get_data_parallel_world_size() == 8
        assert gpt_mesh.shape["dp"] == 1
