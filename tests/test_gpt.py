"""GPT model tests: tp-sharded forward/loss/grad vs dense math, on the
8-device virtual CPU mesh (SURVEY.md §4 philosophy — smallest real mesh,
analytic/dense-reference expectations; mirrors the reference's
run_megatron_gpt_pipeline.py end-to-end tier)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.transformer import parallel_state


def small_config(**kw):
    base = dict(
        vocab_size=64,
        num_layers=2,
        hidden_size=32,
        num_attention_heads=4,
        max_position_embeddings=16,
        compute_dtype=jnp.float32,
        remat=False,
        attention_impl="xla",
    )
    base.update(kw)
    return GPTConfig(**base)


def build(mesh, model):
    """jit(shard_map(loss)) + matching param placement."""
    specs = model.param_specs()

    def loss_fn(params, tokens, targets):
        return model.loss(params, tokens, targets)

    sharded = jax.jit(
        jax.shard_map(
            loss_fn,
            mesh=mesh,
            in_specs=(specs, P("dp"), P("dp")),
            out_specs=P(),
        )
    )
    return sharded, specs


def test_gpt_loss_tp_invariant():
    """The same logical params give (numerically) the same loss on a
    tp=1 and a tp=4 mesh."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 12), 0, 64)
    targets = jax.random.randint(jax.random.PRNGKey(2), (8, 12), 0, 64)
    losses = {}
    for tp in (1, 4):
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=tp
        )
        try:
            model = GPTModel(small_config())
            params = model.init(jax.random.PRNGKey(0))
            sharded, specs = build(mesh, model)
            placed = jax.device_put(
                params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                     is_leaf=lambda x: isinstance(x, P))
            )
            losses[tp] = float(sharded(placed, tokens, targets))
            assert np.isfinite(losses[tp])
        finally:
            parallel_state.destroy_model_parallel()
    np.testing.assert_allclose(losses[4], losses[1], rtol=2e-4)


def test_gpt_grads_finite_and_remat_matches():
    mesh = parallel_state.initialize_model_parallel(tensor_model_parallel_size_=2)
    try:
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 64)
        targets = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, 64)
        losses = {}
        grads = {}
        for remat in (False, True):
            model = GPTModel(small_config(remat=remat))
            params = model.init(jax.random.PRNGKey(0))
            specs = model.param_specs()
            grad_fn = jax.jit(
                jax.shard_map(
                    jax.value_and_grad(lambda p, t, y: model.loss(p, t, y)),
                    mesh=mesh,
                    in_specs=(specs, P("dp"), P("dp")),
                    out_specs=(P(), specs),
                )
            )
            placed = jax.device_put(
                params,
                jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P)),
            )
            loss, g = grad_fn(placed, tokens, targets)
            losses[remat] = float(loss)
            grads[remat] = g
            flat = jax.tree.leaves(g)
            assert all(np.all(np.isfinite(np.asarray(x))) for x in flat)
        np.testing.assert_allclose(losses[False], losses[True], rtol=1e-6)
        for a, b in zip(jax.tree.leaves(grads[False]), jax.tree.leaves(grads[True])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                       atol=1e-6)
    finally:
        parallel_state.destroy_model_parallel()


def test_gpt_pipeline_matches_non_pipeline():
    """pp=2 x tp=2 x dp=2 pipeline loss+grads == single-mesh loss+grads."""
    from apex_tpu.transformer.pipeline_parallel import sync_replicated_grads

    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 8), 0, 64)
    targets = jax.random.randint(jax.random.PRNGKey(2), (8, 8), 0, 64)

    # dense reference: tp=1 pp=1 mesh
    mesh = parallel_state.initialize_model_parallel()
    try:
        model = GPTModel(small_config())
        params = model.init(jax.random.PRNGKey(0))
        sharded, specs = build(mesh, model)
        grad_fn = jax.jit(
            jax.shard_map(
                jax.value_and_grad(lambda p, t, y: model.loss(p, t, y)),
                mesh=mesh,
                in_specs=(specs, P("dp"), P("dp")),
                out_specs=(P(), specs),
            )
        )
        ref_loss, ref_grads = grad_fn(params, tokens, targets)
        ref_loss = float(ref_loss)
        ref_grads = jax.device_get(ref_grads)
    finally:
        parallel_state.destroy_model_parallel()

    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=2, pipeline_model_parallel_size_=2
    )
    try:
        model = GPTModel(small_config())
        params = model.init(jax.random.PRNGKey(0))
        specs = model.pipeline_param_specs()

        def pp_loss_and_grad(params, tokens, targets):
            loss, grads = jax.value_and_grad(model.pipeline_loss)(
                params, tokens, targets, 2
            )
            grads = sync_replicated_grads(grads, specs)
            return loss, grads

        grad_fn = jax.jit(
            jax.shard_map(
                pp_loss_and_grad,
                mesh=mesh,
                in_specs=(specs, P("dp"), P("dp")),
                out_specs=(P(), specs),
            )
        )
        placed = jax.device_put(
            params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P))
        )
        loss, grads = grad_fn(placed, tokens, targets)
        np.testing.assert_allclose(float(loss), ref_loss, rtol=2e-4)
        for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_leaves_with_path(jax.device_get(grads)),
            jax.tree_util.tree_leaves_with_path(ref_grads),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-5,
                err_msg=str(ka),
            )
    finally:
        parallel_state.destroy_model_parallel()


def test_gpt_moe_trains():
    """MoE-GPT: tp=2 x dp=4(ep), 4 experts — loss decreases, expert
    grads stay per-expert (dp-sharded)."""
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=2
    )
    try:
        model = GPTModel(small_config(
            num_experts=4, moe_capacity_factor=4.0
        ))
        params = model.init(jax.random.PRNGKey(0))
        specs = model.param_specs()
        assert "moe" in jax.tree_util.tree_structure(
            specs["layers"]
        ).__repr__() or "moe" in specs["layers"]
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 12), 0, 64)
        targets = jax.random.randint(jax.random.PRNGKey(2), (8, 12), 0, 64)

        grad_fn = jax.jit(
            jax.shard_map(
                jax.value_and_grad(lambda p, t, y: model.loss(p, t, y)),
                mesh=mesh,
                in_specs=(specs, P("dp"), P("dp")),
                out_specs=(P(), specs),
            )
        )
        placed = jax.device_put(
            params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P))
        )
        first = None
        for _ in range(40):
            loss, grads = grad_fn(placed, tokens, targets)
            if first is None:
                first = float(loss)
            placed = jax.tree.map(lambda p, g: p - 0.1 * g, placed, grads)
        assert np.isfinite(float(loss))
        assert float(loss) < first
        # expert weights stacked (L, E, h, f), experts sharded over dp
        w1 = placed["layers"]["moe"]["w1"]
        assert w1.shape[1] == 4
    finally:
        parallel_state.destroy_model_parallel()


def test_gpt_dropout_rng_paths():
    mesh = parallel_state.initialize_model_parallel(tensor_model_parallel_size_=2)
    try:
        model = GPTModel(
            small_config(hidden_dropout=0.1, attention_dropout=0.1)
        )
        params = model.init(jax.random.PRNGKey(0))
        specs = model.param_specs()
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, 64)

        def fwd(params, tokens, rng):
            return model.apply(params, tokens, rng)

        sharded = jax.jit(
            jax.shard_map(
                fwd,
                mesh=mesh,
                in_specs=(specs, P("dp"), P()),
                out_specs=P("dp", None, "tp"),
            )
        )
        placed = jax.device_put(
            params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P))
        )
        a = sharded(placed, tokens, jax.random.PRNGKey(3))
        b = sharded(placed, tokens, jax.random.PRNGKey(4))
        assert not np.allclose(np.asarray(a), np.asarray(b))
    finally:
        parallel_state.destroy_model_parallel()


def test_gpt_1f1b_matches_gpipe_pipeline():
    """GPT fwd+bwd through the true 1F1B schedule == jax.grad of the
    GPipe-style pipeline, loss and grads, on the pp=2 x tp=2 x dp=2 mesh."""
    from apex_tpu.transformer.pipeline_parallel import sync_replicated_grads

    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 8), 0, 64)
    targets = jax.random.randint(jax.random.PRNGKey(2), (8, 8), 0, 64)
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=2, pipeline_model_parallel_size_=2
    )
    try:
        model = GPTModel(small_config())
        params = model.init(jax.random.PRNGKey(0))
        specs = model.pipeline_param_specs()
        placed = jax.device_put(
            params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P))
        )

        def gpipe(params, tokens, targets):
            loss, grads = jax.value_and_grad(model.pipeline_loss)(
                params, tokens, targets, 4
            )
            grads = sync_replicated_grads(grads, specs)
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, "dp"), grads
            )
            return loss, grads

        def fb_1f1b(params, tokens, targets):
            return model.pipeline_1f1b_grads(params, tokens, targets, 4)

        outs = {}
        for name, fn in (("gpipe", gpipe), ("1f1b", fb_1f1b)):
            f = jax.jit(jax.shard_map(
                fn, mesh=mesh,
                in_specs=(specs, P("dp"), P("dp")),
                out_specs=(P(), specs),
            ))
            outs[name] = f(placed, tokens, targets)
        (l_ref, g_ref), (l_new, g_new) = outs["gpipe"], outs["1f1b"]
        np.testing.assert_allclose(float(l_new), float(l_ref), rtol=1e-5)
        for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_new),
            jax.tree_util.tree_leaves_with_path(g_ref),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-6,
                err_msg=str(path),
            )
    finally:
        parallel_state.destroy_model_parallel()


def test_gpt_interleaved_1f1b_matches_gpipe_pipeline():
    """GPT fwd+bwd through the interleaved 1F1B schedule (V=2 chunks per
    rank, dispatched by get_forward_backward_func) == jax.grad of the
    GPipe-style pipeline, loss and grads, on the pp=2 x tp=2 x dp=2 mesh
    (reference: fwd_bwd_pipelining_with_interleaving.py:22-308)."""
    from apex_tpu.transformer.pipeline_parallel import sync_replicated_grads

    V = 2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 8), 0, 64)
    targets = jax.random.randint(jax.random.PRNGKey(2), (8, 8), 0, 64)
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size_=2, pipeline_model_parallel_size_=2
    )
    try:
        model = GPTModel(small_config(num_layers=4))
        params = model.init(jax.random.PRNGKey(0))
        specs = model.pipeline_param_specs()
        placed = jax.device_put(
            params, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P))
        )
        chunk_specs = model.pipeline_param_specs(V)
        chunked = model.pipeline_chunk_params(params, V)
        placed_chunks = jax.device_put(
            chunked,
            jax.tree.map(lambda s: NamedSharding(mesh, s), chunk_specs,
                         is_leaf=lambda x: isinstance(x, P)),
        )

        def gpipe(params, tokens, targets):
            loss, grads = jax.value_and_grad(model.pipeline_loss)(
                params, tokens, targets, 4
            )
            grads = sync_replicated_grads(grads, specs)
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
            return loss, grads

        def fb_il(params, tokens, targets):
            return model.pipeline_1f1b_grads(
                params, tokens, targets, 4, num_model_chunks=V
            )

        ref = jax.jit(jax.shard_map(
            gpipe, mesh=mesh,
            in_specs=(specs, P("dp"), P("dp")), out_specs=(P(), specs),
        ))(placed, tokens, targets)
        got = jax.jit(jax.shard_map(
            fb_il, mesh=mesh,
            in_specs=(chunk_specs, P("dp"), P("dp")),
            out_specs=(P(), chunk_specs),
        ))(placed_chunks, tokens, targets)

        np.testing.assert_allclose(float(got[0]), float(ref[0]), rtol=1e-5)
        # chunked grads reshape back to the stacked (L, ...) layout
        g_ref, g_new = ref[1], got[1]
        g_new = {
            **g_new,
            "layers": jax.tree.map(
                lambda x: x.reshape(-1, *x.shape[3:]), g_new["layers"]
            ),
        }
        for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_new),
            jax.tree_util.tree_leaves_with_path(g_ref),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-6,
                err_msg=str(path),
            )
    finally:
        parallel_state.destroy_model_parallel()


def test_measured_optimal_defaults_pinned():
    """The bench flagship inherits GPTConfig's defaults, so an
    accidental default change silently regresses the headline capture.
    Pin the measured-optimal set (PROFILE_r05.json, pre-PR-1 chip run):
    any deliberate re-tune must update this test WITH fresh chip
    evidence."""
    cfg = GPTConfig()
    assert cfg.remat is True
    # PR 27: the dots policy plus the attention kernels' (out, lse);
    # PR 31: and a row-parallel layer's output after its tp sum
    assert cfg.remat_policy == (
        "dots_with_no_batch_dims_and_attention_saveable")
    assert cfg.fused_ce is None  # auto by logits size (PROFILE_r05)
    assert cfg.fused_ce_chunk == 8192
    assert cfg.attention_impl is None  # auto -> pallas on TPU
    assert cfg.position_embedding == "learned"  # reference parity

    from apex_tpu.transformer.tensor_parallel.cross_entropy import (
        FUSED_CE_AUTO_BYTES,
    )

    # flagship (8192 tokens x 32768 vocab = 1.07 GB) must stay on the
    # measured-faster two-step side of the auto rule
    assert 8192 * 32768 * 4 <= FUSED_CE_AUTO_BYTES
