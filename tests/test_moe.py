"""Expert-parallel MoE tests: ep-sharded == dense, routing behaviour."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.moe import MoEMLP

H, F, E = 16, 32, 8
N = 32  # global tokens (b=8, s=4)


def build(mesh, layer):
    specs = layer.param_specs()

    def fwd(params, x):
        out, aux = layer.apply(params, x)
        return out, jax.lax.pmean(aux, "dp")

    fn = jax.jit(
        jax.shard_map(
            fwd, mesh=mesh,
            in_specs=(specs, P("dp")),
            out_specs=(P("dp"), P()),
        )
    )
    return fn, specs


def place(mesh, tree, specs):
    return jax.device_put(
        tree, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                           is_leaf=lambda x: isinstance(x, P))
    )


def test_ep_matches_dense():
    """ep=8-sharded MoE == the same params applied densely, when the
    capacity is large enough that nothing drops."""
    layer = MoEMLP(H, F, E, capacity_factor=float(E))  # no drops
    params = layer.init(jax.random.PRNGKey(0))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (8, 4, H))

    # dense: dp=1 mesh (cp soaks up the devices)
    mesh = parallel_state.initialize_model_parallel(context_parallel_size_=8)
    try:
        fn, specs = build(mesh, layer)
        ref, ref_aux = fn(params, x)
        ref, ref_aux = np.asarray(ref), float(ref_aux)
    finally:
        parallel_state.destroy_model_parallel()

    # expert-parallel: dp=8, experts sharded across ranks
    mesh = parallel_state.initialize_model_parallel()
    try:
        fn, specs = build(mesh, layer)
        placed = place(mesh, params, specs)
        out, aux = fn(placed, x)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                   atol=1e-5)
        # aux loss is per-shard routing stats; just sanity it
        assert np.isfinite(float(aux))
    finally:
        parallel_state.destroy_model_parallel()


def test_capacity_drops_tokens():
    """With a tiny capacity most tokens get zero output (residual path)."""
    mesh = parallel_state.initialize_model_parallel(context_parallel_size_=8)
    try:
        layer = MoEMLP(H, F, E, capacity_factor=0.25)
        params = layer.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, H))
        fn, specs = build(mesh, layer)
        out, _ = fn(params, x)
        flat = np.asarray(out).reshape(-1, H)
        zero_rows = np.sum(np.all(flat == 0, axis=-1))
        assert zero_rows > 0  # overflow tokens dropped
        assert zero_rows < flat.shape[0]  # but not all
    finally:
        parallel_state.destroy_model_parallel()


def test_moe_trains_and_grads_are_per_expert():
    """End-to-end: grads flow, expert grads differ across ep ranks, and
    a few SGD steps reduce the loss."""
    mesh = parallel_state.initialize_model_parallel()
    try:
        layer = MoEMLP(H, F, E, capacity_factor=8.0)
        params = layer.init(jax.random.PRNGKey(0))
        specs = layer.param_specs()
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, H))
        y = jax.random.normal(jax.random.PRNGKey(2), (8, 4, H))

        def loss_fn(params, x, y):
            out, aux = layer.apply(params, x)
            mse = jnp.mean((out - y) ** 2)
            return jax.lax.pmean(mse, "dp") + 0.01 * jax.lax.pmean(aux, "dp")

        step = jax.jit(
            jax.shard_map(
                lambda p, x, y: jax.value_and_grad(loss_fn)(p, x, y),
                mesh=mesh,
                in_specs=(specs, P("dp"), P("dp")),
                out_specs=(P(), specs),
            )
        )
        placed = place(mesh, params, specs)
        losses = []
        for _ in range(200):
            loss, grads = step(placed, x, y)
            losses.append(float(loss))
            placed = jax.tree.map(lambda p, g: p - 1.0 * g, placed, grads)
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0] * 0.9
        # expert grads are ep-sharded arrays of global shape (E, ...)
        g_w1 = grads["w1"]
        assert g_w1.shape == (E, H, F)
    finally:
        parallel_state.destroy_model_parallel()


def _dense_topk_reference(layer, params, x, k):
    """Token-by-token numpy mixture: Σ_{i<=k} gate_i * FFN_{e_i}(x)."""
    b, s, h = x.shape
    flat = np.asarray(x).reshape(-1, h)
    w_r = np.asarray(params["router"]["weight"], np.float32)
    w1 = np.asarray(params["w1"], np.float32)
    w2 = np.asarray(params["w2"], np.float32)
    logits = flat.astype(np.float32) @ w_r
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(flat, dtype=np.float32)
    for t in range(flat.shape[0]):
        idx = np.argsort(-probs[t])[:k]
        g = probs[t, idx]
        if k > 1:
            g = g / g.sum()
        for e, gi in zip(idx, g):
            h1 = flat[t] @ w1[e]
            h1 = 0.5 * h1 * (1 + np.tanh(
                np.sqrt(2 / np.pi) * (h1 + 0.044715 * h1 ** 3)))
            out[t] += gi * (h1 @ w2[e])
    return out.reshape(b, s, h)


def test_top2_matches_dense_mixture():
    """top_k=2 ep-sharded routing == the dense 2-expert mixture
    (GShard/Mixtral convention: renormalized top-2 gates), capacity
    large enough that nothing drops, on the 8-device mesh."""
    layer = MoEMLP(H, F, E, top_k=2, capacity_factor=float(E))
    params = layer.init(jax.random.PRNGKey(2))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (8, 4, H))
    ref = _dense_topk_reference(layer, params, x, k=2)

    mesh = parallel_state.initialize_model_parallel()  # dp=8 = ep
    try:
        fn, specs = build(mesh, layer)
        placed = place(mesh, params, specs)
        out, aux = fn(placed, x)
        np.testing.assert_allclose(
            np.asarray(out), ref, rtol=2e-4, atol=2e-5
        )
        assert np.isfinite(float(aux))
    finally:
        parallel_state.destroy_model_parallel()


def test_top2_capacity_priority():
    """Choice-major priority: every 1st choice outranks every 2nd choice
    for capacity (GShard ordering).  Alternating-preference setup with
    cap=2 per expert: choice-major keeps tokens {0,2} on expert 0 and
    {1,3} on expert 1 (all 1st choices); token-major order would keep
    {0,1} on both instead — so tokens 2 and 3 surviving, and 4-7
    dropping, pins the ordering."""
    E2, k, n = 2, 2, 8
    # cap = int(cf * k * n / E) = 2
    layer = MoEMLP(H, F, E2, top_k=2, capacity_factor=0.25)
    params = layer.init(jax.random.PRNGKey(4))
    # router reads feature 0: even tokens prefer e0, odd prefer e1
    params["router"]["weight"] = (
        jnp.zeros((H, 2)).at[0, 0].set(1.0).at[0, 1].set(-1.0)
    )
    # distinguishable experts: e0 ≈ +gelu(x), e1 ≈ -gelu(x)
    eye = jnp.eye(H)
    w1 = jnp.zeros((E2, H, F)).at[:, :, :H].set(eye[None])
    w2 = jnp.zeros((E2, F, H))
    w2 = w2.at[0, :H, :].set(eye).at[1, :H, :].set(-eye)
    params = {**params, "w1": w1, "w2": w2}

    # token t: feature0 = +1 (even) / -1 (odd), rest 0.3
    flat = jnp.full((n, H), 0.3)
    flat = flat.at[:, 0].set(jnp.where(jnp.arange(n) % 2 == 0, 1.0, -1.0))
    x = flat.reshape(2, 4, H)

    mesh = parallel_state.initialize_model_parallel(context_parallel_size_=8)
    try:
        fn, specs = build(mesh, layer)
        out, aux = fn(params, x)
        s = np.asarray(out).reshape(n, H).sum(-1)
        # tokens 0,2 kept on e0 (+), 1,3 on e1 (−); 4-7 fully dropped
        assert s[0] > 1e-3 and s[2] > 1e-3, s
        assert s[1] < -1e-3 and s[3] < -1e-3, s
        np.testing.assert_allclose(s[4:], 0.0, atol=1e-6)
    finally:
        parallel_state.destroy_model_parallel()


def test_router_z_loss():
    """router_z_loss_weight adds mean(logsumexp²) to the aux scalar."""
    base = MoEMLP(H, F, E, top_k=2)
    withz = MoEMLP(H, F, E, top_k=2, router_z_loss_weight=1.0)
    params = base.init(jax.random.PRNGKey(5))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(6), (2, 4, H))
    mesh = parallel_state.initialize_model_parallel(context_parallel_size_=8)
    try:
        _, aux0 = build(mesh, base)[0](params, x)
        _, aux1 = build(mesh, withz)[0](params, x)
        flat = np.asarray(x).reshape(-1, H).astype(np.float32)
        logits = flat @ np.asarray(params["router"]["weight"], np.float32)
        z = np.log(np.exp(logits - logits.max(-1, keepdims=True))
                   .sum(-1)) + logits.max(-1)
        np.testing.assert_allclose(
            float(aux1) - float(aux0), np.mean(z * z), rtol=1e-5
        )
    finally:
        parallel_state.destroy_model_parallel()


def test_top_k_validation():
    with pytest.raises(ValueError, match="top_k"):
        MoEMLP(H, F, E, top_k=0)
    with pytest.raises(ValueError, match="top_k"):
        MoEMLP(H, F, E, top_k=E + 1)


def test_moe_aux_threads_through_pipeline():
    """MoE under pp>1: the aux-loss accumulator rides the activation
    stream, so the pipeline loss equals mean-over-microbatches of the
    sequential per-microbatch (ce + w*aux), and the aux weight reaches
    the router gradients (the round-4 advisor gap, now closed)."""
    from apex_tpu.models.gpt import GPTConfig, GPTModel

    W = 0.1
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=2
    )
    try:
        cfg = dict(
            vocab_size=64, num_layers=2, hidden_size=32,
            num_attention_heads=4, max_position_embeddings=16,
            compute_dtype=jnp.float32, remat=False, attention_impl="xla",
            num_experts=4, moe_capacity_factor=8.0, moe_aux_weight=W,
        )
        model = GPTModel(GPTConfig(**cfg))
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 12), 0, 64)
        targets = jax.random.randint(jax.random.PRNGKey(2), (8, 12), 0, 64)
        num_micro = 2

        # pipeline: pp-sharded params through pipeline_1f1b_grads
        pspecs = model.pipeline_param_specs()

        def pp_fb(p, t, y):
            return model.pipeline_1f1b_grads(p, t, y, num_micro)

        pp_fn = jax.jit(jax.shard_map(
            pp_fb, mesh=mesh,
            in_specs=(pspecs, P("dp"), P("dp")),
            out_specs=(P(), pspecs),
        ))
        placed_pp = place(mesh, params, pspecs)
        pp_loss, pp_grads = pp_fn(placed_pp, tokens, targets)

        # sequential reference: full stack replicated on the same mesh,
        # per-microbatch loss (ce + W*aux on identical dp shards)
        sspecs = model.param_specs()
        seq_loss = jax.jit(jax.shard_map(
            model.loss, mesh=mesh,
            in_specs=(sspecs, P("dp"), P("dp")), out_specs=P(),
        ))
        placed_seq = place(mesh, params, sspecs)
        mb = tokens.shape[0] // num_micro
        expected = np.mean([
            float(seq_loss(placed_seq,
                           tokens[m * mb:(m + 1) * mb],
                           targets[m * mb:(m + 1) * mb]))
            for m in range(num_micro)
        ])
        np.testing.assert_allclose(float(pp_loss), expected, rtol=2e-5)

        # the aux weight must influence the router gradient
        model0 = GPTModel(GPTConfig(**{**cfg, "moe_aux_weight": 0.0}))

        def pp_fb0(p, t, y):
            return model0.pipeline_1f1b_grads(p, t, y, num_micro)

        pp_fn0 = jax.jit(jax.shard_map(
            pp_fb0, mesh=mesh,
            in_specs=(pspecs, P("dp"), P("dp")),
            out_specs=(P(), pspecs),
        ))
        _, pp_grads0 = pp_fn0(place(mesh, params, pspecs), tokens, targets)
        g_router = np.asarray(pp_grads["layers"]["moe"]["router"]["weight"])
        g_router0 = np.asarray(
            pp_grads0["layers"]["moe"]["router"]["weight"])
        assert np.isfinite(g_router).all()
        assert np.abs(g_router - g_router0).max() > 1e-7, (
            "aux weight does not reach the router gradient under pp"
        )
    finally:
        parallel_state.destroy_model_parallel()


def test_moe_decode_raises_with_design_note():
    """The serving decode path through the CAPACITY expert layer must
    refuse LOUDLY (silent dense fallback would corrupt generations); the
    error names the layer kind that does serve (``HeldExpertsMLP``),
    and every gpt.py decode entry point routes through it."""
    layer = MoEMLP(H, F, E)
    with pytest.raises(NotImplementedError, match="HeldExpertsMLP"):
        layer.decode()

    from apex_tpu.models import GPTConfig, GPTModel

    model = GPTModel(GPTConfig(
        vocab_size=64, num_layers=2, hidden_size=H,
        num_attention_heads=2, max_position_embeddings=32,
        num_experts=E, compute_dtype=jnp.float32, remat=False,
        attention_impl="xla"))
    # the guard fires before any argument is touched — decode through
    # an MoE model is refused at every serving entry point
    for entry, nargs in ((model.decode_step, 6),
                         (model.prefill_chunk, 7),
                         (model.verify_step, 7)):
        with pytest.raises(NotImplementedError,
                           match="HeldExpertsMLP"):
            entry(*([None] * nargs))
