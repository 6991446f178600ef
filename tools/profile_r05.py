"""PROFILE_r05: single-process step-time decomposition on the real chip.

Which lever moves the step time.  All variants run in ONE process (so
every row sees the same chip state; one process drives the chip), each
timed by the host clock around a chain of steps that ends in a loss
readback.  Prints the table and writes PROFILE_r05.json at the repo
root.

Run (chip required):  python tools/profile_r05.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# flagship bench config — imported from bench.py so the decomposition's
# headline is byte-for-byte the bench headline's program
from bench import FLAGSHIP  # noqa: E402

VOCAB = FLAGSHIP["vocab_size"]
LAYERS = FLAGSHIP["num_layers"]
HIDDEN = FLAGSHIP["hidden_size"]
HEADS = FLAGSHIP["num_attention_heads"]
SEQ = FLAGSHIP["seq"]
BATCH = FLAGSHIP["batch"]
WARMUP, STEPS = 2, 10


def _require_tpu():
    plat = jax.devices()[0].platform
    if plat != "tpu":
        raise SystemExit(f"profile must run on TPU (got {plat})")


def make_step(model, opt, mesh, specs, opt_specs, *, fwd_only=False,
              opt_only=False, no_opt=False):
    """Build the jitted train step for one decomposition variant.

    Factored out of :func:`build` so tests can compile the EXACT
    harness step (notably the ``no_opt`` fwd+bwd-no-optimizer variant,
    whose tp-varying zero grad-sum was rejected by ``out_specs P()``
    during the r05 capture) on a small model over a tp>1 mesh.
    """

    def train_step(params, opt_state, tokens, targets):
        if fwd_only:
            loss = model.loss(params, tokens, targets)
            return params, opt_state, loss
        loss, grads = jax.value_and_grad(model.loss)(
            params, tokens, targets)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
        if opt_only:
            # optimizer tail in isolation: grads replaced by params*0
            # so the bwd graph is DCE'd but the opt update is intact.
            # p*0 keeps the REAL grad dtype (grads match the bf16
            # params), so the isolated tail reads the same bytes/elem
            # as the full step's optimizer
            grads = jax.tree.map(lambda p: p * 0, params)
        if no_opt:
            # fwd+bwd without the optimizer: fold grads into the loss.
            # tp-sharded grad leaves make the bare sum tp-varying, which
            # out_specs P() rejects — pmean it back to replicated (it is
            # zero anyway; only the data dependency matters)
            gsum = sum(jnp.sum(g.astype(jnp.float32) * 0)
                       for g in jax.tree.leaves(grads))
            gsum = jax.lax.pmean(gsum, "tp")
            return params, opt_state, loss + gsum
        new_params, new_opt = opt.step(opt_state, grads, params)
        return new_params, new_opt, loss

    return jax.jit(
        jax.shard_map(
            train_step, mesh=mesh,
            in_specs=(specs, opt_specs, P("dp"), P("dp")),
            out_specs=(specs, opt_specs, P()),
        ),
        donate_argnums=(0, 1),
    )


def build(**cfg_over):
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.tensor_parallel.layers import state_specs_like

    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel()
    cfg_kw = dict(
        vocab_size=VOCAB, num_layers=LAYERS, hidden_size=HIDDEN,
        num_attention_heads=HEADS, max_position_embeddings=SEQ,
        compute_dtype=jnp.bfloat16, remat=True,
    )
    cfg_kw.update(cfg_over)
    opt_only = cfg_kw.pop("_opt_only", False)
    fwd_only = cfg_kw.pop("_fwd_only", False)
    no_opt = cfg_kw.pop("_no_opt", False)
    model = GPTModel(GPTConfig(**cfg_kw))
    params = model.init(jax.random.PRNGKey(0))
    specs = model.param_specs()
    opt = FusedAdam(lr=1e-4, master_weights=True)
    opt_state = opt.init(params)
    opt_specs = state_specs_like(specs, opt_state)

    step = make_step(model, opt, mesh, specs, opt_specs,
                     fwd_only=fwd_only, opt_only=opt_only, no_opt=no_opt)
    place = lambda tree, sp: jax.device_put(
        tree, jax.tree.map(lambda s: NamedSharding(mesh, s), sp,
                           is_leaf=lambda x: isinstance(x, P)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    return (place(params, specs), place(opt_state, opt_specs), step,
            n_params)


def measure(label, **cfg_over):
    params, opt_state, step, n_params = build(**cfg_over)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (BATCH, SEQ), 0, VOCAB)
    targets = jnp.roll(tokens, -1, axis=1)
    for _ in range(WARMUP):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        params, opt_state, loss = step(params, opt_state, tokens, targets)
    final = float(loss)
    dt = (time.perf_counter() - t0) / STEPS
    assert jnp.isfinite(final), f"{label}: non-finite loss"
    print(f"{label:28s} {dt * 1e3:8.2f} ms/step", flush=True)
    return {"label": label, "ms_per_step": round(dt * 1e3, 2),
            "n_params": n_params}


def main():
    _require_tpu()
    # headline must succeed (everything is relative to it); each variant
    # is individually fallible — an OOM (remat off is expected to flirt
    # with it) must not cost the already-captured rows
    rows = [measure("headline (bf16+remat+autoCE)")]
    n_params = rows[0]["n_params"]
    for label, kw in (
        # the default is fused_ce=None (auto → two-step at the flagship
        # config); the r5 sweep resolved r3/r4's contradiction — the
        # fused scan loses at every chunk size here (8192: +2.54 ms,
        # one-chunk: +1.97 vs two-step), so the variants force it
        ("fused_ce scan chunk=8192", {"fused_ce": True}),
        ("fused_ce scan chunk=16384", {"fused_ce": True,
                                       "fused_ce_chunk": 16384}),
        ("fused_ce scan chunk=32768", {"fused_ce": True,
                                       "fused_ce_chunk": 32768}),
        ("attention xla", {"attention_impl": "xla"}),
        ("remat off", {"remat": False}),
        ("remat dots_saveable", {"remat_policy": "dots_saveable"}),
        ("fwd only", {"_fwd_only": True}),
        ("fwd+bwd, no optimizer", {"_no_opt": True}),
        ("optimizer tail only", {"_opt_only": True}),
    ):
        try:
            rows.append(measure(label, **kw))
        except Exception as e:
            # includes non-finite-loss asserts: a broken VARIANT is a
            # finding to record, not a reason to discard the headline
            # and every completed row of a scarce chip session
            print(f"{label}: FAILED ({str(e)[:160]})", flush=True)
            rows.append({"label": label, "ms_per_step": None,
                         "error": str(e)[:300]})

    head_ms = rows[0]["ms_per_step"]
    flops_per_token = 6 * n_params + 12 * LAYERS * HIDDEN * SEQ
    tok_s = BATCH * SEQ / (head_ms / 1e3)
    kind = getattr(jax.devices()[0], "device_kind", "")
    from bench import _peak_flops  # one bf16-peak table for all tools

    peak = _peak_flops(jax.devices()[0])
    mfu = tok_s * flops_per_token / peak if peak else None

    doc = {
        "config": {"vocab": VOCAB, "layers": LAYERS, "hidden": HIDDEN,
                   "heads": HEADS, "seq": SEQ, "batch": BATCH,
                   "device_kind": kind},
        "rows": rows,
        "tokens_per_sec": round(tok_s, 1),
        "mfu": round(mfu, 4) if mfu else None,
    }
    with open(os.path.join(REPO, "PROFILE_r05.json"), "w") as f:
        json.dump(doc, f, indent=1)

    print(json.dumps({"mfu": doc["mfu"],
                      "tokens_per_sec": doc["tokens_per_sec"]}))


if __name__ == "__main__":
    main()
