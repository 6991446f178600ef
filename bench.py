"""Benchmark: flagship GPT training-step throughput (+ MFU) on one chip.

Prints ONE JSON line on stdout:
  {"metric": "gpt_tp1_tokens_per_sec", "value": N, "unit": "tokens/s",
   "vs_baseline": R, ...extra diagnostic fields...}

``vs_baseline`` is the speedup of the framework's fast path (bf16 compute
+ flash attention + fused master-weight Adam — the amp-O5 analog) over an
O0-analog baseline measured in the same run (fp32 compute, XLA attention,
same optimizer math).  The reference publishes no numeric baselines
(BASELINE.md), so the baseline is measured, not copied.

This file is an orchestrator: every measurement runs in a bounded
subprocess.  The parent NEVER imports jax — a process that has touched
jax holds the chip, and the children that measure on it would then fail
or hang.  Keep it that way: jax imports live inside the ``child_*``
functions only.

It needs a TPU.  With none, the gpt child exits non-zero naming the
platform it found, and so does the bench — no throughput is printed.
A child that fails makes the bench exit non-zero after the others ran.

Extra BASELINE.md targets (RN50-style images/sec, FusedLAMB step time vs
an unfused per-tensor LAMB with identical math) are also measured and
written to BENCH_EXTRA.json + stderr, keeping stdout a single line.
"""

import json
import os
import signal
import subprocess
import sys
import time

# Flagship GPT measurement config (the TPU path of child_gpt);
# tools/profile_r05.py decomposes the SAME program — one definition so
# the decomposition's headline cannot drift from the bench headline
FLAGSHIP = dict(vocab_size=32768, num_layers=12, hidden_size=1024,
                num_attention_heads=8, seq=1024, batch=8)

CHILD_TIMEOUT = int(os.environ.get("APEX_BENCH_CHILD_TIMEOUT", "1200"))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# --------------------------------------------------------------------- child
def _install_sigterm_exit():
    """Let a child exit cleanly on SIGTERM so the JAX client tears down
    and releases the chip (a hard kill can leave it held)."""
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))


def _pin_cpu():
    import jax

    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def _peak_flops(device):
    """Per-chip peak bf16 FLOP/s — the telemetry table
    (apex_tpu.telemetry.metrics.device_peak_flops), so the bench MFU
    and the live StepStats MFU share one denominator."""
    from apex_tpu.telemetry.metrics import device_peak_flops

    return device_peak_flops(device)


def _require_tpu():
    """A chip measurement on anything but a TPU is an error, not a
    smaller run: exit non-zero naming what was found."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"bench needs a TPU; jax found platform {d.platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")


def child_gpt(platform: str):
    if platform == "cpu":
        _pin_cpu()
    else:
        _require_tpu()
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.tensor_parallel.layers import state_specs_like

    on_tpu = platform != "cpu"
    # `--platform cpu` (by hand only — main() never asks for it) uses a
    # small config; the TPU config is the measurement
    cfg_common = dict(
        vocab_size=FLAGSHIP["vocab_size"] if on_tpu else 4096,
        num_layers=FLAGSHIP["num_layers"] if on_tpu else 2,
        hidden_size=FLAGSHIP["hidden_size"] if on_tpu else 256,
        num_attention_heads=(FLAGSHIP["num_attention_heads"]
                             if on_tpu else 4),
    )
    BATCH = FLAGSHIP["batch"] if on_tpu else 2
    # MFU is batch-sensitive: the fast path sweeps these and keeps the
    # best (HBM permitting — the sweep ends quietly at the first OOM),
    # the baseline uses BATCH for comparability
    FAST_BATCHES = (8, 16, 32, 64) if on_tpu else (2,)
    SEQ = FLAGSHIP["seq"] if on_tpu else 256
    WARMUP = 2
    STEPS = 10 if on_tpu else 4

    def build_step(fast: bool, **cfg_over):
        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel()
        cfg = GPTConfig(
            max_position_embeddings=SEQ,
            compute_dtype=jnp.bfloat16 if fast else jnp.float32,
            attention_impl=(None if on_tpu else "xla") if fast else "xla",
            **{**cfg_common, "remat": True, **cfg_over},
        )
        model = GPTModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        specs = model.param_specs()
        opt = FusedAdam(lr=1e-4, master_weights=fast)
        opt_state = opt.init(params)
        opt_specs = state_specs_like(specs, opt_state)

        def train_step(params, opt_state, tokens, targets):
            loss, grads = jax.value_and_grad(model.loss)(
                params, tokens, targets
            )
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
            new_params, new_opt = opt.step(opt_state, grads, params)
            return new_params, new_opt, loss

        step = jax.jit(
            jax.shard_map(
                train_step,
                mesh=mesh,
                in_specs=(specs, opt_specs, P("dp"), P("dp")),
                out_specs=(specs, opt_specs, P()),
            ),
            donate_argnums=(0, 1),
        )
        place = lambda tree, sp: jax.device_put(
            tree,
            jax.tree.map(
                lambda s: NamedSharding(mesh, s), sp,
                is_leaf=lambda x: isinstance(x, P),
            ),
        )
        n_params = sum(x.size for x in jax.tree.leaves(params))
        if fast:
            # bf16 model params, fp32 masters live in the optimizer state
            params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
        return place(params, specs), place(opt_state, opt_specs), step, n_params

    def run(fast: bool, batch: int, **cfg_over):
        params, opt_state, step, n_params = build_step(fast, **cfg_over)
        key = jax.random.PRNGKey(1)
        tokens = jax.random.randint(
            key, (batch, SEQ), 0, cfg_common["vocab_size"]
        )
        targets = jnp.roll(tokens, -1, axis=1)
        for _ in range(WARMUP):
            params, opt_state, loss = step(params, opt_state, tokens, targets)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(STEPS):
            params, opt_state, loss = step(params, opt_state, tokens, targets)
        final_loss = float(loss)
        dt = time.perf_counter() - t0
        assert jnp.isfinite(final_loss), "non-finite loss in benchmark"
        tps = batch * SEQ * STEPS / dt
        log(f"{'fast' if fast else 'base'} b={batch}: "
            f"{dt/STEPS*1e3:.1f} ms/step, {tps:,.0f} tokens/s, "
            f"loss {final_loss:.3f}")
        return tps, n_params

    log(f"devices: {jax.devices()}")
    base, _ = run(fast=False, batch=BATCH)
    fast, best_batch, n_params = 0.0, BATCH, 0
    fast_matched = None  # fast-path tokens/s at the baseline's batch
    last_err = None
    for b in FAST_BATCHES:
        try:
            tps, n_params = run(fast=True, batch=b)
        except AssertionError:
            raise  # non-finite loss is a correctness failure, never OOM
        except Exception as e:
            msg = str(e)
            oom = any(t in msg.upper() for t in
                      ("RESOURCE_EXHAUSTED", "OUT OF MEMORY", "OOM",
                       "ALLOCAT"))
            if not oom or fast == 0.0:
                raise  # only HBM exhaustion ends the sweep quietly
            last_err = e
            log(f"fast b={b} OOM ({msg[:120]}); keeping best so far")
            break
        if b == BATCH:
            fast_matched = tps
        if tps > fast:
            fast, best_batch = tps, b
    if fast == 0.0:
        raise RuntimeError("fast path failed at every batch") from last_err

    # in-process A/B of the perf levers (fused CE, remat): same process
    # so both sides see the same chip state.  Each entry is
    # headline/variant tokens-per-sec, so >1 means the lever helps.
    ab = {}
    if on_tpu:
        # the default is fused_ce=None (auto by logits size, PROFILE_r05)
        # — the headline already runs whatever auto picks at best_batch,
        # so the informative variant is the FORCED OPPOSITE of that
        # choice.  New key name (fused_ce_auto_speedup) because the old
        # fused_ce_speedup trended the inverse lever (forced-off vs a
        # forced-fused headline); > 1 means auto beat the opposite path.
        # The prediction uses the dispatcher's own exported rule on the
        # shard_map-LOCAL sizes (tokens/dp, vocab/tp) — global shapes
        # would mispredict on any multi-device mesh.
        from apex_tpu.transformer.tensor_parallel.cross_entropy import (
            fused_ce_auto,
        )

        try:
            mesh = parallel_state.get_mesh()
            dp, tp = mesh.shape["dp"], mesh.shape["tp"]
        except Exception:
            # headline already captured — a surprise here must degrade
            # to the single-chip arithmetic, not lose the whole child
            dp = tp = 1
        auto_fused = fused_ce_auto(
            best_batch // dp * SEQ, cfg_common["vocab_size"] // tp
        )
        for tag, over in (
            ("fused_ce_auto", {"fused_ce": not auto_fused}),
            ("remat", {"remat": False}),
        ):
            try:
                tps_var, _ = run(fast=True, batch=best_batch, **over)
                ab[f"{tag}_speedup"] = round(fast / tps_var, 3)
            except Exception as e:
                # includes a variant's non-finite-loss assert: after the
                # headline is captured, a broken VARIANT is a finding to
                # record — re-raising would discard the captured
                # headline
                ab[f"{tag}_speedup"] = None
                ab[f"{tag}_error"] = str(e)[:200]
                log(f"ab {tag} variant failed: {str(e)[:160]}")

    # model FLOPs per token: 6*N (fwd+bwd matmuls) + 12*L*h*s attention
    # — the shared estimate (telemetry.metrics), one numerator for
    # bench MFU and the live StepStats MFU
    from apex_tpu.telemetry.metrics import transformer_flops_per_token

    flops_per_token = transformer_flops_per_token(
        n_params, cfg_common["num_layers"], cfg_common["hidden_size"], SEQ
    )
    peak = _peak_flops(jax.devices()[0]) if on_tpu else None
    mfu = round(fast * flops_per_token / peak, 4) if peak else None
    print(json.dumps({
        "metric": "gpt_tp1_tokens_per_sec",
        "value": round(fast, 1),
        "unit": "tokens/s",
        # matched-batch comparison isolates the fast-path changes (bf16 +
        # flash + fused masters); batch-size scaling is reported via
        # value@best_batch separately.  CPU run: null, not a
        # number — bf16 has no CPU matrix units, so a ratio measured
        # there would misrepresent TPU (the note carries the why)
        "vs_baseline": (round((fast_matched or fast) / base, 3)
                        if on_tpu else None),
        "platform": platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "mfu": mfu,
        "n_params": n_params,
        "batch": best_batch,
        "seq": SEQ,
        "steps": STEPS,
        "warmup": WARMUP,
        "ms_per_step": round(best_batch * SEQ / fast * 1e3, 2),
        **({"ab": ab} if ab else {}),
        **({} if on_tpu else {"note": (
            "cpu run (--platform cpu): bf16 has no CPU matrix "
            "units, so vs_baseline is not representative of TPU"
        )}),
    }))


def child_extras(platform: str):
    """BASELINE.md extra targets: RN50-ish images/sec (bf16+SyncBN-off,
    O2-analog) and FusedLAMB vs unfused per-tensor LAMB step time on a
    BERT-large-shaped param set (scaled down under --platform cpu)."""
    if platform == "cpu":
        _pin_cpu()
    else:
        _require_tpu()
    import jax
    import jax.numpy as jnp

    on_tpu = platform != "cpu"
    out = {"platform": platform}

    def _emit_partial():
        # cumulative snapshot after each section: if a later section's
        # cold compile outlives the child budget, _run_child salvages
        # the last JSON line instead of losing the whole run (the r5
        # round-start extras child died exactly this way)
        print(json.dumps({**out, "partial": True}), flush=True)

    # ---- RN50 images/sec, amp-O2 analog (bf16 compute, fp32 masters)
    from apex_tpu.models.resnet import ResNet, ResNetConfig
    from apex_tpu.optimizers import FusedAdam

    batch = 64 if on_tpu else 4
    size = 224 if on_tpu else 32
    model = ResNet(ResNetConfig(
        depth=50 if on_tpu else 18,
        compute_dtype=jnp.bfloat16,
        sync_bn_axis=None,
    ))
    params, batch_stats = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-3, master_weights=True)
    opt_state = opt.init(params)
    images = jax.random.normal(
        jax.random.PRNGKey(1), (batch, size, size, 3), jnp.bfloat16
    )
    labels = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 1000)

    @jax.jit
    def rn_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            logits, new_stats = model.apply(
                p, batch_stats, images, training=True
            )
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(
                jnp.take_along_axis(logp, labels[:, None], axis=1)
            ), new_stats

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        new_params, new_opt = opt.step(opt_state, grads, params)
        return new_params, new_stats, new_opt, loss

    p = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    for _ in range(2):
        p, batch_stats, opt_state, loss = rn_step(
            p, batch_stats, opt_state, images, labels
        )
    float(loss)
    steps = 10 if on_tpu else 3
    t0 = time.perf_counter()
    for _ in range(steps):
        p, batch_stats, opt_state, loss = rn_step(
            p, batch_stats, opt_state, images, labels
        )
    float(loss)
    dt = time.perf_counter() - t0
    out["rn50_images_per_sec"] = round(batch * steps / dt, 1)
    out["rn50_batch"] = batch
    out["rn50_depth"] = model.config.depth
    out["rn50_image_size"] = size
    # measurement spec, so regressions are reproducible
    out["rn50_spec"] = {
        "steps": steps, "warmup": 2, "compute_dtype": "bfloat16",
        "params_dtype": "bfloat16 + fp32 masters (O2-analog)",
        "optimizer": "FusedAdam(master_weights=True)",
    }
    log(f"rn50: {out['rn50_images_per_sec']} images/s (batch {batch})")
    _emit_partial()

    # ---- FusedLAMB (one jitted pytree step) vs unfused LAMB (same math,
    # one dispatch per tensor per stage — the pre-multi-tensor torch
    # optimizer pattern the reference's fused kernels beat),
    # BERT-large-shaped tensor list (~1 embed + 4 mats x L layers)
    from apex_tpu.optimizers import FusedLAMB

    h, L, vocab = (1024, 24, 30522) if on_tpu else (256, 4, 1024)
    key = jax.random.PRNGKey(3)
    params = {"embed": jax.random.normal(key, (vocab, h)) * 0.02}
    for i in range(L):
        params[f"l{i}"] = {
            "qkv": jax.random.normal(key, (h, 3 * h)) * 0.02,
            "proj": jax.random.normal(key, (h, h)) * 0.02,
            "fc1": jax.random.normal(key, (h, 4 * h)) * 0.02,
            "fc2": jax.random.normal(key, (4 * h, h)) * 0.02,
        }
    grads = jax.tree.map(lambda p: p * 1e-3, params)

    lamb = FusedLAMB(lr=1e-3, use_nvlamb=True)
    lamb_state = lamb.init(params)
    lamb_step = jax.jit(lambda s, g, p: lamb.step(s, g, p))

    # unfused reference: identical LAMB math, leaf at a time
    b1, b2, eps, wd, lr, max_norm = 0.9, 0.999, 1e-6, 0.01, 1e-3, 1.0

    @jax.jit
    def leaf_sqnorm(g):
        return jnp.sum(jnp.square(g.astype(jnp.float32)))

    @jax.jit
    def leaf_lamb(p, g, m, v, clip, step):
        g = g.astype(jnp.float32) * clip
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** step)
        vhat = v / (1 - b2 ** step)
        upd = mhat / (jnp.sqrt(vhat) + eps) + wd * p
        pn = jnp.sqrt(jnp.sum(jnp.square(p)))
        un = jnp.sqrt(jnp.sum(jnp.square(upd)))
        trust = jnp.where((pn > 0) & (un > 0), pn / un, 1.0)
        return p - lr * trust * upd, m, v

    def unfused_step(state, grads, params):
        leaves_p, treedef = jax.tree.flatten(params)
        leaves_g = jax.tree.leaves(grads)
        # one dispatch per tensor for the norm, host-side combine — the
        # unfused pattern (reference computes this fused in one kernel)
        gnorm = float(
            jnp.sqrt(sum(float(leaf_sqnorm(g)) for g in leaves_g))
        )
        clip = min(1.0, max_norm / max(gnorm, 1e-12))
        step = state["step"] + 1
        new_p, new_m, new_v = [], [], []
        for p_, g_, m_, v_ in zip(
            leaves_p, leaves_g, state["m"], state["v"]
        ):
            p2, m2, v2 = leaf_lamb(p_, g_, m_, v_, clip, step)
            new_p.append(p2)
            new_m.append(m2)
            new_v.append(v2)
        return (
            {"step": step, "m": new_m, "v": new_v},
            jax.tree.unflatten(treedef, new_p),
        )

    zeros = [jnp.zeros_like(x, jnp.float32) for x in jax.tree.leaves(params)]
    unfused_state = {"step": 0, "m": list(zeros), "v": list(zeros)}

    def timeit(fn, *args, n=20):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(n):
            outp = fn(*args)
        jax.block_until_ready(outp)
        return (time.perf_counter() - t0) / n * 1e3

    out["fused_lamb_ms"] = round(
        timeit(lamb_step, lamb_state, grads, params), 3
    )
    out["unfused_lamb_ms"] = round(
        timeit(unfused_step, unfused_state, grads, params), 3
    )
    out["lamb_speedup"] = round(
        out["unfused_lamb_ms"] / out["fused_lamb_ms"], 2
    )
    out["lamb_spec"] = {
        "timeit_iters": 20, "warmup": 1, "dtype": "float32",
        "shape": f"BERT-large-ish h={h} L={L} vocab={vocab} "
                 f"({1 + 4 * L} tensors)",
        "use_nvlamb": True,
    }
    log(f"lamb fused {out['fused_lamb_ms']} ms vs unfused "
        f"{out['unfused_lamb_ms']} ms ({out['lamb_speedup']}x)")
    _emit_partial()

    # ---- DCGAN-style multi-model / multi-loss-scaler step (BASELINE.md:
    # 'DCGAN multi-model/multi-loss scaling, functional, 3 loss scalers')
    from apex_tpu import amp as apex_amp

    mp = apex_amp.initialize(opt_level="O1", num_losses=3)
    gb, zdim, img = (64, 64, 784) if on_tpu else (16, 16, 64)
    kG, kD, kz = jax.random.split(jax.random.PRNGKey(4), 3)
    G = {"w1": 0.1 * jax.random.normal(kG, (zdim, 256)),
         "w2": 0.1 * jax.random.normal(kG, (256, img))}
    D = {"w1": 0.1 * jax.random.normal(kD, (img, 256)),
         "w2": 0.1 * jax.random.normal(kD, (256, 1))}
    g_opt = FusedAdam(lr=2e-4)
    d_opt = FusedAdam(lr=2e-4)
    g_state, d_state = g_opt.init(G), d_opt.init(D)
    amp_state = mp.init()
    real = jax.random.normal(jax.random.PRNGKey(5), (gb, img))

    def gen(Gp, z):
        h_ = jnp.tanh(z @ Gp["w1"].astype(z.dtype))
        return jnp.tanh(h_ @ Gp["w2"].astype(h_.dtype))

    def disc(Dp, x_):
        h_ = jnp.tanh(x_ @ Dp["w1"].astype(x_.dtype))
        return h_ @ Dp["w2"].astype(h_.dtype)

    bce = lambda logit, y: jnp.mean(
        jnp.maximum(logit, 0) - logit * y
        + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    )

    @jax.jit
    def gan_step(G, D, g_state, d_state, amp_state, z, real):
        low = jnp.float16
        # D step: two separately-scaled losses (real, fake), like the
        # reference's errD_real/errD_fake with per-loss scalers
        def d_loss_real(Dp):
            l = bce(disc(Dp, real.astype(low)).astype(jnp.float32), 1.0)
            return mp.scale_loss(amp_state, l, loss_id=0), l

        def d_loss_fake(Dp):
            fake = gen(jax.tree.map(lambda w: w.astype(low), G),
                       z.astype(low))
            l = bce(disc(Dp, fake).astype(jnp.float32), 0.0)
            return mp.scale_loss(amp_state, l, loss_id=1), l

        gr, lr_ = jax.grad(d_loss_real, has_aux=True)(D)
        gr, f0, amp_state = mp.unscale_and_adjust(amp_state, gr, loss_id=0)
        gf, lf_ = jax.grad(d_loss_fake, has_aux=True)(D)
        gf, f1, amp_state = mp.unscale_and_adjust(amp_state, gf, loss_id=1)
        d_grads = jax.tree.map(lambda a, b: a + b, gr, gf)
        D, d_state = d_opt.step(d_state, d_grads, D,
                                grads_finite=f0 & f1)

        # G step: third scaler
        def g_loss(Gp):
            fake = gen(jax.tree.map(lambda w: w.astype(low), Gp),
                       z.astype(low))
            l = bce(disc(jax.tree.map(lambda w: w.astype(low), D),
                         fake).astype(jnp.float32), 1.0)
            return mp.scale_loss(amp_state, l, loss_id=2), l

        gg, lg_ = jax.grad(g_loss, has_aux=True)(G)
        gg, f2, amp_state = mp.unscale_and_adjust(amp_state, gg, loss_id=2)
        G, g_state = g_opt.step(g_state, gg, G, grads_finite=f2)
        return G, D, g_state, d_state, amp_state, lr_ + lf_, lg_

    z = jax.random.normal(kz, (gb, zdim))
    for _ in range(2):
        G, D, g_state, d_state, amp_state, dl, gl = gan_step(
            G, D, g_state, d_state, amp_state, z, real
        )
    jax.device_get((dl, gl))
    gan_steps = 10 if on_tpu else 3
    t0 = time.perf_counter()
    for _ in range(gan_steps):
        G, D, g_state, d_state, amp_state, dl, gl = gan_step(
            G, D, g_state, d_state, amp_state, z, real
        )
    dl, gl = jax.device_get((dl, gl))
    dt = time.perf_counter() - t0
    out["dcgan_multi_scaler"] = {
        "ms_per_step": round(dt / gan_steps * 1e3, 3),
        "d_loss": round(float(dl), 4),
        "g_loss": round(float(gl), 4),
        "finite": bool(jnp.isfinite(dl)) and bool(jnp.isfinite(gl)),
        "spec": {"steps": gan_steps, "warmup": 2, "batch": gb,
                 "opt_level": "O1 (fp16 + 3 dynamic per-loss scalers)"},
    }
    log(f"dcgan: {out['dcgan_multi_scaler']}")
    _emit_partial()

    # ---- long-sequence flash attention (streamed-K/V capability on the
    # record: the reference's fmha caps at seqlen 512, setup.py:405-415).
    # Guarded: a failure here (e.g. HBM exhaustion) must not discard the
    # extras already measured above (same policy as the GPT child's OOM
    # handling).
    try:
        _flash_long_seq(out, on_tpu, timeit)
    except Exception as e:  # pragma: no cover - depends on chip state
        out["flash_long_seq"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        log(f"flash long-seq skipped: {type(e).__name__}")
    _emit_partial()
    try:
        _t5_extra(out, on_tpu)
    except Exception as e:  # pragma: no cover - depends on chip state
        out["t5_encdec"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        log(f"t5 extra skipped: {type(e).__name__}")
    print(json.dumps(out))


def child_gradsync():
    """Grad-sync A/B row: ms/step of a 2-microbatch accumulate+reduce
    loop on the 8-virtual-device (dcn=2 x ici=4) hierarchical mesh,
    overlap on/off x compression on/off, against a no-collective
    compute baseline — ``exposed_comm_ms`` is the difference.  Always
    runs on virtual CPU devices (a single TPU chip has no dp axis to
    reduce over), so per the PR 3 convention ``vs_baseline`` is null:
    the structural win is tracked by OVERLAP_AUDIT/COMM_AUDIT, this
    row tracks that the code paths stay runnable and their relative
    cost across PRs."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _pin_cpu()
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel import hierarchical_data_parallel_mesh
    from apex_tpu.parallel.distributed import Reducer

    mesh = hierarchical_data_parallel_mesh(ici_size=4)
    L, W, ROWS, K = 4, 128, 16, 2
    ks = jax.random.split(jax.random.PRNGKey(0), L + 1)
    params = {f"l{i}": {"w": 0.1 * jax.random.normal(ks[i], (W, W)),
                        "b": jnp.zeros((W,))} for i in range(L)}
    params["head"] = 0.1 * jax.random.normal(ks[L], (W, 2 * W))

    def loss(p, x):
        h = x
        for i in range(L):
            h = jnp.tanh(h @ p[f"l{i}"]["w"] + p[f"l{i}"]["b"])
        z = h @ p["head"]
        return jnp.sum(z * z) / z.size

    pspec = jax.tree.map(lambda _: P(), params)
    data = jax.random.normal(
        jax.random.PRNGKey(1), (K, ROWS * 8, W))

    def build(reducer):
        # every variant returns ONE pmean'd scalar computed from its
        # (reduced or local) grads — a data dependency that keeps the
        # collectives alive, with an out-spec every shard_map
        # replication checker accepts
        def gsum(tree):
            return sum(jnp.sum(g * g) for g in jax.tree.leaves(tree))

        def step(p, batch):
            if reducer is None:  # compute-only baseline
                g = None
                for k in range(K):
                    gk = jax.grad(loss)(p, batch[k])
                    g = gk if g is None else jax.tree.map(
                        lambda a, b_: a + b_, g, gk)
                return jax.lax.pmean(gsum(g), ("dcn", "ici"))
            acc = reducer.init(p)
            for k in range(K):
                acc = reducer.accumulate(
                    acc, jax.grad(loss)(p, batch[k]))
            grads, _ = reducer.reduce(acc)
            return jax.lax.pmean(gsum(grads), ("dcn", "ici"))

        return jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(pspec, P(None, ("dcn", "ici"))),
            out_specs=P(),
        ))

    def measure(fn, steps=10):
        float(fn(params, data))
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(params, data)
        float(out)
        return (time.perf_counter() - t0) / steps * 1e3

    compute_ms = measure(build(None))
    rows = []
    for overlap in (False, True):
        for comp in (None, "int8"):
            red = Reducer(axis_name=("dcn", "ici"),
                          overlap_grad_sync=overlap,
                          bucket_bytes=96 * 1024, compression=comp)
            ms = measure(build(red))
            rows.append({
                "overlap": overlap,
                "compression": comp or "none",
                "ms_per_step": round(ms, 3),
                "exposed_comm_ms": round(max(ms - compute_ms, 0.0), 3),
            })
            log(f"grad-sync overlap={overlap} comp={comp or 'none'}: "
                f"{ms:.2f} ms/step")
    print(json.dumps({
        "metric": "grad_sync_ms_per_step",
        "platform": "cpu-virtual",
        # no TPU measurement happened on this mesh: null, not a fake
        # ratio (PR 3 convention)
        "vs_baseline": None,
        "note": "8 virtual CPU devices (dcn=2 x ici=4): relative cost "
                "only — DCN wall-clock wins are proven structurally "
                "by OVERLAP_AUDIT/COMM_AUDIT",
        "compute_only_ms": round(compute_ms, 3),
        "spec": {"layers": L, "width": W, "rows_per_device": ROWS,
                 "num_micro": K, "bucket_kb": 96, "steps": 10,
                 "warmup": 1},
        "rows": rows,
    }))


def child_zero3():
    """ZeRO-3 A/B row: ms/step of the full-parameter-sharding train
    step (gather-on-use weights + reduce-scatter grads + sharded
    update) vs the replicated FusedAdam step at the flagship
    CPU-dryrun GPT shape on the 8-virtual-device dp mesh, plus the
    param-gather cost measured in isolation.  Always a CPU
    measurement, so per the PR 3 convention ``vs_baseline`` is null —
    the memory win is proven structurally by MEMORY_AUDIT (compiled
    per-device bytes) and the wire win by ZERO3_AUDIT; this row tracks
    that the sharded path stays runnable and its step-time tax across
    PRs."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    _pin_cpu()
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.tensor_parallel.layers import (
        state_specs_like,
    )

    # the flagship CPU-dryrun shape (child_gpt's fallback config)
    VOCAB, LAYERS, HIDDEN, HEADS, SEQ, BATCH = 4096, 2, 256, 4, 256, 8
    WARMUP, STEPS = 2, 10
    BUCKET_KB = 256
    mesh = parallel_state.initialize_model_parallel()
    model = GPTModel(GPTConfig(
        vocab_size=VOCAB, num_layers=LAYERS, hidden_size=HIDDEN,
        num_attention_heads=HEADS, max_position_embeddings=SEQ,
        compute_dtype=jnp.float32, attention_impl="xla", remat=False,
    ))
    params = model.init(jax.random.PRNGKey(0))
    specs = model.param_specs()
    place = lambda tree, sp: jax.device_put(
        tree, jax.tree.map(lambda s: NamedSharding(mesh, s), sp,
                           is_leaf=lambda x: isinstance(x, P)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ),
                                0, VOCAB)
    targets = jnp.roll(tokens, -1, axis=1)

    def measure(fn, *args):
        for _ in range(WARMUP):
            out = fn(*args)
        jax.block_until_ready(jax.tree.leaves(out)[-1])
        t0 = time.perf_counter()
        for _ in range(STEPS):
            out = fn(*args)
        jax.block_until_ready(jax.tree.leaves(out)[-1])
        return (time.perf_counter() - t0) / STEPS * 1e3

    # replicated baseline
    ropt = FusedAdam(lr=1e-4, master_weights=True)
    rstate = ropt.init(params)
    rspecs = state_specs_like(specs, rstate)

    def rep_step(p, s, tok, tgt):
        loss, grads = jax.value_and_grad(model.loss)(p, tok, tgt)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
        p, s = ropt.step(s, grads, p)
        return p, s, loss

    rstep = jax.jit(jax.shard_map(
        rep_step, mesh=mesh,
        in_specs=(specs, rspecs, P("dp"), P("dp")),
        out_specs=(specs, rspecs, P())))
    rep_ms = measure(rstep, place(params, specs),
                     place(rstate, rspecs), tokens, targets)

    # zero3: gather-on-use
    opt = DistributedFusedAdam(lr=1e-4, shard_params=True,
                               bucket_bytes=BUCKET_KB * 1024)
    opt.build_layout(params, mesh=mesh)
    sspec, stspecs = opt.shard_spec(), opt.state_specs()
    shards = jax.jit(jax.shard_map(
        opt.init_shards, mesh=mesh, in_specs=(specs,),
        out_specs=sspec))(place(params, specs))
    state = jax.jit(jax.shard_map(
        opt.init, mesh=mesh, in_specs=(sspec,),
        out_specs=stspecs))(shards)

    def z3_step(sh, s, tok, tgt):
        p, s = opt.gather_params(sh, s)
        loss, grads = jax.value_and_grad(model.loss)(p, tok, tgt)
        sh, s = opt.step(s, grads, sh)
        return sh, s, loss

    zstep = jax.jit(jax.shard_map(
        z3_step, mesh=mesh,
        in_specs=(sspec, stspecs, P("dp"), P("dp")),
        out_specs=(sspec, stspecs, P())))
    z3_ms = measure(zstep, shards, state, tokens, targets)

    # the gather alone: what one full weight materialization costs
    def gather_only(sh):
        p, _ = opt.gather_params(sh)
        return sum(jnp.sum(l) for l in jax.tree.leaves(p))

    gfn = jax.jit(jax.shard_map(
        gather_only, mesh=mesh, in_specs=(sspec,), out_specs=P()))
    gather_ms = measure(gfn, shards)

    n_params = sum(int(l.size) for l in jax.tree.leaves(params))
    log(f"zero3: replicated {rep_ms:.2f} ms/step, zero3 {z3_ms:.2f} "
        f"ms/step, param-gather alone {gather_ms:.2f} ms")
    print(json.dumps({
        "metric": "zero3_ms_per_step",
        "value": round(z3_ms, 3),
        "unit": "ms/step (8 virtual CPU devices)",
        # no TPU measurement happened on this mesh: null, not a fake
        # ratio (PR 3 convention)
        "vs_baseline": None,
        "platform": "cpu-virtual",
        "note": "relative cost only — the memory win is MEMORY_AUDIT's "
                "compiled bytes, the wire win ZERO3_AUDIT's; this row "
                "tracks the sharded step's runnable cost across PRs",
        "ms_per_step_replicated": round(rep_ms, 3),
        "ms_per_step_zero3": round(z3_ms, 3),
        "param_gather_ms": round(gather_ms, 3),
        "exposed_zero3_tax_ms": round(max(z3_ms - rep_ms, 0.0), 3),
        "spec": {"vocab": VOCAB, "layers": LAYERS, "hidden": HIDDEN,
                 "heads": HEADS, "seq": SEQ, "batch": BATCH,
                 "n_params": n_params, "bucket_kb": BUCKET_KB,
                 "steps": STEPS, "warmup": WARMUP},
    }))


def child_decode():
    """Decode-throughput rows: tokens/s/chip of the fused serving
    decode step (paged cache + fmha_decode + on-device sampling, the
    whole ``GPTModel.decode_step`` pipeline) at decode batch
    {1, 8, 64, 256} for fp32 / bf16 / int8-KV caches, the
    WEIGHT-WIDTH rows: weight {bf16, int8, int4} x KV {fp32, int8} at
    batch {1, 8, 64} with the step's weight-stream GB/s, plus one
    mixed prefill+decode row (a continuous-batching window that admits
    a prompt mid-stream), the MIXED-LOAD rows: TTFT p50/p95 and
    decode-stall time of long-prompt arrivals with chunked prefill on
    vs off vs on-with-shared-prefix (prefix-cache hits) at decode
    batch {8, 64, 256}, and the SPECULATIVE rows: n-gram
    draft-and-verify (k=4) vs the plain step at batch {1, 8, 64} on
    repetitive vs adversarial prompts — tokens/s plus
    accepted-tokens/step, plus the TENSOR-PARALLEL rows: the sharded
    decode step at tp {1, 2, 4} x weight {bf16, int8, int4} with
    per-chip pool bytes and weight-stream GB/s/chip.  Runs the
    flagship CPU-dryrun GPT shape on ONE device (tp rows shard over
    virtual devices) so "per chip" is honest; always a CPU measurement here, so
    per the PR 3 convention ``vs_baseline`` is null — the row tracks
    that the serving stack stays runnable and how the variants rank,
    not a TPU rate."""
    _pin_cpu()
    # the tensor-parallel rows below shard over up to 4 virtual
    # devices — force the host split BEFORE jax initialises
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving.kv_cache import (
        KVCacheConfig, PagedKVCache, init_pools,
    )
    from apex_tpu.serving.serve import init_carry
    from apex_tpu.transformer import parallel_state

    # the flagship CPU-dryrun shape (child_gpt's fallback config)
    VOCAB, LAYERS, HIDDEN, HEADS, SEQ = 4096, 2, 256, 4, 256
    PAGE, PROMPT, WARMUP, STEPS = 32, 64, 2, 10
    BATCHES = [1, 8, 64, 256]
    mesh = parallel_state.initialize_model_parallel(
        devices=jax.devices()[:1])
    model = GPTModel(GPTConfig(
        vocab_size=VOCAB, num_layers=LAYERS, hidden_size=HIDDEN,
        num_attention_heads=HEADS,
        # the mixed-load rows admit 520-token prompts (512-token
        # shared prefix + tail) whose cache rounds up to 17 pages
        max_position_embeddings=1024,
        compute_dtype=jnp.float32, attention_impl="xla", remat=False,
    ))
    params = model.init(jax.random.PRNGKey(0))

    # weight-pool block for the quantized-weight rows: HIDDEN=256 puts
    # the projection widths at {768, 256, 1024} — block 64 divides
    # every one AND keeps whole blocks per int4 nibble half
    WQ_BLOCK = 64

    def run_variant(kv_name, batch, weight=None, mesh=mesh,
                    wq_block=WQ_BLOCK):
        kv_dtype = jnp.int8 if kv_name == "int8" else None
        dtype = (jnp.float32 if kv_name == "float32"
                 else jnp.bfloat16)
        pages_per_seq = -(-(PROMPT + STEPS + WARMUP + 4) // PAGE)
        cfg = KVCacheConfig(
            num_layers=LAYERS, num_heads=HEADS,
            head_dim=HIDDEN // HEADS,
            num_pages=1 + batch * pages_per_seq, page_size=PAGE,
            max_seqs=batch, pages_per_seq=pages_per_seq,
            dtype=dtype, kv_dtype=kv_dtype,
        )
        fns = model.decode_fns(params, mesh, cfg,
                               max_prompt_len=PROMPT,
                               weight_dtype=weight,
                               weight_block=wq_block)
        cache = PagedKVCache(cfg)
        pools = init_pools(cfg)
        carry = init_carry(batch, sharding=fns.carry_sharding)
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (1, PROMPT), 0, VOCAB
        ).astype(jnp.int32)
        key = jax.random.PRNGKey(2)
        t_pref = None
        for slot in range(batch):
            cache.admit(slot, PROMPT + STEPS + WARMUP + 4)
            t0 = time.perf_counter()
            pools, first = fns.prefill(
                pools, toks, jnp.int32(PROMPT),
                jnp.asarray(cache.page_table[slot]), key)
            jax.block_until_ready(first)
            t_pref = time.perf_counter() - t0   # last = steady-state
            carry = {
                "tokens": carry["tokens"].at[slot].set(first),
                "lengths": carry["lengths"].at[slot].set(PROMPT),
                "steps_left": carry["steps_left"].at[slot].set(
                    STEPS + WARMUP + 2),
                "done": carry["done"].at[slot].set(False),
                "sample_keys": carry["sample_keys"],
            }
        pt = jnp.asarray(cache.page_table)
        for _ in range(WARMUP):
            pools, carry = fns.decode(pools, carry, pt)
        jax.block_until_ready(carry["tokens"])
        t0 = time.perf_counter()
        for _ in range(STEPS):
            pools, carry = fns.decode(pools, carry, pt)
        jax.block_until_ready(carry["tokens"])
        ms = (time.perf_counter() - t0) / STEPS * 1e3
        return ms, batch / ms * 1e3, t_pref * 1e3, \
            int(fns.weight_stream_bytes)

    rows = {}
    mixed_src = None
    for kv_name in ("float32", "bfloat16", "int8"):
        per_batch = {}
        for batch in BATCHES:
            ms, tps, pref_ms, _ = run_variant(kv_name, batch)
            per_batch[str(batch)] = {
                "ms_per_step": round(ms, 3),
                "tokens_per_sec_per_chip": round(tps, 1),
            }
            if kv_name == "bfloat16" and batch == 8:
                mixed_src = (ms, pref_ms)
            log(f"decode {kv_name} b{batch}: {ms:.2f} ms/step, "
                f"{tps:,.0f} tokens/s/chip")
        rows[kv_name] = per_batch

    # ---- weight-width rows: the quantized weight pools (block-wise
    # int8, packed int4 — dequantized inside the matmul tiles) vs the
    # bf16 cast, each over fp32 and int8 KV caches at batch {1,8,64}.
    # weight_stream_gbs is the decode step's weight traffic (the whole
    # param pool per step) over the measured wall — the roofline the
    # tentpole moves; on CPU the step is compute-bound, so the
    # in-tile dequant arithmetic can RAISE ms/step while the weight
    # bytes shrink — the TPU capture reads the GB/s column, not the
    # CPU wall ratio.
    wq = {}
    for weight in ("bf16", "int8", "int4"):
        per_w = {}
        for kv_name in ("float32", "int8"):
            per_b = {}
            for batch in (1, 8, 64):
                ms, tps, _, wbytes = run_variant(
                    kv_name, batch, weight=weight)
                per_b[str(batch)] = {
                    "ms_per_step": round(ms, 3),
                    "tokens_per_sec_per_chip": round(tps, 1),
                    "weight_stream_gbs": round(
                        wbytes / ms * 1e3 / 1e9, 3),
                }
                log(f"decode w={weight} kv={kv_name} b{batch}: "
                    f"{ms:.2f} ms/step, {tps:,.0f} tokens/s/chip")
            per_w[f"kv_{kv_name}"] = per_b
        per_w["weight_pool_bytes"] = wbytes
        wq[weight] = per_w
    wq["note"] = (
        f"weight_block={WQ_BLOCK}; pool converted once by decode_fns "
        "and streamed whole every step; CPU rows price the dequant "
        "arithmetic — the bandwidth win is the weight_pool_bytes "
        "column (projections shrink ~4x int8 / ~8x int4 under fp32; "
        "embeddings/norms stay model-dtype, and this bench shape's "
        "4096-vocab embedding dominates its tiny pool)")
    rows["weight_quant"] = wq

    # mixed prefill+decode: a continuous-batching window at b=8 where
    # one slot re-admits (prefill) between decode windows — the
    # serving steady state, not a pure-decode best case.  Derived from
    # the loop's already-measured bf16/b=8 cell (a re-run would pay the
    # variant's compile + warmup again for identical numbers).
    ms, pref_ms = mixed_src
    mixed_tps = (8 * STEPS + PROMPT) / (ms * STEPS + pref_ms) * 1e3
    rows["mixed_prefill_decode"] = {
        "decode_ms_per_step": round(ms, 3),
        "prefill_ms": round(pref_ms, 3),
        "tokens_per_sec_per_chip": round(mixed_tps, 1),
        "note": "b=8 bf16: one prompt admission per "
                f"{STEPS}-step decode window",
    }

    # ---- mixed-load rows: long-prompt arrivals against a full batch
    # of already-decoding slots, chunked prefill OFF vs ON vs ON with
    # a shared 512-token prefix (prefix-cache hits).  Measures TTFT
    # p50/p95 of the long arrivals and the decode stall their prefills
    # impose (total + worst single stall while decode slots were
    # live), recorded against the batch-256 cliff above (bf16 tokens/s
    # peaks at b=64 and FALLS at 256) so the next TPU capture
    # quantifies the stall-free win where the cliff lives.  All three
    # variants serve IDENTICAL long prompts (shared 512-token prefix +
    # distinct tails); only the scheduler mode changes.
    from apex_tpu.serving.serve import ContinuousBatcher, Request

    import numpy as np

    MIX_PREFIX, MIX_TAIL, CHUNK = 512, 8, 256
    LONGS, SHORT_NEW, LONG_NEW = 4, 24, 8
    mix_rng = np.random.RandomState(11)
    shared_prefix = mix_rng.randint(1, VOCAB, (MIX_PREFIX,))
    long_prompts = [
        list(map(int, shared_prefix))
        + list(map(int, mix_rng.randint(1, VOCAB, (MIX_TAIL,))))
        for _ in range(LONGS)
    ]
    short_prompts = [list(map(int, mix_rng.randint(1, VOCAB, (8,))))
                     for _ in range(256)]

    def run_mixed(batch, chunked, prefix):
        # the decode STEP's cost is set by the compiled slot width
        # (fixed shapes), not by how many slots are live — so the
        # short-decoder count is capped to keep the CPU row affordable
        # while `batch` still sets the shape whose cliff is measured
        n_short = min(batch, 32) - 1
        long_len = MIX_PREFIX + MIX_TAIL
        pps = -(-(long_len + LONG_NEW) // PAGE)
        num_pages = 1 + n_short * (-(-(8 + SHORT_NEW) // PAGE)) \
            + (LONGS + 2) * pps
        cfg = KVCacheConfig(
            num_layers=LAYERS, num_heads=HEADS,
            head_dim=HIDDEN // HEADS, num_pages=num_pages,
            page_size=PAGE, max_seqs=batch, pages_per_seq=pps,
            dtype=jnp.bfloat16)
        fns = model.decode_fns(
            params, mesh, cfg, max_prompt_len=long_len,
            prefill_chunk=CHUNK if chunked else None)
        batcher = ContinuousBatcher(
            fns.prefill, fns.decode, PagedKVCache(cfg),
            init_pools(cfg), max_prompt_len=long_len, harvest_every=4,
            chunk_fn=fns.chunk,
            prefill_chunk=CHUNK if chunked else None,
            prefix_cache=prefix, measure_stall=True)
        # prime: serve the shared prefix once OUTSIDE the measured
        # window (registers the prefix pages; also pays first-call
        # compiles), then measure the mixed workload where every long
        # arrival can hit
        batcher.run([Request(uid="prime", prompt=long_prompts[0],
                             max_new_tokens=2)])
        batcher.decode_stall_s = 0.0
        batcher.max_prefill_stall_s = 0.0
        for k in batcher.prefix_stats:
            batcher.prefix_stats[k] = 0
        reqs = [Request(uid=f"s{i}", prompt=short_prompts[i],
                        max_new_tokens=SHORT_NEW)
                for i in range(n_short)]
        reqs += [Request(uid=f"L{j}", prompt=long_prompts[j],
                         max_new_tokens=LONG_NEW)
                 for j in range(LONGS)]
        t0 = time.perf_counter()
        comps = batcher.run(reqs)
        wall = time.perf_counter() - t0
        ttfts = sorted(c.ttft_s for uid, c in comps.items()
                       if str(uid).startswith("L"))
        pct = lambda q: ttfts[min(len(ttfts) - 1,
                                  int(round(q * (len(ttfts) - 1))))]
        row = {
            "ttft_p50_ms": round(pct(0.50) * 1e3, 2),
            "ttft_p95_ms": round(pct(0.95) * 1e3, 2),
            "decode_stall_ms": round(batcher.decode_stall_s * 1e3, 2),
            "max_prefill_stall_ms": round(
                batcher.max_prefill_stall_s * 1e3, 2),
            "wall_ms": round(wall * 1e3, 1),
        }
        if chunked:
            row["prefill_chunks"] = batcher.prefill_chunks
        if prefix:
            # rate over the LONG arrivals only: the short decoders'
            # sub-page prompts are structurally unmatchable and would
            # dilute the headline with the short/long mix, not the
            # cache's effectiveness
            px = batcher.prefix_stats
            row["prefix_hit_rate_long_arrivals"] = round(
                px["hits"] / LONGS, 3)
            row["prefill_tokens_skipped"] = px["tokens_skipped"]
            row["pages_shared"] = px["shared_pages"]
        return row

    mixed_load = {}
    for batch in (8, 64, 256):
        per = {}
        for name, chunked, prefix in (
                ("monolithic", False, False),
                ("chunked", True, False),
                ("chunked_prefix", True, True)):
            per[name] = run_mixed(batch, chunked, prefix)
            log(f"mixed b{batch} {name}: "
                f"ttft p95 {per[name]['ttft_p95_ms']} ms, "
                f"max stall {per[name]['max_prefill_stall_ms']} ms")
        per["note"] = (
            f"{min(batch, 32) - 1} short decoders + {LONGS} long "
            f"arrivals ({MIX_PREFIX}-token shared prefix + {MIX_TAIL} "
            "tail) at the batch-wide compiled decode shape; stall = "
            "prefill wall while decode slots were live, queue-drained "
            "before each measurement; prefix primed out-of-window")
        mixed_load[str(batch)] = per
    rows["mixed_load"] = mixed_load

    # ---- speculative decoding rows: n-gram self-speculation (k=4,
    # draft-and-verify through the paged pool) vs the plain one-token
    # step at decode batch {1, 8, 64}, on REPETITIVE prompts (tiled
    # 4-token cycle — the drafter's best case: an untrained model's
    # greedy loop gives the n-gram matcher a periodic context to hit)
    # and ADVERSARIAL prompts (uniform-random tokens — near-zero hits,
    # so the row prices pure verify overhead).  tokens/s is end-to-end
    # through the batcher (prefill + verify + per-step host sync);
    # accepted_tokens_per_step is committed tokens per live slot-step
    # (1.0 = never better than plain).  CPU rows are compute-bound
    # where a TPU decode step is weight-bandwidth-bound, so the on/off
    # ratio here UNDERSTATES the TPU win — informational, not gated.
    from apex_tpu.serving.speculate import NGramDraftSource

    SPEC_K, SPEC_NEW, SPEC_PROMPT = 4, 24, 32
    spec_rng = np.random.RandomState(17)

    def spec_prompts(kind, n):
        out = []
        for _ in range(n):
            if kind == "repetitive":
                pat = spec_rng.randint(1, VOCAB, (4,))
                out.append(list(map(int, np.tile(
                    pat, SPEC_PROMPT // 4)[:SPEC_PROMPT])))
            else:
                out.append(list(map(int, spec_rng.randint(
                    1, VOCAB, (SPEC_PROMPT,)))))
        return out

    def run_spec(batch, spec_on):
        pps = -(-(SPEC_PROMPT + SPEC_NEW) // PAGE)
        cfg = KVCacheConfig(
            num_layers=LAYERS, num_heads=HEADS,
            head_dim=HIDDEN // HEADS, num_pages=1 + batch * pps,
            page_size=PAGE, max_seqs=batch, pages_per_seq=pps,
            dtype=jnp.bfloat16)
        fns = model.decode_fns(
            params, mesh, cfg, max_prompt_len=SPEC_PROMPT,
            speculate_k=SPEC_K if spec_on else None)
        per_kind = {}
        for kind in ("repetitive", "adversarial"):
            kw = {}
            if spec_on:
                kw = dict(spec_fn=fns.spec, speculate_k=SPEC_K,
                          draft_source=NGramDraftSource(SPEC_K))
            batcher = ContinuousBatcher(
                fns.prefill, fns.decode, PagedKVCache(cfg),
                init_pools(cfg), max_prompt_len=SPEC_PROMPT,
                harvest_every=4, **kw)
            prompts = spec_prompts(kind, batch)
            # prime wave pays the first-call compiles out-of-window
            batcher.run([Request(uid="prime", prompt=prompts[0],
                                 max_new_tokens=4)])
            if spec_on:
                for k in list(batcher.spec_stats):
                    batcher.spec_stats[k] = (
                        {} if k == "by_source" else 0)
            reqs = [Request(uid=f"q{i}", prompt=p,
                            max_new_tokens=SPEC_NEW)
                    for i, p in enumerate(prompts)]
            t0 = time.perf_counter()
            comps = batcher.run(reqs)
            wall = time.perf_counter() - t0
            toks = sum(len(c.tokens) for c in comps.values())
            row = {
                "tokens_per_sec": round(toks / wall, 1),
                "wall_ms": round(wall * 1e3, 1),
            }
            if spec_on:
                st = batcher.spec_stats
                row["accepted_tokens_per_step"] = round(
                    st["committed"] / max(st["slot_steps"], 1), 3)
                row["draft_hit_rate"] = round(
                    st["accepted"] / max(st["drafted"], 1), 3)
                row["verify_steps"] = st["steps"]
            per_kind[kind] = row
            log(f"spec b{batch} {'on' if spec_on else 'off'} "
                f"{kind}: {row['tokens_per_sec']:,.0f} tokens/s"
                + (f", {row['accepted_tokens_per_step']} acc/step"
                   if spec_on else ""))
        return per_kind

    speculative = {}
    for batch in (1, 8, 64):
        speculative[str(batch)] = {
            "plain": run_spec(batch, False),
            "speculate_k4": run_spec(batch, True),
        }
    speculative["note"] = (
        f"n-gram self-speculation k={SPEC_K}, {SPEC_NEW} new tokens "
        f"over {SPEC_PROMPT}-token prompts; accepted_tokens_per_step "
        "is committed/slot-step (plain step = 1.0); the untrained "
        "bench weights loop regardless of prompt, so adversarial rows "
        "still draft-hit once the generated tail goes periodic — the "
        "split prices verify overhead, not model-dependent hit rates; "
        "CPU verify is compute-bound so on/off wall ratios understate "
        "the weight-stream win — see docs/serving.md")

    # ---- draft-source crossover cells: the speculation ladder's
    # three real tiers (ngram, model, model ± off-ramp tree) on the
    # ADVERSARIAL prompt set only — repetitive prompts are the n-gram
    # drafter's home turf; the recorded crossover number is
    # accepted_tokens_per_step model vs ngram where prompt-lookup has
    # nothing to hit.  The draft model is THIS model's own int4 pool
    # (shared tokenizer by construction) serving from its own KV
    # slice; draft_wall_frac prices the host-sequential draft loop
    # against the whole serving wall.
    from apex_tpu.serving.speculate import (
        ModelDraftSource, offramp_tree,
    )

    def run_draft_source(source):
        batch = 4
        pps = -(-(SPEC_PROMPT + SPEC_NEW + 2 * SPEC_K) // PAGE)
        cfg = KVCacheConfig(
            num_layers=LAYERS, num_heads=HEADS,
            head_dim=HIDDEN // HEADS, num_pages=1 + batch * pps,
            page_size=PAGE, max_seqs=batch, pages_per_seq=pps,
            dtype=jnp.bfloat16)
        tree = (offramp_tree(SPEC_K) if source == "model_tree"
                else None)
        dm = None
        kw = {}
        if source == "ngram":
            kw = dict(draft_source=NGramDraftSource(SPEC_K))
        else:
            dcfg = KVCacheConfig(
                num_layers=LAYERS, num_heads=HEADS,
                head_dim=HIDDEN // HEADS, num_pages=1 + batch * pps,
                page_size=PAGE, max_seqs=batch, pages_per_seq=pps,
                dtype=jnp.bfloat16)
            dm = ModelDraftSource(
                model, params, mesh, dcfg, k=SPEC_K, tree=tree,
                weight_dtype="int4", weight_block=WQ_BLOCK)
        fns = model.decode_fns(
            params, mesh, cfg, max_prompt_len=SPEC_PROMPT,
            speculate_k=SPEC_K, spec_tree=tree, draft_model=dm)
        batcher = ContinuousBatcher(
            fns.prefill, fns.decode, PagedKVCache(cfg),
            init_pools(cfg), max_prompt_len=SPEC_PROMPT,
            harvest_every=4, spec_fn=fns.spec, speculate_k=SPEC_K,
            **kw)
        prompts = spec_prompts("adversarial", batch)
        batcher.run([Request(uid="prime", prompt=prompts[0],
                             max_new_tokens=4)])
        for k in list(batcher.spec_stats):
            batcher.spec_stats[k] = (
                {} if k == "by_source"
                else 0.0 if k == "draft_s" else 0)
        reqs = [Request(uid=f"q{i}", prompt=p,
                        max_new_tokens=SPEC_NEW)
                for i, p in enumerate(prompts)]
        t0 = time.perf_counter()
        comps = batcher.run(reqs)
        wall = time.perf_counter() - t0
        toks = sum(len(c.tokens) for c in comps.values())
        st = batcher.spec_stats
        row = {
            "tokens_per_sec": round(toks / wall, 1),
            "wall_ms": round(wall * 1e3, 1),
            "accepted_tokens_per_step": round(
                st["committed"] / max(st["slot_steps"], 1), 3),
            "draft_hit_rate": round(
                st["accepted"] / max(st["drafted"], 1), 3),
            "verify_steps": st["steps"],
            "draft_wall_frac": round(
                min(st["draft_s"] / max(wall, 1e-9), 1.0), 3),
        }
        if tree is not None:
            row["offramp_commits"] = st["offramp"]
        log(f"spec source={source} adversarial: "
            f"{row['accepted_tokens_per_step']} acc/slot-step, "
            f"hit {row['draft_hit_rate']}")
        return row

    speculative["draft_source"] = {
        src: run_draft_source(src)
        for src in ("ngram", "model", "model_tree")}
    speculative["draft_source"]["note"] = (
        "adversarial prompts, batch 4: the n-gram-vs-model crossover "
        "as a recorded number; the int4 draft model pays a "
        "host-sequential draft loop (draft_wall_frac) to keep "
        "accepting where lookup misses — on TPU the verify stays "
        "weight-bandwidth-bound so the acceptance gain converts to "
        "wall-clock at scale")
    rows["speculative"] = speculative

    # ---- tensor-parallel rows: the SAME decode step sharded over a
    # tp group (head-sharded KV pool + column/row-split projections,
    # logits gathered only at the sampling seam) at tp {1, 2, 4} x
    # weight {bf16, int8, int4}, one decode batch.  tokens/s/chip
    # divides by tp — on CPU the shard_map partitions fight for the
    # same cores so the wall ratio is pessimistic; the number that
    # transfers is per_chip_weight_pool_bytes (each chip streams 1/tp
    # of the pool, ~1/16th of bf16 at tp=4 x int4 — the weight-stream
    # roofline the tentpole moves).  Block 32 so the int4 per-shard
    # packing divides the tp=4 projection slices (qkv 768 -> 192/chip).
    TP_BLOCK, TP_BATCH = 32, 8
    tp_rows = {}
    for tp in (1, 2, 4):
        parallel_state.destroy_model_parallel()
        tmesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size_=tp,
            devices=jax.devices()[:tp])
        per_w = {}
        for weight in ("bf16", "int8", "int4"):
            ms, tps, _, wbytes = run_variant(
                "bfloat16", TP_BATCH, weight=weight, mesh=tmesh,
                wq_block=TP_BLOCK)
            per_w[weight] = {
                "ms_per_step": round(ms, 3),
                "tokens_per_sec_per_chip": round(tps / tp, 1),
                "per_chip_weight_pool_bytes": wbytes,
                "weight_stream_gbs_per_chip": round(
                    wbytes / ms * 1e3 / 1e9, 3),
            }
            log(f"decode tp={tp} w={weight} b{TP_BATCH}: "
                f"{ms:.2f} ms/step, {tps / tp:,.0f} tokens/s/chip, "
                f"{wbytes / 1e6:.2f} MB/chip pool")
        tp_rows[str(tp)] = per_w
    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        devices=jax.devices()[:1])
    tp_rows["note"] = (
        f"b={TP_BATCH}, bf16 KV, weight_block={TP_BLOCK} (int4 "
        "per-shard packing needs the tp=4 projection slice divisible "
        "by 2*block); virtual CPU devices share cores, so ms/step "
        "rises with tp here — read the per-chip pool bytes column; "
        "output is token-identical across tp (pinned in "
        "tests/test_tp_decode.py)")
    rows["tensor_parallel"] = tp_rows

    best = max(v["tokens_per_sec_per_chip"]
               for v in rows["bfloat16"].values())
    print(json.dumps({
        "metric": "decode_tokens_per_sec_per_chip",
        "value": best,
        "unit": "tokens/s/chip (1 virtual CPU device, bf16 KV)",
        # no TPU measurement happened here: null, not a fake ratio
        # (PR 3 convention)
        "vs_baseline": None,
        "platform": "cpu-virtual",
        "note": "relative cost only — TPU decode rates come from the "
                "next capture's validate_fmha_decode sweep; this row "
                "tracks that the serving stack stays runnable and how "
                "fp32/bf16/int8-KV rank across PRs",
        "batches": rows,
        "spec": {"vocab": VOCAB, "layers": LAYERS, "hidden": HIDDEN,
                 "heads": HEADS, "page_size": PAGE, "prompt": PROMPT,
                 "steps": STEPS, "warmup": WARMUP,
                 "mixed_prefix": MIX_PREFIX, "mixed_tail": MIX_TAIL,
                 "prefill_chunk": CHUNK, "speculate_k": SPEC_K,
                 "spec_prompt": SPEC_PROMPT, "spec_new": SPEC_NEW,
                 "weight_block": WQ_BLOCK, "tp_batch": TP_BATCH,
                 "tp_weight_block": TP_BLOCK},
    }))


def child_fleet():
    """Fleet-tier rows: two continuous-batching replicas behind one
    :class:`~apex_tpu.fleet.FleetRouter`, replaying the deterministic
    bursty shared-prefix trace (``tools/load_gen.py``) under
    prefix-affinity + SLO-priority scheduling vs the round-robin
    baseline, plus the replica-kill drill's ledger.  The headline is
    the interactive p99 TTFT speedup (rr / affinity) on pools sized so
    round-robin thrashes the prefix index — same engineered shape as
    the ``_dryrun_fleet`` gate, but the bench row RECORDS rather than
    asserts.  Always a CPU measurement, so per the PR 3 convention
    ``vs_baseline`` is null."""
    _pin_cpu()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.fleet import FleetPolicy, FleetRouter, Replica
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving.kv_cache import (
        KVCacheConfig, PagedKVCache, init_pools,
    )
    from apex_tpu.serving.serve import ContinuousBatcher, Request
    from apex_tpu.transformer import parallel_state
    from tools.load_gen import (
        make_mixed_trace, make_trace, replay, summarize_trace,
    )

    VOCAB, LAYERS, HIDDEN, HEADS = 256, 2, 64, 4
    PAGE, CHUNK, MAXP, PAGES, REPLICAS = 4, 8, 96, 49, 2
    mesh = parallel_state.initialize_model_parallel(
        devices=jax.devices()[:1])
    model = GPTModel(GPTConfig(
        vocab_size=VOCAB, num_layers=LAYERS, hidden_size=HIDDEN,
        num_attention_heads=HEADS, max_position_embeddings=128,
        compute_dtype=jnp.float32, attention_impl="xla", remat=False,
    ))
    params = model.init(jax.random.PRNGKey(0))
    cfg = KVCacheConfig(
        num_layers=LAYERS, num_heads=HEADS, head_dim=HIDDEN // HEADS,
        num_pages=PAGES, page_size=PAGE, max_seqs=2,
        pages_per_seq=-(-MAXP // PAGE), dtype=jnp.float32)
    fns = model.decode_fns(params, mesh, cfg, max_prompt_len=MAXP,
                           prefill_chunk=CHUNK)

    def replicas(n=REPLICAS, offload=None):
        return [
            Replica(f"r{i}", ContinuousBatcher(
                fns.prefill, fns.decode, PagedKVCache(cfg),
                init_pools(cfg), max_prompt_len=MAXP, harvest_every=2,
                chunk_fn=fns.chunk, prefill_chunk=CHUNK,
                prefix_cache=True, offload=offload))
            for i in range(n)
        ]

    # warm every jit outside the measured traces (budget >= 3 covers
    # both decode carry signatures — see _dryrun_fleet)
    rng = np.random.RandomState(15)
    warm = ContinuousBatcher(
        fns.prefill, fns.decode, PagedKVCache(cfg), init_pools(cfg),
        max_prompt_len=MAXP, harvest_every=2, chunk_fn=fns.chunk,
        prefill_chunk=CHUNK, prefix_cache=True)
    warm.run([Request(
        uid="warm", max_new_tokens=4, seed=1,
        prompt=[int(t) for t in rng.randint(1, VOCAB, (88,))])])

    traces = [
        make_trace(n_requests=64, seed=sd, vocab_size=VOCAB,
                   mean_gap=0.5, burstiness=6.0, prompt_len=(68, 88),
                   new_tokens=(4, 8), interactive_frac=0.5, cohorts=4,
                   cohort_frac=0.9, prefix_len=64)
        for sd in (11, 6)
    ]
    rows = {}
    ttfts = {}
    for routing in ("affinity", "least_loaded", "round_robin"):
        pooled = []
        chunks = hits = 0
        t0 = time.perf_counter()
        for trace in traces:
            router = FleetRouter(replicas(),
                                 FleetPolicy(routing=routing))
            recs = replay(router, trace)
            pooled += [r["ttft_s"] for r in recs
                       if r.get("slo") == "interactive"
                       and isinstance(r.get("ttft_s"), (int, float))]
            chunks += sum(r.batcher.prefill_chunks
                          for r in router.replicas)
            hits += sum(r.batcher.prefix_stats["hits"]
                        for r in router.replicas)
        wall = time.perf_counter() - t0
        pooled.sort()
        pct = lambda q: pooled[min(len(pooled) - 1,
                                   int(round(q * (len(pooled) - 1))))]
        ttfts[routing] = pct(0.99)
        rows[routing] = {
            "interactive_ttft_p50_ms": round(pct(0.50) * 1e3, 2),
            "interactive_ttft_p99_ms": round(pct(0.99) * 1e3, 2),
            "prefill_chunks": chunks,
            "prefix_hits": hits,
            "wall_ms": round(wall * 1e3, 1),
        }
        log(f"fleet {routing}: i-p99 "
            f"{rows[routing]['interactive_ttft_p99_ms']} ms, "
            f"{chunks} chunks, {hits} prefix hits")

    # replica-kill drill: r0 dies mid-trace, nothing may be lost
    drill = FleetRouter(replicas(), FleetPolicy())
    drill.replicas[0].fail_after(6)
    dsum = summarize_trace(replay(drill, traces[0]))
    ref = FleetRouter(replicas(), FleetPolicy())
    replay(ref, traces[0])
    identical = all(
        drill.completions[u].tokens == c.tokens
        for u, c in ref.completions.items())
    rows["kill_drill"] = {
        "migrated": drill.stats["migrations"],
        "lost": dsum["lost"],
        "completed": dsum["completed"],
        "token_identical_to_unkilled": identical,
    }
    log(f"fleet drill: {rows['kill_drill']}")

    # disaggregated prefill/decode roles vs unified, same fleet size.
    # The regime where disagg wins BOTH interactive p99 TTFT and ITL:
    # bursty long-prompt arrivals with a real decode budget.  Unified
    # replicas interleave chunked prefills with co-resident decode
    # (stalling ITL) and spread decode across the fleet at batch 1-2;
    # the disagg decode replica gets a role-shaped pool (more slots,
    # same page geometry — compat_key ignores slot counts) so decode
    # consolidates into fewer, larger dispatches, and harvests less
    # often.  Prefill replicas keep harvest_every=2 so finished
    # prefills export promptly.
    from apex_tpu.serving.kv_cache import HostOffloadPool

    pps = -(-MAXP // PAGE)

    def mkcfg(seqs):
        return KVCacheConfig(
            num_layers=LAYERS, num_heads=HEADS,
            head_dim=HIDDEN // HEADS, num_pages=1 + seqs * pps,
            page_size=PAGE, max_seqs=seqs, pages_per_seq=pps,
            dtype=jnp.float32)

    dec_fns = {2: fns}
    for s in (4, 8):
        dec_fns[s] = model.decode_fns(
            params, mesh, mkcfg(s), max_prompt_len=MAXP,
            prefill_chunk=CHUNK)

    def shaped(rid, seqs=2, he=2):
        f, c = dec_fns[seqs], mkcfg(seqs)
        return Replica(rid, ContinuousBatcher(
            f.prefill, f.decode, PagedKVCache(c), init_pools(c),
            max_prompt_len=MAXP, harvest_every=he, chunk_fn=f.chunk,
            prefill_chunk=CHUNK, prefix_cache=True))

    for s in (4, 8):
        shaped("w", seqs=s).batcher.run([Request(
            uid="warm", max_new_tokens=4, seed=1,
            prompt=[int(t) for t in rng.randint(1, VOCAB, (88,))])])

    DEC_HE = 8
    topos = {
        "unified_2r": (lambda: [shaped(f"r{i}") for i in range(2)],
                       None),
        "disagg_2r": (lambda: [shaped("r0"),
                               shaped("r1", seqs=4, he=DEC_HE)],
                      ("prefill", "decode")),
        "unified_4r": (lambda: [shaped(f"r{i}") for i in range(4)],
                       None),
        "disagg_4r": (lambda: [shaped(f"r{i}") for i in range(3)]
                      + [shaped("r3", seqs=8, he=DEC_HE)],
                      ("prefill", "prefill", "prefill", "decode")),
    }
    mixed = make_mixed_trace(
        n_requests=48, seed=21, vocab_size=VOCAB, mean_gap=2.0,
        burstiness=6.0, long_frac=0.6, short_prompt=(8, 16),
        long_prompt=(40, 64), new_tokens=(16, 28), session_frac=0.25,
        idle_gap=16.0)
    pc = lambda xs, q: xs[min(len(xs) - 1,
                              int(round(q * (len(xs) - 1))))]
    med = lambda xs: sorted(xs)[len(xs) // 2]
    # one unmeasured replay per topology warms its handoff/import
    # jits, then 3 INTERLEAVED measured rounds over all topologies —
    # a load spike on the shared CPU then hits every topology in the
    # round, not just whichever happened to be running; the rows are
    # the per-topology medians (token streams are deterministic, only
    # timing varies)
    for build, roles in topos.values():
        replay(FleetRouter(build(), FleetPolicy(roles=roles)), mixed)
    samples = {name: [] for name in topos}
    stats = {}
    for _ in range(3):
        for name, (build, roles) in topos.items():
            t0 = time.perf_counter()
            router = FleetRouter(build(), FleetPolicy(roles=roles))
            recs = replay(router, mixed)
            wall = time.perf_counter() - t0
            stats[name] = router.stats
            inter = [r for r in recs if r.get("slo") == "interactive"
                     and "reason" in r]
            tt = sorted(r["ttft_s"] for r in inter
                        if isinstance(r.get("ttft_s"), (int, float)))
            il = sorted(r["itl_ms"] for r in inter
                        if isinstance(r.get("itl_ms"), (int, float)))
            samples[name].append(
                (pc(tt, .5) * 1e3, pc(tt, .99) * 1e3,
                 pc(il, .5), pc(il, .99), wall * 1e3))
    for name in topos:
        topo, nr = name.split("_")
        reps = samples[name]
        rows[name] = {
            "interactive_ttft_p50_ms": round(med([r[0] for r in reps]), 2),
            "interactive_ttft_p99_ms": round(med([r[1] for r in reps]), 2),
            "interactive_itl_p50_ms": round(med([r[2] for r in reps]), 3),
            "interactive_itl_p99_ms": round(med([r[3] for r in reps]), 3),
            "handoffs": stats[name]["handoffs"],
            "handoff_pages": stats[name]["handoff_pages"],
            "handoff_wire_bytes": stats[name]["handoff_bytes"],
            "wall_ms": round(med([r[4] for r in reps]), 1),
        }
        if topo == "disagg":
            rows[name]["decode_max_seqs"] = 4 if nr == "2r" else 8
            rows[name]["decode_harvest_every"] = DEC_HE
        log(f"fleet {name}: ttft p99 "
            f"{rows[name]['interactive_ttft_p99_ms']} ms, itl p99 "
            f"{rows[name]['interactive_itl_p99_ms']} ms, "
            f"{stats[name]['handoffs']} handoffs")

    # host-RAM offload tier: a prefix working set sized 2x ONE
    # replica's pool, revisited after churn evicted it — fault-in
    # (offload) vs full prefill recompute (none)
    rng_ws = np.random.RandomState(23)
    ws = [[int(t) for t in rng_ws.randint(1, VOCAB, (32,))]
          for _ in range(2 * (PAGES - 1) // (32 // PAGE))]
    for mode in ("offload", "recompute"):
        off = (HostOffloadPool(max_pages=4 * (PAGES - 1))
               if mode == "offload" else None)
        b = replicas(n=1, offload=off)[0].batcher

        def wave(tag):
            c0 = b.prefill_chunks
            t0 = time.perf_counter()
            for i, p in enumerate(ws):
                b.run([Request(uid=f"{tag}{i}", prompt=p,
                               max_new_tokens=4, seed=31 + i)])
            return (round((time.perf_counter() - t0) * 1e3, 1),
                    b.prefill_chunks - c0)
        w1_ms, w1_chunks = wave("w1_")
        w2_ms, w2_chunks = wave("w2_")
        rows[f"offload_{mode}"] = {
            "working_set_pages": len(ws) * (32 // PAGE),
            "replica_pool_pages": PAGES - 1,
            "wave1_ms": w1_ms, "wave1_prefill_chunks": w1_chunks,
            "wave2_ms": w2_ms, "wave2_prefill_chunks": w2_chunks,
        }
        if off is not None:
            rows["offload_offload"].update({
                "pages_offloaded": off.stats["offloaded"],
                "pages_faulted": off.stats["faulted"],
                "host_bytes_peak": off.stats["bytes_in"],
            })
        log(f"offload {mode}: wave2 {w2_ms} ms, "
            f"{w2_chunks} prefill chunks")

    speedup = ttfts["round_robin"] / ttfts["affinity"]
    print(json.dumps({
        "metric": "fleet_interactive_p99_ttft_speedup",
        "value": round(speedup, 2),
        "unit": "x (round_robin / affinity+SLO, 2 replicas, "
                "2 pooled 64-request traces)",
        # no TPU measurement happened here: null, not a fake ratio
        # (PR 3 convention)
        "vs_baseline": None,
        "platform": "cpu-virtual",
        "note": "scheduling-quality row — pools sized so round-robin "
                "thrashes the prefix index (4 cohorts, ~2 fit); "
                "records the routing win and the zero-loss drill, "
                "asserted by the _dryrun_fleet gate",
        "rows": rows,
        "spec": {"vocab": VOCAB, "layers": LAYERS, "hidden": HIDDEN,
                 "heads": HEADS, "page_size": PAGE,
                 "prefill_chunk": CHUNK, "num_pages": PAGES,
                 "replicas": REPLICAS, "max_prompt_len": MAXP,
                 "trace_seeds": [11, 6], "requests_per_trace": 64,
                 "mixed_trace_seed": 21, "mixed_requests": 48,
                 "disagg_decode_harvest_every": 8},
    }))


def child_telemetry():
    """Telemetry-overhead row: ms/step of the flagship CPU-dryrun-shape
    GPT step (the same reduced config child_gpt runs under
    --platform cpu) with runtime metrics ON (MetricsLogger at the default
    flush cadence, JSONL sink) vs OFF, plus the logger's self-measured
    overhead split into bookkeeping tax vs amortized resolve wait.
    Always a CPU measurement, so per the PR 3 convention
    ``vs_baseline`` is null — the row tracks that async harvesting
    stays effectively free across PRs, not a TPU win."""
    import tempfile

    _pin_cpu()
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.telemetry.metrics import MetricsLogger, StepStats
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.tensor_parallel.layers import state_specs_like

    # the flagship CPU-dryrun shape (child_gpt's fallback config)
    VOCAB, LAYERS, HIDDEN, HEADS, SEQ, BATCH = 4096, 2, 256, 4, 256, 2
    WARMUP, STEPS, REPEATS = 2, 10, 3
    mesh = parallel_state.initialize_model_parallel()
    cfg = GPTConfig(
        vocab_size=VOCAB, num_layers=LAYERS, hidden_size=HIDDEN,
        num_attention_heads=HEADS, max_position_embeddings=SEQ,
        compute_dtype=jnp.bfloat16, attention_impl="xla", remat=True,
    )
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    specs = model.param_specs()
    opt = FusedAdam(lr=1e-4, master_weights=True)
    opt_state = opt.init(params)
    opt_specs = state_specs_like(specs, opt_state)

    def train_step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(model.loss)(
            params, tokens, targets)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
        new_params, new_opt = opt.step(opt_state, grads, params)
        return new_params, new_opt, loss

    step = jax.jit(jax.shard_map(
        train_step, mesh=mesh,
        in_specs=(specs, opt_specs, P("dp"), P("dp")),
        out_specs=(specs, opt_specs, P()),
    ))
    place = lambda tree, sp: jax.device_put(
        tree, jax.tree.map(lambda s: NamedSharding(mesh, s), sp,
                           is_leaf=lambda x: isinstance(x, P)))
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ),
                                0, VOCAB)
    targets = jnp.roll(tokens, -1, axis=1)

    def run_once(with_metrics):
        p = place(params, specs)
        s = place(opt_state, opt_specs)
        tlm = None
        if with_metrics:
            tlm = MetricsLogger(
                jsonl_path=os.path.join(tempfile.mkdtemp(), "m.jsonl"),
                console=False, flush_every=10,
                stats=StepStats(tokens_per_step=BATCH * SEQ,
                                peak_flops=None),
            )
        for _ in range(WARMUP):
            p, s, loss = step(p, s, tokens, targets)
        float(loss)
        t0 = time.perf_counter()
        for i in range(STEPS):
            p, s, loss = step(p, s, tokens, targets)
            if tlm is not None:
                if i == 0:
                    tlm.stats.begin(loss)
                else:
                    tlm.stats.tick()
                tlm.log_scalars(i, loss=loss)
        if tlm is not None:
            tlm.close()
        else:
            float(loss)
        dt = time.perf_counter() - t0
        return dt / STEPS * 1e3, tlm

    off_ms = min(run_once(False)[0] for _ in range(REPEATS))
    on_runs = [run_once(True) for _ in range(REPEATS)]
    on_ms = min(ms for ms, _ in on_runs)
    tlm = min(on_runs, key=lambda r: r[0])[1]
    overhead_pct = round(
        tlm.overhead_s / STEPS / (on_ms / 1e3) * 100, 4)
    resolve_pct = round(
        tlm.resolve_wait_s / STEPS / (on_ms / 1e3) * 100, 4)
    log(f"telemetry: off {off_ms:.2f} ms/step, on {on_ms:.2f} ms/step, "
        f"self-measured tax {overhead_pct}% (+{resolve_pct}% resolve "
        "wait)")
    print(json.dumps({
        "metric": "telemetry_overhead_pct",
        # headline = the logger's self-measured bookkeeping tax as a
        # fraction of step time (stable); the on-vs-off A/B rides along
        # but min-of-3 wall clocks on a shared CPU host carry ±% noise
        "value": overhead_pct,
        "unit": "% of step time",
        "vs_baseline": None,
        "platform": "cpu",
        "note": "flagship CPU-dryrun shape; vs_baseline null per the "
                "PR 3 CPU convention — the <1% gate runs in the "
                "multichip dryrun's telemetry config",
        "ms_per_step_metrics_off": round(off_ms, 3),
        "ms_per_step_metrics_on": round(on_ms, 3),
        "resolve_wait_pct": resolve_pct,
        "flush_every": 10,
        "spec": {"vocab": VOCAB, "layers": LAYERS, "hidden": HIDDEN,
                 "heads": HEADS, "seq": SEQ, "batch": BATCH,
                 "steps": STEPS, "warmup": WARMUP,
                 "repeats": REPEATS},
    }))


def child_opttail():
    """Optimizer-tail A/B row: ms/step of the fused multi-tensor tail
    (``FusedAdam(fused_tail=True).step_scaled`` — unscale + finiteness
    + Adam + master→bf16 cast in ONE pass over packed buffers) vs the
    seed per-leaf chain (``scaler.unscale`` pass + per-leaf ``upd``),
    on a flagship-layout GPT param tree scaled to the CPU dryrun
    budget.  Always a CPU measurement, so per the PR 3 convention
    ``vs_baseline`` is null — the real bandwidth gate is
    ``tools/kernel_validation.py validate_opt_tail`` on the next TPU
    capture (PROFILE_r05's 11.85 ms / 440 GB/s tail baseline); this
    row tracks that both paths stay runnable, their relative cost, and
    that fused-vs-per-leaf outputs stay BIT-identical."""
    _pin_cpu()
    import numpy as np
    import jax
    import jax.numpy as jnp

    from apex_tpu.amp.scaler import all_finite, scale_gradients
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.optimizers.fused_tail import (
        tail_traffic_bytes,
        time_opt_tail,
    )

    LAYERS, HIDDEN, VOCAB = 2, 256, 4096  # child_gpt's CPU shape
    ks = jax.random.split(jax.random.PRNGKey(0), LAYERS + 2)
    params = {"emb": 0.02 * jax.random.normal(
        ks[0], (VOCAB, HIDDEN), jnp.bfloat16)}
    for l in range(LAYERS):
        params[f"l{l}"] = {
            "qkv": 0.02 * jax.random.normal(
                ks[l + 1], (HIDDEN, 3 * HIDDEN), jnp.bfloat16),
            "mlp": 0.02 * jax.random.normal(
                ks[l + 1], (HIDDEN, 4 * HIDDEN), jnp.bfloat16),
            "ln": jnp.ones((HIDDEN,), jnp.bfloat16),
        }
    grads = jax.tree.map(
        lambda p: 0.01 * jax.random.normal(
            jax.random.PRNGKey(9), jnp.shape(p),
            jnp.float32).astype(p.dtype),
        params)
    inv = 1.0 / 1024.0

    fused = FusedAdam(lr=1e-3, master_weights=True, fused_tail=True)
    perleaf = FusedAdam(lr=1e-3, master_weights=True)
    f_state, p_state = fused.init(params), perleaf.init(params)

    # parity before timing: the fused tail's contract is bit-identity
    fp, fs, _ = jax.jit(
        lambda s, g, p: fused.step_scaled(s, g, p, jnp.float32(inv))
    )(f_state, grads, params)
    rg = scale_gradients(grads, inv)
    rp, rs = jax.jit(
        lambda s, g, p, f: perleaf.step(s, g, p, grads_finite=f)
    )(p_state, rg, params, all_finite(grads))
    for a, b in zip(jax.tree.leaves(fp), jax.tree.leaves(rp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    f = time_opt_tail(fused, f_state, grads, params, inv_scale=inv,
                      iters=10)

    def seed_chain(s, g, p):
        g2 = scale_gradients(g, inv)
        finite = all_finite(g)
        return perleaf.step(s, g2, p, grads_finite=finite)

    jseed = jax.jit(seed_chain)
    out = jseed(p_state, grads, params)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(10):
        out = jseed(p_state, grads, params)
    jax.block_until_ready(out)
    seed_ms = (time.perf_counter() - t0) / 10 * 1e3
    n_elems = sum(int(np.prod(jnp.shape(l)))
                  for l in jax.tree.leaves(params))
    log(f"opt tail: fused {f['ms']:.2f} ms vs per-leaf "
        f"{seed_ms:.2f} ms ({n_elems / 1e6:.1f}M params)")
    print(json.dumps({
        "metric": "opt_tail_ms_per_step",
        "value": round(f["ms"], 3),
        "unit": "ms (fused tail, CPU)",
        "vs_baseline": None,
        "platform": "cpu",
        "note": "CPU-dryrun-scale tail; vs_baseline null per the PR 3 "
                "convention — the bandwidth gate is kernel_validation "
                "validate_opt_tail on TPU (11.85 ms r05 baseline). "
                "fused_vs_per_leaf < 1 HERE is the CPU backend's "
                "unfused concatenate (the bucket pack is a real copy "
                "on CPU; TPU fuses concats into the consumer loop)",
        "fused_ms": round(f["ms"], 3),
        "per_leaf_ms": round(seed_ms, 3),
        "fused_vs_per_leaf": round(seed_ms / max(f["ms"], 1e-9), 2),
        "traffic_bytes": tail_traffic_bytes(params, fused),
        "cpu_gbs": round(f["gbs"], 2),
        "bit_identical": True,
        "spec": {"layers": LAYERS, "hidden": HIDDEN, "vocab": VOCAB,
                 "elements": n_elems, "steps": 10, "warmup": 2,
                 "unscale_folded": True},
    }))


def _flash_long_seq(out, on_tpu, timeit):
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.attention import flash_attention

    S_long = 8192 if on_tpu else 512
    bq, hq, dq = (2, 8, 128) if on_tpu else (1, 2, 32)
    qkv_keys = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(qkv_keys[0], (bq, hq, S_long, dq), jnp.bfloat16)
    k = jax.random.normal(qkv_keys[1], (bq, hq, S_long, dq), jnp.bfloat16)
    v = jax.random.normal(qkv_keys[2], (bq, hq, S_long, dq), jnp.bfloat16)
    fa_grad = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True
        ).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    ))
    out["flash_long_seq"] = {
        "seq": S_long, "shape": [bq, hq, S_long, dq], "dtype": "bfloat16",
        "causal": True,
        "fwd_bwd_ms": round(timeit(fa_grad, q, k, v, n=10), 2),
    }
    log(f"flash s={S_long}: {out['flash_long_seq']['fwd_bwd_ms']:.2f} ms fwd+bwd")


def _t5_extra(out, on_tpu):
    # T5 encoder-decoder train step (enc-dec model family on the record;
    # sequential tp=1 path on the single chip)
    import time

    import jax
    import jax.numpy as jnp

    from apex_tpu.models import T5Config, T5Model
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state
    from apex_tpu.transformer.tensor_parallel.layers import state_specs_like
    from jax.sharding import NamedSharding, PartitionSpec as P

    t5_cfg = T5Config(
        vocab_size=32768 if on_tpu else 256,
        num_encoder_layers=6 if on_tpu else 1,
        num_decoder_layers=6 if on_tpu else 1,
        hidden_size=512 if on_tpu else 64,
        num_attention_heads=8 if on_tpu else 2,
        max_position_embeddings=512,
        compute_dtype=jnp.bfloat16,
    )
    t5_s = 512 if on_tpu else 32
    t5_b = 16 if on_tpu else 2
    t5 = T5Model(t5_cfg)
    t5_params = t5.init(jax.random.PRNGKey(7))
    t5_specs = t5.param_specs()
    t5_opt = FusedAdam(lr=1e-4, master_weights=True)
    t5_opt_state = t5_opt.init(t5_params)
    t5_opt_specs = state_specs_like(t5_specs, t5_opt_state)
    t5_mesh = parallel_state.initialize_model_parallel() \
        if not parallel_state.model_parallel_is_initialized() \
        else parallel_state.get_mesh()

    def t5_step(params, opt_state, enc, dec, tgt):
        loss, grads = jax.value_and_grad(t5.loss)(params, enc, dec, tgt)
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
        new_params, new_opt = t5_opt.step(opt_state, grads, params)
        return new_params, new_opt, loss

    t5_fn = jax.jit(
        jax.shard_map(
            t5_step, mesh=t5_mesh,
            in_specs=(t5_specs, t5_opt_specs, P("dp"), P("dp"), P("dp")),
            out_specs=(t5_specs, t5_opt_specs, P()),
        ),
        donate_argnums=(0, 1),
    )
    t5_place = lambda tree, sp: jax.device_put(
        tree, jax.tree.map(lambda s_: NamedSharding(t5_mesh, s_), sp,
                           is_leaf=lambda x_: isinstance(x_, P)))
    t5_params = jax.tree.map(lambda p_: p_.astype(jnp.bfloat16), t5_params)
    tp_, ts_ = t5_place(t5_params, t5_specs), t5_place(t5_opt_state, t5_opt_specs)
    t5_enc = jax.random.randint(
        jax.random.PRNGKey(8), (t5_b, t5_s), 0, t5_cfg.vocab_size)
    t5_dec = jax.random.randint(
        jax.random.PRNGKey(9), (t5_b, t5_s), 0, t5_cfg.vocab_size)
    t5_tgt = jax.random.randint(
        jax.random.PRNGKey(10), (t5_b, t5_s), 0, t5_cfg.vocab_size)
    for _ in range(2):
        tp_, ts_, t5_loss = t5_fn(tp_, ts_, t5_enc, t5_dec, t5_tgt)
    float(t5_loss)
    t5_steps = 10 if on_tpu else 3
    t0 = time.perf_counter()
    for _ in range(t5_steps):
        tp_, ts_, t5_loss = t5_fn(tp_, ts_, t5_enc, t5_dec, t5_tgt)
    t5_final = float(t5_loss)
    dt = time.perf_counter() - t0
    out["t5_encdec"] = {
        # decoder tokens/s (the enc side adds 6 more bidirectional layers
        # of work per step on the same count)
        "tokens_per_sec": round(t5_b * t5_s * t5_steps / dt, 1),
        "ms_per_step": round(dt / t5_steps * 1e3, 2),
        "loss": round(t5_final, 4),
        "spec": {"enc_layers": t5_cfg.num_encoder_layers,
                 "dec_layers": t5_cfg.num_decoder_layers,
                 "hidden": t5_cfg.hidden_size, "seq": t5_s,
                 "batch": t5_b, "steps": t5_steps, "warmup": 2,
                 "compute_dtype": "bfloat16",
                 "optimizer": "FusedAdam(master_weights=True)"},
    }
    log(f"t5: {out['t5_encdec']['tokens_per_sec']} dec tokens/s "
        f"({out['t5_encdec']['ms_per_step']} ms/step)")


# ---------------------------------------------------------------- orchestrator
def _merge_bench_extra(path, extras):
    """Merge this run's extras into BENCH_EXTRA.json instead of
    clobbering it: a budget-starved run that only produced (say) the
    fleet row must not erase the grad-sync/zero3/decode rows a fuller
    earlier capture wrote.  This run's keys win on collision (they are
    fresher measurements of the same thing); unknown or unreadable
    existing content is replaced, not merged."""
    merged = dict(extras)
    try:
        with open(path) as f:
            prior = json.load(f)
        if isinstance(prior, dict):
            merged = {**prior, **extras}
    except (OSError, ValueError):
        pass
    try:
        with open(path, "w") as f:
            json.dump(merged, f, indent=1)
    except OSError as e:
        log(f"extras write failed: {e}")


def _run_child(args, timeout):
    """Run `python bench.py <args>` bounded; return (ok, last_json, tail).

    Timeout handling is SIGTERM-first with a long grace period, never an
    immediate SIGKILL: a child that dies without tearing its JAX client
    down can leave the chip held.  SIGTERM hits the child's clean-exit
    handler (`_install_sigterm_exit`); SIGKILL only after the grace
    expires.
    """
    env = dict(os.environ)
    # persistent XLA-executable cache: a bench re-running the same
    # flagship program should pay tracing, not compilation.  Where the
    # environment already names a cache directory, that one is used.
    # TPU children only: cached CPU AOT executables warn about host
    # machine-feature mismatches ("could lead to SIGILL"), and CPU
    # compiles are cheap anyway.
    if "cpu" not in args:
        env.setdefault(
            "JAX_COMPILATION_CACHE_DIR",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".jax_cache"),
        )
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__)] + args,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env,
    )
    timed_out = False
    try:
        out, errtxt = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        timed_out = True
        proc.terminate()  # SIGTERM -> child's clean-exit handler
        try:
            out, errtxt = proc.communicate(timeout=45)
        except subprocess.TimeoutExpired:
            log("child ignored SIGTERM for 45s; escalating to SIGKILL "
                "(the chip may stay held)")
            proc.kill()
            out, errtxt = proc.communicate()
    sys.stderr.write((errtxt or "")[-4000:])
    if timed_out or proc.returncode != 0:
        # salvage: children emit cumulative partial JSON at section
        # boundaries, so a timeout mid-compile keeps completed sections
        for line in reversed((out or "").strip().splitlines()):
            try:
                partial = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(partial, dict):
                reason = (f"timeout after {timeout}s" if timed_out
                          else f"rc={proc.returncode}")
                if partial.get("partial"):
                    partial["truncated_by"] = reason
                    log(f"child died ({reason}) but left a partial "
                        "result; keeping it")
                else:
                    # complete result printed, then a messy teardown
                    log(f"child died in teardown ({reason}) after a "
                        "complete result; keeping it")
                return True, partial, ""
            break
        if timed_out:
            return False, None, f"timeout after {timeout}s"
        return False, None, (errtxt or "")[-1500:]
    for line in reversed((out or "").strip().splitlines()):
        try:
            return True, json.loads(line), ""
        except json.JSONDecodeError:
            continue
    return False, None, "no JSON in child output"


#: the BENCH_EXTRA.json rows measured on the 8-virtual-device CPU mesh:
#: (child name, BENCH_EXTRA key)
CPU_VIRTUAL_ROWS = (
    ("gradsync", "grad_sync"),      # overlap x compression A/B
    ("opttail", "opt_tail"),        # fused multi-tensor tail vs per-leaf
    ("zero3", "zero3"),             # gather-on-use step vs replicated
    ("telemetry", "telemetry_overhead"),  # metrics on vs off
    ("decode", "decode"),           # serving rows at batch {1,8,64,256}
    ("fleet", "fleet"),             # multi-replica routing + failover
)


def main() -> int:
    """Run the gpt child on the chip, then the extras rows; print the
    gpt result as the one stdout line.  Returns the exit code: non-zero
    when there is no chip or the gpt child fails (nothing is printed
    then), and non-zero — after every other child has run — when any
    child failed."""
    failed = []
    ok, result, err = _run_child(
        ["--child", "gpt", "--platform", "tpu"], CHILD_TIMEOUT)
    if not ok:
        log(f"gpt child failed: {err[-600:]}")
        return 1

    ok, extras, err = _run_child(
        ["--child", "extras", "--platform", "tpu"], CHILD_TIMEOUT)
    if ok:
        log(f"extras: {extras}")
    else:
        extras = None
        failed.append("extras")
        log(f"extras child failed: {err[-300:]}")

    # rows that ride BENCH_EXTRA.json, never the headline
    for child, key in CPU_VIRTUAL_ROWS:
        ok, row, err = _run_child(
            ["--child", child, "--platform", "cpu"], 600)
        if ok:
            extras = extras if extras is not None else {
                "platform": "cpu-virtual"}
            extras[key] = row
            log(f"{key}: {row}")
        else:
            failed.append(child)
            log(f"{child} child failed: {err[-300:]}")

    if extras is not None:
        _merge_bench_extra(
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "BENCH_EXTRA.json"),
            extras)
    print(json.dumps(result))
    if failed:
        log(f"children failed: {', '.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    if "--child" in sys.argv:
        _install_sigterm_exit()
        kind = sys.argv[sys.argv.index("--child") + 1]
        plat = (
            sys.argv[sys.argv.index("--platform") + 1]
            if "--platform" in sys.argv else "cpu"
        )
        if kind == "gpt":
            child_gpt(plat)
        elif kind == "extras":
            child_extras(plat)
        elif kind == "gradsync":
            child_gradsync()
        elif kind == "zero3":
            child_zero3()
        elif kind == "opttail":
            child_opttail()
        elif kind == "telemetry":
            child_telemetry()
        elif kind == "decode":
            child_decode()
        elif kind == "fleet":
            child_fleet()
        else:
            raise SystemExit(f"unknown child {kind}")
    else:
        sys.exit(main())
