"""Runner for traffic of kind ``train``: the trainer's own step, fed.

``examples/gpt_pretrain.py:main`` builds the mesh, the model, the
optimizer and the jitted step at the configuration's sizes, runs one
step (which compiles) and returns the step with its live state; the
harness then feeds that step seeded batches.  It dispatches step ``i``
and only then blocks on step ``i - 1``'s loss, so the device always has
the next step queued and every completion gets a timestamp.  The rate is

    tokens per step x (completions - 1) / (last completion - first)

over the completions from the window's opening until ``--seconds`` have
passed AND the step then in flight has finished: whole steps over the
time those same steps took, whatever the window's edges fall on.
"""

from __future__ import annotations

import contextlib
import importlib.util
import math
import os
import time

import numpy as np

import rooflines
import timing
import traffic as traffic_gen
from reference import gpt as reference


def padded_vocab(vocab: int, tp: int) -> int:
    """The program's Megatron rule: the next multiple of 128 x tp."""
    unit = 128 * tp
    return -(-vocab // unit) * unit


def _load_trainer(root: str):
    spec = importlib.util.spec_from_file_location(
        "gpt_pretrain", os.path.join(root, "examples", "gpt_pretrain.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _loss_check(run, model, params, tokens, targets) -> dict:
    """The program's loss on one seeded micro-batch of two sequences
    against the plain reference's on the same weights."""
    import jax
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer import parallel_state

    cfg = run.config
    served = jax.jit(jax.shard_map(
        lambda p, t, y: model.loss(p, t, y),
        mesh=parallel_state.get_mesh(),
        in_specs=(model.param_specs(), P("dp"), P("dp")), out_specs=P()))(
        params, tokens, targets)
    ref = reference.loss(reference.from_stacked(params), tokens, targets,
                         heads=cfg["n_head"], layers=cfg["n_layer"],
                         eps=cfg["layer_norm_epsilon"])
    served, ref = float(served), float(ref)
    return {"program": served, "reference": ref,
            "relative": abs(served - ref) / abs(ref)}


def run(run) -> dict:
    import jax
    import jax.numpy as jnp

    cfg, tr = run.config, run.traffic
    tp, dp = int(tr["tp"]), int(tr["dp"])
    if tp * dp != len(run.devices):
        raise SystemExit(f"traffic lays out tp {tp} x dp {dp} but there are "
                         f"{len(run.devices)} devices")
    vocab_rows = padded_vocab(cfg["vocab_size"], tp)
    global_batch = int(tr["micro_batch"]) * int(tr["num_micro"]) * dp
    tokens_per_step = global_batch * int(tr["seq"])

    with run.phase("batches_from_seed"):
        pool = [(jnp.asarray(t), jnp.asarray(y)) for t, y in
                traffic_gen.train_batches(tr, cfg["vocab_size"], run.seed,
                                          global_batch)]
        check_rng = traffic_gen.rng_for(run.seed, 4)
        check_tokens = traffic_gen.zipf_tokens(
            check_rng, cfg["vocab_size"], (2, int(tr["seq"])))
    # ``main`` builds the UNSHARDED model, masters and Adam moments on the
    # default device before it places them on the mesh (14 B a parameter).
    # Where that is more than one chip holds, it is built on the host.
    state_bytes = 14 * rooflines.parameter_count(cfg, vocab_rows)
    on_host = (run.devices[0].platform == "tpu" and state_bytes > 0.8
               * rooflines.peaks(run.devices[0].device_kind)["hbm_bytes"])
    build_on = jax.default_device(jax.devices("cpu")[0]) if on_host \
        else contextlib.nullcontext()
    run.note(f"trainer state {state_bytes / 1e9:.1f} GB unsharded: built on "
             f"the {'host' if on_host else 'default device'}")
    with run.phase("trainer_build_and_first_step"), build_on:
        trainer = _load_trainer(run.root)
        out = trainer.main([
            "--tp", str(tp), "--vocab", str(vocab_rows),
            "--layers", str(cfg["n_layer"]), "--hidden", str(cfg["n_embd"]),
            "--heads", str(cfg["n_head"]), "--seq", str(tr["seq"]),
            "--opt-level", tr["opt_level"],
            "--micro-batch", str(tr["micro_batch"]),
            "--num-micro", str(tr["num_micro"]),
            "--steps", "1", "--log-every", "1000000"])
    step, model = out["step"], out["model"]
    state = list(out["step_args"][:4])
    why = []
    with run.phase("reference_check"):
        chk = _loss_check(run, model, state[0], jnp.asarray(check_tokens),
                          jnp.asarray(np.roll(check_tokens, -1, axis=1)))
    # bf16 compute against float32: the mean over 2 x seq tokens of a
    # loss near ln(vocabulary) moves by parts in 1e5 (measured, PERF.md);
    # an 8-bit path moves it by parts in 1e3
    tolerance = float(tr["loss_tolerance"])
    run.note(f"reference: program loss {chk['program']:.6f}, float32 "
             f"reference {chk['reference']:.6f}, relative difference "
             f"{chk['relative']:.2e} (tolerance {tolerance})")
    if not (math.isfinite(chk["relative"]) and chk["relative"] <= tolerance):
        why.append(f"loss differs from the reference by {chk['relative']}")

    def advance(batch):
        state[0], state[1], state[2], state[3], loss = step(*state, *batch)
        return loss

    with run.phase("warm_up_steps"):
        for i in range(2):       # the harness's own batches, same program
            jax.block_until_ready(advance(pool[i % len(pool)]))

    # ------------------------------------------------------- the window
    before = run.clock.snapshot()
    t_open = time.perf_counter()
    t_close = t_open + run.seconds
    run.tracer.arm(t_close - float(tr.get("trace_seconds", 3.0)), t_close)
    completions, losses = [], []
    pending, i = None, 0
    while True:
        with run.span("bench.batch_fetch"):
            batch = pool[i % len(pool)]
        with run.span("bench.dispatch"):
            loss = advance(batch)
        i += 1
        if pending is not None:
            with run.span("bench.block"):
                jax.block_until_ready(pending)
            now = time.perf_counter()
            completions.append(now)
            losses.append(pending)
            if now >= t_close:
                break
            run.tracer.poll(now)
        pending = loss
    with run.span("bench.block"):       # the step in flight finishes too
        jax.block_until_ready(loss)
    completions.append(time.perf_counter())
    losses.append(loss)
    run.tracer.stop()
    compiled = run.clock.snapshot() - before

    boundaries = [(t, k * tokens_per_step)
                  for k, t in enumerate(completions)]
    rate = timing.rate_between(boundaries)
    losses = [float(x) for x in jax.device_get(losses)]
    bad = [x for x in losses if not math.isfinite(x)]
    if bad:
        why.append(f"{len(bad)} of {len(losses)} losses are not finite")
    steps = len(completions) - 1
    step_s = (completions[-1] - completions[0]) / steps
    flops = rooflines.train_flops_per_token(cfg, vocab_rows, int(tr["seq"]))
    run.note(f"window: {steps} steps between the first and the last "
             f"completion, {step_s * 1e3:.3f} ms a step, first loss "
             f"{losses[0]:.4f}, last {losses[-1]:.4f}; required FLOPs a "
             f"token {flops:.4g}")
    if run.trace:
        with run.span("bench.compiled_text"):
            run.hlo_texts["jit_train_step"] = step.lower(
                *state, *pool[0]).compile().as_text()
    per_chip = rate / len(run.devices)
    return {
        "t_open": t_open, "correct": not why, "why_incorrect": why,
        "attempted": len(losses), "failed": len(bad),
        "compiled_in_window": dict(compiled),
        "end_to_end": {"train_tokens_per_s_per_chip": per_chip},
        "counters": {
            "steps": steps, "step_ms": step_s * 1e3,
            "tokens_per_step": tokens_per_step,
            "loss_check_relative": chk["relative"],
            "batch_per_chip": global_batch // dp,
            "heads_per_chip": cfg["n_head"] // tp,
            "seq": int(tr["seq"]),
            "head_dim": cfg["n_embd"] // cfg["n_head"],
            "required_flops_per_token": flops,
        },
    }
