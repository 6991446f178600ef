"""Pipeline activation-memory profile: compiled temp memory vs microbatch
count (writes PIPELINE_MEMORY.json).

The compiled GPipe-with-remat schedule keeps per-tick stage inputs for the
backward; the table below measures how compiled temp memory actually
scales with ``num_micro`` at pp=4 (virtual CPU mesh, XLA memory analysis)
for remat on/off, next to the analytic expectation: with remat, the
backward stash is one activation per tick (num_micro + pp - 1 ticks);
without, every stage's full activation set lives until backward.

Writes PIPELINE_MEMORY.json.  Run: python tools/pipeline_memory.py

Reading the numbers (r4 A/B notes):

- the 1f1b absolute temp level moved 1.77 → 3.9 MB between rounds from
  the measurement environment, not the schedule: the round-3
  schedules.py re-measured in the round-4 environment gives 3.874 MB at
  micro=32 vs 3.899 for round-4 code (+0.6%).  The property that
  matters — temp FLAT in num_micro while GPipe grows — holds in both.
- interleaved 1f1b measuring slightly BELOW plain 1f1b (3.66 vs 3.9 MB)
  despite a V×-larger input buffer: each interleaved tick
  rematerializes one chunk (layers/V of a stage), so its per-tick vjp
  workspace is V× smaller — at this config the workspace term
  dominates the buffer term.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.pipeline_parallel import (
    pipeline,
    pipeline_1f1b,
    pipeline_1f1b_interleaved,
    pipeline_stage_specs,
    sync_replicated_grads,
)

LAYERS_PER_STAGE = 2
PP = 4
HIDDEN = 256
MB_ROWS = 8
VOCAB = 1024


def set_config(hidden=256, mb_rows=8, vocab=1024, layers_per_stage=2):
    """Swap the sweep's model scale (the r5 crossover sweep runs a
    hidden=1024 / 64-row config where per-tick activations dominate the
    constant workspace, making the GPipe-vs-1F1B crossover visible)."""
    global HIDDEN, MB_ROWS, VOCAB, LAYERS_PER_STAGE
    HIDDEN, MB_ROWS, VOCAB, LAYERS_PER_STAGE = (
        hidden, mb_rows, vocab, layers_per_stage)


def _setup(num_micro: int):
    """Model, specs, and data shared by both schedules' measurements —
    one definition so the GPipe and 1F1B rows stay comparable."""
    n_layers = PP * LAYERS_PER_STAGE
    params = {
        "w": jnp.zeros((n_layers, HIDDEN, HIDDEN)),
        "b": jnp.zeros((n_layers, HIDDEN)),
        "head": jnp.zeros((HIDDEN, VOCAB)),
    }
    specs = pipeline_stage_specs({"w": P(None, None, None),
                                  "b": P(None, None)})
    specs = {**specs, "head": P()}
    x = jnp.zeros((num_micro, MB_ROWS, HIDDEN))
    y = jnp.zeros((num_micro, MB_ROWS, HIDDEN))
    return params, specs, x, y


def _stage_body(local, h):
    def body(c, lp):
        return jnp.tanh(c @ lp["w"] + lp["b"]), None

    out, _ = jax.lax.scan(body, h, local)
    return out


def _head_loss(head, h, mb):
    return jnp.mean((h @ head)[..., :HIDDEN] * 0 + (h - mb["y"]) ** 2)


def _memory_row(f, params, x, y, **tags):
    mem = f.lower(params, x, y).compile().memory_analysis()
    return {
        **tags,
        "temp_mb": round(mem.temp_size_in_bytes / 1e6, 3),
        "argument_mb": round(mem.argument_size_in_bytes / 1e6, 3),
        "output_mb": round(mem.output_size_in_bytes / 1e6, 3),
    }


def measure(num_micro: int, remat: bool) -> dict:
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=PP
    )
    try:
        params, specs, x, y = _setup(num_micro)

        def loss(params, x, y):
            local = {"w": params["w"], "b": params["b"]}
            per = pipeline(
                first_fn=lambda mb: mb["x"],
                stage_fn=lambda h: _stage_body(local, h),
                last_fn=lambda h, mb: _head_loss(params["head"], h, mb),
                microbatches={"x": x, "y": y},
                remat=remat,
            )
            return jnp.mean(per)

        f = jax.jit(jax.shard_map(
            jax.value_and_grad(loss), mesh=mesh,
            in_specs=(specs, P(), P()), out_specs=(P(), specs),
        ))
        return _memory_row(f, params, x, y, schedule="gpipe",
                           num_micro=num_micro, remat=remat)
    finally:
        parallel_state.destroy_model_parallel()


def measure_1f1b(num_micro: int) -> dict:
    """True 1F1B: in-flight state bounded by 2*pp saved stage inputs —
    temp memory must be ~flat in num_micro."""
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=PP
    )
    try:
        params, specs, x, y = _setup(num_micro)

        def fb(params, x, y):
            losses, grads = pipeline_1f1b(
                first_fn=lambda prm, mb: mb["x"],
                stage_fn=lambda prm, h: _stage_body(
                    {"w": prm["w"], "b": prm["b"]}, h
                ),
                last_fn=lambda prm, h, mb: _head_loss(prm["head"], h, mb),
                params=params,
                microbatches={"x": x, "y": y},
            )
            grads = sync_replicated_grads(grads, specs)
            return jnp.mean(losses), grads

        f = jax.jit(jax.shard_map(
            fb, mesh=mesh, in_specs=(specs, P(), P()),
            out_specs=(P(), specs),
        ))
        return _memory_row(f, params, x, y, schedule="1f1b",
                           num_micro=num_micro,
                           remat="per-stage (built in)")
    finally:
        parallel_state.destroy_model_parallel()


def measure_interleaved(num_micro: int, V: int = 2) -> dict:
    """Interleaved 1F1B: (V, 2*pp) saved chunk inputs — temp memory must
    stay ~flat in num_micro (the fwd-only interleaved schedule it
    replaces paid GPipe's O(num_micro))."""
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=PP
    )
    try:
        params, specs, x, y = _setup(num_micro)
        # same total layers, chunked (V, pp, per, ...)
        per = params["w"].shape[0] // (V * PP)
        params = {
            "w": params["w"].reshape(V, PP, per, HIDDEN, HIDDEN),
            "b": params["b"].reshape(V, PP, per, HIDDEN),
            "head": params["head"],
        }
        specs = {"w": P(None, "pp", None, None, None),
                 "b": P(None, "pp", None, None), "head": P()}

        def fb(params, x, y):
            def chunk_fn(prm, h, v):
                local = {
                    "w": jax.lax.dynamic_index_in_dim(
                        prm["w"], v, 0, False)[0],
                    "b": jax.lax.dynamic_index_in_dim(
                        prm["b"], v, 0, False)[0],
                }
                return _stage_body(local, h)

            losses, grads = pipeline_1f1b_interleaved(
                first_fn=lambda prm, mb: mb["x"],
                chunk_fn=chunk_fn,
                last_fn=lambda prm, h, mb: _head_loss(prm["head"], h, mb),
                params=params,
                microbatches={"x": x, "y": y},
                num_model_chunks=V,
            )
            grads = sync_replicated_grads(grads, specs)
            return jnp.mean(losses), grads

        f = jax.jit(jax.shard_map(
            fb, mesh=mesh, in_specs=(specs, P(), P()),
            out_specs=(P(), specs),
        ))
        return _memory_row(f, params, x, y, schedule="1f1b_interleaved",
                           num_micro=num_micro, num_model_chunks=V,
                           remat="per-chunk (built in)")
    finally:
        parallel_state.destroy_model_parallel()


def measure_encdec(num_micro: int, fb_1f1b: bool) -> dict:
    """Enc-dec fused schedules: the 1F1B variant must hold temp ~flat in
    num_micro (O(pp) saved {x, mem} pairs) where vjp-through-GPipe grows
    with the tape."""
    from apex_tpu.transformer.pipeline_parallel import (
        pipeline_encdec_fused,
        pipeline_encdec_fused_1f1b,
    )

    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size_=PP
    )
    try:
        params, specs, x, y = _setup(num_micro)
        split = PP // 2

        def stage_fn(prm, h, mem, stage_idx):
            local = {"w": prm["w"], "b": prm["b"]}
            # homogeneous body with a gated "cross" term standing in for
            # cross-attention: FLOP shape matches the fused T5 design
            gate = (stage_idx >= split).astype(h.dtype)
            h = _stage_body(local, h)
            return h + gate * jnp.tanh(mem @ local["w"][0]) * 0.1

        def enc_entry(prm, mb):
            return mb["x"]

        def dec_entry(prm, mb):
            return mb["x"] * 0.5

        def last_fn(prm, h, mb):
            return _head_loss(prm["head"], h, mb)

        if fb_1f1b:
            def fb(params, x, y):
                losses, grads = pipeline_encdec_fused_1f1b(
                    enc_entry, dec_entry, stage_fn, last_fn,
                    params, {"x": x, "y": y}, split,
                )
                grads = sync_replicated_grads(grads, specs)
                return jnp.mean(losses), grads
        else:
            def fb(params, x, y):
                def loss(prm):
                    per = pipeline_encdec_fused(
                        lambda mb: enc_entry(prm, mb),
                        lambda mb: dec_entry(prm, mb),
                        lambda h, mem, s: stage_fn(prm, h, mem, s),
                        lambda h, mb: last_fn(prm, h, mb),
                        {"x": x, "y": y}, split, remat=True,
                    )
                    return jnp.mean(per)

                l, grads = jax.value_and_grad(loss)(params)
                grads = sync_replicated_grads(grads, specs)
                return l, grads

        f = jax.jit(jax.shard_map(
            fb, mesh=mesh, in_specs=(specs, P(), P()),
            out_specs=(P(), specs),
        ))
        return _memory_row(
            f, params, x, y,
            schedule="encdec_1f1b" if fb_1f1b else "encdec_gpipe_vjp",
            num_micro=num_micro,
        )
    finally:
        parallel_state.destroy_model_parallel()


def _config_doc():
    return {
        "pp": PP, "hidden": HIDDEN, "mb_rows": MB_ROWS,
        "vocab": VOCAB, "layers_per_stage": LAYERS_PER_STAGE,
        "activation_mb": MB_ROWS * HIDDEN * 4 / 1e6,
    }


def main():
    rows = []
    for remat in (True, False):
        for num_micro in (2, 4, 8, 16, 32):
            row = measure(num_micro, remat)
            rows.append(row)
            print(json.dumps(row))
    for num_micro in (2, 4, 8, 16, 32):
        row = measure_1f1b(num_micro)
        rows.append(row)
        print(json.dumps(row))
    for num_micro in (4, 8, 16, 32):  # interleaved needs micro % pp == 0
        row = measure_interleaved(num_micro)
        rows.append(row)
        print(json.dumps(row))
    for num_micro in (2, 8, 32):
        for fb_1f1b in (False, True):
            row = measure_encdec(num_micro, fb_1f1b)
            rows.append(row)
            print(json.dumps(row))
    small_config = _config_doc()

    # ---- offset decomposition (r4 verdict: the ~1.5 MB constant the
    # 1f1b temp level sits above gpipe+remat at the small config).
    # Three controlled variants at micro=8 attribute it to measured
    # components rather than guesses: (a) vocab=1 removes the LM-head
    # stash + dhead workspace; (b) mb_rows doubled scales activation-
    # proportional terms; (c) gpipe+remat under the same variants.
    decomp = []
    for tag, hidden, mb_rows, vocab in (
        ("base", 256, 8, 1024),
        ("no_head", 256, 8, 1),
        ("2x_rows", 256, 16, 1024),
    ):
        set_config(hidden=hidden, mb_rows=mb_rows, vocab=vocab)
        a = measure_1f1b(8)
        b = measure(8, True)
        decomp.append({"variant": tag, "config": _config_doc(),
                       "1f1b_temp_mb": a["temp_mb"],
                       "gpipe_remat_temp_mb": b["temp_mb"],
                       "offset_mb": round(a["temp_mb"] - b["temp_mb"], 3)})
        print(json.dumps(decomp[-1]))
    set_config()

    # ---- crossover sweep: hidden=1024 / 64-row microbatches, where a
    # tick's activation (64*1024*4 = 256 KB) dwarfs the constant
    # workspace.  GPipe+remat stashes one activation per tick
    # (num_micro + pp - 1 of them), 1F1B keeps O(pp) in flight — the
    # curves must cross as num_micro grows.
    set_config(hidden=1024, mb_rows=64, vocab=1024)
    large_rows = []
    for num_micro in (4, 8, 16, 32, 64):
        row = measure(num_micro, True)
        large_rows.append(row)
        print(json.dumps(row))
        row = measure_1f1b(num_micro)
        large_rows.append(row)
        print(json.dumps(row))
    large_config = _config_doc()
    set_config()
    crossover = None
    for m in (4, 8, 16, 32, 64):
        g = next(r["temp_mb"] for r in large_rows
                 if r["schedule"] == "gpipe" and r["num_micro"] == m)
        o = next(r["temp_mb"] for r in large_rows
                 if r["schedule"] == "1f1b" and r["num_micro"] == m)
        if o < g:
            crossover = m
            break

    doc = {
        "config": small_config,
        "rows": rows,
        "offset_decomposition": decomp,
        "large_config": large_config,
        "large_rows": large_rows,
        "crossover_num_micro": crossover,
        "notes": (
            "large sweep: gpipe+remat temp grows ~one activation per tick "
            "(num_micro + pp - 1), 1f1b holds O(pp) stage inputs; "
            "crossover_num_micro is the first measured num_micro where "
            "1f1b temp < gpipe+remat temp at the large config (r5 "
            "capture: gpipe 17.8->76.5 MB over micro 4->64 vs 1f1b flat "
            "at 39.1 MB, crossing at micro=32). The small-config ~1.5 MB "
            "constant offset decomposes per offset_decomposition: "
            "removing the LM head (no_head) cuts it ~35% (head-grad "
            "buffers held across the fwd+bwd scan), while doubling "
            "activation rows (2x_rows) leaves it ~flat — the offset is "
            "per-program vjp workspace (1f1b's single scan carries both "
            "fwd and bwd temporaries), constant in num_micro AND in "
            "activation size, i.e. exactly the term that stops "
            "mattering at production scale where the large sweep's "
            "per-tick activations dominate."
        ),
    }
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "PIPELINE_MEMORY.json")
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
