"""The served path of the hybrid state-space cell, broken on purpose,
through the cell's OWN comparison: each control builds the model as the
runner does, breaks one thing in the SERVED side only, and hands what
``runners.serve_hybrid_ssm.served_check`` read to the same ``verdict``
(reference, ``compare``, ``judge``, the traffic file's limits) that
decides the cell's ``correct``.  A control that comes out ``correct`` is
a fault the check does not see.

    python3 benchmarks/controls_hybrid_ssm.py --workload <cell> \\
        --seeds <n>,<m> --controls sound,zero_state_chunks,state_bf16

One JSON line a control and seed (also appended to
``chiprun_out/benchmarks/controls.jsonl``).  The limits of ``PERF.md``
section 4 were set from these lines.  Each control compiles its
programs again.

- ``sound``: nothing broken (the reading the limits sit above).
- ``zero_state_chunks``: every prefill chunk starts from zero recurrent
  state, the chunks after the first of a prompt included.
- ``conv_window_shifted``: the convolution's carried window is read one
  token off (its oldest input twice, its newest not at all), at every
  chunk and every decode step.
- ``no_ssm``: the state-space branch adds nothing to the residual
  stream.
- ``decode_advances_prefilling``: a decode step advances the recurrent
  state of every slot, those still between their prompt's chunks and
  the empty ones included.
- ``state_bf16``: the served recurrent state is kept in bfloat16
  (``falcon_h1.STATE_DTYPE`` patched); the reference is unchanged.
- ``weights_8bit``: every served matrix keeps 3 of bfloat16's 7 mantissa
  bits (``controls_latent_moe._rounded``); the reference gets the
  unrounded weights.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import run as bench                                       # noqa: E402
from controls_latent_moe import _patched, _rounded        # noqa: E402
from runners import serve_hybrid_ssm as runner            # noqa: E402

CONTROLS = ("sound", "zero_state_chunks", "conv_window_shifted", "no_ssm",
            "decode_advances_prefilling", "state_bf16", "weights_8bit")


def broken(control: str):
    """A context in which the program's served path has the fault
    ``control`` names."""
    import jax.numpy as jnp

    from apex_tpu.models import falcon_h1 as M

    model_type = M.FalconH1Model
    if control in ("sound", "weights_8bit"):
        return contextlib.nullcontext()
    if control == "state_bf16":
        return _patched(M, "STATE_DTYPE", jnp.bfloat16)
    if control == "zero_state_chunks":
        step = model_type.chunk_step

        def chunk_step(self, params, pools, toks, start, plen, write_from,
                       page_row, slot, **kw):
            pools = dict(pools, **{M.STATE: pools[M.STATE].at[:, slot].set(0)})
            return step(self, params, pools, toks, start, plen, write_from,
                        page_row, slot, **kw)

        return _patched(model_type, "chunk_step", chunk_step)
    if control == "conv_window_shifted":
        conv, conv_step = M.causal_conv, M.causal_conv_step
        stack = contextlib.ExitStack()
        stack.enter_context(_patched(M, "causal_conv", lambda x, w, *a: conv(
            x, jnp.concatenate([w[:1], w[:-1]]), *a)))
        stack.enter_context(_patched(
            M, "causal_conv_step", lambda x, w, *a: conv_step(
                x, jnp.concatenate([w[:, :1], w[:, :-1]], axis=1), *a)))
        return stack
    if control == "no_ssm":
        return _patched(model_type, "_ssm_out",
                        lambda self, sp, y, z: jnp.zeros(
                            (y.shape[0], self.config.hidden_size)))
    if control == "decode_advances_prefilling":
        update = M.ssm_state_update
        return _patched(M, "ssm_state_update",
                        lambda pool, layer, *a: update(
                            pool, layer, *a[:-1], jnp.ones_like(a[-1])))
    raise SystemExit(f"controls_hybrid_ssm.py: no control {control!r}; "
                     f"there are {CONTROLS}")


def reading(run, control: str) -> dict:
    """One control: the model built, the fault in, the served side read,
    the fault out, the verdict."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.falcon_h1 import FalconH1Model

    init = FalconH1Model.init
    with contextlib.ExitStack() as stack:
        stack.enter_context(broken(control))
        if control == "weights_8bit":
            stack.enter_context(_patched(
                FalconH1Model, "init", lambda self, key: jax.tree.map(
                    lambda a: _rounded(a) if a.dtype == jnp.bfloat16 else a,
                    init(self, key))))
        model, params, ccfg, fns, make_pools = runner.build(run)
        served = runner.served_check(
            run, fns, ccfg, make_pools(), run.config["vocab_size"])
    if control == "weights_8bit":
        del params, fns                 # two models do not fit the chip
        params = runner.build(run)[1]   # the unrounded weights
    why, numbers = runner.verdict(run, params, ccfg.max_seqs, *served)
    return {"control": control, "seed": run.seed, "correct": not why,
            "why_incorrect": why, **numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; every control runs each")
    ap.add_argument("--controls", required=True)
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse off the chip (numbers mean nothing)")
    args = ap.parse_args(argv)
    import jax

    if not args.allow_cpu:
        jax.config.update(
            "jax_compilation_cache_dir",
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.allow_cpu:
        print("controls_hybrid_ssm.py: needs a TPU", file=sys.stderr)
        return 3
    cell, config, traffic, _ = bench.resolve(
        bench.load_json(args.manifest), args.workload)
    if not args.allow_cpu:
        os.makedirs(bench.OUT_DIR, exist_ok=True)
    for control in args.controls.split(","):
        for seed in map(int, args.seeds.split(",")):
            gc.collect()        # the last reading's weights: two do not fit
            run = bench.Run(
                cell=cell, config=config, traffic=traffic, seed=seed,
                seconds=0.0, trace=False, devices=devices,
                clock=bench.CompileClock(), t_start=time.perf_counter())
            t0 = time.perf_counter()
            line = dict(reading(run, control),
                        seconds=time.perf_counter() - t0)
            print(json.dumps(line), flush=True)
            if not args.allow_cpu:      # the record of chip runs only
                with open(os.path.join(bench.OUT_DIR, "controls.jsonl"),
                          "a") as f:
                    f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
