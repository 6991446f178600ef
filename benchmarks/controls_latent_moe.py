"""The served path of a latent-attention expert cell, broken on purpose,
through the cell's OWN comparison: each control builds the model as the
runner does, breaks one thing in the SERVED side only, and hands what
``runners.serve_latent_moe.served_check`` read to the same ``verdict``
(reference, ``compare``, ``judge``, the traffic file's limits) that
decides the cell's ``correct``.  A control that comes out ``correct``
is a fault the check does not see.

    python3 benchmarks/controls_latent_moe.py --workload <cell> \
        --seed <n> --controls sound,weights_8bit,one_step,cache_8bit

One JSON line a control (also appended to
``chiprun_out/benchmarks/controls.jsonl``).  The limits of
``PERF.md`` section 4 were set from these lines.  Each control that
changes a program compiles it again (minutes at the published widths):
name only those you need.

- ``sound``: nothing broken (the reading the limits sit above).
- ``weights_8bit``: every served matrix keeps 3 of bfloat16's 7 mantissa
  bits (float8_e4m3's precision with bfloat16's range, by integer
  arithmetic on the bits: nothing a compiler folds away); the reference
  gets the unrounded weights.
- ``cache_8bit``: every cached row and index key rounded the same way
  as it is written.
- ``one_step``: two faults that each hit ONE decode step of the first
  request: the rotary position off by one two fifths of the way through
  its steps, and a wrong page (the next entry) three fifths through.
- ``wrong_page``: page 10 of every slot's table is page 11 of the slot
  before it, in every decode step (for the first request: real rows of
  the same prompt, 64 positions on).
- ``scale_without_m2``: the softmax scale without YaRN's ``m^2``.
- ``dense_attention``: every context token selected.
- ``gate_from_c``: the experts' weights from the bias-corrected scores.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import run as bench                                       # noqa: E402
from runners import serve_latent_moe as runner            # noqa: E402


def _rounded(a):
    """bfloat16 with 3 mantissa bits kept, round half up."""
    import jax
    import jax.numpy as jnp

    u = jax.lax.bitcast_convert_type(a.astype(jnp.bfloat16), jnp.uint16)
    return jax.lax.bitcast_convert_type(
        (u + jnp.uint16(8)) & jnp.uint16(0xFFF0), jnp.bfloat16
    ).astype(a.dtype)


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def broken(control: str, n: int, new: int):
    """A context in which the program's served path has the fault
    ``control`` names (``n``, ``new``: the checked prompt's length and
    the tokens its first request generates)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import deepseek_v32 as M
    from apex_tpu.serving import kv_cache as KV
    from apex_tpu.transformer import moe as MOE

    model_type = M.DeepSeekV32Model
    if control in ("sound", "weights_8bit"):
        return contextlib.nullcontext()
    if control == "cache_8bit":
        write = KV.write_latent_tokens
        return _patched(
            KV, "write_latent_tokens",
            lambda pools, layer, row, key, pages, offsets: write(
                pools, layer, _rounded(row), _rounded(key), pages, offsets))
    if control in ("one_step", "wrong_page"):
        step = model_type.decode_step
        rope_at, page_at = n + 2 * (new - 1) // 5, n + 3 * (new - 1) // 5

        def decode_step(self, params, pools, tokens, positions, active,
                        page_table, **kw):
            # page 10 of every slot <- page 11 of the slot before it (the
            # first request's: the last request's, real rows of the same
            # prompt, 64 positions on)
            wrong = page_table.at[:, 10].set(
                jnp.roll(page_table, 1, axis=0)[:, 11])
            if control == "wrong_page":
                return step(self, params, pools, tokens, positions, active,
                            wrong, **kw)
            rope = self._rope_rows
            self._rope_rows = lambda table, p: rope(
                table, p + (p == rope_at))
            try:
                return step(
                    self, params, pools, tokens, positions, active,
                    jnp.where(jnp.any(active & (positions == page_at)),
                              wrong, page_table), **kw)
            finally:
                del self._rope_rows

        return _patched(model_type, "decode_step", decode_step)
    if control == "scale_without_m2":
        return _patched(
            M.DeepSeekV32Config, "softmax_scale", property(
                lambda self: (self.qk_nope_head_dim
                              + self.qk_rope_head_dim) ** -0.5))
    if control == "dense_attention":
        from_hf = M.DeepSeekV32Config.from_hf
        return _patched(
            M.DeepSeekV32Config, "from_hf", classmethod(
                lambda cls, *a, **k: dataclasses.replace(
                    from_hf(*a, **k), index_topk=10 ** 6)))
    if control == "gate_from_c":
        route = MOE.HeldExpertsMLP.route

        def from_c(self, params, x):
            chosen, _ = route(self, params, x)
            s = jax.nn.sigmoid(jnp.matmul(
                x, params["router"]["weight"].astype(x.dtype),
                preferred_element_type=jnp.float32))
            c = jnp.take_along_axis(
                s + params["router"]["bias"], chosen, axis=1)
            return chosen, self.routed_scaling_factor * c / (
                jnp.sum(c, -1, keepdims=True) + 1e-20)

        return _patched(MOE.HeldExpertsMLP, "route", from_c)
    raise SystemExit(f"controls_latent_moe.py: no control {control!r}")


def reading(run, control: str) -> dict:
    """One control: the model built, the fault in, the served side read,
    the fault out, the verdict."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.deepseek_v32 import DeepSeekV32Model

    n, new, _ = runner.check_plan(run.traffic)
    init = DeepSeekV32Model.init
    with contextlib.ExitStack() as stack:
        stack.enter_context(broken(control, n, new))
        if control == "weights_8bit":
            stack.enter_context(_patched(
                DeepSeekV32Model, "init", lambda self, key: jax.tree.map(
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
    ap.add_argument("--seed", type=int, required=True)
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
        print("controls_latent_moe.py: needs a TPU", file=sys.stderr)
        return 3
    cell, config, traffic, _ = bench.resolve(
        bench.load_json(args.manifest), args.workload)
    if not args.allow_cpu:
        os.makedirs(bench.OUT_DIR, exist_ok=True)
    for control in args.controls.split(","):
        run = bench.Run(
            cell=cell, config=config, traffic=traffic, seed=args.seed,
            seconds=0.0, trace=False, devices=devices,
            clock=bench.CompileClock(), t_start=time.perf_counter())
        t0 = time.perf_counter()
        line = dict(reading(run, control),
                    seconds=time.perf_counter() - t0)
        print(json.dumps(line), flush=True)
        if not args.allow_cpu:          # the record of chip runs only
            with open(os.path.join(bench.OUT_DIR, "controls.jsonl"),
                      "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
