"""The served path of the window-and-full-attention expert cell, broken
on purpose, through the cell's OWN comparison: each control builds the
model as the runner does, breaks one thing in the SERVED side only, and
hands what ``runners.serve_window_moe.served_check`` read to the same
``verdict`` (reference, ``compare``, ``judge``, the traffic file's
limits) that decides the cell's ``correct``.  A control that comes out
``correct`` is a fault the check does not see.

    python3 benchmarks/controls_window_moe.py --workload <cell> \
        --seed <n> --controls sound,weights_8bit,window_whole_context

One JSON line a control (also appended to
``chiprun_out/benchmarks/controls.jsonl``).  The limits of ``PERF.md``
section 4 were set from these lines.  Each control that changes a
program compiles it again: name only those you need.

- ``sound``: nothing broken (the reading the limits sit above).
- ``weights_8bit``: every served matrix keeps 3 of bfloat16's 7 mantissa
  bits (``controls_latent_moe._rounded``); the reference gets the
  unrounded weights.
- ``window_whole_context``: the window layers' mask and page walk are
  given the whole context (whatever their ring still holds of it).
- ``full_cut_to_window``: the full layer sees the last ``sliding_window``
  positions only.
- ``rope_on_full``: the full layer rotates q and k as a window layer
  does.
- ``neighbour_window_page``: in every decode step one column of every
  slot's window-class table is the next column of the slot before it.
"""

from __future__ import annotations

import argparse
import contextlib
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
from runners import serve_window_moe as runner            # noqa: E402

CONTROLS = ("sound", "weights_8bit", "window_whole_context",
            "full_cut_to_window", "rope_on_full", "neighbour_window_page")


def broken(control: str, n: int):
    """A context in which the program's served path has the fault
    ``control`` names (``n``: the checked prompt's length)."""
    import jax.numpy as jnp

    from apex_tpu.models import afmoe as M

    model_type = M.AfmoeModel
    if control in ("sound", "weights_8bit"):
        return contextlib.nullcontext()
    if control == "window_whole_context":
        window = model_type._window
        return _patched(model_type, "_window", lambda self, layer: (
            10 ** 9 if window(self, layer) else 0))
    if control == "full_cut_to_window":
        return _patched(model_type, "_window", lambda self, layer:
                        self.config.sliding_window)
    if control == "rope_on_full":
        return _patched(model_type, "_rotates", lambda self, layer: True)
    if control == "neighbour_window_page":
        step = model_type.decode_step

        def decode_step(self, params, pools, tokens, positions, active,
                        page_table, *, cache_config, **kw):
            (lo, hi), = [cols for cl, cols in zip(
                cache_config.classes, cache_config.table_columns)
                if cl.window]
            # a page well inside the checked prompt's window
            col = lo + (n // cache_config.page_size - 8) % (hi - lo)
            other = lo + (col + 1 - lo) % (hi - lo)
            wrong = page_table.at[:, col].set(
                jnp.roll(page_table, 1, axis=0)[:, other])
            return step(self, params, pools, tokens, positions, active,
                        wrong, cache_config=cache_config, **kw)

        return _patched(model_type, "decode_step", decode_step)
    raise SystemExit(f"controls_window_moe.py: no control {control!r}; "
                     f"there are {CONTROLS}")


def reading(run, control: str) -> dict:
    """One control: the model built, the fault in, the served side read,
    the fault out, the verdict."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.afmoe import AfmoeModel

    n = runner.check_plan(run.traffic)[0]
    init = AfmoeModel.init
    with contextlib.ExitStack() as stack:
        stack.enter_context(broken(control, n))
        if control == "weights_8bit":
            stack.enter_context(_patched(
                AfmoeModel, "init", lambda self, key: jax.tree.map(
                    lambda a: _rounded(a) if a.dtype == jnp.bfloat16 else a,
                    init(self, key))))
        model, params, ccfg, fns, make_pools = runner.build(run)
        served = runner.served_check(
            run, fns, ccfg, make_pools(), run.config["vocab_size"])
    if control == "weights_8bit":
        del params, fns                 # two models do not fit the chip
        params = runner.build(run)[1]   # the unrounded weights
    why, numbers = runner.verdict(run, model, params, ccfg.max_seqs, *served)
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
        print("controls_window_moe.py: needs a TPU", file=sys.stderr)
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
