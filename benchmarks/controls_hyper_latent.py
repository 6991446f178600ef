"""The served path of a hyper-connection latent-attention cell, broken
on purpose, through the cell's OWN comparison: each control builds the
model as the runner does, breaks one thing in the SERVED side only, and
hands what ``served_check`` read to the same ``verdict`` (reference,
``compare``, ``judge``, the traffic file's limits) that decides the
cell's ``correct``.  A control that comes out ``correct`` is a fault the
check does not see.

    python3 benchmarks/controls_hyper_latent.py --workload <cell> \
        --seed <n> --controls sound,h_res_identity,streams_16bit

One JSON line a control (also appended to
``chiprun_out/benchmarks/controls.jsonl``); the options, the loop and
the record are ``controls_latent_moe.py``'s.  The limits of ``PERF.md``
section 4 were set from these lines.  Each control that changes a
program compiles it again: name only those you need.

- ``sound``: nothing broken (the reading the limits sit above).
- ``weights_8bit``: every served bfloat16 matrix keeps 3 of its 7
  mantissa bits (``controls_latent_moe._rounded``); the reference gets
  the unrounded weights: the precision below the configuration's.
- ``h_res_identity``: ``H_res`` = I, a plain residual a stream.
- ``no_dynamic``: the three dynamic terms dropped (``alpha`` = 0): the
  mappings are their biases, the same for every token.
- ``h_post_without_2``: ``H_post`` = sigmoid(.), half of what it is.
- ``streams_16bit``: the streams rounded to bfloat16 after every mix
  (and the embedding copied in that way).
- ``walk_one_page_short``: every decode walk stops at the last page
  boundary: the slot's newest rows (1 to 64 of them, the query's own
  among them) are not read.
- ``yarn_factor_40``: the rotary frequencies and the softmax scale's
  ``m`` at YaRN factor 40 (DeepSeek-V3.2's) instead of 64.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import controls_latent_moe as base                        # noqa: E402
from controls_latent_moe import _patched, _rounded         # noqa: E402
from runners import serve_hyper_latent as runner           # noqa: E402

CONTROLS = ("sound", "weights_8bit", "h_res_identity", "no_dynamic",
            "h_post_without_2", "streams_16bit", "walk_one_page_short",
            "yarn_factor_40")


def broken(control: str):
    """A context in which the program's served path has the fault
    ``control`` names."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import xing4 as M

    if control == "sound":
        return contextlib.nullcontext()
    if control == "weights_8bit":
        init = M.Xing4Model.init
        return _patched(
            M.Xing4Model, "init", lambda self, key: jax.tree.map(
                lambda a: _rounded(a) if a.dtype == jnp.bfloat16 else a,
                init(self, key)))
    if control in ("h_res_identity", "no_dynamic", "h_post_without_2"):
        mapping = M.hc_mapping

        def faulty(streams, phi, alpha, bias, **kw):
            if control == "no_dynamic":
                alpha = jnp.zeros_like(alpha)
            pre, post, res = mapping(streams, phi, alpha, bias, **kw)
            if control == "h_post_without_2":
                post = post / 2.0
            if control == "h_res_identity":
                res = jnp.broadcast_to(
                    jnp.eye(res.shape[0])[:, :, None], res.shape)
            return pre, post, res

        return _patched(M, "hc_mapping", faulty)
    if control == "streams_16bit":
        mix, streams = M.hc_mix, M.Xing4Model._streams
        rounded = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
        stack = contextlib.ExitStack()
        stack.enter_context(_patched(
            M, "hc_mix", lambda *a: rounded(mix(*a))))
        stack.enter_context(_patched(
            M.Xing4Model, "_streams",
            lambda self, params, tokens: rounded(
                streams(self, params, tokens))))
        return stack
    if control == "walk_one_page_short":
        paged = M.mla_paged

        def short(q_nope, q_rope, pool, layer, page_table, lengths, *a,
                  **kw):
            page = pool.shape[2]
            return paged(q_nope, q_rope, pool, layer, page_table,
                         (jnp.maximum(lengths, 1) - 1) // page * page,
                         *a, **kw)

        return _patched(M, "mla_paged", short)
    if control == "yarn_factor_40":
        from_hf = M.Xing4Config.from_hf.__func__
        return _patched(
            M.Xing4Config, "from_hf", classmethod(
                lambda cls, *a, **k: dataclasses.replace(
                    from_hf(cls, *a, **k), rope_factor=40.0)))
    raise SystemExit(f"controls_hyper_latent.py: no control {control!r}; "
                     f"it has {CONTROLS}")


def reading(run, control: str) -> dict:
    """One control: the model built, the fault in, the served side read,
    the fault out, the verdict."""
    with broken(control):
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
    # the options, the loop over the controls and the record are the
    # latent cell's; a reading is this file's
    with _patched(base, "reading", reading):
        return base.main(argv)


if __name__ == "__main__":
    sys.exit(main())
