"""Readers for the scopes and counters of a latent-attention expert
model's DECODE program (``tlm.attn.*`` / ``tlm.moe.*`` inside
``jit__decode``).

The layers run under ``lax.scan`` and the experts under a loop of their
own, so an operation sits inside nested ``while``s: operations are taken
at ANY depth, by their SELF time, and matched by the ``op_name`` their
instruction has in the compiled text the runner left in
``run.hlo_texts``.  A program without these scopes (the parent of the PR
that added them) gives every reader nothing.
"""

import re

import rooflines
import rooflines_latent_moe


def _scope_ms(trace, params, run):
    """(ms under the scope per run of the module on chip 0, runs)."""
    module = params["module"]
    scopes = run.scopes(module)
    wanted = re.compile(params["scope"])
    if not trace or not trace.devices or not any(
            wanted.search(v) for v in scopes.values()):
        return None, 0
    dev = trace.devices[0]
    calls = dev.module_calls(module, trace.t0, trace.t1)
    if not calls:
        return None, 0
    lo, hi = calls[0][0], calls[-1][1]
    total = sum(o.self_dur for o in dev.ops
                if o.module == module and lo <= o.start and o.end <= hi
                and wanted.search(scopes.get(o.name, "")))
    _note_split(trace, params, run, calls)
    return total / len(calls) / 1e6, len(calls)


def _note_split(trace, params, run, calls):
    """Once a run: the module's self time by ``tlm.*`` scope, for the
    people who read the log."""
    said = vars(run).setdefault("latent_moe_said", set())
    module = params["module"]
    if module in said:
        return
    said.add(module)
    scopes = run.scopes(module)
    lo, hi = calls[0][0], calls[-1][1]
    by = {}
    for o in trace.devices[0].ops:
        if o.module == module and lo <= o.start and o.end <= hi:
            found = re.findall(r"tlm\.[\w.]+", scopes.get(o.name, ""))
            scope = found[-1] if found else "(no scope)"
            by[scope] = by.get(scope, 0.0) + o.self_dur
    run.note(f"{module}: {len(calls)} runs, self ms a run by innermost "
             f"scope: " + ", ".join(
                 f"{k} {v / len(calls) / 1e6:.3f}" for k, v in sorted(
                     by.items(), key=lambda kv: -kv[1])))


def scope_self_ms(trace, counters, params, run):
    """Device milliseconds per run of ``params['module']`` in operations
    whose ``op_name`` matches the regular expression ``params['scope']``."""
    return _scope_ms(trace, params, run)[0]


def scope_roofline(trace, counters, params, run):
    """The least time the chip could take for what the scope's work
    needs in one decode step (``rooflines_latent_moe.KERNELS[params[
    'kernel']]`` of the step's own counters) over the time the scope
    took, in percent."""
    ms, _ = _scope_ms(trace, params, run)
    if not ms or "decode_steps_counted" not in counters:
        return None
    flops, nbytes = rooflines_latent_moe.KERNELS[params["kernel"]](
        counters, run.config)
    least = rooflines.least_seconds(
        flops, nbytes, run.devices[0].device_kind)
    return 100.0 * least / (ms / 1e3)
