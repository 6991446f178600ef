"""Readers for the CHUNK programs of a hyper-connection latent-attention
model by scope (``tlm.resid.*`` inside ``jit__chunk``).

The chunk program has one executable a context extent, all under ONE
name in the trace, and XLA numbers their instructions differently (a
``fusion.417`` is a wrapper's mix in one and an expert product in
another), so one text cannot name the operations of every run.  The
runner leaves each extent's compiled text under ``<module>@<extent>``;
each run of the module in the trace is read against the text whose
instructions (name AND result shape) its operations match best.
Operations are taken at any depth by their self time, as
``readers/latent_moe.py`` takes them.  A program without the scope, or a
run without these texts, gives nothing.
"""

import re

import rooflines
import rooflines_hyper_latent
import trace_reduce

_LINE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+ = .*)$", re.M)


def _programs(run, module):
    """``<module>@<extent>`` -> (instruction name -> result shape as the
    trace spells it, instruction name -> ``op_name``), parsed once."""
    held = vars(run).setdefault("hyper_latent_programs", {})
    for key, text in run.hlo_texts.items():
        if key.startswith(module + "@") and key not in held:
            shapes = {}
            for m in _LINE.finditer(text):
                rec = trace_reduce.parse_instruction(m.group(1))
                shapes[rec["name"]] = rec["shape"]
            held[key] = (shapes, trace_reduce.hlo_scopes(text))
    return {k: v for k, v in held.items() if k.startswith(module + "@")}


def _scope_ms(trace, params, run):
    """ms under the scope per run of the module on chip 0."""
    module = params["module"]
    programs = _programs(run, module)
    if not trace or not trace.devices or not programs:
        return None
    wanted = re.compile(params["scope"])
    if not any(wanted.search(v) for _, scopes in programs.values()
               for v in scopes.values()):
        return None
    dev = trace.devices[0]
    calls = dev.module_calls(module, trace.t0, trace.t1)
    if not calls:
        return None
    ops = [o for o in dev.ops if o.module == module]
    total, ran = 0.0, {}
    for lo, hi in calls:
        mine = [o for o in ops if lo <= o.start and o.end <= hi]
        key = max(programs, key=lambda k: sum(
            programs[k][0].get(o.name) == o.shape for o in mine))
        ran[key] = ran.get(key, 0) + 1
        scopes = programs[key][1]
        total += sum(o.self_dur for o in mine
                     if wanted.search(scopes.get(o.name, "")))
    said = vars(run).setdefault("hyper_latent_said", set())
    if module not in said:
        said.add(module)
        run.note(f"{module}: {len(calls)} runs read against their own "
                 f"program's text: {dict(sorted(ran.items()))}")
    return total / len(calls) / 1e6


def scope_self_ms(trace, counters, params, run):
    """Device milliseconds per run of ``params['module']`` in operations
    whose ``op_name`` matches the regular expression ``params['scope']``."""
    return _scope_ms(trace, params, run)


def scope_roofline(trace, counters, params, run):
    """The least time the chip could take for what the scope's work
    needs in one run of ``params['module']``
    (``rooflines_hyper_latent.KERNELS[params['kernel']]`` of the
    runner's counters) over the time the scope took, in percent."""
    ms = _scope_ms(trace, params, run)
    if not ms or "chunk_tokens" not in counters:
        return None
    flops, nbytes = rooflines_hyper_latent.KERNELS[params["kernel"]](
        counters, run.config)
    least = rooflines.least_seconds(
        flops, nbytes, run.devices[0].device_kind)
    return 100.0 * least / (ms / 1e3)
