"""Device time of the Mosaic kernels, found by NAME.

Every ``pallas_call`` of the program is named ``tlm.kernel.<name>``
(``apex_tpu/telemetry/spans.py:kernel_name``): ``pallas_call`` scopes
the call under that name, so the compiled instruction's ``op_name``
holds it — a ``custom_vjp`` backward under its own name, the forward
that remat recomputes under ``rematted_computation/tlm.kernel.<name>``.
The trace's events carry the instruction, not its ``op_name``; that is
looked up in the compiled program's text the runner left in
``run.hlo_texts`` (``run.scopes``).

Layers run under ``lax.scan``, so a kernel sits inside a ``while``:
operations are taken at ANY depth and by their self time, so the loop
around a kernel is not counted with it.
"""

import re

KERNEL_SCOPE = "tlm.kernel."
REMAT = "rematted_computation"


def scope_ms(trace, counters, params, run):
    """Device milliseconds per run of the program ``params['module']``
    on chip 0 in operations whose ``op_name`` matches the regular
    expression ``params['scope']``; with ``params['rematted']`` given,
    only those inside (true) or outside (false) a rematerialised
    computation.  None where the program's compiled text names no
    kernel at all (a program from before the names, or an executable
    from a compile cache that predates them)."""
    module = params["module"]
    scopes = run.scopes(module)
    if not trace or not trace.devices:
        return None
    if not any(KERNEL_SCOPE in v for v in scopes.values()):
        said = vars(run).setdefault("named_kernels_said", set())
        if module not in said:          # once a run, not once a metric
            said.add(module)
            run.note(f"named kernels: no {KERNEL_SCOPE}* scope in the "
                     f"compiled text of {module} ({len(scopes)} "
                     f"instructions with an op_name)")
        return None
    dev = trace.devices[0]
    calls = dev.module_calls(module, trace.t0, trace.t1)
    if not calls:
        return None
    lo, hi = calls[0][0], calls[-1][1]
    wanted, rematted = re.compile(params["scope"]), params.get("rematted")
    total = 0.0
    for o in dev.ops:
        where = scopes.get(o.name, "")
        if (o.module == module and lo <= o.start and o.end <= hi
                and wanted.search(where)
                and rematted in (None, REMAT in where)):
            total += o.self_dur
    return total / len(calls) / 1e6
