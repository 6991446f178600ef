"""Readers over whole programs and kinds of operations in the device
trace."""


def call_ms(trace, counters, params, run):
    """Mean device milliseconds of one run of the program
    ``params['module']`` (e.g. ``jit__decode``) on chip 0."""
    return trace.module_ms(params["module"]) if trace else None


def collective_ms(trace, counters, params, run):
    """Device milliseconds of all-reduce / all-gather / reduce-scatter
    (and permutes) per run of ``params['module']`` on chip 0: total, not
    the exposed part.  Nothing on one chip."""
    if not trace or len(trace.devices) < 2:
        return None
    return trace.collective_ms(params["module"])
