"""Readers over the program's ``tlm.*`` phase scopes.

The scope of an operation is not in the trace; it is looked up by the
instruction's name in the compiled program's text, which the runner puts
into ``run.hlo_texts`` under the program's name."""



def scope_ms(trace, counters, params, run):
    """Device milliseconds under scope ``params['scope']`` per run of
    the program ``params['module']`` on chip 0."""
    scopes = run.scopes(params["module"])
    if not trace or not any(params["scope"] in v for v in scopes.values()):
        return None         # no trace, or no such scope in the compiled text
    return trace.scope_ms(params["module"], params["scope"], scopes)
