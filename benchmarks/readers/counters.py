"""Readers over what the runner counted on the host."""


def value(trace, counters, params, run):
    """The runner's counter ``params['counter']``, as it is."""
    return counters.get(params["counter"])
