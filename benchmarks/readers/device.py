"""Readers over the device as a whole."""


def idle_share(trace, counters, params, run):
    """Percent of the traced window in which no operation ran, mean
    over the chips."""
    share = trace.idle_share() if trace else None
    return None if share is None else 100.0 * share


def peak_hbm_gb(trace, counters, params, run):
    """``peak_bytes_in_use`` on the fullest chip, in GB (1e9 bytes)."""
    stats = [d.memory_stats() for d in run.devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats) / 1e9
