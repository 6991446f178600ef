"""Roofline shares of the Mosaic kernels, from the device trace.

A kernel is found as a ``tpu_custom_call`` inside a named program (the
trace carries no kernel name yet); what it has to do at the least comes
from ``rooflines.py`` and the shapes the runner counted."""

import rooflines


def _share(least_s: float, calls) -> float:
    return 100.0 * least_s / (sum(o.dur for o in calls) / 1e9)


def train_attention_roofline(trace, counters, params, run):
    """All attention calls of the traced train steps: the least time
    the chip could take for them over the time they took.  A call with
    more than four operands is a backward pass, any other a forward one
    (the rematerialised forward is work the device did, so it is in the
    denominator AND, being a real forward call, in the numerator)."""
    calls = trace.kernel_calls(params["module"], "tpu_custom_call") \
        if trace else []
    if not calls:
        return None
    kind = run.devices[0].device_kind
    least = 0.0
    for op in calls:
        flops, nbytes = rooflines.train_attention(
            "backward" if op.operands > 4 else "forward",
            counters["batch_per_chip"], counters["heads_per_chip"],
            counters["seq"], counters["head_dim"])
        least += rooflines.least_seconds(flops, nbytes, kind)
    return _share(least, calls)


def decode_attention_roofline(trace, counters, params, run):
    """The decode-attention calls of the traced decode steps: the bytes
    of K and V the live sequences hold (their context lengths as the
    host mirrors them, mean over the traced pumps) over the HBM peak,
    against the kernel's time."""
    calls = trace.kernel_calls(params["module"], "tpu_custom_call") \
        if trace else []
    ctx = counters.get("traced_live_context_tokens_mean")
    if not calls or not ctx:
        return None
    flops, nbytes = rooflines.decode_attention(
        ctx, counters["heads_per_chip"], counters["head_dim"])
    least = rooflines.least_seconds(
        flops, nbytes, run.devices[0].device_kind)
    return _share(least * len(calls), calls)
