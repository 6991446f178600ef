"""The one reader the window-and-full-attention cell adds: a scope's
share of ITS roofline, the least time from ``rooflines_window_moe``.
The scope's device time is ``readers.latent_moe``'s (operations at any
depth, by self time, matched by ``op_name``); a program without the
scope, or a run without the decode steps' counters, gives nothing."""

import rooflines
import rooflines_window_moe
from readers import latent_moe


def scope_roofline(trace, counters, params, run):
    """The least time the chip could take for what the scope's work
    needs in one decode step (``rooflines_window_moe.KERNELS[params[
    'kernel']]`` of the step's own counters) over the time the scope
    took, in percent."""
    ms, _ = latent_moe._scope_ms(trace, params, run)
    if not ms or "decode_steps_counted" not in counters:
        return None
    flops, nbytes = rooflines_window_moe.KERNELS[params["kernel"]](
        counters, run.config)
    least = rooflines.least_seconds(
        flops, nbytes, run.devices[0].device_kind)
    return 100.0 * least / (ms / 1e3)
