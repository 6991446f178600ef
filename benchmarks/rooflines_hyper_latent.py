"""What the hyper-connection wrappers of ONE PREFILL CHUNK need at the
least, beside ``rooflines.py`` (whose peaks and ``least_seconds`` these
counts are set against) and ``rooflines_latent_moe.py`` (whose
``mla_decode`` and ``moe_experts`` serve this model's decode step
unedited: the walk reads ``mla_rows_per_step`` rows, every choice lands
on a held expert).

A count is what the ALGORITHM needs, nothing padded, nothing
recomputed; a share above 100 % means a count here is too high or the
time leaves out part of the work, and is never clipped.
"""

from __future__ import annotations

from typing import Tuple


def hc_chunk(counters: dict, config: dict) -> Tuple[float, float]:
    """The wrappers of a chunk of ``chunk_tokens`` tokens, two a layer:
    the mapping, the read-out and the mix-and-write-in.

    Bytes: the float32 streams (``hc_mult`` x ``hidden_size`` a token)
    read THREE times and written once is the least for a wrapper whose
    three parts are separate passes — the mapping needs all of a
    token's streams before ``H_pre`` exists, the mix needs the
    sub-layer's output, which needs the read-out — plus the read-out
    written and the sub-layer's output read, one float32 row a token
    each.  (A single pass that kept a token's 57 KB on the chip between
    the mapping and the read-out would need a read less; no part of the
    program does that, and the count does not assume it.)

    FLOPs: ``x~ phi`` (2 x streams x ``n (n + 2)`` outputs), the
    read-out (2 a stream value), the mix (2 ``n`` a stream value) and
    the write-in (2 a stream value); the ``n`` x ``n`` work of the
    Sinkhorn iterations is some thousand operations a token and is left
    out."""
    n, hidden = config["hc_mult"], config["hidden_size"]
    tokens = counters["chunk_tokens"]
    wrappers = 2 * counters["layers"]
    streams = tokens * n * hidden                       # values
    flops = wrappers * streams * (2.0 * n * (n + 2) + 2.0 + 2.0 * n + 2.0)
    nbytes = wrappers * 4.0 * (4 * streams + 2 * tokens * hidden)
    return flops, nbytes


KERNELS = {"hc_chunk": hc_chunk}
