"""What the grouped-query attention of ONE DECODE STEP over two page
classes needs at the least, beside ``rooflines.py`` (whose peaks and
``least_seconds`` these counts are set against) and
``rooflines_latent_moe.py`` (whose ``moe_experts`` reads this
configuration's keys as they are).

Each count is what the ALGORITHM needs for the rows the step's own
counters say its page walks read
(``benchmarks/runners/serve_window_moe.py:derived_counters``), whatever
implements the walk; nothing padded, nothing recomputed.  A share above
100 % means a count here is too high or the time leaves out part of the
work; it is never clipped.
"""

from __future__ import annotations

from typing import Tuple

from rooflines_latent_moe import moe_experts


def _rows(rows: float, config: dict) -> Tuple[float, float]:
    """``rows`` cached rows read once for all query heads: a row is K
    and V of every K/V head (2 x heads x head_dim x 2 B); every query
    head scores it and weights it (2 x 2 FLOPs a head and element)."""
    d = config["head_dim"]
    return (rows * config["num_attention_heads"] * d * 4.0,
            rows * 2.0 * config["num_key_value_heads"] * d * 2.0)


def window_decode(counters: dict, config: dict) -> Tuple[float, float]:
    """The window layers' walks of a decode step, all of them:
    ``window_rows_per_step`` already sums slots AND layers."""
    return _rows(counters["window_rows_per_step"], config)


def full_decode(counters: dict, config: dict) -> Tuple[float, float]:
    """The full layers' walks of a decode step."""
    return _rows(counters["full_rows_per_step"], config)


KERNELS = {"window_decode": window_decode, "full_decode": full_decode,
           "moe_experts": moe_experts}
