"""Boundary arithmetic: how the benchmark turns timestamps into numbers.

The system delivers work in lumps: a training step completes as a whole,
and the server hands tokens to the host once per harvest window
(``harvest_every`` decode steps, ~0.8 s).  Counting lumps inside a fixed
wall window and dividing by the nominal seconds lets the position of the
window's edges against the lumps decide the result (one lump more or
less in 30 s is 2.7 %).  So every rate here is taken between two of the
system's own boundaries (step completions, ``pump`` returns): the work
committed after the first boundary up to the last one, over the time
between those same two.  ``--seconds`` decides how long a run measures,
never the denominator.

Pure Python; nothing here touches jax.
"""

from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

#: one boundary: (time on the host clock at which the event was seen,
#: cumulative units of work committed up to and including it)
Boundary = Tuple[float, float]


def window_boundaries(boundaries: Sequence[Boundary], t_open: float,
                      seconds: float) -> List[Boundary]:
    """The boundaries a window owns: from the first at or after
    ``t_open`` to the first at or after ``t_open + seconds`` (the run
    keeps going until that one exists, so the event in flight when the
    nominal window closes is counted whole)."""
    inside = [b for b in boundaries if b[0] >= t_open]
    out: List[Boundary] = []
    for b in inside:
        out.append(b)
        if b[0] >= t_open + seconds:
            break
    return out


def rate_between(boundaries: Sequence[Boundary]) -> Optional[float]:
    """Units per second from the first boundary to the last: what was
    committed AFTER the first, over the time between the two.  None
    with fewer than two boundaries or no time between them."""
    if len(boundaries) < 2:
        return None
    (t0, c0), (t1, c1) = boundaries[0], boundaries[-1]
    if t1 <= t0:
        return None
    return (c1 - c0) / (t1 - t0)


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics; None for no values."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def time_weighted_mean(samples: Sequence[Tuple[float, float]]
                       ) -> Optional[float]:
    """Mean of a gauge read at boundaries: sample ``i`` holds from
    boundary ``i - 1`` to boundary ``i`` (the first sample only anchors
    the clock)."""
    if len(samples) < 2:
        return None
    total = sum((t1 - t0) * v for (t0, _), (t1, v)
                in zip(samples, samples[1:]))
    span = samples[-1][0] - samples[0][0]
    return total / span if span > 0 else None


def quartile_spread(values: Sequence[float]) -> Optional[float]:
    """(Q3 - Q1) / median, quartiles as ``statistics.quantiles(n=4)``
    gives them: the spread a bound is set from."""
    import statistics

    if len(values) < 2:
        return None
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med if med else None
