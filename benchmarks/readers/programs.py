"""Readers over the program's ledger of executables
(``apex_tpu/telemetry/programs.py``; docs/observability.md "Programs
obtained"): what set-up was made of, by program and by stage.

The ledger lives in the benchmark's own process (the runners import the
program), so it is read here directly and not from the trace.  A run's
set-up is the records with ``run.t_start <= t_end <`` the tracer's start
(now, where no trace started): all of set-up and none of the window, in
which a correct run obtains nothing.  The harness's own "executables
obtained" runs to the end of the run: where a runner makes an eager
operation after its window, the note says how many came after the trace
began, and the two counts differ by them.

*Own* records are those whose name a module of the main path claimed
(``programs.own``); the others are eager one-operation programs and
whatever the harness jits beside the program (its reference, its
weights).  A program without the ledger (the parent of the PR that added
it) gives every reader nothing, and the metric is left out.
"""

from __future__ import annotations

import time
from typing import List, Optional


def _ledger():
    """The program's ledger module, or None where it has none."""
    try:
        from apex_tpu.telemetry import programs
    except ImportError:
        return None
    return programs


def setup_records(run) -> Optional[list]:
    """Once a run: the set-up's records, kept on ``run`` with the own
    ones among them (and the per-program table printed above the result
    line)."""
    if not hasattr(run, "program_records"):
        programs = _ledger()
        if programs is None:
            run.program_records = run.program_own = None
            return None
        until = run.tracer.t_started
        if until is None:
            until = time.perf_counter()
        recs = programs.ledger.records(since=run.t_start, until=until)
        own = [r for r in recs if programs.layer_of(r.name)]
        run.program_records, run.program_own = recs, own
        # what the harness's own count also holds: a harness that makes
        # an eager operation after its window (a traced run's compiled
        # texts) obtains it after the trace began
        later = programs.ledger.records(since=until)
        run.note(
            f"programs obtained in set-up: {len(recs)} executables, "
            f"{len(own)} of them the program's own; trace "
            f"{sum(r.trace_s for r in recs):.2f} s, lowering "
            f"{sum(r.lower_s for r in recs):.2f} s, obtaining "
            f"{sum(r.obtain_s for r in recs):.2f} s; after the trace "
            f"began: {len(later)}"
            + (f" ({', '.join(sorted({r.name for r in later}))})"
               if later else "") + "\n"
            + programs.format_table(recs, programs.layer_of, top=16))
    return run.program_records


def _own(run) -> Optional[List]:
    setup_records(run)
    return run.program_own


def trace_lower_s(trace, counters, params, run):
    """Seconds jax spent tracing and lowering the program's OWN
    executables in set-up: what their bodies cost whatever the compile
    cache holds."""
    own = _own(run)
    if not own:
        return None
    return sum(r.trace_s + r.lower_s for r in own)


def obtain_s(trace, counters, params, run):
    """Seconds spent obtaining the program's OWN executables in set-up:
    XLA's compilation on a miss of the cache, the read on a hit."""
    own = _own(run)
    if not own:
        return None
    return sum(r.obtain_s for r in own)


def executables(trace, counters, params, run):
    """Executables obtained in set-up, own and other: the harness's
    ``executables obtained`` counted from inside the program."""
    recs = setup_records(run)
    return None if recs is None else len(recs)


def cache_hit_share(trace, counters, params, run):
    """Percent of the set-up's executables the persistent cache gave
    (hits / (hits + misses)): 100 in a warm run, 0 in a cold one; None
    where no cache was in use."""
    recs = setup_records(run)
    if recs is None:
        return None
    hits = sum(r.cache == "hit" for r in recs)
    asked = hits + sum(r.cache == "miss" for r in recs)
    return 100.0 * hits / asked if asked else None
