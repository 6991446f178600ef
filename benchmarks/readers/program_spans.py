"""Readers over the program's own host spans (``tlm.serve.*``).

``ContinuousBatcher`` writes a ``jax.profiler.TraceAnnotation`` around
every part of a scheduler turn (``apex_tpu/telemetry/spans.py:host_span``;
docs/observability.md "Serving spans"): they land on the ``/host:CPU``
plane of the same ``.xplane.pb`` as the device's operations, on one
clock, with their stats.  ``trace_reduce.load_xplane`` keeps only the
benchmark's ``bench.*`` spans, so this file opens the trace itself for
the ``tlm.serve.*`` events and takes the device side from the ``trace``
it is handed.

Spans of one thread nest, so at every instant the innermost span is the
one thing the host was doing: a span's *self* time leaves out its
children, and chip 0's idle time is split exactly over the innermost
spans that cover it (what no span of the program covers is the
harness's).  A program without these spans (the parent of the PR that
added them) gives every reader nothing, and the metric is left out.

Times inside are nanoseconds on the profiler's clock.
"""

from __future__ import annotations

import collections
import dataclasses
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

PREFIX = "tlm.serve."
NO_SPAN = "no program span"
PREFILL_PROGRAMS = ("jit__prefill", "jit__chunk")   # what a dispatch_prefill
                                                    # span enqueues


@dataclasses.dataclass
class Span:
    name: str                   # without the prefix: "pump", "harvest", ...
    start: float
    end: float
    stats: dict
    parent: Optional["Span"] = None
    self_ns: float = 0.0

    @property
    def dur(self) -> float:
        return self.end - self.start

    def under(self, name: str) -> Optional["Span"]:
        """The nearest enclosing span called ``name`` (itself included)."""
        s = self
        while s is not None and s.name != name:
            s = s.parent
        return s


# ------------------------------------------------------------- loading
def load_spans(path: str) -> List[list]:
    """``[name, start_ns, duration_ns, stats]`` of every ``tlm.serve.*``
    event of the host thread that wrote most of them (the scheduler's)."""
    from jax.profiler import ProfileData

    best: List[list] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            found = [[e.name, e.start_ns, e.duration_ns, dict(e.stats)]
                     for e in line.events if e.name.startswith(PREFIX)]
            if len(found) > len(best):
                best = found
    return best


def nest(raw: Iterable[Sequence]) -> List[Span]:
    """Spans in start order with parents and self times resolved."""
    spans = sorted((Span(r[0][len(PREFIX):], r[1], r[1] + r[2], r[3])
                    for r in raw), key=lambda s: (s.start, -s.end))
    stack: List[Span] = []
    for s in spans:
        s.self_ns = s.dur
        while stack and stack[-1].end <= s.start:
            stack.pop()
        if stack:
            s.parent = stack[-1]
            stack[-1].self_ns -= s.dur
        stack.append(s)
    return spans


# ------------------------------------------------------------ reductions
def innermost(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """The covered time cut into ``(start, end, name)`` pieces, each
    named by the innermost span over it: a partition, in time order."""
    marks = sorted({t for s in spans for t in (s.start, s.end)})
    out: List[Tuple[float, float, str]] = []
    stack: List[Span] = []
    i = 0
    for a, b in zip(marks, marks[1:]):
        while stack and stack[-1].end <= a:
            stack.pop()
        while i < len(spans) and spans[i].start <= a:
            if spans[i].end > a:
                stack.append(spans[i])
            i += 1
        if stack:
            out.append((a, b, stack[-1].name))
    return out


def split_idle(idle: Iterable[Tuple[float, float]],
               pieces: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Each idle interval's nanoseconds by the piece that covers them;
    what no piece covers goes to ``NO_SPAN``.  The parts sum to the
    whole."""
    out: Dict[str, float] = collections.Counter()
    j = 0
    for a, b in sorted(idle):
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            part = min(b, pieces[k][1]) - max(a, pieces[k][0])
            if part > 0:
                out[pieces[k][2]] += part
                covered += part
            k += 1
        out[NO_SPAN] += (b - a) - covered
    return dict(out)


def idle_intervals(busy: List[List[float]], t0: float, t1: float
                   ) -> List[Tuple[float, float]]:
    """The complement of the (sorted, disjoint) busy intervals in
    [t0, t1]."""
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def pair_dispatches(dispatches: List[Span], runs: List[Tuple[float, float]]
                    ) -> List[Tuple[Span, Tuple[float, float]]]:
    """(dispatch span, the device run it enqueued).  The device runs
    programs in the order they were enqueued and none before its enqueue
    began, so each dispatch, in order, takes the first unclaimed run
    that starts after it."""
    out = []
    j = 0
    for d in dispatches:
        while j < len(runs) and runs[j][0] < d.start:
            j += 1
        if j == len(runs):
            break
        out.append((d, runs[j]))
        j += 1
    return out


def request_waits(spans: List[Span], runs: List[Tuple[float, float]]
                  ) -> List[dict]:
    """Per request with a prefill run and a first token inside the
    trace: ``queued_s`` (its last ``dispatch_prefill`` -> that run's
    start: the prefill queued behind the device's other work), ``run_s``
    (the run) and ``harvest_wait_s`` (the run's end -> its
    ``first_token`` span: the token sat on the device until a harvest
    brought it to the host)."""
    last = {d.stats.get("uid"): (d, run) for d, run in pair_dispatches(
        [s for s in spans if s.name == "dispatch_prefill"], runs)}
    out = []
    for s in spans:
        uid = s.stats.get("uid")
        if s.name != "first_token" or uid not in last:
            continue
        d, (r0, r1) = last.pop(uid)
        if s.start >= r1:
            out.append({"uid": uid, "queued_s": (r0 - d.start) / 1e9,
                        "run_s": (r1 - r0) / 1e9,
                        "harvest_wait_s": (s.start - r1) / 1e9})
    return out


# ------------------------------------------------------------- analysis
@dataclasses.dataclass
class Analysis:
    turns: int
    pump_ms: float                      # mean duration of a turn
    host_ms: float                      # mean turn minus its harvests
    self_ms: Dict[str, float]           # span name -> self time a turn
    idle_ms: Optional[Dict[str, float]]  # covering span -> idle, whole window
    requests: List[dict]


def analyse(spans: List[Span], trace) -> Optional[Analysis]:
    """``trace`` is the reduced trace (``trace_reduce.Trace``) or None;
    without a device in it only the host-side numbers are filled."""
    if trace is not None and trace.host_spans:
        spans = [s for s in spans
                 if s.start >= trace.t0 and s.end <= trace.t1]
    pumps = [s for s in spans if s.name == "pump"]
    if not pumps:
        return None
    self_ns: Dict[str, float] = collections.Counter()
    turns_ns = harvests_ns = 0.0
    for s in spans:
        if s.under("pump") is None:
            continue
        self_ns[s.name] += s.self_ns
        turns_ns += s.dur if s.name == "pump" else 0.0
        harvests_ns += s.dur if s.name == "harvest" else 0.0
    n = len(pumps)
    idle, requests = None, []
    if trace is not None and trace.devices:
        dev = trace.devices[0]
        idle = split_idle(
            idle_intervals(dev.busy(trace.t0, trace.t1), trace.t0, trace.t1),
            innermost(spans))
        idle = {k: v / 1e6 for k, v in idle.items()}
        requests = request_waits(spans, [
            (s, e) for name, s, e in dev.modules
            if name in PREFILL_PROGRAMS])
    return Analysis(
        turns=n, pump_ms=turns_ns / n / 1e6,
        host_ms=(turns_ns - harvests_ns) / n / 1e6,
        self_ms={k: v / n / 1e6 for k, v in self_ns.items()},
        idle_ms=idle, requests=requests)


def _note(a: Analysis, trace) -> str:
    order = sorted(a.self_ms, key=lambda k: -a.self_ms[k])
    lines = [
        f"program spans: {a.turns} turns ({PREFIX}pump) of mean "
        f"{a.pump_ms:.3f} ms, {a.host_ms:.3f} ms of it not in harvest; "
        f"self time a turn (ms): "
        + ", ".join(f"{k} {a.self_ms[k]:.3f}" for k in order)
        + f"; sum {sum(a.self_ms.values()):.3f}"]
    if a.idle_ms is not None:
        total = sum(a.idle_ms.values())
        names = sorted(a.idle_ms, key=lambda k: -a.idle_ms[k])
        lines.append(
            f"chip 0 idle {total:.3f} ms of the {trace.window_s:.3f} s "
            f"traced, by the innermost span over it (ms; a turn in "
            f"brackets): " + ", ".join(
                f"{k} {a.idle_ms[k]:.3f} ({a.idle_ms[k] / a.turns:.3f})"
                for k in names if a.idle_ms[k] > 0))
    if a.requests:
        med = lambda key: statistics.median(r[key] for r in a.requests)
        lines.append(
            f"requests with prefill run and first token in the trace: "
            f"{len(a.requests)}; medians: dispatch -> prefill starts "
            f"{med('queued_s'):.4f} s, prefill runs {med('run_s'):.4f} s, "
            f"prefill ends -> first token on the host "
            f"{med('harvest_wait_s'):.4f} s")
    return "\n".join(lines)


def _analysis(trace, run) -> Optional[Analysis]:
    """Once a run: the analysis, kept on ``run`` (and its table printed
    above the result line)."""
    if not hasattr(run, "program_spans"):
        path = run.tracer.xplane()
        spans = nest(load_spans(path)) if path else []
        run.program_spans = analyse(spans, trace)
        if run.program_spans is not None:
            run.note(_note(run.program_spans, trace))
        elif path:
            run.note(f"program spans: no {PREFIX}* span in the trace")
    return run.program_spans


# -------------------------------------------------------------- readers
def host_ms_per_pump(trace, counters, params, run):
    """Mean milliseconds of a scheduler turn (``pump`` span) NOT inside
    its ``harvest`` children: the host's own work, and whatever an
    enqueue blocks on."""
    a = _analysis(trace, run)
    return None if a is None else a.host_ms


def idle_under_spans_ms(trace, counters, params, run):
    """Chip 0's idle milliseconds per turn while the innermost span of
    the scheduler's thread was one of ``params['spans']``."""
    a = _analysis(trace, run)
    if a is None or a.idle_ms is None:
        return None
    return sum(a.idle_ms.get(k, 0.0) for k in params["spans"]) / a.turns


def harvest_wait_p50_s(trace, counters, params, run):
    """Median seconds from the end of a request's prefill run on the
    device to its ``first_token`` span on the host."""
    a = _analysis(trace, run)
    if a is None or not a.requests:
        return None
    return statistics.median(r["harvest_wait_s"] for r in a.requests)
