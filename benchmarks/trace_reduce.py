"""From a profiler trace to numbers: the reduction every PR shares.

``load_xplane`` reads the ``.xplane.pb`` the jax profiler wrote (with
nothing but jax) into a small plain structure, ``Trace`` reduces it:

- a TPU's plane ``/device:TPU:<n>`` has a line ``XLA Modules`` (one
  event per executed program, named ``jit_<fn>(<fingerprint>)``), a line
  ``XLA Ops`` (one event per HLO operation, its name the instruction's
  text; the body of a ``while`` is NESTED inside the while's own event)
  and a line ``Async XLA Ops`` (start..done of asynchronous copies and
  collectives);
- the host's plane ``/host:CPU`` carries the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans (``bench.*``) on the same clock.

Busy is the union of the intervals in which an operation ran; the traced
window is the stretch the benchmark's own spans cover; an idle gap is
named by the span the host was in and the programs on either side.  An
operation's *self* time leaves out what is nested inside it, so that a
list of the largest operations does not count a loop and its body twice.
The phase (``tlm.*`` scope) of an operation is not in the trace: it is
looked up by instruction name in the compiled program's text
(``hlo_scopes``).

Times inside are nanoseconds as the profiler gives them; results are
seconds or milliseconds as named.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Tuple

_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_MODULE_ID = re.compile(r"\(\d+\)$")
_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"', re.M)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


# ------------------------------------------------------------- parsing
def parse_instruction(text: str) -> dict:
    """``%name = <shape> opcode(<operands>), attributes`` -> the few
    fields the readers use.  Works on the profiler's event names and on
    a plain op name alike (then everything but ``name`` is empty)."""
    name, _, rest = text.partition(" = ")
    out = {"name": name.strip().lstrip("%"), "opcode": "", "shape": "",
           "operands": 0, "target": ""}
    rest = " " + rest
    m = _OPCODE.search(rest) if rest.strip() else None
    if not m:
        return out
    out["opcode"] = m.group(1)
    out["shape"] = rest[:m.start()].strip()[:120]
    depth, count = 0, 0
    for ch in rest[m.end() - 1:]:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        elif ch == "%" and depth == 1:
            count += 1
    out["operands"] = count
    t = _TARGET.search(rest)
    out["target"] = t.group(1) if t else ""
    return out


def hlo_scopes(compiled_text: str) -> Dict[str, str]:
    """instruction name -> its ``op_name`` (the chain of jax scopes,
    ``tlm.*`` phases among them) from a compiled program's text."""
    return {m.group(1): m.group(2) for m in _HLO_LINE.finditer(compiled_text)}


def load_xplane(path: str) -> dict:
    """The raw structure ``Trace`` takes, from an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    raw = {"devices": [], "host_spans": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "modules": [], "ops": [], "async": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [
                        [_MODULE_ID.sub("", e.name), e.start_ns, e.duration_ns]
                        for e in line.events]
                elif line.name in ("XLA Ops", "Async XLA Ops"):
                    key = "ops" if line.name == "XLA Ops" else "async"
                    for e in line.events:
                        rec = parse_instruction(e.name)
                        rec["start"], rec["dur"] = e.start_ns, e.duration_ns
                        dev[key].append(rec)
            raw["devices"].append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                raw["host_spans"] += [
                    [e.name, e.start_ns, e.duration_ns]
                    for e in line.events if e.name.startswith("bench.")]
    raw["devices"].sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return raw


# ----------------------------------------------------------- structure
@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    shape: str
    operands: int
    target: str
    start: float
    dur: float
    self_dur: float = 0.0
    depth: int = 0
    module: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur

    def label(self) -> str:
        kind = self.target or self.opcode
        return f"{self.module}/{self.name} {kind} {self.shape}"[:120]


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Device:
    """One chip's lines, with nesting resolved."""

    def __init__(self, raw: dict):
        self.name = raw["name"]
        self.modules = sorted(
            ((m[0], m[1], m[1] + m[2]) for m in raw["modules"]),
            key=lambda m: m[1])
        self._mod_starts = [m[1] for m in self.modules]
        fields = ("name", "opcode", "shape", "operands", "target",
                  "start", "dur")
        self.ops = sorted((Op(**{k: r[k] for k in fields})
                           for r in raw["ops"]),
                          key=lambda o: (o.start, -o.dur))
        self.async_ops = [Op(**{k: r[k] for k in fields})
                          for r in raw.get("async", [])]
        stack: List[Op] = []
        for op in self.ops:
            op.self_dur = op.dur
            while stack and stack[-1].end <= op.start:
                stack.pop()
            if stack:
                stack[-1].self_dur -= op.dur
            op.depth = len(stack)
            op.module = self.module_at(op.start)
            stack.append(op)
        for op in self.async_ops:
            op.self_dur, op.module = op.dur, self.module_at(op.start)

    def module_at(self, t: float) -> str:
        i = bisect.bisect_right(self._mod_starts, t) - 1
        if i >= 0 and t < self.modules[i][2]:
            return self.modules[i][0]
        return ""

    def busy(self, t0: float, t1: float) -> List[List[float]]:
        """Union of the operations' intervals, clipped to [t0, t1]."""
        return _union((max(o.start, t0), min(o.end, t1)) for o in self.ops
                      if o.depth == 0 and o.end > t0 and o.start < t1)

    def module_calls(self, module: str, t0: float, t1: float
                     ) -> List[Tuple[float, float]]:
        """(start, end) of every run of ``module`` wholly inside."""
        return [(s, e) for n, s, e in self.modules
                if n == module and s >= t0 and e <= t1]


class Trace:
    def __init__(self, raw: dict):
        self.devices = [Device(d) for d in raw["devices"]]
        self.host_spans = sorted(
            ((s[0], s[1], s[1] + s[2]) for s in raw["host_spans"]),
            key=lambda s: s[1])
        if self.host_spans:
            self.t0 = min(s[1] for s in self.host_spans)
            self.t1 = max(s[2] for s in self.host_spans)
        else:  # no span of the benchmark's: the device's own extent
            ops = [o for d in self.devices for o in d.ops]
            self.t0 = min((o.start for o in ops), default=0.0)
            self.t1 = max((o.end for o in ops), default=0.0)

    # ------------------------------------------------------ busy / idle
    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        per = [sum(e - s for s, e in d.busy(self.t0, self.t1))
               for d in self.devices]
        return sum(per) / len(per) / 1e9

    def idle_share(self) -> Optional[float]:
        return (1.0 - self.busy_s() / self.window_s
                if self.window_s > 0 and self.devices else None)

    def _span_at(self, t: float) -> str:
        inner = ""
        for name, s, e in self.host_spans:
            if s > t:
                break
            if t < e:
                inner = name        # the latest-starting span holding t
        return inner or "outside"

    def idle_gaps(self, top: int = 10) -> List[List]:
        """The idle time of chip 0 by what the host was doing: each gap
        is named ``<bench span>:<program before>-><program after>`` and
        gaps of one name are added up."""
        if not self.devices:
            return []
        dev = self.devices[0]
        busy = dev.busy(self.t0, self.t1)
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        total: Dict[str, float] = collections.Counter()
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            before = dev.module_at(s - 1) or "start"
            after = dev.module_at(e + 1) or "end"
            total[f"{self._span_at(s)}:{before}->{after}"] += (e - s) / 1e9
        return [[k, v] for k, v in sorted(
            total.items(), key=lambda kv: -kv[1])[:top]]

    def top_ops(self, top: int = 10) -> List[List]:
        """Chip 0's operations with most SELF time inside the window,
        occurrences of one instruction added up."""
        if not self.devices:
            return []
        total: Dict[str, float] = collections.Counter()
        for o in self.devices[0].ops:
            if o.start >= self.t0 and o.end <= self.t1:
                total[o.label()] += o.self_dur / 1e9
        return [[k, v] for k, v in sorted(
            total.items(), key=lambda kv: -kv[1])[:top]]

    # ---------------------------------------------------------- readers
    def module_ms(self, module: str) -> Optional[float]:
        """Mean device milliseconds of one run of ``module`` (chip 0)."""
        calls = self.devices[0].module_calls(module, self.t0, self.t1) \
            if self.devices else []
        if not calls:
            return None
        return sum(e - s for s, e in calls) / len(calls) / 1e6

    def _in_runs(self, module: str, ops: Iterable[Op]):
        """(number of runs of ``module`` on chip 0 wholly inside the
        window, those of ``ops`` that lie inside them)."""
        if not self.devices:
            return 0, []
        calls = self.devices[0].module_calls(module, self.t0, self.t1)
        if not calls:
            return 0, []
        lo, hi = calls[0][0], calls[-1][1]
        return len(calls), [o for o in ops if o.module == module
                            and lo <= o.start and o.end <= hi]

    def scope_ms(self, module: str, scope: str,
                 scopes: Dict[str, str]) -> Optional[float]:
        """Device milliseconds under ``scope`` per run of ``module``:
        the outermost operations whose ``op_name`` holds the scope
        (a loop counts whole, with its body)."""
        runs, ops = self._in_runs(module, self.devices[0].ops
                                  if self.devices else [])
        if not runs:
            return None
        return sum(o.dur for o in ops if o.depth == 0
                   and scope in scopes.get(o.name, "")) / runs / 1e6

    def collective_ms(self, module: str) -> Optional[float]:
        """Device milliseconds of collectives per run of ``module`` on
        chip 0: synchronous ones by their self time, asynchronous ones
        from start to done.  Total, not the exposed part."""
        dev = self.devices[0] if self.devices else None
        runs, ops = self._in_runs(module, [] if dev is None else (
            [o for o in dev.ops if o.opcode in COLLECTIVES]
            + [o for o in dev.async_ops if o.opcode.endswith("-start")
               and o.opcode[:-len("-start")] in COLLECTIVES]))
        if not runs:
            return None
        return sum(o.self_dur for o in ops) / runs / 1e6

    def kernel_calls(self, module: str, target: str) -> List[Op]:
        """Chip 0's custom calls to ``target`` inside runs of ``module``
        wholly inside the window."""
        return self._in_runs(module, [
            o for o in (self.devices[0].ops if self.devices else [])
            if o.target == target])[1]
