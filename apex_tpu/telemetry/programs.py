"""Programs obtained — a ledger of every executable jax traces, lowers
and obtains in this process, by name and stage.

The fourth channel of the telemetry package, and the only one that hears
jax's compile events.  Set-up (a cold start, a warm one that reads the
persistent cache) and a recompilation in a live server are both made of
the same three stages a program, and jax announces each through
``jax.monitoring``, for nothing on a warm step:

- ``/jax/core/compile/jaxpr_trace_duration`` (``fun_name="_decode"``),
  with one nested event for every jitted function traced inside it;
- ``/jax/core/compile/jaxpr_to_mlir_module_duration``
  (``fun_name="jit(_decode)"``): the lowering;
- ``/jax/core/compile/backend_compile_duration``: XLA's compilation on
  a miss of the persistent cache, the read on a hit; inside it, without
  a name, ``/jax/compilation_cache/cache_hits`` or ``cache_misses``
  (fired where the entry is written) and ``cache_retrieval_time_sec``.

Each duration is preceded by a ``record_scalar`` of the same name that
marks its BEGIN, which is how nested traces are told from their callers.
The ledger listens to all of them and keeps IN MEMORY one
:class:`ProgramRecord` per executable obtained: a span in the sense of
the other channels (name, start, end, what it was made of), on
``time.perf_counter()`` — the clock of ``Request.arrival_s`` and
``Completion.queue_wait_s``.  One record is a dozen plain fields beside
an executable jax itself keeps; nothing is capped or written anywhere.

Rules the ledger keeps:

- a nested function's trace seconds are never added to a sum that holds
  its caller's: only the outermost trace of a thread makes a
  ``trace_s``, the functions traced inside it are COUNTED
  (``traced_inside``: every ``jnp`` call in a kernel body is one);
- a trace that obtains no executable (a hit of jax's in-memory cache of
  traces, an ``eval_shape``, a ``.lower()`` never compiled) makes no
  record;
- a program obtained while another is being traced (an eager operation
  on concrete values inside a traced body) gets a record of its own,
  with ``trace_s`` 0: its trace was counted inside the caller's, and its
  lowering and obtaining are taken out of the caller's ``trace_s``;
- appends are safe from any thread; the stages of one program are
  matched per thread.

There is one ledger a process (:data:`ledger`), installed when this
module is first imported; jax's listeners cannot be scoped to a server
or a model, so neither can it.  :func:`own` is how a reading tells the
main path's programs from eager one-operation ones (``jit(concatenate)``)
and from whatever a harness jits beside them: the module that makes a
``jax.jit`` of the main path claims the traced function's name.  A claim
is a set insert; it wraps nothing and changes no compiled program.

When the event bus has a sink, every record is also one
``program_obtained`` event (``name``, ``seq``, ``trace_s``, ``lower_s``,
``obtain_s``, ``cache``, ``traced_inside``); with none nothing is built.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Dict, Iterable, List, Optional

import jax

from apex_tpu.telemetry import events as _events

__all__ = ["ProgramRecord", "ProgramLedger", "ledger", "install",
           "uninstall", "own", "layer_of", "records", "table",
           "format_table",
           "TRACE_EVENT", "LOWER_EVENT", "OBTAIN_EVENT"]

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
OBTAIN_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


@dataclasses.dataclass(frozen=True)
class ProgramRecord:
    """One executable obtained."""

    name: str               # the traced function's: "_decode"
    seq: int                # the n-th executable of that name, from 1
    t_begin: float          # perf_counter: the trace's BEGIN mark, or
                            # t_end minus the stages heard
    t_end: float            # perf_counter when the executable arrived
    trace_s: float          # the outermost trace, what it obtained
                            # meanwhile taken out; 0 where jax had the
                            # trace already
    traced_inside: int      # jitted functions traced inside that trace
    lower_s: float          # jaxpr -> MLIR module
    obtain_s: float         # XLA compilation, or the cache's read
    cache: str              # "hit", "miss" (compiled, and written to the
                            # cache), or "off": no persistent cache, or
                            # one that left this program out
    retrieval_s: float      # the read alone, on a hit

    @property
    def total_s(self) -> float:
        return self.trace_s + self.lower_s + self.obtain_s


def _bare(fun_name: Optional[str]) -> str:
    """``jit(_decode)`` / ``pmap(step)`` -> the traced function's name."""
    name = fun_name or "?"
    if name.endswith(")") and "(" in name:
        return name[name.index("(") + 1:-1]
    return name


class _Pending:
    """The stages heard so far of a program not yet obtained."""

    __slots__ = ("name", "t_begin", "trace_s", "traced_inside", "lower_s")

    def __init__(self, name, t_begin=None, trace_s=0.0, traced_inside=0):
        self.name, self.t_begin = name, t_begin
        self.trace_s, self.traced_inside = trace_s, traced_inside
        self.lower_s = 0.0


class _ThreadState(threading.local):
    """Where one thread stands between jax's events."""

    def __init__(self):
        self.depth = 0          # open traces: 1 is the outermost
        self.t_begin = 0.0      # the outermost's BEGIN
        self.inside = 0         # functions traced inside it so far
        self.stolen = 0.0       # lowered/obtained inside it, seconds
        self.lowering = 0       # open lowerings (their rules trace too)
        self.pending: Optional[_Pending] = None   # traced, at depth 0
        self.inner: Optional[_Pending] = None     # lowered inside a trace
        self.obtaining = False  # a backend_compile is open
        self.cache = "off"
        self.retrieval_s = 0.0


class ProgramLedger:
    """See the module's docstring.  ``count`` and ``obtain_s_total`` are
    plain attributes a hot path may read."""

    def __init__(self):
        self.count = 0              # executables obtained so far
        self.obtain_s_total = 0.0
        self._records: List[ProgramRecord] = []
        self._seq: Dict[str, int] = collections.Counter()
        self._owned: Dict[str, str] = {}
        self._lock = threading.Lock()
        self._tls = _ThreadState()
        self._installed = False

    # ---------------------------------------------------------- listeners
    def install(self) -> None:
        """Register the three listeners; a second call registers none."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        jax.monitoring.register_scalar_listener(self._on_scalar)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def uninstall(self) -> None:
        """Stop listening (tests); what was recorded stays."""
        with self._lock:
            if not self._installed:
                return
            self._installed = False
        jax.monitoring.unregister_scalar_listener(self._on_scalar)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_scalar(self, event, value, fun_name=None, **_):
        """jax's BEGIN marks."""
        if event == TRACE_EVENT:
            st = self._tls
            if st.depth:            # nested: counted, never timed
                st.depth += 1
                st.inside += 1
            elif not st.lowering:   # (a lowering rule's own traces are
                #                      the lowering's, and it is timed)
                st.depth, st.inside, st.stolen = 1, 0, 0.0
                st.t_begin = time.perf_counter()
        elif event == LOWER_EVENT:
            self._tls.lowering += 1
        elif event == OBTAIN_EVENT:
            st = self._tls
            st.obtaining, st.cache, st.retrieval_s = True, "off", 0.0

    def _on_duration(self, event, duration, fun_name=None, **_):
        if event == TRACE_EVENT:
            st = self._tls
            if st.depth > 1:
                st.depth -= 1
            elif st.depth == 1:
                st.depth = 0
                st.pending = _Pending(
                    fun_name or "?", st.t_begin,
                    max(duration - st.stolen, 0.0), st.inside)
            # depth 0: a trace that began before the ledger listened
        elif event == LOWER_EVENT:
            st = self._tls
            st.lowering = max(st.lowering - 1, 0)
            self._stage(fun_name, duration).lower_s += duration
        elif event == OBTAIN_EVENT:
            self._obtained(self._stage(fun_name, duration), duration)
        elif event == CACHE_RETRIEVAL_EVENT:
            st = self._tls
            if st.obtaining:
                st.retrieval_s = duration

    def _on_event(self, event, **_):
        """The persistent cache's unnamed events belong to the
        ``backend_compile`` open on this thread."""
        if event == CACHE_HIT_EVENT or event == CACHE_MISS_EVENT:
            st = self._tls
            if st.obtaining:
                st.cache = "hit" if event == CACHE_HIT_EVENT else "miss"

    def _stage(self, fun_name, duration) -> _Pending:
        """The pending program a lowering or an obtaining belongs to:
        the one this thread traced last, if the name is its; else one
        whose trace jax already had."""
        st, name = self._tls, _bare(fun_name)
        if st.depth:                # inside another program's trace
            st.stolen += duration
            if st.inner is None or st.inner.name != name:
                st.inner = _Pending(name)
            return st.inner
        if st.pending is None or st.pending.name != name:
            st.pending = _Pending(name)
        return st.pending

    def _obtained(self, p: _Pending, obtain_s: float) -> None:
        st = self._tls
        t_end = time.perf_counter()
        t_begin = p.t_begin if p.t_begin is not None \
            else t_end - obtain_s - p.lower_s
        cache, retrieval_s = st.cache, st.retrieval_s
        st.obtaining = False
        if st.depth:
            st.inner = None
        else:
            st.pending = None
        with self._lock:
            self._seq[p.name] += 1
            rec = ProgramRecord(
                name=p.name, seq=self._seq[p.name], t_begin=t_begin,
                t_end=t_end, trace_s=p.trace_s,
                traced_inside=p.traced_inside, lower_s=p.lower_s,
                obtain_s=obtain_s, cache=cache, retrieval_s=retrieval_s)
            self._records.append(rec)
            self.obtain_s_total += obtain_s
            self.count += 1         # last: a reader of ``count`` finds
                                    # the record it counts
        if _events.have_sinks():
            _events.emit(
                "program_obtained", name=rec.name, seq=rec.seq,
                trace_s=rec.trace_s, lower_s=rec.lower_s,
                obtain_s=rec.obtain_s, cache=rec.cache,
                traced_inside=rec.traced_inside)

    # ------------------------------------------------------------- claims
    def own(self, *names: str, layer: str) -> None:
        """Claim the traced functions ``names`` for the main path's
        ``layer``, where their ``jax.jit`` is made."""
        for name in names:
            self._owned[name] = layer

    def layer_of(self, name: str) -> Optional[str]:
        """The layer that claimed ``name``; None for an eager operation
        or a program of somebody else's."""
        return self._owned.get(name)

    # ------------------------------------------------------------ reading
    def records(self, since: Optional[float] = None,
                until: Optional[float] = None) -> List[ProgramRecord]:
        """The records with ``since <= t_end < until``, in the order
        they were obtained."""
        lo = float("-inf") if since is None else since
        hi = float("inf") if until is None else until
        with self._lock:
            return [r for r in self._records if lo <= r.t_end < hi]

    def records_from(self, count: int) -> List[ProgramRecord]:
        """The records obtained after ``count`` stood where the caller
        read it."""
        with self._lock:
            return self._records[count:]

    def table(self, since: Optional[float] = None,
              until: Optional[float] = None,
              top: Optional[int] = None) -> str:
        """One line a name, the costliest first; read a cold start off
        it (docs/observability.md "Programs obtained")."""
        return format_table(self.records(since, until), self.layer_of,
                            top=top)


def format_table(records: Iterable[ProgramRecord], layer_of,
                 top: Optional[int] = None) -> str:
    """``records`` by name (executables, trace, traced inside, lowering,
    obtaining, hits/misses, ``layer_of(name)`` or "other"), the
    costliest first; past ``top`` names the rest is one line."""
    rows: Dict[str, list] = {}
    for r in records:
        row = rows.setdefault(r.name, [0, 0.0, 0, 0.0, 0.0, 0, 0])
        row[0] += 1
        row[1] += r.trace_s
        row[2] += r.traced_inside
        row[3] += r.lower_s
        row[4] += r.obtain_s
        row[5] += r.cache == "hit"
        row[6] += r.cache == "miss"
    order = sorted(rows, key=lambda k: -(rows[k][1] + rows[k][3]
                                         + rows[k][4]))
    shown = order if top is None else order[:top]
    width = max([len(k) for k in shown] + [7])
    lines = [f"{'program':<{width}}  exec  trace_s  inside  lower_s  "
             f"obtain_s  hit/miss  layer"]
    for k in shown:
        n, tr, inside, lo, ob, hit, miss = rows[k]
        lines.append(
            f"{k:<{width}}  {n:>4}  {tr:>7.3f}  {inside:>6}  {lo:>7.3f}  "
            f"{ob:>8.3f}  {hit:>3}/{miss:<4}  {layer_of(k) or 'other'}")
    rest = order[len(shown):]
    if rest:
        n, tr, inside, lo, ob, hit, miss = (
            sum(rows[k][i] for k in rest) for i in range(7))
        lines.append(
            f"{f'({len(rest)} more)':<{width}}  {n:>4}  {tr:>7.3f}  "
            f"{inside:>6}  {lo:>7.3f}  {ob:>8.3f}  {hit:>3}/{miss:<4}")
    return "\n".join(lines)


# one a process: a reload of this module keeps the ledger it had
if "ledger" not in globals():
    ledger = ProgramLedger()
ledger.install()

install = ledger.install
uninstall = ledger.uninstall
own = ledger.own
layer_of = ledger.layer_of
records = ledger.records
table = ledger.table
