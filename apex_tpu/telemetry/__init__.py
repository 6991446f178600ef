"""apex_tpu.telemetry — runtime metrics, events and phase traces.

The runtime half of the observability story (:mod:`apex_tpu.pyprof` is
the offline half: trace capture + XLA cost analysis).  Four modules:

- :mod:`~apex_tpu.telemetry.metrics` — :class:`MetricsLogger`
  (counters/gauges/timings/step scalars, process-0 JSONL sink with
  atomic appends, console sink) with **async scalar harvesting**:
  device scalars are held as unresolved ``jax.Array`` futures and
  resolved in one batched transfer at the flush cadence, removing the
  per-step ``float(loss)`` host sync from the trainers; plus
  :class:`StepStats` (live tokens/s + MFU from the same FLOP model the
  benchmarks report).
- :mod:`~apex_tpu.telemetry.events` — the subsystem event bus:
  StepGuard escalations, checkpoint save/restore/verify outcomes,
  AutoResume GC, watchdog stalls and per-bucket comm estimates all
  :func:`~apex_tpu.telemetry.events.emit` here; free when no sink
  listens.
- :mod:`~apex_tpu.telemetry.spans` — ``tlm.<phase>`` named-scope step
  segmentation for xprof, ``tlm.kernel.<name>`` kernel names,
  ``tlm.<name>`` host spans on the profiler's clock, and
  :class:`TraceTrigger` (touch-file / env armed mid-run xplane capture
  of K steps).
- :mod:`~apex_tpu.telemetry.programs` — the ledger of every executable
  jax traces, lowers and obtains in this process, by name and stage
  (:data:`~apex_tpu.telemetry.programs.ledger`, installed on import;
  :func:`~apex_tpu.telemetry.programs.own` claims the main path's
  names): what set-up is made of, and how a scheduler turn says that it
  recompiled.

``tools/metrics_report.py`` turns the JSONL stream into a run summary;
the workflow is documented in docs/observability.md.

:mod:`~apex_tpu.telemetry.events` loads eagerly (it is stdlib-only and
the subsystems import it at module top); the jax-importing halves load
lazily, mirroring the ``apex_tpu`` package pattern.
"""

from apex_tpu.telemetry import events  # noqa: F401  (stdlib-only)

_LAZY_ATTRS = {
    "metrics": "apex_tpu.telemetry.metrics",
    "spans": "apex_tpu.telemetry.spans",
    "programs": "apex_tpu.telemetry.programs",
    "MetricsLogger": "apex_tpu.telemetry.metrics",
    "StepStats": "apex_tpu.telemetry.metrics",
    "transformer_flops_per_token": "apex_tpu.telemetry.metrics",
    "device_peak_flops": "apex_tpu.telemetry.metrics",
    "phase": "apex_tpu.telemetry.spans",
    "kernel_name": "apex_tpu.telemetry.spans",
    "host_span": "apex_tpu.telemetry.spans",
    "PHASES": "apex_tpu.telemetry.spans",
    "TraceTrigger": "apex_tpu.telemetry.spans",
    "emit": "apex_tpu.telemetry.events",
    "add_sink": "apex_tpu.telemetry.events",
    "remove_sink": "apex_tpu.telemetry.events",
    "ring_wire_bytes": "apex_tpu.telemetry.events",
}

__all__ = ["events"] + sorted(_LAZY_ATTRS)


def __getattr__(name):
    if name in _LAZY_ATTRS:
        import importlib

        mod = importlib.import_module(_LAZY_ATTRS[name])
        val = mod if name in ("metrics", "spans", "programs") \
            else getattr(mod, name)
        globals()[name] = val
        return val
    raise AttributeError(
        f"module 'apex_tpu.telemetry' has no attribute {name!r}"
    )
