"""Phase-segmented traces: device scopes, host spans and an on-demand
trigger.

Three parts, one channel (the jax profiler's trace):

**Phase spans** — :func:`phase` wraps a region of a (traced) step
function in ``jax.named_scope`` under a common ``tlm.<name>`` prefix,
so every op the region emits carries the phase in its HLO metadata and
xprof/tensorboard group the device timeline by phase instead of by
mangled fusion names.  The canonical phases (:data:`PHASES`) are the
step anatomy the example trainers annotate: ``data`` (batch selection),
``fwd_bwd`` (loss + grads), ``grad_sync`` (the DDP/Reducer collectives
— :class:`~apex_tpu.parallel.distributed.Reducer` annotates its own),
``optimizer`` (the parameter update) and ``checkpoint`` (host-side
save).  Being ``jax.named_scope``, the spans cost nothing at runtime —
they exist only in compile-time metadata (the same mechanism
:func:`apex_tpu.pyprof.annotate` uses; this module adds the shared
naming convention and the mid-run capture below).

**Kernel names** — :func:`kernel_name` is the same mechanism one level
down: every Mosaic ``pallas_call`` takes ``tlm.kernel.<name>`` as its
``name=``, which scopes the call and names its instruction, so a
reduction finds the kernel by name after a refactor changes its
operands.

**Host spans** — :func:`host_span` is the host-side half:
``jax.profiler.TraceAnnotation("tlm.<name>", **stats)``.  A compiled
function's CALL carries no scope (a named scope names operations while
a function is traced and does nothing at a call), so what the host does
between and around the device's programs — admit, dispatch, harvest,
commit (:mod:`apex_tpu.serving.serve`) — is marked with these.  They
land in the same ``.xplane.pb`` as the device's operations, on one
clock, whenever a profiler session runs (a :class:`TraceTrigger`
capture, :func:`apex_tpu.pyprof.trace`, a benchmark's ``--trace 1``);
the session IS the switch.  With none a span is a TraceMe check, about
a microsecond.

**On-demand trace trigger** — :class:`TraceTrigger` answers "the run
is live and slow *now*; get me a trace without restarting".  The
training loop calls :meth:`TraceTrigger.poll` once per step (a
host-side ``os.path`` check, amortized by ``poll_every``); arming it —
by touching a file, or exporting ``APEX_TPU_TRACE_DIR`` before launch
— captures an xplane window of the next K steps with the same
``jax.profiler.start_trace``/``stop_trace`` pair
:func:`apex_tpu.pyprof.trace` wraps, then disarms.  Re-touching the
file captures another window; each capture lands in its own
``step<N>`` subdirectory, ready for tensorboard's profile plugin.
"""

from __future__ import annotations

import contextlib
import logging
import os
from typing import Iterator, Optional

import jax

from apex_tpu.telemetry import events as _events

__all__ = ["PHASES", "phase", "kernel_name", "host_span", "TraceTrigger"]

logger = logging.getLogger("apex_tpu.telemetry")

#: The step-anatomy phases the example trainers annotate.
#: ``param_gather`` is the ZeRO-3 gather-on-use weight all-gather
#: (apex_tpu/parallel/zero3.py) — present only under ``shard_params``.
#: ``prefill``/``decode`` are the SERVING step anatomy, opened inside
#: the traced bodies of ``GPTModel.decode_fns`` (``_prefill``/``_chunk``;
#: ``_decode``/``_spec``/``_spec_tree``): prompt ingestion through the
#: training attention ladder, and the fused per-token
#: cache-attend-sample step.
PHASES = ("data", "param_gather", "fwd_bwd", "grad_sync", "optimizer",
          "checkpoint", "prefill", "decode")

#: Every span shares this prefix so a trace viewer filter of "tlm."
#: shows exactly the phase segmentation.
PHASE_PREFIX = "tlm."

#: Kernel names sit under this prefix: ``tlm.kernel.fmha_mid.fwd``.
KERNEL_PREFIX = PHASE_PREFIX + "kernel."


@contextlib.contextmanager
def phase(name: str) -> Iterator[None]:
    """Annotate a region as one step phase (``tlm.<name>`` named
    scope).  It names the operations emitted while the region is TRACED
    and does nothing around a call of an already-compiled function
    (mark that with :func:`host_span`).  Scopes nest
    (``tlm.fwd_bwd/tlm.attention``) like any ``jax.named_scope``; as a
    decorator (``@phase("decode")``) it scopes a whole traced body and
    keeps the function's name."""
    with jax.named_scope(PHASE_PREFIX + name):
        yield


def kernel_name(name: str) -> str:
    """``tlm.kernel.<name>``, for a Mosaic kernel call's ``name=``::

        out = pl.pallas_call(kernel, ..., name=kernel_name("fmha_mid.fwd"))

    ``pallas_call`` opens a named scope of that name around the call it
    emits and names the compiled instruction after it, so the kernel is
    found by name in the compiled text and the device trace — a
    ``custom_vjp`` backward under its own, a rematerialised forward
    under ``rematted_computation/tlm.kernel.<name>``."""
    return KERNEL_PREFIX + name


def host_span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span ``tlm.<name>`` in the profiler's trace, for a
    ``with`` statement.  ``stats`` are values already at hand (ints, an
    id as given) and come back as the event's stats; one known only at
    the end is added inside the block with ``span.set_metadata(k=v)``.
    Nothing is recorded, formatted or kept when no session runs."""
    return jax.profiler.TraceAnnotation(PHASE_PREFIX + name, **stats)


class TraceTrigger:
    """Capture an xplane window of K steps mid-run, on demand.

    Parameters
    ----------
    trace_dir:
        Where captures land (each in a ``step<N>`` subdirectory).
        Defaults to ``$APEX_TPU_TRACE_DIR`` when set — which ALSO arms
        the trigger once at startup, so exporting the variable before
        launch captures the run's first K steps with no code change.
    steps:
        Steps per capture window (``$APEX_TPU_TRACE_STEPS`` overrides).
    trigger_file:
        Touch this path mid-run to arm a capture; the trigger consumes
        (deletes) it on arming, so touching it again captures another
        window.  Defaults to ``$APEX_TPU_TRACE_TOUCH`` when set, else
        ``<trace_dir>/TRACE_REQUEST`` once a trace_dir is known.  If
        the touched file's first line names a directory, the capture
        goes there instead (steer one capture without re-launching).
    poll_every:
        Check the touch-file every N ``poll`` calls (the only per-step
        cost is this modulo when idle).

    Wire it into a loop::

        trig = TraceTrigger(trace_dir="/tmp/run_traces")
        for i in range(steps):
            out = step(...)
            trig.poll(i)

    ``poll`` starts the profiler *between* steps, so a window covers
    whole dispatched steps; :meth:`close` stops a capture the loop's
    end would otherwise truncate.
    """

    def __init__(
        self,
        trace_dir: Optional[str] = None,
        steps: Optional[int] = None,
        trigger_file: Optional[str] = None,
        poll_every: int = 1,
    ):
        if poll_every < 1:
            raise ValueError(f"poll_every must be >= 1, got {poll_every}")
        env_dir = os.environ.get("APEX_TPU_TRACE_DIR")
        self.trace_dir = trace_dir or env_dir
        self.steps = int(
            steps if steps is not None
            else os.environ.get("APEX_TPU_TRACE_STEPS", "4")
        )
        if self.steps < 1:
            raise ValueError(f"steps must be >= 1, got {self.steps}")
        self.trigger_file = trigger_file or os.environ.get(
            "APEX_TPU_TRACE_TOUCH"
        ) or (os.path.join(self.trace_dir, "TRACE_REQUEST")
              if self.trace_dir else None)
        if self.trigger_file:
            # the arming mechanism must exist to be touchable: create
            # the directory the touch-file lives in (best-effort — a
            # read-only location just disables mid-run arming)
            d = os.path.dirname(self.trigger_file)
            if d:
                try:
                    os.makedirs(d, exist_ok=True)
                except OSError as e:
                    logger.warning(
                        "trace trigger dir %s not creatable (%s); "
                        "touch-file arming disabled", d, e)
                    self.trigger_file = None
        self.poll_every = poll_every
        self._polls = 0
        self._armed_by_env = env_dir is not None
        self._capturing_dir: Optional[str] = None
        self._remaining = 0
        self.captures = 0

    # ------------------------------------------------------------ helpers
    def _consume_touch(self) -> Optional[str]:
        """If the touch-file exists: read an optional dir override from
        it, delete it (re-touch = re-arm), return the target dir."""
        tf = self.trigger_file
        if not tf or not os.path.exists(tf):
            return None
        target = None
        try:
            with open(tf) as f:
                first = f.readline().strip()
            if first:
                target = first
        except OSError:
            pass
        try:
            os.remove(tf)
        except OSError as e:
            # cannot consume it -> would re-trigger every window; warn
            # and fall through (the capture itself still proceeds)
            logger.warning("could not consume trace trigger %s: %s", tf, e)
        return target or self.trace_dir or "/tmp/apex_tpu_trace"

    def _start(self, target: str, step: int) -> None:
        out = os.path.join(target, f"step{step}")
        try:
            jax.profiler.start_trace(out)
        except Exception as e:  # an already-active trace, bad dir, ...
            logger.warning("trace trigger could not start capture: %s", e)
            return
        self._capturing_dir = out
        self._remaining = self.steps
        logger.info("trace trigger: capturing %d steps to %s",
                    self.steps, out)
        _events.emit("trace_start", dir=out, step=step,
                     window=self.steps)

    def _stop(self, step: int) -> None:
        out, self._capturing_dir = self._capturing_dir, None
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            logger.warning("trace trigger could not stop capture: %s", e)
            return
        self.captures += 1
        logger.info("trace trigger: captured %s", out)
        _events.emit("trace_captured", dir=out, step=step,
                     window=self.steps)

    # -------------------------------------------------------------- poll
    @property
    def capturing(self) -> bool:
        return self._capturing_dir is not None

    def poll(self, step: int) -> bool:
        """Advance the trigger one step; returns True while a capture
        window is open.  Call once per training step, after the step's
        dispatch."""
        if self._capturing_dir is not None:
            self._remaining -= 1
            if self._remaining <= 0:
                self._stop(step)
            return self._capturing_dir is not None
        self._polls += 1
        armed_dir: Optional[str] = None
        if self._armed_by_env:
            # env arming is one-shot: the variable cannot change
            # mid-run, so it means "capture the first window"
            self._armed_by_env = False
            armed_dir = self.trace_dir
        elif self._polls % self.poll_every == 0:
            armed_dir = self._consume_touch()
        if armed_dir:
            self._start(armed_dir, step)
        return self._capturing_dir is not None

    def close(self) -> None:
        """Stop an in-flight capture (call when the loop ends)."""
        if self._capturing_dir is not None:
            self._stop(step=-1)
