"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration (``benchmarks/configs/``) under a traffic mix
(``benchmarks/traffic/``).  This file resolves the cell by name to those
files and hands it to the runner the traffic file names
(``benchmarks/runners/``); with ``--trace 1`` the per-layer metrics are
read by the readers the files under ``benchmarks/layer_metrics/`` name
(``benchmarks/readers/``).  It holds no cell, configuration or metric
name itself: a new one of each is a new file and an entry in
``BENCHMARK.json``.

It runs on the machine it is started on, refuses any platform but
``tpu`` and any other number of chips than the cell asks for (exit code
other than 0, no result line), and prints as the LAST line of standard
output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``.  Earlier lines are
for people: the realised traffic, the set-up split, what compiled.
Every run also appends one line with all its values to
``chiprun_out/benchmarks/runs.jsonl`` (git-ignored).
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()     # before anything heavy is imported

import argparse
import collections
import contextlib
import glob
import importlib
import json
import os
import shutil
import sys
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, "chiprun_out", "benchmarks")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def seconds_before_import() -> float:
    """Process start to this module's first line (interpreter start-up),
    from the kernel's record of when the process began; 0 where that is
    not readable."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return max(age - (time.perf_counter() - _T_IMPORT), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


class CompileClock:
    """Executables jax obtained (an XLA compilation on a cold cache, a
    cache read on a warm one) per jitted function, and the seconds they
    took, from jax's own monitoring events (as ``chip_smoke.py`` counts
    them; copied, not imported)."""

    def __init__(self):
        import jax

        self.total = 0.0
        self.times: collections.Counter = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, fun_name=None, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.total += duration
            self.times[fun_name] += 1

    def snapshot(self) -> collections.Counter:
        return collections.Counter(self.times)


class Tracer:
    """A few seconds of profiler trace at the END of the window: it
    starts at the first boundary at or after ``start_at`` and stops at
    the first at or after ``stop_at`` (the window's closing boundary),
    so the cost of stopping (about 3 s of the host on the v5e machine;
    starting takes some 40 ms) falls outside the measured window.  In an
    open loop it falls into the drain and stretches the last requests'
    latencies: a traced run's end-to-end numbers are not the benchmark's,
    its per-layer numbers are.  ``stop_s`` is how long the stop blocked
    the host, so that the drain's deadline does not count it: the drain
    gets its grace in the server's own time.  ``t_stopped`` is the moment
    the stop was called, the end of the traced stretch."""

    def __init__(self, enabled: bool, directory: str):
        self.enabled, self.directory = enabled, directory
        self.start_at = self.stop_at = float("inf")
        self.t_started: Optional[float] = None
        self.t_stopped: Optional[float] = None
        self.stop_s = 0.0

    def arm(self, start_at: float, stop_at: float) -> None:
        self.start_at, self.stop_at = start_at, stop_at

    def _start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # spans, not every frame
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)

    def poll(self, now: float) -> None:
        """Call at a boundary of the system's own events."""
        if not self.enabled:
            return
        if self.t_started is None and now >= self.start_at:
            self._start()
            self.t_started = time.perf_counter()
        elif (self.t_started is not None and self.t_stopped is None
              and now >= self.stop_at):
            self.stop()

    def stop(self) -> None:
        if self.t_started is not None and self.t_stopped is None:
            import jax

            self.t_stopped = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - self.t_stopped

    def xplane(self) -> Optional[str]:
        found = glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        return found[0] if self.t_stopped is not None and found else None


class Run:
    """What a runner is handed: the cell's files, the seed, the clock
    of compilations, the set-up split and the tracer."""

    def __init__(self, *, cell: dict, config: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool, devices: list,
                 clock: CompileClock, t_start: float):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices, self.clock, self.t_start = devices, clock, t_start
        self.root = ROOT
        self.setup: Dict[str, float] = collections.OrderedDict()
        self.tracer = Tracer(trace, os.path.join(
            OUT_DIR, "trace", cell["name"]))
        self.hlo_texts: Dict[str, str] = {}   # program name -> compiled text
        self._scopes: Dict[str, Dict[str, str]] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one part of set-up."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add_setup(name, time.perf_counter() - t0)

    def add_setup(self, name: str, seconds: float) -> None:
        self.setup[name] = self.setup.get(name, 0.0) + seconds

    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace (free when none runs)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    @staticmethod
    def note(text: str) -> None:
        """A line for people, above the result line."""
        print(text, flush=True)

    def scopes(self, module: str) -> Dict[str, str]:
        """instruction name -> ``op_name`` of the compiled program the
        runner left under ``module`` (parsed once; empty without it)."""
        if module not in self._scopes:
            import trace_reduce

            self._scopes[module] = trace_reduce.hlo_scopes(
                self.hlo_texts.get(module, ""))
        return self._scopes[module]


# ---------------------------------------------------------------- files
def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def resolve(manifest: dict, workload: str):
    """(cell, config, traffic, directory) for a cell's name.  The
    configuration's file comes from its entry; its directory's parent is
    the directory whose ``traffic/`` and ``layer_metrics/`` the cell's
    other files are found in by name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in the manifest; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config_path = os.path.join(ROOT, entry["file"])
    directory = os.path.dirname(os.path.dirname(config_path))
    traffic = load_json(os.path.join(
        directory, "traffic", cell["traffic"] + ".json"))
    return cell, load_json(config_path), traffic, directory


def metrics_for(manifest: dict, section: str, workload: str) -> List[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports:
    those without a ``workloads`` key, and those that list it."""
    return [m for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]]


def read_layer_metrics(manifest: dict, workload: str, trace, counters: dict,
                       run: Run, metrics_dir: str) -> Dict[str, dict]:
    """Each per-layer metric of the cell through the reader its file
    names; a reader that finds nothing returns None and the metric is
    left out of the line."""
    out: Dict[str, dict] = {}
    for entry in metrics_for(manifest, "per_layer", workload):
        path = os.path.join(metrics_dir, entry["name"] + ".json")
        if not os.path.exists(path):
            run.note(f"per-layer metric {entry['name']}: no file {path}")
            continue
        spec = load_json(path)
        module, _, func = spec["reader"].partition(".")
        reader = getattr(importlib.import_module("readers." + module), func)
        value = reader(trace=trace, counters=counters,
                       params=spec.get("params", {}), run=run)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


# ------------------------------------------------------------------ run
def device_record(devices: list) -> dict:
    stats = [d.memory_stats() for d in devices]
    peak = (max(s["peak_bytes_in_use"] for s in stats)
            if all(s is not None for s in stats) else None)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(manifest: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True,
             t_start: Optional[float] = None) -> Optional[dict]:
    """Run one cell; returns the result object, or None (after a
    message on stderr) where the machine is not what the cell asks for."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell, config, traffic, directory = resolve(manifest, workload)
    metrics_dir = os.path.join(directory, "layer_metrics")

    # the compile cache: where the environment says, else one fixed
    # directory inside the checkout (the path is part of the cache's key)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    t0 = time.perf_counter()
    import jax

    if require_tpu:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"run.py: needs a TPU; jax found platform "
              f"{devices[0].platform!r} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r})", file=sys.stderr)
        return None
    if require_tpu and len(devices) != cell["chips"]:
        print(f"run.py: cell {workload} is laid out for {cell['chips']} "
              f"chip(s); jax found {len(devices)}", file=sys.stderr)
        return None
    clock = CompileClock()
    run = Run(cell=cell, config=config, traffic=traffic, seed=seed,
              seconds=seconds, trace=trace, devices=devices, clock=clock,
              t_start=t_start)
    run.add_setup("interpreter_and_imports", t0 - t_start)
    run.add_setup("backend_start", time.perf_counter() - t0)
    entries_before = len(os.listdir(cache_dir)) \
        if os.path.isdir(cache_dir) else 0
    run.note(f"cell {workload}: config {cell['config']}, traffic "
             f"{cell['traffic']} ({traffic['kind']}), {len(devices)} x "
             f"{devices[0].device_kind}; seed {seed}, {seconds} s, trace "
             f"{int(trace)}; compile cache {cache_dir} "
             f"({entries_before} entries)")

    runner = importlib.import_module("runners." + traffic["runner"])
    try:
        result = runner.run(run)
    finally:
        run.tracer.stop()

    setup_s = result["t_open"] - t_start
    accounted = sum(run.setup.values())
    run.add_setup("other", setup_s - accounted)
    in_window = {k: v for k, v in result["compiled_in_window"].items() if v}
    correct = bool(result["correct"]) and not in_window
    run.note("set-up split (s): " + ", ".join(
        f"{k} {v:.2f}" for k, v in run.setup.items())
        + f"; total {setup_s:.2f}; executables obtained "
        f"{sum(clock.times.values())} in {clock.total:.2f} s; compiled "
        f"inside the window: {in_window or 0}")
    for why in result.get("why_incorrect", []):
        run.note(f"NOT CORRECT: {why}")

    end_to_end = dict(result["end_to_end"], setup_s=setup_s)
    line = {"correct": correct, "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": {},
            "device": device_record(devices)}
    counters = dict(result.get("counters", {}))
    layer: Dict[str, dict] = {}
    if trace:
        import trace_reduce

        path = run.tracer.xplane()
        reduced = trace_reduce.Trace(trace_reduce.load_xplane(path)) \
            if path else None
        if reduced is not None and reduced.devices:
            line["device"]["busy_s"] = reduced.busy_s()
            line["device"]["window_s"] = reduced.window_s
            line["breakdown"] = {"device_ops": reduced.top_ops(10),
                                 "idle_gaps": reduced.idle_gaps(10)}
        layer = read_layer_metrics(manifest, workload, reduced, counters,
                                   run, metrics_dir)
        line["metrics"] = layer
    else:
        for entry in metrics_for(manifest, "end_to_end", workload):
            value = end_to_end.get(entry["name"])
            if value is not None:
                line["metrics"][entry["name"]] = {
                    "value": value, "unit": entry["unit"]}

    if require_tpu:             # the record of chip runs, not of CPU tests
        _append_record({
            "workload": workload, "seed": seed, "seconds": seconds,
            "trace": int(trace), "correct": correct,
            "attempted": line["attempted"], "failed": line["failed"],
            "end_to_end": end_to_end, "counters": counters,
            "per_layer": {k: v["value"] for k, v in layer.items()},
            "setup": dict(run.setup),
            "executables": sum(clock.times.values()),
            "compile_s": clock.total, "cache_entries_before": entries_before,
            "device": line["device"], "at": time.time()})
    return line


def _append_record(record: dict) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "runs.jsonl"), "a") as f:
        f.write(json.dumps(record) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    t_start = _T_IMPORT - seconds_before_import()
    if not os.path.isdir(os.path.join(ROOT, "apex_tpu")):
        print(f"run.py: the program (apex_tpu/) is not in {ROOT}; the "
              f"benchmark measures a checkout, not itself", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)            # this checkout's program
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    line = run_cell(manifest, args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start=t_start)
    if line is None:
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
