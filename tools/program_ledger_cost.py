"""What the ledger of programs costs (``apex_tpu/telemetry/programs.py``),
measured on the host it runs on:

    python tools/program_ledger_cost.py [--out chiprun_out/ledger_cost.json]

1. one listener call: a nested trace event (a BEGIN scalar and a
   duration) delivered through ``jax.monitoring`` inside an open trace,
   which is what a program's set-up fires thousands of; and one whole
   record (trace, lowering, obtaining);
2. the two attribute reads ``ContinuousBatcher.pump`` makes a turn
   (``ledger.count``, ``ledger.obtain_s_total``), twice a turn;
3. the sink ON: a ``MetricsLogger`` on the event bus through one tiny
   training and serving run (``chip_smoke.train`` / ``chip_smoke.serve``
   at 2 layers: the events are one a program, whatever its size) — the
   ``program_obtained`` events it wrote and the seconds it spent on
   them (``MetricsLogger.overhead_s``);

and prints the run's per-program table.  Host-clock numbers of the
machine it ran on; nothing here is a device metric.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import timeit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def listener_costs(n: int = 20000) -> dict:
    import jax

    from apex_tpu.telemetry.programs import (
        LOWER_EVENT, OBTAIN_EVENT, TRACE_EVENT, ledger,
    )

    scalar = jax.monitoring.record_scalar
    duration = jax.monitoring.record_event_duration_secs
    scalar(TRACE_EVENT, 0.0, fun_name="_cost_outer")
    t0 = time.perf_counter()
    for _ in range(n):
        scalar(TRACE_EVENT, 0.0, fun_name="_cost_nested")
        duration(TRACE_EVENT, 0.0, fun_name="_cost_nested")
    nested_s = (time.perf_counter() - t0) / n
    duration(TRACE_EVENT, 0.0, fun_name="_cost_outer")
    count0 = ledger.count
    t0 = time.perf_counter()
    for _ in range(n // 20):
        for event, name in ((TRACE_EVENT, "_cost_whole"),
                            (LOWER_EVENT, "jit(_cost_whole)"),
                            (OBTAIN_EVENT, "jit(_cost_whole)")):
            scalar(event, 0.0, fun_name=name)
            duration(event, 0.0, fun_name=name)
    record_s = (time.perf_counter() - t0) / (n // 20)
    assert ledger.count - count0 == n // 20
    reads = timeit.timeit(
        "ledger.count; ledger.obtain_s_total; ledger.count != 0",
        globals={"ledger": ledger}, number=1_000_000) / 1_000_000
    return {"nested_trace_event_us": 1e6 * nested_s,
            "whole_record_us": 1e6 * record_s,
            "pump_reads_us": 1e6 * reads}


def sink_on() -> dict:
    import jax

    import chip_smoke
    from apex_tpu.telemetry import events
    from apex_tpu.telemetry.metrics import MetricsLogger
    from apex_tpu.telemetry.programs import ledger
    from apex_tpu.transformer import parallel_state

    n = len(jax.devices())
    size = chip_smoke.Size(layers=2, hidden=128, heads=4, seq=64, vocab=512,
                           slots=2, page_size=8, requests=4, new_tokens=4)
    plan = chip_smoke.layout(n if size.heads % n == 0 else 1, size.heads)
    vocab = size.padded_vocab(plan["serve_tp"])
    out = tempfile.mkdtemp()
    path = os.path.join(out, "events.jsonl")
    logger = MetricsLogger(jsonl_path=path, console=False)
    clock = chip_smoke.CompileClock()
    count0, t0 = ledger.count, time.perf_counter()
    try:
        with events.sink(logger):
            trained = chip_smoke.train(
                size, tp=plan["train_tp"], vocab=vocab, clock=clock,
                on_tpu=False, metrics_jsonl=os.path.join(out, "train.jsonl"))
            chip_smoke.serve(size, trained["model"], trained["params"],
                             tp=plan["serve_tp"], clock=clock, on_tpu=False)
    finally:
        parallel_state.destroy_model_parallel()
    wall = time.perf_counter() - t0
    logger.close()
    with open(path) as f:
        written = [json.loads(line) for line in f]
    obtained = [r for r in written if r.get("event") == "program_obtained"]
    assert len(obtained) == ledger.count - count0, \
        (len(obtained), ledger.count - count0)
    print(ledger.table(since=t0, top=12))
    return {"run_wall_s": wall, "program_obtained_events": len(obtained),
            "sink_overhead_s": logger.overhead_s,
            "sink_us_per_event": 1e6 * logger.overhead_s
            / max(len(written), 1),
            "obtain_s": clock.total}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax

    result = {"platform": jax.devices()[0].platform,
              "device_kind": jax.devices()[0].device_kind}
    result.update(sink_on())
    result.update(listener_costs())
    print(json.dumps(result))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
