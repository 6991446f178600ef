"""The runners end to end on the CPU, on the toy cells under ``cells/``
(a configuration, three traffic mixes and two per-layer metrics added as
files only: ``run.py`` is not edited for them).  Numbers from these runs
mean nothing and are checked for shape only."""

import copy
import json
import os
import shutil
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def bench():
    import run as bench      # benchmarks/run.py, by conftest's sys.path

    return bench


@pytest.fixture(scope="module")
def manifest(bench):
    return bench.load_json(os.path.join(HERE, "cells", "manifest.json"))


def _line(bench, manifest, cell, trace):
    line = bench.run_cell(manifest, cell, 3000000019, 1.0, trace,
                          require_tpu=False)
    json.dumps(line)                                    # one JSON object
    assert {"correct", "attempted", "failed", "metrics", "device"} <= \
        set(line)
    assert set(line) <= {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    return line


def test_train_cell(bench, manifest):
    line = _line(bench, manifest, "tiny-train", False)
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert line["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0


def test_backlog_cell_and_its_layer_metrics(bench, manifest):
    line = _line(bench, manifest, "tiny-backlog", False)
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    traced = _line(bench, manifest, "tiny-backlog", True)
    # the counter is read; the device-trace reader finds no TPU plane in
    # a CPU trace, returns nothing, and the metric is left out
    assert set(traced["metrics"]) == {"tiny.slots_live_mean"}
    assert 0 < traced["metrics"]["tiny.slots_live_mean"]["value"] <= 4


def test_openloop_cell(bench, manifest):
    line = _line(bench, manifest, "tiny-openloop", False)
    assert set(line["metrics"]) == {"ttft_p90_s", "tpot_p50_ms", "setup_s"}
    assert line["attempted"] == 8                       # round(8/s x 1 s)
    assert line["metrics"]["ttft_p90_s"]["value"] > 0


# A traced open loop whose profiler stop blocks the host past the grace:
# one slot and outputs of 40-48 tokens at 32 requests/s put the server
# behind at the close, so measured requests are still queued when the
# stop returns and the drain needs more than one pump after it.
BLOCK_S = 6.0
BEHIND = {"slots": 1, "rate_per_s": 32.0, "grace_seconds": 5.0,
          "prompt": {"dist": "lognormal", "median": 6, "sigma": 0.3,
                     "lo": 4, "hi": 8},
          "output": {"dist": "loguniform", "lo": 40, "hi": 48}}


def _behind_at_close(bench, manifest, tmp_path):
    """The manifest with tiny-openloop's traffic copied under
    ``tmp_path`` and changed by ``BEHIND`` (the committed files are
    left as they are).  The configuration is copied beside it because
    ``run.resolve`` finds a cell's traffic next to its configuration's
    directory."""
    manifest = copy.deepcopy(manifest)
    entry = next(c for c in manifest["configs"] if c["name"] == "tiny-gpt")
    for sub in ("configs", "traffic"):
        (tmp_path / sub).mkdir()
    shutil.copy(os.path.join(bench.ROOT, entry["file"]),
                tmp_path / "configs" / "tiny-gpt.json")
    entry["file"] = str(tmp_path / "configs" / "tiny-gpt.json")
    traffic = bench.load_json(
        os.path.join(HERE, "cells", "traffic", "tiny-openloop.json"))
    traffic.update(BEHIND)
    (tmp_path / "traffic" / "tiny-openloop.json").write_text(
        json.dumps(traffic))
    return manifest


@pytest.mark.parametrize("trace", [False, True],
                         ids=["untraced", "traced-stop-blocks-past-grace"])
def test_openloop_drain_does_not_count_the_trace_stop(
        bench, manifest, tmp_path, monkeypatch, trace):
    """The drain gets ``grace_seconds`` of the server's own time: the
    seconds ``stop_trace`` blocks the host are not counted.  Untraced,
    nothing is stopped and the committed cell reads as before; traced,
    a stop that blocks longer than the grace still ends with every
    measured request served, where a deadline of close + grace would
    have cut the queued ones off."""
    import jax

    import runners.serve as serve_runner

    made = {}
    for owner, name in ((bench, "Tracer"), (serve_runner, "Driver")):
        init = getattr(owner, name).__init__

        def record(self, *a, _init=init, _name=name, **k):
            _init(self, *a, **k)
            made[_name] = self

        monkeypatch.setattr(getattr(owner, name), "__init__", record)
    stop = jax.profiler.stop_trace

    def blocking_stop():
        stop()
        time.sleep(BLOCK_S)

    monkeypatch.setattr(jax.profiler, "stop_trace", blocking_stop)
    if not trace:
        line = _line(bench, manifest, "tiny-openloop", False)
        assert line["attempted"] == 8 and line["failed"] == 0
        assert made["Tracer"].stop_s == 0.0
        return
    line = _line(bench, _behind_at_close(bench, manifest, tmp_path),
                 "tiny-openloop", True)
    tracer, drv = made["Tracer"], made["Driver"]
    assert tracer.stop_s >= BLOCK_S
    assert line["attempted"] == 32 and line["failed"] == 0
    # the scenario holds: the stop came at the close, and requests were
    # still served after the first boundary past close + grace, where the
    # deadline without the stop's seconds would have ended the drain
    t_close, grace = tracer.stop_at, BEHIND["grace_seconds"]
    assert tracer.t_stopped >= t_close
    cut = next(t for t, _ in drv.boundaries if t >= t_close + grace)
    assert max(drv.t_last.values()) > cut


def test_train_cell_over_four_devices(bench, manifest):
    """dp 2 x tp 2 on four virtual CPU devices; run it with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (with one
    device the other tests run and this one is skipped)."""
    import jax

    if jax.device_count() != 4:
        pytest.skip("needs exactly four devices")
    line = _line(bench, manifest, "tiny-train-dp2tp2", True)
    assert line["device"]["count"] == 4
