"""The runners end to end on the CPU, on the toy cells under ``cells/``
(a configuration, three traffic mixes and two per-layer metrics added as
files only: ``run.py`` is not edited for them).  Numbers from these runs
mean nothing and are checked for shape only."""

import json
import os

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


def test_train_cell_over_four_devices(bench, manifest):
    """dp 2 x tp 2 on four virtual CPU devices; run it with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (with one
    device the other tests run and this one is skipped)."""
    import jax

    if jax.device_count() != 4:
        pytest.skip("needs exactly four devices")
    line = _line(bench, manifest, "tiny-train-dp2tp2", True)
    assert line["device"]["count"] == 4
