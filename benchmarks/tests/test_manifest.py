"""``BENCHMARK.json`` against the contract's limits, and the files each
of its entries needs."""

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["paths"] == ["benchmarks"]
    assert all(not w.startswith("/") and ".." not in w
               for w in MANIFEST["command"])
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= \
        max(len(CELLS) // 4, 1)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    layer = metric in MANIFEST["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if layer else {"bound"})
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = metric.get("workloads", CELLS)
    assert cells and set(cells) <= set(CELLS)
    if layer:
        moved = next(m for m in MANIFEST["end_to_end"]
                     if m["name"] == metric["moves"])
        assert set(cells) <= set(moved.get("workloads", CELLS))
        assert 1 <= len(metric["layer"]) <= 200
        if "roofline" in metric["name"] or "mfu" in metric["name"]:
            assert metric["unit"] == "%"
        with open(os.path.join(BENCH, "layer_metrics",
                               metric["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["name"] == metric["name"]
        assert (spec["unit"], spec["layer"], spec["moves"]) == (
            metric["unit"], metric["layer"], metric["moves"])
        module, _, func = spec["reader"].partition(".")
        sys.path.insert(0, BENCH)
        reader = __import__("readers." + module, fromlist=[func])
        assert callable(getattr(reader, func))
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1


def test_names_are_unique_and_setup_is_everywhere():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    setup = next(m for m in MANIFEST["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1
    for cell in CELLS:
        e2e = [m for m in MANIFEST["end_to_end"]
               if cell in m.get("workloads", CELLS)]
        per = [m for m in MANIFEST["per_layer"]
               if cell in m.get("workloads", CELLS)]
        assert len(e2e) >= 2 and len(per) >= 1


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    config = next(c for c in MANIFEST["configs"]
                  if c["name"] == cell["config"])
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"].startswith("benchmarks/")
    with open(os.path.join(ROOT, config["file"])) as f:
        held = json.load(f)
    assert held["reduced"] == config["reduced"] and "assumed" in held
    assert not any(k.endswith(("_dim", "_rank")) or k in (
        "n_embd", "n_inner", "n_head") for k in config["reduced"])
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] in ("train", "backlog", "openloop")
    assert os.path.exists(os.path.join(
        BENCH, "runners", traffic["runner"] + ".py"))


def test_run_py_names_no_cell_configuration_or_metric():
    with open(os.path.join(BENCH, "run.py")) as f:
        text = f.read()
    names = CELLS + [c["name"] for c in MANIFEST["configs"]] + [
        m["name"] for m in METRICS if m["name"] != "setup_s"] + [
        w["traffic"] for w in MANIFEST["workloads"]]
    assert [n for n in names if n in text] == []


def test_files_under_paths_are_named_from_the_allowed_characters():
    tracked = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard",
         "benchmarks"], cwd=ROOT, capture_output=True, text=True).stdout.split()
    assert tracked
    assert [p for p in tracked if not re.match(r"^[A-Za-z0-9_.\-/]+$", p)] == []


def test_run_py_refuses_the_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
