"""The readers over the program's ledger of executables
(``readers/programs.py``) and over the key-block counts of its
``dispatch_prefill`` spans (``readers/flash_blocks.py``): on hand-made
records and spans with known answers, and once end to end on the CPU —
the tiny training and backlog cells, traced, with the four ``programs.*``
entries appended to the rehearsal manifest in memory."""

import copy
import os
import types

import pytest

from readers import flash_blocks, program_spans as ps
from readers import programs as reader

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER = "programs (jit trace, lowering, executables)"
MS = 1e6    # ns


def _record(programs, name, t_end, *, trace_s=0.0, lower_s=0.0,
            obtain_s=0.0, cache="off"):
    return programs.ProgramRecord(
        name=name, seq=1, t_begin=t_end - trace_s - lower_s - obtain_s,
        t_end=t_end, trace_s=trace_s, traced_inside=0, lower_s=lower_s,
        obtain_s=obtain_s, cache=cache, retrieval_s=0.0)


@pytest.fixture()
def hand_made(monkeypatch):
    """A ledger of its own with six records around a run that started at
    t=100 and whose trace started at t=200; ``_decode`` and ``_chunk``
    are claimed.  Returns a maker of runs over it."""
    from apex_tpu.telemetry import programs

    ledger = programs.ProgramLedger()       # not installed: hears nothing
    ledger.own("_decode", "_chunk", layer="serving steps")
    ledger._records.extend([
        _record(programs, "_decode", 99.0, trace_s=50.0),       # before
        _record(programs, "_decode", 100.0, trace_s=1.0, lower_s=2.0,
                obtain_s=4.0, cache="miss"),                    # at t_start
        _record(programs, "weights", 150.0, trace_s=8.0, obtain_s=16.0,
                cache="hit"),                                   # harness's
        _record(programs, "_chunk", 160.0, trace_s=0.5, lower_s=0.25,
                obtain_s=0.125, cache="hit"),
        _record(programs, "concatenate", 199.0, obtain_s=32.0),  # eager
        _record(programs, "_chunk", 200.0, trace_s=64.0, obtain_s=64.0,
                cache="miss"),                  # at the tracer's start
    ])
    fake = types.SimpleNamespace(ledger=ledger, layer_of=ledger.layer_of,
                                 format_table=programs.format_table)
    monkeypatch.setattr(reader, "_ledger", lambda: fake)

    def make(t_started=200.0):
        notes = []
        return types.SimpleNamespace(
            t_start=100.0, note=notes.append, notes=notes,
            tracer=types.SimpleNamespace(t_started=t_started))

    return make


def test_set_up_is_cut_at_t_start_and_at_the_tracer_s_start(hand_made):
    run = hand_made()
    recs = reader.setup_records(run)
    assert [(r.name, r.t_end) for r in recs] == [
        ("_decode", 100.0), ("weights", 150.0), ("_chunk", 160.0),
        ("concatenate", 199.0)]
    assert reader.executables(None, {}, {}, run) == 4
    # the table is printed once, however many readers ask
    reader.obtain_s(None, {}, {}, run)
    assert len(run.notes) == 1
    assert "4 executables, 2 of them the program's own" in run.notes[0]
    assert "after the trace began: 1 (_chunk)" in run.notes[0]
    assert "serving steps" in run.notes[0] and "other" in run.notes[0]


def test_without_a_trace_set_up_runs_to_now(hand_made, monkeypatch):
    monkeypatch.setattr(reader.time, "perf_counter", lambda: 1000.0)
    run = hand_made(t_started=None)
    assert reader.executables(None, {}, {}, run) == 5


def test_own_records_are_summed_and_the_harness_s_left_out(hand_made):
    run = hand_made()
    assert reader.trace_lower_s(None, {}, {}, run) == 1.0 + 2.0 + 0.5 + 0.25
    assert reader.obtain_s(None, {}, {}, run) == 4.0 + 0.125


def test_hit_share_counts_every_record_that_asked_the_cache(hand_made):
    run = hand_made()
    # miss, hit, hit; the eager one never asked
    assert reader.cache_hit_share(None, {}, {}, run) == \
        pytest.approx(100.0 * 2 / 3)


def test_no_cache_in_use_gives_no_share_not_zero(hand_made):
    from apex_tpu.telemetry import programs

    run = hand_made()
    run.program_records = run.program_own = [
        _record(programs, "_decode", 120.0, obtain_s=1.0)]
    assert reader.cache_hit_share(None, {}, {}, run) is None
    assert reader.executables(None, {}, {}, run) == 1
    assert reader.obtain_s(None, {}, {}, run) == 1.0


def test_a_program_without_the_ledger_gives_every_reader_nothing(
        monkeypatch):
    monkeypatch.setattr(reader, "_ledger", lambda: None)
    run = types.SimpleNamespace(
        t_start=0.0, note=lambda text: pytest.fail("nothing to note"),
        tracer=types.SimpleNamespace(t_started=None))
    for read in (reader.trace_lower_s, reader.obtain_s, reader.executables,
                 reader.cache_hit_share):
        assert read(None, {}, {}, run) is None


def test_the_real_module_is_found_and_a_missing_one_is_not_an_error(
        monkeypatch):
    from apex_tpu.telemetry import programs

    assert reader._ledger() is programs
    import builtins

    real = builtins.__import__

    def without(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "apex_tpu.telemetry" and "programs" in (fromlist or ()):
            raise ImportError("the parent has no such module")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", without)
    assert reader._ledger() is None


# ------------------------------------------------------- key-block counts
def _prefill(start, **stats):
    return [ps.PREFIX + "dispatch_prefill", start * MS, 1 * MS,
            dict(uid="a", slot=0, prompt_tokens=9, **stats)]


def _block_run(raw, path="somewhere.xplane.pb"):
    run = types.SimpleNamespace(
        tracer=types.SimpleNamespace(xplane=lambda: path))
    run.flash_block_spans = ps.nest(raw)
    return run


def test_blocks_run_share_sums_before_it_divides():
    run = _block_run([
        _prefill(10, chunk=0, k_blocks_run=1, k_blocks_extent=1),
        _prefill(20, chunk=1, k_blocks_run=3, k_blocks_extent=4),
        _prefill(30, chunk=2, k_blocks_run=6, k_blocks_extent=15),
        _prefill(40, chunk=-1)])        # a monolithic prefill: no counts
    assert flash_blocks.run_share(None, {}, {}, run) == \
        pytest.approx(100.0 * 10 / 20)


def test_blocks_outside_the_traced_stretch_are_left_out():
    run = _block_run([
        _prefill(10, chunk=0, k_blocks_run=1, k_blocks_extent=1),
        _prefill(60, chunk=1, k_blocks_run=1, k_blocks_extent=4)])
    trace = types.SimpleNamespace(host_spans=[["bench.pump", 50 * MS, 50 * MS]],
                                  t0=50 * MS, t1=100 * MS)
    assert flash_blocks.run_share(trace, {}, {}, run) == pytest.approx(25.0)


def test_no_span_with_the_counts_gives_nothing():
    assert flash_blocks.run_share(
        None, {}, {}, _block_run([_prefill(10, chunk=-1)])) is None
    assert flash_blocks.run_share(None, {}, {}, _block_run([])) is None
    no_trace = types.SimpleNamespace(
        tracer=types.SimpleNamespace(xplane=lambda: None))
    assert flash_blocks.run_share(None, {}, {}, no_trace) is None


# ------------------------------------------------------------- end to end
@pytest.fixture()
def empty_compile_cache(tmp_path):
    """A persistent cache that holds nothing, as the chip run's cold
    one (off the chip ``run.py`` sets none): every program is a miss and
    is written, none is read back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (str(tmp_path), 0.0, -1)):
        jax.config.update(n, v)
    try:
        yield
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-backlog"])
def test_a_traced_cpu_rehearsal_yields_all_four(cell, monkeypatch,
                                                empty_compile_cache):
    """The ledger is read in the benchmark's own process; what it counts
    is what the harness's own clock of compilations counted."""
    import run as bench
    from apex_tpu.transformer import parallel_state

    manifest = copy.deepcopy(bench.load_json(
        os.path.join(HERE, "cells", "manifest.json")))
    for name, unit, better in (("trace_lower_s", "s", "lower"),
                               ("obtain_s", "s", "lower"),
                               ("executables", "count", "lower"),
                               ("cache_hit_share", "%", "higher")):
        manifest["per_layer"].append({
            "name": "tiny.programs." + name, "unit": unit, "better": better,
            "source": "program_counter", "layer": LAYER, "moves": "setup_s",
            "workloads": ["tiny-train", "tiny-backlog"]})
    runs = []

    class Kept(bench.Run):
        def __init__(self, **kw):
            super().__init__(**kw)
            runs.append(self)

    monkeypatch.setattr(bench, "Run", Kept)
    if parallel_state.model_parallel_is_initialized():
        parallel_state.destroy_model_parallel()     # an earlier cell's
    try:
        line = bench.run_cell(manifest, cell, 3000000019, 1.0, True,
                              require_tpu=False)
    finally:
        if parallel_state.model_parallel_is_initialized():
            parallel_state.destroy_model_parallel()
    (run,) = runs
    got = {k[len("tiny.programs."):]: v["value"]
           for k, v in line["metrics"].items()
           if k.startswith("tiny.programs.")}
    assert line["correct"] is True
    assert set(got) == {"trace_lower_s", "obtain_s", "executables",
                        "cache_hit_share"}
    # cold: at most a few one-operation programs, made twice to the
    # letter, are read back from what this very run wrote
    assert 0.0 <= got["cache_hit_share"] <= 25.0
    assert got["executables"] == sum(run.clock.times.values()) > 0
    assert got["trace_lower_s"] > 0 and got["obtain_s"] > 0
    setup_s = sum(run.setup.values())
    assert got["trace_lower_s"] + got["obtain_s"] < setup_s
    own = "train_step" if cell == "tiny-train" else "_decode"
    assert any(r.name == own for r in run.program_records)
