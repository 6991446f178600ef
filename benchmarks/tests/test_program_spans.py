"""The readers over the program's ``tlm.serve.*`` host spans
(``readers/program_spans.py``) on a hand-built structure with known
answers, and once end to end on the CPU: the tiny backlog cell, traced,
with one more per-layer metric than the rehearsal manifest lists."""

import copy
import os
import types

import pytest

import trace_reduce as tr
from readers import program_spans as ps

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6    # ns


def _span(name, start, dur, **stats):
    return [ps.PREFIX + name, start * MS, dur * MS, stats]


def _op(name, start, dur):
    return {"name": name, "opcode": "fusion", "shape": "f32[8]",
            "operands": 1, "target": "", "start": start * MS,
            "dur": dur * MS}


RAW_SPANS = [
    # turn 0 (0..1000 ms): admits request "a" (prefill enqueued at 10),
    # enqueues two decode steps, waits 800 ms in the harvest, commits
    _span("pump", 0, 1000, turn=0, queued=1, live_slots=0),
    _span("admit", 0, 30, admitted=1, backpressured=0),
    _span("dispatch_prefill", 10, 5, uid="a", slot=0, prompt_tokens=9,
          chunk=-1),
    _span("dispatch_decode", 40, 10, step=0, live_slots=1),
    _span("dispatch_decode", 50, 10, step=1, live_slots=1),
    _span("harvest", 100, 800, steps=2, firsts=1),
    _span("commit", 900, 50, tokens=3),
    _span("first_token", 910, 1, uid="a", slot=0),
    _span("retire", 950, 20, retired=0),
    # turn 1 (1100..1400 ms): nothing admitted, one step, a short harvest
    _span("pump", 1100, 300, turn=1, queued=0, live_slots=1),
    _span("admit", 1100, 10, admitted=0, backpressured=0),
    _span("dispatch_decode", 1120, 10, step=2, live_slots=1),
    _span("harvest", 1150, 200, steps=1, firsts=0),
    _span("commit", 1350, 20, tokens=1),
    _span("retire", 1380, 10, retired=1),
]


@pytest.fixture()
def spans():
    return ps.nest(RAW_SPANS)


@pytest.fixture()
def trace():
    """Chip 0 runs a prefill 60..110 ms and decode steps 110..400,
    420..700 (turn 0) and 1130..1300 (turn 1) inside a 0..1500 ms
    window of the benchmark's own spans."""
    ops = [_op("prefill.1", 60, 50), _op("decode.2", 110, 290),
           _op("decode.3", 420, 280), _op("decode.4", 1130, 170)]
    return tr.Trace({
        "devices": [{"name": "/device:TPU:0", "ops": ops, "async": [],
                     "modules": [["jit__prefill", 60 * MS, 50 * MS],
                                 ["jit__decode", 110 * MS, 290 * MS],
                                 ["jit__decode", 420 * MS, 280 * MS],
                                 ["jit__decode", 1130 * MS, 170 * MS]]}],
        "host_spans": [["bench.pump", 0, 1000 * MS],
                       ["bench.books", 1000 * MS, 100 * MS],
                       ["bench.pump", 1100 * MS, 400 * MS]]})


def test_spans_nest_and_self_time_leaves_out_children(spans):
    by = {(s.name, s.start): s for s in spans}
    pump0, admit0 = by["pump", 0], by["admit", 0]
    assert by["dispatch_prefill", 10 * MS].parent is admit0
    assert admit0.parent is pump0 and pump0.parent is None
    assert by["first_token", 910 * MS].parent is by["commit", 900 * MS]
    assert by["first_token", 910 * MS].under("pump") is pump0
    assert admit0.self_ns == pytest.approx(25 * MS)          # 30 - 5
    # a turn's children and its own self time make up its duration
    for pump in (pump0, by["pump", 1100 * MS]):
        children = [s for s in spans if s.parent is pump]
        assert pump.self_ns + sum(c.dur for c in children) == \
            pytest.approx(pump.dur)
    assert pump0.self_ns == pytest.approx((1000 - 30 - 20 - 800 - 50 - 20)
                                          * MS)


def test_host_time_per_pump_is_the_turn_minus_its_harvests(spans):
    a = ps.analyse(spans, None)
    assert a.turns == 2
    assert a.pump_ms == pytest.approx((1000 + 300) / 2)
    assert a.host_ms == pytest.approx((200 + 100) / 2)
    assert a.self_ms["harvest"] == pytest.approx((800 + 200) / 2)
    assert sum(a.self_ms.values()) == pytest.approx(a.pump_ms)
    assert a.idle_ms is None and a.requests == []


def test_idle_is_split_exactly_over_the_innermost_spans(spans, trace):
    a = ps.analyse(spans, trace)
    # idle: 0..60, 400..420, 700..1130, 1300..1500 = 710 ms of 1500
    want = {
        "admit": 5 + 5 + 15 + 10,      # 0..10, 15..30 and 1100..1110
        "dispatch_prefill": 5,          # 10..15
        "pump": 10 + 30 + 10 + 10 + 10,     # a turn's self time: 30..40,
                                        # 970..1000, 1110..1120,
                                        # 1370..1380, 1390..1400
        "dispatch_decode": 20 + 10,     # 40..60 and 1120..1130
        "harvest": 20 + 200 + 50,       # 400..420, 700..900, 1300..1350
        "commit": 10 + 39 + 20,         # 900..910, 911..950, 1350..1370
        "first_token": 1,
        "retire": 20 + 10,
        ps.NO_SPAN: 100 + 100,          # 1000..1100 and 1400..1500
    }
    assert {k: pytest.approx(v) for k, v in want.items()} == a.idle_ms
    whole = trace.idle_share() * trace.window_s * 1e3
    assert sum(a.idle_ms.values()) == pytest.approx(whole) == \
        pytest.approx(710.0)


def test_harvest_wait_runs_from_the_prefill_s_end_to_the_first_token(
        spans, trace):
    (r,) = ps.analyse(spans, trace).requests
    assert r["uid"] == "a"
    assert r["queued_s"] == pytest.approx(0.050)        # 10 -> 60 ms
    assert r["run_s"] == pytest.approx(0.050)
    assert r["harvest_wait_s"] == pytest.approx(0.800)  # 110 -> 910 ms


def test_dispatches_pair_with_runs_in_order_and_skip_stale_runs():
    d = ps.nest([_span("dispatch_prefill", 10, 1, uid="a"),
                 _span("dispatch_prefill", 12, 1, uid="b"),
                 _span("dispatch_prefill", 500, 1, uid="c")])
    runs = [(5 * MS, 9 * MS),           # enqueued before the trace began
            (300 * MS, 350 * MS), (350 * MS, 400 * MS)]
    assert ps.pair_dispatches(d, runs) == [(d[0], runs[1]), (d[1], runs[2])]


def _fake_run(spans):
    notes = []
    run = types.SimpleNamespace(note=notes.append, notes=notes)
    run.program_spans = spans           # as ``_analysis`` keeps it
    return run


def test_readers_return_the_numbers_and_nothing_without_spans(spans, trace):
    run = _fake_run(ps.analyse(spans, trace))
    assert ps.host_ms_per_pump(trace, {}, {}, run) == pytest.approx(150.0)
    assert ps.harvest_wait_p50_s(trace, {}, {}, run) == pytest.approx(0.8)
    assert ps.idle_under_spans_ms(
        trace, {}, {"spans": ["harvest"]}, run) == pytest.approx(270 / 2)
    assert ps.idle_under_spans_ms(
        trace, {}, {"spans": ["admit", "dispatch_prefill",
                              "dispatch_decode", "draft"]}, run) == \
        pytest.approx((35 + 5 + 30) / 2)
    # a program without the spans: every reader gives nothing
    empty = _fake_run(ps.analyse([], trace))
    assert empty.program_spans is None
    for reader in (ps.host_ms_per_pump, ps.harvest_wait_p50_s):
        assert reader(trace, {}, {}, empty) is None
    assert ps.idle_under_spans_ms(
        trace, {}, {"spans": ["harvest"]}, empty) is None
    # a trace with no device (a CPU run): host numbers only
    host_only = _fake_run(ps.analyse(spans, tr.Trace(
        {"devices": [], "host_spans": [["bench.pump", 0, 1500 * MS]]})))
    assert ps.host_ms_per_pump(None, {}, {}, host_only) == \
        pytest.approx(150.0)
    assert ps.harvest_wait_p50_s(None, {}, {}, host_only) is None


def test_the_note_names_every_part(spans, trace):
    text = ps._note(ps.analyse(spans, trace), trace)
    assert "2 turns" in text and "150.000 ms of it not in harvest" in text
    assert "chip 0 idle 710.000 ms" in text and ps.NO_SPAN in text
    assert "prefill ends -> first token on the host 0.8000 s" in text


def test_tiny_backlog_cell_reports_host_ms_per_pump_from_a_cpu_trace():
    """End to end: the program's spans come back from a real profiler
    session with their stats, and the reader finds the file itself."""
    import run as bench

    manifest = copy.deepcopy(bench.load_json(
        os.path.join(HERE, "cells", "manifest.json")))
    manifest["per_layer"].append({
        "name": "tiny.host_ms_per_pump", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "serving entry",
        "moves": "serve_tokens_per_s", "workloads": ["tiny-backlog"]})
    line = bench.run_cell(manifest, "tiny-backlog", 3000000019, 1.0, True,
                          require_tpu=False)
    assert line["correct"] is True
    assert line["metrics"]["tiny.host_ms_per_pump"]["value"] > 0
