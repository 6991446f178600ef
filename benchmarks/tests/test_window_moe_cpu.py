"""``runners/serve_window_moe.py`` end to end on the CPU, on a toy cell
added as files only (``cells/manifest_window_moe.json``): the model's
build over two page classes, the reference check through chunks and
paged decode steps, the fill, the window and the counters, so that the
first run of the real cell on a chip is not the runner's first run.
Shape only: numbers from these runs mean nothing."""

import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tiny-window-backlog"
REAL_TRAFFIC = os.path.join(
    HERE, "..", "traffic", "backlog-mixed-short-long-in-mid-out.json")


@pytest.fixture(scope="module")
def bench():
    import run as bench      # benchmarks/run.py, by conftest's sys.path

    return bench


@pytest.fixture(scope="module")
def manifest(bench):
    return bench.load_json(
        os.path.join(HERE, "cells", "manifest_window_moe.json"))


@pytest.fixture(scope="module")
def lines(bench, manifest):
    return {traced: bench.run_cell(manifest, CELL, 3000000019, 1.0, traced,
                                   require_tpu=False)
            for traced in (False, True)}


def test_untraced_line(lines):
    line = lines[False]
    json.dumps(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


def test_traced_line_reads_the_counters_and_leaves_device_metrics_out(lines):
    line = lines[True]
    assert line["correct"] is True
    # the counters are read; the device-trace readers find no TPU plane
    # in a CPU trace, return nothing, and their metrics are left out
    assert set(line["metrics"]) == {"tiny.window_read_share",
                                    "tiny.pages_in_use_share_window"}
    # half the prompts are 2.5-4.5 toy windows long: the window engaged
    assert 0 < line["metrics"]["tiny.window_read_share"]["value"] < 100
    assert 0 < line["metrics"]["tiny.pages_in_use_share_window"][
        "value"] <= 100


def _lengths(generation):
    return [(len(r.prompt) - r.aged_tokens, r.new_tokens + r.aged_tokens,
             r.aged_tokens) for r in generation]


@pytest.mark.parametrize("traffic_file,n,cut", [
    (REAL_TRAFFIC, 24, 2048),
    (os.path.join(HERE, "cells", "traffic", "tiny-window-backlog.json"),
     8, 12)])
def test_every_eight_hold_four_short_and_four_long_and_span_the_outputs(
        bench, traffic_file, n, cut):
    """The two-part multiset at fixed quantiles, the same order for
    every seed (the seed draws the token ids only), every 8 consecutive
    requests 4 short and 4 long with one output from each eighth."""
    from runners.serve_window_moe import MixedBacklog, mixed_pairs

    tr = bench.load_json(traffic_file)
    pairs = mixed_pairs(tr, n)
    assert sum(p < cut for p, _ in pairs) == n // 2
    ranked = sorted(o for _, o in pairs)
    one, other = (MixedBacklog(tr, 96, seed) for seed in (5, 3000000019))
    orders = []
    for g in range(3):
        a, b = one.next_generation(), other.next_generation()
        assert _lengths(a) == _lengths(b)
        assert sorted((p, o) for p, o, _ in _lengths(a)) == sorted(pairs)
        assert any(x.prompt.tolist() != y.prompt.tolist()
                   for x, y in zip(a, b))
        assert (max(aged for _, _, aged in _lengths(a)) > 0) == (g == 0)
        for start in range(0, n, 8):
            block = _lengths(a)[start:start + 8]
            assert sum(p < cut for p, _, _ in block) == 4
            per = n // 8
            assert sorted(ranked.index(o) // per for _, o, _ in block) == \
                sorted(set(ranked.index(o) // per for _, o, _ in block))
        orders.append(_lengths(a))
    assert orders[1] != orders[2]       # a generation is not the last's


def test_the_real_cells_lengths(bench):
    from runners.serve_window_moe import mixed_pairs

    tr = bench.load_json(REAL_TRAFFIC)
    pairs = mixed_pairs(tr, 24)
    short = sorted(p for p, _ in pairs if p < 2048)
    long = sorted(p for p, _ in pairs if p >= 2048)
    assert len(short) == len(long) == 12
    assert 256 <= short[0] and short[-1] <= 1024
    assert 6144 <= long[0] and long[-1] <= 12288      # 1.5-3 windows
    assert all(128 <= o <= 512 for _, o in pairs)
    assert max(p + o for p, o in pairs) <= tr["max_total_len"] == \
        tr["pages_per_seq"] * tr["page_size"]
    # the checked prompt is two windows deep and its walk divides
    from runners.serve_window_moe import check_plan

    n, new, steps = check_plan(tr)
    assert n >= 8192 and steps >= 16
    assert (n + new - 1) % tr["reference_q_block"] == 0


def test_the_weights_are_one_draw_the_configuration_names(bench, manifest):
    import inspect

    from runners import serve_window_moe

    text = inspect.getsource(serve_window_moe.build)
    assert 'int(cfg["weights_seed"])' in text and "run.seed" not in text
    assert "weights_seed" in bench.resolve(manifest, CELL)[1]
    real = bench.load_json(os.path.join(
        HERE, "..", "configs", "trinity-large-ep8-share.json"))
    assert real["weights_seed"] == 32
    assert real["held_experts"] == list(range(32))


def test_the_configuration_keeps_every_published_width(bench):
    real = bench.load_json(os.path.join(
        HERE, "..", "configs", "trinity-large-ep8-share.json"))
    assert {k: real[k] for k in (
        "hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "intermediate_size", "moe_intermediate_size",
        "num_experts_per_tok", "sliding_window", "route_scale")} == dict(
        hidden_size=3072, head_dim=128, num_attention_heads=48,
        num_key_value_heads=8, intermediate_size=12288,
        moe_intermediate_size=3072, num_experts_per_tok=4,
        sliding_window=4096, route_scale=2.448)
    assert real["published"] == dict(
        num_hidden_layers=60, num_dense_layers=6, num_experts=256,
        vocab_size=200192, layer_types=(
            ["sliding_attention"] * 3 + ["full_attention"]) * 15)
    assert sorted(real["reduced"]) == sorted(real["published"])
    assert real["layer_types"] == ["sliding_attention"] * 4 + [
        "full_attention"]


def test_judge_holds_medians_and_single_readings_of_logits_and_attention():
    from runners.serve_window_moe import judge

    tr = dict(logit_tolerance=0.04, logit_tolerance_single=0.2,
              attn_tolerance=0.04, attn_tolerance_single=0.2)
    ok = [0.02] * 30 + [0.08]
    why, numbers = judge(tr, ok, [0.03, 0.03], [ok, ok])
    assert why == [] and numbers["logits_check_ratio_max"] == 0.08
    why, _ = judge(tr, [0.06] * 31, [0.06, 0.06], [ok, ok])
    assert len(why) == 1 and "served logits, median" in why[0]
    why, _ = judge(tr, ok, [0.03, 0.25], [ok, ok])
    assert len(why) == 1 and "one position" in why[0]
    why, _ = judge(tr, ok, [0.03, 0.03], [[0.3] * 31, ok])
    assert len(why) == 2 and all("window layer" in w for w in why)
    why, _ = judge(tr, ok, [0.03, 0.03], [ok, [0.02] * 30 + [0.5]])
    assert len(why) == 1 and "full layer" in why[0] and "one step" in why[0]


def test_compare_reads_each_position_against_its_own_reference_row():
    from runners.serve_window_moe import compare

    n, vocab = 5, 7
    ref_logits = np.arange(4 * vocab, dtype=np.float32).reshape(4, vocab)
    ref_attn = np.zeros((5, 4, 3), np.float32)
    ref_attn[3] = np.arange(12).reshape(4, 3)           # largest 11
    ref_attn[4] = 2.0
    served = [{
        "chunk_at": 4, "chunk_logits": ref_logits[0] + 2.7,
        "at": np.array([6, 7]),
        "logits": np.stack([ref_logits[2], ref_logits[3] - 5.4]),
        "attn": np.stack([np.stack([ref_attn[3, 2] + 1.1, ref_attn[4, 2]]),
                          np.stack([ref_attn[3, 3], ref_attn[4, 3] - 1.0])])}]
    errors, chunk_errors, attn_errors, scale = compare(
        served, n, ref_logits, ref_attn, (3, 4))
    assert scale == 27.0
    assert chunk_errors == [pytest.approx(0.1)]
    assert errors == [0.0, pytest.approx(0.2)]
    assert attn_errors == [[pytest.approx(0.1), 0.0], [0.0, pytest.approx(0.5)]]


def test_derived_counters_and_rooflines_arithmetic():
    import rooflines_window_moe as r
    from runners.serve_window_moe import derived_counters

    names = ("decode_steps", "decode_choices", "decode_choices_held",
             "decode_experts_touched", "decode_load_max",
             "decode_window_rows", "decode_full_rows", "decode_context_rows",
             "decode_slot_layers")
    c = dict.fromkeys(names, 0.0)
    assert derived_counters(c, 32, 4) == {}
    c.update(decode_steps=10, decode_choices=960, decode_choices_held=120,
             decode_experts_touched=400, decode_load_max=40,
             decode_window_rows=4 * 24 * 2500 * 10,
             decode_full_rows=24 * 5000 * 10,
             decode_context_rows=24 * 5000 * 10, decode_slot_layers=1200)
    d = derived_counters(c, 32, 4)
    assert d["moe_held_choice_share"] == pytest.approx(12.5)
    assert d["attn_window_read_share"] == pytest.approx(50.0)
    assert d["moe_load_max_over_mean"] == pytest.approx(40 / (120 / 32))
    assert d["window_rows_per_step"] == 4 * 24 * 2500
    cfg = {"head_dim": 128, "num_attention_heads": 48,
           "num_key_value_heads": 8, "hidden_size": 3072,
           "moe_intermediate_size": 3072}
    flops, nbytes = r.window_decode(d, cfg)
    assert nbytes == d["window_rows_per_step"] * 4096       # 4 KiB a row
    assert flops == d["window_rows_per_step"] * 48 * 128 * 4
    assert r.full_decode(d, cfg)[1] == 24 * 5000 * 4096
    flops, nbytes = r.moe_experts(d, cfg)
    assert nbytes == pytest.approx(40 * 28.31e6 * 2, rel=1e-3)


def test_parent_without_the_model_fails_cleanly(bench, manifest, monkeypatch):
    """The driver tries a new cell on the parent commit first: a program
    without the model must exit non-zero at once, with a message."""
    import sys

    monkeypatch.setitem(sys.modules, "apex_tpu.models.afmoe", None)
    with pytest.raises(SystemExit) as e:
        bench.run_cell(manifest, CELL, 1, 1.0, False, require_tpu=False)
    assert "no window-and-full-attention expert model" in str(e.value)


def test_controls_go_through_the_cells_own_comparison(capsys):
    """``controls_window_moe.py`` breaks the served side and hands it to
    the runner's ``verdict``: a line a control, with the numbers that
    were compared; a full layer cut to the window, or a window layer
    given everything, moves that layer's attention output."""
    import controls_window_moe

    assert controls_window_moe.main([
        "--workload", CELL, "--seed", "5", "--allow-cpu", "--controls",
        "sound,full_cut_to_window,window_whole_context,rope_on_full,"
        "neighbour_window_page", "--manifest",
        os.path.join(HERE, "cells", "manifest_window_moe.json")]) == 0
    lines = {line["control"]: line for line in (
        json.loads(text) for text in capsys.readouterr().out.split("\n")
        if text.startswith("{"))}
    assert len(lines) == 5
    for line in lines.values():
        assert {"correct", "why_incorrect", "logits_check_ratio",
                "logits_check_ratio_max", "attn_window_check_ratio",
                "attn_full_check_ratio_max"} <= set(line)
    sound = lines["sound"]
    assert lines["full_cut_to_window"]["attn_full_check_ratio"] > \
        4 * sound["attn_full_check_ratio"]
    assert lines["rope_on_full"]["attn_full_check_ratio"] > \
        4 * sound["attn_full_check_ratio"]
    assert lines["window_whole_context"]["attn_window_check_ratio"] > \
        4 * sound["attn_window_check_ratio"]
    assert lines["neighbour_window_page"]["attn_window_check_ratio_max"] > \
        4 * sound["attn_window_check_ratio_max"]
