"""``runners/serve_latent_moe.py`` end to end on the CPU, on a toy cell
added as files only (``cells/manifest_latent_moe.json``): the model's
build, the reference check through chunks and a paged decode step, the
fill, the window and the counters, so that the first run of the real
cell on a chip is not the runner's first run.  Shape only: numbers from
these runs mean nothing."""

import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tiny-latent-backlog"


@pytest.fixture(scope="module")
def bench():
    import run as bench      # benchmarks/run.py, by conftest's sys.path

    return bench


@pytest.fixture(scope="module")
def manifest(bench):
    return bench.load_json(
        os.path.join(HERE, "cells", "manifest_latent_moe.json"))


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


def test_traced_line_reads_the_counter_and_leaves_device_metrics_out(lines):
    line = lines[True]
    assert line["correct"] is True
    # the counter is read; the two device-trace readers find no TPU plane
    # in a CPU trace, return nothing, and their metrics are left out
    assert set(line["metrics"]) == {"tiny.held_choice_share"}
    share = line["metrics"]["tiny.held_choice_share"]["value"]
    assert 0 < share < 100            # 4 of 16 experts held


def _lengths(generation):
    return [(len(r.prompt) - r.aged_tokens, r.new_tokens + r.aged_tokens,
             r.aged_tokens) for r in generation]


@pytest.mark.parametrize("traffic_file", [
    os.path.join(HERE, "..", "traffic", "backlog-longdoc-in-mid-out.json"),
    os.path.join(HERE, "cells", "traffic", "tiny-latent-backlog.json")])
def test_every_seed_queues_the_same_work(bench, traffic_file):
    """The order of every generation is the traffic file's: two seeds
    queue the same lengths in the same places and differ in the token
    ids only; the multiset, its pairing and the pre-ageing are
    ``traffic.Backlog``'s."""
    import traffic as traffic_gen
    from runners.serve_latent_moe import FileOrderBacklog

    tr = bench.load_json(traffic_file)
    one, other = (FileOrderBacklog(tr, 96, seed)
                  for seed in (5, 3000000019))
    plain = traffic_gen.Backlog(tr, 96, 5)
    orders = []
    for _ in range(3):
        a, b, c = (s.next_generation() for s in (one, other, plain))
        assert _lengths(a) == _lengths(b)
        assert sorted(_lengths(a)) == sorted(_lengths(c))
        assert any(x.prompt.tolist() != y.prompt.tolist()
                   for x, y in zip(a, b))
        orders.append(_lengths(a))
    assert orders[0][0][2] > 0 or orders[0][1][2] > 0     # pre-aged
    assert all(aged == 0 for g in orders[1:] for _, _, aged in g)
    assert orders[1] != orders[2]       # a generation is not the last's


def test_a_stretch_of_eight_requests_spans_the_outputs(bench):
    """Block-stratified by OUTPUT, which is what decides when a slot
    comes free: every 8 consecutive requests of a generation hold one
    output from each eighth of the distribution."""
    from runners.serve_latent_moe import FileOrderBacklog

    tr = bench.load_json(os.path.join(
        HERE, "..", "traffic", "backlog-longdoc-in-mid-out.json"))
    source = FileOrderBacklog(tr, 96, 1)
    ranked = sorted(o for _, o in source.pairs)
    for _ in range(2):
        outputs = [o for _, o, _ in _lengths(source.next_generation())]
        for start in range(0, 32, 8):
            eighths = sorted(ranked.index(o) // 4
                             for o in outputs[start:start + 8])
            assert eighths == list(range(8))


def test_the_weights_are_one_draw_the_configuration_names(bench, manifest):
    """``--seed`` does not reach the weights: the key is the
    configuration's ``weights_seed`` (the real cell's and the toy's)."""
    import inspect

    from runners import serve_latent_moe

    text = inspect.getsource(serve_latent_moe.build)
    assert 'int(cfg["weights_seed"])' in text and "run.seed" not in text
    assert "weights_seed" in bench.resolve(manifest, CELL)[1]
    real = bench.load_json(os.path.join(
        HERE, "..", "configs", "deepseek-v3.2-ep16-share.json"))
    assert isinstance(real["weights_seed"], int)


def test_the_rate_is_the_whole_windows():
    """``serve_tokens_per_s`` is ``Driver.window_counters``' rate between
    the window's first and last pump return, as in the other backlog
    cell; the runner has no span of its own."""
    import inspect

    from runners import serve_latent_moe

    text = inspect.getsource(serve_latent_moe.run)
    assert 'counters = drv.window_counters(t_open, run.seconds)' in text
    assert '{"serve_tokens_per_s": counters["tokens_per_s"]}' in text
    assert "rate_between" not in inspect.getsource(serve_latent_moe)


def test_check_plan_lets_the_first_request_outlive_the_fill():
    from runners.serve_latent_moe import check_plan

    real = dict(slots=32, prefill_chunk=2048, check_tokens=4608,
                check_decode_steps=16)
    n, new, steps = check_plan(real)
    assert (n, new, steps) == (4558, 51, 16)
    assert n + new - 1 == 4608              # what the reference walks
    # chunks before the last request decodes: its own 3, 30 others, 3
    assert new - 1 >= 30 + 3 + steps
    assert n > 2 * 2048                     # the third chunk selects


def test_judge_holds_the_median_and_every_single_position():
    from runners.serve_latent_moe import judge

    tr = dict(selection_overlap_floor=0.96, logit_tolerance=0.04,
              logit_tolerance_single=0.2)
    sound = [0.02] * 30 + [0.08]
    why, numbers = judge(tr, [[0.98] * 31, [0.99] * 31], sound, [0.03, 0.03])
    assert why == [] and numbers["logits_check_ratio_max"] == 0.08
    assert numbers["logits_check_ratio"] == 0.02
    # every position moved (fewer bits): the median catches it
    why, _ = judge(tr, [[0.98] * 31], [0.06] * 31, [0.06, 0.06])
    assert len(why) == 1 and "median" in why[0]
    # one decode step broken, or one chunk's logits: the single limit
    why, _ = judge(tr, [[0.98] * 31], [0.02] * 30 + [0.3], [0.03, 0.03])
    assert len(why) == 1 and "one position" in why[0]
    why, _ = judge(tr, [[0.98] * 31], [0.02] * 31, [0.03, 0.25])
    assert len(why) == 1 and "one position" in why[0]
    # the selected sets drifted
    why, _ = judge(tr, [[0.98] * 31, [0.9] * 31], sound, [0.03, 0.03])
    assert len(why) == 1 and "selected sets" in why[0]


def test_compare_reads_each_position_against_its_own_reference_row():
    from runners.serve_latent_moe import compare

    n, vocab = 5, 7
    ref_logits = np.arange(4 * vocab, dtype=np.float32).reshape(4, vocab)
    selections = [np.array([[1, 1, 0, 0, 0, 1, 0, 0],
                            [1, 0, 1, 0, 0, 0, 1, 0],
                            [0, 1, 1, 0, 0, 0, 0, 1]], bool)]
    served = [{
        "chunk_at": 4, "chunk_logits": ref_logits[0] + 2.7,
        "at": np.array([6, 7]),
        "logits": np.stack([ref_logits[2], ref_logits[3] - 5.4]),
        "selected": np.array([[[0, 2, 6]], [[1, 2, 3]]]),
        "valid": np.array([[[True, True, True]], [[True, True, False]]])}]
    overlaps, errors, chunk_errors, scale = compare(
        served, n, ref_logits, selections)
    assert scale == 27.0
    assert chunk_errors == [pytest.approx(0.1)]
    assert errors == [0.0, pytest.approx(0.2)]
    assert overlaps == [[1.0, pytest.approx(2 / 3)]]


def test_derived_counters_arithmetic():
    from runners.serve_latent_moe import derived_counters

    c = dict.fromkeys((
        "decode_steps", "decode_choices", "decode_choices_held",
        "decode_experts_touched", "decode_load_max", "decode_selected_rows",
        "decode_context_rows", "decode_slot_layers"), 0.0)
    assert derived_counters(c, 16) == {}
    c.update(decode_steps=10, decode_choices=1000, decode_choices_held=62.5,
             decode_experts_touched=50, decode_load_max=30,
             decode_selected_rows=2048 * 100, decode_context_rows=5000 * 100,
             decode_slot_layers=100)
    d = derived_counters(c, 16)
    assert d["moe_held_choice_share"] == pytest.approx(6.25)
    assert d["attn_selected_share"] == pytest.approx(40.96)
    assert d["moe_load_max_over_mean"] == pytest.approx(30 / (62.5 / 16))
    assert d["experts_touched_per_step"] == 5.0
    assert d["mla_rows_per_step"] == 20480.0


def test_rooflines_count_what_the_issue_states():
    import rooflines_latent_moe as r

    cfg = {"index_head_dim": 128, "index_n_heads": 64, "kv_lora_rank": 512,
           "qk_rope_head_dim": 64, "num_attention_heads": 128,
           "hidden_size": 7168, "moe_intermediate_size": 2048}
    c = {"index_context_tokens_per_step": 1000.0, "mla_rows_per_step": 10.0,
         "held_choices_per_step": 3.0, "experts_touched_per_step": 2.0}
    assert r.index_score(c, cfg) == (1000 * 64 * 128 * 2.0, 1000 * 128 * 2.0)
    assert r.mla_decode(c, cfg) == (10 * 128 * (576 + 512) * 2.0,
                                    10 * 576 * 2.0)
    flops, nbytes = r.moe_experts(c, cfg)
    assert flops == 3 * 6 * 7168 * 2048
    assert nbytes == pytest.approx(2 * 88.08e6, rel=1e-3)


def test_parent_without_the_model_fails_cleanly(bench, manifest, monkeypatch):
    """The driver tries a new cell on the parent commit first: a program
    without the model must exit non-zero at once, with a message."""
    import sys

    monkeypatch.setitem(sys.modules, "apex_tpu.models.deepseek_v32", None)
    with pytest.raises(SystemExit) as e:
        bench.run_cell(manifest, CELL, 1, 1.0, False, require_tpu=False)
    assert "no latent-attention expert model" in str(e.value)


def test_controls_go_through_the_cells_own_comparison(capsys):
    """``controls_latent_moe.py`` breaks the served side and hands it to
    the runner's ``verdict``: a line a control, with the numbers that
    were compared; a softmax scale without YaRN's m^2 moves the logits."""
    import controls_latent_moe

    assert controls_latent_moe.main([
        "--workload", CELL, "--seed", "5", "--allow-cpu",
        "--controls", "sound,scale_without_m2", "--manifest",
        os.path.join(HERE, "cells", "manifest_latent_moe.json")]) == 0
    lines = [json.loads(text) for text in capsys.readouterr().out.split("\n")
             if text.startswith("{")]
    assert [line["control"] for line in lines] == ["sound",
                                                   "scale_without_m2"]
    for line in lines:
        assert {"correct", "why_incorrect", "logits_check_ratio",
                "logits_check_ratio_max", "logits_check_ratio_chunk_max",
                "selection_overlap_min"} <= set(line)
    assert lines[1]["logits_check_ratio"] > 2 * lines[0]["logits_check_ratio"]
