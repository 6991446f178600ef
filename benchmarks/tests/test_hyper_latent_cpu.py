"""``runners/serve_hyper_latent.py`` end to end on the CPU, on a toy cell
added as files only (``cells/manifest_hyper_latent.json``): the model's
build, the reference check through chunks and paged decode steps, the
fill, the window, the counters and the chunk programs' compiled texts,
with the readers, the rooflines and the controls beside it, so that the
first run of the real cell on a chip is not the runner's first run.
Shape only: numbers from these runs mean nothing."""

import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tiny-hyper-backlog"
MANIFEST = os.path.join(HERE, "cells", "manifest_hyper_latent.json")


@pytest.fixture(scope="module")
def bench():
    import run as bench      # benchmarks/run.py, by conftest's sys.path

    return bench


@pytest.fixture(scope="module")
def manifest(bench):
    return bench.load_json(MANIFEST)


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
    # the counter is read; the device-trace readers find no TPU plane in
    # a CPU trace, return nothing, and their metrics are left out
    assert set(line["metrics"]) == {"tiny.experts_touched_mean"}
    touched = line["metrics"]["tiny.experts_touched_mean"]["value"]
    assert 1 <= touched <= 8          # of 8 experts, per expert layer


def test_the_real_cells_traffic_is_the_issues(bench):
    """The cell's files say what ISSUE 35 fixes: the slots, the pages,
    the chunk, the lengths, the check's prompt of at least 12,288 tokens
    whose reference walk the query blocks divide."""
    from runners.serve_latent_moe import check_plan

    tr = bench.load_json(os.path.join(
        HERE, "..", "traffic", "backlog-16k-in-mid-out.json"))
    assert (tr["slots"], tr["page_size"], tr["pages_per_seq"],
            tr["prefill_chunk"]) == (16, 64, 336, 4096)
    assert (tr["prompt"]["lo"], tr["prompt"]["hi"]) == (12288, 20480)
    assert (tr["output"]["lo"], tr["output"]["hi"]) == (256, 768)
    assert tr["prompt"]["hi"] + tr["output"]["hi"] <= tr["max_total_len"]
    n, new, steps = check_plan(tr)
    assert n >= 12288 and steps == tr["check_decode_steps"]
    assert (n + new - 1) % tr["reference_q_block"] == 0
    # six context extents: six chunk programs
    assert -(-tr["max_prompt_len"] // tr["prefill_chunk"]) == 6


def test_every_seed_queues_the_same_work(bench):
    from runners.serve_latent_moe import FileOrderBacklog

    tr = bench.load_json(os.path.join(
        HERE, "..", "traffic", "backlog-16k-in-mid-out.json"))
    lengths = lambda g: [(len(r.prompt) - r.aged_tokens,
                          r.new_tokens + r.aged_tokens) for r in g]
    one, other = (FileOrderBacklog(tr, 96, s) for s in (5, 3000000019))
    for _ in range(2):
        a, b = one.next_generation(), other.next_generation()
        assert lengths(a) == lengths(b) and len(a) == 16
        assert any(x.prompt.tolist() != y.prompt.tolist()
                   for x, y in zip(a, b))


def test_the_weights_are_one_draw_the_configuration_names(bench, manifest):
    import inspect

    from runners import serve_hyper_latent

    text = inspect.getsource(serve_hyper_latent.build)
    assert 'int(cfg["weights_seed"])' in text and "run.seed" not in text
    real = bench.load_json(os.path.join(
        HERE, "..", "configs", "xing4.0-29b-a4b-depth6.json"))
    assert real["weights_seed"] == 35
    assert bench.resolve(manifest, CELL)[1]["weights_seed"] == 35


def test_the_configuration_is_the_catalogs_but_for_the_depth(bench):
    """Every number of the published ``config.json`` under its key,
    except the three keys ``reduced`` lists, whose published values are
    kept beside them."""
    real = bench.load_json(os.path.join(
        HERE, "..", "configs", "xing4.0-29b-a4b-depth6.json"))
    assert real["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "num_nextn_predict_layers"]
    assert real["published"] == {"num_hidden_layers": 40,
                                 "first_k_dense_replace": 2,
                                 "num_nextn_predict_layers": 1}
    assert (real["num_hidden_layers"], real["first_k_dense_replace"],
            real["num_nextn_predict_layers"]) == (6, 1, 0)
    assert (real["hidden_size"], real["hc_mult"], real["n_routed_experts"],
            real["num_experts_per_tok"], real["vocab_size"],
            real["rope_scaling"]["factor"]) == (3584, 4, 64, 4, 131072, 64)
    for key in ("source", "deployment", "assumed"):
        assert real[key]


def test_judge_holds_the_median_and_every_single_position():
    from runners.serve_hyper_latent import judge

    tr = dict(logit_tolerance=0.04, logit_tolerance_single=0.2)
    why, numbers = judge(tr, [0.02] * 30 + [0.08], [0.03, 0.03])
    assert why == [] and numbers["logits_check_ratio_max"] == 0.08
    assert numbers["logits_check_ratio"] == 0.02
    why, _ = judge(tr, [0.06] * 31, [0.06, 0.06])
    assert len(why) == 1 and "median" in why[0]
    why, _ = judge(tr, [0.02] * 30 + [0.3], [0.03, 0.03])
    assert len(why) == 1 and "one position" in why[0]
    why, _ = judge(tr, [0.02] * 31, [0.03, 0.25])
    assert len(why) == 1 and "one position" in why[0]


def test_compare_reads_each_position_against_its_own_reference_row():
    from runners.serve_hyper_latent import compare

    n, vocab = 5, 7
    ref_logits = np.arange(4 * vocab, dtype=np.float32).reshape(4, vocab)
    served = [{"chunk_at": 4, "chunk_logits": ref_logits[0] + 2.7,
               "at": np.array([6, 7]),
               "logits": np.stack([ref_logits[2], ref_logits[3] - 5.4])}]
    errors, chunk_errors, scale = compare(served, n, ref_logits)
    assert scale == 27.0
    assert chunk_errors == [pytest.approx(0.1)]
    assert errors == [0.0, pytest.approx(0.2)]


def test_rooflines_count_what_the_issue_states():
    import rooflines_hyper_latent as r
    import rooflines_latent_moe as latent

    cfg = {"hc_mult": 4, "hidden_size": 3584, "kv_lora_rank": 512,
           "qk_rope_head_dim": 64, "num_attention_heads": 32,
           "moe_intermediate_size": 1024}
    flops, nbytes = r.hc_chunk({"chunk_tokens": 4096, "layers": 6}, cfg)
    streams = 4096 * 4 * 3584 * 4                       # 235 MB
    # streams read three times and written once, h out and y in
    assert nbytes == 12 * (4 * streams + 2 * 4096 * 3584 * 4)
    assert nbytes / 12 == pytest.approx(0.94e9 + 0.117e9, rel=0.01)
    assert flops == 12 * 4096 * 4 * 3584 * (2 * 24 + 2 + 8 + 2)
    # the walk and the experts are the latent cell's functions, fed by
    # this runner's counters: 16 slots of 16.6k rows over 6 layers
    c = {"mla_rows_per_step": 16 * 16600 * 6.0,
         "held_choices_per_step": 64 * 5.0, "experts_touched_per_step": 200.0}
    assert latent.mla_decode(c, cfg)[1] == 16 * 16600 * 6 * 576 * 2.0
    assert latent.moe_experts(c, cfg)[1] == 200 * 3 * 3584 * 1024 * 2.0


def test_parent_without_the_model_fails_cleanly(bench, manifest, monkeypatch):
    """The driver tries a new cell on the parent commit first: a program
    without the model must exit non-zero at once, with a message."""
    import sys

    monkeypatch.setitem(sys.modules, "apex_tpu.models.xing4", None)
    with pytest.raises(SystemExit) as e:
        bench.run_cell(manifest, CELL, 1, 1.0, False, require_tpu=False)
    assert "has no such model" in str(e.value)


def test_controls_go_through_the_cells_own_comparison(capsys):
    """``controls_hyper_latent.py`` breaks the served side and hands it
    to the runner's ``verdict``: a line a control, with the numbers that
    were compared; a plain residual in place of ``H_res`` and a walk that
    stops a page short both move the logits."""
    import controls_hyper_latent

    assert controls_hyper_latent.main([
        "--workload", CELL, "--seed", "5", "--allow-cpu", "--controls",
        "sound,h_res_identity,walk_one_page_short", "--manifest",
        MANIFEST]) == 0
    lines = [json.loads(text) for text in capsys.readouterr().out.split("\n")
             if text.startswith("{")]
    assert [line["control"] for line in lines] == [
        "sound", "h_res_identity", "walk_one_page_short"]
    for line in lines:
        assert {"correct", "why_incorrect", "logits_check_ratio",
                "logits_check_ratio_max",
                "logits_check_ratio_chunk_max"} <= set(line)
    assert lines[1]["logits_check_ratio"] > 5 * lines[0]["logits_check_ratio"]
    assert lines[2]["logits_check_ratio_max"] \
        > 5 * lines[0]["logits_check_ratio_max"]
    # the chunk program has no walk: its positions stay sound
    assert lines[2]["logits_check_ratio_chunk_max"] \
        < 2 * lines[0]["logits_check_ratio_chunk_max"] + 1e-6


@pytest.mark.parametrize("control", [
    "no_dynamic", "h_post_without_2", "streams_16bit", "yarn_factor_40",
    "weights_8bit"])
def test_every_control_builds_its_fault(control):
    """Each further control patches what it names and puts it back."""
    import controls_hyper_latent

    from apex_tpu.models import xing4

    patched = lambda: (
        xing4.hc_mapping, xing4.hc_mix, xing4.mla_paged,
        xing4.Xing4Model._streams, xing4.Xing4Model.init,
        xing4.Xing4Config.from_hf)
    before = patched()
    with controls_hyper_latent.broken(control):
        assert patched() != before
    assert patched()[:5] == before[:5]
    with pytest.raises(SystemExit):
        controls_hyper_latent.broken("no_such_control")


def test_a_chunk_run_is_read_against_its_own_programs_text():
    """Two context extents number their instructions differently:
    ``fusion.7`` is a wrapper's mix in one and an expert product in the
    other.  Each run of ``jit__chunk`` is matched to the text whose
    names AND shapes its operations carry, then read by scope."""
    import types

    import trace_reduce as tr
    from readers import hyper_latent

    MS = 1e6
    text = lambda ctx, mix, experts: f"""
HloModule jit__chunk
ENTRY %main {{
  %while.1 = (f32[4,8,64]) while(%t), metadata={{op_name="jit(_chunk)/tlm.prefill/while"}}
  %{mix} = f32[4,8,64]{{2,1,0}} fusion(%a, %b), kind=kLoop, metadata={{op_name="jit(_chunk)/tlm.prefill/while/body/tlm.resid.hc_mix/add"}}
  %{experts} = bf16[8,64]{{1,0}} fusion(%c), kind=kOutput, metadata={{op_name="jit(_chunk)/tlm.prefill/while/body/tlm.moe.experts/dot"}}
  %keys.3 = bf16[{ctx},192]{{1,0}} fusion(%d), kind=kLoop, metadata={{op_name="jit(_chunk)/tlm.prefill/while/body/tlm.attn.mla/dot"}}
  ROOT %tlm.kernel.hc_map.5 = f32[4,8]{{1,0}} custom-call(%x), custom_call_target="tpu_custom_call", metadata={{op_name="jit(_chunk)/tlm.prefill/while/body/tlm.resid.hc_map/tlm.kernel.hc_map/pallas_call"}}
}}
"""
    op = lambda name, shape, start, dur, opcode="fusion": {
        "name": name, "opcode": opcode, "shape": shape, "operands": 2,
        "target": "", "start": start * MS, "dur": dur * MS}
    ops = []
    # run 1 is the 16-token extent (fusion.7 mixes), run 2 the 32-token
    for base, ctx, mix, experts in ((0, 16, "fusion.7", "fusion.8"),
                                    (100, 32, "fusion.8", "fusion.7")):
        ops += [op("while.1", "(f32[4,8,64])", base, 90, "while"),
                op(mix, "f32[4,8,64]{2,1,0}", base + 5, 10),
                op(experts, "bf16[8,64]{1,0}", base + 20, 30),
                op("keys.3", f"bf16[{ctx},192]{{1,0}}", base + 55, 7),
                op("tlm.kernel.hc_map.5", "f32[4,8]{1,0}", base + 70, 4,
                   "custom-call")]
    trace = tr.Trace({
        "devices": [{"name": "/device:TPU:0", "ops": ops, "async": [],
                     "modules": [["jit__chunk", 0, 95 * MS],
                                 ["jit__chunk", 100 * MS, 95 * MS]]}],
        "host_spans": [["bench.block", 0, 200 * MS]]})
    notes = []
    run = types.SimpleNamespace(
        hlo_texts={"jit__chunk@16": text(16, "fusion.7", "fusion.8"),
                   "jit__chunk@32": text(32, "fusion.8", "fusion.7"),
                   "jit__decode": "unrelated"},
        note=notes.append, config={"hc_mult": 4, "hidden_size": 64},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    read = lambda scope: hyper_latent.scope_self_ms(
        trace, {}, {"module": "jit__chunk", "scope": scope}, run)
    assert read(r"tlm\.resid\.hc_") == pytest.approx(14.0)   # 10 + 4, both
    assert read(r"tlm\.moe\.experts") == pytest.approx(30.0)
    assert read(r"tlm\.attn\.mla") == pytest.approx(7.0)
    assert len(notes) == 1 and "'jit__chunk@16': 1" in notes[0] \
        and "'jit__chunk@32': 1" in notes[0]
    # with ONE text for both runs the second run's mix would be read as
    # an expert product: 10 + 4 in one run, 30 + 4 in the other
    run.hlo_texts.pop("jit__chunk@32")
    vars(run).pop("hyper_latent_programs")
    assert read(r"tlm\.resid\.hc_") == pytest.approx((14.0 + 34.0) / 2)
    # the share: the least time of the wrappers' bytes over 14 ms
    run.hlo_texts["jit__chunk@32"] = text(32, "fusion.8", "fusion.7")
    vars(run).pop("hyper_latent_programs")
    share = hyper_latent.scope_roofline(
        trace, {"chunk_tokens": 8, "layers": 3},
        {"module": "jit__chunk", "scope": r"tlm\.resid\.hc_",
         "kernel": "hc_chunk"}, run)
    nbytes = 6 * 4.0 * (4 * 8 * 4 * 64 + 2 * 8 * 64)
    assert share == pytest.approx(100 * (nbytes / 819e9) / 14e-3, rel=1e-3)
    # no such scope, no texts, no trace: nothing
    assert read(r"tlm\.attn\.window") is None
    assert hyper_latent.scope_self_ms(
        None, {}, {"module": "jit__chunk", "scope": "x"}, run) is None
    run.hlo_texts = {}
    vars(run).pop("hyper_latent_programs")
    assert read(r"tlm\.resid\.hc_") is None
