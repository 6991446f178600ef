"""``runners/serve_hybrid_ssm.py`` end to end on the CPU, on a toy cell
added as files only (``cells/manifest_hybrid_ssm.json``): the model's
build with its two per-slot states, the reference check through chunks
that carry the state and paged decode steps between them, the fill, the
window, the counters and every new per-layer reader, so that the first
run of the real cell on a chip is not the runner's first run.  Shape
only: numbers from these runs mean nothing."""

import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "tiny-hybrid-backlog"
REAL_TRAFFIC = os.path.join(
    HERE, "..", "traffic", "backlog-chat-in-reasoning-out.json")
REAL_CONFIG = os.path.join(HERE, "..", "configs", "falcon-h1-34b-depth4.json")


@pytest.fixture(scope="module")
def bench():
    import run as bench      # benchmarks/run.py, by conftest's sys.path

    return bench


@pytest.fixture(scope="module")
def manifest(bench):
    return bench.load_json(
        os.path.join(HERE, "cells", "manifest_hybrid_ssm.json"))


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


def test_traced_line_reads_counters_and_spans_and_leaves_device_out(lines):
    """The counters and the batcher's spans are read; the device-trace
    readers (the two kernels' rooflines, the mixer's scope) find no TPU
    plane in a CPU trace, return nothing, and their metrics are left
    out."""
    line = lines[True]
    assert line["correct"] is True
    metrics = line["metrics"]
    assert set(metrics) == {"tiny.slots_live_mean", "tiny.host_ms_per_pump",
                            "tiny.ssm_chunks_carried_share"}
    assert metrics["tiny.slots_live_mean"]["value"] == 4
    # prompts of 4-20 tokens (pre-aged up to 40) in 8-token chunks: some
    # chunks start from zeros, some from a carried state
    assert 0 < metrics["tiny.ssm_chunks_carried_share"]["value"] < 100


@pytest.mark.parametrize("name,counters", [
    ("ssm_state_update", {"ssm_state_bytes_per_step": 8.0e8,
                          "live_slot_layers_per_step": 384.0}),
    ("ssd_chunk_scan", {"chunk_tokens": 512})])
def test_rooflines_at_the_real_cell(bench, name, counters):
    """The counts at the published widths: the state update is bound by
    its bytes, and its bytes are what the step counted."""
    import rooflines
    import rooflines_hybrid_ssm

    config = bench.load_json(REAL_CONFIG)
    flops, nbytes = rooflines_hybrid_ssm.KERNELS[name](counters, config)
    assert flops > 0 and nbytes > 0
    least = rooflines.least_seconds(flops, nbytes, "TPU v5 lite")
    if name == "ssm_state_update":
        assert nbytes == counters["ssm_state_bytes_per_step"]
        assert least == nbytes / 819e9
    if name == "ssd_chunk_scan":
        # four layers of 512 tokens: 11 GFLOP (56 us at the peak) and
        # 109 MB (133 us): bound by its bytes
        assert 1e9 < flops < 2e10 and least == nbytes / 819e9 < 2e-4


def test_the_real_cells_lengths_and_check(bench):
    from runners.serve_latent_moe import FileOrderBacklog, check_plan
    import traffic as traffic_gen

    tr = bench.load_json(REAL_TRAFFIC)
    pairs = traffic_gen.length_multiset(tr, tr["generation"])
    assert all(256 <= p <= 1536 and 1024 <= o <= 3072 for p, o in pairs)
    assert max(p + o for p, o in pairs) <= tr["max_total_len"] == \
        tr["pages_per_seq"] * tr["page_size"]
    # the pre-aged first generation (a prompt and the aged share of its
    # output) fits the prompt window, and needs its longest bucket
    C = tr["prefill_chunk"]
    aged = max(len(r.prompt) for r in FileOrderBacklog(
        tr, 97, 2**31 + 5).next_generation())
    assert tr["max_prompt_len"] - C < aged <= tr["max_prompt_len"]
    n, new, steps = check_plan(tr)
    # three chunks, the last one padded, as many as the window's longest
    # prompt: the check runs every chunk program the window runs
    assert n >= 1025 and -(-n // C) == 3 == -(-tr["prompt"]["hi"] // C)
    assert n % C and steps >= 16
    assert tr["slots"] == 96 and C == 512


def test_parent_without_the_model_exits_at_once(bench, manifest,
                                                monkeypatch):
    """A checkout whose program has no hybrid model: the runner's build
    raises ``SystemExit`` before any device work."""
    import builtins

    real = builtins.__import__

    def no_model(name, *args, **kwargs):
        if name == "apex_tpu.models.falcon_h1":
            raise ImportError("no module named falcon_h1")
        return real(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", no_model)
    with pytest.raises(SystemExit, match="no hybrid state-space model"):
        bench.run_cell(manifest, CELL, 1, 1.0, False, require_tpu=False)
