"""The reduction from a trace to numbers, on a hand-made trace with known
answers and on a small recorded one (``recorded_trace.json``: a prefill
and three decode steps of GPT-2 345M on one v5e, cut from a real run)."""

import json
import os

import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6    # ns


def _op(name, start, dur, opcode="fusion", target="", operands=2):
    return {"name": name, "opcode": opcode, "shape": "f32[8]",
            "operands": operands, "target": target,
            "start": start * MS, "dur": dur * MS}


@pytest.fixture()
def made():
    """Two runs of ``jit_step`` (10..50 and 60..100 ms) on chip 0, each a
    fusion, a while with two nested operations and a custom call; a 10 ms
    gap between them while the host sits in ``bench.block``."""
    ops = []
    for base in (10, 60):
        ops += [_op("fusion.1", base, 5),
                _op("while.2", base + 5, 25, opcode="while"),
                _op("body_dot.3", base + 6, 10),
                _op("body_kernel.4", base + 17, 12, opcode="custom-call",
                    target="tpu_custom_call", operands=3),
                _op("adam.5", base + 30, 10)]
    return tr.Trace({
        "devices": [{"name": "/device:TPU:0", "ops": ops, "async": [
            dict(_op("all-reduce-start.7", 12, 6, opcode="all-reduce-start"))],
            "modules": [["jit_step", 10 * MS, 40 * MS],
                        ["jit_step", 60 * MS, 40 * MS]]},
            {"name": "/device:TPU:1", "ops": [_op("fusion.1", 10, 45)],
             "async": [], "modules": [["jit_step", 10 * MS, 45 * MS]]}],
        "host_spans": [["bench.dispatch", 5 * MS, 1 * MS],
                       ["bench.block", 6 * MS, 99 * MS]]})


def test_busy_is_a_union_and_idle_is_its_complement(made):
    assert made.window_s == pytest.approx(0.100)        # 5 .. 105 ms
    # chip 0 is busy 80 ms (nesting not counted twice), chip 1 45 ms
    assert made.busy_s() == pytest.approx((0.080 + 0.045) / 2)
    assert made.idle_share() == pytest.approx(1 - 0.0625 / 0.100)


def test_self_time_leaves_out_what_is_nested(made):
    top = dict((k.split()[0], v) for k, v in made.top_ops(10))
    assert top["jit_step/while.2"] == pytest.approx(2 * 0.003)   # 25-10-12
    assert top["jit_step/body_kernel.4"] == pytest.approx(2 * 0.012)


def test_gaps_are_named_by_the_host_span_and_the_programs_around(made):
    gaps = dict(made.idle_gaps())
    assert gaps["bench.block:jit_step->jit_step"] == pytest.approx(0.010)
    assert gaps["bench.dispatch:start->jit_step"] == pytest.approx(0.005)
    assert sum(gaps.values()) == pytest.approx(0.020)


def test_a_scope_counts_outermost_operations_whole(made):
    scopes = {"fusion.1": "jit(step)/tlm.fwd_bwd/mul",
              "while.2": "jit(step)/tlm.fwd_bwd/while",
              "body_dot.3": "jit(step)/tlm.fwd_bwd/while/body/dot",
              "adam.5": "jit(step)/tlm.optimizer/add"}
    assert made.scope_ms("jit_step", "tlm.fwd_bwd", scopes) == \
        pytest.approx(30.0)
    assert made.scope_ms("jit_step", "tlm.optimizer", scopes) == \
        pytest.approx(10.0)
    assert made.scope_ms("jit_other", "tlm.optimizer", scopes) is None


def test_kernels_programs_and_collectives_are_found(made):
    calls = made.kernel_calls("jit_step", "tpu_custom_call")
    assert [c.operands for c in calls] == [3, 3]
    assert made.module_ms("jit_step") == pytest.approx(40.0)
    assert made.collective_ms("jit_step") == pytest.approx(3.0)  # 6 ms / 2


def test_instruction_text_is_parsed():
    text = ('%checkpoint.9 = (bf16[256,1024,128]{2,1,0:T(8,128)(2,1)}, '
            'bf16[256,1024,128]{2,1,0:T(8,128)(2,1)}) custom-call('
            'bf16[256,1024,128]{2,1,0:T(8,128)(2,1)} %bitcast.411, '
            'f32[256,1,1024]{2,1,0:T(1,128)S(1)} %custom-call.33, '
            'f32[256,1,1024]{2,1,0} %broadcast_in_dim.294), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    rec = tr.parse_instruction(text)
    assert rec["name"] == "checkpoint.9" and rec["opcode"] == "custom-call"
    assert rec["operands"] == 3 and rec["target"] == "tpu_custom_call"
    assert tr.parse_instruction("copy.55")["name"] == "copy.55"
    hlo = ('ENTRY %main {\n  %fusion.338 = f32[8]{0} fusion(f32[8]{0} %p), '
           'kind=kLoop, metadata={op_name="jit(train_step)/tlm.fwd_bwd/mul" '
           'source_file="x.py"}\n  ROOT adam.5 = f32[8]{0} add(%a, %b), '
           'metadata={op_name="jit(train_step)/tlm.optimizer/add"}\n}')
    assert tr.hlo_scopes(hlo) == {
        "fusion.338": "jit(train_step)/tlm.fwd_bwd/mul",
        "adam.5": "jit(train_step)/tlm.optimizer/add"}


def test_recorded_trace():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        T = tr.Trace(json.load(f))
    assert 0.4 < T.window_s < 0.5
    assert 0 < T.busy_s() < T.window_s
    assert 0.0 < T.idle_share() < 0.1
    assert 90 < T.module_ms("jit__decode") < 105        # three decode steps
    assert 50 < T.module_ms("jit__prefill") < 56
    kernels = T.kernel_calls("jit__decode", "tpu_custom_call")
    assert len(kernels) == 3 * 24                       # one a layer a step
    assert all(k.shape.startswith("bf16[32,16,1,64]") for k in kernels)
    assert T.top_ops(1)[0][0].startswith("jit__decode/copy.")
    assert all(name.startswith("bench.pump:") for name, _ in T.idle_gaps())
