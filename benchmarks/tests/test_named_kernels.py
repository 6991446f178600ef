"""``readers/named_kernels.py`` on a hand-made trace: kernels are found
by the ``tlm.kernel.*`` scope in the compiled text's ``op_name``, at any
depth, by their self time, the rematerialised forward apart."""

import types

import pytest

import trace_reduce as tr
from readers import named_kernels as nk

MS = 1e6    # ns
FWD, BWD = r"tlm\.kernel\.fmha_\w+\.fwd", r"tlm\.kernel\.fmha_\w+\.bwd"
SCOPES = {
    "while.2": "jit(train_step)/tlm.fwd_bwd/while",
    "tlm.kernel.fmha_mid.fwd.4":
        "jit(train_step)/tlm.fwd_bwd/jvp()/while/body/"
        "tlm.kernel.fmha_mid.fwd/pallas_call",
    "while.6": "jit(train_step)/tlm.fwd_bwd/transpose(jvp())/while",
    "tlm.kernel.fmha_mid.fwd.7":
        "jit(train_step)/tlm.fwd_bwd/transpose(jvp())/while/body/checkpoint/"
        "rematted_computation/tlm.kernel.fmha_mid.fwd/pallas_call",
    "tlm.kernel.fmha_mid.bwd.8":
        "jit(train_step)/tlm.fwd_bwd/transpose(jvp())/while/body/checkpoint/"
        "tlm.kernel.fmha_mid.bwd/pallas_call",
    "adam.9": "jit(train_step)/tlm.optimizer/add",
}


def _op(name, start, dur, opcode="fusion", target=""):
    return {"name": name, "opcode": opcode, "shape": "f32[8]",
            "operands": 3, "target": target, "start": start * MS,
            "dur": dur * MS}


def _kernel(name, start, dur):
    return _op(name, start, dur, "custom-call", "tpu_custom_call")


@pytest.fixture()
def trace():
    """Two runs of ``jit_train_step`` (0..100 and 100..200 ms): a forward
    ``while`` holding the forward kernel once (6 ms), a backward
    ``while`` holding the recomputed forward (7 ms) and the backward
    kernel (20 ms), then the optimizer."""
    ops = []
    for base in (0, 100):
        ops += [_op("while.2", base, 30, opcode="while"),
                _kernel("tlm.kernel.fmha_mid.fwd.4", base + 5, 6),
                _op("while.6", base + 30, 60, opcode="while"),
                _kernel("tlm.kernel.fmha_mid.fwd.7", base + 35, 7),
                _kernel("tlm.kernel.fmha_mid.bwd.8", base + 45, 20),
                _op("adam.9", base + 90, 10)]
    return tr.Trace({
        "devices": [{"name": "/device:TPU:0", "ops": ops, "async": [],
                     "modules": [["jit_train_step", 0, 100 * MS],
                                 ["jit_train_step", 100 * MS, 100 * MS]]}],
        "host_spans": [["bench.block", 0, 200 * MS]]})


def _run(scopes):
    notes = []
    return types.SimpleNamespace(scopes=lambda module: scopes,
                                 note=notes.append, notes=notes)


def _read(trace, run, scope, **more):
    return nk.scope_ms(trace, {}, dict(module="jit_train_step", scope=scope,
                                       **more), run)


def test_a_kernel_inside_a_while_is_counted_once_and_by_name(trace):
    run = _run(SCOPES)
    assert _read(trace, run, FWD, rematted=False) == pytest.approx(6.0)
    assert _read(trace, run, FWD, rematted=True) == pytest.approx(7.0)
    assert _read(trace, run, BWD) == pytest.approx(20.0)
    assert _read(trace, run, FWD) == pytest.approx(13.0)    # both forwards


def test_the_three_parts_sum_to_the_calls_the_roofline_metric_counts(trace):
    run = _run(SCOPES)
    parts = (_read(trace, run, FWD, rematted=False)
             + _read(trace, run, FWD, rematted=True) + _read(trace, run, BWD))
    calls = trace.kernel_calls("jit_train_step", "tpu_custom_call")
    assert parts == pytest.approx(sum(c.dur for c in calls) / 2 / MS)


def test_no_kernel_scope_in_the_text_is_nothing_not_zero(trace):
    """A program from before the names (or an executable out of an old
    compile cache): None, and the note says why."""
    old = {k.replace("tlm.kernel.fmha_mid.", "custom-call."):
           v.replace("tlm.kernel.fmha_mid.fwd/", "").replace(
               "tlm.kernel.fmha_mid.bwd/", "") for k, v in SCOPES.items()}
    run = _run(old)
    assert _read(trace, run, FWD, rematted=False) is None
    assert _read(trace, run, BWD) is None
    assert len(run.notes) == 1 and "no tlm.kernel.* scope" in run.notes[0]
    assert _read(trace, _run({}), BWD) is None
    assert _read(None, _run(SCOPES), BWD) is None
    # named kernels, but none of this kind: that IS zero
    assert _read(trace, _run(SCOPES), r"tlm\.kernel\.paged_decode") == 0.0
