"""``tools/compiled_text_diff.py``: what it strips from a compiled
program's text, what it renames, and the verdicts it gives — on short
made-up texts (the compiles themselves need the TPU's compiler and run
from the tool, not from the tests)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import compiled_text_diff as ctd

TEXT = """HloModule jit__decode, is_scheduled=true

FileNames
1 "/root/repo/apex_tpu/models/gpt.py"

FunctionNames
1 "GPTModel.decode_step"

FileLocations
1 {file_name_id=1 function_name_id=1 line=1200 end_line=1200 column=8 end_column=30}

StackFrames
1 {file_location_id=1 parent_frame_id=1}

%fused_computation.3 (param_0.7: f32[8]) -> f32[8] {
  %param_0.7 = f32[8]{0} parameter(0)
  ROOT %add.12 = f32[8]{0} add(%param_0.7, %param_0.7), metadata={op_name="jit(_decode)/add" stack_frame_id=1}
}

ENTRY %main.5 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x \\"quoted {brace}\\""}
  ROOT %fusion.2 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.3, frontend_attributes={kernel_metadata={}}
}
"""


def test_strip_takes_locations_and_leaves_the_program():
    out = ctd.strip_metadata(TEXT)
    assert "FileNames" not in out and "StackFrames" not in out
    assert "/root/repo" not in out and "op_name" not in out
    assert "stack_frame_id" not in out
    # an attribute whose name merely ends in "metadata" is the program's
    assert "frontend_attributes={kernel_metadata={}}" in out
    assert "ROOT %add.12 = f32[8]{0} add(%param_0.7, %param_0.7)\n" in out
    assert out.startswith("HloModule jit__decode, is_scheduled=true\n")


def test_names_are_ranked_by_first_appearance():
    a = ctd.strip_metadata(TEXT)
    b = a.replace("add.12", "add.40").replace("param_0.7", "param_0.9")
    assert a != b
    assert ctd.without_names(a) == ctd.without_names(b)
    # another opcode under the same names is another program
    c = a.replace("add(", "multiply(")
    assert ctd.without_names(a) != ctd.without_names(c)
    # so is another operand
    d = a.replace("fusion(%x.1)", "fusion(%fusion.2)")
    assert ctd.without_names(a) != ctd.without_names(d)


@pytest.mark.parametrize("change,verdict,ok", [
    (lambda t: t, "EQUAL", True),
    (lambda t: t.replace("add.12", "add.13"),
     "EQUAL BUT FOR INSTRUCTION NAMES", True),
    (lambda t: t.replace("f32[8]", "f32[16]"), "DIFFERENT", False),
    (lambda t: "", "DIFFERENT", False),
])
def test_compare_gives_one_verdict_a_program(tmp_path, capsys, change,
                                             verdict, ok):
    parent, changed = tmp_path / "parent", tmp_path / "change"
    parent.mkdir()
    changed.mkdir()
    text = ctd.strip_metadata(TEXT)
    (parent / "serve.jit__decode.txt").write_text(text)
    if change(text):
        (changed / "serve.jit__decode.txt").write_text(change(text))
    assert ctd.compare(str(parent), str(changed)) is ok
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("serve.jit__decode")][0]
    assert line.endswith("  " + verdict)
    assert (changed / "serve.jit__decode.diff").exists() is (not ok)
