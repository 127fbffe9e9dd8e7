"""tools/hlo_same.py: two optimized-HLO texts are the same program when their
instruction lists agree with metadata, kernels' payloads and the source
tables left out; an empty list is an error, never "equal"."""
import importlib.util
import os

import pytest

_spec = importlib.util.spec_from_file_location(
    "hlo_same", os.path.join(os.path.dirname(__file__), "..", "tools",
                             "hlo_same.py"))
hlo_same = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(hlo_same)

MODULE = """HloModule jit_step, entry_computation_layout={()->f32[]}

FileNames
1 "/root/repo/paddle_tpu/ops/fused_ops.py"

FileLocations
1 {file_name_id=1 function_name_id=1 line=%(line)d}

ENTRY main {
  a = f32[8]{0} parameter(0), metadata={op_name="x" source_line=%(line)d}
  k = f32[8]{0} custom-call(a), custom_call_target="tpu_custom_call", backend_config="%(payload)s"
  ROOT r = f32[] reduce(k), metadata={op_name="y"}
}
"""


def _text(line=260, payload="abc", tables_last=False):
    text = MODULE % {"line": line, "payload": payload}
    if tables_last:   # a CPU dump ends with its tables
        head, _, rest = text.partition("FileNames")
        tables, _, body = rest.partition("ENTRY")
        text = head + "ENTRY" + body + "\nFileNames" + tables
    return text.splitlines()


@pytest.mark.parametrize("tables_last", [False, True])
def test_source_lines_and_payloads_are_left_out(tables_last):
    a = hlo_same.instructions(_text(260, "abc", tables_last))
    b = hlo_same.instructions(_text(274, "xyz", tables_last))
    assert len(a) == 5 and hlo_same.compare(a, b) is None


def test_another_instruction_is_a_difference():
    a = hlo_same.instructions(_text())
    b = [x.replace("reduce(k)", "reduce(a)") for x in a]
    assert len(hlo_same.compare(a, b)) == 1
    assert hlo_same.compare(a, b[:-1]) is not None


def test_nothing_to_compare_is_an_error(tmp_path):
    with pytest.raises(ValueError, match="no instructions"):
        hlo_same.compare([], [])
    # a text that is all tables, as one cut at the first table of a TPU dump
    assert hlo_same.instructions(_text()[:6]) == []
    first, second = tmp_path / "a", tmp_path / "b"
    for d in (first, second):
        d.mkdir()
        (d / "module_0001.jit_step.tpu_after_optimizations.txt").write_text(
            "\n".join(_text()[:6]))
    assert hlo_same.main(str(first), str(second)) == 1
    assert hlo_same.main(str(first), str(tmp_path / "none")) == 1
