"""The controls and planted faults of a training cell, at a size a test
run holds, in the program's place: each has to fail the limits of the
chip cell.  The configuration states float32 storage with matmul
operands at the TPU's default precision (bfloat16), so one control is
the reference with its matmul operands rounded to fp8 and the other is
the reference stored and computed in bfloat16.  The tiny table keeps the
chip cell's ratio of table rows to rows a step touches, on which the
bfloat16 table's drift depends."""
import pytest

from bench import calibrate, run
from bench.tests import tiny

# the drift over the change goes as sqrt(table rows / rows a step
# touches): 2,814,000 over ~2,600 on the chip, 160,400 over ~140 here
CONTROL = dict(tiny.TINY, name="tiny-control",
               tables=dict(tiny.TINY["tables"],
                           sizes=[80_000, 80_000, 100, 100, 100, 100]))


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny.copy_bench(tmp_path_factory.mktemp("b"))
    tiny.add_cell(root, "tiny.control", CONTROL, "tiny.train", tiny.TRAIN,
                  tiny.limits("wdl-s1.esd.1c"))
    return run.Cell(root, "tiny.control")


@pytest.fixture(scope="module", params=[3, 2 ** 31 + 5])
def readings(cell, request):
    return {r["kind"]: r for r in calibrate.controls(cell, request.param)}


@pytest.mark.parametrize("kind", ["control_fp8", "control_bf16",
                                  "fault_half_batch"])
def test_control_and_faults_fail(readings, kind):
    assert not readings[kind]["correct"], readings[kind]
