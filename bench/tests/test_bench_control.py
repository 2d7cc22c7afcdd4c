"""The control: the reference solver in the program's place. At full
float32 the cells' limits pass it; one precision step below (`high`, the
three-pass bfloat16 products) they refuse it. At a tiny size on the CPU;
the readings at the cells' own size on the chip are in PERF.md."""
import pytest

from bench import control, run

from bench.tests.small import SMALL, small_cell


@pytest.mark.parametrize("precision,correct", [("highest", True),
                                               ("high", False)])
@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_is_refused_and_the_reference_passes(name, precision,
                                                     correct):
    cell = small_cell(name)
    (out,) = control.control(cell, [2 ** 31 + 3], precision, 1,
                             SMALL[name])
    assert out["correct"] is correct, out["numbers"]
