"""The control, the reference computed in bfloat16 in the program's
place, fails at least one of each cell's limits."""

import pytest

import study
import tiny


@pytest.mark.parametrize("name", ["deep64_train_b1024",
                                  "mnist_fig14_train_b10",
                                  "deep64_serve_poisson"])
def test_control_fails_a_limit(name):
    cell = tiny.cell(name)
    readings = study.control(cell, 2**31 + 9, 0.5)
    over = [k for k, v in readings.items()
            if k in cell.limits and v > cell.limits[k]]
    assert over, (readings, cell.limits)
