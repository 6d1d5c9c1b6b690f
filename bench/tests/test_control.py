"""The control, the reference computed in float8 in the program's place, and
the half-batch fault come out not correct at a size a test run can hold. On
the chip the same readings run at the cell's own size:
``python3 bench/control.py``."""
import pytest

from bench import common
from bench import control


@pytest.mark.parametrize("reading", ["control", "half_batch"])
def test_train_control_fails(tiny_bench, reading):
    cell = common.load_cell("tiny.train", tiny_bench)
    for seed in (1, 2, 2**36 + 5):
        failed, checks = control.judge(cell, control.train_controls(cell, seed)[reading])
        assert failed, checks
