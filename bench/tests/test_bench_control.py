"""The control, at a size a CPU test run can hold: the reference put in the
program's place, in the precision below the configuration's (float8 matmul
operands) or with a fault planted, comes out not correct under the cell's
limits."""
import jax
import pytest

import bench.lib.drivers.train as train
from bench_smoke import zamba2_cell

LEVELS = ["fp8", "fault:half_batch", "fault:altered"]


@pytest.fixture(scope="module")
def controls():
    return train.control(zamba2_cell(), jax.devices()[:1], LEVELS)


@pytest.mark.parametrize("level", LEVELS)
def test_control_is_not_correct(controls, level):
    assert not all(c.ok for c in controls[level]), (level, controls[level])
