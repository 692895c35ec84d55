"""The control: the float64 reference computed one precision down
(bfloat16 values and operands, float32 sums), put in the program's place,
has to come out as not correct in every cell.  At the cells' own size it
runs on the chip through ``bench/readings.py --control``; here at the
configurations' test sizes on the CPU."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import run as R
from bench.reference import Bf16Control, Reference

with open(os.path.join(R.REPO, "BENCHMARK.json")) as _f:
    ONE_CHIP = [w["name"] for w in json.load(_f)["workloads"]
                if w["chips"] == 1]


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_control_is_not_correct(cell):
    import jax
    c = R.load_cell(cell)
    c.cfg.update(c.cfg["cpu_test_overrides"])
    res = R.run_cell(c, 2**31 + 17, 0.3, False, jax.devices()[:1],
                     R.CompileClock(), None, control=True)
    assert res["correct"] is False, res["checks"]


def test_control_rounds_to_bfloat16_and_sums_in_float32():
    indptr = np.array([0, 2, 3])
    indices = np.array([0, 1, 1])
    data = np.array([1.0 + 2.0 ** -10, 1.0, 3.0])
    ctl = Bf16Control(indptr, indices, data, (2, 2))
    y = ctl(np.array([1.0, 2.0 ** -12], np.float32))
    assert y.dtype == np.float32
    # 1 + 2^-10 rounds to 1 in bfloat16; 2^-12 survives as an operand
    assert y[0] == np.float32(1.0 + 2.0 ** -12)
    ref_y, mag = Reference(indptr, indices, data, (2, 2)).apply(
        np.array([1.0, 2.0 ** -12]))
    assert ref_y[0] == 1.0 + 2.0 ** -10 + 2.0 ** -12
    assert mag[1] == 3.0 * 2.0 ** -12
