"""Each fault that a cell can have, planted under the timed path, makes
``correct`` come out false; the same run without the fault is correct.

The runs skip the harness's look for a chip and drive the rest of a run
on the CPU at the configurations' test sizes.  Faults:

answer_altered    one element of every apply's device result is changed
                  where the executor fetches it;
state_unchanged   the apply returns its operand (one-chip apply cells),
                  or the CG solve returns its start x0 = 0 (CG cell);
exchange_dropped  every all_to_all of the four-chip program returns
                  zeros (x4 cell, in a four-device subprocess).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run as R

HERE = os.path.dirname(os.path.abspath(__file__))


def altered(fetch):
    """``fetch_mesh_array`` with one element of its result changed."""
    def fetch_altered(w):
        out = np.array(fetch(w))
        out.reshape(-1)[0] += 1.0
        return out
    return fetch_altered


def _cpu_run(cell_name):
    import jax
    cell = R.load_cell(cell_name)
    cell.cfg.update(cell.cfg["cpu_test_overrides"])
    return R.run_cell(cell, 2**31 + 3, 0.3, False, jax.devices()[:1],
                      R.CompileClock(), None)


def _plant(monkeypatch, fault, cell):
    import repro.amg.solve as solve
    import repro.api as api
    import repro.mesh.buffers as buffers
    if fault == "answer_altered":
        monkeypatch.setattr(buffers, "fetch_mesh_array",
                            altered(buffers.fetch_mesh_array))
    elif cell.endswith("cg50"):
        def unchanged(a, b, tol=0.0, maxiter=1, spmv=None, callback=None,
                      **_):
            x = np.zeros_like(b)
            for it in range(1, maxiter + 1):
                if callback is not None:
                    callback(it, x)
            return x, maxiter, 1.0
        monkeypatch.setattr(solve, "cg_solve", unchanged)
    else:
        monkeypatch.setattr(api.NapOperator, "__call__",
                            lambda self, x, **_: np.asarray(x))


@pytest.mark.parametrize("fault", ["answer_altered", "state_unchanged"])
@pytest.mark.parametrize("cell", ["hpcg_27pt_104.spmv", "hpcg_27pt_104.cg50",
                                  "paper_random_25.spmv"])
def test_fault_makes_the_run_incorrect(cell, fault, monkeypatch):
    _plant(monkeypatch, fault, cell)
    res = _cpu_run(cell)
    assert res["correct"] is False, res["checks"]


@pytest.fixture(scope="module")
def x4_runs():
    p = subprocess.run([sys.executable, os.path.join(HERE, "x4_prog.py")],
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return {r["run"]: r for r in map(json.loads, p.stdout.splitlines())}


@pytest.mark.parametrize("run,correct", [
    ("sound", True), ("sound_traced", True),
    ("answer_altered", False), ("exchange_dropped", False)])
def test_four_chip_cell_on_four_cpu_devices(x4_runs, run, correct):
    assert x4_runs[run]["correct"] is correct, x4_runs[run]["checks"]


def test_four_chip_traced_run_reports_its_layers(x4_runs):
    assert set(x4_runs["sound"]["metrics"]) == {"spmv_ms.x4",
                                                "spmv_p95_ms.x4", "setup_s"}
    # no peaks table entry for the CPU: the roofline readers stay silent
    assert set(x4_runs["sound_traced"]["metrics"]) == {
        "plan_compile_s", "host_ms.x4", "device_idle_pct.x4",
        "collective_ms.x4"}
