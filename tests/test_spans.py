"""The program's host spans and named scopes.

A profiled apply records ``repro.apply`` with its five steps nested in
order; a profiled ``cg_solve`` records one ``repro.cg.iteration`` per
iteration, each holding its apply; the numpy solver module loads without
jax.  The shard programs' scopes are checked on four host devices in
tests/multidev/spans_prog.py.
"""
import glob
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import jax

import repro.api as nap
from repro.amg.solve import cg_solve
from repro.core.topology import Topology
from repro.sparse.generators import poisson_2d

ROOT = pathlib.Path(__file__).resolve().parents[1]
STEPS = ["repro.pack", "repro.stage", "repro.dispatch", "repro.fetch",
         "repro.unpack"]


def profiled(tmp_path, fn):
    """Host events named ``repro.*`` of a profiler trace around ``fn()``,
    as (name, start_ns, end_ns) sorted by start, longer first."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("repro.")]
    return sorted(out, key=lambda ev: (ev[1], -ev[2]))


def inside(outer, events):
    return [ev for ev in events if outer[1] <= ev[1] and ev[2] <= outer[2]
            and ev is not outer]


@pytest.fixture(scope="module")
def op():
    a = poisson_2d(12)
    return nap.operator(a, topo=Topology(1, 1), local_compute="ell")


@pytest.mark.parametrize("integrity", ["off", "detect"])
def test_apply_records_its_five_steps_nested(tmp_path, integrity):
    a = poisson_2d(12)
    x = np.arange(a.shape[1], dtype=np.float32)
    o = nap.operator(a, topo=Topology(1, 1), local_compute="ell",
                     integrity=integrity)
    o @ x                                  # compile outside the trace
    events = profiled(tmp_path, lambda: o @ x)
    applies = [ev for ev in events if ev[0] == "repro.apply"]
    assert len(applies) == 1
    children = inside(applies[0], events)
    steps = [ev for ev in children if ev[0] in STEPS]
    assert [ev[0] for ev in steps] == STEPS
    for before, after in zip(steps, steps[1:]):
        assert before[2] <= after[1]       # one after the other
    verify = [ev for ev in children if ev[0] == "repro.verify"]
    assert len(verify) == (integrity != "off")
    assert {ev[0] for ev in events} == {"repro.apply", *STEPS,
                                        *(ev[0] for ev in verify)}


def test_cg_records_one_span_per_iteration(tmp_path, op):
    b = np.ones(op.shape[0])
    cg_solve(None, b, tol=0.0, maxiter=2, spmv=op)
    seen = []
    events = profiled(tmp_path, lambda: cg_solve(
        None, b, tol=0.0, maxiter=5, spmv=op,
        callback=lambda it, x: seen.append(it)))
    iters = [ev for ev in events if ev[0] == "repro.cg.iteration"]
    init = [ev for ev in events if ev[0] == "repro.cg.init"]
    assert seen == [1, 2, 3, 4, 5] and len(iters) == 5 and len(init) == 1
    for span in iters + init:
        assert [ev[0] for ev in inside(span, events)
                if ev[0] == "repro.apply"] == ["repro.apply"]
    assert len([ev for ev in events if ev[0] == "repro.apply"]) == 6


def test_solver_loads_without_jax():
    code = ("import sys, repro.amg.solve, repro.core.executors\n"
            "from repro.core.spans import span\n"
            "with span('repro.x'):\n"
            "    pass\n"
            "print('jax' in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "False"


@pytest.mark.multidev
def test_shard_programs_carry_their_scopes_4dev():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)  # the program sets its own device count
    p = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "multidev" / "spans_prog.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    assert "SPANS OK" in p.stdout
