"""Forward ELL column ids composed with Algorithm 3's buffer gathers.

The four-device checks run once, as one subprocess
(``tests/multidev/composed_ell_prog.py``), and each of its cases is a
test of its own here: bit-identity of the composed program with the
product over the materialised ``[v_loc | bnode | boff]`` domain, for
every comm, nv, matrix family and operator shape, and the integrity
programs on the composed ids.  The counter and the plan arrays are
checked in process.
"""
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

import repro.api as nap
from repro.core.partition import contiguous_partition
from repro.core.spmv_jax import compile_plan, padded_traffic
from repro.core.topology import Topology
from repro.sparse import CSR, random_fixed_nnz

ROOT = pathlib.Path(__file__).resolve().parents[1]
COMMS = ("nap", "multistep", "standard")
CASES = [f"{comm}-nv{nv}-{fam}-{shape}" for comm in COMMS for nv in (1, 8)
         for fam in ("random", "stencil", "kron")
         for shape in ("square", "rect")]
INTEGRITY_CASES = [f"integrity-{comm}" for comm in COMMS]


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)  # the program sets its own device count
    p = subprocess.run(
        [sys.executable,
         str(ROOT / "tests" / "multidev" / "composed_ell_prog.py")]
        + CASES + INTEGRITY_CASES,
        capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout


def _case(results, name):
    m = re.search(rf"^CASE {re.escape(name)} (OK|FAIL)$", results, re.M)
    assert m, f"case {name} did not run:\n{results}"
    assert m.group(1) == "OK", results[m.start():]


@pytest.mark.multidev
@pytest.mark.parametrize("name", CASES)
def test_composed_ell_forward_is_bit_identical(results, name):
    _case(results, name)


@pytest.mark.multidev
@pytest.mark.parametrize("name", INTEGRITY_CASES)
def test_composed_ell_integrity_catches_faults(results, name):
    _case(results, name)


def _plan(comm, local_compute="auto"):
    topo = Topology(2, 2)
    a = random_fixed_nnz(200, 9, seed=4)
    part = contiguous_partition(200, topo.n_procs)
    return compile_plan(a, part, topo, comm=comm, cache=False,
                        local_compute=local_compute)


@pytest.mark.parametrize("comm", COMMS)
def test_buffer_gather_elems_counts_the_buffer_step(comm):
    c = _plan(comm)
    gathered = (c.pads["bnode"] + c.pads["boff"] if comm != "standard"
                else c.pads["buf"])
    for fmt, want in (("ell", 0), ("bsr", gathered), ("coo", gathered)):
        assert padded_traffic(c, local_compute=fmt)["buffer_gather_elems"] \
            == want, fmt


def test_operator_stats_report_the_resolved_format():
    topo = Topology(2, 2)
    a = random_fixed_nnz(200, 9, seed=4)
    part = contiguous_partition(200, topo.n_procs)
    for fmt in ("ell", "coo"):
        op = nap.operator(a, topo=topo, part=part, backend="shardmap",
                          local_compute=fmt, cache=False)
        c = op.executor.compiled
        want = 0 if fmt == "ell" else c.pads["bnode"] + c.pads["boff"]
        assert op.stats()["buffer_gather_elems"] == want


@pytest.mark.parametrize("comm", COMMS)
def test_composed_ids_are_the_packed_ids_through_the_map(comm):
    """Every composed id is its packed-domain id sent through
    ``recv_domain_map``; padding stays -1 and values keep their slots."""
    c = _plan(comm, local_compute="ell")
    c.ensure_ell()
    packed, vals, _, _ = c.packed_ell()
    composed, remap = c.arrays["ell_cols"], c.recv_domain_map()
    assert remap.shape == (c.topo.n_procs, c.packed_x_len)
    assert composed.max() < c.recv_x_len
    assert np.array_equal(vals, c.arrays["ell_vals"])
    assert np.array_equal(packed < 0, composed < 0)
    for r in range(c.topo.n_procs):
        real = packed[r] >= 0
        assert np.array_equal(composed[r][real], remap[r][packed[r][real]])


@pytest.mark.parametrize("comm", COMMS)
def test_swap_values_refreshes_the_received_domain_abft(comm):
    c = _plan(comm, local_compute="ell")
    c.ensure_abft_recv()
    before = c.arrays["abft_col_recv"].copy()
    a = c.a_ref
    changed = c.swap_values(CSR(indptr=a.indptr, indices=a.indices,
                                data=a.data * 2.0, shape=a.shape))
    assert {"abft_col_recv", "abft_col_abs_recv"} <= set(changed)
    np.testing.assert_allclose(c.arrays["abft_col_recv"], 2.0 * before,
                               rtol=1e-6)
    # the received-domain sums are the packed ones moved through the map
    remap = c.recv_domain_map()
    for r in range(c.topo.n_procs):
        moved = np.zeros(c.recv_x_len)
        np.add.at(moved, remap[r], c.arrays["abft_col"][r].astype(np.float64))
        np.testing.assert_array_equal(c.arrays["abft_col_recv"][r],
                                      moved.astype(np.float32))
