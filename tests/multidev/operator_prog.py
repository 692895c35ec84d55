"""Multi-device sweep of the NapOperator shardmap backend (subprocess).

For topologies (1,4), (2,2), (4,2), both methods (nap / standard), and
nv in {1, 8}: the operator's forward must match the dense ``A @ x`` and
its ``.T`` the dense ``A.T @ x`` — the transpose compiled from the SAME
plan with reversed send/recv roles — plus the simulate backend as the
float64 cross-oracle.  Also checks: multi-RHS column consistency, the
``donate=True`` entry, per-format local_compute overrides, and that
operator results agree with the raw builder + pack/unpack path
bit-for-bit (the operator adds no numerics of its own).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np

import repro.api as nap
from repro.compat import make_mesh
from repro.core.partition import make_partition
from repro.core.spmv_jax import (compile_plan, forward_program,
                                 pack_vector, unpack_vector)
from repro.core.topology import Topology
from repro.sparse import random_fixed_nnz

TOPOS = [(1, 4), (2, 2), (4, 2)]


def dense_oracle(a, v):
    if v.ndim == 1:
        return a.matvec(v)
    return np.stack([a.matvec(v[:, i]) for i in range(v.shape[1])], axis=1)


def check(topo_shape, kind, nv, seed):
    nn, ppn = topo_shape
    topo = Topology(n_nodes=nn, ppn=ppn)
    rng = np.random.default_rng(seed)
    n = int(rng.integers(topo.n_procs * 3, 72))
    a = random_fixed_nnz(n, int(rng.integers(3, 9)), seed=seed)
    part = make_partition(kind, n, topo.n_procs,
                          indptr=a.indptr, indices=a.indices, seed=seed)
    at = a.transpose()
    v = rng.standard_normal(n) if nv == 1 else rng.standard_normal((n, nv))
    want_f, want_t = dense_oracle(a, v), dense_oracle(at, v)

    sim = nap.operator(a, topo=topo, part=part, method="nap",
                       backend="simulate")
    np.testing.assert_allclose(sim @ v, want_f, rtol=1e-9, atol=1e-11)
    np.testing.assert_allclose(sim.T @ v, want_t, rtol=1e-9, atol=1e-11)

    for method in ("nap", "standard"):
        op = nap.operator(a, topo=topo, part=part, method=method,
                          backend="shardmap", block_shape=(8, 16))
        got_f, got_t = op @ v, op.T @ v
        np.testing.assert_allclose(got_f, want_f, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got_t, want_t, rtol=1e-4, atol=1e-5)
        # the transpose direction reports the format it actually runs —
        # now the transpose autotuner's ell/coo verdict, not a default
        rep = op.autotune_report()
        assert op.T.local_compute in ("ell", "coo")
        assert op.T.local_compute == rep["transpose_resolved"]
        # donate entry returns the same numbers
        np.testing.assert_allclose(op(v, donate=True), got_f,
                                   rtol=1e-6, atol=1e-7)

    # explicit local_compute overrides all agree (nv=8 only, cost)
    if nv == 8:
        for fmt in ("coo", "ell", "bsr"):
            op_f = nap.operator(a, topo=topo, part=part, method="nap",
                                backend="shardmap", block_shape=(8, 16),
                                local_compute=fmt)
            np.testing.assert_allclose(op_f @ v, want_f, rtol=1e-4, atol=1e-5)
            assert op_f.local_compute == fmt


def check_operator_equals_builder_path():
    """The operator is plumbing, not math: its forward must equal the raw
    compile_plan + forward_program + pack/unpack path bit-for-bit."""
    topo = Topology(n_nodes=2, ppn=4)
    mesh = make_mesh((2, 4), ("node", "proc"))
    n, nv = 256, 8
    a = random_fixed_nnz(n, 6, seed=11)
    part = make_partition("contiguous", n, topo.n_procs)
    v = np.random.default_rng(11).standard_normal((n, nv))

    compiled = compile_plan(a, part, topo)
    run = forward_program(compiled, mesh)
    raw = unpack_vector(
        np.asarray(run(pack_vector(v, part, topo, compiled.rows_pad))),
        part, topo)
    op = nap.operator(a, topo=topo, part=part, backend="shardmap", mesh=mesh)
    assert np.array_equal(np.asarray(op @ v), raw)
    print("operator == builder+pack/unpack path, bit-for-bit", flush=True)


def main():
    seed = 300
    for topo_shape in TOPOS:
        for nv in (1, 8):
            kind = ["contiguous", "strided", "balanced"][seed % 3]
            check(topo_shape, kind, nv, seed)
            print(f"topo={topo_shape} kind={kind} nv={nv} ok", flush=True)
            seed += 1
    check_operator_equals_builder_path()
    print("ALL OK")


if __name__ == "__main__":
    main()
