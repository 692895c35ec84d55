"""The row-split ELL on four CPU devices (subprocess).

A Graph500 Kronecker graph's PageRank matrix (scale 10: hub rows of a
few hundred entries beside rows of one) through ``repro.api.operator``
on Topology(2, 2), for each case ``<comm>-<direction>-nv<nv>`` (comm
``nap``/``multistep``/``standard``, ``op @ x`` or ``op.T @ x``, nv 1 or
8): the autotuner splits the rows of that direction's ELL
(``ell_split_width`` below the longest row, a ``chunk_row`` map beside
the arrays), the forward program reads the received buffers through the
composed ids, and the result matches a float64 scipy product within
float32 rounding (``|y - A x| <= 1e-5 |A| |x|``, every element).

The cases ``pad-<direction>-nv<nv>`` run a random matrix of
``2^17 + 1024`` rows per rank, whose unsplit forward ELL the stack pads
by 512 rows per rank (``repro.sparse.ell.slot_loop_rows``): the result
is bit for bit that of the same operator with the rule switched off
while its arrays are emitted, and within float32 rounding of scipy.

Prints ``CASE <name> OK`` per case (``FAIL`` with the error otherwise).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import sys
import traceback

import numpy as np
import scipy.sparse as sp

import repro.api as nap
import repro.sparse.ell as ell_mod
from repro.core.topology import Topology
from repro.sparse import graph500_kronecker, pagerank_matrix, random_fixed_nnz

TOPO = Topology(n_nodes=2, ppn=2)
B = pagerank_matrix(graph500_kronecker(10, seed=7))
REF = sp.csr_matrix((B.data, B.indices, B.indptr), shape=B.shape)


PAD_ROWS = (1 << 17) + 1024     # per rank: a full last 1024-row tile
PADDED = {}


def padded_ops():
    """The random matrix through the operator as it runs and with the
    stack's rule switched off (built once)."""
    if not PADDED:
        a = random_fixed_nnz(TOPO.n_procs * PAD_ROWS, 5, seed=9)
        rule = ell_mod.slot_loop_rows
        for name in ("padded", "plain"):
            if name == "plain":
                ell_mod.slot_loop_rows = lambda n: n
            try:
                op = nap.operator(a, topo=TOPO, backend="shardmap",
                                  local_compute="ell", cache=False)
                op.executor.compiled.ensure_ell()
                op.executor.compiled.ensure_ell_t()
            finally:
                ell_mod.slot_loop_rows = rule
            PADDED[name] = op
        PADDED["ref"] = sp.csr_matrix((a.data, a.indices, a.indptr),
                                      shape=a.shape)
    return PADDED


def check_padded(direction, nv):
    ops = padded_ops()
    c = ops["padded"].executor.compiled
    name = "ell_cols" if direction == "F" else "ell_t_cols"
    plain = ops["plain"].executor.compiled.arrays[name]
    assert c.arrays[name].shape[1] == ell_mod.slot_loop_rows(plain.shape[1])
    ref = ops["ref"] if direction == "F" else ops["ref"].T.tocsr()
    if direction == "F":
        assert c.rows_pad == PAD_ROWS and plain.shape[1] == PAD_ROWS
        assert c.arrays[name].shape[1] == PAD_ROWS + 512
        assert ops["padded"].stats()["ell_pad_rows"] == 512
    x = np.random.default_rng(nv).standard_normal(
        (ref.shape[1], nv) if nv > 1 else ref.shape[1]).astype(np.float32)
    got = [np.asarray((op if direction == "F" else op.T) @ x)
           for op in (ops["padded"], ops["plain"])]
    assert np.array_equal(got[0], got[1])
    xd = x.astype(np.float64)
    err = np.abs(got[0] - ref @ xd) / np.maximum(abs(ref) @ np.abs(xd), 1e-30)
    assert err.max() <= 1e-5, err.max()


def check_case(name):
    comm, direction, nvs = name.split("-")
    if comm == "pad":
        return check_padded(direction, int(nvs[2:]))
    nv = int(nvs[2:])
    op = nap.operator(B, topo=TOPO, method=comm, backend="shardmap",
                      local_compute="auto", cache=False)
    rep = op.autotune_report()
    c = op.executor.compiled
    if direction == "T":
        at, cr, fmt = rep["transpose"], "ell_t_chunk_row", \
            rep["transpose_resolved"]
        target, ref = op.T, REF.T.tocsr()
    else:
        at, cr, fmt = rep, "ell_chunk_row", rep["resolved"]
        target, ref = op, REF
    assert fmt == "ell", fmt
    assert at["ell"]["ell_split_width"] < at["stats"]["ell_kmax"], at["ell"]
    x = np.random.default_rng(len(name)).standard_normal(
        (B.shape[1], nv) if nv > 1 else B.shape[1]).astype(np.float32)
    y = np.asarray(target @ x, np.float64)
    assert cr in c.arrays, sorted(c.arrays)
    assert c.arrays[cr].shape == c.arrays[cr.replace("chunk_row", "cols")
                                          ].shape[:2]
    if direction == "F":
        assert c.arrays["ell_cols"].max() < c.recv_x_len
    want = ref @ x.astype(np.float64)
    mag = abs(ref) @ np.abs(x.astype(np.float64))
    err = np.abs(y - want) / np.maximum(mag, 1e-30)
    assert err.max() <= 1e-5, err.max()


def main():
    for name in sys.argv[1:]:
        try:
            check_case(name)
            print(f"CASE {name} OK", flush=True)
        except Exception:
            print(f"CASE {name} FAIL\n{traceback.format_exc()}", flush=True)


if __name__ == "__main__":
    main()
