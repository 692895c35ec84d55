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

Prints ``CASE <name> OK`` per case (``FAIL`` with the error otherwise).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import sys
import traceback

import numpy as np
import scipy.sparse as sp

import repro.api as nap
from repro.core.topology import Topology
from repro.sparse import graph500_kronecker, pagerank_matrix

TOPO = Topology(n_nodes=2, ppn=2)
B = pagerank_matrix(graph500_kronecker(10, seed=7))
REF = sp.csr_matrix((B.data, B.indices, B.indptr), shape=B.shape)


def check_case(name):
    comm, direction, nvs = name.split("-")
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
