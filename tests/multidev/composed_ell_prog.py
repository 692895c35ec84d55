"""Composed ELL column ids on four CPU devices (subprocess).

The forward ELL programs read the received buffers directly: the plan
composes each column id with Algorithm 3's buffer gathers
(``bnode_gather``/``boff_gather``, or ``buf_gather`` for the standard
plan), so the program runs no ``bnode``/``boff`` gather.  For every case
(comm ``nap``/``multistep``/``standard`` x nv 1/8 x the random family, a
5-point stencil and a Graph500 Kronecker graph's PageRank matrix, whose
rows are split, x a square and a rectangular operator) this checks:

* the shard_map ELL output is BIT-identical to ``ell_spmm_packed`` run on
  the materialised ``[v_loc | bnode | boff]`` domain (``[v_loc | buf]``
  for standard) with the packed-domain ids (split and combined alike).  That reference is built here
  from the plan arrays: the exchange is replayed in numpy through the
  send/gather maps, the buffers are gathered, and the packed ids are
  emitted from the plan's local blocks;
* the composed ids on the replayed received buffers give the same bits;
* the ELL output matches the COO program within the usual tolerance.

Integrity cases run the composed ELL programs under ``integrity="detect"``:
a clean apply is bit-identical to the uninstrumented one and its ABFT
residual stays under tolerance; a bitflip planted on every message phase
is caught by the wire checksums and a compute bitflip by ABFT.

Runs the cases named on the command line (``<comm>-nv<nv>-<family>-
<shape>`` or ``integrity-<comm>``) and prints ``CASE <name> OK`` per case
(``FAIL`` with the error otherwise).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import sys
import traceback

import numpy as np

import repro.api as nap
from repro.compat import make_mesh
from repro.core.integrity import (IntegrityError, abft_tolerance,
                                  build_fault_spec, message_phases)
from repro.core.partition import contiguous_partition, strided_partition
from repro.core.spmv_jax import (_split_width, compile_plan,
                                 forward_program, pack_vector, unpack_vector)
from repro.core.topology import Topology
from repro.kernels.ell_spmv import ell_spmm_packed
from repro.sparse import (graph500_kronecker, pagerank_matrix, poisson_2d,
                          random_fixed_nnz)
from repro.sparse.ell import ELL, stack_ell

TOPO = Topology(n_nodes=2, ppn=2)
NN, PPN, NP = TOPO.n_nodes, TOPO.ppn, TOPO.n_procs
MESH = make_mesh((NN, PPN), ("node", "proc"))


def matrix(fam, shape):
    a = {"random": lambda: random_fixed_nnz(150, 7, seed=5),
         "stencil": lambda: poisson_2d(12),
         "kron": lambda: pagerank_matrix(graph500_kronecker(9, seed=5))}[fam]()
    if shape == "rect":   # every other row: an [n/2, n] restriction-like R A
        a = a.select_rows(np.arange(0, a.shape[0], 2))
    return a


def compile_case(comm, a, shape):
    rp = contiguous_partition(a.shape[0], NP)
    cp = strided_partition(a.shape[1], NP) if shape == "rect" else None
    return compile_plan(a, rp, TOPO, comm=comm, block_shape=(8, 16),
                        cache=False, col_part=cp), rp, cp or rp


# -- numpy replay of the forward exchange (tiled all_to_alls) ---------------

def rank(n, p):
    return n * PPN + p


def a2a_proc(bufs):
    """all_to_all over "proc": device (n, p) receives slot q from (n, q)."""
    return [np.stack([bufs[rank(r // PPN, q)][r % PPN] for q in range(PPN)])
            for r in range(NP)]


def a2a_node(bufs):
    """all_to_all over "node": device (n, p) receives slot m from (m, p)."""
    return [np.stack([bufs[rank(m, r % PPN)][r // PPN] for m in range(NN)])
            for r in range(NP)]


def a2a_flat(bufs):
    """all_to_all over ("node", "proc"): device r receives slot s from s."""
    return [np.stack([bufs[s][r] for s in range(NP)]) for r in range(NP)]


def flat(x, nv):
    return x.reshape(-1, nv)


def replay(c, comm, v_loc, nv):
    """Per rank: (the received segments the composed ids index, the
    materialised [v_loc | bnode | boff] (or [v_loc | buf]) segments)."""
    ar = c.arrays
    if comm == "standard":
        recv = a2a_flat([v_loc[s][ar["send_idx"][s]] for s in range(NP)])
        return [((v_loc[r], flat(recv[r], nv)),
                 (v_loc[r], flat(recv[r], nv)[ar["buf_gather"][r]]))
                for r in range(NP)]
    full = a2a_proc([v_loc[s][ar["full_send"][s]] for s in range(NP)])
    init = a2a_proc([v_loc[s][ar["init_send"][s]] for s in range(NP)])
    staged = [np.concatenate([v_loc[s], flat(init[s], nv)]) for s in range(NP)]
    inter = a2a_node([staged[s][ar["inter_gather"][s]] for s in range(NP)])
    final = a2a_proc([flat(inter[s], nv)[ar["final_send"][s]]
                      for s in range(NP)])
    off = [[flat(inter[r], nv), flat(final[r], nv)] for r in range(NP)]
    if comm == "multistep":
        direct = a2a_flat([v_loc[s][ar["direct_send"][s]] for s in range(NP)])
        for r in range(NP):
            off[r].append(flat(direct[r], nv))
    out = []
    for r in range(NP):
        bnode = flat(full[r], nv)[ar["bnode_gather"][r]]
        boff = np.concatenate(off[r])[ar["boff_gather"][r]]
        out.append(((v_loc[r], flat(full[r], nv)) + tuple(off[r]),
                    (v_loc[r], bnode, boff)))
    return out


def packed_ell(c, comm):
    """The packed-domain ELL ids, values and chunk-row map, emitted from
    the plan's local blocks, split at the plan's width, as the product
    read them before composition."""
    if comm == "standard":
        segs = [(0, ("on_proc",)), (c.cols_pad, ("on_node", "off_node"))]
    else:
        segs = [(0, ("on_proc",)), (c.cols_pad, ("on_node",)),
                (c.cols_pad + c.pads["bnode"], ("off_node",))]
    per_rank = []
    for blk in c.local_blocks:
        parts = []
        for start, kinds in segs:
            for k in kinds:
                rr, cc, vv = getattr(blk, k).to_coo()
                parts.append((rr, cc + start, vv))
                if k != "on_proc":
                    start += getattr(blk, f"{k}_cols").size
        per_rank.append(tuple(np.concatenate(z) for z in zip(*parts)))
    cols, vals, _, chunk_row = stack_ell([
        ELL.from_coo(rr, cc, vv, (c.rows_pad, c.packed_x_len),
                     n_rows_pad=c.rows_pad, split=_split_width(c.autotune))
        for rr, cc, vv in per_rank])
    return cols, vals, chunk_row


def ell_product(cols, vals, chunk_row, segs, n_rows):
    """``ell_spmm_packed`` on one rank's arrays, combined where split."""
    if chunk_row is None:
        return np.asarray(ell_spmm_packed(cols, vals, segs))
    return np.asarray(ell_spmm_packed(cols, vals, segs, chunk_row,
                                      n_rows=n_rows))


def forward(c, comm, fmt, **kw):
    return forward_program(c, MESH, local_compute=fmt, **kw)


def check_case(name):
    comm, nvs, fam, shape = name.split("-")
    nv = int(nvs[2:])
    a = matrix(fam, shape)
    c, rp, cp = compile_case(comm, a, shape)
    rng = np.random.default_rng(len(name))
    v = rng.standard_normal((a.shape[1], nv))
    shards = pack_vector(v, cp, TOPO, c.cols_pad)

    run = forward(c, comm, "ell")
    assert run.local_compute == "ell"
    got = np.asarray(run(shards)).reshape(NP, c.rows_pad, nv)
    assert c.arrays["ell_cols"].max() < c.recv_x_len

    cols, vals, chunk_row = packed_ell(c, comm)
    assert np.array_equal(vals, c.arrays["ell_vals"])
    if chunk_row is not None:
        assert np.array_equal(chunk_row, c.arrays["ell_chunk_row"])
    v_loc = shards.reshape(NP, c.cols_pad, nv)
    for r, (recv_segs, packed_segs) in enumerate(replay(c, comm, v_loc, nv)):
        cr = None if chunk_row is None else chunk_row[r]
        want = ell_product(cols[r], vals[r], cr, packed_segs, c.rows_pad)
        composed = ell_product(c.arrays["ell_cols"][r],
                               c.arrays["ell_vals"][r], cr, recv_segs,
                               c.rows_pad)
        assert np.array_equal(composed, want), f"rank {r}: composed ids"
        assert np.array_equal(got[r], want), f"rank {r}: program"

    y = unpack_vector(got.reshape(NN, PPN, c.rows_pad, nv), rp, TOPO)
    y_coo = unpack_vector(np.asarray(forward(c, comm, "coo")(shards)), rp,
                          TOPO)
    np.testing.assert_allclose(y, y_coo, rtol=1e-4, atol=1e-5)
    dense = np.stack([a.matvec(v[:, i]) for i in range(nv)], axis=1)
    np.testing.assert_allclose(y, dense, rtol=1e-4, atol=1e-5)
    if comm == "multistep" and fam == "random":
        assert any(c.plan.direct.sends), "the direct exchange is empty"


def check_integrity(name):
    comm = name.split("-")[1]
    a = random_fixed_nnz(64, 12, seed=3)
    part = contiguous_partition(64, NP)
    v = np.random.default_rng(3).standard_normal(64)

    def build(integrity):
        return nap.operator(a, topo=TOPO, part=part, method=comm,
                            backend="shardmap", block_shape=(8, 16),
                            local_compute="ell", integrity=integrity)

    op_off, op_det = build("off"), build("detect")
    assert op_det.local_compute == "ell"
    y0 = op_off @ v
    assert np.array_equal(op_det @ v, y0), "clean detect != off"
    rep = op_det.integrity_report()
    assert rep["wire_mismatches"] == 0 and rep["abft_mismatches"] == 0, rep

    # the clean ABFT residual, read off the instrumented program itself
    c = op_det.executor.compiled
    spec = build_fault_spec(TOPO, [], comm)
    run = forward(c, comm, "ell", integrity=True, fault_fetch=lambda: spec)
    _, _, abft = run(pack_vector(v, part, TOPO, c.cols_pad))
    abft = np.asarray(abft, np.float64)[..., 0]
    y, d, scale = abft[..., 0], abft[..., 1], abft[..., 2]
    tol = abft_tolerance(scale, y, d, c.rows_pad + c.packed_x_len)
    assert np.all(np.abs(y - d) <= tol), (np.abs(y - d), tol)
    assert np.all(scale > 0)

    for phase in message_phases(comm):
        slot = {"inter": 1, "init": 0}.get(phase, 1)
        op_det.inject_fault(phase, "bitflip", node=0, proc=0, slot=slot,
                            element=1, bit=20)
        try:
            op_det @ v
            raise AssertionError(f"{phase} bitflip NOT detected")
        except IntegrityError as e:
            assert any(m.check == "wire" and m.phase == phase
                       for m in e.mismatches), [str(m) for m in e.mismatches]
    op_det.inject_fault("compute", "bitflip", node=NN - 1, proc=PPN - 1,
                        element=2, bit=25)
    try:
        op_det @ v
        raise AssertionError("compute bitflip NOT detected")
    except IntegrityError as e:
        m = e.mismatches[0]
        assert m.check == "abft" and (m.node, m.proc) == (NN - 1, PPN - 1), m
    assert np.array_equal(op_det @ v, y0), "clean apply after faults"


def main():
    for name in sys.argv[1:]:
        try:
            (check_integrity if name.startswith("integrity-")
             else check_case)(name)
            print(f"CASE {name} OK", flush=True)
        except Exception:
            print(f"CASE {name} FAIL\n{traceback.format_exc()}", flush=True)


if __name__ == "__main__":
    main()
