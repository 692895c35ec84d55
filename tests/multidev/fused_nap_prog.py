"""Multi-device property check of the adaptive NAPSpMV engine (subprocess).

Seeded-random sweep on an 8-device host platform: for every topology
``(n_nodes, ppn) ∈ {(1,4), (2,2), (4,2)}``, block sizes, partition kinds
and ``nv ∈ {1, 8, 128}``, the fused-BSR shard_map executor must agree with

  * the numpy message-passing simulator (exact MPI semantics oracle), and
  * the dense ``A @ x`` ground truth,

to 1e-5, in Pallas interpret mode.  The ELL, COO and autotuned executors
and the standard-algorithm executor are swept at nv=8 as cross-checks,
and the zero-copy packed-x path is checked bit-for-bit against the
materialised-concat path (``materialize_x=True``).  The TRANSPOSE
executors (reversed send/recv roles, same compiled plans) are checked at
nv=8 against both the reversed-flow simulator and dense ``A.T @ x``.

A block-hostile low-density problem additionally asserts the format
autotuner rejects BSR, and a jaxpr scan asserts the packed x operand is
NOT materialised as an HBM concat by the zero-copy BSR executor (while
its materialize_x oracle path IS — a differential check, immune to shape
coincidences); the XLA ELL product, whose column ids index the received
buffers, concatenates those exactly once and never the packed x.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np

import jax
import jax.extend.core

from repro.compat import make_mesh
from repro.core.comm_graph import build_nap_plan
from repro.core.partition import contiguous_partition, make_partition
from repro.core.spmv import simulate_nap_spmv, simulate_nap_spmv_transpose
from repro.core.spmv_jax import (compile_nap, compile_standard,
                                 nap_forward_shardmap, nap_transpose_shardmap,
                                 pack_vector, standard_forward_shardmap,
                                 standard_transpose_shardmap, unpack_vector)
from repro.core.topology import Topology
from repro.sparse import random_fixed_nnz

TOPOS = [(1, 4), (2, 2), (4, 2)]
NVS = [1, 8, 128]


def dense_oracle(a, v):
    return np.stack([a.matvec(v[:, i]) for i in range(v.shape[1])], axis=1)


def check(topo_shape, kind, block_shape, nv, seed):
    nn, ppn = topo_shape
    topo = Topology(n_nodes=nn, ppn=ppn)
    rng = np.random.default_rng(seed)
    n = int(rng.integers(topo.n_procs * 3, 64))
    a = random_fixed_nnz(n, int(rng.integers(3, 9)), seed=seed)
    part = make_partition(kind, n, topo.n_procs,
                          indptr=a.indptr, indices=a.indices, seed=seed)
    mesh = make_mesh((nn, ppn), ("node", "proc"))
    compiled = compile_nap(a, part, topo, block_shape=block_shape, cache=False)
    v = rng.standard_normal((n, nv))
    want = dense_oracle(a, v)

    # oracle 1: the numpy message-passing simulator (column-wise)
    nap_plan = build_nap_plan(a.indptr, a.indices, part, topo,
                              pairing="aligned")
    sim = np.stack([simulate_nap_spmv(a, v[:, i], nap_plan)
                    for i in range(nv)], axis=1)
    np.testing.assert_allclose(sim, want, rtol=1e-9, atol=1e-11)

    # fused Pallas BSR shard_map executor (zero-copy) vs both oracles
    run = nap_forward_shardmap(compiled, mesh, local_compute="bsr")
    shards = pack_vector(v, part, topo, compiled.rows_pad)
    got_raw = np.asarray(run(shards))
    got = unpack_vector(got_raw, part, topo)
    np.testing.assert_allclose(got, sim, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    # zero-copy in-kernel gather == materialised HBM concat, bit-for-bit
    run_mat = nap_forward_shardmap(compiled, mesh, local_compute="bsr",
                                   materialize_x=True)
    assert np.array_equal(np.asarray(run_mat(shards)), got_raw)

    if nv == 8:
        for fmt in ("coo", "ell", "auto"):
            run_f = nap_forward_shardmap(compiled, mesh, local_compute=fmt)
            got_f = unpack_vector(np.asarray(run_f(shards)), part, topo)
            np.testing.assert_allclose(got_f, want, rtol=1e-4, atol=1e-5)
        assert run_f.local_compute == compiled.chosen_local_compute
        cstd = compile_standard(a, part, topo, block_shape=block_shape,
                                cache=False)
        for fmt in ("bsr", "auto"):
            run_std = standard_forward_shardmap(cstd, mesh, local_compute=fmt)
            got_std = unpack_vector(np.asarray(run_std(shards)), part, topo)
            np.testing.assert_allclose(got_std, want, rtol=1e-4, atol=1e-5)

        # transpose executors vs the reversed-flow simulator AND dense A.T
        at = a.transpose()
        want_t = dense_oracle(at, v)
        sim_t = np.stack([simulate_nap_spmv_transpose(a, v[:, i], nap_plan)
                          for i in range(nv)], axis=1)
        np.testing.assert_allclose(sim_t, want_t, rtol=1e-9, atol=1e-11)
        run_t = nap_transpose_shardmap(compiled, mesh)
        got_t = unpack_vector(np.asarray(run_t(shards)), part, topo)
        np.testing.assert_allclose(got_t, sim_t, rtol=1e-4, atol=1e-5)
        run_ts = standard_transpose_shardmap(cstd, mesh)
        got_ts = unpack_vector(np.asarray(run_ts(shards)), part, topo)
        np.testing.assert_allclose(got_ts, want_t, rtol=1e-4, atol=1e-5)


def _count_packed_x_concats(fn, shards, n_x, nv) -> int:
    """Occurrences of a concatenate producing the packed x operand
    ([n_x, nv] elementwise or [n_x/bn, bn, nv] block form) in the
    executor's jaxpr.  The walk does NOT descend into pallas_call bodies:
    interpret mode traces kernel internals as jax eqns, and a concat of
    VMEM refs inside the kernel is not an HBM materialisation — the
    assertion targets the per-call executor graph."""
    jaxpr = jax.make_jaxpr(fn)(shards)

    def walk(jx):
        hits = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "concatenate":
                shape = eqn.outvars[0].aval.shape
                if (len(shape) >= 2 and shape[0] == n_x
                        and shape[-1] == nv):
                    hits += 1
            if "pallas" in eqn.primitive.name:
                continue
            for val in eqn.params.values():
                leaves = val if isinstance(val, (list, tuple)) else [val]
                for leaf in leaves:
                    if isinstance(leaf, jax.extend.core.ClosedJaxpr):
                        hits += walk(leaf.jaxpr)
                    elif isinstance(leaf, jax.extend.core.Jaxpr):
                        hits += walk(leaf)
        return hits

    return walk(jaxpr.jaxpr)


def check_block_hostile_autotune():
    """Low-density (<= 12 nnz/row) matrix: auto must reject BSR, match the
    dense oracle, and never materialise the packed x concat."""
    topo = Topology(n_nodes=2, ppn=4)
    mesh = make_mesh((2, 4), ("node", "proc"))
    n, nv = 1024, 8
    a = random_fixed_nnz(n, 8, seed=7)
    part = contiguous_partition(n, topo.n_procs)
    compiled = compile_nap(a, part, topo, cache=False)
    assert compiled.chosen_local_compute in ("ell", "coo"), compiled.autotune
    assert all(e["choice"] != "bsr" for e in compiled.autotune["per_rank"])

    rng = np.random.default_rng(1)
    v = rng.standard_normal((n, nv))
    shards = pack_vector(v, part, topo, compiled.rows_pad)
    want = dense_oracle(a, v)
    n_x = compiled.packed_x_len

    n_recv = compiled.recv_x_len
    assert n_recv != n_x
    for fmt in ("auto", "ell", "bsr"):
        run = nap_forward_shardmap(compiled, mesh, local_compute=fmt)
        got = unpack_vector(np.asarray(run(shards)), part, topo)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        # neither the zero-copy BSR kernel nor the composed ELL product
        # materialises the packed x concat; the XLA ELL product
        # concatenates the received domain once instead
        n_cat = _count_packed_x_concats(run.run4, shards, n_x, nv)
        assert n_cat == 0, (fmt, n_cat)
        n_recv_cat = _count_packed_x_concats(run.run4, shards, n_recv, nv)
        assert n_recv_cat == (run.local_compute == "ell"), (fmt, n_recv_cat)
    # ...while the BSR materialize_x oracle path DOES (differential: proves
    # the scan actually sees the concat when it exists)
    run_mat = nap_forward_shardmap(compiled, mesh, local_compute="bsr",
                                materialize_x=True)
    assert _count_packed_x_concats(run_mat.run4, shards, n_x, nv) >= 1
    print(f"block-hostile autotune ok: chose {compiled.chosen_local_compute}, "
          f"no packed-x concat in the zero-copy BSR or composed ELL jaxpr",
          flush=True)


def main():
    seed = 100
    for topo_shape in TOPOS:
        for nv in NVS:
            kind = ["contiguous", "strided", "balanced"][seed % 3]
            check(topo_shape, kind, (8, 16), nv, seed)
            print(f"topo={topo_shape} kind={kind} bs=(8,16) nv={nv} ok", flush=True)
            seed += 1
    # block-size sweep on one topology (incl. the MXU-native 128-lane tile)
    for block_shape in [(8, 8), (16, 16), (8, 128)]:
        check((2, 2), "contiguous", block_shape, 8, seed)
        print(f"topo=(2,2) bs={block_shape} nv=8 ok", flush=True)
        seed += 1
    check_block_hostile_autotune()
    print("ALL OK")


if __name__ == "__main__":
    main()
