"""Plan compilation: vectorised split/compile, slot maps, cache, traffic.

Single-process tests of everything compile-side (no device mesh needed):
the block splitter's reconstruction invariant, the slot-map lookup tables,
the compile cache, effective-vs-padded traffic accounting, the fused BSR
layout, and the mailbox's duplicate-post guard.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.comm_graph import (build_nap_plan, flat_slot_map, lookup_slots,
                                   Message)
from repro.core.partition import make_partition
from repro.core.spmv import _MailBox, split_all_blocks
from repro.core.spmv_jax import (STRATEGIES, clear_compile_cache,
                                 compile_plan, padded_traffic)
from repro.core.topology import Topology
from repro.sparse import random_fixed_nnz
from repro.sparse.bsr import BSR

TOPOS = [(1, 4), (2, 2), (4, 2)]


def problem(nn, ppn, n=60, nnz=6, kind="contiguous", seed=0):
    topo = Topology(n_nodes=nn, ppn=ppn)
    a = random_fixed_nnz(n, nnz, seed=seed)
    part = make_partition(kind, n, topo.n_procs,
                          indptr=a.indptr, indices=a.indices, seed=seed)
    return topo, a, part


@pytest.mark.parametrize("nn,ppn", TOPOS)
@pytest.mark.parametrize("kind", ["contiguous", "strided", "balanced"])
def test_split_blocks_reconstruct(nn, ppn, kind):
    """on_proc + on_node + off_node (mapped back to global cols) == A rows."""
    topo, a, part = problem(nn, ppn, kind=kind, seed=3)
    dense = a.to_dense()
    for blk in split_all_blocks(a, part, topo):
        got = np.zeros((blk.rows.size, a.shape[1]))
        got[:, blk.rows] += blk.on_proc.to_dense()
        if blk.on_node_cols.size:
            got[:, blk.on_node_cols] += blk.on_node.to_dense()
        if blk.off_node_cols.size:
            got[:, blk.off_node_cols] += blk.off_node.to_dense()
        np.testing.assert_allclose(got, dense[blk.rows])


def test_flat_slot_map_roundtrip():
    msgs = [Message(src=0, dst=2, idx=np.array([3, 7, 11])),
            Message(src=1, dst=2, idx=np.array([1, 5]))]
    idx, pos = flat_slot_map(msgs, [0, 1], pad=4)
    assert idx.tolist() == [1, 3, 5, 7, 11]
    # slot * pad + position-in-message
    assert lookup_slots((idx, pos), np.array([7, 1, 11])).tolist() == [1, 4, 2]
    with pytest.raises(AssertionError):
        lookup_slots((idx, pos), np.array([2]))  # never delivered


def test_flat_slot_map_rejects_duplicate_delivery():
    msgs = [Message(src=0, dst=2, idx=np.array([3, 7])),
            Message(src=1, dst=2, idx=np.array([7]))]
    with pytest.raises(AssertionError):
        flat_slot_map(msgs, [0, 1], pad=4)


@pytest.mark.parametrize("nn,ppn", TOPOS)
def test_recv_slot_map_matches_messages(nn, ppn):
    topo, a, part = problem(nn, ppn, seed=5)
    plan = build_nap_plan(a.indptr, a.indices, part, topo, pairing="aligned")
    inter, = [p for p in STRATEGIES["nap"].schedule(plan, topo).phases
              if p.name == "inter"]
    inter = dataclasses.replace(inter, pad=100)
    for r in range(topo.n_procs):
        idx, pos = inter.recv_slot_map(topo, r)
        for m in plan.inter_recvs[r]:
            want = topo.node_of(m.src) * 100 + np.arange(m.size)
            got = lookup_slots((idx, pos), m.idx)
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("nn,ppn", TOPOS)
def test_padded_traffic_effective_le_padded(nn, ppn):
    topo, a, part = problem(nn, ppn, seed=7)
    t = padded_traffic(compile_plan(a, part, topo, cache=False))
    for phase in ("inter", "full", "init", "final"):
        assert t[f"{phase}_effective"] <= t[f"{phase}_padded"], (phase, t)
    # effective inter bytes must equal the plan's true payload
    plan = build_nap_plan(a.indptr, a.indices, part, topo, pairing="aligned")
    want = 4 * sum(m.size for msgs in plan.inter_sends for m in msgs)
    assert t["inter_effective"] == want


def test_compile_cache_hits_and_distinguishes():
    clear_compile_cache()
    topo, a, part = problem(2, 2, seed=9)
    c1 = compile_plan(a, part, topo)
    assert compile_plan(a, part, topo) is c1                     # pure cache hit
    assert compile_plan(a, part, topo, block_shape=(8, 8)) is not c1
    a2 = random_fixed_nnz(60, 6, seed=10)                        # new structure
    assert compile_plan(a2, part, topo) is not c1
    a3 = random_fixed_nnz(60, 6, seed=9)
    a3.data = a3.data * 2.0                                      # same structure, new values
    assert compile_plan(a3, part, topo) is not c1
    assert compile_plan(a, part, topo, cache=False) is not c1
    clear_compile_cache()


@pytest.mark.parametrize("nn,ppn", TOPOS)
def test_fused_bsr_layout_equals_local_blocks(nn, ppn):
    """The fused blocks, viewed densely per rank, reproduce the three
    column blocks at their layout offsets."""
    topo, a, part = problem(nn, ppn, seed=11)
    compiled = compile_plan(a, part, topo, block_shape=(8, 16), cache=False)
    compiled.ensure_fused()
    bm, bn = compiled.block_shape
    blocks = split_all_blocks(a, part, topo)
    for r, blk in enumerate(blocks):
        cols = compiled.arrays["fused_cols"][r]
        data = compiled.arrays["fused_blocks"][r]
        n_bcols = compiled.packed_x_len // bn
        dense = np.zeros((cols.shape[0] * bm, n_bcols * bn))
        for i in range(cols.shape[0]):
            for k in range(cols.shape[1]):
                c = cols[i, k]
                if c >= 0:
                    dense[i * bm:(i + 1) * bm, c * bn:(c + 1) * bn] += data[i, k]
        nr = blk.rows.size
        np.testing.assert_allclose(
            dense[:nr, :nr], blk.on_proc.to_dense(), atol=1e-6)
        o = compiled.cols_pad
        np.testing.assert_allclose(
            dense[:nr, o:o + blk.on_node.shape[1]], blk.on_node.to_dense(),
            atol=1e-6)
        o += compiled.pads["bnode"]
        np.testing.assert_allclose(
            dense[:nr, o:o + blk.off_node.shape[1]], blk.off_node.to_dense(),
            atol=1e-6)


def test_mailbox_duplicate_post_fails_loudly():
    box = _MailBox()
    m1 = Message(src=0, dst=1, idx=np.array([2, 4]))
    m2 = Message(src=0, dst=1, idx=np.array([6]))  # same pair, different idx
    box.post(m1, np.array([1.0, 2.0]))
    np.testing.assert_array_equal(box.fetch(m1), [1.0, 2.0])
    with pytest.raises(AssertionError, match="duplicate message"):
        box.post(m2, np.array([3.0]))


def test_bsr_from_coo_matches_from_csr():
    a = random_fixed_nnz(40, 5, seed=1)
    rows, cols, vals = a.to_coo()
    b1 = BSR.from_csr(a, bm=8, bn=8)
    b2 = BSR.from_coo(rows, cols, vals, a.shape, bm=8, bn=8)
    np.testing.assert_array_equal(b1.indptr, b2.indptr)
    np.testing.assert_array_equal(b1.indices, b2.indices)
    np.testing.assert_allclose(b1.data, b2.data)
    np.testing.assert_allclose(b2.to_dense()[:40, :40], a.to_dense(), atol=1e-6)
