"""Seed (pre-vectorisation) plan compiler, kept verbatim as the benchmark
baseline for BENCH_spmv.json's plan-compile speedup measurement.

This is the dict/per-element-loop implementation of ``split_local_blocks``
and ``compile_nap`` exactly as shipped in the seed commit (returning its
pads and arrays, no plan object); the library path
(``repro.core.spmv`` / ``repro.core.spmv_jax``) replaced it with bulk
``np.searchsorted`` indexing.  Do not "fix" or speed this file up — its
slowness is the datum.  (The fused-BSR arrays did not exist in the seed,
so the legacy compile measures strictly LESS work than the new one.)
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.comm_graph import Message, NAPPlan, build_nap_plan
from repro.core.partition import RowPartition
from repro.core.spmv import LocalBlocks
from repro.core.spmv_jax import _pad_to
from repro.core.topology import Topology
from repro.sparse.csr import CSR


def _pos_in(idx: np.ndarray, j: int) -> int:
    p = int(np.searchsorted(idx, j))
    assert p < idx.size and idx[p] == j
    return p


def legacy_split_local_blocks(a: CSR, part: RowPartition, topo: Topology, rank: int) -> LocalBlocks:
    rows = part.rows_of(rank)
    local = a.select_rows(rows)
    g_rows, g_cols, vals = local.to_coo()  # g_rows are positions within `rows`
    col_owner = part.owner[g_cols]
    col_node = topo.node_of_array(col_owner)
    me_node = topo.node_of(rank)

    on_proc_m = col_owner == rank
    on_node_m = (col_owner != rank) & (col_node == me_node)
    off_node_m = col_node != me_node

    # on-process: remap columns to local index within R(r)
    glob_to_loc = {int(g): i for i, g in enumerate(rows)}
    op_cols = np.array([glob_to_loc[int(c)] for c in g_cols[on_proc_m]], dtype=np.int64)
    on_proc = CSR.from_coo(g_rows[on_proc_m], op_cols, vals[on_proc_m],
                           (rows.size, rows.size), sum_duplicates=False)

    def buffer_block(mask: np.ndarray) -> Tuple[CSR, np.ndarray]:
        cols = np.unique(g_cols[mask])
        slot = {int(c): i for i, c in enumerate(cols)}
        bc = np.array([slot[int(c)] for c in g_cols[mask]], dtype=np.int64)
        blk = CSR.from_coo(g_rows[mask], bc, vals[mask],
                           (rows.size, max(int(cols.size), 1)), sum_duplicates=False)
        return blk, cols

    on_node, on_node_cols = buffer_block(on_node_m)
    off_node, off_node_cols = buffer_block(off_node_m)
    return LocalBlocks(rank=rank, rows=rows, on_proc=on_proc, on_node=on_node,
                       off_node=off_node, on_node_cols=on_node_cols,
                       off_node_cols=off_node_cols)


def legacy_split_all_blocks(a: CSR, part: RowPartition, topo: Topology) -> List[LocalBlocks]:
    return [legacy_split_local_blocks(a, part, topo, r) for r in range(topo.n_procs)]


def legacy_compile_nap(a: CSR, part: RowPartition, topo: Topology,
                plan: Optional[NAPPlan] = None
                ) -> Tuple[Dict[str, int], Dict[str, np.ndarray]]:
    if plan is None:
        plan = build_nap_plan(a.indptr, a.indices, part, topo, pairing="aligned")
    n_procs, ppn, n_nodes = topo.n_procs, topo.ppn, topo.n_nodes
    blocks = legacy_split_all_blocks(a, part, topo)
    local_index = part.local_index()
    rows_pad = max(1, int(part.counts().max()))

    def msg_pad(phase: List[List[Message]]) -> int:
        sizes = [m.size for msgs in phase for m in msgs]
        return max(1, max(sizes, default=1))

    full_pad = msg_pad(plan.local_full_sends)
    init_pad = msg_pad(plan.local_init_sends)
    inter_pad = msg_pad(plan.inter_sends)
    final_pad = msg_pad(plan.local_final_sends)
    bnode_pad = max(1, max(b.on_node_cols.size for b in blocks))
    boff_pad = max(1, max(b.off_node_cols.size for b in blocks))
    nnz_pads = {
        "on_proc": max(1, max(b.on_proc.nnz for b in blocks)),
        "on_node": max(1, max(b.on_node.nnz for b in blocks)),
        "off_node": max(1, max(b.off_node.nnz for b in blocks)),
    }

    A: Dict[str, List[np.ndarray]] = {k: [] for k in (
        "v_loc_init",  # not an index array; filled by caller
    )}
    arrays: Dict[str, np.ndarray] = {}

    def stack_int(name: str, per_rank: List[np.ndarray], shape: Tuple[int, ...]) -> None:
        out = np.zeros((n_procs,) + shape, dtype=np.int32)
        for r, arr in enumerate(per_rank):
            out[r] = arr
        arrays[name] = out

    full_send, init_send, final_send = [], [], []
    inter_gather, bnode_gather, boff_gather = [], [], []
    coo = {k: {"rows": [], "cols": [], "vals": []} for k in nnz_pads}

    for r in range(n_procs):
        p_r, n_r = topo.proc_node(r)
        blk = blocks[r]

        # -- full-local sends: [ppn, full_pad] source local-row positions ----
        fs = np.zeros((ppn, full_pad), dtype=np.int32)
        for m in plan.local_full_sends[r]:
            q = topo.local_of(m.dst)
            fs[q, : m.size] = local_index[m.idx]
        full_send.append(fs)

        # -- init sends -------------------------------------------------------
        isnd = np.zeros((ppn, init_pad), dtype=np.int32)
        for m in plan.local_init_sends[r]:
            q = topo.local_of(m.dst)
            isnd[q, : m.size] = local_index[m.idx]
        init_send.append(isnd)

        # -- inter gather: positions into concat(v_loc, init_recv_flat) -------
        init_recv_by_src = {topo.local_of(m.src): m for m in plan.local_init_recvs[r]}
        ig = np.zeros((n_nodes, inter_pad), dtype=np.int32)
        for m in plan.inter_sends[r]:
            dst_node = topo.node_of(m.dst)
            for k, j in enumerate(m.idx):
                if part.owner[j] == r:
                    ig[dst_node, k] = local_index[j]
                else:
                    src_p = topo.local_of(int(part.owner[j]))
                    msg = init_recv_by_src[src_p]
                    ig[dst_node, k] = rows_pad + src_p * init_pad + _pos_in(msg.idx, int(j))
        inter_gather.append(ig)

        # -- final sends: positions into inter_recv_flat ----------------------
        inter_recv_by_node = {topo.node_of(m.src): m for m in plan.inter_recvs[r]}
        fsnd = np.zeros((ppn, final_pad), dtype=np.int32)
        for m in plan.local_final_sends[r]:
            q = topo.local_of(m.dst)
            for k, j in enumerate(m.idx):
                src_n = None
                for nn, rmsg in inter_recv_by_node.items():
                    hit = np.searchsorted(rmsg.idx, j)
                    if hit < rmsg.idx.size and rmsg.idx[hit] == j:
                        src_n = nn
                        fsnd[q, k] = nn * inter_pad + hit
                        break
                assert src_n is not None, "final-send value must have arrived inter-node"
        final_send.append(fsnd)

        # -- on-node buffer gather: positions into full_recv_flat -------------
        full_recv_by_src = {topo.local_of(m.src): m for m in plan.local_full_recvs[r]}
        bg = np.zeros((bnode_pad,), dtype=np.int32)
        for slot, j in enumerate(blk.on_node_cols):
            src_p = topo.local_of(int(part.owner[j]))
            msg = full_recv_by_src[src_p]
            bg[slot] = src_p * full_pad + _pos_in(msg.idx, int(j))
        bnode_gather.append(bg)

        # -- off-node buffer gather: concat(inter_recv_flat, final_recv_flat) -
        final_recv_by_src = {topo.local_of(m.src): m for m in plan.local_final_recvs[r]}
        og = np.zeros((boff_pad,), dtype=np.int32)
        for slot, j in enumerate(blk.off_node_cols):
            placed = False
            for nn, rmsg in inter_recv_by_node.items():
                hit = np.searchsorted(rmsg.idx, j)
                if hit < rmsg.idx.size and rmsg.idx[hit] == j:
                    og[slot] = nn * inter_pad + hit
                    placed = True
                    break
            if not placed:
                for src_p, rmsg in final_recv_by_src.items():
                    hit = np.searchsorted(rmsg.idx, j)
                    if hit < rmsg.idx.size and rmsg.idx[hit] == j:
                        og[slot] = n_nodes * inter_pad + src_p * final_pad + hit
                        placed = True
                        break
            assert placed, f"rank {r} off-node col {j} unreachable"
        boff_gather.append(og)

        # -- COO blocks --------------------------------------------------------
        for key, block in (("on_proc", blk.on_proc), ("on_node", blk.on_node),
                           ("off_node", blk.off_node)):
            rows_i, cols_i, vals_i = block.to_coo()
            coo[key]["rows"].append(rows_i.astype(np.int32))
            coo[key]["cols"].append(cols_i.astype(np.int32))
            coo[key]["vals"].append(vals_i)

    stack_int("full_send", full_send, (ppn, full_pad))
    stack_int("init_send", init_send, (ppn, init_pad))
    stack_int("final_send", final_send, (ppn, final_pad))
    stack_int("inter_gather", inter_gather, (n_nodes, inter_pad))
    stack_int("bnode_gather", bnode_gather, (bnode_pad,))
    stack_int("boff_gather", boff_gather, (boff_pad,))
    for key in coo:
        arrays[f"{key}_rows"] = _pad_to(coo[key]["rows"], nnz_pads[key]).astype(np.int32)
        arrays[f"{key}_cols"] = _pad_to(coo[key]["cols"], nnz_pads[key]).astype(np.int32)
        arrays[f"{key}_vals"] = _pad_to(
            [v.astype(np.float32) for v in coo[key]["vals"]], nnz_pads[key], fill=0.0)

    pads = dict(full=full_pad, init=init_pad, inter=inter_pad, final=final_pad,
                bnode=bnode_pad, boff=boff_pad, **{f"nnz_{k}": v for k, v in nnz_pads.items()})
    return pads, arrays
