"""Communication graphs for standard and node-aware SpMV (paper Secs. 2.1, 4.1, 4.2).

Implements, verbatim, the paper's set machinery:

* standard:    ``P(r)`` (Eq. 8), ``D(r, t)`` (Eq. 9)
* node level:  ``N(n)`` (Eq. 13), ``E(n, m)`` (Eq. 14)
* distribution:``T((p,n))`` (Eq. 15), ``U((p,n))`` (Eq. 16)
* inter-node:  ``G((p,n))`` (Eq. 17), ``I((p,n),(q,m))`` (Eq. 18)
* intra-node:  ``L(·, locality)`` and ``J(·, ·, locality)`` for the three
  localities (on→off Eq. 19/20, off→on Eq. 21/22, on→on Eq. 23/24).

Note on index semantics: Eqs. (9), (14), (18)… write ``{i | A_ij ≠ 0 …}`` but
the worked Example 2.1 (Tables 2, 6, 9) clearly communicates the *vector*
indices ``j`` owned by the sender — the row index ``i`` merely witnesses the
need.  We implement the ``j`` semantics, which is what the algorithm consumes.

Note on the T/U orderings: the text maps the destination node with the most
data to ``(0, n)`` for sends and to ``(ppn-1, n)`` for receives; the paper's
hand-worked Table 9 does not follow any single consistent ordering (e.g. node
0's sends are in *ascending* data order).  We follow the text's rule with
node-id tie-breaking, and additionally support the TPU-natural pairing
``q = p`` (sender slot = receiver slot) used by the SPMD all-to-all lowering
— the paper itself notes the mapping is a free choice affecting only
intra-node traffic (Sec. 4.1).

All sets are computed once, "as the matrix is formed" (Sec. 2.1), in numpy;
the SPMD executor bakes them in as static gather/scatter maps.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Literal, Optional, Sequence, Tuple

import numpy as np

from repro.core.partition import RowPartition
from repro.core.topology import Topology

Locality = Literal["on_on", "on_off", "off_on"]


@dataclasses.dataclass(frozen=True)
class Message:
    """One point-to-point message: global vector indices ``idx`` from src to dst."""

    src: int
    dst: int
    idx: np.ndarray  # global vector (column) indices, ascending

    @property
    def size(self) -> int:
        return int(self.idx.size)


def flat_slot_map(msgs: Sequence[Message], slots: Sequence[int],
                  pad: int) -> Tuple[np.ndarray, np.ndarray]:
    """Sorted lookup table from global index -> flat padded-buffer position.

    ``msgs[i]`` lands in buffer slot ``slots[i]``; element k of a message
    sits at flat position ``slots[i] * pad + k``.  Returns parallel arrays
    ``(idx, pos)`` with ``idx`` ascending, so consumers resolve whole index
    arrays with one ``np.searchsorted`` instead of per-element probing.
    Indices must be disjoint across the phase's messages (asserted).
    """
    if not msgs:
        z = np.empty(0, dtype=np.int64)
        return z, z.copy()
    idx = np.concatenate([m.idx for m in msgs])
    pos = np.concatenate([s * pad + np.arange(m.size, dtype=np.int64)
                          for s, m in zip(slots, msgs)])
    order = np.argsort(idx, kind="stable")
    idx, pos = idx[order], pos[order]
    assert idx.size < 2 or (np.diff(idx) > 0).all(), \
        "phase delivers one index through two messages"
    return idx, pos


def lookup_slots(table: Tuple[np.ndarray, np.ndarray],
                 query: np.ndarray) -> np.ndarray:
    """Resolve ``query`` indices against a :func:`flat_slot_map` table."""
    idx, pos = table
    query = np.asarray(query, dtype=np.int64)
    p = np.searchsorted(idx, query)
    ok = (p < idx.size) & (idx[np.minimum(p, max(idx.size - 1, 0))] == query) \
        if idx.size else np.zeros(query.shape, bool)
    assert bool(np.all(ok)), \
        f"indices never delivered to this rank: {query[~ok][:8]}"
    return pos[p]


def _group_sorted(keys: np.ndarray, vals: np.ndarray) -> Dict[int, np.ndarray]:
    """{key: sorted unique vals with that key} for parallel arrays."""
    out: Dict[int, np.ndarray] = {}
    if keys.size == 0:
        return out
    order = np.argsort(keys, kind="stable")
    keys, vals = keys[order], vals[order]
    bounds = np.flatnonzero(np.diff(keys)) + 1
    for chunk_keys, chunk_vals in zip(np.split(keys, bounds), np.split(vals, bounds)):
        out[int(chunk_keys[0])] = np.unique(chunk_vals)
    return out


# ---------------------------------------------------------------------------
# Structure extraction: which (row-owner, col) pairs need communication
# ---------------------------------------------------------------------------

def _offproc_pairs(indptr: np.ndarray, indices: np.ndarray,
                   row_part: RowPartition, col_part: RowPartition
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(row_owner t, col_owner r, col j) for every off-process nonzero, deduped.

    The communication structure of an SpMV is a function of TWO
    partitions: ``row_part`` says which rank computes row i (and hence
    *needs* x_j for every nonzero A_ij), ``col_part`` says which rank
    owns x_j.  For the paper's square systems the two coincide; a
    rectangular operator (AMG P / R) separates them.
    """
    n_rows = len(indptr) - 1
    rows = np.repeat(np.arange(n_rows), np.diff(indptr))
    cols = indices
    t = row_part.owner[rows]
    r = col_part.owner[cols]
    off = t != r
    t, r, j = t[off], r[off], cols[off]
    # dedupe (t, r, j); j indexes the x/column space of size col_part.n_rows
    key = (t.astype(np.int64) * row_part.n_procs + r) * col_part.n_rows + j
    _, uniq = np.unique(key, return_index=True)
    return t[uniq], r[uniq], j[uniq]


# ---------------------------------------------------------------------------
# Standard plan (Sec. 2.1, Algorithm 1)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StandardPlan:
    """P(r) and D(r, t) realised as message lists per rank.

    ``partition`` is the ROW partition (who computes/owns output rows);
    ``col_partition`` the COLUMN partition (who owns x entries — the
    values the messages carry).  ``None`` means square single-partition
    (col == row), the paper's setting.
    """

    topology: Topology
    partition: RowPartition
    sends: List[List[Message]]  # sends[r] = messages rank r sends
    recvs: List[List[Message]]  # recvs[t] = messages rank t receives
    col_partition: Optional[RowPartition] = None

    @property
    def col_part(self) -> RowPartition:
        return self.col_partition if self.col_partition is not None \
            else self.partition

    def P(self, r: int) -> List[int]:
        return [m.dst for m in self.sends[r]]

    def D(self, r: int, t: int) -> np.ndarray:
        for m in self.sends[r]:
            if m.dst == t:
                return m.idx
        return np.empty(0, dtype=np.int64)


def build_standard_plan(indptr: np.ndarray, indices: np.ndarray,
                        part: RowPartition, topo: Topology,
                        col_part: Optional[RowPartition] = None,
                        pairs: Optional[Tuple[np.ndarray, np.ndarray,
                                              np.ndarray]] = None) -> StandardPlan:
    """``part`` is the row partition; ``col_part`` the column/x partition
    (defaults to ``part`` — the square single-partition case).

    ``pairs`` optionally supplies precomputed deduped off-process triples
    ``(t, r, j)`` (row owner, col owner, col) in place of extracting them
    from the matrix structure — the multi-step strategy splits one
    extraction between two sub-plans.  The default path is unchanged.
    """
    cpart = part if col_part is None else col_part
    t, r, j = pairs if pairs is not None else \
        _offproc_pairs(indptr, indices, part, cpart)
    sends: List[List[Message]] = [[] for _ in range(topo.n_procs)]
    recvs: List[List[Message]] = [[] for _ in range(topo.n_procs)]
    # group by sender r then receiver t
    for src in np.unique(r):
        mask = r == src
        for dst, idx in sorted(_group_sorted(t[mask], j[mask]).items()):
            msg = Message(src=int(src), dst=int(dst), idx=idx)
            sends[int(src)].append(msg)
            recvs[int(dst)].append(msg)
    return StandardPlan(topology=topo, partition=part, sends=sends,
                        recvs=recvs, col_partition=col_part)


# ---------------------------------------------------------------------------
# Node-aware plan (Sec. 4)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class NAPPlan:
    """Node-aware plan.  ``partition`` is the ROW partition,
    ``col_partition`` the COLUMN/x partition (``None`` = square,
    col == row) — see :class:`StandardPlan`."""

    topology: Topology
    partition: RowPartition
    # node-level sets
    node_dests: List[List[int]]                     # N(n)
    node_idx: Dict[Tuple[int, int], np.ndarray]     # E(n, m)
    # per-rank slot assignment (node ids, possibly repeated for chunk splits)
    T: List[List[int]]                              # T((p, n)) — dest nodes of rank
    U: List[List[int]]                              # U((p, n)) — src nodes of rank
    # realised message lists
    inter_sends: List[List[Message]]                # G/I — crosses the network
    inter_recvs: List[List[Message]]
    local_init_sends: List[List[Message]]           # L/J (on_node → off_node)
    local_init_recvs: List[List[Message]]
    local_final_sends: List[List[Message]]          # L/J (off_node → on_node)
    local_final_recvs: List[List[Message]]
    local_full_sends: List[List[Message]]           # L/J (on_node → on_node)
    local_full_recvs: List[List[Message]]
    col_partition: Optional[RowPartition] = None

    @property
    def col_part(self) -> RowPartition:
        return self.col_partition if self.col_partition is not None \
            else self.partition

    def N(self, n: int) -> List[int]:
        return self.node_dests[n]

    def E(self, n: int, m: int) -> np.ndarray:
        return self.node_idx.get((n, m), np.empty(0, dtype=np.int64))

    def G(self, rank: int) -> List[int]:
        return [m.dst for m in self.inter_sends[rank]]

    def I(self, rank: int, dst: int) -> np.ndarray:
        out = [m.idx for m in self.inter_sends[rank] if m.dst == dst]
        return np.unique(np.concatenate(out)) if out else np.empty(0, dtype=np.int64)


def _distribute_slots(items: Sequence[Tuple[int, int]], ppn: int) -> List[List[Tuple[int, int]]]:
    """Distribute (node, weight) items over ppn slots, balancing count & volume.

    Returns per-slot list of (node, n_chunks_for_this_pair-index) placeholders:
    concretely, a list per slot of (node, chunk_id) where chunk_id enumerates
    the contiguous chunk of E to use.  When there are fewer items than slots,
    heavy items are split across several slots so all processes communicate
    (Sec. 4.1); when more, items are dealt round-robin in descending-weight
    order (largest → slot 0, per the text).
    """
    slots: List[List[Tuple[int, int]]] = [[] for _ in range(ppn)]
    if not items:
        return slots
    ordered = sorted(items, key=lambda kv: (-kv[1], kv[0]))
    if len(ordered) >= ppn:
        for i, (node, _w) in enumerate(ordered):
            slots[i % ppn].append((node, 0))
        return slots
    # fewer destinations than processes: split the heavy ones.
    n_items = len(ordered)
    extra = ppn - n_items
    weights = np.array([w for _, w in ordered], dtype=np.float64)
    shares = np.ones(n_items, dtype=np.int64)
    if weights.sum() > 0:
        frac = weights / weights.sum() * extra
        add = np.floor(frac).astype(np.int64)
        rem = extra - add.sum()
        order = np.argsort(-(frac - add), kind="stable")
        add[order[:rem]] += 1
        shares += add
    else:
        shares[:extra] += 1
    slot = 0
    for (node, _w), k in zip(ordered, shares):
        for c in range(int(k)):
            slots[slot].append((node, c))
            slot += 1
    return slots


def _chunk(arr: np.ndarray, k: int, c: int) -> np.ndarray:
    """c-th of k near-equal contiguous chunks of arr."""
    bounds = np.linspace(0, arr.size, k + 1).astype(np.int64)
    return arr[bounds[c] : bounds[c + 1]]


def build_nap_plan(indptr: np.ndarray, indices: np.ndarray, part: RowPartition,
                   topo: Topology, pairing: str = "balanced",
                   col_part: Optional[RowPartition] = None,
                   pairs: Optional[Tuple[np.ndarray, np.ndarray,
                                         np.ndarray]] = None) -> NAPPlan:
    """Build the full node-aware plan.

    ``part`` is the row partition, ``col_part`` the column/x partition
    (defaults to ``part``: the paper's square single-partition case).

    pairing:
      * ``"balanced"`` — the paper's rule: send slots in descending-data order
        from p=0; receive slots in descending-data order from p=ppn-1.
      * ``"aligned"``  — TPU adaptation: receiver local id q equals sender
        local id p, so the inter-node phase is an all-to-all over the node
        mesh axis (documented in DESIGN.md §2).

    ``pairs`` optionally supplies precomputed deduped off-process triples
    ``(t, r, j)`` instead of extracting them from the structure — the
    multi-step strategy routes only its low-duplication share elsewhere
    and hands the rest here.  The default path is unchanged.
    """
    if pairing not in ("balanced", "aligned"):
        raise ValueError(pairing)
    cpart = part if col_part is None else col_part
    ppn, n_nodes, n_procs = topo.ppn, topo.n_nodes, topo.n_procs
    t, r, j = pairs if pairs is not None else \
        _offproc_pairs(indptr, indices, part, cpart)
    tn = topo.node_of_array(t)  # receiver node m
    rn = topo.node_of_array(r)  # sender node n
    off_node = tn != rn

    # ---- N(n), E(n, m) ----------------------------------------------------
    node_idx: Dict[Tuple[int, int], np.ndarray] = {}
    node_dests: List[List[int]] = [[] for _ in range(n_nodes)]
    on_t, on_r, on_j = t[off_node], r[off_node], j[off_node]
    on_tn, on_rn = tn[off_node], rn[off_node]
    for n in np.unique(on_rn):
        mask = on_rn == n
        grouped = _group_sorted(on_tn[mask], on_j[mask])
        node_dests[int(n)] = sorted(grouped)
        for m, idx in grouped.items():
            node_idx[(int(n), int(m))] = idx

    # ---- T/U slot assignment ----------------------------------------------
    send_slots: List[List[List[Tuple[int, int]]]] = []  # [n][p] -> [(m, chunk)]
    recv_slots: List[List[List[Tuple[int, int]]]] = []  # [m][q] -> [(n, chunk)]
    for n in range(n_nodes):
        items = [(m, int(node_idx[(n, m)].size)) for m in node_dests[n]]
        send_slots.append(_distribute_slots(items, ppn))
    node_srcs: List[List[int]] = [[] for _ in range(n_nodes)]
    for (n, m) in node_idx:
        node_srcs[m].append(n)
    for m in range(n_nodes):
        items = [(n, int(node_idx[(n, m)].size)) for n in sorted(node_srcs[m])]
        dist = _distribute_slots(items, ppn)
        if pairing == "balanced":
            dist = dist[::-1]  # largest fills from p = ppn-1 downward (text rule)
        recv_slots.append(dist)

    # chunk counts per (n, m) pair must agree on both sides
    send_count: Dict[Tuple[int, int], int] = {}
    recv_count: Dict[Tuple[int, int], int] = {}
    for n in range(n_nodes):
        for p in range(ppn):
            for (m, _c) in send_slots[n][p]:
                send_count[(n, m)] = send_count.get((n, m), 0) + 1
    for m in range(n_nodes):
        for q in range(ppn):
            for (n, _c) in recv_slots[m][q]:
                recv_count[(n, m)] = recv_count.get((n, m), 0) + 1

    # enumerate concrete chunk endpoints
    send_eps: Dict[Tuple[int, int], List[int]] = {k: [] for k in node_idx}  # ranks
    recv_eps: Dict[Tuple[int, int], List[int]] = {k: [] for k in node_idx}
    T: List[List[int]] = [[] for _ in range(n_procs)]
    U: List[List[int]] = [[] for _ in range(n_procs)]
    for n in range(n_nodes):
        for p in range(ppn):
            for (m, _c) in send_slots[n][p]:
                send_eps[(n, m)].append(topo.rank(p, n))
                T[topo.rank(p, n)].append(m)
    if pairing == "aligned":
        for (n, m), senders in send_eps.items():
            for s in senders:
                q = topo.local_of(s)
                recv_eps[(n, m)].append(topo.rank(q, m))
                U[topo.rank(q, m)].append(n)
    else:
        for m in range(n_nodes):
            for q in range(ppn):
                for (n, _c) in recv_slots[m][q]:
                    recv_eps[(n, m)].append(topo.rank(q, m))
                    U[topo.rank(q, m)].append(n)

    # ---- realise inter-node messages (G / I) -------------------------------
    # (vectorized: plan setup runs "as the matrix is formed" — its cost is
    # part of the paper's crossover story, so it must scale to 10^7+ nnz)
    inter_sends: List[List[Message]] = [[] for _ in range(n_procs)]
    inter_recvs: List[List[Message]] = [[] for _ in range(n_procs)]
    # (m, j) -> rank holding j after the inter phase, as parallel arrays
    rh_keys: List[np.ndarray] = []
    rh_home: List[np.ndarray] = []
    for (n, m), idx in node_idx.items():
        senders = send_eps[(n, m)]
        receivers = recv_eps[(n, m)]
        # k = max(...) with cycling keeps *both* sides as busy as they can be
        # (Sec. 4.1: all processes local to a node send and receive a similar
        # number and size of messages).  Empty chunks are skipped.
        k = max(len(senders), len(receivers), 1)
        for c in range(k):
            chunk = _chunk(idx, k, c)
            if chunk.size == 0:
                continue
            src = senders[c % len(senders)] if senders else topo.rank(0, n)
            dst = receivers[c % len(receivers)] if receivers else topo.rank(0, m)
            msg = Message(src=src, dst=dst, idx=chunk)
            inter_sends[src].append(msg)
            inter_recvs[dst].append(msg)
            rh_keys.append(m * np.int64(cpart.n_rows) + chunk)
            rh_home.append(np.full(chunk.size, dst, dtype=np.int64))

    def _emit(per_pair: Dict[int, np.ndarray], sends, recvs) -> None:
        for key in sorted(per_pair):
            src, dst = divmod(int(key), n_procs)
            msg = Message(src=src, dst=dst, idx=per_pair[key])
            sends[src].append(msg)
            recvs[dst].append(msg)

    # ---- local init redistribution (on_node -> off_node), Eqs. 19/20 ------
    local_init_sends: List[List[Message]] = [[] for _ in range(n_procs)]
    local_init_recvs: List[List[Message]] = [[] for _ in range(n_procs)]
    init_src, init_dst, init_j = [], [], []
    for rank in range(n_procs):
        for msg in inter_sends[rank]:
            owners = cpart.owner[msg.idx]
            off = owners != rank
            if off.any():
                init_src.append(owners[off])
                init_dst.append(np.full(int(off.sum()), rank, dtype=np.int64))
                init_j.append(msg.idx[off])
    if init_src:
        keys = np.concatenate(init_src) * n_procs + np.concatenate(init_dst)
        _emit(_group_sorted(keys, np.concatenate(init_j)),
              local_init_sends, local_init_recvs)

    # ---- local final redistribution (off_node -> on_node), Eqs. 21/22 -----
    # join (receiver rank t, col j) pairs against the (m, j) -> home map
    local_final_sends: List[List[Message]] = [[] for _ in range(n_procs)]
    local_final_recvs: List[List[Message]] = [[] for _ in range(n_procs)]
    if rh_keys:
        rhk = np.concatenate(rh_keys)
        rhh = np.concatenate(rh_home)
        order = np.argsort(rhk, kind="stable")
        rhk, rhh = rhk[order], rhh[order]
        pair_keys = on_tn.astype(np.int64) * cpart.n_rows + on_j
        pos = np.searchsorted(rhk, pair_keys)
        home = rhh[pos]                       # every needed (m, j) has a home
        mask = on_t != home
        if mask.any():
            keys = home[mask] * n_procs + on_t[mask]
            _emit(_group_sorted(keys, on_j[mask]),
                  local_final_sends, local_final_recvs)

    # ---- fully local (on_node -> on_node), Eqs. 23/24 ----------------------
    local_full_sends: List[List[Message]] = [[] for _ in range(n_procs)]
    local_full_recvs: List[List[Message]] = [[] for _ in range(n_procs)]
    same_node = ~off_node
    sn_t, sn_r, sn_j = t[same_node], r[same_node], j[same_node]
    if sn_t.size:
        keys = sn_r.astype(np.int64) * n_procs + sn_t
        _emit(_group_sorted(keys, sn_j), local_full_sends, local_full_recvs)

    return NAPPlan(
        topology=topo, partition=part, node_dests=node_dests, node_idx=node_idx,
        T=T, U=U,
        inter_sends=inter_sends, inter_recvs=inter_recvs,
        local_init_sends=local_init_sends, local_init_recvs=local_init_recvs,
        local_final_sends=local_final_sends, local_final_recvs=local_final_recvs,
        local_full_sends=local_full_sends, local_full_recvs=local_full_recvs,
        col_partition=col_part,
    )


# ---------------------------------------------------------------------------
# Message statistics (drives Figs. 8 & 9 and the cost model)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PhaseStats:
    """max-over-ranks message count / bytes sent by a single process."""

    max_msgs: int
    max_bytes: int
    total_msgs: int
    total_bytes: int

    @staticmethod
    def of(msg_lists: List[List[Message]], bytes_per_val: int = 8) -> "PhaseStats":
        counts = [len(msgs) for msgs in msg_lists]
        sizes = [sum(m.size for m in msgs) * bytes_per_val for msgs in msg_lists]
        return PhaseStats(
            max_msgs=max(counts, default=0), max_bytes=max(sizes, default=0),
            total_msgs=sum(counts), total_bytes=sum(sizes),
        )


def standard_stats(plan: StandardPlan, bytes_per_val: int = 8) -> Dict[str, PhaseStats]:
    topo = plan.topology
    inter = [[m for m in msgs if not topo.same_node(m.src, m.dst)] for msgs in plan.sends]
    intra = [[m for m in msgs if topo.same_node(m.src, m.dst)] for msgs in plan.sends]
    return {
        "inter": PhaseStats.of(inter, bytes_per_val),
        "intra": PhaseStats.of(intra, bytes_per_val),
    }


def nap_stats(plan: NAPPlan, bytes_per_val: int = 8) -> Dict[str, PhaseStats]:
    intra = [a + b + c for a, b, c in zip(
        plan.local_init_sends, plan.local_full_sends, plan.local_final_sends)]
    return {
        "inter": PhaseStats.of(plan.inter_sends, bytes_per_val),
        "intra": PhaseStats.of(intra, bytes_per_val),
        "intra_init": PhaseStats.of(plan.local_init_sends, bytes_per_val),
        "intra_full": PhaseStats.of(plan.local_full_sends, bytes_per_val),
        "intra_final": PhaseStats.of(plan.local_final_sends, bytes_per_val),
    }
