"""SPMD (shard_map) executor for the node-aware distributed SpGEMM.

The on-device program mirrors the NAPSpMV three-step exactly — intra-node
all_to_all (fully-local + init), ONE aggregated inter-node all_to_all,
intra-node final scatter — with every buffer generalised from vector
slots to **value-level row blocks**: a message slot of the SpMV carried
one x value per index; here it carries the concatenated CSR values of
the B rows it names, padded to the compile-time value budget of its
phase (:meth:`repro.spgemm.plan.SpGemmPlan.value_pads`).  Row structure
(indices + counts) never moves at run time: it is compiled into static
gather maps host-side at plan build, exactly where the SpMV plans bake
their slot maps.

Local compute is the vectorised row-expansion kernel of
:func:`repro.amg.matmul.csr_matmul` ported to jnp: every local A nonzero
``a_ik`` multiplies B row k gathered from the packed value domain
``[b_loc | full_recv | inter_recv | final_recv]`` (positions precomputed
per expanded product), and duplicates merge with one ``segment_sum``
into the precomputed C nnz slots.  C's structure (the merged sparsity of
every rank's rows) is compiled host-side; the device program computes
values only, so re-running with new B values (same structure) costs one
pack -> SPMD run -> unpack.

``dtype`` selects the payload precision: float32 (the repo's device
default) or float64 when jax's x64 mode is enabled — the float64 program
matches the host ``csr_matmul`` to round-off (~1 ulp: XLA's scatter-add
associates sums differently than the host ``reduceat``; the *simulate*
backend of :mod:`repro.spgemm.simulate` is the bit-for-bit oracle).
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.integrity import (IntegrityError, NAP_MESSAGE_PHASES,
                                  build_fault_spec, message_phases,
                                  phase_index, verify_wire)
from repro.core.partition import RowPartition
from repro.core.topology import Topology
from repro.spgemm.plan import (SpGemmPlan, build_spgemm_plan,
                               expand_positions, local_value_index,
                               lookup_row_starts)
from repro.spgemm.simulate import simulate_spgemm
from repro.sparse.csr import CSR

# number of shard_map SpGEMM program applications this process has run —
# the multidev sweep asserts hierarchy assembly actually went through the
# device program (not a host fallback)
_RUN_COUNTER = {"runs": 0}


def shardmap_spgemm_runs() -> int:
    return _RUN_COUNTER["runs"]


_MESH_CACHE: Dict[Tuple[int, int], object] = {}


def _default_mesh(topo: Topology):
    """One ("node", "proc") mesh per topology shape — a stable mesh
    identity keeps the per-compiled-plan program memo effective."""
    key = (topo.n_nodes, topo.ppn)
    if key not in _MESH_CACHE:
        from repro.compat import make_mesh
        _MESH_CACHE[key] = make_mesh(key, ("node", "proc"))
    return _MESH_CACHE[key]


def _spgemm_namespace():
    """Registry-backed device memo (repro.mesh.buffers), like SpMV plans."""
    from repro.mesh.buffers import default_registry
    return default_registry().namespace("spgemm-plan")


@dataclasses.dataclass
class CompiledSpGemm:
    """Static arrays for the shard_map SpGEMM, stacked over ranks.

    ``arrays`` holds the per-phase value gather maps + the expansion
    triple (positions into the packed value domain, output C slots, A
    values); ``c_rows``/``c_cols``/``c_nnz`` the host-side C structure
    used to assemble the global CSR from per-rank value shards.
    """

    topo: Topology
    row_part: RowPartition
    mid_part: RowPartition
    shape: Tuple[int, int]
    method: str
    b_nnz_pad: int
    vpads: Dict[str, int]
    exp_pad: int
    c_nnz_pad: int
    arrays: Dict[str, np.ndarray]
    c_rows: List[np.ndarray]          # per rank: global C row ids (merged)
    c_cols: List[np.ndarray]          # per rank: C col ids (merged, row-major)
    c_nnz: List[int]
    plan: Optional[SpGemmPlan] = None
    _dev_cache: Dict[str, object] = dataclasses.field(
        default_factory=_spgemm_namespace, repr=False, compare=False)
    # jitted program memo per (mesh id, payload dtype): repeated
    # applications (AMG setup sweeps, benchmarks) re-use one trace
    _run_cache: Dict[tuple, object] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    def device_arrays(self, dtype) -> Dict[str, object]:
        import jax.numpy as jnp
        from repro.core.spmv_jax import _memo_device_arrays

        arrs = dict(self.arrays)
        # exp_a is staged per requested payload dtype (cache key per dtype)
        key = f"exp_a_{np.dtype(dtype).name}"
        arrs[key] = self.arrays["exp_a"].astype(dtype)
        del arrs["exp_a"]
        out = _memo_device_arrays(self.topo, arrs, self._dev_cache)
        out["exp_a"] = out.pop(key)
        return out


_SPGEMM_CACHE: Dict[tuple, CompiledSpGemm] = {}
_SPGEMM_CACHE_MAX = 8


def clear_spgemm_cache() -> None:
    _SPGEMM_CACHE.clear()


def _spgemm_cache_key(a: CSR, b: CSR, row_part: RowPartition,
                      mid_part: RowPartition, topo: Topology,
                      method: str) -> tuple:
    h = hashlib.sha1()
    # A's values are baked into the expansion arrays; B's values are a
    # runtime input, so only B's STRUCTURE keys the compiled program.
    for arr in (a.indptr, a.indices, a.data, b.indptr, b.indices,
                row_part.owner, mid_part.owner):
        h.update(np.ascontiguousarray(arr).tobytes())
    return (method, h.hexdigest(), a.shape, b.shape, topo.n_nodes, topo.ppn)


def compile_spgemm(a: CSR, b: CSR, row_part: RowPartition,
                   mid_part: RowPartition, topo: Topology,
                   method: str = "nap", plan: Optional[SpGemmPlan] = None,
                   cache: bool = True) -> CompiledSpGemm:
    """Compile the SpGEMM plan into static shard_map arrays.

    Builds (or accepts) the :class:`SpGemmPlan`, resolves every B row a
    rank consumes to its position in the packed value domain, expands
    the local products and merges C's structure — all bulk numpy, cached
    like :func:`repro.core.spmv_jax.compile_plan`.
    """
    key = None
    if plan is None and cache:
        key = _spgemm_cache_key(a, b, row_part, mid_part, topo, method)
        hit = _SPGEMM_CACHE.pop(key, None)
        if hit is not None:
            _SPGEMM_CACHE[key] = hit
            return hit
    if plan is None:
        plan = build_spgemm_plan(a, b, row_part, mid_part, topo,
                                 method=method)
    assert plan.method == method
    comm = plan.comm
    n_procs, ppn, n_nodes = topo.n_procs, topo.ppn, topo.n_nodes
    b_counts = plan.b_counts
    lvi = local_value_index(mid_part, b_counts)
    owner = mid_part.owner
    b_nnz_pad = max(1, int(mid_part_value_counts(mid_part, b_counts).max()))
    vpads = plan.value_pads()

    def send_map(msgs, n_slots: int, vpad: int, slot_of, base_of) -> np.ndarray:
        out = np.zeros((n_slots, vpad), dtype=np.int32)
        for m in msgs:
            pos = expand_positions(base_of(m.idx), b_counts[m.idx])
            out[slot_of(m), : pos.size] = pos
        return out

    arrays: Dict[str, np.ndarray] = {}
    per_rank: Dict[str, List[np.ndarray]] = {k: [] for k in (
        "full_send_v", "init_send_v", "inter_gather_v", "final_send_v",
        "send_v", "exp_pos", "exp_out", "exp_a")}
    c_rows: List[np.ndarray] = []
    c_cols: List[np.ndarray] = []
    c_nnz: List[int] = []

    if method == "nap":
        off_full = b_nnz_pad
        off_inter = off_full + ppn * vpads["full"]
        off_final = off_inter + n_nodes * vpads["inter"]
        domain_len = off_final + ppn * vpads["final"]
    else:
        off_recv = b_nnz_pad
        domain_len = off_recv + n_procs * vpads["pair"]

    for r in range(n_procs):
        loc_base = lambda idx: lvi[idx]

        if method == "nap":
            per_rank["full_send_v"].append(send_map(
                comm.local_full_sends[r], ppn, vpads["full"],
                lambda m: topo.local_of(m.dst), loc_base))
            per_rank["init_send_v"].append(send_map(
                comm.local_init_sends[r], ppn, vpads["init"],
                lambda m: topo.local_of(m.dst), loc_base))

            init_map = plan.recv_value_map(r, "init", vpads["init"])

            def inter_base(idx: np.ndarray) -> np.ndarray:
                own = owner[idx] == r
                base = np.empty(idx.size, dtype=np.int64)
                base[own] = lvi[idx[own]]
                if not own.all():
                    base[~own] = b_nnz_pad + lookup_row_starts(
                        init_map, idx[~own])
                return base

            per_rank["inter_gather_v"].append(send_map(
                comm.inter_sends[r], n_nodes, vpads["inter"],
                lambda m: topo.node_of(m.dst), inter_base))

            inter_map = plan.recv_value_map(r, "inter", vpads["inter"])
            per_rank["final_send_v"].append(send_map(
                comm.local_final_sends[r], ppn, vpads["final"],
                lambda m: topo.local_of(m.dst),
                lambda idx: lookup_row_starts(inter_map, idx)))

            full_map = plan.recv_value_map(r, "full", vpads["full"])
            final_map = plan.recv_value_map(r, "final", vpads["final"])
            # combined off-node row -> domain start (inter buffer when this
            # rank is the row's home, final buffer otherwise; disjoint)
            comb_rows = np.concatenate([inter_map[0], final_map[0]])
            comb_starts = np.concatenate([off_inter + inter_map[1],
                                          off_final + final_map[1]])
            order = np.argsort(comb_rows, kind="stable")
            comb = (comb_rows[order], comb_starts[order])
            assert comb[0].size < 2 or (np.diff(comb[0]) > 0).all(), \
                "off-node B row delivered through two phases"

            def domain_base(k: np.ndarray) -> np.ndarray:
                own = owner[k] == r
                on_node = (~own) & (topo.node_of_array(owner[k])
                                    == topo.node_of(r))
                off = ~(own | on_node)
                base = np.empty(k.size, dtype=np.int64)
                base[own] = lvi[k[own]]
                if on_node.any():
                    base[on_node] = off_full + lookup_row_starts(
                        full_map, k[on_node])
                if off.any():
                    base[off] = lookup_row_starts(comb, k[off])
                return base
        else:
            per_rank["send_v"].append(send_map(
                comm.sends[r], n_procs, vpads["pair"],
                lambda m: m.dst, loc_base))
            pair_map = plan.recv_value_map(r, "pair", vpads["pair"])

            def domain_base(k: np.ndarray) -> np.ndarray:
                own = owner[k] == r
                base = np.empty(k.size, dtype=np.int64)
                base[own] = lvi[k[own]]
                if not own.all():
                    base[~own] = off_recv + lookup_row_starts(
                        pair_map, k[~own])
                return base

        # -- row expansion + C structure merge (per rank, bulk numpy) --------
        g_rows = row_part.rows_of(r)
        local = a.select_rows(g_rows)
        ai, ak, av = local.to_coo()
        counts = b_counts[ak] if ak.size else np.empty(0, dtype=np.int64)
        pos = expand_positions(domain_base(ak) if ak.size
                                else np.empty(0, dtype=np.int64), counts)
        b_take = expand_positions(plan.b_indptr[ak] if ak.size
                                   else np.empty(0, dtype=np.int64), counts)
        cols_exp = plan.b_indices[b_take]
        rows_exp = np.repeat(ai, counts)
        a_exp = np.repeat(av, counts)
        key_exp = rows_exp * np.int64(plan.shape[1]) + cols_exp
        uniq, exp_out = np.unique(key_exp, return_inverse=True)
        per_rank["exp_pos"].append(pos.astype(np.int32))
        per_rank["exp_out"].append(exp_out.astype(np.int32))
        per_rank["exp_a"].append(a_exp)
        c_rows.append(g_rows[(uniq // plan.shape[1]).astype(np.int64)])
        c_cols.append((uniq % plan.shape[1]).astype(np.int64))
        c_nnz.append(int(uniq.size))

    assert domain_len < np.iinfo(np.int32).max

    exp_pad = max(1, max(p.size for p in per_rank["exp_pos"]))
    c_nnz_pad = max(1, max(c_nnz))

    def stack(name: str, pads: Tuple[int, ...], dtype=np.int32,
              fill=0) -> None:
        out = np.full((n_procs,) + pads, fill, dtype=dtype)
        for r, arr in enumerate(per_rank[name]):
            if arr.ndim == 1:
                out[r, : arr.size] = arr
            else:
                out[r] = arr
        arrays[name] = out

    if method == "nap":
        stack("full_send_v", (ppn, vpads["full"]))
        stack("init_send_v", (ppn, vpads["init"]))
        stack("inter_gather_v", (n_nodes, vpads["inter"]))
        stack("final_send_v", (ppn, vpads["final"]))
    else:
        stack("send_v", (n_procs, vpads["pair"]))
    stack("exp_pos", (exp_pad,))
    stack("exp_out", (exp_pad,))
    stack("exp_a", (exp_pad,), dtype=np.float64, fill=0.0)

    compiled = CompiledSpGemm(
        topo=topo, row_part=row_part, mid_part=mid_part, shape=plan.shape,
        method=method, b_nnz_pad=b_nnz_pad, vpads=vpads, exp_pad=exp_pad,
        c_nnz_pad=c_nnz_pad, arrays=arrays, c_rows=c_rows, c_cols=c_cols,
        c_nnz=c_nnz, plan=plan)
    if key is not None:
        while len(_SPGEMM_CACHE) >= _SPGEMM_CACHE_MAX:
            _SPGEMM_CACHE.pop(next(iter(_SPGEMM_CACHE)))
        _SPGEMM_CACHE[key] = compiled
    return compiled


def mid_part_value_counts(mid_part: RowPartition,
                          b_counts: np.ndarray) -> np.ndarray:
    """Total B values owned per rank (the b_loc shard lengths)."""
    out = np.zeros(mid_part.n_procs, dtype=np.int64)
    for r in range(mid_part.n_procs):
        rows = mid_part.rows_of(r)
        out[r] = int(b_counts[rows].sum()) if rows.size else 0
    return out


def pack_b_values(b: CSR, compiled: CompiledSpGemm, dtype) -> np.ndarray:
    """B values -> [n_nodes, ppn, b_nnz_pad] shards (rows concatenated in
    ascending-row order per owner, matching :func:`local_value_index`)."""
    topo, part = compiled.topo, compiled.mid_part
    out = np.zeros((topo.n_procs, compiled.b_nnz_pad), dtype=dtype)
    counts = np.diff(b.indptr)
    for r in range(topo.n_procs):
        rows = part.rows_of(r)
        if rows.size:
            take = expand_positions(b.indptr[rows], counts[rows])
            out[r, : take.size] = b.data[take]
    return out.reshape(topo.n_nodes, topo.ppn, compiled.b_nnz_pad)


def unpack_c_values(c_shards: np.ndarray, compiled: CompiledSpGemm) -> CSR:
    """Per-rank C value shards -> the global C CSR (host structure +
    device values).  Per-rank slots beyond ``c_nnz[r]`` are padding."""
    topo = compiled.topo
    w = np.asarray(c_shards).reshape(topo.n_procs, -1)
    rows = np.concatenate(compiled.c_rows) if compiled.c_rows else \
        np.empty(0, dtype=np.int64)
    cols = np.concatenate(compiled.c_cols) if compiled.c_cols else \
        np.empty(0, dtype=np.int64)
    vals = np.concatenate([w[r, : compiled.c_nnz[r]].astype(np.float64)
                           for r in range(topo.n_procs)]) if rows.size else \
        np.empty(0, dtype=np.float64)
    # per-rank structure is merged and row-major; the global from_coo is a
    # pure re-sort across ranks (each C row lives on exactly one rank)
    return CSR.from_coo(rows, cols, vals, compiled.shape,
                        sum_duplicates=False)


def spgemm_shardmap(compiled: CompiledSpGemm, mesh, dtype=None,
                    integrity: bool = False):
    """Build the jitted shard_map SpGEMM: f(b_shards) -> c_value_shards.

    ``b_shards`` is [n_nodes, ppn, b_nnz_pad] (``pack_b_values``); the
    output [n_nodes, ppn, c_nnz_pad] per-rank C values in the compiled
    structure's order.  ``dtype`` pins the payload precision (float32
    default; float64 needs jax x64 mode and matches the host product to
    round-off — the simulate backend is the bit-for-bit oracle).

    ``integrity=True`` builds the INSTRUMENTED program: every value-block
    payload is checksummed by the sender before the scripted fault
    boundary, the per-call fault-spec argument (the SpMV operators'
    :func:`repro.core.integrity.build_fault_spec` array) is applied as a
    pure transform at the pack boundary, and the receiver recomputes
    after the collective — ``run(b_shards, fault_spec)`` then returns
    ``(c_shards, chk)`` with ``chk`` the
    [n_nodes, ppn, n_phases, 2, max_slots] aux output of
    :func:`repro.core.integrity.verify_wire`.  With ``integrity=False``
    the emitted program is the bare one, bit-for-bit.
    """
    import jax
    import jax.numpy as jnp
    from jax.ops import segment_sum
    from jax.sharding import PartitionSpec as P
    from repro.core.spmv_jax import _apply_fault, _msg_checksums, _stack_chk

    if dtype is None:
        dtype = jnp.float32
    run_key = (id(mesh), np.dtype(dtype).name, bool(integrity))
    hit = compiled._run_cache.get(run_key)
    if hit is not None:
        return hit
    topo = compiled.topo
    nn, ppn = topo.n_nodes, topo.ppn
    c_nnz_pad, vpads = compiled.c_nnz_pad, compiled.vpads
    ph = phase_index(compiled.method)
    max_slots = max(ppn, nn) if compiled.method == "nap" else topo.n_procs

    def make_exchange(fault_spec, chks):
        # Sender checksums the CLEAN payload, the scripted fault (if
        # armed for this device+phase) corrupts it at the pack boundary,
        # payload and checksum words travel through the same collective,
        # the receiver recomputes.  Uninstrumented this is literally the
        # bare all_to_all.
        def exchange(buf, phase, axis):
            if not integrity:
                return jax.lax.all_to_all(buf, axis, 0, 0, tiled=True)
            sent = _msg_checksums(buf)
            buf = _apply_fault(buf, fault_spec[ph[phase]])
            recv = jax.lax.all_to_all(buf, axis, 0, 0, tiled=True)
            expect = jax.lax.all_to_all(sent[:, None], axis, 0, 0,
                                        tiled=True)[:, 0]
            chks[phase] = (expect, _msg_checksums(recv))
            return recv
        return exchange

    if compiled.method == "nap":
        names = ["full_send_v", "init_send_v", "inter_gather_v",
                 "final_send_v", "exp_pos", "exp_out", "exp_a"]

        def per_device(b_loc, *args):
            squeeze = lambda x: x.reshape(x.shape[2:])
            fault_spec = None
            if integrity:
                fault_spec = squeeze(args[0])               # [n_phases, 4]
                args = args[1:]
            (b_loc, full_send_v, init_send_v, inter_gather_v, final_send_v,
             exp_pos, exp_out, exp_a) = map(squeeze, (b_loc,) + args)
            chks = {}
            exchange = make_exchange(fault_spec, chks)
            # Phases A+B: intra-node row-block exchanges over "proc".
            full_recv = exchange(b_loc[full_send_v], "full", "proc")
            init_recv = exchange(b_loc[init_send_v], "init", "proc")
            # Phase C: ONE aggregated inter-node all_to_all over "node".
            staged = jnp.concatenate([b_loc, init_recv.reshape(-1)])
            inter_recv = exchange(staged[inter_gather_v], "inter", "node")
            inter_flat = inter_recv.reshape(-1)
            # Phase D: intra-node scatter of the aggregated rows.
            final_recv = exchange(inter_flat[final_send_v], "final", "proc")
            domain = jnp.concatenate([b_loc, full_recv.reshape(-1),
                                      inter_flat, final_recv.reshape(-1)])
            # local compute: csr_matmul's row expansion + duplicate merge
            c = segment_sum(exp_a * domain[exp_pos], exp_out,
                            num_segments=c_nnz_pad)
            if not integrity:
                return c.reshape(1, 1, c_nnz_pad)
            chk = _stack_chk([chks[p] for p in NAP_MESSAGE_PHASES],
                             max_slots)
            return (c.reshape(1, 1, c_nnz_pad),
                    chk.reshape((1, 1) + chk.shape))
    else:
        names = ["send_v", "exp_pos", "exp_out", "exp_a"]

        def per_device(b_loc, *args):
            squeeze = lambda x: x.reshape(x.shape[2:])
            fault_spec = None
            if integrity:
                fault_spec = squeeze(args[0])
                args = args[1:]
            b_loc, send_v, exp_pos, exp_out, exp_a = map(
                squeeze, (b_loc,) + args)
            chks = {}
            exchange = make_exchange(fault_spec, chks)
            recv = exchange(b_loc[send_v], "pair", ("node", "proc"))
            domain = jnp.concatenate([b_loc, recv.reshape(-1)])
            c = segment_sum(exp_a * domain[exp_pos], exp_out,
                            num_segments=c_nnz_pad)
            if not integrity:
                return c.reshape(1, 1, c_nnz_pad)
            chk = _stack_chk([chks["pair"]], max_slots)
            return (c.reshape(1, 1, c_nnz_pad),
                    chk.reshape((1, 1) + chk.shape))

    dev = compiled.device_arrays(dtype)
    spec = P("node", "proc")
    n_in = 1 + len(names) + (1 if integrity else 0)
    smapped = jax.shard_map(per_device, mesh=mesh,
                            in_specs=(spec,) * n_in,
                            out_specs=(spec, spec) if integrity else spec,
                            check_vma=False)
    if integrity:
        jitted = jax.jit(lambda b_shards, fault_spec: smapped(
            b_shards, fault_spec, *[dev[k] for k in names]))

        def run(b_shards, fault_spec):
            import jax.numpy as jnp
            _RUN_COUNTER["runs"] += 1
            return jitted(jnp.asarray(b_shards, dtype),
                          jnp.asarray(np.asarray(fault_spec), jnp.int32))
    else:
        jitted = jax.jit(lambda b_shards: smapped(
            b_shards, *[dev[k] for k in names]))

        def run(b_shards):
            import jax.numpy as jnp
            _RUN_COUNTER["runs"] += 1
            return jitted(jnp.asarray(b_shards, dtype))

    run.method = compiled.method
    run.integrity = bool(integrity)
    compiled._run_cache[run_key] = run
    return run


def distributed_spgemm(a: CSR, b: CSR, row_part: RowPartition,
                       mid_part: RowPartition, topo: Topology, *,
                       method: str = "nap", backend: str = "shardmap",
                       mesh=None, dtype=None, cache: bool = True,
                       integrity: str = "off", faults=(),
                       report: Optional[dict] = None) -> CSR:
    """One-call distributed ``C = A @ B``.

    ``backend="simulate"`` runs the exact float64 message-passing oracle
    (bit-for-bit equal to the host :func:`repro.amg.matmul.csr_matmul`);
    ``"shardmap"`` compiles and runs the SPMD program (float32 payloads
    by default; ``dtype=jnp.float64`` under jax x64 mode matches the
    host product to round-off).

    ``integrity="detect"`` runs the checksum-instrumented program and
    raises :class:`repro.core.integrity.IntegrityError` with
    phase+message attribution when any value-exchange payload arrives
    different from what the sender packed; ``"recover"`` retries the
    whole product once with the fault boundary cleared (the scripted
    faults in ``faults`` — :class:`repro.core.integrity.MessageFault`
    on this method's exchange phases, forward direction — fire on the
    first run only, so a recovered product is bit-identical to the
    fault-free run).  Pass a dict as ``report`` to receive the check
    counters.  Integrity is shardmap-only: the simulate backend IS the
    bit-exact oracle the checks are calibrated against.
    """
    if integrity not in ("off", "detect", "recover"):
        raise ValueError(f"integrity must be 'off'|'detect'|'recover', "
                         f"got {integrity!r}")
    if faults and integrity == "off":
        raise ValueError("scripted message faults need "
                         "integrity='detect'|'recover'")
    if integrity != "off" and backend != "shardmap":
        raise ValueError("integrity-checked SpGEMM is shardmap-only (the "
                         "simulate backend is the bit-exact oracle the "
                         "checks are calibrated against)")
    if backend == "simulate":
        plan = build_spgemm_plan(a, b, row_part, mid_part, topo,
                                 method=method)
        return simulate_spgemm(a, b, plan)
    if backend != "shardmap":
        raise ValueError(f"backend must be 'shardmap'|'simulate', "
                         f"got {backend!r}")
    compiled = compile_spgemm(a, b, row_part, mid_part, topo, method=method,
                              cache=cache)
    if mesh is None:
        mesh = _default_mesh(topo)
    np_dtype = np.dtype(np.float32 if dtype is None else dtype)
    b_shards = pack_b_values(b, compiled, np_dtype)
    if integrity == "off":
        run = spgemm_shardmap(compiled, mesh, dtype=dtype)
        return unpack_c_values(np.asarray(run(b_shards)), compiled)

    for f in faults:
        if f.direction not in ("any", "forward"):
            raise ValueError("SpGEMM message faults are forward-only "
                             "(the product has no transpose exchange)")
        if f.phase == "compute":
            raise ValueError("SpGEMM integrity covers the value exchanges; "
                             "compute-side faults belong to the SpMV "
                             "operators' ABFT check")
    run = spgemm_shardmap(compiled, mesh, dtype=dtype, integrity=True)
    spec = build_fault_spec(topo, faults, method)
    phases = message_phases(method)
    counters = {"wire_checks": topo.n_procs * len(phases),
                "wire_mismatches": 0, "faults_injected": len(list(faults)),
                "retries": 0, "recovered": 0}
    c_shards, chk = run(b_shards, spec)
    mism = verify_wire(np.asarray(chk), phases, topo.ppn, "forward")
    if mism:
        counters["wire_mismatches"] = len(mism)
        if integrity == "detect":
            if report is not None:
                report.update(counters)
            raise IntegrityError(
                f"{len(mism)} integrity mismatch(es) in distributed "
                f"SpGEMM: " + "; ".join(str(m) for m in mism), mism)
        counters["retries"] = 1
        c_shards, chk = run(b_shards, np.zeros_like(spec))
        again = verify_wire(np.asarray(chk), phases, topo.ppn, "forward")
        if again:
            if report is not None:
                report.update(counters)
            raise IntegrityError(
                "integrity mismatch persisted through the clean SpGEMM "
                "retry: " + "; ".join(str(m) for m in again), again)
        counters["recovered"] = 1
    if report is not None:
        report.update(counters)
    return unpack_c_values(np.asarray(c_shards), compiled)
