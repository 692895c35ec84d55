"""SPMD (shard_map) programs for the distributed SpMV on a device mesh.

Entry points: the canonical user-facing surface is
:func:`repro.api.operator` (one ``NapOperator`` over every backend); this
module holds what its ``"shardmap"`` backend runs:

* :class:`Schedule` — an exchange strategy as data: its message phases
  in order, and the buffers of Algorithm 3 gathered from what they
  receive.  ``nap``, ``multistep`` and ``standard`` are three schedules
  (:data:`STRATEGIES`), not three code paths.
* :func:`compile_plan` — the one plan compiler: it builds a strategy's
  comm-graph plan and schedule, fills each phase's send array and each
  buffer's gather array, and returns a :class:`CompiledPlan`.
* :func:`forward_program` / :func:`transpose_program` — the one program
  builder per direction; both walk the plan's schedule.

**Schedules.**  ``nap`` (Algorithms 2+3 with ``pairing="aligned"``) runs
``full`` and ``init`` over the intra-node ``"proc"`` axis, ONE
aggregated inter-node ``inter`` exchange over ``"node"`` (the only phase
that crosses pods), then ``final`` over ``"proc"``; its buffers are
``bnode`` (from ``full``) and ``boff`` (from ``inter | final``).
``multistep`` is nap's schedule plus a flat ``direct`` phase for the
low-duplication columns, which ``boff`` also reads.  ``standard``
(Algorithm 1) is one flat ``pair`` exchange and one buffer ``buf``.
Phase names are those of :func:`repro.core.integrity.message_phases`:
they name the fault-spec rows and the named scopes
``repro.exchange.<phase>``.

**Rectangular operators**: every compiled plan carries TWO partitions —
``part`` (rows: who owns the output) and ``col_part`` (columns: who owns
the x entries).  Send/recv/gather maps derive from ``col_part`` and the
output layout from ``part``; the transpose direction simply swaps the
two.  A square single-partition operator (``col_part=None``) behaves
exactly as before; AMG restriction/prolongation pass a genuine ``[m, n]``
matrix with independent partitions.

**Transpose SpMV**: ``A.T @ x`` against the SAME compiled plan, with the
send/recv roles reversed — every forward gather ``buf = recv[idx_map]``
becomes a scatter-add ``segment_sum(contrib, idx_map)`` and every tiled
``all_to_all`` is its own adjoint (it is a (device, slot) transposition),
so the reversed program is the exact adjoint of the forward one.  Padded
map slots all point at position 0 but carry exactly-zero contributions
(no nonzero references a padding slot), so the scatters stay inert where
the forward gathers were.  AMG restriction and BiCG-type solvers get the
transpose for free from the forward plan — no second plan build.

XLA programs are static-SPMD, so the comm plans of :mod:`comm_graph` are
*compiled* into padded gather maps + collectives, once, at plan-build time
(exactly where the paper's MPI implementation builds its send lists).

Mesh convention: ``("node", "proc")`` with shape ``(n_nodes, ppn)`` — on a
real fleet "node" is the pod/DCI axis and "proc" the intra-pod ICI axis.

Local compute (``local_compute=``) — the **adaptive engine**:

* ``"auto"`` (default) — a density-driven format autotuner: plan
  compilation records per-rank layout stats (block fill density, padded
  FLOPs, bytes moved — see :func:`repro.core.cost_model.local_format_times`)
  and picks the cheapest of bsr/ell/coo under a two-term roofline.  The
  decision is recorded on :class:`CompiledPlan` (``.autotune``).
* ``"bsr"`` — the **fused Pallas BSR path**: the ``local_spmv`` blocks of
  Algorithm 3 are compiled into one MXU-aligned block-sparse matmul over
  the packed ``[v_loc | buffers...]`` x domain
  (:mod:`repro.kernels.bsr_spmv.fused`), with multi-RHS (nv-wide SpMM)
  support.  Slots are ordered on-process → on-node → off-node, so the
  Pallas pipeline streams the blocks that depend on inter-node data
  last — the paper's Isend/compute overlap, expressed as pipeline stages.
* ``"ell"`` — the **ELL path** (:mod:`repro.kernels.ell_spmv`, plain XLA)
  for low-density / block-hostile ranks where padded BSR tiles densify:
  kmax-padded rows, one vectorised row gather per slot.
* ``"coo"`` — scalar ``segment_sum`` gathers, one block per packed-domain
  segment (the pre-fusion reference path, kept as an in-graph oracle).

**Zero-copy x**: every per-rank buffer length is rounded up to the block
lane width bn at compile time, so the fused BSR kernel reads ``v_loc``
and each buffer as separate refs via slot-indexed index_maps — the
packed x operand is never materialised as an HBM pad/concat
(``materialize_x=True`` re-enables the old concat path as a bit-for-bit
A/B oracle).  The ELL path forms no buffer: its column ids are composed
with the buffer gathers at plan compile and index the received domain
``[v_loc | each buffer's source phases...]``, which it concatenates once
per call before its row gathers.

Plan compilation is fully vectorised (bulk ``np.searchsorted`` against
each phase's receive slot map — no per-element Python loops) and cached
keyed on (strategy family, matrix structure+values, partition, topology,
block shape, requested local_compute, autotuner params), so repeated
SpMVs (AMG V-cycles, training steps) pay the plan-build cost once.

Padding note: all per-rank buffers are padded to the max over ranks; the
paper's T/U load balancing minimises exactly this padding.  Effective vs
padded bytes are both reported by :func:`padded_traffic`.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.ops import segment_sum
from jax.sharding import Mesh, PartitionSpec as P

from repro.comm.multistep import build_multistep_plan, resolve_threshold
from repro.core.comm_graph import (Message, NAPPlan, build_nap_plan,
                                   build_standard_plan, flat_slot_map,
                                   lookup_slots)
from repro.core.integrity import COMPUTE_PHASE
from repro.core.cost_model import (LOCAL_FORMATS, LocalComputeParams,
                                   TPU_V5E_LOCAL, choose_ell_split,
                                   choose_local_format, local_format_times)
from repro.core.partition import RowPartition
from repro.core.spans import span
from repro.core.spmv import LocalBlocks, split_all_blocks
from repro.core.topology import Topology
from repro.kernels.bsr_spmv.fused import fused_bsr_spmm, fused_bsr_spmm_packed
from repro.kernels.ell_spmv.kernel import ell_spmm_packed
from repro.sparse.bsr import BSR
from repro.sparse.csr import CSR
from repro.sparse.ell import ELL, stack_ell


def _pad_to(arrs: List[np.ndarray], pad: int, fill: float = 0) -> np.ndarray:
    out = np.full((len(arrs), pad), fill, dtype=arrs[0].dtype if arrs else np.int64)
    for i, a in enumerate(arrs):
        out[i, : a.size] = a
    return out


def _ceil_to(x: int, b: int) -> int:
    return -(-x // b) * b


def _memo_device_arrays(topo: Topology, arrays: Dict[str, np.ndarray],
                        cache: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Mesh-shaped ((n_nodes, ppn, ...)) device copies of the host arrays.

    Memoized per array name: repeated executor binds against one compiled
    plan reuse the device buffers instead of re-staging every host array
    on every bind (lazy format arrays appear later, so the cache fills
    incrementally — existing entries are never re-copied).

    ``cache`` is normally a :class:`repro.mesh.buffers.BufferNamespace`
    (dict protocol) so the persistent-buffer registry accounts staging,
    reuse and eviction; placement goes through
    :func:`repro.mesh.buffers.stage_mesh_array` — one shard per mesh
    device, a global ``jax.Array`` under a multi-process
    ``jax.distributed`` mesh.
    """
    from repro.mesh.buffers import stage_mesh_array
    nn, ppn = topo.n_nodes, topo.ppn
    for k, v in arrays.items():
        if k not in cache:
            cache[k] = stage_mesh_array(v.reshape((nn, ppn) + v.shape[1:]),
                                        topo)
    return {k: cache[k] for k in arrays}


def _plan_namespace():
    """Fresh buffer namespace for one compiled plan's ``_dev_cache``."""
    from repro.mesh.buffers import default_registry
    return default_registry().namespace("spmv-plan")


# ---------------------------------------------------------------------------
# Exchange schedules
# ---------------------------------------------------------------------------

#: The flat rank axis: standard's ``pair`` and multistep's ``direct``.
FLAT = ("node", "proc")
#: Mesh axes in the order the programs take their phases' send arrays:
#: intra-node phases, then the inter-node one, then the flat ones.
_AXIS_ORDER = ("proc", "node", FLAT)


@dataclasses.dataclass(frozen=True)
class Phase:
    """One message phase: a tiled ``all_to_all`` over ``axis``.

    Each rank sends ``slots`` messages of ``pad`` elements, one per peer
    on the axis.  ``send`` names the plan array ``[n_procs, slots, pad]``
    of positions in the phase's source: the flattened concatenation of
    ``source``, where ``"v_loc"`` (always first) is the rank's own x
    shard and any other entry an earlier phase's receive buffer.
    ``sends``/``recvs`` are the comm-graph plan's messages per rank.
    """

    name: str
    axis: object
    send: str
    source: Tuple[str, ...]
    sends: List[List[Message]] = dataclasses.field(repr=False)
    recvs: List[List[Message]] = dataclasses.field(repr=False)
    slots: int
    pad: int

    def slot_of(self, topo: Topology, rank: int) -> int:
        """The slot a message to or from ``rank`` occupies."""
        if self.axis == "proc":
            return topo.local_of(rank)
        return topo.node_of(rank) if self.axis == "node" else rank

    def recv_slot_map(self, topo: Topology, rank: int):
        """:func:`flat_slot_map` of ``rank``'s receive buffer.

        The program lays out what a phase receives as ``[slots, pad]``:
        the slot is the sender's local id on ``"proc"``, its node on
        ``"node"`` (the buffer the aggregated inter-node all-to-all
        produces) and its rank on the flat axis.  Every send and gather
        array is built against this layout."""
        msgs = self.recvs[rank]
        return flat_slot_map(msgs, [self.slot_of(topo, m.src) for m in msgs],
                             self.pad)


@dataclasses.dataclass(frozen=True)
class Buffer:
    """One buffer of Algorithm 3: the columns of the ``blocks`` column
    classes of :class:`repro.core.spmv.LocalBlocks`, in that order, padded
    to ``pad``, gathered through the plan array ``gather``
    ``[n_procs, pad]`` from the flattened concatenation of the ``source``
    phases' receive buffers."""

    name: str
    gather: str
    source: Tuple[str, ...]
    blocks: Tuple[str, ...]
    pad: int = 0


@dataclasses.dataclass(frozen=True)
class Schedule:
    """Message phases in the order they run, and the buffers read from
    them.  The packed x domain is ``[v_loc | buffers...]``; the received
    domain, which composed ELL ids index, ``[v_loc | each buffer's source
    phases...]``."""

    phases: Tuple[Phase, ...]
    buffers: Tuple[Buffer, ...]

    def send_names(self) -> List[str]:
        return [p.send for p in sorted(
            self.phases, key=lambda p: _AXIS_ORDER.index(p.axis))]


def _phase(topo: Topology, name: str, axis, send: str, source, sends,
           recvs) -> Phase:
    slots = {"proc": topo.ppn, "node": topo.n_nodes}.get(axis, topo.n_procs)
    pad = max(1, max((m.size for msgs in sends for m in msgs), default=1))
    return Phase(name, axis, send, tuple(source), sends, recvs, slots, pad)


def _nap_schedule(plan: NAPPlan, topo: Topology, direct=None) -> Schedule:
    """Algorithm 3's four phases (plus multistep's ``direct`` one, from
    its :class:`StandardPlan`), and the on-node / off-node buffers."""
    phases = [
        _phase(topo, "full", "proc", "full_send", ("v_loc",),
               plan.local_full_sends, plan.local_full_recvs),
        _phase(topo, "init", "proc", "init_send", ("v_loc",),
               plan.local_init_sends, plan.local_init_recvs),
        _phase(topo, "inter", "node", "inter_gather", ("v_loc", "init"),
               plan.inter_sends, plan.inter_recvs),
        _phase(topo, "final", "proc", "final_send", ("inter",),
               plan.local_final_sends, plan.local_final_recvs)]
    off = ("inter", "final")
    if direct is not None:
        phases.append(_phase(topo, "direct", FLAT, "direct_send", ("v_loc",),
                             direct.sends, direct.recvs))
        off += ("direct",)
    return Schedule(tuple(phases), (
        Buffer("bnode", "bnode_gather", ("full",), ("on_node",)),
        Buffer("boff", "boff_gather", off, ("off_node",))))


def _standard_schedule(plan, topo: Topology) -> Schedule:
    return Schedule(
        (_phase(topo, "pair", FLAT, "send_idx", ("v_loc",), plan.sends,
                plan.recvs),),
        (Buffer("buf", "buf_gather", ("pair",), ("on_node", "off_node")),))


@dataclasses.dataclass(frozen=True)
class Strategy:
    """What :func:`compile_plan` needs of one exchange strategy."""

    family: Callable   # (topo, threshold) -> the compile cache's family tag
    build: Callable    # (a, part, topo, col_part, threshold) -> comm plan
    schedule: Callable  # (comm plan, topo) -> Schedule


STRATEGIES: Dict[str, Strategy] = {
    "nap": Strategy(
        lambda topo, thr: "nap",
        lambda a, part, topo, cp, thr: build_nap_plan(
            a.indptr, a.indices, part, topo, pairing="aligned",
            col_part=cp),
        _nap_schedule),
    "multistep": Strategy(
        # the threshold changes the split, so it is part of the family
        lambda topo, thr: f"multistep:{resolve_threshold(thr, topo)}",
        lambda a, part, topo, cp, thr: build_multistep_plan(
            a.indptr, a.indices, part, topo, pairing="aligned",
            col_part=cp, threshold=thr),
        lambda plan, topo: _nap_schedule(plan.nap, topo, plan.direct)),
    "standard": Strategy(
        lambda topo, thr: "standard",
        lambda a, part, topo, cp, thr: build_standard_plan(
            a.indptr, a.indices, part, topo, col_part=cp),
        _standard_schedule),
}


# ---------------------------------------------------------------------------
# Compiled plan
# ---------------------------------------------------------------------------

#: ABFT checksum-vector names — value arrays derived from the matrix
#: values, so a hot swap refreshes them like the format value arrays.
_ABFT_NAMES = ("abft_col", "abft_col_abs", "abft_row", "abft_row_abs")
#: ... and their received-domain twins, which the composed ELL programs read.
_ABFT_RECV_NAMES = ("abft_col_recv", "abft_col_abs_recv")


@dataclasses.dataclass
class CompiledPlan:
    """Static arrays for one strategy's shard_map SpMV, stacked over ranks.

    Rectangular contract: ``part`` is the ROW partition (output layout,
    ``rows_pad`` rows per shard) and ``col_part`` the COLUMN partition
    (input x layout, ``cols_pad`` entries per shard).  They coincide for
    square single-partition operators; an AMG P / R separates them.
    ``arrays`` holds each phase's send array and each buffer's gather
    array (see :class:`Schedule`), then the local formats, emitted lazily
    from ``local_blocks`` by the ``ensure_*`` methods.
    """

    topo: Topology
    part: RowPartition
    rows_pad: int
    schedule: Schedule
    arrays: Dict[str, np.ndarray]  # stacked [n_procs, ...] index/value arrays
    col_part: Optional[RowPartition] = None  # None = square (col == row)
    cols_pad: int = 0                        # 0 = square (== rows_pad)
    comm: str = "nap"               # the strategy (a key of STRATEGIES)
    plan: Optional[object] = None   # its comm-graph plan
    block_shape: Tuple[int, int] = (8, 128)  # fused BSR (bm, bn)
    local_blocks: Optional[List[LocalBlocks]] = None
    # format autotuner verdict + inputs (chosen format, per-rank stats,
    # modeled per-format times) for BOTH directions (the transpose
    # verdict lives under autotune["transpose"])
    autotune: Dict[str, object] = dataclasses.field(default_factory=dict)
    requested_local_compute: str = "auto"
    ell_kmax: int = 0
    ell_t_kmax: int = 0
    # per-name device-array memo (see _memo_device_arrays) — a registry
    # namespace, so resident plan buffers are accounted and releasable
    _dev_cache: Dict[str, jnp.ndarray] = dataclasses.field(
        default_factory=_plan_namespace, repr=False, compare=False)
    # matrix whose VALUES this plan currently carries (swap_values target)
    a_ref: Optional[CSR] = dataclasses.field(
        default=None, repr=False, compare=False)
    # compile-cache key to retire on a value swap (the global cache keys on
    # the ORIGINAL data hash — a swapped plan must not satisfy it)
    _cache_token: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False)

    def __post_init__(self) -> None:
        if self.col_part is None:
            self.col_part = self.part
        if not self.cols_pad:
            self.cols_pad = self.rows_pad

    # -- the domains --------------------------------------------------------
    @property
    def pads(self) -> Dict[str, int]:
        """Each phase's message pad and each buffer's pad, by name."""
        s = self.schedule
        return {p.name: p.pad for p in s.phases + s.buffers}

    @property
    def segments(self) -> Tuple[str, ...]:
        """The packed x domain's segments: ``v_loc``, then the buffers."""
        return ("v_loc",) + tuple(b.name for b in self.schedule.buffers)

    def lengths(self) -> Dict[str, int]:
        """Element length of ``v_loc`` and of each phase's receive buffer."""
        out = {"v_loc": self.cols_pad}
        out.update((p.name, p.slots * p.pad) for p in self.schedule.phases)
        return out

    @property
    def packed_x_len(self) -> int:
        """Element length of the packed x ``[v_loc | buffers...]``."""
        return self.cols_pad + sum(b.pad for b in self.schedule.buffers)

    @property
    def recv_x_len(self) -> int:
        """Element length of the received x domain ``[v_loc | each
        buffer's source phases...]`` (each recv buffer flattened) that
        the forward ELL ids index."""
        n = self.lengths()
        return self.cols_pad + sum(n[s] for b in self.schedule.buffers
                                   for s in b.source)

    def recv_domain_map(self) -> np.ndarray:
        """[n_procs, packed_x_len] int32: where each packed-domain column
        sits in the received domain (:attr:`recv_x_len`) — v_loc maps to
        itself, each buffer slot through its gather array into its source
        phases' block.  Composing a column id with this map replaces the
        program's buffer gathers."""
        n, lengths = self.topo.n_procs, self.lengths()
        out = [np.broadcast_to(np.arange(self.cols_pad, dtype=np.int32),
                               (n, self.cols_pad))]
        at = self.cols_pad
        for b in self.schedule.buffers:
            out.append((at + self.arrays[b.gather]).astype(np.int32))
            at += sum(lengths[s] for s in b.source)
        return np.concatenate(out, axis=1)

    def segment_coo(self, blk: LocalBlocks) -> List[Tuple[np.ndarray, ...]]:
        """One rank's ``(rows, cols, vals)`` per packed-domain segment,
        columns local to the segment: the on-process block for ``v_loc``,
        then each buffer's blocks, a later block's columns after the
        earlier blocks' ones."""
        out = [blk.on_proc.to_coo()]
        for b in self.schedule.buffers:
            parts, at = [], 0
            for k in b.blocks:
                rr, cc, vv = getattr(blk, k).to_coo()
                parts.append((rr, cc + at, vv))
                at += getattr(blk, f"{k}_cols").size
            out.append(parts[0] if len(parts) == 1 else
                       tuple(np.concatenate(z) for z in zip(*parts)))
        return out

    def packed_coo(self):
        """Per rank, ``(rows, cols, vals)`` over the packed x domain."""
        assert self.local_blocks is not None, "compiled plan lost its blocks"
        starts = np.cumsum([0, self.cols_pad]
                           + [b.pad for b in self.schedule.buffers])
        for blk in self.local_blocks:
            segs = self.segment_coo(blk)
            yield (np.concatenate([s[0] for s in segs]),
                   np.concatenate([s[1] + o for s, o in zip(segs, starts)]),
                   np.concatenate([s[2] for s in segs]))

    # -- local formats ------------------------------------------------------
    @property
    def chosen_local_compute(self) -> str:
        return str(self.autotune.get("chosen", "coo"))

    def resolve_local_compute(self, requested: str) -> str:
        """Map an executor's ``local_compute`` request to a concrete format.

        Precedence: an explicit executor request wins; an executor
        ``"auto"`` defers to a concrete format requested at compile time,
        and only then to the autotuner's verdict."""
        if requested == "auto":
            if self.requested_local_compute != "auto":
                return self.requested_local_compute
            return self.chosen_local_compute
        if requested not in LOCAL_FORMATS:
            raise ValueError(requested)
        return requested

    def resolve_transpose_local_compute(self, requested: str) -> str:
        """Transpose-direction format: only ``ell`` and ``coo`` have
        transposed programs, so an explicit ``ell``/``coo`` request wins,
        while ``auto`` — and ``bsr``, which has no transposed Pallas
        kernel — defer to the transpose autotuner verdict recorded at
        compile time under ``autotune["transpose"]``."""
        if requested not in ("auto",) + LOCAL_FORMATS:
            raise ValueError(requested)
        for cand in (requested, self.requested_local_compute):
            if cand in ("ell", "coo"):
                return cand
        t = self.autotune.get("transpose", {})
        return str(t.get("chosen", "coo")) if isinstance(t, dict) else "coo"

    def local_arrays(self, fmt: str, transpose: bool = False) -> List[str]:
        """Materialise the local product's arrays for ``fmt`` in one
        direction; their names, in the order the program takes them."""
        if fmt == "coo":
            self.ensure_coo()
            return [f"{s}_{w}" for s in self.segments
                    for w in ("rows", "cols", "vals")]
        if fmt == "bsr":
            self.ensure_fused()
            return ["fused_cols", "fused_blocks"]
        (self.ensure_ell_t if transpose else self.ensure_ell)()
        t = "_t" if transpose else ""
        return [f"ell{t}_cols", f"ell{t}_vals"] + (
            [f"ell{t}_chunk_row"] if f"ell{t}_chunk_row" in self.arrays
            else [])

    def ensure_coo(self) -> None:
        """Materialise the COO blocks (lazily, once): ``<segment>_rows``,
        ``_cols`` and ``_vals`` per packed-domain segment, padded to the
        segment's max over ranks."""
        if "v_loc_rows" in self.arrays:
            return
        assert self.local_blocks is not None, "compiled plan lost its blocks"
        per_rank = [self.segment_coo(blk) for blk in self.local_blocks]
        for i, seg in enumerate(self.segments):
            pad = max(1, max(p[i][0].size for p in per_rank))
            for j, what in enumerate(("rows", "cols")):
                self.arrays[f"{seg}_{what}"] = _pad_to(
                    [p[i][j].astype(np.int32) for p in per_rank],
                    pad).astype(np.int32)
            self.arrays[f"{seg}_vals"] = _pad_to(
                [p[i][2].astype(np.float32) for p in per_rank], pad,
                fill=0.0)

    def packed_ell(self, transpose: bool = False):
        """A_r (``transpose``: A_r^T) of every rank as ELL over the packed
        domain, rows split at the width that direction's autotuner chose:
        :func:`repro.sparse.ell.stack_ell`'s ``(cols, vals, width,
        chunk_row)``."""
        at = self.autotune.get("transpose", {}) if transpose else self.autotune
        shape = (self.rows_pad, self.packed_x_len)[::-1 if transpose else 1]
        per_rank = []
        for rr, cc, vv in self.packed_coo():
            if transpose:
                rr, cc = cc, rr
            per_rank.append(ELL.from_coo(rr, cc, vv, shape,
                                         n_rows_pad=shape[0],
                                         split=_split_width(at)))
        return stack_ell(per_rank)

    def ensure_ell(self) -> None:
        """Materialise the forward ELL arrays (lazily, once) — the
        block-hostile branch of the adaptive engine.  The column ids are
        composed with :meth:`recv_domain_map` at emission, so the ELL
        program reads the received buffers directly and gathers no
        buffer; slot order and values are those of the packed-domain
        emission.  Rows are split at the width the autotuner chose
        (``autotune["ell"]``), with ``ell_chunk_row`` beside the arrays
        where it is below the longest row."""
        if "ell_cols" in self.arrays:
            return
        cols, vals, self.ell_kmax, chunk_row = self.packed_ell()
        self.arrays["ell_cols"] = _compose_cols(cols, self.recv_domain_map())
        self.arrays["ell_vals"] = vals
        if chunk_row is not None:
            self.arrays["ell_chunk_row"] = chunk_row

    def ensure_ell_t(self) -> None:
        """Materialise the TRANSPOSED packed ELL arrays (lazily, once):
        A_r^T over the packed contribution domain with x = u_loc — the
        vectorised alternative to the transpose COO scatter path, split
        at the transpose autotuner's width like :meth:`ensure_ell`."""
        if "ell_t_cols" in self.arrays:
            return
        cols, vals, self.ell_t_kmax, chunk_row = self.packed_ell(True)
        self.arrays["ell_t_cols"] = cols
        self.arrays["ell_t_vals"] = vals
        if chunk_row is not None:
            self.arrays["ell_t_chunk_row"] = chunk_row

    def ensure_fused(self) -> None:
        """Materialise the fused Pallas BSR arrays (lazily, once).

        Each rank's blocks become one padded-uniform BSR over the packed
        domain, whose segments are all multiples of bn, so a block column
        never straddles two buffers.  Block columns sort ascending within
        each block row, which orders slots on-process → on-node →
        off-node — the overlap-friendly streaming order.  The fused
        layout densifies (bm, bn) tiles, which on block-hostile
        structures costs far more memory/time than the gather maps — so
        it is built only when a "bsr" executor is requested, and cached
        on the compiled plan.
        """
        if "fused_cols" in self.arrays:
            return
        bm, bn = self.block_shape
        shape = (self.rows_pad, self.packed_x_len)
        cols, blocks, _ = _stack_padded_bsr([
            BSR.from_coo(rr, cc, vv, shape, bm=bm, bn=bn)
            for rr, cc, vv in self.packed_coo()])
        self.arrays["fused_cols"] = cols
        self.arrays["fused_blocks"] = blocks

    def ensure_abft(self) -> None:
        """Materialise the ABFT checksum vectors (lazily, once): the
        per-rank COLUMN sums ``c_p = 1^T A_p`` over the packed x domain
        (forward check: ``sum(y_p) == c_p · x_packed``) and ROW sums
        ``A_p 1`` over the output rows (transpose check), plus their
        absolute-value twins feeding the dtype-aware tolerance scale.
        Accumulated in float64 from the f32-rounded values the kernels
        actually multiply, then stored f32 — value arrays, so a hot swap
        refreshes them with zero retraces."""
        if "abft_col" in self.arrays:
            return
        n, n_x, rows_pad = self.topo.n_procs, self.packed_x_len, self.rows_pad
        col = np.zeros((n, n_x), np.float64)
        cola = np.zeros((n, n_x), np.float64)
        row = np.zeros((n, rows_pad), np.float64)
        rowa = np.zeros((n, rows_pad), np.float64)
        for r, (rr, cc, vv) in enumerate(self.packed_coo()):
            v32 = vv.astype(np.float32).astype(np.float64)
            np.add.at(col[r], cc, v32)
            np.add.at(cola[r], cc, np.abs(v32))
            np.add.at(row[r], rr, v32)
            np.add.at(rowa[r], rr, np.abs(v32))
        for k, v in zip(_ABFT_NAMES, (col, cola, row, rowa)):
            self.arrays[k] = v.astype(np.float32)

    def ensure_abft_recv(self) -> None:
        """Materialise (lazily, once) ``abft_col_recv``/``abft_col_abs_recv``:
        the ABFT column sums moved onto the received domain through
        :meth:`recv_domain_map`, so a composed ELL program checks
        ``sum(y_p)`` against the very buffers its product reads.  Padding
        slots carry zero weight, so the scatter-add is exact wherever the
        map sends each real column to its own position."""
        if _ABFT_RECV_NAMES[0] in self.arrays:
            return
        self.ensure_abft()
        remap = self.recv_domain_map()
        for src, dst in zip(_ABFT_NAMES, _ABFT_RECV_NAMES):
            packed = self.arrays[src].astype(np.float64)
            out = np.zeros((packed.shape[0], self.recv_x_len), np.float64)
            for r in range(packed.shape[0]):
                np.add.at(out[r], remap[r], packed[r])
            self.arrays[dst] = out.astype(np.float32)

    def device_arrays(self) -> Dict[str, jnp.ndarray]:
        """Mesh-shaped (n_nodes, ppn, ...) device arrays, memoized per name."""
        return _memo_device_arrays(self.topo, self.arrays, self._dev_cache)

    def swap_values(self, a_new: CSR) -> List[str]:
        """Hot-swap matrix VALUES in place; sparsity must be identical.

        Rebuilds every materialised value array (the lazy formats and the
        ABFT vectors) against the SAME pads and gather maps, evicts only
        those names from the device memo, and retires the plan from the
        global compile cache (which keys on the old data hash).  Executors
        bound to this plan pick the new values up on their next call with
        zero retraces — value arrays are jit arguments, and the
        replacements have identical shapes/dtypes.  Returns the changed
        array names.
        """
        old = self.a_ref
        if old is None:
            raise ValueError("compiled plan lost its matrix reference; "
                             "recompile instead of swapping values")
        if (tuple(a_new.shape) != tuple(old.shape)
                or not np.array_equal(a_new.indptr, old.indptr)
                or not np.array_equal(a_new.indices, old.indices)):
            raise ValueError(
                "swap_values requires an identical sparsity structure (same "
                "shape, indptr, indices); a structural change needs a "
                "recompile")
        self.local_blocks = split_all_blocks(a_new, self.part, self.topo,
                                             col_part=self.col_part)
        segs = self.segments
        recv = _ABFT_RECV_NAMES[0] in self.arrays
        # (names, value names, ensure): structural companions regenerate
        # to identical values, so their device-memo entries stay valid
        lazy = [
            ([f"{s}_{w}" for s in segs for w in ("rows", "cols", "vals")],
             [f"{s}_vals" for s in segs], self.ensure_coo),
            (["ell_cols", "ell_vals"], ["ell_vals"], self.ensure_ell),
            (["ell_t_cols", "ell_t_vals"], ["ell_t_vals"], self.ensure_ell_t),
            (["fused_cols", "fused_blocks"], ["fused_blocks"],
             self.ensure_fused),
            (list(_ABFT_NAMES + (_ABFT_RECV_NAMES if recv else ())),
             list(_ABFT_NAMES + (_ABFT_RECV_NAMES if recv else ())),
             self.ensure_abft_recv if recv else self.ensure_abft)]
        changed = []
        for names, values, ensure in lazy:
            if names[0] in self.arrays:
                for k in names:
                    del self.arrays[k]
                ensure()
                changed += values
        for name in changed:
            self._dev_cache.pop(name, None)
        self.a_ref = a_new
        if self._cache_token is not None:
            _COMPILE_CACHE.pop(self._cache_token, None)
            self._cache_token = None
        return changed


def _compose_cols(cols: np.ndarray, remap: np.ndarray) -> np.ndarray:
    """Rewrite stacked ``[n_procs, rows, kmax]`` packed-domain column ids
    through the per-rank ``remap`` (in place, rank by rank); ``-1``
    padding slots stay ``-1``."""
    for r in range(cols.shape[0]):
        c = cols[r]
        cols[r] = np.where(c >= 0, remap[r][np.maximum(c, 0)], -1)
    return cols


def _split_width(autotune: Dict[str, object]) -> int:
    """The ELL split width an autotune verdict (one direction's) chose,
    0 where it chose the unsplit layout."""
    width = int(autotune.get("ell", {}).get("ell_split_width", 0))
    kmax = int(autotune.get("stats", {}).get("ell_kmax", 0))
    return width if 0 < width < kmax else 0


def _ell_product(ell_args, xs, n_rows: int):
    """The ELL local product over the program's ELL arguments
    ``(cols, vals[, chunk_row])`` (see :meth:`CompiledPlan.local_arrays`),
    as its ``[n_rows, nv]`` result: the stack's padding rows are dropped."""
    cols, vals, *chunk_row = ell_args
    return ell_spmm_packed(cols, vals, xs, *chunk_row, n_rows=n_rows)


# ---------------------------------------------------------------------------
# Plan compilation (vectorised + cached)
# ---------------------------------------------------------------------------

_COMPILE_CACHE: Dict[tuple, CompiledPlan] = {}
_COMPILE_CACHE_MAX = 16  # LRU bound: entries retain plans + dense fused blocks


def clear_compile_cache() -> None:
    _COMPILE_CACHE.clear()


def _cache_put(key: tuple, compiled: CompiledPlan) -> None:
    while len(_COMPILE_CACHE) >= _COMPILE_CACHE_MAX:
        _COMPILE_CACHE.pop(next(iter(_COMPILE_CACHE)))
    _COMPILE_CACHE[key] = compiled


def _cache_get(key: tuple) -> Optional[CompiledPlan]:
    hit = _COMPILE_CACHE.pop(key, None)
    if hit is not None:
        _COMPILE_CACHE[key] = hit  # re-insert: dict order is the LRU order
    return hit


def _cache_key(a: CSR, part: RowPartition, topo: Topology,
               block_shape: Tuple[int, int], local_compute: str,
               tuner: LocalComputeParams, tag: str,
               col_part: Optional[RowPartition] = None) -> tuple:
    h = hashlib.sha1()
    arrs = [a.indptr, a.indices, a.data, part.owner]
    if col_part is not None:
        arrs.append(col_part.owner)
    for arr in arrs:
        h.update(np.ascontiguousarray(arr).tobytes())
    # block_shape and the tuner signature cover every autotuner input that
    # is not a function of the hashed matrix (fill density etc. derive from
    # structure + block shape); local_compute covers the requested mode and
    # tag the strategy family — switching any of them can never return a
    # stale compiled plan.
    return (tag, h.hexdigest(), a.shape, topo.n_nodes, topo.ppn,
            tuple(block_shape), str(local_compute), tuner.signature())


def _format_stats_from_coo(per_rank_rc: List[Tuple[np.ndarray, np.ndarray]],
                           rows_pad: int, n_x: int, nnz_pad_total: int,
                           block_shape: Tuple[int, int],
                           tuner: LocalComputeParams,
                           ell_n_x: Optional[int] = None) -> Dict[str, object]:
    """Layout stats + format decision from per-rank packed-domain COOs,
    without materialising any format.  ``ell_n_x`` is the x length the
    ELL product reads (the received domain its composed ids index;
    defaults to ``n_x``).

    BSR tile counts come from unique (block row, block col) keys over the
    packed column domain; ELL kmax from per-row counts, and the chunk
    rows of each row-split width (the powers of two below kmax) from the
    same counts — all pure bulk numpy.  The SPMD program is
    bulk-synchronous, so the global decision uses stats maxed over
    ranks; per-rank verdicts are recorded for diagnostics/benchmarks.
    ``"ell"`` holds the ELL layout the tuner picked
    (:func:`_ell_layout`).
    """
    bm, bn = block_shape
    nbc = n_x // bn
    n_brows = -(-rows_pad // bm)
    per_rank = []
    row_counts = []
    kb_global = 1
    for rank, (rows, cols) in enumerate(per_rank_rc):
        keys = np.unique((rows // bm) * nbc + cols // bn)
        kb = int(np.bincount((keys // nbc).astype(np.int64),
                             minlength=n_brows).max(initial=0))
        counts = np.bincount(rows.astype(np.int64), minlength=rows_pad)
        nnz = int(rows.size)
        per_rank.append({
            "rank": rank, "nnz": nnz, "bsr_tiles": int(keys.size),
            "bsr_fill": nnz / max(int(keys.size) * bm * bn, 1),
            "ell_kmax": max(1, int(counts.max(initial=0))),
        })
        row_counts.append(counts)
        kb_global = max(kb_global, kb)
    ke_global = max(e["ell_kmax"] for e in per_rank)
    # chunk rows of each split width K, per rank: ceil(row length / K)
    # summed over rows (a rank whose rows all fit K keeps one chunk row
    # per row that holds an entry); the stack runs the largest, padded
    # by slot_loop_rows as the tuner prices it
    widths = [1 << i for i in range(max(0, (ke_global - 1).bit_length()))]
    chunks = [[int((-(-c // k)).sum()) for k in widths] for c in row_counts]
    stats = {
        "rows_pad": rows_pad, "n_x": n_x, "nnz_pad": nnz_pad_total,
        "bsr_blocks": n_brows * kb_global, "bm": bm, "bn": bn,
        "ell_kmax": ke_global, "ell_n_x": n_x if ell_n_x is None else ell_n_x,
        "ell_splits": [[k, max(1, max(c[i] for c in chunks))]
                       for i, k in enumerate(widths)],
    }
    times = local_format_times(stats, tuner)
    for entry, c in zip(per_rank, chunks):
        rank_stats = dict(stats, bsr_blocks=entry["bsr_tiles"],
                          ell_kmax=entry["ell_kmax"], nnz_pad=entry["nnz"],
                          ell_splits=[[k, n] for k, n in zip(widths, c)
                                      if k < entry["ell_kmax"]])
        entry["choice"] = choose_local_format(rank_stats, tuner)
    return {
        "chosen": min(LOCAL_FORMATS, key=lambda f: times[f]),
        "times": times,
        "stats": stats,
        "ell": _ell_layout(stats, tuner,
                           max((e["nnz"] for e in per_rank), default=0)),
        "per_rank": per_rank,
        "tuner": tuner.name,
    }


def _ell_layout(stats: Dict[str, object], tuner: LocalComputeParams,
                nnz: int) -> Dict[str, object]:
    """The ELL layout the tuner picks for one direction, as
    ``autotune_report()`` and ``op.stats()`` report it: the split width
    (``ell_kmax`` where rows are not split), the chunk rows and slots
    each rank's program runs, of them the rows the stack added
    (``ell_pad_rows``, :func:`repro.sparse.ell.slot_loop_rows`), and
    the slots per entry of the fullest rank."""
    width, chunk_rows, _ = choose_ell_split(stats, tuner)
    counted = dict(stats.get("ell_splits", ())).get(width, stats["rows_pad"])
    slots = width * chunk_rows
    return {"ell_split_width": width, "ell_chunk_rows": chunk_rows,
            "ell_pad_rows": chunk_rows - counted,
            "ell_slots": slots, "ell_slots_per_nnz": slots / max(nnz, 1)}


def _transpose_format_stats(per_rank_rc_t: List[Tuple[np.ndarray, np.ndarray]],
                            out_len: int, n_x: int, nnz_pad_total: int,
                            block_shape: Tuple[int, int],
                            tuner: LocalComputeParams) -> Dict[str, object]:
    """Format stats + verdict for the TRANSPOSED local compute.

    The transpose program multiplies A_r^T (shape [packed contribution
    domain, rows_pad]) against u_loc, so the roofline runs with the roles
    swapped: output rows = the packed domain, x = the row-partition
    shard.  Only ``ell`` and ``coo`` are candidates — there is no
    transposed Pallas BSR kernel — so the verdict is the argmin of those
    two (this is what ``op.T`` resolves ``local_compute="auto"`` to).
    """
    at = _format_stats_from_coo(per_rank_rc_t, out_len, n_x, nnz_pad_total,
                                block_shape, tuner)
    times = {f: at["times"][f] for f in ("ell", "coo")}
    return {"chosen": min(times, key=lambda f: times[f]), "times": times,
            "stats": at["stats"], "ell": at["ell"],
            "per_rank": at["per_rank"], "tuner": tuner.name}


def _stack_padded_bsr(per_rank: List[BSR]) -> Tuple[np.ndarray, np.ndarray, int]:
    """Align every rank's padded-uniform layout to one shared kmax and stack
    into the [n_procs, n_brows, kmax(, bm, bn)] arrays the kernel consumes."""
    kmax = max(1, max((int(np.diff(b.indptr).max(initial=0)) for b in per_rank),
                      default=1))
    cols_s, blocks_s = [], []
    for b in per_rank:
        c, d, _ = b.padded_uniform(kmax=kmax)
        cols_s.append(c)
        blocks_s.append(d)
    return np.stack(cols_s), np.stack(blocks_s), kmax


def _exchange_arrays(sched: Schedule, blocks: List[LocalBlocks],
                     cpart: RowPartition, topo: Topology,
                     cols_pad: int) -> Dict[str, np.ndarray]:
    """Each phase's send array and each buffer's gather array, filled
    rank by rank with bulk ``searchsorted`` lookups (no element loops)."""
    n = topo.n_procs
    local_index = cpart.local_index()
    arrays = {p.send: np.zeros((n, p.slots, p.pad), np.int32)
              for p in sched.phases}
    arrays.update((b.gather, np.zeros((n, b.pad), np.int32))
                  for b in sched.buffers)
    lengths = {p.name: p.slots * p.pad for p in sched.phases}
    for r, blk in enumerate(blocks):
        maps = {p.name: p.recv_slot_map(topo, r) for p in sched.phases}
        tables = {}

        def table(names):
            """Sorted (index, position) lookup table into the flattened
            concatenation of the ``names`` phases' receive buffers."""
            if names not in tables:
                at, idx, pos = 0, [], []
                for nm in names:
                    idx.append(maps[nm][0])
                    pos.append(maps[nm][1] + at)
                    at += lengths[nm]
                if len(names) > 1:
                    idx, pos = np.concatenate(idx), np.concatenate(pos)
                    order = np.argsort(idx, kind="stable")
                    idx, pos = [idx[order]], [pos[order]]
                tables[names] = (idx[0], pos[0])
            return tables[names]

        for p in sched.phases:
            rest = tuple(s for s in p.source if s != "v_loc")
            for m in p.sends[r]:
                if not rest:
                    pos = local_index[m.idx]
                elif len(rest) == len(p.source):
                    pos = lookup_slots(table(rest), m.idx)
                else:
                    # v_loc first: own entries by local index, the rest
                    # after it in the received phases
                    own = cpart.owner[m.idx] == r
                    pos = np.empty(m.size, dtype=np.int64)
                    pos[own] = local_index[m.idx[own]]
                    if not own.all():
                        pos[~own] = cols_pad + lookup_slots(
                            table(rest), m.idx[~own])
                arrays[p.send][r, p.slot_of(topo, m.dst), : m.size] = pos
        for b in sched.buffers:
            cols = np.concatenate([getattr(blk, f"{k}_cols")
                                   for k in b.blocks])
            arrays[b.gather][r, : cols.size] = lookup_slots(table(b.source),
                                                            cols)
    return arrays


def compile_plan(a: CSR, part: RowPartition, topo: Topology,
                 comm: str = "nap", plan=None,
                 block_shape: Tuple[int, int] = (8, 128),
                 cache: bool = True, local_compute: str = "auto",
                 tuner: LocalComputeParams = TPU_V5E_LOCAL,
                 col_part: Optional[RowPartition] = None,
                 threshold="auto") -> CompiledPlan:
    """Compile one exchange strategy to static shard_map arrays.

    ``comm`` names the strategy (:data:`STRATEGIES`): ``"nap"``,
    ``"multistep"`` (split at the duplication ``threshold``) or
    ``"standard"``; ``plan`` optionally supplies its prebuilt comm-graph
    plan (and bypasses the cache).  ``part`` is the ROW partition (output
    layout); ``col_part`` the COLUMN/x partition — defaults to ``part``,
    the square case.  A rectangular ``a`` REQUIRES ``col_part`` (shapes
    are validated).
    """
    if local_compute not in ("auto",) + LOCAL_FORMATS:
        raise ValueError(local_compute)
    strategy = STRATEGIES[comm]
    cpart = part if col_part is None else col_part
    if part.n_rows != a.shape[0] or cpart.n_rows != a.shape[1]:
        raise ValueError(
            f"partition/matrix mismatch: a is {a.shape}, row partition has "
            f"{part.n_rows} rows, column partition {cpart.n_rows}")
    key = None
    if plan is None and cache:
        key = _cache_key(a, part, topo, block_shape, local_compute, tuner,
                         strategy.family(topo, threshold), col_part=col_part)
        hit = _cache_get(key)
        if hit is not None:
            return hit
    if plan is None:
        plan = strategy.build(a, part, topo, col_part, threshold)
    blocks = split_all_blocks(a, part, topo, col_part=cpart)
    bn = block_shape[1]
    if bn % 8 != 0:
        raise ValueError(f"bn must be a multiple of the 8-wide sublane "
                         f"tile, got {bn}")
    # Segment lengths of the packed x operand are rounded up to the lane
    # width bn, so v_loc and the buffers are bn-aligned views of one
    # packed domain and the fused BSR kernel reads them zero-copy (no
    # HBM pad/concat per call).  Padding slots beyond the true sizes are
    # never referenced by a nonzero, so the rounding is mathematically
    # inert everywhere (incl. the COO path's segment_sum).  rows_pad is
    # the row-partition output pad, cols_pad the column-partition v_loc
    # pad (identical in the square single-partition case).
    rows_pad = _ceil_to(max(1, int(part.counts().max())), bn)
    cols_pad = _ceil_to(max(1, int(cpart.counts().max())), bn)
    sched = strategy.schedule(plan, topo)
    sched = dataclasses.replace(sched, buffers=tuple(
        dataclasses.replace(b, pad=_ceil_to(max(1, max(
            sum(getattr(blk, f"{k}_cols").size for k in b.blocks)
            for blk in blocks)), bn))
        for b in sched.buffers))
    compiled = CompiledPlan(
        topo=topo, part=part, col_part=cpart, rows_pad=rows_pad,
        cols_pad=cols_pad, schedule=sched,
        arrays=_exchange_arrays(sched, blocks, cpart, topo, cols_pad),
        comm=comm, plan=plan, block_shape=tuple(block_shape), local_blocks=blocks,
        requested_local_compute=local_compute, a_ref=a, _cache_token=key)
    # the COO blocks' pads, one per segment: the padded triples COO runs
    nnz_pad = max(1, max(blk.on_proc.nnz for blk in blocks)) + sum(
        max(1, max(sum(getattr(blk, k).nnz for k in b.blocks)
                   for blk in blocks)) for b in sched.buffers)
    rc = [(rr, cc) for rr, cc, _ in compiled.packed_coo()]
    n_x = compiled.packed_x_len
    compiled.autotune = _format_stats_from_coo(
        rc, rows_pad, n_x, nnz_pad, tuple(block_shape), tuner,
        ell_n_x=compiled.recv_x_len)
    compiled.autotune["transpose"] = _transpose_format_stats(
        [(cc, rr) for rr, cc in rc], n_x, rows_pad, nnz_pad,
        tuple(block_shape), tuner)
    if key is not None:
        _cache_put(key, compiled)
    return compiled


# ---------------------------------------------------------------------------
# Vector packing
# ---------------------------------------------------------------------------

def pack_vector(v: np.ndarray, part: RowPartition, topo: Topology, rows_pad: int) -> np.ndarray:
    """Global vector/multivector -> [n_nodes, ppn, rows_pad(, nv)] shards.

    ``part`` is whichever partition owns ``v``: the COLUMN partition with
    ``rows_pad=compiled.cols_pad`` for a forward operand, the ROW
    partition with ``compiled.rows_pad`` for a transpose operand.  Empty
    ranks simply contribute all-zero shards.
    """
    v = np.asarray(v)
    out = np.zeros((topo.n_procs, rows_pad) + v.shape[1:], dtype=np.float32)
    for r in range(topo.n_procs):
        rows = part.rows_of(r)
        out[r, : rows.size] = v[rows]
    return out.reshape((topo.n_nodes, topo.ppn, rows_pad) + v.shape[1:])


def unpack_vector(w: np.ndarray, part: RowPartition, topo: Topology) -> np.ndarray:
    """[n_nodes, ppn, pad(, nv)] -> global vector/multivector.

    ``part`` is whichever partition owns the RESULT (row partition after
    a forward apply, column partition after a transpose); per-rank slots
    beyond the rank's count are padding and ignored.  Exact inverse of
    :func:`pack_vector` under the same partition, for any pad ≥ the max
    rank count — empty ranks and uneven m≠n tails round-trip bit-for-bit.
    """
    w = np.asarray(w)
    w = w.reshape((topo.n_procs, -1) + w.shape[3:] if w.ndim == 4
                  else (topo.n_procs, -1))
    out = np.zeros((part.n_rows,) + w.shape[2:], dtype=w.dtype)
    for r in range(topo.n_procs):
        rows = part.rows_of(r)
        out[rows] = w[r, : rows.size]
    return out


# ---------------------------------------------------------------------------
# In-graph integrity primitives (jnp twins of repro.core.integrity)
# ---------------------------------------------------------------------------

def _msg_checksums(buf: jnp.ndarray) -> jnp.ndarray:
    """Per-message position-weighted Fletcher fold, [n_slots] uint32.

    Bit-for-bit twin of :func:`repro.core.integrity.checksum_np`: the
    payload's raw bit pattern viewed as 32-bit words ``w_i``, with
    ``s1 = Σ w_i`` and ``s2 = Σ i·w_i`` (1-based) both wrapping mod 2^32,
    folded as ``s1 ^ rotl32(s2, 7)``.  uint32 arithmetic wraps, and
    reduction mod 2^32 is a ring homomorphism, so the jnp and numpy
    evaluations agree exactly.
    """
    n = buf.shape[0]
    flat = buf.reshape(n, -1)
    words = jax.lax.bitcast_convert_type(flat, jnp.uint32).reshape(n, -1)
    idx = jnp.arange(1, words.shape[1] + 1, dtype=jnp.uint32)
    s1 = jnp.sum(words, axis=1, dtype=jnp.uint32)
    s2 = jnp.sum(words * idx[None, :], axis=1, dtype=jnp.uint32)
    return s1 ^ (((s2 << 7) & jnp.uint32(0xFFFFFFFF)) | (s2 >> 25))


def _apply_fault(buf: jnp.ndarray, spec_row: jnp.ndarray) -> jnp.ndarray:
    """Pure in-graph message-fault transform at the pack boundary.

    ``spec_row`` is one int32 ``(kind_code, slot, element, bit)`` row of
    the fault-spec ARGUMENT (see integrity.build_fault_spec) — kind 0
    returns ``buf`` unchanged, so the armed/clean distinction is a data
    value, never a retrace.  Every variant is computed (cheap elementwise
    work) and selected by ``where``: bitflip XORs one bit of one 32-bit
    word; zero and drop blank the slot (a dropped message in a static
    SPMD program IS a zero payload); stale shifts the slot's elements by
    one (a plausibly-valid but stale buffer); duplicate delivers the
    NEXT slot's payload in place of this one.
    """
    kind, slot, elem, bit = (spec_row[0], spec_row[1], spec_row[2],
                             spec_row[3])
    n = buf.shape[0]
    flat = buf.reshape(n, -1)
    slot = jnp.mod(slot, n)
    is_slot = (jnp.arange(n, dtype=jnp.int32) == slot)[:, None]
    words = jax.lax.bitcast_convert_type(flat, jnp.uint32)
    w2 = words.reshape(n, -1)
    elem_w = jnp.mod(elem, w2.shape[1])
    hit = is_slot & (jnp.arange(w2.shape[1], dtype=jnp.int32)[None, :]
                     == elem_w)
    mask = jnp.where(
        hit, jnp.uint32(1) << jnp.clip(bit, 0, 31).astype(jnp.uint32),
        jnp.uint32(0))
    flipped = jax.lax.bitcast_convert_type(
        (w2 ^ mask).reshape(words.shape), flat.dtype).reshape(n, -1)
    zeroed = jnp.where(is_slot, jnp.zeros_like(flat), flat)
    stale = jnp.where(is_slot, jnp.roll(flat, 1, axis=1), flat)
    dup = jnp.where(is_slot, jnp.roll(flat, -1, axis=0), flat)
    out = flat
    for code, variant in ((1, flipped), (2, zeroed), (3, stale),
                          (4, zeroed), (5, dup)):
        out = jnp.where(kind == code, variant, out)
    return out.reshape(buf.shape)


def _stack_chk(pairs: List[Tuple[jnp.ndarray, jnp.ndarray]],
               max_slots: int) -> jnp.ndarray:
    """Stack per-phase (expected, actual) checksum vectors into the
    [n_phases, 2, max_slots] aux output (padded slots zero on BOTH rows,
    so padding can never read as a mismatch)."""
    rows = []
    for expect, actual in pairs:
        pad = max_slots - expect.shape[0]
        rows.append(jnp.stack([jnp.pad(expect, (0, pad)),
                               jnp.pad(actual, (0, pad))]))
    return jnp.stack(rows)


def _abft_dots(col: jnp.ndarray, col_abs: jnp.ndarray,
               segs) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ABFT checksum dot and tolerance scale, ``(c · x, |c| · |x|)``,
    over an x domain given as consecutive segments, each read where it
    lies (the concatenation is never formed)."""
    bounds = np.cumsum([0] + [seg.shape[0] for seg in segs])
    spans = list(zip(bounds[:-1], bounds[1:], segs))

    def dot(w, f):
        terms = (w[a: b] @ f(seg) for a, b, seg in spans)
        return sum(terms, next(terms))

    return dot(col, lambda x: x), dot(col_abs, jnp.abs)


def _make_run(call4, fmt: str, arg_fetch, stage, fault_fetch=None):
    """Wrap a 4-D shard program into the public run callable.

    ``run(v_shards, donate=False)`` accepts [n_nodes, ppn, rows_pad] or
    [..., nv] shards; ``donate=True`` dispatches to a separately-jitted
    entry with ``donate_argnums=(0,)`` (built lazily) so XLA may reuse the
    input shard buffer — the ``NapOperator.__call__(donate=...)`` path.

    ``arg_fetch()`` returns the plan's CURRENT device arrays, passed as
    jit arguments on every call (see :func:`_plan_arg_fetch`), so a
    hot value swap flows into the same executable.  ``run.n_traces()``
    counts program traces: it must not grow across a value swap with
    unchanged shapes.  ``stage`` (:func:`repro.mesh.buffers.input_stager`)
    places the packed operand on the mesh, one shard per device, under
    the host span ``repro.stage``; the jitted call and the reshapes
    around it run under ``repro.dispatch``.

    ``fault_fetch()`` (integrity-instrumented programs only) returns the
    armed fault-spec array — same shape/dtype every call, so arming or
    clearing scripted faults never retraces either.  With it set, ``run``
    returns the instrumented triple ``(w_shards, chk, abft)``.

    ``run.jitted`` and ``run.args()`` expose the jitted 4-D entry and its
    plan arguments, so the program can be lowered against described
    devices (compile rehearsals) without running it.
    """
    counter = {"n": 0}

    def traced(*args):   # Python body runs only when jax (re)traces
        counter["n"] += 1
        return call4(*args)

    jits = {False: jax.jit(traced)}

    def args():
        if fault_fetch is None:
            return arg_fetch()
        return (stage(np.asarray(fault_fetch()), np.int32),) + arg_fetch()

    def run(v_shards, donate: bool = False):
        with span("repro.stage"):
            v_shards = stage(v_shards)
        with span("repro.dispatch"):
            donate = bool(donate)
            if donate and donate not in jits:
                jits[True] = jax.jit(traced, donate_argnums=(0,))
            fn = jits[donate]
            if v_shards.ndim == 4:
                return fn(v_shards, *args())
            out = fn(v_shards[..., None], *args())
            if fault_fetch is None:
                return out[..., 0]
            w, chk, abft = out
            return w[..., 0], chk, abft

    run.local_compute = fmt
    run.integrity = fault_fetch is not None
    run.jitted = jits[False]
    run.args = args
    # jitted 4-D entry, exposed for jaxpr/HLO checks — keeps the
    # single-argument contract by binding the current plan arrays.
    run.run4 = lambda v_shards: jits[False](v_shards, *args())
    run.n_traces = lambda: counter["n"]
    return run


def _plan_arg_fetch(compiled, names: List[str]):
    """``arg_fetch()`` returning the plan arrays ``names`` for a shard
    program applied as ``smapped(v_shards, [fault_spec,] *arrays)``.

    Every plan array — gather/scatter maps, column indices and values —
    is a jit ARGUMENT read off the LIVE compiled plan on each call, never
    a closure constant: jax embeds closed-over arrays in the HLO as
    literals, which at 10^6 rows turns one program into hundreds of MB
    of constants, and a global multi-process ``jax.Array`` cannot be
    closed over at all.  ``swap_values`` therefore takes effect on the
    next call without retracing (replacement arrays have identical
    shapes/dtypes).
    """
    compiled.device_arrays()     # stage every named array once

    def arg_fetch():
        d = compiled.device_arrays()
        return tuple(d[k] for k in names)

    return arg_fetch

# ---------------------------------------------------------------------------
# Program builders: one per direction, both walking the schedule
# ---------------------------------------------------------------------------

class _Wire:
    """One device's side of a program's exchanges.

    ``__call__`` is a phase's ``all_to_all``.  With a fault spec (the
    integrity-instrumented program) the sender checksums the CLEAN
    payload, the scripted fault (if armed for this device and phase)
    corrupts it at the pack boundary, payload and checksum words travel
    through the same collective, and the receiver recomputes; without
    one it is the bare ``all_to_all``.
    """

    def __init__(self, schedule: Schedule, fault_spec):
        self.schedule, self.fault_spec = schedule, fault_spec
        names = [p.name for p in schedule.phases] + [COMPUTE_PHASE]
        self.row = {n: i for i, n in enumerate(names)}
        self.chks = {}

    def __call__(self, buf, phase: Phase):
        if self.fault_spec is None:
            return jax.lax.all_to_all(buf, phase.axis, 0, 0, tiled=True)
        sent = _msg_checksums(buf)
        buf = _apply_fault(buf, self.fault_spec[self.row[phase.name]])
        recv = jax.lax.all_to_all(buf, phase.axis, 0, 0, tiled=True)
        expect = jax.lax.all_to_all(sent[:, None], phase.axis, 0, 0,
                                    tiled=True)[:, 0]
        self.chks[phase.name] = (expect, _msg_checksums(recv))
        return recv

    def compute_fault(self, x):
        """Scripted compute-side corruption (what ABFT exists to catch)."""
        return _apply_fault(x[None], self.fault_spec[self.row[COMPUTE_PHASE]])[0]

    def checksums(self):
        """The [n_phases, 2, max_slots] (expected, actual) aux output."""
        phases = self.schedule.phases
        return _stack_chk([self.chks[p.name] for p in phases],
                          max(p.slots for p in phases))


def _local_product(fmt: str, arrs, xs, out_lens: List[int], bn: int = 128,
                   nv_block: int = 128, materialize_x: bool = False):
    """One rank's local product ``y = A_r x`` under ``repro.local``.

    ``xs`` is x's domain as consecutive segments and ``out_lens`` the
    lengths of y's consecutive segments; y's segments are returned.  The
    forward program passes the packed (or, for composed ELL ids, the
    received) domain's segments and one output of ``rows_pad`` rows; the
    transpose passes ``(u_loc,)`` and the packed contribution domain's
    segment lengths.  ``arrs`` are the format's plan arrays
    (:meth:`CompiledPlan.local_arrays`).
    """
    nv = xs[0].shape[-1]
    n_out = sum(out_lens)
    with jax.named_scope("repro.local"):
        if fmt == "ell":
            y = _ell_product(arrs, tuple(xs), n_out)
        elif fmt == "bsr":
            # segment lengths are bn-aligned at compile time: the buffers
            # ARE the packed x domain — no pad/concat round-trip
            cols, blocks = arrs
            if materialize_x:
                tiles = fused_bsr_spmm(cols, blocks,
                                       jnp.concatenate(xs).reshape(-1, bn, nv),
                                       nv_block=nv_block)
            else:
                tiles = fused_bsr_spmm_packed(
                    cols, blocks, tuple(x.reshape(-1, bn, nv) for x in xs),
                    nv_block=nv_block)
            y = tiles.reshape(-1, nv)[:n_out]
        else:
            # one (rows, cols, vals) block per packed-domain segment: the
            # forward block i reads x segment i, the transposed one
            # writes y segment i
            ys = []
            for i in range(len(arrs) // 3):
                rows, cols, vals = arrs[3 * i: 3 * i + 3]
                if len(out_lens) > 1:
                    ys.append(segment_sum(vals[:, None] * xs[0][rows], cols,
                                          num_segments=out_lens[i]))
                    continue
                term = segment_sum(vals[:, None] * xs[i][cols], rows,
                                   num_segments=n_out)
                ys = [ys[0] + term] if ys else [term]
            return ys
        return _split(y, out_lens)


def _split(x, lens: List[int]):
    """``x``'s consecutive row segments of ``lens`` (``x`` itself for one)."""
    if len(lens) == 1:
        return [x]
    out, at = [], 0
    for n in lens:
        out.append(x[at: at + n])
        at += n
    return out


def _program(compiled: CompiledPlan, mesh: Mesh, per_device, names,
             fmt: str, integrity: bool, fault_fetch):
    """shard_map ``per_device(x_loc, [fault_spec,] *plan arrays)`` over
    the mesh and wrap it into the public run callable (:func:`_make_run`)."""
    spec = P("node", "proc")
    n_in = 1 + len(names) + (1 if integrity else 0)
    smapped = jax.shard_map(per_device, mesh=mesh, in_specs=(spec,) * n_in,
                            out_specs=(spec, spec, spec) if integrity
                            else spec,
                            check_vma=False)
    from repro.mesh.buffers import input_stager
    return _make_run(smapped, fmt, _plan_arg_fetch(compiled, names),
                     input_stager(compiled.topo),
                     fault_fetch=fault_fetch if integrity else None)


def _device_args(schedule: Schedule, names: List[str], x_loc, args,
                 integrity: bool):
    """Strip the mesh dims off one device's operand and plan arrays (the
    fault spec first, where instrumented): ``(wire, x_loc, arrays)``."""
    squeeze = lambda x: x.reshape(x.shape[2:])
    fault_spec = squeeze(args[0]) if integrity else None   # [n_phases, 4]
    x_loc = squeeze(x_loc)                                 # [pad, nv]
    arrs = [squeeze(x) for x in args[1 if integrity else 0:]]
    return _Wire(schedule, fault_spec), x_loc, dict(zip(names, arrs))


def _outputs(y, wire: _Wire, abft):
    """The program's per-device outputs, mesh dims restored: ``y`` alone,
    or instrumented ``(y, chk, abft)``."""
    lift = lambda a: a.reshape((1, 1) + a.shape)
    if wire.fault_spec is None:
        return lift(y)
    with jax.named_scope("repro.abft"):
        chk = wire.checksums()
    return lift(y), lift(chk), lift(abft)


def forward_program(compiled: CompiledPlan, mesh: Mesh,
                    local_compute: str = "auto", nv_block: int = 128,
                    materialize_x: bool = False,
                    integrity: bool = False, fault_fetch=None):
    """Build the jitted shard_map SpMV of the plan's schedule:
    f(v_shards) -> w_shards.

    ``v_shards`` is [n_nodes, ppn, cols_pad] or [n_nodes, ppn, cols_pad, nv]
    (multi-RHS SpMM) — COLUMN-partition packed; the output is ROW-partition
    packed [n_nodes, ppn, rows_pad(, nv)] (identical shapes in the square
    single-partition case).  The program runs the schedule's phases in
    order, each under the named scope ``repro.exchange.<phase>``: the
    packing gather from its source, then the ``all_to_all``.  Then either
    the composed ELL product reads the received domain, or the buffers
    are gathered under ``repro.buffers`` and the local product
    (``repro.local``) reads the packed domain ``[v_loc | buffers...]``.

    ``local_compute`` selects the local kernel: ``"auto"`` (default)
    defers to the compile-time format autotuner, ``"bsr"`` forces the
    fused Pallas kernel, ``"ell"`` the XLA ELL product and ``"coo"`` the
    scalar segment_sum reference.  The resolved format is exposed as
    ``run.local_compute``.  ``materialize_x=True`` re-enables the legacy
    HBM pad/concat of the BSR kernel's packed x operand (bit-for-bit equal
    to the default zero-copy read; kept as an A/B oracle).

    ``integrity=True`` builds the INSTRUMENTED program instead: every
    message payload is checksummed around the scripted fault boundary
    (:class:`_Wire`; the armed fault-spec argument comes from
    ``fault_fetch``), and the ABFT triple
    ``(sum(y_p), c_p · x, |c_p| · |x|)`` is emitted per device over the
    very buffers the product read — ``run`` then returns
    ``(w_shards, chk, abft)``.  With ``integrity=False`` the emitted
    program is bit-for-bit the uninstrumented one (no extra arguments,
    outputs, or ops).
    """
    fmt = compiled.resolve_local_compute(local_compute)
    sched, rows_pad = compiled.schedule, compiled.rows_pad
    # The ELL ids are composed with the buffer gathers at plan compile
    # (CompiledPlan.recv_domain_map), so the ELL program reads the
    # received buffers as they arrive; BSR (whose zero-copy x needs
    # bn-aligned buffers) and COO gather the buffers here.
    composed = fmt == "ell"
    local = compiled.local_arrays(fmt)
    names = sched.send_names() + (
        [] if composed else [b.gather for b in sched.buffers]) + local
    if integrity:
        if composed:
            compiled.ensure_abft_recv()
            names += list(_ABFT_RECV_NAMES)
        else:
            compiled.ensure_abft()
            names += ["abft_col", "abft_col_abs"]

    def per_device(v_loc, *args):
        wire, v_loc, arg = _device_args(sched, names, v_loc, args, integrity)
        nv = v_loc.shape[-1]
        recv, flat = {}, {"v_loc": v_loc}

        def flat_of(name):
            if name not in flat:
                flat[name] = recv[name].reshape(-1, nv)
            return flat[name]

        for p in sched.phases:
            if len(p.source) == 1:
                src = flat_of(p.source[0])
            else:
                with jax.named_scope("repro.buffers"):
                    src = jnp.concatenate([flat_of(s) for s in p.source])
            with jax.named_scope(f"repro.exchange.{p.name}"):
                recv[p.name] = wire(src[arg[p.send]], p)
        # flatten each buffer's source phases once; last buffer first
        # keeps the op order the benchmark's nap programs were measured in
        for b in reversed(sched.buffers):
            for s in b.source:
                flat_of(s)
        if composed:
            x_segs = [v_loc] + [flat[s] for b in sched.buffers
                                for s in b.source]
        else:
            with jax.named_scope("repro.buffers"):
                x_segs = [v_loc] + [
                    jnp.concatenate([flat[s] for s in b.source])[arg[b.gather]]
                    for b in sched.buffers]
        w, = _local_product(fmt, [arg[n] for n in local], x_segs, [rows_pad],
                            compiled.block_shape[1], nv_block, materialize_x)
        abft = None
        if integrity:
            with jax.named_scope("repro.abft"):
                # compute fault on the LOCAL result, after the wire but
                # before the check; sum(y_p) vs c_p · x over the SAME
                # buffers the product consumed, plus the |c_p|·|x| scale
                w = wire.compute_fault(w)
                d, s = _abft_dots(arg[names[-2]], arg[names[-1]], x_segs)
                abft = jnp.stack([jnp.sum(w, axis=0), d, s])
        return _outputs(w, wire, abft)

    return _program(compiled, mesh, per_device, names, fmt, integrity,
                    fault_fetch)


def transpose_program(compiled: CompiledPlan, mesh: Mesh,
                      local_compute: str = "auto", nv_block: int = 128,
                      integrity: bool = False, fault_fetch=None):
    """Build the jitted shard_map transpose SpMV: f(u_shards) -> z_shards
    with ``z = A.T u`` — the exact adjoint of :func:`forward_program`.

    ``u_shards`` is ROW-partition packed ([.., rows_pad(, nv)]); the
    output is COLUMN-partition packed ([.., cols_pad(, nv)]) — for the
    square single-partition case the two coincide and this is invisible.

    The forward program is reversed operation by operation: the local
    product runs transposed first, into the packed contribution domain
    ``[z | one segment per buffer]``; then the phases run last to first.
    Before a phase, every buffer that reads it scatters its contributions
    back into its source phases' receive layout (``repro.buffers``); the
    phase re-applies its ``all_to_all`` (a tiled all_to_all is an
    involution and its own adjoint) and scatter-adds over its send array
    into its source, whose ``v_loc`` part accumulates into ``z``.

    Transposed local compute runs through the adaptive engine like the
    forward direction: ``"auto"`` resolves against the transpose verdict
    recorded on ``compiled.autotune["transpose"]`` (argmin of ell/coo —
    there is no transposed Pallas BSR kernel, so a ``"bsr"`` request also
    defers to that verdict).  ``"ell"`` runs A_r^T as ONE ELL SpMM over
    the packed contribution domain; ``"coo"`` is the scalar segment_sum
    scatter reference.  ``integrity=True`` instruments it like the
    forward program, with the compute fault and the transpose ABFT
    (``sum(c_p) == (A_p 1) · u_loc``) applied before any communication.
    """
    fmt = compiled.resolve_transpose_local_compute(local_compute)
    sched, lengths = compiled.schedule, compiled.lengths()
    local = compiled.local_arrays(fmt, transpose=True)
    names = (sched.send_names() + [b.gather for b in sched.buffers] + local)
    if integrity:
        compiled.ensure_abft()
        names += ["abft_row", "abft_row_abs"]
    seg_lens = [compiled.cols_pad] + [b.pad for b in sched.buffers]

    def per_device(u_loc, *args):
        wire, u_loc, arg = _device_args(sched, names, u_loc, args, integrity)
        nv = u_loc.shape[-1]
        ys = _local_product(fmt, [arg[n] for n in local], [u_loc], seg_lens,
                            nv_block=nv_block)
        abft = None
        if integrity:
            with jax.named_scope("repro.abft"):
                packed = wire.compute_fault(jnp.concatenate(ys))
                abft = jnp.stack([jnp.sum(packed, axis=0),
                                  arg["abft_row"] @ u_loc,
                                  arg["abft_row_abs"] @ jnp.abs(u_loc)])
                ys = _split(packed, seg_lens)
        acc = {"v_loc": ys[0]}
        pending = {b.name: y for b, y in zip(sched.buffers, ys[1:])}

        def add(source, c):
            # c over the flattened source: each part onto its segment
            at = 0
            for s in source:
                part = c if len(source) == 1 else c[at: at + lengths[s]]
                acc[s] = acc[s] + part if s in acc else part
                at += lengths[s]

        for p in reversed(sched.phases):
            for b in sched.buffers:
                if p.name in b.source and b.name in pending:
                    with jax.named_scope("repro.buffers"):
                        c = segment_sum(pending.pop(b.name), arg[b.gather],
                                        num_segments=sum(lengths[s]
                                                         for s in b.source))
                    add(b.source, c)
            c = acc.pop(p.name).reshape(p.slots, p.pad, nv)
            with jax.named_scope(f"repro.exchange.{p.name}"):
                recv = wire(c, p)
                c = segment_sum(recv.reshape(-1, nv), arg[p.send].reshape(-1),
                                num_segments=sum(lengths[s]
                                                 for s in p.source))
            add(p.source, c)
        return _outputs(acc["v_loc"], wire, abft)

    return _program(compiled, mesh, per_device, names, fmt, integrity,
                    fault_fetch)


# ---------------------------------------------------------------------------
# Traffic accounting
# ---------------------------------------------------------------------------

def padded_traffic(compiled: CompiledPlan, integrity: str = "off",
                   local_compute: str = "auto") -> Dict[str, object]:
    """Padded (SPMD-actual) vs effective bytes per phase, float32 payloads.

    Padded bytes are what the static all-to-alls actually move (every rank
    sends its full padded buffer every time); effective bytes are the plan's
    true message payloads — the gap is the padding the paper's T/U balancing
    minimises.  Effective ≤ padded always.  One entry set per phase of the
    plan's schedule (nap: full/init/inter/final; multistep: + direct;
    standard: pair).  Two per-direction extras ride along:

    * ``{phase}_max_rank_effective`` — the bottleneck rank's true payload
      for the FORWARD program (sender side), with the transpose twins
      (computed from the recv lists, since every message reverses) under
      ``out["transpose"]``.  Phase totals are direction-independent.
    * with ``integrity != "off"``, ``{phase}_checksum`` counts the
      side-channel all_to_all the instrumented program runs per phase
      (one u32 per slot per rank), and ``checksum_total`` sums them —
      the wires the integrity mode adds are not free.

    ``buffer_gather_elems`` counts the buffer positions (each ``nv``
    wide) that the forward program's buffer step gathers per apply on
    the bottleneck rank — the sum of the schedule's buffer pads, padding
    included, as the SPMD gather runs them.  It is 0 when
    ``local_compute`` resolves to ``ell``, whose column ids are composed
    with those gathers at plan compile.

    ``ell_split_width``, ``ell_chunk_rows``, ``ell_pad_rows``,
    ``ell_slots`` and ``ell_slots_per_nnz`` (forward at the top level,
    transpose under ``"transpose"``) report the ELL layout the autotuner
    picked.
    """
    n = compiled.topo.n_procs
    out: Dict[str, object] = {}
    transpose: Dict[str, int] = {}
    checksum_total = 0
    for p in compiled.schedule.phases:
        name = p.name
        out[f"{name}_padded"] = n * p.slots * p.pad * 4
        out[f"{name}_effective"] = 4 * sum(
            m.size for msgs in p.sends for m in msgs)
        out[f"{name}_max_rank_effective"] = 4 * max(
            (sum(m.size for m in msgs) for msgs in p.sends), default=0)
        transpose[f"{name}_padded"] = out[f"{name}_padded"]
        transpose[f"{name}_effective"] = 4 * sum(
            m.size for msgs in p.recvs for m in msgs)
        transpose[f"{name}_max_rank_effective"] = 4 * max(
            (sum(m.size for m in msgs) for msgs in p.recvs), default=0)
        if integrity != "off":
            chk = n * p.slots * 4
            out[f"{name}_checksum"] = chk
            transpose[f"{name}_checksum"] = chk
            checksum_total += chk
    if integrity != "off":
        out["checksum_total"] = checksum_total
        transpose["checksum_total"] = checksum_total
    out["buffer_gather_elems"] = (
        0 if compiled.resolve_local_compute(local_compute) == "ell"
        else sum(b.pad for b in compiled.schedule.buffers))
    # the ELL layout each direction's autotuner picked (split width,
    # chunk rows, padding rows, slots and slots per entry)
    out.update(compiled.autotune.get("ell", {}))
    transpose.update(compiled.autotune.get("transpose", {}).get("ell", {}))
    out["transpose"] = transpose
    return out
