"""SPMD (shard_map) executors for the distributed SpMV on a device mesh.

Entry points: the canonical user-facing surface is
:func:`repro.api.operator` (one ``NapOperator`` over every backend); this
module holds the compiled-plan containers (:class:`CompiledNAP`,
:class:`CompiledStandard`) and the shard_map program builders the
``"shardmap"`` backend registers —

* :func:`nap_forward_shardmap` / :func:`nap_transpose_shardmap`
* :func:`standard_forward_shardmap` / :func:`standard_transpose_shardmap`

(The one-release deprecation shims ``nap_spmv_shardmap`` /
``standard_spmv_shardmap`` are GONE — the migration table survives in
``src/repro/kernels/README.md``.)

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
(exactly where the paper's MPI implementation builds its send lists):

* ``standard``  — Algorithm 1: one padded all-to-all over the **flat** rank
  axis (every rank pair may exchange), i.e. topology-oblivious.
* ``allgather`` — the dense-JAX baseline: replicate v everywhere.
* ``nap``       — Algorithms 2+3 with ``pairing="aligned"``: intra-node
  all-to-all (proc axis) → **one aggregated inter-node all-to-all (node
  axis)** → intra-node all-to-all.  Only the middle step crosses pods.

Mesh convention: ``("node", "proc")`` with shape ``(n_nodes, ppn)`` — on a
real fleet "node" is the pod/DCI axis and "proc" the intra-pod ICI axis.

Local compute (``local_compute=``) — the **adaptive engine**:

* ``"auto"`` (default) — a density-driven format autotuner: plan
  compilation records per-rank layout stats (block fill density, padded
  FLOPs, bytes moved — see :func:`repro.core.cost_model.local_format_times`)
  and picks the cheapest of bsr/ell/coo under a two-term roofline.  The
  decision is recorded on :class:`CompiledNAP` (``.autotune``).
* ``"bsr"`` — the **fused Pallas BSR path**: the three ``local_spmv``
  blocks of Algorithm 3 are compiled into one MXU-aligned block-sparse
  matmul over the packed ``[v_loc | b_on_node | b_off_node]`` x domain
  (:mod:`repro.kernels.bsr_spmv.fused`), with multi-RHS (nv-wide SpMM)
  support.  Slots are ordered on-process → on-node → off-node, so the
  Pallas pipeline streams the blocks that depend on inter-node data
  last — the paper's Isend/compute overlap, expressed as pipeline stages.
* ``"ell"`` — the **ELL path** (:mod:`repro.kernels.ell_spmv`, plain XLA)
  for low-density / block-hostile ranks where padded BSR tiles densify:
  kmax-padded rows, one vectorised row gather per slot.
* ``"coo"`` — scalar ``segment_sum`` gathers (the pre-fusion reference
  path, kept as an in-graph oracle and for nv on hardware without Pallas).

**Zero-copy x**: every per-rank buffer length is rounded up to the block
lane width bn at compile time, so the fused BSR kernel reads ``v_loc``,
``b_on_node`` and ``b_off_node`` as separate refs via slot-indexed
index_maps — the packed x operand is never materialised as an HBM
pad/concat (``materialize_x=True`` re-enables the old concat path as a
bit-for-bit A/B oracle).  The ELL path never forms ``b_on_node`` or
``b_off_node``: its column ids are composed with those buffer gathers
at plan compile and index the received buffers
``[v_loc | full | inter | final (| direct)]`` (``[v_loc | recv]`` for
the standard plan), which it concatenates once per call before its row
gathers.

Plan compilation is fully vectorised (bulk ``np.searchsorted`` against the
slot maps :meth:`NAPPlan.recv_slot_map` exposes — no per-element Python
loops) and cached keyed on (matrix structure+values, partition, topology,
block shape, requested local_compute, autotuner params), so repeated
SpMVs (AMG V-cycles, training steps) pay the plan-build cost once.

Padding note: all per-rank buffers are padded to the max over ranks; the
paper's T/U load balancing minimises exactly this padding.  Effective vs
padded bytes are both reported by :func:`padded_traffic`.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.ops import segment_sum
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.comm_graph import (Message, NAPPlan, StandardPlan,
                                   build_nap_plan, build_standard_plan,
                                   lookup_slots)
from repro.core.integrity import (MULTISTEP_MESSAGE_PHASES,
                                  NAP_MESSAGE_PHASES, STD_MESSAGE_PHASES,
                                  phase_index)
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


def _resolve_local_compute(requested: str, compile_requested: str,
                           chosen: str) -> str:
    """Executor request -> concrete format (shared by both compiled plans).

    Precedence: an explicit executor request wins; an executor ``"auto"``
    defers to a concrete format requested at compile time, and only then
    to the autotuner's verdict.
    """
    if requested == "auto":
        if compile_requested != "auto":
            return compile_requested
        return chosen
    if requested not in LOCAL_FORMATS:
        raise ValueError(requested)
    return requested


def _resolve_transpose_local_compute(requested: str, compile_requested: str,
                                     autotune: Dict[str, object]) -> str:
    """Transpose-direction analogue of :func:`_resolve_local_compute`.

    Only ``ell`` and ``coo`` have transposed programs (transposed Pallas
    BSR is a roadmap item), so an explicit ``ell``/``coo`` request wins,
    while ``auto`` — and ``bsr``, which cannot be honoured — defer to the
    transpose autotuner verdict recorded under ``autotune["transpose"]``.
    """
    if requested not in ("auto",) + LOCAL_FORMATS:
        raise ValueError(requested)
    for cand in (requested, compile_requested):
        if cand in ("ell", "coo"):
            return cand
    t = autotune.get("transpose", {})
    return str(t.get("chosen", "coo")) if isinstance(t, dict) else "coo"


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


@dataclasses.dataclass
class CompiledNAP:
    """Static arrays for the shard_map NAPSpMV, stacked over ranks.

    Rectangular contract: ``part`` is the ROW partition (output layout,
    ``rows_pad`` rows per shard) and ``col_part`` the COLUMN partition
    (input x layout, ``cols_pad`` entries per shard).  They coincide for
    square single-partition operators; an AMG P / R separates them.  The
    packed x domain is ``[v_loc(cols_pad) | b_on_node | b_off_node]``.
    """

    topo: Topology
    part: RowPartition
    rows_pad: int
    pads: Dict[str, int]          # full/init/inter/final/bnode/boff/nnz pads
    arrays: Dict[str, np.ndarray]  # stacked [n_procs, ...] index/value arrays
    col_part: Optional[RowPartition] = None  # None = square (col == row)
    cols_pad: int = 0                        # 0 = square (== rows_pad)
    plan: Optional[NAPPlan] = None          # kept for traffic accounting
    block_shape: Tuple[int, int] = (8, 128)  # fused BSR (bm, bn)
    # element column offsets of the packed fused x operand, all multiples
    # of bn: [0, vblk) = v_loc, [vblk, vblk+nblk) = on-node buffer,
    # [vblk+nblk, vblk+nblk+oblk) = off-node buffer.
    bsr_layout: Dict[str, int] = dataclasses.field(default_factory=dict)
    # rank-local blocks retained for lazy fused-BSR / ELL emission
    local_blocks: Optional[List[LocalBlocks]] = None
    # format autotuner verdict + inputs (chosen format, per-rank stats,
    # modeled per-format times) — filled by compile_nap for BOTH
    # directions (the transpose verdict lives under autotune["transpose"])
    autotune: Dict[str, object] = dataclasses.field(default_factory=dict)
    requested_local_compute: str = "auto"
    ell_kmax: int = 0
    ell_t_kmax: int = 0
    # exchange strategy this plan lowers: "nap" (single aggregated
    # inter-node all_to_all) or "multistep" (adds the fifth "direct"
    # exchange for low-duplication columns; pads["direct"] + the
    # direct_send array exist, and ms_plan holds the full
    # repro.comm.multistep.MultistepPlan — ``plan`` stays the NAP
    # sub-plan so every nap-shaped consumer keeps working).
    comm: str = "nap"
    ms_plan: Optional[object] = None
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

    @property
    def chosen_local_compute(self) -> str:
        return str(self.autotune.get("chosen", "coo"))

    def resolve_local_compute(self, requested: str) -> str:
        """Map an executor's ``local_compute`` request to a concrete format."""
        return _resolve_local_compute(requested, self.requested_local_compute,
                                      self.chosen_local_compute)

    def resolve_transpose_local_compute(self, requested: str) -> str:
        """Transpose-direction format: honours an explicit ``ell``/``coo``
        request; ``auto`` (and ``bsr``, which has no transposed Pallas
        kernel) defer to the transpose autotuner verdict recorded at
        compile time under ``autotune["transpose"]``."""
        return _resolve_transpose_local_compute(
            requested, self.requested_local_compute, self.autotune)

    @property
    def packed_x_len(self) -> int:
        """Element length of the packed [v_loc | b_on_node | b_off_node] x."""
        return self.cols_pad + self.pads["bnode"] + self.pads["boff"]

    @property
    def recv_x_len(self) -> int:
        """Element length of the received x domain
        ``[v_loc | full_recv | inter_recv | final_recv (| direct_recv)]``
        (each recv buffer flattened) that the forward ELL ids index."""
        topo, p = self.topo, self.pads
        n = (self.cols_pad + topo.ppn * p["full"]
             + topo.n_nodes * p["inter"] + topo.ppn * p["final"])
        if self.comm == "multistep":
            n += topo.n_procs * p["direct"]
        return n

    def recv_domain_map(self) -> np.ndarray:
        """[n_procs, packed_x_len] int32: where each packed-domain column
        sits in the received domain (:attr:`recv_x_len`) — v_loc maps to
        itself, b_on_node through ``bnode_gather`` into ``full_recv`` and
        b_off_node through ``boff_gather`` into the ``inter | final
        (| direct)`` block that follows it.  Composing a column id with
        this map replaces the program's two buffer gathers."""
        off = self.cols_pad + self.topo.ppn * self.pads["full"]
        return _recv_domain_map(self.cols_pad, [
            (self.cols_pad, self.arrays["bnode_gather"]),
            (off, self.arrays["boff_gather"])])

    def ensure_ell(self) -> None:
        """Materialise the forward ELL arrays (lazily, once) — the
        block-hostile branch of the adaptive engine.  The column ids are
        composed with :meth:`recv_domain_map` at emission, so the ELL
        program reads the received buffers directly and never gathers
        ``bnode``/``boff``; slot order and values are those of the
        packed-domain emission.  Rows are split at the width the
        autotuner chose (``autotune["ell"]``), with ``ell_chunk_row``
        beside the arrays where it is below the longest row."""
        if "ell_cols" in self.arrays:
            return
        assert self.local_blocks is not None, "compiled plan lost its blocks"
        cols, vals, kmax, chunk_row = _fused_ell_arrays(
            self.local_blocks, self.rows_pad, self.cols_pad,
            self.pads["bnode"], self.pads["boff"],
            split=_split_width(self.autotune))
        self.arrays["ell_cols"] = _compose_cols(cols, self.recv_domain_map())
        self.arrays["ell_vals"] = vals
        _set_chunk_row(self.arrays, "ell_chunk_row", chunk_row)
        self.ell_kmax = kmax

    def ensure_ell_t(self) -> None:
        """Materialise the TRANSPOSED packed ELL arrays (lazily, once):
        A_r^T over the packed contribution domain
        ``[z(cols_pad) | c_on_node | c_off_node]`` with x = u_loc — the
        vectorised alternative to the transpose COO scatter path, split
        at the transpose autotuner's width like :meth:`ensure_ell`."""
        if "ell_t_cols" in self.arrays:
            return
        assert self.local_blocks is not None, "compiled plan lost its blocks"
        cols_pad, bnode_pad = self.cols_pad, self.pads["bnode"]
        out_len = self.packed_x_len
        split = _split_width(self.autotune.get("transpose", {}))
        per_rank: List[ELL] = []
        for blk in self.local_blocks:
            op_r, op_c, op_v = blk.on_proc.to_coo()
            on_r, on_c, on_v = blk.on_node.to_coo()
            off_r, off_c, off_v = blk.off_node.to_coo()
            rows_t = np.concatenate([op_c, cols_pad + on_c,
                                     cols_pad + bnode_pad + off_c])
            cols_t = np.concatenate([op_r, on_r, off_r])
            vals = np.concatenate([op_v, on_v, off_v])
            per_rank.append(ELL.from_coo(rows_t, cols_t, vals,
                                         (out_len, self.rows_pad),
                                         n_rows_pad=out_len, split=split))
        cols, vals, kmax, chunk_row = stack_ell(per_rank)
        self.arrays["ell_t_cols"] = cols
        self.arrays["ell_t_vals"] = vals
        _set_chunk_row(self.arrays, "ell_t_chunk_row", chunk_row)
        self.ell_t_kmax = kmax

    def ensure_fused(self) -> None:
        """Materialise the fused Pallas BSR arrays (lazily, once).

        The fused layout densifies (bm, bn) tiles, which on block-hostile
        structures costs far more memory/time than the gather maps — so it
        is built only when a "bsr" executor is requested, and cached on the
        compiled plan (the compile cache then amortises it across SpMVs).
        """
        if "fused_cols" in self.arrays:
            return
        assert self.local_blocks is not None, "compiled plan lost its blocks"
        bm, bn = self.block_shape
        fc, fb, layout = _fused_bsr_arrays(
            self.local_blocks, self.rows_pad, self.cols_pad,
            self.pads["bnode"], self.pads["boff"], bm, bn)
        self.arrays["fused_cols"] = fc
        self.arrays["fused_blocks"] = fb
        self.bsr_layout.update(layout)

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
        assert self.local_blocks is not None, "compiled plan lost its blocks"
        n, n_x, rows_pad = self.topo.n_procs, self.packed_x_len, self.rows_pad
        col = np.zeros((n, n_x), np.float64)
        cola = np.zeros((n, n_x), np.float64)
        row = np.zeros((n, rows_pad), np.float64)
        rowa = np.zeros((n, rows_pad), np.float64)
        offs = (("on_proc", 0), ("on_node", self.cols_pad),
                ("off_node", self.cols_pad + self.pads["bnode"]))
        for r, blk in enumerate(self.local_blocks):
            for key_c, off in offs:
                rr, cc, vv = getattr(blk, key_c).to_coo()
                v32 = vv.astype(np.float32).astype(np.float64)
                np.add.at(col[r], cc + off, v32)
                np.add.at(cola[r], cc + off, np.abs(v32))
                np.add.at(row[r], rr, v32)
                np.add.at(rowa[r], rr, np.abs(v32))
        self.arrays["abft_col"] = col.astype(np.float32)
        self.arrays["abft_col_abs"] = cola.astype(np.float32)
        self.arrays["abft_row"] = row.astype(np.float32)
        self.arrays["abft_row_abs"] = rowa.astype(np.float32)

    def device_arrays(self) -> Dict[str, jnp.ndarray]:
        """Mesh-shaped (n_nodes, ppn, ...) device arrays, memoized per name."""
        return _memo_device_arrays(self.topo, self.arrays, self._dev_cache)

    def swap_values(self, a_new: CSR) -> List[str]:
        """Hot-swap matrix VALUES in place; sparsity must be identical.

        Rebuilds every value array (eager COO blocks plus any materialised
        lazy format) against the SAME pads and gather maps, evicts only
        those names from the device memo, and retires the plan from the
        global compile cache (which keys on the old data hash).  Executors
        bound to this plan pick the new values up on their next call with
        zero retraces — value arrays are jit arguments, and the
        replacements have identical shapes/dtypes.  Returns the changed
        array names.
        """
        _swap_check_structure(self, a_new)
        blocks = split_all_blocks(a_new, self.part, self.topo,
                                  col_part=self.col_part)
        self.local_blocks = blocks
        changed = []
        for key_c in ("on_proc", "on_node", "off_node"):
            self.arrays[f"{key_c}_vals"] = _pad_to(
                [getattr(b, key_c).to_coo()[2].astype(np.float32)
                 for b in blocks],
                self.pads[f"nnz_{key_c}"], fill=0.0)
            changed.append(f"{key_c}_vals")
        changed += _swap_refresh_lazy(self, [
            ("ell_cols", "ell_vals", self.ensure_ell),
            ("ell_t_cols", "ell_t_vals", self.ensure_ell_t),
            ("fused_cols", "fused_blocks", self.ensure_fused)])
        changed += _swap_refresh_abft(self)
        _swap_finish(self, a_new, changed)
        return changed


def _swap_check_structure(compiled, a_new: CSR) -> None:
    old = compiled.a_ref
    if old is None:
        raise ValueError("compiled plan lost its matrix reference; "
                         "recompile instead of swapping values")
    if (tuple(a_new.shape) != tuple(old.shape)
            or not np.array_equal(a_new.indptr, old.indptr)
            or not np.array_equal(a_new.indices, old.indices)):
        raise ValueError(
            "swap_values requires an identical sparsity structure (same "
            "shape, indptr, indices); a structural change needs a recompile")


def _swap_refresh_lazy(compiled, formats) -> List[str]:
    """Re-emit each MATERIALISED lazy format from the refreshed blocks.

    Structural companions (cols) regenerate to identical values, so their
    device-memo entries stay valid; only the value names report changed.
    """
    changed = []
    for cols_name, vals_name, ensure in formats:
        if cols_name in compiled.arrays:
            del compiled.arrays[cols_name], compiled.arrays[vals_name]
            ensure()
            changed.append(vals_name)
    return changed


#: ABFT checksum-vector names — value arrays derived from the matrix
#: values, so a hot swap refreshes them like the format value arrays.
_ABFT_NAMES = ("abft_col", "abft_col_abs", "abft_row", "abft_row_abs")
#: ... and their received-domain twins, which the composed ELL programs read.
_ABFT_RECV_NAMES = ("abft_col_recv", "abft_col_abs_recv")


def _swap_refresh_abft(compiled) -> List[str]:
    """Re-emit the ABFT checksum vectors if they were materialised."""
    if "abft_col" not in compiled.arrays:
        return []
    recv = _ABFT_RECV_NAMES[0] in compiled.arrays
    names = _ABFT_NAMES + (_ABFT_RECV_NAMES if recv else ())
    for k in names:
        del compiled.arrays[k]
    compiled.ensure_abft()
    if recv:
        _ensure_abft_recv(compiled)
    return list(names)


def _recv_domain_map(cols_pad: int,
                     segments: List[Tuple[int, np.ndarray]]) -> np.ndarray:
    """Per-rank map from the packed x domain ``[v_loc | seg_0 | seg_1 ...]``
    to the received domain: v_loc to itself, packed slot ``j`` of segment
    ``i`` to ``start_i + gather_i[:, j]``, where ``gather_i`` is the
    program's ``[n_procs, pad_i]`` buffer gather into the received
    buffers and ``start_i`` where those buffers begin."""
    n = segments[0][1].shape[0]
    ident = np.broadcast_to(np.arange(cols_pad, dtype=np.int32), (n, cols_pad))
    return np.concatenate(
        [ident] + [(start + g).astype(np.int32) for start, g in segments],
        axis=1)


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


def _set_chunk_row(arrays: Dict[str, np.ndarray], name: str,
                   chunk_row: Optional[np.ndarray]) -> None:
    """Keep a split layout's ``chunk_row`` map under ``name`` (nothing
    for an unsplit one)."""
    if chunk_row is not None:
        arrays[name] = chunk_row


def _ell_names(compiled, cols: str, vals: str, chunk_row: str) -> List[str]:
    """The ELL product's plan arrays, its ``chunk_row`` map last where
    the layout is split."""
    return [cols, vals] + ([chunk_row] if chunk_row in compiled.arrays
                           else [])


def _ell_product(ell_args, xs, n_rows: int):
    """The ELL local product over the program's ELL arguments
    ``(cols, vals[, chunk_row])`` (see :func:`_ell_names`), as its
    ``[n_rows, nv]`` result: the stack's padding rows are dropped."""
    cols, vals, *chunk_row = ell_args
    return ell_spmm_packed(cols, vals, xs, *chunk_row, n_rows=n_rows)


def _ensure_abft_recv(compiled) -> None:
    """Materialise (lazily, once) ``abft_col_recv``/``abft_col_abs_recv``:
    the ABFT column sums ``abft_col``/``abft_col_abs`` moved onto the
    received domain through ``recv_domain_map``, so a composed ELL
    program checks ``sum(y_p)`` against the very buffers its product
    reads.  Padding slots carry zero weight, so the scatter-add is exact
    wherever the map sends each real column to its own position."""
    if _ABFT_RECV_NAMES[0] in compiled.arrays:
        return
    compiled.ensure_abft()
    remap = compiled.recv_domain_map()
    n_x = compiled.recv_x_len
    for src, dst in zip(("abft_col", "abft_col_abs"), _ABFT_RECV_NAMES):
        packed = compiled.arrays[src].astype(np.float64)
        out = np.zeros((packed.shape[0], n_x), np.float64)
        for r in range(packed.shape[0]):
            np.add.at(out[r], remap[r], packed[r])
        compiled.arrays[dst] = out.astype(np.float32)


def _swap_finish(compiled, a_new: CSR, changed: List[str]) -> None:
    for name in changed:
        compiled._dev_cache.pop(name, None)
    compiled.a_ref = a_new
    if compiled._cache_token is not None:
        _COMPILE_CACHE.pop(compiled._cache_token, None)
        compiled._cache_token = None


# ---------------------------------------------------------------------------
# Plan compilation (vectorised + cached)
# ---------------------------------------------------------------------------

_COMPILE_CACHE: Dict[tuple, CompiledNAP] = {}
_COMPILE_CACHE_MAX = 16  # LRU bound: entries retain plans + dense fused blocks


def clear_compile_cache() -> None:
    _COMPILE_CACHE.clear()


def _cache_put(key: tuple, compiled: CompiledNAP) -> None:
    while len(_COMPILE_CACHE) >= _COMPILE_CACHE_MAX:
        _COMPILE_CACHE.pop(next(iter(_COMPILE_CACHE)))
    _COMPILE_CACHE[key] = compiled


def _cache_get(key: tuple) -> Optional[CompiledNAP]:
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
    # tag the plan family (nap vs standard) — switching any of them can
    # never return a stale compiled plan.
    return (tag, h.hexdigest(), a.shape, topo.n_nodes, topo.ppn,
            tuple(block_shape), str(local_compute), tuner.signature())


def _fused_bsr_arrays(blocks: List[LocalBlocks], rows_pad: int, cols_pad: int,
                      bnode_pad: int, boff_pad: int,
                      bm: int, bn: int) -> Tuple[np.ndarray, np.ndarray, Dict[str, int]]:
    """Fuse each rank's three column blocks into one padded-uniform BSR.

    The element column domain is the concatenated x operand
    ``[v_loc(cols_pad) | b_on_node | b_off_node]`` with every segment
    padded to a multiple of bn, so segment boundaries land on block
    boundaries and a block column never straddles two buffers.  Block
    columns sort ascending within each block row, which orders slots
    on-process → on-node → off-node — the overlap-friendly streaming
    order.  ``rows_pad`` (the row-partition output pad) and ``cols_pad``
    (the column-partition v_loc pad) coincide only in the square case.
    """
    vblk = _ceil_to(max(cols_pad, 1), bn)
    nblk = _ceil_to(max(bnode_pad, 1), bn)
    oblk = _ceil_to(max(boff_pad, 1), bn)
    n_cols = vblk + nblk + oblk
    per_rank: List[BSR] = []
    for blk in blocks:
        op_r, op_c, op_v = blk.on_proc.to_coo()
        on_r, on_c, on_v = blk.on_node.to_coo()
        off_r, off_c, off_v = blk.off_node.to_coo()
        rows = np.concatenate([op_r, on_r, off_r])
        cols = np.concatenate([op_c, vblk + on_c, vblk + nblk + off_c])
        vals = np.concatenate([op_v, on_v, off_v])
        per_rank.append(BSR.from_coo(rows, cols, vals, (rows_pad, n_cols),
                                     bm=bm, bn=bn))
    cols, data, kmax = _stack_padded_bsr(per_rank)
    layout = dict(vblk=vblk, nblk=nblk, oblk=oblk,
                  n_brows=per_rank[0].n_brows, kmax=kmax)
    return cols, data, layout


def _fused_ell_arrays(blocks: List[LocalBlocks], rows_pad: int, cols_pad: int,
                      bnode_pad: int, boff_pad: int, split: int = 0):
    """Emit each rank's three column blocks as one ELL over the packed x
    domain ``[v_loc(cols_pad) | b_on_node | b_off_node]`` (offsets
    cols_pad and cols_pad + bnode_pad), rows split at ``split`` (0: not
    split), stacked to a shared width: :func:`repro.sparse.ell.stack_ell`'s
    ``(cols, vals, width, chunk_row)``."""
    n_x = cols_pad + bnode_pad + boff_pad
    per_rank: List[ELL] = []
    for blk in blocks:
        op_r, op_c, op_v = blk.on_proc.to_coo()
        on_r, on_c, on_v = blk.on_node.to_coo()
        off_r, off_c, off_v = blk.off_node.to_coo()
        rows = np.concatenate([op_r, on_r, off_r])
        cols = np.concatenate([op_c, cols_pad + on_c,
                               cols_pad + bnode_pad + off_c])
        vals = np.concatenate([op_v, on_v, off_v])
        per_rank.append(ELL.from_coo(rows, cols, vals, (rows_pad, n_x),
                                     n_rows_pad=rows_pad, split=split))
    return stack_ell(per_rank)


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
    (:func:`_ell_layout`).  Shared by compile_nap (three-segment packed
    domain) and standard_spmv_shardmap (two-segment).
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


def _autotune_stats(blocks: List[LocalBlocks], rows_pad: int, cols_pad: int,
                    bnode_pad: int, boff_pad: int, nnz_pad_total: int,
                    block_shape: Tuple[int, int],
                    tuner: LocalComputeParams,
                    recv_x_len: int) -> Dict[str, object]:
    """NAP three-segment packed domain -> format stats + decision,
    for BOTH directions: the forward verdict at the top level and the
    transpose verdict (over the reversed domain) under ``"transpose"``.
    ``recv_x_len`` is the received domain the forward ELL ids index."""
    per_rank_rc = []
    for blk in blocks:
        parts = [blk.on_proc.to_coo(), blk.on_node.to_coo(),
                 blk.off_node.to_coo()]
        offs = [0, cols_pad, cols_pad + bnode_pad]
        rows = np.concatenate([p[0] for p in parts])
        cols = np.concatenate([p[1] + o for p, o in zip(parts, offs)])
        per_rank_rc.append((rows, cols))
    n_x = cols_pad + bnode_pad + boff_pad
    out = _format_stats_from_coo(per_rank_rc, rows_pad, n_x,
                                 nnz_pad_total, block_shape, tuner,
                                 ell_n_x=recv_x_len)
    out["transpose"] = _transpose_format_stats(
        [(c, r) for r, c in per_rank_rc], n_x, rows_pad, nnz_pad_total,
        block_shape, tuner)
    return out


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


def compile_nap(a: CSR, part: RowPartition, topo: Topology,
                plan: Optional[NAPPlan] = None,
                block_shape: Tuple[int, int] = (8, 128),
                cache: bool = True, local_compute: str = "auto",
                tuner: LocalComputeParams = TPU_V5E_LOCAL,
                col_part: Optional[RowPartition] = None) -> CompiledNAP:
    """Compile the node-aware plan to static shard_map arrays.

    ``part`` is the ROW partition (output layout); ``col_part`` the
    COLUMN/x partition — defaults to ``part``, the square case.  A
    rectangular ``a`` REQUIRES ``col_part`` (shapes are validated).
    """
    if local_compute not in ("auto",) + LOCAL_FORMATS:
        raise ValueError(local_compute)
    cpart = part if col_part is None else col_part
    if part.n_rows != a.shape[0] or cpart.n_rows != a.shape[1]:
        raise ValueError(
            f"partition/matrix mismatch: a is {a.shape}, row partition has "
            f"{part.n_rows} rows, column partition {cpart.n_rows}")
    key = None
    if plan is None and cache:
        key = _cache_key(a, part, topo, block_shape, local_compute, tuner,
                         "nap", col_part=col_part)
        hit = _cache_get(key)
        if hit is not None:
            return hit
    if plan is None:
        plan = build_nap_plan(a.indptr, a.indices, part, topo,
                              pairing="aligned", col_part=col_part)
    n_procs, ppn, n_nodes = topo.n_procs, topo.ppn, topo.n_nodes
    blocks = split_all_blocks(a, part, topo, col_part=cpart)
    local_index = cpart.local_index()
    bn = block_shape[1]
    if bn % 8 != 0:
        raise ValueError(f"bn must be a multiple of the 8-wide sublane "
                         f"tile, got {bn}")
    # Segment lengths of the packed x operand are rounded up to the lane
    # width bn, so v_loc / b_on_node / b_off_node are bn-aligned views of
    # one packed domain and the fused BSR kernel reads them zero-copy (no
    # HBM pad/concat per call).  Padding slots beyond the true sizes are
    # never referenced by a nonzero, so the rounding is mathematically
    # inert everywhere (incl. the COO path's segment_sum).  rows_pad is
    # the row-partition output pad, cols_pad the column-partition v_loc
    # pad (identical in the square single-partition case).
    rows_pad = _ceil_to(max(1, int(part.counts().max())), bn)
    cols_pad = _ceil_to(max(1, int(cpart.counts().max())), bn)
    bnode_pad = _ceil_to(max(1, max(b.on_node_cols.size for b in blocks)), bn)
    boff_pad = _ceil_to(max(1, max(b.off_node_cols.size for b in blocks)), bn)

    def msg_pad(phase: List[List[Message]]) -> int:
        sizes = [m.size for msgs in phase for m in msgs]
        return max(1, max(sizes, default=1))

    full_pad = msg_pad(plan.local_full_sends)
    init_pad = msg_pad(plan.local_init_sends)
    inter_pad = msg_pad(plan.inter_sends)
    final_pad = msg_pad(plan.local_final_sends)
    nnz_pads = {
        "on_proc": max(1, max(b.on_proc.nnz for b in blocks)),
        "on_node": max(1, max(b.on_node.nnz for b in blocks)),
        "off_node": max(1, max(b.off_node.nnz for b in blocks)),
    }

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
        blk = blocks[r]

        # -- full-local sends: [ppn, full_pad] source local-row positions ----
        fs = np.zeros((ppn, full_pad), dtype=np.int32)
        for m in plan.local_full_sends[r]:
            fs[topo.local_of(m.dst), : m.size] = local_index[m.idx]
        full_send.append(fs)

        # -- init sends -------------------------------------------------------
        isnd = np.zeros((ppn, init_pad), dtype=np.int32)
        for m in plan.local_init_sends[r]:
            isnd[topo.local_of(m.dst), : m.size] = local_index[m.idx]
        init_send.append(isnd)

        # -- inter gather: positions into concat(v_loc, init_recv_flat) -------
        # (bulk searchsorted against the init-phase slot map; no element loops)
        init_map = plan.recv_slot_map(r, "init", init_pad)
        ig = np.zeros((n_nodes, inter_pad), dtype=np.int32)
        for m in plan.inter_sends[r]:
            owners = cpart.owner[m.idx]
            own = owners == r
            pos = np.empty(m.size, dtype=np.int64)
            pos[own] = local_index[m.idx[own]]
            if not own.all():
                pos[~own] = cols_pad + lookup_slots(init_map, m.idx[~own])
            ig[topo.node_of(m.dst), : m.size] = pos
        inter_gather.append(ig)

        # -- final sends: positions into inter_recv_flat ----------------------
        inter_map = plan.recv_slot_map(r, "inter", inter_pad)
        fsnd = np.zeros((ppn, final_pad), dtype=np.int32)
        for m in plan.local_final_sends[r]:
            fsnd[topo.local_of(m.dst), : m.size] = lookup_slots(inter_map, m.idx)
        final_send.append(fsnd)

        # -- on-node buffer gather: positions into full_recv_flat -------------
        full_map = plan.recv_slot_map(r, "full", full_pad)
        bg = np.zeros((bnode_pad,), dtype=np.int32)
        bg[: blk.on_node_cols.size] = lookup_slots(full_map, blk.on_node_cols)
        bnode_gather.append(bg)

        # -- off-node buffer gather: concat(inter_recv_flat, final_recv_flat) -
        final_map = plan.recv_slot_map(r, "final", final_pad)
        comb_idx = np.concatenate([inter_map[0], final_map[0]])
        comb_pos = np.concatenate([inter_map[1],
                                   n_nodes * inter_pad + final_map[1]])
        order = np.argsort(comb_idx, kind="stable")
        og = np.zeros((boff_pad,), dtype=np.int32)
        og[: blk.off_node_cols.size] = lookup_slots(
            (comb_idx[order], comb_pos[order]), blk.off_node_cols)
        boff_gather.append(og)

        # -- COO blocks --------------------------------------------------------
        for key_c, block in (("on_proc", blk.on_proc), ("on_node", blk.on_node),
                             ("off_node", blk.off_node)):
            rows_i, cols_i, vals_i = block.to_coo()
            coo[key_c]["rows"].append(rows_i.astype(np.int32))
            coo[key_c]["cols"].append(cols_i.astype(np.int32))
            coo[key_c]["vals"].append(vals_i)

    stack_int("full_send", full_send, (ppn, full_pad))
    stack_int("init_send", init_send, (ppn, init_pad))
    stack_int("final_send", final_send, (ppn, final_pad))
    stack_int("inter_gather", inter_gather, (n_nodes, inter_pad))
    stack_int("bnode_gather", bnode_gather, (bnode_pad,))
    stack_int("boff_gather", boff_gather, (boff_pad,))
    for key_c in coo:
        arrays[f"{key_c}_rows"] = _pad_to(coo[key_c]["rows"], nnz_pads[key_c]).astype(np.int32)
        arrays[f"{key_c}_cols"] = _pad_to(coo[key_c]["cols"], nnz_pads[key_c]).astype(np.int32)
        arrays[f"{key_c}_vals"] = _pad_to(
            [v.astype(np.float32) for v in coo[key_c]["vals"]], nnz_pads[key_c], fill=0.0)

    pads = dict(full=full_pad, init=init_pad, inter=inter_pad, final=final_pad,
                bnode=bnode_pad, boff=boff_pad, **{f"nnz_{k}": v for k, v in nnz_pads.items()})
    compiled = CompiledNAP(topo=topo, part=part, col_part=cpart,
                           rows_pad=rows_pad, cols_pad=cols_pad, pads=pads,
                           arrays=arrays, plan=plan,
                           block_shape=tuple(block_shape),
                           local_blocks=blocks,
                           requested_local_compute=local_compute,
                           a_ref=a, _cache_token=key)
    compiled.autotune = _autotune_stats(
        blocks, rows_pad, cols_pad, bnode_pad, boff_pad,
        sum(nnz_pads.values()), tuple(block_shape), tuner,
        compiled.recv_x_len)
    if key is not None:
        _cache_put(key, compiled)
    return compiled


def compile_multistep(a: CSR, part: RowPartition, topo: Topology,
                      plan=None, block_shape: Tuple[int, int] = (8, 128),
                      cache: bool = True, local_compute: str = "auto",
                      tuner: LocalComputeParams = TPU_V5E_LOCAL,
                      col_part: Optional[RowPartition] = None,
                      threshold="auto") -> CompiledNAP:
    """Compile the multi-step plan (``repro.comm.multistep``) to static
    shard_map arrays.

    Produces a :class:`CompiledNAP` with ``comm="multistep"``: the four
    NAP arrays are built from the high-duplication sub-plan exactly as
    :func:`compile_nap` builds them, plus a ``direct_send``
    ``[n_procs, direct_pad]`` gather for the fifth (flat, low-duplication)
    exchange, and ``boff_gather`` resolves off-node columns against the
    concatenation of all THREE recv buffers
    ``[inter | final | direct]``.  ``plan`` optionally supplies a
    prebuilt :class:`repro.comm.multistep.MultistepPlan`.
    """
    from repro.comm.multistep import build_multistep_plan, resolve_threshold
    if local_compute not in ("auto",) + LOCAL_FORMATS:
        raise ValueError(local_compute)
    cpart = part if col_part is None else col_part
    if part.n_rows != a.shape[0] or cpart.n_rows != a.shape[1]:
        raise ValueError(
            f"partition/matrix mismatch: a is {a.shape}, row partition has "
            f"{part.n_rows} rows, column partition {cpart.n_rows}")
    thr = resolve_threshold(threshold, topo)
    key = None
    if plan is None and cache:
        # the threshold changes the split, so it is part of the plan family
        key = _cache_key(a, part, topo, block_shape, local_compute, tuner,
                         f"multistep:{thr}", col_part=col_part)
        hit = _cache_get(key)
        if hit is not None:
            return hit
    if plan is None:
        plan = build_multistep_plan(a.indptr, a.indices, part, topo,
                                    pairing="aligned", col_part=col_part,
                                    threshold=thr)
    nap_plan, direct = plan.nap, plan.direct
    n_procs, ppn, n_nodes = topo.n_procs, topo.ppn, topo.n_nodes
    blocks = split_all_blocks(a, part, topo, col_part=cpart)
    local_index = cpart.local_index()
    bn = block_shape[1]
    if bn % 8 != 0:
        raise ValueError(f"bn must be a multiple of the 8-wide sublane "
                         f"tile, got {bn}")
    rows_pad = _ceil_to(max(1, int(part.counts().max())), bn)
    cols_pad = _ceil_to(max(1, int(cpart.counts().max())), bn)
    bnode_pad = _ceil_to(max(1, max(b.on_node_cols.size for b in blocks)), bn)
    boff_pad = _ceil_to(max(1, max(b.off_node_cols.size for b in blocks)), bn)

    def msg_pad(phase: List[List[Message]]) -> int:
        sizes = [m.size for msgs in phase for m in msgs]
        return max(1, max(sizes, default=1))

    full_pad = msg_pad(nap_plan.local_full_sends)
    init_pad = msg_pad(nap_plan.local_init_sends)
    inter_pad = msg_pad(nap_plan.inter_sends)
    final_pad = msg_pad(nap_plan.local_final_sends)
    direct_pad = msg_pad(direct.sends)
    nnz_pads = {
        "on_proc": max(1, max(b.on_proc.nnz for b in blocks)),
        "on_node": max(1, max(b.on_node.nnz for b in blocks)),
        "off_node": max(1, max(b.off_node.nnz for b in blocks)),
    }

    arrays: Dict[str, np.ndarray] = {}

    def stack_int(name: str, per_rank: List[np.ndarray], shape: Tuple[int, ...]) -> None:
        out = np.zeros((n_procs,) + shape, dtype=np.int32)
        for r, arr in enumerate(per_rank):
            out[r] = arr
        arrays[name] = out

    full_send, init_send, final_send, direct_send = [], [], [], []
    inter_gather, bnode_gather, boff_gather = [], [], []
    coo = {k: {"rows": [], "cols": [], "vals": []} for k in nnz_pads}

    for r in range(n_procs):
        blk = blocks[r]

        fs = np.zeros((ppn, full_pad), dtype=np.int32)
        for m in nap_plan.local_full_sends[r]:
            fs[topo.local_of(m.dst), : m.size] = local_index[m.idx]
        full_send.append(fs)

        isnd = np.zeros((ppn, init_pad), dtype=np.int32)
        for m in nap_plan.local_init_sends[r]:
            isnd[topo.local_of(m.dst), : m.size] = local_index[m.idx]
        init_send.append(isnd)

        init_map = nap_plan.recv_slot_map(r, "init", init_pad)
        ig = np.zeros((n_nodes, inter_pad), dtype=np.int32)
        for m in nap_plan.inter_sends[r]:
            owners = cpart.owner[m.idx]
            own = owners == r
            pos = np.empty(m.size, dtype=np.int64)
            pos[own] = local_index[m.idx[own]]
            if not own.all():
                pos[~own] = cols_pad + lookup_slots(init_map, m.idx[~own])
            ig[topo.node_of(m.dst), : m.size] = pos
        inter_gather.append(ig)

        inter_map = nap_plan.recv_slot_map(r, "inter", inter_pad)
        fsnd = np.zeros((ppn, final_pad), dtype=np.int32)
        for m in nap_plan.local_final_sends[r]:
            fsnd[topo.local_of(m.dst), : m.size] = lookup_slots(inter_map, m.idx)
        final_send.append(fsnd)

        # -- direct sends: [n_procs, direct_pad] source local-row positions,
        #    one slot per destination rank in the flat fifth exchange.
        ds = np.zeros((n_procs, direct_pad), dtype=np.int32)
        for m in direct.sends[r]:
            ds[m.dst, : m.size] = local_index[m.idx]
        direct_send.append(ds)

        full_map = nap_plan.recv_slot_map(r, "full", full_pad)
        bg = np.zeros((bnode_pad,), dtype=np.int32)
        bg[: blk.on_node_cols.size] = lookup_slots(full_map, blk.on_node_cols)
        bnode_gather.append(bg)

        # -- off-node gather over concat(inter | final | direct) recvs -------
        final_map = nap_plan.recv_slot_map(r, "final", final_pad)
        direct_map = direct.recv_slot_map(r, direct_pad)
        comb_idx = np.concatenate([inter_map[0], final_map[0], direct_map[0]])
        comb_pos = np.concatenate([
            inter_map[1],
            n_nodes * inter_pad + final_map[1],
            n_nodes * inter_pad + ppn * final_pad + direct_map[1]])
        order = np.argsort(comb_idx, kind="stable")
        og = np.zeros((boff_pad,), dtype=np.int32)
        og[: blk.off_node_cols.size] = lookup_slots(
            (comb_idx[order], comb_pos[order]), blk.off_node_cols)
        boff_gather.append(og)

        for key_c, block in (("on_proc", blk.on_proc), ("on_node", blk.on_node),
                             ("off_node", blk.off_node)):
            rows_i, cols_i, vals_i = block.to_coo()
            coo[key_c]["rows"].append(rows_i.astype(np.int32))
            coo[key_c]["cols"].append(cols_i.astype(np.int32))
            coo[key_c]["vals"].append(vals_i)

    stack_int("full_send", full_send, (ppn, full_pad))
    stack_int("init_send", init_send, (ppn, init_pad))
    stack_int("final_send", final_send, (ppn, final_pad))
    stack_int("direct_send", direct_send, (n_procs, direct_pad))
    stack_int("inter_gather", inter_gather, (n_nodes, inter_pad))
    stack_int("bnode_gather", bnode_gather, (bnode_pad,))
    stack_int("boff_gather", boff_gather, (boff_pad,))
    for key_c in coo:
        arrays[f"{key_c}_rows"] = _pad_to(coo[key_c]["rows"], nnz_pads[key_c]).astype(np.int32)
        arrays[f"{key_c}_cols"] = _pad_to(coo[key_c]["cols"], nnz_pads[key_c]).astype(np.int32)
        arrays[f"{key_c}_vals"] = _pad_to(
            [v.astype(np.float32) for v in coo[key_c]["vals"]], nnz_pads[key_c], fill=0.0)

    pads = dict(full=full_pad, init=init_pad, inter=inter_pad, final=final_pad,
                direct=direct_pad, bnode=bnode_pad, boff=boff_pad,
                **{f"nnz_{k}": v for k, v in nnz_pads.items()})
    compiled = CompiledNAP(topo=topo, part=part, col_part=cpart,
                           rows_pad=rows_pad, cols_pad=cols_pad, pads=pads,
                           arrays=arrays, plan=nap_plan,
                           block_shape=tuple(block_shape),
                           local_blocks=blocks,
                           requested_local_compute=local_compute,
                           comm="multistep", ms_plan=plan,
                           a_ref=a, _cache_token=key)
    compiled.autotune = _autotune_stats(
        blocks, rows_pad, cols_pad, bnode_pad, boff_pad,
        sum(nnz_pads.values()), tuple(block_shape), tuner,
        compiled.recv_x_len)
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
# Shared run wrapper
# ---------------------------------------------------------------------------

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
# NAP executor
# ---------------------------------------------------------------------------

def nap_forward_shardmap(compiled: CompiledNAP, mesh: Mesh,
                         local_compute: str = "auto", nv_block: int = 128,
                         materialize_x: bool = False,
                         integrity: bool = False, fault_fetch=None):
    """Build the jitted shard_map NAPSpMV: f(v_shards) -> w_shards.

    ``v_shards`` is [n_nodes, ppn, cols_pad] or [n_nodes, ppn, cols_pad, nv]
    (multi-RHS SpMM) — COLUMN-partition packed; the output is ROW-partition
    packed [n_nodes, ppn, rows_pad(, nv)] (identical shapes in the square
    single-partition case).  ``local_compute`` selects the
    local kernel: ``"auto"`` (default) defers to the compile-time format
    autotuner, ``"bsr"`` forces the fused Pallas kernel, ``"ell"`` the
    XLA ELL product and ``"coo"`` the scalar segment_sum reference.  The
    resolved format is exposed as ``run.local_compute``.
    ``materialize_x=True`` re-enables the legacy HBM pad/concat of the
    BSR kernel's packed x operand (bit-for-bit equal to the default
    zero-copy read; kept as an A/B oracle).

    ``integrity=True`` builds the INSTRUMENTED program instead: every
    message payload is checksummed by the sender before the scripted
    fault boundary (the checksum words travel through a second tiny
    all_to_all over the same axis) and re-checksummed by the receiver,
    the armed fault-spec argument (``fault_fetch``) is applied as a pure
    transform at the pack boundary, and the ABFT triple
    ``(sum(y_p), c_p · x_packed, |c_p| · |x_packed|)`` is emitted per
    device — ``run`` then returns ``(w_shards, chk, abft)``.  With
    ``integrity=False`` the emitted program is bit-for-bit the
    uninstrumented one (no extra arguments, outputs, or ops).
    """
    fmt = compiled.resolve_local_compute(local_compute)
    if fmt == "bsr":
        compiled.ensure_fused()
    elif fmt == "ell":
        compiled.ensure_ell()
    topo = compiled.topo
    rows_pad = compiled.rows_pad
    bn = compiled.block_shape[1]
    # multistep plans add the fifth "direct" exchange; with comm="nap"
    # every ms branch below is dead at trace time and the emitted program
    # is bit-for-bit the single-step one.
    ms = compiled.comm == "multistep"
    ph = phase_index("multistep" if ms else "nap")
    msg_phases = MULTISTEP_MESSAGE_PHASES if ms else NAP_MESSAGE_PHASES
    max_slots = topo.n_procs if ms else max(topo.ppn, topo.n_nodes)
    # The ELL ids are composed with the buffer gathers at plan compile
    # (CompiledNAP.recv_domain_map), so the ELL program reads the
    # received buffers as they arrive; BSR (whose zero-copy x needs
    # bn-aligned buffers) and COO gather bnode/boff here.
    composed = fmt == "ell"
    if integrity:
        compiled.ensure_abft()
        if composed:
            _ensure_abft_recv(compiled)

    def per_device(v_loc, *args):
        squeeze = lambda x: x.reshape(x.shape[2:])
        if integrity:
            fault_spec = squeeze(args[0])                   # [n_phases, 4]
            args = args[1:]
        v_loc = squeeze(v_loc)                              # [rows_pad, nv]
        full_send, init_send, final_send, inter_gather = map(squeeze, args[:4])
        args = args[4:]
        if not composed:
            bnode_gather, boff_gather = map(squeeze, args[:2])
            args = args[2:]
        direct_send = squeeze(args[0]) if ms else None
        tail = tuple(map(squeeze, args[1 if ms else 0:]))
        if integrity:
            abft_col, abft_abs = tail[-2:]
            tail = tail[:-2]
        nv = v_loc.shape[-1]

        chks = {}

        def exchange(src, send, phase, axis):
            # One phase under the scope repro.exchange.<phase>: the
            # packing gather src[send], then the sender checksums the
            # CLEAN payload, the scripted fault (if armed for this
            # device+phase) corrupts it at the pack boundary, and payload
            # and checksum words travel through the same collective; the
            # receiver recomputes.  Uninstrumented (integrity=False) this
            # is literally the gather and the bare all_to_all.
            with jax.named_scope(f"repro.exchange.{phase}"):
                buf = src[send]
                if not integrity:
                    return jax.lax.all_to_all(buf, axis, 0, 0, tiled=True)
                sent = _msg_checksums(buf)
                buf = _apply_fault(buf, fault_spec[ph[phase]])
                recv = jax.lax.all_to_all(buf, axis, 0, 0, tiled=True)
                expect = jax.lax.all_to_all(sent[:, None], axis, 0, 0,
                                            tiled=True)[:, 0]
                chks[phase] = (expect, _msg_checksums(recv))
                return recv

        # Phase A+B (overlap in Alg. 3): intra-node exchanges over "proc".
        full_recv = exchange(v_loc, full_send, "full", "proc")
        init_recv = exchange(v_loc, init_send, "init", "proc")

        # Phase C: ONE aggregated inter-node all-to-all over "node".
        with jax.named_scope("repro.buffers"):
            staged = jnp.concatenate([v_loc, init_recv.reshape(-1, nv)])
        inter_recv = exchange(staged, inter_gather, "inter", "node")

        # Phase D: intra-node scatter of received off-node data.
        inter_flat = inter_recv.reshape(-1, nv)
        final_recv = exchange(inter_flat, final_send, "final", "proc")

        boff_parts = [inter_flat, final_recv.reshape(-1, nv)]
        if ms:
            # Phase E (multistep only): the low-duplication columns ship
            # owner -> requester in one flat exchange, bypassing the
            # aggregation; boff_gather resolves against all three buffers.
            direct_recv = exchange(v_loc, direct_send, "direct",
                                   ("node", "proc"))
            boff_parts.append(direct_recv.reshape(-1, nv))

        if composed:
            # the received domain [v_loc | full | inter | final (| direct)]
            x_segs = (v_loc, full_recv.reshape(-1, nv)) + tuple(boff_parts)
        else:
            # Buffers of Algorithm 3's three local_spmv calls.
            with jax.named_scope("repro.buffers"):
                bnode = full_recv.reshape(-1, nv)[bnode_gather]  # [bnode_pad, nv]
                boff = jnp.concatenate(boff_parts)[boff_gather]
            x_segs = (v_loc, bnode, boff)

        with jax.named_scope("repro.local"):
            if fmt == "bsr":
                fused_cols, fused_blocks = tail
                # segment lengths are bn-aligned at compile time: the three
                # buffers ARE the packed x domain — no pad/concat round-trip.
                if materialize_x:
                    x_cat = jnp.concatenate([v_loc, bnode, boff])
                    w_tiles = fused_bsr_spmm(fused_cols, fused_blocks,
                                             x_cat.reshape(-1, bn, nv),
                                             nv_block=nv_block)
                else:
                    xs = tuple(seg.reshape(-1, bn, nv)
                               for seg in (v_loc, bnode, boff))
                    w_tiles = fused_bsr_spmm_packed(fused_cols, fused_blocks,
                                                    xs, nv_block=nv_block)
                w = w_tiles.reshape(-1, nv)[:rows_pad]
            elif fmt == "ell":
                w = _ell_product(tail, x_segs, rows_pad)
            else:
                (on_proc_rows, on_proc_cols, on_proc_vals,
                 on_node_rows, on_node_cols, on_node_vals,
                 off_node_rows, off_node_cols, off_node_vals) = tail
                # local_spmv(A_on_process, v) — no communication (Alg. 3).
                w = segment_sum(on_proc_vals[:, None] * v_loc[on_proc_cols],
                                on_proc_rows, num_segments=rows_pad)
                # local_spmv(A_on_node, b_l->l)
                w = w + segment_sum(
                    on_node_vals[:, None] * bnode[on_node_cols],
                    on_node_rows, num_segments=rows_pad)
                # local_spmv(A_off_node, b_nl->l)
                w = w + segment_sum(
                    off_node_vals[:, None] * boff[off_node_cols],
                    off_node_rows, num_segments=rows_pad)
        if not integrity:
            return w.reshape(1, 1, rows_pad, -1)
        with jax.named_scope("repro.abft"):
            # Scripted compute-side corruption (what ABFT exists to catch)
            # is applied to the LOCAL result, after the wire but before
            # the check.
            w = _apply_fault(w[None], fault_spec[ph["compute"]])[0]
            # ABFT: sum(y_p) vs c_p · x over the SAME buffers the compute
            # consumed (c_p on the received domain when composed), plus
            # the |c_p|·|x| tolerance scale.
            d, s = _abft_dots(abft_col, abft_abs, x_segs)
            abft = jnp.stack([jnp.sum(w, axis=0), d, s])
            chk = _stack_chk([chks[p] for p in msg_phases], max_slots)
        return (w.reshape(1, 1, rows_pad, -1),
                chk.reshape((1, 1) + chk.shape),
                abft.reshape((1, 1) + abft.shape))

    names = ["full_send", "init_send", "final_send", "inter_gather"]
    if not composed:
        names += ["bnode_gather", "boff_gather"]
    if ms:
        names.append("direct_send")
    if fmt == "bsr":
        names += ["fused_cols", "fused_blocks"]
    elif fmt == "ell":
        names += _ell_names(compiled, "ell_cols", "ell_vals", "ell_chunk_row")
    else:
        names += ["on_proc_rows", "on_proc_cols", "on_proc_vals",
                  "on_node_rows", "on_node_cols", "on_node_vals",
                  "off_node_rows", "off_node_cols", "off_node_vals"]
    if integrity:
        names += list(_ABFT_RECV_NAMES if composed
                      else ("abft_col", "abft_col_abs"))
    spec = P("node", "proc")
    n_in = 1 + len(names) + (1 if integrity else 0)
    smapped = jax.shard_map(per_device, mesh=mesh,
                            in_specs=(spec,) * n_in,
                            out_specs=(spec, spec, spec) if integrity
                            else spec,
                            check_vma=False)
    from repro.mesh.buffers import input_stager
    return _make_run(smapped, fmt, _plan_arg_fetch(compiled, names),
                     input_stager(compiled.topo),
                     fault_fetch=fault_fetch if integrity else None)


def nap_transpose_shardmap(compiled: CompiledNAP, mesh: Mesh,
                           local_compute: str = "auto", nv_block: int = 128,
                           integrity: bool = False, fault_fetch=None):
    """Build the jitted shard_map transpose NAPSpMV: f(u_shards) -> z_shards
    with ``z = A.T u`` — the exact adjoint of :func:`nap_forward_shardmap`.

    ``u_shards`` is ROW-partition packed ([.., rows_pad(, nv)]); the
    output is COLUMN-partition packed ([.., cols_pad(, nv)]) — for the
    square single-partition case the two coincide and this is invisible.

    The forward program is reversed operation by operation: the three
    local_spmv blocks run transposed first (producing per-buffer
    contribution vectors), then each communication phase runs backwards —
    final, inter, init, full — with every forward gather map reused as a
    scatter-add map and every ``all_to_all`` re-applied (a tiled
    all_to_all is an involution and its own adjoint).

    Transposed local compute runs through the adaptive engine like the
    forward direction: ``"auto"`` resolves against the transpose verdict
    recorded on ``compiled.autotune["transpose"]`` (argmin of ell/coo —
    there is no transposed Pallas BSR kernel, so a ``"bsr"`` request also
    defers to that verdict).  ``"ell"`` runs A_r^T as ONE ELL SpMM over
    the packed contribution domain ``[z | c_on_node | c_off_node]``;
    ``"coo"`` is the scalar segment_sum scatter reference.
    """
    fmt = compiled.resolve_transpose_local_compute(local_compute)
    if fmt == "ell":
        compiled.ensure_ell_t()
    topo = compiled.topo
    rows_pad, cols_pad = compiled.rows_pad, compiled.cols_pad
    pads = compiled.pads
    nn, ppn = topo.n_nodes, topo.ppn
    n_procs = topo.n_procs
    full_pad, init_pad = pads["full"], pads["init"]
    inter_pad, final_pad = pads["inter"], pads["final"]
    bnode_pad, boff_pad = pads["bnode"], pads["boff"]
    # see nap_forward_shardmap: with comm="nap" the ms branches are dead
    # at trace time and the program is bit-for-bit the single-step one.
    ms = compiled.comm == "multistep"
    direct_pad = pads.get("direct", 0)
    ph = phase_index("multistep" if ms else "nap")
    msg_phases = MULTISTEP_MESSAGE_PHASES if ms else NAP_MESSAGE_PHASES
    max_slots = n_procs if ms else max(ppn, nn)
    if integrity:
        compiled.ensure_abft()

    def per_device(u_loc, *args):
        squeeze = lambda x: x.reshape(x.shape[2:])
        if integrity:
            fault_spec = squeeze(args[0])                   # [n_phases, 4]
            args = args[1:]
        u_loc = squeeze(u_loc)                              # [rows_pad, nv]
        (full_send, init_send, final_send, inter_gather, bnode_gather,
         boff_gather) = map(squeeze, args[:6])
        direct_send = squeeze(args[6]) if ms else None
        tail = tuple(map(squeeze, args[7 if ms else 6:]))
        if integrity:
            abft_row, abft_abs = tail[-2:]
            tail = tail[:-2]
        nv = u_loc.shape[-1]

        chks = {}

        def exchange(buf, send, num_segments, phase, axis):
            # Reverse-direction twin of the forward builder's exchange(),
            # under the same scope repro.exchange.<phase>: checksum the
            # clean pre-exchange contribution buffer, apply the armed
            # fault at the pack boundary, verify post-delivery, then
            # scatter-add over ``send`` (the adjoint of the forward
            # packing gather).
            with jax.named_scope(f"repro.exchange.{phase}"):
                if not integrity:
                    recv = jax.lax.all_to_all(buf, axis, 0, 0, tiled=True)
                else:
                    sent = _msg_checksums(buf)
                    buf = _apply_fault(buf, fault_spec[ph[phase]])
                    recv = jax.lax.all_to_all(buf, axis, 0, 0, tiled=True)
                    expect = jax.lax.all_to_all(sent[:, None], axis, 0, 0,
                                                tiled=True)[:, 0]
                    chks[phase] = (expect, _msg_checksums(recv))
                return segment_sum(recv.reshape(-1, nv), send.reshape(-1),
                                   num_segments=num_segments)

        # -- transposed local_spmv blocks: rows index u, cols index the
        #    output domain of each block (local x rows / buffer slots).
        with jax.named_scope("repro.local"):
            if fmt == "ell":
                contrib = _ell_product(tail, (u_loc,), compiled.packed_x_len)
                z = contrib[:cols_pad]
                c_node = contrib[cols_pad: cols_pad + bnode_pad]
                c_off = contrib[cols_pad + bnode_pad:]
            else:
                (on_proc_rows, on_proc_cols, on_proc_vals,
                 on_node_rows, on_node_cols, on_node_vals,
                 off_node_rows, off_node_cols, off_node_vals) = tail
                z = segment_sum(on_proc_vals[:, None] * u_loc[on_proc_rows],
                                on_proc_cols, num_segments=cols_pad)
                c_node = segment_sum(
                    on_node_vals[:, None] * u_loc[on_node_rows],
                    on_node_cols, num_segments=bnode_pad)
                c_off = segment_sum(
                    off_node_vals[:, None] * u_loc[off_node_rows],
                    off_node_cols, num_segments=boff_pad)

        if integrity:
            # Compute-side fault + transpose ABFT over the packed
            # contribution domain, BEFORE any communication: the sum of
            # every local contribution equals the row-sum vector (A_p 1)
            # dotted with u_loc.
            with jax.named_scope("repro.abft"):
                packed_c = jnp.concatenate([z, c_node, c_off])
                packed_c = _apply_fault(packed_c[None],
                                        fault_spec[ph["compute"]])[0]
                abft = jnp.stack([jnp.sum(packed_c, axis=0),
                                  abft_row @ u_loc,
                                  abft_abs @ jnp.abs(u_loc)])
                z = packed_c[:cols_pad]
                c_node = packed_c[cols_pad: cols_pad + bnode_pad]
                c_off = packed_c[cols_pad + bnode_pad:]

        # -- reverse of boff = concat(inter | final [| direct])[boff_gather]
        with jax.named_scope("repro.buffers"):
            comb = segment_sum(
                c_off, boff_gather,
                num_segments=(nn * inter_pad + ppn * final_pad
                              + (n_procs * direct_pad if ms else 0)))
        inter_c = comb[: nn * inter_pad]
        final_recv_c = comb[nn * inter_pad: nn * inter_pad + ppn * final_pad
                            ].reshape(ppn, final_pad, nv)
        z_direct = None
        if ms:
            # -- reverse phase E: direct contributions ride the adjoint flat
            #    all_to_all straight back and scatter into the owners' rows.
            direct_recv_c = comb[nn * inter_pad + ppn * final_pad:
                                 ].reshape(n_procs, direct_pad, nv)
            z_direct = exchange(direct_recv_c, direct_send, cols_pad,
                                "direct", ("node", "proc"))

        # -- reverse phase D: adjoint all_to_all + scatter over final_send
        inter_c = inter_c + exchange(final_recv_c, final_send,
                                     nn * inter_pad, "final", "proc")

        # -- reverse phase C: adjoint inter-node all_to_all + scatter over
        #    inter_gather into the staged domain concat(v_loc, init_recv)
        staged_c = exchange(inter_c.reshape(nn, inter_pad, nv), inter_gather,
                            cols_pad + ppn * init_pad, "inter", "node")
        z = z + staged_c[:cols_pad]

        # -- reverse phase B: init redistribution back to the owners
        init_recv_c = staged_c[cols_pad:].reshape(ppn, init_pad, nv)
        z = z + exchange(init_recv_c, init_send, cols_pad, "init", "proc")

        # -- reverse phase A: on-node buffer contributions back to owners
        with jax.named_scope("repro.buffers"):
            full_recv_c = segment_sum(c_node, bnode_gather,
                                      num_segments=ppn * full_pad)
        z = z + exchange(full_recv_c.reshape(ppn, full_pad, nv), full_send,
                         cols_pad, "full", "proc")
        if ms:
            z = z + z_direct
        if not integrity:
            return z.reshape(1, 1, cols_pad, -1)
        with jax.named_scope("repro.abft"):
            chk = _stack_chk([chks[p] for p in msg_phases], max_slots)
        return (z.reshape(1, 1, cols_pad, -1),
                chk.reshape((1, 1) + chk.shape),
                abft.reshape((1, 1) + abft.shape))

    names = ["full_send", "init_send", "final_send", "inter_gather",
             "bnode_gather", "boff_gather"]
    if ms:
        names.insert(6, "direct_send")
    if fmt == "ell":
        names += _ell_names(compiled, "ell_t_cols", "ell_t_vals",
                            "ell_t_chunk_row")
    else:
        names += ["on_proc_rows", "on_proc_cols", "on_proc_vals",
                  "on_node_rows", "on_node_cols", "on_node_vals",
                  "off_node_rows", "off_node_cols", "off_node_vals"]
    if integrity:
        names += ["abft_row", "abft_row_abs"]
    spec = P("node", "proc")
    n_in = 1 + len(names) + (1 if integrity else 0)
    smapped = jax.shard_map(per_device, mesh=mesh,
                            in_specs=(spec,) * n_in,
                            out_specs=(spec, spec, spec) if integrity
                            else spec,
                            check_vma=False)
    from repro.mesh.buffers import input_stager
    return _make_run(smapped, fmt, _plan_arg_fetch(compiled, names),
                     input_stager(compiled.topo),
                     fault_fetch=fault_fetch if integrity else None)


# ---------------------------------------------------------------------------
# Standard (Algorithm 1) compiled plan + executors
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompiledStandard:
    """Static arrays for the shard_map standard (Alg. 1) SpMV.

    The packed x domain is two-segment: ``[0, cols_pad) = v_loc`` (the
    COLUMN-partition shard) and ``[cols_pad, cols_pad + buf_pad)`` the
    single off-process recv buffer, both bn-aligned (zero-copy kernel
    domain); the output is ``rows_pad`` ROW-partition rows.  Format
    arrays (COO / ELL / fused BSR over that domain) emit lazily from
    ``per_rank_coo``, exactly like :class:`CompiledNAP`'s.
    """

    topo: Topology
    part: RowPartition
    rows_pad: int
    buf_pad: int
    pair_pad: int
    nnz_pad: int
    block_shape: Tuple[int, int]
    arrays: Dict[str, np.ndarray]          # send_idx, buf_gather + lazy fmts
    per_rank_coo: List[Tuple[np.ndarray, np.ndarray, np.ndarray]]
    col_part: Optional[RowPartition] = None  # None = square (col == row)
    cols_pad: int = 0                        # 0 = square (== rows_pad)
    plan: Optional[StandardPlan] = None
    autotune: Dict[str, object] = dataclasses.field(default_factory=dict)
    requested_local_compute: str = "auto"
    ell_t_kmax: int = 0
    _dev_cache: Dict[str, jnp.ndarray] = dataclasses.field(
        default_factory=_plan_namespace, repr=False, compare=False)
    # see the identically-named CompiledNAP fields (swap_values support)
    a_ref: Optional[CSR] = dataclasses.field(
        default=None, repr=False, compare=False)
    _cache_token: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False)

    def __post_init__(self) -> None:
        if self.col_part is None:
            self.col_part = self.part
        if not self.cols_pad:
            self.cols_pad = self.rows_pad

    @property
    def n_x(self) -> int:
        return self.cols_pad + self.buf_pad

    @property
    def packed_x_len(self) -> int:
        return self.n_x

    @property
    def recv_x_len(self) -> int:
        """Element length of the received domain ``[v_loc | recv]`` (the
        flat ``[n_procs, pair_pad]`` recv buffer) the forward ELL ids
        index."""
        return self.cols_pad + self.topo.n_procs * self.pair_pad

    def recv_domain_map(self) -> np.ndarray:
        """[n_procs, n_x] int32: each packed column's position in the
        received domain — v_loc to itself, the buffer through
        ``buf_gather`` (see :meth:`CompiledNAP.recv_domain_map`)."""
        return _recv_domain_map(self.cols_pad, [
            (self.cols_pad, self.arrays["buf_gather"])])

    @property
    def chosen_local_compute(self) -> str:
        return str(self.autotune.get("chosen", "coo"))

    def resolve_local_compute(self, requested: str) -> str:
        return _resolve_local_compute(requested, self.requested_local_compute,
                                      self.chosen_local_compute)

    def resolve_transpose_local_compute(self, requested: str) -> str:
        """See :meth:`CompiledNAP.resolve_transpose_local_compute`."""
        return _resolve_transpose_local_compute(
            requested, self.requested_local_compute, self.autotune)

    def ensure_coo(self) -> None:
        if "A_rows" in self.arrays:
            return
        self.arrays["A_rows"] = _pad_to(
            [rr.astype(np.int32) for rr, _, _ in self.per_rank_coo],
            self.nnz_pad).astype(np.int32)
        self.arrays["A_cols"] = _pad_to(
            [cc.astype(np.int32) for _, cc, _ in self.per_rank_coo],
            self.nnz_pad).astype(np.int32)
        self.arrays["A_vals"] = _pad_to(
            [vv.astype(np.float32) for _, _, vv in self.per_rank_coo],
            self.nnz_pad, fill=0.0)

    def ensure_ell(self) -> None:
        """Forward ELL arrays, column ids composed with
        :meth:`recv_domain_map` (no ``buf_gather`` in the ELL program)."""
        if "ell_cols" in self.arrays:
            return
        split = _split_width(self.autotune)
        e_cols, e_vals, _, chunk_row = stack_ell([
            ELL.from_coo(rr, cc, vv, (self.rows_pad, self.n_x),
                         n_rows_pad=self.rows_pad, split=split)
            for rr, cc, vv in self.per_rank_coo])
        self.arrays["ell_cols"] = _compose_cols(e_cols, self.recv_domain_map())
        self.arrays["ell_vals"] = e_vals
        _set_chunk_row(self.arrays, "ell_chunk_row", chunk_row)

    def ensure_ell_t(self) -> None:
        """Transposed ELL over the packed contribution domain
        ``[z(cols_pad) | buf]`` with x = u_loc (rows_pad)."""
        if "ell_t_cols" in self.arrays:
            return
        split = _split_width(self.autotune.get("transpose", {}))
        e_cols, e_vals, kmax, chunk_row = stack_ell([
            ELL.from_coo(cc, rr, vv, (self.n_x, self.rows_pad),
                         n_rows_pad=self.n_x, split=split)
            for rr, cc, vv in self.per_rank_coo])
        self.arrays["ell_t_cols"] = e_cols
        self.arrays["ell_t_vals"] = e_vals
        _set_chunk_row(self.arrays, "ell_t_chunk_row", chunk_row)
        self.ell_t_kmax = kmax

    def ensure_fused(self) -> None:
        if "fused_cols" in self.arrays:
            return
        bm, bn = self.block_shape
        f_cols, f_blocks, _ = _stack_padded_bsr([
            BSR.from_coo(rr, cc, vv, (self.rows_pad, self.n_x), bm=bm, bn=bn)
            for rr, cc, vv in self.per_rank_coo])
        self.arrays["fused_cols"] = f_cols
        self.arrays["fused_blocks"] = f_blocks

    def ensure_abft(self) -> None:
        """ABFT checksum vectors over the two-segment packed domain —
        see :meth:`CompiledNAP.ensure_abft` (same contract)."""
        if "abft_col" in self.arrays:
            return
        n, n_x, rows_pad = self.topo.n_procs, self.n_x, self.rows_pad
        col = np.zeros((n, n_x), np.float64)
        cola = np.zeros((n, n_x), np.float64)
        row = np.zeros((n, rows_pad), np.float64)
        rowa = np.zeros((n, rows_pad), np.float64)
        for r, (rr, cc, vv) in enumerate(self.per_rank_coo):
            v32 = vv.astype(np.float32).astype(np.float64)
            np.add.at(col[r], cc, v32)
            np.add.at(cola[r], cc, np.abs(v32))
            np.add.at(row[r], rr, v32)
            np.add.at(rowa[r], rr, np.abs(v32))
        self.arrays["abft_col"] = col.astype(np.float32)
        self.arrays["abft_col_abs"] = cola.astype(np.float32)
        self.arrays["abft_row"] = row.astype(np.float32)
        self.arrays["abft_row_abs"] = rowa.astype(np.float32)

    def device_arrays(self) -> Dict[str, jnp.ndarray]:
        """Mesh-shaped (n_nodes, ppn, ...) device arrays, memoized per name."""
        return _memo_device_arrays(self.topo, self.arrays, self._dev_cache)

    def swap_values(self, a_new: CSR) -> List[str]:
        """Hot-swap matrix VALUES in place; sparsity must be identical.
        See :meth:`CompiledNAP.swap_values` — same contract, over the
        two-segment standard-plan domain (``per_rank_coo`` refreshes and
        every materialised format re-emits against the same pads)."""
        _swap_check_structure(self, a_new)
        blocks = split_all_blocks(a_new, self.part, self.topo,
                                  col_part=self.col_part)
        cols_pad = self.cols_pad
        per_rank_coo = []
        for blk in blocks:   # same packed-column layout as compile_standard
            rr0, cc0, vv0 = blk.on_proc.to_coo()
            rr1, cc1, vv1 = blk.on_node.to_coo()
            rr2, cc2, vv2 = blk.off_node.to_coo()
            rr = np.concatenate([rr0, rr1, rr2])
            cc = np.concatenate([cc0, cols_pad + cc1,
                                 cols_pad + blk.on_node_cols.size + cc2])
            vv = np.concatenate([vv0, vv1, vv2])
            per_rank_coo.append((rr, cc, vv))
        self.per_rank_coo = per_rank_coo
        changed = _swap_refresh_lazy(self, [
            ("A_rows", "A_vals", self.ensure_coo),
            ("ell_cols", "ell_vals", self.ensure_ell),
            ("ell_t_cols", "ell_t_vals", self.ensure_ell_t),
            ("fused_cols", "fused_blocks", self.ensure_fused)])
        changed += _swap_refresh_abft(self)
        _swap_finish(self, a_new, changed)
        return changed


def compile_standard(a: CSR, part: RowPartition, topo: Topology,
                     plan: Optional[StandardPlan] = None,
                     block_shape: Tuple[int, int] = (8, 128),
                     cache: bool = True, local_compute: str = "auto",
                     tuner: LocalComputeParams = TPU_V5E_LOCAL,
                     col_part: Optional[RowPartition] = None) -> CompiledStandard:
    """Compile Algorithm 1's flat plan into static shard_map arrays.

    ``part`` is the ROW partition, ``col_part`` the COLUMN/x partition
    (defaults to ``part`` — the square case; see :func:`compile_nap`).
    """
    if local_compute not in ("auto",) + LOCAL_FORMATS:
        raise ValueError(local_compute)
    cpart = part if col_part is None else col_part
    if part.n_rows != a.shape[0] or cpart.n_rows != a.shape[1]:
        raise ValueError(
            f"partition/matrix mismatch: a is {a.shape}, row partition has "
            f"{part.n_rows} rows, column partition {cpart.n_rows}")
    key = None
    if plan is None and cache:
        key = _cache_key(a, part, topo, block_shape, local_compute, tuner,
                         "standard", col_part=col_part)
        hit = _cache_get(key)
        if hit is not None:
            return hit
    if plan is None:
        plan = build_standard_plan(a.indptr, a.indices, part, topo,
                                   col_part=col_part)
    n_procs = topo.n_procs
    blocks = split_all_blocks(a, part, topo, col_part=cpart)
    local_index = cpart.local_index()
    bm, bn = block_shape
    if bn % 8 != 0:
        raise ValueError(f"bn must be a multiple of the 8-wide sublane "
                         f"tile, got {bn}")
    # bn-aligned segments: [0, cols_pad) = v_loc (column-partition shard),
    # [cols_pad, cols_pad+buf_pad) = the single off-process recv buffer
    # (zero-copy kernel domain); rows_pad is the row-partition output pad.
    rows_pad = _ceil_to(max(1, int(part.counts().max())), bn)
    cols_pad = _ceil_to(max(1, int(cpart.counts().max())), bn)
    buf_pad = _ceil_to(
        max(1, max(b.on_node_cols.size + b.off_node_cols.size for b in blocks)),
        bn)
    pair_pad = max(1, max((m.size for msgs in plan.sends for m in msgs), default=1))

    send_idx = np.zeros((n_procs, n_procs, pair_pad), dtype=np.int32)
    for r in range(n_procs):
        for m in plan.sends[r]:
            send_idx[r, m.dst, : m.size] = local_index[m.idx]

    nnz_pad = max(1, max(b.on_node.nnz + b.off_node.nnz + b.on_proc.nnz
                         for b in blocks))

    # --- packed two-segment domain [v_loc | buf] + format decision --------
    n_x = cols_pad + buf_pad
    per_rank_coo = []
    buf_gather = np.zeros((n_procs, buf_pad), dtype=np.int32)
    for r in range(n_procs):
        blk = blocks[r]
        cols_all = np.concatenate([blk.on_node_cols, blk.off_node_cols])
        buf_gather[r, : cols_all.size] = lookup_slots(
            plan.recv_slot_map(r, pair_pad), cols_all)
        rr0, cc0, vv0 = blk.on_proc.to_coo()
        rr1, cc1, vv1 = blk.on_node.to_coo()
        rr2, cc2, vv2 = blk.off_node.to_coo()
        rr = np.concatenate([rr0, rr1, rr2])
        cc = np.concatenate([cc0, cols_pad + cc1,
                             cols_pad + blk.on_node_cols.size + cc2])
        vv = np.concatenate([vv0, vv1, vv2])
        per_rank_coo.append((rr, cc, vv))
    compiled = CompiledStandard(
        topo=topo, part=part, col_part=cpart, rows_pad=rows_pad,
        cols_pad=cols_pad, buf_pad=buf_pad,
        pair_pad=pair_pad, nnz_pad=nnz_pad, block_shape=tuple(block_shape),
        arrays=dict(send_idx=send_idx, buf_gather=buf_gather),
        per_rank_coo=per_rank_coo, plan=plan,
        requested_local_compute=local_compute, a_ref=a, _cache_token=key)
    compiled.autotune = _format_stats_from_coo(
        [(rr, cc) for rr, cc, _ in per_rank_coo], rows_pad, n_x,
        nnz_pad, (bm, bn), tuner, ell_n_x=compiled.recv_x_len)
    compiled.autotune["transpose"] = _transpose_format_stats(
        [(cc, rr) for rr, cc, _ in per_rank_coo], n_x, rows_pad,
        nnz_pad, (bm, bn), tuner)
    if key is not None:
        _cache_put(key, compiled)
    return compiled


def standard_forward_shardmap(compiled: CompiledStandard, mesh: Mesh,
                              local_compute: str = "auto",
                              nv_block: int = 128,
                              materialize_x: bool = False,
                              integrity: bool = False, fault_fetch=None):
    """Algorithm 1 as a flat padded all-to-all over ("node","proc").

    Local compute runs through the same adaptive engine as the NAP path —
    ``"auto"`` (default) picks bsr/ell/coo from the format cost model over
    the two-segment ``[v_loc | recv buffer]`` packed x domain; the fused
    BSR kernel reads the segments zero-copy.  The resolved format is exposed as
    ``run.local_compute``.  ``integrity=True`` instruments the single
    ``pair`` exchange + ABFT exactly like :func:`nap_forward_shardmap`.
    """
    fmt = compiled.resolve_local_compute(local_compute)
    {"coo": compiled.ensure_coo, "ell": compiled.ensure_ell,
     "bsr": compiled.ensure_fused}[fmt]()
    topo = compiled.topo
    rows_pad = compiled.rows_pad
    bn = compiled.block_shape[1]
    ph = phase_index("standard")
    # ELL ids index [v_loc | recv] directly (see CompiledStandard.ensure_ell)
    composed = fmt == "ell"
    if integrity:
        compiled.ensure_abft()
        if composed:
            _ensure_abft_recv(compiled)

    def per_device(v_loc, *args):
        squeeze = lambda x: x.reshape(x.shape[2:])
        if integrity:
            fault_spec = squeeze(args[0])                   # [n_phases, 4]
            args = args[1:]
        v_loc, send_idx = map(squeeze, (v_loc, args[0]))
        args = args[1:]
        if not composed:
            buf_gather = squeeze(args[0])
            args = args[1:]
        tail = tuple(map(squeeze, args))
        if integrity:
            abft_col, abft_abs = tail[-2:]
            tail = tail[:-2]
        nv = v_loc.shape[-1]
        with jax.named_scope("repro.exchange.pair"):
            out = v_loc[send_idx]                     # [n_procs, pair_pad, nv]
            if integrity:
                sent = _msg_checksums(out)
                out = _apply_fault(out, fault_spec[ph["pair"]])
            recv = jax.lax.all_to_all(out, ("node", "proc"), 0, 0,
                                      tiled=True)
            if integrity:
                expect = jax.lax.all_to_all(sent[:, None], ("node", "proc"),
                                            0, 0, tiled=True)[:, 0]
                chk_pair = (expect, _msg_checksums(recv))
        if composed:
            buf = recv.reshape(-1, nv)
        else:
            with jax.named_scope("repro.buffers"):
                buf = recv.reshape(-1, nv)[buf_gather]      # [buf_pad, nv]
        with jax.named_scope("repro.local"):
            if fmt == "bsr":
                fused_cols, fused_blocks = tail
                if materialize_x:
                    x_cat = jnp.concatenate([v_loc, buf]).reshape(-1, bn, nv)
                    w_tiles = fused_bsr_spmm(fused_cols, fused_blocks, x_cat,
                                             nv_block=nv_block)
                else:
                    w_tiles = fused_bsr_spmm_packed(
                        fused_cols, fused_blocks,
                        (v_loc.reshape(-1, bn, nv), buf.reshape(-1, bn, nv)),
                        nv_block=nv_block)
                w = w_tiles.reshape(-1, nv)[:rows_pad]
            elif fmt == "ell":
                w = _ell_product(tail, (v_loc, buf), rows_pad)
            else:
                A_rows, A_cols, A_vals = tail
                full = jnp.concatenate([v_loc, buf])
                w = segment_sum(A_vals[:, None] * full[A_cols], A_rows,
                                num_segments=rows_pad)
        if not integrity:
            return w.reshape(1, 1, rows_pad, -1)
        with jax.named_scope("repro.abft"):
            w = _apply_fault(w[None], fault_spec[ph["compute"]])[0]
            d, s = _abft_dots(abft_col, abft_abs, (v_loc, buf))
            abft = jnp.stack([jnp.sum(w, axis=0), d, s])
            chk = _stack_chk([chk_pair], topo.n_procs)
        return (w.reshape(1, 1, rows_pad, -1),
                chk.reshape((1, 1) + chk.shape),
                abft.reshape((1, 1) + abft.shape))

    names = ["send_idx"] if composed else ["send_idx", "buf_gather"]
    names += {"bsr": ["fused_cols", "fused_blocks"],
              "ell": _ell_names(compiled, "ell_cols", "ell_vals",
                                "ell_chunk_row"),
              "coo": ["A_rows", "A_cols", "A_vals"]}[fmt]
    if integrity:
        names += list(_ABFT_RECV_NAMES if composed
                      else ("abft_col", "abft_col_abs"))
    spec = P("node", "proc")
    n_in = 1 + len(names) + (1 if integrity else 0)
    smapped = jax.shard_map(per_device, mesh=mesh,
                            in_specs=(spec,) * n_in,
                            out_specs=(spec, spec, spec) if integrity
                            else spec,
                            check_vma=False)
    from repro.mesh.buffers import input_stager
    return _make_run(smapped, fmt, _plan_arg_fetch(compiled, names),
                     input_stager(compiled.topo),
                     fault_fetch=fault_fetch if integrity else None)


def standard_transpose_shardmap(compiled: CompiledStandard, mesh: Mesh,
                                local_compute: str = "auto",
                                nv_block: int = 128,
                                integrity: bool = False, fault_fetch=None):
    """Transpose of Algorithm 1 against the same compiled plan:
    f(u_shards) -> z_shards with ``z = A.T u``.

    ``u_shards`` is ROW-partition packed; the output COLUMN-partition
    packed ([.., cols_pad(, nv)]).  Reverse of
    :func:`standard_forward_shardmap`: the local SpMV runs transposed
    over the packed two-segment domain, buffer contributions scatter back
    through ``buf_gather`` into the recv layout, the flat all_to_all
    re-applies (its own adjoint), and ``send_idx`` scatters the returned
    contributions into the owners' rows.  Transposed local compute runs
    the adaptive engine restricted to ell/coo — ``"auto"`` resolves
    against ``compiled.autotune["transpose"]``, ``"ell"`` runs one ELL
    SpMM of A_r^T over the packed contribution domain.
    """
    fmt = compiled.resolve_transpose_local_compute(local_compute)
    if fmt == "ell":
        compiled.ensure_ell_t()
    else:
        compiled.ensure_coo()
    topo = compiled.topo
    rows_pad, cols_pad = compiled.rows_pad, compiled.cols_pad
    pair_pad, n_x = compiled.pair_pad, compiled.n_x
    n_procs = topo.n_procs
    ph = phase_index("standard")
    if integrity:
        compiled.ensure_abft()

    def per_device(u_loc, *args):
        squeeze = lambda x: x.reshape(x.shape[2:])
        if integrity:
            fault_spec = squeeze(args[0])                   # [n_phases, 4]
            args = args[1:]
        u_loc, send_idx, buf_gather = map(squeeze, (u_loc,) + args[:2])
        tail = tuple(map(squeeze, args[2:]))
        if integrity:
            abft_row, abft_abs = tail[-2:]
            tail = tail[:-2]
        nv = u_loc.shape[-1]
        # transposed local SpMV over the packed domain [v_loc | buf]
        with jax.named_scope("repro.local"):
            if fmt == "ell":
                c = _ell_product(tail, (u_loc,), n_x)
            else:
                A_rows, A_cols, A_vals = tail
                c = segment_sum(A_vals[:, None] * u_loc[A_rows], A_cols,
                                num_segments=n_x)
        if integrity:
            # compute fault + transpose ABFT pre-communication (see the
            # NAP transpose builder — same contract)
            with jax.named_scope("repro.abft"):
                c = _apply_fault(c[None], fault_spec[ph["compute"]])[0]
                abft = jnp.stack([jnp.sum(c, axis=0), abft_row @ u_loc,
                                  abft_abs @ jnp.abs(u_loc)])
        z = c[:cols_pad]
        # reverse of buf = recv.reshape(-1)[buf_gather]
        with jax.named_scope("repro.buffers"):
            recv_c = segment_sum(c[cols_pad:], buf_gather,
                                 num_segments=n_procs * pair_pad)
        with jax.named_scope("repro.exchange.pair"):
            out = recv_c.reshape(n_procs, pair_pad, nv)
            if integrity:
                sent = _msg_checksums(out)
                out = _apply_fault(out, fault_spec[ph["pair"]])
            out_c = jax.lax.all_to_all(out, ("node", "proc"), 0, 0,
                                       tiled=True)
            if integrity:
                expect = jax.lax.all_to_all(sent[:, None], ("node", "proc"),
                                            0, 0, tiled=True)[:, 0]
                chk_pair = (expect, _msg_checksums(out_c))
            # reverse of out = v_loc[send_idx]
            z = z + segment_sum(out_c.reshape(-1, nv), send_idx.reshape(-1),
                                num_segments=cols_pad)
        if not integrity:
            return z.reshape(1, 1, cols_pad, -1)
        with jax.named_scope("repro.abft"):
            chk = _stack_chk([chk_pair], n_procs)
        return (z.reshape(1, 1, cols_pad, -1),
                chk.reshape((1, 1) + chk.shape),
                abft.reshape((1, 1) + abft.shape))

    names = ["send_idx", "buf_gather"]
    names += (_ell_names(compiled, "ell_t_cols", "ell_t_vals",
                         "ell_t_chunk_row") if fmt == "ell"
              else ["A_rows", "A_cols", "A_vals"])
    if integrity:
        names += ["abft_row", "abft_row_abs"]
    spec = P("node", "proc")
    n_in = 1 + len(names) + (1 if integrity else 0)
    smapped = jax.shard_map(per_device, mesh=mesh,
                            in_specs=(spec,) * n_in,
                            out_specs=(spec, spec, spec) if integrity
                            else spec,
                            check_vma=False)
    from repro.mesh.buffers import input_stager
    return _make_run(smapped, fmt, _plan_arg_fetch(compiled, names),
                     input_stager(compiled.topo),
                     fault_fetch=fault_fetch if integrity else None)


# ---------------------------------------------------------------------------
# Traffic accounting
# ---------------------------------------------------------------------------

def _phase_lists(compiled) -> Dict[str, Tuple[int, List, List]]:
    """Per message phase: (n_slots per rank, send lists, recv lists).

    Dispatches on the compiled family: NAP phases for ``comm="nap"``,
    NAP + "direct" for ``comm="multistep"``, the single "pair" exchange
    for :class:`CompiledStandard`.  Phases whose plan was dropped (plans
    are optional on a compiled object) are omitted.
    """
    topo = compiled.topo
    if isinstance(compiled, CompiledStandard):
        if compiled.plan is None:
            return {}
        return {"pair": (topo.n_procs, compiled.plan.sends,
                         compiled.plan.recvs)}
    plan = compiled.plan
    if plan is None:
        return {}
    out = {
        "full": (topo.ppn, plan.local_full_sends, plan.local_full_recvs),
        "init": (topo.ppn, plan.local_init_sends, plan.local_init_recvs),
        "inter": (topo.n_nodes, plan.inter_sends, plan.inter_recvs),
        "final": (topo.ppn, plan.local_final_sends, plan.local_final_recvs),
    }
    if getattr(compiled, "comm", "nap") == "multistep" \
            and compiled.ms_plan is not None:
        direct = compiled.ms_plan.direct
        out["direct"] = (topo.n_procs, direct.sends, direct.recvs)
    return out


def padded_traffic(compiled, integrity: str = "off",
                   local_compute: str = "auto") -> Dict[str, object]:
    """Padded (SPMD-actual) vs effective bytes per phase, float32 payloads.

    Padded bytes are what the static all-to-alls actually move (every rank
    sends its full padded buffer every time); effective bytes are the plan's
    true message payloads — the gap is the padding the paper's T/U balancing
    minimises.  Effective ≤ padded always.

    Works for every compiled family: NAP (full/init/inter/final),
    multistep (+ the "direct" exchange), and standard (the single "pair"
    exchange).  Two per-direction extras ride along:

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
    the bottleneck rank — ``bnode`` + ``boff`` (NAP, multistep) or
    ``buf`` (standard), padding included, as the SPMD gather runs them.
    It is 0 when ``local_compute`` resolves to ``ell``, whose column ids
    are composed with those gathers at plan compile.

    ``ell_split_width``, ``ell_chunk_rows``, ``ell_pad_rows``,
    ``ell_slots`` and ``ell_slots_per_nnz`` (forward at the top level,
    transpose under ``"transpose"``) report the ELL layout the autotuner
    picked.
    """
    topo = compiled.topo
    pads = getattr(compiled, "pads", None)
    n = topo.n_procs

    def pad_of(phase: str) -> int:
        if pads is not None:
            return pads[phase]
        return compiled.pair_pad  # CompiledStandard

    out: Dict[str, object] = {}
    transpose: Dict[str, int] = {}
    checksum_total = 0
    for name, (n_slots, sends, recvs) in _phase_lists(compiled).items():
        pad = pad_of(name)
        out[f"{name}_padded"] = n * n_slots * pad * 4
        out[f"{name}_effective"] = 4 * sum(
            m.size for msgs in sends for m in msgs)
        out[f"{name}_max_rank_effective"] = 4 * max(
            (sum(m.size for m in msgs) for msgs in sends), default=0)
        transpose[f"{name}_padded"] = out[f"{name}_padded"]
        transpose[f"{name}_effective"] = 4 * sum(
            m.size for msgs in recvs for m in msgs)
        transpose[f"{name}_max_rank_effective"] = 4 * max(
            (sum(m.size for m in msgs) for msgs in recvs), default=0)
        if integrity != "off":
            chk = n * n_slots * 4
            out[f"{name}_checksum"] = chk
            transpose[f"{name}_checksum"] = chk
            checksum_total += chk
    if integrity != "off":
        out["checksum_total"] = checksum_total
        transpose["checksum_total"] = checksum_total
    if compiled.resolve_local_compute(local_compute) == "ell":
        out["buffer_gather_elems"] = 0
    elif pads is not None:
        out["buffer_gather_elems"] = pads["bnode"] + pads["boff"]
    else:
        out["buffer_gather_elems"] = compiled.buf_pad
    # the ELL layout each direction's autotuner picked (split width,
    # chunk rows, padding rows, slots and slots per entry)
    out.update(compiled.autotune.get("ell", {}))
    transpose.update(compiled.autotune.get("transpose", {}).get("ell", {}))
    out["transpose"] = transpose
    return out
