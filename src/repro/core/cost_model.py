"""Communication performance models (paper Sec. 3) and plan cost evaluation.

* Eq. (10): **max-rate** model for inter-node messages
      T = alpha + ppn*s / min(B_N, B_max + (ppn-1) * B_inj)
  (with the paper's Blue Waters measurements, Table 3)
* Eq. (11): postal model (ppn = 1 special case)
* Eq. (12): **intra-node** model  T_l = alpha_l + s_l / B_max_l  (Table 4)

Protocol selection (short / eager / rendezvous) follows MPI size thresholds;
the paper does not state Blue Waters' cutoffs, so we use MPICH-on-Gemini's
conventional 512 B (short) and 8 KiB (eager->rendezvous) — the benchmarks
expose them as parameters.

A TPU parameter set expresses the same two-level asymmetry for a v5e fleet
(ICI intra-pod vs DCI inter-pod); it feeds the NAP-vs-flat collective
choice and the §Roofline collective term.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

from repro.core.comm_graph import Message, NAPPlan, StandardPlan

SHORT_CUTOFF = 512        # bytes
EAGER_CUTOFF = 8 * 1024   # bytes


@dataclasses.dataclass(frozen=True)
class ProtocolParams:
    alpha: float   # start-up latency (s)
    b_inj: float   # per-node injection rate (B/s)
    b_max: float   # per-process achievable rate (B/s)
    b_n: float     # NIC peak (B/s)


@dataclasses.dataclass(frozen=True)
class LocalParams:
    alpha: float
    b_max: float


@dataclasses.dataclass(frozen=True)
class MachineParams:
    """Two-level machine: inter-node (max-rate) + intra-node (postal)."""

    name: str
    inter: Dict[str, ProtocolParams]  # keyed by protocol
    intra: Dict[str, LocalParams]
    short_cutoff: int = SHORT_CUTOFF
    eager_cutoff: int = EAGER_CUTOFF

    def protocol(self, nbytes: int) -> str:
        if nbytes <= self.short_cutoff:
            return "short"
        if nbytes <= self.eager_cutoff:
            return "eager"
        return "rend"


# Paper Table 3 (inter) and Table 4 (intra) — Blue Waters Cray XE / Gemini.
BLUE_WATERS = MachineParams(
    name="blue_waters",
    inter={
        "short": ProtocolParams(alpha=4.0e-6, b_inj=6.3e8, b_max=1.8e7, b_n=float("inf")),
        "eager": ProtocolParams(alpha=1.1e-5, b_inj=1.7e9, b_max=6.2e7, b_n=float("inf")),
        "rend": ProtocolParams(alpha=2.0e-5, b_inj=3.6e9, b_max=6.1e8, b_n=5.5e9),
    },
    intra={
        "short": LocalParams(alpha=1.3e-6, b_max=4.2e8),
        "eager": LocalParams(alpha=1.6e-6, b_max=7.4e8),
        "rend": LocalParams(alpha=4.2e-6, b_max=3.1e9),
    },
)

# TPU v5e-fleet analogue: "node" = pod slice (ICI), "network" = inter-pod DCI.
# ICI: ~5e10 B/s per link; DCI modelled at ~6.25e9 B/s per chip with ~10 us
# collective start-up; intra-pod start-up ~1 us.  Single protocol (bulk DMA).
TPU_V5E = MachineParams(
    name="tpu_v5e",
    inter={k: ProtocolParams(alpha=1.0e-5, b_inj=2.5e10, b_max=6.25e9, b_n=1.0e11)
           for k in ("short", "eager", "rend")},
    intra={k: LocalParams(alpha=1.0e-6, b_max=5.0e10) for k in ("short", "eager", "rend")},
)


def inter_node_time(nbytes: int, ppn: int, machine: MachineParams) -> float:
    """Eq. (10) max-rate model for one inter-node message of ``nbytes``."""
    p = machine.inter[machine.protocol(nbytes)]
    rate = min(p.b_n, p.b_max + (ppn - 1) * p.b_inj) if ppn > 1 else p.b_max
    if ppn == 1:
        return p.alpha + nbytes / p.b_max  # Eq. (11), postal model
    return p.alpha + (ppn * nbytes) / rate


def intra_node_time(nbytes: int, machine: MachineParams) -> float:
    """Eq. (12) intra-node postal model."""
    p = machine.intra[machine.protocol(nbytes)]
    return p.alpha + nbytes / p.b_max


# ---------------------------------------------------------------------------
# Plan costing: per-rank sum of message times, max over ranks per phase.
# Phases within an algorithm are sequential (Alg. 3 dependencies), messages
# of one rank within a phase are pipelined (Isend/Irecv): we charge
# max(sum of per-message alpha, per-rank serialisation) per the postal custom:
# each rank pays alpha per message plus bytes at the phase rate.
# ---------------------------------------------------------------------------

def _rank_phase_time(msgs: List[Message], machine: MachineParams, ppn: int,
                     inter: bool, bytes_per_val: int = 8) -> float:
    t = 0.0
    for m in msgs:
        nbytes = m.size * bytes_per_val
        t += inter_node_time(nbytes, ppn, machine) if inter else intra_node_time(nbytes, machine)
    return t


def standard_cost(plan: StandardPlan, machine: MachineParams,
                  bytes_per_val: int = 8) -> Dict[str, float]:
    topo = plan.topology
    inter_t, intra_t = [], []
    for r in range(topo.n_procs):
        inter_msgs = [m for m in plan.sends[r] if not topo.same_node(m.src, m.dst)]
        intra_msgs = [m for m in plan.sends[r] if topo.same_node(m.src, m.dst)]
        inter_t.append(_rank_phase_time(inter_msgs, machine, topo.ppn, True, bytes_per_val))
        intra_t.append(_rank_phase_time(intra_msgs, machine, topo.ppn, False, bytes_per_val))
    # standard SpMV sends everything at once: phases overlap fully.
    return {
        "inter": max(inter_t, default=0.0),
        "intra": max(intra_t, default=0.0),
        "total": max((a + b) for a, b in zip(inter_t, intra_t)) if inter_t else 0.0,
    }


def nap_cost(plan: NAPPlan, machine: MachineParams,
             bytes_per_val: int = 8) -> Dict[str, float]:
    topo = plan.topology
    phases = {
        "intra_init": (plan.local_init_sends, False),
        "inter": (plan.inter_sends, True),
        "intra_final": (plan.local_final_sends, False),
        "intra_full": (plan.local_full_sends, False),
    }
    out: Dict[str, float] = {}
    for name, (sends, is_inter) in phases.items():
        per_rank = [_rank_phase_time(sends[r], machine, topo.ppn, is_inter, bytes_per_val)
                    for r in range(topo.n_procs)]
        out[name] = max(per_rank, default=0.0)
    # Alg. 3 dependencies: init -> inter -> final are sequential; the fully
    # local exchange overlaps the inter-node phase (it has no dependencies).
    out["intra"] = out["intra_init"] + out["intra_final"] + out["intra_full"]
    out["total"] = (out["intra_init"] + max(out["inter"], out["intra_full"])
                    + out["intra_final"])
    return out


def multistep_cost(plan, machine: MachineParams,
                   bytes_per_val: int = 8) -> Dict[str, float]:
    """Cost of a :class:`repro.comm.multistep.MultistepPlan`: the NAP
    sub-plan's phase chain plus the direct exchange, which shares the
    network with (and so serialises against) the aggregated inter
    phase; the fully-local exchange still overlaps both."""
    out = nap_cost(plan.nap, machine, bytes_per_val)
    direct = standard_cost(plan.direct, machine, bytes_per_val)
    # every direct message crosses nodes, and the shared network
    # serialises it with the aggregated inter phase
    out["direct"] = direct["inter"]
    out["inter"] = out["inter"] + direct["inter"]
    out["total"] = (out["intra_init"] + max(out["inter"], out["intra_full"])
                    + out["intra_final"])
    return out


def compute_time(nnz: int, flop_rate: float = 2.0e9) -> float:
    """Local SpMV compute estimate: 2 flops per nonzero at an effective rate
    (memory-bound; ~2 GF/s/core is representative of Interlagos SpMV)."""
    return 2.0 * nnz / flop_rate


# ---------------------------------------------------------------------------
# Postal comm term for the comm-strategy autotuner (repro.comm)
# ---------------------------------------------------------------------------
#
# The models above cost individual MPI-style messages at their EFFECTIVE
# size.  The SPMD lowerings ship PADDED slots (every message in an
# all_to_all stretches to the phase's max message), so the comm-strategy
# chooser needs an alpha-beta term over the slot-granular padded bytes
# that ``repro.comm.cost.planned_traffic`` reports — effective bytes say
# what must move, padded bytes say what the program actually injects.

@dataclasses.dataclass(frozen=True)
class PostalParams:
    """Flat two-level postal model: per-message start-up alpha plus
    padded bytes at rate beta, separately for network (inter-node) and
    intra-node hops.  TPU v5e-ish defaults (DCI vs ICI)."""

    name: str = "tpu_v5e_postal"
    alpha_inter: float = 1.0e-5
    beta_inter: float = 6.25e9
    alpha_intra: float = 1.0e-6
    beta_intra: float = 5.0e10

    def signature(self) -> tuple:
        return dataclasses.astuple(self)

    @classmethod
    def calibrated(cls, walls: List[Dict],
                   name: str = "calibrated") -> "PostalParams":
        """Fit the postal constants from MEASURED per-phase exchange walls.

        ``walls`` — records with ``n_msgs`` (bottleneck-rank messages),
        ``nbytes`` (bottleneck-rank padded bytes), ``inter`` (bool level
        flag) and ``seconds``, exactly what
        :func:`repro.mesh.scaling.measure_phase_walls` emits.  Each level
        solves the least-squares system ``seconds ≈ alpha*n_msgs +
        nbytes/beta`` over its records; a level with fewer than two
        usable records — or a fit with a non-positive coefficient (noise
        at micro-benchmark scale) — keeps that constant's TPU_V5E
        default, so a partial calibration degrades gracefully instead of
        producing a nonsense machine model.
        """
        import numpy as np
        d = cls()
        fitted = {"inter": (d.alpha_inter, d.beta_inter),
                  "intra": (d.alpha_intra, d.beta_intra)}
        for level in ("inter", "intra"):
            recs = [w for w in walls
                    if bool(w["inter"]) == (level == "inter")
                    and w["n_msgs"] > 0 and w["seconds"] > 0]
            if len(recs) < 2:
                continue
            design = np.array([[r["n_msgs"], r["nbytes"]] for r in recs],
                              dtype=np.float64)
            t = np.array([r["seconds"] for r in recs], dtype=np.float64)
            coef, *_ = np.linalg.lstsq(design, t, rcond=None)
            alpha, inv_beta = (float(coef[0]), float(coef[1]))
            da, db = fitted[level]
            fitted[level] = (alpha if alpha > 0 else da,
                             1.0 / inv_beta if inv_beta > 0 else db)
        return cls(name=name,
                   alpha_inter=fitted["inter"][0],
                   beta_inter=fitted["inter"][1],
                   alpha_intra=fitted["intra"][0],
                   beta_intra=fitted["intra"][1])


TPU_V5E_POSTAL = PostalParams()


def postal_phase_time(n_msgs: int, nbytes: float, inter: bool,
                      params: PostalParams = TPU_V5E_POSTAL) -> float:
    """alpha-beta time for one exchange phase at one rank: ``n_msgs``
    start-ups plus ``nbytes`` (padded) at the level's rate."""
    if n_msgs == 0:
        return 0.0
    alpha, beta = (params.alpha_inter, params.beta_inter) if inter \
        else (params.alpha_intra, params.beta_intra)
    return n_msgs * alpha + nbytes / beta


def postal_comm_time(traffic: Dict, params: PostalParams = TPU_V5E_POSTAL
                     ) -> Dict[str, float]:
    """Modeled seconds for one exchange schedule.

    ``traffic`` is a :func:`repro.comm.cost.planned_traffic` payload.
    Phases run sequentially (the lowerings are bulk-synchronous); each
    phase is charged at its bottleneck rank using the slot-granular
    padded bytes plus the integrity side-channel when armed.
    """
    out: Dict[str, float] = {}
    total = 0.0
    for name, ph in traffic["phases"].items():
        t = postal_phase_time(
            ph["max_rank_msgs"],
            ph["max_rank_padded_bytes"] + ph["checksum_bytes"],
            ph["inter"], params)
        out[name] = t
        total += t
    out["total"] = total
    return out


# ---------------------------------------------------------------------------
# Local-compute format autotuner (BSR vs ELL vs COO)
# ---------------------------------------------------------------------------
#
# The shared-memory SpMV literature's core lesson — no single sparse format
# wins across structures — applied to the rank-local compute of the
# distributed SpMV.  Each candidate is scored with a two-term roofline
#
#     t = max(padded_flops / unit_rate, bytes_moved / hbm_bw)
#
# where "padded" counts the work the static layout actually issues (dense
# (bm, bn) tiles for BSR, kmax-padded rows for ELL, nnz-padded triples for
# COO), and the unit rate reflects which hardware unit executes it: BSR
# feeds the MXU, ELL the VPU (vector gather + FMA), COO an effective
# scatter/segment-sum rate that is brutally low on TPU.  The SPMD program
# is bulk-synchronous, so the per-call decision uses stats maxed over
# ranks; per-rank estimates are still recorded for diagnostics.


@dataclasses.dataclass(frozen=True)
class LocalComputeParams:
    """Effective unit rates for the local-compute roofline (f32, TPU-ish).

    Absolute values matter less than ratios: MXU >> VPU >> scatter, and
    everything can be HBM-bound.  ``ell_hbm_budget`` bounds the device
    bytes the ELL product may hold (:func:`ell_resident_bytes`): half of
    a v5e chip's 16 GiB, leaving the rest to the exchange buffers, the
    operand and the other direction's format arrays.
    ``ell_combine_slots`` prices one chunk partial of a row-split ELL
    (its sorted ``segment_sum`` into the result) in slots of the slot
    loop.
    """

    name: str = "tpu_v5e_local"
    mxu_flops: float = 5.0e13     # dense-block matmul rate
    vpu_flops: float = 2.0e12     # vectorised gather+FMA rate
    scatter_flops: float = 4.0e9  # segment_sum / scalar scatter-add rate
    hbm_bw: float = 8.1e11        # HBM bandwidth
    ell_hbm_budget: int = 8 * 2**30  # max device bytes of one ELL product
    # one combined partial, in slots: a sorted segment_sum partial took
    # 8.9 to 9.4 ns and a slot 6.8 to 7.3 ns on a TPU v5e (ratios 1.23 to
    # 1.39 over seven shapes: the scale-20 Graph500 graph at K = 4 to 32,
    # the transposed 2^20 x 25 random matrix at K = 8 to 32; PERF.md)
    ell_combine_slots: float = 1.3

    def signature(self) -> tuple:
        return dataclasses.astuple(self)


TPU_V5E_LOCAL = LocalComputeParams()

LOCAL_FORMATS = ("bsr", "ell", "coo")


def _tiled_bytes(n: int, width: int) -> int:
    """Device bytes of a 4-byte ``[n, width]`` working array on a TPU.

    The compiler lays the long axis on the 128 lanes and the short one on
    the sublanes: a width of 1 packs densely (tile (1, 128)), any other
    width pads to the 8-row sublane tile.
    """
    sub = 1 if width == 1 else -(-width // 8) * 8
    return 4 * sub * (-(-n // 128) * 128)


def ell_resident_bytes(rows: int, kmax: int, n_x: int, nv: int,
                       chunks: int = 0) -> int:
    """Device bytes the ELL product holds inside the shard_map program,
    tile padding included.

    cols + vals ``[rows, kmax]`` twice: as staged (tile (1, 128), kmax
    unpadded) and as the slot loop's sublane-padded working copy, which
    the compiler makes by relayout; the x segments and their concat
    (``[n_x, nv]`` each, where ``n_x`` is the domain the column ids
    index: the received buffers for a composed forward program); the
    ``[rows, nv]`` result.  A row-split layout of ``chunks`` chunk rows
    (``kmax`` is then the split width) holds cols + vals over its chunk
    rows instead, and besides them the ``[chunks, nv]`` partials and the
    int32 ``chunk_row`` map.  The slot loop keeps no gather temporary
    (see ``kernels/ell_spmv/kernel.py``).  ``tests/test_tpu_compile.py``
    checks this against the compiler's own memory analysis at 2^20 rows.
    """
    slot_rows = chunks or rows
    staged = 4 * kmax * (-(-slot_rows // 128) * 128)
    held = (2 * staged + 2 * _tiled_bytes(slot_rows, kmax)
            + 2 * _tiled_bytes(n_x, nv) + _tiled_bytes(rows, nv))
    if chunks:
        held += _tiled_bytes(chunks, nv) + 4 * (-(-chunks // 128) * 128)
    return held


def _ell_time(rows: int, width: int, chunks: int, n_x: int, nv: int,
              params: LocalComputeParams) -> float:
    """Modeled seconds of one ELL layout: ``rows`` rows of ``width``
    slots (``chunks == 0``), or ``chunks`` chunk rows of ``width`` slots
    and their combine."""
    if ell_resident_bytes(rows, width, n_x, nv, chunks) \
            > params.ell_hbm_budget:
        return float("inf")  # the product does not fit the device
    slots = (chunks or rows) * width + params.ell_combine_slots * chunks
    return max(2.0 * slots * nv / params.vpu_flops,
               (slots * 8 + n_x * 4 * nv + 4 * rows * nv) / params.hbm_bw)


def choose_ell_split(stats: Dict[str, object],
                     params: LocalComputeParams = TPU_V5E_LOCAL,
                     nv: int = 1) -> Tuple[int, int, float]:
    """``(width, chunk rows, modeled seconds)`` of the cheapest ELL
    layout: the unsplit one (``ell_kmax`` slots over ``rows_pad`` rows,
    no combine) or a split ``[K, chunk rows]`` of ``stats["ell_splits"]``
    (the powers of two below ``ell_kmax``).  Ties keep the unsplit
    layout; where none fits the device, the one that holds least wins."""
    rows, kmax = int(stats["rows_pad"]), int(stats["ell_kmax"])
    n_x = int(stats.get("ell_n_x", stats["n_x"]))
    layouts = [(kmax, rows, 0)] + [(int(k), int(c), int(c))
                                   for k, c in stats.get("ell_splits", ())]

    def cost(layout):
        t = _ell_time(rows, layout[0], layout[2], n_x, nv, params)
        return t, (ell_resident_bytes(rows, layout[0], n_x, nv, layout[2])
                   if t == float("inf") else 0)

    best = min(layouts, key=cost)
    return best[0], best[1], cost(best)[0]


def local_format_times(stats: Dict[str, float],
                       params: LocalComputeParams = TPU_V5E_LOCAL,
                       nv: int = 1) -> Dict[str, float]:
    """Per-format modeled seconds for one local SpMV application.

    ``stats`` (all padded to the SPMD max over ranks, per-rank element
    counts — see ``spmv_jax._autotune_stats``):
      rows_pad   output rows
      n_x        packed x length (v_loc + on-node + off-node buffers)
      nnz_pad    COO triples incl. cross-rank padding
      bsr_blocks padded (bm, bn) tiles incl. cross-rank kmax alignment
      bm, bn     block shape
      ell_kmax   padded ELL slots per row (cross-rank max)
      ell_n_x    x length the ELL product reads: the received domain
                 its composed column ids index (optional; n_x if absent)
      ell_splits ``[K, chunk rows]`` of each row-split ELL layout, chunk
                 rows padded to the max over ranks (optional; the ELL
                 time is that of the best layout, :func:`choose_ell_split`)
    """
    bm, bn = int(stats["bm"]), int(stats["bn"])
    rows, n_x = stats["rows_pad"], stats["n_x"]
    out_b = 4 * rows * nv

    blocks = stats["bsr_blocks"]
    bsr_flops = 2.0 * blocks * bm * bn * nv
    bsr_bytes = blocks * (bm * bn * 4 + bn * 4 * nv) + out_b
    times = {"bsr": max(bsr_flops / params.mxu_flops,
                        bsr_bytes / params.hbm_bw)}

    times["ell"] = choose_ell_split(stats, params, nv)[2]

    nnz = stats["nnz_pad"]
    coo_flops = 2.0 * nnz * nv
    coo_bytes = nnz * 12 + nnz * 4 * nv + out_b
    times["coo"] = max(coo_flops / params.scatter_flops,
                       coo_bytes / params.hbm_bw)
    return times


def choose_local_format(stats: Dict[str, float],
                        params: LocalComputeParams = TPU_V5E_LOCAL,
                        nv: int = 1) -> str:
    """argmin-time format for the given layout stats."""
    times = local_format_times(stats, params, nv=nv)
    return min(LOCAL_FORMATS, key=lambda f: times[f])
