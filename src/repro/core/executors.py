"""Pluggable executor registry behind the :class:`repro.api.NapOperator`.

An *executor* binds one (backend, method) pair to a concrete matrix +
layout and exposes the four things the operator front-end needs:

* ``forward(v, donate=False)``  — global ``A @ v`` (1-RHS or multi-RHS)
* ``transpose(u, donate=False)``— global ``A.T @ u`` against the SAME plan
* ``stats()`` / ``cost(machine)`` / ``autotune_report()`` — plan-level
  message statistics, modeled comm time, and the local-format verdict
  (for BOTH directions — the transpose verdict rides along under
  ``"transpose"`` / ``"transpose_resolved"``).

Every executor is built over TWO partitions: ``row_part`` (output
ownership, ``a.shape[0]`` rows) and ``col_part`` (x ownership,
``a.shape[1]`` entries).  Square single-partition operators pass the same
object twice; rectangular AMG P / R operators separate them.  The forward
direction consumes a ``col_part``-owned operand and yields a
``row_part``-owned result; the transpose swaps the two.

Backends registered here:

* ``("shardmap", "nap" | "standard" | "multistep")`` — the jitted SPMD
  executors of :mod:`repro.core.spmv_jax`, sharing ONE packed-x path
  (:func:`pack_vector` / :func:`unpack_vector`) for forward and
  transpose, with lazy per-direction compilation (the transpose program
  is only built when ``op.T`` is first applied).
* ``("simulate", "nap" | "standard" | "multistep")`` — the exact numpy
  message-passing simulators (float64 correctness oracles).
* ``("moe", "flat" | "nap" | "auto")`` — MoE token->expert dispatch over
  a CSR routing matrix ``R [E, T]``: forward is the weighted
  dispatch-sum ``R @ X`` with every x payload quantized to
  ``spec.wire_dtype`` on the wire (f64 accumulation on receive),
  transpose the weighted combine; ``"auto"`` resolves flat-vs-nap PER
  DIRECTION from the modeled injected inter-pod bytes
  (:func:`repro.moe.plan.choose_dispatch`).  Built on the simulate
  mailboxes, so integrity checksums run over the QUANTIZED words.

The comm-strategy subsystem (:mod:`repro.comm`) treats the method as a
pluggable exchange strategy: ``repro.api.operator(comm=...)`` maps a
strategy name onto the method here, and ``comm="auto"`` resolves one per
operator (and per direction) from the modeled injected traffic.

Future backends — a true-TPU Mosaic lowering, the collective-permute
overlap executor of the roadmap's open item (d) — plug in with
``@register_executor("mosaic", "nap")`` and become reachable from every
call site through ``repro.api.operator(..., backend="mosaic")`` without
touching the operator or any ported caller.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Tuple

import numpy as np

from repro.comm.multistep import multistep_stats
from repro.core.comm_graph import (build_nap_plan, build_standard_plan,
                                   nap_stats, standard_stats)
from repro.core.cost_model import (LocalComputeParams, MachineParams,
                                   TPU_V5E_LOCAL, multistep_cost, nap_cost,
                                   standard_cost)
from repro.core.integrity import (IntegrityError, IntegrityState, MessageFault,
                                  SimWire)
from repro.core.partition import RowPartition
from repro.core.spans import span
from repro.core.spmv import (simulate_nap_spmv, simulate_nap_spmv_transpose,
                             simulate_standard_spmv,
                             simulate_standard_spmv_transpose)
from repro.core.topology import Topology

# NOTE: repro.core.spmv_jax (and thus jax) is imported lazily inside the
# shardmap executors — the simulate backend stays importable and usable on
# a jax-free numpy installation (repro.core.integrity is numpy-only).

#: strategy -> (message stats, modeled comm cost) of its comm-graph plan
_PLAN_MODELS = {"nap": (nap_stats, nap_cost),
                "multistep": (multistep_stats, multistep_cost),
                "standard": (standard_stats, standard_cost)}


def _plan_stats(method: str, plan) -> Dict[str, object]:
    return {f"messages_{k}": v
            for k, v in _PLAN_MODELS[method][0](plan).items()}


@dataclasses.dataclass(frozen=True)
class OperatorSpec:
    """Everything an executor factory needs beyond (a, row/col parts, topo)."""

    method: str = "nap"
    backend: str = "shardmap"
    local_compute: str = "auto"
    pairing: str = "aligned"
    block_shape: Tuple[int, int] = (8, 128)
    nv_block: int = 128
    cache: bool = True
    tuner: LocalComputeParams = TPU_V5E_LOCAL
    integrity: str = "off"          # "off" | "detect" | "recover"
    # duplication threshold for method="multistep" ("auto" or int >= 1);
    # ignored by the single-strategy methods
    threshold: object = "auto"
    # wire payload encoding for the moe dispatch backend ("f32" | "bf16" |
    # "fp8_e4m3"); "f32" is the identity codec — bit-for-bit today's path
    wire_dtype: str = "f32"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[Tuple[str, str], Callable] = {}


def register_executor(backend: str, method: str):
    """Class/factory decorator: makes ``backend``/``method`` constructible
    through :func:`bind_executor` (and thus ``repro.api.operator``).  A
    factory signature is ``factory(a, row_part, col_part, topo, spec,
    mesh=None)``."""

    def deco(factory):
        _REGISTRY[(backend, method)] = factory
        return factory

    return deco


def available_executors() -> List[Tuple[str, str]]:
    return sorted(_REGISTRY)


def bind_executor(backend: str, method: str, a, row_part: RowPartition,
                  col_part: RowPartition, topo: Topology, spec: OperatorSpec,
                  mesh=None):
    """Instantiate the registered executor for (backend, method)."""
    try:
        factory = _REGISTRY[(backend, method)]
    except KeyError:
        avail = ", ".join(f"{b}/{m}" for b, m in available_executors())
        raise ValueError(
            f"no executor registered for backend={backend!r} "
            f"method={method!r}; available: {avail}") from None
    return factory(a, row_part, col_part, topo, spec, mesh=mesh)


def check_operand(n: int, v: np.ndarray) -> np.ndarray:
    """Shared operand validation: a global [n] vector or [n, nv] multivector."""
    v = np.asarray(v)
    if v.shape[:1] != (n,) or v.ndim > 2:
        raise ValueError(f"operand must be [{n}] or [{n}, nv], got {v.shape}")
    return v


# ---------------------------------------------------------------------------
# shard_map backend (shared packed-x path, lazy per-direction compile)
# ---------------------------------------------------------------------------

class _ShardmapExecutor:
    """Common shard_map plumbing: one pack/unpack path for every method
    and direction; the forward/transpose programs build lazily and are
    memoized per direction.  Forward packs the operand by ``col_part``
    (cols_pad) and unpacks by ``row_part``; transpose swaps both."""

    backend = "shardmap"

    def __init__(self, a, row_part: RowPartition, col_part: RowPartition,
                 topo: Topology, spec: OperatorSpec, mesh=None):
        self.a, self.topo, self.spec = a, topo, spec
        self.row_part, self.col_part = row_part, col_part
        self._mesh = mesh
        self._compiled = None
        self._runs: Dict[str, Callable] = {}
        self._integrity = (IntegrityState(spec.integrity, topo,
                                          type(self).method)
                           if spec.integrity != "off" else None)

    # -- lazy resources ----------------------------------------------------
    @property
    def mesh(self):
        if self._mesh is None:
            # memoized per (n_nodes, ppn) — every executor on the same
            # layout shares one mesh object (repro.mesh.buffers)
            from repro.mesh.buffers import mesh_for
            self._mesh = mesh_for(self.topo)
        return self._mesh

    @property
    def compiled(self):
        if self._compiled is None:
            self._compiled = self._compile()
        return self._compiled

    def _compile(self):
        from repro.core.spmv_jax import compile_plan
        return compile_plan(self.a, self.row_part, self.topo,
                            comm=self.method,
                            block_shape=self.spec.block_shape,
                            cache=self.spec.cache,
                            local_compute=self.spec.local_compute,
                            tuner=self.spec.tuner, col_part=self.col_part,
                            threshold=self.spec.threshold)

    def _build(self, direction: str) -> Callable:
        from repro.core.spmv_jax import forward_program, transpose_program
        kw = dict(local_compute=self.spec.local_compute,
                  nv_block=self.spec.nv_block)
        if self._integrity is not None:
            kw.update(integrity=True, fault_fetch=self._integrity.fetch_spec)
        build = (forward_program if direction == "forward"
                 else transpose_program)
        return build(self.compiled, self.mesh, **kw)

    def _run(self, direction: str) -> Callable:
        if direction not in self._runs:
            self._runs[direction] = self._build(direction)
        return self._runs[direction]

    # -- the ONE packed-x path shared by all shard_map executors -----------
    def _apply(self, direction: str, v: np.ndarray, donate: bool) -> np.ndarray:
        """One apply, under the host span ``repro.apply`` with a child
        span per step: ``repro.pack``, ``repro.stage`` and
        ``repro.dispatch`` (in the run callable), ``repro.fetch`` and
        ``repro.unpack``."""
        from repro.core.spmv_jax import pack_vector, unpack_vector
        from repro.mesh.buffers import fetch_mesh_array

        with span("repro.apply"):
            c = self.compiled
            if direction == "forward":
                in_part, in_pad = self.col_part, c.cols_pad
                out_part = self.row_part
                v = check_operand(self.a.shape[1], v)
            else:
                in_part, in_pad = self.row_part, c.rows_pad
                out_part = self.col_part
                v = check_operand(self.a.shape[0], v)
            with span("repro.pack"):
                shards = pack_vector(v, in_part, self.topo, in_pad)
            if self._integrity is not None:
                w = self._apply_verified(direction, shards)
            else:
                w = self._run(direction)(shards, donate=donate)
            # fetch_mesh_array == np.asarray single-process; under a
            # multi-process mesh it gathers the global shards bitwise-exactly
            with span("repro.fetch"):
                w = fetch_mesh_array(w)
            with span("repro.unpack"):
                return unpack_vector(w, out_part, self.topo)

    def _apply_verified(self, direction: str, shards) -> np.ndarray:
        """Integrity path: arm any scripted faults, run the instrumented
        program (which also returns the wire-checksum and ABFT aux
        outputs), verify on the host, and — under ``"recover"`` — retry
        the apply from the RETAINED packed shards with the fault consumed
        (never donated), which reproduces the fault-free result
        bit-for-bit.  Persistent mismatches raise after the retry."""
        from repro.mesh.buffers import fetch_mesh_array
        st = self._integrity
        c = self.compiled
        n_terms = c.rows_pad + c.packed_x_len
        st.counters["applies"] += 1
        st.arm(direction)
        try:
            w, chk, abft = self._run(direction)(shards, donate=False)
            with span("repro.verify"):
                mism = st.verify(fetch_mesh_array(chk),
                                 fetch_mesh_array(abft), direction, n_terms)
            if not mism:
                return w
            if st.mode == "detect":
                raise IntegrityError(
                    f"{len(mism)} integrity mismatch(es) on {direction} "
                    f"apply: " + "; ".join(str(m) for m in mism), mism)
            # recover: scripted faults were consumed at arm time, so the
            # retry runs the identical program on identical inputs clean.
            st.counters["retries"] += 1
            st.disarm()
            w, chk, abft = self._run(direction)(shards, donate=False)
            with span("repro.verify"):
                mism = st.verify(fetch_mesh_array(chk),
                                 fetch_mesh_array(abft), direction, n_terms)
            if mism:
                raise IntegrityError(
                    f"integrity mismatch persisted through retry on "
                    f"{direction} apply: " + "; ".join(str(m) for m in mism),
                    mism)
            st.counters["recovered"] += 1
            return w
        finally:
            st.disarm()

    # -- integrity surface -------------------------------------------------
    def queue_fault(self, fault: MessageFault) -> None:
        """Script a deterministic message fault for the NEXT matching
        apply (fires once; requires ``integrity != "off"``)."""
        if self._integrity is None:
            raise ValueError("fault injection requires integrity='detect' "
                             "or 'recover' on the operator")
        self._integrity.queue_fault(fault)

    def integrity_report(self) -> Dict[str, object]:
        if self._integrity is None:
            return {"mode": "off"}
        return self._integrity.report()

    def forward(self, v: np.ndarray, donate: bool = False) -> np.ndarray:
        return self._apply("forward", v, donate)

    def transpose(self, u: np.ndarray, donate: bool = False) -> np.ndarray:
        return self._apply("transpose", u, donate)

    def swap_values(self, a_new) -> None:
        """Hot-swap matrix VALUES (sparsity must be identical): the
        compiled plan rebuilds its value arrays in place and every
        already-built direction program picks them up on the next call
        WITHOUT retracing — value arrays are per-call jit arguments
        (see :func:`repro.core.spmv_jax._plan_arg_fetch`)."""
        self.compiled.swap_values(a_new)
        self.a = a_new

    def trace_counts(self) -> Dict[str, int]:
        """Program (re)trace count per built direction; the serve plan
        cache asserts these stay flat across hot value swaps."""
        return {d: run.n_traces() for d, run in self._runs.items()}

    @property
    def local_compute(self) -> str:
        return self.compiled.resolve_local_compute(self.spec.local_compute)

    @property
    def transpose_local_compute(self) -> str:
        """Resolved transpose-direction format (the argmin of ell/coo from
        the compile-time transpose autotuner unless explicitly pinned —
        transposed Pallas BSR kernels remain a roadmap item)."""
        return self.compiled.resolve_transpose_local_compute(
            self.spec.local_compute)

    def autotune_report(self) -> Dict[str, object]:
        return dict(self.compiled.autotune,
                    resolved=self.local_compute,
                    transpose_resolved=self.transpose_local_compute,
                    requested=self.spec.local_compute)

    def stats(self) -> Dict[str, object]:
        from repro.core.spmv_jax import padded_traffic
        out = _plan_stats(self.method, self.compiled.plan)
        out.update(padded_traffic(self.compiled,
                                  integrity=self.spec.integrity,
                                  local_compute=self.spec.local_compute))
        return out

    def cost(self, machine: MachineParams) -> Dict[str, float]:
        return _PLAN_MODELS[self.method][1](self.compiled.plan, machine)


@register_executor("shardmap", "nap")
class NapShardmapExecutor(_ShardmapExecutor):
    method = "nap"


@register_executor("shardmap", "multistep")
class MultistepShardmapExecutor(_ShardmapExecutor):
    method = "multistep"


@register_executor("shardmap", "standard")
class StandardShardmapExecutor(_ShardmapExecutor):
    method = "standard"


# ---------------------------------------------------------------------------
# Simulator backend (exact message passing, float64 oracle)
# ---------------------------------------------------------------------------

class _SimulateExecutor:
    """Exact numpy message-passing backend; multi-RHS loops per column."""

    backend = "simulate"
    local_compute = "numpy"
    transpose_local_compute = "numpy"

    def __init__(self, a, row_part: RowPartition, col_part: RowPartition,
                 topo: Topology, spec: OperatorSpec, mesh=None):
        self.a, self.topo, self.spec = a, topo, spec
        self.row_part, self.col_part = row_part, col_part
        self._plan = None
        self._integrity = (IntegrityState(spec.integrity, topo,
                                          type(self).method)
                           if spec.integrity != "off" else None)

    @property
    def plan(self):
        if self._plan is None:
            self._plan = self._build_plan()
        return self._plan

    def _columnwise(self, fn, v: np.ndarray, n: int) -> np.ndarray:
        v = np.asarray(check_operand(n, v), dtype=np.float64)
        if v.ndim == 1:
            return fn(v)
        return np.stack([fn(v[:, i]) for i in range(v.shape[1])], axis=1)

    def forward(self, v: np.ndarray, donate: bool = False) -> np.ndarray:
        if self._integrity is None:
            return self._columnwise(lambda col: self._forward(col), v,
                                    self.a.shape[1])
        return self._forward_verified(v)

    def _forward_verified(self, v: np.ndarray) -> np.ndarray:
        """Integrity path over the numpy mailboxes: one :class:`SimWire`
        spans the whole (possibly multi-RHS) apply; a scripted fault
        fires on its first matching message.  Detect raises, recover
        re-runs clean (faults are consumed) — exact by construction."""
        st = self._integrity
        st.counters["applies"] += 1
        wire = SimWire(self.topo, st.take_pending("forward"))
        out = self._columnwise(lambda col: self._forward(col, wire=wire), v,
                               self.a.shape[1])
        mism = st.note_sim(wire)
        if not mism:
            return out
        if st.mode == "detect":
            raise IntegrityError(
                f"{len(mism)} integrity mismatch(es) on forward apply: "
                + "; ".join(str(m) for m in mism), mism)
        st.counters["retries"] += 1
        out = self._columnwise(lambda col: self._forward(col), v,
                               self.a.shape[1])
        st.counters["recovered"] += 1
        return out

    def transpose(self, u: np.ndarray, donate: bool = False) -> np.ndarray:
        st = self._integrity
        if st is not None:
            if any(f.direction in ("any", "transpose") for f in st.pending):
                raise NotImplementedError(
                    "message-fault injection on the transpose direction is "
                    "shardmap-only: the simulate transposes reverse the "
                    "exchange phases algebraically without mailboxes")
            st.counters["applies"] += 1
        return self._columnwise(lambda col: self._transpose(col), u,
                                self.a.shape[0])

    # -- integrity surface -------------------------------------------------
    def queue_fault(self, fault: MessageFault) -> None:
        if self._integrity is None:
            raise ValueError("fault injection requires integrity='detect' "
                             "or 'recover' on the operator")
        self._integrity.queue_fault(fault)

    def integrity_report(self) -> Dict[str, object]:
        if self._integrity is None:
            return {"mode": "off"}
        return self._integrity.report()

    def swap_values(self, a_new) -> None:
        """Hot-swap matrix VALUES; the comm plan is pure structure and is
        reused as-is.  Same structural contract as the shardmap backend."""
        old = self.a
        if (tuple(a_new.shape) != tuple(old.shape)
                or not np.array_equal(a_new.indptr, old.indptr)
                or not np.array_equal(a_new.indices, old.indices)):
            raise ValueError(
                "swap_values requires an identical sparsity structure "
                "(same shape, indptr, indices); rebuild the operator for "
                "a structural change")
        self.a = a_new

    def trace_counts(self) -> Dict[str, int]:
        return {}   # nothing is traced: exact numpy execution

    def stats(self) -> Dict[str, object]:
        return _plan_stats(self.method, self.plan)

    def cost(self, machine: MachineParams) -> Dict[str, float]:
        return _PLAN_MODELS[self.method][1](self.plan, machine)

    def autotune_report(self) -> Dict[str, object]:
        return {"resolved": self.local_compute,
                "transpose_resolved": self.transpose_local_compute,
                "note": "simulate backend runs exact numpy local compute in "
                        "both directions; the format autotuner applies to "
                        "shardmap only"}


@register_executor("simulate", "nap")
class NapSimulateExecutor(_SimulateExecutor):
    method = "nap"

    def _build_plan(self):
        return build_nap_plan(self.a.indptr, self.a.indices, self.row_part,
                              self.topo, pairing=self.spec.pairing,
                              col_part=self.col_part)

    def _forward(self, v, wire=None):
        return simulate_nap_spmv(self.a, v, self.plan, wire=wire)

    def _transpose(self, u):
        return simulate_nap_spmv_transpose(self.a, u, self.plan)


@register_executor("simulate", "multistep")
class MultistepSimulateExecutor(_SimulateExecutor):
    method = "multistep"

    def _build_plan(self):
        from repro.comm.multistep import build_multistep_plan
        return build_multistep_plan(self.a.indptr, self.a.indices,
                                    self.row_part, self.topo,
                                    pairing=self.spec.pairing,
                                    col_part=self.col_part,
                                    threshold=self.spec.threshold)

    def _forward(self, v, wire=None):
        from repro.comm.simulate import simulate_multistep_spmv
        return simulate_multistep_spmv(self.a, v, self.plan, wire=wire)

    def _transpose(self, u):
        from repro.comm.simulate import simulate_multistep_spmv_transpose
        return simulate_multistep_spmv_transpose(self.a, u, self.plan)


@register_executor("simulate", "standard")
class StandardSimulateExecutor(_SimulateExecutor):
    method = "standard"

    def _build_plan(self):
        return build_standard_plan(self.a.indptr, self.a.indices,
                                   self.row_part, self.topo,
                                   col_part=self.col_part)

    def _forward(self, v, wire=None):
        return simulate_standard_spmv(self.a, v, self.plan, wire=wire)

    def _transpose(self, u):
        return simulate_standard_spmv_transpose(self.a, u, self.plan)


# ---------------------------------------------------------------------------
# MoE dispatch backend (routing matrix over the simulate mailboxes,
# quantized wire payloads; see repro/moe/README.md)
# ---------------------------------------------------------------------------

class _MoeDispatchExecutor(_SimulateExecutor):
    """Shared moe-dispatch plumbing over the numpy mailboxes.

    Differences from the plain simulate backend:

    * every forward apply threads a wire from
      :func:`repro.moe.wire.make_wire` — narrow ``spec.wire_dtype``
      payloads are quantized at each send and f64-accumulated on
      receive; ``"f32"`` without integrity threads no wire at all
      (bit-identical to the plain simulators);
    * the transpose (weighted combine) quantizes the y operand once
      before the algebraic reverse route — one combine hop in the
      model; the in-graph nap path pays up to 2
      (:func:`repro.moe.wire.wire_error_bound` budgets both);
    * ``integrity="detect"|"recover"`` checksums the QUANTIZED words
      (idempotent re-encode on the receive side), so scripted faults on
      quantized messages attribute and retry exactly like f32 ones —
      and the recover retry re-runs with a CLEAN quantizing wire, so
      the retried result still reflects the wire encoding;
    * ``stats()`` adds the per-direction dispatch/combine injected
      byte accounting at the wire width.
    """

    backend = "moe"

    def _wire(self, faults=()):
        from repro.moe.wire import make_wire
        return make_wire(self.topo, self.spec.wire_dtype, faults,
                         force=self._integrity is not None)

    def forward(self, v: np.ndarray, donate: bool = False) -> np.ndarray:
        if self._integrity is None:
            wire = self._wire()
            return self._columnwise(lambda col: self._forward(col, wire=wire),
                                    v, self.a.shape[1])
        return self._forward_verified(v)

    def _forward_verified(self, v: np.ndarray) -> np.ndarray:
        st = self._integrity
        st.counters["applies"] += 1
        wire = self._wire(st.take_pending("forward"))
        out = self._columnwise(lambda col: self._forward(col, wire=wire), v,
                               self.a.shape[1])
        mism = st.note_sim(wire)
        if not mism:
            return out
        if st.mode == "detect":
            raise IntegrityError(
                f"{len(mism)} integrity mismatch(es) on forward apply: "
                + "; ".join(str(m) for m in mism), mism)
        st.counters["retries"] += 1
        clean = self._wire()
        out = self._columnwise(lambda col: self._forward(col, wire=clean), v,
                               self.a.shape[1])
        st.counters["recovered"] += 1
        return out

    def transpose(self, u: np.ndarray, donate: bool = False) -> np.ndarray:
        from repro.moe.wire import quantize_np
        u = np.asarray(check_operand(self.a.shape[0], u), dtype=np.float64)
        return super().transpose(quantize_np(u, self.spec.wire_dtype), donate)

    def stats(self) -> Dict[str, object]:
        from repro.moe.plan import dispatch_traffic
        out = {f"messages_{k}": v for k, v in self._plan_stats().items()}
        for direction, name in (("forward", "dispatch"),
                                ("transpose", "combine")):
            t = dispatch_traffic(self.plan, wire_dtype=self.spec.wire_dtype,
                                 nv=1, direction=direction,
                                 integrity=self.spec.integrity)
            out[f"{name}_injected_inter_bytes"] = t["injected_inter_bytes"]
            out[f"{name}_injected_intra_bytes"] = t["injected_intra_bytes"]
            out["bytes_per_val"] = t["bytes_per_val"]
        out["wire_dtype"] = self.spec.wire_dtype
        return out

    def autotune_report(self) -> Dict[str, object]:
        rep = super().autotune_report()
        rep.update(wire_dtype=self.spec.wire_dtype,
                   dispatch_resolved=type(self).method,
                   combine_resolved=type(self).method)
        return rep


@register_executor("moe", "flat")
class FlatMoeDispatchExecutor(_MoeDispatchExecutor):
    """Algorithm-1 analogue: every (token, owning-chip) payload crosses
    the flat pairwise exchange directly."""

    method = "flat"

    def _build_plan(self):
        return build_standard_plan(self.a.indptr, self.a.indices,
                                   self.row_part, self.topo,
                                   col_part=self.col_part)

    def _forward(self, v, wire=None):
        return simulate_standard_spmv(self.a, v, self.plan, wire=wire)

    def _transpose(self, u):
        return simulate_standard_spmv_transpose(self.a, u, self.plan)

    def _plan_stats(self):
        return standard_stats(self.plan)

    def cost(self, machine: MachineParams) -> Dict[str, float]:
        return standard_cost(self.plan, machine)


@register_executor("moe", "nap")
class NapMoeDispatchExecutor(_MoeDispatchExecutor):
    """NAPSpMV three-step dispatch: a token bound for several experts on
    one remote pod crosses the inter-pod boundary ONCE (the paper's
    E(n, m) dedup), via intra-gather -> one aggregated inter-pod
    exchange -> intra-scatter; the combine reverses every message."""

    method = "nap"

    def _build_plan(self):
        return build_nap_plan(self.a.indptr, self.a.indices, self.row_part,
                              self.topo, pairing=self.spec.pairing,
                              col_part=self.col_part)

    def _forward(self, v, wire=None):
        return simulate_nap_spmv(self.a, v, self.plan, wire=wire)

    def _transpose(self, u):
        return simulate_nap_spmv_transpose(self.a, u, self.plan)

    def _plan_stats(self):
        return nap_stats(self.plan)

    def cost(self, machine: MachineParams) -> Dict[str, float]:
        return nap_cost(self.plan, machine)


@register_executor("moe", "auto")
class AutoMoeDispatchExecutor:
    """Per-direction flat-vs-nap resolution for MoE dispatch.

    Binds :func:`repro.moe.plan.choose_dispatch` over the routing
    structure once, then delegates: ``forward`` runs the chosen dispatch
    executor, ``transpose`` the chosen combine executor (they may
    differ, mirroring ``comm="auto"``'s per-direction split).  The
    candidate plans are built once and shared with the sub-executors.
    """

    backend = "moe"
    method = "auto"
    local_compute = "numpy"
    transpose_local_compute = "numpy"

    def __init__(self, a, row_part: RowPartition, col_part: RowPartition,
                 topo: Topology, spec: OperatorSpec, mesh=None):
        from repro.moe.plan import build_dispatch_plans, choose_dispatch
        self.a, self.topo, self.spec = a, topo, spec
        self.row_part, self.col_part = row_part, col_part
        plans = build_dispatch_plans(a, row_part, col_part, topo,
                                     pairing=spec.pairing)
        verdict = choose_dispatch(a, row_part, col_part, topo,
                                  wire_dtype=spec.wire_dtype,
                                  integrity=spec.integrity, plans=plans)
        self.dispatch_report = {"dispatch": verdict["dispatch"],
                                "combine": verdict["combine"]}

        def sub(method: str):
            s = dataclasses.replace(spec, method=method)
            ex = _REGISTRY[("moe", method)](a, row_part, col_part, topo, s,
                                            mesh=mesh)
            ex._plan = plans[method]   # reuse the scored plan
            return ex

        fwd_m = verdict["dispatch"]["chosen"]
        bwd_m = verdict["combine"]["chosen"]
        self._fwd = sub(fwd_m)
        self._bwd = self._fwd if bwd_m == fwd_m else sub(bwd_m)

    def forward(self, v: np.ndarray, donate: bool = False) -> np.ndarray:
        return self._fwd.forward(v, donate=donate)

    def transpose(self, u: np.ndarray, donate: bool = False) -> np.ndarray:
        return self._bwd.transpose(u, donate=donate)

    def queue_fault(self, fault: MessageFault) -> None:
        target = self._bwd if fault.direction == "transpose" else self._fwd
        target.queue_fault(fault)

    def integrity_report(self) -> Dict[str, object]:
        rep = dict(self._fwd.integrity_report())
        if self._bwd is not self._fwd:
            rep["combine"] = self._bwd.integrity_report()
        return rep

    def swap_values(self, a_new) -> None:
        self._fwd.swap_values(a_new)
        if self._bwd is not self._fwd:
            self._bwd.swap_values(a_new)
        self.a = a_new

    def trace_counts(self) -> Dict[str, int]:
        return {}

    def stats(self) -> Dict[str, object]:
        out = dict(self._fwd.stats())
        if self._bwd is not self._fwd:
            b = self._bwd.stats()
            out["combine_injected_inter_bytes"] = \
                b["combine_injected_inter_bytes"]
            out["combine_injected_intra_bytes"] = \
                b["combine_injected_intra_bytes"]
        out["dispatch_resolved"] = type(self._fwd).method
        out["combine_resolved"] = type(self._bwd).method
        return out

    def cost(self, machine: MachineParams) -> Dict[str, float]:
        return self._fwd.cost(machine)

    def autotune_report(self) -> Dict[str, object]:
        return {"resolved": "numpy", "transpose_resolved": "numpy",
                "requested": "auto",
                "wire_dtype": self.spec.wire_dtype,
                "dispatch_resolved": type(self._fwd).method,
                "combine_resolved": type(self._bwd).method,
                "moe_dispatch": self.dispatch_report}
