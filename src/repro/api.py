"""One LinearOperator-style front-end over every NAPSpMV backend.

The paper's NAPSpMV is one kernel inside larger solvers — AMG cycles need
``A @ x`` *and* the restriction ``P.T @ r`` against node-aware
communication plans on every level.  This module is the single entry
point over the executor registry::

    import repro.api as nap

    op = nap.operator(a, topo=Topology(n_nodes=4, ppn=4))
    w  = op @ v          # forward SpMV (1-RHS or [n, nv] multi-RHS)
    z  = op.T @ v        # transpose SpMV, same compiled plan reversed
    op.stats(), op.cost(BLUE_WATERS), op.autotune_report()

**Rectangular operators.**  An operator is a genuine ``[m, n]`` linear
map over TWO partitions: ``row_part`` lays out the m output rows,
``col_part`` the n input entries.  The communication plan derives its
send/recv/gather maps from ``col_part`` (who owns the x values a rank
needs) and its output layout from ``row_part`` (who computes each row);
``op.T`` swaps the two through the same compiled plan.  ``part=`` stays
as the square-case sugar that sets both::

    p_op = nap.operator(p, topo=topo, row_part=fine, col_part=coarse)
    r    = p_op.T @ residual      # node-aware AMG restriction

**Operator algebra.**  ``@`` between operators is LAZY composition:
``R @ A @ P`` returns a :class:`ComposedOperator` that chains the
executors right-to-left with compatible-partition checking at compose
time, and rolls up per-stage ``.stats()`` / ``.cost()`` — the Galerkin
triple product applied as three node-aware SpMVs, never materialised
implicitly.  When the product will be applied many times,
``composed.materialize()`` collapses the chain through the node-aware
distributed SpGEMM (:mod:`repro.spgemm` — the same three-step exchange
carrying variable-length B-row blocks) into ONE concrete operator on
the outer partitions.

Backends resolve through the pluggable registry in
:mod:`repro.core.executors` — ``backend="shardmap"`` is the jitted SPMD
executor (Pallas local compute, zero-copy packed x), ``"simulate"`` the
exact float64 message-passing oracle; new backends (true-TPU Mosaic,
collective-permute overlap) register themselves without touching any call
site.  Compilation is lazy per backend *and* per direction: building an
operator costs one plan build; the forward program JITs on first
``op @ x`` and the transpose program only on first ``op.T @ x``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from repro.core.cost_model import (LocalComputeParams, MachineParams,
                                   TPU_V5E_LOCAL)
from repro.core.executors import (OperatorSpec, available_executors,
                                  bind_executor, register_executor)
from repro.core.integrity import IntegrityError, MessageFault
from repro.core.partition import RowPartition, contiguous_partition
from repro.core.topology import Topology

__all__ = ["operator", "NapOperator", "ComposedOperator",
           "available_executors", "register_executor",
           "IntegrityError", "MessageFault"]


def operator(a, topo: Optional[Topology] = None,
             part: Optional[RowPartition] = None, *,
             row_part: Optional[RowPartition] = None,
             col_part: Optional[RowPartition] = None,
             method: str = "nap", backend: str = "shardmap",
             comm: Optional[str] = None, threshold: object = "auto",
             local_compute: str = "auto", mesh=None,
             pairing: str = "aligned",
             block_shape: Tuple[int, int] = (8, 128), nv_block: int = 128,
             cache: bool = True,
             tuner: LocalComputeParams = TPU_V5E_LOCAL,
             integrity: str = "off",
             wire_dtype: str = "f32") -> "NapOperator":
    """Build a :class:`NapOperator` for ``a`` on a (topo, partitions) layout.

    Parameters
    ----------
    a : CSR
        Sparse ``[m, n]`` matrix — square or rectangular.
    topo : Topology, optional
        Machine shape.  ``None`` AUTODISCOVERS from the live runtime
        (:func:`repro.mesh.discover.discover_topology`): one "node" per
        jax process, ``ppn`` local devices — a plain single-device
        process discovers ``Topology(1, 1)``, bit-identical to the old
        declared default; after :func:`repro.mesh.launcher.attach` the
        operator spans the whole multi-process mesh.  Pass an explicit
        (n_nodes, ppn) to pin a layout (e.g. simulating a larger
        machine than the one running).
    part : RowPartition, optional
        Square-case sugar: sets ``row_part`` AND ``col_part`` to the same
        partition (requires ``m == n``; mutually exclusive with passing
        either of the two explicitly).
    row_part : RowPartition, optional
        Ownership of the m output rows; defaults to
        ``contiguous_partition(m, topo.n_procs)``.
    col_part : RowPartition, optional
        Ownership of the n input/x entries; defaults to ``row_part``
        when the matrix is square (the single-partition case, whatever
        layout ``row_part`` has), else to
        ``contiguous_partition(n, topo.n_procs)``.  Ranks may own zero
        entries (coarse AMG levels smaller than the machine).
    method : ``"nap"`` (Algorithms 2+3), ``"standard"`` (Algorithm 1) or
        ``"multistep"`` (the duplication-split node-aware exchange —
        see :mod:`repro.comm`).
    backend : ``"shardmap"`` (jitted SPMD) | ``"simulate"`` (exact numpy
        oracle) | any backend later added to the executor registry.
    comm : optional exchange-strategy override — ``"standard"`` |
        ``"nap"`` | ``"multistep"`` pin the strategy (taking precedence
        over ``method``), ``"auto"`` lets the comm autotuner
        (:func:`repro.comm.choose_comm`) pick one PER DIRECTION from the
        modeled injected inter-node bytes + postal time; when forward
        and transpose disagree, the operator holds a second executor for
        the transpose direction.  The verdict is merged into
        ``op.autotune_report()`` under ``comm``/``comm_resolved``/
        ``comm_transpose_resolved``.  ``None`` (default) follows
        ``method`` unchanged.
    threshold : duplication threshold for the multistep strategy
        (``"auto"`` or an int >= 1; ``d < threshold`` columns go direct).
    local_compute : shardmap local kernel — ``"auto"`` | ``"bsr"`` |
        ``"ell"`` | ``"coo"`` (see kernels/README.md).  The Pallas kernel
        behind ``"bsr"`` compiles on a TPU and runs in interpret mode
        only when jax's default backend is the CPU
        (:func:`repro.compat.interpret_mode`).  The transpose
        direction autotunes independently over ell/coo (no transposed
        Pallas BSR kernel yet); see ``op.autotune_report()``.
    mesh : optional pre-built jax mesh with axes ("node", "proc");
        shardmap builds one lazily otherwise.
    pairing : inter-node slot pairing for the nap plan ("aligned" is the
        TPU all-to-all-natural choice and the only one the shardmap
        backend lowers; "balanced" is the paper's text rule, available on
        the simulate backend).
    integrity : ``"off"`` (default — the program is bit-for-bit the
        uninstrumented one) | ``"detect"`` (wire checksums over every
        exchange message + ABFT result verification per apply; a mismatch
        raises :class:`IntegrityError` with phase/message attribution) |
        ``"recover"`` (same checks, but a mismatch retries the apply from
        the retained packed refs — bit-identical to the fault-free run —
        and only raises when the mismatch persists).  Inspect with
        ``op.integrity_report()``; script deterministic faults with
        ``op.inject_fault(...)``.
    wire_dtype : wire payload encoding — ``"f32"`` (default; identity
        codec, bit-for-bit today's path) | ``"bf16"`` | ``"fp8_e4m3"``.
        Consumed by the ``backend="moe"`` dispatch executors
        (:mod:`repro.moe`): payloads are quantized at every wire
        crossing and accumulated at full width on receive, the modeled
        traffic/verdicts charge the narrow width, and integrity
        checksums run over the quantized words.  Other backends accept
        only ``"f32"`` (their programs never quantize).
    """
    m, n = a.shape
    if part is not None:
        if row_part is not None or col_part is not None:
            raise ValueError("pass either part= (square sugar) or "
                             "row_part=/col_part=, not both")
        if m != n:
            raise ValueError(
                f"part= is the square-case sugar (sets row AND col "
                f"partition); a is {a.shape} — pass row_part=/col_part=")
        row_part = col_part = part
    if topo is None:
        from repro.mesh.discover import discover_topology
        topo = discover_topology()
    if row_part is None:
        row_part = contiguous_partition(m, topo.n_procs)
    if col_part is None:
        col_part = (row_part if n == row_part.n_rows
                    else contiguous_partition(n, topo.n_procs))
    if row_part.n_rows != m or col_part.n_rows != n:
        raise ValueError(
            f"partition/matrix mismatch: a is {a.shape}, row_part has "
            f"{row_part.n_rows} rows, col_part {col_part.n_rows}")
    if backend == "shardmap" and pairing != "aligned":
        raise ValueError("the shardmap backend lowers pairing='aligned' "
                         "only (the all-to-all slot contract)")
    if integrity not in ("off", "detect", "recover"):
        raise ValueError(f"integrity must be off|detect|recover, "
                         f"got {integrity!r}")
    from repro.moe.wire import check_wire_dtype
    check_wire_dtype(wire_dtype)
    if wire_dtype != "f32" and backend != "moe":
        raise ValueError(
            f"wire_dtype={wire_dtype!r} is a moe-backend feature (the "
            f"quantized dispatch wire); backend={backend!r} programs "
            f"never quantize — pass wire_dtype='f32'")
    comm_report = None
    t_method = None
    if comm is not None:
        from repro.comm import COMM_CHOICES, choose_comm
        if comm not in COMM_CHOICES:
            raise ValueError(f"comm must be one of {COMM_CHOICES}, "
                             f"got {comm!r}")
        if comm == "auto":
            verdict = choose_comm(a.indptr, a.indices, row_part, topo,
                                  pairing=pairing, col_part=col_part,
                                  threshold=threshold, integrity=integrity)
            method = verdict["forward"]["chosen"]
            t_method = verdict["transpose"]["chosen"]
            comm_report = {
                "requested": "auto",
                "resolved": method,
                "transpose_resolved": t_method,
                "threshold": verdict["threshold"],
                "forward": verdict["forward"],
                "transpose": verdict["transpose"],
            }
        else:
            method = t_method = comm
            comm_report = {"requested": comm, "resolved": comm,
                           "transpose_resolved": comm}
    spec = OperatorSpec(method=method, backend=backend,
                        local_compute=local_compute, pairing=pairing,
                        block_shape=tuple(block_shape), nv_block=nv_block,
                        cache=cache, tuner=tuner,
                        integrity=integrity, threshold=threshold,
                        wire_dtype=wire_dtype)
    exec_ = bind_executor(backend, method, a, row_part, col_part, topo, spec,
                         mesh=mesh)
    t_exec = None
    if t_method is not None and t_method != method:
        # forward and transpose verdicts disagree: a dedicated executor
        # (own plan + programs) serves the transpose direction.
        t_spec = dataclasses.replace(spec, method=t_method)
        t_exec = bind_executor(backend, t_method, a, row_part, col_part,
                               topo, t_spec, mesh=mesh)
    return NapOperator(a=a, row_part=row_part, col_part=col_part, topo=topo,
                       spec=spec, executor=exec_,
                       transpose_executor=t_exec, comm_report=comm_report)


def _is_operator(x) -> bool:
    return isinstance(x, (NapOperator, ComposedOperator))


@dataclasses.dataclass
class NapOperator:
    """Distributed SpMV as a composable linear operator.

    ``op @ x`` / ``op(x)`` apply ``A``; ``op.T @ x`` applies ``A.T``
    through the SAME compiled communication plan with send/recv roles
    reversed.  ``x`` is a global ``[n]`` vector or ``[n, nv]``
    multivector (numpy or jax); the result is ``[m(, nv)]``.  ``op @ other_op``
    composes lazily into a :class:`ComposedOperator` instead of applying.
    """

    a: object
    row_part: RowPartition
    col_part: RowPartition
    topo: Topology
    spec: OperatorSpec
    executor: object
    # set when comm="auto" resolves the two directions to DIFFERENT
    # strategies: the transpose direction runs through its own executor
    transpose_executor: Optional[object] = None
    comm_report: Optional[dict] = None
    transposed: bool = False
    _parent: Optional["NapOperator"] = dataclasses.field(
        default=None, repr=False)

    # -- application / composition ----------------------------------------
    def __call__(self, x, donate: bool = False,
                 precision: Optional[str] = None) -> np.ndarray:
        """Apply the operator.

        ``donate=True`` lets XLA reuse the packed input buffer (shardmap
        backend; ignored by simulate).  ``precision`` pins the result
        dtype: ``"float32"`` / ``"float64"`` / None (backend native —
        float32 for shardmap, float64 for simulate).  The shardmap
        backend computes in float32 regardless; asking it for float64
        raises rather than implying precision it cannot deliver.
        """
        if precision not in (None, "float32", "float64"):
            raise ValueError(f"precision must be float32|float64, "
                             f"got {precision!r}")
        if precision == "float64" and self.spec.backend == "shardmap":
            raise NotImplementedError(
                "the shardmap backend computes in float32; use "
                "backend='simulate' for float64 results")
        if self.transposed:
            ex = (self.transpose_executor
                  if self.transpose_executor is not None else self.executor)
            apply = ex.transpose
        else:
            apply = self.executor.forward
        out = apply(x, donate=donate)
        if precision is not None:
            out = np.asarray(out, dtype=precision)
        return out

    def __matmul__(self, x):
        if _is_operator(x):
            return ComposedOperator.of(self, x)
        return self(x)

    def matvec(self, x) -> np.ndarray:
        return self(x)

    # -- structure ---------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, int]:
        m, n = self.a.shape
        return (n, m) if self.transposed else (m, n)

    @property
    def range_part(self) -> RowPartition:
        """Partition laying out THIS view's output (shape[0] entries)."""
        return self.col_part if self.transposed else self.row_part

    @property
    def domain_part(self) -> RowPartition:
        """Partition laying out THIS view's operand (shape[1] entries)."""
        return self.row_part if self.transposed else self.col_part

    @property
    def method(self) -> str:
        return self.spec.method

    @property
    def backend(self) -> str:
        return self.spec.backend

    @property
    def local_compute(self) -> str:
        """Resolved local-compute format for THIS direction (the transpose
        direction autotunes independently over ell/coo — see
        ``autotune_report()["transpose_resolved"]``)."""
        if self.transposed:
            return getattr(self.executor, "transpose_local_compute",
                           getattr(self.executor, "local_compute", "unknown"))
        return getattr(self.executor, "local_compute", "unknown")

    @property
    def T(self) -> "NapOperator":
        """Transpose view sharing this operator's executor and compiled
        plan (``op.T.T is op``)."""
        if self._parent is not None:
            return self._parent
        return dataclasses.replace(self, transposed=not self.transposed,
                                   _parent=self)

    # -- hot value swap ----------------------------------------------------
    def swap_values(self, a_new) -> None:
        """Swap the matrix VALUES behind this operator without recompiling.

        ``a_new`` must have the exact sparsity structure of the current
        matrix (same shape, indptr, indices — always the UNtransposed
        orientation, even when called on a ``.T`` view: the transpose
        shares the executor and picks the new values up automatically).
        On the shardmap backend the compiled communication plan and both
        jitted direction programs are reused with ZERO retraces — value
        arrays are per-call jit arguments; verify with
        :meth:`trace_counts`.  The serve layer's plan cache keys on
        structure alone and leans on this for multi-tenant value updates.
        """
        self.executor.swap_values(a_new)
        if self.transpose_executor is not None:
            self.transpose_executor.swap_values(a_new)
        self.a = a_new
        if self._parent is not None:
            self._parent.a = a_new

    def trace_counts(self):
        """Per-direction program (re)trace counts — ``{"forward": n,
        "transpose": m}`` on shardmap (a direction appears once built),
        empty for backends that never trace.  Flat counts across a
        :meth:`swap_values` prove the hot-swap reused the compiled
        program."""
        counts = dict(self.executor.trace_counts())
        if self.transpose_executor is not None:
            counts.pop("transpose", None)
            counts.update(
                {k: v for k, v
                 in self.transpose_executor.trace_counts().items()
                 if k == "transpose"})
        return counts

    # -- integrity ---------------------------------------------------------
    def integrity_report(self):
        """Check/mismatch counters, scope attribution, per-node strikes
        and quarantine candidates (``{"mode": "off"}`` when the operator
        was built without integrity)."""
        rep = self.executor.integrity_report()
        if self.transpose_executor is not None:
            rep = dict(rep)
            rep["transpose_executor"] = \
                self.transpose_executor.integrity_report()
        return rep

    def inject_fault(self, phase: str, kind: str = "bitflip", *,
                     node: int = 0, proc: int = 0, slot: int = 0,
                     element: int = 0, bit: int = 30,
                     direction: Optional[str] = None) -> MessageFault:
        """Script ONE deterministic message fault for the next matching
        apply (requires ``integrity != "off"``; the fault fires once and
        replays exactly — see :class:`repro.api.MessageFault`).
        ``direction`` defaults to this view's own direction, so
        ``op.T.inject_fault(...)`` targets the transpose apply."""
        if direction is None:
            direction = "transpose" if self.transposed else "forward"
        fault = MessageFault(phase=phase, kind=kind, node=node, proc=proc,
                             slot=slot, element=element, bit=bit,
                             direction=direction)
        self.queue_fault(fault)
        return fault

    def queue_fault(self, fault: MessageFault) -> None:
        """Script a pre-built :class:`MessageFault` (see
        :meth:`inject_fault` for the keyword convenience)."""
        if (fault.direction == "transpose"
                and self.transpose_executor is not None):
            self.transpose_executor.queue_fault(fault)
        else:
            self.executor.queue_fault(fault)

    # -- introspection -----------------------------------------------------
    def stats(self):
        """Plan-level message statistics (+ padded traffic on shardmap)."""
        return self.executor.stats()

    def cost(self, machine: MachineParams):
        """Modeled communication time under a machine model (Eqs. 10-12)."""
        return self.executor.cost(machine)

    def autotune_report(self):
        """Local-compute format decision (chosen format, modeled times,
        per-rank stats) where the backend runs the adaptive engine —
        forward verdict at the top level, transpose verdict under
        ``"transpose"`` / ``"transpose_resolved"``.  When the operator
        was built with ``comm=``, the exchange-strategy verdict rides
        along under ``"comm"`` / ``"comm_resolved"`` /
        ``"comm_transpose_resolved"``."""
        rep = self.executor.autotune_report()
        if self.comm_report is None:
            return rep
        rep = dict(rep or {})
        rep["comm"] = self.comm_report
        rep["comm_resolved"] = self.comm_report["resolved"]
        rep["comm_transpose_resolved"] = \
            self.comm_report["transpose_resolved"]
        return rep

    def __repr__(self) -> str:
        t = ".T" if self.transposed else ""
        m, n = self.shape
        return (f"NapOperator{t}(shape=({m}, {n}), "
                f"method={self.spec.method!r}, backend={self.spec.backend!r}, "
                f"topo=({self.topo.n_nodes}x{self.topo.ppn}))")


@dataclasses.dataclass(frozen=True)
class ComposedOperator:
    """Lazy right-to-left chain of operators: ``(R @ A @ P) @ x`` runs
    ``P @ x`` first, then ``A``, then ``R`` — three node-aware SpMVs, the
    Galerkin product never materialised.

    Compose-time checking: adjacent shapes must chain
    (``left.shape[1] == right.shape[0]``) and the interface partitions
    must MATCH (``left.domain_part`` lays out the same entries as
    ``right.range_part``), so values flow stage to stage without a hidden
    host-side repartition.  ``.stats()`` / ``.cost()`` /
    ``.autotune_report()`` report per stage, with ``cost()["total"]``
    summing the chain (stages are sequential by data dependence).
    """

    factors: Tuple  # application order: factors[0] @ (... @ (factors[-1] @ x))

    @staticmethod
    def of(left, right) -> "ComposedOperator":
        """Compose two operators (either may already be composed)."""
        lf = left.factors if isinstance(left, ComposedOperator) else (left,)
        rf = right.factors if isinstance(right, ComposedOperator) else (right,)
        factors = tuple(lf) + tuple(rf)
        for l, r in zip(factors[:-1], factors[1:]):
            if l.shape[1] != r.shape[0]:
                raise ValueError(
                    f"operator shapes do not chain: {l.shape} @ {r.shape}")
            lp, rp = l.domain_part, r.range_part
            if lp.n_procs != rp.n_procs or \
                    not np.array_equal(lp.owner, rp.owner):
                raise ValueError(
                    "incompatible partitions at a composition interface: "
                    f"{l!r} consumes a different layout than {r!r} "
                    "produces — rebuild one side so the interface "
                    "partitions match (no hidden repartition)")
        return ComposedOperator(factors=factors)

    # -- application / further composition ---------------------------------
    def __call__(self, x, donate: bool = False,
                 precision: Optional[str] = None) -> np.ndarray:
        for f in reversed(self.factors):
            x = f(x, donate=donate)
        if precision is not None:
            x = np.asarray(x, dtype=precision)
        return x

    def __matmul__(self, x):
        if _is_operator(x):
            return ComposedOperator.of(self, x)
        return self(x)

    def matvec(self, x) -> np.ndarray:
        return self(x)

    # -- structure ---------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, int]:
        return (self.factors[0].shape[0], self.factors[-1].shape[1])

    @property
    def range_part(self) -> RowPartition:
        return self.factors[0].range_part

    @property
    def domain_part(self) -> RowPartition:
        return self.factors[-1].domain_part

    @property
    def T(self) -> "ComposedOperator":
        """(ABC).T = C.T B.T A.T — each stage's node-aware transpose."""
        return ComposedOperator(
            factors=tuple(f.T for f in reversed(self.factors)))

    # -- materialisation: the node-aware distributed SpGEMM ---------------
    def materialize(self, *, spgemm_backend: Optional[str] = None,
                    spgemm_method: Optional[str] = None, dtype=None,
                    cross_check: bool = False, mesh=None) -> "NapOperator":
        """Collapse the lazy chain into ONE concrete :class:`NapOperator`
        on the outer partitions, multiplying the factors right-to-left
        through the node-aware distributed SpGEMM
        (:mod:`repro.spgemm`) — remote B rows route through the same
        three-step exchange the SpMV plans use, carrying variable-length
        CSR row blocks.

        The lazy chain pays k SpMVs (plus interface traffic) per apply;
        the materialised operator pays the SpGEMM once and ONE SpMV per
        apply — it wins whenever the operator is applied more than a few
        times (the AMG solve's coarse operator: one V-cycle already
        applies it several times).  See ``src/repro/spgemm/README.md``
        for the break-even discussion.

        ``spgemm_backend``: ``"simulate"`` (exact float64 products,
        bit-for-bit equal to the host ``csr_matmul`` chain) or
        ``"shardmap"`` (the SPMD program; float32 payloads unless
        ``dtype`` overrides under x64).  Defaults to ``"simulate"`` when
        any factor runs the simulate backend, else ``"shardmap"``.
        ``spgemm_method`` defaults to the leftmost factor's method.
        ``cross_check=True`` asserts every intermediate against the host
        ``csr_matmul`` oracle.  The result reuses the leftmost factor's
        executor spec (backend, local compute, block shape, ...) AND its
        mesh — factors built over an explicit device mesh keep the
        SpGEMM products and the concrete operator on the same devices
        (``mesh=`` overrides).
        """
        from repro.spgemm import assert_matches_host, distributed_spgemm

        factors = self.factors
        topo = factors[0].topo
        for f in factors:
            if (f.topo.n_nodes, f.topo.ppn) != (topo.n_nodes, topo.ppn):
                raise ValueError("cannot materialize a chain spanning "
                                 "different topologies")
        backend = spgemm_backend or (
            "simulate" if any(f.spec.backend == "simulate" for f in factors)
            else "shardmap")
        method = spgemm_method or factors[0].spec.method
        if mesh is None:
            # first explicitly meshed factor wins (executors hold _mesh
            # only when one was passed in or lazily built)
            for f in factors:
                mesh = getattr(f.executor, "_mesh", None)
                if mesh is not None:
                    break

        def csr_of(f: "NapOperator"):
            return f.a.transpose() if f.transposed else f.a

        cur = csr_of(factors[-1])
        for f in reversed(factors[:-1]):
            cur = distributed_spgemm(csr_of(f), cur, f.range_part,
                                     f.domain_part, topo, method=method,
                                     backend=backend, dtype=dtype,
                                     mesh=mesh)
        if cross_check:
            from repro.amg.matmul import csr_matmul
            want = csr_of(factors[-1])
            for f in reversed(factors[:-1]):
                want = csr_matmul(csr_of(f), want)
            assert_matches_host(cur, want, backend, "materialize")
        spec = factors[0].spec
        return operator(cur, topo=topo, row_part=self.range_part,
                        col_part=self.domain_part, method=spec.method,
                        backend=spec.backend,
                        local_compute=spec.local_compute, mesh=mesh,
                        pairing=spec.pairing, block_shape=spec.block_shape,
                        nv_block=spec.nv_block, cache=spec.cache, tuner=spec.tuner,
                        integrity=spec.integrity, threshold=spec.threshold,
                        wire_dtype=spec.wire_dtype)

    # -- per-stage introspection, rolled up --------------------------------
    def stats(self) -> List[object]:
        """Per-stage plan statistics, in application (right-to-left) order
        reversed to match ``factors`` (left-to-right)."""
        return [f.stats() for f in self.factors]

    def cost(self, machine: MachineParams):
        """Per-stage modeled comm times + their sum (stages are data-
        dependent, so the chain is sequential)."""
        stages = [f.cost(machine) for f in self.factors]
        return {"stages": stages,
                "total": float(sum(s["total"] for s in stages))}

    def autotune_report(self) -> List[object]:
        return [f.autotune_report() for f in self.factors]

    def __repr__(self) -> str:
        inner = " @ ".join(repr(f) for f in self.factors)
        return f"ComposedOperator({inner})"
