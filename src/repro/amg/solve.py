"""AMG V-cycle + (preconditioned) CG over NapOperator-backed SpMVs.

These exercise the hierarchy end-to-end; the *distributed* SpMV inside
each level is what the paper optimizes.  Every solver accepts either a
plain callable or a :class:`repro.api.NapOperator` (operators are
callable), and :func:`level_operators` builds a **fully distributed
hierarchy**: one square operator for each level's A *and one rectangular
operator for each P* (its ``.T`` view is the restriction), so the
V-cycle's grid transfers run as node-aware SpMVs too — ``P.T @ r``
through the reversed communication plan instead of a host-side gather.
``examples/amg_spmv.py`` wires the NAPSpMV executors into this loop with
no raw lambdas.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from repro.amg.hierarchy import Level
from repro.core.integrity import IntegrityError
from repro.core.partition import contiguous_partition
from repro.core.spans import span
from repro.sparse.csr import CSR


@dataclasses.dataclass
class LevelOperators:
    """The distributed operators of one hierarchy level.

    ``a`` — square NapOperator for A_l (row == col partition);
    ``p`` — RECTANGULAR NapOperator for the prolongation
    (row_part = level l's partition, col_part = level l+1's);
    ``r`` — the restriction, ``p.T``: the same compiled plan with
    send/recv roles reversed (never a second plan build).
    Any of the three is ``None`` where the level is too small to
    distribute; :func:`amg_vcycle` falls back to local matvecs there.
    """

    a: Optional[object] = None
    p: Optional[object] = None
    r: Optional[object] = None

    def galerkin(self, materialize: bool = False,
                 **materialize_kwargs) -> Optional[object]:
        """The coarse-grid operator ``R @ A @ P`` (None if any factor is).

        ``materialize=False`` (default) returns the lazy
        :class:`repro.api.ComposedOperator` — three chained node-aware
        SpMVs per apply.  ``materialize=True`` collapses the chain
        through the node-aware distributed SpGEMM into a CONCRETE
        :class:`repro.api.NapOperator` on the coarse partitions (one
        SpMV per apply; wins past a few applies — see
        ``src/repro/spgemm/README.md``).  Extra kwargs pass to
        :meth:`repro.api.ComposedOperator.materialize`.
        """
        if self.a is None or self.p is None or self.r is None:
            return None
        composed = self.r @ self.a @ self.p
        if not materialize:
            return composed
        return composed.materialize(**materialize_kwargs)


def level_operators(levels: Sequence[Level], topo, *, method: str = "nap",
                    backend: str = "simulate", min_rows: Optional[int] = None,
                    parts: Optional[Sequence] = None,
                    materialize: bool = False,
                    spgemm_backend: str = "simulate",
                    spgemm_dtype=None,
                    comm: Optional[str] = None,
                    **kwargs) -> List[LevelOperators]:
    """One :class:`LevelOperators` (A + rectangular P/R) per AMG level.

    ``parts`` optionally supplies one partition per level (defaults to
    ``contiguous_partition`` of each level's row count); level l's P uses
    ``row_part=parts[l], col_part=parts[l+1]``, so every composition
    interface in the V-cycle (``P.T @ r``, ``R @ A @ P``) chains with
    MATCHING partitions.  Levels with fewer rows than ``min_rows``
    (default: the machine size) get ``a=None``; their grid transfers stay
    distributed as long as the FINE side is large enough — the coarse
    partition simply has empty ranks.  Extra ``kwargs`` pass straight to
    :func:`repro.api.operator`.

    ``comm`` selects the exchange strategy PER LEVEL and PER DIRECTION:
    each level's A and P get their own :func:`repro.api.operator` call,
    so ``comm="auto"`` runs the comm autotuner against that level's own
    sparsity — a near-dense coarse level can resolve to ``"multistep"``
    (or ``"standard"``) while the fine levels stay ``"nap"``, and a
    rectangular P's restriction direction can differ from its forward.
    Inspect the per-level verdicts via each operator's
    ``autotune_report()["comm"]``.

    ``materialize=True`` assembles every coarse-level matrix through the
    node-aware distributed SpGEMM (:func:`repro.spgemm.galerkin_rap` on
    ``spgemm_backend``) instead of trusting the hierarchy's host-side
    product: each level's ``A_c = R (A P)`` chains from the previous
    distributed product and is cross-checked against the hierarchy's
    host ``csr_matmul`` assembly — bit-for-bit on the float64
    ``"simulate"`` backend, to round-off on ``"shardmap"`` — and the
    coarse operators are built FROM the distributed product.
    """
    import repro.api as nap  # local import keeps numpy-only users jax-free

    floor = topo.n_procs if min_rows is None else min_rows
    if parts is None:
        parts = [contiguous_partition(lvl.a.shape[0], topo.n_procs)
                 for lvl in levels]
    a_mats = [levels[0].a] + [None] * (len(levels) - 1)
    if materialize:
        from repro.spgemm import assert_matches_host, galerkin_rap
        for i in range(len(levels) - 1):
            lvl = levels[i]
            r_mat = lvl.r if lvl.r is not None else lvl.p.transpose()
            a_mats[i + 1] = galerkin_rap(
                r_mat, a_mats[i], lvl.p, parts[i], parts[i + 1], topo,
                method=method if method in ("nap", "standard") else "nap",
                backend=spgemm_backend, dtype=spgemm_dtype,
                mesh=kwargs.get("mesh"))
            # float32 products chain level-to-level, so the tolerance vs
            # the float64 host hierarchy grows with the chain depth
            assert_matches_host(a_mats[i + 1], levels[i + 1].a,
                                spgemm_backend, f"level {i + 1} A_c",
                                rtol=5e-5 * (i + 1))
    else:
        a_mats = [lvl.a for lvl in levels]
    ops: List[LevelOperators] = []
    for i, lvl in enumerate(levels):
        entry = LevelOperators()
        if lvl.a.shape[0] >= floor:
            entry.a = nap.operator(a_mats[i], topo=topo, part=parts[i],
                                   method=method, backend=backend,
                                   comm=comm, **kwargs)
            if lvl.p is not None:
                entry.p = nap.operator(lvl.p, topo=topo,
                                       row_part=parts[i],
                                       col_part=parts[i + 1],
                                       method=method, backend=backend,
                                       comm=comm, **kwargs)
                entry.r = entry.p.T
        ops.append(entry)
    return ops


def _level_entry(operators, lvl: int) -> Tuple[Optional[object],
                                               Optional[object],
                                               Optional[object]]:
    """(a_op, p_op, r_op) for one level; tolerates the legacy form where
    ``operators[lvl]`` is a bare A operator (or None)."""
    if operators is None or lvl >= len(operators):
        return None, None, None
    entry = operators[lvl]
    if entry is None:
        return None, None, None
    if isinstance(entry, LevelOperators):
        return entry.a, entry.p, entry.r
    return entry, None, None


def _diag(a: CSR) -> np.ndarray:
    rows, cols, vals = a.to_coo()
    d = np.zeros(a.shape[0])
    m = rows == cols
    d[rows[m]] = vals[m]
    d[d == 0] = 1.0
    return d


def jacobi(a: CSR, x: np.ndarray, b: np.ndarray, d: np.ndarray,
           sweeps: int = 2, omega: float = 2.0 / 3.0,
           spmv: Optional[Callable] = None) -> np.ndarray:
    """``spmv`` may be a callable or a NapOperator (operators are callable)."""
    mv = spmv or a.matvec
    for _ in range(sweeps):
        x = x + omega * (b - mv(x)) / d
    return x


def amg_vcycle(levels: List[Level], b: np.ndarray,
               x: Optional[np.ndarray] = None, lvl: int = 0,
               spmv_at: Optional[Callable[[int, np.ndarray], np.ndarray]] = None,
               operators: Optional[Sequence[Optional[object]]] = None
               ) -> np.ndarray:
    """One V(2,2)-cycle.

    Per-level SpMV resolution: ``operators[lvl]`` — a
    :class:`LevelOperators` from :func:`level_operators` (A plus the
    rectangular P/R, so restriction runs as the node-aware ``P.T @ r``
    and prolongation as ``P @ x_c``; ``None`` members fall back to the
    level's local matvecs), or legacy bare A operators — or the
    lower-level ``spmv_at(lvl, v)`` callback.
    """
    a = levels[lvl].a
    a_op = p_op = r_op = None
    if operators is not None and spmv_at is None:
        a_op, p_op, r_op = _level_entry(operators, lvl)
    if a_op is not None:
        mv = a_op
    elif spmv_at is not None:
        mv = lambda v: spmv_at(lvl, v)
    else:
        mv = a.matvec
    if x is None:
        x = np.zeros_like(b)
    if lvl == len(levels) - 1 or levels[lvl].p is None:
        dense = a.to_dense()
        return np.linalg.lstsq(dense, b, rcond=None)[0]
    d = _diag(a)
    x = jacobi(a, x, b, d, spmv=mv)
    res = b - mv(x)
    # restriction: the node-aware transpose SpMV (P.T against the SAME
    # compiled plan as prolongation) where distributed, else host matvec
    coarse_b = (r_op @ res) if r_op is not None else levels[lvl].r.matvec(res)
    coarse_x = amg_vcycle(levels, coarse_b, None, lvl + 1, spmv_at, operators)
    x = x + ((p_op @ coarse_x) if p_op is not None
             else levels[lvl].p.matvec(coarse_x))
    return jacobi(a, x, b, d, spmv=mv)


def cg_solve(a: CSR, b: np.ndarray, tol: float = 1e-8, maxiter: int = 500,
             precond: Optional[Callable[[np.ndarray], np.ndarray]] = None,
             spmv: Optional[Callable] = None,
             x0: Optional[np.ndarray] = None,
             callback: Optional[Callable[[int, np.ndarray], None]] = None,
             verify_every: int = 0, verify_tol: float = 1e-6):
    """(Preconditioned) conjugate gradients; returns (x, iters, relres).

    ``spmv`` may be a plain callable or a NapOperator.  ``x0`` warm-starts
    the iteration (the serve layer's elastic recovery restarts from the
    last checkpointed iterate); ``callback(it, x)`` fires after every
    iteration — raising from it aborts the solve mid-stream, which the
    fault harness uses to model a node dying at step k.  A restarted CG
    rebuilds its Krylov space from the checkpointed x, so iterate
    trajectories differ from an uninterrupted run, but any solve driven
    to ``tol`` satisfies the same residual contract.

    ``verify_every=k`` (0 = off; the default path is bit-identical to a
    build without the feature) adds a SELF-VERIFYING replay check every k
    iterations: the recursive residual ``r`` is compared against the true
    residual ``b - A x`` (one extra SpMV).  A silently corrupted SpMV
    poisons the recursion — the two drift apart far beyond float
    round-off — so on a drift past ``verify_tol`` (relative to ``||b||``)
    the solver rolls back to the LAST VERIFIED iterate and replays; a
    transient fault replays clean and the trajectory re-joins the
    fault-free one exactly.  Drift that persists at the same iterate
    raises :class:`repro.core.integrity.IntegrityError` (the corruption
    is not transient — retrying cannot help).
    """
    mv = spmv or a.matvec
    with span("repro.cg.init"):
        x = np.zeros_like(b) if x0 is None else np.array(x0, dtype=b.dtype)
        r = b - mv(x)
        z = precond(r) if precond else r
        p = z.copy()
        rz = float(r @ z)
        b_norm = max(float(np.linalg.norm(b)), 1e-30)
        rel = float(np.linalg.norm(r)) / b_norm
    if rel < tol:     # warm start already converged
        return x, 0, rel
    snap = (x.copy(), r.copy(), p.copy(), rz) if verify_every else None
    snap_it = 0
    failed_at = -1
    it = 1
    while it <= maxiter:
        # one iteration's host work, its applies included, ends before
        # the callback, so a caller may close its own spans there
        with span("repro.cg.iteration"):
            ap = mv(p)
            alpha = rz / max(float(p @ ap), 1e-300)
            x += alpha * p
            r -= alpha * ap
            verified = False
            if verify_every and it % verify_every == 0:
                drift = float(np.linalg.norm((b - mv(x)) - r)) / b_norm
                if drift > verify_tol:
                    if failed_at == it:
                        raise IntegrityError(
                            f"CG true-residual replay check failed twice "
                            f"at iteration {it} (drift {drift:.3e} > "
                            f"{verify_tol:.1e}): persistent SpMV "
                            f"corruption")
                    failed_at = it
                    x, r, p = snap[0].copy(), snap[1].copy(), snap[2].copy()
                    rz = snap[3]
                    it = snap_it + 1
                    continue
                verified = True
                failed_at = -1
            rel = float(np.linalg.norm(r)) / b_norm
            if not rel < tol:
                z = precond(r) if precond else r
                rz_new = float(r @ z)
                p = z + (rz_new / max(rz, 1e-300)) * p
                rz = rz_new
                # snapshot AFTER the direction update: the saved tuple is
                # the complete loop-top state of iteration it+1, so a
                # rollback replays the clean trajectory exactly (a
                # verify-point snapshot would pair the new x/r with the
                # PREVIOUS search direction)
                if verified:
                    snap = (x.copy(), r.copy(), p.copy(), rz)
                    snap_it = it
        if callback is not None:
            callback(it, x)
        if rel < tol:
            return x, it, rel
        it += 1
    return x, maxiter, float(np.linalg.norm(r)) / b_norm


def pagerank(op, d: float = 0.85, iters: int = 20,
             dangling: Optional[np.ndarray] = None) -> np.ndarray:
    """PageRank as LDBC Graphalytics defines it, ``iters`` iterations
    from ``1 / N``, over an operator of ``B = I - d * A^T * D_out^-1``
    (:func:`repro.sparse.generators.pagerank_matrix`).

    One iteration is one apply of B, in Jacobi form::

        x <- x + (c - B x),   c = (1 - d) / N + d / N * sum(x[dangling])

    which is the power iteration ``x <- d A^T D^-1 x + c``: the teleport
    share and the mass of the dangling vertices (no out-edges), spread
    uniformly.  ``dangling`` is a boolean mask; without it the dangling
    vertices are read from B's column sums, one ``op.T`` apply (a
    dangling column holds only its diagonal 1, every other sums to
    ``1 - d``).  Each iteration runs under the host span
    ``repro.pagerank.iteration``.
    """
    n = op.shape[1]
    if dangling is None:
        colsum = np.asarray(op.T @ np.ones(n, np.float32), np.float64)
        dangling = colsum > 1.0 - d / 2
    x = np.full(n, 1.0 / n)
    for _ in range(iters):
        with span("repro.pagerank.iteration"):
            c = (1.0 - d) / n + d / n * float(x[dangling].sum())
            x = x + (c - np.asarray(op @ x.astype(np.float32), np.float64))
    return x


def _safe_div(num: float, den: float) -> float:
    """num/den with a sign-preserving breakdown guard (BiCG denominators
    are legitimately negative — clamping with max() would flip search
    directions into garbage)."""
    if abs(den) < 1e-300:
        den = 1e-300 if den >= 0 else -1e-300
    return num / den


def bicgstab_solve(a: CSR, b: np.ndarray, tol: float = 1e-8,
                   maxiter: int = 500, spmv: Optional[Callable] = None,
                   spmv_t: Optional[Callable] = None,
                   verify_every: int = 0, verify_tol: float = 1e-6):
    """BiCG-stabilised solve for nonsymmetric systems; returns
    (x, iters, relres).

    BiCGSTAB itself needs only ``A @ v``, but the classic BiCG it
    stabilises needs ``A.T @ v`` — pass ``spmv_t`` (e.g. ``op.T``) to run
    plain BiCG instead, exercising the transpose SpMV the NapOperator
    front-end provides from the same compiled plan.

    ``verify_every=k`` adds the same true-residual replay check as
    :func:`cg_solve` (0 = off, default path untouched): drift between
    the recursive and true residual past ``verify_tol`` rolls back to
    the last verified iterate and replays; persistent drift at the same
    iterate raises :class:`repro.core.integrity.IntegrityError`.
    """
    mv = spmv or a.matvec
    x = np.zeros_like(b)
    r = b - mv(x)
    b_norm = max(float(np.linalg.norm(b)), 1e-30)

    def _check(it, x, r, failed_at) -> bool:
        """Shared replay check: True means drift past tolerance (roll
        back); a REPEAT failure at the same iterate raises instead —
        retrying cannot fix a persistent corruption."""
        drift = float(np.linalg.norm((b - mv(x)) - r)) / b_norm
        if drift <= verify_tol:
            return False
        if failed_at == it:
            raise IntegrityError(
                f"true-residual replay check failed twice at "
                f"iteration {it} (drift {drift:.3e} > "
                f"{verify_tol:.1e}): persistent SpMV corruption")
        return True

    if spmv_t is not None:
        # plain BiCG (Lanczos biorthogonalisation) using A and A.T
        rt = r.copy()
        p, pt = r.copy(), rt.copy()
        rho = float(rt @ r)
        snap = (x.copy(), r.copy(), rt.copy(), p.copy(), pt.copy(), rho) \
            if verify_every else None
        snap_it, failed_at, it = 0, -1, 1
        while it <= maxiter:
            ap = mv(p)
            alpha = _safe_div(rho, float(pt @ ap))
            x += alpha * p
            r -= alpha * ap
            verified = False
            if verify_every and it % verify_every == 0:
                if _check(it, x, r, failed_at):
                    failed_at = it
                    x, r, rt, p, pt = (s.copy() for s in snap[:5])
                    rho = snap[5]
                    it = snap_it + 1
                    continue
                verified, failed_at = True, -1
            rel = float(np.linalg.norm(r)) / b_norm
            if rel < tol:
                return x, it, rel
            rt = rt - alpha * spmv_t(pt)
            rho_new = float(rt @ r)
            beta = _safe_div(rho_new, rho)
            p = r + beta * p
            pt = rt + beta * pt
            rho = rho_new
            # snapshot AFTER the direction updates — the complete loop-top
            # state of iteration it+1, so a rollback replays exactly
            if verified:
                snap = (x.copy(), r.copy(), rt.copy(), p.copy(), pt.copy(),
                        rho)
                snap_it = it
            it += 1
        return x, maxiter, float(np.linalg.norm(r)) / b_norm
    rt0 = r.copy()
    rho = alpha = omega = 1.0
    v = p = np.zeros_like(b)
    snap = (x.copy(), r.copy(), v.copy(), p.copy(), rho, alpha, omega) \
        if verify_every else None
    snap_it, failed_at, it = 0, -1, 1
    while it <= maxiter:
        rho_new = float(rt0 @ r)
        beta = _safe_div(rho_new, rho) * _safe_div(alpha, omega)
        rho = rho_new
        p = r + beta * (p - omega * v)
        v = mv(p)
        alpha = _safe_div(rho, float(rt0 @ v))
        s = r - alpha * v
        t = mv(s)
        omega = _safe_div(float(t @ s), float(t @ t))
        x += alpha * p + omega * s
        r = s - omega * t
        if verify_every and it % verify_every == 0:
            if _check(it, x, r, failed_at):
                failed_at = it
                x, r, v, p = (s_.copy() for s_ in snap[:4])
                rho, alpha, omega = snap[4:]
                it = snap_it + 1
                continue
            failed_at = -1
            # BiCGSTAB updates every recurrence at the loop TOP, so the
            # verify-point state IS the loop-top state of iteration it+1
            snap = (x.copy(), r.copy(), v.copy(), p.copy(), rho, alpha,
                    omega)
            snap_it = it
        rel = float(np.linalg.norm(r)) / b_norm
        if rel < tol:
            return x, it, rel
        it += 1
    return x, maxiter, float(np.linalg.norm(r)) / b_norm
