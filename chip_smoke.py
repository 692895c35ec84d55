#!/usr/bin/env python3
"""Smoke run of the node-aware SpMV operator on a TPU, at deployment size.

    python chip_smoke.py [--seed 0]             # one chip: apply + service
    python chip_smoke.py --chips 4 [--seed 0]   # the cross-chip path only

One process drives the chips through the user entry points
(``repro.api.operator`` and ``repro.serve.SolverService``) and checks every
result against a float64 host reference built from ``--seed`` with the
repo's own ``CSR.matvec``, elementwise:

    |y - y_ref| <= 1e-5 * (|A| @ |x|) + 1e-30

apply    ``random_fixed_nnz(2**20, 25)`` (the paper's unstructured family)
         and ``rotated_anisotropic_2d(1024)`` (9-point stencil, 1,048,576
         rows: HPCG's 104^3 local grid is 1.1M rows) on Topology(1, 1):
         ``op @ x`` and ``op.T @ x`` at nv 1 and 8, with
         ``local_compute="auto"`` and with each fixed format.  Forced BSR
         runs on the stencil only: on the random matrix every (8, 128)
         block row meets about 200 block columns, and the padded tiles
         would take about 107 GB.
service  ``SolverService(backend="shardmap")``: four ``spmv`` requests on
         the 2**20-row matrix, answered as one batched multi-RHS apply, and
         two CG ``solve`` requests on ``poisson_2d(256)`` (65,536 rows,
         tol 1e-6, maxiter 1000; CG with float32 SpMVs needs about 640
         iterations).  Each solution's true residual is checked in float64.
chips 4  Topology(2, 2) on the four chips of a v5e:2x2 host: both matrices,
         ``comm`` standard | nap | multistep | auto, both directions, and a
         check that the plan's shards sit on four distinct chips.

Walls printed here are smoke readings of one run, not metrics.  The last
line of stdout is one JSON object, and ``"ok": true`` appears only when
every check passed on a TPU.  Without a TPU the script exits 2 and prints no
result; a failed check or a raised phase exits 1.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

RTOL = 1e-5
ATOL = 1e-30
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class Sizes:
    rand_rows: int = 2**20
    rand_nnz: int = 25
    aniso_n: int = 1024
    poisson_n: int = 256
    cg_tol: float = 1e-6
    cg_maxiter: int = 1000


class SmokeFailure(AssertionError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds jax spends in XLA backend compiles (persistent-cache reads
    included), accumulated from jax's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration


def matrices(sizes: Sizes, seed: int):
    from repro.sparse import random_fixed_nnz
    from repro.sparse.generators import rotated_anisotropic_2d
    return {"random": random_fixed_nnz(sizes.rand_rows, sizes.rand_nnz,
                                       seed=seed),
            "aniso": rotated_anisotropic_2d(sizes.aniso_n)}


def operand(rng, n: int, nv: int) -> np.ndarray:
    """[n, nv] float64 values that float32 holds exactly."""
    return rng.standard_normal((n, nv)).astype(np.float32).astype(np.float64)


def reference(a, x: np.ndarray):
    """(A @ x, |A| @ |x|) in float64, column by column."""
    from repro.sparse.csr import CSR
    abs_a = CSR(a.indptr, a.indices, np.abs(a.data), a.shape)
    y = np.stack([a.matvec(c) for c in x.T], axis=1)
    mag = np.stack([abs_a.matvec(np.abs(c)) for c in x.T], axis=1)
    return y, mag


def checked(y, ref, what: str) -> float:
    """Max of |y - y_ref| / (|A| @ |x|); raises past the RTOL bound."""
    y_ref, mag = ref
    y = np.asarray(y, np.float64).reshape(y_ref.shape)
    err = np.abs(y - y_ref)
    if not np.all(err <= RTOL * mag + ATOL):
        bad = int(np.count_nonzero(~(err <= RTOL * mag + ATOL)))
        raise SmokeFailure(f"{what}: {bad} entries outside "
                           f"{RTOL:g} * (|A| @ |x|) + {ATOL:g}")
    return float(np.max(err / (mag + ATOL)))


def kernel_in_program(op, direction: str) -> bool:
    """Whether the operator's shard program for ``direction`` holds a
    compiled Pallas kernel (``tpu_custom_call`` in its lowering)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    ex = op.executor
    run = ex._run(direction)
    pad = ex.compiled.cols_pad if direction == "forward" else ex.compiled.rows_pad
    v = jax.ShapeDtypeStruct((op.topo.n_nodes, op.topo.ppn, pad, 1),
                             np.float32,
                             sharding=NamedSharding(ex.mesh, P("node", "proc")))
    return "tpu_custom_call" in run.jitted.lower(v, *run.args()).as_text()


def timed_apply(view, x, clock: CompileClock):
    """First call (trace + XLA compile + apply), then one steady call."""
    c0, t0 = clock.seconds, time.perf_counter()
    y = view @ x
    first = time.perf_counter() - t0
    xla = clock.seconds - c0
    t0 = time.perf_counter()
    y2 = view @ x
    steady = time.perf_counter() - t0
    return y, y2, first, xla, steady


def describe(name: str, a, op, plan_s: float) -> None:
    rep = op.autotune_report()
    log(f"[{name}] rows={a.shape[0]} nnz={a.nnz} topo={op.topo.n_nodes}x"
        f"{op.topo.ppn} requested={rep['requested']} "
        f"format fwd={rep['resolved']} T={rep['transpose_resolved']} "
        f"(autotuner: fwd={rep['chosen']} "
        f"T={rep['transpose']['chosen']}) "
        f"comm fwd={rep.get('comm_resolved', op.method)} "
        f"T={rep.get('comm_transpose_resolved', op.method)} "
        f"plan_compile={plan_s:.3f}s")


def apply_cases(name, a, op, xs, refs, clock, nvs) -> None:
    """Forward and transpose at each nv, each checked against float64."""
    for direction, view in (("forward", op), ("transpose", op.T)):
        for nv in nvs:
            x = xs[direction][:, :nv]
            x_in = x[:, 0] if nv == 1 else x
            y, y2, first, xla, steady = timed_apply(view, x_in, clock)
            ref = tuple(r[:, :nv] for r in refs[direction])
            err = checked(y, ref, f"{name} {direction} nv={nv}")
            err = max(err, checked(y2, ref, f"{name} {direction} nv={nv} "
                                            f"(second call)"))
            log(f"  {direction:9s} nv={nv} format={view.local_compute} "
                f"max_err={err:.3e} xla_compile={xla:.3f}s "
                f"first_call={first:.3f}s steady_wall={steady:.6f}s "
                f"(smoke reading, not a metric)")


def directional_refs(a, rng, nv: int):
    xs = {"forward": operand(rng, a.shape[1], nv),
          "transpose": operand(rng, a.shape[0], nv)}
    at = a.transpose()
    refs = {"forward": reference(a, xs["forward"]),
            "transpose": reference(at, xs["transpose"])}
    return xs, refs


def apply_phase(mats, seed: int, clock: CompileClock, nvs=(1, 8)) -> None:
    """Topology(1, 1): auto and every fixed format the matrix fits."""
    import repro.api as nap
    from repro.compat import interpret_mode
    from repro.core.topology import Topology
    topo = Topology(1, 1)
    for name, a in mats.items():
        rng = np.random.default_rng(seed)
        xs, refs = directional_refs(a, rng, max(nvs))
        formats = (("auto", "ell", "coo") if name == "random"
                   else ("auto", "ell", "bsr", "coo"))
        for fmt in formats:
            t0 = time.perf_counter()
            op = nap.operator(a, topo=topo, local_compute=fmt, cache=False,
                              comm="auto" if fmt == "auto" else None)
            op.autotune_report()            # compiles the plan
            plan_s = time.perf_counter() - t0
            describe(f"apply {name} {fmt}", a, op, plan_s)
            kernel = kernel_in_program(op, "forward")
            want = op.local_compute == "bsr" and not interpret_mode()
            if kernel != want:
                raise SmokeFailure(
                    f"{name} {fmt}: forward program "
                    f"{'holds' if kernel else 'lacks'} a tpu_custom_call")
            log(f"  forward program tpu_custom_call={kernel}")
            apply_cases(f"{name} {fmt}", a, op, xs, refs, clock, nvs)
            del op
            gc.collect()


def service_phase(a, sizes: Sizes, seed: int, n_spmv: int = 4,
                  n_solve: int = 2) -> None:
    """Batched spmv requests on ``a`` and CG solves on a Poisson stencil."""
    from repro.core.topology import Topology
    from repro.serve import SolverService
    from repro.sparse.generators import poisson_2d
    rng = np.random.default_rng(seed + 1)
    svc = SolverService(Topology(1, 1), backend="shardmap")
    svc.register_matrix("random", a)
    x = operand(rng, a.shape[1], n_spmv)
    ref = reference(a, x)
    t0 = time.perf_counter()
    tickets = [svc.submit(f"tenant{j}", "random", x[:, j], kind="spmv")
               for j in range(n_spmv)]
    steps = svc.run()
    wall = time.perf_counter() - t0
    if steps != 1 or any(t.status != "done" for t in tickets):
        raise SmokeFailure(f"spmv requests: {steps} steps, statuses "
                           f"{[t.status for t in tickets]}")
    y = np.stack([t.result() for t in tickets], axis=1)
    err = checked(y, ref, "service spmv")
    log(f"[service spmv] {n_spmv} requests in one batched apply "
        f"(nv={n_spmv}) rows={a.shape[0]} max_err={err:.3e} "
        f"wall incl. plan + XLA compile={wall:.3f}s "
        f"(smoke reading, not a metric)")

    p = poisson_2d(sizes.poisson_n)
    svc.register_matrix("poisson", p)
    b = operand(rng, p.shape[0], n_solve)
    t0 = time.perf_counter()
    tickets = [svc.submit(f"solver{j}", "poisson", b[:, j], kind="solve",
                          tol=sizes.cg_tol, maxiter=sizes.cg_maxiter)
               for j in range(n_solve)]
    svc.run()
    wall = time.perf_counter() - t0
    for j, t in enumerate(tickets):
        if t.status != "done":
            raise SmokeFailure(f"solve {j}: {t.status} ({t.reason})")
        iters = t.request.iters
        res = (np.linalg.norm(b[:, j] - p.matvec(t.result()))
               / np.linalg.norm(b[:, j]))
        if iters >= sizes.cg_maxiter or not res <= 2 * sizes.cg_tol:
            raise SmokeFailure(f"solve {j}: {iters} iterations, true "
                               f"relative residual {res:.3e}")
        log(f"[service solve {j}] poisson_2d({sizes.poisson_n}) "
            f"rows={p.shape[0]} iters={iters} true_rel_residual={res:.3e} "
            f"(tol {sizes.cg_tol:g})")
    log(f"[service solve] wall for {n_solve} batched solves={wall:.3f}s "
        f"(smoke reading, not a metric)")


def check_placement(op, devices) -> None:
    """Every plan array holds one [1, 1, ...] shard on each of the
    topology's devices, all distinct."""
    want = op.topo.n_procs
    for name, arr in op.executor.compiled.device_arrays().items():
        shards = arr.addressable_shards
        devs = {s.device for s in shards}
        if len(devs) != want or len(shards) != want or any(
                s.data.shape[:2] != (1, 1) for s in shards):
            raise SmokeFailure(f"plan array {name!r} is on {len(devs)} "
                               f"devices in {len(shards)} shards, want "
                               f"{want} distinct")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    log(f"  plan shards on {want} distinct devices; bytes_in_use per "
        f"device={in_use}")
    if any(b == 0 for b in in_use[:want]):
        raise SmokeFailure(f"a device holds nothing: {in_use}")


def chips4_phase(mats, seed: int, clock: CompileClock) -> None:
    """Topology(2, 2) on four chips: every comm strategy, both directions."""
    import jax
    import repro.api as nap
    from repro.core.topology import Topology
    topo = Topology(2, 2)
    devices = jax.devices()
    if len(devices) < topo.n_procs:
        raise SmokeFailure(f"--chips 4 needs 4 devices, found "
                           f"{len(devices)}")
    for name, a in mats.items():
        rng = np.random.default_rng(seed)
        xs, refs = directional_refs(a, rng, 1)
        for comm in ("standard", "nap", "multistep", "auto"):
            t0 = time.perf_counter()
            op = nap.operator(a, topo=topo, comm=comm)
            op.autotune_report()
            plan_s = time.perf_counter() - t0
            describe(f"chips4 {name} comm={comm}", a, op, plan_s)
            check_placement(op, devices)
            apply_cases(f"{name} {comm}", a, op, xs, refs, clock, (1,))
            del op
            gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 2
    from repro.compat import use_compile_cache
    cache_dir = use_compile_cache(ROOT)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: {device} jax {jax.__version__} compile cache {cache_dir}")
    try:
        clock = CompileClock()
        t0 = time.perf_counter()
        mats = matrices(Sizes(), args.seed)
        log(f"matrices built in {time.perf_counter() - t0:.3f}s")
        if args.chips == 4:
            chips4_phase(mats, args.seed, clock)
        else:
            apply_phase(mats, args.seed, clock)
            service_phase(mats["random"], Sizes(), args.seed)
    except Exception:
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
