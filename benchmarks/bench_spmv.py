"""BENCH_spmv.json: machine-readable perf trajectory of the distributed SpMV.

Measures, on a forced 8-device host platform (2 nodes x 4 ppn):

* ``plan_compile`` — wall time of plan compilation (split_all_blocks +
  compile_plan) on a 20k-row random matrix: the seed dict/per-element
  implementation (``benchmarks/_legacy_plan.py``, kept verbatim) vs the
  vectorised one, plus the cached-recompile time.  ``speedup`` is THE
  claim source for any plan-compile speedup quoted in docs (ROADMAP /
  CHANGES quote this field, not a rounded slogan).
* ``local_emit`` — one-off cost + size of materialising each lazy local
  format (fused BSR tiles vs packed ELL) on the block-hostile matrix, and
  the autotuner's verdict.
* ``spmv_wall`` — steady-state wall time per SpMV application for the
  standard (Alg. 1) executor and the NAP executor across every local
  format (coo / ell / fused bsr) plus the autotuned "auto" path, at nv in
  {1, 8}.  Fairness: every variant gets the same explicit warmup
  iterations and ``jax.block_until_ready`` around every timed call.
  Pallas runs in interpret mode on CPU, so absolute numbers are NOT
  hardware numbers — they track relative regressions across PRs.
  Additionally ``operator_forward_nv*_s`` / ``operator_transpose_nv*_s``
  record the END-TO-END `repro.api` operator wall (pack -> SPMD run ->
  unpack, and the reversed-plan transpose), ``operator_rect_*`` the same
  for a RECTANGULAR [m, m/2] operator with independent row/col
  partitions, and ``galerkin_vcycle_s`` / ``galerkin_triple_product_s``
  a full AMG V-cycle whose every P/R is a rectangular shardmap operator
  plus the lazily composed ``(R @ A @ P) @ x`` chain — all share the
  wall dict, so benchmarks/run.py's >1.5x regression gate covers them
  like every other wall entry.
  The ``integrity_detect_overhead_s`` / ``integrity_recover_s`` walls
  time the same operator apply with wire checksums + ABFT verification
  armed, and with a scripted bitflip fired + recovered (detection plus
  the clean retry) — the overhead numbers the README threat model
  quotes, under the same regression gate.
* ``modeled_bytes`` — padded vs effective bytes per phase (the quantity
  the paper's T/U balancing minimises) and plan-level message stats.
* ``comm_autotune`` + the ``comm_multistep_forward_s`` /
  ``comm_autotune_hierarchy_s`` walls — the comm-strategy chooser on a
  skewed near-dense matrix: modeled injected inter-node bytes for
  nap vs nap-multistep (``comm_autotune.forward.reduction`` is THE
  claim source for any multi-step traffic number), the ``comm="auto"``
  resolution, and the per-level verdicts over a 3-level hierarchy whose
  coarse level leaves the nap path.  The walls share run.py's gate.
* ``rap_assemble`` + the ``spgemm_rap_*`` / ``hierarchy_assemble_*``
  walls — the distributed-SpGEMM Galerkin assembly: one fine-level RAP
  through host csr_matmul vs the float64 simulator vs the steady-state
  shard_map program, and the whole hierarchy setup host vs distributed.
  ``rap_assemble.speedup`` (distributed/host ratio) is THE claim source
  for any RAP-assembly number quoted in docs; the walls sit under
  run.py's 1.5x regression gate like every other entry.
* ``moe_dispatch`` — the MoE NAP-dispatch subsystem: measured
  pod-crossing bytes of the compiled shard_map island per dispatch mode
  and wire dtype (nap < flat and fp8 <= 0.55x f32 are asserted),
  plan-layer modeled inter-pod bytes, the executor f32 bit-identity
  flag, and island-apply walls under the same regression gate (keyed on
  the block's ``config``).

    PYTHONPATH=src python -m benchmarks.bench_spmv [--quick] [--out PATH]

Must run as its own process: it forces the device count before jax loads.
"""
from __future__ import annotations

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse
import json
import time

import numpy as np

WARMUP_ITERS = 2


def _best_of(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_plan_compile(n_rows: int, nnz_per_row: int) -> dict:
    from benchmarks._legacy_plan import legacy_compile_nap
    from repro.core.comm_graph import build_nap_plan
    from repro.core.partition import contiguous_partition
    from repro.core.spmv_jax import clear_compile_cache, compile_plan
    from repro.core.topology import Topology
    from repro.sparse import random_fixed_nnz

    topo = Topology(n_nodes=2, ppn=4)
    a = random_fixed_nnz(n_rows, nnz_per_row, seed=0)
    part = contiguous_partition(n_rows, topo.n_procs)
    # share one plan build: the comm-graph build was always vectorised, the
    # measured quantity is the *compile* step (split + gather maps + arrays)
    plan = build_nap_plan(a.indptr, a.indices, part, topo, pairing="aligned")

    t_legacy = _best_of(lambda: legacy_compile_nap(a, part, topo, plan=plan), 2)
    t_new = _best_of(lambda: compile_plan(a, part, topo, plan=plan), 5)
    clear_compile_cache()
    compile_plan(a, part, topo)                     # populate cache
    t_cached = _best_of(lambda: compile_plan(a, part, topo), 3)
    clear_compile_cache()
    speedup = round(t_legacy / t_new, 2)
    return {
        "n_rows": n_rows, "nnz": a.nnz, "n_procs": topo.n_procs,
        "legacy_s": round(t_legacy, 4),
        "vectorized_s": round(t_new, 4),
        "cached_s": round(t_cached, 6),
        "speedup": speedup,
        # the quotable claim, derived from the measured field above
        "speedup_claim": f"{speedup}x (BENCH_spmv.json plan_compile.speedup)",
    }


def bench_local_emit(n_rows: int, nnz_per_row: int) -> dict:
    """One-off cost + bytes of materialising each lazy local format, and
    what the autotuner chose (all lazy; the compile cache amortises the
    chosen format's emission across repeated SpMVs)."""
    from repro.core.partition import contiguous_partition
    from repro.core.spmv_jax import compile_plan
    from repro.core.topology import Topology
    from repro.sparse import random_fixed_nnz

    topo = Topology(n_nodes=2, ppn=4)
    a = random_fixed_nnz(n_rows, nnz_per_row, seed=0)
    part = contiguous_partition(n_rows, topo.n_procs)
    compiled = compile_plan(a, part, topo, cache=False)
    t0 = time.perf_counter()
    compiled.ensure_fused()
    t_bsr = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled.ensure_ell()
    t_ell = time.perf_counter() - t0
    ell_mb = (compiled.arrays["ell_cols"].nbytes
              + compiled.arrays["ell_vals"].nbytes) / 2**20
    chosen = compiled.chosen_local_compute
    auto_mb = {"bsr": round(compiled.arrays["fused_blocks"].nbytes / 2**20, 3),
               "ell": round(ell_mb, 3), "coo": 0.0}[chosen]
    return {"n_rows": n_rows, "nnz": a.nnz,
            "block_shape": list(compiled.block_shape),
            "bsr_emit_s": round(t_bsr, 4),
            "bsr_blocks_mb": round(compiled.arrays["fused_blocks"].nbytes / 2**20, 3),
            "ell_emit_s": round(t_ell, 4),
            "ell_mb": round(ell_mb, 3),
            "autotune_chosen": chosen,
            "auto_emitted_mb": auto_mb}


def bench_spmv_wall(n_rows: int, nnz_per_row: int, quick: bool) -> dict:
    import jax
    import repro.api as nap_api
    from repro.compat import make_mesh
    from repro.core.comm_graph import build_standard_plan, nap_stats, standard_stats, build_nap_plan
    from repro.core.partition import contiguous_partition
    from repro.core.spmv_jax import (compile_plan, forward_program,
                                     pack_vector, padded_traffic)
    from repro.core.topology import Topology
    from repro.sparse import random_fixed_nnz

    topo = Topology(n_nodes=2, ppn=4)
    mesh = make_mesh((topo.n_nodes, topo.ppn), ("node", "proc"))
    a = random_fixed_nnz(n_rows, nnz_per_row, seed=0)
    part = contiguous_partition(n_rows, topo.n_procs)
    compiled = compile_plan(a, part, topo, cache=False)
    compiled_std = compile_plan(a, part, topo, comm="standard", cache=False)
    rng = np.random.default_rng(0)

    def timed(fn, *args):
        # fairness: identical explicit warmup + a block_until_ready
        # fence around every timed application for every variant;
        # best-of-iters so shared-CPU load spikes don't masquerade as
        # regressions under run.py's 1.5x gate
        for _ in range(WARMUP_ITERS):
            jax.block_until_ready(fn(*args))
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    iters = 3 if quick else 10
    walls = {}
    auto_vs_best = {}
    # one operator reused across nv (jit retraces per shape; the plan
    # compile + format emission happen once, like the shard-level paths)
    op = nap_api.operator(a, part=part, topo=topo, method="nap",
                          backend="shardmap", mesh=mesh, cache=False)
    for nv in ((8,) if quick else (1, 8)):
        v = rng.standard_normal((n_rows, nv))
        shards = pack_vector(v, part, topo, compiled.rows_pad)
        run_auto = forward_program(compiled, mesh, local_compute="auto")
        # auto is timed adjacent to the cheap fixed formats it resolves
        # against, not in the heap-churn shadow of the 11 MB BSR variant
        paths = {
            "standard_bsr": forward_program(compiled_std, mesh,
                                            local_compute="bsr"),
            "nap_coo": forward_program(compiled, mesh, local_compute="coo"),
            "nap_ell": forward_program(compiled, mesh, local_compute="ell"),
            "nap_auto": run_auto,
            "nap_fused_bsr": forward_program(compiled, mesh,
                                             local_compute="bsr"),
        }
        for name, run in paths.items():
            walls[f"{name}_nv{nv}_s"] = round(timed(run, shards), 5)
        best_fixed = min(walls[f"nap_{f}_nv{nv}_s"]
                         for f in ("coo", "ell", "fused_bsr"))
        auto_vs_best[f"nv{nv}"] = round(
            walls[f"nap_auto_nv{nv}_s"] / best_fixed, 3)

        # operator-level end-to-end walls (pack -> run -> unpack), forward
        # and reversed-plan transpose, through the repro.api front-end
        walls[f"operator_forward_nv{nv}_s"] = round(
            timed(lambda: op @ v), 5)
        walls[f"operator_transpose_nv{nv}_s"] = round(
            timed(lambda: op.T @ v), 5)

    # -- integrity walls ----------------------------------------------------
    # integrity_detect_overhead_s: the same end-to-end operator apply
    # with the wire checksums + ABFT verification armed ("detect") — the
    # relative overhead vs operator_forward_nv1_s is the number the
    # README threat-model section quotes.  integrity_recover_s: one
    # apply with a scripted inter-phase bitflip fired, so the wall
    # includes detection + the clean retry from the retained packed
    # shards.  Both sit in the shared wall dict under run.py's 1.5x
    # gate; the integrity="off" program is unchanged (it IS the
    # operator_forward walls above).
    v1 = rng.standard_normal(n_rows)
    op_det = nap_api.operator(a, part=part, topo=topo, method="nap",
                              backend="shardmap", mesh=mesh, cache=False,
                              integrity="detect")
    walls["integrity_detect_overhead_s"] = round(
        timed(lambda: op_det @ v1), 5)
    op_rec = nap_api.operator(a, part=part, topo=topo, method="nap",
                              backend="shardmap", mesh=mesh, cache=False,
                              integrity="recover")

    def recover_apply():
        op_rec.inject_fault("inter", "bitflip", node=1, proc=0, slot=0,
                            element=1, bit=20)
        return op_rec @ v1

    walls["integrity_recover_s"] = round(timed(recover_apply), 5)
    assert op_rec.integrity_report()["recovered"] > 0

    # -- rectangular operator walls (independent row/col partitions) -------
    # forward packs by the column partition, transpose by the row
    # partition; the transpose runs the autotuned ell/coo transposed
    # local compute.  Shares the regression gate with every other wall.
    from repro.core.partition import contiguous_partition
    from repro.sparse import CSR
    m_r, n_r = n_rows, n_rows // 2
    rng_r = np.random.default_rng(1)
    rows_r = np.repeat(np.arange(m_r), nnz_per_row)
    a_rect = CSR.from_coo(rows_r,
                          rng_r.integers(0, n_r, size=rows_r.size),
                          rng_r.standard_normal(rows_r.size), (m_r, n_r))
    op_rect = nap_api.operator(a_rect, topo=topo, mesh=mesh,
                               row_part=contiguous_partition(m_r, topo.n_procs),
                               col_part=contiguous_partition(n_r, topo.n_procs),
                               backend="shardmap", cache=False)
    v_r = rng.standard_normal(n_r)
    u_r = rng.standard_normal(m_r)
    walls["operator_rect_forward_nv1_s"] = round(timed(lambda: op_rect @ v_r), 5)
    walls["operator_rect_transpose_nv1_s"] = round(
        timed(lambda: op_rect.T @ u_r), 5)

    # -- distributed AMG: composed Galerkin + full V-cycle ------------------
    # every restriction/prolongation is a rectangular shardmap operator
    # (restriction through the node-aware transpose executor); the lazy
    # (R @ A @ P) chain is timed separately.
    from repro.amg import (amg_vcycle, level_operators,
                           smoothed_aggregation_hierarchy)
    from repro.sparse import rotated_anisotropic_2d
    a_amg = rotated_anisotropic_2d(16 if quick else 24, eps=0.1)
    levels = smoothed_aggregation_hierarchy(a_amg, theta=0.1, coarse_size=32)
    ops = level_operators(levels, topo, backend="shardmap", mesh=mesh)
    b_amg = rng.standard_normal(a_amg.shape[0])
    walls["galerkin_vcycle_s"] = round(
        timed(lambda: amg_vcycle(levels, b_amg, operators=ops)), 5)
    gal = ops[0].galerkin()
    if gal is not None:
        xc = rng.standard_normal(gal.shape[1])
        walls["galerkin_triple_product_s"] = round(timed(lambda: gal @ xc), 5)

    # -- distributed SpGEMM: RAP + hierarchy assembly walls -----------------
    # spgemm_rap_* times ONE Galerkin triple product A_c = R (A P) on the
    # fine level: host csr_matmul, the float64 message-passing simulator,
    # and the steady-state shard_map program (compile + trace cached, so
    # the wall is pack -> 2x SPMD product -> unpack); hierarchy_assemble_*
    # times the WHOLE setup (every level's RAP) host vs distributed.  All
    # share run.py's 1.5x regression gate; rap_assemble.speedup (the
    # distributed-vs-host ratio on the shardmap path) is the claim source
    # for any RAP-assembly number quoted in docs.
    from repro.amg.matmul import csr_matmul
    from repro.spgemm import distributed_rap, galerkin_rap
    lvl0 = levels[0]
    fine = contiguous_partition(lvl0.a.shape[0], topo.n_procs)
    coarse = contiguous_partition(lvl0.p.shape[1], topo.n_procs)
    walls["spgemm_rap_host_s"] = round(timed(
        lambda: csr_matmul(lvl0.r, csr_matmul(lvl0.a, lvl0.p))), 5)
    walls["spgemm_rap_simulate_s"] = round(timed(
        lambda: galerkin_rap(lvl0.r, lvl0.a, lvl0.p, fine, coarse, topo,
                             backend="simulate")), 5)
    walls["spgemm_rap_shardmap_s"] = round(timed(
        lambda: galerkin_rap(lvl0.r, lvl0.a, lvl0.p, fine, coarse, topo,
                             backend="shardmap", mesh=mesh)), 5)
    theta_amg, cs_amg = 0.1, 32
    walls["hierarchy_assemble_host_s"] = round(timed(
        lambda: smoothed_aggregation_hierarchy(a_amg, theta=theta_amg,
                                               coarse_size=cs_amg)), 5)
    dist_rap = distributed_rap(topo, backend="simulate")
    walls["hierarchy_assemble_distributed_s"] = round(timed(
        lambda: smoothed_aggregation_hierarchy(a_amg, theta=theta_amg,
                                               coarse_size=cs_amg,
                                               rap=dist_rap)), 5)
    rap_assemble = {
        "n_fine_rows": lvl0.a.shape[0],
        "host_s": walls["spgemm_rap_host_s"],
        "simulate_s": walls["spgemm_rap_simulate_s"],
        "shardmap_s": walls["spgemm_rap_shardmap_s"],
        "speedup": round(walls["spgemm_rap_host_s"]
                         / walls["spgemm_rap_shardmap_s"], 3),
        "note": "distributed (steady-state shard_map, interpret-mode CPU) "
                "vs host csr_matmul wall for one fine-level RAP; quote "
                "rap_assemble.speedup, not a rounded slogan",
    }

    # -- solver-service walls ----------------------------------------------
    # serve_submit_p50_s: median submit -> done wall for a 1-RHS SpMV
    # request through the full service path (admission, EDF batching, plan
    # cache, accounting) on the simulate backend — the service overhead
    # number, dominated by the oracle SpMV itself.  serve_recover_rebuild_s:
    # the elastic-recovery wall after a scripted node death (survivor
    # repartition + plan-cache rebuild + eager recompile + checkpoint
    # probe), as measured by the service's own stats.  Both sit in the
    # shared wall dict, so run.py's 1.5x gate covers them.
    from repro.serve import FaultPlan, SolverService, dead_node
    svc = SolverService(topo, backend="simulate", queue_limit=64)
    svc.register_matrix("A", a)
    submit_walls = []
    for i in range(3 if quick else 9):
        b_req = rng.standard_normal(n_rows)
        t0 = time.perf_counter()
        t = svc.submit("bench", "A", b_req, kind="spmv")
        svc.run()
        submit_walls.append(time.perf_counter() - t0)
        assert t.status == "done"
    walls["serve_submit_p50_s"] = round(
        float(np.median(submit_walls)), 5)
    svc_f = SolverService(topo, backend="simulate",
                          fault_plan=FaultPlan.of(dead_node(1, "node1")),
                          heartbeat_timeout=2.5)
    svc_f.register_matrix("A", a)
    t = svc_f.submit("bench", "A", rng.standard_normal(n_rows), kind="spmv")
    svc_f.run(max_steps=40)
    assert t.status == "done" and svc_f.stats["recoveries"] == 1
    walls["serve_recover_rebuild_s"] = round(
        svc_f.stats["last_recover_rebuild_s"], 5)

    std_plan = build_standard_plan(a.indptr, a.indices, part, topo)
    nap_plan = compiled.plan or build_nap_plan(
        a.indptr, a.indices, part, topo, pairing="aligned")
    s, n = standard_stats(std_plan, 4), nap_stats(nap_plan, 4)
    modeled = {
        "standard_inter_bytes": s["inter"].total_bytes,
        "standard_intra_bytes": s["intra"].total_bytes,
        "nap_inter_bytes": n["inter"].total_bytes,
        "nap_intra_bytes": n["intra"].total_bytes,
        **padded_traffic(compiled),
    }
    at = compiled.autotune
    autotune = {
        "chosen": at["chosen"],
        "modeled_times_s": {k: float(f"{v:.3e}") for k, v in at["times"].items()},
        "per_rank_choice": [e["choice"] for e in at["per_rank"]],
        "per_rank_bsr_fill": [round(e["bsr_fill"], 5) for e in at["per_rank"]],
        "per_rank_ell_kmax": [e["ell_kmax"] for e in at["per_rank"]],
        "auto_vs_best_fixed": auto_vs_best,
    }
    return {"n_rows": n_rows, "nnz": a.nnz, "topo": [topo.n_nodes, topo.ppn],
            "interpret_mode": True, "iters": iters, "warmup": WARMUP_ITERS,
            "timing": "best_of_iters",
            "wall": walls, "autotune": autotune, "modeled_bytes": modeled,
            "rap_assemble": rap_assemble}


def _skewed_matrix(topo, rows_per_rank: int, bulk: int, seed: int = 0):
    """Near-dense coarse-level structure: shared d=ppn background columns
    plus a d=1 bulk pulled by one node only — the pattern where peeling
    low-duplication columns out of the aggregated inter exchange shrinks
    the pad every inter message pays (see src/repro/comm/README.md)."""
    from repro.core.partition import contiguous_partition
    from repro.sparse import CSR

    n = rows_per_rank * topo.n_procs
    part = contiguous_partition(n, topo.n_procs)
    rng = np.random.default_rng(seed)
    rows = [[] for _ in range(n)]
    lo = lambda r: r * rows_per_rank
    for r in range(topo.n_procs):
        node, lr = topo.node_of(r), topo.local_of(r)
        remote = [q for q in range(topo.n_procs) if topo.node_of(q) != node]
        base = lo(r)
        for i in range(rows_per_rank):
            rows[base + i].append(base + i)
        for src in remote:
            for i in range(rows_per_rank):
                rows[base + i].append(lo(src))
        if node == 0:
            src = remote[lr]
            for k in range(bulk):
                gi = base + int(rng.integers(rows_per_rank))
                rows[gi].append(lo(src) + 1 + k)
    indptr = [0]
    indices = []
    for rr in rows:
        cols = sorted(set(rr))
        indices.extend(cols)
        indptr.append(len(indices))
    data = rng.standard_normal(len(indices))
    return CSR(np.array(indptr, np.int64), np.array(indices, np.int64),
               data, (n, n)), part


def bench_comm_autotune(quick: bool) -> dict:
    """Comm-strategy walls + the machine-readable ``comm_autotune`` block.

    ``comm_multistep_forward_s``: steady-state end-to-end operator apply
    through the five-phase multi-step shardmap program on the skewed
    near-dense matrix.  ``comm_autotune_hierarchy_s``: building the
    3-level operator stack with ``comm="auto"`` — one candidate-plan
    build + per-direction verdict per level operator.  Both walls merge
    into the shared ``spmv_wall.wall`` dict, so run.py's 1.5x gate
    covers them.  The block quotes the chooser's verdict on the skewed
    matrix (nap vs multistep modeled injected inter-node bytes and the
    reduction — THE claim source for any multi-step traffic number in
    docs) plus the per-level resolutions over the hierarchy.
    """
    import jax
    import repro.api as nap_api
    from repro.amg import Level, level_operators
    from repro.comm import choose_comm
    from repro.compat import make_mesh
    from repro.core.topology import Topology
    from repro.sparse import random_fixed_nnz

    topo = Topology(n_nodes=2, ppn=4)
    mesh = make_mesh((topo.n_nodes, topo.ppn), ("node", "proc"))
    rows_per_rank = 32 if quick else 64
    a, part = _skewed_matrix(topo, rows_per_rank, bulk=3 * rows_per_rank // 4)
    n2 = a.shape[0]
    iters = 3 if quick else 10
    rng = np.random.default_rng(0)
    walls = {}

    def timed(fn):
        for _ in range(WARMUP_ITERS):
            jax.block_until_ready(fn())
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    op_ms = nap_api.operator(a, part=part, topo=topo, backend="shardmap",
                             mesh=mesh, cache=False, comm="multistep")
    v = rng.standard_normal(n2)
    walls["comm_multistep_forward_s"] = round(timed(lambda: op_ms @ v), 5)

    # chooser verdict on the skewed matrix (what comm="auto" sees)
    verdict = choose_comm(a.indptr, a.indices, part, topo)
    op_auto = nap_api.operator(a, part=part, topo=topo, backend="simulate",
                               comm="auto")

    def quote(d):
        c = verdict[d]["candidates"]
        nap_b = c["nap"]["injected_inter_bytes"]
        ms_b = c["multistep"]["injected_inter_bytes"]
        return {
            "chosen": verdict[d]["chosen"],
            "nap_injected_inter_bytes": nap_b,
            "multistep_injected_inter_bytes": ms_b,
            "reduction": round(1.0 - ms_b / nap_b, 3) if nap_b else 0.0,
            "standard_injected_inter_bytes":
                c["standard"]["injected_inter_bytes"],
        }

    # 3-level hierarchy: uniform fine/mid, skewed near-dense coarse
    from repro.sparse import CSR
    n1, n0 = n2 * 2, n2 * 4
    fine_a = random_fixed_nnz(n0, 4, seed=13)
    mid_a = random_fixed_nnz(n1, 6, seed=14)

    def injection_p(nf, nc):
        k = nf // nc
        indptr = np.arange(nf + 1, dtype=np.int64)
        indices = (np.arange(nf) // k).astype(np.int64)
        return CSR(indptr, indices, np.ones(nf), (nf, nc))

    levels = [Level(a=fine_a, p=injection_p(n0, n1)),
              Level(a=mid_a, p=injection_p(n1, n2)),
              Level(a=a)]
    walls["comm_autotune_hierarchy_s"] = round(timed(
        lambda: level_operators(levels, topo, backend="simulate",
                                comm="auto")), 5)
    ops = level_operators(levels, topo, backend="simulate", comm="auto")
    per_level = []
    for i, entry in enumerate(ops):
        rep = entry.a.autotune_report()["comm"]
        row = {"level": i, "n_rows": levels[i].a.shape[0],
               "a_forward": rep["resolved"],
               "a_transpose": rep["transpose_resolved"]}
        if entry.p is not None:
            prep = entry.p.autotune_report()["comm"]
            row["p_forward"] = prep["resolved"]
            row["p_transpose"] = prep["transpose_resolved"]
        per_level.append(row)

    block = {
        "n_rows": n2,
        "topo": [topo.n_nodes, topo.ppn],
        "threshold": verdict["threshold"],
        "forward": quote("forward"),
        "transpose": quote("transpose"),
        "auto_resolved": op_auto.method,
        "per_level": per_level,
        "note": "modeled injected inter-node bytes (slot-granular, pad-"
                "charged) on the skewed near-dense matrix; quote "
                "comm_autotune.forward.reduction, not a rounded slogan",
    }
    return {"wall": walls, "comm_autotune": block}


def bench_moe_dispatch(quick: bool) -> dict:
    """The MoE NAP-dispatch block: measured + modeled traffic and walls.

    ``measured_dci_bytes``: pod-crossing bytes of the compiled shard_map
    island (``analyze_hlo`` with ``pod_boundary=4`` on the 2x4 mesh) for
    flat/f32, nap/f32 and the quantized nap wires — the claim source for
    the dispatch traffic numbers in docs (nap < flat, fp8 <= 0.55x f32
    are ASSERTED here, so a regression breaks the bench).
    ``modeled_inter_bytes``: the plan layer's slot-granular injected
    inter-pod bytes for the same geometry.  ``walls``: steady-state
    island applies per mode/wire, gated by run.py's 1.5x rule whenever
    the ``config`` matches the committed baseline.
    """
    import jax
    import jax.numpy as jnp
    import repro.api as nap_api
    from repro.compat import make_mesh
    from repro.configs import get_reduced
    from repro.core.hlo_analysis import analyze_hlo
    from repro.models.moe import EPInfo, moe_apply_sharded, moe_init
    from repro.moe.dispatch import topology_of_mesh
    from repro.moe.plan import (dispatch_partitions, dispatch_traffic,
                                build_dispatch_plans, representative_routing,
                                routing_matrix)
    from repro.moe.wire import wire_bytes

    d = 32 if quick else 64
    cfg0 = get_reduced("qwen3-moe-235b-a22b").replace(
        n_experts=8, top_k=4, moe_dff=d, d_model=d, capacity_factor=8.0)
    mesh = make_mesh((2, 4), ("pod", "model"))
    ep = EPInfo(inner_axis="model", pod_axis="pod")
    params = moe_init(jax.random.key(0), cfg0, jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 16, d)) * 0.3, jnp.float32)
    iters = 3 if quick else 10

    walls, measured = {}, {}
    with jax.set_mesh(mesh):
        for mode, wd in (("flat", "f32"), ("nap", "f32"),
                         ("nap", "bf16"), ("nap", "fp8_e4m3")):
            cfg = cfg0.replace(moe_dispatch=mode, wire_dtype=wd)
            fn = jax.jit(lambda p, xx, c=cfg: moe_apply_sharded(p, c, xx,
                                                                ep, mesh))
            compiled = fn.lower(params, x).compile()
            # pod_boundary=4: devices 0-3 are pod 0, 4-7 pod 1
            measured[f"{mode}_{wd}"] = analyze_hlo(
                compiled.as_text(), pod_boundary=4).dci_bytes
            for _ in range(WARMUP_ITERS):
                jax.block_until_ready(fn(params, x))
            best = float("inf")
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(params, x))
                best = min(best, time.perf_counter() - t0)
            walls[f"island_{mode}_{wd}_s"] = round(best, 5)

    # the acceptance inequalities are load-bearing: fail the bench loudly
    # rather than record a payload that contradicts the claims
    assert measured["nap_f32"] < measured["flat_f32"], measured
    assert measured["nap_fp8_e4m3"] <= 0.55 * measured["nap_f32"], measured

    # plan-layer modeled traffic for the same geometry (per token batch:
    # nv = d_model values per routed copy)
    topo = topology_of_mesh(mesh)
    T, E = 64, cfg0.n_experts
    ids, w = representative_routing(T, E, cfg0.top_k, seed=0)
    r = routing_matrix(ids, w, E)
    ep_, tp_ = dispatch_partitions(E, T, topo)
    plans = build_dispatch_plans(r, ep_, tp_, topo)
    modeled = {}
    for name, wd in (("flat_f32", "f32"), ("nap_f32", "f32"),
                     ("nap_fp8_e4m3", "fp8_e4m3")):
        plan = plans[name.split("_", 1)[0]]
        modeled[name] = dispatch_traffic(plan, wire_dtype=wd,
                                         nv=d)["injected_inter_bytes"]
    assert modeled["nap_f32"] < modeled["flat_f32"], modeled
    assert modeled["nap_fp8_e4m3"] * 4 == modeled["nap_f32"], modeled

    # executor f32 path must be bitwise the simulate oracle
    xv = rng.standard_normal((T, d))
    sim = nap_api.operator(r, topo=topo, row_part=ep_, col_part=tp_,
                           backend="simulate", method="nap")
    moe = nap_api.operator(r, topo=topo, row_part=ep_, col_part=tp_,
                           backend="moe", method="nap")
    f32_bit_identical = bool(
        np.array_equal(moe @ xv, sim @ xv)
        and np.array_equal(moe.T @ (sim @ xv), sim.T @ (sim @ xv)))
    assert f32_bit_identical

    return {
        "config": {"n_experts": E, "top_k": cfg0.top_k, "d_model": d,
                   "capacity_factor": cfg0.capacity_factor,
                   "mesh": [2, 4], "n_tokens_modeled": T},
        "measured_dci_bytes": measured,
        "dci_reduction_nap_vs_flat": round(
            measured["flat_f32"] / measured["nap_f32"], 2),
        "fp8_vs_f32_wire_ratio": round(
            measured["nap_fp8_e4m3"] / measured["nap_f32"], 3),
        "modeled_inter_bytes": modeled,
        "wire_bytes_per_val": {wd: wire_bytes(wd)
                               for wd in ("f32", "bf16", "fp8_e4m3")},
        "f32_bit_identical": f32_bit_identical,
        "walls": walls,
        "note": "measured_dci_bytes comes from analyze_hlo(pod_boundary=4) "
                "over the compiled island; quote dci_reduction_nap_vs_flat "
                "and fp8_vs_f32_wire_ratio, not rounded slogans",
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_spmv.json")
    args = ap.parse_args()
    from repro.compat import use_compile_cache
    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    t0 = time.time()
    result = {
        "bench": "spmv",
        "plan_compile": bench_plan_compile(
            4000 if args.quick else 20000, 12),
        "local_emit": bench_local_emit(1024 if args.quick else 2048, 8),
        "spmv_wall": bench_spmv_wall(1024 if args.quick else 2048, 8,
                                     args.quick),
    }
    # hoist the RAP-assembly claim source next to plan_compile
    result["rap_assemble"] = result["spmv_wall"].pop("rap_assemble")
    # comm-strategy walls ride the shared wall dict (run.py 1.5x gate);
    # the chooser verdict is hoisted like rap_assemble
    comm = bench_comm_autotune(args.quick)
    result["spmv_wall"]["wall"].update(comm["wall"])
    result["comm_autotune"] = comm["comm_autotune"]
    # MoE dispatch block: own walls subdict (gated by run.py whenever the
    # committed baseline's config matches), measured + modeled traffic
    result["moe_dispatch"] = bench_moe_dispatch(args.quick)
    result["total_s"] = round(time.time() - t0, 1)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    pc = result["plan_compile"]
    print(f"plan compile ({pc['n_rows']} rows, {pc['n_procs']} ranks): "
          f"legacy {pc['legacy_s']}s -> vectorized {pc['vectorized_s']}s "
          f"({pc['speedup']}x, cached {pc['cached_s']}s)")
    at = result["spmv_wall"]["autotune"]
    print(f"autotune: chose {at['chosen']} "
          f"(auto/best {at['auto_vs_best_fixed']}), "
          f"emitted {result['local_emit']['auto_emitted_mb']} MB")
    ra = result["rap_assemble"]
    print(f"rap assemble ({ra['n_fine_rows']} fine rows): host {ra['host_s']}s, "
          f"simulate {ra['simulate_s']}s, shardmap {ra['shardmap_s']}s "
          f"(speedup {ra['speedup']}x)")
    ca = result["comm_autotune"]
    print(f"comm autotune ({ca['n_rows']} rows): forward chose "
          f"{ca['forward']['chosen']} "
          f"(nap {ca['forward']['nap_injected_inter_bytes']} B -> multistep "
          f"{ca['forward']['multistep_injected_inter_bytes']} B, "
          f"reduction {ca['forward']['reduction']}); per-level "
          f"{[r['a_forward'] for r in ca['per_level']]}")
    md = result["moe_dispatch"]
    print(f"moe dispatch (E={md['config']['n_experts']} "
          f"top_k={md['config']['top_k']} on 2x4): measured DCI "
          f"flat {md['measured_dci_bytes']['flat_f32']:.0f} B -> "
          f"nap {md['measured_dci_bytes']['nap_f32']:.0f} B "
          f"({md['dci_reduction_nap_vs_flat']}x), fp8 wire "
          f"{md['fp8_vs_f32_wire_ratio']}x of f32, "
          f"f32_bit_identical={md['f32_bit_identical']}")
    for k, v in result["spmv_wall"]["wall"].items():
        print(f"  {k}: {v}")
    for k, v in md["walls"].items():
        print(f"  moe_dispatch.{k}: {v}")
    print(f"wrote {args.out} in {result['total_s']}s")


if __name__ == "__main__":
    main()
