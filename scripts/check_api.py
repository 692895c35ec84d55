"""API smoke: the unified NapOperator surface + the post-deprecation contract.

Run as its own process (it forces the XLA host device count before jax
initialises); wired into the tier-1 pytest run via tests/test_api.py.

Checks, on a (2, 2) machine on CPU:
  * `repro.api` imports and `operator(...)` builds on both backends;
  * forward AND transpose match the dense oracle (1e-9 on simulate,
    f32 tolerance on shardmap), 1-RHS and multi-RHS, on a 64-row square
    operator AND a 64x40 RECTANGULAR operator (row_part != col_part);
  * `(R @ A @ P)` composes lazily and matches the scipy triple product;
  * the distributed-SpGEMM surface exists and works:
    `repro.spgemm.build_spgemm_plan` + `simulate_nap_spgemm` produce the
    host `csr_matmul` product bit-for-bit, and
    `ComposedOperator.materialize()` collapses `(R @ A @ P)` into a
    concrete NapOperator on the coarse partitions;
  * the integrity surface works end to end: `integrity="detect"` raises
    an attributed `IntegrityError` on a scripted wire fault (clean
    applies stay bit-identical to `integrity="off"`), `"recover"`
    reproduces the fault-free result bit-for-bit, and
    `op.integrity_report()` carries the retry/strike counters;
  * the one-release deprecation shims are GONE: `nap_spmv_shardmap`,
    `standard_spmv_shardmap` and `DistSpMV.run` no longer exist (their
    release has passed) and no removed shim has resurfaced.

    PYTHONPATH=src python scripts/check_api.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np


def main() -> None:
    import repro.api as nap
    import repro.core.spmv_jax as spmv_jax_mod
    from repro.core.partition import contiguous_partition
    from repro.core.spmv import DistSpMV
    from repro.core.topology import Topology
    from repro.sparse import CSR, random_fixed_nnz

    n = 64
    topo = Topology(n_nodes=2, ppn=2)
    a = random_fixed_nnz(n, 6, seed=0)
    rng = np.random.default_rng(0)
    v = rng.standard_normal(n)
    v4 = rng.standard_normal((n, 4))
    at = a.transpose()

    # -- operator forward + transpose on both backends ----------------------
    for backend, rtol, atol in [("simulate", 1e-9, 1e-12),
                                ("shardmap", 1e-4, 1e-5)]:
        for method in ("nap", "standard"):
            op = nap.operator(a, topo=topo, method=method, backend=backend)
            np.testing.assert_allclose(op @ v, a.matvec(v), rtol=rtol, atol=atol)
            np.testing.assert_allclose(op.T @ v, at.matvec(v), rtol=rtol, atol=atol)
            w4, z4 = op @ v4, op.T @ v4
            for i in range(4):
                np.testing.assert_allclose(w4[:, i], a.matvec(v4[:, i]),
                                           rtol=rtol, atol=atol)
                np.testing.assert_allclose(z4[:, i], at.matvec(v4[:, i]),
                                           rtol=rtol, atol=atol)
            assert op.T.T is op
    print("operator forward+transpose OK on simulate + shardmap "
          "(nap & standard, 1-RHS & multi-RHS)")

    # -- rectangular operator + lazy composition ----------------------------
    nc = 40
    pm = (rng.random((n, nc)) < 0.2) * rng.standard_normal((n, nc))
    p = CSR.from_dense(pm)
    fine = contiguous_partition(n, topo.n_procs)
    coarse = contiguous_partition(nc, topo.n_procs)
    xc = rng.standard_normal(nc)
    u = rng.standard_normal(n)
    for backend, rtol, atol in [("simulate", 1e-9, 1e-12),
                                ("shardmap", 1e-3, 1e-4)]:
        a_op = nap.operator(a, topo=topo, part=fine, backend=backend)
        p_op = nap.operator(p, topo=topo, row_part=fine, col_part=coarse,
                            backend=backend)
        assert p_op.shape == (n, nc) and p_op.T.shape == (nc, n)
        np.testing.assert_allclose(p_op @ xc, pm @ xc, rtol=rtol, atol=atol)
        np.testing.assert_allclose(p_op.T @ u, pm.T @ u, rtol=rtol, atol=atol)
        gal = p_op.T @ a_op @ p_op
        want = pm.T @ (a.to_dense() @ (pm @ xc))
        np.testing.assert_allclose(gal @ xc, want, rtol=5e-3, atol=5e-4)
        rep = p_op.autotune_report()
        if backend == "shardmap":
            assert rep["transpose_resolved"] in ("ell", "coo"), rep
            assert "transpose" in rep, "compile must record the transpose verdict"
    print("rectangular operator + (R @ A @ P) composition OK on both backends")

    # -- distributed SpGEMM surface + materialize ---------------------------
    from repro.amg.matmul import csr_matmul
    from repro.spgemm import build_spgemm_plan, simulate_nap_spgemm

    plan = build_spgemm_plan(a, p, fine, fine, topo, method="nap")
    c = simulate_nap_spgemm(a, p, plan)
    host = csr_matmul(a, p)
    assert np.array_equal(c.indptr, host.indptr) and \
        np.array_equal(c.indices, host.indices) and \
        np.array_equal(c.data, host.data), \
        "simulate_nap_spgemm must equal host csr_matmul bit-for-bit"
    assert hasattr(nap.ComposedOperator, "materialize"), \
        "ComposedOperator.materialize is part of the public surface"
    a_op = nap.operator(a, topo=topo, part=fine, backend="simulate")
    p_op = nap.operator(p, topo=topo, row_part=fine, col_part=coarse,
                        backend="simulate")
    conc = (p_op.T @ a_op @ p_op).materialize(cross_check=True)
    assert isinstance(conc, nap.NapOperator) and conc.shape == (nc, nc)
    np.testing.assert_allclose(conc @ xc, pm.T @ (a.to_dense() @ (pm @ xc)),
                               rtol=1e-9, atol=1e-10)
    print("spgemm surface OK (build_spgemm_plan + simulate_nap_spgemm "
          "bit-for-bit, ComposedOperator.materialize concrete on coarse "
          "partitions)")

    # -- the serve surface: service round-trip + hot swap, no retrace -------
    from repro.serve import (FaultPlan, PlanCache, SolverService, dead_node)
    from repro.sparse.csr import CSR as _CSR

    svc = SolverService(topo, backend="simulate",
                        fault_plan=FaultPlan.of(dead_node(2, "node1")),
                        heartbeat_timeout=2.5)
    m_int = np.rint(a.to_dense() * 4)
    ai = CSR.from_dense(m_int + m_int.T + np.eye(n) * 80.0)   # integer SPD
    svc.register_matrix("A", ai)
    bi = rng.integers(-8, 9, size=n).astype(np.float64)
    t_spmv = svc.submit("tenant", "A", bi, kind="spmv")
    t_solve = svc.submit("tenant", "A", bi, kind="solve", tol=1e-10)
    svc.run(max_steps=40)
    assert t_spmv.status == "done" and t_solve.status == "done", \
        (t_spmv.status, t_solve.status)
    assert svc.stats["recoveries"] == 1 and svc.topo.n_nodes == 1, \
        "node1's scripted death must drive one elastic recovery"
    np.testing.assert_array_equal(t_spmv.result(), ai.matvec(bi))
    np.testing.assert_allclose(ai.matvec(t_solve.result()), bi,
                               rtol=1e-8, atol=1e-8)
    # hot value swap reuses the compiled shardmap program: zero retraces
    op = nap.operator(a, topo=topo, backend="shardmap")
    _ = op @ v
    before = dict(op.trace_counts())
    op.swap_values(_CSR(indptr=a.indptr.copy(), indices=a.indices.copy(),
                        data=a.data * 2.0, shape=a.shape))
    w_sw = op @ v
    assert op.trace_counts() == before, \
        f"hot swap retraced: {before} -> {op.trace_counts()}"
    np.testing.assert_allclose(w_sw, 2.0 * a.matvec(v), rtol=1e-4, atol=1e-4)
    cache = PlanCache(topo, backend="simulate")
    op_c = cache.operator_for(a, fine)
    assert cache.operator_for(a, fine) is op_c and cache.stats["hits"] == 1
    print("serve surface OK (service solve + elastic recovery; hot swap "
          "with zero retraces; structure-keyed plan cache)")

    # -- the integrity surface ----------------------------------------------
    # detect raises an attributed IntegrityError on a scripted wire
    # fault; recover returns the fault-free result bit-for-bit; the
    # report carries the counters the serve quarantine path reads.
    assert nap.IntegrityError is not None and nap.MessageFault is not None
    y_clean = nap.operator(a, topo=topo, backend="shardmap") @ v
    op_det = nap.operator(a, topo=topo, backend="shardmap",
                          integrity="detect")
    assert np.array_equal(op_det @ v, y_clean), \
        "clean detect must be bit-identical to integrity='off'"
    op_det.inject_fault("inter", "bitflip", node=1, proc=0, slot=0,
                        element=1, bit=20)
    try:
        op_det @ v
        raise AssertionError("scripted bitflip must raise under detect")
    except nap.IntegrityError as e:
        assert e.mismatches and e.mismatches[0].phase == "inter", \
            [str(m) for m in e.mismatches]
    op_rec = nap.operator(a, topo=topo, backend="shardmap",
                          integrity="recover")
    op_rec.inject_fault("inter", "bitflip", node=1, proc=0, slot=0,
                        element=1, bit=20)
    assert np.array_equal(op_rec @ v, y_clean), \
        "recover must reproduce the fault-free apply bit-for-bit"
    rep = op_rec.integrity_report()
    assert rep["recovered"] == 1 and rep["retries"] == 1, rep
    assert rep["strikes"].get("node1") == 1, rep
    print("integrity surface OK (detect raises attributed, recover "
          "bit-identical, report counters populated)")

    # -- the deprecation shims are GONE -------------------------------------
    for mod, name in [(spmv_jax_mod, "nap_spmv_shardmap"),
                      (spmv_jax_mod, "standard_spmv_shardmap"),
                      (DistSpMV, "run")]:
        assert not hasattr(mod, name), \
            f"{name} must be removed (its deprecation release has passed)"
    try:
        import repro.deprecation  # noqa: F401
        raise AssertionError("repro.deprecation should be gone with the shims")
    except ImportError:
        pass
    print("deprecation shims removed (DistSpMV.run, nap_spmv_shardmap, "
          "standard_spmv_shardmap)")

    # -- comm-strategy surface ----------------------------------------------
    # comm="multistep" matches the oracle on both backends; comm="nap" is
    # bit-identical to the pre-existing operator; comm="auto" records the
    # per-direction verdict on autotune_report().
    for backend, rtol, atol in [("simulate", 1e-9, 1e-12),
                                ("shardmap", 1e-4, 1e-5)]:
        op = nap.operator(a, topo=topo, backend=backend, comm="multistep")
        np.testing.assert_allclose(op @ v, a.matvec(v), rtol=rtol, atol=atol)
        np.testing.assert_allclose(op.T @ v, at.matvec(v),
                                   rtol=rtol, atol=atol)
        base = nap.operator(a, topo=topo, backend=backend)
        pinned = nap.operator(a, topo=topo, backend=backend, comm="nap")
        np.testing.assert_array_equal(np.asarray(base @ v),
                                      np.asarray(pinned @ v))
    op = nap.operator(a, topo=topo, backend="simulate", comm="auto")
    rep = op.autotune_report()
    assert rep["comm"]["requested"] == "auto"
    assert rep["comm_resolved"] in ("standard", "nap", "multistep")
    assert rep["comm_transpose_resolved"] in ("standard", "nap", "multistep")
    cand = rep["comm"]["forward"]["candidates"]
    assert set(cand) == {"standard", "nap", "multistep"}
    for c in cand.values():
        assert c["injected_inter_bytes"] >= c["effective_inter_bytes"] >= 0
    np.testing.assert_allclose(op @ v, a.matvec(v), rtol=1e-9, atol=1e-12)
    print("comm surface OK (multistep both backends, comm='nap' "
          "bit-identical, comm='auto' verdict on autotune_report)")

    # -- the mesh runtime surface -------------------------------------------
    # topology autodiscovery (operator(a) with no topo), the persistent
    # buffer registry behind every compiled plan, and the launcher's env
    # contract — all single-process here; the 2-process path is
    # tests/multidev/mesh_prog.py.
    from repro.mesh import (default_registry, discover_topology, launch,
                            mesh_env, pick_coordinator)
    from repro.mesh.buffers import is_multiprocess
    from repro.mesh.launcher import ENV_COORDINATOR

    assert not is_multiprocess()
    disc = discover_topology()
    assert disc.n_nodes == 1 and disc.ppn == 4, disc   # forced 4-device host
    op_auto = nap.operator(a, backend="shardmap")       # topo autodiscovered
    assert op_auto.topo == disc
    oracle = nap.operator(a, topo=disc, backend="shardmap")
    assert np.array_equal(np.asarray(op_auto @ v), np.asarray(oracle @ v)), \
        "autodiscovered topo must be bit-identical to the declared one"
    reg = default_registry()
    rep = reg.report()
    assert rep["staged"] > 0, rep                       # plans stage through it
    assert rep["resident_bytes"] > 0, rep
    env = mesh_env(pick_coordinator(), 2, 1, local_devices=3)
    assert env[ENV_COORDINATOR].startswith("127.0.0.1:")
    assert callable(launch)
    print("mesh surface OK (autodiscovered topo bit-identical, buffer "
          "registry live, launcher env contract)")

    # -- the MoE dispatch subsystem -----------------------------------------
    # backend="moe" executors registered; f32 wire is the identity codec
    # (bitwise vs the matching simulator); quantized byte accounting on
    # stats(); dispatch_operator resolves "auto" per direction.
    from repro.models.config import ModelConfig
    from repro.moe import (dispatch_partitions, representative_routing,
                           routing_matrix, wire_bytes)
    from repro.moe.dispatch import dispatch_operator

    for m in ("flat", "nap", "auto"):
        assert ("moe", m) in nap.available_executors(), \
            f"moe/{m} executor must be registered"
    tt = Topology(n_nodes=2, ppn=2)
    ids, w = representative_routing(64, 4, 2, seed=1)
    r = routing_matrix(ids, w, 4)
    ep_, tp_ = dispatch_partitions(4, 64, tt)
    xt = rng.standard_normal((64, 3))
    ref = nap.operator(r, topo=tt, row_part=ep_, col_part=tp_,
                       backend="simulate", method="nap")
    moe_op = nap.operator(r, topo=tt, row_part=ep_, col_part=tp_,
                          backend="moe", method="nap")
    assert np.array_equal(moe_op @ xt, ref @ xt), \
        "f32 wire must be bit-identical to the simulate oracle"
    assert np.array_equal(moe_op.T @ (ref @ xt), ref.T @ (ref @ xt))
    st = {wd: nap.operator(r, topo=tt, row_part=ep_, col_part=tp_,
                           backend="moe", method="nap",
                           wire_dtype=wd).stats()
          for wd in ("f32", "bf16", "fp8_e4m3")}
    for wd, s in st.items():
        assert s["bytes_per_val"] == wire_bytes(wd), (wd, s["bytes_per_val"])
    assert st["fp8_e4m3"]["dispatch_injected_inter_bytes"] * 4 == \
        st["f32"]["dispatch_injected_inter_bytes"], \
        "quantized byte accounting must scale with the wire width"
    cfg_moe = ModelConfig(name="t", family="moe", n_layers=1, d_model=3,
                          n_heads=1, n_kv_heads=1, d_ff=8, vocab=8,
                          n_experts=4, top_k=2, moe_dff=8,
                          moe_dispatch="auto", wire_dtype="bf16")
    rep = dispatch_operator(cfg_moe, topo=tt, routing=(ids, w)).autotune_report()
    assert rep["dispatch_resolved"] in ("flat", "nap") and \
        rep["combine_resolved"] in ("flat", "nap") and \
        rep["wire_dtype"] == "bf16", rep
    print("moe dispatch surface OK (moe/flat|nap|auto registered, f32 "
          "bit-identical, wire-width byte accounting, auto per-direction "
          "verdicts)")
    print("API OK")


if __name__ == "__main__":
    main()
