"""Silent-data-corruption defense: wire checksums, ABFT verification,
scripted message faults, self-verifying solvers, digest-checked
checkpoints, and the service-level detect/recover/quarantine flow.

Tier-1 runs the numpy/simulate layers in-process (the checksum twins, a
seeded clean-apply sweep over rectangular / empty-rank / uneven layouts,
fault detection with phase+message attribution on the simulate wire,
solver replay-rollback, checkpoint digests, deterministic retry jitter,
and the SolverService scenarios) plus the --quick 4-device shardmap
program as a subprocess.  The full 8-device kind x phase x direction
sweep is the ``multidev``-marked run of the same program.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import repro.api as nap
from repro.amg.solve import bicgstab_solve, cg_solve
from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.core.integrity import (IntegrityError, MessageFault,
                                  build_fault_spec, checksum_np,
                                  corrupt_payload_np, message_phases)
from repro.core.partition import contiguous_partition, make_partition
from repro.core.topology import Topology
from repro.serve import FaultEvent, FaultPlan, SolverService
from repro.serve.faultplan import (corrupt_message, drop_message,
                                   duplicate_message)
from repro.sparse import CSR, random_fixed_nnz
from repro.spgemm.shardmap import distributed_spgemm

ROOT = pathlib.Path(__file__).resolve().parents[1]


def band_spd(n, diag=4.0, bands=(1, 7)):
    m = np.eye(n) * diag
    for d in bands:
        idx = np.arange(n - d)
        m[idx, idx + d] = m[idx + d, idx] = -1.0
    return CSR.from_dense(m)


# ------------------------- checksum primitives -----------------------------

def test_checksum_np_matches_jnp_twin():
    """The host Fletcher checksum and the in-graph one are bit-identical
    twins over f32 AND f64 payloads — the wire comparison depends on it."""
    import jax
    import jax.numpy as jnp
    from repro.core.spmv_jax import _msg_checksums
    rng = np.random.default_rng(0)
    # f64 words need x64 enabled to survive jnp.asarray un-truncated
    # (the f64 SpGEMM wire runs under the same flag)
    with jax.enable_x64(True):
        for dtype in (np.float32, np.float64):
            for shape in [(3, 8), (1, 1), (4, 5)]:
                buf = rng.standard_normal(shape).astype(dtype)
                buf[0, -1] = 0.0      # padding-like zeros included
                got = np.asarray(_msg_checksums(jnp.asarray(buf)))
                want = [checksum_np(row) for row in buf]
                assert got.tolist() == want


def test_checksum_position_weighted():
    """Swapping two elements (same multiset of words) changes the
    checksum — what lets the wire catch stale/shifted payloads."""
    rng = np.random.default_rng(1)
    v = rng.standard_normal(16).astype(np.float32)
    w = v.copy()
    w[2], w[9] = v[9], v[2]
    assert checksum_np(v) != checksum_np(w)
    assert checksum_np(v) == checksum_np(v.copy())


def test_corrupt_payload_np_kinds():
    rng = np.random.default_rng(2)
    v = rng.standard_normal(8).astype(np.float32)
    prev = rng.standard_normal(8).astype(np.float32)
    c0 = checksum_np(v)
    for kind in ("zero", "drop"):
        assert not corrupt_payload_np(v, kind).any()
    assert checksum_np(corrupt_payload_np(v, "stale")) != c0
    assert np.array_equal(corrupt_payload_np(v, "stale"), np.roll(v, 1))
    assert np.array_equal(corrupt_payload_np(v, "duplicate", other=prev), prev)
    assert not corrupt_payload_np(v, "duplicate").any()  # no other message
    flipped = corrupt_payload_np(v, "bitflip", element=3, bit=20)
    assert checksum_np(flipped) != c0
    # flipping the same bit twice restores the payload exactly
    assert np.array_equal(
        corrupt_payload_np(flipped, "bitflip", element=3, bit=20), v)
    with pytest.raises(ValueError):
        corrupt_payload_np(v, "gamma-ray")


def test_message_fault_validation():
    with pytest.raises(ValueError):
        MessageFault(phase="warp")
    with pytest.raises(ValueError):
        MessageFault(phase="full", kind="gamma-ray")
    with pytest.raises(ValueError):
        MessageFault(phase="compute", kind="zero")   # ABFT models bitflips
    with pytest.raises(ValueError):
        MessageFault(phase="full", direction="sideways")


def test_build_fault_spec_pure_and_validated():
    topo = Topology(2, 2)
    faults = [MessageFault(phase="inter", node=1, proc=0, slot=0,
                           element=3, bit=20)]
    s1 = build_fault_spec(topo, faults, "nap")
    s2 = build_fault_spec(topo, faults, "nap")
    assert np.array_equal(s1, s2) and s1.dtype == np.int32
    assert not build_fault_spec(topo, [], "nap").any()
    with pytest.raises(ValueError):        # pair is standard-only
        build_fault_spec(topo, [MessageFault(phase="pair")], "nap")
    with pytest.raises(ValueError):        # sender outside the topology
        build_fault_spec(topo, [MessageFault(phase="full", node=5)], "nap")
    with pytest.raises(ValueError):        # two faults, same device+phase
        build_fault_spec(topo, [MessageFault(phase="full", slot=0),
                                MessageFault(phase="full", slot=1)], "nap")
    assert message_phases("nap") == ("full", "init", "inter", "final")
    assert message_phases("standard") == ("pair",)


# ------------------------- simulate-backend wire ---------------------------

def sim_op(a, topo, integrity, method="nap"):
    return nap.operator(a, topo=topo,
                        part=contiguous_partition(a.shape[0], topo.n_procs),
                        method=method, backend="simulate",
                        integrity=integrity)


def test_simulate_detect_attribution_and_recover():
    """Scripted faults on REAL message edges of the simulate wire: detect
    raises with phase + receiver + scope attribution, recover reruns
    clean bit-for-bit, strikes accumulate against the implicated node."""
    topo = Topology(2, 2)
    a = band_spd(64)
    rng = np.random.default_rng(3)
    v = rng.standard_normal(64)
    y0 = sim_op(a, topo, "off") @ v

    op = sim_op(a, topo, "detect")
    assert np.array_equal(op @ v, y0)      # clean detect adds no numerics
    rep = op.integrity_report()
    assert rep["wire_mismatches"] == 0 and rep["wire_checks"] > 0, rep

    # real edges for this band matrix on (2, 2): see the message list the
    # SimWire actually carries (intra-node neighbors + the node pair)
    edges = [("full", 0, 0, 1, "on_node"), ("init", 0, 1, 0, "off_node"),
             ("inter", 1, 0, 0, "off_node"), ("final", 1, 1, 0, "off_node")]
    for phase, node, proc, slot, scope in edges:
        op.inject_fault(phase, "bitflip", node=node, proc=proc, slot=slot,
                        element=0, bit=20)
        with pytest.raises(IntegrityError) as ei:
            op @ v
        m = ei.value.mismatches[0]
        assert (m.phase, m.scope, m.direction) == (phase, scope, "forward")

    rec = sim_op(a, topo, "recover")
    rec.inject_fault("inter", "bitflip", node=1, proc=0, slot=0,
                     element=0, bit=20)
    assert np.array_equal(rec @ v, y0)
    rep = rec.integrity_report()
    assert rep["retries"] == 1 and rep["recovered"] == 1, rep
    assert rep["strikes"].get("node1") == 1, rep

    # transpose fault injection is shardmap-only on this backend
    rec.T.inject_fault("inter", "bitflip", node=1, proc=0, slot=0)
    with pytest.raises(NotImplementedError):
        rec.T @ v

    with pytest.raises(ValueError):        # integrity="off" has no wire
        sim_op(a, topo, "off").queue_fault(MessageFault(phase="full"))
    with pytest.raises(ValueError):
        nap.operator(a, topo=topo, integrity="sometimes")


@pytest.mark.parametrize("seed", range(5))
def test_clean_apply_checksum_sweep(seed):
    """Seeded sweep over square / rectangular / empty-rank / uneven
    layouts, both methods: every pack -> exchange -> unpack round trip
    re-verifies its checksums with ZERO mismatches, and the instrumented
    apply is bit-identical to the uninstrumented one."""
    rng = np.random.default_rng(seed)
    topo = Topology(2, 2)
    m = int(rng.integers(9, 70))
    n = m if seed % 2 == 0 else int(rng.integers(3, 70))
    a = random_fixed_nnz(m, int(rng.integers(2, 7)), seed=seed) \
        if m == n else CSR.from_dense(
            (rng.random((m, n)) < 0.3) * rng.standard_normal((m, n)))
    kind = ["contiguous", "strided"][seed % 2]
    row_part = make_partition(kind, m, topo.n_procs, indptr=a.indptr,
                              indices=a.indices, seed=seed)
    col_part = row_part if m == n else contiguous_partition(n, topo.n_procs)
    method = ["nap", "standard"][seed % 2]
    v = rng.standard_normal(n)
    kw = dict(topo=topo, row_part=row_part, col_part=col_part,
              method=method, backend="simulate")
    y0 = nap.operator(a, **kw) @ v
    op = nap.operator(a, integrity="detect", **kw)
    assert np.array_equal(op @ v, y0)
    u = rng.standard_normal(m)
    assert np.array_equal(op.T @ u, nap.operator(a, **kw).T @ u)
    rep = op.integrity_report()
    assert rep["wire_mismatches"] == 0 and rep["abft_mismatches"] == 0, rep
    assert rep["wire_checks"] > 0


# ------------------------- self-verifying solvers --------------------------

def test_cg_replay_rollback_bit_identical():
    a = band_spd(64)
    rng = np.random.default_rng(1)
    b = rng.standard_normal(64)
    x_clean, it_clean, _ = cg_solve(a, b, tol=1e-10)

    calls = {"n": 0}

    def transient_mv(v):
        calls["n"] += 1
        w = a.matvec(v)
        if calls["n"] == 7:                # fires once, then clean
            w = w.copy()
            w[3] += 1.0
        return w

    x_v, it_v, _ = cg_solve(a, b, tol=1e-10, spmv=transient_mv,
                            verify_every=2)
    assert np.array_equal(x_v, x_clean)
    # the clean run is also bit-identical with verification enabled
    x_d, it_d, _ = cg_solve(a, b, tol=1e-10, verify_every=2)
    assert np.array_equal(x_d, x_clean) and it_d == it_clean


def test_cg_persistent_corruption_raises():
    a = band_spd(64)
    b = np.ones(64)

    def persistent_mv(v):
        w = a.matvec(v)
        w = w.copy()
        w[3] += 1.0
        return w

    with pytest.raises(IntegrityError, match="twice"):
        cg_solve(a, b, tol=1e-10, spmv=persistent_mv, verify_every=2)


def test_bicgstab_replay_rollback_bit_identical():
    a = band_spd(64)
    rng = np.random.default_rng(4)
    b = rng.standard_normal(64)
    x_clean, _, _ = bicgstab_solve(a, b, tol=1e-10)

    calls = {"n": 0}

    def transient_mv(v):
        calls["n"] += 1
        w = a.matvec(v)
        if calls["n"] == 5:
            w = w.copy()
            w[0] += 1.0
        return w

    x_v, _, _ = bicgstab_solve(a, b, tol=1e-10, spmv=transient_mv,
                               verify_every=2)
    assert np.array_equal(x_v, x_clean)
    # the BiCG branch (explicit transpose recurrence) verifies too
    spmv_t = lambda v: a.to_dense().T @ v
    x_t, _, _ = bicgstab_solve(a, b, tol=1e-10, spmv_t=spmv_t)
    x_tv, _, _ = bicgstab_solve(a, b, tol=1e-10, spmv_t=spmv_t,
                                verify_every=3)
    assert np.array_equal(x_tv, x_t)


# ------------------------- checkpoint digests ------------------------------

def test_checkpoint_digest_detects_shard_corruption(tmp_path):
    p = save_checkpoint(tmp_path, 1, {"x": np.arange(32.0)})
    load_checkpoint(tmp_path)              # clean load verifies quietly
    shard = pathlib.Path(p) / "shard_0.npz"
    raw = bytearray(shard.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    shard.write_bytes(bytes(raw))
    with pytest.raises(IntegrityError, match="shard_0.npz"):
        load_checkpoint(tmp_path)


def test_checkpoint_pre_digest_manifest_still_loads(tmp_path):
    p = pathlib.Path(save_checkpoint(tmp_path, 1, {"x": np.arange(8.0)}))
    mf = p / "manifest.json"
    manifest = json.loads(mf.read_text())
    del manifest["shard_digests"]          # a checkpoint from before ABFT
    mf.write_text(json.dumps(manifest))
    tree, _extra = load_checkpoint(tmp_path)
    assert np.array_equal(tree["x"], np.arange(8.0))


# ------------------------- service-level flow ------------------------------

def _service(plan=None, integrity="off", a=None, **kw):
    svc = SolverService(Topology(2, 2), fault_plan=plan,
                       integrity=integrity, **kw)
    svc.register_matrix("A", a if a is not None else band_spd(64))
    return svc


def _run_requests(svc):
    rng = np.random.default_rng(5)
    tickets = [svc.submit(t, "A", rng.standard_normal(64), kind=k, tol=1e-10)
               for t, k in (("t0", "spmv"), ("t1", "solve"))]
    svc.run()
    return [t.result() for t in tickets]


def test_backoff_jitter_deterministic():
    svc = _service()
    d1 = svc._backoff_delay(17, 2)
    d2 = _service()._backoff_delay(17, 2)
    assert d1 == d2                        # pure function of (id, attempt)
    assert 2.0 <= d1 <= 2.5                # base 2.0, jitter in [0, 25%]
    assert len({svc._backoff_delay(i, 1) for i in range(20)}) == 20


def test_service_recover_bit_identical_under_scripted_faults():
    base = _run_requests(_service())
    plan = FaultPlan.of(
        corrupt_message(1, ("inter", (1, 0), 0), kind="bitflip",
                        element=1, bit=20),
        drop_message(2, ("final", (1, 1), 0)))
    got = _run_requests(_service(plan=plan, integrity="recover"))
    for w0, w1 in zip(base, got):
        assert np.array_equal(w0, w1)


def test_service_detect_retries_then_completes_clean():
    base = _run_requests(_service())
    svc = _service(plan=FaultPlan.of(
        corrupt_message(1, ("full", (0, 1), 0), kind="zero", element=0)),
        integrity="detect")
    got = _run_requests(svc)
    assert svc.stats["integrity_detected"] >= 1, svc.stats
    assert svc.stats["retries"] >= 1
    for w0, w1 in zip(base, got):          # fault fires once; retry clean
        assert np.array_equal(w0, w1)


def test_service_off_drops_message_faults_logged():
    base = _run_requests(_service())
    plan = FaultPlan.of(corrupt_message(1, ("inter", (1, 0), 0)),
                        drop_message(2, ("final", (1, 1), 0)))
    svc = _service(plan=plan, integrity="off")
    got = _run_requests(svc)
    assert svc.stats["message_faults"] == 2
    assert any("dropped" in line for line in svc.log)
    for w0, w1 in zip(base, got):
        assert np.array_equal(w0, w1)


def test_service_quarantines_repeat_offender_node():
    events = [corrupt_message(s, ("inter", (1, 0), 0), kind="bitflip",
                              element=1, bit=20) for s in (1, 2, 3)]
    svc = _service(plan=FaultPlan.of(*events), integrity="recover",
                   quarantine_strikes=2, batch_limit=1)
    rng = np.random.default_rng(5)
    tickets = [svc.submit("t", "A", rng.standard_normal(64), kind="spmv")
               for _ in range(4)]
    svc.run()
    assert svc.stats["quarantines"] == 1, svc.stats
    assert "node1" not in svc.nodes and svc.topo.n_nodes == 1
    assert svc.stats["recoveries"] >= 1
    assert all(t.request.status == "done" for t in tickets)


# ------------------------- fault-plan determinism --------------------------

def test_faultplan_random_message_kinds_pure():
    nodes = ["node0", "node1"]
    p1 = FaultPlan.random(3, nodes, 10, n_events=8, ppn=2)
    p2 = FaultPlan.random(3, nodes, 10, n_events=8, ppn=2)
    assert p1.events == p2.events          # pure function of the seed
    kinds = {e.kind for s in range(16)
             for e in FaultPlan.random(s, nodes, 10, n_events=8,
                                       ppn=2).events}
    assert kinds & set(FaultEvent.MESSAGE_KINDS)
    # without the node width the draw stays on the legacy kinds
    legacy = {e.kind for s in range(16)
              for e in FaultPlan.random(s, nodes, 10, n_events=8).events}
    assert not (legacy & set(FaultEvent.MESSAGE_KINDS))
    ev = duplicate_message(4, ("full", (0, 1), 1))
    assert ev.kind == "duplicate_message" and ev.step == 4


def test_spgemm_integrity_argument_validation():
    rng = np.random.default_rng(0)
    a = CSR.from_dense(rng.standard_normal((8, 8)))
    topo = Topology(2, 2)
    part = contiguous_partition(8, topo.n_procs)
    f = MessageFault(phase="inter", node=0, proc=0, slot=1)
    with pytest.raises(ValueError):        # faults need integrity on
        distributed_spgemm(a, a, part, part, topo, faults=[f])
    with pytest.raises(ValueError):        # simulate has no spgemm wire
        distributed_spgemm(a, a, part, part, topo, backend="simulate",
                           integrity="detect")
    with pytest.raises(ValueError):
        distributed_spgemm(a, a, part, part, topo, integrity="sometimes")


# ------------------------- shardmap program (subprocess) -------------------

def _run_prog(args, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)  # the program sets its own device count
    proc = subprocess.run(
        [sys.executable,
         str(ROOT / "tests" / "multidev" / "integrity_prog.py")] + args,
        capture_output=True, text=True, env=env, timeout=timeout)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "ALL OK" in proc.stdout


def test_integrity_shardmap_quick():
    """Tier-1 shardmap integrity smoke (subprocess; 4-device subset):
    detect attribution, ABFT, recover bit-identity, zero retraces."""
    _run_prog(["--quick"])


@pytest.mark.multidev
def test_integrity_shardmap_8dev_full():
    """Full 8-device program: every fault kind x every phase x both
    directions detected with correct attribution, recover bit-identical,
    SpGEMM integrity on the (2, 4) mesh."""
    _run_prog([])
