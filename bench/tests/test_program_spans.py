"""The program's view of a trace (bench/program_spans.py): scope names
read from HLO text, the reduction on synthetic events with known
answers, and every reading on a trace recorded from the program on four
CPU devices (fixtures/cpu_x4_program.xplane.pb, with the scope map
recorded beside it); the benchmark's older recorded trace, which holds no
program span, gives none.

Re-record the fixture from the repository root with
``JAX_PLATFORMS=cpu python -m bench.tests.test_program_spans``."""
from __future__ import annotations

import json
import os
import types

import pytest

from bench import program_spans as PS
from bench import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "cpu_x4_program.xplane.pb")
SCOPES = os.path.join(HERE, "fixtures", "cpu_x4_program.scopes.json")
OLD_FIXTURE = os.path.join(HERE, "fixtures", "cpu_apply.xplane.pb")
DEVICES = [0, 1, 2, 3]
N_APPLIES, N_ITERS = 3, 3
PHASES = ["full", "init", "inter", "final"]
ns = 1e-9


def test_scope_names_come_from_hlo_text():
    text = (
        '  %fusion.4 = f32[2,8,1]{2,1,0:T(2,128)} fusion(f32[8,1] %p), '
        'kind=kCustom, calls=%fused.1, metadata={op_name="jit(traced)/'
        'shard_map/repro.exchange.full/gather" source_file="x.py"}\n'
        '  ROOT %while.3 = (s32[]) while(%t), body=%b, metadata={op_name='
        '"jit(traced)/shard_map/repro.local/while"}\n'
        '  %copy.1 = f32[8]{0} copy(%p)\n'
        '  %add.2 = f32[8]{0} add(%a, %b), metadata={op_name="jit(f)/add"}\n')
    assert PS.hlo_scopes(text) == {"fusion.4": "repro.exchange.full",
                                   "while.3": "repro.local"}
    assert PS.scope_of("a/repro.buffers/b/repro.local/c") == "repro.local"
    assert PS.scope_of("jit(f)/add") == ""


def _synthetic():
    # one chip; window 0..100 ns; one apply 10..90 with its steps
    ops = {0: [("gather", 20, 30), ("%all_to_all.1 = f32[4] all-to-all(x)",
                                    30, 34),
               ("%while.2 = (s32[]) while(%t)", 40, 70), ("fusion", 45, 60),
               ("copy", 70, 72)]}
    scopes = {0: ["repro.exchange.full", "repro.exchange.full",
                  "repro.local", "repro.local", ""]}
    spans = [("bench.window", 0, 100), ("bench.apply", 10, 90)]
    steps = [("repro.apply", 11, 89), ("repro.pack", 12, 18),
             ("repro.stage", 18, 19), ("repro.dispatch", 19, 20),
             ("repro.fetch", 20, 80), ("repro.unpack", 80, 88)]
    host = sorted(spans + steps + [("numpy", 13, 15)],
                  key=lambda e: (e[1], -e[2]))
    return T.Events(device_ops=ops, spans=spans, host_thread=host), scopes


def test_reduce_on_synthetic_events():
    ev, scopes = _synthetic()
    ps = PS.reduce(ev, scopes, [0])
    assert ps.counts == {"bench.apply": 1, "repro.apply": 1,
                         "repro.pack": 1, "repro.stage": 1,
                         "repro.dispatch": 1, "repro.fetch": 1,
                         "repro.unpack": 1}
    # busy 20..34 (the exchange) and 40..72 (the loop, then a copy)
    assert ps.step_host_s["repro.fetch"] == pytest.approx([14 * ns])
    assert ps.step_host_s["repro.pack"] == pytest.approx([6 * ns])
    assert {k: v[0] for k, v in ps.scope_dev["bench.apply"].items()} == \
        pytest.approx({"repro.exchange.full": 14 * ns,
                       "repro.local": 30 * ns, PS.UNSCOPED: 2 * ns})
    assert ps.busy_dev["repro.apply"] == pytest.approx({0: 46 * ns})
    assert ps.nested_s["bench.apply"]["repro.fetch"] == pytest.approx(
        60 * ns)
    assert ps.nested_s["repro.apply"]["repro.unpack"] == pytest.approx(
        8 * ns)
    assert PS.pack_ms(ps) == pytest.approx(14 * ns * 1e3)
    assert PS.transfer_ms(ps) == pytest.approx(15 * ns * 1e3)
    assert PS.exchange_ms(ps) == pytest.approx(14 * ns * 1e3)
    assert PS.cg_host_ms(ps) is None
    assert dict(ps.idle_gaps) == pytest.approx({
        "window": 20 * ns,                              # 0-10, 90-100
        "bench.apply": 2 * ns,                          # 10-11, 89-90
        "bench.apply>repro.apply": 2 * ns,              # 11-12, 88-89
        "bench.apply>repro.pack": 4 * ns,
        "bench.apply>repro.pack>numpy": 2 * ns,         # 13-15
        "bench.apply>repro.stage": 1 * ns,
        "bench.apply>repro.dispatch": 1 * ns,
        "bench.apply>repro.fetch": 14 * ns,             # 34-40, 72-80
        "bench.apply>repro.unpack": 8 * ns})


@pytest.fixture(scope="module")
def recorded():
    with open(SCOPES) as f:
        scopes = json.load(f)
    ev = T.load(FIXTURE)
    return ev, PS.reduce(ev, PS.op_scopes(FIXTURE, scopes), DEVICES)


def _run(ev):
    """What share_pct reads of a run: the benchmark's summary, a peak,
    and each chip's share of a 256-row product."""
    return types.SimpleNamespace(
        trace=T.reduce(ev, DEVICES), peaks={"hbm_bytes_per_s": 819e9},
        shares=[{"rows": 64, "nnz": 300, "x_entries": 64}] * 4,
        cell=types.SimpleNamespace(mix={"nv": 1}))


def test_recorded_program_trace_gives_every_reading(recorded):
    ev, ps = recorded
    assert ps.counts["bench.apply"] == N_APPLIES
    assert ps.counts["repro.cg.iteration"] == N_ITERS
    # the CG set's applies: one for the initial residual, one per iteration
    assert ps.counts["repro.apply"] == N_APPLIES + 1 + N_ITERS
    for step in ("repro.pack", "repro.stage", "repro.dispatch",
                 "repro.fetch", "repro.unpack"):
        assert ps.counts[step] == ps.counts["repro.apply"]
        assert ps.nested_s["repro.apply"][step] > 0
    # the CPU compiler fuses the buffers' gathers into their neighbours
    assert set(ps.scope_dev["bench.apply"]) >= {
        f"repro.exchange.{p}" for p in PHASES} | {"repro.local"}
    readings = {"pack_ms": PS.pack_ms(ps), "transfer_ms": PS.transfer_ms(ps),
                "exchange_ms": PS.exchange_ms(ps),
                "cg_host_ms": PS.cg_host_ms(ps),
                "local_spmv_roofline": PS.local_spmv_roofline(_run(ev), ps)}
    for name, value in readings.items():
        assert value is not None and value > 0, name
    assert readings["local_spmv_roofline"] < 100
    host = T.reduce(ev, DEVICES).span_host_s["bench.apply"]
    apply_host_ms = sum(host) / len(host) * 1e3
    assert readings["pack_ms"] + readings["transfer_ms"] <= apply_host_ms
    assert any(k.startswith("bench.apply>repro.") for k, _ in ps.idle_gaps)
    assert "repro.local=" in PS.scopes_line(ps)


def test_a_trace_without_program_spans_gives_none():
    ev = T.load(OLD_FIXTURE)
    ps = PS.reduce(ev, PS.op_scopes(OLD_FIXTURE, {}), [0])
    for read in (PS.pack_ms, PS.transfer_ms, PS.exchange_ms, PS.cg_host_ms):
        assert read(ps) is None
    assert PS.local_spmv_roofline(None, ps) is None
    assert PS.scopes_line(ps).startswith("scopes: no repro.apply")
    assert set(ps.scope_dev["bench.apply"]) == {PS.UNSCOPED}


@pytest.mark.parametrize("cell", ["paper_random_25.spmv",
                                  "hpcg_27pt_104.cg50"])
def test_a_traced_cell_run_gives_the_program_view(cell):
    import jax
    from bench import run as R
    c = R.load_cell(cell)
    c.cfg.update(c.cfg["cpu_test_overrides"])
    result, ps, run = PS.traced_run(c, 2**31 + 13, 0.3, jax.devices()[:1],
                                    None)
    assert result["correct"] is True
    assert T.Tracer is not None and T.Tracer.__module__ == "bench.trace"
    got = PS.view(ps, run)
    want = {"pack_ms", "transfer_ms"} | (
        {"local_ms"} if cell.endswith("spmv") else {"cg_host_ms"})
    assert {k for k, v in got.items() if v is not None} >= want
    assert got["local_spmv_roofline"] is None      # no peak for the CPU


def record_fixture() -> None:
    """Trace N_APPLIES applies of the node-aware operator on Topology(2, 2)
    and an N_ITERS-iteration CG set through it, under the benchmark's
    spans; keep the trace and the program's scope map."""
    import glob
    import shutil

    import jax
    import numpy as np

    import repro.api as nap
    from repro.amg.solve import cg_solve
    from repro.core.topology import Topology
    from repro.sparse.generators import poisson_2d

    span = jax.profiler.TraceAnnotation
    a = poisson_2d(16)
    op = nap.operator(a, topo=Topology(2, 2), comm="nap", local_compute="ell")
    xs = [np.random.default_rng(i).standard_normal(a.shape[1])
          .astype(np.float32) for i in range(N_APPLIES)]
    b = a.matvec(np.ones(a.shape[1]))

    def spmv(v):
        with span("bench.spmv"):
            return op(v)
    op(xs[0])
    cg_solve(None, b, tol=0.0, maxiter=2, spmv=op)
    tracer = T.Tracer()
    tracer.start()
    with span(T.WINDOW_SPAN):
        for x in xs:
            with span("bench.apply"):
                op(x)
        with span("bench.cg_set"):
            cg_solve(None, b, tol=0.0, maxiter=N_ITERS, spmv=spmv)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(os.path.join(tracer.dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))[0], FIXTURE)
    shutil.rmtree(tracer.dir)
    scopes = {m: s for m, s in PS.program_scopes().items() if s}
    with open(SCOPES, "w") as f:
        json.dump(scopes, f, indent=1, sort_keys=True)


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    record_fixture()
