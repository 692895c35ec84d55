"""The trace reduction (bench/trace.py): interval arithmetic on synthetic
events with known answers, and the whole reduction on a small trace
recorded on the CPU (fixtures/cpu_apply.xplane.pb).

Re-record the fixture with ``python -m bench.tests.test_bench_trace``
from the repository root (``JAX_PLATFORMS=cpu``)."""
from __future__ import annotations

import os

import pytest

from bench import trace as T

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "cpu_apply.xplane.pb")
N_APPLIES = 5


def test_merged_union_covered_and_gaps():
    m = T.Merged([(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)])
    assert list(zip(m.starts, m.ends)) == [(0, 3), (5, 9), (12, 13)]
    assert m.covered(0, 13) == 3 + 4 + 1
    assert m.covered(2, 6) == 1 + 1
    assert m.covered(9, 12) == 0
    assert m.gaps(1, 14) == [(3, 5), (9, 12), (13, 14)]
    assert m.gaps(5, 9) == []


def test_nest_finds_the_innermost_event():
    nest = T.Nest([("outer", 0, 100), ("a", 10, 20), ("a.1", 12, 14),
                   ("b", 30, 40), ("c", 60, 90)])
    assert nest.innermost(13) == "a.1"
    assert nest.innermost(15) == "a"
    assert nest.innermost(50) == "outer"
    assert nest.innermost(95) == "outer"
    assert nest.innermost(150) == ""


def test_self_times_take_nested_ops_out():
    ops = [("%while.1 = (...) while(...)", 0, 100),
           ("%fusion.2 = f32[8] fusion(...)", 10, 40),
           ("%fusion.2 = f32[8] fusion(...)", 50, 80), ("copy.3", 120, 130)]
    assert T.self_times(ops) == {"while.1": 40, "fusion.2": 60, "copy.3": 10}
    assert T.op_name("%all_to_all.4 = f32[4] all-to-all(x)") == "all_to_all.4"


@pytest.mark.parametrize("name,opcode", [
    # HLO text as a TPU trace names its ops
    ("%all_to_all.13 = f32[2,16345,1]{1,2,0:T(1,128)S(1)} all-to-all("
     "%reshape.148), channel_id=1, replica_groups={{0,1},{2,3}}",
     "all-to-all"),
    ("%while.14 = (s32[]{:T(128)}, f32[1124864,1]{0,1:T(1,128)}, "
     "/*index=5*/s32[]{:T(128)}) while(%tuple.3), condition=%cond",
     "while"),
    ("%fusion.4 = f32[1,1]{0,1:T(1,128)} fusion(f32[1,1]{0,1:T(1,128)} "
     "%constant_dynamic-slice_fusion.2), kind=kLoop, calls=%fused.16",
     "fusion"),
    ("%copy-done.6 = s32[1,1,128]{2,1,0:T(1,128)S(1)} copy-done(%cs.6)",
     "copy-done"),
    # instruction names alone, as the CPU backend names them
    ("all-to-all", "all-to-all"), ("while.13", "while"),
    ("multiply_add_fusion", "multiply_add_fusion")])
def test_opcode_is_read_from_the_hlo_text(name, opcode):
    assert T.opcode(name) == opcode


def _synthetic():
    # two chips; window 0..100 ns; two applies, 10..50 and 60..90
    a2a = "%all_to_all.1 = f32[4] all-to-all(%x)"
    loop = "%while.2 = (s32[], f32[4]) while(%t)"
    ops = {0: [("fusion", 15, 25), (a2a, 25, 30), (loop, 65, 75),
               ("fusion", 66, 70)],
           1: [("fusion", 20, 35), (a2a, 35, 37), (loop, 70, 80)]}
    spans = [("bench.window", 0, 100), ("bench.apply", 10, 50),
             ("bench.apply", 60, 90)]
    host = spans + [("PjitFunction(run)", 12, 40), ("unpack", 40, 50)]
    host = sorted(host, key=lambda e: (e[1], -e[2]))
    return T.Events(device_ops=ops, spans=spans, host_thread=host)


def test_reduce_on_synthetic_events():
    s = T.reduce(_synthetic(), [0, 1])
    ns = 1e-9
    assert s.window_s == pytest.approx(100 * ns)
    assert s.busy_s == pytest.approx({0: 25 * ns, 1: 27 * ns})
    assert s.span_collective_dev["bench.apply"] == pytest.approx(
        {0: 5 * ns, 1: 2 * ns})
    assert s.span_loop_dev["bench.apply"] == pytest.approx(
        {0: 10 * ns, 1: 10 * ns})
    assert s.span_walls["bench.apply"] == pytest.approx([40 * ns, 30 * ns])
    # any chip busy: 15..37 in the first apply, 65..80 in the second
    assert s.span_host_s["bench.apply"] == pytest.approx([18 * ns, 15 * ns])
    assert s.span_busy_dev["bench.apply"] == pytest.approx(
        {0: 25 * ns, 1: 27 * ns})
    assert dict(s.device_ops) == pytest.approx(
        {"fusion": 14.5 * ns, "all_to_all.1": 3.5 * ns, "while.2": 8 * ns})
    idle = dict(s.idle_gaps)
    assert idle == pytest.approx({
        "window": (10 + 10 + 10) * ns,                 # 0-10, 50-60, 90-100
        "bench.apply>PjitFunction(run)": (3 + 3) * ns,  # 12-15, 37-40
        "bench.apply": (2 + 5 + 10) * ns,               # 10-12, 60-65, 80-90
        "bench.apply>unpack": 10 * ns})                 # 40-50
    assert sum(idle.values()) + 37 * ns == pytest.approx(s.window_s)


def test_reduce_wants_one_window():
    ev = _synthetic()
    ev.spans = [sp for sp in ev.spans if sp[0] != "bench.window"]
    with pytest.raises(ValueError):
        T.reduce(ev, [0])


def test_reduce_on_a_recorded_cpu_trace():
    s = T.reduce(T.load(FIXTURE), [0])
    assert 0 < s.window_s < 60
    assert 0 < s.busy_s[0] <= s.window_s
    walls = s.span_walls["bench.apply"]
    assert len(walls) == N_APPLIES
    for wall, host in zip(walls, s.span_host_s["bench.apply"]):
        assert 0 <= host <= wall
    assert sum(walls) <= s.window_s
    assert s.span_busy_dev["bench.apply"][0] <= s.busy_s[0] + 1e-12
    assert any(name.startswith("dot") for name, _ in s.device_ops)
    assert sum(v for _, v in s.idle_gaps) <= s.window_s - s.busy_s[0] + 1e-9
    assert s.span_collective_dev["bench.apply"] == {0: 0.0}


def record_fixture(path: str = FIXTURE) -> None:
    """Trace N_APPLIES small jitted products under the benchmark's spans
    and keep the trace file at ``path``."""
    import glob
    import shutil
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    tracer = T.Tracer()
    tracer.start()
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for i in range(N_APPLIES):
            with jax.profiler.TraceAnnotation("bench.apply"):
                f(x + i).block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(os.path.join(tracer.dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))[0], path)
    shutil.rmtree(tracer.dir)


if __name__ == "__main__":
    record_fixture()
