"""The benchmark harness on the CPU at a tiny size: BENCHMARK.json keeps
to its contract, every configuration and mix builds, every one-chip cell
runs (with and without the trace) and comes out correct, a run without a
TPU exits non-zero with no result, and a new configuration is found by
its name alone.  The four-chip cell runs in
``test_bench_faults.py``'s subprocess on four CPU devices."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import run as R

REPO = R.REPO
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
CELLS = [w["name"] for w in SPEC["workloads"]]
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def tiny(cell: R.Cell) -> R.Cell:
    cell.cfg.update(cell.cfg["cpu_test_overrides"])
    return cell


def cpu_run(cell: R.Cell, seed: int = 2**31 + 11, seconds: float = 0.3,
            trace: bool = False, **kw) -> dict:
    import jax
    return R.run_cell(cell, seed, seconds, trace, jax.devices()[:cell.chips],
                      R.CompileClock(), None, **kw)


# -- BENCHMARK.json -----------------------------------------------------------

def test_benchmark_json_keeps_its_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 s
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    names = [m["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for m in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        assert c["file"].startswith("bench/")
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    pairs = {(w["config"], w["traffic"]) for w in SPEC["workloads"]}
    assert len(pairs) == len(SPEC["workloads"])
    for m in SPEC["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    c = R.load_cell(cell)
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(c.readers[m["name"]].read)


# -- configurations and mixes -------------------------------------------------

@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_config_builds_at_its_test_size(config):
    cfg = R._read_json(os.path.join(REPO, "bench", "configs",
                                    config + ".json"))
    build = R.load_module(os.path.join(REPO, "bench", "configs",
                                       config + ".py"), "cfg_" + config)
    cfg.update(cfg["cpu_test_overrides"])
    indptr, indices, data, shape = build.build(cfg, 2**31 + 5)
    assert indptr[0] == 0 and indptr[-1] == indices.size == data.size
    assert indices.min() >= 0 and indices.max() < shape[1]
    assert np.all(data.astype(np.float32) == data)
    for r in range(shape[0]):
        row = indices[indptr[r]:indptr[r + 1]]
        assert np.all(np.diff(row) > 0) and r in row
    again = build.build(cfg, 2**31 + 5)
    assert all(np.array_equal(a, b) for a, b in zip(again[:3],
                                                   (indptr, indices, data)))


def test_hpcg_matrix_is_the_27_point_stencil():
    cfg = R._read_json(os.path.join(REPO, "bench", "configs",
                                    "hpcg_27pt_104.json"))
    build = R.load_module(os.path.join(REPO, "bench", "configs",
                                       "hpcg_27pt_104.py"), "cfg_hpcg")
    indptr, indices, data, shape = build.build(cfg, 0)
    assert shape == (104 ** 3, 104 ** 3)
    assert indices.size == 310 ** 3          # (3 * 104 - 2)^3
    assert np.diff(indptr).max() == 27 and np.diff(indptr).min() == 8


def test_paper_random_rows_hold_a_fixed_count():
    cfg = R._read_json(os.path.join(REPO, "bench", "configs",
                                    "paper_random_25.json"))
    build = R.load_module(os.path.join(REPO, "bench", "configs",
                                       "paper_random_25.py"), "cfg_rand")
    cfg.update(cfg["cpu_test_overrides"])
    a = build.build(cfg, 1)
    b = build.build(cfg, 2)
    assert np.all(np.diff(a[0]) == cfg["nnz_per_row"])
    assert not np.array_equal(a[1], b[1])
    assert np.abs(a[2]).max() <= 1.0


@pytest.mark.parametrize("mix", sorted(
    {w["traffic"] for w in SPEC["workloads"]}))
def test_traffic_mix_parses(mix):
    from bench.drive import load_kind
    m = R._read_json(os.path.join(REPO, "bench", "traffic", mix + ".json"))
    kind = load_kind(m)
    assert m["limits"] and kind.COUNTS


@pytest.mark.parametrize("change", [
    {"clients": 4}, {"nv": "8"}, {"direction": "sideways"}, {"kind": "burst"},
    {"limits": {}}])
def test_a_mix_the_kind_would_not_read_is_refused(change):
    from bench.drive import load_kind
    m = R._read_json(os.path.join(REPO, "bench", "traffic", "spmv.json"))
    m.update(change)
    with pytest.raises(ValueError):
        load_kind(m)


def test_a_new_traffic_kind_is_found_by_its_name(tmp_path):
    """A kind of traffic is a module of its own: a new one is a new file
    in ``bench/kinds/`` and a mix that names it, with no other edit."""
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    src = (tmp_path / "bench" / "kinds" / "apply.py").read_text()
    (tmp_path / "bench" / "kinds" / "apply_twice.py").write_text(
        src.replace("y = op(operands[k])",
                    "y = op(operands[k])\n" + " " * 20 + "op(operands[k])"))
    mix = R._read_json(os.path.join(REPO, "bench", "traffic", "spmv.json"))
    mix["kind"] = "apply_twice"
    with open(tmp_path / "bench" / "traffic" / "twice.json", "w") as f:
        json.dump(mix, f)
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "paper_random_25.twice",
                              "config": "paper_random_25",
                              "traffic": "twice", "chips": 1, "why": "t"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and "paper_random_25.spmv" in m["workloads"]:
            m["workloads"].append("paper_random_25.twice")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    c = tiny(R.load_cell("paper_random_25.twice", repo=str(tmp_path)))
    assert c.kind.__file__.endswith("apply_twice.py")
    res = cpu_run(c)
    assert res["correct"] is True
    assert {"spmv_ms", "spmv_p95_ms", "setup_s"} == set(res["metrics"])


# -- whole runs on the CPU ------------------------------------------------------

@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_one_chip_cell_runs_correct_on_cpu(cell, trace):
    c = tiny(R.load_cell(cell))
    res = cpu_run(c, trace=trace)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    want = c.per_layer if trace else c.end_to_end
    # no peaks table entry for the CPU: the roofline readers stay silent
    expect = {m["name"] for m in want} - {"spmv_roofline",
                                          "ell_spmv_roofline"}
    assert set(res["metrics"]) == expect
    for v in res["metrics"].values():
        assert v["value"] >= 0
    if trace:
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"]


@pytest.mark.parametrize("cell", ["paper_random_25.spmv",
                                  "hpcg_27pt_104.cg50"])
def test_same_seed_same_inputs(cell):
    c = tiny(R.load_cell(cell))

    def inputs(seed):
        return c.kind.make_inputs(c.mix, c.builder.build(c.cfg, seed), seed)
    a, b, other = inputs(2**31 + 7), inputs(2**31 + 7), inputs(2**31 + 8)
    for k in a:
        assert all(np.array_equal(x, y) for x, y in zip(a[k], b[k]))
        assert not all(np.array_equal(x, y) for x, y in zip(a[k], other[k]))


def _run_script(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_exits_nonzero_with_no_result():
    p = _run_script(["bench/run.py", "--workload", CELLS[0], "--seed", "1",
                     "--seconds", "1", "--trace", "0"], REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_script(["bench/run.py", "--workload", CELLS[0], "--seed", "1",
                     "--seconds", "1", "--trace", "0"], str(tmp_path),
                    {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_a_new_config_is_found_by_its_name(tmp_path):
    """A configuration, its cell and nothing else are added as files; the
    harness runs it without an edit to any file it already has."""
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    cfg = R._read_json(os.path.join(REPO, "bench", "configs",
                                    "paper_random_25.json"))
    cfg.update(name="tiny_band", n_rows=300, nnz_per_row=3)
    with open(tmp_path / "bench" / "configs" / "tiny_band.json", "w") as f:
        json.dump(cfg, f)
    (tmp_path / "bench" / "configs" / "tiny_band.py").write_text(
        "import numpy as np\n"
        "def build(cfg, seed):\n"
        "    n = cfg['n_rows']\n"
        "    rows = np.arange(n)\n"
        "    cols = np.stack([(rows - 1) % n, rows, (rows + 1) % n], 1)\n"
        "    cols = np.sort(cols, axis=1).reshape(-1)\n"
        "    rng = np.random.default_rng(seed)\n"
        "    data = rng.uniform(-1, 1, cols.size).astype(np.float32)\n"
        "    return (np.arange(n + 1) * 3, cols, data.astype(np.float64),"
        " (n, n))\n")
    spec["configs"].append({"name": "tiny_band", "source": "test",
                            "file": "bench/configs/tiny_band.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny_band.spmv", "config": "tiny_band",
                              "traffic": "spmv", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and "paper_random_25.spmv" in m["workloads"]:
            m["workloads"].append("tiny_band.spmv")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    c = R.load_cell("tiny_band.spmv", repo=str(tmp_path))
    res = cpu_run(c)
    assert res["correct"] is True
    assert {"spmv_ms", "spmv_p95_ms", "setup_s"} == set(res["metrics"])


@pytest.mark.parametrize("direction,nv", [("transpose", 1), ("forward", 8)])
def test_a_new_mix_is_only_a_data_file(tmp_path, direction, nv):
    """The transpose and multi-column mixes that PERF.md keeps for later
    need a mix file and a cell entry, no code."""
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    mix = R._read_json(os.path.join(REPO, "bench", "traffic", "spmv.json"))
    mix.update(direction=direction, nv=nv)
    with open(tmp_path / "bench" / "traffic" / "new_mix.json", "w") as f:
        json.dump(mix, f)
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "paper_random_25.new_mix",
                              "config": "paper_random_25",
                              "traffic": "new_mix", "chips": 1, "why": "t"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and "paper_random_25.spmv" in m["workloads"]:
            m["workloads"].append("paper_random_25.new_mix")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    c = tiny(R.load_cell("paper_random_25.new_mix", repo=str(tmp_path)))
    assert cpu_run(c)["correct"] is True
    assert cpu_run(c, control=True)["correct"] is False
