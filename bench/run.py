#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

One process, on the chips of the machine it starts on:

1. finds a TPU with as many chips as the cell asks for, or exits 2 with
   no result;
2. keeps jax's persistent compilation cache in ``<checkout>/.jax_cache``
   (or where ``JAX_COMPILATION_CACHE_DIR`` says);
3. builds the cell's matrix and inputs from ``--seed`` with the
   configuration's own builder (``bench/configs/<config>.py``);
4. builds the operator through ``repro.api.operator(a, topo=...,
   local_compute="auto", comm="auto")``;
5. warms up the traffic's own shapes; all of that is ``setup_s``;
6. drives the traffic mix (``bench/traffic/<mix>.json``, through the
   module of its kind, ``bench/kinds/<kind>.py``) for ``--seconds``,
   under the profiler with ``--trace 1``;
7. compares what the window produced with the float64 reference;
8. prints each compared number beside its limit on stderr, then one JSON
   line on stdout: ``correct``, ``attempted``, ``failed``, ``metrics``,
   ``device``, with ``--trace 1`` ``breakdown``, and ``checks`` last.

Everything that belongs to one configuration, mix or metric is a file
found by its name in ``BENCHMARK.json``: ``bench/configs/<config>.json``
(+ ``.py``), ``bench/traffic/<mix>.json`` (+ ``bench/kinds/<kind>.py``)
and ``bench/metrics/<metric>.py`` (``read(run) -> float | None``).  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The machine holds no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- finding a cell's files by name -------------------------------------------

def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""

    name: str
    chips: int
    cfg: dict                   # bench/configs/<config>.json
    builder: object             # bench/configs/<config>.py
    mix: dict                   # bench/traffic/<mix>.json
    kind: object                # bench/kinds/<mix kind>.py
    end_to_end: List[dict]      # BENCHMARK.json metric entries of the cell
    per_layer: List[dict]
    readers: Dict[str, object]  # metric name -> bench/metrics/<name>.py


def load_cell(name: str, repo: str = REPO) -> Cell:
    from bench.drive import load_kind
    bench = _read_json(os.path.join(repo, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    root = os.path.join(repo, "bench")

    def mine(m, e2e_names=None):
        if "workloads" in m:
            return name in m["workloads"]
        return e2e_names is None or m["moves"] in e2e_names

    mix = _read_json(os.path.join(root, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if mine(m, names)]
    readers = {m["name"]: load_module(
        os.path.join(root, "metrics", m["name"] + ".py"),
        "bench_metric_" + m["name"].replace(".", "_").replace("-", "_"))
        for m in e2e + layer}
    return Cell(
        name=name, chips=int(w["chips"]),
        cfg=_read_json(os.path.join(repo, cfg["file"])),
        builder=load_module(os.path.join(root, "configs",
                                         w["config"] + ".py"),
                            "bench_config_" + w["config"]),
        mix=mix, kind=load_kind(mix, os.path.join(root, "kinds")),
        end_to_end=e2e, per_layer=layer, readers=readers)


# -- the chip and jax ---------------------------------------------------------

def find_chips(chips: int):
    """The first ``chips`` devices; raises :class:`NoChip` without a TPU
    or with too few chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax found "
                     f"{len(devices)}")
    return devices[:chips]


def use_compile_cache(repo: str = REPO) -> str:
    """jax's persistent compilation cache at a fixed path in the checkout
    (``JAX_COMPILATION_CACHE_DIR`` wins), every program cached."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(repo, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Seconds jax spends in XLA backend compiles (persistent-cache reads
    included), from jax's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration


def chip_shares(indptr, shape, chips: int) -> List[dict]:
    """Rows, nnz and owned x entries of each chip under a contiguous row
    split (remainder rows on the leading chips, as the operator's default
    partition)."""
    def bounds(n):
        base, extra = divmod(n, chips)
        counts = [base + (i < extra) for i in range(chips)]
        out, at = [], 0
        for c in counts:
            out.append((at, at + c))
            at += c
        return out
    return [{"rows": r1 - r0, "nnz": int(indptr[r1] - indptr[r0]),
             "x_entries": c1 - c0}
            for (r0, r1), (c0, c1) in zip(bounds(shape[0]), bounds(shape[1]))]


def latency_line(w) -> str:
    """The window's walls, one per counted apply or iteration, summed up:
    how many took over 1.5 times the median, and the seconds they lost."""
    import numpy as np
    lat = np.asarray(w.latencies)
    if not lat.size:
        return f"latencies ({w.counts}): none"
    med = float(np.median(lat))
    slow = lat[lat > 1.5 * med]
    return (f"latencies ({w.counts}, ms): n={lat.size} "
            f"min={lat.min() * 1e3:.6f} median={med * 1e3:.6f} "
            f"p95={np.percentile(lat, 95) * 1e3:.6f} "
            f"max={lat.max() * 1e3:.6f} over_1.5x_median={slow.size} "
            f"their_excess_s={float((slow - med).sum()):.6f}")


# -- one run ------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    cell: Cell
    setup: Dict[str, float]
    window: object                   # bench.drive.Window
    trace: Optional[object]          # bench.trace.Summary, --trace 1 only
    shares: List[dict]               # per chip, see chip_shares
    peaks: Optional[dict]            # bench.peaks entry of the device kind


def build_operator(cell: Cell, indptr, indices, data, shape):
    """The system under test, through its public entry."""
    import repro.api as nap
    from repro.core.topology import Topology
    from repro.sparse.csr import CSR
    a = CSR(indptr=indptr, indices=indices, data=data, shape=tuple(shape))
    topo = Topology(*cell.cfg["topology"][str(cell.chips)])
    op = nap.operator(a, topo=topo, local_compute="auto", comm="auto")
    report = op.autotune_report()          # compiles the plan
    return cell.kind.target(cell.mix, op), report


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             clock: CompileClock, peaks: Optional[dict],
             control: bool = False, t_start: float = T_START) -> dict:
    """Set up, measure and check one cell on ``devices``; returns the
    result object.  ``control`` puts the bfloat16 reference in the
    program's place (``bench/readings.py``)."""
    from bench import drive
    from bench.reference import Bf16Control, Reference
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device: {device}")
    setup = {"jax_init": time.perf_counter() - t_start}

    t = time.perf_counter()
    indptr, indices, data, shape = cell.builder.build(cell.cfg, seed)
    kind, mix = cell.kind, cell.mix
    view = kind.view(mix, indptr, indices, data, shape)
    inputs = kind.make_inputs(mix, view, seed)
    setup["generate"] = time.perf_counter() - t

    t = time.perf_counter()
    if control:
        op, report = Bf16Control(*view), {"control": "bf16"}
    else:
        op, report = build_operator(cell, indptr, indices, data, shape)
    setup["plan_compile"] = time.perf_counter() - t
    log(f"operator: rows={shape[0]} nnz={len(indices)} chips={cell.chips} "
        f"format fwd={report.get('resolved')} "
        f"T={report.get('transpose_resolved')} "
        f"comm={report.get('comm_resolved')}"
        f"/{report.get('comm_transpose_resolved')}")

    t, c0 = time.perf_counter(), clock.seconds
    kind.warm_up(mix, op, inputs)
    setup["xla_compile"] = clock.seconds - c0
    setup["warmup"] = time.perf_counter() - t - setup["xla_compile"]
    setup["total"] = time.perf_counter() - t_start
    log("setup_s split: " + " ".join(f"{k}={v:.6f}" for k, v in setup.items()))

    traces0 = dict(op.trace_counts()) if hasattr(op, "trace_counts") else {}
    # device id of each rank (mesh order), so chip shares meet their chips
    mesh = getattr(getattr(op, "executor", None), "mesh", None)
    rank_devices = ([d.id for d in mesh.devices.flat] if mesh is not None
                    else [d.id for d in devices])
    tracer = None
    if trace:
        from bench.trace import Tracer
        tracer = Tracer()
        tracer.start()
    span = drive.span_factory(trace)
    c0 = clock.seconds
    window = kind.run_window(mix, op, inputs, seconds, seed, span)
    summary = tracer.stop(rank_devices) if tracer else None
    retraces = {k: v - traces0.get(k, 0)
                for k, v in (op.trace_counts().items()
                             if hasattr(op, "trace_counts") else ())}
    log(f"window: {window.seconds:.6f}s completed={window.completed} "
        f"attempted={window.attempted} failed={window.failed} "
        f"xla_compile_in_window={clock.seconds - c0:.6f}s "
        f"retraces={retraces}")
    log(latency_line(window))
    for err in window.errors[:2]:
        log(err)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    device["memory_peak_bytes"] = int(peak)
    if summary is not None:
        device["busy_s"] = summary.busy_mean_s()
        device["window_s"] = summary.window_s
    del op
    gc.collect()

    checks = kind.compare(mix, Reference(*view), inputs, window)
    correct = window.failed == 0 and all(drive.passed(c) for c in checks)

    run = Run(cell=cell, setup=setup, window=window, trace=summary,
              shares=chip_shares(indptr, shape, cell.chips), peaks=peaks)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.readers[m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": window.attempted,
              "failed": window.failed, "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.device_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} "
            f"{'>=' if c.get('at_least') else '<='} limit {c['limit']!r} "
            f"{'ok' if drive.passed(c) else 'FAILED'}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        devices = find_chips(cell.chips)
    except NoChip as e:
        log(f"bench: {e}; nothing was measured")
        return 2
    from bench.peaks import peaks_for
    peaks = peaks_for(devices[0].device_kind)
    log(f"compile cache: {use_compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, CompileClock(), peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
