"""Measured weak/strong-scaling harness over the REAL operator stack.

The seed's paper-figure benchmarks modeled every wall with the Blue
Waters constants.  This module measures instead:

* :func:`measure_spmv` — end-to-end ``op @ x`` walls through
  ``repro.api.operator`` (pack → jitted shard_map exchange+compute →
  unpack), best-of-``repeats`` after a warm-up apply.
* :func:`measure_phase_walls` — per-phase EXCHANGE walls: each phase of
  the plan's :func:`repro.comm.cost.planned_traffic` is reproduced as a
  standalone jitted shard_map ``all_to_all`` with the plan's actual slot
  count and pad, timed in isolation.  These are the records
  :meth:`repro.core.cost_model.PostalParams.calibrated` fits.
* :func:`scaling_sweep` — a weak/strong ladder over (n_nodes, ppn)
  shapes × comm methods (standard vs nap vs multistep), emitting
  machine-readable walls + comm fractions + calibration records.

Run as its own process (it must force the XLA host device count before
jax initialises)::

    PYTHONPATH=src python -m repro.mesh.scaling config.json out.json

``config.json`` may override any :data:`DEFAULT_CONFIG` key.  Importing
this module never touches jax; every jax import lives inside a function.
"""
from __future__ import annotations

import json
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from repro.core.topology import Topology

__all__ = ["DEFAULT_CONFIG", "measure_phase_walls", "measure_spmv",
           "scaling_sweep", "calibration_records"]

DEFAULT_CONFIG: Dict[str, object] = {
    "mode": "strong",            # "strong" (fixed n) | "weak" (n per rank)
    "n_rows": 1024,              # strong: global rows; weak: rows PER RANK
    "nnz_per_row": 8,
    "seed": 0,
    "matrix": {"kind": "random"},  # or {"kind": "suitesparse_like",
                                   #     "name": ..., "scale": ...}
    "partition": "contiguous",   # contiguous | strided | balanced
    "ladder": [[1, 2], [2, 2], [2, 4]],   # (n_nodes, ppn) shapes
    "methods": ["standard", "nap", "multistep"],
    "repeats": 3,
}


def _best_of(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(max(1, int(repeats))):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _axis_slots(phase: str, topo: Topology):
    """(mesh axis, slot count) the lowering uses for one exchange phase."""
    if phase == "inter":
        return "node", topo.n_nodes
    if phase in ("direct", "pair"):
        return ("node", "proc"), topo.n_procs
    return "proc", topo.ppn           # full / init / final — intra-node


def measure_phase_walls(plan, topo: Topology, bytes_per_val: int = 4,
                        repeats: int = 3) -> List[Dict[str, object]]:
    """Measured wall per exchange phase of ``plan`` (standalone timers).

    Each non-empty phase of :func:`repro.comm.cost.planned_traffic` runs
    as a bare jitted shard_map ``all_to_all`` over the SAME mesh axis
    with the plan's slot count and pad — the exchange the full program
    issues, minus local compute.  The standard plan's flat pair exchange
    (accounted as ``pair_inter`` + ``pair_intra``) is one collective and
    is timed once, as ``pair``.

    Records carry ``n_msgs``/``nbytes`` per BOTTLENECK RANK (matching
    the postal model's charging) plus the measured ``seconds`` — the
    exact shape :meth:`PostalParams.calibrated` consumes.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.comm.cost import planned_traffic
    from repro.mesh.buffers import fetch_mesh_array, input_stager, mesh_for

    traffic = planned_traffic(plan, bytes_per_val=bytes_per_val)
    phases: Dict[str, Dict] = {}
    for name, ph in traffic["phases"].items():
        if ph["n_msgs"] == 0:
            continue
        if name.startswith("pair_"):   # one flat collective, two entries
            merged = phases.setdefault("pair", dict(ph, inter=True))
            merged["max_rank_msgs"] = max(merged["max_rank_msgs"],
                                          ph["max_rank_msgs"])
            continue
        phases[name] = ph

    mesh = mesh_for(topo)
    stage = input_stager(topo)
    spec = P("node", "proc")
    walls: List[Dict[str, object]] = []
    for name, ph in phases.items():
        axis, n_slots = _axis_slots(name, topo)
        pad = int(ph["pad"])

        def per_device(x, axis=axis):
            return jax.lax.all_to_all(x.reshape(-1), axis, 0, 0,
                                      tiled=True).reshape(x.shape)

        smapped = jax.shard_map(per_device, mesh=mesh, in_specs=(spec,),
                                out_specs=spec, check_vma=False)
        f = jax.jit(smapped)
        host = np.random.default_rng(0).standard_normal(
            (topo.n_nodes, topo.ppn, n_slots * pad)).astype(np.float32)
        x = stage(host)
        fetch_mesh_array(f(x))            # warm-up: trace + compile
        wall = _best_of(lambda: fetch_mesh_array(f(x)), repeats)
        walls.append({
            "phase": name,
            "inter": bool(ph["inter"]),
            "axis": "x".join(axis) if isinstance(axis, tuple) else axis,
            "n_slots": int(n_slots),
            "pad": pad,
            # bottleneck-rank charging, matching postal_phase_time
            "n_msgs": int(ph["max_rank_msgs"]),
            "nbytes": int(ph["max_rank_msgs"]) * pad * bytes_per_val,
            "seconds": float(wall),
        })
    return walls


def calibration_records(sweep: Dict[str, object]) -> List[Dict[str, object]]:
    """Flatten a :func:`scaling_sweep` payload into the wall records
    :meth:`PostalParams.calibrated` fits (one per measured phase)."""
    recs: List[Dict[str, object]] = []
    for point in sweep["points"]:
        for m in point["methods"].values():
            recs.extend(m["phase_walls"])
    return recs


def _build_matrix(cfg: Dict[str, object], n_rows: int, seed: int):
    mcfg = dict(cfg.get("matrix") or {"kind": "random"})
    if mcfg.get("kind") == "suitesparse_like":
        from repro.sparse import suitesparse_like
        return suitesparse_like.build(mcfg["name"], scale=int(mcfg["scale"]))
    from repro.sparse import random_fixed_nnz
    return random_fixed_nnz(n_rows, int(cfg.get("nnz_per_row", 8)), seed=seed)


def _build_partition(kind: str, a, n_procs: int):
    from repro.core.partition import make_partition
    if kind == "balanced":
        return make_partition("balanced", a.shape[0], n_procs,
                              a.indptr, a.indices)
    return make_partition(kind, a.shape[0], n_procs)


def measure_spmv(a, part, topo: Topology, method: str,
                 repeats: int = 3) -> Dict[str, object]:
    """Measured ``op @ x`` wall + per-phase exchange walls for one
    (matrix, partition, topology, method) point on the shardmap stack."""
    import repro.api as nap

    op = nap.operator(a, topo=topo, part=part, method=method,
                      backend="shardmap", cache=False)
    rng = np.random.default_rng(1)
    v = rng.standard_normal(a.shape[1])
    op @ v                                  # warm-up: compile + trace
    wall = _best_of(lambda: op @ v, repeats)
    phase_walls = measure_phase_walls(op.executor.compiled.plan, topo,
                                      repeats=repeats)
    comm_wall = sum(w["seconds"] for w in phase_walls)
    return {
        "wall_s": float(wall),
        "comm_wall_s": float(comm_wall),
        "comm_fraction": float(min(1.0, comm_wall / wall)) if wall else 0.0,
        "phase_walls": phase_walls,
    }


def scaling_sweep(config: Optional[Dict[str, object]] = None
                  ) -> Dict[str, object]:
    """Run the ladder described by ``config`` (see :data:`DEFAULT_CONFIG`).

    Ladder shapes needing more devices than the process addresses are
    skipped (recorded under ``"skipped"`` — no silent truncation).
    """
    import jax

    from repro.mesh.discover import discovery_report

    cfg = dict(DEFAULT_CONFIG)
    cfg.update(config or {})
    n_devices = int(jax.device_count())
    points: List[Dict[str, object]] = []
    skipped: List[Dict[str, object]] = []
    for nn, ppn in cfg["ladder"]:
        topo = Topology(n_nodes=int(nn), ppn=int(ppn))
        if topo.n_procs > n_devices:
            skipped.append({"n_nodes": nn, "ppn": ppn,
                            "reason": f"needs {topo.n_procs} devices, "
                                      f"have {n_devices}"})
            continue
        n_rows = (int(cfg["n_rows"]) * topo.n_procs
                  if cfg["mode"] == "weak" else int(cfg["n_rows"]))
        a = _build_matrix(cfg, n_rows, int(cfg["seed"]))
        if a.shape[0] < topo.n_procs:
            skipped.append({"n_nodes": nn, "ppn": ppn,
                            "reason": f"{a.shape[0]} rows < "
                                      f"{topo.n_procs} ranks"})
            continue
        part = _build_partition(str(cfg["partition"]), a, topo.n_procs)
        methods = {}
        for method in cfg["methods"]:
            methods[str(method)] = measure_spmv(a, part, topo, str(method),
                                                repeats=int(cfg["repeats"]))
        points.append({
            "n_nodes": topo.n_nodes, "ppn": topo.ppn,
            "n_rows": int(a.shape[0]), "nnz": int(a.nnz),
            "mode": cfg["mode"], "methods": methods,
        })
    return {"config": cfg, "discovery": discovery_report(),
            "points": points, "skipped": skipped}


def main(argv: List[str]) -> int:
    """Subprocess entry: force the device count for the LARGEST ladder
    shape before jax loads, sweep, write JSON."""
    if not argv or len(argv) > 2:
        print("usage: python -m repro.mesh.scaling config.json [out.json]",
              file=sys.stderr)
        return 2
    with open(argv[0]) as f:
        cfg = json.load(f)
    ladder = cfg.get("ladder", DEFAULT_CONFIG["ladder"])
    need = max(int(nn) * int(ppn) for nn, ppn in ladder)
    import os
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={need}"
    out = scaling_sweep(cfg)
    payload = json.dumps(out, indent=2)
    if len(argv) == 2:
        with open(argv[1], "w") as f:
            f.write(payload)
    else:
        print(payload)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
