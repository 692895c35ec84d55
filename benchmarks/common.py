"""Shared helpers for the paper-figure benchmarks.

All Blue Waters numbers are MODEL-derived (Eqs. 10-12 with the paper's
Tables 3-4 constants): this container has no Gemini interconnect to measure.
The experiments reproduce the *structure* of each figure — which algorithm
wins, where, and by how much — at simulation scale (32 nodes x 16 ppn).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from repro.configs.paper_spmv import CONFIG
from repro.core.comm_graph import (build_nap_plan, build_standard_plan,
                                   nap_stats, standard_stats)
from repro.core.cost_model import (BLUE_WATERS, compute_time, nap_cost,
                                   standard_cost)
from repro.core.partition import make_partition
from repro.core.topology import Topology
from repro.sparse.csr import CSR


def default_topology() -> Topology:
    return Topology(n_nodes=CONFIG.n_nodes, ppn=CONFIG.ppn)


def spmv_times(a: CSR, part, topo: Topology, bytes_per_val: int = 8
               ) -> Dict[str, float]:
    """Modeled standard vs NAP SpMV times (comm + local compute)."""
    std = build_standard_plan(a.indptr, a.indices, part, topo)
    nap = build_nap_plan(a.indptr, a.indices, part, topo,
                         pairing=CONFIG.pairing)
    t_std = standard_cost(std, BLUE_WATERS, bytes_per_val)["total"]
    t_nap = nap_cost(nap, BLUE_WATERS, bytes_per_val)["total"]
    comp = compute_time(int(np.diff(a.indptr).max()) * 1)  # rough per-rank
    nnz_per_rank = a.nnz / topo.n_procs
    comp = compute_time(int(nnz_per_rank))
    return {
        "standard": t_std + comp,
        "nap": t_nap + comp,
        "standard_comm": t_std,
        "nap_comm": t_nap,
        "compute": comp,
        "speedup": (t_std + comp) / max(t_nap + comp, 1e-30),
    }


def measured_sweep(config: Dict) -> Dict:
    """Run :mod:`repro.mesh.scaling` in its own process and return the
    sweep payload.

    A subprocess is mandatory, not a convenience: the harness must force
    the XLA host device count for the ladder's largest shape before jax
    initialises, and the figure driver's jax is already up on one
    device.  Any inherited forced count is dropped so the child sizes
    its own platform.  CPU-only: a parent whose backend is up holds a
    chip, so this must not run on a TPU machine.
    """
    import json
    import os
    import subprocess
    import sys
    import tempfile

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    with tempfile.TemporaryDirectory() as td:
        cfg_path = os.path.join(td, "config.json")
        out_path = os.path.join(td, "out.json")
        with open(cfg_path, "w") as f:
            json.dump(config, f)
        proc = subprocess.run(
            [sys.executable, "-m", "repro.mesh.scaling", cfg_path, out_path],
            env=env, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"repro.mesh.scaling failed:\n{proc.stderr[-4000:]}")
        with open(out_path) as f:
            return json.load(f)


def message_stats(a: CSR, part, topo: Topology) -> Dict[str, Dict]:
    std = build_standard_plan(a.indptr, a.indices, part, topo)
    nap = build_nap_plan(a.indptr, a.indices, part, topo,
                         pairing=CONFIG.pairing)
    return {"standard": standard_stats(std), "nap": nap_stats(nap)}


class Table:
    def __init__(self, title: str, columns: List[str]):
        self.title = title
        self.columns = columns
        self.rows: List[List] = []

    def add(self, *row) -> None:
        self.rows.append(list(row))

    def render(self) -> str:
        widths = [max(len(str(c)), *(len(_fmt(r[i])) for r in self.rows))
                  if self.rows else len(str(c))
                  for i, c in enumerate(self.columns)]
        out = [f"== {self.title} =="]
        out.append(" | ".join(str(c).ljust(w) for c, w in zip(self.columns, widths)))
        out.append("-+-".join("-" * w for w in widths))
        for r in self.rows:
            out.append(" | ".join(_fmt(v).ljust(w) for v, w in zip(r, widths)))
        return "\n".join(out)


def _fmt(v) -> str:
    if isinstance(v, float):
        if v == 0:
            return "0"
        if abs(v) < 1e-3 or abs(v) >= 1e5:
            return f"{v:.3e}"
        return f"{v:.4g}"
    return str(v)
