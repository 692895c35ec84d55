"""Benchmark driver: one experiment per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--quick]

CPU-only until the chip benchmark exists: every wall here comes from
XLA's CPU backend and the Pallas interpreter on forced host devices, and
the parent starts its JAX children with no backend of its own.  On a
machine with a TPU, use ``python chip_smoke.py`` (one process per chip).

The run fails (non-zero exit) if the freshly measured BENCH_spmv.json
regresses plan-compile or local-compute wall time by more than
``REGRESSION_FACTOR`` versus the committed baseline — keep it green
across PRs.  The gate walks EVERY key shared by the two ``spmv_wall.wall``
dicts, which includes the operator-level end-to-end walls
(``operator_forward_nv*_s`` / ``operator_transpose_nv*_s`` — the
`repro.api` pack->run->unpack path) alongside the shard-level executor
walls.  The MEASURED scaling block (benchmarks/bench_scaling.py over
repro.mesh.scaling) rides the same gate: every ``scaling.walls`` entry
shared by baseline and fresh payloads is compared whenever the sweep
configs match, and so do the MoE dispatch island walls
(``moe_dispatch.walls``) whenever that block's config matches.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REGRESSION_FACTOR = 1.5
# interpret-mode walls in the low-ms range jitter well past 1.5x on a
# shared CPU even with best-of-iters timing; a regression must also
# clear an absolute floor so scheduler noise can't fail the gate while
# a real slowdown (ms -> tens of ms) still does.
REGRESSION_MIN_DELTA_S = 0.005


def check_regressions(baseline: dict, fresh: dict,
                      factor: float = REGRESSION_FACTOR,
                      min_delta: float = REGRESSION_MIN_DELTA_S) -> list:
    """Compare the perf fields shared by two BENCH_spmv.json payloads.

    Sections whose problem size differs between the payloads (e.g. a
    --quick baseline vs a full run) are skipped — same keys, different
    workloads, not comparable.
    """
    regs = []

    def compare(label: str, old, new) -> None:
        if old and new and new > factor * old and new - old > min_delta:
            regs.append(f"{label}: {old}s -> {new}s (> {factor}x)")

    old_pc, new_pc = baseline.get("plan_compile", {}), fresh.get("plan_compile", {})
    if old_pc.get("n_rows") == new_pc.get("n_rows"):
        compare("plan_compile.vectorized_s",
                old_pc.get("vectorized_s"), new_pc.get("vectorized_s"))
    old_sw, new_sw = baseline.get("spmv_wall", {}), fresh.get("spmv_wall", {})
    if old_sw.get("n_rows") == new_sw.get("n_rows"):
        old_wall = old_sw.get("wall", {})
        new_wall = new_sw.get("wall", {})
        for k in sorted(set(old_wall) & set(new_wall)):
            compare(f"spmv_wall.wall.{k}", old_wall[k], new_wall[k])
    old_sc, new_sc = baseline.get("scaling", {}), fresh.get("scaling", {})
    if old_sc.get("config") and old_sc.get("config") == new_sc.get("config"):
        old_walls = old_sc.get("walls", {})
        new_walls = new_sc.get("walls", {})
        for k in sorted(set(old_walls) & set(new_walls)):
            compare(f"scaling.walls.{k}", old_walls[k], new_walls[k])
    # MoE dispatch island walls: same config (geometry + token count) ->
    # same workload, comparable
    old_md, new_md = baseline.get("moe_dispatch", {}), fresh.get("moe_dispatch", {})
    if old_md.get("config") and old_md.get("config") == new_md.get("config"):
        old_walls = old_md.get("walls", {})
        new_walls = new_md.get("walls", {})
        for k in sorted(set(old_walls) & set(new_walls)):
            compare(f"moe_dispatch.walls.{k}", old_walls[k], new_walls[k])
    return regs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the slowest experiments (fig13-15)")
    args = ap.parse_args()
    t_start = time.time()

    from benchmarks import (fig02_comm_fraction, fig05_message_model,
                            fig08_10_amg_levels, fig11_12_random,
                            roofline_cells)

    print("#" * 72)
    print("# NAPSpMV benchmark suite — all Blue Waters numbers are")
    print("# cost-model-derived (Eqs. 10-12, Tables 3-4); roofline numbers")
    print("# come from the compiled multi-pod dry-run (results/dryrun.json).")
    print("#" * 72, flush=True)

    print(fig02_comm_fraction.run().render())
    print()
    print(fig02_comm_fraction.run_measured().render())
    print()
    print(fig05_message_model.run().render())
    print()
    for prob in ("anisotropic", "elasticity"):
        for t in fig08_10_amg_levels.run(prob):
            print(t.render())
            print()
    print(fig11_12_random.run_fig11().render())
    print()
    print(fig11_12_random.run_fig12().render())
    print()
    if not args.quick:
        from benchmarks import fig13_15_suitesparse
        t13, t14 = fig13_15_suitesparse.run_fig13_14()
        print(t13.render())
        print()
        print(t14.render())
        print()
        print(fig13_15_suitesparse.run_fig15().render())
        print()
        print(fig13_15_suitesparse.run_measured().render())
        print()
    print(roofline_cells.run().render())

    # machine-readable SpMV perf trajectory (own process: it forces the
    # host device count before jax initialises)
    baseline = None
    if os.path.exists("BENCH_spmv.json"):
        with open("BENCH_spmv.json") as f:
            baseline = json.load(f)
    cmd = [sys.executable, "-m", "benchmarks.bench_spmv",
           "--out", "BENCH_spmv.json"] + (["--quick"] if args.quick else [])
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    print(proc.stdout, end="")
    if proc.returncode != 0:
        print(f"bench_spmv FAILED:\n{proc.stderr}", flush=True)
        raise SystemExit(proc.returncode)

    # measured scaling walls merge into the same payload (own process:
    # it too forces the host device count before jax initialises)
    cmd = [sys.executable, "-m", "benchmarks.bench_scaling",
           "--out", "BENCH_spmv.json"] + (["--quick"] if args.quick else [])
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    print(proc.stdout, end="")
    if proc.returncode != 0:
        print(f"bench_scaling FAILED:\n{proc.stderr}", flush=True)
        raise SystemExit(proc.returncode)

    if baseline is not None:
        with open("BENCH_spmv.json") as f:
            fresh = json.load(f)
        regs = check_regressions(baseline, fresh)
        if regs:
            # keep the baseline in place so a rerun can't silently pass by
            # comparing the regressed numbers against themselves; park the
            # failing measurement next to it for inspection
            with open("BENCH_spmv.rejected.json", "w") as f:
                json.dump(fresh, f, indent=2)
            with open("BENCH_spmv.json", "w") as f:
                json.dump(baseline, f, indent=2)
            print("PERF REGRESSION vs committed BENCH_spmv.json baseline "
                  "(fresh numbers parked in BENCH_spmv.rejected.json):")
            for r in regs:
                print(f"  {r}")
            raise SystemExit(1)
        print("no perf regressions vs committed baseline "
              f"(threshold {REGRESSION_FACTOR}x)")

    print(f"\nall benchmarks done in {time.time() - t_start:.1f}s")


if __name__ == "__main__":
    main()
