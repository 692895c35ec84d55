"""``scaling`` block of BENCH_spmv.json: MEASURED weak/strong walls.

Runs :func:`repro.mesh.scaling.scaling_sweep` over a small
(n_nodes, ppn) ladder — standard vs nap vs multistep through the real
``repro.api`` shardmap stack — plus the per-phase exchange walls and the
:meth:`PostalParams.calibrated` fit of the postal constants from those
walls.  The result is MERGED into an existing BENCH_spmv.json under the
``"scaling"`` key (other sections untouched) so benchmarks/run.py's
1.5x regression gate covers the flattened ``scaling.walls`` dict like
every other wall entry.

    PYTHONPATH=src python -m benchmarks.bench_scaling [--quick] [--out PATH]

Must run as its own process: it forces the device count before jax loads.
"""
from __future__ import annotations

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse
import dataclasses
import json

FULL_LADDER = [[1, 2], [2, 2], [2, 4]]
QUICK_LADDER = [[1, 2], [2, 2]]


def flatten_walls(sweep: dict) -> dict:
    """``{"<nn>x<ppn>.<method>.<wall>": seconds}`` — the flat dict the
    regression gate walks (point/method identity in the key, so baseline
    and fresh runs compare like with like)."""
    walls = {}
    for point in sweep["points"]:
        shape = f"{point['n_nodes']}x{point['ppn']}"
        for method, m in point["methods"].items():
            walls[f"{shape}.{method}.wall_s"] = m["wall_s"]
            walls[f"{shape}.{method}.comm_wall_s"] = m["comm_wall_s"]
    return walls


def run(quick: bool = False) -> dict:
    from repro.core.cost_model import PostalParams
    from repro.mesh.scaling import calibration_records, scaling_sweep

    config = {
        "mode": "strong",
        "n_rows": 2048,
        "nnz_per_row": 8,
        "ladder": QUICK_LADDER if quick else FULL_LADDER,
        "methods": ["standard", "nap", "multistep"],
        "repeats": 3,
    }
    sweep = scaling_sweep(config)
    records = calibration_records(sweep)
    params = PostalParams.calibrated(records)
    sweep["walls"] = flatten_walls(sweep)
    sweep["calibration"] = dict(dataclasses.asdict(params),
                                n_records=len(records))
    return sweep


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_spmv.json")
    args = ap.parse_args()
    from repro.compat import use_compile_cache
    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    sweep = run(quick=args.quick)

    payload = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            payload = json.load(f)
    payload["scaling"] = sweep
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)

    cal = sweep["calibration"]
    print(f"scaling: {len(sweep['points'])} points, "
          f"{len(sweep['skipped'])} skipped, "
          f"{cal['n_records']} calibration records")
    for key, wall in sorted(sweep["walls"].items()):
        print(f"  {key}: {wall * 1e3:.3f} ms")
    print(f"  calibrated postal: alpha_inter={cal['alpha_inter']:.3e}s "
          f"beta_inter={cal['beta_inter']:.3e}B/s "
          f"alpha_intra={cal['alpha_intra']:.3e}s "
          f"beta_intra={cal['beta_intra']:.3e}B/s")


if __name__ == "__main__":
    main()
