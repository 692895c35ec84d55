#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` over many seeds, in one
process: the program's (lower readings) or, with ``--control``, the
bfloat16 reference's in the program's place (upper readings).

    python bench/readings.py --workload <cell> --seeds 11,12,13 \
        --seconds 5 [--control]

Each seed is a whole run of the cell (set-up, a ``--seconds`` window at
the cell's own load, the comparison) and prints one JSON line on stdout
with the seed, ``correct``, the compared numbers and the end-to-end
metrics.  The benchmark's own runs (``bench/run.py``) never run the
control.  Needs the chips the cell asks for, like ``run.py``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from run import (NoChip, CompileClock, find_chips, load_cell, log,
                 run_cell, use_compile_cache)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        devices = find_chips(cell.chips)
    except NoChip as e:
        log(f"readings: {e}")
        return 2
    from bench.peaks import peaks_for
    from repro.core.spmv_jax import clear_compile_cache
    peaks = peaks_for(devices[0].device_kind)
    use_compile_cache()
    clock = CompileClock()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run_cell(cell, seed, args.seconds, False, devices, clock,
                       peaks, control=args.control,
                       t_start=time.perf_counter())
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": args.control,
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "metrics": res["metrics"]}), flush=True)
        clear_compile_cache()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
