#!/usr/bin/env python3
"""Time the ELL slot loop either side of the TPU compiler's row-tile classes.

    python3 scripts/slot_loop_tiles.py [--seed N] [--reps 15] [--out FILE]

The TPU compiler lays the slot loop's ``[rows]`` vectors in tiles of 1024
rows and picks one of two loop structures from the row count: where the
last tile is full or nearly so, the slot's column ids are read by a
``dynamic-slice`` into an ``[rows, 1]`` array; otherwise by a ``reduce``
over the slot axis.  On a TPU v5e the ``reduce`` loop ran 6.8% faster at
nv 1 and the ``dynamic-slice`` loop 30% faster at nv 8 (PERF.md).
:func:`repro.sparse.ell.slot_loop_rows` pads every stacked ELL to 512
past a multiple of 1024 for nv 1, and the product's loop skips those rows
at nv > 1; this script re-measures both when the compiler changes.

Each case times ``ell_spmm_packed`` as the shard program runs it
(``form`` "program": ``n_rows`` the real rows) at the unpadded and the
padded count, interleaved, ``--reps`` times after two warm-up calls, and
prints the median, the nanoseconds per real slot and the loop structure
the chip's compiler chose.  At nv > 1 the program's loop skips the
padding rows, so there a padded count is also timed with the loop over
every row (``form`` "every_row"), which is the class that count falls
in.  The combine case times the row-split layout's sorted
``segment_sum`` into 2^20 and 2^20 + 512 rows.  Inputs are drawn on the
device from ``--seed``: uniform column ids over the case's x domain, as
the paper's random matrices gather.  Exits 2 without a TPU; run by no
benchmark cell.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.ops import segment_sum  # noqa: E402

from repro.kernels.ell_spmv import ell_spmm_packed  # noqa: E402

M = 1 << 20
ONE_CHIP_X = (M, 128, 128)                           # v_loc | on | off
X4_X = (1 << 18, 2 * 261519, 2 * 262143, 2 * 261504)  # v_loc | received
HPCG = 1_124_864

# (name, rows, kmax, x segments, [row counts the loop runs], nvs)
CASES = [
    ("random_2e20", M, 25, ONE_CHIP_X,
     [M, M + 256, M + 512, M + 768, M + 900], (1,)),
    ("random_2e20", M, 25, ONE_CHIP_X, [M, M + 512], (8,)),
    ("random_x4", 1 << 18, 25, X4_X, [1 << 18, (1 << 18) + 512], (1, 8)),
    ("hpcg_rows", HPCG, 27, (HPCG, 128, 128), [HPCG], (1, 8)),
    ("hpcg_transpose_rows", HPCG + 256, 27, (HPCG,),
     [HPCG + 256, HPCG + 1024], (1,)),
]
COMBINE_PARTIALS = 3_735_040  # the transposed 2^20 x 25 matrix at K = 8


def loop_class(text: str) -> str:
    """The slot loop's structure in compiled TPU HLO: ``reduce`` or
    ``dynamic-slice`` (how the loop body reads a slot's column ids)."""
    if re.search(r"%maximum_reduce_fusion[.\d]* = ", text):
        return "reduce"
    if re.search(r"%dynamic-slice_maximum_fusion[.\d]* = ", text):
        return "dynamic-slice"
    return "unknown"


def _once(fn, args) -> float:
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


def ell_case(key, rows, kmax, segs, counts, nv, reps):
    kc, kv, kx = jax.random.split(key, 3)
    n_x = sum(segs)
    cols = jax.random.randint(kc, (rows, kmax), 0, n_x, jnp.int32)
    vals = jax.random.uniform(kv, (rows, kmax), jnp.float32, -1.0, 1.0)
    x = jax.random.normal(kx, (n_x, nv), jnp.float32)
    xs = tuple(x[sum(segs[:i]): sum(segs[: i + 1])]
               for i in range(len(segs)))
    variants = []
    for n in counts:
        pad = n - rows
        c = jnp.concatenate([cols, jnp.full((max(pad, 0), kmax), -1,
                                            jnp.int32)])[:n]
        v = jnp.concatenate([vals, jnp.zeros((max(pad, 0), kmax),
                                             jnp.float32)])[:n]
        real = min(rows, n)
        forms = {"program": lambda c, v, *xs, real=real: ell_spmm_packed(
            c, v, xs, n_rows=real)}
        if nv > 1 and n > real:
            forms["every_row"] = lambda c, v, *xs, real=real: \
                ell_spmm_packed(c, v, xs)[:real]
        args = (c, v) + xs
        for form, body in forms.items():
            fn = jax.jit(body)
            text = fn.lower(*args).compile().as_text()
            for _ in range(2):
                _once(fn, args)
            variants.append(dict(rows_run=n, real_rows=real, form=form,
                                 fn=fn, args=args, loop=loop_class(text),
                                 times=[]))
    for _ in range(reps):           # interleaved, so drift hits all alike
        for var in variants:
            var["times"].append(_once(var["fn"], var["args"]))
    out = []
    for var in variants:
        ms = 1e3 * statistics.median(var["times"])
        out.append(dict(rows_run=var["rows_run"],
                        residue=var["rows_run"] % 1024, form=var["form"],
                        real_rows=var["real_rows"], kmax=kmax, nv=nv,
                        n_x=n_x, loop=var["loop"], median_ms=ms,
                        min_ms=1e3 * min(var["times"]),
                        max_ms=1e3 * max(var["times"]),
                        ns_per_slot=ms * 1e6 / (var["real_rows"] * kmax)))
    return out


def combine_case(key, reps):
    """Sorted ``segment_sum`` of the chunk partials into 2^20 and
    2^20 + 512 rows (the row-split layout's combine)."""
    kp, kr = jax.random.split(key)
    per_row = -(-(jax.random.poisson(kr, 24.0, (M,)) + 1) // 8)
    chunk_row = jnp.repeat(jnp.arange(M, dtype=jnp.int32), per_row,
                           total_repeat_length=COMBINE_PARTIALS)
    partial = jax.random.normal(kp, (COMBINE_PARTIALS, 1), jnp.float32)
    variants = []
    for n in (M, M + 512):
        fn = jax.jit(lambda w, r, n=n: segment_sum(
            w, r, num_segments=n, indices_are_sorted=True)[:M])
        for _ in range(2):
            _once(fn, (partial, chunk_row))
        variants.append(dict(n=n, fn=fn, times=[]))
    for _ in range(reps):
        for var in variants:
            var["times"].append(_once(var["fn"], (partial, chunk_row)))
    return [dict(segments=var["n"], residue=var["n"] % 1024,
                 partials=COMBINE_PARTIALS,
                 median_ms=1e3 * statistics.median(var["times"]),
                 min_ms=1e3 * min(var["times"]),
                 max_ms=1e3 * max(var["times"]))
            for var in variants]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=2147700001)
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--out", help="also write every reading to this JSON file")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: jax found {dev.platform}", file=sys.stderr)
        return 2
    key = jax.random.key(args.seed % 2**32)
    rows = []
    for i, (name, n, kmax, segs, counts, nvs) in enumerate(CASES):
        for nv in nvs:
            for r in ell_case(jax.random.fold_in(key, i), n, kmax, segs,
                              counts, nv, args.reps):
                rows.append(dict(case=name, **r))
                print(json.dumps(rows[-1]), flush=True)
    combine = combine_case(jax.random.fold_in(key, len(CASES)), args.reps)
    for r in combine:
        print(json.dumps(dict(case="combine", **r)), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": {"platform": dev.platform,
                                  "device_kind": dev.device_kind},
                       "seed": args.seed, "reps": args.reps,
                       "slot_loop": rows, "combine": combine}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
