import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init); 512 host devices back both the 16x16 single-pod mesh
and the 2x16x16 multi-pod mesh.

Per cell this records into an incremental JSON (safe to re-run; finished
cells are skipped unless --force):
  * compile + lower wall time
  * memory_analysis (argument/output/temp/generated-code bytes per device)
  * cost_analysis flops/bytes (XLA's view, NOT trip-count aware)
  * hlo_analysis flops/bytes/collective bytes (trip-count aware) and the
    three roofline terms (core/roofline.py)

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out out.json]
  python -m repro.launch.dryrun --all --both-meshes
"""

import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs import all_arch_ids, get_config
from repro.configs.shapes import SHAPES, cell_runnable
from repro.core.hlo_analysis import analyze_hlo
from repro.core.roofline import build_roofline, model_flops_for
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell, lower_cell

DEFAULT_OUT = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun.json"


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save_hlo: str = "", overrides=None) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "ok": False}
    if overrides:
        rec["overrides"] = overrides
    runnable, why = cell_runnable(arch, shape_name)
    if not runnable:
        rec.update(skipped=True, reason=why, ok=True)
        return rec
    try:
        chips = 512 if multi_pod else 256
        # pin the chip budget: the dry-run cells are defined at 256/512
        # regardless of how many host devices back them
        mesh = make_production_mesh(multi_pod=multi_pod, n_devices=chips,
                                    n_pods=2 if multi_pod else None)
        t0 = time.time()
        cell = build_cell(arch, shape_name, mesh, multi_pod,
                          overrides=overrides)
        lowered = lower_cell(cell, mesh)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        ma = compiled.memory_analysis()
        mem = {
            "argument_gb": ma.argument_size_in_bytes / 1e9,
            "output_gb": ma.output_size_in_bytes / 1e9,
            "temp_gb": ma.temp_size_in_bytes / 1e9,
            "code_gb": getattr(ma, "generated_code_size_in_bytes", 0) / 1e9,
            "alias_gb": getattr(ma, "alias_size_in_bytes", 0) / 1e9,
        }
        # peak per device: args + temps (aliased/donated buffers overlap args).
        # NB the CPU backend's float-normalization pass materialises f32
        # copies of every bf16 weight/cache (TPU runs bf16 natively), so this
        # OVERSTATES the TPU footprint; `analytic` is the TPU-native budget.
        mem["peak_gb"] = mem["argument_gb"] + mem["temp_gb"]
        mem["analytic"] = cell.analytic_gb
        ca = compiled.cost_analysis()
        text = compiled.as_text()
        cost = analyze_hlo(text, pod_boundary=256 if multi_pod else 0)
        mf = model_flops_for(cell.kind, cell.n_active_params, cell.tokens)
        roof = build_roofline(arch, shape_name, mesh_name, chips, cost, mf)
        rec.update(
            ok=True, kind=cell.kind, chips=chips,
            t_lower_s=round(t_lower, 2), t_compile_s=round(t_compile, 2),
            memory=mem,
            xla_cost={"flops": ca.get("flops"),
                      "bytes": ca.get("bytes accessed")},
            hlo={"dot_flops": cost.dot_flops, "hbm_bytes": cost.hbm_bytes,
                 "collective_bytes": cost.collective_bytes,
                 "collective_counts": cost.collective_counts,
                 "dci_bytes": cost.dci_bytes},
            roofline={"t_compute": roof.t_compute, "t_memory": roof.t_memory,
                      "t_collective": roof.t_collective,
                      "t_collective_wire": roof.t_collective_wire,
                      "dominant": roof.dominant, "mfu": roof.mfu,
                      "model_flops": mf, "useful_ratio": roof.useful_ratio},
            tokens=cell.tokens, n_active_params=cell.n_active_params,
        )
        if save_hlo:
            import gzip
            with gzip.open(save_hlo, "wt") as f:
                f.write(text)
    except Exception as e:  # noqa: BLE001 — a failed cell is a result
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    return rec


def load_results(path: pathlib.Path) -> dict:
    if path.exists():
        return json.loads(path.read_text())
    return {"cells": {}}


def save_results(path: pathlib.Path, results: dict) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(results, indent=1))


def cell_key(arch, shape, multi_pod):
    return f"{arch}|{shape}|{'2x16x16' if multi_pod else '16x16'}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", default="")
    ap.add_argument("--out", default=str(DEFAULT_OUT))
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (perf iterations); "
                         "results stored under a suffixed cell key")
    ap.add_argument("--tag", default="",
                    help="suffix for the cell key of an override run")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                v = {"true": True, "false": False}.get(v.lower(), v)
        overrides[k] = v

    out = pathlib.Path(args.out)
    results = load_results(out)

    if args.all:
        archs = all_arch_ids()
        shapes = list(SHAPES)
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        archs, shapes = [args.arch], [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                key = cell_key(arch, shape, multi_pod)
                if args.tag:
                    key += f"#{args.tag}"
                if not args.force and results["cells"].get(key, {}).get("ok"):
                    print(f"[skip] {key} (cached)")
                    continue
                print(f"[run ] {key} ...", flush=True)
                rec = run_cell(arch, shape, multi_pod, save_hlo=args.save_hlo,
                               overrides=overrides or None)
                results["cells"][key] = rec
                save_results(out, results)
                if rec["ok"]:
                    if rec.get("skipped"):
                        print(f"       SKIP: {rec['reason']}")
                    else:
                        r = rec["roofline"]
                        print(f"       ok compile={rec['t_compile_s']}s "
                              f"peak={rec['memory']['peak_gb']:.1f}GB "
                              f"dom={r['dominant']} mfu={r['mfu']*100:.1f}%")
                else:
                    failures += 1
                    print(f"       FAIL: {rec['error']}")
    print(f"done; {failures} failures -> {out}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
