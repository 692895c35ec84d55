"""The four-chip cell on four CPU devices, sound and with faults planted
underneath its timed path; prints one JSON line per run.  Started as its
own process by test_bench_faults.py (XLA_FLAGS has to be set before jax
is imported)."""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import run as R  # noqa: E402
from bench.tests.test_bench_faults import altered  # noqa: E402
from repro.core.spmv_jax import clear_compile_cache  # noqa: E402
import repro.mesh.buffers as buffers  # noqa: E402


def run(name):
    cell = R.load_cell("paper_random_25.spmv_x4")
    cell.cfg.update(cell.cfg["cpu_test_overrides"])
    res = R.run_cell(cell, 2**31 + 99, 0.3, name == "sound_traced",
                     jax.devices()[:4], R.CompileClock(), None)
    print(json.dumps({"run": name, "correct": res["correct"],
                      "checks": res["checks"],
                      "metrics": sorted(res["metrics"])}), flush=True)
    clear_compile_cache()


run("sound")
run("sound_traced")
fetch = buffers.fetch_mesh_array
buffers.fetch_mesh_array = altered(fetch)
run("answer_altered")
buffers.fetch_mesh_array = fetch
jax.lax.all_to_all = lambda x, *a, **k: jnp.zeros_like(x)
run("exchange_dropped")
