"""NAPSpMV applied to Mixture-of-Experts dispatch (the paper -> LMs bridge).

Exercises the first-class MoE dispatch subsystem (:mod:`repro.moe`) on a
simulated 2-pod x 4-chip mesh, from both of its faces:

1. **In-graph** (the training/serving path): the SAME MoE layer through
   its dispatch modes and wire dtypes via ``moe_apply_sharded``, showing
   * all modes agree numerically with the dense-masked oracle,
   * the NAP (3-step, pod-deduplicated) dispatch injects FEWER bytes
     across the inter-pod boundary than the flat all-to-all — the
     paper's E(n, m) dedup, applied to tokens routed to multiple
     experts on one remote pod, measured from the compiled HLO, and
   * quantized wire payloads (``wire_dtype="bf16" | "fp8_e4m3"``) cut
     the measured pod-crossing bytes again while staying inside the
     modeled error budget.
2. **Registered-operator** (the plan/analysis path):
   ``dispatch_operator`` compiles a concrete routing into the NAP plan
   machinery — per-direction flat-vs-nap verdicts and slot-granular
   quantized byte accounting, no devices required.

    PYTHONPATH=src python examples/moe_nap_dispatch.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np
import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.configs import get_reduced
from repro.core.hlo_analysis import analyze_hlo
from repro.models.moe import EPInfo, moe_apply_local, moe_apply_sharded, moe_init
from repro.moe import wire_error_bound
from repro.moe.dispatch import dispatch_operator


def main() -> None:
    cfg = get_reduced("qwen3-moe-235b-a22b").replace(
        n_experts=8, top_k=4, moe_dff=64, d_model=64, capacity_factor=8.0)
    mesh = make_mesh((2, 4), ("pod", "model"))
    params = moe_init(jax.random.key(0), cfg, jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 16, cfg.d_model)) * 0.3,
                    jnp.float32)

    want = moe_apply_local(params, cfg, x)
    ep = EPInfo(inner_axis="model", pod_axis="pod")

    def run(mcfg):
        fn = jax.jit(lambda p, xx: moe_apply_sharded(p, mcfg, xx, ep, mesh))
        with jax.set_mesh(mesh):
            compiled = fn.lower(params, x).compile()
            got = np.asarray(fn(params, x))
        # pod_boundary=4: devices 0-3 are pod 0, 4-7 pod 1 on the (2,4) mesh
        return got, analyze_hlo(compiled.as_text(), pod_boundary=4)

    # ---- in-graph: flat vs nap at f32 (measured from the compiled HLO) ----
    results = {}
    for mode in ("flat", "nap"):
        got, cost = run(cfg.replace(moe_dispatch=mode))
        results[mode] = (cost.dci_bytes, cost.total_collective_bytes, got)
        err = np.abs(got - np.asarray(want)).max() / np.abs(np.asarray(want)).max()
        print(f"{mode:4s} dispatch: max rel err vs dense oracle = {err:.2e}, "
              f"pod-crossing (DCI) bytes = {cost.dci_bytes:,.0f}, "
              f"total = {cost.total_collective_bytes:,.0f}")
        assert err < 1e-4, f"{mode} dispatch diverged from the oracle"

    (flat_dci, flat_tot, _), (nap_dci, nap_tot, nap_out) = \
        results["flat"], results["nap"]
    print(f"\nEXPENSIVE-axis (inter-pod) bytes: flat {flat_dci:,.0f} -> "
          f"nap {nap_dci:,.0f}  ({flat_dci / max(nap_dci, 1):.2f}x less)")
    print(f"cheap intra-pod bytes grow: {flat_tot - flat_dci:,.0f} -> "
          f"{nap_tot - nap_dci:,.0f} — the paper's Figs. 8-vs-9 trade.")
    assert nap_dci < flat_dci, "NAP must reduce pod-crossing traffic"

    # ---- in-graph: quantized wire payloads on the nap exchange ------------
    print("\nquantized wire (nap dispatch):")
    scale = np.abs(np.asarray(want)).max()
    for wd in ("bf16", "fp8_e4m3"):
        wcfg = cfg.replace(moe_dispatch="nap", wire_dtype=wd)
        got, cost = run(wcfg)
        err = np.abs(got - nap_out).max() / scale
        bound = wire_error_bound(wcfg)
        print(f"  {wd:8s}: DCI bytes = {cost.dci_bytes:,.0f} "
              f"({nap_dci / max(cost.dci_bytes, 1):.2f}x less than f32), "
              f"rel err vs f32 = {err:.2e} (budget {bound:.2e})")
        assert cost.dci_bytes < nap_dci, f"{wd} must shrink the DCI bytes"

    # ---- registered operator: routing compiled into the plan machinery ----
    print("\ndispatch_operator (plan layer, auto mode):")
    acfg = cfg.replace(moe_dispatch="auto", wire_dtype="fp8_e4m3")
    op = dispatch_operator(acfg, mesh, n_tokens=256)
    rep = op.autotune_report()
    st = op.stats()
    print(f"  per-direction verdicts: dispatch={rep['dispatch_resolved']} "
          f"combine={rep['combine_resolved']}")
    print(f"  modeled injected inter-pod bytes/RHS: "
          f"dispatch {st['dispatch_injected_inter_bytes']:,} "
          f"combine {st['combine_injected_inter_bytes']:,} "
          f"at {st['bytes_per_val']} B/value on the wire")


if __name__ == "__main__":
    main()
