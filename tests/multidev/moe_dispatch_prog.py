"""Multi-device MoE dispatch verification (subprocess; 2 pods x 4 chips).

flat and nap sharded dispatch must match the dense-masked oracle, and the
nap mode must put FEWER bytes on the inter-pod all-to-all when top_k spreads
a token over several experts of one remote pod.  The quantized wire must
SHRINK the measured pod-crossing bytes while staying inside the modeled
error budget, and the registered executor path (``backend="moe"``) must
agree with the island and carry the integrity surface over QUANTIZED
messages.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.core.hlo_analysis import analyze_hlo
from repro.models.moe import EPInfo, moe_apply_local, moe_apply_sharded, moe_init

cfg0 = get_reduced("qwen3-moe-235b-a22b").replace(
    n_experts=8, top_k=4, moe_dff=32, d_model=32, capacity_factor=8.0)
from repro.compat import make_mesh

mesh = make_mesh((2, 4), ("pod", "model"))
params = moe_init(jax.random.key(0), cfg0, jnp.float32)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((4, 16, cfg0.d_model)) * 0.3, jnp.float32)
want = np.asarray(moe_apply_local(params, cfg0, x))

def run_island(cfg):
    ep = EPInfo(inner_axis="model", pod_axis="pod")
    fn = jax.jit(lambda p, xx: moe_apply_sharded(p, cfg, xx, ep, mesh))
    with jax.set_mesh(mesh):
        compiled = fn.lower(params, x).compile()
        got = np.asarray(fn(params, x))
    # pod_boundary=4: devices 0-3 are pod 0, 4-7 pod 1 on the (2, 4) mesh
    return got, analyze_hlo(compiled.as_text(), pod_boundary=4)


a2a_bytes, dci_bytes, outs = {}, {}, {}
for mode in ("flat", "nap"):
    got, cost = run_island(cfg0.replace(moe_dispatch=mode))
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, (mode, err)
    a2a_bytes[mode] = cost.total_collective_bytes
    dci_bytes[mode] = cost.dci_bytes
    outs[mode] = got
    print(mode, "err", err, "coll bytes", a2a_bytes[mode],
          "dci bytes", dci_bytes[mode])
assert dci_bytes["nap"] < dci_bytes["flat"], \
    "nap must put fewer bytes on the inter-pod boundary"

# quantized wire: measured DCI bytes SHRINK, error stays inside the budget
from repro.moe import wire_error_bound

scale = np.abs(want).max()
for wd in ("bf16", "fp8_e4m3"):
    wcfg = cfg0.replace(moe_dispatch="nap", wire_dtype=wd)
    got, cost = run_island(wcfg)
    err = np.abs(got - outs["nap"]).max() / scale
    bound = wire_error_bound(wcfg)
    assert cost.dci_bytes < dci_bytes["nap"], (wd, cost.dci_bytes)
    assert err <= bound, (wd, err, bound)
    print(wd, "dci bytes", cost.dci_bytes, "err", err, "budget", bound)


# gradient path agrees with the oracle too
def loss(p, xx, m):
    c = cfg0.replace(moe_dispatch=m)
    ep = EPInfo(inner_axis="model", pod_axis="pod")
    return (moe_apply_sharded(p, c, xx, ep, mesh) ** 2).sum()

def loss_ref(p, xx):
    return (moe_apply_local(p, cfg0, xx) ** 2).sum()

g_ref = jax.grad(loss_ref)(params, x)
with jax.set_mesh(mesh):
    g_nap = jax.jit(jax.grad(lambda p, xx: loss(p, xx, "nap")))(params, x)
for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_nap)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-3, atol=5e-4)
print("grads ok")

# ---------------------------------------------------------------------------
# registered executor path: dispatch_operator on this mesh + integrity
# over a corrupted QUANTIZED message
# ---------------------------------------------------------------------------
import repro.api as nap_api
from repro.moe.dispatch import dispatch_operator, topology_of_mesh
from repro.moe.plan import (dispatch_partitions, representative_routing,
                            routing_matrix)

topo = topology_of_mesh(mesh)
assert (topo.n_nodes, topo.ppn) == (2, 4), topo
acfg = cfg0.replace(moe_dispatch="auto", wire_dtype="fp8_e4m3")
op = dispatch_operator(acfg, mesh, n_tokens=128, integrity="detect")
ids, w = representative_routing(128, cfg0.n_experts, cfg0.top_k)
r = routing_matrix(ids, w, cfg0.n_experts)
ep_, tp_ = dispatch_partitions(cfg0.n_experts, 128, topo)
xv = np.random.default_rng(2).standard_normal((128, cfg0.d_model))
ref = nap_api.operator(r, topo=topo, row_part=ep_, col_part=tp_,
                       backend="simulate", method="nap") @ xv
out = op @ xv                                   # clean quantized apply
assert np.all(np.isfinite(out)) and not np.array_equal(out, ref)
rel = np.abs(out - ref).max() / np.abs(ref).max()
assert rel < 0.2, rel                           # fp8 ballpark, budget in tier-1
op.inject_fault("inter", kind="bitflip", node=1, proc=0, slot=0,
                element=2, bit=6)
try:
    op @ xv
    raise AssertionError("corrupted quantized message must raise")
except nap_api.IntegrityError as e:
    assert e.mismatches and e.mismatches[0].phase == "inter"
rep = op.integrity_report()
assert rep["faults_injected"] == 1 and rep["wire_mismatches"] == 1, rep
print("executor path ok (auto+fp8 on the mesh topology; quantized "
      "fault detected and attributed)")
print("ALL OK")
