"""First-class MoE NAP dispatch: the in-graph executors + operator entry.

This module is the home of the distributed MoE dispatch that used to be
private to ``models/moe.py``, promoted to a subsystem with two faces:

* :func:`moe_apply_sharded` — the in-graph shard_map path the LM stack
  (training ``examples/train_lm.py`` and serving) routes through.  The
  three modes mirror the paper: ``flat`` is the Algorithm-1 analogue
  (one capacity-padded all-to-all over the flat expert-parallel axes,
  every (token, expert-choice) copy crossing separately), ``nap`` the
  Algorithms-2+3 analogue (per-destination-POD dedup — a token bound
  for several experts on one remote pod crosses DCI once, the paper's
  E(n, m) — one aggregated inter-pod all-to-all, intra-pod fan-out,
  and the transpose route for the weighted combine), and ``auto``
  resolves per layer from the modeled injected inter-pod bytes of
  :func:`repro.moe.plan.choose_dispatch` at trace time.
* :func:`dispatch_operator` — compiles a CONCRETE token -> expert
  routing into the real NAP plan machinery through the executor
  registry (``backend="moe"``, methods ``flat | nap | auto`` in
  :mod:`repro.core.executors`): ``op @ x`` is the weighted
  dispatch-sum ``R @ X``, ``op.T @ y`` the weighted combine
  ``R.T @ Y``, with quantized wire payloads, slot-granular traffic
  accounting, postal cost, and the integrity surface
  (``detect``/``recover`` over checksums of the QUANTIZED words).

Wire quantization (``cfg.wire_dtype``, :mod:`repro.moe.wire`) encodes
the token payload ONCE at the pack boundary — the gateway that builds
the per-destination buffer — ships the narrow words through every hop
(the nap relay forwards wire words, it never re-rounds), and decodes to
f32 on the receive side before any accumulation.  The combine path
re-encodes at each genuine re-accumulation point (expert outputs onto
the inner wire, the pod gateway's local gather-back onto the DCI wire),
so nap pays at most 2 combine hops — the budget
:func:`repro.moe.wire.wire_error_bound` charges.  ``wire_dtype="f32"``
inserts NOTHING: the jaxpr is bit-for-bit the unquantized program.

Static-shape realisation is unchanged from the private implementation:
all buffers are capacity-padded; FIFO slots are assigned by cumsum and
overflowing copies are dropped (standard MoE token dropping;
capacity_factor controls the padding the paper's T/U balancing
minimises).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from repro.core.topology import Topology
from repro.moe.plan import (DISPATCH_MODES, choose_dispatch,
                            dispatch_partitions, representative_routing,
                            routing_matrix)
from repro.moe.wire import check_wire_dtype, decode_jnp, encode_jnp

__all__ = [
    "EPInfo", "moe_apply_sharded", "dispatch_operator",
    "resolve_dispatch_mode", "topology_of_mesh",
]


# ---------------------------------------------------------------------------
# expert-parallel geometry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EPInfo:
    """Expert-parallel geometry: which mesh axes hold experts.

    axes ordering is (outer, inner) = (pod, model); single-pod meshes pass
    pod_axis=None and the nap mode degenerates to flat over `inner`.
    """
    inner_axis: str = "model"
    pod_axis: Optional[str] = None

    @property
    def manual_axes(self) -> Tuple[str, ...]:
        return ((self.pod_axis,) if self.pod_axis else ()) + (self.inner_axis,)


def topology_of_mesh(mesh, ep: Optional[EPInfo] = None) -> Topology:
    """Map a device mesh's EP axes onto the plan layer's Topology:
    one "node" per pod, ``ppn`` inner (model) chips."""
    ep = ep or EPInfo(inner_axis="model", pod_axis="pod")
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_in = shape[ep.inner_axis]
    n_out = shape.get(ep.pod_axis, 1) if ep.pod_axis else 1
    return Topology(n_nodes=n_out, ppn=n_in)


# ---------------------------------------------------------------------------
# router / shared-expert pieces (referenced by models/moe.py's oracle too)
# ---------------------------------------------------------------------------

def _router(p, cfg, x2d: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Return (weights [T, K], expert ids [T, K]); normalized top-k softmax."""
    logits = (x2d.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = lax.top_k(probs, cfg.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, ids.astype(jnp.int32)


def _shared_ffn(p, x):
    s = p["shared"]
    return (jax.nn.silu(x @ s["w_gate"]) * (x @ s["w_up"])) @ s["w_down"]


# ---------------------------------------------------------------------------
# in-graph dispatch (shard_map; flat and nap modes, quantized wire)
# ---------------------------------------------------------------------------

def _a2a_wire(q: jax.Array, axes, wire_dtype: str) -> jax.Array:
    """``lax.all_to_all`` pinned to the wire dtype.

    XLA is free to hoist the receive-side decode across a collective —
    it folds ``convert(a2a(convert(x)))`` into an f32 exchange (same
    values, but the WIRE carries full-width words and the measured DCI
    bytes don't shrink; XLA:CPU even deletes optimization barriers
    placed around the collective).  Bitcasting the quantized payload to
    its same-width unsigned-integer WORDS defeats the fold: float
    converts cannot commute with an integer-typed collective, so the
    compiled all-to-all ships u16/u8.  The f32 identity path inserts
    nothing, preserving bit-identity with the pre-wire program.
    """
    if wire_dtype == "f32":
        return lax.all_to_all(q, axes, 0, 0, tiled=True)
    wdt = q.dtype
    words = lax.bitcast_convert_type(q, jnp.dtype(f"uint{wdt.itemsize * 8}"))
    out = lax.all_to_all(words, axes, 0, 0, tiled=True)
    return lax.bitcast_convert_type(out, wdt)


def _fifo_slots(need: jax.Array, capacity: int) -> jax.Array:
    """need [T, n_dst] bool -> slot [T, n_dst] in [0, capacity) or `capacity`
    (dropped; scatter mode='drop' discards it)."""
    slots = jnp.cumsum(need.astype(jnp.int32), axis=0) - 1
    return jnp.where(need & (slots < capacity), slots, capacity)


def _expert_compute(p_loc, cfg, tokens: jax.Array, meta_e: jax.Array,
                    meta_w: jax.Array, e_base: jax.Array, E_loc: int,
                    capacity: int) -> jax.Array:
    """Run this chip's experts over arrived copies.

    tokens [R, d]; meta_e [R, K] global expert ids (-1 pad); meta_w [R, K]
    router weights; e_base scalar — first global expert id on this chip.
    p_loc: expert weights [E_loc, d, ff] etc.
    Returns per-copy outputs [R, d] = sum over my experts hit by the copy.
    """
    R, d = tokens.shape
    out = jnp.zeros((R, d), jnp.float32)
    for el in range(E_loc):                      # static small loop
        gid = e_base + el
        hit = (meta_e == gid)
        w = (meta_w * hit).sum(-1)               # [R] combined weight
        need = hit.any(-1)
        slot = _fifo_slots(need[:, None], capacity)[:, 0]
        buf = jnp.zeros((capacity + 1, d), tokens.dtype).at[slot].set(
            tokens, mode="drop")[:capacity]
        h = jax.nn.silu(buf @ p_loc["w_gate"][el]) * (buf @ p_loc["w_up"][el])
        y = (h @ p_loc["w_down"][el]).astype(jnp.float32)
        back = jnp.where(slot[:, None] < capacity, y[jnp.minimum(slot, capacity - 1)], 0.0)
        out = out + back * w[:, None]
    return out


def resolve_dispatch_mode(cfg, n_pods: int, n_inner: int,
                          tokens_per_pod: int) -> Tuple[str, Dict]:
    """Resolve ``moe_dispatch="auto"`` from the modeled injected
    inter-pod bytes of a seeded representative routing (uniform expert
    choice at ``cfg.top_k`` — the capacity-factor design point).  Pure
    host numpy over static shapes, so it runs at trace time; memoized
    per geometry."""
    return _resolve_cached(cfg.n_experts, cfg.top_k, cfg.d_model,
                           getattr(cfg, "wire_dtype", "f32"),
                           n_pods, n_inner, tokens_per_pod)


@functools.lru_cache(maxsize=64)
def _resolve_cached(n_experts: int, top_k: int, d_model: int, wire_dtype: str,
                    n_pods: int, n_inner: int,
                    tokens_per_pod: int) -> Tuple[str, Dict]:
    topo = Topology(n_nodes=n_pods, ppn=n_inner)
    t_global = tokens_per_pod * n_pods
    ids, w = representative_routing(t_global, n_experts, top_k, seed=0)
    r = routing_matrix(ids, w, n_experts)
    expert_part, token_part = dispatch_partitions(n_experts, t_global, topo)
    v = choose_dispatch(r, expert_part, token_part, topo,
                        wire_dtype=wire_dtype, nv=d_model)
    return v["dispatch"]["chosen"], {"dispatch": v["dispatch"],
                                     "combine": v["combine"]}


def moe_apply_sharded(p, cfg, x: jax.Array, ep: EPInfo, mesh) -> jax.Array:
    """Distributed MoE: x [B, S, d] (batch sharded over dp axes, replicated
    over the EP axes); experts sharded over ep.manual_axes."""
    B, S, d = x.shape
    in_dtype = x.dtype

    def island(x_blk, router, w_gate, w_up, w_down):
        # f32 at the shard_map boundary: the transpose-of-replication psum
        # the autodiff inserts for x must be f32 — XLA:CPU's
        # all-reduce-promotion pass CHECK-fails on bf16 psums whose reduction
        # computation carries a trailing `copy` (backend bug, documented in
        # DESIGN.md); compute inside stays in the model dtype.
        y = _moe_island(cfg, ep, x_blk.astype(in_dtype), router,
                        w_gate, w_up, w_down)
        return y.astype(jnp.float32)

    from jax.sharding import PartitionSpec as P
    pod = ep.pod_axis
    x_spec = P(pod, None, None) if pod else P(None, None, None)
    e_spec = P(ep.manual_axes if pod else ep.inner_axis)
    out = jax.shard_map(
        island, mesh=mesh,
        in_specs=(x_spec, P(), e_spec, e_spec, e_spec),
        out_specs=x_spec,
        axis_names=set(ep.manual_axes),
        check_vma=False,
    )(x.astype(jnp.float32), p["router"], p["w_gate"], p["w_up"],
      p["w_down"]).astype(in_dtype)
    if cfg.n_shared_experts:
        out = out + _shared_ffn(p, x.reshape(-1, d)).reshape(B, S, d)
    return out


def _moe_island(cfg, ep, x, router, w_gate, w_up, w_down):
    """Manual-collective MoE over the EP axes; runs per (pod?, model) chip."""
    n_in = lax.axis_size(ep.inner_axis)
    n_out = lax.axis_size(ep.pod_axis) if ep.pod_axis else 1
    my_in = lax.axis_index(ep.inner_axis)
    my_out = lax.axis_index(ep.pod_axis) if ep.pod_axis else 0
    n_chips = n_in * n_out
    E, E_loc = cfg.n_experts, cfg.n_experts // n_chips
    B, S, d = x.shape
    T = B * S
    x2 = x.reshape(T, d)
    wd = check_wire_dtype(getattr(cfg, "wire_dtype", "f32"))

    # every inner-axis instance holds the same tokens (activations are
    # replicated over TP); instance m becomes the *gateway* for chunk m —
    # the paper's T/U distribution of node-level sends over local processes.
    Tc = T // n_in
    chunk = lax.dynamic_slice_in_dim(x2, my_in * Tc, Tc, 0)
    w, ids = _router({"router": router}, cfg, chunk)       # [Tc, K]
    K = cfg.top_k
    dst_chip = ids // E_loc                                # global EP chip
    # NB: global chip id c = pod * n_in + inner  (experts laid out pod-major)

    cap_factor = cfg.capacity_factor
    mode = cfg.moe_dispatch if (ep.pod_axis and n_out > 1) else "flat"
    if mode == "auto":
        # static-shape host resolution at trace time (modeled inter-pod
        # bytes on a representative routing; memoized per geometry)
        mode, _ = resolve_dispatch_mode(cfg, n_out, n_in, T)

    if mode == "flat":
        # ---- Algorithm 1 analogue: per-(token, k) copies, flat a2a --------
        capacity = max(1, int(Tc * K * cap_factor / n_chips))
        need = jnp.zeros((Tc, n_chips), bool)
        send_slot = jnp.full((Tc, K), capacity, jnp.int32)
        # sequential-k FIFO so each (t, k) copy gets its own slot
        counts = jnp.zeros((n_chips,), jnp.int32)
        toks = jnp.zeros((n_chips, capacity, d), x.dtype)
        meta_e = jnp.full((n_chips, capacity, K), -1, jnp.int32)
        meta_w = jnp.zeros((n_chips, capacity, K), jnp.float32)
        for k in range(K):                                  # static loop
            c = dst_chip[:, k]
            onehot = jax.nn.one_hot(c, n_chips, dtype=jnp.int32)
            slot = counts[None, :] + jnp.cumsum(onehot, 0) - onehot
            slot_k = (slot * onehot).sum(-1)                # [Tc]
            slot_k = jnp.where(slot_k < capacity, slot_k, capacity)
            toks = toks.at[c, slot_k].set(chunk, mode="drop")
            me = jnp.full((Tc, K), -1, jnp.int32).at[:, 0].set(ids[:, k])
            mw = jnp.zeros((Tc, K), jnp.float32).at[:, 0].set(w[:, k])
            meta_e = meta_e.at[c, slot_k].set(me, mode="drop")
            meta_w = meta_w.at[c, slot_k].set(mw, mode="drop")
            send_slot = send_slot.at[:, k].set(slot_k)
            counts = counts + onehot.sum(0)
        axes = ep.manual_axes if ep.pod_axis else ep.inner_axis
        # wire: encode at the pack boundary, ship narrow, f32 on receive
        r_toks = _a2a_wire(encode_jnp(toks, wd), axes, wd)
        r_me = lax.all_to_all(meta_e, axes, 0, 0, tiled=True)
        r_mw = lax.all_to_all(meta_w, axes, 0, 0, tiled=True)
        e_base = (my_out * n_in + my_in) * E_loc
        cap_e = max(1, int(Tc * K * cap_factor / E_loc))
        y = _expert_compute({"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
                            cfg, decode_jnp(r_toks, wd, x.dtype).reshape(-1, d),
                            r_me.reshape(-1, K), r_mw.reshape(-1, K),
                            e_base, E_loc, cap_e)
        # transpose route back: outputs in the same slots (y re-encoded —
        # expert outputs are a fresh payload for the return wire)
        y = decode_jnp(
            _a2a_wire(encode_jnp(y.reshape(n_chips, capacity, d), wd),
                      axes, wd), wd)
        out_chunk = jnp.zeros((Tc, d), jnp.float32)
        for k in range(K):
            c, s = dst_chip[:, k], send_slot[:, k]
            val = jnp.where((s < capacity)[:, None],
                            y[c, jnp.minimum(s, capacity - 1)], 0.0)
            out_chunk = out_chunk + val
    else:
        # ---- NAPSpMV 3-step: pod-dedup -> one DCI a2a -> local fan-out -----
        # dedup bound: a token crosses to pod o at most ONCE, so cap_pod = Tc
        # is exact (no drops at the DCI stage) — vs Tc*K/n_out copies in flat.
        cap_pod = Tc
        dst_pod = dst_chip // n_in
        need_pod = jnp.zeros((Tc, n_out), bool)
        for k in range(K):
            need_pod = need_pod | (dst_pod[:, k:k + 1] == jnp.arange(n_out)[None])
        pod_slot = _fifo_slots(need_pod, cap_pod)           # [Tc, n_out]
        toks = jnp.zeros((n_out, cap_pod, d), x.dtype)
        meta_e = jnp.full((n_out, cap_pod, K), -1, jnp.int32)
        meta_w = jnp.zeros((n_out, cap_pod, K), jnp.float32)
        for o in range(n_out):                              # static tiny loop
            sel = pod_slot[:, o]
            toks = toks.at[o, sel].set(chunk, mode="drop")
            # ship only the expert choices that live on pod o (E(n,m) dedup)
            on_o = dst_pod == o
            meta_e = meta_e.at[o, sel].set(jnp.where(on_o, ids, -1), mode="drop")
            meta_w = meta_w.at[o, sel].set(jnp.where(on_o, w, 0.0), mode="drop")
        # step 2: ONE aggregated inter-pod exchange (same inner slot pairing).
        # wire: the gateway encodes ONCE; the wire words relay through the
        # intra-pod fan-out below without re-rounding (codec idempotence).
        toks = _a2a_wire(encode_jnp(toks, wd), ep.pod_axis, wd)
        meta_e = lax.all_to_all(meta_e, ep.pod_axis, 0, 0, tiled=True)
        meta_w = lax.all_to_all(meta_w, ep.pod_axis, 0, 0, tiled=True)
        # step 3: fan out to owning chips within this pod
        R0 = n_out * cap_pod
        ft, fe, fw = (toks.reshape(R0, d), meta_e.reshape(R0, K),
                      meta_w.reshape(R0, K))
        cap_loc = max(1, int(Tc * K * cap_factor / n_in))
        loc_of = jnp.where(fe >= 0, (fe // E_loc) % n_in, -1)
        need_loc = jnp.zeros((R0, n_in), bool)
        for k in range(K):
            need_loc = need_loc | (loc_of[:, k:k + 1] == jnp.arange(n_in)[None])
        loc_slot = _fifo_slots(need_loc, cap_loc)
        lt = jnp.zeros((n_in, cap_loc, d), ft.dtype)   # stays in wire dtype
        le = jnp.full((n_in, cap_loc, K), -1, jnp.int32)
        lw = jnp.zeros((n_in, cap_loc, K), jnp.float32)
        for i in range(n_in):
            sel = loc_slot[:, i]
            on_i = loc_of == i
            lt = lt.at[i, sel].set(ft, mode="drop")
            le = le.at[i, sel].set(jnp.where(on_i, fe, -1), mode="drop")
            lw = lw.at[i, sel].set(jnp.where(on_i, fw, 0.0), mode="drop")
        lt = _a2a_wire(lt, ep.inner_axis, wd)
        le = lax.all_to_all(le, ep.inner_axis, 0, 0, tiled=True)
        lw = lax.all_to_all(lw, ep.inner_axis, 0, 0, tiled=True)
        e_base = (my_out * n_in + my_in) * E_loc
        cap_e = max(1, int(Tc * K * cap_factor / E_loc))
        y = _expert_compute({"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
                            cfg, decode_jnp(lt, wd, x.dtype).reshape(-1, d),
                            le.reshape(-1, K),
                            lw.reshape(-1, K), e_base, E_loc, cap_e)
        # ---- transpose route: local gather-back, pod a2a back, combine ----
        # each hop that re-accumulates re-encodes: expert outputs onto the
        # inner wire, the gateway's pod_back sum onto the DCI wire (the 2
        # combine hops wire_error_bound charges for nap).
        y = decode_jnp(
            _a2a_wire(encode_jnp(y.reshape(n_in, cap_loc, d), wd),
                      ep.inner_axis, wd),
            wd).reshape(n_in * cap_loc, d)
        # each original pod-copy slot sums its local fan-out returns
        pod_back = jnp.zeros((R0, d), jnp.float32)
        for i in range(n_in):
            sel = loc_slot[:, i]
            val = jnp.where((sel < cap_loc)[:, None],
                            y[i * cap_loc + jnp.minimum(sel, cap_loc - 1)], 0.0)
            pod_back = pod_back + val
        pod_back = decode_jnp(
            _a2a_wire(encode_jnp(pod_back.reshape(n_out, cap_pod, d), wd),
                      ep.pod_axis, wd), wd)
        out_chunk = jnp.zeros((Tc, d), jnp.float32)
        for o in range(n_out):
            sel = pod_slot[:, o]
            val = jnp.where((sel < cap_pod)[:, None],
                            pod_back[o, jnp.minimum(sel, cap_pod - 1)], 0.0)
            out_chunk = out_chunk + val

    # reassemble this pod's token set across its gateways (chunks were split
    # over the inner axis; pods hold different batch shards, no pod gather).
    # NB stays f32: a bf16 all_gather here transposes to a bf16 reduce-scatter
    # whose copy-rooted reduction trips the XLA:CPU promotion bug (see
    # moe_apply_sharded).
    full = lax.all_gather(out_chunk, ep.inner_axis, axis=0, tiled=True)
    return full.reshape(B, S, d)


# ---------------------------------------------------------------------------
# registered-executor entry: routing -> NAP plan machinery
# ---------------------------------------------------------------------------

def dispatch_operator(cfg, mesh=None, *, topo: Optional[Topology] = None,
                      n_tokens: Optional[int] = None, routing=None,
                      integrity: str = "off", seed: int = 0):
    """Compile token -> expert routing into a registered dispatch operator.

    Builds the CSR routing matrix ``R [E, T]`` (from ``routing=(ids
    [T, K], weights [T, K])``, or a seeded representative routing over
    ``n_tokens``) on the pod-major expert / gateway-contiguous token
    partitions, and binds the ``backend="moe"`` executor named by
    ``cfg.moe_dispatch`` through :func:`repro.api.operator` — so the
    full operator surface applies: ``op @ x`` is the weighted
    dispatch-sum (x payloads quantized to ``cfg.wire_dtype`` on every
    wire crossing, f32/f64 accumulated on receive), ``op.T @ y`` the
    weighted combine over the reversed plan, ``op.stats()`` the
    slot-granular quantized byte accounting, ``op.autotune_report()``
    the per-direction flat-vs-nap verdict (``method="auto"``), and
    ``integrity="detect"|"recover"`` checksums the quantized words.

    ``mesh`` maps its ("pod", "model") axes onto the plan topology;
    pass ``topo=Topology(n_pods, chips_per_pod)`` to pin one directly.
    """
    from repro import api as nap_api
    if cfg.moe_dispatch not in DISPATCH_MODES:
        raise ValueError(f"cfg.moe_dispatch must be one of "
                         f"{'|'.join(DISPATCH_MODES)}, "
                         f"got {cfg.moe_dispatch!r}")
    if topo is None:
        if mesh is None:
            raise ValueError("dispatch_operator needs a mesh (with "
                             "'pod'/'model' axes) or an explicit topo=")
        topo = topology_of_mesh(mesh)
    if routing is None:
        if n_tokens is None:
            raise ValueError("pass routing=(ids, weights) or n_tokens= for "
                             "a seeded representative routing")
        routing = representative_routing(n_tokens, cfg.n_experts, cfg.top_k,
                                         seed=seed)
    ids, weights = routing
    r = routing_matrix(np.asarray(ids), np.asarray(weights), cfg.n_experts)
    expert_part, token_part = dispatch_partitions(cfg.n_experts, r.shape[1],
                                                  topo)
    return nap_api.operator(r, topo=topo, row_part=expert_part,
                            col_part=token_part, backend="moe",
                            method=cfg.moe_dispatch,
                            wire_dtype=getattr(cfg, "wire_dtype", "f32"),
                            integrity=integrity)
