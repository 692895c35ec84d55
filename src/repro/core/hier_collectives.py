"""Node-aware (hierarchical) collectives — the paper's 3-step pattern on a pod mesh.

The NAPSpMV insight (Sec. 4): traffic that must cross the *expensive* network
level should first be aggregated at the *cheap* level, cross once per
(node, node) pair deduplicated, and then be redistributed cheaply on the
receiving side.  On a TPU fleet the two levels are intra-pod ICI
(~50 GB/s/link) and inter-pod DCI (scarce).  Mesh convention throughout:
``outer_axis`` = "pod" (expensive, crosses DCI), ``inner_axis`` = intra-pod
axis (cheap ICI).

All functions here are *manual-collective* primitives: they must be called
inside :func:`jax.shard_map` with the named axes in scope.  Each has a flat
(topology-oblivious) counterpart so benchmarks can compare like-for-like:

====================  =========================================
flat                   node-aware
====================  =========================================
``psum(x, (i, o))``    ``nap_psum`` : RS(inner) -> psum(outer) -> AG(inner)
``all_gather(flat)``   ``nap_all_gather`` : AG(outer on 1/inner bytes) -> AG(inner)
``psum_scatter(flat)`` ``nap_reduce_scatter``
``all_to_all(flat)``   ``nap_all_to_all`` : 3-step (gather, inject once, scatter)
====================  =========================================

DCI byte count: a flat psum over ``(inner, outer)`` moves the *full* buffer
across DCI; ``nap_psum`` moves ``1/|inner|`` of it — the same factor the paper
gets by deduplicating node-pair messages (Fig. 8).

``compressed_psum_outer`` additionally quantizes the DCI stage to int8 with
error feedback (residual carried in optimizer state), a beyond-paper
distributed-optimization trick: ICI stays full precision, only the scarce
DCI link carries compressed payloads.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


Pytree = Any


# ---------------------------------------------------------------------------
# Shape plumbing
# ---------------------------------------------------------------------------

def _flatten_concat(tree: Pytree) -> Tuple[jnp.ndarray, Any, list]:
    """Concatenate all leaves into one flat f32 vector (for fused collectives)."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = [(l.shape, l.dtype) for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves]) \
        if leaves else jnp.zeros((0,), jnp.float32)
    return flat, treedef, shapes


def _split_restore(flat: jnp.ndarray, treedef, shapes) -> Pytree:
    out, off = [], 0
    for shape, dtype in shapes:
        n = 1
        for s in shape:
            n *= s
        out.append(flat[off:off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


def _pad_to_multiple(x: jnp.ndarray, k: int) -> jnp.ndarray:
    pad = (-x.shape[0]) % k
    return jnp.pad(x, ((0, pad),)) if pad else x


# ---------------------------------------------------------------------------
# Hierarchical all-reduce (gradient synchronisation)
# ---------------------------------------------------------------------------

def nap_psum(x: jnp.ndarray, inner_axis: str, outer_axis: str) -> jnp.ndarray:
    """all-reduce over (inner x outer) with 1/|inner| of the bytes on DCI.

    reduce-scatter over ``inner_axis`` (ICI), psum over ``outer_axis`` (DCI,
    on the scattered shard), all-gather over ``inner_axis`` (ICI).
    Equivalent to ``lax.psum(x, (inner_axis, outer_axis))``.
    """
    inner = lax.axis_size(inner_axis)
    orig_shape = x.shape
    flat = _pad_to_multiple(x.reshape(-1), inner)
    shard = lax.psum_scatter(flat, inner_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, outer_axis)
    full = lax.all_gather(shard, inner_axis, axis=0, tiled=True)
    n = 1
    for s in orig_shape:
        n *= s
    return full[:n].reshape(orig_shape)


def nap_psum_tree(tree: Pytree, inner_axis: str, outer_axis: str) -> Pytree:
    """Fused hierarchical all-reduce of a whole gradient pytree.

    One RS/AG pair for the entire flattened gradient — fewer collective
    launches (the paper's message-count reduction) *and* minimal DCI bytes.
    """
    flat, treedef, shapes = _flatten_concat(tree)
    red = nap_psum(flat, inner_axis, outer_axis)
    return _split_restore(red, treedef, shapes)


def flat_psum_tree(tree: Pytree, axes: Sequence[str]) -> Pytree:
    """Reference topology-oblivious gradient sync."""
    return jax.tree.map(lambda g: lax.psum(g, tuple(axes)), tree)


def nap_all_gather(x: jnp.ndarray, inner_axis: str, outer_axis: str,
                   axis: int = 0) -> jnp.ndarray:
    """all-gather over (outer x inner): cross DCI first on small shards, then
    replicate over ICI.  Equivalent to gathering over both axes flat, with
    1/|inner| of the bytes injected per DCI hop."""
    pod = lax.all_gather(x, outer_axis, axis=axis, tiled=True)
    return lax.all_gather(pod, inner_axis, axis=axis, tiled=True)


def nap_reduce_scatter(x: jnp.ndarray, inner_axis: str, outer_axis: str) -> jnp.ndarray:
    """reduce-scatter over (inner x outer): ICI RS shrinks the buffer |inner|x
    before the DCI RS touches it."""
    shard = lax.psum_scatter(x, inner_axis, scatter_dimension=0, tiled=True)
    return lax.psum_scatter(shard, outer_axis, scatter_dimension=0, tiled=True)


# ---------------------------------------------------------------------------
# Hierarchical (3-step) all-to-all — the literal NAPSpMV pattern
# ---------------------------------------------------------------------------

def nap_all_to_all(x: jnp.ndarray, inner_axis: str, outer_axis: str) -> jnp.ndarray:
    """All-to-all over the flat (outer*inner) grid via the paper's 3 steps.

    ``x`` has leading dim ``n_out*n_in`` (destination rank, SMP order: rank
    ``d = o*n_in + i``).  Step 1 (local gather): intra-pod all-to-all so that
    slot ``p`` of each pod holds everything the pod must send to remote slot
    ``p`` — the T/U "aligned" pairing of comm_graph.  Step 2: ONE aggregated
    inter-pod all-to-all.  Step 3 (local scatter): intra-pod all-to-all
    delivering to final destinations.  Bitwise-equal to the flat all-to-all
    over ``(outer, inner)``.
    """
    n_in = lax.axis_size(inner_axis)
    n_out = lax.axis_size(outer_axis)
    rest = x.shape[1:]
    # [n_out*n_in, ...] -> [n_out, n_in, ...]: row o = payload for pod o.
    y = x.reshape((n_out, n_in) + rest)
    # Step 1: bring "everything this pod sends to pod o" onto local slot o%?
    # aligned pairing: local slot p keeps destination-slot p payloads.
    # all_to_all over inner on the *destination-slot* dim (axis 1).
    y = lax.all_to_all(y, inner_axis, split_axis=1, concat_axis=1, tiled=True)
    # now y[o] on local slot p = payloads from every local slot s to (o, p):
    # shape [n_out, n_in, ...] where axis-1 index s = source slot.
    # Step 2: one aggregated DCI all-to-all over the pod axis (axis 0).
    y = lax.all_to_all(y, outer_axis, split_axis=0, concat_axis=0, tiled=True)
    # y[o'] = payload from pod o' destined to (this pod, this slot), per src slot.
    # Step 3: local scatter — deliver source-slot payloads home: the data is
    # already at the right (pod, slot); flatten source grid back to rank order.
    return y.reshape((n_out * n_in,) + rest)


def flat_all_to_all(x: jnp.ndarray, inner_axis: str, outer_axis: str) -> jnp.ndarray:
    """Topology-oblivious all-to-all over the combined (outer, inner) axis."""
    return lax.all_to_all(x, (outer_axis, inner_axis), split_axis=0,
                          concat_axis=0, tiled=True)


# ---------------------------------------------------------------------------
# int8 error-feedback compression for the DCI stage (beyond paper)
# ---------------------------------------------------------------------------

def _quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum_outer(x: jnp.ndarray, outer_axis: str,
                          residual: Optional[jnp.ndarray] = None
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """psum over the pod axis with int8-on-the-wire + error feedback.

    Ring reduce-scatter then ring all-gather over ``outer_axis`` using
    ``ppermute``; every hop carries int8 payload + one f32 scale per chunk.
    ``residual`` (same shape as x) carries quantization error to the next
    step (error feedback keeps SGD/Adam convergence unbiased in practice).

    Returns (sum, new_residual).
    """
    n = lax.axis_size(outer_axis)
    if residual is None:
        residual = jnp.zeros_like(x)
    xc = x + residual
    if n == 1:
        return xc, jnp.zeros_like(x)

    orig = xc.shape
    flat = _pad_to_multiple(xc.reshape(-1), n)
    chunks = flat.reshape(n, -1)  # chunk c belongs to rank c after RS
    idx = lax.axis_index(outer_axis)

    sent_err = jnp.zeros_like(chunks)

    # ring reduce-scatter: step s, send chunk (idx - s - 1) to right neighbour;
    # receive the chunk our left neighbour sent, (idx - s - 2), and accumulate.
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc = chunks
    for s in range(n - 1):
        send_c = (idx - s - 1) % n
        payload = acc[send_c]
        q, scale = _quantize_int8(payload)
        deq = q.astype(jnp.float32) * scale
        # record what we failed to transmit for the chunk we just sent
        sent_err = sent_err.at[send_c].add(payload - deq)
        acc = acc.at[send_c].set(0.0)  # sent away; zero to avoid double count
        q_in = lax.ppermute(q, outer_axis, perm)
        scale_in = lax.ppermute(scale, outer_axis, perm)
        rc = (idx - s - 2) % n
        acc = acc.at[rc].add(q_in.astype(jnp.float32) * scale_in)
    # after n-1 steps rank holds the full sum of chunk ``idx`` (mod quant error)
    mine = acc[idx]

    # ring all-gather of the reduced chunks, int8 on the wire again.  Every
    # rank applies the *dequantized* value (including the chunk owner) so the
    # result is bitwise identical on all replicas — parameters cannot drift.
    q, scale = _quantize_int8(mine)
    mine_deq = q.astype(jnp.float32) * scale
    out = jnp.zeros_like(chunks)
    out = out.at[idx].set(mine_deq)
    ag_err = jnp.zeros_like(chunks)
    ag_err = ag_err.at[idx].add(mine - mine_deq)
    cur_q, cur_s, cur_c = q, scale, idx
    for s in range(n - 1):
        cur_q = lax.ppermute(cur_q, outer_axis, perm)
        cur_s = lax.ppermute(cur_s, outer_axis, perm)
        cur_c = lax.ppermute(cur_c, outer_axis, perm)
        out = out.at[cur_c].add(cur_q.astype(jnp.float32) * cur_s)

    total = out.reshape(-1)[: xc.size].reshape(orig)
    # error feedback: local quantization error of chunks this rank transmitted
    new_residual = (sent_err + ag_err).reshape(-1)[: xc.size].reshape(orig)
    return total, new_residual


def nap_psum_compressed(x: jnp.ndarray, inner_axis: str, outer_axis: str,
                        residual: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Hierarchical all-reduce with int8 DCI stage: RS(ICI, fp32) ->
    compressed psum(DCI, int8+EF) -> AG(ICI, fp32)."""
    inner = lax.axis_size(inner_axis)
    orig = x.shape
    flat = _pad_to_multiple(x.reshape(-1), inner)
    shard = lax.psum_scatter(flat, inner_axis, scatter_dimension=0, tiled=True)
    if residual is None:
        res_in = jnp.zeros_like(shard)
    else:
        res_in = residual
    shard, res_out = compressed_psum_outer(shard, outer_axis, res_in)
    full = lax.all_gather(shard, inner_axis, axis=0, tiled=True)
    n = 1
    for s in orig:
        n *= s
    return full[:n].reshape(orig), res_out


def residual_shape_for(x_shape: Tuple[int, ...], inner: int) -> Tuple[int, ...]:
    """Shape of the error-feedback residual for nap_psum_compressed."""
    n = 1
    for s in x_shape:
        n *= s
    padded = n + ((-n) % inner)
    return (padded // inner,)


# ---------------------------------------------------------------------------
# NAP MoE dispatch: the paper's technique applied to expert parallelism
# ---------------------------------------------------------------------------

def nap_moe_dispatch(tokens: jnp.ndarray, dest_chip: jnp.ndarray,
                     inner_axis: str, outer_axis: str,
                     capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Send each token to the expert-parallel chip(s) in ``dest_chip``.

    The token->expert routing matrix is literally a sparse matrix, so MoE
    dispatch *is* an SpMV gather: NAPSpMV applies verbatim.  A token bound
    for two experts hosted on the *same remote pod* crosses DCI **once**
    (the paper's E(n, m) dedup) and is fanned out on the receiving pod.

    tokens:    [T, D]      local token shard
    dest_chip: [T, K]      global EP-chip id per (token, expert-choice),
                           -1 for dropped.
    capacity:  per-(src chip, dst chip) buffer slots.

    Returns (recv_tokens [n_chips*capacity_in, D], recv_src_slot, recv_valid)
    where the receive buffer is ordered by source chip.  This primitive is
    exercised by the MoE layer; see models/moe.py for the full layer.
    """
    n_in = lax.axis_size(inner_axis)
    n_out = lax.axis_size(outer_axis)
    T, D = tokens.shape
    K = dest_chip.shape[1]
    my_pod = lax.axis_index(outer_axis)
    my_loc = lax.axis_index(inner_axis)
    my_chip = my_pod * n_in + my_loc

    dest_pod = jnp.where(dest_chip >= 0, dest_chip // n_in, -1)

    # --- dedup: does token t need pod o at all? (E(n,m) membership) ---------
    need_pod = jnp.zeros((T, n_out), dtype=bool)
    for k in range(K):
        need_pod = need_pod | (dest_pod[:, k:k + 1] == jnp.arange(n_out)[None, :])

    # slot of token t in the pod-o buffer (capacity-dropped FIFO); slots past
    # capacity go out-of-bounds and are dropped by scatter mode="drop".
    pod_slot = jnp.cumsum(need_pod.astype(jnp.int32), axis=0) - 1  # [T, n_out]
    pod_slot = jnp.where(need_pod & (pod_slot < capacity), pod_slot, capacity)

    # pack [n_out, capacity, D] + the token's chip list so the remote pod can
    # fan out: we ship dest_chip along with the payload.  Source provenance is
    # a global id (chip * T + token) so the combine path can route back.
    buf = jnp.zeros((n_out, capacity, D), tokens.dtype)
    meta = jnp.full((n_out, capacity, K), -1, jnp.int32)       # dest chips
    srcs = jnp.full((n_out, capacity), -1, jnp.int32)          # global src id
    src_gid = my_chip * T + jnp.arange(T, dtype=jnp.int32)
    for o in range(n_out):  # static tiny loop over pods
        sel = pod_slot[:, o]
        buf = buf.at[o, sel].set(tokens, mode="drop")
        meta = meta.at[o, sel].set(dest_chip, mode="drop")
        srcs = srcs.at[o, sel].set(src_gid, mode="drop")

    # --- step 1+2: aggregate intra-pod is implicit (tokens start sharded);
    # ONE aggregated inter-pod exchange ---------------------------------------
    buf = lax.all_to_all(buf, outer_axis, 0, 0, tiled=True)    # [n_out, cap, D]
    meta = lax.all_to_all(meta, outer_axis, 0, 0, tiled=True)
    srcs = lax.all_to_all(srcs, outer_axis, 0, 0, tiled=True)

    # --- step 3: local scatter to the owning chips within this pod ----------
    flat_tok = buf.reshape(n_out * capacity, D)
    flat_meta = meta.reshape(n_out * capacity, K)
    flat_src = srcs.reshape(n_out * capacity)
    # which local chip(s) need each arrived token?
    local_of = jnp.where(flat_meta >= 0, flat_meta % n_in, -1)
    pod_of = jnp.where(flat_meta >= 0, flat_meta // n_in, -1)
    need_local = jnp.zeros((n_out * capacity, n_in), bool)
    for k in range(K):
        need_local = need_local | ((pod_of[:, k:k + 1] == my_pod) &
                                   (local_of[:, k:k + 1] == jnp.arange(n_in)[None, :]))
    loc_slot = jnp.cumsum(need_local.astype(jnp.int32), axis=0) - 1
    loc_slot = jnp.where(need_local & (loc_slot < capacity), loc_slot, capacity)
    lbuf = jnp.zeros((n_in, capacity, D), tokens.dtype)
    lsrc = jnp.full((n_in, capacity), -1, jnp.int32)
    for i in range(n_in):
        sel = loc_slot[:, i]
        lbuf = lbuf.at[i, sel].set(flat_tok, mode="drop")
        lsrc = lsrc.at[i, sel].set(flat_src, mode="drop")
    lbuf = lax.all_to_all(lbuf, inner_axis, 0, 0, tiled=True)
    lsrc = lax.all_to_all(lsrc, inner_axis, 0, 0, tiled=True)
    recv = lbuf.reshape(n_in * capacity, D)
    recv_src = lsrc.reshape(n_in * capacity)
    return recv, recv_src, recv_src >= 0
