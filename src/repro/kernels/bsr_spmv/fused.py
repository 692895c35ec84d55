"""Fused Pallas TPU kernel: all three NAPSpMV ``local_spmv`` calls in one.

Algorithm 3 multiplies three rank-local column blocks — on-process, on-node
and off-node — each against its own buffer (owned values, intra-node recv
buffer, inter-node recv buffer).  Running them as three scalar gathers (or
three separate kernels) reads the output tile three times and leaves the
MXU idle between calls.  Here the plan compiler concatenates the three
buffers into ONE padded x operand (``[v_loc | b_on_node | b_off_node]``,
each segment zero-padded up to the block grid) and rewrites the block
columns of all three matrices into that concatenated domain, so the whole
local compute is a single block-sparse matmul accumulating into one output
tile per block row.

Slot ordering is the overlap story of the paper's Algorithm 3 (and of
arXiv:1106.5908's explicit Isend/compute overlap): within each block row
the on-process slots come first, then on-node, then off-node.  The Pallas
pipeline streams (matrix block, x block) pairs in slot order with double
buffering, so the DMAs touching the last-arriving inter-node data are
issued last, behind the MXU work on locally-available blocks.

Multi-RHS (SpMM): x carries ``nv`` right-hand sides.  The nv axis is tiled
by ``nv_block`` as a second parallel grid axis, bounding VMEM per step at

    (bm x bn  +  bn x nv_block  +  bm x nv_block) x 4 bytes

e.g. 192 KiB at (128, 128, 128) — double buffered < 0.5 MiB of ~16 MiB
VMEM; at nv = 1024 the nv tiling keeps the budget flat where an untiled x
block would claim 0.5 MiB per operand on its own.

Padding slots (block col == -1) carry all-zero matrix blocks, so they are
mathematically inert; the index_map clamps them to 0 to stay in bounds.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import interpret_mode


@functools.partial(jax.jit, static_argnames=("nv_block", "interpret"))
def fused_bsr_spmm(cols: jax.Array, blocks: jax.Array, x: jax.Array,
                   *, nv_block: int = 128,
                   interpret: Optional[bool] = None) -> jax.Array:
    """w = A @ x for the fused padded-uniform BSR layout, nv-tiled.

    cols:   [n_brows, ktot] int32 block-column ids into the concatenated
            x domain (-1 = padding slot)
    blocks: [n_brows, ktot, bm, bn] (padding slots zero-filled)
    x:      [n_bcols, bn, nv] — concat(v_loc, b_on_node, b_off_node) blocks
    returns [n_brows, bm, nv] float32

    The one-segment case of :func:`fused_bsr_spmm_packed` (the
    materialised-concat A/B oracle of the zero-copy path).
    """
    return fused_bsr_spmm_packed(cols, blocks, (x,), nv_block=nv_block,
                                 interpret=interpret)


def fused_bsr_spmm_ref(cols, blocks, x) -> jnp.ndarray:
    """Pure-jnp oracle with the same contract as :func:`fused_bsr_spmm`."""
    gathered = x[jnp.maximum(cols, 0)]                    # [nbr, ktot, bn, nv]
    valid = (cols >= 0)[..., None, None]
    prod = jnp.einsum("rkmn,rknv->rkmv", blocks,
                      jnp.where(valid, gathered, 0.0))
    return prod.sum(axis=1).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Zero-copy packed-x kernel
# ---------------------------------------------------------------------------
#
# The x operand arrives as SEPARATE bn-aligned segments (v_loc,
# b_on_node, b_off_node) instead of one HBM-materialised concat.  Each
# segment gets its own ref whose index_map routes the prefetched
# block-column id into that segment's local block index (clamped to 0
# when the slot belongs to another segment); the kernel then selects the
# one block that is in range.  Because an out-of-range ref's index_map
# pins it to block 0, the Pallas pipeline re-fetches it only on segment
# transitions — slots are sorted on-process -> on-node -> off-node, so
# each x ref's DMA stream stays monotone and the extra traffic is at
# most one block per segment switch.
#
# The block-column ids are scalar-prefetched into SMEM (1 MiB on a v5e),
# flattened to 1-D: a 2-D [n_brows, ktot] array pads its minor axis to
# 128 words, which at 2^20 rows (131072 block rows) needs 64 MiB.  Block
# rows are split into calls of at most _SMEM_COL_IDS ids each.

_SMEM_COL_IDS = 2**17


def _make_packed_kernel(bounds, ktot):
    def kernel(cols_ref, blk_ref, *rest):
        *x_refs, o_ref = rest
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        c = cols_ref[pl.program_id(0) * ktot + k]
        x = x_refs[-1][0]
        for b, x_ref in zip(reversed(bounds[:-1]), reversed(x_refs[:-1])):
            x = jnp.where(c < b, x_ref[0], x)
        # full f32 passes on the MXU: every local format meets one f32 bound
        o_ref[...] += jnp.dot(blk_ref[0, 0], x,
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)

    return kernel


def _segment_spec(lo, hi, bn, nv_block, ktot):
    # route block col c into this segment's local index; pin to 0 otherwise
    def index_map(i, v, k, cols):
        c = cols[i * ktot + k]
        return (jnp.where((c >= lo) & (c < hi), c - lo, 0), 0, v)

    return pl.BlockSpec((1, bn, nv_block), index_map)


def _block_spec(r0, bm, bn):
    # matrix blocks of this call's block rows, offset into the full array
    return pl.BlockSpec((1, 1, bm, bn),
                        lambda i, v, k, cols: (i + r0, k, 0, 0))


@functools.partial(jax.jit, static_argnames=("nv_block", "interpret"))
def fused_bsr_spmm_packed(cols: jax.Array, blocks: jax.Array, xs, *,
                          nv_block: int = 128,
                          interpret: Optional[bool] = None) -> jax.Array:
    """w = A @ concat(xs) without materialising the concat in HBM.

    cols:   [n_brows, ktot] int32 block-column ids into the packed domain
            (-1 = padding slot); segment s covers block columns
            [sum(len(xs[:s])), sum(len(xs[:s+1]))) in block units
    blocks: [n_brows, ktot, bm, bn] (padding slots zero-filled)
    xs:     tuple of [n_bcols_s, bn, nv] segments (1..3 of them)
    returns [n_brows, bm, nv] float32 — bit-for-bit equal to
    ``fused_bsr_spmm(cols, blocks, jnp.concatenate(xs))``.

    Grid per call: (block rows, nv tiles, ktot) — block rows and nv tiles
    are parallel, the slot axis is the sequential accumulation axis.  nv
    is padded up to a multiple of ``nv_block`` and sliced back.
    """
    xs = tuple(jnp.asarray(x, jnp.float32) for x in xs)
    n_brows, ktot, bm, bn = blocks.shape
    nv = xs[0].shape[-1]
    nv_block = min(nv_block, max(nv, 1))
    nv_pad = -(-nv // nv_block) * nv_block
    if nv_pad != nv:
        xs = tuple(jnp.pad(x, ((0, 0), (0, 0), (0, nv_pad - nv))) for x in xs)
    bounds = []
    acc = 0
    for x in xs:
        acc += x.shape[0]
        bounds.append(acc)
    seg_specs = [_segment_spec(lo, hi, bn, nv_block, ktot)
                 for lo, hi in zip([0] + bounds[:-1], bounds)]
    kernel = _make_packed_kernel(tuple(bounds), ktot)
    step = max(1, _SMEM_COL_IDS // ktot)
    outs = []
    for r0 in range(0, n_brows, step):
        n = min(step, n_brows - r0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, nv_pad // nv_block, ktot),
            in_specs=[_block_spec(r0, bm, bn)] + seg_specs,
            out_specs=pl.BlockSpec((1, bm, nv_block),
                                   lambda i, v, k, cols: (i, 0, v)),
        )
        outs.append(pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n, bm, nv_pad), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret_mode(interpret),
        )(cols[r0:r0 + n].reshape(-1), blocks, *xs))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    return out[..., :nv] if nv_pad != nv else out
