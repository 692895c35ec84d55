"""Pallas TPU kernel: block-sparse-row SpMV / SpMM (the NAPSpMV local_spmv).

TPU adaptation of the paper's MKL/Eigen CSR ``local_spmv`` (DESIGN.md §2):
scalar CSR row kernels cannot feed the 128x128 MXU, so the local matrix is
stored as BSR with MXU-aligned dense blocks (``sparse/bsr.py``) and each
(block-row i, slot k) grid step issues one ``(bm, bn) @ (bn, nv)`` MXU
matmul against the x-block selected by the **scalar-prefetched** block-column
index — the sparse gather happens in the BlockSpec index_map, so the block
DMA (HBM -> VMEM) is overlapped with compute by the Pallas pipeline (the
double buffering the paper gets from posting MPI_Isend early).

Layout/VMEM budget per grid step (f32):
  matrix block  (bm, bn)        = 64 KiB at 128x128
  x block       (bn, nv)        = 64 KiB at nv = 128
  out block     (bm, nv)        = 64 KiB
With double buffering this is < 0.5 MiB of ~16 MiB VMEM, leaving headroom
for larger nv or multi-row blocks.

Padding slots (block col == -1) carry all-zero matrix blocks, so they are
mathematically inert; the index_map clamps them to 0 to stay in bounds.

The distributed executor (``core/spmv_jax.py``) does NOT call this kernel
three times for Algorithm 3's three ``local_spmv`` blocks — it uses the
**fused** variant in :mod:`repro.kernels.bsr_spmv.fused`, which multiplies
the on-process / on-node / off-node blocks against one concatenated x
operand in a single ``pallas_call`` (one output-tile accumulation, slots
ordered so locally-available blocks are streamed first).  The fused kernel
also tiles the nv (multi-RHS) axis: at nv = 128 the per-step VMEM budget
matches the figure above; at larger nv the budget stays flat because nv is
a parallel grid axis, not a larger block.  See fused.py for the breakdown
and what remains for a real multi-host mesh (ROADMAP "Open items").
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import interpret_mode


def _kernel(cols_ref, blk_ref, x_ref, o_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(blk_ref[0, 0], x_ref[0],
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_spmm_padded(cols: jax.Array, blocks: jax.Array, x: jax.Array,
                    *, interpret: Optional[bool] = None) -> jax.Array:
    """w = A @ x for the padded-uniform BSR layout.

    cols:   [n_brows, kmax] int32 block-column ids (-1 = padding)
    blocks: [n_brows, kmax, bm, bn] (padding slots zero-filled)
    x:      [n_bcols, bn, nv]
    returns [n_brows, bm, nv] float32
    """
    n_brows, kmax, bm, bn = blocks.shape
    nv = x.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_brows, kmax),
        in_specs=[
            pl.BlockSpec((1, 1, bm, bn), lambda i, k, cols: (i, k, 0, 0)),
            # the sparse gather: x block chosen by the prefetched col id
            pl.BlockSpec((1, bn, nv),
                         lambda i, k, cols: (jnp.maximum(cols[i, k], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, nv), lambda i, k, cols: (i, 0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_brows, bm, nv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret_mode(interpret),
    )(cols, blocks, x)
