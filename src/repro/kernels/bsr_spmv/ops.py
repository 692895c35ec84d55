"""User-facing jitted wrappers around the BSR SpMV Pallas kernel."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bsr_spmv.kernel import bsr_spmm_padded
from repro.sparse.bsr import BSR


def bsr_spmm(bsr: BSR, x, *, interpret: Optional[bool] = None) -> jax.Array:
    """w = A @ x with x [n_cols, nv]; returns [n_rows, nv] (padded shape)."""
    cols, blocks, _ = bsr.padded_uniform()
    bm, bn = bsr.block_shape
    x = jnp.asarray(x, jnp.float32)
    n_bcols = bsr.shape[1] // bn
    pad_rows = bsr.shape[1] - x.shape[0]
    if pad_rows:
        x = jnp.pad(x, ((0, pad_rows), (0, 0)))
    xb = x.reshape(n_bcols, bn, -1)
    out = bsr_spmm_padded(jnp.asarray(cols), jnp.asarray(blocks), xb,
                          interpret=interpret)
    return out.reshape(bsr.shape[0], -1)


def bsr_spmv(bsr: BSR, v, *, interpret: Optional[bool] = None) -> jax.Array:
    """w = A @ v for a single vector; returns [n_rows] (padded shape)."""
    v = jnp.asarray(v, jnp.float32).reshape(-1, 1)
    return bsr_spmm(bsr, v, interpret=interpret).reshape(-1)
