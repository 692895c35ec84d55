"""Pure-jnp oracle for the packed ELL SpMV/SpMM kernel."""
from __future__ import annotations

import jax.numpy as jnp


def ell_spmm_packed_ref(cols, vals, xs, chunk_row=None,
                        n_rows=None) -> jnp.ndarray:
    """Same contract as :func:`kernel.ell_spmm_packed` (gather + reduce)."""
    x = jnp.concatenate([jnp.asarray(x, jnp.float32) for x in xs], axis=0)
    gathered = x[jnp.maximum(cols, 0)]                   # [n_rows, kmax, nv]
    valid = (cols >= 0)[..., None]
    w = (vals[..., None] * jnp.where(valid, gathered, 0.0)).sum(axis=1)
    if chunk_row is None:
        return w
    return jnp.zeros((n_rows, w.shape[1]), w.dtype).at[chunk_row].add(
        w, mode="drop")


def ell_spmv_ref(ell, v):
    """Oracle on a sparse.ELL container + element vector (numpy/jnp)."""
    chunk_row = None if ell.chunk_row is None else jnp.asarray(ell.chunk_row)
    out = ell_spmm_packed_ref(jnp.asarray(ell.cols), jnp.asarray(ell.vals),
                              (jnp.asarray(v).reshape(-1, 1),), chunk_row,
                              ell.n_rows)
    return out.reshape(-1)
