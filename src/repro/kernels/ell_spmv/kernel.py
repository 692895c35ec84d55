"""ELL (padded-row) SpMV / SpMM over a packed x operand, as plain XLA.

This is the block-hostile branch of the adaptive local-compute engine
(`core/spmv_jax.py`): where the fused BSR path would densify (bm, bn)
tiles at low block fill, the ELL path keeps the matrix as two
[n_rows, kmax] arrays (column ids + values) and gathers x rows on the
VPU — no MXU tiles, no scatter, padding overhead bounded by
kmax / mean-row-length.

The product is one XLA loop over the kmax slots: slot k gathers
``x[cols[:, k]]`` and accumulates ``vals[:, k] * x[cols[:, k]]`` into the
[n_rows, nv] result.  It is not a Pallas kernel because Mosaic's gather
lowering refuses an in-kernel row gather at every shape ("Shape mismatch
in input, indices and output").  The slot loop compiles on the TPU at
any size and never materialises the [n_rows, kmax, nv] gather that the
one-shot ``(vals[..., None] * x[cols]).sum(1)`` spelling would (1 GiB at
2^20 rows x kmax 25, nv 1).

The NAPSpMV's buffers (``v_loc``, on-node recv, off-node recv) arrive as
separate segments; column ids live in the packed domain
``[0, len(v) | len(v)+len(bnode) | ...)`` and the segments are
concatenated once per call (one [n_x, nv] copy).  What the product holds
in device memory is counted by
:func:`repro.core.cost_model.ell_resident_bytes`, which the format
autotuner uses to refuse ELL when it does not fit.

Padding slots (col == -1, val == 0) clamp to x row 0 and multiply by
zero, so they are mathematically inert.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


@jax.jit
def ell_spmm_packed(cols: jax.Array, vals: jax.Array, xs) -> jax.Array:
    """w = A @ concat(xs) for the ELL layout.

    cols: [n_rows, kmax] int32 column ids in the packed x domain (-1 = pad)
    vals: [n_rows, kmax] float32 (0 on padding slots)
    xs:   tuple of [len_i, nv] segments; the packed domain is their
          concatenation in order (e.g. (v_loc, b_on_node, b_off_node))
    returns [n_rows, nv] float32
    """
    xs = tuple(jnp.asarray(x, jnp.float32) for x in xs)
    x = xs[0] if len(xs) == 1 else jnp.concatenate(xs)
    n_rows, kmax = cols.shape

    def slot(k, acc):
        c = lax.dynamic_index_in_dim(cols, k, 1, keepdims=False)
        v = lax.dynamic_index_in_dim(vals, k, 1, keepdims=False)
        return acc + v[:, None] * x[jnp.maximum(c, 0)]

    return lax.fori_loop(0, kmax, slot,
                         jnp.zeros((n_rows, x.shape[1]), jnp.float32))
