"""The paper's random matrices (arXiv:1612.08060, Sec. 5): a fixed number
of non-zeros per row, the diagonal included, the other columns uniform
over all columns and distinct within the row, values U(-1, 1) rounded to
float32.  Pattern and values come from the seed."""
from __future__ import annotations

import numpy as np


def build(cfg: dict, seed: int):
    """(indptr int64, indices int64, data float64, shape)."""
    n, k = int(cfg["n_rows"]), int(cfg["nnz_per_row"])
    rng = np.random.default_rng([seed, 1])
    cols = rng.integers(0, n, size=(n, k), dtype=np.int64)
    cols[:, 0] = np.arange(n)
    redo = np.arange(n)
    while redo.size:
        block = np.sort(cols[redo], axis=1)
        dup = (block[:, 1:] == block[:, :-1]).any(axis=1)
        cols[redo] = block
        redo = redo[dup]
        fresh = rng.integers(0, n, size=(redo.size, k), dtype=np.int64)
        fresh[:, 0] = redo
        cols[redo] = fresh
    vals = rng.uniform(-1.0, 1.0, size=(n, k)).astype(np.float32)
    indptr = np.arange(n + 1, dtype=np.int64) * k
    return indptr, cols.reshape(-1), vals.astype(np.float64).reshape(-1), (n, n)
