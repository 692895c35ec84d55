"""HPCG's 27-point stencil matrix (GenerateProblem_ref), built from its
spec: on an nx x ny x nz grid, row ``ix + nx*(iy + ny*iz)`` couples to
every grid neighbour with offsets in {-1, 0, 1}^3 that lies inside the
grid, with ``diagonal`` on itself and ``off_diagonal`` elsewhere.  The
matrix does not depend on the seed."""
from __future__ import annotations

import numpy as np


def build(cfg: dict, seed: int):
    """(indptr int64, indices int64, data float64, shape)."""
    del seed
    nx, ny, nz = int(cfg["nx"]), int(cfg["ny"]), int(cfg["nz"])
    n = nx * ny * nz
    iz, iy, ix = np.unravel_index(np.arange(n), (nz, ny, nx))
    cols, vals = [], []
    # offsets in HPCG's loop order (sz, sy, sx), so columns come ascending
    for sz in (-1, 0, 1):
        for sy in (-1, 0, 1):
            for sx in (-1, 0, 1):
                ok = ((ix + sx >= 0) & (ix + sx < nx) & (iy + sy >= 0)
                      & (iy + sy < ny) & (iz + sz >= 0) & (iz + sz < nz))
                col = np.arange(n) + sx + nx * (sy + ny * sz)
                cols.append(np.where(ok, col, -1).astype(np.int32))
                vals.append(cfg["diagonal"] if (sx, sy, sz) == (0, 0, 0)
                            else cfg["off_diagonal"])
    cols = np.stack(cols, axis=1)
    keep = cols >= 0
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(keep.sum(axis=1), out=indptr[1:])
    data = np.broadcast_to(np.asarray(vals, np.float64), cols.shape)[keep]
    return indptr, cols[keep].astype(np.int64), data, (n, n)
