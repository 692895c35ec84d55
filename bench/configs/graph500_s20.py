"""Graph500 Kronecker graph under PageRank (Graph500 specification's
generator; LDBC Graphalytics' PageRank).

``2^scale`` vertices and ``edge_factor * 2^scale`` edges, each edge
placed by ``scale`` quadrant draws with the initiator probabilities
A/B/C/D (one uniform per bit: the spec's joint bit distribution), vertex
labels permuted, then made undirected: both directions of every edge,
self-loops and duplicates dropped.  The matrix is one PageRank step's,
``B = I - d * A * D^-1`` (``A`` the symmetric adjacency, ``D`` the
degrees), values rounded to float32; isolated vertices keep only their
diagonal, and their mass is spread outside the matrix.  Pattern and
values come from the seed."""
from __future__ import annotations

import numpy as np


def build(cfg: dict, seed: int):
    """(indptr int64, indices int64, data float64, shape)."""
    scale, d = int(cfg["scale"]), float(cfg["damping"])
    n, m = 1 << scale, int(cfg["edge_factor"]) << scale
    a, b, c = (float(p) for p in cfg["initiator"][:3])
    rng = np.random.default_rng([seed, 1])
    a, ab, abc = np.float32(a), np.float32(a + b), np.float32(a + b + c)
    src = np.zeros(m, np.int32)
    dst = np.zeros(m, np.int32)
    u = np.empty(m, np.float32)
    hi, lo, top = (np.empty(m, bool) for _ in range(3))
    for _ in range(scale):              # in place: the draws dominate
        rng.random(out=u, dtype=np.float32)
        np.greater_equal(u, ab, out=hi)                 # quadrant C or D
        np.greater_equal(u, a, out=lo)
        lo ^= hi
        lo |= np.greater_equal(u, abc, out=top)         # quadrant B or D
        src <<= 1
        src += hi
        dst <<= 1
        dst += lo
    del u, hi, lo, top
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    keep = src != dst
    src, dst = src[keep], dst[keep]
    edges = np.unique(np.concatenate([(src << scale) | dst,
                                      (dst << scale) | src]))
    deg = np.bincount(edges >> scale, minlength=n)
    diag = np.arange(n, dtype=np.int64) * (n + 1)     # no edge is a loop
    key = np.insert(edges, np.searchsorted(edges, diag), diag)
    rows, cols = key >> scale, key & (n - 1)
    off = rows != cols
    data = np.ones(key.size)
    data[off] = -d / deg[cols[off]]
    indptr = np.concatenate([[0], np.cumsum(np.bincount(rows, minlength=n))])
    return (indptr.astype(np.int64), cols,
            data.astype(np.float32).astype(np.float64), (n, n))
