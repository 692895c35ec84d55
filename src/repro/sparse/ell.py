"""ELLPACK (padded-row) container — the VPU-friendly local SpMV format.

BSR (``sparse/bsr.py``) wins when nonzeros cluster into dense (bm, bn)
tiles; on block-hostile structures (random matrices at <= 12 nnz/row,
graph Laplacians, AMG coarse levels) densifying blocks inflates both the
bytes moved and the padded FLOPs by 1/fill.  ELL pads every *row* to the
matrix's max nnz/row instead: two [n_rows, kmax] arrays (column ids and
values), a layout whose padding overhead is ``kmax / mean_nnz_row`` — tiny
whenever the row-length distribution is flat, which is exactly the regime
where blocks are hostile.

The Pallas kernel in ``kernels/ell_spmv`` consumes this layout directly:
each row-tile does a vectorised gather of x rows by ``cols`` and a
multiply-accumulate over the kmax axis on the VPU (no MXU, no scatter).

Padding slots use ``cols == -1`` with ``vals == 0``; consumers clamp the
column to 0, so padding is mathematically inert against any finite x.

**Row splitting.**  One hub row pads every row to its length, so on a
power-law graph ELL issues far more slots than entries.  A split width
``K`` below the longest row cuts each row of ``n`` entries into
``ceil(n / K)`` consecutive *chunk rows* of ``K`` slots; ``chunk_row``
(int32, sorted) names each chunk row's output row, and the product adds
each row's chunk partials after the slot loop.  A row with no entries
gets no chunk row.  Without a split there is no ``chunk_row`` and chunk
row ``i`` is row ``i``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from repro.sparse.csr import CSR


@dataclasses.dataclass
class ELL:
    """Row-padded sparse matrix: chunk row i holds ``cols[i, :]`` /
    ``vals[i, :]`` of output row ``chunk_row[i]`` (row ``i`` unsplit)."""

    cols: np.ndarray   # int32 [n_chunks, kmax], -1 = padding slot
    vals: np.ndarray   # float32 [n_chunks, kmax], 0 on padding slots
    shape: Tuple[int, int]  # logical element shape (n_rows may exceed shape[0])
    chunk_row: Optional[np.ndarray] = None  # int32 [n_chunks], sorted
    n_out: int = 0     # output rows of a split layout

    @property
    def kmax(self) -> int:
        """Slots per chunk row: the split width, or the longest row."""
        return int(self.cols.shape[1])

    @property
    def n_rows(self) -> int:
        return self.n_out if self.chunk_row is not None \
            else int(self.cols.shape[0])

    @property
    def n_chunks(self) -> int:
        return int(self.cols.shape[0])

    @property
    def nnz(self) -> int:
        return int((self.cols >= 0).sum())

    @property
    def fill(self) -> float:
        """Fraction of ELL slots holding real nonzeros (1 = no padding)."""
        return self.nnz / max(self.cols.size, 1)

    def _combine(self, partial: np.ndarray) -> np.ndarray:
        if self.chunk_row is None:
            return partial
        out = np.zeros((self.n_rows,) + partial.shape[1:], partial.dtype)
        np.add.at(out, self.chunk_row, partial)
        return out

    def matvec(self, v: np.ndarray) -> np.ndarray:
        """Dense gather oracle (numpy); v indexed by the stored column ids."""
        gathered = np.asarray(v)[np.maximum(self.cols, 0)]
        return self._combine(
            (self.vals * np.where(self.cols >= 0, gathered, 0.0)).sum(axis=1))

    def to_dense(self) -> np.ndarray:
        out = np.zeros((self.n_rows, self.shape[1]))
        r, k = np.nonzero(self.cols >= 0)
        rows = r if self.chunk_row is None else self.chunk_row[r]
        np.add.at(out, (rows, self.cols[r, k]), self.vals[r, k])
        return out

    @staticmethod
    def from_coo(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                 shape: Tuple[int, int], n_rows_pad: int = 0,
                 kmax: int = 0, split: int = 0) -> "ELL":
        """COO -> ELL, fully vectorised (no per-row Python loops).

        ``n_rows_pad`` pads the row axis (extra all-padding rows); ``kmax``
        forces a wider slot axis than the data needs — both are used to
        align per-rank layouts across an SPMD mesh.  A ``split`` width
        below the longest row emits the row-split layout (module
        docstring); at or above it the arrays are the unsplit ones.
        """
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = np.asarray(vals)
        n_rows = max(shape[0], n_rows_pad)
        order = np.argsort(rows, kind="stable")
        rows, cols, vals = rows[order], cols[order], vals[order]
        counts = np.bincount(rows, minlength=n_rows)
        longest = int(counts.max(initial=0))
        kmax = max(kmax, 1, longest)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        slot = np.arange(rows.size) - starts[rows]
        chunk_row = None
        if 0 < split < longest:
            per_row = -(-counts // split)
            first = np.concatenate([[0], np.cumsum(per_row)[:-1]])
            rows, slot = first[rows] + slot // split, slot % split
            chunk_row = np.repeat(np.arange(n_rows, dtype=np.int32), per_row)
            n_rows, kmax = chunk_row.size, split
        out_cols = np.full((n_rows, kmax), -1, dtype=np.int32)
        out_vals = np.zeros((n_rows, kmax), dtype=np.float32)
        out_cols[rows, slot] = cols.astype(np.int32)
        out_vals[rows, slot] = vals.astype(np.float32)
        if chunk_row is None:
            return ELL(cols=out_cols, vals=out_vals, shape=shape)
        return ELL(cols=out_cols, vals=out_vals, shape=shape,
                   chunk_row=chunk_row, n_out=max(shape[0], n_rows_pad))

    @staticmethod
    def from_csr(a: CSR, n_rows_pad: int = 0, kmax: int = 0,
                 split: int = 0) -> "ELL":
        rows, cols, vals = a.to_coo()
        return ELL.from_coo(rows, cols, vals, a.shape,
                            n_rows_pad=n_rows_pad, kmax=kmax, split=split)


def _chunks(e: ELL, width: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(cols, vals, chunk_row) of ``e`` as a split layout of ``width``
    slots; an unsplit ``e`` (every row within ``width``) keeps one chunk
    row per row that holds an entry."""
    if e.chunk_row is not None:
        return e.cols, e.vals, e.chunk_row
    held = np.flatnonzero((e.cols >= 0).any(axis=1))
    cols = np.full((held.size, width), -1, dtype=np.int32)
    vals = np.zeros((held.size, width), dtype=np.float32)
    cols[:, : e.kmax] = e.cols[held]
    vals[:, : e.kmax] = e.vals[held]
    return cols, vals, held.astype(np.int32)


def padded_chunk_rows(n: int) -> int:
    """The chunk-row count a split layout is stacked to.

    Up to 2^17 the count stays as it is.  Above, it is padded to 512 past
    a multiple of 1024.  The TPU compiler lays the slot loop's
    ``[n_chunks]`` vectors in tiles of 1024 rows, and where the last tile
    is full or nearly so (``n_chunks % 1024`` 0 or above about 800) it
    tiles the loop in windows that ran it 5 to 6% slower (TPU v5e, K = 8:
    316 against 298 ms on the scale-20 Graph500 graph, 249.5 against
    234.8 ms on the transposed 2^20 x 25 random matrix, for the same
    chunks padded to each count).  One residue also gives every matrix of
    a size the same program.  The padding is under 1024 chunk rows."""
    if n <= 1 << 17:
        return n
    return n + (512 - n) % 1024


def stack_ell(per_rank: List["ELL"], kmax: Optional[int] = None
              ) -> Tuple[np.ndarray, np.ndarray, int, Optional[np.ndarray]]:
    """Align ranks to one shared kmax and stack into the
    [n_procs, n_rows, kmax] cols/vals arrays the SPMD executor shards;
    the fourth item is None.

    Where any rank is split, every rank is aligned to its split width
    ``K``: the arrays are [n_procs, n_chunks, K] with the chunk-row count
    padded to the maximum over ranks, and the fourth item is the
    [n_procs, n_chunks] ``chunk_row`` map, whose padding chunks point at
    the padding row ``n_rows`` (one past the last output row, which the
    product's combine drops); ``n_chunks`` is :func:`padded_chunk_rows`
    of the largest count."""
    n_procs = len(per_rank)
    if all(e.chunk_row is None for e in per_rank):
        kmax = max(kmax or 1, max(e.kmax for e in per_rank))
        n_rows = max(e.n_rows for e in per_rank)
        cols = np.full((n_procs, n_rows, kmax), -1, dtype=np.int32)
        vals = np.zeros((n_procs, n_rows, kmax), dtype=np.float32)
        for r, e in enumerate(per_rank):
            cols[r, : e.n_rows, : e.kmax] = e.cols
            vals[r, : e.n_rows, : e.kmax] = e.vals
        return cols, vals, kmax, None
    width = max(e.kmax for e in per_rank if e.chunk_row is not None)
    parts = [_chunks(e, width) for e in per_rank]
    n_rows = max(e.n_rows for e in per_rank)
    n_chunks = padded_chunk_rows(max(1, max(c.shape[0]
                                            for c, _, _ in parts)))
    cols = np.full((n_procs, n_chunks, width), -1, dtype=np.int32)
    vals = np.zeros((n_procs, n_chunks, width), dtype=np.float32)
    chunk_row = np.full((n_procs, n_chunks), n_rows, dtype=np.int32)
    for r, (c, v, cr) in enumerate(parts):
        cols[r, : c.shape[0]] = c
        vals[r, : c.shape[0]] = v
        chunk_row[r, : cr.size] = cr
    return cols, vals, width, chunk_row
