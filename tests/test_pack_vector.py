"""pack_vector / unpack_vector edge cases.

The packed-shards layout ([n_nodes, ppn, rows_pad(, nv)]) is the one
contract every shardmap executor and the operator front-end share, so the
edges get explicit coverage: uneven ``contiguous_partition`` tails
(remainder rows on the leading ranks), EMPTY ranks (more ranks than
rows), non-contiguous partitions, and round-trips under the bn-aligned
rows_pad the compiled plans use.
"""
import numpy as np
import pytest

from repro.core.partition import (contiguous_partition, make_partition,
                                  strided_partition)
from repro.core.spmv_jax import pack_vector, unpack_vector
from repro.core.topology import Topology


def _roundtrip(v, part, topo, rows_pad):
    shards = pack_vector(v, part, topo, rows_pad)
    assert shards.shape[:3] == (topo.n_nodes, topo.ppn, rows_pad)
    return shards, unpack_vector(shards, part, topo)


@pytest.mark.parametrize("n,nn,ppn", [(37, 2, 3), (41, 4, 2), (65, 4, 4)])
def test_uneven_contiguous_tail_roundtrip(n, nn, ppn):
    """n not divisible by n_procs: remainder rows sit on leading ranks."""
    topo = Topology(n_nodes=nn, ppn=ppn)
    part = contiguous_partition(n, topo.n_procs)
    assert int(part.counts().max()) != int(part.counts().min())  # truly uneven
    v = np.random.default_rng(0).standard_normal(n)
    rows_pad = int(part.counts().max())
    shards, back = _roundtrip(v, part, topo, rows_pad)
    np.testing.assert_array_equal(back, v.astype(np.float32))


def test_empty_ranks():
    """More ranks than rows: trailing ranks own zero rows; their shard
    slots must stay zero and unpack must ignore them."""
    topo = Topology(n_nodes=2, ppn=4)
    n = 5  # < 8 ranks
    part = contiguous_partition(n, topo.n_procs)
    assert (part.counts() == 0).any()
    v = np.arange(1.0, n + 1.0)
    shards, back = _roundtrip(v, part, topo, rows_pad=3)
    np.testing.assert_array_equal(back, v.astype(np.float32))
    flat = shards.reshape(topo.n_procs, 3)
    for r in range(topo.n_procs):
        cnt = int(part.counts()[r])
        assert (flat[r, cnt:] == 0).all()


@pytest.mark.parametrize("kind", ["strided", "balanced"])
def test_non_contiguous_partitions_roundtrip(kind):
    topo = Topology(n_nodes=2, ppn=2)
    n = 23
    rng = np.random.default_rng(1)
    indptr = np.arange(n + 1) * 2
    indices = rng.integers(0, n, size=2 * n)
    part = make_partition(kind, n, topo.n_procs, indptr=indptr,
                          indices=indices, seed=3)
    v = rng.standard_normal(n)
    _, back = _roundtrip(v, part, topo, int(part.counts().max()))
    np.testing.assert_array_equal(back, v.astype(np.float32))


@pytest.mark.parametrize("bn", [8, 16, 128])
def test_bn_aligned_padding_roundtrip(bn):
    """rows_pad rounded up to the kernel lane width (what compile_plan
    does): padding slots never leak values and unpack still recovers v."""
    topo = Topology(n_nodes=2, ppn=2)
    n = 30
    part = strided_partition(n, topo.n_procs)
    rows_pad = -(-int(part.counts().max()) // bn) * bn
    v = np.random.default_rng(2).standard_normal(n)
    shards, back = _roundtrip(v, part, topo, rows_pad)
    np.testing.assert_array_equal(back, v.astype(np.float32))
    flat = shards.reshape(topo.n_procs, rows_pad)
    for r in range(topo.n_procs):
        assert (flat[r, int(part.counts()[r]):] == 0).all()


def test_rectangular_row_and_col_partitions_roundtrip():
    """m != n: the forward pack uses the COLUMN partition (n entries,
    cols_pad) while the output unpacks by the ROW partition (m entries,
    rows_pad) — both sides must round-trip bit-for-bit with their own
    partition, including the uneven tails two different sizes produce."""
    topo = Topology(n_nodes=2, ppn=3)
    m, n = 41, 100                      # both leave uneven tails over 6 ranks
    row_part = contiguous_partition(m, topo.n_procs)
    col_part = contiguous_partition(n, topo.n_procs)
    assert int(row_part.counts().max()) != int(row_part.counts().min())
    assert int(col_part.counts().max()) != int(col_part.counts().min())
    rng = np.random.default_rng(4)
    u, v = rng.standard_normal(m), rng.standard_normal(n)
    rows_pad = -(-int(row_part.counts().max()) // 8) * 8
    cols_pad = -(-int(col_part.counts().max()) // 8) * 8
    assert rows_pad != cols_pad         # genuinely two pads in flight
    _, back_u = _roundtrip(u, row_part, topo, rows_pad)
    _, back_v = _roundtrip(v, col_part, topo, cols_pad)
    np.testing.assert_array_equal(back_u, u.astype(np.float32))
    np.testing.assert_array_equal(back_v, v.astype(np.float32))


def test_empty_column_partition_ranks():
    """A coarse AMG col partition can own FEWER entries than there are
    ranks: the empty ranks' shards stay all-zero, unpack ignores them,
    and the round-trip is bit-for-bit — for 1-RHS and multi-RHS."""
    topo = Topology(n_nodes=4, ppn=2)
    n = 3                               # 3 entries over 8 ranks
    part = contiguous_partition(n, topo.n_procs)
    assert int((part.counts() == 0).sum()) == 5
    rng = np.random.default_rng(5)
    for v in (rng.standard_normal(n), rng.standard_normal((n, 4))):
        shards, back = _roundtrip(v, part, topo, rows_pad=8)
        np.testing.assert_array_equal(back, v.astype(np.float32))
        flat = shards.reshape((topo.n_procs, 8) + shards.shape[3:])
        for r in range(topo.n_procs):
            cnt = int(part.counts()[r])
            assert (flat[r, cnt:] == 0).all()


def test_multirhs_roundtrip_and_order():
    """[n, nv] multivectors: packing is column-independent."""
    topo = Topology(n_nodes=2, ppn=2)
    n, nv = 19, 5
    part = contiguous_partition(n, topo.n_procs)
    rng = np.random.default_rng(3)
    v = rng.standard_normal((n, nv))
    shards, back = _roundtrip(v, part, topo, rows_pad=8)
    assert shards.shape == (2, 2, 8, nv)
    np.testing.assert_array_equal(back, v.astype(np.float32))
    for i in range(nv):
        col = pack_vector(v[:, i], part, topo, 8)
        np.testing.assert_array_equal(col, shards[..., i])
