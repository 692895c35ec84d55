"""The row-split ELL layout, its product, the tuner's choice of split
width, and the Graph500 / PageRank deployment that needs it.

The four-device checks run once, as one subprocess
(``tests/multidev/split_ell_prog.py``), and each of its cases is a test
of its own here.  Everything else runs in process: the layout and its
stacking across ranks, the product against dense and float64 scipy on
one device, the tuner keeping the unsplit layout (and so the unsplit
program) on the stencil, the uniform random forward matrix and the
four-chip plan, the reports, the generator and PageRank.  Besides, the
one row-count rule every stacked ELL follows (``slot_loop_rows``): its
cases, padded products bit for bit against the same operator with the
rule off, the programs it leaves alone, and each cell's layout and
``ell_pad_rows`` at its real size.
"""
import importlib.util
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

import repro.api as nap
from repro.amg.solve import pagerank
from repro.core.cost_model import (TPU_V5E_LOCAL, LocalComputeParams,
                                   choose_ell_split, ell_resident_bytes,
                                   local_format_times)
from repro.core.partition import contiguous_partition
import repro.core.spmv_jax as spmv_jax
import repro.sparse.ell as ell_mod
from repro.core.spmv_jax import (_compose_cols, _ell_layout,
                                 _fused_ell_arrays, compile_nap,
                                 nap_forward_shardmap, nap_transpose_shardmap)
from repro.core.topology import Topology
from repro.kernels.ell_spmv import ell_spmm_packed, ell_spmm_packed_ref
from repro.sparse import (ELL, graph500_kronecker, pagerank_matrix,
                          random_fixed_nnz, stack_ell)
from repro.sparse.ell import slot_loop_rows

ROOT = pathlib.Path(__file__).resolve().parents[1]
MULTIDEV = [f"{comm}-{d}-nv{nv}"
            for comm in ("nap", "multistep", "standard", "pad")
            for d in ("F", "T") for nv in (1, 8)]


@pytest.fixture(scope="module")
def graph():
    return pagerank_matrix(graph500_kronecker(10, seed=3))


def _skewed_coo(seed=0, n=40):
    """Rows of 0 to 30 entries, one hub row, some rows empty."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 4, n)
    counts[5], counts[7] = 30, 0
    rows = np.repeat(np.arange(n), counts)
    cols = np.concatenate([rng.choice(n, c, replace=False) for c in counts])
    return rows, cols, rng.standard_normal(rows.size)


# -- the layout ---------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 4, 8, 16])
def test_split_layout_holds_every_row(k):
    rows, cols, vals = _skewed_coo()
    plain = ELL.from_coo(rows, cols, vals, (40, 40))
    e = ELL.from_coo(rows, cols, vals, (40, 40), split=k)
    counts = np.bincount(rows, minlength=40)
    assert e.kmax == k and e.n_rows == 40
    assert np.all(np.diff(e.chunk_row) >= 0)
    assert np.array_equal(np.bincount(e.chunk_row, minlength=40),
                          -(-counts // k))       # none for an empty row
    np.testing.assert_array_equal(e.to_dense(), plain.to_dense())
    v = np.random.default_rng(1).standard_normal(40)
    np.testing.assert_allclose(e.matvec(v), plain.matvec(v), rtol=1e-6)


def test_split_at_or_above_the_longest_row_is_the_unsplit_layout():
    rows, cols, vals = _skewed_coo()
    plain = ELL.from_coo(rows, cols, vals, (40, 40), n_rows_pad=48)
    for k in (30, 64):
        e = ELL.from_coo(rows, cols, vals, (40, 40), n_rows_pad=48, split=k)
        assert e.chunk_row is None
        assert np.array_equal(e.cols, plain.cols)
        assert np.array_equal(e.vals, plain.vals)


def test_stack_aligns_split_and_unsplit_ranks_to_one_width():
    rows, cols, vals = _skewed_coo()
    split = ELL.from_coo(rows, cols, vals, (40, 40), split=4)
    short = ELL.from_coo(np.array([0, 0, 3]), np.array([1, 2, 3]),
                         np.array([1.0, 2.0, 3.0]), (40, 40), split=4)
    assert short.chunk_row is None                # its rows fit 4 slots
    c, v, width, cr = stack_ell([split, short])
    assert width == 4 and c.shape == (2, split.n_chunks, 4)
    assert cr.shape == (2, split.n_chunks)
    assert list(cr[1, :2]) == [0, 3] and np.all(cr[1, 2:] == 40)
    assert np.all(c[1, 2:] == -1) and not v[1, 2:].any()
    for r, e in enumerate((split, short)):
        again = ELL(cols=c[r], vals=v[r], shape=(40, 40), chunk_row=cr[r],
                    n_out=40)
        np.testing.assert_array_equal(again.to_dense(), e.to_dense())
    # unsplit ranks stack as they always did
    c2, v2, k2, cr2 = stack_ell([short, short])
    assert cr2 is None and k2 == 2 and c2.shape == (2, 40, 2)


@pytest.mark.parametrize("n,want", [
    (75, 75), (1 << 17, 1 << 17),           # up to 2^17 the count stays
    (131073, 131584),
    (1 << 18, 262656),                      # paper_random_25 per chip on 4
    (1 << 20, 1049088),                     # paper_random_25 on one chip
    (1124864, 1124864),                     # HPCG's 104^3: already at 512
    (3735140, 3736064),                     # the transposed random matrix
    (4735938, 4736512), (4736156, 4736512),  # the scale-20 graph's seeds
    (4736253, 4736512), (4736512, 4736512)])
def test_chunk_rows_pad_to_512_past_a_multiple_of_1024(n, want):
    got = slot_loop_rows(n)
    assert got == want and n <= got < n + 1024
    assert got <= 1 << 17 or got % 1024 == 512
    assert slot_loop_rows(got) == got       # a padded count maps to itself


# -- the product ----------------------------------------------------------------

@pytest.mark.parametrize("nv", [1, 8])
def test_split_product_matches_dense(nv):
    rows, cols, vals = _skewed_coo(seed=4)
    e = ELL.from_coo(rows, cols, vals, (40, 40), split=4)
    c, v, _, cr = stack_ell([e, ELL.from_coo(rows[:9], cols[:9], vals[:9],
                                             (40, 40), split=4)])
    x = np.random.default_rng(nv).standard_normal((40, nv)).astype(np.float32)
    xs = (jnp.asarray(x[:25]), jnp.asarray(x[25:]))
    w = np.asarray(ell_spmm_packed(c[0], v[0], xs, cr[0], n_rows=40))
    np.testing.assert_allclose(w, e.to_dense() @ x, rtol=1e-5, atol=1e-5)
    ref = ell_spmm_packed_ref(c[0], v[0], xs, cr[0], 40)
    np.testing.assert_allclose(w, np.asarray(ref), rtol=1e-6, atol=1e-6)
    # the padded rank: padding chunks point past the last row, dropped
    w1 = np.asarray(ell_spmm_packed(c[1], v[1], xs, cr[1], n_rows=40))
    assert not w1[rows[8] + 1:].any()


@pytest.mark.parametrize("nv", [1, 8])
def test_unsplit_product_drops_the_stack_padding_rows(nv):
    rows, cols, vals = _skewed_coo(seed=5)
    e = ELL.from_coo(rows, cols, vals, (40, 40))
    pad_c = np.concatenate([e.cols, np.full((24, e.kmax), -1, np.int32)])
    pad_v = np.concatenate([e.vals, np.zeros((24, e.kmax), np.float32)])
    x = np.random.default_rng(nv).standard_normal((40, nv)).astype(np.float32)
    xs = (jnp.asarray(x[:25]), jnp.asarray(x[25:]))
    plain = np.asarray(ell_spmm_packed(e.cols, e.vals, xs))
    w = np.asarray(ell_spmm_packed(pad_c, pad_v, xs, n_rows=40))
    assert w.shape == (40, nv) and np.array_equal(w, plain)
    np.testing.assert_allclose(
        np.asarray(ell_spmm_packed_ref(pad_c, pad_v, xs, None, 40)), plain,
        rtol=1e-6, atol=1e-6)
    # with nothing to drop, the program is the one without n_rows
    assert str(jax.make_jaxpr(lambda c, v, *xs: ell_spmm_packed(
        c, v, xs, n_rows=40))(e.cols, e.vals, *xs)) == str(
        jax.make_jaxpr(lambda c, v, *xs: ell_spmm_packed(c, v, xs))(
            e.cols, e.vals, *xs))


def test_combine_runs_under_its_scope():
    e = ELL.from_coo(*_skewed_coo(), (40, 40), split=4)
    text = jax.jit(lambda c, v, x, r: ell_spmm_packed(
        c, v, (x,), r, n_rows=40)).lower(
        e.cols, e.vals, np.ones((40, 1), np.float32), e.chunk_row).as_text(
        debug_info=True)
    assert re.search(r"repro\.local\.combine", text)
    plain = jax.make_jaxpr(lambda c, v, x: ell_spmm_packed(c, v, (x,)))(
        e.cols, e.vals, np.ones((40, 1), np.float32))
    assert "scatter-add" not in str(plain)


# -- the operator on one device --------------------------------------------------

@pytest.mark.parametrize("nv", [1, 8])
@pytest.mark.parametrize("direction", ["forward", "transpose"])
def test_graph_operator_matches_float64_scipy(graph, direction, nv):
    op = nap.operator(graph, local_compute="auto", cache=False)
    rep = op.autotune_report()
    a = sp.csr_matrix((graph.data, graph.indices, graph.indptr),
                      shape=graph.shape)
    if direction == "transpose":
        op, a, at = op.T, a.T.tocsr(), rep["transpose"]
    else:
        at = rep
    assert at["ell"]["ell_split_width"] < at["stats"]["ell_kmax"]
    x = np.random.default_rng(nv).standard_normal(
        (graph.shape[1], nv) if nv > 1 else graph.shape[1]).astype(np.float32)
    y = np.asarray(op @ x, np.float64)
    xd = x.astype(np.float64)
    err = np.abs(y - a @ xd) / np.maximum(abs(a) @ np.abs(xd), 1e-30)
    assert err.max() <= 1e-5


def test_graph_reports_the_split_layout_per_direction(graph):
    op = nap.operator(graph, local_compute="auto", cache=False)
    rep, stats = op.autotune_report(), op.stats()
    assert rep["resolved"] == "ell" and rep["transpose_resolved"] == "ell"
    for got in (rep["ell"], rep["transpose"]["ell"], stats,
                stats["transpose"]):
        k, chunks = got["ell_split_width"], got["ell_chunk_rows"]
        assert k < rep["stats"]["ell_kmax"]
        assert got["ell_slots"] == k * chunks
        assert 1.0 <= got["ell_slots_per_nnz"] < 1.5
    assert stats["ell_slots"] == rep["ell"]["ell_slots"]
    assert stats["transpose"]["ell_slots"] == rep["transpose"]["ell"]["ell_slots"]


# -- the tuner keeps the unsplit layout where rows are even ---------------------

def _builder(config):
    path = ROOT / "bench" / "configs" / f"{config}.py"
    spec = importlib.util.spec_from_file_location(f"cfg_{config}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build


def _cell_matrix(config, **cfg):
    from repro.sparse import CSR
    indptr, indices, data, shape = _builder(config)(cfg, 2**31 + 1)
    return CSR(indptr=indptr, indices=indices, data=data, shape=shape)


CELL_PLANS = {
    # the 27-point stencil at 16^3 rows (HPCG's kmax 27 with its boundary
    # rows; 4096 rows fill the 128-row tiles, as 104^3 does)
    "stencil": (lambda: _cell_matrix("hpcg_27pt_104", nx=16, ny=16, nz=16,
                                     diagonal=26.0, off_diagonal=-1.0),
                (1, 1)),
    "random": (lambda: _cell_matrix("paper_random_25", n_rows=512,
                                    nnz_per_row=25), (1, 1)),
    "random_x4": (lambda: _cell_matrix("paper_random_25", n_rows=512,
                                       nnz_per_row=25), (2, 2)),
}


@pytest.mark.parametrize("name", sorted(CELL_PLANS))
def test_even_rows_keep_the_unsplit_layout_and_program(name):
    make, shape = CELL_PLANS[name]
    a, topo = make(), Topology(*shape)
    c = compile_nap(a, contiguous_partition(a.shape[0], topo.n_procs), topo,
                    cache=False)
    assert c.chosen_local_compute == "ell"
    at = c.autotune
    assert at["ell"]["ell_split_width"] == at["stats"]["ell_kmax"]
    c.ensure_ell()
    assert "ell_chunk_row" not in c.arrays
    if name == "stencil":            # symmetric: its transpose too
        at = c.autotune["transpose"]
        assert at["ell"]["ell_split_width"] == at["stats"]["ell_kmax"]
        c.ensure_ell_t()
        assert "ell_t_chunk_row" not in c.arrays
    cols, vals, kmax, chunk_row = _fused_ell_arrays(
        c.local_blocks, c.rows_pad, c.cols_pad, c.pads["bnode"],
        c.pads["boff"])
    assert chunk_row is None and kmax == c.ell_kmax
    assert np.array_equal(_compose_cols(cols, c.recv_domain_map()),
                          c.arrays["ell_cols"])
    assert np.array_equal(vals, c.arrays["ell_vals"])
    if topo.n_procs == 1:
        from repro.compat import make_mesh
        run = nap_forward_shardmap(c, make_mesh((1, 1), ("node", "proc")),
                                   local_compute="ell")
        jaxpr = str(jax.make_jaxpr(run.jitted)(
            np.zeros((1, 1, c.cols_pad, 1), np.float32), *run.args()))
        # the slot loop (a fori_loop of static length) and no combine
        assert "scatter-add" not in jaxpr and jaxpr.count(" scan[") == 1


# -- one row count for every slot loop -------------------------------------------

PAD_ROWS = (1 << 17) + 1024     # a full last 1024-row tile: the rule adds 512


@pytest.fixture(scope="module")
def padded_pair():
    """One matrix of ``PAD_ROWS`` rows through the operator on one device
    twice: as it runs (every ELL stacked to ``slot_loop_rows``), and with
    the stack's rule switched off while its arrays are emitted."""
    a = random_fixed_nnz(PAD_ROWS, 5, seed=2)
    ops = {}
    for name in ("padded", "plain"):
        with pytest.MonkeyPatch.context() as mp:
            if name == "plain":
                mp.setattr(ell_mod, "slot_loop_rows", lambda n: n)
            op = nap.operator(a, local_compute="ell", cache=False)
            op.executor.compiled.ensure_ell()
            op.executor.compiled.ensure_ell_t()
        ops[name] = op
    return a, ops


@pytest.mark.parametrize("nv", [1, 8])
@pytest.mark.parametrize("direction", ["forward", "transpose"])
def test_padded_rows_leave_every_real_row_bit_for_bit(padded_pair,
                                                       direction, nv):
    a, ops = padded_pair
    name, ref = ("ell_cols", sp.csr_matrix(
        (a.data, a.indices, a.indptr), shape=a.shape))
    if direction == "transpose":
        name, ref = "ell_t_cols", ref.T.tocsr()
    pad = ops["padded"].executor.compiled
    plain = ops["plain"].executor.compiled
    run_rows = pad.arrays[name].shape[1]
    assert run_rows == slot_loop_rows(plain.arrays[name].shape[1])
    if direction == "forward":      # unsplit: 512 rows of padding slots
        assert run_rows == pad.rows_pad + 512
        assert ops["padded"].stats()["ell_pad_rows"] == 512
    x = np.random.default_rng(nv).standard_normal(
        (a.shape[1], nv) if nv > 1 else a.shape[1]).astype(np.float32)
    got = [np.asarray((op if direction == "forward" else op.T) @ x)
           for op in (ops["padded"], ops["plain"])]
    assert np.array_equal(got[0], got[1])
    xd = x.astype(np.float64)
    err = np.abs(got[0] - ref @ xd) / np.maximum(abs(ref) @ np.abs(xd), 1e-30)
    assert err.max() <= 1e-5


def _parent_ell_product(ell_args, xs, n_rows):
    """The product as the shard programs called it before the stack
    padded unsplit layouts: no ``n_rows`` without a ``chunk_row`` map."""
    if len(ell_args) == 2:
        return ell_spmm_packed(*ell_args, xs)
    cols, vals, chunk_row = ell_args
    return ell_spmm_packed(cols, vals, xs, chunk_row, n_rows=n_rows)


def _ell_program_jaxpr(c, direction):
    from repro.compat import make_mesh
    build = (nap_forward_shardmap if direction == "forward"
             else nap_transpose_shardmap)
    run = build(c, make_mesh((1, 1), ("node", "proc")), local_compute="ell")
    n_in = c.cols_pad if direction == "forward" else c.rows_pad
    return str(jax.make_jaxpr(run.jitted)(
        np.zeros((1, 1, n_in, 1), np.float32), *run.args()))


UNPADDED_PLANS = {
    # HPCG's stencil at 56^3 = 175,616 rows: like 104^3, 512 past a
    # multiple of 1024, so the rule adds no row above 2^17
    "stencil_56": lambda: _cell_matrix("hpcg_27pt_104", nx=56, ny=56, nz=56,
                                       diagonal=26.0, off_diagonal=-1.0),
    "graph": lambda: pagerank_matrix(graph500_kronecker(10, seed=3)),
    "random": lambda: _cell_matrix("paper_random_25", n_rows=512,
                                   nnz_per_row=25),
}


@pytest.mark.parametrize("name,direction", [
    ("stencil_56", "forward"), ("graph", "forward"), ("graph", "transpose"),
    ("random", "transpose")])
def test_layouts_the_rule_adds_no_rows_to_keep_the_parent_program(
        monkeypatch, name, direction):
    a = UNPADDED_PLANS[name]()
    c = compile_nap(a, contiguous_partition(a.shape[0], 1), Topology(1, 1),
                    cache=False)
    at = c.autotune if direction == "forward" else c.autotune["transpose"]
    assert at["ell"]["ell_pad_rows"] == 0
    (c.ensure_ell if direction == "forward" else c.ensure_ell_t)()
    cols = c.arrays["ell_cols" if direction == "forward" else "ell_t_cols"]
    split = at["ell"]["ell_split_width"] < at["stats"]["ell_kmax"]
    assert split == (name != "stencil_56")
    counted = (dict(at["stats"]["ell_splits"])[at["ell"]["ell_split_width"]]
               if split else at["stats"]["rows_pad"])
    assert cols.shape[1] == counted == at["ell"]["ell_chunk_rows"]
    if name == "stencil_56":
        assert c.rows_pad == 175_616 > 1 << 17
    now = _ell_program_jaxpr(c, direction)
    monkeypatch.setattr(spmv_jax, "_ell_product", _parent_ell_product)
    assert now == _ell_program_jaxpr(c, direction)


def _cell_stats(rows, kmax, n_x, row_lengths):
    """The tuner's stats of one benchmark cell's ELL at its real size,
    with every split width's chunk rows counted from ``row_lengths``."""
    widths = [1 << i for i in range((kmax - 1).bit_length())]
    return {"rows_pad": rows, "n_x": n_x, "ell_n_x": n_x, "ell_kmax": kmax,
            "ell_splits": [[k, int((-(-row_lengths // k)).sum())]
                           for k in widths]}


def _transposed_random_lengths():
    # the columns of the 2^20 x 25 random matrix: the diagonal and
    # Poisson(24) more, over the packed domain 2^20 + 256
    n = (1 << 20) + 256
    lengths = np.random.default_rng(17).poisson(24, n) + 1
    lengths[1 << 20:] = 0
    return lengths


CELL_LAYOUTS = {
    # cell: (stats, split width, chunk rows, pad rows) as the program runs
    "paper_random_25.spmv": (
        lambda: _cell_stats(1 << 20, 25, (1 << 20) + 256,
                            np.full(1 << 20, 25)), 25, 1049088, 512),
    "paper_random_25.spmv_x4": (
        lambda: _cell_stats(1 << 18, 25, 1_832_476, np.full(1 << 18, 25)),
        25, 262656, 512),
    "hpcg_27pt_104.spmv": (
        lambda: _cell_stats(1_124_864, 27, 1_124_864 + 256,
                            np.full(1_124_864, 27)), 27, 1_124_864, 0),
    "paper_random_25.transpose": (
        lambda: _cell_stats((1 << 20) + 256, 53, 1 << 20,
                            _transposed_random_lengths()), 8, None, None),
}


@pytest.mark.parametrize("cell", sorted(CELL_LAYOUTS))
def test_cells_keep_their_layout_and_report_the_pad_rows(cell):
    make, width, chunk_rows, pad = CELL_LAYOUTS[cell]
    stats = make()
    got = _ell_layout(stats, TPU_V5E_LOCAL, 1)
    assert got["ell_split_width"] == width
    counted = dict(stats["ell_splits"]).get(width, stats["rows_pad"])
    assert got["ell_chunk_rows"] == slot_loop_rows(counted)
    assert got["ell_pad_rows"] == got["ell_chunk_rows"] - counted
    if chunk_rows is not None:
        assert (got["ell_chunk_rows"], got["ell_pad_rows"]) == (chunk_rows,
                                                                pad)
    assert got["ell_slots"] == width * got["ell_chunk_rows"]


# -- the price of a split ------------------------------------------------------------

def _graph_stats():
    return {"rows_pad": 1024, "n_x": 1024, "nnz_pad": 4000, "bsr_blocks": 512,
            "bm": 8, "bn": 128, "ell_kmax": 500,
            "ell_splits": [[1, 4000], [2, 2600], [4, 1500], [8, 1100],
                           [16, 1050], [32, 1030], [64, 1025], [128, 1025],
                           [256, 1025]]}


def test_the_tuner_prices_slots_and_chunk_partials():
    stats = _graph_stats()
    width, chunks, t = choose_ell_split(stats)
    # slots + 1.3 per partial: K=4 issues 6000 + 1950, K=2 5200 + 3380,
    # K=8 8800 + 1430
    assert (width, chunks) == (4, 1500)
    assert t == local_format_times(stats)["ell"]
    assert local_format_times(stats)["ell"] < local_format_times(
        dict(stats, ell_splits=[]))["ell"]
    # partials dearer than any saving: the unsplit layout
    dear = LocalComputeParams(ell_combine_slots=1e4)
    assert choose_ell_split(stats, dear)[:2] == (500, 1024)
    assert ell_resident_bytes(1024, 4, 1024, 1, 1500) < ell_resident_bytes(
        1024, 500, 1024, 1)
    assert TPU_V5E_LOCAL.ell_combine_slots > 0


# -- Graph500 and PageRank ------------------------------------------------------------

def test_graph500_kronecker_is_an_undirected_power_law_graph():
    a = graph500_kronecker(10, seed=3)
    m = sp.csr_matrix((a.data, a.indices, a.indptr), shape=a.shape)
    assert a.shape == (1024, 1024)
    assert (m != m.T).nnz == 0 and not m.diagonal().any()
    assert m.nnz <= 2 * 16 * 1024 and set(np.unique(a.data)) == {1.0}
    deg = np.diff(a.indptr)
    assert deg.max() > 20 * max(np.median(deg), 1)
    b = graph500_kronecker(10, seed=3)
    assert np.array_equal(a.indices, b.indices)
    assert not np.array_equal(a.indices, graph500_kronecker(10, seed=4).indices)


def test_pagerank_matrix_keeps_every_diagonal(graph):
    m = sp.csr_matrix((graph.data, graph.indices, graph.indptr),
                      shape=graph.shape)
    assert np.all(m.diagonal() == 1.0)
    colsum = np.asarray(m.sum(axis=0)).ravel()
    assert np.allclose(colsum[colsum != 1.0], 1.0 - 0.85, atol=1e-6)
    assert np.all(graph.data.astype(np.float32) == graph.data)


def _power_iteration(adj, d, iters):
    """Graphalytics' PageRank in float64, straight from the adjacency."""
    n = adj.shape[0]
    a = sp.csr_matrix((adj.data, adj.indices, adj.indptr), shape=adj.shape)
    out = np.asarray(a.sum(axis=1)).ravel()
    dangling = out == 0
    p = a.T.tocsr() @ sp.diags(np.where(dangling, 0.0, 1.0 / np.maximum(out, 1)))
    x = np.full(n, 1.0 / n)
    for _ in range(iters):
        x = (1 - d) / n + d * (p @ x) + d / n * x[dangling].sum()
    return x


def test_pagerank_matches_a_float64_power_iteration():
    adj = graph500_kronecker(10, seed=3)
    op = nap.operator(pagerank_matrix(adj), local_compute="auto", cache=False)
    x = pagerank(op, iters=30)
    want = _power_iteration(adj, 0.85, 30)
    assert abs(x.sum() - 1.0) < 1e-5
    assert np.abs(x - want).max() <= 1e-5 * want.max()
    deg = np.diff(adj.indptr)
    again = pagerank(op, iters=30, dangling=deg == 0)
    np.testing.assert_allclose(again, x, rtol=1e-6, atol=1e-9)


# -- four devices ------------------------------------------------------------------------

@pytest.fixture(scope="module")
def multidev_results():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)  # the program sets its own device count
    p = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "multidev" / "split_ell_prog.py")]
        + MULTIDEV, capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout


@pytest.mark.multidev
@pytest.mark.parametrize("name", MULTIDEV)
def test_split_ell_on_four_devices_matches_float64(multidev_results, name):
    m = re.search(rf"^CASE {re.escape(name)} (OK|FAIL)$", multidev_results,
                  re.M)
    assert m, f"case {name} did not run:\n{multidev_results}"
    assert m.group(1) == "OK", multidev_results[m.start():]
