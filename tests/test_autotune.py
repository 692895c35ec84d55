"""Density-driven local-compute format autotuner (single-process).

Covers the cost-model chooser's three regimes (dense blocks -> bsr,
flat low-density rows -> ell, skewed rows / too big for HBM -> coo), the
stats + verdict compile_nap records on CompiledNAP, the packed ELL
emission's layout invariant, and the cache-key extensions that keep
``local_compute`` / tuner switches from returning stale plans.
"""
import numpy as np
import pytest

from repro.core.cost_model import (LOCAL_FORMATS, LocalComputeParams,
                                   TPU_V5E_LOCAL, choose_local_format,
                                   ell_resident_bytes, local_format_times)
from repro.core.partition import contiguous_partition, make_partition
from repro.core.spmv import split_all_blocks
from repro.core.spmv_jax import (clear_compile_cache, compile_nap)
from repro.core.topology import Topology
from repro.sparse import CSR, ELL, random_fixed_nnz

TOPOS = [(1, 4), (2, 2), (4, 2)]


# ---------------------------------------------------------------------------
# chooser regimes
# ---------------------------------------------------------------------------

def test_chooser_prefers_bsr_on_dense_blocks():
    stats = {"rows_pad": 256, "n_x": 320, "nnz_pad": 2048,
             "bsr_blocks": 36, "bm": 8, "bn": 8, "ell_kmax": 8}
    times = local_format_times(stats)
    assert choose_local_format(stats) == "bsr"
    assert times["bsr"] < times["ell"] < times["coo"]


def test_chooser_prefers_ell_on_flat_low_density():
    # the BENCH block-hostile regime: ~8 nnz/row, (8, 128) tiles at <1% fill
    stats = {"rows_pad": 256, "n_x": 1408, "nnz_pad": 2111,
             "bsr_blocks": 352, "bm": 8, "bn": 128, "ell_kmax": 8}
    assert choose_local_format(stats) == "ell"


def test_chooser_prefers_coo_on_skewed_rows():
    # one super-dense row blows up ELL's kmax padding
    stats = {"rows_pad": 256, "n_x": 1408, "nnz_pad": 2300,
             "bsr_blocks": 352, "bm": 8, "bn": 128, "ell_kmax": 2000}
    assert choose_local_format(stats) == "coo"


def test_chooser_rejects_ell_when_x_exceeds_vmem():
    # the ELL product's device footprint (the padded working copy of
    # cols + vals alone is 8 GiB at 2^26 rows x kmax 12 -> 16 sublanes)
    # exceeds the HBM budget
    stats = {"rows_pad": 2**26, "n_x": 2**26, "nnz_pad": 40_000,
             "bsr_blocks": 5000, "bm": 8, "bn": 128, "ell_kmax": 12}
    assert ell_resident_bytes(2**26, 12, 2**26, 1) > \
        TPU_V5E_LOCAL.ell_hbm_budget
    assert local_format_times(stats)["ell"] == float("inf")
    assert choose_local_format(stats) != "ell"
    # a 6M-entry packed x is no reason to refuse ELL any more: the x
    # operand stays in HBM, not in a VMEM block
    stats.update(rows_pad=4096, n_x=6_000_000)
    assert local_format_times(stats)["ell"] < float("inf")


@pytest.mark.parametrize("rows,kmax,n_x,nv,want", [
    # staged cols/vals keep kmax unpadded; the working copy pads it to 8
    # sublanes; width 1 packs densely on the lanes, other widths pad to 8
    (2**20, 25, 2**20 + 256, 1,
     2 * 4 * 25 * 2**20 + 2 * 4 * 32 * 2**20 + 2 * 4 * (2**20 + 256)
     + 4 * 2**20),
    (2**20, 25, 2**20 + 256, 8,
     2 * 4 * 25 * 2**20 + 2 * 4 * 32 * 2**20 + 2 * 4 * 8 * (2**20 + 256)
     + 4 * 8 * 2**20),
    (100, 3, 130, 2,
     2 * 4 * 3 * 128 + 2 * 4 * 8 * 128 + 2 * 4 * 8 * 256 + 4 * 8 * 128),
])
def test_ell_resident_bytes_counts_tile_padding(rows, kmax, n_x, nv, want):
    assert ell_resident_bytes(rows, kmax, n_x, nv) == want


# ---------------------------------------------------------------------------
# compile-time recording
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nn,ppn", TOPOS)
def test_compile_records_autotune_verdict(nn, ppn):
    topo = Topology(n_nodes=nn, ppn=ppn)
    a = random_fixed_nnz(64, 5, seed=1)
    part = make_partition("contiguous", 64, topo.n_procs)
    compiled = compile_nap(a, part, topo, block_shape=(8, 16), cache=False)
    at = compiled.autotune
    assert at["chosen"] in LOCAL_FORMATS
    assert set(at["times"]) == set(LOCAL_FORMATS)
    assert len(at["per_rank"]) == topo.n_procs
    for entry in at["per_rank"]:
        assert entry["choice"] in LOCAL_FORMATS
        assert 0.0 <= entry["bsr_fill"] <= 1.0
        assert entry["ell_kmax"] >= 1
    assert compiled.chosen_local_compute == at["chosen"]
    assert compiled.resolve_local_compute("auto") == at["chosen"]
    assert compiled.resolve_local_compute("coo") == "coo"
    with pytest.raises(ValueError):
        compiled.resolve_local_compute("csr")


def test_block_hostile_low_density_selects_non_bsr():
    """<= 12 nnz/row at (8, 128) tiles densifies ~1/fill: never pick bsr."""
    topo = Topology(n_nodes=2, ppn=4)
    for seed, nnz_row in ((0, 8), (1, 12), (2, 4)):
        a = random_fixed_nnz(2048, nnz_row, seed=seed)
        part = contiguous_partition(2048, topo.n_procs)
        compiled = compile_nap(a, part, topo, cache=False)
        assert compiled.chosen_local_compute in ("ell", "coo")
        assert all(e["choice"] in ("ell", "coo")
                   for e in compiled.autotune["per_rank"])


def test_dense_block_diagonal_selects_bsr():
    """Dense (8, 8) diagonal blocks are the MXU's home turf."""
    n, b = 128, 8
    rng = np.random.default_rng(3)
    dense = np.zeros((n, n))
    for i in range(0, n, b):
        dense[i:i + b, i:i + b] = rng.standard_normal((b, b))
    a = CSR.from_dense(dense)
    topo = Topology(n_nodes=2, ppn=2)
    part = contiguous_partition(n, topo.n_procs)
    compiled = compile_nap(a, part, topo, block_shape=(8, 8), cache=False)
    assert compiled.chosen_local_compute == "bsr"


# ---------------------------------------------------------------------------
# packed ELL emission
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nn,ppn", TOPOS)
def test_packed_ell_layout_equals_local_blocks(nn, ppn):
    """The ELL arrays, viewed densely per rank over the received domain
    and read back through the plan's packed -> received column map,
    reproduce the three column blocks at their packed-domain offsets
    (v_loc | on-node | off-node)."""
    topo = Topology(n_nodes=nn, ppn=ppn)
    a = random_fixed_nnz(60, 6, seed=11)
    part = make_partition("contiguous", 60, topo.n_procs)
    compiled = compile_nap(a, part, topo, block_shape=(8, 16), cache=False)
    compiled.ensure_ell()
    rows_pad, pads = compiled.rows_pad, compiled.pads
    remap = compiled.recv_domain_map()
    assert remap.shape == (topo.n_procs, compiled.packed_x_len)
    for r, blk in enumerate(split_all_blocks(a, part, topo)):
        ell = ELL(cols=compiled.arrays["ell_cols"][r],
                  vals=compiled.arrays["ell_vals"][r],
                  shape=(rows_pad, compiled.recv_x_len))
        dense = ell.to_dense()[:, remap[r]]
        nr = blk.rows.size
        np.testing.assert_allclose(dense[:nr, :nr], blk.on_proc.to_dense(),
                                   atol=1e-6)
        o = rows_pad
        np.testing.assert_allclose(dense[:nr, o:o + blk.on_node.shape[1]],
                                   blk.on_node.to_dense(), atol=1e-6)
        o = rows_pad + pads["bnode"]
        np.testing.assert_allclose(dense[:nr, o:o + blk.off_node.shape[1]],
                                   blk.off_node.to_dense(), atol=1e-6)
        assert not dense[nr:].any()


def test_packed_segments_are_lane_aligned():
    """Every packed-x segment length is rounded to the bn lane width, so the
    kernels can view v_loc / b_on_node / b_off_node zero-copy."""
    topo = Topology(n_nodes=2, ppn=2)
    a = random_fixed_nnz(50, 5, seed=2)      # 50 rows -> ragged per-rank counts
    part = make_partition("contiguous", 50, topo.n_procs)
    for bn in (8, 16, 128):
        compiled = compile_nap(a, part, topo, block_shape=(8, bn), cache=False)
        assert compiled.rows_pad % bn == 0
        assert compiled.pads["bnode"] % bn == 0
        assert compiled.pads["boff"] % bn == 0


# ---------------------------------------------------------------------------
# cache keying
# ---------------------------------------------------------------------------

def test_cache_distinguishes_local_compute_and_tuner():
    clear_compile_cache()
    topo = Topology(n_nodes=2, ppn=2)
    a = random_fixed_nnz(60, 6, seed=9)
    part = make_partition("contiguous", 60, topo.n_procs)
    c_auto = compile_nap(a, part, topo)
    assert compile_nap(a, part, topo) is c_auto
    c_ell = compile_nap(a, part, topo, local_compute="ell")
    assert c_ell is not c_auto
    assert compile_nap(a, part, topo, local_compute="ell") is c_ell
    # a compile-time format request is an override that "auto" executors
    # resolve to (explicit executor requests still win)
    assert c_ell.resolve_local_compute("auto") == "ell"
    assert c_ell.resolve_local_compute("coo") == "coo"
    assert c_auto.resolve_local_compute("auto") == c_auto.autotune["chosen"]
    # autotuner inputs (rate model) are part of the key too
    slow_scatter = LocalComputeParams(scatter_flops=1.0)
    c_tuned = compile_nap(a, part, topo, tuner=slow_scatter)
    assert c_tuned is not c_auto
    assert c_tuned.autotune["times"]["coo"] > c_auto.autotune["times"]["coo"]
    with pytest.raises(ValueError):
        compile_nap(a, part, topo, local_compute="csr")
    clear_compile_cache()
