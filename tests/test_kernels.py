"""Kernel sweeps vs pure-jnp / numpy oracles.

Pallas kernels run in interpret mode here (``interpret=True``); the ELL
product is plain XLA.  ``tests/test_tpu_compile.py`` compiles the same
kernels for a described TPU.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.bsr_spmv import (bsr_spmm, bsr_spmv, fused_bsr_spmm,
                                    fused_bsr_spmm_packed, fused_bsr_spmm_ref)
from repro.kernels.bsr_spmv.kernel import bsr_spmm_padded
from repro.kernels.bsr_spmv.ref import bsr_spmm_padded_ref, bsr_spmv_ref
from repro.kernels.decode_attn import decode_attention, decode_attention_ref
from repro.kernels.decode_attn.kernel import decode_attention_grouped
from repro.kernels.ell_spmv import (ell_spmm_packed, ell_spmm_packed_ref,
                                    ell_spmv_ref)
from repro.sparse import BSR, CSR, ELL, poisson_2d, random_fixed_nnz


# ---------------------------------------------------------------------------
# BSR SpMV / SpMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bm,bn,nv", [(8, 8, 1), (8, 16, 4), (16, 8, 8),
                                      (32, 32, 16), (8, 128, 128)])
def test_bsr_kernel_vs_ref_shapes(bm, bn, nv):
    rng = np.random.default_rng(bm * 1000 + bn * 10 + nv)
    nbr, nbc, kmax = 3, 4, 3
    cols = rng.integers(-1, nbc, size=(nbr, kmax)).astype(np.int32)
    blocks = rng.standard_normal((nbr, kmax, bm, bn)).astype(np.float32)
    blocks[cols < 0] = 0.0
    x = rng.standard_normal((nbc, bn, nv)).astype(np.float32)
    got = bsr_spmm_padded(jnp.asarray(cols), jnp.asarray(blocks),
                          jnp.asarray(x), interpret=True)
    want = bsr_spmm_padded_ref(jnp.asarray(cols), jnp.asarray(blocks),
                               jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_bsr_spmv_matches_csr_matvec(dtype):
    a = poisson_2d(12)
    bsr = BSR.from_csr(a, bm=8, bn=8)
    rng = np.random.default_rng(0)
    v = rng.standard_normal(a.shape[1]).astype(dtype)
    vpad = np.zeros(bsr.shape[1])
    vpad[: v.size] = v
    got = np.asarray(bsr_spmv(bsr, vpad, interpret=True))[: a.shape[0]]
    want = a.matvec(v.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # and the jnp oracle agrees
    np.testing.assert_allclose(np.asarray(bsr_spmv_ref(bsr, vpad))[: a.shape[0]],
                               want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("bm,bn,nv,nv_block", [
    (8, 8, 1, 128),     # single RHS, no tiling
    (8, 16, 8, 4),      # nv tiled into 2 blocks
    (16, 8, 128, 64),   # wide multi-RHS, 2 nv tiles
    (8, 128, 12, 8),    # nv not a multiple of nv_block (pad + slice)
])
def test_fused_bsr_kernel_vs_ref(bm, bn, nv, nv_block):
    """The fused (nv-tiled) kernel against its gather+einsum oracle."""
    rng = np.random.default_rng(bm * 1000 + bn * 10 + nv + nv_block)
    nbr, nbc, ktot = 3, 5, 4
    cols = rng.integers(-1, nbc, size=(nbr, ktot)).astype(np.int32)
    blocks = rng.standard_normal((nbr, ktot, bm, bn)).astype(np.float32)
    blocks[cols < 0] = 0.0
    x = rng.standard_normal((nbc, bn, nv)).astype(np.float32)
    got = fused_bsr_spmm(jnp.asarray(cols), jnp.asarray(blocks),
                         jnp.asarray(x), nv_block=nv_block, interpret=True)
    want = fused_bsr_spmm_ref(jnp.asarray(cols), jnp.asarray(blocks),
                              jnp.asarray(x))
    assert got.shape == (nbr, bm, nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_bsr_spmm_multi_vector():
    a = random_fixed_nnz(64, 5, seed=3)
    bsr = BSR.from_csr(a, bm=16, bn=16)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((bsr.shape[1], 8)).astype(np.float32)
    got = np.asarray(bsr_spmm(bsr, x, interpret=True))
    want = bsr.to_dense() @ x
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n_segments", [1, 2, 3])
@pytest.mark.parametrize("nv,nv_block", [(1, 128), (8, 4), (12, 8)])
def test_fused_bsr_packed_bitwise_equals_concat(n_segments, nv, nv_block):
    """The zero-copy segment-routed kernel must equal the materialised-
    concat kernel bit-for-bit (same dots, same accumulation order)."""
    rng = np.random.default_rng(n_segments * 100 + nv + nv_block)
    bm, bn, nbr, ktot = 8, 16, 4, 5
    seg_lens = [3, 2, 4][:n_segments]
    nbc = sum(seg_lens)
    cols = rng.integers(-1, nbc, size=(nbr, ktot)).astype(np.int32)
    blocks = rng.standard_normal((nbr, ktot, bm, bn)).astype(np.float32)
    blocks[cols < 0] = 0.0
    x = rng.standard_normal((nbc, bn, nv)).astype(np.float32)
    bounds = np.cumsum([0] + seg_lens)
    xs = tuple(x[bounds[i]:bounds[i + 1]] for i in range(n_segments))
    got = fused_bsr_spmm_packed(jnp.asarray(cols), jnp.asarray(blocks), xs,
                                nv_block=nv_block, interpret=True)
    want = fused_bsr_spmm(jnp.asarray(cols), jnp.asarray(blocks),
                          jnp.asarray(x), nv_block=nv_block, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# ELL SpMV / SpMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_segments", [1, 3])
@pytest.mark.parametrize("nv,seed", [
    (1, 0),      # single RHS
    (8, 0),      # multi-RHS on one sublane tile
    (12, 8),     # nv not a multiple of the sublane tile
    (128, 16),   # wide multi-RHS
])
def test_ell_packed_kernel_vs_ref(n_segments, nv, seed):
    rng = np.random.default_rng(n_segments * 10 + nv + seed)
    n_rows, kmax = 32, 5
    seg_lens = [16, 8, 24][:n_segments]
    n_x = sum(seg_lens)
    cols = rng.integers(-1, n_x, size=(n_rows, kmax)).astype(np.int32)
    vals = rng.standard_normal((n_rows, kmax)).astype(np.float32)
    vals[cols < 0] = 0.0
    bounds = np.cumsum([0] + seg_lens)
    x = rng.standard_normal((n_x, nv)).astype(np.float32)
    xs = tuple(x[bounds[i]:bounds[i + 1]] for i in range(n_segments))
    got = ell_spmm_packed(jnp.asarray(cols), jnp.asarray(vals), xs)
    want = ell_spmm_packed_ref(jnp.asarray(cols), jnp.asarray(vals), xs)
    assert got.shape == (n_rows, nv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # zero-copy multi-segment == materialised single-segment, bit-for-bit
    got_cat = ell_spmm_packed(jnp.asarray(cols), jnp.asarray(vals), (x,))
    assert np.array_equal(np.asarray(got), np.asarray(got_cat))


def test_ell_spmv_matches_csr_matvec():
    a = poisson_2d(12)
    ell = ELL.from_csr(a)
    rng = np.random.default_rng(0)
    v = rng.standard_normal(a.shape[1])
    want = a.matvec(v)
    np.testing.assert_allclose(ell.matvec(v), want, rtol=1e-6)
    got = ell_spmm_packed(jnp.asarray(ell.cols), jnp.asarray(ell.vals),
                          (v.reshape(-1, 1).astype(np.float32),))
    np.testing.assert_allclose(np.asarray(got).ravel()[: a.shape[0]], want,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ell_spmv_ref(ell, v))[: a.shape[0]],
                               want, rtol=1e-4, atol=1e-5)


def test_ell_padding_slots_are_inert():
    """col == -1 slots must not contribute even against nonzero x rows."""
    cols = np.array([[0, -1], [1, 0]], np.int32)
    vals = np.array([[2.0, 0.0], [3.0, 1.0]], np.float32)
    x = np.array([[10.0], [100.0]], np.float32)
    got = ell_spmm_packed(jnp.asarray(np.tile(cols, (4, 1))),
                          jnp.asarray(np.tile(vals, (4, 1))), (x,))
    np.testing.assert_allclose(np.asarray(got)[:2].ravel(), [20.0, 310.0])


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Hkv,g,D,S,block_s", [
    (2, 2, 4, 32, 256, 64),
    (1, 4, 1, 64, 512, 128),
    (3, 1, 8, 16, 128, 128),
])
def test_decode_attn_vs_ref(B, Hkv, g, D, S, block_s):
    rng = np.random.default_rng(B * 100 + S)
    q = rng.standard_normal((B, Hkv, g, D)).astype(np.float32)
    k = rng.standard_normal((B, Hkv, S, D)).astype(np.float32)
    v = rng.standard_normal((B, Hkv, S, D)).astype(np.float32)
    lengths = rng.integers(1, S + 1, size=(B,)).astype(np.int32)
    scale = 1.0 / np.sqrt(D)
    got = decode_attention_grouped(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), jnp.asarray(lengths),
                                   scale=scale, block_s=block_s,
                                   interpret=True)
    want = decode_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v), jnp.asarray(lengths),
                                scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_decode_attn_flat_api_and_softcap(softcap):
    rng = np.random.default_rng(7)
    B, H, Hkv, D, S = 2, 8, 2, 32, 200     # S not a block multiple -> padding
    q = rng.standard_normal((B, H, D)).astype(np.float32)
    kc = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
    vc = rng.standard_normal((B, S, Hkv, D)).astype(np.float32)
    lengths = np.array([150, 200], np.int32)
    got = decode_attention(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                           jnp.asarray(lengths), softcap=softcap,
                           block_s=64, interpret=True)
    want = decode_attention_ref(
        jnp.asarray(q.reshape(B, Hkv, H // Hkv, D)),
        jnp.asarray(np.swapaxes(kc, 1, 2)), jnp.asarray(np.swapaxes(vc, 1, 2)),
        jnp.asarray(lengths), scale=1.0 / np.sqrt(D), softcap=softcap,
    ).reshape(B, H, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_decode_attn_length_zero_tail_is_ignored():
    """Values beyond `lengths` must not leak into the output."""
    rng = np.random.default_rng(9)
    B, Hkv, g, D, S = 1, 1, 2, 16, 128
    q = rng.standard_normal((B, Hkv, g, D)).astype(np.float32)
    k = rng.standard_normal((B, Hkv, S, D)).astype(np.float32)
    v = rng.standard_normal((B, Hkv, S, D)).astype(np.float32)
    lengths = np.array([40], np.int32)
    out1 = decode_attention_grouped(jnp.asarray(q), jnp.asarray(k),
                                    jnp.asarray(v), jnp.asarray(lengths),
                                    scale=0.25, block_s=32, interpret=True)
    k2, v2 = k.copy(), v.copy()
    k2[:, :, 40:] = 1e6
    v2[:, :, 40:] = -1e6
    out2 = decode_attention_grouped(jnp.asarray(q), jnp.asarray(k2),
                                    jnp.asarray(v2), jnp.asarray(lengths),
                                    scale=0.25, block_s=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)
