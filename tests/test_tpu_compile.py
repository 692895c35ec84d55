"""Compile rehearsals of the local products for a described TPU v5e.

Nothing runs: each test lowers one local product of the shard_map
program at a deployment size (2^20 rows, the unstructured family at 25
nnz/row or the 9-point stencil) and compiles it with the TPU compiler
against a ``v5e:2x2`` topology described in a fixture, so what the chip's
compiler refuses fails here at no chip time.  The topology is described
inside a module-scoped fixture, never at import: only one process may
load the TPU library at a time (see the on-chip measurement notes).
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.ops import segment_sum
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.cost_model import ell_resident_bytes
from repro.kernels.bsr_spmv.fused import fused_bsr_spmm_packed
from repro.kernels.ell_spmv import ell_spmm_packed

ROWS = 2**20            # rows per device: HPCG's 104^3 local grid scale
KMAX = 25               # random_fixed_nnz(2**20, 25): forward ELL width
KMAX_T = 55             # ... and its transpose (longest column)
BN = 128                # packed segment alignment (block lane width)
SEGS = (ROWS, BN, BN)   # v_loc | on-node | off-node on one chip


@pytest.fixture(scope="module")
def chip():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("node", "proc"))
    yield NamedSharding(mesh, P("node", "proc"))
    jax.config.update("jax_enable_compilation_cache", was)


def _shard_compile(sharding, per_device, shapes):
    """Compile ``per_device`` as the executor runs it: one [1, 1, ...]
    shard per argument of a shard_map over the described chip."""
    mesh = sharding.mesh

    def squeezed(*args):
        out = per_device(*[a.reshape(a.shape[2:]) for a in args])
        return out.reshape((1, 1) + out.shape)

    fn = jax.jit(jax.shard_map(squeezed, mesh=mesh,
                               in_specs=(P("node", "proc"),) * len(shapes),
                               out_specs=P("node", "proc"), check_vma=False))
    args = [jax.ShapeDtypeStruct((1, 1) + s, d, sharding=sharding)
            for s, d in shapes]
    return fn.lower(*args).compile()


def _held_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)


@pytest.mark.parametrize("nv", [1, 8])
def test_ell_forward_compiles_at_2e20_rows(chip, nv):
    shapes = [((ROWS, KMAX), jnp.int32), ((ROWS, KMAX), jnp.float32)]
    shapes += [((n, nv), jnp.float32) for n in SEGS]
    compiled = _shard_compile(
        chip, lambda c, v, *xs: ell_spmm_packed(c, v, xs), shapes)
    assert "tpu_custom_call" not in compiled.as_text()   # plain XLA
    # the autotuner's admission count is what the program holds, padding
    # included (the compiler adds a few scalars and loop buffers)
    count = ell_resident_bytes(ROWS, KMAX, sum(SEGS), nv)
    held = _held_bytes(compiled)
    assert abs(count - held) <= 0.1 * held, (held, count)


# paper_random_25.spmv_x4 per chip (2^20 rows on Topology(2, 2)): v_loc
# and the received buffers the composed ELL ids index, each flattened —
# full_recv 2 x 261,519, inter_recv 2 x 262,143, final_recv 2 x 261,504
X4_ROWS = 2**18
X4_SEGS = (X4_ROWS, 2 * 261519, 2 * 262143, 2 * 261504)


@pytest.mark.parametrize("nv", [1, 8])
def test_composed_ell_forward_compiles_at_the_x4_cell_shapes(chip, nv):
    shapes = [((X4_ROWS, KMAX), jnp.int32), ((X4_ROWS, KMAX), jnp.float32)]
    shapes += [((n, nv), jnp.float32) for n in X4_SEGS]
    compiled = _shard_compile(
        chip, lambda c, v, *xs: ell_spmm_packed(c, v, xs), shapes)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text                 # plain XLA
    assert len(re.findall(r" while\(", text)) == 1       # the slot loop
    # the autotuner's count over the received domain bounds what the
    # product holds; at the cell's nv 1 it is within 10%, while at nv 8
    # the compiler reads the segments without forming their concat and
    # holds about a quarter less than counted
    count = ell_resident_bytes(X4_ROWS, KMAX, sum(X4_SEGS), nv)
    ma = compiled.memory_analysis()
    held = _held_bytes(compiled)
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes <= 1.1 * count
    if nv == 1:
        assert abs(count - held) <= 0.1 * held, (held, count)
    else:
        assert held <= count, (held, count)


@pytest.mark.parametrize("nv", [1, 8])
def test_ell_transpose_compiles_at_2e20_rows(chip, nv):
    n_out = sum(SEGS)   # the packed contribution domain
    shapes = [((n_out, KMAX_T), jnp.int32), ((n_out, KMAX_T), jnp.float32),
              ((ROWS, nv), jnp.float32)]
    compiled = _shard_compile(
        chip, lambda c, v, u: ell_spmm_packed(c, v, (u,)), shapes)
    count = ell_resident_bytes(n_out, KMAX_T, ROWS, nv)
    held = _held_bytes(compiled)
    assert abs(count - held) <= 0.1 * held, (held, count)


@pytest.mark.parametrize("nv", [1, 8])
def test_fused_bsr_packed_compiles_to_a_kernel(chip, nv):
    # the 9-point stencil on a 1024^2 grid: 131072 (8, 128) block rows,
    # at most 6 tiles each (two per stencil band)
    bm, ktot = 8, 6
    n_brows = ROWS // bm
    shapes = [((n_brows, ktot), jnp.int32),
              ((n_brows, ktot, bm, BN), jnp.float32)]
    shapes += [((n // BN, BN, nv), jnp.float32) for n in SEGS]

    def per_device(cols, blocks, *xs):
        return fused_bsr_spmm_packed(cols, blocks, xs, interpret=False)

    compiled = _shard_compile(chip, per_device, shapes)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("nv", [1, 8])
def test_coo_local_product_compiles_at_2e20_rows(chip, nv):
    nnz = ROWS * KMAX
    shapes = [((nnz,), jnp.int32), ((nnz,), jnp.int32),
              ((nnz,), jnp.float32), ((sum(SEGS), nv), jnp.float32)]

    def per_device(rows, cols, vals, x):
        return segment_sum(vals[:, None] * x[cols], rows, num_segments=ROWS)

    compiled = _shard_compile(chip, per_device, shapes)
    assert "tpu_custom_call" not in compiled.as_text()
