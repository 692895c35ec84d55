"""Compile rehearsals of the local products for a described TPU v5e.

Nothing runs: each test lowers one local product of the shard_map
program at a deployment size (2^20 rows, the unstructured family at 25
nnz/row or the 9-point stencil) and compiles it with the TPU compiler
against a ``v5e:2x2`` topology described in a fixture, so what the chip's
compiler refuses fails here at no chip time.  The topology is described
inside a module-scoped fixture, never at import: only one process may
load the TPU library at a time (see the on-chip measurement notes).

Every ELL slot loop runs over ``slot_loop_rows`` of its rows, and the
compiled loop shows which of the compiler's two row-tile classes it
fell in (``scripts/slot_loop_tiles.py``'s ``loop_class``): each cell's
loop is held to the fast one here.
"""
import importlib.util
import pathlib
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
from repro.sparse.ell import slot_loop_rows

ROOT = pathlib.Path(__file__).resolve().parents[1]

ROWS = 2**20            # rows per device: HPCG's 104^3 local grid scale
RUN_ROWS = slot_loop_rows(ROWS)   # ... as the stacked ELL holds them
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


def _loop_class():
    path = ROOT / "scripts" / "slot_loop_tiles.py"
    spec = importlib.util.spec_from_file_location("slot_loop_tiles", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.loop_class


@pytest.mark.parametrize("nv", [1, 8])
def test_ell_forward_compiles_at_2e20_rows(chip, nv):
    shapes = [((RUN_ROWS, KMAX), jnp.int32), ((RUN_ROWS, KMAX), jnp.float32)]
    shapes += [((n, nv), jnp.float32) for n in SEGS]
    compiled = _shard_compile(
        chip, lambda c, v, *xs: ell_spmm_packed(c, v, xs, n_rows=ROWS),
        shapes)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text                 # plain XLA
    assert compiled.out_info.shape == (1, 1, ROWS, nv)  # padding dropped
    # nv 1 runs the padded rows in the fast class; nv 8 skips them and
    # keeps the unpadded loop, the faster one at that width
    assert _loop_class()(text) == ("reduce" if nv == 1 else "dynamic-slice")
    # the autotuner's admission count is what the program holds, padding
    # included (the compiler adds a few scalars and loop buffers)
    count = ell_resident_bytes(RUN_ROWS, KMAX, sum(SEGS), nv)
    held = _held_bytes(compiled)
    assert abs(count - held) <= 0.1 * held, (held, count)


# paper_random_25.spmv_x4 per chip (2^20 rows on Topology(2, 2)): v_loc
# and the received buffers the composed ELL ids index, each flattened —
# full_recv 2 x 261,519, inter_recv 2 x 262,143, final_recv 2 x 261,504
X4_ROWS = 2**18
X4_RUN_ROWS = slot_loop_rows(X4_ROWS)
X4_SEGS = (X4_ROWS, 2 * 261519, 2 * 262143, 2 * 261504)


@pytest.mark.parametrize("nv", [1, 8])
def test_composed_ell_forward_compiles_at_the_x4_cell_shapes(chip, nv):
    shapes = [((X4_RUN_ROWS, KMAX), jnp.int32),
              ((X4_RUN_ROWS, KMAX), jnp.float32)]
    shapes += [((n, nv), jnp.float32) for n in X4_SEGS]
    compiled = _shard_compile(
        chip, lambda c, v, *xs: ell_spmm_packed(c, v, xs, n_rows=X4_ROWS),
        shapes)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text                 # plain XLA
    assert len(re.findall(r" while\(", text)) == 1       # the slot loop
    assert _loop_class()(text) == ("reduce" if nv == 1 else "dynamic-slice")
    # the autotuner's count over the received domain bounds what the
    # product holds; at the cell's nv 1 it is within 10%, while at nv 8
    # the compiler reads the segments without forming their concat and
    # holds about a quarter less than counted
    count = ell_resident_bytes(X4_RUN_ROWS, KMAX, sum(X4_SEGS), nv)
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
    run = slot_loop_rows(n_out)
    shapes = [((run, KMAX_T), jnp.int32), ((run, KMAX_T), jnp.float32),
              ((ROWS, nv), jnp.float32)]
    compiled = _shard_compile(
        chip, lambda c, v, u: ell_spmm_packed(c, v, (u,), n_rows=n_out),
        shapes)
    count = ell_resident_bytes(run, KMAX_T, ROWS, nv)
    held = _held_bytes(compiled)
    assert abs(count - held) <= 0.1 * held, (held, count)


# graph500_s20.spmv on one chip: 2^20 rows split at K = 8 into 4,736,466
# chunk rows (the scale-20 graph of seed 1), stacked to 512 past a
# multiple of 1024; x = v_loc | on-node | off-node
G500_K = 8
G500_CHUNKS = slot_loop_rows(4_736_466)


@pytest.mark.parametrize("nv", [1, 8])
def test_split_ell_forward_compiles_at_the_graph500_shapes(chip, nv):
    shapes = [((G500_CHUNKS, G500_K), jnp.int32),
              ((G500_CHUNKS, G500_K), jnp.float32),
              ((G500_CHUNKS,), jnp.int32)]
    shapes += [((n, nv), jnp.float32) for n in SEGS]
    compiled = _shard_compile(
        chip, lambda c, v, r, *xs: ell_spmm_packed(c, v, xs, r, n_rows=ROWS),
        shapes)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text                 # plain XLA
    assert len(re.findall(r" while\(", text)) == 1       # the slot loop
    # the split layout's count: cols + vals over the chunk rows, their
    # working copy, the partials, chunk_row, x and the result; at nv 8
    # within 10% of what the compiler holds, while at the cell's nv 1 the
    # slot loop reads the staged arrays and the compiler makes no working
    # copy, so there the count is an upper bound that is the copy too high
    count = ell_resident_bytes(ROWS, G500_K, sum(SEGS), nv, G500_CHUNKS)
    held = _held_bytes(compiled)
    if nv == 1:
        copy = 2 * 4 * G500_K * G500_CHUNKS
        assert held <= count and abs(count - copy - held) <= 0.1 * held, \
            (held, count)
    else:
        assert abs(count - held) <= 0.1 * held, (held, count)


# each cell's slot loop at nv 1: (rows the stack holds, slots, x segments,
# output rows, split); the transposed random matrix's 3,735,140 chunk
# rows at K = 8 (seed 1) are stacked like the graph's
CELL_LOOPS = {
    "paper_random_25.spmv": (RUN_ROWS, KMAX, SEGS, ROWS, False),
    "paper_random_25.spmv_x4": (X4_RUN_ROWS, KMAX, X4_SEGS, X4_ROWS, False),
    "hpcg_27pt_104": (1_124_864, 27, (1_124_864, BN, BN), 1_124_864, False),
    "graph500_s20.spmv": (G500_CHUNKS, G500_K, SEGS, ROWS, True),
    "paper_random_25.transpose": (slot_loop_rows(3_735_140), 8, (ROWS,),
                                  sum(SEGS), True),
    # the 2^20 rows unpadded, as before the rule covered every layout:
    # the slower class, for contrast
    "unpadded_2e20": (ROWS, KMAX, SEGS, ROWS, False),
}


@pytest.mark.parametrize("cell", sorted(CELL_LOOPS))
def test_every_cell_slot_loop_compiles_to_the_fast_tile_class(chip, cell):
    rows, k, segs, n_out, split = CELL_LOOPS[cell]
    shapes = [((rows, k), jnp.int32), ((rows, k), jnp.float32)]
    if split:
        shapes.append(((rows,), jnp.int32))

        def product(c, v, r, *xs):
            return ell_spmm_packed(c, v, xs, r, n_rows=n_out)
    else:
        def product(c, v, *xs):
            return ell_spmm_packed(c, v, xs, n_rows=n_out)
    shapes += [((n, 1), jnp.float32) for n in segs]
    text = _shard_compile(chip, product, shapes).as_text()
    want = "dynamic-slice" if cell == "unpadded_2e20" else "reduce"
    assert _loop_class()(text) == want, (cell, rows % 1024)


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
