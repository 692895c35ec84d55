"""hlo_analysis: trip-count-aware costing on real compiled modules +
synthetic HLO snippets for the parsers."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.hlo_analysis import (_comp_header_name, _crosses_pod,
                                     _first_group, _shape_bytes, analyze_hlo)


def test_scan_trip_count_flops():
    """XLA's cost_analysis counts a while body once; ours multiplies."""
    def g(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    c = jax.jit(g).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    r = analyze_hlo(c.as_text())
    assert r.dot_flops == 10 * 2 * 64**3
    xla = c.cost_analysis()["flops"]
    assert xla == pytest.approx(2 * 64**3, rel=0.01)  # one body only


def test_nested_scan_flops():
    def h(x):
        def outer(c, _):
            def inner(cc, _):
                return cc @ cc, None
            y, _ = jax.lax.scan(inner, c, None, length=5)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    c = jax.jit(h).lower(jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    r = analyze_hlo(c.as_text())
    assert r.dot_flops == 15 * 2 * 32**3


def test_header_parse_nested_tuple():
    assert _comp_header_name(
        "%region_0.2 (arg: (s32[], f32[256,256])) -> (s32[], f32[256,256]) {"
    ) == "region_0.2"
    assert _comp_header_name("ENTRY %main.4 (x: f32[2]) -> f32[2] {") == "main.4"
    assert _comp_header_name("not a header") is None


def test_shape_bytes_tuple_with_comments():
    s = ("(s32[], f32[2,16,2048,16000]{3,2,1,0}, /*index=5*/pred[2,16,2048]"
         "{2,1,0})")
    want = 4 + 2 * 16 * 2048 * 16000 * 4 + 2 * 16 * 2048 * 1
    assert _shape_bytes(s) == want


def test_replica_group_iota_reconstruction():
    # [4,2]<=[2,4]T(1,0): transpose(reshape(iota(8),[2,4]),[1,0]) ->
    # [[0,4],[1,5],[2,6],[3,7]] — groups PAIR ACROSS the pod boundary 4
    attrs = "replica_groups=[4,2]<=[2,4]T(1,0), use_global_device_ids=true"
    assert _first_group(attrs) == [0, 4]
    assert _crosses_pod(attrs, pod_boundary=4)
    # [2,4]<=[8]: [[0,1,2,3],[4,5,6,7]] — within-pod groups
    attrs2 = "replica_groups=[2,4]<=[8]"
    assert _first_group(attrs2) == [0, 1, 2, 3]
    assert not _crosses_pod(attrs2, pod_boundary=4)


def test_explicit_groups_and_permute_pairs():
    assert _crosses_pod("replica_groups={{0,4},{1,5}}", 4)
    assert not _crosses_pod("replica_groups={{0,1},{2,3}}", 4)
    assert _crosses_pod("source_target_pairs={{0,4},{4,0}}", 4)
    assert not _crosses_pod("source_target_pairs={{0,1},{1,0}}", 4)
