"""Fused superstep path == reference path, for every algorithm.

The fused Pallas kernel (kernels/fused_superstep.py) must be a pure
performance substitution: ``min``-combine algorithms (BFS, SSSP, CC) are
compared *exactly* — a min over any reduction order is order-insensitive —
while ``sum``-combine algorithms (PageRank, BC) are compared to tight
tolerances, since reassociating an f32 sum legitimately moves the last ulp.
Also covers the span-overflow fallback (adversarial gappy destinations) and
the block-metadata invariants it keys off.
"""
import numpy as np
import pytest

from repro.core import graph as G
from repro.core import partition as PT
from repro.core.bsp import BSPEngine
from repro.algorithms import (bfs, pagerank, sssp, connected_components,
                              betweenness_centrality)
from repro.algorithms.cc import symmetrize

INTERP = dict(interpret=True)
SCALE = 10
PARTS = 4
BLOCK_E = 256  # small blocks → many grid cells, still 128-aligned


@pytest.fixture(scope="module", params=PT.STRATEGIES)
def engines(request):
    """(reference, fused) engine pair per partitioning strategy."""
    g = G.rmat(SCALE, 4, seed=13).with_uniform_weights(seed=1)
    pg = PT.partition(g, PARTS, request.param, include_reverse=True)
    return (BSPEngine(pg, **INTERP),
            BSPEngine(pg, fused=True, block_e=BLOCK_E, **INTERP))


@pytest.fixture(scope="module", params=PT.STRATEGIES)
def cc_engines(request):
    g = symmetrize(G.rmat(SCALE, 4, seed=13))
    pg = PT.partition(g, PARTS, request.param)
    return (BSPEngine(pg, **INTERP),
            BSPEngine(pg, fused=True, block_e=BLOCK_E, **INTERP))


def test_bfs_parity(engines):
    ref, fus = engines
    lr, sr = bfs(ref, 0)
    lf, sf = bfs(fus, 0)
    np.testing.assert_array_equal(lr, lf)   # min combine: exact
    assert sr == sf


def test_sssp_parity(engines):
    ref, fus = engines
    dr, _ = sssp(ref, 0)
    df, _ = sssp(fus, 0)
    np.testing.assert_array_equal(dr, df)   # min combine: exact


def test_pagerank_parity(engines):
    ref, fus = engines
    pr = pagerank(ref, num_iterations=10)
    pf = pagerank(fus, num_iterations=10)
    np.testing.assert_allclose(pr, pf, rtol=1e-6, atol=1e-9)


def test_bc_parity(engines):
    ref, fus = engines
    br, sr = betweenness_centrality(ref, 0)
    bf, sf = betweenness_centrality(fus, 0)
    assert sr == sf
    np.testing.assert_allclose(br, bf, rtol=1e-5, atol=1e-5)


def test_cc_parity(cc_engines):
    ref, fus = cc_engines
    cr, _ = connected_components(ref)
    cf, _ = connected_components(fus)
    np.testing.assert_array_equal(cr, cf)   # min combine: exact


# ---------------------------------------------------------------------------
# span-overflow fallback
# ---------------------------------------------------------------------------

def _gappy_graph(n=512, hub_edges=64, seed=5):
    """A hub fanning out to destinations spread across the id space: one
    sorted edge block then spans ~the whole segment range."""
    rng = np.random.default_rng(seed)
    src = np.full(hub_edges, 0, dtype=np.int64)
    dst = np.sort(rng.choice(np.arange(1, n), size=hub_edges, replace=False))
    extra_src = rng.integers(0, n, size=n)
    extra_dst = rng.integers(0, n, size=n)
    return G.from_edge_list(np.concatenate([src, extra_src]),
                            np.concatenate([dst, extra_dst]), n)


def test_span_overflow_triggers_fallback():
    g = _gappy_graph()
    pg = PT.partition(g, 2, PT.RAND)
    blk = PT.build_block_metadata(pg.fwd, block_e=128)
    assert not blk.fused_ok(max_span=8)     # adversarial spans exceed bound
    eng = BSPEngine(pg, fused=True, block_e=128, max_span=8, **INTERP)
    ref = BSPEngine(pg, **INTERP)
    lr, _ = bfs(ref, 0)
    lf, _ = bfs(eng, 0)
    np.testing.assert_array_equal(lr, lf)   # fallback is exact


def test_span_limit_respects_vmem_budget():
    from repro.kernels.ops import fused_span_limit
    # Caller bound wins when blocks are small …
    assert fused_span_limit(128, "sum", max_span=4096) == 4096
    # … the VMEM budget wins when blocks are large (8 MiB / 4B / block_e) …
    assert fused_span_limit(1024, "sum", max_span=4096) == 2048
    # … and min-combine's two [block_e, span] arrays halve the limit.
    assert fused_span_limit(1024, "min", max_span=4096) == 1024
    assert fused_span_limit(256, "sum", max_span=4096) == 4096


def test_vmem_budget_fallback_parity():
    """span fits max_span but busts the [block_e, span] VMEM budget →
    byte-gated fallback, identical results.  Spans count distinct
    segments per block (≤ block_e), so only a wide block busts it."""
    g = G.rmat(SCALE, 4, seed=13)
    pg = PT.partition(g, PARTS, PT.HIGH)
    blk = PT.build_block_metadata(pg.fwd, block_e=4096)
    from repro.kernels.ops import KERNEL_PATHS, fused_span_limit
    if blk.span <= fused_span_limit(4096, "min"):
        pytest.skip("graph too benign to bust the budget")
    ref = BSPEngine(pg, **INTERP)
    fus = BSPEngine(pg, fused=True, block_e=4096, **INTERP)
    before = KERNEL_PATHS[("fused_superstep", "xla")]
    lr, _ = bfs(ref, 0)
    lf, _ = bfs(fus, 0)
    np.testing.assert_array_equal(lr, lf)
    assert KERNEL_PATHS[("fused_superstep", "xla")] > before


def test_fallback_engine_matches_for_weighted_min():
    g = _gappy_graph().with_uniform_weights(seed=2)
    pg = PT.partition(g, 2, PT.RAND)
    ref = BSPEngine(pg, **INTERP)
    fb = BSPEngine(pg, fused=True, block_e=128, max_span=8, **INTERP)
    dr, _ = sssp(ref, 0)
    df, _ = sssp(fb, 0)
    np.testing.assert_array_equal(dr, df)


# ---------------------------------------------------------------------------
# block metadata invariants
# ---------------------------------------------------------------------------

def test_block_metadata_invariants():
    g = G.rmat(9, 8, seed=11)
    pg = PT.partition(g, PARTS, PT.HIGH)
    blk = PT.build_block_metadata(pg.fwd, block_e=256)
    assert blk.e_pad % blk.block_e == 0
    assert blk.span % 128 == 0 and blk.span >= blk.span_req
    # each block's id table, indexed by the local ranks, reconstructs
    # dst_ext for every real edge
    nb = blk.num_blocks
    ids = np.take_along_axis(
        blk.ids, blk.local.reshape(pg.num_parts, nb, blk.block_e),
        axis=2).reshape(pg.num_parts, -1)
    e_max = pg.fwd.e_max
    real = blk.mask[:, :e_max].astype(bool)
    np.testing.assert_array_equal(ids[:, :e_max][real],
                                  pg.fwd.dst_ext[real])
    # local offsets always inside the compiled span
    assert blk.local.min() >= 0 and blk.local.max() < blk.span
    # per-partition histogram accounts for every block
    hist = blk.span_histogram()
    assert hist.shape[0] == pg.num_parts and int(hist.sum()) == \
        pg.num_parts * nb


def test_padding_edges_never_widen_span():
    """A partition with very few edges still gets span == one lane tile."""
    src = np.array([0, 1], dtype=np.int64)
    dst = np.array([1, 0], dtype=np.int64)
    g = G.from_edge_list(src, dst, 4)
    pg = PT.partition(g, 2, PT.RAND)
    blk = PT.build_block_metadata(pg.fwd, block_e=128)
    assert blk.span == 128
    assert blk.span_req <= 2
