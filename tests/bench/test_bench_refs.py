"""The benchmark's references against the engine at a tiny size, the TEPS
edge-count rule on a hand-built graph, and the controls, which must come
out as not correct."""
import json

import numpy as np
import pytest

import bench_helpers  # noqa: F401  (puts bench on the path)
from bench import gen, harness, refs
from repro.algorithms.bfs import bfs
from repro.algorithms.pagerank import pagerank
from repro.core import partition as PT
from repro.core.bsp import BSPEngine
from repro.core.graph import CSRGraph

PAGERANK = json.loads((harness.ROOT / "bench" / "traffic" /
                       "pagerank.json").read_text())
BFS_MIX = {"algorithm": "bfs", "teps_edges": "reached"}
BFS = harness.load_algorithm(harness.ROOT, BFS_MIX)
PR = harness.load_algorithm(harness.ROOT, PAGERANK)
SPECS = {
    "kron": dict(generator="kronecker", structure_seed=3, scale=9,
                 edge_factor=16, a=0.57, b=0.19, c=0.19, undirected=True),
    "uniform": dict(generator="uniform", structure_seed=3, scale=9,
                    edge_factor=16, undirected=False),
}


@pytest.fixture(scope="module", params=["kron", "uniform"])
def graph(request):
    gg = gen.generate(SPECS[request.param], 3)
    pg = PT.partition(CSRGraph(gg.row_ptr, gg.col), 4, PT.HIGH)
    return gg, pg


def test_bfs_reference_equals_engine(graph):
    gg, pg = graph
    engine = BSPEngine(pg, direction_switch=False)
    for key in BFS.search_keys(gg, 3, seed=5):
        levels, _ = bfs(engine, int(key))
        want = refs.bfs_levels(gg.row_ptr, gg.col, int(key))
        assert refs.level_mismatches(levels, want) == 0


def test_pagerank_reference_equals_engine(graph):
    gg, pg = graph
    got = pagerank(BSPEngine(pg), 10)
    want = refs.pagerank(gg.row_ptr, gg.col, 10)
    assert refs.max_rel_err(got, want) < 1e-5
    # dangling vertices send nothing, so mass leaves the system
    if np.any(gg.out_degrees() == 0):
        assert want.sum() < 1.0


def test_bfs_levels_by_hand():
    # 0 -> 1 -> 2 -> 3, 0 -> 2, 4 unreachable
    row_ptr = np.array([0, 2, 3, 4, 4, 4])
    col = np.array([1, 2, 2, 3], dtype=np.int32)
    assert refs.bfs_levels(row_ptr, col, 0).tolist() == [
        0, 1, 1, 2, np.inf]


def test_pagerank_by_hand():
    # 0 -> 1, 1 -> 0, 1 -> 2; vertex 2 dangles.  One round from 1/3 each.
    row_ptr = np.array([0, 1, 3, 3])
    col = np.array([1, 0, 2], dtype=np.int32)
    d, n = 0.85, 3
    got = refs.pagerank(row_ptr, col, 1)
    want = [(1 - d) / n + d * (1 / 3) / 2,
            (1 - d) / n + d * (1 / 3),
            (1 - d) / n + d * (1 / 3) / 2]
    assert got == pytest.approx(want, rel=1e-12)


def test_teps_edge_count_rule():
    # Undirected tuples (0,1), (1,2), (2,2), (0,1) again, (3,4), each
    # stored both ways.  From 0 the search reaches {0, 1, 2}: tuples within
    # it are (0,1) twice, (1,2) and the self-loop (2,2) -> 4.
    tuples = [(0, 1), (1, 2), (2, 2), (0, 1), (3, 4)]
    src = [u for u, v in tuples] + [v for u, v in tuples]
    dst = [v for u, v in tuples] + [u for u, v in tuples]
    order = np.lexsort((dst, src))
    src, dst = np.asarray(src)[order], np.asarray(dst)[order]
    row_ptr = np.concatenate([[0], np.cumsum(np.bincount(src,
                                                         minlength=5))])
    g = gen.GeneratedGraph(row_ptr=row_ptr, col=dst.astype(np.int32),
                           num_tuples=len(tuples), undirected=True)
    levels = refs.bfs_levels(g.row_ptr, g.col, 0)[None]
    assert BFS.traversed_edges(g, BFS_MIX, levels) == 4
    # A unit of two searches counts both: from 3 the tuple (3,4).
    both = np.stack([levels[0], refs.bfs_levels(g.row_ptr, g.col, 3)])
    assert BFS.traversed_edges(g, BFS_MIX, both) == 5
    # Directed: the stored out-edges of the reached vertices.
    directed = gen.GeneratedGraph(row_ptr=g.row_ptr, col=g.col,
                                  num_tuples=10, undirected=False)
    assert BFS.traversed_edges(directed, BFS_MIX, levels) == 8
    assert PR.traversed_edges(directed, {"iterations": 10}, levels) == 100
    with pytest.raises(harness.BenchError, match="counts edges by"):
        harness.load_algorithm(harness.ROOT, dict(BFS_MIX, teps_edges="x"))
    with pytest.raises(harness.BenchError, match="has no module"):
        harness.load_algorithm(harness.ROOT, dict(BFS_MIX, algorithm="x"))


def test_pagerank_control_fails_the_limit(graph):
    gg, _ = graph
    want = refs.pagerank(gg.row_ptr, gg.col, 10)
    control = refs.pagerank_bf16(gg.row_ptr, gg.col, 10)
    limit = PAGERANK["limits"]["rank_max_rel_err"]
    assert refs.max_rel_err(control, want) > 3 * limit


def test_bfs_control_fails_the_limit(graph):
    gg, pg = graph
    key = int(BFS.search_keys(gg, 1, seed=5)[0])
    want = refs.bfs_levels(gg.row_ptr, gg.col, key)
    control = refs.bfs_levels_exchange_dropped(gg.row_ptr, gg.col, key,
                                               pg.assignment.part_of)
    assert refs.level_mismatches(control, want) > 0


def test_max_rel_err_of_non_finite_ranks_is_inf():
    assert refs.max_rel_err(np.array([np.nan, 1.0]),
                            np.array([1.0, 1.0])) == float("inf")
