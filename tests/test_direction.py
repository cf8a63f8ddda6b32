"""Direction-optimized traversal: parity, per-query votes, zero-retrace
switching, mutation round-trips, and the fitted crossover.

Direction (top-down push vs bottom-up pull) is a pure performance choice
for min combines — both directions reduce the same value multiset per
destination, so every cell of the auto/push/pull × backend × device-count
matrix must agree *bitwise* (docs/traversal.md).  The multi-device matrix
runs in subprocesses (``repro.launch.direction_selftest``) so forced host
device counts never leak into this process's jax runtime.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import graph as G
from repro.core import partition as PT
from repro.core import perf_model
from repro.core.bsp import BSPEngine

SRC = str(Path(__file__).resolve().parents[1] / "src")
INTERP = dict(interpret=True)


def _run(ndev: int, module: str, *args, timeout=900):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                            ""))
    return subprocess.run([sys.executable, "-m", module, *args],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


@pytest.mark.parametrize("ndev", [1, 2, 4])
def test_direction_parity_matrix(ndev):
    """bfs/sssp/cc × {reference, fused, hybrid} × {push, pull, auto} vs
    the single-device push baseline — bitwise, per device count."""
    r = _run(ndev, "repro.launch.direction_selftest", "--parts", "4")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DIRECTION SELFTEST OK" in r.stdout


def _star_and_chain(spokes=29, chain=10):
    """Hub 0 → spokes (one dense-frontier superstep) plus a disjoint
    directed chain (a frontier of exactly one vertex per superstep)."""
    n = 1 + spokes + chain
    hub_src = np.zeros(spokes, np.int64)
    hub_dst = np.arange(1, 1 + spokes)
    c0 = 1 + spokes
    ch_src = np.arange(c0, n - 1)
    ch_dst = np.arange(c0 + 1, n)
    g = G.from_edge_list(np.concatenate([hub_src, ch_src]),
                         np.concatenate([hub_dst, ch_dst]), n)
    return g, c0


def test_per_query_direction_vote():
    """Satellite: the frontier-density vote is per *query*, not a batch
    aggregate — in one batch, the hub query (dense frontier superstep)
    switches direction while the chain query (always-sparse frontier)
    never leaves push."""
    from repro.algorithms.bfs import bfs_batched, bfs_reference

    g, c0 = _star_and_chain()
    pg = PT.partition(g, 2, PT.RAND)
    eng = BSPEngine(pg, pull_threshold=0.3, **INTERP)
    lv, _ = bfs_batched(eng, [0, c0])
    ref = np.stack([bfs_reference(g, s) for s in (0, c0)])
    np.testing.assert_array_equal(lv, ref)
    st = eng.last_direction_stats
    assert st is not None
    # hub: density 1/n → push, spokes/n → pull, 0 → push: ≥ 2 switches
    assert st["switches"][0] >= 1
    # chain: one-vertex frontier forever stays under the 0.3 crossover
    assert st["switches"][1] == 0
    assert (st["direction"][1] == 0).all()
    assert (st["edges_examined"] > 0).all()


def test_switching_never_retraces():
    """A direction flip is `lax.cond` data inside one compiled while_loop:
    a warm same-Q batch that switches adds zero jit cache entries."""
    from repro.algorithms.bfs import bfs_batched

    g, c0 = _star_and_chain()
    pg = PT.partition(g, 2, PT.RAND)
    eng = BSPEngine(pg, pull_threshold=0.3, **INTERP)
    bfs_batched(eng, [0, c0])                       # compiles
    assert eng.last_direction_stats["switches"][0] >= 1
    before = BSPEngine._run_batched._cache_size()
    bfs_batched(eng, [0, c0 + 1])                   # same Q, still switches
    assert eng.last_direction_stats["switches"][0] >= 1
    assert BSPEngine._run_batched._cache_size() == before


@pytest.mark.parametrize("direction", ["auto", "push", "pull"])
def test_dynamic_mutation_roundtrip_both_layouts(direction):
    """Mutate → rerun parity through the transposed/push arenas: inserts
    and deletes reach the pull ELL *and* the push arena through the one
    compiled scatter, in every direction mode."""
    from repro.algorithms.bfs import bfs_batched, bfs_reference
    from repro.core.dynamic import DynamicGraph

    g = G.rmat(7, 6, seed=5)
    rng = np.random.default_rng(0)
    dg = DynamicGraph(g, 2, PT.RAND, mutation_capacity=64)
    eng = BSPEngine(dg, backend="hybrid", direction=direction, **INTERP)
    lv0, _ = bfs_batched(eng, [0, 3])
    np.testing.assert_array_equal(
        lv0, np.stack([bfs_reference(g, s) for s in (0, 3)]))

    ins_s = rng.integers(0, g.num_vertices, 30)
    ins_d = rng.integers(0, g.num_vertices, 30)
    ei = rng.choice(g.col.size, 10, replace=False)
    del_s = np.repeat(np.arange(g.num_vertices), np.diff(g.row_ptr))[ei]
    batch = G.MutationBatch(
        src=np.concatenate([ins_s, del_s]),
        dst=np.concatenate([ins_d, g.col[ei]]),
        insert=np.concatenate([np.ones(30, bool), np.zeros(10, bool)]))
    dg.apply_mutations(batch)

    lv1, _ = bfs_batched(eng, [0, 3])
    g2 = dg.mutated_csr()
    np.testing.assert_array_equal(
        lv1, np.stack([bfs_reference(g2, s) for s in (0, 3)]))
    assert (eng.last_direction_stats["edges_examined"] > 0).all()


def test_sum_combines_are_ineligible():
    """Direction optimization is min-semiring-only: a partial bottom-up
    scan would double-count a sum.  PageRank runs untouched and reports
    no direction stats."""
    from repro.algorithms.pagerank import (initial_state,
                                           make_pagerank_program)
    from repro.core.bsp import batch_state

    g = G.rmat(7, 4, seed=3)
    pg = PT.partition(g, 2, PT.RAND)
    eng = BSPEngine(pg, direction="pull", **INTERP)
    program = make_pagerank_program(g.num_vertices)
    out = eng.execute(program, batch_state(initial_state(pg)),
                      num_steps=3)
    assert eng.last_direction_stats is None
    assert np.isfinite(np.asarray(out["rank"])).all()


def test_pull_threshold_monotone_in_degree():
    """The fitted crossover must not *rise* with average degree: denser
    graphs amortize a bottom-up scan sooner, never later."""
    degs = [1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0]
    for backend in ("hybrid", "fused", "reference"):
        thrs = [perf_model.fit_pull_threshold(d, 32, backend=backend)
                for d in degs]
        assert all(a >= b for a, b in zip(thrs, thrs[1:])), (backend, thrs)
        assert all(1e-4 <= t <= 0.9 for t in thrs), (backend, thrs)
    shard = perf_model.fit_shard_pull_thresholds(
        [2.0, 8.0, 32.0], [16, 16, 16], backend="fused")
    assert shard.shape == (3,) and shard.dtype == np.float32
    assert shard[0] >= shard[1] >= shard[2]


def test_bottomup_early_exit_exact_for_uniform_frontier():
    """With a uniform frontier the first live parent *is* the row min, so
    early exit returns bitwise-identical values while scanning no more
    slots than the full pass."""
    from repro.kernels.ops import bottomup_scan_op

    rng = np.random.default_rng(7)
    v, kmax, nx = 24, 6, 32
    col = rng.integers(0, nx, (v, kmax)).astype(np.int32)
    col[rng.random((v, kmax)) < 0.3] = nx            # sentinel slots
    kreal = (col != nx).sum(axis=1).astype(np.int32)
    x = np.full((2, nx + 1), np.inf, np.float32)
    frontier = rng.random((2, nx)) < 0.4
    x[:, :nx][frontier] = 5.0                        # uniform message
    y0, s0 = bottomup_scan_op(col, None, x, kreal, semiring="min",
                              early_exit=False, interpret=True)
    y1, s1 = bottomup_scan_op(col, None, x, kreal, semiring="min",
                              early_exit=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    assert (np.asarray(s1) <= np.asarray(s0)).all()
    assert np.asarray(s1).sum() < np.asarray(s0).sum()


@pytest.mark.parametrize("semiring", ["min", "min_plus"])
def test_bottomup_slot_tiling_matches_one_block(semiring):
    """Rows folded over several 8-slot grid steps keep the row min bitwise
    and the early-exit counts equal between a wide batch and one query
    scanned alone."""
    from repro.kernels import ell_spmv as ell
    from repro.kernels.ops import bottomup_scan_op

    rng = np.random.default_rng(11)
    v, kmax, nx, q = 40, 72, 64, 32
    assert kmax > 8 * ell.SLOTS
    col = rng.integers(0, nx, (v, kmax)).astype(np.int32)
    col[rng.random((v, kmax)) < 0.3] = nx
    kreal = (col != nx).sum(axis=1).astype(np.int32)
    val = (rng.uniform(0, 1, (v, kmax)).astype(np.float32)
           if semiring == "min_plus" else None)
    x = np.full((q, nx + 1), np.inf, np.float32)
    x[:, :nx][rng.random((q, nx)) < 0.1] = 3.0
    y, s = bottomup_scan_op(col, val, x, kreal, semiring=semiring,
                            early_exit=True, interpret=True)
    for i in (0, q - 1):            # Q=1 fits one slot block
        y1, s1 = bottomup_scan_op(col, val, x[i:i + 1], kreal,
                                  semiring=semiring, early_exit=True,
                                  interpret=True)
        np.testing.assert_array_equal(np.asarray(y)[i], np.asarray(y1)[0])
        np.testing.assert_array_equal(np.asarray(s)[i], np.asarray(s1)[0])


def test_uniform_frontier_flags():
    """BFS declares the uniform frontier (early-exit licence); CC and
    SSSP frontiers carry distinct values and must not."""
    from repro.algorithms.bfs import BFS_PROGRAM, BFS_RELAX_PROGRAM
    from repro.algorithms.cc import CC_PROGRAM
    from repro.algorithms.sssp import SSSP_PROGRAM

    assert BFS_PROGRAM.edge_msg.frontier_uniform
    assert not BFS_RELAX_PROGRAM.edge_msg.frontier_uniform
    assert not CC_PROGRAM.edge_msg.frontier_uniform
    assert not SSSP_PROGRAM.edge_msg.frontier_uniform
