"""The sliced ELL of the hybrid backend (kernels/ell_spmv.py,
``HybridGraph.sliced``): the row order ``degree_split`` gives it, and the
invariant that lets every layout share one kernel — a row folds its slots
in 8-slot blocks in slot order, so the sliced layout and the rectangular
one give bitwise the same ``y`` and the same ``scanned`` counts."""
import numpy as np
import pytest

from repro.core import graph as G
from repro.core.hybrid import (add_identity, degree_split, hybrid_spmv,
                               hybrid_spmv_scan)
from repro.kernels import ops
from repro.kernels.ell_spmv import SEMIRINGS, SLOTS

V = 1_000                       # not a multiple of the 512-row chunk
WIDE = 7                        # the one very wide row


def _graph(seed=0, weighted=False):
    """Short rows (0-6 in-edges), rows with none, and one row of 150."""
    rng = np.random.default_rng(seed)
    deg = rng.integers(0, 7, V)
    deg[rng.random(V) < 0.2] = 0
    deg[WIDE] = 150
    dst = np.repeat(np.arange(V), deg)
    src = rng.integers(0, V, len(dst))
    w = (rng.uniform(0.5, 2.0, len(dst)).astype(np.float32)
         if weighted else None)
    return G.from_edge_list(src, dst, V, weights=w)


def _x(q, semiring, seed=1):
    """[Q, V] sources: +inf for most under a min semiring (a frontier)."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(0.0, 10.0, (q, V)).astype(np.float32)
    if semiring != "plus_times":
        x[rng.random((q, V)) < 0.7] = np.inf
    return x


def _bits(a):
    return np.asarray(a).view(np.int32)


@pytest.mark.parametrize("q", [1, 4])
@pytest.mark.parametrize("k_dense", [0, 48])
@pytest.mark.parametrize("semiring", ["plus_times", "min_plus", "min"])
def test_sliced_ell_is_bitwise_the_rectangular_ell(semiring, k_dense, q):
    g = _graph(weighted=semiring != "min")
    hg = degree_split(g, k_dense, semiring=semiring)
    col, val, widths = hg.sliced()
    assert widths.sum() < -(-hg.ell_col.shape[1] // SLOTS) * SLOTS * len(
        widths)                                   # padding really went
    x = _x(q, semiring)
    args = dict(semiring=semiring, k_dense=k_dense, interpret=True)
    rect = hybrid_spmv(hg.dense_block, hg.ell_col, hg.ell_val, x, **args)
    sliced = hybrid_spmv(hg.dense_block, col, val, x, widths=widths, **args)
    np.testing.assert_array_equal(_bits(sliced), _bits(rect))
    # ... and both are the semiring's product over the graph's edges, in
    # the split's vertex ids
    src, dst = hg.inv_perm[g.edge_sources()], hg.inv_perm[g.col]
    w = (g.weights if g.weights is not None
         else np.zeros(g.num_edges, np.float32))
    want = np.full((V, q), add_identity(semiring), np.float64)
    if semiring == "plus_times":
        np.add.at(want, dst, (x[:, src] * w).T)
    elif semiring == "min_plus":
        np.minimum.at(want, dst, (x[:, src] + w).T)
    else:
        np.minimum.at(want, dst, x[:, src].T)
    np.testing.assert_allclose(np.asarray(rect), want.T, rtol=1e-5)


@pytest.mark.parametrize("q", [1, 4])
@pytest.mark.parametrize("semiring", ["min_plus", "min"])
def test_sliced_bottomup_scan_counts_as_the_rectangular_scan(semiring, q):
    g = _graph(seed=2, weighted=semiring == "min_plus")
    hg = degree_split(g, 48, semiring=semiring)
    col, val, widths = hg.sliced()
    kreal = (hg.ell_col != V).sum(axis=1).astype(np.int32)
    x = _x(q, semiring, seed=3)
    args = dict(semiring=semiring, k_dense=48, early_exit=True,
                interpret=True)
    y_r, n_r = hybrid_spmv_scan(hg.dense_block, hg.ell_col, hg.ell_val, x,
                                kreal, **args)
    y_s, n_s = hybrid_spmv_scan(hg.dense_block, col, val, x, kreal,
                                widths=widths, **args)
    np.testing.assert_array_equal(_bits(y_s), _bits(y_r))
    np.testing.assert_array_equal(np.asarray(n_s), np.asarray(n_r))
    # per row: the slots a sequential scan examines up to its first live
    # parent, all of its real slots when it has none
    xs = np.concatenate([x, np.full((q, 1), np.inf, np.float32)], axis=1)
    hit = np.where(xs[:, hg.ell_col] < np.inf,
                   np.arange(hg.ell_col.shape[1]), 1 << 30).min(axis=2)
    per_row = np.minimum(hit + 1, kreal)
    y, scanned = ops.bottomup_scan_op(
        col, val if semiring == "min_plus" else None, xs, kreal,
        semiring=semiring, early_exit=True, widths=widths, interpret=True)
    np.testing.assert_array_equal(np.asarray(scanned), per_row)
    np.testing.assert_array_equal(np.asarray(n_s), per_row.sum(axis=1))


@pytest.mark.parametrize("k_dense", [0, 48])
def test_degree_split_orders_rows_longest_first(k_dense):
    g = _graph(seed=4)
    hg = degree_split(g, k_dense)
    assert np.array_equal(np.sort(hg.perm), np.arange(V))
    assert np.array_equal(hg.inv_perm[hg.perm], np.arange(V))
    # the dense rows keep the total-degree order
    total = g.out_degrees() + g.in_degrees()
    np.testing.assert_array_equal(
        hg.perm[:k_dense], np.argsort(-total, kind="stable")[:k_dense])
    # the rest by ELL row length, longest first
    length = (hg.ell_col != V).sum(axis=1)
    assert (np.diff(length[k_dense:]) <= 0).all()
    assert hg.perm[0] == WIDE            # the longest row, and the densest


def test_sliced_widths_round_each_chunk_up_to_eight():
    g = _graph(seed=5)
    hg = degree_split(g, 0)
    col, val, widths = hg.sliced()
    chunk = col.shape[1]
    length = (hg.ell_col != V).sum(axis=1)
    length = np.pad(length, (0, len(widths) * chunk - V))
    longest = length.reshape(len(widths), chunk).max(axis=1)
    np.testing.assert_array_equal(
        widths, np.maximum(SLOTS, -(-longest // SLOTS) * SLOTS))
    assert col.shape == val.shape == (widths.sum(), chunk)
    # padding slots are sentinels with the ⊗-identity
    pad = col == V
    assert (val[pad] == SEMIRINGS["plus_times"][2]).all()
    assert pad.size - pad.sum() == hg.sparse_edges
