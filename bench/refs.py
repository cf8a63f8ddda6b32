"""Plain NumPy references of the benchmark's algorithms.

They import nothing of the program and take only the generated CSR arrays
(``row_ptr``, ``col``), so what they answer is the yardstick for the
program's answer on the same graph:

- ``bfs_levels``: level-synchronous BFS levels from one source, ``inf``
  where unreached (float32, as the engine stores them).
- ``pagerank``: push PageRank with the engine program's semantics (damping
  0.85, ranks start at ``1/n``, each vertex sends ``rank / out_degree``
  along its out-edges, a dangling vertex sends nothing and its mass leaves
  the system, every vertex gets ``(1 - d) / n`` plus ``d`` times what it
  received), computed in ``dtype``: float64 for the reference; the
  benchmark's control runs it in bfloat16 with float32 sums.

The controls that must come out as not correct live here too.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

DAMPING = 0.85


def _neighbours(row_ptr: np.ndarray, col: np.ndarray,
                frontier: np.ndarray) -> np.ndarray:
    starts = row_ptr[frontier]
    counts = row_ptr[frontier + 1] - starts
    total = int(counts.sum())
    if not total:
        return np.empty(0, dtype=col.dtype)
    offsets = np.repeat(starts - (np.cumsum(counts) - counts), counts)
    return col[offsets + np.arange(total)]


def bfs_levels(row_ptr: np.ndarray, col: np.ndarray,
               source: int) -> np.ndarray:
    """Levels of a breadth-first search from ``source``."""
    n = len(row_ptr) - 1
    level = np.full(n, np.inf, dtype=np.float32)
    level[source] = 0.0
    frontier = np.array([source], dtype=np.int64)
    depth = 0
    while len(frontier):
        nbrs = _neighbours(row_ptr, col, frontier)
        nbrs = np.unique(nbrs[np.isinf(level[nbrs])])
        depth += 1
        level[nbrs] = depth
        frontier = nbrs.astype(np.int64)
    return level


def pagerank(row_ptr: np.ndarray, col: np.ndarray, iterations: int,
             dtype=np.float64, damping: float = DAMPING) -> np.ndarray:
    """Ranks after ``iterations`` push rounds, computed in ``dtype``.

    Products and ranks are rounded to ``dtype``; a vertex's received sum is
    accumulated in float64 for float64 and in float32 otherwise (the
    accumulator a bfloat16 path would keep), then rounded to ``dtype``.
    """
    n = len(row_ptr) - 1
    deg = np.diff(row_ptr).astype(np.float64)
    src = np.repeat(np.arange(n), np.diff(row_ptr))
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0).astype(dtype)
    delta = np.asarray((1.0 - damping) / n, dtype=dtype)
    d = np.asarray(damping, dtype=dtype)
    rank = np.full(n, 1.0 / n, dtype=dtype)
    for _ in range(iterations):
        sent = (rank * inv).astype(dtype)[src]
        if dtype == np.float64:
            acc = np.bincount(col, weights=sent, minlength=n)
        else:
            acc = np.zeros(n, dtype=np.float32)
            np.add.at(acc, col, sent.astype(np.float32))
        rank = (delta + (d * acc.astype(dtype)).astype(dtype)).astype(dtype)
    return rank.astype(np.float64)


def pagerank_bf16(row_ptr: np.ndarray, col: np.ndarray,
                  iterations: int) -> np.ndarray:
    """The PageRank control: the reference in bfloat16, float32 sums."""
    return pagerank(row_ptr, col, iterations, dtype=ml_dtypes.bfloat16)


def max_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest ``|got - want| / |want|`` over vertices (ranks are >= the
    teleport share ``(1 - d) / n``, so no denominator is near 0)."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want) / np.abs(want)))


def level_mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Vertices whose level differs (``inf`` equals ``inf``)."""
    return int(np.count_nonzero(np.asarray(got) != np.asarray(want)))


def bfs_levels_exchange_dropped(row_ptr: np.ndarray, col: np.ndarray,
                                source: int,
                                part_of: np.ndarray) -> np.ndarray:
    """The BFS control: levels with every edge between two partitions left
    out, the answer of an engine whose exchange delivers nothing."""
    n = len(row_ptr) - 1
    src = np.repeat(np.arange(n), np.diff(row_ptr))
    keep = part_of[src] == part_of[col]
    kept_ptr = np.zeros(n + 1, dtype=np.int64)
    kept_ptr[1:] = np.cumsum(np.bincount(src[keep], minlength=n))
    return bfs_levels(kept_ptr, col[keep], source)
