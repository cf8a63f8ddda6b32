"""The chip's peaks, and the least bytes a graph superstep or kernel moves.

Peaks come from ``peaks.json``, keyed by JAX's ``device_kind``; a kind that
is not in the table is an error, never a default.  The byte counts use the
graph's own vertex and edge counts, never the program's padded shapes, so
they read the same work whichever backend runs it, and each is a lower
bound on what that work moves through HBM: a share computed from them
cannot exceed 1.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"
WORD = 4          # bytes of a vertex id, a float32 value or a result


def peaks(device_kind: str, path: pathlib.Path = PEAKS_FILE) -> dict:
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path.name} "
                       f"(known: {sorted(table)}); add its published peaks "
                       f"with their source")
    return table[device_kind]


def superstep_least_bytes(num_vertices: int, num_edges: int,
                          queries: int = 1) -> int:
    """A whole-graph superstep: each stored edge's column id read once, and
    per query each vertex's state read once and written once.  (The
    per-edge source value is left out: it can come from on-chip memory
    when the state vector fits there.)"""
    return WORD * num_edges + 2 * WORD * num_vertices * queries


def ell_spmv_least_bytes(nonzeros: int, rows: int, queries: int = 1) -> int:
    """One ``ell_spmv`` call: per query, one gathered value for each
    stored, unpadded ELL non-zero read, and one result per row written."""
    return WORD * (nonzeros + rows) * queries


def share(least_bytes: float, seconds: float, bytes_per_s: float) -> float:
    """``least_bytes`` moved in ``seconds`` as a fraction of the peak."""
    if seconds <= 0:
        raise ValueError(f"seconds must be > 0, got {seconds}")
    return least_bytes / (seconds * bytes_per_s)
