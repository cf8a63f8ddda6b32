"""Breadth-first search traffic: Graph500 kernel 2.

A traffic mix with ``"algorithm": "bfs"`` gives

- ``keys``: how many search keys to draw, distinct vertices of degree
  >= 1, which the units cycle through.  The set is drawn once per graph
  structure, so every seed searches from the same vertices, renamed and
  in another order: the same work whatever the seed;
- ``queries``: searches per unit, one engine call through the program's
  ``repro.algorithms.bfs.bfs_batched`` (``bfs`` is its one-key case);
- ``check_keys``: how many keys, drawn from the seed after the window,
  have every unit that searched them compared with ``bench/refs.py``;
- ``teps_edges``: ``reached``, Graph500's edge count;
- ``limits``: ``level_mismatches``, vertices whose level differs.

The control puts the reference in the program's place with every edge
between two of the program's partitions left out: the answer of an
engine whose exchange delivers nothing.
"""
from __future__ import annotations

import collections
import importlib
from typing import Dict, List

import numpy as np

from bench import refs

TEPS_RULE = "reached"


def search_keys(gg, count: int, seed: int) -> np.ndarray:
    """``count`` distinct vertices of degree >= 1 of ``gg``'s structure,
    drawn from its structure seed, in the run's ids and in an order drawn
    from ``seed``."""
    live = np.flatnonzero(gg.out_degrees()[gg.ids] > 0)
    chosen = np.random.default_rng([gg.structure_seed, 1]).choice(
        live, size=min(count, len(live)), replace=False)
    keys = gg.ids[np.sort(chosen)]
    np.random.default_rng([seed, 1]).shuffle(keys)
    return keys


def _control(engine, gg, batch):
    part_of = engine.pg.assignment.part_of
    levels = np.stack([refs.bfs_levels_exchange_dropped(
        gg.row_ptr, gg.col, key, part_of) for key in batch])
    return levels, int(np.max(levels[np.isfinite(levels)])) + 1


def make_unit(engine, gg, traffic: dict, seed: int, control: bool = False):
    """``unit(i) -> (keys, levels [Q, n], supersteps)``: the ``i``-th batch
    of ``queries`` keys, taken in turn from the seed's key list."""
    keys = search_keys(gg, int(traffic["keys"]), seed)
    queries = int(traffic.get("queries", 1))
    # Looked up at each call, so a test can plant a fault in the program.
    program = importlib.import_module("repro.algorithms.bfs")

    def unit(i):
        batch = [int(keys[(i * queries + j) % len(keys)])
                 for j in range(queries)]
        if control:
            levels, steps = _control(engine, gg, batch)
        else:
            levels, steps = program.bfs_batched(engine, batch)
        return batch, np.asarray(levels), int(np.max(steps))
    return unit


def traversed_edges(gg, traffic: dict, answers: np.ndarray) -> int:
    """Graph500's count, summed over the unit's searches: the input tuples
    whose endpoints were both reached, duplicates and self-loops included,
    a tuple stored both ways counted once.  A search reaches all of a
    component or none of it, so that is the stored out-degree of the
    reached vertices, halved when every tuple is stored twice."""
    deg = gg.out_degrees()
    reached = sum(int(deg[np.isfinite(levels)].sum()) for levels in answers)
    return reached // 2 if gg.undirected else reached


def check(gg, traffic: dict, units: List, seed: int) -> Dict[str, float]:
    """Every unit's answer for ``check_keys`` keys drawn from the seed,
    against the reference's levels."""
    by_key: Dict[int, List[np.ndarray]] = collections.defaultdict(list)
    for u in units:
        for key, levels in zip(u.keys, u.answers):
            by_key[key].append(levels)
    rng = np.random.default_rng([seed, 2])
    sample = sorted(by_key)
    count = min(int(traffic["check_keys"]), len(sample))
    sample = rng.choice(sample, size=count, replace=False)
    mismatches, failed = 0, 0
    for key in sample:
        want = refs.bfs_levels(gg.row_ptr, gg.col, int(key))
        for levels in by_key[int(key)]:
            wrong = refs.level_mismatches(levels, want)
            mismatches += wrong
            failed += wrong > 0
    return {"level_mismatches": mismatches, "units_failed": failed,
            "units_checked": sum(len(by_key[int(k)]) for k in sample)}
