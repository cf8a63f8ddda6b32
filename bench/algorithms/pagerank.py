"""PageRank traffic: TOTEM's headline algorithm.

A traffic mix with ``"algorithm": "pagerank"`` gives ``iterations`` (a
unit is one call of the program's ``repro.algorithms.pagerank.pagerank``
for that many iterations), ``queries`` (1: the program's PageRank runs one
rank vector a call), ``teps_edges`` ``edges_x_iterations`` (TOTEM's rate:
stored edges times iterations), and ``limits``: ``rank_max_rel_err``, the
largest relative error of a rank against the float64 reference of
``bench/refs.py``.

The control puts the reference in the program's place, computed in
bfloat16 with float32 sums: the precision below the float32 the
configuration states.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

import numpy as np

from bench import refs
from bench.harness import BenchError

TEPS_RULE = "edges_x_iterations"


def make_unit(engine, gg, traffic: dict, seed: int, control: bool = False):
    """``unit(i) -> ([], ranks [1, n], iterations)``."""
    if int(traffic.get("queries", 1)) != 1:
        raise BenchError("the program's pagerank computes one rank vector "
                         "a call: a pagerank mix takes queries 1")
    iterations = int(traffic["iterations"])
    program = importlib.import_module("repro.algorithms.pagerank")

    def unit(i):
        if control:
            ranks = refs.pagerank_bf16(gg.row_ptr, gg.col, iterations)
        else:
            ranks = program.pagerank(engine, iterations)
        return [], np.asarray(ranks)[None], iterations
    return unit


def traversed_edges(gg, traffic: dict, answers: np.ndarray) -> int:
    return gg.num_edges * int(traffic["iterations"])


def check(gg, traffic: dict, units: List, seed: int) -> Dict[str, float]:
    """Every unit's ranks against the float64 reference."""
    want = refs.pagerank(gg.row_ptr, gg.col, int(traffic["iterations"]))
    limit = float(traffic["limits"]["rank_max_rel_err"])
    errs = [refs.max_rel_err(u.answers[0], want) for u in units]
    return {"rank_max_rel_err": max(errs, default=float("inf")),
            "units_failed": sum(e > limit for e in errs),
            "units_checked": len(errs)}
