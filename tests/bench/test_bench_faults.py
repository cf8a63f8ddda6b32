"""A run with the timed path broken underneath comes out as not correct:
the harness's look for a chip is skipped, the rest of the run is driven at
a tiny size on the CPU, and each fault the cells can have is planted in
the program."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import make_root  # also puts bench on the path
from bench import harness
from repro.core import bsp
from repro.core.partition import PartitionedGraph

bfs_mod = importlib.import_module("repro.algorithms.bfs")
SEED = 2**31 + 23
CELLS = ["g500-s20.bfs", "uniform-s20.pagerank", "uniform-s20.bfs"]


def run(root, workload, **kw):
    kw.setdefault("expect_mosaic", False)
    return harness.run_cell(root, workload, SEED, 0.2, False,
                            require_tpu=False, **kw)


def failed(result):
    return [name for name, c in result["checks"].items()
            if c["value"] > c["limit"]]


@pytest.mark.parametrize("workload", CELLS)
def test_step_returning_its_state_unchanged(tiny_root, monkeypatch,
                                            workload):
    def unchanged(self, program, state, *, num_steps=None, **_):
        if num_steps is not None:
            return state
        return state, jnp.ones((bsp.num_queries(state),), jnp.int32)

    monkeypatch.setattr(bsp.BSPEngine, "execute", unchanged)
    result = run(tiny_root, workload)
    assert not result["correct"]
    assert set(failed(result)) & {"level_mismatches", "rank_max_rel_err"}


def test_exchange_between_partitions_left_out(tiny_root, monkeypatch):
    # The reference backend's exchange moves outboxes to their peers; left
    # out, every peer receives the min-identity.
    monkeypatch.setattr(bsp.BSPEngine, "_exchange",
                        staticmethod(lambda outbox: jnp.full_like(
                            outbox, jnp.inf)))
    result = run(tiny_root, "g500-s20.bfs")
    assert not result["correct"]
    assert "level_mismatches" in failed(result)


@pytest.mark.parametrize("workload", CELLS)
def test_answer_altered_where_it_is_produced(tiny_root, monkeypatch,
                                             workload):
    original = PartitionedGraph.gather_global

    def altered(self, per_part):
        out = original(self, per_part).copy()
        i = int(np.argmin(out))        # a BFS source, or the smallest rank
        out[i] = out[i] + 1 if out[i] == 0 else out[i] * 1.01
        return out

    monkeypatch.setattr(PartitionedGraph, "gather_global", altered)
    result = run(tiny_root, workload)
    assert not result["correct"]
    assert set(failed(result)) & {"level_mismatches", "rank_max_rel_err"}


def test_interpreted_kernel_fails_a_hybrid_run(tiny_root):
    # On the CPU every Pallas call site takes the interpreter.
    result = run(tiny_root, "uniform-s20.pagerank", expect_mosaic=True)
    assert not result["correct"]
    assert result["checks"]["non_mosaic_kernel_calls"]["value"] > 0


def test_compile_inside_the_window_fails(tiny_root, monkeypatch):
    original = bfs_mod.bfs_batched

    def compiling(engine, sources):
        jax.jit(lambda x: x + 1)(jnp.zeros(()))      # a new program
        return original(engine, sources)

    monkeypatch.setattr(bfs_mod, "bfs_batched", compiling)
    result = run(tiny_root, "g500-s20.bfs")
    assert not result["correct"]
    assert "compiles_in_window" in failed(result)


@pytest.mark.parametrize("workload", CELLS)
def test_control_in_the_programs_place_is_not_correct(tiny_root, workload):
    result = run(tiny_root, workload, control=True)
    assert not result["correct"]
    assert set(failed(result)) & {"level_mismatches", "rank_max_rel_err"}


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
