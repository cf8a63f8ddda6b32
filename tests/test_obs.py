"""repro.obs: host spans (counts, total and self time, the profiler's
trace) and the device phases of the compiled superstep loops."""
import glob
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from hlo_scopes import compiled_text, instructions, phase_of, unphased
from repro import obs
from repro.algorithms.bfs import BFS_PROGRAM, bfs_batched, multi_source_state
from repro.algorithms.pagerank import (initial_state, make_pagerank_program,
                                       pagerank)
from repro.core import graph as G, partition as PT
from repro.core.bsp import BSPEngine, batch_state


def _delta(before, after):
    """Per-span (and per-counter) change of the table between two
    snapshots."""
    return {name: {k: row[k] - before.get(name, {}).get(k, 0) for k in row}
            for name, row in after.items()
            if row != before.get(name)}


def test_nested_spans_count_total_and_self(monkeypatch):
    # execute [0, 10) holds wait [1, 3) and fetch [4, 9), which holds wait
    # [5, 6): execute's self time is 10 - 2 - 5, fetch's 5 - 1.
    ticks = iter([0.0, 1.0, 3.0, 4.0, 5.0, 6.0, 9.0, 10.0])
    monkeypatch.setattr(obs.time, "perf_counter", lambda: next(ticks))
    before = obs.snapshot()
    with obs.span(obs.EXECUTE):
        with obs.span(obs.WAIT):
            pass
        with obs.span(obs.FETCH):
            with obs.span(obs.WAIT):
                pass
    monkeypatch.undo()
    assert _delta(before, obs.snapshot()) == {
        obs.EXECUTE: {"count": 1, "total_s": 10.0, "self_s": 3.0},
        obs.WAIT: {"count": 2, "total_s": 3.0, "self_s": 3.0},
        obs.FETCH: {"count": 1, "total_s": 5.0, "self_s": 4.0}}


def test_span_is_a_decorator_and_records_through_an_exception(
        monkeypatch):
    @obs.span(obs.STATE_INIT)
    def init(fail):
        if fail:
            raise RuntimeError("boom")
        return 7

    # init [0, 1); fetch [10, 20) around a failed init [12, 15)
    ticks = iter([0.0, 1.0, 10.0, 12.0, 15.0, 20.0])
    monkeypatch.setattr(obs.time, "perf_counter", lambda: next(ticks))
    before = obs.snapshot()
    assert init(False) == 7
    with pytest.raises(RuntimeError, match="boom"):
        with obs.span(obs.FETCH):
            init(True)
    monkeypatch.undo()
    assert _delta(before, obs.snapshot()) == {
        obs.STATE_INIT: {"count": 2, "total_s": 4.0, "self_s": 4.0},
        obs.FETCH: {"count": 1, "total_s": 10.0, "self_s": 7.0}}
    assert obs._local.stack == []


def test_self_time_ignores_spans_of_other_threads():
    entered, done = threading.Event(), threading.Event()

    def other():
        entered.wait(5)
        with obs.span(obs.WAIT):
            pass
        done.set()

    worker = threading.Thread(target=other)
    worker.start()
    before = obs.snapshot()
    with obs.span(obs.HYBRID_SPLIT):
        entered.set()
        assert done.wait(5)
    worker.join(5)
    assert not worker.is_alive()
    row = _delta(before, obs.snapshot())[obs.HYBRID_SPLIT]
    assert row["self_s"] == row["total_s"] > 0


def test_counters_sum_their_amounts():
    before = obs.snapshot()
    obs.count(obs.ELL_PACK, slots=40, nonzeros=16)
    obs.count(obs.ELL_PACK, slots=24, nonzeros=16)
    assert _delta(before, obs.snapshot())[obs.ELL_PACK] == {
        "count": 2, "slots": 64, "nonzeros": 32}
    with pytest.raises(ValueError, match="unknown counter"):
        obs.count("repro.nothing", slots=1)


def test_snapshot_is_a_copy():
    with obs.span(obs.WAIT):
        pass
    snap = obs.snapshot()
    kept = dict(snap[obs.WAIT])
    snap[obs.WAIT]["count"] = -1
    del snap[obs.WAIT]
    assert obs.snapshot()[obs.WAIT] == kept
    with obs.span(obs.WAIT):
        pass
    assert obs.snapshot()[obs.WAIT]["count"] == kept["count"] + 1


def test_unknown_names_are_refused():
    with pytest.raises(ValueError, match="unknown span"):
        with obs.span("repro.nope"):
            pass
    with pytest.raises(ValueError, match="unknown phase"):
        obs.phase("bsp.nope")
    assert obs.ELL not in obs.PHASES


@pytest.fixture(scope="module")
def pg():
    return PT.partition(G.rmat(8, 8, seed=3), 4, PT.HIGH)


def test_profile_holds_the_host_spans(pg, tmp_path):
    engine = BSPEngine(pg)
    bfs_batched(engine, [0])                       # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        bfs_batched(engine, [1])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in data.planes for line in plane.lines
             for ev in line.events}
    assert {obs.STATE_INIT, obs.EXECUTE, obs.FETCH, obs.WAIT} <= names


def test_engine_entry_points_record_their_spans(pg):
    hybrid = BSPEngine(pg, backend="hybrid")
    before = obs.snapshot()
    ranks = pagerank(hybrid, 3)
    levels, _ = bfs_batched(hybrid, [0, 5])
    d = _delta(before, obs.snapshot())
    assert d[obs.EXECUTE]["count"] == 2
    assert d[obs.STATE_INIT]["count"] == 2 and d[obs.FETCH]["count"] == 2
    # one degree split per direction the engine builds (PageRank, BFS),
    # inside execute: execute's self time leaves it out
    assert d[obs.HYBRID_SPLIT]["count"] == 2
    assert d[obs.EXECUTE]["self_s"] < d[obs.EXECUTE]["total_s"]
    # pagerank's fetch, bfs's fetch, and the direction counters' read
    assert d[obs.WAIT]["count"] == 3
    assert ranks.shape == (pg.num_vertices,)
    assert levels.shape == (2, pg.num_vertices)


# loop: (engine options, algorithm, fixed steps, the phases it must hold,
# whether it runs an ELL leg)
CORE = {"bsp.gather", "bsp.reduce", "bsp.apply"}
LOOPS = {
    "reference-bfs-push": (dict(direction_switch=False), "bfs", None,
                           CORE | {"bsp.exchange"}, False),
    "reference-bfs-switch": ({}, "bfs", None,
                             CORE | {"bsp.exchange", "bsp.direction"}, True),
    "hybrid-pagerank": (dict(backend="hybrid"), "pagerank", 3,
                        CORE | {"bsp.layout"}, True),
    "hybrid-bfs-switch": (dict(backend="hybrid"), "bfs", None,
                          CORE | {"bsp.layout", "bsp.direction"}, True),
}


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_phases_cover_the_compiled_loop(pg, loop):
    options, alg, steps, phases, ell = LOOPS[loop]
    engine = BSPEngine(pg, **options)
    if alg == "bfs":
        program = BFS_PROGRAM
        state = {"level": jnp.asarray(multi_source_state(pg, [0]))}
    else:
        program = make_pagerank_program(pg.num_vertices)
        state = batch_state(initial_state(pg))
    rows = instructions(compiled_text(engine, program, state, steps))
    assert any(r["op"].split(":")[-1] in ("gather", "scatter")
               for r in rows)
    assert unphased(rows) == []
    seen = {phase_of(r["op_name"]) for r in rows} - {None}
    assert phases <= seen <= set(obs.PHASES)
    in_ell = [r for r in rows if obs.ELL in r["op_name"].split("/")]
    assert bool(in_ell) == ell
    # the ELL leg's ops keep their phases inside the container scope
    assert all(phase_of(r["op_name"]) for r in in_ell)
    assert any(phase_of(r["op_name"]) == "bsp.gather"
               and r["op"].split(":")[-1] == "gather" for r in in_ell) == ell

