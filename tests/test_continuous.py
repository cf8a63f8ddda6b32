"""Continuous batching: slot refill in the resident loop == drain-batch.

The serving contract for PR 7: a ``ServeSession`` that compacts finished
queries out of the ``[Q, Pl, v_max]`` state at chunk boundaries and
refills freed slots from the stream must (a) return every query's result
**bitwise** equal to plain drain-batch ``run_batched`` — per backend
{reference, fused, hybrid} and on {1, 2, 4} forced devices via the
subprocess selftest — (b) never retrace after warmup across >= 3 refill
cycles, (c) compose with mutations, quarantine, admission control and
the degradation ladder, and (d) checkpoint/restore *mid-refill* with the
occupancy mask and per-slot query ids riding the carry.  The
``engine.execute`` facade and ``ServeConfig`` validation (the
api_redesign satellites) are pinned here too.
"""
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

from repro.core import graph as G
from repro.core import partition as PT
from repro.core.bsp import BSPEngine
from repro.checkpoint import CheckpointManager
from repro.runtime import (DegradationLadder, FaultInjector,
                           QuarantinePolicy, ServeSession, WorkerFailure,
                           chaos, drain_reference, serve_with_restarts)

INTERP = dict(interpret=True)
SRC = str(Path(__file__).resolve().parents[1] / "src")

BACKENDS = {
    "reference": dict(),
    "fused": dict(fused=True, block_e=256),
    "hybrid": dict(backend="hybrid"),
}

SLOTS = 4


@pytest.fixture(scope="module")
def graph():
    return G.rmat(8, 6, seed=13).with_uniform_weights(seed=1)


@pytest.fixture(scope="module")
def pg(graph):
    return PT.partition(graph, 4, PT.HIGH)


@pytest.fixture(scope="module")
def stream(graph):
    rng = np.random.default_rng(3)
    deg = graph.out_degrees()
    # hub + fringe + random: mixed convergence, so slots free at
    # different boundaries and refill asymmetrically
    return np.concatenate([
        [int(np.argmax(deg)), int(np.argmin(deg))],
        rng.integers(0, graph.num_vertices, size=8 * SLOTS - 2)])


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("alg", ["bfs", "sssp"])
def test_refill_bitwise_equals_drain_batch(pg, stream, backend, alg):
    """>= 4xQ stream through one resident session, per backend: every
    completion bitwise equal to its drain-batch row, every slot refilled,
    zero retraces after warmup."""
    eng = BSPEngine(pg, **BACKENDS[backend], **INTERP)
    want = drain_reference(eng, alg, stream, SLOTS)
    session = ServeSession(eng, alg, slots=SLOTS, chunk=2)
    qids = session.submit(stream)
    rep = session.drain()
    results = {r["query"]: r["result"] for r in session.poll()}
    assert len(results) == len(stream)
    for qid, row in zip(qids, want):
        np.testing.assert_array_equal(results[qid], row)
    assert rep["min_slot_refills"] >= 3
    assert rep["retraces"] == 0, rep
    assert rep["refills"] == len(stream) - SLOTS


@pytest.mark.parametrize("ndev", [1, 2, 4])
def test_distributed_refill_parity(ndev):
    """Distributed engines (votes psum'd across shards): subprocess
    selftest so the forced device count never leaks."""
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
               PYTHONPATH=SRC + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.continuous_selftest",
         "--parts", "4", "--batch", "4"],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "CONTINUOUS SELFTEST OK" in r.stdout


def test_zero_retrace_across_refill_cycles(pg, graph):
    """>= 3 full refill cycles of every slot; the chunk jit and the slot
    swap compile once, then the cache must not grow."""
    eng = BSPEngine(pg)
    rng = np.random.default_rng(7)
    session = ServeSession(eng, "bfs", slots=SLOTS, chunk=2)
    session.submit(rng.integers(0, graph.num_vertices, size=8 * SLOTS))
    session.drain()
    assert int(session.slot_refills.min()) >= 3
    assert session.retraces() == 0
    # a second session over the same engine is fully warm: entries stay
    entries = session._cache_entries()
    s2 = ServeSession(eng, "bfs", slots=SLOTS, chunk=2)
    s2.submit(rng.integers(0, graph.num_vertices, size=4 * SLOTS))
    s2.drain()
    assert s2._cache_entries() == entries


def test_refill_composed_with_mutate(graph):
    """Mutations land in the same resident engine that is continuously
    serving: drain -> mutate -> drain waves, parity per graph version,
    zero retraces (the dynamic chunk jit carries the payload)."""
    from repro.core.dynamic import DynamicGraph
    from repro.data.graphs import edge_stream

    dg = DynamicGraph(graph, 4, "high", mutation_capacity=64)
    eng = BSPEngine(dg, **INTERP)
    mstream = edge_stream(graph, 2, 32, churn=1.0, seed=5)
    rng = np.random.default_rng(9)
    session = ServeSession(eng, "bfs", slots=SLOTS, chunk=2)
    for wave in range(3):
        if wave > 0:
            session.mutate(mstream[wave - 1])
        srcs = rng.integers(0, graph.num_vertices, size=2 * SLOTS)
        qids = session.submit(srcs)
        session.drain()
        want = drain_reference(eng, "bfs", srcs, SLOTS)
        results = {r["query"]: r["result"] for r in session.poll()}
        for qid, row in zip(qids, want):
            np.testing.assert_array_equal(results[qid], row)
    assert session.retraces() == 0


def test_checkpoint_restore_mid_refill(pg, stream):
    """Snapshot after refills have begun; a fresh session restores the
    occupancy (mask + per-slot query ids + step frames) and finishes with
    results bitwise equal to the uninterrupted run."""
    eng = BSPEngine(pg)
    want = drain_reference(eng, "sssp", stream, SLOTS)

    s1 = ServeSession(eng, "sssp", slots=SLOTS, chunk=2)
    qids = s1.submit(stream)
    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td, keep=3)
        while s1.refills == 0:
            assert s1.step(), "drained before any refill"
        s1.snapshot(mgr, 1)
        assert s1.occupied.any()

        s2 = ServeSession(BSPEngine(pg), "sssp", slots=SLOTS, chunk=2)
        s2.restore(mgr)
        assert s2.occupied.tolist() == s1.occupied.tolist()
        assert s2.slot_query.tolist() == s1.slot_query.tolist()
        assert s2.refills == s1.refills
        while not s2.drained():
            s2.step()
    results = {r["query"]: r["result"] for r in s2.poll()}
    assert len(results) == len(stream)
    for qid, row in zip(qids, want):
        np.testing.assert_array_equal(results[qid], row)


def test_serve_with_restarts_resumes_refilled_occupancy(pg, stream):
    """An injected worker fault mid-session: the factory rebuilds the
    session, restore resumes the refilled occupancy, results stay
    bitwise."""
    want = drain_reference(BSPEngine(pg), "bfs", stream, SLOTS)

    def make_session():
        s = ServeSession(BSPEngine(pg), "bfs", slots=SLOTS, chunk=2)
        s.submit(stream)
        return s

    with tempfile.TemporaryDirectory() as td:
        inj = FaultInjector(sites={"superstep.chunk": [{"at": 4}]})
        with chaos.active(inj):
            session, summary = serve_with_restarts(
                make_session, CheckpointManager(td, keep=3))
    assert summary["failures"] == 1
    assert session.refills > 0
    results = {r["query"]: r["result"] for r in session.poll()}
    assert len(results) == len(stream)
    for qid, row in enumerate(want):
        np.testing.assert_array_equal(results[qid], row)


def test_ladder_handoff_carries_occupancy(pg, stream):
    """DegradationLadder threads the session API: primary dies, the
    fallback session adopts the refilled carry and finishes bitwise."""
    want = drain_reference(BSPEngine(pg), "bfs", stream, SLOTS)

    class Dying(ServeSession):
        def step(self):
            raise WorkerFailure("injected primary death")

    primary = Dying(BSPEngine(pg, fused=True, block_e=256, **INTERP),
                    "bfs", slots=SLOTS, chunk=2)
    fallback = ServeSession(BSPEngine(pg), "bfs", slots=SLOTS, chunk=2)
    primary.submit(stream)
    ladder = DegradationLadder(retries=1)
    primary.step_with_fallback(fallback, ladder)
    assert len(ladder.downgrades) == 1
    while not fallback.drained():
        fallback.step()
    results = {r["query"]: r["result"] for r in fallback.poll()}
    assert len(results) == len(stream)
    for qid, row in enumerate(want):
        np.testing.assert_array_equal(results[qid], row)


def test_quarantined_slot_is_refilled(pg, stream):
    """A tiny superstep budget quarantines deep queries; their slots go
    to the next tenants in the same window, and non-quarantined results
    stay bitwise."""
    want = drain_reference(BSPEngine(pg), "bfs", stream, SLOTS)
    quar = QuarantinePolicy(superstep_budget=2)
    session = ServeSession(BSPEngine(pg), "bfs", slots=SLOTS, chunk=2,
                           quarantine=quar)
    session.submit(stream)
    rep = session.drain()
    results = session.poll()
    assert len(results) == len(stream)          # quarantined still complete
    assert rep["quarantined"], "budget=2 should have quarantined something"
    assert rep["refills"] == len(stream) - SLOTS
    for r in results:
        if not r["quarantined"]:
            np.testing.assert_array_equal(r["result"], want[r["query"]])
    # reports name query ids, not slot indices
    assert {q["query"] for q in quar.quarantined} == set(rep["quarantined"])


def test_admission_capacity_rejects_with_reason(pg, stream):
    session = ServeSession(BSPEngine(pg), "bfs", slots=SLOTS, chunk=2,
                           queue_capacity=6)
    qids = session.submit(stream)
    rejected = [q for q in qids if q is None]
    assert len(rejected) == len(stream) - 6
    assert all(r["reason"] == "queue_full"
               for r in session.admission.rejected)
    session.drain()
    assert len(session.poll()) == 6


def test_depth_scheduler_admits_shallow_first(graph, pg):
    deg = graph.out_degrees()
    session = ServeSession(BSPEngine(pg), "bfs", slots=2, chunk=2,
                           scheduler="depth",
                           depth_key=lambda s: -int(deg[s]))
    lo, hi = int(np.argmin(deg)), int(np.argmax(deg))
    session.submit([lo, lo, lo, hi])
    # the hub (shallow BFS) must jump the fringe queries in the queue
    assert session.admission._queue[0][0][1] == hi
    session.drain()
    assert len(session.poll()) == 4

    with pytest.raises(ValueError, match="depth_key"):
        ServeSession(BSPEngine(pg), "bfs", slots=2, scheduler="depth")


# ---------------------------------------------------------------------------
# api_redesign satellites: execute facade + ServeConfig validation
# ---------------------------------------------------------------------------

def test_execute_facade_routes_all_modes(pg):
    from repro.algorithms.bfs import BFS_PROGRAM, multi_source_state

    eng = BSPEngine(pg)
    state = {"level": multi_source_state(pg, [1, 2])}
    edges = eng._edges_or_none(BFS_PROGRAM)
    want_state, want_steps = eng._run_batched(BFS_PROGRAM, edges,
                                              dict(state))
    got_state, got_steps = eng.execute(BFS_PROGRAM, dict(state))
    np.testing.assert_array_equal(np.asarray(got_state["level"]),
                                  np.asarray(want_state["level"]))
    np.testing.assert_array_equal(np.asarray(got_steps),
                                  np.asarray(want_steps))

    # chunked mode returns the chunked triple
    _, steps_q, info = eng.execute(BFS_PROGRAM, dict(state), chunk=2)
    assert info["chunks"] >= 1 and info["refilled"] == 0
    np.testing.assert_array_equal(np.asarray(steps_q),
                                  np.asarray(want_steps))

    # fixed-step mode (num_steps=) routes to _run_fixed_batched
    want = eng._run_fixed_batched(BFS_PROGRAM, 3, edges, dict(state))
    got = eng.execute(BFS_PROGRAM, dict(state), num_steps=3)
    np.testing.assert_array_equal(np.asarray(got["level"]),
                                  np.asarray(want["level"]))


def test_execute_facade_actionable_errors(pg):
    from repro.algorithms.bfs import BFS_PROGRAM, multi_source_state

    eng = BSPEngine(pg)
    state = {"level": multi_source_state(pg, [1])}
    with pytest.raises(ValueError, match="mutually exclusive"):
        eng.execute(BFS_PROGRAM, state, num_steps=3, chunk=2)
    with pytest.raises(ValueError, match="chunk="):
        eng.execute(BFS_PROGRAM, state, on_chunk=lambda s: None)
    with pytest.raises(ValueError, match="chunk="):
        eng.execute(BFS_PROGRAM, state, max_chunks=2)


def test_serve_config_validation():
    from repro.launch.graph_serve import ServeConfig

    ServeConfig(continuous=True, mutate=True).validate()      # composes
    ServeConfig(continuous=True, deadline_ms=50.0,
                queue_capacity=8, depth_buckets=2).validate()  # composes
    with pytest.raises(ValueError, match="--continuous"):
        ServeConfig(mutate=True, deadline_ms=50.0).validate()
    with pytest.raises(ValueError, match="--continuous"):
        ServeConfig(depth_buckets=2, queue_capacity=8).validate()
    with pytest.raises(ValueError, match="chaos"):
        ServeConfig(chaos=True, continuous=True).validate()
    with pytest.raises(ValueError, match="step-translatable"):
        ServeConfig(continuous=True, alg="bc").validate()
    with pytest.raises(ValueError, match="drain-batch"):
        from repro.algorithms import continuous_form
        continuous_form("ppr")
