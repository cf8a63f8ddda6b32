"""Reference vs fused BSP superstep timings → BENCH_superstep.json.

Times one jitted superstep of the reference path (gather → [Pl, e_max]
messages → scatter-reduce) against the fused Pallas path for a sum-combine
program (PageRank) and a min-combine program (BFS), across RMAT scales and
all three partitioning strategies (RAND/HIGH/LOW).

Also verifies the fused path's core claim **structurally**: the compiled HLO
of the fused superstep must contain no non-parameter op producing an
``f32[Pl, e_max]`` (or ``f32[Pl, e_pad]``) value — i.e. the edge-message
array is never materialized in HBM.  The reference superstep must contain at
least one (that's the array being eliminated).  BFS and PageRank take no
``f32[Pl, e_max]``-shaped *inputs* either, so the check is exact for them.

Runs in interpret mode on CPU (the container default); on a real TPU the
same script times the compiled kernels.

Usage (from the repo root):
  python benchmarks/superstep_bench.py [--scales 10 11] [--parts 4]
      [--quick] [--hybrid] [--batched] [--dopt] [--distributed]
      [--devices 8] [--seed 1] [--out BENCH_superstep.json]

``--quick`` keeps only the smallest scale (the CI bench job's ~5-minute
budget); ``--hybrid`` also times the degree-split two-engine backend per
cell; ``--seed`` pins the RMAT topology so cells are comparable across runs.
``--batched`` adds the query-throughput column: full batched BFS runs at
Q ∈ {1, 8, 32} against Q sequential single-source runs on the same engine,
recording queries/sec, the amortized per-query time, the amortization
ratio, and the compile-cache growth across same-Q batches.  The
deterministic claim is asserted everywhere: a batch of Q queries runs
through **one** compiled while_loop (``retraces == 0`` across batches with
different sources — the compile-cache-hit contract).  The throughput claim
— amortized per-query time strictly below the sequential per-query time
for Q ≥ 8 — is asserted on a real TPU backend, where one while_loop
dispatch and one kernel-launch sequence genuinely replace Q of each; in
CPU interpret mode the Pallas grids execute Q× Python cells and XLA-CPU
compute scales ~linearly with Q, so (exactly like the fused/reference
economics, see ROADMAP) the ratio inverts and is *recorded* and
regression-gated by ``scripts/bench_check.py`` instead.  Point
``--scales 18`` at it for the rmat18 serving measurement.
``--dopt`` adds the direction-optimized traversal column (docs/traversal.md):
batched BFS over the *symmetrized* bench graph under forced top-down
(``direction="push"``) vs the fitted per-shard auto switch, recording wall
times (noisy, baseline-gated) and the deterministic superstep-indexed
counters that are absolutely asserted — auto examines strictly fewer edges
than top-down through at least one real switch, stays bitwise-identical to
the numpy oracle, respects the once-per-edge push bound, and never
retraces across a switch.
``--distributed`` adds a multi-device column: the bench re-executes itself
in a subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
when the runtime has fewer than ``--devices`` devices, then times one
superstep of the sharded fused engine against the sharded *hybrid* engine
(per-shard degree split + aggregated-outbox exchange) and records the
per-superstep exchanged bytes: the full ``[pl, P, o_max]`` tensor the
fused/reference exchange ships vs the compact used-slot blocks of the
hybrid exchange, next to the β·|E|·4 aggregation bound (paper §3.4).
``scripts/bench_check.py`` diffs the JSON against a baseline and fails on
>20% fused-superstep regression — and deterministically on any >20% growth
in exchanged bytes or fused temp bytes.
"""
from __future__ import annotations

import argparse
import functools
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / "src"))

from benchmarks.common import timeit  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import graph as G  # noqa: E402
from repro.core import partition as PT  # noqa: E402
from repro.core.bsp import BSPEngine  # noqa: E402
from repro.kernels.ops import fused_span_limit  # noqa: E402
from repro.algorithms.bfs import BFS_PROGRAM  # noqa: E402
from repro.algorithms.pagerank import (initial_state,  # noqa: E402
                                       make_pagerank_program)

_SKIP_OPS = ("parameter(", " copy(", "bitcast(", "constant(")


def message_array_lines(hlo: str, pl_count: int, e_sizes) -> list:
    """HLO lines where a non-parameter op produces an f32[Pl, e_*] value
    (with or without the engine's leading query-batch dim: f32[Q, Pl, e_*]
    counts too — a batched message array is still a message array)."""
    pats = [re.compile(rf"f32\[(?:\d+,)?{pl_count},{e}\]")
            for e in set(e_sizes)]
    hits = []
    for line in hlo.splitlines():
        lhs = line.split(" = ", 1)
        if len(lhs) != 2 or any(tok in lhs[1] for tok in _SKIP_OPS):
            continue
        head = lhs[1].split("(", 1)[0]   # output shape + op name
        if any(p.search(head) for p in pats):
            hits.append(line.strip())
    return hits


def _superstep_fn(eng: BSPEngine, program):
    from repro.core.bsp import batch_state

    edges = eng._edges_or_none(program)
    step_fn = eng._step_fn(program, edges, eng._exchange, eng._all_finished)
    # The internal step runs on [Q, Pl, ...] state; time it as a Q=1 batch
    # (exactly what run() executes per superstep).
    return jax.jit(lambda s, i: step_fn(batch_state(s), i))


def _program_and_state(pg, parts: int, alg: str):
    """The benchmarked program + initial state, shared by the single-device
    and distributed cells so their timings stay comparable."""
    if alg == "pagerank":
        return make_pagerank_program(pg.num_vertices), initial_state(pg)
    level0 = np.full((parts, pg.v_max), np.inf, dtype=np.float32)
    level0[0, 0] = 0.0
    return BFS_PROGRAM, {"level": jnp.asarray(level0)}


def bench_cell(pg, scale: int, parts: int, strategy: str, alg: str,
               block_e: int, hybrid: bool = False) -> dict:
    ref_eng = BSPEngine(pg)
    fus_eng = BSPEngine(pg, fused=True, block_e=block_e)
    program, state = _program_and_state(pg, parts, alg)

    blk = fus_eng._fwd_blk
    e_sizes = (pg.fwd.e_max, blk.e_pad)
    rec = dict(scale=scale, parts=parts, strategy=strategy, algorithm=alg,
               combine=program.combine, e_max=pg.fwd.e_max, e_pad=blk.e_pad,
               span=blk.span, span_req=blk.span_req, block_e=block_e,
               num_blocks=blk.num_blocks, v_max=pg.v_max,
               beta=pg.beta_with_reduction,
               # False → span exceeded max_span/VMEM budget and this cell's
               # "fused" engine statically fell back to the reference chain.
               fused_active=blk.span <= fused_span_limit(
                   block_e, program.combine))

    engines = [("ref", ref_eng), ("fused", fus_eng)]
    if hybrid:
        hyb_eng = BSPEngine(pg, backend="hybrid")
        engines.append(("hybrid", hyb_eng))
        plan = hyb_eng.hybrid_plan()
        rec["hybrid_k_dense"] = plan["k_dense"]
        rec["hybrid_mode"] = plan["mode"]

    step0 = jnp.int32(0)
    for name, eng in engines:
        fn = _superstep_fn(eng, program)
        lowered = fn.lower(state, step0)
        compiled = lowered.compile()
        hlo = compiled.as_text()
        rec[f"{name}_hlo_msg_arrays"] = len(
            message_array_lines(hlo, parts, e_sizes))
        try:
            rec[f"{name}_temp_bytes"] = int(
                compiled.memory_analysis().temp_size_in_bytes)
        except Exception:
            rec[f"{name}_temp_bytes"] = None
        rec[f"{name}_ms"] = timeit(fn, state, step0, warmup=1, iters=5) * 1e3

    rec["speedup"] = rec["ref_ms"] / max(rec["fused_ms"], 1e-12)
    return rec


def bench_batched_cell(pg, scale: int, parts: int, strategy: str,
                       q: int, block_e: int, seed: int,
                       backend: str = "reference") -> dict:
    """One query-throughput cell: a batch of Q BFS queries through one
    ``run_batched`` while_loop vs Q sequential single-source runs on the
    same engine.  Wall-clock timings are full-run (including host-side
    state construction and gather — the serving-realistic cost)."""
    import time

    from repro.algorithms.bfs import bfs, bfs_batched

    if backend == "fused":
        eng = BSPEngine(pg, fused=True, block_e=block_e)
    elif backend == "hybrid":
        eng = BSPEngine(pg, backend="hybrid")
    else:
        eng = BSPEngine(pg)
    rng = np.random.default_rng(seed)
    sources = rng.integers(0, pg.num_vertices, size=q)

    def wall(fn, iters=3):
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2]

    bfs_batched(eng, sources)                  # compile the Q-batch loop
    cache_fn = BSPEngine._run_batched
    entries0 = cache_fn._cache_size()
    # Different sources, same Q: must reuse the compiled loop (no retrace).
    bfs_batched(eng, rng.integers(0, pg.num_vertices, size=q))
    retraces = cache_fn._cache_size() - entries0
    batched_s = wall(lambda: bfs_batched(eng, sources))

    bfs(eng, int(sources[0]))                  # compile the Q=1 loop
    seq_s = wall(lambda: [bfs(eng, int(s)) for s in sources], iters=1)

    return dict(
        scale=scale, parts=parts, strategy=strategy, algorithm="bfs",
        combine="min", mode=f"batched_q{q}", q=q, block_e=block_e,
        backend=backend, v_max=pg.v_max,
        batched_ms=batched_s * 1e3,
        batched_ms_per_query=batched_s * 1e3 / q,
        seq_ms=seq_s * 1e3, seq_ms_per_query=seq_s * 1e3 / q,
        amortization=seq_s / max(batched_s, 1e-12),
        queries_per_sec=q / max(batched_s, 1e-12),
        retraces=retraces,
        compile_cache_entries=cache_fn._cache_size())


def bench_dopt_cell(g, pg, scale: int, parts: int, strategy: str,
                    seed: int, backend: str = "reference",
                    block_e: int = 256, q: int = 4) -> dict:
    """One direction-optimized traversal cell: a Q-batch of BFS queries
    under forced ``direction="push"`` (classic top-down) vs ``"auto"``
    (per-query, per-shard fitted switching — docs/traversal.md), on the
    same engine backend.  Timings are noisy on CPU and only recorded; the
    asserted halves are the *deterministic* edge counters: auto must
    examine fewer edges than top-down while staying bitwise-identical to
    the numpy oracle, top-down must respect the once-per-edge BFS bound
    (every vertex joins the frontier exactly once, so a query scans at
    most |E| edges), and a direction switch must not retrace.

    The column traverses the *symmetrized* bench graph — undirected BFS
    is the canonical direction-optimized setting (arXiv 1503.04359):
    every visited vertex is a reachable parent through its in-edges, so
    the bottom-up scans early-exit instead of paying full rows for a
    permanently-unreachable tail."""
    import time

    from repro.algorithms.bfs import bfs_batched, bfs_reference
    from repro.algorithms.cc import symmetrize

    g = symmetrize(g)
    pg = PT.partition(g, parts, strategy)
    kw = {}
    if backend == "fused":
        kw = dict(fused=True, block_e=block_e)
    elif backend == "hybrid":
        kw = dict(backend="hybrid")
    top = BSPEngine(pg, direction="push", **kw)
    dopt = BSPEngine(pg, direction="auto", **kw)

    rng = np.random.default_rng(seed)
    sources = rng.integers(0, pg.num_vertices, size=q)

    def wall(fn, iters=3):
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2]

    lv_top, _ = bfs_batched(top, sources)          # compile the push loop
    st_top = top.last_direction_stats
    lv_dopt, _ = bfs_batched(dopt, sources)        # compile the auto loop
    st = dopt.last_direction_stats
    cache_fn = BSPEngine._run_batched
    entries0 = cache_fn._cache_size()
    # Different sources, same Q: switch points move between supersteps and
    # queries, but direction is traced-carry data — no retrace allowed.
    bfs_batched(dopt, rng.integers(0, pg.num_vertices, size=q))
    retraces = cache_fn._cache_size() - entries0

    oracle = np.stack([bfs_reference(g, int(s)) for s in sources])
    bitwise = int(np.array_equal(np.asarray(lv_top), oracle)
                  and np.array_equal(np.asarray(lv_dopt), oracle))

    topdown_ms = wall(lambda: bfs_batched(top, sources)) * 1e3
    dopt_ms = wall(lambda: bfs_batched(dopt, sources)) * 1e3

    topdown_edges = int(np.asarray(st_top["edges_examined"]).sum())
    dopt_edges = int(np.asarray(st["edges_examined"]).sum())
    return dict(
        scale=scale, parts=parts, strategy=strategy, algorithm="bfs",
        combine="min", mode="dopt", q=q, block_e=block_e, backend=backend,
        num_edges=g.num_edges,
        topdown_ms=topdown_ms, dopt_ms=dopt_ms,
        topdown_edges=topdown_edges, dopt_edges=dopt_edges,
        # once-per-edge push bound: Q queries scan at most Q·|E| edges
        edges_bound=q * g.num_edges,
        edges_saved_ratio=1.0 - dopt_edges / max(topdown_edges, 1),
        dopt_switches=int(np.asarray(st["switches"]).sum()),
        topdown_switches=int(np.asarray(st_top["switches"]).sum()),
        retraces=retraces,
        bitwise=bitwise)


def bench_mutations_cell(g, scale: int, parts: int, strategy: str,
                         seed: int, backend: str = "reference",
                         block_e: int = 256, rounds: int = 4,
                         mutation_batch: int = 256) -> dict:
    """One dynamic-graph cell: in-place mutation throughput + incremental
    warm-start economics on a resident DynamicGraph.

    Applies ``rounds`` insert-only mutation batches (insert-only keeps the
    window monotone so the warm-vs-cold comparison is apples-to-apples),
    recording edges/s applied through the compiled scatter, the warm-start
    vs cold superstep counts for a standing BFS query set, and the dynamic
    runner's compile-cache growth across batches (``retraces`` — 0 is the
    contract, gated deterministically by scripts/bench_check.py alongside
    ``incremental_steps``/``cold_steps``).
    """
    from repro.core import bsp
    from repro.core.dynamic import DynamicGraph
    from repro.data.graphs import edge_stream
    from repro.algorithms.bfs import bfs_batched, bfs_incremental

    dg = DynamicGraph(g, parts, strategy,
                      mutation_capacity=mutation_batch)
    if backend == "fused":
        eng = BSPEngine(dg, fused=True, block_e=block_e)
    elif backend == "hybrid":
        eng = BSPEngine(dg, backend="hybrid")
    else:
        eng = BSPEngine(dg)
    rng = np.random.default_rng(seed)
    sources = rng.integers(0, g.num_vertices, size=8)
    prev, _ = bfs_batched(eng, sources)            # compile + first fixpoint
    stream = edge_stream(g, rounds + 1, mutation_batch, churn=1.0,
                         seed=seed)

    # warm-up round: compiles the incremental (relaxation) program too, so
    # the retrace counter below sees only genuine re-traces
    mark = dg.mark()
    dg.apply_mutations(stream[0])
    dirty, _ = dg.dirty_since(mark)
    prev, _ = bfs_incremental(eng, prev, dirty)
    prev, _ = bfs_batched(eng, sources)

    entries0 = bsp._run_dyn_jit._cache_size() + \
        bsp._run_dyn_hybrid_jit._cache_size()
    edges = apply_s = 0.0
    warm_steps = cold_steps = 0
    bitwise = True
    mark = dg.mark()
    for mb in stream[1:]:
        rep = dg.apply_mutations(mb)
        edges += rep["num_edges"]
        apply_s += rep["apply_ms"] / 1e3
        dirty, monotone = dg.dirty_since(mark)
        assert monotone                            # churn=1.0 stream
        warm, wsteps = bfs_incremental(eng, prev, dirty)
        cold, csteps = bfs_batched(eng, sources)
        bitwise = bitwise and bool(np.array_equal(warm, cold))
        warm_steps += int(wsteps.max())
        cold_steps += int(csteps.max())
        prev = cold
        mark = dg.mark()
    retraces = (bsp._run_dyn_jit._cache_size()
                + bsp._run_dyn_hybrid_jit._cache_size() - entries0)
    return dict(
        scale=scale, parts=parts, strategy=strategy, algorithm="bfs",
        combine="min", mode="mutations", block_e=block_e, backend=backend,
        v_max=dg.pg.v_max, delta_slots=dg.delta_slots,
        mutation_rounds=rounds, mutation_batch=mutation_batch,
        mutation_edges=int(edges),
        mutation_edges_per_sec=edges / max(apply_s, 1e-12),
        apply_ms_per_batch=apply_s * 1e3 / max(rounds, 1),
        incremental_steps=warm_steps, cold_steps=cold_steps,
        warm_bitwise_equal=bitwise,
        compactions=dg.compactions,
        hybrid_rebuilds=eng.hybrid_dyn_rebuilds, retraces=retraces)


def bench_checkpoint_cell(pg, scale: int, parts: int, strategy: str,
                          seed: int, chunk: int = 2, q: int = 8) -> dict:
    """One fault-tolerance cell: snapshot overhead + recovery time of the
    checkpointable chunked run mode (docs/robustness.md).

    Runs a Q-query BFS batch three ways on the same engine: the resident
    while_loop (the reference result), the chunked mode bare, and the
    chunked mode with a blocking ``save_tree`` snapshot at every chunk
    boundary + the quarantine scan.  Records the per-superstep snapshot
    overhead, the recovery time (restore the *first* snapshot and resume
    to the fixpoint), and the deterministic halves gated by
    scripts/bench_check.py: ``resume_bitwise`` (the resumed fixpoint
    equals the resident loop's bitwise), ``chunk_retraces`` (chunked
    windows reuse one compile), and ``quarantined`` (0 on the clean path).
    """
    import tempfile
    import time

    from repro.checkpoint import CheckpointManager
    from repro.runtime import QuarantinePolicy

    eng = BSPEngine(pg)
    rng = np.random.default_rng(seed)
    sources = rng.integers(0, pg.num_vertices, size=(q, 1))
    from repro.algorithms.bfs import multi_source_state
    state0 = {"level": jnp.asarray(multi_source_state(pg, sources))}
    ref_state, ref_steps = eng.execute(BFS_PROGRAM, dict(state0))

    def wall(fn, iters=3):
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2]

    # warm the chunked windows, then hold the compile-cache baseline
    eng.execute(BFS_PROGRAM, dict(state0), chunk=chunk)
    entries0 = BSPEngine._run_chunk._cache_size()
    bare_s = wall(lambda: eng.execute(
        BFS_PROGRAM, dict(state0), chunk=chunk))

    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td, keep=4096)   # keep every snapshot
        quar = QuarantinePolicy(superstep_budget=int(pg.num_vertices))
        quar.begin(q)
        ckpt_ms = []

        def on_chunk(snap):
            t0 = time.perf_counter()
            mgr.save_tree(snap["step"],
                          {"state": snap["state"], "fin": snap["fin"],
                           "steps_q": snap["steps_q"]}, blocking=True)
            ckpt_ms.append((time.perf_counter() - t0) * 1e3)
            return quar.scan(snap)

        t0 = time.perf_counter()
        st, sq, info = eng.execute(
            BFS_PROGRAM, dict(state0), chunk=chunk,
            on_chunk=on_chunk)
        ckpt_run_s = time.perf_counter() - t0

        # recovery: restore the FIRST snapshot, resume to the fixpoint
        like = {"state": {"level": np.zeros_like(np.asarray(st["level"]))},
                "fin": np.zeros(q, bool), "steps_q": np.zeros(q, np.int32)}
        t0 = time.perf_counter()
        step, tree = mgr.restore_tree(like, chunk)
        final, fsq, _ = eng.execute(
            BFS_PROGRAM, tree["state"], chunk=chunk,
            start_step=step, fin=tree["fin"], steps_q=tree["steps_q"])
        recovery_s = time.perf_counter() - t0

    resume_bitwise = bool(
        np.array_equal(np.asarray(final["level"]),
                       np.asarray(ref_state["level"]))
        and np.array_equal(np.asarray(fsq), np.asarray(ref_steps))
        and np.array_equal(np.asarray(st["level"]),
                           np.asarray(ref_state["level"])))
    supersteps = max(info["final_step"], 1)
    return dict(
        scale=scale, parts=parts, strategy=strategy, algorithm="bfs",
        combine="min", mode="checkpoint", block_e=None, q=q,
        checkpoint_every=chunk, v_max=pg.v_max,
        supersteps=info["final_step"], chunks=info["chunks"],
        chunked_ms=bare_s * 1e3,
        chunked_ckpt_ms=ckpt_run_s * 1e3,
        ckpt_ms_per_superstep=sum(ckpt_ms) / supersteps,
        ckpt_overhead_ratio=(ckpt_run_s / max(bare_s, 1e-12)),
        recovery_ms=recovery_s * 1e3,
        snapshots=len(ckpt_ms),
        resume_bitwise=int(resume_bitwise),
        quarantined=len(quar.quarantined),
        chunk_retraces=BSPEngine._run_chunk._cache_size() - entries0)


def bench_verify_cell(g, pg, scale: int, parts: int, strategy: str,
                      seed: int, chunk: int = 2, q: int = 8) -> dict:
    """One integrity cell: what the silent-corruption defense costs
    (docs/robustness.md, "Silent faults").

    Runs a Q-query BFS batch through the chunked mode bare, then with the
    in-loop invariant monitor armed, and finally certifies every harvested
    fixpoint with the O(V+E) result certifier.  The monitor cost is
    measured *inside* ``observe`` (pure host NumPy at window boundaries)
    and the certifier cost as the wall time of ``certify_batch`` — both
    are the actual added work, not a noisy whole-run diff.  Deterministic
    halves gated by scripts/bench_check.py: ``certified_ok == q`` (a clean
    fixpoint always certifies) and ``monitors_fired == 0`` (no false
    positives); the timing half gates ``verify_overhead_ratio`` — the
    ISSUE contract is <= 0.10 of the bare chunked run.
    """
    import time

    from repro.algorithms.bfs import gather_batch, multi_source_state
    from repro.runtime import ResultCertifier, monitor_for

    eng = BSPEngine(pg)
    rng = np.random.default_rng(seed)
    sources = rng.integers(0, pg.num_vertices, size=(q, 1))
    state0 = {"level": jnp.asarray(multi_source_state(pg, sources))}

    def wall(fn, iters=3):
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2]

    eng.execute(BFS_PROGRAM, dict(state0), chunk=chunk)  # warm the windows
    bare_s = wall(lambda: eng.execute(
        BFS_PROGRAM, dict(state0), chunk=chunk))

    mon = monitor_for("bfs", chunk=chunk)
    mon_s = [0.0]
    observe = mon.observe

    def timed_observe(snap):
        t0 = time.perf_counter()
        rec = observe(snap)
        mon_s[0] += time.perf_counter() - t0
        return rec

    mon.observe = timed_observe
    st, _, info = eng.execute(
        BFS_PROGRAM, dict(state0), chunk=chunk, monitor=mon)

    certifier = ResultCertifier("bfs", g)
    levels = gather_batch(pg, st["level"])
    t0 = time.perf_counter()
    verdicts = certifier.certify_batch(levels,
                                       sources=sources.reshape(-1))
    certify_s = time.perf_counter() - t0

    return dict(
        scale=scale, parts=parts, strategy=strategy, algorithm="bfs",
        combine="min", mode="verify", block_e=None, q=q,
        checkpoint_every=chunk, v_max=pg.v_max,
        supersteps=info["final_step"], chunks=info["chunks"],
        chunked_ms=bare_s * 1e3,
        monitor_ms=mon_s[0] * 1e3,
        certify_ms=certify_s * 1e3,
        certify_ms_per_query=certify_s * 1e3 / q,
        verify_overhead_ratio=(mon_s[0] + certify_s) / max(bare_s, 1e-12),
        monitors_fired=info["monitors_fired"],
        certified_ok=sum(1 for v in verdicts if v.ok),
        certify_failed=[v.reason() for v in verdicts if not v.ok])


def bench_continuous_cell(pg, scale: int, parts: int, strategy: str,
                          seed: int, chunk: int = 2, q: int = 8,
                          stream_factor: int = 8) -> dict:
    """One continuous-batching cell: q/s and p99-under-load of a resident
    ``ServeSession`` (slot refill at chunk boundaries) vs fixed-batch
    drain at the same Q, over a ``stream_factor``x-Q stream submitted up
    front.

    Timing is CPU-noisy; the deterministic halves are gated instead
    (refill decisions depend only on superstep-indexed convergence, so
    they are reproducible for a fixed seed): ``bitwise`` (every
    completion equals its drain-batch row), ``retraces`` (0 after
    warmup), ``refills`` (== stream - Q: every extra query rode a freed
    slot) and ``min_slot_refills``.
    """
    import time

    from repro.runtime import ServeSession, drain_reference

    eng = BSPEngine(pg)
    rng = np.random.default_rng(seed)
    stream = rng.integers(0, pg.num_vertices, size=stream_factor * q)

    # warm every compile outside the timed runs: one throwaway session
    # (chunk jit + slot swap + one refill cycle) and one drain batch
    ws = ServeSession(eng, "bfs", slots=q, chunk=chunk)
    ws.submit(np.resize(stream, 2 * q))
    ws.drain()
    drain_reference(eng, "bfs", stream[:q], q)

    # fixed-batch drain baseline: a query's latency is its batch's
    # completion time (batch-synchronous serving)
    drain_lat = []
    want = []
    t0 = time.perf_counter()
    for i in range(0, len(stream), q):
        want.append(drain_reference(eng, "bfs", stream[i:i + q], q))
        done_ms = (time.perf_counter() - t0) * 1e3
        drain_lat.extend([done_ms] * q)
    drain_wall = time.perf_counter() - t0
    want = np.concatenate(want, axis=0)

    session = ServeSession(eng, "bfs", slots=q, chunk=chunk)
    qids = session.submit(stream)
    t0 = time.perf_counter()
    rep = session.drain()
    cont_wall = time.perf_counter() - t0
    results = {r["query"]: r["result"] for r in session.poll()}
    bitwise = int(
        len(results) == len(stream)
        and all(np.array_equal(results[qid], row)
                for qid, row in zip(qids, want)))
    cont_lat = sorted(session._latency_ms.values())

    def pct(vals, p):
        return float(np.percentile(vals, p, method="nearest"))

    return dict(
        scale=scale, parts=parts, strategy=strategy, algorithm="bfs",
        combine="min", mode="continuous", block_e=None, q=q,
        stream=len(stream), chunk=chunk, v_max=pg.v_max,
        windows=rep["windows"], supersteps=rep["final_step"],
        drain_qps=len(stream) / drain_wall,
        drain_p50_ms=pct(drain_lat, 50), drain_p99_ms=pct(drain_lat, 99),
        continuous_qps=len(stream) / cont_wall,
        continuous_p50_ms=pct(cont_lat, 50),
        continuous_p99_ms=pct(cont_lat, 99),
        refills=rep["refills"],
        min_slot_refills=rep["min_slot_refills"],
        max_slot_refills=rep["max_slot_refills"],
        retraces=rep["retraces"], bitwise=bitwise)


def bench_oocore_cell(pg, scale: int, parts: int, strategy: str, seed: int,
                      block_e: int, win_blocks: int = 8,
                      backend: str = "fused", iters: int = 10) -> dict:
    """One out-of-core cell: the tiered engine (cold partitions host-resident,
    streamed through the superstep in double-buffered windows) vs the
    all-resident engine on the same partitioned graph.

    The HBM budget is *probed*: a throwaway plan with an unbounded budget
    yields the per-split byte table, and the cell pins the budget to the
    ``parts//2``-hot row — half the partitions are forced host-tier, so the
    cell always streams.  Deterministic halves gated by
    scripts/bench_check.py and asserted here: the streamed fixpoint is
    bitwise identical to the resident one for a sum-combine program
    (PageRank — the FMA/layout-sensitive case) and a min-combine one (BFS),
    arena HBM stays under the budget, and repeat runs add zero
    compile-cache entries (``retraces``).  The recorded byte fields
    (``hbm_resident_bytes``, ``host_bytes``, ``streamed_bytes_per_superstep``,
    ``window_count``) are plan-deterministic for a pinned seed.
    """
    import time

    from repro.core.partition import build_tier_plan
    from repro.algorithms.bfs import bfs_batched
    from repro.algorithms.pagerank import pagerank

    if backend == "fused":
        bkw = dict(fused=True, block_e=block_e)
    elif backend == "hybrid":
        bkw = dict(backend="hybrid", block_e=block_e)
    else:
        bkw = dict(block_e=block_e)
    probe = build_tier_plan(pg, 1 << 60, block_e=block_e,
                            win_blocks=win_blocks,
                            fused=backend != "reference")
    budget = int(probe.table[parts // 2]["hbm_bytes"])
    res_eng = BSPEngine(pg, **bkw)
    tier_eng = BSPEngine(pg, tiered=budget, win_blocks=win_blocks, **bkw)
    stats = tier_eng.tiered_stats()

    rng = np.random.default_rng(seed)
    sources = rng.integers(0, pg.num_vertices, size=4)
    ranks_res = pagerank(res_eng, iters)
    ranks_tier = pagerank(tier_eng, iters)
    lv_res, st_res = bfs_batched(res_eng, sources)
    lv_tier, st_tier = bfs_batched(tier_eng, sources)
    bitwise = bool(np.array_equal(ranks_res, ranks_tier)
                   and np.array_equal(lv_res, lv_tier)
                   and np.array_equal(st_res, st_tier))

    # warm runs above compiled every window; repeats must add no entries
    entries0 = tier_eng.tiered_cache_entries()
    pagerank(tier_eng, iters)
    bfs_batched(tier_eng, rng.integers(0, pg.num_vertices, size=4))
    retraces = tier_eng.tiered_cache_entries() - entries0

    def wall(fn, iters_=3):
        times = []
        for _ in range(iters_):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2]

    resident_s = wall(lambda: pagerank(res_eng, iters))
    tiered_s = wall(lambda: pagerank(tier_eng, iters))

    residency = tier_eng.residency_bytes()
    return dict(
        scale=scale, parts=parts, strategy=strategy, algorithm="pagerank",
        combine="sum", mode="oocore", block_e=block_e, backend=backend,
        win_blocks=win_blocks, v_max=pg.v_max,
        hbm_budget=budget, bitwise=int(bitwise), retraces=int(retraces),
        resident_ms=resident_s * 1e3, tiered_ms=tiered_s * 1e3,
        stream_penalty=tiered_s / max(resident_s, 1e-12),
        residency_hbm_bytes=int(residency["hbm_bytes"]),
        residency_host_bytes=int(residency["host_bytes"]),
        **stats)


def bench_distributed_cell(pg, scale: int, parts: int, strategy: str,
                           alg: str, n_dev: int) -> dict:
    """One multi-device cell: sharded fused vs sharded hybrid superstep,
    plus the per-superstep wire accounting (paper §3.4 aggregation-β)."""
    from repro.core.bsp import DistributedBSPEngine

    mesh = jax.make_mesh((n_dev,), ("parts",))
    fus = DistributedBSPEngine(pg, mesh, fused=True)
    hyb = DistributedBSPEngine(pg, mesh, backend="hybrid")
    program, state = _program_and_state(pg, parts, alg)

    shd, _ = hyb._hybrid_dist_for(program)
    # Independent wire accounting straight from the partition outbox maps
    # (not the engine's own counters): cross-device used slots × 4B.
    pl = parts // n_dev
    om = pg.fwd.outbox_mask
    cross_slots = int(om.sum() - sum(
        int(om[s * pl:(s + 1) * pl, s * pl:(s + 1) * pl].sum())
        for s in range(n_dev)))
    plan = hyb.hybrid_plan()
    e4 = pg.num_edges * 4.0
    rec = dict(
        scale=scale, parts=parts, strategy=strategy, algorithm=alg,
        combine=program.combine, mode="distributed", devices=n_dev,
        block_e=None, v_max=pg.v_max, o_max=pg.fwd.o_max,
        beta=pg.beta_with_reduction,
        # wire traffic per superstep, totalled over shards:
        # fused/reference exchange ships the full [pl, P, o_max] tensor;
        # the hybrid exchange ships only the used cross-device slot blocks
        # (exchanged_bytes = aggregated payload, outbox slots × 4B;
        # exchange_buffer_bytes = the shard-uniform padded SPMD buffer).
        full_exchange_bytes=int(parts * parts * pg.fwd.o_max * 4),
        exchanged_bytes=int(shd.wire_slots_used * 4),
        cross_slots_bytes=int(cross_slots * 4),
        exchange_buffer_bytes=int(n_dev * shd.wire_values_per_superstep()
                                  * 4),
        beta_slots_bytes=pg.beta_with_reduction * e4,
        beta_edges_bytes=pg.beta_no_reduction * e4,
        hybrid_k_per_shard=[r["k_dense"] for r in plan["per_shard"]],
        predicted_makespan=plan["makespan"],
        predicted_t_comm=max(r["t_comm"] for r in plan["per_shard"]),
    )
    step0 = jnp.int32(0)
    for name, eng in (("dist_fused", fus), ("dist_hybrid", hyb)):
        fn = eng.superstep(program)
        rec[f"{name}_ms"] = timeit(fn, state, step0, warmup=1, iters=5) * 1e3
    rec["dist_speedup"] = rec["dist_fused_ms"] / max(rec["dist_hybrid_ms"],
                                                     1e-12)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scales", type=int, nargs="+", default=[10, 11])
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--edge-factor", type=int, default=8)
    # 256 keeps [block_e, span] inside the VMEM budget (ops.fused_span_limit)
    # for the spans these scales produce, so every cell measures the kernel.
    ap.add_argument("--block-e", type=int, default=256)
    ap.add_argument("--out", default=str(
        Path(__file__).resolve().parents[1] / "BENCH_superstep.json"))
    ap.add_argument("--no-assert", action="store_true",
                    help="record HLO counts without failing on violations")
    ap.add_argument("--quick", action="store_true",
                    help="smallest scale only (keeps the CI job under ~5min)")
    ap.add_argument("--hybrid", action="store_true",
                    help="also time the hybrid degree-split backend")
    ap.add_argument("--batched", action="store_true",
                    help="add the query-throughput column: batched BFS at "
                         "Q in {1,8,32} vs Q sequential runs, with "
                         "amortization + retrace assertions")
    ap.add_argument("--batch-sizes", type=int, nargs="+", default=[1, 8, 32],
                    help="Q values for --batched")
    ap.add_argument("--batched-backend", default="reference",
                    choices=("reference", "fused", "hybrid"),
                    help="engine backend for the --batched column")
    ap.add_argument("--dopt", action="store_true",
                    help="add the direction-optimized traversal column "
                         "(top-down vs auto BFS, deterministic "
                         "edges-examined counters + bitwise/retrace guards)")
    ap.add_argument("--dopt-backend", default="reference",
                    choices=["reference", "fused", "hybrid"],
                    help="engine backend for the --dopt column")
    ap.add_argument("--mutations", action="store_true",
                    help="add the dynamic-graph column: in-place mutation "
                         "edges/s, incremental-vs-cold supersteps, and the "
                         "zero-retrace guard on a resident DynamicGraph")
    ap.add_argument("--mutations-backend", default="reference",
                    choices=("reference", "fused", "hybrid"),
                    help="engine backend for the --mutations column")
    ap.add_argument("--checkpoint", action="store_true",
                    help="add the fault-tolerance column: per-superstep "
                         "snapshot overhead + recovery time of the chunked "
                         "run mode, with the bitwise-resume and clean-path "
                         "zero-quarantine guards")
    ap.add_argument("--checkpoint-every", type=int, default=2,
                    help="supersteps per chunk for --checkpoint")
    ap.add_argument("--verify", action="store_true",
                    help="add the integrity column: in-loop invariant "
                         "monitor + result-certifier overhead on the "
                         "chunked run mode, with the clean-certification, "
                         "zero-monitor-fire, and <=10%% overhead guards")
    ap.add_argument("--continuous", action="store_true",
                    help="add the continuous-batching column: resident-"
                         "session q/s and p99-under-load vs fixed-batch "
                         "drain at the same Q, with the bitwise-parity, "
                         "zero-retrace and refill-count guards")
    ap.add_argument("--oocore", action="store_true",
                    help="add the out-of-core column: tiered engine with a "
                         "probed HBM budget forcing half the partitions "
                         "host-tier vs the all-resident engine, with the "
                         "bitwise-parity, under-budget and zero-retrace "
                         "guards")
    ap.add_argument("--oocore-backend", default="fused",
                    choices=("reference", "fused", "hybrid"),
                    help="engine backend for the --oocore column")
    ap.add_argument("--win-blocks", type=int, default=8,
                    help="double-buffered window size (edge blocks) for the "
                         "--oocore column")
    ap.add_argument("--distributed", action="store_true",
                    help="add multi-device cells (sharded fused vs sharded "
                         "hybrid + exchanged-bytes accounting)")
    ap.add_argument("--devices", type=int, default=8,
                    help="forced host device count for --distributed")
    ap.add_argument("--seed", type=int, default=1,
                    help="RMAT topology seed (pinned for reproducible cells)")
    args = ap.parse_args(argv)
    if args.quick:
        args.scales = [min(args.scales)]

    enable_compile_cache()
    if args.distributed and len(jax.devices()) < args.devices:
        # Forced host devices must be set before JAX starts, by the caller's
        # environment (see the bench-dist Makefile target); this process
        # already holds its devices, so it never re-executes itself.
        print(f"--distributed needs >= {args.devices} devices but JAX sees "
              f"{len(jax.devices())} ({jax.default_backend()}); on CPU run "
              f"with XLA_FLAGS=--xla_force_host_platform_device_count="
              f"{args.devices}, or pass fewer --devices", file=sys.stderr)
        return 2

    results = []
    failures = []
    for scale in args.scales:
        g = G.rmat(scale, args.edge_factor, seed=args.seed)
        # distributed cells need num_parts % devices == 0
        parts_dist = (args.parts if args.parts % args.devices == 0
                      else args.devices)
        for strategy in PT.STRATEGIES:
            pg = PT.partition(g, args.parts, strategy)
            pg_dist = None
            if args.distributed:
                pg_dist = (pg if parts_dist == args.parts
                           else PT.partition(g, parts_dist, strategy))
            for alg in ("pagerank", "bfs"):
                rec = bench_cell(pg, scale, args.parts, strategy, alg,
                                 args.block_e, hybrid=args.hybrid)
                results.append(rec)
                if args.distributed:
                    drec = bench_distributed_cell(pg_dist, scale, parts_dist,
                                                  strategy, alg, args.devices)
                    results.append(drec)
                    print(f"scale={scale} {strategy:>4} {alg:>8} "
                          f"[{args.devices}dev]: "
                          f"fused={drec['dist_fused_ms']:.2f}ms "
                          f"hybrid={drec['dist_hybrid_ms']:.2f}ms "
                          f"wire={drec['exchanged_bytes']}B "
                          f"(buf={drec['exchange_buffer_bytes']}B, "
                          f"full={drec['full_exchange_bytes']}B, "
                          f"β·E·4={drec['beta_slots_bytes']:.0f}B) "
                          f"k={drec['hybrid_k_per_shard']}", flush=True)
                    # §3.4 claim: the aggregated exchange payload must stay
                    # within β_with_reduction·|E|·4 — wire traffic scales
                    # with unique boundary pairs, not per-edge messages.
                    # The falsifiable half: the engine's own slot counter
                    # must match the cross-device slot count derived
                    # independently from the partition outbox maps — an
                    # engine regression that shipped per-edge values (or
                    # dropped slots) breaks the equality.
                    if drec["exchanged_bytes"] != drec["cross_slots_bytes"]:
                        failures.append(
                            f"exchange payload ({drec['exchanged_bytes']}B) "
                            f"!= cross-device outbox slots × 4B "
                            f"({drec['cross_slots_bytes']}B) in "
                            f"{strategy}/{alg} — source-side aggregation "
                            f"is no longer slot-exact")
                    if drec["exchanged_bytes"] > drec["beta_slots_bytes"]:
                        failures.append(
                            f"exchange payload ({drec['exchanged_bytes']}B) "
                            f"exceeds the aggregation bound "
                            f"(beta_wr*E*4={drec['beta_slots_bytes']:.0f}B) "
                            f"in {strategy}/{alg}")
                    if (drec["exchange_buffer_bytes"]
                            >= drec["full_exchange_bytes"]):
                        failures.append(
                            f"compact exchange buffer not smaller than the "
                            f"full outbox tensor in {strategy}/{alg}: {drec}")
                print(f"scale={scale} {strategy:>4} {alg:>8}: "
                      f"ref={rec['ref_ms']:.2f}ms fused={rec['fused_ms']:.2f}ms "
                      f"({rec['speedup']:.2f}x) span={rec['span']} "
                      f"active={rec['fused_active']} "
                      f"msg_arrays ref={rec['ref_hlo_msg_arrays']} "
                      f"fused={rec['fused_hlo_msg_arrays']}", flush=True)
                # Structural claim: when the kernel is active it never
                # materializes the message array; the reference always does
                # (it's the array being eliminated).
                if rec["fused_active"] and rec["fused_hlo_msg_arrays"] != 0:
                    failures.append(f"fused HLO materializes [Pl, e_max] f32 "
                                    f"arrays in {rec}")
                if rec["ref_hlo_msg_arrays"] == 0:
                    failures.append(f"reference HLO unexpectedly clean "
                                    f"(check the detector) in {rec}")
            if args.dopt:
                drec = bench_dopt_cell(g, pg, scale, args.parts, strategy,
                                       args.seed, backend=args.dopt_backend,
                                       block_e=args.block_e)
                results.append(drec)
                print(f"scale={scale} {strategy:>4} dopt"
                      f"[{drec['backend']}]: topdown "
                      f"{drec['topdown_ms']:.1f}ms/"
                      f"{drec['topdown_edges']}e vs dopt "
                      f"{drec['dopt_ms']:.1f}ms/{drec['dopt_edges']}e "
                      f"(saved {drec['edges_saved_ratio']:.1%}, "
                      f"switches={drec['dopt_switches']}, "
                      f"retraces={drec['retraces']}, "
                      f"bitwise={drec['bitwise']})", flush=True)
                # Direction-optimization contract, all halves deterministic
                # (the counters are superstep-indexed int32 sums — no
                # timing noise): auto must beat top-down on examined edges
                # via at least one real switch, stay bitwise-identical to
                # the numpy oracle, respect the once-per-edge push bound,
                # and never retrace across a switch.
                if not drec["bitwise"]:
                    failures.append(
                        f"dopt {strategy}: push/auto BFS diverged from the "
                        f"reference fixpoint — direction is no longer a "
                        f"pure performance choice")
                if drec["retraces"] != 0:
                    failures.append(
                        f"dopt {strategy}: {drec['retraces']} compile-cache "
                        f"entries added across direction switches — "
                        f"direction is no longer traced-carry data")
                if drec["dopt_edges"] >= drec["topdown_edges"]:
                    failures.append(
                        f"dopt {strategy}: auto examined "
                        f"{drec['dopt_edges']} edges, not fewer than "
                        f"top-down's {drec['topdown_edges']} — the fitted "
                        f"crossover no longer wins on the scale-free graph")
                if drec["dopt_switches"] == 0:
                    failures.append(
                        f"dopt {strategy}: auto never left push on the "
                        f"scale-free graph (0 switches)")
                if drec["topdown_switches"] != 0:
                    failures.append(
                        f"dopt {strategy}: forced push reported "
                        f"{drec['topdown_switches']} switches")
                if drec["topdown_edges"] > drec["edges_bound"]:
                    failures.append(
                        f"dopt {strategy}: top-down examined "
                        f"{drec['topdown_edges']} edges, above the "
                        f"once-per-edge bound {drec['edges_bound']} — the "
                        f"push counter is over-charging")
            if args.mutations:
                mrec = bench_mutations_cell(g, scale, args.parts, strategy,
                                            args.seed,
                                            backend=args.mutations_backend,
                                            block_e=args.block_e)
                results.append(mrec)
                print(f"scale={scale} {strategy:>4} mutations: "
                      f"{mrec['mutation_edges_per_sec']:.0f} edges/s "
                      f"applied ({mrec['apply_ms_per_batch']:.1f} ms/batch "
                      f"of {mrec['mutation_batch']}), incremental "
                      f"{mrec['incremental_steps']} vs cold "
                      f"{mrec['cold_steps']} supersteps, "
                      f"retraces={mrec['retraces']} "
                      f"compactions={mrec['compactions']}", flush=True)
                # Dynamic contract, deterministic halves: mutation batches
                # must reuse the compiled loops (no compaction and no
                # spare-ELL-overflow split rebuild => no cache growth),
                # warm starts must be bitwise-exact and never run MORE
                # supersteps than cold recomputes.
                if (mrec["compactions"] == 0
                        and mrec["hybrid_rebuilds"] == 0
                        and mrec["retraces"] != 0):
                    failures.append(
                        f"mutations {strategy}: {mrec['retraces']} "
                        f"compile-cache entries added across mutation "
                        f"batches — the dynamic payload is no longer "
                        f"shape-stable")
                if not mrec["warm_bitwise_equal"]:
                    failures.append(
                        f"mutations {strategy}: warm-start BFS diverged "
                        f"from the cold rerun (monotone window)")
                if mrec["incremental_steps"] > mrec["cold_steps"]:
                    failures.append(
                        f"mutations {strategy}: incremental refresh ran "
                        f"{mrec['incremental_steps']} supersteps, more "
                        f"than cold {mrec['cold_steps']}")
            if args.oocore:
                orec = bench_oocore_cell(pg, scale, args.parts, strategy,
                                         args.seed, args.block_e,
                                         win_blocks=args.win_blocks,
                                         backend=args.oocore_backend)
                results.append(orec)
                print(f"scale={scale} {strategy:>4} oocore: "
                      f"hbm={orec['hbm_resident_bytes']}B "
                      f"(budget {orec['hbm_budget']}B) "
                      f"host={orec['host_bytes']}B, streams "
                      f"{orec['streamed_bytes_per_superstep']}B/superstep "
                      f"over {orec['window_count']} windows "
                      f"({orec['num_hot']} hot/{orec['num_cold']} cold); "
                      f"tiered {orec['tiered_ms']:.1f} vs resident "
                      f"{orec['resident_ms']:.1f} ms "
                      f"({orec['stream_penalty']:.2f}x), "
                      f"bitwise={orec['bitwise']} "
                      f"retraces={orec['retraces']}", flush=True)
                # Out-of-core contract, deterministic halves: the streamed
                # fixpoint is bitwise identical to the resident one, the
                # arena stays under the forced budget, the cell genuinely
                # streams (>= 1 host-tier partition), and steady-state
                # repeats add no compile-cache entries.
                if not orec["bitwise"]:
                    failures.append(
                        f"oocore {strategy}: streamed fixpoint diverged "
                        f"from the resident engine (PageRank/BFS bitwise)")
                if orec["hbm_resident_bytes"] > orec["hbm_budget"]:
                    failures.append(
                        f"oocore {strategy}: arena hbm "
                        f"{orec['hbm_resident_bytes']}B exceeds the "
                        f"budget {orec['hbm_budget']}B")
                if orec["num_cold"] < 1:
                    failures.append(
                        f"oocore {strategy}: no host-tier partitions — "
                        f"the cell never streamed")
                if orec["retraces"] != 0:
                    failures.append(
                        f"oocore {strategy}: {orec['retraces']} "
                        f"compile-cache entries added across repeat runs "
                        f"— the window schedule is no longer shape-stable")
            if args.checkpoint:
                crec = bench_checkpoint_cell(pg, scale, args.parts, strategy,
                                             args.seed,
                                             chunk=args.checkpoint_every)
                results.append(crec)
                print(f"scale={scale} {strategy:>4} checkpoint: "
                      f"{crec['ckpt_ms_per_superstep']:.2f} ms/superstep "
                      f"snapshot overhead ({crec['snapshots']} snapshots, "
                      f"{crec['ckpt_overhead_ratio']:.2f}x bare chunked), "
                      f"recovery {crec['recovery_ms']:.0f} ms, "
                      f"resume_bitwise={crec['resume_bitwise']} "
                      f"quarantined={crec['quarantined']} "
                      f"chunk_retraces={crec['chunk_retraces']}", flush=True)
                # Fault-tolerance contract, deterministic halves: the
                # resumed fixpoint is bitwise identical to the resident
                # loop, chunk windows reuse one compile, and nothing is
                # quarantined on a clean run.
                if not crec["resume_bitwise"]:
                    failures.append(
                        f"checkpoint {strategy}: resumed fixpoint is not "
                        f"bitwise identical to the resident while_loop")
                if crec["quarantined"] != 0:
                    failures.append(
                        f"checkpoint {strategy}: {crec['quarantined']} "
                        f"queries quarantined on the clean path")
                if crec["chunk_retraces"] != 0:
                    failures.append(
                        f"checkpoint {strategy}: chunked windows retraced "
                        f"{crec['chunk_retraces']}x after warmup")
            if args.verify:
                vrec = bench_verify_cell(g, pg, scale, args.parts, strategy,
                                         args.seed,
                                         chunk=args.checkpoint_every)
                results.append(vrec)
                print(f"scale={scale} {strategy:>4} verify: "
                      f"certify {vrec['certify_ms']:.2f} ms "
                      f"({vrec['certify_ms_per_query']:.2f} ms/query), "
                      f"monitor {vrec['monitor_ms']:.2f} ms, "
                      f"overhead {vrec['verify_overhead_ratio']:.3f}x "
                      f"bare chunked ({vrec['chunked_ms']:.2f} ms); "
                      f"certified {vrec['certified_ok']}/{vrec['q']} "
                      f"monitors_fired={vrec['monitors_fired']}", flush=True)
                # Integrity contract: clean fixpoints certify, monitors
                # never fire on a clean run, and the whole defense stays
                # within 10% of the bare chunked window.
                if vrec["certified_ok"] != vrec["q"]:
                    failures.append(
                        f"verify {strategy}: "
                        f"{vrec['q'] - vrec['certified_ok']} clean "
                        f"fixpoints failed certification "
                        f"({vrec['certify_failed']})")
                if vrec["monitors_fired"] != 0:
                    failures.append(
                        f"verify {strategy}: {vrec['monitors_fired']} "
                        f"invariant monitors fired on a clean run")
                if vrec["verify_overhead_ratio"] > 0.10:
                    failures.append(
                        f"verify {strategy}: monitor+certifier overhead "
                        f"{vrec['verify_overhead_ratio']:.3f}x exceeds the "
                        f"0.10x bare-chunked contract")
            if args.continuous:
                srec = bench_continuous_cell(pg, scale, args.parts, strategy,
                                             args.seed,
                                             chunk=args.checkpoint_every)
                results.append(srec)
                print(f"scale={scale} {strategy:>4} continuous: "
                      f"{srec['continuous_qps']:.0f} q/s vs drain "
                      f"{srec['drain_qps']:.0f} q/s; p99 "
                      f"{srec['continuous_p99_ms']:.0f} vs "
                      f"{srec['drain_p99_ms']:.0f} ms; "
                      f"refills={srec['refills']} "
                      f"(min/slot={srec['min_slot_refills']}), "
                      f"retraces={srec['retraces']} "
                      f"bitwise={srec['bitwise']}", flush=True)
                # Continuous-batching contract, deterministic halves
                # (refill decisions are superstep-indexed, so they are
                # reproducible; CPU timing is noisy and only recorded):
                # every completion bitwise equals drain-batch, every
                # extra query rode a freed slot, slots actually cycled,
                # and nothing retraced after warmup.
                if not srec["bitwise"]:
                    failures.append(
                        f"continuous {strategy}: completions diverge from "
                        f"drain-batch run_batched")
                if srec["retraces"] != 0:
                    failures.append(
                        f"continuous {strategy}: {srec['retraces']} "
                        f"compile-cache entries added across refill "
                        f"cycles — the slot swap is no longer "
                        f"shape-stable")
                if srec["refills"] != srec["stream"] - srec["q"]:
                    failures.append(
                        f"continuous {strategy}: {srec['refills']} refills "
                        f"for a {srec['stream']}-query stream over "
                        f"{srec['q']} slots — freed slots are not being "
                        f"refilled")
                if srec["min_slot_refills"] < 3:
                    failures.append(
                        f"continuous {strategy}: a slot was refilled only "
                        f"{srec['min_slot_refills']}x over a "
                        f"{srec['stream'] // srec['q']}x-Q stream — "
                        f"refill is not reaching every slot")
            if args.batched:
                for q in args.batch_sizes:
                    brec = bench_batched_cell(pg, scale, args.parts,
                                              strategy, q, args.block_e,
                                              args.seed,
                                              backend=args.batched_backend)
                    results.append(brec)
                    print(f"scale={scale} {strategy:>4} batched[Q={q:>2}]: "
                          f"{brec['batched_ms']:.1f}ms/batch "
                          f"{brec['batched_ms_per_query']:.2f}ms/q vs seq "
                          f"{brec['seq_ms_per_query']:.2f}ms/q "
                          f"(amortization {brec['amortization']:.2f}x, "
                          f"{brec['queries_per_sec']:.0f} q/s, "
                          f"retraces={brec['retraces']})", flush=True)
                    # Serving contract, deterministic half: same-Q batches
                    # with different sources share one compiled while_loop
                    # (the compile-cache-hit assertion; holds everywhere).
                    if brec["retraces"] != 0:
                        failures.append(
                            f"batched Q={q} {strategy} retraced the "
                            f"compiled loop {brec['retraces']}x — the "
                            f"query batch is no longer shape-stable")
                    # Throughput half: on a real accelerator one while_loop
                    # dispatch + one kernel-launch sequence replace Q of
                    # each, so Q >= 8 must amortize strictly below the
                    # sequential per-query time.  Interpret-mode CPU
                    # executes Q× Pallas grid cells in Python and scales
                    # compute linearly, inverting the ratio (see module
                    # docstring) — there the field is baseline-gated by
                    # bench_check instead of absolutely asserted.
                    if (jax.default_backend() == "tpu" and q >= 8
                            and brec["amortization"] <= 1.0):
                        failures.append(
                            f"batched Q={q} {strategy} amortized "
                            f"{brec['batched_ms_per_query']:.2f}ms/query, "
                            f"not below sequential "
                            f"{brec['seq_ms_per_query']:.2f}ms/query")

    out = dict(backend=jax.default_backend(),
               interpret=jax.default_backend() != "tpu",
               block_e=args.block_e, parts=args.parts,
               edge_factor=args.edge_factor, seed=args.seed,
               devices=(args.devices if args.distributed else 1),
               results=results)
    Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {args.out} ({len(results)} cells)")
    if failures and not args.no_assert:
        for f in failures:
            print("FAIL:", f, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
