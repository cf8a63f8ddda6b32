"""Batched graph query-serving driver — the throughput face of the engine.

The paper evaluates BFS/SSSP/BC "for a single source" (Table 4); a serving
deployment instead amortizes **one resident partitioned graph** across many
concurrent queries.  This driver is that regime end to end:

  1. load a synthetic workload (RMAT / uniform, the paper's Table 2
     generators) and partition it once;
  2. build one engine (reference / fused / hybrid backend) — the graph
     topology, block metadata, and degree splits stay device-resident for
     the whole run;
  3. drain a synthetic query stream in fixed-size batches of Q sources:
     every batch runs through **one** compiled ``lax.while_loop``
     (``BSPEngine.run_batched``), so per-query cost amortizes the dispatch,
     kernel-launch, and graph-residency overheads Q ways;
  4. report queries/sec, per-query latency percentiles (a query's latency
     is its batch's wall time — batch-synchronous serving), the amortized
     per-query time, and the engine's compile-cache growth across batches
     (0 retraces after warmup is the serving contract).

  PYTHONPATH=src python -m repro.launch.graph_serve \
      [--scale 12] [--parts 4] [--alg bfs] [--batch 32] \
      [--num-queries 256] [--backend fused] [--out serve_report.json]

``--smoke`` shrinks everything for CI.  The first batch per algorithm pays
compilation and is reported separately (``cold_ms``); steady-state numbers
exclude it.

Serving modes compose through a validated :class:`ServeConfig` (built from
the CLI flags; incompatible combinations fail fast with the flag to add).
``--continuous`` swaps drain-batch scheduling for a resident
:class:`~repro.runtime.session.ServeSession`: converged query slots are
compacted out and refilled from the admission queue at chunk boundaries
inside one compiled loop (zero retraces), and the report compares
``continuous_qps``/p99 against the drain-batch baseline on the same
stream.  ``--mutate`` and ``--depth-buckets`` compose with it; see
``docs/serving.md`` for the slot lifecycle and the API migration table.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Optional

import numpy as np

from repro.compile_cache import enable_compile_cache


def _percentile(vals, p: float) -> float:
    if not len(vals):
        return float("nan")
    return float(np.percentile(vals, p, method="nearest"))


@dataclasses.dataclass
class ServeConfig:
    """The validated serving-mode surface — one place for the flags that
    used to sprawl across ``main()``'s dispatch order.

    The old dispatch silently ignored combinations (``--chaos`` dropped
    ``--depth-buckets``; ``--mutate`` dropped ``--deadline-ms``/
    ``--queue-capacity``); :meth:`validate` makes every incompatible pair
    an actionable error instead, and names the spelling that *does*
    compose (usually ``--continuous``, whose :class:`ServeSession` takes
    the other knobs as options).
    """
    alg: str = "bfs"
    batch: int = 32
    mutate: bool = False
    chaos: bool = False
    corrupt: bool = False
    continuous: bool = False
    depth_buckets: int = 0
    deadline_ms: Optional[float] = None
    queue_capacity: Optional[int] = None
    chunk: int = 2

    @classmethod
    def from_args(cls, args) -> "ServeConfig":
        return cls(alg=args.alg, batch=args.batch, mutate=args.mutate,
                   chaos=args.chaos, corrupt=args.corrupt,
                   continuous=args.continuous,
                   depth_buckets=args.depth_buckets,
                   deadline_ms=args.deadline_ms,
                   queue_capacity=args.queue_capacity,
                   chunk=args.checkpoint_every).validate()

    def validate(self) -> "ServeConfig":
        def bad(combo: str, why: str, instead: str):
            raise ValueError(f"incompatible serving flags: {combo} — {why}. "
                             f"{instead}")

        if self.chaos:
            for name, on in (("--mutate", self.mutate),
                             ("--continuous", self.continuous),
                             ("--depth-buckets", bool(self.depth_buckets)),
                             ("--deadline-ms", self.deadline_ms is not None),
                             ("--queue-capacity",
                              self.queue_capacity is not None)):
                if on:
                    bad(f"--chaos + {name}",
                        "the chaos drill is a self-contained mutating "
                        "session with its own injection schedule",
                        "Run --chaos alone; fault tolerance for continuous "
                        "sessions is serve_with_restarts (see "
                        "tests/test_continuous.py).")
        if self.corrupt:
            if self.chaos:
                bad("--chaos + --corrupt",
                    "the drills have disjoint injection schedules (worker "
                    "faults vs silent bit-flips)",
                    "Run them as two invocations — CI does.")
            for name, on in (("--mutate", self.mutate),
                             ("--continuous", self.continuous),
                             ("--depth-buckets", bool(self.depth_buckets)),
                             ("--deadline-ms", self.deadline_ms is not None),
                             ("--queue-capacity",
                              self.queue_capacity is not None)):
                if on:
                    bad(f"--corrupt + {name}",
                        "the corruption drill runs its own sessions across "
                        "all three backends with a fixed injection schedule",
                        "Run --corrupt alone; certification in production "
                        "sessions is ServeSession(certifier=..., "
                        "monitor=...) — see docs/robustness.md.")
            if self.alg not in ("bfs", "sssp"):
                raise ValueError(
                    f"--corrupt drills the continuous-session certification "
                    f"path, which serves step-translatable programs only "
                    f"(bfs, sssp), not {self.alg!r}.")
        if self.continuous and self.alg not in ("bfs", "sssp"):
            raise ValueError(
                f"--continuous serves step-translatable programs only "
                f"(bfs, sssp), not {self.alg!r}: slot refill re-seeds a "
                f"query mid-loop in the global step frame "
                f"(algorithms/continuous.py).  Drop --continuous to "
                f"drain-batch {self.alg!r}.")
        if not self.continuous:
            if self.mutate:
                for name, on in (("--depth-buckets",
                                  bool(self.depth_buckets)),
                                 ("--deadline-ms",
                                  self.deadline_ms is not None),
                                 ("--queue-capacity",
                                  self.queue_capacity is not None)):
                    if on:
                        bad(f"--mutate + {name}",
                            "the drain-batch mutating driver has no "
                            "admission queue or scheduler",
                            "Add --continuous: ServeSession composes "
                            "mutations with deadlines, admission and the "
                            "depth scheduler in one resident engine.")
            elif self.depth_buckets:
                for name, on in (("--deadline-ms",
                                  self.deadline_ms is not None),
                                 ("--queue-capacity",
                                  self.queue_capacity is not None)):
                    if on:
                        bad(f"--depth-buckets + {name}",
                            "the bucketed A/B driver re-runs the stream "
                            "twice and reports buckets, not SLA",
                            "Add --continuous to schedule depth-first "
                            "under a deadline, or drop --depth-buckets.")
        return self

    @property
    def mode(self) -> str:
        if self.chaos:
            return "chaos"
        if self.corrupt:
            return "corrupt"
        if self.continuous:
            return "continuous"
        if self.mutate:
            return "mutate"
        if self.depth_buckets:
            return "depth"
        return "drain"


def run_query_batch(engine, alg: str, sources: np.ndarray) -> np.ndarray:
    """Dispatch one batch of queries; returns the [Q, n] result block."""
    from repro.algorithms import (betweenness_centrality_batched,
                                  bfs_batched, personalized_pagerank,
                                  sssp_batched)

    if alg == "bfs":
        return bfs_batched(engine, sources)[0]
    if alg == "sssp":
        return sssp_batched(engine, sources)[0]
    if alg == "bc":
        return betweenness_centrality_batched(engine, sources)[0]
    if alg == "ppr":
        return personalized_pagerank(engine, sources, num_iterations=10)
    raise ValueError(f"unknown algorithm {alg!r}")


def serve(engine, alg: str, sources: np.ndarray, batch: int,
          check_fn=None, deadline_ms=None, queue_capacity=None) -> dict:
    """Drain ``sources`` in batches of ``batch``; returns the metrics dict.

    ``check_fn(sources, results)`` optionally validates a batch (the
    selftest hook).  The query stream is padded to a whole number of
    batches with repeats of its head so every batch compiles to the same Q.

    ``queue_capacity`` bounds admission: sources beyond it are rejected
    with a reason (``report["admission"]``) instead of growing tail
    latency.  ``deadline_ms`` is a per-query SLA: a query's latency is its
    queue wait plus its batch's wall time (batch-synchronous serving);
    queries over deadline are counted in ``report["sla"]``.
    """
    admission = None
    if queue_capacity is not None:
        from repro.runtime import AdmissionController
        ctl = AdmissionController(queue_capacity)
        for s in np.asarray(sources).reshape(-1):
            ctl.offer(int(s), deadline_ms)
        sources = np.asarray(ctl.take(len(ctl)))
        admission = dict(capacity=queue_capacity, admitted=ctl.admitted,
                         rejected=len(ctl.rejected),
                         reject_reasons=sorted(
                             {r["reason"] for r in ctl.rejected}))
    num = len(sources)
    pad = (-num) % batch
    # np.resize repeats the stream cyclically, so padding works even when
    # pad > num (a stream shorter than one batch).
    stream = np.resize(sources, num + pad)
    batches = stream.reshape(-1, batch)

    tiered = getattr(engine, "tier_plan", None) is not None
    cache_fn = type(engine)._run_batched
    entries0 = None
    lat_ms, cold_ms = [], None
    batch_done_ms = []                  # cumulative wall at batch completion
    served = 0
    t_all = time.perf_counter()
    for i, srcs in enumerate(batches):
        t0 = time.perf_counter()
        out = run_query_batch(engine, alg, srcs)
        dt = (time.perf_counter() - t0) * 1e3
        batch_done_ms.append((time.perf_counter() - t_all) * 1e3)
        if i == 0:
            cold_ms = dt               # includes compilation
            if tiered:                 # streamed path: its own jit set
                entries0 = engine.tiered_cache_entries()
            else:
                try:
                    entries0 = cache_fn._cache_size()
                except AttributeError:  # non-jitted run_batched (distributed)
                    entries0 = None
        else:
            lat_ms.append(dt)
        served += batch
        if check_fn is not None:
            check_fn(srcs, out)
    wall_s = time.perf_counter() - t_all

    retraces = 0
    if entries0 is not None:
        cur = (engine.tiered_cache_entries() if tiered
               else cache_fn._cache_size())
        retraces = cur - entries0

    warm_s = sum(lat_ms) / 1e3
    warm_queries = max(served - batch, 0)
    report = dict(
        algorithm=alg, batch=batch, num_queries=num,
        batches=len(batches), cold_ms=cold_ms,
        queries_per_sec=(warm_queries / warm_s) if warm_s > 0 else None,
        ms_per_query=(warm_s * 1e3 / warm_queries) if warm_queries else None,
        batch_p50_ms=_percentile(lat_ms, 50),
        batch_p90_ms=_percentile(lat_ms, 90),
        batch_p99_ms=_percentile(lat_ms, 99),
        wall_s=wall_s,
        # compiled-loop reuse across batches: 0 == no per-batch retrace
        retraces=retraces,
        backend=getattr(engine, "backend", None),
        engine=type(engine).__name__,
    )
    if admission is not None:
        report["admission"] = admission
    if deadline_ms is not None:
        # query i rides batch i // batch; its latency is that batch's
        # completion time (queue wait included)
        lat_q = np.asarray(batch_done_ms)[
            np.arange(num) // batch] if num else np.zeros(0)
        misses = int((lat_q > deadline_ms).sum())
        report["sla"] = dict(deadline_ms=deadline_ms, misses=misses,
                             met=num - misses)
    return report


def build_engine(args, dynamic: bool = False):
    from repro.core import graph as G
    from repro.core import partition as PT
    from repro.core.bsp import BSPEngine
    from repro.core.dynamic import DynamicGraph

    gen = G.rmat if args.graph == "rmat" else G.uniform
    g = gen(args.scale, args.edge_factor, seed=args.seed)
    if args.alg == "sssp":
        g = g.with_uniform_weights(seed=args.seed + 1)
    kw = {}
    if args.backend == "fused":
        kw = dict(fused=True, block_e=args.block_e)
    elif args.backend == "hybrid":
        kw = dict(backend="hybrid")
    if getattr(args, "hbm_budget", None) is not None:
        kw["tiered"] = args.hbm_budget
        kw["win_blocks"] = args.win_blocks
        kw.setdefault("block_e", args.block_e)
    if dynamic:
        dg = DynamicGraph(g, args.parts, args.strategy,
                          include_reverse=(args.alg == "bc"),
                          mutation_capacity=args.mutation_batch)
        return g, dg, BSPEngine(dg, **kw)
    pg = PT.partition(g, args.parts, args.strategy,
                      include_reverse=(args.alg == "bc"))
    return g, pg, BSPEngine(pg, **kw)


def estimate_depth_order(g, sources: np.ndarray) -> np.ndarray:
    """Order ``sources`` by estimated traversal depth, shallow first.

    A batch runs ``max_q(steps_q)`` supersteps, so one deep query taxes
    every shallow query sharing its batch.  The proxy: BFS from a hub
    reaches the massive component in few levels, BFS from a fringe vertex
    walks long chains first — out-degree (cheap, already resident) orders
    hubs before fringe.  Returns indices into ``sources``.
    """
    deg = g.out_degrees()[np.asarray(sources)]
    return np.argsort(-deg, kind="stable")


def serve_depth_bucketed(engine, g, alg: str, sources: np.ndarray,
                         batch: int, num_buckets: int = 4) -> dict:
    """Depth-bucketing scheduler: drain the stream in estimated-depth order
    so shallow queries never ride a deep query's superstep count.

    Runs the same stream twice — arrival order (baseline: batches mix
    depths) and depth-bucketed — and reports per-bucket p50/p99 per-query
    latency for both (a query's latency is its batch's wall time).  The
    shallow buckets' p99 is the win; the deep buckets pay what they always
    paid.
    """
    order = estimate_depth_order(g, sources)
    num = len(sources)
    num_buckets = max(1, min(num_buckets, num))  # every bucket non-empty
    bucket_of = np.empty(num, dtype=np.int64)   # by stream position
    for b in range(num_buckets):
        lo = b * num // num_buckets
        hi = (b + 1) * num // num_buckets
        bucket_of[order[lo:hi]] = b

    run_query_batch(engine, alg, np.asarray(sources[:batch]))  # warm compile

    def drain(stream_idx):
        lat = np.empty(num, dtype=np.float64)
        for i in range(0, num, batch):
            idx = stream_idx[i: i + batch]
            srcs = np.asarray(sources)[idx]
            if len(srcs) < batch:                 # pad the tail batch
                srcs = np.resize(srcs, batch)
            t0 = time.perf_counter()
            run_query_batch(engine, alg, srcs)
            lat[idx] = (time.perf_counter() - t0) * 1e3
        return lat

    lat_base = drain(np.arange(num))              # arrival order (mixed)
    lat_buck = drain(order)                       # depth-homogeneous batches
    buckets = []
    for b in range(num_buckets):
        m = bucket_of == b
        buckets.append(dict(
            bucket=b, queries=int(m.sum()),
            min_degree=int(g.out_degrees()[sources[m]].min()),
            baseline_p50_ms=_percentile(lat_base[m], 50),
            baseline_p99_ms=_percentile(lat_base[m], 99),
            bucketed_p50_ms=_percentile(lat_buck[m], 50),
            bucketed_p99_ms=_percentile(lat_buck[m], 99)))
    return dict(num_buckets=num_buckets, batch=batch,
                baseline_p99_ms=_percentile(lat_base, 99),
                bucketed_p99_ms=_percentile(lat_buck, 99),
                buckets=buckets)


def refresh_standing(engine, dg, alg: str, sources, prev, mark) -> dict:
    """Refresh a standing query set after mutations: warm-start when the
    window allows (monotone program + insert-only batches), cold otherwise.
    Runs the cold path too, so the report can state the superstep savings
    honestly.  Returns the new results + metrics.
    """
    from repro.algorithms import (bfs_batched, bfs_incremental, sssp_batched,
                                  sssp_incremental)

    dirty, monotone = dg.dirty_since(mark)
    incremental = {"bfs": (bfs_incremental, bfs_batched),
                   "sssp": (sssp_incremental, sssp_batched)}.get(alg)
    cold_fn = (incremental[1] if incremental
               else (lambda e, s: (run_query_batch(e, alg, s), None)))
    t0 = time.perf_counter()
    cold_out = cold_fn(engine, sources)
    cold_ms = (time.perf_counter() - t0) * 1e3
    cold_res, cold_steps = cold_out if incremental else (cold_out[0], None)
    rec = dict(mode="cold", cold_ms=cold_ms,
               cold_steps=(None if cold_steps is None
                           else [int(s) for s in cold_steps]))
    result = cold_res
    if incremental is not None and monotone:
        warm_fn = incremental[0]
        t0 = time.perf_counter()
        warm_res, warm_steps = warm_fn(engine, prev, dirty)
        rec.update(mode="incremental", warm_ms=(time.perf_counter() - t0)
                   * 1e3, warm_steps=[int(s) for s in warm_steps],
                   bitwise_equal=bool(np.array_equal(warm_res, cold_res)))
        result = warm_res
    return dict(rec, result=result)


def serve_mutating(engine, dg, alg: str, *, batches, batch: int,
                   standing: int, query_batches_per_round: int,
                   seed: int = 1, compact: bool = True,
                   skew_drift_threshold: float = 0.5,
                   resplit_threshold: float = 0.10) -> dict:
    """Interleave mutation batches with query batches against the resident
    graph — the evolving-graph serving regime end to end.

    Per round: one mutation batch is applied in place (edges/s), fresh
    random queries are served cold, and a *standing* query set is kept
    fresh — warm-started from its previous fixpoint when the window is
    monotone, recomputed cold otherwise — under the zero-retrace contract
    (the dynamic runner's jit cache must not grow after warmup; a
    compaction pause is the one excepted, separately-reported event).
    Compactions trigger on the staleness signals (including degree-skew
    drift at ``skew_drift_threshold``) or, on the hybrid backend, on
    ``engine.should_resplit_hybrid`` — the ``perf_model.should_resplit``
    vote that the drifted degree ranking beats the frozen split's
    predicted makespan by ``resplit_threshold``.
    """
    from repro.core import bsp

    rng = np.random.default_rng(seed)
    n = dg.pg.num_vertices
    standing_sources = rng.integers(0, n, size=standing)

    # warm-up: compile the cold path + serve loop before timing
    prev = run_query_batch(engine, alg, standing_sources)
    mark = dg.mark()
    cache_fns = [bsp._run_dyn_jit, bsp._run_dyn_hybrid_jit]

    def cache_entries():
        return sum(f._cache_size() for f in cache_fns)

    rounds, lat_ms = [], []
    mut_edges = mut_s = 0.0
    compact_ms = 0.0
    resplits = 0
    warm_steps_all, cold_steps_all = [], []
    retraces = 0
    entries_prev = rebinds_prev = rebuilds_prev = None
    warm_versions = set()     # graph versions whose warm path has compiled
    t_all = time.perf_counter()
    for i, mb in enumerate(batches):
        rep = dg.apply_mutations(mb)
        mut_edges += rep["num_edges"]
        mut_s += rep["apply_ms"] / 1e3
        if rep["compacted"]:
            # capacity-overflow auto-compaction inside apply_mutations —
            # --no-compact only disables the *threshold-driven* kind
            compact_ms += dg.last_compaction_ms
        if compact and dg.should_compact(
                max_skew_drift=skew_drift_threshold):
            compact_ms += dg.compact()
        elif compact and engine.should_resplit_hybrid(resplit_threshold):
            # re-ranking the degree split rides a compaction: the rebind
            # re-runs the perf-model plan on the mutated graph
            compact_ms += dg.compact()
            resplits += 1
        for _ in range(query_batches_per_round):
            srcs = rng.integers(0, n, size=batch)
            t0 = time.perf_counter()
            run_query_batch(engine, alg, srcs)
            lat_ms.append((time.perf_counter() - t0) * 1e3)
        ref = refresh_standing(engine, dg, alg, standing_sources, prev, mark)
        prev = ref.pop("result")
        mark = dg.mark()
        if ref.get("warm_steps"):
            warm_steps_all.append(max(ref["warm_steps"]))
        if ref.get("cold_steps"):
            cold_steps_all.append(max(ref["cold_steps"]))
        rounds.append(dict(round=i, mutation=dict(
            (k, v) for k, v in rep.items() if k != "dirty"), refresh=ref))
        # Zero-retrace accounting, per round: cache growth counts as a
        # retrace unless something legitimately new compiled this round —
        # a compaction rebind (shape-changed loops recompile), or the warm
        # path's first run at the current graph version (its relaxation
        # program compiles once per shape).  Those rounds just reset the
        # baseline; the gate stays armed for every other round (round 0
        # seeds the baseline after warm-up compiles).
        legit = (engine.dynamic_rebinds != rebinds_prev
                 or engine.hybrid_dyn_rebuilds != rebuilds_prev)
        if (ref.get("mode") == "incremental"
                and dg.version not in warm_versions):
            warm_versions.add(dg.version)
            legit = True
        if entries_prev is not None and not legit:
            retraces += cache_entries() - entries_prev
        entries_prev = cache_entries()
        rebinds_prev = engine.dynamic_rebinds
        rebuilds_prev = engine.hybrid_dyn_rebuilds
    wall_s = time.perf_counter() - t_all

    report = dict(
        algorithm=alg, batch=batch, rounds=len(rounds),
        standing=standing,
        mutation_edges_per_sec=(mut_edges / mut_s) if mut_s else None,
        mutation_edges=int(mut_edges),
        incremental_steps=(int(np.mean(warm_steps_all))
                           if warm_steps_all else None),
        cold_steps=(int(np.mean(cold_steps_all))
                    if cold_steps_all else None),
        batch_p50_ms=_percentile(lat_ms, 50),
        batch_p99_ms=_percentile(lat_ms, 99),
        compactions=dg.compactions, compaction_pause_ms=compact_ms,
        resplits=resplits,
        dynamic_rebinds=engine.dynamic_rebinds,
        hybrid_rebuilds=engine.hybrid_dyn_rebuilds,
        retraces=retraces,
        wall_s=wall_s, per_round=rounds,
        staleness=dg.staleness())
    return report


# ---------------------------------------------------------------------------
# fault-tolerant serving (docs/robustness.md)
# ---------------------------------------------------------------------------

def chunked_refresh(engine, alg: str, sources, *, chunk: int,
                    on_chunk=None, round_i: int = 0):
    """Refresh a standing query set through the checkpointable chunked run
    mode.  Returns ([Q, n] results, steps [Q], info)."""
    import jax.numpy as jnp

    from repro.algorithms.bfs import (BFS_PROGRAM, gather_batch,
                                      multi_source_state)
    from repro.algorithms.sssp import SSSP_PROGRAM
    from repro.runtime import chaos

    pg = engine.pg
    if alg == "bfs":
        program, key = BFS_PROGRAM, "level"
        state = {"level": jnp.asarray(multi_source_state(pg, sources))}
    elif alg == "sssp":
        program, key = SSSP_PROGRAM, "dist"
        d0 = multi_source_state(pg, sources)
        state = {"dist": jnp.asarray(d0),
                 "active": jnp.asarray(np.isfinite(d0))}
    else:
        raise ValueError(f"chunked refresh supports bfs/sssp, not {alg!r}")
    if chaos.visit("query.poison", round=round_i):
        # data-level fault drill: corrupt query 0's initial state — the
        # quarantine scan must catch it at the first chunk boundary
        arr = np.asarray(state[key]).copy()
        arr[0] = np.nan
        state[key] = jnp.asarray(arr)
    state, steps_q, info = engine.execute(
        program, state, chunk=chunk, on_chunk=on_chunk,
        chaos_ctx={"round": round_i})
    return gather_batch(pg, state[key]), np.asarray(steps_q), info


def serve_fault_tolerant(args, manager, *, midrun_manager=None,
                         hard_limit_s=None):
    """Mutating serving session that survives injected (or real) faults.

    Per round: apply one mutation batch (acknowledged only after the
    device scatter completes), serve a fresh query batch through the
    degradation ladder (primary backend → retry → reference fallback),
    refresh the standing set through the chunked run mode with the
    quarantine scan and the superstep watchdog at every chunk boundary,
    then snapshot ``{standing results, dynamic payload}`` +
    ``{round, acked cursor}`` via ``save_tree``.

    Recovery (on a retryable fault anywhere in the round): exponential
    backoff, rebuild the graph from base, **replay the acknowledged
    mutation log**, restore the latest round snapshot, and assert the
    replayed device payload is bitwise identical to the snapshotted one —
    a crash between compactions loses no acknowledged mutation.  The
    watchdog's ``hard_limit_s`` triggers checkpoint-now: the in-flight
    chunk carry is snapshotted to ``midrun_manager`` without waiting for
    the round boundary.

    Returns (report, standing results [Q, n], quarantined query-id set).
    """
    from repro.core import bsp
    from repro.core.bsp import BSPEngine
    from repro.core.dynamic import DynamicGraph
    from repro.core.graph import apply_mutation_batches
    from repro.data.graphs import edge_stream
    from repro.runtime import (RETRYABLE_EXCEPTIONS, DegradationLadder,
                               QuarantinePolicy, RestartPolicy, StepWatchdog,
                               chaos)

    from repro.core import graph as G

    gen = G.rmat if args.graph == "rmat" else G.uniform
    g = gen(args.scale, args.edge_factor, seed=args.seed)
    if args.alg == "sssp":
        g = g.with_uniform_weights(seed=args.seed + 1)
    kw = {}
    if args.backend == "fused":
        kw = dict(fused=True, block_e=args.block_e)
    elif args.backend == "hybrid":
        kw = dict(backend="hybrid")

    rounds = args.mutation_rounds
    stream = edge_stream(g, rounds, args.mutation_batch, churn=args.churn,
                         seed=args.seed)
    rng = np.random.default_rng(args.seed)
    standing = rng.integers(0, g.num_vertices, size=(args.standing, 1))

    def build_session():
        dg = DynamicGraph(g, args.parts, args.strategy,
                          mutation_capacity=args.mutation_batch)
        primary = BSPEngine(dg, **kw)
        fallback = BSPEngine(dg) if kw else primary
        return dg, primary, fallback

    policy = RestartPolicy(max_failures=args.max_restarts,
                           backoff_s=args.restart_backoff_s)
    quar = QuarantinePolicy(superstep_budget=args.superstep_budget)
    ladder = DegradationLadder(retries=1)
    wd = StepWatchdog(warmup_steps=2, hard_limit_s=hard_limit_s)
    midrun_snapshots = 0

    dg, engine, fb_engine = build_session()
    # warm both rungs of the ladder so later downgrades reuse the caches
    warm = rng.integers(0, g.num_vertices, size=args.batch)
    run_query_batch(engine, args.alg, warm)
    if fb_engine is not engine:
        run_query_batch(fb_engine, args.alg, warm)

    cache_fns = [bsp._run_dyn_jit, bsp._run_dyn_hybrid_jit,
                 bsp._run_dyn_chunk_jit, bsp._run_dyn_hybrid_chunk_jit]

    def cache_entries():
        return sum(f._cache_size() for f in cache_fns)

    acked = 0                 # durable cursor: stream[:acked] acknowledged
    round_i = 0
    prev = None
    snapshots = 0
    entries0 = None
    n = g.num_vertices

    def recover():
        nonlocal dg, engine, fb_engine, round_i, prev
        dg, engine, fb_engine = build_session()
        if acked:
            dg.replay(stream[:acked])   # the durable log IS the truth
        latest = manager.latest_step()
        if latest is None:
            round_i, prev = 0, None
            return
        dyn_tree, dyn_extra = dg.snapshot()
        like = {"standing": np.zeros((args.standing, n), np.float32),
                "dyn": dyn_tree}
        _, tree = manager.restore_tree(like, latest)
        extra = manager.manifest_extra(latest)
        round_i = int(extra["round"])
        prev = tree["standing"]
        if (int(extra["cursor"]) == dyn_extra["cursor"]
                and int(extra["version"]) == dyn_extra["version"]):
            # zero-lost-mutations proof: rebuilding from base + replaying
            # the acked log reproduces the snapshotted delta/tombstone
            # payload bitwise
            from repro.checkpoint.manager import _flatten
            snap_flat = _flatten(tree["dyn"])
            live_flat = {k: np.asarray(v)
                         for k, v in _flatten(dyn_tree).items()}
            for name, a in snap_flat.items():
                if not np.array_equal(np.asarray(a), live_flat[name]):
                    raise RuntimeError(
                        f"replayed payload leaf {name!r} differs from the "
                        f"snapshot — a mutation was lost or double-applied")

    while round_i < rounds:
        try:
            chaos.visit("serve.round", round=round_i)
            if round_i >= acked:
                dg.apply_mutations(stream[round_i])
                acked = round_i + 1
            # fresh queries ride the degradation ladder
            srcs = np.random.default_rng(
                args.seed + 100 + round_i).integers(0, n, size=args.batch)
            r = round_i

            def primary():
                chaos.visit("kernel.dispatch", round=r,
                            backend=args.backend)
                return run_query_batch(engine, args.alg, srcs)

            ladder.run(primary,
                       lambda: run_query_batch(fb_engine, args.alg, srcs),
                       label=f"round{r}:{args.alg}")

            # standing refresh through the checkpointable chunked mode
            quar.begin(args.standing)
            t_chunk = [time.perf_counter()]

            def on_chunk(snap):
                nonlocal midrun_snapshots
                now = time.perf_counter()
                flagged = wd.report(snap["step"], now - t_chunk[0])
                t_chunk[0] = now
                if flagged and midrun_manager is not None:
                    # checkpoint-now: persist the in-flight chunk carry
                    midrun_manager.save_tree(
                        snap["step"],
                        {"state": snap["state"], "fin": snap["fin"],
                         "steps_q": snap["steps_q"]},
                        extra={"round": r, "step": snap["step"],
                               "mid_run": True}, blocking=True)
                    midrun_snapshots += 1
                return quar.scan(snap)

            prev, steps_q, info = chunked_refresh(
                engine, args.alg, standing, chunk=args.checkpoint_every,
                on_chunk=on_chunk, round_i=round_i)

            dyn_tree, dyn_extra = dg.snapshot()
            manager.save_tree(
                round_i + 1,
                {"standing": np.asarray(prev), "dyn": dyn_tree},
                extra=dict(round=round_i + 1, acked=acked, **dyn_extra),
                blocking=True)
            snapshots += 1
            round_i += 1
            if entries0 is None:
                entries0 = cache_entries()
        except RETRYABLE_EXCEPTIONS as e:
            # Only the restart whitelist (worker faults, XLA runtime errors,
            # exchange corruption) burns the retry budget; programming bugs
            # propagate — matching RestartPolicy.handle's own contract.
            sleep_s = policy.handle(e, context=dict(round=round_i))
            if sleep_s:
                time.sleep(sleep_s)
            recover()

    # ledger-vs-oracle audit: the served graph equals a from-scratch apply
    # of every acknowledged batch
    mut = dg.mutated_csr()
    oracle = apply_mutation_batches(g, stream[:acked])
    if not (np.array_equal(mut.row_ptr, oracle.row_ptr)
            and np.array_equal(mut.col, oracle.col)):
        raise RuntimeError("mutated CSR diverged from the mutation-log "
                           "oracle — acknowledged mutations were lost")

    retraces = (cache_entries() - entries0) if entries0 is not None else 0
    report = dict(
        rounds=rounds, acked=acked, snapshots=snapshots,
        midrun_snapshots=midrun_snapshots,
        failures=policy.failures, restarts=policy.restarts,
        downgrades=ladder.downgrades, quarantined=quar.quarantined,
        stragglers=len(wd.stragglers), retraces=retraces,
        backend=args.backend, algorithm=args.alg)
    quarantined_ids = {rec["query"] for rec in quar.quarantined}
    return report, np.asarray(prev), quarantined_ids


# ---------------------------------------------------------------------------
# continuous batching (docs/serving.md)
# ---------------------------------------------------------------------------

def serve_continuous(engine, g, cfg: ServeConfig, sources, *,
                     dg=None, mutation_stream=None, parity: bool = False,
                     warm: bool = True) -> dict:
    """Serve ``sources`` through one resident :class:`ServeSession` and
    report it against fixed-batch drain at the same Q.

    Non-mutating: the whole stream is submitted up front ("under load" —
    every query's latency includes its queue wait) and drained by ONE
    resident compiled loop; the same stream then runs through drain-batch
    ``run_batched`` for the q/s / p99 baseline and, with ``parity=True``,
    the bitwise oracle.  With ``mutation_stream`` (requires a dynamic
    ``dg``), the stream is served in waves — drain, mutate, drain — so
    every query completes against exactly one graph version and parity
    holds per wave.
    """
    from repro.runtime import ServeSession, drain_reference

    deg = g.out_degrees()
    scheduler = "depth" if cfg.depth_buckets else "fifo"
    depth_key = (lambda s: -int(deg[s])) if cfg.depth_buckets else None

    def make_session():
        return ServeSession(
            engine, cfg.alg, slots=cfg.batch, chunk=cfg.chunk,
            queue_capacity=cfg.queue_capacity, deadline_ms=cfg.deadline_ms,
            scheduler=scheduler, depth_key=depth_key)

    if warm:
        # pay every compile (chunk jit, slot swap, drain-batch loop)
        # outside the timed run: a 2x-slots throwaway stream forces one
        # refill cycle, and the oracle warms run_batched
        warm_srcs = np.resize(np.asarray(sources), 2 * cfg.batch)
        ws = make_session()
        ws.submit(warm_srcs)
        ws.drain()
        drain_reference(engine, cfg.alg, warm_srcs[:cfg.batch], cfg.batch)

    waves = [np.asarray(sources).reshape(-1)]
    if mutation_stream is not None:
        if dg is None:
            raise ValueError("mutation_stream needs the dynamic graph (dg)")
        waves = np.array_split(np.asarray(sources).reshape(-1),
                               len(mutation_stream) + 1)

    session = make_session()
    mismatches = 0
    checked = 0
    drain_lat: list = []
    drain_wall = 0.0
    t_all = time.perf_counter()
    cont_wall = 0.0
    for w, wave in enumerate(waves):
        if w > 0:
            session.mutate(mutation_stream[w - 1])
        qids = session.submit(wave)
        t0 = time.perf_counter()
        session.drain()
        cont_wall += time.perf_counter() - t0
        # fixed-batch drain of the same wave on the same graph version:
        # the q/s + p99 baseline, and (parity=True) the bitwise oracle
        t0 = time.perf_counter()
        num = len(wave)
        ref_rows = []
        for i in range(0, num, cfg.batch):
            batch = np.resize(wave[i:i + cfg.batch], cfg.batch)
            ref_rows.append(run_query_batch(engine, cfg.alg, batch))
            # a drained query's latency is its batch's completion time
            done_ms = (time.perf_counter() - t0) * 1e3
            drain_lat.extend([done_ms] * min(cfg.batch, num - i))
        drain_wall += time.perf_counter() - t0
        if parity:
            ref = np.concatenate(ref_rows, axis=0)[:num]
            by_qid = {q: j for j, q in enumerate(qids) if q is not None}
            for r in session.poll():
                if r["query"] in by_qid:
                    checked += 1
                    if not np.array_equal(r["result"],
                                          ref[by_qid[r["query"]]]):
                        mismatches += 1
    wall_s = time.perf_counter() - t_all
    rep = session.report()
    cont_lat = sorted(session._latency_ms.values())
    completed = rep["completed"]
    report = dict(
        mode="continuous", algorithm=cfg.alg, slots=cfg.batch,
        chunk=cfg.chunk, stream=len(np.asarray(sources).reshape(-1)),
        waves=len(waves), completed=completed,
        rejected=rep["rejected"], windows=rep["windows"],
        refills=rep["refills"],
        min_slot_refills=rep["min_slot_refills"],
        max_slot_refills=rep["max_slot_refills"],
        retraces=rep["retraces"], sla_misses=rep["sla_misses"],
        scheduler=scheduler,
        continuous_qps=(completed / cont_wall) if cont_wall else None,
        continuous_p50_ms=_percentile(cont_lat, 50),
        continuous_p99_ms=_percentile(cont_lat, 99),
        drain_qps=(len(drain_lat) / drain_wall) if drain_wall else None,
        drain_p50_ms=_percentile(drain_lat, 50),
        drain_p99_ms=_percentile(drain_lat, 99),
        wall_s=wall_s,
        backend=getattr(engine, "backend", None),
        engine=type(engine).__name__)
    if parity:
        report["parity_checked"] = checked
        report["parity_mismatches"] = mismatches
    return report


def run_chaos_drill(args) -> int:
    """``--chaos``: clean session vs fault-injected session, with recovery
    and parity asserts (the CI chaos job).

    Injected faults: a crash between mutation batches (``serve.round``), a
    shard/worker death mid-refresh (``superstep.chunk``), a crash
    mid-mutation-batch before the device scatter (``mutation.scatter``), a
    kernel-dispatch fault that exhausts its retry (``kernel.dispatch`` ×2 →
    reference fallback), and a poisoned query (``query.poison`` → NaN
    state, quarantined every round).  Asserts: the session recovers within
    the restart budget, the mutation log replays with zero lost mutations,
    non-quarantined standing results are **bitwise identical** to the
    uninjected run, retraces stay bounded by restarts, and the clean path
    quarantines nothing.
    """
    import tempfile

    from repro.checkpoint import CheckpointManager
    from repro.runtime import FaultInjector, chaos

    rounds = args.mutation_rounds
    with tempfile.TemporaryDirectory() as td:
        clean_rep, clean_res, clean_quar = serve_fault_tolerant(
            args, CheckpointManager(td + "/clean", keep=3))
        print(f"clean session: rounds={clean_rep['rounds']} "
              f"snapshots={clean_rep['snapshots']} "
              f"retraces={clean_rep['retraces']} "
              f"quarantined={len(clean_rep['quarantined'])}", flush=True)
        assert clean_rep["failures"] == 0 and not clean_quar
        assert clean_rep["retraces"] == 0, \
            f"clean path retraced: {clean_rep['retraces']}"

        inj = FaultInjector(sites={
            "serve.round": [{"round": min(1, rounds - 1)}],
            "superstep.chunk": [{"round": min(1, rounds - 1), "chunk": 1}],
            "mutation.scatter": [{"index": min(2, rounds - 1)}],
            "kernel.dispatch": [{"round": min(2, rounds - 1)},
                                {"round": min(2, rounds - 1)}],
            "query.poison": [{"round": r, "flag": True}
                             for r in range(rounds)],
        })
        with chaos.active(inj):
            faulty_rep, faulty_res, faulty_quar = serve_fault_tolerant(
                args, CheckpointManager(td + "/faulty", keep=3),
                midrun_manager=CheckpointManager(td + "/midrun", keep=3),
                hard_limit_s=0.0)

    print(f"faulty session: failures={faulty_rep['failures']} "
          f"restarts={[r.get('round') for r in faulty_rep['restarts']]} "
          f"downgrades={len(faulty_rep['downgrades'])} "
          f"quarantined={sorted(faulty_quar)} "
          f"midrun_snapshots={faulty_rep['midrun_snapshots']} "
          f"retraces={faulty_rep['retraces']}", flush=True)

    assert faulty_rep["failures"] >= 3, \
        "expected >=3 injected worker faults to fire"
    assert faulty_rep["acked"] == rounds, "mutation log not fully replayed"
    assert len(faulty_rep["downgrades"]) == 1, \
        "kernel fault did not fall back to the reference backend"
    assert faulty_quar == {0}, \
        f"poisoned query 0 not quarantined: {faulty_quar}"
    assert any(rec["reason"] == "nonfinite"
               for rec in faulty_rep["quarantined"])
    assert faulty_rep["midrun_snapshots"] > 0, \
        "watchdog checkpoint-now never fired"
    assert faulty_rep["retraces"] <= faulty_rep["failures"], \
        (f"retraces ({faulty_rep['retraces']}) exceed restarts "
         f"({faulty_rep['failures']})")

    ok = np.ones(len(clean_res), bool)
    for q in faulty_quar | clean_quar:
        ok[q] = False
    assert np.array_equal(clean_res[ok], faulty_res[ok]), \
        "recovered results diverge from the uninjected run"
    print(f"chaos parity: {int(ok.sum())}/{len(ok)} standing queries "
          f"bitwise identical to the uninjected run "
          f"(quarantined: {sorted(faulty_quar)})", flush=True)
    print("CHAOS OK")
    return 0


def run_corrupt_drill(args) -> int:
    """``--corrupt``: the silent-corruption drill (the CI corruption job).

    Worker faults raise; silent faults don't — this drill flips bits at
    every data-corruption seam and asserts the integrity layer converts
    each one into a *detection* (checksum mismatch, monitor fire, or
    certifier rejection with a recompute) or a *mask* (the harvested
    result is bitwise identical to the clean run anyway).  Per backend
    (reference, fused, hybrid):

    - clean pass: a certified ``ServeSession`` and a certified chunked
      refresh produce **zero** false positives (no recompute, no monitor
      fire, every fixpoint certifies);
    - ``state.corrupt``: a bit-flipped state row at a window boundary is
      caught by the invariant monitor and/or the harvest certifier, and
      the recompute-once policy restores the right answer;
    - ``exchange.payload``: a corrupted outbox element mismatches its
      inbox-side reduction tag → ``ExchangeCorruption`` → a clean window
      replay reproduces the uncorrupted result bitwise (the hybrid
      single-device path has no wire exchange, so the site is inert
      there and the result must stay bitwise clean).

    Backend-independent sites, drilled once: ``checkpoint.torn`` (a torn
    tensor fails its manifest CRC at restore; the previous snapshot still
    loads) and ``tombstone.flip`` (a resurrected deleted edge on the
    dynamic path yields a fixpoint the certifier rejects against the true
    mutated graph).
    """
    import tempfile

    from repro.checkpoint import CheckpointManager
    from repro.checkpoint.manager import CheckpointCorruption
    from repro.runtime import (ExchangeCorruption, FaultInjector,
                               QuarantinePolicy, ResultCertifier,
                               ServeSession, chaos, monitor_for)

    rng = np.random.default_rng(args.seed)
    detections = 0
    masked = 0

    def flag(site, **ctx):
        return FaultInjector(sites={site: [dict(ctx, flag=True)]})

    for backend in ("reference", "fused", "hybrid"):
        a = argparse.Namespace(**vars(args))
        a.backend = backend
        g, _, engine = build_engine(a)
        sources = rng.integers(0, g.num_vertices, size=args.num_queries)
        certifier = ResultCertifier(args.alg, g)

        def run_session():
            # all three detection layers armed: in-loop monitors, the
            # non-finite/budget quarantine, and harvest certification
            s = ServeSession(engine, args.alg, slots=args.batch,
                             chunk=args.checkpoint_every,
                             quarantine=QuarantinePolicy(
                                 superstep_budget=args.superstep_budget),
                             certifier=ResultCertifier(args.alg, g),
                             monitor=monitor_for(args.alg,
                                                 chunk=args.checkpoint_every))
            s.submit(sources)
            s.drain()
            rep = s.report()
            res = {r["query"]: r["result"] for r in s.poll()}
            return res, rep, set(s.quarantined_qids)

        # -- clean pass: zero false positives -----------------------------
        clean, rep, cq = run_session()
        assert rep["recomputed"] == 0 and not rep["certify_failed"], \
            f"[{backend}] clean session raised certifier false positives: " \
            f"{rep['certify_failed']}"
        assert rep["monitors_fired"] == 0 and not cq, \
            f"[{backend}] clean session fired {rep['monitors_fired']} " \
            f"invariant monitors, quarantined {sorted(cq)}"
        std = sources[:args.batch]
        clean_chunk, _, _ = chunked_refresh(
            engine, args.alg, std, chunk=args.checkpoint_every)
        verdicts = certifier.certify_batch(clean_chunk, sources=std)
        assert all(v.ok for v in verdicts), \
            f"[{backend}] clean chunked fixpoint failed certification: " \
            f"{[v.reason() for v in verdicts if not v.ok]}"
        print(f"[{backend}] clean: {rep['completed']} queries certified, "
              f"0 false positives, 0 monitor fires", flush=True)

        # -- state.corrupt: bit-flipped state row at a window boundary ----
        with chaos.active(flag("state.corrupt", step=0)):
            dirty, rep, dq = run_session()
        hits = rep["monitors_fired"] + rep["recomputed"] + len(dq)
        parity = all(np.array_equal(dirty[q], clean[q])
                     for q in clean if q not in dq)
        assert hits or parity, \
            f"[{backend}] state.corrupt neither detected nor masked"
        assert all(r["recovered"] for r in rep["certify_failed"]), \
            f"[{backend}] certifier recompute did not recover: " \
            f"{rep['certify_failed']}"
        assert parity or rep["recomputed"], \
            f"[{backend}] state.corrupt changed results without a recompute"
        detections += bool(hits)
        masked += bool(not hits)
        print(f"[{backend}] state.corrupt: "
              f"{'detected' if hits else 'masked'} "
              f"(monitors={rep['monitors_fired']} "
              f"recomputes={rep['recomputed']} "
              f"quarantined={sorted(dq)})", flush=True)

        # -- exchange.payload: corrupted wire block vs reduction tags -----
        try:
            with chaos.active(flag("exchange.payload", step=0)):
                got, _, _ = chunked_refresh(
                    engine, args.alg, std, chunk=args.checkpoint_every)
            caught = None
        except ExchangeCorruption as e:
            caught = e
        if caught is not None:
            # bounded window-replay: the clean re-run IS the recovery
            replay, _, _ = chunked_refresh(
                engine, args.alg, std, chunk=args.checkpoint_every)
            assert np.array_equal(replay, clean_chunk), \
                f"[{backend}] post-corruption replay diverged"
            detections += 1
            print(f"[{backend}] exchange.payload: detected "
                  f"({caught}); replay bitwise clean", flush=True)
        else:
            assert backend == "hybrid", \
                f"[{backend}] corrupted exchange escaped the tag check"
            assert np.array_equal(got, clean_chunk), \
                "[hybrid] inert exchange site still changed the result"
            masked += 1
            print("[hybrid] exchange.payload: masked (single-device hybrid "
                  "supersteps have no wire exchange)", flush=True)

    # -- checkpoint.torn: torn tensor vs manifest CRC (backend-free) ------
    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td, keep=3)
        tree = {"state": rng.standard_normal(64).astype(np.float32)}
        mgr.save_tree(0, tree, blocking=True)
        with chaos.active(flag("checkpoint.torn", step=1)):
            mgr.save_tree(1, tree, blocking=True)
        try:
            mgr.restore_tree(tree)
            raise AssertionError("torn checkpoint restored silently")
        except CheckpointCorruption as e:
            detections += 1
            print(f"checkpoint.torn: detected ({e})", flush=True)
        _, prev = mgr.restore_tree(tree, step=0)
        assert np.array_equal(prev["state"], tree["state"]), \
            "fallback snapshot does not match the saved state"
        print("checkpoint.torn: fallback to step 0 bitwise clean",
              flush=True)

    # -- tombstone.flip: resurrected deleted edge on the dynamic path -----
    from repro.data.graphs import edge_stream

    a = argparse.Namespace(**vars(args))
    a.backend, a.alg = "reference", "bfs"
    g, dg, engine = build_engine(a, dynamic=True)
    batch = edge_stream(g, 1, args.mutation_batch, churn=0.5,
                        seed=args.seed)[0]
    dg.apply_mutations(batch)
    truth = dg.mutated_csr()
    cert = ResultCertifier("bfs", truth)
    std = rng.integers(0, g.num_vertices, size=args.batch)
    base, _, _ = chunked_refresh(engine, "bfs", std,
                                 chunk=args.checkpoint_every)
    verdicts = cert.certify_batch(base, sources=std)
    assert all(v.ok for v in verdicts), \
        "clean dynamic fixpoint failed certification against the " \
        "mutated graph"
    # flip at EVERY window so the engine converges to a consistent fixpoint
    # of the *wrong* graph — the hardest case: only a certifier that checks
    # against the true mutated topology can tell
    persistent = FaultInjector(sites={"tombstone.flip": [
        {"step": s, "flag": True}
        for s in range(0, 64, args.checkpoint_every)]})
    with chaos.active(persistent):
        flipped, _, _ = chunked_refresh(engine, "bfs", std,
                                        chunk=args.checkpoint_every)
    verdicts = cert.certify_batch(flipped, sources=std)
    bad = [v.reason() for v in verdicts if not v.ok]
    if np.array_equal(flipped, base):
        masked += 1
        assert not bad, f"masked tombstone flip still failed: {bad}"
        print("tombstone.flip: masked (min-semiring path redundancy "
              "absorbed the flipped slot; fixpoint bitwise clean)",
              flush=True)
    else:
        detections += 1
        assert bad, "tombstone flip changed the fixpoint but every " \
                    "certifier check passed"
        print(f"tombstone.flip: detected ({bad[0]})", flush=True)
    # teeth proof: had the flip produced ANY wrong fixpoint, the certifier
    # rejects it — perturb one reached vertex's level by one and re-certify
    slot = next(i for i in range(len(std))
                if (np.isfinite(flipped[i]) & (flipped[i] > 0)).any())
    wrong = np.asarray(flipped[slot]).copy()
    v = int(np.flatnonzero(np.isfinite(wrong) & (wrong > 0))[0])
    wrong[v] -= 1.0
    verdict = cert.certify(wrong, source=int(std[slot]))
    assert not verdict.ok, \
        "certifier accepted a provably wrong BFS fixpoint"
    print(f"tombstone.flip: certifier rejects a perturbed fixpoint "
          f"({verdict.reason()})", flush=True)

    print(f"corruption drill: {detections} detected, {masked} masked, "
          f"0 false positives across 3 backends", flush=True)
    print("CORRUPT OK")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--graph", choices=("rmat", "uniform"), default="rmat")
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--strategy", default="high",
                    choices=("rand", "high", "low"))
    ap.add_argument("--backend", default="fused",
                    choices=("reference", "fused", "hybrid"))
    ap.add_argument("--block-e", type=int, default=256)
    ap.add_argument("--win-blocks", type=int, default=8,
                    help="edge blocks per out-of-core streaming window "
                         "(with --hbm-budget; the double-buffer costs "
                         "2*win_blocks*block_e edge slots of HBM)")
    ap.add_argument("--hbm-budget", type=int, default=None, metavar="BYTES",
                    help="out-of-core tiering: device-memory byte budget "
                         "for the graph arenas; partitions that do not fit "
                         "go host-tier and stream through double-buffered "
                         "windows (admission charges only the HBM figure "
                         "against this budget)")
    ap.add_argument("--alg", default="bfs",
                    choices=("bfs", "sssp", "bc", "ppr"))
    ap.add_argument("--batch", type=int, default=32,
                    help="queries per batch (the Q axis)")
    ap.add_argument("--num-queries", type=int, default=256,
                    help="synthetic query stream length")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None, help="write the report JSON here")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (scale 8, 3 batches of 4)")
    # --- dynamic-graph serving (docs/dynamic.md) ---
    ap.add_argument("--mutate", action="store_true",
                    help="interleave edge-mutation batches with query "
                         "batches against a resident DynamicGraph")
    ap.add_argument("--mutation-batch", type=int, default=256,
                    help="edges per mutation batch")
    ap.add_argument("--mutation-rounds", type=int, default=8,
                    help="mutation batches in the stream")
    ap.add_argument("--churn", type=float, default=0.7,
                    help="insert fraction of each mutation batch (the rest "
                         "deletes; 1.0 keeps warm starts monotone)")
    ap.add_argument("--standing", type=int, default=8,
                    help="standing query set kept fresh across mutations")
    ap.add_argument("--no-compact", action="store_true",
                    help="disable threshold-driven compaction (capacity-"
                         "overflow auto-compaction still applies; its "
                         "pauses are reported either way)")
    # --- depth-bucketing scheduler (ROADMAP open item) ---
    ap.add_argument("--depth-buckets", type=int, default=0, metavar="B",
                    help="serve the stream in B estimated-depth buckets and "
                         "report per-bucket p99 vs the unbucketed baseline")
    # --- fault tolerance & SLA (docs/robustness.md) ---
    ap.add_argument("--chaos", action="store_true",
                    help="run the fault-injection drill: a clean mutating "
                         "session, then the same session with injected "
                         "crashes; assert recovery, zero lost mutations, "
                         "and bitwise parity")
    ap.add_argument("--corrupt", action="store_true",
                    help="run the silent-corruption drill: inject bit-flips "
                         "at every data-corruption site (state rows, "
                         "exchange payloads, checkpoint tensors, tombstone "
                         "masks) across all three backends; assert every "
                         "fault is detected-or-masked and the clean path "
                         "raises zero false positives")
    ap.add_argument("--checkpoint-every", type=int, default=2,
                    help="supersteps per checkpointable chunk in the "
                         "fault-tolerant refresh path")
    ap.add_argument("--superstep-budget", type=int, default=64,
                    help="quarantine standing queries still unconverged "
                         "after this many supersteps (divergence watchdog)")
    ap.add_argument("--max-restarts", type=int, default=5,
                    help="retryable-failure budget for the serving session")
    ap.add_argument("--restart-backoff-s", type=float, default=0.0,
                    help="base exponential-backoff sleep between restarts")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-query SLA deadline; misses are reported")
    ap.add_argument("--queue-capacity", type=int, default=None,
                    help="admission-control bound on the query queue; "
                         "overflow is rejected with a reason")
    # --- continuous batching (docs/serving.md) ---
    ap.add_argument("--continuous", action="store_true",
                    help="serve through one resident ServeSession: refill "
                         "converged query slots mid-loop instead of "
                         "draining the batch (composes with --mutate, "
                         "--deadline-ms, --queue-capacity, --depth-buckets)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.smoke:
        args.scale = min(args.scale, 8)
        args.batch = min(args.batch, 4)
        args.num_queries = min(args.num_queries, 3 * args.batch)
        args.mutation_batch = min(args.mutation_batch, 32)
        args.mutation_rounds = min(args.mutation_rounds, 3)
        args.standing = min(args.standing, 4)

    try:
        cfg = ServeConfig.from_args(args)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    if cfg.mode == "chaos":
        return run_chaos_drill(args)

    if cfg.mode == "corrupt":
        return run_corrupt_drill(args)

    if cfg.mode == "continuous":
        dg = stream = None
        if cfg.mutate:
            from repro.data.graphs import edge_stream

            g, dg, engine = build_engine(args, dynamic=True)
            stream = edge_stream(g, args.mutation_rounds,
                                 args.mutation_batch, churn=args.churn,
                                 seed=args.seed)
        else:
            g, _, engine = build_engine(args)
        print(f"resident graph: |V|={g.num_vertices:,} "
              f"|E|={g.num_edges:,} parts={args.parts} "
              f"backend={args.backend} continuous slots={cfg.batch}",
              flush=True)
        rng = np.random.default_rng(args.seed)
        sources = rng.integers(0, g.num_vertices, size=args.num_queries)
        report = serve_continuous(engine, g, cfg, sources, dg=dg,
                                  mutation_stream=stream,
                                  parity=args.smoke)
        print(f"{cfg.alg}: {report['completed']}/{report['stream']} "
              f"queries through {report['slots']} resident slots "
              f"({report['waves']} wave(s)) -> "
              f"{report['continuous_qps']:.1f} q/s continuous vs "
              f"{report['drain_qps']:.1f} q/s drain; p99 "
              f"{report['continuous_p99_ms']:.1f} vs "
              f"{report['drain_p99_ms']:.1f} ms; "
              f"refills={report['refills']} "
              f"(min/slot={report['min_slot_refills']}); "
              f"retraces={report['retraces']}", flush=True)
        if "parity_checked" in report:
            print(f"parity: {report['parity_checked']} checked, "
                  f"{report['parity_mismatches']} mismatches", flush=True)
            assert report["parity_mismatches"] == 0, \
                "continuous results diverge from drain-batch"
        if report["retraces"]:
            print(f"WARNING: {report['retraces']} compile-cache entries "
                  f"added after warmup — refills are retracing",
                  file=sys.stderr)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(dict(vars(args), **report), f, indent=2)
            print(f"wrote {args.out}")
        print("GRAPH SERVE OK")
        return 0

    if cfg.mode == "mutate":
        from repro.data.graphs import edge_stream

        g, dg, engine = build_engine(args, dynamic=True)
        print(f"resident dynamic graph: |V|={g.num_vertices:,} "
              f"|E|={g.num_edges:,} parts={args.parts} "
              f"strategy={args.strategy} backend={args.backend} "
              f"delta_slots={dg.delta_slots}/partition", flush=True)
        stream = edge_stream(g, args.mutation_rounds, args.mutation_batch,
                             churn=args.churn, seed=args.seed)
        report = serve_mutating(
            engine, dg, args.alg, batches=stream, batch=args.batch,
            standing=args.standing, query_batches_per_round=2,
            seed=args.seed, compact=not args.no_compact)
        inc = report["incremental_steps"]
        cold = report["cold_steps"]
        savings = (f"{inc} vs {cold} supersteps "
                   f"({cold / max(inc, 1):.1f}x fewer)"
                   if inc is not None and cold else "n/a (non-monotone)")
        print(f"{args.alg}: {report['rounds']} mutation rounds x "
              f"{args.mutation_batch} edges -> "
              f"{report['mutation_edges_per_sec']:.0f} edges/s applied; "
              f"incremental refresh {savings}; query batch "
              f"p50={report['batch_p50_ms']:.1f} "
              f"p99={report['batch_p99_ms']:.1f} ms; "
              f"compactions={report['compactions']} "
              f"({report['compaction_pause_ms']:.0f} ms paused); "
              f"retraces={report['retraces']}", flush=True)
        if report["retraces"]:
            print(f"WARNING: {report['retraces']} compile-cache entries "
                  f"added after warmup without a compaction — mutation "
                  f"batches are retracing", file=sys.stderr)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(dict(vars(args), **report), f, indent=2)
            print(f"wrote {args.out}")
        print("GRAPH SERVE OK")
        return 0

    g, pg, engine = build_engine(args)
    print(f"resident graph: |V|={g.num_vertices:,} |E|={g.num_edges:,} "
          f"parts={args.parts} strategy={args.strategy} "
          f"backend={args.backend}", flush=True)
    if engine.tier_plan is not None:
        # Admission charges the *HBM* figure only against the device
        # budget: host-tier partitions stream from DRAM and must not be
        # counted as device residency (memory_footprint_bytes per-tier
        # split).  The arena figure is what the tier split itself gated.
        stats = engine.tiered_stats()
        resid = engine.residency_bytes()
        print(f"tiered: {stats['num_hot']} hot / {stats['num_cold']} "
              f"host-tier partitions; arena hbm={stats['hbm_resident_bytes']:,}"
              f" B <= budget {args.hbm_budget:,} B; residency "
              f"hbm={resid['hbm_bytes']:,} B host={resid['host_bytes']:,} B "
              f"(streams {stats['streamed_bytes_per_superstep']:,} B/"
              f"superstep over {stats['window_count']} windows)", flush=True)
        if stats["hbm_resident_bytes"] > args.hbm_budget:
            print("error: tier plan exceeds the HBM budget", file=sys.stderr)
            return 2

    rng = np.random.default_rng(args.seed)
    sources = rng.integers(0, g.num_vertices, size=args.num_queries)

    if cfg.mode == "depth":
        rep = serve_depth_bucketed(engine, g, args.alg, sources, args.batch,
                                   num_buckets=args.depth_buckets)
        for b in rep["buckets"]:
            print(f"bucket {b['bucket']} (deg>={b['min_degree']}, "
                  f"{b['queries']} queries): p99 "
                  f"{b['baseline_p99_ms']:.1f} -> "
                  f"{b['bucketed_p99_ms']:.1f} ms "
                  f"(p50 {b['baseline_p50_ms']:.1f} -> "
                  f"{b['bucketed_p50_ms']:.1f})", flush=True)
        print(f"stream p99 {rep['baseline_p99_ms']:.1f} -> "
              f"{rep['bucketed_p99_ms']:.1f} ms with {args.depth_buckets} "
              f"depth buckets", flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(dict(vars(args), **rep), f, indent=2)
            print(f"wrote {args.out}")
        print("GRAPH SERVE OK")
        return 0

    report = serve(engine, args.alg, sources, args.batch,
                   deadline_ms=args.deadline_ms,
                   queue_capacity=args.queue_capacity)
    if "admission" in report:
        a = report["admission"]
        print(f"admission: {a['admitted']} admitted, {a['rejected']} "
              f"rejected ({', '.join(a['reject_reasons']) or 'none'}) at "
              f"capacity {a['capacity']}", flush=True)
    if "sla" in report:
        s = report["sla"]
        print(f"SLA {s['deadline_ms']:.0f} ms: {s['met']} met, "
              f"{s['misses']} missed", flush=True)

    if report["ms_per_query"] is None:
        # Single-batch stream: everything landed in the cold batch.
        print(f"{args.alg}: {report['num_queries']} queries in one cold "
              f"batch of {args.batch} -> {report['cold_ms']:.0f} ms incl. "
              f"compilation (add batches for steady-state numbers)",
              flush=True)
    else:
        print(f"{args.alg}: {report['num_queries']} queries in batches of "
              f"{args.batch} -> {report['queries_per_sec']:.1f} q/s, "
              f"{report['ms_per_query']:.2f} ms/query amortized "
              f"(cold first batch {report['cold_ms']:.0f} ms; warm batch "
              f"p50={report['batch_p50_ms']:.1f} "
              f"p90={report['batch_p90_ms']:.1f} "
              f"p99={report['batch_p99_ms']:.1f} ms; "
              f"retraces={report['retraces']})", flush=True)
    if report["retraces"]:
        print(f"WARNING: {report['retraces']} compile-cache entries added "
              f"after warmup — batches are retracing", file=sys.stderr)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(dict(vars(args), **report), f, indent=2)
        print(f"wrote {args.out}")
    print("GRAPH SERVE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
