#!/usr/bin/env python3
"""Chip smoke test: the graph engine's main path on a TPU, end to end.

Runs in one process through the engine's own entry points
(``BSPEngine.execute``, ``runtime.session.ServeSession``) and checks every
result against the NumPy oracle or the ``runtime/verify.py`` certifier.

One chip (the default):

1. reference backend, RMAT scale 22 at edge factor 16
   (``configs/totem_rmat.RMAT_LARGE``: 4.19M vertices, 67.1M edges) in 4
   HIGH partitions resident on the chip — a stream of BFS queries served
   by a ``ServeSession`` with ``SERVE_SLOTS`` slots (every result
   certified), then 10 PageRank iterations against the NumPy oracle;
2. fused backend, RMAT scale 18 (``RMAT_SMALL``): BFS from 4 sources and
   10 PageRank iterations, against the NumPy oracles;
3. hybrid backend, uniform scale 18 (``UNIFORM_SMALL``) with a
   2,048-vertex dense block: the same checks.

``--four-chips`` runs only ``DistributedBSPEngine`` over a 4-device mesh
(fused on RMAT 18, hybrid on uniform 18; BFS and PageRank) and compares it
with a single-device engine on the same graph.

Each phase prints one JSON line: backend, graph size, supersteps, compile
and run seconds, whether the results matched, and the compute path every
kernel call site took (``kernels.ops.KERNEL_PATHS``).  A fused or hybrid
phase fails if any call site took the XLA chain or the Pallas
interpreter.  The last line is ``{"ok": true, "device": {...}}``; with no
TPU, or on any failure, the script exits non-zero without it.

Usage: python chip_smoke.py [--four-chips] [--seed N]
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

PARTS = 4
SERVE_SLOTS = 8            # Q=16 needs 16.02 GB of HBM at scale 22 (XLA)
SERVE_QUERIES = 12         # > slots, so converged slots are refilled
SERVE_CHUNK = 2
BFS_SOURCES = 4
PR_ITERS = 10
HYBRID_K_DENSE = 2_048     # the planner picks 0 on a uniform graph
KERNEL_SITES = {"fused": {"fused_superstep"},
                "hybrid": {"ell_spmv", "bottomup_scan", "dense_spmv",
                           "dense_spmv_minplus"}}


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends lowering to HLO and compiling it, from its own
    monitoring events — the rest of a timed section (tracing included) is
    run time."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **_) -> None:
        if name in self.EVENTS:
            self.total += secs


class Timed:
    def __init__(self, clock: CompileClock):
        self.clock = clock

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.total
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile = self.clock.total - self.c0


def graph_for(kind: str, scale: int, seed: int):
    from repro.core import graph as G
    from repro.configs.totem_rmat import RMAT_LARGE, RMAT_SMALL

    edge_factor = {22: RMAT_LARGE, 18: RMAT_SMALL}[scale].edge_factor
    if kind == "rmat":
        return G.rmat(scale, edge_factor, seed=seed)
    return G.uniform(scale, edge_factor, seed=seed)


def pick_sources(g, count: int, seed: int):
    import numpy as np

    live = np.flatnonzero(g.out_degrees() > 0)
    rng = np.random.default_rng(seed)
    return [int(s) for s in rng.choice(live, size=count, replace=False)]


def ranks_match(got, want) -> bool:
    import numpy as np

    n = len(want)
    return bool(np.all(np.isfinite(got))
                and np.allclose(got, want, rtol=1e-4, atol=1e-4 / n))


def max_rel_err(got, want) -> float:
    import numpy as np

    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want),
                                                         1e-30)))


def paths_since(before: collections.Counter) -> dict:
    from repro.kernels.ops import KERNEL_PATHS

    out: dict = {}
    for (site, path), n in KERNEL_PATHS.items():
        if n > before[(site, path)]:
            out.setdefault(site, []).append(path)
    return out


def check_paths(backend: str, paths: dict) -> None:
    """Fused/hybrid phases: every kernel site compiled by Mosaic, none on
    the XLA chain or the interpreter, and each expected site present."""
    for site, taken in paths.items():
        check(taken == ["mosaic"], f"{backend}: {site} took {taken}")
    missing = KERNEL_SITES[backend] - set(paths)
    check(not missing, f"{backend}: kernel sites never traced: {missing}")


def report(**rec) -> None:
    print(json.dumps(rec), flush=True)


def compiled_text(engine, program, num_steps: int, state) -> str:
    """HLO of the fixed-step loop ``execute(num_steps=)`` runs (the
    persistent cache serves the compile)."""
    from repro.core.bsp import BSPEngine

    return BSPEngine._run_fixed_batched.lower(
        engine, program, num_steps, engine._edges_or_none(program),
        state).compile().as_text()


def run_pagerank(engine, g, clock, backend: str, base: dict) -> None:
    from repro.algorithms.pagerank import (initial_state,
                                           make_pagerank_program, pagerank,
                                           pagerank_reference)
    from repro.core.bsp import batch_state

    with Timed(clock) as t:
        rank = pagerank(engine, PR_ITERS)
    want = pagerank_reference(g, PR_ITERS)
    ok = ranks_match(rank, want)
    custom_call = None
    if backend != "reference":
        program = make_pagerank_program(engine.pg.num_vertices)
        custom_call = "tpu_custom_call" in compiled_text(
            engine, program, PR_ITERS, batch_state(initial_state(engine.pg)))
    report(**base, alg="pagerank", supersteps=PR_ITERS,
           compile_s=t.compile, run_s=t.wall - t.compile, wall_s=t.wall,
           match=ok, max_rel_err=max_rel_err(rank, want),
           tpu_custom_call=custom_call)
    check(ok, f"{backend} pagerank differs from pagerank_reference")
    check(custom_call is not False,
          f"{backend} pagerank step has no tpu_custom_call")


def run_bfs(engine, g, clock, base: dict, seed: int) -> None:
    import numpy as np

    from repro.algorithms.bfs import bfs_batched, bfs_reference

    sources = pick_sources(g, BFS_SOURCES, seed)
    with Timed(clock) as t:
        levels, steps = bfs_batched(engine, sources)
    ok = all(np.array_equal(levels[i], bfs_reference(g, s))
             for i, s in enumerate(sources))
    report(**base, alg="bfs", queries=len(sources),
           supersteps=int(np.max(steps)), compile_s=t.compile,
           run_s=t.wall - t.compile, wall_s=t.wall, match=ok)
    check(ok, f"{base['backend']} bfs differs from bfs_reference")


def phase_reference_serve(clock, seed: int) -> None:
    """Reference backend at RMAT scale 22: served BFS + PageRank."""
    from repro.core import partition as PT
    from repro.core.bsp import BSPEngine
    from repro.runtime.session import ServeSession
    from repro.runtime.verify import ResultCertifier

    t0 = time.perf_counter()
    g = graph_for("rmat", 22, seed)
    pg = PT.partition(g, PARTS, PT.HIGH)
    engine = BSPEngine(pg, backend="reference")
    setup_s = time.perf_counter() - t0
    base = dict(phase="reference-rmat22", backend="reference", graph="rmat",
                scale=22, V=g.num_vertices, E=g.num_edges, setup_s=setup_s)

    session = ServeSession(engine, "bfs", slots=SERVE_SLOTS,
                           chunk=SERVE_CHUNK,
                           certifier=ResultCertifier("bfs", g))
    session.submit(pick_sources(g, SERVE_QUERIES, seed))
    with Timed(clock) as t:
        rep = session.drain()
    ok = (rep["completed"] == SERVE_QUERIES
          and rep["certified_ok"] == SERVE_QUERIES
          and rep["recomputed"] == 0 and not rep["quarantined"])
    report(**base, alg="bfs-serve", slots=SERVE_SLOTS,
           queries=SERVE_QUERIES, refills=rep["refills"],
           supersteps=rep["final_step"], compile_s=t.compile,
           run_s=t.wall - t.compile, wall_s=t.wall,
           latency_p50_ms=rep["latency_p50_ms"],
           latency_p99_ms=rep["latency_p99_ms"], match=ok)
    check(ok, f"served bfs not all certified: {rep}")
    run_pagerank(engine, g, clock, "reference", base)


def phase_kernels(clock, backend: str, seed: int) -> None:
    """Fused (RMAT 18) or hybrid (uniform 18) backend on Mosaic kernels."""
    from repro.core import partition as PT
    from repro.core.bsp import BSPEngine
    from repro.kernels.ops import KERNEL_PATHS

    before = collections.Counter(KERNEL_PATHS)
    kind = "rmat" if backend == "fused" else "uniform"
    t0 = time.perf_counter()
    g = graph_for(kind, 18, seed)
    pg = PT.partition(g, PARTS, PT.HIGH)
    if backend == "fused":
        # Direction optimization's transposed ELL pads every row to the
        # largest in-degree (27 GB here), so fused BFS stays push-only.
        engine = BSPEngine(pg, backend="fused", direction_switch=False)
    else:
        engine = BSPEngine(pg, backend="hybrid",
                           hybrid_k_dense=HYBRID_K_DENSE)
    base = dict(phase=f"{backend}-{kind}18", backend=backend, graph=kind,
                scale=18, V=g.num_vertices, E=g.num_edges,
                setup_s=time.perf_counter() - t0)
    run_bfs(engine, g, clock, base, seed)
    run_pagerank(engine, g, clock, backend, base)
    paths = paths_since(before)
    report(phase=base["phase"], backend=backend, kernel_paths=paths)
    check_paths(backend, paths)


def phase_four_chips(clock, seed: int) -> None:
    """DistributedBSPEngine on 4 chips vs a single-device engine."""
    import jax
    import numpy as np

    from repro.algorithms.bfs import BFS_PROGRAM, gather_batch
    from repro.algorithms.bfs import multi_source_state
    from repro.algorithms.pagerank import pagerank, pagerank_distributed
    from repro.core import partition as PT
    from repro.core.bsp import BSPEngine, DistributedBSPEngine
    from repro.kernels.ops import KERNEL_PATHS

    check(len(jax.devices()) >= PARTS,
          f"--four-chips needs {PARTS} devices, JAX sees "
          f"{len(jax.devices())}")
    mesh = jax.make_mesh((PARTS,), ("parts",))
    for backend, kind in (("fused", "rmat"), ("hybrid", "uniform")):
        before = collections.Counter(KERNEL_PATHS)
        t0 = time.perf_counter()
        g = graph_for(kind, 18, seed)
        pg = PT.partition(g, PARTS, PT.HIGH)
        opts = dict(backend=backend)
        if backend == "fused":
            opts["direction_switch"] = False
        dist = DistributedBSPEngine(pg, mesh, **opts)
        single = BSPEngine(pg, **opts)
        base = dict(phase=f"four-chips-{backend}-{kind}18", backend=backend,
                    graph=kind, scale=18, V=g.num_vertices, E=g.num_edges,
                    setup_s=time.perf_counter() - t0)

        sources = pick_sources(g, BFS_SOURCES, seed)
        state = {"level": multi_source_state(pg, sources)}
        with Timed(clock) as t:
            out, steps = dist.execute(BFS_PROGRAM, dict(state))
            got = gather_batch(pg, out["level"])
        shards = out["level"].addressable_shards
        devices = {s.device for s in shards}
        nonempty = all(s.data.size > 0 for s in shards)
        real = bool(np.all(np.asarray(pg.vertex_mask).any(axis=1)))
        want_state, _ = single.execute(BFS_PROGRAM, dict(state))
        ok = bool(np.array_equal(got, gather_batch(pg, want_state["level"])))
        report(**base, alg="bfs", queries=len(sources),
               supersteps=int(np.max(np.asarray(steps))),
               compile_s=t.compile, run_s=t.wall - t.compile, wall_s=t.wall,
               devices=len(devices), shards_nonempty=nonempty and real,
               match_single_device=ok)
        check(len(devices) == PARTS and nonempty and real,
              f"{backend}: state not spread over {PARTS} devices "
              f"({len(devices)} devices, nonempty={nonempty}, "
              f"every partition has vertices={real})")
        check(ok, f"distributed {backend} bfs differs from single device")

        with Timed(clock) as t:
            rank = pagerank_distributed(dist, PR_ITERS)
        want = pagerank(single, PR_ITERS)
        ok = ranks_match(rank, want)
        report(**base, alg="pagerank", supersteps=PR_ITERS,
               compile_s=t.compile, run_s=t.wall - t.compile, wall_s=t.wall,
               match_single_device=ok, max_rel_err=max_rel_err(rank, want))
        check(ok, f"distributed {backend} pagerank differs from single "
                  f"device")
        paths = paths_since(before)
        report(phase=base["phase"], backend=backend, kernel_paths=paths)
        for site, taken in paths.items():
            check(taken == ["mosaic"], f"{backend}: {site} took {taken}")
        want = {"fused": "fused_superstep", "hybrid": "outbox_reduce"}
        check(want[backend] in paths,
              f"{backend}: {want[backend]} never traced")
        del dist, single, out, want_state
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device DistributedBSPEngine phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="graph and query-source seed")
    args = ap.parse_args(argv)

    try:
        import jax

        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the engine ({e}); run from the "
              f"repository root, next to src/repro", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found — JAX sees {len(jax.devices())} "
              f"{dev.platform} device(s); this smoke test needs a TPU chip "
              f"and never falls back to the CPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    clock = CompileClock()
    try:
        if args.four_chips:
            phase_four_chips(clock, args.seed)
        else:
            phase_reference_serve(clock, args.seed)
            gc.collect()
            phase_kernels(clock, "fused", args.seed)
            gc.collect()
            phase_kernels(clock, "hybrid", args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
