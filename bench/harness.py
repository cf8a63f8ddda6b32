"""One benchmark cell, one run: set-up, measured window, check, metrics.

Everything a cell is made of is found by name under a root directory that
holds ``BENCHMARK.json``:

- the cell: an entry of ``workloads`` (``config``, ``traffic``, ``chips``);
- its configuration: the JSON file the ``configs`` entry names (graph
  generator, partitioning, engine options);
- its traffic mix: ``bench/traffic/<traffic>.json``, the parameters of
  one algorithm's traffic (queries, keys, the edge-count rule, the limits
  of the check);
- that algorithm: ``bench/algorithms/<algorithm>.py``, which gives
  ``TEPS_RULE`` (the ``teps_edges`` its mixes name),
  ``make_unit(engine, graph, traffic, seed, control)`` (one unit through
  the program's entry point, or through the control in its place),
  ``traversed_edges(graph, traffic, answers)`` and
  ``check(graph, traffic, units, seed)`` (the numbers compared with its
  reference, ``units_failed`` and ``units_checked`` among them);
- each per-layer metric: ``bench/metrics/<name>.py``, whose ``read(run)``
  returns the value, or None where the run holds nothing to read.

Adding a cell, a configuration, a mix, an algorithm or a metric adds
files; nothing here changes.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import gen, roofline, trace as tr

ROOT = pathlib.Path(__file__).resolve().parent.parent
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class BenchError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found or
    read."""


# ---------------------------------------------------------------------------
# Finding a cell's pieces by name
# ---------------------------------------------------------------------------

def load_spec(root: pathlib.Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _entry(entries: List[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json (known: "
                     f"{sorted(e['name'] for e in entries)})")


def load_cell(root: pathlib.Path, workload: str):
    """(cell, configuration, traffic, per-layer metric entries) of
    ``workload``."""
    spec = load_spec(root)
    cell = _entry(spec["workloads"], workload, "workload")
    cfg_entry = _entry(spec["configs"], cell["config"], "config")
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic_file = root / "bench" / "traffic" / f"{cell['traffic']}.json"
    if not traffic_file.is_file():
        raise BenchError(f"traffic mix {cell['traffic']!r} has no file "
                         f"{traffic_file}")
    traffic = json.loads(traffic_file.read_text())
    layer = [m for m in spec["per_layer"]
             if workload in m.get("workloads", [workload])]
    return cell, config, traffic, layer


def _load_module(path: pathlib.Path, name: str):
    module_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def load_metric(root: pathlib.Path, name: str) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"per-layer metric {name!r} has no reader {path}")
    return _load_module(path, f"bench_metric_{name.replace('.', '_')}").read


def load_algorithm(root: pathlib.Path, traffic: dict):
    """``bench/algorithms/<algorithm>.py`` of a traffic mix, checked
    against the mix's edge-count rule."""
    name = traffic["algorithm"]
    path = root / "bench" / "algorithms" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"algorithm {name!r} has no module {path}")
    module = _load_module(path, f"bench_algorithm_{name.replace('.', '_')}")
    if traffic["teps_edges"] != module.TEPS_RULE:
        raise BenchError(f"a {name} mix counts edges by "
                         f"{module.TEPS_RULE!r}, not "
                         f"{traffic['teps_edges']!r}")
    return module


# ---------------------------------------------------------------------------
# What a run hands to the metric readers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Unit:
    """One whole algorithm run inside the window."""

    keys: List[int]            # its queries' keys (BFS sources), if any
    start: float               # host clock, seconds
    end: float
    supersteps: int
    edges: int                 # traversed edges by the traffic's rule
    answers: np.ndarray        # one answer per query, [Q, n]


@dataclasses.dataclass
class Run:
    workload: dict
    config: dict
    traffic: dict
    stages: Dict[str, float]
    num_vertices: int
    num_edges: int             # stored entries
    distinct_edges: int
    units: List[Unit]
    peaks: dict
    engine: dict               # backend and the hybrid plan's k_dense
    trace: Optional[tr.Summary] = None

    @property
    def queries(self) -> int:
        return int(self.traffic.get("queries", 1))


class CompileLog:
    """Counts JAX's compile requests and persistent-cache hits and misses
    while open, from its own monitoring events."""

    def __init__(self):
        self.requests = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

    def _duration(self, name: str, secs: float, **_) -> None:
        if name == COMPILE_EVENTS[1]:
            self.requests += 1
        if name in COMPILE_EVENTS:
            self.seconds += secs

    def _event(self, name: str, **_) -> None:
        self.hits += name == CACHE_HIT
        self.misses += name == CACHE_MISS

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------

def non_mosaic_calls(before: collections.Counter,
                     after: collections.Counter) -> int:
    """Kernel call sites traced since ``before`` on the XLA chain or the
    Pallas interpreter instead of the compiled (Mosaic) kernel."""
    return sum(n - before[(site, path)] for (site, path), n in after.items()
               if path != "mosaic" and n > before[(site, path)])


def limits_of(traffic: dict, config: dict, expect_mosaic: bool) -> dict:
    """Each compared number's limit: a run is correct when every number is
    at or below its limit and at least one unit was checked."""
    limits = {"compiles_in_window": 0, "units_failed": 0}
    limits.update(traffic["limits"])
    if expect_mosaic and config["engine"].get("backend") in ("hybrid",
                                                             "fused"):
        limits["non_mosaic_kernel_calls"] = 0
    return limits


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------

def devices_for(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < chips):
        seen = collections.Counter(d.platform for d in devices)
        raise NoChip(
            f"this cell needs {chips} TPU chip(s); JAX sees "
            + ", ".join(f"{n} {p} device(s)" for p, n in seen.items())
            + f" ({devices[0].device_kind}); the benchmark never falls back "
              f"to another platform")
    return devices[:chips]


def _peak_bytes(devices) -> Optional[int]:
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(int(s["peak_bytes_in_use"]) for s in stats)


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: Optional[float] = None,
             control: bool = False, require_tpu: bool = True,
             expect_mosaic: Optional[bool] = None,
             log: Callable[[dict], None] = lambda rec: None) -> dict:
    """Run ``workload`` once; returns the result line's object.

    ``t_start`` is when the process started (set-up counts from there);
    ``control`` puts the algorithm's control in the program's place;
    ``log`` receives the set-up record and the window's per-unit record.
    ``require_tpu=False`` and ``expect_mosaic=False`` let a test drive a
    run on the CPU.
    """
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    cell, config, traffic, layer = load_cell(root, workload)
    algorithm = load_algorithm(root, traffic)
    devices = devices_for(int(cell["chips"]), require_tpu)
    kind = devices[0].device_kind
    if expect_mosaic is None:
        expect_mosaic = devices[0].platform == "tpu"
    peaks = (roofline.peaks(kind, root / "bench" / "peaks.json")
             if trace else {})

    from repro.core import partition as PT
    from repro.core.bsp import BSPEngine
    from repro.core.graph import CSRGraph
    from repro.kernels.ops import KERNEL_PATHS

    stages: Dict[str, float] = {}
    with CompileLog() as setup_log:
        t = time.perf_counter()
        gg = gen.generate(config["graph"], seed)
        stages["generate_s"] = time.perf_counter() - t
        generate_peak = _peak_bytes(devices)

        t = time.perf_counter()
        part = config["partition"]
        pg = PT.partition(CSRGraph(gg.row_ptr, gg.col), int(part["parts"]),
                          part["strategy"], align=int(part.get("align", 8)))
        stages["partition_s"] = time.perf_counter() - t

        t = time.perf_counter()
        engine = BSPEngine(pg, **config["engine"])
        stages["engine_build_s"] = time.perf_counter() - t

        unit = algorithm.make_unit(engine, gg, traffic, seed, control)
        paths_before = collections.Counter(KERNEL_PATHS)
        t = time.perf_counter()
        unit(0)
        stages["warmup_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    log({"stage": "setup", "workload": workload, "seed": seed,
         "setup_s": setup_s, **stages,
         "compile_s": setup_log.seconds,
         "cache_hits": setup_log.hits, "cache_misses": setup_log.misses,
         "cache": "cold" if setup_log.misses else "warm",
         "generate_peak_bytes": generate_peak,
         "V": gg.num_vertices, "E": gg.num_edges})

    units: List[Unit] = []
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    with CompileLog() as window_log:
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                w0 = time.perf_counter()
                i = 1
                while time.perf_counter() - w0 < seconds:
                    with jax.profiler.TraceAnnotation("bench.unit"):
                        u0 = time.perf_counter()
                        keys, answers, steps = unit(i)
                        u1 = time.perf_counter()
                    with jax.profiler.TraceAnnotation("bench.between_units"):
                        units.append(Unit(keys, u0, u1, steps, 0, answers))
                        i += 1
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
    memory_peak = _peak_bytes(devices)
    engine_info = {"backend": engine.backend,
                   "k_dense": (engine.hybrid_plan() or {}).get("k_dense")}
    paths_after = collections.Counter(KERNEL_PATHS)
    del engine, pg, unit
    gc.collect()

    for u in units:
        u.edges = algorithm.traversed_edges(gg, traffic, u.answers)
    log({"stage": "window", "workload": workload, "seed": seed,
         "units": [[u.keys, u.end - u.start, u.supersteps, u.edges]
                   for u in units]})
    checks = algorithm.check(gg, traffic, units, seed)
    checks["compiles_in_window"] = window_log.requests
    limits = limits_of(traffic, config, expect_mosaic)
    if "non_mosaic_kernel_calls" in limits:
        checks["non_mosaic_kernel_calls"] = non_mosaic_calls(paths_before,
                                                             paths_after)
    correct = bool(units) and checks.get("units_checked", 0) > 0 and all(
        checks[name] <= limit for name, limit in limits.items())

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(units),
              "failed": int(checks["units_failed"])}
    run = Run(workload=cell, config=config, traffic=traffic,
              stages=stages, num_vertices=gg.num_vertices,
              num_edges=gg.num_edges, distinct_edges=gg.distinct_edges(),
              units=units, peaks=peaks, engine=engine_info)
    if trace_dir:
        try:
            loaded = tr.load(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = tr.window_of(loaded, "bench.window")
        run.trace = tr.summarize(loaded, lo, hi)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        metrics = {}
        for entry in layer:
            value = load_metric(root, entry["name"])(run)
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
        result["breakdown"] = {"device_ops": run.trace.top_ops(),
                               "idle_gaps": run.trace.idle}
    else:
        metrics = end_to_end(run, memory_peak, setup_s)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {name: {"value": checks[name], "limit": limit}
                        for name, limit in limits.items()}
    return result


def end_to_end(run: Run, memory_peak: Optional[int],
               setup_s: float) -> dict:
    metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
    if run.units:
        wall = run.units[-1].end - run.units[0].start
        metrics["gteps"] = {"value": sum(u.edges for u in run.units)
                            / wall / 1e9, "unit": "GTEPS"}
    if memory_peak is not None:
        metrics["hbm_peak_gb"] = {"value": memory_peak / 1e9, "unit": "GB"}
    return metrics


def check_lines(result: dict) -> List[str]:
    """``<name> <value> limit <limit>`` for every number compared."""
    return [f"check {name} {c['value']} limit {c['limit']}"
            for name, c in result["checks"].items()]


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"bench: cannot import the program ({e}); run from a checkout "
              f"that holds src/repro", file=sys.stderr)
        return 2
    try:
        enable_compile_cache()
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start,
                          log=lambda rec: print(json.dumps(rec), flush=True))
    except (NoChip, BenchError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    for line in check_lines(result):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
