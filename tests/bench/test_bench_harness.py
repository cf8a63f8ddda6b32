"""The harness end to end on the CPU at a tiny size: cells found by name,
a run that is correct, and the refusal to run without a TPU."""
import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench_helpers import make_root  # also puts bench on the path
from bench import harness

REPO = harness.ROOT
SEED = 2**31 + 11
CELLS = ["g500-s20.bfs", "uniform-s20.pagerank", "uniform-s20.bfs"]


def cpu_run(root, workload, seconds=0.3, **kw):
    kw.setdefault("expect_mosaic", False)
    return harness.run_cell(root, workload, SEED, seconds, False,
                            require_tpu=False, **kw)


@pytest.mark.parametrize("workload", CELLS)
def test_cpu_run_is_correct(tiny_root, workload):
    setup = []
    result = cpu_run(tiny_root, workload, log=setup.append)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["metrics"]["gteps"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "checks"
    assert result["checks"]["compiles_in_window"] == {"value": 0,
                                                      "limit": 0}
    for stage in ("generate_s", "partition_s", "engine_build_s",
                  "warmup_s"):
        assert setup[0][stage] >= 0
    lines = harness.check_lines(result)
    assert all(line.startswith("check ") and " limit " in line
               for line in lines)


# A traffic mix of an algorithm the benchmark did not have, added as two
# files: the mix and the algorithm's module with its own reference.
CC_MODULE = """
import numpy as np

TEPS_RULE = "stored_edges"


def make_unit(engine, gg, traffic, seed, control=False):
    from repro.algorithms.cc import connected_components

    def unit(i):
        labels, steps = connected_components(engine)
        return [], np.asarray(labels)[None], int(steps)
    return unit


def traversed_edges(gg, traffic, answers):
    return gg.num_edges


def reference(gg):
    label = np.arange(gg.num_vertices, dtype=np.float64)
    src = gg.edge_sources()
    while True:
        new = label.copy()
        np.minimum.at(new, gg.col, label[src])
        if np.array_equal(new, label):
            return label
        label = new


def check(gg, traffic, units, seed):
    want = reference(gg)
    wrong = [int(np.count_nonzero(u.answers[0] != want)) for u in units]
    return {"label_mismatches": sum(wrong),
            "units_failed": sum(w > 0 for w in wrong),
            "units_checked": len(wrong)}
"""


def test_new_config_traffic_and_metric_are_found_by_name(tiny_root):
    before = hashlib.sha256((REPO / "BENCHMARK.json").read_bytes()).digest()
    # A configuration, two traffic mixes (a batched BFS, and connected
    # components with its algorithm module) and a per-layer metric, each
    # added as files.
    cfg = json.loads((tiny_root / "bench/configs/g500-s20.json").read_text())
    cfg["graph"]["scale"] = 8
    (tiny_root / "bench/configs/ring-s8.json").write_text(json.dumps(cfg))
    mix = json.loads((tiny_root / "bench/traffic/bfs.json").read_text())
    mix.update(keys=6, queries=2)
    (tiny_root / "bench/traffic/bfs-q2.json").write_text(json.dumps(mix))
    (tiny_root / "bench/traffic/cc.json").write_text(json.dumps(
        {"algorithm": "cc", "teps_edges": "stored_edges",
         "limits": {"label_mismatches": 0}}))
    (tiny_root / "bench/algorithms/cc.py").write_text(CC_MODULE)
    (tiny_root / "bench/metrics/queries_per_unit.py").write_text(
        "def read(run):\n    return run.queries\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "ring-s8", "source": "test",
                            "file": "bench/configs/ring-s8.json",
                            "reduced": [], "why": "test"})
    for traffic in ("bfs-q2", "cc"):
        spec["workloads"].append({"name": f"ring-s8.{traffic}",
                                  "config": "ring-s8", "traffic": traffic,
                                  "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "queries_per_unit", "unit": "1",
                              "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "gteps",
                              "workloads": ["ring-s8.bfs-q2"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell, config, traffic, layer = harness.load_cell(tiny_root,
                                                     "ring-s8.bfs-q2")
    assert config["graph"]["scale"] == 8 and traffic["queries"] == 2
    assert [m["name"] for m in layer] == ["queries_per_unit"]
    window = []
    result = cpu_run(tiny_root, "ring-s8.bfs-q2",
                     log=lambda rec: window.append(rec.get("units")))
    assert result["correct"], result["checks"]
    # Each unit searched two keys, and counted both searches' edges.
    assert all(len(keys) == 2 for keys, *_ in window[-1])
    read = harness.load_metric(tiny_root, "queries_per_unit")
    assert read(harness.Run(
        workload=cell, config=config, traffic=traffic, stages={},
        num_vertices=1, num_edges=1, distinct_edges=1, units=[],
        peaks={}, engine={})) == 2

    result = cpu_run(tiny_root, "ring-s8.cc")
    assert result["correct"], result["checks"]
    assert result["checks"]["label_mismatches"] == {"value": 0, "limit": 0}
    after = hashlib.sha256((REPO / "BENCHMARK.json").read_bytes()).digest()
    assert before == after


def test_pagerank_mix_of_more_than_one_query_is_refused(tiny_root):
    mix = json.loads((tiny_root / "bench/traffic/pagerank.json").read_text())
    mix["queries"] = 8
    (tiny_root / "bench/traffic/pagerank.json").write_text(json.dumps(mix))
    with pytest.raises(harness.BenchError, match="queries 1"):
        cpu_run(tiny_root, "uniform-s20.pagerank")


def test_unknown_names_are_errors(tiny_root):
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.load_cell(tiny_root, "nope.bfs")
    with pytest.raises(harness.BenchError, match="has no reader"):
        harness.load_metric(tiny_root, "nope")


def test_run_refuses_without_a_tpu(tiny_root):
    with pytest.raises(harness.NoChip, match="cpu device"):
        harness.run_cell(tiny_root, CELLS[0], SEED, 0.1, False)


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_cli_without_a_tpu_exits_nonzero_and_prints_no_result():
    proc = _cli(REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "JAX sees" in proc.stderr and "cpu" in proc.stderr


def test_cli_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
