"""A cell of several chips: the engine over the cell's mesh, the graph drawn
on every chip of it, and the partition count that has to divide over them.
The runs on several devices go to a subprocess that forces four CPU
devices, so the forced count never reaches this process's JAX."""
import json
import os
import subprocess
import sys

import pytest

from bench_helpers import REPO, make_root  # also puts bench on the path
from bench import gen, harness

SEED = 2**31 + 23

# Runs ``g500-s20.bfs`` and a copy of it with ``chips`` 4 through run_cell
# at the helpers' tiny scale, recording each engine the program's BFS is
# handed and its levels per key.
HARNESS_RUN = r"""
import importlib, json, pathlib, sys, tempfile
from bench_helpers import make_root
from bench import harness
from repro.core.bsp import DistributedBSPEngine

seed = int(sys.argv[1])
root = make_root(pathlib.Path(tempfile.mkdtemp()))
spec = json.loads((root / "BENCHMARK.json").read_text())
spec["workloads"].append({"name": "g500-s20.bfs.4chip", "config": "g500-s20",
                          "traffic": "bfs", "chips": 4, "why": "test"})
(root / "BENCHMARK.json").write_text(json.dumps(spec))

seen = []
program = importlib.import_module("repro.algorithms.bfs")
batched = program.bfs_batched


def recording(engine, sources):
    levels, steps = batched(engine, sources)
    mesh = getattr(engine, "mesh", None)
    seen.append({"distributed": isinstance(engine, DistributedBSPEngine),
                 "mesh_devices": None if mesh is None else
                 sorted(d.id for d in mesh.devices.flat),
                 "levels": {int(k): row.tolist()
                            for k, row in zip(sources, levels)}})
    return levels, steps


program.bfs_batched = recording
out = {}
for workload in ("g500-s20.bfs", "g500-s20.bfs.4chip"):
    seen.clear()
    setup = []
    result = harness.run_cell(root, workload, seed, 0.3, False,
                              require_tpu=False, expect_mosaic=False,
                              log=setup.append)
    out[workload] = {"result": result, "setup": setup[0],
                     "calls": list(seen)}
print("RESULT " + json.dumps(out))
"""

# Draws two graphs at scale 12 on 1, 2, 3 and 4 devices, recording the
# device and chunk count of every drawing program.
GEN_RUN = r"""
import json
import jax
import numpy as np
from bench import gen

specs = [dict(generator="kronecker", structure_seed=1, scale=12,
              edge_factor=16, a=0.57, b=0.19, c=0.19, undirected=True),
         dict(generator="uniform", structure_seed=1, scale=12,
              edge_factor=16, undirected=False)]
drawn = []
tuples = gen._tuples


def recording(*args, **kw):
    out = tuples(*args, **kw)
    drawn.append([sorted(d.id for d in out[0].devices()),
                  kw["num_tuples"] // kw["chunk"]])
    return out


gen._tuples = recording
report = []
for chunk in (gen.CHUNK_EDGES, 1 << 12):
    gen.CHUNK_EDGES = chunk
    for spec in specs:
        one = gen.generate(spec, 2**31 + 7)
        for count in (1, 2, 3, 4):
            drawn.clear()
            g = gen.generate(spec, 2**31 + 7, jax.devices()[:count])
            report.append({
                "chunk": chunk, "devices": count, "programs": list(drawn),
                "same": all(np.array_equal(getattr(g, k), getattr(one, k))
                            for k in ("row_ptr", "col", "ids"))})
print("RESULT " + json.dumps(report))
"""


def run_on_four_devices(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "tests" / "bench"), str(REPO),
                    str(REPO / "src"), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", script, *map(str, args)],
                          env=env, capture_output=True, text=True,
                          timeout=240, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_four_chip_cell_runs_the_distributed_engine_and_matches_one_chip():
    out = run_on_four_devices(HARNESS_RUN, SEED)
    one, four = out["g500-s20.bfs"], out["g500-s20.bfs.4chip"]

    assert one["setup"]["chips"] == 1 and four["setup"]["chips"] == 4
    assert one["result"]["device"]["count"] == 1
    assert four["result"]["device"]["count"] == 4
    assert all(not c["distributed"] for c in one["calls"])
    assert four["calls"] and all(
        c["distributed"] and c["mesh_devices"] == [0, 1, 2, 3]
        for c in four["calls"])

    assert one["result"]["correct"], one["result"]["checks"]
    assert four["result"]["attempted"] >= 1
    assert four["result"]["failed"] == 0
    # Every number compared holds but the compile count: the program's
    # DistributedBSPEngine builds a new jit for each call (PERF.md, open
    # questions), which the window's check counts, as it should.
    failing = {name for name, c in four["result"]["checks"].items()
               if c["value"] > c["limit"]}
    assert failing <= {"compiles_in_window"}
    assert four["result"]["correct"] == (not failing)

    # Each key searched on both: the same levels, vertex for vertex.
    levels_one, levels_four = {}, {}
    for calls, into in ((one["calls"], levels_one),
                        (four["calls"], levels_four)):
        for c in calls:
            into.update(c["levels"])
    both = set(levels_one) & set(levels_four)
    assert both
    for key in both:
        assert levels_four[key] == levels_one[key], key


def test_generation_is_the_same_on_any_device_count():
    report = run_on_four_devices(GEN_RUN)
    assert len(report) == 2 * 2 * 4
    for rec in report:
        assert rec["same"], rec
        chunks = sum(n for _, n in rec["programs"])
        devices = [d for ds, _ in rec["programs"] for d in ds]
        # one program per device that draws, each within its share
        assert len(devices) == len(set(devices)) == min(rec["devices"],
                                                        chunks)
        assert max(n for _, n in rec["programs"]) <= -(-chunks
                                                       // rec["devices"])
    # the small chunk gives 16 chunks a graph: every device draws
    assert any(rec["chunk"] == 1 << 12 and rec["devices"] == 4
               and len(rec["programs"]) == 4 for rec in report)


@pytest.mark.parametrize("chunks, parts, want", [
    (16, 4, [(0, 4), (4, 4), (8, 4), (12, 4)]),
    (10, 4, [(0, 3), (3, 3), (6, 3), (9, 1)]),
    (5, 4, [(0, 2), (2, 2), (4, 1), (5, 0)]),
    (1, 4, [(0, 1), (1, 0), (1, 0), (1, 0)]),
    (32, 1, [(0, 32)]),
])
def test_chunk_shares(chunks, parts, want):
    assert gen.chunk_shares(chunks, parts) == want


def _with_cell(root, **cell):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "g500-s20.bfs.test", "config":
                              "g500-s20", "traffic": "bfs", "why": "test",
                              **cell})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return "g500-s20.bfs.test"


def test_partitions_that_do_not_divide_over_the_chips_are_refused(tmp_path):
    root = make_root(tmp_path)
    cfg_file = root / "bench/configs/g500-s20.json"
    cfg = json.loads(cfg_file.read_text())
    cfg["partition"]["parts"] = 6
    cfg_file.write_text(json.dumps(cfg))
    workload = _with_cell(root, chips=4)
    with pytest.raises(harness.BenchError, match="6 partitions"):
        harness.run_cell(root, workload, SEED, 0.1, False,
                         require_tpu=False)


def test_fewer_devices_than_chips_is_no_chip(tmp_path):
    # This process sees one CPU device; a cell of four never runs on it.
    root = make_root(tmp_path)
    workload = _with_cell(root, chips=4)
    with pytest.raises(harness.NoChip, match="needs 4"):
        harness.run_cell(root, workload, SEED, 0.1, False,
                         require_tpu=False)
