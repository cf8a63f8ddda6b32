"""Helpers of the benchmark's tests: the ``bench`` package on the path, and
a throw-away benchmark root whose cells are the committed ones at a size
the CPU runs in a second.  (Not a ``conftest.py``: the repository's tests
import names from ``tests/conftest.py`` by module name.)"""
import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_SCALE = 9
CPU_PEAKS = {"cpu": {"source": "test stand-in, not a measured peak",
                     "hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e9,
                     "hbm_bytes": 1e9}}


def make_root(tmp: pathlib.Path, scale: int = TINY_SCALE) -> pathlib.Path:
    """A copy of the committed benchmark with every graph at ``scale``."""
    bench = tmp / "bench"
    shutil.copytree(REPO / "bench" / "traffic", bench / "traffic")
    shutil.copytree(REPO / "bench" / "algorithms", bench / "algorithms",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(REPO / "bench" / "metrics", bench / "metrics")
    (bench / "configs").mkdir()
    (bench / "peaks.json").write_text(json.dumps(CPU_PEAKS))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for entry in spec["configs"]:
        cfg = json.loads((REPO / entry["file"]).read_text())
        cfg["graph"]["scale"] = scale
        cfg["partition"].pop("align", None)
        (tmp / entry["file"]).write_text(json.dumps(cfg))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
