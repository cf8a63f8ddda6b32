#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix and per-layer metrics are found
by name from ``BENCHMARK.json`` (see ``bench/harness.py``).  The run needs
a TPU: with no accelerator, or fewer chips than the cell asks for, it
names what JAX sees and exits non-zero without a result.  With
``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of the window gives its per-layer metrics.
"""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
