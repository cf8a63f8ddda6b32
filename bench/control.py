"""Read a cell's control: the run with the algorithm's control in the
program's place, which must come out as not correct.

Each algorithm module of ``bench/algorithms/`` defines its control (the
reference with one step down that a later change might be tempted to
take) and runs it when ``make_unit`` is given ``control=True``.  For each
seed this prints the compared numbers of one short run of a cell with the
control in place:

    python3 bench/control.py --workload uniform-s20.pagerank \
        --seeds 11,12,13 --seconds 5
"""
from __future__ import annotations

import json
import pathlib
import sys
import time


def main(argv=None) -> int:
    import argparse

    t_start = time.perf_counter()
    root = pathlib.Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import harness

    ap = argparse.ArgumentParser(description="Read a cell's control.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run_cell(root, args.workload, seed, args.seconds,
                                  False, t_start=t_start, control=True)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
