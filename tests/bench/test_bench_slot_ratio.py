"""The per-layer metric that reads the program's sliced-ELL pack counter."""
import sys

import bench_helpers
from bench import harness

PACK = "repro.ell.pack"
SPLIT = "repro.hybrid.split"


def _slot_ratio():
    return harness.load_metric(bench_helpers.REPO, "ell_slot_ratio")(None)


def test_ell_slot_ratio_reads_the_pack_counter(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "snapshot", lambda: {
        PACK: {"count": 2, "slots": 61.0, "nonzeros": 50.0},
        SPLIT: {"count": 2, "total_s": 2.5, "self_s": 2.0}})
    assert _slot_ratio() == 61.0 / 50.0


def test_ell_slot_ratio_is_none_without_a_pack(monkeypatch):
    # A program that builds no sliced ELL (another backend), or one from
    # before the counter: nothing to read, no error.
    import repro
    from repro import obs

    monkeypatch.setattr(obs, "snapshot", lambda: {
        SPLIT: {"count": 1, "total_s": 2.5, "self_s": 2.0}})
    assert _slot_ratio() is None
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _slot_ratio() is None


def test_cpu_run_reads_the_slot_ratio_of_a_hybrid_cell(tmp_path):
    # The program's own count: the sliced ELL's slots over its real
    # non-zeros.  Scale 12 is the least at which the planner leaves an ELL
    # (below it the whole graph goes to the dense block).
    from repro import obs

    root = bench_helpers.make_root(tmp_path, scale=12)
    before = obs.snapshot().get(PACK, {})
    harness.run_cell(root, "uniform-s20.bfs", 2**31 + 7, 0.2, False,
                     require_tpu=False, expect_mosaic=False)
    row = obs.snapshot()[PACK]
    slots = row["slots"] - before.get("slots", 0)
    nonzeros = row["nonzeros"] - before.get("nonzeros", 0)
    assert row["count"] > before.get("count", 0)
    assert nonzeros > 0 and slots >= nonzeros
    assert _slot_ratio() == row["slots"] / row["nonzeros"]
