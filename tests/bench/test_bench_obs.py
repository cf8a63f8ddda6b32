"""The per-layer metric that reads the program's span table."""
import sys

import bench_helpers
from bench import harness


def _read():
    return harness.load_metric(bench_helpers.REPO, "hybrid_split_s")(None)


def test_hybrid_split_s_reads_the_split_span(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "snapshot", lambda: {
        obs.HYBRID_SPLIT: {"count": 1, "total_s": 2.5, "self_s": 2.0},
        obs.EXECUTE: {"count": 9, "total_s": 30.0, "self_s": 1.0}})
    assert _read() == 2.0


def test_hybrid_split_s_is_none_without_a_split(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "snapshot", lambda: {
        obs.EXECUTE: {"count": 9, "total_s": 30.0, "self_s": 1.0}})
    assert _read() is None


def test_hybrid_split_s_is_none_without_the_span_table(monkeypatch):
    # A program from before the span table: nothing to read, no error.
    import repro
    from repro import obs

    monkeypatch.setattr(obs, "snapshot", lambda: {
        obs.HYBRID_SPLIT: {"count": 1, "total_s": 2.5, "self_s": 2.0}})
    assert _read() == 2.0
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _read() is None


def test_traced_cpu_run_reads_the_split_of_a_hybrid_cell(tmp_path):
    # The reader's number is the split the program timed in this process.
    from repro import obs

    root = bench_helpers.make_root(tmp_path)
    before = obs.snapshot().get(obs.HYBRID_SPLIT, {"self_s": 0.0})
    harness.run_cell(root, "uniform-s20.pagerank", 2**31 + 5, 0.2, False,
                     require_tpu=False, expect_mosaic=False)
    split = obs.snapshot()[obs.HYBRID_SPLIT]["self_s"] - before["self_s"]
    assert split > 0
    assert _read() == obs.snapshot()[obs.HYBRID_SPLIT]["self_s"]
