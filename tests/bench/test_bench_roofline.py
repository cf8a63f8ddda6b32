"""The peak table and the least-bytes functions, on shapes computed by
hand."""
import json

import pytest

import bench_helpers  # noqa: F401  (puts bench on the path)
from bench import roofline


def test_v5e_peaks_from_the_table():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_raises(tmp_path):
    with pytest.raises(KeyError, match="not in peaks.json"):
        roofline.peaks("TPU v99")
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"cpu": {"hbm_bytes_per_s": 1.0}}))
    assert roofline.peaks("cpu", table)["hbm_bytes_per_s"] == 1.0
    with pytest.raises(KeyError):
        roofline.peaks("TPU v5 lite", table)


def test_superstep_least_bytes_by_hand():
    # 2**20 vertices, 33,554,432 stored entries, one query:
    # 4 B per entry + (4 B read + 4 B write) per vertex.
    assert roofline.superstep_least_bytes(1 << 20, 33_554_432) == (
        134_217_728 + 8_388_608)
    assert roofline.superstep_least_bytes(10, 100, queries=3) == 400 + 240


def test_ell_spmv_least_bytes_by_hand():
    # 16,777,216 non-zeros over 2**20 rows, one query: 4 B each.
    assert roofline.ell_spmv_least_bytes(16_777_216, 1 << 20) == (
        4 * (16_777_216 + 1_048_576))
    assert roofline.ell_spmv_least_bytes(8, 2, queries=2) == 80


@pytest.mark.parametrize("least, seconds, expect", [
    (819e9, 1.0, 1.0),
    (819e6, 1.0, 1e-3),
    (roofline.superstep_least_bytes(1 << 20, 33_554_432), 0.9, None),
])
def test_share(least, seconds, expect):
    s = roofline.share(least, seconds, 819e9)
    if expect is not None:
        assert s == pytest.approx(expect)
    assert 0 < s <= 1.0


def test_share_needs_time():
    with pytest.raises(ValueError):
        roofline.share(1.0, 0.0, 819e9)


# Traced runs on one TPU v5e (uniform-s20.pagerank, seed 2147483901):
# 50 ell_spmv calls took 0.0380 s of device time in all; the window ran
# 5 units of 10 supersteps in 17.99 s.  Both shares are far below 1.
RECORDED = [
    ("ell_spmv", 50 * roofline.ell_spmv_least_bytes(16_777_216, 1 << 20),
     0.0380, 0.1146),
    ("step", 50 * roofline.superstep_least_bytes(1 << 20, 16_777_216),
     17.99, 0.000256),
]


@pytest.mark.parametrize("what, least, seconds, expect", RECORDED,
                         ids=[r[0] for r in RECORDED])
def test_recorded_shares_stay_at_or_below_one(what, least, seconds, expect):
    s = roofline.share(least, seconds,
                       roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"])
    assert s == pytest.approx(expect, rel=0.01)
    assert s <= 1.0


def _run_on(chips, trace):
    from bench import harness

    unit = harness.Unit(keys=[0], start=0.0, end=2.0, supersteps=7,
                        edges=0, answers=None)
    return harness.Run(
        workload={"name": "w", "chips": chips}, config={}, traffic={},
        stages={}, num_vertices=1 << 20, num_edges=33_554_432,
        distinct_edges=16_777_216, units=[unit],
        peaks={"hbm_bytes_per_s": 819e9}, engine={"k_dense": 0},
        trace=trace)


def test_step_share_divides_over_the_cells_chips():
    from bench import harness, trace as tr

    read = harness.load_metric(harness.ROOT, "step_hbm_share")
    summary = tr.Summary(window_s=2.0, busy_s=2.0, num_devices=1,
                         device_ops=[[]], idle=[])
    one, four = read(_run_on(1, summary)), read(_run_on(4, summary))
    assert one == pytest.approx(100 * 7 * roofline.superstep_least_bytes(
        1 << 20, 33_554_432) / (2.0 * 819e9))
    assert four == pytest.approx(one / 4)
    assert roofline.share(819e9, 1.0, 819e9, chips=4) == 0.25


def test_ell_roofline_is_silent_on_several_chips():
    # Each shard's ELL holds only its own edges: the whole graph's count
    # would read too high there.
    from bench import harness, trace as tr

    read = harness.load_metric(harness.ROOT, "ell_spmv_roofline")
    call = tr.Event("%ell_spmv.5 = f32[1,8] custom-call()", 0.0, 1e6)
    summary = tr.Summary(window_s=2.0, busy_s=2.0, num_devices=1,
                         device_ops=[[call]], idle=[])
    assert read(_run_on(1, summary)) == pytest.approx(
        100 * roofline.ell_spmv_least_bytes(16_777_216, 1 << 20)
        / (1e-3 * 819e9))
    assert read(_run_on(4, summary)) is None
