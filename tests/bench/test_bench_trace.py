"""Trace reduction on a synthetic trace: busy union, idle gaps named by the
benchmark's host spans, kernel time by name."""
import pytest

import bench_helpers  # noqa: F401  (puts bench on the path)
from bench import trace as tr


def ev(name, start, end):
    return tr.Event(name, float(start), float(end - start))


@pytest.fixture
def synthetic():
    # Window [0, 100): two units; the device runs a loop [5, 40) with two
    # ops in it, [5, 30) and [20, 40) (overlapping), then [50, 55), the
    # kernel at [60, 90) and an op reading its result at [90, 90.5).
    fusion = "%fusion.1 = f32[8]{0:T(1024)} fusion(f32[8]{0} %p.1)"
    ops = [ev("%while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]) %t)",
              5, 40),
           ev(fusion, 5, 30), ev("%gather.2 = f32[8]{0} gather(f32[16]{0} %a, "
                                 "s32[8]{0} %b)", 20, 40),
           ev(fusion, 50, 55),
           ev("%ell_spmv.5 = f32[1,8]{1,0:T(1,128)} custom-call(f32[1,2,8]"
              "{2,1,0} %g), custom_call_target=\"tpu_custom_call\"", 60, 90),
           ev("%fusion.7 = f32[8]{0} fusion(f32[1,8]{1,0} %ell_spmv.5)",
              90, 90.5)]
    host = [ev("bench.window", 0, 100),
            ev("bench.unit", 0, 45), ev("bench.between_units", 45, 50),
            ev("bench.unit", 50, 100),
            ev("PjitFunction(_run_batched)", 1, 5),
            ev("np.asarray", 40, 49), ev("gather_global", 41, 48)]
    return tr.Trace(devices=[ops], host=host)


def test_merge_and_gaps():
    busy = tr.merge([(5, 30), (20, 40), (50, 55), (-5, 2), (95, 120)],
                    0, 100)
    assert busy == [(0, 2), (5, 40), (50, 55), (95, 100)]
    assert tr.gaps(busy, 0, 100) == [(2, 5), (40, 50), (55, 95)]
    assert tr.gaps([], 0, 10) == [(0, 10)]


def test_summary_busy_idle_and_kernels(synthetic):
    lo, hi = tr.window_of(synthetic, "bench.window")
    s = tr.summarize(synthetic, lo, hi)
    assert s.window_s == pytest.approx(100e-9)
    # union: [5, 40) + [50, 55) + [60, 90.5) = 35 + 5 + 30.5
    assert s.busy_s == pytest.approx(70.5e-9)
    # the op reading the kernel's result is no call of the kernel
    assert s.kernel_seconds("ell_spmv") == pytest.approx(30e-9)
    assert s.kernel_calls("ell_spmv") == 1
    assert s.kernel_calls("ell_spmv.5") == 1
    assert s.kernel_calls("ell") == 0
    assert s.kernel_calls("bottomup_scan") == 0
    # the loop is left out of the top ops; layouts and operands are cut
    assert dict(s.top_ops()) == pytest.approx({
        "%ell_spmv.5 = f32[1,8] custom-call(f32[1,2,8]), "
        "custom_call_target=\"tpu_custom_call\"": 30e-9,
        "%fusion.1 = f32[8] fusion(f32[8])": 30e-9,
        "%gather.2 = f32[8] gather(f32[16], s32[8])": 20e-9,
        "%fusion.7 = f32[8] fusion(f32[1,8])": 0.5e-9})
    assert s.top_ops(top=1)[0][1] == pytest.approx(30e-9)
    # gaps, longest first: [40, 50) 10, [90.5, 100) 9.5, [0, 5) 5,
    # [55, 60) 5
    lengths = [sec * 1e9 for _, sec in s.idle]
    assert lengths == pytest.approx([10, 9.5, 5, 5])
    names = [name for name, _ in s.idle]
    # [40, 50) spans the end of a unit and the gap between units: the span
    # holding its midpoint names it, with the shortest host event covering
    # half of it.
    assert names[0] == "bench.between_units / gather_global"
    assert names[1] == "bench.unit / -"
    assert "bench.unit / PjitFunction(_run_batched)" in names


def test_gap_outside_any_span():
    assert tr.name_gap(0, 10, []) == "outside bench spans / -"


def test_summary_needs_device_ops():
    with pytest.raises(ValueError, match="no device operations"):
        tr.summarize(tr.Trace(devices=[], host=[]), 0, 1)


def test_busy_is_averaged_over_devices(synthetic):
    two = tr.Trace(devices=[synthetic.devices[0],
                            [ev("fusion.9", 0, 100)]],
                   host=synthetic.host)
    s = tr.summarize(two, 0, 100)
    assert s.busy_s == pytest.approx((70.5e-9 + 100e-9) / 2)
    assert s.kernel_seconds("ell_spmv") == pytest.approx(15e-9)


# As a v5e names the engine's exchange.
A2A = "%all_to_all.10 = f32[1,1,1,4,1,8]{5,4,3,2,1,0} " \
      "all-to-all(f32[1,1,1,4,1,8]{5,4,3,2,1,0} %o), channel_id=1"
LOOP = "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]) %t)"


@pytest.mark.parametrize("others, exposed", [
    ([(5, 25)], 0),                 # fully hidden under a fusion
    ([(15, 30)], 5),                # [10, 15) runs alone
    ([(30, 40)], 10),               # fully exposed
    ([(0, 12), (18, 40)], 6),       # hidden at both ends
], ids=["hidden", "partly", "exposed", "both-ends"])
def test_exposed_collective_time(others, exposed):
    # The all-to-all runs [10, 20) inside a loop that spans everything;
    # the loop hides nothing, the other ops do.
    ops = [ev(LOOP, 0, 50), ev(A2A, 10, 20)]
    ops += [ev(f"%fusion.{i} = f32[8]{{0}} fusion(f32[8]{{0}} %p)", *span)
            for i, span in enumerate(others)]
    s = tr.summarize(tr.Trace(devices=[ops], host=[]), 0, 50)
    assert s.kernel_seconds("all_to_all") == pytest.approx(10e-9)
    assert s.exposed_seconds("all_to_all") == pytest.approx(exposed * 1e-9)


def test_exposed_time_per_device_and_overlapping_calls():
    # Device 0: two overlapping all-to-alls [10, 20) and [15, 25), nothing
    # else: 15 exposed.  Device 1: one all-to-all hidden under a fusion.
    dev0 = [ev(LOOP, 0, 50), ev(A2A, 10, 20), ev(A2A, 15, 25)]
    dev1 = [ev(LOOP, 0, 50), ev(A2A, 10, 20),
            ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)", 0, 30)]
    s = tr.summarize(tr.Trace(devices=[dev0, dev1], host=[]), 0, 50)
    assert [len(ops) for ops in s.device_ops] == [3, 3]
    assert len(s.ops) == 6
    assert s.exposed_seconds("all_to_all") == pytest.approx(15e-9 / 2)
    assert s.kernel_seconds("all_to_all") == pytest.approx(30e-9 / 2)
    assert s.exposed_seconds("all-reduce") == 0
