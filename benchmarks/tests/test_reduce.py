"""reduce.py on hand arithmetic: percentiles, busy union, idle share,
per-operation time, exposed collectives, idle gaps by span; and on a small
trace recorded on a v5e (three 4096^3 bf16 matmuls, 50 ms of sleep between
them, each inside bm::step then bm::sync)."""

import os

import pytest

from benchmarks import reduce
from benchmarks.reduce import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "matmul_3steps.xplane.pb")


def op(name, start, dur, plane=DEV, line="XLA Ops"):
    return Event(plane, line, name, float(start), float(dur))


def test_percentiles_interpolate_linearly():
    assert reduce.percentile([1, 2, 3, 4], 50) == 2.5
    assert reduce.percentile([1, 2, 3, 4, 5], 90) == pytest.approx(4.6)
    assert reduce.percentile([7], 99) == 7
    assert reduce.percentile([], 50) is None
    assert reduce.percentile(range(101), 90) == 90


OVERLAP = [
    op("%fusion.1 = bf16[8,128]{1,0} fusion(...)", 0, 100),
    op("%fusion.2 = f32[4]{0} fusion(...)", 50, 100),          # overlaps .1
    op("%all-reduce.3 = f32[4]{0} all-reduce(...)", 120, 100),  # 120..220
    op("%fusion.1 = bf16[8,128]{1,0} fusion(...)", 300, 100),
    op("jit_step(1)", 0, 400, line="XLA Modules"),
    op("bm::step", 0, 230, HOST, "python"),
    op("bm::sync", 230, 100, HOST, "python"),
]


def test_busy_is_the_union_of_overlapping_operations():
    s = reduce.device_summary(OVERLAP)
    # [0,150) u [120,220) u [300,400) = 220 + 100
    assert s["busy_s"] == pytest.approx(320e-9)
    assert s["window_s"] == pytest.approx(400e-9)
    assert reduce.REDUCERS["idle_share"]({"events": OVERLAP}, {}) == pytest.approx(20.0)


def test_time_per_operation_and_the_longest():
    assert reduce.op_seconds(OVERLAP, r"fusion\.1") == pytest.approx(200e-9)
    top = reduce.top_ops(OVERLAP)
    assert top[0] == ["fusion.1:bf16[8,128]", pytest.approx(200e-9)]
    assert reduce.modules(OVERLAP) == {"jit_step(1)": [1, pytest.approx(400e-9)]}


def test_exposed_collective_is_what_no_compute_hides():
    # all-reduce 120..220; compute covers up to 150: 70 ns exposed
    assert reduce.exposed_collective_seconds(OVERLAP, 0) == pytest.approx(70e-9)


def test_an_idle_gap_is_charged_to_the_span_it_falls_in():
    gaps = dict(reduce.idle_gaps_by_span(OVERLAP))
    # idle is 220..300: 10 ns still under bm::step, 70 ns under bm::sync
    assert gaps == {"bm::sync": pytest.approx(70e-9),
                    "bm::step": pytest.approx(10e-9)}


def test_two_devices_average_busy_and_take_the_worst_collective():
    ev = OVERLAP + [op("%all-gather.9 = bf16[8]{0} all-gather(...)", 0, 400,
                       plane="/device:TPU:1")]
    s = reduce.device_summary(ev)
    assert s["per_device"] == [pytest.approx(320e-9), pytest.approx(400e-9)]
    assert s["busy_s"] == pytest.approx(360e-9)
    got = reduce.REDUCERS["exposed_collective"]({"events": ev}, {})
    assert got == pytest.approx(100.0)          # device 1: all of the window


def test_recorded_trace():
    ev = reduce.read_xplane(TRACE)
    assert reduce.devices_in(ev) == [0]
    mods = reduce.modules(ev)
    (name, (runs, secs)), = mods.items()
    assert name.startswith("jit_f(") and runs == 3
    assert secs == pytest.approx(3 * 711.1e-6, rel=1e-3)
    s = reduce.device_summary(ev)
    # three matmuls of 0.711 ms, 52 ms apart
    assert s["busy_s"] == pytest.approx(2.1331e-3, rel=1e-3)
    assert s["window_s"] == pytest.approx(0.104997, rel=1e-3)
    assert reduce.REDUCERS["idle_share"]({"events": ev}, {}) == pytest.approx(
        100 * (1 - 2.1331e-3 / 0.104997), rel=1e-4)
    assert reduce.top_ops(ev)[0][0] == "fusion:bf16[]"
    # 4096^3 x 2 FLOPs in 0.711 ms: 193 TFLOP/s of the chip's 197
    assert 2 * 4096 ** 3 / (secs / 3) == pytest.approx(193.3e12, rel=5e-3)
    gaps = dict(reduce.idle_gaps_by_span(ev))
    assert set(gaps) == {"bm::sync", "bm::step", "outside_spans"}
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"],
                                               rel=1e-6)
    assert gaps["outside_spans"] > 0.09        # the two sleeps of 50 ms
