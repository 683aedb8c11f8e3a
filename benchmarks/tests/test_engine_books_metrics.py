"""The engine's own books as per-layer metrics (ISSUE 36): data-only specs
over the counters ``engine.stats()`` keeps of its device stream, its
admission path and its requests' waits, and over the ``serving::drain``
span; and ``tools/books.py``, the reader of that span's stats, which lays
the books beside the device's record of one traced run."""

import json
import os

import pytest

from benchmarks import reduce, run
from benchmarks.tools import books
from conftest import ROOT

BENCH_FILE = os.path.join(ROOT, "BENCHMARK.json")
BENCH = json.load(open(BENCH_FILE))
DECODE, TTFT = "mistral-7b.batch-decode", "mistral-7b.short-answers"
# a window's counters, by hand: 45 s between the first and the last stamp,
# 30 s of clean ticks (2,400 of them), 9.9 s of admission work, 0.9 s the
# books could not attribute; prefill programs 256,000 tokens wide, 32,000
# of them padding; 250 first tokens, 0.5 s of the host enqueueing their
# prefills and 17.5 s behind them
COUNTERS = {
    "engine.stream_s": 45.0, "engine.stream_tick_s": 30.0,
    "engine.stream_ticks": 2400, "engine.stream_admit_s": 9.9,
    "engine.stream_unattributed_s": 0.9,
    "engine.prefill_width_tokens": 256000,
    "engine.prefill_pad_tokens": 32000, "engine.first_tokens": 250,
    "engine.prefill_dispatch_s": 0.5, "engine.first_token_wait_s": 17.5,
}
# a traced window of 1,000 ns in which the host sat in two drains, 450 and
# (clipped at the window's end) 300 ns
EVENTS = [
    reduce.Event("/device:TPU:0", reduce.OPS_LINE, "%fusion.1 = f32[] fusion()", 0, 1000),
    reduce.Event(reduce.HOST_PLANE, "t", "serving::drain", 50, 450),
    reduce.Event(reduce.HOST_PLANE, "t", "serving::reconcile", 500, 100),
    reduce.Event(reduce.HOST_PLANE, "t", "serving::drain", 700, 400),
    reduce.Event(reduce.HOST_PLANE, "t", "bm::step", 0, 1000),
]
BY_HAND = {
    "prefill_stream_share.decode": 22.0, "tick_stream_ms.decode": 12.5,
    "prefill_pad_share.decode": 12.5, "host_wait_share.decode": 75.0,
    "stream_unattributed_share.decode": 2.0,
    "prefill_stream_share.ttft": 22.0, "prefill_dispatch_mean_ms.ttft": 2.0,
    "first_token_wait_mean_ms.ttft": 70.0, "host_wait_share.ttft": 75.0,
    "stream_unattributed_share.ttft": 2.0,
}


def _spec(name):
    cell = DECODE if name.endswith(".decode") else TTFT
    return {m["name"]: m for m in run.resolve(cell, BENCH_FILE)[3]}[name]


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_each_books_spec_resolves_and_reads_hand_worked_numbers(name):
    spec = _spec(name)
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert {k: spec[k] for k in entry} == entry
    ctx = {"counters": COUNTERS, "events": EVENTS}
    got = reduce.REDUCERS[spec["reducer"]](ctx, spec)
    assert got == pytest.approx(BY_HAND[name], rel=1e-12)
    # a program without the books (this PR's parent) reads as nothing
    bare = {"counters": {"engine.preemptions": 0}, "events": EVENTS[:1]}
    assert reduce.REDUCERS[spec["reducer"]](bare, spec) is None


def _drain(end, block, interval_us, admit_calls, chained, ticks=1):
    return reduce.Event(reduce.HOST_PLANE, "t", "serving::drain", end - 2e3,
                        2e3, {"block": block, "interval_us": interval_us,
                              "ticks": ticks, "admit_calls": admit_calls,
                              "chained": chained})


def _trace():
    """A window of 110 us by hand. Blocks 1 and 2: clean ticks of 10 us.
    Block 3: a prefill of 20 us in front of its tick. Block 4 (K 2): two
    ticks in 20 us. Block 5 ran 5 us and was ready before the host looked:
    its stamp is 4 us late and its run stays open; block 6, behind it,
    closes the run: two ticks in 10 us. Block 7 wakes a quiet device 10 us
    later and runs 10 us. At block 8 the books gave 10 us up."""
    dev = "/device:TPU:0"
    mod = lambda name, a, b: reduce.Event(                    # noqa: E731
        dev, reduce.MODULES_LINE, name, a * 1e3, (b - a) * 1e3)
    run_, pre = "jit_run(1)", "jit_prefill_paged_256(2)"
    return [
        reduce.Event(dev, reduce.OPS_LINE, "%fusion.1 = f32[] fusion()", 0, 110e3),
        mod(run_, 0, 10), mod(run_, 10, 20), mod(pre, 20, 40),
        mod(run_, 40, 50), mod(run_, 50, 60), mod(run_, 60, 70),
        mod(run_, 70, 75), mod(run_, 75, 80), mod(run_, 90, 100),
        mod(run_, 100, 110),
        _drain(10e3, 1, 10, 0, 1), _drain(20e3, 2, 10, 0, 1),
        _drain(50e3, 3, 30, 1, 1), _drain(70e3, 4, 20, 0, 1, ticks=2),
        _drain(79e3, 5, 9, 0, 2), _drain(80e3, 6, 10, 0, 1, ticks=2),
        _drain(100e3, 7, 10, 0, 1), _drain(110e3, 8, 10, 0, 0),
    ]


def test_the_books_tool_lays_the_drains_runs_beside_the_modules():
    got = books.check(_trace())
    assert got["window_s"] == pytest.approx(110e-6)
    assert got["intervals"] == {
        "clean": {"n": 5, "s": pytest.approx(60e-6)},
        "admit": {"n": 1, "s": pytest.approx(30e-6)},
        "given_up": {"n": 1, "s": pytest.approx(10e-6)},
        "left_open": {"n": 1, "s": pytest.approx(9e-6)}}
    assert got["books"] == {
        "prefill_stream_share": pytest.approx(100 * 20 / 110),
        "tick_stream_ms": pytest.approx(0.060 / 7),
        "stream_gap_share": pytest.approx(100 * 10 / 110),
        "stream_unattributed_share": pytest.approx(100 * 10 / 110)}
    assert got["device"] == {
        "other_modules_share": pytest.approx(100 * 20 / 110),
        "tick_module_ms": pytest.approx(0.080 / 9),
        "tick_module_runs": 9, "idle_share": pytest.approx(100 * 10 / 110)}
    assert got["closed_busy_share"] == pytest.approx(100.0)
    assert got["admit_s_books_vs_device"] == [pytest.approx(20e-6)] * 2
    assert got["admit_clipped"] == 0 and got["admit_worst_ms"] == [
        [pytest.approx(0.0, abs=1e-9), pytest.approx(0.030),
         pytest.approx(0.020), pytest.approx(0.010)]]
    # the host sat 2 us in either drain, 7 and 8 us after the span began,
    # the device busy all through
    assert got["not_closed"] == [
        [5, "left_open", pytest.approx(0.009), 0, pytest.approx(0.002),
         pytest.approx(0.007), pytest.approx(100.0)],
        [8, "given_up", pytest.approx(0.010), 0, pytest.approx(0.002),
         pytest.approx(0.008), pytest.approx(100.0)]]
    # a program without the books: its drains carry no interval
    bare = [e for e in _trace() if e.name != "serving::drain"]
    assert books.check(bare) is None
