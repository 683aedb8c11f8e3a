"""Profiler (scheduler/RecordEvent/chrome trace/summary) and device API."""

import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler as prof_mod
from paddle_tpu.profiler import (Profiler, ProfilerState, RecordEvent,
                                 SortedKeys, export_chrome_tracing,
                                 make_scheduler, benchmark)
from paddle_tpu import device as dev


# ---------------------------------------------------------------------------
# scheduler state machine
# ---------------------------------------------------------------------------

def test_make_scheduler_states():
    s = make_scheduler(closed=1, ready=1, record=2, repeat=2, skip_first=1)
    states = [s(i) for i in range(10)]
    S = ProfilerState
    assert states == [S.CLOSED,                       # skip_first
                      S.CLOSED, S.READY, S.RECORD, S.RECORD_AND_RETURN,
                      S.CLOSED, S.READY, S.RECORD, S.RECORD_AND_RETURN,
                      S.CLOSED]                       # repeat exhausted


def test_make_scheduler_validation():
    with pytest.raises(ValueError):
        make_scheduler(closed=0, ready=0, record=0)


def test_profiler_cycles_and_chrome_export(tmp_path):
    exported = []
    p = Profiler(scheduler=make_scheduler(closed=1, ready=0, record=2, repeat=2),
                 on_trace_ready=lambda pr: exported.append(
                     export_chrome_tracing(str(tmp_path))(pr)))
    p.start()
    for step in range(8):
        with RecordEvent(f"op_step{step}"):
            time.sleep(0.002)
        p.step()
    p.stop()
    assert len(exported) == 2
    trace = json.load(open(exported[0]))
    names = {e["name"] for e in trace["traceEvents"]}
    # cycle 1 records steps 1..2 (step 0 is CLOSED)
    assert "op_step1" in names and "op_step2" in names
    assert "op_step0" not in names
    for e in trace["traceEvents"]:
        assert e["dur"] > 0


def test_profiler_summary_and_step_info():
    p = Profiler()
    p.start()
    for _ in range(3):
        with RecordEvent("matmul"):
            time.sleep(0.001)
        p.step()
    p.stop()
    s = p.summary()
    assert "matmul" in s and "Calls" in s
    assert "steps/sec" in p.step_info()


def test_back_to_back_rar_cycles_each_export_once_no_bleed():
    """record=1 makes EVERY step RECORD_AND_RETURN: consecutive cycles
    must each export exactly once, and the collector must drain between
    cycles so no event bleeds into the next export."""
    exports = []
    p = Profiler(scheduler=make_scheduler(closed=0, ready=0, record=1),
                 on_trace_ready=lambda pr: exports.append(
                     [e.name for e in pr.result.events]))
    p.start()
    for i in range(3):
        with RecordEvent(f"ev{i}"):
            pass
        p.step()
    p.stop()
    # one export per cycle, each holding exactly its own cycle's event
    # (stop() may flush one final empty cycle)
    assert [e for e in exports if e] == [["ev0"], ["ev1"], ["ev2"]]
    assert len(exports) <= 4


def test_scheduler_repeat_closes_after_n_cycles():
    exports = []
    p = Profiler(scheduler=make_scheduler(closed=0, ready=0, record=2,
                                          repeat=2),
                 on_trace_ready=lambda pr: exports.append(
                     [e.name for e in pr.result.events]))
    p.start()
    for i in range(8):
        with RecordEvent(f"ev{i}"):
            pass
        p.step()
    p.stop()
    # cycles [0,1] and [2,3] export once each; steps >= 4 are CLOSED and
    # their events are never collected
    assert exports == [["ev0", "ev1"], ["ev2", "ev3"]]
    assert prof_mod._collector.events == []


def test_step_info_reports_true_samples_per_sec():
    p = Profiler(timer_only=True)
    p.start()
    for _ in range(4):
        time.sleep(0.002)
        p.step(num_samples=32)
    p.stop()
    info = p.step_info()
    assert "samples/sec" in info
    rate = float(re.search(r"\(([\d.]+) samples/sec\)", info).group(1))
    true_rate = 4 * 32 / sum(p._step_times)
    assert rate == pytest.approx(true_rate, rel=0.01)
    # a custom unit label is honored
    assert "imgs/s" in p.step_info(unit="imgs/s")
    # no sample counts -> falls back to steps/sec WITH the correct label
    p2 = Profiler(timer_only=True)
    p2.start()
    p2.step()
    p2.stop()
    assert "steps/sec" in p2.step_info()
    assert "samples/sec" not in p2.step_info()


def test_summary_honors_sorted_by():
    p = Profiler()
    p.start()
    for _ in range(6):
        with RecordEvent("many_small"):
            time.sleep(0.01)
    with RecordEvent("one_big"):
        time.sleep(0.03)
    p.step()
    p.stop()
    first_row = lambda s: s.splitlines()[1].split()[0]
    assert first_row(p.summary()) == "many_small"          # CPUTotal default
    assert first_row(p.summary(sorted_by=SortedKeys.CPUTotal)) == "many_small"
    assert first_row(p.summary(sorted_by=SortedKeys.CPUAvg)) == "one_big"
    assert first_row(p.summary(sorted_by=SortedKeys.CPUMax)) == "one_big"
    # int values (reference code passes enum members; ints must work too)
    assert first_row(p.summary(sorted_by=SortedKeys.GPUAvg.value)) == "one_big"
    assert "SortedKeys" in prof_mod.__all__


def test_record_event_noop_when_not_recording():
    ev = RecordEvent("outside")
    with ev:
        pass  # collector disabled → nothing stored, no error
    assert prof_mod._collector.events == []


def test_benchmark_timer():
    b = benchmark()
    b.reset()
    b.begin()
    for _ in range(3):
        time.sleep(0.001)
        b.step(num_samples=32)
    r = b.report()
    assert r["steps"] == 3
    assert r["ips"] > 0


def test_record_event_tag_adds_attrs_to_a_running_span_only():
    idle = RecordEvent("outside", a=1)
    with idle:
        idle.tag(b=2)                 # nothing records: nothing to add to
    assert idle.attrs == {"a": 1}
    p = Profiler()
    p.start()
    with RecordEvent("inside", a=1) as ev:
        ev.tag(b=2)
    p.stop()
    (got,) = [e for e in p.result.events if e.name == "inside"]
    assert got.attrs == {"a": 1, "b": 2}


# ---------------------------------------------------------------------------
# device API
# ---------------------------------------------------------------------------

def test_synchronize_and_properties():
    dev.synchronize()
    props = dev.get_device_properties()
    assert props.platform in ("cpu", "tpu", "gpu")
    assert isinstance(dev.get_all_device_type(), list)
    assert dev.get_available_device()


def test_stream_event_shims():
    s = dev.current_stream()
    e = dev.Event(enable_timing=True)
    e.record(s)
    x = jnp.ones((8, 8)) @ jnp.ones((8, 8))
    s.track(x)
    e2 = dev.Event(enable_timing=True)
    e2.record(s)
    s.synchronize()
    assert e.query()
    assert e.elapsed_time(e2) >= 0
    with dev.stream_guard(dev.Stream()) as st:
        assert dev.current_stream(st.device) is st


def test_places():
    p = dev.CPUPlace()
    assert p.jax_device().platform == "cpu"
    assert dev.CPUPlace() == dev.CPUPlace()
    # CUDAPlace must resolve to whatever accelerator exists (fallback ok)
    d = dev.CUDAPlace(0).jax_device()
    assert d is not None


def test_memory_stats_shape():
    st = dev.memory_stats()
    assert isinstance(st, dict)


def test_summary_fallback_rate_labeled_steps_per_sec():
    """ISSUE 9 satellite: summary()'s trailing throughput line inherits
    step_info's fallback labeling — steps without num_samples must render
    a `steps/sec` label there too, never `samples/sec` over a
    steps-derived number (the docs drift this regression pins)."""
    from paddle_tpu.profiler import SortedKeys

    p = Profiler(timer_only=True)
    p.start()
    for _ in range(3):
        p.step()
    p.stop()
    s = p.summary(sorted_by=SortedKeys.CPUAvg)
    assert "steps/sec" in s
    assert "samples/sec" not in s
