"""Cost observatory (ISSUE 9): analytical flop/byte attribution over
optimized HLO, the priced collective census, the OpCostDB, and the live
breakdown/MFU gauges.

Wall-clock assertions follow the bench-variance policy for this noisy
host: interleaved min-of-rounds, and RATIOS (K=4 vs K=1) rather than
absolute seconds. Everything else is exact arithmetic over deterministic
HLO text."""

import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.analysis.hlo import parse_hlo
from paddle_tpu.observability import costs
from paddle_tpu.observability.metrics import REGISTRY


# ---------------------------------------------------------------------------
# analytical attribution
# ---------------------------------------------------------------------------

def test_dot_flops_exact():
    M, K, N = 64, 32, 48
    c = jax.jit(lambda a, b: a @ b).lower(
        jnp.zeros((M, K)), jnp.zeros((K, N))).compile()
    rep = costs.attribute_costs(parse_hlo(c.as_text()))
    assert rep.total_flops == 2 * M * K * N
    assert rep.dots[0][:3] == (M, K, N)
    # operands + output, f32
    assert rep.total_bytes == 4 * (M * K + K * N + M * N)


def test_scan_trip_count_multiplies_flops():
    w = jnp.asarray(np.random.RandomState(0).randn(16, 16), jnp.float32)
    f1 = jax.jit(lambda x: x @ w).lower(jnp.zeros((16, 16))).compile()
    f4 = jax.jit(lambda x: jax.lax.scan(
        lambda c, _: (c @ w, ()), x, None, length=4)[0]).lower(
        jnp.zeros((16, 16))).compile()
    r1 = costs.attribute_costs(parse_hlo(f1.as_text()))
    r4 = costs.attribute_costs(parse_hlo(f4.as_text()))
    # the while body's dot runs known_trip_count times; the loop adds a
    # few counter ops, so the ratio is 4 within a couple percent
    assert r4.total_flops / r1.total_flops == pytest.approx(4.0, rel=0.05)
    assert not r4.unmodeled


def test_roofline_bounds_and_report_shape():
    M = 256
    c = jax.jit(lambda a, b: a @ b).lower(
        jnp.zeros((M, M)), jnp.zeros((M, M))).compile()
    spec = costs.DeviceSpec(kind="synthetic", peak_flops=1e12,
                            hbm_bw=1e11, link_bw=1e10)
    rep = costs.attribute_costs(parse_hlo(c.as_text()), spec=spec)
    assert rep.predicted_step_s > 0
    assert rep.predicted_step_s == pytest.approx(
        sum(o.seconds for o in rep.ops), rel=1e-9)
    for o in rep.ops:
        assert o.bound in ("compute", "hbm", "comm")
    # buckets partition the predicted time
    assert sum(rep.bound_seconds.values()) == pytest.approx(
        rep.predicted_step_s, rel=1e-9)


def test_async_collective_done_pairs_not_double_counted():
    """TPU lowers collectives as -start/-done pairs: the -done must book
    ZERO flops and ZERO bytes (everything is attributed at the -start),
    or pod graphs inflate analytical_flops / HBM bytes with phantom
    elementwise costs."""
    hlo = """HloModule m

ENTRY %main (p0: f32[128,128]) -> f32[128,128] {
  %p0 = f32[128,128]{1,0} parameter(0)
  %ar-start = f32[128,128]{1,0} all-reduce-start(f32[128,128]{1,0} %p0), replica_groups={{0,1}}, to_apply=%add
  ROOT %ar-done = f32[128,128]{1,0} all-reduce-done(f32[128,128]{1,0} %ar-start)
}
"""
    rep = costs.attribute_costs(parse_hlo(hlo))
    payload = 128 * 128 * 4
    assert rep.total_flops == 0          # no phantom elementwise flops
    assert rep.total_comm_bytes == payload        # counted exactly once
    # HBM traffic booked at the -start only (operand + output)
    assert rep.total_bytes == 2 * payload


# ---------------------------------------------------------------------------
# priced census (dp2 x tp2 canonical graph) — exact ratios, no wall clock
# ---------------------------------------------------------------------------

def test_priced_census_proportional_to_bytes_dp2tp2():
    import paddle_tpu.analysis as A
    g = A.build_graph("tp_fused_ce")
    rep = A.analyze(g.compiled, g.name, g.contract, mesh=g.mesh)
    census = rep.collectives
    assert census["total_collective_bytes"] > 0
    # every collective in this graph is pinned to the tp axis (the PR 8
    # contract), so one synthetic bandwidth prices the whole table
    p1 = costs.price_census(census, bandwidths={"tp": 1e9})
    p2 = costs.price_census(census, bandwidths={"tp": 2e9})
    assert set(p1["per_axis"]) == {"tp"}
    # seconds == bytes / bw, and doubling bandwidth exactly halves time
    assert p1["per_axis"]["tp"]["seconds"] == pytest.approx(
        census["total_collective_bytes"] / 1e9, rel=1e-12)
    assert p1["total_comm_s"] == pytest.approx(2 * p2["total_comm_s"],
                                               rel=1e-12)
    # per-op rows decompose the total exactly
    assert sum(r["seconds"] for r in p1["per_op"]) == pytest.approx(
        p1["total_comm_s"], rel=1e-12)


# ---------------------------------------------------------------------------
# predicted step time (ISSUE 9 acceptance): K=1 vs K=4, by attribution
# ---------------------------------------------------------------------------

def test_predicted_vs_measured_ratio_k1_vs_k4():
    """Across the canonical train-step K=1 and K=4 graphs the flop
    attribution and the roofline-predicted step time both scale by the
    trip count. Both come from the compiled HLO alone; how the CPU's
    measured times compare is not asserted (a CPU timing under six test
    workers is not evidence of anything)."""
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    from op_cost_probe import measure_graphs

    m = measure_graphs(["train_step_k1", "train_step_k4"],
                       rounds=1, iters=1)
    k1, k4 = m["train_step_k1"], m["train_step_k4"]
    assert k4["flops"] / k1["flops"] == pytest.approx(4.0, rel=0.02)
    assert k4["predicted_s"] / k1["predicted_s"] == pytest.approx(
        4.0, rel=0.02)


# ---------------------------------------------------------------------------
# OpCostDB persistence
# ---------------------------------------------------------------------------

def test_opcostdb_roundtrip_and_reload_hits(tmp_path):
    path = str(tmp_path / "op_cost_db.json")
    db = costs.OpCostDB(path)
    key = costs.OpCostDB.graph_key("train_step_k1", "cpu")
    db.record(key, {"t_s": 0.005, "flops": 5.1e7})
    db.save()
    fresh = costs.OpCostDB(path)
    hit = fresh.lookup(key)
    assert hit is not None and hit["flops"] == 5.1e7
    # dot keys carry exact (unbucketed) shape dims
    dkey = costs.OpCostDB.dot_key(40, 64, 2048, "f32", "cpu")
    assert "m=40" in dkey and "k=64" in dkey and "n=2048" in dkey


def test_opcostdb_corrupt_file_warns_like_tunedb(tmp_path):
    """The acceptance criterion: a corrupt calibration file degrades
    LOUDLY (the TuneDB._load warning path), never silently."""
    path = str(tmp_path / "corrupt_cost.json")
    with open(path, "w") as f:
        f.write("{not json")
    db = costs.OpCostDB(path)
    with pytest.warns(RuntimeWarning, match="corrupt op cost DB"):
        assert db.lookup("anything") is None


def test_calibrate_records_measured_and_analytical(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    from op_cost_probe import calibrate

    path = str(tmp_path / "cal.json")
    out = calibrate(graphs=["fused_ce"], rounds=1, iters=2, db_path=path,
                    top_dots=1)
    assert out["recorded"]
    with open(path) as f:
        raw = json.load(f)
    gkey = costs.OpCostDB.graph_key("fused_ce",
                                    costs.current_device_kind())
    assert gkey in raw
    rec = raw[gkey]
    assert rec["t_s"] > 0 and rec["flops"] > 0 and rec["predicted_s"] > 0


# ---------------------------------------------------------------------------
# empty-histogram exposition (satellite)
# ---------------------------------------------------------------------------

def test_empty_histogram_round_trips_zeroed_buckets():
    from paddle_tpu.observability.exporters import (parse_prometheus,
                                                    render_prometheus)
    name = "pt_test_empty_hist_issue9"
    REGISTRY.histogram(name, "registered but never observed", "s")
    snap = REGISTRY.collect()
    entry = [e for e in snap if e["name"] == name]
    assert len(entry) == 1
    e = entry[0]
    assert e["count"] == 0 and e["sum"] == 0.0
    assert all(cum == 0 for _, cum in e["buckets"])
    text = render_prometheus(snap)
    parsed = parse_prometheus(text)
    # the scraper sees the full zeroed series set from the first scrape
    buckets = parsed[f"{name}_bucket"]
    assert buckets and all(v == 0.0 for v in buckets.values())
    assert parsed[f"{name}_count"][()] == 0.0
    assert parsed[f"{name}_sum"][()] == 0.0
    # one observation replaces the zero series with the real one
    enabled = REGISTRY.enabled
    REGISTRY.enable()
    try:
        REGISTRY.histogram(name).observe(0.003)
    finally:
        REGISTRY.enabled = enabled
    snap2 = REGISTRY.collect()
    e2 = [x for x in snap2 if x["name"] == name]
    assert len(e2) == 1 and e2[0]["count"] == 1


# ---------------------------------------------------------------------------
# live gauges: trainer + serving
# ---------------------------------------------------------------------------

def test_trainer_publishes_breakdown_and_mfu_gauges():
    from paddle_tpu import nn
    from paddle_tpu.nn.layer import Layer
    from paddle_tpu.optimizer import SGD
    from paddle_tpu.trainer import Trainer

    class TinyReg(Layer):
        def __init__(self):
            super().__init__()
            self.l1 = nn.Linear(8, 16)
            self.l2 = nn.Linear(16, 1)

        def forward(self, x, y):
            h = jnp.tanh(self.l1(x))
            return jnp.mean((self.l2(h) - y) ** 2)

    model = TinyReg()
    tr = Trainer(model, SGD(learning_rate=0.05, parameters=model))
    rs = np.random.RandomState(0)

    def batches(n):
        return [{"x": jnp.asarray(rs.randn(4, 8).astype(np.float32)),
                 "y": jnp.asarray(rs.randn(4, 1).astype(np.float32))}
                for _ in range(n)]

    seen = []
    REGISTRY.enable()
    try:
        tr.fit(iter(batches(12)), steps=12, log_every=4,
               on_metrics=seen.append)
        lbl = {"component": "train"}
        mfu = REGISTRY.gauge("pt_model_flops_utilization").value(**lbl)
        assert math.isfinite(mfu) and mfu > 0
        hbm = REGISTRY.gauge("pt_hbm_bw_utilization").value(**lbl)
        assert math.isfinite(hbm) and hbm > 0
        ratio = REGISTRY.gauge(
            "pt_step_time_predicted_over_measured").value(**lbl)
        assert math.isfinite(ratio) and ratio > 0
        bd = {b: REGISTRY.gauge("pt_step_time_breakdown").value(
            bucket=b, **lbl)
            for b in ("compute", "collective", "exposed_comm",
                      "host", "stall")}
        assert all(v >= 0 for v in bd.values())
        # the breakdown invariant: buckets sum EXACTLY to the measured
        # per-step time of the last published window
        assert sum(bd.values()) == pytest.approx(seen[-1].step_time_s,
                                                 rel=1e-6)
    finally:
        REGISTRY.disable()


def test_cost_watch_reobserves_on_executable_change():
    """A trainer with bucketed batch shapes dispatches DIFFERENT
    executables across windows: the watch must re-attribute the one on
    the clock (and serve repeats from its per-id report cache), never
    pin the first-compiled program's flop count forever."""
    w = costs.CostWatch("t")
    c1 = jax.jit(lambda a: a @ a).lower(jnp.zeros((8, 8))).compile()
    c2 = jax.jit(lambda a: a @ a).lower(jnp.zeros((16, 16))).compile()
    assert w.observe_executable(c1)
    f1 = w.report.total_flops
    assert w.observe_executable(c2)
    assert w.report.total_flops == 8 * f1     # 2*16^3 vs 2*8^3
    assert w.observe_executable(c1)           # cache hit, no re-parse
    assert w.report.total_flops == f1


def test_serving_publishes_cost_gauges():
    import paddle_tpu as pt
    from paddle_tpu.inference import ContinuousBatchingEngine, serving
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    rs = np.random.RandomState(0)
    REGISTRY.enable()
    try:
        eng = ContinuousBatchingEngine(
            model, max_batch=2, page_size=8, max_len=32,
            generation_config=GenerationConfig(max_new_tokens=8,
                                               do_sample=False),
            decode_block=4)
        for L in (6, 8, 5):
            eng.submit(rs.randint(0, 32, (L,)).astype(np.int32))
        out = eng.run()
        assert sum(len(v) for v in out.values()) > 0
        # the gauges read the books' recent CLEAN tick: by the wall clock
        # that takes a block the host WAITED for with no admission in front
        # of it, which three short requests on a loaded host may never give.
        # The books take a clock, so play them one tick of 1 ms a token
        now = [0.0]
        eng._books = serving._StreamBooks(clock=lambda: now[0])
        block = eng._books.dispatched(busy=False, behind=False)
        now[0] = eng.decode_block * 1e-3
        eng._books.drained(K=eng.decode_block, **block, waited=True)
        assert eng._books.recent_tick_s() == pytest.approx(1e-3)
        eng.publish_metrics()
        mfu = REGISTRY.gauge("pt_model_flops_utilization").value(
            component="serving")
        assert math.isfinite(mfu) and mfu > 0
        bd_sum = sum(
            REGISTRY.gauge("pt_step_time_breakdown").value(
                bucket=b, component="serving")
            for b in ("compute", "collective", "exposed_comm",
                      "host", "stall"))
        assert bd_sum > 0
    finally:
        REGISTRY.disable()


def test_serving_parity_with_metrics_enabled():
    """The eager lower+compile the cost watch triggers must not change
    the served stream: metrics-on output == metrics-off output."""
    import paddle_tpu as pt
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 32, (L,)).astype(np.int32)
               for L in (6, 9, 5)]

    def serve():
        eng = ContinuousBatchingEngine(
            model, max_batch=2, page_size=8, max_len=32,
            generation_config=GenerationConfig(max_new_tokens=8,
                                               do_sample=False),
            decode_block=4)
        for p in prompts:
            eng.submit(p)
        return [v.tolist() for v in eng.run().values()]

    REGISTRY.disable()
    off = serve()
    REGISTRY.enable()
    try:
        on = serve()
    finally:
        REGISTRY.disable()
    assert on == off


# ---------------------------------------------------------------------------
# graph_lint flop floor (satellite)
# ---------------------------------------------------------------------------

def test_snapshot_carries_analytical_flops_and_floor_fires():
    import paddle_tpu.analysis as A
    g = A.build_graph("fused_ce")
    rep = A.analyze(g.compiled, g.name, g.contract)
    snap = A.snapshot_report(rep)
    assert snap["analytical_flops"] > 0
    # a budget pinned ABOVE the actual flop count = an op fell out of the
    # fused path -> the floor violation names the rule
    entry = {"budget": {"analytical_flops": snap["analytical_flops"] + 1}}
    v = A.check_budget(rep, entry)
    assert any(x.rule == "budget.analytical_flops" for x in v)
    # pinned AT the actual value passes
    entry = {"budget": {"analytical_flops": snap["analytical_flops"]}}
    assert not [x for x in A.check_budget(rep, entry)
                if x.rule == "budget.analytical_flops"]


def test_one_flop_definition_shared():
    """The live gauge and graph_lint's floor both route through
    observability.costs.attribute_costs — grep-level assertion that no
    second flop formula crept into those call sites."""
    import inspect

    import paddle_tpu.analysis.contracts as contracts
    import paddle_tpu.trainer.trainer as trainer_mod
    src_contracts = inspect.getsource(contracts.snapshot_report)
    assert "attribute_costs" in src_contracts
    src_watch = inspect.getsource(trainer_mod.Trainer._publish_step_costs)
    assert "CostWatch" in src_watch
