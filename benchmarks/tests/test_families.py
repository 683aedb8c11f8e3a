"""The move to families changed where the harness LOOKS, and nothing it
finds: the real configurations' leaves and seeded bits are the parent's
(values pinned from the commit before the move), every reducer that existed
reads the recorded trace as it did, the program's spans are kept with their
stats, and a kernel's roofline read by name cannot pass 100%."""

import hashlib
import json
import os
import re
import zlib

import numpy as np
import pytest

from benchmarks import families, reduce, weights
from conftest import ROOT

DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
TRACE = os.path.join(DATA, "matmul_3steps.xplane.pb")
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}

# from the parent (commit 8618a39, ``weights.leaf_shapes`` and ``make_some``
# on the CPU): leaves, the sha256 of [[name, shape, kind], ...] in order, and
# the crc32 of three leaves' float32 bytes at seed 2147483659
PINNED = {
    "mistral-7b-v0.3.serve-1chip": (
        99, "3cc31c73a90bbe7e6308c4666a8def22b4fa75973cb55c5c2b7152eb012bcaaf",
        {"layers.0.o": 2463451478, "layers.7.qkv": 1425825240,
         "layers.15.down": 3604283604}),
    "olmoe-1b-7b.train-1chip": (
        10, "a6f58f778b88feddbb473e29526df26d6d922d415ec925ff06f22699bf763552",
        {"layers.0.router": 4085068969, "layers.0.o": 3498003013,
         "layers.0.qkv": 2493976533}),
}


def config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def _config_files():
    import glob
    return sorted(glob.glob(os.path.join(ROOT, "benchmarks", "configs", "*.json"))
                  + glob.glob(os.path.join(DATA, "configs", "*.json")))


@pytest.mark.parametrize("path", _config_files(), ids=os.path.basename)
def test_every_configuration_names_a_family_that_gives_the_three_groups(path):
    with open(path) as f:
        cfg = json.load(f)
    family = families.of(cfg)
    assert family.__name__ == cfg["family"]
    assert all(callable(getattr(family, f)) for f in families.REQUIRED)
    shapes = family.leaf_shapes(cfg)
    assert shapes and all(kind in ("norm", "router", "matrix") and all(
        isinstance(d, int) and d > 0 for d in shape) for shape, kind in shapes.values())
    assert family.train_flops_per_token(cfg, 128) > 0
    assert family.decode_tick_bytes(cfg, 1000) > family.decode_tick_bytes(cfg, 0) > 0
    assert family.loss0_expected(cfg, weights.INIT_STD) > 0


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_leaves_and_their_seeded_bits_are_the_parents(name):
    n, digest, crcs = PINNED[name]
    cfg = config(name)
    shapes = weights.leaf_shapes(cfg)
    assert shapes == families.of(cfg).leaf_shapes(cfg) and len(shapes) == n
    blob = json.dumps([[k, list(s), kind] for k, (s, kind) in shapes.items()])
    assert hashlib.sha256(blob.encode()).hexdigest() == digest
    got = weights.make_some(2147483659, cfg, list(crcs))
    assert {k: zlib.crc32(np.asarray(v).tobytes()) for k, v in got.items()} == crcs


def test_every_reducer_that_existed_reads_the_recorded_trace_as_it_did():
    """Numbers printed by the parent's reduce.py on the same file."""
    ev = reduce.read_xplane(TRACE)
    assert len(ev) == 21
    R = reduce.REDUCERS
    ctx = {"events": ev, "samples": {"x": [1.0, 2.0, 4.0]}, "counters": {"c": 7},
           "window_s": 2.0, "memory_peak_bytes": 3 * 2 ** 30}
    assert R["idle_share"](ctx, {}) == 97.9683870963748
    assert R["device_op_share"](ctx, {"pattern": "fusion"}) == 2.0315633785492517
    assert R["exposed_collective"](ctx, {}) is None
    assert R["sample_percentile"](ctx, {"sample": "x", "q": 90}) == 3.6
    assert R["sample_mean"](ctx, {"sample": "x"}) == 2.3333333333333335
    assert R["counter"](ctx, {"counter": "c"}) == 7.0
    assert R["counter_rate"](ctx, {"counter": "c"}) == 3.5
    assert R["memory_peak"](ctx, {}) == 3.0
    assert reduce.top_ops(ev) == [["fusion:bf16[]", 0.002133087],
                                  ["copy-start:bf16[4096,4096]", 4e-08],
                                  ["copy-done:bf16[4096,4096]", 1.2000000000000002e-08]]
    assert reduce.idle_gaps_by_span(ev) == [["outside_spans", 0.099398796],
                                            ["bm::sync", 0.0027193],
                                            ["bm::step", 0.00074608]]
    assert reduce.top_modules(ev) == [["jit_f(5420128688597419897)", 3, 0.002133154]]
    assert reduce.device_summary(ev) == {"busy_s": 0.002133139, "window_s": 0.104997315,
                                         "per_device": [0.002133139]}
    # the runtime's own C++ events on the host plane are no spans
    assert {e.name for e in ev if e.plane == reduce.HOST_PLANE} == {"bm::step", "bm::sync"}


def test_mfu_and_both_old_rooflines_read_through_the_family_what_counts_says():
    from benchmarks import counts
    mistral, olmoe = config("mistral-7b-v0.3.serve-1chip"), config("olmoe-1b-7b.train-1chip")
    R = reduce.REDUCERS
    ctx = {"model": olmoe, "shapes": {"seq_len": 4096, "rows_per_chip": 8},
           "peaks": PEAKS, "e2e": {"train_tok_s_chip": 39460.0}}
    assert R["mfu"](ctx, {"rate": "train_tok_s_chip"}) == (
        100.0 * 39460.0 * counts.train_flops_per_token(olmoe, 4096) / 197e12)
    assert R["mfu"](ctx, {"rate": "train_tok_s_chip"}) == pytest.approx(21.471, abs=1e-3)
    dev = "/device:TPU:0"
    flash = ("%jvp_flash.1 = bf16[8,16,4096,128]{3,2,1,0} custom-call(bf16[8,16,4096,128]"
             "{3,2,1,0} %x)")
    ev = [reduce.Event(dev, reduce.MODULES_LINE, "jit_one_step(1)", i * 1e9, 8e8)
          for i in range(3)]
    ev += [reduce.Event(dev, reduce.OPS_LINE, flash, i * 1e9, 24.4e6) for i in range(3)]
    spec = json.load(open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                       "flash_attn_roofline.json")))
    f, b = counts.flash_flops(olmoe, 8, 4096), counts.flash_bytes(olmoe, 8, 4096)
    least = sum(max(f[k] / 197e12, b[k] / 819e9) for k in ("fwd", "bwd"))
    # the parent's own arithmetic, to the bit (one layer)
    assert R["roofline"](dict(ctx, events=ev), spec) == (
        100.0 * least * olmoe["num_hidden_layers"] * 3 / (3 * 24.4e6 / 1e9))
    tick = [reduce.Event(dev, reduce.MODULES_LINE, "jit_run(1)", i * 2e7, 15e6)
            for i in range(4)]
    got = R["roofline"]({"events": tick, "model": mistral, "peaks": PEAKS,
                         "counters": {"live_tokens_mean": 18_000}},
                        {"work": "decode_tick", "module": r"^jit_run\("})
    assert got == 100.0 * (counts.decode_tick_bytes(mistral, 18_000) / 819e9) / (6e7 / 1e9 / 4)


def _named_events(step_ns, scale=1.0):
    """Three runs of a step program whose expert GEMMs (the recorded
    operations' own texts) take ``scale`` times the least time the chip's
    peaks allow, split over the six of them."""
    with open(os.path.join(DATA, "op_texts.named.json")) as f:
        texts = json.load(f)["cells"]["olmoe.pretrain-4k"]
    spec = json.load(open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                                       "expert_gemm_roofline.train.json")))
    mine = [t for t in texts if re.search(spec["pattern"], t)]
    assert len(mine) == 6
    ev = []
    for i in range(3):
        ev.append(reduce.Event("/device:TPU:0", reduce.MODULES_LINE,
                               "jit_one_step(7)", i * step_ns, step_ns))
        at = i * step_ns
        for t in texts:     # every other operation of the step runs too
            dur = scale * LEAST_NS / 6 if t in mine else 1000.0
            ev.append(reduce.Event("/device:TPU:0", reduce.OPS_LINE, t, at, dur))
            at += dur
    return ev, spec


OLMOE_SHAPES = {"seq_len": 4096, "rows_per_chip": 8}
# by hand: 32768 tokens x top-8 through 3 x 2048 x 1024 weights, 2 FLOPs each,
# forward + twice that backward, at 197 TFLOP/s; the bytes (0.8 GB of
# weights, 1.07 GB a copy of the routed rows) take a tenth of that
LEAST_NS = 3 * (2 * 32768 * 8 * 3 * 2048 * 1024) / 197e12 * 1e9


def test_the_expert_gemms_required_work_by_hand():
    need = families.kernel_work(config("olmoe-1b-7b.train-1chip"), "expert_gemm",
                                OLMOE_SHAPES)
    assert need["fwd"]["flops"] == 2 * 32768 * 8 * 3 * 2048 * 1024 == 3_298_534_883_328
    assert need["bwd"]["flops"] == 2 * need["fwd"]["flops"]
    weights_b, rows_b = 64 * 3 * 2048 * 1024 * 2, 32768 * 8 * 2048 * 2
    assert need["fwd"]["bytes"] == weights_b + 2 * rows_b
    assert need["bwd"]["bytes"] == 2 * weights_b + 3 * rows_b
    assert LEAST_NS == pytest.approx(50.23e6, rel=1e-3)       # 50.2 ms a step
    for p in need.values():                                   # compute-bound
        assert p["flops"] / 197e12 > 4 * p["bytes"] / 819e9


def test_a_kernels_roofline_by_name_cannot_pass_100_when_the_device_takes_the_least_time():
    ctx = {"model": config("olmoe-1b-7b.train-1chip"), "shapes": OLMOE_SHAPES,
           "peaks": PEAKS, "counters": {}}
    ev, spec = _named_events(step_ns=9e8)
    assert spec["work"] == "kernel" and spec["counts"] == "expert_gemm"
    assert reduce.REDUCERS["roofline"](dict(ctx, events=ev), spec) == pytest.approx(100.0)
    slow, _ = _named_events(step_ns=9e8, scale=7.0)      # 352.7 ms of GEMMs a step
    assert reduce.REDUCERS["roofline"](dict(ctx, events=slow), spec) == pytest.approx(100 / 7)
    # nothing to read: no such operation, or no run of the module
    none = [e for e in ev if "grouped_matmul" not in e.name and "ragged" not in e.name]
    assert reduce.REDUCERS["roofline"](dict(ctx, events=none), spec) is None
    assert reduce.REDUCERS["roofline"](dict(ctx, events=ev), dict(spec, module="^jit_run")) is None
    with pytest.raises(AttributeError, match="no count function"):
        reduce.REDUCERS["roofline"](dict(ctx, events=ev), dict(spec, counts="nope"))


def test_counter_ratio_and_span_share_by_hand():
    R = reduce.REDUCERS
    ctx = {"counters": {"engine.spec_tokens_accepted": 30, "engine.spec_tokens_proposed": 40,
                        "zero": 0}}
    spec = {"numerator": "engine.spec_tokens_accepted",
            "denominator": "engine.spec_tokens_proposed", "scale": 100.0}
    assert R["counter_ratio"](ctx, spec) == 75.0
    assert R["counter_ratio"](ctx, dict(spec, denominator="zero")) is None
    assert R["counter_ratio"](ctx, dict(spec, numerator="missing")) is None
    ev = [reduce.Event("/device:TPU:0", reduce.OPS_LINE, "%fusion.1 = f32[] fusion()", 100, 800),
          reduce.Event(reduce.HOST_PLANE, "t", "serving::prefill", 0, 300),      # 100..300 inside
          reduce.Event(reduce.HOST_PLANE, "t", "serving::prefill", 250, 150),    # overlaps: ..400
          reduce.Event(reduce.HOST_PLANE, "t", "serving::drain", 500, 100),
          reduce.Event(reduce.HOST_PLANE, "t", "bm::step", 0, 1000)]
    assert R["span_share"]({"events": ev}, {"pattern": "^serving::prefill$"}) == pytest.approx(37.5)
    assert R["span_share"]({"events": ev}, {"pattern": "^serving::"}) == pytest.approx(50.0)
    assert R["span_share"]({"events": ev}, {"pattern": "^compile::"}) is None
    assert R["span_share"]({"events": ev[1:]}, {"pattern": "^serving::"}) is None   # no device window


def test_an_idle_gap_goes_to_the_innermost_span_that_covers_it():
    ev = [reduce.Event("/device:TPU:0", reduce.OPS_LINE, "%fusion.1 = f32[] fusion()", 0, 100),
          reduce.Event("/device:TPU:0", reduce.OPS_LINE, "%fusion.1 = f32[] fusion()", 900, 100),
          reduce.Event(reduce.HOST_PLANE, "t", "bm::step", 0, 700),
          reduce.Event(reduce.HOST_PLANE, "t", "serving::admit", 100, 50, {"queued": 3}),
          reduce.Event(reduce.HOST_PLANE, "t", "serving::prefill", 150, 250, {"rid": 9}),
          reduce.Event(reduce.HOST_PLANE, "t", "compile::prefill_paged_256", 200, 100)]
    gaps = dict(reduce.idle_gaps_by_span(ev))
    assert gaps == {"serving::admit": pytest.approx(50e-9),
                    "compile::prefill_paged_256": pytest.approx(100e-9),
                    "serving::prefill": pytest.approx(150e-9),
                    "bm::step": pytest.approx(300e-9),
                    "outside_spans": pytest.approx(200e-9)}


def test_read_xplane_keeps_the_programs_spans_with_their_stats(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bm::step"):
        with jax.profiler.TraceAnnotation("serving::admit", queued=3, free_pages=17):
            jnp.ones((8, 8)).sum().block_until_ready()
        with jax.profiler.TraceAnnotation("serving::prefill", rid=5, bucket=256):
            pass
        with jax.profiler.StepTraceAnnotation("trainer::dispatch", step_num=12):
            pass
        with jax.profiler.TraceAnnotation("compile::prefill_paged_256"):
            pass
        with jax.profiler.TraceAnnotation("Not::A::Span"):
            pass
    jax.profiler.stop_trace()
    spans = {e.name: e for e in reduce.read_xplane(str(tmp_path))
             if e.plane == reduce.HOST_PLANE}
    assert set(spans) == {"bm::step", "serving::admit", "serving::prefill",
                          "trainer::dispatch", "compile::prefill_paged_256"}
    assert {k: int(v) for k, v in spans["serving::admit"].stats.items()} == {
        "queued": 3, "free_pages": 17}
    assert int(spans["serving::prefill"].stats["rid"]) == 5
    assert int(spans["serving::prefill"].stats["bucket"]) == 256
    assert int(spans["trainer::dispatch"].stats["step_num"]) == 12
    inner, outer = spans["serving::admit"], spans["bm::step"]
    assert outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns
