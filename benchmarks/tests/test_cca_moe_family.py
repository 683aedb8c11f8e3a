"""The family of ZAYA1-8B (``families/cca_moe.py``): its leaves are the
program's parameters at the published widths (built abstractly: no weight is
made), its counts are ISSUE 31's numbers (or the test says why one differs),
the cell resolves through a harness that did not change and, shrunk, runs
end to end through ``run.py``'s own entry."""

import json
import math
import os
import re

import pytest

from benchmarks import families, program, run
from conftest import ROOT

CELL = "zaya1-8b.reasoning"
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "zaya1-8b.serve-1chip.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = ("batch_occupancy_pct", "cca_attn_share",
           "decode_tick_roofline", "decode_ticks_s", "device_idle_pct",
           "expert_gemm_share", "expert_peak_load", "itl_p50_ms",
           "moe_skip_share", "peak_hbm_gib")


@pytest.fixture(scope="module")
def resolved():
    return run.resolve(CELL, os.path.join(ROOT, "BENCHMARK.json"))


def test_the_cell_resolves(resolved):
    cell, config, mix, metrics, e2e = resolved
    assert config["family"] == "benchmarks.families.cca_moe"
    assert (mix["loop"], mix["clients"], mix["requests"],
            mix["stratify_block"]) == ("closed", 128, 1024, 128)
    assert (mix["prompt_len"], mix["output_len"]) == (
        {"dist": "loguniform", "lo": 128, "hi": 1024},
        {"dist": "loguniform", "lo": 512, "hi": 2048})
    assert {m["name"] for m in e2e} == {"serve_tok_s", "setup_s"}
    assert sorted(m["name"] for m in metrics) == sorted(
        n + ".zaya" for n in METRICS)
    assert all(m["moves"] == "serve_tok_s" and m["workloads"] == [CELL]
               for m in metrics)


def test_the_programs_parameters_are_the_familys_leaves_at_published_widths(resolved):
    """``program.build_model`` builds MoEForCausalLM abstractly from the
    configuration's fields and raises where a parameter's shape is not its
    leaf's: every leaf is some parameter's, and none is left over."""
    config = resolved[1]
    model, names = program.build_model(config)
    shapes = families.of(config).leaf_shapes(config)
    assert sorted(names.values()) == sorted(shapes)
    assert "head" not in shapes and shapes["embed"][0] == (262272, 2048)
    assert shapes["layers.3.q_down"][0] == (2048, 1024)
    assert shapes["layers.3.k_down"][0] == shapes["layers.3.v_down"][0] == (2048, 256)
    assert shapes["layers.3.conv0"] == ((2, 1280), "norm")
    assert shapes["layers.3.conv1"] == ((2, 10, 128, 128), "matrix")
    assert shapes["layers.3.o"][0] == (1024, 2048)
    assert shapes["layers.0.router_w3"] == ((256, 17), "router")
    assert shapes["layers.19.experts_gate_up"][0] == (16, 2048, 4096)
    assert shapes["layers.19.experts_down"][0] == (16, 2048, 2048)
    assert model.attention_kind == "cca"
    assert model.tick_counters == ("moe_assignments", "moe_peak_load",
                                   "moe_skipped")


def test_the_counts_are_the_issues_numbers(resolved):
    config = resolved[1]
    family = families.of(config)
    # by part: experts 201.33 M, CCA's projections 5.24 M, its convolutions
    # 0.33 M, the router 0.66 M
    assert 16 * family.expert_params(config) == 201_326_592
    assert family.projection_params(config) == 5_242_880
    assert family.conv_params(config) == 332_800
    assert family.router_params(config) == 660_992
    # ISSUE 31 reads 207,583,746 a layer: 4,096 = two H-wide vectors more
    # than the equations it writes out name (two norms and 2 x (s_r, s_o,
    # b_o) are 8 vectors of 2,048 here, and the temperature 2 numbers)
    assert family.small_params(config) == 8 * 2048 + 2
    assert family.layer_params(config) == 207_579_650 == 207_583_746 - 2 * 2048
    assert config["vocab_size"] * config["hidden_size"] == 537_133_056
    assert family.param_count(config) == 4_688_728_104
    assert round(family.param_count(config) / 1e9, 3) == 4.689
    total = sum(math.prod(s) for s, _ in family.leaf_shapes(config).values())
    assert total == family.param_count(config)
    # K and V of 2 heads of 128 in bf16: 1,024 B a token a layer
    assert family.kv_bytes_per_token(config) == 20 * 1024
    # 2 x 1,280 + 128 numbers of state a slot a layer; 13.8 MB over the engine
    assert family.slot_state_bytes(config) == 5376
    assert 128 * 20 * family.slot_state_bytes(config) == 13_762_560
    # 128 rows choosing 1 of 16 at random reach 15.996 of them
    assert round(family.experts_hit(config, 128), 3) == 15.996
    # a tick's weights: everything, the tied matrix once as the head
    assert round(family.weight_bytes(config) / 1e9, 2) == 9.40
    assert round(family.weight_bytes(config, 128) / 1e9, 2) == 9.40
    assert family.decode_tick_bytes(config, 0) == (
        family.weight_bytes(config, 128) + 2 * 13_762_560)
    assert (family.decode_tick_bytes(config, 137_000)
            - family.decode_tick_bytes(config, 0)) == 137_000 * 20_480
    assert family.train_flops_per_token(config, 4096) > 6 * (
        537_133_056 + 20 * 3 * 2048 * 2048)


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's row under the same key, but the keys in
    ``reduced`` (which the file states the published values of); what was
    read elsewhere is under ``assumed``, what was left out under its key."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == ["num_hidden_layers"]
    assert config["published"]["num_hidden_layers"] == 40
    assert config["num_hidden_layers"] == 20
    assert config["rope_theta"] == config["rope_parameters"]["hybrid"]["rope_theta"]
    assert "router_balancing_bias" in config["left_out"]
    assert len(config["assumed"]) >= 10


# event texts of the device trace's ``XLA Ops`` line, as the traced chip run
# of PR 31 printed them (seed 2147483701; cut where its dump cut them); the
# prefill's product as the program compiled for the described v5e prints it
PAGED = ("%paged_attention_decode.41 = bf16[128,2,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} custom-call("
         "s32[128,24]{1,0:T(8,128)S(1)} %get-tuple-element.1597, s32[128]{0:T(128)S(1)} %copy-done.319, "
         "bf16[128,2,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} %pad_maximum_fu")
TICK_GATE_UP = ("%fusion.210 = f32[16,128,4096]{2,1,0:T(8,128)S(1)} fusion(bf16[16,2048,4096]"
                "{2,1,0:T(8,128)(2,1)} %params__layers_15_moe_experts_w_gate_up__.1, "
                "bf16[128,2048]{1,0:T(8,128)(2,1)S(1)} %pallas_call.133), kind=kOutput, calls=%fused_computation.")
PREFILL_RAGGED = ("%ragged-dot-none.39 = f32[1024,4096]{1,0:T(8,128)S(1)} custom-call(%get-tuple-element.952, "
                  "%get-tuple-element.953, %get-tuple-element.954, %get-tuple-element.955, %get-tuple-element.952, "
                  "/*index=5*/%fusion, %params__layers_0_moe_experts_w_gate_up__.1), custom_call_target=\"tpu_custom_call\"")
HEAD = ("%fusion.6344 = bf16[128,262272]{1,0:T(8,128)(2,1)} fusion(bf16[128,2048]{1,0:T(8,128)(2,1)S(1)} "
        "%pallas_call.142, bf16[262272,2048]{1,0:T(8,128)(2,1)} %params__embed_tokens__.1), kind=kOutput, "
        "calls=%fused_computation.5980")
# CCA's front in the tick (the product over q_down, as the compiled program
# prints it), which no pattern can name: its weight arrives as prefetched
# slices behind a ConcatBitcast (PERF.md section 7, seam (g))
TICK_FRONT = ("%fusion.6312 = bf16[128,1024]{1,0:T(8,128)(2,1)S(1)} fusion(%pallas_call.108, %custom-call.98), "
              "kind=kOutput, calls=%fused_computation.5949")
TEXTS = (PAGED, TICK_GATE_UP, PREFILL_RAGGED, HEAD, TICK_FRONT)


@pytest.mark.parametrize("metric,reads", [
    ("cca_attn_share.zaya", {PAGED}),
    ("expert_gemm_share.zaya", {TICK_GATE_UP, PREFILL_RAGGED}),
])
def test_each_share_reads_its_own_operations_and_no_others(resolved, metric, reads):
    rx = re.compile(next(m for m in resolved[3] if m["name"] == metric)["pattern"])
    assert {t for t in TEXTS if rx.search(t)} == reads


def test_the_cell_runs_end_to_end_at_a_tiny_size(tmp_path, monkeypatch):
    """The committed cell with its model, its engine and its traffic shrunk
    (same kinds, same files, same keys), through ``run.run_cell`` with the
    trace on: ``correct`` against the reference, and every metric a run
    without a chip can read (the device's own need the device trace)."""
    from benchmarks import traffic
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=128, moe_intermediate_size=256, head_dim=32,
               num_attention_heads=4, num_hidden_layers=2, vocab_size=512,
               dtype="float32",
               engine=dict(max_batch=8, max_len=64, page_size=16, num_pages=28),
               check=dict(cfg["check"], served_logit_gap_max=1e-3,
                          served_logit_gap_mean=1e-4))
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    next(c for c in bench["configs"]
         if c["name"] == "zaya1-8b.serve-1chip")["file"] = str(tmp_path / "tiny.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = dict(traffic.load("reasoning"), clients=8, requests=64,
               stratify_block=8,
               prompt_len={"dist": "loguniform", "lo": 4, "hi": 32},
               output_len={"dist": "loguniform", "lo": 8, "hi": 32})
    monkeypatch.setattr(traffic, "load", lambda name: mix)
    out = run.run_cell(CELL, 2**31 + 31, 3.0, True, require_chip=False,
                       benchmark_file=str(tmp_path / "BENCHMARK.json"))
    assert out["correct"] is True and out["failed"] == 0
    got = set(out["metrics"])
    assert {n + ".zaya" for n in ("batch_occupancy_pct", "decode_ticks_s",
                                  "expert_peak_load", "itl_p50_ms",
                                  "moe_skip_share")} <= got
    assert 1.0 <= out["metrics"]["expert_peak_load.zaya"]["value"] <= 16.0
    assert 0.0 <= out["metrics"]["moe_skip_share.zaya"]["value"] < 100.0
