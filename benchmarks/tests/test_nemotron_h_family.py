"""The family of NVIDIA-Nemotron-3-Nano-30B-A3B (``families/nemotron_h.py``):
its leaves are the program's parameters at the published widths (built
abstractly: no weight is made), its counts are ISSUE 33's hand arithmetic,
the reference agrees with the program at a small size, and the cell resolves
through a harness that did not change and, shrunk, runs end to end through
``run.py``'s own entry."""

import hashlib
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import families, program, run, weights
from conftest import ROOT
from test_mla_moe_family import UNCHANGED

CELL = "nemotron-3-nano.agent-turns"
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "nemotron-3-nano-30b-a3b.serve-1chip.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = ("batch_occupancy_pct", "decode_tick_roofline", "decode_ticks_s",
           "device_idle_pct", "expert_gemm_share", "expert_held_share",
           "expert_peak_load", "itl_p50_ms", "paged_attn_share",
           "peak_hbm_gib", "ssm_update_roofline", "ssm_update_share")


@pytest.fixture(scope="module")
def resolved():
    return run.resolve(CELL, os.path.join(ROOT, "BENCHMARK.json"))


def test_the_cell_resolves_with_every_harness_file_unchanged(resolved):
    cell, config, mix, metrics, e2e = resolved
    assert config["family"] == "benchmarks.families.nemotron_h"
    assert cell["chips"] == 1 and cell["traffic"] == "agent-turns"
    assert (mix["loop"], mix["clients"], mix["requests"],
            mix["stratify_block"]) == ("closed", 192, 1536, 192)
    assert (mix["prompt_len"], mix["output_len"]) == (
        {"dist": "loguniform", "lo": 512, "hi": 4096},
        {"dist": "loguniform", "lo": 256, "hi": 1024})
    assert mix["clients"] == config["engine"]["max_batch"]
    assert {m["name"] for m in e2e} == {"serve_tok_s", "setup_s"}
    assert sorted(m["name"] for m in metrics) == sorted(
        n + ".nemotron" for n in METRICS)
    assert all(m["moves"] == "serve_tok_s" and m["workloads"] == [CELL]
               for m in metrics)
    for path, digest in UNCHANGED.items():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, path


def test_the_prompts_reach_fourteen_prefill_programs_all_warmed(resolved):
    """Prompts of 512-4096 tokens cover 29 page multiples; the engine, whose
    table spans 40 pages, pads them to steps of 2 pages (14 programs, where
    ISSUE 33 hoped for 12 at most), and the harness's warm-up (one prompt a
    page multiple) reaches every program they build."""
    from benchmarks import traffic
    from paddle_tpu.inference.serving import MAX_PREFILL_PROGRAMS
    _, config, mix, _, _ = resolved
    eng = config["engine"]
    assert set(eng) == {"max_batch", "max_len", "page_size", "num_pages"}
    step = eng["page_size"] * -(-(eng["max_len"] // eng["page_size"])
                                // MAX_PREFILL_PROGRAMS)
    assert step == 256
    sched = traffic.serving_schedule(mix, 2**31 + 33, 45.0,
                                     config["vocab_size"], eng["max_len"])
    lens = [len(r.prompt) for r in sched.requests]
    assert min(lens) >= 512 and max(lens) <= 4096
    assert len(set(lens[:192])) > 180       # a block is 192 lengths
    assert max(len(r.prompt) + r.out_len for r in sched.requests) <= eng["max_len"]
    programs = {-(-n // step) * step for n in lens}
    warmed = {-(-(-(-n // eng["page_size"]) * eng["page_size"]) // step) * step
              for n in lens}
    assert programs == warmed == set(range(768, 4097, 256))


def test_the_programs_parameters_are_the_familys_leaves_at_published_widths(resolved):
    config = resolved[1]
    model, names = program.build_model(config)
    shapes = families.of(config).leaf_shapes(config)
    assert sorted(names.values()) == sorted(shapes)
    assert model.cfg.kinds == "MEMEM*EMEMEM*EME"
    assert shapes["embed"][0] == (65536, 2688) and shapes["head"][0] == (2688, 65536)
    assert shapes["layers.0.in_proj"][0] == (2688, 4096 + 6144 + 64)
    assert shapes["layers.0.conv"] == ((4, 6144), "norm")
    assert shapes["layers.0.conv_bias"] == ((6144,), "router")
    assert shapes["layers.0.A_log"] == shapes["layers.0.dt_bias"] == ((64,), "router")
    assert shapes["layers.0.gate_norm"][0] == (4096,)
    assert shapes["layers.0.out_proj"][0] == (4096, 2688)
    assert shapes["layers.5.qkv"][0] == (2688, (32 + 2 * 2) * 128)
    assert shapes["layers.5.o"][0] == (4096, 2688)
    assert shapes["layers.1.router"] == ((2688, 128), "router")
    assert shapes["layers.1.router_bias"] == ((128,), "router")
    assert shapes["layers.1.experts_up"][0] == (64, 2688, 1856)
    assert shapes["layers.1.experts_down"][0] == (64, 1856, 2688)
    assert shapes["layers.1.shared_up"][0] == (2688, 3712)
    assert model.attention_kind == "hybrid"
    assert model.tick_counters == ("moe_assignments", "moe_peak_load",
                                   "moe_assignments_held")


def test_the_counts_are_the_issues_numbers(resolved):
    config = resolved[1]
    family = families.of(config)
    uncut = dict(config, num_hidden_layers=52, n_routed_experts=128,
                 vocab_size=131072)
    # by part: in_proj 2688 x 10304 + out_proj 4096 x 2688; q and o 2 x 2688
    # x 4096 + k and v 2 x 2688 x 256; one expert 2 x 2688 x 1856
    assert family.mamba_matrix_params(config) == 27_697_152 + 11_010_048
    assert family.mamba_small_params(config) == 5 * 6144 + 3 * 64 + 4096 + 2688
    assert family.attention_params(config) == 2 * 2688 * 4096 + 2 * 2688 * 256
    assert family.expert_params(config) == 9_977_856
    assert family.shared_params(config) == 2 * 2688 * 3712
    assert family.router_params(config) == 2688 * 128 + 128
    assert family.layer_params(uncut, "E") == 1_297_468_160
    assert family.layer_params(config, "E") == 658_885_376
    assert family.kinds(uncut).count("M") == family.kinds(uncut).count("E") == 23
    assert family.kinds(uncut).count("*") == 6
    assert (family.kinds(config).count("M"), family.kinds(config).count("E"),
            family.kinds(config).count("*")) == (7, 7, 2)
    assert round(family.param_count(uncut) / 1e9, 2) == 31.58
    assert family.param_count(config) == 5_282_534_208
    assert round(family.param_count(config) / 1e9, 2) == 5.28
    total = sum(math.prod(s) for s, _ in family.leaf_shapes(config).values())
    assert total == family.param_count(config)
    # K and V of 2 heads of 128 in bf16 in the 2 attention layers
    assert family.kv_bytes_per_token(config) == 2 * 2 * 2 * 128 * 2 == 2048
    # a slot a layer: [64, 64, 128] float32 and [3, 6144] bf16
    assert family.slot_state_bytes(config) == 2_097_152 + 36_864
    assert 192 * 7 * family.slot_state_bytes(config) == 2_868_117_504
    # 192 rows choosing 6 of 128 at random reach all but 0.006 of the 64 held
    assert round(family.experts_hit(config, 192), 2) == 63.99
    assert round(family.weight_bytes(config) / 1e9, 2) == 10.22
    state = 2 * 192 * 7 * family.slot_state_bytes(config)
    assert family.decode_tick_bytes(config, 0) == (
        family.weight_bytes(config, 192) + state)
    assert (family.decode_tick_bytes(config, 400_000)
            - family.decode_tick_bytes(config, 0)) == 400_000 * 2048
    # the kernel, one tick: 5 P N FLOPs a head; the float32 state once each
    # way and x, dt x's share and y a head, B and C a group beside it
    work = family.ssm_state_update(config, {})["fwd"]
    assert work["flops"] == 7 * 192 * 64 * 5 * 64 * 128
    assert work["bytes"] == 7 * 192 * (2 * 2_097_152 + 4 * (3 * 4096 + 2 * 1024))
    assert round(work["bytes"] / 819e9 * 1e3, 2) == 6.98      # ms a tick
    assert family.train_flops_per_token(config, 4096) > 6 * 2688 * 65536


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row under the same value, but the keys in
    ``reduced`` (which the file states the published values of); what the
    config does not settle is under ``assumed``."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    for key in config["reduced"]:
        assert config["published"][key] == row["config"][key]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (16, 64, 65536)
    assert config["router_width"] == 128 and config["num_experts_per_tok"] == 6
    assert "2 chips" in config["deployment"]
    said = " ".join(config["assumed"])
    for word in ("d_inner", "time_step_limit", "NO ROTARY", "float32",
                 "norm_before_gate", "e_score_correction_bias"):
        assert word in said, word


# event texts of the device trace's ``XLA Ops`` line, as the tick compiled for
# the described v5e prints them (cut where the operands end); the two
# ``ragged-dot``s are the sorted path's, which a tick of more than
# ``MoELayer.DENSE_ROWS`` rows would print
SSM = ("%ssm_state_update.14 = (f32[192,1,64,64]{3,2,1,0:T(8,128)S(1)}, f32[192,64,64,128]{3,2,1,0:T(8,128)}) "
       "custom-call(%broadcast.176, %multiply_bitcast_fusion.6, %bitcast.606, %bitcast.605, %slot_state_0__1_.1), "
       "custom_call_target=\"tpu_custom_call\"")
PAGED = ("%paged_attention_decode.4 = bf16[192,2,16,128]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call(%copy-done.84, "
         "%copy-done.186, %bitcast.590, %bitcast.50, %bitcast.54), custom_call_target=\"tpu_custom_call\"")
RAGGED_DOWN = ("%ragged-dot-none.4 = f32[1152,2688]{1,0:T(8,128)} custom-call(%get-tuple-element.362, %copy-done.173, "
               "%copy-done.174, %copy-done.175, %get-tuple-element.362, /*index=5*/%maximum_multiply_fusion.2, "
               "%params__layers_10_mixer_experts_w_down__.1), custom_call_target=\"tpu_custom_call\"")
RAGGED_UP = ("%ragged-dot-none.3 = f32[1152,1856]{1,0:T(8,128)S(1)} custom-call(%get-tuple-element.354, %copy-done.176, "
             "%copy-done.177, %copy-done.178, %get-tuple-element.354, /*index=5*/%fusion.14, %copy.428), "
             "custom_call_target=\"tpu_custom_call\"")
HEAD = ("%fusion.360 = bf16[192,65536]{1,0:T(8,128)(2,1)} fusion(%pallas_call.154, %params__lm_head__.1), "
        "kind=kOutput, calls=%fused_computation.560")
# the tick's experts as the traced chip run of PR 33 printed them (every held
# expert over every row: one fused batched product a layer)
TICK_EXPERTS = ("%bitcast_convert_fusion.6 = bf16[192,2688]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[64,1856,2688]{2,1,0:T(8,128)(2,1)} "
                "%params__layers_1_mixer_experts_w_down__.1, bf16[64,2688,1856]{1,2,0:T(8,128)(2,1)} %params__layers_1_mixer_experts_w_up__.1")
TEXTS = (SSM, PAGED, RAGGED_DOWN, RAGGED_UP, HEAD, TICK_EXPERTS)


@pytest.mark.parametrize("metric,reads", [
    ("ssm_update_share.nemotron", {SSM}),
    ("ssm_update_roofline.nemotron", {SSM}),
    ("paged_attn_share.nemotron", {PAGED}),
    ("expert_gemm_share.nemotron", {RAGGED_DOWN, RAGGED_UP, TICK_EXPERTS}),
])
def test_each_share_reads_its_own_operations_and_no_others(resolved, metric, reads):
    rx = re.compile(next(m for m in resolved[3] if m["name"] == metric)["pattern"])
    assert {t for t in TEXTS if rx.search(t)} == reads


def _tiny(tmp_path, **more):
    """The committed cell's files with the model, the engine and the traffic
    shrunk (same kinds, same keys): (benchmark file, configuration, mix)."""
    from benchmarks import traffic
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_hidden_layers=7, mamba_num_heads=8,
               mamba_head_dim=8, n_groups=2, chunk_size=16,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               router_width=16, n_routed_experts=8, first_expert_held=0,
               num_experts_per_tok=3, moe_intermediate_size=48,
               moe_shared_expert_intermediate_size=96, vocab_size=512,
               dtype="float32",
               engine=dict(max_batch=8, max_len=96, page_size=16,
                           num_pages=40),
               check=dict(cfg["check"], served_logit_gap_max=1e-3,
                          served_logit_gap_mean=1e-4))
    cfg.update(more)
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    next(c for c in bench["configs"]
         if c["name"] == cfg["name"])["file"] = str(tmp_path / "tiny.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = dict(traffic.load("agent-turns"), clients=8, requests=64,
               stratify_block=8,
               prompt_len={"dist": "loguniform", "lo": 8, "hi": 48},
               output_len={"dist": "loguniform", "lo": 8, "hi": 32})
    return str(tmp_path / "BENCHMARK.json"), cfg, mix


def test_the_reference_agrees_with_the_program_at_a_small_size(tmp_path):
    """Seven blocks ``MEMEM*E`` with half the experts held: the program's
    whole-sequence forward (the chunked scan, the sorted expert products)
    against the family's reference (the recurrence token by token, every
    held expert over every token), in float32 on the family's seeded
    weights, to a few units of float32 rounding on logits of size ~1."""
    _, cfg, _ = _tiny(tmp_path)
    model, names = program.build_model(cfg)
    program.install(model, names, weights.make_all(5, cfg))
    ids = np.random.default_rng(0).integers(0, 512, (2, 40), dtype=np.int32)
    got = np.asarray(model.eval()(jnp.asarray(ids)))
    get = lambda ns: weights.make_some(5, cfg, ns)
    rows, cols = np.repeat(np.arange(2), 40), np.tile(np.arange(40), 2)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(families.of(cfg).logits_at(
            cfg, get, [(jnp.asarray(ids), rows, cols)])[0]).reshape(2, 40, 512)
    assert np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < 3e-5


def test_the_cell_runs_end_to_end_at_a_tiny_size(tmp_path, monkeypatch):
    """Through ``run.run_cell`` with the trace on: ``correct`` against the
    reference, and every metric a run without a chip can read (the device's
    own need the device trace)."""
    from benchmarks import traffic
    bench, _, mix = _tiny(tmp_path)
    monkeypatch.setattr(traffic, "load", lambda name: mix)
    out = run.run_cell(CELL, 2**31 + 33, 3.0, True, require_chip=False,
                       benchmark_file=bench)
    assert out["correct"] is True and out["failed"] == 0
    got = set(out["metrics"])
    assert {n + ".nemotron" for n in ("batch_occupancy_pct", "decode_ticks_s",
                                      "expert_peak_load", "expert_held_share",
                                      "itl_p50_ms")} <= got
    # the spec scales by the published router's 128 outputs; this one has 16
    peak = out["metrics"]["expert_peak_load.nemotron"]["value"] * 16 / 128
    assert 1.0 <= peak <= 16.0
    assert 35.0 < out["metrics"]["expert_held_share.nemotron"]["value"] < 65.0
