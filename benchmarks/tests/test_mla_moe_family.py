"""The family of GLM-4.7-Flash (``families/mla_moe.py``): its leaves are the
program's parameters at the published widths (built abstractly: no weight is
made), its counts are ISSUE 27's hand-worked numbers, and the cell resolves
through a harness that did not change."""

import hashlib
import json
import math
import os

import pytest

from benchmarks import families, program, run
from conftest import ROOT

CELL = "glm-4.7-flash.long-answers"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# sha256 of the harness's files at the parent (commit 4c5daeb): a new
# architecture is new files only
UNCHANGED = {
    "benchmarks/families/__init__.py": "b0753e08d7d52f8317fb90b0e83e892586ca0136a3619a0daaaf6f7137c37d7b",
    "benchmarks/check.py": "0a618cd40dcda901f834abff2a296577fbb51870019bf19bfe65a29aa0037d50",
    "benchmarks/serve.py": "b94a2b0bcbaedccf33ca87669cf15ebacfc7d2540795ac4ff4c3d95ceadefa1d",
    "benchmarks/reduce.py": "5751242f76432d20a6cb8b68626834a657bbf3ea597459c38a71da29a62482ea",
    "benchmarks/weights.py": "65b8edfd1f21e5edc795df6b9f495d5b779c84f8687c4c1851a82b73280a8ea9",
    "benchmarks/run.py": "671ca707ae9dd7dd6be82728c0da476452b3debb3bb23e513736e321a93df96c",
    "benchmarks/program.py": "e672f58d4257383cfb992cf3b67e79c3c89ef7756b0126f0416182c296d1cced",
    "benchmarks/traffic.py": "8276879a24988b5e9fc904bbefa5bbf0090e9c7b9aacd101bfd08fe72a65a768",
    "benchmarks/refs/decoder.py": "d2f9cca366d5dbce940d77f041bbf416d4b6bc608ad2062093fdd3c69271fc44",
    "benchmarks/refs/olmoe.py": "ccb50b1aee775b467786c55834d3ead5259dde1bb5caec6c33e04224c0850b25",
    "benchmarks/tools/control.py": "c7dfd71fe25a4d3c671278bae2eb0c02d74f51488b8df1894afaa06825ea79f1",
}


@pytest.fixture(scope="module")
def resolved():
    return run.resolve(CELL, os.path.join(ROOT, "BENCHMARK.json"))


def test_the_cell_resolves_with_every_harness_file_unchanged(resolved):
    cell, config, mix, metrics, e2e = resolved
    assert config["family"] == "benchmarks.families.mla_moe"
    assert (mix["loop"], mix["clients"]) == ("closed", 64)
    assert {m["name"] for m in e2e} == {"serve_tok_s", "setup_s"}
    assert sorted(m["name"] for m in metrics) == sorted(
        n + ".glm" for n in ("batch_occupancy_pct", "decode_tick_roofline",
                             "decode_ticks_s", "device_idle_pct",
                             "expert_gemm_share", "expert_peak_load",
                             "itl_p50_ms", "latent_attn_share",
                             "peak_hbm_gib"))
    assert all(m["moves"] == "serve_tok_s" and m["workloads"] == [CELL]
               for m in metrics)
    for path, digest in UNCHANGED.items():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, path


def test_the_programs_parameters_are_the_familys_leaves_at_published_widths(resolved):
    """``program.build_model`` builds MoEForCausalLM abstractly from the
    configuration's fields and raises where a parameter's shape is not its
    leaf's: every leaf is some parameter's, and none is left over."""
    config = resolved[1]
    model, names = program.build_model(config)
    shapes = families.of(config).leaf_shapes(config)
    assert sorted(names.values()) == sorted(shapes)
    assert shapes["layers.3.kv_a"][0] == (2048, 576)
    assert shapes["layers.3.kv_b"][0] == (512, 20 * (192 + 256))
    assert shapes["layers.3.experts_gate_up"][0] == (64, 2048, 2 * 1536)
    assert shapes["layers.0.gate_up"][0] == (2048, 2 * 10240)
    assert shapes["layers.1.router_bias"] == ((64,), "router")
    assert model.attention_kind == "mla"
    assert model.tick_counters == ("moe_assignments", "moe_peak_load")


def test_the_counts_are_the_issues_hand_worked_numbers(resolved):
    config = resolved[1]
    family = families.of(config)
    # attention 21.76 M a layer, an expert 9.44 M, 4.531 B parameters in all
    assert family.attention_params(config) == 21_757_952
    assert family.expert_params(config) == 9_437_184
    assert family.param_count(config) == 4_530_936_960
    assert round(family.param_count(config) / 1e9, 3) == 4.531
    total = sum(math.prod(s) for s, _ in family.leaf_shapes(config).values())
    assert total == family.param_count(config)
    # every weight but the embedding: 8.43 GB; with the routed experts as
    # far as 64 rows x top-4 are expected to hit them (62.97 of 64): 8.31 GB
    assert round(family.experts_hit(config, 64), 2) == 62.97
    assert round(family.weight_bytes(config) / 1e9, 2) == 8.43
    assert round(family.weight_bytes(config, 64) / 1e9, 2) == 8.31
    # one 576-wide bf16 row a token a layer: 1,152 B, 8,064 B over 7 layers
    assert family.kv_bytes_per_token(config) == 8064
    assert family.decode_tick_bytes(config, 0) == family.weight_bytes(config, 64)
    assert (family.decode_tick_bytes(config, 96_000)
            - family.decode_tick_bytes(config, 0)) == 96_000 * 8064
    # the absorbed attention: 2 x 20 x (576 + 512) = 43.5 kFLOP a cached
    # row a layer; at 96,000 live rows 774 MB of rows a tick, and the
    # queries in and the contexts out
    work = family.latent_attention_decode(
        config, {"live_tokens": 96_000, "rows": 64})["fwd"]
    assert work["flops"] == 7 * 96_000 * 43_520
    assert work["bytes"] == 96_000 * 8064 + 7 * 2 * 64 * 20 * (576 + 512)
    # the routed experts of a tick: 6 layers x 62.97 experts x 18.9 MB
    work = family.expert_gemm_decode(config, {"rows": 64})["fwd"]
    assert round(work["bytes"] / 1e9, 2) == 7.14
    assert work["flops"] == 6 * 2 * 64 * 4 * 9_437_184
    assert family.train_flops_per_token(config, 4096) > 0


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog's row under the same key, but the keys in
    ``reduced`` (which the file states the published values of)."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-4.7-Flash")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-4.7-flash.serve-1chip.json")) as f:
        config = json.load(f)
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert {k: config["published"][k] for k in differs} == {
        k: row["config"][k] for k in differs}


# event texts of the device trace's ``XLA Ops`` line, from a traced run of the
# cell on the chip (PR 27), cut after the operands the patterns read
TICK_GATE_UP = ("%fusion.79 = f32[64,64,3072]{2,1,0:T(8,128)S(1)} fusion(bf16[64,2048,3072]"
                "{2,1,0:T(8,128)(2,1)} %params__layers_1_moe_experts_w_gate_up__.1, bf16[64,2048]")
TICK_DOWN = ("%bitcast_convert_fusion.3 = bf16[64,2048]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[64,1536,"
             "2048]{2,1,0:T(8,128)(2,1)} %params__layers_3_moe_experts_w_down__.1, f32[64,64,3072]")
PREFILL_RAGGED = ("%ragged-dot-none.7 = f32[2560,3072]{1,0:T(8,128)S(1)} custom-call(s32[1]"
                  "{0:T(128)} %get-tuple-element.36, s32[65]{0:T(128)S(1)} %get-tuple-element.37")
LATENT = ("%latent_attention_decode.14 = bf16[64,20,512]{2,1,0:T(8,128)(2,1)S(1)} custom-call("
          "s32[64,24]{1,0:T(8,128)S(1)} %get-tuple-element.501, s32[64]{0:T(128)S(1)} %copy-done.1")
HEAD = ("%fusion.400 = bf16[64,154880]{1,0:T(8,128)(2,1)} fusion(bf16[64,2048]{1,0:T(8,128)(2,1)"
        "S(1)} %pallas_call.93, bf16[2048,154880]{1,0:T(8,128)(2,1)} %params__lm_head__.1)")
SHARED = ("%fusion.207 = bf16[64,2048]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[64,2048]{1,0} "
          "%convolution_add_fusion.12, bf16[1536,2048]{1,0} %params__layers_1_shared_experts_down_proj__.1)")
AFTER = ("%fusion.5 = bf16[64,5120]{1,0} fusion(bf16[64,20,512]{2,1,0} "
         "%latent_attention_decode.14, bf16[512,8960]{1,0} %params__layers_0_self_attn_kv_b_proj__.1)")


def _pattern(resolved, name):
    import re
    return re.compile(next(m for m in resolved[3] if m["name"] == name)["pattern"])


def test_the_expert_share_reads_the_ticks_matmuls_and_the_prefills_ragged_dots(resolved):
    rx = _pattern(resolved, "expert_gemm_share.glm")
    for text in (TICK_GATE_UP, TICK_DOWN, PREFILL_RAGGED,
                 "%jvp_grouped_matmul_.2 = f32[8,8]{1,0} custom-call(s32[4]{0} %a)"):
        assert rx.search(text), text
    for text in (LATENT, HEAD, SHARED, AFTER):
        assert not rx.search(text), text


def test_the_latent_share_reads_the_kernel_alone(resolved):
    from paddle_tpu.ops.pallas import KERNEL_NAMES
    rx = _pattern(resolved, "latent_attn_share.glm")
    assert rx.search(LATENT)
    for text in (TICK_GATE_UP, TICK_DOWN, PREFILL_RAGGED, HEAD, SHARED, AFTER):
        assert not rx.search(text), text
    for kernel in KERNEL_NAMES:
        assert bool(rx.search(f"%{kernel}.3 = bf16[8]{{0}} custom-call(")) == (
            kernel == "latent_attention_decode")
