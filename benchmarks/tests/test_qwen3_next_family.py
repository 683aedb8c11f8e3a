"""The family of Qwen3-Next-80B-A3B-Instruct (``families/qwen3_next.py``): its
leaves are the program's parameters at the published widths (built
abstractly: no weight is made), its counts are ISSUE 46's hand arithmetic,
the reference (the delta rule token by token, a full softmax) agrees with the
program (chunks, kernels' twins) at a small size, and the cell resolves
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

CELL = "qwen3-next.long-generation"
NAME = "qwen3-next-80b-a3b.serve-1chip"
CONFIG = os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = ("batch_occupancy_pct", "decode_tick_roofline", "decode_ticks_s",
           "delta_update_roofline", "delta_update_share", "device_idle_pct",
           "expert_gemm_share", "expert_held_share", "expert_peak_load",
           "host_wait_share", "itl_p50_ms", "paged_attn_share",
           "peak_hbm_gib", "prefill_stream_share",
           "stream_unattributed_share", "tick_stream_ms")


@pytest.fixture(scope="module")
def resolved():
    return run.resolve(CELL, os.path.join(ROOT, "BENCHMARK.json"))


def test_the_cell_resolves_with_every_harness_file_unchanged(resolved):
    cell, config, mix, metrics, e2e = resolved
    assert config["family"] == "benchmarks.families.qwen3_next"
    assert cell["chips"] == 1 and cell["traffic"] == "long-generation"
    assert (mix["loop"], mix["clients"], mix["requests"],
            mix["stratify_block"]) == ("closed", 192, 1536, 48)
    assert (mix["prompt_len"], mix["output_len"]) == (
        {"dist": "loguniform", "lo": 128, "hi": 1024},
        {"dist": "loguniform", "lo": 512, "hi": 2048})
    assert mix["clients"] == config["engine"]["max_batch"]
    assert {m["name"] for m in e2e} == {"serve_tok_s", "setup_s"}
    assert sorted(m["name"] for m in metrics) == sorted(
        n + ".qwen3next" for n in METRICS)
    assert all(m["moves"] == "serve_tok_s" and m["workloads"] == [CELL]
               for m in metrics)
    for path, digest in UNCHANGED.items():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, path


def test_what_this_cell_added_to_the_benchmark_file_keeps_its_form():
    """The driver's rules of form, held against the entries this family
    brought (found by name: a later PR appends behind them): names of at
    most 64 of their characters, one-line texts of 1 to 200 printable
    characters, just the keys each kind of entry has, the whole file under
    64 KiB."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    bench = json.load(open(path))
    assert os.path.getsize(path) <= 64 * 1024
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    unit = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
    config = next(c for c in bench["configs"] if c["name"] == NAME)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == NAME
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert CELL in serve["workloads"] and serve["bound"] == 0.02
    metrics = [m for m in bench["per_layer"]
               if m["name"].endswith(".qwen3next")]
    assert sorted(m["name"] for m in metrics) == sorted(
        n + ".qwen3next" for n in METRICS)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    for m in metrics:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert unit.fullmatch(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["layer"] in ("engine", "model", "kernels", "device")
    for text in ([config["why"], config["source"], cell["why"]]
                 + [m["layer"] for m in metrics]):
        assert 1 <= len(text) <= 200 and text.isprintable(), text
    for word in ([config["name"], cell["name"], cell["config"],
                  cell["traffic"]] + [m["name"] for m in metrics]):
        assert name.fullmatch(word), word
    assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", config["file"])
    # a roofline share of the accepted benchmark is reported where its
    # end-to-end metric is: the tick's, under this cell's own name
    assert "decode_tick_roofline.qwen3next" in {m["name"] for m in metrics}


def test_the_prompts_reach_eight_prefill_programs_and_no_page_runs_out(resolved):
    """A table of 24 pages keeps page-wide prefill programs: prompts of
    128-1,024 tokens reach at most EIGHT, 128 to 1,024, every one warmed by
    the harness; the longest request fits ``max_len``, and 4,608 pages are
    24 a slot: the named traffic can preempt nothing."""
    from benchmarks import traffic
    from paddle_tpu.inference.serving import MAX_PREFILL_PROGRAMS
    _, config, mix, _, _ = resolved
    eng = config["engine"]
    assert eng == {"max_batch": 192, "max_len": 3072, "page_size": 128,
                   "num_pages": 4608}
    assert eng["max_len"] // eng["page_size"] == 24 <= MAX_PREFILL_PROGRAMS
    assert eng["num_pages"] == eng["max_batch"] * 24
    sched = traffic.serving_schedule(mix, 2**31 + 46, 45.0,
                                     config["vocab_size"], eng["max_len"])
    lens = [len(r.prompt) for r in sched.requests]
    outs = [r.out_len for r in sched.requests[192:]]    # the first are cut
    assert min(lens) >= 128 and max(lens) <= 1024
    assert min(outs) >= 512 and max(outs) <= 2048
    assert 425 < np.mean(lens) < 437 and 1100 < np.mean(outs) < 1116
    assert len(set(lens[:48])) >= 47            # a block is 48 lengths
    assert max(n + o for n, o in zip(lens[192:], outs)) <= eng["max_len"]
    assert {-(-n // 128) * 128 for n in lens} <= set(range(128, 1025, 128))
    assert max(int(r.prompt.max()) for r in sched.requests) < 18992


def test_the_programs_parameters_are_the_familys_leaves_at_published_widths(resolved):
    config = resolved[1]
    family = families.of(config)
    model, names = program.build_model(config)
    shapes = family.leaf_shapes(config)
    assert sorted(names.values()) == sorted(shapes)
    assert model.cfg.kinds == config["hybrid_pattern"] == family.pattern(config)
    assert family.kinds(config) == "ddda" * 3
    assert len(model.cfg.kinds) == 24 and config["num_hidden_layers"] == 12
    assert shapes["embed"][0] == (18992, 2048)
    assert shapes["head"][0] == (2048, 18992)       # untied
    assert shapes["final_norm"] == ((2048,), "router")      # zero-centred
    assert shapes["layers.0.norm"] == ((2048,), "router")
    assert shapes["layers.0.in_proj"][0] == (2048, 2048 + 2048 + 4096 + 4096 + 64)
    assert shapes["layers.0.conv"] == ((4, 8192), "norm")
    assert shapes["layers.0.A_log"] == ((32,), "router")
    assert shapes["layers.0.gate_norm"] == ((128,), "norm")
    assert shapes["layers.0.out_proj"][0] == (4096, 2048)
    assert shapes["layers.6.qkv"][0] == (2048, 16 * 512 + 2 * 512)
    assert shapes["layers.6.q_norm"] == ((256,), "router")
    assert shapes["layers.6.o"][0] == (4096, 2048)
    assert shapes["layers.1.router"] == ((2048, 512), "router")
    assert shapes["layers.1.shared_gate"] == ((2048, 1), "router")
    assert shapes["layers.1.experts_gate_up"][0] == (64, 2048, 1024)
    assert shapes["layers.1.experts_down"][0] == (64, 512, 2048)
    assert shapes["layers.1.shared_gate_up"][0] == (2048, 1024)
    assert "layers.22.qkv" in shapes and "layers.23.router" in shapes
    assert model.attention_kind == "hybrid"
    assert model.tick_counters == ("moe_assignments", "moe_peak_load",
                                   "moe_assignments_held")
    assert len(model.pool_layers()) == 3
    assert len(jax.eval_shape(lambda: model.alloc_slot_state(192))) == 9
    assert model.expert_path(192) == ("dense", None)
    assert model.expert_path(1024)[0] == "loop"


def test_the_counts_are_the_issues_numbers(resolved):
    config = resolved[1]
    family = families.of(config)
    assert (family.delta_matrix_params(config)
            + family.delta_small_params(config)) == 33_720_512
    assert family.attention_matrix_params(config) == 27_262_976
    assert family.router_params(config) == 1_052_672
    assert family.shared_params(config) == 3_145_728
    assert 64 * family.expert_params(config) == 201_326_592
    assert family.param_count(config) == 2_929_374_400
    total = sum(math.prod(s) for s, _ in family.leaf_shapes(config).values())
    assert total == family.param_count(config)
    assert round(total / 1e9, 2) == 2.93 and round(2 * total / 1e9, 2) == 5.86
    # a slot a layer: 32 heads x 128 x 128 float32 and 3 x 8,192 bf16
    assert family.slot_state_bytes(config) == 2_097_152 + 49_152
    assert round(192 * 9 * family.slot_state_bytes(config) / 1e9, 2) == 3.71
    assert family.kv_bytes_per_token(config) == 6144
    assert round(family.experts_hit(config, 192), 2) == 62.55
    # a tick: the weights but the embedding (5.70 GB with 62.55 of the 64
    # held experts), the state once each way (7.42), 6,144 B a live token
    tick0 = family.decode_tick_bytes(config, 0)
    assert tick0 == (family.weight_bytes(config, 192)
                     + 2 * 192 * 9 * 2_146_304)
    assert round(tick0 / 1e9, 2) == 13.12
    tick = family.decode_tick_bytes(config, 207_360)
    assert tick - tick0 == 207_360 * 6144
    assert round(tick / 1e9, 2) == 14.39
    assert round(tick / 819e9 * 1e3, 1) == 17.6                 # ms a tick
    held = 2 * total + 192 * 9 * 2_146_304 + 4608 * 128 * 6144
    assert round(held / 1e9, 1) == 13.2 and round(100 * held / 16.9e9) == 78
    # the tick's kernel, one tick: 7 operations an element of the state;
    # the state once each way and the rows beside it
    work = family.gated_delta_state_update(config, {})["fwd"]
    assert work["flops"] == 9 * 192 * 32 * 7 * 128 * 128
    assert work["bytes"] == 9 * 192 * (2 * 2_097_152 + 32 * (128 * 6 + 8)
                                       + 2 * 16 * 128 * 2)
    assert round(work["bytes"] / 819e9 * 1e3, 2) == 8.92        # ms a tick
    assert work["bytes"] / 819e9 > work["flops"] / 197e12       # by memory
    # the prompt's form, one prefill program of 1,024 positions (the
    # default) and of 256
    chunked = family.gated_delta_chunked(config, {})["fwd"]
    per = 32 * (4 * 64 * 128 + 64 * 256 + 6 * 128 * 128 + 2 * 64 * 128)
    assert chunked["flops"] == 9 * 1024 * per
    assert round(9 * per / 1e6, 1) == 47.2      # MFLOP a position, 9 layers
    short = family.gated_delta_chunked(config, {"prompt_tokens": 256})
    assert short["fwd"]["flops"] * 4 == chunked["flops"]
    # near the ridge: by memory at the bfloat16 peak, by the MXU at six passes
    assert chunked["flops"] / 197e12 < chunked["bytes"] / 819e9
    assert 6 * chunked["flops"] / 197e12 > chunked["bytes"] / 819e9
    assert family.train_flops_per_token(config, 4096) > 6 * 2048 * 18992
    assert abs(family.loss0_expected(config, 0.02)
               - (math.log(18992) + 2048 * 0.02 ** 2 / 2)) < 1e-9


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row under the same value but the three
    ``reduced`` lists, each with its published value beside it; the 32-chip
    deployment and what this chip holds; every ``assumed`` item with "the
    config has no key for it" where that is so; what is left out."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items()
                  if config.get(k, "absent") != v) == sorted(config["reduced"])
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (12, 64, 18992)
    assert (config["published"]["num_hidden_layers"],
            config["published"]["num_experts"],
            config["published"]["vocab_size"]) == (48, 512, 151936)
    assert (config["router_width"], config["first_expert_held"],
            config["num_experts_per_tok"]) == (512, 0, 10)
    for word in ("32 v5e chips", "4 pipeline stages of 12 layers",
                 "8 chips sharing each layer", "experts 0-63",
                 "is another chip's to add", "the final norm and the head"):
        assert word in config["deployment"], word
    said = " ".join(config["assumed"])
    assert said.count("the config has no key for it") >= 5
    for word in ("zero-centred", "x / rms(x) * (1 + w)", "NOT zero-centred",
                 "[q | k | v | z | b | a]", "WITHOUT a bias", "eps 1e-6",
                 "1 / sqrt(128)", "norm first, then the gate",
                 "float32 whatever the activation dtype", "[q | gate]",
                 "FIRST 64", "sigmoid(u w_sg)", "hybrid_pattern",
                 "chunk of 64", "4,608 pages"):
        assert word in said, word
    left = " ".join(config["left_out"])
    assert "multi-token-prediction" in left and "auxiliary loss" in left
    check = config["check"]
    assert set(check) == {"sample_requests", "served_logit_gap_max",
                          "served_logit_gap_mean", "about"}


# event texts of the device trace's ``XLA Ops`` line, as the tick compiled
# for the described v5e prints them (cut where the operands end)
UPDATE = ("%gated_delta_state_update.9 = (f32[192,32,128]{2,1,0:T(8,128)}, f32[192,32,128,128]{3,2,1,0:T(8,128)}) "
          "custom-call(%fusion.180, %fusion.181, %slot_state_4__1_.1), custom_call_target=\"tpu_custom_call\"")
PAGED = ("%paged_attention_decode.3 = bf16[192,16,256]{2,1,0:T(8,128)(2,1)} custom-call(s32[192,24]{1,0} %p, "
         "%fusion.3), custom_call_target=\"tpu_custom_call\"")
POWER = ("%power_state_update.9 = (f32[32,8,8,128]{3,2,1,0:T(8,128)S(1)}, f32[32,8,72,128]{3,2,1,0:T(8,128)}, "
         "f32[32,8,65,128,128]{4,3,2,1,0:T(8,128)}) custom-call(%reshape.233), custom_call_target=\"tpu_custom_call\"")
SSM2 = ("%ssm_state_update.14 = (f32[192,1,64,64]{3,2,1,0:T(8,128)S(1)}, f32[192,64,64,128]{3,2,1,0:T(8,128)}) "
        "custom-call(%broadcast.176, %multiply_bitcast_fusion.6), custom_call_target=\"tpu_custom_call\"")
EXPERTS = ("%fusion.77 = bf16[64,192,1024]{2,1,0:T(8,128)(2,1)} fusion(bf16[192,2048]{1,0} %fusion.76, bf16[64,2048,1024]"
           "{2,1,0} %params__layers_3_mixer_experts_w_gate_up__.1), kind=kOutput")
CONSUMER = ("%fusion.12 = bf16[192,4096]{1,0:T(8,128)(2,1)} fusion(f32[192,32,128]{2,1,0} %gated_delta_state_update.9), "
            "kind=kLoop")
TEXTS = (UPDATE, PAGED, POWER, SSM2, EXPERTS, CONSUMER)


@pytest.mark.parametrize("metric,reads", [
    ("delta_update_share.qwen3next", {UPDATE}),
    ("delta_update_roofline.qwen3next", {UPDATE}),
    ("paged_attn_share.qwen3next", {PAGED}),
    ("expert_gemm_share.qwen3next", {EXPERTS}),
])
def test_each_share_reads_its_own_operations_and_no_others(resolved, metric, reads):
    rx = re.compile(next(m for m in resolved[3] if m["name"] == metric)["pattern"])
    assert {t for t in TEXTS if rx.search(t)} == reads
    for other in ("power_update_share.brumby", "ssm_update_share.nemotron",
                  "selective_update_share.jamba"):
        spec = json.load(open(os.path.join(
            ROOT, "benchmarks", "layer_metrics", other + ".json")))
        assert not re.search(spec["pattern"], UPDATE)


def test_the_kernel_is_named_in_the_programs_table(resolved):
    from paddle_tpu.ops.pallas import KERNEL_NAMES
    assert "gated_delta_state_update" in KERNEL_NAMES
    rx = re.compile(json.load(open(os.path.join(
        ROOT, "benchmarks", "layer_metrics",
        "delta_update_share.qwen3next.json")))["pattern"])
    assert len([k for k in KERNEL_NAMES if rx.search(f"%{k}.3 = ")]) == 1
    roof = next(m for m in resolved[3]
                if m["name"] == "delta_update_roofline.qwen3next")
    assert (roof["work"], roof["counts"], roof["module"]) == (
        "kernel", "gated_delta_state_update", "^jit_run\\(")
    assert callable(getattr(families.of(resolved[1]), roof["counts"]))


def _tiny(tmp_path, **more):
    """The committed cell's files with the model, the engine and the traffic
    shrunk (same kinds, same keys): (benchmark file, configuration, mix)."""
    from benchmarks import traffic
    from benchmarks.refs import qwen3_next as ref
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_hidden_layers=4, linear_num_key_heads=2,
               linear_num_value_heads=4, linear_key_head_dim=16,
               linear_value_head_dim=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, moe_intermediate_size=32,
               shared_expert_intermediate_size=32, num_experts=8,
               router_width=16, num_experts_per_tok=3, vocab_size=512,
               dtype="float32",
               engine=dict(max_batch=8, max_len=96, page_size=16,
                           num_pages=48),
               check=dict(cfg["check"], served_logit_gap_max=2e-3,
                          served_logit_gap_mean=1e-4))
    cfg.update(more)
    cfg["hybrid_pattern"] = ref.pattern(cfg)
    cfg["program"]["config_fields"]["delta_chunk_size"] = 16
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    next(c for c in bench["configs"]
         if c["name"] == cfg["name"])["file"] = str(tmp_path / "tiny.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = dict(traffic.load("long-generation"), clients=8, requests=64,
               stratify_block=8,
               prompt_len={"dist": "loguniform", "lo": 8, "hi": 48},
               output_len={"dist": "loguniform", "lo": 8, "hi": 32})
    return str(tmp_path / "BENCHMARK.json"), cfg, mix


def test_the_reference_agrees_with_the_program_at_a_small_size(tmp_path):
    """Four layers (eight blocks ``dededeae``: one published period): the
    program's whole-sequence forward (chunks of 16, XLA's flash twin)
    against the family's reference (token by token, a full softmax), in
    float32 on the family's seeded weights, to a few units of float32
    rounding on logits of size ~1; and the reference's loss and gradient are
    the ones ``jax.grad`` takes of its own logits."""
    _, cfg, _ = _tiny(tmp_path)
    assert cfg["hybrid_pattern"] == "dededeae"
    family = families.of(cfg)
    model, names = program.build_model(cfg)
    program.install(model, names, weights.make_all(5, cfg))
    ids = np.random.default_rng(0).integers(0, 512, (2, 40), dtype=np.int32)
    got = np.asarray(model.eval()(jnp.asarray(ids)))
    get = lambda ns: weights.make_some(5, cfg, ns)
    rows, cols = np.repeat(np.arange(2), 40), np.tile(np.arange(40), 2)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(family.logits_at(
            cfg, get, [(jnp.asarray(ids), rows, cols)])[0]).reshape(2, 40, 512)
        leaves = weights.make_some(5, cfg, list(family.leaf_shapes(cfg)))
        labels = jnp.asarray(np.roll(ids, -1, 1))
        loss, grads = family.loss_and_grads(cfg, leaves, jnp.asarray(ids),
                                            labels, rows_per_block=1)
    assert np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < 3e-5
    logp = jax.nn.log_softmax(jnp.asarray(want), -1)
    nll = -np.mean(np.take_along_axis(np.asarray(logp),
                                      np.asarray(labels)[..., None], -1))
    assert abs(float(loss) - nll) < 1e-5
    assert abs(nll - family.loss0_expected(cfg, weights.INIT_STD)) < 0.1
    assert set(grads) == set(leaves)
    norms = {k: float(jnp.linalg.norm(v)) for k, v in grads.items()}
    assert all(np.isfinite(v) and v > 0 for v in norms.values()), norms


def test_the_cell_runs_end_to_end_at_a_tiny_size(tmp_path, monkeypatch):
    """Through ``run.run_cell`` with the trace on: ``correct`` against the
    reference, and every metric a run without a chip can read (the device's
    own need the device trace)."""
    from benchmarks import traffic
    bench, _, mix = _tiny(tmp_path)
    monkeypatch.setattr(traffic, "load", lambda name: mix)
    out = run.run_cell(CELL, 2**31 + 46, 3.0, True, require_chip=False,
                       benchmark_file=bench)
    assert out["correct"] is True and out["failed"] == 0
    got = set(out["metrics"])
    assert {n + ".qwen3next" for n in (
        "batch_occupancy_pct", "decode_ticks_s", "itl_p50_ms",
        "tick_stream_ms", "prefill_stream_share",
        "stream_unattributed_share", "expert_held_share",
        "expert_peak_load")} <= got
    assert out["metrics"]["batch_occupancy_pct.qwen3next"]["value"] > 90.0
    # 8 of the router's 16 outputs are held: half of the choices
    assert 35.0 < out["metrics"]["expert_held_share.qwen3next"]["value"] < 65.0
