"""The family of AI21-Jamba2-3B (``families/jamba.py``): its leaves are the
program's parameters at the published widths AND depth (built abstractly: no
weight is made), its counts are ISSUE 38's hand arithmetic, the reference
agrees with the program at a small size, and the cell resolves through a
harness that did not change and, shrunk, runs end to end through ``run.py``'s
own entry."""

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

CELL = "jamba2-3b.batch-reasoning"
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "ai21-jamba2-3b.serve-1chip.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = ("batch_occupancy_pct", "decode_tick_roofline", "decode_ticks_s",
           "device_idle_pct", "host_wait_share", "itl_p50_ms",
           "paged_attn_share", "peak_hbm_gib", "prefill_stream_share",
           "selective_scan_share", "selective_update_roofline",
           "selective_update_share", "stream_unattributed_share",
           "tick_stream_ms")


@pytest.fixture(scope="module")
def resolved():
    return run.resolve(CELL, os.path.join(ROOT, "BENCHMARK.json"))


def test_the_cell_resolves_with_every_harness_file_unchanged(resolved):
    cell, config, mix, metrics, e2e = resolved
    assert config["family"] == "benchmarks.families.jamba"
    assert cell["chips"] == 1 and cell["traffic"] == "batch-reasoning"
    assert (mix["loop"], mix["clients"], mix["requests"],
            mix["stratify_block"]) == ("closed", 256, 2048, 256)
    assert (mix["prompt_len"], mix["output_len"]) == (
        {"dist": "loguniform", "lo": 128, "hi": 1024},
        {"dist": "loguniform", "lo": 512, "hi": 2048})
    assert mix["clients"] == config["engine"]["max_batch"]
    assert {m["name"] for m in e2e} == {"serve_tok_s", "setup_s"}
    assert sorted(m["name"] for m in metrics) == sorted(
        n + ".jamba" for n in METRICS)
    assert all(m["moves"] == "serve_tok_s" and m["workloads"] == [CELL]
               for m in metrics)
    for path, digest in UNCHANGED.items():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, path


def test_what_this_cell_added_to_the_benchmark_file_keeps_its_form():
    """The driver's rules of form, held against the entries this family
    brought: a name of at most 64 of its characters, a one-line ``why``,
    ``layer`` and ``source`` of 1 to 200 printable characters (a
    configuration's ``why`` too, which ``test_harness`` does not hold), just
    the keys each kind of entry has, the whole file under 64 KiB."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    bench = json.load(open(path))
    assert os.path.getsize(path) <= 64 * 1024
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    unit = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
    config = next(c for c in bench["configs"]
                  if c["name"] == "ai21-jamba2-3b.serve-1chip")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    metrics = [m for m in bench["per_layer"] if m["name"].endswith(".jamba")]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert config["reduced"] == [] and len(metrics) == len(METRICS)
    for m in metrics:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert unit.fullmatch(m["unit"]) and m["better"] in ("lower", "higher")
    for text in ([config["why"], config["source"], cell["why"]]
                 + [m["layer"] for m in metrics]):
        assert 1 <= len(text) <= 200 and text.isprintable(), text
    for word in ([config["name"], cell["name"], cell["config"],
                  cell["traffic"]] + [m["name"] for m in metrics]):
        assert name.fullmatch(word), word
    assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", config["file"])


def test_the_prompts_reach_seven_prefill_programs_all_warmed(resolved):
    """The table spans 24 pages, so the engine pads to whole pages (one
    program a page multiple, under ``MAX_PREFILL_PROGRAMS``) and the
    harness's warm-up (one prompt a page multiple) reaches every one.
    Prompts of 128-1024 tokens reach SEVEN of them, 256 to 1024 (ISSUE 38
    counted 8: the program of one page would take a prompt of exactly 128
    tokens, the distribution's edge, and the mid-points of its slices all
    lie above it); the longest request fits its table."""
    from benchmarks import traffic
    from paddle_tpu.inference.serving import MAX_PREFILL_PROGRAMS
    _, config, mix, _, _ = resolved
    eng = config["engine"]
    assert eng == {"max_batch": 256, "max_len": 3072, "page_size": 128,
                   "num_pages": 6144}
    assert eng["max_len"] // eng["page_size"] == 24 <= MAX_PREFILL_PROGRAMS
    assert eng["num_pages"] == 256 * 24
    sched = traffic.serving_schedule(mix, 2**31 + 38, 45.0,
                                     config["vocab_size"], eng["max_len"])
    lens = [len(r.prompt) for r in sched.requests]
    # the first block's outputs are cut short to stagger the clients
    outs = [r.out_len for r in sched.requests[256:]]
    assert min(lens) >= 128 and max(lens) <= 1024
    assert min(outs) >= 512 and max(outs) <= 2048
    assert 410 < np.mean(lens) < 450 and 1080 < np.mean(outs) < 1140
    assert len(set(lens[:256])) > 200       # a block is 256 lengths
    assert max(n + o for n, o in zip(lens[256:], outs)) <= eng["max_len"]
    assert {-(-n // 128) * 128 for n in lens} == set(range(256, 1025, 128))


def test_the_programs_parameters_are_the_familys_leaves_at_published_widths(resolved):
    config = resolved[1]
    family = families.of(config)
    model, names = program.build_model(config)
    shapes = family.leaf_shapes(config)
    assert sorted(names.values()) == sorted(shapes)
    assert "head" not in shapes            # tied: the table is the head
    assert model.cfg.kinds == config["hybrid_pattern"] == family.pattern(config)
    assert len(model.cfg.kinds) == 56 and config["num_hidden_layers"] == 28
    assert [i for i, k in enumerate(family.kinds(config)) if k == "*"] == [7, 21]
    assert shapes["embed"][0] == (65536, 2560)
    assert shapes["layers.0.in_proj"][0] == (2560, 2 * 5120)
    assert shapes["layers.0.conv"] == ((4, 5120), "norm")
    assert shapes["layers.0.conv_bias"] == ((5120,), "router")
    assert shapes["layers.0.x_proj"][0] == (5120, 160 + 16 + 16)
    assert [shapes[f"layers.0.{n}_norm"] for n in ("dt", "b", "c")] == [
        ((160,), "norm"), ((16,), "norm"), ((16,), "norm")]
    assert shapes["layers.0.dt_proj"][0] == (160, 5120)
    assert shapes["layers.0.dt_bias"] == ((5120,), "router")
    assert shapes["layers.0.A_log"] == ((16, 5120), "router")
    assert shapes["layers.0.D"] == ((5120,), "norm")
    assert shapes["layers.0.out_proj"][0] == (5120, 2560)
    assert shapes["layers.1.gate_up"][0] == (2560, 2 * 8192)
    assert shapes["layers.1.down"][0] == (8192, 2560)
    assert shapes["layers.14.qkv"][0] == (2560, (20 + 2 * 1) * 128)
    assert shapes["layers.14.o"][0] == (2560, 2560)
    assert "layers.15.gate_up" in shapes and "layers.42.qkv" in shapes
    assert model.attention_kind == "hybrid" and model.tick_counters == ()


def test_the_counts_are_the_issues_numbers(resolved):
    config = resolved[1]
    family = families.of(config)
    # a Mamba layer: in_proj 2560 x 10240, conv 5120 x 4 + bias, x_proj 5120 x
    # 192, dt_proj 160 x 5120 + bias, A_log 5120 x 16, D, out_proj 5120 x
    # 2560, three inner norms
    assert family.mamba_matrix_params(config) == (
        26_214_400 + 983_040 + 819_200 + 13_107_200)
    assert family.mamba_small_params(config) == (
        25_600 + 5_120 + 81_920 + 5_120 + 192)
    assert (family.mamba_matrix_params(config)
            + family.mamba_small_params(config)) == 41_241_792
    assert family.mlp_params(config) == 62_914_560
    assert family.attention_params(config) == 2 * 6_553_600 + 2 * 327_680
    assert family.layer_params(config, "m") == 104_161_472
    assert family.layer_params(config, "*") == 76_682_240
    assert family.param_count(config) == 3_029_337_472
    total = sum(math.prod(s) for s, _ in family.leaf_shapes(config).values())
    assert total == family.param_count(config)
    assert round(family.weight_bytes(config) / 1e9, 2) == 6.07
    # K and V of ONE head of 128 in bf16 in the 2 attention layers
    assert family.kv_bytes_per_token(config) == 2 * 2 * 1 * 128 * 2 == 1024
    # a slot a layer: [16, 5120] float32 and [3, 5120] bf16
    assert family.slot_state_bytes(config) == 327_680 + 30_720 == 358_400
    assert 26 * family.slot_state_bytes(config) == 9_318_400
    assert 256 * 26 * family.slot_state_bytes(config) == 2_385_510_400
    state = 2 * 256 * 26 * family.slot_state_bytes(config)
    assert family.decode_tick_bytes(config, 0) == (
        family.weight_bytes(config) + state)
    assert (family.decode_tick_bytes(config, 300_000)
            - family.decode_tick_bytes(config, 0)) == 300_000 * 1024
    assert round(family.decode_tick_bytes(config, 256_000) / 1e9, 1) == 11.1
    # the tick's kernel, one tick: 7 I N operations a slot a layer; the
    # float32 state once each way, x in bf16 and the step and y in float32,
    # B and C beside it a slot; A once a layer
    work = family.selective_state_update(config, {})["fwd"]
    assert work["flops"] == 26 * 256 * 7 * 5120 * 16 == 3_816_816_640
    assert work["bytes"] == 26 * (256 * (2 * 327_680 + 10 * 5120 + 128)
                                  + 327_680)
    assert round(work["bytes"] / 819e9 * 1e3, 2) == 5.75      # ms a tick
    assert work["bytes"] / 819e9 > work["flops"] / 197e12     # by memory
    # the prompt's kernel, one prefill program of 1,024 positions (the
    # default) and of one page
    scan = family.selective_scan(config, {})["fwd"]
    assert scan["flops"] == 26 * 1024 * 7 * 5120 * 16
    assert scan["bytes"] == 26 * (1024 * (10 * 5120 + 128) + 2 * 327_680)
    page = family.selective_scan(config, {"prompt_tokens": 128})["fwd"]
    assert page["flops"] * 8 == scan["flops"]
    assert page["bytes"] == 26 * (128 * (10 * 5120 + 128) + 2 * 327_680)
    assert round(scan["bytes"] / 819e9 * 1e3, 2) == 1.69      # ms a prompt
    assert family.train_flops_per_token(config, 4096) > 6 * 2560 * 65536
    assert abs(family.loss0_expected(config, 0.02)
               - (math.log(65536) + 2560 * 0.02 ** 2 / 2)) < 1e-9


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row under the same value and ``reduced``
    empty: nothing is cut; what the config does not settle is under
    ``assumed``."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if config.get(k) != v] == []
    assert config["reduced"] == [] and "nothing is changed" in \
        config["published"]["about"]
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["hidden_size"]) == (28, 65536, 2560)
    assert "WHOLE" in config["deployment"] and "ONE v5e chip" in \
        config["deployment"]
    said = " ".join(config["assumed"])
    for word in ("attn_layer_period", "13 : 1", "7:1", "hybrid_pattern",
                 "head_dim", "NO ROTARY", "x FIRST", "dt_layernorm",
                 "NO clamp", "float32 [16, 5120]", "A_log is stored",
                 "tie_word_embeddings", "max_batch 256"):
        assert word in said, word
    check = config["check"]
    assert set(check) == {"sample_requests", "served_logit_gap_max",
                          "served_logit_gap_mean", "about"}


# event texts of the device trace's ``XLA Ops`` line, as the tick and the
# widest prefill compiled for the described v5e print them (cut where the
# operands end)
UPDATE = ("%selective_state_update.27 = (f32[256,5120]{1,0:T(8,128)S(1)}, f32[256,16,5120]{2,1,0:T(8,128)}) "
          "custom-call(%fusion.541, %fusion.540, %multiply_fusion.26, %bitcast.1512, %bitcast.1511, %slot_state_3__1_.1), "
          "custom_call_target=\"tpu_custom_call\"")
SCAN = ("%selective_scan.3 = (f32[1,1024,5120]{2,1,0:T(8,128)}, f32[1,16,5120]{2,1,0:T(8,128)}) "
        "custom-call(%fusion.88, %fusion.301, %fusion.300, %bitcast.944, %bitcast.943), "
        "custom_call_target=\"tpu_custom_call\"")
SSM2 = ("%ssm_state_update.14 = (f32[192,1,64,64]{3,2,1,0:T(8,128)S(1)}, f32[192,64,64,128]{3,2,1,0:T(8,128)}) "
        "custom-call(%broadcast.176, %multiply_bitcast_fusion.6), custom_call_target=\"tpu_custom_call\"")
PAGED = ("%paged_attention_decode.4 = bf16[256,1,20,128]{3,2,1,0:T(8,128)(2,1)S(1)} custom-call(%copy-done.84, "
         "%copy-done.186, %bitcast.590, %bitcast.50, %bitcast.54), custom_call_target=\"tpu_custom_call\"")
CONSUMER = ("%fusion.12 = bf16[256,5120]{1,0:T(8,128)(2,1)} fusion(f32[256,5120]{1,0} %selective_state_update.27, "
            "f32[1,1024,5120]{2,1,0} %selective_scan.3), kind=kLoop")
TEXTS = (UPDATE, SCAN, SSM2, PAGED, CONSUMER)


@pytest.mark.parametrize("metric,reads", [
    ("selective_update_share.jamba", {UPDATE}),
    ("selective_update_roofline.jamba", {UPDATE}),
    ("selective_scan_share.jamba", {SCAN}),
    ("paged_attn_share.jamba", {PAGED}),
])
def test_each_share_reads_its_own_operations_and_no_others(resolved, metric, reads):
    rx = re.compile(next(m for m in resolved[3] if m["name"] == metric)["pattern"])
    assert {t for t in TEXTS if rx.search(t)} == reads
    nemotron = json.load(open(os.path.join(
        ROOT, "benchmarks", "layer_metrics", "ssm_update_share.nemotron.json")))
    assert {t for t in TEXTS if re.search(nemotron["pattern"], t)} == {SSM2}


def test_the_two_kernels_are_named_in_the_programs_table():
    from paddle_tpu.ops.pallas import KERNEL_NAMES
    assert {"selective_state_update", "selective_scan"} <= set(KERNEL_NAMES)
    for spec in ("selective_update_share", "selective_scan_share"):
        rx = re.compile(json.load(open(os.path.join(
            ROOT, "benchmarks", "layer_metrics", spec + ".jamba.json")))["pattern"])
        assert len([k for k in KERNEL_NAMES if rx.search(f"%{k}.3 = ")]) == 1


def _tiny(tmp_path, **more):
    """The committed cell's files with the model, the engine and the traffic
    shrunk (same kinds, same keys): (benchmark file, configuration, mix)."""
    from benchmarks import traffic
    from benchmarks.refs import jamba as ref
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_hidden_layers=6, attn_layer_period=3,
               attn_layer_offset=1, mamba_dt_rank=8, num_attention_heads=20,
               head_dim=16, intermediate_size=96, vocab_size=512,
               dtype="float32",
               engine=dict(max_batch=8, max_len=96, page_size=16,
                           num_pages=40),
               check=dict(cfg["check"], served_logit_gap_max=1e-3,
                          served_logit_gap_mean=1e-4))
    cfg.update(more)
    cfg["hybrid_pattern"] = ref.pattern(cfg)
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    next(c for c in bench["configs"]
         if c["name"] == cfg["name"])["file"] = str(tmp_path / "tiny.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = dict(traffic.load("batch-reasoning"), clients=8, requests=64,
               stratify_block=8,
               prompt_len={"dist": "loguniform", "lo": 8, "hi": 48},
               output_len={"dist": "loguniform", "lo": 8, "hi": 32})
    return str(tmp_path / "BENCHMARK.json"), cfg, mix


def test_the_reference_agrees_with_the_program_at_a_small_size(tmp_path):
    """Six layers ``m- *- m- m- *- m-`` (12 blocks): the program's
    whole-sequence forward (the scan's twin on a state [N, I]) against the
    family's reference (the recurrence token by token on [I, N]), in
    float32 on the family's seeded weights, to a few units of float32
    rounding on logits of size ~1; and the reference's loss and gradient
    are the ones ``jax.grad`` takes of its own logits."""
    _, cfg, _ = _tiny(tmp_path)
    assert cfg["hybrid_pattern"] == "m-*-m-m-*-m-"
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
    out = run.run_cell(CELL, 2**31 + 38, 3.0, True, require_chip=False,
                       benchmark_file=bench)
    assert out["correct"] is True and out["failed"] == 0
    got = set(out["metrics"])
    assert {n + ".jamba" for n in (
        "batch_occupancy_pct", "decode_ticks_s", "itl_p50_ms",
        "tick_stream_ms", "prefill_stream_share",
        "stream_unattributed_share")} <= got
    assert out["metrics"]["batch_occupancy_pct.jamba"]["value"] > 90.0
