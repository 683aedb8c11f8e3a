"""The family of Brumby-14B-Base (``families/brumby.py``): its leaves are the
program's parameters at the published widths (built abstractly: no weight is
made), its counts are ISSUE 41's hand arithmetic, the reference (the
QUADRATIC form) agrees with the program (a recurrence) at a small size, and
the cell resolves through a harness that did not change and, shrunk, runs
end to end through ``run.py``'s own entry on an engine with no page."""

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

CELL = "brumby-14b.context-answers"
NAME = "brumby-14b-base.serve-1chip"
CONFIG = os.path.join(ROOT, "benchmarks", "configs", NAME + ".json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
METRICS = ("batch_occupancy_pct", "decode_tick_roofline", "decode_ticks_s",
           "device_idle_pct", "host_wait_share", "itl_p50_ms", "peak_hbm_gib",
           "power_prefill_share", "power_update_roofline",
           "power_update_share", "prefill_stream_share",
           "stream_unattributed_share", "tick_stream_ms")


@pytest.fixture(scope="module")
def resolved():
    return run.resolve(CELL, os.path.join(ROOT, "BENCHMARK.json"))


def test_the_cell_resolves_with_every_harness_file_unchanged(resolved):
    cell, config, mix, metrics, e2e = resolved
    assert config["family"] == "benchmarks.families.brumby"
    assert cell["chips"] == 1 and cell["traffic"] == "context-answers"
    assert (mix["loop"], mix["clients"], mix["requests"],
            mix["stratify_block"]) == ("closed", 32, 256, 32)
    assert (mix["prompt_len"], mix["output_len"]) == (
        {"dist": "loguniform", "lo": 512, "hi": 4096},
        {"dist": "loguniform", "lo": 256, "hi": 1024})
    assert mix["clients"] == config["engine"]["max_batch"]
    assert {m["name"] for m in e2e} == {"serve_tok_s", "setup_s"}
    assert sorted(m["name"] for m in metrics) == sorted(
        n + ".brumby" for n in METRICS)
    assert all(m["moves"] == "serve_tok_s" and m["workloads"] == [CELL]
               for m in metrics)
    for path, digest in UNCHANGED.items():
        with open(os.path.join(ROOT, path), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == digest, path


def test_what_this_cell_added_to_the_benchmark_file_keeps_its_form():
    """The driver's rules of form, held against the entries this family
    brought: names of at most 64 of their characters, one-line texts of 1 to
    200 printable characters, just the keys each kind of entry has, the new
    entries LAST in their lists, eight cells, the whole file under 64 KiB."""
    path = os.path.join(ROOT, "BENCHMARK.json")
    bench = json.load(open(path))
    assert os.path.getsize(path) <= 64 * 1024
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    unit = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
    config, cell = bench["configs"][-1], bench["workloads"][-1]
    assert (config["name"], cell["name"]) == (NAME, CELL)
    assert len(bench["workloads"]) == 8 and len(bench["configs"]) == 7
    serve = next(m for m in bench["end_to_end"] if m["name"] == "serve_tok_s")
    assert serve["workloads"][-1] == CELL and serve["bound"] == 0.02
    metrics = bench["per_layer"][-len(METRICS):]
    assert sorted(m["name"] for m in metrics) == sorted(
        n + ".brumby" for n in METRICS)
    assert not any(m["name"].endswith(".brumby")
                   for m in bench["per_layer"][:-len(METRICS)])
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert config["reduced"] == ["num_hidden_layers"]
    for m in metrics:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert unit.fullmatch(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["layer"] in ("engine", "kernels", "device")
    for text in ([config["why"], config["source"], cell["why"]]
                 + [m["layer"] for m in metrics]):
        assert 1 <= len(text) <= 200 and text.isprintable(), text
    for word in ([config["name"], cell["name"], cell["config"],
                  cell["traffic"]] + [m["name"] for m in metrics]):
        assert name.fullmatch(word), word
    assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", config["file"])
    # a roofline share of the accepted benchmark is reported where its
    # end-to-end metric is: the tick's, under this cell's own name
    assert "decode_tick_roofline.brumby" in {m["name"] for m in metrics}


def test_the_prompts_reach_fourteen_prefill_programs_all_warmed(resolved):
    """No page exists; ``page_size`` 128 is the step of the prefill
    programs' widths, and 40 such steps span the table, over
    ``MAX_PREFILL_PROGRAMS``, so the engine pads a prompt to whole 256s.
    Prompts of 512-4096 tokens reach FOURTEEN programs, 768 to 4096 (ISSUE
    41 counted up to 15: the 512 program would take a prompt of exactly 512
    tokens, the distribution's edge); the harness's warm-up (one prompt a
    128 multiple) reaches every one; the longest request fits ``max_len``."""
    from benchmarks import traffic
    from paddle_tpu.inference.serving import MAX_PREFILL_PROGRAMS
    _, config, mix, _, _ = resolved
    eng = config["engine"]
    assert eng == {"max_batch": 32, "max_len": 5120, "page_size": 128}
    assert eng["max_len"] // eng["page_size"] == 40 > MAX_PREFILL_PROGRAMS
    sched = traffic.serving_schedule(mix, 2**31 + 41, 45.0,
                                     config["vocab_size"], eng["max_len"])
    lens = [len(r.prompt) for r in sched.requests]
    # the first block's outputs are cut short to stagger the clients
    outs = [r.out_len for r in sched.requests[32:]]
    assert min(lens) >= 512 and max(lens) <= 4096
    assert min(outs) >= 256 and max(outs) <= 1024
    assert 1690 < np.mean(lens) < 1760 and 540 < np.mean(outs) < 570
    assert len(set(lens[:32])) == 32            # a block is 32 lengths
    assert max(n + o for n, o in zip(lens[32:], outs)) <= eng["max_len"]
    assert {-(-n // 256) * 256 for n in lens} == set(range(768, 4097, 256))


def test_the_programs_parameters_are_the_familys_leaves_at_published_widths(resolved):
    config = resolved[1]
    family = families.of(config)
    model, names = program.build_model(config)
    shapes = family.leaf_shapes(config)
    assert sorted(names.values()) == sorted(shapes)
    assert model.cfg.kinds == config["hybrid_pattern"] == family.pattern(config)
    assert len(model.cfg.kinds) == 10 and config["num_hidden_layers"] == 5
    assert shapes["embed"][0] == (151936, 5120)
    assert shapes["head"][0] == (5120, 151936)      # untied
    assert shapes["layers.0.qkv"][0] == (5120, (40 + 2 * 8) * 128)
    assert shapes["layers.0.gate"] == ((5120, 8), "router")
    assert shapes["layers.0.q_norm"] == ((128,), "norm")
    assert shapes["layers.0.k_norm"] == ((128,), "norm")
    assert shapes["layers.0.o"][0] == (5120, 5120)
    assert shapes["layers.1.gate_up"][0] == (5120, 2 * 17408)
    assert shapes["layers.1.down"][0] == (17408, 5120)
    assert "layers.8.qkv" in shapes and "layers.9.down" in shapes
    assert model.attention_kind == "hybrid" and model.tick_counters == ()
    assert model.cfg.rope_theta == 1_000_000


def test_the_counts_are_the_issues_numbers(resolved):
    config = resolved[1]
    family = families.of(config)
    # a layer: q and o 5120 x 5120, k and v 5120 x 1024, the gate 5120 x 8,
    # two head norms, the MLP 3 x 5120 x 17,408, two norms
    assert family.retention_matrix_params(config) == (
        2 * 26_214_400 + 2 * 5_242_880)
    assert family.retention_small_params(config) == 40_960 + 256
    assert family.mlp_params(config) == 267_386_880
    assert family.layer_params(config) == 330_352_896
    assert family.param_count(config) == (
        5 * 330_352_896 + 2 * 777_912_320 + 5120)
    total = sum(math.prod(s) for s, _ in family.leaf_shapes(config).values())
    assert total == family.param_count(config)
    whole = 40 * 330_352_896 + 2 * 777_912_320 + 5120
    assert round(whole / 1e9, 2) == 14.77           # the published 14B
    assert round(2 * 330_352_896 / 1e6, 1) == 660.7     # MB a layer
    # a slot a layer: 8 KV heads x (8,256 x 128 + 8,256) float32
    assert family.state_rows(config) == 128 * 129 // 2 == 8256
    assert family.slot_state_bytes(config) == 8 * (8256 * 128 + 8256) * 4
    assert round(family.slot_state_bytes(config) / 1e6, 2) == 34.08
    assert round(32 * 5 * family.slot_state_bytes(config) / 1e9, 2) == 5.45
    # a tick: the 5 layers' weights once (3.30 GB), the head once (1.556),
    # every slot's state once each way (10.91): 15.8 GB, the state 69%
    tick = family.decode_tick_bytes(config, 0)
    state = 2 * 32 * 5 * family.slot_state_bytes(config)
    assert tick == family.weight_bytes(config) + state
    assert family.decode_tick_bytes(config, 80_000) == tick     # no live term
    assert round(tick / 1e9, 1) == 15.8 and round(state / 1e9, 2) == 10.91
    assert round(100 * state / tick) == 69
    assert round(tick / 819e9 * 1e3, 1) == 19.2                 # ms a tick
    held = (family.param_count(config) * 2
            + 32 * 5 * family.slot_state_bytes(config))
    assert round(held / 1e9, 2) == 11.87 and round(100 * held / 16e9) == 74
    # the tick's kernel, one tick: 13 operations an element of the state
    # and of the normaliser (decay, rank-1 product and sum, five readings
    # of two), phi of k and of five q; the state once each way and the
    # rows beside it
    work = family.power_state_update(config, {})["fwd"]
    assert work["flops"] == 5 * 32 * 8 * (13 * 8256 * 129 + 12 * 8256)
    assert round(work["flops"] / 1e9, 1) == 17.8
    assert work["bytes"] == 5 * 32 * (2 * 34_080_768 + 8 * (
        7 * 128 * 2 + 4 + 4 * 5 * 128))
    assert round(work["bytes"] / 819e9 * 1e3, 2) == 13.32       # ms a tick
    assert work["bytes"] / 819e9 > work["flops"] / 197e12       # by memory
    # the prompt's kernel, one prefill program of 4,096 positions (the
    # default) and of 768: 12 x 8,256 x 129 for the readings and the
    # state's build a KV head a position, phi, and the chunk's inside
    chunked = family.power_retention_chunked(config, {})["fwd"]
    per = 8 * (12 * 8256 * 129 + 12 * 8256 + 5 * 515 * 128)
    assert chunked["flops"] == 5 * 4096 * per
    assert round(per / 1e6) == 106              # MFLOP a position a layer
    assert chunked["bytes"] == 5 * (4096 * 8 * (7 * 128 * 2 + 4 + 2560)
                                    + 34_080_768)
    short = family.power_retention_chunked(config, {"prompt_tokens": 768})
    assert short["fwd"]["flops"] * 4096 == chunked["flops"] * 768
    assert chunked["flops"] / 197e12 > chunked["bytes"] / 819e9  # by the MXU
    assert round(chunked["flops"] / 197e12 * 1e3, 1) == 11.0    # ms a prompt
    assert family.train_flops_per_token(config, 4096) > 6 * 5120 * 151936
    assert abs(family.loss0_expected(config, 0.02)
               - (math.log(151936) + 5120 * 0.02 ** 2 / 2)) < 1e-9


def test_the_configuration_keeps_every_published_number():
    """Every key of the catalog's row under the same value but the depth,
    which ``reduced`` lists with the published 40 stated beside it; the
    8-stage deployment and what this chip holds; every ``assumed`` item of
    ISSUE 41, each with "the config has no key for it" where that is so."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Brumby-14B-Base")
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if config.get(k) != v] == [
        "num_hidden_layers"] == config["reduced"]
    assert config["num_hidden_layers"] == 5
    assert config["published"]["num_hidden_layers"] == 40 == row["layers"]
    assert (config["vocab_size"], config["hidden_size"], config["head_dim"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["intermediate_size"]) == (151936, 5120, 128, 40, 8, 17408)
    for word in ("8-STAGE PIPELINE", "chips that share a layer: 1",
                 "BOTH the embedding table and the head",
                 "a real stage holds at most one", "NO PAGE", "74% of the chip"):
        assert word in config["deployment"], word
    said = " ".join(config["assumed"])
    assert said.count("the config has no key for it") >= 4
    for word in ("degree p = 2", "one number a KV head a token",
                 "sum of its weights with eps 1e-6", "per-head RMSNorm",
                 "the norm FIRST", "1 / sqrt(128) inside the square",
                 "float32 whatever the activation dtype",
                 "buffers a chunk of K and V", "NOT built here",
                 "hybrid_pattern", "kind 'router'",
                 "cannot see a fault in what is carried between chunks",
                 "max_batch 32"):
        assert word in said, word
    check = config["check"]
    assert set(check) == {"sample_requests", "served_logit_gap_max",
                          "served_logit_gap_mean", "about"}


# event texts of the device trace's ``XLA Ops`` line, as the tick and the
# widest prefill compiled for the described v5e print them (cut where the
# operands end)
UPDATE = ("%power_state_update.9 = (f32[32,8,8,128]{3,2,1,0:T(8,128)S(1)}, f32[32,8,72,128]{3,2,1,0:T(8,128)}, "
          "f32[32,8,65,128,128]{4,3,2,1,0:T(8,128)}) custom-call(%reshape.233, %fusion.180, %slot_state_4__1_.1, "
          "%slot_state_4__0_.1), custom_call_target=\"tpu_custom_call\"")
CHUNKED = ("%power_retention_chunked.4 = (f32[1,8,4096,640]{3,2,1,0:T(8,128)}, f32[1,8,65,128,128]{4,3,2,1,0:T(8,128)}, "
           "f32[1,8,65,8,128]{4,3,2,1,0:T(8,128)}) custom-call(%reshape.85, %copy.31, %fusion.71, %fusion.70, "
           "%bitcast.209, %bitcast.208), custom_call_target=\"tpu_custom_call\"")
SELECTIVE = ("%selective_state_update.27 = (f32[256,5120]{1,0:T(8,128)S(1)}, f32[256,16,5120]{2,1,0:T(8,128)}) "
             "custom-call(%fusion.541), custom_call_target=\"tpu_custom_call\"")
SSM2 = ("%ssm_state_update.14 = (f32[192,1,64,64]{3,2,1,0:T(8,128)S(1)}, f32[192,64,64,128]{3,2,1,0:T(8,128)}) "
        "custom-call(%broadcast.176, %multiply_bitcast_fusion.6), custom_call_target=\"tpu_custom_call\"")
CONSUMER = ("%fusion.12 = bf16[32,5120]{1,0:T(8,128)(2,1)} fusion(f32[32,8,8,128]{3,2,1,0} %power_state_update.9, "
            "f32[1,8,4096,640]{3,2,1,0} %power_retention_chunked.4), kind=kLoop")
TEXTS = (UPDATE, CHUNKED, SELECTIVE, SSM2, CONSUMER)


@pytest.mark.parametrize("metric,reads", [
    ("power_update_share.brumby", {UPDATE}),
    ("power_update_roofline.brumby", {UPDATE}),
    ("power_prefill_share.brumby", {CHUNKED}),
])
def test_each_share_reads_its_own_operations_and_no_others(resolved, metric, reads):
    rx = re.compile(next(m for m in resolved[3] if m["name"] == metric)["pattern"])
    assert {t for t in TEXTS if rx.search(t)} == reads
    for other in ("selective_update_share.jamba", "ssm_update_share.nemotron"):
        spec = json.load(open(os.path.join(
            ROOT, "benchmarks", "layer_metrics", other + ".json")))
        assert not any(re.search(spec["pattern"], t)
                       for t in (UPDATE, CHUNKED))


def test_the_two_kernels_are_named_in_the_programs_table(resolved):
    from paddle_tpu.ops.pallas import KERNEL_NAMES
    assert {"power_state_update", "power_retention_chunked"} <= set(KERNEL_NAMES)
    for spec in ("power_update_share", "power_prefill_share"):
        rx = re.compile(json.load(open(os.path.join(
            ROOT, "benchmarks", "layer_metrics", spec + ".brumby.json")))["pattern"])
        assert len([k for k in KERNEL_NAMES if rx.search(f"%{k}.3 = ")]) == 1
    roof = next(m for m in resolved[3]
                if m["name"] == "power_update_roofline.brumby")
    assert (roof["work"], roof["counts"], roof["module"]) == (
        "kernel", "power_state_update", "^jit_run\\(")
    assert callable(getattr(families.of(resolved[1]), roof["counts"]))


def _tiny(tmp_path, **more):
    """The committed cell's files with the model, the engine and the traffic
    shrunk (same kinds, same keys): (benchmark file, configuration, mix)."""
    from benchmarks import traffic
    from benchmarks.refs import brumby as ref
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_hidden_layers=3, num_attention_heads=10,
               num_key_value_heads=2, head_dim=16, intermediate_size=96,
               vocab_size=512, dtype="float32",
               engine=dict(max_batch=8, max_len=96, page_size=16),
               check=dict(cfg["check"], served_logit_gap_max=2e-3,
                          served_logit_gap_mean=1e-4))
    cfg.update(more)
    cfg["hybrid_pattern"] = ref.pattern(cfg)
    cfg["program"]["config_fields"]["chunk_size"] = 16
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    next(c for c in bench["configs"]
         if c["name"] == cfg["name"])["file"] = str(tmp_path / "tiny.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = dict(traffic.load("context-answers"), clients=8, requests=64,
               stratify_block=8,
               prompt_len={"dist": "loguniform", "lo": 8, "hi": 48},
               output_len={"dist": "loguniform", "lo": 8, "hi": 32})
    return str(tmp_path / "BENCHMARK.json"), cfg, mix


def test_the_reference_agrees_with_the_program_at_a_small_size(tmp_path):
    """Three layers (six blocks ``p-p-p-``): the program's whole-sequence
    forward (chunks of 16 over a state of 9 tiles a head) against the
    family's reference (the quadratic form), in float32 on the family's
    seeded weights, to a few units of float32 rounding on logits of size
    ~1; and the reference's loss and gradient are the ones ``jax.grad``
    takes of its own logits."""
    _, cfg, _ = _tiny(tmp_path)
    assert cfg["hybrid_pattern"] == "p-p-p-"
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
    """Through ``run.run_cell`` with the trace on, on an engine that holds
    no page: ``correct`` against the reference, and every metric a run
    without a chip can read (the device's own need the device trace)."""
    from benchmarks import traffic
    bench, _, mix = _tiny(tmp_path)
    monkeypatch.setattr(traffic, "load", lambda name: mix)
    out = run.run_cell(CELL, 2**31 + 41, 3.0, True, require_chip=False,
                       benchmark_file=bench)
    assert out["correct"] is True and out["failed"] == 0
    got = set(out["metrics"])
    assert {n + ".brumby" for n in (
        "batch_occupancy_pct", "decode_ticks_s", "itl_p50_ms",
        "tick_stream_ms", "prefill_stream_share",
        "stream_unattributed_share")} <= got
    assert out["metrics"]["batch_occupancy_pct.brumby"]["value"] > 90.0
