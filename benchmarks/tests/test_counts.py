"""The byte and FLOP functions against counts worked by hand for the three
configurations, so that no share can read over 100% by a miscount. Each is
reached the way the harness reaches it: through the configuration's family."""

import json
import os

import pytest

from benchmarks import families, reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def cfg(name, **over):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return dict(json.load(f), **over)


of = families.of
MISTRAL = "mistral-7b-v0.3.serve-1chip"
OLMOE = "olmoe-1b-7b.train-1chip"


def test_mistral_serving_counts():
    m = cfg(MISTRAL)
    # one layer: qkv 4096 x 6144 + o 4096 x 4096 + SwiGLU 3 x 4096 x 14336
    assert of(m).layer_matmul_params(m) == 25_165_824 + 16_777_216 + 176_160_768
    # embedding + head + final norm + 16 x (layer + two norms)
    assert of(m).param_count(m) == 2 * 134_217_728 + 4096 + 16 * (218_103_808 + 8192)
    assert of(m).param_count(m) == 3_758_231_552
    # K and V, 16 layers, 8 heads of 128, bf16
    assert of(m).kv_bytes_per_token(m) == 2 * 16 * 8 * 128 * 2 == 65_536
    # bf16 matrices of 16 layers and the head; norms are float32
    want = (16 * 218_103_808 + 134_217_728) * 2 + 16 * 2 * 4096 * 4
    assert of(m).weight_bytes(m) == want == 7_248_281_600
    assert of(m).decode_tick_bytes(m, 20_000) == want + 20_000 * 65_536


def test_mistral_training_flops_depth_8():
    m = cfg(MISTRAL, num_hidden_layers=8)
    # 6 x (8 layers + head) + causal attention 8 x 6 x 32 x 128 x 4097
    assert of(m).train_flops_per_token(m, 4096) == (
        6 * (8 * 218_103_808 + 134_217_728) + 8 * 6 * 4096 * 4097)
    assert of(m).train_flops_per_token(m, 4096) == 12_079_792_128


def test_olmoe_counts_only_the_routed_experts():
    m = cfg(OLMOE)
    attn, router, expert = 12_582_912 + 4_194_304, 2048 * 64, 3 * 2048 * 1024
    assert of(m).layer_matmul_params(m) == attn + router + 8 * expert
    assert of(m).layer_matmul_params(m, active_only=False) == attn + router + 64 * expert
    assert of(m).param_count(m) == 625_612_800
    head = 2048 * 50304
    assert of(m).train_flops_per_token(m, 4096) == (
        6 * (67_239_936 + head) + 6 * 16 * 128 * 4097) == 1_071_919_104


def test_causal_flash_counts():
    m = cfg(OLMOE)
    pairs = 8 * 16 * 4096 * 4097 // 2          # query-key pairs, diagonal in
    f = of(m).flash_flops(m, 8, 4096)
    assert f["fwd"] == 2 * 2 * 128 * pairs == 549_890_031_616
    assert f["bwd"] == 2 * f["fwd"]
    b = of(m).flash_bytes(m, 8, 4096)
    q = 8 * 4096 * 16 * 128 * 2
    assert b == {"fwd": 4 * q, "bwd": 8 * q}
    g = of(cfg(MISTRAL)).flash_bytes(cfg(MISTRAL), 1, 4096)      # 8 KV heads of 32
    assert g["fwd"] == 2 * 4096 * 32 * 128 * 2 + 2 * 4096 * 8 * 128 * 2


def test_a_share_cannot_pass_100_when_the_device_takes_the_least_time():
    m = cfg(MISTRAL)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    least_ns = of(m).decode_tick_bytes(m, 10_000) / 819e9 * 1e9
    ev = [reduce.Event("/device:TPU:0", "XLA Modules", "jit_run(1)",
                       i * 2 * least_ns, least_ns) for i in range(5)]
    ev += [reduce.Event("/device:TPU:0", "XLA Modules", "jit_run(2)", 0, 5)]
    ctx = {"events": ev, "model": m, "shapes": {}, "peaks": peaks,
           "counters": {"live_tokens_mean": 10_000}}
    spec = {"work": "decode_tick", "module": r"^jit_run\("}
    assert reduce.REDUCERS["roofline"](ctx, spec) == pytest.approx(100.0)
    slow = [reduce.Event(e.plane, e.line, e.name, e.start_ns, e.dur_ns * 4)
            for e in ev]
    assert reduce.REDUCERS["roofline"](dict(ctx, events=slow), spec) == pytest.approx(25.0)
