"""The four ``*_share`` metrics read kernels by the names the program gives
them (``paddle_tpu.ops.pallas.KERNEL_NAMES``). Each metric's ``pattern`` is
held here to event texts as the device trace prints them: recorded ones, and
the three forms the TPU compiler gives a named Pallas call — plain, under
``jvp`` and under ``transpose(jvp)``."""

import json
import os
import re

import pytest

from benchmarks import reduce
from conftest import ROOT

DATA = os.path.join(ROOT, "benchmarks", "tests", "data")
METRICS = os.path.join(ROOT, "benchmarks", "layer_metrics")

# metric -> the kernels (by the program's name for them) it is to read
READS = {
    "expert_gemm_share.train": ["grouped_matmul"],
    "loss_head_share.train": ["fused_vocab_ce_fwd", "fused_vocab_ce_bwd_dh",
                              "fused_vocab_ce_bwd_dw"],
    "flash_attn_share.train": ["flash_attention_fwd", "flash_attention_bwd_dq",
                               "flash_attention_bwd_dkv"],
    "flash_attn_share.ttft": ["flash_attention_fwd", "flash_attention_bwd_dq",
                              "flash_attention_bwd_dkv"],
}
TAIL = (" = bf16[8,16,4096,128]{3,2,1,0:T(8,128)(2,1)} custom-call(bf16[8,16,"
        "4096,128]{3,2,1,0:T(8,128)(2,1)} %copy_bitcast_fusion.3), "
        "custom_call_target=\"tpu_custom_call\"")


def spec(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        return json.load(f)


def forms(kernel):
    """The instruction names the compiler derives from a Pallas call named
    ``kernel``: its name stack's last scope, sanitised, numbered."""
    return [f"%{kernel}.3", f"%jvp_{kernel}_.1",
            f"%transpose_jvp_{kernel}__.2", f"%{kernel}.1.remat"]


def recorded(which):
    with open(os.path.join(DATA, f"op_texts.{which}.json")) as f:
        return json.load(f)["cells"]


@pytest.mark.parametrize("metric", sorted(READS))
def test_a_share_reads_its_kernels_names_and_no_other_kernels(metric):
    from paddle_tpu.ops.pallas import KERNEL_NAMES
    s = spec(metric)
    assert s["reducer"] == "device_op_share" and s["unit"] == "%"
    rx = re.compile(s["pattern"])
    for kernel in KERNEL_NAMES:
        for text in forms(kernel):
            assert bool(rx.search(text + TAIL)) == (kernel in READS[metric]), (
                metric, text)
    # an operation that only CONSUMES a kernel's result is not the kernel
    for kernel in READS[metric]:
        assert not rx.search(f"%fusion.2 = bf16[262144,2048]{{1,0}} fusion("
                             f"f32[270336,2048]{{1,0}} %{kernel}.14, s32[8]{{0}} "
                             f"%copy-done.14), kind=kCustom")


def test_the_parents_recorded_texts_match_only_where_the_table_says():
    """Before the kernels had names: the grouped matmul's Pallas calls were
    ``%jvp__.13/.14`` and flash's ``%jvp__.11``/``%transpose_jvp___.8/.9``
    (no share can tell them apart, none reads them), the loss head's
    carried its scope's name, and XLA's ``ragged-dot`` was itself."""
    texts = recorded("parent")["olmoe.pretrain-4k"]
    hits = {m: [t.split(" = ")[0] for t in texts
                if re.search(spec(m)["pattern"], t)] for m in READS}
    assert sorted(hits["expert_gemm_share.train"]) == [
        "%ragged-dot-none", "%ragged-dot-none.1", "%ragged-dot-none.2",
        "%ragged-dot-none.3"]
    assert hits["loss_head_share.train"] == []
    assert hits["flash_attn_share.train"] == []
    serving = recorded("parent")["mistral-7b.short-answers"]
    assert not [t for t in serving
                if re.search(spec("flash_attn_share.ttft")["pattern"], t)]
    assert any(t.startswith("%jvp__.13 = ") for t in texts)
    assert any(t.startswith("%fusion.11 = ") for t in texts)


def test_the_named_kernels_recorded_texts_match_where_the_table_says():
    """After the kernels had names (PR 24's chip run): each share finds its
    kernels among the 40 longest operations of a train step, and no custom
    call is left that no share can name."""
    texts = recorded("named")["olmoe.pretrain-4k"]
    hits = {m: sorted(t.split(" = ")[0] for t in texts
                      if re.search(spec(m)["pattern"], t)) for m in READS}
    assert hits["expert_gemm_share.train"] == [
        "%jvp_grouped_matmul_.2", "%jvp_grouped_matmul_.3",
        "%ragged-dot-none", "%ragged-dot-none.1", "%ragged-dot-none.2",
        "%ragged-dot-none.3"]
    assert hits["loss_head_share.train"] == [
        "%fused_vocab_ce_bwd_dh.1", "%fused_vocab_ce_bwd_dw.1",
        "%fused_vocab_ce_fwd.1"]
    assert hits["flash_attn_share.train"] == [
        "%jvp_flash_attention_fwd_.1",
        "%transpose_jvp_flash_attention_bwd_dkv__.1",
        "%transpose_jvp_flash_attention_bwd_dq__.1"]
    named = {h for m in READS for h in hits[m]}
    calls = [t.split(" = ")[0] for t in texts if " custom-call(" in t]
    assert sorted(calls) == sorted(named)


def test_a_share_is_its_kernels_time_over_the_window():
    ev = [reduce.Event("/device:TPU:0", reduce.OPS_LINE, n, s, d) for n, s, d in (
        ("%flash_attention_fwd.3" + TAIL, 0.0, 10.0),
        ("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %flash_attention_fwd.3)", 10.0, 50.0),
        ("%transpose_jvp_flash_attention_bwd_dkv__.2" + TAIL, 60.0, 30.0),
        ("%jvp_grouped_matmul_.4" + TAIL, 90.0, 10.0))]
    got = reduce.REDUCERS["device_op_share"]({"events": ev},
                                             spec("flash_attn_share.train"))
    assert got == pytest.approx(100.0 * 40.0 / 100.0)
