"""Main-path kernels compile for the chip at real widths — without the chip.

The installed TPU compiler compiles for a DESCRIBED ``v5e:2x2`` topology
(``on-chip-measurement`` guide, section 2.3). Interpret-mode tests cannot see
what it refuses: a scoped-VMEM overrun (the fused vocab-CE backward asked for
19.55 MiB against a 16 MiB default at Llama-3 widths), a tile that does not
align, a Mosaic call GSPMD was asked to partition. One parametrised case per
kernel and shape of the trainer's and the serving engine's compiled programs;
nothing runs, so this says nothing about results or times.

The file name sorts first on purpose: a guard the suite's clock never reaches
guards nothing. Code that asks the backend sees the CPU here, so the mesh
cases steer it in the test (``backend_kind``, ``_device_kind``) rather than
through an option of the program.
"""

import json
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from paddle_tpu.ops.pallas import KERNEL_NAMES, autotune

BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
KIND = "TPU v5 lite"                    # what a v5e chip reports


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _grad_sum(fn, argnums):
    """fwd+bwd of ``fn``: gradient of the f32 sum of its outputs."""
    def loss(*a):
        return sum(jnp.sum(o.astype(F32)) for o in jax.tree.leaves(fn(*a)))
    return jax.grad(loss, argnums=argnums)


# -- one chip ----------------------------------------------------------------
# each case: () -> (fn, [(shape, dtype), ...])

def _flash(s, bq, bk, causal=True, d=128, h=32, h_kv=8, seg=False,
           grad=True, **kw):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas

    def fn(q, k, v, *ids):
        return flash_attention_pallas(
            q, k, v, causal=causal, block_q=bq, block_k=bk,
            segment_ids=ids[0] if ids else None, **kw)
    args = [((1, s, h, d), BF16), ((1, s, h_kv, d), BF16),
            ((1, s, h_kv, d), BF16)] + ([((1, s), I32)] if seg else [])
    return (_grad_sum(fn, (0, 1, 2)) if grad else fn), args


def _flash_rule(s, d=128, **kw):
    """A call at the blocks the rule gives its length (what a cell whose
    shape the tune DB does not hold runs on the chip)."""
    return _flash(s, *autotune._default_blocks(s, s, d), d=d, **kw)


def _tuned_flash_cases():
    """Every flash-attention entry of the shipped tune DB, at its blocks."""
    with open(autotune._SHIPPED) as f:
        db = json.load(f)
    for key, cfg in sorted(db.items()):
        if not key.startswith("flash_attention|"):
            continue
        dims = dict(kv.split("=") for kv in key.split("|")[3].split(","))
        yield pytest.param(
            lambda dims=dims, cfg=cfg: _flash(
                int(dims["sq"]), cfg["block_q"], cfg["block_k"],
                causal=bool(int(dims["causal"])), d=int(dims["d"]),
                h=32 if dims["d"] == "128" else 16,
                h_kv=8 if dims["d"] == "128" else 16),
            id="flash_tuned[%s]" % key.split("|")[3])


def _paged(page, dtype, rows=8, h=32, h_kv=8, per_seq=None, d=128):
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention
    per_seq = per_seq or 2048 // page
    pages = rows * per_seq + 1
    quant = dtype == I8

    def fn(q, kp, vp, tables, lens, *scales):
        kw = dict(k_scales=scales[0], v_scales=scales[1]) if quant else {}
        return paged_decode_attention(q, kp, vp, tables, lens, **kw)
    pool = ((h_kv, pages, page, d), dtype)
    args = [((rows, h, d), BF16), pool, pool, ((rows, per_seq), I32),
            ((rows,), I32)] + ([((pages,), F32)] * 2 if quant else [])
    return fn, args


def _latent_decode():
    """GLM-4.7-Flash's decode tick: 64 rows of 20 absorbed query heads over
    a pool of 1,536 + 1 pages of 128 rows of 576 = 512 + 64 numbers."""
    from paddle_tpu.ops.pallas.latent_attention import latent_decode_attention

    def fn(q, pages, tables, lens):
        return latent_decode_attention(q, pages, tables, lens, 512, 1 / 16)
    return fn, [((64, 20, 576), BF16), ((1, 1537, 128, 576), BF16),
                ((64, 24), I32), ((64,), I32)]


def _ssm_update(rows=192, h=64, p=64, n=128, g=8):
    """Nemotron-3-Nano's decode tick, one Mamba-2 layer: every slot's
    64 x [64, 128] float32 state as 32 tiles [128, 2 x 64], one slot's
    2 MB a grid step."""
    from paddle_tpu.ops.pallas.ssm import ssm_state_update
    r = 128 // p
    return ssm_state_update, [((rows, h // r, n, r * p), F32),
                              ((rows, h, p), BF16),
                              ((rows, h), F32), ((h,), F32),
                              ((rows, g, n), BF16), ((rows, g, n), BF16)]


def _selective_update(rows=256, d=5120, n=16):
    """AI21-Jamba2-3B's decode tick, one Mamba-1 layer: every slot's
    [16, 5120] float32 state, 8 slots (2.6 MB) a grid step, from the raw
    step to the gated bf16 row (the gate: the second half of [x | z])."""
    from paddle_tpu.ops.pallas.selective_ssm import selective_state_update
    return selective_state_update, [((rows, n, d), F32), ((rows, d), BF16),
                                    ((rows, d), F32), ((d,), F32),
                                    ((n, d), F32), ((rows, n), F32),
                                    ((rows, n), F32), ((d,), F32),
                                    ((rows, 2 * d), BF16)]


def _window_step(rows=256, d=5120, k=4):
    """The same layer's convolution window [256, 3, 5120] bf16 stepping in
    place, 32 slots a grid step, the token's input the first half of
    [x | z]."""
    from paddle_tpu.ops.pallas.selective_ssm import conv_window_step
    return conv_window_step, [((rows, k - 1, d), BF16),
                              ((rows, 2 * d), BF16), ((k, d), F32),
                              ((d,), F32)]


def _selective_scan(length=1024, d=5120, n=16):
    """AI21-Jamba2-3B's widest prefill program, one Mamba-1 layer: 1,024
    positions in blocks of 64, the state [16, 5120] resident in VMEM."""
    from paddle_tpu.ops.pallas.selective_ssm import selective_scan
    return selective_scan, [((1, length, d), BF16), ((1, length, d), F32),
                            ((n, d), F32), ((1, length, n), F32),
                            ((1, length, n), F32)]


def _delta_update(slots=192, hk=16, hv=32, d=128):
    """Qwen3-Next's decode tick, one Gated DeltaNet layer: every slot's 32
    value heads' [128, 128] float32 state, one slot's 2 MB a grid step (in
    and out, double-buffered: 8 of the 32 MiB of VMEM asked for), two value
    heads a key head."""
    from paddle_tpu.ops.pallas.gated_delta import gated_delta_state_update
    return gated_delta_state_update, [
        ((slots, hv, d, d), F32), ((slots, hk, d), F32), ((slots, hk, d), F32),
        ((slots, hv, d), BF16), ((slots, hv), F32), ((slots, hv), F32)]


def _power_update(slots=32, hq=40, hkv=8, d=128):
    """Brumby-14B-Base's decode tick, one layer: 32 slots of 8 KV heads'
    [65, 128, 128] float32 (4.26 MB a grid step, in and out, double-buffered:
    the 64 MiB of VMEM asked for is what lets it compile) and five query
    heads a KV head."""
    from paddle_tpu.ops.pallas.power_retention import power_state_update
    return power_state_update, [
        ((slots, hkv, 65, d, d), F32), ((slots, hkv, 72, d), F32),
        ((slots, hq, d), BF16), ((slots, hkv, d), BF16),
        ((slots, hkv, d), BF16), ((slots, hkv), F32)]


def _power_chunked(length=4096, hq=40, hkv=8, d=128):
    """Brumby's widest prefill program, one layer: 4,096 positions in chunks
    of 128, a KV head's state [65, 128, 128] resident in VMEM as the
    kernel's output block."""
    from paddle_tpu.ops.pallas.power_retention import power_retention_chunked
    return power_retention_chunked, [
        ((1, length, hq, d), BF16), ((1, length, hkv, d), BF16),
        ((1, length, hkv, d), BF16), ((1, length, hkv), F32)]


def _rms_norm():
    from paddle_tpu.ops.pallas.fused_norm import rms_norm_pallas
    return (_grad_sum(lambda x, w: rms_norm_pallas(x, w, 1e-5), (0, 1)),
            [((8, 2048, 4096), BF16), ((4096,), F32)])


def _rope():
    from paddle_tpu.ops.pallas.fused_rope import fused_rope_pallas
    return (lambda q, k, c, s: fused_rope_pallas(q, k, c, s, block_s=128),
            [((8, 2048, 32, 128), BF16), ((8, 2048, 8, 128), BF16),
             ((2048, 128), F32), ((2048, 128), F32)])


def _fused_ce(n, h, v):
    """fwd+bwd with the blocks the chooser picks on a v5e (no DB entry for
    this op: the VMEM-fitting defaults) — the train step's loss head."""
    from paddle_tpu.ops.pallas.fused_vocab_ce import (fused_ce_supported,
                                                      lse_and_target)
    bn, bv = autotune.fused_vocab_ce_config(n, h, v, "bfloat16")
    assert fused_ce_supported(n, h, v, BF16, bn, bv), (bn, bv)
    assert bv >= 256, f"blocks shrunk until the kernel is pointless: {bn, bv}"

    def fn(hid, w, lab):
        return lse_and_target(hid, w, lab, bn, bv, "pallas", False)
    return _grad_sum(fn, (0, 1)), [((n, h), BF16), ((h, v), BF16),
                                   ((n,), I32)]


def _int8_matmul():
    from paddle_tpu.ops.pallas.int8_matmul import int8_matmul_pallas
    return int8_matmul_pallas, [((8, 4096), BF16), ((14336, 4096), I8),
                                ((14336,), F32)]


ONE_CHIP = [
    pytest.param(lambda: _flash(2048, 128, 128), id="flash[s2048,128/128]"),
    pytest.param(lambda: _flash(8192, 128, 128), id="flash[s8192,128/128]"),
    pytest.param(lambda: _flash(2048, 512, 1024, seg=True),
                 id="flash_segment_ids[s2048,512/1024]"),
    pytest.param(lambda: _flash(8192, 1024, 1024, seg=True),
                 id="flash_segment_ids[s8192,1024/1024]"),
    *_tuned_flash_cases(),
    # the rule's blocks (PR 42: one rule for every length, blocks that need
    # not divide it). olmoe.pretrain-4k's call, forward and the one-pass
    # backward (a KV head's whole dk and dv in VMEM), and the two-pass form
    # it falls back to; the serving cells' buckets at lengths no power of
    # two, with Mistral's, Nemotron's and Jamba's head groups; packed
    # documents at a ragged length
    pytest.param(lambda: _flash_rule(4096, h=16, h_kv=16),
                 id="flash_rule[s4096,16/16,one_pass]"),
    pytest.param(lambda: _flash_rule(4096, h=16, h_kv=16, vmem_budget=0),
                 id="flash_rule[s4096,16/16,two_pass]"),
    pytest.param(lambda: _flash_rule(8192, vmem_budget=0),
                 id="flash_rule[s8192,32/8,two_pass]"),
    pytest.param(lambda: _flash_rule(1920, grad=False),
                 id="flash_rule_fwd[s1920,32/8]"),
    pytest.param(lambda: _flash_rule(896, grad=False),
                 id="flash_rule_fwd[s896,32/8]"),
    pytest.param(lambda: _flash_rule(2688, h_kv=2, grad=False),
                 id="flash_rule_fwd[s2688,32/2]"),
    pytest.param(lambda: _flash_rule(3840, h=20, h_kv=1, grad=False),
                 id="flash_rule_fwd[s3840,20/1]"),
    pytest.param(lambda: _flash_rule(1920, seg=True),
                 id="flash_rule_segment_ids[s1920,32/8]"),
    # latent attention's expanded prefill: 20 heads of 256 (192 nope + 64
    # rope; values 256 too), forward only, at the blocks the rule gives
    # the serving buckets on a v5e (one block of the whole length each)
    pytest.param(lambda: _flash_rule(2048, d=256, h=20, h_kv=20, grad=False),
                 id="flash_fwd[s2048,d256]"),
    pytest.param(lambda: _flash_rule(1792, d=256, h=20, h_kv=20, grad=False),
                 id="flash_fwd[s1792,d256]"),
    pytest.param(lambda: _flash_rule(1920, d=256, h=20, h_kv=20, grad=False),
                 id="flash_fwd[s1920,d256]"),
    pytest.param(_latent_decode, id="latent_decode[bf16,64x20x576,page128]"),
    pytest.param(lambda: _paged(128, BF16), id="paged_decode[bf16,page128]"),
    pytest.param(lambda: _paged(16, BF16), id="paged_decode[bf16,page16]"),
    pytest.param(lambda: _paged(128, I8), id="paged_decode[int8,page128]"),
    # the two serving cells' calls: zaya1-8b.reasoning (128 rows, 8 query /
    # 2 KV heads, 24-page tables) and mistral-7b.* (32 rows, 32 / 8, 16)
    pytest.param(lambda: _paged(128, BF16, rows=128, h=8, h_kv=2, per_seq=24),
                 id="paged_decode[bf16,128x8/2,24pages]"),
    pytest.param(lambda: _paged(128, BF16, rows=32, per_seq=16),
                 id="paged_decode[bf16,32x32/8,16pages]"),
    # nemotron-3-nano.agent-turns: 192 rows, 32 query / 2 KV heads (16 a KV
    # head: a group no other cell runs), 40-page tables
    pytest.param(lambda: _paged(128, BF16, rows=192, h=32, h_kv=2, per_seq=40),
                 id="paged_decode[bf16,192x32/2,40pages]"),
    pytest.param(_ssm_update, id="ssm_state_update[192x64x64x128]"),
    # jamba2-3b.batch-reasoning: 256 rows, 20 query heads on ONE KV head (a
    # group that is no power of two), 24-page tables; its three kernels
    pytest.param(lambda: _paged(128, BF16, rows=256, h=20, h_kv=1, per_seq=24),
                 id="paged_decode[bf16,256x20/1,24pages]"),
    pytest.param(_selective_update, id="selective_state_update[256x16x5120]"),
    pytest.param(_window_step, id="conv_window_step[256x3x5120]"),
    pytest.param(_selective_scan, id="selective_scan[1024x16x5120]"),
    # brumby-14b.context-answers: 32 rows, 40 query heads on 8 KV heads, a
    # state of 65 tiles of [128, 128] a KV head; its two kernels
    pytest.param(_power_update, id="power_state_update[32x8x65x128x128]"),
    pytest.param(_power_chunked, id="power_retention_chunked[4096x40/8x128]"),
    pytest.param(lambda: _power_chunked(768),
                 id="power_retention_chunked[768x40/8x128]"),
    # qwen3-next.long-generation: 192 rows, 16 query heads on 2 KV heads of
    # 256 (the first cell at that head size), 24-page tables; its tick kernel
    pytest.param(lambda: _paged(128, BF16, rows=192, h=16, h_kv=2, per_seq=24,
                                d=256),
                 id="paged_decode[bf16,192x16/2,d256,24pages]"),
    pytest.param(lambda: _flash_rule(1024, d=256, h=16, h_kv=2, grad=False),
                 id="flash_fwd[s1024,16/2,d256]"),
    pytest.param(_delta_update, id="gated_delta_state_update[192x32x128x128]"),
    pytest.param(_rms_norm, id="rms_norm[D4096]"),
    pytest.param(_rope, id="rope[s2048,32/8]"),
    pytest.param(lambda: _fused_ce(16384, 4096, 128256),
                 id="fused_ce[16384x4096x128256]"),
    pytest.param(lambda: _fused_ce(16384, 1536, 32000),
                 id="fused_ce[16384x1536x32000]"),
    # olmoe.pretrain-4k's loss head: 8 rows of 4096, the whole vocabulary
    pytest.param(lambda: _fused_ce(32768, 2048, 50304),
                 id="fused_ce[32768x2048x50304]"),
    pytest.param(_int8_matmul, id="int8_matmul[8x4096x14336]"),
]


def _mosaic_calls(text):
    """Instruction names of the Mosaic custom calls of a compiled module."""
    return re.findall(r"^\s*(?:ROOT )?%(\S+) = [^\n]*"
                      r"custom_call_target=\"tpu_custom_call\"", text, re.M)


@pytest.mark.parametrize("case", ONE_CHIP)
def test_kernel_compiles_for_v5e(topo, case, monkeypatch):
    monkeypatch.setattr(autotune, "_device_kind", lambda default="cpu": KIND)
    fn, shapes = case()
    dev = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=dev) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the compiler names a Mosaic call after the kernel's own name, forward
    # (``%flash_attention_fwd.1``) and under jvp/transpose alike: that name
    # is the kernel's event text in the device trace (PERF.md section 3)
    calls = _mosaic_calls(text)
    assert calls and all(any(k in c for k in KERNEL_NAMES) for c in calls), calls


def test_the_olmoe_step_runs_flash_attention_in_two_bf16_calls(
        topo, monkeypatch):
    """``olmoe.pretrain-4k``'s whole ``jit_one_step`` (the cell's trainer,
    built abstractly) compiled for one described chip: attention is two
    Mosaic calls, the forward and ONE backward, whose first operand and
    first result are 4-D bf16 (what ``flash_attn_roofline``'s pattern finds
    them by); no float32 ``[b, h, sq, d]`` buffer stands in for dq anywhere;
    the build's row says how the kernel runs.

    Since PR 43 the program recomputes NOTHING (until then one
    ``fusion.remat``: the routed layer's first gather, 1 GiB, run a second
    time in the backward because the step stood over the compiler's
    rematerialization limit, 8.77 ms a step on the chip) and is no larger
    than it was with that recomputation (14.493 GiB): the router's weight
    is applied to the sorted rows before the down product, so the layer
    keeps no ``[k, t, d]`` array for its backward and builds none there (no
    ``bf16[8,32768,2048]`` broadcast of d out), each product's two
    cotangents leave the backward together, and the weighted activation is
    remade from the pre-activation (PERF.md section 6, PR 43)."""
    from benchmarks import program, run as bench, traffic
    from paddle_tpu import optimizer as opt_mod
    from paddle_tpu.core import compile_cache
    from paddle_tpu.ops import registry
    from paddle_tpu.trainer import Trainer
    monkeypatch.setattr(autotune, "_device_kind", lambda default="cpu": KIND)
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _, config, mix, _, _ = bench.resolve(
        "olmoe.pretrain-4k", os.path.join(root, "BENCHMARK.json"))
    model, _ = program.build_model(config)
    o = dict(config["optimizer"])
    opt = getattr(opt_mod, o.pop("class"))(parameters=model, **o)
    make_state = opt.init_state       # the leaves are shapes: so is the state
    monkeypatch.setattr(opt, "init_state",
                        lambda params: jax.eval_shape(make_state, params))
    tr = Trainer(model, opt)
    tr._ensure_built()
    rows = config["trainer"]["rows_per_chip"]
    batch = traffic.training_rows(mix, 1, 0, rows, config["vocab_size"])
    dev = SingleDeviceSharding(topo.devices[0])
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                       if not hasattr(a, "dtype") else a.dtype,
                                       sharding=dev),
        (tr.params, tr.opt_state, dict(batch), tr._lr_scalar(),
         tr._key_data()))
    log = []
    with compile_cache.building("one_step", log):
        lowered = tr._step_jit.lower(*args)
    assert log[0]["flash_plan"] == [dict(
        block_q=2048, block_k=2048, nq=2, nk=2, interior=1, edge=2, dead=1,
        fwd_parts=4, bwd_parts=8, backward="one_pass", group=1)]
    compiled = lowered.compile()
    text = compiled.as_text()
    flash = re.findall(r"^\s*%(\S*flash_attention\S*) = (\(?\w+\[[\d,]*\])"
                       r"[^\n]*? custom-call\(([^)]*)\)", text, re.M)
    assert sorted(name.split(".")[0] for name, _, _ in flash) == [
        "jvp_flash_attention_fwd_", "transpose_jvp_flash_attention_bwd__"]
    seq = int(mix["seq_len"])
    heads = f"bf16[{rows},16,{seq},128]"
    for name, result, operands in flash:
        assert result.lstrip("(") == heads, (name, result)
        first = operands.split(",")[0].strip().lstrip("%")
        assert re.search(r"^\s*%%%s = %s" % (re.escape(first),
                                            re.escape(heads)), text, re.M), (
            name, first)
    # dq leaves its kernel in bf16: no float32 [b, h, sq, d] anywhere, in a
    # fusion or out of one
    assert f"f32[{rows},16,{seq},128]" not in text
    assert len(set(re.findall(r"%(\S*\.remat\S*) = ", text))) == 0
    ktd = "bf16\\[%d,%d,%d\\]" % (config["num_experts_per_tok"], rows * seq,
                                  config["hidden_size"])
    assert not re.search(r"= %s[^\n]* broadcast\(" % ktd, text)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= 14.50 * 2 ** 30, total / 2 ** 30


def test_the_loss_head_backward_keeps_one_slab_of_logit_cotangents(
        topo, monkeypatch):
    """OLMoE's loss head as ``olmoe.pretrain-4k`` runs it (32768 rows, 2048
    wide, 50304 classes, bf16), forward and backward: the backward stores the
    logit cotangents it forms once, ONE slab of vocab blocks at a time. All
    of them would be 3.3 GB: no buffer of rows x vocabulary (padded or not)
    exists in any dtype, nothing in the program is larger than a slab's
    budget, and the blocks the chooser gives pass the gate for both backward
    kernels (the dW kernel's row block under its own estimate)."""
    from paddle_tpu.analysis import (BanRule, banned_buffers,
                                     materialization_report, parse_hlo)
    from paddle_tpu.ops.pallas import fused_vocab_ce as fce
    monkeypatch.setattr(autotune, "_device_kind", lambda default="cpu": KIND)
    n, h, v = 32768, 2048, 50304
    fn, shapes = _fused_ce(n, h, v)      # asserts fused_ce_supported
    bn, bv = autotune.fused_vocab_ce_config(n, h, v, "bfloat16")
    dw_n = fce.dw_block_n(n, bn, bv, h, 2)
    assert n % dw_n == 0
    assert fce._dw_vmem_bytes(dw_n, bv, h, 2) <= fce.VMEM_BUDGET
    n_blocks = -(-v // bv)
    per_slab = fce.slab_blocks(n, bv, 2, n_blocks)
    assert 1 <= per_slab < n_blocks      # several slabs at this size
    assert n * per_slab * bv * 2 <= fce.SLAB_BYTES
    dev = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=dev) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mod = parse_hlo(compiled.as_text())
    hits = banned_buffers(mod, [BanRule(v, n), BanRule(n_blocks * bv, n)])
    assert hits == [], "\n".join(hit.describe() for hit in hits)
    report = materialization_report(mod)
    assert report["largest_intermediate_bytes"] <= fce.SLAB_BYTES, report
    # the slab, dhidden's float32 sum, W padded and dW, and little besides
    assert compiled.memory_analysis().temp_size_in_bytes < 5 * fce.SLAB_BYTES


def test_a_cca_decode_layer_compiles_around_the_paged_kernel(topo, monkeypatch):
    """One layer of ZAYA1-8B's decode tick (``zaya1-8b.reasoning``: 128
    rows): CCA's front (the down-projections, the two convolutions from the
    slot's state, the q-k mean, L2 norm, partial rotary) is XLA's, the
    attention behind it the existing paged kernel at 2 KV heads of 128 with
    4 query heads each over pools of 1,664 + 1 pages, and nothing else is a
    Mosaic call. The slot's state comes back in the shapes it went in."""
    from paddle_tpu.models.moe_lm import CompressedConvAttention, MoEConfig
    from paddle_tpu.ops import registry
    monkeypatch.setattr(autotune, "_device_kind", lambda default="cpu": KIND)
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")
    attn = CompressedConvAttention(MoEConfig(
        hidden_size=2048, num_attention_heads=8, num_key_value_heads=2,
        head_dim=128, attention="cca", partial_rotary_factor=0.5,
        rope_theta=5e6, dtype="bfloat16"))

    def step(p, x, pos, kv, tables, state):
        with attn._bind(p):
            return attn.decode_paged(x, None, None, pos, kv, tables, state)
    dev = SingleDeviceSharding(topo.devices[0])
    abstract = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), t)
    pool = jax.ShapeDtypeStruct((2, 1665, 128, 128), BF16)
    state = jax.eval_shape(lambda: attn.alloc_slot_state(128))
    args = abstract((attn.raw_parameters(),
                     jax.ShapeDtypeStruct((128, 1, 2048), BF16),
                     jax.ShapeDtypeStruct((128,), I32), (pool, pool),
                     jax.ShapeDtypeStruct((128, 24), I32), state))
    compiled = jax.jit(step, donate_argnums=(3, 5)).lower(*args).compile()
    calls = _mosaic_calls(compiled.as_text())
    assert calls and all("paged_attention_decode" in c for c in calls), calls
    out, kv, new_state = jax.eval_shape(step, *args)
    assert out.shape == (128, 1, 2048)
    assert [a.shape for a in new_state] == [(128, 1280), (128, 1280),
                                            (128, 128)]
    # the pools are written in place: no copy of a pool in the program
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_a_mamba_decode_layer_updates_its_state_in_place(topo, monkeypatch):
    """One Mamba-2 layer of Nemotron-3-Nano's decode tick at 192 slots: the
    state update is the one Mosaic call, the donated state (403 MB of
    float32 and 7 MB of convolution inputs) comes back in the buffers it
    went in by, and the program keeps no second copy of it (one more and
    the cell's tick would not fit beside 10.6 GB of weights)."""
    from paddle_tpu.models.hybrid_lm import HybridConfig, Mamba2Mixer
    from paddle_tpu.ops import registry
    monkeypatch.setattr(autotune, "_device_kind", lambda default="cpu": KIND)
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")
    mixer = Mamba2Mixer(HybridConfig(pattern="M", dtype="bfloat16"))

    def step(p, u, state):
        with mixer._bind(p):
            return mixer.decode(u, state)
    dev = SingleDeviceSharding(topo.devices[0])
    abstract = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), t)
    state = jax.eval_shape(lambda: mixer.alloc_slot_state(192))
    args = abstract((mixer.raw_parameters(),
                     jax.ShapeDtypeStruct((192, 1, 2688), BF16), state))
    compiled = jax.jit(step, donate_argnums=(2,)).lower(*args).compile()
    calls = _mosaic_calls(compiled.as_text())
    assert calls and all("ssm_state_update" in c for c in calls), calls
    out, new_state = jax.eval_shape(step, *args)
    assert out.shape == (192, 1, 2688)
    assert [(a.shape, a.dtype) for a in new_state] == [
        ((192, 3, 6144), BF16), ((192, 32, 128, 128), F32)]
    mem = compiled.memory_analysis()
    state_bytes = 192 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


def test_a_mamba1_decode_layer_updates_its_state_in_place(topo, monkeypatch):
    """One Mamba-1 layer of AI21-Jamba2-3B's decode tick at 256 slots: the
    window's step and the state update are the two Mosaic calls, the donated
    state (84 MB of float32 and 8 MB of convolution inputs) comes back in
    the buffers it went in by, and the program keeps no second copy of it:
    the window reaches its kernel as a bitcast of the leaf (a tap a plane,
    which is how the chip lays ``[256, 3, 5120]`` out), never as a copy."""
    from paddle_tpu.models.hybrid_lm import HybridConfig, Mamba1Mixer
    from paddle_tpu.ops import registry
    monkeypatch.setattr(autotune, "_device_kind", lambda default="cpu": KIND)
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")
    mixer = Mamba1Mixer(HybridConfig(pattern="m", hidden_size=2560,
                                     dtype="bfloat16"))
    assert mixer.state_path(None, 256) == "fused"
    assert mixer.state_path(1024, 1) == "kernel"

    def step(p, u, state):
        with mixer._bind(p):
            return mixer.decode(u, state)
    dev = SingleDeviceSharding(topo.devices[0])
    abstract = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), t)
    state = jax.eval_shape(lambda: mixer.alloc_slot_state(256))
    args = abstract((mixer.raw_parameters(),
                     jax.ShapeDtypeStruct((256, 1, 2560), BF16), state))
    compiled = jax.jit(step, donate_argnums=(2,)).lower(*args).compile()
    text = compiled.as_text()
    calls = _mosaic_calls(text)
    assert sorted(c.split(".")[0] for c in calls) == [
        "conv_window_step", "selective_state_update"], calls
    assert not re.search(r"bf16\[(256,3|3,256),5120\]\S* (copy|transpose)\(",
                         text)
    out, new_state = jax.eval_shape(step, *args)
    assert out.shape == (256, 1, 2560)
    assert [(a.shape, a.dtype) for a in new_state] == [
        ((256, 3, 5120), BF16), ((256, 16, 5120), F32)]
    mem = compiled.memory_analysis()
    state_bytes = 256 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert state_bytes == 256 * 358_400
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


def test_the_jamba_tick_holds_one_copy_of_its_slot_state(topo, monkeypatch):
    """``jamba2-3b.batch-reasoning``'s whole decode tick (28 layers, 256
    slots, the cell's engine) compiled for one described chip: 26 calls of
    each of the tick's two kernels, the 2,385,510,400 B of slot state
    aliased in place (ONE copy), no copy of a window anywhere, and the
    program no larger than the 8.82 GiB it took before the window stepped
    in place (8.73 since)."""
    from benchmarks import program, run as bench
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.ops import registry
    monkeypatch.setattr(autotune, "_device_kind", lambda default="cpu": KIND)
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = bench.resolve("jamba2-3b.batch-reasoning",
                           os.path.join(root, "BENCHMARK.json"))[1]
    model, _ = program.build_model(config)
    eng = ContinuousBatchingEngine(
        model.eval(), generation_config=GenerationConfig(do_sample=False),
        **config["engine"])
    assert model.state_path(None, eng.max_batch) == "fused"
    eng._init_state(jax.ShapeDtypeStruct((config["vocab_size"],), BF16))
    eng._tables_dev = jnp.asarray(eng.tables)
    dev = SingleDeviceSharding(topo.devices[0])
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                       if not hasattr(a, "dtype") else a.dtype,
                                       sharding=dev),
        eng._decode_args(False))
    compiled = eng._build_decode(1, False, "paged").lower(*args).compile()
    text = compiled.as_text()
    names = [c.split(".")[0] for c in _mosaic_calls(text)]
    assert names.count("conv_window_step") == 26
    assert names.count("selective_state_update") == 26
    assert not re.search(r"bf16\[(256,3|3,256),5120\]\S* (copy|transpose)\(",
                         text)
    mem = compiled.memory_analysis()
    assert eng.stats()["slot_state_bytes"] == 2_385_510_400
    assert mem.alias_size_in_bytes >= 2_385_510_400
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= 8.83 * 2 ** 30, total / 2 ** 30


def test_the_brumby_tick_holds_one_copy_of_its_slot_state_and_no_page(
        topo, monkeypatch):
    """``brumby-14b.context-answers``'s whole decode tick (5 layers, 32
    slots, the cell's engine, NO pool) and its widest prefill program
    compiled for one described chip: five calls of the tick's kernel (of
    the prompt's in the prefill), the 5,499,781,120 B of slot state aliased
    in place (ONE copy: a second would be 5.5 GB and would not fit), no
    copy or transpose of a state leaf anywhere, and both programs under
    12 GiB of the chip's 15.75."""
    from benchmarks import program, run as bench
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.ops import registry
    monkeypatch.setattr(autotune, "_device_kind", lambda default="cpu": KIND)
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = bench.resolve("brumby-14b.context-answers",
                           os.path.join(root, "BENCHMARK.json"))[1]
    model, _ = program.build_model(config)
    eng = ContinuousBatchingEngine(
        model.eval(), generation_config=GenerationConfig(do_sample=False),
        **config["engine"])
    assert eng.pools == [] and eng.stats()["paged_layers"] == 0
    assert model.state_path(None, eng.max_batch) == "kernel"
    assert model.state_path(4096, 1) == "kernel"
    assert eng.stats()["slot_state_bytes"] == 5_499_781_120
    eng._init_state(jax.ShapeDtypeStruct((config["vocab_size"],), BF16))
    eng._tables_dev = jnp.asarray(eng.tables)
    dev = SingleDeviceSharding(topo.devices[0])
    abstract = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                       if not hasattr(a, "dtype") else a.dtype,
                                       sharding=dev), t)
    programs = {
        "power_state_update": eng._build_decode(1, False, "paged").lower(
            *abstract(eng._decode_args(False))),
        "power_retention_chunked": eng._prefill_fn(4096).lower(*abstract((
            eng._params, jnp.zeros((1, 4096), I32), eng.pools,
            jnp.asarray(eng.tables[:1]), jnp.int32(0), eng.slot_state,
            np.int32(0))))}
    for kernel, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        names = [c.split(".")[0] for c in _mosaic_calls(text)]
        assert names.count(kernel) == 5, (kernel, names)
        assert set(names) <= {kernel, "fused_rmsnorm_fwd"}, names
        assert not re.search(
            r"f32\[32,8,(65,128,128|72,128)\]\S* (copy|transpose)\(", text)
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= 5_499_781_120
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        assert total <= 12 * 2 ** 30, (kernel, total / 2 ** 30)


def test_the_qwen3_next_tick_holds_one_copy_of_its_slot_state(topo,
                                                              monkeypatch):
    """``qwen3-next.long-generation``'s whole decode tick (12 layers, 192
    slots, the cell's engine) compiled for one described chip: nine calls of
    the tick's kernel beside three of the paged kernel at a head of 256, the
    3,708,813,312 B of slot state and the pools aliased in place (ONE copy),
    no copy or transpose of a state leaf, and the program under 13 GiB of
    the chip's 15.75 (12.44; the widest prefill, whose chunked delta rule is
    XLA's, counts 12.76: PERF.md section 4)."""
    from benchmarks import program, run as bench
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.inference.generation import GenerationConfig
    from paddle_tpu.ops import registry
    monkeypatch.setattr(autotune, "_device_kind", lambda default="cpu": KIND)
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = bench.resolve("qwen3-next.long-generation",
                           os.path.join(root, "BENCHMARK.json"))[1]
    model, _ = program.build_model(config)
    eng = ContinuousBatchingEngine(
        model.eval(), generation_config=GenerationConfig(do_sample=False),
        **config["engine"])
    assert len(eng.pools) == 3 and eng.stats()["paged_layers"] == 3
    assert model.state_path(None, eng.max_batch) == "kernel"
    assert model.state_path(1024, 1) == "xla"
    assert eng.stats()["slot_state_bytes"] == 3_708_813_312
    pool_bytes = 3 * 2 * 2 * 4609 * 128 * 256 * 2
    eng._init_state(jax.ShapeDtypeStruct((config["vocab_size"],), BF16))
    eng._tables_dev = jnp.asarray(eng.tables)
    dev = SingleDeviceSharding(topo.devices[0])
    abstract = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                       if not hasattr(a, "dtype") else a.dtype,
                                       sharding=dev), t)
    compiled = eng._build_decode(1, False, "paged").lower(
        *abstract(eng._decode_args(False))).compile()
    text = compiled.as_text()
    names = sorted(c.split(".")[0] for c in _mosaic_calls(text))
    assert names == (["gated_delta_state_update"] * 9
                     + ["paged_attention_decode"] * 3), names
    assert not re.search(r"f32\[192,32,128,128\]\S* (copy|transpose)\(", text)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 3_708_813_312 + pool_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total <= 13 * 2 ** 30, total / 2 ** 30


@pytest.mark.parametrize("tokens,step,temp_mib", [(1536, 192, 160),
                                                 (4096, 256, 400)])
def test_a_prompts_routed_layer_keeps_one_copy_of_its_sorted_rows(
        topo, tokens, step, temp_mib):
    """One expert layer of Nemotron-3-Nano's prefill (64 of 128 experts of
    2688 x 1856 held, top-6) at a mid and at the widest prompt: the rows
    run the loop over an expert's rows at the step the shapes give, no
    ``ragged-dot``, no padded copy and no scatter of the float32 rows; the
    k gathers back run one after another (a second loop), so the program
    keeps the sorted rows once in bf16 and once in float32 and a choice's
    rows beside them: at 4,096 tokens 381 MiB where the scatter form kept
    510 (PR 37), in the program that sets the cell's peak (0.24 GiB of the
    chip are left there)."""
    from paddle_tpu.base import LazyGuard
    from paddle_tpu.parallel.moe import MoELayer
    with LazyGuard():
        layer = MoELayer(2688, 1856, 128, top_k=6, capacity_factor=None,
                         dtype="bfloat16", scoring="sigmoid",
                         select_bias=True, norm_topk_prob=True,
                         routed_scaling_factor=2.5, experts_held=(0, 64),
                         expert_act="relu2").eval()
    assert layer.inference_path(tokens) == ("loop", step)

    def fn(p, x):
        with layer._bind(p):
            return layer.forward_inference(x)
    dev = SingleDeviceSharding(topo.devices[0])
    leaves = {n: jax.ShapeDtypeStruct(p.value.shape, p.value.dtype,
                                      sharding=dev)
              for n, p in layer.named_parameters()}
    x = jax.ShapeDtypeStruct((1, tokens, 2688), BF16, sharding=dev)
    compiled = jax.jit(fn).lower(leaves, x).compile()
    text = compiled.as_text()
    assert text.count(" while(") == 2 and "ragged-dot" not in text
    rows = tokens * 6
    assert f"f32[{rows},2688]" in text and f"[{rows + step},2688]" not in text
    assert not re.search(rf"f32\[{rows},2688\]\S* scatter\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < temp_mib * 2 ** 20


@pytest.fixture(scope="module")
def olmoe_routed_layer(topo):
    """OLMoE's routed layer as ``olmoe.pretrain-4k`` runs it (8 x 4096
    tokens, 64 experts of 2048 x 1024, top-8, dropless, bf16), forward and
    backward, compiled for one described chip. The loss comes back with the
    gradients, as a step's does: since PR 43 the way back from the experts
    is linear (the router's weight is applied before the down product), so
    the gradients of a linear loss alone would not need the forward's down
    product at all."""
    from paddle_tpu.parallel.moe import MoELayer
    moe = MoELayer(hidden_size=2048, ffn_size=1024, num_experts=64, top_k=8,
                   capacity_factor=None, dtype="bfloat16")

    def loss(p, x):
        out, aux = moe.functional_call(p, x)
        return jnp.sum(out.astype(F32)) + 0.01 * aux
    dev = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
        moe.raw_parameters())
    x = jax.ShapeDtypeStruct((8, 4096, 2048), BF16, sharding=dev)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile()


def test_the_expert_products_compile_to_ragged_dots_alone(olmoe_routed_layer):
    """Both expert products, their two ``dx`` and their two ``dw``
    are XLA's ``ragged-dot`` instructions and no kernel of this repo stands
    among them (PR 28: the Pallas grouped matmul took 8x their time on the
    chip). XLA:TPU emits a ``ragged-dot`` as a Mosaic call of its own, so
    ``tpu_custom_call`` does occur: under the compiler's names alone."""
    calls = _mosaic_calls(olmoe_routed_layer.as_text())
    products = [c for c in calls if not c.startswith("ragged-dot-metadata")]
    assert len(products) == 6, calls
    assert all(c.startswith("ragged-dot") for c in calls), calls
    assert not any(k in c for k in KERNEL_NAMES for c in calls), calls


def test_the_routed_rows_move_by_gathers_in_the_activation_dtype(
        olmoe_routed_layer):
    """The same compiled layer (PR 30): its 262,144 routed rows of 2048 go
    to their experts and come back by gathers, forward and backward, as
    bf16. No scatter over them (on TPU a scatter whose indices XLA cannot
    know to be a permutation is two passes over a ``u32[262144,2048]``
    copy, a ``pred`` mask and an index sort), no float32 copy of them
    around a backward product, and so under 6 GiB of temporaries (4.02
    since PR 43 keeps no [k, t, d] array for the backward, 4.53 before; 9.52
    with the scatter form and float32 cotangents)."""
    text = olmoe_routed_layer.as_text()
    # the entry computation's instructions are the buffers the program
    # holds; inside a fusion a float32 value lives in registers (v5e's
    # vector unit has no bf16 arithmetic)
    results = re.findall(r"^\s*(?:ROOT )?%(\S+) = (.*?) [a-z][\w\-]*\(",
                         text[text.index("\nENTRY "):], re.M)
    assert len(results) > 100
    wide = [(name, shape) for name, shape in results if re.search(
        r"u32\[262144,|pred\[262144,2048\]|f32\[262144,", shape)]
    assert not wide, wide
    scatters = [(name, shape) for name, shape in results
                if "scatter" in name and "[262144,2048]" in shape]
    assert not scatters, scatters
    temp = olmoe_routed_layer.memory_analysis().temp_size_in_bytes
    assert temp < 6 * 2 ** 30, temp / 2 ** 30


# -- the 2x2 mesh ------------------------------------------------------------
# the kernels as the MODEL reaches them under a mesh: through dispatch and
# per_shard (GSPMD cannot partition a Mosaic call), for both four-chip
# layouts of chip_smoke.py --chips 4

def _mesh_norm_rope_attention(hm):
    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops import rope as rope_ops
    act = NamedSharding(hm.mesh, P(("dp", "fsdp"), None, None))
    rep = NamedSharding(hm.mesh, P())

    def fn(x, w, wq, cos, sin):
        h = F.rms_norm(x, w, 1e-5)
        qkv = (h @ wq).reshape(4, 2048, 48, 128)
        q, k, v = jnp.split(qkv, [32, 40], axis=2)
        q, k = rope_ops.apply_rotary_pos_emb(q, k, cos, sin)
        return F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                              training=False)
    args = [jax.ShapeDtypeStruct((4, 2048, 4096), BF16, sharding=act),
            jax.ShapeDtypeStruct((4096,), F32, sharding=rep),
            jax.ShapeDtypeStruct((4096, 6144), BF16, sharding=NamedSharding(
                hm.mesh, P("fsdp", "tp"))),
            jax.ShapeDtypeStruct((2048, 128), F32, sharding=rep),
            jax.ShapeDtypeStruct((2048, 128), F32, sharding=rep)]
    return _grad_sum(fn, (0, 1, 2)), args, 4     # norm + rope + flash, f+b


def _mesh_loss_head(hm):
    """The fused loss head: vocab-parallel over tp where the mesh has one
    (parallel_fused_linear_cross_entropy), data-parallel otherwise."""
    from paddle_tpu.models.llama import fused_causal_lm_loss
    args = [jax.ShapeDtypeStruct((4, 4096, 4096), BF16, sharding=NamedSharding(
                hm.mesh, P(("dp", "fsdp"), None, None))),
            jax.ShapeDtypeStruct((4096, 128256), BF16, sharding=NamedSharding(
                hm.mesh, P("fsdp", "tp"))),
            jax.ShapeDtypeStruct((4, 4096), I32, sharding=NamedSharding(
                hm.mesh, P(("dp", "fsdp"), None)))]
    return jax.grad(fused_causal_lm_loss, argnums=(0, 1)), args, 3


@pytest.mark.parametrize("layout", [dict(fsdp=4), dict(fsdp=2, tp=2)],
                         ids=["fsdp4", "fsdp2_tp2"])
@pytest.mark.parametrize("build", [_mesh_norm_rope_attention,
                                   _mesh_loss_head],
                         ids=["norm_rope_attention", "fused_loss_head"])
def test_kernels_compile_on_the_2x2_mesh(topo, layout, build, monkeypatch):
    from paddle_tpu.ops import registry
    from paddle_tpu.parallel import HybridMesh
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")
    monkeypatch.setattr(autotune, "_device_kind", lambda default="cpu": KIND)
    hm = HybridMesh.build(devices=topo.devices, **layout)
    with hm:
        fn, args, at_least = build(hm)
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= at_least
    if hm.axis_size("tp") > 1 and build is _mesh_loss_head:
        assert "all-reduce" in text      # the lse/target combine over tp
