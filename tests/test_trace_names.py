"""Names on the device's work and one span clock (ISSUE 24): RecordEvent
spans land in the jax profiler's own trace with their ids, every engine and
trainer program and every Pallas kernel has a stable name, a request leaves
one timeline record, and each built program one build_log row."""

import glob
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, profiler
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.nn.layer import Layer
from paddle_tpu.ops import pallas as pallas_ops
from paddle_tpu.optimizer import SGD
from paddle_tpu.trainer import Trainer
from paddle_tpu.trainer.trainer import TRAINER_PROGRAMS

PAGE = 8


def _engine(model, **kw):
    kw.setdefault("generation_config",
                  GenerationConfig(max_new_tokens=4, do_sample=False))
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    return ContinuousBatchingEngine(model, page_size=PAGE, **kw)


def _prompts(n, length, vocab, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, vocab, length).astype(np.int32) for _ in range(n)]


class _TinyReg(Layer):
    def __init__(self):
        super().__init__()
        self.l1 = nn.Linear(8, 16)
        self.l2 = nn.Linear(16, 1)

    def forward(self, x, y):
        return jnp.mean((self.l2(jnp.tanh(self.l1(x))) - y) ** 2)


def _trainer():
    pt.seed(0)
    model = _TinyReg()
    batch = {"x": np.ones((4, 8), np.float32), "y": np.ones((4, 1), np.float32)}
    return Trainer(model, SGD(learning_rate=0.05, parameters=model),
                   donate=False), batch


def _host_spans(trace_dir):
    """{name: [stats dict, ...]} of the program's spans on the /host:CPU
    plane of the profiler's .xplane.pb, with (start, end) under ``_t``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if "::" in e.name and e.name.split("::")[0] in (
                        "serving", "trainer", "compile"):
                    stats = dict(e.stats)
                    stats["_t"] = (e.start_ns, e.start_ns + e.duration_ns)
                    out.setdefault(e.name, []).append(stats)
    return out


# -- spans on the profiler's clock -------------------------------------------

def test_engine_spans_land_on_the_profilers_host_plane(tiny_llama, tmp_path):
    eng = _engine(tiny_llama)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for p in _prompts(3, 5, tiny_llama.cfg.vocab_size):
            eng.submit(p)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    assert set(profiler.SERVING_EVENTS) <= set(spans), sorted(spans)
    # the ids that tie them together
    assert {"queued", "free_pages"} <= set(spans["serving::admit"][0])
    pre = spans["serving::prefill"][0]
    assert pre["kind"] == "full" and pre["bucket"] == PAGE
    assert {"rid", "slot"} <= set(pre) and {"rid", "slot"} <= set(
        spans["serving::activate"][0])
    dispatched = {s["block"]: s for s in spans["serving::dispatch"]}
    drained = {s["block"]: s for s in spans["serving::drain"]}
    reconciled = {s["block"] for s in spans["serving::reconcile"]}
    assert dispatched and set(dispatched) == set(drained) == reconciled
    assert all({"K", "active"} <= set(s) for s in dispatched.values())
    # one clock: a block is drained after it was dispatched, and with
    # async_depth 2 some block N+1 is dispatched before block N drains
    for n, d in drained.items():
        assert dispatched[n]["_t"][0] <= d["_t"][0]
    assert any(dispatched[n + 1]["_t"][0] < drained[n]["_t"][0]
               for n in drained if n + 1 in dispatched)
    # the first dispatch of a program is its build
    assert "compile::run" in spans or "compile::prefill_paged" in spans


def test_a_cca_models_prefill_says_its_attention_and_its_tick_counts_skips(
        tmp_path):
    """``serving::prefill``'s ``attn`` attribute reads the model's attention
    kind ("cca" here; "gqa", "mla" otherwise), and a model whose router has
    a skip choice adds ``moe_skipped`` to ``stats()`` beside the gauge
    ``slot_state_bytes`` (0 for a model that keeps no state beside its
    pages)."""
    from paddle_tpu.models.moe_lm import MoEConfig, MoEForCausalLM
    model = MoEForCausalLM(MoEConfig(
        vocab_size=512, hidden_size=128, moe_intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_experts=4, max_position_embeddings=256, rms_norm_eps=1e-5,
        attention="cca", head_dim=16, partial_rotary_factor=0.5,
        capacity_factor=None, first_k_dense_replace=0, num_shared_experts=0,
        num_experts_per_tok=1, router="mlp", router_hidden_size=32,
        router_skip_choice=True, residual_scaling=True,
        tie_word_embeddings=True)).eval()
    eng = _engine(model)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.submit(_prompts(1, 5, 512)[0])
        eng.run()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    pre = spans["serving::prefill"][0]
    assert pre["attn"] == "cca" and pre["kind"] == "full"
    # the prefill program's build says by name how its experts run: 8 rows
    # x top-1 over 4 experts are expected on all of them, so every expert over
    # every row (``expert_step_rows`` rides only with the sorted rows' loop)
    (built,) = spans["compile::prefill_paged"]
    assert built["expert_path"] == "dense" and built["bucket"] == 8
    assert "expert_step_rows" not in built
    (row,) = [r for r in eng.build_log if r["name"] == "prefill_paged"]
    assert row["expert_path"] == "dense" and "expert_step_rows" not in row
    stats = eng.stats()
    assert {"moe_assignments", "moe_peak_load", "moe_skipped",
            "kv_bytes_per_token", "slot_state_bytes"} <= set(stats)
    c = 4 * 16 + 2 * 16         # Cq + Ck
    assert stats["slot_state_bytes"] == 2 * 2 * (2 * c + 16) * 4
    assert eng.attention_kind == "cca"


def test_trainer_dispatch_is_a_step_span_with_its_step_number(tmp_path):
    tr, batch = _trainer()
    tr.train_step(batch)                   # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            loss = tr.train_step(batch)
        loss.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    steps = _host_spans(tmp_path)["trainer::dispatch"]
    assert [s["step_num"] for s in steps] == [1, 2, 3]
    assert all(s["kind"] == "step" for s in steps)


def test_a_tick_without_trace_or_profiler_builds_no_host_event(
        tiny_llama, monkeypatch):
    """Counts constructions, not time: with nothing recording, the spans of
    whole engine runs and trainer steps allocate no _HostEvent and read no
    clock; the same shim then shows the recording path is what it counts."""
    built = {"events": 0, "clock": 0}
    orig = profiler._HostEvent.__init__

    def counting(self, *a, **kw):
        built["events"] += 1
        orig(self, *a, **kw)

    class _Clock:
        perf_counter = staticmethod(time.perf_counter)

        @staticmethod
        def perf_counter_ns():
            built["clock"] += 1
            return time.perf_counter_ns()

    monkeypatch.setattr(profiler._HostEvent, "__init__", counting)
    monkeypatch.setattr(profiler, "time", _Clock)
    assert not profiler._collector.enabled and profiler._flight_sink is None
    eng = _engine(tiny_llama)
    tr, batch = _trainer()
    for p in _prompts(2, 5, tiny_llama.cfg.vocab_size):
        eng.submit(p)
    eng.run()
    tr.train_step(batch)
    assert built == {"events": 0, "clock": 0}
    with profiler.Profiler() as prof:
        eng.submit(_prompts(1, 5, tiny_llama.cfg.vocab_size)[0])
        eng.run()
        tr.train_step(batch)
    assert built["events"] > 0
    # one clock read at the begin and ONE at the end of each recorded span
    assert built["clock"] == 2 * built["events"]
    by_name = {e.name: e for e in prof.result.events}
    assert set(profiler.SERVING_EVENTS) <= set(by_name)
    assert by_name["trainer::dispatch"].attrs["step_num"] == 1
    args = {e["name"]: e.get("args") for e in
            prof.result.chrome_trace()["traceEvents"]}
    assert "block" in args["serving::drain"]


# -- program names ------------------------------------------------------------

def _module(fn, *args):
    return re.search(r"module @(\S+)", fn.lower(*args).as_text()).group(1)


def test_every_engine_program_has_a_name_of_its_own(tiny_llama):
    vocab = tiny_llama.cfg.vocab_size
    eng = _engine(tiny_llama, spec_k=2, prefix_cache=True, max_len=32,
                  generation_config=GenerationConfig(max_new_tokens=3,
                                                     do_sample=False))
    shared = _prompts(1, 2 * PAGE, vocab)[0]
    eng.submit(shared)                     # full prefill, spec decode
    eng.run()
    eng.submit(shared)                     # full-prompt hit: tail logits
    eng.submit(np.concatenate([shared, _prompts(1, 3, vocab, 1)[0]]))  # suffix
    eng.run()
    payload = eng.serialize_pages(shared)                 # gather
    other = _engine(tiny_llama, prefix_cache=True, max_len=32)
    other.adopt_pages(payload)                            # scatter
    rid = other.submit(_prompts(1, 5, vocab, 2)[0])
    other.step()
    assert other.cancel(rid)                              # deactivate
    eng._cow_page(1, 2)                                   # decode-time COW
    plain = _engine(tiny_llama)
    builders = {
        "decode": plain._build_decode(1, False, "dense"),
        "spec": eng._build_spec_decode(2, False),
        "prefill": eng._prefill_cache[2 * PAGE],
        "chunk": eng._chunk_fns[PAGE],
        "tail": eng._tail_fn, "cow": eng._cow_fn, "act": eng._act_fn,
        "hist": eng._hist_set_fn, "deact": other._deact_fn,
        "gather": eng._gather_fn, "scatter": other._scatter_fn,
    }
    names = {k: fn.__name__ for k, fn in builders.items()}
    assert all(n for n in names.values())
    assert len(set(names.values())) == len(names), names
    for n in names.values():
        assert re.sub(r"_\d+$", "", n) in profiler.SERVING_PROGRAMS, n
    assert names["decode"] == "run"        # what the benchmark's metric reads
    assert names["prefill"] == f"prefill_paged_{2 * PAGE}"
    assert names["chunk"] == f"prefill_chunk_{PAGE}"
    # the name a program is built under is the module the trace prints
    plain._init_state(jnp.zeros((vocab,), jnp.float32))
    plain._tables_dev = jnp.asarray(plain.tables)
    assert _module(builders["decode"],
                   *plain._decode_args(False)) == "jit_run"
    assert _module(builders["cow"], eng.pools, jnp.int32(1),
                   jnp.int32(2)) == "jit_cow_page"
    # ...and every build_log row is of one of them
    rows = eng.build_log + other.build_log
    assert {r["name"] for r in rows} <= set(profiler.SERVING_PROGRAMS)
    assert {"prefill_paged", "prefill_chunk", "tail_logits", "hist_set",
            "spec_decode_block", "gather_pages", "scatter_pages",
            "deactivate", "cow_page", "activate_slot"} <= {
                r["name"] for r in rows}


def test_the_trainers_programs_keep_their_names():
    tr, batch = _trainer()
    tr._ensure_built()
    assert (tr._step_jit.__name__, tr._superstep_jit.__name__) == TRAINER_PROGRAMS
    args = (tr.params, tr.opt_state, batch, tr._lr_scalar(), tr._key_data())
    assert _module(tr._step_jit, *args) == "jit_one_step"


# -- kernel names -------------------------------------------------------------

def _kernels(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernels(sub, out)
    return out


def _flash():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas
    q = jnp.zeros((1, 256, 4, 128), jnp.bfloat16)
    f = lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, interpret=True).astype(jnp.float32).sum()
    return jax.grad(f, argnums=(0, 1, 2)), (q, q, q)


def _flash_two_pass():
    """A KV head whose dk and dv the backward may not keep in VMEM."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas
    q = jnp.zeros((1, 256, 4, 128), jnp.bfloat16)
    f = lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, interpret=True,
        vmem_budget=0).astype(jnp.float32).sum()
    return jax.grad(f, argnums=(0, 1, 2)), (q, q, q)


def _rmsnorm():
    from paddle_tpu.ops.pallas.fused_norm import rms_norm_pallas
    f = lambda x, w: rms_norm_pallas(x, w, interpret=True).sum()
    return jax.grad(f, argnums=(0, 1)), (jnp.zeros((16, 256)), jnp.ones((256,)))


def _rope():
    from paddle_tpu.ops.pallas.fused_rope import fused_rope_pallas
    q, cs = jnp.zeros((1, 128, 2, 128)), jnp.zeros((128, 128))
    return (lambda q, k: fused_rope_pallas(q, k, cs, cs, interpret=True)), (q, q)


def _vocab_ce():
    from paddle_tpu.ops.pallas.fused_vocab_ce import fused_linear_cross_entropy
    lab = jnp.zeros((128,), jnp.int32)
    f = lambda h, w: fused_linear_cross_entropy(h, w, lab, impl="pallas",
                                                interpret=True)
    return jax.grad(f, argnums=(0, 1)), (jnp.zeros((128, 128)),
                                         jnp.zeros((128, 512)))


def _int8():
    from paddle_tpu.ops.pallas.int8_matmul import int8_matmul_pallas
    wq, sc = jnp.zeros((128, 128), jnp.int8), jnp.ones((128,))
    return (lambda x: int8_matmul_pallas(x, wq, sc, interpret=True)), (
        jnp.zeros((128, 128), jnp.bfloat16),)


def _paged():
    from paddle_tpu.ops.pallas.paged_attention import paged_decode_attention
    kp, bt = jnp.zeros((2, 8, 16, 128)), jnp.zeros((2, 4), jnp.int32)
    sl = jnp.array([3, 5], jnp.int32)
    return (lambda q: paged_decode_attention(q, kp, kp, bt, sl,
                                             interpret=True)), (
        jnp.zeros((2, 4, 128)),)


def _latent():
    from paddle_tpu.ops.pallas.latent_attention import latent_decode_attention
    pages, bt = jnp.zeros((1, 8, 16, 160)), jnp.zeros((2, 4), jnp.int32)
    sl = jnp.array([3, 5], jnp.int32)
    return (lambda q: latent_decode_attention(q, pages, bt, sl, 128, 0.1,
                                              interpret=True)), (
        jnp.zeros((2, 4, 160)),)


def _ssm():
    from paddle_tpu.ops.pallas.ssm import ssm_state_update
    rest = (jnp.ones((2, 8, 8)), jnp.ones((2, 8)), -jnp.ones((8,)),
            jnp.ones((2, 2, 128)), jnp.ones((2, 2, 128)))
    return (lambda s: ssm_state_update(s, *rest, interpret=True)[0]), (
        jnp.zeros((2, 2, 128, 32)),)


def _selective_update():
    from paddle_tpu.ops.pallas.selective_ssm import selective_state_update
    rest = (jnp.ones((2, 128)), jnp.ones((2, 128)), jnp.zeros((128,)),
            -jnp.ones((16, 128)), jnp.ones((2, 16)), jnp.ones((2, 16)),
            jnp.ones((128,)), jnp.ones((2, 256)))
    return (lambda s: selective_state_update(s, *rest, interpret=True)[0]), (
        jnp.zeros((2, 16, 128)),)


def _window_step():
    from paddle_tpu.ops.pallas.selective_ssm import conv_window_step
    rest = (jnp.ones((2, 256)), jnp.ones((4, 128)), jnp.zeros((128,)))
    return (lambda w: conv_window_step(w, *rest, interpret=True)[0]), (
        jnp.zeros((2, 3, 128)),)


def _selective_scan():
    from paddle_tpu.ops.pallas.selective_ssm import selective_scan
    rest = (jnp.ones((1, 16, 128)), -jnp.ones((16, 128)),
            jnp.ones((1, 16, 16)), jnp.ones((1, 16, 16)))
    return (lambda x: selective_scan(x, *rest, interpret=True)[0]), (
        jnp.zeros((1, 16, 128)),)


def _power_update():
    from paddle_tpu.ops.pallas.power_retention import power_state_update
    rest = (jnp.zeros((1, 1, 72, 128)), jnp.ones((1, 5, 128)),
            jnp.ones((1, 1, 128)), jnp.ones((1, 1, 128)), jnp.zeros((1, 1)))
    return (lambda s: power_state_update(s, *rest, interpret=True)[0]), (
        jnp.zeros((1, 1, 65, 128, 128)),)


def _power_chunked():
    from paddle_tpu.ops.pallas.power_retention import power_retention_chunked
    rest = (jnp.ones((1, 128, 1, 128)), jnp.ones((1, 128, 1, 128)),
            jnp.zeros((1, 128, 1)))
    return (lambda q: power_retention_chunked(q, *rest, interpret=True)[0]), (
        jnp.ones((1, 128, 5, 128)),)


def _delta_update():
    from paddle_tpu.ops.pallas.gated_delta import gated_delta_state_update
    rest = (jnp.ones((1, 4, 16)), jnp.ones((1, 4, 16)), jnp.ones((1, 8, 128)),
            jnp.zeros((1, 8)), jnp.ones((1, 8)))
    return (lambda s: gated_delta_state_update(s, *rest, interpret=True)[0]), (
        jnp.zeros((1, 8, 16, 128)),)


@pytest.mark.parametrize("entry,expect", [
    (_flash, ["flash_attention_fwd", "flash_attention_bwd"]),
    (_flash_two_pass, ["flash_attention_fwd", "flash_attention_bwd_dq",
                       "flash_attention_bwd_dkv"]),
    (_rmsnorm, ["fused_rmsnorm_fwd", "fused_rmsnorm_bwd"]),
    (_rope, ["fused_rope"]),
    (_vocab_ce, ["fused_vocab_ce_fwd", "fused_vocab_ce_bwd_dh",
                 "fused_vocab_ce_bwd_dw"]),
    (_int8, ["int8_matmul"]),
    (_paged, ["paged_attention_decode"]),
    (_latent, ["latent_attention_decode"]),
    (_ssm, ["ssm_state_update"]),
    (_selective_update, ["selective_state_update"]),
    (_window_step, ["conv_window_step"]),
    (_selective_scan, ["selective_scan"]),
    (_power_update, ["power_state_update"]),
    (_power_chunked, ["power_retention_chunked"]),
    (_delta_update, ["gated_delta_state_update"]),
], ids=lambda v: v.__name__.strip("_") if callable(v) else None)
def test_a_pallas_entry_point_names_its_kernels(entry, expect):
    """Forward and gradient: every pallas_call in the traced program carries
    its documented name, which contains its module's."""
    fn, args = entry()
    got = _kernels(jax.make_jaxpr(fn)(*args).jaxpr, [])
    assert got == expect
    assert set(got) <= set(pallas_ops.KERNEL_NAMES)


def test_the_decode_attention_share_reads_the_paged_kernel_alone():
    """``decode_attn_share.decode`` (PR 25) finds the decode tick's
    attention by the kernel's name, in the forms the TPU compiler gives a
    named Pallas call, and no other kernel nor an operation that only
    consumes the kernel's result."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           "decode_attn_share.decode.json")) as f:
        spec = json.load(f)
    assert spec["reducer"] == "device_op_share" and spec["unit"] == "%"
    rx = re.compile(spec["pattern"])
    tail = (" = bf16[32,8,4,128]{3,2,1,0:T(4,128)(2,1)} custom-call(s32[32,16]"
            "{1,0} %fusion.3), custom_call_target=\"tpu_custom_call\"")
    for kernel in pallas_ops.KERNEL_NAMES:
        for text in (f"%{kernel}.3", f"%jvp_{kernel}_.1", f"{kernel}.7"):
            assert bool(rx.search(text + tail)) == (
                kernel == "paged_attention_decode"), text
    assert not rx.search("%fusion.2 = bf16[32,4096]{1,0} fusion(bf16[32,8,4,"
                         "128]{3,2,1,0} %paged_attention_decode.14), "
                         "kind=kLoop")


def test_xla_own_share_reads_what_is_neither_a_kernel_nor_an_expert_product():
    """``xla_own_share.train`` (PR 30) is the training step's device time
    under XLA's own names: fusions, converts, copies, gathers. Neither a
    ``ragged-dot`` (the expert products and their metadata) nor a named
    kernel of this repo in any of the forms the compiler gives one."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           "xla_own_share.train.json")) as f:
        spec = json.load(f)
    assert spec["reducer"] == "device_op_share" and spec["unit"] == "%"
    assert spec["workloads"] == ["olmoe.pretrain-4k"]
    rx = re.compile(spec["pattern"])
    tail = "bf16[262144,2048]{1,0:T(8,128)(2,1)} fusion(bf16[32768,2048] %p)"
    for own in ("%fusion.7 = ", "%convert_element_type.26 = ", "%copy.11 = ",
                "%gather.3 = ", "%pad_maximum_fusion = ", "fusion.2 = ",
                # an operation that only CONSUMES a kernel's result is XLA's
                "%fusion.9 = bf16[8,8]{1,0} fusion(%ragged-dot-none.2), x = "):
        assert rx.search(own + tail), own
    for not_own in ("%ragged-dot-none.2 = ", "%ragged-dot-metadata = ",
                    "%jvp_fused_vocab_ce_fwd_.1 = ",
                    "%transpose_jvp_flash_attention_fwd__.3 = ",
                    "%fused_rmsnorm_bwd.2 = ", "%fused_rope.1 = ",
                    "ragged-dot-none = "):
        assert not rx.search(not_own + tail), not_own
    # every kernel a training step can run is named in the pattern
    for kernel in pallas_ops.KERNEL_NAMES:
        served_only = kernel in ("paged_attention_decode", "int8_matmul",
                                 "latent_attention_decode",
                                 "ssm_state_update",
                                 "selective_state_update", "selective_scan",
                                 "conv_window_step", "power_state_update",
                                 "power_retention_chunked",
                                 "gated_delta_state_update")
        assert bool(rx.search(f"%jvp_{kernel}_.1 = " + tail)) == served_only


@pytest.mark.parametrize("metric,kernel", [
    ("selective_update_share.jamba", "selective_state_update"),
    ("selective_update_roofline.jamba", "selective_state_update"),
    ("selective_scan_share.jamba", "selective_scan")])
def test_the_jamba_metrics_read_their_kernel_and_not_the_windows(metric,
                                                                 kernel):
    """The tick's two kernels stand side by side in the trace, 26 of each:
    the accepted metrics of the state update and of the prompt's scan find
    their kernel by a SUBSTRING of the instruction's name, so the window's
    kernel carries a name that holds neither, in every form the compiler
    gives a named Pallas call."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           metric + ".json")) as f:
        rx = re.compile(json.load(f)["pattern"])
    tail = (" = (bf16[256,5120]{1,0:T(8,128)(2,1)S(1)}, bf16[3,256,5120]"
            "{2,1,0:T(8,128)(2,1)}) custom-call(f32[4,5120]{1,0} %copy-done.2"
            "), custom_call_target=\"tpu_custom_call\"")
    for text in ("%{}.56", "%jvp_{}_.1", "{}.7"):
        assert rx.search(text.format(kernel) + tail)
        assert not rx.search(text.format("conv_window_step") + tail)


def test_kernel_names_are_all_documented_once():
    names = pallas_ops.KERNEL_NAMES
    assert len(names) == len(set(names)) == 20


# -- request timelines --------------------------------------------------------

STAMPS = ("submit_t", "admit_t", "prefill_start_t", "prefill_dispatched_t",
          "first_tok_t", "done_t")


def test_request_timelines_are_monotone_and_survive_preemption(tiny_llama):
    vocab = tiny_llama.cfg.vocab_size
    new = PAGE + 4
    eng = _engine(tiny_llama, num_pages=3, max_len=8 * PAGE,
                  generation_config=GenerationConfig(max_new_tokens=new,
                                                     do_sample=False))
    rids = [eng.submit(p) for p in _prompts(2, PAGE - 2, vocab, 4)]
    out = eng.run()
    assert eng.preemptions >= 1
    recs = {r["rid"]: r for r in eng.request_timelines()}
    assert sorted(recs) == sorted(rids)
    for rid, r in recs.items():
        stamps = [r[k] for k in STAMPS]
        assert all(s > 0 for s in stamps)
        assert stamps == sorted(stamps), r
        assert r["tokens"] == len(out[rid]) == new
    assert sum(r["preemptions"] for r in recs.values()) == eng.preemptions
    # chunked prefill: the prefill's span runs from its first chunk's start
    # to its last chunk's dispatch
    ch = _engine(tiny_llama, chunked_prefill=True, prefill_chunk=PAGE)
    ch.submit(_prompts(1, 3 * PAGE - 1, vocab, 5)[0])
    ch.run()
    (r,) = ch.request_timelines()
    assert [r[k] for k in STAMPS] == sorted(r[k] for k in STAMPS)
    assert r["prefill_dispatched_t"] > r["prefill_start_t"]


def test_latency_stats_is_computed_from_the_one_record_store(tiny_llama):
    eng = _engine(tiny_llama)
    assert not hasattr(eng, "_latencies") and eng.latency_stats() == {}
    for p in _prompts(5, 5, tiny_llama.cfg.vocab_size, 6):
        eng.submit(p)
    eng.run()
    lat, recs = eng.latency_stats(), eng.request_timelines()
    # the former keys, with the former arithmetic on the same stamps
    ttft = [r["first_tok_t"] - r["submit_t"] for r in recs]
    total = [r["done_t"] - r["submit_t"] for r in recs]
    assert lat["requests"] == 5 and lat["tokens"] == 20
    assert lat["ttft_p50_s"] == float(np.percentile(ttft, 50))
    assert lat["ttft_p99_s"] == float(np.percentile(ttft, 99))
    assert lat["latency_p50_s"] == float(np.percentile(total, 50))
    assert lat["latency_p99_s"] == float(np.percentile(total, 99))
    assert {"itl_p50_s", "itl_p99_s"} <= set(lat)
    # the wait split where the engine's layers hand a request on
    for key in ("queue_wait", "prefill", "first_drain_wait"):
        assert 0.0 <= lat[f"{key}_p50_s"] <= lat[f"{key}_p99_s"]
    parts = [lat[f"{k}_p50_s"] for k in ("queue_wait", "prefill",
                                          "first_drain_wait")]
    assert max(parts) <= lat["ttft_p99_s"]
    eng.reset_latency_stats()
    assert eng.latency_stats() == {} and eng.request_timelines() == []


# -- the engine's own books (ISSUE 36) ------------------------------------------

NEW_KEYS = ("prefill_width_tokens", "prefill_pad_tokens", "first_tokens",
            "prefill_dispatch_s", "first_token_wait_s", "stream_s",
            "stream_tick_s", "stream_ticks", "stream_admit_s",
            "stream_unattributed_s")


class _Script:
    """A device stream played to ``_StreamBooks`` on a scripted clock, in
    milliseconds: ``dispatch`` returns what the engine keeps on the block,
    ``drain`` hands it back at the instant the block's tokens arrive."""

    def __init__(self):
        from paddle_tpu.inference import serving
        self.t = 0.0
        self.books = serving._StreamBooks(clock=lambda: self.t / 1e3)
        self.inflight = []

    def prefill(self, t, busy=None):
        """``busy``: a block dispatched before is still unfinished (as a
        rule one that is not drained yet is)."""
        self.t = t
        self.books.admitted(bool(self.inflight) if busy is None else busy)

    def dispatch(self, t, K=1, busy=None):
        self.t = t
        self.inflight.append(dict(K=K, **self.books.dispatched(
            bool(self.inflight) if busy is None else busy,
            bool(self.inflight))))

    def drain(self, t, waited=True):
        self.t = t
        _, said = self.books.drained(**self.inflight.pop(0), waited=waited)
        self.said = said
        return said["interval_us"] / 1e3, said["chained"]

    def read(self):
        b = self.books
        return {k: round(getattr(b, k) * 1e3, 6) for k in (
            "stream_s", "stream_tick_s", "stream_admit_s",
            "stream_unattributed_s")} | {"ticks": b.stream_ticks}


def _four_intervals():
    """Intervals of 10, 10, 30 (one prefill in front of its tick) and 10
    ms, each block dispatched behind its predecessor and waited for."""
    s = _Script()
    s.dispatch(0)                   # a quiet engine: the stream starts here
    s.dispatch(1)
    assert s.drain(10) == (10.0, True)
    s.prefill(11)
    s.dispatch(12)
    assert s.drain(20) == (10.0, True)
    s.dispatch(21)
    assert s.drain(50) == (30.0, True)
    assert s.drain(60) == (10.0, True)
    return s


def test_the_stream_books_split_an_interval_into_its_ticks_and_its_prefill():
    got = _four_intervals().read()
    assert got == {"stream_s": 60.0, "stream_tick_s": 30.0, "ticks": 3,
                   "stream_admit_s": 20.0, "stream_unattributed_s": 0.0}
    # what the sums leave of stream_s is attributed too: the prefill
    # interval's own tick, at the recent clean tick's time
    assert got["stream_s"] - got["stream_tick_s"] - got["stream_admit_s"] \
        == got["stream_tick_s"] / got["ticks"]


def test_the_stream_books_book_a_quiet_devices_gap_and_start_at_the_enqueue():
    s = _four_intervals()
    s.dispatch(100)                 # nothing in flight: 40 ms after a stamp
    assert s.drain(112) == (12.0, True)
    got = s.read()
    # the 40 ms the device was quiet are in stream_s and in no program's
    # sum, and are not "unattributed" either: the books know it idled
    assert got["stream_s"] == 112.0 and got["stream_tick_s"] == 42.0
    assert got["ticks"] == 4 and got["stream_unattributed_s"] == 0.0
    # a prefill that wakes a quiet engine starts the interval itself, and
    # its tick is taken at the mean of the last clean ticks (10, 10, 10, 12)
    s.prefill(130)
    s.dispatch(133)
    assert s.drain(190) == (60.0, True)
    got = s.read()
    assert got["stream_s"] == 190.0 and got["stream_unattributed_s"] == 0.0
    assert got["stream_admit_s"] == 20.0 + 60.0 - 10.5


def _flowing():
    """A stream in full flow: three clean ticks of 10 ms, each block
    dispatched behind its predecessor, one block (sent at 21) in flight."""
    s = _Script()
    s.dispatch(0)
    for t in (0, 10, 20):
        s.dispatch(t + 1)
        assert s.drain(t + 10) == (10.0, 1)
    return s


def test_a_late_stamp_cannot_split_a_run_and_does_not_break_it():
    s = _flowing()
    before = s.read()
    # the block in flight was ready before the host looked: its stamp (45)
    # is late, but the next went out behind it while it was unfinished, so
    # the device ran both back to back from 30 to 52: one run, two ticks
    s.dispatch(31)
    assert s.drain(45, waited=False) == (15.0, 2)       # left open
    assert s.read() == before                           # nothing booked yet
    s.dispatch(46)
    assert s.drain(52) == (22.0, 1) and s.said["ticks"] == 2
    got = s.read()
    assert got["stream_s"] == 52.0 and got["stream_tick_s"] == 52.0
    assert got["ticks"] == 5
    # two late stamps in a row, a prefill in front of the third block: one
    # run of 3 ticks and 1 program, its ticks at the recent clean tick
    # (10, 10, 10, 11)
    s.dispatch(53)
    assert s.drain(65, waited=False) == (13.0, 2)
    s.prefill(66)
    s.dispatch(67)
    assert s.drain(110, waited=False) == (58.0, 2)
    s.dispatch(111)
    assert s.drain(120) == (68.0, 1)
    assert (s.said["ticks"], s.said["admit_calls"]) == (3, 1)
    assert s.read() == {"stream_s": 120.0, "stream_tick_s": 52.0, "ticks": 5,
                        "stream_admit_s": 68.0 - 3 * 10.25,
                        "stream_unattributed_s": 0.0}


def test_what_the_books_cannot_start_or_end_is_left_unattributed():
    s = _flowing()
    s.dispatch(31)
    assert s.drain(40) == (10.0, 1)
    # the block sent at 31 had FINISHED when the next was enqueued at 55
    # (the host was that late): the device idled from some instant the
    # books cannot know to 55, where it started the next. 40 to 55 is
    # given up; the block enqueued at 55 is a clean tick of 11
    s.dispatch(55, busy=False)
    assert s.drain(56, waited=False) == (16.0, 2)
    s.dispatch(57)
    assert s.drain(66) == (11.0, 1)
    got = s.read()
    assert got["stream_s"] == 66.0 and got["stream_unattributed_s"] == 15.0
    assert got["stream_tick_s"] == 51.0 and got["ticks"] == 5
    assert s.drain(76) == (10.0, 1)
    # a prefill enqueued behind a block that is drained before the
    # prefill's own block goes out: that stamp lies inside the interval,
    # and the device may have idled behind the prefill
    s.dispatch(77)
    s.prefill(78)
    assert s.drain(87) == (10.0, 1)
    s.dispatch(95)
    s.dispatch(96)
    assert s.drain(125) == (38.0, 0)
    got = s.read()
    assert got["stream_admit_s"] == 0.0
    assert got["stream_unattributed_s"] == 15.0 + 38.0
    # a stamp the host waited for is exact whatever came before it: the
    # block behind it starts there
    assert s.drain(135) == (10.0, 1)
    assert s.read()["stream_s"] == 135.0


def test_the_stream_books_divide_a_block_by_its_own_k():
    s = _Script()
    s.prefill(0)
    s.dispatch(2, K=4)
    s.dispatch(3, K=4)
    assert s.drain(70) == (70.0, True)
    # chained, but no clean tick has been seen yet to take its ticks at
    assert s.read() == {"stream_s": 70.0, "stream_tick_s": 0.0, "ticks": 0,
                        "stream_admit_s": 0.0,
                        "stream_unattributed_s": 70.0}
    s.prefill(71)
    s.dispatch(72, K=2)
    assert s.drain(110) == (40.0, True)
    assert s.books.recent_tick_s() == pytest.approx(0.010)
    assert s.drain(180) == (70.0, True)
    got = s.read()
    assert got["ticks"] == 4 and got["stream_tick_s"] == 40.0
    assert got["stream_admit_s"] == 50.0
    assert got["stream_s"] == 180.0 and got["stream_unattributed_s"] == 70.0


def _preempting(model):
    return _engine(model, num_pages=3, max_len=8 * PAGE,
                   generation_config=GenerationConfig(
                       max_new_tokens=PAGE + 4, do_sample=False))


def _chunking(model):
    return _engine(model, chunked_prefill=True, prefill_chunk=PAGE)


@pytest.mark.parametrize("make, lengths", [(_preempting, (PAGE - 2,) * 2),
                                           (_chunking, (3 * PAGE - 1, 5))],
                         ids=["preemption", "chunked_prefill"])
def test_every_new_key_of_stats_is_monotone(tiny_llama, make, lengths):
    eng = make(tiny_llama)
    for i, n in enumerate(lengths):
        eng.submit(_prompts(1, n, tiny_llama.cfg.vocab_size, i)[0])
    prev = eng.stats()
    assert set(NEW_KEYS) <= set(prev) and not any(prev[k] for k in NEW_KEYS)
    while eng.has_work():
        eng.step()
        now = eng.stats()
        for k in NEW_KEYS:
            assert isinstance(now[k], (int, float)) and now[k] >= prev[k], k
        prev = now
    eng.run()
    last = eng.stats()
    assert all(last[k] >= prev[k] for k in NEW_KEYS)
    assert last["first_tokens"] == len(lengths)
    assert last["prefill_width_tokens"] > last["prefill_pad_tokens"] >= 0
    assert last["stream_s"] >= (last["stream_tick_s"] + last["stream_admit_s"]
                                + last["stream_unattributed_s"]) > 0


def test_the_prefill_counters_count_what_each_call_forwards(tiny_llama):
    vocab = tiny_llama.cfg.vocab_size
    eng = _engine(tiny_llama)
    lengths = (5, PAGE, PAGE + 3, 2 * PAGE + 1)
    for i, n in enumerate(lengths):
        eng.submit(_prompts(1, n, vocab, i)[0])
    eng.run()
    st = eng.stats()
    assert st["prefill_width_tokens"] == sum(-(-n // PAGE) * PAGE
                                             for n in lengths)
    assert st["prefill_width_tokens"] - st["prefill_pad_tokens"] \
        == sum(lengths)
    # a chunked prompt: one call a chunk, the last one padded; a preempted
    # request's replay is prefilled (and counted) again, with what it had
    # generated
    for make, lengths in ((_chunking, (3 * PAGE - 1,)),
                          (_preempting, (PAGE - 2,) * 2)):
        eng = make(tiny_llama)
        with profiler.Profiler() as prof:
            for i, n in enumerate(lengths):
                eng.submit(_prompts(1, n, vocab, 4 + i)[0])
            eng.run()
        calls = [e.attrs for e in prof.result.events
                 if e.name == "serving::prefill"]
        st = eng.stats()
        assert st["prefill_width_tokens"] == sum(c["bucket"] for c in calls)
        forwarded = st["prefill_width_tokens"] - st["prefill_pad_tokens"]
        if eng.chunked_prefill:
            assert [c["bucket"] for c in calls] == [PAGE] * 3
            assert forwarded == sum(lengths) and st["prefill_pad_tokens"] == 1
        else:
            assert eng.preemptions >= 1
            assert len(calls) == len(lengths) + eng.preemptions
            assert forwarded > sum(lengths)


def test_the_first_tokens_waits_are_the_timelines_sums(tiny_llama):
    eng = _preempting(tiny_llama)
    for p in _prompts(2, PAGE - 2, tiny_llama.cfg.vocab_size, 4):
        eng.submit(p)
    eng.run()
    for p in _prompts(3, 5, tiny_llama.cfg.vocab_size, 7):
        eng.submit(p)
    eng.run()
    assert eng.preemptions >= 1
    recs, st = eng.request_timelines(), eng.stats()
    assert st["first_tokens"] == len(recs) == 5      # a replay counts once
    for key, a, b in (("prefill_dispatch_s", "prefill_start_t",
                       "prefill_dispatched_t"),
                      ("first_token_wait_s", "prefill_dispatched_t",
                       "first_tok_t")):
        assert st[key] == pytest.approx(sum(r[b] - r[a] for r in recs),
                                        rel=1e-9, abs=1e-12), key
    assert st["prefill_dispatch_s"] + st["first_token_wait_s"] <= sum(
        r["first_tok_t"] - r["submit_t"] for r in recs) + 1e-9


def test_the_drain_span_carries_the_books_stats(tiny_llama, tmp_path):
    eng = _engine(tiny_llama)
    eng.submit(_prompts(1, 3, tiny_llama.cfg.vocab_size)[0])
    eng.run()                               # builds outside the trace
    before = eng.stats()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for p in _prompts(3, PAGE + 3, tiny_llama.cfg.vocab_size, 2):
            eng.submit(p)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    spans, st = _host_spans(tmp_path), eng.stats()
    assert [s["bucket"] for s in spans["serving::prefill"]] == [2 * PAGE] * 3
    drains = spans["serving::drain"]
    assert all({"block", "admit_calls", "ticks", "interval_us", "chained"}
               <= set(s) for s in drains)
    assert all(s["chained"] in (0, 1, 2) and s["interval_us"] >= 0
               for s in drains)
    # a drain that leaves its run open (2) hands its programs and ticks on
    # to the one that closes it (1) or gives it up (0)
    closed = [s for s in drains if s["chained"] != 2]
    assert sum(s["admit_calls"] for s in closed) <= 3 \
        <= sum(s["admit_calls"] for s in drains)
    # every run of the books lies in the trace: the lengths of those that
    # closed are what the books attributed, plus what they could not
    closed_s = sum(s["interval_us"] for s in drains if s["chained"] == 1) / 1e6
    booked = sum(st[k] - before[k] for k in ("stream_tick_s",
                                             "stream_admit_s"))
    assert booked <= closed_s + 1e-5 * len(drains)
    assert st["stream_ticks"] - before["stream_ticks"] <= sum(
        s["ticks"] for s in closed)


def test_the_drain_stamps_are_gone_and_the_books_feed_the_cost_gauges(
        tiny_llama):
    eng = _engine(tiny_llama)
    assert not hasattr(eng, "_drain_stamps")
    assert eng._books.recent_tick_s() is None
    eng._publish_cost_metrics()             # nothing attached: nothing read

    class Watch:
        attached, got = True, []

        def publish(self, seconds, steps_per_exec):
            self.got.append((seconds, steps_per_exec))

    eng._cost_watch = Watch()
    eng._publish_cost_metrics()             # no clean tick yet: nothing said
    assert Watch.got == []
    eng._books._recent.extend([0.010, 0.012])
    eng._publish_cost_metrics()
    assert Watch.got == [(pytest.approx(0.011), eng.decode_block)]


# -- build log ----------------------------------------------------------------

def test_build_log_has_one_row_per_program_built(tiny_llama):
    vocab = tiny_llama.cfg.vocab_size
    eng = _engine(tiny_llama)
    eng.submit(_prompts(1, 5, vocab)[0])
    eng.run()
    rows = list(eng.build_log)
    assert sorted(r["name"] for r in rows) == ["activate_slot",
                                               "prefill_paged", "run"]
    for r in rows:
        assert r["seconds"] > 0 and r["t_s"] > 0
        assert r["cache"] in ("hit", "miss", "uncached")
    assert next(r for r in rows if r["name"] == "prefill_paged")["bucket"] == PAGE
    eng.submit(_prompts(1, 6, vocab, 1)[0])           # same shapes
    eng.run()
    assert eng.build_log == rows
    eng.submit(_prompts(1, PAGE + 3, vocab, 2)[0])    # a new prefill bucket
    eng.run()
    assert [r["name"] for r in eng.build_log[len(rows):]] == ["prefill_paged"]
    assert eng.build_log[-1]["bucket"] == 2 * PAGE


def test_the_programs_that_run_flash_attention_say_its_plan(monkeypatch):
    """A prefill program's row and the trainer's step row carry
    ``flash_plan``: the distinct plans the flash kernel built its grids
    from while the program was traced (blocks, the classes' counts, the
    backward's form), written by the kernel itself through
    ``compile_cache.note``. On the CPU the op runs XLA's composition and
    says nothing; here the kernel stands in, interpreted."""
    import functools
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.ops import registry
    from paddle_tpu.ops.pallas.flash_attention import (flash_attention_pallas,
                                                       flash_plan)
    model = LlamaForCausalLM(LlamaConfig.tiny())      # fresh: traced here
    cfg = model.cfg
    eng = _engine(model)
    eng.submit(_prompts(1, 5, cfg.vocab_size)[0])
    eng.run()
    assert not any("flash_plan" in r for r in eng.build_log)

    monkeypatch.setitem(registry._KERNELS, ("flash_attention", "cpu"),
                        functools.partial(flash_attention_pallas,
                                          interpret=True))
    model = LlamaForCausalLM(LlamaConfig.tiny())
    eng = _engine(model)
    eng.submit(_prompts(1, PAGE + 3, cfg.vocab_size)[0])
    eng.run()
    heads = cfg.num_attention_heads // cfg.num_key_value_heads
    d = cfg.hidden_size // cfg.num_attention_heads
    for row in eng.build_log:
        if row["name"] == "prefill_paged":
            s = row["bucket"]
            assert row["flash_plan"] == [flash_plan(
                s, s, d, True, heads, dtype="float32")._asdict()]
        else:
            assert "flash_plan" not in row, row

    from paddle_tpu.core import compile_cache
    compile_cache.clear()
    tr = Trainer(model, SGD(learning_rate=0.05, parameters=model),
                 donate=False)
    ids = np.ones((2, 32), np.int32)
    tr.train_step({"input_ids": ids, "labels": ids})
    (row,) = tr.build_log
    assert row["name"] == "one_step"
    assert row["flash_plan"] == [flash_plan(32, 32, d, True, heads,
                                            dtype="float32")._asdict()]
    assert row["flash_plan"][0]["backward"] == "one_pass"


def test_the_trainers_build_log_names_the_step_program():
    from paddle_tpu.core import compile_cache
    compile_cache.clear()
    tr, batch = _trainer()
    tr.train_step(batch)
    assert [(r["name"], r["how"]) for r in tr.build_log] == [
        ("one_step", "compile")]
    assert tr.build_log[0]["seconds"] > 0
    tr.train_step(batch)
    assert len(tr.build_log) == 1
    # a second trainer of the same fingerprint reuses the executable:
    # nothing is built, so nothing is logged
    tr2, _ = _trainer()
    tr2.train_step(batch)
    assert tr2.build_log == []
