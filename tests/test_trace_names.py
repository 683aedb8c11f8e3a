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
    pre = _host_spans(tmp_path)["serving::prefill"][0]
    assert pre["attn"] == "cca" and pre["kind"] == "full"
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
    args = (plain._params, plain.pools, jnp.asarray(plain.tables),
            plain._base_key, plain._state, plain._knobs)
    assert _module(builders["decode"], *args) == "jit_run"
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


@pytest.mark.parametrize("entry,expect", [
    (_flash, ["flash_attention_fwd", "flash_attention_bwd_dq",
              "flash_attention_bwd_dkv"]),
    (_rmsnorm, ["fused_rmsnorm_fwd", "fused_rmsnorm_bwd"]),
    (_rope, ["fused_rope"]),
    (_vocab_ce, ["fused_vocab_ce_fwd", "fused_vocab_ce_bwd_dh",
                 "fused_vocab_ce_bwd_dw"]),
    (_int8, ["int8_matmul"]),
    (_paged, ["paged_attention_decode"]),
    (_latent, ["latent_attention_decode"]),
    (_ssm, ["ssm_state_update"]),
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
                                 "ssm_state_update")
        assert bool(rx.search(f"%jvp_{kernel}_.1 = " + tail)) == served_only


def test_kernel_names_are_all_documented_once():
    names = pallas_ops.KERNEL_NAMES
    assert len(names) == len(set(names)) == 13


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
