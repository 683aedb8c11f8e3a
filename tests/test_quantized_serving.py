"""Quantized end-to-end serving (ISSUE 17): converter round-trip,
weight-only + int8-KV logit parity, and the engine feature-matrix
agreement gates (spec_k x prefix x async depth x chunked prefill).

The gates are two-tier by design. TEACHER-FORCED checks (same token
history into both paths) carry tight logit tolerances — per-step
quantization error is ~1e-2. FREE-RUNNING greedy streams only get an
agreement floor: a random tiny model has near-tie logit margins
(<1e-3) that a single quantization flip turns into a divergent suffix,
so exact stream equality is NOT the contract there (trained checkpoints
have wide margins; the bit-exactness contracts live on the page bytes —
see test_fabric_handoff's int8 section)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.inference.generation import generate_scan
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.quantization import (int8_config, quantize_model,
                                     quantize_state_dict)

PAGE = 8
NEW = 10
# free-running agreement floor (mean over prompts) vs the bf16 greedy
# stream: observed ~0.75-0.95 on this seed/platform; catastrophic
# breakage (scale plumbing, garbage pages) lands near vocab-random ~0
AGREE_FLOOR = 0.5
LOGIT_TOL = 0.08


@pytest.fixture(scope="module")
def bf16(tiny_llama):
    return tiny_llama


@pytest.fixture(scope="module")
def quant(bf16):
    """int8 weights + int8 KV — the full quantized serving config."""
    return quantize_model(bf16, kv_dtype="int8")


@pytest.fixture(scope="module")
def prompts(bf16):
    rs = np.random.RandomState(11)
    v = bf16.cfg.vocab_size
    return [rs.randint(0, v, (n,)).astype(np.int32) for n in (6, 11, 17)]


@pytest.fixture(scope="module")
def ref_streams(bf16, prompts):
    gc = GenerationConfig(max_new_tokens=NEW, do_sample=False)
    return [np.asarray(generate_scan(
        bf16, jnp.asarray(p)[None], gc))[0, len(p):].tolist()
        for p in prompts]


def _agreement(streams, refs):
    fr = [sum(int(a) == int(b) for a, b in zip(s, r)) / max(len(r), 1)
          for s, r in zip(streams, refs)]
    return sum(fr) / len(fr)


# ---------------------------------------------------------------------------
# converter
# ---------------------------------------------------------------------------

def test_converter_round_trip(bf16):
    """quantize_state_dict emits transposed int8 weights + fp32 scales
    for every projection, loads into an int8-mode model, and refuses to
    double-quantize."""
    sd = bf16.state_dict()
    qsd = quantize_state_dict(sd)
    n_proj = 0
    for name, w in sd.items():
        if name in qsd and qsd[name].dtype == jnp.int8:
            n_proj += 1
            k, n = w.shape
            assert qsd[name].shape == (n, k)          # transposed layout
            sc = qsd[name + "_scale"]
            assert sc.shape == (n,) and sc.dtype == jnp.float32
            # per-channel absmax: dequant reconstructs within one step
            deq = (np.asarray(qsd[name], np.float32)
                   * np.asarray(sc)[:, None]).T
            err = np.abs(deq - np.asarray(w, np.float32))
            assert err.max() <= np.abs(np.asarray(w)).max() / 127 + 1e-6
        else:
            np.testing.assert_array_equal(np.asarray(qsd[name]),
                                          np.asarray(w))
    assert n_proj > 0
    with pytest.raises(ValueError):
        quantize_state_dict(qsd)                      # already int8
    qm = LlamaForCausalLM(int8_config(bf16.cfg))
    qm.set_state_dict(qsd)                            # shapes line up


def test_int8_mode_refuses_training(quant):
    x = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError):
        quant(x, labels=x)


# ---------------------------------------------------------------------------
# logit-tolerance gates (teacher-forced)
# ---------------------------------------------------------------------------

def test_weight_only_logit_parity(bf16, prompts):
    """Full-forward logits of the int8-weight model stay within the
    quantization tolerance of bf16 on the same prompt, argmaxes agree."""
    qw = quantize_model(bf16)                         # weights only
    x = jnp.asarray(prompts[2])[None, :]
    lb = np.asarray(bf16(x), np.float32)
    lq = np.asarray(qw(x), np.float32)
    assert np.abs(lb - lq).max() <= LOGIT_TOL
    assert (lb.argmax(-1) == lq.argmax(-1)).mean() >= 0.95


def test_int8_kv_teacher_forced_step_parity(bf16, prompts, ref_streams):
    """Paged decode over an int8 pool, fed the SAME history as the bf16
    pool: per-step logits within tolerance, argmaxes agree. This is the
    quality gate free-running agreement can't give (no cascade)."""
    kvq = LlamaForCausalLM(dataclasses.replace(bf16.cfg,
                                               kv_dtype="int8"))
    kvq.set_state_dict(bf16.state_dict())
    p, stream = prompts[1], ref_streams[1]
    full = np.concatenate([p, stream]).astype(np.int32)
    per_model = {}
    for label, model in (("bf16", bf16), ("int8", kvq)):
        core = model.model
        pools, tables = core.alloc_paged_caches(1, len(full) + PAGE,
                                                PAGE)
        h, pools, state = core.prefill_paged(
            jnp.asarray(p)[None, :], pools, tables, core.alloc_slot_state(1),
            0, len(p) - 1)
        logits = [np.asarray(model.logits(h[:, -1]), np.float32)]
        for i in range(len(p), len(full) - 1):
            tok = jnp.asarray(full[i:i + 1])
            pos = jnp.asarray([i], jnp.int32)
            h, pools, state, _ = core.decode_step_paged(tok, pos, pools,
                                                        tables, state)
            logits.append(np.asarray(model.logits(h[:, -1]),
                                     np.float32))
        per_model[label] = np.concatenate(logits, axis=0)
    err = np.abs(per_model["bf16"] - per_model["int8"]).max()
    agree = (per_model["bf16"].argmax(-1)
             == per_model["int8"].argmax(-1)).mean()
    assert err <= LOGIT_TOL, f"per-step logit err {err}"
    assert agree >= 0.9, f"per-step argmax agreement {agree}"


# ---------------------------------------------------------------------------
# engine feature matrix (free-running agreement floor)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec_k", [0, 3])
@pytest.mark.parametrize("prefix", [False, True])
def test_quant_engine_matrix(quant, prompts, ref_streams, spec_k,
                             prefix):
    """Both async depths ride ONE engine per (spec, prefix) cell:
    ``async_depth`` is a host-side drain-window knob read per tick, so
    the depth-2 pass reuses the depth-1 pass's compiled executables
    (and, with prefix on, exercises re-admission over the quantized
    cached pages — the sharing path the ISSUE cares about)."""
    eng = ContinuousBatchingEngine(
        quant, max_batch=len(prompts), page_size=PAGE, max_len=64,
        generation_config=GenerationConfig(max_new_tokens=NEW,
                                           do_sample=False),
        spec_k=spec_k, prefix_cache=prefix, async_depth=1)
    for depth in (1, 2):
        eng.async_depth = depth
        rids = [eng.submit(p) for p in prompts]
        out = eng.run()
        assert eng.kv_quant and eng.kv_quant_ticks > 0
        streams = [list(out[r]) for r in rids]
        a = _agreement(streams, ref_streams)
        assert a >= AGREE_FLOOR, \
            f"spec_k={spec_k} prefix={prefix} depth={depth}: " \
            f"agreement {a}"


def test_quant_engine_chunked_prefill_and_metrics(quant, prompts,
                                                  ref_streams, bf16):
    """Chunked-prefill cell of the matrix, doubling as the telemetry
    gate (one engine, one set of compiles): kv_quant counters/gauges
    publish under the engine label, and the quant knobs land in the
    trainer fingerprint so a dtype flip can't reuse a stale compile."""
    from paddle_tpu.observability.metrics import REGISTRY
    was_enabled = REGISTRY.enabled
    REGISTRY.enable()
    try:
        eng = ContinuousBatchingEngine(
            quant, max_batch=len(prompts), page_size=PAGE, max_len=64,
            generation_config=GenerationConfig(max_new_tokens=NEW,
                                               do_sample=False),
            chunked_prefill=True, prefill_chunk=PAGE, name="q-chunk")
        rids = [eng.submit(p) for p in prompts]
        out = eng.run()
        assert eng.kv_quant_ticks > 0
        a = _agreement([list(out[r]) for r in rids], ref_streams)
        assert a >= AGREE_FLOOR, f"chunked prefill: agreement {a}"
        assert REGISTRY.counter(
            "pt_serving_kv_quant_ticks_total").value(
                engine="q-chunk") > 0
        assert REGISTRY.gauge("pt_serving_kv_quant_enabled").value(
            engine="q-chunk") == 1.0
        assert REGISTRY.gauge("pt_serving_kv_quant_pool_bytes").value(
            engine="q-chunk") > 0
    finally:
        REGISTRY.enabled = was_enabled
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.trainer import Trainer
    tr = Trainer(bf16, AdamW(learning_rate=1e-4, parameters=bf16))
    assert tr._fp_parts()["quantization"] == {
        "weight_dtype": "native", "kv_dtype": "native"}
    # trainer fingerprint: weight/kv dtype are labeled parts
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.trainer import Trainer
    tr = Trainer(bf16, AdamW(learning_rate=1e-4, parameters=bf16))
    fp = tr._fp_parts()
    assert fp["quantization"] == {"weight_dtype": "native",
                                  "kv_dtype": "native"}


def test_int8_pages_keep_more_slots_resident_at_equal_bytes(bf16, quant):
    """What int8 KV pages buy, counted through the engine's own allocator:
    one byte budget gives each pool dtype the pages it affords; eight
    requests of three pages each overfill the native pool (it preempts)
    and fit the int8 pool (it does not), and both finish every stream."""
    def page_bytes(model):
        sizes = []
        for n in (1, 2):
            pools, _ = model.model.alloc_paged_caches(1, n * PAGE, PAGE)
            sizes.append(sum(a.size * a.dtype.itemsize
                             for entry in pools for a in entry))
        return sizes[1] - sizes[0]

    per_page = {"native": page_bytes(bf16), "int8": page_bytes(quant)}
    # int8 values + one float32 scale per row: under half the native bytes
    assert per_page["native"] > 2 * per_page["int8"]
    budget = 13 * per_page["native"]     # 1 reserved + 4 slots x 3 pages
    rs = np.random.RandomState(8)
    reqs = [rs.randint(0, bf16.cfg.vocab_size, (2 * PAGE,)).astype(np.int32)
            for _ in range(8)]
    preempted = {}
    for name, model in (("native", bf16), ("int8", quant)):
        eng = ContinuousBatchingEngine(
            model, max_batch=8, page_size=PAGE, max_len=4 * PAGE,
            num_pages=budget // per_page[name],
            generation_config=GenerationConfig(max_new_tokens=PAGE,
                                               do_sample=False))
        rids = [eng.submit(p) for p in reqs]
        out = eng.run()
        assert all(len(out[r]) == PAGE for r in rids)
        preempted[name] = eng.preemptions
    assert preempted["native"] > 0 and preempted["int8"] == 0, preempted


# ---------------------------------------------------------------------------
# BanRule dtype narrowing (the quant graph contract's mechanism)
# ---------------------------------------------------------------------------

def test_banrule_dtype_narrowing():
    from paddle_tpu.analysis.materialization import BanRule
    blind = BanRule(16, 256, label="any")
    narrow = BanRule(16, 256, label="f32-only", dtype="f32")
    assert blind.matches((2, 16, 8, 16), "s8")
    assert blind.matches((2, 16, 8, 16), "f32")
    assert not narrow.matches((2, 16, 8, 16), "s8")
    assert narrow.matches((2, 16, 8, 16), "f32")
    assert not narrow.matches((2, 16, 8, 8), "f32")
