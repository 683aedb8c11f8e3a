"""A hybrid Mamba-1 / attention decoder with a dense MLP behind every mixer
and a tied head (``jamba``'s layout, as AI21-Jamba2-3B publishes it) through
the program's normal paths, held to the benchmark family's plain reference
(``benchmarks/families/jamba.py`` -> ``refs/jamba.py``: the recurrence token
by token on a state [channels, state size], no cache, no kernel) at a tiny
size on the CPU, in float32, on seeded random weights
(``benchmarks/weights.py``).

Tolerances, and why: program and reference compute the same float32
mathematics in different orders (a state [state size, channels] swept by the
scan's twin and then by the tick's update against a token-by-token recurrence
on its transpose), so logits of size ~1 agree to a few units of float32
rounding over eight blocks: 3e-5 absolute, and a served token lies under the
reference's best by no more.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt  # noqa: E402
from benchmarks import families, program, weights  # noqa: E402
from benchmarks.refs import jamba as ref  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.inference.generation import GenerationConfig  # noqa: E402
from paddle_tpu.models.hybrid_lm import (HybridConfig,  # noqa: E402
                                         HybridForCausalLM, Mamba1Mixer)
from paddle_tpu.ops.pallas import selective_ssm  # noqa: E402
from paddle_tpu.ops.pallas.selective_ssm import (  # noqa: E402
    conv_window_step, conv_window_step_supported, conv_window_step_xla,
    selective_recurrence_xla, selective_scan, selective_scan_supported,
    selective_scan_xla, selective_state_update,
    selective_state_update_supported, selective_state_update_xla)

TOL = 3e-5
SEED = 11
ENGINE = dict(max_batch=2, max_len=96, page_size=16, num_pages=16)
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "ai21-jamba2-3b.serve-1chip.json")


def tiny_config(**kw):
    """The Jamba configuration's file with every size shrunk and four layers
    ``m- *- m- m-`` (the published keys say which: period 4, offset 1): the
    program is built from it exactly as ``benchmarks/program.build_engine``
    builds the cell's. 20 query heads on ONE KV head, as published."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_hidden_layers=4, attn_layer_period=4,
               attn_layer_offset=1, mamba_dt_rank=8, num_attention_heads=20,
               num_key_value_heads=1, head_dim=16, intermediate_size=96,
               vocab_size=256, dtype="float32", engine=ENGINE)
    cfg.update(kw)
    cfg["hybrid_pattern"] = ref.pattern(cfg)
    return cfg


def _build(cfg):
    model, names = program.build_model(cfg)
    program.install(model, names, weights.make_all(SEED, cfg))
    return model.eval()


def _reference(cfg):
    get = lambda ns: weights.make_some(SEED, cfg, ns)

    def logits(ids):
        """Reference logits [s, V] of one row of token ids."""
        ids = np.asarray(ids, np.int32)[None]
        s = ids.shape[1]
        with jax.default_matmul_precision("highest"):
            return np.asarray(families.of(cfg).logits_at(
                cfg, get, [(jnp.asarray(ids), np.zeros(s, int),
                            np.arange(s))])[0])
    return logits


@pytest.fixture(scope="module")
def hybrid():
    """(config, model in eval mode with seeded weights, reference logits)."""
    cfg = tiny_config()
    return cfg, _build(cfg), _reference(cfg)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)


def _engine(model, **kw):
    return ContinuousBatchingEngine(
        model, generation_config=GenerationConfig(do_sample=False),
        **dict(ENGINE, **kw))


def _gaps(reference, prompts, outs):
    gaps = []
    for p, t in zip(prompts, outs):
        r = reference(np.concatenate([p, t[:-1]]))[len(p) - 1:]
        gaps.append(r.max(-1) - r[np.arange(len(t)), t])
    return np.concatenate(gaps)


# -- (a) the Mamba-1 mixer and its two kernels ---------------------------------

def _mixer(seed=0):
    pt.seed(seed)
    cfg = HybridConfig.tiny(pattern="m", initializer_range=0.2)
    mixer = Mamba1Mixer(cfg)
    key = jax.random.key(seed + 1)
    for i, (name, p) in enumerate(mixer.named_parameters()):
        if name in ("conv_bias", "dt_bias", "A_log", "D", "conv_weight",
                    "dt_norm", "b_norm", "c_norm"):
            p.value = p.value + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), p.value.shape)
    return cfg, mixer


def _mixer_leaves(mixer):
    return {"in_proj": mixer.in_proj, "conv": mixer.conv_weight,
            "conv_bias": mixer.conv_bias, "x_proj": mixer.x_proj,
            "dt_norm": mixer.dt_norm, "b_norm": mixer.b_norm,
            "c_norm": mixer.c_norm, "dt_proj": mixer.dt_proj,
            "dt_bias": mixer.dt_bias, "A_log": mixer.A_log, "D": mixer.D,
            "out_proj": mixer.out_proj}


def _mixer_model(cfg):
    return dict(hidden_size=cfg.hidden_size, mamba_expand=cfg.mamba_expand,
                mamba_d_state=cfg.mamba_d_state,
                mamba_dt_rank=cfg.mamba_dt_rank, mamba_d_conv=cfg.conv_kernel,
                rms_norm_eps=cfg.rms_norm_eps)


@pytest.mark.parametrize("length", [16, 37, 64])
def test_the_scan_the_recurrence_and_the_reference_agree(length):
    """One Mamba-1 layer on seeded weights, three ways: the whole-sequence
    scan (the kernel's twin), the serving recurrence one token at a time
    through the slot state, and the plain reference."""
    cfg, mixer = _mixer()
    u = 0.5 * jax.random.normal(jax.random.key(7), (2, length, cfg.hidden_size))
    whole, tail, last = mixer._sequence(u)
    with jax.default_matmul_precision("highest"):
        want = ref.mamba(_mixer_model(cfg), _mixer_leaves(mixer), u)
    assert np.abs(np.asarray(want)).max() > 0.1
    assert np.abs(np.asarray(whole - want)).max() < TOL
    state = mixer.alloc_slot_state(2)
    steps = []
    for t in range(length):
        y, state = mixer.decode(u[:, t:t + 1], state)
        steps.append(y)
    assert np.abs(np.asarray(jnp.concatenate(steps, 1) - want)).max() < TOL
    assert np.abs(np.asarray(state[1] - last)).max() < TOL
    assert np.abs(np.asarray(state[0] - tail)).max() == 0.0


def test_padding_to_a_bucket_does_not_advance_the_state():
    """A prompt of 21 positions padded to 32 and to 48: the state and the
    convolution's window written are those after position 20, bit for bit
    the same whatever the bucket, and the state after 21 unpadded."""
    cfg, mixer = _mixer(1)
    u = 0.5 * jax.random.normal(jax.random.key(3), (1, 48, cfg.hidden_size))
    _, tail, last = mixer._sequence(u[:, :21])
    for bucket in (32, 48):
        _, state = mixer.prefill(u[:, :bucket], mixer.alloc_slot_state(3), 2,
                                 jnp.int32(20))
        assert np.abs(np.asarray(state[1][2] - last[0])).max() < 1e-6
        assert np.abs(np.asarray(state[0][2] - tail[0])).max() == 0.0
        assert float(jnp.abs(state[1][:2]).max()) == 0.0     # other slots


UPDATE_SHAPES = [(2, 16, 128), (8, 16, 256), (16, 16, 640), (3, 8, 128)]
SCAN_SHAPES = [(1, 16, 16, 128), (2, 48, 16, 256), (1, 128, 16, 640),
               (1, 37, 16, 128)]     # the last: padded to whole tiles of time


def _update_args(shape, dtype=jnp.float32):
    """(state, x, the RAW step, dt_bias, A, B, C, D, [x | z]): what the
    fused update takes of a layer."""
    B, N, D = shape
    k = jax.random.split(jax.random.key(B + D), 9)
    return (jax.random.normal(k[0], (B, N, D)),
            jax.random.normal(k[1], (B, D)).astype(dtype),
            jax.random.normal(k[2], (B, D)),
            0.3 * jax.random.normal(k[6], (D,)),
            -jnp.exp(0.3 * jax.random.normal(k[3], (N, D))),
            jax.random.normal(k[4], (B, N)), jax.random.normal(k[5], (B, N)),
            1.0 + 0.3 * jax.random.normal(k[7], (D,)),
            jax.random.normal(k[8], (B, 2 * D)).astype(dtype))


def _window_args(shape, dtype=jnp.float32):
    """(window [B, 3, D], [x | z], the four taps, the bias)."""
    B, _, D = shape
    k = jax.random.split(jax.random.key(B + D + 1), 4)
    return (jax.random.normal(k[0], (B, 3, D)).astype(dtype),
            jax.random.normal(k[1], (B, 2 * D)).astype(dtype),
            1.0 + 0.3 * jax.random.normal(k[2], (4, D)),
            0.3 * jax.random.normal(k[3], (D,)))


def _close(got, want, dtype):
    """Equal to float32 rounding in another order; in bfloat16 to one unit
    in the last place of the cast at the end."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    tol = 2e-5 if dtype == jnp.float32 else 2.0 ** -7
    return bool((np.abs(got - want) <= tol * (1.0 + np.abs(want))).all())


def _scan_args(shape, dtype=jnp.float32):
    b, L, N, D = shape
    k = jax.random.split(jax.random.key(L + D), 5)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, L, D)))
    return (jax.random.normal(k[0], (b, L, D)).astype(dtype),
            dt * (jnp.arange(L) < L - 5)[None, :, None],    # a padded tail
            -jnp.exp(0.3 * jax.random.normal(k[2], (N, D))),
            jax.random.normal(k[3], (b, L, N)),
            jax.random.normal(k[4], (b, L, N)))


@pytest.mark.parametrize("shape", UPDATE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_update_kernel_equals_its_twin_in_interpret_mode(shape, dtype):
    """The fused form: softplus, ``Delta x``, the recurrence, the skip and
    the gate inside, the row out in the activation dtype; and the twin is
    the plain recurrence with the same arithmetic around it."""
    args = _update_args(shape, dtype)
    state, x, step, bias, a, b, c, skip, xz = args
    y0, h0 = selective_state_update_xla(*args)
    y1, h1 = selective_state_update(*args, interpret=True)
    assert y0.dtype == y1.dtype == dtype
    assert np.abs(np.asarray(y0, np.float32)).max() > 1.0
    assert _close(y1, y0, dtype)
    assert np.abs(np.asarray(h0 - h1)).max() < 2e-6
    y, h = selective_recurrence_xla(state, x, jax.nn.softplus(step + bias),
                                    a, b, c)
    want = (y + skip * x.astype(jnp.float32)) * jax.nn.silu(
        xz[:, shape[2]:].astype(jnp.float32))
    assert _close(y0, want.astype(dtype), dtype)
    assert np.abs(np.asarray(h0 - h)).max() == 0.0


@pytest.mark.parametrize("shape", UPDATE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_window_kernel_equals_its_twin_in_interpret_mode(shape, dtype):
    """``silu(conv)`` of the three kept inputs and the token's, and the
    window one token on: rows 1 and 2 moved up, the token's input last,
    bit for bit."""
    args = _window_args(shape, dtype)
    y0, w0 = conv_window_step_xla(*args)
    y1, w1 = conv_window_step(*args, interpret=True)
    assert y0.dtype == y1.dtype == w1.dtype == dtype
    assert w1.shape == args[0].shape
    assert np.abs(np.asarray(y0, np.float32)).max() > 1.0
    assert _close(y1, y0, dtype)
    want = jnp.concatenate([args[0][:, 1:], args[1][:, None, :shape[2]]], 1)
    for w in (w0, w1):
        assert np.abs(np.asarray(w - want, np.float32)).max() == 0.0


@pytest.mark.parametrize("shape", SCAN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_scan_kernel_equals_its_twin_in_interpret_mode(shape, dtype):
    """y at every position and the state at the end; the last 5 positions
    take a step of 0 and leave the state where position L - 6 left it."""
    args = _scan_args(shape, dtype)
    y0, h0 = selective_scan_xla(*args)
    y1, h1 = selective_scan(*args, interpret=True)
    assert np.abs(np.asarray(y0)).max() > 1.0
    assert np.abs(np.asarray(y0 - y1)).max() < 2e-5
    assert np.abs(np.asarray(h0 - h1)).max() < 2e-6
    cut = tuple(a[:, :shape[1] - 5] if a.ndim == 3 else a for a in args)
    assert np.abs(np.asarray(selective_scan_xla(*cut)[1] - h1)).max() < 2e-6


def test_the_scan_is_the_update_token_by_token():
    args = _scan_args((2, 24, 16, 128))
    y, last = selective_scan_xla(*args)
    h = jnp.zeros((2, 16, 128))
    for t in range(24):
        y_t, h = selective_recurrence_xla(h, args[0][:, t], args[1][:, t],
                                          args[2], args[3][:, t],
                                          args[4][:, t])
        assert np.abs(np.asarray(y_t - y[:, t])).max() < 1e-5
    assert np.abs(np.asarray(h - last)).max() < 1e-5


@pytest.mark.parametrize("kernel,make,operand,seen", [
    (selective_state_update, _update_args, 8, (8, 16, 128)),
    (conv_window_step, _window_args, 3, (3, 8, 128))],
    ids=["selective_state_update", "conv_window_step"])
def test_the_update_kernel_updates_its_state_operand_in_place(
        kernel, make, operand, seen):
    """``input_output_aliases``: the state operand of either Pallas call of
    a tick (the last one) is its second result; the window goes in as the
    kernel sees it, a tap a plane."""
    args = make((8, 16, 128))

    def calls(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                calls(sub, out)
        return out
    (call,) = calls(jax.make_jaxpr(
        lambda *a: kernel(*a, interpret=True))(*args).jaxpr, [])
    assert call.params["name"] == kernel.__name__
    assert tuple(call.params["input_output_aliases"]) == ((operand, 1),)
    assert len(call.invars) == operand + 1
    assert call.invars[operand].aval.shape == \
        call.outvars[1].aval.shape == seen


def test_the_gates_say_what_mosaic_takes(monkeypatch):
    """Whole (8, 128) tiles, whole steps of 8 slots (or all of them in one),
    whole bfloat16 sublane tiles of time; the cell's shapes pass."""
    S = jax.ShapeDtypeStruct
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert selective_state_update_supported(S((256, 16, 5120), f32))
    assert selective_state_update_supported(S((2, 16, 128), f32))
    assert not selective_state_update_supported(S((12, 16, 128), f32))
    assert not selective_state_update_supported(S((8, 16, 96), f32))
    assert not selective_state_update_supported(S((8, 16, 128), bf16))
    # the window: whole sublane tiles of slots in ITS dtype (16 of bfloat16)
    assert conv_window_step_supported(S((256, 3, 5120), bf16))
    assert conv_window_step_supported(S((8, 3, 128), f32))
    assert conv_window_step_supported(S((48, 3, 128), bf16))
    assert not conv_window_step_supported(S((8, 3, 128), bf16))
    assert not conv_window_step_supported(S((2, 3, 128), f32))
    assert not conv_window_step_supported(S((16, 3, 96), bf16))
    for bucket in range(128, 1025, 128):
        assert selective_scan_supported(S((1, bucket, 5120), bf16), 16)
    assert selective_scan_supported(S((1, 24, 128), bf16), 16)  # pads time
    assert not selective_scan_supported(S((1, 32, 96), bf16), 16)
    monkeypatch.setenv("PT_DISABLE_PALLAS", "1")        # the kill-switch
    assert not selective_state_update_supported(S((256, 16, 5120), f32))
    assert not selective_scan_supported(S((1, 1024, 5120), bf16), 16)
    assert not conv_window_step_supported(S((256, 3, 5120), bf16))


# -- (b) the model: two blocks a layer, MQA 20/1, a tied head ------------------

def test_a_jamba_layer_is_two_blocks_and_the_head_is_the_table(hybrid):
    cfg, model, _ = hybrid
    assert model.cfg.kinds == cfg["hybrid_pattern"] == "m-*-m-m-"
    names = [n for n, _ in model.named_parameters()]
    assert "lm_head" not in names and "embed_tokens" in names
    h = jax.random.normal(jax.random.key(0), (3, 64))
    assert np.abs(np.asarray(
        model.logits(h) - h @ model.embed_tokens.T)).max() == 0.0
    untied = HybridForCausalLM(HybridConfig.tiny(pattern="m-"))
    assert "lm_head" in [n for n, _ in untied.named_parameters()]
    with pytest.raises(ValueError, match="'m' \\(Mamba-1\\).*'-' \\(dense"):
        HybridConfig.tiny(pattern="m-X")


@pytest.mark.parametrize("mode", ["eval", "train"])
def test_forward_logits_match_the_reference(hybrid, mode):
    _, model, reference = hybrid
    ids = _ids(37)
    getattr(model, mode)()
    try:
        got = np.asarray(model(jnp.asarray(ids[None])))[0]
    finally:
        model.eval()
    want = reference(ids)
    assert np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < TOL


def test_mqa_20_on_1_through_the_paged_helpers_equals_the_reference():
    """Every layer an attention layer (period 1): 20 query heads on ONE KV
    head, a prompt through ``_kv_write_prompt`` and the flash path, then
    ticks through ``_kv_scatter_tokens`` and the paged decode."""
    cfg = tiny_config(num_hidden_layers=2, attn_layer_period=1,
                      attn_layer_offset=0)
    assert cfg["hybrid_pattern"] == "*-*-"
    model, reference = _build(cfg), _reference(cfg)
    state = model.alloc_slot_state(2)
    assert jax.tree.leaves(state) == []
    ids = _ids(30, 4)
    want = reference(ids)
    pools, tables = model.alloc_paged_caches(2, 64, 16)
    assert [a.shape for a in pools[0]] == [(1, 8, 16, 16)] * 2
    padded = jnp.zeros((1, 32), jnp.int32).at[0, :19].set(ids[:19])
    h, pools, state = model.prefill_paged(padded, pools, tables[1:2], state,
                                          1, jnp.int32(18))
    assert np.abs(np.asarray(model.logits(h[0, 18])) - want[18]).max() < TOL
    pos = jnp.array([0, 19], jnp.int32)
    for t in range(19, 30):
        h, pools, state, _ = model.decode_step_paged(
            jnp.array([0, ids[t]], jnp.int32), pos, pools, tables, state)
        assert np.abs(np.asarray(model.logits(h[1, 0])) - want[t]).max() < TOL
        pos = pos + jnp.array([0, 1], jnp.int32)


def _kernels_on_the_cpu(monkeypatch):
    """The Mamba layers take the forms they take on a TPU, with every one
    of their kernels in interpret mode (the attention layers keep asking
    the backend themselves)."""
    import functools
    from paddle_tpu.models import hybrid_lm
    monkeypatch.setattr(hybrid_lm, "_on_tpu", lambda: True)
    for name in ("conv_window_step", "selective_state_update",
                 "selective_scan"):
        monkeypatch.setattr(selective_ssm, name, functools.partial(
            getattr(selective_ssm, name), interpret=True))


@pytest.mark.parametrize("path,slots", [("xla", 2), ("fused", 8)])
def test_prefill_then_ticks_through_the_slot_state_equal_the_full_forward(
        hybrid, path, slots, monkeypatch):
    """A prompt of 21 tokens padded to TWO buckets leaves the same state and
    the same next-token logits; then 15 decode ticks through the pages and
    the slot state read the reference's logits at every position: through
    the twins, and through the tick's two kernels (and the prompt's) at 8
    slots, a whole float32 tile of them."""
    _, model, reference = hybrid
    if path == "fused":
        _kernels_on_the_cpu(monkeypatch)
    assert model.state_path(None, slots) == path
    ids = _ids(36, 1)
    want = reference(ids)
    pools, tables = model.alloc_paged_caches(slots, 64, 16)
    seen = []
    for bucket in (32, 48):
        padded = jnp.zeros((1, bucket), jnp.int32).at[0, :21].set(ids[:21])
        h, filled, state = model.prefill_paged(
            padded, pools, tables[1:2], model.alloc_slot_state(slots), 1,
            jnp.int32(20))
        logits = np.asarray(model.logits(h[0, 20]))
        assert np.abs(logits - want[20]).max() < TOL
        seen.append((logits, state))
    assert np.abs(seen[0][0] - seen[1][0]).max() < 1e-6
    for a, b in zip(jax.tree.leaves(seen[0][1]), jax.tree.leaves(seen[1][1])):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-6
    pos = jnp.zeros((slots,), jnp.int32).at[1].set(21)
    for t in range(21, 36):
        h, filled, state, _ = model.decode_step_paged(
            jnp.zeros((slots,), jnp.int32).at[1].set(ids[t]), pos, filled,
            tables, state)
        assert np.abs(np.asarray(model.logits(h[1, 0])) - want[t]).max() < TOL
        pos = pos.at[1].add(1)


# -- (c) through the engine ----------------------------------------------------

@pytest.fixture(scope="module")
def served(hybrid):
    """Five requests through a two-slot engine (every slot is used again;
    every prompt is shorter than its bucket)."""
    _, model, _ = hybrid
    eng = _engine(model)
    prompts = [_ids(n, 10 + n) for n in (5, 17, 33, 40, 9)]
    rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    out = eng.run()
    return eng, prompts, [out[r] for r in rids]


def test_engine_serves_the_references_tokens_with_slots_reused(hybrid,
                                                               served):
    _, _, reference = hybrid
    eng, prompts, outs = served
    assert all(len(t) == 10 for t in outs)
    assert _gaps(reference, prompts, outs).max() < TOL
    stats = eng.stats()
    assert stats["active"] == 0 and stats["free_pages"] == ENGINE["num_pages"]
    assert not hasattr(eng.core, "tick_counters") or not eng.core.tick_counters


def test_a_preempted_request_is_rebuilt_by_its_prefill(hybrid):
    """Two requests that outgrow five pages between them: one is preempted,
    its pages freed, and its prefill (prompt + what it had generated)
    rebuilds pages and slot state; both still serve the reference's
    tokens."""
    _, model, reference = hybrid
    eng = _engine(model, num_pages=5)
    prompts = [_ids(14, 50), _ids(15, 51)]
    rids = [eng.submit(p, max_new_tokens=30) for p in prompts]
    out = eng.run()
    assert eng.preemptions >= 1
    outs = [out[r] for r in rids]
    assert all(len(t) == 30 for t in outs)
    assert _gaps(reference, prompts, outs).max() < TOL


def test_the_build_log_says_which_form_the_recurrence_took(hybrid, served,
                                                           monkeypatch):
    """``build_log``'s rows of the tick and of every prefill program carry
    ``state_path``: the twin off the TPU; the kernels where the backend is a
    TPU and the shapes are Mosaic's (the cell's are; a tiny model of 128
    channels too, a bucket of whole sublane tiles of time). A tick says
    "fused" where the window's kernel runs too (whole sublane tiles of slots
    in the window's dtype: the cell's 256), "kernel" where the update's runs
    alone. A model without a state-space layer says nothing."""
    eng = served[0]
    rows = [r for r in eng.build_log if r["name"] in ("prefill_paged", "run")]
    assert {r["name"] for r in rows} == {"prefill_paged", "run"}
    assert len(rows) >= 4 and all(r["state_path"] == "xla" for r in rows)
    assert all("state_path" not in r for r in eng.build_log
               if r["name"] not in ("prefill_paged", "run"))
    from paddle_tpu.ops import registry
    model = hybrid[1]
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")
    assert model.state_path(None, 2) == model.state_path(32, 2) == "kernel"
    assert model.state_path(None, 8) == model.state_path(None, 64) == "fused"
    assert model.state_path(None, 12) == "xla"       # no whole steps of slots
    big = HybridForCausalLM(HybridConfig(pattern="m-", hidden_size=2560,
                                         vocab_size=8, dtype="bfloat16"))
    assert big.state_path(None, 256) == "fused"
    assert big.state_path(None, 8) == "kernel"       # half a bfloat16 tile
    assert all(big.state_path(b, 256) == "kernel"
               for b in range(128, 1025, 128))
    plain = HybridForCausalLM(HybridConfig.tiny(pattern="*-"))
    assert plain.state_path(None, 2) is None


def test_the_gauges_at_the_published_sizes():
    """What the engine would keep for AI21-Jamba2-3B at 256 slots, from
    shapes alone (nothing is allocated): 2,385,510,400 B of slot state,
    1,024 B of pages a token, 3,029,337,472 parameters."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    model, _ = program.build_model(cfg)
    assert model.cfg.kinds == ref.pattern(cfg) and len(model.cfg.kinds) == 56
    assert model.cfg.kinds.count("m") == 26 and model.cfg.kinds.count("*") == 2
    assert [i for i, k in enumerate(ref.kinds(cfg)) if k == "*"] == [7, 21]
    state = jax.eval_shape(lambda: model.alloc_slot_state(256))
    assert len(state) == 26
    assert sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(state)) == 2_385_510_400
    pools, _ = jax.eval_shape(lambda: model.alloc_paged_caches(1, 256, 128))
    assert sum(a.shape[0] * a.shape[3] * a.dtype.itemsize
               for entry in pools for a in entry) == 1024
    assert sum(int(np.prod(p.value.shape))
               for _, p in model.named_parameters()) == 3_029_337_472


# -- (d) what must not have moved ----------------------------------------------

# The Nemotron cell's programs at the tiny size of ``test_hybrid_serving``
# (five blocks MEM*E through a two-slot engine), as the commit before this
# file traced them, hashed: the tick and the prefill of a 32-token bucket.
# A Mamba-1 kind, a dense-MLP kind, a tied head and ``state_path`` beside
# them must leave both as they were, equation for equation (jax 0.9.0's
# printing: a jax upgrade re-pins them from the commit before it).
NEMOTRON_JAXPRS = {"run": "abf7e740d8a25cff",
                   "prefill_paged_32": "e483687403098b4f"}


def test_the_nemotron_cells_programs_are_the_parents():
    import test_hybrid_serving as nemotron
    eng = nemotron._engine(nemotron._build(nemotron.tiny_config()))
    eng._init_state(jax.ShapeDtypeStruct((256,), jnp.float32))
    eng._tables_dev = jnp.asarray(eng.tables)

    def sha(fn, *args):
        return hashlib.sha256(
            str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()[:16]
    assert sha(eng._build_decode(1, False, "paged"),
               *eng._decode_args(False)) == NEMOTRON_JAXPRS["run"]
    assert sha(eng._prefill_fn(32), eng._params,
               jnp.zeros((1, 32), jnp.int32), eng.pools,
               jnp.asarray(eng.tables[:1]), jnp.int32(20), eng.slot_state,
               np.int32(0)) == NEMOTRON_JAXPRS["prefill_paged_32"]


@pytest.mark.parametrize("flag,benches", [
    ("--selective-update", ["selective_state_update", "conv_window_step",
                            "mamba1_decode_layer"]),
    ("--selective-scan", ["selective_scan"])])
def test_the_tuning_tool_times_each_kernel_against_its_twin(flag, benches,
                                                            capsys,
                                                            monkeypatch):
    """``tools/tune_kernels.py --selective-update`` / ``--selective-scan``
    at a tiny size in interpret mode: one line a shape, the kernel's time
    and its twin's beside the time the bytes would take (no timing of a
    CPU run is kept: the line's device says so). ``--selective-update``
    judges each half of a layer's tick: alone, and inside whole layers in
    the three forms ``state_path`` names, with the Pallas calls each form
    MADE beside its time."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "tune_kernels", os.path.join(ROOT, "tools", "tune_kernels.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.setattr(sys, "argv", ["tune_kernels.py", "--interpret", flag])
    tool.main()
    assert selective_ssm.conv_window_step is conv_window_step   # put back
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert lines[-1] == {"tuned": False, "cases": len(benches)}
    assert [l["bench"] for l in lines[:-1]] == benches
    assert all(l["device"] == "cpu" for l in lines[:-1])
    for line in lines[:-1]:
        if line["bench"] == "mamba1_decode_layer":
            assert line["fused_calls"] == ["conv_window_step",
                                           "selective_state_update"]
            assert line["kernel_calls"] == ["selective_state_update"]
            assert line["xla_calls"] == []
            assert min(line[f"{p}_us"] for p in ("fused", "kernel", "xla")) > 0
            assert {line["window_winner"], line["update_winner"]} <= {
                "pallas", "xla"}
        else:
            assert line["pallas_us"] > 0 and line["xla_us"] > 0
            if "winner" in line:
                assert line["pallas_calls"] == [line["bench"]]
                assert line["xla_calls"] == [] and line["bytes"] > 0
