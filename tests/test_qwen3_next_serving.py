"""A decoder of Gated DeltaNet, gated-attention and softmax-routed expert
blocks (``qwen3_next``'s layout, as Qwen3-Next-80B-A3B-Instruct publishes
it) through the program's normal paths, held to the benchmark family's plain
reference (``benchmarks/families/qwen3_next.py`` -> ``refs/qwen3_next.py``:
the delta rule token by token, a full softmax, every held expert over every
token) at a tiny size on the CPU, in float32, on seeded random weights
(``benchmarks/weights.py``).

The seeded decay forgets half of the state a token, so what a prompt's
chunks and a tick carry hardly shows. Every serving test here therefore runs
a SLOW variant too: ``A_log`` = -6 (log alpha ~ -1/500) and, as in
``tests/test_brumby_serving.py``, column 0 of the table set to 20, which no
layer's output outweighs, so every normalised input has ~8 in its first
coordinate at every depth, and the projection's ``b`` columns read it with
0.75: beta = sigmoid(~6). Program and reference both get the patched leaves.

Tolerances, and why: program and reference compute the same float32
function in two algebraic forms (chunks of 16 solved as triangular systems
against a token loop; XLA's flash twin against a full softmax; a batched
product over the held experts against a loop over them). Logits of size ~1
agree to 3e-5 (measured 2e-6); the routed block is DISCONTINUOUS in its
input (a near-tie at the top-k boundary), so the seeds here were checked to
have no such tie. The same comparison in bfloat16 reads ~1e-2 and must fail
the float32 tolerance.
"""

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

from benchmarks import families, program, weights  # noqa: E402
from benchmarks.refs import qwen3_next as ref  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.inference.generation import GenerationConfig  # noqa: E402
from paddle_tpu.models import hybrid_gated as gated  # noqa: E402
from paddle_tpu.models.hybrid_lm import (HybridBlock,  # noqa: E402
                                         HybridConfig, HybridForCausalLM)

TOL = 3e-5
SEED = 13
ENGINE = dict(max_batch=2, max_len=160, page_size=16, num_pages=20)
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "qwen3-next-80b-a3b.serve-1chip.json")


def tiny_config(**kw):
    """The cell's configuration file with every size shrunk: four layers
    (one published period, eight blocks ``dededeae``), 2 key heads serving 4
    value heads of [16, 128], chunks of 16 so that a prompt of 70 crosses
    four edges, attention 4 / 2 heads of 32 of which 8 dims turn, 8 of 16
    experts held, top-3; built exactly as ``benchmarks/program.build_engine``
    builds the cell's."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_hidden_layers=4, linear_num_key_heads=2,
               linear_num_value_heads=4, linear_key_head_dim=16,
               linear_value_head_dim=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, moe_intermediate_size=32,
               shared_expert_intermediate_size=32, num_experts=8,
               router_width=16, num_experts_per_tok=3, vocab_size=256,
               dtype="float32", engine=ENGINE)
    cfg.update(kw)
    cfg["hybrid_pattern"] = ref.pattern(cfg)
    cfg["program"]["config_fields"]["delta_chunk_size"] = 16
    return cfg


def _leaves(cfg, gates):
    """name -> leaf (float32), the slow variant's patches applied."""
    hv = cfg["linear_num_value_heads"]

    def patch(name, leaf):
        if gates != "slow":
            return leaf
        if name == "embed":
            return leaf.at[:, 0].set(20.0)
        if name.endswith(".A_log"):
            return jnp.full_like(leaf, -6.0)
        if name.endswith(".in_proj"):       # the b columns read coordinate 0
            return leaf.at[0, -2 * hv:-hv].set(0.75)
        return leaf
    return lambda names: {n: patch(n, v) for n, v in weights.make_some(
        SEED, cfg, names).items()}


def _build(cfg, get):
    model, names = program.build_model(cfg)
    program.install(model, names, get(list(families.of(cfg).leaf_shapes(cfg))))
    return model.eval()


def _reference(cfg, get):
    def logits(ids):
        """Reference logits [s, V] of one row of token ids."""
        ids = np.asarray(ids, np.int32)[None]
        s = ids.shape[1]
        with jax.default_matmul_precision("highest"):
            return np.asarray(families.of(cfg).logits_at(
                cfg, get, [(jnp.asarray(ids), np.zeros(s, int),
                            np.arange(s))])[0])
    return logits


@pytest.fixture(scope="module", params=["fast", "slow"])
def qwen(request):
    """(gates, config, model in eval mode, reference logits)."""
    cfg = tiny_config()
    get = _leaves(cfg, request.param)
    return request.param, cfg, _build(cfg, get), _reference(cfg, get)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)


def _engine(model, **kw):
    return ContinuousBatchingEngine(
        model, generation_config=GenerationConfig(do_sample=False),
        **dict(ENGINE, **kw))


# -- (f) the small formulas -----------------------------------------------------

def test_the_zero_centred_norm_scales_by_one_plus_w():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 64)) * 5,
                    jnp.float32)
    unit = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    norm = gated.ZeroCentredRMSNorm(64, 1e-6)
    assert not np.asarray(norm.weight).any()        # w = 0: the identity scale
    assert np.abs(np.asarray(norm(x) - unit)).max() < 1e-6
    w = jnp.linspace(-0.5, 0.5, 64)
    norm.weight = w
    assert np.abs(np.asarray(norm(x) - unit * (1 + w))).max() < 1e-6
    # a property of the block's kind, not a switch of the configuration
    cfg = HybridConfig.tiny(pattern="dae*E-")
    kinds = [type(HybridBlock(cfg, k).norm).__name__ for k in cfg.pattern]
    assert kinds == ["ZeroCentredRMSNorm"] * 3 + ["RMSNorm"] * 3
    assert type(HybridForCausalLM(cfg).norm).__name__ == "RMSNorm"
    assert type(HybridForCausalLM(HybridConfig.tiny(pattern="de")).norm
                ).__name__ == "ZeroCentredRMSNorm"


def test_the_rotary_embedding_turns_the_first_quarter_alone():
    """Head size 256, ``partial_rotary_factor`` 0.25: 64 dims turn (pairs (i,
    i + 32) by the angle pos / theta^(2 i / 64)), 192 do not."""
    from paddle_tpu.ops.rope import rope_at
    cfg = HybridConfig.tiny(pattern="a", head_dim=256, rope_theta=1e7,
                            partial_rotary_factor=0.25)
    mixer = gated.GatedAttention(cfg)
    assert mixer.rot == 64
    t = jnp.asarray(np.random.default_rng(1).normal(size=(1, 5, 2, 256)),
                    jnp.float32)
    pos = jnp.asarray([0, 1, 7, 1000, 3071])
    cos, sin = rope_at(pos, 64, 1e7)
    got = np.asarray(mixer._rotate(t, cos[:, None], sin[:, None]))
    assert np.array_equal(got[..., 64:], np.asarray(t)[..., 64:])
    assert np.array_equal(got[:, 0], np.asarray(t)[:, 0])      # position 0
    ang = np.asarray(pos, np.float64)[:, None] / 1e7 ** (np.arange(32) / 32)
    x1, x2 = np.asarray(t)[..., :32], np.asarray(t)[..., 32:64]
    c, s = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    # float32 angles: 3,071 radians carry ~2e-4 of rounding
    assert np.abs(got[..., :32] - (x1 * c - x2 * s)).max() < 2e-3
    assert np.abs(got[..., 32:64] - (x2 * c + x1 * s)).max() < 2e-3
    assert np.abs(got[:, :3, :, :32] - (x1 * c - x2 * s)[:, :3]).max() < 1e-5


def test_the_output_gate_and_the_shared_experts_gate():
    cfg = HybridConfig.tiny(pattern="ae", head_dim=16, hidden_size=64,
                            shared_expert_intermediate_size=32)
    rng = np.random.default_rng(2)
    attn = gated.GatedAttention(cfg)
    u = jnp.asarray(rng.normal(size=(2, 3, 64)), jnp.float32)
    out = jnp.asarray(rng.normal(size=(2, 3, 4, 16)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(2, 3, 4, 16)) * 3, jnp.float32)
    want = (out / (1 + jnp.exp(-gate))).reshape(2, 3, 64) @ attn.o_proj
    assert np.abs(np.asarray(attn._o(out, gate, u) - want)).max() < 1e-6
    shared = gated.GatedSharedExpert(cfg)
    shared.shared_gate = jnp.asarray(rng.normal(size=(64, 1)), jnp.float32)
    g, up = jnp.split(u @ shared.gate_up_proj, 2, -1)
    want = (jax.nn.silu(g) * up) @ shared.down_proj / (
        1 + jnp.exp(-(u @ shared.shared_gate)))
    assert float(jnp.abs(want).max()) > 1e-4
    assert np.abs(np.asarray(shared(u) - want)).max() < 1e-7


# -- (c) forward against the reference ------------------------------------------

def _mixer_leaves(mixer, kind):
    if kind == "d":
        return {"in_proj": mixer.in_proj, "conv": mixer.conv_weight,
                "dt_bias": mixer.dt_bias, "A_log": mixer.A_log,
                "gate_norm": mixer.norm_weight, "out_proj": mixer.out_proj}
    return {"qkv": mixer.qkv_proj, "q_norm": mixer.q_norm,
            "k_norm": mixer.k_norm, "o": mixer.o_proj}


@pytest.mark.parametrize("block,kind", [(0, "d"), (6, "a"), (1, "e")])
def test_each_new_block_kind_alone_is_the_references(qwen, block, kind):
    """One block's mixer against the reference's function of the same
    leaves: the projection's six groups, the convolution without a bias, the
    unit-length q and k, the norm before the gate (``d``); a head's [q |
    gate], the zero-centred head norms, the partial rotation, the output
    gate (``a``); softmax over the router's 16, top-3 renormalised, the 8
    held experts' share, the gated shared expert (``e``)."""
    gates, cfg, model, _ = qwen
    layer = model.layers[block]
    assert layer.kind == kind
    u = jnp.asarray(np.random.default_rng(5).normal(size=(2, 50, 64)),
                    jnp.float32)
    if gates == "slow":
        u = u.at[:, :, 0].set(8.0)
    with jax.default_matmul_precision("highest"):
        if kind == "e":
            w = {"router": layer.mixer.gate_weight,
                 "experts_gate_up": layer.mixer.experts.w_gate_up,
                 "experts_down": layer.mixer.experts.w_down,
                 "shared_gate_up": layer.shared_expert.gate_up_proj,
                 "shared_down": layer.shared_expert.down_proj,
                 "shared_gate": layer.shared_expert.shared_gate}
            want = np.asarray(ref.experts(cfg, w, u))
            got = np.asarray(layer.experts(u)[0])
        else:
            fn = ref.delta_net if kind == "d" else ref.attention
            want = np.asarray(fn(cfg, _mixer_leaves(layer.mixer, kind), u))
            got = np.asarray(layer.mixer(u))
    # relative to the block's own size (an expert block's output is ~5e-3
    # at these widths; a flipped choice would move it by a third of that)
    scale = np.abs(want).max()
    assert scale > 2e-3
    assert np.abs(got - want).max() < 1e-4 * scale


def test_a_whole_period_matches_the_reference_and_bfloat16_does_not(qwen):
    gates, cfg, model, reference = qwen
    ids = _ids(70, 1)
    got = np.asarray(jax.jit(model.forward)(jnp.asarray(ids)[None]))[0]
    want = reference(ids)
    assert np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < TOL
    if gates == "slow":
        return
    # the same model in bfloat16 reads two orders wider: the tolerance is
    # float32's, not a formality
    low, names = program.build_model(dict(cfg, dtype="bfloat16"))
    leaves = _leaves(cfg, gates)(list(names.values()))
    for n, p in low.named_parameters():
        p.value = leaves[names[n]].astype(p.value.dtype)
    rough = np.asarray(jax.jit(low.eval().forward)(jnp.asarray(ids)[None]),
                       np.float32)[0]
    assert np.abs(rough - want).max() > 100 * TOL


# -- (e) the shares of a layer add up -------------------------------------------

def test_eight_shares_routed_parts_and_the_shared_expert_once_are_the_layer():
    """The guide's share test: the uncut reference's routed block (16
    experts, all held) equals the sum of the routed parts of the 8 shares
    the PROGRAM builds (2 experts each behind the same 16-wide router, its
    top-3 and its renormalisation over all 16) plus the gated shared expert
    counted once."""
    cfg = tiny_config(num_experts=16)       # the whole layer: 16 of 16 held
    get = _leaves(cfg, "fast")
    w = {n.split(".", 2)[2]: v for n, v in get(
        [f"layers.1.{t}" for t in ref.LEAVES["e"]]).items()}
    u = jnp.asarray(np.random.default_rng(7).normal(size=(2, 30, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.experts(cfg, w, u))
        total = np.zeros_like(whole)
        counted = 0
        for share in range(8):
            block = program.build_model(dict(
                cfg, num_experts=2, first_expert_held=2 * share))[0].layers[1]
            block.mixer.gate_weight = w["router"]
            held = slice(2 * share, 2 * share + 2)
            block.mixer.experts.w_gate_up = w["experts_gate_up"][held]
            block.mixer.experts.w_down = w["experts_down"][held]
            routed, load = block.mixer.forward_inference(u)
            total += np.asarray(routed)
            counted += int(load.sum())
            # and the reference given the same share says the same
            part = ref.routed(dict(cfg, num_experts=2,
                                   first_expert_held=2 * share),
                              dict(w, experts_gate_up=w["experts_gate_up"][held],
                                   experts_down=w["experts_down"][held]),
                              u.reshape(-1, 64))
            assert np.abs(np.asarray(part).reshape(whole.shape)
                          - np.asarray(routed)).max() < 1e-7
        total += np.asarray(ref.shared(w, u.reshape(-1, 64))).reshape(
            whole.shape)
    assert counted == 2 * 30 * 3            # every choice is one share's
    assert np.abs(whole).max() > 2e-3       # an expert block's size here
    assert np.abs(total - whole).max() < 1e-4 * np.abs(whole).max()


# -- (d) prefill, then ticks, through pages and slot state -----------------------

def test_prefill_then_ticks_equal_the_full_forward(qwen):
    """A prompt of 37 tokens (three chunks of 16, the last ragged) padded to
    TWO buckets leaves the same state, the same window and the same
    next-token logits; then 20 decode ticks through the slot state and the
    pages read the reference's logits at every position, each row at its
    OWN position (the rotary embedding's and the pages'): the other slot's
    garbage moves nothing."""
    gates, _, model, reference = qwen
    ids = _ids(57, 2)
    want = reference(ids)
    pools, tables = model.alloc_paged_caches(2, 64, 16)
    assert len(pools) == 1 and pools[0][0].shape == (2, 8, 16, 32)
    seen = []
    prefill, tick = jax.jit(model.prefill_paged), jax.jit(
        model.decode_step_paged)
    for bucket in (48, 64):
        padded = jnp.zeros((1, bucket), jnp.int32).at[0, :37].set(ids[:37])
        h, filled, state = prefill(
            padded, pools, tables[1:2], model.alloc_slot_state(2), 1,
            jnp.int32(36))
        logits = np.asarray(model.logits(h[0, 36]))
        assert np.abs(logits - want[36]).max() < TOL
        seen.append((logits, state))
    assert len(state) == 3 and [a.shape for a in state[0]] == [
        (2, 3, 2 * 2 * 16 + 4 * 128), (2, 4, 16, 128)]
    for a, b in zip(jax.tree.leaves(seen[0][1]), jax.tree.leaves(seen[1][1])):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-5
        assert not np.asarray(a)[0].any()          # slot 0 was not written
        assert np.asarray(a)[1].any()
    pos = jnp.asarray([5, 37], jnp.int32)
    for t in range(37, 57):
        h, filled, state, counts = tick(
            jnp.asarray([7, ids[t]], jnp.int32), pos, filled, tables, state)
        assert np.abs(np.asarray(model.logits(h[1, 0]))
                      - want[t]).max() < TOL
        # 2 rows x top-3 in 4 expert layers, whoever holds the choice
        assert counts.shape == (3,) and int(counts[0]) == 2 * 3 * 4
        assert 0 < int(counts[2]) <= int(counts[0])
        pos = pos + 1


@pytest.fixture(scope="module")
def served(qwen):
    """Five requests of different lengths through a two-slot engine (every
    slot is used again; every prompt is shorter than its bucket; the longest
    crosses five chunks)."""
    _, _, model, _ = qwen
    eng = _engine(model)
    prompts = [_ids(n, 10 + n) for n in (5, 17, 70, 40, 9)]
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    out = eng.run()
    return eng, prompts, [out[r] for r in rids]


def test_engine_serves_the_references_tokens_with_slots_reused(qwen, served):
    """Two slots of different lengths decode side by side, and a reused
    slot's state is overwritten by its next prefill from ZERO (nothing
    clears it in between), or the third, fourth and fifth requests would
    read their predecessors' context: every served token is the reference's
    best to the logits' tolerance."""
    _, _, _, reference = qwen
    eng, prompts, outs = served
    assert all(len(t) == 12 for t in outs)
    for p, t in zip(prompts, outs):
        r = reference(np.concatenate([p, t[:-1]]))[len(p) - 1:]
        assert (r.max(-1) - r[np.arange(len(t)), t]).max() < TOL
    state = jax.tree.leaves(eng.slot_state)
    assert len(state) == 6 and all(np.asarray(a).any() for a in state)


def test_the_engine_keeps_pages_for_the_attention_layer_alone(qwen, served):
    """Pools for the one ``a`` block, slot state for the three ``d`` blocks,
    the three expert counters in ``stats()``, the recurrence's form in
    ``build_log``; and what needs a snapshot of the state is refused."""
    _, _, model, _ = qwen
    eng = served[0]
    stats = eng.stats()
    assert stats["paged_layers"] == 1 and len(eng.pools) == 1
    assert stats["kv_bytes_per_token"] == 2 * 2 * 32 * 4
    assert stats["slot_state_bytes"] == 2 * 3 * (
        4 * 16 * 128 * 4 + 3 * (2 * 2 * 16 + 4 * 128) * 4)
    assert stats["preemptions"] == 0
    ticks = sum(eng.attn_path_ticks.values())
    assert stats["moe_assignments"] == ticks * 2 * 3 * 4
    assert 0 < stats["moe_assignments_held"] < stats["moe_assignments"]
    assert 0 < stats["moe_peak_load"] <= stats["moe_assignments_held"]
    rows = [r for r in eng.build_log if r["name"] in ("prefill_paged", "run")]
    assert {r["name"] for r in rows} == {"prefill_paged", "run"}
    assert all(r["state_path"] == "xla" for r in rows)
    assert all(r["expert_path"] in ("dense", "loop") for r in rows
               if r["name"] == "prefill_paged")
    with pytest.raises(ValueError, match="prefix_cache"):
        _engine(model, prefix_cache=True)


def test_the_build_log_says_which_form_the_recurrence_took(monkeypatch):
    """On a TPU the cell's tick takes the kernel (32 tiles of [128, 128] a
    slot) and every prompt XLA's chunked form; 64-lane values take the twin;
    a tick of 192 rows runs every held expert over every row."""
    from paddle_tpu.ops import registry
    with open(CONFIG) as f:
        cfg = json.load(f)
    model, _ = program.build_model(cfg)
    assert model.state_path(None, 192) == model.state_path(1024, 1) == "xla"
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")
    assert model.state_path(None, 192) == "kernel"
    assert all(model.state_path(b, 1) == "xla" for b in range(128, 1025, 128))
    assert model.expert_path(192) == ("dense", None)
    small = HybridForCausalLM(HybridConfig.tiny(
        pattern="de", linear_value_head_dim=64, linear_key_head_dim=16,
        linear_num_key_heads=2, linear_num_value_heads=8))
    assert small.state_path(None, 2) == "xla"


def test_the_gauges_at_the_published_sizes():
    """What the engine would keep for this chip's share at 192 slots, from
    shapes alone (nothing is allocated): pools [2, pages, 128, 256] for the
    3 attention layers only, 18 state leaves for the 9 DeltaNet layers only,
    3,708,813,312 B of them, 2,929,374,400 parameters held."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    model, _ = program.build_model(cfg)
    assert model.cfg.kinds == ref.pattern(cfg) == "dededeae" * 3
    assert model.cfg.rope_theta == 1e7 and model.cfg.delta_chunk_size == 64
    assert model.cfg.partial_rotary_factor == 0.25
    pools, _ = jax.eval_shape(lambda: model.alloc_paged_caches(1, 256, 128))
    assert len(pools) == 3
    assert all(a.shape == (2, 2, 128, 256) and a.dtype == jnp.bfloat16
               for pool in pools for a in pool)
    state = jax.eval_shape(lambda: model.alloc_slot_state(192))
    assert len(state) == 9
    assert [(a.shape, a.dtype) for a in state[0]] == [
        ((192, 3, 8192), jnp.bfloat16), ((192, 32, 128, 128), jnp.float32)]
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert held == 192 * 9 * families.of(cfg).slot_state_bytes(cfg)
    assert held == 3_708_813_312
    assert sum(int(np.prod(p.value.shape))
               for _, p in model.named_parameters()) == 2_929_374_400
