"""A hybrid Mamba-2 / attention / routed-expert model (``nemotron_h``'s
layout) through the program's normal paths, held to the benchmark family's
plain reference (``benchmarks/families/nemotron_h.py`` ->
``refs/nemotron_h.py``: the recurrence token by token, no cache, no kernel) at
a tiny size on the CPU, in float32, on seeded random weights
(``benchmarks/weights.py``).

Tolerances, and why: program and reference compute the same float32
mathematics in different orders (a chunked scan and a per-slot state against a
token-by-token recurrence, sorted or batched expert products against a scan
over experts), so logits of size ~1 agree to a few units of float32 rounding
over five blocks: 3e-5 absolute, and a served token lies under the reference's
best by no more.
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
from benchmarks.refs import nemotron_h as ref  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.inference.generation import GenerationConfig  # noqa: E402
from paddle_tpu.models.hybrid_lm import (HybridConfig,  # noqa: E402
                                         HybridForCausalLM, Mamba2Mixer,
                                         ssd_chunked)
from paddle_tpu.ops.pallas.ssm import (pack_state,  # noqa: E402
                                       ssm_state_update,
                                       ssm_state_update_xla, unpack_state)
from paddle_tpu.base import LazyGuard  # noqa: E402
from paddle_tpu.parallel.moe import (MoELayer, blocked_expert_rows,  # noqa: E402
                                     expert_ffn, expert_step_rows,
                                     inverse_permutation,
                                     xla_grouped_matmul)

TOL = 3e-5
SEED = 11
ENGINE = dict(max_batch=2, max_len=96, page_size=16, num_pages=16)


def tiny_config(**kw):
    """The Nemotron configuration's file with every size shrunk (the state
    size stays 128: the kernel's lane width) and five blocks ``MEM*E``: the
    program is built from it exactly as ``benchmarks/program.build_engine``
    builds the cell's. Experts 2-5 of 8 are held, so a share that does not
    start at 0 is what every test runs."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.serve-1chip.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_hidden_layers=5,
               hybrid_override_pattern="MEM*E", mamba_num_heads=8,
               mamba_head_dim=8, n_groups=2, chunk_size=16,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               router_width=8, n_routed_experts=4, first_expert_held=2,
               num_experts_per_tok=3, moe_intermediate_size=48,
               moe_shared_expert_intermediate_size=96, vocab_size=256,
               dtype="float32", engine=ENGINE)
    cfg.update(kw)
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


# -- (a) the Mamba-2 mixer ----------------------------------------------------

def _mixer(seed=0):
    pt.seed(seed)
    cfg = HybridConfig.tiny(pattern="M")
    mixer = Mamba2Mixer(cfg)
    key = jax.random.key(seed + 1)
    for i, (name, p) in enumerate(mixer.named_parameters()):
        if name in ("conv_bias", "dt_bias", "A_log", "D", "norm_weight",
                    "conv_weight"):
            p.value = p.value + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), p.value.shape)
    return cfg, mixer


def _mixer_leaves(mixer):
    return {"in_proj": mixer.in_proj, "conv": mixer.conv_weight,
            "conv_bias": mixer.conv_bias, "dt_bias": mixer.dt_bias,
            "A_log": mixer.A_log, "D": mixer.D,
            "gate_norm": mixer.norm_weight, "out_proj": mixer.out_proj}


def _mixer_model(cfg):
    return dict(mamba_num_heads=cfg.mamba_num_heads,
                mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.n_groups,
                ssm_state_size=cfg.ssm_state_size,
                conv_kernel=cfg.conv_kernel, rms_norm_eps=cfg.rms_norm_eps)


@pytest.mark.parametrize("length", [16, 37, 64])
def test_the_chunked_scan_the_recurrence_and_the_reference_agree(length):
    """``ssd_chunked`` (products inside a chunk of 16), the recurrence a
    token at a time through ``ssm_state_update_xla`` and the reference's
    ``lax.scan`` give one output; the two program forms leave one state."""
    cfg, mixer = _mixer()
    u = jax.random.normal(jax.random.key(5), (2, length, cfg.hidden_size))
    got, tail, state = mixer._sequence(u)
    with jax.default_matmul_precision("highest"):
        want = ref.mamba(_mixer_model(cfg), _mixer_leaves(mixer), u)
    assert np.abs(np.asarray(want)).max() > 0.3
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL
    slot = mixer.alloc_slot_state(2)
    steps = []
    for t in range(length):
        y, slot = mixer.decode(u[:, t:t + 1], slot)
        steps.append(y)
    assert np.abs(np.asarray(jnp.concatenate(steps, 1))
                  - np.asarray(want)).max() < TOL
    assert np.abs(np.asarray(unpack_state(slot[1], cfg.mamba_num_heads))
                  - np.asarray(state)).max() < TOL
    assert np.abs(np.asarray(slot[0]) - np.asarray(tail)).max() < 1e-6


def test_padding_to_a_bucket_does_not_advance_the_state():
    """One prompt of 21 tokens padded to 32 and to 48: the slot's state is
    the state after token 20 both times (what follows takes a step of 0 and
    the convolution's window is cut at the true end) and equals the unpadded
    sequence's."""
    cfg, mixer = _mixer(1)
    u = jax.random.normal(jax.random.key(6), (1, 48, cfg.hidden_size))
    _, tail, state = mixer._sequence(u[:, :21])
    for bucket in (32, 48):
        _, (conv, ssm) = mixer.prefill(u[:, :bucket],
                                       mixer.alloc_slot_state(3), 1,
                                       jnp.int32(20))
        assert np.abs(np.asarray(unpack_state(ssm[1], cfg.mamba_num_heads))
                      - np.asarray(state[0])).max() < 1e-6
        assert np.abs(np.asarray(conv[1]) - np.asarray(tail[0])).max() < 1e-6
        assert not np.asarray(ssm[0]).any() and not np.asarray(ssm[2]).any()


def test_the_scan_starts_from_zero_and_a_zero_step_moves_nothing():
    x = jax.random.normal(jax.random.key(0), (1, 32, 4, 8))
    b = jax.random.normal(jax.random.key(1), (1, 32, 2, 128))
    c = jax.random.normal(jax.random.key(2), (1, 32, 2, 128))
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(3), (1, 32, 4)))
    a = -jnp.exp(jnp.linspace(-1.0, 1.0, 4))
    _, full = ssd_chunked(x, dt, a, b, c, 16)
    cut = dt.at[:, 20:].set(0.0)
    y, frozen = ssd_chunked(x, cut, a, b, c, 16)
    _, short = ssd_chunked(x[:, :32], cut.at[:, 16:].set(0.0), a, b, c, 16)
    assert np.isfinite(np.asarray(y)).all()
    assert np.abs(np.asarray(frozen) - np.asarray(full)).max() > 1e-3
    # the state after 20 tokens by the kernel's twin, a token at a time
    s = pack_state(jnp.zeros((1, 4, 8, 128)), 2)
    for t in range(20):
        _, s = ssm_state_update_xla(s, x[:, t], dt[:, t], a, b[:, t], c[:, t])
    s = unpack_state(s, 4)
    assert np.abs(np.asarray(frozen) - np.asarray(s)).max() < TOL
    assert np.abs(np.asarray(short) - np.asarray(s)).max() > 1e-3


@pytest.mark.parametrize("shape", [(3, 8, 8, 128, 2), (2, 16, 64, 128, 8),
                                   (1, 64, 64, 128, 8)],
                         ids=["small", "two-blocks", "published"])
def test_the_kernel_equals_its_twin_in_interpret_mode(shape):
    B, H, P, N, G = shape
    k = jax.random.split(jax.random.key(B), 6)
    state = pack_state(jax.random.normal(k[0], (B, H, P, N)), G)
    x = jax.random.normal(k[1], (B, H, P)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[2], (B, H)))
    a = -jnp.exp(jax.random.normal(k[3], (H,)) * 0.2)
    b, c = (jax.random.normal(k[4], (B, G, N)),
            jax.random.normal(k[5], (B, G, N)))
    y0, s0 = ssm_state_update_xla(state, x, dt, a, b, c)
    y1, s1 = ssm_state_update(state, x, dt, a, b, c, interpret=True)
    assert np.abs(np.asarray(y0) - np.asarray(y1)).max() < 1e-4
    assert np.abs(np.asarray(s0) - np.asarray(s1)).max() < 1e-5


def test_the_kernel_updates_its_state_operand_in_place():
    """The state is aliased to the second result (``input_output_aliases``
    of the one ``pallas_call``), and a jitted step that donates its state
    keeps one buffer of it: the lowered program marks the operand as the
    result's buffer."""
    state = pack_state(jnp.zeros((2, 8, 8, 128)), 2)
    args = (state, jnp.ones((2, 8, 8)), jnp.ones((2, 8)), -jnp.ones((8,)),
            jnp.ones((2, 2, 128)), jnp.ones((2, 2, 128)))
    fn = lambda *a: ssm_state_update(*a, interpret=True)

    def calls(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                calls(sub, out)
        return out
    (call,) = calls(jax.make_jaxpr(fn)(*args).jaxpr, [])
    assert tuple(call.params["input_output_aliases"]) == ((4, 1),)
    assert call.invars[4].aval.shape == call.outvars[1].aval.shape == \
        state.shape
    text = jax.jit(fn, donate_argnums=(0,)).lower(*args).as_text()
    assert "tf.aliasing_output = 1" in text or "jax.buffer_donor" in text


# -- (b) MoELayer: a share of the experts, non-gated experts -------------------

def _set(layer, **values):
    params = dict(layer.named_parameters())
    for name, value in values.items():
        params[name.replace("__", ".")].value = jnp.asarray(value)


def _routed(held=None, act="relu2", seed=3):
    """The initialiser's 0.02 through a square would leave outputs of 1e-3:
    expert weights of ~0.15 give routed outputs of order 1."""
    pt.seed(seed)
    layer = MoELayer(32, 48, 8, top_k=3, capacity_factor=None,
                     dtype="float32", scoring="sigmoid", select_bias=True,
                     norm_topk_prob=True, routed_scaling_factor=2.5,
                     experts_held=held, expert_act=act)
    first = "experts__w_up" if act == "relu2" else "experts__w_gate_up"
    _set(layer,
         gate_weight=0.5 * jax.random.normal(jax.random.key(seed), (32, 8)),
         gate_bias=0.02 * jax.random.normal(jax.random.key(seed + 1), (8,)),
         experts__w_down=8.0 * layer.experts.w_down,
         **{first: 8.0 * layer.experts.w_in})
    return layer


def _share(whole, first, count):
    part = _routed((first, count))
    _set(part, gate_weight=whole.gate_weight, gate_bias=whole.gate_bias,
         experts__w_up=whole.experts.w_up[first:first + count],
         experts__w_down=whole.experts.w_down[first:first + count])
    return part


def _ref_experts(layer, x, first=0, held=8):
    """The reference's routed output for the share (first, held) of
    ``layer``'s 8 experts: no shared expert (one of zeros)."""
    model = dict(num_experts_per_tok=3, norm_topk_prob=True,
                 routed_scaling_factor=2.5, router_width=8,
                 first_expert_held=first, n_routed_experts=held)
    zeros = jnp.zeros((x.shape[-1], 4))
    w = {"router": layer.gate_weight, "router_bias": layer.gate_bias,
         "experts_up": layer.experts.w_up, "experts_down": layer.experts.w_down,
         "shared_up": zeros, "shared_down": zeros.T}
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.experts(model, w, x))


@pytest.mark.parametrize("rows,path", [(6, "dense"), (320, "sorted"),
                                       (24, "train")])
def test_the_two_halves_add_up_to_the_whole_layer_and_the_reference(rows,
                                                                    path):
    """Experts 0-2 on one chip and 3-7 on another, each routing over all
    eight: their routed outputs add up to the uncut layer's and to the uncut
    reference's; each half equals the reference given that share; ``load``
    is over the held experts and the two loads make rows x top-k."""
    whole = _routed()
    halves = [_share(whole, 0, 3), _share(whole, 3, 5)]
    x = jax.random.normal(jax.random.key(9), (1, rows, 32))
    for layer in [whole] + halves:
        layer.train() if path == "train" else layer.eval()

    def run(layer):
        if path == "train":
            return layer(x)[0], None
        return layer.forward_inference(x)
    want, load = run(whole)
    parts = [run(h) for h in halves]
    total = parts[0][0] + parts[1][0]
    assert np.abs(np.asarray(want)).max() > 0.05
    assert np.abs(np.asarray(total) - np.asarray(want)).max() < TOL
    assert np.abs(np.asarray(want) - _ref_experts(whole, x)).max() < TOL
    for (first, count), (got, _) in zip(((0, 3), (3, 5)), parts):
        assert np.abs(np.asarray(got) - _ref_experts(
            halves[first > 0], x, first, count)).max() < TOL
    if path != "train":
        assert [p[1].shape for p in parts] == [(3,), (5,)]
        assert np.array_equal(np.concatenate([np.asarray(p[1])
                                              for p in parts]),
                              np.asarray(load))
        assert int(load.sum()) == rows * 3


def test_relu2_experts_have_no_gate_and_swiglu_ones_are_as_they_were():
    layer = _routed()
    names = [n for n, _ in layer.named_parameters()]
    assert "experts.w_up" in names and "experts.w_gate_up" not in names
    assert layer.experts.w_up.shape == (8, 32, 48)
    x = jax.random.normal(jax.random.key(2), (8, 5, 32))
    got = layer.experts(x)
    want = jnp.einsum("ecf,efd->ecd", jnp.square(jax.nn.relu(jnp.einsum(
        "ecd,edf->ecf", x, layer.experts.w_up))), layer.experts.w_down)
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    gated = _routed(act="swiglu")
    assert gated.experts.w_gate_up.shape == (8, 32, 96)
    up = lambda a, w: a @ w
    assert np.allclose(
        np.asarray(expert_ffn(x[0], gated.experts.w_gate_up[0],
                              gated.experts.w_down[0], "swiglu", up, up)),
        np.asarray((jax.nn.silu(x[0] @ gated.experts.w_gate_up[0, :, :48])
                    * (x[0] @ gated.experts.w_gate_up[0, :, 48:]))
                   @ gated.experts.w_down[0]), atol=1e-6)


@pytest.mark.parametrize("block", [16, 32, 64])
@pytest.mark.parametrize("act", ["relu2", "swiglu"])
def test_blocked_expert_rows_equal_the_ragged_products(act, block):
    """Rows sorted by expert, through a loop over steps of ``block`` rows of
    one expert against ``ragged_dot``: an expert with no row, one with less
    than a step, one with exactly two steps of 16, one that ends inside a
    step, one that needs three steps of 64 and more of the others, and rows
    past every expert's, which come back 0. ``m`` is no whole number of
    steps and the rows are NOT padded: the last expert's last step is moved
    up to end with the array and writes only its own rows."""
    load = jnp.array([0, 5, 32, 37, 0, 150, 1], jnp.int32)
    m, d, f = int(load.sum()) + 9, 24, 40
    assert m % block and int(load.max()) > 2 * block
    k = jax.random.split(jax.random.key(4), 3)
    xs = jax.random.normal(k[0], (m, d))
    w_in = jax.random.normal(k[1], (7, d, f * (2 if act == "swiglu" else 1)))
    w_dn = jax.random.normal(k[2], (7, f, d))
    run = jax.jit(lambda *a: blocked_expert_rows(*a, act, load, block))
    got = run(xs, w_in, w_dn)
    assert "pad" not in str(jax.make_jaxpr(run)(xs, w_in, w_dn))
    gmm = lambda a, w: xla_grouped_matmul(a, w, load)
    want = expert_ffn(xs, w_in, w_dn, act, gmm, gmm)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert np.abs(np.asarray(want)).max() > 1.0
    assert np.allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                       atol=1e-4)
    assert not np.asarray(got[int(load.sum()):]).any()
    # every row an expert's: the last step ends exactly with the array
    full = jnp.array([0, 5, 32, 37, 0, 150, 10], jnp.int32)
    got = jax.jit(lambda *a: blocked_expert_rows(*a, act, full, block))(
        xs, w_in, w_dn)
    gmm = lambda a, w: xla_grouped_matmul(a, w, full)
    assert np.allclose(np.asarray(got), np.asarray(
        expert_ffn(xs, w_in, w_dn, act, gmm, gmm)), rtol=1e-5, atol=1e-4)


def test_the_rows_come_back_by_a_gather_equal_to_the_scatter():
    """``ys[inverse_permutation(order)]`` is ``zeros.at[order].set(ys)`` for
    the stable argsort of choices of which some belong to no held expert
    (they sort behind every expert's run): the same numbers in the same
    places, as a gather."""
    ids = jax.random.randint(jax.random.key(0), (333,), 0, 7)   # 6: none
    order = jnp.argsort(ids, stable=True).astype(jnp.int32)
    assert int((ids == 6).sum()) > 20
    ys = jax.random.normal(jax.random.key(1), (333, 5))
    inv = inverse_permutation(order)
    assert np.array_equal(np.asarray(inv[order]), np.arange(333))
    assert np.array_equal(np.asarray(ys[inv]),
                          np.asarray(jnp.zeros_like(ys).at[order].set(ys)))
    text = str(jax.make_jaxpr(lambda y, o: y[inverse_permutation(o)])(
        ys, order))
    import re
    assert re.findall(r"(\w+\[[\d,]*\]) = scatter", text) == ["i32[333]"]


@pytest.mark.parametrize("shape,step", [
    # (t, k, router width, dtype): the three routed cells' prompts
    ((768, 6, 128, "bfloat16"), 128),       # Nemotron: 36 rows an expert
    ((1536, 6, 128, "bfloat16"), 192),      # 72
    ((3072, 6, 128, "bfloat16"), 256),      # 144: the ridge
    ((640, 4, 64, "bfloat16"), 128),        # GLM: 40
    ((1152, 4, 64, "bfloat16"), 192),       # 72
    ((1920, 4, 64, "bfloat16"), 256),       # 120
    ((256, 1, 16, "bfloat16"), 64),         # ZAYA: 16
    ((512, 1, 16, "bfloat16"), 64),         # 32
    ((1024, 1, 16, "bfloat16"), 128),       # 64
    # the edges: fewer expected rows than the least step, more than the
    # largest, and float32, whose ridge lies at twice the rows
    ((241, 1, 64, "bfloat16"), 64),
    ((4096, 8, 8, "bfloat16"), 256),
    ((4096, 8, 8, "float32"), 512),
    ((300, 3, 8, "float32"), 256),
])
def test_a_step_holds_twice_the_rows_an_expert_is_expected_to_get(shape,
                                                                  step):
    t, k, e, dtype = shape
    got = expert_step_rows(t, k, e, dtype)
    assert got == step and got % 64 == 0
    assert got >= min(2 * t * k / e, 120 * jnp.dtype(dtype).itemsize)


def test_a_prompts_rows_take_the_loop_at_every_width():
    """More rows than ``DENSE_ROWS`` are sorted and run the loop over an
    expert's rows whatever the experts' widths (Nemotron's 2688 x 1856, which
    ``ragged_dot`` tiles 128^3, and OLMoE's, GLM's and ZAYA's, where the
    loop read 1.5 to 1.9 times faster): no ``ragged_dot`` is left on the
    inference path, and a call's step follows its rows. A layer of widths
    that are not whole 256-lane tiles, half its experts held, agrees with
    the reference on the sorted path."""
    for d, f in ((2688, 1856), (2048, 1024), (2048, 1536), (2048, 2048)):
        with LazyGuard():
            wide = MoELayer(d, f, 64, top_k=4, capacity_factor=None,
                            dtype="bfloat16")
        assert wide.inference_path(64) == ("dense", None)
        assert wide.inference_path(640) == ("loop", 128)
        assert wide.inference_path(1920, jnp.float32) == ("loop", 256)
    pt.seed(5)
    layer = MoELayer(384, 320, 8, top_k=3, capacity_factor=None,
                     dtype="float32", scoring="sigmoid", select_bias=True,
                     norm_topk_prob=True, routed_scaling_factor=2.5,
                     experts_held=(2, 4), expert_act="relu2").eval()
    _set(layer, gate_weight=0.2 * jax.random.normal(jax.random.key(1),
                                                    (384, 8)),
         experts__w_up=4.0 * layer.experts.w_up,
         experts__w_down=4.0 * layer.experts.w_down)
    x = jax.random.normal(jax.random.key(2), (1, 300, 384))
    got, load = layer.forward_inference(x)
    text = str(jax.make_jaxpr(layer.forward_inference)(x))
    assert "while" in text and "ragged_dot" not in text
    assert layer.inference_path(300) == ("loop", 256)
    want = _ref_experts(layer, x, 2, 4)
    assert np.abs(want).max() > 0.3 and int(load.sum()) > 300
    assert np.abs(np.asarray(got) - want).max() < 1e-4


def test_the_probe_times_both_forms_and_leaves_the_layer_as_it_was(capsys):
    """``tools/expert_path_probe.py`` at a tiny size on the CPU: one line a
    length with the loads, the steps they need and the rule's choice (what
    the engine writes into ``build_log``), a variant for ``ragged_dot`` and
    one a step; no device plane in a CPU trace, so every time is null; the
    functions it steers the layer by are back in place after it."""
    import importlib.util
    from paddle_tpu.parallel import moe
    spec = importlib.util.spec_from_file_location(
        "expert_path_probe", os.path.join(ROOT, "tools",
                                          "expert_path_probe.py"))
    probe = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe)
    before = (moe.expert_step_rows, moe.blocked_expert_rows)
    probe.main("--t 300,500 --k 3 --e 8 --held 4 --d 64 --f 48 --act relu2 "
               "--dtype float32 --steps 16,64 --skew 0.2 --iters 1".split())
    assert (moe.expert_step_rows, moe.blocked_expert_rows) == before
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["t"] for l in lines] == [300, 500]
    for line in lines:
        assert line["expert_path"] == "loop"
        assert line["expert_step_rows"] == expert_step_rows(
            line["t"], 3, 8, "float32")
        assert list(line["ms"]) == ["rule", "ragged", "loop_16", "loop_64"]
        assert line["device"] == "cpu" and not any(line["ms"].values())
        load = line["load"]
        assert 0 < load["sum"] < 3 * line["t"]      # half the experts held
        assert load["steps"]["16"] > load["steps"]["64"] >= 4


@pytest.mark.parametrize("kw,word", [
    (dict(experts_held=(0, 4)), "experts_held"),
    (dict(expert_act="relu2"), "expert_act"),
])
def test_capacity_and_expert_parallel_paths_refuse_both_by_name(kw, word):
    with pytest.raises(ValueError, match=word):
        MoELayer(16, 8, 8, top_k=2, capacity_factor=1.25, **kw)
    layer = MoELayer(16, 8, 8, top_k=2, capacity_factor=None,
                     dtype="float32", **kw)
    assert not layer._gshard_router     # what the ep paths check, by name
    import inspect
    assert word in inspect.getsource(MoELayer.forward)
    with pytest.raises(ValueError, match="experts_held"):
        MoELayer(16, 8, 8, capacity_factor=None, experts_held=(6, 4))
    with pytest.raises(ValueError, match="expert_act"):
        MoELayer(16, 8, 8, capacity_factor=None, expert_act="gelu")


# the text of the DEFAULT layer's programs as the parent commit (PR 32)
# traced them, hashed: the router's three variants on the dense and sorted
# inference paths (4 and 320 rows), the dropless training path and its
# gradient, and the capacity path. ``experts_held`` and ``expert_act`` at
# their defaults must leave every one of them as it was, equation for
# equation. (jax 0.9.0's printing: a jax upgrade re-pins them from the
# commit before it.)
PARENT_JAXPRS = {
    "gshard.infer4": "ceb4f45443e080c5",
    "glm.infer4": "4c1df47df0307047",
    "capacity.train": "362adbfaf6c2cb73",
}
# The dropless TRAINING path is PR 43's: the router's weight multiplies the
# sorted rows' hidden activation before the down product, the rows come back
# by a gather and a sum alone, the index work is two sorts (name: (PR 43's,
# PR 32's = the parent's)). Outputs and gradients against the formulation
# it replaces: tests/test_moe_dropless_routing.py. The inference programs
# and the capacity path are untouched.
TRAINING_JAXPRS = {
    "gshard.train": ("ebf8de4d24e0a0e3", "8b10471cbf1c1896"),
    "gshard.grad": ("c89045330665293c", "aa6b84a2d69af151"),
    "glm.train": ("4cccc584109c6acd", "129fe7319009d631"),
    "glm.grad": ("c9238ca234a33932", "53a89d1ffdd80f3e"),
    "zaya.train": ("f244ee126c3b33f8", "ce7271d7d7478edc"),
    "zaya.grad": ("72afd7bca7ed69ff", "010f90f8331694ce"),
}
# The ONE program of the default layer that PR 33 did change: ``DENSE_ROWS``
# went 128 -> 240, so a call of 129-240 rows whose choices outnumber the
# experts runs every expert over every row where the parent sorted the rows.
# No cell of the parent's makes such a call (128-token pages, 32 / 64 / 128
# slots); one that comes to (a 192-slot tick, a 192-token prompt) runs the
# program pinned here, not the parent's (name: (PR 33's, the parent's)).
CHANGED_JAXPRS = {
    "gshard.infer192": ("cb623e2962d91b43", "4add2fc62f354902"),
    "glm.infer192": ("8adb0262f0108aa2", "21591b9956195c71"),
    "zaya.infer192": ("6995382409854b85", "5c84fd9f810d6450"),
}
# The sorted inference path is PR 37's: the loop over an expert's rows at
# every width (the default layer's widths took ``ragged_dot`` before) and the
# rows back by gathers (name: (PR 37's, PR 33's = the parent's)). The dense
# path (4 and 192 rows: what a decode tick runs) is untouched.
SORTED_JAXPRS = {
    "gshard.infer320": ("af1260f576b70290", "3a8fb23ca48c1280"),
    "glm.infer320": ("54c1856b5997cf0e", "84954e4197067162"),
    "zaya.infer320": ("af1bcec1762b81ec", "f83a19fae139d987"),
    # 4 rows x top-1 are expected on 4 of the 8 experts: fewer than are
    # held, so such a call was sorted before and is now
    "zaya.infer4": ("ee712a2ba9af1256", "ecc687bcccd8f601"),
}
ROUTERS = {
    "gshard": {},
    "glm": dict(scoring="sigmoid", select_bias=True, norm_topk_prob=True,
                routed_scaling_factor=1.8),
    "zaya": dict(top_k=1, router="mlp", router_hidden_size=16,
                 skip_choice=True),
}


def _sha(fn, *a):
    return hashlib.sha256(
        str(jax.make_jaxpr(fn)(*a)).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", list(ROUTERS) + ["capacity"])
def test_the_default_layers_jaxpr_is_the_parents(name):
    pt.seed(0)
    if name == "capacity":
        cap = MoELayer(32, 48, 8, top_k=2, capacity_factor=1.25,
                       dtype="float32")
        assert _sha(lambda x: cap(x), jnp.ones((2, 8, 32))) == \
            PARENT_JAXPRS["capacity.train"]
        return
    kw = dict(dict(top_k=2), **ROUTERS[name])
    layer = MoELayer(32, 48, 8, capacity_factor=None, dtype="float32", **kw)
    state = ((lambda x: layer.router_state(x))
             if kw.get("router") == "mlp" else (lambda x: None))
    layer.eval()
    for rows in (4, 320):
        text = str(jax.make_jaxpr(lambda x: layer.forward_inference(
            x, state(x)))(jnp.ones((1, rows, 32))))
        sha = hashlib.sha256(text.encode()).hexdigest()[:16]
        if layer.inference_path(rows)[0] == "dense":
            assert sha == PARENT_JAXPRS[f"{name}.infer{rows}"]
            continue
        ours, parents = SORTED_JAXPRS[f"{name}.infer{rows}"]
        assert sha == ours != parents
        assert "ragged_dot" not in text and "while" in text
    ours, parents = CHANGED_JAXPRS[f"{name}.infer192"]
    text = str(jax.make_jaxpr(
        lambda x: layer.forward_inference(x, state(x)))(jnp.ones((1, 192, 32))))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == ours != parents
    assert "ragged_dot" not in text
    layer.train()
    x = jnp.ones((2, 8, 32))
    ours, parents = TRAINING_JAXPRS[f"{name}.train"]
    assert _sha(lambda x: layer(x, state(x)), x) == ours != parents
    ours, parents = TRAINING_JAXPRS[f"{name}.grad"]
    assert _sha(jax.grad(lambda x: layer(x, state(x))[0].sum()), x) == \
        ours != parents


def test_a_stage_builds_a_prefix_of_the_pattern():
    """``num_hidden_layers`` cuts the pattern (a pipeline stage holds the
    first blocks; the published pattern stays whole in the configuration)."""
    whole = "MEMEM*EMEMEM*EME"
    cfg = HybridConfig.tiny(pattern=whole, num_hidden_layers=6)
    assert cfg.kinds == "MEMEM*" and HybridConfig.tiny().kinds == "MEM*E"
    model = jax.eval_shape(lambda: HybridForCausalLM(cfg).alloc_slot_state(2))
    assert len(model) == 3          # one state entry a Mamba-2 block built
    for bad in (dict(num_hidden_layers=0), dict(num_hidden_layers=17),
                dict(pattern="MEX")):
        with pytest.raises(ValueError):
            HybridConfig.tiny(**dict(dict(pattern=whole), **bad))


# -- (c) the hybrid model through the engine -----------------------------------

@pytest.mark.parametrize("mode", ["eval", "train"])
def test_forward_logits_match_the_reference(hybrid, mode):
    """The whole-sequence forward (the chunked scan; ``forward_inference``
    in eval mode, the dropless path otherwise), experts 2-5 of 8 held."""
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


def test_prefill_then_ticks_through_the_slot_state_equal_the_full_forward(
        hybrid):
    """A prompt of 21 tokens padded to TWO buckets leaves the same state and
    the same next-token logits; then 15 decode ticks through the pages and
    the slot state read the reference's logits at every position."""
    _, model, reference = hybrid
    ids = _ids(36, 1)
    want = reference(ids)
    pools, tables = model.alloc_paged_caches(2, 64, 16)
    seen = []
    for bucket in (32, 48):
        padded = jnp.zeros((1, bucket), jnp.int32).at[0, :21].set(ids[:21])
        h, filled, state = model.prefill_paged(
            padded, pools, tables[1:2], model.alloc_slot_state(2), 1,
            jnp.int32(20))
        logits = np.asarray(model.logits(h[0, 20]))
        assert np.abs(logits - want[20]).max() < TOL
        seen.append((logits, state))
    assert np.abs(seen[0][0] - seen[1][0]).max() < 1e-6
    for a, b in zip(jax.tree.leaves(seen[0][1]), jax.tree.leaves(seen[1][1])):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-6
    pos = jnp.array([0, 21], jnp.int32)
    for t in range(21, 36):
        h, filled, state, counts = model.decode_step_paged(
            jnp.array([0, ids[t]], jnp.int32), pos, filled, tables, state)
        assert np.abs(np.asarray(model.logits(h[1, 0])) - want[t]).max() < TOL
        pos = pos + jnp.array([0, 1], jnp.int32)
    assert int(counts[0]) == 2 * 3 * 2 and 0 < int(counts[2]) <= int(counts[0])


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
    """Prefill (chunked scan), then decode through the pages of the ONE
    attention layer and the state of the two Mamba-2 layers: every served
    token has the reference's best logit to within TOL."""
    _, _, reference = hybrid
    eng, prompts, outs = served
    assert all(len(t) == 10 for t in outs)
    assert _gaps(reference, prompts, outs).max() < TOL
    stats = eng.stats()
    assert stats["active"] == 0 and stats["free_pages"] == ENGINE["num_pages"]


@pytest.mark.parametrize("kind", ["hybrid", "routed-gqa", "llama"])
def test_a_prefill_programs_build_log_row_says_how_its_experts_run(hybrid,
                                                                   kind):
    """``build_log``'s row of a prefill program (and the
    ``compile::prefill_paged`` span with it) carries ``expert_path`` and,
    where the rows are sorted, ``expert_step_rows``: what the model's
    ``expert_path(rows)`` says, static per program. A bucket of at most
    ``DENSE_ROWS`` rows runs every held expert over every row; a model
    without routed layers leaves both keys out."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.moe_lm import MoEConfig, MoEForCausalLM
    model = {
        "hybrid": lambda: hybrid[1],
        "routed-gqa": lambda: MoEForCausalLM(MoEConfig.tiny(
            capacity_factor=None, dtype="float32")).eval(),
        "llama": lambda: LlamaForCausalLM(
            LlamaConfig.tiny(dtype="float32")).eval(),
    }[kind]()
    eng = _engine(model, max_len=288, num_pages=40)
    for n in (20, 250):
        eng.submit(_ids(n, n) % 200, max_new_tokens=2)
    eng.run()
    rows = {r["bucket"]: r for r in eng.build_log
            if r["name"] == "prefill_paged"}
    assert sorted(rows) == [32, 256]
    if kind == "llama":
        assert not hasattr(model, "expert_path")
        assert all("expert_path" not in r and "expert_step_rows" not in r
                   for r in eng.build_log)
        return
    assert rows[32]["expert_path"] == "dense"
    assert "expert_step_rows" not in rows[32]
    layer = next(l for l in model.sublayers() if isinstance(l, MoELayer))
    step = expert_step_rows(256, layer.top_k, layer.num_experts, "float32")
    assert rows[256]["expert_path"] == "loop"
    assert rows[256]["expert_step_rows"] == step == model.expert_path(256)[1]
    assert all("expert_path" not in r for r in eng.build_log
               if not r["name"].startswith("prefill"))


def test_a_request_in_a_reused_slot_equals_itself_in_a_fresh_engine(hybrid,
                                                                    served):
    """The last request was admitted into a slot another request had left
    its state in: its prefill overwrites the slot's state, so it reads what
    it reads alone in a fresh engine, token for token."""
    _, model, _ = hybrid
    _, prompts, outs = served
    eng = _engine(model)
    rid = eng.submit(prompts[-1], max_new_tokens=10)
    assert np.array_equal(eng.run()[rid], outs[-1])


def test_a_preempted_request_is_rebuilt_by_its_prefill(hybrid):
    """A pool too small for both sequences forces a preemption; the evicted
    request's state is not saved: its re-prefill (prompt + what it had
    generated) writes the slot's state anew."""
    _, model, reference = hybrid
    eng = _engine(model, num_pages=3)
    prompts = [_ids(14, 3), _ids(14, 4)]
    rids = [eng.submit(p, max_new_tokens=20) for p in prompts]
    out = eng.run()
    assert eng.preemptions >= 1
    assert _gaps(reference, prompts, [out[r] for r in rids]).max() < TOL


def test_pools_for_the_attention_layers_and_state_for_the_mamba_layers(
        hybrid, served):
    """One pool entry (the ``*`` block), two state entries (the ``M``
    blocks): a convolution state in the activation dtype and the SSM state in
    float32, every leaf leading with the slot; the gauges are the family's
    counts."""
    cfg, model, _ = hybrid
    eng, _, _ = served
    assert eng.attention_kind == "hybrid"
    assert len(eng.pools) == 1 and len(eng.slot_state) == 2
    kp, vp = eng.pools[0]
    assert kp.shape == vp.shape == (2, ENGINE["num_pages"] + 1, 16, 16)
    for conv, ssm in eng.slot_state:
        assert conv.shape == (2, 3, 64 + 2 * 2 * 128)
        # 8 heads of 8 in 2 groups: 4 heads side by side on a tile's lanes
        assert ssm.shape == (2, 2, 128, 32) and ssm.dtype == jnp.float32
    family, stats = families.of(cfg), eng.stats()
    assert stats["slot_state_bytes"] == 2 * 2 * family.slot_state_bytes(cfg, 4)
    assert stats["kv_bytes_per_token"] == family.kv_bytes_per_token(cfg, 4)
    # at the published sizes, abstractly, in bf16: the issue's two gauges
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron-3-nano-30b-a3b.serve-1chip.json")) as f:
        big = json.load(f)
    model, _ = program.build_model(big)
    state = jax.eval_shape(lambda: model.alloc_slot_state(192))
    assert len(state) == 7 and state[0][1].dtype == jnp.float32
    assert sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(state)) == 2_868_117_504
    pools, _ = jax.eval_shape(lambda: model.alloc_paged_caches(1, 256, 128))
    assert len(pools) == 2 and sum(
        a.shape[0] * a.shape[3] * a.dtype.itemsize
        for entry in pools for a in entry) == 2048


def test_engine_counts_all_choices_and_those_that_fell_on_a_held_expert(
        served):
    """``moe_assignments``: rows x top-3 over the two expert blocks of every
    tick, whoever holds the chosen expert; ``moe_assignments_held``: those
    on experts 2-5; ``moe_peak_load``: the busiest of THOSE."""
    eng, _, _ = served
    stats = eng.stats()
    ticks = stats["attn_paged_ticks"] + stats["attn_dense_ticks"]
    assert stats["moe_assignments"] == ticks * ENGINE["max_batch"] * 3 * 2
    assert 0 < stats["moe_assignments_held"] < stats["moe_assignments"]
    assert stats["moe_assignments_held"] / 4 <= stats["moe_peak_load"] <= \
        stats["moe_assignments_held"]


def test_the_tick_keeps_one_copy_of_the_state_and_donates_it(served):
    """The engine hands the slot state to the tick as a donated argument and
    takes it back: the same leaves, shape for shape."""
    eng, _, _ = served
    args = eng._decode_args(False)
    (run,) = eng._decode_fns.values()
    out = jax.eval_shape(run, *args)
    assert jax.tree.structure(out[4]) == jax.tree.structure(eng.slot_state)
    assert [a.shape for a in jax.tree.leaves(out[4])] == \
        [a.shape for a in jax.tree.leaves(eng.slot_state)]
    text = run.lower(*args).as_text()
    donors = text.count("jax.buffer_donor") + text.count("tf.aliasing_output")
    assert donors >= len(jax.tree.leaves((eng.pools, eng.slot_state)))


@pytest.mark.parametrize("knob,needs", [
    ({"chunked_prefill": True}, "chunked_prefill=True needs a snapshot"),
    ({"prefix_cache": True}, "prefix_cache=True needs a snapshot"),
    ({"spec_k": 2}, "spec_k=2 needs a snapshot"),
])
def test_engine_refuses_by_name_what_needs_a_snapshot_of_the_state(
        hybrid, knob, needs):
    with pytest.raises(ValueError, match=needs):
        _engine(hybrid[1], **knob)


def test_a_long_table_pads_prompts_to_steps_the_engine_derives(
        hybrid, served):
    """A table of more pages than ``MAX_PREFILL_PROGRAMS`` pads a prompt to
    the fewest pages that keep the prefill programs within it (48 pages:
    steps of 2), the pages of the whole width are the request's, and the
    tokens are those of the page-wide engine; a table of 24 pages or fewer
    keeps page-wide widths."""
    from paddle_tpu.inference.serving import MAX_PREFILL_PROGRAMS
    _, model, _ = hybrid
    page_wide, prompts, outs = served
    assert [page_wide._bucket(n) for n in (1, 16, 17, 96)] == [16, 16, 32, 96]
    assert _engine(model, max_len=16 * MAX_PREFILL_PROGRAMS)._bucket(17) == 32
    eng = _engine(model, max_len=16 * 2 * MAX_PREFILL_PROGRAMS)
    assert [eng._bucket(n) for n in (1, 32, 33, 700, 768)] == [32, 32, 64,
                                                               704, 768]
    widths, build = [], eng._prefill_fn
    eng._prefill_fn = lambda b: widths.append(b) or build(b)
    rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    out = eng.run()
    assert widths == [32, 32, 64, 64, 32]
    for r, t in zip(rids, outs):
        assert np.array_equal(out[r], t)
    assert eng.stats()["free_pages"] == ENGINE["num_pages"]
