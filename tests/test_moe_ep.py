"""Expert parallelism as the sixth planner axis (ISSUE 20).

What is pinned here, on the conftest 8-virtual-device CPU mesh:

* ``ParallelConfig`` grows ``ep`` WITHOUT breaking any pre-EP artifact:
  ep=1 plan/config strings are byte-identical to the 5-axis era, the
  parser accepts ``ep`` segments anywhere, and enumeration only offers
  ep on MoE models where it divides both the expert count and dp;
* ``estimate_hbm`` divides expert params/optimizer slots/grads by ep
  and charges the a2a staging buffer — the planner's memory gate knows
  experts shard;
* the acceptance bar: a SKEWED routing histogram fed to
  ``price_config(..., moe_histogram=...)`` RAISES the predicted price
  of an ep config vs uniform routing (entropy-priced all-to-all), and
  the ep-pure census carries real ``all-to-all[ep]`` rows;
* the parity anchor: 4 SGD steps of a dropless MoE layer on an ep=2
  mesh reproduce the ep=1 losses to 1e-4 (bit-exact in practice) with
  routing decisions bit-identical — expert parallelism is an
  execution-plan change, not a model change;
* satellite regression: ``accumulate_steps>1`` keeps grads
  fsdp-sharded through the accumulation scan — the compiled census
  shows ZERO extra all-gather rows vs accumulate_steps=1;
* the grouped matmul (``lax.ragged_dot`` forward and backward since
  PR 28) matches a per-expert Python loop in value and gradient
  (uneven/empty groups, float32 and bfloat16), and a dropless layer's
  step is six ``ragged_dot``s and no ``pallas_call``.

The heavy pieces share ONE compiled dp2_ep2 build (module fixture);
everything else is analytic or tiny-layer compiles — tier-1 budget is
tight (see MEMORY).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu.distributed.auto_parallel import (
    ParallelConfig, enumerate_configs, ep_imbalance, estimate_hbm,
    price_compiled, price_config)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.moe_lm import MoEConfig
from paddle_tpu.parallel import HybridMesh, shard_tensor
from paddle_tpu.parallel.moe import MoELayer


def moe_cfg(**kw):
    base = dict(vocab_size=320, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=48, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2,
                num_experts=4, num_experts_per_tok=2,
                num_shared_experts=1, first_k_dense_replace=1,
                capacity_factor=None, max_position_embeddings=128)
    base.update(kw)
    return MoEConfig(**base)


@pytest.fixture(scope="module")
def priced_ep2():
    """ONE compiled+priced dp2_ep2 MoE config, priced with a SKEWED
    routing histogram (26/2/2/2 → bottleneck imbalance ×1.75), shared
    by the census/pricing/plan tests — the compile is the expensive
    part; repricing the kept build is arithmetic."""
    return price_config(ParallelConfig(dp=2, ep=2), moe_cfg(),
                        devices=jax.devices()[:2], global_batch=4,
                        seq_len=32, check_memory=False, keep_build=True,
                        moe_histogram=[26, 2, 2, 2])


# ---------------------------------------------------------------------------
# config algebra: parse/str/enumerate
# ---------------------------------------------------------------------------

def test_parse_str_roundtrip_ep():
    c = ParallelConfig.parse("dp2_ep2")
    assert (c.dp, c.ep) == (2, 2)
    assert str(c) == "dp2_ep2_tp1_pp1_sep1"
    assert ParallelConfig.parse(str(c)) == c
    # ep composes with fsdp/tp in the string and the parser is
    # order-insensitive
    c2 = ParallelConfig.parse("ep2_dp4_fsdp2_tp2")
    assert (c2.dp, c2.ep, c2.fsdp, c2.tp) == (4, 2, 2, 2)
    assert ParallelConfig.parse(str(c2)) == c2
    # "sep" must never feed the ep matcher
    c3 = ParallelConfig.parse("dp2_sep2")
    assert (c3.sep, c3.ep) == (2, 1)


def test_ep1_strings_byte_identical_to_pre_ep_era():
    """ep=1 artifacts (plan JSON config_str, bench row labels, budget
    keys) must not change under the sixth axis."""
    assert str(ParallelConfig(dp=4, tp=2)) == "dp4_tp2_pp1_sep1"
    assert str(ParallelConfig(fsdp=2, tp=2)) == "dp1_fsdp2_tp2_pp1_sep1"
    # no "_epN" segment ever appears at ep=1 ("sep1" != an ep segment)
    assert "_ep" not in str(ParallelConfig(dp=8))


def test_enumerate_ep_legality():
    cands = enumerate_configs(8, moe_cfg(), global_batch=8, seq_len=64)
    names = {str(c) for c in cands}
    assert "dp4_ep2_tp2_pp1_sep1" in names or \
        any(c.ep == 2 and c.tp == 2 for c in cands)
    # ep divides num_experts (4): ep=8 never offered
    assert not any(c.ep == 8 for c in cands)
    # ep is carved out of dp: ep must divide dp
    assert all(c.dp % c.ep == 0 for c in cands if c.ep > 1)
    # no pp/sep composition with ep yet
    assert not any(c.ep > 1 and (c.pp > 1 or c.sep > 1) for c in cands)
    # dense models never get an ep>1 candidate
    dense = enumerate_configs(
        8, LlamaConfig(vocab_size=320, hidden_size=64,
                       intermediate_size=96, num_hidden_layers=2,
                       num_attention_heads=4, num_key_value_heads=2,
                       max_position_embeddings=128),
        global_batch=8, seq_len=64)
    assert all(c.ep == 1 for c in dense)


# ---------------------------------------------------------------------------
# memory model + entropy pricing
# ---------------------------------------------------------------------------

def test_estimate_hbm_divides_expert_state_by_ep():
    cfg = moe_cfg()
    m1 = estimate_hbm(cfg, ParallelConfig(dp=4), global_batch=8,
                      seq_len=64)
    m2 = estimate_hbm(cfg, ParallelConfig(dp=4, ep=2), global_batch=8,
                      seq_len=64)
    m4 = estimate_hbm(cfg, ParallelConfig(dp=4, ep=4), global_batch=8,
                      seq_len=64)
    # the routed-expert slice halves again from ep=2 to ep=4
    assert m4.detail["expert_params_bytes"] == pytest.approx(
        m2.detail["expert_params_bytes"] / 2)
    assert m4.params_bytes < m2.params_bytes < m1.params_bytes
    assert m4.opt_bytes < m2.opt_bytes < m1.opt_bytes
    # ep>1 charges the dispatch+combine staging buffer; ep=1 doesn't
    assert m1.detail["moe_a2a_staging_bytes"] == 0.0
    assert m2.detail["moe_a2a_staging_bytes"] > 0.0


def test_ep_imbalance_statistic():
    assert ep_imbalance([8, 8, 8, 8], 2) == 1.0
    # shard {26,2} vs {2,2}: max shard share 28/32, x ep=2 -> 1.75
    assert ep_imbalance([26, 2, 2, 2], 2) == pytest.approx(1.75)
    # degenerate inputs clamp to >= 1
    assert ep_imbalance([0, 0], 2) >= 1.0


def test_ep_census_has_real_all_to_all(priced_ep2):
    counts = dict(priced_ep2.graph.census_counts)
    assert counts.get("all-to-all[ep]", 0) > 0, counts
    # plan artifact carries the 6th axis + the ep batch spec
    assert priced_ep2.plan.axes["ep"] == 2
    assert "ep" in str(priced_ep2.plan.batch_spec)


def test_skewed_histogram_raises_predicted_price(priced_ep2):
    """The acceptance bar: same compiled graph, uniform routing priced
    via price_compiled vs the fixture's skewed moe_histogram — the skew
    must COST (ep-axis bandwidth divided by the bottleneck imbalance)
    and say so in the notes."""
    uniform = price_compiled(priced_ep2.build.compiled,
                             mesh=priced_ep2.build.mesh)
    assert priced_ep2.predicted_step_s > uniform.predicted_step_s
    assert any("imbalance" in n for n in priced_ep2.graph.notes)


# ---------------------------------------------------------------------------
# parity anchor: ep=2 is an execution-plan change, not a model change
# ---------------------------------------------------------------------------

def _train4(ep):
    pt.seed(0)
    moe = MoELayer(hidden_size=16, ffn_size=32, num_experts=4, top_k=2,
                   capacity_factor=None)   # dropless: nothing dropped,
    devs = jax.devices()[:2]               # parity can be exact
    hm = (HybridMesh.build(dp=2, ep=2, devices=devs) if ep == 2
          else HybridMesh.build(dp=2, devices=devs))
    x = jnp.asarray(
        np.random.RandomState(0).randn(4, 8, 16).astype(np.float32))
    with hm:
        xs = shard_tensor(x, spec=(P(("dp", "ep"), None, None)
                                   if ep == 2 else P("dp", None, None)))
        params = dict(moe.raw_parameters())

        def loss_fn(p, xb):
            o, a = moe.functional_call(p, xb)
            return jnp.mean(o ** 2) + 0.01 * a

        @jax.jit
        def step(p, xb):
            l, g = jax.value_and_grad(loss_fn)(p, xb)
            return l, jax.tree.map(lambda w, gw: w - 0.1 * gw, p, g)

        losses = []
        for _ in range(4):
            l, params = step(params, xs)
            losses.append(float(l))
        # routing decisions after training: top-k expert ids per token
        logits = x.reshape(-1, 16) @ np.asarray(params["gate_weight"])
        routing = np.asarray(jax.lax.top_k(jnp.asarray(logits), 2)[1])
    return losses, routing


def test_ep2_matches_ep1_over_4_steps():
    l1, r1 = _train4(1)
    l2, r2 = _train4(2)
    np.testing.assert_allclose(l1, l2, atol=1e-4, rtol=0)
    assert (r1 == r2).all(), "routing decisions diverged under ep"


# ---------------------------------------------------------------------------
# satellite: accumulate_steps>1 keeps grads fsdp-sharded
# ---------------------------------------------------------------------------

def _fsdp_census(accum, cfg, splan):
    from paddle_tpu.analysis.collectives import collective_census
    from paddle_tpu.analysis.hlo import parse_hlo
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.trainer import Trainer
    pt.seed(0)
    m = LlamaForCausalLM(cfg)
    tr = Trainer(m, AdamW(learning_rate=1e-3, parameters=m),
                 donate=False, accumulate_steps=accum)
    hm = tr.apply_plan(splan, devices=jax.devices()[:2])
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (4, 17))
    with hm:
        if accum == 1:
            batch = splan.shard_batch(
                {"input_ids": jnp.asarray(ids[:, :-1]),
                 "labels": jnp.asarray(ids[:, 1:])}, hm)
        else:
            # microbatch dim leads; the per-microbatch batch dim shards
            sh = NamedSharding(hm.mesh, P(None, "fsdp", None))
            batch = {k: jax.device_put(
                jnp.asarray(v).reshape(accum, 4 // accum, 16), sh)
                for k, v in (("input_ids", ids[:, :-1]),
                             ("labels", ids[:, 1:]))}
        tr._ensure_built()
        args = (tr.params, tr.opt_state, batch, tr._lr_scalar(),
                tr._key_data())
        compiled = tr._step_jit.lower(*args).compile()
    return collective_census(parse_hlo(compiled.as_text()),
                             mesh=hm.mesh)["counts"]


def test_accumulate_steps_keeps_grads_fsdp_sharded():
    """Regression (ISSUE 20 satellite): the accumulation scan must
    carry grads in their SHARDED (reduce-scattered) form — a naive
    carry would all-gather every microbatch's grads, visible as extra
    all-gather census rows vs accumulate_steps=1."""
    from paddle_tpu.distributed.auto_parallel import plan_for_config
    cfg = LlamaConfig(vocab_size=128, hidden_size=32,
                      intermediate_size=48, num_hidden_layers=1,
                      num_attention_heads=2, num_key_value_heads=1,
                      max_position_embeddings=64)
    splan = plan_for_config(cfg, ParallelConfig(fsdp=2),
                            devices=jax.devices()[:2])
    c1 = _fsdp_census(1, cfg, splan)
    c2 = _fsdp_census(2, cfg, splan)
    gathers = lambda c: sum(v for k, v in c.items()
                            if k.startswith("all-gather"))
    assert gathers(c2) == gathers(c1), (c1, c2)


# ---------------------------------------------------------------------------
# the grouped matmul: lax.ragged_dot forward and backward (PR 28)
# ---------------------------------------------------------------------------

def _loop_grouped_matmul(xs, w, counts):
    """The oracle: one plain float32 matmul per expert over its run of
    rows, in Python. Nothing of ``parallel/moe.py`` in it."""
    xs, w = np.asarray(xs, np.float32), np.asarray(w, np.float32)
    out, row = np.zeros((xs.shape[0], w.shape[2]), np.float32), 0
    for e, c in enumerate(counts):
        out[row:row + c] = xs[row:row + c] @ w[e]
        row += c
    return out


def _loop_grouped_matmul_grads(xs, w, counts, gy):
    """Its gradients for a cotangent ``gy``: dx = gy @ w[e].T and
    dw[e] = x_run.T @ gy_run, run by run."""
    xs, w = np.asarray(xs, np.float32), np.asarray(w, np.float32)
    dx, dw, row = np.zeros_like(xs), np.zeros_like(w), 0
    for e, c in enumerate(counts):
        dx[row:row + c] = gy[row:row + c] @ w[e].T
        dw[e] = xs[row:row + c].T @ gy[row:row + c]
        row += c
    return dx, dw


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("counts", [[12, 12, 12, 12], [10, 3, 22, 13],
                                    [10, 0, 25, 13], [0, 0, 48, 0]],
                         ids=["even", "uneven", "one_empty", "all_in_one"])
def test_grouped_matmul_value_and_grad_match_a_per_expert_loop(counts, dtype,
                                                               tol):
    """Value and both gradients (all in the inputs' dtype) against the
    loop, over the inputs as stored: bf16 inputs are rounded once, on both
    sides alike, and a bf16 result is the loop's float32 one rounded."""
    from paddle_tpu.parallel.moe import grouped_matmul
    rs = np.random.RandomState(0)
    xs = jnp.asarray(rs.randn(48, 16), dtype)
    w = jnp.asarray(rs.randn(4, 16, 24) * 0.1, dtype)
    gy = np.asarray(jnp.asarray(rs.randn(48, 24), dtype), np.float32)
    gs = jnp.asarray(counts, jnp.int32)
    out, vjp = jax.vjp(lambda a, b: grouped_matmul(a, b, gs), xs, w)
    assert out.dtype == xs.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               _loop_grouped_matmul(xs, w, counts),
                               rtol=tol, atol=tol)
    dx, dw = vjp(jnp.asarray(gy, dtype))
    assert (dx.dtype, dw.dtype) == (xs.dtype, w.dtype)
    rx, rw = _loop_grouped_matmul_grads(xs, w, counts, gy)
    np.testing.assert_allclose(np.asarray(dx, np.float32), rx,
                               rtol=tol, atol=tol)
    # a dw entry sums up to 48 products: bf16 rounds the result to 2^-8 of it
    np.testing.assert_allclose(np.asarray(dw, np.float32), rw,
                               rtol=tol, atol=tol * max(1.0, np.abs(rw).max()))


def _live_primitives(jaxpr, counts=None):
    """Primitive name -> count over the equations ``jaxpr``'s outputs
    depend on. ``make_jaxpr`` keeps dead equations, and the backward's
    ``jax.vjp`` traces the forward product once more only to drop it."""
    counts = {} if counts is None else counts
    live = {v for v in jaxpr.outvars if not hasattr(v, "val")}
    for eqn in reversed(jaxpr.eqns):
        if not any(v in live for v in eqn.outvars):
            continue
        live.update(v for v in eqn.invars if not hasattr(v, "val"))
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _live_primitives(sub, counts)
    return counts


def test_a_dropless_layers_step_is_six_ragged_dots_and_no_pallas_call():
    """A routed layer's two expert products are ``lax.ragged_dot`` forward
    (2) and backward (dx and dw of each: 4), on every platform: no
    ``pallas_call`` anywhere in the layer's forward + backward."""
    moe = MoELayer(hidden_size=16, ffn_size=32, num_experts=4, top_k=2,
                   capacity_factor=None)
    x = jnp.asarray(np.random.RandomState(8).randn(1, 16, 16), jnp.float32)

    def loss(p, xb):
        o, a = moe.functional_call(p, xb)
        return jnp.sum(o ** 2) + 0.01 * a
    params = moe.raw_parameters()
    fwd = _live_primitives(jax.make_jaxpr(loss)(params, x).jaxpr)
    both = _live_primitives(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr)
    assert fwd["ragged_dot_general"] == 2
    assert both["ragged_dot_general"] == 2 + 4
    assert "pallas_call" not in fwd and "pallas_call" not in both


# ---------------------------------------------------------------------------
# full matrix (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("axes", [dict(dp=2, ep=2), dict(dp=4, ep=2),
                                  dict(dp=4, ep=4),
                                  dict(dp=2, ep=2, tp=2)])
def test_ep_forward_matrix_matches_replicated(axes):
    """MoE forward across the ep x tp x dp matrix == single-device
    reference (the hybrid/GSPMD-fallback meshes included)."""
    pt.seed(0)
    moe = MoELayer(hidden_size=16, ffn_size=32, num_experts=4, top_k=2,
                   capacity_factor=None)
    x = jnp.asarray(
        np.random.RandomState(2).randn(8, 4, 16).astype(np.float32))
    out_ref, aux_ref = moe(x)
    # ep is carved out of dp, so the device count is dp x tp
    n = axes.get("dp", 1) * axes.get("tp", 1)
    hm = HybridMesh.build(devices=jax.devices()[:n], **axes)
    with hm:
        spec = (P(("dp", "ep"), None, None) if "ep" in hm.mesh.axis_names
                else P("dp", None, None))
        xs = shard_tensor(x, spec=spec)
        out, aux = jax.jit(lambda xb: moe(xb))(xs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(float(aux), float(aux_ref),
                                   rtol=1e-5)
