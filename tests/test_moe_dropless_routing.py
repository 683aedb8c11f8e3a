"""The dropless path moves its routed rows by gathers alone (PR 30), and the
router's weight rides the sorted rows into the down product (PR 43).

``MoELayer._forward_dropless`` sends rows to their experts through
``dispatch_rows`` and brings them back through ``combine_rows``: gathers
whose hand-written transposes are each other (a gather from [t, d]; a gather
and a sum over k). Each sorted row's hidden activation is multiplied by its
router weight in float32 before the down product (``weighted_hidden``: the
product is linear in its rows), the index work is two sorts
(``sorted_assignments``), and ``grouped_matmul``'s backward takes its
products in the activation dtype. The reference kept here is the scatter
formulation the layer had before PR 30 with the weight applied AFTER the
rows come back, under jax's own differentiation, with float32 backward
products: ``zeros.at[order].set``, ``flat[order % t]``, ``bincount``,
``ragged_dot(..., float32)`` and a cast. Same outputs, same gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn import functional as F
from paddle_tpu.parallel.moe import (MoELayer, _aux_loss, combine_rows,
                                     dispatch_rows, inverse_permutation,
                                     permute_scalars, sorted_assignments,
                                     weighted_hidden)

D, FFN, E = 16, 8, 6


def _scatter_reference(layer, params, x):
    """``forward`` as it stood before PR 30, the weight applied after the
    rows are back, on ``params``. The linear router is written out; the MLP
    router's scores are the layer's own (``_route``: not what is tested)."""
    t, k, e = x.shape[0] * x.shape[1], layer.top_k, layer.num_experts
    held = layer.num_held
    flat = x.reshape(t, -1)
    if layer.router == "mlp":
        with layer._bind(params):
            scores, gates, ids = layer._route(flat, layer.router_state(x))
    else:
        logits = jnp.matmul(flat.astype(jnp.float32), params["gate_weight"])
        scores = (jax.nn.softmax(logits, -1) if layer.scoring == "softmax"
                  else jax.nn.sigmoid(logits))
        if "gate_bias" in params:
            _, ids = jax.lax.top_k(scores + params["gate_bias"], k)
            gates = jnp.take_along_axis(scores, ids, axis=-1)
        else:
            gates, ids = jax.lax.top_k(scores, k)
    # an expert held elsewhere, or the skip choice: no group
    local = ids - layer.first_held
    ids = jnp.where((local >= 0) & (local < held), local, held)
    flat_e = ids.T.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sizes = jnp.bincount(flat_e, length=held).astype(jnp.int32)

    def product(a, w):
        return jax.lax.ragged_dot(
            a, w.astype(a.dtype), sizes,
            preferred_element_type=jnp.float32).astype(a.dtype)
    if layer.experts.act == "swiglu":
        g, u = jnp.split(product(flat[order % t],
                                 params["experts.w_gate_up"]), 2, axis=-1)
        hidden = F.silu(g) * u
    else:
        hidden = jnp.square(jax.nn.relu(
            product(flat[order % t], params["experts.w_up"])))
    ys = product(hidden, params["experts.w_down"])
    y_cm = jnp.zeros_like(ys).at[order].set(ys).reshape(k, t, -1)
    y_cm = jnp.where((ids.T < held)[..., None], y_cm, 0)
    g_km = gates.T
    if layer.renormalize:
        g_km = g_km / jnp.maximum(jnp.sum(g_km, 0, keepdims=True), 1e-9)
    g_km = g_km * layer.routed_scaling_factor
    out = jnp.sum(g_km[..., None].astype(ys.dtype) * y_cm, axis=0)
    aux = (jnp.zeros((), jnp.float32) if layer.router == "mlp"
           else _aux_loss(scores, e))
    return out.reshape(x.shape), aux, sizes


SIGMOID = dict(scoring="sigmoid", select_bias=True, norm_topk_prob=True,
               routed_scaling_factor=1.8)

SKIP = dict(router="mlp", router_hidden_size=8, skip_choice=True)

CASES = [
    # id, dtype, top_k, layer arguments, an expert no row may choose
    ("float32-top1", "float32", 1, {}, None),
    ("float32-top2", "float32", 2, {}, None),
    ("float32-top8", "float32", 8, {}, None),
    ("bfloat16-top2", "bfloat16", 2, {}, None),
    ("bfloat16-top8", "bfloat16", 8, {}, None),
    ("float32-top2-an_expert_without_rows", "float32", 2, {}, 3),
    ("bfloat16-top2-an_expert_without_rows", "bfloat16", 2, {}, 3),
    ("float32-top2-sigmoid_bias_scale", "float32", 2, SIGMOID, None),
    ("bfloat16-top4-sigmoid_bias_scale", "bfloat16", 4, SIGMOID, None),
    # PR 43: the other activation, and rows that belong to no group
    ("float32-top2-relu2", "float32", 2, dict(expert_act="relu2"), None),
    ("bfloat16-top2-relu2", "bfloat16", 2, dict(expert_act="relu2"), None),
    ("float32-top3-experts_held", "float32", 3,
     dict(experts_held=(2, 3)), None),
    ("bfloat16-top3-experts_held_relu2", "bfloat16", 3,
     dict(experts_held=(1, 4), expert_act="relu2"), None),
    ("float32-top1-skip_choice", "float32", 1, SKIP, None),
    ("bfloat16-top1-skip_choice", "bfloat16", 1, SKIP, None),
]


@pytest.mark.parametrize("dtype,top_k,kwargs,starved",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_outputs_and_gradients_equal_the_scatter_formulation(
        dtype, top_k, kwargs, starved):
    e = max(E, top_k + 2)
    layer = MoELayer(D, FFN, e, top_k=top_k, capacity_factor=None,
                     dtype=dtype, **kwargs)
    params = dict(layer.raw_parameters())
    # weights large enough that every gradient stands clear of rounding
    # (the router's 0.5, the experts' 0.3; biases and gains as they are)
    for i, (name, leaf) in enumerate(sorted(params.items())):
        if leaf.ndim >= 2:
            scale = 0.3 if name.startswith("experts.") else 0.5
            params[name] = (jax.random.normal(
                jax.random.fold_in(jax.random.key(7), i), leaf.shape)
                * scale).astype(leaf.dtype)
    if "gate_bias" in params:
        params["gate_bias"] = jnp.linspace(-0.3, 0.3, e)
    x = jax.random.normal(jax.random.key(9), (2, 24, D)).astype(dtype)
    if starved is not None:
        # positive activations against a large negative column: that
        # expert's score is the lowest of every row
        x = jnp.abs(x)
        params["gate_weight"] = params["gate_weight"].at[:, starved].set(-8.0)
    mix = jax.random.normal(jax.random.key(8), x.shape)

    def ours(p, x_):
        with layer._bind(p):
            state = layer.router_state(x_) if layer.router == "mlp" else None
            return layer(x_, state)

    def loss_of(forward):
        def loss(p, x_):
            out, aux = forward(p, x_)[:2]
            return jnp.sum(out.astype(jnp.float32) * mix) + 0.5 * aux
        return loss
    got_out, got_aux = ours(params, x)
    want_out, want_aux, sizes = _scatter_reference(layer, params, x)
    if starved is not None:
        assert int(sizes[starved]) == 0
    routed = int(sizes.sum())
    if layer.skip_choice or layer.num_held < e:
        assert 0 < routed < top_k * 48      # some rows have no group
    else:
        assert routed == top_k * 48
    got = jax.grad(loss_of(ours), (0, 1))(params, x)
    want = jax.grad(loss_of(lambda p, x_: _scatter_reference(layer, p, x_)),
                    (0, 1))(params, x)

    # float32: the weight multiplies before the down product instead of
    # after it, so sums round in another order; bfloat16: test_moe_ep.py's
    tol = 1e-5 if dtype == "float32" else 2e-2
    assert got_out.dtype == x.dtype and float(got_aux) == float(want_aux)

    def close(a, b, what):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape, what
        scale = max(1.0, float(np.abs(b).max()))
        assert np.abs(a - b).max() <= tol * scale, (
            what, float(np.abs(a - b).max()), scale)
    close(got_out, want_out, "out")
    close(got[1], want[1], "dx")
    assert got[1].dtype == x.dtype and np.abs(np.asarray(
        want[1], np.float32)).max() > 0
    moved = set()
    for name in params:
        close(got[0][name], want[0][name], name)
        assert got[0][name].dtype == params[name].dtype
        if np.abs(np.asarray(want[0][name], np.float32)).max() > 0:
            moved.add(name)
    # d gates reaches the router (the path d gs takes) and both products
    router = ({"router_down", "router_w1", "router_w2", "router_w3"}
              if layer.router == "mlp" else {"gate_weight"})
    assert moved >= router | {n for n in params if n.startswith("experts.")}


@pytest.mark.parametrize("flat_e,groups", [
    ([3, 0, 3, 3, 1, 0, 5, 3, 0, 1, 1, 3], 6),     # experts 2 and 4: no row
    ([2, 4, 0, 4, 4, 1, 3, 4, 0, 2], 4),           # 4: rows of no group
    ([1, 1, 1, 1, 1, 1, 1], 3),                    # all rows on one expert
    ([5, 5, 5], 5),                                # no row has a group
], ids=["an_expert_without_rows", "rows_of_no_group", "one_expert",
        "no_group_at_all"])
def test_the_index_work_by_sorts_is_the_scatters(flat_e, groups):
    """``sorted_assignments``: the sizes read off the sorted keys are
    ``bincount``'s, the argsort of the permutation is
    ``inverse_permutation``'s scatter, ``live`` the rows that have a group."""
    flat_e = jnp.asarray(flat_e, jnp.int32)
    order, inv, sizes, live = jax.jit(
        sorted_assignments, static_argnums=1)(flat_e, groups)
    want_order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    assert order.dtype == inv.dtype == sizes.dtype == jnp.int32
    assert np.array_equal(np.asarray(order), np.asarray(want_order))
    assert np.array_equal(np.asarray(inv),
                          np.asarray(inverse_permutation(want_order)))
    assert np.array_equal(
        np.asarray(sizes), np.asarray(jnp.bincount(flat_e, length=groups)))
    assert sizes.shape == (groups,)
    assert np.array_equal(np.asarray(live),
                          np.asarray(flat_e[want_order] < groups))


def _plain_weighted_hidden(pre, gs, act):
    """``weighted_hidden``'s plain twin: float32, rounded once."""
    pre32 = pre.astype(jnp.float32)
    if act == "swiglu":
        g, u = jnp.split(pre32, 2, axis=-1)
        hidden = jax.nn.silu(g) * u
    else:
        hidden = jnp.square(jax.nn.relu(pre32))
    return (hidden * gs[:, None]).astype(pre.dtype)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-6), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("act", ["swiglu", "relu2"])
def test_the_weighted_activations_vjp_is_jaxs_of_its_plain_twin(
        act, dtype, tol):
    """d pre and d gs (= rowsum(d h x activation), float32) as the
    hand-written backward forms them from the saved pre-activation, against
    ``jax.vjp`` of the same mathematics in plain ``jnp``; a weight of 0 (a
    row of no group) among them."""
    rows, f = 24, 16
    keys = jax.random.split(jax.random.key(3), 3)
    pre = (jax.random.normal(keys[0], (rows, 2 * f if act == "swiglu" else f))
           * 2).astype(dtype)
    gs = jax.random.uniform(keys[1], (rows,), jnp.float32).at[5].set(0.0)
    dh = jax.random.normal(keys[2], (rows, f)).astype(dtype)
    out, vjp = jax.vjp(lambda p, g: weighted_hidden(p, g, act), pre, gs)
    want, ref = jax.vjp(lambda p, g: _plain_weighted_hidden(p, g, act),
                        pre, gs)
    assert out.dtype == pre.dtype
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(want, np.float32))
    (dpre, dgs), (want_dpre, want_dgs) = vjp(dh), ref(dh)
    assert dpre.dtype == pre.dtype and dgs.dtype == jnp.float32
    assert dpre.shape == pre.shape and dgs.shape == gs.shape
    for a, b in ((dpre, want_dpre), (dgs, want_dgs)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(b).max() > 0
        assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max())


def test_the_inverse_of_a_sort_with_ties_is_its_inverse():
    """Every routing has ties (many assignments an expert): the stable
    argsort is still a permutation and ``inverse_permutation`` its
    inverse, either way round."""
    flat_e = jnp.asarray([3, 0, 3, 3, 1, 0, 5, 3, 0, 1, 1, 3], jnp.int32)
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    inv = inverse_permutation(order)
    n = np.arange(flat_e.shape[0])
    assert inv.dtype == jnp.int32
    assert np.array_equal(np.asarray(inv[order]), n)
    assert np.array_equal(np.asarray(order[inv]), n)


def test_the_two_helpers_transpose_to_gathers():
    """``permute_scalars`` (a sort) is the gather ``x[idx]`` and its
    cotangent the gather by the inverse; ``dispatch_rows``' cotangent is a
    gather and a sum over k, ``combine_rows``' the dispatch's gather:
    against jax's own transposes of the same gathers (a scatter and a
    scatter-add)."""
    k, t, d = 3, 5, 4
    rs = np.random.RandomState(1)
    order = jnp.asarray(rs.permutation(k * t), jnp.int32)
    inv = inverse_permutation(order)
    flat = jnp.asarray(rs.randn(t, d), jnp.float32)
    rows = jnp.asarray(rs.randn(k * t, d), jnp.float32)
    g = jnp.asarray(rs.randn(k * t, d), jnp.float32)
    out, vjp = jax.vjp(lambda a: permute_scalars(a, inv, order), rows[:, 0])
    want, ref = jax.vjp(lambda a: a[inv], rows[:, 0])
    assert np.array_equal(np.asarray(out), np.asarray(want))
    assert np.array_equal(np.asarray(vjp(g[:, 0])[0]),
                          np.asarray(ref(g[:, 0])[0]))
    out, vjp = jax.vjp(lambda a: dispatch_rows(a, order, inv), flat)
    want, ref = jax.vjp(lambda a: a[order % t], flat)
    assert np.array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_allclose(np.asarray(vjp(g)[0]), np.asarray(ref(g)[0]),
                               rtol=1e-6, atol=1e-6)
    # coming back is the dispatch's transpose, and the other way round; rows
    # of no group (the sorted tail) add nothing and get no cotangent
    gt = jnp.asarray(rs.randn(t, d), jnp.float32)
    for live in (None, jnp.arange(k * t) < k * t - 4):
        keep = 1.0 if live is None else live[:, None]
        out, vjp = jax.vjp(lambda a: combine_rows(a, order, inv, live, t),
                           rows)
        want, ref = jax.vjp(
            lambda a: jnp.sum((a * keep)[inv].reshape(k, t, d), 0), rows)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vjp(gt)[0]),
                                   np.asarray(ref(gt)[0]),
                                   rtol=1e-6, atol=1e-6)
        _, vjp = jax.vjp(lambda a: dispatch_rows(a, order, inv, live), flat)
        _, ref = jax.vjp(lambda a: a[order % t], flat)
        np.testing.assert_allclose(np.asarray(vjp(g)[0]),
                                   np.asarray(ref(g * keep)[0]),
                                   rtol=1e-6, atol=1e-6)
