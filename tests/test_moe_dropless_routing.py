"""The dropless path moves its routed rows by gathers alone (PR 30).

``MoELayer._forward_dropless`` sends rows to their experts through
``dispatch_rows`` and brings them back through ``permute_rows``: gathers
with hand-written transposes (the gather by the inverse permutation; a
gather and a sum over k), and ``grouped_matmul``'s backward takes its
products in the activation dtype. The reference kept here is the scatter
formulation the layer had before, under jax's own differentiation, with
float32 backward products: ``zeros.at[order].set``, ``flat[order % t]``,
``ragged_dot(..., float32)`` and a cast. Same outputs, same gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn import functional as F
from paddle_tpu.parallel.moe import (MoELayer, _aux_loss, dispatch_rows,
                                     inverse_permutation, permute_rows)

D, FFN, E = 16, 8, 6


def _scatter_reference(layer, params, x):
    """``forward`` as it stood at the parent commit, on ``params``."""
    t, k, e = x.shape[0] * x.shape[1], layer.top_k, layer.num_experts
    flat = x.reshape(t, -1)
    logits = jnp.matmul(flat.astype(jnp.float32), params["gate_weight"])
    scores = (jax.nn.softmax(logits, -1) if layer.scoring == "softmax"
              else jax.nn.sigmoid(logits))
    if "gate_bias" in params:
        _, ids = jax.lax.top_k(scores + params["gate_bias"], k)
        gates = jnp.take_along_axis(scores, ids, axis=-1)
    else:
        gates, ids = jax.lax.top_k(scores, k)
    flat_e = ids.T.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)

    def product(a, w):
        return jax.lax.ragged_dot(
            a, w.astype(a.dtype), sizes,
            preferred_element_type=jnp.float32).astype(a.dtype)
    gu = product(flat[order % t], params["experts.w_gate_up"])
    g, u = jnp.split(gu, 2, axis=-1)
    ys = product(F.silu(g) * u, params["experts.w_down"])
    y_cm = jnp.zeros_like(ys).at[order].set(ys).reshape(k, t, -1)
    g_km = gates.T
    if layer.renormalize:
        g_km = g_km / jnp.maximum(jnp.sum(g_km, 0, keepdims=True), 1e-9)
    g_km = g_km * layer.routed_scaling_factor
    out = jnp.sum(g_km[..., None].astype(ys.dtype) * y_cm, axis=0)
    return out.reshape(x.shape), _aux_loss(scores, e), sizes


SIGMOID = dict(scoring="sigmoid", select_bias=True, norm_topk_prob=True,
               routed_scaling_factor=1.8)

CASES = [
    # id, dtype, top_k, router arguments, an expert no row may choose
    ("float32-top1", "float32", 1, {}, None),
    ("float32-top2", "float32", 2, {}, None),
    ("float32-top8", "float32", 8, {}, None),
    ("bfloat16-top2", "bfloat16", 2, {}, None),
    ("bfloat16-top8", "bfloat16", 8, {}, None),
    ("float32-top2-an_expert_without_rows", "float32", 2, {}, 3),
    ("bfloat16-top2-an_expert_without_rows", "bfloat16", 2, {}, 3),
    ("float32-top2-sigmoid_bias_scale", "float32", 2, SIGMOID, None),
    ("bfloat16-top4-sigmoid_bias_scale", "bfloat16", 4, SIGMOID, None),
]


@pytest.mark.parametrize("dtype,top_k,router,starved",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_outputs_and_gradients_equal_the_scatter_formulation(
        dtype, top_k, router, starved):
    e = max(E, top_k + 2)
    layer = MoELayer(D, FFN, e, top_k=top_k, capacity_factor=None,
                     dtype=dtype, **router)
    params = dict(layer.raw_parameters())
    keys = jax.random.split(jax.random.key(7), 4)
    # weights large enough that every gradient stands clear of rounding
    params["gate_weight"] = jax.random.normal(keys[0], (D, e)) * 0.5
    params["experts.w_gate_up"] = (jax.random.normal(
        keys[1], (e, D, 2 * FFN)) * 0.3).astype(dtype)
    params["experts.w_down"] = (jax.random.normal(
        keys[2], (e, FFN, D)) * 0.3).astype(dtype)
    if "gate_bias" in params:
        params["gate_bias"] = jnp.linspace(-0.3, 0.3, e)
    x = jax.random.normal(keys[3], (2, 24, D)).astype(dtype)
    if starved is not None:
        # positive activations against a large negative column: that
        # expert's score is the lowest of every row
        x = jnp.abs(x)
        params["gate_weight"] = params["gate_weight"].at[:, starved].set(-8.0)
    mix = jax.random.normal(jax.random.key(8), x.shape)

    def loss_of(forward):
        def loss(p, x_):
            out, aux = forward(p, x_)[:2]
            return jnp.sum(out.astype(jnp.float32) * mix) + 0.5 * aux
        return loss
    got_out, got_aux = layer.functional_call(params, x)
    want_out, want_aux, sizes = _scatter_reference(layer, params, x)
    if starved is not None:
        assert int(sizes[starved]) == 0
    assert int(sizes.sum()) == top_k * 48
    got = jax.grad(loss_of(layer.functional_call), (0, 1))(params, x)
    want = jax.grad(loss_of(lambda p, x_: _scatter_reference(layer, p, x_)),
                    (0, 1))(params, x)

    tol = 1e-6 if dtype == "float32" else 2e-2      # test_moe_ep.py's
    assert got_out.dtype == x.dtype and float(got_aux) == float(want_aux)

    def close(a, b, what):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape, what
        scale = max(1.0, float(np.abs(b).max()))
        assert np.abs(a - b).max() <= tol * scale, (
            what, float(np.abs(a - b).max()), scale)
        assert np.abs(b).max() > 0, what
    close(got_out, want_out, "out")
    close(got[1], want[1], "dx")
    assert got[1].dtype == x.dtype
    for name in ("gate_weight", "experts.w_gate_up", "experts.w_down"):
        close(got[0][name], want[0][name], name)
        assert got[0][name].dtype == params[name].dtype


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
    """``permute_rows``' cotangent is the gather by the inverse, and
    ``dispatch_rows``' a gather and a sum over k: against jax's own
    transposes of the same two gathers (a scatter and a scatter-add)."""
    k, t, d = 3, 5, 4
    rs = np.random.RandomState(1)
    order = jnp.asarray(rs.permutation(k * t), jnp.int32)
    inv = inverse_permutation(order)
    flat = jnp.asarray(rs.randn(t, d), jnp.float32)
    rows = jnp.asarray(rs.randn(k * t, d), jnp.float32)
    g = jnp.asarray(rs.randn(k * t, d), jnp.float32)
    out, vjp = jax.vjp(lambda a: permute_rows(a, inv, order), rows)
    want, ref = jax.vjp(lambda a: a[inv], rows)
    assert np.array_equal(np.asarray(out), np.asarray(want))
    assert np.array_equal(np.asarray(vjp(g)[0]), np.asarray(ref(g)[0]))
    out, vjp = jax.vjp(lambda a: dispatch_rows(a, order, inv), flat)
    want, ref = jax.vjp(lambda a: a[order % t], flat)
    assert np.array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_allclose(np.asarray(vjp(g)[0]), np.asarray(ref(g)[0]),
                               rtol=1e-6, atol=1e-6)
